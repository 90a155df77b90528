"""Request traffic whose lengths take one of TWO values: `requests.py`
as it is (arrivals, rounds, orders, token ids), with one more kind of
distribution, ``{"kind": "two_point", "min": a, "max": b,
"share_at_max": p}``: the share ``p`` of a round's values is ``b``,
the rest ``a``, at the same evenly spaced quantiles.  The patch is
made on this file's own copy of that module."""

from __future__ import annotations

import numpy as np

from benchmark import harness

requests = harness.load_module("generators", "requests")
_plain = requests.quantile_values


def quantile_values(spec: dict, n: int) -> np.ndarray:
    if spec["kind"] != "two_point":
        return _plain(spec, n)
    u = (np.arange(n) + 0.5) / n
    return np.where(u < 1.0 - spec["share_at_max"], float(spec["min"]),
                    float(spec["max"]))


requests.quantile_values = quantile_values
generate = requests.generate
