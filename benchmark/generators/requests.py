"""The one general generator of request traffic: arrivals (an open
loop on a schedule, or a closed loop of clients), prompt and output
lengths, and how much of a prompt is shared, all from the parameters
of a traffic file.

**Every seed gets the same work, in another order.**  One *round* of
traffic is ``horizon_seconds`` of arrivals (open loop) or ``requests``
requests (closed loop), sized to what one window takes.  Its lengths
and the gaps between its arrivals are the values of the stated
distribution at evenly spaced quantiles, so the set is the same for
every seed; the seed puts each set into an order of its own, a uniform
random permutation with nothing stratified.  So a window holds bursts,
lulls, and long prompts in a row, as independent users send them, and
two seeds differ in order and content but not in the amount of work.
``rounds`` such rounds follow each other, each in a new order, so that
no window runs out of requests.  Token ids come from the seed.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def quantile_values(spec: dict, n: int) -> np.ndarray:
    """``n`` values of the distribution ``spec`` at the quantiles
    ``(i + 0.5) / n``, ascending.  Kinds: ``uniform`` (min, max),
    ``lognormal`` (median, sigma) and ``exponential`` (mean)."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["kind"]
    if kind == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        return spec["median"] * np.exp(spec["sigma"] * z)
    if kind == "exponential":
        return -np.log1p(-u) * spec["mean"]
    raise ValueError(f"unknown distribution kind {kind!r}")


def token_counts(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths of ``spec``, clipped to its ``min``/``max``."""
    v = np.clip(quantile_values(spec, n), spec["min"], spec["max"])
    return np.rint(v).astype(np.int64)


def generate(traffic: dict, *, seed: int, vocab: int) -> dict:
    """The requests of one run: ``{"requests": [...], "contexts":
    [...], "closed_clients": n or 0}``.  A request is ``{"id", "due"
    (seconds into the window; None in a closed loop), "prompt" (list of
    token ids), "max_tokens", "context" (index or None)}``; requests
    are in the order they are due (open loop) or taken (closed loop)."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    arrivals = traffic["arrivals"]
    closed = arrivals["kind"] == "closed"
    rounds = int(traffic.get("rounds", 1))
    if closed:
        per_round = int(traffic["requests"])
    else:
        per_round = math.ceil(
            arrivals["rate_per_s"] * float(traffic["horizon_seconds"]))

    def in_new_order(values):
        return np.concatenate([rng.permutation(values) for _ in range(rounds)])

    prompt_len = in_new_order(token_counts(traffic["prompt_tokens"], per_round))
    output_len = in_new_order(token_counts(traffic["output_tokens"], per_round))
    n = per_round * rounds

    shared = traffic.get("shared_prefix")
    contexts, context_of = [], [None] * n
    if shared:
        contexts = [rng.integers(0, vocab, size=int(shared["tokens"])).tolist()
                    for _ in range(int(shared["contexts"]))]
        context_of = in_new_order(
            np.arange(per_round) % len(contexts)).tolist()

    dues = [None] * n
    if not closed:
        gaps = quantile_values(
            {"kind": "exponential", "mean": 1.0 / arrivals["rate_per_s"]},
            per_round)
        dues = np.cumsum(in_new_order(gaps)).tolist()

    requests = []
    for i in range(n):
        own = rng.integers(0, vocab, size=int(prompt_len[i])).tolist()
        ctx = context_of[i]
        prompt = (contexts[ctx] + own) if ctx is not None else own
        requests.append({"id": f"r{i}", "due": dues[i], "prompt": prompt,
                         "max_tokens": int(output_len[i]), "context": ctx})
    return {"requests": requests, "contexts": contexts,
            "closed_clients": int(arrivals["clients"]) if closed else 0}
