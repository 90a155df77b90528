"""Profiling and structured metrics (SURVEY §5 observability plan).

The reference's observability is a printf of wall time and correctness
(`attention.c:186-188`), and its per-phase analysis was done by ablation
builds rather than instrumentation (report Q2).  Here:

  * :func:`trace` wraps ``jax.profiler.trace`` so any benchmark or test
    can capture an XLA/TPU trace (xplane) for the profiler UI;
  * phases are named by ``attention_tpu.obs.span``, which enters a
    ``jax.profiler.TraceAnnotation``: inside a :func:`trace` block the
    program's spans are events of the capture itself (the
    instrumentation the reference lacked);
  * :class:`RunRecord` is the structured per-run JSON record
    (config, timing, GFLOPs, utilization, device) that replaces printf.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Any, Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace of the enclosed block."""
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield


@dataclasses.dataclass
class RunRecord:
    """One benchmark run, JSON-serializable."""

    config: str
    backend: str
    m: int
    n: int
    dk: int
    dv: int
    dtype: str
    best_us: float
    median_us: float
    gflops_per_chip: float
    utilization: float | None  # None where the device has no published peak
    device_kind: str
    n_devices: int
    mesh_axes: dict[str, int] | None = None
    extra: dict[str, Any] | None = None
    timestamp: float = dataclasses.field(default_factory=time.time)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def append_jsonl(path: str, record: RunRecord) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
        f.write(record.to_json() + "\n")


#: the profiler's per-device lane that carries one event per executed
#: XLA module (the lane every device-time clock reads)
DEVICE_LANE = "XLA Modules"


def _latest_capture(log_dir: str) -> str | None:
    """Newest ``.trace.json.gz`` under a ``trace(log_dir)`` capture,
    by mtime.  Capture directories are timestamp-named, but path sort
    order is NOT capture order across a rollover boundary (e.g.
    ``..._09_59`` sorts after ``..._10_01`` under some stamp formats),
    so recency must come from the filesystem, not the name."""
    import glob

    paths = glob.glob(f"{log_dir}/plugins/profile/*/*.trace.json.gz")
    if not paths:
        return None
    return max(paths, key=os.path.getmtime)


def device_module_slices(
    log_dir: str,
) -> list[tuple[str, float, float]] | None:
    """Per-slice device events from a ``trace(log_dir)`` capture.

    Parses the newest Chrome-trace export under ``log_dir`` and returns
    every complete event on the device "XLA Modules" lane as
    ``(module_name, ts_us, dur_us)`` tuples (trace-local clock), or
    None when no trace/device lane exists (e.g. CPU platforms).
    :func:`device_module_seconds` aggregates it.
    """
    import gzip
    import json as _json

    path = _latest_capture(log_dir)
    if path is None:
        return None
    try:
        data = _json.load(gzip.open(path))
        lanes = {}
        for e in data["traceEvents"]:
            if e.get("ph") == "M" and e.get("name") == "thread_name":
                lanes[(e["pid"], e["tid"])] = e["args"]["name"]
        slices = [
            (e["name"].split("(")[0], float(e["ts"]), float(e["dur"]))
            for e in data["traceEvents"]
            if (e.get("ph") == "X"
                and lanes.get((e.get("pid"), e.get("tid")))
                == DEVICE_LANE)
        ]
    except (ValueError, KeyError, EOFError, OSError):
        # a truncated/partial capture (interrupted profiler) reads as
        # "no device lane": `cli obs export` stays usable on a damaged
        # dump, and `benchmark_auto` decides what a missing lane means
        return None
    return slices or None


def device_module_seconds(log_dir: str) -> dict[str, float] | None:
    """Per-module device seconds from a ``trace(log_dir)`` capture.

    Sums the duration of each module on the device "XLA Modules" lane
    of the newest capture.  Returns ``{module_name: seconds}``, or None
    when no trace/device lane exists — the shared parser for every
    device-time clock (`utils.timing.benchmark_traced`,
    `scripts/speculative_bench.py`).
    """
    slices = device_module_slices(log_dir)
    if slices is None:
        return None
    per_module: dict[str, float] = {}
    for key, _, dur_us in slices:
        per_module[key] = per_module.get(key, 0.0) + dur_us / 1e6
    return per_module or None
