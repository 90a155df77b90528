"""The program's compile log: what JAX traced, lowered and compiled,
when, for which function, and what the persistent cache did about it.

Always on, with no switch: importing `attention_tpu.obs` registers ONE
duration listener and ONE event listener with `jax.monitoring` (they
cannot be taken off again, so once a process).  JAX calls them only
when something is traced, lowered or compiled, on the thread that does
it, so a step that compiles nothing pays nothing.  Every event becomes
a row ``(kind, fun_name, end, seconds)`` of a bounded ring, ``end`` a
`time.perf_counter` stamp taken in the callback (the clock of
`obs.spans`, of `StepMetrics.wall_s` and of the benchmark's window):

* ``trace`` / ``lower`` / ``compile``: JAX's three
  ``/jax/core/compile/*`` durations (Python tracing to a jaxpr, jaxpr
  to MLIR, the backend's compile or the cache's retrieval in its
  place), each with the function's name;
* ``cache_hits`` / ``cache_misses``: the persistent cache's events,
  as counts (``seconds`` 0.0);
* ``cache_retrieval`` / ``time_saved``: its two durations, as sums.

`count` is the number of rows ever recorded, a plain int that only
grows: a caller reads it before and after a region, and where it moved
asks `summary` (``since=``) what happened (`ServingEngine.step`).  The
ring drops its oldest rows; seconds and counts by ``(kind, fun_name)``
are kept apart, so a whole-life `summary` still adds up after a wrap.
`obs.reset()` leaves the log alone: it is the process's record (the
compiled programs outlive a reset too), and `count` never goes back.
`obs.enable()` changes nothing about it.
"""

from __future__ import annotations

import bisect
import collections
import itertools
import threading
import time
from typing import Any

import jax.monitoring

#: ring capacity (rows); oldest rows drop first.  A step program of
#: eight layers is 1,000-1,900 rows (every inner jitted function is a
#: trace row), a warm-up of 20-29 shapes 31-38 thousand: this holds the
#: largest warm-up measured (85 thousand rows) three times over, at 128
#: bytes a row: 34 MB of host memory once a process has traced that much
COMPILE_RING_CAPACITY = 262144

#: the three kinds whose rows are intervals of work on the host
KINDS = ("trace", "lower", "compile")

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
    "/jax/compilation_cache/compile_time_saved_sec": "time_saved",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

#: rows ever recorded
count = 0

_lock = threading.Lock()
# (kind, fun_name, end, seconds), in the order of their stamps
_rows: collections.deque = collections.deque(maxlen=COMPILE_RING_CAPACITY)
# (kind, fun_name) -> [seconds, rows], over the process's life
_totals: dict[tuple[str, str], list] = {}


def _record(kind: str, fun_name: str, seconds: float) -> None:
    global count
    with _lock:
        _rows.append((kind, fun_name, time.perf_counter(), seconds))
        total = _totals.setdefault((kind, fun_name), [0.0, 0])
        total[0] += seconds
        total[1] += 1
        count += 1


def _on_duration(event: str, seconds: float, **kw: Any) -> None:
    kind = _DURATIONS.get(event)
    if kind is not None:
        # another JAX version may name the function otherwise, or not
        # at all: the row still counts
        _record(kind, str(kw.get("fun_name", "")), float(seconds))


def _on_event(event: str, **kw: Any) -> None:
    kind = _EVENTS.get(event)
    if kind is not None:
        _record(kind, "", 0.0)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def _union_s(intervals) -> float:
    """The length of the union of ``(start, end)`` intervals: a
    function traced inside another's trace counts once."""
    total, covered = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > covered:
            total += end - max(start, covered)
            covered = end
    return total


def summary(since: float | None = None,
            until: float | None = None) -> dict[str, Any]:
    """The log between two `time.perf_counter` stamps (a row belongs
    where its ``end`` lies, both ends included; ``None``: no bound).

    ``trace_s`` / ``lower_s`` / ``compile_s`` are the length of the
    UNION of each kind's intervals and ``all_s`` that of the three
    kinds together (a jitted function traced inside another's trace
    nests; summed durations would count it twice).  ``traces`` counts
    trace events (nested ones too), ``programs`` backend-compile
    events (a cache hit is one as well), ``cache_hits`` /
    ``cache_misses`` the persistent cache's events (a miss is counted
    where the compiled program is written to it),
    ``cache_retrieval_s`` / ``time_saved_s`` its two durations summed.
    ``by_function`` holds the ten ``(function, kind)`` with most
    seconds, summed, and their counts.

    Without bounds the counts, sums and ``by_function`` cover the
    process's life, a wrapped ring included; the four union lengths
    come from the rows the ring still holds, and ``dropped`` says how
    many it no longer does."""
    bounded = since is not None or until is not None
    with _lock:
        dropped = count - len(_rows)
        if since is None:
            rows = list(_rows)
        else:
            # a step asks for its own rows, the newest: walk back to
            # the stamp and leave the rest of the ring where it is
            rows = list(itertools.takewhile(
                lambda r: r[2] >= since, reversed(_rows)))
            rows.reverse()
        totals = {} if bounded else {k: tuple(t) for k, t in _totals.items()}
    if bounded:
        if until is not None:
            del rows[bisect.bisect_right(rows, until, key=lambda r: r[2]):]
        for kind, fun_name, _end, s in rows:
            was = totals.get((kind, fun_name), (0.0, 0))
            totals[kind, fun_name] = (was[0] + s, was[1] + 1)
    seconds: dict[str, float] = collections.defaultdict(float)
    events: dict[str, int] = collections.defaultdict(int)
    for (kind, _), (s, n) in totals.items():
        seconds[kind] += s
        events[kind] += n
    spans = {kind: [(end - s, end) for k, _, end, s in rows if k == kind]
             for kind in KINDS}
    top = sorted(((s, n, fun_name, kind)
                  for (kind, fun_name), (s, n) in totals.items()
                  if kind in KINDS), reverse=True)[:10]
    return {
        "trace_s": _union_s(spans["trace"]),
        "lower_s": _union_s(spans["lower"]),
        "compile_s": _union_s(spans["compile"]),
        "all_s": _union_s(i for kind in KINDS for i in spans[kind]),
        "traces": events["trace"],
        "programs": events["compile"],
        "cache_hits": events["cache_hits"],
        "cache_misses": events["cache_misses"],
        "cache_retrieval_s": seconds["cache_retrieval"],
        "time_saved_s": seconds["time_saved"],
        "by_function": [{"function": fun_name, "kind": kind,
                         "seconds": s, "count": n}
                        for s, n, fun_name, kind in top],
        "dropped": dropped,
    }
