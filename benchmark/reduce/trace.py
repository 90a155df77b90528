"""From a profiler trace to seconds: device busy and idle time, time by
operation, and the host's doing in each idle gap.

Everything works on plain `Event` rows, so the arithmetic is tested on
a small recorded trace (``fixtures/``) with no profiler and no chip;
`load_xplane` makes the rows from the ``.xplane.pb`` the JAX profiler
writes (read with `jax.profiler.ProfileData`, nothing else).

Planes and lines as the profiler of this stack names them (PERF.md,
PR 21): one plane ``/device:TPU:<i>`` per chip whose line ``XLA
Modules`` has one event per executed program and ``XLA Ops`` one per
operation on the core; the plane ``/host:CPU`` has one line per host
thread, with the benchmark's own spans (`TraceAnnotation`) among the
runtime's events.  All planes share one clock.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES = "XLA Modules"
OPS = "XLA Ops"
NO_HOST_EVENT = "_no_host_event_"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float     # seconds on the trace's clock
    dur: float       # seconds


def load_xplane(trace_dir: str) -> list[Event]:
    """Every event of the newest capture under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(
        max(paths, key=os.path.getmtime))
    events = []
    for plane in data.planes:
        keep_all = bool(DEVICE_PLANE.match(plane.name))
        if not keep_all and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if keep_all and line.name not in (MODULES, OPS):
                continue
            for e in line.events:
                events.append(Event(plane.name, line.name, e.name,
                                    e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return events


def save_events(events: list[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def device_planes(events: list[Event]) -> list[str]:
    """Device planes in the order of their chip numbers."""
    found = {e.plane for e in events if DEVICE_PLANE.match(e.plane)}
    return sorted(found, key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def op_name(name: str) -> str:
    """An operation's stable name: ``fusion.123`` and ``fusion`` are one
    kind, ``%copy.4 = ...`` is ``copy``."""
    name = name.split(" = ")[0].lstrip("%").split("(")[0]
    return re.sub(r"[.\d]+$", "", name) or name


def select(events, plane: str, line: str, pattern: str | None = None):
    rx = re.compile(pattern) if pattern else None
    return [e for e in events if e.plane == plane and e.line == line
            and (rx is None or rx.search(e.name))]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of the merged intervals ``a`` not covered by ``b``."""
    out = []
    b = list(b)
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def intervals(events) -> list[tuple[float, float]]:
    return [(e.start, e.start + e.dur) for e in events]


def busy_seconds(events, plane: str) -> float:
    """Seconds in which an operation ran on the chip's core."""
    return total(union(intervals(select(events, plane, OPS))))


def mean_busy_seconds(events) -> float:
    """Busy seconds averaged over the chips in the trace."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(busy_seconds(events, p) for p in planes) / len(planes)


def seconds_by_op(events, plane: str) -> dict[str, float]:
    out: dict[str, float] = collections.defaultdict(float)
    for e in select(events, plane, OPS):
        out[op_name(e.name)] += e.dur
    return dict(out)


def module_events(events, plane: str, pattern: str):
    return select(events, plane, MODULES, pattern)


def idle_gaps(events, plane: str, window: tuple[float, float]):
    """The gaps between operations on one chip inside ``window``."""
    busy = union(intervals(select(events, plane, OPS)))
    return subtract([window], busy)


def gaps_by_host_event(events, plane: str, window: tuple[float, float],
                       exclude: tuple[str, ...] = ()) -> dict[str, float]:
    """Idle seconds of one chip by what the host was doing: each gap
    goes to the shortest event that covers its middle (the innermost
    of nested spans) on the host thread that runs the benchmark's loop
    (the one whose line holds the ``exclude`` spans; every host thread
    where there is none), or to ``_no_host_event_``.  The runtime's
    worker threads are left out: their allocations and transfers are
    always under way and would take every gap.  Spans named in
    ``exclude`` (the one around the whole slice) explain nothing."""
    import bisect

    own = {e.line for e in events
           if e.plane == HOST_PLANE and e.name in exclude}
    host = sorted((e for e in events if e.plane == HOST_PLANE and e.dur > 0
                   and e.name not in exclude
                   and (not own or e.line in own)), key=lambda e: e.start)
    # short events are found by looking back `reach` seconds from the
    # gap's middle; the few longer ones are tried one by one
    reach = 0.05
    long_events = [e for e in host if e.dur > reach]
    short = [e for e in host if e.dur <= reach]
    starts = [e.start for e in short]
    out: dict[str, float] = collections.defaultdict(float)
    for a, b in idle_gaps(events, plane, window):
        mid = (a + b) / 2
        best = None
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and short[i].start >= mid - reach:
            e = short[i]
            if e.start + e.dur >= mid and (best is None or e.dur < best.dur):
                best = e
            i -= 1
        if best is None:
            for e in long_events:
                if e.start <= mid <= e.start + e.dur and (
                        best is None or e.dur < best.dur):
                    best = e
        out[op_name(best.name) if best else NO_HOST_EVENT] += b - a
    return dict(out)


def top(table: dict[str, float], n: int = 10) -> list[list]:
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def span_window(events, name: str) -> tuple[float, float] | None:
    """From the first start to the last end of the host spans ``name``
    (the traced window on the trace's own clock)."""
    found = [e for e in events if e.plane == HOST_PLANE and e.name == name]
    if not found:
        return None
    return (min(e.start for e in found),
            max(e.start + e.dur for e in found))
