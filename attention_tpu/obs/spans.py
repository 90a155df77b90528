"""Host-side spans: profiler annotations, plus a bounded ring when enabled.

``span(name, **fields)`` ALWAYS enters a
``jax.profiler.TraceAnnotation(name, **fields)``: while any profiler
capture is running (``jax.profiler.start_trace``, ``serve-sim
--obs-profile``, the benchmark's ``--trace 1``) the span is an event on
the ``/host:CPU`` plane of the capture's ``.xplane.pb``, on the thread
that entered it and on the SAME clock as the device's ``XLA Ops`` lane,
with ``fields`` as the event's stats — so a gap on the device can be put
down to the program's phase the host was in.  With no capture running
the annotation is inert (about half a microsecond).

While telemetry is enabled (``obs.enable()``) the span also records a
(name, ts, dur, thread, fields) row into an in-memory ring buffer
(bounded — a long serve run cannot grow without bound) for the JSONL
and Chrome exporters.  Disabled: annotation only; no clock read, no
ring row, the name is not validated
(``tests/test_obs.py::test_disabled_overhead_under_5_percent``).

Names must not end in a digit or a dot: readers of a capture strip
such suffixes (they number the runtime's own events).
"""

from __future__ import annotations

import threading
import time
from typing import Any

from jax.profiler import TraceAnnotation

from attention_tpu.obs import registry as _registry
from attention_tpu.obs.naming import require_name

#: ring capacity (events); oldest events drop first
SPAN_RING_CAPACITY = 65536

_lock = threading.Lock()
# (name, ts_us, dur_us, tid, fields)
_ring: list[tuple[str, float, float, int, dict[str, Any] | None]] = []
_ring_start = 0  # index of the logical head when the ring has wrapped
_t0 = time.perf_counter()


class _Span:
    """The enabled path: the annotation plus a ring row."""

    __slots__ = ("name", "fields", "_note", "_t_start")

    def __init__(self, name: str, fields: dict[str, Any]):
        self.name = name
        self.fields = fields
        self._note = TraceAnnotation(name, **fields)

    def __enter__(self):
        self._note.__enter__()
        self._t_start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur_us = (time.perf_counter() - self._t_start) * 1e6
        self._note.__exit__(*exc)
        record_event(self.name, (self._t_start - _t0) * 1e6, dur_us,
                     fields=self.fields)
        return False


def span(name: str, **fields: Any):
    """Context manager marking the enclosed block as ``name`` on the
    profiler's host timeline (always) and in the span ring (while
    telemetry is enabled).  ``fields`` are small scalars or strings:
    the annotation's stats and the ring row's ``fields``.

    The name is validated on the enabled path and by the lint script,
    not on the disabled fast path."""
    if not _registry._enabled:
        return TraceAnnotation(name, **fields)
    require_name(name)
    return _Span(name, fields)


def record_event(name: str, ts_us: float, dur_us: float,
                 tid: int | None = None,
                 fields: dict[str, Any] | None = None) -> None:
    """Append one span event to the ring (used by `_Span` and by code
    that measured a region manually)."""
    if not _registry._enabled:
        return
    if tid is None:
        tid = threading.get_ident()
    with _lock:
        global _ring_start
        if len(_ring) < SPAN_RING_CAPACITY:
            _ring.append((name, ts_us, dur_us, tid, fields or None))
        else:
            _ring[_ring_start] = (name, ts_us, dur_us, tid, fields or None)
            _ring_start = (_ring_start + 1) % SPAN_RING_CAPACITY


def events() -> list[dict[str, Any]]:
    """Recorded span events, oldest first, as plain dicts."""
    with _lock:
        ordered = _ring[_ring_start:] + _ring[:_ring_start]
    out = []
    for n, ts, dur, tid, fields in ordered:
        row = {"name": n, "ts_us": round(ts, 3), "dur_us": round(dur, 3),
               "tid": tid}
        if fields:
            row["fields"] = dict(fields)
        out.append(row)
    return out


def clear() -> None:
    global _ring, _ring_start
    with _lock:
        _ring = []
        _ring_start = 0
