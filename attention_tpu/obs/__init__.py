"""Unified telemetry: typed instruments, host spans, merged timelines.

The observability layer SURVEY §5 planned and the serving engine needs:
the reference's entire story was a printf of wall time
(`attention.c:186-188`); ours is four composable pieces sharing one
process-wide state:

* **Registry** (`obs.registry`) — counters / gauges / fixed-bucket
  histograms with labeled series, ``snapshot()``/``reset()``;
* **Spans** (`obs.spans`) — ``with obs.span("engine.step", step=n):``
  is ALWAYS a ``jax.profiler.TraceAnnotation``: under any profiler
  capture the span is an event on the capture's host plane, on the
  device trace's clock, with its fields as stats; enabled, it also
  records a row into a bounded ring;
* **Exporters** (`obs.export`) — Prometheus text (:func:`prom_text`),
  JSONL, and a Chrome-trace timeline of the ring's host spans
  (``cli obs export --format chrome|prom|jsonl``);
* **Compile log** (`obs.compiles`) — ALWAYS on, whatever the flag
  below: ``summary()`` is JAX's trace, lower and compile seconds (unions
  by kind), programs, persistent-cache hits and misses and the ten
  costliest functions; seen in ``cli obs report``, ``serve-sim``'s
  ``compiled_steps`` and a step's ``StepMetrics.compile_s``.

The registry and the ring are **disabled by default**: a disabled
instrument is a single flag check and a disabled span only the inert
annotation (no clock read, no ring row — asserted by test).  Enable
with :func:`enable` or ``ATTN_TPU_OBS=1``.  Instrument handles may be
created at import time regardless of the flag::

    from attention_tpu import obs

    _CALLS = obs.counter("ops.flash.calls")

    def f(q, ...):
        _CALLS.inc(bucket=obs.shape_bucket(q.shape))
        with obs.span("engine.step"):
            ...

Names follow ``layer.component.verb`` (`obs.naming`, linted tree-wide
by ``scripts/check_obs_names.py``).
"""

from __future__ import annotations

from typing import Any

from attention_tpu.obs.export import (  # noqa: F401
    chrome_trace,
    device_dir_of,
    dump,
    jsonl_lines,
    live_snapshot,
    load_anomaly,
    load_blackbox,
    load_dump,
    load_forecast,
    load_slo,
    load_traces,
    prom_text,
    write_anomaly,
    write_forecast,
    write_jsonl,
    write_slo,
)
from attention_tpu.obs.naming import (  # noqa: F401
    ANOMALY_DETECTORS,
    BLACKBOX_EVENTS,
    FROZEN_SERIES,
    TRACE_EVENTS,
    TRACE_TERMINAL_EVENTS,
    check_blackbox_event,
    check_event,
    check_name,
    require_blackbox_event,
    require_event,
    require_name,
)
from attention_tpu.obs.quantile import (  # noqa: F401
    QuantileDigest,
    merge_digests,
)
from attention_tpu.obs.registry import (  # noqa: F401
    DEFAULT_BUCKETS,
    REGISTRY,
    Counter,
    Digest,
    Gauge,
    Histogram,
    Registry,
    counter,
    digest,
    disable,
    enable,
    gauge,
    histogram,
    is_enabled,
)
from attention_tpu.obs.spans import (  # noqa: F401
    SPAN_RING_CAPACITY,
    events,
    record_event,
    span,
)
from attention_tpu.obs import anomaly  # noqa: F401
from attention_tpu.obs import blackbox  # noqa: F401
from attention_tpu.obs import capacity  # noqa: F401
from attention_tpu.obs import compiles  # noqa: F401
from attention_tpu.obs import forecast  # noqa: F401
from attention_tpu.obs import postmortem  # noqa: F401
from attention_tpu.obs import slo  # noqa: F401
from attention_tpu.obs import spans as _spans
from attention_tpu.obs import trace  # noqa: F401


def enabled() -> bool:
    """Alias of :func:`is_enabled` (reads better at call sites)."""
    return is_enabled()


def reset() -> None:
    """Zero every metric series and drop every span event, request
    trace, and flight-recorder record (instrument registrations
    survive, and so does the compile log: `obs.compiles`)."""
    REGISTRY.reset()
    _spans.clear()
    trace.clear()
    blackbox.clear()


def shape_bucket(*dims: int) -> str:
    """Power-of-two shape-bucket label, e.g. ``shape_bucket(3000, 128)
    -> "4096x128"`` — the tuning cache's bucketing discipline reused as
    a low-cardinality metric label."""
    out = []
    for d in dims:
        d = int(d)
        b = 1
        while b < d:
            b <<= 1
        out.append(str(b))
    return "x".join(out)


_RUNS = counter("bench.runs.recorded",
                "RunRecords re-emitted through the registry")
_RUN_US = gauge("bench.run.best_us", "best-run µs by config/backend")
_RUN_UTIL = gauge("bench.run.utilization",
                  "fraction-of-peak by config/backend")


def record_run(record: Any) -> None:
    """Re-emit a `utils.profiling.RunRecord` (or its dict) through the
    registry, so benchmark rows and engine summaries land in the same
    scrape as live counters."""
    if not is_enabled():
        return
    import dataclasses

    d = (dataclasses.asdict(record)
         if dataclasses.is_dataclass(record) else dict(record))
    labels = {"config": str(d.get("config", "")),
              "backend": str(d.get("backend", ""))}
    _RUNS.inc(**labels)
    _RUN_US.set(float(d.get("best_us", 0.0)), **labels)
    if d.get("utilization") is not None:
        _RUN_UTIL.set(float(d["utilization"]), **labels)
