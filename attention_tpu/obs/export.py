"""Exporters: Prometheus text, JSONL event log, Chrome trace.

Three views of the same state:

* :func:`prom_text` — Prometheus text exposition of the registry
  snapshot (scrape-able; round-trip pinned by test);
* :func:`jsonl_lines` / :func:`write_jsonl` — one JSON object per span
  event plus one per metric series, the archival format
  (`profiling.append_jsonl`'s discipline applied to telemetry);
* :func:`chrome_trace` — a Chrome-trace/Perfetto JSON timeline of
  the ring's host spans (pid "host"), the request journeys and the
  incident windows.  It has no device lane: the program's spans are
  profiler annotations (`obs.spans`), so a ``jax.profiler`` capture
  (``serve-sim --obs-profile`` writes one under ``<run>/device``)
  already holds them beside the device's lanes on ONE clock — open
  that capture for host-against-device questions.

:func:`dump` / :func:`load_dump` persist a run's telemetry
(``metrics.json`` + ``events.jsonl`` [+ ``device/`` profiler capture])
so ``cli obs report/export`` can work on finished runs.
"""

from __future__ import annotations

import json
import os
from typing import Any, Iterator

from attention_tpu.obs import spans
from attention_tpu.obs import trace as _trace
from attention_tpu.obs.naming import prom_name
from attention_tpu.obs.registry import REGISTRY

#: file names inside a dump directory
DUMP_METRICS = "metrics.json"
DUMP_EVENTS = "events.jsonl"
DUMP_TRACES = "traces.jsonl"
DUMP_SLO = "slo.json"
DUMP_FORECAST = "forecast.json"
DUMP_ANOMALY = "anomaly.json"
DUMP_BLACKBOX = "blackbox.jsonl"
DUMP_DEVICE = "device"

#: percentile-key -> Prometheus quantile-label spelling
_PROM_QUANTILES = {"p50": "0.5", "p90": "0.9", "p99": "0.99",
                   "p999": "0.999"}


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    body = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + body + "}"


def _fmt_value(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


def prom_text(snapshot: dict[str, Any] | None = None) -> str:
    """Prometheus text exposition (format 0.0.4) of ``snapshot``
    (default: the live registry)."""
    snap = REGISTRY.snapshot() if snapshot is None else snapshot
    lines: list[str] = []
    seen_type: set[str] = set()

    def _type_line(flat: str, kind: str) -> None:
        if flat not in seen_type:
            seen_type.add(flat)
            lines.append(f"# TYPE {flat} {kind}")

    for s in snap.get("counters", []):
        flat = prom_name(s["name"], kind="counter")
        _type_line(flat, "counter")
        lines.append(
            f"{flat}{_fmt_labels(s['labels'])} {_fmt_value(s['value'])}")
    for s in snap.get("gauges", []):
        flat = prom_name(s["name"])
        _type_line(flat, "gauge")
        lines.append(
            f"{flat}{_fmt_labels(s['labels'])} {_fmt_value(s['value'])}")
    for s in snap.get("histograms", []):
        flat = prom_name(s["name"])
        _type_line(flat, "histogram")
        cum = 0
        for b, c in zip(s["buckets"], s["counts"]):
            cum += c
            lab = dict(s["labels"], le=_fmt_value(b))
            lines.append(f"{flat}_bucket{_fmt_labels(lab)} {cum}")
        cum += s["counts"][len(s["buckets"])]
        lab = dict(s["labels"], le="+Inf")
        lines.append(f"{flat}_bucket{_fmt_labels(lab)} {cum}")
        lines.append(
            f"{flat}_sum{_fmt_labels(s['labels'])} {_fmt_value(s['sum'])}")
        lines.append(
            f"{flat}_count{_fmt_labels(s['labels'])} {s['count']}")
    for s in snap.get("digests", []):
        # digests export as Prometheus summaries: pre-computed quantile
        # values, not bucket series (Histogram keeps that role)
        flat = prom_name(s["name"])
        _type_line(flat, "summary")
        for pk, q in _PROM_QUANTILES.items():
            lab = dict(s["labels"], quantile=q)
            lines.append(
                f"{flat}{_fmt_labels(lab)} "
                f"{_fmt_value(s['percentiles'][pk])}")
        lines.append(
            f"{flat}_sum{_fmt_labels(s['labels'])} {_fmt_value(s['sum'])}")
        lines.append(
            f"{flat}_count{_fmt_labels(s['labels'])} {s['count']}")
    return "\n".join(lines) + ("\n" if lines else "")


def jsonl_lines(span_events: list[dict] | None = None,
                snapshot: dict[str, Any] | None = None) -> Iterator[str]:
    """One JSON object per line: span events, then metric series."""
    evs = spans.events() if span_events is None else span_events
    snap = REGISTRY.snapshot() if snapshot is None else snapshot
    for e in evs:
        yield json.dumps({"type": "span", **e})
    for kind in ("counters", "gauges", "histograms", "digests"):
        for s in snap.get(kind, []):
            yield json.dumps({"type": kind[:-1], **s})


def write_jsonl(path: str, span_events: list[dict] | None = None,
                snapshot: dict[str, Any] | None = None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        for line in jsonl_lines(span_events, snapshot):
            f.write(line + "\n")


#: nominal tick width when laying request journeys on the timeline —
#: ticks are virtual time, so the scale is presentational only
TICK_US = 1000.0


def chrome_trace(span_events: list[dict] | None = None,
                 request_traces: dict[str, list[dict]] | None = None,
                 incidents: list[dict[str, Any]] | None = None,
                 ) -> dict[str, Any]:
    """The ring's host spans as a Chrome-trace dict.

    ``request_traces`` (request id -> event chain, default the live
    trace store) adds one lane per request under a process of its own:
    each journey is a span from submit to terminal with an instant
    mark per trace event.  ``incidents`` (loaded postmortem bundles)
    adds a lane marking each incident's evidence window and trigger
    tick."""
    evs = spans.events() if span_events is None else span_events
    trace_events: list[dict[str, Any]] = [
        {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
         "args": {"name": "host"}},
    ]
    host_t0 = min((e["ts_us"] for e in evs), default=0.0)
    tids = sorted({e["tid"] for e in evs})
    tid_map = {t: i + 1 for i, t in enumerate(tids)}
    for t, i in tid_map.items():
        trace_events.append(
            {"ph": "M", "pid": 1, "tid": i, "name": "thread_name",
             "args": {"name": f"host spans (thread {t})"}})
    for e in evs:
        row = {
            "ph": "X", "pid": 1, "tid": tid_map[e["tid"]],
            "name": e["name"],
            "ts": round(e["ts_us"] - host_t0, 3),
            "dur": round(e["dur_us"], 3),
        }
        if e.get("fields"):
            row["args"] = e["fields"]
        trace_events.append(row)

    chains = (_trace.all_traces() if request_traces is None
              else request_traces)
    if chains:
        trace_events.append(
            {"ph": "M", "pid": 3, "tid": 0, "name": "process_name",
             "args": {"name": "requests"}})
        for lane, rid in enumerate(sorted(chains), start=1):
            chain = chains[rid]
            if not chain:
                continue
            trace_events.append(
                {"ph": "M", "pid": 3, "tid": lane, "name": "thread_name",
                 "args": {"name": rid}})
            t_first = min(ev["tick"] for ev in chain)
            t_last = max(ev["tick"] for ev in chain)
            trace_events.append({
                "ph": "X", "pid": 3, "tid": lane, "name": rid,
                "ts": t_first * TICK_US,
                "dur": max((t_last - t_first) * TICK_US, 1.0),
                "args": {"events": len(chain),
                         "terminal": _trace.terminal_of(chain)},
            })
            for ev in chain:
                args = {k: v for k, v in ev.items()
                        if k != "event" and v is not None}
                trace_events.append({
                    "ph": "i", "pid": 3, "tid": lane, "s": "t",
                    "name": ev["event"], "ts": ev["tick"] * TICK_US,
                    "args": args,
                })

    if incidents:
        from attention_tpu.obs.postmortem import incident_lane

        trace_events.extend(incident_lane(incidents))
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def live_snapshot() -> dict[str, Any]:
    """What :func:`dump` writes as ``metrics.json`` and
    :func:`load_dump` gives back: the registry's snapshot with the
    process's compile log beside it (``compiles``:
    `obs.compiles.summary`)."""
    from attention_tpu.obs import compiles as _compiles

    return dict(REGISTRY.snapshot(), compiles=_compiles.summary())


def dump(out_dir: str) -> None:
    """Persist the live telemetry state under ``out_dir``."""
    from attention_tpu.obs import blackbox as _blackbox

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, DUMP_METRICS), "w") as f:
        json.dump(live_snapshot(), f, indent=1)
        f.write("\n")
    write_jsonl(os.path.join(out_dir, DUMP_EVENTS))
    chains = _trace.all_traces()
    if chains:
        with open(os.path.join(out_dir, DUMP_TRACES), "w") as f:
            for rid in sorted(chains):
                f.write(json.dumps(
                    {"request_id": rid, "events": chains[rid]}) + "\n")
    ring = _blackbox.events()
    if ring:
        with open(os.path.join(out_dir, DUMP_BLACKBOX), "w") as f:
            for rec in ring:
                f.write(json.dumps(rec, sort_keys=True) + "\n")


def load_dump(run_dir: str) -> tuple[dict[str, Any], list[dict]]:
    """(snapshot, span_events) from a :func:`dump` directory."""
    with open(os.path.join(run_dir, DUMP_METRICS)) as f:
        snapshot = json.load(f)
    evs: list[dict] = []
    events_path = os.path.join(run_dir, DUMP_EVENTS)
    if os.path.exists(events_path):
        with open(events_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                row = json.loads(line)
                if row.get("type") == "span":
                    row.pop("type")
                    evs.append(row)
    return snapshot, evs


def load_traces(run_dir: str) -> dict[str, list[dict]]:
    """Request-trace chains from a :func:`dump` directory (request id
    -> event chain; {} when the run recorded none)."""
    path = os.path.join(run_dir, DUMP_TRACES)
    chains: dict[str, list[dict]] = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                row = json.loads(line)
                chains[row["request_id"]] = row["events"]
    return chains


def write_slo(out_dir: str, report: dict[str, Any]) -> None:
    """Persist an `obs.slo.slo_report` next to the metrics dump, in
    canonical form (sorted keys) so same-seed runs are byte-identical."""
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, DUMP_SLO), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")


def load_slo(run_dir: str) -> dict[str, Any] | None:
    """The dump's SLO report, or None if the run wrote none."""
    path = os.path.join(run_dir, DUMP_SLO)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_forecast(out_dir: str, report: dict[str, Any]) -> None:
    """Persist an `obs.capacity.observatory_report` next to the metrics
    dump, in canonical form (sorted keys) so same-seed runs are
    byte-identical."""
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, DUMP_FORECAST), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")


def load_forecast(run_dir: str) -> dict[str, Any] | None:
    """The dump's forecast report, or None if the run wrote none."""
    path = os.path.join(run_dir, DUMP_FORECAST)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_anomaly(out_dir: str, report: dict[str, Any]) -> None:
    """Persist an `obs.anomaly.AnomalyTracker.report` next to the
    metrics dump, in canonical form (sorted keys) so same-seed runs
    are byte-identical."""
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, DUMP_ANOMALY), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")


def load_anomaly(run_dir: str) -> dict[str, Any] | None:
    """The dump's anomaly report, or None if the run wrote none."""
    path = os.path.join(run_dir, DUMP_ANOMALY)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_blackbox(run_dir: str) -> list[dict[str, Any]]:
    """Flight-recorder ring records from a :func:`dump` directory
    ([] when the run recorded none)."""
    path = os.path.join(run_dir, DUMP_BLACKBOX)
    out: list[dict[str, Any]] = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    out.append(json.loads(line))
    return out


def device_dir_of(run_dir: str) -> str | None:
    """The dump's device capture dir, if the run profiled one."""
    d = os.path.join(run_dir, DUMP_DEVICE)
    return d if os.path.isdir(d) else None
