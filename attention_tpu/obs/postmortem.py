"""Incident postmortems: atomic evidence bundles + causal timelines.

When something goes wrong — a typed error, a chaos invariant
violation, an anomaly-detector firing, an injected fault — the
serving stack dumps an ``incident-<tick>/`` bundle: the flight-
recorder ring sliced around the incident tick, the registry snapshot,
and every request trace chain active in the window.  The bundle is
the whole story: ``cli obs postmortem --run DIR`` reconstructs the
cross-replica causal timeline from the bundle alone, correlates the
alarm with its trigger events, and renders a byte-deterministic
incident report (same seed → same bytes, the `write_slo` canon).

Bundles are written with the snapshot discipline: every file is
fsync'd inside a temp directory, then one ``os.replace`` publishes
the bundle — a crash mid-dump leaves either no bundle or a whole one,
never a torn one.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
from typing import Any

from attention_tpu.obs import blackbox as _blackbox
from attention_tpu.obs import trace as _trace
from attention_tpu.obs.registry import REGISTRY

INCIDENT_REPORT_VERSION = 1

#: bundle directory prefix (``incident-<tick>[-<n>]``)
INCIDENT_PREFIX = "incident-"

#: bundle member files
INCIDENT_META = "incident.json"
INCIDENT_RING = "blackbox.jsonl"
INCIDENT_METRICS = "metrics.json"
INCIDENT_TRACES = "traces.jsonl"

#: ring/trace slice width: ticks of history captured before the
#: incident tick
INCIDENT_WINDOW = 64

#: the closed set of incident causes — `incident.json:cause` is one of
#: these, and the chaos `incident_completeness` invariant reasons about
#: them structurally
INCIDENT_CAUSES = frozenset({
    "fault",        # a chaos injector fired (detail: fault kind)
    "typed_error",  # a fault-class typed error surfaced in the frontend
    "detector",     # an obs/anomaly.py detector crossed its bound
    "invariant",    # a chaos invariant checker reported violations
    "actuation",    # a fleet scale-down was followed by sheds inside
                    # its guard window (mis-actuation)
})


def _fsync_write(path: str, text: str) -> None:
    with open(path, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())


def _jsonl(rows: list[dict[str, Any]]) -> str:
    return "".join(json.dumps(r, sort_keys=True) + "\n" for r in rows)


def _deterministic_snapshot() -> dict[str, Any]:
    """Registry snapshot minus the wall-clock reporting channels.

    ``*_ms`` instruments (``engine.step.wall_ms``,
    ``engine.snapshot.save_ms``) time
    host/device walls — ATP801's sanctioned reporting channel,
    excluded from every byte-determinism contract in the repo.  An
    incident bundle IS such a contract (same seed must dump
    byte-identical bundles), so they stay out of ``metrics.json``."""
    return {
        kind: [s for s in series if not s["name"].endswith("_ms")]
        if isinstance(series, list) else series
        for kind, series in REGISTRY.snapshot().items()
    }


def dump_incident(out_dir: str, *, tick: int, cause: str,
                  detail: dict[str, Any],
                  window: int = INCIDENT_WINDOW,
                  name: str | None = None) -> str:
    """Atomically write one ``incident-<tick>/`` bundle under
    ``out_dir``; returns the published bundle path.

    The bundle captures the live stores at dump time: the blackbox
    ring sliced to ``[tick - window, tick]``, the registry snapshot
    (minus wall-clock channels — see ``_deterministic_snapshot``),
    and every trace chain with an event in the window.  ``detail``
    must be plain scalars (it is the incident's identity — the
    completeness invariant matches bundles to causes by it)."""
    if cause not in INCIDENT_CAUSES:
        raise ValueError(
            f"unknown incident cause {cause!r}; causes are the closed "
            f"set: {', '.join(sorted(INCIDENT_CAUSES))}")
    for k, v in detail.items():
        if v is not None and not isinstance(v, (str, int, float, bool)):
            raise TypeError(
                f"incident detail {k}={v!r} must be a plain scalar")
    os.makedirs(out_dir, exist_ok=True)
    if name is None:
        name = f"{INCIDENT_PREFIX}{int(tick):06d}"
        final = os.path.join(out_dir, name)
        n = 2
        while os.path.exists(final):
            final = os.path.join(out_dir, f"{name}-{n}")
            n += 1
    else:
        final = os.path.join(out_dir, name)

    lo = int(tick) - int(window)
    ring = _blackbox.events(since_tick=lo, until_tick=int(tick))
    chains = {
        rid: chain
        for rid, chain in sorted(_trace.all_traces().items())
        if any(lo <= ev["tick"] <= int(tick) for ev in chain)
    }
    meta = {
        "version": INCIDENT_REPORT_VERSION,
        "generated_at": 0,
        "tick": int(tick),
        "cause": cause,
        "detail": {k: detail[k] for k in sorted(detail)},
        "window": int(window),
        "ring_events": len(ring),
        "trace_chains": len(chains),
    }

    tmp = tempfile.mkdtemp(dir=out_dir, prefix=".tmp-incident-")
    try:
        _fsync_write(os.path.join(tmp, INCIDENT_META),
                     json.dumps(meta, indent=1, sort_keys=True) + "\n")
        _fsync_write(os.path.join(tmp, INCIDENT_RING), _jsonl(ring))
        _fsync_write(
            os.path.join(tmp, INCIDENT_METRICS),
            json.dumps(_deterministic_snapshot(), indent=1,
                       sort_keys=True) + "\n")
        _fsync_write(
            os.path.join(tmp, INCIDENT_TRACES),
            _jsonl([{"request_id": rid, "events": chains[rid]}
                    for rid in sorted(chains)]))
        dfd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        os.replace(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return final


class PostmortemWriter:
    """Per-frontend incident dumper: dedup + flood control.

    One writer owns one run's incident directory.  ``maybe_dump``
    writes at most one bundle per distinct ``(tick, cause, detail)``
    (an injector marking the same fault twice, or a detector whose
    condition is re-reported, folds into one incident) and stops at
    ``limit`` bundles — a chaotic campaign must not turn the disk into
    the incident."""

    def __init__(self, out_dir: str, *, window: int = INCIDENT_WINDOW,
                 limit: int = 256):
        self.out_dir = out_dir
        self.window = int(window)
        self.limit = int(limit)
        #: (tick, cause, sorted detail items) of every bundle written
        self.written: list[tuple[int, str, tuple]] = []
        self.suppressed = 0

    def maybe_dump(self, *, tick: int, cause: str,
                   detail: dict[str, Any]) -> str | None:
        key = (int(tick), cause,
               tuple(sorted((k, v) for k, v in detail.items())))
        if key in self._seen():
            return None
        if len(self.written) >= self.limit:
            self.suppressed += 1
            return None
        path = dump_incident(self.out_dir, tick=tick, cause=cause,
                             detail=detail, window=self.window)
        self.written.append(key)
        _blackbox.note("incident_dump", tick=int(tick), cause=cause,
                       bundle=os.path.basename(path))
        return path

    def _seen(self) -> set[tuple]:
        return set(self.written)


# -- bundle loading + timeline reconstruction ------------------------------


def list_incidents(run_dir: str) -> list[str]:
    """Bundle directories under ``run_dir``, incident order (tick,
    then collision suffix)."""
    if not os.path.isdir(run_dir):
        return []
    out = []
    for entry in sorted(os.listdir(run_dir)):
        full = os.path.join(run_dir, entry)
        if (entry.startswith(INCIDENT_PREFIX) and os.path.isdir(full)
                and os.path.isfile(os.path.join(full, INCIDENT_META))):
            out.append(full)
    return out


def load_incident(bundle_dir: str) -> dict[str, Any]:
    """One bundle, parsed: ``{"name", "meta", "ring", "traces",
    "snapshot"}`` — everything the timeline needs, from disk alone."""
    with open(os.path.join(bundle_dir, INCIDENT_META)) as f:
        meta = json.load(f)
    ring: list[dict[str, Any]] = []
    ring_path = os.path.join(bundle_dir, INCIDENT_RING)
    if os.path.exists(ring_path):
        with open(ring_path) as f:
            ring = [json.loads(line) for line in f if line.strip()]
    traces: dict[str, list[dict[str, Any]]] = {}
    traces_path = os.path.join(bundle_dir, INCIDENT_TRACES)
    if os.path.exists(traces_path):
        with open(traces_path) as f:
            for line in f:
                if line.strip():
                    row = json.loads(line)
                    traces[row["request_id"]] = row["events"]
    snapshot: dict[str, Any] = {}
    metrics_path = os.path.join(bundle_dir, INCIDENT_METRICS)
    if os.path.exists(metrics_path):
        with open(metrics_path) as f:
            snapshot = json.load(f)
    return {"name": os.path.basename(bundle_dir), "meta": meta,
            "ring": ring, "traces": traces, "snapshot": snapshot}


_COORD_KEYS = ("kind", "event", "tick", "replica", "incarnation",
               "step", "seq", "request_id")


def _fmt_entry(tick: int, label: str, replica: str | None,
               incarnation: int, step: int,
               extras: dict[str, Any]) -> str:
    where = ""
    if replica is not None:
        where = f" replica={replica} inc={incarnation}"
        if step >= 0:
            where += f" step={step}"
    tail_items = [f"{k}={extras[k]}" for k in sorted(extras)
                  if extras[k] is not None]
    tail = (" [" + " ".join(tail_items) + "]") if tail_items else ""
    return f"  [tick {tick:>5}] {label}{where}{tail}"


def timeline(bundle: dict[str, Any]) -> list[str]:
    """The cross-replica causal timeline of one loaded bundle: ring
    records and trace-chain events merged in (tick, source, seq)
    order, one line each."""
    entries: list[tuple[tuple, str]] = []
    for rec in bundle["ring"]:
        extras = {k: v for k, v in rec.items() if k not in _COORD_KEYS}
        line = _fmt_entry(rec["tick"], rec["kind"], rec.get("replica"),
                          rec.get("incarnation", 0),
                          rec.get("step", -1), extras)
        entries.append(((rec["tick"], 0, rec.get("seq", 0), ""), line))
    for rid in sorted(bundle["traces"]):
        for i, ev in enumerate(bundle["traces"][rid]):
            extras = {k: v for k, v in ev.items()
                      if k not in _COORD_KEYS}
            extras["request"] = rid
            line = _fmt_entry(ev["tick"], f"trace:{ev['event']}",
                              ev.get("replica"),
                              ev.get("incarnation", 0),
                              ev.get("step", -1), extras)
            entries.append(((ev["tick"], 1, i, rid), line))
    entries.sort(key=lambda e: e[0])
    return [line for _, line in entries]


#: ring kinds that can be an incident's trigger, by cause
_TRIGGER_KINDS = {
    "fault": ("fault_injected",),
    "detector": ("anomaly_fire",),
    "typed_error": ("shed", "replica_kill", "store_corrupt",
                    "lease_expire"),
    "invariant": ("fault_injected", "anomaly_fire"),
    "actuation": ("scale_down", "shed"),
}


def correlate(bundle: dict[str, Any]) -> list[str]:
    """Alarm → trigger correlation: the ring records that plausibly
    caused this incident (matching kind, at or before the incident
    tick, nearest first)."""
    meta = bundle["meta"]
    kinds = _TRIGGER_KINDS.get(meta["cause"], ())
    cands = [rec for rec in bundle["ring"]
             if rec["kind"] in kinds and rec["tick"] <= meta["tick"]]
    cands.sort(key=lambda r: (-r["tick"], -r.get("seq", 0)))
    lines = []
    for rec in cands[:8]:
        extras = {k: v for k, v in rec.items() if k not in _COORD_KEYS}
        lines.append(_fmt_entry(rec["tick"], rec["kind"],
                                rec.get("replica"),
                                rec.get("incarnation", 0),
                                rec.get("step", -1), extras))
    return lines


def report_lines(run_dir: str) -> list[str]:
    """The full ``cli obs postmortem`` body for every bundle under
    ``run_dir`` — byte-deterministic (sorted bundles, sorted keys, no
    clocks)."""
    bundles = [load_incident(d) for d in list_incidents(run_dir)]
    lines = [f"incident postmortem: {len(bundles)} bundle(s)"]
    for b in bundles:
        meta = b["meta"]
        detail = " ".join(f"{k}={meta['detail'][k]}"
                          for k in sorted(meta["detail"]))
        lines.append("")
        lines.append(f"== {b['name']} ==")
        lines.append(f"cause: {meta['cause']}"
                     + (f" [{detail}]" if detail else ""))
        lines.append(
            f"window: ticks {meta['tick'] - meta['window']}.."
            f"{meta['tick']}, {meta['ring_events']} ring event(s), "
            f"{meta['trace_chains']} trace chain(s)")
        corr = correlate(b)
        if corr:
            lines.append("trigger correlation:")
            lines.extend(corr)
        lines.append("timeline:")
        lines.extend(timeline(b))
    return lines


def incident_lane(bundles: list[dict[str, Any]]) -> list[dict[str, Any]]:
    """Chrome-trace events for the incident lane (pid 4): one span
    per bundle covering its evidence window plus one instant at the
    incident tick — rendered beside the host/device/request lanes by
    `obs.export.chrome_trace`."""
    from attention_tpu.obs.export import TICK_US

    if not bundles:
        return []
    out: list[dict[str, Any]] = [
        {"ph": "M", "pid": 4, "tid": 0, "name": "process_name",
         "args": {"name": "incidents"}},
        {"ph": "M", "pid": 4, "tid": 1, "name": "thread_name",
         "args": {"name": "incident bundles"}},
    ]
    for b in bundles:
        meta = b["meta"]
        t0 = (meta["tick"] - meta["window"]) * TICK_US
        out.append({
            "ph": "X", "pid": 4, "tid": 1, "name": b["name"],
            "ts": t0,
            "dur": max(meta["window"] * TICK_US, 1.0),
            "args": {"cause": meta["cause"], **meta["detail"]},
        })
        out.append({
            "ph": "i", "pid": 4, "tid": 1, "s": "t",
            "name": meta["cause"], "ts": meta["tick"] * TICK_US,
            "args": {"bundle": b["name"]},
        })
    return out
