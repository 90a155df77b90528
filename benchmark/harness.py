"""What every run of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, the device check, the compile cache, host
spans, the compile counter, percentiles and the result line.

Importing this starts no backend: JAX takes the chip on first use.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChipError(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py`` — found by the name a
    data file gives, so a later PR adds a file and edits none."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic
    mix and the metrics that are reported in it."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or load_benchmark()
        try:
            self.entry = next(w for w in bench["workloads"]
                              if w["name"] == name)
        except StopIteration:
            raise KeyError(
                f"no workload {name!r}; BENCHMARK.json has "
                f"{[w['name'] for w in bench['workloads']]}") from None
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = next(c for c in bench["configs"]
                   if c["name"] == self.entry["config"])
        with open(os.path.join(ROOT, cfg["file"])) as f:
            self.config = json.load(f)
        self.config_name = cfg["name"]
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in e2e]

    def reference(self):
        """The configuration's plain reference, kept beside its file."""
        return load_module("configs", self.config_name + "_reference")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a device off the table is an
    error, not a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]


# -- the device ------------------------------------------------------------

def configure_compile_cache() -> str:
    """JAX's persistent cache at a fixed place inside the checkout
    (where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that and
    nothing is set here).  Every program is cached, however quickly it
    compiled, so that only a checkout's first run compiles."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices, or `NoChipError`."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChipError(
            f"the cell needs {n} TPU chip(s); JAX reports platform="
            f"{devices[0].platform} kind={devices[0].device_kind!r} "
            f"count={len(devices)}")
    return devices[:n]


def open_cell(workload: str):
    """What every command of the benchmark starts with: the cell, its
    runner (which imports the program, before the chip is taken), the
    compile cache and the chips.  Without the chips it says so on the
    standard error and exits with code 2, printing no result."""
    cell = Cell(workload)
    runner = load_module("runners", cell.config["runner"])
    cache_dir = configure_compile_cache()
    try:
        devices = require_chips(cell.chips)
    except NoChipError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        raise SystemExit(2) from None
    return cell, runner, devices, cache_dir


def device_block(devices) -> dict:
    """``device`` of the result line: what JAX reports, and the peak
    bytes in use on the fullest of the chips used."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts what JAX traces and compiles (or loads from the
    persistent cache in a compile's place).  Listeners cannot be taken
    off again: one per process."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event in self._EVENTS:
            self.count += 1
            self.seconds += seconds


# -- host spans ------------------------------------------------------------

class Spans:
    """The benchmark's own spans, kept in memory: ``(name, start, end)``
    on `time.perf_counter`.  While a profiler trace is on, each span is
    also written into it (`jax.profiler.TraceAnnotation`), which puts it
    on the device trace's clock for the attribution of idle gaps."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        note = (jax.profiler.TraceAnnotation(name) if self.annotate
                else contextlib.nullcontext())
        with note:
            t0 = self.clock()
            try:
                yield
            finally:
                self.records.append((name, t0, self.clock()))

    def durations(self, name: str) -> list[float]:
        return [b - a for n, a, b in self.records if n == name]


class SliceTracer:
    """The profiler over the last ``trace_seconds`` of a ``--trace 1``
    run's window (or, with ``stop_after``, a slice that ends earlier),
    when queues and caches are in their steady state: a
    whole window of a serving cell is over a million events, which
    take minutes to reduce.  Host TraceMe events are on, the Python
    tracer is off (it would slow the loop that is being measured).
    The span ``bench.traced`` marks the slice on the trace's clock."""

    def __init__(self, enabled: bool, spans: Spans, out_dir: str,
                 start_after: float, stop_after: float = math.inf):
        self.enabled, self.spans, self.out_dir = enabled, spans, out_dir
        self.start_after, self.stop_after = start_after, stop_after
        self.started_at: float | None = None   # on the spans' clock
        self._span, self._running = None, False
        if enabled:
            # the first start of the profiler in a process sets it up,
            # which can take seconds: paid here, in set-up, and not by
            # the step that the slice would begin with
            self._start()
            jax.profiler.stop_trace()

    def _start(self) -> None:
        shutil.rmtree(self.out_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=options)

    def tick(self, elapsed: float) -> None:
        """Call between units of work with the seconds since the
        window opened; starts the trace once its time has come, and
        stops it at ``stop_after`` where the slice ends before the
        window does (arrivals that end early, so that all are served):
        the span closes there, the profiler runs on to the window's end,
        since stopping it takes seconds that the requests still in
        flight would wait."""
        if self._span is not None and elapsed >= self.stop_after:
            self._close_span()
        if (not self.enabled or self.started_at is not None
                or elapsed < self.start_after):
            return
        self._start()
        self._running = True
        self.spans.annotate = True
        self.started_at = self.spans.clock()
        self._span = self.spans.span("bench.traced")
        self._span.__enter__()

    def _close_span(self) -> None:
        self._span.__exit__(None, None, None)
        self._span = None
        self.spans.annotate = False

    def stop(self) -> None:
        if not self._running:
            return
        if self._span is not None:
            self._close_span()
        self._running = False
        jax.profiler.stop_trace()


# -- arithmetic ------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics; a missing sample is ``inf`` and sorts last, so a
    percentile that reaches into the missing ones is ``inf``."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[lo] == math.inf or (pos > lo and xs[hi] == math.inf):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


class Checks:
    """The numbers that decide ``correct``: each is printed beside its
    limit, in every run."""

    def __init__(self):
        self.rows: list[dict] = []

    def add(self, name: str, value: float, limit: float) -> None:
        ok = bool(value <= limit)  # NaN compares false: not correct
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": ok})
        print(json.dumps(self.rows[-1]))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def compared(self) -> dict:
        """``{check: {"value", "limit"}}`` for the result line.  JSON
        has no NaN and no infinity: such a number goes as its name."""
        def plain(x):
            return float(x) if math.isfinite(x) else str(float(x))
        return {r["check"]: {"value": plain(r["value"]),
                             "limit": plain(r["limit"])}
                for r in self.rows}

    def report(self, file) -> None:
        """Each number compared beside its limit, one to a line: the
        last lines a run writes to its standard error."""
        for r in self.rows:
            print(f"compared: {r['check']} {float(r['value']):.9g} limit "
                  f"{float(r['limit']):.9g} {'ok' if r['ok'] else 'NOT OK'}",
                  file=file, flush=True)


def result_line(*, checks: Checks, attempted: int, failed: int,
                metrics: dict, units: dict, device: dict,
                breakdown: dict | None = None) -> str:
    """The last line of a run: one JSON object, numbers as measured.
    A metric whose value is missing or not finite is left out.  The
    numbers that decided ``correct`` come last, each beside its limit
    (the driver keeps the end of this line of a run that is not
    correct, and nothing else of its standard output)."""
    out = {}
    for name, value in metrics.items():
        if value is None or not math.isfinite(value):
            continue
        out[name] = {"value": float(value), "unit": units[name]}
    line = {"correct": checks.correct, "attempted": int(attempted),
            "failed": int(failed), "metrics": out, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = checks.compared()
    return json.dumps(line)
