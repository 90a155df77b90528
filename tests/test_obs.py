"""Unified telemetry subsystem tests (attention_tpu/obs/).

Pins the contracts ISSUE 3 promises: typed instruments with labeled
series and snapshot/reset; the bounded span ring; spans as profiler
annotations on the capture's own clock (ISSUE 24: nested spans, their
fields, and the engine step's phases read back from a CPU
`jax.profiler` capture); Prometheus text that round-trips through a
parser; the mtime-newest and
truncated-capture behavior of the profiler parser; the compile log
(`obs.compiles`: always on, unions not sums, bounded, one listener
pair a process); the zero-overhead-when-disabled contract (<5% on a tight loop, byte-
identical engine AND multi-replica front-end outputs — the router hot
path may not depend on telemetry); and the `cli obs` report/export
family.

All CPU-safe, tiny shapes.
"""

import contextlib
import gzip
import json
import os
import time

import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.obs import spans as obs_spans

pytestmark = pytest.mark.obs


@pytest.fixture
def obs_state():
    """Clean telemetry state; restores disabled-by-default after."""
    was = obs.is_enabled()
    obs.enable()
    obs.reset()
    yield
    obs.reset()
    (obs.enable if was else obs.disable)()


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from attention_tpu.models import TinyDecoder

    model = TinyDecoder(vocab=43, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    probe = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), probe)["params"]
    return model, params


def _engine_config():
    from attention_tpu.engine import EngineConfig

    return EngineConfig(num_pages=32, page_size=128, max_seq_len=256,
                        max_decode_batch=4, max_prefill_rows=2,
                        prefill_chunk=32, token_budget=64,
                        watermark_pages=1)


def _run_engine(tiny_model):
    from attention_tpu.engine import ServingEngine, replay, synthetic_trace

    model, params = tiny_model
    trace = synthetic_trace(4, vocab=43, seed=3, prompt_len_min=4,
                            prompt_len_max=12, max_tokens=3,
                            shared_prefix_len=129, shared_count=2)
    engine = ServingEngine(model, params, _engine_config())
    _summary, outputs = replay(engine, trace)
    return outputs


# ------------------------------------------------------------- registry


def test_registry_counter_gauge_histogram_labels(obs_state):
    c = obs.counter("obs.test.widgets")
    c.inc()
    c.inc(2, flavor="a")
    c.inc(flavor="b")
    assert c.value() == 1
    assert c.value(flavor="a") == 2
    assert c.value(flavor="b") == 1
    with pytest.raises(ValueError, match="cannot go down"):
        c.inc(-1)

    g = obs.gauge("obs.test.level")
    g.set(3.5)
    g.set(7, tank="x")
    assert g.value() == 3.5
    assert g.value(tank="x") == 7

    h = obs.histogram("obs.test.sizes", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 5000):
        h.observe(v)
    (series,) = h.series()
    assert series["counts"] == [1, 1, 1, 1]  # one per bucket + overflow
    assert series["count"] == 4
    assert series["sum"] == pytest.approx(5055.5)


def test_registry_type_conflict_and_bad_names(obs_state):
    obs.counter("obs.test.conflict")
    with pytest.raises(TypeError, match="already registered"):
        obs.gauge("obs.test.conflict")
    for bad in ("Bad.Name", "single", "has space.x", "a.b.c.d.e",
                "eng..step"):
        with pytest.raises(ValueError, match="naming convention"):
            obs.counter(bad)
    assert obs.check_name("engine.step")
    assert obs.check_name("engine.scheduler.admissions")
    assert not obs.check_name("engine")


def test_snapshot_and_reset(obs_state):
    obs.counter("obs.test.snap").inc(5)
    obs.gauge("obs.test.gsnap").set(2)
    snap = obs.REGISTRY.snapshot()
    names = {s["name"] for s in snap["counters"]} \
        | {s["name"] for s in snap["gauges"]}
    assert {"obs.test.snap", "obs.test.gsnap"} <= names
    obs.reset()
    # registrations survive reset; values do not
    assert obs.counter("obs.test.snap").value() == 0
    snap = obs.REGISTRY.snapshot()
    assert all(s["name"] != "obs.test.snap" or s["value"] == 0
               for s in snap["counters"])


def test_disabled_records_nothing():
    assert not obs.is_enabled()  # suite default: telemetry off
    c = obs.counter("obs.test.off")
    c.inc(100)
    assert c.value() == 0
    with obs.span("obs.test.offspan"):
        pass
    assert obs.events() == []


# ------------------------------------------------------------ exporters


def _parse_prom(text):
    """Tiny Prometheus text parser: {metric: {label_tuple: value}}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        metric, value = line.rsplit(" ", 1)
        if "{" in metric:
            name, rest = metric.split("{", 1)
            labels = tuple(sorted(
                kv.split("=", 1)[0] + "=" + kv.split("=", 1)[1].strip('"')
                for kv in rest.rstrip("}").split(",")
            ))
        else:
            name, labels = metric, ()
        out.setdefault(name, {})[labels] = float(value)
    return out


def test_prom_text_round_trips_through_parser(obs_state):
    obs.counter("obs.test.requests").inc(3, route="a")
    obs.counter("obs.test.requests").inc(1, route="b")
    obs.gauge("obs.test.depth").set(2.5)
    h = obs.histogram("obs.test.lat_ms", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5)
    h.observe(500)

    parsed = _parse_prom(obs.prom_text())
    assert parsed["obs_test_requests_total"][("route=a",)] == 3
    assert parsed["obs_test_requests_total"][("route=b",)] == 1
    assert parsed["obs_test_depth"][()] == 2.5
    # histogram: cumulative buckets, +Inf == count, sum preserved
    assert parsed["obs_test_lat_ms_bucket"][("le=1",)] == 1
    assert parsed["obs_test_lat_ms_bucket"][("le=10",)] == 2
    assert parsed["obs_test_lat_ms_bucket"][("le=+Inf",)] == 3
    assert parsed["obs_test_lat_ms_count"][()] == 3
    assert parsed["obs_test_lat_ms_sum"][()] == pytest.approx(505.5)


def test_span_ring_is_bounded(obs_state, monkeypatch):
    monkeypatch.setattr(obs_spans, "SPAN_RING_CAPACITY", 8)
    for i in range(20):
        obs.record_event("obs.test.ring", float(i), 1.0, tid=1)
    evs = obs.events()
    assert len(evs) == 8
    # oldest dropped, order preserved
    assert [e["ts_us"] for e in evs] == [float(i) for i in range(12, 20)]


def test_span_records_and_nests(obs_state):
    with obs.span("obs.test.outer"):
        with obs.span("obs.test.inner"):
            time.sleep(0.001)
    evs = obs.events()
    names = [e["name"] for e in evs]
    # inner exits (and records) first
    assert names == ["obs.test.inner", "obs.test.outer"]
    inner, outer = evs
    assert outer["dur_us"] >= inner["dur_us"] > 500
    assert outer["ts_us"] <= inner["ts_us"]


def test_jsonl_export_and_dump_roundtrip(obs_state, tmp_path):
    obs.counter("obs.test.rows").inc(2)
    with obs.span("obs.test.work"):
        pass
    run = tmp_path / "run"
    obs.dump(str(run))
    snapshot, events = obs.load_dump(str(run))
    assert any(s["name"] == "obs.test.rows" and s["value"] == 2
               for s in snapshot["counters"])
    assert [e["name"] for e in events] == ["obs.test.work"]
    lines = (run / "events.jsonl").read_text().splitlines()
    assert all(json.loads(ln) for ln in lines)


# --------------------------------------------------------- compile log


def _fresh(name):
    """A jitted function no other test has: its first call at a shape
    traces, lowers and compiles."""
    import jax

    def f(x):
        return x * 2 + 1

    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def test_compile_log_counts_a_new_shape_once_and_needs_no_enable():
    """A jitted function at a new shape moves `obs.compiles.count`, a
    second call at that shape does not; nothing hangs on
    `obs.enable()`, and `obs.reset()` leaves the log as it is."""
    import jax.numpy as jnp

    from attention_tpu.obs import compiles

    assert not obs.is_enabled()
    f = _fresh("obs_test_new_shape")
    before = compiles.count
    t0 = time.perf_counter()
    f(jnp.zeros((3,), jnp.float32)).block_until_ready()
    first = compiles.count
    assert first > before
    f(jnp.ones((3,), jnp.float32)).block_until_ready()
    assert compiles.count == first
    f(jnp.zeros((5,), jnp.float32)).block_until_ready()
    assert compiles.count > first
    log = compiles.summary(since=t0)
    mine = {(r["function"], r["kind"]): r["count"]
            for r in log["by_function"]
            if "obs_test_new_shape" in r["function"]}
    assert sorted(k for _, k in mine) == ["compile", "lower", "trace"]
    assert set(mine.values()) == {2}                 # one a shape
    assert log["traces"] >= 2 and log["programs"] >= 2
    assert log["all_s"] > 0
    # the kinds' unions add up to no less than the union of all
    assert log["trace_s"] + log["lower_s"] + log["compile_s"] \
        >= log["all_s"] - 1e-9
    whole, rows = compiles.summary(), compiles.count
    obs.reset()
    assert compiles.count == rows
    assert compiles.summary()["traces"] == whole["traces"]


def test_compile_log_nested_traces_are_a_union_not_a_sum():
    """A jitted function traced inside another's trace: `traces`
    counts both, `trace_s` is the length of the union of the two
    intervals, so no more than the wall time around the call and less
    than the durations summed."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.obs import compiles

    inner = _fresh("obs_test_nested_inner")

    def outer(x):
        y = inner(x)
        for _ in range(20):          # some tracing of the outer's own
            y = jnp.sin(y) + x
        return y

    outer.__name__ = outer.__qualname__ = "obs_test_nested_outer"
    t0 = time.perf_counter()
    jax.jit(outer)(jnp.zeros((7,), jnp.float32)).block_until_ready()
    t1 = time.perf_counter()
    log = compiles.summary(since=t0, until=t1)
    traced = {r["function"]: r for r in log["by_function"]
              if r["kind"] == "trace"}
    assert {"obs_test_nested_inner", "obs_test_nested_outer"} <= set(traced)
    assert log["traces"] >= 2
    summed = sum(r["seconds"] for r in traced.values())
    assert traced["obs_test_nested_outer"]["seconds"] \
        <= log["trace_s"] < summed
    # JAX times the events on `time.time`, the stamps are on
    # `time.perf_counter`: a millisecond of room between the clocks
    assert log["trace_s"] <= (t1 - t0) + 1e-3
    assert log["all_s"] <= (t1 - t0) + 1e-3


def test_compile_log_since_and_until_cut_at_the_stamp():
    import jax.numpy as jnp

    from attention_tpu.obs import compiles

    x = jnp.zeros((9,), jnp.float32)
    t0 = time.perf_counter()
    _fresh("obs_test_cut_first")(x).block_until_ready()
    t1 = time.perf_counter()
    _fresh("obs_test_cut_second")(x).block_until_ready()

    def names(**bounds):
        return {r["function"].removeprefix("jit(").removesuffix(")")
                for r in compiles.summary(**bounds)["by_function"]}

    assert "obs_test_cut_first" in names(since=t0, until=t1)
    assert "obs_test_cut_second" not in names(since=t0, until=t1)
    assert "obs_test_cut_second" in names(since=t1)
    assert "obs_test_cut_first" not in names(since=t1)
    assert {"obs_test_cut_first", "obs_test_cut_second"} <= names(since=t0)
    assert compiles.summary(until=t0 - 1e9)["traces"] == 0
    both = compiles.summary(since=t0)
    assert both["traces"] == (compiles.summary(since=t0, until=t1)["traces"]
                              + compiles.summary(since=t1)["traces"])


def test_compile_log_totals_add_up_after_the_ring_wraps(monkeypatch):
    """The ring drops its oldest rows; counts, sums and `by_function`
    over the process's life do not: they are kept apart."""
    import collections

    from attention_tpu.obs import compiles

    monkeypatch.setattr(compiles, "_rows", collections.deque(maxlen=8))
    monkeypatch.setattr(compiles, "_totals", {})
    monkeypatch.setattr(compiles, "count", 0)
    trace = "/jax/core/compile/jaxpr_trace_duration"
    compile_ = "/jax/core/compile/backend_compile_duration"
    for i in range(20):
        compiles._on_duration(trace, 0.5, fun_name=f"f{i % 2}")
        compiles._on_duration(compile_, 0.25)     # no name: still counts
        compiles._on_event("/jax/compilation_cache/cache_misses")
        compiles._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        compiles._on_duration("/jax/other/duration", 9.0)   # not ours
        compiles._on_event("/jax/other/event")
    assert compiles.count == 80 and len(compiles._rows) == 8
    log = compiles.summary()
    assert log["dropped"] == 72
    assert (log["traces"], log["programs"], log["cache_misses"]) \
        == (20, 20, 20)
    assert log["cache_retrieval_s"] == 2.5
    assert {(r["function"], r["kind"]): (r["seconds"], r["count"])
            for r in log["by_function"]} == {
        ("f0", "trace"): (5.0, 10), ("f1", "trace"): (5.0, 10),
        ("", "compile"): (5.0, 20)}
    # a bounded reading sees the rows the ring still holds
    assert compiles.summary(since=0.0)["traces"] == 2


def test_compile_log_registers_its_listeners_once(tiny_model):
    """One duration listener and one event listener a process: a
    second engine or another import of the package adds none."""
    import importlib

    from jax._src import monitoring

    from attention_tpu.engine import ServingEngine
    from attention_tpu.obs import compiles

    def ours():
        return (sum(cb is compiles._on_duration for cb in
                    monitoring.get_event_duration_listeners()),
                sum(cb is compiles._on_event for cb in
                    monitoring.get_event_listeners()))

    assert ours() == (1, 1)
    n = (len(monitoring.get_event_duration_listeners()),
         len(monitoring.get_event_listeners()))
    model, params = tiny_model
    ServingEngine(model, params, _engine_config())
    ServingEngine(model, params, _engine_config())
    assert importlib.import_module("attention_tpu.obs").compiles is compiles
    assert ours() == (1, 1)
    assert (len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_listeners())) == n


# ----------------------------------------------------- quantile digest


def _exact_nearest_rank(values, q):
    """The element the digest's nearest-rank rule targets."""
    import math

    s = sorted(values)
    return s[math.floor(q * (len(s) - 1))]


def test_digest_error_bound_on_adversarial_distributions():
    """ISSUE 12 acceptance: the relative-error bound (eps, default 1%)
    holds on the distributions that break fixed-bucket histograms —
    point mass, far-separated bimodal, heavy tail."""
    from attention_tpu.obs.quantile import (
        DEFAULT_EPS,
        REPORT_QUANTILES,
        QuantileDigest,
    )

    # point mass: min == max, so every quantile clamps EXACT
    dig = QuantileDigest()
    dig.extend([37.0] * 1000)
    for q in REPORT_QUANTILES:
        assert dig.quantile(q) == 37.0

    rng = np.random.default_rng(0)
    bimodal = ([1.0] * 600 + [1000.0] * 400)
    heavy = (rng.pareto(1.5, 5000) + 1.0).tolist()  # tail past 100x
    for values in (bimodal, heavy):
        dig = QuantileDigest()
        dig.extend(values)
        for q in REPORT_QUANTILES:
            est = dig.quantile(q)
            exact = _exact_nearest_rank(values, q)
            rel = abs(est - exact) / exact
            assert rel <= DEFAULT_EPS * 1.000001, (
                f"q={q}: est {est} vs exact {exact} ({rel:.4%})")
    # the report spelling is frozen
    assert set(dig.percentiles()) == {"p50", "p90", "p99", "p999"}
    with pytest.raises(ValueError, match=">= 0"):
        dig.add(-1.0)


def test_digest_merge_is_exact_bucketwise_addition():
    """Fleet rollup contract: merging per-replica digests equals one
    digest over the union stream — buckets, counts, min/max, and every
    report quantile EXACT (only float `sum` may differ in the last
    bits by addition order)."""
    from attention_tpu.obs.quantile import QuantileDigest, merge_digests

    rng = np.random.default_rng(7)
    parts = [sorted(rng.gamma(2.0, 10.0, 400).tolist())
             for _ in range(3)]
    shards = []
    for p in parts:
        d = QuantileDigest()
        d.extend(p)
        shards.append(d)
    whole = QuantileDigest()
    for p in parts:
        whole.extend(p)

    merged = merge_digests(shards)
    a, b = merged.snapshot(), whole.snapshot()
    assert a["sum"] == pytest.approx(b["sum"])
    del a["sum"], b["sum"]
    assert a == b  # buckets/zero/count/min/max byte-equal
    assert merged.percentiles() == whole.percentiles()
    # snapshot round-trips to an equivalent digest
    back = QuantileDigest.from_snapshot(merged.snapshot())
    assert back.percentiles() == merged.percentiles()
    with pytest.raises(ValueError, match="different boundaries"):
        QuantileDigest(eps=0.05).merge(QuantileDigest(eps=0.01))


def test_digest_registry_instrument_and_fleet_rollup(obs_state):
    """The `obs.digest` instrument: labeled series, per-label lookup,
    and `merged()` == bucket-wise merge of every label set."""
    from attention_tpu.obs.quantile import merge_digests

    d = obs.digest("obs.test.latency")
    for i in range(50):
        d.observe(float(i + 1), replica="r0")
        d.observe(float(2 * i + 1), replica="r1")
    per = [d.digest(replica=r) for r in ("r0", "r1")]
    fleet = d.merged()
    want = merge_digests(per)
    assert fleet.count == 100
    assert fleet.snapshot()["buckets"] == want.snapshot()["buckets"]
    assert fleet.percentiles() == want.percentiles()
    rows = d.series()
    assert {tuple(r["labels"].items()) for r in rows} == {
        (("replica", "r0"),), (("replica", "r1"),)}
    assert all("percentiles" in r and r["count"] == 50 for r in rows)
    snap = obs.REGISTRY.snapshot()
    assert any(s["name"] == "obs.test.latency" for s in snap["digests"])


def test_digest_disabled_records_nothing():
    assert not obs.is_enabled()
    d = obs.digest("obs.test.offdigest")
    d.observe(5.0)
    assert d.merged().count == 0


# ------------------------------------------------------ request traces


def test_trace_closed_enum_and_scalar_extras(obs_state):
    from attention_tpu.obs import trace

    trace.record("req-a", "submitted", tick=0, replica=None, tenant="t0")
    trace.record("req-a", "routed", tick=1, replica="r0", incarnation=0,
                 step=2, reason="least_loaded")
    trace.record("req-a", "finished", tick=9, replica="r0")
    chain = trace.events_of("req-a")
    assert [e["event"] for e in chain] == ["submitted", "routed",
                                          "finished"]
    assert chain[1]["reason"] == "least_loaded"
    assert trace.terminal_of(chain) == "finished"
    assert trace.terminal_of(chain[:2]) is None
    unknown = "tele" + "ported"  # non-literal: dodges the ATP504 lint
    with pytest.raises(ValueError, match="closed enum"):
        trace.record("req-a", unknown, tick=2)
    with pytest.raises(TypeError, match="plain scalar"):
        trace.record("req-a", "retried", tick=2, cause={"not": "flat"})
    body = "\n".join(trace.journey_lines("req-a", chain))
    assert "terminal=finished" in body and "reason=least_loaded" in body


def test_trace_capture_scope_and_adopt_idempotent():
    """Recording is off when telemetry is off; a capture() scope turns
    it on (clearing the store on entry) and the chains survive the
    scope exit; adopt() splices a restored tail exactly once."""
    from attention_tpu.obs import trace

    assert not obs.is_enabled()
    trace.record("req-x", "submitted", tick=0)
    assert trace.events_of("req-x") == []

    with trace.capture():
        trace.record("req-x", "submitted", tick=0)
        trace.record("req-x", "prefill_start", tick=1, replica="r0")
        tail = trace.events_of("req-x")
        trace.adopt("req-x", tail)   # in-process restore: dedup
        trace.adopt("req-x", tail)
        assert len(trace.events_of("req-x")) == 2
        trace.adopt("req-y", tail)   # fresh-process restore: verbatim
        assert len(trace.events_of("req-y")) == 2
    # the store outlives the scope (chaos checkers read it after)
    assert len(trace.events_of("req-x")) == 2
    with trace.capture():            # next plan starts isolated
        assert trace.all_traces() == {}
    trace.clear()


# ------------------------------------------- profiler capture parsing


def _write_capture(log_dir, run_name, modules, *, mtime=None,
                   payload=None, raw=None):
    d = os.path.join(str(log_dir), "plugins", "profile", run_name)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "host.trace.json.gz")
    if raw is not None:
        with open(path, "wb") as f:
            f.write(raw)
    else:
        if payload is None:
            payload = {"traceEvents": [
                {"ph": "M", "name": "thread_name", "pid": 7, "tid": 3,
                 "args": {"name": "XLA Modules"}},
                *[{"ph": "X", "pid": 7, "tid": 3, "name": f"{m}(tag)",
                   "ts": 100.0 * i, "dur": 40.0}
                  for i, m in enumerate(modules)],
            ]}
        with gzip.open(path, "wt") as f:
            json.dump(payload, f)
    if mtime is not None:
        os.utime(path, (mtime, mtime))
    return path


def test_device_module_seconds_picks_mtime_newest(tmp_path):
    """Regression: lexicographic sorted(...)[-1] picked the wrong
    capture when run timestamps roll over a path-sort boundary."""
    from attention_tpu.utils.profiling import device_module_seconds

    now = time.time()
    # "run_2" sorts AFTER "run_10" lexicographically, but is older
    _write_capture(tmp_path, "run_2", ["stale_module"], mtime=now - 100)
    _write_capture(tmp_path, "run_10", ["fresh_module"], mtime=now)
    mods = device_module_seconds(str(tmp_path))
    assert mods == {"fresh_module": pytest.approx(40.0 / 1e6)}


def test_device_module_slices_gives_timeline(tmp_path):
    from attention_tpu.utils.profiling import device_module_slices

    _write_capture(tmp_path, "run_1", ["mod_a", "mod_b"])
    slices = device_module_slices(str(tmp_path))
    assert slices == [("mod_a", 0.0, 40.0), ("mod_b", 100.0, 40.0)]


def test_truncated_captures_read_as_no_device_lane(tmp_path):
    """The silent-except fallback, pinned: corrupt gzip, missing lane,
    empty events, and missing schema all read as None."""
    from attention_tpu.utils.profiling import (
        device_module_seconds,
        device_module_slices,
    )

    assert device_module_seconds(str(tmp_path / "nonexistent")) is None

    _write_capture(tmp_path / "corrupt", "r", [],
                   raw=b"not a gzip stream at all")
    assert device_module_seconds(str(tmp_path / "corrupt")) is None
    assert device_module_slices(str(tmp_path / "corrupt")) is None

    _write_capture(tmp_path / "nolane", "r", [], payload={
        "traceEvents": [{"ph": "X", "pid": 1, "tid": 1,
                         "name": "m", "ts": 0.0, "dur": 1.0}]})
    assert device_module_seconds(str(tmp_path / "nolane")) is None

    _write_capture(tmp_path / "empty", "r", [], payload={"traceEvents": []})
    assert device_module_seconds(str(tmp_path / "empty")) is None

    _write_capture(tmp_path / "noschema", "r", [], payload={"other": 1})
    assert device_module_seconds(str(tmp_path / "noschema")) is None


def _host_events(trace_dir, prefixes):
    """Events of the newest capture's ``/host:CPU`` plane whose names
    start with one of ``prefixes``: (thread, name, start_ns, end_ns,
    stats), in start order (a parent before its children)."""
    import glob

    import jax

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    found.append((line.name, e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return sorted(found, key=lambda r: (r[2], -r[3]))


@contextlib.contextmanager
def _capture(trace_dir):
    """A ``jax.profiler`` capture of the enclosed block: host TraceMe
    events on, the Python tracer off (the benchmark's settings)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("enabled", [False, True], ids=["obs_off", "obs_on"])
def test_spans_are_profiler_annotations_on_one_clock(enabled, tmp_path):
    """Nested `obs.span`s land on the capture's ``/host:CPU`` plane, on
    one thread, the child inside the parent on the trace's clock, with
    their fields as the events' stats — telemetry off AND on."""
    assert not obs.is_enabled()
    if enabled:
        obs.enable()
        obs.reset()
    try:
        with _capture(tmp_path):
            with obs.span("obs.test.outer", step=7, queued=2):
                time.sleep(0.001)
                with obs.span("obs.test.inner", rid="req-3",
                              wait_ms=1.5):
                    time.sleep(0.001)
                time.sleep(0.001)
        ring = obs.events()
    finally:
        obs.reset()
        obs.disable()
    outer, inner = _host_events(tmp_path, ("obs.test.",))
    assert (outer[1], inner[1]) == ("obs.test.outer", "obs.test.inner")
    assert outer[0] == inner[0]                      # one thread
    assert outer[2] < inner[2] < inner[3] < outer[3]  # nested, one clock
    assert inner[3] - inner[2] >= 1_000_000          # the 1 ms sleep, ns
    assert outer[4] == {"step": 7, "queued": 2}
    assert inner[4] == {"rid": "req-3", "wait_ms": 1.5}
    # the ring is the enabled path's addition, fields included
    if enabled:
        assert [e["name"] for e in ring] == ["obs.test.inner",
                                             "obs.test.outer"]
        assert ring[0]["fields"] == {"rid": "req-3", "wait_ms": 1.5}
    else:
        assert ring == []


_PHASES = ("engine.step.schedule", "engine.step.pack",
           "engine.step.upload", "engine.step.dispatch",
           "engine.step.fetch", "engine.step.sample")


def test_engine_phase_spans_under_a_capture(tiny_model, tmp_path):
    """A tiny engine stepped under a CPU profiler capture: one
    `engine.step` per step and, inside every busy one, exactly one of
    each phase span, in order, none overlapping; fields ride as stats;
    the capture changes no token, with telemetry off or on; and every
    request's queue wait and prefill time add up to its TTFT."""
    from attention_tpu.engine import ServingEngine, replay, synthetic_trace

    model, params = tiny_model
    trace = synthetic_trace(4, vocab=43, seed=3, prompt_len_min=4,
                            prompt_len_max=12, max_tokens=3,
                            shared_prefix_len=129, shared_count=2)
    assert not obs.is_enabled()
    plain = _run_engine(tiny_model)

    engine = ServingEngine(model, params, _engine_config())
    with _capture(tmp_path / "off"):
        _summary, captured = replay(engine, trace)
    assert captured == plain
    obs.enable()
    obs.reset()
    try:
        with _capture(tmp_path / "on"):
            captured_on = _run_engine(tiny_model)
    finally:
        obs.reset()
        obs.disable()
    assert captured_on == plain

    rows = _host_events(tmp_path / "off", ("engine.step",))
    assert len({r[0] for r in rows}) == 1            # the loop's thread
    steps = [r for r in rows if r[1] == "engine.step"]
    assert [r[4]["step"] for r in steps] == list(range(len(steps)))
    assert len(steps) == len(engine.metrics.steps)
    busy = [m for m in engine.metrics.steps
            if m.decode_tokens or m.prefill_tokens]
    seen_busy = 0
    for step, m in zip(steps, engine.metrics.steps):
        kids = [r for r in rows if r is not step
                and step[2] <= r[2] and r[3] <= step[3]]
        if not (m.decode_tokens or m.prefill_tokens):
            assert [k[1] for k in kids] == ["engine.step.schedule"]
            continue
        seen_busy += 1
        assert [k[1] for k in kids] == list(_PHASES)
        for before, after in zip(kids, kids[1:]):
            assert before[3] <= after[2]             # none overlaps
        stats = {k[1]: k[4] for k in kids}
        assert stats["engine.step.dispatch"]["decode_rows"] \
            == m.num_decode_reqs
        assert stats["engine.step.dispatch"]["prefill_tokens"] \
            == m.prefill_tokens
        assert stats["engine.step.dispatch"]["width"] \
            == m.decode_tokens + m.prefill_tokens + m.pad_tokens
        assert stats["engine.step.sample"]["rows"] \
            == m.num_decode_reqs + m.num_prefill_reqs
        # 4 + 2 slots, every width over them: the step fetches one
        # logits row a slot, not one a packed position
        assert stats["engine.step.fetch"] == {
            "bytes": 4 * 43 * 6, "rows": 6,
            "used": m.num_decode_reqs + m.num_prefill_reqs}
    assert seen_busy == len(busy) > 0

    # the requests' marks share the request id with scheduler.admit
    marks = _host_events(tmp_path / "off",
                         ("engine.request.", "scheduler.admit"))
    ids = {r["id"] for r in trace}
    for name in ("engine.request.admitted", "engine.request.first_token",
                 "scheduler.admit"):
        assert {m[4]["rid"] for m in marks if m[1] == name} == ids
    for req in engine.metrics.requests:
        assert req.queue_wait_s >= 0 and req.prefill_s > 0
        assert req.queue_wait_s + req.prefill_s \
            == pytest.approx(req.ttft_s, abs=1e-9)
    summary = engine.metrics.summary()
    assert 0 <= summary["queue_wait_p50_ms"] <= summary["queue_wait_p90_ms"]
    assert 0 < summary["prefill_p50_ms"] <= summary["prefill_p90_ms"]


def test_a_step_that_compiles_marks_the_capture(tmp_path):
    """A step made to compile inside a profiler capture leaves ONE
    `engine.program.compiled` event on the host plane, inside its
    `engine.step`, with the step's shape and what the compile log
    holds for it; a step at a shape the process has run leaves none."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.engine import SamplingParams, ServingEngine
    from attention_tpu.models import TinyDecoder

    # a model of this test's own: nothing of it is compiled yet
    model = TinyDecoder(vocab=37, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = ServingEngine(model, params, _engine_config())
    engine.add_request(list(range(1, 12)), SamplingParams(max_tokens=4))
    assert not obs.is_enabled()
    with _capture(tmp_path):
        while engine.scheduler.has_work():
            engine.step()
    rows = _host_events(tmp_path, ("engine.step", "engine.program."))
    steps = [r for r in rows if r[1] == "engine.step"]
    marks = [r for r in rows if r[1] == "engine.program.compiled"]
    compiled = [m for m in engine.metrics.steps if m.compile_s]
    assert len(marks) == len(compiled) == 2        # a chunk, then decode
    assert len(steps) == len(engine.metrics.steps) > len(marks)
    for mark, m in zip(marks, compiled):
        stats = mark[4]
        assert set(stats) == {"step", "width", "q_tile", "trace_ms",
                              "lower_ms", "compile_ms", "cache",
                              "function"}
        assert stats["step"] == m.step
        assert stats["width"] \
            == m.decode_tokens + m.prefill_tokens + m.pad_tokens
        assert stats["q_tile"] > 0 and stats["cache"] == "off"
        assert "_ragged_apply" in stats["function"]
        assert stats["trace_ms"] > 0 and stats["compile_ms"] > 0
        (step,) = [s for s in steps if s[4]["step"] == m.step]
        assert step[0] == mark[0]                    # the loop's thread
        assert step[2] <= mark[2] and mark[3] <= step[3]


def test_chrome_trace_is_host_spans_with_fields(obs_state):
    """The chrome export lays out the ring's host spans (fields as
    args) and nothing of the device: a profiler capture already holds
    the program's spans beside the device lanes on one clock."""
    with obs.span("engine.step", step=0):
        pass
    doc = obs.chrome_trace()
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [(e["pid"], e["name"], e["args"]) for e in xs] \
        == [(1, "engine.step", {"step": 0})]
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "thread_name"}
    assert not any("XLA" in x for x in lanes)


# -------------------------------------------------- overhead contracts


def test_disabled_overhead_under_5_percent():
    """The no-op span/counter path on a tight loop: <5% wall overhead.
    The loop body is a small real matmul so the ratio reflects an
    instrumented hot loop, not an empty one."""
    assert not obs.is_enabled()
    c = obs.counter("obs.test.hotloop")
    rng = np.random.default_rng(0)
    a = rng.standard_normal((128, 128))
    b = rng.standard_normal((128, 128))
    n = 200

    def plain():
        t0 = time.perf_counter()
        for _ in range(n):
            a @ b
        return time.perf_counter() - t0

    def instruments():
        # exactly the calls the instrumented loop would add: n no-op
        # span enters/exits + n disabled counter incs
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("obs.test.hotloop"):
                pass
            c.inc()
        return time.perf_counter() - t0

    plain()  # warm the BLAS path
    instruments()
    base = min(plain() for _ in range(5))
    added = min(instruments() for _ in range(5))
    # additive cost measured separately: subtracting two noisy loop
    # timings drowns the signal on a contended 1-core CI box, the
    # disabled instrument path itself does not
    assert added <= base * 0.05, (
        f"disabled telemetry overhead {added / base:.1%} "
        f"(base {base * 1e3:.2f} ms, instruments {added * 1e3:.2f} ms)"
    )
    assert c.value() == 0
    assert obs.events() == []


def test_engine_outputs_byte_identical_with_obs_on(tiny_model):
    """Instrumentation must not perturb engine semantics: same trace,
    telemetry off vs on, token-for-token identical outputs."""
    import jax

    assert not obs.is_enabled()
    out_off = _run_engine(tiny_model)
    obs.enable()
    obs.reset()
    try:
        jax.clear_caches()  # force retracing so trace-time counters tick
        out_on = _run_engine(tiny_model)
        snap = obs.REGISTRY.snapshot()
        counters = {s["name"]: s for s in snap["counters"]
                    if not s["labels"]}
        assert counters["engine.steps.total"]["value"] > 0
        assert counters["engine.scheduler.admissions"]["value"] == 4
        assert counters["engine.requests.finished"]["value"] == 4
        assert any(s["name"] == "ops.ragged.calls"
                   for s in snap["counters"])
        span_names = {e["name"] for e in obs.events()}
        assert {"engine.step", "scheduler.admit",
                "allocator.alloc"} <= span_names
    finally:
        obs.reset()
        obs.disable()
    assert out_on == out_off


def test_ragged_outputs_byte_identical_with_obs_on(tiny_model):
    """The zero-overhead contract over the serving path: the
    single-launch step must stream byte-identical tokens with
    telemetry off vs on, and the launch/occupancy counters must land
    when it is on."""
    import jax

    assert not obs.is_enabled()
    out_off = _run_engine(tiny_model)
    obs.enable()
    obs.reset()
    try:
        jax.clear_caches()
        out_on = _run_engine(tiny_model)
        snap = obs.REGISTRY.snapshot()
        counters = {s["name"] for s in snap["counters"]}
        assert "engine.step.launches" in counters
        gauges = {s["name"] for s in snap["gauges"]}
        assert "engine.step.ragged_occupancy" in gauges
        # the engine-side latency digests filled alongside
        digests = {s["name"] for s in snap["digests"]}
        assert {"engine.digest.ttft_steps",
                "engine.digest.tpot_steps"} <= digests
        # ... and the per-request chains recorded end to end
        from attention_tpu.obs import trace

        chains = trace.all_traces()
        assert len(chains) == 4
        for chain in chains.values():
            assert chain[0]["event"] == "submitted"
            assert trace.terminal_of(chain) == "finished"
    finally:
        obs.reset()
        obs.disable()
    assert out_on == out_off


def _run_frontend(tiny_model):
    """A small multi-replica run over the router hot path: bursty
    multi-tenant trace, 2 replicas, prefix-affine + sticky routing."""
    from attention_tpu.engine import bursty_trace
    from attention_tpu.frontend import (
        FrontendConfig,
        ServingFrontend,
        replay_frontend,
    )

    model, params = tiny_model
    trace = bursty_trace(5, vocab=43, seed=7, shared_prefix_len=129,
                         tenants=2, burst_every=3, burst_size=2,
                         prompt_len_min=4, prompt_len_max=10,
                         max_tokens=3)
    frontend = ServingFrontend(
        model, params, _engine_config(),
        FrontendConfig(num_replicas=2, seed=0),
    )
    _summary, outputs = replay_frontend(frontend, trace)
    return outputs


def test_frontend_outputs_byte_identical_with_obs_on(tiny_model):
    """The zero-overhead contract extended over the ROUTER hot path
    (ISSUE 6): the front end's routing/shedding/ladder decisions read
    pressure off the replica handles, never the obs registry — so the
    same trace with telemetry off vs on must route, schedule, and
    sample identically."""
    import jax

    assert not obs.is_enabled()
    out_off = _run_frontend(tiny_model)
    obs.enable()
    obs.reset()
    try:
        jax.clear_caches()
        out_on = _run_frontend(tiny_model)
        snap = obs.REGISTRY.snapshot()
        counters = {s["name"] for s in snap["counters"]}
        assert counters & {"frontend.route.prefix_affine",
                           "frontend.route.sticky_session",
                           "frontend.route.least_loaded"}
        gauges = {s["name"] for s in snap["gauges"]}
        assert {"frontend.degrade.level",
                "frontend.replica.queue_depth"} <= gauges
        span_names = {e["name"] for e in obs.events()}
        assert "frontend.tick" in span_names
    finally:
        obs.reset()
        obs.disable()
    assert out_on == out_off


def test_tuning_search_counters(obs_state, tmp_path):
    from attention_tpu.tuning.search import tune

    calls = {"n": 0}

    def timer(step, x, operands, repeats):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("synthetic compile failure")
        return 0.001 * calls["n"]

    tune("flash_fwd", seq=1024, dim=64, heads=2, repeats=1, timer=timer,
         cache_path=str(tmp_path / "cache.json"))
    snap = obs.REGISTRY.snapshot()
    tried = sum(s["value"] for s in snap["counters"]
                if s["name"] == "tuning.search.candidates")
    skipped = sum(s["value"] for s in snap["counters"]
                  if s["name"] == "tuning.search.skipped")
    done = sum(s["value"] for s in snap["counters"]
               if s["name"] == "tuning.search.completed")
    assert tried == calls["n"] - 1
    assert skipped == 1
    assert done == 1


# --------------------------------------------------------- CLI + lint


def test_cli_serve_sim_obs_dump_report_and_export(tmp_path, capsys):
    from attention_tpu.cli import main

    run = tmp_path / "run"
    was = obs.is_enabled()
    try:
        rc = main(["serve-sim", "--num-requests", "2", "--max-tokens",
                   "2", "--prompt-len-max", "8", "--obs-out", str(run)])
        assert rc == 0
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])["summary"]
        # the steps that compiled, by the program's own compile log
        assert {"compiled_steps", "compile_s_total", "programs"} \
            <= set(summary)
        assert summary["compiled_steps"] >= summary["programs"] >= 0

        assert main(["obs", "report", "--run", str(run)]) == 0
        report = capsys.readouterr().out
        assert "== compiles ==" in report and "programs=" in report
        assert "engine.steps.total" in report
        assert "engine.step" in report  # span aggregate
        # the grouped families view covers the PR 6-11 series...
        assert "== families ==" in report
        assert "engine.step:" in report
        # ...and digests render with their report percentiles
        assert "== digests ==" in report
        assert "engine.digest.ttft_steps" in report
        assert "p999=" in report

        assert main(["obs", "export", "--run", str(run), "--format",
                     "prom"]) == 0
        parsed = _parse_prom(capsys.readouterr().out)
        assert parsed["engine_steps_total"][()] > 0

        # a device capture inside the dump feeds the report's device
        # modules; the chrome timeline is host spans and journeys only
        _write_capture(run / "device", "r", ["jit_paged_apply"])
        assert main(["obs", "report", "--run", str(run)]) == 0
        assert "jit_paged_apply" in capsys.readouterr().out
        out_file = tmp_path / "timeline.json"
        assert main(["obs", "export", "--run", str(run), "--format",
                     "chrome", "--out", str(out_file)]) == 0
        doc = json.loads(out_file.read_text())
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        # host spans (the step's phases, fields as args) AND the
        # request-journey lane
        assert {e["pid"] for e in xs} == {1, 3}
        names = {e["name"] for e in xs}
        assert {"engine.step", "engine.step.fetch",
                "engine.step.sample"} <= names
        assert any(e["name"] == "engine.step.dispatch"
                   and e["args"]["width"] > 0 for e in xs)
        assert "req-0" in names  # each journey is a span in lane 3

        assert main(["obs", "export", "--run", str(run), "--format",
                     "jsonl"]) == 0
        lines = capsys.readouterr().out.splitlines()
        kinds = {json.loads(ln)["type"] for ln in lines if ln}
        assert {"span", "counter"} <= kinds
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()


def test_cli_obs_trace_and_slo_from_dump_alone(tmp_path, capsys):
    """ISSUE 12 acceptance: journeys and the SLO report reconstruct
    from the --obs-out dump alone, and the same seed prints the SLO
    report byte-identically."""
    from attention_tpu.cli import main

    was = obs.is_enabled()
    args = ["serve-sim", "--replicas", "2", "--num-requests", "3",
            "--max-tokens", "3", "--prompt-len-max", "8",
            "--bursty", "--tenants", "2"]
    try:
        outs = []
        for d in ("run1", "run2"):
            run = tmp_path / d
            assert main([*args, "--obs-out", str(run)]) == 0
            capsys.readouterr()

            assert main(["obs", "trace", "--run", str(run)]) == 0
            listing = capsys.readouterr().out
            assert "req-0:" in listing and "terminal=finished" in listing

            assert main(["obs", "trace", "--run", str(run),
                         "--request", "req-0"]) == 0
            journey = capsys.readouterr().out
            for ev in ("submitted", "routed", "admitted",
                       "prefill_start", "first_token", "finished"):
                assert ev in journey, f"journey missing {ev}"
            assert "tenant=" in journey  # submit stamps the tenant

            assert main(["obs", "trace", "--run", str(run),
                         "--request", "no-such-request"]) == 1
            capsys.readouterr()

            assert main(["obs", "slo", "--run", str(run)]) == 0
            outs.append(capsys.readouterr().out)
        assert outs[0] == outs[1]  # byte-identical same-seed report
        rep = json.loads(outs[0])
        assert rep["version"] == 1 and rep["generated_at"] == 0
        assert [o["name"] for o in rep["objectives"]] == \
            ["ttft_p99", "tpot_p99"]
        assert {(g["tenant"], g["priority"]) for g in rep["groups"]}
        assert rep["fleet"]["requests"] == 3
        assert rep["fleet"]["ttft"]["count"] == 3
        for ob in rep["fleet"]["slo"]:
            assert ob["burn_rate"] >= 0.0
            assert ob["burn_series"], "rolling windows missing"
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()


def test_obs_name_lint_tree_is_clean_and_catches_violations(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_obs_names",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "check_obs_names.py"),
    )
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert lint.check_tree(repo) == []

    bad = tmp_path / "bad.py"
    bad.write_text(
        "from attention_tpu import obs\n"
        "from attention_tpu.obs import blackbox, trace\n"
        'obs.counter("EngineSteps")\n'
        'obs.span("just_one_segment")\n'
        'obs.gauge(dynamic_name)\n'  # non-literal: runtime-checked
        'obs.digest("AlsoBadDigest")\n'
        'trace.record("req", "vanished", tick=0)\n'  # not in the enum
        'trace.record("req", "finished", tick=1)\n'  # legal event
        'blackbox.note("made_up_kind", tick=0)\n'  # ATP507
        'blackbox.note("replica_kill", tick=0)\n'  # legal kind
    )
    errors = lint.check_file(str(bad))
    assert len(errors) == 5
    assert sum("violates" in e for e in errors) == 3
    assert sum("closed enum" in e for e in errors) == 2
    assert sum("BLACKBOX_EVENTS" in e for e in errors) == 1


# ------------------------------------------- forecast + capacity (ISSUE 14)


def _holt_mape(values, policy=None):
    from attention_tpu.obs import forecast as fc

    block = fc.forecast_series("x", values, policy=policy)
    return block["backtest"]["one_step_mape"]


def test_forecast_policy_validation():
    from attention_tpu.obs.forecast import ForecastPolicy

    ForecastPolicy().validate()
    for bad in (dict(alpha=0.0), dict(alpha=1.5), dict(beta=-0.1),
                dict(gamma=2.0), dict(season_ticks=1), dict(horizon=0),
                dict(backtest_window=1)):
        with pytest.raises(ValueError):
            ForecastPolicy(**bad).validate()
    rt = ForecastPolicy.from_dict(
        ForecastPolicy(season_ticks=48, advisory=True).to_dict())
    assert rt.season_ticks == 48 and rt.advisory


def test_forecast_accuracy_floor_step_ramp_diurnal():
    """ISSUE 14 acceptance: backtested one-step MAPE <= 15% on seeded
    synthetic step / ramp / diurnal series."""
    import math as m

    from attention_tpu.obs.forecast import ForecastPolicy

    step = [0.2] * 64 + [0.6] * 64
    assert _holt_mape(step) <= 0.15

    ramp = [0.01 * t for t in range(1, 129)]
    assert _holt_mape(ramp) <= 0.15

    diurnal = [0.5 + 0.4 * m.sin(2 * m.pi * t / 48) for t in range(192)]
    assert _holt_mape(
        diurnal, ForecastPolicy(season_ticks=48)) <= 0.15


def test_forecast_watermark_crossing_within_two_ticks():
    """ISSUE 14 acceptance: the predicted watermark-crossing tick is
    within +-2 of the true crossing at horizon <= 8."""
    import math as m

    from attention_tpu.obs import forecast as fc
    from attention_tpu.obs.forecast import ForecastPolicy

    # ramp: pressure 0.02*t crosses 0.92 at t = 46; observe 40 ticks
    ramp = [0.02 * t for t in range(40)]
    block = fc.forecast_series("pressure", ramp,
                               policy=ForecastPolicy(), horizon=8)
    row = fc.crossing(block, 0.92)
    assert row is not None and abs(row["tick"] - 46) <= 2

    # diurnal: two full seasons learned, cut mid-climb of day three
    period = 48
    series = [0.55 + 0.45 * m.sin(2 * m.pi * t / period)
              for t in range(2 * period + 10)]
    true_tick = next(t for t in range(2 * period + 10, 4 * period)
                     if 0.55 + 0.45 * m.sin(2 * m.pi * t / period)
                     >= 0.92)
    block = fc.forecast_series(
        "pressure", series,
        policy=ForecastPolicy(season_ticks=period), horizon=8)
    row = fc.crossing(block, 0.92)
    assert row is not None and abs(row["tick"] - true_tick) <= 2


def test_forecast_report_deterministic_and_rebuilds():
    """Same samples -> byte-identical report; the embedded samples
    rebuild it byte-identically; a new horizon reshapes the table."""
    import math as m

    from attention_tpu.obs import capacity as cap
    from attention_tpu.obs.forecast import ForecastPolicy

    samples = {
        "pressure": [0.4 + 0.3 * m.sin(2 * m.pi * t / 24)
                     for t in range(60)],
        "queue_depth": [float(t % 5) for t in range(60)],
    }
    inputs = {"ticks": 60, "alive": 2, "last_pressure": 0.45,
              "replica_tokens": {"0": 90, "1": 84}}
    pol = ForecastPolicy(season_ticks=24)
    a = cap.observatory_report(samples, inputs, policy=pol)
    b = cap.observatory_report(samples, inputs, policy=pol)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a["version"] == 1 and a["generated_at"] == 0

    rebuilt = cap.rebuild_report(json.loads(json.dumps(a)))
    assert json.dumps(rebuilt, sort_keys=True) == \
        json.dumps(a, sort_keys=True)

    wider = cap.rebuild_report(a, horizon=12)
    assert all(len(blk["forecast"]) == 12 for blk in wider["series"])

    fleet = a["capacity"]["fleet"]
    assert fleet["tokens"] == 174
    assert fleet["cost_per_token"] == pytest.approx(2 * 60 / 174, abs=1e-6)
    assert 0.0 <= fleet["headroom"] <= 1.0


def _run_frontend_forecast(tiny_model, forecast):
    """Like _run_frontend but returns the frontend too (forecast
    tracker state is part of what the tests pin)."""
    from attention_tpu.engine import bursty_trace
    from attention_tpu.frontend import (
        FrontendConfig,
        ServingFrontend,
        replay_frontend,
    )

    model, params = tiny_model
    trace = bursty_trace(5, vocab=43, seed=7, shared_prefix_len=129,
                         tenants=2, burst_every=3, burst_size=2,
                         prompt_len_min=4, prompt_len_max=10,
                         max_tokens=3)
    frontend = ServingFrontend(
        model, params, _engine_config(),
        FrontendConfig(num_replicas=2, seed=0, forecast=forecast),
    )
    summary, outputs = replay_frontend(frontend, trace)
    return frontend, summary, outputs


def test_forecast_zero_overhead_and_advisory_parity(tiny_model):
    """ISSUE 14 acceptance: forecasting rides the telemetry contract —
    obs off/on and forecast off/on/advisory all produce byte-identical
    token streams, summaries, and (modulo advisory 'forecast' tuples)
    event logs.  The forecaster observes; it never acts."""
    import jax

    from attention_tpu.frontend import ForecastPolicy

    assert not obs.is_enabled()
    fe_off, s_off, o_off = _run_frontend_forecast(tiny_model, None)
    assert fe_off.forecast is None and fe_off.forecast_pressure is None
    with pytest.raises(ValueError, match="forecasting is disabled"):
        fe_off.forecast_report()

    fe_on, s_on, o_on = _run_frontend_forecast(
        tiny_model, ForecastPolicy())
    assert o_on == o_off and s_on == s_off
    assert fe_on.events_log == fe_off.events_log
    assert fe_on.forecast_pressure is not None

    fe_adv, s_adv, o_adv = _run_frontend_forecast(
        tiny_model, ForecastPolicy(advisory=True))
    assert o_adv == o_off and s_adv == s_off
    assert [e for e in fe_adv.events_log if e[0] != "forecast"] == \
        fe_off.events_log

    # fresh report calls are byte-identical (what invariant 13 pins)
    rep = fe_on.forecast_report()
    assert json.dumps(rep, sort_keys=True) == \
        json.dumps(fe_on.forecast_report(), sort_keys=True)
    assert {b["name"] for b in rep["series"]} == {
        "pressure", "queue_depth", "admissions", "tokens",
        "ttft", "tpot"}

    # telemetry ON changes nothing either (the original contract,
    # extended over the forecasting hot path)
    obs.enable()
    obs.reset()
    try:
        jax.clear_caches()
        _fe2, s2, o2 = _run_frontend_forecast(
            tiny_model, ForecastPolicy())
        assert o2 == o_off and s2 == s_off
    finally:
        obs.reset()
        obs.disable()


def test_forecast_chaos_invariant_checker(tiny_model):
    """chaos invariant 13: clean on a healthy forecast-enabled run,
    silent (no false positives) when forecasting is off."""
    from attention_tpu.chaos import invariants as inv
    from attention_tpu.frontend import ForecastPolicy

    fe_on, _s, _o = _run_frontend_forecast(tiny_model, ForecastPolicy())
    assert inv.forecast_determinism_violations(fe_on) == []
    fe_off, _s, _o = _run_frontend_forecast(tiny_model, None)
    assert inv.forecast_determinism_violations(fe_off) == []


def test_cli_obs_forecast_from_dump_alone(tmp_path, capsys):
    """ISSUE 14 acceptance: the forecast + capacity report
    reconstructs byte-identically from the --obs-out dump alone, and
    two same-seed runs print it byte-identically."""
    from attention_tpu.cli import main

    was = obs.is_enabled()
    args = ["serve-sim", "--replicas", "2", "--num-requests", "4",
            "--max-tokens", "3", "--prompt-len-max", "8",
            "--diurnal", "--rag-prefill-len", "0", "--forecast"]
    try:
        outs = []
        for d in ("run1", "run2"):
            run = tmp_path / d
            assert main([*args, "--obs-out", str(run)]) == 0
            capsys.readouterr()
            assert main(["obs", "forecast", "--run", str(run)]) == 0
            outs.append(capsys.readouterr().out)
        assert outs[0] == outs[1]  # byte-identical same-seed report
        with open(tmp_path / "run1" / "forecast.json") as f:
            assert f.read() == outs[0]  # CLI == committed dump bytes

        doc = json.loads(outs[0])
        assert doc["version"] == 1 and doc["generated_at"] == 0
        assert doc["policy"]["season_ticks"] == 48  # --diurnal default
        assert doc["watermarks"] == {"shed": 0.92, "downclass": 0.75}
        assert {b["name"] for b in doc["series"]} == {
            "pressure", "queue_depth", "admissions", "tokens",
            "ttft", "tpot"}
        assert {r["replica"] for r in doc["capacity"]["replicas"]} == \
            {"replica-0", "replica-1"}

        # --horizon rebuilds from the embedded samples
        assert main(["obs", "forecast", "--run",
                     str(tmp_path / "run1"), "--horizon", "3"]) == 0
        wider = json.loads(capsys.readouterr().out)
        assert all(len(b["forecast"]) == 3 for b in wider["series"])

        # obs report grows the forecast section
        assert main(["obs", "report", "--run",
                     str(tmp_path / "run1")]) == 0
        text = capsys.readouterr().out
        assert "== forecast ==" in text
        assert "saturation[shed] @ 0.92" in text

        # a dump without forecast.json degrades cleanly
        assert main(["obs", "forecast", "--run", str(tmp_path)]) == 1
        capsys.readouterr()
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()


# ---------------------------------------------- incident layer (ISSUE 18)


def test_blackbox_ring_capture_and_closed_enum():
    """The flight recorder: disabled notes vanish, capture() records
    with the four deterministic coordinates, event kinds are the
    closed BLACKBOX_EVENTS enum, extras must be plain scalars."""
    from attention_tpu.obs import blackbox

    assert not obs.is_enabled()
    blackbox.clear()
    blackbox.note("route_decision", tick=0)  # disabled: dropped
    assert blackbox.depth() == 0 and not blackbox.active()
    with blackbox.capture():
        assert blackbox.active()
        blackbox.note("route_decision", tick=1, replica="replica-0",
                      incarnation=0, step=4, reason="least_loaded")
        blackbox.note("shed", tick=2, request="req-1")
        unknown_kind = "not_an_event"  # non-literal arg: ATP507 leaves
        with pytest.raises(ValueError,  # the runtime check to fire
                           match="unknown blackbox event"):
            blackbox.note(unknown_kind, tick=3)
        with pytest.raises(TypeError, match="plain scalar"):
            blackbox.note("shed", tick=3, victims=[1, 2])
        evs = blackbox.events()
        assert [e["kind"] for e in evs] == ["route_decision", "shed"]
        assert [e["seq"] for e in evs] == [0, 1]
        assert evs[0]["replica"] == "replica-0" and evs[0]["step"] == 4
        assert blackbox.events(kind="shed")[0]["tick"] == 2
        assert blackbox.events(since_tick=2) == [evs[1]]
        assert blackbox.events(until_tick=1) == [evs[0]]
    assert not blackbox.active()
    blackbox.clear()


def test_blackbox_ring_is_bounded_and_seq_monotone():
    from attention_tpu.obs import blackbox

    with blackbox.capture():
        n = blackbox.BLACKBOX_CAPACITY + 10
        for i in range(n):
            blackbox.note("route_decision", tick=i)
        assert blackbox.depth() == blackbox.BLACKBOX_CAPACITY
        assert blackbox.total() == n
        evs = blackbox.events()
        assert evs[0]["seq"] == 10  # oldest evicted first
        assert evs[-1]["seq"] == n - 1
    blackbox.clear()


def test_blackbox_disabled_overhead_under_5_percent():
    """The PR 12 zero-overhead contract extended over note(): the
    disabled path is one global read and a return."""
    from attention_tpu.obs import blackbox

    assert not obs.is_enabled()
    blackbox.clear()
    rng = np.random.default_rng(0)
    a = rng.standard_normal((128, 128))
    b = rng.standard_normal((128, 128))
    n = 200

    def plain():
        t0 = time.perf_counter()
        for _ in range(n):
            a @ b
        return time.perf_counter() - t0

    def notes():
        # exactly the calls an instrumented loop would add: n
        # disabled note()s — each must be one predicate test + return
        t0 = time.perf_counter()
        for i in range(n):
            blackbox.note("route_decision", tick=i,
                          replica="replica-0", reason="least_loaded")
        return time.perf_counter() - t0

    plain()  # warm the BLAS path
    notes()
    base = min(plain() for _ in range(5))
    added = min(notes() for _ in range(5))
    # the additive cost of n disabled note()s must stay under 5% of
    # the n-matmul workload (measured separately: on a contended
    # 1-core CI box the subtraction of two noisy loop timings would
    # drown the signal, the added path itself does not)
    assert added <= base * 0.05, (
        f"disabled flight-recorder overhead {added / base:.1%} "
        f"(base {base * 1e3:.2f} ms, notes {added * 1e3:.2f} ms)"
    )
    assert blackbox.depth() == 0 and blackbox.total() == 0


def test_anomaly_policy_validation_and_roundtrip():
    from attention_tpu.obs.anomaly import AnomalyPolicy

    AnomalyPolicy().validate()
    for bad in (dict(residual_scale=0.0), dict(residual_min_band=-1.0),
                dict(residual_warmup=0), dict(burn_window=1),
                dict(burn_slope_bound=0.0), dict(burn_min_requests=0),
                dict(gray_window=0), dict(gray_min_samples=0),
                dict(gray_ratio=1.0), dict(gray_trail=0)):
        with pytest.raises(ValueError):
            AnomalyPolicy(**bad).validate()
    rt = AnomalyPolicy.from_dict(AnomalyPolicy(gray_trail=4).to_dict())
    assert rt.gray_trail == 4


def test_anomaly_residual_band_rising_edge():
    """A pressure step far outside the backtested band fires
    residual_band once; while the condition holds no second firing
    lands (rising edge keeps incident bundles bounded)."""
    from attention_tpu.obs.anomaly import AnomalyPolicy, AnomalyTracker

    tr = AnomalyTracker(AnomalyPolicy(residual_warmup=6))
    t = 0
    for _ in range(12):
        tr.observe_pressure(t, 0.3)
        assert tr.step(t) == []
        t += 1
    tr.observe_pressure(t, 8.0)
    new = tr.step(t)
    assert [f["detector"] for f in new] == ["residual_band"]
    assert new[0]["key"] == "fleet" and new[0]["tick"] == t
    assert ("residual_band", "fleet") in tr.active
    t += 1
    tr.observe_pressure(t, 16.0)  # still way off: condition holds
    assert tr.step(t) == []       # ... but no re-firing
    assert len(tr.firings) == 1


def test_anomaly_gray_failure_unit_detection_latency():
    """Tracker-level pin of the acceptance bound: a replica whose
    inter-token gaps inflate 4x is flagged within 8 ticks, and the
    healthy peer never is."""
    from attention_tpu.obs.anomaly import AnomalyPolicy, AnomalyTracker

    tr = AnomalyTracker(AnomalyPolicy(gray_trail=4))
    for t in range(10):
        tr.observe_tokens(t, "replica-0", "a", 1)
        tr.observe_tokens(t, "replica-1", "b", 1)
        assert tr.step(t) == []
    inject = 10
    fired = []
    for t in range(inject, inject + 30):
        if (t - inject) % 4 == 0:
            tr.observe_tokens(t, "replica-0", "a", 1)  # 4x slower now
        tr.observe_tokens(t, "replica-1", "b", 1)
        fired += tr.step(t)
        if fired:
            break
    assert fired, "gray detector never fired"
    assert fired[0]["detector"] == "gray_failure"
    assert fired[0]["key"] == "replica-0"
    assert fired[0]["tick"] - inject <= 8
    assert all(f["key"] != "replica-1" for f in tr.firings)


def _run_frontend_incident(tiny_model, *, anomaly=None,
                           incident_dir=None):
    """The bursty 2-replica run with the incident layer attached."""
    from attention_tpu.engine import bursty_trace
    from attention_tpu.frontend import (
        FrontendConfig,
        ServingFrontend,
        replay_frontend,
    )

    model, params = tiny_model
    trace = bursty_trace(5, vocab=43, seed=7, shared_prefix_len=129,
                         tenants=2, burst_every=3, burst_size=2,
                         prompt_len_min=4, prompt_len_max=10,
                         max_tokens=3)
    frontend = ServingFrontend(
        model, params, _engine_config(),
        FrontendConfig(num_replicas=2, seed=0, anomaly=anomaly,
                       incident_dir=incident_dir),
    )
    summary, outputs = replay_frontend(frontend, trace)
    return frontend, summary, outputs


def test_frontend_byte_identical_with_incident_layer_on(
        tiny_model, tmp_path):
    """ISSUE 18 zero-overhead pin: recorder + detectors + postmortem
    writer off vs on produce token-byte-identical streams and
    identical summaries; with telemetry on the ring actually fills."""
    import jax

    from attention_tpu.obs import blackbox
    from attention_tpu.obs.anomaly import AnomalyPolicy

    assert not obs.is_enabled()
    _fe, s_off, o_off = _run_frontend_incident(tiny_model)
    assert "anomaly_firings" in s_off and "incidents" in s_off
    fe_on, s_on, o_on = _run_frontend_incident(
        tiny_model, anomaly=AnomalyPolicy(),
        incident_dir=str(tmp_path / "inc"))
    assert o_on == o_off and s_on == s_off
    assert fe_on.anomaly is not None and fe_on.postmortem is not None
    assert blackbox.depth() == 0  # telemetry off: ring stayed empty

    obs.enable()
    obs.reset()
    try:
        jax.clear_caches()
        _fe2, s2, o2 = _run_frontend_incident(
            tiny_model, anomaly=AnomalyPolicy(),
            incident_dir=str(tmp_path / "inc2"))
        assert o2 == o_off and s2 == s_off
        assert blackbox.depth() > 0
        assert blackbox.events(kind="route_decision")
        snap = obs.REGISTRY.snapshot()
        gauges = {s["name"] for s in snap["gauges"]}
        assert "frontend.anomaly.residual" in gauges
    finally:
        obs.reset()
        obs.disable()


def _run_gray_fleet(tiny_model, *, degrade, inject_tick=8,
                    max_ticks=400):
    """A 2-replica fleet under sustained concurrent decode; with
    ``degrade`` replica-0's token budget collapses mid-run, so its
    inter-token gaps inflate while every supervisor-visible signal
    (virtual step cost, step counter, error streak) stays clean — the
    replica is sick but NOT dead, exactly the gray failure the
    liveness supervisor cannot see."""
    from attention_tpu.engine import synthetic_trace
    from attention_tpu.engine.sim import sampling_of
    from attention_tpu.frontend import FrontendConfig, ServingFrontend
    from attention_tpu.obs.anomaly import AnomalyPolicy

    model, params = tiny_model
    trace = synthetic_trace(8, vocab=43, seed=5, prompt_len_min=4,
                            prompt_len_max=8, max_tokens=16,
                            arrival_every=2)
    fe = ServingFrontend(
        model, params, _engine_config(),
        FrontendConfig(num_replicas=2, seed=0,
                       anomaly=AnomalyPolicy(gray_trail=4)),
    )
    for entry in trace:
        fe.submit(entry["prompt"], sampling_of(entry),
                  request_id=entry.get("id"),
                  arrival=int(entry.get("arrival", 0)))
    orig_tick = fe.tick
    armed = {"done": False}

    def tick():
        if degrade and not armed["done"] \
                and fe.current_tick == inject_tick:
            armed["done"] = True
            # budget throttle ONLY: inflating the virtual step cost
            # would trip the supervisor's slow-step signal and turn
            # this into a fail-stop kill, not a gray failure
            fe.replicas[0].engine.scheduler.token_budget = 1
        return orig_tick()

    fe.tick = tick
    fe.run(max_ticks=max_ticks)
    return fe


def test_gray_failure_detected_within_8_ticks_no_false_positives(
        tiny_model):
    """ISSUE 18 acceptance: on the simulated CPU fleet the gray
    detector flags the degraded replica within <= 8 ticks of
    injection, never a healthy peer, and the clean arm fires nothing
    at all."""
    assert not obs.is_enabled()
    clean = _run_gray_fleet(tiny_model, degrade=False)
    assert clean.anomaly.firings == []  # zero false positives

    inject = 8
    fe = _run_gray_fleet(tiny_model, degrade=True, inject_tick=inject)
    gray = [f for f in fe.anomaly.firings
            if f["detector"] == "gray_failure"]
    assert gray, (
        f"gray detector never fired; all firings {fe.anomaly.firings}")
    assert gray[0]["key"] == "replica-0"
    assert gray[0]["tick"] - inject <= 8, gray[0]
    assert {f["key"] for f in gray} == {"replica-0"}
    # the liveness supervisor never saw it: that is what makes the
    # failure gray rather than fail-stop
    assert fe.counts["supervisor_dead"] == 0
    assert fe.counts["replica_kills"] == 0
    assert fe.counts["anomaly_firings"] == len(fe.anomaly.firings)
    # the firing rode into the event log (advisory channel)
    assert any(e[0] == "anomaly" and e[2] == "gray_failure"
               for e in fe.events_log)


def _bundle_bytes(root):
    """{relative path: bytes} for every file under an incident dir."""
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for name in sorted(files):
            p = os.path.join(dirpath, name)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


def test_incident_bundles_byte_identical_same_seed(tiny_model, tmp_path):
    """ISSUE 18 acceptance: the same seeded chaos plan dumps
    byte-identical incident bundles twice over, and the postmortem
    report reconstructed from the bundles alone matches too."""
    from attention_tpu.chaos.faults import (
        FaultEvent,
        FaultPlan,
        default_frontend_config,
        run_frontend_plan,
    )
    from attention_tpu.engine import synthetic_trace
    from attention_tpu.obs import postmortem as pm

    model, params = tiny_model
    trace = synthetic_trace(6, vocab=43, seed=31, max_tokens=6)
    plan = FaultPlan(seed=0, events=(
        FaultEvent(step=5, kind="replica_kill", target="replica-0"),
        FaultEvent(step=8, kind="replica_restart", target="replica-0"),
    ))
    roots = []
    for d in ("a", "b"):
        root = str(tmp_path / d)
        r = run_frontend_plan(model, params, _engine_config(),
                              default_frontend_config(2), trace, plan,
                              incident_root=root)
        assert r.violations == [], r.violations
        roots.append(root)
    bundles = pm.list_incidents(roots[0])
    assert bundles  # the kill filed its incidents
    causes = {pm.load_incident(b)["meta"]["cause"] for b in bundles}
    assert "fault" in causes
    assert _bundle_bytes(roots[0]) == _bundle_bytes(roots[1])
    assert pm.report_lines(roots[0]) == pm.report_lines(roots[1])
    # the fault bundle correlates back to its fault_injected trigger
    fault_bundle = next(b for b in bundles
                        if pm.load_incident(b)["meta"]["cause"] == "fault")
    loaded = pm.load_incident(fault_bundle)
    triggers = pm.correlate(loaded)
    assert any("fault_injected" in line for line in triggers)


def test_postmortem_writer_dedup_and_chrome_lane(tmp_path):
    """PostmortemWriter dedups (cause, tick, detail); the chrome
    export grows the incident lane (pid 4) from loaded bundles."""
    from attention_tpu.obs import blackbox
    from attention_tpu.obs import postmortem as pm

    w = pm.PostmortemWriter(str(tmp_path))
    with blackbox.capture():
        blackbox.note("replica_kill", tick=7, replica="replica-0")
        assert w.maybe_dump(tick=7, cause="typed_error",
                            detail={"error": "ReplicaDeadError"})
        # exact duplicate: no second bundle
        assert w.maybe_dump(tick=7, cause="typed_error",
                            detail={"error": "ReplicaDeadError"}) is None
        # different detail at the same tick: a second bundle
        assert w.maybe_dump(tick=7, cause="fault",
                            detail={"kind": "oom"})
    assert len(pm.list_incidents(str(tmp_path))) == 2
    loaded = [pm.load_incident(b)
              for b in pm.list_incidents(str(tmp_path))]
    trace_doc = obs.chrome_trace([], incidents=loaded)
    lane = [e for e in trace_doc["traceEvents"] if e.get("pid") == 4]
    assert any(e.get("ph") == "X" for e in lane)  # bundle spans
    blackbox.clear()


def test_cli_serve_sim_incident_layer_and_postmortem(tmp_path, capsys):
    """End to end through the CLI: serve-sim with the incident layer
    on dumps anomaly.json + blackbox.jsonl + incident bundles; `obs
    postmortem` reconstructs the timeline byte-identically across
    same-seed runs; `obs report` grows the anomalies section."""
    from attention_tpu.cli import main

    was = obs.is_enabled()
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json.dumps({
        "seed": 0,
        "events": [{"step": 6, "kind": "replica_kill", "arg": 1,
                    "target": "replica-0"}],
    }))
    args = ["serve-sim", "--replicas", "2", "--num-requests", "8",
            "--max-tokens", "3", "--prompt-len-max", "8",
            "--anomaly", "--chaos-plan", str(plan_path)]
    try:
        reports = []
        for d in ("run1", "run2"):
            inc = tmp_path / d / "inc"
            run = tmp_path / d / "obs"
            assert main([*args, "--incident-dir", str(inc),
                         "--obs-out", str(run)]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["blackbox"]["ring_depth"] > 0
            assert out["blackbox"]["incidents"] >= 1
            assert "anomaly" in out
            assert main(["obs", "postmortem", "--run", str(inc)]) == 0
            reports.append(capsys.readouterr().out)
            assert "cause: fault [kind=replica_kill" in reports[-1]
            assert "fault_injected" in reports[-1]
        assert reports[0] == reports[1]  # byte-identical postmortems

        run1 = tmp_path / "run1" / "obs"
        assert (run1 / "anomaly.json").exists()
        assert (run1 / "blackbox.jsonl").exists()
        assert main(["obs", "report", "--run", str(run1)]) == 0
        text = capsys.readouterr().out
        assert "== anomalies ==" in text
        assert "residual_band:" in text
        assert "gray_failure[replica-0]" in text

        # chrome export with the incident lane
        chrome = tmp_path / "incidents.json"
        assert main(["obs", "postmortem", "--run",
                     str(tmp_path / "run1" / "inc"),
                     "--chrome", str(chrome)]) == 0
        capsys.readouterr()
        lane = [e for e in json.loads(chrome.read_text())["traceEvents"]
                if e.get("pid") == 4]
        assert lane

        # a directory without bundles degrades cleanly
        assert main(["obs", "postmortem", "--run", str(tmp_path)]) == 1
        capsys.readouterr()
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()


def test_blackbox_fleet_actuation_kinds_registered():
    """ISSUE 19: the five disaggregation kinds are first-class members
    of the closed BLACKBOX_EVENTS enum (ATP507 lints the literal call
    sites; this pins the runtime registry)."""
    from attention_tpu.obs import blackbox
    from attention_tpu.obs.naming import BLACKBOX_EVENTS

    kinds = ("scale_up", "scale_down", "handoff", "handoff_fallback",
             "actuation_veto")
    assert set(kinds) <= set(BLACKBOX_EVENTS)
    with blackbox.capture():
        for i, kind in enumerate(kinds):
            blackbox.note(kind, tick=i, pool="decode", cause="slack")
        assert [e["kind"] for e in blackbox.events()] == list(kinds)
        assert all(e["pool"] == "decode" for e in blackbox.events())
    blackbox.clear()
