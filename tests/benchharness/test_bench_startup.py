"""The six `startup.*` readers (`benchmark/reduce/startup.py`): the
program's compile log up to the opening of the window, on a hand-made
log and on a toy engine's warm-up.  That the six entries list every
cell of `BENCHMARK.json` is `test_bench_contract.py`'s
``the_six_startup_entries_move_setup_s_in_every_cell``."""

import collections
import importlib.util
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.reduce import startup
from benchmark.runners import serve

METRICS = ("startup.trace_s", "startup.lower_s", "startup.compile_s",
           "startup.cache_misses", "startup.programs", "startup.rest_s")

# (kind, fun_name, end, seconds): one program before the window opens
# at 3.0 (traced 0.5-1.0 with a function nested in it, lowered
# 1.25-1.5, a cache miss, compiled 1.5-2.0) and one after it
ROWS = [
    ("trace", "inner", 0.875, 0.125),
    ("trace", "f", 1.0, 0.5),
    ("lower", "jit(f)", 1.5, 0.25),
    ("cache_misses", "", 2.0, 0.0),
    ("compile", "jit(f)", 2.0, 0.5),
    ("trace", "g", 4.0, 0.5),
    ("cache_misses", "", 5.0, 0.0),
    ("compile", "jit(g)", 5.0, 1.0),
]
CTX = {"window": (3.0, 48.0), "values": {"setup_s": 2.875}}


@pytest.fixture
def made_log(monkeypatch):
    from attention_tpu.obs import compiles

    monkeypatch.setattr(compiles, "_rows", collections.deque(ROWS))
    monkeypatch.setattr(compiles, "count", len(ROWS))
    return compiles


@pytest.mark.parametrize("metric, want", zip(METRICS, (
    0.5,        # the nested trace counts once
    0.25, 0.5,
    1.0, 1.0,   # the second program came after the window opened
    2.875 - 1.25)))
def test_each_reader_reads_the_log_before_the_window(made_log, metric, want):
    reader = harness.load_module("layer_metrics", metric)
    assert reader.read(CTX) == want


def test_rest_and_the_logs_all_s_add_up_to_setup_s(made_log):
    log = startup.before_window(CTX)
    assert log["all_s"] == 1.25 and log["traces"] == 2
    assert startup.rest_s(CTX) + log["all_s"] == CTX["values"]["setup_s"]
    assert log["trace_s"] + log["lower_s"] + log["compile_s"] \
        >= log["all_s"]
    # a window that opens later takes the second program in
    later = dict(CTX, window=(6.0, 51.0))
    assert startup.programs(later) == 2.0
    assert startup.cache_misses(later) == 2.0
    assert startup.rest_s(dict(later, values={})) is None


def test_a_program_without_the_log_gives_the_readers_nothing(monkeypatch):
    """The parent of the PR that brought the log, which the driver's
    traced runs put under these readers: no `obs/compiles.py`, so every
    reader returns None and raises nothing, and the line leaves the
    metric out.  A module that is there and broken fails the run."""
    import importlib.util

    find_spec = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: None
        if name == "attention_tpu.obs.compiles" else find_spec(name, *a))
    assert startup.before_window(CTX) is None
    for metric in METRICS:
        reader = harness.load_module("layer_metrics", metric)
        assert reader.read(CTX) is None
    monkeypatch.undo()
    from attention_tpu.obs import compiles

    monkeypatch.delattr(compiles, "summary")
    with pytest.raises(AttributeError):
        startup.before_window(CTX)


def test_the_setup_line_says_what_the_log_holds(made_log, monkeypatch):
    """An untraced run prints no `startup.*`: its ``setup:`` line ends
    with the same numbers and the costliest functions.  A program
    without the log prints the line as it was."""
    assert serve.compile_log_summary(3.0) == (
        "; compile log: trace_s 0.50, lower_s 0.25, compile_s 0.50, "
        "cache_misses 1, programs 1; costliest: jit(f) compile 0.50 s x 1; "
        "f trace 0.50 s x 1; jit(f) lower 0.25 s x 1")
    assert "programs 2; costliest: jit(g) compile 1.00 s x 1; " in (
        serve.compile_log_summary(6.0))
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None)
    assert serve.compile_log_summary(3.0) == ""


def test_programs_are_no_fewer_than_the_warm_ups_shapes():
    """A toy engine through the runner's warm-up: every ``(width,
    q_tile)`` shape compiles in exactly one step, the engine says which
    (`StepMetrics.compile_s`), and `startup.programs` counts no fewer
    programs than the ``step_shapes`` the ``setup:`` line prints."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.engine import EngineConfig, ServingEngine
    from attention_tpu.models import TinyDecoder
    from attention_tpu.obs import compiles

    # a model of this test's own: nothing of it is compiled yet
    model = TinyDecoder(vocab=53, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine_cfg = dict(num_pages=24, page_size=128, max_seq_len=256,
                      max_decode_batch=3, max_prefill_rows=1,
                      prefill_chunk=32, token_budget=40, watermark_pages=1)
    traffic = {"prompt_tokens": {"min": 8, "max": 40}}
    t_start = time.perf_counter()
    engine = ServingEngine(model, params, EngineConfig(**engine_cfg))
    steps, shapes = serve.warm_up(engine, traffic, engine_cfg,
                                  np.random.default_rng(5), model.vocab)
    opened = time.perf_counter()
    assert 1 < shapes < steps == len(engine.metrics.steps)
    summary = engine.metrics.summary()
    assert summary["compiled_steps"] == summary["programs"] == shapes
    log = compiles.summary(since=t_start, until=opened)
    assert log["programs"] >= shapes
    assert summary["compile_s_total"] <= log["all_s"] + 1e-3
    ctx = {"window": (opened, opened + 1.0),
           "values": {"setup_s": opened - t_start}}
    # the readers count from the start of the process
    assert startup.programs(ctx) >= log["programs"]
    assert startup.rest_s(ctx) <= ctx["values"]["setup_s"] - log["all_s"]
