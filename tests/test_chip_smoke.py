"""`chip_smoke.py` on the CPU: its phases pass at a toy width, and the
script refuses everything it must refuse (no TPU, a raising phase, a
failing check, interpreted kernels).  Plus the compile-cache helper it
and the other entry points call."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from attention_tpu import api, obs
from attention_tpu.utils import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# group 4 in bf16: the ragged kernel's aligned-tile path.  One layer and
# a chunk that swallows a whole prompt keep the executables few: on the
# CPU this test's cost is all compilation.
TOY_MODEL = dict(vocab=512, dim=64, depth=1, num_q_heads=8,
                 num_kv_heads=2, rope=True)
TOY_ENGINE = dict(num_pages=16, page_size=128, max_seq_len=384,
                  max_decode_batch=4, max_prefill_rows=2,
                  prefill_chunk=256, token_budget=512)
TOY_TRACE = dict(num_requests=3, seed=21, prompt_len_min=8,
                 prompt_len_max=40, max_tokens=3, arrival_every=1,
                 shared_prefix_len=130, shared_count=3)
TOY_BIN = dict(m=256, n=384, dk=64, dv=64)
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def obs_on():
    obs.enable()
    yield
    obs.disable()
    obs.reset()


def test_engine_phase_passes_at_toy_width(cs, obs_on, capsys):
    """The serving phase on a 2-shard mesh engine — the single-device
    checks plus what a four-chip host adds (parameters placed on the
    whole mesh) — through `run_phase`, which prints the report."""
    engine = cs.run_phase("engine", cs.engine_phase, cs.CompileClock(),
                          model_kw=TOY_MODEL, engine_kw=TOY_ENGINE,
                          trace_kw=TOY_TRACE, mesh_shards=2)
    assert engine["platform"] == "cpu" and engine["count"] == 8
    assert engine["requests"] == 3 and engine["output_tokens"] == 9
    assert engine["mesh_shards"] == 2
    assert engine["nonfinite_events"] == 0
    assert engine["prefix_cached_tokens"] >= 128
    assert engine["worst_logit_deficit"] <= cs.LOGIT_MARGIN
    assert engine["executables"] > 0 and engine["compile_s"] > 0
    assert len(engine["memory_while_serving"]) == 2
    # the variant each ragged lowering resolved to is on the report
    assert sum(engine["ragged_lowered"].values()) > 0
    (line,) = capsys.readouterr().out.splitlines()
    assert json.loads(line)["phase"] == "engine"


def test_bin_contract_phase_passes_at_toy_width(cs, obs_on):
    contract = cs.run_phase("bin_contract", cs.bin_contract_phase,
                            cs.CompileClock(), **TOY_BIN)
    assert contract["verdict"] == "Correct!"
    assert contract["flash_lowered"] == {"online->online": 1}


def test_mesh_attention_phase_passes_at_toy_width(cs):
    attn = cs.mesh_attention_phase(seq=512, dim=64)
    assert set(attn["max_abs_err"]) == {"kv_sharded", "ring"}


def test_failed_check_raises(cs, monkeypatch):
    monkeypatch.setitem(api._BACKENDS, "flash",
                        api._BACKENDS["chaos-broken"])
    with pytest.raises(cs.SmokeCheckError, match="Wrong!"):
        cs.bin_contract_phase(**TOY_BIN)


def test_interpreted_kernels_are_refused(cs, monkeypatch):
    with pytest.raises(cs.SmokeCheckError, match="interpret=True"):
        cs.require_compiled_kernels()
    # main() checks it before any phase, even on a (pretended) TPU
    monkeypatch.setattr(cs, "configure_compile_cache", lambda: "unused")
    monkeypatch.setattr(cs, "require_tpu", lambda: FAKE_TPU)
    monkeypatch.setattr(cs, "engine_phase", pytest.fail)
    with pytest.raises(cs.SmokeCheckError, match="interpret=True"):
        cs.main()


@pytest.mark.parametrize("failure", [RuntimeError("phase blew up"),
                                     "SmokeCheckError"])
def test_main_prints_no_result_when_a_phase_fails(cs, monkeypatch, capsys,
                                                  failure):
    """An exception out of a phase leaves main() — the process exits
    non-zero — and the result line is never printed."""
    if failure == "SmokeCheckError":
        failure = cs.SmokeCheckError("check failed")

    def bad_phase(**_kw):
        raise failure

    monkeypatch.setattr(cs, "configure_compile_cache", lambda: "unused")
    monkeypatch.setattr(cs, "require_tpu", lambda: FAKE_TPU)
    monkeypatch.setattr(cs, "require_compiled_kernels", lambda: None)
    monkeypatch.setattr(cs, "engine_phase", bad_phase)
    try:
        with pytest.raises(type(failure)):
            cs.main()
    finally:
        obs.disable()
        obs.reset()
    assert '"ok"' not in capsys.readouterr().out


def test_script_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "platform=cpu" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_env_is_left_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.configure_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_ignored_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = runtime.configure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # fixed: asking again resolves to the same place
        assert runtime.configure_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
