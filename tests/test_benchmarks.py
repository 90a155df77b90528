"""Smoke tests for the benchmark suite + profiling on the CPU mesh.

These assert structure/consistency, not absolute performance (CPU timing
is meaningless); real numbers come from bench.py on TPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.benchmarks import ablation_table, strong_scaling, weak_scaling
from attention_tpu.ops.flash import BlockSizes
from attention_tpu.parallel.mesh import default_mesh
from attention_tpu.utils.profiling import RunRecord, append_jsonl, trace

BS = BlockSizes(64, 64)


def test_ablation_table_structure():
    table = ablation_table(128, 128, 32, 32, repeats=1, block_sizes=BS)
    assert {"baseline", "fused", "mixed", "full"} <= set(table)
    for rec in table.values():
        assert rec.best_us > 0
        assert np.isfinite(rec.gflops_per_chip)
        assert rec.extra["speedup_vs_baseline"] > 0
        assert rec.utilization is None  # the CPU has no published peak
    assert table["baseline"].extra["speedup_vs_baseline"] == 1.0


def test_ablation_with_mesh():
    mesh = default_mesh("kv", devices=jax.devices()[:2])
    table = ablation_table(64, 128, 16, 16, repeats=1, block_sizes=BS, mesh=mesh)
    assert "overlap" in table
    assert table["overlap"].n_devices == 2
    assert table["overlap"].mesh_axes == {"kv": 2}


def test_strong_scaling_records():
    recs = strong_scaling(64, 256, 16, 16, device_counts=(1, 2, 4), repeats=1,
                          block_sizes=BS, dtype=jnp.float32)
    assert [r.n_devices for r in recs] == [1, 2, 4]
    assert recs[0].extra["speedup_vs_smallest"] == 1.0


def test_weak_scaling_records():
    recs = weak_scaling(64, m=64, dk=16, dv=16, device_counts=(1, 2), repeats=1,
                        block_sizes=BS, dtype=jnp.float32)
    assert [r.n for r in recs] == [64, 128]


def test_placement_table_orders():
    from attention_tpu.benchmarks import placement_table

    recs = placement_table(64, 256, 16, 16, repeats=1, block_sizes=BS,
                           dtype=jnp.float32)
    assert set(recs) == {"identity", "reversed", "strided"}
    assert recs["identity"].extra["relative_time_vs_identity"] == 1.0
    assert all(r.n_devices == 8 for r in recs.values())


def test_run_record_jsonl(tmp_path):
    rec = RunRecord(
        config="t", backend="b", m=1, n=2, dk=3, dv=4, dtype="f32",
        best_us=1.0, median_us=2.0, gflops_per_chip=3.0, utilization=0.1,
        device_kind="cpu", n_devices=1,
    )
    path = str(tmp_path / "runs.jsonl")
    append_jsonl(path, rec)
    append_jsonl(path, rec)
    lines = open(path).read().strip().split("\n")
    assert len(lines) == 2
    parsed = json.loads(lines[0])
    assert parsed["backend"] == "b" and parsed["utilization"] == 0.1


def test_trace_and_span(tmp_path):
    logdir = str(tmp_path / "trace")
    with trace(logdir):
        with obs.span("bench.test.phase"):
            jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    # a trace produces at least one file under the log dir
    found = [f for _, _, fs in os.walk(logdir) for f in fs]
    assert found, "no trace output written"


def test_benchmark_amortized_positive():
    """Amortized slope timing returns a sane positive per-iteration time."""
    from attention_tpu.utils.timing import benchmark_amortized

    x = jnp.ones((256, 256), jnp.float32)
    per = benchmark_amortized(lambda a: a @ a / 256.0, x, repeats=2,
                              n_short=2, n_long=6)
    assert per > 0


@pytest.mark.parametrize("arm", ["headline", "engine"])
def test_bench_refuses_to_run_off_tpu(arm, capsys):
    """Both bench.py arms measure the chip: on the CPU backend they
    exit non-zero, name the platform they found, and print no record."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        rc = mod.main(["--arm", arm, "--seq", "256", "--dim", "64",
                       "--repeats", "1", "--serial-seq", "256"])
    finally:
        # main() placed the compile cache; keep the test process hermetic
        jax.config.update("jax_compilation_cache_dir", None)
    captured = capsys.readouterr()
    assert rc != 0
    assert "platform=cpu" in captured.err
    assert captured.out == ""


def test_blocksizes_for_shape_rules():
    """The measured tile lookup (round 4: one universal big tile under
    the raised VMEM budget): 4096x2048 for unwindowed long d<=128
    shapes regardless of heads, stepping down to keep padding bounded
    when the tile does not divide m; 2048x2048 for causal; 512x512 for
    windowed; general default elsewhere; explicit block_sizes= always
    wins (callers pass it through)."""
    from attention_tpu.ops.flash import BlockSizes

    assert BlockSizes.for_shape(1, 8192, 128) == BlockSizes(4096, 2048)
    assert BlockSizes.for_shape(32, 16384, 128) == BlockSizes(4096, 2048)
    assert BlockSizes.for_shape(1, 10240, 128) == BlockSizes(2048, 2048)
    assert BlockSizes.for_shape(1, 32768, 128, causal=True) == \
        BlockSizes(2048, 2048)
    assert BlockSizes.for_shape(1, 32768, 128, window=1024) == \
        BlockSizes(512, 512)
    assert BlockSizes.for_shape(1, 4096, 128) == BlockSizes()
    assert BlockSizes.for_shape(1, 8192, 256) == BlockSizes()
    assert BlockSizes.for_shape(4, 4096, 128, window=64) == BlockSizes()


def test_benchmark_auto_names_its_clock():
    """On CPU (no device trace lane) benchmark_auto uses the slope
    clock, returns a positive per-iteration time, and says so."""
    import jax.numpy as jnp

    from attention_tpu.utils.timing import benchmark_auto

    t = benchmark_auto(lambda x: x * 2.0, jnp.ones((64, 64)),
                       n_short=2, n_long=6, repeats=2)
    assert t > 0
    assert t.clock == "wall-slope"


def test_peak_flops_raises_off_the_table():
    """No published peak, no utilization: an unknown device is an
    error, and the benchmark rows report none instead of a made-up
    one."""
    from attention_tpu.utils.flops import UnknownDeviceError, peak_flops

    with pytest.raises(UnknownDeviceError, match="cpu"):
        peak_flops()


def test_device_module_seconds_missing_dir(tmp_path):
    from attention_tpu.utils.profiling import device_module_seconds

    assert device_module_seconds(str(tmp_path / "nope")) is None


def test_blocksizes_stats_and_backward_defaults():
    """Pin the tile-default rules: stats tiles share the universal big
    tile now that the VMEM budget is raised (the old 1024 cap was a
    budget artifact), and the backward defaults are window-aware."""
    import jax.numpy as jnp

    from attention_tpu.ops.flash import BlockSizes
    from attention_tpu.ops.flash_bwd import (
        default_bwd_block_sizes,
        default_fused_bwd_block_sizes,
    )

    assert BlockSizes.for_shape(16, 8192, 128, returns_stats=True) == \
        BlockSizes(4096, 2048)
    assert BlockSizes.for_shape(16, 8192, 128) == BlockSizes(4096, 2048)
    assert default_fused_bwd_block_sizes(128, jnp.bfloat16) == \
        BlockSizes(512, 4096)
    assert default_fused_bwd_block_sizes(128, jnp.bfloat16, 1024) == \
        BlockSizes(512, 512)
    assert default_bwd_block_sizes(128, jnp.bfloat16, None) == \
        BlockSizes(1024, 1024)
    assert default_bwd_block_sizes(128, jnp.float32, None) == \
        BlockSizes(512, 1024)
    assert default_bwd_block_sizes(128, jnp.bfloat16, 1024) == \
        BlockSizes(512, 512)
    assert default_bwd_block_sizes(256, jnp.bfloat16, None) == \
        BlockSizes(512, 512)
