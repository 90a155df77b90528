"""Test environment: a virtual 8-device CPU mesh.

The reference tests its distributed path by launching the same binary at
varying `mpirun -np` counts on a real cluster (README.md:136-142); it has
no fake backend.  We do have one: XLA's forced host-device count gives
eight CPU "chips", so every mesh/collective path (kv-sharded, ring,
ulysses) runs in CI without TPU hardware.  Pallas kernels run in
interpreter mode on CPU (selected automatically in ops.flash).

These env vars must be set before jax is imported anywhere.
"""

import os

# Force CPU even if the outer environment points JAX at a TPU: unit tests
# must be hermetic and exercise the 8-device virtual mesh.  Set
# ATTN_TPU_TEST_PLATFORM to override (e.g. to smoke-test on real TPU).
# A plugin may have imported jax before this file runs, so the env vars
# alone are not enough — jax.config is updated too.
_platform = os.environ.get("ATTN_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Hermetic tile resolution: a developer's real ~/.cache tuning entries
# must not leak into unit-test kernel dispatch (the golden tests pin
# the heuristic tiles byte-for-byte).  Tests that exercise cache pickup
# monkeypatch ATTN_TPU_TUNING_CACHE to their own tmp file.
if "ATTN_TPU_TUNING_CACHE" not in os.environ:
    import tempfile as _tempfile

    os.environ["ATTN_TPU_TUNING_CACHE"] = os.path.join(
        _tempfile.mkdtemp(prefix="attn_tpu_test_tuning_"), "cache.json"
    )

# Hermetic compiles: entry points under test place the persistent
# compilation cache (utils.runtime.configure_compile_cache); the test
# process must neither read nor fill one.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The fast round-gate tier (`pytest -m smoke`): one or two representative
# tests per kernel / distributed / serving family, <=5 min on a 1-core
# host (the full suite is ~35-40 min there — README "Testing").  Keys are
# test modules, values are test-function names (bare name = every
# parametrization; "name[param]" = that case only).  Deliberately NOT in
# the tier: multi-process crash/multihost tests and exhaustive feature
# matrices (too slow), test_graft_entry (the driver compile-checks the
# entry separately every round), test_sampling/test_properties (pure-math
# helpers already transitively exercised by the generate/kernel entries),
# and duplicate per-family variants (e.g. q_sharded rides kv_sharded's
# plumbing) — each cut bought the <=5 min budget.
SMOKE_TESTS = {
    "test_core": ["test_oracle_matches_scalar_loops",
                  "test_testcase_roundtrip", "test_verify_tolerance"],
    "test_native_cli": ["test_native_matches_numpy_oracle",
                        "test_cli_end_to_end"],
    "test_ops": ["test_flash_causal", "test_flash_mha_gqa",
                 "test_bound_mode_matches_online[causal]",
                 "test_bound_mode_matches_online[full]",
                 "test_bound_mode_underflow_demotes"],
    "test_vjp": ["test_grads_match_dense_causal", "test_grads_gqa_3d"],
    "test_flash_bwd": ["test_pallas_matches_xla_backward_causal",
                       "test_fused_and_two_kernel_paths_agree"],
    "test_decode": ["test_flash_decode_matches_oracle_ragged",
                    "test_flash_decode_chunk_equals_sequential_decode",
                    "test_cached_decode_matches_full_forward"],
    "test_engine": ["test_engine_token_parity_prefix_and_mixed_batching"],
    "test_frontend": ["test_routing_affinity_keeps_prefix_hit_rate"],
    "test_quant": ["test_quantized_decode_close_to_fp",
                   "test_quantized_chunk_equals_sequential_decode"],
    "test_paged": ["test_paged_decode_matches_dense",
                   "test_paged_chunk_equals_sequential_decode"],
    "test_ragged": ["test_ragged_equal_lengths_match_plain_generate"],
    "test_window": ["test_window_forward_matches_oracle"],
    "test_sinks": ["test_sinks_forward_matches_oracle"],
    "test_softcap": ["test_softcap_forward_matches_oracle"],
    "test_segments": ["test_segmented_forward_matches_oracle"],
    "test_rope": ["test_rope_cached_decode_matches_full_forward"],
    "test_parallel": ["test_kv_sharded_matches_oracle",
                      "test_ring_matches_oracle",
                      "test_ulysses_matches_oracle"],
    "test_cp": ["test_cp_matches_single_device[True-None]",
                "test_ring_diff_matches_single_device[True-None]"],
    "test_models": ["test_sharded_training_step_decreases_loss"],
    "test_moe": ["test_moe_matches_per_token_reference"],
    "test_pipeline": ["test_pipeline_matches_sequential"],
    "test_serving": ["test_head_sharded_matches_single_device"],
    "test_tp_serving": ["test_tp_generate_matches_single_device"],
    "test_speculative": ["test_speculative_matches_greedy_random_draft[3]"],
    "test_beam": ["test_beam_one_equals_greedy"],
    "test_seq2seq": ["test_seq2seq_flash_matches_xla_impl"],
    "test_cross_attention": ["test_cross_attention_matches_manual_oracle"],
    "test_checkpoint": ["test_checkpoint_roundtrip_resumes_training"],
    "test_benchmarks": ["test_blocksizes_for_shape_rules"],
    "test_tuning": [
        "test_golden_empty_cache_matches_heuristics_all_entry_points",
        "test_cache_entry_overrides_for_shape_and_decode",
        "test_shipped_table_passes_lint",
    ],
    "test_prefixstore": ["test_engine_export_then_import_parity"],
    # test_graft_entry is NOT in the smoke tier: the driver
    # compile-checks the entry separately every round anyway
}


def pytest_configure(config):
    # the telemetry tier (tests/test_obs.py): registered here beside
    # the smoke plumbing so `pytest -m obs` selects it without warnings
    config.addinivalue_line(
        "markers",
        "obs: unified telemetry subsystem (attention_tpu/obs/) — "
        "registry, spans, exporters, merged timeline; CPU-only, "
        "tier-1 fast",
    )
    # the chaos tier (tests/test_chaos.py): fuzz smoke campaigns stay
    # tier-1 (<=~30 s CPU); long campaigns also carry `slow` and are
    # excluded by tier-1's `-m 'not slow'`
    config.addinivalue_line(
        "markers",
        "chaos: differential fuzzing + fault injection "
        "(attention_tpu/chaos/) — seeded fuzz/fault campaigns, "
        "shrinker, invariant checkers; CPU-only",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running campaigns/sweeps excluded from tier-1",
    )
    # the resilient-serving tier (tests/test_frontend.py): multi-
    # replica router, deadlines, retry, shedding, degradation, and
    # the replica-kill chaos storm; CPU-only, tier-1 fast
    config.addinivalue_line(
        "markers",
        "frontend: resilient multi-replica serving front end "
        "(attention_tpu/frontend/) — routing, deadlines, retry-with-"
        "backoff, load shedding, degradation ladder; CPU-only",
    )
    # the disaggregation tier (tests/test_fleet.py): role-typed
    # pools, KV-page handoffs, the closed-loop autoscaler, and the
    # disagg chaos storm; CPU-only, tier-1 fast except the broad
    # sweep (also carries slow)
    config.addinivalue_line(
        "markers",
        "fleet: disaggregated prefill/decode serving "
        "(attention_tpu/fleet/) — role pools, KV handoff records, "
        "elastic autoscaler, actuation-ledger invariant; CPU-only",
    )
    # the static-analysis tier (tests/test_analysis.py): AST passes,
    # baseline round-trips, and the tree-wide-clean gate; jax-free
    # and CPU-fast, tier-1
    config.addinivalue_line(
        "markers",
        "analysis: static-analysis framework (attention_tpu/analysis/) "
        "— ATP### passes, suppressions, baseline, renderers; tier-1 "
        "fast",
    )
    # the durability tier (tests/test_snapshot.py): checksummed atomic
    # snapshots, write-ahead journal, warm recovery; CPU-only and
    # tier-1 fast except the crash-storm sweep (also carries slow)
    config.addinivalue_line(
        "markers",
        "snapshot: crash-consistent durability (attention_tpu/engine/"
        "snapshot.py + journal.py) — save/restore round trips, "
        "corruption table, journal replay, warm recovery parity; "
        "CPU-only",
    )
    # the gray-failure tier (tests/test_supervisor.py): supervisor
    # state machine, live migration, standby promotion, gray storms;
    # CPU-only and tier-1 fast except the broad sweep (also slow)
    config.addinivalue_line(
        "markers",
        "supervisor: gray-failure detection + live migration "
        "(attention_tpu/frontend/supervisor.py + migrate.py) — "
        "hysteresis state machine, drain parity, warm-standby "
        "promotion, gray-storm campaigns; CPU-only",
    )
    # the fleet prefix tier (tests/test_prefixstore.py): content-
    # addressed KV record round trips, engine export/import parity,
    # single-flight storms, lease lifecycle, store persistence;
    # CPU-only and tier-1 fast except the storm sweep (also slow)
    config.addinivalue_line(
        "markers",
        "prefixstore: global prefix-cache tier (attention_tpu/"
        "prefixstore/) — content-addressed KV records, engine export/"
        "import parity, single-flight de-dup leases, store "
        "persistence; CPU-only",
    )


def pytest_collection_modifyitems(config, items):
    matched: dict[tuple[str, str], bool] = {}
    collected_mods = set()
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        collected_mods.add(mod)
        names = SMOKE_TESTS.get(mod)
        if not names:
            continue
        # entries may name a bare function (all parametrizations) or a
        # single "name[param]" case
        for name in (item.name, item.name.split("[", 1)[0]):
            if name in names:
                item.add_marker(pytest.mark.smoke)
                matched[(mod, name)] = True
                break
    # An entry matching zero collected items means the smoke tier
    # silently shrank (renamed test, reordered parametrize ids) —
    # fail collection loudly instead.  Only validate modules that were
    # actually collected (single-file runs stay usable), and skip when
    # the invocation selects individual nodes or keywords (those
    # legitimately collect a subset of a module).
    if (any("::" in str(a) for a in config.args)
            or config.getoption("keyword", "")
            or config.getoption("deselect", None)):
        return
    stale = [
        f"{mod}::{name}"
        for mod, names in SMOKE_TESTS.items()
        if mod in collected_mods
        for name in names
        if not matched.get((mod, name))
    ]
    if stale:
        raise pytest.UsageError(
            f"SMOKE_TESTS entries match no collected test: {stale}"
        )


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs_between_modules():
    """Free each module's compiled XLA programs when it finishes.

    One pytest process compiles thousands of XLA:CPU executables across
    the suite; each holds mmapped code, and the accumulation can exhaust
    the kernel's per-process mapping budget (vm.max_map_count, default
    65530) — observed as a deterministic SIGSEGV inside
    ``backend_compile_and_load`` once the suite grew past ~370 tests.
    Modules share almost no jitted functions, so clearing between
    modules costs little recompilation and keeps the map count flat.
    """
    yield
    jax.clear_caches()
