"""What ISSUE 34 adds to the benchmark: the ``longcat-flash-omni``
configuration against its source, the runner that wraps
`serve_experts` for the latent attention's and the zero experts'
counts at toy size on the CPU, the four readers on hand-made events,
the arithmetic of the two count modules, and the cell's traffic."""

import collections
import json
import os

import pytest

from benchmark import gated_experts_flops, harness, mla_flops, run
from benchmark.reduce import trace
from benchmark.reduce.trace import Event

CELL = "longcat-flash-omni.docqa-closed"
BENCH = harness.load_benchmark()
DEV = "/device:TPU:0"
TOY = {
    "config": {
        "hidden_size": 64, "num_attention_heads": 8, "num_layers": 2,
        "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "ffn_hidden_size": 96,
        "expert_ffn_hidden_size": 48, "n_routed_experts": 4,
        "expert_share": {"index": 1, "of": 2}, "zero_expert_num": 4,
        "moe_topk": 3, "vocab_size": 512, "torch_dtype": "float32",
        "engine": {"num_pages": 40, "max_seq_len": 512,
                   "max_decode_batch": 3, "prefill_chunk": 32,
                   "token_budget": 35},
    },
    "traffic": {
        "arrivals": {"clients": 3}, "requests": 48,
        "prompt_tokens": {"min": 20, "max": 70},
        "output_tokens": {"min": 2, "max": 6},
        "shared_prefix": {"contexts": 2, "tokens": 256},
        # float32 at toy size: rounding only (the cell's own limit is
        # set from the chip's readings in bf16, PERF.md)
        "check": {"sample_requests": 3, "logit_gap_limit": 1e-3},
    },
}


def test_the_configuration_keeps_the_published_widths():
    cfg = harness.Cell(CELL).config
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "longcat-flash-omni")
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert set(entry["reduced"]) < set(cfg["reduced"])
    assert entry["source"] in cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"],
            cfg["moe_topk"], cfg["zero_expert_num"],
            cfg["routed_scaling_factor"], cfg["rope_theta"]) == (
                6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 12, 256, 6,
                10_000_000)
    assert cfg["attention_method"] == "MLA"
    assert cfg["mla_scale_q_lora"] is cfg["mla_scale_kv_lora"] is True
    # the share: 16 of 512 experts, an eighth of the vocabulary, 4 of 28
    assert cfg["expert_share"] == {"index": 0, "of": 32}
    assert cfg["n_routed_experts"] * 32 == cfg["published"][
        "n_routed_experts"] == 512
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert (cfg["num_layers"], cfg["published"]["num_layers"]) == (4, 28)
    assert cfg["torch_dtype"] == "bfloat16"
    for key in ("block", "attention", "experts", "torch_dtype", "weights"):
        assert cfg["assumed"][key]
    assert "not built" in cfg["omitted"]["encoders"]
    assert "32 chips share each layer" in cfg["reduced"]["deployment"]
    assert "T / 64" in cfg["reduced"]["n_routed_experts"]
    assert "640 lanes" in cfg["reduced"]["num_layers"]


def test_the_catalog_row_is_copied_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "LongCat-Flash-Omni")
    cfg = harness.Cell(CELL).config
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_layers", "n_routed_experts", "vocab_size"}
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "longcat-flash-omni")
    assert entry["source"] == row["source_url"]


def test_the_cell_is_the_issues():
    cell = harness.Cell(CELL)
    eng, traffic = cell.config["engine"], cell.traffic
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_per_s",
                                                    "setup_s"}
    assert (eng["max_decode_batch"], eng["max_prefill_rows"],
            eng["prefill_chunk"], eng["token_budget"], eng["page_size"]) == (
                32, 1, 256, 288, 128)
    assert traffic["arrivals"] == {"kind": "closed", "clients": 32}
    assert traffic["shared_prefix"] == {"contexts": 8, "tokens": 24576}
    assert (traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"],
            traffic["output_tokens"]["min"],
            traffic["output_tokens"]["max"]) == (128, 512, 64, 256)
    assert (traffic["requests"], traffic["rounds"], traffic["drain_seconds"],
            traffic["trace_seconds"]) == (128, 2, 30, 8)
    assert traffic["check"]["sample_requests"] == 8
    # every request at its longest, the documents' pages beside them
    longest = 24576 + 512 + 256
    assert eng["max_seq_len"] == longest
    shared = 8 * 24576 // eng["page_size"]
    own = -(-(512 + 256) // eng["page_size"]) + 1
    assert eng["num_pages"] > shared + 33 * own
    # the metrics the cell reports: the closed-loop serving ones, the
    # four this configuration brings and what `setup_s` is made of; a
    # later PR may list the cell under more, never under an `.open` one
    names = {m["name"] for m in cell.per_layer}
    assert {"kernel.mla_roofline", "kernel.gated_experts_roofline",
            "kernel.gated_experts_share_of_step.closed",
            "model.zero_expert_pair_share.closed",
            "engine.prefix_hit_share", "device.peak_hbm_share",
            "kernel.ragged_share_of_step.closed",
            "engine.expert_load_max_over_mean.closed",
            "startup.trace_s", "startup.lower_s", "startup.compile_s",
            "startup.cache_misses", "startup.programs",
            "startup.rest_s"} <= names
    assert not [n for n in names if n.endswith(".open")]


def test_the_step_shapes_the_cells_set_up_walks():
    """The ``(width, q_tile)`` programs of the cell, by the harness's
    own arithmetic over the program's rules: ONE KV head, so a group of
    64, and tiles of 1 (decode-only) and 8-256."""
    import types

    from attention_tpu.engine import EngineConfig
    from attention_tpu.models import decoder_from_config

    cell = harness.Cell(CELL)
    serve = harness.load_module("runners", "serve")
    engine = types.SimpleNamespace(
        config=EngineConfig(**cell.config["engine"]),
        model=decoder_from_config(cell.config))
    longest = {}
    for r in serve.chunk_sizes(cell.traffic, cell.config["engine"]):
        longest[serve.step_shape(engine, 0, r)[1]] = r
    seen = {serve.step_shape(engine, d, r)
            for d in range(engine.config.max_decode_batch + 1)
            for r in sorted(longest.values()) + [0] if d + r}
    assert sorted({t for _, t in seen}) == [1, 8, 16, 24, 32, 48, 64, 96,
                                            128, 192, 256]
    assert max(w for w, _ in seen) == 384
    assert len(seen) == 34, sorted(seen)


def test_the_references_statistic_is_a_requests_mean_gap():
    """What `compare_sample` keeps of a request under the name of the
    widest gap is, for this configuration, the mean over its served
    tokens (the reference's docstring says why), and the cell's limit
    holds one token far off and many a little off."""
    import numpy as np

    reference = harness.Cell(CELL).reference()
    limit = harness.Cell(CELL).traffic["check"]["logit_gap_limit"]
    assert 0 < limit < 0.01
    logits = np.zeros((200, 16))
    logits[:, 3] = 1.0                    # the reference's best
    tokens = np.full(200, 3)
    assert reference.widest_gap(logits, tokens) == 0.0
    tokens[[10, 150]] = 7                 # two routing flips, a gap of 1 each
    assert reference.token_gaps(logits, tokens).max() == 1.0
    assert reference.widest_gap(logits, tokens) == pytest.approx(1e-2)
    # every tenth token a little off, as a lower precision puts it
    tokens = np.where(np.arange(200) % 10 == 0, 7, 3)
    logits[:, 7] = 1.0 - 20 * limit
    assert reference.widest_gap(logits, tokens) > limit


def test_a_whole_run_of_the_new_runner_at_toy_size_is_correct(capsys):
    import jax

    runner = harness.load_module("runners", "serve_latent")
    cell = harness.Cell(CELL)
    line = json.loads(run.run_cell(
        cell, runner, seed=3_000_000_019, seconds=5.0, trace=False,
        devices=jax.devices()[:1], t_start=0.0, sizes=TOY))
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_per_s", "setup_s"}
    assert '"check": "compiles_in_window", "value": 0' in out
    assert "prefix_fill_steps 16" in out      # 2 documents x 8 chunks


def test_the_runner_reads_the_controls_at_toy_size():
    import jax

    runner = harness.load_module("runners", "serve_latent")
    cell = harness.Cell(CELL)
    (row,) = runner.control(cell, seeds=[3_000_000_029], seconds=2.0,
                            devices=jax.devices()[:1], sizes=TOY)
    assert row["requests"] == 3 and row["compiles_in_window"] == 0
    assert len(row["program.max"]) == len(row["control.fp8.mean"]) == 3
    assert max(row["program.max"]) <= 1e-3
    out = runner.left_out(cell, seed=3_000_000_031, length=200, rows=64,
                          sizes=TOY)
    assert set(out) >= {"fp8", "no_experts", "no_zero", "no_s_kv",
                        "branch_first", "routing_flips"}
    # a piece of the mathematics left out moves a best token at toy
    # size (where the branch lands moves none here: the logits
    # themselves are held by tests/test_shortcut_experts.py)
    for which in ("no_experts", "no_zero", "no_s_kv"):
        assert out[which]["max"] > 0
    assert out["branch_first"]["max"] >= 0
    flips = out["routing_flips"]
    assert flips["choices"] == 200 * 2            # two expert layers
    assert flips["flipped"] < 0.2 * flips["choices"]


def test_the_runner_sums_the_traced_steps_counts():
    runner = harness.load_module("runners", "serve_latent")
    Step = collections.namedtuple(
        "Step", runner.FIELDS + ("decode_tokens", "prefill_tokens",
                                 "expert_pairs_local", "expert_pairs_absent"))
    steps = [Step(9, 9, 9, 9, 9, 9, 9)] * 5 + [
        Step(100, 5000, 30, 4, 10, 20, 118), Step(110, 9000, 40, 5, 11, 30, 122),
        Step(120, 9500, 50, 6, 12, 40, 126), Step(1, 1, 1, 1, 1, 1, 1)]
    spans = harness.Spans()
    spans.records = [("bench.step", t, t + 0.5) for t in (1.0, 2.0, 3.0)]
    # 5 set-up steps, 3 in the window, 1 draining after it
    facts = {"traced_from": 1.9, "engine_steps": 4}
    assert runner.latent_work(steps, spans, facts) == {
        "steps": 2, "kv_pages": 230, "attn_qk_pairs": 18500,
        "expert_pairs_zero": 90, "tokens": 5 + 11 + 6 + 12,
        "expert_pairs": 90 + 30 + 122 + 40 + 126}
    assert runner.latent_work(steps, spans, {"traced_from": None}) is None
    # a program whose steps lack the fields (the parent of this PR)
    Old = collections.namedtuple("Old", "decode_tokens kv_pages")
    assert runner.latent_work([Old(1, 2)] * 9, spans, facts) is None


def _ctx(events, latent, experts, cell=CELL):
    return {"events": events, "planes": [DEV],
            "facts": {"latent": latent, "experts": experts},
            "cell": harness.Cell(cell), "peaks": harness.peaks("TPU v5 lite")}


def test_the_new_readers_on_hand_made_events(capsys):
    def reader(name):
        return harness.load_module("layer_metrics", name)

    events = [
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.0, 0.040),
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.050, 0.060),
        Event(DEV, trace.OPS, "%_ragged_paged_attention_jit.1 = bf16[1,20480,"
              "512] custom-call(...)", 0.001, 0.020),
        Event(DEV, trace.OPS, "%_ragged_paged_attention_jit.1 = bf16[1,20480,"
              "512] custom-call(...)", 0.051, 0.040),
        Event(DEV, trace.OPS, "%_gated_experts_gmm_jit.2 = f32[496,6144] "
              "custom-call(...)", 0.030, 0.004),
        Event(DEV, trace.OPS, "%_gated_experts_gmm_jit.2 = f32[3968,6144] "
              "custom-call(...)", 0.095, 0.006),
        Event(DEV, trace.OPS, "fusion.7", 0.035, 0.004),
    ]
    latent = {"steps": 2, "kv_pages": 6300 + 6500, "tokens": 32 + 288,
              "attn_qk_pairs": 32 * 25000 + 32 * 25000 + 256 * 24800,
              "expert_pairs_zero": 5000, "expert_pairs": 15360}
    experts = {"steps": 2, "expert_pairs_local": 320,
               "expert_pairs_absent": 10040, "expert_load_max": 40,
               "experts_reached": 90}
    ctx = _ctx(events, latent, experts)
    assert reader("kernel.gated_experts_share_of_step.closed").read(
        ctx) == pytest.approx(10.0)
    assert reader("model.zero_expert_pair_share.closed").read(
        ctx) == pytest.approx(100.0 * 5000 / 15360)
    # 8 sublayers; 2 x 64 x 320 operations a pair against the pages'
    # 576 values at 2 bytes and the rows in and out
    ops = 8 * 2 * 64 * 320 * latent["attn_qk_pairs"]
    nbytes = 8 * (12800 * 128 * 576 * 2 + 320 * 64 * 320 * 2)
    least = max(ops / 197e12, nbytes / 819e9)
    assert reader("kernel.mla_roofline").read(ctx) == pytest.approx(
        100.0 * least / 0.060)
    # 90 experts of 3 x 6144 x 2048 x 2 B + 320 pairs' rows
    nbytes = 90 * 3 * 6144 * 2048 * 2 + 320 * 2 * 6144 * 2
    assert reader("kernel.gated_experts_roofline").read(
        ctx) == pytest.approx(100.0 * nbytes / 819e9 / 0.010)
    out = capsys.readouterr().out
    assert out.count("the memory roof binds") == 2
    assert reader("engine.expert_load_max_over_mean.closed").read(
        ctx) == pytest.approx(40 * 16 / 320)
    # nothing to read: no counts (a program without the layers), no
    # kernel events, no pairs
    for name in ("kernel.mla_roofline", "kernel.gated_experts_roofline",
                 "model.zero_expert_pair_share.closed"):
        assert reader(name).read(_ctx(events, None, None)) is None
    bare = events[:2] + events[6:]
    assert reader("kernel.mla_roofline").read(
        _ctx(bare, latent, experts)) is None
    assert reader("kernel.gated_experts_roofline").read(
        _ctx(bare, latent, experts)) is None
    assert reader("kernel.gated_experts_share_of_step.closed").read(
        _ctx(events[2:], latent, experts)) is None
    assert reader("kernel.gated_experts_roofline").read(_ctx(
        events, latent, dict(experts, expert_pairs_local=0))) is None
    assert reader("model.zero_expert_pair_share.closed").read(_ctx(
        events, dict(latent, expert_pairs=0), experts)) is None
    assert reader("kernel.mla_roofline").read(_ctx(
        events, dict(latent, attn_qk_pairs=0), experts)) is None


def test_a_chunk_steps_attention_is_compute_bound_in_the_count():
    """A decode row's 25k keys are bytes; a chunk of 256 on 24.5k is
    operations, at the PUBLISHED form's count."""
    assert mla_flops.mla_flops(1000, 64, 128, 64, 128) == (
        2 * 64 * 320 * 1000)
    assert mla_flops.mla_bytes(10, 3, page=128, row=576, heads=64, nope=128,
                               rope=64, v=128, itemsize=2) == (
        10 * 128 * 576 * 2 + 3 * 64 * 320 * 2)
    decode_ops = mla_flops.mla_flops(32 * 25000, 64, 128, 64, 128)
    decode_bytes = mla_flops.mla_bytes(32 * 197, 32, page=128, row=576,
                                       heads=64, nope=128, rope=64, v=128,
                                       itemsize=2)
    assert decode_ops / 197e12 < decode_bytes / 819e9
    chunk_ops = mla_flops.mla_flops(256 * 24700, 64, 128, 64, 128)
    chunk_bytes = mla_flops.mla_bytes(194, 256, page=128, row=576, heads=64,
                                      nope=128, rope=64, v=128, itemsize=2)
    assert chunk_ops / 197e12 > 10 * chunk_bytes / 819e9


def test_gated_experts_flops_arithmetic():
    assert gated_experts_flops.gated_experts_flops(7, 4, 6) == 6 * 4 * 6 * 7
    assert gated_experts_flops.gated_experts_bytes(7, 3, 4, 6,
                                                   itemsize=2) == (
        3 * 3 * 4 * 6 * 2 + 7 * 2 * 4 * 2)
    # an expert nobody reached costs nothing; zero experts' pairs neither
    assert gated_experts_flops.gated_experts_bytes(
        0, 0, 6144, 2048, itemsize=2) == 0
    # half a pair an expert a decode step: the bytes are all weights
    weights = 8 * 3 * 6144 * 2048 * 2
    assert weights / gated_experts_flops.gated_experts_bytes(
        8, 8, 6144, 2048, itemsize=2) > 0.999


def test_the_reference_makes_bfloat16_leaves_and_a_float32_router():
    import jax
    import jax.numpy as jnp

    from attention_tpu.models import decoder_from_config

    cell = harness.Cell(CELL)
    config = {**cell.config, **{k: v for k, v in TOY["config"].items()
                                if k != "engine"}}
    reference = cell.reference()
    model = decoder_from_config(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.jit(lambda k: reference.init_params(shapes, k))(
        jax.random.PRNGKey(5))
    flat = {jax.tree_util.keystr(p): a
            for p, a in jax.tree_util.tree_flatten_with_path(params)[0]}
    wide = {n for n, a in flat.items() if a.dtype == jnp.float32}
    assert all(n.endswith("['router']") or n.endswith("['router_bias']")
               for n in wide) and len(wide) == 2 * 2
    assert {str(a.dtype) for n, a in flat.items() if n not in wide} == {
        "bfloat16"}
    block = params["ShortcutExpertsBlock_0"]
    # residual writers are a 1 / sqrt(140) below their fan-in's scale
    o = float(jnp.std(block["attn_0"]["o_proj"]["kernel"].astype(jnp.float32)))
    q = float(jnp.std(block["attn_0"]["q_a_proj"]["kernel"].astype(
        jnp.float32)))
    assert o == pytest.approx((8 * 16) ** -0.5 * 140 ** -0.5, rel=0.1)
    assert q == pytest.approx(64 ** -0.5, rel=0.1)
    assert float(jnp.std(params["Embed_0"]["embedding"].astype(
        jnp.float32))) == pytest.approx(1.0, rel=0.05)
    assert not block["experts"]["router_bias"].any()
