"""What ISSUE 41 adds to the benchmark: the ``deepseek-v3.2-exp``
configuration against its source, the runner that wraps `serve_latent`
for the selectors' and the sparse attention's counts at toy size on the
CPU (with the exact check of the keys attended), the five readers on
hand-made events, the arithmetic of the two count modules, and the
cell's traffic."""

import collections
import json
import os

import pytest

from benchmark import harness, index_flops, run, sparse_attn_flops
from benchmark.reduce import trace
from benchmark.reduce.trace import Event

CELL = "deepseek-v3.2-exp.longctx-closed"
BENCH = harness.load_benchmark()
DEV = "/device:TPU:0"
NEW = ("kernel.index_roofline", "kernel.index_share_of_step.closed",
       "kernel.select_share_of_step.closed", "kernel.sparse_attn_roofline",
       "model.selected_key_share.closed")
TOY = {
    "config": {
        "hidden_size": 64, "num_attention_heads": 8, "num_hidden_layers": 3,
        "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 4,
        "index_head_dim": 16, "index_topk": 64, "intermediate_size": 96,
        "moe_intermediate_size": 48, "n_routed_experts": 4,
        "expert_share": {"index": 1, "of": 4}, "num_experts_per_tok": 3,
        "n_group": 4, "topk_group": 2, "vocab_size": 512,
        "rope_scaling": {"original_max_position_embeddings": 64},
        "torch_dtype": "float32",
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
                 if c["name"] == "deepseek-v3.2-exp")
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "n_routed_experts", "vocab_size"]
    assert set(entry["reduced"]) < set(cfg["reduced"])
    assert entry["source"] in cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["index_n_heads"],
            cfg["index_head_dim"], cfg["index_topk"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["routed_scaling_factor"], cfg["rope_theta"],
            cfg["max_position_embeddings"]) == (
                7168, 128, 1536, 512, 128, 64, 128, 64, 128, 2048, 18432,
                2048, 8, 8, 4, 2.5, 10000, 163840)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the share: 16 of 256 experts, an eighth of the vocabulary, one
    # dense and four expert layers of 61
    assert cfg["expert_share"] == {"index": 0, "of": 16}
    assert cfg["n_routed_experts"] * 16 == cfg["published"][
        "n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (5, 1)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["first_k_dense_replace"]) == (61, 3)
    assert cfg["torch_dtype"] == "bfloat16"
    # the accepted reader of the experts' roofline takes the width
    # under another configuration's name for it
    assert cfg["expert_ffn_hidden_size"] == cfg["moe_intermediate_size"]
    for key in ("block", "attention", "indexer", "experts",
                "expert_ffn_hidden_size", "torch_dtype", "weights"):
        assert cfg["assumed"][key]
    for key in ("mtp", "fp8"):
        assert cfg["omitted"][key]
    assert "16 chips share each layer" in cfg["reduced"]["deployment"]
    assert "T / 32" in cfg["reduced"]["n_routed_experts"]
    assert "1,536 B a layer" in cfg["reduced"]["num_hidden_layers"]


def test_the_catalog_row_is_copied_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "DeepSeek-V3.2-Exp")
    cfg = harness.Cell(CELL).config
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size"}
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "deepseek-v3.2-exp")
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
    assert traffic["shared_prefix"] == {"contexts": 4, "tokens": 49152}
    assert (traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"],
            traffic["output_tokens"]["min"],
            traffic["output_tokens"]["max"]) == (128, 512, 128, 512)
    # a round is 64 requests, each document asked 16 times, three
    # rounds one after another: ISSUE 41's traffic as it gives it
    assert (traffic["requests"], traffic["rounds"], traffic["drain_seconds"],
            traffic["trace_seconds"]) == (64, 3, 30, 8)
    # every request at its longest, the documents' pages beside them
    longest = 49152 + 512 + 512
    assert eng["max_seq_len"] == longest == 392 * eng["page_size"]
    shared = 4 * 49152 // eng["page_size"]
    own = -(-(512 + 512) // eng["page_size"])
    assert eng["num_pages"] > shared + 33 * own
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "kernel.gated_experts_roofline",
        "kernel.gated_experts_share_of_step.closed",
        "engine.prefix_hit_share", "device.peak_hbm_share",
        "kernel.ragged_share_of_step.closed",
        "engine.expert_load_max_over_mean.closed",
        "startup.trace_s", "startup.lower_s", "startup.compile_s",
        "startup.cache_misses", "startup.programs",
        "startup.rest_s"} <= names
    # it counts every causal pair and 2 x num_layers sublayers; the
    # layers route over no zero-compute expert
    assert not {"kernel.mla_roofline",
                "model.zero_expert_pair_share.closed"} & names
    assert not [n for n in names if n.endswith(".open")]
    for name in NEW:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "out_tok_per_s" and entry["unit"] == "%"


def test_the_step_shapes_the_cells_set_up_walks():
    """ONE KV head, so a group of 128: 8 tokens of it are a whole
    block of the row-blocked form, the tile only bounds a span's
    blocks and is never under 256 (`recommended_q_tile`): 7 programs
    where docqa's group of 64 has 34, and the set-up's walk reaches
    every shape the window can."""
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
    assert sorted(seen) == [(8, 1), (16, 1), (24, 1), (32, 1), (48, 1),
                            (256, 256), (384, 256)]
    chunk = engine.config.prefill_chunk
    assert seen == {serve.step_shape(engine, d, r)
                    for d in range(engine.config.max_decode_batch + 1)
                    for r in range(chunk + 1) if d + r}


def test_a_whole_run_of_the_new_runner_at_toy_size_is_correct(capsys):
    """Documents of 256 tokens and ``index_topk`` 64: every question
    and answer row chooses 64 of 276 or more keys."""
    import jax

    runner = harness.load_module("runners", "serve_sparse")
    cell = harness.Cell(CELL)
    line = json.loads(run.run_cell(
        cell, runner, seed=3_000_000_019, seconds=5.0, trace=False,
        devices=jax.devices()[:1], t_start=0.0, sizes=TOY))
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_per_s", "setup_s"}
    assert line["compared"]["keys_attended_off_rule"] == {
        "value": 0, "limit": 0}
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert "prefix_fill_steps 16" in out      # 2 documents x 8 chunks
    assert " 0 off the rule's count (3 sublayers" in out


def test_the_runner_reads_both_controls_at_toy_size():
    import jax

    runner = harness.load_module("runners", "serve_sparse")
    cell = harness.Cell(CELL)
    sizes = {"config": TOY["config"], "traffic": dict(
        TOY["traffic"], check=dict(TOY["traffic"]["check"],
                                   control_requests=2))}
    (row,) = runner.control(cell, seeds=[3_000_000_029], seconds=2.0,
                            devices=jax.devices()[:1], sizes=sizes)
    assert row["requests"] == 2 and row["compiles_in_window"] == 0
    assert (len(row["program.max"]) == len(row["control.fp8.mean"])
            == len(row["control.newest.mean"]) == 2)
    assert max(row["program.max"]) <= 1e-3
    assert min(row["control.fp8.mean"]) >= 0


def test_the_runner_sums_the_traced_steps_counts():
    runner = harness.load_module("runners", "serve_sparse")
    Step = collections.namedtuple(
        "Step", runner.FIELDS + ("decode_tokens", "prefill_tokens",
                                 "num_decode_reqs", "num_prefill_reqs"))
    steps = [Step(9, 9, 9, 9, 9, 9, 9, 9)] * 5 + [
        Step(100, 5000, 30, 6, 10, 20, 10, 1),
        Step(12480, 1_590_000, 327_680, 65_536, 32, 0, 32, 0),
        Step(12870, 14_000_000, 2_949_120, 589_824, 32, 256, 32, 1),
        Step(1, 1, 1, 1, 1, 1, 1, 1)]
    spans = harness.Spans()
    spans.records = [("bench.step", t, t + 0.5) for t in (1.0, 2.0, 3.0)]
    # 5 set-up steps, 3 in the window, 1 draining after it
    facts = {"traced_from": 1.9, "engine_steps": 4}
    config = {"index_topk": 2048, "num_hidden_layers": 5,
              "engine": {"page_size": 128}}
    assert runner.sparse_work(steps, spans, facts, config) == {
        "steps": 2, "sublayers": 5, "kv_pages": 12480 + 12870,
        "attn_qk_pairs": 15_590_000, "attn_keys_attended": 3_276_800,
        "attn_keys_selected": 655_360, "tokens": 32 + 32 + 256,
        "kept_rows": 2048 * 32 + 2048 * 33}
    # short contexts: the pages' rows, not index_topk a slot
    facts = {"traced_from": 0.5, "engine_steps": 4}
    assert runner.sparse_work(steps, spans, facts, config)[
        "kept_rows"] == 100 * 128 + 2048 * 65
    assert runner.sparse_work(steps, spans, {"traced_from": None},
                              config) is None
    # a program whose steps lack the fields (the parent of this PR)
    Old = collections.namedtuple("Old", "decode_tokens kv_pages")
    assert runner.sparse_work([Old(1, 2)] * 9, spans, facts, config) is None


def _ctx(events, sparse, cell=CELL):
    return {"events": events, "planes": [DEV], "facts": {"sparse": sparse},
            "cell": harness.Cell(cell), "peaks": harness.peaks("TPU v5 lite")}


# an operation's text in the device trace names its operands: the
# selection's holds the scores' name and the attention kernel's the
# selection's (the chip's first traced run of the cell, PR 41, read
# ``index_scores`` at the time of both kernels for it), so the readers
# match the name a text STARTS with
SELECT_OP = ("%index_select.1 = f32[65,8,50176] custom-call(s32[1] "
             "%reshape.9, s32[65,8,128] %broadcast.3, f32[65,8,50176] "
             "%index_scores.1)")
ATTEND_OP = ("%_ragged_paged_attention_jit.1 = bf16[1,4224,512] "
             "custom-call(s32[33] %get.1, bf16[1,36864,640] %reshape.12, "
             "f32[65,8,50176] %index_select.1)")


def test_the_new_readers_on_hand_made_events(capsys):
    def reader(name):
        return harness.load_module("layer_metrics", name)

    events = [
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.0, 0.100),
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.110, 0.100),
        Event(DEV, trace.OPS, "%index_scores.1 = f32[65,8,50176] "
              "custom-call(...)", 0.001, 0.010),
        Event(DEV, trace.OPS, SELECT_OP, 0.012, 0.006),
        Event(DEV, trace.OPS, ATTEND_OP, 0.020, 0.050),
        Event(DEV, trace.OPS, "%index_scores.1 = f32[65,8,50176] "
              "custom-call(...)", 0.111, 0.010),
        Event(DEV, trace.OPS, SELECT_OP, 0.122, 0.006),
        Event(DEV, trace.OPS, ATTEND_OP, 0.130, 0.050),
        Event(DEV, trace.OPS, "fusion.7", 0.185, 0.004),
    ]
    pairs = 2 * 32 * 49_700
    sparse = {"steps": 2, "sublayers": 5, "kv_pages": 2 * 32 * 389,
              "tokens": 64, "attn_qk_pairs": pairs,
              "attn_keys_attended": 5 * 64 * 2048,
              "attn_keys_selected": 64 * 2048, "kept_rows": 64 * 2048}
    ctx = _ctx(events, sparse)
    assert reader("kernel.index_share_of_step.closed").read(
        ctx) == pytest.approx(10.0)
    assert reader("kernel.select_share_of_step.closed").read(
        ctx) == pytest.approx(6.0)
    assert reader("model.selected_key_share.closed").read(
        ctx) == pytest.approx(100.0 * 2048 / 49_700)
    # scoring: 2 x 64 x 128 operations a pair against the index pages'
    # 128 values at 2 bytes and the rows' queries and weights
    ops = 5 * 2 * 64 * 128 * pairs
    nbytes = 5 * (sparse["kv_pages"] * 128 * 128 * 2
                  + 64 * 64 * (128 * 2 + 4))
    assert nbytes / 819e9 > ops / 197e12
    assert reader("kernel.index_roofline").read(ctx) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.020)
    # attention: 2 x 128 x 320 operations an ATTENDED pair against
    # 2,048 latent rows of 576 values a slot and the rows in and out
    ops = 2 * 128 * 320 * sparse["attn_keys_attended"]
    nbytes = 5 * (64 * 2048 * 576 * 2 + 64 * 128 * 320 * 2)
    least = max(ops / 197e12, nbytes / 819e9)
    assert reader("kernel.sparse_attn_roofline").read(
        ctx) == pytest.approx(100.0 * least / 0.100)
    assert 0 < 100.0 * least / 0.100 < 5
    out = capsys.readouterr().out
    assert out.count("the memory roof binds") == 2
    # nothing to read: no counts (the parent of this PR), no kernel
    # events, no pairs
    for name in ("kernel.index_roofline", "kernel.sparse_attn_roofline",
                 "model.selected_key_share.closed"):
        assert reader(name).read(_ctx(events, None)) is None
    bare = events[:2] + events[8:]
    assert reader("kernel.index_roofline").read(_ctx(bare, sparse)) is None
    assert reader("kernel.sparse_attn_roofline").read(
        _ctx(bare, sparse)) is None
    assert reader("kernel.index_share_of_step.closed").read(
        _ctx(events[2:], sparse)) is None
    assert reader("kernel.select_share_of_step.closed").read(
        _ctx(bare, sparse)) == 0.0
    assert reader("kernel.sparse_attn_roofline").read(_ctx(
        events, dict(sparse, attn_keys_attended=0))) is None
    assert reader("model.selected_key_share.closed").read(_ctx(
        events, dict(sparse, attn_qk_pairs=0))) is None


def test_the_count_modules_arithmetic():
    assert index_flops.index_flops(1000, 64, 128) == 2 * 64 * 128 * 1000
    assert index_flops.index_bytes(10, 3, page=128, heads=64, dim=128,
                                   itemsize=2) == (
        10 * 128 * 128 * 2 + 3 * 64 * (128 * 2 + 4))
    assert sparse_attn_flops.sparse_attn_flops(1000, 128, 128, 64, 128) == (
        2 * 128 * 320 * 1000)
    assert sparse_attn_flops.sparse_attn_bytes(
        2048, 1, row=576, heads=128, nope=128, rope=64, v=128,
        itemsize=2) == 2048 * 576 * 2 + 128 * 320 * 2
    # a decode row's scoring is bytes (12.7 MB of index keys a layer),
    # a chunk's is operations
    row = index_flops.index_bytes(389, 1, page=128, heads=64, dim=128,
                                  itemsize=2)
    assert 12.6e6 < row < 12.9e6
    assert index_flops.index_flops(49_700, 64, 128) / 197e12 < row / 819e9
    assert (index_flops.index_flops(256 * 49_500, 64, 128) / 197e12
            > index_flops.index_bytes(389, 256, page=128, heads=64, dim=128,
                                      itemsize=2) / 819e9)


def test_the_reference_makes_bfloat16_leaves_and_a_float32_router():
    import jax
    import jax.numpy as jnp

    from attention_tpu.models import decoder_from_config

    cell = harness.Cell(CELL)
    config = harness.load_module("runners", "serve_sparse").merged(
        cell.config, {k: v for k, v in TOY["config"].items()
                      if k != "engine"})
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
    attn = params["LatentBlock_1"]["attn"]
    assert not attn["index_k_norm"]["bias"].any()
    # residual writers are a 1 / sqrt(122) below their fan-in's scale
    o = float(jnp.std(attn["o_proj"]["kernel"].astype(jnp.float32)))
    q = float(jnp.std(attn["q_a_proj"]["kernel"].astype(jnp.float32)))
    assert o == pytest.approx((8 * 16) ** -0.5 * 122 ** -0.5, rel=0.1)
    assert q == pytest.approx(64 ** -0.5, rel=0.1)


def test_the_references_bands_change_nothing():
    """A block of query rows against the keys up to its BAND's end
    (one compiled body a band) gives what a block against the keys up
    to its own end gives: the selection bit for bit, attention to
    rounding; the last, shorter block is a band of its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cell = harness.Cell(CELL)
    REFERENCE = cell.reference()
    assert REFERENCE._bands(50176, 1024, 7) == [
        (a, 7, 1024) for a in range(0, 50176, 7168)]
    assert REFERENCE._bands(100, 16, 4) == [(0, 4, 16), (64, 2, 16),
                                            (96, 1, 4)]
    seq, dim = 96, 24
    rng = np.random.default_rng(5)
    sizes = dict(REFERENCE._sizes(dict(cell.config, index_topk=8), seq),
                 index_heads=2, index_dim=16, rope=8)
    p = {"index_k_proj": {"kernel": rng.standard_normal((dim, 16))},
         "index_k_norm": {"scale": np.ones(16), "bias": np.zeros(16)},
         "index_w_proj": {"kernel": rng.standard_normal((dim, 2))},
         "index_q_proj": {"kernel": rng.standard_normal((12, 32))}}
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)
    x = jnp.asarray(rng.standard_normal((seq, dim)), jnp.float32)
    c_q = jnp.asarray(rng.standard_normal((seq, 12)), jnp.float32)
    q, k, v = (jnp.asarray(rng.standard_normal((seq, 8)), jnp.float32)
               for _ in range(3))

    def run(block, band, positions=False):
        bits = REFERENCE._selection(
            p, x, c_q, sizes=dict(sizes, index_block=block, band=band),
            quant=lambda a: a, left_out=None, positions=positions)
        if positions:
            return np.asarray(bits)
        return np.asarray(bits), np.asarray(REFERENCE._attend(
            q, k, v, bits, 0.3, block, band))

    whole_bits, whole = run(seq, 1)
    kept = np.asarray(REFERENCE._unpack(jnp.asarray(whole_bits)))
    assert (kept.sum(axis=1) == np.minimum(np.arange(seq) + 1, 8)).all()
    for block, band in ((16, 1), (16, 4), (32, 2), (40, 2)):
        bits, out = run(block, band)
        assert (bits == whole_bits).all(), (block, band)
        np.testing.assert_allclose(out, whole, atol=1e-6)
        # the same choice as POSITIONS (the gathered form's), -1 where
        # a row sees fewer than 8 keys
        at = run(block, band, positions=True)
        assert at.shape == (seq, 8)
        for t in range(seq):
            assert sorted(at[t][at[t] >= 0]) == list(np.flatnonzero(kept[t]))


def test_the_rule_as_positions_breaks_ties_as_the_mask_does():
    """Scores with many equal values at the k-th place: `_chosen`
    (`jax.lax.top_k`, the lower index first among equals) keeps the
    keys `_kept` keeps."""
    import jax.numpy as jnp
    import numpy as np

    REFERENCE = harness.Cell(CELL).reference()
    rng = np.random.default_rng(9)
    scores = jnp.asarray(rng.integers(0, 4, size=(24, 40)), jnp.float32)
    for first in (0, 16):
        kept = np.asarray(REFERENCE._kept(scores, first, 6))
        at = np.asarray(REFERENCE._chosen(scores, first, 6))
        for i in range(24):
            assert sorted(at[i][at[i] >= 0]) == list(np.flatnonzero(kept[i]))
            assert (at[i] >= 0).sum() == min(6, first + i + 1)

