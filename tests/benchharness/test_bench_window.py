"""What ISSUE 43 adds to the benchmark: the ``trinity-mini``
configuration against its source, the runner that wraps `serve_experts`
for the two page spaces' counts at toy size on the CPU, the three
readers on hand-made events, the arithmetic of the count module, and
the cell's traffic."""

import collections
import functools
import json
import os
import types

import pytest

from benchmark import harness, paged_attn_flops, run
from benchmark.reduce import trace
from benchmark.reduce.trace import Event

CELL = "trinity-mini.sessions-closed"
BENCH = harness.load_benchmark()
DEV = "/device:TPU:0"
NEW = ("model.window_key_share.closed",
       "engine.window_pages_live_share.closed", "kernel.paged_attn_roofline")
S, F = "sliding_attention", "full_attention"
TOY = {
    "config": {
        "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
        "num_hidden_layers": 5, "layer_types": [S, S, S, F] * 3,
        "served_layers": [0, 4, 5, 6, 7], "num_dense_layers": 1,
        "num_experts": 4, "expert_share": {"index": 1, "of": 4},
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "intermediate_size": 96, "sliding_window": 160,
        "torch_dtype": "float32",
        "engine": {"num_pages": 64, "num_window_pages": 28,
                   "max_seq_len": 768, "max_decode_batch": 3,
                   "prefill_chunk": 64, "token_budget": 67,
                   "min_prefill_tile": 64},
    },
    "traffic": {
        "arrivals": {"clients": 3}, "requests": 24,
        "prompt_tokens": {"min": 20, "max": 70},
        "output_tokens": {"min": 2, "max": 6},
        "shared_prefix": {"contexts": 3, "tokens": 640},
        # float32 at toy size: rounding only (the cell's own limit is
        # set from the chip's readings in bf16, PERF.md)
        "check": {"sample_requests": 3, "control_requests": 1,
                  "logit_gap_limit": 1e-3},
    },
}


def test_the_configuration_keeps_the_published_widths():
    cfg = harness.Cell(CELL).config
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts", "vocab_size"]
    assert set(entry["reduced"]) < set(cfg["reduced"])
    assert entry["source"] in cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["route_scale"],
            cfg["sliding_window"], cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["max_position_embeddings"]) == (
                2048, 32, 4, 128, 6144, 1024, 8, 2.826, 2048, 10000, 1e-5,
                131072)
    # the published list whole, 3 : 1, and the layers served of it
    assert cfg["layer_types"] == [S, S, S, F] * 8
    assert cfg["served_layers"] == [0, 4, 5, 6, 7, 8, 9, 10, 11]
    kinds = [cfg["layer_types"][i] for i in cfg["served_layers"]]
    assert (kinds.count(S), kinds.count(F)) == (7, 2)
    # the share: 16 of 128 experts, an eighth of the vocabulary, one
    # dense and eight expert layers of 32
    assert cfg["expert_share"] == {"index": 0, "of": 8}
    assert cfg["num_experts"] * 8 == cfg["published"]["num_experts"] == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (9, 1)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["num_dense_layers"]) == (32, 2)
    assert cfg["torch_dtype"] == "bfloat16"
    # the accepted reader of the experts' roofline takes the width
    # under another configuration's name for it
    assert cfg["expert_ffn_hidden_size"] == cfg["moe_intermediate_size"]
    # what no key of config.json states, each with its alternative
    assert (cfg["qk_head_norm"], cfg["attention_gate"], cfg["sandwich_norm"],
            cfg["full_attention_rotary"]) == (True, True, True, False)
    for key in ("block", "attention", "embedding", "experts",
                "served_layers", "expert_ffn_hidden_size", "torch_dtype",
                "weights"):
        assert cfg["assumed"][key]
    for key in ("block", "attention", "embedding", "served_layers"):
        assert "exclude" in cfg["assumed"][key]
    assert "8 chips share each layer" in cfg["reduced"]["deployment"]
    assert "T / 16" in cfg["reduced"]["num_experts"]
    assert "262,144 B a layer" in cfg["reduced"]["num_hidden_layers"]


def test_the_catalog_row_is_copied_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Trinity-Mini")
    cfg = harness.Cell(CELL).config
    changed = {k for k, v in row["config"].items()
               if cfg.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "num_dense_layers",
                       "num_experts", "vocab_size"}
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini")
    assert entry["source"] == row["source_url"]


def test_the_cell_is_the_issues():
    cell = harness.Cell(CELL)
    eng, traffic = cell.config["engine"], cell.traffic
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_per_s",
                                                    "setup_s"}
    assert (eng["max_decode_batch"], eng["max_prefill_rows"],
            eng["prefill_chunk"], eng["token_budget"], eng["page_size"],
            eng["min_prefill_tile"]) == (32, 1, 256, 288, 128, 256)
    assert traffic["generator"] == "requests"
    assert traffic["arrivals"] == {"kind": "closed", "clients": 32}
    assert traffic["shared_prefix"] == {"contexts": 32, "tokens": 32768}
    assert (traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"],
            traffic["output_tokens"]["min"],
            traffic["output_tokens"]["max"]) == (128, 512, 128, 512)
    # a round is 64 requests, every session asked twice, three rounds
    assert (traffic["requests"], traffic["rounds"], traffic["drain_seconds"],
            traffic["trace_seconds"]) == (64, 3, 30, 8)
    longest = 32768 + 512 + 512
    assert eng["max_seq_len"] == longest == 264 * eng["page_size"]
    # the full layers' space holds every history and every request at
    # its longest; the window layers' the cached tails and the
    # requests' own pages, under a fifth of what one space would give
    shared = 32 * 32768 // eng["page_size"]
    own = -(-(512 + 512) // eng["page_size"]) + 1
    assert eng["num_pages"] > shared + 33 * own
    tail = -(-(2048 + 256 - 1) // eng["page_size"])
    assert eng["num_window_pages"] > 32 * tail + 33 * own
    assert eng["num_window_pages"] < eng["num_pages"] / 5
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "entry.request_tpot_p50_ms.closed", "engine.prefix_hit_share",
        "model.step_device_ms_p50.closed",
        "model.pool_copy_share_of_step.closed",
        "model.compiles_in_window.closed",
        "kernel.ragged_share_of_step.closed", "device.peak_hbm_share",
        "engine.exposed_schedule_ms_per_step.closed",
        "engine.exposed_pack_ms_per_step.closed",
        "engine.exposed_upload_ms_per_step.closed",
        "engine.exposed_dispatch_ms_per_step.closed",
        "engine.exposed_fetch_ms_per_step.closed",
        "engine.exposed_sample_ms_per_step.closed",
        "engine.expert_load_max_over_mean.closed",
        "kernel.gated_experts_roofline",
        "kernel.gated_experts_share_of_step.closed",
        "startup.trace_s", "startup.lower_s", "startup.compile_s",
        "startup.cache_misses", "startup.programs",
        "startup.rest_s"} == names
    for name in NEW:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "out_tok_per_s" and entry["unit"] == "%"


def test_the_step_shapes_the_cells_set_up_walks():
    """``min_prefill_tile`` 256: a turn's last chunk of any length
    runs the whole chunks' tile, so set-up compiles 7 programs where the
    kernel's ten tiers of 8 to 256 would give 34, and the walk reaches
    every shape the window can (a last chunk of ONE token rides as a
    decode row: 33 rows, width 48).  The shapes are the ENGINE's word."""
    from attention_tpu.engine import EngineConfig, ServingEngine
    from attention_tpu.models import decoder_from_config

    cell = harness.Cell(CELL)
    runner = harness.load_module("runners", "serve_window")
    serve = runner.serve
    engine = types.SimpleNamespace(
        config=EngineConfig(**cell.config["engine"]),
        model=decoder_from_config(cell.config))
    engine._q_tile = functools.partial(ServingEngine._q_tile, engine)
    engine.step_shape = functools.partial(ServingEngine.step_shape, engine)
    longest = {}
    for r in serve.chunk_sizes(cell.traffic, cell.config["engine"]):
        longest[serve.step_shape(engine, 0, r)[1]] = r
    assert longest == {1: 1, 256: 256}
    seen = {serve.step_shape(engine, d, r)
            for d in range(engine.config.max_decode_batch + 1)
            for r in sorted(longest.values()) + [0] if d + r}
    assert sorted(seen) == [(8, 1), (16, 1), (24, 1), (32, 1), (48, 1),
                            (256, 256), (384, 256)]
    chunk = engine.config.prefill_chunk
    assert seen == {serve.step_shape(engine, d, r)
                    for d in range(engine.config.max_decode_batch + 1)
                    for r in range(chunk + 1) if d + r}
    # without the knob: the kernel's own tiers
    engine.config = EngineConfig(**dict(cell.config["engine"],
                                        min_prefill_tile=0))
    assert len({serve.step_shape(engine, 0, r)[1]
                for r in range(2, chunk + 1)}) == 10


def test_a_whole_run_of_the_new_runner_at_toy_size_is_correct(capsys):
    """Histories of 640 tokens behind a window of 160: every turn
    lands on a cached tail, in both page spaces."""
    import jax

    runner = harness.load_module("runners", "serve_window")
    cell = harness.Cell(CELL)
    line = json.loads(run.run_cell(
        cell, runner, seed=3_000_000_019, seconds=5.0, trace=False,
        devices=jax.devices()[:1], t_start=0.0, sizes=TOY))
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_per_s", "setup_s"}
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert "step_shapes 3" in out             # (8, 1), (64, 64), (96, 64)
    assert "prefix_fill_steps 30" in out      # 3 histories x 10 chunks
    assert "page spaces: most pages in use" in out
    (row,) = runner.control(cell, seeds=[3_000_000_029], seconds=2.0,
                            devices=jax.devices()[:1], sizes=TOY)
    assert row["requests"] == 1 and row["compiles_in_window"] == 0
    assert row["program.widest_logit_gap"] <= 1e-3
    assert set(row) >= {f"control.{c}.widest_logit_gap"
                        for c in runner.CONTROLS}
    assert runner.CONTROLS == ("fp8", "window_as_full", "rope_on_full",
                               "no_gate")


def test_the_runner_sums_the_traced_steps_counts():
    runner = harness.load_module("runners", "serve_window")
    Step = collections.namedtuple(
        "Step", runner.FIELDS + ("decode_tokens", "prefill_tokens"))
    steps = [Step(*[9] * 11)] * 5 + [
        Step(100, 40, 10, 4, 12, 5, 50, 20, 0, 3, 0),
        Step(1_131_000, 65_536, 8_900, 544, 9_100, 608, 8_500, 900, 2,
             32, 0),
        Step(1_700_000, 590_000, 9_170, 563, 9_400, 640, 8_510, 905, 1,
             32, 256),
        Step(*[1] * 11)]
    spans = harness.Spans()
    spans.records = [("bench.step", t, t + 0.5) for t in (1.0, 2.0, 3.0)]
    # 5 set-up steps, 3 in the window, 1 draining after it
    facts = {"traced_from": 1.9, "engine_steps": 4}
    config = harness.Cell(CELL).config
    got = runner.window_work(steps, spans, facts, config)
    assert got == {
        "steps": 2, "tokens": 32 + 32 + 256,
        "attn_qk_pairs": 2_831_000, "attn_qk_pairs_window": 655_536,
        "attn_qk_pairs_full": 2_831_000 - 655_536,
        "attn_band_pages": 18_070, "attn_band_pages_window": 1_107,
        "attn_band_pages_full": 18_070 - 1_107,
        "kv_pages": 18_500, "kv_pages_window": 1_248,
        "used_pages": 17_010, "window_used_pages": 1_805,
        "window_pages_released": 3, "window_layers": 7, "full_layers": 2}
    assert runner.window_work(steps, spans, {"traced_from": None},
                              config) is None
    # a program whose steps lack the fields (the parent of this PR), or
    # a model with one page space (every window count 0)
    Old = collections.namedtuple("Old", "decode_tokens kv_pages")
    assert runner.window_work([Old(1, 2)] * 9, spans, facts, config) is None
    flat = [s._replace(attn_qk_pairs_window=0) for s in steps]
    assert runner.window_work(flat, spans, facts, config) is None


def _ctx(events, window, cell=CELL):
    return {"events": events, "planes": [DEV], "facts": {"window": window},
            "cell": harness.Cell(cell), "peaks": harness.peaks("TPU v5 lite")}


# an operation's text in the device trace names its operands: the
# readers match the name a text STARTS with
ATTEND_OP = ("%_ragged_paged_attention_jit.1 = bf16[1,4,384,1024] "
             "custom-call(s32[] %get.1, bf16[9024,4,128,128] %param.12, "
             "bf16[1,4,2304,128] %kv_row_append.3)")
APPEND_OP = ("%kv_row_append.3 = bf16[9024,4,128,128] custom-call("
             "bf16[1,4,384,128] %_ragged_paged_attention_jit.0)")


def test_the_new_readers_on_hand_made_events(capsys):
    def reader(name):
        return harness.load_module("layer_metrics", name)

    events = [
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.0, 0.030),
        Event(DEV, trace.OPS, APPEND_OP, 0.001, 0.001),
        Event(DEV, trace.OPS, ATTEND_OP, 0.002, 0.010),
        Event(DEV, trace.OPS, ATTEND_OP, 0.013, 0.002),
        Event(DEV, trace.OPS, "fusion.7", 0.016, 0.004),
    ]
    # one decode step of 32 rows at 33.3k keys: a full layer attends
    # every key, a window layer 2,048; 261 and 17 pages a slot
    window = {"steps": 1, "tokens": 32, "full_layers": 2, "window_layers": 7,
              "attn_qk_pairs_full": 32 * 33_300,
              "attn_qk_pairs_window": 32 * 2048,
              "attn_band_pages_full": 32 * 261,
              "attn_band_pages_window": 32 * 17,
              "used_pages": 8_600, "window_used_pages": 860}
    ctx = _ctx(events, window)
    assert reader("model.window_key_share.closed").read(
        ctx) == pytest.approx(100.0 * 2048 / 33_300)
    assert reader("engine.window_pages_live_share.closed").read(
        ctx) == pytest.approx(10.0)
    # 4 x 32 heads x 128 operations a pair against the pages' K and V,
    # 262,144 B a page and layer, and the rows in and out
    pairs = 2 * 32 * 33_300 + 7 * 32 * 2048
    ops = 4 * 32 * 128 * pairs
    nbytes = ((2 * 32 * 261 + 7 * 32 * 17) * 262_144
              + 9 * 32 * 2 * 32 * 128 * 2)
    assert paged_attn_flops.page_bytes(4, 128, 128, itemsize=2) == 262_144
    assert nbytes / 819e9 > ops / 197e12
    assert reader("kernel.paged_attn_roofline").read(ctx) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.012)     # the append is not counted
    assert 0 < 100.0 * (nbytes / 819e9) / 0.012 < 100
    assert "the memory roof binds" in capsys.readouterr().out
    # nothing to read: no counts (the parent of this PR, or a model
    # with one page space), no kernel events
    for name in NEW:
        assert reader(name).read(_ctx(events, None)) is None
    bare = events[:2] + events[4:]
    assert reader("kernel.paged_attn_roofline").read(
        _ctx(bare, window)) is None


def test_the_accepted_expert_readers_find_this_cells_keys():
    """The experts' readers are other configurations' files and take
    the held experts' count and width by THEIR keys
    (``n_routed_experts``, ``expert_ffn_hidden_size``): the file
    carries both beside ``num_experts`` / ``moe_intermediate_size``, or
    a traced run's line lacks a metric the cell lists."""
    cfg = harness.Cell(CELL).config
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 16
    assert cfg["expert_ffn_hidden_size"] == cfg["moe_intermediate_size"]
    assert {"n_routed_experts", "expert_ffn_hidden_size"} < set(
        cfg["assumed"])
    events = [
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.0, 0.050),
        Event(DEV, trace.OPS, "%gated_experts_gmm.2 = bf16[384,2048] "
              "custom-call(...)", 0.020, 0.005),
    ]
    # 8 expert layers, 2 traced steps of 32 rows: 8 of 128 a token
    experts = {"steps": 2, "expert_pairs_local": 512,
               "expert_pairs_absent": 3584, "expert_load_max": 48,
               "experts_reached": 250}
    ctx = dict(_ctx(events, None), facts={"experts": experts})
    read = {name: harness.load_module("layer_metrics", name).read(ctx)
            for name in ("engine.expert_load_max_over_mean.closed",
                         "kernel.gated_experts_roofline",
                         "kernel.gated_experts_share_of_step.closed")}
    assert read["engine.expert_load_max_over_mean.closed"] == (
        pytest.approx(48 * 16 / 512))
    assert read["kernel.gated_experts_share_of_step.closed"] == (
        pytest.approx(10.0))
    # 250 experts of 3 x 2048 x 1024 x 2 B + 512 pairs' rows
    nbytes = 250 * 3 * 2048 * 1024 * 2 + 512 * 2 * 2048 * 2
    assert read["kernel.gated_experts_roofline"] == pytest.approx(
        100.0 * nbytes / 819e9 / 0.005)


def test_the_count_modules_arithmetic():
    assert paged_attn_flops.paged_attn_flops(10, 32, 128) == 4 * 32 * 128 * 10
    assert paged_attn_flops.paged_attn_bytes(
        3, 5, heads=32, kv_heads=4, page=128, head_dim=128, itemsize=2) == (
            3 * 2 * 4 * 128 * 128 * 2 + 5 * 2 * 32 * 128 * 2)
    # the least any kernel reads: a decode row's window layer is 17
    # pages of a 33k context's 261
    assert -(-2048 // 128) + 1 == 17
