"""What ISSUE 31 adds to the benchmark: the ``nemotron-3-super-120b``
configuration against its source, the runner that wraps
`serve_config` for the expert layers' counts at toy size on the CPU,
the five readers on hand-made events, the arithmetic of the two count
modules, and the queued decode-heavy cell of StarCoder2."""

import collections
import json
import os

import pytest

from benchmark import experts_flops, harness, run, ssm_flops
from benchmark.reduce import trace
from benchmark.reduce.trace import Event

CELL = "nemotron-3-super-120b.reason-closed"
HEAVY = "starcoder2-7b.decode-heavy"
BENCH = harness.load_benchmark()
DEV = "/device:TPU:0"
TOY = {
    "config": {
        "hidden_size": 64, "vocab_size": 512, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 7,
        "hybrid_override_pattern": "MEM*EMEMEM",
        "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
        "n_groups": 2, "n_routed_experts": 4,
        "expert_share": {"index": 2, "of": 4}, "num_experts_per_tok": 4,
        "moe_intermediate_size": 48, "moe_latent_size": 32,
        "moe_shared_expert_intermediate_size": 96, "torch_dtype": "float32",
        "engine": {"num_pages": 24, "max_seq_len": 256,
                   "max_decode_batch": 3, "prefill_chunk": 32,
                   "token_budget": 64},
    },
    "traffic": {
        "arrivals": {"clients": 3}, "requests": 24,
        "prompt_tokens": {"min": 20, "max": 90},
        "output_tokens": {"min": 2, "max": 6},
        # float32 at toy size: rounding only (the cell's own limit is
        # set from the chip's readings in bf16, PERF.md)
        "check": {"sample_requests": 3, "logit_gap_limit": 1e-3},
    },
}


def test_the_configuration_keeps_the_published_widths():
    cfg = harness.Cell(CELL).config
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron-3-super-120b")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert set(entry["reduced"]) < set(cfg["reduced"])
    assert entry["source"] in cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["moe_latent_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["routed_scaling_factor"]) == (
                4096, 32, 2, 128, 128, 64, 128, 8, 4, 22, 2688, 1024,
                5376, 5)
    # the pattern whole; the first 11 letters hold the published ratio
    assert len(cfg["hybrid_override_pattern"]) == 88
    served = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    assert collections.Counter(served) == {"M": 5, "E": 5, "*": 1}
    assert collections.Counter(cfg["hybrid_override_pattern"]) == {
        "M": 40, "E": 40, "*": 8}
    # the share: 64 of 512 experts, an eighth of the vocabulary
    assert cfg["expert_share"] == {"index": 0, "of": 8}
    assert cfg["n_routed_experts"] * 8 == cfg["published"][
        "n_routed_experts"] == 512
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 88
    assert cfg["attention_rotary"] is False
    for key in ("attention", "norm", "mamba", "mamba_init", "experts",
                "torch_dtype", "weights"):
        assert cfg["assumed"][key]
    assert "NOT built" in cfg["omitted"]["multi_token_prediction"]
    assert "8 chips share each layer" in cfg["reduced"]["deployment"]
    assert "22 T / 512" in cfg["reduced"]["n_routed_experts"]


def test_the_catalog_row_is_copied_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    cfg = harness.Cell(CELL).config
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "n_routed_experts", "vocab_size"}


def test_the_engine_blocks_hold_every_request_at_its_longest():
    for name, clients in ((CELL, 64), (HEAVY, 32)):
        cell = harness.Cell(name)
        eng, traffic = cell.config["engine"], cell.traffic
        longest = (traffic["prompt_tokens"]["max"]
                   + traffic["output_tokens"]["max"])
        assert eng["max_seq_len"] >= longest - 1
        pages = -(-longest // eng["page_size"])
        assert eng["num_pages"] > clients * pages
        assert (traffic["arrivals"]["clients"] == clients
                == eng["max_decode_batch"])
        assert traffic["shared_prefix"] is None
        assert {m["name"] for m in cell.end_to_end} == {"out_tok_per_s",
                                                        "setup_s"}
    heavy = harness.Cell(HEAVY).traffic
    assert heavy["prompt_tokens"]["min"] == heavy["prompt_tokens"]["max"] == 128
    assert heavy["output_tokens"]["min"] == heavy["output_tokens"]["max"] == 1024
    # the control cell reads none of the new kernels' metrics
    assert not [m["name"] for m in harness.Cell(HEAVY).per_layer
                if "ssm" in m["name"] or "expert" in m["name"]]
    assert {"kernel.ssm_share_of_step.closed", "kernel.ssm_roofline",
            "kernel.experts_share_of_step.closed", "kernel.experts_roofline",
            "engine.expert_load_max_over_mean.closed"} <= {
                m["name"] for m in harness.Cell(CELL).per_layer}


def test_the_cell_keeps_the_issues_prefill_row():
    eng = harness.Cell(CELL).config["engine"]
    assert (eng["max_decode_batch"], eng["max_prefill_rows"],
            eng["prefill_chunk"], eng["token_budget"]) == (64, 1, 256, 320)


@pytest.mark.parametrize("name, shapes", [
    (CELL, 45), ("starcoder2-7b.chat-poisson", 29),
    ("olmo-hybrid-7b.longdoc-closed", 20), (HEAVY, 6)])
def test_the_step_shapes_a_cells_set_up_walks(name, shapes):
    """The ``(width, q_tile)`` programs of a cell, by the harness's own
    arithmetic (`runners/serve.py:warm_up`) over the program's rules.
    The query tile's floor of 8 tokens in a step that holds a chunk
    (PR 31) took the new cell from 59 to 45 and moved no other cell: a
    group of 9 or of 1 was held to tiles of 8 by the sublane rule."""
    import types

    from attention_tpu.engine import EngineConfig
    from attention_tpu.models import decoder_from_config

    cell = harness.Cell(name)
    serve = harness.load_module("runners", "serve")
    build = (decoder_from_config if cell.config["runner"] != "serve"
             else serve.build_model)
    engine = types.SimpleNamespace(
        config=EngineConfig(**cell.config["engine"]),
        model=build(cell.config))
    longest = {}
    for r in serve.chunk_sizes(cell.traffic, cell.config["engine"]):
        longest[serve.step_shape(engine, 0, r)[1]] = r
    seen = {serve.step_shape(engine, d, r)
            for d in range(engine.config.max_decode_batch + 1)
            for r in sorted(longest.values()) + [0] if d + r}
    assert len(seen) == shapes, sorted(seen)
    # no tile of a step with a chunk is under 8 tokens
    assert min(t for _, t in seen if t > 1) >= 8


def test_the_references_statistic_is_a_requests_mean_gap():
    """What `compare_sample` keeps of a request under the name of the
    widest gap is, for this configuration, the mean over its served
    tokens (the reference's docstring says why)."""
    import numpy as np

    reference = harness.Cell(CELL).reference()
    logits = np.zeros((1000, 16))
    logits[:, 3] = 1.0                    # the reference's best
    tokens = np.full(1000, 3)
    assert reference.widest_gap(logits, tokens) == 0.0
    tokens[[10, 500]] = 7                 # two routing flips, a gap of 1 each
    assert reference.token_gaps(logits, tokens).max() == 1.0
    assert reference.widest_gap(logits, tokens) == pytest.approx(2e-3)
    limit = harness.Cell(CELL).traffic["check"]["logit_gap_limit"]
    assert 0 < limit < 0.01
    # every tenth token a little off, as a lower precision puts it:
    # over the limit; and so is ONE token far off
    tokens = np.where(np.arange(1000) % 10 == 0, 7, 3)
    logits[:, 7] = 1.0 - 20 * limit
    assert reference.widest_gap(logits, tokens) > limit
    one = np.full(1000, 3)
    one[77] = 5
    logits[77, 3] = 1001 * limit
    assert reference.widest_gap(logits, one) > limit


def test_a_whole_run_of_the_new_runner_at_toy_size_is_correct(capsys):
    import jax

    runner = harness.load_module("runners", "serve_experts")
    cell = harness.Cell(CELL)
    line = json.loads(run.run_cell(
        cell, runner, seed=3_000_000_019, seconds=6.0, trace=False,
        devices=jax.devices()[:1], t_start=0.0, sizes=TOY))
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_per_s", "setup_s"}
    assert '"check": "compiles_in_window", "value": 0' in out


def test_the_runner_reads_both_controls_at_toy_size():
    import jax

    runner = harness.load_module("runners", "serve_experts")
    (row,) = runner.control(
        harness.Cell(CELL), seeds=[3_000_000_029], seconds=3.0,
        devices=jax.devices()[:1], sizes=TOY)
    assert row["requests"] == 3 and row["compiles_in_window"] == 0
    program = row["program.widest_logit_gap"]
    assert program <= TOY["traffic"]["check"]["logit_gap_limit"]
    # a dozen served tokens at toy width: a control can move a best
    # token here, it need not (the chip's readings set the cell's limit)
    for control in ("fp8", "state_bf16"):
        assert row[f"control.{control}.widest_logit_gap"] >= program


def test_the_reference_counts_routing_flips_at_toy_size():
    """bf16 operands against float32: a choice flips only at the
    margin, every swap is counted once, and a held expert is one of
    four here."""
    import jax
    import numpy as np

    from attention_tpu.models import decoder_from_config

    cell = harness.Cell(CELL)
    config = {**cell.config, **{k: v for k, v in TOY["config"].items()
                                if k != "engine"}}
    reference = cell.reference()
    model = decoder_from_config(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    params = jax.jit(lambda k: reference.init_params(shapes, k))(
        jax.random.PRNGKey(5))
    tokens = np.random.default_rng(7).integers(0, 512, size=200).tolist()
    flips = reference.routing_flips(params, config, tokens, pad_to=256)
    assert flips["choices"] == 200 * 3               # three E layers of 7
    assert 0 < flips["flipped"] < 0.2 * flips["choices"]
    assert flips["flipped"] <= flips["experts_swapped"] <= 4 * flips["flipped"]
    assert flips["held_swapped"] <= 2 * flips["experts_swapped"]
    # the bf16 pass is a pass of its own, between float32 and fp8
    exact, bf16, fp8 = (
        reference.served_logits(params, config, tokens[:150], [1, 2, 3],
                                pad_to=256, rows=3, low_precision=low)
        for low in (False, "bf16", "fp8"))
    assert 0 < np.abs(bf16 - exact).max() < np.abs(fp8 - exact).max()


def test_the_runner_sums_the_traced_steps_expert_pairs():
    runner = harness.load_module("runners", "serve_experts")
    Step = collections.namedtuple("Step", runner.FIELDS)
    steps = [Step(9, 9, 9, 9)] * 5 + [Step(10, 78, 4, 9), Step(30, 234, 7, 12),
                                      Step(3, 85, 2, 3), Step(1, 1, 1, 1)]
    spans = harness.Spans()
    spans.records = [("bench.step", t, t + 0.5) for t in (1.0, 2.0, 3.0)]
    # 5 set-up steps, 3 in the window, 1 draining after it
    facts = {"traced_from": 1.9, "engine_steps": 4}
    assert runner.expert_work(steps, spans, facts) == {
        "steps": 2, "expert_pairs_local": 33, "expert_pairs_absent": 319,
        "expert_load_max": 9, "experts_reached": 15}
    assert runner.expert_work(steps, spans, {"traced_from": None}) is None
    # a program whose steps report no pairs (the parent of this PR)
    Old = collections.namedtuple("Old", "decode_tokens")
    assert runner.expert_work([Old(1)] * 9, spans, facts) is None
    none = [Step(0, 0, 0, 0)] * 9
    assert runner.expert_work(none, spans, facts) is None


def _ctx(events, recurrent, experts, cell=CELL):
    return {"events": events, "planes": [DEV],
            "facts": {"recurrent": recurrent, "experts": experts},
            "cell": harness.Cell(cell), "peaks": harness.peaks("TPU v5 lite")}


def test_the_new_readers_on_hand_made_events(capsys):
    def reader(name):
        return harness.load_module("layer_metrics", name)

    events = [
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.0, 0.040),
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.050, 0.040),
        Event(DEV, trace.OPS, "%_ragged_ssm_scan_jit.3 = f32[128,392,64] "
              "custom-call(...)", 0.001, 0.004),
        Event(DEV, trace.OPS, "%_ragged_ssm_scan_jit.3 = f32[128,392,64] "
              "custom-call(...)", 0.051, 0.004),
        Event(DEV, trace.OPS, "%_latent_experts_gmm_jit.2 = f32[1856,1024] "
              "custom-call(...)", 0.010, 0.010),
        Event(DEV, trace.OPS, "%_latent_experts_gmm_jit.2 = f32[1856,1024] "
              "custom-call(...)", 0.060, 0.006),
        Event(DEV, trace.OPS, "%_ragged_paged_attention_jit.1 = ...",
              0.025, 0.001),
        Event(DEV, trace.OPS, "fusion.7", 0.030, 0.010),
    ]
    recurrent = {"steps": 2, "tokens": 2 * 64, "slot_steps": 2 * 64}
    experts = {"steps": 2, "expert_pairs_local": 1760,
               "expert_pairs_absent": 12320, "expert_load_max": 90,
               "experts_reached": 600}
    ctx = _ctx(events, recurrent, experts)
    assert reader("kernel.ssm_share_of_step.closed").read(ctx) == (
        pytest.approx(10.0))
    assert reader("kernel.experts_share_of_step.closed").read(ctx) == (
        pytest.approx(20.0))
    # 5 layers; the states' bytes bind: 128 slot-steps x 128 x 64 x 128
    # x 4 B x 2 and 128 tokens x 2 B x (2 x 8192 + 2 x 1024 + 128)
    nbytes = 5 * (2 * 4 * 128 * 128 * 64 * 128
                  + 128 * 2 * (2 * 8192 + 2 * 1024 + 128))
    assert reader("kernel.ssm_roofline").read(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.008)
    # 600 experts of 2 x 1024 x 2688 x 2 B + 1760 pairs' rows
    nbytes = 600 * 2 * 1024 * 2688 * 2 + 1760 * 2 * 1024 * 2
    assert reader("kernel.experts_roofline").read(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.016)
    assert capsys.readouterr().out.count("the memory roof binds") == 2
    assert reader("engine.expert_load_max_over_mean.closed").read(
        ctx) == pytest.approx(90 * 64 / 1760)
    # nothing to read: no counts (a program without the layers), no
    # kernel events, no pairs, or another configuration's cell
    for name in ("kernel.ssm_roofline", "kernel.experts_roofline",
                 "engine.expert_load_max_over_mean.closed"):
        assert reader(name).read(_ctx(events, None, None)) is None
    bare = events[:2] + events[6:]
    assert reader("kernel.ssm_roofline").read(
        _ctx(bare, recurrent, experts)) is None
    assert reader("kernel.experts_roofline").read(
        _ctx(bare, recurrent, experts)) is None
    assert reader("kernel.ssm_share_of_step.closed").read(
        _ctx(events[2:], recurrent, experts)) is None
    assert reader("kernel.experts_roofline").read(_ctx(
        events, recurrent, dict(experts, expert_pairs_local=0))) is None
    assert reader("kernel.ssm_roofline").read(_ctx(
        events, recurrent, None, "olmo-hybrid-7b.longdoc-closed")) is None
    assert reader("engine.expert_load_max_over_mean.closed").read(_ctx(
        events, None, experts, HEAVY)) is None


def test_ssm_and_experts_flops_arithmetic():
    assert ssm_flops.ssm_flops(5, 3, 4, 8) == 4 * 4 * 8 * 3 * 5
    assert ssm_flops.ssm_bytes(5, 2, 3, 4, 8, 2, itemsize=2) == (
        2 * 4 * 2 * 3 * 4 * 8 + 5 * 2 * (2 * 12 + 2 * 16 + 3))
    # one decode token a slot: the state's bytes are all but everything
    one = ssm_flops.ssm_bytes(1, 1, 128, 64, 128, 8, itemsize=2)
    assert 0.99 < 2 * 4 * 128 * 64 * 128 / one < 1.0
    assert experts_flops.experts_flops(7, 4, 6) == 4 * 4 * 6 * 7
    assert experts_flops.experts_bytes(7, 3, 4, 6, itemsize=2) == (
        3 * 2 * 4 * 6 * 2 + 7 * 2 * 4 * 2)
    # an expert nobody reached costs nothing; pairs elsewhere neither
    assert experts_flops.experts_bytes(0, 0, 1024, 2688, itemsize=2) == 0
    # 2.75 pairs an expert: 0.05% of the bytes are rows
    weights = 64 * 2 * 1024 * 2688 * 2
    assert weights / experts_flops.experts_bytes(
        176, 64, 1024, 2688, itemsize=2) > 0.998
