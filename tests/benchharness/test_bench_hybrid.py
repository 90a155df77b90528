"""What ISSUE 27 adds to the benchmark: the ``olmo-hybrid-7b``
configuration against its source, the runner that takes its model from
the program's builder at toy size on the CPU, the two-point traffic
kind, the two readers of the delta-rule kernel on hand-made events,
and the arithmetic of its operations and bytes."""

import collections
import json
import os

import pytest

from benchmark import delta_flops, harness, run
from benchmark.reduce import trace
from benchmark.reduce.trace import Event

CELL = "olmo-hybrid-7b.longdoc-closed"
BENCH = harness.load_benchmark()
DEV = "/device:TPU:0"
TOY = {
    "config": {
        "hidden_size": 64, "intermediate_size": 96, "vocab_size": 512,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "num_hidden_layers": 4, "linear_num_key_heads": 2,
        "linear_num_value_heads": 2, "linear_key_head_dim": 16,
        "linear_value_head_dim": 32, "torch_dtype": "float32",
        "engine": {"num_pages": 48, "max_seq_len": 640,
                   "max_decode_batch": 3, "prefill_chunk": 64,
                   "token_budget": 128},
    },
    "traffic": {
        "arrivals": {"clients": 3}, "requests": 24,
        "prompt_tokens": {"min": 130, "max": 400},
        "output_tokens": {"min": 2, "max": 6},
        # float32 at toy size: rounding only (the cell's own limit is
        # set from the chip's readings in bf16, PERF.md)
        "check": {"sample_requests": 3, "logit_gap_limit": 1e-3},
    },
}


def test_the_configuration_keeps_the_published_widths():
    cfg = harness.Cell(CELL).config
    entry = next(c for c in BENCH["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    assert entry["source"] in cfg["source"]
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
            ) == (3840, 11008, 30, 30, 100352, 30, 30, 96, 192, 4)
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 128
    # the published list whole: three linear layers to one full, 8 times
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 8
    assert cfg["num_hidden_layers"] % 4 == 0      # whole periods served
    assert cfg["rope_parameters"] == {"rope_theta": None}
    # where the norms sit is stated, and read by the builder
    assert cfg["post_norm"] is True and cfg["qk_norm"] is True
    for key in ("block", "qk_norm", "rope", "linear_layers",
                "A_log_dt_bias", "weights", "deployment"):
        assert cfg["assumed"][key]


def test_the_catalog_row_is_copied_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Olmo-Hybrid-7B")
    cfg = harness.Cell(CELL).config
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"}


def test_the_engine_block_holds_every_request_at_its_longest():
    cell = harness.Cell(CELL)
    eng, traffic = cell.config["engine"], cell.traffic
    longest = (traffic["prompt_tokens"]["max"]
               + traffic["output_tokens"]["max"])
    assert eng["max_seq_len"] >= longest - 1
    pages = -(-longest // eng["page_size"])
    assert eng["num_pages"] > traffic["arrivals"]["clients"] * pages
    assert traffic["arrivals"]["clients"] == eng["max_decode_batch"] == 8
    assert (traffic["prompt_tokens"]["min"],
            traffic["prompt_tokens"]["max"]) == (2048, 6144)
    assert (traffic["output_tokens"]["min"],
            traffic["output_tokens"]["max"]) == (64, 256)
    assert traffic["shared_prefix"] is None


def test_a_whole_run_of_the_new_runner_at_toy_size_is_correct(capsys):
    import jax

    runner = harness.load_module("runners", "serve_config")
    cell = harness.Cell(CELL)
    line = json.loads(run.run_cell(
        cell, runner, seed=3_000_000_019, seconds=6.0, trace=False,
        devices=jax.devices()[:1], t_start=0.0, sizes=TOY))
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_per_s", "setup_s"}
    assert '"check": "compiles_in_window", "value": 0' in out
    assert "preempted" in out and "prefix evictions 0" in out


def test_the_runner_reads_both_controls_at_toy_size():
    """One control for each precision the configuration states: the
    weight matmuls' operands a step down (fp8) and the recurrent state
    a step down (bfloat16 after every token)."""
    import jax

    runner = harness.load_module("runners", "serve_config")
    (row,) = runner.control(
        harness.Cell(CELL), seeds=[3_000_000_029], seconds=3.0,
        devices=jax.devices()[:1], sizes=TOY)
    assert row["requests"] == 3 and row["compiles_in_window"] == 0
    program = row["program.widest_logit_gap"]
    assert program <= TOY["traffic"]["check"]["logit_gap_limit"]
    assert row["control.fp8.widest_logit_gap"] > 0.1 > program
    # float32 at toy size: a bfloat16 state moves few of the best tokens
    assert row["control.state_bf16.widest_logit_gap"] >= program


def test_a_bfloat16_state_moves_the_references_logits():
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
    prompt = np.random.default_rng(7).integers(0, 512, size=200).tolist()
    exact, fp8, state = (
        reference.served_logits(params, config, prompt, [1, 2, 3],
                                pad_to=256, rows=3, low_precision=low)
        for low in (False, True, "state_bf16"))
    assert np.array_equal(fp8, reference.served_logits(
        params, config, prompt, [1, 2, 3], pad_to=256, rows=3,
        low_precision="fp8"))
    # 2^-9 a token on the state alone; fp8 on every operand
    assert 1e-4 < np.abs(state - exact).max() < np.abs(fp8 - exact).max()


def test_the_runner_counts_the_traced_steps_work():
    runner = harness.load_module("runners", "serve_config")
    Step = collections.namedtuple(
        "Step", "decode_tokens prefill_tokens num_decode_reqs "
        "num_prefill_reqs")
    steps = [Step(9, 9, 9, 9)] * 5 + [Step(2, 0, 2, 0), Step(3, 64, 3, 1),
                                      Step(4, 17, 4, 1), Step(1, 0, 1, 0)]
    spans = harness.Spans()
    spans.records = [("bench.step", t, t + 0.5) for t in (1.0, 2.0, 3.0)]
    spans.records.insert(1, ("bench.submit", 1.6, 1.7))
    # 5 set-up steps, 3 in the window, 1 draining after it
    facts = {"traced_from": 1.9, "engine_steps": 4}
    assert runner.recurrent_work(steps, spans, facts) == {
        "steps": 2, "tokens": 3 + 64 + 4 + 17, "slot_steps": 4 + 5}
    assert runner.recurrent_work(steps, spans, {"traced_from": None}) is None


def _ctx(events, work):
    return {"events": events, "planes": [DEV],
            "facts": {"recurrent": work}, "cell": harness.Cell(CELL),
            "peaks": harness.peaks("TPU v5 lite")}


def test_the_delta_readers_on_hand_made_events(capsys):
    share = harness.load_module(
        "layer_metrics", "kernel.delta_share_of_step.closed")
    roof = harness.load_module("layer_metrics", "kernel.delta_roofline")
    events = [
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.0, 0.040),
        Event(DEV, trace.MODULES, "jit__ragged_apply(1)", 0.050, 0.040),
        Event(DEV, trace.OPS, "%_ragged_gated_delta_jit.3 = f32[9,30,256,"
              "192] custom-call(...)", 0.001, 0.004),
        Event(DEV, trace.OPS, "%_ragged_gated_delta_jit.3 = f32[9,30,256,"
              "192] custom-call(...)", 0.051, 0.004),
        Event(DEV, trace.OPS, "%_ragged_paged_attention_jit.1 = ...",
              0.010, 0.010),
        Event(DEV, trace.OPS, "fusion.7", 0.020, 0.010),
    ]
    work = {"steps": 2, "tokens": 2 * 264, "slot_steps": 2 * 9}
    assert share.read(_ctx(events, work)) == pytest.approx(10.0)
    # 6 layers; the states' bytes bind: 18 slot-steps x 30 x 96 x 192
    # x 4 B x 2 and 528 tokens x 30 x (2 B x 576 + 8 B), over 819 GB/s
    nbytes = 6 * (2 * 4 * 18 * 30 * 96 * 192 + 528 * 30 * (2 * 576 + 8))
    assert roof.read(_ctx(events, work)) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.008)
    assert "the memory roof binds" in capsys.readouterr().out
    # nothing to read: no counts (a program without the layer), no
    # kernel events, or no tokens
    assert roof.read(_ctx(events, None)) is None
    assert roof.read(_ctx(events[:2] + events[4:], work)) is None
    assert share.read(_ctx(events[2:], work)) is None
    assert roof.read(_ctx(events, dict(work, tokens=0))) is None


def test_delta_flops_arithmetic():
    assert delta_flops.gated_delta_flops(5, 3, 4, 8) == 6 * 4 * 8 * 3 * 5
    assert delta_flops.gated_delta_bytes(
        5, 2, 3, 4, 8, itemsize=2) == (2 * 4 * 2 * 3 * 4 * 8
                                       + 5 * 3 * (2 * 24 + 8))
    # one decode token a slot: the state's bytes are all but everything
    one = delta_flops.gated_delta_bytes(1, 1, 30, 96, 192, itemsize=2)
    assert 0.99 < 2 * 4 * 30 * 96 * 192 / one < 1.0


def test_two_point_traffic_keeps_the_shares_and_reuses_the_generator():
    two = harness.load_module("generators", "two_point")
    plain = harness.load_module("generators", "requests")
    traffic = harness.load_json("traffic", "long-mixed.json")
    assert traffic["generator"] == "two_point"
    g = two.generate(traffic, seed=3_000_000_019, vocab=1000)
    lengths = collections.Counter(len(r["prompt"]) for r in g["requests"])
    assert set(lengths) == {256, 3072}
    assert lengths[3072] / sum(lengths.values()) == pytest.approx(0.25,
                                                                  abs=0.01)
    assert g == two.generate(traffic, seed=3_000_000_019, vocab=1000)
    other = two.generate(traffic, seed=7, vocab=1000)
    assert [len(r["prompt"]) for r in other["requests"]] != [
        len(r["prompt"]) for r in g["requests"]]
    # every other kind is the plain generator's, and the plain module is
    # left as it was
    chat = harness.load_json("traffic", "chat-poisson.json")
    assert two.generate(chat, seed=5, vocab=100) == plain.generate(
        chat, seed=5, vocab=100)
    with pytest.raises(ValueError, match="unknown distribution kind"):
        plain.quantile_values({"kind": "two_point"}, 4)
    # the runner warms up between the two lengths the file states
    assert (traffic["prompt_tokens"]["min"],
            traffic["prompt_tokens"]["max"]) == (256, 3072)
    arrivals = traffic["arrivals"]
    assert arrivals["load"] == 0.8
    assert arrivals["rate_per_s"] == pytest.approx(0.8 * arrivals["knee_per_s"])


@pytest.mark.parametrize("seed", (1, 7, 3_000_000_019, 4_000_000_211))
def test_long_mixed_offers_every_seed_the_same_tokens_inside_the_window(seed):
    """ONE round whose last request is due five seconds before a 45 s
    window closes, whatever the order: `out_tok_per_s` there is the
    offered load, the same for every seed, while the system keeps up."""
    two = harness.load_module("generators", "two_point")
    traffic = harness.load_json("traffic", "long-mixed.json")
    bench = harness.load_benchmark()
    g = two.generate(traffic, seed=seed, vocab=1000)["requests"]
    assert traffic["rounds"] == 1 and len(g) == 64
    assert g[-1]["due"] == pytest.approx(39.78, abs=0.01)
    assert sum(r["max_tokens"] for r in g) == 5111
    lead = bench["run_seconds"] - g[-1]["due"]
    assert lead >= traffic["trace_lead_seconds"] == 5
    # the longest request of the mix is served in that time at 16 ms a
    # step: twelve chunks and 256 tokens
    assert (12 + 256) * 0.016 < lead
    long_mixed = harness.Cell("starcoder2-7b.long-mixed")
    assert [m["name"] for m in long_mixed.end_to_end] == [
        "out_tok_per_s", "setup_s"]
    assert "entry.tpot_p50_ms.closed" in {
        m["name"] for m in long_mixed.per_layer}
