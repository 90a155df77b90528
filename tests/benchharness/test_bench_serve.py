"""The serving runner at toy size on the CPU: a whole run past the
harness's look for a chip comes out correct; with the timed path
broken underneath (a token altered where it is produced) it does not;
and the control, the reference in fp8, reads several times the
program's number."""

import json

import pytest

from benchmark import harness, run
from benchmark.runners import serve

CELL = "starcoder2-7b.chat-poisson"
TOY = {
    "config": {
        "hidden_size": 256, "intermediate_size": 1024,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "num_hidden_layers": 2, "vocab_size": 512,
        "engine": {"num_pages": 96, "max_seq_len": 1024,
                   "max_decode_batch": 4},
    },
    "traffic": {
        "check": {"sample_requests": 3, "logit_gap_limit": 0.25},
        "prompt_tokens": {"min": 128, "max": 384, "median": 256},
        "output_tokens": {"min": 2, "max": 6, "median": 4},
        "arrivals": {"rate_per_s": 1.5}, "drain_seconds": 20,
    },
}


def toy_run(seed, seconds=5.0, cell=CELL, sizes=TOY):
    import jax

    line = run.run_cell(harness.Cell(cell), serve, seed=seed, seconds=seconds,
                        trace=False, devices=jax.devices()[:1], t_start=0.0,
                        sizes=sizes)
    return json.loads(line)


def test_a_whole_run_at_toy_size_is_correct(capsys):
    line = toy_run(3_000_000_019)
    out, err = capsys.readouterr()
    assert line["correct"] is True, out
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {
        m["name"] for m in harness.Cell(CELL).end_to_end} > {"setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert '"check": "widest_logit_gap"' in out
    assert '"check": "compiles_in_window", "value": 0' in out
    # each number compared beside its limit: the end of the standard
    # error, and the last key of the result line
    assert list(line)[-1] == "compared"
    assert list(line["compared"]) == [
        "widest_logit_gap", "finished_with_wrong_token_count",
        "nonfinite_logit_rows", "compiles_in_window"]
    assert line["compared"]["widest_logit_gap"]["limit"] == 0.25
    last = err.splitlines()[-4:]
    assert last[0].startswith("compared: widest_logit_gap ")
    assert last[3] == "compared: compiles_in_window 0 limit 0 ok"


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from attention_tpu.engine import ServingEngine

    sound = ServingEngine._sample

    def altered(self, req, logits_row):
        # the runner-up in place of the best token, every fourth time
        token = sound(self, req, logits_row)
        if len(req.output_tokens) % 4 == 1:
            row = logits_row.copy()
            row[token] = -1e30
            return int(row.argmax())
        return token

    monkeypatch.setattr(ServingEngine, "_sample", altered)
    line = toy_run(7)
    out = capsys.readouterr().out
    assert line["correct"] is False, out
    failed = [json.loads(x) for x in out.splitlines()
              if x.startswith('{"check"') and '"ok": false' in x]
    assert [f["check"] for f in failed] == ["widest_logit_gap"]


def test_the_closed_loop_cell_hits_the_prefix_cache():
    import jax

    sizes = {
        "config": TOY["config"],
        "traffic": {"prompt_tokens": {"min": 128, "max": 256},
                    "output_tokens": {"min": 2, "max": 5},
                    "arrivals": {"clients": 3}, "requests": 32,
                    "check": {"sample_requests": 3, "logit_gap_limit": 0.25},
                    "shared_prefix": {"contexts": 2, "tokens": 256}},
    }
    cell = harness.Cell("starcoder2-7b.repo-closed")
    ran = serve.run(cell, seed=5, seconds=4.0, trace=False,
                    devices=jax.devices()[:1], t_start=0.0, trace_dir="",
                    sizes=sizes)
    assert ran["checks"].correct
    assert ran["values"]["out_tok_per_s"] > 0
    reader = harness.load_module("layer_metrics", "engine.prefix_hit_share")
    share = reader.read({"facts": ran["facts"], "window": ran["window"]})
    # 256 of every 384-512 prompt tokens come from the cache
    assert 45.0 < share < 70.0


def test_control_in_fp8_reads_above_the_program():
    """The control at toy size, three seeds: bf16 through the engine
    against the float32 reference, and the reference in fp8 at the same
    prompts and tokens.  (The limit itself is set from the chip's
    readings at the cell's own size: PERF.md.)"""
    import jax

    sizes = {"config": dict(TOY["config"], torch_dtype="bfloat16"),
             "traffic": dict(TOY["traffic"], output_tokens={
                 "min": 12, "max": 24, "median": 16})}
    rows = serve.control(harness.Cell(CELL), seeds=[1, 2, 3], seconds=4.0,
                         devices=jax.devices()[:1], sizes=sizes)
    assert all(r["served_tokens"] > 0 for r in rows)
    program = max(r["program.widest_logit_gap"] for r in rows)
    control = min(r["control.widest_logit_gap"] for r in rows)
    assert control > 3 * program, rows


def test_warm_up_reaches_every_shape_of_the_window():
    """Nothing compiles in the window: checked in every run, and here
    at a second set of toy sizes."""
    sizes = {"config": dict(TOY["config"], engine=dict(
        TOY["config"]["engine"], max_decode_batch=9)),
        "traffic": dict(TOY["traffic"], arrivals={"rate_per_s": 3.0})}
    line = toy_run(11, seconds=4.0, sizes=sizes)
    assert line["correct"] is True


def test_reference_shape_covers_the_longest_request():
    chat = harness.load_json("traffic", "chat-poisson.json")
    repo = harness.load_json("traffic", "repo-closed.json")
    assert serve.reference_shape({}, chat) == (3328, 256)
    assert serve.reference_shape({}, repo) == (3200, 128)
