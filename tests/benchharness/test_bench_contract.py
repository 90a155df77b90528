"""`BENCHMARK.json` against the files it names, and the harness's
refusals: no chip, unknown device, unknown names."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import flops, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = harness.load_benchmark()


def test_every_named_file_exists():
    for cfg in BENCH["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, cfg["file"]))
        assert os.path.exists(os.path.join(
            harness.HERE, "configs", cfg["name"] + "_reference.py"))
    for w in BENCH["workloads"]:
        cell = harness.Cell(w["name"], BENCH)
        assert os.path.exists(os.path.join(
            harness.HERE, "generators", cell.traffic["generator"] + ".py"))
        assert os.path.exists(os.path.join(
            harness.HERE, "runners", cell.config["runner"] + ".py"))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in BENCH["per_layer"]:
        assert callable(harness.load_module("layer_metrics", m["name"]).read)


def test_names_units_and_references_between_entries():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for entry in (BENCH["configs"] + BENCH["workloads"]
                  + BENCH["end_to_end"] + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        for w in entry.get("workloads", []):
            assert w in cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(cells) // 4)


def test_reduced_names_no_width_and_file_matches_source_sizes():
    cfg = json.load(open(os.path.join(
        harness.HERE, "configs", "starcoder2-7b.json")))
    entry = next(c for c in BENCH["configs"] if c["name"] == "starcoder2-7b")
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    # the published widths of bigcode/starcoder2-7b
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"], cfg["sliding_window"]) == (
                4608, 18432, 36, 4, 49152, 4096)


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peaks("cpu")


def test_unknown_workload_and_reader_are_errors():
    with pytest.raises(KeyError, match="no workload"):
        harness.Cell("nope.nope")
    with pytest.raises(FileNotFoundError):
        harness.load_module("layer_metrics", "nope")


def test_run_refuses_the_cpu_backend_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "sdpa-paper.32k-flash", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, cwd=harness.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


@pytest.mark.parametrize("values, q, want", [
    ([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 90, 19.0), ([1, float("inf")], 50, float("inf")),
    ([1, 2, 3, float("inf")], 50, 2.5), ([5], 90, 5),
])
def test_percentile(values, q, want):
    assert harness.percentile(values, q) == pytest.approx(want)


def test_result_line_leaves_out_what_no_reader_found():
    checks = harness.Checks()
    checks.add("x", 1.0, 2.0)
    line = json.loads(harness.result_line(
        checks=checks, attempted=3, failed=0,
        metrics={"a": 1.5, "b": None, "c": float("inf")},
        units={"a": "ms", "b": "ms", "c": "ms"},
        device={"platform": "tpu"}))
    assert line["correct"] is True and list(line["metrics"]) == ["a"]
    checks.add("nan is not correct", float("nan"), 1.0)
    assert checks.correct is False
    assert harness.Checks().correct is False  # nothing compared


def test_flops_arithmetic():
    assert flops.attention_flops(4, 8, 2, 2) == 2 * 4 * 8 * 4
    assert flops.attention_flops(4, 8, 2, 2, causal=True) == 4 * 8 * 4
    assert flops.attention_bytes(4, 8, 2, 2, itemsize=2) == 2 * (16 + 32)
    peak = {"bf16_flops_per_s": 10.0, "hbm_bytes_per_s": 1.0}
    assert flops.roofline_seconds(100, 5, peak) == (10.0, "compute")
    assert flops.roofline_seconds(10, 5, peak) == (5.0, "memory")


def test_slice_tracer_off_is_a_no_op_and_on_waits_for_its_time(tmp_path):
    spans = harness.Spans(clock=lambda: 5.0)
    off = harness.SliceTracer(False, spans, "unused", start_after=0.0)
    off.tick(10.0)
    off.stop()
    assert off.started_at is None and spans.records == []
    early = harness.SliceTracer(True, spans, str(tmp_path / "trace"),
                                start_after=37.0)
    early.tick(36.9)             # not yet: the slice has not begun
    assert early.started_at is None
    early.stop()
