"""`BENCHMARK.json` against the contract and the files it names, on the
file as it is AND on the file with one more cell (the way a
`model_config` PR extends it, so that a check which pins how many cells
or metrics there are fails here, at once); the harness's refusals: no
chip, unknown device, unknown names."""

import copy
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import flops, harness
from benchmark.reduce import phases

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = harness.load_benchmark()
STARTUP = ("startup.trace_s", "startup.lower_s", "startup.compile_s",
           "startup.cache_misses", "startup.programs", "startup.rest_s")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def with_one_more_cell(bench: dict) -> dict:
    """A copy of ``bench`` extended by the rule in `benchmark/run.py`'s
    docstring: one more entry of ``workloads`` whose name is APPENDED
    to its end-to-end metric's list and to the list of every per-layer
    metric it reports, and nothing else.  The new cell reports what the
    cell that the last such PR appended to ``out_tok_per_s`` reports,
    on that cell's traffic, under the first configuration that the file
    does not pair with that traffic yet."""
    bench = copy.deepcopy(bench)
    throughput = next(m for m in bench["end_to_end"]
                      if m["name"] == "out_tok_per_s")
    like = next(w for w in bench["workloads"]
                if w["name"] == throughput["workloads"][-1])
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    config = next(c["name"] for c in bench["configs"]
                  if (c["name"], like["traffic"]) not in pairs)
    name = f"{config}.{like['traffic']}"
    bench["workloads"].append({
        "name": name, "config": config, "traffic": like["traffic"],
        "chips": 1, "why": f"one more cell: what {like['name']} reports"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like["name"] in m.get("workloads", ()):
            m["workloads"].append(name)
    return bench


def cells_of(bench: dict) -> list[str]:
    return [w["name"] for w in bench["workloads"]]


# -- what holds of the whole file, however many cells it has -----------------

def only_the_keys_and_values_the_contract_allows(bench):
    assert set(bench) == {"command", "paths", "run_seconds", *KEYS}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/benchharness"]
    assert 1 <= bench["run_seconds"] <= 51
    for kind, keys in KEYS.items():
        # a metric may say in which cells it is read, nothing else may
        may = keys | ({"workloads"} if kind in ("end_to_end", "per_layer")
                      else set())
        for entry in bench[kind]:
            assert keys <= set(entry) <= may, entry["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
    assert len(json.dumps(bench, indent=1)) < 64 * 1024


def names_are_names_and_no_two_are_the_same(bench):
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in bench[kind]]
        assert sorted(set(names)) == sorted(names), kind
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert sorted(set(metrics)) == sorted(metrics)
    for name in (metrics + cells_of(bench)
                 + [c["name"] for c in bench["configs"]]
                 + [w[k] for w in bench["workloads"]
                    for k in ("config", "traffic")]
                 + [k for c in bench["configs"] for k in c["reduced"]]):
        assert NAME.match(name), name


def one_line_says_why_an_entry_exists(bench):
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
        assert "\n" not in entry["why"] and "\t" not in entry["why"]
    for m in bench["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert len(c["reduced"]) <= 16


def every_configuration_has_a_file_of_its_own_and_a_cell(bench):
    files = [c["file"] for c in bench["configs"]]
    assert sorted(set(files)) == sorted(files)
    used = {w["config"] for w in bench["workloads"]}
    for cfg in bench["configs"]:
        assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
        assert os.path.exists(os.path.join(harness.ROOT, cfg["file"]))
        assert os.path.exists(os.path.join(
            harness.HERE, "configs", cfg["name"] + "_reference.py"))
        assert cfg["name"] in used
    assert used <= {c["name"] for c in bench["configs"]}


def a_pair_of_configuration_and_traffic_appears_once(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert sorted(set(pairs)) == sorted(pairs)


def at_most_a_quarter_of_the_cells_take_four_chips(bench):
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)


def every_cell_finds_its_generator_and_its_runner(bench):
    for name in cells_of(bench):
        cell = harness.Cell(name, bench)
        assert os.path.exists(os.path.join(
            harness.HERE, "generators", cell.traffic["generator"] + ".py"))
        assert os.path.exists(os.path.join(
            harness.HERE, "runners", cell.config["runner"] + ".py"))


def every_cell_reports_setup_s_one_more_end_to_end_metric_and_a_layers(bench):
    for name in cells_of(bench):
        cell = harness.Cell(name, bench)
        reported = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in reported and set(reported) - {"setup_s"}, name
        assert cell.per_layer, name


def every_metric_lists_cells_that_are_there_and_report_what_it_moves(bench):
    cells = set(cells_of(bench))
    reports = {name: {m["name"] for m in harness.Cell(name, bench).end_to_end}
               for name in cells}
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in bench["per_layer"]:
        # an explicit list on every per-layer metric: without one the
        # metric would be owed in every cell a later PR adds
        assert m["workloads"], m["name"]
        assert sorted(set(m["workloads"])) == sorted(m["workloads"])
        for name in m["workloads"]:
            assert name in cells, (m["name"], name)
            assert m["moves"] in reports[name], (m["name"], name)


def every_per_layer_metric_has_a_reader_and_every_reader_an_entry(bench):
    for m in bench["per_layer"]:
        assert callable(harness.load_module("layer_metrics", m["name"]).read)
    files = {f[:-3] for f in os.listdir(os.path.join(harness.HERE,
                                                     "layer_metrics"))
             if f.endswith(".py")}
    assert files == {m["name"] for m in bench["per_layer"]}


def the_six_startup_entries_move_setup_s_in_every_cell(bench):
    """Every cell of ``workloads`` is listed, in the file's order, by
    each of the six, and reports all six: what `setup_s` is made of is
    read wherever `setup_s` is."""
    cells = cells_of(bench)
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"].startswith("startup.")}
    assert tuple(entries) == STARTUP
    for m in entries.values():
        assert (m["moves"], m["source"], m["layer"], m["better"]) == (
            "setup_s", "program_counter", "startup", "lower")
        assert m["workloads"] == cells
    for name in cells:
        reported = {m["name"] for m in harness.Cell(name, bench).per_layer}
        assert set(STARTUP) <= reported


def every_phase_has_its_exposed_reader_in_both_loops(bench):
    """`reduce/phases.py:PHASES` and the ``engine.exposed_*`` entries
    are the same list: the open loop's move `tpot_p50_ms`, the closed
    loop's `out_tok_per_s`."""
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"].startswith("engine.exposed_")}
    assert set(entries) == {
        f"engine.exposed_{phase}_ms_per_step.{loop}"
        for phase in phases.PHASES for loop in ("open", "closed")}
    for name, m in entries.items():
        assert (m["layer"], m["source"], m["unit"], m["better"]) == (
            "engine", "program_span", "ms", "lower")
        assert m["moves"] == ("tpot_p50_ms" if name.endswith(".open")
                              else "out_tok_per_s")


CHECKS = (
    only_the_keys_and_values_the_contract_allows,
    names_are_names_and_no_two_are_the_same,
    one_line_says_why_an_entry_exists,
    every_configuration_has_a_file_of_its_own_and_a_cell,
    a_pair_of_configuration_and_traffic_appears_once,
    at_most_a_quarter_of_the_cells_take_four_chips,
    every_cell_finds_its_generator_and_its_runner,
    every_cell_reports_setup_s_one_more_end_to_end_metric_and_a_layers,
    every_metric_lists_cells_that_are_there_and_report_what_it_moves,
    every_per_layer_metric_has_a_reader_and_every_reader_an_entry,
    the_six_startup_entries_move_setup_s_in_every_cell,
    every_phase_has_its_exposed_reader_in_both_loops,
)


@pytest.mark.parametrize("extend", (copy.deepcopy, with_one_more_cell),
                         ids=("the-file", "one-more-cell"))
@pytest.mark.parametrize("check", CHECKS, ids=lambda check: check.__name__)
def test_the_file_and_the_file_with_one_more_cell(check, extend):
    """Adding a cell is an operation these tests perform: every check
    that speaks of ALL cells or ALL metrics runs on `BENCHMARK.json` and
    on its extension.  One that passes on the first and fails on the
    second pins a count, and is to be rewritten as what it stood for."""
    check(extend(BENCH))


def test_one_more_cell_is_appended_and_nothing_else_moves():
    more = with_one_more_cell(BENCH)
    assert BENCH == harness.load_benchmark()      # the copy is a copy
    (new,) = [w for w in more["workloads"] if w not in BENCH["workloads"]]
    assert more["workloads"][:-1] == BENCH["workloads"]
    assert {k: more[k] for k in ("command", "paths", "run_seconds",
                                 "configs")} == {
        k: BENCH[k] for k in ("command", "paths", "run_seconds", "configs")}
    throughput = next(m for m in more["end_to_end"]
                      if m["name"] == "out_tok_per_s")
    assert throughput["workloads"][-1] == new["name"]
    like = harness.Cell(throughput["workloads"][-2], more)
    cell = harness.Cell(new["name"], more)
    assert cell.traffic == like.traffic and cell.chips == 1
    for kind in ("end_to_end", "per_layer"):
        assert ([m["name"] for m in getattr(cell, kind)]
                == [m["name"] for m in getattr(like, kind)])
        for was, now in zip(BENCH[kind], more[kind]):
            grown = dict(now)
            if new["name"] in now.get("workloads", ()):
                assert now["workloads"][-1] == new["name"]
                grown["workloads"] = now["workloads"][:-1]
            assert grown == was


def test_files_under_paths_are_named_from_a_names_characters():
    for path in BENCH["paths"]:
        for folder, _dirs, files in os.walk(os.path.join(harness.ROOT, path)):
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), harness.ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


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
    # what was compared comes last, each number beside its limit
    assert list(line)[-1] == "compared"
    assert line["compared"] == {"x": {"value": 1.0, "limit": 2.0}}
    checks.add("nan is not correct", float("nan"), float("inf"))
    assert checks.correct is False
    assert json.loads(harness.result_line(
        checks=checks, attempted=3, failed=0, metrics={}, units={},
        device={}, breakdown={}))["compared"]["nan is not correct"] == {
            "value": "nan", "limit": "inf"}
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
    # a slice that ends before the window does: the span closes at
    # `stop_after`, the profiler only when the window's owner says so
    cut = harness.SliceTracer(True, spans, str(tmp_path / "cut"),
                              start_after=1.0, stop_after=2.0)
    cut.tick(1.5)
    assert cut.started_at == 5.0 and spans.annotate
    cut.tick(1.9)
    assert spans.records == []
    cut.tick(2.0)
    assert [r[0] for r in spans.records] == ["bench.traced"]
    assert not spans.annotate
    cut.tick(2.5)                # no second slice
    cut.stop()
    cut.stop()
    assert [r[0] for r in spans.records] == ["bench.traced"]
