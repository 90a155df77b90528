"""The chip's idle time by engine-step phase (`benchmark/reduce/phases.py`)
on hand-made events and on a small trace recorded on the chip."""

import os

import pytest

from benchmark import harness
from benchmark.reduce import phases, trace
from benchmark.reduce.trace import Event

FIXTURES = os.path.join(harness.HERE, "reduce", "fixtures")
DEV = "/device:TPU:0"
WINDOW = (0.0, 10.0)


def op(start, dur):
    return Event(DEV, trace.OPS, "fusion.1", start, dur)


def host(name, start, dur, line="loop"):
    return Event(trace.HOST_PLANE, line, name, start, dur)


def two_steps():
    """Two busy steps and a step that found nothing to run.  The chip
    works in [1, 2], [4.5, 5] and [6.5, 7.5]: idle 7.5 s of 10."""
    return [
        op(1.0, 1.0), op(4.5, 0.5), op(6.5, 1.0),
        host("bench.traced", 0.0, 10.0),
        host("bench.step", 0.5, 4.0),
        host("engine.step", 0.5, 4.0),
        host("engine.step.schedule", 0.5, 0.25),
        host("scheduler.admit", 0.5, 0.125),          # a child: no phase
        host("engine.step.pack", 0.75, 0.25),
        host("engine.step.upload", 1.0, 0.5),          # chip busy: hidden
        host("engine.step.dispatch", 1.5, 0.25),
        host("engine.step.fetch", 1.75, 1.75),         # idle from 2.0
        host("engine.step.sample", 3.5, 0.75),         # to 4.25, then self
        host("bench.step", 5.5, 3.0),
        host("engine.step", 5.5, 3.0),
        host("engine.step.schedule", 5.5, 0.5),
        host("engine.step.pack", 6.0, 0.25),
        host("engine.step.upload", 6.25, 0.25),
        host("engine.step.dispatch", 6.5, 0.5),
        host("engine.step.fetch", 7.0, 1.0),           # idle from 7.5
        host("engine.step.sample", 8.0, 0.5),
        host("bench.step", 9.0, 0.5),
        host("engine.step", 9.0, 0.5),                 # nothing to run
        host("engine.step.schedule", 9.0, 0.25),
        # another thread's spans of the same names explain nothing
        host("engine.step", 0.0, 10.0, line="other"),
        host("engine.step.fetch", 0.0, 10.0, line="other"),
        host("engine.step.dispatch", 0.0, 10.0, line="other"),
    ]


def test_overlap_of_interval_lists():
    assert phases.overlap([(0, 2), (3, 5)], [(1, 4)]) == 2
    assert phases.overlap([(0, 1)], [(1, 2)]) == 0
    assert phases.overlap([], [(0, 1)]) == 0
    assert phases.overlap([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == 3


def test_a_gap_across_two_phases_is_split_and_the_parts_add_up():
    b = phases.breakdown(two_steps(), DEV, WINDOW)
    assert (b["steps"], b["busy_steps"], b["bench_steps"]) == (3, 2, 3)
    assert b["step_s"] == b["bench_step_s"] == 7.5
    assert b["idle_s"] == 7.5
    # the gap [2, 4.5] lies under fetch (to 3.5), sample (to 4.25) and
    # the step's self time (to 4.5): split at the boundaries, not given
    # whole to the phase that holds its middle
    assert b["exposed_s"] == {
        "schedule": 0.25 + 0.5 + 0.25, "pack": 0.25 + 0.25,
        "upload": 0.25, "dispatch": 0.0,
        "fetch": 1.5 + 0.5, "sample": 0.75 + 0.5}
    assert b["self_s"] == 0.25 + 0.25       # [4.25, 4.5] and [9.25, 9.5]
    assert b["outside_s"] == 0.5 + 0.5 + 0.5 + 0.5
    assert (sum(b["exposed_s"].values()) + b["self_s"] + b["outside_s"]
            == b["idle_s"])
    assert b["duration_s"]["fetch"] == 2.75
    line = phases.describe(b)
    assert line.startswith("phases: 3 engine.step spans of 2500.000 ms "
                           "(2 busy; 3 bench.step of 2500.000 ms)")
    assert "fetch 1375.000 / 1000.000" in line
    assert line.endswith("chip idle 7.5000 s = phases 5.0000 + self "
                         "0.5000 + outside any step 2.0000")


def test_readers_give_ms_per_busy_step_and_print_one_line(capsys):
    events = two_steps()
    ctx = {"events": events, "planes": [DEV], "trace_window": WINDOW}
    # the entries of `BENCHMARK.json` that name these readers:
    # `test_bench_contract.py`, one for each phase in both loops
    got = {}
    for phase in phases.PHASES:
        for kind in ("open", "closed"):
            name = f"engine.exposed_{phase}_ms_per_step.{kind}"
            got[name] = harness.load_module("layer_metrics", name).read(ctx)
    for kind in ("open", "closed"):
        assert got[f"engine.exposed_fetch_ms_per_step.{kind}"] == 1000.0
        assert got[f"engine.exposed_sample_ms_per_step.{kind}"] == 625.0
        assert got[f"engine.exposed_schedule_ms_per_step.{kind}"] == 500.0
        assert got[f"engine.exposed_pack_ms_per_step.{kind}"] == 250.0
        assert got[f"engine.exposed_upload_ms_per_step.{kind}"] == 125.0
        assert got[f"engine.exposed_dispatch_ms_per_step.{kind}"] == 0.0
    out = capsys.readouterr().out
    assert out.count("phases: ") == 1 and out.count("\n") == 1


def test_a_program_without_the_spans_gives_none_not_zero(capsys):
    """What the parent commit's traces look like: the benchmark's own
    spans and the runtime's events, no program span."""
    events = [e for e in two_steps() if not e.name.startswith("engine.")]
    assert phases.breakdown(events, DEV, WINDOW) is None
    ctx = {"events": events, "planes": [DEV], "trace_window": WINDOW}
    assert phases.exposed_fetch_ms_per_step(ctx) is None
    assert phases.exposed_sample_ms_per_step(ctx) is None
    assert capsys.readouterr().out == ""
    # spans on another thread than the slice's own are no program spans
    events = [e for e in two_steps()
              if not e.name.startswith("engine.") or e.line == "other"]
    assert phases.breakdown(events, DEV, WINDOW) is None
    # steps that launched nothing: no busy step to divide by
    events = [e for e in two_steps() if "dispatch" not in e.name]
    ctx = {"events": events, "planes": [DEV], "trace_window": WINDOW}
    assert phases.exposed_fetch_ms_per_step(ctx) is None
    line = harness.result_line(
        checks=harness.Checks(), attempted=0, failed=0,
        metrics={"engine.exposed_fetch_ms_per_step.open": None},
        units={"engine.exposed_fetch_ms_per_step.open": "ms"}, device={})
    assert "exposed_fetch" not in line


def test_recorded_serving_trace_by_phase():
    """Three engine steps of the chat cell, each with a 256-token
    prefill chunk beside 10-11 decode rows (TPU v5 lite, PR 24): chip
    0's operations and programs and the step loop's thread, the
    slice's own span cut to the three steps.  The cut also caught the
    `engine.step.schedule` of a fourth step whose `engine.step` it did
    not: a phase without its step in the slice explains nothing."""
    events = trace.load_events(os.path.join(FIXTURES, "serve_phases.json.gz"))
    assert trace.device_planes(events) == [DEV]
    window = trace.span_window(events, phases.MARK)
    b = phases.breakdown(events, DEV, window)
    assert (b["steps"], b["busy_steps"], b["bench_steps"]) == (3, 3, 3)
    assert sum(e.name == "engine.step.schedule" for e in events) == 4
    # the program's span around the step is the benchmark's, to 0.02 ms
    assert b["bench_step_s"] - b["step_s"] == pytest.approx(0.0, abs=6e-5)
    # every idle second has one owner
    idle = trace.total(trace.idle_gaps(events, DEV, window))
    assert b["idle_s"] == idle == pytest.approx(0.096993, abs=1e-6)
    assert (sum(b["exposed_s"].values()) + b["self_s"] + b["outside_s"]
            == pytest.approx(idle, abs=1e-9))
    # the loop is synchronous: the chip stands still under every phase
    # but the fetch, whose first 37 ms a step the chip works through
    for phase in ("schedule", "pack", "upload", "dispatch", "sample"):
        assert b["exposed_s"][phase] == pytest.approx(
            b["duration_s"][phase], rel=1e-6)
    ms = {p: 1e3 * s / 3 for p, s in b["exposed_s"].items()}
    assert ms == pytest.approx(
        {"schedule": 0.0509, "pack": 0.4487, "upload": 6.0836,
         "dispatch": 5.2354, "fetch": 19.1301, "sample": 0.5980}, abs=1e-3)
    assert 1e3 * b["duration_s"]["fetch"] / 3 == pytest.approx(56.458, abs=1e-3)
    assert 1e3 * b["self_s"] / 3 == pytest.approx(0.429, abs=1e-3)
    ctx = {"events": events, "planes": [DEV], "trace_window": window}
    reader = harness.load_module("layer_metrics",
                                 "engine.exposed_fetch_ms_per_step.open")
    assert reader.read(ctx) == pytest.approx(19.1301, abs=1e-3)
    # `gaps_by_host_event` gives a whole gap to the event at its middle.
    # Here one gap runs from the end of a step's device work through
    # the sampling and the next step's schedule, pack, upload and
    # dispatch; its middle lies in the fetch, which is so given a half
    # more than the chip stood still under it
    gaps = trace.gaps_by_host_event(events, DEV, window,
                                    exclude=(phases.MARK,))
    assert "bench.step" not in gaps
    assert gaps["np.asarray"] == pytest.approx(0.0854, abs=1e-4)
    assert gaps["np.asarray"] > 1.4 * b["exposed_s"]["fetch"]
