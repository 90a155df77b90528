"""The kernel runner: `attn_ms` arithmetic on a fake clock, the cell's
loop at toy size on the CPU, and the control that has to come out as
not correct."""

import numpy as np
import pytest

from benchmark import harness
from benchmark.runners import kernel

CELL = "sdpa-paper.32k-flash"
# the limit on max |err| belongs to a size: 512 keys average far less
# than 32768, so the toy's own is wider than the cell's
TOY = {"m": 512, "n": 512, "resident_cases": 3, "calls_per_batch": 4,
       "max_abs_err_limit": 0.006}


def test_one_fence_to_a_batch_and_the_next_batch_dispatched_first():
    """A call costs 2 ms of dispatch; a fence waits 30 ms.  Batch k + 1
    is dispatched before batch k is fenced, there is one fence for K
    calls, and the samples run from fence to fence."""
    now, log = [0.0], []
    clock = lambda: now[0]  # noqa: E731

    def call(i):
        log.append(("call", i))
        now[0] += 0.002
        return i

    def fence(result):
        log.append(("fence", result))
        now[0] += 0.030

    samples, (result, last), window = kernel.fenced_batches(
        call, fence, calls_per_batch=5, seconds=0.1, clock=clock,
        spans=harness.Spans(clock))
    # batches are dispatched at 0, 10 and 50 ms (each under 100 ms)
    # and a fourth at 90; the fifth would come at 130 and does not
    assert samples == [pytest.approx(x) for x in
                       (0.010, 0.008, 0.008, 0.006)]
    assert window == (0.0, pytest.approx(0.16))
    assert [k for k, _ in log].count("fence") == 4
    assert [e for e in log if e[0] == "fence" or e[1] % 5 == 4][:4] == [
        ("call", 4), ("call", 9), ("fence", 4), ("call", 14)]
    assert (result, last) == (19, 19)   # only the last result is handed on
    assert sum(samples) * 5 == pytest.approx(window[1] - window[0])


def test_a_stall_in_one_batch_moves_attn_ms_and_not_the_median():
    """`attn_ms` is the window over its calls: of 5 batches of 4 calls
    at 10 ms each, one whose fence hangs for 200 ms reads 20 ms a call,
    where the batches' median still reads 10."""
    now, fences = [0.0], [0]
    clock = lambda: now[0]  # noqa: E731

    def call(i):
        now[0] += 0.010
        return i

    def fence(result):
        fences[0] += 1
        now[0] += 0.200 if fences[0] == 2 else 0.0

    samples, _, window = kernel.fenced_batches(
        call, fence, calls_per_batch=4, seconds=0.39, clock=clock,
        spans=harness.Spans(clock))
    assert len(samples) == 5 and harness.median(samples) == pytest.approx(0.01)
    assert kernel.time_per_call_ms(window, 20) == pytest.approx(20.0)


def test_the_cells_loop_at_toy_size(capsys):
    import jax

    cell = harness.Cell(CELL)
    ran = kernel.run(cell, seed=3_000_000_019, seconds=0.3, trace=False,
                     devices=jax.devices()[:1], t_start=0.0, trace_dir="",
                     sizes=TOY)
    assert ran["checks"].correct
    names = [r["check"] for r in ran["checks"].rows]
    assert "max_abs_err.paper_contract" in names
    assert "compiles_in_window" in names
    facts = ran["facts"]
    assert facts["calls"] == 4 * len(facts["samples"]) == ran["attempted"]
    t0, t1 = ran["window"]
    assert ran["values"]["attn_ms"] == pytest.approx(
        (t1 - t0) / facts["calls"] * 1e3)
    # the samples run from fence to fence, so they make up the window
    assert sum(facts["samples"]) * 4 == pytest.approx(t1 - t0)
    assert "value" in capsys.readouterr().out  # each number beside its limit


def test_control_in_fp8_is_not_correct():
    """The reference in the program's place, computed in fp8: at every
    seed its error is several times the program's, and a limit set
    between the two refuses it (on the chip, at 32768: PERF.md)."""
    import jax

    cell = harness.Cell(CELL)
    rows = kernel.control(cell, seeds=[1, 2, 3_000_000_019], seconds=0,
                          devices=jax.devices()[:1], sizes=TOY)
    worst_program = max(r["program.max_abs_err"] for r in rows)
    best_control = min(r["control.max_abs_err"] for r in rows)
    assert best_control > 3 * worst_program
    limit = (worst_program * best_control) ** 0.5
    reference = cell.reference()
    cases, _ = harness.load_module("generators", "tensors").generate(
        cell.traffic, cell.config, seed=1, devices=jax.devices()[:1],
        sizes=dict(TOY, resident_cases=1))
    q, k, v = (np.asarray(x.astype("float32")) for x in cases[0])
    checks = harness.Checks()
    kernel.compare_rows(reference, reference.control_rows(q, k, v), q, k, v,
                        checks, abs_tolerance=0.02, limit=limit)
    assert checks.correct is False


def test_sample_rows_cover_both_ends():
    reference = harness.Cell(CELL).reference()
    rows = reference.sample_rows(1000, 64, 5)
    assert rows[0] == 0 and rows[-1] == 999 and len(set(rows)) == len(rows)
    assert list(rows) == list(reference.sample_rows(1000, 64, 5))
    assert list(rows) != list(reference.sample_rows(1000, 64, 6))


def test_ring_cell_on_four_virtual_devices():
    import jax

    cell = harness.Cell("sdpa-paper.131k-ring-x4")
    ran = kernel.run(cell, seed=11, seconds=0.2, trace=False,
                     devices=jax.devices()[:4], t_start=0.0, trace_dir="",
                     sizes=dict(TOY, calls_per_batch=2))
    assert ran["checks"].correct and ran["facts"]["chips"] == 4
