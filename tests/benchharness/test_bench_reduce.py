"""The trace reduction on hand-made events and on a small trace
recorded on the chip (`benchmark/reduce/fixtures/`)."""

import os

import pytest

from benchmark import flops, harness
from benchmark.reduce import trace
from benchmark.reduce.trace import Event

FIXTURES = os.path.join(harness.HERE, "reduce", "fixtures")
DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return Event(plane, line, name, start, dur)


def test_union_and_subtract():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.total(trace.union([(0, 1), (1, 2)])) == 2
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6)]) == [
        (0, 1), (2, 4), (6, 10)]
    assert trace.subtract([(0, 1)], [(0, 1)]) == []
    assert trace.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]


@pytest.mark.parametrize("raw, want", [
    ("fusion.123", "fusion"),
    ("%copy.4 = bf16[8]{0} copy(bf16[8]{0} %p)", "copy"),
    ("%_flash_attention_jit.1 = bf16[1,8,8] custom-call(...)",
     "_flash_attention_jit"),
    ("jit__ragged_apply(123)", "jit__ragged_apply"),
    ("copy-start", "copy-start"),
])
def test_op_name_is_stable(raw, want):
    assert trace.op_name(raw) == want


def test_busy_idle_and_gap_attribution():
    events = [
        ev(trace.OPS, "fusion.1", 0.0, 1.0),
        ev(trace.OPS, "kernel", 1.0, 2.0),
        ev(trace.OPS, "fusion.2", 5.0, 1.0),
        ev(trace.MODULES, "jit_step(1)", 0.0, 3.0),
        ev(trace.MODULES, "jit_step(1)", 5.0, 1.0),
        # host: an outer step span with the fetch nested in it, and the
        # window's own span, which explains nothing
        Event(trace.HOST_PLANE, "main", "bench.traced", 0.0, 7.0),
        Event(trace.HOST_PLANE, "main", "bench.step", 2.5, 2.4),
        Event(trace.HOST_PLANE, "main", "np.asarray(jax.Array)", 3.5, 1.0),
    ]
    assert trace.device_planes(events) == [DEV]
    assert trace.busy_seconds(events, DEV) == pytest.approx(4.0)
    assert trace.mean_busy_seconds(events) == pytest.approx(4.0)
    assert trace.idle_gaps(events, DEV, (0.0, 7.0)) == [(3.0, 5.0), (6.0, 7.0)]
    by_op = trace.seconds_by_op(events, DEV)
    assert by_op == {"fusion": pytest.approx(2.0), "kernel": pytest.approx(2.0)}
    gaps = trace.gaps_by_host_event(events, DEV, (0.0, 7.0),
                                    exclude=("bench.traced",))
    # the 2 s gap's middle (4.0) lies in the fetch, the innermost span
    assert gaps == {"np.asarray": pytest.approx(2.0),
                    trace.NO_HOST_EVENT: pytest.approx(1.0)}
    assert trace.top(gaps, 1) == [["np.asarray", pytest.approx(2.0)]]
    assert trace.span_window(events, "bench.traced") == (0.0, 7.0)


def test_busy_is_averaged_over_chips():
    events = [ev(trace.OPS, "a", 0.0, 1.0),
              ev(trace.OPS, "a", 0.0, 3.0, plane="/device:TPU:1")]
    assert trace.device_planes(events) == [DEV, "/device:TPU:1"]
    assert trace.mean_busy_seconds(events) == pytest.approx(2.0)


def test_recorded_flash_trace():
    """50 ms of the 32k flash cell's traced window (TPU v5 lite)."""
    events = trace.load_events(os.path.join(FIXTURES, "flash_32k.json.gz"))
    assert trace.device_planes(events) == [DEV]
    kernel = trace.select(events, DEV, trace.OPS, "flash")
    modules = trace.select(events, DEV, trace.MODULES)
    assert len(kernel) == len(modules) > 5
    assert {trace.op_name(e.name) for e in kernel} == {"_flash_attention_jit"}
    lo = min(e.start for e in modules)
    hi = max(e.start + e.dur for e in modules)
    busy = trace.busy_seconds(events, DEV)
    # (an operation of a call cut off at the slice's edge may stand alone)
    assert 0.95 * (hi - lo) < busy < 1.01 * (hi - lo)
    # back-to-back calls: the kernel is all but the whole of each call
    per_call = sum(e.dur for e in kernel) / len(kernel)
    least, roof = flops.roofline_seconds(
        flops.attention_flops(32768, 32768, 128, 128),
        flops.attention_bytes(32768, 32768, 128, 128, itemsize=2),
        harness.peaks("TPU v5 lite"))
    assert roof == "compute"
    assert 0.5 < least / per_call < 1.0


def test_save_and_load_round_trip(tmp_path):
    events = [ev(trace.OPS, "a", 0.5, 0.25)]
    path = str(tmp_path / "t.json.gz")
    trace.save_events(events, path)
    assert trace.load_events(path) == events


def test_recorded_serving_trace():
    """Five decode-only engine steps of the chat cell (TPU v5 lite),
    with the slice's own span on the benchmark's thread."""
    from benchmark.reduce import steps

    events = trace.load_events(os.path.join(FIXTURES, "serve_steps.json.gz"))
    ctx = {"events": events, "planes": trace.device_planes(events)}
    mods = steps.step_modules(ctx)
    assert len(mods) == 5
    assert all(0.026 < e.dur < 0.027 for e in mods)
    assert steps.op_share_of_step(ctx, "ragged_paged") == pytest.approx(
        36.7, abs=0.1)
    reader = harness.load_module("layer_metrics",
                                 "model.pool_copy_share_of_step.open")
    assert reader.read(ctx) == pytest.approx(18.7, abs=0.1)
    window = trace.span_window(events, "bench.traced")
    busy = trace.busy_seconds(events, DEV)
    idle = trace.total(trace.idle_gaps(events, DEV, window))
    assert busy + idle == pytest.approx(window[1] - window[0])
    gaps = trace.gaps_by_host_event(events, DEV, window,
                                    exclude=("bench.traced",))
    assert sum(gaps.values()) == pytest.approx(idle)
    # the benchmark's own thread explains the gaps, not the runtime's
    # allocator and transfer threads
    assert {"bench.step", "DevicePutWithSharding"} <= set(gaps)
    assert not any("Allocat" in name or "Transfer" in name for name in gaps)
