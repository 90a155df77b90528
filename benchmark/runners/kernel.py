"""A kernel cell: fenced batches of back-to-back calls of one jitted
attention, inputs and results resident on the device.

One batch is ``calls_per_batch`` calls dispatched back to back and one
`block_until_ready` on its last result: one fence to at least a quarter
of a second of device work, no fetch inside a batch, and only the last
result of a batch kept alive.  The batches overlap: the host dispatches
batch ``k + 1`` before it fences batch ``k``, so the device's queue
never runs empty while the host turns round after a fence.  (With the
queue drained at every fence that turn-round, some milliseconds on a
quiet host and tens on a shared one, was device idle time 130 times a
window: the driver's check read ``attn_ms`` 2.4% apart within one set.)
A host that stalls for less than a batch no longer shows; one that
stalls longer, and anything that slows the device, still does.
``attn_ms`` is the whole window, from the first dispatch to the last
fence, over all the calls made in it; the time from one fence to the
next over a batch's calls is a sample, and their median is printed
beside it.
"""

from __future__ import annotations

import time

import jax
import numpy as np

import attention_tpu  # the program under test, before the chip is taken

from benchmark import harness


def fenced_batches(call, fence, *, calls_per_batch: int, seconds: float,
                   clock, spans: harness.Spans, tick=None):
    """Run overlapped fenced batches until ``seconds`` have passed.
    ``call(i)`` dispatches the ``i``-th call and returns its result
    without waiting; ``fence(result)`` waits for it.  Batch ``k + 1``
    is dispatched before batch ``k`` is fenced; the last batch is
    fenced with nothing behind it.  Returns the samples (seconds a
    call, fence to fence), the last result with its call number, and
    the window's start and end on ``clock``.  ``tick(elapsed)`` is
    called between batches (the traced run starts its profiler
    there)."""
    issued = 0

    def batch():
        nonlocal issued
        with spans.span("bench.call"):
            for _ in range(calls_per_batch):
                result = call(issued)
                issued += 1
        return result

    samples = []
    t0 = t = clock()
    ahead = batch()
    while ahead is not None:
        if tick is not None:
            tick(clock() - t0)
        result = ahead
        ahead = batch() if clock() - t0 < seconds else None
        with spans.span("bench.fence"):
            fence(result)
        now = clock()
        samples.append((now - t) / calls_per_batch)
        t = now
    return samples, (result, issued - 1), (t0, t)


def time_per_call_ms(window: tuple[float, float], calls: int) -> float:
    """``attn_ms``: all the window's time over all its calls, so a
    stall in any batch shows (the batches' median would hide it)."""
    return (window[1] - window[0]) / calls * 1e3


def compare_rows(reference, out_rows, q_rows, k, v, checks: harness.Checks,
                 *, abs_tolerance: float, limit: float | None) -> float:
    """The result's sampled rows against the fp64 reference: the
    source's own tolerance, and the tighter limit set from readings."""
    want = reference.attention_rows(q_rows, k, v)
    err = float(np.max(np.abs(np.asarray(out_rows, np.float64) - want)))
    checks.add("max_abs_err.paper_contract", err, abs_tolerance)
    if limit is not None:
        checks.add("max_abs_err", err, limit)
    return err


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        devices, t_start: float, trace_dir: str, sizes: dict | None = None,
        clock=time.perf_counter) -> dict:
    config, traffic = cell.config, cell.traffic
    spans = harness.Spans(clock)
    compiles = harness.CompileCounter()
    generator = harness.load_module("generators", traffic["generator"])
    cases, mesh = generator.generate(traffic, config, seed=seed,
                                     devices=devices, sizes=sizes)
    kwargs = {"mesh": mesh} if mesh is not None else {}
    backend = traffic["backend"]
    k_calls = int((sizes or {}).get("calls_per_batch",
                                    traffic["calls_per_batch"]))

    def call(i):
        q, k, v = cases[i % len(cases)]
        return attention_tpu.attention(q, k, v, backend=backend, **kwargs)

    # warm-up: the one shape this cell uses, then one whole batch so
    # that the window opens on a queue in its steady state
    jax.block_until_ready(call(0))
    t = clock()
    for i in range(k_calls):
        out = call(i)
    jax.block_until_ready(out)
    print(f"setup: {len(cases)} cases, warm batch of {k_calls} calls "
          f"{(clock() - t) / k_calls * 1e3:.4f} ms a call, "
          f"{compiles.count} traces/compiles {compiles.seconds:.2f} s")
    compiled_before = compiles.count
    setup_s = clock() - t_start

    tracer = harness.SliceTracer(
        trace, spans, trace_dir,
        start_after=seconds - float(traffic["trace_seconds"]))
    samples, (result, last), window = fenced_batches(
        call, jax.block_until_ready, calls_per_batch=k_calls,
        seconds=seconds, clock=clock, spans=spans, tick=tracer.tick)
    tracer.stop()
    device = harness.device_block(devices)
    compiles_in_window = compiles.count - compiled_before

    calls = len(samples) * k_calls
    attn_ms = time_per_call_ms(window, calls)
    # the first sample holds the dispatch of two batches and the last
    # one only what was left in the queue: the others are a batch each
    inner = samples[1:-1] or samples
    print(f"attn_ms: {window[1] - window[0]:.4f} s / {calls} calls = "
          f"{attn_ms:.5f} ms; {len(samples)} batches of {k_calls}, fence "
          f"to fence: median {harness.median(samples) * 1e3:.5f}, min "
          f"{min(inner) * 1e3:.5f}, max {max(inner) * 1e3:.5f}; "
          f"compiles in window {compiles_in_window}")

    # correct: the window's last result, on sampled rows, against fp64
    checks = harness.Checks()
    reference = cell.reference()
    q, k, v = cases[last % len(cases)]
    rows = reference.sample_rows(q.shape[0], int(traffic["check_rows"]), seed)
    t = clock()
    # gathered on the device, so that only the sampled rows cross
    out_rows = np.asarray(result[rows].astype("float32"))
    q_rows = np.asarray(q[rows].astype("float32"))
    err = compare_rows(reference, out_rows, q_rows,
                       np.asarray(k.astype("float32")),
                       np.asarray(v.astype("float32")), checks,
                       abs_tolerance=float(config["abs_tolerance"]),
                       limit=(sizes or {}).get(
                           "max_abs_err_limit",
                           traffic.get("max_abs_err_limit")))
    checks.add("compiles_in_window", compiles_in_window, 0)
    print(f"reference: {len(rows)} rows of call {last} in "
          f"{clock() - t:.2f} s")

    return {
        "checks": checks, "attempted": calls, "failed": 0,
        "values": {"attn_ms": attn_ms, "setup_s": setup_s},
        "device": device, "spans": spans, "window": window,
        "facts": {"calls": calls, "samples": samples,
                  "max_abs_err": err, "m": q.shape[0], "n": k.shape[0],
                  "chips": len(devices),
                  "compiles_in_window": compiles_in_window},
    }


def control(cell: harness.Cell, *, seeds, seconds: float, devices,
            sizes: dict | None = None) -> list[dict]:
    """For each seed, at the cell's own size: the number `correct`
    compares as the program gives it, and as the control gives it (the
    reference one precision lower, in the program's place).  Needs no
    measured window: one call a seed."""
    del seconds
    config, traffic = cell.config, cell.traffic
    reference = cell.reference()
    generator = harness.load_module("generators", traffic["generator"])
    out = []
    for seed in seeds:
        cases, mesh = generator.generate(
            traffic, config, seed=seed, devices=devices,
            sizes=dict(sizes or {}, resident_cases=1))
        q, k, v = cases[0]
        kwargs = {"mesh": mesh} if mesh is not None else {}
        result = jax.block_until_ready(attention_tpu.attention(
            q, k, v, backend=traffic["backend"], **kwargs))
        rows = reference.sample_rows(
            q.shape[0], int(traffic["check_rows"]), seed)
        q_rows = np.asarray(q[rows].astype("float32"))
        kh = np.asarray(k.astype("float32"))
        vh = np.asarray(v.astype("float32"))
        want = reference.attention_rows(q_rows, kh, vh)
        got = np.asarray(result[rows].astype("float32"), np.float64)
        low = reference.control_rows(q_rows, kh, vh)
        out.append({"seed": seed,
                    "program.max_abs_err": float(np.max(np.abs(got - want))),
                    "control.max_abs_err": float(np.max(np.abs(low - want)))})
        print(out[-1], flush=True)
    return out
