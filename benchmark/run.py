"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and, traced, ``breakdown``), then ``compared``: each number that
decided ``correct`` beside its limit, which are also the last lines of
its standard error.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for (code 2),
or when the program under test is not beside it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by its name:
``configs/<config>.json`` (+ ``<config>_reference.py``),
``traffic/<traffic>.json``, ``generators/<kind>.py``,
``runners/<kind>.py``, ``layer_metrics/<metric>.py``.

A cell is added with NEW FILES for its configuration, reference,
traffic, runner, readers and their ``*_flops.py``; NEW ENTRIES in
``configs``, ``workloads`` and ``per_layer``; and the cell's name
APPENDED to ``out_tok_per_s`` or ``tpot_p50_ms`` and to every existing
per-layer metric it reports, the six ``startup.*`` among them: no other
line of a file that is there.  Run first the test that makes this very
extension in memory and holds it to every rule of the file:
``tests/benchharness/test_bench_contract.py -k one_more_cell``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.reduce import trace as reduce_trace  # noqa: E402


def layer_metrics(cell: harness.Cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something
    to read; a reader that finds nothing returns None and the metric
    is left out of the line."""
    out = {}
    for metric in cell.per_layer:
        reader = harness.load_module("layer_metrics", metric["name"])
        out[metric["name"]] = reader.read(ctx)
    return out


def traced_context(cell, ran: dict, trace_dir: str) -> tuple[dict, dict, dict]:
    """Reduce the trace: the context the readers take their numbers
    from, ``busy_s`` / ``window_s`` for the device block, and the
    breakdown."""
    t = time.perf_counter()
    events = reduce_trace.load_xplane(trace_dir)
    planes = reduce_trace.device_planes(events)
    if not planes:
        raise RuntimeError("the trace has no /device:TPU plane")
    window = reduce_trace.span_window(events, "bench.traced")
    if window is None:
        raise RuntimeError("the trace lacks the bench.traced span")
    inside = [e for e in events
              if e.start >= window[0] and e.start + e.dur <= window[1]]
    busy_s = reduce_trace.mean_busy_seconds(inside)
    ops = reduce_trace.seconds_by_op(inside, planes[0])
    gaps = reduce_trace.gaps_by_host_event(
        inside, planes[0], window, exclude=("bench.traced",))
    print(f"trace: {len(events)} events, {len(planes)} device plane(s), "
          f"reduced in {time.perf_counter() - t:.1f} s")
    ctx = dict(ran, cell=cell, events=inside, planes=planes,
               trace_window=window, peaks=harness.peaks(
                   ran["device"]["kind"]))
    extra = {"busy_s": busy_s, "window_s": window[1] - window[0]}
    breakdown = {"device_ops": reduce_trace.top(ops),
                 "idle_gaps": reduce_trace.top(gaps)}
    return ctx, extra, breakdown


def run_cell(cell: harness.Cell, runner, *, seed: int, seconds: float,
             trace: bool, devices, t_start: float,
             sizes: dict | None = None) -> str:
    """Everything of a run after the look for a chip: the runner, the
    reduction of the trace, and the result line."""
    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    ran = runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                     devices=devices, t_start=t_start, trace_dir=trace_dir,
                     sizes=sizes)
    bench = harness.load_benchmark()
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    device, breakdown = ran["device"], None
    if trace:
        ctx, extra, breakdown = traced_context(cell, ran, trace_dir)
        device = dict(device, **extra)
        metrics = layer_metrics(cell, ctx)
    else:
        metrics = {m["name"]: ran["values"].get(m["name"])
                   for m in cell.end_to_end}
    line = harness.result_line(
        checks=ran["checks"], attempted=ran["attempted"],
        failed=ran["failed"], metrics=metrics, units=units, device=device,
        breakdown=breakdown)
    ran["checks"].report(sys.stderr)
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cell, runner, devices, cache_dir = harness.open_cell(args.workload)
    print(f"workload={cell.name} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind} chips={cell.chips}; "
          f"compile cache: {cache_dir}")
    print(run_cell(cell, runner, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices, t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
