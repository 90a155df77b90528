"""The flash kernel's share of its roofline: the least time one chip
could take for its part of a call (operations over the bf16 peak, or
bytes over the memory bandwidth, whichever is more: compute binds at
these shapes) over the kernel's own time, which is the time covered by
its events on chip 0 divided by the calls in the trace.  Not the
window: gaps between calls are the idle share's business.  A trace in
which no event bears the kernel's name is an error: the configuration's
``kernel_op_pattern`` has then to be brought up to the new lowering."""

import bisect

from benchmark import flops
from benchmark.reduce import trace


def read(ctx):
    config, facts = ctx["cell"].config, ctx["facts"]
    pattern = config.get("kernel_op_pattern")
    if not pattern or "m" not in facts:
        return None
    plane = ctx["planes"][0]
    kernel = trace.select(ctx["events"], plane, trace.OPS, pattern)
    if not kernel:
        raise RuntimeError(
            f"no operation on chip 0 matches kernel_op_pattern {pattern!r}")
    calls = count_calls(ctx["events"], plane, kernel)
    if not calls:
        return None
    chips = facts["chips"]
    m, n, dk, dv = facts["m"], facts["n"], config["dk"], config["dv"]
    least, roof = flops.roofline_seconds(
        flops.attention_flops(m, n, dk, dv, causal=config["causal"],
                              heads=config["heads"]) / chips,
        flops.attention_bytes(m, n, dk, dv, itemsize=2,
                              heads=config["heads"]) / chips,
        ctx["peaks"])
    per_call = trace.total(trace.union(trace.intervals(kernel))) / calls
    print(f"kernel.flash_roofline: {len(kernel)} kernel events in {calls} "
          f"calls, {per_call * 1e3:.5f} ms a call on chip 0, least "
          f"{least * 1e3:.5f} ms, the {roof} roof binds")
    return 100.0 * least / per_call


def count_calls(events, plane, kernel) -> int:
    """Calls whose kernel events lie in the trace: the programs on the
    chip that hold at least one of them."""
    starts = sorted(e.start for e in kernel)
    calls = 0
    for mod in trace.select(events, plane, trace.MODULES):
        i = bisect.bisect_left(starts, mod.start)
        if i < len(starts) and starts[i] < mod.start + mod.dur:
            calls += 1
    return calls
