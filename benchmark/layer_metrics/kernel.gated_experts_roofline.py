"""The gated experts' kernel's share of its roofline: the least time
one chip could take for the token-expert pairs that the traced steps
routed to real experts held here (operations over the bf16 peak, or
bytes over the memory bandwidth, whichever is more: at a few rows an
expert the weights' bytes bind) over the time of the kernel's own
events on chip 0.  The counts are the engine's per-step metrics over
the traced slice, summed over the expert layers (``facts["experts"]``,
`runners/serve_latent.py`); the arithmetic is
`benchmark/gated_experts_flops.py`, with the weights at the 2 bytes
the configuration states.  Without those counts or without an
operation of that name there is nothing to read."""

from benchmark import flops, gated_experts_flops
from benchmark.reduce import trace

PATTERN = "gated_experts"


def read(ctx):
    work = ctx["facts"].get("experts")
    if not work or not work["expert_pairs_local"]:
        return None
    kernel = trace.select(ctx["events"], ctx["planes"][0], trace.OPS,
                          PATTERN)
    if not kernel:
        return None
    config = ctx["cell"].config
    width = int(config["hidden_size"])
    hidden = int(config["expert_ffn_hidden_size"])
    pairs, reached = work["expert_pairs_local"], work["experts_reached"]
    least, roof = flops.roofline_seconds(
        gated_experts_flops.gated_experts_flops(pairs, width, hidden),
        gated_experts_flops.gated_experts_bytes(pairs, reached, width,
                                                hidden, itemsize=2),
        ctx["peaks"])
    took = sum(e.dur for e in kernel)
    print(f"kernel.gated_experts_roofline: {len(kernel)} kernel events in "
          f"{work['steps']} steps ({pairs} local pairs, {reached} experts "
          f"reached, {work['expert_pairs_absent']} pairs absent), "
          f"{took * 1e3:.3f} ms on chip 0, least {least * 1e3:.3f} ms, "
          f"the {roof} roof binds")
    return 100.0 * least / took
