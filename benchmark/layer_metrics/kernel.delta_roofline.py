"""The gated-delta-rule kernel's share of its roofline: the least time
one chip could take for what the traced steps handed the recurrent
layers (operations over the bf16 peak, or bytes over the memory
bandwidth, whichever is more: at these shapes the states' bytes bind)
over the time of the kernel's own events on chip 0.  The counts of
tokens and slot-steps are the engine's per-step metrics over the
traced slice (``facts["recurrent"]``, `runners/serve_config.py`); the
arithmetic is `benchmark/delta_flops.py`.  Without those counts, or
without an operation of that name, there is nothing to read."""

from benchmark import delta_flops, flops
from benchmark.reduce import trace

PATTERN = "gated_delta"


def read(ctx):
    work = ctx["facts"].get("recurrent")
    if not work or not work["tokens"]:
        return None
    kernel = trace.select(ctx["events"], ctx["planes"][0], trace.OPS,
                          PATTERN)
    if not kernel:
        return None
    config = ctx["cell"].config
    depth = int(config["num_hidden_layers"])
    layers = list(config["layer_types"][:depth]).count("linear_attention")
    heads = int(config["linear_num_value_heads"])
    dk = int(config["linear_key_head_dim"])
    dv = int(config["linear_value_head_dim"])
    least, roof = flops.roofline_seconds(
        layers * delta_flops.gated_delta_flops(
            work["tokens"], heads, dk, dv),
        layers * delta_flops.gated_delta_bytes(
            work["tokens"], work["slot_steps"], heads, dk, dv, itemsize=2),
        ctx["peaks"])
    took = sum(e.dur for e in kernel)
    print(f"kernel.delta_roofline: {len(kernel)} kernel events in "
          f"{work['steps']} steps ({work['tokens']} tokens, "
          f"{work['slot_steps']} slot-steps, {layers} layers), "
          f"{took * 1e3:.3f} ms on chip 0, least {least * 1e3:.3f} ms, "
          f"the {roof} roof binds")
    return 100.0 * least / took
