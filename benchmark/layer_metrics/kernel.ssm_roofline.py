"""The state-space scan kernel's share of its roofline: the least time
one chip could take for what the traced steps handed the state-space
layers (operations over the bf16 peak, or bytes over the memory
bandwidth, whichever is more: the states' bytes bind) over the time of
the kernel's own events on chip 0.  The counts of tokens and
slot-steps are the engine's per-step metrics over the traced slice
(``facts["recurrent"]``, `runners/serve_config.py`); the arithmetic is
`benchmark/ssm_flops.py`.  Without those counts, without the keys of
such a layer in the configuration, or without an operation of that
name, there is nothing to read."""

from benchmark import flops, ssm_flops
from benchmark.reduce import trace

PATTERN = "ssm_scan"


def read(ctx):
    work = ctx["facts"].get("recurrent")
    config = ctx["cell"].config
    if not work or not work["tokens"] or "mamba_num_heads" not in config:
        return None
    kernel = trace.select(ctx["events"], ctx["planes"][0], trace.OPS,
                          PATTERN)
    if not kernel:
        return None
    depth = int(config["num_hidden_layers"])
    layers = config["hybrid_override_pattern"][:depth].count("M")
    heads = int(config["mamba_num_heads"])
    p, n = int(config["mamba_head_dim"]), int(config["ssm_state_size"])
    least, roof = flops.roofline_seconds(
        layers * ssm_flops.ssm_flops(work["tokens"], heads, p, n),
        layers * ssm_flops.ssm_bytes(
            work["tokens"], work["slot_steps"], heads, p, n,
            int(config["n_groups"]), itemsize=2),
        ctx["peaks"])
    took = sum(e.dur for e in kernel)
    print(f"kernel.ssm_roofline: {len(kernel)} kernel events in "
          f"{work['steps']} steps ({work['tokens']} tokens, "
          f"{work['slot_steps']} slot-steps, {layers} layers), "
          f"{took * 1e3:.3f} ms on chip 0, least {least * 1e3:.3f} ms, "
          f"the {roof} roof binds")
    return 100.0 * least / took
