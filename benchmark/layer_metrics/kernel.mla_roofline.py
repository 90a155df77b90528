"""The ragged kernel's share of its roofline where it serves latent
attention (MLA): the least time one chip could take for the traced
steps' attention (operations over the bf16 peak, or bytes over the
memory bandwidth, whichever is more) over the time of the kernel's own
events on chip 0.  The counts are the engine's per-step metrics over
the traced slice (``facts["latent"]``, `runners/serve_latent.py`):
(query token, key) pairs and live (slot, page) pairs of ONE attention
sublayer a step, times the sublayers; the arithmetic is
`benchmark/mla_flops.py`, the published form's work at the 2 bytes the
configuration states.  Without those counts or without an operation of
that name there is nothing to read."""

from benchmark import flops, mla_flops
from benchmark.reduce import trace

PATTERN = "ragged_paged"


def read(ctx):
    work = ctx["facts"].get("latent")
    if not work or not work.get("attn_qk_pairs"):
        return None
    kernel = trace.select(ctx["events"], ctx["planes"][0], trace.OPS,
                          PATTERN)
    if not kernel:
        return None
    config = ctx["cell"].config
    heads = int(config["num_attention_heads"])
    nope, rope, v = (int(config[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    sublayers = 2 * int(config["num_layers"])
    least, roof = flops.roofline_seconds(
        sublayers * mla_flops.mla_flops(work["attn_qk_pairs"], heads, nope,
                                        rope, v),
        sublayers * mla_flops.mla_bytes(
            work["kv_pages"], work["tokens"],
            page=int(config["engine"]["page_size"]),
            row=int(config["kv_lora_rank"]) + rope, heads=heads, nope=nope,
            rope=rope, v=v, itemsize=2),
        ctx["peaks"])
    took = sum(e.dur for e in kernel)
    print(f"kernel.mla_roofline: {len(kernel)} kernel events in "
          f"{work['steps']} steps x {sublayers} sublayers "
          f"({work['attn_qk_pairs']} query-key pairs, {work['kv_pages']} "
          f"live pages, {work['tokens']} tokens a sublayer), "
          f"{took * 1e3:.3f} ms on chip 0, least {least * 1e3:.3f} ms, "
          f"the {roof} roof binds")
    return 100.0 * least / took
