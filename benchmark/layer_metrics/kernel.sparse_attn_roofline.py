"""The ragged kernel's share of its roofline where it attends the keys
a selector chose: the least time one chip could take for the traced
steps' ATTENDED pairs (operations over the bf16 peak, or bytes over the
memory bandwidth, whichever is more) over the time of the kernel's own
events on chip 0 (operations named ``ragged_paged``).  The counts are
the engine's per-step metrics over the traced slice
(``facts["sparse"]``, `runners/serve_sparse.py`): the pairs the
device's masks let through (every sublayer's), and the cache rows a
sublayer cannot do without, ``index_topk`` a busy slot but no more than
its live pages hold; the arithmetic is
`benchmark/sparse_attn_flops.py`, the published form's work at the 2
bytes the configuration states.  A kernel that walks every live page
and masks reads low here, and says so.  Without those counts or
without an operation of that name there is nothing to read."""

from benchmark import flops, sparse_attn_flops
from benchmark.reduce import trace

PATTERN = "ragged_paged"


def read(ctx):
    work = ctx["facts"].get("sparse")
    if not work or not work.get("attn_keys_attended"):
        return None
    kernel = trace.select(ctx["events"], ctx["planes"][0], trace.OPS,
                          PATTERN)
    if not kernel:
        return None
    config = ctx["cell"].config
    heads = int(config["num_attention_heads"])
    nope, rope, v = (int(config[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    sublayers = work["sublayers"]
    sizes = dict(heads=heads, nope=nope, rope=rope, v=v)
    least, roof = flops.roofline_seconds(
        sparse_attn_flops.sparse_attn_flops(work["attn_keys_attended"],
                                            **sizes),
        sublayers * sparse_attn_flops.sparse_attn_bytes(
            work["kept_rows"], work["tokens"],
            row=int(config["kv_lora_rank"]) + rope, itemsize=2, **sizes),
        ctx["peaks"])
    took = sum(e.dur for e in kernel)
    print(f"kernel.sparse_attn_roofline: {len(kernel)} kernel events in "
          f"{work['steps']} steps x {sublayers} sublayers "
          f"({work['attn_keys_attended']} attended pairs in all, "
          f"{work['kept_rows']} cache rows and {work['tokens']} tokens a "
          f"sublayer), {took * 1e3:.3f} ms on chip 0, least "
          f"{least * 1e3:.3f} ms, the {roof} roof binds")
    return 100.0 * least / took
