"""The lightning indexer's scoring kernel's share of its roofline: the
least time one chip could take for the traced steps' scoring
(operations over the bf16 peak, or bytes over the memory bandwidth,
whichever is more) over the time of the kernel's own events on chip 0
(operations named ``index_scores``: by the name an operation's text
STARTS with, since the selection's text holds the scores as its
operand).  The counts are the engine's
per-step metrics over the traced slice (``facts["sparse"]``,
`runners/serve_sparse.py`): the causal (query token, key) pairs and the
live (slot, page) pairs of ONE sublayer a step, times the sublayers;
the arithmetic is `benchmark/index_flops.py`, at the 2 bytes the
configuration states.  Without those counts or without an operation of
that name there is nothing to read."""

from benchmark import flops, index_flops
from benchmark.reduce import trace

PATTERN = r"^%?index_scores(\.\d+)?( |$)"


def read(ctx):
    work = ctx["facts"].get("sparse")
    if not work or not work.get("attn_qk_pairs"):
        return None
    kernel = trace.select(ctx["events"], ctx["planes"][0], trace.OPS,
                          PATTERN)
    if not kernel:
        return None
    config = ctx["cell"].config
    heads, dim = int(config["index_n_heads"]), int(config["index_head_dim"])
    sublayers = work["sublayers"]
    least, roof = flops.roofline_seconds(
        sublayers * index_flops.index_flops(work["attn_qk_pairs"], heads,
                                            dim),
        sublayers * index_flops.index_bytes(
            work["kv_pages"], work["tokens"],
            page=int(config["engine"]["page_size"]), heads=heads, dim=dim,
            itemsize=2),
        ctx["peaks"])
    took = sum(e.dur for e in kernel)
    print(f"kernel.index_roofline: {len(kernel)} kernel events in "
          f"{work['steps']} steps x {sublayers} sublayers "
          f"({work['attn_qk_pairs']} scored pairs, {work['kv_pages']} live "
          f"pages, {work['tokens']} tokens a sublayer), "
          f"{took * 1e3:.3f} ms on chip 0, least {least * 1e3:.3f} ms, "
          f"the {roof} roof binds")
    return 100.0 * least / took
