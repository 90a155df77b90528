"""The paged attention kernel's share of its roofline where window
layers stand beside full layers: the least time one chip could take for
the traced steps' attention (operations over the bf16 peak, or bytes
over the memory bandwidth, whichever is more: at decode rows the K and
V pages' bytes bind) over the time of the attention kernel's own events
on chip 0 (operations whose name starts with ``ragged_paged_attention``,
whatever their operands are called).  The counts are the engine's
per-step metrics over the traced slice (``facts["window"]``,
`runners/serve_window.py`), a layer of each kind apart: the pairs
attended, and the pages that hold a key some row of the step attends;
the arithmetic is `benchmark/paged_attn_flops.py`, at the 2 bytes the
configuration states.  A kernel that walks more pages than the band
holds reads low here, and says so.  Without those counts or without an
operation of that name there is nothing to read."""

from benchmark import flops, paged_attn_flops
from benchmark.reduce import trace

PATTERN = r"^%?_*ragged_paged_attention"


def read(ctx):
    work = ctx["facts"].get("window")
    if not work or not work.get("attn_qk_pairs_full"):
        return None
    kernel = trace.select(ctx["events"], ctx["planes"][0], trace.OPS,
                          PATTERN)
    if not kernel:
        return None
    config = ctx["cell"].config
    heads, kv_heads = (int(config[k]) for k in (
        "num_attention_heads", "num_key_value_heads"))
    head_dim = int(config.get("head_dim",
                              int(config["hidden_size"]) // heads))
    full, window = work["full_layers"], work["window_layers"]
    pairs = (full * work["attn_qk_pairs_full"]
             + window * work["attn_qk_pairs_window"])
    pages = (full * work["attn_band_pages_full"]
             + window * work["attn_band_pages_window"])
    least, roof = flops.roofline_seconds(
        paged_attn_flops.paged_attn_flops(pairs, heads, head_dim),
        paged_attn_flops.paged_attn_bytes(
            pages, (full + window) * work["tokens"], heads=heads,
            kv_heads=kv_heads, page=int(config["engine"]["page_size"]),
            head_dim=head_dim, itemsize=2),
        ctx["peaks"])
    took = sum(e.dur for e in kernel)
    print(f"kernel.paged_attn_roofline: {len(kernel)} kernel events in "
          f"{work['steps']} steps x ({full} full + {window} window layers) "
          f"({work['attn_band_pages_full']} / "
          f"{work['attn_band_pages_window']} pages and "
          f"{work['attn_qk_pairs_full']} / {work['attn_qk_pairs_window']} "
          f"pairs a full / a window layer, {work['tokens']} tokens), "
          f"{took * 1e3:.3f} ms on chip 0, least {least * 1e3:.3f} ms, the "
          f"{roof} roof binds")
    return 100.0 * least / took
