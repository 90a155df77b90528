"""Of the (query token, key) pairs the window layers would attend as
full layers over the traced steps, the share they attend behind their
window, in %, in a closed-loop cell: the counter that says the window
binds (``sliding_window`` / context where every row sees more keys than
its window holds; 100 = no row is cut).  The counts are
``facts["window"]`` (`runners/serve_window.py`), from the engine's
per-step metrics: the pairs ONE window sublayer attends over the pairs
ONE full sublayer attends in the same steps, both counted on the host
from each step's own lengths.  A program whose steps report no such
count leaves nothing to read."""


def read(ctx):
    work = ctx["facts"].get("window")
    if not work or not work.get("attn_qk_pairs_full"):
        return None
    return 100.0 * work["attn_qk_pairs_window"] / work["attn_qk_pairs_full"]
