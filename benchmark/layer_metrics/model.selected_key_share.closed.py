"""Of the (query token, key) pairs the traced steps' selectors scored,
the share attention then attended, in %, in a closed-loop cell: the
counter that says the mechanism engaged (``index_topk`` / context where
every row sees more keys than it may keep; 100 = every key attended).
The counts are ``facts["sparse"]`` (`runners/serve_sparse.py`), from
the engine's per-step metrics: the DEVICE's count of attended pairs
over the sublayers times the host's count of causal pairs.  A program
whose steps report no such count leaves nothing to read."""


def read(ctx):
    work = ctx["facts"].get("sparse")
    if not work or not work.get("attn_qk_pairs"):
        return None
    return (100.0 * work["attn_keys_attended"]
            / (work["sublayers"] * work["attn_qk_pairs"]))
