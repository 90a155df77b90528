"""The share of the traced steps' token-expert pairs that went to
zero-compute experts, in a closed-loop cell: the counter that says the
mechanism engaged (an even router gives zero columns / all columns).
The counts are ``facts["latent"]`` (`runners/serve_latent.py`), from
the engine's per-step metrics.  A program whose steps report no such
pairs leaves nothing to read."""


def read(ctx):
    work = ctx["facts"].get("latent")
    if not work or not work.get("expert_pairs"):
        return None
    return 100.0 * work["expert_pairs_zero"] / work["expert_pairs"]
