"""How uneven the routing left the experts held here, in a closed-loop
cell: over the traced steps, the pairs of the fullest held expert (a
step's pairs summed over its expert layers) over the mean's.  1.0 is
an even load; the grouped product's time follows the experts reached
and their row tiles, not this, until one expert's rows outgrow a tile.
The counts are ``facts["experts"]`` (`runners/serve_experts.py`)."""


def read(ctx):
    work = ctx["facts"].get("experts")
    held = ctx["cell"].config.get("n_routed_experts")
    if not work or not held or not work["expert_pairs_local"]:
        return None
    return work["expert_load_max"] * int(held) / work["expert_pairs_local"]
