"""What the serving cells' per-layer readers share: the ragged step's
programs on chip 0 in the traced slice, and the numbers read from them.
Each quantity is listed twice in ``BENCHMARK.json``, as ``<name>.open``
and ``<name>.closed``, because the open-loop cell's end-to-end metric is
`tpot_p50_ms` and the closed loop's is `out_tok_per_s`; the two reader
files of a quantity both point here."""

from benchmark import harness
from benchmark.reduce import trace

STEP_MODULE = "ragged_apply"


def step_modules(ctx):
    return trace.module_events(ctx["events"], ctx["planes"][0], STEP_MODULE)


def op_share_of_step(ctx, pattern: str):
    """Time of chip 0's operations that match ``pattern``, as a
    percentage of the time of the step programs."""
    mods = step_modules(ctx)
    if not mods:
        return None
    ops = trace.select(ctx["events"], ctx["planes"][0], trace.OPS, pattern)
    return 100.0 * sum(e.dur for e in ops) / sum(e.dur for e in mods)


def step_device_ms_p50(ctx):
    """Device time of one engine step: the median duration of the
    ragged step's program on chip 0 (``XLA Modules`` lane)."""
    mods = step_modules(ctx)
    return 1e3 * harness.median([e.dur for e in mods]) if mods else None


def pool_copy_share_of_step(ctx):
    """The copies of the KV pools that the undonated step makes: time
    of ``copy`` operations over the time of the step's program."""
    return op_share_of_step(ctx, r"^%?copy(\.\d+)?( |$)")


def ragged_share_of_step(ctx):
    """The ragged paged attention kernel's share of the step's device
    time."""
    return op_share_of_step(ctx, "ragged_paged")


def compiles_in_window(ctx):
    """Traces and compilations that JAX made inside the measured window
    (its own monitoring events); must read 0."""
    n = ctx["facts"].get("compiles_in_window")
    return None if n is None else float(n)


def request_tpot_p50_ms(ctx):
    """The median over the requests that completed in the window of a
    request's mean gap between tokens (first token to last, over the
    gaps between).  It counts decode-only steps and steps that carry a
    prefill chunk as a request meets them, and at four fifths of the
    knee it swings by several per cent with the host's speed, which is
    why it carries no bound."""
    tpot = ctx["facts"].get("metrics", {}).get("request_tpot_ms")
    return harness.median(tpot) if tpot else None
