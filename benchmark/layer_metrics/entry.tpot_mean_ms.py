"""The mean of every gap between consecutive tokens, pooled over the
requests that completed in the window: it counts a decode-only step and
a step that carries a prefill chunk as the tokens met them, where the
end-to-end median sees the first kind only.  From seed to seed it
ranges over 4-6% (the order of arrivals decides how many tokens wait on
a chunk), which is why it carries no bound."""


def read(ctx):
    return ctx["facts"].get("metrics", {}).get("tpot_mean_ms")
