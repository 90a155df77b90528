"""The 90th percentile of every gap between consecutive tokens, pooled
over the requests that completed in the window: a step that carries a
prefill chunk, which the end-to-end median does not see."""


def read(ctx):
    return ctx["facts"].get("metrics", {}).get("tpot_p90_ms")
