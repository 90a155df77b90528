"""The median of every gap between consecutive tokens, pooled over the
requests that completed in the window, in a cell whose end-to-end
metric is `out_tok_per_s`: what `tpot_p50_ms` is in `chat-poisson`.
In `long-mixed` it carries no bound: a decode-only step takes 13.4,
14.1 or 15.7 ms with the host's speed, which changes between runs and
inside one, so runs of one seed read 13.5 to 15.9 (PERF.md, PR 40)."""


def read(ctx):
    return ctx["facts"].get("metrics", {}).get("tpot_p50_ms")
