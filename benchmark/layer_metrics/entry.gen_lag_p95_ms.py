"""How late the load generator ran: sent minus due, 95th percentile,
on the generator's own clock.  A starved generator would otherwise
read as a fast server."""

from benchmark import harness


def read(ctx):
    lag = ctx["facts"].get("metrics", {}).get("gen_lag_ms")
    return harness.percentile(lag, 95.0) if lag else None
