"""The median of the samples whose 90th percentile is ``ttft_p90_ms``:
first token out minus the time the request was due."""

from benchmark import harness


def read(ctx):
    ttft = ctx["facts"].get("metrics", {}).get("ttft_ms")
    return harness.median(ttft) if ttft else None
