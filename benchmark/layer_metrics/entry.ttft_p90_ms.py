"""The 90th percentile of first token out minus the time the request
was due, over the requests due in the window; an unanswered request is
missing.  A tail of about a hundred samples: too wide from run to run
to carry a bound, so it stands here and not among the end-to-end
metrics."""

from benchmark import harness


def read(ctx):
    ttft = ctx["facts"].get("metrics", {}).get("ttft_ms")
    return harness.percentile(ttft, 90.0) if ttft else None
