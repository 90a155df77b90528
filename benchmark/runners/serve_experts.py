"""A serving cell whose model has sparse expert layers: load, warm-up,
window, metrics and the comparison with the reference are
`runners/serve.py`'s own, with the program's builder, both controls
and the recurrent layers' counts as `runners/serve_config.py` has them,
on a private copy of that module.

A traced run also sums, over the steps of the traced slice, what the
engine reports of its expert layers (``facts["experts"]``: token-expert
pairs of the experts held here and of those held elsewhere, the
fullest held expert's pairs, and the (layer, held expert) that received
any; from the engine's own per-step metrics), for the roofline of the
experts' kernel and the load's imbalance.  A program whose steps
report no expert pairs leaves ``facts["experts"]`` None.
"""

from __future__ import annotations

import time

# the program's served expert layer, imported before the chip is taken:
# a program without it cannot build this cell's model, and says so here
from attention_tpu.engine import ServingEngine
from attention_tpu.models.moe import LatentExperts  # noqa: F401

from benchmark import harness

serve_config = harness.load_module("runners", "serve_config")
serve = serve_config.serve

# what `benchmark/sweep.py` and `benchmark/control.py` ask of a runner
merged, serve_once, serve_metrics, control = (
    serve_config.merged, serve_config.serve_once,
    serve_config.serve_metrics, serve_config.control)

FIELDS = ("expert_pairs_local", "expert_pairs_absent", "expert_load_max",
          "experts_reached")


def expert_work(step_metrics, spans, facts: dict) -> dict | None:
    """Sums of `FIELDS` over the traced slice: the window's i-th
    ``bench.step`` span is the engine's step ``first + i``."""
    since = facts.get("traced_from")
    if since is None or not all(
            hasattr(m, f) for m in step_metrics[:1] for f in FIELDS):
        return None
    first = len(step_metrics) - facts["engine_steps"]
    starts = [a for name, a, _ in spans.records if name == "bench.step"]
    traced = [step_metrics[first + i] for i, a in enumerate(starts)
              if a >= since]
    out = {f: sum(getattr(m, f) for m in traced) for f in FIELDS}
    if not out["expert_pairs_local"] + out["expert_pairs_absent"]:
        return None
    return dict(out, steps=len(traced))


def run(cell: harness.Cell, *, clock=time.perf_counter, **kw) -> dict:
    """`serve_config.run` with one more reading of the engine's
    per-step metrics (not a wrapper around it: one engine hook)."""
    kept = []

    def engine(model, params, config):
        # the per-step metrics outlive the engine, which `serve_once`
        # frees before the reference runs
        built = ServingEngine(model, params, config)
        kept.append(built.metrics)
        return built

    serve.ServingEngine = engine
    try:
        ran = serve.run(cell, clock=clock, **kw)
    finally:
        serve.ServingEngine = ServingEngine
    steps, facts = kept[0].steps, ran["facts"]
    facts["recurrent"] = serve_config.recurrent_work(steps, ran["spans"],
                                                     facts)
    facts["experts"] = expert_work(steps, ran["spans"], facts)
    return ran
