"""A serving cell whose model attends through latent (MLA) pools and
routes over gated and zero-compute experts: load, warm-up, window,
metrics and the comparison with the reference are `runners/serve.py`'s
own, with the program's builder and the experts' counts as
`runners/serve_experts.py` has them, on a private copy of that module.
The builder gives the decoder ONE KV head (the latent), so
`serve.step_shape` and `serve.warm_up` reach the shapes the engine
really runs, a group of every query head, as they are.

A traced run also sums, over the steps of the traced slice, what the
engine reports of its attention and of its zero-compute experts
(``facts["latent"]``: steps, tokens, the live (slot, page) pairs and
the (query token, key) pairs of ONE attention sublayer, the pairs that
went to zero experts and all pairs; from the engine's own per-step
metrics), for the roofline of the attention kernel and the zero
experts' share.  A program whose steps lack those fields leaves
``facts["latent"]`` None.
"""

from __future__ import annotations

import gc
import time

import numpy as np

# the program's latent attention and gated experts, imported before the
# chip is taken: a program without them cannot build this cell's model,
# and says so here
from attention_tpu.engine import ServingEngine
from attention_tpu.models.latent_attention import LatentAttention  # noqa: F401
from attention_tpu.models.moe import GatedExperts  # noqa: F401

from benchmark import harness

serve_experts = harness.load_module("runners", "serve_experts")
serve_config, serve = serve_experts.serve_config, serve_experts.serve

# what `benchmark/sweep.py` asks of a runner
merged, serve_once, serve_metrics = (
    serve_config.merged, serve_config.serve_once,
    serve_config.serve_metrics)

FIELDS = ("kv_pages", "attn_qk_pairs", "expert_pairs_zero")


def latent_work(step_metrics, spans, facts: dict) -> dict | None:
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
    return dict(
        out, steps=len(traced),
        tokens=sum(m.decode_tokens + m.prefill_tokens for m in traced),
        expert_pairs=out["expert_pairs_zero"] + sum(
            m.expert_pairs_local + m.expert_pairs_absent for m in traced))


def run(cell: harness.Cell, *, clock=time.perf_counter, **kw) -> dict:
    """`serve_experts.run` with one more reading of the engine's
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
    facts["experts"] = serve_experts.expert_work(steps, ran["spans"], facts)
    facts["latent"] = latent_work(steps, ran["spans"], facts)
    return ran


def control(cell: harness.Cell, *, seeds, seconds: float, devices,
            sizes: dict | None = None, clock=time.perf_counter) -> list[dict]:
    """`serve.control` (the program's gap and the fp8 control's, at the
    cell's own size) with, for each seed, every sampled request's own
    largest and mean gap beside the window's, so that the statistic the
    limit is set on can be chosen from the readings."""
    sizes = sizes or {}
    config = merged(cell.config, sizes.get("config"))
    traffic = merged(cell.traffic, sizes.get("traffic"))
    pad_to, rows = serve.reference_shape(config, traffic)
    out = []
    for seed in seeds:
        got = serve_once(cell, config, traffic, seed=seed, seconds=seconds,
                         devices=devices, clock=clock,
                         spans=harness.Spans(clock))
        reference, records = got["reference"], got["records"]
        sample = serve.pick_sample(
            records, got["window"], int(traffic["check"]["sample_requests"]),
            seed)
        row = {"seed": seed, "requests": len(sample), "program.max": [],
               "program.mean": [], "control.fp8.max": [],
               "control.fp8.mean": [],
               "compiles_in_window": got["facts"]["compiles_in_window"]}
        t = clock()
        for rid in sample:
            prompt, tokens = records[rid]["prompt"], records[rid]["tokens"]
            exact, low = (reference.served_logits(
                got["params"], config, prompt, tokens, pad_to=pad_to,
                rows=rows, low_precision=p) for p in (False, "fp8"))
            for name, picked in (("program", tokens),
                                 ("control.fp8", low.argmax(axis=1))):
                gaps = reference.token_gaps(exact, picked)
                row[name + ".max"].append(float(gaps.max()))
                row[name + ".mean"].append(float(gaps.mean()))
        row["reference_s"] = clock() - t
        out.append(row)
        print(row, flush=True)
        del got, reference, records
        gc.collect()
    return out


def left_out(cell: harness.Cell, *, seed: int, length: int, rows: int = 128,
             sizes: dict | None = None) -> dict:
    """The controls that leave a piece of the mathematics out, on the
    reference alone: over one seeded sequence of ``length`` tokens, the
    widest and the mean gap by which the token that a reference WITHOUT
    the piece puts first lies below the whole reference's best, at the
    last ``rows`` positions (the reference's own best reads 0)."""
    import jax

    config = merged(cell.config, (sizes or {}).get("config"))
    reference = cell.reference()
    model = serve_config.decoder_from_config(config)
    params = jax.block_until_ready(
        serve.make_params(model, reference, seed))
    tokens = np.random.default_rng([int(seed), 0x1EF7]).integers(
        0, model.vocab, size=length).tolist()
    prompt, served = tokens[:length - rows + 1], tokens[length - rows + 1:]
    served = served + [0]          # `rows` positions predict `rows` tokens
    pad_to = -(-length // 128) * 128

    def logits(which):
        return reference.served_logits(params, config, prompt, served,
                                       pad_to=pad_to, rows=rows,
                                       low_precision=which)

    exact = logits(False)
    out = {"seed": seed, "length": length, "rows": rows}
    for which in ("fp8", *reference.LEFT_OUT):
        gaps = reference.token_gaps(exact, logits(which).argmax(axis=1))
        out[which] = {"max": float(gaps.max()), "mean": float(gaps.mean())}
    flips = reference.routing_flips(params, config, tokens, pad_to=pad_to)
    out["routing_flips"] = flips
    print(out, flush=True)
    return out
