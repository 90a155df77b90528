"""A serving cell whose model has window and full attention layers
side by side, each kind with a page space of its own, and sparse expert
layers: load, warm-up, window, metrics and the comparison with the
reference are `runners/serve.py`'s own, with the program's builder and
the experts' counts as `runners/serve_experts.py` has them, on a
private copy of that module.  The step shapes set-up walks are the
ENGINE's word (`ServingEngine.step_shape`: the configuration may hold
every chunk's step to the whole chunks' query tile), not
`serve.step_shape`'s arithmetic.

A traced run also sums, over the steps of the traced slice, what the
engine reports of its two page spaces (``facts["window"]``: steps,
tokens, the (query token, key) pairs and the pages that hold an
attended key, ONE full sublayer's and ONE window sublayer's apart, the
pages each space has in use after each step, the window pages given
back, and how many layers there are of each kind), for the window's
share of the keys, the window space's share of the pages and the
roofline of the attention kernel.  A program whose steps lack those
fields leaves ``facts["window"]`` None.
"""

from __future__ import annotations

import gc
import time

from attention_tpu.engine import ServingEngine

from benchmark import harness

serve_experts = harness.load_module("runners", "serve_experts")
serve_config, serve = serve_experts.serve_config, serve_experts.serve
serve.step_shape = lambda engine, decoding, chunk: engine.step_shape(
    decoding, chunk)

# what `benchmark/sweep.py` asks of a runner
merged, serve_once, serve_metrics = (
    serve_config.merged, serve_config.serve_once,
    serve_config.serve_metrics)

FIELDS = ("attn_qk_pairs", "attn_qk_pairs_window", "attn_band_pages",
          "attn_band_pages_window", "kv_pages", "kv_pages_window",
          "used_pages", "window_used_pages", "window_pages_released")

#: the reference's controls: the precision below the one the
#: configuration states, and three pieces of the mathematics changed
CONTROLS = ("fp8", "window_as_full", "rope_on_full", "no_gate")

SLIDING = "sliding_attention"


def layer_kinds(config: dict) -> list[str]:
    served = config.get("served_layers",
                        range(int(config["num_hidden_layers"])))
    return [config["layer_types"][i] for i in served]


def window_work(step_metrics, spans, facts: dict,
                config: dict) -> dict | None:
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
    if not out["attn_qk_pairs_window"]:
        return None
    kinds = layer_kinds(config)
    return dict(
        out, steps=len(traced),
        tokens=sum(m.decode_tokens + m.prefill_tokens for m in traced),
        attn_qk_pairs_full=(out["attn_qk_pairs"]
                            - out["attn_qk_pairs_window"]),
        attn_band_pages_full=(out["attn_band_pages"]
                              - out["attn_band_pages_window"]),
        window_layers=kinds.count(SLIDING),
        full_layers=len(kinds) - kinds.count(SLIDING))


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
    config = merged(cell.config, (kw.get("sizes") or {}).get("config"))
    in_window = steps[len(steps) - facts["engine_steps"]:]
    used = [(m.used_pages, getattr(m, "window_used_pages", 0))
            for m in in_window]
    print("page spaces: most pages in use in the window and its drain, "
          f"full {max((full for full, _ in used), default=0)}, "
          f"window {max((window for _, window in used), default=0)}; "
          "window pages given back "
          f"{sum(getattr(m, 'window_pages_released', 0) for m in in_window)}")
    facts["experts"] = serve_experts.expert_work(steps, ran["spans"], facts)
    facts["window"] = window_work(steps, ran["spans"], facts, config)
    return ran


def control(cell: harness.Cell, *, seeds, seconds: float, devices,
            sizes: dict | None = None, clock=time.perf_counter) -> list[dict]:
    """The program's gap and every control's of `CONTROLS` at the
    cell's own size, the plain reference in the program's place: for
    each seed a window, then over the first ``check.control_requests``
    of the sampled requests (a pass of the reference over 33k positions
    takes seconds) the largest of a request's mean gap."""
    sizes = sizes or {}
    config = merged(cell.config, sizes.get("config"))
    traffic = merged(cell.traffic, sizes.get("traffic"))
    pad_to, rows = serve.reference_shape(config, traffic)
    check = traffic["check"]
    out = []
    for seed in seeds:
        got = serve_once(cell, config, traffic, seed=seed, seconds=seconds,
                         devices=devices, clock=clock,
                         spans=harness.Spans(clock))
        reference, records = got["reference"], got["records"]
        sample = serve.pick_sample(
            records, got["window"], int(check["sample_requests"]), seed)
        sample = sample[:int(check.get("control_requests", len(sample)))]
        row = {"seed": seed, "requests": len(sample),
               "program.widest_logit_gap": 0.0,
               **{f"control.{c}.widest_logit_gap": 0.0 for c in CONTROLS},
               "compiles_in_window": got["facts"]["compiles_in_window"]}
        t = clock()
        for rid in sample:
            prompt, tokens = records[rid]["prompt"], records[rid]["tokens"]

            def logits(low):
                return reference.served_logits(
                    got["params"], config, prompt, tokens, pad_to=pad_to,
                    rows=rows, low_precision=low)

            exact = logits(False)
            gaps = {"program": reference.widest_gap(exact, tokens)}
            for c in CONTROLS:
                gaps[f"control.{c}"] = reference.widest_gap(
                    exact, logits(c).argmax(axis=1))
            for name, gap in gaps.items():
                key = name + ".widest_logit_gap"
                row[key] = max(row[key], gap)
        row["reference_s"] = clock() - t
        out.append(row)
        print(row, flush=True)
        del got, reference, records
        gc.collect()
    return out
