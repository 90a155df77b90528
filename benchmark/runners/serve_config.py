"""A serving cell whose model the PROGRAM builds from the
configuration's keys (`attention_tpu.models.decoder_from_config`):
layer kinds per layer, gated MLPs, recurrent layers.  Load, warm-up,
window, metrics and the comparison with the reference are
`runners/serve.py`'s own, on a private copy of that module whose
``build_model`` is the program's builder.

A traced run also counts, over the steps of the traced slice, what the
engine handed the recurrent layers (``facts["recurrent"]``: steps,
tokens, slot-steps; from the engine's own per-step metrics), for the
roofline of their kernel.
"""

from __future__ import annotations

import gc
import time

from attention_tpu.engine import ServingEngine
from attention_tpu.models import decoder_from_config

from benchmark import harness

serve = harness.load_module("runners", "serve")
serve.build_model = decoder_from_config

# what `benchmark/sweep.py` and `benchmark/control.py` ask of a runner
merged, serve_once, serve_metrics = (serve.merged, serve.serve_once,
                                     serve.serve_metrics)

#: the reference's controls, one for each precision the configuration
#: states: bf16 operands of the weight matmuls, a float32 recurrent state
CONTROLS = ("fp8", "state_bf16")


def control(cell: harness.Cell, *, seeds, seconds: float, devices,
            sizes: dict | None = None, clock=time.perf_counter) -> list[dict]:
    """`serve.control` with every control of `CONTROLS`: for each seed
    a short window at the cell's own load, then the widest gap as the
    program gives it and as each control gives it, at the same prompts
    and served tokens.  The limit of `correct` lies under every control
    that this comparison can see (at the cell's size a bfloat16 state
    moves no best token and reads 0: PERF.md section 6)."""
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
        row = {"seed": seed, "requests": len(sample),
               "program.widest_logit_gap": 0.0,
               **{f"control.{c}.widest_logit_gap": 0.0 for c in CONTROLS},
               "compiles_in_window": got["facts"]["compiles_in_window"]}
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
        out.append(row)
        print(row, flush=True)
        del got, reference, records
        gc.collect()
    return out


def recurrent_work(step_metrics, spans, facts: dict) -> dict | None:
    """Steps, tokens and slot-steps of the traced slice: the window's
    i-th ``bench.step`` span is the engine's step ``first + i``."""
    since = facts.get("traced_from")
    if since is None:
        return None
    first = len(step_metrics) - facts["engine_steps"]
    starts = [a for name, a, _ in spans.records if name == "bench.step"]
    traced = [step_metrics[first + i] for i, a in enumerate(starts)
              if a >= since]
    return {
        "steps": len(traced),
        "tokens": sum(m.decode_tokens + m.prefill_tokens for m in traced),
        "slot_steps": sum(m.num_decode_reqs + m.num_prefill_reqs
                          for m in traced),
    }


def run(cell: harness.Cell, *, clock=time.perf_counter, **kw) -> dict:
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
    ran["facts"]["recurrent"] = recurrent_work(
        kept[0].steps, ran["spans"], ran["facts"])
    return ran
