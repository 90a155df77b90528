"""A serving cell whose latent attention CHOOSES its keys (a lightning
indexer with a cache of its own beside the latent pool) and routes
over gated experts: load, warm-up, window, metrics and the comparison
with the reference are `runners/serve.py`'s own, with the program's
builder and the experts' counts as `runners/serve_latent.py` has them,
on a private copy of that module.

Every run compares, beside the logits, two exact numbers: the keys the
DEVICE attended in the window's steps against the keys the rule
selects, counted on the host from each step's own lengths
(``keys_attended_off_rule``, limit 0), and the compiles in the window.

A traced run also sums, over the steps of the traced slice, what the
engine reports of its selectors and its attention (``facts["sparse"]``:
steps, tokens, the live (slot, page) pairs, the causal (query token,
key) pairs ONE sublayer's selector scores, the pairs attention attended
over ALL sublayers as the device counted them, and the cache rows a
sublayer cannot do without), for the rooflines of the scoring and the
attention kernels and the selected share.  A program whose steps lack
those fields leaves ``facts["sparse"]`` None.
"""

from __future__ import annotations

import gc
import time

# the program's selector, imported before the chip is taken: a program
# without it cannot build this cell's model, and says so here
from attention_tpu.engine import ServingEngine
from attention_tpu.ops.sparse_index import select_keys  # noqa: F401

from benchmark import harness

serve_latent = harness.load_module("runners", "serve_latent")
serve_experts, serve = serve_latent.serve_experts, serve_latent.serve

# what `benchmark/sweep.py` asks of a runner
merged, serve_once, serve_metrics = (
    serve_latent.merged, serve_latent.serve_once, serve_latent.serve_metrics)

FIELDS = ("kv_pages", "attn_qk_pairs", "attn_keys_attended",
          "attn_keys_selected")


def sparse_work(step_metrics, spans, facts: dict, config: dict) -> dict | None:
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
    top_k, page = int(config["index_topk"]), int(config["engine"]["page_size"])
    return dict(
        {f: sum(getattr(m, f) for m in traced) for f in FIELDS},
        steps=len(traced), sublayers=int(config["num_hidden_layers"]),
        tokens=sum(m.decode_tokens + m.prefill_tokens for m in traced),
        # ``index_topk`` rows a busy slot, no more than its pages hold:
        # the slot-by-slot sum of min(index_topk, kv_len) wherever a
        # step's slots lie on one side of index_topk, as here
        kept_rows=sum(min(top_k * (m.num_decode_reqs + m.num_prefill_reqs),
                          m.kv_pages * page) for m in traced))


def run(cell: harness.Cell, *, clock=time.perf_counter, **kw) -> dict:
    """`serve_latent.run` with one more reading of the engine's
    per-step metrics and one more exact check (not a wrapper around it:
    one engine hook)."""
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
    sublayers = int(config["num_hidden_layers"])
    off = sum(abs(m.attn_keys_attended - sublayers * m.attn_keys_selected)
              for m in in_window)
    attended = sum(m.attn_keys_attended for m in in_window)
    print(f"selection: {attended} keys attended in {len(in_window)} steps of "
          f"the window and its drain, {off} off the rule's count "
          f"({sublayers} sublayers x the rows' min(index_topk, keys seen))")
    ran["checks"].add("keys_attended_off_rule",
                      off if attended else float("nan"), 0)
    facts["experts"] = serve_experts.expert_work(steps, ran["spans"], facts)
    facts["latent"] = serve_latent.latent_work(steps, ran["spans"], facts)
    facts["sparse"] = sparse_work(steps, ran["spans"], facts, config)
    return ran


def control(cell: harness.Cell, *, seeds, seconds: float, devices,
            sizes: dict | None = None, clock=time.perf_counter) -> list[dict]:
    """The program's gap and TWO controls' at the cell's own size, the
    plain reference in the program's place: in fp8, the precision below
    the one the configuration states, and attending the NEWEST
    ``index_topk`` keys in place of the chosen ones (the SELECTION
    control).  For each seed, a window, then for the first
    ``check.control_requests`` of the sampled requests (a pass of the
    reference over 50k positions takes tens of seconds) each one's
    largest and mean gap."""
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
        names = ("program", "control.fp8", "control.newest")
        row = {"seed": seed, "requests": len(sample),
               **{f"{n}.{stat}": [] for n in names
                  for stat in ("max", "mean")},
               "compiles_in_window": got["facts"]["compiles_in_window"]}
        t = clock()
        for rid in sample:
            prompt, tokens = records[rid]["prompt"], records[rid]["tokens"]
            exact, low, newest = (reference.served_logits(
                got["params"], config, prompt, tokens, pad_to=pad_to,
                rows=rows, low_precision=p) for p in (False, "fp8", "newest"))
            for name, picked in zip(names, (tokens, low.argmax(axis=1),
                                            newest.argmax(axis=1))):
                gaps = reference.token_gaps(exact, picked)
                row[name + ".max"].append(float(gaps.max()))
                row[name + ".mean"].append(float(gaps.mean()))
        row["reference_s"] = clock() - t
        out.append(row)
        print(row, flush=True)
        del got, reference, records
        gc.collect()
    return out
