"""A serving cell: requests from the general generator through
`ServingEngine.add_request` and the engine's own step loop, on one
thread, with the clock read where the engine hands a token out.

Set-up builds ONE engine (weights from the seed on the device, in the
float32 the program stores), drives it through every step shape the
cell's traffic can reach, fills the prefix cache with the shared
contexts where the traffic has them, and hands that same engine to the
window.  After the window the engine is freed and a seeded sample of
the requests it finished is teacher-forced through the plain reference.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

# the program under test, imported before the chip is taken: its import
# (orbax under `attention_tpu.models`) is most of a warm set-up, and runs
# slower beside the TPU runtime's threads
from attention_tpu.engine import EngineConfig, SamplingParams, ServingEngine
from attention_tpu.models import TinyDecoder
from attention_tpu.ops.ragged_paged import packed_bucket, recommended_q_tile

from benchmark import harness
from benchmark.reduce import startup


# -- building the system under test ------------------------------------------

def merged(base: dict, over: dict | None) -> dict:
    """``base`` with ``over`` laid on top, one level of nesting deep."""
    out = dict(base)
    for key, value in (over or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = dict(out[key], **value)
        else:
            out[key] = value
    return out


def build_model(config: dict):
    """The program's decoder at the configuration's sizes."""
    dim, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    if dim // heads != int(config["head_dim"]):
        raise ValueError("hidden_size / num_attention_heads != head_dim")
    if int(config["intermediate_size"]) != 4 * dim:
        raise ValueError("the program's MLP is 4x wide")
    return TinyDecoder(
        vocab=int(config["vocab_size"]), dim=dim,
        depth=int(config["num_hidden_layers"]), num_q_heads=heads,
        num_kv_heads=int(config["num_key_value_heads"]), impl="flash",
        dtype=jnp.dtype(config["torch_dtype"]),
        window=int(config["sliding_window"]), rope=True,
        rope_theta=float(config["rope_theta"]))


def make_params(model, reference, seed: int):
    """The model's weights from the seed: one jitted call, on the
    device, nothing made on the host."""
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(lambda k: reference.init_params(shapes, k))(key)


def chunk_sizes(traffic: dict, engine_cfg: dict) -> list[int]:
    """The prefill chunk lengths the traffic can reach: whole chunks,
    and what is left of a prompt (or of its unshared part) after them."""
    chunk = int(engine_cfg["prefill_chunk"])
    spec = traffic["prompt_tokens"]
    lengths = range(int(spec["min"]), int(spec["max"]) + 1)
    return sorted({n % chunk or chunk for n in lengths}
                  | {min(n, chunk) for n in lengths})


def step_shape(engine, decoding: int, chunk: int) -> tuple[int, int]:
    """The ``(width, q_tile)`` of the program the engine's ragged step
    runs for ``decoding`` decode rows beside one prefill chunk of
    ``chunk`` tokens (0: none): the arithmetic of
    `ServingEngine._run_ragged`, by the program's own functions."""
    cfg, model = engine.config, engine.model
    q_tile = recommended_q_tile(
        max(chunk, 1), model.num_q_heads // model.num_kv_heads,
        heads=model.num_q_heads, kv_heads=model.num_kv_heads,
        seq=cfg.max_seq_len, dim=model.dim // model.num_q_heads,
        batch=cfg.max_decode_batch + cfg.max_prefill_rows,
        dtype=cfg.cache_dtype or model.dtype)
    return packed_bucket(max(decoding + chunk, q_tile)), q_tile


def warm_up(engine, traffic: dict, engine_cfg: dict, rng,
            vocab: int) -> tuple[int, int]:
    """Drive the engine through every step shape the window can reach:
    beside 0..max_decode_batch decoding requests, a chunk of each query
    tile and no chunk at all.  The longest chunk of a tile reaches,
    over those counts, every width the tile has.  Returns the steps
    made and the number of shapes."""
    def prompt(n):
        return rng.integers(0, vocab, size=n).tolist()

    longest = {}
    for r in chunk_sizes(traffic, engine_cfg):
        longest[step_shape(engine, 0, r)[1]] = r
    chunks = sorted(longest.values())
    dmax = int(engine_cfg["max_decode_batch"])
    stay = 3 * dmax + 8 * len(chunks)      # more steps than are made
    seen, ids, steps = set(), [], 0
    for d in range(dmax + 1):
        for r in chunks + [0]:
            shape = step_shape(engine, d, r)
            if shape in seen or d + r == 0:
                continue
            seen.add(shape)
            if r:                  # one chunk, served and gone in a step
                engine.add_request(prompt(r), SamplingParams(max_tokens=1))
            engine.step()
            steps += 1
        if d < dmax:               # one more request that goes on decoding
            req = engine.add_request(
                prompt(chunks[0]), SamplingParams(max_tokens=stay))
            ids.append(req.request_id)
            engine.step()
            steps += 1
    assert steps < stay, (steps, stay)
    for rid in ids:
        engine.cancel(rid)
    return steps, len(seen)


def prefix_fill(engine, contexts) -> int:
    """Serve each shared context once, so that its pages are in the
    prefix cache when the window opens."""
    steps = 0
    for ctx in contexts:
        engine.add_request(ctx, SamplingParams(max_tokens=1))
        while engine.scheduler.has_work():
            engine.step()
            steps += 1
    return steps


# -- the load ------------------------------------------------------------------

class Load:
    """Feeds the generator's requests to the engine and keeps, for each,
    when it was due, when it was sent, and when each token came out."""

    def __init__(self, engine, generated: dict, clock, spans):
        self.engine, self.clock, self.spans = engine, clock, spans
        self.requests = generated["requests"]
        self.clients = generated["closed_clients"]
        self.next = 0
        self.idle_since: list[float] = []     # closed loop: idle clients
        self.records: dict[str, dict] = {}
        self.refused = 0
        self.t0 = 0.0
        engine.on_token = self._on_token
        engine.on_finish = self._on_finish

    def start(self, t0: float) -> None:
        self.t0 = t0
        self.idle_since = [t0] * self.clients

    def _on_token(self, req, token) -> None:
        rec = self.records[req.request_id]
        rec["token_times"].append(self.clock())
        rec["tokens"].append(int(token))

    def _on_finish(self, req) -> None:
        rec = self.records[req.request_id]
        rec["finished"] = self.clock()
        rec["prefix_cached_tokens"] = int(req.prefix_cached_tokens)
        if self.clients:
            self.idle_since.append(rec["finished"])

    def _send(self, spec: dict, due: float) -> None:
        rec = {"due": due, "prompt": spec["prompt"],
               "max_tokens": spec["max_tokens"], "token_times": [],
               "tokens": [], "finished": None, "prefix_cached_tokens": 0}
        self.records[spec["id"]] = rec
        try:
            with self.spans.span("bench.submit"):
                self.engine.add_request(
                    spec["prompt"],
                    SamplingParams(max_tokens=spec["max_tokens"]),
                    request_id=spec["id"])
        except Exception as e:  # noqa: BLE001 - a refusal counts, never ends the run
            self.refused += 1
            rec["refused"] = repr(e)
        rec["sent"] = self.clock()

    def submit_due(self, now: float) -> None:
        """Open loop: everything due by ``now``.  Closed loop: one
        request for every idle client, due when the client fell idle."""
        if self.clients:
            while self.idle_since and self.next < len(self.requests):
                due = self.idle_since.pop(0)
                self._send(self.requests[self.next], due)
                self.next += 1
            return
        while (self.next < len(self.requests)
               and self.t0 + self.requests[self.next]["due"] <= now):
            spec = self.requests[self.next]
            self._send(spec, self.t0 + spec["due"])
            self.next += 1

    def next_due(self) -> float | None:
        if self.clients or self.next >= len(self.requests):
            return None
        return self.t0 + self.requests[self.next]["due"]

    def waiting_for_first_token(self) -> bool:
        return any(not r["token_times"] and "refused" not in r
                   for r in self.records.values())


def drive(engine, load: Load, *, seconds: float, drain_seconds: float,
          clock, spans, tracer=None) -> tuple[float, float]:
    """The measured window: submit what is due, step while there is
    work.  Then (open loop) step on without new arrivals until every
    request that was sent has its first token, ``drain_seconds`` at
    most; nothing after the window's end counts toward a rate.
    Returns the window's start and end."""
    t0 = clock()
    load.start(t0)
    while True:
        now = clock()
        if now - t0 >= seconds:
            break
        if tracer is not None:
            tracer.tick(now - t0)
        load.submit_due(now)
        if engine.scheduler.has_work():
            with spans.span("bench.step"):
                engine.step()
        else:
            due = load.next_due()
            with spans.span("bench.idle"):
                time.sleep(min(0.002, max(0.0, due - now) if due else 0.002))
    t1 = clock()
    if tracer is not None:
        tracer.stop()
    load.submit_due(t1)   # due inside the last step: counted, and drained
    while (load.waiting_for_first_token() and engine.scheduler.has_work()
           and clock() - t1 < drain_seconds):
        engine.step()
    return t0, t1


# -- metrics ---------------------------------------------------------------------

def serve_metrics(records: dict, window: tuple[float, float]) -> dict:
    """The end-to-end numbers of a window from the per-request records.
    TPOT: every gap between consecutive tokens, pooled over the
    requests that completed in the window: its mean, median and 90th
    percentile (``tpot_mean_ms``, ``tpot_p50_ms``, ``tpot_p90_ms``);
    beside it each such request's own mean gap (``request_tpot_ms``).
    TTFT: first token out minus the time the request was due, over the
    requests due in the window; refused or never answered counts as
    missing (inf).  Rate: tokens handed out in the window over its
    length."""
    t0, t1 = window
    gaps, tpot, ttft, tokens_out = [], [], [], 0
    for rec in records.values():
        tokens_out += sum(1 for t in rec["token_times"] if t0 <= t <= t1)
        if rec["due"] < t1:
            ttft.append((rec["token_times"][0] - rec["due"]) * 1e3
                        if rec["token_times"] else float("inf"))
        if rec["finished"] is not None and rec["finished"] <= t1:
            times = rec["token_times"]
            gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:]))
            if len(times) > 1:
                tpot.append((times[-1] - times[0]) * 1e3 / (len(times) - 1))
    lag = [(rec["sent"] - rec["due"]) * 1e3 for rec in records.values()]
    return {
        "token_gaps_ms": gaps, "request_tpot_ms": tpot, "ttft_ms": ttft,
        "gen_lag_ms": lag,
        "tpot_mean_ms": sum(gaps) / len(gaps) if gaps else float("nan"),
        "tpot_p50_ms": harness.median(gaps),
        "tpot_p90_ms": harness.percentile(gaps, 90.0),
        "ttft_p90_ms": harness.percentile(ttft, 90.0),
        "out_tok_per_s": tokens_out / (t1 - t0),
        "tokens_out": tokens_out,
    }


# -- correct -----------------------------------------------------------------------

def pick_sample(records: dict, window, count: int, seed: int) -> list[str]:
    """A seeded sample of the requests finished in the window, the
    longest (prompt + served tokens) among them."""
    done = sorted(rid for rid, r in records.items()
                  if r["finished"] is not None and r["finished"] <= window[1])
    if not done:
        return []
    longest = max(done, key=lambda rid: (len(records[rid]["prompt"])
                                         + len(records[rid]["tokens"]), rid))
    rest = [rid for rid in done if rid != longest]
    rng = np.random.default_rng([int(seed), 0xC0DE])
    take = min(count - 1, len(rest))
    picked = [rest[i] for i in rng.choice(len(rest), size=take, replace=False)]
    return [longest] + sorted(picked)


def reference_shape(config: dict, traffic: dict) -> tuple[int, int]:
    """One static shape for the reference's pass over any request of
    the mix: rows = the longest output, length = the longest prompt
    (shared context included) plus that, rounded up to 128."""
    rows = int(traffic["output_tokens"]["max"])
    shared = traffic.get("shared_prefix") or {}
    longest = (int(traffic["prompt_tokens"]["max"])
               + int(shared.get("tokens", 0)))
    return -(-(longest + rows) // 128) * 128, rows


def compare_sample(reference, params, config, traffic, records, sample,
                   *, control: bool = False) -> dict:
    """Teacher-force the sampled requests through the reference: the
    widest gap by which a served token's logit lies below the
    reference's best.  With ``control``, also the gap of the token the
    lower precision puts first, at the same positions."""
    pad_to, rows = reference_shape(config, traffic)
    out = {"served_tokens": 0, "program_gap": 0.0, "control_gap": 0.0}
    for rid in sample:
        rec = records[rid]
        logits = reference.served_logits(
            params, config, rec["prompt"], rec["tokens"],
            pad_to=pad_to, rows=rows)
        out["served_tokens"] += len(rec["tokens"])
        out["program_gap"] = max(
            out["program_gap"], reference.widest_gap(logits, rec["tokens"]))
        if control:
            low = reference.served_logits(
                params, config, rec["prompt"], rec["tokens"],
                pad_to=pad_to, rows=rows, low_precision=True)
            out["control_gap"] = max(
                out["control_gap"],
                reference.widest_gap(logits, low.argmax(axis=1)))
    return out


# -- a run ---------------------------------------------------------------------------

def compile_log_summary(until: float) -> str:
    """What the program's compile log holds up to ``until`` (a
    `time.perf_counter` stamp), for the ``setup:`` line: the numbers the
    `startup.*` readers take from a traced run, and the three costliest
    (function, kind) rows, so that an untraced run says what its
    `setup_s` is made of.  Empty for a program without the log."""
    log = startup.log_until(until)
    if log is None:
        return ""
    costliest = "; ".join(
        f"{row['function']} {row['kind']} {row['seconds']:.2f} s x "
        f"{row['count']}" for row in log["by_function"][:3])
    return (f"; compile log: trace_s {log['trace_s']:.2f}, lower_s "
            f"{log['lower_s']:.2f}, compile_s {log['compile_s']:.2f}, "
            f"cache_misses {log['cache_misses']}, programs "
            f"{log['programs']}; costliest: {costliest}")


def serve_once(cell, config, traffic, *, seed, seconds, devices, clock,
               spans, trace=False, trace_dir="", t_start=None) -> dict:
    """Set-up, window and the reading of the device: everything of a
    run up to the comparison with the reference."""
    t_start = clock() if t_start is None else t_start
    parts = {"start_up_s": clock() - t_start}
    compiles = harness.CompileCounter()
    reference = cell.reference()
    model = build_model(config)
    t = clock()
    params = jax.block_until_ready(make_params(model, reference, seed))
    parts["weights_s"] = clock() - t
    t = clock()
    engine = ServingEngine(model, params, EngineConfig(**config["engine"]))
    rng = np.random.default_rng([int(seed), 0xA11])
    steps, shapes = warm_up(engine, traffic, config["engine"], rng,
                            model.vocab)
    parts["warm_up_s"], parts["warm_up_steps"] = clock() - t, steps
    parts["step_shapes"] = shapes
    t = clock()
    generator = harness.load_module("generators", traffic["generator"])
    generated = generator.generate(traffic, seed=seed, vocab=model.vocab)
    parts["traffic_s"] = clock() - t
    t = clock()
    parts["prefix_fill_steps"] = prefix_fill(engine, generated["contexts"])
    parts["prefix_fill_s"] = clock() - t
    parts["compiles"] = compiles.count
    load = Load(engine, generated, clock, spans)
    setup_s = clock() - t_start
    print("setup: " + ", ".join(
        f"{k} {v:.2f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in parts.items()) + f", setup_s {setup_s:.2f}"
        + compile_log_summary(time.perf_counter()))

    compiled_before = compiles.count
    steps_before = engine.current_step
    ends = seconds - float(traffic.get("trace_lead_seconds", 0.0))
    tracer = harness.SliceTracer(trace, spans, trace_dir, stop_after=ends,
        start_after=ends - float(traffic["trace_seconds"]))
    window = drive(engine, load, seconds=seconds,
                   drain_seconds=float(traffic.get("drain_seconds", 0.0)),
                   clock=clock, spans=spans, tracer=tracer)
    in_window = [m for m in engine.metrics.steps if m.step >= steps_before]
    slow = sorted(in_window, key=lambda m: -m.wall_s)[:5]
    print("slowest steps (ms, fetch wait ms, decode rows, prefill tokens, "
          "free pages, queued, preempted): " + "; ".join(
              f"#{m.step - steps_before} {m.wall_s * 1e3:.0f} "
              f"{(m.wall_s - m.host_overhead_s) * 1e3:.0f} "
              f"{m.num_decode_reqs} {m.prefill_tokens} {m.free_pages} "
              f"{m.queue_depth} {m.preempted}" for m in slow)
          + "; longest submit / idle span "
          f"{max(spans.durations('bench.submit'), default=0) * 1e3:.0f} / "
          f"{max(spans.durations('bench.idle'), default=0) * 1e3:.0f} ms"
          + f"; least free pages "
          f"{min((m.free_pages for m in in_window), default=-1)}, "
          f"prefix evictions {engine.allocator.prefix_evictions}")
    facts = {
        "traced_from": tracer.started_at,
        "compiles_in_window": compiles.count - compiled_before,
        "steps": len(spans.durations("bench.step")),
        "nonfinite_events": int(engine.nonfinite_events),
        "preemptions": int(engine.scheduler.num_preemptions),
        "engine_steps": engine.current_step - steps_before,
    }
    device = harness.device_block(devices)
    records, refused = load.records, load.refused
    # free the program's state before the reference runs
    engine.on_token = engine.on_finish = None
    del engine, load
    gc.collect()
    return {"params": params, "reference": reference, "records": records,
            "refused": refused, "window": window, "facts": facts,
            "device": device, "setup_s": setup_s}


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        devices, t_start: float, trace_dir: str, sizes: dict | None = None,
        clock=time.perf_counter) -> dict:
    sizes = sizes or {}
    config = merged(cell.config, sizes.get("config"))
    traffic = merged(cell.traffic, sizes.get("traffic"))
    spans = harness.Spans(clock)
    got = serve_once(cell, config, traffic, seed=seed, seconds=seconds,
                     devices=devices, clock=clock, spans=spans, trace=trace,
                     trace_dir=trace_dir, t_start=t_start)
    records, window, facts = got["records"], got["window"], got["facts"]
    m = serve_metrics(records, window)
    due = len(m["ttft_ms"])
    missing = sum(1 for x in m["ttft_ms"] if x == float("inf"))
    done = sum(1 for r in records.values()
               if r["finished"] is not None and r["finished"] <= window[1])
    print(f"window: {window[1] - window[0]:.3f} s, {facts['steps']} steps, "
          f"{due} requests due, {done} finished, {missing} unanswered, "
          f"{got['refused']} refused, {m['tokens_out']} tokens out")
    gaps = m["token_gaps_ms"]
    print(f"tpot: {len(gaps)} gaps of {len(m['request_tpot_ms'])} "
          f"requests, mean {m['tpot_mean_ms']:.4f} median "
          f"{m['tpot_p50_ms']:.4f} p90 {m['tpot_p90_ms']:.4f}; median over "
          f"requests of a request's mean gap "
          f"{harness.median(m['request_tpot_ms']):.4f}; "
          f"ttft: {due} samples, p50 "
          f"{harness.median(m['ttft_ms']):.2f} p90 {m['ttft_p90_ms']:.2f} ms; "
          f"generator lag p95 "
          f"{harness.percentile(m['gen_lag_ms'], 95.0):.3f} ms; "
          f"out_tok_per_s {m['out_tok_per_s']:.4f}")

    checks = harness.Checks()
    t = clock()
    sample = pick_sample(records, window,
                         int(traffic["check"]["sample_requests"]), seed)
    compared = compare_sample(got["reference"], got["params"], config,
                              traffic, records, sample)
    print(f"reference: {len(sample)} requests, {compared['served_tokens']} "
          f"served tokens in {clock() - t:.2f} s")
    limit = traffic["check"]["logit_gap_limit"]
    checks.add("widest_logit_gap", compared["program_gap"] if sample
               else float("nan"), float("inf") if limit is None else limit)
    short = sum(1 for rid in records
                if records[rid]["finished"] is not None
                and len(records[rid]["tokens"]) != records[rid]["max_tokens"])
    checks.add("finished_with_wrong_token_count", short, 0)
    checks.add("nonfinite_logit_rows", facts["nonfinite_events"], 0)
    checks.add("compiles_in_window", facts["compiles_in_window"], 0)

    facts.update(metrics=m, records=records, chips=len(devices))
    return {
        "checks": checks, "attempted": due,
        "failed": missing + got["refused"],
        "values": {"tpot_mean_ms": m["tpot_mean_ms"],
                   "tpot_p50_ms": m["tpot_p50_ms"],
                   "tpot_p90_ms": m["tpot_p90_ms"],
                   "out_tok_per_s": m["out_tok_per_s"],
                   "setup_s": got["setup_s"]},
        "device": got["device"], "spans": spans, "window": window,
        "facts": facts,
    }


def control(cell: harness.Cell, *, seeds, seconds: float, devices,
            sizes: dict | None = None, clock=time.perf_counter) -> list[dict]:
    """For each seed, a short window at the cell's own load, then the
    widest gap as the program gives it and as the control gives it (the
    reference in fp8, read at the same prompts and served tokens)."""
    sizes = sizes or {}
    config = merged(cell.config, sizes.get("config"))
    traffic = merged(cell.traffic, sizes.get("traffic"))
    out = []
    for seed in seeds:
        got = serve_once(cell, config, traffic, seed=seed, seconds=seconds,
                         devices=devices, clock=clock,
                         spans=harness.Spans(clock))
        sample = pick_sample(got["records"], got["window"],
                             int(traffic["check"]["sample_requests"]), seed)
        compared = compare_sample(got["reference"], got["params"], config,
                                  traffic, got["records"], sample,
                                  control=True)
        m = serve_metrics(got["records"], got["window"])
        out.append({"seed": seed, "requests": len(sample),
                    "served_tokens": compared["served_tokens"],
                    "program.widest_logit_gap": compared["program_gap"],
                    "control.widest_logit_gap": compared["control_gap"],
                    "tpot_p50_ms": m["tpot_p50_ms"],
                    "compiles_in_window":
                        got["facts"]["compiles_in_window"]})
        print(out[-1], flush=True)
        del got
        gc.collect()
    return out
