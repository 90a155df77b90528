"""Serving metrics: per-step counters and per-request latency records.

The observability layer the ROADMAP's "serve heavy traffic" goal needs:
every engine step emits a `StepMetrics` row (batch composition, queue
depth, page utilization, cumulative prefix-cache and preemption
counters) and every finished request a `RequestMetrics` row (TTFT,
TPOT, prefix reuse, preemption count).  Both are plain dataclasses with
``to_dict``/JSON helpers; :meth:`EngineMetrics.to_run_record` folds the
aggregate into a `utils.profiling.RunRecord` so engine runs land in the
same JSONL streams (`profiling.append_jsonl`) as every kernel
benchmark.

These rows are also re-emitted through the unified telemetry registry
(`attention_tpu.obs`): every recorded step updates the ``engine.*``
counters/gauges/histograms and every RunRecord goes through
``obs.record_run`` — so ``cli obs report``/``obs.prom_text()`` show
engine state alongside op-dispatch and tuning counters.  Emission is
no-op while telemetry is disabled (the default); these dataclasses
stay the source of truth for the deterministic per-run JSON.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any

from attention_tpu import obs
from attention_tpu.obs.naming import (
    SERIES_ENGINE_TPOT_DIGEST,
    SERIES_ENGINE_TTFT_DIGEST,
)
from attention_tpu.obs.quantile import QuantileDigest
from attention_tpu.utils.profiling import RunRecord

_STEPS = obs.counter("engine.steps.total", "engine steps recorded")
_DECODE_TOKENS = obs.counter("engine.tokens.decode",
                             "decode tokens scheduled")
_PREFILL_TOKENS = obs.counter("engine.tokens.prefill",
                              "real prefill tokens scheduled")
_FINISHED = obs.counter("engine.requests.finished", "requests finished")
_QUEUE = obs.gauge("engine.queue.depth", "waiting requests after step")
_RUNNING = obs.gauge("engine.queue.running", "running requests after step")
_PAGES_USED = obs.gauge("engine.pages.used",
                        "pool pages in use (pool=window: the second page "
                        "space's)")
_PAGES_FREE = obs.gauge("engine.pages.free",
                        "pool pages free (pool=window: the second page "
                        "space's)")
_STEP_WALL = obs.histogram("engine.step.wall_ms", "engine step wall ms",
                           buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25,
                                    50, 100, 250, 500, 1000))
_TTFT = obs.histogram("engine.request.ttft_steps",
                      "steps from arrival to first token",
                      buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
_TPOT = obs.histogram("engine.request.tpot_steps",
                      "mean steps per output token after the first",
                      buckets=(1, 1.5, 2, 3, 4, 8, 16, 32))
_PAD_TOKENS = obs.counter(
    "engine.step.pad_tokens",
    "pad tokens dispatched (packed/padded width minus real tokens)")
_RAGGED_OCC = obs.gauge(
    "engine.step.ragged_occupancy",
    "real-token fraction of the last non-empty step's launch width")
_TTFT_DIG = obs.digest(SERIES_ENGINE_TTFT_DIGEST,
                       "TTFT quantile digest (engine steps)")
_TPOT_DIG = obs.digest(SERIES_ENGINE_TPOT_DIGEST,
                       "TPOT quantile digest (steps/token)")


@dataclasses.dataclass
class StepMetrics:
    """One scheduler/engine step."""

    step: int
    wall_s: float = 0.0
    num_decode_reqs: int = 0
    num_prefill_reqs: int = 0
    decode_tokens: int = 0
    prefill_tokens: int = 0          # real prompt tokens (pads excluded)
    queue_depth: int = 0             # waiting (incl. preempted) after step
    running: int = 0
    admitted: int = 0
    preempted: int = 0
    finished: int = 0
    timed_out: int = 0               # deadline-sweep expiries this step
    free_pages: int = 0
    used_pages: int = 0
    page_utilization: float = 0.0
    prefix_hit_tokens_total: int = 0  # cumulative
    preemptions_total: int = 0        # cumulative
    pad_tokens: int = 0              # pads dispatched this step
    ragged_occupancy: float = 0.0    # real / dispatched width
    kv_pages: int = 0                # (slot, page) pairs the kernel walked
    # the step's spans of ONE token (decode rows) that the attention
    # kernel served at a tile of their own beside a wider one
    # (`ops.ragged_paged.span_tile_rows`: groups that are a multiple
    # of 8): 0 on a step whose tile is the one-token tile (every
    # decode-only step), at another group, and where a list of rows is
    # attended and no tile is
    own_tile_spans: int = 0
    # the grid steps of the step's page-walking attention kernels,
    # summed over the attention sublayers: a sublayer's work items
    # (``kv_pages`` of its kind; a block's pages in the row-blocked
    # form) times the blocks its KV heads are carried in
    # (`ops.ragged_paged.head_block`: one block where a grid step
    # carries every head); 0 where a list of rows is attended
    ragged_grid_steps: int = 0
    # (query token, key) pairs one attention sublayer attends: over the
    # step's slots, each query token times the keys it reaches
    attn_qk_pairs: int = 0
    # a model whose attention CHOOSES its keys (`ops.sparse_index`):
    # the (query token, key) pairs the attention kernels' masks let
    # through, counted on the DEVICE and summed over the sublayers;
    # ``attn_qk_pairs`` above is what a sublayer's selector scored,
    # and ``attn_keys_selected`` what ONE sublayer keeps by the rule
    # (each query row the ``index_topk`` best of the keys it sees),
    # counted on the host from the step's own lengths
    attn_keys_attended: int = 0
    attn_keys_selected: int = 0
    # the cache rows ONE attention sublayer's kernels read this step,
    # counted on the host by the device's rule: a kernel that walks
    # pages reads every row of every live (slot, page) pair
    # (``kv_pages`` x the page size), one that attends a list of rows
    # the list (``attn_keys_selected``; a listed row is moved as the
    # 8-row memory tile it lies in, which this count does not weigh)
    attn_rows_read: int = 0
    host_overhead_s: float = 0.0     # wall minus the logits device sync
    # a step in which JAX traced, lowered or compiled something (a
    # shape the process had not run: `obs.compiles`): the seconds that
    # took on the host, nested traces counted once, and the programs
    # compiled or loaded from the persistent cache; 0 / 0 otherwise
    compile_s: float = 0.0
    compiled_programs: int = 0
    # a model with expert layers (`models.moe.LatentExperts`,
    # `GatedExperts`), summed over them: token-expert pairs of the
    # experts held here, pairs of experts held elsewhere, the most
    # pairs one held expert took, the (layer, held expert) that took
    # any, and the pairs that went to zero-compute experts
    expert_pairs_local: int = 0
    expert_pairs_absent: int = 0
    expert_load_max: int = 0
    experts_reached: int = 0
    expert_pairs_zero: int = 0
    # a model of TWO page spaces (sliding-window layers beside
    # full-attention layers): ``kv_pages``, ``attn_qk_pairs`` and
    # ``attn_rows_read`` above are then the SUM of one sublayer of each
    # kind, and these the window kind's part of the first two; the
    # pages that hold a key some row attends, whatever tile the kernel
    # walks in (one sublayer of each kind, and the window kind's); the
    # window pool's pages in use (requests' and the prefix cache's) and
    # free after the step, as ``used_pages`` / ``free_pages`` are the
    # full layers' pool's; and the window pages the running requests'
    # bands slid past and gave back before the step
    kv_pages_window: int = 0
    attn_qk_pairs_window: int = 0
    attn_band_pages: int = 0
    attn_band_pages_window: int = 0
    window_used_pages: int = 0
    window_free_pages: int = 0
    window_pages_released: int = 0

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict())


@dataclasses.dataclass
class RequestMetrics:
    """One finished request.  Step-denominated latencies are exact and
    deterministic (the unit of serving time is the engine step);
    wall-clock figures ride along for throughput reporting."""

    request_id: str
    arrival_step: int
    first_scheduled_step: int
    first_token_step: int
    finish_step: int
    prompt_tokens: int
    output_tokens: int
    prefix_cached_tokens: int
    preemptions: int
    ttft_s: float
    finish_s: float
    # ttft_s split at the request's first admission: seconds in the
    # queue, then seconds from admission to the first token
    queue_wait_s: float = 0.0
    prefill_s: float = 0.0

    @property
    def ttft_steps(self) -> int:
        return self.first_token_step - self.arrival_step

    @property
    def tpot_steps(self) -> float:
        """Mean steps per output token after the first."""
        if self.output_tokens <= 1:
            return 0.0
        return (self.finish_step - self.first_token_step) \
            / (self.output_tokens - 1)

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["ttft_steps"] = self.ttft_steps
        d["tpot_steps"] = round(self.tpot_steps, 3)
        return d


class EngineMetrics:
    """Collects step and request rows over an engine's lifetime."""

    def __init__(self, *, table_entries: int = 0, held_experts: int = 0,
                 sparse_sublayers: int = 0):
        # slots x table width: what `StepMetrics.kv_pages` is a share of
        self.table_entries = table_entries
        # attention sublayers that choose their keys: the scored
        # pairs' multiple in `selected_key_share`
        self.sparse_sublayers = sparse_sublayers
        # experts an expert layer holds: the mean load's denominator
        self.held_experts = held_experts
        self.steps: list[StepMetrics] = []
        self.requests: list[RequestMetrics] = []
        # the (width, q_tile) of every step that compiled
        self.compiled_shapes: set[tuple[int, int]] = set()
        self._t0 = time.perf_counter()

    def record_step(self, m: StepMetrics) -> None:
        self.steps.append(m)
        if obs.enabled():
            _STEPS.inc()
            if m.decode_tokens:
                _DECODE_TOKENS.inc(m.decode_tokens)
            if m.prefill_tokens:
                _PREFILL_TOKENS.inc(m.prefill_tokens)
            _QUEUE.set(m.queue_depth)
            _RUNNING.set(m.running)
            _PAGES_USED.set(m.used_pages)
            _PAGES_FREE.set(m.free_pages)
            if m.window_used_pages or m.window_free_pages:
                _PAGES_USED.set(m.window_used_pages, pool="window")
                _PAGES_FREE.set(m.window_free_pages, pool="window")
            _STEP_WALL.observe(m.wall_s * 1e3)
            if m.pad_tokens:
                _PAD_TOKENS.inc(m.pad_tokens)
            if m.decode_tokens or m.prefill_tokens:
                _RAGGED_OCC.set(m.ragged_occupancy)

    def record_request(self, m: RequestMetrics) -> None:
        self.requests.append(m)
        if obs.enabled():
            _FINISHED.inc()
            _TTFT.observe(m.ttft_steps)
            _TTFT_DIG.observe(m.ttft_steps)
            if m.output_tokens > 1:
                _TPOT.observe(m.tpot_steps)
                _TPOT_DIG.observe(m.tpot_steps)

    def latency_digests(self) -> tuple[QuantileDigest, QuantileDigest]:
        """(ttft, tpot) digests rebuilt from the deterministic request
        rows — works with telemetry disabled, so summaries never depend
        on the obs flag."""
        ttft, tpot = QuantileDigest(), QuantileDigest()
        for r in self.requests:
            ttft.add(max(r.ttft_steps, 0))
            if r.output_tokens > 1:
                tpot.add(r.tpot_steps)
        return ttft, tpot

    def summary(self) -> dict[str, Any]:
        wall = time.perf_counter() - self._t0
        out_tokens = sum(r.output_tokens for r in self.requests)
        prompt_tokens = sum(r.prompt_tokens for r in self.requests)
        cached = sum(r.prefix_cached_tokens for r in self.requests)
        ttfts = [r.ttft_steps for r in self.requests]
        tpots = [r.tpot_steps for r in self.requests if r.output_tokens > 1]
        busy = [s for s in self.steps if s.decode_tokens or s.prefill_tokens]
        mixed = [s for s in busy if s.decode_tokens and s.prefill_tokens]
        ttft_dig, tpot_dig = self.latency_digests()
        pairs_local = sum(s.expert_pairs_local for s in self.steps)
        pairs_zero = sum(s.expert_pairs_zero for s in self.steps)
        pairs_all = pairs_local + pairs_zero + sum(
            s.expert_pairs_absent for s in self.steps)
        scored = self.sparse_sublayers * sum(
            s.attn_qk_pairs for s in self.steps)
        wait_dig, prefill_dig = QuantileDigest(), QuantileDigest()
        for r in self.requests:
            wait_dig.add(r.queue_wait_s * 1e3)
            prefill_dig.add(r.prefill_s * 1e3)
        attended = sum(s.attn_keys_attended for s in self.steps)
        # a selector's model alone: the rows its attention kernels read
        # for every key they attended (1.0: the chosen rows and no
        # other; context / top_k for a walk of every page that masks)
        chosen = {"rows_read_per_key_attended": round(
            self.sparse_sublayers * sum(s.attn_rows_read for s in self.steps)
            / attended, 4)} if attended else {}
        return {
            "num_requests": len(self.requests),
            "num_steps": len(self.steps),
            "wall_s": round(wall, 4),
            "prompt_tokens": prompt_tokens,
            "output_tokens": out_tokens,
            "tokens_per_s": round(out_tokens / wall, 2) if wall else 0.0,
            "prefix_cached_tokens": cached,
            "prefix_cache_hit_rate": round(
                cached / prompt_tokens, 4) if prompt_tokens else 0.0,
            "mean_ttft_steps": round(
                sum(ttfts) / len(ttfts), 2) if ttfts else 0.0,
            "max_ttft_steps": max(ttfts) if ttfts else 0,
            # digest-backed quantiles (bounded relative error, not the
            # fixed Prometheus buckets) — the SLO accounting surface
            "ttft_p50_steps": round(ttft_dig.quantile(0.5), 3),
            "ttft_p99_steps": round(ttft_dig.quantile(0.99), 3),
            # wall-clock split of TTFT at first admission (the
            # operator's view with no profiler attached)
            "queue_wait_p50_ms": round(wait_dig.quantile(0.5), 3),
            "queue_wait_p90_ms": round(wait_dig.quantile(0.9), 3),
            "prefill_p50_ms": round(prefill_dig.quantile(0.5), 3),
            "prefill_p90_ms": round(prefill_dig.quantile(0.9), 3),
            "mean_tpot_steps": round(
                sum(tpots) / len(tpots), 3) if tpots else 0.0,
            "tpot_p50_steps": round(tpot_dig.quantile(0.5), 3),
            "tpot_p99_steps": round(tpot_dig.quantile(0.99), 3),
            "mixed_batch_steps": len(mixed),
            "mean_batched_tokens_per_step": round(
                sum(s.decode_tokens + s.prefill_tokens for s in busy)
                / len(busy), 2) if busy else 0.0,
            "peak_page_utilization": round(
                max((s.page_utilization for s in self.steps), default=0.0),
                4),
            "preemptions": self.steps[-1].preemptions_total
            if self.steps else 0,
            "pad_tokens_total": sum(s.pad_tokens for s in self.steps),
            "mean_ragged_occupancy": round(
                sum(s.ragged_occupancy for s in busy) / len(busy), 4)
            if busy else 0.0,
            # share of the page-table entries the attention kernel's
            # grid walked: 1.0 is every slot full to the table's end
            "mean_kv_page_share": round(
                sum(s.kv_pages for s in busy)
                / (len(busy) * self.table_entries), 4)
            if busy and self.table_entries else 0.0,
            # (query token, key) pairs an attention sublayer attends a
            # busy step
            "mean_attn_qk_pairs": round(
                sum(s.attn_qk_pairs for s in busy) / len(busy), 1)
            if busy else 0.0,
            # of the pairs the selectors scored, the share attention
            # attended: 1.0 while every row sees fewer keys than it
            # may keep, top_k / context far beyond (0: no selector)
            "selected_key_share": round(attended / scored, 4)
            if scored else 0.0,
            **chosen,
            # expert layers: the share of routed pairs whose expert is
            # held here (1 / shares at an even router), and the fullest
            # held expert's pairs over the mean's, both over all steps
            "local_pair_share": round(
                pairs_local / pairs_all, 4) if pairs_all else 0.0,
            # the share that went to zero-compute experts, which cost
            # nothing wherever the token is
            "zero_pair_share": round(
                pairs_zero / pairs_all, 4) if pairs_all else 0.0,
            "expert_load_max_over_mean": round(
                sum(s.expert_load_max for s in self.steps)
                * self.held_experts / pairs_local, 4)
            if pairs_local and self.held_experts else 0.0,
            "mean_host_overhead_ms": round(
                sum(s.host_overhead_s for s in busy) * 1e3 / len(busy),
                3) if busy else 0.0,
            # steps that traced or compiled something, what that took,
            # and the distinct (width, q_tile) shapes among them: a
            # step of seconds in a served run is one of these, or not.
            # `programs` here counts SHAPES; `obs.compiles.summary()`'s
            # `programs` counts backend compiles, several a shape (the
            # step, its upload and sampling helpers)
            "compiled_steps": sum(1 for s in self.steps if s.compile_s),
            "compile_s_total": round(
                sum(s.compile_s for s in self.steps), 4),
            "programs": len(self.compiled_shapes),
        }

    def to_run_record(self, *, config: str = "engine-serve",
                      backend: str = "engine",
                      extra: dict[str, Any] | None = None) -> RunRecord:
        """The aggregate as a `RunRecord` (the repo's uniform benchmark
        row).  m/n carry prompt/output token totals; the serving-
        specific detail rides in ``extra``."""
        import jax

        s = self.summary()
        per_tok_us = (s["wall_s"] * 1e6 / s["output_tokens"]
                      if s["output_tokens"] else 0.0)
        try:
            dev = jax.devices()[0]
            device_kind, n_dev = dev.device_kind, jax.device_count()
        except Exception:  # noqa: BLE001 - metrics must not need a device
            device_kind, n_dev = "unknown", 0
        record = RunRecord(
            config=config,
            backend=backend,
            m=s["prompt_tokens"],
            n=s["output_tokens"],
            dk=0,
            dv=0,
            dtype="",
            best_us=round(per_tok_us, 2),
            median_us=round(per_tok_us, 2),
            gflops_per_chip=0.0,
            utilization=None,
            device_kind=device_kind,
            n_devices=n_dev,
            extra={**s, **(extra or {})},
        )
        obs.record_run(record)
        return record
