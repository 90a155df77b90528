"""Benchmark harness: TPU flash attention vs the serial C baseline.

Headline metric = the reference's own headline (BASELINE.md): speedup of
the optimized distributed implementation over the serial fp64
`attention.c` baseline, at this repo's north-star shape m=n=32768,
d_k=d_v=128.  The reference's best published speedup is 7.49x (scale5,
64 MPI processes, report.pdf Q6); ``vs_baseline`` is our speedup divided
by that bar.

Both arms measure the TPU and refuse to start on any other backend
(`utils.runtime.require_tpu`); every record carries the ``device`` it
ran on and the run exits non-zero when a correctness check fails.

Method notes (both sides measured, nothing assumed):
  * TPU side: the kernel is timed by DEVICE-side profiler module time
    over a scan chain (``utils.timing.benchmark_auto``, clock
    ``device-trace``): host dispatch and fetch latency would swamp a
    fenced wall-clock reading of a sub-millisecond op.  The record's
    ``clock`` field names the clock; a capture without the device lane
    is an error on the chip, not a fallback.
  * CPU side: the serial fp64 C oracle (csrc/attention_serial.c, the
    `attention.c:20-75` role) is timed at two smaller sizes (seq/2 and
    seq) and extrapolated with min(measured per-doubling ratio, the
    ideal 4x) — attention is Θ(m*n*(dk+dv)), so real serial time at 32k
    is at LEAST quadratic in seq (more once K/V leave cache); the min
    keeps timer noise from exponentiating into an inflated headline,
    making the reported speedup a lower bound.  Running the full 32k
    serial case would take minutes per bench invocation;
    ``--serial-seq 32768`` times it directly instead.

Prints ONE JSON line.  ``--all`` adds the full config ladder
(BASELINE.md configs) to ``detail``.  ``--arm engine`` switches to the
serving benchmark: continuous-batching engine throughput
(`attention_tpu.engine`) vs sequential `generate_paged` on the same
request trace, with per-step scheduler metrics in ``detail``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time


def _hbm_streaming_gbps(repeats: int = 2) -> float:
    """Measured same-session HBM READ-streaming ceiling in GB/s.

    Decode is read-dominated (the cache streams in, the output is
    tiny), so the fair roofline is a read-heavy kernel, not a copy — a
    copy pays for write-allocate traffic decode never issues (measured
    on this chip: elementwise add 558 GB/s r+w, skinny matvec 718, this
    probe 755 — the k=1 matvec leaves the MXU too idle to keep the DMA
    queue full).  Times a (rows, 128) bf16 x (128, 8) matmul + full
    reduction over a 512 MB matrix: reads the whole buffer, writes
    ~1/16 of it, arithmetic intensity 16 flops/elem (still hard
    memory-bound at 197 TFLOP/s), and the scan carry threads through
    the reduction so XLA can neither hoist nor dead-code the read."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.utils.timing import benchmark_auto

    rows = 2 * 2**20  # x 128 cols bf16 -> 512 MB matrix
    big = jnp.ones((rows, 128), jnp.bfloat16)
    carry = jnp.ones((128, 8), jnp.float32)

    def read_pass(c, m):
        # bf16 on purpose: this probe measures DMA bandwidth, and the
        # result only feeds a 1e-12-scaled carry
        y = m @ c.astype(jnp.bfloat16)  # atp: disable=ATP301
        return c + (jnp.sum(y.astype(jnp.float32)) * 1e-12)

    s = benchmark_auto(read_pass, carry, repeats=repeats,
                       n_short=2, n_long=8, operands=(big,))
    return rows * 128 * 2 / s / 1e9


def _headline_contract(seq: int, dim: int, *, seed: int = 7,
                       max_mode: str = "bound",
                       block_sizes=None) -> dict:
    """End-to-end ±0.02 contract run at full problem size: generate a
    `.bin` testcase whose expected output comes from the blockwise fp64
    oracle, run the bf16 flash kernel on the chip, and pass the result
    through the same file reader/verifier the CLI harness uses
    (`core/testcase.py`; the reference verifies every run this way,
    `attention.c:184`, tolerance `:143`).  ``max_mode`` and
    ``block_sizes`` must be the EXACT configuration the headline timing
    used — the reference verifies the very binary it times
    (`attention.c:181-184`), and round 4's contract silently verified
    the online kernel while the headline timed the bound kernel.
    Returns a record for the bench JSON (carrying the verified mode and
    tiles); also used by scripts/verify_headline.py for shapes too
    expensive to regenerate per bench run (131k)."""
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from attention_tpu.core.testcase import (
        generate_testcase,
        read_testcase,
        verify_file,
        write_testcase,
    )
    from attention_tpu.ops.flash import BlockSizes, flash_attention

    if block_sizes is None:
        block_sizes = BlockSizes.for_shape(1, seq, dim, None,
                                           dtype="bfloat16")
    t0 = time.time()
    case = generate_testcase(seq, seq, dim, dim, seed=seed)
    oracle_s = time.time() - t0
    fd, path = tempfile.mkstemp(suffix=".bin")
    os.close(fd)
    try:
        write_testcase(path, case)
        loaded = read_testcase(path)
        out = np.asarray(
            flash_attention(
                jnp.asarray(loaded.q, jnp.bfloat16),
                jnp.asarray(loaded.k, jnp.bfloat16),
                jnp.asarray(loaded.v, jnp.bfloat16),
                max_mode=max_mode,
                block_sizes=block_sizes,
            ),
            np.float32,
        )
        ok, msg = verify_file(path, out)
        err = float(np.max(np.abs(out.astype(np.float64) - loaded.expected)))
        return {
            "verified": bool(ok),
            "seq": seq,
            "dim": dim,
            "max_mode": max_mode,
            "block_q": block_sizes.block_q,
            "block_k": block_sizes.block_k,
            "max_abs_err": round(err, 5),
            "tolerance": 0.02,
            "oracle_s": round(oracle_s, 1),
            "harness_msg": msg.splitlines()[0] if msg else "",
        }
    finally:
        os.unlink(path)


def _bench_flash_s(seq: int, dim: int, repeats: int, block_q: int | None,
                   block_k: int | None, *, heads: int | None = None,
                   kv_heads: int | None = None, window: int | None = None,
                   n_short: int = 4, n_long: int = 20,
                   max_mode: str = "bound", backward: bool = False,
                   causal: bool | None = None):
    """Per-call seconds of the fused flash kernel at (seq, dim), bf16.

    ``heads``/``kv_heads`` switch to multi-head (h, seq, dim) inputs
    (GQA when kv_heads < heads); ``window`` benchmarks causal
    sliding-window attention.  Shared by bench.py (headline) and
    scripts/kernel_sweep.py so both use one timing method and one input
    recipe.

    ``max_mode`` defaults to the library's fastest exact kernel
    ("bound": the precomputed Cauchy-Schwarz max — same output and lse
    as the online kernel, oracle-pinned in tests/test_ops.py; measured
    0.92-0.97 util vs 0.78-0.82 online, scripts/max_mode_exp.py).
    ``backward=True`` times a full value_and_grad step instead (forward
    + both Pallas backward kernels).
    """
    import jax
    import jax.numpy as jnp

    from attention_tpu.ops.flash import BlockSizes, flash_attention
    from attention_tpu.utils.timing import benchmark_auto

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    qshape = (seq, dim) if heads is None else (heads, seq, dim)
    kvshape = (seq, dim) if heads is None else (kv_heads or heads, seq, dim)
    q = jax.random.normal(kq, qshape, jnp.bfloat16)
    k = jax.random.normal(kk, kvshape, jnp.bfloat16)
    v = jax.random.normal(kv, kvshape, jnp.bfloat16)
    # None -> the library's measured per-shape default (BlockSizes.for_shape);
    # a partial override fills the other field from that EFFECTIVE tile,
    # so the run and any FLOPs estimate derived from effective_block_sizes
    # agree in every flag combination.
    eff = BlockSizes.for_shape(heads or 1, seq, dim, window,
                               dtype="bfloat16")
    if block_q is None and block_k is None:
        bs = None  # let the library resolve (same as eff)
    else:
        bs = BlockSizes(block_q or eff.block_q, block_k or eff.block_k)
    causal = (window is not None) if causal is None else causal
    if backward:
        from attention_tpu.ops.flash_vjp import flash_attention_diff

        def grad_step(x, kk_, vv_):
            def loss(args):
                o = flash_attention_diff(
                    *args, block_sizes=bs, causal=causal,
                    window=window, max_mode=max_mode,
                )
                return jnp.sum(o.astype(jnp.float32))

            l, grads = jax.value_and_grad(loss)((x, kk_, vv_))
            # fold ALL grads into the timed value: returning only dQ
            # would let XLA dead-code-eliminate the dK/dV kernel and
            # overstate backward utilization ~1.8x.  The carry must
            # stay DISTRIBUTION-STATIONARY: chaining the raw gradient
            # (plus broadcast scalar sums) as the next Q inflates
            # ||q|| ~1e4, which bound mode's overshoot guard correctly
            # demotes to the online kernel — the chain would then time
            # a kernel no sane training step runs (round-5 find: the
            # "regression" was the guard doing its job on garbage Q).
            combined = (grads[0].astype(jnp.float32)
                        + jnp.sum(grads[1]).astype(jnp.float32)
                        + jnp.sum(grads[2]).astype(jnp.float32))
            return x.astype(jnp.float32) + 1e-12 * combined

        return benchmark_auto(grad_step, q, repeats=repeats,
                              n_short=n_short, n_long=n_long,
                              operands=(k, v))
    step = lambda x, kk, vv: flash_attention(  # noqa: E731
        x, kk, vv, block_sizes=bs, causal=causal, window=window,
        max_mode=max_mode,
    )
    # benchmark_auto: deterministic device-trace clock, slope fallback.
    return benchmark_auto(step, q, repeats=repeats, n_short=n_short,
                          n_long=n_long, operands=(k, v))


def _bench_decode_s(batch: int, heads: int, kv_heads: int, cache_len: int,
                    dim: int, repeats: int, *,
                    quantized: "bool | str" = False):
    """Per-step seconds of fused flash-decode at a full KV cache.
    ``quantized``: False (bf16), True (int8), or "int4"."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.ops.decode import flash_decode
    from attention_tpu.utils.timing import benchmark_auto

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch, heads, dim), jnp.bfloat16)
    kc = jax.random.normal(kk, (batch, kv_heads, cache_len, dim), jnp.bfloat16)
    vc = jax.random.normal(kv, (batch, kv_heads, cache_len, dim), jnp.bfloat16)
    lens = jnp.full((batch,), cache_len, jnp.int32)
    if quantized == "int4":
        # token-paired packing — the measured-faster int4 layout
        # (0.402 ms vs 0.748 feature-dim vs 0.445 int8 at this shape;
        # scripts/int4_pack_exp.py, artifacts/int4_pack_exp.json);
        # identical
        # quantization math and bytes, so the accounting is unchanged.
        # Capacities ≡ 128 (mod 256) have no valid token-paired block
        # (quantize_kv_int4_tok rejects them at build time) — those
        # fall back to the feature-dim layout instead of crashing the
        # bench (ADVICE.md round 5).
        if cache_len % 256:
            from attention_tpu.ops.quant import (
                flash_decode_int4,
                quantize_kv_int4,
            )

            print(f"int4 bench: cache_len {cache_len} is not a "
                  "256-multiple; using the feature-dim layout",
                  file=sys.stderr)
            c4f = quantize_kv_int4(kc, vc)
            step4f = lambda x, c, ll: (  # noqa: E731
                flash_decode_int4(x, c, ll).astype(x.dtype))
            return benchmark_auto(step4f, q, repeats=repeats,
                                  operands=(c4f, lens))
        from attention_tpu.ops.quant import (
            flash_decode_int4_tok,
            quantize_kv_int4_tok,
        )

        c4 = quantize_kv_int4_tok(kc, vc)
        step4 = lambda x, c, ll: (  # noqa: E731
            flash_decode_int4_tok(x, c, ll).astype(x.dtype))
        return benchmark_auto(step4, q, repeats=repeats,
                              operands=(c4, lens))
    if quantized:
        from attention_tpu.ops.quant import (
            flash_decode_quantized,
            quantize_kv,
        )

        qkv = quantize_kv(kc, vc)
        stepq = lambda x, c, ll: (  # noqa: E731
            flash_decode_quantized(x, c, ll).astype(x.dtype))
        return benchmark_auto(stepq, q, repeats=repeats,
                              operands=(qkv, lens))
    stepd = lambda x, kcc, vcc, ll: flash_decode(x, kcc, vcc, ll)  # noqa: E731
    return benchmark_auto(stepd, q, repeats=repeats,
                          operands=(kc, vc, lens))


def _bench_paged_decode_s(batch: int, heads: int, kv_heads: int,
                          cache_len: int, dim: int, repeats: int,
                          *, page_size: int | None = None):
    """Per-step seconds of paged flash-decode (block-table translation)
    at a full KV cache, physical pages scrambled.  ``page_size`` None
    resolves through `recommended_page_size` (tuning tables, falling
    back to the measured 2048 streaming block)."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.ops.paged import PagePool, paged_from_dense, \
        paged_flash_decode, recommended_page_size
    from attention_tpu.utils.timing import benchmark_auto

    if page_size is None:
        page_size = recommended_page_size(
            cache_len, batch=batch, heads=heads, kv_heads=kv_heads,
            d=dim, dtype=jnp.bfloat16)

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch, heads, dim), jnp.bfloat16)
    kc = jax.random.normal(kk, (batch, kv_heads, cache_len, dim),
                           jnp.bfloat16)
    vc = jax.random.normal(kv, (batch, kv_heads, cache_len, dim),
                           jnp.bfloat16)
    import random

    num_pages = batch * (cache_len // page_size)
    pool = PagePool(num_pages)
    # genuine fragmentation via the public API: claim every page, then
    # free in seeded-shuffled order so later allocs interleave
    ids = pool.alloc(num_pages)
    random.Random(0).shuffle(ids)
    pool.free(ids)
    cache = paged_from_dense(
        kc, vc, jnp.full((batch,), cache_len, jnp.int32), pool,
        num_pages=num_pages, page_size=page_size,
    )
    stepp = lambda x, c: paged_flash_decode(x, c).astype(x.dtype)  # noqa: E731
    return benchmark_auto(stepp, q, repeats=repeats, operands=(cache,))



# A slope implying more than this fraction of peak matmul FLOPs is
# treated as the chip's known absurd-fast outlier and re-measured.
# A reading is implausible past ~1.0 of peak, not past the best kernel
# we had when this screen was written: the round-4 VMEM-unlocked 131k
# forward legitimately sustains 0.984 (reproduces to the decimal on the
# device clock, and its output passes the full-size ±0.02 contract), so
# the old 0.98 cap started flagging honest measurements.  0.995 still
# rejects every physical impossibility the screen exists for (observed
# outliers implied 1.2-2.6x peak).
PLAUSIBLE_UTIL = 0.995


def _measure_plausible(measure, flops, attempts=4):
    """(seconds, plausible): re-run ``measure()`` until the timing is
    physically possible (util <= PLAUSIBLE_UTIL of peak matmul FLOPs).

    A reading past the chip's peak is a measurement artifact, and
    reporting one would be dishonest; up to ``attempts`` total tries,
    first plausible attempt wins, else the last attempt ships flagged.
    An exception from ``measure()`` propagates: a failing measurement
    is a failing run, not something to retry around."""
    from attention_tpu.utils.flops import peak_flops

    t = None
    for _ in range(attempts):
        t = measure()
        if flops / t / peak_flops() <= PLAUSIBLE_UTIL:
            return t, True
    return t, False


def _time_serial_once(seq: int, dim: int) -> float:
    import numpy as np

    from attention_tpu.core.native import attention_native

    rng = np.random.default_rng(0)
    q = rng.standard_normal((seq, dim))
    k = rng.standard_normal((seq, dim))
    v = rng.standard_normal((seq, dim))
    attention_native(q[:128], k, v)  # warm the code/data paths
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        attention_native(q, k, v)
        best = min(best, time.perf_counter() - t0)
    return best


def _bench_serial_s(seq: int, dim: int, target_seq: int):
    """Seconds for the serial fp64 C oracle at target_seq, measured in
    this run on this host (nothing is read from or written to the
    checkout).

    Measured directly when seq >= target_seq ("measured-now").
    Otherwise extrapolated from seq/2 and seq with min(measured
    per-doubling ratio, the ideal 4x): work is Θ(seq²), so the true
    ratio is >= 4 (above 4 once K/V fall out of cache), and a
    noisy-high measured ratio would exponentiate into an INFLATED
    headline speedup — at worst the min understates the serial side,
    i.e. the reported speedup is a lower bound.
    """
    if seq >= target_seq:
        return _time_serial_once(target_seq, dim), "measured-now"
    t_half = _time_serial_once(seq // 2, dim)
    t_full = _time_serial_once(seq, dim)
    ratio = min(t_full / t_half, 4.0)
    est = t_full * ratio ** math.log2(target_seq / seq)
    return est, "extrapolated"


def _bench_prefix_fleet(model, params, args) -> dict:
    """The ``--prefix-store`` detail block: the SAME RAG-heavy diurnal
    trace — under the SAME deterministic rolling restart — through a
    2-replica front end with the fleet prefix store OFF and ON.

    Every second request carries its tenant's 256-token retrieval
    header (two full shared pages).  Per-replica prefix caches plus
    sticky routing already capture most steady-state reuse, so the
    fleet tier's measurable win is CHURN: the rolling restart (each
    replica killed once mid-trace and restarted cold two ticks later —
    a deploy) wipes the local caches.  Store-off re-prefills every
    subsequent header from scratch while arrivals pile up; store-on
    re-imports the committed pages at admission for free.
    `obs.capacity.cost_per_token` (alive-replica ticks per finished
    token) must come DOWN, and every request finished by BOTH runs
    must be token-identical — the store may never cost a token, only
    ticks."""
    from attention_tpu.engine import EngineConfig
    from attention_tpu.engine.sim import diurnal_trace, sampling_of
    from attention_tpu.frontend import FrontendConfig, ServingFrontend
    from attention_tpu.frontend.frontend import FrontendRequestState
    from attention_tpu.obs.forecast import ForecastPolicy
    from attention_tpu.prefixstore import PrefixStoreConfig

    trace = diurnal_trace(
        args.engine_requests * 3, vocab=256, seed=11,
        rag_every=2, rag_prefill_len=256, tenants=2,
        prompt_len_min=4, prompt_len_max=24, max_tokens=8,
        peak_rate=4.0,
    )
    config = EngineConfig(
        num_pages=64, page_size=128, max_seq_len=384,
        max_decode_batch=8, max_prefill_rows=2, prefill_chunk=64,
        token_budget=192, watermark_pages=1,
    )
    restarts = ((10, "replica-0"), (16, "replica-1"))

    def _run(with_store):
        fe = ServingFrontend(model, params, config, FrontendConfig(
            num_replicas=2, seed=0, forecast=ForecastPolicy(),
            prefix_store=PrefixStoreConfig() if with_store else None,
        ))
        for e in trace:
            fe.submit(e["prompt"], sampling_of(e),
                      request_id=e.get("id"),
                      arrival=int(e.get("arrival", 0)),
                      session=e.get("session"),
                      priority=int(e.get("priority", 1)))
        while fe.has_work():
            t = fe.current_tick
            for kill_tick, rid in restarts:
                if t == kill_tick:
                    fe.kill_replica(rid)
                elif t == kill_tick + 2:
                    fe.restart_replica(rid)
            fe.tick()
        summary = fe.summary()
        fleet = fe.forecast_report()["capacity"]["fleet"]
        finished = {
            rid: list(fr.tokens)
            for rid, fr in fe.requests.items()
            if fr.state is FrontendRequestState.FINISHED
        }
        return summary, finished, fleet

    s_off, fin_off, fleet_off = _run(False)
    s_on, fin_on, fleet_on = _run(True)
    store_counts = s_on.get("prefixstore", {})
    common = sorted(set(fin_off) & set(fin_on))
    return {
        "replicas": 2,
        "requests": len(trace),
        "rolling_restarts": [list(r) for r in restarts],
        "store_off": {
            "ticks": s_off["ticks"],
            "cost_per_token": fleet_off["cost_per_token"],
            "tokens_per_tick": fleet_off["tokens_per_tick"],
            "finished": len(fin_off),
        },
        "store_on": {
            "ticks": s_on["ticks"],
            "cost_per_token": fleet_on["cost_per_token"],
            "tokens_per_tick": fleet_on["tokens_per_tick"],
            "finished": len(fin_on),
            "fleet_prefix_hit_rate": store_counts.get(
                "fleet_prefix_hit_rate", 0.0),
            "imported_tokens": store_counts.get("imported_tokens", 0),
            "exports": store_counts.get("exports", 0),
            "imports": store_counts.get("imports", 0),
            "singleflight_coalesced": store_counts.get(
                "singleflight_coalesced", 0),
        },
        "cost_per_token_ratio": (
            round(fleet_on["cost_per_token"]
                  / fleet_off["cost_per_token"], 4)
            if fleet_off["cost_per_token"] else None),
        # the invariant, checked right here in the bench: fleet reuse
        # must never change a token of any commonly-finished stream
        "tokens_match_store_off": all(
            fin_on[r] == fin_off[r] for r in common),
    }


def _bench_disagg_fleet(model, params, args) -> dict:
    """The ``--disagg`` detail block: the SAME seeded mixed workload
    (steady decode-heavy sessions + tenant RAG prefill bursts, 160-token
    retrieval headers — long enough to commit full pages) through a
    3-replica front end twice — a monolithic arm where every replica
    serves both phases, and a disaggregated arm where admissions land
    in a 1-replica prefill pool and hand off to a 2-replica decode pool
    at prompt commit, shipping the committed KV pages, with the
    closed-loop autoscaler free to rebalance the split from the shared
    standby bench.

    The comparison the record exists for: per-phase latency digests
    (TTFT is the prefill pool's problem, TPOT the decode pool's — the
    monolithic arm pays for bursts in everyone's TPOT) plus the SLO
    burn rates over the same `obs.slo` objectives, and the handoff
    economics (pages shipped == re-prefill tokens avoided on the decode
    side).  Both arms are fully deterministic and must finish every
    request with IDENTICAL tokens — disaggregation moves WHERE tokens
    are computed, never WHICH."""
    from attention_tpu.engine import EngineConfig
    from attention_tpu.engine.sim import disagg_trace, sampling_of
    from attention_tpu.fleet import AutoscalerPolicy, FleetTopology
    from attention_tpu.frontend import FrontendConfig, ServingFrontend
    from attention_tpu.frontend.frontend import FrontendRequestState
    from attention_tpu.obs import slo as slo_mod

    trace = disagg_trace(
        args.engine_requests * 2, vocab=256, seed=11,
        rate=1.5, tenants=2, burst_every=4, burst_size=2,
        rag_prefill_len=160, prompt_len_min=4, prompt_len_max=12,
        max_tokens=8,
    )
    config = EngineConfig(
        num_pages=64, page_size=128, max_seq_len=384,
        max_decode_batch=8, max_prefill_rows=2, prefill_chunk=64,
        token_budget=192, watermark_pages=1,
    )

    def _run(disagg):
        fleet = autoscaler = None
        if disagg:
            fleet = FleetTopology(prefill_replicas=1, decode_replicas=2)
            autoscaler = AutoscalerPolicy(
                scale_up_after=2, scale_down_after=4,
                cooldown_ticks=8, guard_window=6)
        fe = ServingFrontend(model, params, config, FrontendConfig(
            num_replicas=3, seed=0, standbys=2,
            fleet=fleet, autoscaler=autoscaler,
        ))
        for e in trace:
            fe.submit(e["prompt"], sampling_of(e),
                      request_id=e.get("id"),
                      arrival=int(e.get("arrival", 0)),
                      session=e.get("session"),
                      priority=int(e.get("priority", 1)))
        while fe.has_work():
            fe.tick()
        summary = fe.summary()
        report = slo_mod.slo_report(fe.latency_rows(),
                                    horizon_tick=summary["ticks"])
        finished = {
            rid: list(fr.tokens)
            for rid, fr in fe.requests.items()
            if fr.state is FrontendRequestState.FINISHED
        }
        return summary, report, finished

    s_mono, rep_mono, fin_mono = _run(False)
    s_dis, rep_dis, fin_dis = _run(True)
    common = sorted(set(fin_mono) & set(fin_dis))

    def _arm(summary, report):
        fb = report["fleet"]
        return {
            "ticks": summary["ticks"],
            "finished": summary["states"]["finished"],
            "ttft": fb["ttft"],
            "tpot": fb["tpot"],
            "slo": {ob["objective"]: {
                "burn_rate": ob["burn_rate"],
                "budget_remaining": ob["budget_remaining"],
                "violations": ob["violations"],
            } for ob in fb["slo"]},
        }

    return {
        "replicas": 3,
        "standbys": 2,
        "requests": len(trace),
        "monolithic": _arm(s_mono, rep_mono),
        "disaggregated": {
            **_arm(s_dis, rep_dis),
            "pools": s_dis["fleet"]["pools"],
            "actuations": s_dis["fleet"]["actuations"],
            "handoffs": s_dis["handoffs"],
            "handoff_fallbacks": s_dis["handoff_fallbacks"],
            "reprefill_avoided_tokens":
                s_dis["reprefill_avoided_tokens"],
            "scale_ups": s_dis["scale_ups"],
            "scale_downs": s_dis["scale_downs"],
        },
        # the tentpole contract, checked right here in the bench:
        # disaggregation moves WHERE tokens are computed, never WHICH
        "tokens_match_monolithic": all(
            fin_dis[r] == fin_mono[r] for r in common),
    }


def _bench_gray_fleet(model, params, args) -> dict:
    """The ``--gray-failure`` detail block: the RAG-heavy diurnal
    trace through a 2-replica front end with the anomaly detectors
    on, twice — a clean arm and a degraded arm where replica-0's
    decode token budget collapses mid-run.

    The degradation is deliberately *gray*: the throttled replica
    keeps stepping, its virtual step cost stays at the fleet median,
    and it raises no typed errors, so every supervisor liveness
    signal stays green — only its inter-token gaps inflate.  The
    record reports the injection tick, the gray detector's first
    firing tick and which replica it named, and the clean arm's
    firing count (the false-positive check).  Both arms are fully
    deterministic, so the latency figure is a property of the
    detector, not of the host."""
    from attention_tpu.engine import EngineConfig
    from attention_tpu.engine.sim import diurnal_trace, sampling_of
    from attention_tpu.frontend import FrontendConfig, ServingFrontend
    from attention_tpu.obs.anomaly import AnomalyPolicy

    # moderate diurnal load (peak_rate=2.0): heavy enough that the
    # brownout's victims queue behind each other, light enough that
    # the healthy arm's contention never crosses the gray bound
    trace = diurnal_trace(
        args.engine_requests * 3, vocab=256, seed=11,
        rag_every=2, rag_prefill_len=256, tenants=2,
        prompt_len_min=4, prompt_len_max=24, max_tokens=8,
        peak_rate=2.0,
    )
    config = EngineConfig(
        num_pages=64, page_size=128, max_seq_len=384,
        max_decode_batch=8, max_prefill_rows=2, prefill_chunk=64,
        token_budget=192, watermark_pages=1,
    )
    inject_tick = 16

    def _run(degrade):
        fe = ServingFrontend(model, params, config, FrontendConfig(
            num_replicas=2, seed=0,
            anomaly=AnomalyPolicy(gray_trail=4),
        ))
        for e in trace:
            fe.submit(e["prompt"], sampling_of(e),
                      request_id=e.get("id"),
                      arrival=int(e.get("arrival", 0)),
                      session=e.get("session"),
                      priority=int(e.get("priority", 1)))
        ticks = 0
        while fe.has_work() and ticks < 600:
            if degrade and fe.current_tick == inject_tick:
                # budget throttle ONLY — inflating the virtual step
                # cost would trip the supervisor and turn this into a
                # fail-stop kill, which is a different (easier) bench
                fe.replicas[0].engine.scheduler.token_budget = 1
            fe.tick()
            ticks += 1
        return fe

    clean = _run(False)
    deg = _run(True)
    gray = [f for f in deg.anomaly.firings
            if f["detector"] == "gray_failure"]
    first = gray[0] if gray else None
    return {
        "replicas": 2,
        "requests": len(trace),
        "injection_tick": inject_tick,
        "degradation": "replica-0 token_budget -> 1 (supervisor-"
        "invisible brownout: steps advance, cost normal, no errors)",
        "detection_tick": first["tick"] if first else None,
        "detection_latency_ticks": (
            first["tick"] - inject_tick if first else None),
        "detected_replica": first["key"] if first else None,
        "gray_firings": [
            {"tick": f["tick"], "key": f["key"], "value": f["value"],
             "bound": f["bound"]} for f in gray],
        "clean_false_positives": len(clean.anomaly.firings),
        # the gray premise, checked right here in the bench: the
        # liveness supervisor never saw the sick replica
        "supervisor_blind": (
            deg.counts["supervisor_dead"] == 0
            and deg.counts["replica_kills"] == 0),
        "degraded_finished_tokens": sum(
            len(fr.tokens) for fr in deg.requests.values()),
        "clean_finished_tokens": sum(
            len(fr.tokens) for fr in clean.requests.values()),
    }


def _bench_engine(args, device: dict) -> dict:
    """The ``--arm engine`` record: continuous-batching throughput of
    `attention_tpu.engine` on a synthetic overlapping-request trace vs
    the same requests served one at a time through `generate_paged`.

    Both sides run the same paged kernels and the same greedy sampling,
    so the delta is pure scheduling: iteration-level batching + chunked
    prefill + prefix reuse against sequential request-at-a-time
    serving.  Per-step scheduler metrics ride along in ``detail``.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from attention_tpu.engine import (
        EngineConfig,
        ServingEngine,
        replay,
        synthetic_trace,
    )
    from attention_tpu.models import TinyDecoder
    from attention_tpu.models.decode import generate_paged

    model = TinyDecoder(vocab=256, dim=args.engine_dim, depth=2,
                        num_q_heads=4, num_kv_heads=2, impl="flash",
                        dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    trace = synthetic_trace(
        args.engine_requests, vocab=256, seed=7,
        prompt_len_min=24, prompt_len_max=args.engine_prompt,
        max_tokens=args.engine_steps, arrival_every=1,
        shared_prefix_len=129, shared_count=args.engine_requests // 2,
    )
    config = EngineConfig(
        num_pages=args.engine_requests
        * (-(-(args.engine_prompt + 129 + args.engine_steps) // 128)) + 4,
        page_size=128,
        max_seq_len=args.engine_prompt + 129 + args.engine_steps,
        max_decode_batch=8, max_prefill_rows=2, prefill_chunk=64,
        token_budget=192, watermark_pages=1,
    )
    # One untimed warmup replay compiles both fixed-shape executables
    # (decode + prefill-chunk) outside the timed region — the same
    # warmup-then-time discipline as the CLI harness.  The timed engine
    # is fresh; compiled executables are shared via the static-model jit.
    replay(ServingEngine(model, params, config), trace[:2])

    engine = ServingEngine(model, params, config)
    t0 = _time.perf_counter()
    summary, outputs = replay(engine, trace)
    engine_s = _time.perf_counter() - t0
    out_tokens = sum(len(v) for v in outputs.values())

    def _sequential_pass():
        total = 0
        for entry in trace:
            prompt = entry["prompt"]
            toks, _caches, _pools = generate_paged(
                model, params, jnp.asarray([prompt], jnp.int32),
                jnp.asarray([len(prompt)], jnp.int32),
                steps=entry["max_tokens"],
            )
            total += int(np.asarray(toks).shape[1])
        return total

    # first pass warms the per-shape compile caches (generate_paged's
    # re-tracing per call is genuine steady-state sequential cost and
    # stays in the timed pass; XLA compiles do not)
    _sequential_pass()
    t0 = _time.perf_counter()
    seq_tokens = _sequential_pass()
    sequential_s = _time.perf_counter() - t0

    eng_tps = out_tokens / engine_s
    seq_tps = seq_tokens / sequential_s
    # fold the engine aggregate into the telemetry registry too
    # (to_run_record routes through obs.record_run; no-op when disabled)
    engine.metrics.to_run_record(config="bench-engine")

    def _mean_sync_ms(metrics):
        # per-step device time: the step's single blocking fetch (mesh
        # engines reassemble replicated logits inside it), wall minus
        # host-side packing — busy steps only, idle steps never launch
        syncs = [(m.wall_s - m.host_overhead_s) * 1e3
                 for m in metrics.steps
                 if m.num_decode_reqs or m.num_prefill_reqs]
        return sum(syncs) / len(syncs) if syncs else 0.0

    mesh_detail = None
    if args.mesh_shards:
        # same trace through a KV-head-sharded engine: report per-shard
        # kernel time and the collective overhead vs the single-device
        # run above (identical schedule, so the delta is the mesh cost)
        mesh_config = dataclasses.replace(config,
                                          mesh_shards=args.mesh_shards)
        replay(ServingEngine(model, params, mesh_config), trace[:2])
        mesh_engine = ServingEngine(model, params, mesh_config)
        t0 = _time.perf_counter()
        _mesh_summary, mesh_outputs = replay(mesh_engine, trace)
        mesh_s = _time.perf_counter() - t0
        single_sync_ms = _mean_sync_ms(engine.metrics)
        mesh_sync_ms = _mean_sync_ms(mesh_engine.metrics)
        mesh_detail = {
            "shards": args.mesh_shards,
            "mesh_tokens_per_s": round(
                sum(len(v) for v in mesh_outputs.values()) / mesh_s, 2),
            "per_shard_kernel_ms": round(
                mesh_sync_ms / args.mesh_shards, 4),
            "single_device_kernel_ms": round(single_sync_ms, 4),
            "collective_overhead_ms": round(
                mesh_sync_ms - single_sync_ms, 4),
            # the tentpole contract, checked right here in the bench:
            # sharding must never change a token
            "tokens_match_single_device": mesh_outputs == outputs,
        }

    fleet_detail = None
    if args.prefix_store:
        fleet_detail = _bench_prefix_fleet(model, params, args)

    gray_detail = None
    if args.gray_failure:
        gray_detail = _bench_gray_fleet(model, params, args)

    disagg_detail = None
    if args.disagg:
        disagg_detail = _bench_disagg_fleet(model, params, args)

    return {
        "metric": "engine continuous-batching decode throughput vs "
        "sequential generate_paged (same model, same requests, "
        f"{device['count']}x {device['kind']})",
        "value": round(eng_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(eng_tps / seq_tps, 2) if seq_tps else None,
        "device": device,
        "clock": "wall-fence",
        "detail": {
            "engine_tokens_per_s": round(eng_tps, 2),
            "sequential_tokens_per_s": round(seq_tps, 2),
            "engine_wall_s": round(engine_s, 3),
            "sequential_wall_s": round(sequential_s, 3),
            "output_tokens": out_tokens,
            # packing economics of the single-launch step: pads
            # dispatched, occupancy, and the host-side cost of a step
            "pad_tokens_total": summary.get("pad_tokens_total", 0),
            "mean_ragged_occupancy": summary.get(
                "mean_ragged_occupancy", 0.0),
            "mean_host_overhead_ms": summary.get(
                "mean_host_overhead_ms", 0.0),
            "summary": summary,
            "mesh": mesh_detail,
            "prefix_fleet": fleet_detail,
            "gray_fleet": gray_detail,
            "disagg_fleet": disagg_detail,
            "per_step": [m.to_dict() for m in engine.metrics.steps],
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument(
        "--arm", choices=("headline", "engine"), default="headline",
        help="'headline': the flash-kernel speedup record (default); "
        "'engine': continuous-batching serving throughput vs "
        "sequential generate_paged (attention_tpu.engine)",
    )
    p.add_argument("--engine-requests", type=int, default=12)
    p.add_argument("--engine-steps", type=int, default=16,
                   help="generated tokens per request (engine arm)")
    p.add_argument("--engine-prompt", type=int, default=96,
                   help="max prompt body length (engine arm)")
    p.add_argument("--engine-dim", type=int, default=64)
    p.add_argument(
        "--prefix-store", action="store_true",
        help="engine arm: ALSO run a RAG-heavy diurnal trace through "
        "a 2-replica front end with the fleet prefix store off and on "
        "(attention_tpu.prefixstore) and report the "
        "obs.capacity.cost_per_token delta + store counters "
        "(token streams must match exactly)",
    )
    p.add_argument(
        "--gray-failure", action="store_true",
        help="engine arm: ALSO run the diurnal trace through a "
        "2-replica front end with the anomaly detectors on, clean and "
        "with a mid-run supervisor-invisible brownout of replica-0 "
        "(attention_tpu.obs.anomaly), and report gray-failure "
        "detection tick vs injection tick + clean-arm false positives",
    )
    p.add_argument(
        "--disagg", action="store_true",
        help="engine arm: ALSO run the seeded mixed workload (steady "
        "decode sessions + RAG prefill bursts) through a monolithic "
        "3-replica front end and through the disaggregated prefill/"
        "decode fleet with the closed-loop autoscaler "
        "(attention_tpu.fleet) and report TTFT/TPOT digests, SLO burn "
        "rates, and re-prefill-avoided tokens (token streams must "
        "match exactly)",
    )
    p.add_argument(
        "--mesh-shards", type=int, default=0,
        help="engine arm: ALSO run the trace through a KV-head-sharded "
        "mesh engine (EngineConfig.mesh_shards=N) and report per-shard "
        "kernel ms + collective overhead vs the single-device run "
        "(needs >= N local devices; on CPU set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    p.add_argument("--seq", type=int, default=32768)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument(
        "--repeats", type=int, default=5,
        help="amortized-slope timing repeats; the min fights the shared "
        "chip's large run-to-run contention variance",
    )
    p.add_argument("--block-q", type=int, default=None,
                   help="override the library's per-shape default tile")
    p.add_argument("--block-k", type=int, default=None)
    p.add_argument(
        "--serial-seq", type=int, default=4096,
        help="m=n at which the serial C oracle is timed (then extrapolated)",
    )
    p.add_argument(
        "--max-mode",
        choices=("online", "bound", "flashd", "amla", "auto"),
        default="bound",
        help="flash rescaling-math strategy; 'bound' (default) is the "
        "VFA-style precomputed bound — same output/lse, ~0.95 vs ~0.81 "
        "util (scripts/max_mode_exp.py); 'flashd'/'amla' are the "
        "deferred-division and exponent-add variants; 'auto' reads the "
        "measured per-device tuning table",
    )
    p.add_argument("--all", action="store_true", help="full config ladder")
    p.add_argument(
        "--autotune", action="store_true",
        help="run the timed tile search at the headline shape first "
        "(attention_tpu.tuning), persist the winner in the per-device "
        "cache, and time the headline with it; explicit --block-q/"
        "--block-k still win",
    )
    p.add_argument(
        "--no-contract", action="store_true",
        help="skip the full-size .bin ±0.02 contract verification "
        "(~30 s of fp64 oracle at seq=32k; the reference verifies "
        "every run, so the default keeps it on)",
    )
    args = p.parse_args(argv)

    from attention_tpu.utils.runtime import (
        NoAcceleratorError,
        configure_compile_cache,
        describe_run,
        require_tpu,
    )

    cache_dir = configure_compile_cache()
    try:
        device = require_tpu()
    except NoAcceleratorError as e:
        print(f"bench.py: {e}", file=sys.stderr)
        return 2
    print(describe_run(device, cache_dir), file=sys.stderr)

    if args.arm == "engine":
        record = _bench_engine(args, device)
        print(json.dumps(record))
        parity = {f"{block}.{name}": ok
                  for block in ("mesh", "prefix_fleet", "disagg_fleet")
                  for name, ok in (record["detail"][block] or {}).items()
                  if name.startswith("tokens_match")}
        if not all(parity.values()):
            print(f"bench.py: token-parity check failed: {parity}",
                  file=sys.stderr)
            return 1
        return 0

    from attention_tpu.utils.flops import attention_flops, peak_flops

    flops = attention_flops(args.seq, args.seq, args.dim, args.dim)

    # Fresh measured optima on request: the tile search runs BEFORE the
    # headline (recording winners in the per-device cache, where the
    # next plain run's BlockSizes.for_shape finds them), and this run's
    # headline times the freshly measured best.  Explicit tile flags
    # keep priority — an operator pinning a tile is pinning it.
    autotune_rec = None
    if args.autotune and args.block_q is None and args.block_k is None:
        from attention_tpu.tuning.search import tune

        autotune_rec = tune(
            "flash_fwd", seq=args.seq, dim=args.dim,
            max_mode=args.max_mode, repeats=args.repeats,
            log=lambda s: print(s, file=sys.stderr),
        )
        args.block_q = autotune_rec["entry"]["block_q"]
        args.block_k = autotune_rec["entry"]["block_k"]

    # The EXACT tile configuration the headline times (explicit flags,
    # else the library's per-shape default) — the correctness spot-check
    # AND the full-size contract below must verify this configuration,
    # not some other kernel (the reference verifies the binary it
    # times, attention.c:181-184).
    from attention_tpu.ops.flash import BlockSizes

    _eff_bs = BlockSizes.for_shape(1, args.seq, args.dim, None,
                                   dtype="bfloat16")
    used_bs = BlockSizes(args.block_q or _eff_bs.block_q,
                         args.block_k or _eff_bs.block_k)

    tpu_s, plausible = _measure_plausible(
        lambda: _bench_flash_s(args.seq, args.dim, args.repeats,
                               args.block_q, args.block_k,
                               max_mode=args.max_mode), flops)
    serial_s, serial_method = _bench_serial_s(
        min(args.serial_seq, args.seq), args.dim, args.seq)
    speedup = serial_s / tpu_s

    # On-device correctness spot-check of the exact kernel being timed:
    # the headline must never report a fast-but-wrong kernel.  8192 rows
    # is the smallest square shape above the bound->online static
    # resolution (`ops.flash._BOUND_MIN_SCORE_ELEMS`), so the check
    # lowers the MODE the headline timed through the production
    # dispatch, against the XLA dense oracle at highest precision.
    def _kernel_check():
        import jax
        import jax.numpy as jnp
        import numpy as np

        from attention_tpu.ops.flash import flash_attention
        from attention_tpu.ops.reference import attention_xla

        # the EXACT tile the headline timed — bound-mode code paths are
        # tile-dependent (per-lane l loop, bound init)
        check_bs = used_bs
        rows = min(8192, args.seq)
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        cq = jax.random.normal(kq, (rows, args.dim), jnp.bfloat16)
        ck = jax.random.normal(kk, (rows, args.dim), jnp.bfloat16)
        cv = jax.random.normal(kv, (rows, args.dim), jnp.bfloat16)
        got = np.asarray(
            flash_attention(cq, ck, cv, max_mode=args.max_mode,
                            block_sizes=check_bs),
            np.float32,
        )
        with jax.default_matmul_precision("highest"):
            want = np.asarray(
                attention_xla(
                    cq.astype(jnp.float32), ck.astype(jnp.float32),
                    cv.astype(jnp.float32),
                ),
                np.float32,
            )
        return float(np.max(np.abs(got - want)))

    check_err = _kernel_check()

    # End-to-end ±0.02 contract at the FULL headline shape: the
    # reference verifies every run at full problem size
    # (attention.c:184, tolerance :143) — a 4k spot check is not that.
    # Round-trips an actual .bin file through the same reader/verifier
    # the CLI uses.  131k is too slow to regenerate per run (its fp64
    # oracle alone is ~7 min); scripts/verify_headline.py writes a
    # cached on-chip record that is included below with its provenance.
    contract = None
    if not args.no_contract:
        # Shapes past 32k pay minutes of fp64 oracle per run — reuse a
        # verified artifact for the requested shape when one exists
        # (written by scripts/verify_headline.py), with its provenance
        # on the record; the default 32k regenerates fresh every run.
        art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "artifacts",
                           f"headline_verify_{args.seq}.json")
        if args.seq > 32768 and os.path.exists(art):
            with open(art) as f:
                contract = json.load(f)
            # the cached record must describe the VERY configuration
            # being timed — mode and tiles included — or it is not this
            # run's contract
            if (contract.get("dim") == args.dim
                    and contract.get("verified")
                    and contract.get("max_mode") == args.max_mode
                    and contract.get("block_q") == used_bs.block_q
                    and contract.get("block_k") == used_bs.block_k):
                contract["source"] = f"cached artifacts/{os.path.basename(art)}"
            else:
                contract = None
        if contract is None:
            contract = _headline_contract(args.seq, args.dim,
                                          max_mode=args.max_mode,
                                          block_sizes=used_bs)

    util = flops / tpu_s / peak_flops()
    result = {
        "metric": f"attention speedup vs serial attention.c baseline "
        f"(seq={args.seq}, d={args.dim}, bf16 flash, 1 chip)",
        "value": round(speedup, 1),
        "unit": "x",
        "vs_baseline": round(speedup / 7.49, 2),
        "device": device,
        "clock": tpu_s.clock,
        "detail": {
            "tpu_kernel_ms": round(tpu_s * 1e3, 3),
            "tpu_gflops_per_chip": round(flops / tpu_s / 1e9, 1),
            "mxu_utilization_of_peak": round(util, 4),
            "max_mode": args.max_mode,
            "kernel_check_max_abs_err_8k": round(check_err, 5),
            "serial_c_s": round(serial_s, 1),
            "serial_method": serial_method,
            "serial_timed_at_seq": min(args.serial_seq, args.seq),
            "reference_best_speedup": 7.49,
        },
    }
    if autotune_rec is not None:
        result["detail"]["autotune"] = autotune_rec
    if contract is not None:
        result["detail"]["headline_contract"] = contract
        if not contract.get("verified"):
            result["detail"]["headline_contract_failed"] = True
    art_131k = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts", "headline_verify_131072.json")
    # at --seq 131072 the cached record already IS headline_contract —
    # don't emit the same file twice
    if args.seq != 131072 and os.path.exists(art_131k):
        with open(art_131k) as f:
            rec = json.load(f)
        rec["source"] = "cached artifacts/headline_verify_131072.json"
        result["detail"]["headline_contract_131k"] = rec
    if check_err > 0.02:
        result["detail"]["kernel_check_failed"] = True
    if not plausible:
        result["detail"]["implausible_timing"] = (
            "slope exceeds peak FLOPs after 4 attempts; chip outlier"
        )

    if args.all:
        # The BASELINE.md config ladder (serial config 1 is the
        # denominator above; configs 2-5 measured here on one chip).
        ladder = {}
        for name, (seq, dim, h, hkv) in {
            "single_chip_8k": (8192, 128, None, None),
            "seq_32k": (32768, 128, None, None),
            "long_131k": (131072, 128, None, None),
            "gqa_32q4kv_16k": (16384, 128, 32, 4),
        }.items():
            fl = attention_flops(seq, seq, dim, dim) * (h or 1)
            if (seq, dim, h) == (args.seq, args.dim, None):
                s, ok = tpu_s, plausible  # headline already measured
            else:
                # Scan-chain lengths scale inversely with per-call cost:
                # small configs need long chains to rise above dispatch
                # jitter; big configs keep chains short so compile+upload
                # don't dominate wall time.
                n_long = max(8, min(64, (32768 // seq) * 16))
                s, ok = _measure_plausible(
                    lambda: _bench_flash_s(
                        seq, dim, args.repeats, args.block_q,
                        args.block_k, heads=h, kv_heads=hkv,
                        n_short=max(2, n_long // 8), n_long=n_long,
                        max_mode=args.max_mode), fl)
            ladder[name] = {
                "ms": round(s * 1e3, 3),
                "clock": s.clock,
                "gflops": round(fl / s / 1e9, 1),
                "util": round(fl / s / peak_flops(), 4),
            }
            if not ok:
                ladder[name]["implausible_timing"] = True
        # rescaling-math variant arms at the headline shape: one row
        # per max_mode the forward can lower — the measured-dispatch
        # dimension tune(max_mode="auto") races.  The row matching the
        # run's own --max-mode reuses the headline measurement.
        from attention_tpu.tuning.space import FLASH_FWD_MAX_MODES

        head_fl = attention_flops(args.seq, args.seq, args.dim, args.dim)
        variants = {}
        for mode in FLASH_FWD_MAX_MODES:
            if mode == args.max_mode:
                v_s, v_ok = tpu_s, plausible
            else:
                v_s, v_ok = _measure_plausible(
                    lambda m=mode: _bench_flash_s(
                        args.seq, args.dim, args.repeats, args.block_q,
                        args.block_k, n_short=2, n_long=8, max_mode=m),
                    head_fl)
            variants[mode] = {
                "ms": round(v_s * 1e3, 3),
                "clock": v_s.clock,
                "util": round(head_fl / v_s / peak_flops(), 4),
            }
            if not v_ok:
                variants[mode]["implausible_timing"] = True
        ladder["max_mode_variants_headline"] = variants
        # sliding-window config: banded grid, cost ~ window not sequence
        # band FLOPs estimate uses the same effective tile the run uses
        # (explicit flag wins; else for_shape's windowed default)
        from attention_tpu.ops.flash import BlockSizes

        w_bq = args.block_q or BlockSizes.for_shape(
            1, 32768, 128, window=1024, dtype="bfloat16").block_q
        w_fl = 2 * 32768 * (1024 + w_bq) * (128 + 128)
        w_s, w_ok = _measure_plausible(
            lambda: _bench_flash_s(32768, 128, args.repeats, args.block_q,
                                   args.block_k, window=1024, n_short=4,
                                   n_long=32, max_mode=args.max_mode), w_fl)
        ladder["swa_w1024_32k"] = {
            "ms": round(w_s * 1e3, 3),
            "clock": w_s.clock,
            "gflops": round(w_fl / w_s / 1e9, 1),
        }
        if not w_ok:
            ladder["swa_w1024_32k"]["implausible_timing"] = True
        # forward+backward at the headline shape (round-2 VERDICT #8: the
        # BENCH record carried forward-only numbers).  FLOPs accounting,
        # exact matmul counts for dk=dv=d (fwd = 4·m·n·d):
        #   * algorithmic: the math needs fwd 4mnd + bwd 10mnd (S, dP,
        #     dV, dQ, dK once each) = 3.5x fwd — the "useful" rate.
        #   * executed: the fused single-pass backward (flash_bwd.py,
        #     round 4) computes S and dO·V^T ONCE, so it executes exactly
        #     the algorithmic 14mnd (large m chunks Q through the same
        #     kernel; window/sinks band it; segments mask it); only
        #     oversized explicit tiles, chunk-scale segmented calls, and
        #     pallas without vmem_limit_bytes fall back to the two-kernel
        #     path, which re-derives both in each kernel: 18mnd = 4.5x.
        from attention_tpu.ops.flash_bwd import fused_backward_applicable

        # mirror _bench_flash_s's effective-tile resolution: explicit
        # --block-q/--block-k flow into flash_backward and can flip the
        # dispatch (oversized tiles fail the fused VMEM plan), so the
        # accounting must ask with the same tiles the run uses
        if args.block_q is None and args.block_k is None:
            bwd_bs = None
        else:
            _eff = BlockSizes.for_shape(1, args.seq, args.dim, None,
                                        dtype="bfloat16")
            bwd_bs = BlockSizes(args.block_q or _eff.block_q,
                                args.block_k or _eff.block_k)
        bwd_fused = fused_backward_applicable(
            args.seq, args.dim, window=None, sinks=None, segmented=False,
            block_sizes=bwd_bs)
        bwd_fl_exec = int((3.5 if bwd_fused else 4.5) * flops)
        bwd_s, bwd_ok = _measure_plausible(
            lambda: _bench_flash_s(args.seq, args.dim, args.repeats,
                                   args.block_q, args.block_k,
                                   backward=True, max_mode=args.max_mode,
                                   n_short=2, n_long=8), bwd_fl_exec)
        ladder["fwd_bwd_32k"] = {
            "ms": round(bwd_s * 1e3, 3),
            "clock": bwd_s.clock,
            "bwd_impl": "fused" if bwd_fused else "two_kernel",
            "util_executed_flops": round(
                bwd_fl_exec / bwd_s / peak_flops(), 4),
            "util_algorithmic_flops": round(
                3.5 * flops / bwd_s / peak_flops(), 4),
        }
        if not bwd_ok:
            ladder["fwd_bwd_32k"]["implausible_timing"] = True
        # causal and windowed backward rows: the fused kernel's banded /
        # diagonal-skipping paths (plausibility screened on algorithmic
        # FLOPs, which lower-bound executed; util is not reported — the
        # causal band is tile-quantized and the window band estimate
        # belongs to the forward row)
        bwd_ca_s, bwd_ca_ok = _measure_plausible(
            lambda: _bench_flash_s(args.seq, args.dim, args.repeats,
                                   args.block_q, args.block_k,
                                   backward=True, causal=True,
                                   max_mode=args.max_mode,
                                   n_short=2, n_long=8),
            int(1.75 * flops))
        ladder["fwd_bwd_32k_causal"] = {"ms": round(bwd_ca_s * 1e3, 3),
                                        "clock": bwd_ca_s.clock}
        if not bwd_ca_ok:
            ladder["fwd_bwd_32k_causal"]["implausible_timing"] = True
        # truly algorithmic band (window columns only, no tile slack) so
        # the screen's FLOPs genuinely lower-bound any tiling's executed
        w_bwd_fl = int(3.5 * 2 * args.seq * 1024 * (args.dim * 2))
        bwd_w_s, bwd_w_ok = _measure_plausible(
            lambda: _bench_flash_s(args.seq, args.dim, args.repeats,
                                   args.block_q, args.block_k,
                                   backward=True, window=1024,
                                   max_mode=args.max_mode,
                                   n_short=2, n_long=12),
            w_bwd_fl)
        ladder["fwd_bwd_swa_w1024_32k"] = {"ms": round(bwd_w_s * 1e3, 3),
                                           "clock": bwd_w_s.clock}
        if not bwd_w_ok:
            ladder["fwd_bwd_swa_w1024_32k"]["implausible_timing"] = True
        # fixed config (name encodes it) — independent of --dim/--seq
        dec_b, dec_h, dec_hkv, dec_len, dec_d = 8, 32, 4, 32768, 128
        dec_s = _bench_decode_s(dec_b, dec_h, dec_hkv, dec_len, dec_d,
                                args.repeats)
        cache_bytes = 2 * dec_b * dec_hkv * dec_len * dec_d * 2
        # Same-session HBM streaming ceiling (round-3 VERDICT weak #3:
        # a decode row once implied 979 GB/s, past the chip's physical
        # streaming rate).  Decode bandwidth is reported as a fraction
        # of this measured ceiling, and fractions > 1.0 are flagged as
        # implausible the way _measure_plausible flags >0.98 matmul
        # util — a physically impossible reading must never stand.
        ceiling_gbps = _hbm_streaming_gbps(args.repeats)

        def _decode_row(t_s, bytes_read):
            gbps = bytes_read / t_s / 1e9
            row = {
                "ms": round(t_s * 1e3, 3),
                "clock": t_s.clock,
                "tokens_per_s": round(dec_b / t_s, 1),
                "cache_read_gb_per_s": round(gbps, 1),
                "frac_of_streaming_ceiling": round(gbps / ceiling_gbps, 3),
            }
            # the ceiling PROBE is itself a measurement (~±1%); frac a
            # hair over 1.0 means decode and probe agree at the
            # roofline.  Flag only readings past the probe's
            # uncertainty — those are timing artifacts (the round-3
            # 979 GB/s case would read frac ~1.3 here) — the same
            # philosophy as PLAUSIBLE_UTIL's margin on the matmul side.
            if gbps > ceiling_gbps * 1.05:
                row["implausible_timing"] = True
            return row

        ladder["hbm_streaming_ceiling_gb_per_s"] = round(ceiling_gbps, 1)
        ladder["decode_b8_32q4kv_cache32k"] = _decode_row(dec_s, cache_bytes)
        dq_s = _bench_decode_s(dec_b, dec_h, dec_hkv, dec_len, dec_d,
                               args.repeats, quantized=True)
        # int8 values + 32B/row replicated fp32 scales vs bf16 values
        int8_bytes = cache_bytes * (dec_d + 32) // (2 * dec_d)
        ladder["decode_int8_cache32k"] = {
            **_decode_row(dq_s, int8_bytes),
            "hbm_vs_bf16": round((dec_d + 32) / (2 * dec_d), 2),
        }
        d4_s = _bench_decode_s(dec_b, dec_h, dec_hkv, dec_len, dec_d,
                               args.repeats, quantized="int4")
        # packed nibbles + 32B/row replicated fp32 scales vs bf16
        int4_bytes = cache_bytes * (dec_d // 2 + 32) // (2 * dec_d)
        ladder["decode_int4_cache32k"] = {
            **_decode_row(d4_s, int4_bytes),
            "hbm_vs_bf16": round((dec_d // 2 + 32) / (2 * dec_d), 2),
        }
        pg_s = _bench_paged_decode_s(dec_b, dec_h, dec_hkv, dec_len,
                                     dec_d, args.repeats)
        ladder["decode_paged_cache32k"] = _decode_row(pg_s, cache_bytes)
        result["detail"]["ladder"] = ladder

    # Re-emit the headline row through the unified telemetry registry
    # (attention_tpu.obs): one scrape shows benchmark results next to
    # op-dispatch and tuning counters.  No-op while obs is disabled.
    from attention_tpu import obs

    if obs.enabled():
        obs.gauge("bench.headline.speedup",
                  "speedup vs the serial attention.c baseline").set(
            result["value"])
        obs.gauge("bench.headline.kernel_ms").set(
            result["detail"]["tpu_kernel_ms"])
        obs.gauge("bench.headline.utilization").set(
            result["detail"]["mxu_utilization_of_peak"])
        obs.counter("bench.runs.recorded").inc(
            config=f"headline-{args.seq}", backend="flash")

    print(json.dumps(result))
    failed = [k for k in ("kernel_check_failed", "headline_contract_failed")
              if result["detail"].get(k)]
    if failed:
        print(f"bench.py: correctness check failed: {failed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
