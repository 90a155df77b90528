"""Timed on-device tile search for the four kernel families.

The measurement contract mirrors bench.py: same input recipe (seeded
normal bf16 tensors), same chained-scan clock
(`utils.timing.benchmark_candidate`, median-of-k), shorter chains because a sweep times many candidates.
Candidates that fail to COMPILE (scoped-VMEM overflow on oversized
tiles) are recorded and skipped, not fatal — the space deliberately
overshoots every chip's budget so a roomier future generation can move
the optimum without a code change.

``timer`` is injectable (``timer(step, x, operands, repeats) ->
seconds``) so the search loop itself is unit-testable on CPU without
timing real kernels.
"""

from __future__ import annotations

import os
import time

from attention_tpu import obs
from attention_tpu.tuning import space
from attention_tpu.tuning.cache import (
    default_cache_path,
    device_key,
    load_table_cached,
    make_key,
)
from attention_tpu.tuning.lookup import dtype_name, key_fields

# Tuning-search progress telemetry (attention_tpu.obs, off by
# default): candidates tried / skipped (compile failures et al.) per
# kernel family, plus one tick per completed search.
_CANDIDATES = obs.counter("tuning.search.candidates",
                          "candidates timed, by kernel family")
_SKIPPED = obs.counter("tuning.search.skipped",
                       "candidates skipped, by kernel family and error")
_SEARCHES = obs.counter("tuning.search.completed",
                        "tune() calls that produced a winner")

#: CLI spelling -> internal kernel family name.
CLI_KERNELS = {
    "flash": "flash_fwd",
    "flash-bwd": "flash_bwd",
    "flash-bwd-fused": "flash_bwd_fused",
    "decode": "decode",
    "paged": "paged",
}


def _default_timer(step, x, operands, repeats):
    from attention_tpu.utils.timing import benchmark_candidate

    return benchmark_candidate(step, x, operands=operands, repeats=repeats)


def _measure_factory(kernel: str, cand, *, heads, kv_heads, seq, dim,
                     batch, dtype, causal, window, sinks, stats,
                     max_mode, interpret):
    """(step, x, operands) for timing one candidate of one family."""
    import jax
    import jax.numpy as jnp

    jdt = jnp.dtype(dtype)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    hkv = kv_heads or heads

    if kernel in ("flash_fwd", "flash_bwd", "flash_bwd_fused"):
        from attention_tpu.ops.flash import BlockSizes

        bs = BlockSizes(*cand)
        q = jax.random.normal(kq, (heads, seq, dim), jdt)
        k = jax.random.normal(kk, (hkv, seq, dim), jdt)
        v = jax.random.normal(kv, (hkv, seq, dim), jdt)
        if kernel == "flash_fwd":
            if stats:
                from attention_tpu.ops.flash import flash_attention_partials

                def step(x, kk_, vv_):
                    o, _, _ = flash_attention_partials(
                        x, kk_, vv_, block_sizes=bs, causal=causal,
                        window=window, sinks=sinks, max_mode=max_mode,
                        interpret=interpret)
                    return o
            else:
                from attention_tpu.ops.flash import flash_attention

                def step(x, kk_, vv_):
                    return flash_attention(
                        x, kk_, vv_, block_sizes=bs, causal=causal,
                        window=window, sinks=sinks, max_mode=max_mode,
                        interpret=interpret)
            return step, q, (k, v)

        # backward families: a full value_and_grad step with every
        # gradient folded into the timed value (bench.py's grad_step
        # discipline — returning only dQ lets XLA dead-code the dK/dV
        # kernel) and a distribution-stationary carry.
        from attention_tpu.ops.flash_vjp import flash_attention_diff

        def grad_step(x, kk_, vv_):
            def loss(args):
                # (no interpret kwarg: flash_attention_diff resolves
                # interpret mode from the backend itself)
                o = flash_attention_diff(
                    *args, block_sizes=bs, causal=causal, window=window,
                    sinks=sinks, max_mode=max_mode)
                return jnp.sum(o.astype(jnp.float32))

            _, grads = jax.value_and_grad(loss)((x, kk_, vv_))
            combined = (grads[0].astype(jnp.float32)
                        + jnp.sum(grads[1]).astype(jnp.float32)
                        + jnp.sum(grads[2]).astype(jnp.float32))
            return (x.astype(jnp.float32) + 1e-12 * combined).astype(jdt)

        return grad_step, q, (k, v)

    if kernel == "decode":
        from attention_tpu.ops.decode import flash_decode

        q = jax.random.normal(kq, (batch, heads, dim), jdt)
        kc = jax.random.normal(kk, (batch, hkv, seq, dim), jdt)
        vc = jax.random.normal(kv, (batch, hkv, seq, dim), jdt)
        lens = jnp.full((batch,), seq, jnp.int32)

        def dstep(x, kcc, vcc, ll):
            return flash_decode(x, kcc, vcc, ll, block_k=cand,
                                window=window, sinks=sinks,
                                max_mode=max_mode,
                                interpret=interpret)

        return dstep, q, (kc, vc, lens)

    if kernel == "paged":
        import random as _random

        from attention_tpu.ops.paged import (
            PagePool,
            paged_flash_decode,
            paged_from_dense,
        )

        q = jax.random.normal(kq, (batch, heads, dim), jdt)
        kc = jax.random.normal(kk, (batch, hkv, seq, dim), jdt)
        vc = jax.random.normal(kv, (batch, hkv, seq, dim), jdt)
        num_pages = batch * (seq // cand)
        pool = PagePool(num_pages)
        # scrambled physical pages, bench.py's fragmentation recipe
        ids = pool.alloc(num_pages)
        _random.Random(0).shuffle(ids)
        pool.free(ids)
        cache = paged_from_dense(
            kc, vc, jnp.full((batch,), seq, jnp.int32), pool,
            num_pages=num_pages, page_size=cand)

        def pstep(x, c):
            return paged_flash_decode(x, c, window=window, sinks=sinks,
                                      interpret=interpret).astype(x.dtype)

        return pstep, q, (cache,)

    raise ValueError(f"unknown kernel family {kernel!r}")


def tune(kernel: str, *, seq: int, dim: int, heads: int = 1,
         kv_heads: int | None = None, batch: int = 8,
         dtype="bfloat16", causal: bool = False,
         window: int | None = None, sinks: int | None = None,
         stats: bool = False, max_mode: str = "bound",
         repeats: int = 3, timer=None, cache_path: str | None = None,
         write: bool = True, interpret: bool | None = None,
         log=None) -> dict:
    """Search one kernel family's space at one shape; persist the winner.

    ``max_mode="auto"`` widens the race to the cross product of tiles
    and the family's rescaling-math variants
    (:func:`space.max_mode_candidates`) and records the winning variant
    in the entry's ``max_mode`` field — the value the kernels'
    ``max_mode="auto"`` dispatch later reads back.  An explicit
    ``max_mode`` pins the variant (and is recorded likewise for
    mode-capable families); the default ``"bound"`` measures each
    family's historical forward default (decode/ragged cannot lower
    bound and fall to ``"online"``).

    Returns a record: per-candidate ``ms`` (or ``error`` for candidates
    that failed to compile/run), the winning entry, the cache key it was
    stored under, and whether it was written.  Raises RuntimeError only
    when EVERY candidate fails.
    """
    if kernel not in CLI_KERNELS.values():
        raise ValueError(f"unknown kernel family {kernel!r}; "
                         f"one of {sorted(CLI_KERNELS.values())}")
    timer = timer or _default_timer
    fields = key_fields(kernel, heads=heads, kv_heads=kv_heads, seq=seq,
                        dim=dim, batch=batch, causal=causal,
                        window=window, sinks=sinks, stats=stats)
    cands = space.candidates(kernel, m=seq, n=seq, d=dim, window=window)
    if not cands:
        raise RuntimeError(
            f"no shape-legal candidates for {kernel} at seq={seq}")
    mode_cands = space.max_mode_candidates(kernel)
    if max_mode == "auto":
        # joint (tile, mode) race; families without a mode field keep
        # the forward's historical default
        mode_list = list(mode_cands) or ["bound"]
    else:
        mode_list = [max_mode]
        if mode_cands and max_mode not in mode_cands:
            if max_mode != "bound":
                raise ValueError(
                    f"{kernel} cannot lower max_mode {max_mode!r}; one "
                    f"of {mode_cands + ('auto',)}")
            # decode/ragged cannot lower "bound" (the tune() default,
            # kept for CLI compatibility): measure their online default
            mode_list = ["online"]
    results: dict = {}
    best_cand = None
    best_mode = None
    best_s = None
    force_two_kernel = kernel == "flash_bwd"
    if force_two_kernel:
        # the two-kernel family's entry feeds default_bwd_block_sizes,
        # which only governs the NON-fused dispatch — measure that path
        import attention_tpu.ops.flash_bwd as _bwd

        prev_force = _bwd._FORCE_TWO_KERNEL
        _bwd._FORCE_TWO_KERNEL = True
    try:
        for cand in cands:
            base = (f"{cand[0]}x{cand[1]}" if isinstance(cand, tuple)
                    else str(cand))
            for mode in mode_list:
                label = f"{base}@{mode}" if len(mode_list) > 1 else base
                try:
                    with obs.span("tuning.search.measure"):
                        step, x, operands = _measure_factory(
                            kernel, cand, heads=heads, kv_heads=kv_heads,
                            seq=seq, dim=dim, batch=batch, dtype=dtype,
                            causal=causal, window=window, sinks=sinks,
                            stats=stats, max_mode=mode,
                            interpret=interpret)
                        sec = float(timer(step, x, operands, repeats))
                    _CANDIDATES.inc(kernel=kernel)
                except Exception as e:  # noqa: BLE001 - VMEM overflow
                    results[label] = {"error": f"{type(e).__name__}: "
                                               f"{str(e)[:160]}"}
                    _SKIPPED.inc(kernel=kernel, error=type(e).__name__)
                    if log:
                        log(f"  {label}: SKIP ({type(e).__name__})")
                    continue
                results[label] = {"ms": round(sec * 1e3, 4)}
                if log:
                    log(f"  {label}: {sec * 1e3:.3f} ms")
                if best_s is None or sec < best_s:
                    best_s, best_cand, best_mode = sec, cand, mode
    finally:
        if force_two_kernel:
            _bwd._FORCE_TWO_KERNEL = prev_force
    if best_cand is None:
        raise RuntimeError(
            f"every candidate failed for {kernel} at seq={seq}: {results}")
    _SEARCHES.inc(kernel=kernel)

    if kernel == "decode":
        entry = {"block_k": int(best_cand)}
    elif kernel == "paged":
        entry = {"page_size": int(best_cand)}
    else:
        entry = {"block_q": int(best_cand[0]), "block_k": int(best_cand[1])}
    if mode_cands:
        entry["max_mode"] = best_mode
    entry.update({
        "ms": round(best_s * 1e3, 4),
        "source": "measured",
        "recorded": time.strftime("%Y-%m-%d"),
    })
    key = make_key(device_key(), kernel, dtype=dtype_name(dtype),
                   **fields)
    path = cache_path or default_cache_path()
    written = False
    if write:
        table = load_table_cached(path)
        table.put(key, entry)
        table.save(path)
        written = True
        if log and os.environ.get("ATTN_TPU_TUNING_CACHE") != path:
            log(f"wrote {path}; dispatch reads it only when "
                f"ATTN_TPU_TUNING_CACHE={path} is exported")
    return {
        "kernel": kernel,
        "key": key,
        "candidates": results,
        "entry": entry,
        "cache_path": path,
        "written": written,
    }
