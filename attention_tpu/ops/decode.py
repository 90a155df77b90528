"""Fused Pallas flash-decode kernel: one new token vs a KV cache.

Inference surface the reference never had (it is a forward-only batch
kernel, `attention-mpi.c:191-407`); this is the autoregressive-decoding
analog of its online-softmax pass (`attention-mpi.c:168-189`): a single
query row scans the cached KV rows with a running (max, sumexp)
recurrence, fused in one kernel (the tile body is shared with the
forward kernel, `flash.py::_flash_tile`).

TPU-native design notes:
  * Decode is HBM-bandwidth-bound (the used KV prefix streams through
    VMEM once per step), so the kernel's job is to keep the DMA pipeline
    full — the KV grid dimension gives Pallas' automatic double
    buffering — and to spend nothing on the unused cache tail: the
    per-sequence lengths are **scalar-prefetched** so the K/V BlockSpec
    index maps clamp every out-of-range block index to the last valid
    block.  Pallas elides the DMA when consecutive grid steps map to the
    same block, and `@pl.when(j * block_k < valid)` skips the compute,
    so both bandwidth and FLOPs scale with the *used* prefix, not the
    cache capacity — at ``block_k`` granularity: the default 2048 rows
    (sweep-chosen: 512-row blocks cap streaming at ~450-500 GB/s where
    2048 reaches ~730-900) means a short prefix still pays one full
    block per KV head (~0.05 ms); pass a smaller ``block_k`` if a
    workload lives entirely at short lengths.
  * All Q heads sharing one KV head (GQA) are processed together as the
    row-block of a single (group, block_k) MXU matmul, so the KV cache
    is read once per KV head, not once per Q head.
  * Per-batch cache lengths make a ragged batch decode in one call with
    no host-side bucketing.

Layout: Q (B, H, d) — one token per sequence; caches (B, Hkv, N, d|dv)
with static capacity N; lengths (B,) int32 (or a scalar, broadcast).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu import obs
from attention_tpu.ops.flash import (
    _LOG2E,
    _STAT_LANES,
    NEG_INF,
    _ceil_to,
    _compiler_params,
    _flash_tile,
    _should_interpret,
    _tuned_max_mode,
    check_softcap,
)

# Op-dispatch telemetry (attention_tpu.obs, off by default): one tick
# per host-side dispatch; calls inside an enclosing jit tick per trace.
# `ops.decode.lowered` ticks at TRACE time inside the jitted bodies and
# records which rescaling-math variant each dispatch actually lowered
# (the decode analog of `ops.flash.lowered`).
_DECODE_CALLS = obs.counter(
    "ops.decode.calls", "flash_decode dispatches by cache shape bucket")
_DECODE_LOWERED = obs.counter(
    "ops.decode.lowered",
    "decode kernel lowerings by requested/resolved max mode")

#: max_mode values the decode kernels accept — "bound" is forward-only
#: (it needs the key-norm prefetch the decode grid does not carry).
DECODE_MAX_MODES = ("online", "flashd", "amla", "auto")


def _resolve_decode_max_mode(max_mode: str, *, batch, h, hkv, n, d,
                             dtype, window, sinks) -> str:
    """Validate and statically resolve a decode-side ``max_mode``:
    "auto" consults the tuning tables (decode family key), anything the
    table cannot legally pick falls back to the online oracle."""
    if max_mode not in DECODE_MAX_MODES:
        raise ValueError(
            f"unknown decode max_mode {max_mode!r}; one of "
            f"{DECODE_MAX_MODES} (bound mode is forward-only)")
    if max_mode != "auto":
        return max_mode
    return _tuned_max_mode(
        "decode", dtype=dtype, allowed=("online", "flashd", "amla"),
        heads=h, kv_heads=hkv, seq=n, dim=d, batch=batch,
        window=window, sinks=sinks)


def _decode_kernel(
    lens_ref, q_ref, k_ref, v_ref, o_ref, acc_scr, m_scr, l_scr,
    *, hkv: int, block_k: int, block_q: int, n: int,
    softcap2: float | None = None, window: int | None = None,
    sinks: int | None = None, chunk: int | None = None,
    variant: str = "online",
):
    """One (batch*kv-head, kv-block) grid step of cached decode.

    ``window`` restricts attention to the last ``window`` cached rows of
    each sequence (the query sits at position valid-1), with the first
    ``sinks`` rows pinned (StreamingLLM) — the decode-side counterpart
    of the forward kernel's banded mask.

    ``chunk`` (static): speculative-verify mode — the q block packs
    ``chunk`` consecutive query tokens per group head ((g, s) rows,
    s-minor), the per-sequence length is the length AFTER the chunk's
    rows were appended, and row (g, s) sits at position
    ``valid - chunk + s``: causal within the chunk, window/sinks bands
    per row.  One cache stream scores the whole chunk — the
    arithmetic-intensity win speculative decoding exists for.
    """
    bh = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    valid = lens_ref[bh // hkv]
    if chunk is not None:
        # per-row bands ride the causal+window mask in _flash_tile; the
        # block-level live/clamp below widens the window by chunk-1 so
        # every row's band is covered
        w_eff = None if window is None else window + chunk - 1
    else:
        w_eff = window
    kv_min = None
    if chunk is None and window is not None:
        kv_min = jnp.maximum(valid - window, 0)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = banded_live(j, valid, block_k, w_eff, sinks)

    @pl.when(live)
    def _tile():
        if chunk is None:
            _flash_tile(
                q_ref, k_ref, v_ref, acc_scr, m_scr, l_scr,
                valid=valid, q_offset=0, kv_offset=0,
                kv_idx=j, q_idx=0,
                n_true=n, block_k=block_k, causal=False, block_q=block_q,
                softcap2=softcap2, kv_min=kv_min, sinks=sinks,
                variant=variant,
            )
        else:
            _flash_tile(
                q_ref, k_ref, v_ref, acc_scr, m_scr, l_scr,
                valid=valid, q_offset=valid - chunk, kv_offset=0,
                kv_idx=j, q_idx=0,
                n_true=n, block_k=block_k, causal=True, block_q=block_q,
                softcap2=softcap2, window=window, sinks=sinks,
                pos_mod=chunk, variant=variant,
            )

    @pl.when(j == num_j - 1)
    def _finalize():
        if variant == "flashd":
            # the accumulator is already normalized — no epilogue divide
            o_ref[0] = acc_scr[...].astype(o_ref.dtype)
        else:
            l = jnp.max(l_scr[...], axis=-1, keepdims=True)
            # empty-cache guard, the reference's 1/gsum div-by-zero
            # guard (attention-mpi.c:358-362)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def check_band(window, sinks) -> None:
    """Shared validation for the decode-side window/sinks contract
    (mirrors flash_attention's): sinks require a window, both >= 1."""
    if sinks is not None:
        if window is None:
            raise ValueError("sinks require window= (see flash_attention)")
        if sinks < 1:
            raise ValueError(f"sinks must be >= 1, got {sinks}")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def banded_live(j, valid, block_k: int, window, sinks):
    """Compute-guard predicate paired with :func:`banded_block_clamp`:
    True for blocks holding valid rows inside the window band or pinned
    sink rows.  The two MUST stay mirrored — a block the clamp remaps
    must never compute, and a live block must keep its identity index.
    Written in operators alone, so it takes a kernel's scalars, a
    traced array and the host's NumPy arrays alike (``j >= 0``)."""
    live = j * block_k < valid
    if window is not None:
        # a block ends above 0, so the window's start needs no clamp
        above_min = (j + 1) * block_k > valid - window
        if sinks:
            above_min = above_min | (j * block_k < sinks)
        live = live & above_min
    return live


def banded_block_clamp(j, valid, block_k: int, window, sinks):
    """DMA-eliding clamp for a decode kernel's KV block index.

    Past-the-prefix blocks clamp to the last valid block (Pallas elides
    the HBM->VMEM DMA when consecutive grid steps map to the same
    block, so bandwidth scales with the used prefix).  With a window,
    leading blocks below the window start clamp UP to the window's
    first block — keeping sink blocks at their identity indices when
    sinks are on — so bandwidth scales with the WINDOW, not the prefix.
    Shared by the bf16 (`flash_decode`) and int8
    (`flash_decode_quantized`) kernels; the clamp must mirror their
    `live` compute guards.
    """
    last = jnp.maximum((valid + block_k - 1) // block_k - 1, 0)
    jj = jnp.minimum(j, last)
    if window is not None:
        floor = jnp.minimum(jnp.maximum(valid - window, 0) // block_k, last)
        if sinks:
            sink_last = (sinks - 1) // block_k
            jj = jnp.where(jj <= sink_last, jj, jnp.maximum(jj, floor))
        else:
            jj = jnp.maximum(jj, floor)
    return jj


def _pick_block_k(n: int, want: int) -> int:
    """Largest multiple of 128 that divides n and is <= want."""
    if n % 128:
        raise ValueError(f"cache capacity {n} must be a multiple of 128")
    bk = min(_ceil_to(want, 128), n)
    while n % bk:
        bk -= 128
    return bk


# The sweep-chosen dense-decode KV block (the heuristic the tuner falls
# back to): 512-row blocks cap streaming at ~450-500 GB/s where 2048
# reaches ~730-900 (module docstring).
_DEFAULT_BLOCK_K = 2048


def _default_block_k(batch: int, h: int, hkv: int, n: int, d: int,
                     dtype, window, sinks) -> int:
    """Resolve an unspecified decode ``block_k``: tuning tables first
    (user cache -> shipped table, keyed by device kind — see
    `attention_tpu.tuning`), then the measured `_DEFAULT_BLOCK_K`, so
    hosts with no cache entries behave exactly as before."""
    try:
        from attention_tpu.tuning.lookup import key_fields, lookup

        entry = lookup(
            "decode", dtype=dtype,
            **key_fields("decode", heads=h, kv_heads=hkv, seq=n, dim=d,
                         batch=batch, window=window, sinks=sinks),
        )
        if entry is not None:
            bk = int(entry["block_k"])
            if bk > 0 and bk % 128 == 0:
                return bk
    except Exception:  # noqa: BLE001 - tuning must never break dispatch
        pass
    return _DEFAULT_BLOCK_K


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_k", "interpret", "softcap", "window",
                     "sinks", "max_mode"),
)
def _flash_decode_jit(
    q: jax.Array,        # (B, H, d)
    k_cache: jax.Array,  # (B, Hkv, N, d)
    v_cache: jax.Array,  # (B, Hkv, N, dv)
    lengths: jax.Array,  # (B,) int32 valid rows per sequence, or scalar
    *,
    scale: float | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
    max_mode: str = "online",
) -> jax.Array:
    """softmax(q K[:len]^T * scale) V[:len] per sequence -> (B, H, dv).

    ``softcap`` applies Gemma-2-style logit capping before softmax.
    ``window`` attends only the last ``window`` valid rows per sequence
    (sliding-window serving on a dense/ragged cache — each query sits at
    its sequence's position ``len-1``); ``sinks`` additionally pins the
    first ``sinks`` rows (StreamingLLM), requires ``window``.
    ``max_mode`` picks the rescaling math ("online"/"flashd"/"amla",
    same softmax — see `flash_attention`); "auto" consults the tuning
    tables and falls back to "online"."""
    check_softcap(softcap)
    check_band(window, sinks)
    if q.ndim != 3 or k_cache.ndim != 4 or v_cache.ndim != 4:
        raise ValueError(
            f"expected q (B,H,d), caches (B,Hkv,N,d): got "
            f"Q{q.shape} K{k_cache.shape} V{v_cache.shape}"
        )
    b, h, d = q.shape
    bk_, hkv, n, dk = k_cache.shape
    dv = v_cache.shape[-1]
    if bk_ != b or v_cache.shape[:3] != (b, hkv, n) or dk != d:
        raise ValueError(
            f"cache shapes inconsistent: Q{q.shape} K{k_cache.shape} "
            f"V{v_cache.shape}"
        )
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    group = h // hkv

    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))

    # Pre-scale Q by scale*log2(e) (flash.py's log2-domain trick) and lay
    # the q-head group out as the row block of one matmul per KV head.
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    qs = qs.reshape(b * hkv, group, d)
    group_pad = _ceil_to(group, 16)  # min sublane tile (bf16-safe)
    if group_pad != group:
        qs = jnp.pad(qs, ((0, 0), (0, group_pad - group), (0, 0)))

    if block_k is None:
        block_k = _default_block_k(b, h, hkv, n, d, q.dtype, window, sinks)
    block_k = _pick_block_k(n, block_k)
    variant = _resolve_decode_max_mode(
        max_mode, batch=b, h=h, hkv=hkv, n=n, d=d, dtype=q.dtype,
        window=window, sinks=sinks)
    if obs.is_enabled():
        _DECODE_LOWERED.inc(requested=max_mode, lowered=variant,
                            entry="decode")
    kc = k_cache.reshape(b * hkv, n, d)
    vc = v_cache.reshape(b * hkv, n, dv)

    def kv_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, banded_block_clamp(j, valid, block_k, window, sinks), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n // block_k),
        in_specs=[
            pl.BlockSpec((1, group_pad, d), lambda bh, j, lens_ref: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, group_pad, dv), lambda bh, j, lens_ref: (bh, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group_pad, dv), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, hkv=hkv, block_k=block_k, block_q=group_pad,
            n=n,
            softcap2=None if softcap is None else softcap * _LOG2E,
            window=window, sinks=sinks, variant=variant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, group_pad, dv), v_cache.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * n * (d + dv),
            bytes_accessed=(kc.size + vc.size) * kc.dtype.itemsize
            + qs.size * qs.dtype.itemsize,
            transcendentals=b * h * n,
        ),
        interpret=interpret,
    )(lens, qs, kc, vc)

    return out[:, :group].reshape(b, h, dv)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, **kwargs) -> jax.Array:
    """One-token-per-sequence decode (telemetry shim; full docs on
    :func:`_flash_decode_jit`)."""
    if obs.is_enabled():
        _DECODE_CALLS.inc(
            bucket=obs.shape_bucket(q.shape[0], k_cache.shape[-2],
                                    q.shape[-1]),
            entry="decode")
    return _flash_decode_jit(q, k_cache, v_cache, lengths, **kwargs)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_k", "interpret", "softcap", "window",
                     "sinks", "max_mode"),
)
def _flash_decode_chunk_jit(
    q: jax.Array,          # (B, H, S, d) — S new tokens per sequence
    k_cache: jax.Array,    # (B, Hkv, N, d), chunk rows ALREADY appended
    v_cache: jax.Array,    # (B, Hkv, N, dv)
    new_lengths: jax.Array,  # (B,) int32 lengths AFTER the append
    *,
    scale: float | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
    max_mode: str = "online",
) -> jax.Array:
    """Score S appended tokens per sequence in ONE cache stream
    -> (B, H, S, dv).

    The speculative-verify primitive on ragged caches: token s of
    sequence b sits at position ``new_lengths[b] - S + s`` and attends
    its causal prefix (window/sinks bands per row).  Equivalent to S
    sequential `flash_decode` calls but reads the cache once — the
    chunked-prefill arithmetic-intensity trade (the reference's Q-batch
    pipelining idea, `attention-mpi.c:268-330`, turned inward), with the
    whole (group, S) row block as one MXU matmul per KV block (the GQA
    trick of this module extended to chunk rows)."""
    check_softcap(softcap)
    check_band(window, sinks)
    if q.ndim != 4 or k_cache.ndim != 4 or v_cache.ndim != 4:
        raise ValueError(
            f"expected q (B,H,S,d), caches (B,Hkv,N,d): got "
            f"Q{q.shape} K{k_cache.shape} V{v_cache.shape}"
        )
    b, h, s_chunk, d = q.shape
    bk_, hkv, n, dk = k_cache.shape
    dv = v_cache.shape[-1]
    if bk_ != b or v_cache.shape[:3] != (b, hkv, n) or dk != d:
        raise ValueError(
            f"cache shapes inconsistent: Q{q.shape} K{k_cache.shape} "
            f"V{v_cache.shape}"
        )
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    group = h // hkv

    lens = jnp.broadcast_to(jnp.asarray(new_lengths, jnp.int32), (b,))

    # rows pack the whole GQA group's chunk: (g, s) with s minor, so the
    # kernel's pos_mod=s_chunk recovers each row's token index
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    qs = qs.reshape(b, hkv, group * s_chunk, d).reshape(
        b * hkv, group * s_chunk, d)
    rows = group * s_chunk
    rows_pad = _ceil_to(rows, 16)  # min sublane tile (bf16-safe)
    if rows_pad != rows:
        qs = jnp.pad(qs, ((0, 0), (0, rows_pad - rows), (0, 0)))

    if block_k is None:
        block_k = _default_block_k(b, h, hkv, n, d, q.dtype, window, sinks)
    block_k = _pick_block_k(n, block_k)
    variant = _resolve_decode_max_mode(
        max_mode, batch=b, h=h, hkv=hkv, n=n, d=d, dtype=q.dtype,
        window=window, sinks=sinks)
    if obs.is_enabled():
        _DECODE_LOWERED.inc(requested=max_mode, lowered=variant,
                            entry="chunk")
    kc = k_cache.reshape(b * hkv, n, d)
    vc = v_cache.reshape(b * hkv, n, dv)
    w_eff = None if window is None else window + s_chunk - 1

    def kv_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, banded_block_clamp(j, valid, block_k, w_eff, sinks), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n // block_k),
        in_specs=[
            pl.BlockSpec((1, rows_pad, d), lambda bh, j, lens_ref: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, rows_pad, dv), lambda bh, j, lens_ref: (bh, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows_pad, dv), jnp.float32),
            pltpu.VMEM((rows_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows_pad, _STAT_LANES), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, hkv=hkv, block_k=block_k, block_q=rows_pad,
            n=n,
            softcap2=None if softcap is None else softcap * _LOG2E,
            window=window, sinks=sinks, chunk=s_chunk, variant=variant,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows_pad, dv),
                                       v_cache.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * s_chunk * n * (d + dv),
            bytes_accessed=(kc.size + vc.size) * kc.dtype.itemsize
            + qs.size * qs.dtype.itemsize,
            transcendentals=b * h * s_chunk * n,
        ),
        interpret=interpret,
    )(lens, qs, kc, vc)

    return out[:, :rows].reshape(b, hkv, group, s_chunk, dv).reshape(
        b, h, s_chunk, dv)


def flash_decode_chunk(q: jax.Array, k_cache: jax.Array,
                       v_cache: jax.Array, new_lengths: jax.Array,
                       **kwargs) -> jax.Array:
    """Chunked (speculative-verify) decode (telemetry shim; full docs
    on :func:`_flash_decode_chunk_jit`)."""
    if obs.is_enabled():
        _DECODE_CALLS.inc(
            bucket=obs.shape_bucket(q.shape[0], k_cache.shape[-2],
                                    q.shape[-1]),
            entry="chunk")
    return _flash_decode_chunk_jit(q, k_cache, v_cache, new_lengths,
                                   **kwargs)
