"""Pallas flash-attention backward kernels for TPU.

The reference is forward-only (no backward exists in `attention.c` /
`attention-mpi.c`); this is new training surface.  The math is the
standard flash backward — recompute P tile-wise from the saved
log-sum-exp, then

    P  = exp(S - lse)            D  = rowsum(dO ∘ O)   (precomputed)
    dV = Pᵀ dO                   dS = P ∘ (dO Vᵀ - D)
    dQ = scale · dS K            dK = scale · dSᵀ Q

— executed as two Pallas kernels instead of blocked XLA einsums:

  * **dQ kernel**: grid (head, q-block, kv-block), kv innermost; dQ
    accumulates in VMEM scratch across the KV sweep (the mirror of the
    forward's online accumulator).
  * **dK/dV kernel**: grid (kv-block, q-head, q-block) with the q-head
    dimension ordered so all Q heads sharing one KV head (GQA) form a
    contiguous run — dK/dV accumulate across the whole run in VMEM
    scratch and are written once per KV head.  The grouped reduction
    never materializes `jnp.repeat`-expanded gradients in HBM.

Tiles are **Q-major** ((block_q, block_k)), matching the forward
kernel: the per-row stats lse/D enter lane-replicated as
(block_q, _STAT_LANES) blocks — the same layout the forward emits —
because Mosaic requires the last two block dims to be (8k, 128m), which
a narrow (1, block_q) row-vector block violates.  Lane-replicated
stats reduce to (block_q, 1) columns with no in-kernel transposes, and
the MXU contracts over either operand dimension, so Pᵀ dO / dSᵀ Q are
single dot_generals on the Q-major tiles.

Domain bookkeeping matches the forward (`flash.py::_flash_call`): Q is
pre-scaled by scale·log2(e) and re-rounded to the input dtype, so scores
are log2-domain and P = exp2(S₂ - lse₂) reproduces the forward's exact
probabilities; dK picks up a ln2 factor (dK = ln2 · dSᵀ Q_scaled) and dQ
the plain `scale` (contraction against unscaled K).

Sliding-window note: like the forward kernel, windowed backward uses
banded grids — the dQ kernel's KV sweep and the dK/dV kernel's Q sweep
cover only the blocks the window can touch (skipped grid steps are not
free: they pay un-overlapped DMA latency), so windowed backward
wall-time scales with the window, not the sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu.ops.flash import (
    _LN2,
    _LOG2E,
    _STAT_LANES,
    NEG_INF,
    BlockSizes,
    _big_tile_device,
    _ceil_to,
    _compiler_params,
)


def _stat_col(ref):
    """Lane-replicated (block_q, _STAT_LANES) stat block -> (block_q, 1)."""
    return jnp.max(ref[0], axis=-1, keepdims=True)


def _recompute_p(qs, k, lse_col, *, causal, q_base, k_base,
                 q_off=0, kv_off=0, valid=None,
                 q_seg_ref=None, kv_seg_ref=None, window=None,
                 softcap2=None):
    """(block_q, block_k) probability tile, Q-major; returns (p, dcap)
    where ``dcap`` is the softcap derivative factor 1 - tanh^2 (None
    when no softcap).

    ``qs`` is the forward's pre-scaled Q (scores come out log2-domain),
    ``lse_col`` a (block_q, 1) log2-domain log-sum-exp column.
    ``q_off``/``kv_off`` are the global positions of this call's local
    Q/KV row 0 (dynamic scalars — causal masking stays correct when the
    caller holds only a shard, the forward kernel's offsets contract);
    ``valid`` is a traced count of valid LOCAL KV rows, or None when
    every row is real.
    """
    s2 = jax.lax.dot_general(
        qs, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (block_q, block_k)
    dcap = None
    if softcap2 is not None:
        t = jnp.tanh(s2 / softcap2)
        s2 = softcap2 * t
        dcap = 1.0 - t * t
    p = jnp.exp2(s2 - lse_col)
    mask = None
    if causal or valid is not None:
        row = q_base + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        col = k_base + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    if valid is not None:
        # rows the forward fully masked have lse == -inf (guard them too:
        # exp2(s - -inf) would be +inf, not 0)
        mask = jnp.logical_and(col < valid, lse_col != NEG_INF)
    if causal:
        # also guards rows the forward fully masked (lse == -inf)
        cm = jnp.logical_and(col + kv_off <= row + q_off,
                             lse_col != NEG_INF)
        mask = cm if mask is None else jnp.logical_and(mask, cm)
        if window is not None:
            mask = jnp.logical_and(
                mask, col + kv_off >= row + q_off - (window - 1))
    if q_seg_ref is not None:
        q_ids = jnp.max(q_seg_ref[...], axis=-1, keepdims=True)
        kv_ids = jnp.max(kv_seg_ref[...], axis=0, keepdims=True)
        seg = q_ids == kv_ids
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p, dcap


def _p_and_ds(qs, k, v, do, lse_ref, delta_ref, *, causal, q_base, k_base,
              q_off, kv_off, valid, q_seg_ref, kv_seg_ref, window,
              softcap2):
    """Shared tile derivation for all three backward kernels: recompute
    P from the saved lse, form dP = dO Vᵀ and dS = P ∘ (dP - D) with the
    softcap chain factor applied.  One definition keeps the fused and
    two-kernel gradients provably identical."""
    p, dcap = _recompute_p(
        qs, k, _stat_col(lse_ref), causal=causal,
        q_base=q_base, k_base=k_base, q_off=q_off, kv_off=kv_off,
        valid=valid, q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref,
        window=window, softcap2=softcap2,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (block_q, block_k) = dO Vᵀ
    ds = p * (dp - _stat_col(delta_ref))
    if dcap is not None:
        ds = ds * dcap  # chain through cap*tanh(s/cap)
    return p, ds


def _dq_kernel(
    offsets_ref, lse_ref, delta_ref, qs_ref, k_ref, v_ref, do_ref, *rest,
    causal, block_q, block_k, scale, out_dtype, compute_dtype, segmented,
    window, n_j_total, softcap2, dynamic_valid,
):
    if segmented:
        q_seg_ref, kv_seg_ref, *rest = rest
    else:
        q_seg_ref = kv_seg_ref = None
    dq_ref, acc_scr = rest
    q_off = offsets_ref[0]
    kv_off = offsets_ref[1]
    jb = pl.program_id(2)
    q_base = pl.program_id(1) * block_q
    if window is None:
        j = jb
    else:
        # banded grid (mirrors the forward kernel): skipped grid steps
        # are not free, so the j dimension covers only the window band
        j = jnp.maximum(
            (q_base + q_off - kv_off - (window - 1)) // block_k, 0
        ) + jb
    k_base = j * block_k

    @pl.when(jb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        qs, k = qs_ref[0], k_ref[0]
        _, ds = _p_and_ds(
            qs, k, v_ref[0], do_ref[0], lse_ref, delta_ref,
            causal=causal, q_base=q_base, k_base=k_base,
            q_off=q_off, kv_off=kv_off,
            valid=offsets_ref[2] if dynamic_valid else None,
            q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref, window=window,
            softcap2=softcap2,
        )
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(compute_dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, d) = dS K

    keep = True
    guarded = False
    if causal:
        # KV tiles strictly above the diagonal are all zeros under the
        # causal mask — skip them (halves causal backward FLOPs); the
        # banded window grid can also run past the last real KV block.
        keep = jnp.logical_and(
            keep, k_base + kv_off <= q_base + block_q - 1 + q_off
        )
        guarded = True
        if window is not None:
            keep = jnp.logical_and(keep, j < n_j_total)
    if dynamic_valid:
        # blocks wholly past the valid KV prefix contribute nothing
        keep = jnp.logical_and(keep, k_base < offsets_ref[2])
        guarded = True
    if guarded:
        pl.when(keep)(_compute)
    else:
        _compute()

    @pl.when(jb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (acc_scr[...] * scale).astype(out_dtype)


def _dkv_kernel(
    offsets_ref, lse_ref, delta_ref, qs_ref, k_ref, v_ref, do_ref, *rest,
    causal, block_q, block_k, group, compute_dtype, segmented, window,
    n_i_total, softcap2, dynamic_valid,
):
    if segmented:
        q_seg_ref, kv_seg_ref, *rest = rest
    else:
        q_seg_ref = kv_seg_ref = None
    dk_ref, dv_ref, dk_scr, dv_scr = rest
    q_off = offsets_ref[0]
    kv_off = offsets_ref[1]
    h = pl.program_id(1)
    ib = pl.program_id(2)
    h_in_group = jax.lax.rem(h, group)
    k_base = pl.program_id(0) * block_k
    if window is None:
        i = ib
    else:
        # banded: only q blocks within [diagonal, diagonal + window)
        # contribute to this kv block (diagonal in LOCAL q coordinates:
        # the first local q row that can see local kv row k_base)
        i = jnp.maximum(
            (k_base + kv_off - q_off) // block_q, 0
        ) + ib
    q_base = i * block_q

    @pl.when(jnp.logical_and(h_in_group == 0, ib == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        qs, do = qs_ref[0], do_ref[0]
        p, ds = _p_and_ds(
            qs, k_ref[0], v_ref[0], do, lse_ref, delta_ref,
            causal=causal, q_base=q_base, k_base=k_base,
            q_off=q_off, kv_off=kv_off,
            valid=offsets_ref[2] if dynamic_valid else None,
            q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref, window=window,
            softcap2=softcap2,
        )
        dv_scr[...] += jax.lax.dot_general(
            p.astype(compute_dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, dv) = Pᵀ dO — contraction over the q dim
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(compute_dtype), qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, d) = dSᵀ Q_scaled

    keep = True
    guarded = False
    if causal:
        # Q tiles wholly above the diagonal contribute nothing to this
        # KV block — skip them (halves causal backward FLOPs); the
        # banded window grid can also run past the last real Q block.
        keep = jnp.logical_and(
            keep, k_base + kv_off <= q_base + block_q - 1 + q_off
        )
        guarded = True
        if window is not None:
            # band_i overestimates by one tile when block_k % block_q
            # == 0: also skip q tiles wholly past the window end
            keep = jnp.logical_and(keep, i < n_i_total)
            keep = jnp.logical_and(
                keep,
                q_base + q_off - (window - 1)
                <= k_base + block_k - 1 + kv_off,
            )
    if dynamic_valid:
        keep = jnp.logical_and(keep, k_base < offsets_ref[2])
        guarded = True
    if guarded:
        pl.when(keep)(_compute)
    else:
        _compute()

    @pl.when(
        jnp.logical_and(
            h_in_group == group - 1, ib == pl.num_programs(2) - 1
        )
    )
    def _finalize():
        # Q_scaled carries scale·log2(e); ln2 restores the plain `scale`.
        dk_ref[0] = dk_scr[...] * _LN2
        dv_ref[0] = dv_scr[...]


def _fused_bwd_kernel(
    offsets_ref, lse_ref, delta_ref, qs_ref, k_ref, v_ref, do_ref,
    *rest,
    causal, block_q, block_k, scale, compute_dtype, softcap2,
    dynamic_valid, window, n_i_total, segmented,
):
    """Single-pass fused backward: S, dO·Vᵀ and dS are computed ONCE per
    (q, kv) tile and all three gradients come out of the same sweep —
    10·m·n·d backward matmul FLOPs, the algorithmic minimum under lse
    recompute, vs the two-kernel path's 14·m·n·d (which re-derives S and
    dO·Vᵀ in both kernels).

    Grid is (head, kv-block, q-block) with the q sweep innermost:

      * dK/dV accumulate in VMEM scratch across the q sweep and are
        written once per (head, kv-block) — per-Q-head PARTIALS under
        GQA (the group sum is a cheap XLA reduction outside; unlike the
        two-kernel dK/dV kernel there is no in-kernel group run).
      * dQ accumulates directly in its OUTPUT block: the out spec maps
        on the head alone, so the whole (m_pad, d) fp32 buffer stays
        VMEM-resident across the entire (kv, q) sweep of one head and is
        DMA'd out exactly once — the revisits are all consecutive, which
        is what makes out-ref accumulation legal.  This is also the
        kernel's capacity bound: m_pad·d fp32 (double-buffered) must fit
        VMEM next to the tiles, so `flash_backward` only dispatches here
        for m_pad ≤ ~32k at d=128 (the benchmark headline shape).
    """
    if segmented:
        q_seg_ref, kv_seg_ref, *rest = rest
    else:
        q_seg_ref = kv_seg_ref = None
    dq_ref, dkp_ref, dvp_ref, dk_scr, dv_scr = rest
    q_off = offsets_ref[0]
    kv_off = offsets_ref[1]
    jb = pl.program_id(1)
    ib = pl.program_id(2)
    k_base = jb * block_k
    if window is None:
        i = ib
    else:
        # banded q sweep (mirrors the two-kernel dK/dV kernel): only q
        # blocks within [diagonal, diagonal + window) touch kv block jb
        i = jnp.maximum((k_base + kv_off - q_off) // block_q, 0) + ib
    q_base = i * block_q

    @pl.when(jnp.logical_and(jb == 0, ib == 0))
    def _zero_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(ib == 0)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        qs, k, do = qs_ref[0], k_ref[0], do_ref[0]
        p, ds = _p_and_ds(
            qs, k, v_ref[0], do, lse_ref, delta_ref,
            causal=causal, q_base=q_base, k_base=k_base,
            q_off=q_off, kv_off=kv_off,
            valid=offsets_ref[2] if dynamic_valid else None,
            q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref, window=window,
            softcap2=softcap2,
        )
        dv_scr[...] += jax.lax.dot_general(
            p.astype(compute_dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, dv) = Pᵀ dO
        ds_c = ds.astype(compute_dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds_c, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, d) = dSᵀ Q_scaled
        dq_tile = jax.lax.dot_general(
            ds_c, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, d) = dS K
        sl = pl.dslice(q_base, block_q)
        dq_ref[0, sl, :] += dq_tile * scale

    keep = True
    guarded = False
    if causal:
        # q tiles wholly above the diagonal contribute nothing
        keep = jnp.logical_and(
            keep, k_base + kv_off <= q_base + block_q - 1 + q_off
        )
        guarded = True
        if window is not None:
            # the banded sweep can overrun the real q blocks, and can
            # include q tiles wholly past the window end
            keep = jnp.logical_and(keep, i < n_i_total)
            keep = jnp.logical_and(
                keep,
                q_base + q_off - (window - 1)
                <= k_base + block_k - 1 + kv_off,
            )
    if dynamic_valid:
        keep = jnp.logical_and(keep, k_base < offsets_ref[2])
        guarded = True
    if guarded:
        pl.when(keep)(_compute)
    else:
        _compute()

    @pl.when(ib == pl.num_programs(2) - 1)
    def _finalize():
        # Q_scaled carries scale·log2(e); ln2 restores the plain `scale`.
        dkp_ref[0] = dk_scr[...] * _LN2
        dvp_ref[0] = dv_scr[...]


# VMEM budget for the fused kernel's working set (dQ out block,
# double-buffered, plus the fp32 P/dP/dS tile temporaries and the
# double-buffered input blocks).  88 MB reproduces the on-chip
# compile-success boundary: 512x4096 and 1024x2048 at 32k compile
# (~70 MB by this model), 1024x4096 / 2048x2048 / 512x8192 do not
# (~100 MB).
_FUSED_VMEM_BUDGET = 88 * 2**20

# Q-row chunk sizes tried (largest first) when a sequence exceeds the
# fused kernel's resident-dQ budget as a whole — see the chunk loop in
# `flash_backward`.  Module-level so tests can shrink it to exercise
# the chunked path at test scale.
_FUSED_CHUNK_CANDIDATES = (65536, 32768, 16384, 8192)

# Perf-triage/tuning ONLY (the `_UNSAFE_SKIP_GUARD` precedent in
# flash.py: a code-settable module global, not an env var): force the
# two-kernel backward even where the fused plan fits.  The tuner's
# "flash_bwd" family sets this around its sweep — its entries feed
# `default_bwd_block_sizes`, which only governs the non-fused dispatch,
# so measuring them through the fused kernel would tune the wrong path.
_FORCE_TWO_KERNEL = False


def _fused_plan(m, n, d, dv, block_sizes, dtype, window=None):
    """The (BlockSizes, vmem_estimate) the fused kernel would run with,
    or None when its working set (including the caller's explicit tiles
    and the REAL block-multiple padding) exceeds the VMEM budget."""
    bs = block_sizes or default_fused_bwd_block_sizes(d, dtype, window,
                                                      m=m, n=n)
    bq = min(bs.block_q, _ceil_to(m, 128))
    bk = min(bs.block_k, _ceil_to(n, 128))
    m_pad = _ceil_to(m, bq)
    itemsize = jnp.dtype(dtype).itemsize
    vmem = (
        2 * m_pad * d * 4           # double-buffered dQ out block
        + 4 * bq * bk * 4           # P/dP/dS fp32 tile temporaries
        + 2 * (bq + bk) * (d + dv) * itemsize  # in blocks, double-buffered
        + bk * (d + dv) * 4         # dK/dV scratch accumulators
    )
    if vmem > _FUSED_VMEM_BUDGET:
        return None
    return bs


def _fused_chunk_choice(m, n, d, dv, block_sizes, dtype, *, window,
                        segmented):
    """The Q-row chunk size the chunked-fused path would use, or None
    when that path can't serve the call (feature flags, explicit tiles,
    whole-m already fits, or no candidate fits VMEM).  The SINGLE
    eligibility definition shared by `flash_backward`'s dispatch and
    `fused_backward_applicable` — bench.py keys FLOP accounting off the
    latter, so the two must never drift.  Sinks deliberately do NOT
    gate chunking: each chunk patches its sink sliver via per-chunk
    q_offset (`_sink_patch`), so they are chunk-compatible by design."""
    if (segmented or block_sizes is not None
            or not _big_tile_device()
            or _fused_plan(m, n, d, dv, None, dtype, window) is not None):
        return None
    return next(
        (c for c in _FUSED_CHUNK_CANDIDATES
         if c < m and _fused_plan(c, n, d, dv, None, dtype, window)),
        None,
    )


def fused_backward_applicable(m: int, d: int, *, window, sinks,
                              segmented: bool, n: int | None = None,
                              dv: int | None = None,
                              block_sizes: BlockSizes | None = None,
                              dtype=jnp.bfloat16) -> bool:
    """True when `flash_backward` will take the fused single-pass kernel
    — whole (the resident-dQ plan fits) or Q-chunked (default tiles
    only, any chunk candidate fits).  bench.py keys its executed-FLOPs
    accounting off this: fused executes 10·mnd backward FLOPs, the
    two-kernel path 14·mnd.  ``sinks`` stays in the signature so
    callers describe the full call, but never gates eligibility —
    sinks are chunk-compatible by design (`_fused_chunk_choice`)."""
    if not _big_tile_device():
        return False
    n_eff = n if n is not None else m
    dv_eff = dv if dv is not None else d
    if _fused_plan(m, n_eff, d, dv_eff, block_sizes, dtype,
                   window) is not None:
        return True  # segments ride whole-fused; chunking excludes them
    return _fused_chunk_choice(
        m, n_eff, d, dv_eff, block_sizes, dtype,
        window=window, segmented=segmented) is not None


def _fused_backward(qs, k, v, lse_rep, delta_rep, do, offsets, *,
                    h, hkv, m_pad, n_pad, d, dv, causal, scale,
                    block_q, block_k, softcap, dynamic_valid, interpret,
                    window=None, seg_inputs=()):
    """Drive `_fused_bwd_kernel`; returns (dq, dk, dv) with dk/dv already
    group-summed (fp32)."""
    group = h // hkv
    num_i = m_pad // block_q
    num_j = n_pad // block_k
    if window is None:
        band_i = num_i
    else:
        # banded: q blocks within [diagonal, diagonal + window) per kv
        # block (same bound as the two-kernel dK/dV kernel)
        band_i = min(num_i, (block_k - 1 + window - 1) // block_q + 2)

    def i_c(jj, ii, off):
        # Map the grid's ii to the absolute q block and clamp skipped
        # steps to a block the sweep does compute: Pallas elides the
        # HBM->VMEM DMA when consecutive grid steps map to the same
        # block, so causally skipped (and band-overrun) steps stop
        # fetching q/dO/stat blocks they never read.  The clamp equals
        # the true index for every computed step (same bounds as the
        # kernel's keep guard).
        i0 = jnp.maximum(
            (jj * block_k + off[1] - off[0]) // block_q, 0
        )
        if window is None:
            ii_abs = jnp.maximum(ii, i0) if causal else ii
        else:
            win_last = jnp.maximum(
                (jj * block_k + block_k - 1 + window - 1
                 + off[1] - off[0]) // block_q,
                0,
            )
            ii_abs = jnp.minimum(i0 + ii, win_last)
        return jnp.minimum(ii_abs, num_i - 1)

    stat_spec = pl.BlockSpec(
        (1, block_q, _STAT_LANES),
        lambda hh, jj, ii, off: (hh, i_c(jj, ii, off), 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, num_j, band_i),
        in_specs=[
            stat_spec,
            stat_spec,
            pl.BlockSpec((1, block_q, d),
                         lambda hh, jj, ii, off: (hh, i_c(jj, ii, off), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hh, jj, ii, off: (hh // group, jj, 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda hh, jj, ii, off: (hh // group, jj, 0)),
            pl.BlockSpec((1, block_q, dv),
                         lambda hh, jj, ii, off: (hh, i_c(jj, ii, off), 0)),
            *([
                pl.BlockSpec((block_q, _STAT_LANES),
                             lambda hh, jj, ii, off: (i_c(jj, ii, off), 0)),
                pl.BlockSpec((8, block_k),
                             lambda hh, jj, ii, off: (0, jj)),
            ] if seg_inputs else []),
        ],
        out_specs=[
            pl.BlockSpec((1, m_pad, d), lambda hh, jj, ii, off: (hh, 0, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda hh, jj, ii, off: (hh, jj, 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda hh, jj, ii, off: (hh, jj, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
    )
    dq, dkp, dvp = pl.pallas_call(
        functools.partial(
            _fused_bwd_kernel,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            scale=scale,
            compute_dtype=qs.dtype,
            softcap2=None if softcap is None else softcap * _LOG2E,
            dynamic_valid=dynamic_valid,
            window=window,
            n_i_total=num_i,
            segmented=bool(seg_inputs),
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, m_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((h, n_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((h, n_pad, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=110 * 2**20),
        cost_estimate=pl.CostEstimate(
            # executed tiles = num_j x band_i (banded under window)
            flops=10 * h * n_pad * (band_i * block_q) * d,
            bytes_accessed=(qs.size + do.size) * qs.dtype.itemsize
            + h * (k.size + v.size) // hkv * k.dtype.itemsize
            + (h * m_pad * d + h * n_pad * (d + dv)) * 4,
            transcendentals=h * n_pad * (band_i * block_q),
        ),
        interpret=interpret,
    )(offsets, lse_rep, delta_rep, qs, k, v, do, *seg_inputs)
    if group > 1:
        dkp = dkp.reshape(hkv, group, n_pad, d).sum(axis=1)
        dvp = dvp.reshape(hkv, group, n_pad, dv).sum(axis=1)
    return dq, dkp, dvp


def _sink_patch(q, k, v, out, lse, dout, *, scale, window, sinks, softcap,
                q_offset=None, kv_valid=None):
    """Gradient contributions of sink pairs OUTSIDE the window band.

    The visible set of a windowed+sinks forward partitions exactly into
    window pairs (col within the last `window` positions — covered by
    the banded Pallas kernels with their window-only mask) and sink
    pairs past the window (col < sinks and col < row - (window-1) —
    covered here).  P is recomputed from the saved lse exactly like the
    kernels (same pre-scaled, re-rounded Q; see `flash.py::_flash_call`),
    so each pair is counted once with the forward's probabilities.  The
    sliver is (m x sinks<=window start) — O(m·sinks·d) FLOPs, a few
    fused XLA einsums; no Pallas variant needed.

    ``q_offset`` (dynamic) gives the global position of local Q row 0 —
    sinks under context parallelism, where the caller holds a Q shard
    against full local KV (kv_offset must be 0: sink rows are absolute
    positions of THIS call's KV); ``kv_valid`` masks a padded KV tail.
    """
    h, m, d = q.shape
    hkv, n, dv = v.shape
    group = h // hkv
    se = min(sinks, n)
    kx = _gqa_repeat(k[:, :se], group)
    vx = _gqa_repeat(v[:, :se], group)
    q32 = q.astype(jnp.float32)
    k32 = kx.astype(jnp.float32)
    do32 = dout.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), -1)  # (h, m)
    qsi = (q32 * (scale * _LOG2E)).astype(q.dtype).astype(jnp.float32)
    s = jnp.einsum("hmd,hsd->hms", qsi, k32) * _LN2
    dcap = None
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        s = softcap * t
        dcap = 1.0 - t * t
    lse32 = lse.astype(jnp.float32)[..., None]
    rows = jnp.arange(m) + (0 if q_offset is None else q_offset)
    mask = (jnp.arange(se)[None, :] < rows[:, None] - (window - 1))[None]
    if kv_valid is not None:
        mask = jnp.logical_and(mask,
                               (jnp.arange(se) < kv_valid)[None, None, :])
    mask = jnp.logical_and(mask, lse32 != NEG_INF)
    p = jnp.where(mask, jnp.exp(s - jnp.where(mask, lse32, 0.0)), 0.0)
    dp = jnp.einsum("hme,hse->hms", do32, vx.astype(jnp.float32))
    ds = p * (dp - delta[..., None])
    if dcap is not None:
        ds = ds * dcap
    dq_s = jnp.einsum("hms,hsd->hmd", ds, k32) * scale
    dk_s = jnp.einsum("hms,hmd->hsd", ds, q32) * scale
    dv_s = jnp.einsum("hms,hme->hse", p, do32)
    if group > 1:
        dk_s = dk_s.reshape(hkv, group, se, d).sum(axis=1)
        dv_s = dv_s.reshape(hkv, group, se, dv).sum(axis=1)
    return dq_s, dk_s, dv_s, se


def _gqa_repeat(x, group):
    return jnp.repeat(x, group, axis=0) if group > 1 else x


def _tuned_bwd_tiles(kernel: str, d: int, dtype, window, m, n):
    """Tuning-table tiles for a backward family, or None (heuristic).
    Skipped when the caller has no shape (``m`` None — the defaults are
    also exercised shape-free by tests and docs)."""
    if m is None:
        return None
    try:
        from attention_tpu.tuning.lookup import key_fields, lookup

        entry = lookup(kernel, dtype=dtype,
                       **key_fields(kernel, seq=m, dim=d, window=window))
    except Exception:  # noqa: BLE001 - tuning must never break dispatch
        return None
    if entry is None:
        return None
    try:
        bq, bk = int(entry["block_q"]), int(entry["block_k"])
    except (KeyError, TypeError, ValueError):
        return None
    if bq <= 0 or bk <= 0 or bq % 128 or bk % 128:
        return None
    return BlockSizes(min(bq, _ceil_to(m, 128)),
                      min(bk, _ceil_to(n if n is not None else m, 128)))


def default_bwd_block_sizes(d: int, dtype, window, *,
                            m: int | None = None,
                            n: int | None = None) -> BlockSizes:
    """Measured backward tile defaults (see the rationale comment at the
    use site in :func:`flash_backward`), behind a tuning-table lookup
    (`attention_tpu.tuning`; a host with no cache entries resolves to
    the heuristic below unchanged).  Windowed shapes keep the
    round-1 512x512 — the banded grid covers
    ceil((window-1+block_q)/block_k)+1 KV blocks, so a taller tile
    computes more masked band columns; confirmed by a device-clock
    sweep at w=1024 seq=32k: 512x512 = 3.96 ms vs 4.10-6.23 for every
    other tile tried."""
    import jax.numpy as _jnp

    tuned = _tuned_bwd_tiles("flash_bwd", d, dtype, window, m, n)
    if tuned is not None:
        return tuned
    if window is not None or d > 128:
        return BlockSizes(512, 512)
    if _jnp.dtype(dtype).itemsize <= 2:
        return BlockSizes(1024, 1024)
    return BlockSizes(512, 1024)


def default_fused_bwd_block_sizes(d: int, dtype,
                                  window=None, *,
                                  m: int | None = None,
                                  n: int | None = None) -> BlockSizes:
    """Tile defaults for the fused single-pass backward kernel (swept
    separately from the two-kernel path: the fused kernel's VMEM also
    holds the per-head (m_pad, d) fp32 dQ block, so its tile budget is
    tighter).  Device-clock sweep on the real v5e chip: a wide
    **512x4096** wins every shape tried — 32k single-head 10.32 ms (vs
    10.66 for 1024x1024, 10.49 for 512x2048), 32k causal 6.17, GQA
    8q/2kv 32k causal 51.2 (vs 55.9), fp32 4h/8k 3.10 (vs 3.19 for the
    old 512x1024); 512x8192 and 1024x4096 fail to compile (VMEM).
    Windowed shapes take a compact square: executed band columns per q
    row scale with (window + block_q + block_k), so small tiles waste
    the least band (the same argument as the two-kernel windowed
    default).  Swept at seq=32k: 512x512 wins w=1024 (0.977 ms vs
    1.068 for 512x1024) and w=256 (0.707, tied with 256x256's 0.705),
    and sits 2% off 1024x1024 at w=4096 (2.028 vs 1.987) — one default
    within 2% of best across the window range beats a size ladder.
    Like :func:`default_bwd_block_sizes`, a tuning-table entry (user
    cache -> shipped table) overrides the heuristic; note tuned fused
    tiles still pass through `_fused_plan`'s VMEM feasibility check, so
    an oversized entry demotes the call rather than failing compile."""
    tuned = _tuned_bwd_tiles("flash_bwd_fused", d, dtype, window, m, n)
    if tuned is not None:
        return tuned
    if window is not None:
        return BlockSizes(512, 512)
    return BlockSizes(512, 4096)


def flash_backward(
    q: jax.Array,  # (h, m, d)
    k: jax.Array,  # (hkv, n, d)
    v: jax.Array,  # (hkv, n, dv)
    out: jax.Array,  # (h, m, dv)
    lse: jax.Array,  # (h, m), natural-log domain
    dout: jax.Array,  # (h, m, dv)
    *,
    scale: float,
    causal: bool = False,
    block_sizes: BlockSizes | None = None,
    interpret: bool = False,
    q_segment_ids=None,
    kv_segment_ids=None,
    window: int | None = None,
    softcap: float | None = None,
    sinks: int | None = None,
    q_offset=None,
    kv_offset=None,
    kv_valid=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """dQ, dK, dV via the two Pallas backward kernels.

    ``softcap`` must match the forward's: P is recomputed from capped
    scores and dS picks up the 1 - tanh^2 chain factor.  ``sinks``
    (StreamingLLM, requires ``window``) adds the out-of-window sink
    pairs via the XLA sliver `_sink_patch` on top of the banded
    window-masked kernels.

    ``q_offset``/``kv_offset``/``kv_valid`` are dynamic scalars with the
    same contract as the forward kernel's (`flash.py::_flash_call`): the
    global sequence positions of local row 0 and the count of valid
    local KV rows — what makes the backward composable under context
    parallelism (each device differentiates its shard of the reference's
    orchestrated distribution, `attention-mpi.c:191-407`).  ``sinks``
    pins ABSOLUTE positions and is not supported together with offsets.
    """
    if sinks is not None and kv_offset is not None:
        raise ValueError(
            "sinks do not compose with kv_offset (sink positions are "
            "absolute positions of THIS call's KV rows — a shifted KV "
            "shard cannot contain them); q_offset/kv_valid are fine"
        )
    segmented = q_segment_ids is not None
    if segmented != (kv_segment_ids is not None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires causal=True")
    if sinks is not None:
        if window is None:
            raise ValueError("sinks require window= (see flash_attention)")
        if segmented:
            raise ValueError("sinks do not compose with segment_ids")
    # Backward default pinned independently of the forward's: with the
    # deterministic device clock (scripts/bwd_sweep.py), 1024x1024 beats the round-1 512x512 by
    # 22-28% on bf16 at every shape that compiles (9.43->7.39 ms at
    # 16q/4kv 8k causal; 8.24->6.41 at 16k; 6.50->5.40 non-causal 8k),
    # where 2048x1024 / 1024x2048 VMEM-OOM on some shapes.  fp32 inputs
    # double the q/k/v/dO tile bytes and 1024x1024 OOMs inside the full
    # VJP module (16.79M vs the 16M scoped limit at 16q/4kv 8k, even
    # though it compiles standalone), so fp32 takes 512x1024 (still 15%
    # over the old default: 8.98 vs 10.60 ms).  Larger head dims keep
    # the smallest footprint.
    h, m, d = q.shape
    hkv, n, dv = v.shape
    group = h // hkv

    # Long sequences exceed the fused kernel's resident-dQ budget as a
    # WHOLE but not per Q-row chunk — the context-parallel decomposition
    # applied locally: run the fused kernel per chunk with the chunk's
    # global q_offset and sum the dK/dV contributions (exactly what the
    # CP orchestrator does across devices, `parallel/cp.py`).  10·mnd
    # executed FLOPs instead of the two-kernel fallback's 14·mnd at
    # 131k.  Chunk rounding to bf16 before the sum matches the CP
    # path's per-shard precision (each shard's dK/dV are cast before
    # the psum there too).
    chunk = (None if _FORCE_TWO_KERNEL else
             _fused_chunk_choice(m, n, d, dv, block_sizes, q.dtype,
                                 window=window, segmented=segmented))
    if chunk is not None:
        base_off = 0 if q_offset is None else q_offset
        dq_parts = []
        dk32 = dv32 = None
        for s0 in range(0, m, chunk):
            e0 = min(m, s0 + chunk)
            off = (base_off + s0
                   if causal or q_offset is not None else None)
            dq_c, dk_c, dv_c = flash_backward(
                q[:, s0:e0], k, v, out[:, s0:e0], lse[:, s0:e0],
                dout[:, s0:e0], scale=scale, causal=causal,
                window=window, softcap=softcap, sinks=sinks,
                interpret=interpret, q_offset=off,
                kv_offset=kv_offset, kv_valid=kv_valid,
            )
            dq_parts.append(dq_c)
            dk_c = dk_c.astype(jnp.float32)
            dv_c = dv_c.astype(jnp.float32)
            dk32 = dk_c if dk32 is None else dk32 + dk_c
            dv32 = dv_c if dv32 is None else dv32 + dv_c
        return (jnp.concatenate(dq_parts, axis=1),
                dk32.astype(k.dtype), dv32.astype(v.dtype))

    use_fused = not _FORCE_TWO_KERNEL and fused_backward_applicable(
        m, d, window=window, sinks=sinks, segmented=segmented,
        n=n, dv=dv, block_sizes=block_sizes, dtype=q.dtype)
    if use_fused:
        bs = _fused_plan(m, n, d, dv, block_sizes, q.dtype, window)
    elif block_sizes is not None:
        bs = block_sizes
    else:
        bs = default_bwd_block_sizes(q.shape[-1], q.dtype, window,
                                     m=m, n=n)

    # Same pre-scaled (and re-rounded) Q the forward kernel saw, so the
    # recomputed P matches the forward probabilities bit-for-bit modulo
    # fp32 non-associativity.
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    lse2 = lse.astype(jnp.float32) * _LOG2E
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)

    block_q = min(bs.block_q, _ceil_to(m, 128))
    block_k = min(bs.block_k, _ceil_to(n, 128))
    m_pad = _ceil_to(m, block_q)
    n_pad = _ceil_to(n, block_k)
    do32 = dout.astype(jnp.float32)
    if m_pad != m:
        # Padded Q rows are zero ⇒ their scores are 0 and (with lse2
        # padded to 0) P = 1, but dO = D = 0 zeroes every contribution.
        qs = jnp.pad(qs, ((0, 0), (0, m_pad - m), (0, 0)))
        do32 = jnp.pad(do32, ((0, 0), (0, m_pad - m), (0, 0)))
        lse2 = jnp.pad(lse2, ((0, 0), (0, m_pad - m)))
        delta = jnp.pad(delta, ((0, 0), (0, m_pad - m)))
    if n_pad != n:
        # Padded K/V rows are zero ⇒ they null dQ contributions (dS K
        # hits zero K rows); their dK/dV rows are sliced away below.
        k = jnp.pad(k, ((0, 0), (0, n_pad - n), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, n_pad - n), (0, 0)))
    do = do32.astype(q.dtype)
    compute_dtype = q.dtype

    # Stats enter lane-replicated — Mosaic's block tiling needs the last
    # two dims (8k, 128m), which a (1, block_q) row block violates.
    lse_rep = jnp.broadcast_to(lse2[..., None], (h, m_pad, _STAT_LANES))
    delta_rep = jnp.broadcast_to(delta[..., None], (h, m_pad, _STAT_LANES))

    num_i = m_pad // block_q
    num_j = n_pad // block_k
    if window is None:
        band_j = num_j
        band_i = num_i
    else:
        # banded grids: the inner sweep covers only blocks the window
        # can touch (see the forward kernel's banded-grid note)
        band_j = min(num_j, -(-(window - 1 + block_q) // block_k) + 1)
        band_i = min(num_i, (block_k - 1 + window - 1) // block_q + 2)

    dynamic_valid = kv_valid is not None
    offsets = jnp.stack(
        [
            jnp.asarray(0 if q_offset is None else q_offset, jnp.int32),
            jnp.asarray(0 if kv_offset is None else kv_offset, jnp.int32),
            jnp.asarray(n if kv_valid is None else kv_valid, jnp.int32),
        ]
    )

    if use_fused:
        # single-pass fused kernel: 10·mnd executed backward FLOPs vs the
        # two-kernel path's 14·mnd (S and dO·Vᵀ computed once, not twice)
        fused_seg = ()
        if segmented:
            from attention_tpu.ops.flash import segment_masks

            fused_seg = segment_masks(q_segment_ids, kv_segment_ids,
                                      m, n, m_pad, n_pad)
        dq_f, dk_f, dv_f = _fused_backward(
            qs, k, v, lse_rep, delta_rep, do, offsets,
            h=h, hkv=hkv, m_pad=m_pad, n_pad=n_pad, d=d, dv=dv,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            softcap=softcap, dynamic_valid=dynamic_valid,
            interpret=interpret, window=window, seg_inputs=fused_seg)
        dq_f = dq_f[:, :m]
        dk_f, dv_f = dk_f[:, :n], dv_f[:, :n]
        if sinks is not None:
            # out-of-window sink pairs, same sliver as the two-kernel
            # composition (the banded fused kernel covers the window
            # band only)
            dq_s, dk_s, dv_s, se = _sink_patch(
                q, k[:, :n], v[:, :n], out, lse, dout,
                scale=scale, window=window, sinks=sinks, softcap=softcap,
                q_offset=q_offset, kv_valid=kv_valid,
            )
            dq_f = dq_f + dq_s
            dk_f = dk_f.at[:, :se].add(dk_s)
            dv_f = dv_f.at[:, :se].add(dv_s)
        return (dq_f.astype(q.dtype), dk_f.astype(k.dtype),
                dv_f.astype(v.dtype))

    def j_abs(ii, jj, off):
        # clamp band-tail steps to the last block the row actually
        # computes (its causal diagonal), so their DMAs elide instead of
        # fetching a never-used block
        if window is None:
            jj_c = jj
        else:
            base = jnp.maximum(
                (ii * block_q + off[0] - off[1] - (window - 1)) // block_k,
                0,
            )
            causal_last = jnp.maximum(
                (ii * block_q + block_q - 1 + off[0] - off[1]) // block_k, 0
            )
            jj_c = jnp.minimum(base + jj,
                               jnp.minimum(causal_last, num_j - 1))
        if dynamic_valid:
            valid_last = jnp.maximum(
                (off[2] + block_k - 1) // block_k - 1, 0
            )
            jj_c = jnp.minimum(jj_c, valid_last)
        return jj_c

    def i_abs(jj, ii, off):
        # clamp to the last q block inside this kv block's window span
        if window is None:
            return ii
        first = jnp.maximum(
            (jj * block_k + off[1] - off[0]) // block_q, 0
        )
        win_last = jnp.maximum(
            (jj * block_k + block_k - 1 + window - 1 + off[1] - off[0])
            // block_q,
            0,
        )
        return jnp.minimum(first + ii,
                           jnp.minimum(win_last, num_i - 1))

    seg_inputs = ()
    seg_specs_q = []
    seg_specs_kv = []
    if segmented:
        from attention_tpu.ops.flash import segment_masks

        q_rep, kv_rep = segment_masks(q_segment_ids, kv_segment_ids,
                                      m, n, m_pad, n_pad)
        seg_inputs = (q_rep, kv_rep)
        seg_specs_q = [
            pl.BlockSpec((block_q, _STAT_LANES),
                         lambda hh, ii, jj, off: (ii, 0)),
            pl.BlockSpec((8, block_k),
                         lambda hh, ii, jj, off: (0, j_abs(ii, jj, off))),
        ]
        seg_specs_kv = [
            pl.BlockSpec((block_q, _STAT_LANES),
                         lambda jj, hh, ii, off: (i_abs(jj, ii, off), 0)),
            pl.BlockSpec((8, block_k), lambda jj, hh, ii, off: (0, jj)),
        ]

    stat_spec_q = pl.BlockSpec(
        (1, block_q, _STAT_LANES), lambda hh, ii, jj, off: (hh, ii, 0)
    )
    dq_grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, num_i, band_j),
        in_specs=[
            stat_spec_q,
            stat_spec_q,
            pl.BlockSpec((1, block_q, d),
                         lambda hh, ii, jj, off: (hh, ii, 0)),
            pl.BlockSpec(
                (1, block_k, d),
                lambda hh, ii, jj, off: (hh // group, j_abs(ii, jj, off), 0),
            ),
            pl.BlockSpec(
                (1, block_k, dv),
                lambda hh, ii, jj, off: (hh // group, j_abs(ii, jj, off), 0),
            ),
            pl.BlockSpec((1, block_q, dv),
                         lambda hh, ii, jj, off: (hh, ii, 0)),
            *seg_specs_q,
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda hh, ii, jj, off: (hh, ii, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            scale=scale,
            out_dtype=q.dtype,
            compute_dtype=compute_dtype,
            segmented=segmented,
            window=window,
            n_j_total=num_j,
            softcap2=None if softcap is None else softcap * _LOG2E,
            dynamic_valid=dynamic_valid,
        ),
        grid_spec=dq_grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, m_pad, d), q.dtype),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=110 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=6 * h * m_pad * (band_j * block_k) * d,
            bytes_accessed=(qs.size + do.size) * qs.dtype.itemsize
            + h * (k.size + v.size) // hkv * k.dtype.itemsize
            + h * m_pad * d * qs.dtype.itemsize,
            transcendentals=h * m_pad * (band_j * block_k),
        ),
        interpret=interpret,
    )(offsets, lse_rep, delta_rep, qs, k, v, do, *seg_inputs)[:, :m]

    stat_spec_kv = pl.BlockSpec(
        (1, block_q, _STAT_LANES),
        lambda jj, hh, ii, off: (hh, i_abs(jj, ii, off), 0),
    )
    dkv_grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_j, h, band_i),
        in_specs=[
            stat_spec_kv,
            stat_spec_kv,
            pl.BlockSpec((1, block_q, d),
                         lambda jj, hh, ii, off: (hh, i_abs(jj, ii, off), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda jj, hh, ii, off: (hh // group, jj, 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda jj, hh, ii, off: (hh // group, jj, 0)),
            pl.BlockSpec((1, block_q, dv),
                         lambda jj, hh, ii, off: (hh, i_abs(jj, ii, off), 0)),
            *seg_specs_kv,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d),
                         lambda jj, hh, ii, off: (hh // group, jj, 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda jj, hh, ii, off: (hh // group, jj, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
    )
    dk, dvg = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            group=group,
            compute_dtype=compute_dtype,
            segmented=segmented,
            window=window,
            n_i_total=num_i,
            softcap2=None if softcap is None else softcap * _LOG2E,
            dynamic_valid=dynamic_valid,
        ),
        grid_spec=dkv_grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hkv, n_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((hkv, n_pad, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=110 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=8 * h * (band_i * block_q) * n_pad * d,
            bytes_accessed=(qs.size + do.size) * qs.dtype.itemsize
            + h * (k.size + v.size) // hkv * k.dtype.itemsize
            + (n_pad * (d + dv)) * hkv * 4,
            transcendentals=h * (band_i * block_q) * n_pad,
        ),
        interpret=interpret,
    )(offsets, lse_rep, delta_rep, qs, k, v, do, *seg_inputs)
    dk, dvg = dk[:, :n], dvg[:, :n]
    if sinks is not None:
        dq_s, dk_s, dv_s, se = _sink_patch(
            q, k[:, :n], v[:, :n], out, lse, dout,
            scale=scale, window=window, sinks=sinks, softcap=softcap,
            q_offset=q_offset, kv_valid=kv_valid,
        )
        dq = (dq.astype(jnp.float32) + dq_s).astype(q.dtype)
        dk = dk.at[:, :se].add(dk_s)
        dvg = dvg.at[:, :se].add(dv_s)
    return dq, dk.astype(k.dtype), dvg.astype(v.dtype)
