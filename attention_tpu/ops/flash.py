"""Fused Pallas flash-attention kernel for TPU.

This is the TPU-native rebuild of the reference's entire AVX-512 kernel
stack (`attention-mpi.c:103-189`):

  * ``dot_avx512`` (QK^T inner loop)      → tiled `jax.lax.dot_general` on
    the 128x128 MXU;
  * ``axpy_avx512`` (softmax-weighted V)  → the P·V tile matmul, also MXU;
  * ``memset_zero_scale``                 → vectorized scratch init /
    rescale on the VPU;
  * ``online_softmax_attention`` (running rmax/rsum, rescale by
    exp(old-new), `attention-mpi.c:168-189`) → the in-kernel online
    softmax carried in VMEM scratch across the KV grid dimension;
  * ``_mm_prefetch`` of the next K/V rows → Pallas' automatic grid
    double-buffering of the next K/V block's HBM→VMEM DMA;
  * ``cvt_d2f_avx512`` mixed precision    → bf16/fp32 inputs with fp32
    accumulation (``preferred_element_type``).

Two entry points share one kernel:

  * :func:`flash_attention` — normalized output, the single-chip fused op.
  * :func:`flash_attention_partials` — returns ``(out_unnorm, row_max,
    row_sumexp)`` per KV shard, the exact contract of the reference's
    local pass (each rank's (contrib, lmax, lsum), `attention-mpi.c:333-338`)
    that the distributed two-phase normalization
    (`attention_tpu.parallel`) merges across devices.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu import obs

_logger = logging.getLogger("attention_tpu.ops.flash")

# Op-dispatch telemetry (attention_tpu.obs, off by default).  Call
# counts tick per host-side dispatch; a call inside an enclosing jit
# trace ticks once per TRACE, not per execution — Python cannot see
# compiled re-executions.  `ops.flash.lowered` ticks at trace time in
# `_flash_call` and records the bound->online static dispatch choice.
_FLASH_CALLS = obs.counter("ops.flash.calls",
                           "flash_attention dispatches by shape bucket")
_FLASH_LOWERED = obs.counter(
    "ops.flash.lowered",
    "kernel lowerings by requested/resolved max mode")

NEG_INF = float("-inf")
_STAT_LANES = 128  # stats are carried lane-replicated: min f32 tile is (8, 128)
_LOG2E = 1.4426950408889634  # log2(e)
_LN2 = 0.6931471805599453  # 1/log2(e)

# Bound mode's runtime safety threshold, in log2 units.  The bound kernel
# computes p = exp2(s - b) with b >= the true row max; every probability
# is scaled by 2^-(overshoot).  fp32 normals reach 2^-126, so overshoot
# past ~126 silently underflows ALL of a row's probabilities -> l = 0 ->
# the div-guard returns zeros.  96 keeps the per-row max probability a
# normal float with 30 log2 units of margin, and entries within 2^-26 of
# it exactly representable (bf16 inputs carry ~2^-8 anyway).  Calls whose
# estimated overshoot exceeds this self-demote to the online kernel
# (`_bound_overshoot_estimate`) — the analog of the reference *buying*
# its fp32 headroom deliberately (attention-mpi.c:224-225) rather than
# assuming it.
SAFE_OVERSHOOT_LOG2 = 96.0

# Perf-triage ONLY (see the dispatch in `_flash_call`): monkeypatch to
# True to time the bound kernel without its guard/cond.  Deliberately a
# code-settable module global, not an env var — correctness bypasses
# must not ride process environments into CI, and jit caches freeze the
# value at first trace anyway.
_UNSAFE_SKIP_GUARD = False

# Static small-shape resolution of max_mode="bound" -> online (see the
# dispatch in `_flash_call`): below this many score elements
# (h * m_pad * n_pad, halved for causal) the overshoot guard's flat
# cond cost exceeds bound mode's VPU saving.  Measured round 5 between
# causal 4k (8.4M elems, online wins by 35%) and causal 8k (33.6M,
# bound wins by 21%) — 24M sits with margin on both sides.
_BOUND_MIN_SCORE_ELEMS = 24 * 2**20


def _compiler_params(semantics, vmem_limit_bytes=None):
    """`pltpu.CompilerParams` with dimension semantics (shared by the
    forward, backward, decode and ragged kernels).  ``vmem_limit_bytes``
    raises Mosaic's scoped-VMEM budget above its ~16 MB default — the
    big forward tiles, the fused backward's VMEM-resident (m_pad, d)
    fp32 dQ block and the ragged kernel's resident packed blocks
    legitimately exceed it."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


class BlockSizes(NamedTuple):
    """Tile sizes for the flash kernel grid.

    Defaults target v5e: 128-aligned so QK^T and P·V tiles map directly to
    the MXU, sized so q/k/v/acc blocks fit comfortably in ~16 MB VMEM with
    double buffering (the compiler pipelines the next K/V block while the
    current one computes — the `_mm_prefetch` analog).  256x1024 measured
    best on the real chip at seq=32k, d=128: 88.7% of peak matmul FLOPs
    vs 73.6% for 512x512 (scripts/kernel_sweep.py).
    """

    block_q: int = 256
    block_k: int = 1024

    @classmethod
    def for_shape(cls, heads: int, m: int, d: int,
                  window: int | None = None,
                  returns_stats: bool = False,
                  causal: bool = False,
                  dtype=None) -> "BlockSizes":
        """Per-shape defaults (callers may always override): the tuning
        tables first (user cache, then the shipped table — both keyed
        by device kind, so CPU/interpret runs with no cache entries
        resolve exactly as before), then the measured heuristic
        (:meth:`heuristic_for_shape`).  ``python -m attention_tpu.cli
        tune`` records fresh per-device optima into the user cache.
        """
        tuned = _tuned_flash_tiles(heads, m, d, window=window,
                                   returns_stats=returns_stats,
                                   causal=causal, dtype=dtype)
        if tuned is not None:
            return cls(*tuned)
        return cls(*cls.heuristic_for_shape(m, d, window=window,
                                            returns_stats=returns_stats,
                                            causal=causal))

    @classmethod
    def heuristic_for_shape(cls, m: int, d: int, *,
                            window: int | None = None,
                            returns_stats: bool = False,
                            causal: bool = False,
                            big_tiles: bool | None = None
                            ) -> tuple[int, int]:
        """The measured heuristic defaults (the tuner's final fallback;
        ``scripts/make_shipped_table.py`` seeds the shipped table from
        this with ``big_tiles=True`` — the measured-generation value —
        while ``None`` probes the local device).

        Round 4: raising the kernel's scoped-VMEM budget (it sat at
        Mosaic's ~16 MB default, which rejected every tile bigger than
        the then-measured optima — the sweep space was cut off exactly
        at the boundary the defaults sat on) unlocks a universal
        **4096x2048** for every unwindowed d<=128 shape with m >= 8192,
        stats outputs included.  Device clock: single-head 8k 185.0 us
        (0.943 vs 0.925 for the old 2048x1024), 32k 2.867 ms (0.973 vs
        0.951), 131k 45.39 ms (0.984 vs 0.959), GQA 32q/4kv@16k
        23.55 ms (0.948 vs 0.918 for the old 1024x2048), partials 32k
        2.967 ms (0.941 vs 0.888 for the old capped 1024x1024 — the
        cap existed only because of the old VMEM budget).
        Windowed long sequences keep the compact **512x512** tile — the
        band covers ceil((window-1+block_q)/block_k)+1 KV blocks, so
        smaller square tiles waste less of the band on masked columns:
        at seq=32k (device clock) w=1024 runs 227 us vs 329 for the
        general default, w=4096 575 vs 718, w=256 166 vs 153 for
        256x512 (within a whisker of the best).
        """
        if d <= 128 and m >= 8192:
            if window is not None:
                return (512, 512)
            if big_tiles is None:
                big_tiles = _big_tile_device()
            if not big_tiles:
                # without enough physical VMEM (v2/v3 cores ~16 MB
                # accept the raised budget but cannot honor it) the big
                # tiles cannot compile: keep the defaults that fit
                # ~16 MB
                return (1024, 1024) if returns_stats else (2048, 1024)
            # padding-aware: _flash_call pads m to a block_q multiple,
            # so a 4096-row tile on e.g. m=10240 would compute +20%
            # garbage rows; 2048 bounds the padding at 2047 rows
            bq = 4096 if m % 4096 == 0 else 2048
            if causal:
                # the diagonal wastes more of a taller tile: 2048x2048
                # measured 1.580 ms at causal 32k vs 1.643 for the
                # non-causal optimum (and 1.618 for the old 2048x1024)
                bq = min(bq, 2048)
            return (bq, 2048 if m % 2048 == 0 else 1024)
        return (cls._field_defaults["block_q"],
                cls._field_defaults["block_k"])


def _tuned_flash_tiles(heads, m, d, *, window, returns_stats, causal,
                       dtype):
    """Tuning-table tiles for the forward kernel, or None (heuristic).

    Floor-pow2 bucketing means an entry measured at one shape serves a
    range; the entry's tiles are re-bounded to THIS call's padding the
    same way the heuristic bounds its own (block_q that doesn't divide
    m caps at 2048 / block_k at 1024 — `_flash_call` pads m to a
    block_q multiple, and an unbounded tile on an unaligned m computes
    garbage rows).
    """
    try:
        from attention_tpu.tuning.lookup import key_fields, lookup

        entry = lookup(
            "flash_fwd", dtype=dtype,
            **key_fields("flash_fwd", heads=heads, seq=m, dim=d,
                         causal=causal, window=window,
                         stats=returns_stats),
        )
    except Exception:  # noqa: BLE001 - tuning must never break dispatch
        return None
    if entry is None:
        return None
    try:
        bq, bk = int(entry["block_q"]), int(entry["block_k"])
    except (KeyError, TypeError, ValueError):
        return None
    if bq % 128 or bk % 128 or bq <= 0 or bk <= 0:
        return None
    bq = min(bq, _ceil_to(m, 128))
    bk = min(bk, _ceil_to(m, 128))
    if m % bq:
        bq = min(bq, 2048)
    if m % bk:
        bk = min(bk, 1024)
    return bq, bk


def _tuned_max_mode(kernel: str, *, dtype=None, default: str = "online",
                    allowed=None, **kf_kwargs) -> str:
    """Tuning-table rescaling-math pick for ``max_mode="auto"`` calls,
    or ``default`` on a miss/invalid entry.

    Shared by the flash forward, decode, and ragged dispatchers: each
    passes its own family name plus `key_fields` kwargs (and its own
    ``allowed`` set — the decode-side kernels cannot lower "bound",
    which needs the forward kernel's key-norm prefetch).  The fallback
    is the online oracle — NOT bound — so an empty-cache CPU run of an
    "auto" call lowers exactly the kernel the plain default would.
    """
    try:
        from attention_tpu.tuning.lookup import key_fields, lookup

        entry = lookup(kernel, dtype=dtype,
                       **key_fields(kernel, **kf_kwargs))
    except Exception:  # noqa: BLE001 - tuning must never break dispatch
        return default
    if entry is None:
        return default
    mode = entry.get("max_mode")
    return mode if mode in (allowed or MAX_MODES) else default


@functools.cache
def _big_tile_device() -> bool:
    """Whether the default device's physical VMEM can hold the big-tile
    defaults (~110 MB scoped budget).  A v2/v3 core (~16 MB VMEM)
    accepts ``vmem_limit_bytes`` and then fails to compile, so gate on
    the generation.
    Non-TPU backends (pallas interpret mode) have no VMEM to exhaust."""
    try:
        dev = jax.devices()[0]
    except Exception:  # noqa: BLE001 - no backend at all
        return False
    if dev.platform != "tpu":
        return True
    kind = getattr(dev, "device_kind", "").lower()
    return any(gen in kind for gen in ("v4", "v5", "v6", "v7"))


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _flash_kernel(
    offsets_ref,
    knmax_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,
    n_true: int,
    block_k: int,
    causal: bool,
    block_q: int,
    normalize: bool,
    out_dtype,
    dynamic_valid: bool,
    segmented: bool,
    window: int | None,
    n_true_blocks: int,
    softcap2: float | None = None,
    sinks: int | None = None,
    sink_blocks: int = 0,
    variant: str = "online",
):
    """One (head, q-block, kv-block) grid step of online-softmax attention.

    ``offsets_ref`` holds (q_offset, kv_offset, kv_valid) as dynamic SMEM
    scalars: the global positions of this call's Q/KV rows (causal masking
    stays correct when the caller holds only a shard — ring attention
    rotates KV shards and computes the rotating offset from its device
    index) and the number of valid local KV rows (< n when the caller's
    shard includes padding from an indivisible global sequence).
    ``window`` (static) keeps only the last ``window`` positions per row
    (sliding-window attention; requires causal).
    ``variant`` picks the rescaling math (all variants compute the same
    softmax; they differ in which per-tile VPU work the recurrence
    carries — see `_softmax_variant_update`):

      * ``"online"`` — the classic running rmax/rsum recurrence.
      * ``"bound"`` (the VFA idea, PAPERS.md: global-max precompute) —
        replaces the online max recurrence with a per-row upper bound on
        the scores, computed in-kernel at the first KV step from the
        resident Q block and the prefetched per-KV-head max key norm
        (``knmax_ref``, Cauchy-Schwarz: |q·k| <= ||q||·max||k||):
        softmax is invariant to which max is subtracted, so using a
        bound instead of the true running max gives the same normalized
        output and lse while deleting the row-max reduce, the corr exp2,
        the accumulator rescale and the m-scratch traffic from the
        serial VPU chain.  ``l`` then accumulates per-lane and reduces
        once at finalize.  The m scratch holds the bound (written once,
        read per tile) instead of the running max.
      * ``"flashd"`` (FLASH-D, PAPERS.md) — keeps the accumulator
        NORMALIZED throughout: the division is folded into the tile
        update, the m scratch carries the running log-sum-exp, and the
        finalize has no ``l``-division epilogue.
      * ``"amla"`` (AMLA, PAPERS.md) — quantizes the running max to
        integers so every rescale factor is a power of two, applied as
        an integer add on the fp32 exponent field instead of a
        multiply.

    ``rest`` = ([q_seg, kv_seg,] o_ref, m_out, l_out, acc, m, l).
    """
    if segmented:
        q_seg_ref, kv_seg_ref, *rest = rest
    else:
        q_seg_ref = kv_seg_ref = None
    o_ref, m_out_ref, l_out_ref, acc_scr, m_scr, l_scr = rest
    # program_id is read at the kernel top level: interpret mode on CPU
    # substitutes grid indices only there, and the values are
    # loop-invariant anyway.
    h_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    jb = pl.program_id(2)
    if window is None:
        kv_idx = jb
    else:
        # Banded grid: the j dimension covers only the window band, and
        # the absolute KV block index is band-start + j.  A full-width
        # grid with per-step skip guards is NOT free — each skipped step
        # still pays un-overlapped DMA latency (~10 us measured), which
        # made a w=1024 window 5x SLOWER than full causal at seq=32k.
        # with sinks, the first sink_blocks grid steps visit blocks
        # [0, sink_blocks) and the band starts no earlier than that
        # (no block is ever visited twice)
        base = jnp.maximum(
            (q_idx * block_q + offsets_ref[0] - offsets_ref[1]
             - (window - 1)) // block_k,
            sink_blocks,
        )
        if sink_blocks:
            kv_idx = jnp.where(jb < sink_blocks, jb,
                               base + jb - sink_blocks)
        else:
            kv_idx = base + jb

    @pl.when(jb == 0)
    def _init():
        if variant == "bound":
            # Cauchy-Schwarz bound from the resident (pre-scaled) Q
            # block and this head's prefetched max key norm; softcap
            # tightens it (|cap·tanh(s/cap)| <= min(|s|, cap)).
            q0 = q_ref[0].astype(jnp.float32)
            qn = jnp.sqrt(jnp.sum(q0 * q0, axis=-1, keepdims=True))
            b = qn * knmax_ref[h_idx]
            if softcap2 is not None:
                b = jnp.minimum(b, softcap2)
            m_scr[...] = jnp.broadcast_to(b, m_scr.shape)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Skip tiles that masking zeroes entirely: under causal, KV blocks
    # strictly above the diagonal (first column already past the last
    # row); under dynamic kv_valid, blocks wholly past the valid prefix.
    # The running (m, l, acc) state is untouched for skipped tiles —
    # exactly what computing them would produce — so init/finalize stay
    # outside the guard.  This halves causal FLOPs (the score rectangle
    # becomes a triangle).
    compute_tile = True
    if causal:
        compute_tile = jnp.logical_and(
            compute_tile,
            kv_idx * block_k + offsets_ref[1]
            <= q_idx * block_q + block_q - 1 + offsets_ref[0],
        )
    if window is not None:
        # the band's top edge can run past the last real KV block (the
        # index map clips the DMA; skip the compute)
        compute_tile = jnp.logical_and(
            compute_tile, kv_idx < n_true_blocks
        )
    if dynamic_valid:
        compute_tile = jnp.logical_and(
            compute_tile, kv_idx * block_k < offsets_ref[2]
        )

    tile_kwargs = dict(
        valid=offsets_ref[2] if dynamic_valid else None,
        q_offset=offsets_ref[0],
        kv_offset=offsets_ref[1],
        kv_idx=kv_idx, q_idx=q_idx,
        n_true=n_true, block_k=block_k,
        block_q=block_q,
        q_seg_ref=q_seg_ref, kv_seg_ref=kv_seg_ref,
        softcap2=softcap2,
        variant=variant,
    )
    # Round-5 measured NEGATIVE result: splitting the body into an
    # interior fast path (mask chain statically compiled out for tiles
    # fully inside the causal triangle / window band) vs a diagonal
    # path — two @pl.when bodies on complementary predicates — ran
    # SLOWER on the real chip (causal 32k 1.72 ms vs 1.65 single-body
    # same-session; windowed w=1024 0.36 vs 0.21): Mosaic schedules
    # the dual-body step worse than it pays for the skipped VPU mask
    # chain.  Single masked body kept (the reference's aligned-vs-tail
    # split, attention-mpi.c:107-119, does not transplant here).
    @pl.when(compute_tile)
    def _compute():
        _flash_tile(q_ref, k_ref, v_ref, acc_scr, m_scr, l_scr,
                    causal=causal, window=window, sinks=sinks,
                    **tile_kwargs)

    @pl.when(jb == pl.num_programs(2) - 1)
    def _finalize():
        acc = acc_scr[...]
        if variant == "bound":
            # l accumulated per lane: one cross-lane reduce, here only
            l = jnp.sum(l_scr[...], axis=-1, keepdims=True)
        else:
            l = jnp.max(l_scr[...], axis=-1, keepdims=True)
        if normalize and variant != "flashd":
            # 1/gsum normalization with the divide-by-zero guard the
            # reference applies (attention-mpi.c:358-362).
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc / l_safe).astype(out_dtype)
        else:
            # flashd carries the accumulator normalized — the division
            # already happened inside the tile updates, so the epilogue
            # is a plain cast either way.
            o_ref[0] = acc.astype(out_dtype)
        if m_out_ref is not None:
            # Stats leave the kernel in the natural-log domain (the
            # distributed pmax/psum merge computes exp(lmax - gmax)).
            # In bound mode m_scr holds the bound — any value >= the
            # true row max yields the same merge and lse; in flashd it
            # holds the running log-sum-exp with l == 1 (the merge
            # identity sum_i out_i*exp(lse_i-gmax) / sum_i exp(lse_i-
            # gmax) is the standard two-phase combine); in amla the
            # integer-quantized max — still the actually-subtracted max.
            m_out_ref[0] = m_scr[...] * _LN2
            if variant == "bound":
                l_out_ref[0] = jnp.broadcast_to(l, l_out_ref[0].shape)
            else:
                l_out_ref[0] = l_scr[...]


def banded_keep(col, kv_min, sinks):
    """Decode-side band keep-mask: columns inside [kv_min, ...) or in the
    pinned first ``sinks`` rows.  One definition shared by `_flash_tile`
    and the int8 decode kernel so the band semantics cannot diverge."""
    keep = col >= kv_min
    if sinks is not None:
        keep = jnp.logical_or(keep, col < sinks)
    return keep


def _flash_tile(
    q_ref, k_ref, v_ref, acc_scr, m_scr, l_scr,
    *, valid, q_offset, kv_offset, kv_idx, q_idx, n_true, block_k, causal,
    block_q, q_seg_ref=None, kv_seg_ref=None, window=None, softcap2=None,
    sinks=None, kv_min=None, variant="online", pos_mod=None,
):
    """The per-tile online-softmax update (body of `_flash_kernel`; also
    the tile body of the decode kernel, `ops/decode.py`).  ``valid`` is a
    traced count of valid KV rows, or None when all ``n_true`` rows are
    valid (static masking only).  ``q_seg_ref``/``kv_seg_ref`` are
    segment-id blocks (lane-replicated (block_q, 128) / sublane-
    replicated (8, block_k) — see `segment_masks`); scores cross segment
    boundaries are masked.  ``pos_mod`` (static): the tile's rows pack
    several independent row streams (GQA group heads, or a speculative
    verify chunk replicated per head) — the row's SEQUENCE position is
    ``q_offset + row % pos_mod`` instead of ``q_offset + row``, so
    causal/window masks repeat every ``pos_mod`` rows."""
    dynamic_valid = valid is not None
    segmented = q_seg_ref is not None
    banded = kv_min is not None  # decode-side window: cols in
    # [kv_min, valid) plus the pinned first `sinks` positions

    # Q arrives pre-scaled by scale*log2(e) (`_flash_call`), so `s` is the
    # scores in the log2 domain: exp(s_nat - m_nat) == exp2(s - m).  This
    # removes the per-score scale multiply AND turns every exp into a raw
    # exp2 (TPU's native transcendental) — the kernel is VPU-bound, so
    # each elementwise op on the (block_q, block_k) tile is ~10% of step
    # time.  Stats are converted back to the natural domain at finalize.
    q = q_ref[0]  # (block_q, d)
    k = k_ref[0]  # (block_k, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (block_q, block_k), log2-domain
    if softcap2 is not None:
        # logit soft-capping (Gemma-2 style): cap * tanh(s / cap),
        # applied before masking; softcap2 is the cap in log2 units
        # (cap * log2(e)) since s is log2-domain
        s = softcap2 * jnp.tanh(s / softcap2)

    needs_tail_mask = n_true % block_k != 0
    masked = needs_tail_mask or causal or dynamic_valid or segmented or banded
    if masked:
        col = kv_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1
        )
        mask = col < (valid if dynamic_valid else n_true)
        if causal:
            row = jax.lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=0
            )
            if pos_mod is not None:
                row = jax.lax.rem(row, pos_mod)
            row = q_idx * block_q + row
            mask = jnp.logical_and(
                mask, col + kv_offset <= row + q_offset
            )
            if window is not None:
                # keep the last `window` positions per row, plus the
                # pinned first `sinks` positions (StreamingLLM)
                win = col + kv_offset >= row + q_offset - (window - 1)
                if sinks is not None:
                    win = jnp.logical_or(win, col + kv_offset < sinks)
                mask = jnp.logical_and(mask, win)
        if banded:
            mask = jnp.logical_and(mask, banded_keep(col, kv_min, sinks))
        if segmented:
            # (block_q, 1) vs (1, block_k): all lanes/sublanes of the
            # replicated id blocks are equal, so max() is just a reshape.
            q_ids = jnp.max(q_seg_ref[...], axis=-1, keepdims=True)
            kv_ids = jnp.max(kv_seg_ref[...], axis=0, keepdims=True)
            mask = jnp.logical_and(mask, q_ids == kv_ids)
        s = jnp.where(mask, s, NEG_INF)

    if variant == "bound":
        # Bound mode (VFA): the per-row score max is replaced by the
        # upper bound `_init` stored in m_scr, so there is no running
        # max, no corr, no accumulator rescale — the whole tile update
        # is one exp2, one per-lane partial sum and the P·V matmul.
        # Masked entries are -inf ⇒ exp2(-inf - b) = 0 (bound finite).
        b_col = jnp.max(m_scr[...], axis=-1, keepdims=True)
        p = jnp.exp2(s - b_col)
        # per-lane partial sums via lane-aligned slices (a reshape-based
        # (bq, bk/128, 128) reduce forces a Mosaic relayout — measured
        # 1.6x slower and +10MB scoped VMEM at 32k)
        lane_sum = p[:, :_STAT_LANES]
        for g in range(1, block_k // _STAT_LANES):
            lane_sum = lane_sum + p[:, g * _STAT_LANES:(g + 1) * _STAT_LANES]
        l_scr[...] += lane_sum
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] += pv
        return

    p, update_acc = _softmax_variant_update(s, m_scr, l_scr,
                                            variant=variant, masked=masked)

    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype),
        v_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = update_acc(acc_scr[...], pv)


def _online_softmax_update(s, m_scr, l_scr, *, masked):
    """The rmax/rsum recurrence of `online_softmax_attention`
    (attention-mpi.c:175-182), shared by the forward, decode, and
    quantized-decode kernels.  Updates the lane-replicated (rows, 128)
    m/l VMEM scratches in place from log2-domain scores ``s`` and
    returns ``(p, corr)`` — the probability tile and the accumulator
    rescale factor exp(old_max - new_max) (attention-mpi.c:179-181).
    Stats are reduced back to (rows, 1) columns instead of lane-slicing.
    """
    m_prev = jnp.max(m_scr[...], axis=-1, keepdims=True)  # (rows, 1)
    l_prev = jnp.max(l_scr[...], axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    if masked:
        # the where-guards keep fully masked blocks/rows from producing
        # NaN via exp2(-inf - -inf)
        corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp2(m_prev - m_next))
        p = jnp.where(m_next == NEG_INF, 0.0, jnp.exp2(s - m_next))
    else:
        # Unmasked: m_next is finite (a real row max), so exp2(-inf - m)
        # underflows to 0 on its own — skip the two per-element selects.
        corr = jnp.exp2(m_prev - m_next)
        p = jnp.exp2(s - m_next)
    l_next = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)
    return p, corr


#: valid per-tile rescaling-math variants (see `_softmax_variant_update`);
#: ``"auto"`` additionally resolves through the tuning tables at dispatch.
MAX_MODES = ("online", "bound", "flashd", "amla")


def _softmax_variant_update(s, m_scr, l_scr, *, variant, masked):
    """Per-tile softmax-recurrence dispatch shared by the flash forward,
    decode, and ragged kernel bodies (which differ only in how they index
    Q/K/V around this update).

    Returns ``(p, update_acc)``: the probability tile to feed the P·V
    matmul and a closure ``update_acc(acc, pv) -> new_acc`` folding the
    variant's rescale math into the accumulator update.  ``"bound"`` is
    NOT dispatched here — it needs the prefetched key-norm bound and has
    its own tile body in `_flash_tile`.
    """
    if variant == "flashd":
        return _flashd_update(s, m_scr, l_scr, masked=masked)
    if variant == "amla":
        return _amla_update(s, m_scr, l_scr, masked=masked)
    p, corr = _online_softmax_update(s, m_scr, l_scr, masked=masked)
    return p, lambda acc, pv: acc * corr + pv


def _flashd_update(s, m_scr, l_scr, *, masked):
    """FLASH-D (PAPERS.md, arXiv:2505.14201): hidden softmax division.

    The accumulator is kept NORMALIZED at every step — the tile update
    divides the probability tile and the carried accumulator by the
    running denominator as it goes, so there is no per-block rescale
    multiply against the old un-normalized accumulator and no final
    ``l``-division epilogue.  The m scratch carries the running
    log-sum-exp ``mu = log2(sum_j exp2(s_j))`` instead of the running
    max (itself the nonlinear part of the paper's recurrence); the l
    scratch is pinned to 1 so the stats contract ``out_unnorm = out *
    l * exp(m)/exp(m)`` holds with ``l == 1`` and ``m == lse`` — the
    distributed two-phase merge is unchanged.
    """
    mu_prev = jnp.max(m_scr[...], axis=-1, keepdims=True)  # running lse
    b = jnp.maximum(mu_prev, jnp.max(s, axis=-1, keepdims=True))
    if masked:
        # guards: a fully-masked tile on an empty history has b = -inf
        p = jnp.where(b == NEG_INF, 0.0, jnp.exp2(s - b))
        a = jnp.where(mu_prev == NEG_INF, 0.0, jnp.exp2(mu_prev - b))
    else:
        # unmasked: b is a real (finite) row max, exp2(-inf - b)
        # underflows to the right 0 on its own
        p = jnp.exp2(s - b)
        a = jnp.exp2(mu_prev - b)
    # t = exp2(-b) * (sum of ALL exponentials so far): the new
    # denominator, pre-divided out of both p and the carried acc
    t = a + jnp.sum(p, axis=-1, keepdims=True)
    rt = jnp.where(t == 0.0, 0.0, 1.0 / t)
    # mu_new = log2(sum_j exp2(s_j)); t == 0 only when b == -inf, and
    # -inf + log2(0) = -inf keeps the empty-row sentinel exact
    mu_new = b + jnp.log2(t)
    m_scr[...] = jnp.broadcast_to(mu_new, m_scr.shape)
    l_scr[...] = jnp.ones_like(l_scr)
    corr = a * rt
    return p * rt, lambda acc, pv: acc * corr + pv


def _amla_update(s, m_scr, l_scr, *, masked):
    """AMLA (PAPERS.md, arXiv:2509.25224): rescale multiplies become
    exponent-field integer adds.

    The running max is quantized UP to an integer (scores are already
    log2-domain from the Q prescale, so integer units = powers of two):
    every rescale factor ``exp2(m_prev - m_next)`` then has an exact
    fp32 representation with an all-zero mantissa delta, and multiplying
    the accumulator / denominator by it reduces to adding the (negative)
    integer ``m_prev - m_next`` to their exponent fields
    (`_exponent_add`) — no VPU multiply, bit-exact.  Ceiling (not floor)
    keeps ``s - m_next <= 0`` so ``p <= 1`` retains bound-mode's
    overflow-free property with at most one extra log2 unit of
    underflow headroom spent.
    """
    m_prev = jnp.max(m_scr[...], axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, jnp.ceil(jnp.max(s, axis=-1,
                                                  keepdims=True)))
    if masked:
        p = jnp.where(m_next == NEG_INF, 0.0, jnp.exp2(s - m_next))
    else:
        p = jnp.exp2(s - m_next)
    # diff <= 0 and integer-valued (both maxes are ceil-quantized);
    # fully-masked history (m_prev == -inf) rescales nothing: diff = 0
    diff = jnp.where(m_prev == NEG_INF, 0.0,
                     m_prev - m_next).astype(jnp.int32)
    l_prev = jnp.max(l_scr[...], axis=-1, keepdims=True)
    l_next = _exponent_add(l_prev, diff) + jnp.sum(p, axis=-1,
                                                   keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)
    return p, lambda acc, pv: _exponent_add(acc, diff) + pv


def _exponent_add(x, e):
    """``x * 2**e`` as an integer add on the fp32 exponent field.

    ``e`` is a non-positive int32 (broadcastable against ``x``).  Exact
    for every normal fp32 input; zeros pass through and results whose
    biased exponent would leave the normal range flush to zero (the
    rescale factor is < 2^-126 there — the product is below any budget
    in the ledger).  The sign bit is untouched: with the result exponent
    in [1, 254] the add never borrows past bit 30.
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    exp = jax.lax.shift_right_logical(bits, 23) & 0xFF
    shifted = jax.lax.bitcast_convert_type(bits + (e << 23), jnp.float32)
    return jnp.where((x == 0.0) | (exp + e <= 0), 0.0, shifted)


def _bound_overshoot_estimate(q, k, knmax, offsets, *, m, n, group,
                              causal, window, sinks, softcap2,
                              q_segment_ids, kv_segment_ids,
                              static_diag=False):
    """Upper bound on bound-mode's per-row overshoot (log2 units).

    Bound mode subtracts the Cauchy-Schwarz row bound ``b`` instead of
    the true row max ``max_s``; correctness only needs the overshoot
    ``b - max_s`` to stay inside fp32 exp2 range (SAFE_OVERSHOOT_LOG2).
    ``max_s`` is unknown without running QK^T, but any single column
    certified attended for the row gives ``s_ref <= max_s``, hence
    ``b - s_ref >= b - max_s`` — a cheap O(m*d) overestimate computed
    from one gathered K row per query row.  Reference columns:

      * non-causal: column 0 (attended whenever any column is valid);
      * causal: the diagonal clipped into the valid prefix (column 0 is
        also always attended once the diagonal is local, but the
        diagonal score is far tighter for real models);
      * windowed: the clipped diagonal when it lies in the band, else
        sink column 0 when sinks exist;
      * rows that attend NO columns are excluded — for them bound-mode
        underflow produces exactly the correct zeros.

    Segmented calls certify the reference column only when it shares
    the row's segment; otherwise the row reports +inf (conservative
    demotion).  ``q`` arrives pre-scaled into the log2 domain, so the
    returned value is directly comparable to SAFE_OVERSHOOT_LOG2.

    ``static_diag``: the caller statically knows row i's reference IS
    kv row i (plain causal self-attention: no offsets, no kv_valid,
    m == n) — the diagonal reference becomes a fused elementwise
    q*k pass with NO gather and no exclusions (the diagonal is always
    attended and always inside any window).  This keeps the guard at
    ~1% of a causal 32k forward; the general gather path is reserved
    for sharded/offset callers.
    """
    h = q.shape[0]
    hkv = k.shape[0]
    q32 = q[:, :m].astype(jnp.float32)  # (h, m, d), pre-scaled
    qn = jnp.sqrt(jnp.sum(q32 * q32, axis=-1))  # (h, m)
    b = qn * knmax[:, None]
    if softcap2 is not None:
        b = jnp.minimum(b, softcap2)
    rows = jnp.arange(m, dtype=jnp.int32)
    valid = offsets[2]
    c_ref = None
    if causal and static_diag:
        kr = k[:, :n]  # row-aligned diagonal reference, pure elementwise
        excluded = jnp.zeros((m,), bool)
    elif causal:
        diag = rows + offsets[0] - offsets[1]  # this row's own kv column
        excluded = diag < 0  # whole local shard is in the row's future
        c_ref = jnp.clip(jnp.minimum(diag, valid - 1), 0, n - 1)
        if window is not None:
            in_win = c_ref >= diag - (window - 1)
            if sinks is not None:
                # out-of-band rows still attend sink column 0
                c_ref = jnp.where(in_win, c_ref, 0)
            else:
                # clipped diagonal below the band start <=> the band
                # misses the valid prefix entirely: nothing attended
                excluded = jnp.logical_or(excluded,
                                          jnp.logical_not(in_win))
        # gather in the STORAGE dtype; the cast fuses into the reduce
        # (an fp32 gather materializes 2x the bytes for nothing)
        kr = jnp.take(k[:, :n], c_ref, axis=1)  # (hkv, m, d)
    else:
        # column 0 for every row: (hkv, 1, d) broadcast, no gather
        kr = k[:, :1, :]
        excluded = jnp.zeros((m,), bool)
    excluded = jnp.logical_or(excluded, valid <= 0)
    s_ref = jnp.sum(
        q32.reshape(hkv, group, m, q32.shape[-1])
        * kr.astype(jnp.float32)[:, None], axis=-1
    ).reshape(h, m)
    if softcap2 is not None:
        # monotone, so cap(s_ref) <= cap(max_s): still a lower bound
        s_ref = softcap2 * jnp.tanh(s_ref / softcap2)
    over = b - s_ref
    if q_segment_ids is not None:
        kv_ids = jnp.asarray(kv_segment_ids, jnp.int32)
        if causal and static_diag:
            ref_ids = kv_ids  # row-aligned diagonal reference
        elif c_ref is None:
            ref_ids = kv_ids[0]
        else:
            ref_ids = jnp.take(kv_ids, c_ref)
        match = ref_ids == jnp.asarray(q_segment_ids, jnp.int32)
        over = jnp.where(match[None, :], over, jnp.inf)
    return jnp.max(jnp.where(excluded[None, :], 0.0, over))


def _flash_call(
    q: jax.Array,  # (H, m, d)
    k: jax.Array,  # (Hkv, n, d)
    v: jax.Array,  # (Hkv, n, dv)
    *,
    scale: float,
    causal: bool,
    normalize: bool,
    block_sizes: BlockSizes,
    return_stats: bool,
    interpret: bool,
    out_dtype,
    q_offset=None,
    kv_offset=None,
    kv_valid=None,
    q_segment_ids=None,
    kv_segment_ids=None,
    window=None,
    softcap=None,
    sinks=None,
    max_mode="online",
):
    h, m, d = q.shape
    hkv, n, dv = v.shape
    if max_mode not in MAX_MODES + ("auto",):
        raise ValueError(f"unknown max_mode {max_mode!r}")
    if h % hkv != 0:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    group = h // hkv
    segmented = q_segment_ids is not None
    if segmented != (kv_segment_ids is not None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")
    if window is not None:
        if not causal:
            raise ValueError(
                "window (sliding-window attention) requires causal=True"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if sinks is not None:
        if window is None:
            raise ValueError(
                "sinks (attention sinks) require window= (without a "
                "window every past position is attended anyway)"
            )
        if sinks < 1:
            raise ValueError(f"sinks must be >= 1, got {sinks}")
        if q_segment_ids is not None:
            # the sink mask pins ABSOLUTE buffer positions; in a packed
            # buffer only the first segment would get its sinks — reject
            # rather than silently diverge
            raise ValueError(
                "sinks do not compose with segment_ids (sink positions "
                "are absolute, not per-segment); unpack the batch"
            )
    check_softcap(softcap)

    # Fold softmax scale * log2(e) into Q once (an (m, d) multiply in
    # fp32) so the kernel never scales the (m, n) score matrix and all
    # exponentials are raw exp2 — see the log2-domain note in
    # `_flash_kernel`.  Casting back to q.dtype re-rounds bf16 inputs
    # (~2^-8 relative), which the old score-domain scaling avoided;
    # keeping the kernel input bf16 is what keeps QK^T on the fast MXU
    # path, and measured end-to-end error at seq=32k stays ~2e-4 — two
    # orders under the ±0.02 contract.
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)

    block_q = min(block_sizes.block_q, _ceil_to(m, 128))
    block_k = min(block_sizes.block_k, _ceil_to(n, 128))
    m_pad = _ceil_to(m, block_q)
    n_pad = _ceil_to(n, block_k)
    if m_pad != m:
        q = jnp.pad(q, ((0, 0), (0, m_pad - m), (0, 0)))
    if n_pad != n:
        k = jnp.pad(k, ((0, 0), (0, n_pad - n), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, n_pad - n), (0, 0)))

    num_kv_blocks = n_pad // block_k
    sink_blocks = 0 if sinks is None else min(
        -(-sinks // block_k), num_kv_blocks
    )
    if window is None:
        band_blocks = num_kv_blocks
    else:
        # blocks covering [row - (window-1), row] for a block_q row span,
        # +1 for block misalignment; sink blocks prepend the band
        band_blocks = min(
            num_kv_blocks, -(-(window - 1 + block_q) // block_k) + 1
        )
    grid = (h, m_pad // block_q, sink_blocks + band_blocks)

    variant = max_mode
    if variant == "auto":
        # measured dispatch: the tuning tables (user cache, then the
        # shipped table) pick the rescaling math per (shape, dtype,
        # flags); a miss resolves to the online oracle — on CPU (no
        # tpu-* entries apply) "auto" is byte-identical to the default.
        variant = _tuned_max_mode(
            "flash_fwd", dtype=q.dtype, heads=h, seq=m, dim=d,
            causal=causal, window=window, stats=return_stats)
    bound_mode = variant == "bound"
    if bound_mode and window is not None:
        # Measured (round 5, device clock): on banded grids the bound
        # kernel's VPU saving is within noise of the online kernel
        # (w=1024@32k: 0.227 ms online vs 0.21 bound) while the
        # runtime overshoot guard is a FLAT cost that dwarfs the tiny
        # band kernel (+70% at w=1024).  Same outputs either way —
        # windowed calls statically resolve to the online recurrence.
        bound_mode = False
    if bound_mode and block_k % _STAT_LANES != 0:
        # the bound kernel accumulates l in _STAT_LANES-wide lane
        # slices (`_flash_tile`): a narrower tile cannot feed the
        # (block_q, _STAT_LANES) scratch (shape error), and a wider
        # NON-MULTIPLE tile silently drops columns past the last full
        # slice from l while still accumulating them into P·V —
        # measured 0.31 max abs error at block_k=192.  Both resolve to
        # the online recurrence (latent since round 3, exposed when
        # the sharded paths gained max_mode threading).
        bound_mode = False
    if bound_mode and (h * m_pad * n_pad * (0.5 if causal else 1.0)
                       < _BOUND_MIN_SCORE_ELEMS):
        # Measured crossover (round 5, device clock, d=128 single
        # head; scripts/guard_cost_exp.py, artifacts/guard_cost_exp
        # .json): the guard's flat ~9-30 us cond cost exceeds bound
        # mode's VPU saving on small grids — guarded bound loses to
        # online by 51% at 2k, 27% at 4k, 35% at causal 4k, and wins
        # from 8k (+6%) / causal 8k (+21%) up.  Same outputs either
        # way (bound is exact and demotes to online when unsafe), so
        # small calls statically resolve to the online recurrence;
        # the threshold sits between causal 4k (8.4M elems, online
        # side) and causal 8k (33.6M, bound side) with margin both
        # ways.  Grid work scales with h*m*n (halved causal), so the
        # dispatch uses score elements, mirroring the measurement.
        bound_mode = False
    if variant == "bound" and not bound_mode:
        variant = "online"
    if obs.is_enabled():
        # trace-time: one tick per lowering, recording the static
        # resolution (auto -> table pick, bound -> online demotions)
        _FLASH_LOWERED.inc(requested=max_mode, lowered=variant)
    softcap2 = None if softcap is None else softcap * _LOG2E
    kernel_kwargs = dict(
        n_true=n,
        block_k=block_k,
        causal=causal,
        block_q=block_q,
        normalize=normalize,
        out_dtype=out_dtype,
        dynamic_valid=kv_valid is not None,
        segmented=segmented,
        window=window,
        n_true_blocks=num_kv_blocks,
        softcap2=softcap2,
        sinks=sinks,
        sink_blocks=sink_blocks,
    )

    offsets = jnp.stack(
        [
            jnp.asarray(0 if q_offset is None else q_offset, dtype=jnp.int32),
            jnp.asarray(0 if kv_offset is None else kv_offset, dtype=jnp.int32),
            jnp.asarray(n if kv_valid is None else kv_valid, dtype=jnp.int32),
        ]
    )
    dynamic_valid = kv_valid is not None

    def kv_map(hh, i, j, off, knm):
        # Clamp block indices for tiles the kernel's @pl.when guard will
        # skip (above the causal diagonal / past the dynamic valid
        # prefix) to the last block it will compute: Pallas elides the
        # HBM->VMEM DMA when consecutive grid steps map to the same
        # block, so skipped tiles cost no bandwidth either.  The
        # clamped index always equals j for computed tiles (the clamp
        # bounds mirror the compute_tile conditions in `_flash_kernel`).
        if window is None:
            jj = j
        else:
            # banded grid: absolute block = band start + j, clipped to
            # the last real block (compute is guarded in-kernel);
            # mirrors the sink/band split in `_flash_kernel`
            base = jnp.maximum(
                (i * block_q + off[0] - off[1] - (window - 1)) // block_k,
                sink_blocks,
            )
            if sink_blocks:
                jj = jnp.where(
                    j < sink_blocks, j,
                    jnp.minimum(base + j - sink_blocks, num_kv_blocks - 1),
                )
            else:
                jj = jnp.minimum(base + j, num_kv_blocks - 1)
        if causal:
            causal_last = (
                i * block_q + block_q - 1 + off[0] - off[1]
            ) // block_k
            jj = jnp.minimum(jj, jnp.maximum(causal_last, 0))
        if dynamic_valid:
            valid_last = jnp.maximum((off[2] + block_k - 1) // block_k - 1, 0)
            jj = jnp.minimum(jj, valid_last)
        return (hh // group, jj, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda hh, i, j, off, knm: (hh, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, dv), kv_map),
    ]
    if bound_mode:
        # Per-KV-head max key norm for the in-kernel Cauchy-Schwarz
        # bound on the log2-domain scores: |q·k| <= ||q||·max_j ||k_j||
        # (exact kernel operands: the pre-scaled, re-rounded Q — its
        # norm is computed in-kernel from the resident block — and the
        # padded K).  Softmax output and lse are invariant to the
        # choice of max as long as it is >= the true row max, so
        # overshoot costs only fp32 headroom — and that headroom is
        # ENFORCED at runtime: `_bound_overshoot_estimate` bounds the
        # worst-row overshoot from the same operands, and calls that
        # might leave the fp32 exp2 range (adversarial norms, LLM
        # outlier K channels) self-demote to the online kernel below.
        k32 = k.astype(jnp.float32)
        knmax = jnp.repeat(
            jnp.max(jnp.sqrt(jnp.sum(k32 * k32, axis=-1)), axis=-1),
            group,
        )  # (h,) f32, indexed by the head grid dim in `_init`
        bound_safe = (
            _bound_overshoot_estimate(
                q, k, knmax, offsets, m=m, n=n, group=group,
                causal=causal, window=window, sinks=sinks,
                softcap2=softcap2, q_segment_ids=q_segment_ids,
                kv_segment_ids=kv_segment_ids,
                # gather-free diagonal reference for plain causal
                # self-attention (the training/bench shape)
                static_diag=(causal and q_offset is None
                             and kv_offset is None and kv_valid is None
                             and m == n),
            )
            <= SAFE_OVERSHOOT_LOG2
        )
    else:
        knmax = jnp.zeros((1,), jnp.float32)  # unused placeholder
    seg_inputs = ()
    if segmented:
        q_rep, kv_rep = segment_masks(q_segment_ids, kv_segment_ids,
                                      m, n, m_pad, n_pad)
        seg_inputs = (q_rep, kv_rep)
        in_specs += [
            pl.BlockSpec((block_q, _STAT_LANES),
                         lambda hh, i, j, off, knm: (i, 0)),
            pl.BlockSpec(
                (8, block_k),
                lambda hh, i, j, off, knm: (0, kv_map(hh, i, j, off, knm)[1]),
            ),
        ]
    out_shapes = [jax.ShapeDtypeStruct((h, m_pad, dv), out_dtype)]
    out_specs = [
        pl.BlockSpec((1, block_q, dv), lambda hh, i, j, off, knm: (hh, i, 0))
    ]
    if return_stats:
        stat_shape = jax.ShapeDtypeStruct((h, m_pad, _STAT_LANES), jnp.float32)
        stat_spec = pl.BlockSpec(
            (1, block_q, _STAT_LANES), lambda hh, i, j, off, knm: (hh, i, 0)
        )
        out_shapes += [stat_shape, stat_shape]
        out_specs += [stat_spec, stat_spec]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
        ],
    )

    # Raised scoped-VMEM budget for big tiles only (like the backward
    # kernels): the default ~16 MB budget rejects every tile bigger
    # than the round-3 defaults, cutting the sweep space off exactly at
    # the boundary those defaults sat on — the round-4 universal
    # 4096x2048 needs the raise.  Small tiles keep the default budget:
    # the raise measurably perturbed the windowed 512x512 kernel's
    # schedule (0.208 -> 0.251 ms at w=1024).
    big_tile = block_q * block_k > 2 * 2**20
    compiler_params = _compiler_params(
        ("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=110 * 2**20 if big_tile else None)

    # windowed grids only visit the band's KV columns
    n_eff = band_blocks * block_k
    flops = 2 * h * m_pad * n_eff * (d + dv)

    def _run(variant_: str):
        kern = functools.partial(_flash_kernel, variant=variant_,
                                 **kernel_kwargs)
        if not return_stats:
            kern = functools.partial(_no_stat_kernel, kern)
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=out_shapes,
            compiler_params=compiler_params,
            cost_estimate=pl.CostEstimate(
                flops=flops,
                bytes_accessed=int(
                    (q.size + (k.size + v.size) * n_eff // n_pad)
                    * q.dtype.itemsize
                )
                + h * m_pad * dv * 4,
                transcendentals=h * m_pad * n_eff,
            ),
            interpret=interpret,
        )(offsets, knmax, q, k, v, *seg_inputs)

    if bound_mode:
        # Self-demotion (runtime, data-dependent): the bound kernel is
        # provably exact only while the overshoot stays inside fp32
        # exp2 range; past SAFE_OVERSHOOT_LOG2 the online kernel runs
        # instead.  Both branches compile once; the predicate is a
        # scalar and the guard's own cost is O(m*d) — ~1% of a 32k
        # forward, 0 of the grid's FLOPs.
        if _UNSAFE_SKIP_GUARD:
            # Perf-triage hatch (module global, code-settable only — a
            # process env var would silently disable the guard
            # fleet-wide and be frozen into jit caches): runs the bound
            # kernel with no guard/cond — WRONG (all-zero rows) on
            # inputs whose overshoot leaves fp32 exp2 range.
            _logger.warning(
                "_UNSAFE_SKIP_GUARD is set — bound-mode overshoot "
                "guard DISABLED (triage only)")
            outs = _run("bound")
        else:
            # The cond's STRUCTURE costs ~30-50 us per call on this
            # toolchain regardless of branch content — measured round 5
            # (scripts/guard_cost_exp.py, scripts/passthrough_cond_exp
            # .py, artifacts/guard_cost_exp.json): a trivial-predicate
            # cond pays the same, a pass-through-branch cond pays MORE
            # (37-52 us), and moving the branch in-kernel (one kernel,
            # two grid-invariant @pl.when tile bodies reading the
            # verdict from a scalar-prefetch slot) ran 359 us vs 214 at
            # 8k — Mosaic schedules the union CFG without cross-step
            # overlap, the causal-split lesson again.  Since guarded
            # bound (214 us @8k) still beats online (228 us), this cond
            # IS the measured optimum among every structure tried; the
            # flat cost is the price of the no-silent-zeros guarantee.
            outs = jax.lax.cond(bound_safe,
                                lambda: _run("bound"),
                                lambda: _run("online"))
    else:
        outs = _run(variant)

    out = outs[0][:, :m]
    if return_stats:
        row_max = outs[1][:, :m, 0]
        row_sum = outs[2][:, :m, 0]
        return out, row_max, row_sum
    return out


def _no_stat_kernel(kernel, *args):
    # args = (off, knm, q, k, v, [q_seg, kv_seg], o, acc, m, l): splice
    # None stat-output refs in front of the scratch refs.
    *pre, o_ref, acc, m_scr, l_scr = args
    kernel(*pre, o_ref, None, None, acc, m_scr, l_scr)


def segment_masks(q_seg, kv_seg, m: int, n: int, m_pad: int, n_pad: int):
    """Mosaic-legal segment-id layouts for the flash kernels.

    A narrow (1, block) id vector violates the (8, 128) min-tile rule,
    so ids ship replicated: Q ids lane-replicated (m_pad, _STAT_LANES),
    KV ids sublane-replicated (8, n_pad).  Ids must match the TRUE
    sequence lengths (m, n); only kernel padding gets id -1 (matches
    nothing; real ids are assumed non-negative).
    """
    q_seg = jnp.asarray(q_seg, jnp.int32)
    kv_seg = jnp.asarray(kv_seg, jnp.int32)
    if q_seg.shape != (m,) or kv_seg.shape != (n,):
        raise ValueError(
            f"segment id shapes {q_seg.shape}/{kv_seg.shape} != "
            f"({m},)/({n},)"
        )
    if m_pad != m:
        q_seg = jnp.pad(q_seg, (0, m_pad - m), constant_values=-1)
    if n_pad != n:
        kv_seg = jnp.pad(kv_seg, (0, n_pad - n), constant_values=-1)
    q_rep = jnp.broadcast_to(q_seg[:, None], (m_pad, _STAT_LANES))
    kv_rep = jnp.broadcast_to(kv_seg[None, :], (8, n_pad))
    return q_rep, kv_rep


def check_softcap(softcap) -> None:
    """Shared entry-point validation for the softcap knob."""
    if softcap is not None and softcap <= 0.0:
        raise ValueError(f"softcap must be > 0, got {softcap}")


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _canon(q, k, v):
    """Canonicalize (m, d) / (h, m, d) inputs to (h, m, d); return unbatcher."""
    if q.ndim != k.ndim or q.ndim != v.ndim:
        raise ValueError(f"rank mismatch: Q{q.shape} K{k.shape} V{v.shape}")
    if q.shape[-1] != k.shape[-1] or k.shape[-2] != v.shape[-2]:
        raise ValueError(f"shape mismatch: Q{q.shape} K{k.shape} V{v.shape}")
    if k.shape[:-2] != v.shape[:-2]:
        raise ValueError(f"K/V head dims differ: K{k.shape} V{v.shape}")
    if q.ndim == 4 and q.shape[0] != k.shape[0]:
        raise ValueError(f"batch mismatch: Q{q.shape} K{k.shape}")
    if q.ndim >= 3 and q.shape[-3] % k.shape[-3] != 0:
        raise ValueError(
            f"q heads {q.shape[-3]} not a multiple of kv heads {k.shape[-3]}"
        )
    if q.ndim == 2:
        return q[None], k[None], v[None], lambda o: o[0]
    if q.ndim == 3:
        return q, k, v, lambda o: o
    if q.ndim == 4:  # (B, H, m, d): fold batch into heads
        b, h, m_len, d = q.shape
        bk, hkv, n_len, dkk = k.shape
        qf = q.reshape(b * h, m_len, d)
        kf = k.reshape(bk * hkv, n_len, dkk)
        vf = v.reshape(bk * hkv, n_len, v.shape[-1])
        # Folding batch outside heads keeps q-head→kv-head grouping contiguous
        # only within a batch element; regroup so index h//group is right:
        # q heads of batch b occupy [b*h, (b+1)*h) and kv heads [b*hkv, ...).
        return qf, kf, vf, lambda o: o.reshape(b, h, m_len, -1)
    raise ValueError(f"unsupported rank {q.ndim} for flash attention")


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale",
        "causal",
        "block_sizes",
        "interpret",
        "window",
        "softcap",
        "sinks",
        "max_mode",
    ),
)
def _flash_attention_jit(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float | None = None,
    causal: bool = False,
    block_sizes: BlockSizes | None = None,
    interpret: bool | None = None,
    q_offset=None,
    kv_offset=None,
    kv_valid=None,
    q_segment_ids=None,
    kv_segment_ids=None,
    window: int | None = None,
    softcap: float | None = None,
    sinks: int | None = None,
    max_mode: str = "online",
) -> jax.Array:
    """Fused single-device attention: softmax(q k^T * scale) v.

    Accepts (m, d), (h, m, d) or (b, h, m, d) inputs; for 3D/4D inputs the
    number of KV heads may divide the number of Q heads (GQA — BASELINE
    config 5: 32 Q heads sharing 4 KV heads).  ``q_offset``/``kv_offset``
    (dynamic scalars) give the global sequence positions of the local Q/KV
    rows for causal masking over shards.  ``q_segment_ids``/
    ``kv_segment_ids`` ((m,)/(n,) non-negative int32, shared across
    heads) mask attention across packed-sequence boundaries.  ``window``
    (static int, requires causal) keeps the last ``window`` positions per
    query — sliding-window attention; skipped tiles cost no FLOPs.
    ``softcap`` (static float) applies Gemma-2-style logit capping
    ``cap * tanh(scores / cap)`` before masking and softmax.  ``sinks``
    (static int, requires window) keeps the first ``sinks`` positions
    attendable alongside the window (StreamingLLM attention sinks).
    ``max_mode="bound"`` (VFA, PAPERS.md) replaces the in-kernel online
    max with a precomputed Cauchy-Schwarz row bound — same output and
    stats (softmax is max-choice invariant), shorter per-tile VPU chain.
    Bound mode is runtime-guarded: when the estimated worst-row
    overshoot could leave fp32 exp2 range (adversarial norms, outlier K
    channels), the call self-demotes to the online kernel
    (`_bound_overshoot_estimate`), so the result is exact either way.
    ``max_mode="flashd"`` (FLASH-D) folds the softmax division into the
    accumulator update (no rescale multiply, no division epilogue);
    ``max_mode="amla"`` (AMLA) quantizes the running max to powers of
    two so rescales become exponent-field integer adds — both same
    semantics, fuzzed against the fp64 oracle (`chaos`).
    ``max_mode="auto"`` asks the tuning tables (measured per shape,
    dtype, flags) and falls back to ``"online"`` on a miss.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    if q_segment_ids is not None and q.ndim == 4:
        raise ValueError(
            "segment ids support 2D/3D inputs (ids shared across heads); "
            "vmap over the batch for per-sequence ids"
        )
    qh, kh, vh, unbatch = _canon(q, k, v)
    out = _flash_call(
        qh,
        kh,
        vh,
        scale=scale,
        causal=causal,
        normalize=True,
        block_sizes=block_sizes or BlockSizes.for_shape(
            qh.shape[0], qh.shape[1], qh.shape[2], window,
            causal=causal, dtype=qh.dtype),
        return_stats=False,
        interpret=interpret,
        out_dtype=v.dtype,
        q_offset=q_offset,
        kv_offset=kv_offset,
        kv_valid=kv_valid,
        q_segment_ids=q_segment_ids,
        kv_segment_ids=kv_segment_ids,
        window=window,
        softcap=softcap,
        sinks=sinks,
        max_mode=max_mode,
    )
    return unbatch(out)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    **kwargs) -> jax.Array:
    """Fused single-device attention: softmax(q k^T * scale) v.

    Thin dispatch shim over the jitted kernel (same signature — see
    :func:`_flash_attention_jit` for the full parameter docs) that
    ticks the op-dispatch telemetry when `attention_tpu.obs` is
    enabled; disabled (the default) it is one flag check."""
    if obs.is_enabled():
        _FLASH_CALLS.inc(
            bucket=obs.shape_bucket(q.shape[-2], q.shape[-1]),
            mode=str(kwargs.get("max_mode", "online")),
            entry="attention")
    return _flash_attention_jit(q, k, v, **kwargs)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "block_sizes", "interpret",
                     "window", "softcap", "sinks", "max_mode"),
)
def _flash_attention_partials_jit(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float | None = None,
    causal: bool = False,
    block_sizes: BlockSizes | None = None,
    interpret: bool | None = None,
    q_offset=None,
    kv_offset=None,
    kv_valid=None,
    q_segment_ids=None,
    kv_segment_ids=None,
    window: int | None = None,
    softcap: float | None = None,
    sinks: int | None = None,
    max_mode: str = "online",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Unnormalized attention over a local KV shard.

    Returns ``(out_unnorm, row_max, row_sumexp)`` in float32 — the
    per-shard (contrib, lmax, lsum) triple of the reference's local online
    softmax pass (`attention-mpi.c:168-189`), ready for the global
    two-phase pmax/psum merge.  Shapes: out (..., m, dv), stats (..., m).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    if q_segment_ids is not None and q.ndim == 4:
        raise ValueError(
            "segment ids support 2D/3D inputs (ids shared across heads)"
        )
    qh, kh, vh, unbatch = _canon(q, k, v)
    out, row_max, row_sum = _flash_call(
        qh,
        kh,
        vh,
        scale=scale,
        causal=causal,
        normalize=False,
        block_sizes=block_sizes or BlockSizes.for_shape(
            qh.shape[0], qh.shape[1], qh.shape[2], window,
            returns_stats=True, causal=causal, dtype=qh.dtype),
        return_stats=True,
        interpret=interpret,
        out_dtype=jnp.float32,
        q_offset=q_offset,
        kv_offset=kv_offset,
        kv_valid=kv_valid,
        q_segment_ids=q_segment_ids,
        kv_segment_ids=kv_segment_ids,
        window=window,
        softcap=softcap,
        sinks=sinks,
        max_mode=max_mode,
    )
    if q.ndim == 2:
        return out[0], row_max[0], row_sum[0]
    if q.ndim == 4:
        b, h = q.shape[:2]
        return (
            out.reshape(b, h, *out.shape[1:]),
            row_max.reshape(b, h, -1),
            row_sum.reshape(b, h, -1),
        )
    return out, row_max, row_sum


def flash_attention_partials(
    q: jax.Array, k: jax.Array, v: jax.Array, **kwargs
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Unnormalized attention over a local KV shard (telemetry shim;
    see :func:`_flash_attention_partials_jit` for the full docs)."""
    if obs.is_enabled():
        _FLASH_CALLS.inc(
            bucket=obs.shape_bucket(q.shape[-2], q.shape[-1]),
            mode=str(kwargs.get("max_mode", "online")),
            entry="partials")
    return _flash_attention_partials_jit(q, k, v, **kwargs)
