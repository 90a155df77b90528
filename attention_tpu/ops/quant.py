"""int8-quantized KV cache + fused quantized flash-decode kernel.

Cuts the KV cache's HBM footprint to 0.63x bf16 (int8 values at 0.5x
plus 32B/row of replicated fp32 scales against 128B/row saved) — more
context per chip for free accuracy-wise (~4e-4 output error measured
at seq=32k).

Quantization scheme: symmetric per-token absmax (one fp32 scale per
cached row per head).  The kernel never dequantizes into (block_k, d)
fp tiles via per-row multiplies: a per-token scale is a scalar on the
contraction's token axis, so it commutes out of both matmuls —

    scores = q · (K_q · s_K)ᵀ = (q · K_qᵀ) ∘ s_K     (row-vec, post-matmul)
    out    = p · (V_q · s_V)  = (p ∘ s_V) · V_q       (folded into P)

and the token axis lies along *lanes* of the score/probability tiles,
so the scales apply as (1, block_k) row vectors — no narrow-block
transposes.  Scales ship sublane-replicated (8, N) per (batch, kv head)
(a (1, block_k) vector block would violate Mosaic's (8, 128) min-tile
rule; the 8x replication costs 32B/row against the 224B/row saved).

**Storage is plain int8** (B, Hkv, N, d): blocks DMA at full rate on
the current Mosaic toolchain and dequant is one int8->bf16 convert per
tile.  (An earlier revision stored byte-planar int32 words to dodge a
since-fixed ~10x int8-DMA slowdown — see git history if it ever
regresses; measured now: int8 blocks stream FASTER than bf16 per
block, and the planar unpack's 12 VPU ops/tile made decode ~1.7x
slower than bf16 instead of at parity.)

The reference's mixed-precision boundary (fp64 edges / fp32 compute +
wire, `attention-mpi.c:31-101`) pushed one level further: bf16 compute,
int8 storage.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu.ops.decode import (
    _pick_block_k,
    banded_block_clamp,
    banded_live,
    check_band,
)
from attention_tpu.ops.flash import (
    banded_keep,
    _LOG2E,
    _STAT_LANES,
    NEG_INF,
    _ceil_to,
    _compiler_params,
    _online_softmax_update,
    _should_interpret,
    check_softcap,
)


class QuantizedKV(NamedTuple):
    """int8 KV cache: values (B, Hkv, N, d) int8 + per-token fp32
    scales stored sublane-replicated (B, Hkv, 8, N)."""

    k_q: jax.Array
    k_scale: jax.Array
    v_q: jax.Array
    v_scale: jax.Array

    @property
    def capacity(self) -> int:
        return self.k_q.shape[2]

    @property
    def head_dim(self) -> int:
        return self.k_q.shape[3]


def _quant_rows(x):
    """Symmetric per-token absmax int8 -> (int8 values, (..., 8, N) scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # (..., N)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    scale_rep = jnp.broadcast_to(
        scale[..., None, :], (*scale.shape[:-1], 8, scale.shape[-1])
    )
    return q, scale_rep


def sink_read_rotation(kv: "QuantizedKV", new_total, window: int,
                       sinks: int, theta: float) -> "QuantizedKV":
    """StreamingLLM in-cache sink positions for an int8 cache, at read
    time: dequantize the ``sinks`` pinned key rows, rotate them forward
    by ``delta = max(new_total - (window + sinks), 0)`` (the same
    convention as the bf16 `_sink_read_keys` — RoPE rotations compose
    additively), requantize, and return a READ copy of the cache; the
    stored cache keeps absolute rotations, so there is no compounding
    drift.  Double quantization of the sink rows adds int8-grade noise,
    inside the cache's existing error contract.
    """
    from attention_tpu.ops.rope import apply_rope

    k_sink = (kv.k_q[:, :, :sinks].astype(jnp.float32)
              * kv.k_scale[:, :, 0, :sinks][..., None])
    delta = jnp.maximum(
        jnp.asarray(new_total, jnp.int32) - (window + sinks), 0
    )
    if delta.ndim:  # ragged per-sequence totals -> (B, 1, 1) positions
        delta = delta[:, None, None]
    q_rot, s_rot = _quant_rows(apply_rope(k_sink, delta, theta))
    zero = jnp.zeros((), jnp.int32)
    return kv._replace(
        k_q=jax.lax.dynamic_update_slice(
            kv.k_q, q_rot, (zero, zero, zero, zero)
        ),
        k_scale=jax.lax.dynamic_update_slice(
            kv.k_scale, s_rot, (zero, zero, zero, zero)
        ),
    )


def quantize_kv(k: jax.Array, v: jax.Array) -> QuantizedKV:
    """Quantize full (B, Hkv, N, d) K/V caches to the int8 cache format."""
    k_q, k_s = _quant_rows(k)
    v_q, v_s = _quant_rows(v)
    return QuantizedKV(k_q, k_s, v_q, v_s)


def update_quantized_kv(cache: QuantizedKV, k_new: jax.Array,
                        v_new: jax.Array, index) -> QuantizedKV:
    """Write S new rows (B, Hkv, S, d) at ``index`` (dynamic scalar).

    Overflow (index + S > capacity) NaN-poisons the written scales —
    dynamic_update_slice would otherwise clamp the start index and
    silently destroy earlier rows (same contract as the bf16
    ``KVCache`` path, models/attention_layer.py).
    """
    k_q, k_s = _quant_rows(k_new)
    v_q, v_s = _quant_rows(v_new)
    overflow = index + k_new.shape[2] > cache.capacity
    k_s = jnp.where(overflow, jnp.nan, k_s)
    v_s = jnp.where(overflow, jnp.nan, v_s)
    zero = jnp.zeros((), jnp.int32)
    return QuantizedKV(
        jax.lax.dynamic_update_slice(cache.k_q, k_q, (zero, zero, index, zero)),
        jax.lax.dynamic_update_slice(cache.k_scale, k_s, (zero, zero, zero, index)),
        jax.lax.dynamic_update_slice(cache.v_q, v_q, (zero, zero, index, zero)),
        jax.lax.dynamic_update_slice(cache.v_scale, v_s, (zero, zero, zero, index)),
    )


def _decode_q_kernel(
    lens_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
    acc_scr, m_scr, l_scr,
    *, hkv: int, block_k: int, softcap2: float | None = None,
    window: int | None = None, sinks: int | None = None,
    chunk: int | None = None, unpack=None,
):
    """One (batch*kv-head, kv-block) grid step of quantized-cache
    decode (int8, and int4 via ``unpack``).

    ``window``/``sinks``: the same per-sequence [len-w, len) band +
    pinned sink rows as the bf16 decode kernel (ops/decode.py).
    ``chunk``: speculative-verify mode, mirroring
    `decode._decode_kernel`: rows pack (group, chunk) with s minor,
    row (g, s) at position ``valid - chunk + s``, causal + per-row
    window band.  ``unpack``: tile dequantizer (storage block -> bf16
    values block); None = plain int8 convert.  ONE kernel body serves
    every BYTE-PER-FEATURE storage format so masking/band logic cannot
    drift between them.  Documented exception: the token-paired int4
    layout (`_decode_tok4_kernel`) cannot ride the unpack hook — its
    unpack doubles the ROW count, changing the score tile's lane->token
    map — so it mirrors this body instead; any band/mask semantics
    change here must touch that kernel too, and the cross-layout
    equality tests (tests/test_quant.py::test_int4_tok_matches_feature_
    layout, tpu_smoke's token-paired case) pin the two against drift."""
    bh = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    valid = lens_ref[bh // hkv]
    kv_min = None
    if chunk is None and window is not None:
        kv_min = jnp.maximum(valid - window, 0)
    w_eff = (window + chunk - 1) if (chunk and window) else window

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = banded_live(j, valid, block_k, w_eff, sinks)

    deq = ((lambda x: x.astype(jnp.bfloat16)) if unpack is None
           else unpack)

    @pl.when(live)
    def _tile():
        q = q_ref[0]                       # (group_pad, d), log2-prescaled
        kq = deq(k_ref[0])                 # (block_k, d) bf16 values
        s = jax.lax.dot_general(
            q, kq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        k_scale = jnp.max(ks_ref[0], axis=0, keepdims=True)  # (1, block_k)
        s = s * k_scale                     # dequant on the score tile
        if softcap2 is not None:
            # logit soft-capping in log2 units (see flash.py::_flash_tile)
            s = softcap2 * jnp.tanh(s / softcap2)
        col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < valid
        if chunk is not None:
            # per-row chunk position: causal + window band per row
            pos = valid - chunk + jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), chunk
            )
            mask = jnp.logical_and(mask, col <= pos)
            if window is not None:
                keep = col >= pos - (window - 1)
                if sinks is not None:
                    keep = jnp.logical_or(keep, col < sinks)
                mask = jnp.logical_and(mask, keep)
        elif kv_min is not None:
            mask = jnp.logical_and(mask, banded_keep(col, kv_min, sinks))
        s = jnp.where(mask, s, NEG_INF)

        p, corr = _online_softmax_update(s, m_scr, l_scr, masked=True)
        v_scale = jnp.max(vs_ref[0], axis=0, keepdims=True)  # (1, block_k)
        pv = jax.lax.dot_general(
            (p * v_scale).astype(jnp.bfloat16),   # dequant folded into P
            deq(v_ref[0]),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv

    @pl.when(j == num_j - 1)
    def _finalize():
        l = jnp.max(l_scr[...], axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_k", "interpret", "softcap", "window",
                     "sinks"),
)
def flash_decode_quantized(
    q: jax.Array,          # (B, H, d)
    cache: QuantizedKV,    # int8 caches + scales
    lengths: jax.Array,    # (B,) int32 or scalar
    *,
    scale: float | None = None,
    block_k: int = 4096,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
) -> jax.Array:
    """softmax(q K[:len]^T * scale) V[:len] against an int8 cache.

    ``softcap`` applies Gemma-2-style logit capping before softmax.
    ``window``/``sinks``: sliding-window serving with pinned sink rows,
    same per-sequence band semantics as :func:`ops.decode.flash_decode`.
    Default ``block_k`` is 4096 — measured 445 us vs 519 at 2048 for a
    32k cache (device clock), which is exactly the 0.625x byte ratio of
    int8+scales vs bf16: the int8 stream needs the bigger block to stay
    bandwidth-proportional (the bf16 kernel is already at HBM peak with
    2048).
    """
    check_softcap(softcap)
    check_band(window, sinks)
    b, h, d = q.shape
    bk_, hkv, n, dk_ = cache.k_q.shape
    if bk_ != b or dk_ != d or cache.v_q.shape != (b, hkv, n, d):
        raise ValueError(
            f"cache shapes inconsistent: Q{q.shape} K{cache.k_q.shape} "
            f"V{cache.v_q.shape}"
        )
    if cache.k_scale.shape != (b, hkv, 8, n) or \
            cache.v_scale.shape != (b, hkv, 8, n):
        raise ValueError(
            f"scale shapes {cache.k_scale.shape}/{cache.v_scale.shape} "
            f"!= {(b, hkv, 8, n)}"
        )
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    group = h // hkv

    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(jnp.bfloat16)
    qs = qs.reshape(b * hkv, group, d)
    group_pad = _ceil_to(group, 16)
    if group_pad != group:
        qs = jnp.pad(qs, ((0, 0), (0, group_pad - group), (0, 0)))

    block_k = _pick_block_k(n, block_k)
    kc = cache.k_q.reshape(b * hkv, n, d)
    vc = cache.v_q.reshape(b * hkv, n, d)
    ks = cache.k_scale.reshape(b * hkv, 8, n)
    vs = cache.v_scale.reshape(b * hkv, 8, n)

    def kv_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, banded_block_clamp(j, valid, block_k, window, sinks), 0)

    def scale_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, 0, banded_block_clamp(j, valid, block_k, window, sinks))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n // block_k),
        in_specs=[
            pl.BlockSpec((1, group_pad, d), lambda bh, j, lr: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, 8, block_k), scale_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, 8, block_k), scale_index),
        ],
        out_specs=pl.BlockSpec((1, group_pad, d), lambda bh, j, lr: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group_pad, d), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_q_kernel, hkv=hkv, block_k=block_k,
            softcap2=None if softcap is None else softcap * _LOG2E,
            window=window, sinks=sinks,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, group_pad, d), jnp.bfloat16),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * n * d,
            bytes_accessed=kc.size + vc.size + (ks.size + vs.size) * 4
            + qs.size * 2,
            transcendentals=b * h * n,
        ),
        interpret=interpret,
    )(lens, qs, kc, ks, vc, vs)

    return out[:, :group].reshape(b, h, d)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_k", "interpret", "softcap", "window",
                     "sinks"),
)
def flash_decode_quantized_chunk(
    q: jax.Array,          # (B, H, S, d) — S new tokens per sequence
    cache: QuantizedKV,    # chunk rows ALREADY appended (int8)
    new_lengths: jax.Array,  # (B,) int32 lengths AFTER the append
    *,
    scale: float | None = None,
    block_k: int = 4096,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
) -> jax.Array:
    """Score S appended tokens against the int8 cache in one stream
    -> (B, H, S, d): the speculative-verify primitive on the quantized
    cache (`ops.decode.flash_decode_chunk`'s layout and masking, this
    module's scales-commute-out dequantization)."""
    check_softcap(softcap)
    check_band(window, sinks)
    if q.ndim != 4:
        raise ValueError(f"expected q (B,H,S,d), got {q.shape}")
    b, h, s_chunk, d = q.shape
    bk_, hkv, n, dk_ = cache.k_q.shape
    if bk_ != b or dk_ != d or cache.v_q.shape != (b, hkv, n, d):
        raise ValueError(
            f"cache shapes inconsistent: Q{q.shape} K{cache.k_q.shape} "
            f"V{cache.v_q.shape}"
        )
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    group = h // hkv

    lens = jnp.broadcast_to(jnp.asarray(new_lengths, jnp.int32), (b,))
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(jnp.bfloat16)
    qs = qs.reshape(b * hkv, group * s_chunk, d)
    rows = group * s_chunk
    rows_pad = _ceil_to(rows, 16)
    if rows_pad != rows:
        qs = jnp.pad(qs, ((0, 0), (0, rows_pad - rows), (0, 0)))

    block_k = _pick_block_k(n, block_k)
    kc = cache.k_q.reshape(b * hkv, n, d)
    vc = cache.v_q.reshape(b * hkv, n, d)
    ks = cache.k_scale.reshape(b * hkv, 8, n)
    vs = cache.v_scale.reshape(b * hkv, 8, n)
    w_eff = None if window is None else window + s_chunk - 1

    def kv_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, banded_block_clamp(j, valid, block_k, w_eff, sinks), 0)

    def scale_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, 0, banded_block_clamp(j, valid, block_k, w_eff, sinks))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n // block_k),
        in_specs=[
            pl.BlockSpec((1, rows_pad, d), lambda bh, j, lr: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, 8, block_k), scale_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, 8, block_k), scale_index),
        ],
        out_specs=pl.BlockSpec((1, rows_pad, d), lambda bh, j, lr: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows_pad, d), jnp.float32),
            pltpu.VMEM((rows_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows_pad, _STAT_LANES), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_q_kernel, hkv=hkv, block_k=block_k,
            softcap2=None if softcap is None else softcap * _LOG2E,
            window=window, sinks=sinks, chunk=s_chunk,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows_pad, d), jnp.bfloat16),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * s_chunk * n * d,
            bytes_accessed=kc.size + vc.size + (ks.size + vs.size) * 4
            + qs.size * 2,
            transcendentals=b * h * s_chunk * n,
        ),
        interpret=interpret,
    )(lens, qs, kc, ks, vc, vs)

    return out[:, :rows].reshape(b, h, s_chunk, d)


# ---------------------------------------------------------------------------
# int4 KV cache (round 5): half the int8 value bytes.  Decode sits at
# frac 1.00 of the measured HBM streaming ceiling (bench.py --all), so the
# only remaining currency is bytes streamed — int4 cuts the VALUE
# stream to 0.25x bf16; with the 32B/row replicated fp32 scales the
# total at d=128 is (64+32)/256 = 0.375x bf16 (0.6x of int8's 0.625x
# — the fixed scale bytes dilute the nibble saving; bench.py's
# int4_bytes accounting uses the same formula).
#
# Packing: two int4 values per int8 byte along the FEATURE dim, split
# halves — byte f of a row holds feature f in its low nibble and
# feature f + d/2 in its high nibble, so the in-kernel unpack is a few
# float floor/fma ops and a lane concat (lo half ++ hi half restores
# natural feature order — no interleave relayout, the trap that made
# the byte-planar int8 experiment 1.7x slower, see module docstring).
# Scales stay per-token symmetric absmax (they commute out of both
# matmuls exactly as in int8).
# ---------------------------------------------------------------------------


class Int4KV(NamedTuple):
    """int4-packed KV cache: values (B, Hkv, N, d//2) int8 (two nibbles
    per byte) + per-token fp32 scales (B, Hkv, 8, N), layout-compatible
    with `QuantizedKV`'s scales."""

    k_q: jax.Array
    k_scale: jax.Array
    v_q: jax.Array
    v_scale: jax.Array

    @property
    def capacity(self) -> int:
        return self.k_q.shape[2]

    @property
    def head_dim(self) -> int:
        return 2 * self.k_q.shape[3]


def _quant_rows_int4(x):
    """Symmetric per-token absmax int4: (..., N, d) -> packed
    (..., N, d//2) int8 + (..., 8, N) replicated scales."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"head_dim {d} must be even for int4 packing")
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # (..., N)
    scale = jnp.where(amax == 0.0, 1.0, amax / 7.0)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    q = jnp.clip(q, -7, 7).astype(jnp.int8)
    lo = q[..., : d // 2]
    hi = q[..., d // 2:]
    packed = jnp.bitwise_or(
        jnp.bitwise_and(lo, 0xF), jnp.left_shift(hi, 4)
    ).astype(jnp.int8)
    scale_rep = jnp.broadcast_to(
        scale[..., None, :], (*scale.shape[:-1], 8, scale.shape[-1])
    )
    return packed, scale_rep


def _unpack_nibbles(packed):
    """int8 byte tile -> (lo, hi) bf16 nibble tiles of the same shape.

    Nibble extraction is float floor arithmetic, NOT integer shifts:
    Mosaic fails to legalize `arith.shli` on int8 vectors in-kernel
    (remote-compile HTTP 500, 'failed to legalize operation'), while
    convert/floor/fma all lower cleanly.  floor(p/16) IS the
    arithmetic right shift (rounds toward -inf), so `hi` comes out
    sign-extended; the low nibble is the remainder re-signed.  Values
    are small integers — exact in fp32.  The ONE home of this
    workaround: both int4 layouts (feature-dim and token-paired) build
    their unpacks from it."""
    p = packed.astype(jnp.float32)
    hi = jnp.floor(p * (1.0 / 16.0))
    lo = p - 16.0 * hi                       # [0, 15] unsigned nibble
    lo = jnp.where(lo >= 8.0, lo - 16.0, lo)  # two's-complement sign
    return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)


def _unpack_int4(packed):
    """(rows, d//2) int8 nibbles -> (rows, d) bf16 in natural feature
    order; halves concat along lanes (no element interleave)."""
    lo, hi = _unpack_nibbles(packed)
    return jnp.concatenate([lo, hi], axis=-1)


def quantize_kv_int4(k: jax.Array, v: jax.Array) -> Int4KV:
    """Quantize full (B, Hkv, N, d) K/V caches to the int4 cache format.

    MEASURED error budget (tests/test_quant.py):
    ~4-8e-2 max abs output error on unit-normal inputs at d=64/128
    decode shapes — ~30x int8's ~2e-3, dominated by K's nibble
    granularity (absmax/7 per element) perturbing the logits.  That
    EXCEEDS the framework's ±0.02 harness contract: int4 is an OPT-IN
    bytes/quality trade (0.375x bf16 cache bytes at d=128 vs int8's
    0.625x — scales included) for workloads that tolerate it, NOT a
    drop-in.  Workloads needing contract-grade logits stay on
    `quantize_kv` (int8)."""
    k_q, k_s = _quant_rows_int4(k)
    v_q, v_s = _quant_rows_int4(v)
    return Int4KV(k_q, k_s, v_q, v_s)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_k", "interpret", "softcap", "window",
                     "sinks"),
)
def flash_decode_int4(
    q: jax.Array,          # (B, H, d)
    cache: Int4KV,
    lengths: jax.Array,    # (B,) int32 or scalar
    *,
    scale: float | None = None,
    block_k: int = 4096,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
) -> jax.Array:
    """softmax(q K[:len]^T * scale) V[:len] against an int4 cache.

    Same per-sequence band semantics as :func:`flash_decode_quantized`;
    streams 0.375x the bf16 cache bytes at d=128 (0.6x int8's, scales
    included).  Error budget:
    see `quantize_kv_int4`."""
    check_softcap(softcap)
    check_band(window, sinks)
    b, h, d = q.shape
    bk_, hkv, n, dk_half = cache.k_q.shape
    if bk_ != b or 2 * dk_half != d or cache.v_q.shape != (b, hkv, n, d // 2):
        raise ValueError(
            f"cache shapes inconsistent: Q{q.shape} K{cache.k_q.shape} "
            f"V{cache.v_q.shape}"
        )
    if cache.k_scale.shape != (b, hkv, 8, n) or \
            cache.v_scale.shape != (b, hkv, 8, n):
        raise ValueError(
            f"scale shapes {cache.k_scale.shape}/{cache.v_scale.shape} "
            f"!= {(b, hkv, 8, n)}"
        )
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    group = h // hkv

    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(jnp.bfloat16)
    qs = qs.reshape(b * hkv, group, d)
    group_pad = _ceil_to(group, 16)
    if group_pad != group:
        qs = jnp.pad(qs, ((0, 0), (0, group_pad - group), (0, 0)))

    block_k = _pick_block_k(n, block_k)
    kc = cache.k_q.reshape(b * hkv, n, d // 2)
    vc = cache.v_q.reshape(b * hkv, n, d // 2)
    ks = cache.k_scale.reshape(b * hkv, 8, n)
    vs = cache.v_scale.reshape(b * hkv, 8, n)

    def kv_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, banded_block_clamp(j, valid, block_k, window, sinks), 0)

    def scale_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, 0, banded_block_clamp(j, valid, block_k, window, sinks))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n // block_k),
        in_specs=[
            pl.BlockSpec((1, group_pad, d), lambda bh, j, lr: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d // 2), kv_index),
            pl.BlockSpec((1, 8, block_k), scale_index),
            pl.BlockSpec((1, block_k, d // 2), kv_index),
            pl.BlockSpec((1, 8, block_k), scale_index),
        ],
        out_specs=pl.BlockSpec((1, group_pad, d), lambda bh, j, lr: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group_pad, d), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            # ONE kernel body with the int8 path (unpack hook): the
            # masking/band logic exists in one place for both formats
            _decode_q_kernel, hkv=hkv, block_k=block_k,
            softcap2=None if softcap is None else softcap * _LOG2E,
            window=window, sinks=sinks, unpack=_unpack_int4,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, group_pad, d), jnp.bfloat16),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * n * d,
            bytes_accessed=kc.size + vc.size + (ks.size + vs.size) * 4
            + qs.size * 2,
            transcendentals=b * h * n,
        ),
        interpret=interpret,
    )(lens, qs, kc, ks, vc, vs)

    return out[:, :group].reshape(b, h, d)


# ---------------------------------------------------------------------------
# int4, token-paired packing (round 5, second attempt at the latency
# side).  The feature-dim packing above measured 0.748 ms vs int8's
# 0.445 at the bench decode shape: its (block_k, d/2=64) value tiles
# are HALF the native 128-lane width, so the value stream loses the
# full-width DMA efficiency the int8 kernel rides
# (artifacts/int4_pack_exp.json).
# This layout packs two ADJACENT TOKENS per byte instead — byte row r
# holds token 2r in its low nibble and token 2r+1 in its high nibble,
# per feature — so value tiles stay (rows, d=128) full lane width and
# the unpack splits along SUBLANES (a concat on the major axis, no
# lane relayout).  The pairing stride is a constant 2, so the layout is
# independent of kernel tiling (no block_k coupling); scales ship
# pre-split even/odd (rows 0-7 / 8-15 of a 16-row replicated band) so
# the kernel's lane-concat of the two scale vectors matches the score
# tile's [even tokens | odd tokens] lane order with contiguous fetches.
# Quantization math (per-token symmetric absmax / 7) is IDENTICAL to
# the feature packing, so the error budget carries over unchanged.
# ---------------------------------------------------------------------------


class Int4TokKV(NamedTuple):
    """Token-paired int4 cache: values (B, Hkv, N//2, d) int8 (tokens
    2r/2r+1 in the low/high nibbles of byte row r) + per-token fp32
    scales (B, Hkv, 16, N//2) — sublanes 0-7 replicate the even-token
    scale, 8-15 the odd-token scale."""

    k_q: jax.Array
    k_scale: jax.Array
    v_q: jax.Array
    v_scale: jax.Array

    @property
    def capacity(self) -> int:
        return 2 * self.k_q.shape[2]

    @property
    def head_dim(self) -> int:
        return self.k_q.shape[3]


def _quant_rows_int4_tok(x):
    """Symmetric per-token absmax int4: (..., N, d) -> token-paired
    packed (..., N//2, d) int8 + (..., 16, N//2) even/odd scales."""
    n = x.shape[-2]
    if n % 2:
        raise ValueError(f"cache length {n} must be even for token pairing")
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # (..., N)
    scale = jnp.where(amax == 0.0, 1.0, amax / 7.0)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    q = jnp.clip(q, -7, 7).astype(jnp.int8)
    lo = q[..., 0::2, :]   # even tokens
    hi = q[..., 1::2, :]   # odd tokens
    packed = jnp.bitwise_or(
        jnp.bitwise_and(lo, 0xF), jnp.left_shift(hi, 4)
    ).astype(jnp.int8)
    se = jnp.broadcast_to(scale[..., None, 0::2],
                          (*scale.shape[:-1], 8, n // 2))
    so = jnp.broadcast_to(scale[..., None, 1::2],
                          (*scale.shape[:-1], 8, n // 2))
    return packed, jnp.concatenate([se, so], axis=-2)  # (..., 16, N//2)


def _unpack_int4_tok(packed):
    """(rows, d) token-paired int8 -> two (rows, d) bf16 value tiles
    (even tokens, odd tokens) in natural within-block order — here the
    two nibbles are two TOKEN rows sharing a byte row, so no lane
    concat is needed; the caller stacks the halves along sublanes.
    Nibble math lives in `_unpack_nibbles` (the Mosaic float-floor
    workaround's one home)."""
    return _unpack_nibbles(packed)


def _pick_block_tok(n: int, want: int) -> int:
    """Largest multiple of 256 that divides ``n`` and is <= ``want``
    rounded up to the next 256 (so an undersized ``want`` like 128
    resolves UP to the minimal valid block, 256, instead of failing).

    The token-paired kernel's packed block is ``block_tok // 2`` byte
    rows and must stay a multiple of the 128-row tile, so the token
    block steps by 256 — `decode._pick_block_k`'s 128-stepped search
    can land on an odd 128-multiple (e.g. n=4864, want=4096 -> 2432)
    that is a valid int8 block but not a valid packed one.  A
    256-multiple divisor always exists because `quantize_kv_int4_tok`
    requires n % 256 == 0."""
    if n % 256:
        raise ValueError(
            f"token-paired int4 cache capacity {n} must be a multiple "
            f"of 256"
        )
    bk = min(_ceil_to(want, 256), n)
    while n % bk:
        bk -= 256
    return bk


def quantize_kv_int4_tok(k: jax.Array, v: jax.Array) -> Int4TokKV:
    """Quantize full (B, Hkv, N, d) K/V caches to the token-paired int4
    format.  Same quantization math — and therefore the same measured
    ~4-8e-2 opt-in error budget — as :func:`quantize_kv_int4`; see that
    docstring for the contract discussion."""
    n = k.shape[-2]
    if n % 256:
        # the decode grid needs a 256-multiple token block dividing the
        # capacity; for n ≡ 128 (mod 256) no such block exists, so the
        # cache would be unusable by construction — fail at build time
        # with a capacity-phrased error, not at decode with a
        # block-size one
        raise ValueError(
            f"token-paired int4 needs a 256-multiple cache capacity, "
            f"got {n} (use the feature-dim layout for smaller caches)"
        )
    k_q, k_s = _quant_rows_int4_tok(k)
    v_q, v_s = _quant_rows_int4_tok(v)
    return Int4TokKV(k_q, k_s, v_q, v_s)


def _decode_tok4_kernel(
    lens_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
    acc_scr, m_scr, l_scr,
    *, hkv: int, block_tok: int, softcap2: float | None = None,
    window: int | None = None, sinks: int | None = None,
):
    """One (batch*kv-head, token-block) grid step against a
    token-paired int4 cache.  Mirrors `_decode_q_kernel`'s band logic
    through the same helpers (`banded_live`/`banded_keep`); the body
    differs because the unpack doubles the ROW count: a (bp, d) packed
    block becomes [even-token tile; odd-token tile] stacked along
    sublanes, the score tile's lanes run [even | odd], and the mask's
    column->token map is 2*lane (+1 for the odd half).

    This is the documented EXCEPTION to `_decode_q_kernel`'s one-body
    invariant (see its docstring): keep the two bodies' band/mask
    logic mirrored by hand; the cross-layout equality tests pin them.
    No ``chunk`` (speculative-verify) mode — neither int4 layout has
    one (speculative serving composes with the int8 cache,
    `flash_decode_quantized_chunk`; int4 remains an opt-in decode-only
    capacity/latency trade outside the ±0.02 contract)."""
    bh = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    valid = lens_ref[bh // hkv]
    bp = block_tok // 2
    kv_min = None
    if window is not None:
        kv_min = jnp.maximum(valid - window, 0)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = banded_live(j, valid, block_tok, window, sinks)

    @pl.when(live)
    def _tile():
        q = q_ref[0]                          # (group_pad, d), log2-prescaled
        k_lo, k_hi = _unpack_int4_tok(k_ref[0])
        kt = jnp.concatenate([k_lo, k_hi], axis=0)  # (block_tok, d)
        s = jax.lax.dot_general(
            q, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                     # (group_pad, block_tok)
        ks = ks_ref[0]                        # (16, bp): even rows 0-7
        k_scale = jnp.concatenate(
            [jnp.max(ks[:8], axis=0, keepdims=True),
             jnp.max(ks[8:], axis=0, keepdims=True)], axis=-1
        )                                     # (1, block_tok), [even|odd]
        s = s * k_scale
        if softcap2 is not None:
            s = softcap2 * jnp.tanh(s / softcap2)
        lam = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        base = j * block_tok
        col = jnp.where(lam < bp,
                        base + 2 * lam,
                        base + 2 * (lam - bp) + 1)
        mask = col < valid
        if kv_min is not None:
            mask = jnp.logical_and(mask, banded_keep(col, kv_min, sinks))
        s = jnp.where(mask, s, NEG_INF)

        p, corr = _online_softmax_update(s, m_scr, l_scr, masked=True)
        vs = vs_ref[0]
        v_scale = jnp.concatenate(
            [jnp.max(vs[:8], axis=0, keepdims=True),
             jnp.max(vs[8:], axis=0, keepdims=True)], axis=-1
        )
        v_lo, v_hi = _unpack_int4_tok(v_ref[0])
        vt = jnp.concatenate([v_lo, v_hi], axis=0)  # (block_tok, d)
        pv = jax.lax.dot_general(
            (p * v_scale).astype(jnp.bfloat16),
            vt,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv

    @pl.when(j == num_j - 1)
    def _finalize():
        l = jnp.max(l_scr[...], axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_k", "interpret", "softcap", "window",
                     "sinks"),
)
def flash_decode_int4_tok(
    q: jax.Array,          # (B, H, d)
    cache: Int4TokKV,
    lengths: jax.Array,    # (B,) int32 or scalar
    *,
    scale: float | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
) -> jax.Array:
    """softmax(q K[:len]^T * scale) V[:len] against a token-paired int4
    cache.  Same band semantics and error budget as
    :func:`flash_decode_int4`; ``block_k`` counts TOKENS (the packed
    block is ``block_k // 2`` byte rows at full d-lane width).

    Default block: **16384** tokens unwindowed — the measured optimum
    at the bench decode shape (b8/32q/4kv/32k, device clock: 0.565 /
    0.455 / 0.415 / 0.402 ms at 2048/4096/8192/16384; the unpack's VPU
    cost rewards fewer, larger steps once the stream is no longer
    DMA-bound) — and 4096 windowed, also measured: at w=4096+sinks on
    the same shape, 0.432 / 0.259 / 0.189 / 0.239 ms at
    1024/2048/4096/8192 (int8's same-window default: 0.171 — with the
    stream shrunk to the band, the unpack's VPU cost shows as a ~10%
    premium instead of a win; the capacity trade still stands)."""
    check_softcap(softcap)
    check_band(window, sinks)
    if block_k is None:
        block_k = 16384 if window is None else 4096
    b, h, d = q.shape
    bk_, hkv, n_half, dk_ = cache.k_q.shape
    n = 2 * n_half
    if bk_ != b or dk_ != d or cache.v_q.shape != (b, hkv, n_half, d):
        raise ValueError(
            f"cache shapes inconsistent: Q{q.shape} K{cache.k_q.shape} "
            f"V{cache.v_q.shape}"
        )
    if cache.k_scale.shape != (b, hkv, 16, n_half) or \
            cache.v_scale.shape != (b, hkv, 16, n_half):
        raise ValueError(
            f"scale shapes {cache.k_scale.shape}/{cache.v_scale.shape} "
            f"!= {(b, hkv, 16, n_half)}"
        )
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    group = h // hkv

    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(jnp.bfloat16)
    qs = qs.reshape(b * hkv, group, d)
    group_pad = _ceil_to(group, 16)
    if group_pad != group:
        qs = jnp.pad(qs, ((0, 0), (0, group_pad - group), (0, 0)))

    block_tok = _pick_block_tok(n, block_k)
    bp = block_tok // 2
    kc = cache.k_q.reshape(b * hkv, n_half, d)
    vc = cache.v_q.reshape(b * hkv, n_half, d)
    ks = cache.k_scale.reshape(b * hkv, 16, n_half)
    vs = cache.v_scale.reshape(b * hkv, 16, n_half)

    def kv_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, banded_block_clamp(j, valid, block_tok, window, sinks), 0)

    def scale_index(bh, j, lens_ref):
        valid = lens_ref[bh // hkv]
        return (bh, 0, banded_block_clamp(j, valid, block_tok, window, sinks))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n // block_tok),
        in_specs=[
            pl.BlockSpec((1, group_pad, d), lambda bh, j, lr: (bh, 0, 0)),
            pl.BlockSpec((1, bp, d), kv_index),
            pl.BlockSpec((1, 16, bp), scale_index),
            pl.BlockSpec((1, bp, d), kv_index),
            pl.BlockSpec((1, 16, bp), scale_index),
        ],
        out_specs=pl.BlockSpec((1, group_pad, d), lambda bh, j, lr: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group_pad, d), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((group_pad, _STAT_LANES), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_tok4_kernel, hkv=hkv, block_tok=block_tok,
            softcap2=None if softcap is None else softcap * _LOG2E,
            window=window, sinks=sinks,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, group_pad, d), jnp.bfloat16),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * n * d,
            bytes_accessed=kc.size + vc.size + (ks.size + vs.size) * 4
            + qs.size * 2,
            transcendentals=b * h * n,
        ),
        interpret=interpret,
    )(lens, qs, kc, ks, vc, vs)

    return out[:, :group].reshape(b, h, d)
