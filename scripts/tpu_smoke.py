"""One-command real-TPU smoke sweep of every kernel variant.

CPU tests run the Pallas kernels in interpreter mode; commit e8ed27d
proved interpret-green does not imply Mosaic-green.  This script runs
each kernel variant ONCE on the real chip with tiny shapes and checks it
against a dense oracle — the analog of the course grader running every
testcase (reference spec: run the frozen harness on the full ladder).

Run: python scripts/tpu_smoke.py        (refuses to run without a TPU)
Exit status 0 iff every variant lowered and agreed with its oracle.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from attention_tpu.ops.decode import flash_decode
from attention_tpu.ops.flash import flash_attention, flash_attention_partials
from attention_tpu.ops.flash_vjp import flash_attention_diff
from attention_tpu.ops.paged import PagePool, paged_flash_decode, paged_from_dense
from attention_tpu.ops.quant import flash_decode_quantized, quantize_kv

# This sweep's bound-mode cases exist to prove the BOUND KERNEL lowers
# and agrees with the oracle on real Mosaic; production's small-shape
# static resolution (bound -> online below _BOUND_MIN_SCORE_ELEMS,
# measured round 5) would silently reroute the tiny smoke shapes to the
# online kernel and test nothing new — pin it off for the whole sweep.
import attention_tpu.ops.flash as _flash_mod

# the production threshold, saved BEFORE the sweep-wide pin so the
# dispatch-path case below can run with it intact
_PROD_BOUND_MIN_SCORE_ELEMS = _flash_mod._BOUND_MIN_SCORE_ELEMS
_flash_mod._BOUND_MIN_SCORE_ELEMS = 0

RNG = np.random.default_rng(7)


def _arr(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


def _dense(q, k, v, *, causal=False, window=None, sinks=None, softcap=None,
           q_seg=None, kv_seg=None, q_offset=0, kv_valid=None):
    """fp32 XLA oracle for every mask combination — an independent code
    path from the kernels, with matmuls forced to full fp32 precision
    (the chip's default fp32 matmul precision is bf16 passes, which
    would blur the oracle by the same ~1e-2 the kernels show)."""
    with jax.default_matmul_precision("highest"):
        return _dense_inner(q, k, v, causal=causal, window=window,
                            sinks=sinks, softcap=softcap, q_seg=q_seg,
                            kv_seg=kv_seg, q_offset=q_offset,
                            kv_valid=kv_valid)


def _dense_inner(q, k, v, *, causal, window, sinks, softcap,
                 q_seg, kv_seg, q_offset, kv_valid):
    group = q.shape[0] // k.shape[0] if q.ndim == 3 else 1
    if q.ndim == 3 and group > 1:
        k = jnp.repeat(k, group, axis=0)
        v = jnp.repeat(v, group, axis=0)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("...md,...nd->...mn", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    m, n = s.shape[-2:]
    col = jnp.arange(n)[None, :]
    mask = jnp.ones((m, n), bool)
    if kv_valid is not None:
        mask = jnp.logical_and(mask, col < kv_valid)
    if causal:
        row = jnp.arange(m)[:, None] + q_offset
        mask = jnp.logical_and(mask, col <= row)
        if window is not None:
            win = col >= row - (window - 1)
            if sinks:
                win = jnp.logical_or(win, col < sinks)
            mask = jnp.logical_and(mask, win)
    if q_seg is not None:
        mask = jnp.logical_and(mask, q_seg[:, None] == kv_seg[None, :])
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("...mn,...nd->...md", p, v.astype(jnp.float32))


CASES = []


def case(name):
    def deco(fn):
        CASES.append((name, fn))
        return fn

    return deco


# ----------------------------- forward -----------------------------

@case("fwd/causal")
def _():
    q, k, v = _arr(4, 384, 64), _arr(4, 384, 64), _arr(4, 384, 64)
    got = flash_attention(q, k, v, causal=True)
    return got, _dense(q, k, v, causal=True)


@case("fwd/cross-attention (m!=n, dv!=dk, non-causal)")
def _():
    q, k, v = _arr(2, 256, 64), _arr(2, 384, 64), _arr(2, 384, 128)
    got = flash_attention(q, k, v)
    return got, _dense(q, k, v)


@case("fwd/gqa 8q2kv")
def _():
    q, k, v = _arr(8, 256, 64), _arr(2, 256, 64), _arr(2, 256, 64)
    got = flash_attention(q, k, v, causal=True)
    return got, _dense(q, k, v, causal=True)


@case("fwd/window")
def _():
    q, k, v = _arr(2, 512, 64), _arr(2, 512, 64), _arr(2, 512, 64)
    got = flash_attention(q, k, v, causal=True, window=160)
    return got, _dense(q, k, v, causal=True, window=160)


@case("fwd/window+sinks")
def _():
    q, k, v = _arr(2, 512, 64), _arr(2, 512, 64), _arr(2, 512, 64)
    got = flash_attention(q, k, v, causal=True, window=160, sinks=4)
    return got, _dense(q, k, v, causal=True, window=160, sinks=4)


@case("fwd/bound-max causal")
def _():
    q, k, v = _arr(4, 384, 64), _arr(4, 384, 64), _arr(4, 384, 64)
    got = flash_attention(q, k, v, causal=True, max_mode="bound")
    return got, _dense(q, k, v, causal=True)


@case("fwd/bound-max gqa+softcap")
def _():
    q, k, v = _arr(8, 256, 64), _arr(2, 256, 64), _arr(2, 256, 64)
    got = flash_attention(q, k, v, causal=True, softcap=12.0,
                          max_mode="bound")
    return got, _dense(q, k, v, causal=True, softcap=12.0)


@case("fwd/bound-max window+sinks")
def _():
    q, k, v = _arr(2, 512, 64), _arr(2, 512, 64), _arr(2, 512, 64)
    got = flash_attention(q, k, v, causal=True, window=160, sinks=4,
                          max_mode="bound")
    return got, _dense(q, k, v, causal=True, window=160, sinks=4)


@case("fwd/bound-max offsets (q_offset + kv_valid)")
def _():
    q, k, v = _arr(2, 128, 64), _arr(2, 384, 64), _arr(2, 384, 64)
    got = flash_attention(q, k, v, causal=True, q_offset=192,
                          kv_valid=320, max_mode="bound")
    return got, _dense(q, k, v, causal=True, q_offset=192, kv_valid=320)


@case("bwd/bound-max forward in the VJP")
def _():
    return _grad_case(max_mode="bound")


@case("fwd/softcap")
def _():
    q, k, v = _arr(2, 256, 64), _arr(2, 256, 64), _arr(2, 256, 64)
    got = flash_attention(q, k, v, causal=True, softcap=20.0)
    return got, _dense(q, k, v, causal=True, softcap=20.0)


@case("fwd/segments")
def _():
    q, k, v = _arr(1, 384, 64), _arr(1, 384, 64), _arr(1, 384, 64)
    seg = jnp.asarray(
        np.concatenate([np.zeros(150), np.ones(234)]).astype(np.int32)
    )
    got = flash_attention(q[0], k[0], v[0], causal=True,
                          q_segment_ids=seg, kv_segment_ids=seg)
    return got, _dense(q, k, v, causal=True, q_seg=seg, kv_seg=seg)[0]


@case("fwd/q_offset+kv_valid (chunked decode shape)")
def _():
    q, k, v = _arr(2, 128, 64), _arr(2, 512, 64), _arr(2, 512, 64)
    got = flash_attention(q, k, v, causal=True, q_offset=200,
                          kv_valid=328)
    return got, _dense(q, k, v, causal=True, q_offset=200, kv_valid=328)


@case("fwd/4d batched")
def _():
    q, k, v = _arr(2, 4, 256, 64), _arr(2, 4, 256, 64), _arr(2, 4, 256, 64)
    got = flash_attention(q, k, v, causal=True)
    return got, _dense(q, k, v, causal=True)


@case("fwd/bf16 in, fp32 accum")
def _():
    q, k, v = (x.astype(jnp.bfloat16) for x in
               (_arr(2, 256, 64), _arr(2, 256, 64), _arr(2, 256, 64)))
    got = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    want = _dense(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), causal=True)
    return got, want, 2e-2  # the +-0.02 contract for bf16


@case("fwd/partials 2-shard merge == full")
def _():
    q, k, v = _arr(2, 256, 64), _arr(2, 256, 64), _arr(2, 256, 64)
    want = flash_attention(q, k, v, causal=True)
    acc = m_run = l_run = None
    for off in (0, 128):
        o, lm, ls = flash_attention_partials(
            q, k[:, off:off + 128], v[:, off:off + 128], causal=True,
            kv_offset=jnp.int32(off),
        )
        o, lm, ls = (np.asarray(x, np.float64) for x in (o, lm, ls))
        if acc is None:
            acc, m_run, l_run = o, lm, ls
        else:
            m_new = np.maximum(m_run, lm)
            c_old = np.where(np.isneginf(m_run), 0.0, np.exp(m_run - m_new))
            c_new = np.where(np.isneginf(lm), 0.0, np.exp(lm - m_new))
            acc = acc * c_old[..., None] + o * c_new[..., None]
            l_run = l_run * c_old + ls * c_new
            m_run = m_new
    got = acc / np.where(l_run == 0.0, 1.0, l_run)[..., None]
    return jnp.asarray(got, jnp.float32), want


# ----------------------------- backward -----------------------------

def _grad_case(**kw):
    h, hkv = (4, 2) if kw.pop("gqa", False) else (2, 2)
    m, d = 320, 64
    q, k, v = _arr(h, m, d), _arr(hkv, m, d), _arr(hkv, m, d)
    wt = _arr(h, m, d)

    def floss(q, k, v):
        return jnp.sum(flash_attention_diff(
            q, k, v, causal=True, bwd_impl="pallas", **kw) * wt)

    def dloss(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True,
                              window=kw.get("window"),
                              sinks=kw.get("sinks"),
                              softcap=kw.get("softcap"),
                              q_seg=kw.get("q_segment_ids"),
                              kv_seg=kw.get("kv_segment_ids")) * wt)

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dloss, argnums=(0, 1, 2))(q, k, v)
    got = jnp.concatenate([g.reshape(-1) for g in gf])
    want = jnp.concatenate([g.reshape(-1) for g in gd])
    return got, want, 5e-2


@case("bwd/causal (dq + dkdv kernels)")
def _():
    return _grad_case()


@case("bwd/gqa grouped dkdv")
def _():
    return _grad_case(gqa=True)


@case("bwd/window banded")
def _():
    return _grad_case(window=96)


@case("bwd/window+sinks")
def _():
    return _grad_case(window=96, sinks=5)


@case("bwd/softcap")
def _():
    return _grad_case(softcap=15.0)


@case("bwd/segments")
def _():
    seg = jnp.asarray(
        np.concatenate([np.zeros(130), np.ones(190)]).astype(np.int32)
    )
    h, m, d = 2, 320, 64
    q, k, v = _arr(h, m, d), _arr(h, m, d), _arr(h, m, d)
    wt = _arr(h, m, d)

    def floss(q, k, v):
        return jnp.sum(flash_attention_diff(
            q, k, v, causal=True, bwd_impl="pallas",
            q_segment_ids=seg, kv_segment_ids=seg) * wt)

    def dloss(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True, q_seg=seg,
                              kv_seg=seg) * wt)

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dloss, argnums=(0, 1, 2))(q, k, v)
    got = jnp.concatenate([g.reshape(-1) for g in gf])
    want = jnp.concatenate([g.reshape(-1) for g in gd])
    return got, want, 5e-2


# ----------------------------- decode -----------------------------

def _decode_setup(b=3, h=4, hkv=2, n=512, d=64):
    q = _arr(b, h, d)
    kc, vc = _arr(b, hkv, n, d), _arr(b, hkv, n, d)
    lens = jnp.asarray([n, 129, 300][:b], jnp.int32)
    group = h // hkv
    # dense oracle: per sequence, the q row attends its valid prefix
    with jax.default_matmul_precision("highest"):
        kx = jnp.repeat(kc, group, axis=1)
        vx = jnp.repeat(vc, group, axis=1)
        s = jnp.einsum("bhd,bhnd->bhn", q, kx) / (d ** 0.5)
        mask = jnp.arange(n)[None, None, :] < lens[:, None, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhn,bhnd->bhd", p, vx)
    return q, kc, vc, lens, want


@case("decode/bf16-cache ragged lens")
def _():
    q, kc, vc, lens, want = _decode_setup()
    got = flash_decode(q, kc, vc, lens, block_k=256)
    return got, want


@case("decode/scalar len")
def _():
    q, kc, vc, lens, want = _decode_setup(b=2)
    got = flash_decode(q, kc, vc, jnp.int32(300), block_k=256)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhd,bhnd->bhn", q, jnp.repeat(kc, 2, axis=1)) / 8.0
        mask = jnp.arange(kc.shape[2])[None, None, :] < 300
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhn,bhnd->bhd", p, jnp.repeat(vc, 2, axis=1))
    return got, want


@case("decode/int8 quantized cache")
def _():
    q, kc, vc, lens, want = _decode_setup()
    got = flash_decode_quantized(q, quantize_kv(kc, vc), lens, block_k=256)
    return got, want, 3e-2  # int8 quantization error dominates


@case("decode/paged block-table")
def _():
    q, kc, vc, lens, want = _decode_setup()
    pool = PagePool(num_pages=16)
    cache = paged_from_dense(kc, vc, lens, pool, num_pages=16)
    got = paged_flash_decode(q, cache)
    return got, want


@case("decode/window+sinks ragged lens")
def _():
    q, kc, vc, lens, _ = _decode_setup()
    w, sk = 160, 4
    got = flash_decode(q, kc, vc, lens, block_k=256, window=w, sinks=sk)
    with jax.default_matmul_precision("highest"):
        kx = jnp.repeat(kc, 2, axis=1)
        vx = jnp.repeat(vc, 2, axis=1)
        s = jnp.einsum("bhd,bhnd->bhn", q, kx) / 8.0
        col = jnp.arange(kc.shape[2])[None, None, :]
        ln = lens[:, None, None]
        mask = (col < ln) & ((col >= jnp.maximum(ln - w, 0)) | (col < sk))
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhn,bhnd->bhd", p, vx)
    return got, want


@case("decode/int8 window+sinks")
def _():
    q, kc, vc, lens, _ = _decode_setup()
    w, sk = 160, 4
    got = flash_decode_quantized(q, quantize_kv(kc, vc), lens,
                                 block_k=256, window=w, sinks=sk)
    want = flash_decode(q, kc, vc, lens, block_k=256, window=w, sinks=sk)
    return got, want, 3e-2  # int8 quantization error


@case("decode/paged window+sinks")
def _():
    q, kc, vc, lens, _ = _decode_setup()
    w, sk = 160, 4
    want = flash_decode(q, kc, vc, lens, block_k=256, window=w, sinks=sk)
    pool = PagePool(num_pages=16)
    cache = paged_from_dense(kc, vc, lens, pool, num_pages=16)
    got = paged_flash_decode(q, cache, window=w, sinks=sk)
    return got, want


@case("decode/softcap")
def _():
    q, kc, vc, lens, _ = _decode_setup()
    got = flash_decode(q, kc, vc, lens, block_k=256, softcap=10.0)
    with jax.default_matmul_precision("highest"):
        kx = jnp.repeat(kc, 2, axis=1)
        vx = jnp.repeat(vc, 2, axis=1)
        s = jnp.einsum("bhd,bhnd->bhn", q, kx) / 8.0
        s = 10.0 * jnp.tanh(s / 10.0)
        mask = jnp.arange(kc.shape[2])[None, None, :] < lens[:, None, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhn,bhnd->bhd", p, vx)
    return got, want


@case("decode/chunk verify == sequential decode (speculative)")
def _():
    from attention_tpu.ops.decode import flash_decode_chunk

    b, h, hkv, n, d, S = 2, 4, 2, 512, 64, 3
    lens0 = np.array([300, 140], np.int32)
    q = _arr(b, h, S, d)
    kc, vc = _arr(b, hkv, n, d), _arr(b, hkv, n, d)
    got = flash_decode_chunk(q, kc, vc, jnp.asarray(lens0 + S),
                             block_k=128)
    steps = [
        flash_decode(q[:, :, si], kc, vc, jnp.asarray(lens0 + si + 1),
                     block_k=128)
        for si in range(S)
    ]
    return got, jnp.stack(steps, axis=2)


@case("decode/chunk verify int8 + window+sinks")
def _():
    from attention_tpu.ops.quant import flash_decode_quantized_chunk

    b, h, hkv, n, d, S = 1, 4, 2, 512, 64, 3
    lens0 = np.array([300], np.int32)
    q = _arr(b, h, S, d)
    kc, vc = _arr(b, hkv, n, d), _arr(b, hkv, n, d)
    qkv = quantize_kv(kc, vc)
    kw = dict(block_k=128, window=64, sinks=2)
    got = flash_decode_quantized_chunk(q, qkv, jnp.asarray(lens0 + S),
                                       **kw)
    steps = [
        flash_decode_quantized(q[:, :, si], qkv,
                               jnp.asarray(lens0 + si + 1), **kw)
        for si in range(S)
    ]
    return got, jnp.stack(steps, axis=2), 5e-3  # int8 noise x2 paths


@case("decode/chunk verify paged (4-D q through the table)")
def _():
    from attention_tpu.ops.decode import flash_decode_chunk

    b, h, hkv, n, d, S = 2, 4, 2, 512, 64, 3
    lens = np.array([303, 143], np.int32)  # post-append lengths
    q = _arr(b, h, S, d)
    kc, vc = _arr(b, hkv, n, d), _arr(b, hkv, n, d)
    pool = PagePool(num_pages=2 * (n // 128))
    cache = paged_from_dense(kc, vc, jnp.asarray(lens), pool,
                             num_pages=pool.num_pages, page_size=128)
    got = paged_flash_decode(q, cache)
    want = flash_decode_chunk(q, kc, vc, jnp.asarray(lens), block_k=128)
    return got, want


@case("decode/int4 cache within its documented budget")
def _():
    from attention_tpu.ops.quant import flash_decode_int4, quantize_kv_int4

    b, h, hkv, n, d = 2, 4, 2, 512, 128
    lens = jnp.asarray([512, 300], jnp.int32)
    q = _arr(b, h, d)
    kc, vc = _arr(b, hkv, n, d), _arr(b, hkv, n, d)
    got = flash_decode_int4(q, quantize_kv_int4(kc, vc), lens,
                            block_k=128)
    want = flash_decode(q, kc, vc, lens, block_k=128)
    # int4's measured opt-in budget, NOT the ±0.02 contract
    # (quant.py::quantize_kv_int4)
    return got, want, 0.15


@case("decode/int4 token-paired layout == feature layout")
def _():
    from attention_tpu.ops.quant import (
        flash_decode_int4,
        flash_decode_int4_tok,
        quantize_kv_int4,
        quantize_kv_int4_tok,
    )

    b, h, hkv, n, d = 2, 4, 2, 512, 128
    lens = jnp.asarray([512, 300], jnp.int32)
    q = _arr(b, h, d)
    kc, vc = _arr(b, hkv, n, d), _arr(b, hkv, n, d)
    # the two layouts share quantization math exactly; on-chip they may
    # differ only by fp reassociation of the lane order
    got = flash_decode_int4_tok(q, quantize_kv_int4_tok(kc, vc), lens,
                                block_k=256)
    want = flash_decode_int4(q, quantize_kv_int4(kc, vc), lens,
                             block_k=256)
    return got, want, 1e-2


@case("decode/int4 token-paired windowed+sinks band")
def _():
    from attention_tpu.ops.quant import (
        flash_decode_int4,
        flash_decode_int4_tok,
        quantize_kv_int4,
        quantize_kv_int4_tok,
    )

    b, h, hkv, n, d = 2, 4, 2, 512, 128
    lens = jnp.asarray([512, 300], jnp.int32)
    q = _arr(b, h, d)
    kc, vc = _arr(b, hkv, n, d), _arr(b, hkv, n, d)
    # the [even|odd] column->token map must agree with the band keep
    # mask under real Mosaic lowering, not just interpret mode
    got = flash_decode_int4_tok(q, quantize_kv_int4_tok(kc, vc), lens,
                                block_k=256, window=128, sinks=4)
    want = flash_decode_int4(q, quantize_kv_int4(kc, vc), lens,
                             block_k=256, window=128, sinks=4)
    return got, want, 1e-2


@case("fwd/bound-max production dispatch (small shape -> online)")
def _():
    # Every other bound case pins _BOUND_MIN_SCORE_ELEMS = 0 so the
    # BOUND kernel itself is what lowers; this case restores the
    # PRODUCTION threshold so the small-shape bound->online static
    # resolution (`_flash_call`) — the path production max_mode="bound"
    # callers actually take below 24M score elements — is exercised on
    # real Mosaic too, not only in the CPU unit tests (ADVICE.md r5).
    # Distinct shape + cleared caches keep the pinned-off traces of the
    # other cases from being reused here.
    _flash_mod._BOUND_MIN_SCORE_ELEMS = _PROD_BOUND_MIN_SCORE_ELEMS
    jax.clear_caches()
    try:
        q, k, v = _arr(3, 448, 64), _arr(3, 448, 64), _arr(3, 448, 64)
        got = flash_attention(q, k, v, causal=True, max_mode="bound")
        want = _dense(q, k, v, causal=True)
    finally:
        _flash_mod._BOUND_MIN_SCORE_ELEMS = 0
        jax.clear_caches()
    return got, want


@case("fwd/bound guard demotes adversarial norms on-chip")
def _():
    d = 128
    qa = np.zeros((64, d), np.float32)
    qa[:, 0] = 45.0
    ka = np.zeros((64, d), np.float32)
    ka[0, 1] = 45.0  # orthogonal huge key: unguarded bound underflows
    va = RNG.standard_normal((64, d)).astype(np.float32)
    got = flash_attention(jnp.asarray(qa), jnp.asarray(ka),
                          jnp.asarray(va), max_mode="bound")
    want = flash_attention(jnp.asarray(qa), jnp.asarray(ka),
                           jnp.asarray(va))
    assert float(jnp.max(jnp.abs(got))) > 0.1, "demotion returned zeros"
    return got, want


# ------------------ ragged packed step (the engine) ------------------
# One packed mixed step through the page tables, vs the fp64 oracle, at
# every GQA group: the kernel's per-slot query tile starts at the span
# head rounded down to the 8-row sublane granule, and the span starts
# here (tokens 0..5 and 17 -> rows 0, g, 2g, ...) hit every misalignment
# a group below 8 can produce, and the last chunk hits the in-bounds
# clamp.

def _ragged_case(hq, hkv, dtype, d=128, page=128):
    from attention_tpu.ops.ragged_paged import (
        RaggedPagedStep,
        packed_bucket,
        ragged_paged_append,
        ragged_paged_attention,
        tile_tokens,
    )
    from attention_tpu.ops.reference import ragged_paged_reference

    # (pre-append kv_len, q_len) per slot, decode slots first; one
    # decode row crosses a page boundary, one chunk appends to history
    specs = [(37, 1), (128, 1), (300, 1), (5, 1), (255, 1),
             (90, 12), (0, 12)]
    num_decode = 5
    slots, max_pages = 8, 4
    group = hq // hkv
    total = sum(n for _, n in specs)
    q_tile = tile_tokens(packed_bucket(12, minimum=1), group)
    width = packed_bucket(max(total, q_tile))
    assert q_tile < width  # the tile start is dynamic, not pinned to 0
    table = np.full((slots, max_pages), -1, np.int32)
    kv_lens = np.zeros((slots,), np.int32)
    cu = np.zeros((slots + 1,), np.int32)
    tok_pos = np.zeros((width,), np.int32)
    tok_slot = np.full((width,), -1, np.int32)
    off = nxt = 0
    for s, (kv_pre, n) in enumerate(specs):
        npages = -(-(kv_pre + n) // page)
        table[s, :npages] = np.arange(nxt, nxt + npages)
        nxt += npages
        kv_lens[s] = kv_pre
        tok_pos[off:off + n] = np.arange(kv_pre, kv_pre + n)
        tok_slot[off:off + n] = s
        off += n
        cu[s + 1] = off
    cu[len(specs) + 1:] = off
    pool = (nxt + 1, hkv, page, d)
    dist = jnp.asarray([num_decode, len(specs)], jnp.int32)
    # V at quarter scale: a one-key span returns its V row verbatim, and
    # rounding |v| ~ 4 to a bf16 OUTPUT alone costs 0.016 of the 0.02
    cache = RaggedPagedStep(
        _arr(*pool).astype(dtype), (0.25 * _arr(*pool)).astype(dtype),
        jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(cu), dist,
        jnp.asarray(tok_pos), jnp.asarray(tok_slot),
        np.zeros((q_tile,), np.int32))
    cache = ragged_paged_append(
        cache, _arr(1, hkv, width, d).astype(dtype),
        (0.25 * _arr(1, hkv, width, d)).astype(dtype))
    q = _arr(1, hq, width, d).astype(dtype)
    got = ragged_paged_attention(q, cache)
    want = ragged_paged_reference(
        np.asarray(q, np.float32), np.asarray(cache.k_pool, np.float32),
        np.asarray(cache.v_pool, np.float32), table,
        np.asarray(cache.kv_lens), cu, np.asarray(dist))
    return got.astype(jnp.float32), want


for _hq, _hkv, _dt in [(8, 8, jnp.bfloat16), (8, 4, jnp.bfloat16),
                       (8, 2, jnp.bfloat16), (32, 4, jnp.bfloat16),
                       (8, 2, jnp.float32)]:
    case(f"ragged/packed mixed step {_hq}q{_hkv}kv "
         f"{jnp.dtype(_dt).name}")(
        lambda hq=_hq, hkv=_hkv, dt=_dt: _ragged_case(hq, hkv, dt))


# ------------- distributed arms on a real-chip mesh -------------
# (round-3 VERDICT missing #1: ring / kv-sharded / ulysses / CP train /
# serving had only ever executed on virtual CPU meshes.)  A 1-device
# mesh on the real chip runs the ACTUAL shard_map + collective + Mosaic
# composition path on hardware — the degenerate mesh is the analog of
# the reference's `mpirun -np 1`, which its frozen harness also had to
# pass (SURVEY §4: "single-rank mpirun -np 1 is the degenerate case").

def _mesh1(axis="sp"):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), (axis,))


@case("mesh/kv-sharded two-phase pmax+psum merge")
def _():
    from attention_tpu.parallel import kv_sharded_attention

    q, k, v = _arr(4, 256, 64), _arr(4, 256, 64), _arr(4, 256, 64)
    got = kv_sharded_attention(q, k, v, mesh=_mesh1("kv"), causal=True,
                               softcap=15.0)
    return got, _dense(q, k, v, causal=True, softcap=15.0)


@case("mesh/q-sharded replicated-KV arm")
def _():
    from attention_tpu.parallel import q_sharded_attention

    q, k, v = _arr(4, 256, 64), _arr(4, 256, 64), _arr(4, 256, 64)
    got = q_sharded_attention(q, k, v, mesh=_mesh1("kv"), causal=True)
    return got, _dense(q, k, v, causal=True)


@case("mesh/ring contiguous (ppermute schedule)")
def _():
    from attention_tpu.parallel import ring_attention

    q, k, v = _arr(2, 384, 64), _arr(2, 384, 64), _arr(2, 384, 64)
    got = ring_attention(q, k, v, mesh=_mesh1(), causal=True)
    return got, _dense(q, k, v, causal=True)


@case("mesh/ring zigzag (balanced causal schedule)")
def _():
    from attention_tpu.parallel import ring_attention

    q, k, v = _arr(2, 384, 64), _arr(2, 384, 64), _arr(2, 384, 64)
    got = ring_attention(q, k, v, mesh=_mesh1(), causal=True,
                         schedule="zigzag")
    return got, _dense(q, k, v, causal=True)


@case("mesh/ring differentiable (grads on-chip)")
def _():
    from attention_tpu.parallel.ring import ring_attention_diff

    q, k, v = _arr(2, 320, 64), _arr(2, 320, 64), _arr(2, 320, 64)
    wt = _arr(2, 320, 64)
    mesh = _mesh1()

    def floss(q, k, v):
        return jnp.sum(ring_attention_diff(q, k, v, mesh=mesh,
                                           causal=True) * wt)

    def dloss(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True) * wt)

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dloss, argnums=(0, 1, 2))(q, k, v)
    got = jnp.concatenate([g.reshape(-1) for g in gf])
    want = jnp.concatenate([g.reshape(-1) for g in gd])
    return got, want, 5e-2


@case("mesh/ulysses all-to-all")
def _():
    from attention_tpu.parallel import ulysses_attention

    q, k, v = _arr(4, 256, 64), _arr(4, 256, 64), _arr(4, 256, 64)
    got = ulysses_attention(q, k, v, mesh=_mesh1(), causal=True)
    return got, _dense(q, k, v, causal=True)


@case("mesh/cp attention fwd+grads (the training composition)")
def _():
    from attention_tpu.parallel.cp import cp_flash_attention

    q, k, v = _arr(4, 256, 64), _arr(2, 256, 64), _arr(2, 256, 64)
    wt = _arr(4, 256, 64)
    mesh = _mesh1()

    def floss(q, k, v):
        return jnp.sum(cp_flash_attention(q, k, v, mesh=mesh,
                                          causal=True) * wt)

    def dloss(q, k, v):
        return jnp.sum(_dense(q, k, v, causal=True) * wt)

    gf = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dloss, argnums=(0, 1, 2))(q, k, v)
    got = jnp.concatenate([g.reshape(-1) for g in gf])
    want = jnp.concatenate([g.reshape(-1) for g in gd])
    return got, want, 5e-2


@case("mesh/full sharded train step (loss == direct loss_fn)")
def _():
    from attention_tpu.models.train import (
        init_sharded,
        loss_fn,
        make_mesh_3d,
        make_train_step,
    )
    from attention_tpu.models.transformer import TinyDecoder

    mesh = make_mesh_3d(1)
    model = TinyDecoder(vocab=64, dim=64, depth=1, num_q_heads=8,
                        num_kv_heads=2, impl="flash", cp_axis="sp",
                        mesh=mesh, dtype=jnp.float32)
    params, optimizer, opt_state = init_sharded(model, mesh, batch=2,
                                                seq=64)
    tokens = jnp.asarray(RNG.integers(0, 64, (2, 65)), jnp.int32)
    want = loss_fn(params, model, tokens)  # before step donates params
    step = make_train_step(model, optimizer, mesh)
    params, opt_state, loss = step(params, opt_state, tokens)
    jax.block_until_ready(loss)
    return loss, want, 1e-4


@case("mesh/serving head-sharded prefill")
def _():
    q, k, v = _arr(2, 4, 256, 64), _arr(2, 2, 256, 64), _arr(2, 2, 256, 64)
    from attention_tpu.parallel import head_sharded_prefill

    got = head_sharded_prefill(q, k, v, mesh=_mesh1("tp"), causal=True)
    want = flash_attention(q, k, v, causal=True)
    return got, want


@case("mesh/serving head-sharded decode")
def _():
    from attention_tpu.parallel import head_sharded_decode

    q, kc, vc, lens, want = _decode_setup()
    got = head_sharded_decode(q, kc, vc, lens, mesh=_mesh1("tp"),
                              block_k=256)
    return got, want


@case("mesh/serving cache-sharded decode (two-phase merge)")
def _():
    from attention_tpu.parallel import cache_sharded_decode

    q, kc, vc, lens, _ = _decode_setup(b=2)
    got = cache_sharded_decode(q, kc, vc, jnp.int32(300), mesh=_mesh1())
    want = flash_decode(q, kc, vc, jnp.int32(300), block_k=256)
    return got, want


# ------------------- large-shape compile checks -------------------
# Tiny-shape numerics above can't catch scoped-VMEM overflows: the tile
# defaults only reach full size at real shapes (two compile-time OOMs
# were found this way in round 2 — partials with stats outputs, and the
# fp32 VJP).  These cases compile + run ONE call at the worst-case
# shapes for each default; correctness is covered by the tiny cases.

@case("compile/partials stats tile @16q4kv 8k")
def _():
    q, k, v = _arr(16, 8192, 128), _arr(4, 8192, 128), _arr(4, 8192, 128)
    o, m, l = flash_attention_partials(q, k, v, causal=True)
    return jnp.zeros(()), jnp.zeros(()), 1.0  # compiled + ran = pass


@case("compile/fp32 full vjp @16q4kv 8k")
def _():
    q, k, v = _arr(16, 8192, 128), _arr(4, 8192, 128), _arr(4, 8192, 128)
    g = jax.grad(lambda q: jnp.sum(flash_attention_diff(q, k, v,
                                                        causal=True)))(q)
    jax.block_until_ready(g)
    return jnp.zeros(()), jnp.zeros(()), 1.0


@case("compile/causal 32k big tile: bound == online")
def _():
    # value check at the REAL causal default tile (2048x2048): the
    # bound-max and online-max kernels are independent code paths whose
    # exact math agrees; bf16 rounding under different accumulation
    # orders lands at ~8e-3 at this scale (measured), so 1e-2 catches a
    # real divergence while the default 2e-2 contract would mask one
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (32768, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (32768, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (32768, 128), jnp.bfloat16)
    a = flash_attention(q, k, v, causal=True, max_mode="bound")
    b = flash_attention(q, k, v, causal=True, max_mode="online")
    return a.astype(jnp.float32), np.asarray(b, np.float32), 1e-2


@case("compile/bf16 vjp + big fwd tile @32q4kv 16k")
def _():
    q = _arr(32, 16384, 128).astype(jnp.bfloat16)
    k = _arr(4, 16384, 128).astype(jnp.bfloat16)
    v = _arr(4, 16384, 128).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)  # 2048x1024 tile
    g = jax.grad(lambda q: jnp.sum(flash_attention_diff(
        q, k, v, causal=True).astype(jnp.float32)))(q)
    jax.block_until_ready((out, g))
    return jnp.zeros(()), jnp.zeros(()), 1.0


def main() -> int:
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
        # this sweep validates Mosaic lowering, which only a real chip
        # proves: off-TPU every kernel would run interpreted
        print(f"tpu_smoke.py: {e}")
        return 2
    print(describe_run(device, cache_dir))
    # optional substring filters: `tpu_smoke.py int4 ring` runs only
    # cases whose name contains any argument (full sweep otherwise) —
    # for spot-checking one new case without the ~25-min full pass
    filters = sys.argv[1:]
    if any(a.startswith("-") for a in filters):
        # no flags exist; silently dropping a mistyped one would launch
        # the full ~25-min sweep the filter exists to avoid
        print("usage: tpu_smoke.py [name-substring ...]  "
              "(no flags; bare substrings filter cases)")
        return 1
    cases = ([c for c in CASES if any(f in c[0] for f in filters)]
             if filters else CASES)
    if filters and not cases:
        print(f"no case matches filters {filters}")
        return 1
    failures = []
    for name, fn in cases:
        try:
            res = fn()
            got, want = res[0], res[1]
            atol = res[2] if len(res) > 2 else 2e-2
            got = np.asarray(jax.block_until_ready(got), np.float64)
            want = np.asarray(want, np.float64)
            err = float(np.max(np.abs(got - want)))
            ok = err <= atol
            print(f"{'PASS' if ok else 'FAIL'} {name}: max|err|={err:.2e} "
                  f"(atol {atol:g})")
            if not ok:
                failures.append(name)
        except Exception as e:  # lowering failures land here
            print(f"FAIL {name}: {type(e).__name__}: {e}")
            failures.append(name)
    print(f"\n{len(cases) - len(failures)}/{len(cases)} variants green"
          + (f" (of {len(CASES)} total; filtered)" if filters else "")
          + (f"; FAILED: {failures}" if failures else ""))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
