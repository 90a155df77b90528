"""int8 KV-cache decode tests: quantization round-trip, kernel accuracy
vs the fp oracle, incremental updates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.ops.decode import flash_decode
from attention_tpu.ops.quant import (
    QuantizedKV,
    flash_decode_quantized,
    quantize_kv,
    update_quantized_kv,
)


def _caches(rng, b, hkv, n, d):
    kc = rng.standard_normal((b, hkv, n, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, n, d)).astype(np.float32)
    return jnp.asarray(kc), jnp.asarray(vc)


def test_quantize_roundtrip_error_bounded(rng):
    kc, vc = _caches(rng, 2, 2, 256, 64)
    qkv = quantize_kv(kc, vc)
    assert qkv.k_q.dtype == jnp.int8
    assert qkv.k_q.shape == (2, 2, 256, 64)
    assert qkv.k_scale.shape == (2, 2, 8, 256)
    assert qkv.capacity == 256 and qkv.head_dim == 64
    # round-trip bound: per-token absmax gives |x - deq(x)| <= scale/2
    # = amax/254 (scale rows identical across the 8 replicated sublanes)
    k_q = np.asarray(qkv.k_q, np.int32)
    scale = np.asarray(qkv.k_scale[:, :, 0, :])  # (b, hkv, n)
    deq = k_q * scale[..., None]
    amax = np.max(np.abs(np.asarray(kc)), axis=-1, keepdims=True)
    assert np.all(np.abs(deq - np.asarray(kc)) <= amax / 254 + 1e-6)


@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
def test_quantized_decode_close_to_fp(rng, h, hkv):
    b, n, d = 2, 512, 64
    kc, vc = _caches(rng, b, hkv, n, d)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    lens = jnp.asarray([512, 100], jnp.int32)
    fp = np.asarray(flash_decode(q, kc, vc, lens, block_k=128))
    qt = np.asarray(flash_decode_quantized(
        q, quantize_kv(kc, vc), lens, block_k=128
    ), np.float32)
    # int8 per-token quantization inside the reference's ±0.02 contract
    np.testing.assert_allclose(qt, fp, atol=0.02)


def test_quantized_decode_empty_cache(rng):
    kc, vc = _caches(rng, 1, 2, 128, 64)
    q = jnp.asarray(rng.standard_normal((1, 2, 64)), jnp.float32)
    out = flash_decode_quantized(q, quantize_kv(kc, vc), 0)
    assert bool(jnp.all(out == 0.0))


def test_incremental_update_matches_full_quantization(rng):
    b, hkv, n, d = 1, 2, 256, 32
    kc, vc = _caches(rng, b, hkv, n, d)
    # quantize the first 100 rows, then append rows 100:103 incrementally
    base = quantize_kv(kc.at[:, :, 100:].set(0.0), vc.at[:, :, 100:].set(0.0))
    upd = update_quantized_kv(
        base, kc[:, :, 100:103], vc[:, :, 100:103], jnp.asarray(100)
    )
    full = quantize_kv(kc.at[:, :, 103:].set(0.0), vc.at[:, :, 103:].set(0.0))
    np.testing.assert_array_equal(np.asarray(upd.k_q[:, :, :103]),
                                  np.asarray(full.k_q[:, :, :103]))
    np.testing.assert_allclose(np.asarray(upd.k_scale[..., :103]),
                               np.asarray(full.k_scale[..., :103]))
    q = jnp.asarray(rng.standard_normal((b, hkv, d)), jnp.float32)
    got = np.asarray(flash_decode_quantized(q, upd, 103, block_k=128),
                     np.float32)
    want = np.asarray(flash_decode(q, kc, vc, 103, block_k=128))
    np.testing.assert_allclose(got, want, atol=0.02)


def test_quantized_decode_shape_validation(rng):
    kc, vc = _caches(rng, 1, 2, 128, 64)
    qkv = quantize_kv(kc, vc)
    q = jnp.zeros((1, 2, 32), jnp.float32)  # wrong d
    with pytest.raises(ValueError, match="inconsistent"):
        flash_decode_quantized(q, qkv, 10)


def test_model_int8_decode_close_to_fp(rng):
    """Teacher-forced int8-cache decode tracks the bf16-cache logits."""
    from attention_tpu.models import TinyDecoder

    model = TinyDecoder(vocab=61, dim=64, depth=2, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 61, (2, 9)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    fp_caches = model.init_caches(batch=2, capacity=128)
    l_fp, fp_caches = model.apply({"params": params}, tokens[:, :5], fp_caches)
    q_caches = tuple(c.quantize() for c in fp_caches)
    for t in range(5, 9):
        step = tokens[:, t : t + 1]
        lf, fp_caches = model.apply({"params": params}, step, fp_caches)
        lq, q_caches = model.apply({"params": params}, step, q_caches)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(lf),
                                   atol=0.05, rtol=0.05)
    assert int(q_caches[0].length) == 9


def test_generate_int8_cache_runs_and_matches(rng):
    from attention_tpu.models import TinyDecoder, generate

    model = TinyDecoder(vocab=61, dim=64, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    prompt = jnp.asarray(rng.integers(0, 61, (2, 6)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    fp = np.asarray(generate(model, params, prompt, steps=4))
    q8 = np.asarray(generate(model, params, prompt, steps=4, int8_cache=True))
    # greedy argmax over well-separated random logits: tokens match
    np.testing.assert_array_equal(q8, fp)


def test_quant_cache_chunked_append_and_xla_reject(rng):
    """Round 5: S > 1 on the int8 cache is the speculative-verify chunk
    path (was a ValueError through round 4) — its logits must match the
    same tokens fed one at a time.  The xla impl still has no
    quantized-cache path and must reject loudly."""
    from attention_tpu.models import TinyDecoder

    model = TinyDecoder(vocab=31, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 31, (1, 4)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    caches = model.init_caches(batch=1, capacity=128)
    _, caches = model.apply({"params": params}, tokens[:, :1], caches)
    qcaches = tuple(c.quantize() for c in caches)
    chunk_logits, _ = model.apply(
        {"params": params}, tokens[:, 1:4], qcaches)
    step_caches = qcaches
    for i in range(1, 4):
        step_l, step_caches = model.apply(
            {"params": params}, tokens[:, i:i + 1], step_caches)
        np.testing.assert_allclose(
            np.asarray(chunk_logits[:, i - 1]), np.asarray(step_l[:, 0]),
            atol=1e-4,
        )

    xla_model = TinyDecoder(vocab=31, dim=32, depth=1, num_q_heads=4,
                            num_kv_heads=2, impl="xla", dtype=jnp.float32)
    with pytest.raises(ValueError, match="quantized-cache"):
        xla_model.apply({"params": params}, tokens[:, 1:2], qcaches)


def test_generate_int8_rejects_xla_impl_up_front(rng):
    from attention_tpu.models import TinyDecoder, generate

    model = TinyDecoder(vocab=31, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="xla", dtype=jnp.float32)
    prompt = jnp.zeros((1, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    with pytest.raises(ValueError, match="int8_cache requires"):
        generate(model, params, prompt, steps=2, int8_cache=True)


@pytest.mark.parametrize("sinks", [None, 4])
def test_quantized_decode_window_matches_bf16(rng, sinks):
    """int8 windowed (+sinks) decode == bf16 windowed decode within
    quantization error, ragged lengths."""
    b, h, hkv, n, d, w = 3, 4, 2, 512, 64, 150
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, hkv, n, d)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((b, hkv, n, d)), jnp.bfloat16)
    lens = jnp.asarray([512, 100, 300], jnp.int32)
    want = np.asarray(flash_decode(q.astype(jnp.bfloat16), kc, vc, lens,
                                   block_k=128, window=w, sinks=sinks),
                      np.float32)
    got = np.asarray(flash_decode_quantized(
        q.astype(jnp.bfloat16), quantize_kv(kc, vc), lens, block_k=128,
        window=w, sinks=sinks), np.float32)
    np.testing.assert_allclose(got, want, atol=3e-2)


def test_int8_windowed_model_matches_bf16_logits(rng):
    """Windowed (+sinks) decode on the int8 cache: teacher-forced
    per-step logits match the bf16 cache within quantization error.
    (Token-exact generation comparison is flaky: untrained weights
    produce near-tie logits that int8 noise flips.)"""
    from attention_tpu.models import TinyDecoder

    model = TinyDecoder(vocab=61, dim=64, depth=2, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32,
                        window=32, attn_sinks=4)
    prompt = jnp.asarray(rng.integers(0, 61, (2, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    full = model.init_caches(batch=2, capacity=128)
    _, full = model.apply({"params": params}, prompt, full)
    quant = tuple(c.quantize() for c in full)
    toks = jnp.asarray(rng.integers(0, 61, (2, 48)), jnp.int32)
    for t in range(toks.shape[1]):
        step = toks[:, t : t + 1]
        lf, full = model.apply({"params": params}, step, full)
        lq, quant = model.apply({"params": params}, step, quant)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(lf),
                                   atol=8e-2, rtol=5e-2,
                                   err_msg=f"step {t}")


def test_int8_rope_sinks_window_matches_bf16_logits(rng):
    """rope + sinks + window on the int8 cache: the pinned sink rows are
    dequantized, re-rotated to their in-cache positions, and
    requantized on a read copy each step — teacher-forced logits match
    the bf16 cache within (double-)quantization error, far past the
    window."""
    from attention_tpu.models import TinyDecoder

    model = TinyDecoder(vocab=61, dim=64, depth=2, num_q_heads=4,
                        num_kv_heads=2, impl="flash", dtype=jnp.float32,
                        window=32, attn_sinks=4, rope=True)
    prompt = jnp.asarray(rng.integers(0, 61, (2, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    full = model.init_caches(batch=2, capacity=128)
    _, full = model.apply({"params": params}, prompt, full)
    quant = tuple(c.quantize() for c in full)
    toks = jnp.asarray(rng.integers(0, 61, (2, 60)), jnp.int32)
    for t in range(toks.shape[1]):
        step = toks[:, t : t + 1]
        lf, full = model.apply({"params": params}, step, full)
        lq, quant = model.apply({"params": params}, step, quant)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(lf),
                                   atol=1e-1, rtol=5e-2,
                                   err_msg=f"step {t}")


def test_quantized_chunk_equals_sequential_decode(rng):
    """The int8 speculative-verify chunk kernel must equal S sequential
    quantized decode steps over the same cache rows."""
    from attention_tpu.ops.quant import flash_decode_quantized_chunk

    b, h, hkv, n, d, s_chunk = 2, 8, 4, 256, 64, 4
    lens0 = np.array([50, 7], np.int32)
    kc, vc = _caches(rng, b, hkv, n, d)
    qkv = quantize_kv(kc, vc)
    q = jnp.asarray(
        rng.standard_normal((b, h, s_chunk, d)), jnp.float32
    )
    new_lens = jnp.asarray(lens0 + s_chunk)
    got = np.asarray(flash_decode_quantized_chunk(
        q, qkv, new_lens, block_k=128,
    ))
    for si in range(s_chunk):
        step = np.asarray(flash_decode_quantized(
            q[:, :, si], qkv, jnp.asarray(lens0 + si + 1), block_k=128,
        ))
        np.testing.assert_allclose(got[:, :, si], step, atol=2e-3)


def test_quantized_chunk_windowed(rng):
    """Chunk verify with per-row window+sinks bands on the int8 cache."""
    from attention_tpu.ops.quant import flash_decode_quantized_chunk

    b, h, hkv, n, d, s_chunk = 1, 4, 2, 256, 64, 3
    lens0 = np.array([120], np.int32)
    kc, vc = _caches(rng, b, hkv, n, d)
    qkv = quantize_kv(kc, vc)
    q = jnp.asarray(rng.standard_normal((b, h, s_chunk, d)), jnp.float32)
    kw = dict(window=32, sinks=2, block_k=128)
    got = np.asarray(flash_decode_quantized_chunk(
        q, qkv, jnp.asarray(lens0 + s_chunk), **kw,
    ))
    for si in range(s_chunk):
        step = np.asarray(flash_decode_quantized(
            q[:, :, si], qkv, jnp.asarray(lens0 + si + 1), **kw,
        ))
        np.testing.assert_allclose(got[:, :, si], step, atol=2e-3)


def test_int4_roundtrip_and_unpack_order(rng):
    """Nibble packing: unpack(pack(x)) == round(x/scale) with features
    in NATURAL order (lo half ++ hi half)."""
    from attention_tpu.ops.quant import (
        Int4KV,
        _quant_rows_int4,
        quantize_kv_int4,
    )

    x = jnp.asarray(rng.standard_normal((1, 1, 8, 16)), jnp.float32)
    packed, scale = _quant_rows_int4(x)
    assert packed.shape == (1, 1, 8, 8) and packed.dtype == jnp.int8
    lo = np.right_shift(np.left_shift(np.asarray(packed), 4), 4)
    hi = np.right_shift(np.asarray(packed), 4)
    unpacked = np.concatenate([lo, hi], axis=-1).astype(np.float32)
    want = np.clip(np.round(np.asarray(x) / np.asarray(
        scale[..., 0, :, None])), -7, 7)
    np.testing.assert_array_equal(unpacked, want)
    kc, vc = _caches(rng, 1, 2, 128, 64)
    c4 = quantize_kv_int4(kc, vc)
    assert isinstance(c4, Int4KV)
    assert c4.head_dim == 64 and c4.capacity == 128
    # dequantized error bounded by one nibble step per element
    deq = np.concatenate([
        np.right_shift(np.left_shift(np.asarray(c4.k_q), 4), 4),
        np.right_shift(np.asarray(c4.k_q), 4),
    ], axis=-1) * np.asarray(c4.k_scale)[:, :, 0, :, None]
    step = np.asarray(c4.k_scale)[:, :, 0, :, None]
    assert np.all(np.abs(deq - np.asarray(kc)) <= 0.5 * step + 1e-6)


def test_int4_decode_close_to_fp(rng):
    """int4 decode vs the bf16 decode kernel — pins the MEASURED error
    budget: ~4-8e-2 max abs on unit-normal inputs at d=64/128 (int8 is
    ~2e-3 here), i.e. int4 does NOT meet the ±0.02 harness contract —
    it is the documented opt-in bytes/quality trade (see
    `quantize_kv_int4`)."""
    from attention_tpu.ops.quant import flash_decode_int4, quantize_kv_int4

    for d in (64, 128):
        b, h, hkv, n = 2, 8, 4, 512
        lens = np.array([512, 300], np.int32)
        kc, vc = _caches(rng, b, hkv, n, d)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        want = np.asarray(flash_decode(
            q.astype(jnp.bfloat16), kc.astype(jnp.bfloat16),
            vc.astype(jnp.bfloat16), jnp.asarray(lens),
            block_k=128)).astype(np.float32)
        got = np.asarray(flash_decode_int4(
            q, quantize_kv_int4(kc, vc), jnp.asarray(lens),
            block_k=128)).astype(np.float32)
        err = np.max(np.abs(got - want))
        # regression rail at the measured budget's edge; a pass at the
        # strict 0.02 contract would mean the budget doc is stale
        assert err < 0.15, f"int4 error regressed: {err}"


def test_int4_decode_windowed_and_empty(rng):
    from attention_tpu.ops.quant import flash_decode_int4, quantize_kv_int4

    b, h, hkv, n, d = 2, 4, 2, 256, 64
    kc, vc = _caches(rng, b, hkv, n, d)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    c4 = quantize_kv_int4(kc, vc)
    lens = jnp.asarray([200, 64], jnp.int32)
    got = np.asarray(flash_decode_int4(q, c4, lens, block_k=128,
                                       window=32, sinks=2))
    want = np.asarray(flash_decode_quantized(
        q, quantize_kv(kc, vc), lens, block_k=128, window=32, sinks=2))
    # int4-vs-int8 difference at the measured int4 budget; windowed
    # reads average over ~window tokens instead of the whole prefix, so
    # the quantization noise averages down LESS than the full-cache
    # case (measured ~0.16 here vs ~0.08 full) — the budget scales with
    # 1/sqrt(tokens-attended) (module docstrings)
    assert np.max(np.abs(got.astype(np.float32)
                         - want.astype(np.float32))) < 0.25
    zero = np.asarray(flash_decode_int4(
        q, c4, jnp.zeros((b,), jnp.int32), block_k=128))
    assert np.all(zero == 0)


def test_int4_tok_roundtrip_layout(rng):
    """Token-paired packing: byte row r of (B, Hkv, N//2, d) holds token
    2r (low nibble) and 2r+1 (high nibble) per feature; scales ship
    even/odd as sublane bands 0-7 / 8-15 of (B, Hkv, 16, N//2)."""
    from attention_tpu.ops.quant import (
        Int4TokKV,
        _quant_rows_int4_tok,
        quantize_kv_int4_tok,
    )

    x = jnp.asarray(rng.standard_normal((1, 1, 16, 8)), jnp.float32)
    packed, scales = _quant_rows_int4_tok(x)
    assert packed.shape == (1, 1, 8, 8) and packed.dtype == jnp.int8
    assert scales.shape == (1, 1, 16, 8)
    lo = np.right_shift(np.left_shift(np.asarray(packed), 4), 4)
    hi = np.right_shift(np.asarray(packed), 4)
    want = np.clip(np.round(
        np.asarray(x)
        / np.asarray(jnp.concatenate(
            [scales[..., :1, :], scales[..., 8:9, :]], axis=-2)
        ).transpose(0, 1, 3, 2).reshape(1, 1, 16, 1)), -7, 7)
    np.testing.assert_array_equal(lo, want[..., 0::2, :])
    np.testing.assert_array_equal(hi, want[..., 1::2, :])
    kc, vc = _caches(rng, 1, 2, 256, 64)
    c4 = quantize_kv_int4_tok(kc, vc)
    assert isinstance(c4, Int4TokKV)
    assert c4.head_dim == 64 and c4.capacity == 256


def test_int4_tok_matches_feature_layout(rng):
    """The two int4 layouts share quantization math EXACTLY, so their
    decode outputs must agree to fp32 roundoff across plain,
    windowed+sinks, softcap, ragged, and empty-length calls — the
    layout change is invisible to numerics (scripts/int4_pack_exp.py
    measures the latency side: 0.402 ms token-paired vs 0.748
    feature-dim vs 0.445 int8 at the bench decode shape)."""
    from attention_tpu.ops.quant import (
        flash_decode_int4,
        flash_decode_int4_tok,
        quantize_kv_int4,
        quantize_kv_int4_tok,
    )

    b, h, hkv, n, d = 2, 8, 2, 512, 128
    kc, vc = _caches(rng, b, hkv, n, d)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    cf = quantize_kv_int4(kc, vc)
    ct = quantize_kv_int4_tok(kc, vc)
    lens = jnp.asarray([512, 301], jnp.int32)
    for kw in (
        {},
        {"window": 128, "sinks": 4},
        {"softcap": 30.0},
    ):
        want = np.asarray(flash_decode_int4(q, cf, lens, block_k=256, **kw))
        got = np.asarray(flash_decode_int4_tok(q, ct, lens, block_k=256,
                                               **kw))
        # NOT bitwise: the layouts contract lanes in different orders
        # (natural vs [even|odd] token order), and identical fp sums
        # across reduction orders are an XLA implementation detail that
        # can change with backend/version (ADVICE.md round 5); the
        # shared quantization math pins them to fp32 roundoff.
        np.testing.assert_allclose(got, want, atol=1e-6)
    zero = np.asarray(flash_decode_int4_tok(
        q, ct, jnp.zeros((b,), jnp.int32), block_k=256))
    assert np.all(zero == 0)
    # default block resolution must also work on a small cache
    full = np.asarray(flash_decode_int4_tok(q, ct, lens))
    np.testing.assert_allclose(
        full, np.asarray(flash_decode_int4(q, cf, lens)), atol=1e-6)


def test_int4_tok_rejects_bad_blocks_and_shapes(rng):
    from attention_tpu.ops.quant import (
        flash_decode_int4_tok,
        quantize_kv_int4_tok,
    )

    # capacities with no 256-multiple block (N ≡ 128 mod 256) fail at
    # CACHE BUILD time with a capacity-phrased error — not at decode
    kc, vc = _caches(rng, 1, 2, 128, 64)
    with pytest.raises(ValueError, match="256-multiple cache capacity"):
        quantize_kv_int4_tok(kc, vc)
    # a too-small explicit block resolves UP to the minimal valid 256
    # (block_k is a "want", as in decode._pick_block_k), and awkward
    # capacities whose 128-stepped pick would land on an odd
    # 128-multiple (4864 -> 2432) resolve to a true 256-divisor
    from attention_tpu.ops.quant import _pick_block_tok

    assert _pick_block_tok(256, 128) == 256
    assert _pick_block_tok(4864, 4096) == 256  # 4864 = 256 * 19
    assert _pick_block_tok(4096, 16384) == 4096
    kc, vc = _caches(rng, 1, 2, 256, 64)
    c4 = quantize_kv_int4_tok(kc, vc)
    q = jnp.asarray(rng.standard_normal((1, 4, 64)), jnp.float32)
    lens = jnp.asarray([100], jnp.int32)
    got = np.asarray(flash_decode_int4_tok(q, c4, lens, block_k=128))
    want = np.asarray(flash_decode_int4_tok(q, c4, lens, block_k=256))
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="must be even"):
        from attention_tpu.ops.quant import _quant_rows_int4_tok

        _quant_rows_int4_tok(jnp.zeros((1, 1, 3, 8)))
