"""`ops.ssm`: the ragged state-space scan kernel (interpreted on the
CPU) against the recurrence token by token, the float32 state, and the
biased convolution.  Few grid steps a case: the interpreter rewrites
every operand at each (PR 28's trap)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.ops.gated_delta import RaggedStateStep, ragged_causal_conv
from attention_tpu.ops.ssm import chunk_tokens, ragged_ssm_scan, ssm_scan

H, P, N, G = 4, 16, 32, 2


def _case(q_lens, kv_lens, q_tile, t_pad, seed=0, decay=0.5):
    rng = np.random.default_rng(seed)
    slots = len(q_lens)
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    f32 = np.float32
    x = rng.normal(size=(t_pad, H, P)).astype(f32)
    dt = (np.abs(rng.normal(size=(t_pad, H))) * 0.1).astype(f32)
    log_a = (-np.abs(rng.normal(size=(t_pad, H))) * decay).astype(f32)
    b = rng.normal(size=(t_pad, G, N)).astype(f32)
    c = rng.normal(size=(t_pad, G, N)).astype(f32)
    pool = rng.normal(size=(slots + 1, H, P, N)).astype(f32)
    rows = np.array([s if q_lens[s] else -1 for s in range(slots)], np.int32)
    slot = np.full((t_pad,), -1, np.int32)
    for s in range(slots):
        slot[cu[s]:cu[s + 1]] = s
    step = RaggedStateStep(
        jnp.asarray(pool), jnp.zeros((slots + 1, 3, 8)), jnp.asarray(rows),
        jnp.asarray(np.asarray(kv_lens, np.int32)), jnp.asarray(cu),
        jnp.asarray(slot), np.zeros((q_tile,), np.int32))
    return (x, dt, log_a, b, c), step, pool, cu


@pytest.mark.parametrize("q_lens, kv_lens, q_tile, t_pad", [
    ([1, 1, 1, 0, 1, 37], [5, 0, 9, 0, 3, 0], 48, 64),   # chunks of 16
    ([1, 1, 1], [5, 0, 9], 1, 8),                        # a tile of one
    ([1] * 11 + [5], [5, 0, 9] + [4] * 8 + [70], 8, 16),
    ([3, 70], [0, 128], 96, 96),                         # chunks of 32
    ([0, 0, 0], [5, 0, 9], 8, 8),                        # no token
], ids=["mixed", "decode_only", "decode_rows_share_a_block", "two_chunks",
        "empty"])
def test_the_kernel_matches_the_recurrence_token_by_token(
        q_lens, kv_lens, q_tile, t_pad):
    args, step, pool, cu = _case(q_lens, kv_lens, q_tile, t_pad)
    y, new_pool = ragged_ssm_scan(*args, step)
    y, new_pool = np.asarray(y), np.asarray(new_pool)
    for s, n in enumerate(q_lens):
        if not n:       # a slot without tokens keeps its row
            assert np.array_equal(new_pool[s], pool[s])
            continue
        span = slice(cu[s], cu[s + 1])
        # a request that starts at token 0 starts from a zero state
        state = None if kv_lens[s] == 0 else jnp.asarray(pool[s])
        want_y, want_s = ssm_scan(*(a[span] for a in args), state)
        np.testing.assert_allclose(y[span], want_y, atol=2e-5)
        np.testing.assert_allclose(new_pool[s], want_s, atol=2e-5)
    assert not y[cu[-1]:].any()                   # pad rows are zero
    assert np.array_equal(new_pool[-1], pool[-1])  # nobody's row


def test_a_bfloat16_state_fails_the_float32_check():
    """Over 70 tokens at a weak decay the state keeps what it took; a
    state rounded to bfloat16 after every token drifts by 1e-2, a
    hundred times the kernel's distance from the float32 recurrence."""
    args, step, _, _ = _case([70], [0], 96, 96, seed=3, decay=0.02)
    y, pool = ragged_ssm_scan(*args, step)
    span = slice(0, 70)
    exact_y, exact_s = ssm_scan(*(a[span] for a in args))
    low_y, low_s = ssm_scan(*(a[span] for a in args), keep=lambda s: s.astype(
        jnp.bfloat16).astype(jnp.float32))
    tol = 2e-5
    assert np.abs(np.asarray(y)[span] - exact_y).max() < tol
    assert np.abs(np.asarray(pool)[0] - exact_s).max() < tol
    assert np.abs(low_y - exact_y).max() > 50 * tol
    assert np.abs(low_s - exact_s).max() > 50 * tol
    assert pool.dtype == jnp.float32


def test_the_pool_must_be_float32_and_shaped_by_the_heads():
    args, step, _, _ = _case([1], [0], 8, 8)
    with pytest.raises(ValueError, match="float32"):
        ragged_ssm_scan(*args, step._replace(
            state_pool=step.state_pool.astype(jnp.bfloat16)))
    with pytest.raises(ValueError, match="packed rows disagree"):
        ragged_ssm_scan(args[0], args[1][:, :2], *args[2:], step)


@pytest.mark.parametrize("q_tile, chunk", [
    (1, 8), (4, 8), (8, 8), (24, 8), (48, 16), (96, 32), (192, 64),
    (128, 128), (256, 128)])
def test_chunk_tokens(q_tile, chunk):
    assert chunk_tokens(q_tile) == chunk


def test_the_convolution_takes_an_optional_bias():
    """``conv(x) + b`` on every real token; without the argument the
    call is the delta layers' own."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    cu = np.array([0, 1, 12], np.int32)
    slot = np.full((16,), -1, np.int32)
    slot[0], slot[1:12] = 0, 1
    step = RaggedStateStep(
        jnp.zeros((3, 1, 1, 1)), jnp.asarray(
            rng.normal(size=(3, 3, 8)).astype(np.float32)),
        jnp.array([0, 1], jnp.int32), jnp.array([7, 0], jnp.int32),
        jnp.asarray(cu), jnp.asarray(slot), np.zeros((8,), np.int32))
    plain, tails = ragged_causal_conv(x, w, step)
    biased, tails_b = ragged_causal_conv(x, w, step, bias)
    np.testing.assert_allclose(biased, plain + bias, atol=1e-6)
    assert np.array_equal(tails, tails_b)
    # the second slot starts a request: zeros before its span
    want = sum(np.pad(np.asarray(x[1:12]), ((3, 0), (0, 0)))[i:i + 11]
               * np.asarray(w[i]) for i in range(4)) + np.asarray(bias)
    np.testing.assert_allclose(biased[1:12], want, atol=1e-5)
