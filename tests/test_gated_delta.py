"""The ragged gated-delta-rule kernel (interpret mode) against the
recurrence token by token, and the packed causal convolution against a
plain one."""

import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.ops.gated_delta import (
    RaggedStateStep,
    chunk_tokens,
    gated_delta_scan,
    ragged_causal_conv,
    ragged_gated_delta,
)

H, DK, DV, ROWS = 2, 16, 32, 4
# float32 throughout: the chunked form and the recurrence differ by
# rounding only (a few 1e-7 a token, a few 1e-6 over a 70-token span); a
# state kept in bfloat16 is off by 1e-3 (checked below)
TOL = 2e-5


def _rows(rng, t, shared=0.0):
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((t, H, DK))) * DK ** -0.5
    k = unit(rng.standard_normal((t, H, DK))
             + shared * rng.standard_normal((1, H, DK)))
    v = rng.standard_normal((t, H, DV))
    log_a = -0.5 * np.exp(rng.standard_normal((t, H)))
    beta = 2.0 / (1.0 + np.exp(-rng.standard_normal((t, H))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, log_a, beta))


def _step(pool, spans, *, width, q_tile, channels=8):
    """A packed step from ``spans`` = [(tokens, pool row, computed)]."""
    slots = len(spans) + 1                       # one empty slot
    cu = np.zeros((slots + 1,), np.int32)
    rows = np.full((slots,), -1, np.int32)
    lens = np.zeros((slots,), np.int32)
    token_slot = np.full((width,), -1, np.int32)
    for s, (n, row, computed) in enumerate(spans):
        cu[s + 1] = cu[s] + n
        token_slot[cu[s]:cu[s + 1]] = s
        rows[s], lens[s] = row, computed
    cu[len(spans) + 1:] = cu[len(spans)]
    return RaggedStateStep(
        jnp.asarray(pool), jnp.zeros((ROWS + 1, 3, channels), jnp.float32),
        jnp.asarray(rows), jnp.asarray(lens), jnp.asarray(cu),
        jnp.asarray(token_slot), jnp.zeros((q_tile,), jnp.int32)), cu


@pytest.mark.parametrize("spans, width, q_tile", [
    ([(1, 2, 5), (1, 0, 9)], 8, 8),                 # decode rows only
    ([(64, 1, 0)], 64, 64),                         # one whole chunk
    ([(1, 2, 5), (1, 0, 9), (70, 3, 0)], 96, 96),   # a ragged tail, mixed
    ([(1, 3, 7), (150, 1, 128)], 192, 192),         # chunks of 64, resumed
    ([(5, 2, 7), (8, 0, 3), (40, 1, 0)], 64, 48),   # short spans, chunks of 16
], ids=["spans-of-1", "whole-chunk", "ragged-tail", "three-chunks",
        "short-spans"])
def test_kernel_matches_the_recurrence(spans, width, q_tile):
    rng = np.random.default_rng(len(spans) + width)
    data = _rows(rng, width)
    pool = rng.standard_normal((ROWS + 1, H, DK, DV)).astype(np.float32)
    step, cu = _step(pool, spans, width=width, q_tile=q_tile)
    o, new_pool = ragged_gated_delta(*data, step)
    o, new_pool = np.asarray(o), np.asarray(new_pool)
    touched = set()
    for s, (n, row, computed) in enumerate(spans):
        a, b = cu[s], cu[s + 1]
        want_o, want_s = gated_delta_scan(
            *(x[a:b] for x in data),
            None if computed == 0 else jnp.asarray(pool[row]))
        np.testing.assert_allclose(o[a:b], want_o, atol=TOL)
        np.testing.assert_allclose(new_pool[row], want_s, atol=TOL)
        touched.add(row)
    # rows no slot of the step owns, and the packed axis's pad rows
    for row in set(range(ROWS)) - touched:
        np.testing.assert_array_equal(new_pool[row], pool[row])
    assert not o[cu[-1]:].any()


def test_correlated_keys_do_not_cancel_in_the_chunked_form():
    """Keys that share a direction (what SiLU after the convolution
    gives) with beta near 2: the inverse of a whole 64-token chunk as a
    product of powers is off by 0.1 to 1e23 here; solved a block of
    rows at a time the chunk stays at rounding."""
    rng = np.random.default_rng(11)
    data = _rows(rng, 192, shared=3.0)
    pool = np.zeros((ROWS + 1, H, DK, DV), np.float32)
    step, _ = _step(pool, [(192, 0, 0)], width=192, q_tile=192)
    o, new_pool = ragged_gated_delta(*data, step)
    want_o, want_s = gated_delta_scan(*data)
    np.testing.assert_allclose(o, want_o, atol=TOL)
    np.testing.assert_allclose(np.asarray(new_pool)[0], want_s, atol=TOL)


def test_pad_rows_of_a_chunk_leave_the_state_as_it_was():
    """A span of 3 tokens in a tile of 8: the 5 pad rows of the chunk
    must not move the state (it equals the recurrence over 3 tokens),
    and a bfloat16 state would be caught by the tolerance."""
    rng = np.random.default_rng(7)
    data = _rows(rng, 8)
    pool = rng.standard_normal((ROWS + 1, H, DK, DV)).astype(np.float32)
    step, _ = _step(pool, [(3, 1, 11)], width=8, q_tile=8)
    _, new_pool = ragged_gated_delta(*data, step)
    _, want = gated_delta_scan(*(x[:3] for x in data), jnp.asarray(pool[1]))
    np.testing.assert_allclose(np.asarray(new_pool)[1], want, atol=TOL)
    rounded = np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)
    assert np.abs(rounded - np.asarray(want)).max() > 10 * TOL


def test_chunk_sizes_divide_every_query_tile():
    assert [chunk_tokens(t) for t in (8, 16, 24, 32, 48, 64, 96, 128, 192,
                                      256)] == [8, 16, 8, 32, 16, 64, 32,
                                                64, 64, 64]
    with pytest.raises(ValueError, match="multiple of 8"):
        chunk_tokens(12)


def test_packed_convolution_reads_the_tail_and_writes_it_back():
    rng = np.random.default_rng(5)
    channels, taps, width = 8, 4, 16
    x = jnp.asarray(rng.standard_normal((width, channels)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((taps, channels)), jnp.float32)
    tails = rng.standard_normal((ROWS + 1, taps - 1, channels)).astype(
        np.float32)
    pool = np.zeros((ROWS + 1, H, DK, DV), np.float32)
    # a decode row that continues row 2, a fresh 2-token span (shorter
    # than the taps) on row 0, a resumed 9-token span on row 3
    spans = [(1, 2, 5), (2, 0, 0), (9, 3, 64)]
    step, cu = _step(pool, spans, width=width, q_tile=16)
    step = step._replace(conv_pool=jnp.asarray(tails))
    y, new_tails = ragged_causal_conv(x, w, step)
    y, new_tails = np.asarray(y), np.asarray(new_tails)
    for s, (n, row, computed) in enumerate(spans):
        before = np.zeros_like(tails[row]) if computed == 0 else tails[row]
        seq = np.concatenate([before, np.asarray(x[cu[s]:cu[s + 1]])])
        want = sum(seq[i:i + n] * np.asarray(w[i]) for i in range(taps))
        np.testing.assert_allclose(y[cu[s]:cu[s + 1]], want, atol=1e-6)
        np.testing.assert_allclose(new_tails[row], seq[-(taps - 1):],
                                   atol=0)
    np.testing.assert_array_equal(new_tails[1], tails[1])  # nobody's row
