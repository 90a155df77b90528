"""A learned choice of keys inside paged attention: the selector's
scores over its own paged keys against the equation, the exact top-k
rule (ties to the lower position), the ragged kernel attending the
chosen keys alone, and the count of what its mask let through."""

import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    ragged_paged_append,
    ragged_paged_attention,
)
from attention_tpu.ops.sparse_index import select_keys

PAGE, SLOTS, MAX_PAGES, POOL = 128, 5, 4, 24
HEADS, D, DV = 8, 48, 32
IH, ID = 4, 16          # the selector's heads and key width


def _step(rng, q_lens, kv_before, q_tile, width, index=None,
          max_pages=MAX_PAGES):
    """A packed step over a latent pool and the selector's pool beside
    it, the same page ids in both."""
    n = len(q_lens)
    table = -np.ones((SLOTS, max_pages), np.int32)
    table[:n] = rng.permutation(POOL)[:n * max_pages].reshape(n, max_pages)
    cu = np.zeros(SLOTS + 1, np.int32)
    cu[1:n + 1] = np.cumsum(q_lens)
    cu[n + 1:] = cu[n]
    kv = np.zeros(SLOTS, np.int32)
    kv[:n] = kv_before
    pos, slot = np.zeros(width, np.int32), -np.ones(width, np.int32)
    for s, length in enumerate(q_lens):
        pos[cu[s]:cu[s + 1]] = kv[s] + np.arange(length)
        slot[cu[s]:cu[s + 1]] = s
    pool = jnp.asarray(rng.standard_normal((POOL, 1, PAGE, D)), jnp.float32)
    if index is None:
        index = rng.standard_normal((POOL, 1, PAGE, ID))
    return RaggedPagedStep(
        pool, None, jnp.asarray(table), jnp.asarray(kv), jnp.asarray(cu),
        jnp.asarray([sum(1 for q in q_lens if q == 1), n], jnp.int32),
        jnp.asarray(pos), jnp.asarray(slot), np.zeros((q_tile,), np.int32),
        jnp.asarray(index, jnp.float32))


def _rows(pool, table_row):
    return np.concatenate([np.asarray(pool, np.float32)[p, 0]
                           for p in table_row])


def _chosen(cache, q_idx, w_idx, q_lens, top_k):
    """The rule, written out: per packed token the scores of every key
    it sees and the set it attends (stable sort: ties to the lower
    position)."""
    table, cu = np.asarray(cache.page_table), np.asarray(cache.cu_q_lens)
    after = np.asarray(cache.kv_lens)
    scores, sets = {}, {}
    for s, length in enumerate(q_lens):
        keys = _rows(cache.index_pool, table[s])
        for t in range(length):
            reach = after[s] - length + t + 1
            row = cu[s] + t
            each = np.maximum(np.asarray(q_idx[row], np.float32)
                              @ keys[:reach].T, 0.0)
            scores[row] = (np.asarray(w_idx[row], np.float32)[:, None]
                           * each).sum(0)
            order = np.argsort(-scores[row], kind="stable")
            sets[row] = np.sort(order[:min(top_k, reach)])
    return scores, sets


def _kept(select, cache, q_lens, group):
    """`select_keys`' result as a set of positions a packed token."""
    from attention_tpu.ops.ragged_paged import row_block_list, row_block_shape

    bt, blocks = row_block_shape(cache.q_tile, group)
    listed = row_block_list(
        cache.kv_lens, cache.cu_q_lens, cache.distribution,
        max_pages=cache.page_table.shape[1], page=PAGE, block_tokens=bt,
        blocks=blocks,
        width=cache.token_slot.shape[0])
    cu = np.asarray(cache.cu_q_lens)
    out = {}
    for g in range(int(listed.live)):
        s, b = int(listed.slot[g]), int(listed.block[g])
        for u in range(min(bt, q_lens[s] - b * bt)):
            out[cu[s] + b * bt + u] = np.nonzero(
                np.asarray(select[g, u]))[0]
    return out


CASES = [
    ([1, 1, 1], [5, 130, 300], 1, 8),
    ([1, 1, 200], [5, 130, 77], 256, 256),
    ([1, 37], [400, 0], 64, 64),
]


@pytest.mark.parametrize("q_lens, kv_before, q_tile, width", CASES)
@pytest.mark.parametrize("top_k", [16, 2048])
def test_every_token_keeps_exactly_the_rules_keys(q_lens, kv_before, q_tile,
                                                  width, top_k):
    """top 16 drops keys for every row that sees more than 16; top
    2,048 keeps every key a row sees."""
    rng = np.random.default_rng(2)
    cache = _step(rng, q_lens, kv_before, q_tile, width)
    q_idx = jnp.asarray(rng.standard_normal((width, IH, ID)), jnp.float32)
    w_idx = jnp.asarray(rng.standard_normal((width, IH)), jnp.float32)
    new = rng.standard_normal((1, 1, width, D))
    new_i = rng.standard_normal((1, 1, width, ID))
    cache = ragged_paged_append(
        cache, jnp.asarray(new, jnp.float32),
        index_new=jnp.asarray(new_i, jnp.float32))
    select = select_keys(q_idx, w_idx, cache, top_k=top_k, group=HEADS)
    got = _kept(select, cache, q_lens, HEADS)
    _, want = _chosen(cache, q_idx, w_idx, q_lens, top_k)
    assert sorted(got) == sorted(want)
    for row in want:
        np.testing.assert_array_equal(got[row], want[row], err_msg=str(row))


def test_a_table_of_several_items_a_slot():
    """A table row of 6 entries is three scoring items of two pages
    (6 does not divide by four), the last of one slot half claimed."""
    rng = np.random.default_rng(5)
    q_lens, kv_before = [1, 1, 40], [700, 130, 290]
    cache = _step(rng, q_lens, kv_before, 64, 64, max_pages=6)
    table = np.asarray(cache.page_table).copy()
    table[1, 2:] = -1                   # slot 1 holds 131 tokens
    cache = cache._replace(page_table=jnp.asarray(table))
    q_idx = jnp.asarray(rng.standard_normal((64, IH, ID)), jnp.float32)
    w_idx = jnp.asarray(rng.standard_normal((64, IH)), jnp.float32)
    cache = ragged_paged_append(
        cache, jnp.asarray(rng.standard_normal((1, 1, 64, D)), jnp.float32),
        index_new=jnp.asarray(rng.standard_normal((1, 1, 64, ID)),
                              jnp.float32))
    select = select_keys(q_idx, w_idx, cache, top_k=16, group=HEADS)
    assert select.shape == (SLOTS + 1, 64, 6 * PAGE)
    got = _kept(select, cache, q_lens, HEADS)
    _, want = _chosen(cache, q_idx, w_idx, q_lens, 16)
    for row in want:
        np.testing.assert_array_equal(got[row], want[row], err_msg=str(row))


def test_ties_go_to_the_lower_position():
    """Index keys that are all ONE vector: every score of a row is the
    same number, and the row keeps its first ``top_k`` positions."""
    rng = np.random.default_rng(3)
    q_lens, kv_before = [1, 1, 20], [300, 7, 250]
    index = np.broadcast_to(rng.standard_normal(ID), (POOL, 1, PAGE, ID))
    cache = _step(rng, q_lens, kv_before, 32, 32, index=index)
    q_idx = jnp.asarray(rng.standard_normal((32, IH, ID)), jnp.float32)
    w_idx = jnp.ones((32, IH), jnp.float32)
    same = jnp.broadcast_to(jnp.asarray(index[0, 0, 0], jnp.float32),
                            (1, 1, 32, ID))
    cache = ragged_paged_append(cache, jnp.zeros((1, 1, 32, D)),
                                index_new=same)
    got = _kept(select_keys(q_idx, w_idx, cache, top_k=16, group=HEADS),
                cache, q_lens, HEADS)
    after = np.asarray(cache.kv_lens)
    for s, length in enumerate(q_lens):
        for t in range(length):
            reach = after[s] - length + t + 1
            np.testing.assert_array_equal(
                got[int(cache.cu_q_lens[s]) + t],
                np.arange(min(16, reach)))


@pytest.mark.parametrize("q_lens, kv_before, q_tile, width", CASES)
def test_attention_sees_the_chosen_keys_alone_and_counts_them(
        q_lens, kv_before, q_tile, width):
    rng = np.random.default_rng(4)
    top_k = 16
    cache = _step(rng, q_lens, kv_before, q_tile, width)
    q = jnp.asarray(rng.standard_normal((1, HEADS, width, D)), jnp.float32)
    q_idx = jnp.asarray(rng.standard_normal((width, IH, ID)), jnp.float32)
    w_idx = jnp.asarray(rng.standard_normal((width, IH)), jnp.float32)
    cache = ragged_paged_append(
        cache, jnp.asarray(rng.standard_normal((1, 1, width, D)),
                           jnp.float32),
        index_new=jnp.asarray(rng.standard_normal((1, 1, width, ID)),
                              jnp.float32))
    select = select_keys(q_idx, w_idx, cache, top_k=top_k, group=HEADS)
    out, attended = ragged_paged_attention(q, cache, scale=0.2,
                                           value_dim=DV, select=select)
    _, sets = _chosen(cache, q_idx, w_idx, q_lens, top_k)
    table, cu = np.asarray(cache.page_table), np.asarray(cache.cu_q_lens)
    want = np.zeros((HEADS, width, DV), np.float32)
    for s, length in enumerate(q_lens):
        keys = _rows(cache.k_pool, table[s])
        for t in range(length):
            mine = keys[sets[cu[s] + t]]
            scores = np.asarray(q[0, :, cu[s] + t], np.float32) @ mine.T * 0.2
            p = np.exp(scores - scores.max(-1, keepdims=True))
            want[:, cu[s] + t] = p / p.sum(-1, keepdims=True) @ mine[:, :DV]
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=3e-6)
    assert int(attended) == sum(len(v) for v in sets.values())
    # a choice of every key is dense attention, bit for bit
    every = select_keys(q_idx, w_idx, cache, top_k=4096, group=HEADS)
    dense = ragged_paged_attention(q, cache, scale=0.2, value_dim=DV)
    sparse, count = ragged_paged_attention(q, cache, scale=0.2,
                                           value_dim=DV, select=every)
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(dense))
    after = np.asarray(cache.kv_lens)
    assert int(count) == sum(
        after[s] - length + t + 1
        for s, length in enumerate(q_lens) for t in range(length))


@pytest.mark.parametrize("q_lens, kv_before, q_tile, width", CASES)
def test_the_count_is_the_masks_and_not_the_selectors_marks(
        q_lens, kv_before, q_tile, width):
    """A choice that MARKS every place, keys a row cannot see and rows
    of no token among them: the count is what the mask the softmax is
    given let through (causal, inside the span, chosen), so it reads
    the causal pairs and the result is dense attention's; and marks
    that leave a seen key out are missed in the count."""
    rng = np.random.default_rng(6)
    cache = _step(rng, q_lens, kv_before, q_tile, width)
    q = jnp.asarray(rng.standard_normal((1, HEADS, width, D)), jnp.float32)
    cache = ragged_paged_append(
        cache, jnp.asarray(rng.standard_normal((1, 1, width, D)),
                           jnp.float32),
        index_new=jnp.zeros((1, 1, width, ID), jnp.float32))
    shape = select_keys(
        jnp.zeros((width, IH, ID)), jnp.zeros((width, IH)), cache,
        top_k=16, group=HEADS).shape
    out, count = ragged_paged_attention(
        q, cache, scale=0.2, value_dim=DV, select=jnp.ones(shape))
    dense = ragged_paged_attention(q, cache, scale=0.2, value_dim=DV)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))
    after = np.asarray(cache.kv_lens)
    causal = sum(int(after[s]) - length + t + 1
                 for s, length in enumerate(q_lens) for t in range(length))
    assert int(count) == causal
    # position 0 taken out of every token's choice: one pair a token
    fewer = jnp.ones(shape).at[:, :, 0].set(0.0)
    _, count = ragged_paged_attention(
        q, cache, scale=0.2, value_dim=DV, select=fewer)
    assert int(count) == causal - sum(q_lens)
