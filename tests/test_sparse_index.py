"""A learned choice of keys inside paged attention: the selector's
scores over its own paged keys against the equation, the exact top-k
rule (ties to the lower position) handed on as a list of positions a
token, the list kernel attending those cache rows alone, and the
count of what its mask let through."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    _ragged_paged_attention_jit,
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


def _kept(select, cache, q_lens):
    """`select_keys`' result as a set of positions a packed token: a
    real token's valid entries, rising; a token that is nobody's has
    none."""
    select, cu = np.asarray(select), np.asarray(cache.cu_q_lens)
    real = {cu[s] + t for s, length in enumerate(q_lens)
            for t in range(length)}
    out = {}
    for row, entries in enumerate(select):
        valid = entries[entries >= 0]
        if row not in real:
            assert valid.size == 0, row
            continue
        assert (np.diff(valid) > 0).all(), row
        assert (entries[valid.size:] == -1).all(), row
        out[row] = valid
    return out


def _appended(rng, q_lens, kv_before, q_tile, width, **kw):
    """A step after its append, with the attention's and the selector's
    queries."""
    cache = _step(rng, q_lens, kv_before, q_tile, width, **kw)
    q = jnp.asarray(rng.standard_normal((1, HEADS, width, D)), jnp.float32)
    q_idx = jnp.asarray(rng.standard_normal((width, IH, ID)), jnp.float32)
    w_idx = jnp.asarray(rng.standard_normal((width, IH)), jnp.float32)
    cache = ragged_paged_append(
        cache, jnp.asarray(rng.standard_normal((1, 1, width, D)),
                           jnp.float32),
        index_new=jnp.asarray(rng.standard_normal((1, 1, width, ID)),
                              jnp.float32))
    return cache, q, q_idx, w_idx


def _dense(cache, q, sets, q_lens, scale=0.2):
    """Attention over the keys of ``sets`` (packed token -> positions),
    written out; and the pairs that is."""
    table, cu = np.asarray(cache.page_table), np.asarray(cache.cu_q_lens)
    want = np.zeros((HEADS, q.shape[2], DV), np.float32)
    for s, length in enumerate(q_lens):
        keys = _rows(cache.k_pool, table[s])
        for t in range(length):
            mine = keys[sets[cu[s] + t]]
            scores = (np.asarray(q[0, :, cu[s] + t], np.float32) @ mine.T
                      * scale)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            want[:, cu[s] + t] = p / p.sum(-1, keepdims=True) @ mine[:, :DV]
    return want, sum(len(v) for v in sets.values())


def _causal(cache, q_lens):
    """Every key a token sees, a packed token."""
    cu, after = np.asarray(cache.cu_q_lens), np.asarray(cache.kv_lens)
    return {cu[s] + t: np.arange(after[s] - length + t + 1)
            for s, length in enumerate(q_lens) for t in range(length)}


def _as_list(sets, width, entries, rng=None):
    """``sets`` as `select_keys` would hand them on; with ``rng`` in an
    order of its own, the marked entries anywhere."""
    out = -np.ones((width, entries), np.int32)
    for row, keys in sets.items():
        out[row, :len(keys)] = keys
        if rng is not None:
            out[row] = rng.permutation(out[row])
    return jnp.asarray(out)


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
    2,048 keeps every key a row sees.  The list holds exactly the
    rule's positions, then -1."""
    rng = np.random.default_rng(2)
    cache, _, q_idx, w_idx = _appended(rng, q_lens, kv_before, q_tile, width)
    select = select_keys(q_idx, w_idx, cache, top_k=top_k, group=HEADS)
    assert select.shape == (width, min(-(-top_k // 128) * 128,
                                       MAX_PAGES * PAGE))
    assert select.dtype == jnp.int32
    got = _kept(select, cache, q_lens)
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
    assert select.shape == (64, 128)
    got = _kept(select, cache, q_lens)
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
                cache, q_lens)
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
    """The list kernel against attention written out over the rule's
    keys: the pool's pages are a permutation (`_step`), so a wrong row
    arithmetic reads another token's row."""
    rng = np.random.default_rng(4)
    top_k = 16
    cache, q, q_idx, w_idx = _appended(rng, q_lens, kv_before, q_tile, width)
    select = select_keys(q_idx, w_idx, cache, top_k=top_k, group=HEADS)
    out, attended = ragged_paged_attention(q, cache, scale=0.2,
                                           value_dim=DV, select=select)
    _, sets = _chosen(cache, q_idx, w_idx, q_lens, top_k)
    want, pairs = _dense(cache, q, sets, q_lens)
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=3e-6)
    assert int(attended) == pairs
    # a choice of every key is dense attention, to float32 rounding:
    # the keys enter one softmax where the walk takes a page at a time
    every = select_keys(q_idx, w_idx, cache, top_k=4096, group=HEADS)
    dense = ragged_paged_attention(q, cache, scale=0.2, value_dim=DV)
    sparse, count = ragged_paged_attention(q, cache, scale=0.2,
                                           value_dim=DV, select=every)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               atol=3e-6)
    assert int(count) == sum(len(v) for v in _causal(cache, q_lens).values())


@pytest.mark.parametrize("q_lens, kv_before, q_tile, width", CASES)
def test_the_count_is_the_masks_and_not_the_selectors_marks(
        q_lens, kv_before, q_tile, width):
    """A list that names EVERY position of the table for every token,
    keys a token cannot see and tokens that are nobody's among them:
    the count is what the mask the softmax is given let through
    (listed, causal, a real token's), so it reads the causal pairs and
    the result is dense attention's; and a list that leaves a seen key
    out is missed in the count."""
    rng = np.random.default_rng(6)
    cache, q, _, _ = _appended(rng, q_lens, kv_before, q_tile, width)
    every = jnp.broadcast_to(jnp.arange(MAX_PAGES * PAGE, dtype=jnp.int32),
                             (width, MAX_PAGES * PAGE))
    out, count = ragged_paged_attention(
        q, cache, scale=0.2, value_dim=DV, select=every)
    dense = ragged_paged_attention(q, cache, scale=0.2, value_dim=DV)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=3e-6)
    causal = sum(len(v) for v in _causal(cache, q_lens).values())
    assert int(count) == causal
    # position 0 taken out of every token's list: one pair a token
    _, count = ragged_paged_attention(
        q, cache, scale=0.2, value_dim=DV, select=every.at[:, 0].set(-1))
    assert int(count) == causal - sum(q_lens)


def test_a_row_with_fewer_keys_than_the_list_is_wide():
    """Rows that see 6, 131 and 18-20 keys under a list of 128 entries:
    the marked entries attend nothing and count nothing."""
    rng = np.random.default_rng(7)
    q_lens, kv_before = [1, 1, 3], [5, 130, 17]
    # a table of 5 pages: the list-making cuts it into blocks of 128
    # positions, where an even table is cut into blocks of 256
    cache, q, q_idx, w_idx = _appended(rng, q_lens, kv_before, 8, 8,
                                       max_pages=5)
    select = select_keys(q_idx, w_idx, cache, top_k=100, group=HEADS)
    assert select.shape == (8, 128)
    got = _kept(select, cache, q_lens)
    assert [len(got[row]) for row in sorted(got)] == [6, 100, 18, 19, 20]
    out, attended = ragged_paged_attention(q, cache, scale=0.2,
                                           value_dim=DV, select=select)
    want, pairs = _dense(cache, q, got, q_lens)
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=3e-6)
    assert int(attended) == pairs == 6 + 100 + 18 + 19 + 20


@pytest.mark.parametrize("q_lens, kv_before, q_tile, width", CASES)
def test_the_order_of_a_list_changes_nothing_but_rounding(
        q_lens, kv_before, q_tile, width):
    """The same keys a token in two orders, the marked entries
    anywhere among them: the same result to float32 rounding and the
    same count."""
    rng = np.random.default_rng(8)
    cache, q, q_idx, w_idx = _appended(rng, q_lens, kv_before, q_tile, width)
    _, sets = _chosen(cache, q_idx, w_idx, q_lens, 40)
    rising = _as_list(sets, width, 128)
    mixed = _as_list(sets, width, 128, rng)
    assert not np.array_equal(np.asarray(rising), np.asarray(mixed))
    one, n_one = ragged_paged_attention(q, cache, scale=0.2, value_dim=DV,
                                        select=rising)
    two, n_two = ragged_paged_attention(q, cache, scale=0.2, value_dim=DV,
                                        select=mixed)
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), atol=3e-6)
    assert int(n_one) == int(n_two) == sum(len(v) for v in sets.values())
    want, _ = _dense(cache, q, sets, q_lens)
    np.testing.assert_allclose(np.asarray(two[0]), want, atol=3e-6)
    # a list whose first part (128 entries) is all marked: the softmax
    # starts on a block that holds nothing
    late = jnp.concatenate([jnp.full((width, 128), -1, jnp.int32), mixed],
                           axis=1)
    three, n_three = ragged_paged_attention(q, cache, scale=0.2,
                                            value_dim=DV, select=late)
    np.testing.assert_allclose(np.asarray(three), np.asarray(two), atol=3e-6)
    assert int(n_three) == int(n_two)


def test_a_decode_row_beside_a_chunk_pad_tokens_and_a_poisoned_slot():
    """One step of two decode rows and a chunk of 21 tokens on a packed
    axis of 32: the decode row whose table has no page for its new
    token is poisoned (NaN, no list, no count), the 9 pad tokens are
    zeros, and the other rows attend their lists."""
    rng = np.random.default_rng(9)
    q_lens, kv_before = [1, 1, 21], [200, 256, 100]
    cache = _step(rng, q_lens, kv_before, 32, 32)
    table = np.asarray(cache.page_table).copy()
    table[1, 2:] = -1                   # position 256 has no page
    cache = cache._replace(page_table=jnp.asarray(table))
    q = jnp.asarray(rng.standard_normal((1, HEADS, 32, D)), jnp.float32)
    q_idx = jnp.asarray(rng.standard_normal((32, IH, ID)), jnp.float32)
    w_idx = jnp.asarray(rng.standard_normal((32, IH)), jnp.float32)
    cache = ragged_paged_append(
        cache, jnp.asarray(rng.standard_normal((1, 1, 32, D)), jnp.float32),
        index_new=jnp.asarray(rng.standard_normal((1, 1, 32, ID)),
                              jnp.float32))
    assert np.asarray(cache.kv_lens)[:3].tolist() == [201, -1, 121]
    select = np.asarray(select_keys(q_idx, w_idx, cache, top_k=16,
                                    group=HEADS))
    assert (select[1] == -1).all() and (select[23:] == -1).all()
    out, attended = ragged_paged_attention(
        q, cache, scale=0.2, value_dim=DV, select=jnp.asarray(select))
    out = np.asarray(out[0])
    assert np.isnan(out[:, 1]).all()
    assert (out[:, 23:] == 0).all()
    # the rule for every row, the poisoned one's as if it had appended
    _, sets = _chosen(cache._replace(kv_lens=cache.kv_lens.at[1].set(257)),
                      q_idx, w_idx, q_lens, 16)
    want, pairs = _dense(cache, q, sets, q_lens)
    pairs -= len(sets[1])
    keep = [0, *range(2, 23)]
    np.testing.assert_allclose(out[:, keep], want[:, keep], atol=3e-6)
    assert int(attended) == pairs == 16 * 22


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_a_model_without_a_selector_lowers_to_the_kernel_it_had():
    """`select=None` on a cache of one pool: the jaxpr holds ONE
    kernel, the walk, with its five prefetched scalars (lengths, spans,
    split, table, items), the rows, the pool and the zeros its result
    starts from: no list operand, no count result, and no kernel of
    the list form.  With a list it holds the list form's two kernels
    and no walk."""
    rng = np.random.default_rng(10)
    cache, q, _, _ = _appended(rng, [1, 1, 9], [5, 130, 77], 16, 16)

    def lowered(*select):
        return list(_pallas_calls(jax.make_jaxpr(
            lambda q, cache, *sel: _ragged_paged_attention_jit(
                q, cache, scale=0.2, value_dim=DV,
                **({"select": sel[0]} if sel else {})))(
                    q, cache, *select).jaxpr))

    walk, = lowered()
    assert walk.params["name"] is None
    assert walk.params["grid_mapping"].num_index_operands == 5
    assert len(walk.params["out_avals"]) == 1
    shapes = [v.aval.shape for v in walk.invars]
    rows = 2 * 16 * HEADS               # the packed rows + a spare block
    assert shapes[-3:] == [(1, rows, D), (POOL, 1, PAGE, D), (1, rows, DV)]
    assert not any(v.aval.dtype == jnp.int32 and v.aval.ndim > 2
                   for v in walk.invars)
    rows, listed = lowered(jnp.zeros((16, 128), jnp.int32))
    assert rows.params["name"] == "ragged_paged_list_rows"
    assert listed.params["name"] == "ragged_paged_list_attention"
    assert len(listed.params["out_avals"]) == 2
