"""Latent attention (MLA) on ONE paged latent pool: the ragged kernel's
row-blocked form with keys and values from the same page block, its
work list, the layer's absorbed form against its expanded one, and
that the other configurations' programs are what they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.models.latent_attention import (
    LatentAttention,
    latent_row_width,
)
from attention_tpu.ops import ragged_paged
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    live_pages,
    packed_bucket,
    ragged_paged_append,
    ragged_paged_attention,
    recommended_q_tile,
    row_block_count,
    row_block_list,
    work_items,
)

PAGE, SLOTS, MAX_PAGES, POOL = 128, 5, 4, 24
HEADS, D, DV = 8, 48, 32


def _step(rng, dtype, q_lens, kv_before, q_tile, width, pool_width=D):
    """A packed step over one latent pool: ``q_lens`` new tokens a slot
    on ``kv_before`` cached ones, decode slots (one token) first."""
    n = len(q_lens)
    table = -np.ones((SLOTS, MAX_PAGES), np.int32)
    table[:n] = rng.permutation(POOL)[:n * MAX_PAGES].reshape(n, MAX_PAGES)
    cu = np.zeros(SLOTS + 1, np.int32)
    cu[1:n + 1] = np.cumsum(q_lens)
    cu[n + 1:] = cu[n]
    kv = np.zeros(SLOTS, np.int32)
    kv[:n] = kv_before
    pos, slot = np.zeros(width, np.int32), -np.ones(width, np.int32)
    for s, length in enumerate(q_lens):
        pos[cu[s]:cu[s + 1]] = kv[s] + np.arange(length)
        slot[cu[s]:cu[s + 1]] = s
    pool = jnp.asarray(rng.standard_normal((POOL, 1, PAGE, pool_width)),
                       dtype)
    return RaggedPagedStep(
        pool, None, jnp.asarray(table), jnp.asarray(kv), jnp.asarray(cu),
        jnp.asarray([sum(1 for q in q_lens if q == 1), n], jnp.int32),
        jnp.asarray(pos), jnp.asarray(slot), np.zeros((q_tile,), np.int32))


def _dense(q, cache, q_lens, scale, dv):
    """softmax(q k^T scale) k[:, :dv], causal, slot by slot in NumPy."""
    pool = np.asarray(cache.k_pool, np.float32)
    table, cu = np.asarray(cache.page_table), np.asarray(cache.cu_q_lens)
    after = np.asarray(cache.kv_lens)
    out = np.zeros((q.shape[1], q.shape[2], dv), np.float32)
    for s, length in enumerate(q_lens):
        keys = np.concatenate([pool[p, 0] for p in table[s]])
        for t in range(length):
            reach = after[s] - length + t + 1
            scores = np.asarray(q[0, :, cu[s] + t], np.float32) @ keys[
                :reach].T * scale
            p = np.exp(scores - scores.max(-1, keepdims=True))
            out[:, cu[s] + t] = p / p.sum(-1, keepdims=True) @ keys[:reach,
                                                                    :dv]
    return out


@pytest.mark.parametrize("dtype, q_lens, kv_before, q_tile, width, tol", [
    (jnp.float32, [1, 1, 1], [5, 130, 300], 1, 8, 3e-6),
    (jnp.float32, [1, 1, 200], [5, 130, 77], 256, 256, 3e-6),
    (jnp.float32, [1, 37], [400, 0], 64, 64, 3e-6),
    (jnp.bfloat16, [1, 1, 150], [5, 130, 256], 256, 256, 2e-2),
])
def test_one_pool_serves_keys_and_values_row_block_by_row_block(
        dtype, q_lens, kv_before, q_tile, width, tol):
    """Decode rows and a chunk in one launch; the chunk of 150 or 200
    tokens is two blocks of 128 (1,024 rows at a group of 8), each
    against the pages its last row reaches.  Pad tokens stay zero."""
    rng = np.random.default_rng(0)
    cache = _step(rng, dtype, q_lens, kv_before, q_tile, width)
    q = jnp.asarray(rng.standard_normal((1, HEADS, width, D)), dtype)
    new = jnp.asarray(rng.standard_normal((1, 1, width, D)), dtype)
    cache = ragged_paged_append(cache, new)
    assert cache.v_pool is None
    got = np.asarray(ragged_paged_attention(q, cache, scale=0.2,
                                            value_dim=DV)[0], np.float32)
    assert got.shape == (HEADS, width, DV)
    np.testing.assert_allclose(got, _dense(q, cache, q_lens, 0.2, DV),
                               atol=tol)
    assert not got[:, sum(q_lens):].any()


def test_a_poisoned_slot_is_nan_and_its_neighbours_are_not():
    rng = np.random.default_rng(1)
    cache = _step(rng, jnp.float32, [1, 1, 20], [5, 9, 40], 32, 32)
    cache = cache._replace(page_table=cache.page_table.at[1].set(-1))
    q = jnp.asarray(rng.standard_normal((1, HEADS, 32, D)), jnp.float32)
    cache = ragged_paged_append(cache, q[:, :1])
    assert np.asarray(cache.kv_lens).tolist()[:3] == [6, -1, 60]
    got = np.asarray(ragged_paged_attention(q, cache, scale=0.2,
                                            value_dim=DV)[0])
    assert np.isnan(got[:, 1]).all()
    assert np.isfinite(got[:, [0, *range(2, 32)]]).all()


def _mask_form(lens, cu, dist, block_tokens, blocks):
    """`row_block_list`'s items spelled as a mask over (slot, block, page)."""
    q = np.diff(cu)
    want = []
    for s in range(SLOTS):
        if not (s < dist[1] and q[s] > 0):
            continue
        for b in range(min(-(-q[s] // block_tokens), blocks)):
            reach = max(lens[s], 0) - q[s] + min((b + 1) * block_tokens, q[s])
            for j in range(min(max(-(-reach // PAGE), 1), MAX_PAGES)):
                want.append((s * blocks + b) * MAX_PAGES + j)
    return want or [0]


@pytest.mark.parametrize("q_lens, kv_after", [
    ([1, 1, 1], [6, 131, 301]), ([1, 1, 200], [6, 131, 277]),
    ([70], [70]), ([1, 37], [-1, 37]), ([], [])])
def test_the_row_blocked_work_list(q_lens, kv_after):
    """Slot major, block, then page; a block stops at the page its last
    row reaches; a poisoned slot and an empty step keep one item."""
    n = len(q_lens)
    cu = np.zeros(SLOTS + 1, np.int32)
    cu[1:n + 1] = np.cumsum(q_lens)
    cu[n + 1:] = cu[n]
    lens = np.zeros(SLOTS, np.int32)
    lens[:n] = kv_after
    dist = np.asarray([sum(1 for q in q_lens if q == 1), n], np.int32)
    items, count = row_block_list(
        jnp.asarray(lens), jnp.asarray(cu), jnp.asarray(dist),
        max_pages=MAX_PAGES, page=PAGE, block_tokens=64, blocks=4,
        width=256)[:2]
    want = _mask_form(lens, cu, dist, 64, 4)
    assert int(count) == len(want)
    # the host's count of the same grid (`StepMetrics.ragged_grid_steps`)
    assert row_block_count(lens, cu, dist, max_pages=MAX_PAGES, page=PAGE,
                           block_tokens=64, blocks=4) == len(want)
    assert np.asarray(items)[:len(want)].tolist() == want
    assert (np.asarray(items)[len(want):] == SLOTS * 4 * MAX_PAGES).all()
    assert items.shape == ((SLOTS + 256 // 64) * MAX_PAGES + 1,)


def test_a_cache_of_one_pool_says_what_it_cannot_do():
    rng = np.random.default_rng(2)
    cache = _step(rng, jnp.float32, [1], [5], 1, 8)
    q = jnp.zeros((1, HEADS, 8, D), jnp.float32)
    with pytest.raises(ValueError, match="value_dim"):
        ragged_paged_attention(q, cache)
    with pytest.raises(ValueError, match="window"):
        ragged_paged_attention(q, cache, value_dim=DV, window=64)
    with pytest.raises(ValueError, match="multiple of 8"):
        ragged_paged_attention(
            q[:, :4], cache._replace(q_span=np.zeros((2,), np.int32)),
            value_dim=DV)
    with pytest.raises(ValueError, match="only with one"):
        ragged_paged_append(cache, q[:, :1], q[:, :1])
    pair = cache._replace(v_pool=cache.k_pool)
    with pytest.raises(ValueError, match="value_dim is for"):
        ragged_paged_attention(q, pair, value_dim=DV)


def test_the_blocked_form_fits_vmem_at_the_cells_sizes():
    """64 heads on one latent head, keys of 640 lanes, values of 512,
    a packed width of 288: the resident form's own estimate is over
    any budget, a block of the row-blocked form is under the default."""
    group, d, dv, width, page = 64, 640, 512, 288, 128
    rows = ragged_paged._BLOCK_ROWS
    resident = 2 * width * group * (d + dv) * 2
    blocked = (rows * (d + dv) * 2 + 4 * page * d * 2
               + rows * (dv + 2 * 128) * 4 + 3 * rows * page * 4)
    assert resident > ragged_paged._MAX_SCOPED_VMEM // 2
    assert blocked < ragged_paged._DEFAULT_SCOPED_VMEM // 2
    assert rows // group == 16 and 256 // (rows // group) == 16


# -- the layer -------------------------------------------------------------

LAYER = dict(num_heads=8, q_lora_rank=32, kv_lora_rank=16, nope_dim=16,
             rope_dim=8, v_dim=16, rope_theta=1e4, norm_eps=1e-5,
             dtype=jnp.float32)


def test_the_absorbed_form_is_the_expanded_one():
    """(b): a prompt in two chunks and two decode steps through the
    latent pool, against the whole sequence at once without a cache."""
    layer = LatentAttention(**LAYER)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 90, 64)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    want = np.asarray(layer.apply(params, x))
    width = latent_row_width(16, 8)
    assert width == 128 and latent_row_width(512, 64) == 640
    pool = jnp.zeros((6, 1, PAGE, width), jnp.float32)
    table = jnp.asarray([[3, 1, -1]], jnp.int32)
    got, done = [], 0
    for span in (64, 24, 1, 1):
        bucket = packed_bucket(span)
        pos = np.zeros(bucket, np.int32)
        pos[:span] = done + np.arange(span)
        slot = np.where(np.arange(bucket) < span, 0, -1).astype(np.int32)
        cache = RaggedPagedStep(
            pool, None, table, jnp.asarray([done], jnp.int32),
            jnp.asarray([0, span], jnp.int32),
            jnp.asarray([int(span == 1), 1], jnp.int32), jnp.asarray(pos),
            jnp.asarray(slot),
            np.zeros((recommended_q_tile(span, 8),), np.int32))
        rows = jnp.zeros((1, bucket, 64), jnp.float32).at[:, :span].set(
            x[:, done:done + span])
        out, cache = layer.apply(params, rows, cache)
        pool = cache.k_pool
        got.append(np.asarray(out[0, :span]))
        done += span
    np.testing.assert_allclose(np.concatenate(got), want[0], atol=2e-5)
    # what a token left behind: [c | k_r] and zeros up to the register
    row = np.asarray(pool[3, 0, 5])
    assert row[:24].any() and not row[24:].any()


def test_the_layer_takes_a_packed_steps_pool_and_nothing_else():
    from attention_tpu.models import KVCache

    layer = LatentAttention(**LAYER)
    x = jnp.zeros((1, 8, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="KVCache"):
        layer.apply(params, x, KVCache.create(1, 1, 16, 24, jnp.float32))


# -- the other configurations' programs ------------------------------------

@pytest.mark.parametrize("group, d, expect", [(9, 128, 0), (1, 128, 0),
                                              (16, 128, 0)])
def test_a_cache_of_two_pools_keeps_the_resident_form(group, d, expect,
                                                      monkeypatch):
    """(h): K and V pools take the kernel as it was: the mask's work
    list, whole packed rows resident, no row blocks."""
    seen = {}
    real = ragged_paged._ragged_kernel

    def spy(*refs, **kw):
        seen.update(blocks=kw["blocks"], shared=kw["shared_kv"],
                    refs=len(refs))
        return real(*refs, **kw)

    monkeypatch.setattr(ragged_paged, "_ragged_kernel", spy)
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.standard_normal((8, 1, PAGE, d)), jnp.float32)
    cache = RaggedPagedStep(
        pool, pool, jnp.asarray([[2, 5]], jnp.int32),
        jnp.asarray([140], jnp.int32), jnp.asarray([0, 8], jnp.int32),
        jnp.asarray([0, 1], jnp.int32), jnp.arange(8, dtype=jnp.int32) + 132,
        jnp.zeros((8,), jnp.int32), np.zeros((8,), np.int32))
    q = jnp.asarray(rng.standard_normal((1, group, 8, d)), jnp.float32)
    out = ragged_paged_attention(q, cache)
    assert out.shape == (1, group, 8, d)
    # five scalar refs, q, K, V, out and three scratches
    assert seen == {"blocks": expect, "shared": False, "refs": 12}
    items, n = work_items(live_pages(
        cache.kv_lens, cache.cu_q_lens, cache.distribution, max_pages=2,
        page=PAGE, q_tile=8, window=None, sinks=None))
    assert int(n) == 2 and np.asarray(items).tolist() == [0, 1, 2]
