"""The ragged kernel's grid walks the step's live (slot, page) pairs.

`ops.ragged_paged.ragged_paged_attention` builds, on the device, the
list of table entries that hold something to attend (`live_pages`,
`work_items`) and runs a grid of ``(Hkv, n)`` over it, ``n`` a traced
scalar.  Pinned here on the CPU, interpreted, at the benchmark cells'
table shapes (33 x 34 at a group of 9; 9 x 52 at 30 KV heads):

  * the kernel against the fp64 oracle (`ops.reference`) for sparse,
    full, poisoned, empty, banded, mixed and page-sharing steps;
  * the list itself: its length is the count of entries the kernel's
    own compute guard (`ops.decode.banded_live`) admits, plus the two
    kept entries that hold no page; the host's count (the engine's
    ``kv_pages``) is the device's;
  * the bound is a VALUE: steps of other lengths at one shape add no
    compiled entry.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu.ops.decode import banded_live
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    _ragged_paged_attention_jit,
    live_pages,
    packed_bucket,
    ragged_paged_attention,
    tile_tokens,
    work_items,
)
from attention_tpu.ops.reference import ragged_paged_reference

pytestmark = pytest.mark.engine

_PAGE, _D = 128, 16


def _step(*, slots, max_pages, hq, hkv, spans, active=None, share=(),
          pool_pages=None):
    """One packed step, lengths POST-append: ``spans`` holds (kv_len,
    q_len) per used slot, decode slots (q_len 1) first; ``kv_len`` -1
    poisons the slot.  Every slot gets pages of its own but for
    ``share`` = (slot, other, n): ``slot``'s first ``n`` table entries
    are ``other``'s.  The pools hold the pages used, or
    ``pool_pages``."""
    r = np.random.default_rng(0)
    group = hq // hkv
    table = np.full((slots, max_pages), -1, np.int32)
    nxt = 0
    for s, (kv_len, _) in enumerate(spans):
        n = -(-max(kv_len, 1) // _PAGE)
        table[s, :n] = np.arange(nxt, nxt + n)
        nxt += n
    for s, other, n in share:
        table[s, :n] = table[other, :n]
    q_lens = [q for _, q in spans]
    total = sum(q_lens)
    q_tile = tile_tokens(packed_bucket(max(q_lens, default=1), minimum=1),
                         group)
    width = packed_bucket(max(total, q_tile))
    cu = np.full((slots + 1,), total, np.int32)
    cu[:len(spans) + 1] = np.concatenate([[0], np.cumsum(q_lens)])
    kv_lens = np.zeros((slots,), np.int32)
    kv_lens[:len(spans)] = [kv for kv, _ in spans]
    num_active = len(spans) if active is None else active
    dist = np.asarray([sum(q == 1 for q in q_lens[:num_active]),
                       num_active], np.int32)
    pool = (pool_pages or max(nxt, 1), hkv, _PAGE, _D)
    cache = RaggedPagedStep(
        jnp.asarray(r.standard_normal(pool), jnp.float32),
        jnp.asarray(r.standard_normal(pool), jnp.float32),
        jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(cu),
        jnp.asarray(dist), jnp.zeros((width,), jnp.int32),
        jnp.full((width,), -1, jnp.int32), np.zeros((q_tile,), np.int32))
    q = r.standard_normal((1, hq, width, _D)).astype(np.float32)
    return q, cache, total


_STARCODER2 = dict(slots=33, max_pages=34, hq=36, hkv=4)
_CASES = {
    # (a) a decode-only step of the StarCoder2 cells: 3 of 33 slots
    "three_of_33_slots_group_9": (
        dict(_STARCODER2, spans=[(90, 1), (500, 1), (896, 1)]), {}),
    # (b) Olmo-Hybrid's table: MHA, 30 heads, 9 slots
    "group_1_30_heads_9_slots": (
        dict(slots=9, max_pages=52, hq=30, hkv=30,
             spans=[(130, 1)] * 4 + [(40, 1)] * 4 + [(300, 24)]), {}),
    # (c) every entry of the table live: the list is the old grid
    "every_slot_full": (
        dict(slots=4, max_pages=3, hq=4, hkv=2,
             spans=[(384, 1)] * 3 + [(384, 16)]), {}),
    # (d) NaN rows for the poisoned slot, its neighbours untouched
    "poisoned_between_sound": (
        dict(slots=6, max_pages=4, hq=4, hkv=2,
             spans=[(200, 1), (-1, 1), (50, 1)]), {}),
    # (e) nothing to do: the output is still zeroed
    "no_active_slot": (
        dict(slots=4, max_pages=3, hq=4, hkv=2, spans=[(70, 1)],
             active=0), {}),
    # (f) the band leaves pages 1-3 of the first slot out, between the
    # sink page and the window's first
    "window_and_sinks_leave_a_hole": (
        dict(slots=5, max_pages=8, hq=4, hkv=2,
             spans=[(700, 1), (1000, 1), (60, 1)]),
        dict(window=100, sinks=4)),
    # (g) decode slots at the chunk's tile
    "chunk_beside_decode": (
        dict(_STARCODER2, spans=[(300, 1), (129, 1), (290, 40)]), {}),
    # (h) a common prefix of two pages, one copy in the pool
    "shared_prefix_pages": (
        dict(slots=4, max_pages=5, hq=4, hkv=2,
             spans=[(400, 1), (300, 1)], share=[(1, 0, 2)]), {}),
}


def _guarded_entries(cache, band):
    """The table entries `_ragged_kernel`'s compute guard admits,
    counted one scalar call at a time, and the kept entries beside
    them (an active slot with none; slot 0 of a step with no slot)."""
    lens = np.asarray(cache.kv_lens)
    cu = np.asarray(cache.cu_q_lens)
    num_active = int(cache.distribution[1])
    slots, max_pages = cache.page_table.shape
    w_eff = None
    if band.get("window") is not None:
        w_eff = band["window"] + cache.q_tile - 1
    live = kept = 0
    active = [s for s in range(slots)
              if s < num_active and cu[s + 1] > cu[s]]
    for s in active:
        mine = sum(bool(banded_live(j, max(int(lens[s]), 0), _PAGE, w_eff,
                                    band.get("sinks")))
                   for j in range(max_pages))
        live += mine
        kept += mine == 0
    return live, kept + (not active)


@pytest.mark.parametrize("case", _CASES)
def test_kernel_matches_the_oracle_over_its_work_list(case):
    step, band = _CASES[case]
    q, cache, total = _step(**step)
    got = np.asarray(ragged_paged_attention(jnp.asarray(q), cache, **band))
    want = ragged_paged_reference(
        q, np.asarray(cache.k_pool), np.asarray(cache.v_pool),
        np.asarray(cache.page_table), np.asarray(cache.kv_lens),
        np.asarray(cache.cu_q_lens), np.asarray(cache.distribution),
        **band)
    # NaN where the oracle has NaN (a poisoned slot's rows), nowhere else
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    sound = ~np.isnan(want)
    assert np.abs(got[sound] - want[sound]).max() < 2e-5
    assert np.all(got[..., total:, :] == 0.0)

    # the list: as long as the guard's count, and the host agrees
    slots, max_pages = cache.page_table.shape
    rule = dict(max_pages=max_pages, page=_PAGE, q_tile=cache.q_tile,
                window=band.get("window"), sinks=band.get("sinks"))
    mask = live_pages(cache.kv_lens, cache.cu_q_lens, cache.distribution,
                      **rule)
    items, n = work_items(mask)
    live, kept = _guarded_entries(cache, band)
    assert int(n) == live + kept
    assert kept == {"poisoned_between_sound": 1,
                    "no_active_slot": 1}.get(case, 0)
    if case == "every_slot_full":
        assert int(n) == slots * max_pages
    host = live_pages(np.asarray(cache.kv_lens),
                      np.asarray(cache.cu_q_lens),
                      np.asarray(cache.distribution), xp=np, **rule)
    assert isinstance(host, np.ndarray) and int(host.sum()) == int(n)
    # slot major, page minor, then the sentinel to the end
    items = np.asarray(items)
    np.testing.assert_array_equal(items[:int(n)],
                                  np.flatnonzero(np.asarray(mask)))
    assert np.all(items[int(n):] == slots * max_pages)
    assert items.shape == (slots * max_pages + 1,)


def test_other_lengths_at_one_shape_add_no_compiled_entry():
    """The grid's bound is a traced scalar: steps that hold 1, 21 and
    64 live entries of one 33 x 34 table run one executable."""
    _ragged_paged_attention_jit.clear_cache()
    counts = []
    for spans in ([(90, 1)], [(896, 1)] * 3, [(1024, 1)] * 8):
        q, cache, _ = _step(**_STARCODER2, spans=spans, pool_pages=64)
        assert (q.shape[2], cache.q_tile) == (8, 8)
        ragged_paged_attention(jnp.asarray(q), cache)
        counts.append(int(work_items(live_pages(
            cache.kv_lens, cache.cu_q_lens, cache.distribution,
            max_pages=34, page=_PAGE, q_tile=8, window=None,
            sinks=None))[1]))
    assert counts == [1, 21, 64]
    assert _ragged_paged_attention_jit._cache_size() == 1
