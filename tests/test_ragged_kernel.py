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
    compiled entry;
  * a MIXED step (decode slots beside a chunk) at the cells' groups
    (8, 9, 1, 16), with the decode slot first, last and at the clamped
    end of the packed axis: where the group is a multiple of 8 a span
    of one token is attended at the one-token tile and a longer one at
    the step's, two bodies in one kernel; at another group one body;
  * a grid step carries a BLOCK of KV heads (`head_block`): at the
    cells' groups and head counts, decode-only and beside a chunk,
    the result is the oracle's and, to the bit, the kernel's at a
    block of one head; a budget that holds half the heads gives half,
    and a mesh shard blocks its own heads.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_tpu import obs
from attention_tpu.ops import ragged_paged
from attention_tpu.ops.decode import banded_live
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    _ragged_paged_attention_jit,
    _vmem_need,
    head_block,
    live_pages,
    packed_bucket,
    ragged_paged_attention,
    span_tile_rows,
    tile_tokens,
    work_items,
)
from attention_tpu.ops.reference import ragged_paged_reference

pytestmark = pytest.mark.engine

_PAGE, _D = 128, 16


def _step(*, slots, max_pages, hq, hkv, spans, active=None, share=(),
          pool_pages=None):
    """One packed step, lengths POST-append: ``spans`` holds (kv_len,
    q_len) per used slot, in the order of the packed axis (the engine
    puts decode slots, q_len 1, first; the kernel takes any order);
    ``kv_len`` -1 poisons the slot.  Every slot gets pages of its own
    but for ``share`` = (slot, other, n): ``slot``'s first ``n`` table entries
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
_TRINITY = dict(slots=6, max_pages=20, hq=32, hkv=4)
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
    # MIXED steps.  (i) (j) Trinity's group of 8 (a tile of 384 rows, a
    # decode row's of 8): a full layer and a window layer whose band
    # binds, a decode slot first and last before the chunk
    "mixed_group_8_full_layer": (
        dict(_TRINITY, spans=[(300, 1), (2300, 1), (129, 1), (2400, 40)]),
        {}),
    "mixed_group_8_window_2048": (
        dict(_TRINITY, spans=[(300, 1), (2300, 1), (129, 1), (2400, 40)]),
        dict(window=2048)),
    # (k) the chunk FIRST, decode slots after it
    "mixed_group_8_decode_after_the_chunk": (
        dict(_TRINITY, spans=[(2400, 33), (2300, 1), (7, 1)]),
        dict(window=2048)),
    # (l) a poisoned decode slot beside the chunk: NaN for its 8 rows
    "mixed_group_8_poisoned_decode": (
        dict(_TRINITY, spans=[(300, 1), (-1, 1), (129, 1), (700, 40)]), {}),
    # (m) group 9, one tile of 144 rows: the last decode slot's start is
    # clamped to the packed axis' end
    "mixed_group_9_clamped_end": (
        dict(slots=8, max_pages=4, hq=36, hkv=4,
             spans=[(290, 10), (300, 1), (129, 1), (5, 1), (77, 1),
                    (128, 1), (500, 1)]), {}),
    # (n) StarCoder2's window and sinks beside a chunk
    "mixed_group_9_window_and_sinks": (
        dict(_STARCODER2, spans=[(700, 1), (1000, 1), (290, 40)]),
        dict(window=100, sinks=4)),
    # (o) group 1, one tile of 32 rows: decode slots before the chunk
    # and after it
    "mixed_group_1_both_ends": (
        dict(slots=11, max_pages=5, hq=6, hkv=6,
             spans=[(130, 1)] * 2 + [(300, 22)] + [(40, 1)] * 8), {}),
    # (p) Nemotron's group of 16 (256 rows and 16), with a softcap
    "mixed_group_16_softcap": (
        dict(slots=5, max_pages=5, hq=32, hkv=2,
             spans=[(130, 1), (300, 9), (40, 1)]), dict(softcap=30.0)),
}
# the (tile rows, one-token rows) of each mixed case's program: two
# tile bodies where the group is a multiple of 8, one at any other
_MIXED_TILES = {
    "group_1_30_heads_9_slots": (32, 32),
    "every_slot_full": (40, 40),
    "chunk_beside_decode": (432, 432),
    "mixed_group_8_full_layer": (384, 8),
    "mixed_group_8_window_2048": (384, 8),
    "mixed_group_8_decode_after_the_chunk": (384, 8),
    "mixed_group_8_poisoned_decode": (384, 8),
    "mixed_group_9_clamped_end": (144, 144),
    "mixed_group_9_window_and_sinks": (432, 432),
    "mixed_group_1_both_ends": (32, 32),
    "mixed_group_16_softcap": (256, 16),
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
    assert kept == {"poisoned_between_sound": 1, "no_active_slot": 1,
                    "mixed_group_8_poisoned_decode": 1}.get(case, 0)
    group = step["hq"] // step["hkv"]
    tiles = span_tile_rows(cache.q_tile, q.shape[2], group)
    # one tile where every span is of one token, else a mixed step's two
    assert tiles == _MIXED_TILES.get(case, tiles[:1] * 2)
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


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, kernels' bodies and their branches
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _dot_rows(jaxpr):
    """The row counts of every product's left operand (a head's, where
    the heads of a block go through as a batch)."""
    return {eqn.invars[0].aval.shape[-2] for eqn in _eqns(jaxpr)
            if eqn.primitive.name == "dot_general"}


@pytest.mark.parametrize("case", sorted(_MIXED_TILES))
def test_a_decode_row_beside_a_chunk_is_served_at_its_own_tile(case):
    """The decode rows of a mixed step are, to the bit, the same rows
    of the decode-only step over the same cache, and where the group
    is a multiple of 8 the mixed program's kernel holds the score and
    value products at BOTH row counts, the step's tile's and the
    one-token tile's (which is the decode-only program's only tile);
    at another group it holds the step's alone."""
    step, band = _CASES[case]
    q, cache, _ = _step(**step)
    group = step["hq"] // step["hkv"]
    wide, one_token = _MIXED_TILES[case]
    assert (one_token < wide) == (group % 8 == 0)
    mixed = jax.make_jaxpr(
        lambda q, cache: _ragged_paged_attention_jit(q, cache, **band))(
            jnp.asarray(q), cache)
    assert _dot_rows(mixed.jaxpr) == {one_token, wide}
    got = np.asarray(ragged_paged_attention(jnp.asarray(q), cache, **band))

    # the same slots with the chunk's span emptied: a decode-only step.
    # The band the PAGES are walked in follows the step's tile, so the
    # decode-only step visits fewer of them; what a row attends is its
    # own mask's, the same in both
    cu = np.asarray(cache.cu_q_lens)
    q_lens = np.diff(cu)
    slots = np.flatnonzero(q_lens == 1)
    q_tile = tile_tokens(1, group)
    width = packed_bucket(max(len(slots), q_tile))
    alone = np.zeros((1, step["hq"], width, _D), np.float32)
    alone[0, :, :len(slots)] = q[0][:, cu[slots]]
    cu_alone = np.cumsum([0] + [int(n == 1) for n in q_lens]).astype(np.int32)
    only = cache._replace(
        cu_q_lens=jnp.asarray(cu_alone),
        token_pos=jnp.zeros((width,), jnp.int32),
        token_slot=jnp.full((width,), -1, jnp.int32),
        q_span=np.zeros((q_tile,), np.int32))
    tiles = span_tile_rows(q_tile, width, group)
    assert tiles[0] == tiles[1]
    if group % 8 == 0:
        assert tiles[0] == one_token
    decode_only = jax.make_jaxpr(
        lambda q, cache: _ragged_paged_attention_jit(q, cache, **band))(
            jnp.asarray(alone), only)
    assert _dot_rows(decode_only.jaxpr) == {tiles[0]}
    want = np.asarray(ragged_paged_attention(jnp.asarray(alone), only,
                                             **band))
    np.testing.assert_array_equal(got[0][:, cu[slots]],
                                  want[0][:, :len(slots)])


@contextlib.contextmanager
def _head_budget(budget: int):
    """The kernel under `head_block`'s rule at another ``budget``: 0
    holds no block but one head's."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ragged_paged, "head_block",
                   functools.partial(head_block, budget=budget))
        _ragged_paged_attention_jit.clear_cache()
        try:
            yield
        finally:
            _ragged_paged_attention_jit.clear_cache()


def _grid_head_blocks(q, cache, band):
    """The first bound of the kernel's grid: the blocks its KV heads
    are carried in."""
    jaxpr = jax.make_jaxpr(
        lambda q, cache: _ragged_paged_attention_jit(q, cache, **band))(
            q, cache)
    (blocks,) = [eqn.params["grid_mapping"].grid[0]
                 for eqn in _eqns(jaxpr.jaxpr)
                 if eqn.primitive.name == "pallas_call"]
    return blocks


# the cells' groups and KV heads: Trinity, StarCoder2, Olmo, Nemotron
_HEADS = {"group_8": (32, 4), "group_9": (36, 4), "group_1": (30, 30),
          "group_16": (32, 2)}
# a poisoned slot among the decode rows; window + sinks leave a hole in
# the long slots' pages; the packed axis ends in pad tokens
_DECODE_ONLY = [(700, 1), (-1, 1), (1000, 1), (60, 1), (129, 1)]
_BESIDE_A_CHUNK = [(700, 1), (-1, 1), (1000, 1), (60, 1), (600, 21)]
_BAND = dict(window=300, sinks=4, softcap=30.0)
_BLOCKED = [(heads, step, how)
            for heads in _HEADS
            for step in ("decode_only", "beside_a_chunk")
            for how in ("every_head",)]
_BLOCKED += [("group_8", "beside_a_chunk", "half_the_heads"),
             ("group_9", "decode_only", "a_mesh_shard")]


@pytest.mark.parametrize("heads,step,how", _BLOCKED)
def test_a_grid_step_carries_a_block_of_heads(heads, step, how):
    """Against the oracle, and bit-equal to the kernel that carries one
    head a grid step: every head in one block at the cells' shapes;
    half of them under a budget that holds no more; a mesh shard's
    own."""
    hq, hkv = _HEADS[heads]
    spans = _DECODE_ONLY if step == "decode_only" else _BESIDE_A_CHUNK
    q, cache, total = _step(slots=7, max_pages=8, hq=hq, hkv=hkv, spans=spans)
    q = jnp.asarray(q)
    assert total < q.shape[2]                     # pad tokens at the end
    want = ragged_paged_reference(
        np.asarray(q), np.asarray(cache.k_pool), np.asarray(cache.v_pool),
        np.asarray(cache.page_table), np.asarray(cache.kv_lens),
        np.asarray(cache.cu_q_lens), np.asarray(cache.distribution), **_BAND)
    with _head_budget(0):
        assert _grid_head_blocks(q, cache, _BAND) == hkv
        one_head = np.asarray(ragged_paged_attention(q, cache, **_BAND))

    rule = dict(d=_D, dv=_D, page=_PAGE, q_itemsize=4, kv_itemsize=4)
    shape = (cache.q_tile, q.shape[2], hq // hkv)
    if how == "every_head":
        assert head_block(hkv, *shape, **rule) == hkv
        assert _grid_head_blocks(q, cache, _BAND) == 1
        got = np.asarray(ragged_paged_attention(q, cache, **_BAND))
    elif how == "half_the_heads":
        # what two heads ask for, with the call's half again, and not
        # a byte for a third
        tiles = span_tile_rows(*shape)
        budget = int(1.5 * _vmem_need(
            hkv // 2, *tiles, held_rows=2 * q.shape[2] * hq // hkv,
            kv_lanes=2 * _D, **rule))
        assert head_block(hkv, *shape, budget=budget, **rule) == hkv // 2
        with _head_budget(budget):
            assert _grid_head_blocks(q, cache, _BAND) == 2
            got = np.asarray(ragged_paged_attention(q, cache, **_BAND))
    else:
        from jax.sharding import Mesh

        from attention_tpu.parallel.serving import head_sharded_ragged_step

        # the step before its append, whose rows are nobody's: the
        # lengths advance and no pool row is written
        q_lens = jnp.diff(cache.cu_q_lens)
        before = cache._replace(kv_lens=jnp.where(
            cache.kv_lens < 0, -1, cache.kv_lens - q_lens))
        nobody = jnp.zeros((1, hkv, q.shape[2], _D), jnp.float32)
        got, after = head_sharded_ragged_step(
            q, before, nobody, nobody,
            mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)), **_BAND)
        got = np.asarray(got)
        np.testing.assert_array_equal(np.asarray(after.kv_lens),
                                      np.asarray(cache.kv_lens))
    np.testing.assert_array_equal(got, one_head)
    # NaN for the poisoned slot's rows and nowhere else, zeros for pads
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    assert np.isnan(got[0, :, 1]).all()
    sound = ~np.isnan(want)
    assert np.abs(got[sound] - want[sound]).max() < 2e-5
    assert np.all(got[..., total:, :] == 0.0)


def test_the_head_block_follows_the_budget_at_the_cells_shapes():
    """Every head at each cell's widest step (bf16, heads of 128); half
    of Trinity's at a packed width of 2,048 and a tile of 1,024, where
    four heads' rows and scratch ask for more than the core holds; one
    head a grid step where nothing fits, and in the row-blocked
    form."""
    rule = dict(d=128, dv=128, page=128, q_itemsize=2, kv_itemsize=2)
    for hq, hkv, width in ((32, 4, 512), (36, 4, 512), (30, 30, 512),
                           (32, 2, 512)):
        for q_tile in (256, tile_tokens(1, hq // hkv)):
            assert head_block(hkv, q_tile, width, hq // hkv, **rule) == hkv
    assert head_block(4, 1024, 2048, 8, **rule) == 2
    assert head_block(4, 1024, 2048, 8, budget=0, **rule) == 1
    assert head_block(1, 256, 384, 64, row_blocked=True, **rule) == 1


def test_the_lowering_says_how_many_tile_bodies_and_heads_it_holds():
    """`ops.ragged.lowered` carries ``bodies``: "two" for a program of
    a group of 8 whose tile is wider than one token's, "one" for a
    decode-only shape and for a mixed step at a group of 9; and
    ``heads``: the KV heads a grid step carries of those there are."""
    was = obs.is_enabled()
    obs.reset()
    obs.enable()
    _ragged_paged_attention_jit.clear_cache()   # it ticks at trace time
    try:
        lowered = obs.counter("ops.ragged.lowered")
        q, cache, _ = _step(**_CASES["mixed_group_8_full_layer"][0])
        ragged_paged_attention(jnp.asarray(q), cache)
        assert [s["labels"] for s in lowered.series()] == [
            {"requested": "online", "lowered": "online", "bodies": "two",
             "heads": "4/4"}]
        for case in ("three_of_33_slots_group_9", "chunk_beside_decode"):
            q, cache, _ = _step(**_CASES[case][0])
            ragged_paged_attention(jnp.asarray(q), cache)
        assert lowered.value(requested="online", lowered="online",
                             bodies="one", heads="4/4") == 2
        assert lowered.value(requested="online", lowered="online",
                             bodies="two", heads="4/4") == 1
        q, cache, _ = _step(**_CASES["group_1_30_heads_9_slots"][0])
        ragged_paged_attention(jnp.asarray(q), cache)
        assert lowered.value(requested="online", lowered="online",
                             bodies="one", heads="30/30") == 1
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
