"""Which keys a query row attends, chosen by a learned selector over a
paged cache of the selector's own keys.

A selector (DeepSeek's lightning indexer) keeps ONE small key a token,
``k^I`` (d_i lanes), in an index pool beside the attention cache
(`RaggedPagedStep.index_pool`: the same pages name the same tokens).
For a query token ``t`` with ``H_i`` selector heads ``q^I_{t,j}`` and
weights ``w_{t,j}``,

    I[t, s] = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)        for s <= t

and the token attends the ``min(top_k, t + 1)`` keys of largest
``I[t, .]``, ties to the lower position.  EXACTLY those: the rule is a
threshold found by bisection over the scores' own bits, no
approximate top-k.

Two kernels, each under its own operation name in a device trace:

  * ``index_scores``: the grid walks the step's (group, pages) items
    of `ops.ragged_paged.row_block_list`, the list the attention
    kernel's row-blocked form walks, at `_PAGES_AN_ITEM` table entries
    an item (an index page is 32 KB: four of them a grid step, each
    fetched by its own table entry); an item is those pages of index
    keys against one GROUP's query rows (a block of at most
    ``block_tokens`` tokens of one slot's span).  Linear in the
    context: every live page of every slot, once a group.
  * ``index_select``: a grid step a group; each row's threshold
    ``tau`` = its ``k``-th largest score (32 counts over the row, a
    bit of ``tau`` each), then the place up to which scores EQUAL to
    ``tau`` are still taken (a bit of the position a count), so that
    exactly ``k`` keys are kept whatever ties there are.  The kept
    places of a row are then written as a LIST, rising: the row is
    cut into blocks of 256 positions, a block's running count of kept
    places is one small product, and entry ``n`` of the list is the
    block the ``n``-th kept place falls in (a count over the blocks'
    totals) and its place there (a count over that block's running
    counts, fetched for all entries at once by a one-hot product).
    Only the rows that are a token's get a list.

`select_keys` returns ``(T, width)`` int32: for every packed token the
POSITIONS it chose, exactly ``min(top_k, position + 1)`` of them, every
other entry -1, which `ragged_paged_attention` takes as ``select`` and
reads as a list of cache rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu.ops.flash import _compiler_params, _should_interpret
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    _slot_and_page,
    row_block_list,
    row_block_shape,
)

_INT_MIN = -2**31
#: table entries one grid step of the scoring kernel covers, where the
#: table's width divides by it (else 2, else 1)
_PAGES_AN_ITEM = 4
#: what the selection kernel may hold of one group at the widest
#: context (eight rows of 50k scores are 1.6 MB; a dozen temporaries)
_SELECT_VMEM = 96 * 2**20


def _scores_kernel(items_ref, group_ref, slot_ref, tbl_ref, q_ref, w_ref,
                   *refs):
    """One (group, pages) item: ``w relu(q k^T)`` summed over the
    selector's heads, a page of keys (``refs[:-1]``) at a time into
    its lanes of the result.  ``q_ref`` (1, tokens * heads, d_i) token
    major, ``w_ref`` (1, rows, tokens * heads) with token ``u``'s
    weights in row ``u`` at its own heads' columns, so the sum over
    heads is one product."""
    *k_refs, o_ref = refs
    page = k_refs[0].shape[2]
    for u, k_ref in enumerate(k_refs):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, :, u * page:(u + 1) * page] = jax.lax.dot_general(
            w_ref[0], jnp.maximum(s, 0.0), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


#: a register's lanes: the list's width is whole registers
_LANES = 128


def _select_kernel(live_ref, mine_ref, pos_ref, s_ref, o_ref, keep_scr,
                   run_scr, *, top_k: int, bits: int):
    """One group: ``o`` (1, rows, width) = the positions row ``u``
    keeps, rising, then -1.  ``pos_ref`` (1, rows, 128): the row's
    position (every lane), -1 for a row that is nobody's;
    ``mine_ref[g]`` the group's rows that are a token's (its first
    ones)."""
    o_ref[...] = jnp.full_like(o_ref, -1)
    group = pl.program_id(0)
    tokens = mine_ref[group]

    @pl.when(group < live_ref[0])
    def _():
        pos = pos_ref[0][:, :1]
        x = s_ref[0] + 0.0                      # -0.0 is 0.0
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        seen = col <= pos
        raw = jax.lax.bitcast_convert_type(x, jnp.int32)
        # an int32 in the floats' order; what a row does not see, last
        key = jnp.where(raw < 0, raw ^ jnp.int32(0x7FFFFFFF), raw)
        key = jnp.where(seen, key, _INT_MIN)
        k = jnp.minimum(pos + 1, top_k)

        def count(which):
            return jnp.sum(which.astype(jnp.int32), axis=1, keepdims=True)

        # tau = the largest value that k keys reach: the sign, then a
        # bit a pass from the top
        tau = jnp.where(count(key >= 0) >= k, 0, _INT_MIN)

        def value_bit(it, tau):
            cand = tau | jnp.left_shift(jnp.int32(1), 30 - it)
            return jnp.where(count(key >= cand) >= k, cand, tau)

        tau = jax.lax.fori_loop(0, 31, value_bit, tau)
        tied = key == tau
        need = k - count(key > tau)

        # of the keys AT tau the first ``need`` by position: cut = the
        # position of the need-th of them
        def place_bit(it, cut):
            cand = cut | jnp.left_shift(jnp.int32(1), bits - 1 - it)
            return jnp.where(count(tied & (col < cand)) < need, cand, cut)

        cut = jax.lax.fori_loop(0, bits, place_bit, jnp.zeros_like(pos))
        keep = seen & ((key > tau) | (tied & (col <= cut)))
        keep_scr[...] = keep.astype(keep_scr.dtype)
        rows, width = o_ref.shape[1:]
        halves = run_scr.shape[0]               # registers a block
        size = halves * _LANES                  # a block's positions
        blocks = x.shape[1] // size
        f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16

        # every row cut into blocks of ``size`` positions, block major
        # and a register of lanes at a time: rows [b * rows, (b + 1) *
        # rows) of ``run_scr[h]`` are register h of block b of each
        def lay(b, _):
            for h in range(halves):
                run_scr[h, pl.ds(pl.multiple_of(b * rows, rows), rows), :] = (
                    keep_scr[:, pl.ds(pl.multiple_of(
                        b * size + h * _LANES, _LANES), _LANES)])
            return _

        jax.lax.fori_loop(0, blocks, lay, 0)
        # a block's running count of kept places: ONE product a register
        # for every block of every row (counts up to 256 are exact in
        # bfloat16), the registers before it added
        lane = jax.lax.broadcasted_iota(i32, (_LANES, _LANES), 0)
        upto = (lane <= jax.lax.broadcasted_iota(i32, (_LANES, _LANES), 1)
                ).astype(bf16)
        for h in range(halves):
            run = jax.lax.dot_general(
                run_scr[h].astype(bf16), upto, (((1,), (0,)), ((), ())),
                preferred_element_type=f32)
            if h:
                run = run + run_scr[h - 1][:, _LANES - 1:]
            run_scr[h] = run
        n = jax.lax.broadcasted_iota(i32, (1, width), 1).astype(f32)
        of_block = jax.lax.broadcasted_iota(i32, (blocks, 1), 0)
        earlier = (jax.lax.broadcasted_iota(i32, (blocks, blocks), 1)
                   <= of_block).astype(bf16)
        of_block = of_block.astype(f32)

        def listed(u, _):
            run = jnp.concatenate(
                [run_scr.at[h][pl.ds(u, blocks, stride=rows), :]
                 for h in range(halves)], axis=1)           # (blocks, size)
            total = run[:, size - 1:]
            # the row's count up to each block's end
            end = jax.lax.dot_general(
                earlier, jnp.broadcast_to(total, run.shape).astype(bf16),
                (((1,), (0,)), ((), ())), preferred_element_type=f32)[:, :1]
            # entry n sits in the block after those that end at n or
            # before, past the places they hold
            before = end <= n
            b = jnp.sum(before.astype(f32), axis=0, keepdims=True)
            held = jnp.sum(jnp.where(before, total, 0.0), axis=0,
                           keepdims=True)
            # that block's running counts, for every entry at once
            there = jax.lax.dot_general(
                run.astype(bf16), (of_block == b).astype(bf16),
                (((0,), (0,)), ((), ())), preferred_element_type=f32)
            place = jnp.sum((there <= n - held).astype(f32), axis=0,
                            keepdims=True)
            kept = jnp.minimum(pos_ref[0, pl.ds(u, 1), :][:, :1] + 1, top_k)
            o_ref[0, pl.ds(u, 1), :] = jnp.where(
                n < kept.astype(f32), b * size + place, -1.0).astype(i32)
            return _

        jax.lax.fori_loop(0, tokens, listed, 0)


@functools.partial(jax.jit, static_argnames=("top_k", "group", "interpret"))
def _select_keys_jit(q_idx, w_idx, cache: RaggedPagedStep, *, top_k: int,
                     group: int, interpret: bool):
    t_pad, heads, d_i = q_idx.shape
    pool = cache.index_pool
    s_slots, max_pages = cache.page_table.shape
    page = cache.page_size
    if pool is None or pool.shape[1] != 1 or pool.shape[-1] != d_i:
        raise ValueError(
            f"selector queries of {d_i} lanes need an index pool "
            f"(P, 1, page, {d_i}); the step holds "
            f"{None if pool is None else pool.shape}")
    i32 = jnp.int32
    lens = jnp.asarray(cache.kv_lens, i32)
    cu = jnp.asarray(cache.cu_q_lens, i32)
    dist = jnp.asarray(cache.distribution, i32)
    block_tokens, blocks = row_block_shape(cache.q_tile, group)
    # the attention kernel's groups, over items of ``per`` pages
    per = next(n for n in (_PAGES_AN_ITEM, 2, 1) if max_pages % n == 0)
    listed = row_block_list(
        lens, cu, dist, max_pages=max_pages // per, page=page * per,
        block_tokens=block_tokens, blocks=blocks, width=t_pad)
    groups = listed.slot.shape[0]
    rows = -(-block_tokens // 8) * 8
    # a group's tokens: packed index, and position (-1: nobody's)
    u = jnp.arange(block_tokens, dtype=i32)[None, :]
    off = listed.block[:, None] * block_tokens + u
    q_len = (cu[1:] - cu[:-1])[listed.slot][:, None]
    mine = ((jnp.arange(groups, dtype=i32)[:, None] < listed.live)
            & (off < q_len) & (lens[listed.slot][:, None] >= 0))
    tok = jnp.clip(cu[listed.slot][:, None] + off, 0, t_pad - 1)
    pos = jnp.where(mine, lens[listed.slot][:, None] - q_len + off, -1)
    pos = jnp.pad(pos, ((0, 0), (0, rows - block_tokens)),
                  constant_values=-1)
    q_g = q_idx[tok].reshape(groups, block_tokens * heads, d_i)
    # token u's weights in row u, at its own heads' columns
    w_g = (jnp.eye(rows, block_tokens, dtype=jnp.float32)[None, :, :, None]
           * w_idx.astype(jnp.float32)[tok][:, None]).reshape(
               groups, rows, block_tokens * heads)

    def by_group(i, items_ref, group_ref, slot_ref, tbl_ref):
        return (group_ref[i], 0, 0)

    def key_page(u, i, items_ref, group_ref, slot_ref, tbl_ref):
        # a table entry past the slot's prefix reads -1 and fetches
        # page 0 for nobody: no row sees those positions
        j = _slot_and_page(items_ref[i], max_pages // per)[1]
        return (jnp.maximum(tbl_ref[slot_ref[group_ref[i]], j * per + u], 0),
                0, 0, 0)

    def out_page(i, items_ref, group_ref, slot_ref, tbl_ref):
        return (group_ref[i], 0,
                _slot_and_page(items_ref[i], max_pages // per)[1])

    scores = pl.pallas_call(
        _scores_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(listed.n,),
            in_specs=[
                pl.BlockSpec((1, block_tokens * heads, d_i), by_group),
                pl.BlockSpec((1, rows, block_tokens * heads), by_group),
                *(pl.BlockSpec((1, 1, page, d_i),
                               functools.partial(key_page, u))
                  for u in range(per))],
            out_specs=pl.BlockSpec((1, rows, page * per), out_page)),
        out_shape=jax.ShapeDtypeStruct(
            (groups, rows, max_pages * page), jnp.float32),
        compiler_params=_compiler_params(("arbitrary",)),
        name="index_scores",
        interpret=interpret,
    )(listed.items, listed.group, listed.slot, cache.page_table,
      q_g.astype(pool.dtype), w_g, *([pool] * per))

    def whole(g, live_ref, mine_ref):
        return (g, 0, 0)

    max_tokens = max_pages * page
    width = min(-(-top_k // _LANES) * _LANES, max_tokens)
    # the list-making's blocks: two registers of positions where the
    # table allows (the block to search and the blocks to count are
    # then about as many), else one
    size = 2 * _LANES if max_tokens % (2 * _LANES) == 0 else _LANES
    lists = pl.pallas_call(
        functools.partial(_select_kernel, top_k=top_k,
                          bits=max((max_tokens - 1).bit_length(), 1)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(groups,),
            in_specs=[pl.BlockSpec((1, rows, 128), whole),
                      pl.BlockSpec((1, rows, max_tokens), whole)],
            out_specs=pl.BlockSpec((1, rows, width), whole),
            scratch_shapes=[
                pltpu.VMEM((rows, max_tokens), jnp.float32),
                pltpu.VMEM((size // _LANES, max_tokens // size * rows,
                            _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, rows, width), jnp.int32),
        compiler_params=_compiler_params(("arbitrary",),
                                         vmem_limit_bytes=_SELECT_VMEM),
        name="index_select",
        interpret=interpret,
    )(listed.live.reshape(1), jnp.sum(mine, axis=1, dtype=i32),
      jnp.broadcast_to(pos[:, :, None], (groups, rows, 128)), scores)
    # a token's list: row ``u`` of its span's block ``b``, the group
    # ``b`` after its slot's first
    t_slot = jnp.asarray(cache.token_slot, i32)
    at = jnp.maximum(t_slot, 0)
    off = jnp.arange(t_pad, dtype=i32) - cu[at]
    first = jnp.searchsorted(listed.slot, at, side="left",
                             method="compare_all").astype(i32)
    row = ((first + off // block_tokens) * rows + off % block_tokens)
    mine_too = (t_slot >= 0) & (off >= 0) & (off < block_tokens * blocks)
    return jnp.where(
        mine_too[:, None],
        lists.reshape(groups * rows, width)[
            jnp.clip(row, 0, groups * rows - 1)], -1)


def select_keys(q_idx: jax.Array, w_idx: jax.Array, cache: RaggedPagedStep,
                *, top_k: int, group: int,
                interpret: bool | None = None) -> jax.Array:
    """The keys every packed token chose: ``q_idx`` (T, H_i, d_i) the
    selector's queries, ``w_idx`` (T, H_i) its head weights (every
    constant factor folded in), ``cache`` the step AFTER its append
    (``kv_lens`` post-append, ``index_pool`` holding the step's own
    keys too), ``group`` the query heads a KV head of the attention
    (it fixes the blocks of tokens the scoring cuts a span into).
    Returns ``(T, width)`` int32, ``width`` = ``top_k`` rounded up to
    whole registers (the table's capacity at most): token ``t``'s
    ``min(top_k, position + 1)`` chosen positions in its slot, rising,
    every other entry -1 (a pad token's all of them):
    `ragged_paged_attention`'s ``select``."""
    if interpret is None:
        interpret = _should_interpret()
    return _select_keys_jit(q_idx, w_idx, cache, top_k=top_k, group=group,
                            interpret=interpret)


__all__ = ["select_keys"]
