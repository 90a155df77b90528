"""Ragged paged attention: ONE kernel launch for a mixed decode/prefill step.

The serving engine used to lower every step onto two fixed-shape calls —
decode ``(D, 1)`` + prefill-chunk ``(P, S)`` — padded with inactive
poison rows.  This kernel serves the whole step in a single launch over
a PACKED token axis (the tpu_commons ``ragged_paged_attention`` shape):

  * every real token of the step — one per decode request, ``real`` per
    prefill chunk — sits consecutively on one axis of width ``T``;
  * ``cu_q_lens`` (S+1,) delimits each request's token span,
    ``kv_lens`` (S,) holds each request's post-append KV length, and
    ``distribution`` (2,) = (num_decode, num_active) carries the
    decode/prefill split;
  * each request reads KV through its own page-table row, causal within
    the request: the token at span offset ``s`` attends cache positions
    ``<= kv_len - q_len + s``.

The grid is ``(Hkv // hb, n)``: for each BLOCK of ``hb`` kv heads, the
step's ``n`` WORK ITEMS — the (slot, logical page) pairs that hold
something to attend, slot major and page minor (`work_items`).  The
list is built on the device from the lengths the step already carries,
and ``n`` is a traced scalar, a grid bound that is a VALUE: a
decode-only step of five short requests on a 33 x 34 table walks ~35
items, a full table all 1,122, and both run the one compiled kernel.
A slot the step does not use, and a table entry past a request's
prefix or below its window band, is no grid step at all.  The packed
output block of the block's heads stays VMEM-resident while every slot
accumulates into its own row span (slots never overlap rows, so the
read-modify-write at finalize composes), and each item's page
translates through the scalar-prefetched page table like `ops.paged` —
so the pad waste of a step is just ``T - total_real`` bucketed tokens,
not ``(D - d) + (P*S - real)`` poison rows.

A grid step carries EVERY kv head of its page that the core's VMEM
lets the packed rows be resident for (`head_block`: all of them at a
packed width of 512 or less, which is every step the engine makes).
The heads of a page lie together in a pool ``(pages, Hkv, page, d)``,
so the item's keys are one copy of ``hb x 32 KB`` and not ``hb``
copies a grid visit apart; slot, lengths, band and mask are worked out
once an item; and at a decode row's tile the heads' products and
softmax updates run as one batch, independent chains where one head's
waits on itself.  A visit costs what it costs whatever it moves
(~0.4 us at 64 KB, a fifth of it the bytes): four heads a visit took a
(slot, page) of 32 query heads on 4 from 1.67 to 0.67 us on a v5e.

Where a SELECTOR chose each token's keys (`ops.sparse_index`), nothing
above applies: no page is walked.  `ragged_paged_attention` with
``select`` runs a kernel of its own (`_list_kernel`), a grid step a
packed token, which fetches the cache rows of the token's list by its
own copies and attends them in one softmax.

Static tile discipline: the per-request query tile is ``q_tile`` tokens
(>= the longest span; the engine buckets it to a power of two), and
``T`` is pow2-bucketed, so the whole serving life compiles O(log)
executables instead of one per (D, P) composition — the no-recompile-
cliff property the two fixed shapes bought, kept.

Where the group is a multiple of 8, a span of ONE token (a decode
row) is attended at the one-token tile (`span_tile_rows`: 8 rows at a
group of 8, 16 at 16) whatever the step's ``q_tile``: a chunk of 256
tokens in the same step does not make 31 decode slots multiply each of
their pages against 2,048 rows of which they own 8.  A program of such
a group whose tile is wider than one token's holds two tile bodies,
chosen an item by its span's length; a decode-only program, and every
program of another group, holds one, as it always did.  The work list,
the grid, the scratch and the page band are the step's tile's in both:
nothing is chosen outside the kernel, and no option selects it.

``q_tile`` rides in the SHAPE of the cache's ``q_span`` marker field
(shapes are static under jit, values are not) so the engine can pick
the tile per step without threading a static argument through
``model.apply``.

Mesh sharding: both `ragged_paged_append` and `ragged_paged_attention`
are per-KV-head independent — no cross-head reduction anywhere — so
`parallel.serving.head_sharded_ragged_step` runs them inside one
``shard_map`` with the pools and new K/V rows split on the head axis
and every host-packed index array (page table, ``cu_q_lens``,
``kv_lens``, ``distribution``, token placement) replicated verbatim.
Each shard executes this SAME kernel on its contiguous head slice;
zero collectives, and the packed-token axis (and therefore the pad
economics above) is untouched by the shard count.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu import obs
from attention_tpu.ops.decode import banded_live, check_band
from attention_tpu.ops.flash import (
    _LOG2E,
    _STAT_LANES,
    NEG_INF,
    _compiler_params,
    _online_softmax_update,
    _should_interpret,
    _softmax_variant_update,
    _tuned_max_mode,
    check_softcap,
)

# Op-dispatch telemetry (attention_tpu.obs, off by default): one tick
# per host-side dispatch; calls inside an enclosing jit tick per trace.
# `ops.ragged.lowered` ticks at TRACE time inside the jitted body and
# records which rescaling-math variant the dispatch actually lowered
# (the ragged equivalent of `ops.flash.lowered`), how many tile
# bodies the kernel holds: ``bodies`` "one", "two" (a span of one
# token at its own tile beside a wider one), "list" (no tile), and the
# KV heads a grid step carries of those there are: ``heads`` "4/4".
_RAGGED_CALLS = obs.counter(
    "ops.ragged.calls",
    "ragged paged-attention dispatches by (tokens, capacity, dim) bucket")
_RAGGED_LOWERED = obs.counter(
    "ops.ragged.lowered",
    "ragged kernel lowerings by requested/resolved max mode, tile "
    "bodies and heads a grid step")

# Mosaic's default scoped-VMEM budget, and the ceiling a raised budget
# may ask for (the forward kernel's big-tile figure: v4+ cores hold it).
_DEFAULT_SCOPED_VMEM = 16 * 2**20
_MAX_SCOPED_VMEM = 110 * 2**20

#: max_mode values the ragged kernel accepts — "bound" is forward-only
#: (it needs the key-norm prefetch this grid does not carry).
RAGGED_MAX_MODES = ("online", "flashd", "amla", "auto")

#: rows of a page that the append kernel reads, merges and writes
#: back together: whole memory tiles of 32-, 16- and 8-bit pools
_APPEND_ROWS = 32


class RaggedPagedStep(NamedTuple):
    """One packed engine step over the shared page pool.

    ``k_pool``/``v_pool``: (P, Hkv, page_size, d) — the same pools the
    two-call engine steps.  ``page_table``: (S, max_pages) int32, one
    row per request SLOT (inactive slots all -1).  ``kv_lens``: (S,)
    int32 valid cache tokens per slot — PRE-append when handed to
    `ragged_paged_append`, post-append after it (-1 = poisoned).
    ``cu_q_lens``: (S+1,) int32 cumulative token spans; slot ``s`` owns
    packed tokens ``[cu[s], cu[s+1])``.  ``distribution``: (2,) int32
    (num_decode_slots, num_active_slots); decode slots come first.
    ``token_pos``: (T,) int32 absolute cache position of each packed
    token (drives RoPE and the append).  ``token_slot``: (T,)
    int32 owning slot per token, -1 for pad tokens.  ``q_span``: a
    (q_tile,) int32 zeros marker whose SHAPE carries the static
    per-request query-tile width (values unused).

    A LATENT cache is ONE pool: ``v_pool`` is None and a token's row in
    ``k_pool`` (P, 1, page_size, d) is both its key and, in its first
    lanes, its value (`ragged_paged_attention` with ``value_dim``).

    ``index_pool`` (P, 1, page_size, d_i), beside a latent cache: a
    SELECTOR's key of every cached token, under the same page ids as
    ``k_pool`` (a page names the same tokens in both).  Nothing
    attends it; `ops.sparse_index.select_keys` scores it to choose the
    keys a query row attends (`ragged_paged_attention` with
    ``select``).  None for a cache without a selector.
    """

    k_pool: jax.Array
    v_pool: jax.Array | None
    page_table: jax.Array
    kv_lens: jax.Array
    cu_q_lens: jax.Array
    distribution: jax.Array
    token_pos: jax.Array
    token_slot: jax.Array
    q_span: jax.Array
    index_pool: jax.Array | None = None

    @property
    def length(self):
        """Per-slot lengths (uniform name across cache types)."""
        return self.kv_lens

    @property
    def page_size(self) -> int:
        return self.k_pool.shape[2]

    @property
    def q_tile(self) -> int:
        return self.q_span.shape[0]

    @property
    def max_tokens(self) -> int:
        return self.page_table.shape[1] * self.page_size


def packed_bucket(n_tokens: int, *, minimum: int = 8) -> int:
    """Packed-axis width for ``n_tokens`` real tokens.

    Two tiers per octave: the next power of two, refined down to the
    3·2^k midpoint (8, 16, 24, 32, 48, 64, 96, ...) when the midpoint
    still covers ``n_tokens`` and keeps the width 8-aligned (so
    ``width * group`` stays sublane-legal for every GQA group).  The
    midpoint tier halves the worst-case pow2 pad tail (a 33-token step
    pads to 48, not 64) while only DOUBLING the signature count — still
    O(log max_tokens) distinct jit shapes over a serving life, the
    no-recompile-cliff property the pow2 buckets bought.  Idempotent:
    every returned width buckets to itself."""
    if n_tokens < 0:
        raise ValueError(f"n_tokens must be >= 0, got {n_tokens}")
    w = max(minimum, 1)
    while w < n_tokens:
        w *= 2
    mid = 3 * w // 4
    if w >= 4 and mid >= n_tokens and mid >= max(minimum, 1) \
            and mid % 8 == 0:
        w = mid
    return w


def tile_tokens(max_q_len: int, group: int) -> int:
    """Smallest query tile (in tokens) covering ``max_q_len`` whose row
    count ``tile * group`` hits the fp32 sublane granule (8)."""
    if group < 1:
        raise ValueError(f"group must be >= 1, got {group}")
    t = max(int(max_q_len), 1)
    while (t * group) % 8:
        t += 1
    return t


def _row_tile(q_tile: int, t_pad: int, group: int) -> int:
    """Rows of the kernel's per-slot query tile: ``q_tile * group``,
    plus 8 spare rows when the tile start has to be rounded down to the
    sublane granule (group not a multiple of 8 — see `_ragged_kernel`).
    A tile spanning the whole packed axis starts at row 0 and needs
    none."""
    q_rows = q_tile * group
    if group % 8 == 0 or q_tile == t_pad:
        return q_rows
    return q_rows + 8


def span_tile_rows(q_tile: int, t_pad: int, group: int, *,
                   row_blocked: bool = False) -> tuple[int, int]:
    """``(rows, one_token_rows)`` of the program of a ``(t_pad,
    q_tile)`` step: the rows of the tile a span of several tokens is
    attended at, and of the tile a span of ONE token (a decode row) is
    (`_ragged_kernel`).  Equal where the step's tile is the one-token
    tile, every decode-only shape: that program holds one tile body,
    every other two.  The engine counts from this what a step's decode
    rows were served at (``StepMetrics.own_tile_spans``).

    Only a group that is a multiple of 8 gets the second body (8 rows
    at a group of 8, 16 at 16).  At any other group a token's rows do
    not start on the sublane granule and the one-token tile carries
    the rounded start's spare rows (80 rows at a group of 9, 16 at 1).
    That form runs and is right on the chip too (StarCoder2's group of
    9 a quarter faster or more where half the steps hold a chunk), but
    its second body took 0.37 s more to lower in each of that model's
    23 chunk programs, 12% of its set-up: those groups keep the step's
    tile for every span (PERF.md section 6, PR 44; ROADMAP S1 says
    what has to come first)."""
    if row_blocked:
        rows = row_block_shape(q_tile, group)[0] * group
    else:
        rows = _row_tile(q_tile, t_pad, group)
    if group % 8:
        return rows, rows
    return rows, min(rows, _row_tile(tile_tokens(1, group), t_pad, group))


#: rows a batch over the heads of a block holds at most, all heads
#: together: score temporaries of 256 KB.  Every one-token tile of the
#: cells is under it (4 x 8 rows at a group of 8, 4 x 80 at 9, 30 x 16
#: at 1, 2 x 16 at 16); a wider tile's heads go one after the other
_BATCH_ROWS = 512


def _batched(heads: int, rows: int) -> bool:
    """Whether the ``heads`` heads of a block go through a tile of
    ``rows`` rows as one batch (`_ragged_kernel`)."""
    return 1 < heads and heads * rows <= _BATCH_ROWS


def _vmem_need(heads: int, tile_rows: int, one_token_rows: int, *,
               held_rows: int, d: int, dv: int, kv_lanes: int, page: int,
               q_itemsize: int, kv_itemsize: int) -> int:
    """Scoped-VMEM demand of the kernel at a block of ``heads`` KV
    heads: ``held_rows`` rows of query and result a head (the whole
    packed axis, double-buffered by the pipeline, or one block's rows
    in the row-blocked form), the page buffers of ``kv_lanes`` lanes a
    key (K and V, or one pool's), the fp32 scratch, and the (rows,
    page) score / probability temporaries, which a batch over the
    heads holds for all of them and a loop for one."""
    temp_rows = max(heads * rows if _batched(heads, rows) else rows
                    for rows in (tile_rows, one_token_rows))
    return (
        heads * held_rows * (d * q_itemsize + dv * kv_itemsize)
        + heads * 4 * page * kv_lanes * kv_itemsize
        + heads * tile_rows * (dv + 2 * _STAT_LANES) * 4
        + 3 * temp_rows * page * 4
    )


def head_block(hkv: int, q_tile: int, t_pad: int, group: int, *, d: int,
               dv: int, page: int, q_itemsize: int, kv_itemsize: int,
               row_blocked: bool = False,
               budget: int = _MAX_SCOPED_VMEM) -> int:
    """KV heads a grid step of the program of a ``(t_pad, q_tile)``
    step carries (`_ragged_kernel`): the largest divisor of ``hkv``
    whose scoped-VMEM demand, with the half again the call asks for
    over it, stays under ``budget``.  At every serving cell's shapes
    that is every head (a packed width of 512 or less); a wider step
    falls to a smaller block, and a block of 1 is the kernel of one
    head a grid step.  The row-blocked form has one KV head.  The
    engine counts a step's grid from this
    (``StepMetrics.ragged_grid_steps``)."""
    if row_blocked:
        return 1
    tile_rows, one_token_rows = span_tile_rows(q_tile, t_pad, group)
    for heads in range(hkv, 1, -1):
        if hkv % heads == 0 and 1.5 * _vmem_need(
                heads, tile_rows, one_token_rows,
                held_rows=2 * t_pad * group, d=d, dv=dv, kv_lanes=d + dv,
                page=page, q_itemsize=q_itemsize,
                kv_itemsize=kv_itemsize) <= budget:
            return heads
    return 1


def recommended_q_tile(max_q_len: int, group: int, *, heads: int = 1,
                       kv_heads: int | None = None, seq: int = 0,
                       dim: int = 0, batch: int = 1,
                       dtype=None) -> int:
    """Static query-tile width (tokens) for a step whose longest span
    is ``max_q_len``: pow2-bucketed for jit-signature reuse, sublane-
    aligned, optionally widened toward the tuned ``ragged`` family
    ``block_q`` row count when the measured-dispatch tables ship one.

    A step that holds a prefill chunk (``max_q_len`` > 1) never gets a
    tile under 8 tokens.  A group that is no multiple of 8 is held to
    that by the sublane rule anyway (`tile_tokens`); a group that is
    one (16 query heads a KV head) would otherwise get tiles of 1, 2
    and 4 for the short remainders of a prompt, each a compiled
    program at every packed width.  A decode-only step keeps the tile
    `tile_tokens` gives one token.

    A group so large that 8 tokens of it fill a block of the
    row-blocked form (`_BLOCK_ROWS` rows: 128 query heads on one
    latent head, which no other form can hold) is cut into blocks of 8
    tokens whatever the tile: the tile is then nothing but the bound
    on a span's blocks, and a tier of it buys nothing but one more
    compiled program at every packed width.  Such a group gets no
    tile under 256 tokens (7 step shapes where the ten tiers of 8 to
    256 gave 34, at a chunk of 256 beside 32 decode rows), and the
    common tiers above.  The packed width is then 256 or more in
    every step that holds a chunk: at 256 rows the weights' products
    sit on the ridge between the memory's roof and the matrix unit's,
    so the padding costs no time that the weights' bytes do not."""
    t = packed_bucket(max_q_len, minimum=1)
    try:
        from attention_tpu.tuning.lookup import key_fields, lookup

        entry = lookup(
            "ragged", dtype=dtype,
            **key_fields("ragged", heads=heads, kv_heads=kv_heads,
                         seq=seq, dim=dim, batch=batch),
        )
        if entry is not None:
            cap = int(entry["block_q"]) // max(group, 1)
            if cap >= max_q_len:
                t = min(t, cap)
    except Exception:  # noqa: BLE001 - tuning must never break dispatch
        pass
    if max_q_len > 1:
        t = max(t, 8)
        if 8 * group >= _BLOCK_ROWS:
            t = max(t, 256)
    return tile_tokens(t, group)


def _band_window(window: int | None, q_tile: int) -> int | None:
    """The window a slot's PAGES are banded by: it must admit the
    earliest query row of the tile; per-row exactness comes from the
    kernel's mask (the decode kernels' chunk rule)."""
    return None if window is None else window + q_tile - 1


def live_pages(kv_lens, cu_q_lens, distribution, *, max_pages: int,
               page: int, q_tile: int, window: int | None,
               sinks: int | None, xp=jnp):
    """The step's work, as an ``(S, max_pages)`` mask: True where slot
    ``s`` attends logical page ``j``.  ``kv_lens`` are POST-append (-1
    = poisoned).  A slot counts when it is active (below
    ``distribution[1]``, with a token in the step), a page of it by the
    kernel's own compute guard (`ops.decode.banded_live`: inside the
    prefix, and inside the window band or the sinks).

    Two entries hold no page to attend and are kept all the same, at
    page 0: an active slot with no live page (poisoned) is still
    FINALISED, which is what makes its rows NaN; and a step with no
    active slot still visits slot 0 once, which is what zeroes the
    output block.

    ``xp`` is the array namespace: the device builds the kernel's grid
    from this mask (`work_items`), and the engine counts it on the host
    (``xp=numpy``) for the ``kv_pages`` field of its step span."""
    s_slots = kv_lens.shape[0]
    slot = xp.arange(s_slots)
    active = (slot < distribution[1]) & (cu_q_lens[1:] > cu_q_lens[:-1])
    j = xp.arange(max_pages)[None, :]
    live = active[:, None] & banded_live(
        j, xp.maximum(kv_lens, 0)[:, None], page,
        _band_window(window, q_tile), sinks)
    bare = (active & ~live.any(axis=1)) | ((slot == 0) & ~active.any())
    return live | (bare[:, None] & (j == 0))


def work_items(mask):
    """`live_pages` as the kernel's grid walks it: ``(items, n)``, the
    first ``n`` of ``items`` being the True entries' flat indices
    ``s * max_pages + j`` in rising order, every later one the
    sentinel ``S * max_pages`` (slot ``S``: no slot's neighbour).  One
    entry longer than the mask, so that the item after the last is
    always there to read."""
    flat = mask.reshape(-1)
    filled = jnp.cumsum(flat.astype(jnp.int32))
    # item i is where the running count first reaches i + 1: one
    # compare-and-count fusion (`jnp.nonzero(size=)` would count
    # through a scatter)
    items = jnp.searchsorted(
        filled, jnp.arange(1, flat.shape[0] + 2, dtype=jnp.int32),
        side="left", method="compare_all")
    return items.astype(jnp.int32), filled[-1]


def _slot_and_page(item, max_pages: int):
    """A work item's slot and logical page (`work_items`): one scalar
    divide and one remainder (items are never negative)."""
    width = jnp.int32(max_pages)
    return jax.lax.div(item, width), jax.lax.rem(item, width)


class RowBlocks(NamedTuple):
    """The work of the ROW-BLOCKED form (`row_block_list`).  A GROUP
    is one block of ``block_tokens`` tokens of one slot's span;
    ``items[:n]`` are the (group, page) pairs to visit, coded ``(slot
    * blocks + block) * max_pages + page``, every later entry the
    sentinel; ``group[i]`` is item ``i``'s group, ``slot`` / ``block``
    a group's slot and its place in the slot's span, and the first
    ``live`` groups are the step's."""

    items: jax.Array
    n: jax.Array
    group: jax.Array
    slot: jax.Array
    block: jax.Array
    live: jax.Array


def row_block_shape(q_tile: int, group: int) -> tuple[int, int]:
    """``(block_tokens, blocks)`` of the row-blocked form at a step's
    query tile: blocks of `_BLOCK_ROWS` rows at most, whole tokens,
    ``blocks`` of them to the tile."""
    block_tokens = min(q_tile, max(_BLOCK_ROWS // group, 1))
    return block_tokens, -(-q_tile // block_tokens)


def row_block_list(kv_lens, cu_q_lens, distribution, *, max_pages: int,
                   page: int, block_tokens: int, blocks: int,
                   width: int) -> RowBlocks:
    """The work list of the ROW-BLOCKED form (`_ragged_kernel` with
    ``blocks``): an item is page ``j`` for the ``b``-th block of
    ``block_tokens`` tokens of a slot's span.  Slot major, then block,
    then page, so that a block's pages follow each other.  A block's
    pages are those its LAST row reaches (causal, no window), which is
    a prefix of the table row, so the list is built from counts: which
    slot a block belongs to, then which block an item belongs to, two
    small searches where the mask form would search ``slots * blocks *
    max_pages`` entries.

    The two entries `live_pages` keeps are kept here too: an active
    slot whose length is poisoned still has one item a block (it is
    finalised, as NaN), and a step with no active slot visits slot 0
    once.  The list's static length is ``(slots + width //
    block_tokens) * max_pages + 1``: a slot has one block more than its
    whole ones."""
    s_slots = kv_lens.shape[0]
    i32 = jnp.int32
    slot = jnp.arange(s_slots, dtype=i32)
    q_lens = (cu_q_lens[1:] - cu_q_lens[:-1]).astype(i32)
    active = (slot < distribution[1]) & (q_lens > 0)
    bt, pg = i32(block_tokens), i32(page)
    nb = jnp.where(active, jax.lax.div(q_lens + bt - 1, bt), 0)
    nb = jnp.where((slot == 0) & ~active.any(), 1, nb)
    nb = jnp.minimum(nb, blocks)
    slot_end = jnp.cumsum(nb)
    groups = s_slots + width // block_tokens
    g = jnp.arange(groups, dtype=i32)
    g_slot = jnp.minimum(jnp.searchsorted(
        slot_end, g, side="right", method="compare_all").astype(i32),
        s_slots - 1)
    g_block = g - (slot_end - nb)[g_slot]
    q_len = q_lens[g_slot]
    reach = (jnp.maximum(kv_lens, 0)[g_slot] - q_len
             + jnp.minimum((g_block + 1) * bt, q_len))
    pages = jnp.clip(jax.lax.div(reach + pg - 1, pg), 1, max_pages)
    pages = jnp.where(g < slot_end[-1], pages, 0)
    group_end = jnp.cumsum(pages)
    n = group_end[-1]
    i = jnp.arange(groups * max_pages + 1, dtype=i32)
    of = jnp.minimum(jnp.searchsorted(
        group_end, i, side="right", method="compare_all").astype(i32),
        groups - 1)
    j = i - (group_end - pages)[of]
    items = (g_slot[of] * blocks + g_block[of]) * max_pages + j
    return RowBlocks(
        jnp.where(i < n, items, s_slots * blocks * max_pages), n, of,
        g_slot, g_block, slot_end[-1])


def row_block_count(kv_lens, cu_q_lens, distribution, *, max_pages: int,
                    page: int, block_tokens: int, blocks: int) -> int:
    """`row_block_list`'s ``n``, the row-blocked form's grid, counted
    on the host in NumPy by its rule: a block of an active slot's span
    has the pages its last row reaches, one at the least, and a step
    with no active slot one item."""
    q_lens = np.diff(cu_q_lens)
    active = (np.arange(len(kv_lens)) < distribution[1]) & (q_lens > 0)
    b = np.arange(blocks)[None, :]
    held = active[:, None] & (b * block_tokens < q_lens[:, None])
    reach = (np.maximum(kv_lens, 0) - q_lens)[:, None] + np.minimum(
        (b + 1) * block_tokens, q_lens[:, None])
    pages = np.clip(-(-reach // page), 1, max_pages)
    return int((pages * held).sum()) or 1


def _ragged_kernel(
    lens_ref, cu_ref, dist_ref, tbl_ref, items_ref, q_ref, k_ref, *rest,
    max_pages: int, group: int, page: int, q_tile: int, t_pad: int,
    tile_rows: int, one_token_rows: int, softcap2, window: int | None,
    sinks: int | None, variant: str = "online", dv: int = 0,
    shared_kv: bool = False, blocks: int = 0, block_tokens: int = 0,
    heads: int = 1,
):
    """One (head block, work item) grid step: item ``i`` is page ``j``
    of slot ``r`` (`work_items`), for the ``heads`` KV heads of the
    block at once (`head_block`).  Slot, page, lengths, band and tile
    start are the item's and are worked out once; the heads then go
    through each phase as ONE BATCH where their tiles together are
    small (`_BATCH_ROWS`: every one-token tile of the cells, four
    independent softmax chains where one waited on itself), and one
    after the other in a loop at a wider tile, whose products bind and
    whose score temporaries stay one head's.  A head's result is the
    same operations on the same operands at any block; a block of one
    head indexes head 0 and holds no batch and no loop.

    The output block is the FULL packed row axis of the block's heads,
    index-mapped constant over the items, so it stays VMEM-resident
    while every slot finalizes its own row span into it — the
    single-launch analog of one out-block per decode row.  Slot spans
    never overlap, a slot's items follow each other in page order, and
    the grid is sequential ("arbitrary" semantics), so the masked
    read-modify-write at finalize is race-free.

    A span is loaded, attended and finalised at ``tile_rows`` rows, but
    a span of ONE token (``q_len <= 1``: a decode row) at
    ``one_token_rows``, with a tile start of its own, on the head of
    each scratch (`span_tile_rows`).  Where the two differ, every
    program that can hold a chunk at a group that is a multiple of 8,
    the kernel holds a body for each and an item takes the one its
    span's length names; where they are equal (every decode-only
    shape, every other group) it holds one.  The rows a decode slot no
    longer computes are rows its finalize's mask threw away.  The
    slot's PAGES are still those the step's tile bands (`live_pages`):
    the list is the host's count and the window pages' band too.

    ``shared_kv``: the values are the first ``dv`` lanes of the key
    block (a latent cache: one pool, no ``v_ref``).

    ``blocks`` > 0 is the ROW-BLOCKED form, for a head whose packed
    rows do not fit VMEM (one latent KV head is the group of EVERY
    query head).  The packed query and result stay in HBM; an item is
    a page of one BLOCK of ``block_tokens`` tokens of a slot's span
    (`row_block_list`), whose rows are copied in at the block's first
    item and out at its last.  A span of one token takes its own
    tile here too (a block's, where the resident form has the step's):
    a page is read once for all of a decode row's heads.  The tile
    arithmetic is the resident form's."""
    if shared_kv:
        v_ref = None
    else:
        v_ref, *rest = rest
    if blocks:
        _, o_ref, acc_scr, m_scr, l_scr, q_scr, o_scr, sem = rest
    else:
        o_ref, acc_scr, m_scr, l_scr = rest
    hd = pl.program_id(0)
    i = pl.program_id(1)
    r, j = _slot_and_page(items_ref[i], max_pages)
    first = jnp.logical_or(
        i == 0,
        _slot_and_page(items_ref[jnp.maximum(i - 1, 0)], max_pages)[0] != r)
    last = _slot_and_page(items_ref[i + 1], max_pages)[0] != r
    block = 0
    if blocks:  # the item's first field is (slot, block of its span)
        r, block = (jax.lax.div(r, jnp.int32(blocks)),
                    jax.lax.rem(r, jnp.int32(blocks)))
    raw_len = lens_ref[r]
    kv_len = jnp.maximum(raw_len, 0)  # poisoned slots read nothing
    q_start = cu_ref[r]
    q_len = cu_ref[r + 1] - q_start
    active = jnp.logical_and(r < dist_ref[1], q_len > 0)
    if blocks:
        # a block starts at a token, and ``group`` is a multiple of 8
        block_start = pl.multiple_of(
            (q_start + block * block_tokens) * group, 8)
        # what the block's last row reaches: later pages are no items
        live = jnp.logical_and(active, j * page < kv_len - q_len
                               + jnp.minimum((block + 1) * block_tokens,
                                             q_len))
    else:
        # false only for the two kept entries that hold no page
        # (`live_pages`)
        live = jnp.logical_and(
            active, banded_live(j, kv_len, page,
                                _band_window(window, q_tile), sinks))

    def span_start(rows: int):
        """Where a tile of ``rows`` rows starts, in packed ROWS (token
        * group + head): the span head rounded down to the 8-row
        sublane granule, clamped so the tile stays in-bounds.  Mosaic
        refuses a dynamic sublane slice of a 16-bit ref unless it can
        prove the start 8-aligned, and ``q_start * group`` is only
        provably so when group % 8 == 0 — so the start is aligned here
        and the tile carries `_row_tile`'s 8 spare rows (a span fits
        the tile it is given by the caller contract, ``q_len <=
        q_tile``; rows outside it are masked per row)."""
        return pl.multiple_of(
            jnp.minimum(q_start * group // 8 * 8, t_pad * group - rows), 8)

    def init(m, l, acc):
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)
        acc[...] = jnp.zeros_like(acc)

    def attend(h, qb, tile_start, m, l, acc):
        """Head ``h``'s tile against the item's page; ``h`` a slice:
        every head of the block, one batch."""
        keys = k_ref[0, h]
        batch = ((0,), (0,)) if qb.ndim == 3 else ((), ())
        s = jax.lax.dot_general(
            qb, keys, (((qb.ndim - 1,), (qb.ndim - 1,)), batch),
            preferred_element_type=jnp.float32,
        )  # (q_rows, page), log2-domain (q pre-scaled by scale*log2e)
        if softcap2 is not None:
            s = softcap2 * jnp.tanh(s / softcap2)
        # the mask is the item's, the same for every head of a batch
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        seg = (tile_start + row) // group - q_start  # span offset per row
        pos = kv_len - q_len + seg             # absolute cache position
        col = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        mask = jnp.logical_and(
            jnp.logical_and(seg >= 0, seg < q_len), col <= pos
        )
        if window is not None:
            win = col >= pos - (window - 1)
            if sinks is not None:
                win = jnp.logical_or(win, col < sinks)
            mask = jnp.logical_and(mask, win)
        s = jnp.where(mask, s, NEG_INF)
        p, update_acc = _softmax_variant_update(
            s, m, l, variant=variant, masked=True)
        values = keys[:, :dv] if shared_kv else v_ref[0, h]
        pv = jax.lax.dot_general(
            p.astype(values.dtype), values,
            (((p.ndim - 1,), (p.ndim - 2,)), batch),
            preferred_element_type=jnp.float32,
        )
        acc[...] = update_acc(acc[...], pv)

    def result(tile_start, l, acc):
        """The tile's rows, and which of them are this span's."""
        if variant == "flashd":
            # the accumulator is already normalized (flashd's hidden
            # division) — the per-slot epilogue loses its divide
            res = acc[...]
        else:
            l_max = jnp.max(l[...], axis=-1, keepdims=True)
            res = acc[...] / jnp.where(l_max == 0.0, 1.0, l_max)
        # poisoned slots (bad append, length -1) emit NaN, loudly
        res = jnp.where(raw_len < 0, jnp.nan, res)
        row = jax.lax.broadcasted_iota(jnp.int32, res.shape[-2:], 0)
        seg = (tile_start + row) // group - q_start
        return res, jnp.logical_and(seg >= 0, seg < q_len)

    def head(ref, rows: int):
        return ref.at[pl.ds(0, rows)]

    def over_heads(rows: int, phase):
        """``phase(h)`` for the block's heads: all at once, ``h`` a
        slice, where a tile of ``rows`` rows a head keeps the batch
        small; else head by head in a loop, never unrolled."""
        if heads == 1:
            phase(0)
        elif _batched(heads, rows):
            phase(slice(None))
        else:
            def turn(h, carry):
                phase(h)
                return carry

            jax.lax.fori_loop(0, heads, turn, 0)

    def span_of(rows: int, mine_too):
        """The three phases of a span of the RESIDENT form at a tile of
        ``rows`` rows, the head of each head's scratch."""
        tile_start = span_start(rows)

        def scratch(h):
            return [ref.at[h, pl.ds(0, rows)]
                    for ref in (m_scr, l_scr, acc_scr)]

        @pl.when(jnp.logical_and(mine_too, first))
        def _init():
            init(*scratch(slice(None)))

        @pl.when(jnp.logical_and(mine_too, live))
        def _tile():
            over_heads(rows, lambda h: attend(
                h, q_ref[h, pl.ds(tile_start, rows), :], tile_start,
                *scratch(h)))

        @pl.when(jnp.logical_and(mine_too, jnp.logical_and(last, active)))
        def _finalize():
            def write(h):
                res, mine = result(tile_start, *scratch(h)[1:])
                cur = o_ref[h, pl.ds(tile_start, rows), :]
                o_ref[h, pl.ds(tile_start, rows), :] = jnp.where(
                    mine, res, cur.astype(jnp.float32)
                ).astype(o_ref.dtype)

            over_heads(rows, write)

    def block_of(rows: int, mine_too):
        """The three phases of a block at a tile of ``rows`` rows, the
        head of each scratch.  The result's rows past the span are
        zeros: they are pad tokens' rows, or rows of a later block or
        slot, which writes them after this one."""
        m, l, acc = head(m_scr, rows), head(l_scr, rows), head(acc_scr, rows)

        @pl.when(jnp.logical_and(mine_too, jnp.logical_and(first, active)))
        def _load():
            rows_in = pltpu.make_async_copy(
                q_ref.at[hd, pl.ds(block_start, rows)], head(q_scr, rows),
                sem.at[0])
            rows_in.start()
            init(m, l, acc)
            rows_in.wait()

        @pl.when(jnp.logical_and(mine_too, live))
        def _tile():
            attend(0, q_scr[pl.ds(0, rows), :], block_start, m, l, acc)

        @pl.when(jnp.logical_and(mine_too, jnp.logical_and(last, active)))
        def _store():
            res, mine = result(block_start, l, acc)
            o_scr[pl.ds(0, rows), :] = jnp.where(mine, res, 0.0).astype(
                o_scr.dtype)
            rows_out = pltpu.make_async_copy(
                head(o_scr, rows), o_ref.at[hd, pl.ds(block_start, rows)],
                sem.at[1])
            rows_out.start()
            rows_out.wait()

    if not blocks:
        @pl.when(i == 0)
        def _zero_out():
            o_ref[...] = jnp.zeros_like(o_ref)

    phases = block_of if blocks else span_of
    if tile_rows == one_token_rows:
        phases(tile_rows, True)
    else:
        phases(one_token_rows, q_len <= 1)
        phases(tile_rows, q_len > 1)


#: cache rows one copy of the list form moves: a memory tile's rows,
#: the least of a pool that a copy can name
_LIST_ROWS = 8
#: copies the list form starts a turn of its loop
_LIST_UNROLL = 16
#: list entries the list form attends at a time: their copies share a
#: semaphore, and their tiles are one block of the softmax
_LIST_PART = 128


def _list_rows_kernel(slot_ref, list_ref, tbl_ref, o_ref, *, page: int):
    """Where a token's listed positions lie in the pool: ``o`` (1, 1,
    entries) = the first row of the memory tile that holds position
    ``list[n]`` of the token's slot, ``(table[slot, pos // page] *
    page + pos % page) // 8 * 8``, for all entries at once: the
    slot's table row (``tbl_ref`` (max_pages, slots): the table,
    pages down) against the entries' pages, a compare and a sum.  An
    entry that is none, and a table entry that is unclaimed, give a
    row that exists (position 0's, page 0's): it is fetched for
    nobody."""
    at = jnp.maximum(list_ref[0], 0)                        # (1, entries)
    held = tbl_ref[...]
    mine = jnp.sum(
        jnp.where(jax.lax.broadcasted_iota(jnp.int32, held.shape, 1)
                  == slot_ref[pl.program_id(0)], held, 0),
        axis=1, keepdims=True)                              # (max_pages, 1)
    of_page = jax.lax.broadcasted_iota(jnp.int32, mine.shape, 0)
    there = jnp.sum(jnp.where(of_page == at // page, mine, 0), axis=0,
                    keepdims=True)
    o_ref[0] = (jnp.maximum(there, 0) * page
                + at % page // _LIST_ROWS * _LIST_ROWS)


def _list_kernel(pos_ref, list_ref, q_ref, keys_ref, pool_ref, o_ref,
                 cnt_ref, buf, sem, m_scr, l_scr, acc_scr, *, dv: int):
    """One packed token a grid step, a step LATE: step ``i`` starts the
    copies of token ``i`` and attends token ``i - 1`` (``q_ref`` (1,
    group, d) its heads' rows, pre-scaled into the log2 domain)
    against the cache rows of ITS list and nothing else of the pool.

    ``list_ref`` (1, 1, entries) in SMEM: for each position token
    ``i`` chose, the first row of the memory tile it lies in
    (`_list_rows_kernel`; ``pool_ref``: the pool as rows, in HBM).  A
    copy moves that tile's `_LIST_ROWS` rows to rows ``[8 n, 8 n +
    8)`` of the token's half of ``buf``, so the softmax runs over ``8
    x entries`` rows of which ``keys_ref`` (1, 1, 8 x entries) names
    the wanted ones: the position where row ``8 n + u`` is entry
    ``n``'s own, -1 where it is a neighbour (or the entry is none).
    The softmax takes `_LIST_PART` entries' tiles at a time, online,
    float32, each part as soon as
    its own copies have landed.  Token ``i``'s copies go to the other
    half of ``buf``, and land while token ``i - 1``'s products run.

    ``pos_ref[t]``: the token's position, -1 for a token that is
    nobody's (zeros), -2 for one of a poisoned slot (NaN): neither
    fetches anything.  ``cnt_ref`` (1, 8 x entries) int32, resident:
    the mask the softmax is given (wanted, causal, a real token's),
    summed over the tokens."""
    step = pl.program_id(0)
    t = jnp.maximum(step - 1, 0)                # the token attended
    mine = pos_ref[t]
    parts = list_ref.shape[-1] // _LIST_PART
    wide = _LIST_PART * _LIST_ROWS              # a part's rows of ``buf``

    def copy(first, half, part, n):
        return pltpu.make_async_copy(
            pool_ref.at[pl.ds(pl.multiple_of(first, _LIST_ROWS), _LIST_ROWS)],
            buf.at[half, pl.ds(pl.multiple_of(n * _LIST_ROWS, _LIST_ROWS),
                               _LIST_ROWS)],
            sem.at[half, part])

    @pl.when(step == 0)
    def _zero_count():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(jnp.logical_and(step < pl.num_programs(0) - 1,
                             pos_ref[jnp.minimum(step, pos_ref.shape[0] - 1)]
                             >= 0))
    def _fetch():
        half = jax.lax.rem(step, 2)

        def turn(i, _):
            part = i // (_LIST_PART // _LIST_UNROLL)
            for u in range(_LIST_UNROLL):
                n = i * _LIST_UNROLL + u
                copy(list_ref[0, 0, n], half, part, n).start()
            return _

        jax.lax.fori_loop(0, parts * _LIST_PART // _LIST_UNROLL, turn, 0)

    @pl.when(jnp.logical_and(step > 0, mine < 0))
    def _nobody():
        o_ref[...] = jnp.full_like(o_ref, jnp.where(mine == -2, jnp.nan, 0.0))

    @pl.when(jnp.logical_and(step > 0, mine >= 0))
    def _attend():
        half = jax.lax.rem(t, 2)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def attend(part, _):
            span = pl.ds(pl.multiple_of(part * wide, wide), wide)
            # ONE wait for the part's copies: they signal one semaphore,
            # which counts what has landed, and this asks for their sum
            pltpu.make_async_copy(pool_ref.at[pl.ds(0, wide)],
                                  buf.at[half, span],
                                  sem.at[half, part]).wait()
            rows = buf[half, span, :]
            s = jax.lax.dot_general(
                q_ref[0], rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # (group, wide)
            at = keys_ref[0, :, span]
            mask = jnp.logical_and(at >= 0, at <= mine)
            cnt_ref[:, span] += mask.astype(jnp.int32)
            p, corr = _online_softmax_update(
                jnp.where(mask, s, NEG_INF), m_scr, l_scr, masked=True)
            acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :dv], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return _

        jax.lax.fori_loop(0, parts, attend, 0)
        l = jnp.max(l_scr[...], axis=-1, keepdims=True)
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _list_attention(qs, cache: RaggedPagedStep, select, *, dv: int,
                    out_dtype, interpret: bool):
    """The LIST form (`_list_kernel`): ``qs`` (T, group, d) token-major
    scaled rows, ``select`` (T, entries) int32 the positions each token
    attends.  Returns ``((T, group, dv), attended)``."""
    t_pad, group, d = qs.shape
    pool = cache.k_pool
    page = pool.shape[2]
    s_slots, max_pages = cache.page_table.shape
    if (select.ndim != 2 or select.shape[0] != t_pad
            or select.shape[1] % _LIST_PART):
        raise ValueError(
            f"select {select.shape}: a list of positions a packed token, "
            f"({t_pad}, a multiple of {_LIST_PART})")
    if page % _LIST_ROWS:
        raise ValueError(f"page size {page} is not a multiple of the "
                         f"{_LIST_ROWS} rows one copy moves")
    entries = select.shape[1]
    i32 = jnp.int32
    lens = jnp.asarray(cache.kv_lens, i32)
    cu = jnp.asarray(cache.cu_q_lens, i32)
    slot = jnp.asarray(cache.token_slot, i32)
    at = jnp.clip(slot, 0, s_slots - 1)
    q_len = (cu[1:] - cu[:-1])[at]
    off = jnp.arange(t_pad, dtype=i32) - cu[at]
    real = ((slot >= 0) & (slot < cache.distribution[1])
            & (off >= 0) & (off < q_len))
    # a token's position by the device's rule; -1 nobody's, -2 poisoned
    mine = jnp.where(real, jnp.where(lens[at] < 0, -2,
                                     lens[at] - q_len + off), -1)
    # which of a tile's rows is the entry's own: positions and pool
    # rows agree modulo `_LIST_ROWS` (a page is whole tiles)
    select = jnp.asarray(select, i32)
    keys = jnp.where(
        (select[:, :, None] >= 0)
        & (select[:, :, None] % _LIST_ROWS
           == jnp.arange(_LIST_ROWS, dtype=i32)), select[:, :, None], -1)
    wide = entries * _LIST_ROWS
    item = pool.dtype.itemsize
    vmem = (2 * wide * d * item
            + 5 * group * _LIST_PART * _LIST_ROWS * 4
            + 4 * group * (d + dv) * item + group * dv * 8)
    listed = pl.BlockSpec((1, 1, entries), lambda i, *_: (i, 0, 0))
    tiles = pl.pallas_call(
        functools.partial(_list_rows_kernel, page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t_pad,),
            in_specs=[listed,
                      pl.BlockSpec((max_pages, s_slots),
                                   lambda i, *_: (0, 0))],
            out_specs=listed),
        out_shape=jax.ShapeDtypeStruct((t_pad, 1, entries), i32),
        compiler_params=_compiler_params(("parallel",)),
        name="ragged_paged_list_rows",
        interpret=interpret,
    )(at, select[:, None, :], cache.page_table.T)

    def late(i, *_):
        return (jnp.maximum(i - 1, 0), 0, 0)

    out, count = pl.pallas_call(
        functools.partial(_list_kernel, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # a step more than tokens: step i fetches token i's rows
            # and attends token i - 1
            grid=(t_pad + 1,),
            in_specs=[
                pl.BlockSpec((1, 1, entries),
                             lambda i, *_: (jnp.minimum(i, t_pad - 1), 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, group, d), late),
                pl.BlockSpec((1, 1, wide), late),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, group, dv), late),
                pl.BlockSpec((1, wide), lambda i, *_: (0, 0))],
            scratch_shapes=[pltpu.VMEM((2, wide, d), pool.dtype),
                            pltpu.SemaphoreType.DMA(
                                (2, entries // _LIST_PART)),
                            pltpu.VMEM((group, _STAT_LANES), jnp.float32),
                            pltpu.VMEM((group, _STAT_LANES), jnp.float32),
                            pltpu.VMEM((group, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((t_pad, group, dv), out_dtype),
                   jax.ShapeDtypeStruct((1, wide), i32)],
        # NOT parallel: the count's block is every token's
        compiler_params=_compiler_params(
            ("arbitrary",),
            vmem_limit_bytes=min(max(int(vmem * 1.25), _DEFAULT_SCOPED_VMEM),
                                 _MAX_SCOPED_VMEM)),
        cost_estimate=pl.CostEstimate(
            flops=2 * t_pad * group * wide * (d + dv),
            bytes_accessed=t_pad * (wide * d * item + group * (d + dv) * item
                                    + wide * 4),
            transcendentals=t_pad * group * wide),
        name="ragged_paged_list_attention",
        interpret=interpret,
    )(mine, tiles, qs, keys.reshape(t_pad, 1, wide), pool.reshape(-1, d))
    return out, jnp.sum(count)


#: rows of a block of the row-blocked form (`_ragged_kernel`): what a
#: block keeps in VMEM is about 7 KB a row at keys of 576
_BLOCK_ROWS = 1024


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "softcap", "window", "sinks",
                     "max_mode", "value_dim"),
)
def _ragged_paged_attention_jit(
    q: jax.Array,            # (1, Hq, T, d) packed token axis
    cache: RaggedPagedStep,
    *,
    scale: float | None = None,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
    max_mode: str = "online",
    value_dim: int | None = None,
    select: jax.Array | None = None,
) -> jax.Array:
    """softmax(q K^T * scale) V for every packed token through its
    slot's page table, causal within each request — (1, Hq, T, dv).

    ``kv_lens`` must be POST-append (run `ragged_paged_append` first);
    pad tokens return zeros, poisoned slots NaN.  ``window``/``sinks``
    are the decode kernels' per-request logical band, applied to the
    work list (`live_pages`), so a page outside the band is never
    visited.  ``max_mode`` picks the rescaling math ("online"/"flashd"/
    "amla" — the per-slot masked read-modify-write finalize is exactly
    the epilogue flashd and amla cheapen); "auto" consults the tuning
    tables (ragged family) and falls back to "online".

    A cache with ONE pool (``v_pool`` None: a latent cache) gives keys
    and values from the same page block, the values its first
    ``value_dim`` lanes, and runs the kernel's row-blocked form: its
    one KV head is the group of every query head, so a token's rows
    alone are a tile, and the whole packed rows of that head (89 MB at
    a width of 320, 64 heads and 576 / 512 lanes) are not held in
    VMEM.  No window there.

    ``select`` (a cache of one pool only): `ops.sparse_index.
    select_keys`' result, the positions each token CHOSE.  The pool is
    then not walked at all: a kernel of its own reads the listed cache
    rows and nothing else (`_list_attention`), and the call returns
    ``(out, attended)``, ``attended`` the int32 count of (query token,
    key) pairs the mask let through, counted in the kernel from the
    mask the softmax is given and not from the list's length."""
    check_softcap(softcap)
    check_band(window, sinks)
    if q.ndim != 4 or q.shape[0] != 1:
        raise ValueError(
            f"packed q must be (1, Hq, T, d), got {q.shape}"
        )
    _, h, t_pad, d = q.shape
    p_, hkv, page, dk = cache.k_pool.shape
    shared_kv = cache.v_pool is None
    if shared_kv:
        if value_dim is None or not 0 < value_dim <= dk:
            raise ValueError(
                f"a cache of one pool needs value_dim in (0, {dk}], the "
                f"lanes of a key that are its value; got {value_dim}")
        if window is not None:
            raise ValueError("window: the row-blocked form a cache of "
                             "one pool takes has no band")
        dv, out_dtype = value_dim, cache.k_pool.dtype
    else:
        if value_dim is not None:
            raise ValueError("value_dim is for a cache of one pool")
        dv, out_dtype = cache.v_pool.shape[-1], cache.v_pool.dtype
    s_slots, max_pages = cache.page_table.shape
    if dk != d or (not shared_kv
                   and cache.v_pool.shape[:3] != (p_, hkv, page)):
        raise ValueError(
            f"ragged cache shapes inconsistent: Q{q.shape} "
            f"K{cache.k_pool.shape} "
            f"V{None if shared_kv else cache.v_pool.shape}"
        )
    if cache.cu_q_lens.shape != (s_slots + 1,):
        raise ValueError(
            f"cu_q_lens {cache.cu_q_lens.shape} must be "
            f"({s_slots + 1},) for a {s_slots}-slot table"
        )
    if page % 128:
        raise ValueError(f"page_size {page} must be a multiple of 128")
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    group = h // hkv
    q_tile = cache.q_tile
    if (t_pad * group) % 8 or (q_tile * group) % 8:
        raise ValueError(
            f"packed width {t_pad} and q_tile {q_tile} must keep "
            f"token*group row counts 8-aligned (group {group}); use "
            "packed_bucket/tile_tokens"
        )
    if q_tile > t_pad:
        raise ValueError(f"q_tile {q_tile} > packed width {t_pad}")
    if select is not None and not shared_kv:
        raise ValueError("select: a choice of keys goes with a cache of "
                         "one pool")
    if shared_kv and group % 8:
        raise ValueError(f"a cache of one pool needs a group that is a "
                         f"multiple of 8, got {group}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    if max_mode not in RAGGED_MAX_MODES:
        raise ValueError(
            f"unknown ragged max_mode {max_mode!r}; one of "
            f"{RAGGED_MAX_MODES} (bound mode is forward-only)")
    variant = max_mode
    if variant == "auto":
        variant = _tuned_max_mode(
            "ragged", dtype=q.dtype, allowed=("online", "flashd", "amla"),
            heads=h, kv_heads=hkv, seq=cache.max_tokens, dim=d,
            batch=s_slots, window=window, sinks=sinks)
    # the tile of a span of several tokens and of a span of one: where
    # they differ the kernel holds a body for each (`_ragged_kernel`)
    tile_rows, one_token_rows = span_tile_rows(
        q_tile, t_pad, group, row_blocked=shared_kv)
    # the KV heads a grid step carries: every one the budget lets the
    # resident form hold (`head_block`)
    kv_item = cache.k_pool.dtype.itemsize
    heads = head_block(
        hkv, q_tile, t_pad, group, d=d, dv=dv, page=page,
        q_itemsize=q.dtype.itemsize, kv_itemsize=kv_item,
        row_blocked=shared_kv)
    if obs.is_enabled():
        _RAGGED_LOWERED.inc(
            requested=max_mode, lowered=variant,
            bodies=("list" if select is not None else
                    "one" if tile_rows == one_token_rows else "two"),
            heads=f"{heads}/{hkv}")

    lens = jnp.asarray(cache.kv_lens, jnp.int32)
    cu = jnp.asarray(cache.cu_q_lens, jnp.int32)
    dist = jnp.asarray(cache.distribution, jnp.int32)
    # token-major packed rows: row = token * group + group_head, so a
    # span's rows are contiguous and the per-slot tile is one dynamic
    # sublane slice
    qs = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    qs = qs[0].reshape(hkv, group, t_pad, d).transpose(0, 2, 1, 3)
    qs = qs.reshape(hkv, t_pad * group, d)
    if select is not None:
        if softcap is not None or sinks is not None:
            raise ValueError("select: the list form has no softcap and no "
                             "sinks")
        out, attended = _list_attention(
            qs.reshape(t_pad, group, d), cache, select, dv=dv,
            out_dtype=out_dtype, interpret=interpret)
        return out.transpose(1, 0, 2)[None], attended
    # the row-blocked form: blocks of `_BLOCK_ROWS` rows at most, whole
    # tokens, ``blocks`` of them to the step's query tile
    block_tokens, blocks = row_block_shape(q_tile, group)
    if not shared_kv:
        blocks = 0
    if blocks:
        listed = row_block_list(
            lens, cu, dist, max_pages=max_pages, page=page,
            block_tokens=block_tokens, blocks=blocks, width=t_pad)
        items, n_items = listed.items, listed.n
        # a block is copied whole, so the last token's may reach past
        # the packed rows: spare rows, nobody's
        qs = jnp.pad(qs, ((0, 0), (0, tile_rows), (0, 0)))
    else:
        items, n_items = work_items(live_pages(
            lens, cu, dist, max_pages=max_pages, page=page, q_tile=q_tile,
            window=window, sinks=sinks))
    rows_total = qs.shape[1]

    def kv_index(hd, i, lens_ref, cu_ref, dist_ref, tbl_ref, items_ref):
        # the item's table entry, read on prefetched scalars.  An item
        # that holds a page to attend has its entry claimed; the kept
        # entries that hold none (`live_pages`) may read -1, and fetch
        # page 0 for nobody.
        r, j = _slot_and_page(items_ref[i], max_pages)
        if blocks:
            r = jax.lax.div(r, jnp.int32(blocks))
        return (jnp.maximum(tbl_ref[r, j], 0), hd, 0, 0)

    def head_index(hd, i, *_):
        return (hd, 0, 0)

    kernel = functools.partial(
        _ragged_kernel, max_pages=max_pages, group=group, page=page,
        q_tile=q_tile, t_pad=t_pad, tile_rows=tile_rows,
        one_token_rows=one_token_rows,
        softcap2=None if softcap is None else softcap * _LOG2E,
        window=window, sinks=sinks, variant=variant, dv=dv,
        shared_kv=shared_kv, blocks=blocks, block_tokens=block_tokens,
        heads=heads,
    )
    # Scoped-VMEM demand (`_vmem_need`).  Past Mosaic's ~16 MB default
    # budget (packed width >= 2048 at group 8 and one head; 512 at
    # four) the budget is raised to what the call needs, like the
    # forward kernel's big tiles; small steps keep the default.
    vmem_need = _vmem_need(
        heads, tile_rows, one_token_rows,
        held_rows=tile_rows if blocks else 2 * t_pad * group, d=d, dv=dv,
        kv_lanes=d + (0 if shared_kv else dv), page=page,
        q_itemsize=qs.dtype.itemsize, kv_itemsize=kv_item)
    vmem_limit = None
    if vmem_need > _DEFAULT_SCOPED_VMEM // 2:
        vmem_limit = min(int(vmem_need * 1.5), _MAX_SCOPED_VMEM)
    pools = (cache.k_pool,) if shared_kv else (cache.k_pool, cache.v_pool)
    # the heads of a page lie together in a pool: one copy a pool
    pool_specs = [pl.BlockSpec((1, heads, page, pool.shape[-1]), kv_index)
                  for pool in pools]
    # a scratch a head of the block; the row-blocked form has one head
    lead = () if blocks else (heads,)
    scratch = [
        pltpu.VMEM((*lead, tile_rows, dv), jnp.float32),
        pltpu.VMEM((*lead, tile_rows, _STAT_LANES), jnp.float32),
        pltpu.VMEM((*lead, tile_rows, _STAT_LANES), jnp.float32),
    ]
    out_shape = jax.ShapeDtypeStruct((hkv, rows_total, dv), out_dtype)
    if blocks:
        # rows in and out by the kernel's own copies; the result starts
        # as zeros, which is what a pad token's rows stay
        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [in_hbm, *pool_specs, in_hbm]
        out_specs = [in_hbm]
        scratch += [pltpu.VMEM((tile_rows, d), qs.dtype),
                    pltpu.VMEM((tile_rows, dv), out_dtype),
                    pltpu.SemaphoreType.DMA((2,))]
        operands = (qs, *pools, jnp.zeros(out_shape.shape, out_dtype))
        aliases = {5 + len(operands) - 1: 0}
    else:
        in_specs = [pl.BlockSpec((heads, rows_total, d), head_index),
                    *pool_specs]
        out_specs = [pl.BlockSpec((heads, rows_total, dv), head_index)]
        operands = (qs, *pools)
        aliases = {}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # the second bound is the step's own count of work items, a
        # traced scalar: one executable whatever the step holds
        grid=(hkv // heads, n_items),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    # the estimate has to be a number, so it is the longest list's: a
    # table with every entry live
    full = hkv * s_slots * max_pages
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out_shape],
        input_output_aliases=aliases,
        # NOT parallel: every slot of one head accumulates into the
        # same resident output block
        compiler_params=_compiler_params(("arbitrary", "arbitrary"),
                                         vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=2 * full * tile_rows * page * (d + dv),
            bytes_accessed=full * page * (d + (0 if shared_kv else dv))
            * kv_item + qs.size * qs.dtype.itemsize,
            transcendentals=full * tile_rows * page,
        ),
        interpret=interpret,
    )(lens, cu, dist, cache.page_table, items, *operands)
    out = outs[0][:, :t_pad * group]
    out = out.reshape(hkv, t_pad, group, dv).transpose(0, 2, 1, 3)
    return out.reshape(1, h, t_pad, dv)


def ragged_paged_attention(q: jax.Array, cache: RaggedPagedStep,
                           **kwargs) -> jax.Array:
    """Ragged paged attention (telemetry shim; full docs on
    :func:`_ragged_paged_attention_jit`)."""
    if obs.is_enabled():
        _RAGGED_CALLS.inc(
            bucket=obs.shape_bucket(q.shape[2], cache.max_tokens,
                                    q.shape[-1]))
    return _ragged_paged_attention_jit(q, cache, **kwargs)


def _row_append_kernel(page_ref, block_ref, first_ref, count_ref, *refs):
    """One run a grid step: ``count`` new rows from row ``first`` of
    one `_APPEND_ROWS`-row block of every pool, the block's other rows
    as they were.  ``refs``: each pool's new rows, then each pool's
    block in, then each pool's block out.  Steps past the last run stay
    on its block and write nothing, so nothing moves for them."""
    j = pl.program_id(0)
    first, count = first_ref[j], count_ref[j]
    n = len(refs) // 3

    @pl.when((j == 0) | (count > 0))
    def _():
        for new_ref, in_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                            refs[2 * n:]):
            old = in_ref[0]                          # (Hkv, rows, d)
            row = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
            new = (row >= first) & (row < first + count)
            out_ref[0] = jnp.where(new, new_ref[...], old)


@functools.partial(jax.jit, static_argnames=("max_runs", "interpret"))
def _append_rows(pools, new_rows, tgt, pos, *, max_runs, interpret):
    """``pool[tgt[t], :, pos[t] % page] = rows[:, t]`` for every pool
    of ``pools`` (K and V, or a latent cache's one), in place: the
    pools are aliased to the results, and the kernel moves only the
    blocks of `_APPEND_ROWS` rows that hold a new row.  ``tgt`` equal
    to the pool's page count writes nothing.

    Neighbours on the packed axis that write into one block form a
    RUN, one grid step: a slot's tokens follow each other at rising
    positions, so a step has at most ``max_runs`` of them."""
    pages, hkv, page, _ = pools[0].shape
    t = new_rows[0].shape[1]
    rows = _APPEND_ROWS
    if page % rows:
        raise ValueError(f"page size {page} is not a multiple of the "
                         f"{rows} rows one append moves")
    writes = tgt < pages
    off = pos % page
    key = jnp.where(writes, tgt * (page // rows) + off // rows, -1)
    lead = writes & (key != jnp.concatenate([key[:1] - 1, key[:-1]]))
    run = jnp.cumsum(lead) - 1                       # of each writing token
    lead_tok = jnp.nonzero(lead, size=max_runs, fill_value=0)[0]
    count = jnp.zeros((max_runs,), jnp.int32).at[
        jnp.where(writes, run, max_runs)].add(1, mode="drop")
    first = off[lead_tok] % rows
    # steps past the last run repeat its block; with no run at all,
    # step 0 rewrites block 0 of page 0 as it is
    at = lead_tok[jnp.minimum(jnp.arange(max_runs),
                              jnp.maximum(run[-1], 0))]
    page_of = jnp.where(writes[at], tgt[at], 0)
    block_of = jnp.where(writes[at], off[at] // rows, 0)
    # each run's rows where they go in its block (the rest is not read)
    src = jnp.clip(lead_tok[:, None] - first[:, None]
                   + jnp.arange(rows)[None, :], 0, t - 1).reshape(-1)

    def new_spec(width):
        return pl.BlockSpec((hkv, rows, width),
                            lambda j, pg, bl, fi, co: (0, j, 0))

    def pool_spec(width):
        return pl.BlockSpec((1, hkv, rows, width),
                            lambda j, pg, bl, fi, co: (pg[j], 0, bl[j], 0))

    widths = [pool.shape[3] for pool in pools]
    n = len(pools)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(max_runs,),
        in_specs=([new_spec(w) for w in widths]
                  + [pool_spec(w) for w in widths]),
        out_specs=[pool_spec(w) for w in widths],
    )
    return tuple(pl.pallas_call(
        _row_append_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype)
                   for pool in pools],
        # every element no token writes stays as it is: the new pools
        # ARE the old ones, written in place
        input_output_aliases={4 + n + i: i for i in range(n)},
        compiler_params=_compiler_params(("arbitrary",)),
        name="kv_row_append",
        interpret=interpret,
    )(page_of.astype(jnp.int32), block_of.astype(jnp.int32),
      first.astype(jnp.int32), count,
      *(r[:, src] for r in new_rows), *pools))


def ragged_paged_append(cache: RaggedPagedStep, k_new: jax.Array,
                        v_new: jax.Array | None = None,
                        index_new: jax.Array | None = None
                        ) -> RaggedPagedStep:
    """Write every packed token's K/V row (k/v (1, Hkv, T, d)) at its
    slot's next positions; returns the cache with post-append lengths.
    A cache of one pool (``v_pool`` None) takes ``k_new`` alone, and
    one with a selector's pool (``index_pool``) the selector's keys
    ``index_new`` with it: the same place in the same pages.

    The packed analog of `ops.paged.paged_append`, with the same poison
    contract: a token targeting an unclaimed (-1) table entry or past
    the table's capacity writes NOTHING and marks its whole SLOT's
    length -1 (sticky; the attention kernel then emits NaN for that
    slot's tokens).  Pad tokens (slot -1) always drop, silently.

    The rows are written IN PLACE by one small kernel
    (`_append_rows`): under a jit that donates the pools the step
    holds no second copy of them and moves no more of a pool than the
    32-row blocks its new rows sit in.  A slot's tokens follow each
    other on the packed axis at rising positions (``cu_q_lens``), so
    neighbours share a block's one trip, and a step of ``T`` tokens
    over ``S`` slots makes at most ``T // 32 + 2 * S`` such trips."""
    page = cache.page_size
    t = k_new.shape[2]
    if (cache.v_pool is None) != (v_new is None):
        raise ValueError("new V rows go with a V pool, and only with one")
    if (cache.index_pool is None) != (index_new is None):
        raise ValueError("new index keys go with an index pool, and only "
                         "with one")
    held = {"k_pool": (cache.k_pool, k_new), "v_pool": (cache.v_pool, v_new),
            "index_pool": (cache.index_pool, index_new)}
    held = {f: pair for f, pair in held.items() if pair[0] is not None}
    new = tuple(rows for _, rows in held.values())
    if (any(r.ndim != 4 or r.shape[:3] != k_new.shape[:3] for r in new)
            or k_new.shape[0] != 1
            or t != cache.token_slot.shape[0]):
        raise ValueError(
            f"expected (1, Hkv, {cache.token_slot.shape[0]}, d) packed "
            f"rows: " + " ".join(str(r.shape) for r in new)
        )
    s_slots, max_pages = cache.page_table.shape
    slot = jnp.asarray(cache.token_slot, jnp.int32)
    pos = jnp.asarray(cache.token_pos, jnp.int32)
    safe_slot = jnp.maximum(slot, 0)
    logical = pos // page
    phys = cache.page_table[safe_slot,
                            jnp.minimum(logical, max_pages - 1)]
    bad = ((phys < 0)
           | (logical >= max_pages)
           | (cache.kv_lens[safe_slot] < 0))
    drop = jnp.logical_or(bad, slot < 0)
    # dropped tokens target one-past-the-end
    tgt = jnp.where(drop, cache.k_pool.shape[0], phys)
    pools = tuple(pool for pool, _ in held.values())
    pools = _append_rows(
        pools, tuple(r[0].astype(p.dtype) for r, p in zip(new, pools)),
        tgt, pos, max_runs=min(t, t // _APPEND_ROWS + 2 * s_slots),
        interpret=_should_interpret())
    # per-slot sticky poison: any bad REAL token condemns its slot
    bad_slot = jnp.zeros((s_slots + 1,), jnp.bool_).at[
        jnp.where(slot < 0, s_slots, slot)
    ].max(bad, mode="drop")[:s_slots]
    q_lens = cache.cu_q_lens[1:] - cache.cu_q_lens[:-1]
    new_lens = jnp.where(bad_slot | (cache.kv_lens < 0), -1,
                         cache.kv_lens + q_lens)
    return cache._replace(**dict(zip(held, pools)), kv_lens=new_lens)


__all__ = [
    "RaggedPagedStep",
    "ragged_paged_attention",
    "ragged_paged_append",
    "packed_bucket",
    "tile_tokens",
    "recommended_q_tile",
]
