"""Grouped expert feed-forward: ONE kernel launch a layer for the
token-expert pairs of the experts this chip holds.

An expert ``e`` is ``y = W2_e relu(W1_e u)^2`` on a latent row ``u``
(`grouped_experts`), or, with a gate branch, ``y = Wd_e (silu(Wg_e x)
* (Wu_e x))`` (`grouped_gated_experts`: the same layout and grid, three
weight tiles a step).
A step's pairs (token, held expert) are laid out expert by expert on a
row axis of STATIC length, each expert's rows starting at a multiple
of the row tile (`expert_layout`), so a tile of rows belongs to one
expert and an expert that received few rows is read once.  The grid
is ``(n, hidden tiles)``: the ``n`` row tiles that hold pairs (a
traced scalar: rows past them, and experts without a pair, are no grid
step and no bytes), and the expert's hidden width in tiles, over which
the output tile accumulates in float32.  The weights are read in the
dtype they are stored in and cast in VMEM, so no copy of them is made
in memory.  No shape depends on the routing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu.ops.flash import _compiler_params, _should_interpret


class ExpertLayout(NamedTuple):
    """Where a step's pairs sit on the grouped row axis.

    ``dest``: (T, k) int32, the row of pair (token, choice), or ``rows``
    (one past the end) for a pair of an expert that is not held here or
    of a pad token.  ``row_token``: (rows,) int32, the token whose
    latent row a row holds (0 where the row holds no pair).
    ``tile_expert``: (rows / tile,) int32, the held expert of each row
    tile.  ``num_tiles``: () int32, tiles that hold pairs.  ``counts``:
    (held,) int32 pairs per held expert."""

    dest: jax.Array
    row_token: jax.Array
    tile_expert: jax.Array
    num_tiles: jax.Array
    counts: jax.Array


def row_tile(tokens: int) -> int:
    """Rows a grid step takes for a step of ``tokens`` packed tokens:
    about what one expert receives of it at an even load of a twelfth
    of the tokens, in [8, 32]."""
    tile = 8
    while tile < 32 and tile * 12 < tokens:
        tile *= 2
    return tile


def layout_rows(tokens: int, top_k: int, held: int, tile: int) -> int:
    """The static length of the grouped row axis: every pair of every
    token held here (a token takes an expert once, so at most
    ``min(top_k, held)`` a token), each expert's rows rounded up to
    whole tiles."""
    pairs = tokens * min(top_k, held)
    return -(-(pairs + held * (tile - 1)) // tile) * tile


@functools.partial(jax.jit, static_argnames=("held", "tile"))
def expert_layout(local, valid, *, held: int, tile: int) -> ExpertLayout:
    """Lay the pairs out by held expert (jitted, so that a model's
    expert layers trace it once a shape).  ``local``: (T, k) int32, the
    pair's expert as an index into the held ones, anything outside
    ``[0, held)`` for an expert held elsewhere (a token takes an expert
    at most once); ``valid``: (T,) bool, false for pad tokens.  Pairs
    keep their tokens' order inside an expert.

    A pair's rank inside its expert is the count of earlier tokens
    that took the expert: one product of a triangle of ones with the
    ``(T, held)`` table of who took whom, exact in float32 (a running
    sum over all ``T k`` pairs compiles to six times the code)."""
    tokens, top_k = local.shape
    rows = layout_rows(tokens, top_k, held, tile)
    f32 = jnp.float32
    here = (local >= 0) & (local < held) & valid[:, None]
    onehot = (here[..., None] & (local[..., None] == jnp.arange(
        held, dtype=jnp.int32))).astype(f32)                    # (T, k, held)
    took = onehot.sum(axis=1)                                   # (T, held)
    earlier = jnp.tril(jnp.ones((tokens, tokens), f32), -1)
    rank = jnp.dot(earlier, took, precision=jax.lax.Precision.HIGHEST)
    counts = took.sum(axis=0).astype(jnp.int32)
    tiles = -(-counts // tile)
    ends = jnp.cumsum(tiles)                                    # in tiles
    first = ((ends - tiles) * tile).astype(f32)                 # in rows
    at = (onehot * (rank + first)[:, None, :]).sum(axis=-1).astype(jnp.int32)
    dest = jnp.where(here, at, rows)                            # (T, k)
    token = jnp.broadcast_to(jnp.arange(tokens, dtype=jnp.int32)[:, None],
                             dest.shape)
    row_token = jnp.zeros((rows,), jnp.int32).at[dest.reshape(-1)].set(
        token.reshape(-1), mode="drop")
    tile_expert = jnp.searchsorted(
        ends, jnp.arange(rows // tile, dtype=jnp.int32), side="right",
        method="compare_all").astype(jnp.int32)
    return ExpertLayout(dest, row_token, jnp.minimum(tile_expert, held - 1),
                        ends[-1].astype(jnp.int32), counts)


def hidden_tile(hidden: int) -> int:
    """The hidden width a grid step takes: the largest of 896, 512,
    384, 256, 128 that divides it, else all of it."""
    for t in (896, 512, 384, 256, 128):
        if hidden % t == 0 and hidden > t:
            return t
    return hidden


def _experts_kernel(tile_expert_ref, x_ref, w1_ref, w2_ref, o_ref, *, dtype):
    """One (row tile, hidden tile) grid step."""
    @pl.when(pl.program_id(1) == 0)
    def _clear():
        o_ref[...] = jnp.zeros_like(o_ref)

    h = jnp.dot(x_ref[...], w1_ref[0].astype(dtype),
                preferred_element_type=jnp.float32)
    h = jnp.square(jnp.maximum(h, 0.0)).astype(dtype)
    o_ref[...] += jnp.dot(h, w2_ref[0].astype(dtype),
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _latent_experts_gmm_jit(rows, w1, w2, tile_expert, num_tiles, *,
                            tile: int, interpret: bool | None = None):
    n_rows, width = rows.shape
    held, _, hidden = w1.shape
    if w1.shape != (held, width, hidden) or w2.shape != (held, hidden, width):
        raise ValueError(f"experts disagree: rows{rows.shape} W1{w1.shape} "
                         f"W2{w2.shape}")
    if n_rows % tile or tile_expert.shape != (n_rows // tile,):
        raise ValueError(f"{n_rows} rows in tiles of {tile} need "
                         f"{n_rows // tile} tile experts, got "
                         f"{tile_expert.shape}")
    if interpret is None:
        interpret = _should_interpret()
    step = hidden_tile(hidden)
    item = jnp.dtype(w1.dtype).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # at least one step, so that the output is something (a row
        # tile of nobody's)
        grid=(jnp.maximum(num_tiles, 1), hidden // step),
        in_specs=[
            pl.BlockSpec((tile, width), lambda i, j, e: (i, 0)),
            pl.BlockSpec((1, width, step), lambda i, j, e: (e[i], 0, j)),
            pl.BlockSpec((1, step, width), lambda i, j, e: (e[i], j, 0)),
        ],
        out_specs=[pl.BlockSpec((tile, width), lambda i, j, e: (i, 0))],
    )
    (out,) = pl.pallas_call(
        functools.partial(_experts_kernel, dtype=rows.dtype),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows, width), jnp.float32)],
        compiler_params=_compiler_params(
            ("arbitrary", "arbitrary"),
            # two weight tiles, held twice, and their casts
            vmem_limit_bytes=max(
                32 << 20, int(2 * width * step * (2 * item + 2) * 1.5)
                + (8 << 20))),
        # the estimate has to be a number: every held expert once
        cost_estimate=pl.CostEstimate(
            flops=4 * n_rows * width * hidden,
            bytes_accessed=2 * held * width * hidden * item
            + n_rows * width * 6,
            transcendentals=0),
        name="latent_experts_gmm",
        interpret=interpret,
    )(tile_expert, rows, w1, w2)
    return out


def _gated_experts_kernel(tile_expert_ref, x_ref, wg_ref, wu_ref, wd_ref,
                          o_ref, *, dtype):
    """One (row tile, hidden tile) grid step of the gated expert:
    ``silu(x Wg) * (x Wu)`` on the hidden tile, then its part of the
    down projection."""
    @pl.when(pl.program_id(1) == 0)
    def _clear():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[0].astype(dtype),
                   preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[0].astype(dtype),
                 preferred_element_type=jnp.float32)
    h = (gate * jax.nn.sigmoid(gate) * up).astype(dtype)
    o_ref[...] += jnp.dot(h, wd_ref[0].astype(dtype),
                          preferred_element_type=jnp.float32)


def gated_hidden_tile(width: int, hidden: int, itemsize: int) -> int:
    """The hidden width a grid step of the gated product takes: the
    largest of 512, 256, 128 that divides ``hidden`` and keeps one
    weight tile at 4 MB or under (three are held, twice each), else
    all of it."""
    for t in (512, 256, 128):
        if hidden % t == 0 and hidden > t and width * t * itemsize <= 4 << 20:
            return t
    return hidden


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gated_experts_gmm_jit(rows, wg, wu, wd, tile_expert, num_tiles, *,
                           tile: int, interpret: bool | None = None):
    n_rows, width = rows.shape
    held, _, hidden = wg.shape
    if (wg.shape != (held, width, hidden) or wu.shape != wg.shape
            or wd.shape != (held, hidden, width)):
        raise ValueError(f"experts disagree: rows{rows.shape} Wg{wg.shape} "
                         f"Wu{wu.shape} Wd{wd.shape}")
    if n_rows % tile or tile_expert.shape != (n_rows // tile,):
        raise ValueError(f"{n_rows} rows in tiles of {tile} need "
                         f"{n_rows // tile} tile experts, got "
                         f"{tile_expert.shape}")
    if interpret is None:
        interpret = _should_interpret()
    item = jnp.dtype(wg.dtype).itemsize
    step = gated_hidden_tile(width, hidden, item)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # at least one step, so that the output is something (a row
        # tile of nobody's)
        grid=(jnp.maximum(num_tiles, 1), hidden // step),
        in_specs=[
            pl.BlockSpec((tile, width), lambda i, j, e: (i, 0)),
            pl.BlockSpec((1, width, step), lambda i, j, e: (e[i], 0, j)),
            pl.BlockSpec((1, width, step), lambda i, j, e: (e[i], 0, j)),
            pl.BlockSpec((1, step, width), lambda i, j, e: (e[i], j, 0)),
        ],
        out_specs=[pl.BlockSpec((tile, width), lambda i, j, e: (i, 0))],
    )
    (out,) = pl.pallas_call(
        functools.partial(_gated_experts_kernel, dtype=rows.dtype),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows, width), jnp.float32)],
        compiler_params=_compiler_params(
            ("arbitrary", "arbitrary"),
            # three weight tiles, held twice, and their casts
            vmem_limit_bytes=max(
                32 << 20, int(3 * width * step * (2 * item + 2) * 1.5)
                + (8 << 20))),
        # the estimate has to be a number: every held expert once
        cost_estimate=pl.CostEstimate(
            flops=6 * n_rows * width * hidden,
            bytes_accessed=3 * held * width * hidden * item
            + n_rows * width * 6,
            transcendentals=n_rows * hidden),
        name="gated_experts_gmm",
        interpret=interpret,
    )(tile_expert, rows, wg, wu, wd)
    return out


def grouped_gated_experts(rows, wg, wu, wd, layout: ExpertLayout, *,
                          tile: int, interpret: bool | None = None):
    """``Wd_e (silu(Wg_e x) * (Wu_e x))`` for every row ``x`` of
    ``rows`` (R, width) with its tile's expert ``e``: `grouped_experts`
    for experts with a gate branch, three weight tiles a grid step.
    ``wg``, ``wu``: (held, width, hidden), ``wd``: (held, hidden,
    width), in their stored dtype.  Returns (R, width) float32; rows of
    tiles past ``layout.num_tiles`` are NOT written."""
    return _gated_experts_gmm_jit(rows, wg, wu, wd, layout.tile_expert,
                                  layout.num_tiles, tile=tile,
                                  interpret=interpret)


def grouped_experts(rows, w1, w2, layout: ExpertLayout, *, tile: int,
                    interpret: bool | None = None):
    """``W2_e relu(W1_e u)^2`` for every row ``u`` of ``rows`` (R,
    width) with its tile's expert ``e``.  ``w1``: (held, width,
    hidden), ``w2``: (held, hidden, width), in their stored dtype; the
    products run in ``rows.dtype`` with float32 accumulation.  Returns
    (R, width) float32; rows of tiles past ``layout.num_tiles`` are
    NOT written (whatever memory held), so read only rows that hold a
    pair."""
    return _latent_experts_gmm_jit(rows, w1, w2, layout.tile_expert,
                                   layout.num_tiles, tile=tile,
                                   interpret=interpret)


__all__ = ["ExpertLayout", "expert_layout", "grouped_experts",
           "grouped_gated_experts", "layout_rows", "row_tile", "hidden_tile",
           "gated_hidden_tile"]
