"""Ragged gated delta rule: ONE kernel launch a layer for a mixed
decode/prefill step of a linear-attention (Gated DeltaNet) layer.

Per head the layer keeps a state ``S`` (dk, dv) in float32 that follows

    S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T,   o_t = S_t^T q_t

(arXiv:2412.06464; ``a`` the decay in (0, 1], ``b`` the write strength).
The serving engine's packed step carries every request's tokens of the
step on one axis (``cu_q_lens`` spans: one token for a decode slot, up
to a prefill chunk for a prefill slot).  Each slot owns one row of a
per-layer STATE POOL ``(rows, H, dk, dv)``; the kernel reads the row,
runs the slot's span through the recurrence and writes the row back.
A slot that starts a request (``kv_lens == 0``: nothing computed yet,
which is also what a preempted request readmits with) starts from a
zero state whatever the row holds, so rows are never cleared by hand.

Inside a span the recurrence is evaluated a chunk of ``C`` tokens at a
time in its WY form.  With ``G_i`` the running sum of ``log a`` inside
the chunk and ``w_j = b_j (v_j - a_j S_{j-1}^T k_j)``:

    (I + A) W = diag(b) (V - diag(e^G) K S_0),
        A_jl = b_j e^{G_j - G_l} (k_j . k_l)           for l < j
    O   = diag(e^G) Q S_0 + (M * Q K^T) W,   M_ij = e^{G_i - G_j}, j <= i
    S_C = e^{G_C} S_0 + (diag(e^{G_C - G}) K)^T W

``(I + A)`` is unit lower triangular; its 8x8 diagonal blocks are
inverted by the product ``(I - D)(I + D^2)(I + D^4)`` and the rest by
substitution a block of rows at a time (`_chunk_update` says why not
the product over the whole chunk).  Every exponent is a difference
``G_i - G_j <= 0``, so nothing overflows however strong the decay.
Pad rows of a chunk carry ``b = 0``, ``log a = 0`` and ``k = 0`` and
leave the state as it was.

The grid is ``(slots, heads, chunks)`` with the state carried in VMEM
scratch over the chunk axis.  Spans are first laid out one slot a tile
(``(slots, q_tile)`` rows, gathered from the packed axis by XLA), so
every block the kernel sees is statically aligned; chunks past a span's
end and slots without tokens repeat a block index and are skipped, and
a slot without a state row reads and writes the pool's last row, which
no request owns.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu.ops.flash import _compiler_params, _should_interpret

#: the largest chunk of a span that is evaluated at once
MAX_CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


class RaggedStateStep(NamedTuple):
    """One packed engine step of a recurrent layer.

    ``state_pool``: (R + 1, H, dk, dv) float32, one row a request slot
    and a last row that belongs to nobody.  ``conv_pool``: (R + 1,
    K - 1, channels), the last ``K - 1`` inputs of the layer's causal
    convolution per row.  ``state_rows``: (S,) int32, the pool row of
    each slot of this step (-1: the slot is empty).  ``kv_lens``: (S,)
    int32 tokens the slot's request had computed BEFORE this step; 0
    starts from a zero state.  ``cu_q_lens`` (S + 1,), ``token_slot``
    (T,) and ``q_span`` are the packed step's own (see
    `ops.ragged_paged.RaggedPagedStep`)."""

    state_pool: jax.Array
    conv_pool: jax.Array
    state_rows: jax.Array
    kv_lens: jax.Array
    cu_q_lens: jax.Array
    token_slot: jax.Array
    q_span: jax.Array

    @property
    def q_tile(self) -> int:
        return self.q_span.shape[0]

    @property
    def scratch_row(self) -> int:
        return self.state_pool.shape[0] - 1


def chunk_tokens(q_tile: int) -> int:
    """Tokens evaluated at once for a query tile: the largest of 64,
    32, 16, 8 that divides it (tiles are 8-multiples)."""
    for c in (MAX_CHUNK, 32, 16, 8):
        if q_tile % c == 0:
            return c
    raise ValueError(f"q_tile {q_tile} must be a multiple of 8")


def gated_delta_scan(q, k, v, log_a, beta, state=None):
    """The recurrence token by token (`lax.scan`), the definition the
    kernel is tested against and the path of a call without a cache.
    ``q``/``k``: (T, H, dk), ``v``: (T, H, dv), ``log_a``/``beta``:
    (T, H); ``state``: (H, dk, dv) or None for zeros.  Float32
    throughout.  Returns ``(o (T, H, dv), state)``."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), f32)

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, None, None]
        u = vt - jnp.einsum("hkv,hk->hv", s, kt, precision=_HIGHEST)
        s = s + jnp.einsum("hk,hv->hkv", kt * bt[:, None], u,
                           precision=_HIGHEST)
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=_HIGHEST)

    state, o = jax.lax.scan(
        step, state.astype(f32),
        (q, k, v, log_a.astype(f32), beta.astype(f32)))
    return o, state


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


#: rows solved at once inside a chunk (the float32 sublane granule)
_BLOCK = 8


def _chunk_update(q, k, v, gc, gr, beta, s0, w_ref):
    """One chunk in the WY form of the module's docstring.  ``q``/``k``
    (C, dk), ``v`` (C, dv), ``gc`` (C, 1) and ``gr`` (1, C) the running
    sum of ``log a`` in both layouts, ``beta`` (C, 1), ``s0`` (dk, dv),
    all float32; ``w_ref`` a (C, dv) scratch.  Returns ``(o (C, dv),
    s (dk, dv))``."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    upto = col <= row
    decay = jnp.exp(jnp.where(upto, gc - gr, 0.0))
    nt, nn_, tn = ((1,), (1,)), ((1,), (0,)), ((0,), (0,))
    a = jnp.where(col < row, beta * decay * _dot(k, k, nt), 0.0)
    gamma = jnp.exp(gc)
    rhs = beta * (v - gamma * _dot(k, s0, nn_))
    # (I + A) W = rhs, A strictly lower.  Split A into its 8x8 diagonal
    # blocks D and the rest L.  (I + D)^-1 = (I - D)(I + D^2)(I + D^4)
    # exactly (D^8 = 0), and with so few terms the powers stay small;
    # the same product over a whole chunk cancels catastrophically once
    # keys are correlated.  Then (I + T L) W = T rhs is solved a block
    # of rows at a time, which is the recurrence itself at that grain.
    same = (row // _BLOCK) == (col // _BLOCK)
    diag = jnp.where(same, a, 0.0)
    inv = jnp.where(col == row, 1.0, 0.0) - diag
    power = -diag
    for _ in range(2):
        power = _dot(power, power, nn_)
        inv = inv + _dot(inv, power, nn_)
    rest = _dot(inv, a - diag, nn_)
    target = _dot(inv, rhs, nn_)
    w_ref[...] = jnp.zeros_like(w_ref)
    for b in range(c // _BLOCK):
        rows = slice(b * _BLOCK, (b + 1) * _BLOCK)
        w_ref[rows, :] = target[rows] - _dot(rest[rows], w_ref[...], nn_)
    w = w_ref[...]
    mix = jnp.where(upto, decay * _dot(q, k, nt), 0.0)
    o = gamma * _dot(q, s0, nn_) + _dot(mix, w, nn_)
    g_last = gr[:, c - 1:c]
    s = jnp.exp(g_last) * s0 + _dot(k * jnp.exp(g_last - gc), w, tn)
    return o, s


def _delta_kernel(rows_ref, lens_ref, qlen_ref, q_ref, k_ref, v_ref,
                  gc_ref, gr_ref, b_ref, s_in_ref, o_ref, s_out_ref, s_scr,
                  w_scr, *, chunk: int, num_chunks: int):
    """One (slot, head, chunk) grid step."""
    slot = pl.program_id(0)
    c = pl.program_id(2)
    q_len = qlen_ref[slot]

    @pl.when(c == 0)
    def _load():
        s_scr[...] = jnp.where(lens_ref[slot] == 0, 0.0, s_in_ref[0, 0])

    @pl.when(jnp.logical_and(rows_ref[slot] >= 0, c * chunk < q_len))
    def _update():
        f32 = jnp.float32
        o, s = _chunk_update(
            q_ref[0, 0].astype(f32), k_ref[0, 0].astype(f32),
            v_ref[0, 0].astype(f32), gc_ref[0, 0], gr_ref[0, 0, 0],
            b_ref[0, 0], s_scr[...], w_scr)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        s_scr[...] = s

    @pl.when(c == num_chunks - 1)
    def _store():
        s_out_ref[0, 0] = s_scr[...]


def _slot_tiles(x, index, valid):
    """Packed rows ``x`` (T, H, ...) laid out one slot a tile:
    (S, H, q_tile, ...), zeros where ``valid`` is false."""
    tiles = jnp.where(valid.reshape(valid.shape + (1,) * (x.ndim - 1)),
                      x[index], 0)
    return jnp.moveaxis(tiles, 2, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ragged_gated_delta_jit(q, k, v, log_a, beta, step: RaggedStateStep,
                            *, interpret: bool | None = None):
    t_pad, heads, dk = q.shape
    dv = v.shape[-1]
    pool = step.state_pool
    if (k.shape != q.shape or v.shape[:2] != (t_pad, heads)
            or log_a.shape != (t_pad, heads) or beta.shape != log_a.shape):
        raise ValueError(
            f"packed rows disagree: Q{q.shape} K{k.shape} V{v.shape} "
            f"log_a{log_a.shape} beta{beta.shape}")
    if pool.shape[1:] != (heads, dk, dv) or pool.dtype != jnp.float32:
        raise ValueError(
            f"state pool {pool.shape} {pool.dtype} must be (rows, "
            f"{heads}, {dk}, {dv}) float32")
    q_tile = step.q_tile
    if q_tile > t_pad:
        raise ValueError(f"q_tile {q_tile} > packed width {t_pad}")
    chunk = chunk_tokens(q_tile)
    num_chunks = q_tile // chunk
    if interpret is None:
        interpret = _should_interpret()

    cu = jnp.asarray(step.cu_q_lens, jnp.int32)
    rows = jnp.asarray(step.state_rows, jnp.int32)
    lens = jnp.asarray(step.kv_lens, jnp.int32)
    slots = rows.shape[0]
    q_lens = cu[1:] - cu[:-1]
    offset = jnp.arange(q_tile, dtype=jnp.int32)[None, :]
    valid = offset < q_lens[:, None]                       # (S, q_tile)
    index = jnp.minimum(cu[:-1, None] + offset, t_pad - 1)
    qt, kt, vt = (_slot_tiles(x, index, valid) for x in (q, k, v))
    f32 = jnp.float32
    g = _slot_tiles(log_a.astype(f32), index, valid)       # (S, H, q_tile)
    g = jnp.cumsum(g.reshape(slots, heads, num_chunks, chunk), axis=-1)
    gc = g.reshape(slots, heads, q_tile, 1)
    gr = g.reshape(slots, heads, num_chunks, 1, chunk)
    bt = _slot_tiles(beta.astype(f32), index, valid)[..., None]

    scratch = step.scratch_row

    def tile_index(s, h, c, rows_ref, lens_ref, qlen_ref):
        # chunks past the span's end repeat the last live one: no DMA
        last = jnp.maximum((qlen_ref[s] + chunk - 1) // chunk - 1, 0)
        return (s, h, jnp.minimum(c, last), 0)

    def row_index(s, h, c, rows_ref, lens_ref, qlen_ref):
        row = rows_ref[s]
        return (jnp.where(row < 0, scratch, row), h, 0, 0)

    def tile(width):
        return pl.BlockSpec((1, 1, chunk, width), tile_index)

    state_block = pl.BlockSpec((1, 1, dk, dv), row_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, heads, num_chunks),
        in_specs=[
            tile(dk), tile(dk), tile(dv), tile(1),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda s, h, c, *refs: tile_index(s, h, c, *refs)
                         + (0,)),
            tile(1), state_block,
        ],
        out_specs=[tile(dv), state_block],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((chunk, dv), jnp.float32)],
    )
    tokens = slots * q_tile * heads
    o_tiles, new_pool = pl.pallas_call(
        functools.partial(_delta_kernel, chunk=chunk,
                          num_chunks=num_chunks),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, heads, q_tile, dv), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # rows of the pool that no slot of this step owns stay as they
        # are: the new pool IS the old one, written in place
        input_output_aliases={3 + 6: 1},
        compiler_params=_compiler_params(
            ("arbitrary", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=tokens * (6 * dk * dv + 4 * chunk * (dk + dv)),
            bytes_accessed=4 * (2 * slots * heads * dk * dv
                                + tokens * (2 * dk + 2 * dv + 3)),
            transcendentals=tokens * (chunk + 2),
        ),
        name="gated_delta",
        interpret=interpret,
    )(rows, lens, q_lens, qt, kt, vt, gc, gr, bt, pool)
    # back onto the packed axis: token t is row (t - cu[slot]) of its
    # slot's tile; pad tokens get zeros
    token_slot = jnp.asarray(step.token_slot, jnp.int32)
    slot = jnp.maximum(token_slot, 0)
    at = jnp.clip(jnp.arange(t_pad, dtype=jnp.int32) - cu[slot], 0,
                  q_tile - 1)
    o = jnp.where((token_slot >= 0)[:, None, None], o_tiles[slot, :, at], 0)
    return o, new_pool


def ragged_gated_delta(q, k, v, log_a, beta, step: RaggedStateStep, *,
                       interpret: bool | None = None):
    """The gated delta rule over a packed step.  ``q``/``k``: (T, H,
    dk), ``v``: (T, H, dv), any float dtype (read as float32);
    ``log_a``/``beta``: (T, H).  Returns ``(o (T, H, dv) float32, the
    state pool after the step)``; rows of pad tokens are zero."""
    return _ragged_gated_delta_jit(q, k, v, log_a, beta, step,
                                   interpret=interpret)


def ragged_causal_conv(x, weight, step: RaggedStateStep, bias=None):
    """Depthwise causal convolution over a packed step.  ``x``: (T,
    channels) the layer's inputs on the packed axis, ``weight``: (K,
    channels) with the newest tap last, ``bias``: (channels,) added to
    every output, or None.  A token at offset ``j`` of its
    span reads ``x`` at offsets ``j - K + 1 .. j``; offsets before the
    span come from the slot's row of ``conv_pool`` (zeros when the slot
    starts a request).  Returns ``(y (T, channels), conv_pool after
    the step)``: each slot's row then holds its last ``K - 1`` inputs."""
    taps = weight.shape[0]
    tail_pool = step.conv_pool
    t_pad = x.shape[0]
    cu = jnp.asarray(step.cu_q_lens, jnp.int32)
    rows = jnp.asarray(step.state_rows, jnp.int32)
    rows = jnp.where(rows < 0, step.scratch_row, rows)
    fresh = jnp.asarray(step.kv_lens, jnp.int32) == 0
    tails = jnp.where(fresh[:, None, None], 0,
                      tail_pool[rows]).astype(x.dtype)   # (S, K-1, ch)
    slot = jnp.maximum(jnp.asarray(step.token_slot, jnp.int32), 0)
    at = jnp.arange(t_pad, dtype=jnp.int32)
    offset = at - cu[slot]
    y = jnp.zeros(x.shape, jnp.float32)
    for back in range(taps):
        src = offset - back
        here = x[jnp.maximum(at - back, 0)]
        before = tails[slot, jnp.clip(taps - 1 + src, 0, taps - 2)]
        y = y + (jnp.where((src >= 0)[:, None], here, before)
                 .astype(jnp.float32) * weight[taps - 1 - back])
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    # the new tails: the last K - 1 of (old tail ; span)
    q_lens = cu[1:] - cu[:-1]
    src = q_lens[:, None] - (taps - 1) + jnp.arange(taps - 1)[None, :]
    from_span = x[jnp.clip(cu[:-1, None] + src, 0, t_pad - 1)]
    from_tail = jnp.take_along_axis(
        tails, jnp.clip(taps - 1 + src, 0, taps - 2)[..., None], axis=1)
    new_tails = jnp.where((src >= 0)[..., None], from_span, from_tail)
    return y.astype(x.dtype), tail_pool.at[rows].set(
        new_tails.astype(tail_pool.dtype))


__all__ = [
    "RaggedStateStep",
    "ragged_gated_delta",
    "ragged_causal_conv",
    "gated_delta_scan",
    "chunk_tokens",
]
