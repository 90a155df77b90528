"""Ragged state-space scan: ONE kernel launch a layer for a mixed
decode/prefill step of a Mamba-2 (SSD, arXiv:2405.21060) mixer.

Per head the layer keeps a state ``S`` (P, N) in float32 (``P`` the
head's width, ``N`` the state size) that follows

    S_t = a_t S_{t-1} + dt_t x_t B_t^T,        y_t = S_t C_t

with a SCALAR decay ``a_t = exp(log_a_t)`` in (0, 1] and a step
``dt_t`` per token and head; ``B_t`` and ``C_t`` (N) are shared by the
heads of a group.  (The skip ``D x_t`` is the caller's.)  There is no
``(I + A)^-1`` as in `ops.gated_delta`: the update does not read the
state it writes, so a chunk of ``C`` tokens is three products.  With
``G_i`` the running sum of ``log a`` inside the chunk:

    Y   = diag(e^G) C_m S_0^T + (M * C_m B_m^T) diag(dt) X,
          M_ij = e^{G_i - G_j} for j <= i, else 0
    S_C = e^{G_C} S_0 + (diag(dt e^{G_C - G}) X)^T B_m

Every exponent is a difference ``G_i - G_j <= 0``.  A span of ONE
token (a decode slot) takes neither product: the update is a
broadcast multiply-add over the state and the read-out a lane
reduction, so a decode step costs its states' bytes and little else.

The serving engine's packed step carries every request's tokens on one
axis (`ops.gated_delta.RaggedStateStep`: ``cu_q_lens`` spans, one row
of the STATE POOL ``(rows, H, P, N)`` a slot).  The grid is ``(groups,
n)``: for each group of heads, the step's ``n`` WORK ITEMS, the live
(slot, chunk) pairs in slot order (`ops.ragged_paged.work_items`; ``n``
is a traced scalar, so slots without tokens are no grid step).  One
group's packed rows and its packed output stay resident in VMEM while
every slot reads its own rows and writes its own rows back; a chunk's
window starts at the 8-row granule below the span's start, and rows of
the window outside the span are masked (they are other slots').  A
slot that starts a request (``kv_lens == 0``) starts from a zero state
whatever its pool row holds, as the delta layer's does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attention_tpu.ops.flash import _compiler_params, _should_interpret
from attention_tpu.ops.gated_delta import RaggedStateStep
from attention_tpu.ops.ragged_paged import work_items

#: the largest chunk of a span that is evaluated at once (the family's
#: ``chunk_size``)
MAX_CHUNK = 128

#: rows a window's start is rounded down to (the float32 sublane tile)
_GRANULE = 8

_HIGHEST = jax.lax.Precision.HIGHEST


def chunk_tokens(q_tile: int) -> int:
    """Tokens evaluated at once for a query tile: the largest of 128,
    64, 32, 16, 8 that divides it, and 8 for a tile that none divides
    (a wide GQA group's tile can be narrower than 8)."""
    for c in (MAX_CHUNK, 64, 32, 16):
        if q_tile % c == 0:
            return c
    return _GRANULE


def ssm_scan(x, dt, log_a, b, c, state=None, *, keep=None):
    """The recurrence token by token (`lax.scan`): the definition the
    kernel is tested against and the path of a call without a cache.
    ``x``: (T, H, P); ``dt``/``log_a``: (T, H); ``b``/``c``: (T, G, N)
    with head ``h`` reading group ``h // (H / G)``; ``state``: (H, P,
    N) or None for zeros; ``keep`` rounds the state after every token
    (tests: a bfloat16 state).  Float32 throughout.  Returns ``(y (T,
    H, P), state)``."""
    f32 = jnp.float32
    heads, groups = x.shape[1], b.shape[1]
    x, b, c = x.astype(f32), b.astype(f32), c.astype(f32)
    if state is None:
        state = jnp.zeros((heads, x.shape[2], b.shape[2]), f32)

    def step(s, t):
        xt, dtt, gt, bt, ct = t
        bt = jnp.repeat(bt, heads // groups, axis=0)
        ct = jnp.repeat(ct, heads // groups, axis=0)
        s = (s * jnp.exp(gt)[:, None, None]
             + (xt * dtt[:, None])[:, :, None] * bt[:, None, :])
        if keep is not None:
            s = keep(s)
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=_HIGHEST)

    state, y = jax.lax.scan(
        step, state.astype(f32),
        (x, dt.astype(f32), log_a.astype(f32), b, c))
    return y, state


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


_NT, _NN, _TN = ((1,), (1,)), ((1,), (0,)), ((0,), (0,))


def _ssm_kernel(rows_ref, lens_ref, cu_ref, items_ref, x_ref, b_ref, c_ref,
                dt_ref, la_ref, s_in_ref, o_ref, s_out_ref, s_scr, *,
                chunk: int, num_chunks: int, heads: int):
    """One (group, work item) grid step: item ``i`` is chunk ``ch`` of
    slot ``slot``, for the ``heads`` heads of the group."""
    f32 = jnp.float32
    i = pl.program_id(1)
    width = jnp.int32(num_chunks)
    item = items_ref[i]
    slot, ch = jax.lax.div(item, width), jax.lax.rem(item, width)
    start = cu_ref[slot]
    q_len = cu_ref[slot + 1] - start
    base = jax.lax.div(start, jnp.int32(_GRANULE)) * _GRANULE
    off = start - base

    @pl.when(i == 0)
    def _clear():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ch == 0)
    def _load():
        fresh = jnp.logical_and(lens_ref[slot] == 0, q_len > 0)
        s_scr[...] = jnp.where(fresh, 0.0, s_in_ref[0])

    @pl.when(q_len == 1)
    def _one_token():
        # the token is row ``off`` of its aligned 8-row block
        blk = pl.ds(pl.multiple_of(base, _GRANULE), _GRANULE)
        sel = jax.lax.broadcasted_iota(jnp.int32, (_GRANULE, 1), 0) == off

        def row(ref, *lead):
            return jnp.sum(jnp.where(sel, ref[(*lead, blk, slice(None))],
                                     0.0), axis=0, keepdims=True)

        b_row, c_row = row(b_ref, 0), row(c_ref, 0)       # (1, N)
        dt_row, la_row = row(dt_ref, 0), row(la_ref, 0)   # (1, heads)
        p = x_ref.shape[-1]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))
        for h in range(heads):
            x_row = row(x_ref, h)                         # (1, P)
            # a row as a column, and back, by the identity's mask: no
            # transpose unit, no matmul
            x_col = jnp.sum(jnp.where(eye, jnp.broadcast_to(x_row, (p, p)),
                                      0.0), axis=1, keepdims=True)
            # (1, 1) -> (1, N) -> (P, N): Mosaic broadcasts one way at a time
            a = jnp.exp(jnp.broadcast_to(la_row[:, h:h + 1], b_row.shape))
            s = a * s_scr[h] + x_col * (dt_row[:, h:h + 1] * b_row)
            y_col = jnp.sum(s * c_row, axis=1, keepdims=True)
            y_row = jnp.sum(jnp.where(eye, jnp.broadcast_to(y_col, (p, p)),
                                      0.0), axis=0, keepdims=True)
            o_ref[h, blk, :] = jnp.where(sel, y_row, o_ref[h, blk, :])
            s_scr[h] = s

    @pl.when(q_len != 1)
    def _chunk():
        rows = pl.ds(pl.multiple_of(base + ch * chunk, _GRANULE), chunk)
        at = (jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
              + ch * chunk)
        live = jnp.logical_and(at >= off, at < off + q_len)  # (chunk, 1)
        bm = jnp.where(live, b_ref[0, rows, :], 0.0)
        cm = jnp.where(live, c_ref[0, rows, :], 0.0)
        dt = jnp.where(live, dt_ref[0, rows, :], 0.0)        # (chunk, heads)
        la = jnp.where(live, la_ref[0, rows, :], 0.0)
        r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        upto = c <= r
        ones = jnp.where(upto, 1.0, 0.0).astype(f32)
        # the running sum of log a in both layouts, by the triangle
        g = _dot(ones, la, _NN)                              # (chunk, heads)
        gt = _dot(la, jnp.where(r <= c, 1.0, 0.0).astype(f32), _TN)
        cb = _dot(cm, bm, _NT)                               # (chunk, chunk)
        for h in range(heads):
            gc, gr = g[:, h:h + 1], gt[h:h + 1, :]
            mix = jnp.where(upto, jnp.exp(jnp.where(upto, gc - gr, 0.0)) * cb,
                            0.0)
            xh = jnp.where(live, x_ref[h, rows, :], 0.0)     # (chunk, P)
            dth = dt[:, h:h + 1]
            s0 = s_scr[h]                                    # (P, N)
            y = (jnp.exp(gc) * _dot(cm, s0, _NT)
                 + _dot(mix, dth * xh, _NN))
            g_last = gr[:, chunk - 1:chunk]
            keep = jnp.exp(jnp.broadcast_to(g_last, (1, s0.shape[1])))
            s_scr[h] = (keep * s0
                        + _dot(xh * (dth * jnp.exp(g_last - gc)), bm, _TN))
            o_ref[h, rows, :] = jnp.where(live, y, o_ref[h, rows, :])

    s_out_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ragged_ssm_scan_jit(x, dt, log_a, b, c, step: RaggedStateStep, *,
                         interpret: bool | None = None):
    t_pad, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    pool = step.state_pool
    if (c.shape != b.shape or b.shape[0] != t_pad or heads % groups
            or dt.shape != (t_pad, heads) or log_a.shape != dt.shape):
        raise ValueError(
            f"packed rows disagree: x{x.shape} dt{dt.shape} "
            f"log_a{log_a.shape} B{b.shape} C{c.shape}")
    if pool.shape[1:] != (heads, p, n) or pool.dtype != jnp.float32:
        raise ValueError(
            f"state pool {pool.shape} {pool.dtype} must be (rows, "
            f"{heads}, {p}, {n}) float32")
    q_tile = step.q_tile
    if q_tile > t_pad:
        raise ValueError(f"q_tile {q_tile} > packed width {t_pad}")
    chunk = chunk_tokens(q_tile)
    # a span starts up to 7 rows into its first window
    num_chunks = -(-q_tile // chunk) + 1
    if interpret is None:
        interpret = _should_interpret()
    per = heads // groups

    f32 = jnp.float32
    cu = jnp.asarray(step.cu_q_lens, jnp.int32)
    rows = jnp.asarray(step.state_rows, jnp.int32)
    lens = jnp.asarray(step.kv_lens, jnp.int32)
    q_lens = cu[1:] - cu[:-1]
    reach = cu[:-1] % _GRANULE + q_lens                    # (S,)
    live = ((jnp.arange(num_chunks, dtype=jnp.int32)[None, :] * chunk
             < reach[:, None]) & (q_lens > 0)[:, None])
    # a step without a token still makes one grid step, which zeroes
    # the output (slot 0, no row of it live)
    live = live.at[0, 0].set(live[0, 0] | ~live.any())
    items, n_items = work_items(live)

    # the last window may run past the packed axis: a chunk of zeros
    tail = ((0, chunk), (0, 0), (0, 0))
    xg = jnp.pad(x.astype(f32), tail).transpose(1, 0, 2)   # (H, rows, P)
    bg = jnp.pad(b.astype(f32), tail).transpose(1, 0, 2)   # (G, rows, N)
    cg = jnp.pad(c.astype(f32), tail).transpose(1, 0, 2)

    def by_group(t):                                       # (G, rows, per)
        return jnp.pad(t.astype(f32).reshape(t_pad, groups, per),
                       tail).transpose(1, 0, 2)

    t_rows = t_pad + chunk
    scratch = step.scratch_row

    def group_index(g, i, *_):
        return (g, 0, 0)

    def row_index(g, i, rows_ref, lens_ref, cu_ref, items_ref):
        slot = jax.lax.div(items_ref[i], jnp.int32(num_chunks))
        row = rows_ref[slot]
        return (jnp.where(row < 0, scratch, row), g, 0, 0)

    state_block = pl.BlockSpec((1, per, p, n), row_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(groups, n_items),
        in_specs=[
            pl.BlockSpec((per, t_rows, p), group_index),
            pl.BlockSpec((1, t_rows, n), group_index),
            pl.BlockSpec((1, t_rows, n), group_index),
            pl.BlockSpec((1, t_rows, per), group_index),
            pl.BlockSpec((1, t_rows, per), group_index),
            state_block,
        ],
        out_specs=[pl.BlockSpec((per, t_rows, p), group_index),
                   state_block],
        scratch_shapes=[pltpu.VMEM((per, p, n), jnp.float32)],
    )
    slots = rows.shape[0]
    # the packed blocks are lane-padded to 128 in VMEM and held twice
    lanes = -(-p // 128) * 128
    vmem = 4 * (4 * per * t_rows * lanes + 4 * t_rows * (n + 128)
                + 5 * per * p * n) + (8 << 20)
    y, new_pool = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk, num_chunks=num_chunks,
                          heads=per),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((heads, t_rows, p), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # rows of the pool that no slot of this step owns stay as they
        # are: the new pool IS the old one, written in place
        input_output_aliases={4 + 5: 1},
        compiler_params=_compiler_params(
            ("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(int(vmem), 32 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=t_pad * heads * (4 * p * n + 2 * chunk * (p + n)),
            bytes_accessed=4 * (2 * slots * heads * p * n
                                + t_pad * (2 * heads * p + 2 * groups * n
                                           + 2 * heads)),
            transcendentals=t_pad * heads * (chunk + 2),
        ),
        name="ssm_scan",
        interpret=interpret,
    )(rows, lens, cu, items, xg, bg, cg, by_group(dt), by_group(log_a), pool)
    return y[:, :t_pad].transpose(1, 0, 2), new_pool


def ragged_ssm_scan(x, dt, log_a, b, c, step: RaggedStateStep, *,
                    interpret: bool | None = None):
    """The state-space recurrence over a packed step.  ``x``: (T, H,
    P), any float dtype (read as float32); ``dt``/``log_a``: (T, H);
    ``b``/``c``: (T, G, N).  Returns ``(y (T, H, P) float32, the state
    pool after the step)``; rows of pad tokens are zero."""
    return _ragged_ssm_scan_jit(x, dt, log_a, b, c, step,
                                interpret=interpret)


__all__ = ["ragged_ssm_scan", "ssm_scan", "chunk_tokens"]
