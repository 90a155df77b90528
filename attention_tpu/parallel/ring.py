"""Ring attention: blockwise context parallelism over the ICI ring.

The reference scales long sequences by sharding KV once and all-reducing
softmax stats per Q batch (`attention-mpi.c:340-362`).  Ring attention is
the stronger long-context schedule the reference lacks (SURVEY §2
"parallelism-strategy inventory"): Q *and* KV are sequence-sharded, and KV
shards rotate around the ring with ``lax.ppermute`` while each device
accumulates online-softmax partials for its own Q shard.  After R steps
every device has attended its queries to the full sequence with only
nearest-neighbor ICI traffic and O(n/R) memory per chip — this is what
makes the seq=131072 BASELINE config fit.

The reference's ping-pong discipline lives on in two forms:

  * the per-step online merge of (contrib, lmax, lsum) partials is the same
    rmax/rsum rescale as `attention-mpi.c:179-181`, applied across ring
    steps instead of KV rows;
  * the next KV shard's ``ppermute`` is issued before the current step's
    compute, so XLA's latency-hiding scheduler overlaps transfer with the
    flash kernel — the `MPI_Ibcast`/compute overlap of
    `attention-mpi.c:319-330` expressed as a data dependency instead of
    explicit waits.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from attention_tpu.ops.flash import BlockSizes, flash_attention_partials
from attention_tpu.parallel.mesh import default_mesh

NEG_INF = float("-inf")


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "scale", "block_sizes", "causal",
                     "softcap", "schedule", "window", "sinks", "max_mode"),
)
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_name: str = "sp",
    scale: float | None = None,
    block_sizes: BlockSizes | None = None,
    causal: bool = False,
    softcap: float | None = None,
    schedule: str = "contiguous",
    window: int | None = None,
    sinks: int | None = None,
    q_segment_ids=None,
    kv_segment_ids=None,
    max_mode: str = "bound",
) -> jax.Array:
    """Ring attention over a 1D mesh axis; output is Q-sharded like Q.

    Accepts the same 2D/3D/4D shapes as :func:`flash_attention`.  The
    sequence axes of Q and K/V are sharded over ``axis_name``; both are
    padded to a multiple of the ring size, with padded KV rows masked via
    the kernel's dynamic ``kv_valid`` scalar and padded Q rows sliced off.

    ``schedule="zigzag"`` (causal only) interleaves sequence chunks so
    every device carries equal unmasked work at EVERY ring step — the
    load balance the reference had by construction (owner partitioner,
    ±1 row, `attention-mpi.c:19-27`) and the contiguous causal ring
    lacks (early-shard devices spend most steps on fully-masked
    partials).  See :func:`_zigzag_ring`.

    The kernel's masking surface flows through BOTH schedules:
    ``window``/``sinks`` (expressed in GLOBAL positions via each step's
    rotating ``kv_offset`` — sink contributions arrive when the shard
    holding the sequence head rotates in) and packed-sequence segment
    ids (1D global ids; segment matching is equality-based, so the
    zigzag layout change costs nothing — each chunk-pair call just
    slices its chunks' ids from a replicated vector, cheaper than
    rotating a second buffer).
    """
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if schedule not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring schedule {schedule!r}")
    segmented = q_segment_ids is not None
    if segmented != (kv_segment_ids is not None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")
    if schedule == "zigzag":
        if not causal:
            raise ValueError(
                "zigzag schedule only helps causal attention (non-causal "
                "ring work is already balanced); use schedule='contiguous'"
            )
        return _zigzag_ring(
            q, k, v, mesh=mesh, axis_name=axis_name, scale=scale,
            block_sizes=block_sizes, softcap=softcap, window=window,
            sinks=sinks, max_mode=max_mode,
            segment_ids=(q_segment_ids, kv_segment_ids) if segmented
            else None,
        )

    m = q.shape[-2]
    n = k.shape[-2]
    m_pad = -(-m // n_dev) * n_dev
    n_pad = -(-n // n_dev) * n_dev
    if m_pad != m:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 2) + [(0, m_pad - m), (0, 0)])
    if n_pad != n:
        pad = [(0, 0)] * (k.ndim - 2) + [(0, n_pad - n), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    m_local = m_pad // n_dev
    n_local = n_pad // n_dev

    seq_axis = q.ndim - 2
    seq_spec = P(*([None] * seq_axis), axis_name, None)
    # ring neighbors: shard s moves from device j to device j+1 each step,
    # so after step t device j holds shard (j - t) mod R
    perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]

    in_specs = [seq_spec, seq_spec, seq_spec]
    extra = []
    if segmented:
        # Q ids sharded with Q; KV ids replicated — each step slices the
        # arriving shard's ids instead of rotating a second buffer
        extra = list(_ring_pad_ids(q_segment_ids, kv_segment_ids,
                                   m, n, m_pad, n_pad))
        in_specs += [P(axis_name), P()]

    run_cfg = _RingCfg(
        axis_name=axis_name, n_dev=n_dev, n=n, m_local=m_local,
        n_local=n_local, scale=scale, block_sizes=block_sizes,
        causal=causal, softcap=softcap, window=window, sinks=sinks,
        max_mode=max_mode,
    )

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=tuple(in_specs),
        out_specs=seq_spec,
    )
    def run(q_local, k_local, v_local, *seg_local):
        # one shared copy of the rotate/merge schedule (also the
        # custom-VJP forward): see _ring_fwd_loop
        out, _ = _ring_fwd_loop(
            q_local, k_local, v_local, run_cfg,
            seg=tuple(seg_local) if seg_local else None,
        )
        return out

    out = run(q, k, v, *extra)
    if m_pad != m:
        out = lax.slice_in_dim(out, 0, m, axis=seq_axis)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "batch_axis", "head_axis",
                     "scale", "block_sizes", "causal", "softcap", "window",
                     "sinks", "schedule", "max_mode"),
)
def ring_attention_diff(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_name: str = "sp",
    batch_axis: str | None = "dp",
    head_axis: str | None = "tp",
    scale: float | None = None,
    block_sizes: BlockSizes | None = None,
    causal: bool = False,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
    schedule: str = "contiguous",
    q_segment_ids=None,
    kv_segment_ids=None,
    max_mode: str = "bound",
) -> jax.Array:
    """Differentiable ring attention: O(n/R) KV memory per device in
    BOTH passes.

    The all-gather CP path (`parallel/cp.py`) is the default training
    composition but holds the full K/V per device; this is the
    long-context alternative where even K/V exceed one device.  The
    forward is the contiguous ring (online merge of rotating-shard
    partials, saving the per-row lse); the custom backward runs a
    second ring in which dK/dV accumulators TRAVEL WITH their shard —
    each step calls the offset-aware Pallas backward kernels
    (`flash_backward(q_offset=, kv_offset=, kv_valid=)`) on the local Q
    block against the visiting shard, and a final rotation delivers
    each shard's gradients home.  Ring traffic doubles in the backward
    (k, v, dk, dv rotate together) — the standard ring-attention
    gradient schedule.

    Shapes: (h, m, d) or (b, h, m, d), GQA supported; sequence axes
    sharded over ``axis_name``.  ``window`` requires ``causal``.
    Packed-sequence segment ids ((m,)/(n,) global int32 vectors; 3D
    inputs only — the kernel's ids-shared-across-heads limit) flow
    through BOTH passes of BOTH schedules: Q ids shard with Q on the
    contiguous ring and ride replicated on the zigzag (whose chunk
    calls slice by chunk id — segment matching is positionless), KV
    ids stay replicated and are sliced per visiting shard.

    ``sinks`` (StreamingLLM, requires ``window``) train under the ring
    too: the forward's banded partials handle the sink blocks through
    each step's ``kv_offset``; the backward adds the out-of-window sink
    sliver (`flash_bwd._sink_patch`) exactly once — gated to the ring
    step where the shard holding the absolute sink rows (shard 0, or
    zigzag chunk 0) is resident, so its dK/dV land in that shard's
    traveling gradient buffer.  Sinks must fit in one shard/chunk.

    ``schedule="zigzag"`` (causal self-attention only) applies the
    per-step load balance to BOTH passes: each device differentiates
    its early+late chunk pair, so forward partials and the backward's
    three chunk-pair kernel calls carry equal work on every device at
    every step — the training-time answer to the contiguous causal
    ring's R-fold per-step skew.
    """
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if q.ndim not in (3, 4):
        raise ValueError(f"ring_attention_diff takes 3D/4D, got {q.ndim}D")
    if schedule not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring schedule {schedule!r}")
    segmented = q_segment_ids is not None
    if segmented != (kv_segment_ids is not None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")
    if segmented and q.ndim == 4:
        raise ValueError(
            "segment ids support 3D inputs (ids shared across heads)"
        )
    if sinks is not None:
        if window is None:
            raise ValueError("sinks require window= (see flash_attention)")
        if segmented:
            raise ValueError("sinks do not compose with segment_ids")
    if schedule == "zigzag":
        if not causal:
            raise ValueError("zigzag schedule requires causal=True")
        return _zigzag_ring_diff(
            q, k, v, mesh=mesh, axis_name=axis_name,
            batch_axis=batch_axis, head_axis=head_axis, scale=scale,
            block_sizes=block_sizes, softcap=softcap, window=window,
            sinks=sinks, max_mode=max_mode,
            segment_ids=(q_segment_ids, kv_segment_ids) if segmented
            else None,
        )

    m = q.shape[-2]
    n = k.shape[-2]
    m_pad = -(-m // n_dev) * n_dev
    n_pad = -(-n // n_dev) * n_dev
    if m_pad != m:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 2) + [(0, m_pad - m), (0, 0)])
    if n_pad != n:
        pad = [(0, 0)] * (k.ndim - 2) + [(0, n_pad - n), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    m_local = m_pad // n_dev
    n_local = n_pad // n_dev
    seq_axis = q.ndim - 2
    # batch/head axes shard over the rest of the training mesh when
    # present and divisible (both Q and KV head counts for the head
    # axis), mirroring parallel/cp.py — the ring itself runs over
    # ``axis_name`` only
    from attention_tpu.parallel.cp import _maybe_axis

    h_axis = _maybe_axis(mesh, head_axis, q.shape[-3])
    if h_axis is not None and k.shape[-3] % mesh.shape[h_axis] != 0:
        h_axis = None
    if q.ndim == 4:
        b_axis = _maybe_axis(mesh, batch_axis, q.shape[0])
        seq_spec = P(b_axis, h_axis, axis_name, None)
    else:
        seq_spec = P(h_axis, axis_name, None)

    if sinks is not None and sinks > n_local:
        raise ValueError(
            f"sinks ({sinks}) must fit in one KV shard ({n_local} rows)"
        )
    cfg = dict(
        axis_name=axis_name, n_dev=n_dev, n=n, m_local=m_local,
        n_local=n_local, scale=scale, block_sizes=block_sizes,
        causal=causal, softcap=softcap, window=window, sinks=sinks,
        max_mode=max_mode,
    )

    in_specs = [seq_spec, seq_spec, seq_spec]
    extra = []
    if segmented:
        # Q ids shard with Q rows; KV ids replicate (sliced per shard)
        extra = list(_ring_pad_ids(q_segment_ids, kv_segment_ids,
                                   m, n, m_pad, n_pad))
        in_specs += [P(axis_name), P()]

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=tuple(in_specs),
        out_specs=seq_spec,
    )
    def run(q_local, k_local, v_local, *seg_local):
        if q_local.ndim == 4:
            # fold batch into heads (grouping per batch stays aligned:
            # hh // group lands on that batch's kv head); segments are
            # 3D-only, so this arm never carries them
            b, h, mm, d = q_local.shape
            bk, hkv, nn, dk_ = k_local.shape
            out = _ring_diff(
                q_local.reshape(b * h, mm, d),
                k_local.reshape(bk * hkv, nn, dk_),
                v_local.reshape(bk * hkv, nn, v_local.shape[-1]),
                _RingCfg(**cfg),
            )
            return out.reshape(b, h, mm, -1)
        return _ring_diff(q_local, k_local, v_local, _RingCfg(**cfg),
                          *seg_local)

    out = run(q, k, v, *extra)
    if m_pad != m:
        out = lax.slice_in_dim(out, 0, m, axis=seq_axis)
    return out


class _RingCfg(NamedTuple):
    axis_name: str
    n_dev: int
    n: int
    m_local: int
    n_local: int
    scale: float
    block_sizes: "BlockSizes | None"
    causal: bool
    softcap: "float | None"
    window: "int | None"
    sinks: "int | None" = None
    max_mode: str = "bound"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ring_diff(q, k, v, cfg: _RingCfg, q_ids=None, kv_ids=None):
    out, _ = _ring_diff_fwd_impl(q, k, v, cfg, q_ids, kv_ids)
    return out


def _ring_fwd_loop(q, k, v, cfg: _RingCfg, seg=None):
    """Contiguous ring forward on LOCAL blocks — THE one copy of the
    rotate/merge schedule, shared by `ring_attention` (which discards
    the lse) and the custom-VJP path (which saves it).  ``seg`` is an
    optional (q_ids_local, kv_ids_full) pair; each step slices the
    arriving shard's KV ids from the replicated vector.  Returns
    (normalized out, natural-log lse)."""
    idx = lax.axis_index(cfg.axis_name)
    perm = [(j, (j + 1) % cfg.n_dev) for j in range(cfg.n_dev)]
    acc = jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32)
    m_run = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l_run = jnp.zeros(q.shape[:-1], jnp.float32)
    k_cur, v_cur = k, v
    for t in range(cfg.n_dev):
        # prefetch-then-rotate: the next shard's ppermute is issued
        # before this step's compute so XLA overlaps them
        if t + 1 < cfg.n_dev:
            k_next = lax.ppermute(k_cur, cfg.axis_name, perm)
            v_next = lax.ppermute(v_cur, cfg.axis_name, perm)
        shard = (idx - t) % cfg.n_dev
        seg_kw = {}
        if seg is not None:
            seg_kw = {
                "q_segment_ids": seg[0],
                "kv_segment_ids": lax.dynamic_slice(
                    seg[1], (shard * cfg.n_local,), (cfg.n_local,)
                ),
            }
        out_un, lmax, lsum = flash_attention_partials(
            q, k_cur, v_cur, scale=cfg.scale, block_sizes=cfg.block_sizes,
            causal=cfg.causal, q_offset=idx * cfg.m_local,
            kv_offset=shard * cfg.n_local,
            kv_valid=jnp.clip(cfg.n - shard * cfg.n_local, 0, cfg.n_local),
            softcap=cfg.softcap, window=cfg.window, sinks=cfg.sinks,
            max_mode=cfg.max_mode,
            **seg_kw,
        )
        acc, m_run, l_run = _merge_step((acc, m_run, l_run),
                                        out_un, lmax, lsum)
        if t + 1 < cfg.n_dev:
            k_cur, v_cur = k_next, v_next
    l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = jnp.where(l_run == 0.0, NEG_INF, m_run + jnp.log(l_safe))
    return out, lse


def _ring_diff_fwd_impl(q, k, v, cfg: _RingCfg, q_ids=None, kv_ids=None):
    seg = None if q_ids is None else (q_ids, kv_ids)
    out, lse = _ring_fwd_loop(q, k, v, cfg, seg=seg)
    return out, (q, k, v, q_ids, kv_ids, out, lse)


def _ring_diff_fwd(q, k, v, cfg: _RingCfg, q_ids=None, kv_ids=None):
    out, res = _ring_diff_fwd_impl(q, k, v, cfg, q_ids, kv_ids)
    return out, res


def _ring_diff_bwd(cfg: _RingCfg, res, dout):
    from attention_tpu.ops.flash import _should_interpret
    from attention_tpu.ops.flash_bwd import flash_backward
    from attention_tpu.ops.flash_vjp import _seg_zeros

    q, k, v, q_ids, kv_ids, out, lse = res
    idx = lax.axis_index(cfg.axis_name)
    perm = [(j, (j + 1) % cfg.n_dev) for j in range(cfg.n_dev)]
    interpret = _should_interpret()
    dq = jnp.zeros(q.shape, jnp.float32)
    dk_cur = jnp.zeros(k.shape, jnp.float32)
    dv_cur = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    dk_s = dv_s = None
    if cfg.sinks is not None:
        # Out-of-window sink pairs: the banded kernel covers only the
        # window band, so the sliver supplies the rest.  The sink rows
        # are shard 0's first `sinks` KV rows — fetch JUST that sliver
        # once (all_gather of O(sinks·d), then shard 0's copy) and
        # compute the patch ONCE per device instead of per ring step
        # (it used to run every step and be where-gated off on all but
        # one — O(n_dev · m · sinks · d) redundant work).
        # kv_valid=None: shard 0 is always fully real (sequence padding
        # lives in the LAST shard) and sinks <= n_local is enforced at
        # entry, so the sink columns can't be padded.
        from attention_tpu.ops.flash_bwd import _sink_patch

        se0 = min(cfg.sinks, k.shape[-2])
        k_sink = lax.all_gather(k[:, :se0], cfg.axis_name)[0]
        v_sink = lax.all_gather(v[:, :se0], cfg.axis_name)[0]
        dq_s, dk_s, dv_s, se = _sink_patch(
            q, k_sink, v_sink, out, lse, dout, scale=cfg.scale,
            window=cfg.window, sinks=cfg.sinks, softcap=cfg.softcap,
            q_offset=idx * cfg.m_local,
        )
        dq = dq + dq_s
    for t in range(cfg.n_dev):
        if t + 1 < cfg.n_dev:
            k_next = lax.ppermute(k_cur, cfg.axis_name, perm)
            v_next = lax.ppermute(v_cur, cfg.axis_name, perm)
        shard = (idx - t) % cfg.n_dev
        seg_kw = {}
        if q_ids is not None:
            seg_kw = {
                "q_segment_ids": q_ids,
                "kv_segment_ids": lax.dynamic_slice(
                    kv_ids, (shard * cfg.n_local,), (cfg.n_local,)
                ),
            }
        dq_i, dk_i, dv_i = flash_backward(
            q, k_cur, v_cur, out, lse, dout,
            scale=cfg.scale, causal=cfg.causal,
            block_sizes=None,  # backward keeps its own tuned defaults
            interpret=interpret, window=cfg.window, softcap=cfg.softcap,
            q_offset=idx * cfg.m_local,
            kv_offset=shard * cfg.n_local,
            kv_valid=jnp.clip(cfg.n - shard * cfg.n_local, 0, cfg.n_local),
            **seg_kw,
        )
        dq = dq + dq_i.astype(jnp.float32)
        # accumulate into the buffer of the shard CURRENTLY resident,
        # THEN rotate it together with the shard (add-before-rotate:
        # the arriving buffer belongs to the NEXT shard)
        dk_cur = dk_cur + dk_i.astype(jnp.float32)
        dv_cur = dv_cur + dv_i.astype(jnp.float32)
        if cfg.sinks is not None:
            # the precomputed sink dK/dV must land in shard 0's
            # traveling buffer — gate the (tiny) add to the step where
            # shard 0 is resident; the sliver itself was computed once
            # before the loop against the true sink rows
            gate = shard == 0
            dk_cur = dk_cur.at[:, :se].add(jnp.where(gate, dk_s, 0.0))
            dv_cur = dv_cur.at[:, :se].add(jnp.where(gate, dv_s, 0.0))
        if t + 1 < cfg.n_dev:
            dk_cur = lax.ppermute(dk_cur, cfg.axis_name, perm)
            dv_cur = lax.ppermute(dv_cur, cfg.axis_name, perm)
            k_cur, v_cur = k_next, v_next
    # after R-1 rotations shard s sits at device (s-1) mod R; one more
    # rotation delivers each shard's accumulated gradients home
    dk_home = lax.ppermute(dk_cur, cfg.axis_name, perm)
    dv_home = lax.ppermute(dv_cur, cfg.axis_name, perm)
    return (dq.astype(q.dtype), dk_home.astype(k.dtype),
            dv_home.astype(v.dtype), _seg_zeros(q_ids), _seg_zeros(kv_ids))


_ring_diff.defvjp(_ring_diff_fwd, _ring_diff_bwd)


def _merge_step(state, out_un, lmax, lsum):
    """Online merge of one partials call into a running (acc, m, l)
    state — the rmax/rsum recurrence (`attention-mpi.c:179-181`) applied
    across ring steps; fully-masked calls arrive as lmax=-inf no-ops."""
    acc, m_run, l_run = state
    m_new = jnp.maximum(m_run, lmax)
    c_old = jnp.where(m_run == NEG_INF, 0.0, jnp.exp(m_run - m_new))
    c_new = jnp.where(lmax == NEG_INF, 0.0, jnp.exp(lmax - m_new))
    return (
        acc * c_old[..., None] + out_un * c_new[..., None],
        m_new,
        l_run * c_old + lsum * c_new,
    )


def _zig_prepare(q, k, v, n_dev):
    """Shared zigzag preamble: self-attention shape check + pad the
    sequence to a 2R-chunk multiple.  Returns (q, k, v, chunk, n, m,
    c_pad, seq_axis)."""
    m = q.shape[-2]
    n = k.shape[-2]
    if m != n:
        raise ValueError(
            f"zigzag ring is self-attention-shaped (m == n), got {m} != {n}"
        )
    seq_axis = q.ndim - 2
    n_chunks = 2 * n_dev
    c_pad = -(-n // n_chunks) * n_chunks
    if c_pad != n:
        pad = [(0, 0)] * (q.ndim - 2) + [(0, c_pad - n), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    return q, k, v, c_pad // n_chunks, n, m, c_pad, seq_axis


def _ring_pad_ids(q_segment_ids, kv_segment_ids, m, n, m_pad, n_pad):
    """Validate a (q_ids, kv_ids) pair and pad to the ring-padded
    lengths with DISTINCT negative sentinels (-1 for Q, -2 for KV):
    padded rows match no non-negative real id, and the distinct values
    keep padded Q rows from matching padded KV rows either — the
    output slice-off makes that unobservable today, but the invariant
    no longer depends on it.  Length mismatches must fail at trace
    time: ``lax.dynamic_slice`` CLAMPS out-of-bounds starts, so a
    wrong-length id vector would otherwise hand shards silently wrong
    ids."""
    q_seg = jnp.asarray(q_segment_ids, jnp.int32)
    kv_seg = jnp.asarray(kv_segment_ids, jnp.int32)
    if q_seg.ndim != 1 or kv_seg.ndim != 1:
        raise ValueError("ring segment ids are 1D global vectors")
    if q_seg.shape[0] != m or kv_seg.shape[0] != n:
        raise ValueError(
            f"segment id lengths ({q_seg.shape[0]}, {kv_seg.shape[0]}) "
            f"must match the sequence lengths ({m}, {n})"
        )
    if m_pad != m:
        q_seg = jnp.pad(q_seg, (0, m_pad - m), constant_values=-1)
    if n_pad != n:
        kv_seg = jnp.pad(kv_seg, (0, n_pad - n), constant_values=-2)
    return q_seg, kv_seg


def _zig_pad_ids(segment_ids, m, n, c_pad):
    """Zigzag variant of :func:`_ring_pad_ids`: both vectors pad to the
    2R-chunk-padded length.  Ids stay in GLOBAL order — segment matching
    is equality-based, so the zigzag layout never permutes them; chunk
    calls slice by chunk id instead."""
    return _ring_pad_ids(segment_ids[0], segment_ids[1], m, n,
                         c_pad, c_pad)


def _zigzag_ring(q, k, v, *, mesh, axis_name, scale, block_sizes, softcap,
                 window=None, sinks=None, segment_ids=None,
                 max_mode="bound"):
    """Causal ring attention with the llama-3-style zigzag layout.

    The sequence is split into 2R chunks; device d owns chunks
    (d, 2R-1-d) — one early, one late.  Per ring step each device then
    carries EXACTLY 2·C² causal score work (C = chunk rows): the early
    chunk's missing future work is exactly compensated by the late
    chunk's surplus past work, for every (device, step) pair — the
    per-step analog of the reference's ±1-row owner balance
    (`attention-mpi.c:19-27`).  The contiguous schedule instead gives
    device d at step t either a full, empty, or diagonal shard: device
    R-1 does ~R times the per-step work of device 0, and every step's
    merge waits on the slowest device.

    Of the four (q chunk x kv chunk) pairs per step, (q_lo, kv_hi) is
    empty BY CONSTRUCTION (kv chunk 2R-1-e is always in q chunk d's
    future) and is skipped at trace time; the kernel's dynamic causal
    guard skips the tiles of whichever of (q_lo, kv_lo)/(q_hi, kv_hi)
    is empty at this step.
    """
    n_dev = mesh.shape[axis_name]
    q, k, v, chunk, n, m, c_pad, seq_axis = _zig_prepare(q, k, v, n_dev)
    n_chunks = 2 * n_dev

    # zigzag permutation: device d's contiguous 2-chunk slice holds
    # global chunks (d, 2R-1-d); built as a static numpy gather index
    import numpy as np

    order = []
    for d in range(n_dev):
        order += [d, n_chunks - 1 - d]
    idx = np.concatenate(
        [np.arange(c * chunk, (c + 1) * chunk) for c in order]
    )
    inv = np.empty_like(idx)
    inv[idx] = np.arange(idx.size)
    idx_j = jnp.asarray(idx)
    q_z = jnp.take(q, idx_j, axis=seq_axis)
    k_z = jnp.take(k, idx_j, axis=seq_axis)
    v_z = jnp.take(v, idx_j, axis=seq_axis)

    seq_spec = P(*([None] * seq_axis), axis_name, None)

    zcfg = _ZigCfg(
        axis_name=axis_name, n_dev=n_dev, n=n, chunk=chunk, scale=scale,
        block_sizes=block_sizes, softcap=softcap, window=window,
        sinks=sinks, max_mode=max_mode,
    )

    extra = []
    in_specs = [seq_spec, seq_spec, seq_spec]
    if segment_ids is not None:
        # both id vectors replicated in GLOBAL order; chunk calls slice
        extra = list(_zig_pad_ids(segment_ids, m, n, c_pad))
        in_specs += [P(), P()]

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=tuple(in_specs),
        out_specs=seq_spec,
    )
    def run(q_local, k_local, v_local, *seg_local):
        out_lo, _, out_hi, _ = _zig_fwd_loop(
            q_local, k_local, v_local, zcfg,
            seg=tuple(seg_local) if seg_local else None,
        )
        return jnp.concatenate([out_lo, out_hi], axis=seq_axis)

    out = run(q_z, k_z, v_z, *extra)
    out = jnp.take(out, jnp.asarray(inv), axis=seq_axis)
    if c_pad != n:
        out = lax.slice_in_dim(out, 0, m, axis=seq_axis)
    return out


class _ZigCfg(NamedTuple):
    axis_name: str
    n_dev: int
    n: int
    chunk: int
    scale: float
    block_sizes: "BlockSizes | None"
    softcap: "float | None"
    window: "int | None"
    sinks: "int | None" = None
    max_mode: str = "bound"


def _zig_slices(ndim, chunk):
    sl_lo = tuple([slice(None)] * (ndim - 2) + [slice(0, chunk)])
    sl_hi = tuple([slice(None)] * (ndim - 2) + [slice(chunk, None)])
    return sl_lo, sl_hi


def _zig_chunk_ids(ids_full, cid, chunk):
    """Slice chunk ``cid``'s ids from a replicated global id vector
    (``cid`` is a traced device-dependent chunk index)."""
    return lax.dynamic_slice(ids_full, (cid * chunk,), (chunk,))


def _zig_fwd_loop(q_local, k_local, v_local, z: _ZigCfg, seg=None):
    """The one copy of the zigzag rotate/merge schedule, shared by the
    plain forward (which discards the lse) and the custom-VJP path.
    ``seg`` is an optional (q_ids_full, kv_ids_full) pair of replicated
    GLOBAL id vectors; every chunk-pair call slices its chunks' ids
    (segment matching is positionless, so the zigzag layout needs no id
    permutation).  Returns (out_lo, lse_lo, out_hi, lse_hi) for the
    device's two chunks."""
    n_chunks = 2 * z.n_dev
    idx_d = lax.axis_index(z.axis_name)
    a = idx_d  # early chunk id
    b = n_chunks - 1 - idx_d  # late chunk id
    perm = [(j, (j + 1) % z.n_dev) for j in range(z.n_dev)]
    sl_lo, sl_hi = _zig_slices(q_local.ndim, z.chunk)
    q_lo, q_hi = q_local[sl_lo], q_local[sl_hi]
    if seg is not None:
        q_seg_lo = _zig_chunk_ids(seg[0], a, z.chunk)
        q_seg_hi = _zig_chunk_ids(seg[0], b, z.chunk)

    def fresh(q_c):
        shape = q_c.shape[:-1]
        return (
            jnp.zeros(shape + (v_local.shape[-1],), jnp.float32),
            jnp.full(shape, NEG_INF, jnp.float32),
            jnp.zeros(shape, jnp.float32),
        )

    lo = fresh(q_lo)
    hi = fresh(q_hi)

    def partial_call(q_c, k_c, v_c, q_cid, kv_cid, q_seg_c=None):
        seg_kw = {}
        if seg is not None:
            seg_kw = {
                "q_segment_ids": q_seg_c,
                "kv_segment_ids": _zig_chunk_ids(seg[1], kv_cid, z.chunk),
            }
        return flash_attention_partials(
            q_c, k_c, v_c, scale=z.scale, block_sizes=z.block_sizes,
            causal=True,
            q_offset=q_cid * z.chunk,
            kv_offset=kv_cid * z.chunk,
            kv_valid=jnp.clip(z.n - kv_cid * z.chunk, 0, z.chunk),
            softcap=z.softcap,
            window=z.window,
            sinks=z.sinks,
            max_mode=z.max_mode,
            **seg_kw,
        )

    seg_lo = None if seg is None else q_seg_lo
    seg_hi = None if seg is None else q_seg_hi
    k_cur, v_cur = k_local, v_local
    for t in range(z.n_dev):
        if t + 1 < z.n_dev:
            k_next = lax.ppermute(k_cur, z.axis_name, perm)
            v_next = lax.ppermute(v_cur, z.axis_name, perm)
        e = (idx_d - t) % z.n_dev  # whose KV pair we hold now
        ae = e
        be = n_chunks - 1 - e
        k_lo, k_hi = k_cur[sl_lo], k_cur[sl_hi]
        v_lo, v_hi = v_cur[sl_lo], v_cur[sl_hi]
        # (q_hi, kv_lo): always fully unmasked (b > ae)
        hi = _merge_step(hi, *partial_call(q_hi, k_lo, v_lo, b, ae, seg_hi))
        # (q_lo, kv_lo): nonempty iff ae <= a — dynamic kernel skip
        lo = _merge_step(lo, *partial_call(q_lo, k_lo, v_lo, a, ae, seg_lo))
        # (q_hi, kv_hi): nonempty iff be <= b — dynamic kernel skip
        hi = _merge_step(hi, *partial_call(q_hi, k_hi, v_hi, b, be, seg_hi))
        # (q_lo, kv_hi): empty by construction — skipped at trace time
        if t + 1 < z.n_dev:
            k_cur, v_cur = k_next, v_next

    def finalize(state, q_c):
        acc, m_run, l_run = state
        l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
        out = (acc / l_safe[..., None]).astype(q_c.dtype)
        lse = jnp.where(l_run == 0.0, NEG_INF, m_run + jnp.log(l_safe))
        return out, lse

    out_lo, lse_lo = finalize(lo, q_lo)
    out_hi, lse_hi = finalize(hi, q_hi)
    return out_lo, lse_lo, out_hi, lse_hi


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _zig_diff(q, k, v, z: _ZigCfg, q_ids=None, kv_ids=None):
    seg = None if q_ids is None else (q_ids, kv_ids)
    out_lo, _, out_hi, _ = _zig_fwd_loop(q, k, v, z, seg=seg)
    return jnp.concatenate([out_lo, out_hi], axis=-2)


def _zig_diff_fwd(q, k, v, z: _ZigCfg, q_ids=None, kv_ids=None):
    seg = None if q_ids is None else (q_ids, kv_ids)
    out_lo, lse_lo, out_hi, lse_hi = _zig_fwd_loop(q, k, v, z, seg=seg)
    out = jnp.concatenate([out_lo, out_hi], axis=-2)
    return out, (q, k, v, q_ids, kv_ids, out_lo, lse_lo, out_hi, lse_hi)


def _zig_diff_bwd(z: _ZigCfg, res, dout):
    """Backward zigzag ring: the kv-pair gradient buffers travel with
    their pair (add-before-rotate; one final rotation delivers home),
    and every (device, step) carries the same 3-call balanced work as
    the forward — the load-balance property holds in BOTH passes."""
    from attention_tpu.ops.flash import _should_interpret
    from attention_tpu.ops.flash_bwd import flash_backward
    from attention_tpu.ops.flash_vjp import _seg_zeros

    q, k, v, q_ids, kv_ids, out_lo, lse_lo, out_hi, lse_hi = res
    n_chunks = 2 * z.n_dev
    idx_d = lax.axis_index(z.axis_name)
    a = idx_d
    b = n_chunks - 1 - idx_d
    perm = [(j, (j + 1) % z.n_dev) for j in range(z.n_dev)]
    interpret = _should_interpret()
    sl_lo, sl_hi = _zig_slices(q.ndim, z.chunk)
    q_lo, q_hi = q[sl_lo], q[sl_hi]
    dout_lo, dout_hi = dout[sl_lo], dout[sl_hi]
    seg_lo = seg_hi = None
    if q_ids is not None:
        seg_lo = _zig_chunk_ids(q_ids, a, z.chunk)
        seg_hi = _zig_chunk_ids(q_ids, b, z.chunk)
    dq_lo = jnp.zeros(q_lo.shape, jnp.float32)
    dq_hi = jnp.zeros(q_hi.shape, jnp.float32)
    dk_cur = jnp.zeros(k.shape, jnp.float32)
    dv_cur = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    s12k = s12v = None
    if z.sinks is not None:
        # out-of-window sink pairs (see the contiguous backward): the
        # absolute sink rows live in global chunk 0 = device 0's early
        # chunk; fetch just that sliver once and compute both local q
        # chunks' patches ONCE instead of twice per ring step.
        # kv_valid=None: chunk 0 is always fully real (sequence padding
        # lives in the LAST chunks) and sinks <= chunk is enforced at
        # entry, so the sink columns can't be padded.
        from attention_tpu.ops.flash_bwd import _sink_patch

        se0 = min(z.sinks, z.chunk)
        k_sink = lax.all_gather(k[sl_lo][:, :se0], z.axis_name)[0]
        v_sink = lax.all_gather(v[sl_lo][:, :se0], z.axis_name)[0]
        s1q, s1k, s1v, se = _sink_patch(
            q_hi, k_sink, v_sink, out_hi, lse_hi, dout_hi,
            scale=z.scale, window=z.window, sinks=z.sinks,
            softcap=z.softcap, q_offset=b * z.chunk)
        s2q, s2k, s2v, _ = _sink_patch(
            q_lo, k_sink, v_sink, out_lo, lse_lo, dout_lo,
            scale=z.scale, window=z.window, sinks=z.sinks,
            softcap=z.softcap, q_offset=a * z.chunk)
        dq_hi = dq_hi + s1q
        dq_lo = dq_lo + s2q
        s12k = s1k + s2k
        s12v = s1v + s2v

    def bwd_call(q_c, k_c, v_c, out_c, lse_c, dout_c, q_cid, kv_cid,
                 q_seg_c=None):
        seg_kw = {}
        if q_ids is not None:
            seg_kw = {
                "q_segment_ids": q_seg_c,
                "kv_segment_ids": _zig_chunk_ids(kv_ids, kv_cid, z.chunk),
            }
        return flash_backward(
            q_c, k_c, v_c, out_c, lse_c, dout_c,
            scale=z.scale, causal=True, interpret=interpret,
            window=z.window, softcap=z.softcap,
            q_offset=q_cid * z.chunk,
            kv_offset=kv_cid * z.chunk,
            kv_valid=jnp.clip(z.n - kv_cid * z.chunk, 0, z.chunk),
            **seg_kw,
        )

    for t in range(z.n_dev):
        if t + 1 < z.n_dev:
            k_next = lax.ppermute(k_cur, z.axis_name, perm)
            v_next = lax.ppermute(v_cur, z.axis_name, perm)
        e = (idx_d - t) % z.n_dev
        ae = e
        be = n_chunks - 1 - e
        k_lo, k_hi = k_cur[sl_lo], k_cur[sl_hi]
        v_lo, v_hi = v_cur[sl_lo], v_cur[sl_hi]
        # the forward's three chunk-pair calls, differentiated
        g1q, g1k, g1v = bwd_call(q_hi, k_lo, v_lo, out_hi, lse_hi,
                                 dout_hi, b, ae, seg_hi)
        g2q, g2k, g2v = bwd_call(q_lo, k_lo, v_lo, out_lo, lse_lo,
                                 dout_lo, a, ae, seg_lo)
        g3q, g3k, g3v = bwd_call(q_hi, k_hi, v_hi, out_hi, lse_hi,
                                 dout_hi, b, be, seg_hi)
        dq_hi = dq_hi + g1q.astype(jnp.float32) + g3q.astype(jnp.float32)
        dq_lo = dq_lo + g2q.astype(jnp.float32)
        # upcast each term BEFORE adding (with bf16 k/v the kernel
        # returns bf16 grads; a bf16+bf16 add would round pre-buffer)
        dk_cur = dk_cur.at[sl_lo].add(
            g1k.astype(jnp.float32) + g2k.astype(jnp.float32))
        dk_cur = dk_cur.at[sl_hi].add(g3k.astype(jnp.float32))
        dv_cur = dv_cur.at[sl_lo].add(
            g1v.astype(jnp.float32) + g2v.astype(jnp.float32))
        dv_cur = dv_cur.at[sl_hi].add(g3v.astype(jnp.float32))
        if z.sinks is not None:
            # the precomputed sink dK/dV land in global chunk 0's
            # traveling buffer — resident as the visiting EARLY chunk
            # when ae == 0; the slivers themselves were computed once
            # before the loop against the true sink rows
            gate = ae == 0
            dk_cur = dk_cur.at[:, :se].add(jnp.where(gate, s12k, 0.0))
            dv_cur = dv_cur.at[:, :se].add(jnp.where(gate, s12v, 0.0))
        if t + 1 < z.n_dev:
            dk_cur = lax.ppermute(dk_cur, z.axis_name, perm)
            dv_cur = lax.ppermute(dv_cur, z.axis_name, perm)
            k_cur, v_cur = k_next, v_next
    dk_home = lax.ppermute(dk_cur, z.axis_name, perm)
    dv_home = lax.ppermute(dv_cur, z.axis_name, perm)
    dq = jnp.concatenate([dq_lo, dq_hi], axis=-2)
    return (dq.astype(q.dtype), dk_home.astype(k.dtype),
            dv_home.astype(v.dtype), _seg_zeros(q_ids), _seg_zeros(kv_ids))


_zig_diff.defvjp(_zig_diff_fwd, _zig_diff_bwd)


def _zigzag_exchange(x, axis_name, n_dev, chunk, *, inverse=False):
    """Reshard between contiguous 2-chunk slices and zigzag (early,
    late) slices WITHOUT a global gather — two half-chunk ppermutes
    plus per-device slot selects, all inside shard_map, so the layout
    change stays SPMD-partitionable however the caller's jit shards
    the inputs (a plain `jnp.take` permutation over an sp-sharded
    sequence fails XLA's partitioner).

    Forward: contiguous device d holds chunks (2d, 2d+1); zigzag device
    r wants (r, 2R-1-r).  Since 2R-1 is odd, each device's two target
    chunks always have opposite parity, so the even-chunk and odd-chunk
    flows each form a bijective device permutation.
    """
    n_chunks = 2 * n_dev
    sl_lo, sl_hi = _zig_slices(x.ndim, chunk)
    seq_axis = x.ndim - 2
    r = lax.axis_index(axis_name)
    even = (r % 2) == 0

    def dest_of_chunk(c):
        return c if c < n_dev else n_chunks - 1 - c

    if not inverse:
        h0, h1 = x[sl_lo], x[sl_hi]  # chunks 2d, 2d+1
        perm0 = [(d, dest_of_chunk(2 * d)) for d in range(n_dev)]
        perm1 = [(d, dest_of_chunk(2 * d + 1)) for d in range(n_dev)]
        arr0 = lax.ppermute(h0, axis_name, perm0)  # the even chunk
        arr1 = lax.ppermute(h1, axis_name, perm1)  # the odd chunk
        # device r's early chunk is r (parity r%2), late is 2R-1-r
        lo = jnp.where(even, arr0, arr1)
        hi = jnp.where(even, arr1, arr0)
        return jnp.concatenate([lo, hi], axis=seq_axis)
    # inverse: zigzag device r holds (lo=chunk r, hi=chunk 2R-1-r);
    # route the even/odd chunks back to contiguous device c//2
    lo, hi = x[sl_lo], x[sl_hi]
    a = jnp.where(even, lo, hi)  # the even chunk this device holds
    b = jnp.where(even, hi, lo)  # the odd one
    perm_a = [
        (s, ((s if s % 2 == 0 else n_chunks - 1 - s) // 2))
        for s in range(n_dev)
    ]
    perm_b = [
        (s, (((n_chunks - 1 - s) if s % 2 == 0 else s) // 2))
        for s in range(n_dev)
    ]
    arr_a = lax.ppermute(a, axis_name, perm_a)  # chunk 2d -> h0
    arr_b = lax.ppermute(b, axis_name, perm_b)  # chunk 2d+1 -> h1
    return jnp.concatenate([arr_a, arr_b], axis=seq_axis)


def _zigzag_ring_diff(q, k, v, *, mesh, axis_name, batch_axis, head_axis,
                      scale, block_sizes, softcap, window, sinks=None,
                      segment_ids=None, max_mode="bound"):
    """Differentiable zigzag ring: in-shard_map layout exchange ->
    _zig_diff -> inverse exchange (all collective-based; autodiff
    transposes the ppermutes).  Segment ids ride replicated in GLOBAL
    order — they never enter the exchange (chunk calls slice by chunk
    id; segment matching is positionless)."""
    n_dev = mesh.shape[axis_name]
    q, k, v, chunk, n, m, c_pad, seq_axis = _zig_prepare(q, k, v, n_dev)

    from attention_tpu.parallel.cp import _maybe_axis

    h_axis = _maybe_axis(mesh, head_axis, q.shape[-3])
    if h_axis is not None and k.shape[-3] % mesh.shape[h_axis] != 0:
        h_axis = None
    if q.ndim == 4:
        b_axis = _maybe_axis(mesh, batch_axis, q.shape[0])
        seq_spec = P(b_axis, h_axis, axis_name, None)
    else:
        seq_spec = P(h_axis, axis_name, None)

    if sinks is not None and sinks > chunk:
        raise ValueError(
            f"sinks ({sinks}) must fit in one zigzag chunk ({chunk} rows)"
        )
    zcfg = _ZigCfg(
        axis_name=axis_name, n_dev=n_dev, n=n, chunk=chunk, scale=scale,
        block_sizes=block_sizes, softcap=softcap, window=window,
        sinks=sinks, max_mode=max_mode,
    )

    in_specs = [seq_spec, seq_spec, seq_spec]
    extra = []
    if segment_ids is not None:
        extra = list(_zig_pad_ids(segment_ids, m, n, c_pad))
        in_specs += [P(), P()]

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=tuple(in_specs),
        out_specs=seq_spec,
    )
    def run(q_local, k_local, v_local, *seg_local):
        exch = functools.partial(_zigzag_exchange, axis_name=axis_name,
                                 n_dev=n_dev, chunk=chunk)
        q_z, k_z, v_z = exch(q_local), exch(k_local), exch(v_local)
        if q_z.ndim == 4:
            # segments are 3D-only, so this arm never carries them
            bq, h, mm, d = q_z.shape
            bk, hkv, nn, dk_ = k_z.shape
            out = _zig_diff(
                q_z.reshape(bq * h, mm, d),
                k_z.reshape(bk * hkv, nn, dk_),
                v_z.reshape(bk * hkv, nn, v_z.shape[-1]),
                zcfg,
            )
            out = out.reshape(bq, h, mm, -1)
        else:
            out = _zig_diff(q_z, k_z, v_z, zcfg, *seg_local)
        return exch(out, inverse=True)

    out = run(q, k, v, *extra)
    if c_pad != n:
        out = lax.slice_in_dim(out, 0, m, axis=seq_axis)
    return out
