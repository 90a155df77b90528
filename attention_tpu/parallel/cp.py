"""Differentiable context-parallel flash attention for training.

This is the integration the reference actually is: one orchestrator that
composes the local fused kernel with the distribution scheme
(`attention-mpi.c:191-407` — partitioning, distribution, local online
softmax, global merge in a single `attention()` entry).  Here the
composition must additionally be *differentiable*, because the framework
trains through it: the sharded training step runs the Pallas flash
custom VJP under the mesh rather than leaving sharded-sequence attention
to XLA's auto-SPMD all-gather of the dense einsum path.

Scheme (all-gather context parallelism):

  * activations enter sequence-sharded over the ``cp`` axis (the
    training layout — every other layer of the model is local in the
    sequence dim);
  * inside ``shard_map`` each device all-gathers the (small, GQA) K/V
    heads over the cp axis and runs the fused flash kernel on its local
    Q shard with ``q_offset = axis_index * m_local`` — the kernel's
    dynamic-offset contract keeps causal/window masking globally
    correct (`ops/flash.py::_flash_kernel` offsets_ref);
  * the backward needs no hand-written collective: JAX transposes the
    ``all_gather`` to a ``psum_scatter``, which reduce-scatters each
    device's full-sequence dK/dV contribution back to its shard, and
    the flash custom VJP (`ops/flash_vjp.py`) differentiates the local
    kernel with the same offsets.

Compared to rotating KV around the ring (`parallel/ring.py`), the
all-gather form trades O(n) peak KV memory per device for a single
bulk collective that XLA can schedule ahead of the kernel; for training
blocks where K/V are `(B, H_kv, n, d)` bf16 this is the standard
Megatron/MaxText CP layout.  The ring remains the serving/131k path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from attention_tpu.ops.flash import BlockSizes
from attention_tpu.ops.flash_vjp import flash_attention_diff


def _maybe_axis(mesh: Mesh, axis: str | None, dim: int) -> str | None:
    """Use ``axis`` for a dim only if the mesh has it and it divides."""
    if axis is None or axis not in mesh.axis_names:
        return None
    if dim % mesh.shape[axis] != 0:
        return None
    return axis


def cp_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "sp",
    batch_axis: str | None = "dp",
    head_axis: str | None = "tp",
    scale: float | None = None,
    causal: bool = True,
    window: int | None = None,
    sinks: int | None = None,
    softcap: float | None = None,
    q_segment_ids=None,
    kv_segment_ids=None,
    block_sizes: BlockSizes | None = None,
    bwd_impl: str = "pallas",
    max_mode: str = "bound",
) -> jax.Array:
    """Context-parallel fused attention, differentiable end to end.

    ``q``/``k``/``v`` are (B, H, S, dh) or (H, S, dh) with the sequence
    axis sharded (or shardable) over ``axis_name``; B/H may additionally
    shard over ``batch_axis``/``head_axis`` when present in the mesh and
    divisible (both Q and KV head counts must divide for the head axis
    to be used).  Returns attention output sharded exactly like Q.

    GQA is supported (KV heads dividing Q heads); ``window`` needs
    ``causal=True``; ``sinks`` compose too (the gathered KV holds the
    absolute sink positions, so only q_offset awareness is needed —
    including the backward's sink sliver).  Packed-sequence segment ids
    ((m,)/(n,) global int32; 3D inputs only — the kernel's segment
    limit) shard with Q and replicate with the gathered KV.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no axis {axis_name!r}")
    if q.ndim not in (3, 4):
        raise ValueError(f"cp attention takes 3D/4D inputs, got {q.ndim}D")
    n_dev = mesh.shape[axis_name]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

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

    h_axis = _maybe_axis(mesh, head_axis, q.shape[-3])
    if h_axis is not None and k.shape[-3] % mesh.shape[h_axis] != 0:
        h_axis = None  # KV heads must split too (GQA grouping per shard)
    if q.ndim == 4:
        b_axis = _maybe_axis(mesh, batch_axis, q.shape[0])
        spec = P(b_axis, h_axis, axis_name, None)
    else:
        spec = P(h_axis, axis_name, None)
    seq_axis = q.ndim - 2

    segmented = q_segment_ids is not None
    if segmented != (kv_segment_ids is not None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")
    if segmented and q.ndim == 4:
        raise ValueError(
            "segment ids support 3D inputs (ids shared across heads)"
        )
    in_specs = [spec, spec, spec]
    extra = []
    if segmented:
        q_seg = jnp.asarray(q_segment_ids, jnp.int32)
        kv_seg = jnp.asarray(kv_segment_ids, jnp.int32)
        if m_pad != m:
            q_seg = jnp.pad(q_seg, (0, m_pad - m), constant_values=-1)
        if n_pad != n:
            kv_seg = jnp.pad(kv_seg, (0, n_pad - n), constant_values=-1)
        # Q ids shard with Q rows; KV ids replicate (the gathered KV is
        # the full sequence on every device)
        extra = [q_seg, kv_seg]
        in_specs += [P(axis_name), P()]

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=tuple(in_specs),
        out_specs=spec,
    )
    def run(q_local, k_local, v_local, *seg_local):
        idx = lax.axis_index(axis_name)
        k_full = lax.all_gather(k_local, axis_name, axis=seq_axis,
                                tiled=True)
        v_full = lax.all_gather(v_local, axis_name, axis=seq_axis,
                                tiled=True)
        return flash_attention_diff(
            q_local, k_full, v_full,
            scale=scale, causal=causal,
            q_offset=idx * m_local,
            kv_valid=n if n_pad != n else None,
            window=window, sinks=sinks, softcap=softcap,
            q_segment_ids=seg_local[0] if seg_local else None,
            kv_segment_ids=seg_local[1] if seg_local else None,
            block_sizes=block_sizes, bwd_impl=bwd_impl,
            max_mode=max_mode,
        )

    out = run(q, k, v, *extra)
    if m_pad != m:
        out = lax.slice_in_dim(out, 0, m, axis=seq_axis)
    return out
