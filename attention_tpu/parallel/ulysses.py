"""Ulysses-style sequence parallelism: all-to-all head/sequence reshard.

The alternative context-parallel mode from SURVEY §2's strategy inventory
(not present in the reference, which is allreduce-based): instead of
rotating KV shards, a single ``lax.all_to_all`` converts
sequence-sharding into head-sharding, each device runs *complete*
attention for its subset of heads (no softmax collectives at all), and a
second all-to-all converts back.  Two collectives total per call — cheaper
than a ring when the head count divides the mesh and sequences are only
moderately long.

GQA handling: when the mesh size does not divide the KV head count,
KV heads are repeated just enough to make the reshard uniform —
normally up to the MESH size (the 32Q/4KV BASELINE config on an 8-chip
mesh repeats 2x), falling back to the full Q head count only for
ratios that divide neither way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from attention_tpu.ops.flash import BlockSizes
from attention_tpu.ops.flash_vjp import flash_attention_diff
from attention_tpu.parallel.mesh import default_mesh


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "batch_axis", "scale",
                     "block_sizes", "causal", "softcap", "window", "sinks",
                     "max_mode"),
)
def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_name: str = "sp",
    batch_axis: str | None = "dp",
    scale: float | None = None,
    block_sizes: BlockSizes | None = None,
    causal: bool = False,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
    q_segment_ids=None,
    kv_segment_ids=None,
    max_mode: str = "bound",
) -> jax.Array:
    """All-to-all sequence-parallel attention for multi-head inputs.

    Shapes: (h, m, d) or (b, h, m, d); the sequence axes are sharded over
    ``axis_name`` on the way in and out (4D batches may additionally
    shard over ``batch_axis`` when the mesh has it and it divides).
    Requires the Q head count to be a multiple of the mesh size and
    sequence lengths to be multiples of the mesh size.

    Differentiable end to end: the inner kernel is the flash custom VJP
    and both all-to-alls (plus the GQA repeat) are transposed by
    autodiff — the backward is two more all-to-alls around the Pallas
    backward kernels, so ``cp_impl="ulysses"`` trains
    (`models/attention_layer.py`).

    Carries the single-device kernel's full masking surface (the
    reference's orchestrator supports its kernel's entire surface,
    `attention-mpi.c:191-407`): ``window``/``sinks`` (sliding window +
    StreamingLLM sinks) and packed-sequence segment ids.  After the
    head/seq all-to-all each device holds the FULL sequence for its
    head subset, so the absolute-position features apply unchanged;
    segment ids ((m,)/(n,) global, 3D inputs only — the kernel's
    limit) ride into the shard_map as replicated closures.
    """
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    if q.ndim not in (3, 4):
        raise ValueError(f"ulysses needs (h, m, d) or (b, h, m, d); got {q.shape}")
    hq = q.shape[-3]
    hkv = k.shape[-3]
    if hq % n_dev != 0:
        raise ValueError(f"q heads {hq} not divisible by mesh size {n_dev}")
    if q.shape[-2] % n_dev != 0 or k.shape[-2] % n_dev != 0:
        raise ValueError(
            f"sequence lengths {q.shape[-2]}/{k.shape[-2]} not divisible by "
            f"mesh size {n_dev}"
        )
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

    # GQA survives the all-to-all untouched iff the mesh size divides
    # the KV head count (each device then holds whole kv heads and the
    # contiguous q chunks stay group-aligned).  Otherwise the minimal
    # fix is repeating KV
    # heads up to the MESH size, not the Q head count: device r then
    # holds q heads [r·hq/R, (r+1)·hq/R) and expanded kv head r, whose
    # original head is r//(R/hkv) == (r·hq/R)//(hq/hkv) — the exact head
    # that q-chunk needs.  For 32q/4kv on 8 chips this moves 2x the KV
    # rows over the wire instead of 8x.  Ratios that divide neither way
    # fall back to the full repeat.
    if hkv != hq and hkv % n_dev != 0:
        if hq % hkv != 0:
            raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
        expand = n_dev // hkv if n_dev % hkv == 0 else hq // hkv
        k = jnp.repeat(k, expand, axis=-3)
        v = jnp.repeat(v, expand, axis=-3)

    head_axis = q.ndim - 3
    seq_axis = q.ndim - 2
    if q.ndim == 4:
        from attention_tpu.parallel.cp import _maybe_axis

        b_axis = _maybe_axis(mesh, batch_axis, q.shape[0])
        seq_spec = P(b_axis, None, axis_name, None)
    else:
        seq_spec = P(None, axis_name, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
    )
    def run(q_local, k_local, v_local):
        # seq-sharded -> head-sharded: split heads across devices, gather seq
        qh = lax.all_to_all(q_local, axis_name, head_axis, seq_axis, tiled=True)
        kh = lax.all_to_all(k_local, axis_name, head_axis, seq_axis, tiled=True)
        vh = lax.all_to_all(v_local, axis_name, head_axis, seq_axis, tiled=True)
        out = flash_attention_diff(
            qh, kh, vh, scale=scale, block_sizes=block_sizes, causal=causal,
            softcap=softcap, window=window, sinks=sinks,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            max_mode=max_mode,
        )
        # head-sharded -> seq-sharded
        return lax.all_to_all(out, axis_name, seq_axis, head_axis, tiled=True)

    return run(q, k, v)
