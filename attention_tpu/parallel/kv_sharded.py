"""KV-sharded distributed attention with two-phase softmax normalization.

The TPU-native rebuild of the reference's core distributed algorithm
(`attention-mpi.c:191-407`, SURVEY §3.3):

  * KV rows block-sharded over ranks (owner partitioner,
    `attention-mpi.c:19-27`)           → ``PartitionSpec(axis)`` on K/V
    over a 1D mesh, Q replicated;
  * each rank's local online-softmax pass producing (contrib, lmax, lsum)
    (`attention-mpi.c:333-338`)        → :func:`flash_attention_partials`
    per device inside ``shard_map``;
  * phase 1 ``MPI_Iallreduce(lmax, MAX)`` + rescale by exp(lmax-gmax)
    (`attention-mpi.c:342-351`)        → ``lax.pmax`` over the mesh axis;
  * phase 2 ``MPI_Iallreduce(lsum, SUM)`` + 1/gsum normalize
    (`attention-mpi.c:354-362`)        → ``lax.psum``;
  * ``MPI_Ireduce(contrib → root, SUM)`` (`attention-mpi.c:380`)
                                       → ``lax.psum`` of the normalized
    contributions (all-reduce rather than reduce-to-root: every chip gets
    the result, which is what a fully-sharded consumer wants; XLA lowers
    it to the same ICI reduction tree).

The reference's Q ping-pong broadcast pipeline (`attention-mpi.c:268-330`)
has no hand-written analog: Q is replicated by sharding annotation, XLA's
latency-hiding scheduler overlaps collectives with compute, and the flash
kernel's Q-block grid dimension already streams queries through VMEM in
tiles (the B=512-row batching of `attention-mpi.c:200`, done on-chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from attention_tpu.ops.flash import BlockSizes, flash_attention_partials
from attention_tpu.ops.reference import attention_xla_partials
from attention_tpu.parallel.mesh import default_mesh

NEG_INF = float("-inf")


def merge_partials(out_un, lmax, lsum, axis_name: str):
    """Two-phase global softmax merge over a mesh axis.

    Inputs are each device's (contrib, row_max, row_sumexp); returns the
    globally normalized output on every device.  This is exactly steps 2-4
    of SURVEY §3.3 (reference `attention-mpi.c:340-380`).
    """
    gmax = lax.pmax(lmax, axis_name)  # phase 1: MAX allreduce
    corr = jnp.where(lmax == NEG_INF, 0.0, jnp.exp(lmax - gmax))
    gsum = lax.psum(lsum * corr, axis_name)  # phase 2: SUM allreduce
    contrib = out_un * corr[..., None]
    total = lax.psum(contrib, axis_name)  # contribution reduction
    gsum_safe = jnp.where(gsum == 0.0, 1.0, gsum)  # div-by-zero guard (:358-362)
    return total / gsum_safe[..., None]


def _local_partials(
    q, k, v, *, impl, scale, block_sizes, kv_valid, causal=False, q_offset=0,
    kv_offset=0, softcap=None, window=None, sinks=None, q_segment_ids=None,
    kv_segment_ids=None, max_mode="online",
):
    # ``max_mode`` reaches the flash kernel only: the xla impl is the
    # fp32 oracle whose exact max IS the online recurrence (bound is a
    # kernel optimization, not a semantics change — same outputs)
    if impl == "flash":
        return flash_attention_partials(
            q, k, v, scale=scale, block_sizes=block_sizes, kv_valid=kv_valid,
            causal=causal, q_offset=q_offset, kv_offset=kv_offset,
            softcap=softcap, window=window, sinks=sinks,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            max_mode=max_mode,
        )
    if window is not None or sinks is not None or q_segment_ids is not None:
        raise ValueError(
            "window/sinks/segment ids on the sharded paths run the fused "
            "kernel (impl='flash'); the xla partials oracle does not carry "
            "them"
        )
    return attention_xla_partials(
        q, k, v, scale=scale, kv_valid=kv_valid, causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, softcap=softcap,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh",
        "axis_name",
        "scale",
        "block_sizes",
        "impl",
        "causal",
        "softcap",
        "window",
        "sinks",
        "max_mode",
    ),
)
def kv_sharded_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_name: str = "kv",
    scale: float | None = None,
    block_sizes: BlockSizes | None = None,
    impl: str = "flash",
    causal: bool = False,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
    q_segment_ids=None,
    kv_segment_ids=None,
    max_mode: str = "bound",
) -> jax.Array:
    """Distributed attention with K/V rows sharded over a 1D mesh.

    Q is replicated (broadcast role, `attention-mpi.c:232-241`); K/V rows
    are sharded (scatter role, `:242-266`); softmax is made shard-invariant
    by the two-phase pmax/psum merge.  Output is replicated on every chip.

    Accepts the same 2D/3D/4D shapes as :func:`flash_attention`; the
    sequence axis (second-to-last) of K/V is the sharded one.

    The kernel's full masking surface flows through (the reference's
    orchestrator carries its kernel's entire surface,
    `attention-mpi.c:191-407`): ``window``/``sinks`` masks are expressed
    in GLOBAL positions via each shard's dynamic ``kv_offset``, so a
    band crossing shard boundaries and the absolute sink prefix both
    resolve correctly per shard; packed-sequence segment ids ship with
    their data — Q ids replicated, KV ids sharded alongside K/V rows
    (ids must be 1D, 2D/3D inputs — the kernel's segment limit).
    """
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    n = k.shape[-2]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    segmented = q_segment_ids is not None
    if segmented != (kv_segment_ids is not None):
        raise ValueError("q_segment_ids and kv_segment_ids go together")

    # Pad n up to a multiple of the mesh size; each shard masks its own
    # padded tail via the dynamic kv_valid scalar.
    n_pad = -(-n // n_dev) * n_dev
    if n_pad != n:
        pad = [(0, 0)] * (k.ndim - 2) + [(0, n_pad - n), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    n_local = n_pad // n_dev

    seq_axis = k.ndim - 2
    kv_spec = P(*([None] * seq_axis), axis_name, None)
    in_specs = [P(), kv_spec, kv_spec]
    extra = []
    if segmented:
        kv_seg = jnp.asarray(kv_segment_ids, jnp.int32)
        if n_pad != n:
            # padded rows get id -1: matches no real (non-negative) id
            kv_seg = jnp.pad(kv_seg, (0, n_pad - n), constant_values=-1)
        extra = [jnp.asarray(q_segment_ids, jnp.int32), kv_seg]
        in_specs += [P(), P(axis_name)]

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=tuple(in_specs),
        out_specs=P(),
    )
    def run(q_full, k_local, v_local, *seg_local):
        idx = lax.axis_index(axis_name)
        # valid rows in this shard of the padded sequence (owner_count
        # analog: every shard owns n_local rows, the last ones partly pad)
        kv_valid = jnp.clip(n - idx * n_local, 0, n_local)
        out_un, lmax, lsum = _local_partials(
            q_full,
            k_local,
            v_local,
            impl=impl,
            scale=scale,
            block_sizes=block_sizes,
            kv_valid=kv_valid,
            causal=causal,
            kv_offset=idx * n_local,
            softcap=softcap,
            window=window,
            sinks=sinks,
            q_segment_ids=seg_local[0] if seg_local else None,
            kv_segment_ids=seg_local[1] if seg_local else None,
            max_mode=max_mode,
        )
        return merge_partials(out_un, lmax, lsum, axis_name).astype(q_full.dtype)

    return run(q, k, v, *extra)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "scale", "block_sizes", "causal",
                     "softcap", "max_mode"),
)
def q_sharded_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_name: str = "kv",
    scale: float | None = None,
    block_sizes: BlockSizes | None = None,
    causal: bool = False,
    softcap: float | None = None,
    max_mode: str = "bound",
) -> jax.Array:
    """Replicated-KV attention with Q rows sharded — the 'replicate' arm of
    the adaptive placement policy (small KV, `attention-mpi.c:217-241`).

    Each chip runs the fused kernel on its Q slice against the full K/V;
    there are no per-batch collectives at all.  Output is Q-sharded.
    """
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    m = q.shape[-2]
    m_pad = -(-m // n_dev) * n_dev
    if m_pad != m:
        pad = [(0, 0)] * (q.ndim - 2) + [(0, m_pad - m), (0, 0)]
        q = jnp.pad(q, pad)
    seq_axis = q.ndim - 2
    q_spec = P(*([None] * seq_axis), axis_name, None)

    from attention_tpu.ops.flash import flash_attention

    @functools.partial(
        shard_map, mesh=mesh, check_vma=False, in_specs=(q_spec, P(), P()), out_specs=q_spec
    )
    def run(q_local, k_full, v_full):
        m_local = q_local.shape[-2]
        q_offset = lax.axis_index(axis_name) * m_local
        return flash_attention(
            q_local, k_full, v_full, scale=scale, block_sizes=block_sizes,
            causal=causal, q_offset=q_offset, softcap=softcap,
            max_mode=max_mode,
        )

    out = run(q, k, v)
    if m_pad != m:
        out = lax.slice_in_dim(out, 0, m, axis=seq_axis)
    return out
