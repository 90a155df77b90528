"""Device mesh construction and data-placement policy.

Replaces the reference's L0/L3 runtime plumbing with JAX's declarative
sharding model:

  * the owner partitioner (`attention-mpi.c:19-27`) — block-partitioning n
    KV rows over ranks with ±1-row balance — becomes a
    ``PartitionSpec('kv')`` over a 1D mesh: XLA block-partitions the
    sharded axis the same way;
  * the adaptive Bcast-vs-Scatterv distribution (`attention-mpi.c:210-266`,
    64 MB threshold at `:213-215`) becomes the replicate-vs-shard placement
    choice below.  The reference's insight — small KV is cheaper to
    broadcast than to scatter — maps to: small KV should be *replicated*
    (each chip computes its own Q rows with zero per-batch collectives),
    large KV should be *sharded* (two-phase softmax collectives over ICI);
  * UCX/OMPI env bootstrap (`attention-mpi.c:10-17`) has no analog: ICI
    transport selection is XLA's job.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

# Fallback threshold for callers that cannot supply the query-side shape
# (legacy signature).  The reference flipped Bcast->Scatterv at a
# measured 64 MB (`attention-mpi.c:213-215`, report Q8) — an
# MPI-tree-topology fact, not a TPU one.  When `m` is known the decision
# below uses the fabric-independent byte model instead (see
# `choose_kv_placement`); this constant only gates the m-less path and
# is set where the byte model lands for the repo's square headline
# shapes (m == n, d = 128: crossover at n ~ 2.6k -> ~2.7 MB of fp32 KV;
# kept at the reference's 64 MB would mis-place every square shape from
# 2.6k to 32k — artifacts/placement_sweep.json).
KV_REPLICATE_THRESHOLD_BYTES = 4 * 2**20

# Allreduce-vs-broadcast byte ratio: sharding pays a two-phase merge
# (reduce-scatter + all-gather ~ 2x bytes on the wire) every call where
# replication pays a one-time (1 - 1/R) broadcast — fabric-independent
# factors (the same 2x the reference's Iallreduce pair pays over its
# Ibcast, `attention-mpi.c:342,354` vs `:305`).  Validated directionally
# on the 8-CPU mesh (scripts/placement_sweep.py).
MERGE_ALPHA = 2.0

# Replicating KV on every chip is capacity-bounded long before 16 GB
# HBM fills: leave room for Q, outputs, double buffers.
KV_REPLICATE_HBM_CAP_BYTES = 4 * 2**30


def default_mesh(axis_name: str = "kv", devices=None) -> Mesh:
    """A 1D mesh over all local devices — the `MPI_COMM_WORLD` analog."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


def hybrid_mesh(inner_axis: str = "kv", outer_axis: str = "dp") -> Mesh:
    """A 2D (outer, inner) mesh laid out so the inner axis rides ICI and
    the outer axis rides DCN — the multi-host analog of the reference's
    multi-node MPI world (nodes over ConnectX-5 fabric, ranks within a
    node over shared memory; `README.md:85-89`, process-placement study
    Q5).

    On a multi-host (multi-process) runtime this uses
    `mesh_utils.create_hybrid_device_mesh` so every inner-axis
    collective (the two-phase pmax/psum softmax, ring ppermute) stays
    on-slice; keep only low-frequency traffic (data-parallel gradient
    psum) on the outer axis.  On a single host it degrades to
    (1, n_devices) — same program, no DCN hops.
    """
    devices = jax.devices()
    n_proc = getattr(jax, "process_count", lambda: 1)()
    if n_proc > 1:
        from jax.experimental import mesh_utils

        per_proc = len(devices) // n_proc
        # result shape = mesh_shape * dcn_mesh_shape elementwise:
        # (1, per_proc) x (n_proc, 1) -> (n_proc, per_proc) matching
        # (outer_axis, inner_axis)
        # process_is_granule: DCN granules are hosts (matching n_proc),
        # not ICI slices — a multi-host single-slice pod has 1 slice but
        # n_proc hosts, and the default slice grouping would raise.
        dev_mesh = mesh_utils.create_hybrid_device_mesh(
            (1, per_proc), (n_proc, 1), devices=devices,
            process_is_granule=True,
        )
        return Mesh(dev_mesh, (outer_axis, inner_axis))
    return Mesh(np.asarray(devices).reshape(1, -1), (outer_axis, inner_axis))


def choose_kv_placement(
    n: int,
    dk: int,
    dv: int,
    *,
    itemsize: int = 4,
    threshold_bytes: int = KV_REPLICATE_THRESHOLD_BYTES,
    kv_heads: int = 1,
    m: int | None = None,
    q_heads: int | None = None,
    n_devices: int | None = None,
) -> str:
    """'replicate' or 'shard' — the adaptive distribution policy (C11),
    re-derived for TPU (round 5).

    The reference compared KV size against a measured 64 MB Bcast/
    Scatterv flip (`attention-mpi.c:213-215`) — a property of MPI's
    pre-built broadcast tree.  On a TPU mesh both placements execute
    identical FLOPs; what differs is bytes moved:

      * replicate KV / shard Q: a one-time (1 - 1/R) broadcast of the
        full KV, then ZERO per-call collectives (outputs are already
        Q-sharded);
      * shard KV rows: 1/R of the KV moves, but every call pays the
        two-phase merge — pmax/psum of the (h, m) stats and a psum of
        the (h, m, dv) fp32 contribs, ~2x those bytes on the wire
        (reduce-scatter + all-gather).

    So with the query side known the decision is a byte RATIO (m
    against n), not an absolute KV size: replicate iff
    ``(1 - 1/R) * kv_bytes < MERGE_ALPHA * merge_bytes``, capacity-
    capped by per-chip HBM headroom.  Validated on the 8-CPU mesh
    (scripts/placement_sweep.py -> artifacts/placement_sweep.json).
    Callers that cannot supply ``m`` fall back to the bytes threshold
    (now set where the model lands for square shapes, not at MPI's
    64 MB).
    """
    total_kv = kv_heads * n * (dk + dv) * itemsize
    if total_kv > KV_REPLICATE_HBM_CAP_BYTES:
        return "shard"  # capacity-forced regardless of comm optimum
    if m is None:
        return "replicate" if total_kv < threshold_bytes else "shard"
    if n_devices is None:
        n_devices = max(len(jax.devices()), 1)
    bcast_bytes = (1.0 - 1.0 / n_devices) * total_kv
    # stats ride lane-replicated fp32 (2 vectors) + fp32 contribs
    merge_bytes = (q_heads or kv_heads) * m * (dv + 2) * 4
    return ("replicate"
            if bcast_bytes < MERGE_ALPHA * merge_bytes else "shard")
