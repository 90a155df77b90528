"""GPipe-style pipeline parallelism over a 1D mesh axis.

Not in the reference (a single op has no layer axis; SURVEY §2 marks
pipeline parallelism N/A there) — this is the layer-level scaling leg a
complete framework needs alongside dp/sp/tp/ep.

Schedule, the TPU way: every device holds ONE stage's params (leading
pytree axis sharded over ``pp``); microbatches march through the ring
with ``lax.ppermute`` under a ``lax.scan`` of ticks.  At tick t device
p computes microbatch t-p (the classic GPipe diagonal); fill/drain
bubbles execute on zero inputs (static shapes, no data-dependent
control flow).  The activation hand-off is a data dependency, so XLA's
latency-hiding scheduler overlaps the ppermute with the next tick's
compute — the reference's ping-pong `MPI_Ibcast`/compute overlap
(`attention-mpi.c:268-330`), reborn one axis up.

Backward: plain ``jax.grad`` through the scan+ppermute gives the exact
transposed schedule (ppermute reverses direction under AD) — a correct
1F-then-1B pipeline without hand-written backward passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from attention_tpu.parallel.mesh import default_mesh


def pipeline_apply(
    stage_fn,
    stage_params,
    x: jax.Array,
    *,
    mesh: Mesh | None = None,
    axis_name: str = "pp",
    n_micro: int | None = None,
):
    """Run ``x`` through all pipeline stages; returns the final output.

    ``stage_fn(params_slice, x_mb) -> y_mb`` applies one stage to one
    microbatch (shape-preserving).  ``stage_params`` is a pytree whose
    leaves all have leading axis = number of stages (= mesh size on
    ``axis_name``); slice p lives on device p.  ``x`` (B, ...) is split
    into ``n_micro`` microbatches along axis 0 (default: one per
    stage).  Output is (B, ...), replicated across the axis.
    """
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_stages = mesh.shape[axis_name]
    if n_micro is None:
        n_micro = n_stages
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    leaves = jax.tree_util.tree_leaves(stage_params)
    for leaf in leaves:
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage_params leading axis {leaf.shape[0]} != "
                f"pipeline size {n_stages} on '{axis_name}'"
            )
    mb = b // n_micro
    rest = x.shape[1:]
    xm = x.reshape(n_micro, mb, *rest)
    # no wrap edge: stage 0 reads from the input queue, so the
    # (n_stages-1 -> 0) payload would be discarded — skipping the pair
    # saves one dead activation transfer per tick (devices with no
    # source receive zeros)
    perm = [(j, j + 1) for j in range(n_stages - 1)]

    params_spec = jax.tree_util.tree_map(
        lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))), stage_params
    )

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(params_spec, P()),
        out_specs=P(),
    )
    def run(params_local, xm_repl):
        p = lax.axis_index(axis_name)
        params_slice = jax.tree_util.tree_map(lambda a: a[0], params_local)
        recv0 = jnp.zeros((mb, *rest), x.dtype)
        out0 = jnp.zeros((n_micro, mb, *rest), x.dtype)

        def tick(carry, t):
            recv, outputs = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            first_in = lax.dynamic_index_in_dim(
                xm_repl, mb_idx, 0, keepdims=False
            )
            inp = jnp.where(p == 0, first_in, recv)
            out = stage_fn(params_slice, inp)
            # each device's carried value next tick = this tick's output
            # of its left neighbor
            send = lax.ppermute(out, axis_name, perm)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = jnp.logical_and(t >= n_stages - 1, p == n_stages - 1)
            cur = lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                           keepdims=False)
            upd = jnp.where(valid, out.astype(outputs.dtype), cur)
            outputs = lax.dynamic_update_index_in_dim(outputs, upd,
                                                      out_idx, 0)
            return (send, outputs), None

        (_, outputs), _ = lax.scan(
            tick, (recv0, out0), jnp.arange(n_micro + n_stages - 1)
        )
        # only the last stage's buffer is real; masked psum replicates it
        outputs = lax.psum(
            jnp.where(p == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis_name,
        )
        return outputs.reshape(b, *rest)

    return run(stage_params, xm)
