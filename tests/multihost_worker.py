"""Worker for the multi-process (multi-host analog) smoke test.

Launched by tests/test_multihost.py as N separate processes, each with
its own 4-device virtual CPU "host", joined through the JAX distributed
runtime — the closest single-machine analog of the reference's
multi-node `mpirun` validation (README.md:136-142).  Not collected by
pytest (no test_ prefix).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main() -> int:
    coord = sys.argv[1]
    num_procs = int(sys.argv[2])
    pid = int(sys.argv[3])
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=num_procs, process_id=pid
    )
    assert jax.process_count() == num_procs
    assert len(jax.devices()) == 4 * num_procs, len(jax.devices())

    import jax.numpy as jnp
    import numpy as np

    from attention_tpu.parallel.kv_sharded import merge_partials
    from attention_tpu.parallel.mesh import hybrid_mesh
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = hybrid_mesh(inner_axis="kv", outer_axis="dp")
    assert mesh.shape["dp"] == num_procs
    assert mesh.shape["kv"] == 4

    # Two-phase softmax merge over the inner (ICI-analog) axis with the
    # outer (DCN-analog) axis as pure data parallelism: the reference's
    # placement study Q5, one process per "node".
    import functools

    m, n_local, dv = 16, 32, 8
    rng = np.random.default_rng(0)
    # every process must build the SAME global arrays (single-controller
    # semantics): seed identically, then shard
    contrib = jnp.asarray(
        rng.standard_normal((num_procs, 4, m, dv)), jnp.float32
    )
    lmax = jnp.asarray(rng.standard_normal((num_procs, 4, m)), jnp.float32)
    lsum = jnp.asarray(
        rng.uniform(0.5, 2.0, (num_procs, 4, m)), jnp.float32
    )

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(P("dp", "kv"), P("dp", "kv"), P("dp", "kv")),
        out_specs=P("dp", "kv"),
    )
    def run(c, mx, sm):
        return merge_partials(c[0, 0], mx[0, 0], sm[0, 0], "kv")[None, None]

    out = jax.jit(run)(contrib, lmax, lsum)

    # reference: per dp row, the exact two-phase merge in numpy
    def ref_row(c, mx, sm):
        g = mx.max(axis=0)
        corr = np.exp(mx - g)
        gs = (sm * corr).sum(axis=0)
        tot = (c * corr[..., None]).sum(axis=0)
        return tot / np.where(gs == 0.0, 1.0, gs)[..., None]

    # check THIS process's first shard (its own dp row) vs the oracle
    got = np.asarray(out.addressable_shards[0].data)  # (1, 1, m, dv)
    want = ref_row(np.asarray(contrib[pid]), np.asarray(lmax[pid]),
                   np.asarray(lsum[pid]))
    np.testing.assert_allclose(got[0, 0], want, atol=1e-5)

    # Phase 2: a FULL context-parallel train step across the processes —
    # the flash custom VJP under each host's local sp axis (ICI analog),
    # the data-parallel gradient psum crossing processes (DCN analog).
    # This is the reference's whole multi-node story (kernel + comm in
    # one orchestrated step over `mpirun` ranks, `attention-mpi.c`) run
    # as multi-controller training.  Every process builds identical
    # global values (single-controller semantics) and reports the loss;
    # the parent test matches it against a one-process 8-device run of
    # the same config.
    from attention_tpu.models.train import init_sharded, make_train_step
    from attention_tpu.models.transformer import TinyDecoder

    mesh2 = hybrid_mesh(inner_axis="sp", outer_axis="dp")
    model = TinyDecoder(vocab=32, dim=32, depth=1, num_q_heads=4,
                        num_kv_heads=2, impl="flash", cp_axis="sp",
                        mesh=mesh2, dtype=jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 32, (2, 33)), jnp.int32
    )
    params, opt, opt_state = init_sharded(model, mesh2, batch=2, seq=32)
    step = make_train_step(model, opt, mesh2)
    params, opt_state, loss = step(params, opt_state, tokens)
    params, opt_state, loss2 = step(params, opt_state, tokens)
    l1, l2 = float(loss), float(loss2)
    assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1, (l1, l2)
    print(f"proc {pid}: cp-loss {l1:.6f} {l2:.6f}", flush=True)

    print(f"proc {pid}: OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
