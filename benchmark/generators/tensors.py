"""Inputs of a kernel cell: Q, K and V drawn from the seed on the
device, in one jitted call, in the type they are served in, and laid
out over the cell's chips the way the call under test takes them."""

from __future__ import annotations


def generate(traffic: dict, config: dict, *, seed: int, devices, sizes=None):
    """``(cases, mesh)``: ``resident_cases`` triples of standard normal
    ``(m, dk)``, ``(n, dk)`` and ``(n, dv)`` arrays; with ``mesh_axis``
    in the traffic they are sharded by rows over a 1D mesh of
    ``devices`` (``mesh`` is then that mesh, else None)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    sizes = sizes or {}
    m = int(sizes.get("m", traffic["m"]))
    n = int(sizes.get("n", traffic["n"]))
    dk, dv = int(config["dk"]), int(config["dv"])
    dtype = jnp.dtype(config["dtype"])
    mesh = sharding = None
    if traffic.get("mesh_axis"):
        mesh = Mesh(np.asarray(devices), (traffic["mesh_axis"],))
        sharding = NamedSharding(
            mesh, PartitionSpec(traffic["mesh_axis"], None))
    else:
        sharding = jax.sharding.SingleDeviceSharding(devices[0])

    def make(key):
        kq, kk, kv = jax.random.split(key, 3)
        return (jax.random.normal(kq, (m, dk), dtype),
                jax.random.normal(kk, (n, dk), dtype),
                jax.random.normal(kv, (n, dv), dtype))

    # a seed may pass 2**31: fold it into the key in two 31-bit halves
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    make = jax.jit(make, out_shardings=(sharding,) * 3)
    count = int(sizes.get("resident_cases", traffic["resident_cases"]))
    cases = [make(jax.random.fold_in(key, i)) for i in range(count)]
    return cases, mesh
