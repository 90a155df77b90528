"""Benchmark suite: ablations and scaling sweeps (reference methodology).

Reproduces the reference's performance-analysis methodology (report
Q2-Q7, README.md:95-121) with TPU-native treatments:

  * **Ablation table** (Q2): the reference isolates AVX-512, mixed
    precision, and pipeline overlap against an unoptimized MPI baseline.
    The TPU analogs, each against the un-fused fp32 XLA baseline:
      - ``fused``      — Pallas flash kernel, fp32 (the SIMD/fusion axis)
      - ``mixed``      — un-fused XLA, bf16 in / fp32 accum (the
                         d2f/f2d mixed-precision axis)
      - ``overlap``    — distributed kv-sharded path (the comm/compute
                         overlap axis; meaningful on a multi-device mesh)
      - ``full``       — fused + bf16 (+ sharding when a mesh is given)
  * **Strong scaling** (Q4/Q7): fixed problem, growing mesh.
  * **Weak scaling** (Q7): problem grows with the mesh (n per device
    fixed), the reference's M/P families.

All sweeps emit structured :class:`RunRecord` rows (SURVEY §5) rather
than printf lines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from attention_tpu.ops.flash import BlockSizes, flash_attention
from attention_tpu.ops.reference import attention_xla
from attention_tpu.parallel.kv_sharded import kv_sharded_attention
from attention_tpu.parallel.mesh import default_mesh
from attention_tpu.parallel.ring import ring_attention
from attention_tpu.utils.flops import (
    UnknownDeviceError,
    attention_flops,
    utilization,
)
from attention_tpu.utils.profiling import RunRecord
from attention_tpu.utils.timing import benchmark


def _record(config, backend, m, n, dk, dv, dtype, timing, *, n_devices=1,
            mesh_axes=None, extra=None) -> RunRecord:
    flops = attention_flops(m, n, dk, dv)
    dev = jax.devices()[0]
    try:
        util = utilization(flops, timing.best_s, dev) / n_devices
    except UnknownDeviceError:
        util = None  # off-chip (e.g. the CPU test mesh): no peak to share
    return RunRecord(
        config=config,
        backend=backend,
        m=m, n=n, dk=dk, dv=dv,
        dtype=jnp.dtype(dtype).name,
        best_us=timing.best_us,
        median_us=timing.median_s * 1e6,
        gflops_per_chip=flops / timing.best_s / 1e9 / n_devices,
        utilization=util,
        device_kind=getattr(dev, "device_kind", "unknown"),
        n_devices=n_devices,
        mesh_axes=dict(mesh_axes) if mesh_axes else None,
        extra=extra,
    )


def _qkv(m, n, dk, dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (
        jax.random.normal(ks[0], (m, dk), dtype),
        jax.random.normal(ks[1], (n, dk), dtype),
        jax.random.normal(ks[2], (n, dv), dtype),
    )


def ablation_table(
    m: int = 4096,
    n: int = 4096,
    dk: int = 128,
    dv: int = 128,
    *,
    repeats: int = 5,
    block_sizes: BlockSizes | None = None,
    mesh=None,
) -> dict[str, RunRecord]:
    """The Q2 ablation: each optimization axis alone, then combined.

    Returns records keyed by variant; ``speedup vs baseline`` =
    baseline.best_us / variant.best_us (the reference's relative-speedup
    definition, README.md:95-102).
    """
    bs = block_sizes or BlockSizes()
    variants: dict[str, RunRecord] = {}

    qf, kf, vf = _qkv(m, n, dk, dv, jnp.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))

    t = benchmark(attention_xla, qf, kf, vf, repeats=repeats)
    variants["baseline"] = _record("ablation", "xla-f32", m, n, dk, dv,
                                   "float32", t)
    t = benchmark(flash_attention, qf, kf, vf, block_sizes=bs, repeats=repeats)
    variants["fused"] = _record("ablation", "flash-f32", m, n, dk, dv,
                                "float32", t)
    t = benchmark(attention_xla, qb, kb, vb, repeats=repeats)
    variants["mixed"] = _record("ablation", "xla-bf16", m, n, dk, dv,
                                "bfloat16", t)
    if mesh is not None:
        t = benchmark(
            kv_sharded_attention, qf, kf, vf, mesh=mesh, block_sizes=bs,
            repeats=repeats,
        )
        variants["overlap"] = _record(
            "ablation", "kv-sharded-f32", m, n, dk, dv, "float32", t,
            n_devices=mesh.devices.size, mesh_axes=mesh.shape,
        )
        t = benchmark(
            kv_sharded_attention, qb, kb, vb, mesh=mesh, block_sizes=bs,
            repeats=repeats,
        )
        variants["full"] = _record(
            "ablation", "kv-sharded-bf16", m, n, dk, dv, "bfloat16", t,
            n_devices=mesh.devices.size, mesh_axes=mesh.shape,
        )
    else:
        t = benchmark(flash_attention, qb, kb, vb, block_sizes=bs,
                      repeats=repeats)
        variants["full"] = _record("ablation", "flash-bf16", m, n, dk, dv,
                                   "bfloat16", t)
    base = variants["baseline"].best_us
    for name, rec in variants.items():
        rec.extra = {**(rec.extra or {}), "speedup_vs_baseline": base / rec.best_us}
    return variants


def strong_scaling(
    m: int = 4096,
    n: int = 8192,
    dk: int = 128,
    dv: int = 128,
    *,
    device_counts=(1, 2, 4, 8),
    backend: str = "kv-sharded",
    repeats: int = 3,
    block_sizes: BlockSizes | None = None,
    dtype=jnp.bfloat16,
) -> list[RunRecord]:
    """Fixed problem, growing mesh (report Q4/Q7)."""
    bs = block_sizes or BlockSizes()
    fn = {"kv-sharded": kv_sharded_attention, "ring": ring_attention}[backend]
    q, k, v = _qkv(m, n, dk, dv, dtype)
    out = []
    for r in sorted(device_counts):
        if r > len(jax.devices()):
            continue
        mesh = default_mesh("kv" if backend == "kv-sharded" else "sp",
                            devices=jax.devices()[:r])
        t = benchmark(fn, q, k, v, mesh=mesh, block_sizes=bs, repeats=repeats)
        out.append(
            _record("strong_scaling", backend, m, n, dk, dv, dtype, t,
                    n_devices=r, mesh_axes=mesh.shape)
        )
    if not out:
        raise ValueError(
            f"no device_counts {device_counts} fit the "
            f"{len(jax.devices())} available devices"
        )
    base = out[0].best_us
    for rec in out:
        rec.extra = {"speedup_vs_smallest": base / rec.best_us}
    return out


def placement_table(
    m: int = 2048,
    n: int = 8192,
    dk: int = 128,
    dv: int = 128,
    *,
    n_devices: int | None = None,
    repeats: int = 3,
    block_sizes: BlockSizes | None = None,
    dtype=jnp.bfloat16,
) -> dict[str, RunRecord]:
    """Device-order study — the reference's process-placement experiment
    (report Q5: 16 procs on 1/2/4 nodes, `images/process_placement.png`)
    rebuilt for a TPU mesh: the same 1D kv mesh laid over the devices in
    identity / reversed / strided order.  Device order decides which
    pmax/psum hops ride adjacent ICI links, the analog of ranks sharing
    a node vs crossing the fabric.  (On the virtual CPU mesh all orders
    cost the same — the point there is methodology, not numbers.)
    """
    bs = block_sizes or BlockSizes()
    devs = jax.devices()[: n_devices or len(jax.devices())]
    r = len(devs)
    orders = {"identity": devs, "reversed": devs[::-1]}
    if r >= 4 and r % 2 == 0:
        orders["strided"] = devs[0::2] + devs[1::2]
    q, k, v = _qkv(m, n, dk, dv, dtype)
    out: dict[str, RunRecord] = {}
    for name, order in orders.items():
        mesh = jax.sharding.Mesh(list(order), ("kv",))
        t = benchmark(kv_sharded_attention, q, k, v, mesh=mesh,
                                block_sizes=bs, repeats=repeats)
        out[name] = _record("placement", "kv-sharded", m, n, dk, dv, dtype,
                            t, n_devices=r, mesh_axes=mesh.shape)
    base = out["identity"].best_us
    for rec in out.values():
        rec.extra = {"relative_time_vs_identity": rec.best_us / base}
    return out


def weak_scaling(
    n_per_device: int = 2048,
    m: int = 2048,
    dk: int = 128,
    dv: int = 128,
    *,
    device_counts=(1, 2, 4, 8),
    backend: str = "kv-sharded",
    repeats: int = 3,
    block_sizes: BlockSizes | None = None,
    dtype=jnp.bfloat16,
) -> list[RunRecord]:
    """KV length grows with the mesh: n = n_per_device * R (report Q7's
    M/P families).  Flat time over R = perfect weak scaling."""
    bs = block_sizes or BlockSizes()
    fn = {"kv-sharded": kv_sharded_attention, "ring": ring_attention}[backend]
    out = []
    for r in sorted(device_counts):
        if r > len(jax.devices()):
            continue
        n = n_per_device * r
        q, k, v = _qkv(m, n, dk, dv, dtype)
        mesh = default_mesh("kv" if backend == "kv-sharded" else "sp",
                            devices=jax.devices()[:r])
        t = benchmark(fn, q, k, v, mesh=mesh, block_sizes=bs, repeats=repeats)
        out.append(
            _record("weak_scaling", backend, m, n, dk, dv, dtype, t,
                    n_devices=r, mesh_axes=mesh.shape,
                    extra={"n_per_device": n_per_device})
        )
    return out
