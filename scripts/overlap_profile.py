"""Profile-verify overlap claims (round-1 verdict #6 / SURVEY §7 hard-part 3).

Two captures, two analyses, artifacts under artifacts/:

  (a) real-chip 32k forward: jax.profiler trace of 3 back-to-back fused
      kernel calls.  Reports device-side kernel time vs module time
      (op-level occupancy) — and cross-checks the scan-slope clock.
  (b) ring attention on the 8-CPU mesh (run with JAX_PLATFORMS=cpu and
      xla_force_host_platform_device_count=8): measures, from the
      trace, the wall-time overlap between ppermute events and
      compute events (flash while-loops, fusions) across device
      threads.

Run: python scripts/overlap_profile.py fwd    (on the TPU env)
     JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python scripts/overlap_profile.py ring
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ART = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "artifacts")


def _latest_trace(log_dir):
    return sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.trace.json.gz"))[-1]


def _events(path, min_us=0):
    d = json.load(gzip.open(path))
    return [e for e in d["traceEvents"]
            if e.get("ph") == "X" and e.get("dur", 0) >= min_us]


def fwd() -> None:
    import jax
    import jax.numpy as jnp

    from attention_tpu.ops.flash import flash_attention
    from attention_tpu.utils.profiling import trace

    q = jax.random.normal(jax.random.PRNGKey(0), (32768, 128), jnp.bfloat16)
    f = jax.jit(lambda q: flash_attention(q, q, q))
    jax.block_until_ready(f(q))
    log = "/tmp/overlap_fwd"
    shutil.rmtree(log, ignore_errors=True)
    with trace(log):
        out = None
        for _ in range(3):
            out = f(q)
        jax.block_until_ready(out)
    path = _latest_trace(log)
    ev = _events(path)
    mods = [e for e in ev if e["name"].startswith("jit__lambda")]
    kerns = [e for e in ev if "flash_attention" in e["name"]]
    mod_ms = sorted(e["dur"] for e in mods)[len(mods) // 2] / 1e3
    kern_ms = sorted(e["dur"] for e in kerns)[len(kerns) // 2] / 1e3
    print(json.dumps({
        "device_module_ms": round(mod_ms, 3),
        "device_kernel_ms": round(kern_ms, 3),
        "kernel_occupancy_of_module": round(kern_ms / mod_ms, 4),
        "calls": len(kerns),
    }))
    os.makedirs(ART, exist_ok=True)
    shutil.copy(path, os.path.join(ART, "trace_fwd32k.trace.json.gz"))


def ring() -> None:
    # the ring arm runs on the virtual CPU mesh: reuse the driver
    # entry's platform forcing (env vars alone are not enough once jax
    # is imported)
    from __graft_entry__ import _force_cpu_mesh

    jax = _force_cpu_mesh(8)
    import jax.numpy as jnp

    from attention_tpu.parallel import ring_attention
    from attention_tpu.parallel.mesh import default_mesh
    from attention_tpu.utils.profiling import trace

    mesh = default_mesh("sp")
    q = jax.random.normal(jax.random.PRNGKey(0), (8192, 128), jnp.float32)
    f = jax.jit(lambda q: ring_attention(q, q, q, mesh=mesh, axis_name="sp"))
    jax.block_until_ready(f(q))
    log = "/tmp/overlap_ring"
    shutil.rmtree(log, ignore_errors=True)
    with trace(log):
        jax.block_until_ready(f(q))
    path = _latest_trace(log)
    ev = _events(path, min_us=500)
    perms = [e for e in ev if e["name"].startswith("ppermute")]
    # compute only — `copy` is the rotation's own data movement, and
    # counting it would credit rotation-overlapping-rotation
    comp = [e for e in ev
            if e["name"].startswith(("while", "wrapped_", "fusion"))]

    def overlap_ms(a, others):
        """Per other-tid, merge intervals then intersect with `a` — a
        while region and the fusions nested inside it must not be
        double-counted."""
        s, t = a["ts"], a["ts"] + a["dur"]
        by_tid = {}
        for b in others:
            if b["tid"] == a["tid"]:
                continue
            lo = max(s, b["ts"])
            hi = min(t, b["ts"] + b["dur"])
            if hi > lo:
                by_tid.setdefault(b["tid"], []).append((lo, hi))
        tot = 0.0
        for spans in by_tid.values():
            spans.sort()
            cur_lo, cur_hi = spans[0]
            for lo, hi in spans[1:]:
                if lo > cur_hi:
                    tot += cur_hi - cur_lo
                    cur_lo, cur_hi = lo, hi
                else:
                    cur_hi = max(cur_hi, hi)
            tot += cur_hi - cur_lo
        return tot / 1e3

    perm_ms = sum(e["dur"] for e in perms) / 1e3
    over_ms = sum(overlap_ms(e, comp) for e in perms)
    print(json.dumps({
        "ppermute_events": len(perms),
        "ppermute_total_ms": round(perm_ms, 1),
        "compute_overlapped_ms_on_other_threads": round(over_ms, 1),
        "overlap_ratio": round(over_ms / perm_ms, 2) if perm_ms else None,
    }))
    os.makedirs(ART, exist_ok=True)
    shutil.copy(path, os.path.join(ART, "trace_ring_cpu8.trace.json.gz"))


if __name__ == "__main__":
    {"fwd": fwd, "ring": ring}[sys.argv[1]]()
