"""Measure the replicate-vs-shard KV placement crossover (round 5).

The reference flipped Bcast->Scatterv at a MEASURED 64 MB (report.pdf
Q8); round 1-4 of this repo inherited that constant for a different
decision (replicate-vs-shard placement) on different hardware — MPI
folklore.  This sweep measures the decision's real shape on the 8-CPU
virtual mesh and fits the comm model `parallel/mesh.py` now uses.

Model (both placements execute identical FLOPs; only movement differs):
  * replicate KV / shard Q: distribute the FULL KV to every chip
    (bcast ~ (1-1/R) * kv_bytes per link) and merge nothing;
  * shard KV rows: distribute 1/R of KV, then pay the per-call
    two-phase merge (pmax/psum of (h, m) stats + psum of (h, m, dv)
    fp32 contribs ~ 2*(1-1/R) * merge_bytes, the allreduce factor).
So the crossover is the RATIO kv_bytes vs merge_bytes — m against n —
not an absolute KV size.  The sweep times `q_sharded_attention` vs
`kv_sharded_attention` end-to-end (distribution + compute + merge) on
shapes that hold FLOPs near-constant while sweeping m/n, locating the
empirical crossover ratio; `ALPHA` in `choose_kv_placement` is the
fitted coefficient.

HONESTY: the 8-CPU mesh's "links" are memcpys, not ICI — absolute
times are meaningless; what transfers is the SHAPE of the decision
(which the model predicts and the sweep confirms: crossover tracks
m·dv/n·(dk+dv), not bytes(KV) alone).  The allreduce-vs-gather byte
factors in the model are fabric-independent.

Run: python scripts/placement_sweep.py  (writes
artifacts/placement_sweep.json)
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, *args, reps=5):
    import jax

    fn(*args)  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> int:
    import jax

    # this study runs on the virtual CPU mesh whatever the environment's
    # default platform: force it the way tests/conftest.py does
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from attention_tpu.parallel.kv_sharded import (
        kv_sharded_attention,
        q_sharded_attention,
    )
    from attention_tpu.parallel.mesh import choose_kv_placement

    assert len(jax.devices()) == 8, "expects the 8-device CPU mesh"
    d = 64
    rows = []
    # sweep m/n over 3 decades at two problem scales; the model says
    # the crossover lives at m/n ~ (dk+dv)*itemsize / (2*(dv+2)*4)
    for total in (2**18, 2**20):
        for ratio_log2 in range(-6, 7, 2):
            m = max(64, int((total * 2.0**ratio_log2) ** 0.5))
            n = max(256, total // m)
            m = -(-m // 64) * 64
            n = -(-n // 256) * 256
            kq = jax.random.PRNGKey(0)
            q = jax.random.normal(kq, (m, d), jnp.float32)
            k = jax.random.normal(kq, (n, d), jnp.float32)
            v = jax.random.normal(kq, (n, d), jnp.float32)
            t_q = _time(lambda a, b, c: q_sharded_attention(a, b, c),
                        q, k, v)
            t_kv = _time(lambda a, b, c: kv_sharded_attention(a, b, c),
                         q, k, v)
            pred = choose_kv_placement(n, d, d, itemsize=4, m=m,
                                       q_heads=1, kv_heads=1,
                                       n_devices=8)
            rows.append({
                "m": m, "n": n,
                "kv_bytes": n * 2 * d * 4,
                "merge_bytes": m * (d + 2) * 4,
                "q_sharded_s": round(t_q, 5),
                "kv_sharded_s": round(t_kv, 5),
                "faster": "replicate" if t_q < t_kv else "shard",
                "model_says": pred,
            })
            print(json.dumps(rows[-1]))
    agree = sum(r["faster"] == r["model_says"] for r in rows)
    out = {
        "mesh": "8-device virtual CPU (shape evidence only — see module "
                "docstring; ICI byte factors are fabric-independent)",
        "model_agreement": f"{agree}/{len(rows)}",
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "artifacts", "placement_sweep.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}: agreement {agree}/{len(rows)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
