"""Interleaved decode-variant comparison: bf16 vs int8 vs paged.

Round-1's RESULTS quoted separate-run bests for these rows (e.g. "0.55
ms best"), which the contention-honesty rule forbids; this measures all
three variants round-robin in ONE process (scan-slope clock, medians).

Run: python scripts/decode_variants.py [--rounds 7]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=7)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--kv-heads", type=int, default=4)
    p.add_argument("--len", type=int, default=32768, dest="length")
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--n-short", type=int, default=8)
    p.add_argument("--n-long", type=int, default=64)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from attention_tpu.ops.decode import flash_decode
    from attention_tpu.ops.paged import PagePool, paged_from_dense, paged_flash_decode
    from attention_tpu.ops.quant import flash_decode_quantized, quantize_kv

    b, h, hkv, n, d = (args.batch, args.heads, args.kv_heads, args.length,
                       args.dim)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, d), jnp.bfloat16)
    kc = jax.random.normal(kk, (b, hkv, n, d), jnp.bfloat16)
    vc = jax.random.normal(kv, (b, hkv, n, d), jnp.bfloat16)
    lens = jnp.full((b,), n, jnp.int32)
    qkv = quantize_kv(kc, vc)
    # 2048-row pages, scrambled physical order (the ladder-row config;
    # 128-row vLLM-style pages measured 5x slower — grid-step overhead
    # scales with pages per sequence)
    import random

    page = 2048
    pages = n // page * b
    pool = PagePool(pages)
    ids = pool.alloc(pages)
    random.Random(0).shuffle(ids)
    pool.free(ids)
    cache = paged_from_dense(kc, vc, lens, pool, num_pages=pages,
                             page_size=page)

    def chain(step):
        @functools.partial(jax.jit, static_argnums=(1,))
        def chained(x0, nlen, *ops):
            def body(carry, _):
                return step(carry, *ops).astype(x0.dtype), None

            out, _ = lax.scan(body, x0, None, length=nlen)
            return jnp.sum(out.astype(jnp.float32))

        return chained

    cases = {
        "bf16": (chain(lambda qq, kk_, vv_: flash_decode(qq, kk_, vv_, lens)),
                 (kc, vc)),
        "int8": (chain(lambda qq, ck: flash_decode_quantized(qq, ck, lens)),
                 (qkv,)),
        "paged": (chain(lambda qq, ch: paged_flash_decode(qq, ch)), (cache,)),
    }
    for name, (fn, ops) in cases.items():
        jax.device_get(fn(q, args.n_short, *ops))
        jax.device_get(fn(q, args.n_long, *ops))

    slopes = {c: [] for c in cases}
    for _ in range(args.rounds):
        for cname, (fn, ops) in cases.items():
            t0 = time.perf_counter()
            jax.device_get(fn(q, args.n_short, *ops))
            t_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.device_get(fn(q, args.n_long, *ops))
            t_l = time.perf_counter() - t0
            slopes[cname].append((t_l - t_s) / (args.n_long - args.n_short))

    for cname, ss in slopes.items():
        per = statistics.median(ss)
        bpt = {"bf16": 2 * d * 2, "int8": 2 * (d + 32), "paged": 2 * d * 2}
        gb = b * hkv * n * bpt[cname] / per / 1e9
        print(json.dumps({cname: {
            "us": round(per * 1e6, 1),
            "cache_read_gb_s": round(gb, 0),
            "spread_us": f"{min(ss)*1e6:.0f}-{max(ss)*1e6:.0f}",
        }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
