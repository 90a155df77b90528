"""int4 decode: feature-dim vs token-paired nibble packing, on the chip.

Round 5 measured the feature-dim int4 packing at 0.748 ms vs int8's
0.445 at the bench decode shape — the (block_k, d/2=64) value tiles are
half the native lane width, so the stream loses full-width DMA
efficiency and the kernel leaves the DMA-bound regime.
The token-paired layout (`quantize_kv_int4_tok`) keeps d=128-lane value
tiles by pairing two ADJACENT TOKENS per byte; the unpack splits along
sublanes instead of lanes.  This measures whether that recovers the
latency side of int4 (bytes say ~0.6x int8 -> ~0.27 ms at the read
roofline) or documents a second negative.

Interleaved trials, deterministic device clock, medians.  The two
layouts share quantization math exactly; their bitwise equality is
pinned by tests/test_quant.py::test_int4_tok_matches_feature_layout
(CPU interpret mode) and tpu_smoke's token-paired case (on-chip).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _operands(batch, heads, kv_heads, cache_len, dim):
    import jax
    import jax.numpy as jnp

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch, heads, dim), jnp.bfloat16)
    kc = jax.random.normal(kk, (batch, kv_heads, cache_len, dim),
                           jnp.bfloat16)
    vc = jax.random.normal(kv, (batch, kv_heads, cache_len, dim),
                           jnp.bfloat16)
    lens = jnp.full((batch,), cache_len, jnp.int32)
    return q, kc, vc, lens


def bench_variant(variant, batch, heads, kv_heads, cache_len, dim,
                  repeats):
    from attention_tpu.ops.quant import (
        flash_decode_int4,
        flash_decode_int4_tok,
        flash_decode_quantized,
        quantize_kv,
        quantize_kv_int4,
        quantize_kv_int4_tok,
    )
    from attention_tpu.utils.timing import benchmark_auto

    q, kc, vc, lens = _operands(batch, heads, kv_heads, cache_len, dim)
    if variant == "int8":
        cache, fn = quantize_kv(kc, vc), flash_decode_quantized
    elif variant == "int4_feature":
        cache, fn = quantize_kv_int4(kc, vc), flash_decode_int4
    elif variant == "int4_tok":
        cache, fn = quantize_kv_int4_tok(kc, vc), flash_decode_int4_tok
    else:
        raise ValueError(variant)
    step = lambda x, c, ll: fn(x, c, ll).astype(x.dtype)  # noqa: E731
    return benchmark_auto(step, q, repeats=repeats, operands=(cache, lens))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=32768)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--variants", nargs="+",
                    default=["int8", "int4_feature", "int4_tok"])
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()

    row = {"batch": args.batch, "heads": args.heads,
           "kv_heads": args.kv_heads, "cache_len": args.cache_len,
           "dim": args.dim}
    for variant in args.variants:
        ts = [bench_variant(variant, args.batch, args.heads, args.kv_heads,
                            args.cache_len, args.dim, args.repeats)
              for _ in range(args.trials)]
        row[variant + "_ms"] = statistics.median(ts) * 1e3
        print(json.dumps({variant: row[variant + "_ms"]}))
    print(json.dumps(row))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(row, f, indent=1)


if __name__ == "__main__":
    main()
