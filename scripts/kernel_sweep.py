"""Block-size sweep for the flash kernel on real TPU.

Reuses bench.py's ``_bench_flash_s`` (same input recipe, same
chained-scan clock, see utils/timing.py) and sweeps BlockSizes configs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=32768)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--configs", type=str,
                   default="1024x1024,512x512,2048x1024,512x1024")
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--n-short", type=int, default=4)
    p.add_argument("--n-long", type=int, default=20)
    args = p.parse_args()

    from bench import _bench_flash_s
    import attention_tpu.ops.flash as _F

    # tile sweeps label results with the mode they name; pin off the
    # production small-shape bound->online dispatch so --seq <= 4096
    # sweeps the BOUND kernel, not the online one under its label
    _F._BOUND_MIN_SCORE_ELEMS = 0

    from attention_tpu.utils.flops import attention_flops, peak_flops

    flops = attention_flops(args.seq, args.seq, args.dim, args.dim)
    peak = peak_flops()

    results = {}
    for c in args.configs.split(","):
        bq, bk = (int(x) for x in c.split("x"))
        try:
            per = _bench_flash_s(args.seq, args.dim, args.repeats, bq, bk,
                                 n_short=args.n_short, n_long=args.n_long)
            results[c] = {
                "ms": round(per * 1e3, 3),
                "tflops": round(flops / per / 1e12, 1),
                "util": round(flops / per / peak, 4),
            }
            print(json.dumps({c: results[c]}), flush=True)
        except Exception as e:  # noqa: BLE001 - sweep must survive bad configs
            print(json.dumps({c: {"error": str(e)[:120]}}), flush=True)
    if not results:
        print(json.dumps({"error": "every config failed"}))
        return 1
    best = max(results, key=lambda c_: results[c_]["util"])
    print(json.dumps({"best": best, **results[best]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
