"""Regenerate attention_tpu/tuning/shipped_table.json.

The shipped table is the middle layer of the tile-resolution order
(user cache -> shipped table -> heuristic).  It is seeded FROM the
measured heuristics — the winners of the rounds 1-5 device-clock sweeps
on the v5e chip (scripts/kernel_sweep.py, bwd_sweep.py) —
by calling the heuristic functions themselves, so the committed table
can never drift from the code it mirrors.  Entries are keyed
``tpu-v5e`` (the measured generation); other devices miss and fall to
the same heuristics, so shipping the table changes no dispatch — it
exists so ``cli tune`` runs have a schema-validated base to extend and
so future generations' measured winners have a committed home.

Run: python scripts/make_shipped_table.py          (rewrites in place)
Lint: python scripts/check_shipped_table.py        (CI-run validation)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the heuristics below must answer for the MEASURED generation, not for
# whatever host regenerates the table
os.environ["ATTN_TPU_NO_TUNING"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DEVICE = "tpu-v5e"


def main() -> int:
    from attention_tpu.ops.decode import _DEFAULT_BLOCK_K
    from attention_tpu.ops.flash import BlockSizes
    from attention_tpu.ops.flash_bwd import (
        default_bwd_block_sizes,
        default_fused_bwd_block_sizes,
    )
    from attention_tpu.tuning.cache import (
        TuningTable,
        make_key,
        shipped_table_path,
    )
    from attention_tpu.tuning.lookup import key_fields

    table = TuningTable()

    def put(kernel, tiles_or_entry, dtype, **kf_kwargs):
        entry = (dict(tiles_or_entry) if isinstance(tiles_or_entry, dict)
                 else {"block_q": int(tiles_or_entry[0]),
                       "block_k": int(tiles_or_entry[1])})
        entry["source"] = "heuristic-seed"
        key = make_key(DEVICE, kernel, dtype=dtype,
                       **key_fields(kernel, **kf_kwargs))
        table.put(key, entry)

    d = 128
    # flash forward: the BENCH/BASELINE ladder shapes (single-head 8k..
    # 131k, the GQA 32q/4kv config, the windowed 32k configs), with the
    # big-tile regime pinned on (the v5e measurement the heuristic
    # encodes — big_tiles=True regardless of the regenerating host).
    # max_mode="bound" is the r05 measured rescaling-math winner for
    # the forward (the key-norm bound skip); decode/ragged below ship
    # "online" (they cannot lower bound, and no variant has beaten it
    # on the v5e clock).

    def fwd_tiles(bs):
        return {"block_q": int(bs[0]), "block_k": int(bs[1]),
                "max_mode": "bound"}

    for m in (8192, 16384, 32768, 65536, 131072):
        for causal in (False, True):
            for stats in (False, True):
                put("flash_fwd",
                    fwd_tiles(BlockSizes.heuristic_for_shape(
                        m, d, returns_stats=stats, causal=causal,
                        big_tiles=True)),
                    "bfloat16", heads=1, seq=m, dim=d, causal=causal,
                    stats=stats)
    for causal in (False, True):
        put("flash_fwd",
            fwd_tiles(BlockSizes.heuristic_for_shape(
                16384, d, causal=causal, big_tiles=True)),
            "bfloat16", heads=32, seq=16384, dim=d, causal=causal)
    for window in (256, 1024, 4096):
        for stats in (False, True):
            put("flash_fwd",
                fwd_tiles(BlockSizes.heuristic_for_shape(
                    32768, d, window=window, returns_stats=stats,
                    causal=True, big_tiles=True)),
                "bfloat16", heads=1, seq=32768, dim=d, causal=True,
                stats=stats, window=window)

    # backward families: dtype- and window-split like their heuristics
    for dtype in ("bfloat16", "float32"):
        for m in (8192, 32768):
            for window in (None, 1024):
                put("flash_bwd",
                    default_bwd_block_sizes(d, dtype, window),
                    dtype, seq=m, dim=d, window=window)
                put("flash_bwd_fused",
                    default_fused_bwd_block_sizes(d, dtype, window),
                    dtype, seq=m, dim=d, window=window)

    # decode: the bench serving config (b=8, 32q/4kv) across capacities
    for n in (8192, 32768, 131072):
        for window in (None, 1024):
            put("decode",
                {"block_k": _DEFAULT_BLOCK_K, "max_mode": "online"},
                "bfloat16", heads=32, kv_heads=4, batch=8, seq=n,
                dim=d, window=window)

    # paged: page size == the dense streaming block at the bench shape
    put("paged", {"page_size": 2048}, "bfloat16",
        heads=32, kv_heads=4, batch=8, seq=32768, dim=d)

    # ragged packed step: the serving bench's slot/capacity configs
    for n in (32768, 131072):
        put("ragged", {"block_q": 256, "max_mode": "online"},
            "bfloat16", heads=32, kv_heads=4, batch=8, seq=n, dim=d)

    path = shipped_table_path()
    table.save(path)
    print(f"wrote {path}: {len(table.entries)} entries")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
