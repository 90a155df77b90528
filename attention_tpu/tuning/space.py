"""Tunable-parameter spaces per kernel family.

Candidate lists cover every tile regime the measured history has ever
picked (rounds 1-5: 256x1024 seed default, 512x512 windowed,
1024x1024 stats-capped, 2048x1024/2048 causal, 4096x2048 VMEM-unlocked)
plus one step past each boundary so a new device generation can move
the optimum without a code change.  Candidates that cannot compile on a
given chip (VMEM overflow) are skipped by the search's failure
tolerance, so the lists may safely overshoot.
"""

from __future__ import annotations

# (block_q, block_k) for the flash forward kernel.
FLASH_FWD_TILES = (
    (256, 512), (256, 1024),
    (512, 512), (512, 1024), (512, 2048),
    (1024, 1024), (1024, 2048),
    (2048, 1024), (2048, 2048),
    (4096, 1024), (4096, 2048), (4096, 4096),
)

# (block_q, block_k) for the two-kernel backward (dQ + dK/dV).
FLASH_BWD_TILES = (
    (256, 256),
    (512, 512), (512, 1024),
    (1024, 512), (1024, 1024), (1024, 2048),
    (2048, 1024),
)

# (block_q, block_k) for the fused single-pass backward (resident dQ
# makes its VMEM budget tighter -> wide-k candidates).
FLASH_BWD_FUSED_TILES = (
    (256, 256),
    (512, 512), (512, 1024), (512, 2048), (512, 4096),
    (1024, 1024), (1024, 2048), (1024, 4096),
)

# KV block row counts for the dense decode kernel.
DECODE_BLOCK_K = (256, 512, 1024, 2048, 4096, 8192)

# Physical page sizes for the paged decode kernel.
PAGED_PAGE_SIZES = (128, 256, 512, 1024, 2048, 4096)

# Query-tile ROW counts (q_tile tokens x GQA group) for the ragged
# packed-step kernel; the engine divides by the group to get tokens.
RAGGED_BLOCK_Q = (128, 256, 512)

# Rescaling-math variants per family (the max_mode dispatch dimension).
# "bound" leads for the forward because the r05 key-norm-bound skip won
# the device clock there; decode/ragged cannot lower it (no key-norm
# prefetch on the cache read path), so their lists start at online.
FLASH_FWD_MAX_MODES = ("bound", "online", "flashd", "amla")
DECODE_MAX_MODES = ("online", "flashd", "amla")
RAGGED_MAX_MODES = ("online", "flashd", "amla")


def max_mode_candidates(kernel: str) -> tuple:
    """Rescaling-math variants ``tune(max_mode="auto")`` races for one
    family; empty for families whose entries carry no max_mode (the
    backward kernels recompute through the forward's own dispatch, and
    paged/quantized decode take no max_mode at all)."""
    if kernel == "flash_fwd":
        return FLASH_FWD_MAX_MODES
    if kernel == "decode":
        return DECODE_MAX_MODES
    if kernel == "ragged":
        return RAGGED_MAX_MODES
    return ()


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def candidates(kernel: str, *, m: int, n: int, d: int,
               window: int | None = None) -> list:
    """Shape-legal candidates for one kernel family.

    Tiles are clipped to the padded problem (a 4096-row block on a 2k
    sequence is the 2k block in disguise) and de-duplicated; decode and
    paged blocks must divide the cache capacity (the kernels' own
    `_pick_block_k`-style constraint).
    """
    if kernel == "flash_fwd":
        tiles = FLASH_FWD_TILES
    elif kernel == "flash_bwd":
        tiles = FLASH_BWD_TILES
    elif kernel == "flash_bwd_fused":
        tiles = FLASH_BWD_FUSED_TILES
    elif kernel == "decode":
        return [bk for bk in dict.fromkeys(
            min(bk, _ceil_to(n, 128)) for bk in DECODE_BLOCK_K)
            if n % bk == 0]
    elif kernel == "paged":
        return [p for p in PAGED_PAGE_SIZES if n % p == 0]
    elif kernel == "ragged":
        return [bq for bq in dict.fromkeys(
            min(bq, _ceil_to(m, 128)) for bq in RAGGED_BLOCK_Q)]
    else:
        raise ValueError(f"unknown kernel family {kernel!r}")
    m_pad = _ceil_to(m, 128)
    n_pad = _ceil_to(n, 128)
    out = []
    for bq, bk in tiles:
        cand = (min(bq, m_pad), min(bk, n_pad))
        if window is not None and cand[1] > _ceil_to(window, 128) * 4:
            # a KV block much wider than the band is all masked columns
            continue
        if cand not in out:
            out.append(cand)
    return out
