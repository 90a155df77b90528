"""The read path kernels consult for tuned parameters.

Resolution order is fixed: **user cache -> shipped table -> None**.
``None`` sends the caller to its own measured heuristic, which is what
keeps an empty-cache CPU run byte-for-byte identical to the
pre-autotuner library (the shipped table only carries ``tpu-*`` device
keys, and CPU lookups key as ``cpu``).

The user cache is read ONLY when ``ATTN_TPU_TUNING_CACHE`` names it: a
file outside the checkout must not decide which tiles a run compiles
unless the operator pointed at it.  Without the variable, tiles come
from the committed shipped table and the heuristics alone
(:func:`tables_in_use` says which; the entry points print it).

``ATTN_TPU_NO_TUNING=1`` disables both tables (heuristics only) — the
triage switch for suspect cache entries.

This module deliberately imports nothing from ``attention_tpu.ops`` so
the ops modules can import it without a cycle; it returns plain dict
entries and lets each kernel adapt them (clamping to the call's real
padding stays the kernel's business).
"""

from __future__ import annotations

import os

from attention_tpu.tuning.cache import (
    bucket_pow2,
    device_key,
    load_table_cached,
    make_key,
    shipped_table_path,
    validate_entry,
)


def window_bucket(window: int | None) -> int:
    """Windows bucket like sequence dims (pow2 floor), 0 = unwindowed."""
    return 0 if window is None else bucket_pow2(window)


def dtype_name(dtype) -> str:
    if dtype is None:
        return "any"
    import numpy as np

    return np.dtype(dtype).name


def key_fields(kernel: str, *, heads=1, kv_heads=None, seq=0, dim=0,
               batch=1, causal=False, window=None, sinks=None,
               stats=False) -> dict:
    """The (g, m, n, d, flags) key fields for one family — the SINGLE
    definition shared by the tuner's write side (`search.tune`) and the
    kernels' read side, so the two can never drift.

    Field mapping per family: flash forward keys on (heads bucket,
    m=n=seq, d, causal/stats/window-bucket); the backward families are
    head- and causal-generic (measured: the defaults hold across h and
    the causal band) and key on (m=n=seq, d,
    window-bucket); decode/paged/ragged key on (GQA group, m=batch
    (ragged: active slots), n=cache capacity, d, sinks/window-bucket).
    """
    wb = window_bucket(window)
    if kernel == "flash_fwd":
        return dict(g=heads, m=seq, n=seq, d=dim,
                    flags={"causal": int(bool(causal)),
                           "stats": int(bool(stats)), "window": wb})
    if kernel in ("flash_bwd", "flash_bwd_fused"):
        return dict(g=1, m=seq, n=seq, d=dim, flags={"window": wb})
    if kernel in ("decode", "paged", "ragged"):
        group = heads // (kv_heads or heads)
        return dict(g=group, m=batch, n=seq, d=dim,
                    flags={"sinks": int(bool(sinks)), "window": wb})
    raise ValueError(f"unknown kernel family {kernel!r}")


def tables_in_use(cache_path: str | None = None) -> list[str]:
    """The table files a lookup consults, in resolution order."""
    if os.environ.get("ATTN_TPU_NO_TUNING"):
        return []
    user = cache_path or os.environ.get("ATTN_TPU_TUNING_CACHE")
    return ([user] if user else []) + [shipped_table_path()]


def lookup(kernel: str, *, g: int, m: int, n: int, d: int,
           dtype=None, flags: dict | None = None,
           cache_path: str | None = None) -> dict | None:
    """Tuned entry for a call shape, or None (caller falls back).

    Tries the exact dtype key first, then the ``any``-dtype key, in the
    user cache, then the shipped table.  Never raises: tuning is an
    accelerant, not a dependency — any I/O or schema problem reads as a
    miss.
    """
    try:
        dev = device_key()
        names = [dtype_name(dtype)]
        if names[0] != "any":
            names.append("any")
        keys = [
            make_key(dev, kernel, g=g, m=m, n=n, d=d, dtype=nm, flags=flags)
            for nm in names
        ]
        for path in tables_in_use(cache_path):
            table = load_table_cached(path)
            for key in keys:
                entry = table.get(key)
                if entry is not None:
                    validate_entry(entry)
                    return entry
    except Exception:  # noqa: BLE001 - a broken table must read as a miss
        return None
    return None
