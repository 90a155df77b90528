"""Persistent tuning tables: key schema, JSON I/O, device identity.

Two tables share one schema:

- the **user cache** (``ATTN_TPU_TUNING_CACHE``, default
  ``~/.cache/attention_tpu/tuning_cache.json``): written by
  ``cli tune`` / ``bench.py --autotune`` runs on the machine at hand,
  and read back by kernel dispatch only when the variable names it
  (`lookup.tables_in_use`);
- the **shipped table** (``attention_tpu/tuning/shipped_table.json``,
  committed): seeded from the measured heuristics by
  ``scripts/make_shipped_table.py`` so a fresh host starts from the
  swept defaults instead of nothing.

Schema (version 1)::

    {"version": 1,
     "entries": {"<key>": {"block_q": 4096, "block_k": 2048,
                           "ms": 2.87, "source": "measured",
                           "recorded": "2026-08-04"}, ...}}

Keys are 5 pipe-separated fields::

    <device>|<kernel>|g<G>-m<M>-n<N>-d<D>|<dtype>|<flags>

- ``device``: normalized device kind (``tpu-v5e``, ``cpu``, ...);
- ``kernel``: one of :data:`KERNELS`;
- shape bucket: ``G`` = heads bucket (GQA group for decode), ``M``/``N``
  = floor-power-of-two sequence buckets (``M`` = batch bucket for
  decode/paged), ``D`` = exact head dim — floor bucketing means an
  entry measured at 32k serves every m in [32768, 65535], and the
  kernel adapters re-clamp tiles to the call's real padding;
- ``dtype``: canonical dtype name, or ``any``;
- ``flags``: comma-joined sorted ``k=v`` pairs, ``-`` when empty
  (window flags carry the window's own pow2 bucket).

Entry values carry any of ``block_q``/``block_k``/``page_size`` (all
must be positive multiples of 128 — ``validate_entry`` and the
``scripts/check_shipped_table.py`` lint enforce it), optionally a
``max_mode`` rescaling-math variant (one of :data:`MAX_MODE_VALUES`;
the forward/decode/ragged kernels' ``max_mode="auto"`` dispatch reads
it), plus provenance fields the kernels ignore.
"""

from __future__ import annotations

import json
import os
import re
import tempfile

SCHEMA_VERSION = 1

KERNELS = ("flash_fwd", "flash_bwd", "flash_bwd_fused", "decode", "paged",
           "ragged")

_TILE_FIELDS = ("block_q", "block_k", "page_size")

#: legal values for an entry's optional ``max_mode`` field — the
#: rescaling-math variants ops.flash/decode/ragged_paged can lower
#: (ops.flash.MAX_MODES; spelled out here so a corrupt cache cannot
#: import ops at validation time)
MAX_MODE_VALUES = ("online", "bound", "flashd", "amla")

_BUCKET_RE = re.compile(r"^g(\d+)-m(\d+)-n(\d+)-d(\d+)$")
_FLAG_RE = re.compile(r"^[a-z_]+=\d+$")


def bucket_pow2(x: int) -> int:
    """Floor power-of-two bucket (4864 -> 4096; exact powers map to
    themselves, so tuned shapes hit their own bucket)."""
    if x < 1:
        raise ValueError(f"bucket_pow2 needs x >= 1, got {x}")
    return 1 << (int(x).bit_length() - 1)


def make_key(device: str, kernel: str, *, g: int, m: int, n: int, d: int,
             dtype: str = "any", flags: dict | None = None) -> str:
    """Cache key for a concrete call shape (buckets applied here, so
    callers pass real shapes)."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel family {kernel!r}")
    bucket = (f"g{bucket_pow2(g)}-m{bucket_pow2(m)}"
              f"-n{bucket_pow2(n)}-d{d}")
    items = sorted((flags or {}).items())
    flag_s = ",".join(f"{k}={int(v)}" for k, v in items) or "-"
    return f"{device}|{kernel}|{bucket}|{dtype}|{flag_s}"


def parse_key(key: str) -> dict:
    """Split a key back into fields; raises ValueError on malformed keys
    (the shipped-table lint runs every committed key through this)."""
    parts = key.split("|")
    if len(parts) != 5:
        raise ValueError(f"key must have 5 '|' fields: {key!r}")
    device, kernel, bucket, dtype, flag_s = parts
    if not device:
        raise ValueError(f"empty device field: {key!r}")
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel family {kernel!r} in {key!r}")
    mb = _BUCKET_RE.match(bucket)
    if not mb:
        raise ValueError(f"malformed shape bucket {bucket!r} in {key!r}")
    g, m, n, d = (int(x) for x in mb.groups())
    for dim, name in ((g, "g"), (m, "m"), (n, "n")):
        if dim != bucket_pow2(dim):
            raise ValueError(
                f"bucket field {name}={dim} is not a power of two: {key!r}"
            )
    flags = {}
    if flag_s != "-":
        for pair in flag_s.split(","):
            if not _FLAG_RE.match(pair):
                raise ValueError(f"malformed flag {pair!r} in {key!r}")
            fk, fv = pair.split("=")
            if fk in flags:
                raise ValueError(f"duplicate flag {fk!r} in {key!r}")
            flags[fk] = int(fv)
    if list(flags) != sorted(flags):
        raise ValueError(f"flags not sorted in {key!r}")
    return {"device": device, "kernel": kernel, "g": g, "m": m, "n": n,
            "d": d, "dtype": dtype, "flags": flags}


def validate_entry(entry: dict) -> None:
    """Raise ValueError unless the entry carries at least one tile field,
    every tile field is a positive multiple of 128, and ``max_mode``
    (when present) names a known rescaling-math variant."""
    if not isinstance(entry, dict):
        raise ValueError(f"entry must be a dict, got {type(entry).__name__}")
    tiles = [f for f in _TILE_FIELDS if f in entry]
    if not tiles:
        raise ValueError(f"entry has no tile field {_TILE_FIELDS}: {entry}")
    for f in tiles:
        v = entry[f]
        if not isinstance(v, int) or v <= 0 or v % 128:
            raise ValueError(
                f"{f}={v!r} must be a positive multiple of 128"
            )
    if "max_mode" in entry and entry["max_mode"] not in MAX_MODE_VALUES:
        raise ValueError(
            f"max_mode={entry['max_mode']!r} must be one of "
            f"{MAX_MODE_VALUES}"
        )


def default_cache_path() -> str:
    """Where ``tune`` WRITES the user cache: ``ATTN_TPU_TUNING_CACHE``,
    else ``$XDG_CACHE_HOME/attention_tpu/tuning_cache.json`` (XDG
    default ``~/.cache``).  Dispatch reads it back only through the
    variable."""
    env = os.environ.get("ATTN_TPU_TUNING_CACHE")
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME") or os.path.expanduser("~/.cache")
    return os.path.join(base, "attention_tpu", "tuning_cache.json")


def shipped_table_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "shipped_table.json")


def device_key() -> str:
    """Normalized identity of the default device, the key's first field.

    TPU kinds normalize to ``tpu-v<gen><variant>`` (``TPU v5 lite`` and
    ``TPU v5e`` both -> ``tpu-v5e``) so shipped entries survive PJRT
    spelling drift; non-TPU backends use the platform name, which is
    what keeps CPU/interpret lookups off the TPU-measured shipped
    entries (they miss and fall to the heuristics).
    """
    try:
        import jax

        dev = jax.devices()[0]
    except Exception:  # noqa: BLE001 - no backend at all
        return "unknown"
    if dev.platform != "tpu":
        return str(dev.platform).lower()
    return normalize_device_kind(getattr(dev, "device_kind", "tpu"))


def normalize_device_kind(kind: str) -> str:
    k = (kind or "tpu").lower()
    mg = re.search(r"v(\d+)\s*(p|e|x|lite)?", k)
    if not mg:
        # newer spellings drop the 'v' ("TPU7x")
        mg = re.search(r"tpu\s*(\d+)\s*(p|e|x|lite)?", k)
    if not mg:
        return "tpu-" + re.sub(r"\s+", "-", k.strip())
    variant = mg.group(2) or ""
    if variant == "lite":
        variant = "e"
    return f"tpu-v{mg.group(1)}{variant}"


class TuningTable:
    """One schema-versioned key->entry table with atomic JSON persistence."""

    def __init__(self, entries: dict | None = None, path: str | None = None):
        self.entries: dict = dict(entries or {})
        self.path = path

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        """Load ``path``; missing/corrupt/version-mismatched files load
        as empty (a bad cache must never break kernel dispatch)."""
        try:
            with open(path) as f:
                data = json.load(f)
            if (isinstance(data, dict)
                    and data.get("version") == SCHEMA_VERSION
                    and isinstance(data.get("entries"), dict)):
                return cls(data["entries"], path=path)
        except (OSError, ValueError):
            pass
        return cls({}, path=path)

    def get(self, key: str) -> dict | None:
        e = self.entries.get(key)
        return dict(e) if isinstance(e, dict) else None

    def put(self, key: str, entry: dict) -> None:
        parse_key(key)
        validate_entry(entry)
        self.entries[key] = dict(entry)

    def save(self, path: str | None = None) -> str:
        """Atomic write (tmp + replace): a concurrent reader never sees a
        torn table."""
        path = path or self.path
        if not path:
            raise ValueError("no path to save to")
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        data = {"version": SCHEMA_VERSION,
                "entries": dict(sorted(self.entries.items()))}
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(os.path.abspath(path)), suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self.path = path
        return path


# (path, mtime_ns, size) -> TuningTable: lookups happen at jit-trace
# time, so repeated loads must cost one os.stat, not one json parse —
# while a post-``tune`` write (new mtime) still invalidates in-process.
_TABLE_MEMO: dict = {}


def load_table_cached(path: str) -> TuningTable:
    try:
        st = os.stat(path)
        stamp = (path, st.st_mtime_ns, st.st_size)
    except OSError:
        return TuningTable({}, path=path)
    hit = _TABLE_MEMO.get(path)
    if hit is not None and hit[0] == stamp:
        return hit[1]
    table = TuningTable.load(path)
    _TABLE_MEMO[path] = (stamp, table)
    return table
