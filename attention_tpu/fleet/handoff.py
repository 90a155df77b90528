"""Prefill→decode KV handoff: ship committed pages, not tokens.

PR 10's migration fabric moves a request by replaying its stream —
the destination re-prefills prompt + fed generation from scratch.
That is the right durability story (a dead replica's pages are gone)
but the wrong disaggregation story: a prefill-pool replica that just
spent its whole budget computing a 100k-token prompt holds exactly
the KV the decode destination needs, and throwing it away doubles
the fleet's prefill bill.

This module extends the per-request snapshot record (the PR 9
section format: one manifest line + CRC'd payload sections) with a
``pages`` payload — the request's committed prefix pages as per-shard
``pools.<s>`` head slices, the `prefixstore/records.py` layout with a
leading page axis.  A handoff blob is therefore self-validating and
self-describing:

    meta       the `_request_to_dict` request record + exporter
               fingerprint/geometry + the page-aligned token chain
    pools.<s>  shard s's contiguous KV-head slice of every committed
               page, K layers then V layers, independently CRC'd

The decode-side import mirrors `prefixstore.adapter.import_chain`:
gate on fleet fingerprint + geometry (mismatch = miss, never
corruption), allocate watermark-aware, write the pools, commit the
chain into the local prefix cache, drop the importer's reference —
so the subsequent `resume_request` admission finds the prefix cached
and skips the re-prefill entirely.

Integrity doctrine, same as snapshots and the prefix store: any
structural damage raises the typed `HandoffCorruptError`
(a `PrefixStoreCorruptError` subclass, so every existing typed-error
gate covers it); the handoff path catches it and re-admits WITHOUT
the pages.  A corrupt payload costs a re-prefill, never a wrong
token.
"""

from __future__ import annotations

import dataclasses
import json
import zlib
from typing import Any

import jax.numpy as jnp
import numpy as np

from attention_tpu.engine.errors import HandoffCorruptError
from attention_tpu.engine.snapshot import _jbytes, _np_dtype
from attention_tpu.ops.paged import OutOfPagesError
from attention_tpu.prefixstore.adapter import (
    engine_geometry,
    fleet_fingerprint,
)

HANDOFF_MAGIC = "atp-handoff"
HANDOFF_VERSION = 1


@dataclasses.dataclass(frozen=True)
class HandoffRecord:
    """One decoded handoff: the request record + its shipped pages."""

    request: dict                 # the PR 9 per-request section dict
    tokens: tuple[int, ...]       # page-aligned committed prefix chain
    fingerprint: dict             # exporter's fleet fingerprint
    geometry: dict                # exporter's page geometry
    arrays: tuple                 # 2*layers np arrays, K then V, each
    #                               (num_pages, num_kv_heads,
    #                                page_size, head_dim)


def _corrupt(why: str) -> HandoffCorruptError:
    return HandoffCorruptError(f"handoff record: {why}")


def encode_handoff(*, request: dict, tokens, arrays, fingerprint: dict,
                   geometry: dict, shards: int = 1) -> bytes:
    """Serialize one request + its committed prefix pages.

    ``arrays``: 2*layers host arrays (K pools then V pools), each
    ``(num_pages, num_kv_heads, page_size, head_dim)`` — the page axis
    leads so an S-shard exporter slices heads exactly like a snapshot
    does."""
    heads = geometry["num_kv_heads"]
    if shards < 1 or heads % shards:
        raise ValueError(
            f"shards {shards} does not divide num_kv_heads {heads}"
        )
    toks = [int(t) for t in tokens]
    hosted = [np.asarray(a) for a in arrays]
    num_pages = int(hosted[0].shape[0]) if hosted else 0
    meta = {
        "request": request,
        "tokens": toks,
        "num_pages": num_pages,
        "fingerprint": fingerprint,
        "geometry": geometry,
    }
    hh = heads // shards
    sections = [("meta", _jbytes(meta))] + [
        (f"pools.{s}",
         b"".join(np.ascontiguousarray(
             a[:, s * hh:(s + 1) * hh]).tobytes() for a in hosted))
        for s in range(shards)
    ]
    manifest = {
        "magic": HANDOFF_MAGIC,
        "version": HANDOFF_VERSION,
        "shards": shards,
        "sections": [
            {"name": name, "nbytes": len(payload),
             "crc32": zlib.crc32(payload)}
            for name, payload in sections
        ],
    }
    return (_jbytes(manifest) + b"\n"
            + b"".join(payload for _, payload in sections))


def _read_sections(blob: bytes) -> tuple[dict, dict[str, bytes]]:
    """Manifest + checksummed sections, or the typed corrupt raise —
    the `prefixstore.records` validation chain under the handoff
    magic."""
    nl = blob.find(b"\n")
    if nl < 0:
        raise _corrupt("no manifest line")
    try:
        manifest = json.loads(blob[:nl])
    except ValueError:
        raise _corrupt("unparseable manifest")
    if not isinstance(manifest, dict) \
            or manifest.get("magic") != HANDOFF_MAGIC:
        raise _corrupt("bad magic (not a handoff record)")
    if manifest.get("version") != HANDOFF_VERSION:
        raise _corrupt(
            f"unsupported handoff version {manifest.get('version')!r} "
            f"(reader speaks {HANDOFF_VERSION})"
        )
    shards = manifest.get("shards", 1)
    if not isinstance(shards, int) or isinstance(shards, bool) \
            or shards < 1:
        raise _corrupt(f"bad shards count {shards!r}")
    try:
        entries = [(s["name"], int(s["nbytes"]), int(s["crc32"]))
                   for s in manifest["sections"]]
    except (KeyError, TypeError, ValueError):
        raise _corrupt("malformed section table")
    sections: dict[str, bytes] = {}
    offset = nl + 1
    for name, nbytes, crc in entries:
        payload = blob[offset:offset + nbytes]
        if len(payload) != nbytes:
            raise _corrupt(
                f"section {name!r} truncated "
                f"({len(payload)}/{nbytes} bytes)"
            )
        if zlib.crc32(payload) != crc:
            raise _corrupt(f"section {name!r} checksum mismatch")
        sections[name] = payload
        offset += nbytes
    if offset != len(blob):
        raise _corrupt(f"{len(blob) - offset} trailing bytes")
    required = ("meta", *(f"pools.{s}" for s in range(shards)))
    for name in required:
        if name not in sections:
            raise _corrupt(f"missing section {name!r}")
    return manifest, sections


def decode_handoff(blob: bytes) -> HandoffRecord:
    """Validate + reassemble one handoff; `HandoffCorruptError` on any
    structural damage.  Shard head slices concatenate back along the
    head dim, so exporter and importer shard counts are independent."""
    manifest, sections = _read_sections(blob)
    shards = manifest.get("shards", 1)
    try:
        meta = json.loads(sections["meta"])
        request = dict(meta["request"])
        tokens = tuple(int(t) for t in meta["tokens"])
        num_pages = int(meta["num_pages"])
        fingerprint = meta["fingerprint"]
        geometry = meta["geometry"]
        heads = int(geometry["num_kv_heads"])
        page_size = int(geometry["page_size"])
        head_dim = int(geometry["head_dim"])
        layers = int(geometry["layers"])
        dtype = _np_dtype(geometry["dtype"])
    except (KeyError, TypeError, ValueError):
        raise _corrupt("undecodable meta section")
    if num_pages < 1:
        raise _corrupt(f"bad page count {num_pages}")
    if len(tokens) != num_pages * page_size:
        raise _corrupt(
            f"token chain length {len(tokens)} != num_pages "
            f"{num_pages} * page_size {page_size}"
        )
    if heads < 1 or heads % shards:
        raise _corrupt(
            f"shards {shards} does not divide num_kv_heads {heads}"
        )
    hh = heads // shards
    slice_bytes = num_pages * hh * page_size * head_dim * dtype.itemsize
    per_shard = []
    for s in range(shards):
        payload = sections[f"pools.{s}"]
        if len(payload) != 2 * layers * slice_bytes:
            raise _corrupt(
                f"section 'pools.{s}' carries {len(payload)} bytes, "
                f"geometry implies {2 * layers * slice_bytes}"
            )
        per_shard.append([
            np.frombuffer(
                payload[i * slice_bytes:(i + 1) * slice_bytes], dtype
            ).reshape(num_pages, hh, page_size, head_dim)
            for i in range(2 * layers)
        ])
    arrays = tuple(
        np.concatenate([per_shard[s][i] for s in range(shards)], axis=1)
        if shards > 1 else per_shard[0][i]
        for i in range(2 * layers)
    )
    return HandoffRecord(request=request, tokens=tokens,
                         fingerprint=fingerprint, geometry=geometry,
                         arrays=arrays)


def inspect_handoff(blob: bytes) -> dict[str, Any]:
    """Tolerant manifest-level view of one handoff blob for
    `cli snapshot inspect`: section names, byte counts, and per-section
    CRC verdicts — never raises (damage lands in ``problems``)."""
    info: dict[str, Any] = {"format": "handoff", "valid": True,
                            "problems": []}
    try:
        manifest, sections = _read_sections(blob)
    except HandoffCorruptError as e:
        info["valid"] = False
        info["problems"].append(str(e))
        # degrade to whatever the manifest line still says
        nl = blob.find(b"\n")
        try:
            manifest = json.loads(blob[:max(nl, 0)])
        except ValueError:
            return info
        if not isinstance(manifest, dict):
            return info
        sections = None
    info["shards"] = manifest.get("shards", 1)
    info["version"] = manifest.get("version")
    rows = []
    for s in manifest.get("sections", []):
        try:
            name, nbytes, crc = (s["name"], int(s["nbytes"]),
                                 int(s["crc32"]))
        except (KeyError, TypeError, ValueError):
            continue
        ok = (sections is not None and name in sections
              and zlib.crc32(sections[name]) == crc)
        rows.append({"name": name, "nbytes": nbytes, "crc_ok": ok})
    info["sections"] = rows
    if sections is not None:
        try:
            meta = json.loads(sections["meta"])
            info["request_id"] = meta["request"].get("request_id")
            info["num_pages"] = int(meta["num_pages"])
            info["tokens"] = len(meta["tokens"])
        except (KeyError, TypeError, ValueError):
            info["problems"].append("undecodable meta section")
            info["valid"] = False
    return info


def is_handoff(blob: bytes) -> bool:
    """True iff ``blob`` leads with a handoff manifest line (cheap
    format sniff for the CLI's inspect dispatch)."""
    nl = blob.find(b"\n")
    if nl < 0:
        return False
    try:
        manifest = json.loads(blob[:nl])
    except ValueError:
        return False
    return (isinstance(manifest, dict)
            and manifest.get("magic") == HANDOFF_MAGIC)


def export_handoff(engine, req, request_record: dict) -> bytes | None:
    """Serialize one committed request + its full prefix pages from
    the PREFILL engine; None when no whole page is committed yet
    (the handoff then degrades to the plain PR 10 replay path).

    ``request_record`` is the caller's `_request_to_dict` dict — the
    cut serializes the request exactly once and ships the same record
    in the blob the chaos checkers later audit.  Refuses an engine
    whose model keeps recurrent state: the destination could not
    continue from pages alone."""
    engine.require_pages_only("the fleet's KV hand-off")
    ps = engine.config.page_size
    toks = tuple(int(t) for t in req.prompt)
    full = min(len(toks) // ps, len(req.pages))
    if full == 0:
        return None
    pages = [int(p) for p in list(req.pages)[:full]]
    arrays = tuple(
        np.stack([np.asarray(pool[p]) for p in pages])
        for pool in engine.page_pools()
    )
    return encode_handoff(
        request=request_record,
        tokens=toks[: full * ps],
        arrays=arrays,
        fingerprint=fleet_fingerprint(engine),
        geometry=engine_geometry(engine),
        shards=engine.config.mesh_shards or 1,
    )


def import_handoff(engine, blob: bytes, *, now: int) -> int:
    """Write a handoff's shipped pages into the DECODE engine's pools
    and commit the chain into its local prefix cache; returns prompt
    tokens newly covered (the re-prefill the destination skips).

    Raises `HandoffCorruptError` on structural damage (the caller
    falls back to plain replay); returns 0 on fingerprint/geometry
    mismatch (another fleet's pages: a miss), an already-cached chain,
    or allocator pressure (`for_decode=False`: a busy decode replica
    refuses the import before it refuses decode appends)."""
    engine.require_pages_only("the fleet's KV hand-off")
    rec = decode_handoff(blob)
    if (rec.fingerprint != fleet_fingerprint(engine)
            or rec.geometry != engine_geometry(engine)):
        return 0
    ps = int(rec.geometry["page_size"])
    toks = rec.tokens
    n = len(toks) // ps
    local = engine.allocator.peek_prefix(toks)
    if n <= local:
        return 0   # affinity already holds it; nothing to import
    try:
        pages = engine.allocator.allocate(n - local, for_decode=False)
    except OutOfPagesError:
        return 0
    idx = jnp.asarray(pages, jnp.int32)
    engine.set_page_pools([
        pool.at[idx].set(jnp.asarray(stack[local:], pool.dtype))
        for pool, stack in zip(engine.page_pools(), rec.arrays)])
    chain = engine.allocator.cached_chain(toks)
    engine.allocator.commit_prefix(toks, chain + pages, now=now)
    # drop the importer's reference: the prefix cache's own incref is
    # now the sole owner — the exact end-state a locally computed
    # chain leaves, which the chaos quiescence invariant demands
    engine.allocator.free(pages)
    return (n - local) * ps
