"""Engine ⇄ store glue: export committed pages, import on miss.

The two host-side seams the global prefix tier hooks into the engine:

* `export_chain` — called from `ServingEngine._commit_prefix` right
  after the allocator publishes a prompt's full pages locally.  Each
  page becomes one CRC'd record (already-published chains are just
  touched, keeping them hot); a mesh engine writes its per-shard head
  slices in the ``pools.<s>`` layout.
* `import_chain` — called from request intake, BEFORE admission runs
  its local `lookup_prefix`.  It extends the allocator's cached chain
  with matching store records: validate (corrupt → typed, counted,
  dropped, re-prefill), gate on fingerprint + geometry (mismatch is a
  miss), verify the exact token chain (hash collisions degrade to a
  miss), allocate pages watermark-aware (`for_decode=False`, so a
  busy replica refuses the import before it refuses decode appends),
  write the payloads into the per-layer pools, commit, then drop the
  importer's reference — the drained end-state is pages held by the
  prefix cache at refcount 1, exactly what a locally computed chain
  leaves and what the chaos quiescence invariant demands.

Both paths are no-ops when ``engine.prefix_store`` is None, so a
storeless fleet is byte-identical to the pre-tier code.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from attention_tpu.engine.errors import PrefixStoreCorruptError
from attention_tpu.engine.snapshot import _dtype_name, model_fingerprint
from attention_tpu.obs import blackbox as _blackbox
from attention_tpu.ops.paged import OutOfPagesError
from attention_tpu.prefixstore.records import (
    chain_key,
    decode_record,
    encode_record,
    page_geometry,
)


def fleet_fingerprint(engine) -> dict:
    """`model_fingerprint` PLUS a digest of the actual weights.

    Snapshots only ever reload into the fleet that wrote them, so the
    architecture fingerprint suffices there.  Store records cross
    fleet boundaries (a persisted store can outlive any one fleet),
    and two same-architecture models with different params would pass
    the architecture gate while holding each other's KV — wrong
    tokens, the one unacceptable outcome.  Hashed once per engine
    incarnation (leaf order is the params tree order, deterministic
    for a fixed structure) and cached on the engine."""
    cached = getattr(engine, "_prefixstore_fingerprint", None)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(engine.params):
        arr = np.asarray(leaf)
        h.update(str((arr.dtype.str, arr.shape)).encode())
        h.update(arr.tobytes())
    fp = dict(model_fingerprint(engine.model),
              params_sha256=h.hexdigest())
    engine._prefixstore_fingerprint = fp
    return fp


def engine_geometry(engine) -> dict:
    """The page geometry this engine exports under / imports against."""
    pool = engine.page_pools()[0]
    return page_geometry(
        num_kv_heads=pool.shape[1],
        page_size=engine.config.page_size,
        head_dim=pool.shape[3],
        layers=engine.model.depth,
        dtype=_dtype_name(pool.dtype),
    )


def _page_arrays(engine, page: int) -> list[np.ndarray]:
    """Host copies of one page's K then V arrays across layers."""
    return [np.asarray(pool[page]) for pool in engine.page_pools()]


def export_chain(engine, tokens, pages, *, now: int) -> int:
    """Publish the committed chain ``pages`` (covering the full pages
    of ``tokens``) into the engine's store; returns records newly
    stored.  Safe to call with any committed prefix — existing records
    are touched, not rewritten.  Refuses an engine whose model keeps
    recurrent state (`ServingEngine.require_pages_only`): its pages are
    no prefix without the state they led to."""
    engine.require_pages_only("the prefix store's export")
    store = engine.prefix_store
    if store is None:
        return 0
    ps = engine.config.page_size
    toks = tuple(int(t) for t in tokens)
    full = min(len(toks) // ps, len(pages))
    if full == 0:
        return 0
    fp = fleet_fingerprint(engine)
    geo = engine_geometry(engine)
    shards = engine.config.mesh_shards or 1
    stored = 0
    for i in range(1, full + 1):
        key_toks = toks[: i * ps]
        key = chain_key(key_toks)
        if store.get(key, now=now) is not None:
            continue  # already published; the get kept it hot
        blob = encode_record(
            tokens=key_toks,
            arrays=_page_arrays(engine, pages[i - 1]),
            fingerprint=fp, geometry=geo, shards=shards,
        )
        if store.put(key, blob, now=now):
            stored += 1
    return stored


def import_chain(engine, tokens, *, now: int) -> int:
    """Splice matching store records onto the engine's local prefix
    chain for ``tokens``; returns prompt tokens newly covered (0 on
    miss, mismatch, no store, or page pressure).

    Never raises: corruption is counted + dropped (the caller's later
    cold prefill is the recovery), and an allocator refusal under the
    watermark simply aborts the import.  (An engine whose model keeps
    recurrent state never calls this, `ServingEngine._import_prefix`;
    called directly for one it refuses like `export_chain`.)"""
    engine.require_pages_only("the prefix store's import")
    store = engine.prefix_store
    if store is None:
        return 0
    ps = engine.config.page_size
    toks = tuple(int(t) for t in tokens)
    limit = (len(toks) - 1) // ps
    local = engine.allocator.peek_prefix(toks)
    if limit <= local:
        return 0
    fp = fleet_fingerprint(engine)
    geo = engine_geometry(engine)
    recs = []
    for i in range(local + 1, limit + 1):
        key_toks = toks[: i * ps]
        key = chain_key(key_toks)
        blob = store.get(key, now=now)
        if blob is None:
            break
        try:
            rec = decode_record(blob)
        except PrefixStoreCorruptError:
            store.note_corrupt(key)
            _blackbox.note(
                "store_corrupt", tick=now,
                replica=getattr(engine, "trace_replica", None),
                incarnation=getattr(engine, "trace_incarnation", 0),
                step=engine.current_step, key=key[:12])
            break
        if rec.fingerprint != fp or rec.geometry != geo:
            break  # another fleet's pages: a miss, never corruption
        if rec.tokens != key_toks:
            break  # hash collision: degrade to a miss
        recs.append(rec)
    if not recs:
        return 0
    try:
        pages = engine.allocator.allocate(len(recs), for_decode=False)
    except OutOfPagesError:
        return 0
    idx = jnp.asarray(pages, jnp.int32)
    engine.set_page_pools([
        pool.at[idx].set(jnp.asarray(np.stack(stack), pool.dtype))
        for pool, stack in zip(engine.page_pools(),
                               zip(*(r.arrays for r in recs)))])
    chain = engine.allocator.cached_chain(toks)
    covered = local + len(recs)
    engine.allocator.commit_prefix(
        toks[: covered * ps], chain + pages, now=now
    )
    # drop the importer's reference: the cache's own incref (taken in
    # commit_prefix) is now the sole owner, matching a locally
    # computed chain after its request drains
    engine.allocator.free(pages)
    store.note_import(pages=len(recs), tokens=len(recs) * ps)
    _blackbox.note(
        "store_import", tick=now,
        replica=getattr(engine, "trace_replica", None),
        incarnation=getattr(engine, "trace_incarnation", 0),
        step=engine.current_step,
        pages=len(recs), tokens=len(recs) * ps)
    return len(recs) * ps
