"""Crash-consistent `ServingEngine` snapshots: save, verify, restore.

The training side has had this contract since PR 3
(`models/checkpoint.py` + `models/resilient.py`: checkpoint-every-N,
re-invoke, bit-identical resume); this module gives the *serving*
engine the same durability rung.  A snapshot is a consistent
between-steps cut of everything that determines future outputs:

========== ============================================================
section    contents
========== ============================================================
``meta``   format version, `EngineConfig` fields, model fingerprint
           (vocab/dim/depth/heads/dtype/impl), engine step, seq counter
``pools``  raw per-layer K/V page-pool payloads (``tobytes``; dtype and
           shape recorded in ``meta`` — bf16 round-trips via ml_dtypes).
           A mesh engine (``mesh_shards`` = N > 1) writes ``pools.0``
           .. ``pools.N-1`` instead — each shard's contiguous KV-head
           slice of every pool, independently CRC'd — and the manifest
           records ``shards: N``; restore reassembles along the head
           dim and re-places the pools on the reader's mesh
``state``  `PagePool` free list (exact order) + refcounts, prefix-cache
           index (keys, pages, parent/children links, LRU stamps),
           allocator counters, scheduler knobs
``requests`` waiting + running queues in order: full `Request` fields
           including streamed tokens and ``pending_token`` — the RNG
           chain is NOT serialized; it is reconstructed arithmetically
           (one split per sampled token) exactly like `resume_request`
========== ============================================================

On disk: one ASCII JSON manifest line (magic, version, per-section
byte counts and CRC32s) followed by the concatenated section payloads.
Serialization is deterministic (sorted keys, ordered queues), so
``sha256(serialize(engine))`` is a usable state fingerprint — the
chaos invariant ``restore(save(engine))`` compares exactly that.

Durability discipline (pinned by ATP701, `analysis/durability.py`):
the snapshot file appears atomically AND durably via
``tempfile.mkstemp`` in the target directory, ``os.fsync`` of the
temp fd, ``os.replace``, then an fsync of the directory — a reader
(or a recovery scan) never observes a torn snapshot, only the
previous one, and a landed file survives power loss.  Any validation
failure — bad magic, stale version, truncated or bit-flipped section,
model mismatch — raises the typed `SnapshotCorruptError`; recovery
code treats that as "this candidate does not count" and falls back,
never crashes.

Deliberately NOT serialized: wall-clock bookkeeping (``_wall`` is
re-seeded at restore; TTFT/latency percentiles are observability, not
contract) and `EngineMetrics` history.  Token streams, the scheduler
contract, and page accounting round-trip exactly.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import tempfile
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from attention_tpu import obs
from attention_tpu.obs import trace as _trace
from attention_tpu.engine.allocator import _PrefixEntry
from attention_tpu.engine.engine import (
    EngineConfig,
    ServingEngine,
    require_pages_only,
)
from attention_tpu.engine.errors import SnapshotCorruptError, SnapshotError
from attention_tpu.engine.journal import (
    Journal,
    apply_journal,
    journal_path,
    list_journals,
)
from attention_tpu.engine.request import Request, RequestState, SamplingParams
from attention_tpu.parallel.serving import MeshConfigError

SNAPSHOT_MAGIC = "atp-snapshot"
SNAPSHOT_VERSION = 2
SNAPSHOT_SUFFIX = ".atpsnap"

#: manifest section order for a single-device snapshot.  A mesh
#: engine's snapshot (``EngineConfig.mesh_shards`` = N > 1) replaces
#: the one ``pools`` section with N ``pools.<s>`` sections — one
#: contiguous KV-head slice of every per-layer pool per shard, each
#: with its own CRC — and the manifest records ``shards``: N (absent
#: or 1 = the single-device layout).  Damage to ONE shard slice is
#: therefore detected per shard, and a migrating reader reassembles
#: the logical pools by concatenating the slices along the head dim.
SECTIONS = ("meta", "pools", "state", "requests")

_SNAP_RE = re.compile(r"^snap-(\d{8})\.atpsnap$")

_SAVES = obs.counter("engine.snapshot.saves",
                     "snapshot files written (atomic replace landed)")
_RESTORES = obs.counter("engine.snapshot.restores",
                        "engine restore attempts by outcome")
_CORRUPT = obs.counter("engine.snapshot.corrupt",
                       "snapshot validation failures (typed, recovered)")
_SAVE_MS = obs.histogram("engine.snapshot.save_ms",
                         "serialize + fsync-rename wall time per snapshot",
                         buckets=(1.0, 5.0, 10.0, 50.0, 100.0, 500.0,
                                  1000.0))
_BYTES = obs.histogram("engine.snapshot.bytes",
                       "snapshot file size",
                       buckets=(4096.0, 65536.0, 1048576.0, 16777216.0,
                                268435456.0))
_JOURNAL_LAG = obs.gauge("engine.snapshot.journal_lag",
                         "journal records accumulated since the last "
                         "snapshot (replay cost bound)")


def snapshot_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"snap-{step:08d}{SNAPSHOT_SUFFIX}")


def list_snapshots(directory: str) -> list[tuple[int, str]]:
    """``(step, path)`` pairs under ``directory``, ascending by step."""
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    out = []
    for name in names:
        m = _SNAP_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    return sorted(out)


def _corrupt(path: str, why: str) -> SnapshotCorruptError:
    _CORRUPT.inc()
    return SnapshotCorruptError(f"{path}: {why}")


def _jbytes(o) -> bytes:
    return json.dumps(o, sort_keys=True, separators=(",", ":")).encode()


def _dtype_name(dtype) -> str:
    return np.dtype(dtype).name


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # extension dtypes (bfloat16 et al.) resolve through jnp
        return np.dtype(getattr(jnp, name))


def model_fingerprint(model) -> dict:
    """The architecture identity a snapshot is only valid against."""
    return {
        "vocab": int(model.vocab),
        "dim": int(model.dim),
        "depth": int(model.depth),
        "num_q_heads": int(model.num_q_heads),
        "num_kv_heads": int(model.num_kv_heads),
        "dtype": _dtype_name(model.dtype),
        "impl": str(model.impl),
    }


def _request_to_dict(req: Request, queue: str) -> dict:
    s = req.sampling
    return {
        "queue": queue,
        "request_id": req.request_id,
        "prompt": list(req.prompt),
        "sampling": {
            "max_tokens": s.max_tokens,
            "temperature": s.temperature,
            "top_k": s.top_k,
            "top_p": s.top_p,
            "seed": s.seed,
            "stop_token": s.stop_token,
        },
        "arrival": req.arrival,
        "seq": req.seq,
        "deadline_step": req.deadline_step,
        "state": req.state.value,
        "tokens": list(req.tokens),
        "output_tokens": list(req.output_tokens),
        "pending_token": req.pending_token,
        "computed_tokens": req.computed_tokens,
        "pages": list(req.pages),
        "prefix_cached_tokens": req.prefix_cached_tokens,
        "preemptions": req.preemptions,
        "first_scheduled_step": req.first_scheduled_step,
        "first_token_step": req.first_token_step,
        "finish_step": req.finish_step,
        # the request's trace tail rides the snapshot (obs/trace.py):
        # a warm restart or migration in a FRESH process reconstructs
        # the journey chain from this section alone.  Deterministic —
        # trace events carry only tick/step coordinates, never wall
        # time — so serialize() stays fingerprint-stable.
        "trace": _trace.events_of(req.request_id)
        if _trace.active() else [],
    }


def _request_from_dict(d: dict) -> Request:
    req = Request(
        request_id=d["request_id"],
        prompt=tuple(int(t) for t in d["prompt"]),
        sampling=SamplingParams(**d["sampling"]),
        arrival=d["arrival"],
        seq=d["seq"],
        deadline_step=d["deadline_step"],
    )
    # lifecycle position is restored, not re-derived: assign directly
    # (transition() validates client-visible edges, not resurrection)
    req.state = RequestState(d["state"])
    req.tokens = [int(t) for t in d["tokens"]]
    req.output_tokens = [int(t) for t in d["output_tokens"]]
    req.pending_token = d["pending_token"]
    req.computed_tokens = d["computed_tokens"]
    req.pages = [int(p) for p in d["pages"]]
    req.prefix_cached_tokens = d["prefix_cached_tokens"]
    req.preemptions = d["preemptions"]
    req.first_scheduled_step = d["first_scheduled_step"]
    req.first_token_step = d["first_token_step"]
    req.finish_step = d["finish_step"]
    return req


def _serialize_sections(engine: ServingEngine) -> list[tuple[str, bytes]]:
    # a snapshot holds pages and requests; a recurrent layer's state per
    # request is in neither, so such an engine is refused, not half-saved
    engine.require_pages_only("a snapshot")
    # block until the device pools are final before reading bytes out
    engine.quiesce()
    cfg = dataclasses.asdict(engine.config)
    if cfg["cache_dtype"] is not None:
        cfg["cache_dtype"] = _dtype_name(cfg["cache_dtype"])
    hosted = [np.asarray(a) for a in engine.page_pools()]
    meta = {
        "config": cfg,
        "model": model_fingerprint(engine.model),
        "step": engine.current_step,
        "next_seq": engine._next_seq,
        "pool_dtype": _dtype_name(hosted[0].dtype),
        "pool_shape": list(hosted[0].shape),
    }
    shards = getattr(engine.config, "mesh_shards", 0) or 1
    if shards == 1:
        pool_sections = [("pools", b"".join(a.tobytes() for a in hosted))]
    else:
        # one section per head shard, each carrying that shard's
        # contiguous KV-head slice of every per-layer pool — exactly
        # the bytes the shard's device holds, CRC'd independently so
        # single-shard damage is a typed per-shard refusal
        hh = hosted[0].shape[1] // shards
        pool_sections = [
            (f"pools.{s}", b"".join(
                a[:, s * hh:(s + 1) * hh].tobytes() for a in hosted))
            for s in range(shards)
        ]
    alloc = engine.allocator
    sched = engine.scheduler
    state = {
        "free": [int(p) for p in engine.pool._free],
        "refs": [int(r) for r in engine.pool._refs],
        "watermark_pages": alloc.watermark_pages,
        "prefix": [
            {
                "key": list(e.key),
                "page": e.page,
                "parent": list(e.parent) if e.parent is not None else None,
                "children": sorted(list(c) for c in e.children),
                "last_use": e.last_use,
            }
            for _, e in sorted(alloc._prefix.items())
        ],
        "counters": {
            "prefix_hits": alloc.prefix_hits,
            "prefix_misses": alloc.prefix_misses,
            "prefix_hit_tokens": alloc.prefix_hit_tokens,
            "prefix_evictions": alloc.prefix_evictions,
        },
        "scheduler": {
            "token_budget": sched.token_budget,
            "prefix_admission": sched.prefix_admission,
            "num_preemptions": sched.num_preemptions,
        },
    }
    requests = (
        [_request_to_dict(r, "waiting") for r in sched.waiting]
        + [_request_to_dict(r, "running") for r in sched.running]
    )
    return [("meta", _jbytes(meta)), *pool_sections,
            ("state", _jbytes(state)), ("requests", _jbytes(requests))]


def _pool_section_names(shards: int) -> tuple[str, ...]:
    """The pool section names a ``shards``-way snapshot must carry."""
    if shards == 1:
        return ("pools",)
    return tuple(f"pools.{s}" for s in range(shards))


def serialize(engine: ServingEngine) -> bytes:
    """Deterministic snapshot bytes (manifest line + section payloads)."""
    sections = _serialize_sections(engine)
    shards = sum(1 for name, _ in sections
                 if name == "pools" or name.startswith("pools."))
    manifest = {
        "magic": SNAPSHOT_MAGIC,
        "version": SNAPSHOT_VERSION,
        "shards": shards,
        "sections": [
            {"name": name, "nbytes": len(payload),
             "crc32": zlib.crc32(payload)}
            for name, payload in sections
        ],
    }
    return (_jbytes(manifest) + b"\n"
            + b"".join(payload for _, payload in sections))


def state_fingerprint(engine: ServingEngine) -> str:
    """sha256 of the deterministic serialization — equal fingerprints
    mean byte-identical future outputs (wall-clock metrics excluded by
    construction)."""
    return hashlib.sha256(serialize(engine)).hexdigest()


def _fsync_dir(directory: str) -> None:
    """fsync a directory so a just-landed ``os.replace`` survives power
    loss (no-op on platforms without directory fds)."""
    try:
        dfd = os.open(directory, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def save(engine: ServingEngine, path: str) -> dict:
    """Write one snapshot durably and atomically (tmp in the target
    dir, fsync, ``os.replace``, fsync the directory); returns
    ``{path, nbytes, step}``."""
    t0 = time.perf_counter()
    blob = serialize(engine)
    directory = os.path.dirname(os.path.abspath(path))
    os.makedirs(directory, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
            f.flush()
            # a rename can land while the data blocks are still dirty:
            # without this fsync a power loss can leave the final path
            # holding an empty/partial file, and _prune may by then
            # have dropped the journals an older snapshot needs
            os.fsync(f.fileno())
        os.replace(tmp, path)
        _fsync_dir(directory)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _SAVES.inc()
    _SAVE_MS.observe((time.perf_counter() - t0) * 1e3)
    _BYTES.observe(float(len(blob)))
    return {"path": path, "nbytes": len(blob),
            "step": engine.current_step}


def _read_sections(path: str) -> tuple[dict, dict[str, bytes]]:
    """Parse + checksum every section; raises `SnapshotCorruptError`
    on any structural damage."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as e:
        raise _corrupt(path, f"unreadable: {e}")
    nl = blob.find(b"\n")
    if nl < 0:
        raise _corrupt(path, "no manifest line")
    try:
        manifest = json.loads(blob[:nl])
    except ValueError:
        raise _corrupt(path, "unparseable manifest")
    if not isinstance(manifest, dict) \
            or manifest.get("magic") != SNAPSHOT_MAGIC:
        raise _corrupt(path, "bad magic (not an engine snapshot)")
    if manifest.get("version") != SNAPSHOT_VERSION:
        raise _corrupt(
            path,
            f"unsupported snapshot version {manifest.get('version')!r} "
            f"(reader speaks {SNAPSHOT_VERSION})",
        )
    sections: dict[str, bytes] = {}
    offset = nl + 1
    try:
        entries = [(s["name"], int(s["nbytes"]), int(s["crc32"]))
                   for s in manifest["sections"]]
    except (KeyError, TypeError, ValueError):
        raise _corrupt(path, "malformed section table")
    for name, nbytes, crc in entries:
        payload = blob[offset:offset + nbytes]
        if len(payload) != nbytes:
            raise _corrupt(
                path,
                f"section {name!r} truncated "
                f"({len(payload)}/{nbytes} bytes)",
            )
        if zlib.crc32(payload) != crc:
            raise _corrupt(path, f"section {name!r} checksum mismatch")
        sections[name] = payload
        offset += nbytes
    if offset != len(blob):
        raise _corrupt(path, f"{len(blob) - offset} trailing bytes")
    shards = manifest.get("shards", 1)
    if not isinstance(shards, int) or isinstance(shards, bool) \
            or shards < 1:
        raise _corrupt(path, f"bad shards count {shards!r}")
    required = ("meta", *_pool_section_names(shards),
                "state", "requests")
    for name in required:
        if name not in sections:
            raise _corrupt(path, f"missing section {name!r}")
    return manifest, sections


def verify(path: str) -> list[str]:
    """Validation problems for one snapshot file ([] = valid).

    The CLI surface (`cli snapshot verify`); same checks as
    `restore` minus the model fingerprint (no model at hand)."""
    try:
        _, sections = _read_sections(path)
        for name in ("meta", "state", "requests"):
            json.loads(sections[name])
    except SnapshotError as e:
        return [str(e)]
    except ValueError as e:
        return [f"{path}: undecodable section payload: {e}"]
    return []


def inspect(path: str) -> dict:
    """Manifest + decoded summary for `cli snapshot inspect`."""
    problems = verify(path)
    out: dict = {"path": path, "valid": not problems,
                 "problems": problems}
    if problems:
        return out
    manifest, sections = _read_sections(path)
    meta = json.loads(sections["meta"])
    requests = json.loads(sections["requests"])
    out.update({
        "version": manifest["version"],
        "shards": manifest.get("shards", 1),
        "sections": manifest["sections"],
        "nbytes": os.path.getsize(path),
        "step": meta["step"],
        "model": meta["model"],
        "config": meta["config"],
        "requests": [
            {"request_id": r["request_id"], "queue": r["queue"],
             "state": r["state"],
             "output_tokens": len(r["output_tokens"]),
             # page count, not page ids: ids are engine-local and
             # meaningless to whoever reads the report (pre-PR-19
             # snapshots always carry the key, so no .get needed)
             "pages": len(r["pages"])}
            for r in requests
        ],
    })
    return out


def restore(path: str, model, params, *,
            on_token=None, on_finish=None,
            on_timeout=None) -> ServingEngine:
    """Reconstruct an engine whose subsequent outputs are byte-identical
    to the snapshotted one's.  Raises `SnapshotCorruptError` on any
    validation failure (the caller's cue to fall back cold), and the
    engine's `RecurrentStateUnsupportedError` for a model with
    recurrent layers (no snapshot of one can exist)."""
    require_pages_only(model, "a snapshot")
    manifest, sections = _read_sections(path)
    try:
        meta = json.loads(sections["meta"])
        state = json.loads(sections["state"])
        requests = json.loads(sections["requests"])
    except ValueError as e:
        raise _corrupt(path, f"undecodable section payload: {e}")
    try:
        fp = model_fingerprint(model)
        if meta["model"] != fp:
            raise _corrupt(
                path,
                f"model fingerprint mismatch: snapshot "
                f"{meta['model']}, engine {fp}",
            )
        cfg = dict(meta["config"])
        if cfg.get("cache_dtype") is not None:
            cfg["cache_dtype"] = _np_dtype(cfg["cache_dtype"])
        config = EngineConfig(**cfg)
        try:
            engine = ServingEngine(model, params, config,
                                   on_token=on_token,
                                   on_finish=on_finish,
                                   on_timeout=on_timeout)
        except MeshConfigError as e:
            # the snapshot itself is fine — this HOST can't provide
            # the mesh geometry it was cut on.  Plain SnapshotError
            # (not ...Corrupt...) so recovery still falls back cold
            # without counting the file as damaged.
            raise SnapshotError(
                f"{path}: snapshot needs mesh geometry this host "
                f"cannot provide: {e}"
            )
        dtype = _np_dtype(meta["pool_dtype"])
        shape = tuple(meta["pool_shape"])
        n_arrays = 2 * model.depth
        nb = int(np.prod(shape)) * dtype.itemsize
        shards = manifest.get("shards", 1)
        if shape[1] % shards:
            raise _corrupt(
                path,
                f"pool head dim {shape[1]} not divisible by "
                f"{shards} shard section(s)",
            )
        # each pools.<s> section holds every per-layer array's slice
        # of 1/shards of the KV heads; reassembly concatenates the
        # slices back along the head dim (axis 1)
        slice_nb = nb // shards
        slice_shape = (shape[0], shape[1] // shards, *shape[2:])
        parts: list[list[np.ndarray]] = [[] for _ in range(n_arrays)]
        for name in _pool_section_names(shards):
            payload = sections[name]
            if len(payload) != n_arrays * slice_nb:
                raise _corrupt(
                    path,
                    f"section {name!r} holds {len(payload)} bytes, "
                    f"expected {n_arrays * slice_nb}",
                )
            for i in range(n_arrays):
                parts[i].append(np.frombuffer(
                    payload[i * slice_nb:(i + 1) * slice_nb],
                    dtype=dtype).reshape(slice_shape))
        engine.set_page_pools(
            [p[0] if shards == 1 else np.concatenate(p, axis=1)
             for p in parts])

        engine.pool._free = [int(p) for p in state["free"]]
        engine.pool._refs = [int(r) for r in state["refs"]]
        alloc = engine.allocator
        alloc.watermark_pages = state["watermark_pages"]
        counters = state["counters"]
        alloc.prefix_hits = counters["prefix_hits"]
        alloc.prefix_misses = counters["prefix_misses"]
        alloc.prefix_hit_tokens = counters["prefix_hit_tokens"]
        alloc.prefix_evictions = counters["prefix_evictions"]
        alloc._prefix = {}
        for e in state["prefix"]:
            key = tuple(int(t) for t in e["key"])
            alloc._prefix[key] = _PrefixEntry(
                key=key,
                page=int(e["page"]),
                parent=(tuple(int(t) for t in e["parent"])
                        if e["parent"] is not None else None),
                children={tuple(int(t) for t in c)
                          for c in e["children"]},
                last_use=int(e["last_use"]),
            )
        sched_state = state["scheduler"]
        engine.scheduler.token_budget = sched_state["token_budget"]
        engine.scheduler.prefix_admission = \
            sched_state["prefix_admission"]
        engine.scheduler.num_preemptions = \
            sched_state["num_preemptions"]

        for d in requests:
            req = _request_from_dict(d)
            if d["queue"] == "waiting":
                engine.scheduler.waiting.append(req)
            else:
                engine.scheduler.running.append(req)
            # splice the snapshotted trace tail back into the live
            # store (idempotent: in-process restores already hold it)
            _trace.adopt(req.request_id, d.get("trace", []))
            # wall-clock bookkeeping restarts at restore (TTFT history
            # is observability, not contract)
            engine._wall[req.request_id] = {"added": time.perf_counter()}
            if req.sampling.temperature > 0.0 and req.output_tokens:
                # arithmetic RNG-chain reconstruction: one split per
                # sampled token, the resume_request contract
                key = jax.random.PRNGKey(req.sampling.seed)
                for _ in range(len(req.output_tokens)):
                    key, _ = jax.random.split(key)
                engine._rng_keys[req.request_id] = key
        engine._step = meta["step"]
        engine._next_seq = meta["next_seq"]
    except (KeyError, TypeError, ValueError) as e:
        # CRC-valid but structurally unusable (e.g. a snapshot written
        # by a buggy/foreign writer): still a typed refusal, not a crash
        raise _corrupt(path, f"malformed snapshot contents: {e!r}")
    _RESTORES.inc(outcome="ok")
    return engine


def recover_engine(model, params, directory: str, *,
                   on_token=None, on_finish=None,
                   on_timeout=None) -> tuple[ServingEngine, dict]:
    """Warm recovery: newest valid snapshot + journal replay.

    Scans ``directory`` newest-first, restores the first snapshot that
    validates, then chain-replays every journal at or after that step
    (rotation closes a journal only after the *next* snapshot lands,
    so the chain is complete even when the newest snapshot is the
    corrupt one).  Raises `SnapshotCorruptError` when nothing under
    ``directory`` validates — the caller's cue for the cold path."""
    snaps = list_snapshots(directory)
    skipped: list[dict] = []
    engine = None
    chosen = -1
    chosen_path = None
    for step, path in reversed(snaps):
        try:
            engine = restore(path, model, params, on_token=on_token,
                             on_finish=on_finish, on_timeout=on_timeout)
            chosen, chosen_path = step, path
            break
        except SnapshotError as e:
            skipped.append({"path": path, "error": str(e)})
    if engine is None:
        _RESTORES.inc(outcome="cold_fallback")
        raise SnapshotCorruptError(
            f"{directory}: no valid snapshot among {len(snaps)} "
            f"candidate(s): "
            + (skipped[-1]["error"] if skipped else "directory empty")
        )
    events: list[dict] = []
    for jstep, jpath in list_journals(directory):
        if jstep >= chosen:
            events.extend(Journal.read(jpath))
    replayed = apply_journal(engine, events)
    _RESTORES.inc(outcome="warm")
    return engine, {
        "snapshot_step": chosen,
        "snapshot_path": chosen_path,
        "journal_events": replayed,
        "skipped": skipped,
    }


class SnapshotManager:
    """Periodic snapshotting + journal rotation for one engine.

    Wraps ``engine.step`` by instance-attribute assignment (the same
    composition pattern as `chaos.FaultInjector`, so the two stack) to
    snapshot every ``every`` steps, attaches the write-ahead
    `Journal`, and writes a genesis snapshot at attach so recovery
    always has a base.  Keeps the ``keep`` newest snapshots plus every
    journal needed to chain-replay from the oldest kept one.

    Attach starts a new INCARNATION: every ``snap-*``/``journal-*``
    (and torn ``.tmp``) left by a previous manager of this directory
    is deleted before the genesis lands.  The genesis is a full state
    cut, so those files are pure supersession debris — and because
    their names are keyed by step, leaving them would poison recovery:
    a dead incarnation's journal replays records the genesis already
    contains (duplicated tokens), and after a cold restart its
    higher-step snapshots would outrank the genesis and resurrect
    pre-restart state.  Clearing first keeps every crash window of
    attach safe: a kill before the genesis lands degrades to a cold
    recovery, never to wrong tokens.

    ``crash_next`` is the chaos crash-point: when armed, the next save
    dies "mid-write" — a partial ``.tmp`` file is left behind and the
    final path is never touched, proving the atomic-replace discipline
    (recovery must not even notice).
    """

    def __init__(self, engine: ServingEngine, directory: str, *,
                 every: int = 16, keep: int = 3):
        if every < 1 or keep < 1:
            raise SnapshotError(
                f"SnapshotManager needs every>=1, keep>=1 "
                f"(got every={every}, keep={keep})"
            )
        engine.require_pages_only("SnapshotManager")
        os.makedirs(directory, exist_ok=True)
        self.engine = engine
        self.directory = directory
        self.every = every
        self.keep = keep
        self.crash_next = False
        self.saves = 0
        self.last_snapshot_step = -1
        self._inner_step = engine.step
        engine.step = self._step
        self._clear_stale()
        # the genesis snapshot() below owns journal creation (rotation
        # after the snapshot lands), so nothing is journaled — and the
        # lag gauge reads 0 — until recovery has a base to extend
        engine.journal = None
        self.snapshot()

    def _clear_stale(self) -> None:
        """Delete a dead incarnation's files (see class docstring)."""
        stale = [p for _, p in list_snapshots(self.directory)]
        stale += [p for _, p in list_journals(self.directory)]
        stale += [os.path.join(self.directory, name)
                  for name in os.listdir(self.directory)
                  if name.endswith(".tmp")]
        for path in stale:
            try:
                os.unlink(path)
            except OSError:
                pass

    def _step(self):
        metrics = self._inner_step()
        if self.engine.current_step % self.every == 0:
            self.snapshot()
        return metrics

    def snapshot(self) -> str | None:
        """Take one snapshot now; returns its path (None when the
        armed crash-point fired instead)."""
        engine = self.engine
        step = engine.current_step
        if obs.enabled():
            _JOURNAL_LAG.set(float(engine.journal.records_written)
                             if engine.journal is not None else 0.0)
        if self.crash_next:
            self.crash_next = False
            blob = serialize(engine)
            fd, tmp = tempfile.mkstemp(dir=self.directory,
                                       suffix=".tmp")
            # deliberately torn: simulates the process dying mid-write;
            # the final snapshot path is never touched
            with os.fdopen(fd, "wb") as f:  # atp: disable=ATP701
                f.write(blob[: max(1, len(blob) // 2)])
            return None
        path = snapshot_path(self.directory, step)
        save(engine, path)
        # rotate AFTER the snapshot lands (the genesis call creates the
        # incarnation's first journal): the outgoing journal file stays
        # complete on disk, so replay can chain from an older snapshot
        # if this one is later damaged.  Close the outgoing handle —
        # the file is immutable history from here on.
        if engine.journal is not None:
            engine.journal.close()
        engine.journal = Journal(journal_path(self.directory, step),
                                 snapshot_step=step)
        self.saves += 1
        self.last_snapshot_step = step
        self._prune()
        return path

    def _prune(self) -> None:
        snaps = list_snapshots(self.directory)
        drop = snaps[:-self.keep] if len(snaps) > self.keep else []
        for _, path in drop:
            try:
                os.unlink(path)
            except OSError:
                pass
        oldest_kept = snaps[-self.keep][0] if len(snaps) >= self.keep \
            else (snaps[0][0] if snaps else 0)
        for jstep, jpath in list_journals(self.directory):
            if jstep < oldest_kept:
                try:
                    os.unlink(jpath)
                except OSError:
                    pass

    def detach(self) -> None:
        """Unhook from the engine: step unwrapped, the journal's
        append handle closed and dropped.  `ReplicaHandle.kill` calls
        this so a kill/restart storm cannot leak file descriptors
        (pinned by the ResourceWarning test in tests/
        test_supervisor.py).  Idempotent."""
        self.engine.step = self._inner_step
        if self.engine.journal is not None:
            self.engine.journal.close()
        self.engine.journal = None
