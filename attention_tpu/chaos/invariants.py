"""Engine invariant checkers: what must hold no matter what faults fly.

Each checker returns a list of human-readable violation strings (empty
= invariant holds) and ticks the shared ``chaos.invariant.violations``
counter, so a fault campaign's verdict is observable through the obs
registry like every other subsystem.

The four invariants the fault harness pins (ISSUE 4):

1. **Page/refcount conservation** — the `PagePool` free list and
   refcounts stay mutually consistent, and a drained engine holds
   pages ONLY through its prefix cache (each cached page at refcount
   exactly 1: the cache's own reference).
2. **Token parity** — requests a fault plan did not touch produce
   byte-identical token streams to a fault-free run of the same trace
   (faults are isolated: preemption storms and a neighbor's corrupted
   pages must not leak into anyone else's sampling).
3. **Termination** — the engine drains every trace within a step
   bound; no fault plan may wedge the step loop.
4. **Typed errors** — anything that does escape the step loop is one
   of the typed serving errors (`OutOfPagesError`,
   `PageAccountingError`, and the resilience trio
   `DeadlineExceededError` / `ReplicaDeadError` / `RequestShedError`),
   never a bare RuntimeError three layers down.

The multi-replica front end (ISSUE 6) adds two more:

5. **No request lost** — every request submitted to a
   `ServingFrontend` reaches exactly one of the four terminal states
   (FINISHED / CANCELLED / TIMED_OUT / SHED), finished streams are
   complete, and shed/timed-out requests carry their typed cause.
6. **Replica conservation** — page/refcount conservation (and, once
   drained, prefix-cache-only quiescence) holds on every SURVIVING
   replica of a storm; a neighbor's death may not corrupt anyone
   else's pool.

The durability layer (ISSUE 9) adds two more:

7. **Snapshot round trip** — ``restore(save(engine))`` is
   state-identical: the deterministic serialization fingerprint
   (`engine.snapshot.state_fingerprint`) of the restored engine equals
   the original's, so the restored engine's future outputs are
   byte-identical by construction.
8. **Warm-recovery parity** — a replica recovered warm (snapshot +
   journal replay) finishes every stream token-identical to the
   fault-free run; crash points (kill mid-snapshot, bit-flipped
   sections, torn journal tails) may cost warmth, never tokens.

The gray-failure layer (ISSUE 10) adds three more:

9.  **Migration token parity** — every stream live-migrated off a
    SUSPECT replica (and every stream finished on a promoted standby)
    is token-identical to the fault-free run; migration costs a
    re-prefill, never a token.
10. **No double serve** — after a migration cut, the SOURCE replica
    never emits another token for the moved request (unless a later
    legitimate re-admission hands it back).  Checked against the
    per-token emitter attribution the front end records.
11. **Supervisor consistency** — once a replica's verdict is
    SUSPECT/DEGRADED/DEAD, no NEW admission routes to it until a
    recovery or restart verdict.  Checked by replaying the front
    end's unified event log (append order = global order, so
    within-tick phase ordering is handled by construction).

The observability layer (ISSUE 12) adds one more:

12. **Trace completeness** — every submitted request owns exactly one
    well-formed `obs.trace` chain: it starts with ``submitted``, ends
    with exactly one terminal matching the front end's terminal state,
    retry attempts strictly increase, each migration hop lands on its
    recorded destination, and no chain exists for an unknown request.
    Fault campaigns run inside ``obs.trace.capture()`` so the chains
    exist even with telemetry disabled.

The forecasting layer (ISSUE 14) adds one more:

13. **Forecast determinism** — when the front end ran with forecasting
    enabled (campaigns do, see `default_frontend_config`), the
    observatory report is a pure function of the recorded samples:
    computing it twice yields byte-identical canonical JSON, every
    number in it is finite, and rebuilding it from its own embedded
    samples (`obs.capacity.rebuild_report`) reproduces it exactly —
    under kill, gray, and crash storms alike.

The global prefix tier (ISSUE 17) adds one more:

14. **Prefix import parity** — with a fleet prefix store attached
    (`frontend.prefix_store`), every FINISHED stream is
    token-identical to the fault-free no-store run, no matter which
    replica imported its prefix or how the store was poisoned: a
    corrupt record must surface as `PrefixStoreCorruptError` handling
    (count + discard + cold re-prefill), never as wrong tokens.  The
    store's own byte accounting must also balance.  A no-op on a
    storeless front end.

The incident layer (ISSUE 18) adds one more:

15. **Incident completeness** — the postmortem ledger balances: every
    fault a campaign ACTUALLY injected dumped exactly one incident
    bundle naming its kind and tick, every fault-cause bundle traces
    back to a real injection, every detector-cause bundle to a
    recorded anomaly firing, and no bundle carries an unknown cause.
    The campaign runners attach a throwaway ``incident_dir`` to every
    plan, so the audit runs storm after storm with telemetry off.

The disaggregation layer (ISSUE 19) adds one more:

16. **Actuation ledger** — every fleet pool-size change balances
    against the flight recorder: each `fleet.ledger.ActuationRecord`
    the front end executed maps to exactly one ``scale_up`` /
    ``scale_down`` ring event with the same tick, pool, replica, and
    recorded cause (a closed alphabet), and no pool flaps — opposite
    actuations on one pool are separated by at least the policy's
    cooldown window (chaos ``demote_storm`` forced demotions are
    exempt: the storm IS the flap).  A no-op on a front end that
    never actuated.
"""

from __future__ import annotations

import os
import shutil
import tempfile
from typing import Iterable, Mapping

from attention_tpu import obs
from attention_tpu.engine.errors import (
    DeadlineExceededError,
    PrefixLeaseError,
    PrefixStoreCorruptError,
    ReplicaDeadError,
    ReplicaStateError,
    RequestShedError,
    SnapshotCorruptError,
    SnapshotError,
    StepInterruptedError,
)
from attention_tpu.ops.paged import OutOfPagesError, PageAccountingError

_VIOLATIONS = obs.counter("chaos.invariant.violations",
                          "invariant-checker violations, by invariant")

#: everything that may legitimately escape a serving step/tick loop
TYPED_ERRORS = (OutOfPagesError, PageAccountingError,
                DeadlineExceededError, ReplicaDeadError,
                RequestShedError, SnapshotError, SnapshotCorruptError,
                ReplicaStateError, StepInterruptedError,
                PrefixStoreCorruptError, PrefixLeaseError)


def _report(invariant: str, problems: list[str]) -> list[str]:
    for _ in problems:
        _VIOLATIONS.inc(invariant=invariant)
    return [f"{invariant}: {p}" for p in problems]


def pool_accounting_violations(pool) -> list[str]:
    """Free-list/refcount consistency of one `PagePool`: every page is
    either free (refcount 0, on the free list exactly once) or held
    (refcount > 0, not on the free list)."""
    problems = []
    free = pool._free
    refs = pool._refs
    if len(set(free)) != len(free):
        problems.append("free list holds duplicate page ids")
    free_set = set(free)
    for page, r in enumerate(refs):
        if r < 0:
            problems.append(f"page {page} refcount {r} < 0")
        if r == 0 and page not in free_set:
            problems.append(f"page {page} refcount 0 but not free")
        if r > 0 and page in free_set:
            problems.append(f"page {page} refcount {r} but on free list")
    if pool.free_pages + sum(1 for r in refs if r > 0) != pool.num_pages:
        problems.append(
            f"free {pool.free_pages} + held "
            f"{sum(1 for r in refs if r > 0)} != {pool.num_pages}"
        )
    return _report("page_conservation", problems)


def engine_quiescence_violations(engine) -> list[str]:
    """A drained engine (run() returned) must hold pages only through
    its prefix cache — one cache reference each, nothing leaked by a
    finished, preempted, or cancelled request."""
    problems = []
    if engine.scheduler.waiting:
        problems.append(
            f"{len(engine.scheduler.waiting)} request(s) still waiting")
    if engine.scheduler.running:
        problems.append(
            f"{len(engine.scheduler.running)} request(s) still running")
    alloc = engine.allocator
    cached = {e.page for e in alloc._prefix.values()}
    if len(cached) != len(alloc._prefix):
        problems.append("prefix cache entries share a physical page")
    for page in range(engine.pool.num_pages):
        r = engine.pool.refcount(page)
        if r == 0:
            continue
        if page not in cached:
            problems.append(f"page {page} held (refcount {r}) but not "
                            "in the prefix cache: leaked")
        elif r != 1:
            problems.append(f"cached page {page} refcount {r} != 1 "
                            "after drain")
    return _report("page_conservation", problems)


def token_parity_violations(
    baseline: Mapping[str, list[int]],
    observed: Mapping[str, list[int]],
    *,
    exclude: Iterable[str] = (),
) -> list[str]:
    """Uninjected requests must match the fault-free run exactly."""
    excluded = set(exclude)
    problems = []
    for rid, want in baseline.items():
        if rid in excluded:
            continue
        got = observed.get(rid)
        if got != want:
            problems.append(
                f"request {rid}: tokens diverged from the fault-free "
                f"run (got {got}, want {want})"
            )
    return _report("token_parity", problems)


def termination_violations(finished: bool, error: BaseException | None,
                           *, max_steps: int) -> list[str]:
    """The run must drain (or fail TYPED) within the step bound."""
    problems = []
    if not finished and error is None:
        problems.append(f"engine did not drain within {max_steps} steps")
    if isinstance(error, RuntimeError) and not isinstance(
            error, TYPED_ERRORS):
        # engine.run's max_steps guard surfaces as RuntimeError: a wedge
        problems.append(f"step loop wedged: {error}")
    return _report("termination", problems)


def typed_error_violations(error: BaseException | None) -> list[str]:
    """Anything surfacing out of the step loop must be a typed
    serving error (capacity/accounting or the resilience trio)."""
    if error is None or isinstance(error, TYPED_ERRORS):
        return []
    return _report(
        "typed_errors",
        [f"untyped {type(error).__name__} escaped the engine: {error}"],
    )


# ------------------------------------------------- front-end invariants


def no_request_lost_violations(frontend) -> list[str]:
    """ISSUE 6 headline: every request submitted to a
    `ServingFrontend` terminates in exactly one of FINISHED /
    CANCELLED / TIMED_OUT / SHED — no storm may drop a request on the
    floor or leave it limping in a non-terminal state after the run
    drains.  Terminal bookkeeping must be consistent: finished streams
    complete (max_tokens or stop token), shed and timed-out requests
    carry their typed cause."""
    from attention_tpu.frontend.frontend import FrontendRequestState

    problems = []
    for fr in sorted(frontend.requests.values(), key=lambda f: f.seq):
        if not fr.is_terminal:
            problems.append(
                f"request {fr.request_id} lost: non-terminal state "
                f"{fr.state.name} after drain"
            )
            continue
        if fr.state is FrontendRequestState.FINISHED:
            stopped = (fr.sampling.stop_token is not None
                       and fr.sampling.stop_token in fr.tokens)
            if len(fr.tokens) != fr.sampling.max_tokens and not stopped:
                problems.append(
                    f"request {fr.request_id} FINISHED with "
                    f"{len(fr.tokens)}/{fr.sampling.max_tokens} tokens "
                    "and no stop token"
                )
        elif fr.state is FrontendRequestState.SHED:
            if not isinstance(fr.error, RequestShedError):
                problems.append(
                    f"request {fr.request_id} SHED without a "
                    f"RequestShedError cause (got "
                    f"{type(fr.error).__name__})"
                )
        elif fr.state is FrontendRequestState.TIMED_OUT:
            if not isinstance(fr.error, DeadlineExceededError):
                problems.append(
                    f"request {fr.request_id} TIMED_OUT without a "
                    f"DeadlineExceededError cause (got "
                    f"{type(fr.error).__name__})"
                )
    for name, queue in (("pending", frontend._pending),
                        ("retry", frontend._retry)):
        if queue:
            problems.append(
                f"{len(queue)} request(s) stranded on the front-end "
                f"{name} queue after drain"
            )
    return _report("request_conservation", problems)


def replica_conservation_violations(frontend, *,
                                    drained: bool) -> list[str]:
    """Page/refcount conservation on every SURVIVING replica; after a
    drained run each must also be quiescent (pages held only by its
    prefix cache).  Dead replicas are exempt — their pools died with
    them; what matters is that a neighbor's death never corrupts a
    survivor's accounting."""
    problems: list[str] = []
    for handle in frontend.replicas:
        if not handle.alive:
            continue
        inner = pool_accounting_violations(handle.engine.pool)
        if drained:
            inner += engine_quiescence_violations(handle.engine)
        problems += [f"{handle.replica_id}: {p}" for p in inner]
    return problems


def migration_parity_violations(
    frontend,
    baseline: Mapping[str, list[int]],
) -> list[str]:
    """Invariant 9: live-migrated streams match the fault-free run.

    Every request the migration machinery actually MOVED (a
    `MigrationRecord` with a destination) that went on to FINISH must
    carry exactly the baseline's tokens — the cut preserved the
    streamed prefix and the RNG chain, so divergence means the resume
    path dropped or resampled something."""
    from attention_tpu.frontend.frontend import FrontendRequestState

    problems = []
    moved = sorted({m.request_id
                    for m in getattr(frontend, "migrations", [])
                    if m.dest is not None})
    for rid in moved:
        fr = frontend.requests.get(rid)
        if fr is None or fr.state is not FrontendRequestState.FINISHED:
            continue
        if list(fr.tokens) != list(baseline.get(rid, [])):
            problems.append(
                f"request {rid}: migrated stream {list(fr.tokens)} != "
                f"fault-free {list(baseline.get(rid, []))}"
            )
    return _report("migration_parity", problems)


def prefix_import_parity_violations(
    frontend,
    baseline: Mapping[str, list[int]],
) -> list[str]:
    """Invariant 14: the fleet prefix store never changes tokens.

    Every FINISHED stream of a store-enabled front end must be
    token-identical to the fault-free NO-STORE run of the same trace —
    whether its prefix was prefilled cold, imported from the store, or
    re-prefilled after a poisoned record was rejected.  Wrong tokens
    are never an acceptable corruption outcome; the only legal
    responses to a bad record are the typed `PrefixStoreCorruptError`
    handling path (count + discard + cold prefill) upstream of here.
    Also pins the store's own byte accounting (``total_bytes`` equals
    the sum of live entry sizes — an eviction storm must not leak
    phantom bytes into the budget).  A no-op when the front end runs
    storeless."""
    from attention_tpu.frontend.frontend import FrontendRequestState

    store = getattr(frontend, "prefix_store", None)
    if store is None:
        return []
    problems = []
    for fr in sorted(frontend.requests.values(), key=lambda f: f.seq):
        if fr.state is not FrontendRequestState.FINISHED:
            continue
        want = baseline.get(fr.request_id)
        if want is None:
            continue
        if list(fr.tokens) != list(want):
            problems.append(
                f"request {fr.request_id}: store-enabled stream "
                f"{list(fr.tokens)} != no-store fault-free "
                f"{list(want)}"
            )
    live_bytes = sum(e.nbytes for e in store._entries.values())
    if live_bytes != store.total_bytes:
        problems.append(
            f"store byte accounting drifted: entries hold "
            f"{live_bytes} bytes, budget ledger says "
            f"{store.total_bytes}"
        )
    for name, value in sorted(store.counts.items()):
        if value < 0:
            problems.append(f"store counter {name} negative: {value}")
    return _report("prefix_import_parity", problems)


def no_double_serve_violations(frontend) -> list[str]:
    """Invariant 10: after a migration cut the source replica never
    emits another token for the moved request.

    Evidence: ``FrontendRequest.emitters`` (which engine emitted each
    token, recorded at stream time) against the front end's
    `MigrationRecord`s and admission history.  A token from the source
    at an index >= the cut position is a double serve — the request
    lived on two engines at once — unless a LATER admit event
    legitimately handed the request back to the source (retry or
    warm-restore)."""
    problems = []
    admits: dict[str, list[tuple[int, str]]] = {}
    for ev in getattr(frontend, "events_log", []):
        if ev[0] == "admit":
            admits.setdefault(ev[2], []).append((ev[1], ev[3]))
    for m in getattr(frontend, "migrations", []):
        if m.dest is None:
            continue
        fr = frontend.requests.get(m.request_id)
        if fr is None:
            continue
        seq = admits.get(m.request_id, [])
        # locate the cut's own admission (at most one drain per
        # request per tick, so (tick, dest) pins it exactly); any
        # admit to the source AFTER it makes source tokens legal again
        cut_idx = next((i for i, (tk, rid) in enumerate(seq)
                        if tk == m.tick and rid == m.dest),
                       len(seq) - 1)
        if any(rid == m.source for _, rid in seq[cut_idx + 1:]):
            continue
        offenders = [i for i, rid in enumerate(fr.emitters)
                     if i >= m.tokens_at_cut and rid == m.source]
        if offenders:
            problems.append(
                f"request {m.request_id}: source {m.source} emitted "
                f"token(s) at index {offenders[:3]} after the cut at "
                f"{m.tokens_at_cut} (tick {m.tick})"
            )
    return _report("no_double_serve", problems)


def supervisor_consistency_violations(frontend) -> list[str]:
    """Invariant 11: no admission to a non-HEALTHY replica.

    Replays the front end's unified event log in append order —
    verdict events move a replica's supervisor state, admit events
    must only ever name a replica currently HEALTHY (the default for
    never-judged replicas).  Because the log is appended in the exact
    order actions happened, within-tick ordering (kills before phases,
    verdicts after admissions) needs no special cases."""
    problems = []
    state: dict[str, str] = {}
    for ev in getattr(frontend, "events_log", []):
        if ev[0] == "verdict":
            _, _, rid, _, new, _ = ev
            state[rid] = new
        elif ev[0] == "admit":
            _, tick, req_id, rid = ev
            if state.get(rid, "healthy") != "healthy":
                problems.append(
                    f"request {req_id} admitted to {rid} at tick "
                    f"{tick} while its verdict was {state[rid]}"
                )
    return _report("supervisor_consistency", problems)


def trace_completeness_violations(frontend) -> list[str]:
    """Invariant 12: one well-formed trace chain per submitted request.

    Reads the live `obs.trace` store (the campaign runner wraps the
    whole plan in ``trace.capture()``); an empty store means tracing
    was off for the run and there is nothing to judge."""
    from attention_tpu.obs import trace as _trace
    from attention_tpu.obs.naming import TRACE_TERMINAL_EVENTS

    chains = _trace.all_traces()
    if not chains:
        return []
    problems = []
    known = set(frontend.requests)
    for rid in sorted(set(chains) - known):
        problems.append(f"orphan chain for unknown request {rid}")
    for rid in sorted(known):
        fr = frontend.requests[rid]
        evs = chains.get(rid, [])
        if not evs:
            problems.append(f"request {rid}: no trace chain recorded")
            continue
        names = [e["event"] for e in evs]
        if names[0] != "submitted":
            problems.append(
                f"request {rid}: chain starts with {names[0]!r}, "
                "not 'submitted'")
        terms = [n for n in names if n in TRACE_TERMINAL_EVENTS]
        if fr.is_terminal:
            if len(terms) != 1:
                problems.append(
                    f"request {rid}: {len(terms)} terminal events "
                    f"{terms} (want exactly one)")
            elif names[-1] != terms[0]:
                problems.append(
                    f"request {rid}: terminal {terms[0]!r} is not the "
                    "last event")
            elif terms[0] != fr.state.value:
                problems.append(
                    f"request {rid}: trace terminal {terms[0]!r} != "
                    f"front-end state {fr.state.value!r}")
        elif terms:
            problems.append(
                f"request {rid}: live request carries terminal "
                f"{terms[0]!r}")
        attempts = [e.get("attempt") for e in evs
                    if e["event"] == "retried"]
        if (any(a is None for a in attempts)
                or any(b <= a for a, b in zip(attempts, attempts[1:]))):
            problems.append(
                f"request {rid}: retry attempts {attempts} not "
                "strictly increasing")
        # hop pairing: a retried hop leaves the replica, so the next
        # placement-class event must be a re-placement (or another
        # backoff round / a terminal) — never an engine-side event on
        # a replica the chain never re-entered; a migrated hop must
        # land exactly on its recorded destination
        placement = {"routed", "warm_adopted", "retried", "migrated"}
        for i, ev in enumerate(evs):
            if ev["event"] == "retried":
                nxt = names[i + 1:i + 2]
                if nxt and nxt[0] not in placement \
                        and nxt[0] not in TRACE_TERMINAL_EVENTS:
                    problems.append(
                        f"request {rid}: {nxt[0]!r} follows a retried "
                        "hop without a re-placement")
            elif ev["event"] == "migrated":
                if ev.get("replica") != ev.get("dest"):
                    problems.append(
                        f"request {rid}: migrated hop stamped on "
                        f"{ev.get('replica')!r}, dest was "
                        f"{ev.get('dest')!r}")
    return _report("trace_completeness", problems)


def forecast_determinism_violations(frontend) -> list[str]:
    """Invariant 13: the observatory report is reproducible.

    Three checks over the same front end: compute-twice byte parity,
    no non-finite numbers, and dump-and-rebuild byte parity (the
    ``cli obs forecast`` contract).  A front end constructed without a
    `ForecastPolicy` has nothing to judge."""
    import json

    if getattr(frontend, "forecast", None) is None:
        return []
    from attention_tpu.obs import capacity as _capacity

    problems: list[str] = []
    a = json.dumps(frontend.forecast_report(), sort_keys=True)
    b = json.dumps(frontend.forecast_report(), sort_keys=True)
    if a != b:
        problems.append(
            "forecast report not reproducible: two computations over "
            "the same samples differ")
    if "NaN" in a or "Infinity" in a:
        problems.append("forecast report contains non-finite numbers")
    rebuilt = _capacity.rebuild_report(json.loads(a))
    if json.dumps(rebuilt, sort_keys=True) != a:
        problems.append(
            "forecast report does not rebuild byte-identically from "
            "its own embedded samples")
    return _report("forecast_determinism", problems)


def incident_completeness_violations(frontend, injector) -> list[str]:
    """Invariant 15: the incident ledger balances.

    Reads the bundles the run dumped under the front end's
    ``incident_dir`` straight from disk (the postmortem contract is
    that the bundle alone suffices) and matches the fault-cause ones
    one-to-one against the injector's ``fired`` ledger; detector-cause
    bundles must each trace to a recorded anomaly firing.  A no-op on
    a front end constructed without a postmortem writer."""
    pm = getattr(frontend, "postmortem", None)
    if pm is None:
        return []
    from attention_tpu.obs import postmortem as _postmortem

    problems: list[str] = []
    fault_bundles: set[tuple[str, int]] = set()
    detector_bundles: list[tuple[str, str, int]] = []
    for bundle_dir in _postmortem.list_incidents(pm.out_dir):
        b = _postmortem.load_incident(bundle_dir)
        meta = b["meta"]
        cause = meta.get("cause")
        detail = meta.get("detail", {})
        if cause not in _postmortem.INCIDENT_CAUSES:
            problems.append(
                f"bundle {b['name']}: unknown cause {cause!r}")
        elif cause == "fault":
            fault_bundles.add(
                (str(detail.get("kind")), int(meta["tick"])))
        elif cause == "detector":
            detector_bundles.append(
                (b["name"], str(detail.get("detector")),
                 int(meta["tick"])))
    fired = {(kind, int(tick))
             for kind, tick in getattr(injector, "fired", [])}
    for kind, tick in sorted(fired - fault_bundles):
        problems.append(
            f"injected fault {kind!r} at tick {tick} left no "
            "incident bundle")
    for kind, tick in sorted(fault_bundles - fired):
        problems.append(
            f"bundle names fault {kind!r} at tick {tick} that was "
            "never injected")
    tracker = getattr(frontend, "anomaly", None)
    firings = ({(f["detector"], int(f["tick"]))
                for f in tracker.firings} if tracker is not None
               else set())
    for name, detector, tick in detector_bundles:
        if (detector, tick) not in firings:
            problems.append(
                f"bundle {name} names detector {detector!r} at tick "
                f"{tick} with no recorded firing")
    if pm.suppressed:
        problems.append(
            f"{pm.suppressed} incident(s) suppressed by the writer's "
            f"bundle limit ({pm.limit})")
    return _report("incident_completeness", problems)


def snapshot_roundtrip_violations(engine) -> list[str]:
    """Invariant 7: ``restore(save(engine))`` is state-identical.

    Saves the live engine to a throwaway file, restores it, and
    compares deterministic state fingerprints — equal fingerprints
    mean the restored engine's serialization (pools, page accounting,
    prefix index, request queues, RNG positions) is byte-identical,
    so its future outputs are too.  Any `SnapshotError` on a
    freshly-written snapshot is itself a violation.

    On a mesh engine (``mesh_shards`` > 1) the snapshot must also
    carry the per-shard layout: the manifest's ``shards`` count equal
    to the engine's, and one ``pools.<s>`` section per shard (each
    with its own CRC) — a single-blob pool section from a sharded
    engine would silently lose per-shard damage detection."""
    from attention_tpu.engine import snapshot as snap

    problems: list[str] = []
    tmpdir = tempfile.mkdtemp(prefix="atp_snap_inv_")
    try:
        path = os.path.join(tmpdir, "snap-00000000.atpsnap")
        snap.save(engine, path)
        info = snap.inspect(path)
        want_shards = getattr(engine.config, "mesh_shards", 0) or 1
        if info.get("shards") != want_shards:
            problems.append(
                f"manifest shards {info.get('shards')} != engine "
                f"mesh_shards {want_shards}"
            )
        pool_names = sorted(
            s["name"] for s in info.get("sections", [])
            if s["name"] == "pools" or s["name"].startswith("pools.")
        )
        want_names = sorted(snap._pool_section_names(want_shards))
        if pool_names != want_names:
            problems.append(
                f"pool sections {pool_names} != expected {want_names}"
            )
        clone = snap.restore(path, engine.model, engine.params)
        a = snap.state_fingerprint(engine)
        b = snap.state_fingerprint(clone)
        if a != b:
            problems.append(
                f"restore(save(engine)) fingerprint mismatch: "
                f"{a[:16]}... != {b[:16]}..."
            )
    except SnapshotError as e:
        problems.append(f"fresh snapshot failed validation: {e}")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return _report("snapshot_roundtrip", problems)


def warm_recovery_parity_violations(
    baseline: Mapping[str, list[int]],
    observed: Mapping[str, list[int]],
    finished: Iterable[str],
) -> list[str]:
    """Invariant 8: warm-recovered streams match the fault-free run.

    ``finished`` names the requests that reached FINISHED through the
    storm (kills, warm restarts, crash points included); each must
    carry exactly the fault-free baseline's token stream — warm
    recovery may change WHERE tokens are computed, never WHICH."""
    problems = []
    for rid in sorted(finished):
        if list(observed.get(rid, [])) != list(baseline.get(rid, [])):
            problems.append(
                f"request {rid}: recovered stream "
                f"{list(observed.get(rid, []))} != fault-free "
                f"{list(baseline.get(rid, []))}"
            )
    return _report("warm_recovery_parity", problems)


def actuation_ledger_violations(frontend) -> list[str]:
    """Invariant 16: the actuation ledger balances.

    Matches the front end's executed-resize ledger
    (`ServingFrontend.actuations`) one-to-one, in order, against the
    ``scale_up``/``scale_down`` records in the flight-recorder ring
    (same tick, pool, replica, cause), requires every cause to come
    from the closed `fleet.ledger.ACTUATION_CAUSES` alphabet, and
    checks the anti-flap guarantee: opposite actuations on one pool
    at least ``cooldown_ticks`` apart, chaos ``forced`` demotions
    exempt.  A no-op on a front end that never actuated (and on runs
    where the ring was not captured)."""
    from attention_tpu.obs import blackbox as _blackbox
    from attention_tpu.fleet.ledger import ACTUATION_CAUSES

    ledger = list(getattr(frontend, "actuations", None) or [])
    ring = [ev for ev in _blackbox.events()
            if ev["kind"] in ("scale_up", "scale_down")]
    if not ledger and not ring:
        return []
    problems: list[str] = []
    if len(ledger) != len(ring):
        problems.append(
            f"{len(ledger)} ledger actuation(s) vs {len(ring)} ring "
            f"scale event(s)")
    for rec, ev in zip(ledger, ring):
        got = (ev["kind"], ev["tick"], ev.get("pool"),
               ev.get("replica"), ev.get("cause"))
        want = (rec.kind, rec.tick, rec.pool, rec.replica_id,
                rec.cause)
        if got != want:
            problems.append(
                f"ledger {want} != ring {got}")
    for rec in ledger:
        if rec.cause not in ACTUATION_CAUSES:
            problems.append(
                f"actuation at tick {rec.tick} carries unknown cause "
                f"{rec.cause!r}")
        if rec.kind not in ("scale_up", "scale_down"):
            problems.append(
                f"actuation at tick {rec.tick} carries unknown kind "
                f"{rec.kind!r}")
    policy = getattr(frontend.config, "autoscaler", None)
    cooldown = policy.cooldown_ticks if policy is not None else 0
    last: dict[str, tuple[int, str]] = {}
    for rec in ledger:
        if rec.cause == "forced":
            continue
        prev = last.get(rec.pool)
        if (prev is not None and prev[1] != rec.kind
                and rec.tick - prev[0] < cooldown):
            problems.append(
                f"pool {rec.pool!r} flapped: {prev[1]} at tick "
                f"{prev[0]} then {rec.kind} at tick {rec.tick} "
                f"inside the {cooldown}-tick cooldown")
        last[rec.pool] = (rec.tick, rec.kind)
    return _report("actuation_ledger", problems)
