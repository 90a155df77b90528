"""Typed serving errors: the resilience half of the error taxonomy.

PR 2 introduced the capacity/accounting pair
(`attention_tpu.ops.paged.OutOfPagesError` / `PageAccountingError`);
the multi-replica front end (`attention_tpu.frontend`) adds the three
failure modes a *resilient* serving layer must distinguish:

* :class:`DeadlineExceededError` — a request's TTL expired.  Raised at
  admission when the deadline is already in the past; requests that
  expire mid-flight are not raised but transitioned to the terminal
  ``TIMED_OUT`` state (the step loop must keep serving everyone else).
* :class:`ReplicaDeadError` — an operation touched a replica that has
  been killed.  The front end's retry machinery catches it and
  requeues the victim's requests elsewhere; reaching a caller means
  the retry budget could not absorb the failure.
* :class:`RequestShedError` — admission control rejected the request
  (load shedding, degradation-ladder policy, or an exhausted retry
  budget).  Stored on the shed request so callers see a typed cause,
  never a bare RuntimeError.

The snapshot/journal subsystem (PR 9) adds the durability half:

* :class:`SnapshotError` — base for anything wrong with persisted
  serving state.  Callers that want "warm if possible, cold
  otherwise" catch this one class.
* :class:`SnapshotCorruptError` — a snapshot or journal file failed
  validation (bad magic, stale version, truncated section, checksum
  mismatch).  Recovery code treats it as "this file does not count",
  never as a crash: `ReplicaHandle.restart(warm_from=...)` falls back
  to the cold `resume_request` path.
* :class:`ReplicaStateError` — a lifecycle operation was applied to a
  replica in the wrong state (e.g. `restart` on a live replica).
  Distinct from :class:`ReplicaDeadError`, which covers work routed
  *at* a dead replica.

The gray-failure work (ISSUE 10) adds the transient half:

* :class:`StepInterruptedError` — one engine step aborted before any
  state mutation (an intermittent, non-fail-stop fault).  The front
  end records it on the replica's error streak and retries next tick;
  the `ReplicaSupervisor` escalates only when the streak persists.

The global prefix tier (`attention_tpu.prefixstore`, ISSUE 17) adds
the fleet-reuse half:

* :class:`PrefixStoreCorruptError` — a content-addressed prefix
  record failed validation (bad magic, CRC mismatch, truncated
  payload).  The import path treats it exactly like
  :class:`SnapshotCorruptError` treats a bad snapshot: drop the
  entry, count it, fall back to cold prefill — wrong tokens are
  never acceptable, a re-prefill always is.
* :class:`PrefixLeaseError` — single-flight lease misuse (releasing
  a lease another request holds, acquiring over a live foreign
  lease).  Lease *expiry* is not an error — it is the deterministic
  tick-driven escape hatch when a lease holder dies mid-prefill.

Recurrent layers (ISSUE 27) add the reach half:

* :class:`RecurrentStateUnsupportedError` — a feature that knows only
  KV pages (snapshots, the prefix store, the fleet hand-off, mesh
  serving, the two-call step) was asked to serve a model whose layers
  also keep a recurrent state per request.  Pages alone do not restore
  such a request, so the feature refuses instead of serving wrong
  tokens.

Attention layers that differ inside one model (ISSUE 43):

* :class:`PageSpacesUnsupportedError` — a feature that carries ONE
  list of page ids a request met a model whose window layers and full
  layers keep their pages in two page spaces.

All subclass RuntimeError, the `OutOfPagesError` lineage — the
ATP401 contract (attention_tpu/analysis/errors.py) extends over
``frontend/`` and ``prefixstore/`` so generic raises cannot creep
back in.
"""

from __future__ import annotations


class DeadlineExceededError(RuntimeError):
    """A request's deadline/TTL expired.

    Surfaced by `ServingEngine.add_request`/`resume_request` when the
    deadline predates the admission step; mid-flight expiry instead
    transitions the request to the terminal TIMED_OUT state."""


class ReplicaDeadError(RuntimeError):
    """An operation was routed at a killed replica.

    `ReplicaHandle.step` (and every other engine accessor on a dead
    handle) raises this; the front end's retry-with-backoff path
    catches it and requeues the in-flight requests elsewhere."""


class RequestShedError(RuntimeError):
    """Admission control rejected the request.

    Load shedding under watermark/queue pressure, the degradation
    ladder's lowest-priority cut, or a retry budget that ran dry —
    always deliberate policy, recorded on the request's ``error``
    field so clients can distinguish "shed, retry later" from a
    serving bug."""


class StepInterruptedError(RuntimeError):
    """An engine step aborted before mutating any request state.

    The gray-failure chaos injector raises this from a wrapped
    ``engine.step`` BEFORE the inner step runs, modelling a transient
    host-side fault (driver hiccup, runtime retry) that costs a
    scheduler round but corrupts nothing.  The front end notes it on
    the replica's error streak — the `ReplicaSupervisor`'s
    consecutive-typed-step-errors signal — and simply tries again next
    tick; it is never a reason to cancel or requeue work."""


class SnapshotError(RuntimeError):
    """Base class for serving-state durability failures.

    `recover_engine` and `ReplicaHandle.restart(warm_from=...)` catch
    this class: any subclass means "warm recovery unavailable, take
    the cold path", never a crash."""


class SnapshotCorruptError(SnapshotError):
    """A snapshot or journal file failed validation.

    Bad magic, unsupported version, truncated section, per-section
    CRC mismatch, or a model fingerprint that does not match the
    engine being restored.  Raised by `engine.snapshot.restore` (and
    by `recover_engine` when *no* candidate validates); a torn journal
    *tail* is tolerated silently instead — the valid prefix is used."""


class ReplicaStateError(RuntimeError):
    """A replica lifecycle operation was applied in the wrong state.

    E.g. `ReplicaHandle.restart` on a replica that is still alive:
    the caller must `kill()` first.  Kept distinct from
    :class:`ReplicaDeadError` (work routed at a *dead* replica) so
    chaos invariants can tell misuse from expected fail-stop."""


class PrefixStoreCorruptError(RuntimeError):
    """A fleet prefix-store record or store file failed validation.

    Bad magic, unsupported version, truncated section, per-section
    CRC mismatch, byte-accounting drift, or record metadata that does
    not describe its own payload.  Raised by
    `prefixstore.records.decode_record` / `prefixstore.store.load_store`;
    the engine import path catches it, bumps ``prefixstore.corrupt``,
    discards the poisoned entry, and falls back to cold prefill — a
    corrupt record may cost a re-prefill, never a wrong token."""


class PrefixLeaseError(RuntimeError):
    """Single-flight prefix lease misuse.

    Releasing a lease owned by a different request, or acquiring over
    a live lease held by another owner, is a caller bug and raises
    this.  Tick-driven lease *expiry* (the holder died mid-prefill)
    is deliberately not an error: waiters observe the expired lease,
    the next one in deterministic arrival order takes over, and the
    storm still prefills at most once per lease generation."""


class HandoffCorruptError(PrefixStoreCorruptError):
    """A prefill→decode KV-handoff payload failed validation.

    The disaggregated fleet ships a request's committed prefix pages
    from the prefill pool to its decode destination in the prefix-
    record section format (`attention_tpu.fleet.handoff`); bad magic,
    a truncated or CRC-mismatched ``pools.<s>`` section, or metadata
    that does not describe its payload raises this.  Subclasses
    :class:`PrefixStoreCorruptError` so every existing typed-error
    gate (chaos ``TYPED_ERRORS``, the import-path catch discipline)
    covers it unchanged.  The handoff path catches it, counts a
    ``handoff_fallback``, and re-admits the request WITHOUT the pages
    — the destination re-prefills, token parity holds, and the
    corruption costs compute, never a wrong token."""


class RecurrentStateUnsupportedError(RuntimeError):
    """A pages-only feature met a model with recurrent layers.

    Such a model keeps, beside its KV pages, one state per request and
    recurrent layer that cannot be re-derived page by page.  Snapshot
    save / restore, prefix-store export and import, the fleet's KV
    hand-off and ``mesh_shards > 0`` carry pages only; each raises
    this for such a model
    (`ServingEngine.require_pages_only`).  State checkpoints at page
    boundaries would lift it (ROADMAP R2)."""


class LatentCacheUnsupportedError(RuntimeError):
    """A feature that carries a K pool and a V pool a layer met a
    model whose attention sublayers keep ONE latent pool each.

    Snapshot save / restore, prefix-store export and import and the
    fleet's KV hand-off read and write K / V pool pairs, one a layer;
    a latent-attention model keeps one pool a SUBLAYER and no V pool,
    and each of them raises this for it
    (`ServingEngine.require_pages_only`).  The local prefix cache is
    not among them: page ids are head- and pool-agnostic."""


class PageSpacesUnsupportedError(RuntimeError):
    """A feature that carries ONE list of page ids a request met a
    model with TWO page spaces.

    A model whose sliding-window layers stand beside full-attention
    layers keeps the window layers' K and V in a pool of their own,
    under page ids of their own, and a request holds of them its
    trailing band and no more.  Snapshot save / restore, prefix-store
    export and import, the fleet's KV hand-off and ``mesh_shards > 0``
    write one list of pages a request and one K / V pool pair a layer
    of one size; each raises this for such a model
    (`ServingEngine.require_pages_only`) until the page wire format
    has a record for the second space."""
