"""Seeded fault plans injected into the serving engine's step loop.

The fuzzer (`chaos.fuzzer`) attacks the KERNELS; this module attacks
the ENGINE — the allocator state you never reached and the scheduling
interleavings you never tested.  A :class:`FaultPlan` is a seeded,
JSON-able list of events fired between engine steps:

* ``oom``       — the next N admission-path page allocations raise
                  `OutOfPagesError` (capacity pressure without needing
                  a giant trace);
* ``preempt``   — preemption-by-recompute storm: forcibly preempt the
                  N youngest running requests;
* ``cancel``    — a client abandons the target request mid-flight
                  (`ServingEngine.cancel`);
* ``corrupt``   — NaN-poison one of the target's unshared KV pages
                  (device-memory rot; must stay contained to the
                  target);
* ``watermark`` — flap the allocator's admission reserve.

`run_plan` replays a trace through an engine with the plan attached
and checks the four invariants (`chaos.invariants`); `run_campaign`
does that for many seeded plans against one fault-free baseline.
Everything is deterministic from the seeds, so a violating plan is
its own repro.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
from typing import Any, Callable, Sequence

import numpy as np

from attention_tpu import obs
from attention_tpu.chaos import invariants as inv
from attention_tpu.obs import blackbox as obs_blackbox
from attention_tpu.engine import journal as journal_mod
from attention_tpu.engine import snapshot as snapshot_mod
from attention_tpu.engine.engine import EngineConfig, ServingEngine
from attention_tpu.engine.errors import StepInterruptedError
from attention_tpu.engine.metrics import StepMetrics
from attention_tpu.engine.scheduler import ScheduledStep
from attention_tpu.engine.sim import replay, synthetic_trace
from attention_tpu.ops.paged import OutOfPagesError

_INJECTED = obs.counter("chaos.faults.injected",
                        "fault events actually applied, by kind")

FAULT_KINDS = ("oom", "preempt", "cancel", "corrupt", "watermark")


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    step: int
    kind: str
    arg: int = 1                 # count (oom/preempt) or value (watermark)
    target: str | None = None    # request id (cancel/corrupt)


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    seed: int
    events: tuple[FaultEvent, ...]

    def to_json(self) -> str:
        return json.dumps({
            "seed": self.seed,
            "events": [dataclasses.asdict(e) for e in self.events],
        }, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "FaultPlan":
        data = json.loads(text)
        return cls(seed=int(data["seed"]),
                   events=tuple(FaultEvent(**e) for e in data["events"]))


def random_plan(seed: int, request_ids: Sequence[str], *,
                num_events: int = 4, max_step: int = 20,
                kinds: Sequence[str] = FAULT_KINDS) -> FaultPlan:
    """Sample one seeded plan.  Watermark values deliberately include
    the boundary cases (0 and a value near the pool's reserve) — the
    off-by-one class the allocator's watermark test pins."""
    rng = np.random.default_rng(seed)
    events = []
    for _ in range(num_events):
        kind = kinds[int(rng.integers(len(kinds)))]
        step = int(rng.integers(1, max_step))
        arg, target = 1, None
        if kind in ("oom", "preempt"):
            arg = int(rng.integers(1, 3))
        elif kind == "watermark":
            arg = int(rng.integers(0, 4))
        elif kind in ("cancel", "corrupt"):
            target = request_ids[int(rng.integers(len(request_ids)))]
        events.append(FaultEvent(step=step, kind=kind, arg=arg,
                                 target=target))
    events.sort(key=lambda e: (e.step, e.kind, e.target or ""))
    return FaultPlan(seed=seed, events=tuple(events))


class FaultInjector:
    """Attaches a plan to one engine instance: wraps the allocator's
    ``allocate`` (injected OOM windows) and the engine's ``step``
    (between-step event firing).  Bookkeeps what was ACTUALLY applied
    — the invariant checkers exclude corrupted/cancelled targets from
    token parity."""

    def __init__(self, engine: ServingEngine, plan: FaultPlan):
        self.engine = engine
        self.plan = plan
        self.injected = 0
        self.corrupted: list[str] = []
        self.cancelled: list[str] = []
        self.skipped: list[str] = []
        self._oom_admit = 0
        self._orig_allocate = engine.allocator.allocate
        self._orig_step = engine.step
        engine.allocator.allocate = self._allocate
        engine.step = self._step

    # -- hook points ------------------------------------------------------

    def _allocate(self, n: int, *, for_decode: bool = False):
        if not for_decode and self._oom_admit > 0:
            self._oom_admit -= 1
            self._mark("oom")
            raise OutOfPagesError(
                "chaos: injected admission-path OutOfPagesError"
            )
        return self._orig_allocate(n, for_decode=for_decode)

    def _step(self):
        for ev in self.plan.events:
            if ev.step == self.engine.current_step:
                self._fire(ev)
        return self._orig_step()

    # -- event application ------------------------------------------------

    def _mark(self, kind: str) -> None:
        self.injected += 1
        _INJECTED.inc(kind=kind)

    def _fire(self, ev: FaultEvent) -> None:
        if ev.kind == "oom":
            self._oom_admit += ev.arg
            # marked when the allocation actually raises
        elif ev.kind == "preempt":
            self._preempt_storm(ev.arg)
        elif ev.kind == "cancel":
            if self.engine.cancel(ev.target):
                self.cancelled.append(ev.target)
                self._mark("cancel")
            else:
                self.skipped.append(f"cancel:{ev.target}")
        elif ev.kind == "corrupt":
            if self._corrupt(ev.target):
                self.corrupted.append(ev.target)
                self._mark("corrupt")
            else:
                self.skipped.append(f"corrupt:{ev.target}")
        elif ev.kind == "watermark":
            alloc = self.engine.allocator
            alloc.watermark_pages = max(
                0, min(ev.arg, alloc.pool.num_pages - 1))
            self._mark("watermark")
        else:
            raise ValueError(f"unknown fault kind {ev.kind!r}")

    def _preempt_storm(self, count: int) -> None:
        """Forcibly preempt the ``count`` youngest running requests —
        the allocator-pressure path without needing real pressure."""
        sched = self.engine.scheduler
        for _ in range(count):
            if not sched.running:
                return
            victim = max(sched.running, key=sched._fcfs)
            sched._preempt(victim, ScheduledStep(
                step=self.engine.current_step))
            self._mark("preempt")

    def _corrupt(self, target: str) -> bool:
        """NaN-poison one page the target holds EXCLUSIVELY (shared
        prefix-cache pages would leak the fault into other requests —
        the harness injects contained faults; containment is what the
        parity invariant then proves)."""
        import jax.numpy as jnp

        engine = self.engine
        req = next((r for r in engine.scheduler.running
                    if r.request_id == target and r.pages), None)
        if req is None:
            return False
        cached = {e.page for e in engine.allocator._prefix.values()}
        page = next((p for p in reversed(req.pages)
                     if p not in cached
                     and engine.pool.refcount(p) == 1), None)
        if page is None:
            return False
        # whatever the layers keep under the request's page ids
        engine.set_page_pools([pool.at[page].set(jnp.nan)
                               for pool in engine.page_pools()])
        return True


# ------------------------------------------------------------- plan runs


@dataclasses.dataclass
class PlanReport:
    plan: FaultPlan
    injected: int
    corrupted: list[str]
    cancelled: list[str]
    skipped: list[str]
    outputs: dict[str, list[int]]
    violations: list[str]
    surfaced_error: str | None
    drained: bool
    preemptions: int = 0

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["plan"] = json.loads(self.plan.to_json())
        return d


def default_engine_config(**overrides) -> EngineConfig:
    """Campaign engine geometry: small enough that injected pressure
    means something, large enough to hold the default trace."""
    kw: dict[str, Any] = dict(
        num_pages=16, page_size=128, max_seq_len=192,
        max_decode_batch=4, max_prefill_rows=2, prefill_chunk=16,
        token_budget=32, watermark_pages=1,
    )
    kw.update(overrides)
    return EngineConfig(**kw)


def build_sim_model(*, vocab: int = 43, dim: int = 32, depth: int = 1,
                    q_heads: int = 4, kv_heads: int = 2, seed: int = 0):
    """Deterministic tiny decoder (the `cli serve-sim` discipline:
    params from PRNGKey(seed), so every run is bit-identical)."""
    import jax
    import jax.numpy as jnp

    from attention_tpu.models import TinyDecoder

    model = TinyDecoder(vocab=vocab, dim=dim, depth=depth,
                        num_q_heads=q_heads, num_kv_heads=kv_heads,
                        impl="flash", dtype=jnp.float32)
    probe = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), probe)["params"]
    return model, params


def run_plan(model, params, config: EngineConfig,
             trace: list[dict[str, Any]], plan: FaultPlan, *,
             baseline: dict[str, list[int]] | None = None,
             max_steps: int = 500) -> PlanReport:
    """Replay ``trace`` through a fresh engine with ``plan`` attached;
    check every invariant that applies.  ``baseline`` (a fault-free
    run's outputs) enables the token-parity check."""
    engine = ServingEngine(model, params, config)
    injector = FaultInjector(engine, plan)
    error: BaseException | None = None
    outputs: dict[str, list[int]] = {}
    try:
        _, outputs = replay(engine, trace, max_steps=max_steps)
    except Exception as e:  # noqa: BLE001 - the typed-error invariant
        error = e           # decides what may land here
    drained = error is None and not engine.scheduler.has_work()

    violations = []
    violations += inv.pool_accounting_violations(engine.pool)
    if drained:
        violations += inv.engine_quiescence_violations(engine)
        if baseline is not None:
            untouched_baseline = dict(baseline)
            violations += inv.token_parity_violations(
                untouched_baseline, outputs,
                exclude=set(injector.corrupted) | set(injector.cancelled),
            )
    violations += inv.termination_violations(drained, error,
                                             max_steps=max_steps)
    violations += inv.typed_error_violations(error)
    return PlanReport(
        plan=plan, injected=injector.injected,
        corrupted=injector.corrupted, cancelled=injector.cancelled,
        skipped=injector.skipped, outputs=outputs,
        violations=violations,
        surfaced_error=None if error is None else type(error).__name__,
        drained=drained,
        preemptions=engine.scheduler.num_preemptions,
    )


@dataclasses.dataclass
class FaultCampaignReport:
    seed: int
    baseline_outputs: dict[str, list[int]]
    reports: list[PlanReport]

    @property
    def ok(self) -> bool:
        return all(r.ok for r in self.reports)

    @property
    def total_injected(self) -> int:
        return sum(r.injected for r in self.reports)

    def to_dict(self) -> dict[str, Any]:
        return {
            "seed": self.seed,
            "plans": len(self.reports),
            "injected": self.total_injected,
            "violations": sum(len(r.violations) for r in self.reports),
            "reports": [r.to_dict() for r in self.reports],
        }


def run_campaign(seed: int, *, num_plans: int = 5,
                 num_requests: int = 5, temperature: float = 0.0,
                 events_per_plan: int = 4,
                 config: EngineConfig | None = None,
                 model=None, params=None,
                 log: Callable[[str], None] | None = None,
                 ) -> FaultCampaignReport:
    """One seeded fault campaign: a fault-free baseline run, then
    ``num_plans`` seeded plans against the SAME trace, each checked
    for all four invariants."""
    if model is None or params is None:
        model, params = build_sim_model()
    config = config or default_engine_config()
    trace = synthetic_trace(
        num_requests, vocab=model.vocab, seed=seed, max_tokens=6,
        temperature=temperature,
    )
    engine = ServingEngine(model, params, config)
    _, baseline = replay(engine, trace)
    ids = [t["id"] for t in trace]
    reports = []
    for i in range(num_plans):
        plan = random_plan(seed * 1009 + i, ids,
                           num_events=events_per_plan)
        r = run_plan(model, params, config, trace, plan,
                     baseline=baseline)
        if log is not None:
            log(f"plan {i} (seed {plan.seed}): injected={r.injected} "
                f"violations={len(r.violations)} "
                f"error={r.surfaced_error or 'none'}")
        reports.append(r)
    return FaultCampaignReport(seed=seed, baseline_outputs=baseline,
                               reports=reports)


# ------------------------------------------- multi-replica storm plans


FRONTEND_FAULT_KINDS = ("replica_kill", "replica_restart", "oom",
                        "preempt", "cancel")

#: the durability crash points (ISSUE 9) — only meaningful against a
#: snapshot-configured front end, so they live in their own kind set
#: (plain storms keep their historical sampling sequence)
CRASH_FAULT_KINDS = FRONTEND_FAULT_KINDS + (
    "snap_crash",     # arm the next snapshot save to die mid-write
    "snap_corrupt",   # bit-flip a section of the newest snapshot
    "journal_tear",   # truncate the newest journal mid-record
)

#: the gray failures (ISSUE 10) — a replica that is sick but not dead:
#: each arms a WINDOW of ``arg`` affected steps on the target replica's
#: CURRENT engine, exactly the shapes the `ReplicaSupervisor` detects
GRAY_FAULT_KINDS = (
    "slow_step",      # inflate the engine's virtual step cost
    "flaky_step",     # typed StepInterruptedError before the step runs
    "stall",          # silently swallow the step (counter freezes)
    "nan",            # poison the model's output logits with NaN
)

#: the prefix-store faults (ISSUE 17) — only meaningful against a
#: front end with ``FrontendConfig.prefix_store`` set; each attacks a
#: different leg of the fleet-reuse contract (payload integrity,
#: manifest integrity, the single-flight lease, the byte budget)
STORE_FAULT_KINDS = FRONTEND_FAULT_KINDS + (
    "store_poison",   # flip a byte inside a stored record's payload
    "store_crc",      # flip a byte inside a record's manifest line
    "lease_kill",     # kill the replica serving the lease leader
    "store_evict",    # eviction storm: drop every entry at once
)

#: the disaggregation faults (ISSUE 19) — only meaningful against a
#: front end with ``FrontendConfig.fleet`` set; each attacks a leg of
#: the prefill/decode contract (handoff payload integrity, autoscaler
#: hysteresis)
DISAGG_FAULT_KINDS = FRONTEND_FAULT_KINDS + (
    "handoff_poison",  # corrupt the next N prefill->decode payloads
    "demote_storm",    # force N hysteresis-bypassing scale-downs
)


def random_frontend_plan(seed: int, request_ids: Sequence[str],
                         num_replicas: int, *, num_events: int = 5,
                         max_tick: int = 24,
                         kinds: Sequence[str] = FRONTEND_FAULT_KINDS,
                         ) -> FaultPlan:
    """Sample one seeded multi-replica storm plan.  Reuses the
    engine-plan schema (`FaultEvent.target` carries a replica id for
    replica-scoped kinds, a request id for ``cancel``).  Every
    ``replica_kill`` schedules a matching ``replica_restart`` a few
    ticks later with high probability, so storms exercise the
    kill -> requeue -> recover arc and not just attrition."""
    rng = np.random.default_rng(seed)
    events = []
    for _ in range(num_events):
        kind = kinds[int(rng.integers(len(kinds)))]
        step = int(rng.integers(1, max_tick))
        arg, target = 1, None
        if kind == "replica_kill":
            target = f"replica-{int(rng.integers(num_replicas))}"
            if rng.random() < 0.75:
                events.append(FaultEvent(
                    step=step + int(rng.integers(2, 7)),
                    kind="replica_restart", target=target))
        elif kind == "replica_restart":
            target = f"replica-{int(rng.integers(num_replicas))}"
        elif kind in ("oom", "preempt"):
            arg = int(rng.integers(1, 3))
            target = f"replica-{int(rng.integers(num_replicas))}"
        elif kind == "cancel":
            target = request_ids[int(rng.integers(len(request_ids)))]
        events.append(FaultEvent(step=step, kind=kind, arg=arg,
                                 target=target))
    events.sort(key=lambda e: (e.step, e.kind, e.target or ""))
    return FaultPlan(seed=seed, events=tuple(events))


def random_crash_plan(seed: int, request_ids: Sequence[str],
                      num_replicas: int, *, num_events: int = 6,
                      max_tick: int = 24) -> FaultPlan:
    """Sample one seeded crash-storm plan: the ISSUE 6 storm kinds
    PLUS the three durability crash points, with kills biased toward
    warm-recovery coverage.  Every sampled kill still schedules its
    restart; the crash points target a replica's snapshot directory so
    the restart is forced through the warm-or-degrade decision."""
    rng = np.random.default_rng(seed)
    events = []
    crash_kinds = ("snap_crash", "snap_corrupt", "journal_tear")
    for _ in range(num_events):
        kind = CRASH_FAULT_KINDS[int(rng.integers(len(CRASH_FAULT_KINDS)))]
        step = int(rng.integers(1, max_tick))
        arg, target = 1, None
        if kind == "replica_kill":
            target = f"replica-{int(rng.integers(num_replicas))}"
            if rng.random() < 0.9:
                events.append(FaultEvent(
                    step=step + int(rng.integers(2, 7)),
                    kind="replica_restart", target=target))
        elif kind in ("replica_restart", "oom", "preempt") \
                or kind in crash_kinds:
            target = f"replica-{int(rng.integers(num_replicas))}"
            if kind in ("oom", "preempt"):
                arg = int(rng.integers(1, 3))
            elif kind == "journal_tear":
                arg = int(rng.integers(0, 4))
        elif kind == "cancel":
            target = request_ids[int(rng.integers(len(request_ids)))]
        events.append(FaultEvent(step=step, kind=kind, arg=arg,
                                 target=target))
    # guarantee at least one kill+restart pair per plan: a crash storm
    # that never kills anything never exercises warm recovery
    if not any(e.kind == "replica_kill" for e in events):
        victim = f"replica-{int(rng.integers(num_replicas))}"
        step = int(rng.integers(2, max_tick))
        events.append(FaultEvent(step=step, kind="replica_kill",
                                 target=victim))
        events.append(FaultEvent(step=step + int(rng.integers(2, 7)),
                                 kind="replica_restart", target=victim))
    events.sort(key=lambda e: (e.step, e.kind, e.target or ""))
    return FaultPlan(seed=seed, events=tuple(events))


def random_gray_plan(seed: int, request_ids: Sequence[str],
                     num_replicas: int, *, num_events: int = 6,
                     max_tick: int = 24) -> FaultPlan:
    """Sample one seeded gray storm: sick-but-not-dead windows
    (`GRAY_FAULT_KINDS`) plus the occasional client cancel, with one
    guaranteed slow-step window, one flaky-step window, and one
    fail-stop kill per plan — the acceptance mix (detection, live
    migration, AND standby promotion all get exercised)."""
    rng = np.random.default_rng(seed)
    kinds = GRAY_FAULT_KINDS + ("cancel",)
    events = []
    for _ in range(num_events):
        kind = kinds[int(rng.integers(len(kinds)))]
        step = int(rng.integers(1, max_tick))
        arg, target = 1, None
        if kind in GRAY_FAULT_KINDS:
            arg = int(rng.integers(2, 6))   # window length in steps
            target = f"replica-{int(rng.integers(num_replicas))}"
        else:
            target = request_ids[int(rng.integers(len(request_ids)))]
        events.append(FaultEvent(step=step, kind=kind, arg=arg,
                                 target=target))
    for kind in ("slow_step", "flaky_step"):
        if not any(e.kind == kind for e in events):
            events.append(FaultEvent(
                step=int(rng.integers(1, max_tick)), kind=kind,
                arg=int(rng.integers(2, 6)),
                target=f"replica-{int(rng.integers(num_replicas))}"))
    if not any(e.kind == "replica_kill" for e in events):
        events.append(FaultEvent(
            step=int(rng.integers(2, max_tick)), kind="replica_kill",
            target=f"replica-{int(rng.integers(num_replicas))}"))
    events.sort(key=lambda e: (e.step, e.kind, e.target or ""))
    return FaultPlan(seed=seed, events=tuple(events))


def random_store_plan(seed: int, request_ids: Sequence[str],
                      num_replicas: int, *, num_events: int = 6,
                      max_tick: int = 40) -> FaultPlan:
    """Sample one seeded prefix-store storm: the ISSUE 6 storm kinds
    plus the four store attacks, with at least one store fault
    guaranteed per plan (a store storm that never touches the store
    proves nothing).  ``arg`` on the corruption kinds picks WHICH live
    entry gets hit (mod the live count at fire time), so replays are
    deterministic even as the store fills."""
    rng = np.random.default_rng(seed)
    store_kinds = ("store_poison", "store_crc", "lease_kill",
                   "store_evict")
    events = []
    for _ in range(num_events):
        kind = STORE_FAULT_KINDS[int(rng.integers(len(STORE_FAULT_KINDS)))]
        step = int(rng.integers(1, max_tick))
        arg, target = 1, None
        if kind == "replica_kill":
            target = f"replica-{int(rng.integers(num_replicas))}"
            if rng.random() < 0.9:
                events.append(FaultEvent(
                    step=step + int(rng.integers(2, 7)),
                    kind="replica_restart", target=target))
        elif kind in ("replica_restart", "oom", "preempt"):
            target = f"replica-{int(rng.integers(num_replicas))}"
            if kind in ("oom", "preempt"):
                arg = int(rng.integers(1, 3))
        elif kind == "cancel":
            target = request_ids[int(rng.integers(len(request_ids)))]
        elif kind in ("store_poison", "store_crc"):
            arg = int(rng.integers(0, 8))
        events.append(FaultEvent(step=step, kind=kind, arg=arg,
                                 target=target))
    if not any(e.kind in store_kinds for e in events):
        events.append(FaultEvent(
            step=int(rng.integers(2, max_tick)),
            kind=store_kinds[int(rng.integers(len(store_kinds)))],
            arg=int(rng.integers(0, 8))))
    events.sort(key=lambda e: (e.step, e.kind, e.target or ""))
    return FaultPlan(seed=seed, events=tuple(events))


def random_disagg_plan(seed: int, request_ids: Sequence[str],
                       num_replicas: int, *, num_events: int = 6,
                       max_tick: int = 40) -> FaultPlan:
    """Sample one seeded disaggregation storm: the ISSUE 6 kinds plus
    the two fleet attacks, with at least one of each fleet attack
    guaranteed per plan (a disagg storm that never poisons a handoff
    or forces a demotion proves nothing).  ``arg`` is the window size
    — payloads to corrupt, demotions to force."""
    rng = np.random.default_rng(seed)
    specialty = ("handoff_poison", "demote_storm")
    events = []
    for _ in range(num_events):
        kind = DISAGG_FAULT_KINDS[
            int(rng.integers(len(DISAGG_FAULT_KINDS)))]
        step = int(rng.integers(1, max_tick))
        arg, target = 1, None
        if kind == "replica_kill":
            target = f"replica-{int(rng.integers(num_replicas))}"
            if rng.random() < 0.9:
                events.append(FaultEvent(
                    step=step + int(rng.integers(2, 7)),
                    kind="replica_restart", target=target))
        elif kind in ("replica_restart", "oom", "preempt"):
            target = f"replica-{int(rng.integers(num_replicas))}"
            if kind in ("oom", "preempt"):
                arg = int(rng.integers(1, 3))
        elif kind == "cancel":
            target = request_ids[int(rng.integers(len(request_ids)))]
        elif kind in specialty:
            arg = int(rng.integers(1, 4))
        events.append(FaultEvent(step=step, kind=kind, arg=arg,
                                 target=target))
    for kind in specialty:
        if not any(e.kind == kind for e in events):
            events.append(FaultEvent(
                step=int(rng.integers(2, max_tick)), kind=kind,
                arg=int(rng.integers(1, 4))))
    events.sort(key=lambda e: (e.step, e.kind, e.target or ""))
    return FaultPlan(seed=seed, events=tuple(events))


def _flip_byte(path: str) -> None:
    """Bit-flip the middle byte of a file in place — lands inside the
    (dominant) pools section of a snapshot, so restore must fail its
    section checksum, never deserialize garbage."""
    with open(path, "r+b") as f:
        data = f.read()
        if not data:
            return
        mid = len(data) // 2
        f.seek(mid)
        f.write(bytes([data[mid] ^ 0xFF]))


def _tear_tail(path: str, arg: int) -> None:
    """Truncate a journal mid-record: cut at least 3 bytes so the torn
    line can never still parse (tearing only the trailing newline
    would leave a VALID record, which is no tear at all)."""
    size = os.path.getsize(path)
    os.truncate(path, size - min(size, 3 + arg * 5))


class FrontendFaultInjector:
    """Attaches a storm plan to one `ServingFrontend`: wraps ``tick``
    and fires due events before the round runs.  Replica-scoped OOM
    windows wrap the CURRENT engine's allocator (a restarted engine
    starts clean — exactly like a real process restart shedding its
    fault state)."""

    def __init__(self, frontend, plan: FaultPlan):
        self.frontend = frontend
        self.plan = plan
        self.injected = 0
        self.cancelled: list[str] = []
        self.skipped: list[str] = []
        #: (kind, tick) of every fault ACTUALLY applied, in order —
        #: invariant 15 matches this ledger against the incident
        #: bundles the run dumped
        self.fired: list[tuple[str, int]] = []
        self._orig_tick = frontend.tick
        frontend.tick = self._tick

    def _mark(self, kind: str) -> None:
        self.injected += 1
        _INJECTED.inc(kind=kind)
        tick = self.frontend.current_tick
        self.fired.append((kind, tick))
        obs_blackbox.note("fault_injected", tick=tick, fault=kind)
        # every applied fault files its incident at injection time
        # (deduped per (cause, detail), so a multi-shot window at one
        # tick still yields exactly one bundle)
        self.frontend._incident("fault", {"kind": kind, "tick": tick})

    def _tick(self):
        for ev in self.plan.events:
            if ev.step == self.frontend.current_tick:
                self._fire(ev)
        return self._orig_tick()

    def _handle(self, replica_id: str | None):
        return next((h for h in self.frontend.replicas
                     if h.replica_id == replica_id), None)

    def _fire(self, ev: FaultEvent) -> None:
        if ev.kind == "replica_kill":
            if self.frontend.kill_replica(ev.target):
                self._mark("replica_kill")
            else:
                self.skipped.append(f"replica_kill:{ev.target}")
        elif ev.kind == "replica_restart":
            if self.frontend.restart_replica(ev.target):
                self._mark("replica_restart")
            else:
                self.skipped.append(f"replica_restart:{ev.target}")
        elif ev.kind == "oom":
            handle = self._handle(ev.target)
            if handle is None or not handle.alive:
                self.skipped.append(f"oom:{ev.target}")
                return
            self._arm_oom(handle, ev.arg)
        elif ev.kind == "preempt":
            handle = self._handle(ev.target)
            if handle is None or not handle.alive:
                self.skipped.append(f"preempt:{ev.target}")
                return
            self._preempt_storm(handle, ev.arg)
        elif ev.kind == "cancel":
            if self.frontend.cancel(ev.target):
                self.cancelled.append(ev.target)
                self._mark("cancel")
            else:
                self.skipped.append(f"cancel:{ev.target}")
        elif ev.kind == "snap_crash":
            handle = self._handle(ev.target)
            manager = getattr(handle, "_manager", None)
            if handle is None or not handle.alive or manager is None:
                self.skipped.append(f"snap_crash:{ev.target}")
                return
            manager.crash_next = True
            self._mark("snap_crash")
        elif ev.kind == "snap_corrupt":
            handle = self._handle(ev.target)
            snaps = snapshot_mod.list_snapshots(handle.snapshot_dir) \
                if handle is not None and handle.snapshot_dir else []
            if not snaps:
                self.skipped.append(f"snap_corrupt:{ev.target}")
                return
            _flip_byte(snaps[-1][1])
            self._mark("snap_corrupt")
        elif ev.kind == "journal_tear":
            handle = self._handle(ev.target)
            journals = journal_mod.list_journals(handle.snapshot_dir) \
                if handle is not None and handle.snapshot_dir else []
            if not journals:
                self.skipped.append(f"journal_tear:{ev.target}")
                return
            _tear_tail(journals[-1][1], ev.arg)
            self._mark("journal_tear")
        elif ev.kind in ("store_poison", "store_crc"):
            self._corrupt_store_entry(ev)
        elif ev.kind == "lease_kill":
            self._kill_lease_holder()
        elif ev.kind == "store_evict":
            store = getattr(self.frontend, "prefix_store", None)
            if store is None or not len(store):
                self.skipped.append("store_evict:empty")
                return
            store.evict_all(now=self.frontend.current_tick)
            self._mark("store_evict")
        elif ev.kind == "handoff_poison":
            if not getattr(self.frontend, "pool_of", None):
                self.skipped.append("handoff_poison:no-fleet")
                return
            self.frontend._poison_handoffs += max(1, ev.arg)
            self._mark("handoff_poison")
        elif ev.kind == "demote_storm":
            if getattr(self.frontend, "autoscaler", None) is None:
                self.skipped.append("demote_storm:no-autoscaler")
                return
            self.frontend._force_demotions += max(1, ev.arg)
            self._mark("demote_storm")
        elif ev.kind in GRAY_FAULT_KINDS:
            handle = self._handle(ev.target)
            if handle is None or not handle.alive:
                self.skipped.append(f"{ev.kind}:{ev.target}")
                return
            self._arm_gray(handle, ev.kind, max(1, ev.arg))
        else:
            raise ValueError(f"unknown frontend fault kind {ev.kind!r}")

    def _corrupt_store_entry(self, ev: FaultEvent) -> None:
        """Flip one byte of a live record in place — in the payload
        region (``store_poison``: the section CRC must catch it) or in
        the manifest line (``store_crc``: structural validation must
        catch it).  Either way the ONLY acceptable outcome downstream
        is `PrefixStoreCorruptError` handling: count, discard, cold
        re-prefill — never imported garbage (invariant 14 checks the
        token streams)."""
        store = getattr(self.frontend, "prefix_store", None)
        keys = sorted(store._entries) if store is not None else []
        if not keys:
            self.skipped.append(f"{ev.kind}:no-entries")
            return
        entry = store._entries[keys[ev.arg % len(keys)]]
        blob = bytearray(entry.blob)
        nl = blob.index(b"\n")
        if ev.kind == "store_poison":
            pos = nl + 1 + (len(blob) - nl - 1) // 2
        else:
            pos = nl // 2
        blob[pos] ^= 0xFF
        entry.blob = bytes(blob)
        self._mark(ev.kind)

    def _kill_lease_holder(self) -> None:
        """Fail-stop the replica currently prefilling for a
        single-flight lease leader: the leader rides the retry path to
        another replica (still holding its lease via the front end's
        heartbeat), so coalesced waiters must keep waiting and then
        import — exactly one fleet prefill even across the kill."""
        store = getattr(self.frontend, "prefix_store", None)
        if store is None:
            self.skipped.append("lease_kill:no-store")
            return
        victim = None
        for _key, owner in store.leases.active(
                now=self.frontend.current_tick):
            fr = self.frontend.requests.get(owner)
            if fr is not None and fr.replica_id is not None:
                victim = fr.replica_id
                break
        if victim is None or not self.frontend.kill_replica(victim):
            self.skipped.append("lease_kill:no-holder")
            return
        self._mark("lease_kill")

    def _arm_gray(self, handle, kind: str, count: int) -> None:
        """Arm a gray-failure window of ``count`` steps on the target
        replica's CURRENT engine (like `_arm_oom`, a restart sheds the
        fault state — a fresh process is healthy until proven sick).

        * ``slow_step`` — the step runs normally, then its virtual
          cost is inflated; only the supervisor's EWMA notices.
        * ``flaky_step`` — typed `StepInterruptedError` raised BEFORE
          the inner step, so no request state mutates.
        * ``stall`` — the step is silently swallowed (a fake metrics
          row, frozen step counter): the gray failure with no error.
        * ``nan`` — the model's output logits come back NaN; the
          engine's finite guard must skip sampling (never emit
          garbage) and count the event.
        """
        eng = handle.engine
        state = {"left": count}
        if kind == "nan":
            # wrap the logits device sync, the one seam every step
            # fetches through
            orig_fetch = eng._fetch_logits

            def poisoned(*args, **kwargs):
                out = orig_fetch(*args, **kwargs)
                if state["left"] > 0:
                    state["left"] -= 1
                    self._mark("nan")
                    out = np.full_like(np.asarray(out), np.nan)
                return out

            eng._fetch_logits = poisoned
            return
        orig_step = eng.step

        def wrapped_step():
            if state["left"] > 0 and kind == "flaky_step":
                state["left"] -= 1
                self._mark("flaky_step")
                raise StepInterruptedError(
                    f"chaos: injected step interruption on "
                    f"{handle.replica_id}"
                )
            if state["left"] > 0 and kind == "stall":
                state["left"] -= 1
                self._mark("stall")
                return StepMetrics(step=eng.current_step)
            metrics = orig_step()
            if state["left"] > 0 and kind == "slow_step":
                state["left"] -= 1
                self._mark("slow_step")
                eng.last_step_virtual_cost = 4.0
            return metrics

        eng.step = wrapped_step

    def _arm_oom(self, handle, count: int) -> None:
        """The next ``count`` admission-path allocations on this
        replica's CURRENT engine raise — the scheduler defers those
        admissions, and the front end's stall detector must migrate
        the starved requests elsewhere."""
        alloc = handle.engine.allocator
        state = {"left": count}
        orig = alloc.allocate

        def wrapped(n, *, for_decode=False):
            if not for_decode and state["left"] > 0:
                state["left"] -= 1
                self._mark("oom")
                raise OutOfPagesError(
                    f"chaos: injected admission OutOfPagesError on "
                    f"{handle.replica_id}"
                )
            return orig(n, for_decode=for_decode)

        alloc.allocate = wrapped

    def _preempt_storm(self, handle, count: int) -> None:
        sched = handle.engine.scheduler
        for _ in range(count):
            if not sched.running:
                return
            victim = max(sched.running, key=sched._fcfs)
            sched._preempt(victim, ScheduledStep(
                step=handle.engine.current_step))
            self._mark("preempt")


@dataclasses.dataclass
class FrontendPlanReport:
    """One storm's verdict (the frontend analogue of `PlanReport`)."""

    plan: FaultPlan
    injected: int
    cancelled: list[str]
    skipped: list[str]
    outputs: dict[str, list[int]]
    states: dict[str, str]
    violations: list[str]
    surfaced_error: str | None
    drained: bool
    summary: dict[str, Any]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["plan"] = json.loads(self.plan.to_json())
        return d


def default_frontend_config(num_replicas: int = 3, **overrides):
    """Storm-campaign front-end geometry: tight retry budget so
    exhaustion paths actually fire, short stall window so injected
    OOM windows visibly migrate requests."""
    from attention_tpu.frontend import FrontendConfig, RetryPolicy

    from attention_tpu.obs.forecast import ForecastPolicy

    kw: dict[str, Any] = dict(
        num_replicas=num_replicas, seed=0,
        retry=RetryPolicy(max_retries=4, base_delay_ticks=1,
                          max_delay_ticks=8),
        stall_ticks=3,
        # forecasting on (passive, advisory off) so every campaign
        # exercises invariant 13 under its storm
        forecast=ForecastPolicy(),
    )
    kw.update(overrides)
    return FrontendConfig(**kw)


def run_frontend_plan(model, params, config: EngineConfig,
                      frontend_config, trace: list[dict[str, Any]],
                      plan: FaultPlan, *,
                      baseline: dict[str, list[int]] | None = None,
                      max_ticks: int = 1000,
                      snapshot_roundtrip: bool = False,
                      incident_root: str | None = None,
                      ) -> FrontendPlanReport:
    """Replay ``trace`` through a fresh front end with ``plan``
    attached; check every invariant that applies — including the two
    ISSUE 6 checkers (no request lost, surviving-replica
    conservation).  ``baseline`` (a fault-free SINGLE-replica run)
    enables token parity over finished requests.
    ``snapshot_roundtrip`` additionally pins invariant 7 on every
    surviving replica of a drained run (``restore(save(engine))``
    state-identical).

    The whole plan runs inside ``obs.trace.capture()`` so invariant 12
    (trace completeness) has chains to judge even with telemetry off —
    capture clears the store on entry, isolating each plan's chains.
    ``obs.blackbox.capture()`` wraps it too: every applied fault lands
    in the flight-recorder ring AND dumps an incident bundle under
    ``incident_root`` (a throwaway directory when not given and the
    config carries none), which invariant 15 then audits for
    completeness — no injected fault without its bundle, no
    fault-cause bundle without its injection."""
    from attention_tpu.frontend import ServingFrontend, replay_frontend
    from attention_tpu.obs import trace as obs_trace

    with contextlib.ExitStack() as stack:
        if getattr(frontend_config, "incident_dir", None) is None:
            if incident_root is None:
                incident_root = stack.enter_context(
                    tempfile.TemporaryDirectory(
                        prefix="atp-incidents-"))
            frontend_config = dataclasses.replace(
                frontend_config, incident_dir=incident_root)
        return _run_frontend_plan_inner(
            model, params, config, frontend_config, trace, plan,
            baseline=baseline, max_ticks=max_ticks,
            snapshot_roundtrip=snapshot_roundtrip)


def _run_frontend_plan_inner(model, params, config, frontend_config,
                             trace, plan, *, baseline, max_ticks,
                             snapshot_roundtrip) -> FrontendPlanReport:
    from attention_tpu.frontend import ServingFrontend, replay_frontend
    from attention_tpu.obs import trace as obs_trace

    with obs_trace.capture(), obs_blackbox.capture():
        frontend = ServingFrontend(model, params, config,
                                   frontend_config)
        injector = FrontendFaultInjector(frontend, plan)
        error: BaseException | None = None
        outputs: dict[str, list[int]] = {}
        summary: dict[str, Any] = {}
        try:
            summary, outputs = replay_frontend(frontend, trace,
                                               max_ticks=max_ticks)
        except Exception as e:  # noqa: BLE001 - the typed-error
            error = e           # invariant decides what may land here
            outputs = frontend.outputs()
        drained = error is None and not frontend.has_work()

    from attention_tpu.frontend.frontend import FrontendRequestState

    violations = []
    violations += inv.replica_conservation_violations(frontend,
                                                      drained=drained)
    if drained:
        violations += inv.no_request_lost_violations(frontend)
        if baseline is not None:
            finished = {
                fr.request_id
                for fr in frontend.requests.values()
                if fr.state is FrontendRequestState.FINISHED
            }
            violations += inv.token_parity_violations(
                {rid: toks for rid, toks in baseline.items()
                 if rid in finished},
                outputs,
            )
    # the gray-failure trio (ISSUE 10): all three are no-ops on a
    # front end whose supervisor never issued a verdict
    violations += inv.no_double_serve_violations(frontend)
    violations += inv.supervisor_consistency_violations(frontend)
    if drained and baseline is not None:
        violations += inv.migration_parity_violations(frontend,
                                                      baseline)
    if baseline is not None:
        # invariant 14: a no-op on storeless front ends; with a store
        # attached, finished streams must match the NO-STORE fault-free
        # run and the store's byte ledger must balance
        violations += inv.prefix_import_parity_violations(frontend,
                                                          baseline)
    violations += inv.termination_violations(drained, error,
                                             max_steps=max_ticks)
    violations += inv.typed_error_violations(error)
    # invariant 12: the capture scope above recorded a chain for every
    # submitted request; judge them (incl. gray + crash campaigns,
    # which all funnel through this runner)
    violations += inv.trace_completeness_violations(frontend)
    # invariant 15: the incident ledger balances — every applied fault
    # dumped exactly one bundle naming its kind and tick, and every
    # fault/detector bundle traces back to a real cause
    violations += inv.incident_completeness_violations(frontend,
                                                       injector)
    # invariant 16: a no-op on monolithic front ends; with a fleet
    # attached, every pool resize balances against the blackbox ring
    # and no pool flaps inside the cooldown window
    violations += inv.actuation_ledger_violations(frontend)
    # invariant 13: campaigns enable forecasting (see
    # default_frontend_config) — the observatory report must be a
    # pure function of the recorded samples, storm or no storm
    violations += inv.forecast_determinism_violations(frontend)
    if snapshot_roundtrip and drained:
        for handle in frontend.replicas:
            if handle.alive:
                violations += [
                    f"{handle.replica_id}: {v}"
                    for v in inv.snapshot_roundtrip_violations(
                        handle.engine)
                ]
    return FrontendPlanReport(
        plan=plan, injected=injector.injected,
        cancelled=injector.cancelled, skipped=injector.skipped,
        outputs=outputs,
        states={fr.request_id: fr.state.value
                for fr in sorted(frontend.requests.values(),
                                 key=lambda f: f.seq)},
        violations=violations,
        surfaced_error=None if error is None else type(error).__name__,
        drained=drained,
        summary=summary,
    )


@dataclasses.dataclass
class FrontendCampaignReport:
    seed: int
    num_replicas: int
    baseline_outputs: dict[str, list[int]]
    reports: list[FrontendPlanReport]

    @property
    def ok(self) -> bool:
        return all(r.ok for r in self.reports)

    @property
    def total_injected(self) -> int:
        return sum(r.injected for r in self.reports)

    def to_dict(self) -> dict[str, Any]:
        return {
            "seed": self.seed,
            "replicas": self.num_replicas,
            "plans": len(self.reports),
            "injected": self.total_injected,
            "violations": sum(len(r.violations) for r in self.reports),
            "reports": [r.to_dict() for r in self.reports],
        }


def run_frontend_campaign(seed: int, *, num_plans: int = 5,
                          num_requests: int = 6, num_replicas: int = 3,
                          temperature: float = 0.0,
                          events_per_plan: int = 5,
                          config: EngineConfig | None = None,
                          model=None, params=None,
                          log: Callable[[str], None] | None = None,
                          ) -> FrontendCampaignReport:
    """One seeded storm campaign: a fault-free SINGLE-replica baseline
    run, then ``num_plans`` seeded replica-kill/OOM/preemption storms
    against the same trace through an N-replica front end, each
    checked for all six invariants."""
    if model is None or params is None:
        model, params = build_sim_model()
    config = config or default_engine_config()
    trace = synthetic_trace(
        num_requests, vocab=model.vocab, seed=seed, max_tokens=6,
        temperature=temperature,
    )
    engine = ServingEngine(model, params, config)
    _, baseline = replay(engine, trace)
    ids = [t["id"] for t in trace]
    reports = []
    for i in range(num_plans):
        plan = random_frontend_plan(seed * 2003 + i, ids, num_replicas,
                                    num_events=events_per_plan)
        r = run_frontend_plan(
            model, params, config,
            default_frontend_config(num_replicas), trace, plan,
            baseline=baseline,
        )
        if log is not None:
            log(f"storm {i} (seed {plan.seed}): injected={r.injected} "
                f"violations={len(r.violations)} "
                f"states={sorted(set(r.states.values()))} "
                f"error={r.surfaced_error or 'none'}")
        reports.append(r)
    return FrontendCampaignReport(seed=seed, num_replicas=num_replicas,
                                  baseline_outputs=baseline,
                                  reports=reports)


def run_crash_campaign(seed: int, snapshot_root: str, *,
                       num_plans: int = 5, num_requests: int = 6,
                       num_replicas: int = 2, snapshot_every: int = 2,
                       temperature: float = 0.0,
                       events_per_plan: int = 6,
                       config: EngineConfig | None = None,
                       model=None, params=None,
                       log: Callable[[str], None] | None = None,
                       ) -> FrontendCampaignReport:
    """The ISSUE 9 crash storm: `run_frontend_campaign` with durable
    replicas (periodic snapshots + journals under ``snapshot_root``)
    and the three crash points in the plan mix.  Kills now recover
    WARM when a valid snapshot survives the plan's corruption; on top
    of the six storm invariants each drained plan is checked for
    invariant 7 (round trip on every survivor) and invariant 8
    (every finished stream token-identical to the fault-free run —
    crash points may cost warmth, never tokens).

    Mesh replicas join the same storm by passing ``config`` with
    ``mesh_shards`` > 1 (and a ``model`` whose KV heads divide by
    it): every replica then serves through KV-head-sharded kernels,
    snapshots carry per-shard ``pools.<s>`` sections, and the SAME
    invariants apply unchanged — the fault-free baseline is computed
    with the identical config, so parity failures cannot hide behind
    the sharding."""
    if model is None or params is None:
        model, params = build_sim_model()
    config = config or default_engine_config()
    trace = synthetic_trace(
        num_requests, vocab=model.vocab, seed=seed, max_tokens=6,
        temperature=temperature,
    )
    engine = ServingEngine(model, params, config)
    _, baseline = replay(engine, trace)
    ids = [t["id"] for t in trace]
    reports = []
    for i in range(num_plans):
        plan = random_crash_plan(seed * 5009 + i, ids, num_replicas,
                                 num_events=events_per_plan)
        frontend_config = default_frontend_config(
            num_replicas,
            snapshot_dir=os.path.join(snapshot_root, f"plan-{i}"),
            snapshot_every=snapshot_every,
        )
        r = run_frontend_plan(
            model, params, config, frontend_config, trace, plan,
            baseline=baseline, snapshot_roundtrip=True,
        )
        if r.drained:
            finished = [rid for rid, state in r.states.items()
                        if state == "finished"]
            r.violations += inv.warm_recovery_parity_violations(
                baseline, r.outputs, finished)
        if log is not None:
            log(f"crash storm {i} (seed {plan.seed}): "
                f"injected={r.injected} "
                f"violations={len(r.violations)} "
                f"states={sorted(set(r.states.values()))} "
                f"error={r.surfaced_error or 'none'}")
        reports.append(r)
    return FrontendCampaignReport(seed=seed, num_replicas=num_replicas,
                                  baseline_outputs=baseline,
                                  reports=reports)


def run_gray_campaign(seed: int, snapshot_root: str, *,
                      num_plans: int = 5, num_requests: int = 6,
                      num_replicas: int = 2, standbys: int = 1,
                      snapshot_every: int = 2,
                      temperature: float = 0.0,
                      events_per_plan: int = 6,
                      config: EngineConfig | None = None,
                      model=None, params=None,
                      log: Callable[[str], None] | None = None,
                      ) -> FrontendCampaignReport:
    """The ISSUE 10 gray storm: seeded slow-step / flaky-step / stall /
    NaN windows (plus one guaranteed kill) against a supervised front
    end with ``standbys`` warm spares and durable replicas.  On top of
    the storm and durability invariants each plan is checked for the
    gray trio: migration token parity, no double serve, and supervisor
    consistency — a detected-and-drained replica costs re-prefills,
    never tokens, and never serves after its verdict."""
    from attention_tpu.frontend import SupervisorPolicy

    if model is None or params is None:
        model, params = build_sim_model()
    config = config or default_engine_config()
    trace = synthetic_trace(
        num_requests, vocab=model.vocab, seed=seed, max_tokens=6,
        temperature=temperature,
    )
    engine = ServingEngine(model, params, config)
    _, baseline = replay(engine, trace)
    ids = [t["id"] for t in trace]
    reports = []
    for i in range(num_plans):
        plan = random_gray_plan(seed * 7019 + i, ids, num_replicas,
                                num_events=events_per_plan)
        frontend_config = default_frontend_config(
            num_replicas,
            standbys=standbys,
            snapshot_dir=os.path.join(snapshot_root, f"plan-{i}"),
            snapshot_every=snapshot_every,
            supervisor=SupervisorPolicy(suspect_after=2,
                                        degrade_after=2, dead_after=2,
                                        stall_ticks=2),
        )
        r = run_frontend_plan(
            model, params, config, frontend_config, trace, plan,
            baseline=baseline,
        )
        if r.drained:
            finished = [rid for rid, state in r.states.items()
                        if state == "finished"]
            r.violations += inv.warm_recovery_parity_violations(
                baseline, r.outputs, finished)
        if log is not None:
            log(f"gray storm {i} (seed {plan.seed}): "
                f"injected={r.injected} "
                f"violations={len(r.violations)} "
                f"states={sorted(set(r.states.values()))} "
                f"error={r.surfaced_error or 'none'}")
        reports.append(r)
    return FrontendCampaignReport(seed=seed, num_replicas=num_replicas,
                                  baseline_outputs=baseline,
                                  reports=reports)


def shared_prefix_trace(num_requests: int, *, vocab: int, seed: int,
                        header_tokens: int = 256, tail_tokens: int = 4,
                        max_tokens: int = 4, max_arrival: int = 6,
                        ) -> list[dict[str, Any]]:
    """A RAG-shaped trace: every request shares a ``header_tokens``
    document header (page-aligned so the store can share it) and adds
    a short unique question tail.  Greedy decoding keeps the fault-
    free baseline deterministic.  This is the workload the prefix
    store exists for — the storm campaign runs it so store faults land
    while records are actually live and leased."""
    rng = np.random.default_rng(seed)
    header = [int(t) for t in rng.integers(1, vocab,
                                           size=header_tokens)]
    trace = []
    for i in range(num_requests):
        tail = [int(t) for t in rng.integers(1, vocab,
                                             size=tail_tokens)]
        trace.append({
            "id": f"s{i}", "prompt": header + tail,
            "arrival": int(rng.integers(0, max_arrival)),
            "max_tokens": max_tokens, "temperature": 0.0,
        })
    return trace


def run_store_campaign(seed: int, *, num_plans: int = 4,
                       num_requests: int = 5, num_replicas: int = 2,
                       events_per_plan: int = 6,
                       config: EngineConfig | None = None,
                       model=None, params=None,
                       log: Callable[[str], None] | None = None,
                       ) -> FrontendCampaignReport:
    """The ISSUE 17 store storm: a shared-prefix trace through a
    store-enabled front end under `random_store_plan` faults (poison,
    manifest flip, lease-holder kill, eviction storm, plus the ISSUE 6
    kinds).  The fault-free baseline is a SINGLE storeless engine run,
    so invariant 14 (prefix import parity) judges every finished
    stream against tokens the store could not possibly have touched —
    a poisoned record must cost a re-prefill, never a token."""
    from attention_tpu.prefixstore import PrefixStoreConfig

    if model is None or params is None:
        model, params = build_sim_model()
    config = config or default_engine_config(max_seq_len=384,
                                             num_pages=24)
    trace = shared_prefix_trace(num_requests, vocab=model.vocab,
                                seed=seed)
    engine = ServingEngine(model, params, config)
    _, baseline = replay(engine, trace)
    ids = [t["id"] for t in trace]
    reports = []
    for i in range(num_plans):
        plan = random_store_plan(seed * 9007 + i, ids, num_replicas,
                                 num_events=events_per_plan)
        r = run_frontend_plan(
            model, params, config,
            default_frontend_config(
                num_replicas, prefix_store=PrefixStoreConfig()),
            trace, plan, baseline=baseline,
        )
        if log is not None:
            log(f"store storm {i} (seed {plan.seed}): "
                f"injected={r.injected} "
                f"violations={len(r.violations)} "
                f"states={sorted(set(r.states.values()))} "
                f"error={r.surfaced_error or 'none'}")
        reports.append(r)
    return FrontendCampaignReport(seed=seed, num_replicas=num_replicas,
                                  baseline_outputs=baseline,
                                  reports=reports)


def default_fleet_config(num_replicas: int = 3, *,
                         standbys: int = 2, **overrides):
    """Disagg-campaign front-end geometry: `default_frontend_config`
    plus a 1:N-1 prefill:decode split, a standby bench for the
    autoscaler to work with, and a short-hysteresis policy so storms
    actually actuate inside campaign-length runs."""
    from attention_tpu.fleet import AutoscalerPolicy, FleetTopology

    kw: dict[str, Any] = dict(
        standbys=standbys,
        fleet=FleetTopology(prefill_replicas=1,
                            decode_replicas=num_replicas - 1),
        autoscaler=AutoscalerPolicy(
            scale_up_after=2, scale_down_after=4, cooldown_ticks=8,
            guard_window=6),
    )
    kw.update(overrides)
    return default_frontend_config(num_replicas, **kw)


def run_disagg_campaign(seed: int, *, num_plans: int = 4,
                        num_requests: int = 10, num_replicas: int = 3,
                        events_per_plan: int = 6,
                        temperature: float = 0.0,
                        config: EngineConfig | None = None,
                        model=None, params=None,
                        log: Callable[[str], None] | None = None,
                        ) -> FrontendCampaignReport:
    """The ISSUE 19 disagg storm: a mixed prefill/decode trace
    (`engine.sim.disagg_trace`) through a fleet front end (prefill +
    decode pools, standbys, autoscaler armed) under
    `random_disagg_plan` faults — poisoned handoff payloads, forced
    demotion storms, plus the ISSUE 6 kinds.  The fault-free baseline
    is a SINGLE monolithic engine run, so token parity judges every
    finished stream against tokens no handoff, resize, or fallback
    could have touched; invariant 16 balances the actuation ledger
    per plan."""
    from attention_tpu.engine.sim import disagg_trace

    if model is None or params is None:
        model, params = build_sim_model()
    # RAG headers longer than one 128-token page so handoffs actually
    # ship KV (a payload-less handoff can't exercise the
    # poison/fallback arc)
    config = config or default_engine_config(max_seq_len=384,
                                             num_pages=24)
    trace = disagg_trace(num_requests, vocab=model.vocab, seed=seed,
                         max_tokens=6, rag_prefill_len=160,
                         burst_every=4, burst_size=2)
    engine = ServingEngine(model, params, config)
    _, baseline = replay(engine, trace)
    ids = [t["id"] for t in trace]
    reports = []
    for i in range(num_plans):
        plan = random_disagg_plan(seed * 11003 + i, ids, num_replicas,
                                  num_events=events_per_plan)
        r = run_frontend_plan(
            model, params, config, default_fleet_config(num_replicas),
            trace, plan, baseline=baseline,
        )
        if log is not None:
            log(f"disagg storm {i} (seed {plan.seed}): "
                f"injected={r.injected} "
                f"violations={len(r.violations)} "
                f"states={sorted(set(r.states.values()))} "
                f"error={r.surfaced_error or 'none'}")
        reports.append(r)
    return FrontendCampaignReport(seed=seed, num_replicas=num_replicas,
                                  baseline_outputs=baseline,
                                  reports=reports)
