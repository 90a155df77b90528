"""`ServingFrontend`: N engine replicas behind one resilient door.

The layer the ROADMAP's "millions of users" story needs between
clients and `ServingEngine` replicas.  Requests are submitted once to
the front end; everything after that — routing, admission control,
deadline enforcement, retry, shedding, degradation — happens inside
the deterministic ``tick`` loop:

    submit() ─> QUEUED ──admit──> ASSIGNED ──────────> FINISHED
                  │                 │  ▲ retry            │
                  │ (deadline/shed) │  │ (backoff)        │ stream
                  ▼                 ▼  │                  ▼
          TIMED_OUT / SHED       RETRY_WAIT          on_token/on_finish
                                    │
                                    └──(budget dry)──> SHED

One tick = one scheduler round: expire deadlines in the front-end
queues, admit due arrivals (shed/route/assign), re-admit due retries,
step EVERY alive replica exactly once (keeping each engine's step
counter aligned with the global tick, which is what makes per-replica
deadline translation exact), migrate admission-stalled requests, then
feed the degradation ladder.  The headline invariant — every submitted
request terminates in exactly one of FINISHED / CANCELLED / TIMED_OUT
/ SHED, with finished requests token-identical to a fault-free
single-replica run — is pinned by `chaos.invariants` under replica-kill
storms.

Determinism: the only clocks are the tick counter and each engine's
step counter; backoff jitter is seeded (`frontend.backoff`); routing
tiebreaks on replica index.  Same seed, same trace, same fault plan →
byte-identical summary and `RunRecord`.
"""

from __future__ import annotations

import dataclasses
import enum
import itertools
import os
from typing import Any, Callable, Sequence

from attention_tpu import obs
from attention_tpu.obs import blackbox as _blackbox
from attention_tpu.obs import capacity as _capacity
from attention_tpu.obs import trace as _trace
from attention_tpu.obs.anomaly import AnomalyPolicy, AnomalyTracker
from attention_tpu.obs.forecast import ForecastPolicy, HoltForecaster, _r6
from attention_tpu.obs.postmortem import PostmortemWriter
from attention_tpu.obs.naming import (
    SERIES_TPOT_DIGEST,
    SERIES_TTFT_DIGEST,
)
from attention_tpu.engine.engine import (
    EngineConfig,
    StepLimitExceededError,
)
from attention_tpu.engine.errors import (
    DeadlineExceededError,
    HandoffCorruptError,
    PrefixStoreCorruptError,
    ReplicaDeadError,
    RequestShedError,
    StepInterruptedError,
)
from attention_tpu.engine.request import Request, SamplingParams
from attention_tpu.engine.sim import sampling_of
from attention_tpu.engine.snapshot import _request_to_dict
from attention_tpu.fleet.autoscaler import Autoscaler, AutoscalerPolicy
from attention_tpu.fleet.handoff import export_handoff, import_handoff
from attention_tpu.fleet.ledger import ActuationRecord
from attention_tpu.fleet.topology import (
    POOLS,
    FleetTopology,
    initial_pools,
)
from attention_tpu.frontend.backoff import RetryPolicy
from attention_tpu.frontend.degrade import (
    NUM_PRIORITY_CLASSES,
    DegradationLadder,
    DegradePolicy,
    ShedPolicy,
    pool_pressure,
)
from attention_tpu.frontend.migrate import MigrationRecord, drain_replica
from attention_tpu.frontend.replica import ReplicaHandle
from attention_tpu.frontend.routing import Router
from attention_tpu.frontend.supervisor import (
    ReplicaSupervisor,
    SupervisorPolicy,
    SupervisorState,
)
from attention_tpu.ops.paged import OutOfPagesError
from attention_tpu.prefixstore.records import chain_key, chain_tokens
from attention_tpu.prefixstore.store import (
    STORE_FILENAME,
    PrefixStore,
    PrefixStoreConfig,
    load_store,
    save_store,
)
from attention_tpu.utils.profiling import RunRecord

_SHED = obs.counter("frontend.shed.rejected",
                    "arrivals rejected by admission control")
_DOWNCLASSED = obs.counter("frontend.shed.downclassed",
                           "arrivals demoted one priority class")
_RETRY_SCHED = obs.counter("frontend.retry.scheduled",
                           "requeues placed on the backoff queue")
_RETRY_EXHAUSTED = obs.counter("frontend.retry.exhausted",
                               "requests shed with the budget dry")
_MIGRATED = obs.counter("frontend.retry.migrated",
                        "admission-stalled requests moved off a replica")
_DEADLINE_EXPIRED = obs.counter("frontend.deadline.expired",
                                "front-end-side deadline expiries")
_KILLED = obs.counter("frontend.replica.killed", "replica kills")
_RESTARTED = obs.counter("frontend.replica.restarted",
                         "replica restarts")
_STEP_DOWN = obs.counter("frontend.degrade.step_down",
                         "degradation-ladder level drops")
_RECOVER = obs.counter("frontend.degrade.recover",
                       "degradation-ladder level recoveries")
_LEVEL_G = obs.gauge("frontend.degrade.level",
                     "current degradation-ladder level")
_PRESSURE_G = obs.gauge("frontend.pressure.mean",
                        "mean replica pressure after the tick")
_R_QUEUE_G = obs.gauge("frontend.replica.queue_depth",
                       "per-replica waiting+running requests")
_R_UTIL_G = obs.gauge("frontend.replica.page_util",
                      "per-replica page-pool utilization")
_PROMOTED = obs.counter("frontend.replica.promoted",
                        "warm standbys promoted on a DEAD verdict")
# client-observed latency digests (obs.quantile): per-replica series
# merge bucket-wise into the fleet view, so `cli obs slo` / the SLO
# observatory aggregate replicas without resampling
_TTFT_DIG = obs.digest(SERIES_TTFT_DIGEST,
                       "client TTFT quantile digest (front-end ticks)")
_TPOT_DIG = obs.digest(SERIES_TPOT_DIGEST,
                       "client TPOT quantile digest (ticks per token)")


class FrontendRequestState(enum.Enum):
    QUEUED = "queued"          # submitted, not yet on a replica
    ASSIGNED = "assigned"      # live on a replica
    RETRY_WAIT = "retry_wait"  # backing off before re-assignment
    FINISHED = "finished"
    CANCELLED = "cancelled"
    TIMED_OUT = "timed_out"
    SHED = "shed"


#: the front-end terminal set — the resilience invariant's alphabet
FRONTEND_TERMINAL = frozenset({
    FrontendRequestState.FINISHED, FrontendRequestState.CANCELLED,
    FrontendRequestState.TIMED_OUT, FrontendRequestState.SHED,
})

#: terminal state -> its trace event name (obs.naming TRACE_EVENTS);
#: the `_finalize` funnel records exactly one of these per request
_TERMINAL_EVENT = {
    FrontendRequestState.FINISHED: "finished",
    FrontendRequestState.CANCELLED: "cancelled",
    FrontendRequestState.TIMED_OUT: "timed_out",
    FrontendRequestState.SHED: "shed",
}

# RETRY_WAIT -> RETRY_WAIT is a real edge: a retry that finds no alive
# replica goes straight back on the backoff queue.  ASSIGNED/RETRY_WAIT
# -> SHED is retry-budget exhaustion.
_FE_TRANSITIONS: dict[FrontendRequestState,
                      frozenset[FrontendRequestState]] = {
    FrontendRequestState.QUEUED: frozenset(
        {FrontendRequestState.ASSIGNED, FrontendRequestState.RETRY_WAIT,
         FrontendRequestState.CANCELLED, FrontendRequestState.TIMED_OUT,
         FrontendRequestState.SHED}
    ),
    FrontendRequestState.ASSIGNED: frozenset(
        {FrontendRequestState.RETRY_WAIT, FrontendRequestState.FINISHED,
         FrontendRequestState.CANCELLED, FrontendRequestState.TIMED_OUT,
         FrontendRequestState.SHED}
    ),
    FrontendRequestState.RETRY_WAIT: frozenset(
        {FrontendRequestState.ASSIGNED, FrontendRequestState.RETRY_WAIT,
         FrontendRequestState.CANCELLED, FrontendRequestState.TIMED_OUT,
         FrontendRequestState.SHED}
    ),
    FrontendRequestState.FINISHED: frozenset(),
    FrontendRequestState.CANCELLED: frozenset(),
    FrontendRequestState.TIMED_OUT: frozenset(),
    FrontendRequestState.SHED: frozenset(),
}


@dataclasses.dataclass
class FrontendRequest:
    """One client request as the front end sees it — survives replica
    deaths and re-assignments (the per-replica engine `Request` objects
    are disposable; this record is the durable truth)."""

    request_id: str
    prompt: tuple[int, ...]
    sampling: SamplingParams
    arrival: int                      # front-end tick
    deadline: int | None              # absolute tick (None = no TTL)
    priority: int = 1                 # 0 = highest class
    session: str | None = None
    seq: int = 0

    state: FrontendRequestState = FrontendRequestState.QUEUED
    tokens: list[int] = dataclasses.field(default_factory=list)
    #: which replica emitted each token (parallel to ``tokens``) — the
    #: no-double-serve invariant's evidence trail
    emitters: list[str] = dataclasses.field(default_factory=list)
    replica_id: str | None = None
    last_replica: str | None = None
    routed_by: str | None = None
    attempts: int = 0                 # requeues consumed
    next_retry: int | None = None
    assigned_tick: int = -1
    waiting_since: int | None = None  # stall-detection bookkeeping
    downclassed: bool = False
    prefix_cached_tokens: int = 0
    first_token_tick: int | None = None
    finish_tick: int = -1
    error: BaseException | None = None

    @property
    def is_terminal(self) -> bool:
        return self.state in FRONTEND_TERMINAL

    def transition(self, new: FrontendRequestState) -> None:
        if new not in _FE_TRANSITIONS[self.state]:
            raise ValueError(
                f"request {self.request_id}: illegal front-end "
                f"transition {self.state.name} -> {new.name}"
            )
        self.state = new


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Front-end knobs; every time-like field is in ticks."""

    num_replicas: int = 2
    seed: int = 0
    retry: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    shed: ShedPolicy = dataclasses.field(default_factory=ShedPolicy)
    degrade: DegradePolicy = dataclasses.field(
        default_factory=DegradePolicy)
    default_ttl_ticks: int | None = None  # applied when submit has none
    stall_ticks: int = 4   # un-admitted for this long -> migrate
    # durability (engine.snapshot): when BOTH are set each replica
    # snapshots every N of its own steps into
    # <snapshot_dir>/<replica_id>/ and restart_replica recovers warm
    snapshot_dir: str | None = None
    snapshot_every: int | None = None
    # proactive failure handling (frontend.supervisor / .migrate):
    # detection thresholds, plus N spare engine-less handles promoted
    # warm on a DEAD verdict
    supervisor: SupervisorPolicy = dataclasses.field(
        default_factory=SupervisorPolicy)
    standbys: int = 0
    # load forecasting (obs.forecast): None = disabled, and disabled
    # means ZERO work in the tick loop — the same contract telemetry
    # honors.  Even when set it is passive bookkeeping; only the
    # advisory flag inside the policy makes it *log* (never act).
    forecast: ForecastPolicy | None = None
    # global prefix tier (attention_tpu.prefixstore): None = disabled
    # = byte-identical to the storeless front end.  When set, ONE
    # shared `PrefixStore` is built for the fleet, every replica
    # engine exports/imports through it, routing consults store hits,
    # arrivals coalesce behind single-flight prefill leases, and —
    # with snapshot_dir set — store state persists across warm
    # restarts as its own CRC'd-section file
    prefix_store: PrefixStoreConfig | None = None
    # incident layer (obs.anomaly / obs.postmortem): ``anomaly`` arms
    # the online detectors — deterministic bookkeeping fed from the
    # tick loop, advisory-only, None = disabled = zero tick-loop work
    # (the forecast contract).  ``incident_dir`` arms the postmortem
    # writer: detector firings, replica kills, and injected faults
    # each dump one atomic `incident-<tick>/` bundle there.
    anomaly: AnomalyPolicy | None = None
    incident_dir: str | None = None
    # disaggregated serving (attention_tpu.fleet): ``fleet`` splits
    # the replicas into role-typed prefill/decode pools — fresh
    # admissions route to the prefill pool and at prompt-commit hand
    # off (shipping committed KV pages) to the decode pool.  None =
    # monolithic = byte-identical to the pre-fleet front end.
    fleet: FleetTopology | None = None
    # the closed-loop elastic autoscaler (requires ``fleet``; the
    # standby bench is what it promotes from / demotes to)
    autoscaler: AutoscalerPolicy | None = None

    def validate(self) -> None:
        if self.num_replicas < 1:
            raise ValueError(
                f"num_replicas must be >= 1, got {self.num_replicas}"
            )
        if self.standbys < 0:
            raise ValueError(
                f"standbys must be >= 0, got {self.standbys}"
            )
        if self.stall_ticks < 1:
            raise ValueError(
                f"stall_ticks must be >= 1, got {self.stall_ticks}"
            )
        if (self.default_ttl_ticks is not None
                and self.default_ttl_ticks < 1):
            raise ValueError(
                f"default_ttl_ticks must be >= 1, got "
                f"{self.default_ttl_ticks}"
            )
        if (self.snapshot_dir is None) != (self.snapshot_every is None):
            raise ValueError(
                "snapshot_dir and snapshot_every must be set together"
            )
        if self.snapshot_every is not None and self.snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {self.snapshot_every}"
            )
        self.retry.validate()
        self.shed.validate()
        self.degrade.validate()
        self.supervisor.validate()
        if self.forecast is not None:
            self.forecast.validate()
        if self.prefix_store is not None:
            self.prefix_store.validate()
        if self.anomaly is not None:
            self.anomaly.validate()
        if self.fleet is not None:
            self.fleet.validate(num_replicas=self.num_replicas)
        if self.autoscaler is not None:
            if self.fleet is None:
                raise ValueError(
                    "autoscaler requires a fleet topology "
                    "(FrontendConfig.fleet)")
            self.autoscaler.validate()


def _cumulative_series(pairs, n: int) -> list[float]:
    """Per-tick running mean of ``(tick, value)`` marks over ticks
    ``0..n-1`` (0.0 before the first mark) — the tick-indexed view of
    the latency digests the forecaster consumes."""
    marks = sorted(pairs)
    out: list[float] = []
    i = 0
    total = 0.0
    count = 0
    for t in range(n):
        while i < len(marks) and marks[i][0] <= t:
            total += marks[i][1]
            count += 1
            i += 1
        out.append(total / count if count else 0.0)
    return out


class ForecastTracker:
    """Per-tick fleet sample recorder + incremental pressure forecaster.

    Exists only when ``FrontendConfig.forecast`` is set; every hook in
    the serving hot path is a single ``tracker is None`` check, the
    zero-overhead contract `frontend.degrade` documents for telemetry
    applied to forecasting.  The tracker never reads the obs registry
    and is never consulted for control flow: ``forecast_pressure`` is
    an advisory surface, and the advisory hooks only *log* what
    forecast-driven admission would have done.
    """

    def __init__(self, policy: ForecastPolicy):
        self.policy = policy
        # per-tick sample series (virtual ticks; index == tick)
        self.pressure: list[float] = []
        self.queue_depth: list[float] = []
        self.admissions: list[float] = []
        self.tokens: list[float] = []
        #: tokens emitted per replica over the whole run (capacity input)
        self.replica_tokens: dict[str, int] = {}
        self._pressure_fc = HoltForecaster(policy)
        self._tokens_total = 0
        self._tokens_seen = 0
        #: events_log prefix already counted for the admissions series
        self.events_seen = 0
        #: one-step-ahead mean-pressure forecast after the last tick
        self.forecast_pressure: float | None = None

    def note_token(self, replica_id: str) -> None:
        self._tokens_total += 1
        self.replica_tokens[replica_id] = (
            self.replica_tokens.get(replica_id, 0) + 1)

    def record_tick(self, pressure: float, queue_depth: int,
                    admissions: int) -> float:
        """Append one sample row; returns the one-step forecast of the
        mean fleet pressure (what next tick is predicted to look like)."""
        self.pressure.append(float(pressure))
        self.queue_depth.append(float(queue_depth))
        self.admissions.append(float(admissions))
        self.tokens.append(float(self._tokens_total - self._tokens_seen))
        self._tokens_seen = self._tokens_total
        self._pressure_fc.observe(pressure)
        self.forecast_pressure = self._pressure_fc.predict(1)
        return self.forecast_pressure

    def report(self, rows: list[dict[str, Any]], *, alive: int,
               shed_pressure: float, downclass_pressure: float,
               horizon: int | None = None) -> dict[str, Any]:
        """The combined observatory document (`obs.capacity`) over the
        recorded samples plus tick-indexed TTFT/TPOT series derived
        from the latency rows.  Pure: calling it twice yields the same
        bytes — the chaos ``forecast_determinism`` invariant."""
        n = len(self.pressure)
        samples = {
            "pressure": self.pressure,
            "queue_depth": self.queue_depth,
            "admissions": self.admissions,
            "tokens": self.tokens,
            "ttft": _cumulative_series(
                ((r["first_token_tick"],
                  float(r["first_token_tick"] - r["submit_tick"]))
                 for r in rows if r["first_token_tick"] is not None), n),
            "tpot": _cumulative_series(
                ((r["finish_tick"],
                  (r["finish_tick"] - r["first_token_tick"])
                  / (r["output_tokens"] - 1))
                 for r in rows if r["first_token_tick"] is not None
                 and r["output_tokens"] >= 2), n),
        }
        inputs = {
            "ticks": n,
            "alive": alive,
            "last_pressure": self.pressure[-1] if self.pressure else 0.0,
            "replica_tokens": dict(sorted(self.replica_tokens.items())),
        }
        return _capacity.observatory_report(
            samples, inputs, policy=self.policy, horizon=horizon,
            shed_pressure=shed_pressure,
            downclass_pressure=downclass_pressure)


class ServingFrontend:
    """Deterministic multi-replica serving front end (module doc)."""

    def __init__(self, model, params, engine_config: EngineConfig,
                 config: FrontendConfig | None = None, *,
                 on_token: Callable[..., None] | None = None,
                 on_finish: Callable[..., None] | None = None):
        config = config or FrontendConfig()
        config.validate()
        self.model = model
        self.params = params
        self.engine_config = engine_config
        self.config = config
        self.on_token = on_token
        self.on_finish = on_finish

        # deterministic mirrors of the obs counters (telemetry is off
        # by default; the summary must not depend on it)
        self.counts = {
            "shed_rejected": 0, "downclassed": 0,
            "retries_scheduled": 0, "retries_exhausted": 0,
            "migrations": 0, "deadline_expired": 0,
            "replica_kills": 0, "replica_restarts": 0,
            "warm_restarts": 0, "warm_adoptions": 0,
            "live_migrations": 0, "migrations_stranded": 0,
            "standby_promotions": 0, "supervisor_suspects": 0,
            "supervisor_degraded": 0, "supervisor_dead": 0,
            "supervisor_recoveries": 0,
            "anomaly_firings": 0, "incidents": 0,
            "handoffs": 0, "handoff_fallbacks": 0,
            "reprefill_avoided_tokens": 0,
            "scale_ups": 0, "scale_downs": 0, "actuation_vetoes": 0,
        }
        self._tick = 0
        #: incident-bundle writer (None = no dumping) — constructed
        #: BEFORE the store load so a corrupt persisted store already
        #: has somewhere to file its incident
        self.postmortem = (PostmortemWriter(config.incident_dir)
                           if config.incident_dir is not None else None)
        #: online anomaly detectors (None = disabled = zero tick work)
        self.anomaly = (AnomalyTracker(config.anomaly)
                        if config.anomaly is not None else None)

        # fleet prefix store: built (or warm-reloaded) BEFORE the
        # replicas so every engine incarnation attaches to the one
        # shared instance.  A corrupt persisted store is the same
        # non-event a corrupt snapshot is: typed, counted, start cold.
        self.prefix_store: PrefixStore | None = None
        if config.prefix_store is not None:
            path = (os.path.join(config.snapshot_dir, STORE_FILENAME)
                    if config.snapshot_dir else None)
            if path is not None and os.path.exists(path):
                try:
                    self.prefix_store = load_store(
                        path, config.prefix_store)
                except PrefixStoreCorruptError:
                    self.prefix_store = PrefixStore(config.prefix_store)
                    self.prefix_store.note_corrupt()
                    self._incident("typed_error", {
                        "error": "PrefixStoreCorruptError",
                        "path": path})
            else:
                self.prefix_store = PrefixStore(config.prefix_store)
        #: requests coalesced behind a single-flight prefill lease,
        #: re-evaluated each tick in seq order
        self._store_wait: list[FrontendRequest] = []
        self._coalesced_ids: set[str] = set()

        self.router = Router()
        self.ladder = DegradationLadder(config.degrade)
        self.supervisor = ReplicaSupervisor(config.supervisor)
        self.replicas = [
            self._make_handle(f"replica-{i}")
            for i in range(config.num_replicas)
        ]
        #: engine-less spares, promoted (in order) on a DEAD verdict
        self.standby_pool = [
            self._make_handle(f"standby-{k}", spare=True)
            for k in range(config.standbys)
        ]
        self._seq = itertools.count()
        self.requests: dict[str, FrontendRequest] = {}
        self._pending: list[FrontendRequest] = []  # (arrival, seq) order
        self._retry: list[FrontendRequest] = []
        #: unified append-ordered event log — ("verdict", tick, replica,
        #: old, new, signals) and ("admit", tick, request, replica) in
        #: the exact order they happened; the supervisor-consistency
        #: checker replays it (append order IS the global order, which
        #: sidesteps within-tick phase ordering entirely)
        self.events_log: list[tuple] = []
        #: every drain decision, in order (`frontend.migrate`)
        self.migrations: list[MigrationRecord] = []
        #: load forecaster (None = disabled = zero tick-loop work)
        self.forecast = (ForecastTracker(config.forecast)
                         if config.forecast is not None else None)
        #: fleet role map, replica id -> pool (empty = monolithic:
        #: every fleet hook is a single truthiness check, the
        #: zero-overhead contract telemetry/forecasting honor)
        self.pool_of: dict[str, str] = (
            initial_pools([h.replica_id for h in self.replicas],
                          config.fleet)
            if config.fleet is not None else {})
        #: closed-loop controller (None = static fleet)
        self.autoscaler = (Autoscaler(config.autoscaler)
                           if config.autoscaler is not None else None)
        #: executed resizes, in order — chaos invariant 16 balances
        #: this ledger against the blackbox ring
        self.actuations: list[ActuationRecord] = []
        # chaos knobs (chaos.faults): corrupt the next N handoff
        # payloads / force N hysteresis-bypassing demotions
        self._poison_handoffs = 0
        self._force_demotions = 0
        #: armed mis-actuation guards: (scale_down tick, pool,
        #: shed_rejected count at actuation time)
        self._guards: list[tuple[int, str, int]] = []

    def _make_handle(self, replica_id: str, *,
                     spare: bool = False) -> ReplicaHandle:
        # the token callback closes over the replica id so every
        # streamed token records WHICH engine emitted it — the
        # no-double-serve invariant's raw evidence
        return ReplicaHandle(
            replica_id, self.model, self.params, self.engine_config,
            snapshot_dir=(os.path.join(self.config.snapshot_dir,
                                       replica_id)
                          if self.config.snapshot_dir else None),
            snapshot_every=self.config.snapshot_every,
            on_token=(lambda req, tok, _rid=replica_id:
                      self._on_engine_token(_rid, req, tok)),
            on_finish=self._on_engine_finish,
            on_timeout=self._on_engine_timeout,
            spare=spare,
            prefix_store=self.prefix_store,
        )

    # -- intake -----------------------------------------------------------

    @property
    def current_tick(self) -> int:
        return self._tick

    def submit(self, prompt, sampling: SamplingParams | None = None, *,
               request_id: str | None = None, arrival: int | None = None,
               ttl_ticks: int | None = None, priority: int = 1,
               session: str | None = None) -> FrontendRequest:
        """Register one request.  ``ttl_ticks`` is relative to arrival
        (falling back to the config default); validation happens here
        so the tick loop never trips over a malformed request."""
        sampling = sampling or SamplingParams()
        sampling.validate(self.model.vocab)
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if any(not (0 <= t < self.model.vocab) for t in prompt):
            raise ValueError(
                f"prompt tokens must be in the vocab "
                f"[0, {self.model.vocab})"
            )
        total = len(prompt) + sampling.max_tokens - 1
        if total > self.engine_config.max_seq_len:
            raise ValueError(
                f"prompt + max_tokens - 1 = {total} exceeds "
                f"max_seq_len {self.engine_config.max_seq_len}"
            )
        if not (0 <= priority < NUM_PRIORITY_CLASSES):
            raise ValueError(
                f"priority must be in [0, {NUM_PRIORITY_CLASSES}), "
                f"got {priority}"
            )
        if ttl_ticks is not None and ttl_ticks < 1:
            raise ValueError(f"ttl_ticks must be >= 1, got {ttl_ticks}")
        arrival = self._tick if arrival is None else int(arrival)
        ttl = (ttl_ticks if ttl_ticks is not None
               else self.config.default_ttl_ticks)
        seq = next(self._seq)
        fr = FrontendRequest(
            request_id=request_id or f"req-{seq}",
            prompt=prompt,
            sampling=sampling,
            arrival=arrival,
            deadline=None if ttl is None else arrival + ttl,
            priority=int(priority),
            session=session,
            seq=seq,
        )
        if fr.request_id in self.requests:
            raise ValueError(f"duplicate request id {fr.request_id!r}")
        self.requests[fr.request_id] = fr
        self._pending.append(fr)
        self._pending.sort(key=lambda f: (f.arrival, f.seq))
        self._trace_event(fr, "submitted", tick=fr.arrival,
                          tenant=fr.session, priority=fr.priority)
        return fr

    def cancel(self, request_id: str) -> bool:
        """Client abandons a request wherever it is; False when the
        id is unknown or already terminal."""
        fr = self.requests.get(request_id)
        if fr is None or fr.is_terminal:
            return False
        if fr.state is FrontendRequestState.ASSIGNED:
            handle = self._handle(fr.replica_id)
            if handle is not None and handle.alive:
                handle.engine.cancel(request_id)
        self._finalize(fr, FrontendRequestState.CANCELLED)
        return True

    # -- engine callbacks -------------------------------------------------

    def _on_engine_token(self, replica_id: str, req: Request,
                         token: int) -> None:
        fr = self.requests[req.request_id]
        if not fr.tokens:
            fr.first_token_tick = self._tick
        fr.tokens.append(int(token))
        fr.emitters.append(replica_id)
        fr.waiting_since = None
        if self.forecast is not None:
            self.forecast.note_token(replica_id)
        if self.anomaly is not None:
            self.anomaly.observe_tokens(
                self._tick, replica_id, req.request_id, 1)
        if self.on_token is not None:
            self.on_token(fr, int(token))

    def _on_engine_finish(self, req: Request) -> None:
        fr = self.requests[req.request_id]
        fr.prefix_cached_tokens = req.prefix_cached_tokens
        self._finalize(fr, FrontendRequestState.FINISHED)
        if self.on_finish is not None:
            self.on_finish(fr)

    def _on_engine_timeout(self, req: Request) -> None:
        fr = self.requests[req.request_id]
        self._finalize(
            fr, FrontendRequestState.TIMED_OUT,
            error=DeadlineExceededError(
                f"request {fr.request_id} expired at tick "
                f"{self._tick} (deadline {fr.deadline})"
            ),
        )

    # -- tick loop --------------------------------------------------------

    def tick(self) -> int:
        """One deterministic scheduler round; returns the tick served."""
        t = self._tick
        with obs.span("frontend.tick"):
            self._expire_queued(t)
            self._heartbeat_leases(t)
            self._admit_store_waiters(t)
            self._admit_arrivals(t)
            self._admit_retries(t)
            self._step_replicas(t)
            self._handoff_committed(t)
            self._supervise(t)
            self._migrate_stalled(t)
            self._update_ladder_and_gauges(t)
            self._autoscale(t)
            self._persist_prefix_store(t)
        self._tick += 1
        return t

    def has_work(self) -> bool:
        return any(not fr.is_terminal for fr in self.requests.values())

    def run(self, *, max_ticks: int | None = None) -> dict[str, Any]:
        """Tick until every submitted request is terminal."""
        while self.has_work():
            if max_ticks is not None and self._tick >= max_ticks:
                live = [fr.request_id
                        for fr in self.requests.values()
                        if not fr.is_terminal]
                raise StepLimitExceededError(
                    f"front end exceeded max_ticks={max_ticks} with "
                    f"{len(live)} live request(s): {live[:5]}"
                )
            self.tick()
        return self.summary()

    # -- chaos hooks ------------------------------------------------------

    def kill_replica(self, replica_id: str) -> bool:
        """Fail-stop one replica NOW: its engine (pages, caches,
        in-flight work) is gone; every request assigned to it enters
        the retry-with-backoff path, streamed tokens preserved."""
        handle = self._handle(replica_id)
        if handle is None or not handle.alive:
            return False
        victims = sorted(
            (fr for fr in self.requests.values()
             if fr.state is FrontendRequestState.ASSIGNED
             and fr.replica_id == replica_id),
            key=lambda f: f.seq,
        )
        # note BEFORE the kill so the record carries the dying
        # incarnation's live coordinates
        self._bb_note("replica_kill", replica_id=replica_id,
                      victims=len(victims))
        handle.kill()
        self.router.forget_replica(replica_id)
        self.counts["replica_kills"] += 1
        _KILLED.inc()
        cause = ReplicaDeadError(
            f"replica {replica_id} died at tick {self._tick}"
        )
        self._incident("typed_error", {
            "error": "ReplicaDeadError", "replica": replica_id,
            "victims": len(victims)})
        for fr in victims:
            self._requeue(fr, self._tick, cause)
        return True

    def restart_replica(self, replica_id: str, *,
                        warm: bool | None = None) -> bool:
        """Bring a dead replica back at the current tick.

        ``warm`` defaults to "whenever the replica has a snapshot
        directory": the handle recovers from its newest valid snapshot
        + journal replay and the front end then *reconciles* the
        restored in-flight requests against its own bookkeeping —
        requests whose restored token position matches the streamed
        prefix are adopted in place (no re-prefill, no retry delay);
        anything stale, torn, or already re-homed is cancelled on the
        engine and left to the cold `resume_request` route.  A corrupt
        or missing snapshot degrades to a plain cold restart."""
        handle = self._handle(replica_id)
        if handle is None or handle.alive:
            return False
        want_warm = handle.snapshot_dir is not None \
            if warm is None else warm
        mode = handle.restart(
            tick=self._tick,
            warm_from=handle.snapshot_dir if want_warm else None,
        )
        # fresh engine -> fresh judgement (and the recovery verdict
        # lands in the event log BEFORE any adoption re-admissions)
        verdict = self.supervisor.reset(self._tick, replica_id)
        if verdict is not None:
            self.events_log.append((
                "verdict", self._tick, replica_id,
                verdict.old.value, verdict.new.value,
                list(verdict.signals)))
            self.counts["supervisor_recoveries"] += 1
        if mode == "warm":
            self.counts["warm_restarts"] += 1
            self._reconcile_restored(handle)
        self._apply_ladder_to(handle)
        self.counts["replica_restarts"] += 1
        _RESTARTED.inc()
        self._bb_note("replica_restart", replica_id=replica_id,
                      mode=mode)
        return True

    def _reconcile_restored(self, handle: ReplicaHandle) -> None:
        """Square a warm-restored engine with front-end bookkeeping.

        The snapshot+journal reconstruct the engine's view of its
        in-flight requests; the front end is the source of truth for
        what the CLIENT saw.  A restored request is adopted only when
        it is still wanted (in RETRY_WAIT after the kill-time requeue)
        and its restored output position exactly matches the tokens
        already streamed — a torn journal tail shows up here as a
        position mismatch and falls back to the cold path, preserving
        token parity."""
        eng = handle.engine
        t = self._tick
        for req in (*eng.scheduler.waiting, *eng.scheduler.running):
            fr = self.requests.get(req.request_id)
            if (fr is None
                    or fr.state is not FrontendRequestState.RETRY_WAIT
                    or list(req.output_tokens) != list(fr.tokens)):
                eng.cancel(req.request_id)
                continue
            if fr in self._retry:
                self._retry.remove(fr)
            fr.next_retry = None
            fr.transition(FrontendRequestState.ASSIGNED)
            fr.replica_id = handle.replica_id
            fr.routed_by = "warm-restore"
            fr.assigned_tick = t
            fr.waiting_since = None
            # deadline in the restarted replica's own step space
            req.deadline_step = handle.local_deadline(fr.deadline)
            self.counts["warm_adoptions"] += 1
            self._trace_event(fr, "warm_adopted",
                              tokens_restored=len(fr.tokens))
            self.events_log.append(
                ("admit", t, fr.request_id, handle.replica_id))

    # -- internals --------------------------------------------------------

    def _handle(self, replica_id: str | None) -> ReplicaHandle | None:
        return next((h for h in self.replicas
                     if h.replica_id == replica_id), None)

    def _trace_event(self, fr: FrontendRequest, event: str, *,
                     tick: int | None = None, **extra: Any) -> None:
        """Stamp one front-end trace event with the request's current
        replica coordinates (None/-1 while it sits in a front-end
        queue)."""
        if not _trace.active():
            return
        handle = self._handle(fr.replica_id)
        _trace.record(
            fr.request_id, event,
            tick=self._tick if tick is None else tick,
            replica=fr.replica_id,
            incarnation=handle.deaths if handle is not None else 0,
            step=(handle.engine.current_step
                  if handle is not None and handle.alive else -1),
            **extra,
        )

    def _bb_note(self, kind: str, *, replica_id: str | None = None,
                 tick: int | None = None, **extra: Any) -> None:
        """Stamp one fleet flight-recorder event with the replica's
        current deterministic coordinates (incarnation -1 step while
        it is down), mirroring `_trace_event`'s discipline for
        per-request traces."""
        if not _blackbox.active():
            return
        handle = self._handle(replica_id)
        _blackbox.note(
            kind,
            tick=self._tick if tick is None else tick,
            replica=replica_id,
            incarnation=handle.deaths if handle is not None else 0,
            step=(handle.engine.current_step
                  if handle is not None and handle.alive else -1),
            **extra,
        )

    def _incident(self, cause: str, detail: dict[str, Any]) -> None:
        """File one incident bundle (dedup'd by the writer) for a
        typed error, detector firing, or chaos trigger; a no-op
        without an ``incident_dir``."""
        if self.postmortem is None:
            return
        if self.postmortem.maybe_dump(
                tick=self._tick, cause=cause, detail=detail) is not None:
            self.counts["incidents"] += 1

    def _finalize(self, fr: FrontendRequest,
                  state: FrontendRequestState, *,
                  error: BaseException | None = None) -> None:
        fr.transition(state)
        fr.finish_tick = self._tick
        fr.next_retry = None
        fr.waiting_since = None
        if error is not None:
            fr.error = error
        if fr in self._pending:
            self._pending.remove(fr)
        if fr in self._retry:
            self._retry.remove(fr)
        if fr in self._store_wait:
            self._store_wait.remove(fr)
        if self.prefix_store is not None:
            # a terminal leader frees its single-flight leases NOW
            # (waiters take over next tick) instead of waiting out
            # the tick-expiry window
            self.prefix_store.leases.release_owner(fr.request_id)
        self._trace_event(fr, _TERMINAL_EVENT[state])
        if state is FrontendRequestState.SHED:
            # the flight recorder's watermark-shed / budget-dry event
            # (both shed paths funnel through here)
            self._bb_note("shed", replica_id=fr.last_replica,
                          request=fr.request_id,
                          cause=type(fr.error).__name__
                          if fr.error is not None else None)
        if self.anomaly is not None:
            n = len(fr.tokens)
            ttft = (fr.first_token_tick - fr.arrival
                    if fr.first_token_tick is not None else None)
            tpot = ((fr.finish_tick - fr.first_token_tick) / (n - 1)
                    if fr.first_token_tick is not None and n > 1
                    else None)
            self.anomaly.observe_latency(self._tick, ttft, tpot)
            self.anomaly.forget_request(fr.request_id)
        if obs.enabled() and state is FrontendRequestState.FINISHED:
            labels = {"replica": fr.replica_id or "none"}
            if fr.first_token_tick is not None:
                _TTFT_DIG.observe(
                    max(fr.first_token_tick - fr.arrival, 0), **labels)
                if len(fr.tokens) > 1:
                    _TPOT_DIG.observe(
                        (fr.finish_tick - fr.first_token_tick)
                        / (len(fr.tokens) - 1), **labels)

    def _expire_queued(self, t: int) -> None:
        """Deadline sweep over the FRONT-END queues (pending arrivals
        and the backoff queue); requests live on a replica are swept
        by that engine's own per-step deadline check."""
        for fr in [f for f in (*self._pending, *self._retry,
                               *self._store_wait)
                   if f.deadline is not None and f.deadline <= t]:
            self.counts["deadline_expired"] += 1
            _DEADLINE_EXPIRED.inc()
            self._finalize(
                fr, FrontendRequestState.TIMED_OUT,
                error=DeadlineExceededError(
                    f"request {fr.request_id} expired at tick {t} "
                    f"before reaching a replica (deadline "
                    f"{fr.deadline})"
                ),
            )

    def _shed(self, fr: FrontendRequest, t: int, why: str) -> None:
        self.counts["shed_rejected"] += 1
        _SHED.inc()
        self._finalize(
            fr, FrontendRequestState.SHED,
            error=RequestShedError(
                f"request {fr.request_id} shed at tick {t}: {why}"
            ),
        )

    def _admit_arrivals(self, t: int) -> None:
        while self._pending and self._pending[0].arrival <= t:
            fr = self._pending.pop(0)
            # admission control: judge against the BEST alive replica
            # (pressure recomputed per arrival — each admission grows
            # a queue, so a big burst sheds its own tail)
            best, _ = pool_pressure(
                self.replicas, queue_cap=self.config.shed.queue_cap)
            lowest = fr.priority >= NUM_PRIORITY_CLASSES - 1
            if lowest and (best >= self.config.shed.shed_pressure
                           or self.ladder.level >= 3):
                self._shed(
                    fr, t,
                    f"priority-{fr.priority} arrival under pressure "
                    f"{best:.2f} (ladder level {self.ladder.level})",
                )
                continue
            if (not lowest and fr.priority > 0
                    and best >= self.config.shed.downclass_pressure):
                fr.priority += 1
                fr.downclassed = True
                self.counts["downclassed"] += 1
                _DOWNCLASSED.inc()
            self._assign(fr, t)

    def _admit_retries(self, t: int) -> None:
        due = sorted(
            (fr for fr in self._retry if fr.next_retry <= t),
            key=lambda f: (f.next_retry, f.seq),
        )
        for fr in due:
            self._retry.remove(fr)
            fr.next_retry = None
            self._assign(fr, t, exclude=fr.last_replica)

    def _heartbeat_leases(self, t: int) -> None:
        """A prefill lease belongs to a REQUEST, not a replica: while
        the owning request is live the front end refreshes its leases
        every tick, so a long prefill (many chunked steps) never loses
        its flight to mere elapsed time, and a replica kill just moves
        the same leader through the retry path.  Tick expiry is then
        purely the dead-leader backstop — an owner that vanished
        without its terminal release — which is exactly when waiters
        MUST stop waiting."""
        if self.prefix_store is None:
            return
        leases = self.prefix_store.leases
        if leases.expire(now=t):
            for key in leases.last_expired:
                self._bb_note("lease_expire", tick=t, key=key[:12])
        for key, owner in leases.active(now=t):
            fr = self.requests.get(owner)
            if fr is not None and not fr.is_terminal:
                leases.acquire(key, owner, now=t)

    def _admit_store_waiters(self, t: int) -> None:
        """Re-evaluate every coalesced request (seq order): the leader
        exporting its chain, its terminal release, or plain lease
        expiry all flip the gate, and the waiter then assigns — almost
        always straight into an import hit."""
        if self.prefix_store is None or not self._store_wait:
            return
        waiting = sorted(self._store_wait, key=lambda f: f.seq)
        self._store_wait = []
        for fr in waiting:
            if not fr.is_terminal:
                self._assign(fr, t)

    def _store_gate(self, fr: FrontendRequest, t: int) -> bool:
        """Single-flight de-dup: True = proceed to routing, False =
        coalesced into ``_store_wait`` behind another request's
        prefill lease.  Deterministic: every input is the tick clock,
        the store's contents, and seq order."""
        store = self.prefix_store
        if store is None or fr.tokens:
            return True   # resumes re-prefill their own stream
        ps = self.engine_config.page_size
        key_toks = chain_tokens(fr.prompt, ps)
        if key_toks is None:
            return True   # no full page is shareable
        if store.has_chain(fr.prompt, ps, now=t):
            return True   # import will serve it
        if any(h.alive and h.peek_prefix_pages(fr.prompt) > 0
               for h in self.replicas):
            return True   # a replica holds it locally; affinity routes
        key = chain_key(key_toks)
        owner = store.leases.holder(key, now=t)
        if owner is None or owner == fr.request_id:
            if owner is None:   # fresh grant (not a leader refresh)
                self._bb_note("lease_grant", tick=t,
                              request=fr.request_id, key=key[:12])
            store.leases.acquire(key, fr.request_id, now=t)
            return True   # this request leads the flight
        if fr.request_id not in self._coalesced_ids:
            self._coalesced_ids.add(fr.request_id)
            store.note_coalesced()
        self._store_wait.append(fr)
        return False

    def _persist_prefix_store(self, t: int) -> None:
        """Store durability rides the snapshot cadence: with both a
        store and a snapshot directory configured, the whole store
        lands as its own CRC'd-section file every ``snapshot_every``
        ticks — same atomic write discipline as engine snapshots, so
        a warm fleet restart reloads the prefix tier too."""
        if (self.prefix_store is None
                or self.config.snapshot_dir is None
                or self.config.snapshot_every is None
                or (t + 1) % self.config.snapshot_every != 0):
            return
        save_store(
            self.prefix_store,
            os.path.join(self.config.snapshot_dir, STORE_FILENAME),
        )

    def _assign(self, fr: FrontendRequest, t: int,
                exclude: str | None = None) -> None:
        if not self._store_gate(fr, t):
            return
        eligible = self.supervisor.eligible_ids(self.replicas)
        if self.pool_of:
            # role-typed placement is a PREFERENCE, never a
            # correctness boundary: fresh admissions prefer the
            # prefill pool, resumed streams the decode pool, and an
            # empty intersection falls back to the whole healthy set
            pool = "decode" if fr.tokens else "prefill"
            pooled = {rid for rid in sorted(eligible)
                      if self.pool_of.get(rid) == pool}
            if pooled:
                eligible = pooled
        decision = self.router.route(
            fr.prompt, self.replicas, session=fr.session,
            exclude=exclude,
            eligible=eligible,
            store=self.prefix_store, now=t,
        )
        if decision is None:
            # nothing admissible (dead, or gated by the supervisor):
            # back off and hope for a restart or a recovery verdict
            self._requeue(fr, t, ReplicaDeadError(
                f"no alive HEALTHY replica for {fr.request_id} "
                f"at tick {t}"
            ))
            return
        handle = decision.replica
        deadline_step = handle.local_deadline(fr.deadline)
        try:
            if fr.tokens:
                handle.engine.resume_request(
                    fr.prompt, fr.sampling,
                    request_id=fr.request_id,
                    output_tokens=fr.tokens,
                    deadline_step=deadline_step,
                )
            else:
                handle.engine.add_request(
                    fr.prompt, fr.sampling,
                    request_id=fr.request_id,
                    deadline_step=deadline_step,
                )
        except DeadlineExceededError as e:
            self.counts["deadline_expired"] += 1
            _DEADLINE_EXPIRED.inc()
            self._finalize(fr, FrontendRequestState.TIMED_OUT, error=e)
            return
        fr.transition(FrontendRequestState.ASSIGNED)
        fr.replica_id = handle.replica_id
        fr.routed_by = decision.reason
        fr.assigned_tick = t
        fr.waiting_since = None
        self._trace_event(fr, "routed", reason=decision.reason)
        self._trace_event(fr, "admitted")
        self._bb_note("route_decision", replica_id=handle.replica_id,
                      tick=t, request=fr.request_id,
                      reason=decision.reason)
        self.events_log.append(
            ("admit", t, fr.request_id, handle.replica_id))

    def _requeue(self, fr: FrontendRequest, t: int,
                 cause: BaseException) -> None:
        """Retry-with-backoff, or shed when the budget is dry."""
        fr.attempts += 1
        fr.last_replica = fr.replica_id
        fr.replica_id = None
        fr.waiting_since = None
        if fr.attempts > self.config.retry.max_retries:
            self.counts["retries_exhausted"] += 1
            _RETRY_EXHAUSTED.inc()
            err = RequestShedError(
                f"request {fr.request_id}: retry budget "
                f"({self.config.retry.max_retries}) exhausted; last "
                f"cause: {type(cause).__name__}: {cause}"
            )
            err.__cause__ = cause
            self.counts["shed_rejected"] += 1
            _SHED.inc()
            self._finalize(fr, FrontendRequestState.SHED, error=err)
            return
        delay = self.config.retry.delay_ticks(
            self.config.seed, fr.request_id, fr.attempts)
        fr.next_retry = t + delay
        fr.transition(FrontendRequestState.RETRY_WAIT)
        self._trace_event(fr, "retried", attempt=fr.attempts,
                          delay=delay, from_replica=fr.last_replica,
                          cause=type(cause).__name__)
        if fr not in self._retry:
            self._retry.append(fr)
        self.counts["retries_scheduled"] += 1
        _RETRY_SCHED.inc()

    def _step_replicas(self, t: int) -> None:
        """Step every ALIVE replica exactly once — even idle ones, so
        engine step counters stay aligned with the tick and deadline
        translation stays exact."""
        for handle in self.replicas:
            if not handle.alive:
                continue
            try:
                handle.step()
            except OutOfPagesError as e:
                # capacity failure: relieve AND note it — a replica
                # that can't step is sick until proven otherwise
                handle.note_step_error(e)
                self._relieve_pressure(handle, t, e)
            except StepInterruptedError as e:
                # transient, pre-mutation abort: nothing to clean up,
                # nothing to requeue — just feed the error streak
                handle.note_step_error(e)
            else:
                handle.note_step_ok()

    def _relieve_pressure(self, handle: ReplicaHandle, t: int,
                          cause: OutOfPagesError) -> None:
        """A replica's step failed on capacity: pull its youngest
        request (the same victim preemption would pick) back to the
        front end and retry it elsewhere."""
        eng = handle.engine
        live = [*eng.scheduler.waiting, *eng.scheduler.running]
        if not live:
            return
        victim = max(live, key=lambda r: (r.arrival, r.seq))
        fr = self.requests.get(victim.request_id)
        eng.cancel(victim.request_id)
        if fr is not None and fr.state is FrontendRequestState.ASSIGNED:
            self._requeue(fr, t, cause)

    def _supervise(self, t: int) -> None:
        """Score the fleet, act on the verdicts: drain a replica the
        moment it turns SUSPECT (and again on DEGRADED — destinations
        may have freed up), kill + promote a standby on DEAD.  The
        supervisor judges; this method is the only place that acts."""
        verdicts = self.supervisor.observe(t, self.replicas)
        # log EVERY verdict before acting on ANY: observe() moved all
        # the states atomically, so in append order the tick's state
        # changes precede the actions they trigger (a drain routed to
        # a replica whose recovery verdict sits later in the batch
        # must not read as an admission to a sick replica)
        for v in verdicts:
            self.events_log.append((
                "verdict", t, v.replica_id,
                v.old.value, v.new.value, list(v.signals)))
            if v.is_recovery:
                self.counts["supervisor_recoveries"] += 1
            elif v.new is SupervisorState.SUSPECT:
                self.counts["supervisor_suspects"] += 1
            elif v.new is SupervisorState.DEGRADED:
                self.counts["supervisor_degraded"] += 1
            elif v.new is SupervisorState.DEAD:
                self.counts["supervisor_dead"] += 1
        for v in verdicts:
            if v.is_recovery:
                continue
            handle = self._handle(v.replica_id)
            if v.new is SupervisorState.DEAD:
                if handle is not None and handle.alive:
                    # gray failure crossed the line: treat it as
                    # fail-stop (requeues whatever drain left behind)
                    self.kill_replica(v.replica_id)
                self._promote_standby(t, handle)
            elif handle is not None:
                self.migrations.extend(drain_replica(
                    self, handle, tick=t,
                    eligible=self.supervisor.eligible_ids(
                        self.replicas)))

    def _promote_standby(self, t: int,
                         failed: ReplicaHandle | None) -> bool:
        """Replace a DEAD replica with a warm standby: the spare boots
        from the FAILED replica's snapshot directory (its own manager
        then starts a fresh incarnation in the spare's directory), so
        promotion recovers the dead engine's in-flight state just like
        a warm restart — then reconciliation adopts whatever still
        matches the streamed prefixes."""
        if not self.standby_pool:
            return False
        spare = self.standby_pool.pop(0)
        warm_from = failed.snapshot_dir if failed is not None else None
        mode = spare.restart(tick=t, warm_from=warm_from)
        self.replicas.append(spare)
        if self.pool_of and failed is not None:
            # fleet continuity: the replacement serves the dead
            # replica's pool (the dead handle keeps its entry so a
            # chaos restart rejoins its old role)
            pool = self.pool_of.get(failed.replica_id)
            if pool is not None:
                self.pool_of[spare.replica_id] = pool
        self.supervisor.reset(t, spare.replica_id)
        self.counts["standby_promotions"] += 1
        _PROMOTED.inc()
        self._bb_note("standby_promote", replica_id=spare.replica_id,
                      mode=mode,
                      replaced=(failed.replica_id
                                if failed is not None else None))
        if mode == "warm":
            self.counts["warm_restarts"] += 1
            self._reconcile_restored(spare)
        self._apply_ladder_to(spare)
        return True

    # -- migration hooks (called by frontend.migrate.drain_replica) -------

    def note_migrated(self, fr: FrontendRequest, dest: ReplicaHandle,
                      t: int) -> None:
        """Bookkeeping for one completed cut: the request now lives on
        ``dest`` and nowhere else."""
        fr.last_replica = fr.replica_id
        fr.replica_id = dest.replica_id
        fr.routed_by = "migrated"
        fr.assigned_tick = t
        fr.waiting_since = None
        self.counts["live_migrations"] += 1
        self._trace_event(fr, "migrated", source=fr.last_replica,
                          dest=dest.replica_id,
                          tokens_at_cut=len(fr.tokens))
        self._bb_note("replica_migrate", replica_id=dest.replica_id,
                      tick=t, request=fr.request_id,
                      source=fr.last_replica,
                      tokens_at_cut=len(fr.tokens))
        self.events_log.append(
            ("admit", t, fr.request_id, dest.replica_id))

    def note_migration_stranded(self, fr: FrontendRequest) -> None:
        """No HEALTHY destination: the request stays on the sick
        replica (which keeps serving what it already holds)."""
        self.counts["migrations_stranded"] += 1

    def note_migration_timeout(self, fr: FrontendRequest,
                               e: DeadlineExceededError) -> None:
        """The cut found the request already past its deadline in the
        destination's clock; finalize truthfully."""
        self.counts["deadline_expired"] += 1
        _DEADLINE_EXPIRED.inc()
        self._finalize(fr, FrontendRequestState.TIMED_OUT, error=e)

    # -- disaggregation: prompt-commit handoff + elastic autoscaler -------

    def note_handoff(self, fr: FrontendRequest, dest: ReplicaHandle,
                     t: int, *, avoided: int) -> None:
        """Bookkeeping for one completed prefill->decode cut
        (`note_migrated`'s discipline with the fleet counters):
        ``avoided`` is the re-prefill tokens the shipped KV pages
        saved the destination."""
        fr.last_replica = fr.replica_id
        fr.replica_id = dest.replica_id
        fr.routed_by = "handoff"
        fr.assigned_tick = t
        fr.waiting_since = None
        self.counts["handoffs"] += 1
        self.counts["reprefill_avoided_tokens"] += avoided
        self._trace_event(fr, "migrated", source=fr.last_replica,
                          dest=dest.replica_id,
                          tokens_at_cut=len(fr.tokens))
        self._bb_note("handoff", replica_id=dest.replica_id, tick=t,
                      request=fr.request_id, source=fr.last_replica,
                      avoided_tokens=avoided)
        self.events_log.append(
            ("admit", t, fr.request_id, dest.replica_id))

    def _handoff_committed(self, t: int) -> None:
        """Move every prompt-committed stream (first output token
        sampled, so prefill is done) off the prefill pool and onto a
        decode replica, shipping its committed KV pages so the
        destination resumes without re-prefilling.  No decode
        destination = the stream decodes where it prefilled —
        placement is a preference, never a correctness boundary."""
        if not self.pool_of:
            return
        healthy = self.supervisor.eligible_ids(self.replicas)
        decode_ids = {rid for rid in sorted(healthy)
                      if self.pool_of.get(rid) == "decode"}
        for handle in list(self.replicas):
            if (not handle.alive
                    or self.pool_of.get(handle.replica_id)
                    != "prefill"):
                continue
            dest_ids = decode_ids - {handle.replica_id}
            if not dest_ids:
                continue
            eng = handle.engine
            live = sorted(
                [("waiting", r) for r in eng.scheduler.waiting]
                + [("running", r) for r in eng.scheduler.running],
                key=lambda item: item[1].seq,
            )
            for queue, req in live:
                fr = self.requests.get(req.request_id)
                if (fr is None
                        or fr.state is not FrontendRequestState.ASSIGNED
                        or fr.replica_id != handle.replica_id
                        or not req.output_tokens):
                    continue
                self._handoff_one(handle, queue, req, fr, t, dest_ids)

    def _handoff_one(self, source: ReplicaHandle, queue: str, req,
                     fr: FrontendRequest, t: int,
                     dest_ids: set[str]) -> None:
        """One prefill->decode cut: serialize (PR 9 section format),
        export the committed KV pages, cancel on the source, import +
        resume on the destination.  A corrupt payload is a typed
        `HandoffCorruptError` + re-prefill fallback — the destination
        rebuilds the prefix from the prompt; tokens are never wrong,
        only slower."""
        rec = _request_to_dict(req, queue)
        decision = self.router.route(
            fr.prompt, self.replicas, session=fr.session,
            exclude=source.replica_id, eligible=dest_ids,
        )
        if decision is None:
            return
        dest = decision.replica
        blob = export_handoff(source.engine, req, rec)
        if blob is not None and self._poison_handoffs > 0:
            # chaos `handoff_poison`: flip one payload byte past the
            # manifest so the section CRC — not the JSON parse — is
            # what catches it
            self._poison_handoffs -= 1
            mid = len(blob) // 2
            blob = (blob[:mid] + bytes([blob[mid] ^ 0xFF])
                    + blob[mid + 1:])
        # THE CUT (`frontend.migrate` discipline): source first,
        # destination second — exactly one engine ever holds it
        source.engine.cancel(req.request_id)
        avoided = 0
        if blob is not None:
            try:
                avoided = import_handoff(dest.engine, blob, now=t)
            except HandoffCorruptError:
                self.counts["handoff_fallbacks"] += 1
                self._bb_note("handoff_fallback",
                              replica_id=dest.replica_id, tick=t,
                              request=fr.request_id,
                              source=source.replica_id)
                self._incident("typed_error", {
                    "error": "HandoffCorruptError",
                    "request": fr.request_id,
                    "source": source.replica_id,
                    "dest": dest.replica_id})
        outs = [int(tok) for tok in rec["output_tokens"]]
        sampling = SamplingParams(**rec["sampling"])
        try:
            dest.engine.resume_request(
                rec["prompt"], sampling,
                request_id=fr.request_id, output_tokens=outs,
                deadline_step=dest.local_deadline(fr.deadline),
            )
        except DeadlineExceededError as e:
            self.note_migration_timeout(fr, e)
            self.migrations.append(MigrationRecord(
                tick=t, request_id=fr.request_id,
                source=source.replica_id, dest=None,
                tokens_at_cut=len(fr.tokens), record=rec))
            return
        _trace.adopt(fr.request_id, rec.get("trace", []))
        self.note_handoff(fr, dest, t, avoided=avoided)
        self.migrations.append(MigrationRecord(
            tick=t, request_id=fr.request_id,
            source=source.replica_id, dest=dest.replica_id,
            tokens_at_cut=len(fr.tokens), record=rec))

    def _vetoed_pools(self) -> tuple[str, ...]:
        """Pools the anomaly detectors currently implicate: a
        gray-failure key names a replica, hence its pool; any other
        active firing is fleet-wide and vetoes both."""
        if self.anomaly is None or not self.anomaly.active:
            return ()
        vetoed: set[str] = set()
        for _detector, key in sorted(self.anomaly.active):
            pool = self.pool_of.get(key)
            if pool is not None:
                vetoed.add(pool)
            else:
                vetoed.update(POOLS)
        return tuple(sorted(vetoed))

    def _autoscale(self, t: int) -> None:
        """One controller tick: settle armed mis-actuation guards,
        feed the per-pool pressures, execute the decided actions.
        Runs after `_update_ladder_and_gauges` so the anomaly active
        set feeding the veto is this tick's, not last tick's."""
        if self.autoscaler is None:
            return
        self._check_guards(t)
        pressures: dict[str, float] = {}
        sizes: dict[str, int] = {}
        for pool in POOLS:
            members = [h for h in self.replicas
                       if self.pool_of.get(h.replica_id) == pool]
            sizes[pool] = sum(1 for h in members if h.alive)
            if any(h.alive for h in members):
                _, mean = pool_pressure(
                    members, queue_cap=self.config.shed.queue_cap)
            else:
                mean = 1.0   # an empty/dead pool is saturated
            pressures[pool] = mean
        forced, self._force_demotions = self._force_demotions, 0
        actions = self.autoscaler.decide(
            t, pressures=pressures, pool_sizes=sizes,
            standbys=len(self.standby_pool),
            vetoed=self._vetoed_pools(), forced=forced)
        for act in actions:
            if act.kind == "veto":
                self.counts["actuation_vetoes"] += 1
                self._bb_note("actuation_veto", tick=t,
                              pool=act.pool, cause=act.cause)
            elif act.kind == "scale_up":
                self._scale_up(t, act.pool, act.cause)
            else:
                self._scale_down(t, act.pool, act.cause)

    def _scale_up(self, t: int, pool: str, cause: str) -> None:
        """Promote the next standby (cold boot) into ``pool`` — the
        `_promote_standby` mechanics minus the failed-replica warm
        source, plus the actuation ledger entry."""
        if not self.standby_pool:
            return
        spare = self.standby_pool.pop(0)
        spare.restart(tick=t)
        self.replicas.append(spare)
        self.pool_of[spare.replica_id] = pool
        self.supervisor.reset(t, spare.replica_id)
        self._apply_ladder_to(spare)
        self.counts["scale_ups"] += 1
        self.actuations.append(ActuationRecord(
            tick=t, kind="scale_up", pool=pool,
            replica_id=spare.replica_id, cause=cause))
        self._bb_note("scale_up", replica_id=spare.replica_id,
                      tick=t, pool=pool, cause=cause)

    def _scale_down(self, t: int, pool: str, cause: str) -> None:
        """Drain + demote the youngest alive member of ``pool`` back
        to the standby bench, then arm the mis-actuation guard: a
        shed inside ``guard_window`` ticks indicts this decision
        (incident cause ``actuation``)."""
        members = [h for h in self.replicas
                   if self.pool_of.get(h.replica_id) == pool
                   and h.alive]
        if not members:
            return
        victim = members[-1]
        healthy = self.supervisor.eligible_ids(self.replicas)
        dest_ids = {rid for rid in sorted(healthy)
                    if self.pool_of.get(rid) == pool
                    and rid != victim.replica_id}
        if not dest_ids:
            dest_ids = {rid for rid in sorted(healthy)
                        if rid != victim.replica_id}
        drained = drain_replica(self, victim, tick=t,
                                eligible=dest_ids)
        self.migrations.extend(drained)
        leftovers = sorted(
            (fr for fr in self.requests.values()
             if fr.state is FrontendRequestState.ASSIGNED
             and fr.replica_id == victim.replica_id),
            key=lambda f: f.seq)
        # note BEFORE the kill so the record carries the demoted
        # incarnation's live coordinates (`kill_replica` discipline)
        self._bb_note("scale_down", replica_id=victim.replica_id,
                      tick=t, pool=pool, cause=cause,
                      drained=len(drained))
        victim.kill()
        self.router.forget_replica(victim.replica_id)
        self.replicas.remove(victim)
        del self.pool_of[victim.replica_id]
        self.standby_pool.append(victim)
        err = ReplicaDeadError(
            f"replica {victim.replica_id} demoted to standby at "
            f"tick {t}")
        for fr in leftovers:
            self._requeue(fr, t, err)
        self.counts["scale_downs"] += 1
        self.actuations.append(ActuationRecord(
            tick=t, kind="scale_down", pool=pool,
            replica_id=victim.replica_id, cause=cause))
        self._guards.append((t, pool, self.counts["shed_rejected"]))

    def _check_guards(self, t: int) -> None:
        """Settle armed mis-actuation guards: a scale-down followed
        by ANY shed inside its guard window was capacity the fleet
        still needed — dump one ``actuation`` incident and disarm;
        a guard that ages out clean just expires."""
        if not self._guards:
            return
        gw = self.config.autoscaler.guard_window
        keep: list[tuple[int, str, int]] = []
        for (t0, pool, sheds0) in self._guards:
            if self.counts["shed_rejected"] > sheds0:
                self._incident("actuation", {
                    "pool": pool, "scale_down_tick": t0,
                    "sheds": self.counts["shed_rejected"] - sheds0})
            elif t - t0 < gw:
                keep.append((t0, pool, sheds0))
        self._guards = keep

    def _migrate_stalled(self, t: int) -> None:
        """Admission-stall detection: a request that has sat in a
        replica's waiting queue (injected OOM window, watermark flap,
        pool too full) for ``stall_ticks`` consecutive ticks migrates
        to another replica through the retry path."""
        for handle in self.replicas:
            if not handle.alive:
                continue
            waiting_ids = {r.request_id
                           for r in handle.engine.scheduler.waiting}
            assigned = [fr for fr in self.requests.values()
                        if fr.state is FrontendRequestState.ASSIGNED
                        and fr.replica_id == handle.replica_id]
            for fr in sorted(assigned, key=lambda f: f.seq):
                if fr.request_id not in waiting_ids:
                    fr.waiting_since = None
                    continue
                if fr.waiting_since is None:
                    fr.waiting_since = t
                    continue
                if t - fr.waiting_since + 1 < self.config.stall_ticks:
                    continue
                handle.engine.cancel(fr.request_id)
                self.counts["migrations"] += 1
                _MIGRATED.inc()
                self._requeue(fr, t, OutOfPagesError(
                    f"request {fr.request_id} admission-stalled on "
                    f"{handle.replica_id} for "
                    f"{self.config.stall_ticks} ticks"
                ))

    def _apply_ladder_to(self, handle: ReplicaHandle) -> None:
        if not handle.alive:
            return
        eng = handle.engine
        level = self.ladder.level
        base = self.engine_config.token_budget
        eng.scheduler.token_budget = (
            base if level < 1
            else max(1, int(base * self.config.degrade
                            .token_budget_factor))
        )
        eng.scheduler.prefix_admission = level < 2

    def _update_ladder_and_gauges(self, t: int) -> None:
        _, mean = pool_pressure(
            self.replicas, queue_cap=self.config.shed.queue_cap)
        old = self.ladder.level
        new = self.ladder.observe(mean)
        if new != old:
            (_STEP_DOWN if new > old else _RECOVER).inc()
            for handle in self.replicas:
                self._apply_ladder_to(handle)
        if self.forecast is not None:
            self._observe_forecast(t, mean)
        if self.anomaly is not None:
            self._observe_anomaly(t, mean)
        if obs.enabled():
            _LEVEL_G.set(self.ladder.level)
            _PRESSURE_G.set(mean)
            for handle in self.replicas:
                load = handle.load()
                _R_QUEUE_G.set(load["waiting"] + load["running"],
                               replica=handle.replica_id)
                _R_UTIL_G.set(load["page_utilization"],
                              replica=handle.replica_id)

    def _observe_forecast(self, t: int, mean: float) -> None:
        """Feed the per-tick sample row; then — advisory mode only —
        log what forecast-driven admission WOULD have done.  Nothing
        here feeds back into routing, shedding, or the ladder: the
        forecast stays a measurement until the elastic-scaling PR."""
        tracker = self.forecast
        depth = 0
        for handle in self.replicas:
            if handle.alive:
                load = handle.load()
                depth += load["waiting"] + load["running"]
        admits = sum(1 for ev in self.events_log[tracker.events_seen:]
                     if ev[0] == "admit")
        tracker.events_seen = len(self.events_log)
        pred = tracker.record_tick(mean, depth, admits)
        if not tracker.policy.advisory:
            return
        shed_wm = self.config.shed.shed_pressure
        down_wm = self.config.shed.downclass_pressure
        if pred >= shed_wm and mean < shed_wm:
            self.events_log.append(
                ("forecast", t, "would_shed", _r6(pred), _r6(mean)))
        elif pred >= down_wm and mean < down_wm:
            self.events_log.append(
                ("forecast", t, "would_downclass", _r6(pred), _r6(mean)))

    def _observe_anomaly(self, t: int, mean: float) -> None:
        """Run the online anomaly detectors over this tick's
        frozen-series inputs.  Advisory-only (the forecast contract):
        a firing lands in the event log, the flight recorder, and —
        with an ``incident_dir`` — one postmortem bundle; control
        flow never reads it."""
        tracker = self.anomaly
        tracker.observe_pressure(t, mean)
        new = tracker.step(t)
        for f in new:
            self.counts["anomaly_firings"] += 1
            self.events_log.append((
                "anomaly", t, f["detector"], f["key"],
                f["value"], f["bound"]))
            key = f["key"]
            # a gray-failure key IS a replica id; stamp it so the
            # ring record carries the suspect's coordinates
            rid = key if self._handle(key) is not None else None
            self._bb_note("anomaly_fire", replica_id=rid, tick=t,
                          detector=f["detector"], key=key,
                          value=f["value"], bound=f["bound"])
            self._incident("detector", {
                "detector": f["detector"], "key": key,
                "value": f["value"], "bound": f["bound"]})
        tracker.publish(new)

    @property
    def forecast_pressure(self) -> float | None:
        """One-step-ahead mean-pressure forecast (None while
        forecasting is disabled).  Advisory surface for the supervisor
        / ladder dashboards; control flow never reads it (the
        zero-overhead contract in `frontend.degrade`)."""
        return (None if self.forecast is None
                else self.forecast.forecast_pressure)

    # -- reporting --------------------------------------------------------

    def forecast_report(self, *,
                        horizon: int | None = None) -> dict[str, Any]:
        """The observatory document (`obs.capacity.observatory_report`)
        over this run's recorded samples; ValueError while forecasting
        is disabled."""
        if self.forecast is None:
            raise ValueError(
                "forecasting is disabled (FrontendConfig.forecast is "
                "None); construct the front end with a ForecastPolicy")
        return self.forecast.report(
            self.latency_rows(),
            alive=sum(1 for h in self.replicas if h.alive),
            shed_pressure=self.config.shed.shed_pressure,
            downclass_pressure=self.config.shed.downclass_pressure,
            horizon=horizon)

    def outputs(self) -> dict[str, list[int]]:
        """Streamed tokens per request, submission order."""
        return {fr.request_id: list(fr.tokens)
                for fr in sorted(self.requests.values(),
                                 key=lambda f: f.seq)}

    def latency_rows(self) -> list[dict[str, Any]]:
        """Per-request latency rows in the `obs.slo` schema, submission
        order.  Pure bookkeeping (works with telemetry disabled): the
        SLO observatory is a deterministic function of these rows."""
        rows: list[dict[str, Any]] = []
        for fr in sorted(self.requests.values(), key=lambda f: f.seq):
            rows.append({
                "request_id": fr.request_id,
                "tenant": fr.session or "default",
                "priority": fr.priority,
                "submit_tick": fr.arrival,
                "first_token_tick": fr.first_token_tick,
                "finish_tick": (fr.finish_tick if fr.finish_tick >= 0
                                else self._tick),
                "output_tokens": len(fr.tokens),
                "state": fr.state.value,
            })
        return rows

    def summary(self) -> dict[str, Any]:
        """Deterministic run aggregate: every field is a pure function
        of (seed, trace, fault plan) — no wall-clock anywhere, which is
        what lets the chaos storm pin byte-identical reports."""
        frs = sorted(self.requests.values(), key=lambda f: f.seq)
        by_state = {s.value: 0 for s in FrontendRequestState}
        for fr in frs:
            by_state[fr.state.value] += 1
        finished = [fr for fr in frs
                    if fr.state is FrontendRequestState.FINISHED]
        fin_prompt = sum(len(fr.prompt) for fr in finished)
        fin_cached = sum(fr.prefix_cached_tokens for fr in finished)
        store_block: dict[str, Any] = {}
        if self.prefix_store is not None:
            st = self.prefix_store
            store_block["prefixstore"] = {
                **{k: st.counts[k] for k in sorted(st.counts)},
                "entries": len(st),
                "bytes": st.total_bytes,
                # the fleet-level rate: local affinity hits PLUS
                # store-imported chains, over finished prompt tokens
                "fleet_prefix_hit_rate": round(
                    fin_cached / fin_prompt, 4) if fin_prompt else 0.0,
                "imported_tokens": st.counts["import_tokens"],
            }
        fleet_block: dict[str, Any] = {}
        if self.pool_of:
            fleet_block["fleet"] = {
                "pools": {pool: sum(
                    1 for rid in sorted(self.pool_of)
                    if self.pool_of[rid] == pool) for pool in POOLS},
                "actuations": len(self.actuations),
            }
        return {
            "ticks": self._tick,
            "num_requests": len(frs),
            "states": by_state,
            "streamed_tokens": sum(len(fr.tokens) for fr in frs),
            "finished_output_tokens": sum(len(fr.tokens)
                                          for fr in finished),
            "finished_prompt_tokens": fin_prompt,
            "prefix_cached_tokens": fin_cached,
            "prefix_cache_hit_rate": round(
                fin_cached / fin_prompt, 4) if fin_prompt else 0.0,
            "replica_deaths": sum(h.deaths for h in self.replicas),
            "alive_replicas": sum(1 for h in self.replicas if h.alive),
            "warm_fallbacks": sum(
                h.warm_fallbacks
                for h in (*self.replicas, *self.standby_pool)),
            "standbys_remaining": len(self.standby_pool),
            "supervisor_states": self.supervisor.states(),
            "degrade_level": self.ladder.level,
            "degrade_step_downs": self.ladder.step_downs,
            "degrade_recoveries": self.ladder.recoveries,
            **store_block,
            **fleet_block,
            **self.counts,
        }

    def to_run_record(self, *, config: str = "frontend-serve",
                      extra: dict[str, Any] | None = None) -> RunRecord:
        """The run as the repo's uniform benchmark row.  Deliberately
        deterministic: timing fields (and the record timestamp) are
        zero — the front end's unit of time is the tick — so same
        seed -> byte-identical record."""
        s = self.summary()
        record = RunRecord(
            timestamp=0.0,
            config=config,
            backend="frontend",
            m=s["finished_prompt_tokens"],
            n=s["finished_output_tokens"],
            dk=0,
            dv=0,
            dtype="",
            best_us=0.0,
            median_us=0.0,
            gflops_per_chip=0.0,
            utilization=None,
            device_kind="virtual",
            n_devices=self.config.num_replicas,
            extra={**s, **(extra or {})},
        )
        obs.record_run(record)
        return record


def replay_frontend(frontend: ServingFrontend,
                    trace: Sequence[dict[str, Any]], *,
                    max_ticks: int | None = 10000):
    """Feed a trace (the `engine.sim` JSON schema, plus the optional
    resilience fields ``session`` / ``priority`` / ``deadline_ticks``)
    through a front end and run it dry; returns ``(summary, outputs)``
    like `engine.sim.replay` so single-engine baselines and
    multi-replica runs compare directly."""
    for entry in trace:
        frontend.submit(
            entry["prompt"], sampling_of(entry),
            request_id=entry.get("id"),
            arrival=int(entry.get("arrival", 0)),
            ttl_ticks=entry.get("deadline_ticks"),
            priority=int(entry.get("priority", 1)),
            session=entry.get("session"),
        )
    summary = frontend.run(max_ticks=max_ticks)
    return summary, frontend.outputs()
