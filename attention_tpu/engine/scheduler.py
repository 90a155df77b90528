"""Iteration-level (continuous-batching) scheduler.

Each engine step the scheduler composes ONE batch out of whatever work
exists right now — decode tokens for running requests interleaved with
chunked-prefill slices of admitted requests (Orca-style iteration-level
scheduling: requests join and leave the batch between *tokens*, never
waiting for a whole batch to drain).  Policy, deterministically:

  * FCFS by ``(arrival, seq)`` everywhere: decode order, prefill
    continuation, admission, and the requeue point after preemption.
  * Token budget: a step schedules at most ``token_budget`` real
    tokens (decode = 1 each, prefill = chunk length), so one giant
    prompt cannot starve decode latency.
  * Decode first, then prefill: decode rows are cheap and latency-
    critical; leftover budget admits/advances prefills.
  * Page pressure: decode appends that cannot get a page trigger
    preemption-by-recompute of the YOUNGEST running request (its pages
    are freed, its computed-token count resets, it re-queues at its
    original FCFS position and re-prefills on readmission — generated
    tokens are kept and never resampled).  Admissions that would
    breach the allocator watermark simply wait.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import numpy as np

from attention_tpu import obs
from attention_tpu.engine.allocator import BlockAllocator
from attention_tpu.engine.request import Request, RequestState
from attention_tpu.ops.paged import OutOfPagesError

_ADMITTED = obs.counter("engine.scheduler.admissions",
                        "requests admitted into the running set")
_PREEMPTED = obs.counter("engine.scheduler.preemptions",
                         "preemption-by-recompute events")
_ADMIT_WAITS = obs.counter(
    "engine.scheduler.admit_waits",
    "admissions deferred by the allocator watermark")
_STATE_RESETS = obs.counter(
    "engine.state.resets",
    "recurrent states started from zero, by reason (admit: a new "
    "request; preempt: a preempted one readmitted)")


class StepSegments(NamedTuple):
    """The segments of one packed step's buffer, in the buffer's order
    (`split_step_buffer`); ``state_rows`` is None for a model with no
    recurrent layer, and ``window_tables`` for one with a single page
    space: its buffer ends with ``tables``."""

    tokens: Any
    token_slot: Any
    token_pos: Any
    kv_lens: Any
    cu_q_lens: Any
    distribution: Any
    tables: Any
    state_rows: Any = None
    window_tables: Any = None


def step_buffer_len(width: int, *, slots: int, table_width: int,
                    recurrent: bool, window_tables: bool = False) -> int:
    """int32 entries of a packed step's buffer.  Slots, table width,
    the state segment and the second page space's tables are one
    engine's constants, so the length is a function of ``width`` alone
    there."""
    return (3 * width + 2 * slots + 3 + slots * table_width
            + (slots if recurrent else 0)
            + (slots * table_width if window_tables else 0))


def split_step_buffer(buffer, *, slots: int, table_width: int,
                      recurrent: bool,
                      window_tables: bool = False) -> StepSegments:
    """The segments of a packed step's ``buffer`` (a 1-D int32 array,
    NumPy's or the device's): static slices and two reshapes, so the
    host gets views of the buffer and a jitted caller a handful of
    slices.  The one place that knows the order; `ScheduledStep.pack`
    writes through it and `_ragged_apply` reads through it.  Every
    offset is a Python int made from the buffer's length and the
    arguments: no arithmetic on a traced value."""
    fixed = step_buffer_len(0, slots=slots, table_width=table_width,
                            recurrent=recurrent,
                            window_tables=window_tables)
    width, left = divmod(buffer.size - fixed, 3)
    if buffer.ndim != 1 or width < 0 or left:
        raise ValueError(
            f"a step buffer of shape {buffer.shape} is no packed step of "
            f"{slots} slots and a table row of {table_width}")
    off = 0

    def take(n):
        nonlocal off
        off += n
        return buffer[off - n:off]

    # the segments' order: keyword arguments are evaluated as written
    return StepSegments(
        tokens=take(width).reshape(1, width),
        token_slot=take(width),
        token_pos=take(width),
        kv_lens=take(slots),
        cu_q_lens=take(slots + 1),
        distribution=take(2),
        tables=take(slots * table_width).reshape(slots, table_width),
        state_rows=take(slots) if recurrent else None,
        window_tables=(take(slots * table_width).reshape(slots, table_width)
                       if window_tables else None))


@dataclasses.dataclass
class PackedBatch:
    """One step's work flattened onto a single padded token axis — the
    host-side image of `ops.ragged_paged.RaggedPagedStep`.

    ``buffer`` is the whole step as ONE contiguous int32 array, what
    the engine uploads; every other array here is a VIEW of it, in
    this order of segments (`split_step_buffer`): ``tokens``
    (1, width) feeds the model in one launch; ``token_slot`` /
    ``token_pos`` (width,) map each packed token to its owning request
    slot (-1 = pad) and absolute cache position; ``kv_lens`` (slots,)
    / ``cu_q_lens`` (slots+1,) / ``distribution`` (2,) / ``tables``
    (slots, table_width) are the kernel's scalar-prefetch operands;
    last, for a model with recurrent layers only, ``state_rows``
    (slots,), each slot's row of the recurrent-state pools (-1: an
    empty slot; None where the model keeps no such state); and for a
    model of two page spaces only, ``window_tables`` (slots,
    table_width), each slot's row of WINDOW page ids, as wide as
    ``tables``' and -1 below the request's band (the kernel never reads
    there).  Decode slots come first (the ``distribution`` contract);
    ``num_real`` real tokens occupy the packed prefix, the remaining
    ``width - num_real`` are pad."""

    buffer: np.ndarray
    tokens: np.ndarray
    token_slot: np.ndarray
    token_pos: np.ndarray
    kv_lens: np.ndarray
    cu_q_lens: np.ndarray
    tables: np.ndarray
    distribution: np.ndarray
    state_rows: np.ndarray | None
    window_tables: np.ndarray | None
    width: int
    num_real: int


@dataclasses.dataclass
class ScheduledStep:
    """One step's batch composition (what the engine will lower onto
    kernel calls) plus the events the metrics layer records."""

    step: int
    decode: list[Request] = dataclasses.field(default_factory=list)
    # (request, real tokens of this chunk); `pack` lays out the real
    # tokens only
    prefill: list[tuple[Request, int]] = dataclasses.field(
        default_factory=list
    )
    preempted: list[Request] = dataclasses.field(default_factory=list)
    admitted: list[Request] = dataclasses.field(default_factory=list)
    # two page spaces: window pages the running requests' bands slid
    # past and gave back before this step was composed
    window_pages_released: int = 0

    @property
    def num_decode_tokens(self) -> int:
        return len(self.decode)

    @property
    def num_prefill_tokens(self) -> int:
        return sum(n for _, n in self.prefill)

    @property
    def is_empty(self) -> bool:
        return not self.decode and not self.prefill

    def pack(self, *, width: int, slots: int, table_width: int,
             recurrent: bool = False,
             window_tables: bool = False) -> PackedBatch:
        """Flatten this step onto one padded token axis, decode slots
        first then prefill chunks, each request's tokens contiguous;
        ``recurrent`` says whether the model keeps a recurrent state,
        hence whether the buffer has a ``state_rows`` segment, and
        ``window_tables`` whether it has two page spaces, hence a
        second table.

        Every segment is written in place, into a buffer made for
        this step: one kept across steps would be rewritten under an
        upload that has not read it yet (the chip's copy is
        asynchronous, and the CPU backend may alias the host's memory
        outright).

        CONSUMES pending decode tokens (`Request.feed_pending`) — call
        at most once per step, from the engine's dispatch path."""
        items = [(r, 1) for r in self.decode] + list(self.prefill)
        total = self.num_decode_tokens + self.num_prefill_tokens
        if len(items) > slots:
            raise ValueError(
                f"step has {len(items)} requests but only {slots} slots"
            )
        if total > width:
            raise ValueError(
                f"step has {total} tokens but packed width is {width}"
            )
        consts = dict(slots=slots, table_width=table_width,
                      recurrent=recurrent, window_tables=window_tables)
        buffer = np.zeros(step_buffer_len(width, **consts), np.int32)
        seg = split_step_buffer(buffer, **consts)
        for empty in (seg.token_slot, seg.tables, seg.state_rows,
                      seg.window_tables):
            if empty is not None:
                empty.fill(-1)
        num_decode = len(self.decode)
        off = 0
        for s, (req, n) in enumerate(items):
            c = req.computed_tokens
            if s < num_decode:
                seg.tokens[0, off] = req.feed_pending()
            else:
                seg.tokens[0, off:off + n] = req.tokens[c:c + n]
            seg.token_slot[off:off + n] = s
            seg.token_pos[off:off + n] = np.arange(c, c + n)
            seg.kv_lens[s] = c
            if recurrent:
                seg.state_rows[s] = req.state_slot
            seg.tables[s, :len(req.pages)] = req.pages
            if window_tables:
                seg.window_tables[s, :len(req.window_pages)] = (
                    req.window_pages)
            off += n
            seg.cu_q_lens[s + 1] = off
        seg.cu_q_lens[len(items) + 1:] = off
        seg.distribution[:] = (num_decode, len(items))
        return PackedBatch(buffer=buffer, width=width, num_real=total,
                           **seg._asdict())


class Scheduler:
    def __init__(self, allocator: BlockAllocator, *,
                 max_decode_batch: int, max_prefill_rows: int,
                 prefill_chunk: int, token_budget: int):
        if min(max_decode_batch, max_prefill_rows, prefill_chunk,
               token_budget) < 1:
            raise ValueError("scheduler limits must all be >= 1")
        self.allocator = allocator
        self.max_decode_batch = max_decode_batch
        self.max_prefill_rows = max_prefill_rows
        self.prefill_chunk = prefill_chunk
        self.token_budget = token_budget
        self.waiting: list[Request] = []   # kept FCFS-sorted
        self.running: list[Request] = []   # admission order (== FCFS)
        self.num_preemptions = 0
        # degradation-ladder hook: False turns admission-path prefix-
        # cache lookups off (committed pages stay resident for later
        # recovery, but new admissions recompute instead of increffing
        # shared pages — cheaper page churn under sustained pressure)
        self.prefix_admission = True

    # -- queue plumbing ---------------------------------------------------

    def _fcfs(self, req: Request):
        return (req.arrival, req.seq)

    def add(self, req: Request) -> None:
        self.waiting.append(req)
        self.waiting.sort(key=self._fcfs)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def remove_finished(self, req: Request) -> None:
        self.running.remove(req)

    def requeue_for_recompute(self, req: Request) -> None:
        """Preemption-by-recompute of one running request: release
        every page and the state slot, forget computed KV, requeue at
        the request's original FCFS position.  Emitted tokens and the
        pending token survive — readmission re-prefills ``req.tokens``
        from token 0 (so a recurrent state starts from zero again) and
        resumes decoding without resampling."""
        self.running.remove(req)
        self.allocator.release(req)
        req.computed_tokens = 0
        req.prefix_cached_tokens = 0
        req.preemptions += 1
        req.transition(RequestState.PREEMPTED)
        self.num_preemptions += 1
        _PREEMPTED.inc()
        self.waiting.append(req)
        self.waiting.sort(key=self._fcfs)

    def _preempt(self, victim: Request, sched: ScheduledStep) -> None:
        """`requeue_for_recompute` during step composition: the victim
        also leaves the step being composed."""
        if victim in sched.decode:
            sched.decode.remove(victim)
        sched.prefill = [(r, n) for r, n in sched.prefill if r is not victim]
        self.requeue_for_recompute(victim)
        sched.preempted.append(victim)

    def _preempt_for(self, req: Request, sched: ScheduledStep) -> bool:
        """Free pages for ``req``'s decode append by preempting the
        youngest running request.  Returns True if ``req`` itself was
        the victim (caller skips it this step)."""
        victim = max(self.running, key=self._fcfs)
        if victim is req and len(self.running) == 1:
            # preempting the sole running request to serve itself can
            # never converge — the pool is simply too small for it
            raise OutOfPagesError(
                f"request {req.request_id} needs a page but is the only "
                "running request and nothing is evictable: the pool "
                "cannot hold it"
            )
        self._preempt(victim, sched)
        return victim is req

    # -- step composition -------------------------------------------------

    def _ensure_pages(self, req: Request, cover_tokens: int, *,
                      for_decode: bool) -> None:
        self.allocator.cover(req, cover_tokens, for_decode=for_decode)

    def schedule(self, step: int) -> ScheduledStep:
        sched = ScheduledStep(step=step)
        budget = self.token_budget

        # 0) two page spaces: every running request gives back the
        # window pages its band has slid past, before anyone asks for
        # one
        if self.allocator.window_pool is not None:
            with obs.span("allocator.release_window",
                          running=len(self.running)):
                sched.window_pages_released = sum(
                    self.allocator.release_window(r) for r in self.running)

        # 1) decode: every DECODING request in FCFS order, up to the
        # batch width; each needs page coverage for one appended row
        for req in sorted(
            [r for r in self.running
             if r.state is RequestState.DECODING], key=self._fcfs
        ):
            if len(sched.decode) >= self.max_decode_batch or budget < 1:
                break
            if req.state is not RequestState.DECODING:
                continue  # preempted by an earlier candidate this step
            while True:
                try:
                    self._ensure_pages(req, len(req.tokens) + 1,
                                       for_decode=True)
                    break
                except OutOfPagesError:
                    if self._preempt_for(req, sched):
                        break  # req preempted itself; skip this step
            if req.state is not RequestState.DECODING:
                continue
            sched.decode.append(req)
            budget -= 1

        # 2) prefill continuation: requests already mid-prompt advance
        # before anyone new is admitted (FCFS).  A running request's
        # chunk may drain the watermark reserve and, failing that,
        # preempt the youngest runner — it already holds pages and
        # queue position; stalling it wastes both.
        for req in sorted(
            [r for r in self.running
             if r.state is RequestState.PREFILLING], key=self._fcfs
        ):
            if len(sched.prefill) >= self.max_prefill_rows or budget < 1:
                break
            if req.state is not RequestState.PREFILLING:
                continue  # preempted by an earlier candidate this step
            padded_end = req.computed_tokens + self.prefill_chunk
            while True:
                try:
                    self._ensure_pages(req, padded_end, for_decode=True)
                    break
                except OutOfPagesError:
                    if self._preempt_for(req, sched):
                        break
            if req.state is not RequestState.PREFILLING:
                continue
            self._schedule_chunk(req, sched, budget)
            if sched.prefill and sched.prefill[-1][0] is req:
                budget -= sched.prefill[-1][1]

        # 3) admission: FCFS over due arrivals, watermark-guarded
        while (self.waiting
               and self.waiting[0].arrival <= step
               and len(sched.prefill) < self.max_prefill_rows
               and budget >= 1):
            req = self.waiting[0]
            if not self.allocator.has_free_state_slot:
                # every recurrent-state slot is held: wait, like a
                # watermark refusal, for a running request to finish
                _ADMIT_WAITS.inc()
                break
            with obs.span("scheduler.admit", rid=req.request_id):
                self.allocator.release(req)  # defensive: queued hold nothing
                window_pages: list[int] = []
                pages = (self.allocator.lookup_prefix(
                    req.tokens, now=step, window_out=window_pages)
                    if self.prefix_admission else [])
                hit = len(pages)
                try:
                    req.pages, req.window_pages = pages, window_pages
                    req.computed_tokens = (
                        len(pages) * self.allocator.page_size)
                    req.prefix_cached_tokens = req.computed_tokens
                    before = len(sched.prefill)
                    self._schedule_chunk(req, sched, budget)
                    if len(sched.prefill) == before:
                        raise OutOfPagesError(
                            "admission chunk not scheduled")
                except OutOfPagesError:
                    # watermark refusal: return the prefix references
                    # (and what one page space gave before the other
                    # refused) and wait — running requests drain the
                    # queue eventually
                    if hit:
                        self.allocator.prefix_hits -= 1
                        self.allocator.prefix_hit_tokens -= (
                            hit * self.allocator.page_size
                        )
                    self.allocator.release(req)
                    req.computed_tokens = 0
                    req.prefix_cached_tokens = 0
                    _ADMIT_WAITS.inc()
                    break
                req.state_slot = self.allocator.take_state_slot()
                if req.state_slot >= 0:
                    _STATE_RESETS.inc(
                        reason="preempt" if req.preemptions else "admit")
                self.waiting.pop(0)
                self.running.append(req)
                req.transition(RequestState.PREFILLING)
                if req.first_scheduled_step < 0:
                    req.first_scheduled_step = step
                sched.admitted.append(req)
                _ADMITTED.inc()
                budget -= sched.prefill[-1][1]

        return sched

    def _schedule_chunk(self, req: Request, sched: ScheduledStep,
                        budget: int) -> None:
        """Add one prefill chunk for ``req`` if pages allow.  The chunk
        is padded to ``prefill_chunk`` rows in the kernel call, so page
        coverage must span the padded end (a pad row crossing into an
        unclaimed page would NaN-poison the whole row, real tokens
        included)."""
        remaining = len(req.tokens) - req.computed_tokens
        real = min(self.prefill_chunk, remaining, budget)
        if real < 1:
            return
        padded_end = req.computed_tokens + self.prefill_chunk
        self._ensure_pages(req, padded_end, for_decode=False)
        sched.prefill.append((req, real))
