"""The continuous-batching serving engine: step loop over paged kernels.

`ServingEngine` turns many concurrent requests into batched kernel
steps.  Memory is ONE page-id space across all model layers (per-layer
physical pools share the geometry, so a single `PagePool`/
`BlockAllocator` and one table row per request drive the whole stack),
or TWO where sliding-window layers stand beside full-attention layers
(``model.window_layers``: a second `PagePool` and a second table row a
request, in the same step buffer, for the window layers' pools, of
which a request holds its trailing band and no more);
compute is the model's packed cache path — `ragged_paged_append` +
`ragged_paged_attention` over the same pools and page tables that
`generate_paged` steps one request at a time, which is what the
engine's output is held to, token for token.

Shape discipline (the TPU way): every step lowers onto exactly ONE
jitted call over a PACKED token axis — decode tokens and prefill
chunks ride the same axis, delimited by ``cu_q_lens`` + a
decode/prefill ``distribution`` split (`ops.ragged_paged`).  The
packed width and per-request query tile are power-of-two bucketed, so
a serving life compiles O(log max_tokens) executables and pad waste
per step is just the bucket remainder.  Of that launch the host
fetches only the logits it can sample — each slot's last packed row,
gathered on the device ahead of the final norm and the head once the
packed axis is wider than the slot count (`_ragged_apply`).  A step is
schedule, pack, upload, dispatch, fetch, sample, in that order; no
option selects another.

Tokens stream out through callbacks (``on_token``/``on_finish``) the
moment they are sampled — iteration-level, not request-level, latency.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from attention_tpu import obs
from attention_tpu.obs import compiles as _compiles
from attention_tpu.obs import trace as _trace
from attention_tpu.engine.allocator import BlockAllocator
from attention_tpu.engine.errors import DeadlineExceededError
from attention_tpu.engine.metrics import (
    EngineMetrics,
    RequestMetrics,
    StepMetrics,
)
from attention_tpu.engine.request import Request, RequestState, SamplingParams
from attention_tpu.engine.scheduler import (
    ScheduledStep,
    Scheduler,
    split_step_buffer,
)
from attention_tpu.models import cache_layout as spaces
from attention_tpu.ops.paged import (
    OutOfPagesError,
    PageAccountingError,
    PagePool,
)
from attention_tpu.ops.ragged_paged import (
    head_block,
    live_pages,
    packed_bucket,
    recommended_q_tile,
    row_block_count,
    row_block_shape,
    span_tile_rows,
    tile_tokens,
)

_CANCELLED = obs.counter("engine.requests.cancelled",
                         "requests cancelled mid-flight")
_TIMED_OUT = obs.counter("engine.requests.timed_out",
                         "requests expired by the deadline sweep")
# host-side dispatches of the jitted step: ticks once per LAUNCH (the
# loop's single-launch property is asserted against this; the
# ops.*.calls counters tick per jit trace)
_LAUNCHES = obs.counter("engine.step.launches",
                        "jitted model launches dispatched by the step loop")
# what the step's one device sync moves against what the host reads of
# it: logits rows fetched and rows sampled, summed over steps (the used
# share of the fetch is their ratio)
_LOGIT_ROWS = obs.counter("engine.step.logit_rows",
                          "logits rows fetched to the host / sampled "
                          "there, by kind")
# what the expert layers' routing gave this chip: token-expert pairs of
# experts held here and of experts held elsewhere, summed over the
# expert layers and the steps
_EXPERT_PAIRS = obs.counter(
    "engine.experts.pairs",
    "token-expert pairs a step routed, by where the expert is held")
# a model whose attention chooses its keys: (query token, key) pairs
# the selectors scored, the pairs attention then attended and the cache
# rows its kernels read for them, summed over the sublayers and the
# steps
_ATTENTION_KEYS = obs.counter(
    "engine.attention.keys",
    "query-key pairs of the choosing sublayers, scored or attended, and "
    "the cache rows read")
# mesh-serving surface: how many KV-head shards the per-step launches
# lower onto (1 = single-device).  In the zero-collective head-sharded
# design the kernels exchange nothing; the only cross-shard cost is
# reassembling the replicated logits at the step's single host sync,
# which the ``engine.step.fetch`` span times on every engine.
_MESH_SHARDS = obs.gauge("engine.mesh.shards",
                         "KV-head shards the engine's jitted launches "
                         "lower onto (1 = single-device)")

#: consecutive non-finite-logits steps a request is held back before
#: the finite guard gives up and samples anyway — must exceed any
#: transient nan-injector window (random_gray_plan caps at 5 steps) so
#: gray storms keep token parity, while permanently NaN-corrupted KV
#: pages still terminate instead of wedging the step loop
_NONFINITE_SKIP_LIMIT = 8


class StepLimitExceededError(RuntimeError):
    """`run(max_steps=...)` hit its cap before the queue drained.

    Subclasses RuntimeError for compatibility with callers that caught
    the bare raise this replaces; typed so drivers can distinguish the
    diagnostic guard from a genuine engine failure (the ATP401
    error-taxonomy contract — see attention_tpu/analysis/errors.py)."""


def require_pages_only(model, feature: str) -> None:
    """Refuse ``feature``, which carries the K and V pages of ONE page
    space and nothing else, for a model that keeps anything more: the
    typed error its cache layout names (a recurrent state, one latent
    pool a sublayer or an index pool beside it, a second page space)."""
    refusal = model.cache_layout().pages_only_refusal
    if refusal is not None:
        error, text = refusal
        raise error(f"{feature} {text}")


class RaggedStepIndex(NamedTuple):
    """What one packed step tells every layer: the index fields of
    `RaggedPagedStep` (and of `RaggedStateStep`, which reads
    ``state_rows`` with four of them; None for a model with no
    recurrent layer), and for a model of two page spaces the window
    layers' ``window_table``, which stands in ``page_table``'s place
    in their steps.  The jitted step slices them out of the ONE
    int32 buffer the engine uploads a step (`_step_inputs`); none is
    an upload of its own.  Kept apart from the pools because the step
    donates those, and what every layer reads cannot be given away."""

    page_table: jax.Array
    kv_lens: jax.Array
    cu_q_lens: jax.Array
    distribution: jax.Array
    token_pos: jax.Array
    token_slot: jax.Array
    q_span: jax.Array
    state_rows: jax.Array | None = None
    window_table: jax.Array | None = None


class StepLayout(NamedTuple):
    """What places a packed step in its buffer, and its query tile:
    Python ints, static under the jit.  The first two are an engine's
    constants; whether the buffer ends with ``state_rows`` or a
    second table is read from the model's cache layout."""

    slots: int
    table_width: int
    q_tile: int


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-engine knobs.  Defaults are sized for tiny CPU tests;
    production configs scale ``num_pages``/batch widths up."""

    num_pages: int = 64
    # pages of the SECOND page space, the sliding-window layers' of a
    # model that also has full-attention layers (`model.window_layers`):
    # required for such a model, and no other has one
    num_window_pages: int = 0
    page_size: int = 128           # paged-kernel granule: 128-multiple
    max_seq_len: int = 1024        # per-request prompt + generated cap
    max_decode_batch: int = 8      # decode requests per step, at most
    max_prefill_rows: int = 2      # prefill chunks per step, at most
    prefill_chunk: int = 64        # tokens per prefill slice, at most
    token_budget: int = 128        # real tokens scheduled per step
    watermark_pages: int = 1       # admission must leave this reserve
    # > 0: a step that holds a prefill chunk gets no query tile under
    # this many tokens, so that a prompt's short last chunk runs the
    # whole chunks' program instead of compiling one of its own at
    # every tier (fewer step shapes, more padding in those steps)
    min_prefill_tile: int = 0
    cache_dtype: Any = None        # None -> model dtype
    # 0 = single-device (default).  N >= 1 serves every per-step jitted
    # launch through the KV-head-sharded kernels on a 1D "tp" mesh of
    # the first N devices: one pool slice per head shard, page tables
    # replicated, host-side packing unchanged.
    # Requires num_kv_heads % N == 0 and N available devices (typed
    # MeshConfigError otherwise, raised at engine construction).
    mesh_shards: int = 0

    def validate(self) -> None:
        if self.page_size % 128:
            raise ValueError(
                f"page_size {self.page_size} must be a 128-multiple "
                "(paged kernel granule)"
            )
        if min(self.num_pages, self.max_seq_len, self.max_decode_batch,
               self.max_prefill_rows, self.prefill_chunk,
               self.token_budget) < 1:
            raise ValueError("engine config fields must all be >= 1")
        if min(self.num_window_pages, self.min_prefill_tile) < 0:
            raise ValueError(
                "num_window_pages and min_prefill_tile must be >= 0")
        if not (0 <= self.watermark_pages < self.num_pages):
            raise ValueError(
                f"watermark_pages {self.watermark_pages} outside "
                f"[0, num_pages={self.num_pages})"
            )
        if self.mesh_shards < 0:
            raise ValueError(
                f"mesh_shards {self.mesh_shards} must be >= 0 "
                "(0 = single-device)"
            )

    @property
    def table_width(self) -> int:
        """Page-table row width: the pages of max_seq_len plus one
        prefill chunk.  A compiled shape: it sizes the ``tables``
        segment of a step's buffer and the compare-and-count of the
        kernel's work list (`ops.ragged_paged.work_items`); the grid
        itself walks only the live (slot, page) pairs."""
        return -(-(self.max_seq_len + self.prefill_chunk)
                 // self.page_size)


def _slot_last_rows(cu_q_lens):
    """The packed row each slot samples: the last of its span
    ``[cu[s], cu[s + 1])``.  Empty slots repeat the last offset, so
    the row is clipped at 0.  Takes the host's array or the device's."""
    return (cu_q_lens[1:] - 1).clip(0)


def _sampled_logit_rows(cu_q_lens: np.ndarray, width: int) -> np.ndarray:
    """Row of `_ragged_apply`'s logits that slot ``s`` samples, for
    every slot: ``s`` itself where the step gathered (``width`` over
    the slot count), else the slot's last packed row."""
    slots = len(cu_q_lens) - 1
    if width > slots:
        return np.arange(slots)
    return _slot_last_rows(cu_q_lens)


def _step_inputs(model, buffer, layout: StepLayout):
    """``(tokens, index)`` of a packed step's ``buffer``
    (`PackedBatch.buffer`, on the host or on the device): the token
    axis ``(1, width)`` and the `RaggedStepIndex` every layer reads,
    the width read from the buffer's length.  ``q_span`` is made here,
    ``layout.q_tile`` long: its shape is all anyone reads of it."""
    kept = model.cache_layout()
    seg = split_step_buffer(
        buffer, slots=layout.slots, table_width=layout.table_width,
        recurrent=kept.state_rows, window_tables=kept.window_table)
    return seg.tokens, RaggedStepIndex(
        seg.tables, seg.kv_lens, seg.cu_q_lens, seg.distribution,
        seg.token_pos, seg.token_slot,
        jnp.zeros((layout.q_tile,), jnp.int32), seg.state_rows,
        seg.window_tables)


@functools.partial(jax.jit, static_argnames=("model", "layout"),
                   donate_argnames=("pools",))
def _ragged_apply(model, params, buffer, pools, layout):
    """One PACKED model step: the whole mixed decode/prefill batch as a
    single ``(1, width)`` token axis over the layers' step caches
    (`CacheLayout.steps`) — exactly one attention launch per layer per
    engine step.
    ``buffer`` is the step's ONE upload (`PackedBatch.buffer`), split
    here by static slices into the tokens and the index every layer
    shares (`_step_inputs`).  Its length, a function of the width
    alone in one engine, and ``layout.q_tile`` are pow2-bucketed by
    the caller, so distinct compiled signatures stay
    O(log max_tokens): one a ``(width, q_tile)``.

    ``pools`` holds a layer's arrays, in layer order, as the model's
    cache layout lists them (`models.cache_layout`; None for a layer
    that keeps nothing), and is DONATED: the step writes its rows into
    them in place (`ragged_paged_append`, the recurrent layers' kernel)
    and hands the same buffers back, so the caller's arrays are gone
    after the call and it rebinds from the result.  ``buffer`` stays
    the caller's.

    Returns ``(logits, pools, counts)``, the logits of the rows
    a step can sample, not of every packed position, and for a model
    with expert layers the sum over them of what each sowed
    (`models.moe.LatentExperts`: pairs per held expert, pairs of
    experts held elsewhere, held experts that received a pair), with,
    LAST, for a model whose attention chooses its keys the pairs its
    masks let through, summed over the sublayers
    (`models.latent_attention`); None for a model with neither.  When
    the packed axis is
    wider than the slot count, each slot's last row
    (`_slot_last_rows`) is gathered before the final norm and the
    float32 head, and the result is ``(1, slots, vocab)`` with slot
    ``s`` at row ``s``; a width within the slot count already returns
    no more rows than that and stays ``(1, width, vocab)``
    (`_sampled_logit_rows` is the host's half of this rule).  Both
    sizes are input shapes, and the indices come from the
    ``cu_q_lens`` already on the device: no signature and no upload is
    added."""
    tokens, index = _step_inputs(model, buffer, layout)
    cu = index.cu_q_lens
    rows = None
    if tokens.shape[1] > cu.shape[0] - 1:
        rows = _slot_last_rows(cu)
    counted = [name for name, layers in (
        ("expert_stats", "expert_layers"),
        ("attention_stats", "indexed_layers")) if getattr(model, layers, ())]
    kept = model.cache_layout()
    out = model.apply({"params": params}, tokens,
                      kept.steps(pools, index), logit_rows=rows,
                      mutable=counted or False)
    counts = None
    if counted:
        out, sown = out
        counts = jnp.concatenate([
            jnp.atleast_1d(sum(jax.tree_util.tree_leaves(sown[name])))
            for name in counted])
    logits, steps = out
    return logits, kept.pools(steps), counts


def _qk_pairs(kv_before: np.ndarray, q_lens: np.ndarray,
              window: int | None) -> int:
    """(query token, key) pairs an attention sublayer attends in a
    step: slot by slot, query token ``t`` of ``q`` new ones on ``kv``
    cached reaches ``kv + t + 1`` keys, ``window`` at most."""
    kv, q = kv_before.astype(np.int64), q_lens.astype(np.int64)
    # the first ``free`` of a slot's query tokens reach every key
    free = q if window is None else np.clip(window - kv, 0, q)
    pairs = free * kv + free * (free + 1) // 2
    if window is not None:
        pairs = pairs + (q - free) * window
    return int(pairs.sum())


def _band_pages(kv_before: np.ndarray, q_lens: np.ndarray,
                window: int | None, page: int) -> int:
    """Pages of an attention sublayer's cache that hold a key SOME
    query row of the step attends: slot by slot, from the page of the
    first row's oldest key (``window`` back from it; key 0 without
    one) to the page of the last row's own.  What any kernel has to
    read, whatever tile it walks in."""
    kv, q = kv_before.astype(np.int64), q_lens.astype(np.int64)
    first = 0 if window is None else np.maximum(kv - window + 1, 0)
    pages = (kv + q - 1) // page - first // page + 1
    return int(pages[q > 0].sum())


class ServingEngine:
    """Deterministic continuous-batching engine over a TinyDecoder-
    family model (any ``impl='flash'`` model whose ``apply`` threads
    per-layer caches, the `generate_paged` contract).

    What a request holds is the model's word (`model.cache_layout()`,
    `models.cache_layout`): the engine allocates each layer's arrays as
    the layout lists them, hands them to the step and takes them back,
    and names no kind of layer doing so.  Where a layer keeps a
    recurrent state each running request also holds one STATE SLOT, a
    row of that layer's arrays, taken at admission and given back at
    finish, cancel, time-out and preemption.  There are
    ``max_decode_batch + max_prefill_rows`` slots, as many as a step
    has rows; a request that (re)starts at token 0 starts from a zero
    state inside the kernel, so a preempted request recomputes
    correctly and no row is ever cleared by hand.  Features that know
    only pages refuse such a model (`require_pages_only`)."""

    def __init__(self, model, params, config: EngineConfig, *,
                 on_token: Callable[[Request, int], None] | None = None,
                 on_finish: Callable[[Request], None] | None = None,
                 on_timeout: Callable[[Request], None] | None = None):
        config.validate()
        if model.impl != "flash":
            raise ValueError(
                f"ServingEngine requires impl='flash' (got {model.impl!r})"
            )
        self.model = model
        self.params = params
        self.config = config
        self.on_token = on_token
        self.on_finish = on_finish
        self.on_timeout = on_timeout
        self._layout = layout = model.cache_layout()
        # what the step's COUNTS read of the model's kinds (ROADMAP D23;
        # the pools name none): the layer of every attention SUBLAYER (a
        # double layer has two), those of one latent pool, of a selector
        self._kv_layers = tuple(getattr(
            model, "attention_sublayers", range(model.depth)))
        self._latent_layers = tuple(getattr(model, "latent_layers", ()))
        self._indexed_layers = tuple(getattr(model, "indexed_layers", ()))
        # the keys a row of those layers keeps at most (0: no selector)
        self._index_topk = (dict(model.sublayer)["index_topk"]
                            if self._indexed_layers else 0)
        self._state_layers = tuple(getattr(model, "recurrent_layers", ()))
        self._expert_layers = tuple(getattr(model, "expert_layers", ()))
        # the layers of the second page space, and the window of each
        # kind of attention sublayer: one kind, or full then sliding
        self._window_layers = tuple(getattr(model, "window_layers", ()))
        self._windows = ((None, model.window) if self._window_layers
                         else (model.window,))
        if layout.window_table != bool(config.num_window_pages):
            raise ValueError(
                f"num_window_pages {config.num_window_pages}: the pages "
                "of a second page space, which a model has where "
                "sliding-window layers stand beside full-attention "
                f"layers (this one's window layers: "
                f"{list(self._window_layers)})")
        # mesh mode: a 1D "tp" mesh of the first mesh_shards devices;
        # the step launches run the model's head-sharded cached paths
        # (a clone with tp_axis set — same params, same math per head)
        # over pools placed one KV-head slice per shard.  Host-side
        # state — allocator, watermarks, prefix cache, packing — never
        # shards: page ids are head-agnostic, so one logical pool and
        # one accounting source of truth serve every shard.
        if config.mesh_shards:
            from attention_tpu.parallel.serving import MeshConfigError

            if not layout.shard_kv_heads:
                raise MeshConfigError(
                    f"mesh_shards {config.mesh_shards}: the mesh engine "
                    "shards KV HEADS, and a latent-attention sublayer has "
                    "one; its four-chip layout shards pages, not heads")
            self.require_pages_only("mesh_shards > 0")
            devices = jax.devices()
            if config.mesh_shards > len(devices):
                raise MeshConfigError(
                    f"mesh_shards {config.mesh_shards} exceeds the "
                    f"{len(devices)} available device(s)"
                )
            if model.num_kv_heads % config.mesh_shards:
                raise MeshConfigError(
                    f"kv heads {model.num_kv_heads} not divisible by "
                    f"mesh_shards {config.mesh_shards}"
                )
            self.mesh = Mesh(
                np.asarray(devices[:config.mesh_shards]), ("tp",)
            )
            try:
                self._step_model = model.clone(tp_axis="tp",
                                               mesh=self.mesh)
            except TypeError as e:
                raise MeshConfigError(
                    f"model {type(model).__name__} lacks the "
                    f"tp_axis/mesh fields mesh serving clones "
                    f"(TinyDecoder-family contract): {e}"
                )
            self._pool_sharding = NamedSharding(
                self.mesh, PartitionSpec(None, "tp", None, None)
            )
            # place the parameters on the mesh ONCE: left uncommitted on
            # the default device they would be re-replicated to every
            # shard by each step's launch
            self._replicated = NamedSharding(self.mesh, PartitionSpec())
            self.params = jax.device_put(params, self._replicated)
        else:
            self.mesh = None
            self._step_model = model
            self._pool_sharding = None
            self._replicated = None   # the default device

        # each layer's arrays (None: it keeps nothing), in layer order: the
        # ONE list the step is handed and hands back.  State rows: one a
        # slot, and one more, nobody's, where a step's empty slots land
        state_slots = (config.max_decode_batch + config.max_prefill_rows
                       if layout.state_rows else 0)
        rows = {spaces.PAGES: config.num_pages,
                spaces.WINDOW_PAGES: config.num_window_pages,
                spaces.STATE_ROWS: state_slots + 1}
        dtype = config.cache_dtype or model.dtype
        self._pools = [c and tuple(
            self._place_pool(jnp.zeros(
                (rows[c.space], *(config.page_size if n == spaces.PAGE else n
                                  for n in row)), dtype if d is None else d))
            for row, d in c.arrays) for c in layout.layers]
        self._paged = [i for i, c in enumerate(layout.layers)
                       if c and c.space == spaces.PAGES]
        if obs.is_enabled():
            _MESH_SHARDS.set(float(config.mesh_shards or 1))

        self.pool = PagePool(config.num_pages)
        self.window_pool = (PagePool(config.num_window_pages)
                            if layout.window_table else None)
        # the widest query tile a step is dispatched with: what a
        # window layer's kernel reaches back beyond the window
        self._widest_tile = max(self._q_tile(1),
                                self._q_tile(config.prefill_chunk))
        self.allocator = BlockAllocator(
            self.pool, config.page_size,
            watermark_pages=config.watermark_pages,
            state_slots=state_slots,
            window_pool=self.window_pool,
            band=(model.window + self._widest_tile - 1
                  if layout.window_table else 0),
        )
        self.scheduler = Scheduler(
            self.allocator,
            max_decode_batch=config.max_decode_batch,
            max_prefill_rows=config.max_prefill_rows,
            prefill_chunk=config.prefill_chunk,
            token_budget=config.token_budget,
        )
        self.metrics = EngineMetrics(
            table_entries=(config.max_decode_batch
                           + config.max_prefill_rows) * config.table_width,
            held_experts=getattr(model, "held_experts", 0),
            sparse_sublayers=len(self._indexed_layers))
        # the last step's counts from the device (`_ragged_apply`):
        # expert pairs, keys attended; fetched with its logits
        self._expert_pairs: np.ndarray | None = None
        # the last step's counts of the second page space, by
        # `StepMetrics`' names (empty without one)
        self._window_fields: dict[str, int] = {}
        self._step = 0
        # plain int (not itertools.count) so snapshots can persist the
        # position: auto request-ids and FCFS tiebreaks survive restore
        self._next_seq = 0
        self._finished_in_step = 0
        self._rng_keys: dict[str, jax.Array] = {}
        self._wall: dict[str, dict[str, float]] = {}
        # health signals the replica supervisor reads (frontend/
        # supervisor.py).  ``last_step_virtual_cost`` is the seeded
        # virtual duration of the most recent step — 1.0 unless a
        # chaos slow-step injector inflates it — so slowness detection
        # stays deterministic where real wall time (StepMetrics.wall_s)
        # cannot.  ``nonfinite_events`` counts logits rows the finite
        # guard rejected before sampling.
        self.last_step_virtual_cost = 1.0
        # standing degradation knob: every step's virtual cost starts
        # from this multiplier (1.0 = healthy), so a bench or chaos
        # harness can pin a replica "slow" for a whole window instead
        # of re-injecting per step — the supervisor and the gray-
        # failure detector then see a persistent signal
        self.step_cost_multiplier = 1.0
        self.nonfinite_events = 0
        # consecutive finite-guard skips per request: a TRANSIENT
        # non-finite window (the chaos nan injector poisons returned
        # logits for a few steps) must never emit, but PERMANENTLY
        # poisoned logits (NaN-corrupted KV pages — the chaos
        # ``corrupt`` fault) would livelock the step loop if held back
        # forever; past the limit the request falls through to the
        # documented garbage-but-terminating contract (the checkers
        # exclude corrupted targets from parity)
        self._nonfinite_skips: dict[str, int] = {}
        # seconds this step spent blocked in the logits device sync
        # (host overhead = step wall minus this)
        self._last_fetch_s = 0.0
        # write-ahead log between snapshots; attached by SnapshotManager
        # (engine/snapshot.py), None when durability is off
        self.journal: Any = None
        # fleet prefix store (attention_tpu/prefixstore); attached by
        # the owning ReplicaHandle (or a test) — None keeps every
        # intake/commit path byte-identical to the storeless engine
        self.prefix_store: Any = None
        # request-trace coordinates (obs/trace.py).  A fronting
        # ReplicaHandle stamps these so engine-side events carry
        # (tick, replica, incarnation); standalone engines default to
        # tick == step.  trace_owner says who records submit/terminal
        # events — the frontend's _finalize funnel takes that role for
        # replicas it owns, so a chain never gets two terminals.
        self.trace_replica: str | None = None
        self.trace_incarnation: int = 0
        self.trace_start_tick: int = 0
        self.trace_owner: str = "engine"

    # -- the layers' pools -----------------------------------------------

    def require_pages_only(self, feature: str) -> None:
        """`require_pages_only` for this engine's model."""
        require_pages_only(self.model, feature)

    def page_pools(self) -> list:
        """The arrays kept under a request's page ids, each such layer's
        first, then each one's second: every K then every V pool in a
        model that passed `require_pages_only`, the page wire formats'
        order.  The next step consumes them."""
        return [a for nth in zip(*(self._pools[i] for i in self._paged))
                for a in nth]

    def set_page_pools(self, pools) -> None:
        """Put ``pools``, placed as the engine places its own, in the
        places of `page_pools`' arrays."""
        for j, i in enumerate(self._paged):
            self._pools[i] = tuple(self._place_pool(a)
                                   for a in pools[j::len(self._paged)])

    # -- request tracing --------------------------------------------------

    def _trace_event(self, req: Request, event: str, **extra: Any) -> None:
        """Stamp one trace event with this engine's coordinates."""
        _trace.record(
            req.request_id, event,
            tick=self.trace_start_tick + self._step,
            replica=self.trace_replica,
            incarnation=self.trace_incarnation,
            step=self._step, **extra,
        )

    def _note_first_admissions(self, sched: ScheduledStep) -> None:
        """Stamp the wall clock of each request's FIRST admission (a
        readmission after preemption is not one) and mark it on the
        profiler's timeline with the time it waited in the queue."""
        now = time.perf_counter()
        for req in sched.admitted:
            if req.first_scheduled_step != self._step:
                continue
            wall = self._wall[req.request_id]
            wall["scheduled"] = now
            with obs.span("engine.request.admitted", rid=req.request_id,
                          queue_wait_ms=(now - wall["added"]) * 1e3):
                pass

    # -- request intake ---------------------------------------------------

    @property
    def current_step(self) -> int:
        return self._step

    def _validate_intake(self, prompt, sampling: SamplingParams,
                         deadline_step: int | None) -> tuple[int, ...]:
        """Shared admission validation for add_request/resume_request;
        returns the normalized prompt tuple."""
        sampling.validate(self.model.vocab)
        prompt = tuple(int(t) for t in prompt)
        if any(not (0 <= t < self.model.vocab) for t in prompt):
            raise ValueError(
                f"prompt tokens must be in the vocab [0, "
                f"{self.model.vocab})"
            )
        total = len(prompt) + sampling.max_tokens - 1
        if total > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens "
                f"({sampling.max_tokens}) - 1 = {total} exceeds "
                f"max_seq_len {self.config.max_seq_len}"
            )
        # deadline enforcement AT ADMISSION: a request whose TTL has
        # already elapsed never enters the queue — the typed raise is
        # the front end's signal to mark it TIMED_OUT without burning
        # a queue slot on it
        if deadline_step is not None and deadline_step <= self._step:
            raise DeadlineExceededError(
                f"deadline step {deadline_step} is not after the "
                f"current step {self._step}: expired before admission"
            )
        return prompt

    def _import_prefix(self, prompt: tuple[int, ...]) -> int:
        """Fleet prefix-store import at intake: before admission runs
        its local `lookup_prefix`, splice any matching store chain
        into the allocator so the lookup then hits.  A no-op without
        an attached store; never raises (corruption is counted and
        the request simply cold-prefills)."""
        if self.prefix_store is None or self._layout.state_rows:
            return 0
        from attention_tpu.prefixstore.adapter import import_chain

        return import_chain(
            self, prompt, now=self.trace_start_tick + self._step
        )

    def add_request(self, prompt, sampling: SamplingParams | None = None,
                    *, request_id: str | None = None,
                    arrival: int | None = None,
                    deadline_step: int | None = None) -> Request:
        """Enqueue one request.  ``arrival`` (engine step) defaults to
        now; future arrivals let traces replay deterministically.
        ``deadline_step`` (engine step, exclusive) arms the per-step
        deadline sweep; an already-expired deadline raises the typed
        `DeadlineExceededError` here instead of enqueueing."""
        sampling = sampling or SamplingParams()
        prompt = self._validate_intake(prompt, sampling, deadline_step)
        seq = self._next_seq
        self._next_seq += 1
        req = Request(
            request_id=request_id or f"req-{seq}",
            prompt=prompt,
            sampling=sampling,
            arrival=self._step if arrival is None else arrival,
            seq=seq,
            deadline_step=deadline_step,
        )
        self._import_prefix(prompt)
        self._wall[req.request_id] = {"added": time.perf_counter()}
        self.scheduler.add(req)
        if _trace.active() and self.trace_owner == "engine":
            self._trace_event(req, "submitted")
            self._trace_event(req, "admitted")
        if self.journal is not None:
            self.journal.record_admit(req)
        return req

    def resume_request(self, prompt, sampling: SamplingParams, *,
                       request_id: str,
                       output_tokens: list[int] | None = None,
                       arrival: int | None = None,
                       deadline_step: int | None = None) -> Request:
        """Re-admit a partially generated request — the cross-replica
        half of preemption-by-recompute.  ``output_tokens`` are the
        tokens already streamed to the client (by this engine before a
        fault, or by ANOTHER replica that died); the request re-prefills
        prompt + fed generation and resumes decoding without resampling
        anything, exactly like a preempted request readmitting.

        The RNG chain is restored arithmetically: the engine's sampler
        performs one key split per sampled token, so splitting
        ``PRNGKey(seed)`` ``len(output_tokens)`` times reconstructs the
        live key a dead replica took with it — sampled continuations
        stay token-identical to an uninterrupted run."""
        out = [int(t) for t in (output_tokens or [])]
        prompt = self._validate_intake(prompt, sampling, deadline_step)
        if len(out) >= sampling.max_tokens:
            raise ValueError(
                f"request {request_id}: {len(out)} streamed tokens "
                f"leave nothing to resume (max_tokens "
                f"{sampling.max_tokens})"
            )
        seq = self._next_seq
        self._next_seq += 1
        req = Request(
            request_id=request_id,
            prompt=prompt,
            sampling=sampling,
            arrival=self._step if arrival is None else arrival,
            seq=seq,
            deadline_step=deadline_step,
        )
        if out:
            # between steps the invariant is: every emitted token has
            # been fed back EXCEPT the newest, which waits in
            # pending_token (mirrors `Request.emit`/`feed_pending`)
            req.tokens = list(prompt) + out[:-1]
            req.output_tokens = list(out)
            req.pending_token = out[-1]
            if sampling.temperature > 0.0:
                key = jax.random.PRNGKey(sampling.seed)
                for _ in range(len(out)):
                    key, _ = jax.random.split(key)
                self._rng_keys[request_id] = key
        self._import_prefix(prompt)
        self._wall[req.request_id] = {"added": time.perf_counter()}
        self.scheduler.add(req)
        if self.journal is not None:
            self.journal.record_admit(req)
        return req

    def cancel(self, request_id: str) -> bool:
        """Cancel a request anywhere in its lifecycle (client gone).

        Frees its pages (prefix-cache references, if any, survive — a
        cancelled prompt's committed pages stay reusable), drops its
        RNG chain, removes it from the queue or the running set, and
        transitions it to the terminal CANCELLED state.  Safe to call
        between steps only (the scheduler's contract); returns False
        when no live request has that id."""
        for queue in (self.scheduler.waiting, self.scheduler.running):
            for req in queue:
                if req.request_id != request_id:
                    continue
                queue.remove(req)
                _CANCELLED.inc()
                if _trace.active() and self.trace_owner == "engine":
                    self._trace_event(req, "cancelled")
                self.allocator.release(req)
                req.transition(RequestState.CANCELLED)
                self._rng_keys.pop(req.request_id, None)
                self._wall.pop(req.request_id, None)
                if self.journal is not None:
                    self.journal.record_cancel(request_id)
                return True
        return False

    # -- deadlines --------------------------------------------------------

    def _time_out(self, req: Request) -> None:
        """Expire one request: free pages (prefix-cache references
        survive, like cancel), terminal TIMED_OUT transition, notify."""
        for queue in (self.scheduler.waiting, self.scheduler.running):
            if req in queue:
                queue.remove(req)
        _TIMED_OUT.inc()
        if _trace.active() and self.trace_owner == "engine":
            self._trace_event(req, "timed_out")
        self.allocator.release(req)
        req.transition(RequestState.TIMED_OUT)
        req.finish_step = self._step
        self._rng_keys.pop(req.request_id, None)
        self._wall.pop(req.request_id, None)
        if self.journal is not None:
            self.journal.record_timeout(req.request_id)
        if self.on_timeout is not None:
            self.on_timeout(req)

    def _expire_deadlines(self) -> int:
        """The per-step deadline sweep: every queued or running request
        whose ``deadline_step`` has arrived is timed out before the
        step schedules — a deadline can fire mid-prefill (chunks
        computed, no token ever emitted) exactly as it can mid-decode."""
        expired = [
            r for r in (*self.scheduler.waiting, *self.scheduler.running)
            if r.deadline_step is not None
            and r.deadline_step <= self._step
        ]
        for req in expired:
            self._time_out(req)
        return len(expired)

    # -- step loop --------------------------------------------------------

    def step(self) -> StepMetrics:
        """Run one scheduler iteration: compose a batch, lower it onto
        ONE packed launch, stream out sampled tokens."""
        t0 = time.perf_counter()
        compile_rows = _compiles.count
        self._finished_in_step = 0
        self.last_step_virtual_cost = self.step_cost_multiplier
        self._last_fetch_s = 0.0
        pad_tokens = kv_pages = qk_pairs = keys_selected = rows_read = 0
        width = q_tile = compiled_programs = own_tile = grid_steps = 0
        occupancy = compile_s = 0.0
        self._expert_pairs = None
        self._window_fields = {}
        with obs.span("engine.step", step=self._step,
                      queued=len(self.scheduler.waiting),
                      running=len(self.scheduler.running)):
            with obs.span("engine.step.schedule"):
                timed_out = self._expire_deadlines()
                sched = self.scheduler.schedule(self._step)
                self._note_first_admissions(sched)
                if _trace.active():
                    # preemptions free the pages the admissions claim,
                    # so they precede admissions in the chain too
                    for req in sched.preempted:
                        self._trace_event(req, "preempted")
                    for req in sched.admitted:
                        ev = ("resumed"
                              if (req.preemptions or req.output_tokens)
                              else "prefill_start")
                        self._trace_event(req, ev)
            total = sched.num_decode_tokens + sched.num_prefill_tokens
            if self.window_pool is not None:
                self._window_fields["window_pages_released"] = (
                    sched.window_pages_released)
            if not sched.is_empty:
                (width, q_tile, kv_pages, qk_pairs, keys_selected,
                 rows_read, own_tile, grid_steps) = self._run_ragged(sched)
                pad_tokens = width - total
                occupancy = total / width
            if _compiles.count != compile_rows:
                compile_s, compiled_programs = self._note_compiled(
                    t0, width, q_tile)
            wall_s = time.perf_counter() - t0
            m = StepMetrics(
                step=self._step,
                wall_s=wall_s,
                num_decode_reqs=len(sched.decode),
                num_prefill_reqs=len(sched.prefill),
                decode_tokens=sched.num_decode_tokens,
                prefill_tokens=sched.num_prefill_tokens,
                queue_depth=len(self.scheduler.waiting),
                running=len(self.scheduler.running),
                admitted=len(sched.admitted),
                preempted=len(sched.preempted),
                finished=self._finished_in_step,
                timed_out=timed_out,
                free_pages=self.pool.free_pages,
                used_pages=self.pool.used_pages,
                page_utilization=self.pool.used_pages / self.pool.num_pages,
                prefix_hit_tokens_total=self.allocator.prefix_hit_tokens,
                preemptions_total=self.scheduler.num_preemptions,
                pad_tokens=pad_tokens,
                ragged_occupancy=occupancy,
                kv_pages=kv_pages,
                own_tile_spans=own_tile,
                ragged_grid_steps=grid_steps,
                attn_qk_pairs=qk_pairs,
                attn_keys_selected=keys_selected,
                attn_rows_read=rows_read,
                host_overhead_s=max(0.0, wall_s - self._last_fetch_s),
                compile_s=compile_s,
                compiled_programs=compiled_programs,
                **self._expert_fields(),
                **self._window_fields,
                **self._window_pool_pages(),
            )
            self.metrics.record_step(m)
        self._step += 1
        return m

    def _note_compiled(self, since: float, width: int,
                       q_tile: int) -> tuple[float, int]:
        """A step in which JAX traced or compiled something (the
        compile log moved: tracing, lowering and compiling run on the
        calling thread, so they are over by now) says so: ONE mark on
        the profiler's timeline with the step's shape and what the
        log holds since the step opened, which is also the step's
        ``compile_s`` and ``compiled_programs``.  ``cache`` is "miss"
        where the persistent cache was written to, "hit" where it was
        only read, "off" where neither (none is set, or it declined
        the entries).  Another thread's compiles of the same moments
        are counted with the step's."""
        log = _compiles.summary(since=since)
        top = log["by_function"]
        with obs.span("engine.program.compiled", step=self._step,
                      width=width, q_tile=q_tile,
                      trace_ms=log["trace_s"] * 1e3,
                      lower_ms=log["lower_s"] * 1e3,
                      compile_ms=log["compile_s"] * 1e3,
                      cache=("miss" if log["cache_misses"] else
                             "hit" if log["cache_hits"] else "off"),
                      function=top[0]["function"] if top else ""):
            pass
        if width:
            self.metrics.compiled_shapes.add((width, q_tile))
        return log["all_s"], log["programs"]

    def _window_pool_pages(self) -> dict[str, int]:
        """The second page space's pages free and in use, by
        `StepMetrics`' names; nothing for a model without one."""
        if self.window_pool is None:
            return {}
        return {"window_free_pages": self.window_pool.free_pages,
                "window_used_pages": self.window_pool.used_pages}

    def _expert_fields(self) -> dict[str, int]:
        """The step's counts from the device as `StepMetrics` has
        them: the expert pairs, and LAST the keys attended where the
        attention chooses its keys."""
        pairs = self._expert_pairs
        if pairs is None:
            return {}
        fields = {}
        if self._indexed_layers:
            pairs, fields = pairs[:-1], {
                "attn_keys_attended": int(pairs[-1])}
            if obs.is_enabled():
                _ATTENTION_KEYS.inc(fields["attn_keys_attended"],
                                    which="attended")
        if not self._expert_layers:
            return fields
        # (`models.moe.pair_counts`) the held experts' pairs, then the
        # absent pairs, the experts reached and, where the layers have
        # zero-compute experts, the pairs that went to those
        n = self.metrics.held_experts
        held, (absent, reached, *more) = pairs[:n], pairs[n:].tolist()
        local, zero = int(held.sum()), sum(more)
        if obs.is_enabled():
            _EXPERT_PAIRS.inc(local, where="local")
            _EXPERT_PAIRS.inc(absent, where="absent")
            if more:
                _EXPERT_PAIRS.inc(zero, where="zero")
        return {**fields,
                "expert_pairs_local": local,
                "expert_pairs_absent": absent,
                "expert_load_max": int(held.max()),
                "experts_reached": reached,
                "expert_pairs_zero": zero}

    def run(self, *, max_steps: int | None = None) -> dict[str, Any]:
        """Step until every request finishes; returns the metrics
        summary.  Detects a permanently unschedulable queue (a request
        that can never fit the pool) and raises instead of spinning."""
        stalls = 0
        while self.scheduler.has_work():
            if max_steps is not None and self._step >= max_steps:
                raise StepLimitExceededError(
                    f"engine exceeded max_steps={max_steps} with "
                    f"{len(self.scheduler.waiting)} waiting / "
                    f"{len(self.scheduler.running)} running"
                )
            m = self.step()
            due = (self.scheduler.waiting
                   and self.scheduler.waiting[0].arrival < self._step)
            idle = (m.decode_tokens == 0 and m.prefill_tokens == 0
                    and not self.scheduler.running)
            stalls = stalls + 1 if (idle and due) else 0
            if stalls > 2:
                head = self.scheduler.waiting[0]
                raise OutOfPagesError(
                    f"request {head.request_id} cannot be admitted "
                    "(needs more pages than the pool can ever free)"
                )
        return self.metrics.summary()

    # -- health / drain hooks (the multi-replica front end's probes) ------

    def health(self) -> dict[str, Any]:
        """Cheap host-side pressure snapshot — what a fronting router
        reads every tick to drive load scoring, shedding thresholds,
        and the degradation ladder.  Pure Python state, no device
        sync, safe to call between steps at any frequency."""
        return {
            "step": self._step,
            "waiting": len(self.scheduler.waiting),
            "running": len(self.scheduler.running),
            "free_pages": self.pool.free_pages,
            "used_pages": self.pool.used_pages,
            "page_utilization": self.pool.used_pages
            / self.pool.num_pages,
            "cached_pages": self.allocator.cached_pages,
            **self._window_pool_pages(),
            "preemptions": self.scheduler.num_preemptions,
            "nonfinite_events": self.nonfinite_events,
            "step_virtual_cost": self.last_step_virtual_cost,
        }

    def drain(self, *, max_steps: int | None = None) -> dict[str, Any]:
        """Graceful shutdown: serve the current queue dry and return
        the metrics summary.  New work only arrives through
        add_request/resume_request, so a caller that stops admitting
        and calls drain gets clean quiescence — every page back in the
        pool or held solely by the prefix cache."""
        return self.run(max_steps=max_steps)

    # -- batch lowering ---------------------------------------------------

    def _place_pool(self, arr):
        """Device placement for one per-layer pool: one KV-head slice
        per shard on a mesh engine, plain single-device otherwise.
        Snapshot restore routes reconstructed pools through this too,
        so a restored mesh engine's pools land sharded again."""
        arr = jnp.asarray(arr)
        if self._pool_sharding is None:
            return arr
        return jax.device_put(arr, self._pool_sharding)

    def _upload(self, buffer: np.ndarray) -> jax.Array:
        """A busy step's ONE host-to-device transfer: its whole packed
        buffer (`PackedBatch.buffer`), replicated on a mesh engine as
        the parameters are."""
        return jax.device_put(buffer, self._replicated)

    def _fetch_logits(self, logits_dev, used: int,
                      pairs_dev=None) -> np.ndarray:
        """The step loop's ONLY device sync: materialize on host the
        logits rows the launch returned — the rows that can be sampled
        (`_ragged_apply`), of which this step samples ``used`` — and
        with them, where the model has expert layers, the step's count
        of expert pairs (``pairs_dev``, a few hundred bytes).
        Isolated in one hook so (a) per-step host overhead is
        measurable as wall minus time spent here, and (b) fault
        injectors have a single seam to poison."""
        rows = logits_dev.size // logits_dev.shape[-1]
        if obs.is_enabled():
            _LOGIT_ROWS.inc(rows, kind="fetched")
            _LOGIT_ROWS.inc(used, kind="used")
        with obs.span("engine.step.fetch", bytes=4 * logits_dev.size,
                      rows=rows, used=used):
            t0 = time.perf_counter()
            out = np.asarray(logits_dev, np.float32)
            if pairs_dev is not None:
                self._expert_pairs = np.asarray(pairs_dev)
            self._last_fetch_s += time.perf_counter() - t0
        return out

    def _q_tile(self, max_q: int) -> int:
        """The query tile of a step whose longest span is ``max_q``
        tokens: the kernel's recommendation, and for a step that holds
        a chunk no less than ``min_prefill_tile``."""
        cfg, model = self.config, self.model
        group = model.num_q_heads // model.num_kv_heads
        tile = recommended_q_tile(
            max_q, group, heads=model.num_q_heads,
            kv_heads=model.num_kv_heads, seq=cfg.max_seq_len,
            dim=getattr(model, "head_size", model.dim // model.num_q_heads),
            batch=cfg.max_decode_batch + cfg.max_prefill_rows,
            dtype=cfg.cache_dtype or model.dtype,
        )
        if max_q > 1 and cfg.min_prefill_tile > tile:
            tile = tile_tokens(cfg.min_prefill_tile, group)
        return tile

    def step_shape(self, decoding: int, chunk: int) -> tuple[int, int]:
        """The ``(width, q_tile)`` of the program a step of
        ``decoding`` decode rows beside one prefill chunk of ``chunk``
        tokens (0: none) runs: `_run_ragged`'s own arithmetic, for a
        caller that warms the shapes up."""
        q_tile = self._q_tile(max(chunk, 1))
        return packed_bucket(max(decoding + chunk, q_tile)), q_tile

    def _run_ragged(self, sched: ScheduledStep
                    ) -> tuple[int, int, int, int, int, int, int, int]:
        """Lower the WHOLE step onto one jitted packed launch; returns
        the packed width and the query tile dispatched (the program's
        shape), the step's live (slot, page) pairs (what the attention
        kernel's grid walks, or, where a selector chooses, its
        scoring's), the (query token, key) pairs one attention sublayer
        attends (or, where it chooses its keys, scores), the pairs the
        choice keeps by its rule (0 without one), the cache rows one
        sublayer's attention reads, the spans of one token that the
        kernel served at a tile of their own, and the grid steps of the
        sublayers' page-walking kernels.  Where window layers
        stand beside full layers, pages, pairs and rows are the SUM of
        one sublayer of each kind, and the window kind's part goes to
        ``_window_fields``.

        The per-request query tile covers the longest prefill chunk and
        the packed width covers every real token, both pow2-bucketed —
        occupancy stays high while compiled signatures stay
        O(log max_tokens)."""
        cfg = self.config
        with obs.span("engine.step.pack"):
            slots = cfg.max_decode_batch + cfg.max_prefill_rows
            max_q = max((n for _, n in sched.prefill), default=1)
            q_tile = self._q_tile(max_q)
            if q_tile > self._widest_tile and self.window_pool is not None:
                # the kernel would reach below the band, into pages
                # given back and handed to another request
                raise PageAccountingError(
                    f"a query tile of {q_tile} tokens, wider than the "
                    f"{self._widest_tile} the window pages' band was "
                    "sized for")
            total = sched.num_decode_tokens + sched.num_prefill_tokens
            width = packed_bucket(max(total, q_tile))
            batch = sched.pack(width=width, slots=slots,
                               table_width=cfg.table_width,
                               recurrent=self._layout.state_rows,
                               window_tables=self._layout.window_table)
            q_lens = np.diff(batch.cu_q_lens)
            # the kernel's grid bound for this step, counted here by
            # the rule the device builds it from (a slot the append
            # poisons there reads 1 on the device, its pages here),
            # and the pairs attended: one sublayer of each kind
            walked = [int(live_pages(
                batch.kv_lens + q_lens, batch.cu_q_lens,
                batch.distribution, max_pages=cfg.table_width,
                page=cfg.page_size, q_tile=q_tile, window=window,
                sinks=self.model.attn_sinks or None, xp=np).sum())
                for window in self._windows]
            pairs = [_qk_pairs(batch.kv_lens, q_lens, window)
                     for window in self._windows]
            kv_pages, qk_pairs = sum(walked), sum(pairs)
            # beside a chunk the kernel gives a decode row its own tile
            # (a list of rows is attended at no tile)
            wide, one_token = span_tile_rows(
                q_tile, width,
                self.model.num_q_heads // self.model.num_kv_heads,
                row_blocked=bool(self._latent_layers))
            own_tile = (int((q_lens == 1).sum())
                        if one_token < wide and not self._indexed_layers
                        else 0)
            grid_steps = self._grid_steps(batch, q_lens, walked, width,
                                          q_tile)
            if self._window_layers:
                needed = [_band_pages(batch.kv_lens, q_lens, window,
                                      cfg.page_size)
                          for window in self._windows]
                self._window_fields.update(
                    kv_pages_window=walked[1],
                    attn_qk_pairs_window=pairs[1],
                    attn_band_pages=sum(needed),
                    attn_band_pages_window=needed[1])
            # a row keeps the ``index_topk`` best of the keys it sees:
            # the count of a window that wide
            keys_selected = _qk_pairs(
                batch.kv_lens, np.diff(batch.cu_q_lens), self._index_topk
            ) if self._indexed_layers else 0
            # a walk reads its pages whole; a choice is read as a list
            rows_read = (keys_selected if self._indexed_layers
                         else kv_pages * cfg.page_size)
        with obs.span("engine.step.upload", bytes=batch.buffer.nbytes,
                      arrays=1):
            buffer = self._upload(batch.buffer)
        sampled = len(sched.decode) + len(sched.prefill)
        fields = {}
        if self._state_layers:
            fields = {"recurrent_tokens": total,
                      "recurrent_slot_steps": sampled,
                      "state_layers": len(self._state_layers)}
        if self._window_layers:
            fields["window_layers"] = len(self._window_layers)
            fields["window_kv_pages"] = walked[1]
        if self._latent_layers:
            fields["latent_layers"] = len(self._kv_layers)
        if self._indexed_layers:
            # sublayers whose selector scores `qk_pairs` and whose
            # attention keeps ``index_topk`` of a row's keys at most
            fields["sparse_layers"] = len(self._indexed_layers)
            fields["index_topk"] = self._index_topk
            if obs.is_enabled():
                _ATTENTION_KEYS.inc(qk_pairs * len(self._indexed_layers),
                                    which="scored")
                _ATTENTION_KEYS.inc(rows_read * len(self._indexed_layers),
                                    which="rows_read")
        if self._expert_layers:
            fields["expert_layers"] = len(self._expert_layers)
            if getattr(self.model, "zero_experts", 0):
                # the pairs they take are known at the fetch
                # (`StepMetrics.expert_pairs_zero`)
                fields["zero_experts"] = self.model.zero_experts
        if obs.is_enabled():
            _LAUNCHES.inc()
        with obs.span("engine.step.dispatch", width=width, q_tile=q_tile,
                      decode_rows=len(sched.decode),
                      prefill_tokens=sched.num_prefill_tokens,
                      kv_pages=kv_pages, own_tile_spans=own_tile,
                      ragged_grid_steps=grid_steps, attn_rows=rows_read,
                      **fields):
            logits_dev, pools, pairs_dev = _ragged_apply(
                self._step_model, self.params, buffer, tuple(self._pools),
                StepLayout(slots, cfg.table_width, q_tile))
            self._pools = list(pools)
        logits = self._fetch_logits(logits_dev, sampled, pairs_dev)
        with obs.span("engine.step.sample", rows=sampled):
            row_of = _sampled_logit_rows(batch.cu_q_lens, width)
            num_decode = len(sched.decode)
            for i, req in enumerate(sched.decode):
                self._post_decode(req, logits[0, row_of[i]])
            for s, (req, real) in enumerate(sched.prefill):
                self._post_prefill(
                    req, real, logits[0, row_of[num_decode + s]])
        return (width, q_tile, kv_pages, qk_pairs, keys_selected, rows_read,
                own_tile, grid_steps)

    def _grid_steps(self, batch, q_lens, walked: list[int], width: int,
                    q_tile: int) -> int:
        """The grid steps of the step's page-walking attention kernels,
        by the kernel's own rules: each sublayer's work items (a kind's
        ``walked`` pairs, or the row-blocked form's pages a block)
        times the blocks its KV heads are carried in
        (`ops.ragged_paged.head_block`); 0 where a list is attended."""
        cfg, model = self.config, self.model
        if self._indexed_layers:
            return 0
        group = model.num_q_heads // model.num_kv_heads
        if self._latent_layers:
            block_tokens, blocks = row_block_shape(q_tile, group)
            return len(self._kv_layers) * row_block_count(
                batch.kv_lens + q_lens, batch.cu_q_lens, batch.distribution,
                max_pages=cfg.table_width, page=cfg.page_size,
                block_tokens=block_tokens, blocks=blocks)
        kv_heads, (d, dv) = model.kv_pool_widths()
        kv_heads //= cfg.mesh_shards or 1   # a shard's kernel sees its own
        head_blocks = kv_heads // head_block(
            kv_heads, q_tile, width, group, d=d, dv=dv, page=cfg.page_size,
            q_itemsize=jnp.dtype(model.dtype).itemsize,
            kv_itemsize=jnp.dtype(cfg.cache_dtype or model.dtype).itemsize)
        layers = [len(self._kv_layers) - len(self._window_layers),
                  len(self._window_layers)]
        return head_blocks * sum(n * of for n, of in zip(walked, layers))

    def quiesce(self) -> None:
        """Block until the device pools are final.  A snapshot cut
        runs this before it reads them."""
        jax.block_until_ready(self._pools)

    def _post_decode(self, req: Request, logits_row: np.ndarray) -> None:
        """Consume one decode request's logits row: guard it, sample
        from it, emit."""
        if not np.isfinite(logits_row).all():
            # poisoned logits must never reach sampling: a garbage
            # token would break parity with the fault-free run.
            # Un-feed the pending token (its KV slot is simply
            # overwritten on retry; a recurrent state is recomputed,
            # `_recompute_state`) so the request makes no
            # progress this step, and count the event — the
            # replica supervisor's NaN signal.  Bounded: see
            # _NONFINITE_SKIP_LIMIT.
            self.nonfinite_events += 1
            skips = self._nonfinite_skips.get(req.request_id, 0) + 1
            self._nonfinite_skips[req.request_id] = skips
            if skips <= _NONFINITE_SKIP_LIMIT:
                req.pending_token = req.tokens.pop()
                self._recompute_state(req)
                return
        else:
            self._nonfinite_skips.pop(req.request_id, None)
        req.computed_tokens = len(req.tokens)
        self._emit(req, self._sample(req, logits_row))

    def _recompute_state(self, req: Request) -> None:
        """The retry after a non-finite row, for a model with recurrent
        layers.  A KV row is overwritten in place by the retry; a
        recurrent state was already advanced by the skipped token or
        chunk and would take it a second time.  Nobody kept the state
        before the step, so the request goes back to the queue and is
        recomputed from token 0, as after a preemption."""
        if self._layout.state_rows:
            self.scheduler.requeue_for_recompute(req)

    def _post_prefill(self, req: Request, real: int,
                      last_row: np.ndarray) -> None:
        """Consume one prefill chunk's last logits row: advance the
        request, and on its final chunk sample the first token."""
        if (req.computed_tokens + real >= len(req.tokens)
                and not req.output_tokens
                and not np.isfinite(last_row).all()):
            # the final chunk samples the first token; with
            # non-finite logits, skip the whole chunk (the KV it
            # wrote is recomputed in place next step, a recurrent
            # state from token 0) rather than emit garbage.  Bounded:
            # see _NONFINITE_SKIP_LIMIT.
            self.nonfinite_events += 1
            skips = self._nonfinite_skips.get(req.request_id, 0) + 1
            self._nonfinite_skips[req.request_id] = skips
            if skips <= _NONFINITE_SKIP_LIMIT:
                self._recompute_state(req)
                return
        req.computed_tokens += real
        if req.computed_tokens < len(req.tokens):
            return  # more chunks to go
        self._commit_prefix(req)
        req.transition(RequestState.DECODING)
        if req.output_tokens:
            # resumed after preemption: the recomputed KV now covers
            # every fed token; the pending token was already sampled
            # and streamed — never resample it
            return
        self._emit(req, self._sample(req, last_row))

    def _commit_prefix(self, req: Request) -> None:
        full = req.num_prompt_tokens // self.config.page_size
        if full and not self._layout.state_rows:
            self.allocator.commit_prefix(
                req.prompt, req.pages[:full], now=self._step,
                window_pages=(req.window_pages[:full]
                              if self.window_pool is not None else None),
            )
            if self.prefix_store is not None:
                # fleet export rides the local commit: the pages just
                # became shared-by-reference here, so publish them to
                # the store (waiters on this chain's single-flight
                # lease observe the chain and import next tick)
                from attention_tpu.prefixstore.adapter import export_chain

                export_chain(
                    self, req.prompt, req.pages[:full],
                    now=self.trace_start_tick + self._step,
                )

    # -- token emission ---------------------------------------------------

    def _sample(self, req: Request, logits_row: np.ndarray) -> int:
        if req.sampling.temperature == 0.0:
            return int(np.argmax(logits_row))
        from attention_tpu.models.decode import warp_logits

        key = self._rng_keys.get(req.request_id)
        if key is None:
            key = jax.random.PRNGKey(req.sampling.seed)
        key, sub = jax.random.split(key)
        self._rng_keys[req.request_id] = key
        warped = warp_logits(
            jnp.asarray(logits_row)[None],
            temperature=req.sampling.temperature,
            top_k=req.sampling.top_k,
            top_p=req.sampling.top_p,
        )
        return int(jax.random.categorical(sub, warped, axis=-1)[0])

    def _emit(self, req: Request, token: int) -> None:
        done = req.emit(token)
        if self.journal is not None:
            self.journal.record_token(req.request_id, token)
        if req.first_token_step < 0:
            req.first_token_step = self._step
            wall = self._wall[req.request_id]
            wall["first_token"] = now = time.perf_counter()
            scheduled = wall.get("scheduled", wall["added"])
            with obs.span("engine.request.first_token", rid=req.request_id,
                          queue_wait_ms=(scheduled - wall["added"]) * 1e3,
                          prefill_ms=(now - scheduled) * 1e3):
                pass
            if _trace.active():
                self._trace_event(req, "first_token")
        if self.on_token is not None:
            self.on_token(req, token)
        if done:
            self._finish(req)

    def _finish(self, req: Request) -> None:
        req.transition(RequestState.FINISHED)
        req.finish_step = self._step
        if _trace.active() and self.trace_owner == "engine":
            self._trace_event(req, "finished")
        self._nonfinite_skips.pop(req.request_id, None)
        if self.journal is not None:
            self.journal.record_finish(req.request_id)
        self.allocator.release(req)
        self.scheduler.remove_finished(req)
        self._rng_keys.pop(req.request_id, None)
        self._finished_in_step += 1
        wall = self._wall.pop(req.request_id, {})
        now = time.perf_counter()
        added = wall.get("added", now)
        scheduled = wall.get("scheduled", added)
        first_token = wall.get("first_token", now)
        self.metrics.record_request(RequestMetrics(
            request_id=req.request_id,
            arrival_step=req.arrival,
            first_scheduled_step=req.first_scheduled_step,
            first_token_step=req.first_token_step,
            finish_step=req.finish_step,
            prompt_tokens=req.num_prompt_tokens,
            output_tokens=req.num_output_tokens,
            prefix_cached_tokens=req.prefix_cached_tokens,
            preemptions=req.preemptions,
            ttft_s=first_token - added,
            finish_s=now - added,
            queue_wait_s=scheduled - added,
            prefill_s=first_token - scheduled,
        ))
        if self.on_finish is not None:
            self.on_finish(req)


# re-exported for callers that only import the engine module
__all__ = [
    "EngineConfig",
    "ServingEngine",
    "Request",
    "RequestState",
    "SamplingParams",
    "ScheduledStep",
]
