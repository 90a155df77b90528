"""Continuous-batching serving engine over the paged KV kernels.

The bridge from "fast kernel" to "high-throughput server": many
concurrent requests in, one packed `ragged_paged_append` +
`ragged_paged_attention` launch a step out.

    requests ──> Scheduler ────────> ServingEngine.step()
                   │  FCFS admission,     │  one packed launch:
                   │  chunked prefill ⊕   │  decode rows + chunks
                   │  decode batching,    ▼
                   │  preemption      ops.ragged_paged
                   ▼                      │
               BlockAllocator <───────────┘
                   watermark-guarded pages + hash-keyed
                   prefix cache (incref'd shared pages, LRU eviction)

Modules: `request` (lifecycle + sampling params), `allocator` (pages +
prefix cache), `scheduler` (iteration-level batch composition),
`engine` (the step loop), `metrics` (TTFT/TPOT/page-utilization
records), `sim` (JSON traces + replay — `cli serve-sim`'s core),
`snapshot` + `journal` (crash-consistent durability: checksummed
atomic snapshots, write-ahead log, warm recovery).
"""

from attention_tpu.engine.allocator import (  # noqa: F401
    BlockAllocator,
    pages_for_tokens,
)
from attention_tpu.engine.engine import (  # noqa: F401
    EngineConfig,
    ServingEngine,
    StepLimitExceededError,
)
from attention_tpu.engine.errors import (  # noqa: F401
    DeadlineExceededError,
    PrefixLeaseError,
    PrefixStoreCorruptError,
    RecurrentStateUnsupportedError,
    ReplicaDeadError,
    ReplicaStateError,
    RequestShedError,
    SnapshotCorruptError,
    SnapshotError,
    StepInterruptedError,
)
from attention_tpu.engine.journal import (  # noqa: F401
    Journal,
    apply_journal,
)
from attention_tpu.engine.metrics import (  # noqa: F401
    EngineMetrics,
    RequestMetrics,
    StepMetrics,
)
from attention_tpu.engine.request import (  # noqa: F401
    TERMINAL_STATES,
    Request,
    RequestState,
    SamplingParams,
)
from attention_tpu.engine.scheduler import (  # noqa: F401
    ScheduledStep,
    Scheduler,
)
from attention_tpu.engine.sim import (  # noqa: F401
    bursty_trace,
    diurnal_trace,
    load_trace,
    replay,
    sampling_of,
    save_trace,
    synthetic_trace,
)
from attention_tpu.engine.snapshot import (  # noqa: F401
    SnapshotManager,
    recover_engine,
    state_fingerprint,
)
