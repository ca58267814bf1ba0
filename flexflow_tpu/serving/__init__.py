"""flexflow_tpu.serving: continuous-batching inference on the trained PCG.

The training side of this rebuild compiles a PCG into one jitted train
step; this package is the inference mirror (upstream FlexFlow grew the
same subsystem as FlexFlow Serve): a block-paged KV cache with a
host-side page allocator and block tables (kv_cache),
prefill/decode step functions that re-execute the
compiled graph with a cache-aware attention hook (engine), an Orca-style
iteration-level scheduler with per-request fault isolation, deadlines/
cancellation, and optimistic-admission preemption-by-recompute
(scheduler), a seeded deterministic fault-injection harness (faults),
and the `FFModel.generate` / ServeConfig surface (api). The decode
regime also has its own cost family in search/cost_model.py so the
auto-parallel search can pick a serving strategy (TP over heads at
small batch) distinct from the training one. Observability lives in
its own package (flexflow_tpu.telemetry — metrics registry, Chrome
trace export, rolling-window SLO monitor) and threads through every
seam here via `build_scheduler`'s ServeConfig telemetry knobs
(--metrics-out/--metrics-jsonl/--trace/--slo-ttft-ms/--slo-itl-ms);
SchedulerStats is a façade over the same registry the exporters read.
"""

from flexflow_tpu.serving.api import (
    ServeConfig,
    build_journal,
    build_proposer,
    build_restore_decider,
    build_scheduler,
    build_telemetry,
    generate,
)
from flexflow_tpu.telemetry import Telemetry
from flexflow_tpu.serving.engine import (
    GenerationEngine,
    InflightStep,
    snapshot,
)
from flexflow_tpu.serving.faults import (
    DraftFault,
    FaultError,
    FaultInjector,
    FaultPlan,
    KernelFault,
    ProcessCrash,
)
from flexflow_tpu.serving.journal import (
    JournalCorrupt,
    RecoveredRequest,
    RecoveryState,
    RequestJournal,
    read_journal,
    readmit,
    recover_journal,
)
from flexflow_tpu.serving.kv_cache import (
    KVCacheSpec,
    PagedKVCache,
    PagePoolExhausted,
    default_buckets,
    default_page_size,
)
from flexflow_tpu.serving.scheduler import (
    TERMINAL_STATUSES,
    AsyncContinuousBatchingScheduler,
    ContinuousBatchingScheduler,
    Request,
    RequestStatus,
    SchedulerStats,
    latency_percentiles,
)
from flexflow_tpu.serving.spec import (
    DraftProposer,
    DraftTree,
    ModelDraftProposer,
    NGramDraftProposer,
    accept_drafts,
    accept_tree,
)
from flexflow_tpu.serving.tenancy import (
    AdapterPool,
    AdapterPoolExhausted,
    DeficitRoundRobin,
    PriorityClass,
    make_lora_weights,
    parse_classes,
)
from flexflow_tpu.serving.frontend import (
    DisaggregatedPipeline,
    EngineReplica,
    FrontDoor,
    PrefillOnlyScheduler,
    ReplicaRouter,
    StreamEvent,
    serve_tcp,
)

__all__ = [
    "ServeConfig",
    "generate",
    "build_proposer",
    "build_scheduler",
    "build_telemetry",
    "Telemetry",
    "GenerationEngine",
    "InflightStep",
    "snapshot",
    "KVCacheSpec",
    "PagedKVCache",
    "default_buckets",
    "default_page_size",
    "Request",
    "RequestStatus",
    "TERMINAL_STATUSES",
    "AsyncContinuousBatchingScheduler",
    "ContinuousBatchingScheduler",
    "SchedulerStats",
    "latency_percentiles",
    "FaultError",
    "FaultInjector",
    "FaultPlan",
    "KernelFault",
    "DraftFault",
    "ProcessCrash",
    "RequestJournal",
    "JournalCorrupt",
    "RecoveredRequest",
    "RecoveryState",
    "read_journal",
    "readmit",
    "recover_journal",
    "build_journal",
    "build_restore_decider",
    "PagePoolExhausted",
    "DraftProposer",
    "DraftTree",
    "ModelDraftProposer",
    "NGramDraftProposer",
    "accept_drafts",
    "accept_tree",
    "AdapterPool",
    "AdapterPoolExhausted",
    "DeficitRoundRobin",
    "PriorityClass",
    "make_lora_weights",
    "parse_classes",
    "DisaggregatedPipeline",
    "EngineReplica",
    "FrontDoor",
    "PrefillOnlyScheduler",
    "ReplicaRouter",
    "StreamEvent",
    "serve_tcp",
]
