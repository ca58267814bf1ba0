"""User-facing serving surface: ServeConfig + generate().

`FFModel.generate` (runtime/model.py) delegates here, mirroring how the
reference grew FlexFlow Serve on top of the training FFModel. ServeConfig
rides FFConfig flag parsing (`--max-seqs`, `--max-seq-len`,
`--eos-token`, `--spec-draft`, `--spec-k`), so
serving scripts configure the engine with the same CLI the training
examples use.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from flexflow_tpu.serving.engine import GenerationEngine
from flexflow_tpu.serving.kv_cache import PagedKVCache
from flexflow_tpu.serving.scheduler import (
    AsyncContinuousBatchingScheduler,
    ContinuousBatchingScheduler,
    Request,
)

_SPEC_DRAFTS = ("", "ngram", "model")


@dataclasses.dataclass
class ServeConfig:
    """Serving knobs (reference: RequestManager configuration in FlexFlow
    Serve; Orca's max_batch_size / max_seq_len pair)."""

    max_seqs: int = 8  # KV-cache slots = max in-flight requests
    max_seq_len: int = 256  # max tokens per sequence (prompt + generation)
    eos_token: Optional[int] = None
    temperature: float = 0.0  # 0 = greedy
    seed: int = 0
    prefill_buckets: Tuple[int, ...] = ()  # () = powers of two
    # the KV cache is paged (PagedAttention, SOSP'23): pools of pages
    # routed through block tables
    kv_page_size: int = 0  # 0 = auto (vLLM-style 16, halved to divide max_len)
    kv_pages: int = 0  # 0 = max_seqs * max_seq_len / page_size (same capacity)
    # K/V pool element type (--kv-dtype): "int8" quantizes both pools
    # (fp32 scale per page per head in side pools, dequant fused into
    # the per-chunk attention loop) for ~4x cache bytes.
    kv_dtype: str = "fp32"
    # hashed prefix-page cache (--prefix-cache): admissions map full
    # pages whose chained content hash matches an already-resident
    # prefix (refcounted, copy-on-write on first divergent write)
    # instead of recomputing them; sharing is page-aligned by
    # construction.
    prefix_cache: bool = False
    # speculative decoding (SpecInfer, ASPLOS'24; serving/spec.py):
    # "" = off, "ngram" = weight-free prompt-lookup draft, "model" = a
    # second compiled decoder LM (pass it as build_scheduler/generate's
    # draft_model). spec_k is the draft length per verify step;
    # spec_ngram the lookup n-gram size.
    # spec_branch > 1 switches to token-TREE speculation: each verify
    # scores a deduped tree of up to spec_k * spec_branch draft nodes
    # (depth spec_k, spec_branch alternatives per level) and accepts
    # the longest surviving root-to-leaf path; 1 keeps the linear
    # chain path bit-for-bit.
    spec_draft: str = ""
    spec_k: int = 4
    spec_branch: int = 1
    spec_ngram: int = 2
    # chunked prefill (Sarathi-Serve; serving/scheduler.py):
    # token_budget > 0 caps each iteration's token work — prompts
    # stream into the cache in chunk_size-aligned chunks interleaved
    # with in-flight decodes instead of one monolithic admission
    # prefill (the head-of-line blocking fix). 0 = off.
    # auto.optimize_token_budget picks a budget that meets
    # slo_ttft_ms / slo_itl_ms from the cost model.
    token_budget: int = 0
    chunk_size: int = 16
    # decode/verify attention core (ops/pallas/decode_kernel.py):
    # "auto" = the Pallas flash-decode kernel on TPU when the geometry
    # supports() it (dense otherwise), "pallas" = force the kernel
    # (interpret mode off-TPU — the CI/parity path), "dense" = always
    # the jnp paths.
    decode_kernel: str = "auto"
    # admission policy (serving/scheduler.py):
    # "reserve" gates each admit on its worst-case page need on top of
    # every in-flight reservation (preemption-free); "optimistic"
    # admits on the pages needed NOW and answers later pool exhaustion
    # with preemption-by-recompute, bounded by max_preemptions per
    # request before hard FAILED.
    admission: str = "reserve"
    max_preemptions: int = 3
    # the loop `build_scheduler` gives. True (the default): one decode
    # step stays in flight, step N+1 dispatched with its tokens chained
    # on the device before step N is read back
    # (AsyncContinuousBatchingScheduler), so the device does not wait
    # for the host between steps. Terminal events land at the read-back:
    # cancel() of a running request and a running deadline take effect
    # one step later, and a request that ends on EOS costs one discarded
    # slot-step. False (--serve-async=0): the synchronous loop, the
    # reference the overlapped one is held token-identical to.
    serve_async: bool = True
    # debug: re-run cache.check_invariants() after every scheduler
    # iteration (--check-invariants). Off by default — the full
    # allocator re-derivation is O(slots × pages) per iteration, a
    # debugging/CI posture rather than a serving one.
    debug_invariants: bool = False
    # telemetry (flexflow_tpu.telemetry): setting ANY of these attaches
    # a Telemetry bundle to the engine + scheduler. metrics_out writes
    # Prometheus text exposition at flush; metrics_jsonl streams one
    # sample row per scheduler iteration; trace writes a Chrome
    # trace-event JSON (Perfetto-loadable) of engine phases + request
    # lifecycles; slo_ttft_ms / slo_itl_ms (milliseconds, 0 = no
    # threshold) feed serve_slo_violations_total from rolling windows
    # of slo_window observations. `telemetry=True` force-enables the
    # in-memory bundle with no output paths (tests, embedding callers).
    metrics_out: str = ""
    metrics_jsonl: str = ""
    trace: str = ""
    slo_ttft_ms: float = 0.0
    slo_itl_ms: float = 0.0
    slo_window: int = 1024
    telemetry: bool = False
    # pod serving (serving/distributed.py): serve_mesh = "dp,tp" applies
    # a (data, model) serving mesh via FFModel.compile_for_serving;
    # serve_hosts > 0 partitions slots and the page pool across that
    # many host shards (0 = auto: jax.process_count(), else dp).
    serve_mesh: str = ""
    serve_hosts: int = 0
    # graceful degradation under pressure (serving/kv_cache.py +
    # scheduler.py). kv_swap (--kv-swap): a preemption victim's
    # committed pages are staged to host buffers and restored
    # page-for-page at re-admission — no re-prefill — whenever the cost
    # model prices the copy under the recompute; kv_swap_bytes
    # (--kv-swap-bytes) caps the host bytes held at once (0 =
    # unbounded). prefix_evict (--prefix-evict): "lru" lets published
    # prefix pages whose refcount is publication-only be reclaimed
    # (last-use LRU order) before any live request is preempted;
    # "cost" reclaims the page CHEAPEST to recompute instead (priced
    # by CostModel.prefill_chunk_cost over the page's token span —
    # deep chain tails stay warm); "none" retains them forever (the
    # pre-PR-14 behavior).
    kv_swap: bool = False
    kv_swap_bytes: int = 0
    prefix_evict: str = "none"
    # multi-tenant serving (serving/tenancy/). adapters (--adapters):
    # > 0 attaches a paged multi-LoRA AdapterPool sized for that many
    # resident adapters of rank <= adapter_rank (--adapter-rank);
    # requests pick one via Request.adapter_id (-1 = base model,
    # bit-identical to serving without a pool). classes (--classes):
    # "name:weight[:ttft_ms[:itl_ms]]" entries, comma-separated — more
    # than one class switches admission + chunk grants to weighted-fair
    # deficit round-robin, preemption victims to class-priced cost, and
    # attaches per-class SLO monitors under {"class": name} labels.
    adapters: int = 0
    adapter_rank: int = 8
    classes: str = ""
    # durable serving (serving/journal.py). journal (--journal): path
    # of the append-only write-ahead request journal ("" = off) —
    # submit/commit/terminal records at the host-sync grain, the state
    # a crash-restart rebuilds token-identical streams from.
    # journal_fsync (--journal-fsync): "commit" fsyncs every record,
    # "batch" once per host sync (default), "off" flushes but never
    # fsyncs. journal_snapshot_every (--journal-snapshot-every): > 0
    # journals a KV snapshot of every running slot each N iterations,
    # letting recovery restore KV over import_swap
    # instead of recomputing when build_restore_decider prices the
    # copy cheaper. door_max_pending (--door-max-pending): bounds the
    # front door's admission backlog; past it, per-class weighted-share
    # shedding refuses new streams with a retry_after hint (0 =
    # unbounded). breaker_threshold / breaker_cooldown
    # (--breaker-threshold / --breaker-cooldown): consecutive failed
    # health probes before a replica's circuit breaker opens, and the
    # router iterations it stays open before a half-open trial
    # placement (threshold 0 = breaker off).
    journal: str = ""
    journal_fsync: str = "batch"
    journal_snapshot_every: int = 0
    door_max_pending: int = 0
    breaker_threshold: int = 0
    breaker_cooldown: int = 8

    def __post_init__(self):
        if self.max_seqs < 1 or self.max_seq_len < 2:
            raise ValueError("max_seqs >= 1 and max_seq_len >= 2 required")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}"
            )
        if self.admission not in ("reserve", "optimistic"):
            raise ValueError(
                f"admission must be 'reserve' or 'optimistic', "
                f"got {self.admission!r}"
            )
        if self.max_preemptions < 0:
            raise ValueError("max_preemptions must be >= 0")
        if self.kv_page_size < 0 or self.kv_pages < 0:
            raise ValueError("kv_page_size and kv_pages must be >= 0")
        if self.kv_page_size and self.max_seq_len % self.kv_page_size:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} is not divisible by "
                f"kv_page_size {self.kv_page_size}"
            )
        if self.kv_dtype not in ("fp32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'fp32' or 'int8', got {self.kv_dtype!r}"
            )
        if self.spec_draft not in _SPEC_DRAFTS:
            raise ValueError(
                f"spec_draft must be one of {_SPEC_DRAFTS}, "
                f"got {self.spec_draft!r}"
            )
        if self.spec_draft and self.spec_k < 1:
            raise ValueError("spec_k must be >= 1 when spec_draft is set")
        if self.spec_branch < 1:
            raise ValueError(
                f"spec_branch must be >= 1, got {self.spec_branch}"
            )
        if self.spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        if self.token_budget < 0 or self.chunk_size < 1:
            raise ValueError(
                "token_budget must be >= 0 and chunk_size >= 1, got "
                f"token_budget={self.token_budget} "
                f"chunk_size={self.chunk_size}"
            )
        if self.token_budget:
            if self.token_budget < self.chunk_size:
                raise ValueError(
                    f"token_budget {self.token_budget} < chunk_size "
                    f"{self.chunk_size}: an iteration could never fit "
                    f"one chunk"
                )
            # mirror decode_kernel.supports(): a kernel-active config
            # with a misaligned chunk width would route every chunk to
            # the dense fallback — reject it here, where the flag
            # surface can still tell the operator which knob to turn
            from flexflow_tpu.ops.pallas.decode_kernel import SUBLANES

            if self.decode_kernel != "dense" and self.chunk_size % SUBLANES:
                raise ValueError(
                    f"chunk_size {self.chunk_size} must be a multiple "
                    f"of {SUBLANES} when decode_kernel is "
                    f"{self.decode_kernel!r}"
                )
        from flexflow_tpu.ops.pallas.decode_kernel import MODES

        if self.decode_kernel not in MODES:
            raise ValueError(
                f"decode_kernel must be one of {MODES}, "
                f"got {self.decode_kernel!r}"
            )
        if self.slo_ttft_ms < 0 or self.slo_itl_ms < 0:
            raise ValueError("SLO thresholds must be >= 0 (0 = disabled)")
        if self.slo_window < 1:
            raise ValueError(
                f"slo_window must be >= 1, got {self.slo_window}"
            )
        if self.serve_hosts < 0:
            raise ValueError(
                f"serve_hosts must be >= 0 (0 = auto), got "
                f"{self.serve_hosts}"
            )
        if self.serve_mesh:
            from flexflow_tpu.serving.distributed import parse_serve_mesh

            parse_serve_mesh(self.serve_mesh)  # raises on malformed text
        if self.kv_swap_bytes < 0:
            raise ValueError(
                f"kv_swap_bytes must be >= 0 (0 = unbounded), got "
                f"{self.kv_swap_bytes}"
            )
        if self.prefix_evict not in ("none", "lru", "cost"):
            raise ValueError(
                f"prefix_evict must be 'none', 'lru', or 'cost', got "
                f"{self.prefix_evict!r}"
            )
        if self.prefix_evict != "none" and not self.prefix_cache:
            raise ValueError(
                "prefix_evict needs prefix_cache=True (only published "
                "prefix pages are ever evictable)"
            )
        if self.adapters < 0:
            raise ValueError(
                f"adapters must be >= 0 (0 = no pool), got {self.adapters}"
            )
        if self.adapters and self.adapter_rank < 1:
            raise ValueError(
                f"adapter_rank must be >= 1, got {self.adapter_rank}"
            )
        if self.classes:
            from flexflow_tpu.serving.tenancy.fairness import parse_classes

            parse_classes(self.classes)  # raises on malformed text
        from flexflow_tpu.serving.journal import FSYNC_MODES

        if self.journal_fsync not in FSYNC_MODES:
            raise ValueError(
                f"journal_fsync must be one of {FSYNC_MODES}, "
                f"got {self.journal_fsync!r}"
            )
        if self.journal_snapshot_every < 0:
            raise ValueError(
                f"journal_snapshot_every must be >= 0 (0 = off), got "
                f"{self.journal_snapshot_every}"
            )
        if self.door_max_pending < 0:
            raise ValueError(
                f"door_max_pending must be >= 0 (0 = unbounded), got "
                f"{self.door_max_pending}"
            )
        if self.breaker_threshold < 0:
            raise ValueError(
                f"breaker_threshold must be >= 0 (0 = breaker off), got "
                f"{self.breaker_threshold}"
            )
        if self.breaker_cooldown < 1:
            raise ValueError(
                f"breaker_cooldown must be >= 1, got "
                f"{self.breaker_cooldown}"
            )

    @property
    def kv_layout(self) -> str:
        # read-only, for benchmarks/families/decoder_lm.py:63 and
        # olmoe.py:62, which refuse to run unless this reads "paged"
        return "paged"

    @property
    def telemetry_requested(self) -> bool:
        """True when any telemetry knob asks for the bundle."""
        return bool(
            self.telemetry
            or self.metrics_out
            or self.metrics_jsonl
            or self.trace
            or self.slo_ttft_ms
            or self.slo_itl_ms
        )

    @staticmethod
    def from_config(cfg) -> "ServeConfig":
        """Lift the serve_* fields FFConfig.parse_args fills."""
        return ServeConfig(
            max_seqs=cfg.serve_max_seqs,
            max_seq_len=cfg.serve_max_seq_len,
            eos_token=(
                cfg.serve_eos_token if cfg.serve_eos_token >= 0 else None
            ),
            seed=cfg.seed,
            kv_page_size=cfg.serve_kv_page_size,
            kv_pages=cfg.serve_kv_pages,
            kv_dtype=cfg.serve_kv_dtype,
            prefix_cache=cfg.serve_prefix_cache,
            spec_draft=cfg.serve_spec_draft,
            spec_k=cfg.serve_spec_k,
            spec_branch=cfg.serve_spec_branch,
            token_budget=cfg.serve_token_budget,
            chunk_size=cfg.serve_chunk_size,
            decode_kernel=cfg.serve_decode_kernel,
            admission=cfg.serve_admission,
            max_preemptions=cfg.serve_max_preemptions,
            serve_async=cfg.serve_async,
            debug_invariants=cfg.serve_check_invariants,
            metrics_out=cfg.serve_metrics_out,
            metrics_jsonl=cfg.serve_metrics_jsonl,
            trace=cfg.serve_trace,
            slo_ttft_ms=cfg.serve_slo_ttft_ms,
            slo_itl_ms=cfg.serve_slo_itl_ms,
            telemetry=cfg.serve_telemetry,
            serve_mesh=cfg.serve_mesh,
            serve_hosts=cfg.serve_hosts,
            kv_swap=cfg.serve_kv_swap,
            kv_swap_bytes=cfg.serve_kv_swap_bytes,
            prefix_evict=cfg.serve_prefix_evict,
            adapters=cfg.serve_adapters,
            adapter_rank=cfg.serve_adapter_rank,
            classes=cfg.serve_classes,
            journal=cfg.serve_journal,
            journal_fsync=cfg.serve_journal_fsync,
            journal_snapshot_every=cfg.serve_journal_snapshot_every,
            door_max_pending=cfg.serve_door_max_pending,
            breaker_threshold=cfg.serve_breaker_threshold,
            breaker_cooldown=cfg.serve_breaker_cooldown,
        )


def build_telemetry(serve: ServeConfig):
    """The Telemetry bundle a ServeConfig asks for, or None when every
    telemetry knob is off — the scheduler/engine then skip every
    instrument point on a single predicate. Thin wrapper over the generic
    telemetry.build_telemetry, which also accepts an FFConfig or plain
    kwargs (the training/search entry points use it directly)."""
    from flexflow_tpu.telemetry import build_telemetry as _build

    return _build(serve)


def build_proposer(serve: ServeConfig, draft_model=None):
    """The DraftProposer a ServeConfig asks for (None when spec decoding
    is off). A "model" draft needs a second compiled decoder LM sharing
    the target's vocabulary."""
    if not serve.spec_draft:
        return None
    from flexflow_tpu.serving.spec import (
        ModelDraftProposer,
        NGramDraftProposer,
    )

    if serve.spec_draft == "ngram":
        return NGramDraftProposer(n=serve.spec_ngram)
    if draft_model is None:
        raise ValueError(
            "spec_draft='model' needs a compiled draft_model "
            "(a small decoder LM with the target's vocabulary)"
        )
    return ModelDraftProposer(
        draft_model,
        max_seqs=serve.max_seqs,
        max_len=serve.max_seq_len,
        buckets=serve.prefill_buckets or None,
        decode_kernel=serve.decode_kernel,
    )


def build_journal(serve: ServeConfig, injector=None, telemetry=None):
    """The RequestJournal a ServeConfig asks for, or None when
    durability is off. `injector` threads the chaos harness's
    journal-write-failure site through every append; `telemetry` keeps
    the `serve_journal_bytes` gauge current."""
    if not serve.journal:
        return None
    from flexflow_tpu.serving.journal import RequestJournal

    registry = None
    if telemetry is not None and getattr(telemetry, "enabled", False):
        registry = telemetry.registry
    return RequestJournal(
        serve.journal,
        fsync=serve.journal_fsync,
        injector=injector,
        registry=registry,
    )


def build_scheduler(
    model,
    serve: ServeConfig,
    draft_model=None,
    injector=None,
    telemetry=None,
    scheduler_cls=None,
    journal=None,
):
    """(scheduler, engine, cache) wired to a compiled model — the pieces
    generate() uses, exposed for callers that drive iterations themselves
    (benchmarks/families/, tests). With serve.spec_draft set, the scheduler
    runs the speculative draft/verify loop (serving/spec.py). `injector`
    threads a faults.FaultInjector through the engine and scheduler
    seams — the chaos harness's entry point. `telemetry` threads a
    flexflow_tpu.telemetry.Telemetry bundle through the same seams
    (built from the serve config's telemetry knobs when omitted); the
    attached bundle is reachable as `scheduler.telemetry`.
    `scheduler_cls` overrides the scheduler class the config would pick
    (the disaggregated front door's prefill tier swaps in its
    chunk-only loop this way); it must subclass a serving scheduler.
    `journal` attaches an already-open RequestJournal (a restart reuses
    the one it recovered from); None builds one from `serve.journal`."""
    if (
        (serve.serve_mesh or serve.serve_hosts)
        and getattr(model, "serving_placement", None) is None
        and hasattr(model, "compile_for_serving")
    ):
        # --serve-mesh / --serve-hosts end-to-end path: apply the serving
        # mesh before the cache is built so from_model picks the
        # placement up (idempotent — an explicit compile_for_serving()
        # call beforehand wins)
        model.compile_for_serving(serve_config=serve)
    cache = PagedKVCache.from_model(
        model,
        max_seqs=serve.max_seqs,
        max_len=serve.max_seq_len,
        buckets=serve.prefill_buckets or None,
        page_size=serve.kv_page_size,
        num_pages=serve.kv_pages,
        kv_dtype=serve.kv_dtype,
        prefix_cache=serve.prefix_cache,
        prefix_evict=serve.prefix_evict,
        swap_bytes_budget=serve.kv_swap_bytes,
        evict_pricer=(
            build_evict_pricer(model)
            if serve.prefix_evict == "cost"
            else None
        ),
    )
    if telemetry is None:
        telemetry = build_telemetry(serve)
    adapters = None
    if serve.adapters:
        from flexflow_tpu.serving.tenancy.adapters import AdapterPool

        adapters = AdapterPool.from_model(
            model,
            max_seqs=serve.max_seqs,
            max_adapters=serve.adapters,
            max_rank=serve.adapter_rank,
        )
    engine = GenerationEngine(
        model,
        cache,
        temperature=serve.temperature,
        seed=serve.seed,
        decode_kernel=serve.decode_kernel,
        injector=injector,
        telemetry=telemetry,
        adapters=adapters,
    )
    proposer = build_proposer(serve, draft_model)
    # what this model's engine refuses (latent attention: engine.require)
    # is refused here, before a request is admitted
    engine.require(
        *(("verify", "verify_tree") if proposer is not None else ()),
        *(("chunk",) if serve.token_budget else ()),
        *(("swap",) if serve.kv_swap else ()),
    )
    classes = None
    if serve.classes:
        from flexflow_tpu.serving.tenancy.fairness import parse_classes

        classes = parse_classes(serve.classes)
    cls = ContinuousBatchingScheduler
    if serve.serve_async:
        cls = AsyncContinuousBatchingScheduler
    if scheduler_cls is not None:
        cls = scheduler_cls
    sched = cls(
        engine,
        proposer=proposer,
        spec_k=serve.spec_k,
        spec_branch=serve.spec_branch,
        admission=serve.admission,
        max_preemptions=serve.max_preemptions,
        injector=injector,
        debug_invariants=serve.debug_invariants,
        telemetry=telemetry,
        token_budget=serve.token_budget,
        chunk_size=serve.chunk_size,
        kv_swap=serve.kv_swap,
        swap_decider=(
            build_swap_decider(model) if serve.kv_swap else None
        ),
        classes=classes,
        victim_pricer=(
            build_victim_pricer(model)
            if classes and len(classes) > 1
            else None
        ),
        journal=(
            journal
            if journal is not None
            else build_journal(serve, injector=injector, telemetry=telemetry)
        ),
        journal_snapshot_every=serve.journal_snapshot_every,
    )
    return sched, engine, cache


def build_victim_pricer(model):
    """A `(cache, request) -> float` callable pricing one preemption
    victim's recompute bill (seconds) for the class-priced victim rule:
    estimate_recompute_step over the victim's resident history, the
    same modeled step time build_swap_decider prices swap against. The
    scheduler multiplies the result by the victim's class weight. Falls
    back to None — resident-token-count pricing — when the model
    carries no compiled graph/cost-model context; a pricing failure at
    pick time falls back the same way (the scheduler catches it)."""
    try:
        from flexflow_tpu.core.machine import MachineSpec
        from flexflow_tpu.search.auto import estimate_recompute_step
        from flexflow_tpu.search.cost_model import CostModel
        from flexflow_tpu.search.machine_model import build_machine_model

        graph = getattr(model, "graph", None)
        cfg = getattr(model, "config", None)
        if graph is None or cfg is None or not graph.nodes:
            return None
        spec = MachineSpec(
            num_nodes=max(1, cfg.num_nodes),
            chips_per_node=1,
            chip=cfg.chip,
        )
        cm = CostModel(spec, machine_model=build_machine_model(cfg, spec))
        placement = getattr(model, "serving_placement", None)
        dp = max(1, int(getattr(placement, "dp", 1)))
        tp = max(1, int(getattr(placement, "tp", 1)))
    except Exception:
        return None

    def price(cache, req) -> float:
        resume_len = len(req.prompt) + len(req.generated)
        cost = estimate_recompute_step(
            graph,
            cm,
            dp,
            tp,
            resume_len,
            page_size=cache.spec.page_size,
            decode_kernel="dense",
        )
        if cost is None:
            # nothing to price against: fall back to the token count
            return float(resume_len)
        return float(cost.step_time)

    return price


def build_swap_decider(model):
    """A `(cache, request) -> bool` callable pricing swap vs recompute
    for one preemption victim: True when staging the victim's pages out
    AND back in (2x swap_bytes_for over the host link,
    CostModel.swap_cost) beats recomputing its committed history at
    re-admission (estimate_recompute_step's modeled step time). Falls
    back to None — always-swap — when the model carries no compiled
    graph/cost-model context to price against; a pricing failure at
    preempt time must never lose the victim, so the scheduler also
    treats a raising decider as a refusal."""
    try:
        from flexflow_tpu.core.machine import MachineSpec
        from flexflow_tpu.search.auto import estimate_recompute_step
        from flexflow_tpu.search.cost_model import CostModel
        from flexflow_tpu.search.machine_model import build_machine_model

        graph = getattr(model, "graph", None)
        cfg = getattr(model, "config", None)
        if graph is None or cfg is None or not graph.nodes:
            return None
        spec = MachineSpec(
            num_nodes=max(1, cfg.num_nodes),
            chips_per_node=1,
            chip=cfg.chip,
        )
        cm = CostModel(spec, machine_model=build_machine_model(cfg, spec))
        placement = getattr(model, "serving_placement", None)
        dp = max(1, int(getattr(placement, "dp", 1)))
        tp = max(1, int(getattr(placement, "tp", 1)))
    except Exception:
        return None

    def decide(cache, req) -> bool:
        resume_len = len(req.prompt) + len(req.generated)
        cost = estimate_recompute_step(
            graph,
            cm,
            dp,
            tp,
            resume_len,
            page_size=cache.spec.page_size,
            decode_kernel="dense",
        )
        if cost is None:
            return True  # nothing to price against: prefer the copy
        swap_s = cm.swap_cost(2 * cache.swap_bytes_for(req.slot))
        return swap_s < cost.step_time

    return decide


def build_restore_decider(model):
    """A `(cache, record, resume_len) -> bool` callable pricing a
    crash-recovery KV restore against the recompute: True when adopting
    the journal's snapshot record over the host link (one
    CostModel.swap_cost copy of the record's staged bytes — the journal
    read itself is off the serving path) beats recomputing `resume_len`
    tokens of committed history (estimate_recompute_step's modeled step
    time). The recovery twin of build_swap_decider: same cost model,
    but the copy is 1x the record bytes (journal -> pool) where a
    preemption swap pays 2x (out AND back in). Falls back to None —
    journal.readmit then always restores an available snapshot — when
    the model carries no compiled graph/cost-model context."""
    try:
        from flexflow_tpu.core.machine import MachineSpec
        from flexflow_tpu.search.auto import estimate_recompute_step
        from flexflow_tpu.search.cost_model import CostModel
        from flexflow_tpu.search.machine_model import build_machine_model

        graph = getattr(model, "graph", None)
        cfg = getattr(model, "config", None)
        if graph is None or cfg is None or not graph.nodes:
            return None
        spec = MachineSpec(
            num_nodes=max(1, cfg.num_nodes),
            chips_per_node=1,
            chip=cfg.chip,
        )
        cm = CostModel(spec, machine_model=build_machine_model(cfg, spec))
        placement = getattr(model, "serving_placement", None)
        dp = max(1, int(getattr(placement, "dp", 1)))
        tp = max(1, int(getattr(placement, "tp", 1)))
    except Exception:
        return None

    def decide(cache, record, resume_len) -> bool:
        cost = estimate_recompute_step(
            graph,
            cm,
            dp,
            tp,
            int(resume_len),
            page_size=cache.spec.page_size,
            decode_kernel="dense",
        )
        if cost is None:
            return True  # nothing to price against: prefer the copy
        restore_s = cm.swap_cost(int(record.get("bytes", 0)))
        return restore_s < cost.step_time

    return decide


def build_evict_pricer(model):
    """A `(cursor, chunk) -> seconds` callable pricing the recompute of
    one published prefix page for the cost-aware eviction policy
    (`prefix_evict="cost"`): the page's tokens re-enter as one chunked-
    prefill step of `chunk` positions appended at cache cursor `cursor`
    (CostModel.prefill_chunk_cost summed over the graph, the same shape
    auto.optimize_token_budget prices), so the allocator can reclaim
    the cheapest-to-recompute page first. Falls back to None — the
    cache then orders by cursor, the same monotone order unpriced —
    when the model carries no compiled graph/cost-model context, same
    posture as build_swap_decider."""
    try:
        from flexflow_tpu.core.machine import MachineSpec
        from flexflow_tpu.core.types import OperatorType
        from flexflow_tpu.search.cost_model import CostModel
        from flexflow_tpu.search.machine_model import build_machine_model

        graph = getattr(model, "graph", None)
        cfg = getattr(model, "config", None)
        if graph is None or cfg is None or not graph.nodes:
            return None
        spec = MachineSpec(
            num_nodes=max(1, cfg.num_nodes),
            chips_per_node=1,
            chip=cfg.chip,
        )
        cm = CostModel(spec, machine_model=build_machine_model(cfg, spec))
        nodes = [
            n
            for n in graph.nodes.values()
            if n.op_type != OperatorType.INPUT and not n.is_parallel_op
        ]
        if not nodes:
            return None
    except Exception:
        return None

    def price(cursor: int, chunk: int) -> float:
        return sum(
            cm.prefill_chunk_cost(n, 1, int(cursor), int(chunk)).forward_time
            for n in nodes
        )

    return price


def generate(
    model,
    prompts: Sequence[Sequence[int]],
    max_new_tokens: int = 16,
    serve: Optional[ServeConfig] = None,
    eos_token: Optional[int] = None,
    draft_model=None,
) -> List[List[int]]:
    """Generate continuations for token-id prompts; returns the generated
    tokens (prompt excluded) in the prompts' order. Greedy by default —
    the cache-equivalence contract (tests/test_serving.py) holds for
    greedy decoding, with or without speculative drafting
    (tests/test_spec_decode.py).

    Per-request fault isolation: an invalid request in the batch (e.g. a
    prompt whose prompt + max_new_tokens exceeds the cache horizon)
    becomes a FAILED entry with an empty continuation instead of an
    exception that loses the whole batch — the serving-surface contract
    (one bad client request must not take down its neighbors)."""
    serve = serve or ServeConfig()
    if eos_token is None:
        eos_token = serve.eos_token
    sched, _, _ = build_scheduler(model, serve, draft_model=draft_model)
    reqs = [
        Request(
            rid=i,
            prompt=list(map(int, p)),
            max_new_tokens=max_new_tokens,
            eos_token=eos_token,
        )
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        sched.submit(r, strict=False)
    done = sched.run()
    by_rid = {r.rid: r for r in done}
    return [by_rid[i].generated for i in range(len(reqs))]
