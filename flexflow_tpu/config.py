"""Runtime configuration + CLI flag parsing.

Re-design of FFConfig (reference: include/flexflow/config.h:92-165,
FFConfig::parse_args src/runtime/model.cc:3541-3697). The Legion `-ll:*`
resource flags become mesh/topology settings; search and training flags keep
the reference's spellings so the example scripts read the same.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

# reference: config.h:40-53 compile-time bounds
MAX_NUM_INPUTS = 256
MAX_NUM_WEIGHTS = 64
MAX_NUM_OUTPUTS = 256
MAX_NUM_WORKERS = 1024


@dataclasses.dataclass
class FFConfig:
    # training (reference flags -e/-b/--lr/--wd)
    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    iterations: Optional[int] = None
    # -p/--print-freq: metric print cadence in iterations (reference:
    # FFConfig.printFreq, model.cc:3563; 0 = per-epoch only). Printing
    # forces a device sync, so the loop only pays it on schedule.
    print_freq: int = 0
    # -d/--dataset: dataset directory (reference: dataset_path,
    # model.cc:3567); keras_datasets honors it like FF_DATASETS_DIR
    dataset_path: str = ""

    # sparse embedding-table updates (beyond-reference: the reference's
    # embedding bwd scatter-adds into a DENSE weight-grad region,
    # embedding_kernels.cu — here eligible tables skip the dense gradient
    # and per-step full-table optimizer pass entirely; --no-sparse-embedding
    # disables for A/B)
    sparse_embedding_update: bool = True

    # machine (reference: -ll:gpu/-ll:cpu + numNodes)
    num_nodes: int = 1
    workers_per_node: int = 0  # 0 = use all local devices
    # CHIP_SPECS key the cost model prices (--chip). "" = the chip JAX
    # reports (core.machine.detect_chip), filled in by compile() and by
    # every MachineSpec built from it; naming one is the
    # search-without-hardware override
    chip: str = ""

    # search (reference: --budget/--alpha/--import/--export/…)
    search_budget: int = 0
    search_alpha: float = 1.05
    import_strategy_file: str = ""
    export_strategy_file: str = ""
    only_data_parallel: bool = False
    enable_parameter_parallel: bool = False
    enable_attribute_parallel: bool = False
    enable_sample_parallel: bool = False
    enable_inplace_optimizations: bool = False
    base_optimize_threshold: int = 10  # reference: config.h:155
    substitution_json: str = ""
    # the bundled default rewrite set runs at every compile (the reference
    # runs base_optimize as a core graph_optimize phase, not opt-in);
    # --no-substitution turns it off
    enable_substitution: bool = True
    # search-without-hardware overrides (reference: model.cc:3673-3680)
    search_num_nodes: int = -1
    search_num_workers: int = -1
    # which engine a nonzero --budget runs: "mesh" (mesh × rewrite-site
    # search, search.auto), "unity" (per-op-view DP, search.unity — the
    # reference's Unity path, graph.cc:1346), or "mcmc" (simulated
    # annealing, search.mcmc — the reference's legacy path, model.cc:3271)
    search_engine: str = "mesh"
    # machine model for the search's comm costs (reference:
    # --machine-model-version/-file, model.cc:3650+; graph.cc:1566-1581):
    # 0 = simple ring formulas, 1 = Enhanced from file, 2 = Networked torus
    machine_model_version: int = 0
    machine_model_file: str = ""
    # measured-kernel search calibration (reference: the simulator ALWAYS
    # times real kernels, simulator.cc:532-572; here it is opt-in because
    # the analytic roofline keeps search-without-hardware working).
    # calibration_file persists the measured table across runs.
    measure_costs: bool = False
    calibration_file: str = ""
    # search observability (flexflow_tpu.telemetry.search_trace):
    # --search-trace exports every candidate the strategy search
    # considered as schema-validated JSONL (plus a Chrome trace-event
    # timeline of the search phases as <path>.trace.json);
    # --explain prints the explain_strategy() report — why the winning
    # strategy won — after the search (and alongside any exported
    # trace, which `python -m flexflow_tpu.search.explain` re-reads)
    search_trace_file: str = ""
    search_explain: bool = False

    # runtime
    perform_fusion: bool = False  # reference: --fusion
    profiling: bool = False
    seed: int = 0
    # numerics: bf16 matmul operands with f32 accumulation (reference:
    # --allow-tensor-op-math-conversion picks TF32/FP16 tensor cores,
    # model.cc:3668 — off by default there too)
    allow_mixed_precision: bool = False

    # visualization dumps (reference: --compgraph/--taskgraph/--export-strategy)
    computation_graph_file: str = ""
    task_graph_file: str = ""
    include_costs_dot_graph: bool = False

    # per-iteration dynamic config (reference: FFIterationConfig, config.h:160)
    seq_length: Optional[int] = None

    # serving (flexflow_tpu.serving; upstream grew the same flags in
    # FlexFlow Serve's RequestManager): KV-cache slots, cache length per
    # slot, EOS token (-1 = none). ServeConfig.from_config
    # lifts these into the engine.
    serve_max_seqs: int = 8
    serve_max_seq_len: int = 256
    serve_eos_token: int = -1
    # paged KV cache geometry (PagedAttention): page size in tokens
    # (0 = auto) and pool pages (0 = max_seqs * max_seq_len / page size:
    # every slot can reach max_seq_len)
    serve_kv_page_size: int = 0
    serve_kv_pages: int = 0
    # --kv-dtype: K/V pool element type, "fp32" | "int8" (int8 stores
    # fp32 scales per page per head in side pools)
    serve_kv_dtype: str = "fp32"
    # --prefix-cache: hashed prefix-page cache with copy-on-write
    # forking — admissions map content-matching full pages instead of
    # recomputing them (paged layout only)
    serve_prefix_cache: bool = False
    # speculative decoding (SpecInfer; serving/spec.py): draft source
    # ("" = off, "ngram" = weight-free prompt lookup, "model" = second
    # decoder LM passed to build_scheduler) and draft length per verify
    serve_spec_draft: str = ""
    serve_spec_k: int = 4
    # --spec-branch: token-TREE speculation (SpecInfer tree verify) —
    # branching factor per draft level; 1 keeps the linear chain path,
    # > 1 verifies a deduped tree of up to spec_k * spec_branch nodes
    # in one call and accepts the longest surviving root-to-leaf path
    serve_spec_branch: int = 1
    # chunked prefill (Sarathi-style; serving/scheduler.py):
    # --token-budget > 0 caps each iteration's token work and streams
    # prompts in via --chunk-size-aligned chunks interleaved with
    # decodes; 0 keeps the monolithic admission prefill
    serve_token_budget: int = 0
    serve_chunk_size: int = 16
    # decode/verify attention core (ops/pallas/decode_kernel.py):
    # "auto" = Pallas flash-decode kernel on TPU when supported,
    # "pallas" = force it (interpret mode off-TPU), "dense" = jnp paths
    serve_decode_kernel: str = "auto"
    # paged admission policy (serving/scheduler.py): "reserve" =
    # preemption-free worst-case gate, "optimistic" = admit beyond the
    # reserve and preempt-by-recompute on pool exhaustion, up to
    # --max-preemptions per request
    serve_admission: str = "reserve"
    serve_max_preemptions: int = 3
    # the serving loop keeps one decode step in flight (the default):
    # step N+1 is dispatched, its tokens chained on the device, before
    # step N is read back, so terminal events (EOS, cancel() of a running
    # request, a running deadline) land one step late. --serve-async=0
    # (ServeConfig.serve_async=False) asks for the synchronous loop, the
    # token-identical reference; --serve-async alone names the default
    serve_async: bool = True
    # --check-invariants: run cache.check_invariants() every scheduler
    # iteration (the chaos harness's probe) — debugging/CI posture
    serve_check_invariants: bool = False
    # telemetry (flexflow_tpu.telemetry): --metrics-out writes
    # Prometheus text exposition at the end of a serve OR fit run,
    # --metrics-jsonl streams one sample row per scheduler/training
    # iteration, --trace writes a Chrome trace-event JSON
    # (Perfetto-loadable), --slo-ttft-ms / --slo-itl-ms set
    # rolling-window SLO thresholds (milliseconds; 0 = observe but
    # never count violations), and --serve-telemetry force-enables the
    # in-memory bundle without any output path. The same knobs drive
    # FFModel.fit's training telemetry (train_* series) — the fields
    # keep their historical serve_ prefix
    serve_metrics_out: str = ""
    serve_metrics_jsonl: str = ""
    serve_trace: str = ""
    serve_slo_ttft_ms: float = 0.0
    serve_slo_itl_ms: float = 0.0
    serve_telemetry: bool = False
    # pod-scale serving (serving/distributed.py): --serve-mesh "dp,tp"
    # applies that (data, model) serving mesh at compile_for_serving
    # ("" = search one when compile_for_serving runs; serving without
    # compile_for_serving keeps inheriting the training sharding),
    # --serve-hosts partitions slots/pages across N host views (0 =
    # process count on pods, else the data-axis degree; >1 on the slot
    # KV layout is rejected), --serve-export-strategy writes the
    # applied placement doc (fxlint strategy-validate input)
    serve_mesh: str = ""
    serve_hosts: int = 0
    serve_export_strategy: str = ""
    # graceful degradation under pressure (serving/kv_cache.py +
    # scheduler.py): --kv-swap stages preemption victims' pages to host
    # buffers and restores them at re-admission (no re-prefill),
    # --kv-swap-bytes caps the host bytes held at once (0 = unbounded),
    # --prefix-evict "lru" lets publication-only prefix pages be
    # reclaimed under pool pressure before any live request is
    # preempted ("none" retains them forever)
    serve_kv_swap: bool = False
    serve_kv_swap_bytes: int = 0
    serve_prefix_evict: str = "none"
    # multi-tenant serving (serving/tenancy/): --adapters provisions a
    # paged pool of that many LoRA adapter ids (--adapter-rank rows
    # each); --classes "gold:4:200:20,bronze:1" declares priority
    # classes as name:weight[:ttft_ms[:itl_ms]] and turns the token
    # planner/admission into weighted-fair deficit round-robin
    serve_adapters: int = 0
    serve_adapter_rank: int = 8
    serve_classes: str = ""
    # durable serving (serving/journal.py): --journal attaches an
    # append-only write-ahead request journal at that path (submit/
    # commit/terminal records at the host-sync grain — a crash-restart
    # rebuilds token-identical streams from it); --journal-fsync picks
    # the durability point (commit|batch|off); --journal-snapshot-every
    # N journals a KV snapshot of every running slot each N iterations
    # (paged layout), priced at recovery against recompute.
    # --door-max-pending bounds the front door's admission backlog
    # (past it, per-class weighted-share shedding refuses with a
    # retry-after hint); --breaker-threshold / --breaker-cooldown
    # configure the per-replica circuit breaker.
    serve_journal: str = ""
    serve_journal_fsync: str = "batch"
    serve_journal_snapshot_every: int = 0
    serve_door_max_pending: int = 0
    serve_breaker_threshold: int = 0
    serve_breaker_cooldown: int = 8

    @property
    def num_devices(self) -> int:
        import jax

        if self.workers_per_node <= 0:
            return len(jax.devices()) * max(1, self.num_nodes) // max(1, self.num_nodes)
        return self.num_nodes * self.workers_per_node

    def total_workers(self) -> int:
        if self.workers_per_node > 0:
            return self.num_nodes * self.workers_per_node
        import jax

        return len(jax.devices())

    def get_current_time(self) -> float:
        """reference: FFConfig.get_current_time (flexflow_cffi.py) —
        microseconds; scripts compute 1e-6*(end-start) for seconds."""
        import time

        return time.perf_counter() * 1e6

    @staticmethod
    def parse_args(argv: Optional[Sequence[str]] = None) -> "FFConfig":
        """Parse the reference's CLI spellings (model.cc:3541-3697)."""
        import sys

        cfg = FFConfig()
        args = list(sys.argv[1:] if argv is None else argv)
        i = 0

        def take():
            nonlocal i
            i += 1
            return args[i]

        while i < len(args):
            a = args[i]
            if a in ("-e", "--epochs"):
                cfg.epochs = int(take())
            elif a in ("-b", "--batch-size"):
                cfg.batch_size = int(take())
            elif a == "--lr" or a == "--learning-rate":
                cfg.learning_rate = float(take())
            elif a == "--wd" or a == "--weight-decay":
                cfg.weight_decay = float(take())
            elif a in ("-i", "--iterations"):
                cfg.iterations = int(take())
            elif a in ("-p", "--print-freq"):
                cfg.print_freq = int(take())
            elif a in ("-d", "--dataset"):
                cfg.dataset_path = take()
            elif a == "--budget" or a == "--search-budget":
                cfg.search_budget = int(take())
            elif a == "--alpha" or a == "--search-alpha":
                cfg.search_alpha = float(take())
            elif a == "--import" or a == "--import-strategy":
                cfg.import_strategy_file = take()
            elif a == "--export" or a == "--export-strategy":
                cfg.export_strategy_file = take()
            elif a == "--only-data-parallel":
                cfg.only_data_parallel = True
            elif a == "--enable-parameter-parallel":
                cfg.enable_parameter_parallel = True
            elif a == "--enable-attribute-parallel":
                cfg.enable_attribute_parallel = True
            elif a == "--enable-sample-parallel":
                cfg.enable_sample_parallel = True
            elif a == "--base-optimize-threshold":
                cfg.base_optimize_threshold = int(take())
            elif a == "--substitution-json":
                cfg.substitution_json = take()
            elif a == "--no-substitution":
                cfg.enable_substitution = False
            elif a == "--no-sparse-embedding":
                cfg.sparse_embedding_update = False
            elif a == "--search-num-nodes":
                cfg.search_num_nodes = int(take())
            elif a == "--search-num-workers":
                cfg.search_num_workers = int(take())
            elif a == "--search-engine":
                cfg.search_engine = take()
            elif a == "--machine-model-version":
                cfg.machine_model_version = int(take())
            elif a == "--machine-model-file":
                cfg.machine_model_file = take()
            elif a == "--measure-costs":
                cfg.measure_costs = True
            elif a == "--calibration-file":
                cfg.calibration_file = take()
            elif a == "--search-trace":
                cfg.search_trace_file = take()
            elif a == "--explain":
                cfg.search_explain = True
            elif a == "--fusion":
                cfg.perform_fusion = True
            elif a == "--allow-tensor-op-math-conversion":
                cfg.allow_mixed_precision = True
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--seed":
                cfg.seed = int(take())
            elif a == "--compgraph":
                cfg.computation_graph_file = take()
            elif a == "--include-costs-dot-graph":
                cfg.include_costs_dot_graph = True
            elif a == "--taskgraph":
                cfg.task_graph_file = take()
            elif a == "--nodes":
                cfg.num_nodes = int(take())
            elif a == "-ll:gpu" or a == "-ll:tpu" or a == "--workers-per-node":
                cfg.workers_per_node = int(take())
            elif a == "--chip":
                cfg.chip = take()
            elif a == "--max-seqs":
                cfg.serve_max_seqs = int(take())
            elif a == "--max-seq-len":
                cfg.serve_max_seq_len = int(take())
            elif a == "--kv-page-size":
                cfg.serve_kv_page_size = int(take())
            elif a == "--kv-pages":
                cfg.serve_kv_pages = int(take())
            elif a == "--kv-dtype":
                cfg.serve_kv_dtype = take()
            elif a == "--prefix-cache":
                cfg.serve_prefix_cache = True
            elif a == "--eos-token":
                cfg.serve_eos_token = int(take())
            elif a == "--spec-draft":
                cfg.serve_spec_draft = take()
            elif a == "--spec-k":
                cfg.serve_spec_k = int(take())
            elif a == "--spec-branch":
                cfg.serve_spec_branch = int(take())
            elif a == "--token-budget":
                cfg.serve_token_budget = int(take())
            elif a == "--chunk-size":
                cfg.serve_chunk_size = int(take())
            elif a == "--decode-kernel":
                cfg.serve_decode_kernel = take()
            elif a == "--admission":
                cfg.serve_admission = take()
            elif a == "--max-preemptions":
                cfg.serve_max_preemptions = int(take())
            elif a == "--serve-async" or a.startswith("--serve-async="):
                cfg.serve_async = a.partition("=")[2].lower() not in (
                    "0", "false", "off", "no",
                )
            elif a == "--check-invariants":
                cfg.serve_check_invariants = True
            elif a == "--metrics-out":
                cfg.serve_metrics_out = take()
            elif a == "--metrics-jsonl":
                cfg.serve_metrics_jsonl = take()
            elif a == "--trace":
                cfg.serve_trace = take()
            elif a == "--slo-ttft-ms":
                cfg.serve_slo_ttft_ms = float(take())
            elif a == "--slo-itl-ms":
                cfg.serve_slo_itl_ms = float(take())
            elif a == "--serve-telemetry":
                cfg.serve_telemetry = True
            elif a == "--serve-mesh":
                cfg.serve_mesh = take()
            elif a == "--serve-hosts":
                cfg.serve_hosts = int(take())
            elif a == "--serve-export-strategy":
                cfg.serve_export_strategy = take()
            elif a == "--kv-swap":
                cfg.serve_kv_swap = True
            elif a == "--kv-swap-bytes":
                cfg.serve_kv_swap_bytes = int(take())
            elif a == "--prefix-evict":
                cfg.serve_prefix_evict = take()
            elif a == "--adapters":
                cfg.serve_adapters = int(take())
            elif a == "--adapter-rank":
                cfg.serve_adapter_rank = int(take())
            elif a == "--classes":
                cfg.serve_classes = take()
            elif a == "--journal":
                cfg.serve_journal = take()
            elif a == "--journal-fsync":
                cfg.serve_journal_fsync = take()
            elif a == "--journal-snapshot-every":
                cfg.serve_journal_snapshot_every = int(take())
            elif a == "--door-max-pending":
                cfg.serve_door_max_pending = int(take())
            elif a == "--breaker-threshold":
                cfg.serve_breaker_threshold = int(take())
            elif a == "--breaker-cooldown":
                cfg.serve_breaker_cooldown = int(take())
            # silently accept remaining legion-style flags with one value
            elif a.startswith("-ll:") or a.startswith("-lg:"):
                take()
            i += 1
        return cfg
