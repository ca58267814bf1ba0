"""flexflow_tpu.telemetry: tracing, metrics, and SLO monitoring.

FlexFlow's core loop is *measure, then decide* — the simulator profiles
real kernels before the search commits to a strategy. This package is
that posture applied to the serving runtime, in three pillars:

* **metrics registry** (`registry`) — counters / gauges / fixed-bucket
  histograms with Prometheus text exposition (`--metrics-out`) and a
  per-iteration JSONL time series (`--metrics-jsonl`). SchedulerStats
  is a façade over this registry, so the exported text IS the stats
  surface the benches and tests already read.
* **trace layer** (`trace`) — Chrome trace-event spans for the request
  lifecycle (QUEUED→RUNNING→terminal, rebuilt from the `events` audit
  log) and the engine phases (prefill, dispatch, reconcile, in-flight
  device windows, preemption, kernel fallback), exported via `--trace`
  and loadable in Perfetto — the async pipeline's one-step-stale
  overlap as a picture, not a scalar.
* **SLO monitor** (`slo`) — rolling-window p50/p95/p99 TTFT,
  inter-token latency, and goodput, with `--slo-ttft-ms` /
  `--slo-itl-ms` thresholds feeding `serve_slo_violations_total` — the
  hook the token-budget scheduler (ROADMAP chunked-prefill item) will
  price against.

The `Telemetry` facade bundles the three and owns the output paths;
`serving.build_scheduler` threads one instance through the engine,
scheduler, cache, and fault injector. Cost discipline: when no
Telemetry is attached the serving hot path takes a single predicate
branch per hook and allocates nothing
(tests/test_telemetry.py::test_disabled_telemetry_is_fully_absent).
"""

from __future__ import annotations

import time
from typing import Optional

from flexflow_tpu.telemetry.registry import (
    DEFAULT_LATENCY_BUCKETS_MS,
    DURABILITY_METRICS,
    Counter,
    Gauge,
    Histogram,
    JsonlWriter,
    MetricsRegistry,
    register_durability_metrics,
    series_name,
)
from flexflow_tpu.telemetry.search_trace import SearchTrace
from flexflow_tpu.telemetry.slo import RollingWindow, SLOMonitor, percentiles
from flexflow_tpu.telemetry.trace import (
    PID_ENGINE,
    PID_REQUESTS,
    TID_DEVICE0,
    TID_HOST,
    StepLog,
    StepRecord,
    Tracer,
    request_parts,
    span,
    step_logs,
)
from flexflow_tpu.telemetry.validate import (
    ValidationError,
    check_schema,
    load_schema,
    validate_durability_metrics,
    validate_metrics_jsonl,
    validate_metrics_jsonl_file,
    validate_metrics_text,
    validate_search_trace,
    validate_search_trace_file,
    validate_trace,
    validate_trace_file,
)

__all__ = [
    "Telemetry",
    "build_telemetry",
    "NullTracer",
    "SearchTrace",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlWriter",
    "series_name",
    "DEFAULT_LATENCY_BUCKETS_MS",
    "DURABILITY_METRICS",
    "register_durability_metrics",
    "validate_durability_metrics",
    "Tracer",
    "span",
    "StepLog",
    "StepRecord",
    "request_parts",
    "step_logs",
    "SLOMonitor",
    "RollingWindow",
    "percentiles",
    "ValidationError",
    "check_schema",
    "load_schema",
    "validate_trace",
    "validate_trace_file",
    "validate_metrics_jsonl",
    "validate_metrics_jsonl_file",
    "validate_metrics_text",
    "validate_search_trace",
    "validate_search_trace_file",
    "PID_ENGINE",
    "PID_REQUESTS",
    "TID_HOST",
    "TID_DEVICE0",
]


class NullTracer:
    """No-op Tracer twin: attached when metrics are wanted but tracing
    is not, so instrument points never branch on 'is tracing on'. Every
    recording method swallows its arguments; export methods are
    errors (there is nothing to export)."""

    events = ()
    enabled = False

    def now(self) -> float:
        return 0.0

    def complete(self, *a, **k) -> None:
        pass

    def instant(self, *a, **k) -> None:
        pass

    def device_window(self, *a, **k) -> None:
        pass

    def request_lifecycle(self, req, parts=None) -> None:
        pass

    def host_lane(self, host: int) -> int:
        return 0

    def replica_lane(self, replica: int) -> int:
        return 0

    def span(self, *a, **k):
        return _NULL_CM

    def save(self, path: str) -> None:
        raise RuntimeError("tracing is disabled — no trace to save")


class _NullCM:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CM = _NullCM()


class Telemetry:
    """The bundle one serving session records into.

    Construction chooses the pillars: metrics always (the registry is
    the cheap part), tracing when `trace` names a path or
    `trace_enabled` forces it in-memory, SLO thresholds when the
    `slo_*_ms` knobs are nonzero (rolling windows fill either way so
    the percentile gauges always mean something). `flush()` writes
    whatever paths were configured and is idempotent — schedulers call
    it at the end of `run()`, external drivers call it themselves.
    """

    enabled = True

    def __init__(
        self,
        metrics_out: str = "",
        metrics_jsonl: str = "",
        trace: str = "",
        trace_enabled: Optional[bool] = None,
        slo_ttft_ms: float = 0.0,
        slo_itl_ms: float = 0.0,
        slo_window: int = 1024,
    ):
        self.t0 = time.perf_counter()
        self.metrics_out = metrics_out
        self.trace_path = trace
        self.registry = MetricsRegistry()
        if trace_enabled is None:
            trace_enabled = bool(trace)
        self.tracer = Tracer() if trace_enabled else NullTracer()
        self.slo = SLOMonitor(
            self.registry,
            ttft_ms=slo_ttft_ms,
            itl_ms=slo_itl_ms,
            window=slo_window,
        )
        self._jsonl = JsonlWriter(metrics_jsonl) if metrics_jsonl else None
        self._flushed = False
        # the per-iteration time series only has a consumer when a
        # JSONL path is configured: without one, `sample()` skips the
        # row build AND the rolling-percentile refresh (np.percentile
        # over the windows) — exposition refreshes them at flush/render
        # instead.
        self.wants_samples = self._jsonl is not None

    @property
    def tracing(self) -> bool:
        return isinstance(self.tracer, Tracer)

    # -- per-iteration sampling ----------------------------------------------

    def sample(self, iteration: int) -> Optional[dict]:
        """Refresh the rolling-view gauges and take one JSONL row
        (streamed to `--metrics-jsonl`). The scheduler calls this at
        every iteration end; with no JSONL consumer it is a cheap
        no-op (see `wants_samples`)."""
        if not self.wants_samples:
            return None
        now = time.perf_counter()
        self.slo.publish(now)
        row = self.registry.sample(
            iteration=int(iteration), t_s=round(now - self.t0, 9)
        )
        self._jsonl.write(row)
        return row

    # -- export --------------------------------------------------------------

    def render_prometheus(self) -> str:
        self.slo.publish()
        return self.registry.render_prometheus()

    def flush(self) -> None:
        """Write every configured output path. Idempotent — later
        flushes overwrite with fresher data, which is what a metrics
        file wants."""
        self.slo.publish()
        if self.metrics_out:
            self.registry.write_prometheus(self.metrics_out)
        if self.trace_path and self.tracing:
            self.tracer.save(self.trace_path)
        if self._jsonl is not None:
            self._jsonl.close()
        self._flushed = True


def _cfg_field(cfg, name, default):
    """Read a telemetry knob off either surface: ServeConfig spells
    them bare (`metrics_out`), FFConfig with the serve_ prefix the CLI
    flags historically filled (`serve_metrics_out` — the SAME
    --metrics-out/--metrics-jsonl/--trace flags now drive training and
    search too)."""
    if hasattr(cfg, name):
        return getattr(cfg, name)
    return getattr(cfg, "serve_" + name, default)


def build_telemetry(config=None, **kwargs) -> Optional[Telemetry]:
    """The Telemetry bundle a config asks for, or None when every knob
    is off (callers then skip every instrument point on one predicate —
    the ≤2%-overhead contract both bench gates hold).

    `config` may be a serving.ServeConfig, an FFConfig, or omitted
    entirely; explicit kwargs (`metrics_out=`, `metrics_jsonl=`,
    `trace=`, `trace_enabled=`, `slo_ttft_ms=`, `slo_itl_ms=`,
    `slo_window=`, `telemetry=True` to force the in-memory bundle)
    override the config's fields. Training and search callers no
    longer fake a serving config to get a registry."""
    fields = {
        "metrics_out": "",
        "metrics_jsonl": "",
        "trace": "",
        "slo_ttft_ms": 0.0,
        "slo_itl_ms": 0.0,
        "slo_window": 1024,
        "telemetry": False,
    }
    if config is not None:
        for name, default in list(fields.items()):
            fields[name] = _cfg_field(config, name, default)
    trace_enabled = kwargs.pop("trace_enabled", None)
    unknown = set(kwargs) - set(fields)
    if unknown:
        raise TypeError(
            f"build_telemetry: unknown knob(s) {sorted(unknown)}"
        )
    fields.update(kwargs)
    force = bool(fields.pop("telemetry"))
    requested = force or any(
        bool(fields[k])
        for k in ("metrics_out", "metrics_jsonl", "trace",
                  "slo_ttft_ms", "slo_itl_ms")
    )
    if not requested:
        return None
    if trace_enabled is None:
        trace_enabled = bool(fields["trace"]) or force or None
    return Telemetry(trace_enabled=trace_enabled, **fields)
