"""SLO monitor: rolling-window latency percentiles + threshold counters.

A serving SLO is a *promise about the recent past* — "p95 TTFT under
200 ms" means over the last N requests, not over the process lifetime
(a quiet hour would launder a bad minute) and not over one request (a
single outlier is not a violation regime). So the monitor keeps fixed-
size rolling windows of TTFT, inter-token latency, and goodput samples,
recomputes percentiles on demand from the live window, and counts
threshold crossings (`--slo-ttft-ms` / `--slo-itl-ms`) into the
registry's `serve_slo_violations_total{slo=...}` counter — the signal
the ROADMAP's token-budget scheduler will price chunk/decode mixes
against.

`percentiles()` here is THE percentile implementation for the serving
stack: `scheduler.latency_percentiles` (the post-hoc per-request view)
routes through it, so the rolling-window p95 and the post-hoc p95 agree
exactly whenever the window still holds every sample
(tests/test_telemetry.py::test_rolling_p95_ttft_agrees_with_post_hoc).
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional, Sequence

import numpy as np

from flexflow_tpu.telemetry.registry import (
    DEFAULT_LATENCY_BUCKETS_MS,
    MetricsRegistry,
)

__all__ = ["percentiles", "RollingWindow", "SLOMonitor"]


def percentiles(
    values: Iterable[float], pcts: Sequence[float]
) -> Dict[float, float]:
    """{pct: value} over `values` (linear interpolation, numpy's
    default). All-zero result for an empty input — the post-hoc and
    rolling paths share this exact function, so they can never
    disagree on math."""
    vals = np.asarray(list(values), dtype=np.float64)
    if vals.size == 0:
        return {p: 0.0 for p in pcts}
    return {p: float(np.percentile(vals, p)) for p in pcts}


class RollingWindow:
    """Last `size` observations in a preallocated ring — `observe` is
    an index write (no allocation, hot-path safe), `values()`
    materializes the window in arrival order for exact percentiles."""

    def __init__(self, size: int = 1024):
        if size < 1:
            raise ValueError(f"window size must be >= 1, got {size}")
        self.size = int(size)
        self._buf = np.zeros(self.size, dtype=np.float64)
        self._n = 0  # total observations ever
        self._i = 0  # next write index

    def __len__(self) -> int:
        return min(self._n, self.size)

    @property
    def total(self) -> int:
        return self._n

    def observe(self, value: float) -> None:
        self._buf[self._i] = value
        self._i = (self._i + 1) % self.size
        self._n += 1

    def values(self) -> np.ndarray:
        """Window contents, oldest first."""
        if self._n < self.size:
            return self._buf[: self._n].copy()
        return np.concatenate([self._buf[self._i :], self._buf[: self._i]])

    def percentiles(self, pcts: Sequence[float]) -> Dict[float, float]:
        return percentiles(self.values(), pcts)


_PCTS = (50, 95, 99)


class SLOMonitor:
    """Rolling TTFT / inter-token-latency / goodput tracking with
    optional violation thresholds. Thresholds are milliseconds; 0
    disables the check (observation still happens, so the percentile
    gauges and histograms fill either way).

    Registry series: histograms `serve_ttft_ms` / `serve_itl_ms`
    (lifetime aggregates), counter
    `serve_slo_violations_total{slo="ttft"|"itl"}`, and gauges
    `serve_slo_{ttft,itl}_p{50,95,99}_ms` + `serve_goodput_tokens_per_s`
    refreshed by `publish()` (the per-iteration sampler calls it, so
    the JSONL time series carries the rolling view).

    `labels` scopes every series the monitor owns — the per-class SLO
    monitors (serving.tenancy.slo) are instances of THIS class with
    `labels={"class": name}`, so the unlabelled series stay the
    fleet-wide aggregate and per-class views ride the same JSONL rows
    as `name{class="gold"}` columns."""

    def __init__(
        self,
        registry: MetricsRegistry,
        ttft_ms: float = 0.0,
        itl_ms: float = 0.0,
        window: int = 1024,
        labels: Optional[Dict[str, str]] = None,
    ):
        if ttft_ms < 0 or itl_ms < 0:
            raise ValueError("SLO thresholds must be >= 0 (0 = disabled)")
        self.registry = registry
        self.labels = dict(labels) if labels else None
        self.ttft_ms = float(ttft_ms)
        self.itl_ms = float(itl_ms)
        self.ttft_window = RollingWindow(window)
        self.itl_window = RollingWindow(window)
        # goodput window: (finish perf_counter time, tokens) of FINISHED
        # requests — rate over the span the window covers
        self._goodput_t = RollingWindow(window)
        self._goodput_tokens = RollingWindow(window)
        self._hist_ttft = registry.histogram(
            "serve_ttft_ms",
            DEFAULT_LATENCY_BUCKETS_MS,
            help="submit-to-first-token latency (finished requests)",
            labels=self.labels,
        )
        self._hist_itl = registry.histogram(
            "serve_itl_ms",
            DEFAULT_LATENCY_BUCKETS_MS,
            help="inter-token latency (gap between consecutive emits)",
            labels=self.labels,
        )
        self._violations = {
            "ttft": registry.counter(
                "serve_slo_violations_total",
                help="observations past the configured SLO threshold",
                labels={**(self.labels or {}), "slo": "ttft"},
            ),
            "itl": registry.counter(
                "serve_slo_violations_total",
                labels={**(self.labels or {}), "slo": "itl"},
            ),
        }
        self._gauges = {
            (kind, p): registry.gauge(
                f"serve_slo_{kind}_p{p}_ms", labels=self.labels
            )
            for kind in ("ttft", "itl")
            for p in _PCTS
        }
        self._goodput_gauge = registry.gauge(
            "serve_goodput_tokens_per_s",
            help="rolling goodput: finished-request tokens per second",
            labels=self.labels,
        )

    # -- observation (hot path: O(1), no allocation) -------------------------

    def observe_ttft(self, seconds: float) -> None:
        ms = seconds * 1e3
        self.ttft_window.observe(ms)
        self._hist_ttft.observe(ms)
        if self.ttft_ms and ms > self.ttft_ms:
            self._violations["ttft"].inc()

    def observe_itl(self, seconds: float) -> None:
        ms = seconds * 1e3
        self.itl_window.observe(ms)
        self._hist_itl.observe(ms)
        if self.itl_ms and ms > self.itl_ms:
            self._violations["itl"].inc()

    def observe_finished(self, finish_t: float, tokens: int) -> None:
        self._goodput_t.observe(finish_t)
        self._goodput_tokens.observe(float(tokens))

    # -- rolling views -------------------------------------------------------

    def goodput_tokens_per_s(self, now: Optional[float] = None) -> float:
        ts = self._goodput_t.values()
        if ts.size == 0:
            return 0.0
        end = float(ts[-1]) if now is None else float(now)
        span = end - float(ts[0])
        if span <= 0.0:
            return 0.0
        return float(self._goodput_tokens.values().sum()) / span

    def violations(self) -> Dict[str, int]:
        return {k: int(c.value) for k, c in self._violations.items()}

    def publish(self, now: Optional[float] = None) -> None:
        """Refresh the rolling-view gauges from the live windows (the
        per-iteration sampler's hook)."""
        for kind, win in (("ttft", self.ttft_window), ("itl", self.itl_window)):
            pct = win.percentiles(_PCTS)
            for p in _PCTS:
                self._gauges[(kind, p)].set(round(pct[p], 6))
        self._goodput_gauge.set(round(self.goodput_tokens_per_s(now), 6))

    def snapshot(self) -> Dict[str, object]:
        """The SLO view as one dict — bench artifacts embed it."""
        return {
            "ttft_ms": {
                f"p{p}": round(v, 3)
                for p, v in self.ttft_window.percentiles(_PCTS).items()
            },
            "itl_ms": {
                f"p{p}": round(v, 3)
                for p, v in self.itl_window.percentiles(_PCTS).items()
            },
            "violations": self.violations(),
            "thresholds_ms": {"ttft": self.ttft_ms, "itl": self.itl_ms},
            "goodput_tokens_per_s": round(self.goodput_tokens_per_s(), 3),
            "window": self.ttft_window.size,
            "ttft_observations": self.ttft_window.total,
            "itl_observations": self.itl_window.total,
        }
