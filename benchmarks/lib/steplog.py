"""What the program's own record of its dispatched step programs says of
a cell's window.

The engine writes a `StepRecord` for every program it dispatches (on
`time.perf_counter()`, the clock of the family's `window`) and keeps the
stamps of the requests they ran for; `flexflow_tpu.telemetry.trace.
step_logs()` is the way to the live engines' logs for a reader that, as
these, holds no engine, and `request_parts` accounts a request's first
token and its token gaps by what it waited for. The readers here take
the records called and the requests submitted inside `run.record
["window"]`, from every live log. A program without the record (any
commit before PR 54) gives nothing to read: `window_of` returns None,
the readers return None and the line leaves the metric out, as with a
program without spans (`lib/spans.py`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

from benchmarks.lib import stats

DECODE, PREFILL = "decode", "prefill"


class Window(NamedTuple):
    records: list  # closed StepRecords called inside the window, by t_call
    parts: list  # RequestParts of the requests submitted inside it


def window_of(run) -> Optional[Window]:
    """The window's records and requests, read once a run."""
    if run.record.get("kind") != "serve":
        return None
    if not hasattr(run, "_steplog_window"):
        run._steplog_window = _read(*run.record["window"])
    return run._steplog_window


def _read(lo: float, hi: float) -> Optional[Window]:
    try:
        from flexflow_tpu.telemetry import trace

        logs, request_parts = trace.step_logs(), trace.request_parts
    except (ImportError, AttributeError):
        return None
    records, parts = [], []
    for log in logs:
        whole = list(log.records)
        records += [
            r for r in whole if r.t_ready is not None and lo <= r.t_call < hi
        ]
        parts += [
            request_parts(s, whole) for s in log.requests()
            if s.submit is not None and lo <= s.submit < hi
        ]
    records.sort(key=lambda r: r.t_call)
    return Window(records, parts)


def of_kind(run, kind: str) -> list:
    w = window_of(run)
    return [r for r in w.records if r.kind == kind] if w else []


def mean_ms(seconds: list) -> Optional[float]:
    return 1e3 * sum(seconds) / len(seconds) if seconds else None


def p50(values: list) -> Optional[float]:
    """The median as the end-to-end metrics take it (nearest rank: one
    request's reading, never the mean of two that straddle two modes)."""
    return stats.percentile(values, 50)


def spread_ms(seconds: list) -> dict:
    """A note's median and 90th percentile, in milliseconds."""
    ms = [1e3 * s for s in seconds]
    return {"p50": p50(ms), "p90": stats.percentile(ms, 90, beyond=0)}


def ttft_parts(run) -> list:
    """`queue`, `ahead`, `inflight`, `emit` (seconds) of each window
    request whose own prefill the log holds."""
    w = window_of(run)
    return [p.ttft for p in w.parts if p.ttft is not None] if w else []


def gap_parts(run) -> list:
    """(`others_prefill`, `decode`, `host` in seconds, the other
    requests' prefill programs) of each window request that went on
    past its first token to a terminal event."""
    w = window_of(run)
    return [
        (p.gap, len(p.others_at)) for p in w.parts
        if p.gap is not None and sum(p.gap.values()) > 0.0
    ] if w else []
