"""Trace layer: Chrome trace-event JSON, loadable in Perfetto.

The async double-buffered engine's whole value proposition is a timing
shape — dispatch N+1 runs while step N is still on the device — and a
scalar (`overlap_fraction`) can report that shape but never *show* it.
This tracer records spans the way a profiler would and exports the
Chrome trace-event format (`--trace PATH`), so `chrome://tracing` or
https://ui.perfetto.dev renders the pipeline: host lanes carrying the
iteration/dispatch/reconcile spans, device lanes carrying each step's
in-flight window, request lanes carrying the QUEUED→RUNNING→terminal
lifecycle rebuilt from the per-request `events` audit log.

Span discipline: every span on one (pid, tid) lane must properly nest
(contained or disjoint — the renderer draws a stack per lane). The
in-flight windows of consecutive async steps deliberately OVERLAP in
time, so they alternate between two device lanes by step parity —
each lane nests trivially, and the overlap is visible as two staggered
rows, exactly the double-buffer picture. `validate.validate_trace`
enforces the discipline (plus non-negative durations) and the CI smoke
runs it over a real exported trace.

Timestamps are `time.perf_counter()` seconds relative to the tracer's
construction, exported as microseconds (the trace-event unit). All
recording methods are allocation-light appends; the NullTracer twin in
__init__.py makes every call a no-op when tracing is off.

`span` (module level) is THE way a region of the host loop is marked,
in the trainer and in the server: it always enters a
`jax.profiler.TraceAnnotation`, so a profiler session (the benchmark's
`--trace 1`, `utils/profiling.trace`) shows the program's phases on the
device trace's own clock, and, handed a real `Tracer`, it also appends
the Chrome complete event under the same name on the host lane. With
neither a session nor a tracer it costs one TraceMe activity check.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Mapping, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Tracer", "span", "PID_ENGINE", "PID_REQUESTS", "TID_HOST", "TID_DEVICE0",
]

#: process lanes: engine timeline vs per-request lifecycle
PID_ENGINE = 1
PID_REQUESTS = 2

#: thread lanes inside the engine process
TID_HOST = 1  # scheduler host work: iterations, dispatch, reconcile
TID_DEVICE0 = 10  # in-flight device windows, even steps
TID_DEVICE1 = 11  # in-flight device windows, odd steps (overlap lane)
TID_HOST_BASE = 20  # per-host-partition lanes (pod serving), 20 + host
TID_REPLICA_BASE = 200  # per-engine-replica lanes (front door), 200 + idx


class Tracer:
    """Append-only trace-event recorder."""

    def __init__(self, max_events: int = 1_000_000):
        self.t0 = time.perf_counter()
        self.events: List[dict] = []
        self.dropped_events = 0
        self.max_events = int(max_events)
        self._host_lanes: set = set()
        self._meta(PID_ENGINE, None, "process_name", "flexflow_tpu.serve")
        self._meta(PID_ENGINE, TID_HOST, "thread_name", "host scheduler")
        self._meta(PID_ENGINE, TID_DEVICE0, "thread_name", "device in-flight (even)")
        self._meta(PID_ENGINE, TID_DEVICE1, "thread_name", "device in-flight (odd)")
        self._meta(PID_REQUESTS, None, "process_name", "requests")

    # -- low level -----------------------------------------------------------

    def now(self) -> float:
        return time.perf_counter()

    def _us(self, t: float) -> float:
        return round((t - self.t0) * 1e6, 3)

    def _meta(self, pid: int, tid: Optional[int], name: str, value: str):
        ev = {
            "ph": "M",
            "name": name,
            "pid": pid,
            "args": {"name": value},
        }
        if tid is not None:
            ev["tid"] = tid
        self.events.append(ev)

    def _push(self, ev: dict) -> None:
        # bounded like the request audit log: a runaway trace drops
        # (and counts) rather than eating the host
        if len(self.events) >= self.max_events:
            self.dropped_events += 1
            return
        self.events.append(ev)

    def host_lane(self, host: int) -> int:
        """The engine-process lane for one host partition of a pod
        placement (serving/distributed.py). Lanes register their
        thread_name metadata on first use so the Perfetto UI labels
        them; events land via complete(..., tid=host_lane(h))."""
        tid = TID_HOST_BASE + int(host)
        if tid not in self._host_lanes:
            self._host_lanes.add(tid)
            self._meta(
                PID_ENGINE, tid, "thread_name", f"host {int(host)} partition"
            )
        return tid

    def replica_lane(self, replica: int) -> int:
        """The engine-process lane for one front-door engine replica
        (serving/frontend/router.py) — same registration discipline as
        host_lane, offset past the host range so a routed pod placement
        keeps both label families distinct."""
        tid = TID_REPLICA_BASE + int(replica)
        if tid not in self._host_lanes:
            self._host_lanes.add(tid)
            self._meta(
                PID_ENGINE, tid, "thread_name", f"replica {int(replica)}"
            )
        return tid

    # -- recording -----------------------------------------------------------

    def complete(
        self,
        name: str,
        cat: str,
        start_s: float,
        end_s: float,
        pid: int = PID_ENGINE,
        tid: int = TID_HOST,
        args: Optional[Mapping[str, object]] = None,
    ) -> None:
        """One 'X' (complete) event: a span [start_s, end_s] in tracer
        clock seconds. Zero-length spans are legal; negative ones are
        the caller's bug and clamp to zero so a clock hiccup can never
        make the export invalid."""
        dur = max(0.0, end_s - start_s)
        ev = {
            "ph": "X",
            "name": name,
            "cat": cat,
            "ts": self._us(start_s),
            "dur": round(dur * 1e6, 3),
            "pid": pid,
            "tid": tid,
        }
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def instant(
        self,
        name: str,
        cat: str,
        t_s: Optional[float] = None,
        pid: int = PID_ENGINE,
        tid: int = TID_HOST,
        args: Optional[Mapping[str, object]] = None,
    ) -> None:
        ev = {
            "ph": "i",
            "s": "t",  # thread-scoped instant
            "name": name,
            "cat": cat,
            "ts": self._us(self.now() if t_s is None else t_s),
            "pid": pid,
            "tid": tid,
        }
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def span(
        self,
        name: str,
        cat: str = "host",
        pid: int = PID_ENGINE,
        tid: int = TID_HOST,
        args: Optional[Mapping[str, object]] = None,
    ):
        """Context-managed complete event around a host code block."""
        return span(name, self, args, cat=cat, pid=pid, tid=tid)

    def device_window(
        self, kind: str, step_index: int, start_s: float, end_s: float,
        args: Optional[Mapping[str, object]] = None,
    ) -> None:
        """One in-flight window (dispatch → reconcile-complete) on a
        device lane. Consecutive async windows overlap in time by
        design, so they alternate lanes by step parity — each lane
        nests, and the overlap reads as the staggered double-buffer."""
        a = {"step": int(step_index), "kind": kind}
        if args:
            a.update(args)
        self.complete(
            f"inflight:{kind}",
            "device",
            start_s,
            end_s,
            tid=TID_DEVICE0 if step_index % 2 == 0 else TID_DEVICE1,
            args=a,
        )

    # -- request lifecycle ---------------------------------------------------

    def request_lifecycle(self, req) -> None:
        """Rebuild a terminal request's phase spans from its `events`
        audit log (serving/scheduler.Request.log): QUEUED from
        submit→admit, RUNNING from admit→preempt/terminal, one span per
        re-admission after preemption, instants for first_token and
        preempt, and the terminal status on the closing span's args.
        The log is a ring buffer — a truncated front (dropped events)
        starts the rebuild at the first surviving event."""
        if not req.events:
            return
        tid = int(req.rid)
        self._meta(PID_REQUESTS, tid, "thread_name", f"request {req.rid}")
        phase: Optional[str] = None
        phase_t = 0.0
        last_t = 0.0

        def close(end_t: float, status: Optional[str] = None) -> None:
            nonlocal phase
            if phase is None:
                return
            args = {"rid": int(req.rid)}
            if status:
                args["status"] = status
                args["tokens"] = len(req.generated)
            self.complete(phase, "request", phase_t, end_t,
                          pid=PID_REQUESTS, tid=tid, args=args)
            phase = None

        for t, name, detail in list(req.events):
            last_t = t
            if name == "submit":
                phase, phase_t = "QUEUED", t
            elif name == "admit":
                close(t)
                phase, phase_t = "RUNNING", t
            elif name == "preempt":
                self.instant("preempt", "request", t, pid=PID_REQUESTS,
                             tid=tid, args={"rid": int(req.rid)})
                close(t)
                phase, phase_t = "QUEUED", t
            elif name == "first_token":
                self.instant("first_token", "request", t,
                             pid=PID_REQUESTS, tid=tid,
                             args={"rid": int(req.rid)})
            else:
                # terminal statuses close whatever phase is open
                close(t, status=name)
        close(last_t, status=req.status)

    # -- export --------------------------------------------------------------

    def to_json(self) -> Dict[str, object]:
        doc = {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
        }
        if self.dropped_events:
            doc["droppedEvents"] = self.dropped_events
        return doc

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
            f.write("\n")


class span:
    """`with span(name, tracer, args):` marks one region of host code.

    Always a `jax.profiler.TraceAnnotation(name)`: inside a profiler
    session the region is an event of the Python thread's line, on the
    clock of the device's ops; outside one it is a TraceMe activity
    check. When `tracer` is a real `Tracer` (not None, not the
    NullTracer) the region is also one Chrome complete event under the
    same name. `args` is read at exit, so the block may fill it in. A
    child's name is its parent's plus a dotted suffix
    (`scheduler.step.decode.wait`): readers filter by prefix."""

    __slots__ = ("_ann", "_tracer", "_event", "_t0")

    def __init__(
        self,
        name: str,
        tracer: Optional[Tracer] = None,
        args: Optional[Mapping[str, object]] = None,
        cat: str = "host",
        pid: int = PID_ENGINE,
        tid: int = TID_HOST,
    ):
        self._ann = TraceAnnotation(name)
        self._tracer = tracer if isinstance(tracer, Tracer) else None
        self._event = (name, cat, pid, tid, args)

    def __enter__(self):
        if self._tracer is not None:
            self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._tracer is not None:
            name, cat, pid, tid, args = self._event
            self._tracer.complete(
                name, cat, self._t0, time.perf_counter(),
                pid=pid, tid=tid, args=args,
            )
        return False
