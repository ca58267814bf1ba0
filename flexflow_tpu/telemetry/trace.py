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
time, so the steps alternate between two device lanes (a prefill
program has a third) — each lane nests trivially, and the overlap is
visible as two staggered rows, exactly the double-buffer picture. `validate.validate_trace`
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

`StepRecord` / `StepLog` are the engine's record of every step program
it dispatched (`GenerationEngine.step_log`), stamped on the clock of
`Request.events`; they are always on (one object and four stamps a
program). `request_parts` accounts a request's first token and its
token gaps by what it waited for, from that record; `step_logs()` is
the way to the live engines' logs for a reader that holds no engine.
"""

from __future__ import annotations

import json
import time
import weakref
from collections import deque
from typing import Dict, Iterable, List, Mapping, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = [
    "Tracer", "span", "PID_ENGINE", "PID_REQUESTS", "TID_HOST", "TID_DEVICE0",
    "StepRecord", "StepLog", "RequestStamps", "RequestParts", "step_logs",
    "lifecycle_stamps", "request_parts", "PREFILL_KINDS", "GAP_SHARE_BUCKETS",
]

#: process lanes: engine timeline vs per-request lifecycle
PID_ENGINE = 1
PID_REQUESTS = 2

#: thread lanes inside the engine process
TID_HOST = 1  # scheduler host work: iterations, dispatch, reconcile
TID_DEVICE0 = 10  # in-flight device windows, every other step
TID_DEVICE1 = 11  # in-flight device windows, the steps between (overlap lane)
TID_PREFILL = 12  # in-flight windows of an admission's prefill programs
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
        self._step_windows = 0  # step windows drawn: the next one's lane
        self._meta(PID_ENGINE, None, "process_name", "flexflow_tpu.serve")
        self._meta(PID_ENGINE, TID_HOST, "thread_name", "host scheduler")
        self._meta(PID_ENGINE, TID_DEVICE0, "thread_name", "device in-flight (even)")
        self._meta(PID_ENGINE, TID_DEVICE1, "thread_name", "device in-flight (odd)")
        self._meta(PID_ENGINE, TID_PREFILL, "thread_name", "device in-flight (prefill)")
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
        make the export invalid. The end is rounded as a start is, so
        that two spans that share a stamp share it in the export too."""
        ts = self._us(start_s)
        ev = {
            "ph": "X",
            "name": name,
            "cat": cat,
            "ts": ts,
            "dur": max(0.0, round(self._us(end_s) - ts, 3)),
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
        self, kind: str, seq: int, start_s: float, end_s: float,
        args: Optional[Mapping[str, object]] = None,
    ) -> None:
        """One in-flight window of a closed `StepRecord` (the host enters
        the call -> the read returned) on a device lane; `seq` is the
        record's. Consecutive async windows overlap in time by design, so
        the steps alternate between two lanes as they close, which is the
        order they were dispatched in: each lane nests, and the overlap
        reads as the staggered double-buffer. A prefill program has a
        lane of its own: in an admitting iteration the step before it,
        the prefill and the step chained behind it are in flight
        together."""
        a = {"step": int(seq), "kind": kind}
        if args:
            a.update(args)
        if kind == "prefill":
            tid = TID_PREFILL
        else:
            tid = TID_DEVICE1 if self._step_windows % 2 else TID_DEVICE0
            self._step_windows += 1
        self.complete(
            f"inflight:{kind}", "device", start_s, end_s, tid=tid, args=a,
        )

    # -- request lifecycle ---------------------------------------------------

    def request_lifecycle(self, req, parts: "Optional[RequestParts]" = None) -> None:
        """Rebuild a terminal request's phase spans from its `events`
        audit log (serving/scheduler.Request.log): QUEUED from
        submit→admit, RUNNING from admit→preempt/terminal, one span per
        re-admission after preemption, instants for first_token and
        preempt, and the terminal status on the closing span's args.
        The log is a ring buffer — a truncated front (dropped events)
        starts the rebuild at the first surviving event. With `parts`
        (`request_parts` of the same request) the RUNNING span that
        holds the first token carries `ahead`, `inflight` and `emit` as
        sub-spans, and every other request's prefill program that ran
        between its tokens is an `others_prefill` instant."""
        if not req.events:
            return
        tid = int(req.rid)
        self._meta(PID_REQUESTS, tid, "thread_name", f"request {req.rid}")
        rid = {"rid": int(req.rid)}
        for name, t0, t1, status in _phases(req.events, req.status):
            if name in _INSTANTS:
                self.instant(name, "request", t0, pid=PID_REQUESTS,
                             tid=tid, args=rid)
                continue
            args = dict(rid)
            if status:
                args["status"] = status
                args["tokens"] = len(req.generated)
            self.complete(name, "request", t0, t1,
                          pid=PID_REQUESTS, tid=tid, args=args)
        if parts is None:
            return
        if parts.ttft is not None:
            t = parts.admit
            for name in ("ahead", "inflight", "emit"):
                self.complete(name, "request", t, t + parts.ttft[name],
                              pid=PID_REQUESTS, tid=tid, args=rid)
                t += parts.ttft[name]
        for t in parts.others_at:
            self.instant("others_prefill", "request", t, pid=PID_REQUESTS,
                         tid=tid, args=rid)

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


# -- the request's event walk --------------------------------------------------

_INSTANTS = ("preempt", "first_token")


def _phases(events, status: Optional[str] = None):
    """Walk a request's `events` audit log once, for everyone who reads
    it: yields `(name, start, end, status)`, a QUEUED or RUNNING phase
    (status set on the one a terminal event closed) or, with start ==
    end, a `preempt` / `first_token` instant. The phase still open at
    the last event closes there, under `status`."""
    phase: Optional[str] = None
    phase_t = last_t = 0.0
    for t, name, _detail in list(events):
        last_t = t
        if name == "submit":
            phase, phase_t = "QUEUED", t
        elif name == "admit":
            if phase is not None:
                yield phase, phase_t, t, None
            phase, phase_t = "RUNNING", t
        elif name == "preempt":
            yield name, t, t, None
            if phase is not None:
                yield phase, phase_t, t, None
            phase, phase_t = "QUEUED", t
        elif name == "first_token":
            yield name, t, t, None
        elif phase is not None:
            # terminal statuses close whatever phase is open
            yield phase, phase_t, t, name
            phase = None
    if phase is not None:
        yield phase, phase_t, last_t, status


class RequestStamps(NamedTuple):
    """What the step log keeps of a request: four stamps off its
    `events`, on `time.perf_counter()`. `admit` is the admission its
    first token came out of (the last one before it), `terminal` the
    event that closed its last phase; None where it has not got there."""

    rid: int
    submit: Optional[float]
    admit: Optional[float]
    first_token: Optional[float]
    terminal: Optional[float]


def lifecycle_stamps(rid: int, events) -> RequestStamps:
    """A request's four stamps, from the walk that draws its lane."""
    submit = first = terminal = None
    runs = []
    for name, t0, t1, status in _phases(events):
        if name == "first_token":
            first = t0
        elif name == "QUEUED" and submit is None:
            submit = t0
        elif name == "RUNNING":
            runs.append(t0)
        if status is not None:
            terminal = t1
    if first is not None:
        runs = [t for t in runs if t <= first]
    return RequestStamps(
        int(rid), submit, runs[-1] if runs else None, first, terminal
    )


# -- the record of every dispatched step program -------------------------------

#: the kinds whose programs run prompt tokens: a request's own before
#: its first token, other requests' inside its token gaps
PREFILL_KINDS = ("prefill", "chunk")

#: what a log keeps: the newest programs and retired requests of an
#: engine (an hour of 60 ms steps, nine minutes of 2 ms ones)
STEP_LOG_MAX = 1 << 16
REQUEST_LOG_MAX = 1 << 14

#: a program is read back at most this many dispatches out of order
_READ_OUT_OF_ORDER = 4

#: bounds of `serve_token_gap_part_share{part=...}`: a share of a
#: request's first token -> terminal event
GAP_SHARE_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 0.7, 0.9, 1.0)


class StepRecord:
    """One step program the engine dispatched, on `time.perf_counter()`
    (the clock of `Request.events`). The engine fills the stamps where
    the dispatch and the read happen (`_run_step`, `_readback`), the
    scheduler what only it knows (`iteration`, `rids`).

    `seq` counts the engine's dispatches from 1; `kind` is `prefill`,
    `decode`, `verify`, `verify_tree` or `chunk`; `bucket` a prefill's
    `T = bucket(total)`, else None; `t_call` the host enters the jitted
    call, `t_enqueued` the call returned, `t_read` the host starts the
    blocking read of the program's outputs, `t_ready` the read returned
    (both None while the program is in flight); `rows` the live slots of
    a decode or verify step, the real prompt tokens of a prefill or a
    chunk step; `rids` the requests it ran for; `chained` whether
    another program was in flight when it was dispatched."""

    __slots__ = (
        "seq", "kind", "bucket", "iteration", "t_call", "t_enqueued",
        "t_read", "t_ready", "rows", "rids", "chained",
    )

    def __init__(self, kind: str, rows: int = 0, bucket: Optional[int] = None):
        self.seq = -1
        self.kind = kind
        self.bucket = bucket
        self.iteration = -1
        self.t_call = self.t_enqueued = self.t_read = self.t_ready = None
        self.rows = int(rows)
        self.rids: Tuple[int, ...] = ()
        self.chained = False

    def __repr__(self) -> str:
        fields = ", ".join(f"{n}={getattr(self, n)!r}" for n in self.__slots__)
        return f"StepRecord({fields})"


_LOGS: "weakref.WeakSet[StepLog]" = weakref.WeakSet()


def step_logs() -> "List[StepLog]":
    """The step logs of the engines alive in this process, for a reader
    that holds no engine (a replica router's engines are several)."""
    return list(_LOGS)


class StepLog:
    """An engine's record of the programs it dispatched and of the
    requests they ran for: two bounded rings that count what they drop,
    as the `Tracer` counts `dropped_events`. `records` is in the order
    of `t_call`; a program is read back at most a few dispatches out of
    that order (an admission read at once while a step is in flight)."""

    def __init__(
        self, max_records: int = STEP_LOG_MAX,
        max_requests: int = REQUEST_LOG_MAX,
    ):
        self.records: deque = deque(maxlen=int(max_records))
        self.retired: deque = deque(maxlen=int(max_requests))
        self.dispatched = 0  # records ever written: the newest one's seq
        self.open = 0  # of them, dispatched and not read back yet
        self.dropped_records = 0
        self.dropped_requests = 0
        # the scheduler's slot -> Request map, where one drives the engine
        self.running: Mapping[int, object] = {}
        _LOGS.add(self)

    def enqueued(self, rec: StepRecord, t_call: float, t_enqueued: float) -> None:
        """The program behind `rec` is on the device's queue."""
        records = self.records
        rec.t_call, rec.t_enqueued = t_call, t_enqueued
        rec.chained = self.open > 0
        self.open += 1
        self.dispatched += 1
        rec.seq = self.dispatched
        if len(records) == records.maxlen:
            self.dropped_records += 1
        records.append(rec)

    def read(self, records, t_read: float, t_ready: float) -> None:
        """One blocking read, from `t_read` to `t_ready`, brought the
        outputs of the programs behind `records` to the host."""
        for rec in records:
            rec.t_read, rec.t_ready = t_read, t_ready
        self.open -= len(records)

    def retire(self, rid: int, events) -> RequestStamps:
        """Keep a terminal request's four stamps."""
        stamps = lifecycle_stamps(rid, events)
        if len(self.retired) == self.retired.maxlen:
            self.dropped_requests += 1
        self.retired.append(stamps)
        return stamps

    def requests(self) -> List[RequestStamps]:
        """The stamps of the retired requests the log still holds and of
        the running ones (`terminal` None)."""
        return list(self.retired) + [
            lifecycle_stamps(r.rid, r.events)
            for r in list(self.running.values())
        ]


class RequestParts(NamedTuple):
    """`request_parts`' answer, in seconds. `ttft`: `queue`, `ahead`,
    `inflight`, `emit`, which sum to submit -> first token. `gap`:
    `others_prefill`, `decode`, `host`, which sum to first token ->
    terminal event. Either is None where the request has not got there
    or the log no longer holds its programs (or never did: a prefill on
    another engine). `others_at`: when each
    other request's prefill program inside the gaps was called."""

    rid: int
    admit: Optional[float]
    ttft: Optional[Dict[str, float]]
    gap: Optional[Dict[str, float]]
    others_at: Tuple[float, ...]


def _covered(windows: Iterable[Tuple[float, float]], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the union of `windows` covers."""
    total, reach = 0.0, lo
    for a, b in sorted(windows):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def request_parts(stamps: RequestStamps, records) -> RequestParts:
    """Account a request's time by what it waited for, from its stamps
    and the step records of the engine that served it (`records` in
    dispatch order: `StepLog.records` or a list of them). Disjoint
    parts, on one clock, that sum to the whole:

    submit -> first token = `queue` (submit -> admit) + `ahead` (admit
    -> its own first prefill program's `t_enqueued`: the admission's
    host work and the enqueue held behind what the device still owes)
    + `inflight` (-> `t_ready` of its last prefill program: what was
    queued ahead of it on the device, the prefill, the wake-up) + `emit`
    (-> first_token).

    first token -> terminal event = `others_prefill` (covered by the
    `t_call` -> `t_ready` windows of prefill programs it did not belong
    to) + `decode` (of the rest, covered by the windows of the decode
    and verify steps it took part in) + `host` (the rest: commits, the
    door, a queue after a preemption)."""
    rid, submit, admit, first, terminal = stamps
    if admit is None or first is None:
        return RequestParts(rid, admit, None, None, ())
    end = first if terminal is None else terminal
    own, others, steps = [], [], []
    before = 0  # consecutive programs read back before the admission
    for r in reversed(records):
        if r.t_ready is None or r.t_call >= end:
            continue
        if r.t_ready <= admit:
            before += 1
            if before > _READ_OUT_OF_ORDER:
                break  # nothing older reaches in
            continue
        before = 0
        mine = rid in r.rids
        if r.kind in PREFILL_KINDS:
            if not mine:
                others.append((r.t_call, r.t_ready))
            elif r.t_call < first:
                own.append(r)
        elif mine:
            steps.append((r.t_call, r.t_ready))
    else:
        oldest = records[0] if records else None
        if oldest is not None and oldest.seq > 1 and oldest.t_call > admit:
            # the ring dropped programs this request waited for
            return RequestParts(rid, admit, None, None, ())
    ttft = gap = None
    if own and submit is not None:
        newest, oldest = own[0], own[-1]
        ttft = {
            "queue": admit - submit,
            "ahead": oldest.t_enqueued - admit,
            "inflight": newest.t_ready - oldest.t_enqueued,
            "emit": first - newest.t_ready,
        }
    others = [w for w in others if w[1] > first]
    if terminal is not None:
        theirs = _covered(others, first, terminal)
        stepped = _covered(others + steps, first, terminal) - theirs
        gap = {
            "others_prefill": theirs,
            "decode": stepped,
            "host": (terminal - first) - theirs - stepped,
        }
    return RequestParts(
        rid, admit, ttft, gap, tuple(sorted(max(a, first) for a, _ in others)),
    )
