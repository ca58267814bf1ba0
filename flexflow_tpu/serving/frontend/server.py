"""Streaming front-door server (asyncio submit/stream/cancel).

The serving stack below this file is a synchronous iteration loop; a
front door is the piece that turns it into a service: clients submit a
prompt, stream tokens back AS THEY COMMIT, and disconnect (or cancel)
at any moment without disturbing other streams. `FrontDoor` is that
adapter over any backend exposing the scheduler driving surface —
a single scheduler, a `ReplicaRouter`, or a `DisaggregatedPipeline`
(`submit` / `cancel` / `step` / `work_pending` duck type) — so every
lifecycle guarantee the lower layers prove (deadlines, deferred
cancel, terminal statuses, fault isolation) is what the wire sees.

Design rules:

* **One pump, many streams.** A single background task steps the
  backend and fans committed tokens out to per-request queues; client
  coroutines only await their own queue. The engine never runs
  per-client — exactly the continuous-batching posture.
* **An arrival crosses the door between two steps.** `step()` holds
  the event loop for a whole engine iteration, so between two steps
  the pump yields `FrontDoor.PASSES` passes of the loop: enough for a
  request that fell due during the last step to reach `submit` before
  the next one (the derivation is at `PASSES`). What one thread
  cannot remove is the `step()` in progress when the request arrives.
* **Disconnect is cancel.** A client that stops consuming its stream
  (GeneratorExit / connection reset) cancels its request; the
  scheduler's deferred-cancel semantics retire it at the next safe
  boundary and its slot/pages free. No orphaned streams.
* **Terminal truth from the Request.** The stream's `done` event
  carries `Request.status` verbatim (finished / cancelled / timed_out
  / failed) — the audit trail clients see is the one the scheduler
  wrote.

The wire transport (`serve_tcp`) is deliberately minimal: newline-
delimited JSON over asyncio streams — an HTTP-ish request/streaming-
response shape without an HTTP dependency (the container rule: no new
deps). `{"op": "submit", "prompt": [...], ...}` answers
`{"event": "submitted", "rid": n}` then token events; `{"op":
"cancel", "rid": n}` cancels; closing the connection cancels every
stream it opened.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
from typing import AsyncIterator, Dict, List, Optional

from flexflow_tpu.serving.scheduler import (
    Request,
    TERMINAL_STATUSES,
)
from flexflow_tpu.telemetry.trace import span

__all__ = ["StreamEvent", "FrontDoor", "serve_tcp"]


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One stream element: a committed token (`kind="token"`) or the
    terminal record (`kind="done"`, carrying the request's final
    status and error). A `done` with status "shed" is the overload
    refusal — the request never entered the engine (or the journal, so
    a retry is clean) and `retry_after_s` hints when to try again."""

    rid: int
    kind: str  # "token" | "done"
    token: Optional[int] = None
    status: Optional[str] = None
    error: Optional[str] = None
    retry_after_s: Optional[float] = None

    def to_wire(self) -> Dict[str, object]:
        out: Dict[str, object] = {"event": self.kind, "rid": self.rid}
        if self.kind == "token":
            out["token"] = self.token
        else:
            out["status"] = self.status
            if self.error:
                out["error"] = self.error
            if self.retry_after_s is not None:
                out["retry_after_s"] = self.retry_after_s
        return out


class FrontDoor:
    """Async submit/stream/cancel over a scheduler-shaped backend.

    Three durability/overload layers ride on the base adapter:

    * **idempotent resubmission** — a submit carrying a client
      `request_key` already seen (live, finished, or recovered from the
      journal) re-attaches to the EXISTING stream instead of opening a
      second one; a re-attached stream replays from token 0, so a
      reconnecting client sees the full committed history exactly once;
    * **crash-restart recovery** — constructed with a
      `journal.RecoveryState`, the door re-admits the journal's live
      set into the fresh backend with recompute cursors (or
      journal-referenced KV snapshots when `restore_decider` prices
      the copy under the recompute) and registers their streams, so
      deterministic greedy decode resumes every stream
      token-identically;
    * **overload protection** — `max_pending > 0` bounds the
      admission backlog; past it, a class whose pending count exceeds
      its weighted share (`backend.classes` weights; equal shares
      without them) is SHED: an immediate `done(status="shed")` event
      with a `retry_after_s` hint, never submitted to the engine and
      never journaled — so the retry is clean.

    `submits_by_pass` (`serve_door_submits_total{pass}` in the backend's
    telemetry) counts the submissions handed to the backend by WHEN
    they crossed the door: "idle" with no iteration running, else the
    pass of the loop since the last `step()` (1..`PASSES`). Passes 2
    and later are arrivals that a pump yielding once would have held
    for another whole iteration, or several."""

    #: Passes of the event loop the pump yields between two `step()`s.
    #: `step()` blocks the loop for a whole engine iteration, and every
    #: hop of an arrival's chain is appended to the loop's ready list
    #: BEHIND the pump's own resumption, so a hop runs in the pass the
    #: pump ends by yielding once more. The longest chain the door
    #: serves is the open-loop client's: (1) the timer's (for
    #: `serve_tcp`, the socket's) callback fires, (2) the task sleeping
    #: on it wakes and starts a client task, (3) that task calls
    #: `submit`; a task started in pass p is queued behind the pump's
    #: resumption in pass p + 1, so its `submit` needs the pump to yield
    #: a fourth time. Three are not enough, a fifth buys nothing. A fact
    #: about asyncio's ready list, not a tuning value: nothing sets it.
    PASSES = 4

    def __init__(
        self,
        backend,
        next_rid: int = 0,
        max_pending: int = 0,
        recovery=None,
        restore_decider=None,
    ):
        self.backend = backend
        self.max_pending = int(max_pending)
        self._next_rid = int(next_rid)
        self._requests: Dict[int, Request] = {}
        self._queues: Dict[int, asyncio.Queue] = {}
        self._published: Dict[int, int] = {}
        self._done: set = set()  # rids whose terminal event is queued
        self._pump_task: Optional[asyncio.Task] = None
        # idempotency: request_key -> rid for every stream this door
        # (or the journal it recovered from) knows
        self._keys: Dict[str, int] = {}
        self.shed_total: Dict[str, int] = {}
        # pass of the loop since the last step(); 0 = no iteration running
        self._pass = 0
        self.submits_by_pass: Dict[str, int] = {}
        self.recovered_requests = 0
        self.replayed_tokens = 0
        reg = self._registry()
        if reg is not None:
            from flexflow_tpu.telemetry.registry import (
                register_durability_metrics,
            )

            classes = tuple(getattr(backend, "classes", None) or ())
            register_durability_metrics(
                reg,
                classes=classes or ("default",),
                replicas=range(len(getattr(backend, "replicas", ()) or ())),
            )
        if recovery is not None:
            self._adopt(recovery, restore_decider)

    def _registry(self):
        tele = getattr(self.backend, "telemetry", None)
        if tele is not None and getattr(tele, "enabled", False):
            return tele.registry
        return None

    def _count(
        self, tally: Dict[str, int], name: str, help: str, key: str, label: str
    ) -> None:
        """One more under `label`: in the door's own tally (tests, the
        probe) and in the backend's telemetry as `name{key=label}`."""
        tally[label] = tally.get(label, 0) + 1
        reg = self._registry()
        if reg is not None:
            reg.counter(name, help=help, labels={key: label}).inc()

    def _adopt(self, recovery, restore_decider=None) -> None:
        """Rebuild the live set from a journal RecoveryState: re-admit
        every recovered request into the fresh backend (recompute
        cursor, or a priced KV-snapshot restore) and register its
        stream with the published cursor at 0 — the committed run
        replays to the (re)connecting client, and everything past it
        comes from the resumed deterministic decode. Requests whose
        committed run already satisfied their stopping rule come back
        terminal without touching the engine (re-admitting them would
        emit a duplicate token)."""
        from flexflow_tpu.serving.journal import readmit

        resubmitted, completed = readmit(
            self.backend, recovery, decider=restore_decider
        )
        for req in resubmitted + completed:
            self._requests[req.rid] = req
            self._queues[req.rid] = asyncio.Queue()
            self._published[req.rid] = 0
            if req.request_key:
                self._keys[req.request_key] = req.rid
        # terminal verdicts stay dedupable: a retried submit with a
        # finished request's key replays its recorded stream
        for rid, term in recovery.terminals.items():
            key = term.get("key")
            if key and key not in self._keys:
                self._keys[key] = rid
                self._requests[rid] = Request(
                    rid=rid,
                    prompt=[0],
                    generated=list(term.get("tokens", ())),
                    status=term.get("status") or "failed",
                    error=term.get("error"),
                )
        self._next_rid = max(self._next_rid, recovery.next_rid)
        self.recovered_requests = len(resubmitted) + len(completed)
        self.replayed_tokens = recovery.replayed_tokens
        reg = self._registry()
        if reg is not None:
            reg.counter(
                "serve_recovery_total",
                help="journal crash-restart recoveries",
            ).inc()
            reg.counter(
                "serve_replayed_tokens_total",
                help="committed tokens re-adopted from the journal at "
                "recovery",
            ).inc(self.replayed_tokens)
        self._publish()  # recovered-terminal streams publish immediately

    # -- client surface ------------------------------------------------------

    def _pending_live(self) -> List[Request]:
        return [
            r
            for rid, r in self._requests.items()
            if rid not in self._done and r.status not in TERMINAL_STATUSES
        ]

    def _shed_check(self, priority_class: str) -> Optional[float]:
        """None = admit; a retry_after_s hint = shed. Sheds only when
        the TOTAL backlog is at the bound AND the class's own pending
        count is at its weighted share — so under overload a
        high-weight class keeps admitting while low-weight neighbors
        back off (the per-class degradation order, same posture as the
        scheduler's weighted-fair admission)."""
        if not self.max_pending:
            return None
        pending = self._pending_live()
        if len(pending) < self.max_pending:
            return None
        classes = getattr(self.backend, "classes", None)
        if classes and priority_class in classes:
            weights = {
                name: float(getattr(spec, "weight", 1.0))
                for name, spec in classes.items()
            }
            total = sum(weights.values()) or 1.0
            share = max(
                1,
                int(self.max_pending * weights[priority_class] / total),
            )
            mine = sum(
                1 for r in pending if (r.priority_class or "") == priority_class
            )
            if mine < share:
                return None
        excess = len(pending) - self.max_pending + 1
        return round(0.05 * excess, 4)

    async def submit(
        self,
        prompt: List[int],
        max_new_tokens: int = 16,
        eos_token: Optional[int] = None,
        deadline_s: Optional[float] = None,
        request_key: Optional[str] = None,
        priority_class: str = "",
        tenant: str = "",
        adapter_id: int = -1,
    ) -> int:
        """Submit one request; returns its rid (stream with
        `stream(rid)`). A validation rejection surfaces on the stream
        as an immediate failed `done` event, not an exception here —
        the wire protocol has one error path, not two. A duplicate
        `request_key` re-attaches to the existing stream (replayed from
        token 0); an overloaded door sheds with `done(status="shed")`
        instead of admitting."""
        if request_key:
            hit = self._keys.get(request_key)
            if hit is not None:
                req = self._requests.get(hit)
                if req is not None and hit not in self._queues:
                    # the original consumer detached (reconnect): replay
                    # the full committed stream on a fresh queue
                    self._queues[hit] = asyncio.Queue()
                    self._published[hit] = 0
                    self._done.discard(hit)
                    self._publish()
                return hit
        cls = priority_class or ""
        hint = self._shed_check(cls)
        if hint is not None:
            rid = self._next_rid
            self._next_rid += 1
            queue = asyncio.Queue()
            self._queues[rid] = queue
            self._published[rid] = 0
            queue.put_nowait(
                StreamEvent(
                    rid=rid,
                    kind="done",
                    status="shed",
                    error=(
                        f"admission backlog at bound "
                        f"({self.max_pending} pending)"
                    ),
                    retry_after_s=hint,
                )
            )
            self._done.add(rid)
            self._count(
                self.shed_total,
                "serve_shed_total",
                "admissions shed at the front door, by class",
                "class",
                cls or "default",
            )
            return rid
        rid = self._next_rid
        self._next_rid += 1
        req = Request(
            rid=rid,
            prompt=list(prompt),
            max_new_tokens=max_new_tokens,
            eos_token=eos_token,
            deadline_s=deadline_s,
            request_key=request_key,
            priority_class=priority_class,
            tenant=tenant,
            adapter_id=adapter_id,
        )
        self._requests[rid] = req
        self._queues[rid] = asyncio.Queue()
        self._published[rid] = 0
        if request_key:
            self._keys[request_key] = rid
        self._count(
            self.submits_by_pass,
            "serve_door_submits_total",
            "submissions handed to the backend, by the pass of the event "
            "loop since the last step() (idle: no iteration was running)",
            "pass",
            str(self._pass) if self._pass else "idle",
        )
        self.backend.submit(req)
        self._ensure_pump()
        self._publish()  # a rejected submit is terminal already
        return rid

    async def stream(self, rid: int) -> AsyncIterator[StreamEvent]:
        """Yield this request's events until its terminal record. A
        consumer that stops early — client disconnect, GeneratorExit,
        task cancellation — CANCELS the request (deferred-cancel
        semantics below apply); a fully-consumed stream just cleans
        up."""
        queue = self._queues.get(rid)
        if queue is None:
            raise KeyError(f"unknown rid {rid}")
        try:
            while True:
                event = await queue.get()
                yield event
                if event.kind == "done":
                    return
        finally:
            self._detach(rid)

    async def cancel(self, rid: int) -> bool:
        return self.backend.cancel(rid)

    def request(self, rid: int) -> Optional[Request]:
        return self._requests.get(rid)

    async def drain(self) -> None:
        """Run the backend until every submitted stream is terminal
        (test/bench convenience — a live server just lets the pump
        idle)."""
        await self._iterate()

    # -- engine pump ---------------------------------------------------------

    def _ensure_pump(self) -> None:
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = asyncio.ensure_future(self._pump())

    async def _iterate(self) -> None:
        """Engine iterations until the backend is idle: ONE `step()`,
        publish its commits, then `PASSES` passes of the event loop
        before the next. The first pass lets client coroutines drain
        their queues; all of them let a request that fell due while
        `step()` held the loop reach `submit`, so the next iteration
        admits it. Bounded, so a loop that clients keep busy cannot
        starve the engine."""
        while self.backend.work_pending():
            self.backend.step()
            self._publish()
            for n in range(1, self.PASSES + 1):
                self._pass = n
                await asyncio.sleep(0)
            self._pass = 0
        self._publish()

    async def _pump(self) -> None:
        """THE engine driver: iterate (`_iterate`: one `step()`, then
        `PASSES` passes of the loop, not one, so that an arrival's whole
        chain runs between two steps) until idle. Submissions restart
        it; a request still waits for the `step()` in progress when it
        arrives, which one thread cannot remove. A backend exception
        must not strand consumers on silent queues — every live stream
        gets a failed terminal event before the exception propagates
        into the task."""
        try:
            await self._iterate()
        except Exception as exc:
            for rid, queue in list(self._queues.items()):
                if rid not in self._done:
                    queue.put_nowait(
                        StreamEvent(
                            rid=rid,
                            kind="done",
                            status="failed",
                            error=f"engine pump died: {exc!r}",
                        )
                    )
                    self._done.add(rid)
            raise

    def _publish(self) -> None:
        """Fan out every token committed since the last publish, then
        the terminal record. The scheduler appends to
        `Request.generated` as tokens commit; the cursor diff is the
        stream — no scheduler hook needed, and a burst (speculative
        accepts, chunk-final + decode) publishes as individual
        events."""
        tele = getattr(self.backend, "telemetry", None)
        with span("door.pump.publish", getattr(tele, "tracer", None)):
            for rid, queue in list(self._queues.items()):
                if rid in self._done:
                    continue
                req = self._requests[rid]
                cursor = self._published[rid]
                fresh = req.generated[cursor:]
                for token in fresh:
                    queue.put_nowait(
                        StreamEvent(rid=rid, kind="token", token=int(token))
                    )
                self._published[rid] = cursor + len(fresh)
                if req.status in TERMINAL_STATUSES:
                    # the queue stays registered (buffered events included)
                    # until the consumer detaches — a client may open its
                    # stream after a short request already finished
                    queue.put_nowait(
                        StreamEvent(
                            rid=rid,
                            kind="done",
                            status=req.status,
                            error=req.error,
                        )
                    )
                    self._done.add(rid)

    def _detach(self, rid: int) -> None:
        """A consumer left. If the request is still live this is a
        disconnect: cancel it (the backend's deferred-cancel rules
        decide when it actually retires) and stop publishing to the
        dead queue."""
        req = self._requests.get(rid)
        if req is not None and req.status not in TERMINAL_STATUSES:
            self.backend.cancel(rid)
        self._queues.pop(rid, None)
        self._published.pop(rid, None)
        self._done.discard(rid)


# -- wire transport ----------------------------------------------------------


async def _handle_connection(
    door: FrontDoor, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
) -> None:
    """One client connection: newline-delimited JSON ops in, streamed
    events out. Submitted streams are served by concurrent writer
    tasks so several streams interleave on one connection; dropping
    the connection cancels every stream it still owns."""
    owned: List[int] = []
    stream_tasks: List[asyncio.Task] = []
    lock = asyncio.Lock()  # one writer at a time on the shared socket

    async def send(payload: Dict[str, object]) -> None:
        async with lock:
            writer.write(json.dumps(payload).encode() + b"\n")
            await writer.drain()

    async def run_stream(rid: int) -> None:
        async for event in door.stream(rid):
            await send(event.to_wire())

    try:
        while True:
            line = await reader.readline()
            if not line:
                break
            try:
                msg = json.loads(line)
                op = msg.get("op")
            except Exception:
                await send({"event": "error", "error": "bad json"})
                continue
            if op == "submit":
                rid = await door.submit(
                    prompt=list(msg.get("prompt", ())),
                    max_new_tokens=int(msg.get("max_new_tokens", 16)),
                    eos_token=msg.get("eos_token"),
                    deadline_s=msg.get("deadline_s"),
                )
                owned.append(rid)
                await send({"event": "submitted", "rid": rid})
                stream_tasks.append(asyncio.ensure_future(run_stream(rid)))
            elif op == "cancel":
                ok = await door.cancel(int(msg.get("rid", -1)))
                await send(
                    {"event": "cancelled", "rid": msg.get("rid"), "ok": ok}
                )
            else:
                await send({"event": "error", "error": f"unknown op {op!r}"})
    except (ConnectionResetError, asyncio.IncompleteReadError):
        pass
    finally:
        # connection gone: every stream it owns is a disconnect-cancel
        for task in stream_tasks:
            task.cancel()
        for rid in owned:
            req = door.request(rid)
            if req is not None and req.status not in TERMINAL_STATUSES:
                door.backend.cancel(rid)
        writer.close()


async def serve_tcp(
    backend, host: str = "127.0.0.1", port: int = 0
) -> asyncio.AbstractServer:
    """Bind the front door to a TCP port (port 0 picks a free one —
    read it back from `server.sockets[0].getsockname()`). The caller
    owns the returned server's lifetime."""
    door = FrontDoor(backend)

    async def handler(reader, writer):
        await _handle_connection(door, reader, writer)

    return await asyncio.start_server(handler, host, port)
