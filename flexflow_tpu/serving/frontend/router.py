"""Prefix-affinity replica router (disaggregated serving front door).

One engine replica serves thousands of streams; millions of users need
N replicas behind a router. Placement is the whole game: two requests
sharing a prompt prefix served by the SAME replica share its published
prefix pages (one prefill, CoW decode divergence — kv_cache.py), while
the same pair split across replicas prefills twice. So the router
scores each alive replica by **prefix affinity** — how many published
pages its cache would map for this prompt (`match_prefix` over the
chained blake2b page keys, read-only) — and places the request on the
highest-affinity replica, breaking ties by **priced headroom**: the
replica with the most free capacity under its
`estimate_max_in_flight` ceiling (search/auto.py), so a hot prefix
cannot pile every tenant onto one replica past what its page pool
sustains. No-affinity requests degrade to pure least-loaded.

Replicas are in-process engine instances (the same simulated posture
as the pod placement's hosts in serving/distributed.py); each keeps
its own scheduler/cache/telemetry. Router-level telemetry mirrors the
pod's host labels with a `replica` label:

* `serve_router_requests_total{replica}` — placements;
* `serve_router_prefix_hits_total{replica}` — placements won by
  affinity (≥1 page matched);
* `serve_router_replica_down_total{replica}` — chaos kills;
* `serve_router_reroute_total{replica}` — evacuated streams re-placed
  ONTO that replica.

A killed replica (`kill_replica`, or a `FaultPlan.replica_down_iters`
schedule) evacuates every live request (`scheduler.evacuate`) and
re-routes the survivors' streams: RUNNING streams recompute their
committed history on the new replica (the dead pool is gone), queued
ones just requeue — zero lost requests, the generalized host_down
drain contract.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

from flexflow_tpu.serving.scheduler import (
    Request,
    RequestStatus,
    TERMINAL_STATUSES,
)

__all__ = ["EngineReplica", "ReplicaRouter"]


class EngineReplica:
    """One in-process engine replica: scheduler + engine + cache built
    from a compiled model, plus the router's view of it (alive flag,
    priced capacity ceiling, circuit-breaker state)."""

    def __init__(self, idx: int, model, serve, injector=None, journal=None):
        from flexflow_tpu.serving.api import build_scheduler

        self.idx = int(idx)
        self.scheduler, self.engine, self.cache = build_scheduler(
            model, serve, injector=injector, journal=journal
        )
        self.alive = True
        self.capacity = self._priced_capacity(model, serve)
        # circuit breaker (router-owned; see ReplicaRouter._probe):
        # closed -> open after `breaker_threshold` consecutive failed
        # health probes; open -> half_open after `breaker_cooldown`
        # router iterations; half_open -> closed on the first healthy
        # probe (or straight back to open on a failed one)
        self.breaker_state = "closed"
        self.breaker_failures = 0
        self.breaker_open_until = -1
        self._probe_faults = 0

    def _priced_capacity(self, model, serve) -> int:
        """The replica's in-flight ceiling from the capacity model —
        how many concurrent mean-shaped streams its KV bytes sustain —
        floored at 1 and defaulting to the slot count when the model
        carries no compiled graph to price."""
        try:
            from flexflow_tpu.search.auto import estimate_max_in_flight

            graph = getattr(model, "graph", None)
            if graph is None or not graph.nodes:
                return int(serve.max_seqs)
            spec = self.cache.spec
            cache_bytes = int(spec.total_bytes)
            est = estimate_max_in_flight(
                graph,
                cache_bytes,
                mean_prompt_len=max(1, spec.max_len // 2),
                mean_gen_len=max(1, spec.max_len // 4),
                max_len=spec.max_len,
                page_size=getattr(spec, "page_size", 0),
                admission=serve.admission,
                kv_dtype=getattr(spec, "kv_dtype", "fp32"),
            )
            return max(1, min(int(est), int(serve.max_seqs)))
        except Exception:
            return int(serve.max_seqs)

    @property
    def load(self) -> int:
        """Streams this replica currently owes work to."""
        s = self.scheduler
        return len(s.queue) + len(s.running)

    @property
    def headroom(self) -> int:
        return self.capacity - self.load


class ReplicaRouter:
    """Owns N `EngineReplica`s and a placement table. Presents the
    single-scheduler driving surface (`submit`/`cancel`/`step`/`run`/
    `work_pending`) so the front-door server drives a router exactly
    like one engine. `models` is one compiled model per replica —
    built identically (same seed) they are weight-identical, the
    multi-replica analog of the pod's per-host shards."""

    def __init__(
        self,
        models: Sequence,
        serve,
        injector=None,
        telemetry=None,
        journal=None,
        health_probe=None,
    ):
        if not models:
            raise ValueError("ReplicaRouter needs at least one replica")
        if telemetry is None:
            from flexflow_tpu.serving.api import build_telemetry

            telemetry = build_telemetry(serve)
        self.telemetry = telemetry
        if journal is None:
            from flexflow_tpu.serving.api import build_journal

            # ONE shared journal across replicas: the front door's rid
            # space is router-wide, so one durable record stream is the
            # recovery source of truth (per-replica journals would
            # interleave the same rids across files)
            journal = build_journal(serve, injector=injector,
                                    telemetry=telemetry)
        self.journal = journal
        self.replicas = [
            EngineReplica(i, m, serve, injector=injector, journal=journal)
            for i, m in enumerate(models)
        ]
        self.injector = injector
        self._owner: Dict[int, EngineReplica] = {}
        self.requests: Dict[int, Request] = {}
        self._iter = 0
        self.rerouted = 0
        # evacuation window (kill_replica): rid -> cancelled? while the
        # dead replica's requests are between schedulers; a cancel
        # landing here drops the rid from the re-submit batch
        self._evacuating: Dict[int, bool] = {}
        # requests finalized BY THE ROUTER (cancelled mid-evacuation —
        # they belong to no scheduler's `finished` list)
        self._orphans: List[Request] = []
        # per-replica circuit breaker: after `breaker_threshold`
        # consecutive failed health probes a replica stops taking
        # placements for `breaker_cooldown` router iterations, then
        # allows a half-open trial. The default probe is "no NEW
        # scheduler step faults since the last probe"; `health_probe`
        # overrides it with any `(replica) -> bool` (True = healthy).
        self.breaker_threshold = int(getattr(serve, "breaker_threshold", 0))
        self.breaker_cooldown = int(getattr(serve, "breaker_cooldown", 8))
        self.health_probe = health_probe
        self.breaker_opens = 0

    @property
    def classes(self):
        """The priority-class table (replicas are built identically) —
        the front door's shedding reads weights from it."""
        return self.replicas[0].scheduler.classes

    # -- placement -----------------------------------------------------------

    def route(self, request: Request) -> EngineReplica:
        """Pick the placement: max prefix affinity, then max headroom,
        then lowest index (deterministic). Raises RuntimeError with no
        alive replica — the router's analog of a full outage. Replicas
        whose circuit breaker is OPEN are excluded (half-open ones take
        the placement as their trial) — unless every alive replica is
        open, in which case the alive set routes anyway: availability
        over protection, the breaker must never manufacture an
        outage."""
        alive = [r for r in self.replicas if r.alive]
        if not alive:
            raise RuntimeError("no alive replica to route to")
        routable = [r for r in alive if r.breaker_state != "open"]
        alive = routable or alive
        affinity = {
            r.idx: len(r.cache.match_prefix(request.prompt)) for r in alive
        }
        best = max(affinity.values())
        pool = (
            [r for r in alive if affinity[r.idx] == best] if best else alive
        )
        target = max(pool, key=lambda r: (r.headroom, -r.idx))
        if self.telemetry is not None:
            reg = self.telemetry.registry
            labels = {"replica": str(target.idx)}
            reg.counter(
                "serve_router_requests_total",
                help="requests placed, by replica",
                labels=labels,
            ).inc()
            if best:
                reg.counter(
                    "serve_router_prefix_hits_total",
                    help="placements won by prefix affinity",
                    labels=labels,
                ).inc()
        return target

    # -- scheduler-compatible surface ----------------------------------------

    def submit(self, request: Request, strict: bool = True) -> bool:
        target = self.route(request)
        self.requests[request.rid] = request
        if not target.scheduler.submit(request, strict=strict):
            # strict=False validation reject: the request finalized on
            # `target` — record the owner so cancel/lookup see the
            # terminal record instead of an unknown rid
            self._owner[request.rid] = target
            return False
        self._owner[request.rid] = target
        return True

    def cancel(self, rid: int) -> bool:
        if rid in self._evacuating:
            # the rid is mid-evacuation — owned by no scheduler while
            # kill_replica re-places its batch. Mark it: the drain loop
            # drops it from the re-submit batch and finalizes it
            # CANCELLED at the router, so the cancel lands instead of
            # silently missing the ownership gap.
            self._evacuating[rid] = True
            return True
        owner = self._owner.get(rid)
        return owner is not None and owner.scheduler.cancel(rid)

    def request(self, rid: int) -> Optional[Request]:
        return self.requests.get(rid)

    def work_pending(self) -> bool:
        return any(
            r.alive and r.scheduler._work_pending() for r in self.replicas
        )

    def step(self) -> None:
        """One router iteration: fire any scheduled replica kill, then
        step every alive replica that has work (each replica is its own
        engine — in production they step concurrently; interleaving
        in-process preserves every ordering, as no state crosses
        replicas outside `kill_replica`)."""
        self._iter += 1
        if self.injector is not None:
            victim = self.injector.maybe_replica_down(self._iter)
            if victim is not None:
                self.kill_replica(victim)
        for rep in self.replicas:
            if rep.alive and rep.scheduler._work_pending():
                rep.scheduler.step()
        self._probe_breakers()

    def _probe_breakers(self) -> None:
        """One health probe per replica per router iteration, driving
        the breaker state machine. Default probe: a replica is healthy
        when its scheduler logged NO new step faults since the last
        probe — a replica failing whole steps (kernel faults, engine
        exceptions) trips open before it degrades every stream placed
        on it, while per-request faults (a NaN retiring one rid) don't
        count against it."""
        if not self.breaker_threshold:
            return
        for rep in self.replicas:
            if not rep.alive:
                continue
            if self.health_probe is not None:
                healthy = bool(self.health_probe(rep))
            else:
                faults = int(rep.scheduler.stats.step_faults)
                healthy = faults <= rep._probe_faults
                rep._probe_faults = faults
            if rep.breaker_state == "open":
                if self._iter >= rep.breaker_open_until:
                    rep.breaker_state = "half_open"
                continue
            if healthy:
                if rep.breaker_state == "half_open":
                    rep.breaker_state = "closed"
                rep.breaker_failures = 0
                continue
            rep.breaker_failures += 1
            if (
                rep.breaker_state == "half_open"
                or rep.breaker_failures >= self.breaker_threshold
            ):
                rep.breaker_state = "open"
                rep.breaker_open_until = self._iter + self.breaker_cooldown
                rep.breaker_failures = 0
                self.breaker_opens += 1
                if self.telemetry is not None:
                    self.telemetry.registry.counter(
                        "serve_breaker_open_total",
                        help="circuit-breaker open transitions, by replica",
                        labels={"replica": str(rep.idx)},
                    ).inc()

    def run(self, requests=None) -> List[Request]:
        for r in requests or ():
            self.submit(r)
        while self.work_pending():
            self.step()
        return self.finished

    @property
    def finished(self) -> List[Request]:
        done = [
            req for rep in self.replicas for req in rep.scheduler.finished
        ]
        done.extend(self._orphans)
        return sorted(done, key=lambda r: r.finish_time)

    # -- chaos: replica failure ----------------------------------------------

    def _finalize_orphan(self, req: Request, status: str) -> None:
        """Terminal transition for a request the router owns alone
        (cancelled mid-evacuation: no scheduler will ever see it
        again). Mirrors the scheduler's `_finalize` bookkeeping at the
        router grain — the request lands in `finished` with a terminal
        record, never silently vanishes."""
        if req.status in TERMINAL_STATUSES:
            return
        req.status = status
        req.finish_time = time.perf_counter()
        req.log(status, "cancelled during evacuation")
        self._owner.pop(req.rid, None)
        self._orphans.append(req)
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                "serve_requests_total",
                help="terminal request transitions by status",
                labels={"status": status},
            ).inc()

    def kill_replica(self, idx: int) -> List[Request]:
        """A replica dies mid-stream: evacuate every live request and
        re-route each onto survivors, preserving the client's clock
        (submit_time — queue wait on the dead replica still counts
        against TTFT) and the committed stream (RUNNING evacuees
        recompute prompt + generated-so-far on arrival). Refuses to
        kill the last alive replica — zero survivors means the drain
        contract is unsatisfiable, same rule as the host injector."""
        rep = self.replicas[idx]
        alive = [r for r in self.replicas if r.alive]
        if not rep.alive or len(alive) <= 1:
            return []
        t0 = time.perf_counter()
        rep.alive = False
        moved = rep.scheduler.evacuate()
        # evacuation window: between evacuate() and each re-submit the
        # movers belong to NO scheduler — a cancel arriving now (client
        # disconnect racing the kill) must not fall into the ownership
        # gap. cancel() marks the rid here; the loop below drops marked
        # rids from the re-submit batch and finalizes them CANCELLED at
        # the router.
        self._evacuating = {req.rid: False for req in moved}
        for req in moved:
            if self._evacuating.get(req.rid):
                self._finalize_orphan(req, RequestStatus.CANCELLED)
                continue
            submit_time = req.submit_time
            target = self.route(req)
            # strict=False: a validation re-failure must finalize THIS
            # request on the target (per-request FAILED) — a strict
            # submit would raise and abort the drain loop, stranding
            # the rest of the batch ownerless
            if not target.scheduler.submit(req, strict=False):
                self._owner[req.rid] = target
                continue  # validation re-failure finalized it there
            req.submit_time = submit_time
            self._owner[req.rid] = target
            req.log("reroute", f"replica {idx} -> {target.idx}")
            self.rerouted += 1
            if self.telemetry is not None:
                self.telemetry.registry.counter(
                    "serve_router_reroute_total",
                    help="evacuated streams re-placed, by destination",
                    labels={"replica": str(target.idx)},
                ).inc()
        self._evacuating = {}
        if self.telemetry is not None:
            tele = self.telemetry
            tele.registry.counter(
                "serve_router_replica_down_total",
                help="replica kills the router drained",
                labels={"replica": str(idx)},
            ).inc()
            tele.tracer.complete(
                "replica_down drain",
                f"replica{idx}",
                t0,
                time.perf_counter(),
                tid=tele.tracer.replica_lane(idx),
                args={"replica": idx, "rerouted": len(moved)},
            )
        return moved
