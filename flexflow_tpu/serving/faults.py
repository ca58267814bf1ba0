"""Deterministic fault injection for the serving engine.

Production serving dies from the faults nobody scheduled: a kernel
miscompiles on one geometry, a model emits NaN logits for one request,
a burst of long prompts drains the page pool, a client disconnects
mid-stream. The resilience contract (serving/scheduler.py) is that every
such fault retires ONE request — or falls back to a slower path — while
every unaffected request's greedy token stream stays identical to a
fault-free run. A contract like that is only worth having if it is
*proved*, so this module is a chaos harness: a seeded `FaultInjector`
threaded through the engine/scheduler seams that injects

* **corrupted logits** — one slot's logits row becomes NaN after a
  decode/verify/prefill step (the scheduler's per-step finite guard must
  retire exactly that slot as FAILED);
* **kernel failure** — the next Pallas-kernel dispatch raises
  (the engine must fall back to the dense attention paths, permanently,
  and keep serving);
* **page-pool exhaustion** — pages are stolen from the paged cache's
  free pool for a bounded window (under optimistic admission the
  scheduler must preempt-and-recompute; the allocator invariants must
  hold throughout);
* **step latency spikes** — a host-side sleep before an iteration
  (deadlines must fire, goodput accounting must stay honest);
* **mid-flight cancellation** — `scheduler.cancel(rid)` on a running
  request (its slot and pages must free; the stream must stop);
* **swap failure** — a KV swap_out/swap_in attempt refuses (the
  scheduler must degrade to recompute-preemption / recompute
  re-admission — never a lost request);
* **host-partition failure** — a pod host partition goes down for a
  bounded window (the scheduler must drain its requests to survivors
  and re-join it on recovery);
* **engine-replica failure** — a front-door engine replica dies
  mid-stream (the router must evacuate its requests to surviving
  replicas with zero lost streams);
* **process crash** — the whole engine process dies at an iteration
  boundary, before or after the write-ahead journal's commit flush
  (a restart must rebuild the live set from the journal and resume
  every stream token-identically — serving/journal.py);
* **journal write failure** — an append to the write-ahead journal
  refuses (the journal must degrade to undurable, never block or
  kill serving).

Determinism discipline: every decision draws from a fresh
`np.random.default_rng([seed, iteration, site, key])` stream, so the
schedule is a pure function of (seed, plan, workload) and independent of
host call ordering — the property the token-identity proofs in
tests/test_resilience.py are built on.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.serving.kv_cache import PagePoolExhausted

__all__ = [
    "FaultError",
    "KernelFault",
    "DraftFault",
    "ProcessCrash",
    "PagePoolExhausted",
    "FaultPlan",
    "FaultInjector",
]


class FaultError(RuntimeError):
    """Base class for injected faults."""


class KernelFault(FaultError):
    """Injected Pallas-kernel dispatch failure (the engine answers by
    falling back to the dense attention paths)."""


class DraftFault(FaultError):
    """Injected draft-proposer failure (the scheduler answers by
    degrading the iteration to plain decode)."""


class ProcessCrash(FaultError):
    """Injected engine-process death. Deliberately NOT absorbed by the
    scheduler's per-step fault isolation: it propagates out of `step()`
    to the harness, which abandons the scheduler object entirely and
    restarts from the journal — the in-process stand-in for kill -9."""


# deterministic sub-stream ids per injection site
_SITE = {
    "spike": 1,
    "cancel": 2,
    "nan": 3,
    "kernel": 4,
    "draft": 5,
    "swap_fail": 6,
    "host_down": 7,
    "replica_down": 8,
    "crash": 9,
    "journal_fail": 10,
}


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """What to inject. Rates are per-opportunity probabilities drawn
    from the injector's seeded streams; the `*_iters` fields schedule
    faults at EXACT scheduler iterations for targeted tests (both
    compose). All-zero defaults inject nothing."""

    # corrupted (NaN) logits: per-(iteration, slot) probability, plus an
    # explicit {iteration: [slot, ...]} schedule
    nan_rate: float = 0.0
    nan_iters: Mapping[int, Sequence[int]] = dataclasses.field(
        default_factory=dict
    )
    # Pallas-kernel dispatch failure: per-dispatch probability, plus
    # explicit scheduler iterations. Only fires while the engine is on a
    # kernel path — once fallen back to dense there is nothing to fail.
    kernel_rate: float = 0.0
    kernel_iters: Sequence[int] = ()
    # draft-proposer failure (spec mode): per-iteration probability plus
    # explicit iterations; the iteration degrades to plain decode
    draft_rate: float = 0.0
    draft_iters: Sequence[int] = ()
    # host-side latency spike before an iteration
    spike_rate: float = 0.0
    spike_s: float = 0.0
    # mid-flight cancellation: per-(iteration, running rid) probability,
    # plus an explicit {iteration: [rid, ...]} schedule
    cancel_rate: float = 0.0
    cancel_iters: Mapping[int, Sequence[int]] = dataclasses.field(
        default_factory=dict
    )
    # page-pool exhaustion: at each listed iteration, steal up to
    # `steal_pages` pages from the paged cache's free pool and hold them
    # for `steal_hold` iterations before returning them
    steal_iters: Sequence[int] = ()
    steal_pages: int = 0
    steal_hold: int = 2
    # KV swap failure: per-attempt probability that a swap_out (stage to
    # host) or swap_in (restore) refuses — the scheduler must degrade to
    # recompute-preemption / recompute re-admission, never lose the
    # request
    swap_fail_rate: float = 0.0
    swap_fail_iters: Sequence[int] = ()
    # host-partition failure: {iteration: host} marks that host's
    # partition lost at that iteration; it recovers (scheduler.host_up)
    # `host_down_hold` iterations later
    host_down_iters: Mapping[int, int] = dataclasses.field(
        default_factory=dict
    )
    host_down_hold: int = 3
    # engine-replica failure (front-door router): {iteration: replica}
    # marks that replica killed at that router iteration — the router
    # must evacuate its streams to survivors with zero lost requests.
    # Unlike host_down there is no recovery window: a killed replica's
    # process is gone; the chaos leg proves the drain, not the re-join.
    replica_down_iters: Mapping[int, int] = dataclasses.field(
        default_factory=dict
    )
    # process crash: {iteration: phase} kills the engine process at that
    # scheduler iteration. Phase "begin" crashes at the step boundary
    # BEFORE any work (nothing new to lose); phase "commit" crashes at
    # the END of the iteration AFTER tokens were emitted but BEFORE the
    # journal's commit flush — the worst case: a whole verify or
    # tree-verify round's accepted run (a multi-token commit) is
    # host-visible yet unjournaled, and the restart must recompute it
    # token-identically.
    crash_iters: Mapping[int, str] = dataclasses.field(default_factory=dict)
    # journal write failure: at each listed iteration the NEXT journal
    # append refuses (OSError stand-in); the journal must degrade, not
    # raise into the serving path
    journal_fail_iters: Sequence[int] = ()

    def __post_init__(self):
        for name in ("nan_rate", "kernel_rate", "draft_rate", "spike_rate",
                     "cancel_rate", "swap_fail_rate"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {v}")
        if self.spike_s < 0.0 or self.steal_pages < 0 or self.steal_hold < 0:
            raise ValueError("spike_s / steal_pages / steal_hold must be >= 0")
        if self.host_down_hold < 1:
            raise ValueError(
                f"host_down_hold must be >= 1, got {self.host_down_hold}"
            )
        for it, host in self.host_down_iters.items():
            if int(it) < 0 or int(host) < 0:
                raise ValueError(
                    "host_down_iters maps iterations >= 0 to hosts >= 0, "
                    f"got {{{it}: {host}}}"
                )
        for it, rep in self.replica_down_iters.items():
            if int(it) < 0 or int(rep) < 0:
                raise ValueError(
                    "replica_down_iters maps iterations >= 0 to replicas "
                    f">= 0, got {{{it}: {rep}}}"
                )
        for it, phase in self.crash_iters.items():
            if int(it) < 0 or phase not in ("begin", "commit"):
                raise ValueError(
                    "crash_iters maps iterations >= 0 to phase "
                    f"'begin'|'commit', got {{{it}: {phase!r}}}"
                )
        if any(int(it) < 0 for it in self.journal_fail_iters):
            raise ValueError("journal_fail_iters must be iterations >= 0")


class FaultInjector:
    """Seeded, deterministic fault source threaded through the serving
    seams. The scheduler calls `on_iteration` at every step boundary
    (spikes, cancellations, page steal/return), `corrupt_logits` on each
    step's host-side logits, and `maybe_draft_fault` before proposing;
    the engine calls `maybe_kernel_fault` before each kernel-path
    dispatch. `injected` counts every fault that actually fired, keyed
    by site — the ledger the chaos bench publishes."""

    def __init__(self, plan: FaultPlan = None, seed: int = 0):
        self.plan = plan if plan is not None else FaultPlan()
        self.seed = int(seed) & 0x7FFFFFFF
        self.injected: Counter = Counter()
        self._iter = 0
        # pages stolen from a paged cache's free pool: [(page, release_iter)]
        self._stolen: List[Tuple[int, int]] = []
        # host partitions currently marked down: [(host, recover_iter)]
        self._downed: List[Tuple[int, int]] = []

    def _rng(
        self, site: str, key: int = 0, iteration: Optional[int] = None
    ) -> np.random.Generator:
        it = self._iter if iteration is None else int(iteration)
        return np.random.default_rng(
            [self.seed, it, _SITE[site], int(key) & 0x7FFFFFFF]
        )

    @property
    def stolen_pages(self) -> int:
        """Pages currently held outside the cache's free pool — the
        allocator invariant check must count them (check_invariants
        extra_free)."""
        return len(self._stolen)

    # -- scheduler seams -----------------------------------------------------

    def on_iteration(self, iteration: int, scheduler) -> None:
        """Step-boundary faults: latency spike, cancellations, page
        steal/return. Called by the scheduler BEFORE admission so a
        stolen page affects this iteration's gate."""
        self._iter = int(iteration)
        plan = self.plan
        if plan.spike_s > 0.0 and plan.spike_rate > 0.0:
            if self._rng("spike").random() < plan.spike_rate:
                self.injected["spike"] += 1
                time.sleep(plan.spike_s)
        # cancellations: explicit rids first, then rate draws over the
        # running set (sorted for determinism)
        for rid in plan.cancel_iters.get(self._iter, ()):
            if scheduler.cancel(int(rid)):
                self.injected["cancel"] += 1
        if plan.cancel_rate > 0.0:
            rids = sorted(r.rid for r in scheduler.running.values())
            for rid in rids:
                if self._rng("cancel", rid).random() < plan.cancel_rate:
                    if scheduler.cancel(rid):
                        self.injected["cancel"] += 1
        self._page_faults(scheduler.cache)
        self._host_faults(scheduler)

    def _host_faults(self, scheduler) -> None:
        """Recover held-down hosts whose hold window closed, then fire
        this iteration's scheduled host_down. Never downs the last
        alive host — a pod with zero partitions is an outage, not a
        degradation, and the drain contract (every request completes on
        survivors) would be unsatisfiable."""
        plan = self.plan
        cache = scheduler.cache
        if not plan.host_down_iters and not self._downed:
            return
        kept: List[Tuple[int, int]] = []
        for host, recover_iter in self._downed:
            if self._iter >= recover_iter:
                scheduler.host_up(host)
            else:
                kept.append((host, recover_iter))
        self._downed = kept
        host = plan.host_down_iters.get(self._iter)
        if host is None:
            return
        host = int(host)
        num_hosts = cache.num_hosts
        if num_hosts <= 1:
            return
        down = {h for h, _ in self._downed}
        if host in down or host >= num_hosts:
            return
        if len(down) + 1 >= num_hosts:
            return  # never down the last alive host
        scheduler.host_down(host)
        self._downed.append((host, self._iter + plan.host_down_hold))
        self.injected["host_down"] += 1

    def _page_faults(self, cache) -> None:
        """Steal pages at scheduled iterations; return them after the
        hold window. Stolen pages leave the free heap entirely — the
        closest host-side analog to a neighbor tenant (or a leak)
        draining the pool out from under the allocator."""
        import heapq

        plan = self.plan
        kept: List[Tuple[int, int]] = []
        for page, release_iter in self._stolen:
            if self._iter >= release_iter:
                heapq.heappush(cache._free_pages, page)
            else:
                kept.append((page, release_iter))
        self._stolen = kept
        if self._iter in set(plan.steal_iters) and plan.steal_pages > 0:
            for _ in range(min(plan.steal_pages, len(cache._free_pages))):
                page = heapq.heappop(cache._free_pages)
                self._stolen.append((page, self._iter + plan.steal_hold))
                self.injected["page_steal"] += 1

    def release_stolen_pages(self, cache) -> None:
        """Return every held page immediately (end-of-run cleanup)."""
        import heapq

        for page, _ in self._stolen:
            heapq.heappush(cache._free_pages, page)
        self._stolen = []

    def corrupt_logits(
        self, logits: np.ndarray, slots, rows=None, iteration=None
    ) -> List[int]:
        """Overwrite the listed-or-drawn slots' logits rows with NaN in
        place (logits is a host-side array a step returned). The fault
        schedule is keyed by SLOT id; `rows` maps each slot to its row
        index in `logits` when the two differ (prefill returns one row
        per admitted request, decode/verify one row per slot). Returns
        the corrupted slots. The scheduler's finite guard — not this
        method — decides what happens next, exactly as it would for a
        model-produced NaN.

        `iteration` re-keys the schedule for the async engine's
        in-flight window: a step DISPATCHED at iteration i reconciles —
        and has its logits corrupted — an iteration later, so the async
        scheduler passes the step's dispatch iteration and a seeded
        `nan_iters={i: [slot]}` plan lands on the same step it would
        hit under the sync loop."""
        plan = self.plan
        it = self._iter if iteration is None else int(iteration)
        slots = [int(s) for s in slots]
        rows = slots if rows is None else [int(r) for r in rows]
        hit: List[int] = []
        scheduled = set(plan.nan_iters.get(it, ()))
        for slot, row in sorted(zip(slots, rows)):
            if slot in scheduled or (
                plan.nan_rate > 0.0
                and self._rng("nan", slot, iteration=it).random()
                < plan.nan_rate
            ):
                logits[row] = np.nan
                hit.append(slot)
                self.injected["nan"] += 1
        return hit

    def maybe_swap_fail(self, op: str = "swap_out") -> bool:
        """Whether this swap attempt fails. `op` is "swap_out" (staging
        a victim's pages to host) or "swap_in" (restoring them) — the
        two draw from distinct sub-streams so a plan can be replayed
        regardless of how many of each the scheduler attempts. The
        scheduler degrades a failed swap to recompute; this method only
        decides and counts."""
        plan = self.plan
        if plan.swap_fail_rate <= 0.0 and not plan.swap_fail_iters:
            return False
        key = 0 if op == "swap_out" else 1
        if self._iter in set(plan.swap_fail_iters) or (
            plan.swap_fail_rate > 0.0
            and self._rng("swap_fail", key).random() < plan.swap_fail_rate
        ):
            self.injected["swap_fail"] += 1
            return True
        return False

    def maybe_replica_down(self, iteration: int) -> Optional[int]:
        """The replica scheduled to die at this router iteration, or
        None. Consulted by the front-door router at each step boundary;
        the router — not this method — performs the evacuation (it
        alone knows the survivor set), this method only schedules and
        counts. The router is expected to refuse killing the last alive
        replica, same contract as `_host_faults`."""
        rep = self.plan.replica_down_iters.get(int(iteration))
        if rep is None:
            return None
        self.injected["replica_down"] += 1
        return int(rep)

    def maybe_crash(self, phase: str) -> None:
        """Raise ProcessCrash when the plan schedules this iteration's
        `phase` boundary. The scheduler consults it at two seams:
        "begin" right after `on_iteration` (the step dies before doing
        work) and "commit" at the end of `_end_iteration` BEFORE the
        journal's commit flush (the step's emitted tokens die
        unjournaled — a crash mid-tree-verify, whose multi-token commit
        reconciles exactly once per iteration)."""
        if self.plan.crash_iters.get(self._iter) == phase:
            self.injected["crash"] += 1
            raise ProcessCrash(
                f"injected process crash at iteration {self._iter} "
                f"({phase} phase)"
            )

    def maybe_journal_fail(self) -> bool:
        """Whether the next journal append fails. Consulted by
        RequestJournal inside every `_append`; the journal answers a
        True by entering degraded mode (undurable, still serving)."""
        if self._iter in set(self.plan.journal_fail_iters):
            self.injected["journal_fail"] += 1
            return True
        return False

    def maybe_draft_fault(self) -> None:
        plan = self.plan
        if self._iter in set(plan.draft_iters) or (
            plan.draft_rate > 0.0
            and self._rng("draft").random() < plan.draft_rate
        ):
            self.injected["draft"] += 1
            raise DraftFault(f"injected draft fault at iteration {self._iter}")

    # -- engine seam ---------------------------------------------------------

    def maybe_kernel_fault(self, site: str = "decode") -> None:
        """Raise KernelFault when the plan says this dispatch fails. The
        engine only consults this on kernel-path dispatches, so a
        fallen-back (dense) engine never faults again."""
        plan = self.plan
        if self._iter in set(plan.kernel_iters) or (
            plan.kernel_rate > 0.0
            and self._rng("kernel").random() < plan.kernel_rate
        ):
            self.injected["kernel"] += 1
            raise KernelFault(
                f"injected {site} kernel fault at iteration {self._iter}"
            )

    def summary(self) -> Dict[str, int]:
        return dict(self.injected)

    def publish_metrics(self, registry) -> None:
        """Mirror the injected-fault ledger into a
        telemetry.MetricsRegistry as
        `serve_fault_injections_total{site=...}` — a fault the
        observability layer cannot see is a bug, so the chaos bench
        asserts every site that fired here appears in the exported
        metrics with the same count."""
        for site, n in self.injected.items():
            registry.counter(
                "serve_fault_injections_total",
                help="faults the injector actually fired, by site",
                labels={"site": site},
            ).set_monotonic(n)
