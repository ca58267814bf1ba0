"""Iteration-level request scheduling (Orca, OSDI'22) with per-request
fault isolation and preemption-by-recompute (vLLM / PagedAttention,
SOSP'23).

The unit of scheduling is one model *iteration*, not one request: every
iteration the scheduler (a) admits queued requests into free KV-cache
slots — strictly FIFO, so admission is starvation-free by construction —
running one prefill batch for the newcomers, then (b) runs one decode
step over ALL in-flight slots. A request leaving (EOS or max-new-tokens)
frees its slot at that same iteration boundary, so the next iteration's
admission can refill it. That is the continuous-batching loop; the
throughput win over request-level ("static") batching comes from never
holding finished requests' slots hostage to the longest request in a
batch.

Speculative decoding (SpecInfer, ASPLOS'24; serving/spec.py) is a mode
of the same loop: when a scheduler carries a `DraftProposer`, step (b)
becomes draft → one batched verify call → accept/rollback, emitting
1..spec_k+1 tokens per slot per iteration instead of exactly one. The
iteration-level frame is unchanged — a verify is just a wider decode —
so admission, retirement, and slot recycling all work as before.

**Request lifecycle.** Every request ends in exactly one terminal
status: FINISHED (EOS / token budget), FAILED (bad input, non-finite
logits, an engine fault, or too many preemptions — the error is captured
on the request), CANCELLED (`scheduler.cancel(rid)`), or TIMED_OUT
(`Request.deadline_s` elapsed, whether queued or running). PREEMPTED is
the one transient status: an optimistic-admission victim whose pages
were reclaimed goes back to the queue head and re-enters RUNNING via
prefill-from-recompute. The resilience contract — proved by
tests/test_resilience.py under a seeded FaultInjector — is that a fault
retires only the requests it touches: every other slot's greedy token
stream is identical to a fault-free run, because greedy decode is a pure
function of a slot's own context, never of which neighbors share the
iteration.

**Admission policies**: the default `reserve` policy
admits only when the free pool covers a request's worst case on top of
every in-flight reservation — preemption-free by construction. The
opt-in `optimistic` policy admits on the pages a request needs NOW;
when the pool later runs dry mid-decode (PagePoolExhausted from
`ensure_position`), the scheduler preempts the youngest-by-admission
victims — frees their pages and requeues them at the queue head for
prefill-from-recompute over prompt + tokens generated so far — up to
`max_preemptions` times per request before hard FAILED. Recompute (not
swap) is the right recovery here for the same reason vLLM defaults to
it: a preempted sequence's KV is recomputable from its token history in
one prefill-shaped step, so no swap-space subsystem is needed.

**Async double-buffered loop** (`AsyncContinuousBatchingScheduler`,
`--serve-async`): every decode/verify is split into a dispatch phase
(live-state reads, snapshot taken, step enqueued) and a reconcile
phase (device outputs committed against the snapshot) run one
iteration apart, so host scheduling overlaps device execution. The
synchronous schedulers run the same two phases back-to-back — ONE
implementation, proved token-identical across both timings.

**Chunked prefill** (`--token-budget`, Sarathi-Serve-style): with a
token budget set, admission claims a slot but runs NO monolithic
prefill — the prompt streams into the cache in `--chunk-size`-aligned
chunks over the following iterations, interleaved with the in-flight
decode/verify work, so no single iteration processes more than
~token_budget tokens and a long prompt can no longer head-of-line
block every in-flight decode. Chunk grants are fair-share round-robin
over the prefill-pending slots (FIFO-ordered passes of one chunk
each), so short prompts finish their prefill in one iteration even
while a long prompt is mid-stream. A chunked request starts decoding
only after its LAST chunk lands (that chunk's sampled token is the
first generated token — exactly the monolithic prefill's tail), and
under the async loop chunk progress commits only at reconcile, from
the `InflightStep.chunks` cursor snapshot (fxlint FX105).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.serving.engine import KernelCompileError
from flexflow_tpu.serving.kv_cache import PagePoolExhausted
from flexflow_tpu.telemetry import MetricsRegistry
from flexflow_tpu.telemetry.slo import percentiles as _percentiles
from flexflow_tpu.telemetry.trace import (
    GAP_SHARE_BUCKETS,
    request_parts,
    span,
)


class RequestStatus:
    """String constants (json-friendly) for the request lifecycle."""

    QUEUED = "queued"
    RUNNING = "running"
    PREEMPTED = "preempted"  # transient: requeued for recompute
    FINISHED = "finished"
    FAILED = "failed"
    CANCELLED = "cancelled"
    TIMED_OUT = "timed_out"


#: statuses a request never leaves
TERMINAL_STATUSES = frozenset(
    {
        RequestStatus.FINISHED,
        RequestStatus.FAILED,
        RequestStatus.CANCELLED,
        RequestStatus.TIMED_OUT,
    }
)

_ADMISSION_MODES = ("reserve", "optimistic")


@dataclasses.dataclass
class Request:
    """One generation request. `generated` accumulates post-prompt tokens
    (the first comes from the admission prefill itself). `deadline_s` is
    a wall-clock budget from submit — queued or running, the request is
    TIMED_OUT once it elapses. `events` is the per-request audit log:
    (wall time, event, detail) for submit/admit/first_token/preempt/
    terminal transitions — a RING buffer bounded by `events_max`, so a
    long-running request cannot grow it without bound: past the cap the
    OLDEST entry drops and `events_dropped` counts it (surfaced as the
    `serve_request_events_dropped_total` telemetry counter)."""

    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_token: Optional[int] = None
    deadline_s: Optional[float] = None
    events_max: int = 64
    # multi-tenant serving: free-form tenant tag (telemetry only),
    # priority class name ("" = the first configured class), and the
    # LoRA adapter serving this request (-1 = the base model)
    tenant: str = ""
    priority_class: str = ""
    adapter_id: int = -1
    # durable serving: the CLIENT's idempotency key — a retried submit
    # carrying the same key dedups against the journal/front-door
    # instead of opening a second stream (None = no dedup)
    request_key: Optional[str] = None

    generated: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    status: str = RequestStatus.QUEUED
    error: Optional[str] = None
    preemptions: int = 0
    submit_iter: int = -1
    admit_iter: int = -1
    finish_iter: int = -1
    submit_time: float = 0.0
    first_token_time: float = 0.0
    finish_time: float = 0.0
    events: List[Tuple[float, str, str]] = dataclasses.field(
        default_factory=list
    )
    events_dropped: int = 0
    # inter-token-latency stamp (telemetry only): wall time of the last
    # emitted token — 0.0 until telemetry observes the first one
    last_token_time: float = 0.0
    # chunked prefill (token_budget > 0): the sequence being prefilled
    # (prompt + recompute tokens, fixed at admission), the dispatch
    # cursor (tokens handed to a chunk step, possibly still in flight)
    # and the committed cursor (tokens whose chunk reconciled).
    # prefill_pos < len(prefill_seq) means the request is still
    # prefilling — it neither decodes nor drafts until its last chunk
    # lands. Reconcile-phase code reads cursor state from the
    # InflightStep.chunks snapshot, never these live attrs (FX105).
    prefill_seq: List[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0
    prefill_dispatched: int = 0
    # KV swap-to-host (kv_swap=True): handle of this request's staged
    # pages while it waits PREEMPTED->QUEUED for re-admission — swap_in
    # restores them (no re-prefill); None everywhere else
    swap_handle: Optional[int] = None

    def log(self, event: str, detail: str = "") -> None:
        if len(self.events) >= max(1, self.events_max):
            del self.events[0]
            self.events_dropped += 1
        self.events.append((time.perf_counter(), event, detail))

    @property
    def finished(self) -> bool:
        """Terminal in ANY status — the request will never run again."""
        return self.status in TERMINAL_STATUSES

    @property
    def ok(self) -> bool:
        """Terminal AND successful — the only requests whose latency
        numbers mean anything."""
        return self.status == RequestStatus.FINISHED

    @property
    def latency_s(self) -> float:
        return self.finish_time - self.submit_time

    @property
    def ttft_s(self) -> float:
        """Submit → first generated token (the prefill-side latency a
        user perceives before streaming starts). Meaningless (0.0) for
        a request that never produced a token."""
        if not self.generated:
            return 0.0
        return self.first_token_time - self.submit_time

    @property
    def decode_s_per_token(self) -> float:
        """Mean seconds per generated token AFTER the first — the
        decode-side latency speculative decoding compresses (several
        accepted tokens share one verify step's wall time)."""
        if len(self.generated) <= 1:
            return 0.0
        return (self.finish_time - self.first_token_time) / (
            len(self.generated) - 1
        )

    def deadline_exceeded(self, now: float) -> bool:
        return (
            self.deadline_s is not None
            and now - self.submit_time > self.deadline_s
        )

    def _done_after(self, token: int) -> bool:
        return (
            self.eos_token is not None and token == self.eos_token
        ) or len(self.generated) >= self.max_new_tokens


#: SchedulerStats fields, name -> default. Each is backed by a
#: `serve_stats_<name>` gauge in a telemetry.MetricsRegistry — comments
#: that used to annotate the dataclass fields live here.
_STAT_FIELDS: Dict[str, object] = dict(
    iterations=0,
    decode_steps=0,
    # prefill PROGRAMS dispatched by admissions: one a packed row, so more
    # than one for an admission whose prompts overflow the largest bucket,
    # plus the one chunk step of its prefix-shared prompts
    prefill_batches=0,
    tokens_generated=0,
    slot_steps=0,  # Σ over decode/verify iterations of max_seqs
    busy_slot_steps=0,  # Σ of actually-active slots
    peak_in_flight=0,  # max concurrent running requests observed
    elapsed_s=0.0,
    # speculative decoding (verify iterations only)
    verify_steps=0,
    draft_tokens_proposed=0,
    draft_tokens_accepted=0,
    # token-tree speculation (spec_branch > 1): under trees,
    # draft_tokens_proposed counts the tree DEPTH (the most tokens one
    # verify could accept), so acceptance_rate keeps its meaning — the
    # full node count lives here instead
    tree_verify_steps=0,  # verify steps that scored a draft tree
    tree_nodes_proposed=0,  # Σ tree nodes dispatched for verification
    # chunked prefill (token_budget > 0)
    chunk_steps=0,  # chunk steps dispatched
    chunk_tokens=0,  # Σ prompt tokens streamed in via chunks
    budget_deferrals=0,  # prefill-pending slots granted no tokens
    budget_used=0,  # tokens the LAST iteration charged to its budget
    host_syncs=0,  # step RECONCILES, all kinds: one per step, however
    # many device values it reads (those are device_syncs, below)

    # request lifecycle (filled at terminal transitions)
    submitted_requests=0,
    finished_requests=0,  # FINISHED only — not failures
    failed_requests=0,
    cancelled_requests=0,
    timed_out_requests=0,
    preemptions=0,  # preempt-and-requeue events
    step_faults=0,  # whole-step engine faults (all slots retired)
    draft_faults=0,  # proposer faults degraded to plain decode
    tokens_finished=0,  # Σ generated over FINISHED requests only
    # per-request latency accumulators (FINISHED requests only — a
    # request failing before its first token has no TTFT to aggregate).
    # TTFT and decode latency are stamped at COMMIT (when _emit actually
    # hands the token over), never at dispatch: under the async loop a
    # token's step is enqueued an iteration before its value exists, and
    # dispatch-time stamps would fake latencies exactly as deep as the
    # pipeline.
    ttft_sum_s=0.0,
    decode_latency_sum_s=0.0,  # Σ of per-request decode_s_per_token
    # dispatch/commit split (async double-buffered engine; the sync loop
    # fills them too — its overlap window is just ~empty)
    dispatch_count=0,  # decode/verify steps enqueued
    dispatch_gap_sum_s=0.0,  # Σ wall time between consecutive dispatches
    commit_wait_s=0.0,  # Σ time blocked on device outputs at reconcile
    overlapped_host_s=0.0,  # Σ host work done while a step was in flight
    # how far the overlapped loop engaged: decode steps dispatched while
    # another was still in flight (over decode_steps: the share of steps
    # the device did not wait for the host; 0 under the synchronous loop),
    # and the slot-steps whose token the commit's identity check threw
    # away (a request that ends on EOS costs one; a budgeted end none:
    # the dispatch's budget gate sees it coming)
    decode_steps_chained=0,
    decode_slot_steps_discarded=0,
    # speculative pre-proposals drafted during the in-flight window
    # (async spec mode): used as-is vs rolled back on reconcile mismatch
    pre_proposal_hits=0,
    pre_proposal_misses=0,
    # live jitted verify programs in the engine's LRU (sampled at the
    # end of each iteration — bounded by engine.verify_cache_max)
    verify_cache_entries=0,
    # kernel-failure dense fallbacks (mirrored from the engine's ledger
    # at each iteration end)
    kernel_fallbacks=0,
    # counted by the engine at the boundary where the work happens
    # (mirrored at each iteration end)
    device_syncs=0,  # blocking reads of a device value (wait, readbacks)
    readback_bytes=0,  # bytes those reads brought to the host
    prefill_tokens_real=0,  # prompt tokens run by monolithic prefills
    prefill_tokens_padded=0,  # the bucket(total) tokens they were packed into
    prefill_programs=0,  # packed prefill programs dispatched (engine.prefill)
    # step programs, all kinds, by what became of the KV pools they were
    # handed: consumed by the call (donated: rows written in place), or
    # still alive after it (the backend declined the donation and copied)
    pool_steps_donated=0,
    pool_steps_copied=0,
    # expert layers (ops/moe.py sparse_moe), summed over layers: the token x
    # choice rows computed and the distinct experts with at least one row
    moe_rows_prefill=0,
    moe_rows_decode=0,
    moe_experts_touched_prefill=0,
    moe_experts_touched_decode=0,
    # of a layer that holds a share of its experts: the rows routed to
    # experts it does not hold, which it leaves out
    moe_rows_absent_prefill=0,
    moe_rows_absent_decode=0,
    # the prefill and decode programs dispatched whose expert layers took
    # ops/pallas/grouped_matmul.py (healthy on a TPU at lane-tile widths:
    # every prefill program and every decode step)
    moe_kernel_programs_prefill=0,
    moe_kernel_programs_decode=0,
    # latent attention: the live latent rows the decode steps attended,
    # summed over slots and layers
    mla_rows_read_decode=0,
    # recurrent layers (per-slot state): the (live slot, layer) rows the
    # decode steps advanced, and the (request, layer) rows the prefills
    # wrote from the zero state
    state_rows_decode=0,
    state_resets_prefill=0,
    # the decode steps dispatched whose recurrent layers took
    # ops/pallas/kda_step.py, and the prefill programs whose recurrent
    # layers took ops/pallas/kda_scan.py (healthy on a TPU at lane-tile
    # heads: every one)
    kda_kernel_programs_decode=0,
    kda_kernel_programs_prefill=0,
    # prefix-sharing page cache (--prefix-cache;
    # mirrored from the allocator's ledgers at each iteration end)
    prefix_hits=0,  # admissions that mapped at least one shared page
    prefix_pages_shared=0,  # live shared table entries (gauge-like)
    cow_copies=0,  # copy-on-write page forks
    # graceful degradation under pressure (kv_swap / prefix_evict;
    # mirrored from the allocator's ledgers at each iteration end)
    swap_outs=0,  # victims whose pages rode the host link out
    swap_ins=0,  # swap-restored re-admissions (no re-prefill)
    swap_bytes=0,  # Σ bytes staged across the host link, both ways
    swapped_pages=0,  # pages currently parked in host buffers (gauge)
    prefix_evictions=0,  # publication-only prefix pages reclaimed
    host_downs=0,  # host partitions drained after a failure
    # per-request audit-log ring-buffer drops, summed at finalize
    events_dropped=0,
)

#: derived SchedulerStats properties `publish_derived` exports as
#: gauges so the JSONL time series and text exposition carry them
_STAT_DERIVED = (
    "tokens_per_s",
    "goodput_tokens_per_s",
    "terminal_requests",
    "occupancy",
    "acceptance_rate",
    "mean_dispatch_gap_s",
    "overlap_fraction",
    "mean_ttft_s",
    "mean_decode_s_per_token",
    "host_syncs_per_token",
)


class _StatField:
    """Descriptor backing one SchedulerStats field with its registry
    gauge: reads and writes go straight to the gauge's value, so
    `stats.tokens_generated += 1` and the exported
    `serve_stats_tokens_generated` series can never disagree."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return obj._metrics[self.name].value

    def __set__(self, obj, value):
        obj._metrics[self.name].value = value


class SchedulerStats:
    """Scheduler counters/aggregates — a façade over a
    telemetry.MetricsRegistry. Every field is a `serve_stats_<name>`
    gauge; with telemetry attached the scheduler passes the shared
    registry, so `--metrics-out` exposition and the JSONL time series
    read the SAME storage the tests and benches read through this
    class. Without telemetry each instance owns a private registry —
    the field surface and update syntax are unchanged from the old
    dataclass, and the cost per update is one dict lookup plus an
    attribute write."""

    __slots__ = ("_registry", "_metrics", "_derived")

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._registry = registry if registry is not None else MetricsRegistry()
        self._metrics = {}
        for name, default in _STAT_FIELDS.items():
            gauge = self._registry.gauge("serve_stats_" + name)
            # a fresh stats object owns its series: re-zero so a reused
            # registry (new scheduler, same Telemetry) starts clean
            gauge.value = default
            self._metrics[name] = gauge
        # derived-property gauge handles, resolved once — the
        # per-iteration publish is then pure attribute writes
        self._derived = {
            name: self._registry.gauge("serve_stats_" + name)
            for name in _STAT_DERIVED
        }
        for gauge in self._derived.values():
            gauge.value = 0.0

    def publish_derived(self) -> None:
        """Refresh the derived-property gauges
        (`serve_stats_<property>`) — the per-iteration sampler's hook,
        so ratios like occupancy and overlap_fraction ride the time
        series without consumers re-deriving them."""
        for name, gauge in self._derived.items():
            gauge.value = round(float(getattr(self, name)), 9)

    def as_dict(self) -> Dict[str, object]:
        """Fields + derived properties as one plain dict (bench
        artifacts embed it)."""
        out: Dict[str, object] = {
            name: self._metrics[name].value for name in _STAT_FIELDS
        }
        for name in _STAT_DERIVED:
            out[name] = float(getattr(self, name))
        return out

    def __repr__(self) -> str:
        inner = ", ".join(
            f"{name}={self._metrics[name].value!r}" for name in _STAT_FIELDS
        )
        return f"SchedulerStats({inner})"

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_generated / self.elapsed_s if self.elapsed_s else 0.0

    @property
    def goodput_tokens_per_s(self) -> float:
        """Tokens of successfully FINISHED requests per second — the
        number a resilient scheduler maximizes under faults. Tokens
        generated for requests that later failed, timed out, or were
        cancelled are work, not goodput."""
        return self.tokens_finished / self.elapsed_s if self.elapsed_s else 0.0

    @property
    def terminal_requests(self) -> int:
        return (
            self.finished_requests
            + self.failed_requests
            + self.cancelled_requests
            + self.timed_out_requests
        )

    @property
    def occupancy(self) -> float:
        """Fraction of decode/verify slot-steps that carried a live
        request — the metric continuous batching exists to push toward
        1.0."""
        return self.busy_slot_steps / self.slot_steps if self.slot_steps else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify step accepted — the
        measured α that optimize_spec_k turns into a draft length."""
        if not self.draft_tokens_proposed:
            return 0.0
        return self.draft_tokens_accepted / self.draft_tokens_proposed

    @property
    def mean_dispatch_gap_s(self) -> float:
        """Mean wall time between consecutive step dispatches — the
        host-side critical path per iteration. Under the async loop
        this is what bounds throughput (the device works through the
        gap); under the sync loop it includes the device wait."""
        if self.dispatch_count <= 1:
            return 0.0
        return self.dispatch_gap_sum_s / (self.dispatch_count - 1)

    @property
    def overlap_fraction(self) -> float:
        """Of the wall time between a step's dispatch and the end of
        its reconcile, the fraction the host spent doing useful work
        (admission, page claims, drafting, the next dispatch) instead
        of blocked on device outputs — the number the double-buffered
        loop exists to push toward 1.0. The sync reference loop
        reconciles immediately after dispatching, so it sits at ~0."""
        window = self.overlapped_host_s + self.commit_wait_s
        if window <= 0.0:
            return 0.0
        return self.overlapped_host_s / window

    @property
    def mean_ttft_s(self) -> float:
        if not self.finished_requests:
            return 0.0
        return self.ttft_sum_s / self.finished_requests

    @property
    def mean_decode_s_per_token(self) -> float:
        if not self.finished_requests:
            return 0.0
        return self.decode_latency_sum_s / self.finished_requests

    @property
    def host_syncs_per_token(self) -> float:
        """Host round-trips (step reconciles) per committed token: plain
        decode sits at ~1.0, a verify step that accepts drafts commits
        several tokens behind one."""
        if not self.tokens_generated:
            return 0.0
        return self.host_syncs / self.tokens_generated


for _name in _STAT_FIELDS:
    setattr(SchedulerStats, _name, _StatField(_name))
del _name


class _Admission(NamedTuple):
    """An admission between its prefill's dispatch and its read-back."""

    admitted: List[Request]
    seqs: List[List[int]]  # what each prefill recomputes: prompt + generated
    cursors: List[int]  # prefix-shared extent of each (0: a plain prefill)
    pending: Optional[list]  # `engine.prefill_dispatch`'s, of the plain ones
    programs: int  # engine.prefill_programs before the dispatch
    slots: frozenset  # no decode step until the first token is the host's


class _SchedulerBase:
    """Shared admission/decode/verify machinery. `proposer` switches the
    per-iteration generation step from plain decode to speculative
    draft/verify (serving/spec.py). `admission` picks the paged cache's
    policy ("reserve" = preemption-free worst-case gate, "optimistic" =
    admit-now/preempt-later, bounded by `max_preemptions` per request).
    `injector` threads a faults.FaultInjector through the step
    boundaries; the isolation machinery below runs either way — the
    injector only makes faults happen on schedule."""

    def __init__(
        self,
        engine,
        params=None,
        proposer=None,
        spec_k: int = 4,
        spec_branch: int = 1,
        admission: str = "reserve",
        max_preemptions: int = 3,
        injector=None,
        debug_invariants: bool = False,
        telemetry=None,
        token_budget: int = 0,
        chunk_size: int = 16,
        kv_swap: bool = False,
        swap_decider=None,
        classes=None,
        victim_pricer=None,
        journal=None,
        journal_snapshot_every: int = 0,
    ):
        self.engine = engine
        self.cache = engine.cache
        self.params = params if params is not None else engine.model.params
        self.proposer = proposer
        self.spec_k = int(spec_k)
        if proposer is not None and self.spec_k < 1:
            raise ValueError("speculative decoding needs spec_k >= 1")
        # token-tree speculation: spec_branch > 1 switches the verify
        # step from a single draft chain to a deduped token TREE of up
        # to spec_k * spec_branch nodes (depth spec_k, spec_branch
        # alternatives per level before prefix sharing). The compiled
        # verify width is FIXED at 1 + _tree_nodes — the tree's shape
        # rides in as a parent table (data), so topology changes never
        # recompile. spec_branch == 1 keeps the linear chain path
        # bit-for-bit untouched.
        self.spec_branch = int(spec_branch)
        if self.spec_branch < 1:
            raise ValueError(f"spec_branch must be >= 1, got {spec_branch}")
        self._tree_nodes = self.spec_k * self.spec_branch
        if admission not in _ADMISSION_MODES:
            raise ValueError(
                f"admission must be one of {_ADMISSION_MODES}, "
                f"got {admission!r}"
            )
        self.admission = admission
        self.max_preemptions = int(max_preemptions)
        # chunked prefill: token_budget > 0 switches admission to the
        # chunk-streaming path and caps each iteration's token work.
        # Bad combinations don't raise here — they park an error that
        # _validate raises per-request, so a serving surface built on
        # strict=False degrades to per-request FAILED (the PR 5
        # contract) instead of dying at construction.
        self.token_budget = int(token_budget)
        self.chunk_size = int(chunk_size)
        self._chunk_config_error: Optional[str] = None
        if token_budget < 0:
            self._chunk_config_error = (
                f"token_budget must be >= 0, got {token_budget}"
            )
            self.token_budget = 0
        elif self.token_budget:
            from flexflow_tpu.ops.pallas.decode_kernel import SUBLANES

            if self.chunk_size < 1:
                self._chunk_config_error = (
                    f"chunk_size must be >= 1, got {chunk_size}"
                )
            elif self.token_budget < self.chunk_size:
                self._chunk_config_error = (
                    f"token_budget {token_budget} < chunk_size "
                    f"{chunk_size}: an iteration could never fit one "
                    f"chunk"
                )
            elif self.chunk_size % SUBLANES and self._kernel_active():
                # mirror decode_kernel.supports(): chunk widths are the
                # kernel's query-tile dim, so a misaligned chunk_size
                # would silently route EVERY chunk to the dense fallback
                self._chunk_config_error = (
                    f"chunk_size {chunk_size} must be a multiple of "
                    f"{SUBLANES} when decode_kernel is "
                    f"{engine.decode_kernel!r}"
                )
        self.injector = injector
        # durable serving (serving/journal.py): when a RequestJournal is
        # attached, submit/commit/terminal records flow through it at
        # the seams below — submit() at queue entry, _emit -> note
        # (buffered), _end_iteration -> commit_pending (ONE commit
        # record per request per host sync, so a verify or tree-verify
        # round journals its accepted run at its natural grain),
        # _finalize -> terminal. The commit flush runs INSIDE
        # step(), before any front door can observe the new tokens:
        # journal-before-publish (fxlint FX111).
        self.journal = journal
        self.journal_snapshot_every = int(journal_snapshot_every)
        if self.journal_snapshot_every < 0:
            raise ValueError(
                "journal_snapshot_every must be >= 0, got "
                f"{journal_snapshot_every}"
            )
        # KV swap-to-host: when on, a preemption
        # victim's committed pages ride the host link instead of being
        # recomputed — unless `swap_decider(cache, request)` (built from
        # CostModel.swap_cost vs estimate_recompute_step; None means
        # always-swap) says the recompute is cheaper, or the allocator
        # refuses (budget / in-flight step), or the injector fails it.
        self.kv_swap = bool(kv_swap)
        self.swap_decider = swap_decider
        # ServeConfig.debug_invariants / --check-invariants: re-derive
        # the cache/allocator accounting after EVERY iteration (what the
        # chaos harness does), so an invariant violation surfaces at the
        # iteration that caused it instead of steps later
        self.debug_invariants = bool(debug_invariants)
        # telemetry (flexflow_tpu.telemetry.Telemetry): `_tele` is the
        # hot-path handle — None when disabled, so every instrument
        # point costs exactly one predicate when telemetry is off
        self.telemetry = telemetry
        self._tele = (
            telemetry
            if telemetry is not None and getattr(telemetry, "enabled", False)
            else None
        )
        # what every `span` of the host loop is handed: the bundle's
        # Chrome tracer, or None (then only the profiler's annotation)
        self._tracer = getattr(self._tele, "tracer", None)
        self.queue: deque = deque()
        self.running: Dict[int, Request] = {}  # slot -> request
        self.finished: List[Request] = []
        self.stats = SchedulerStats(
            registry=self._tele.registry if self._tele is not None else None
        )
        self._by_rid: Dict[int, Request] = {}
        self._iter = 0
        self._iter_t0 = 0.0
        self._gauge_handles: Optional[Dict[str, object]] = None
        # the engine's record of every program it dispatches: this
        # scheduler signs the records with what only it knows, hands the
        # log its requests, and advances the dispatch/commit split of
        # its stats from them (`_note_dispatch`, `_note_read`)
        self.step_log = engine.step_log
        self.step_log.running = self.running
        self._noted = None  # the newest step's record (`_note_dispatch`)
        # per-iteration budget ledger: zeroed by _begin_iteration,
        # published as the `budget_used` gauge by _end_iteration
        self._budget_used_iter = 0
        # slots whose FINAL chunk committed this iteration: their first
        # decode/verify waits for the next one, so the chunk planner's
        # grants alone bound the iteration's token work
        self._chunk_unlocked: set = set()
        # an admission whose prefill is dispatched and not read back yet
        # (`_admit_batch` -> `_commit_admission`)
        self._admission: Optional[_Admission] = None
        # -- multi-tenancy ---------------------------------------------------
        # `classes` ({name: PriorityClass}, config order = scheduling
        # order) switches admission and token grants to weighted-fair
        # DRR and preemption victims to class-priced cost. One class or
        # None keeps every decision EXACTLY what it was before classes
        # existed (FIFO admission, youngest-first victims), so single-
        # tenant schedules — including chaos replays — are untouched.
        self.classes = dict(classes) if classes else None
        self._multiclass = bool(self.classes) and len(self.classes) > 1
        self._default_class = next(iter(self.classes)) if self.classes else ""
        self._admit_drr = None
        self._grant_drr: Dict[int, object] = {}  # host -> DRR (token grants)
        self._class_slo: Dict[str, object] = {}
        if self._multiclass:
            from flexflow_tpu.serving.tenancy.fairness import (
                DeficitRoundRobin,
            )

            weights = {n: c.weight for n, c in self.classes.items()}
            self._admit_drr = DeficitRoundRobin(weights, unit=1.0)
        if self.classes and self._tele is not None:
            from flexflow_tpu.serving.tenancy.slo import build_class_monitors

            self._class_slo = build_class_monitors(
                self._tele.registry, self.classes
            )
        # class-priced preemption: weight x resident tokens by default,
        # or the api.py-built CostModel pricer when provided
        self._victim_pricer = victim_pricer
        # paged multi-LoRA adapter pool riding the engine (None = no
        # adapters anywhere; the scheduler owns attach/detach lifecycle)
        self.adapters = getattr(engine, "adapters", None)

    # -- submission / cancellation -------------------------------------------

    def submit(self, request: Request, strict: bool = True) -> bool:
        """Queue a request. Invalid requests raise ValueError when
        `strict` (the library-call contract), or transition straight to
        FAILED when not (the serving-surface contract: one bad request
        must not take down a batch submitted with it). Returns True when
        the request entered the queue."""
        try:
            self._validate(request)
        except ValueError as e:
            if strict:
                raise
            request.submit_iter = self._iter
            request.submit_time = time.perf_counter()
            self._by_rid[request.rid] = request
            self.stats.submitted_requests += 1
            if self.journal is not None:
                # journal the submit BEFORE its terminal record so the
                # strict=False reject leaves the same submit->terminal
                # pair a served request would
                self.journal.submitted(request)
            self._finalize(request, RequestStatus.FAILED, error=str(e))
            return False
        request.status = RequestStatus.QUEUED
        request.submit_iter = self._iter
        request.submit_time = time.perf_counter()
        request.log("submit")
        self._by_rid[request.rid] = request
        self.stats.submitted_requests += 1
        if self.journal is not None:
            self.journal.submitted(request)
        self.queue.append(request)
        return True

    def _validate(self, request: Request) -> None:
        if self._chunk_config_error is not None:
            # rejected chunked-prefill config: every request fails with
            # the parked error — ValueError under strict submit, a
            # per-request FAILED under strict=False
            raise ValueError(self._chunk_config_error)
        if not request.prompt:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"request {request.rid}: max_new_tokens must be >= 1, "
                f"got {request.max_new_tokens}"
            )
        if request.deadline_s is not None and request.deadline_s <= 0:
            raise ValueError(
                f"request {request.rid}: deadline_s must be > 0, "
                f"got {request.deadline_s}"
            )
        need = len(request.prompt) + request.max_new_tokens
        if need > self.cache.spec.max_len:
            raise ValueError(
                f"request {request.rid}: prompt+max_new_tokens {need} "
                f"exceeds cache max_len {self.cache.spec.max_len}"
            )
        if request.priority_class and (
            self.classes is None or request.priority_class not in self.classes
        ):
            raise ValueError(
                f"request {request.rid}: unknown priority class "
                f"{request.priority_class!r} (configured: "
                f"{sorted(self.classes) if self.classes else []})"
            )
        if request.adapter_id != -1:
            if self.adapters is None:
                raise ValueError(
                    f"request {request.rid}: adapter_id "
                    f"{request.adapter_id} but the engine has no adapter "
                    "pool (--adapters)"
                )
            if request.adapter_id not in self.adapters.loaded:
                raise ValueError(
                    f"request {request.rid}: adapter {request.adapter_id} "
                    "is not loaded"
                )

    def _class_of(self, req: Request) -> str:
        """The request's effective priority class — the FIRST configured
        class when it names none (config order is scheduling order)."""
        return req.priority_class or self._default_class

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or running request; its slot and pages free
        at the next finalize. Returns False for unknown or already-
        terminal rids (cancellation races are expected, not errors)."""
        req = self._by_rid.get(rid)
        if req is None or req.status in TERMINAL_STATUSES:
            return False
        self._finalize(req, RequestStatus.CANCELLED)
        return True

    # -- lifecycle core ------------------------------------------------------

    def _finalize(self, req: Request, status: str, error: Optional[str] = None):
        """The ONLY transition into a terminal status: releases the
        slot/pages (or the queue position), notifies the proposer, logs
        the event, and feeds the stats — so every path (finish, fail,
        cancel, timeout, preemption overrun) accounts identically and no
        request can leak a slot or vanish without a terminal record."""
        if req.status in TERMINAL_STATUSES:
            return
        req.status = status
        req.error = error
        req.finish_iter = self._iter
        req.finish_time = time.perf_counter()
        req.log(status, error or "")
        if self.journal is not None:
            # terminal record (preceded inside finalize() by the rid's
            # still-buffered commit run): no request ends undurably
            self.journal.finalize(req.rid, status, error, self._iter)
        slot_host = (
            self.cache.host_of_slot(req.slot)
            if req.slot is not None
            else None
        )
        if req.slot is not None and self.running.get(req.slot) is req:
            if self.proposer is not None:
                self.proposer.retire(req)
            del self.running[req.slot]
            if self.adapters is not None:
                self.adapters.detach(req.slot)
            self.cache.free(req.slot)
            req.slot = None
        else:
            # identity-based removal: Request is a dataclass, so the
            # deque's __eq__-based remove() could drop a twin instead
            for i, queued in enumerate(self.queue):
                if queued is req:
                    del self.queue[i]
                    break
        if req.swap_handle is not None:
            # a terminal request still holding host-swapped pages (e.g.
            # cancelled or timed out while QUEUED) returns its staged
            # bytes to the swap budget
            self.cache.discard_swap(req.swap_handle)
            req.swap_handle = None
        self.finished.append(req)
        stamps = self.step_log.retire(req.rid, req.events)
        stats = self.stats
        stats.events_dropped += req.events_dropped
        if status == RequestStatus.FINISHED:
            stats.finished_requests += 1
            stats.tokens_finished += len(req.generated)
            # latency aggregates take FINISHED requests only: a request
            # retired before its first token has no TTFT, and averaging
            # a 0.0 in would fake lower latencies exactly when faults
            # are making things worse
            stats.ttft_sum_s += req.ttft_s
            stats.decode_latency_sum_s += req.decode_s_per_token
        elif status == RequestStatus.FAILED:
            stats.failed_requests += 1
        elif status == RequestStatus.CANCELLED:
            stats.cancelled_requests += 1
        elif status == RequestStatus.TIMED_OUT:
            stats.timed_out_requests += 1
        tele = self._tele
        if tele is not None:
            reg = tele.registry
            reg.counter(
                "serve_requests_total",
                help="terminal request transitions by status",
                labels={"status": status},
            ).inc()
            if self.classes:
                reg.counter(
                    "serve_requests_total",
                    help="terminal request transitions by status",
                    labels={
                        "status": status,
                        "class": self._class_of(req),
                    },
                ).inc()
            if req.tenant:
                reg.counter(
                    "serve_requests_total",
                    help="terminal request transitions by status",
                    labels={"status": status, "tenant": req.tenant},
                ).inc()
            if (
                self.cache.num_hosts > 1
                and slot_host is not None
            ):
                reg.counter(
                    "serve_requests_total",
                    help="terminal request transitions by status",
                    labels={"status": status, "host": str(slot_host)},
                ).inc()
            if req.events_dropped:
                reg.counter(
                    "serve_request_events_dropped_total",
                    help="audit-log ring-buffer drops (events_max cap)",
                ).inc(req.events_dropped)
            if status == RequestStatus.FINISHED:
                # the SLO view aggregates FINISHED requests only, same
                # rule as the stats accumulators above
                if req.generated:
                    tele.slo.observe_ttft(req.ttft_s)
                tele.slo.observe_finished(
                    req.finish_time, len(req.generated)
                )
                mon = self._class_slo.get(self._class_of(req))
                if mon is not None:
                    if req.generated:
                        mon.observe_ttft(req.ttft_s)
                    mon.observe_finished(
                        req.finish_time, len(req.generated)
                    )
            parts = request_parts(stamps, self.step_log.records)
            for part, seconds in (parts.ttft or {}).items():
                reg.histogram(
                    "serve_ttft_part_ms",
                    help="submit to first token, by what the request "
                    "waited for (telemetry.trace.request_parts)",
                    labels={"part": part},
                ).observe(1e3 * seconds)
            whole = sum((parts.gap or {}).values())
            if whole > 0.0:
                for part, seconds in parts.gap.items():
                    reg.histogram(
                        "serve_token_gap_part_share",
                        bounds=GAP_SHARE_BUCKETS,
                        help="share of first token to terminal event, by "
                        "what the request waited for",
                        labels={"part": part},
                    ).observe(seconds / whole)
            tele.tracer.request_lifecycle(req, parts)

    def _fail(self, req: Request, error: str) -> None:
        self._finalize(req, RequestStatus.FAILED, error=error)

    def _reap_deadlines(self) -> None:
        now = time.perf_counter()
        for req in [r for r in self.queue if r.deadline_exceeded(now)]:
            self._finalize(req, RequestStatus.TIMED_OUT)
        for req in [
            r for r in list(self.running.values()) if r.deadline_exceeded(now)
        ]:
            self._finalize(req, RequestStatus.TIMED_OUT)

    # -- preemption (optimistic admission) -----------------------------------

    def _victim_cost(self, req: Request) -> float:
        """Class-priced eviction cost (multiclass only): what preempting
        this request throws away, weighted by its class — resident
        tokens (prompt + generated so far, the recompute bill) times the
        class weight, so a gold:4 request prices 4x the identical
        bronze one. `victim_pricer` (api.py builds it from the
        CostModel) replaces the token count with a modeled recompute
        cost; the class weight still multiplies it."""
        base = float(len(req.prompt) + len(req.generated))
        if self._victim_pricer is not None:
            try:
                base = float(self._victim_pricer(self.cache, req))
            except Exception:
                pass  # a broken pricer must not break preemption
        return base * self.classes[self._class_of(req)].weight

    def _pick_victim(self) -> Optional[Request]:
        """Youngest-by-admission running request — the vLLM victim rule:
        the newest sequence has the least recompute to lose and, under
        FIFO, the weakest fairness claim. (admit_iter, rid) makes the
        choice deterministic within an admission batch.

        Multiclass flips the rule to cheapest-by-class-priced-cost
        (`_victim_cost`): evict what costs least to redo, priced by
        class weight. Equal cost falls back to the SAME youngest-first
        key — (-admit_iter, -rid) under min() — so ties are
        deterministic by admission order and chaos schedules replay
        exactly."""
        if not self.running:
            return None
        if self._multiclass:
            return min(
                self.running.values(),
                key=lambda r: (self._victim_cost(r), -r.admit_iter, -r.rid),
            )
        return max(
            self.running.values(), key=lambda r: (r.admit_iter, r.rid)
        )

    def _preempt(
        self, req: Request, cause: str = "pool", allow_swap: bool = True
    ) -> None:
        """Reclaim the victim's slot and pages and requeue it at the
        queue HEAD. With kv_swap the victim's committed pages ride the
        host link out (`swap_out`) and restore page-for-page at
        re-admission — no re-prefill; every refusal along that path
        (ineligible victim, cost decider, swap budget, injected
        swap_fail, in-flight step) degrades to prefill-from-recompute
        (prompt + generated so far). A request preempted more than
        `max_preemptions` times hard-fails instead — the bound that
        turns a livelock into a diagnosable error — and the failure
        carries the triggering cause (forensics contract)."""
        req.preemptions += 1
        self.stats.preemptions += 1
        if req.preemptions > self.max_preemptions:
            self._fail(
                req,
                f"preempted {req.preemptions} times "
                f"(max_preemptions {self.max_preemptions}; "
                f"last cause={cause})",
            )
            return
        req.status = RequestStatus.PREEMPTED
        if self.proposer is not None:
            self.proposer.retire(req)
        del self.running[req.slot]
        if self.adapters is not None:
            self.adapters.detach(req.slot)
        action = "recompute"
        if allow_swap and self._swap_eligible(req):
            handle = self.cache.swap_out(req.slot)
            if handle is not None:  # None: budget/in-flight refusal
                req.swap_handle = handle
                action = "swap"
        if action == "recompute":
            self.cache.free(req.slot)
        req.slot = None
        req.log(
            "preempt", f"cause={cause} action={action} iteration {self._iter}"
        )
        if self._tele is not None:
            reg = self._tele.registry
            reg.counter(
                "serve_preemptions_total",
                help="preempt-and-requeue events (optimistic admission)",
            ).inc()
            reg.counter(
                "serve_preemptions_total",
                help="preempt-and-requeue events (optimistic admission)",
                labels={"cause": cause, "action": action},
            ).inc()
        req.status = RequestStatus.QUEUED
        self.queue.appendleft(req)

    def _swap_eligible(self, req: Request) -> bool:
        """Whether this victim's KV should ride the host link instead of
        being recomputed: swap must be ON, the
        slot's committed history worth saving (generated tokens exist
        and no prefill is mid-stream — a half-prefilled slot recomputes
        its chunks anyway), the injector must not fail the swap-out,
        and the cost decider must prefer the copy over the recompute."""
        if not self.kv_swap:
            return False
        if req.slot is None or not req.generated:
            return False
        if self._prefill_pending(req):
            return False
        if self.injector is not None and self.injector.maybe_swap_fail(
            "swap_out"
        ):
            return False
        if self.swap_decider is not None:
            try:
                if not self.swap_decider(self.cache, req):
                    return False
            except Exception:
                return False  # a broken decider must not lose requests
        return True

    def _secure_pages(self, widths: Dict[int, int]) -> None:
        """Claim every page this iteration's step will touch BEFORE the
        jitted call: slot s writes rows lengths[s] .. lengths[s] +
        widths[s] - 1. Under reserve admission the claims are guaranteed
        (a PagePoolExhausted here means something outside the accounting
        drained the pool — an injected fault — and fails just that
        slot); under optimistic admission a dry pool preempts the
        youngest victim and retries, so the engine's own ensure_position
        calls always find the pages already present."""
        for slot in sorted(widths):
            req = self.running.get(slot)
            if req is None:
                continue
            start = int(self.cache.lengths[slot])
            pos = start
            while req.status == RequestStatus.RUNNING and (
                pos < start + widths[slot]
            ):
                try:
                    self.cache.ensure_position(slot, pos)
                    pos += 1
                except PagePoolExhausted as e:
                    # pages pinned by an in-flight step return to the
                    # pool once that step reconciles — drain the
                    # pipeline (async loop; sync has nothing in flight)
                    # and retry before resorting to preemption
                    if self._reclaim_inflight_pages():
                        continue
                    if self.admission != "optimistic":
                        self._fail(req, str(e))
                        break
                    victim = self._pick_victim()
                    if victim is None:
                        self._fail(req, str(e))
                        break
                    self._preempt(victim)
                    # preempting may have evicted `req` itself (it was
                    # the youngest); its requeue ends the claim loop

    def _reclaim_inflight_pages(self) -> bool:
        """Hook for the async loop: reconcile any in-flight step so its
        pinned (limbo) pages return to the free pool. The sync
        schedulers never have a step in flight — nothing to reclaim."""
        return False

    def _admit_swapped(self, req: Request) -> bool:
        """Re-admit a host-swapped queue head: restore its staged pages
        into a fresh slot (no re-prefill — the stream resumes at the
        next decode from generated[-1], and cache.lengths resumes at
        len(prompt) + len(generated) - 1, exactly where free() left it).
        An injected swap_in failure discards the staged copy and sends
        the head back through the normal recompute path — degraded,
        never lost. Returns False when no host can take it right now
        (FIFO: the queue holds behind the head)."""
        if self.injector is not None and self.injector.maybe_swap_fail(
            "swap_in"
        ):
            self.cache.discard_swap(req.swap_handle)
            req.swap_handle = None
            req.log(
                "swap_in_fail",
                f"iteration {self._iter} -> recompute re-admission",
            )
            return True  # head re-enters the loop on the normal path
        # restores are always conservative (reserve the full remaining
        # footprint), even under optimistic admission: a restore that
        # gets re-evicted at the next boundary crossing made no
        # progress but paid the host round-trip twice — bring the
        # stream back only when it can run to completion
        slot = self.cache.swap_in(
            req.swap_handle,
            total_len=len(req.prompt) + req.max_new_tokens,
            optimistic=False,
        )
        if slot is None:
            return False  # handle stays valid for a later iteration
        self._dequeue(req)
        req.swap_handle = None
        req.slot = slot
        req.admit_iter = self._iter
        req.status = RequestStatus.RUNNING
        if self.adapters is not None:
            self.adapters.attach(slot, req.adapter_id)
        # any stale chunk cursors die with the swap restore: the full
        # committed history is already resident, nothing left to stream
        req.prefill_seq = []
        req.prefill_pos = 0
        req.prefill_dispatched = 0
        req.log("admit", f"slot {slot} swap_in")
        self.running[slot] = req
        if self.proposer is not None:
            # the draft cache holds no swapped copy — the proposer
            # re-prefills its side from the committed history (a cold
            # draft degrades acceptance, never correctness)
            self.proposer.admit([req])
        self.stats.peak_in_flight = max(
            self.stats.peak_in_flight, len(self.running)
        )
        return True

    # -- host-failure drain --------------------------------------------------

    def host_down(self, host: int) -> None:
        """Drain a lost host partition: reap its RUNNING requests to
        PREEMPTED (recompute — the dead host's pool content is gone
        with it; queued requests already swapped to host RAM still
        restore on survivors), refuse it new admissions, and stamp the
        event in telemetry. The per-host invariants keep re-deriving
        every iteration: the dead partition's ledgers stay consistent,
        just unused, so recovery is mark_host_up and nothing else."""
        cache = self.cache
        if cache.num_hosts <= 1:
            raise ValueError("host_down needs a multi-host partition")
        t0 = time.perf_counter()
        # in-flight steps may still reference the dying host's slots —
        # drain the pipeline first, same discipline as _secure_pages
        self._reclaim_inflight_pages()
        cache.mark_host_down(host)
        self.stats.host_downs += 1
        victims = sorted(
            (
                r
                for r in self.running.values()
                if cache.host_of_slot(r.slot) == host
            ),
            key=lambda r: (r.admit_iter, r.rid),
        )
        for req in victims:
            # the partition is lost: its device pages cannot be staged
            # out, so the drain always recomputes
            self._preempt(req, cause="host_down", allow_swap=False)
        if self._tele is not None:
            tele = self._tele
            tele.registry.counter(
                "serve_host_down_total",
                help="host partitions drained after an injected failure",
                labels={"host": str(host)},
            ).inc()
            tele.tracer.complete(
                "host_down drain",
                f"host{host}",
                t0,
                time.perf_counter(),
                tid=tele.tracer.host_lane(host),
                args={"host": host, "reaped": len(victims)},
            )

    def host_up(self, host: int) -> None:
        """Re-join a recovered host partition into admission."""
        self.cache.mark_host_up(host)

    # -- cross-engine seams (disaggregated front door) -----------------------

    def stage_out(self, rid: int) -> Optional[int]:
        """Stage a RUNNING request's committed KV out of this engine and
        detach the request, WITHOUT a terminal transition: the caller
        owns the returned swap handle (export it with
        ``cache.export_swap`` to move the pages into another engine) and
        the Request object itself, which re-submits elsewhere with its
        stream intact. This is the prefill-tier half of the
        prefill→decode handoff. Returns None when the request is
        unknown, terminal, not resident, or the cache refuses the copy
        (budget / in-flight step) — the caller retries a later
        iteration; nothing is lost or half-moved."""
        req = self._by_rid.get(rid)
        if req is None or req.status in TERMINAL_STATUSES:
            return None
        if req.slot is None or self.running.get(req.slot) is not req:
            return None
        # pages pinned by an in-flight step would tear mid-copy — drain
        # the pipeline first, same discipline as host_down
        self._reclaim_inflight_pages()
        if req.status in TERMINAL_STATUSES or req.slot is None:
            return None  # reconcile finished/cancelled it
        handle = self.cache.swap_out(req.slot)
        if handle is None:
            return None
        if self.proposer is not None:
            self.proposer.retire(req)
        del self.running[req.slot]
        if self.adapters is not None:
            self.adapters.detach(req.slot)
        del self._by_rid[rid]
        req.slot = None
        req.status = RequestStatus.QUEUED
        req.swap_handle = handle
        # chunk cursors die with the move: the staged copy IS the
        # committed history, nothing left to stream on this engine
        req.prefill_seq = []
        req.prefill_pos = 0
        req.prefill_dispatched = 0
        req.log("stage_out", f"handle {handle} iteration {self._iter}")
        return handle

    def evacuate(self) -> List[Request]:
        """Detach every live request from this engine — the replica-kill
        drain. RUNNING requests drop their device state (the dead
        replica's pool dies with it: no stage-out) and return to QUEUED
        with recompute cursors; queued requests holding swap handles
        discard them (staged copies live in the dead replica's ledger).
        Returns the detached requests in FIFO order (running by
        admission order, then the queue) for the router to re-submit on
        survivors. Not a preemption — the requests never failed, the
        hardware did — so `preemptions` budgets don't tick."""
        self._reclaim_inflight_pages()
        if self.journal is not None:
            # the movers' committed tokens must be durable under THIS
            # journal before they re-enter another scheduler (which may
            # journal elsewhere, or not at all)
            self.journal.commit_pending(self._iter)
        moved: List[Request] = []
        for req in sorted(
            self.running.values(), key=lambda r: (r.admit_iter, r.rid)
        ):
            if self.proposer is not None:
                self.proposer.retire(req)
            if self.adapters is not None:
                self.adapters.detach(req.slot)
            self.cache.free(req.slot)
            req.slot = None
            req.status = RequestStatus.QUEUED
            req.prefill_seq = []
            req.prefill_pos = 0
            req.prefill_dispatched = 0
            req.log("evacuate", f"replica_down iteration {self._iter}")
            moved.append(req)
        self.running.clear()
        for req in self.queue:
            if req.swap_handle is not None:
                self.cache.discard_swap(req.swap_handle)
                req.swap_handle = None
            req.log("evacuate", f"replica_down iteration {self._iter}")
            moved.append(req)
        self.queue.clear()
        for req in moved:
            self._by_rid.pop(req.rid, None)
        return moved

    # -- shared pieces -------------------------------------------------------

    def _dequeue(self, req: Request) -> None:
        """Identity-based queue removal (the multiclass head need not be
        the GLOBAL front; dataclass __eq__ could drop a twin)."""
        for i, queued in enumerate(self.queue):
            if queued is req:
                del self.queue[i]
                return

    def _admission_head(self):
        """The next request admission should try: the global queue front
        (FIFO), or under multiclass the DRR-selected class's front —
        per-class FIFO is the global queue filtered by class, so a
        preempted request's appendleft keeps it at its class front.
        Returns (request, drr_commit) where drr_commit is the closure
        that charges the serve IF the admit lands (select is pure:
        a blocked head charges nothing)."""
        if not self._multiclass:
            return self.queue[0], None
        heads: Dict[str, Request] = {}
        for r in self.queue:
            c = self._class_of(r)
            if c not in heads:
                heads[c] = r
        backlogged = list(heads)
        self._admit_drr.settle(backlogged)
        name, rounds = self._admit_drr.select({c: 1.0 for c in backlogged})

        def commit(drr=self._admit_drr):
            drr.charge(name, rounds, backlogged, cost=1.0)

        return heads[name], commit

    def _admit(
        self, limit: Optional[int] = None, defer: bool = False
    ) -> List[Request]:
        """FIFO admission into free slots (never reorders the queue —
        starvation-free: the head either admits or blocks everyone
        behind it) + ONE prefill batch for the admitted set. Admission
        asks the cache: a free slot and
        enough free PAGES — the request's worst case under the reserve
        policy, only its immediate need under the optimistic one. A
        preempted request re-admits with its recompute sequence
        (prompt + tokens already generated): the prefill rebuilds the
        KV it lost and its next token comes out of that same call.

        Multiclass (`classes` with >1 entry) replaces WHICH head is
        tried — deficit round-robin across per-class FIFO queues, so a
        gold:4 class admits ~4x bronze under contention while every
        backlogged class still serves within bounded rounds — but not
        the blocking rule: a selected head that cannot take a slot NOW
        stops admission for everyone (no bypass), exactly the single-
        class no-reorder guarantee, just applied to the DRR order.

        `defer`: dispatch the prefill and leave its read-back and the
        first tokens to `_commit_admission`, which the overlapped loop
        calls after it has dispatched the running slots' decode step
        behind the prefill. An admission with prefix-shared prompts is
        committed here all the same."""
        with span("scheduler.step.admit", self._tracer):
            return self._admit_batch(limit, defer)

    def _admit_batch(
        self, limit: Optional[int], defer: bool = False
    ) -> List[Request]:
        optimistic = self.admission == "optimistic"
        prefix = bool(self.cache.prefix_cache)
        admitted: List[Request] = []
        seqs: List[List[int]] = []
        cursors: List[int] = []
        while self.queue:
            if limit is not None and len(admitted) >= limit:
                break
            req, drr_commit = self._admission_head()
            if req.swap_handle is not None:
                # host-swapped victim: restore its pages instead of
                # recomputing them — it joins running directly (its
                # stream resumes at the next decode), never the prefill
                # batch below
                if not self._admit_swapped(req):
                    break  # no host can take it NOW — FIFO holds
                if drr_commit is not None and (
                    req.status == RequestStatus.RUNNING
                ):
                    # charge only a LANDED restore (an injected
                    # swap_in failure re-routes through the normal
                    # path without consuming the class's turn)
                    drr_commit()
                continue
            seq = list(req.prompt) + list(req.generated)
            # chunked admission claims pages chunk by chunk (the step's
            # page claims), so nothing is needed NOW — the reserve
            # policy still gates on the same worst case either way
            if prefix:
                # prefix-sharing admission: registered pages matching a
                # prefix of the sequence map into the slot's table and
                # the cursor skips them (prefill recomputes the rest)
                res = self.cache.alloc_shared(
                    seq,
                    prompt_len=0 if self.token_budget else len(seq),
                    total_len=len(req.prompt) + req.max_new_tokens,
                    optimistic=optimistic,
                )
                slot, cursor = (None, 0) if res is None else res
            else:
                slot = self.cache.alloc(
                    0 if self.token_budget else len(seq),
                    len(req.prompt) + req.max_new_tokens,
                    optimistic=optimistic,
                )
                cursor = 0
            if slot is None:
                break
            self._dequeue(req)
            req.slot = slot
            req.admit_iter = self._iter
            req.status = RequestStatus.RUNNING
            if self.adapters is not None:
                self.adapters.attach(slot, req.adapter_id)
            if drr_commit is not None:
                drr_commit()
            req.log(
                "admit",
                f"slot {slot}" + (f" shared {cursor}" if cursor else ""),
            )
            self.running[req.slot] = req
            admitted.append(req)
            seqs.append(seq)
            cursors.append(cursor)
        self.stats.peak_in_flight = max(
            self.stats.peak_in_flight, len(self.running)
        )
        if admitted:
            if self.proposer is not None:
                self.proposer.admit(admitted)
            if self.token_budget:
                # chunked admission: NO monolithic prefill — arm the
                # chunk cursors and let the per-iteration planner
                # stream the sequence in. A preempted request re-admits
                # here too: its recompute sequence (prompt + generated)
                # replaces the old prefill_seq and the cursors restart.
                # Shared admissions start their cursors AT the shared
                # extent: alloc_shared left cache.lengths there, so the
                # planner streams only the unshared suffix.
                for req, seq, cur in zip(admitted, seqs, cursors):
                    req.prefill_seq = [int(t) for t in seq]
                    req.prefill_pos = cur
                    req.prefill_dispatched = cur
                return admitted
            programs = self.engine.prefill_programs
            plain = [i for i, c in enumerate(cursors) if c == 0]
            pending = None
            try:
                if plain:
                    pending = self.engine.prefill_dispatch(
                        self.params,
                        [seqs[i] for i in plain],
                        [admitted[i].slot for i in plain],
                    )
            except Exception as e:
                self._fail_admission(admitted, e)
                return admitted
            if pending is not None:
                for rec, (lo, hi) in zip(pending.records, pending.groups):
                    self._sign(rec, [admitted[i] for i in plain[lo:hi]])
            self._admission = _Admission(
                admitted, seqs, cursors, pending, programs,
                frozenset(r.slot for r in admitted),
            )
            if not defer or len(plain) < len(admitted):
                self._commit_admission()
        return admitted

    def _fail_admission(self, admitted: List[Request], e: Exception) -> None:
        # fault isolation: the batch fails, in-flight slots are untouched
        # and keep decoding
        self.stats.step_faults += 1
        for req in admitted:
            if self.running.get(req.slot) is req:
                self._fail(req, f"prefill failed: {e!r}")

    def _commit_admission(self) -> None:
        """The other half of an admission's prefill: read its tokens and
        logits back and emit the first tokens. A request that left its
        slot since the dispatch (preempted or failed by the decode step
        dispatched in between) is passed over."""
        admitted, seqs, cursors, pending, programs, _ = self._admission
        self._admission = None  # taken: a failure below must not replay it
        plain = [i for i, c in enumerate(cursors) if c == 0]
        shared = [i for i, c in enumerate(cursors) if c > 0]
        try:
            rows: Dict[int, Tuple[int, np.ndarray]] = {}
            if plain:
                nxt_p, last_p = self.engine.prefill_reconcile(pending)
                for j, i in enumerate(plain):
                    rows[i] = (int(nxt_p[j]), np.asarray(last_p[j]))
            if shared:
                # shared slots recompute only tokens[cursor:] — the
                # mapped pages already hold the prefix KV rows
                nxt_s, last_s = self.engine.prefill_suffix(
                    self.params,
                    [seqs[i] for i in shared],
                    [admitted[i].slot for i in shared],
                    [cursors[i] for i in shared],
                )
                # the suffixes' one chunk step, dispatched and read by now
                self._sign(
                    self.step_log.records[-1], [admitted[i] for i in shared]
                )
                for j, i in enumerate(shared):
                    rows[i] = (int(nxt_s[j]), np.asarray(last_s[j]))
            nxt = np.array([rows[i][0] for i in range(len(admitted))])
            last = np.stack([rows[i][1] for i in range(len(admitted))])
        except Exception as e:
            self._fail_admission(admitted, e)
            return
        self.stats.prefill_batches += (
            self.engine.prefill_programs - programs + bool(shared)
        )
        if self._tele is not None and pending is not None:
            for rec in pending.records:
                self._tele.tracer.device_window(
                    rec.kind, rec.seq, rec.t_call, rec.t_ready,
                    args={"iter": rec.iteration, "bucket": rec.bucket},
                )
        live = [self.running.get(r.slot) is r for r in admitted]
        if self.cache.prefix_cache:
            # publish AFTER the prefill returned: a failed dispatch
            # must never leave hash keys pointing at pages whose
            # writes never executed
            for req, seq, ok in zip(admitted, seqs, live):
                if ok:
                    self.cache.register_prefix(req.slot, seq, len(seq))
        if self.injector is not None:
            # np.array (copy): the step's output buffer is read-only
            last = np.array(last)
            self.injector.corrupt_logits(
                last,
                [r.slot for r in admitted],
                rows=range(len(admitted)),
            )
        for i, (tok, req) in enumerate(zip(nxt, admitted)):
            if not live[i]:
                continue
            if not np.isfinite(last[i]).all():
                self._fail(
                    req,
                    f"non-finite prefill logits at iteration "
                    f"{self._iter}",
                )
                continue
            self._emit(req, int(tok))

    def _emit(self, req: Request, token: int) -> None:
        req.generated.append(token)
        if self.journal is not None:
            # journal-before-publish (fxlint FX111): _emit is the ONLY
            # writer of the stream-visible token list, and it notes
            # every token into the journal's pending buffer here —
            # _end_iteration flushes the buffer as commit records before
            # step() returns, so no front door can publish a token the
            # journal never saw
            self.journal.note(req.rid, token)
        if len(req.generated) == 1:
            req.first_token_time = time.perf_counter()
            req.log("first_token")
            req.last_token_time = req.first_token_time
        elif self._tele is not None:
            # inter-token latency: the gap between consecutive COMMITs
            # of one request's tokens (verify emits several per gap —
            # each counts, which is exactly how speculation compresses
            # the latency a user streams at). Telemetry-only: the
            # per-token clock read is the kind of hot-path cost the
            # disabled path must not pay.
            now = time.perf_counter()
            if req.last_token_time:
                self._tele.slo.observe_itl(now - req.last_token_time)
                mon = self._class_slo.get(self._class_of(req))
                if mon is not None:
                    mon.observe_itl(now - req.last_token_time)
            req.last_token_time = now
        self.stats.tokens_generated += 1
        if req._done_after(token):
            self._finalize(req, RequestStatus.FINISHED)

    def _fail_all_running(self, error: str) -> None:
        """Whole-step engine fault with no slot attribution: retire every
        participant with the captured error rather than crash the run —
        the queue behind them keeps serving."""
        self.stats.step_faults += 1
        for req in list(self.running.values()):
            self._fail(req, error)

    def _sign(self, rec, requests) -> None:
        """What only the scheduler knows of a dispatched program."""
        rec.iteration = self._iter
        rec.rids = tuple(r.rid for r in requests)

    def _note_dispatch(self, step) -> None:
        self.stats.dispatch_count += 1
        rec = step.record
        self._sign(rec, step.participants.values())
        if self._noted is not None:
            self.stats.dispatch_gap_sum_s += (
                rec.t_enqueued - self._noted.t_enqueued
            )
        self._noted = rec

    def _note_read(self, step) -> None:
        """A step's record has closed: the dispatch/commit split of the
        stats advances from it, and the trace gets its window."""
        rec = step.record
        self.stats.overlapped_host_s += max(0.0, rec.t_read - rec.t_enqueued)
        self.stats.commit_wait_s += rec.t_ready - rec.t_read
        if self._tele is not None:
            # the step's whole in-flight window (the call → outputs
            # materialized) on a device lane
            self._tele.tracer.device_window(
                rec.kind, rec.seq, rec.t_call, rec.t_ready,
                args={"iter": rec.iteration},
            )

    def _decode_dispatch_step(self, chain=None):
        """Dispatch phase of one decode iteration: claim every page the
        step will touch, build the token/active arrays from the LIVE
        view (this side of the dispatch/reconcile split may read
        mutable state — the snapshot is taken here), and enqueue the
        jitted step. `chain` device-chains input tokens from a
        still-in-flight previous step (async loop): slots whose last
        token is that step's not-yet-materialized output read it on
        device instead of from the host. Returns the InflightStep, or
        None when there is nothing to step."""
        with span("scheduler.step.decode.plan", self._tracer):
            # predicted-view budget gate: a slot whose still-in-flight step
            # will emit its FINAL budgeted token has nothing useful to
            # compute here — the commit-phase identity check would discard
            # the result anyway. EOS is not predictable at dispatch time, so
            # an EOS retire still costs one wasted (discarded) slot-step.
            stepped: Dict[int, Request] = {}
            admitting = self._admission.slots if self._admission else ()
            for slot, req in self.running.items():
                if self._prefill_pending(req) or slot in self._chunk_unlocked:
                    continue  # chunked prefill: no decode until the last
                    #            chunk's token has committed, and none in
                    #            the commit's own iteration (its tokens
                    #            were never in this budget's plan)
                if slot in admitting:
                    continue  # its prefill is dispatched, not read back:
                    #            the first token is not the host's yet
                chained = (
                    chain is not None
                    and chain.kind == "decode"
                    and chain.active[slot]
                    and chain.participants.get(slot) is req
                )
                if len(req.generated) + int(chained) >= req.max_new_tokens:
                    continue
                stepped[slot] = req
            self._secure_pages({slot: 1 for slot in stepped})
            stepped = {
                s: r for s, r in stepped.items() if self.running.get(s) is r
            }
            if not stepped:
                return None
            spec = self.cache.spec
            tokens = np.zeros(spec.max_seqs, dtype=np.int32)
            active = np.zeros(spec.max_seqs, dtype=bool)
            chain_mask = np.zeros(spec.max_seqs, dtype=bool)
            for slot, req in stepped.items():
                tokens[slot] = req.generated[-1]
                active[slot] = True
                if (
                    chain is not None
                    and chain.kind == "decode"
                    and chain.active[slot]
                    and chain.participants.get(slot) is req
                ):
                    chain_mask[slot] = True
        try:
            with span(
                "scheduler.step.decode.dispatch", self._tracer,
                {"iter": self._iter, "active": int(active.sum())},
            ):
                step = self.engine.decode_dispatch(
                    self.params,
                    tokens,
                    active,
                    chain=chain,
                    chain_mask=chain_mask if chain is not None else None,
                )
        except KernelCompileError:
            raise  # never ran: not a fault to isolate
        except Exception as e:
            self._fail_all_running(f"decode step failed: {e!r}")
            return None
        step.iteration = self._iter
        step.participants = stepped
        self._note_dispatch(step)
        self.stats.decode_steps += 1
        self.stats.decode_steps_chained += chain is not None
        self.stats.slot_steps += spec.max_seqs
        self.stats.busy_slot_steps += int(active.sum())
        self._budget_used_iter += int(active.sum())
        return step

    def _reconcile_step(self, step) -> None:
        """Reconcile phase: block on the step's device outputs, then
        commit its results — under the async loop this runs one
        iteration after the dispatch, against the step's snapshot."""
        try:
            if step.kind == "decode":
                nxt, finite = self.engine.decode_reconcile(step)
            elif step.kind == "chunk":
                nxt, logits = self.engine.prefill_chunk_reconcile(step)
            else:
                logits = self.engine.verify_reconcile(step)
        except Exception as e:
            self._fail_all_running(f"{step.kind} step failed: {e!r}")
            return
        self._note_read(step)
        # every reconcile is exactly one host round-trip, whatever the
        # step's width — the denominator of host_syncs_per_token
        self.stats.host_syncs += 1
        # the engine's readback span came first; the commit is the other
        # half of the reconcile. Everything read here comes off the step
        # record, never live cache state (fxlint FX103)
        with span(
            f"scheduler.step.{step.kind}.commit", self._tracer,
            {"iter": step.iteration, "step": step.record.seq},
        ):
            if step.kind == "decode":
                self._commit_decode(step, nxt, finite)
            elif step.kind == "chunk":
                self._commit_chunk(step, nxt, logits)
            elif step.kind == "verify_tree":
                self._commit_verify_tree(step, logits)
            else:
                self._commit_verify(step, logits)

    def _commit_decode(self, step, nxt, finite) -> None:
        """Commit a reconciled decode step: NaN isolation, token emit,
        EOS/budget retirement. `finite` [max_seqs] bool is the step's own
        verdict on each slot's logits row (the rows stay on the device).
        Reads ONLY the step's snapshot — live scheduler/cache state is an
        iteration ahead under the async loop (fxlint FX103 holds this
        path to the snapshot). A participant that retired, was preempted,
        or whose slot was re-admitted while the step was in flight fails
        the identity check and its speculative token is discarded."""
        active_slots = [s for s, a in enumerate(step.active) if a]
        if self.injector is not None:
            # the injector plants NaN in rows of host logits: here a row
            # is the one value that stands for the slot's verdict
            rows = np.where(finite, 0.0, np.nan)
            self.injector.corrupt_logits(
                rows, active_slots, iteration=step.iteration
            )
            finite = np.isfinite(rows)
        for slot in active_slots:
            req = step.participants.get(slot)
            if req is None or self.running.get(slot) is not req:
                self.stats.decode_slot_steps_discarded += 1
                continue
            if not finite[slot]:
                self._fail(
                    req,
                    f"non-finite logits at iteration {step.iteration}",
                )
                continue
            self._emit(req, int(nxt[slot]))

    def _decode_once(self) -> None:
        """Synchronous decode iteration — dispatch + immediate
        reconcile (the reference loop the async engine is proved
        token-identical against)."""
        step = self._decode_dispatch_step()
        if step is not None:
            self._reconcile_step(step)

    def _propose(self, k: int) -> Dict[int, List[int]]:
        """Draft tokens for the running slots; a proposer fault (real or
        injected) degrades THIS iteration to plain decode — empty
        proposals make every verify a w=1 decode — instead of killing
        the run."""
        # chunked prefill: a slot mid-prefill has no committed history
        # to draft from — exclude it until its last chunk lands
        draftable = {
            s: r
            for s, r in self.running.items()
            if not self._prefill_pending(r) and s not in self._chunk_unlocked
        }
        args = {"iter": self._iter}
        try:
            with span("scheduler.step.draft.propose", self._tracer, args):
                if self.injector is not None:
                    self.injector.maybe_draft_fault()
                proposals = self.proposer.propose(draftable, k)
                args["slots"] = len(proposals)
        except KernelCompileError:
            raise  # the draft engine's kernel never ran: not a fault
        except Exception:
            self.stats.draft_faults += 1
            return {}
        return proposals

    def _verify_dispatch_step(self, proposals):
        """Dispatch phase of one speculative iteration: cap each slot's
        drafts to its remaining budget and the cache horizon (live
        reads — this is the dispatch side), claim every page the verify
        writes, and enqueue the batched verify. Returns the
        InflightStep (carrying the draft plan + the pre-step lengths
        snapshot acceptance needs), or None when nothing runs."""
        spec = self.cache.spec
        k = self.spec_k
        plan: Dict[int, List[int]] = {}
        # chunked mode: the iteration's token budget also caps draft
        # widths — every verifying slot keeps its 1-token floor (the
        # budget can pace speculation, not starve decoding), then
        # drafts fit in what remains, first-come by slot id
        budget_left = self.token_budget if self.token_budget else None
        for slot, req in sorted(self.running.items()):
            if self._prefill_pending(req) or slot in self._chunk_unlocked:
                continue  # still streaming its prompt in (or its last
                #            chunk committed THIS iteration) — no verify
            old_len = int(self.cache.lengths[slot])
            # the verify emits up to k_s + 1 tokens and writes k_s + 1
            # rows, so k_s is capped by the request's remaining token
            # budget and by the cache horizon — which also keeps paged
            # verify inside the admission reserve's worst case
            k_s = min(
                len(proposals.get(slot) or ()),
                k,
                req.max_new_tokens - len(req.generated) - 1,
                spec.max_len - old_len - 1,
            )
            if budget_left is not None:
                k_s = min(k_s, max(0, budget_left - 1))
            plan[slot] = list(proposals.get(slot) or ())[: max(0, k_s)]
            if budget_left is not None:
                budget_left -= 1 + len(plan[slot])
        # claim pages for every row the verify writes; optimistic
        # preemption may evict plan slots, so the arrays build AFTER
        self._secure_pages({s: 1 + len(d) for s, d in plan.items()})
        plan = {s: d for s, d in plan.items() if s in self.running}
        if not plan:
            return None
        tokens = np.zeros((spec.max_seqs, k + 1), dtype=np.int32)
        draft_lens = np.zeros(spec.max_seqs, dtype=np.int32)
        for slot, drafts in plan.items():
            req = self.running[slot]
            tokens[slot, 0] = req.generated[-1]
            for j, t in enumerate(drafts):
                tokens[slot, 1 + j] = int(t)
            draft_lens[slot] = 1 + len(drafts)
        try:
            with span(
                "scheduler.step.verify.dispatch", self._tracer,
                {"iter": self._iter, "slots": len(plan)},
            ):
                step = self.engine.verify_dispatch(
                    self.params, tokens, draft_lens
                )
        except KernelCompileError:
            raise  # never ran: not a fault to isolate
        except Exception as e:
            self._fail_all_running(f"verify step failed: {e!r}")
            return None
        step.iteration = self._iter
        step.plan = plan
        step.participants = {s: self.running[s] for s in plan}
        self._note_dispatch(step)
        self.stats.verify_steps += 1
        self.stats.slot_steps += spec.max_seqs
        self.stats.busy_slot_steps += len(plan)
        self._budget_used_iter += int(draft_lens.sum())
        return step

    def _commit_verify(self, step, logits) -> None:
        """Commit a reconciled verify step: per slot accept a prefix of
        the drafts, roll the cache to the accepted length (paged slots
        return surplus pages), and emit accepted + 1 tokens. Acceptance
        runs against the step's SNAPSHOT lengths — the committed
        pre-step lengths — never the live cache view (fxlint FX103). A
        slot whose proposer had nothing degraded to draft_lens 1 —
        exactly a decode step. EOS inside the accepted run retires the
        request AT the EOS position: tokens past it are never emitted."""
        from flexflow_tpu.serving.spec import accept_drafts

        if self.injector is not None:
            logits = np.array(logits)  # writable copy for the injector
            self.injector.corrupt_logits(
                logits, sorted(step.plan), iteration=step.iteration
            )
        for slot in sorted(step.plan):
            req = step.participants.get(slot)
            if req is None or self.running.get(slot) is not req:
                continue
            drafts = step.plan[slot]
            old_len = int(step.lengths[slot])
            if not np.isfinite(logits[slot, : 1 + len(drafts)]).all():
                # lengths never advanced for this slot; freeing it
                # returns its pages, stale verify rows and all
                self._fail(
                    req,
                    f"non-finite logits at iteration {step.iteration}",
                )
                continue
            accepted, emitted = accept_drafts(
                logits[slot],
                drafts,
                temperature=self.engine.temperature,
                seed=self.engine.seed,
                slot=slot,
                base_len=old_len,
            )
            # commit the accepted prefix / roll back the rejected tail
            # BEFORE emitting: _emit may retire the request, which frees
            # the slot (truncating a freed slot would be an error)
            self.cache.truncate(slot, old_len + accepted + 1)
            self.proposer.rollback(slot, old_len + accepted + 1)
            self.stats.draft_tokens_proposed += len(drafts)
            self.stats.draft_tokens_accepted += accepted
            for t in emitted:
                self._emit(req, int(t))
                if req.finished:
                    break  # EOS mid-verify: nothing past it is emitted

    def _verify_once(self) -> None:
        """Synchronous speculative iteration: draft up to spec_k tokens
        per slot (a spec_branch-way tree under tree speculation),
        dispatch ONE batched verify, and reconcile it immediately."""
        if self.spec_branch > 1:
            step = self._verify_tree_dispatch_step(self._propose_trees())
        else:
            step = self._verify_dispatch_step(self._propose(self.spec_k))
        if step is not None:
            self._reconcile_step(step)

    # -- token-tree speculation (spec_branch > 1) ----------------------------

    def _propose_trees(self) -> Dict[int, object]:
        """Tree twin of _propose: draft one deduped token TREE per
        running slot (up to spec_k deep, spec_branch alternatives per
        level, shared prefixes merged). A proposer fault (real or
        injected) degrades THIS iteration to plain decode — empty
        trees make every verify row a w=1 decode — instead of killing
        the run."""
        draftable = {
            s: r
            for s, r in self.running.items()
            if not self._prefill_pending(r) and s not in self._chunk_unlocked
        }
        args = {"iter": self._iter}
        try:
            with span("scheduler.step.draft.propose_tree", self._tracer, args):
                if self.injector is not None:
                    self.injector.maybe_draft_fault()
                trees = self.proposer.propose_trees(
                    draftable, self.spec_k, self.spec_branch
                )
                args["slots"] = len(trees)
        except KernelCompileError:
            raise  # the draft engine's kernel never ran: not a fault
        except Exception:
            self.stats.draft_faults += 1
            return {}
        return trees

    def _verify_tree_dispatch_step(self, trees):
        """Dispatch phase of one tree-speculative iteration: prune each
        slot's draft tree to its budget and horizon caps (live reads —
        this is the dispatch side), claim every page the verify's
        1 + nodes rows need, and enqueue ONE batched tree verify. The
        compiled width is FIXED at 1 + spec_k * spec_branch whatever
        shape the trees take — the topology rides in as a parent table
        (data), so per-iteration tree changes never recompile. Returns
        the InflightStep (carrying the per-slot DraftTree plan + the
        pre-step lengths snapshot acceptance needs), or None when
        nothing runs."""
        from flexflow_tpu.serving.spec import DraftTree

        spec = self.cache.spec
        w = 1 + self._tree_nodes
        plan: Dict[int, object] = {}
        # chunked mode: tree NODES are charged against the iteration's
        # token budget exactly like linear drafts — every verifying
        # slot keeps its 1-token floor, then nodes fit in what remains
        budget_left = self.token_budget if self.token_budget else None
        for slot, req in sorted(self.running.items()):
            if self._prefill_pending(req) or slot in self._chunk_unlocked:
                continue
            old_len = int(self.cache.lengths[slot])
            # every node writes a cache row (horizon cap), but accepted
            # tokens are bounded by the DEPTH — so the request's
            # remaining token budget prunes depth, the cache horizon
            # and iteration budget prune node count
            max_nodes = min(self._tree_nodes, spec.max_len - old_len - 1)
            max_depth = req.max_new_tokens - len(req.generated) - 1
            if budget_left is not None:
                max_nodes = min(max_nodes, max(0, budget_left - 1))
            tree = trees.get(slot) or DraftTree([], [])
            tree = tree.prune(max(0, max_nodes), max(0, max_depth))
            plan[slot] = tree
            if budget_left is not None:
                budget_left -= 1 + len(tree.tokens)
        # claim pages for every row the verify writes; optimistic
        # preemption may evict plan slots, so the arrays build AFTER
        self._secure_pages({s: 1 + len(t.tokens) for s, t in plan.items()})
        plan = {s: t for s, t in plan.items() if s in self.running}
        if not plan:
            return None
        tokens = np.zeros((spec.max_seqs, w), dtype=np.int32)
        draft_lens = np.zeros(spec.max_seqs, dtype=np.int32)
        # pad rows/columns keep a valid chain topology (parent = j - 1)
        parents = np.tile(
            np.arange(-1, w - 1, dtype=np.int32), (spec.max_seqs, 1)
        )
        nodes_total = 0
        for slot, tree in plan.items():
            req = self.running[slot]
            tokens[slot, 0] = req.generated[-1]
            for j, t in enumerate(tree.tokens):
                tokens[slot, 1 + j] = int(t)
            parents[slot] = tree.row_parents(w)
            draft_lens[slot] = 1 + len(tree.tokens)
            nodes_total += len(tree.tokens)
        try:
            with span(
                "scheduler.step.verify_tree.dispatch", self._tracer,
                {"iter": self._iter, "slots": len(plan), "nodes": nodes_total},
            ):
                step = self.engine.verify_tree_dispatch(
                    self.params, tokens, draft_lens, parents
                )
        except KernelCompileError:
            raise  # never ran: not a fault to isolate
        except Exception as e:
            self._fail_all_running(f"tree verify step failed: {e!r}")
            return None
        if self._tele is not None:
            self._tele.registry.counter(
                "serve_spec_tree_nodes_total",
                help="draft-tree nodes dispatched for verification",
            ).inc(nodes_total)
        step.iteration = self._iter
        step.plan = {s: list(t.tokens) for s, t in plan.items()}
        step.tree_plan = plan
        step.participants = {s: self.running[s] for s in plan}
        self._note_dispatch(step)
        self.stats.verify_steps += 1
        self.stats.tree_verify_steps += 1
        self.stats.tree_nodes_proposed += nodes_total
        self.stats.slot_steps += spec.max_seqs
        self.stats.busy_slot_steps += len(plan)
        self._budget_used_iter += int(draft_lens.sum())
        return step

    def _commit_verify_tree(self, step, logits) -> None:
        """Commit a reconciled tree-verify step: per slot walk the
        draft tree against the step's SNAPSHOT plan and lengths (fxlint
        FX103 — under the async loop the live proposer/cache view is an
        iteration ahead), accept the longest surviving root-to-leaf
        path, compact that path's scattered rows into contiguous cache
        positions (truncate + src_rows — dead branches' rows and pages
        return to the reserve in the same call), and emit
        len(path) + 1 tokens. Acceptance counters stay comparable to
        the linear path: proposed counts the tree DEPTH (the most one
        verify could accept), accepted the surviving path length."""
        from flexflow_tpu.serving.spec import accept_tree

        if self.injector is not None:
            logits = np.array(logits)  # writable copy for the injector
            self.injector.corrupt_logits(
                logits, sorted(step.tree_plan), iteration=step.iteration
            )
        for slot in sorted(step.tree_plan):
            req = step.participants.get(slot)
            if req is None or self.running.get(slot) is not req:
                continue
            tree = step.tree_plan[slot]
            n = len(tree.tokens)
            old_len = int(step.lengths[slot])
            if not np.isfinite(logits[slot, : 1 + n]).all():
                # lengths never advanced for this slot; freeing it
                # returns its pages, stale tree rows and all
                self._fail(
                    req,
                    f"non-finite logits at iteration {step.iteration}",
                )
                continue
            path, emitted = accept_tree(
                logits[slot],
                tree,
                temperature=self.engine.temperature,
                seed=self.engine.seed,
                slot=slot,
                base_len=old_len,
            )
            # commit the accepted path / drop every dead branch BEFORE
            # emitting: _emit may retire the request, which frees the
            # slot (truncating a freed slot would be an error). Tree
            # node i's row sits at position old_len + 1 + i; truncate
            # compacts the accepted rows down to old_len + 1 ...
            self.cache.truncate(
                slot,
                old_len + len(path) + 1,
                src_rows=[old_len + 1 + node for node in path],
            )
            self.proposer.rollback(slot, old_len + len(path) + 1)
            self.stats.draft_tokens_proposed += tree.depth()
            self.stats.draft_tokens_accepted += len(path)
            if self._tele is not None:
                self._tele.registry.histogram(
                    "serve_spec_tree_accepted_path_len",
                    bounds=(0, 1, 2, 4, 8, 16, 32),
                    help="accepted root-to-leaf path length per slot "
                    "per tree-verify step",
                ).observe(float(len(path)))
            for t in emitted:
                self._emit(req, int(t))
                if req.finished:
                    break  # EOS mid-verify: nothing past it is emitted

    # -- chunked prefill (token_budget > 0) ----------------------------------

    def _kernel_active(self) -> bool:
        """Whether the engine's decode-kernel mode can actually take the
        Pallas path — `use_kernel`'s mode resolution: "pallas" always
        can, "auto" only on a real TPU backend, "dense" never."""
        mode = getattr(self.engine, "decode_kernel", "dense")
        if mode == "pallas":
            return True
        if mode != "auto":
            return False
        import jax

        return jax.default_backend() == "tpu"

    def _prefill_pending(self, req: Request) -> bool:
        """True while a chunked request still has prompt tokens whose
        chunk has not COMMITTED — it neither decodes nor drafts until
        the last chunk lands. Monolithic admissions (empty prefill_seq)
        are never pending, so every non-chunked path is unaffected."""
        return bool(req.prefill_seq) and req.prefill_pos < len(
            req.prefill_seq
        )

    def _reserved_step_tokens(self, host: Optional[int] = None) -> int:
        """Tokens this iteration's decode/verify step may consume for
        the slots already past prefill — 1 per slot, plus up to spec_k
        drafts each under speculation. The chunk planner budgets around
        this reservation so chunks + decode work stay inside
        token_budget together, which is the whole point: decodes keep
        their cadence WHILE a prompt streams in. `host` narrows the
        count to one host partition's slots (the per-host budget of a
        pod placement)."""
        per = 1 + (
            (self._tree_nodes if self.spec_branch > 1 else self.spec_k)
            if self.proposer is not None
            else 0
        )
        return per * sum(
            1
            for r in self.running.values()
            if not self._prefill_pending(r)
            and len(r.generated) < r.max_new_tokens
            and (host is None or self.cache.host_of_slot(r.slot) == host)
        )

    def _plan_chunks(self, reserved: int) -> Dict[int, int]:
        """Fair-share chunk grants for one iteration: round-robin
        passes over the prefill-pending slots in admission order,
        granting one chunk_size unit (or the remainder) per pass until
        the budget left over from `reserved` runs out. Round-robin —
        not head-of-queue-until-done — is what kills head-of-line
        blocking among prefills themselves: a short prompt admitted
        behind a long one still completes in its first iteration. A
        grant that FINISHES a prompt costs only its own tokens: the
        slot's first decode/verify is deferred one iteration
        (`_chunk_unlocked`), so grants alone bound the iteration's
        token work — charging the unlocked decode here instead would
        wedge the planner when token_budget == chunk_size (a full
        final chunk could never fit). Pending slots granted nothing
        count as budget deferrals (`serve_budget_deferrals_total`).

        Under a multi-host placement the token budget applies PER HOST
        (each host prefills into its own pool shard at its own cadence),
        so the round-robin runs once per host partition over that host's
        pending slots against `token_budget - reserved_on_that_host`."""
        pending_all = sorted(
            (
                r
                for r in self.running.values()
                if r.prefill_dispatched < len(r.prefill_seq)
            ),
            key=lambda r: (r.admit_iter, r.rid),
        )
        if not pending_all:
            return {}
        # keep the chunk step's width inside the Pallas kernel's query
        # tile when a kernel mode is on — a wider grant would silently
        # route the whole step to the dense fallback
        max_grant = self.token_budget
        if self._kernel_active():
            from flexflow_tpu.ops.pallas.decode_kernel import _MAX_W

            max_grant = _MAX_W
        plan: Dict[int, int] = {r.slot: 0 for r in pending_all}
        hosts = range(self.cache.num_hosts)
        for h in hosts:
            if self.cache.num_hosts > 1:
                pending = [
                    r
                    for r in pending_all
                    if self.cache.host_of_slot(r.slot) == h
                ]
                budget = self.token_budget - self._reserved_step_tokens(h)
            else:
                pending = pending_all
                budget = self.token_budget - int(reserved)
            if self._multiclass:
                budget = self._plan_chunks_drr(
                    h, pending, plan, budget, max_grant
                )
                continue
            progress = True
            while progress and budget > 0:
                progress = False
                for req in pending:
                    rem = (
                        len(req.prefill_seq)
                        - req.prefill_dispatched
                        - plan[req.slot]
                    )
                    if rem <= 0 or plan[req.slot] >= max_grant:
                        continue
                    unit = min(
                        self.chunk_size, rem, max_grant - plan[req.slot]
                    )
                    if unit > budget:
                        continue
                    plan[req.slot] += unit
                    budget -= unit
                    progress = True
        deferred = sum(1 for c in plan.values() if c == 0)
        if deferred:
            self.stats.budget_deferrals += deferred
            if self._tele is not None:
                self._tele.registry.counter(
                    "serve_budget_deferrals_total",
                    help="prefill-pending slots granted no chunk tokens "
                    "by an iteration's budget",
                ).inc(deferred)
        return {s: c for s, c in plan.items() if c > 0}

    def _plan_chunks_drr(
        self,
        host: int,
        pending: List[Request],
        plan: Dict[int, int],
        budget: int,
        max_grant: int,
    ) -> int:
        """Weighted-fair grant loop for one host partition: each DRR
        serve grants one chunk unit (up to chunk_size tokens) to the
        selected class's next pending request, so prefill bandwidth
        under the token budget divides by class weight instead of
        admission order. Within a class, requests rotate in admission
        order (the round-robin fairness the single-class loop has).
        The DRR instance persists per host across iterations — carried
        deficits are what make the weighted shares hold over time —
        and idle classes settle to zero so a silent class cannot bank
        credit. Mutates `plan` in place; returns the leftover budget."""
        drr = self._grant_drr.get(host)
        if drr is None:
            from flexflow_tpu.serving.tenancy.fairness import (
                DeficitRoundRobin,
            )

            weights = {n: c.weight for n, c in self.classes.items()}
            drr = DeficitRoundRobin(
                weights, unit=float(max(1, self.chunk_size))
            )
            self._grant_drr[host] = drr
        by_class: Dict[str, List[Request]] = {}
        for r in pending:
            by_class.setdefault(self._class_of(r), []).append(r)
        drr.settle(list(by_class))
        rr: Dict[str, int] = {c: 0 for c in by_class}
        while budget > 0:
            costs: Dict[str, float] = {}
            heads: Dict[str, Tuple[Request, int, int]] = {}
            for c, reqs in by_class.items():
                n = len(reqs)
                for j in range(n):
                    pos = (rr[c] + j) % n
                    req = reqs[pos]
                    rem = (
                        len(req.prefill_seq)
                        - req.prefill_dispatched
                        - plan[req.slot]
                    )
                    if rem <= 0 or plan[req.slot] >= max_grant:
                        continue
                    unit = min(
                        self.chunk_size, rem, max_grant - plan[req.slot]
                    )
                    if unit > budget:
                        continue
                    costs[c] = float(unit)
                    heads[c] = (req, unit, pos)
                    break
            if not costs:
                break
            name, rounds = drr.select(costs)
            req, unit, pos = heads[name]
            plan[req.slot] += unit
            budget -= unit
            drr.charge(name, rounds, list(costs), cost=float(unit))
            rr[name] = (pos + 1) % len(by_class[name])
        if self.debug_invariants:
            drr.check_invariants(max_cost=float(max(1, self.chunk_size)))
        return budget

    def _chunk_dispatch_step(self, plan: Dict[int, int]):
        """Dispatch phase of one chunked-prefill step: claim the pages
        the chunk rows land in, build the token/width arrays from the
        LIVE cursors (this is the dispatch side), advance the dispatch
        cursors, and enqueue the step. The cursor state the commit
        phase needs rides the step record (`InflightStep.chunks`) —
        fxlint FX105 holds the reconcile side to that snapshot. The
        step width pads up to a chunk_size multiple so the engine's
        jitted-program LRU sees a bounded population of widths."""
        if not plan:
            return None
        self._secure_pages(dict(plan))
        live: Dict[int, int] = {}
        for slot, c in plan.items():
            req = self.running.get(slot)
            if req is None:  # optimistic preemption evicted it
                continue
            c = min(c, len(req.prefill_seq) - req.prefill_dispatched)
            if c > 0:
                live[slot] = c
        if not live:
            return None
        spec = self.cache.spec
        unit = max(1, self.chunk_size)
        w = max(live.values())
        w = -(-w // unit) * unit
        tokens = np.zeros((spec.max_seqs, w), dtype=np.int32)
        chunk_lens = np.zeros(spec.max_seqs, dtype=np.int32)
        chunks: Dict[int, tuple] = {}
        for slot, c in sorted(live.items()):
            req = self.running[slot]
            start = req.prefill_dispatched
            tokens[slot, :c] = req.prefill_seq[start : start + c]
            chunk_lens[slot] = c
            chunks[slot] = (start, c, start + c >= len(req.prefill_seq))
        try:
            with span(
                "scheduler.step.chunk.dispatch", self._tracer,
                {"iter": self._iter, "slots": len(chunks),
                 "tokens": int(chunk_lens.sum())},
            ):
                step = self.engine.prefill_chunk_dispatch(
                    self.params, tokens, chunk_lens
                )
        except KernelCompileError:
            raise  # never ran: not a fault to isolate
        except Exception as e:
            self._fail_all_running(f"chunk step failed: {e!r}")
            return None
        for slot, (start, c, _final) in chunks.items():
            self.running[slot].prefill_dispatched = start + c
        if self._tele is not None:
            self._tele.registry.counter(
                "serve_chunks_total",
                help="prompt chunks dispatched (chunked prefill)",
            ).inc(len(chunks))
        step.iteration = self._iter
        step.participants = {s: self.running[s] for s in chunks}
        step.chunks = chunks
        step.chunk_seqs = {s: self.running[s].prefill_seq for s in chunks}
        self._note_dispatch(step)
        self.stats.chunk_steps += 1
        self.stats.chunk_tokens += int(chunk_lens.sum())
        self.stats.slot_steps += spec.max_seqs
        self.stats.busy_slot_steps += len(chunks)
        self._budget_used_iter += int(chunk_lens.sum())
        return step

    def _commit_chunk(self, step, nxt, logits) -> None:
        """Commit a reconciled chunk step: advance each participant's
        committed cursor from the step's OWN cursor record
        (`step.chunks` — never the live prefill_* attrs, fxlint FX105)
        and, on a slot's FINAL chunk, emit the sampled token — exactly
        the monolithic prefill's tail, so the downstream stream is
        token-identical. The usual identity check discards results for
        slots that retired or turned over while the step was in
        flight."""
        if self.injector is not None:
            logits = np.array(logits)  # writable copy for the injector
            self.injector.corrupt_logits(
                logits, sorted(step.chunks), iteration=step.iteration
            )
        for slot in sorted(step.chunks):
            req = step.participants.get(slot)
            if req is None or self.running.get(slot) is not req:
                continue
            start, size, final = step.chunks[slot]
            if not np.isfinite(logits[slot]).all():
                self._fail(
                    req,
                    f"non-finite chunk logits at iteration "
                    f"{step.iteration}",
                )
                continue
            req.prefill_pos = start + size
            if self.cache.prefix_cache:
                # progressive publication: every COMMITTED full page of
                # the streaming prompt becomes matchable immediately —
                # and only committed ones (a faulted chunk never
                # publishes pages with unexecuted writes). Tokens and
                # extent both come from the step record (FX105).
                self.cache.register_prefix(
                    slot, step.chunk_seqs[slot], start + size
                )
            if final:
                self._chunk_unlocked.add(slot)
                self._emit(req, int(nxt[slot]))

    def _chunk_once(self) -> None:
        """Synchronous chunk iteration: plan within the budget left
        after the decode/verify reservation, dispatch, reconcile
        immediately."""
        step = self._chunk_dispatch_step(
            self._plan_chunks(self._reserved_step_tokens())
        )
        if step is not None:
            self._reconcile_step(step)

    def _generate_once(self) -> None:
        if self.proposer is not None:
            self._verify_once()
            return
        self._decode_once()

    def _begin_iteration(self) -> None:
        if self._tele is not None:
            self._iter_t0 = time.perf_counter()
        with span("scheduler.step.begin", self._tracer):
            self._iter += 1
            self.stats.iterations += 1
            self._budget_used_iter = 0
            self._chunk_unlocked.clear()
            if self.injector is not None:
                self.injector.on_iteration(self._iter, self)
                # chaos: process death at the step boundary, before any
                # work — everything journaled so far survives, nothing
                # new is at risk (serving/journal.py proves the restart)
                crash = getattr(self.injector, "maybe_crash", None)
                if crash is not None:
                    crash("begin")
            self._reap_deadlines()

    #: engine ledgers mirrored into the stats at each iteration's end
    _ENGINE_MIRRORS = (
        "verify_cache_entries", "kernel_fallbacks",
        "device_syncs", "readback_bytes", "prefill_tokens_real",
        "prefill_tokens_padded", "prefill_programs", "pool_steps_donated",
        "pool_steps_copied",
        "moe_rows_prefill", "moe_rows_decode",
        "moe_experts_touched_prefill", "moe_experts_touched_decode",
        "moe_rows_absent_prefill", "moe_rows_absent_decode",
        "moe_kernel_programs_prefill", "moe_kernel_programs_decode",
        "mla_rows_read_decode", "state_rows_decode", "state_resets_prefill",
        "kda_kernel_programs_decode", "kda_kernel_programs_prefill",
    )

    def _end_iteration(self) -> None:
        with span("scheduler.step.end", self._tracer):
            # per-iteration gauge: tokens this iteration's dispatches
            # charged against the budget (chunk + decode/verify widths)
            self.stats.budget_used = self._budget_used_iter
            for name in self._ENGINE_MIRRORS:
                setattr(self.stats, name, getattr(self.engine, name, 0))
            cache = self.cache
            self.stats.prefix_hits = cache.prefix_hits
            self.stats.prefix_pages_shared = int(cache._shared.sum())
            self.stats.cow_copies = cache.cow_copies
            self.stats.swap_outs = cache.swap_outs
            self.stats.swap_ins = cache.swap_ins
            self.stats.swap_bytes = cache.swap_bytes_total
            self.stats.swapped_pages = cache.swapped_pages
            self.stats.prefix_evictions = cache.prefix_evictions
            if self.debug_invariants:
                # pages the injector stole this iteration are accounted
                # as extra frees — conservation must hold even mid-chaos
                cache.check_invariants(
                    extra_free=(
                        self.injector.stolen_pages
                        if self.injector is not None
                        else 0
                    )
                )
                if self.adapters is not None:
                    self.adapters.check_invariants()
                if self._admit_drr is not None:
                    self._admit_drr.check_invariants(max_cost=1.0)
        if self._tele is not None:
            # closes the Chrome `iteration` span, so it sits between the
            # two parts of `scheduler.step.end` and not inside one
            self._sample_telemetry()
        if self.injector is not None:
            # chaos: process death AFTER this iteration's tokens were
            # emitted but BEFORE the journal's commit flush below — the
            # worst case: a whole verify or tree-verify round's
            # accepted run is host-visible yet unjournaled, and the
            # restart must recompute it token-identically from the last
            # durable cursor
            crash = getattr(self.injector, "maybe_crash", None)
            if crash is not None:
                crash("commit")
        if self.journal is not None:
            # per-host-sync commit flush, INSIDE step(): the front
            # door's publish runs after step() returns, so the journal
            # always dominates the published cursor (FX111)
            with span("scheduler.step.end", self._tracer):
                self.journal.commit_pending(self._iter)
                if (
                    self.journal_snapshot_every
                    and self._iter % self.journal_snapshot_every == 0
                ):
                    self._journal_snapshots()

    def _journal_snapshots(self) -> None:
        """Journal-referenced KV snapshots: every
        `journal_snapshot_every` iterations, each running slot's
        committed pages ride `snapshot_swap` into a snapshot record, so
        a restart can restore KV over `import_swap` instead of
        recomputing — priced at recovery by `build_restore_decider`.
        `gen_len` stamps the committed-run length the snapshot is
        consistent with; recovery honors the snapshot only while that
        still matches the journal's committed cursor."""
        if self.journal.degraded:
            return
        for slot in sorted(self.running):
            req = self.running[slot]
            if self._prefill_pending(req):
                continue  # mid-prefill KV is not a resumable cursor
            rec = self.cache.snapshot_swap(slot)
            if rec is not None:
                rec["gen_len"] = len(req.generated)
                self.journal.snapshot(req.rid, rec)

    def _sample_telemetry(self) -> None:
        """One iteration's telemetry sample: KV-pool gauges straight
        from the allocator's ledgers, scheduler queue gauges, the fault
        injector's ledger, the derived stats ratios, then one JSONL row
        and the iteration's host span. Runs only with telemetry
        attached — the disabled path never gets here — and resolves
        every gauge handle ONCE, so the steady-state cost is attribute
        writes, not registry lookups."""
        tele = self._tele
        handles = self._gauge_handles
        if handles is None:
            reg = tele.registry
            handles = {
                name: reg.gauge(name)
                for name in self.cache.telemetry_gauges()
            }
            handles["serve_queue_depth"] = reg.gauge(
                "serve_queue_depth", help="requests waiting for admission"
            )
            handles["serve_running_requests"] = reg.gauge(
                "serve_running_requests", help="requests holding a slot"
            )
            self._gauge_handles = handles
        for name, value in self.cache.telemetry_gauges().items():
            handles[name].value = value
        handles["serve_queue_depth"].value = len(self.queue)
        handles["serve_running_requests"].value = len(self.running)
        if self.cache.num_hosts > 1:
            # per-host pool/scheduler slices under a `host` label (the
            # process index on a real pod; simulated-host partitions on
            # one process). The unlabelled series above stay the
            # pod-wide totals, so single-host dashboards see identical
            # streams; labelled series ride the same JSONL sample rows
            # as extra name{host="h"} columns.
            reg = tele.registry
            for h in range(self.cache.num_hosts):
                labels = {"host": str(h)}
                for name, value in self.cache.telemetry_gauges_host(
                    h
                ).items():
                    reg.gauge(name, labels=labels).value = value
                reg.gauge(
                    "serve_running_requests", labels=labels
                ).value = sum(
                    1
                    for r in self.running.values()
                    if self.cache.host_of_slot(r.slot) == h
                )
        if self.classes:
            # per-class scheduler gauges + the rolling per-class SLO
            # views: the unlabelled series stay fleet-wide aggregates,
            # same layering as the per-host block above
            reg = tele.registry
            for name in self.classes:
                labels = {"class": name}
                reg.gauge(
                    "serve_queue_depth", labels=labels
                ).value = sum(
                    1 for r in self.queue if self._class_of(r) == name
                )
                reg.gauge(
                    "serve_running_requests", labels=labels
                ).value = sum(
                    1
                    for r in self.running.values()
                    if self._class_of(r) == name
                )
            for mon in self._class_slo.values():
                mon.publish()
        if self.adapters is not None:
            reg = tele.registry
            for name, value in self.adapters.telemetry_gauges().items():
                reg.gauge(name).value = value
            for name, value in self.adapters.telemetry_counters().items():
                reg.counter(name).set_monotonic(value)
        if self.injector is not None:
            self.injector.publish_metrics(tele.registry)
        if self.proposer is not None:
            for name, value in self.proposer.telemetry_counters().items():
                tele.registry.counter(name).set_monotonic(value)
        for name, value in self.cache.telemetry_counters().items():
            tele.registry.counter(name).set_monotonic(value)
        self.stats.publish_derived()
        tele.sample(self._iter)
        now = time.perf_counter()
        tele.tracer.complete(
            "iteration",
            "host",
            self._iter_t0,
            now,
            args={"iter": self._iter},
        )
        if self.cache.num_hosts > 1:
            # one lane per host partition: the iteration span again, but
            # annotated with that host's running/free-page view so the
            # Perfetto timeline shows per-host load side by side
            free_by_host = self.cache.free_pages_by_host()
            for h in range(self.cache.num_hosts):
                tele.tracer.complete(
                    "iteration",
                    f"host{h}",
                    self._iter_t0,
                    now,
                    tid=tele.tracer.host_lane(h),
                    args={
                        "iter": self._iter,
                        "running": sum(
                            1
                            for r in self.running.values()
                            if self.cache.host_of_slot(r.slot) == h
                        ),
                        "free_pages": free_by_host[h],
                    },
                )

    def _work_pending(self) -> bool:
        return bool(self.queue or self.running)

    def work_pending(self) -> bool:
        """Public driving surface (shared with `ReplicaRouter` and
        `DisaggregatedPipeline`): anything submitted but not yet
        terminal. The front door and the benches drive every backend
        through this same duck type."""
        return self._work_pending()

    def run(self, requests: Optional[Sequence[Request]] = None) -> List[Request]:
        """Drain the queue (plus `requests`, submitted first) to
        completion; returns requests in terminal order — check
        `Request.status`/`Request.ok`, a fault-isolated run finishes
        with FAILED entries instead of raising."""
        for r in requests or ():
            self.submit(r)
        t0 = time.perf_counter()
        while self._work_pending():
            self.step()
        self.stats.elapsed_s += time.perf_counter() - t0
        if self._tele is not None:
            self.stats.publish_derived()
            self.telemetry.flush()
        return self.finished


class ContinuousBatchingScheduler(_SchedulerBase):
    """Orca-style: every iteration joins new prefills with in-flight
    decodes; slots recycle the moment a request retires. With a
    `proposer` + `spec_k`, each iteration runs the speculative
    draft/verify step instead of single-token decode. With a
    `token_budget`, each iteration additionally runs one chunked-
    prefill step for the slots still streaming their prompts in,
    planned so chunks + decode/verify work stay inside the budget."""

    def step(self) -> None:
        self._begin_iteration()
        self._admit()
        if self.token_budget and self.running:
            self._chunk_once()
        if self.running:
            self._generate_once()
        self._end_iteration()


class AsyncContinuousBatchingScheduler(ContinuousBatchingScheduler):
    """Double-buffered Orca loop: overlap host scheduling with device
    steps. The loop `build_scheduler` gives by default
    (`ServeConfig.serve_async`); the synchronous
    ContinuousBatchingScheduler (`serve_async=False`) stays the
    reference it is proved token-identical against.

    The sync loop round-trips every iteration — host admission/paging/
    bookkeeping while the device idles, then the jitted step while the
    host idles. This loop splits each step into its dispatch and
    reconcile halves (engine.InflightStep) and runs them one iteration
    apart: while step N is in flight on the device, the host reaps
    queued deadlines, admits newcomers, claims pages, and dispatches
    step N+1 — chaining N+1's input tokens from N's device outputs so
    the data dependency never touches the host — and only then blocks
    on N's outputs to emit tokens and retire requests.

    One-step-stale semantics: terminal events land at RECONCILE, so a
    request that hits EOS/budget in step N is still (wastefully but
    harmlessly) stepped in N+1 — the identity check in the commit phase
    discards its speculative token, and the cache pins every page an
    in-flight step references (kv_cache limbo) so the row cannot land
    in a page a new sequence owns.
    `cancel()` of a RUNNING request and running-deadline reaping defer
    to the next reconcile for the same reason; queued requests cancel/
    reap immediately. `stats.decode_steps_chained` counts the decode
    steps dispatched with another in flight,
    `stats.decode_slot_steps_discarded` the slot-steps thrown away.

    An admitting iteration dispatches the prefill, then the chained
    decode step of the slots already running, and only then reads the
    prefill back (`_admit(defer=True)` ... `_commit_admission`): the
    admitted slots join the step after.

    When a page claim finds the pool dry because of
    pinned pages, `_reclaim_inflight_pages` drains the pipeline (a
    stall, traded for allocator soundness) before any preemption.

    Speculative mode cannot pipeline two verifies (the next verify's
    input tokens are acceptance DECISIONS, host logic, not a device
    array) — instead the in-flight window hides the proposer: while
    verify N runs, a stateless proposer drafts for N+1 against N's
    predicted (full-accept) history, rolled back at reconcile when the
    prediction misses (stats.pre_proposal_hits/misses)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._inflight: deque = deque()  # InflightStep records, oldest first
        self._pending_cancels: set = set()

    # -- one-step-stale control surface --------------------------------------

    def cancel(self, rid: int) -> bool:
        """Cancel a request. Queued requests finalize immediately; a
        RUNNING request whose slot may be referenced by an in-flight
        step defers to the next reconcile (it may receive at most one
        more token's worth of device work, which is discarded)."""
        req = self._by_rid.get(rid)
        if req is None or req.status in TERMINAL_STATUSES:
            return False
        if req.slot is not None and self._inflight:
            self._pending_cancels.add(rid)
            return True
        return super().cancel(rid)

    def _reap_deadlines(self) -> None:
        now = time.perf_counter()
        for req in [r for r in self.queue if r.deadline_exceeded(now)]:
            self._finalize(req, RequestStatus.TIMED_OUT)
        if not self._inflight:
            for req in [
                r
                for r in list(self.running.values())
                if r.deadline_exceeded(now)
            ]:
                self._finalize(req, RequestStatus.TIMED_OUT)

    def _after_reconcile(self) -> None:
        """Deferred control events land at the commit boundary: cancels
        queued during the in-flight window, then running-deadline
        reaping."""
        for rid in sorted(self._pending_cancels):
            req = self._by_rid.get(rid)
            if req is not None and req.status not in TERMINAL_STATUSES:
                self._finalize(req, RequestStatus.CANCELLED)
        self._pending_cancels.clear()
        now = time.perf_counter()
        for req in [
            r for r in list(self.running.values()) if r.deadline_exceeded(now)
        ]:
            self._finalize(req, RequestStatus.TIMED_OUT)

    # -- pipeline ------------------------------------------------------------

    def _reconcile_front(self) -> None:
        step = self._inflight.popleft()
        self._reconcile_step(step)
        self._after_reconcile()

    def _drain_inflight(self) -> bool:
        drained = bool(self._inflight)
        while self._inflight:
            self._reconcile_front()
        return drained

    def _reclaim_inflight_pages(self) -> bool:
        # pages pinned for the in-flight step return at its reconcile —
        # the drain stalls the pipeline but keeps the allocator sound
        return self._drain_inflight()

    def _journal_snapshots(self) -> None:
        # a snapshot is a slot's COMMITTED rows beside its committed
        # cursor: the step in flight has written one row more, so it
        # lands first, and what it emitted is journaled before the
        # iteration returns (FX111)
        if self._drain_inflight():
            self.journal.commit_pending(self._iter)
        super()._journal_snapshots()

    def _work_pending(self) -> bool:
        return bool(self.queue or self.running or self._inflight)

    def step(self) -> None:
        self._begin_iteration()
        if self.proposer is not None:
            self._admit()
            self._verify_iteration_async()
        else:
            # an admitting iteration: the prefill is dispatched, then the
            # chained decode step of the slots already running, and only
            # then is the prefill read back, so the device goes from the
            # prefill into a decode step and not into the host's wake-up.
            self._admit(defer=True)
            self._decode_iteration_async()
            if self._admission is not None:
                with span("scheduler.step.admit", self._tracer):
                    self._commit_admission()
        self._end_iteration()

    def _decode_iteration_async(self) -> None:
        """Dispatch decode N+1 (token-chained on the in-flight step N's
        device outputs), THEN reconcile N — the double buffer. Under a
        token budget the iteration also dispatches one chunk step ahead
        of the decode: chunk progress has no host data dependency (the
        prompt tokens are accepted by construction, the engine advances
        lengths at dispatch), so chunks pipeline exactly like chained
        decodes and both steps of iteration N ride the device while the
        host reconciles N-1."""
        keep = 0
        if self.token_budget and self.running:
            step = self._chunk_dispatch_step(
                self._plan_chunks(self._reserved_step_tokens())
            )
            if step is not None:
                self._inflight.append(step)
                keep += 1
        if self.running:
            # chain on the newest in-flight DECODE step — an
            # interleaved chunk step never carries the decoding
            # slots' next tokens
            chain = next(
                (s for s in reversed(self._inflight) if s.kind == "decode"),
                None,
            )
            step = self._decode_dispatch_step(chain=chain)
            if step is not None:
                self._inflight.append(step)
                keep += 1
        while len(self._inflight) > keep:
            self._reconcile_front()
        if not keep:
            # nothing enqueued this iteration (drained queue tail,
            # every slot budget-gated behind the in-flight step, or a
            # whole-step fault) — flush the pipeline so its pinned
            # pages and terminal events land instead of livelocking
            self._drain_inflight()

    def _verify_iteration_async(self) -> None:
        """Speculative iteration: while verify N is in flight, draft
        for N+1 against its predicted outcome; reconcile N; dispatch
        N+1 with the surviving pre-proposals. Under a token budget a
        chunk step dispatches BEFORE the drain — it overlaps the
        in-flight verify on the device — and stays in flight through
        this iteration's verify dispatch."""
        pre = self._pre_propose()
        keep = 0
        if self.token_budget and self.running:
            step = self._chunk_dispatch_step(
                self._plan_chunks(self._reserved_step_tokens())
            )
            if step is not None:
                self._inflight.append(step)
                keep += 1
        while len(self._inflight) > keep:
            self._reconcile_front()
        if self.running:
            if self.spec_branch > 1:
                # tree mode: pre-proposals never fire (_pre_propose
                # gates on kind == "verify" — predicting which PATH a
                # tree verify accepts would misfire far more often than
                # a chain's full-acceptance bet), so trees draft fresh
                # against the reconciled state
                step = self._verify_tree_dispatch_step(
                    self._propose_trees()
                )
            else:
                step = self._verify_dispatch_step(
                    self._merge_proposals(pre)
                )
            if step is not None:
                self._inflight.append(step)

    # -- speculative pre-proposals -------------------------------------------

    def _pre_propose(self) -> Dict[int, Tuple[int, List[int]]]:
        """Draft for the NEXT verify while the current one is still in
        flight, against each slot's PREDICTED history: committed tokens
        plus the in-flight drafts, assuming full acceptance (the
        common case in the regimes speculation wins). Only stateless
        proposers pre-draft — a model proposer's cache feeds would need
        their own rollback story. Returns slot -> (predicted generated
        length, proposal); `_merge_proposals` validates the prediction
        at reconcile and rolls mispredictions back to a fresh draft."""
        if (
            not self._inflight
            or self.proposer is None
            or not getattr(self.proposer, "stateless", False)
        ):
            return {}
        step = self._inflight[-1]
        if step.kind != "verify" or not step.plan:
            return {}
        seqs: Dict[int, List[int]] = {}
        basis: Dict[int, int] = {}
        for slot, drafts in step.plan.items():
            req = step.participants.get(slot)
            if req is None or self.running.get(slot) is not req:
                continue
            seqs[slot] = list(req.prompt) + list(req.generated) + [
                int(t) for t in drafts
            ]
            basis[slot] = len(req.generated) + len(drafts)
        if not seqs:
            return {}
        # draft one EXTRA token: the prediction cannot know the verify's
        # bonus/correction token, so a pre-proposal only survives when
        # its first token turns out to BE that token — the rest aligns
        # the draft/verify overlap the async spec loop exists for: this
        # host span sits INSIDE the in-flight verify's device window in
        # the exported trace
        with span(
            "scheduler.step.draft.pre_propose", self._tracer,
            {"iter": self._iter, "slots": len(seqs)},
        ):
            proposals = self.proposer.propose_sequences(seqs, self.spec_k + 1)
        return {
            s: (basis[s], [int(t) for t in proposals.get(s) or ()])
            for s in seqs
        }

    def _merge_proposals(
        self, pre: Dict[int, Tuple[int, List[int]]]
    ) -> Dict[int, List[int]]:
        """Fresh proposals overlaid with the pre-proposals whose
        prediction held: the in-flight verify fully accepted (generated
        grew by exactly drafts + bonus) AND the pre-draft's first token
        is the bonus token it could not see. Everything else is a
        rolled-back misprediction and uses the fresh draft."""
        proposals = self._propose(self.spec_k)
        for slot, (basis, prop) in pre.items():
            req = self.running.get(slot)
            if req is None:
                continue
            if (
                len(req.generated) == basis + 1
                and len(prop) > 1
                and prop[0] == int(req.generated[-1])
            ):
                proposals[slot] = prop[1:]
                self.stats.pre_proposal_hits += 1
            else:
                self.stats.pre_proposal_misses += 1
        return proposals


_LATENCY_METRICS = {
    "latency": lambda r: r.latency_s,
    "ttft": lambda r: r.ttft_s,
    "decode_per_token": lambda r: r.decode_s_per_token,
}


def latency_percentiles(
    requests: Sequence[Request], pcts=(50, 95), metric: str = "latency"
):
    """{pct: seconds} over successfully FINISHED requests (failed,
    cancelled, and timed-out requests have no meaningful latency and
    would drag the percentiles toward zero). metric: "latency"
    (submit→finish, the default), "ttft" (submit→first token), or
    "decode_per_token" (per-generated-token decode latency after the
    first — where speculative decoding's win shows up as latency rather
    than throughput).

    The percentile math itself lives in telemetry.slo.percentiles —
    the ONE implementation the rolling SLO windows also use, so this
    post-hoc view and the live `serve_slo_*` gauges agree exactly
    whenever the window still holds every sample."""
    if metric not in _LATENCY_METRICS:
        raise ValueError(
            f"metric must be one of {sorted(_LATENCY_METRICS)}, got {metric!r}"
        )
    fn = _LATENCY_METRICS[metric]
    return _percentiles((fn(r) for r in requests if r.ok), pcts)
