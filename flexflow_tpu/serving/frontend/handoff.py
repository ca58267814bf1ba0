"""Prefill-tier → decode-tier handoff (disaggregated serving).

The DistServe/Splitwise posture: a burst of long prompts saturating
chunked prefill must not inflate the inter-token latency of streams
already decoding, so prefill and decode run on SEPARATE engines. A
dedicated prefill engine (`PrefillOnlyScheduler` — the continuous loop
with the decode/verify half cut out) streams each prompt in by chunks
and emits the first token; the committed KV pages (int8 scale slivers
included) then stage out over the swap path (`scheduler.stage_out` →
`cache.export_swap`) and restore into the decode tier's cache
(`cache.import_swap`), where the stream resumes as plain decode from
`generated[-1]` — the exact re-admission contract swapped preemption
victims already use, so the restored stream is bit-identical to one
that never moved.

Refusals degrade, never lose: a stage-out the prefill cache refuses
(budget, in-flight step) retries next pipeline step; a record the
decode cache refuses (its own swap budget) falls back to recompute
admission on the decode tier (the prompt + first token re-prefill
there), counted as `serve_handoff_fallback_total`.

Both tiers keep their own telemetry bundles — gauges like
`serve_queue_depth` mean per-tier numbers, and the pipeline's own
`serve_handoff_*` counters land in the decode tier's registry (the
tier that owns the user-visible stream).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

from flexflow_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)

__all__ = ["PrefillOnlyScheduler", "DisaggregatedPipeline"]


class PrefillOnlyScheduler(ContinuousBatchingScheduler):
    """The continuous-batching loop with decode cut out: admissions and
    chunked prefill only. A request is DONE here the moment its last
    chunk commits (the final chunk emits the stream's first token —
    TTFT is a prefill-tier number); it then waits in `running`, holding
    its committed pages, for `stage_out`. Deadlines still reap at every
    step boundary, so a request whose handoff never comes times out
    instead of squatting a slot forever."""

    def step(self) -> None:
        self._begin_iteration()
        self._admit()
        if self.token_budget and self.running:
            self._chunk_once()
        self._end_iteration()

    def ready_for_handoff(self) -> List[Request]:
        """Requests whose prompt is fully committed and first token
        emitted — everything the decode tier needs is in the pool.
        Admission order keeps the handoff FIFO-fair."""
        return sorted(
            (
                r
                for r in self.running.values()
                if r.generated and not self._prefill_pending(r)
            ),
            key=lambda r: (r.admit_iter, r.rid),
        )


class DisaggregatedPipeline:
    """Two engines, one request lifecycle: submit → prefill tier
    (chunked prefill, first token) → KV stage-out/import → decode tier
    (plain decode to completion). Presents the same driving surface as
    a single scheduler (`submit` / `cancel` / `step` / `run` /
    `work_pending`), so the front-door server and the bench drive it
    interchangeably with a monolithic engine.

    `serve` configures the decode tier verbatim (async double-buffering
    included); the prefill tier runs the same config pinned to the
    synchronous chunk-only loop — chunked prefill needs
    `serve.token_budget` set, enforced here because a prefill tier that
    monolithically prefills would hold its admission gate wide open and
    the disaggregation would prove nothing."""

    def __init__(
        self,
        prefill_model,
        decode_model,
        serve,
        injector=None,
    ):
        from flexflow_tpu.serving.api import build_scheduler

        if not serve.token_budget:
            raise ValueError(
                "disaggregated handoff needs a token_budget (the "
                "prefill tier streams prompts in by chunks)"
            )
        pserve = dataclasses.replace(serve, serve_async=False)
        (
            self.prefill_sched,
            self.prefill_engine,
            self.prefill_cache,
        ) = build_scheduler(
            prefill_model,
            pserve,
            injector=injector,
            scheduler_cls=PrefillOnlyScheduler,
        )
        (
            self.decode_sched,
            self.decode_engine,
            self.decode_cache,
        ) = build_scheduler(decode_model, serve, injector=injector)
        self.handoffs = 0
        self.handoff_fallbacks = 0
        self.handoff_bytes = 0
        # wall time spent inside each tier's steps — the clocks a
        # bench attributes latency to: on disaggregated hardware the
        # tiers run concurrently, so decode latency is decode-tier
        # time (not the in-process interleaving's sum), and the
        # overlap a concurrent deployment hides is bounded by the
        # smaller tier's clock
        self.prefill_step_s = 0.0
        self.decode_step_s = 0.0

    # -- scheduler-compatible surface ----------------------------------------

    def submit(self, request: Request) -> bool:
        return self.prefill_sched.submit(request)

    def cancel(self, rid: int) -> bool:
        """Cancel wherever the request currently lives. There is no
        in-between: a handoff completes (or falls back) within one
        `_drain_ready` call, so every non-terminal request is owned by
        exactly one tier."""
        return self.prefill_sched.cancel(rid) or self.decode_sched.cancel(
            rid
        )

    def work_pending(self) -> bool:
        return (
            self.prefill_sched._work_pending()
            or self.decode_sched._work_pending()
        )

    def step(self) -> None:
        """One pipeline iteration: advance the prefill tier, move every
        finished prefill across, advance the decode tier. In the real
        deployment the two tiers step concurrently on separate
        hardware; in-process they interleave, which preserves every
        ordering the concurrent version allows (the handoff is the only
        cross-tier edge and it is explicit)."""
        if self.prefill_sched._work_pending():
            t0 = time.perf_counter()
            self.prefill_sched.step()
            self.prefill_step_s += time.perf_counter() - t0
        self._drain_ready()
        if self.decode_sched._work_pending():
            t0 = time.perf_counter()
            self.decode_sched.step()
            self.decode_step_s += time.perf_counter() - t0

    def run(self, requests=None) -> List[Request]:
        for r in requests or ():
            self.submit(r)
        while self.work_pending():
            self.step()
        return self.finished

    @property
    def finished(self) -> List[Request]:
        """Terminal requests from BOTH tiers in finish order: a
        max_new_tokens=1 stream (or a cancel/timeout during prefill)
        retires on the prefill tier and never crosses."""
        done = list(self.prefill_sched.finished) + list(
            self.decode_sched.finished
        )
        return sorted(done, key=lambda r: r.finish_time)

    def request(self, rid: int) -> Optional[Request]:
        return self.prefill_sched._by_rid.get(
            rid
        ) or self.decode_sched._by_rid.get(rid)

    # -- the handoff ---------------------------------------------------------

    def _drain_ready(self) -> None:
        for req in self.prefill_sched.ready_for_handoff():
            handle = self.prefill_sched.stage_out(req.rid)
            if handle is None:
                # cache refusal (budget / freshly-cancelled) — the
                # request stays resident and retries next step
                continue
            record = self.prefill_cache.export_swap(handle)
            req.swap_handle = None
            self._install(req, record)

    def _install(self, req: Request, record: Dict[str, object]) -> None:
        new_handle = self.decode_cache.import_swap(record)
        # TTFT was stamped when the prefill tier emitted the first
        # token; decode-tier submit() re-stamps submit_time for its own
        # queue accounting, which must not erase the client's clock
        submit_time = req.submit_time
        if new_handle is None:
            # decode-tier swap budget refused the staged bytes:
            # recompute fallback — the decode tier re-prefills
            # prompt + first token on admission. Slower, never lost.
            self.handoff_fallbacks += 1
            req.log("handoff_fallback", "decode tier refused staged bytes")
        else:
            req.swap_handle = new_handle
            self.handoffs += 1
            self.handoff_bytes += int(record["bytes"])
            req.log("handoff", f"decode-tier handle {new_handle}")
        if not self.decode_sched.submit(req):
            return  # validation failure already finalized it there
        req.submit_time = submit_time
        tele = self.decode_sched.telemetry
        if tele is not None:
            reg = tele.registry
            reg.counter(
                "serve_handoff_total",
                help="prefill->decode KV handoffs completed",
            ).inc()
            if new_handle is None:
                reg.counter(
                    "serve_handoff_fallback_total",
                    help="handoffs degraded to recompute admission",
                ).inc()
            else:
                reg.counter(
                    "serve_handoff_bytes_total",
                    help="staged KV bytes moved across the tier boundary",
                ).inc(int(record["bytes"]))
