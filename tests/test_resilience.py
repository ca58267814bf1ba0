"""Serving resilience (flexflow_tpu/serving/{scheduler,faults}.py):
request lifecycle terminal statuses, deadlines + cancellation,
per-request fault isolation (NaN logits, kernel failure, bad input),
optimistic admission with preemption-by-recompute, and the seeded
deterministic fault-injection harness.

The load-bearing proofs: under a seeded FaultInjector schedule every
submitted request reaches exactly one terminal status (nothing is ever
silently lost), unaffected greedy streams are token-identical to a
fault-free run on BOTH kv layouts, and the page allocator's full
accounting holds after every chaos iteration. All CPU-fast (tier 1).
"""

import numpy as np
import pytest

import jax

from test_paged_kv import _check_allocator_invariants

from flexflow_tpu import (
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.serving import (
    FaultInjector,
    FaultPlan,
    PagePoolExhausted,
    Request,
    RequestStatus,
    ServeConfig,
    TERMINAL_STATUSES,
    build_scheduler,
    latency_percentiles,
)
from tests.conftest import page_geometry

pytestmark = pytest.mark.serving

VOCAB = 50


def _lm(batch=4, seq=32, seed=0):
    cfg = FFConfig(batch_size=batch, seed=seed)
    model = FFModel(cfg)
    tok = model.create_tensor([batch, seq], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        model, tok, vocab_size=VOCAB, hidden=32, num_heads=4, num_layers=2,
        ff_dim=64,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=jax.devices()[:1],
    )
    return model


@pytest.fixture(scope="module")
def lm():
    return _lm()


_PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [3, 1, 4, 1, 5]]


def _requests(n=4, max_new=6, **kw):
    return [
        Request(rid=i, prompt=list(_PROMPTS[i % len(_PROMPTS)]),
                max_new_tokens=max_new, **kw)
        for i in range(n)
    ]


def _baseline(lm, layout="paged", max_new=6, n=4, **cfg_kw):
    """Fault-free greedy streams, keyed by rid."""
    out = lm.generate(
        [list(_PROMPTS[i % len(_PROMPTS)]) for i in range(n)],
        max_new_tokens=max_new,
        serve_config=ServeConfig(max_seqs=4, max_seq_len=32,
                                 **page_geometry(layout, 32), **cfg_kw),
    )
    return {i: out[i] for i in range(n)}


def _drain(sched, cache=None, injector=None):
    while sched.queue or sched.running:
        sched.step()
        if cache is not None:
            _check_allocator_invariants(cache, injector=injector)
    return sched.finished


# -- lifecycle basics ---------------------------------------------------------


def test_finished_lifecycle_and_events(lm):
    sched, _, _ = build_scheduler(lm, ServeConfig(max_seqs=4, max_seq_len=32))
    done = sched.run(_requests())
    assert len(done) == 4
    for r in done:
        assert r.status == RequestStatus.FINISHED
        assert r.ok and r.finished and r.error is None
        names = [e[1] for e in r.events]
        assert names[:3] == ["submit", "admit", "first_token"]
        assert names[-1] == RequestStatus.FINISHED
    s = sched.stats
    assert s.submitted_requests == s.finished_requests == 4
    assert s.terminal_requests == 4
    assert s.failed_requests == s.cancelled_requests == 0
    assert s.timed_out_requests == s.preemptions == 0
    assert s.tokens_finished == s.tokens_generated == 24


def test_submit_rejects_bad_requests(lm):
    sched, _, _ = build_scheduler(lm, ServeConfig(max_seqs=2, max_seq_len=32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(Request(rid=0, prompt=[1], max_new_tokens=0))
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit(Request(rid=1, prompt=[]))
    with pytest.raises(ValueError, match="deadline_s"):
        sched.submit(Request(rid=2, prompt=[1], deadline_s=0.0))
    with pytest.raises(ValueError, match="exceeds cache max_len"):
        sched.submit(Request(rid=3, prompt=[1] * 30, max_new_tokens=16))
    assert not sched.queue  # nothing leaked into the queue


def test_serveconfig_rejects_negative_temperature_and_bad_admission():
    with pytest.raises(ValueError, match="temperature"):
        ServeConfig(temperature=-0.5)
    with pytest.raises(ValueError, match="admission"):
        ServeConfig(admission="hopeful")
    with pytest.raises(ValueError, match="max_preemptions"):
        ServeConfig(max_preemptions=-1)


def test_nonstrict_submit_fails_terminally_without_poisoning_stats(lm):
    """submit(strict=False) turns an invalid request into a FAILED
    terminal record (the serving-surface contract) and a request that
    dies before its first token contributes NOTHING to the latency
    aggregates — the zero-token retire-stats fix."""
    sched, _, _ = build_scheduler(lm, ServeConfig(max_seqs=2, max_seq_len=32))
    bad = Request(rid=7, prompt=[1] * 30, max_new_tokens=16)
    assert sched.submit(bad, strict=False) is False
    ok = Request(rid=8, prompt=[1, 2], max_new_tokens=4)
    assert sched.submit(ok, strict=True) is True
    done = sched.run()
    assert {r.rid: r.status for r in done} == {
        7: RequestStatus.FAILED, 8: RequestStatus.FINISHED
    }
    assert "exceeds cache max_len" in bad.error
    s = sched.stats
    assert s.failed_requests == 1 and s.finished_requests == 1
    # ttft/decode means average over the ONE finished request only
    assert s.mean_ttft_s == pytest.approx(ok.ttft_s)
    assert s.mean_decode_s_per_token == pytest.approx(ok.decode_s_per_token)
    # percentile helper likewise skips non-FINISHED requests
    p = latency_percentiles(done, (50,), metric="ttft")
    assert p[50] == pytest.approx(ok.ttft_s)


def test_generate_over_capacity_prompt_is_per_request_failure(lm):
    """FFModel.generate: one over-capacity prompt in a batch returns an
    empty continuation instead of raising away the whole batch."""
    out = lm.generate(
        [[1, 2, 3], list(range(1, 30)), [4, 5]],
        max_new_tokens=6,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32),
    )
    assert out[1] == []
    assert len(out[0]) == 6 and len(out[2]) == 6
    # the valid requests' streams are what a clean batch produces
    clean = lm.generate(
        [[1, 2, 3], [4, 5]], max_new_tokens=6,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32),
    )
    assert out[0] == clean[0] and out[2] == clean[1]


# -- cancellation + deadlines -------------------------------------------------


def test_cancel_queued_and_running(lm):
    sched, _, cache = build_scheduler(
        lm, ServeConfig(max_seqs=1, max_seq_len=32)
    )
    reqs = _requests(3, max_new=10)
    for r in reqs:
        sched.submit(r)
    sched.step()  # rid 0 running, 1 and 2 queued
    assert sched.cancel(1) is True  # queued
    assert sched.cancel(0) is True  # running: slot must free
    assert cache.num_active == 0
    assert sched.cancel(99) is False  # unknown
    assert sched.cancel(0) is False  # already terminal
    done = _drain(sched, cache)
    assert {r.rid: r.status for r in done} == {
        0: RequestStatus.CANCELLED,
        1: RequestStatus.CANCELLED,
        2: RequestStatus.FINISHED,
    }
    assert sched.stats.cancelled_requests == 2
    assert len(reqs[2].generated) == 10


def test_deadline_timeout_queued_and_running(lm):
    sched, _, cache = build_scheduler(
        lm, ServeConfig(max_seqs=1, max_seq_len=32)
    )
    # rid 0 hogs the single slot; rid 1's deadline expires in the queue;
    # rid 2's expires mid-generation (it admits after 0 finishes)
    sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=8))
    sched.submit(Request(rid=1, prompt=[3], max_new_tokens=8,
                         deadline_s=1e-6))
    sched.step()
    import time

    time.sleep(0.01)
    done = _drain(sched, cache)
    st = {r.rid: r.status for r in done}
    assert st[0] == RequestStatus.FINISHED
    assert st[1] == RequestStatus.TIMED_OUT
    assert sched.stats.timed_out_requests == 1
    # a timed-out-in-queue request never consumed a slot or emitted
    timed = next(r for r in done if r.rid == 1)
    assert timed.generated == [] and timed.slot is None
    # zero-token timeout stays out of the latency aggregates
    assert sched.stats.mean_ttft_s == pytest.approx(
        next(r for r in done if r.rid == 0).ttft_s
    )


def test_running_deadline_retires_mid_flight(lm):
    sched, _, cache = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32)
    )
    r = Request(rid=0, prompt=[1, 2], max_new_tokens=30, deadline_s=0.005)
    sched.submit(r)
    sched.step()  # admits + first token
    import time

    time.sleep(0.02)
    done = _drain(sched, cache)
    assert done[0].status == RequestStatus.TIMED_OUT
    assert cache.num_active == 0  # slot freed on timeout
    assert 1 <= len(done[0].generated) < 30


# -- fault isolation: NaN logits ----------------------------------------------


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_nan_fault_retires_only_its_slot(lm, layout):
    """Injected NaN logits on one slot: that request FAILs with the
    captured error; every other request's greedy stream is
    token-identical to a fault-free run — at both page geometries."""
    base = _baseline(lm, layout=layout)
    inj = FaultInjector(FaultPlan(nan_iters={3: [1]}))
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32, **page_geometry(layout, 32)),
        injector=inj,
    )
    done = sched.run(_requests())
    assert inj.summary() == {"nan": 1}
    st = {r.rid: r for r in done}
    assert st[1].status == RequestStatus.FAILED
    assert "non-finite logits" in st[1].error
    for rid in (0, 2, 3):
        assert st[rid].ok
        assert st[rid].generated == base[rid]
    if layout == "paged":
        _check_allocator_invariants(cache)
        assert cache.pages_in_use == 0


def test_nan_fault_at_prefill_fails_before_first_token(lm):
    """NaN on the admission iteration's prefill logits: the request
    fails with ZERO generated tokens and the latency aggregates ignore
    it (the zero-token retire-stats guard, fault-injected)."""
    inj = FaultInjector(FaultPlan(nan_iters={1: [0]}))
    sched, _, _ = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32), injector=inj
    )
    done = sched.run(_requests())
    st = {r.rid: r for r in done}
    assert st[0].status == RequestStatus.FAILED
    assert st[0].generated == []
    assert "non-finite prefill logits" in st[0].error
    finished = [r for r in done if r.ok]
    assert len(finished) == 3
    s = sched.stats
    assert s.mean_ttft_s == pytest.approx(
        sum(r.ttft_s for r in finished) / 3
    )


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_nan_fault_in_verify_mode(lm, layout):
    """The finite guard covers the speculative verify path too: a NaN
    slot FAILs, unaffected slots' spec streams still equal the plain
    fault-free streams (greedy spec == greedy plain)."""
    base = _baseline(lm, layout=layout, max_new=8)
    inj = FaultInjector(FaultPlan(nan_iters={2: [2]}))
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
                    spec_draft="ngram", spec_k=3),
        injector=inj,
    )
    done = sched.run(_requests(max_new=8))
    st = {r.rid: r for r in done}
    assert st[2].status == RequestStatus.FAILED
    for rid in (0, 1, 3):
        assert st[rid].ok and st[rid].generated == base[rid]
    if layout == "paged":
        _check_allocator_invariants(cache)


# -- fault isolation: kernel failure ------------------------------------------


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_kernel_fault_falls_back_to_dense_and_keeps_serving(lm, layout):
    """An injected Pallas-kernel dispatch failure permanently falls the
    engine back to the dense paths — no request is lost, and every
    greedy stream matches the dense engine's."""
    base = _baseline(lm, layout=layout, decode_kernel="dense")
    inj = FaultInjector(FaultPlan(kernel_iters=(2,)))
    sched, engine, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
                    decode_kernel="pallas"),
        injector=inj,
    )
    done = sched.run(_requests())
    assert engine.kernel_fallbacks == 1
    assert engine.decode_kernel == "dense"
    assert "KernelFault" in engine.kernel_fallback_error
    assert inj.summary() == {"kernel": 1}
    for r in done:
        assert r.ok
        assert r.generated == base[r.rid]
    assert sched.stats.step_faults == 0  # fallback, not a step fault
    # the seam raises before the program is called: the pools were intact
    # for the retry, and every program that ran donated them
    assert engine.pool_steps_copied == 0
    assert engine.pool_steps_donated == (
        sched.stats.prefill_batches + sched.stats.decode_steps
    )


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_kernel_that_cannot_compile_raises_instead_of_falling_back(
    lm, layout, monkeypatch
):
    """A kernel that fails on the FIRST dispatch of its program never
    ran: that is the compiler refusing it, not a fault to survive.
    Neither the engine's dense fallback nor the scheduler's per-step
    isolation may absorb it — the run fails with the compiler's message
    (on the chip, the fallback is how a kernel Mosaic refused served
    every request from the dense path and exited 0)."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk
    from flexflow_tpu.serving.engine import KernelCompileError

    def refuse(*args, **kwargs):
        raise NotImplementedError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(dk, "_paged_call", refuse)
    sched, engine, _ = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
                    decode_kernel="pallas"),
    )
    with pytest.raises(KernelCompileError, match="Mosaic failed to compile"):
        sched.run(_requests())
    assert engine.kernel_fallbacks == 0
    assert engine.decode_kernel == "pallas"


def test_runtime_kernel_failure_after_a_good_step_still_falls_back(lm):
    """The other half of the contract: once a program has run, a failure
    of it IS a run-time fault, answered by the permanent dense fallback
    exactly like an injected one."""
    base = _baseline(lm, decode_kernel="dense")
    sched, engine, _ = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    decode_kernel="pallas"),
    )
    good = engine._decode_jit
    calls = {"n": 0}

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("device halted")
        return good(*args)

    engine._decode_jit = flaky
    done = sched.run(_requests())
    assert engine.kernel_fallbacks == 1
    assert engine.decode_kernel == "dense"
    assert "device halted" in engine.kernel_fallback_error
    for r in done:
        assert r.ok and r.generated == base[r.rid]
    # the wrapper raised BEFORE the program ran: the pools it was handed
    # were intact, so the retry stood on them and every step donated
    assert engine.pool_steps_copied == 0
    assert engine.pool_steps_donated == (
        sched.stats.prefill_batches + sched.stats.decode_steps
    )


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_failure_after_the_program_consumed_its_pools_is_pools_lost(
    lm, layout
):
    """A step program owns the pools it is handed (they are donated). A
    failure that surfaces AFTER the call consumed them, as one at
    `block_until_ready` does, leaves nothing to retry on: the engine must
    not call a program on the deleted arrays, nor commit them, but raise
    the named error, which the scheduler reports like any other lost
    step — every request reaches a terminal status that says so."""
    from flexflow_tpu.serving.engine import PoolsLostError

    sched, engine, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
                    decode_kernel="pallas"),
    )
    good = engine._decode_jit
    calls = {"n": 0, "after": 0}

    def consumed_then_failed(*args):
        calls["n"] += 1
        if calls["n"] < 3:
            return good(*args)
        if calls["n"] == 3:
            good(*args)  # runs, and takes the pools with it
            raise RuntimeError("device halted")
        calls["after"] += 1  # a retry, or any later call, on deleted pools
        return good(*args)

    commits = []
    commit = cache.commit

    def watched(*pools):
        commits.append(
            any(a.is_deleted() for a in jax.tree_util.tree_leaves(pools))
        )
        return commit(*pools)

    engine._decode_jit = consumed_then_failed
    cache.commit = watched
    done = sched.run(_requests(n=6))
    assert calls == {"n": 3, "after": 0}
    assert commits and not any(commits)
    assert engine.kernel_fallbacks == 0  # no fallback: nothing to retry on
    assert sched.stats.step_faults >= 1
    assert len(done) == 6
    for r in done:
        assert r.status == RequestStatus.FAILED
        assert "PoolsLostError" in r.error
    # the engine refuses every later dispatch the same way
    slot = cache.alloc(3, 8)
    with pytest.raises(PoolsLostError, match="consumed"):
        engine.prefill(sched.params, [[1, 2, 3]], [slot])


def test_draft_fault_degrades_iteration_to_plain_decode(lm):
    """A faulting draft proposer costs speed, never correctness: the
    iteration runs as plain decode and the streams match the fault-free
    spec run (which itself matches plain greedy)."""
    base = _baseline(lm, max_new=8)
    inj = FaultInjector(FaultPlan(draft_iters=(2, 3)))
    sched, _, _ = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32, spec_draft="ngram",
                    spec_k=3),
        injector=inj,
    )
    done = sched.run(_requests(max_new=8))
    assert sched.stats.draft_faults == 2
    for r in done:
        assert r.ok and r.generated == base[r.rid]


# -- optimistic admission + preemption-by-recompute ---------------------------


def _short_burst(n, max_new=3):
    return [
        Request(rid=i, prompt=[(i * 3 + j) % (VOCAB - 1) + 1
                               for j in range(1 + i % 2)],
                max_new_tokens=max_new)
        for i in range(n)
    ]


def test_optimistic_admission_beats_reserve_concurrency(lm):
    """The capacity case for optimism: the reserve gate prices every
    request at its worst case UP FRONT, so a tight pool runs few of
    them concurrently even when their early footprint is one page each.
    Optimistic admission fills the slots immediately and lets later
    pressure sort itself out with preemption."""
    peak = {}
    for admission in ("reserve", "optimistic"):
        sched, _, cache = build_scheduler(
            lm,
            ServeConfig(max_seqs=8, max_seq_len=32,
                        kv_page_size=4, kv_pages=16, admission=admission,
                        max_preemptions=8),
        )
        reqs = [
            Request(rid=i, prompt=[i % (VOCAB - 1) + 1], max_new_tokens=8)
            for i in range(8)
        ]
        done = sched.run(reqs)
        assert all(r.status == RequestStatus.FINISHED for r in done)
        assert all(len(r.generated) == 8 for r in done)
        peak[admission] = sched.stats.peak_in_flight
        _check_allocator_invariants(cache)
    # worst case 9 tokens = 3 pages: reserve admits floor(16/3) = 5;
    # optimistic starts all 8 on one page each
    assert peak["reserve"] == 5
    assert peak["optimistic"] == 8


def test_preemption_recompute_completes_all_requests(lm):
    """Forced preemption: an overcommitted pool drains with every
    request FINISHED at full length, allocator invariants holding at
    every iteration, and the preempt events on the victims' logs."""
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=8, kv_pages=8, admission="optimistic",
                    max_preemptions=6),
    )
    for r in _requests(5, max_new=20):
        sched.submit(r)
    done = _drain(sched, cache)
    assert len(done) == 5
    for r in done:
        assert r.status == RequestStatus.FINISHED
        assert len(r.generated) == 20
    assert sched.stats.preemptions > 0
    preempted = [r for r in done if r.preemptions > 0]
    assert preempted
    for r in preempted:
        assert "preempt" in [e[1] for e in r.events]
    assert cache.pages_in_use == 0
    _check_allocator_invariants(cache)


def test_preemption_picks_youngest_victim(lm):
    """The victim rule is youngest-by-admission: the FIFO head, admitted
    first, is never the one preempted."""
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=8, kv_pages=8, admission="optimistic",
                    max_preemptions=6),
    )
    done = sched.run(_requests(4, max_new=20))
    eldest = next(r for r in done if r.rid == 0)
    assert eldest.preemptions == 0
    assert sched.stats.preemptions > 0


def test_preemption_bound_hard_fails(lm):
    """max_preemptions=0: the first preemption of a victim becomes a
    hard FAILED with the bound in the error — bounded preemption turns
    a potential livelock into a diagnosable failure, and nothing is
    lost."""
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=8, kv_pages=8, admission="optimistic",
                    max_preemptions=0),
    )
    done = _drain_submit(sched, cache, _requests(5, max_new=20))
    assert all(r.status in TERMINAL_STATUSES for r in done)
    failed = [r for r in done if r.status == RequestStatus.FAILED]
    assert failed
    assert all("preempted" in r.error for r in failed)
    assert [r for r in done if r.ok]  # the survivors completed
    _check_allocator_invariants(cache)


def _drain_submit(sched, cache, reqs):
    for r in reqs:
        sched.submit(r)
    return _drain(sched, cache)


def test_page_steal_under_reserve_fails_only_the_claiming_slot(lm):
    """Reserve admission is preemption-free, so an externally drained
    pool (the injected fault that 'cannot happen') fails exactly the
    slot whose guaranteed claim broke — with the invariant violation in
    its captured error — while slots that never need a fresh page
    finish."""
    inj = FaultInjector(
        FaultPlan(steal_iters=(2,), steal_pages=64, steal_hold=50)
    )
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=4),
        injector=inj,
    )
    # rid 0 crosses a page boundary mid-decode (needs a claim); rid 1
    # fits its whole run inside its prompt's last page (no claim)
    sched.submit(Request(rid=0, prompt=[1, 2, 3, 4], max_new_tokens=8))
    sched.submit(Request(rid=1, prompt=[5, 6, 7, 8, 9], max_new_tokens=2))
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache, injector=inj)
    st = {r.rid: r for r in sched.finished}
    assert st[0].status == RequestStatus.FAILED
    assert "exhausted" in st[0].error
    assert st[1].status == RequestStatus.FINISHED
    inj.release_stolen_pages(cache)
    _check_allocator_invariants(cache)


# -- the combined seeded chaos proof ------------------------------------------


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_chaos_schedule_isolates_faults_both_layouts(lm, layout):
    """The acceptance criterion: a seeded schedule combining a NaN slot,
    a kernel failure, and stolen pages (with small pages and optimistic
    admission: pool exhaustion). Every submitted rid
    reaches a terminal status, and every request the faults did not
    touch streams token-identical to the fault-free run."""
    base = _baseline(lm, layout=layout, max_new=8, n=4,
                     decode_kernel="dense")
    plan = FaultPlan(
        nan_iters={4: [3]},
        kernel_iters=(3,),
        steal_iters=(5,),
        steal_pages=2,
        steal_hold=3,
    )
    inj = FaultInjector(plan, seed=0)
    sched, engine, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=8 if layout == "paged" else 32,
                    admission="optimistic" if layout == "paged" else
                    "reserve",
                    decode_kernel="pallas"),
        injector=inj,
    )
    for r in _requests(4, max_new=8):
        sched.submit(r)
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache, injector=inj)
    done = sched.finished
    # nothing lost: every rid terminal, accounting adds up
    assert {r.rid for r in done} == {0, 1, 2, 3}
    assert all(r.status in TERMINAL_STATUSES for r in done)
    assert sched.stats.terminal_requests == sched.stats.submitted_requests
    # the kernel fault fell back; the NaN slot failed
    assert engine.kernel_fallbacks == 1 and engine.decode_kernel == "dense"
    st = {r.rid: r for r in done}
    assert st[3].status == RequestStatus.FAILED
    # unaffected = finished and never preempted: token-identical streams
    untouched = [r for r in done if r.ok and r.preemptions == 0]
    assert untouched
    for r in untouched:
        assert r.generated == base[r.rid]
    inj.release_stolen_pages(cache)
    _check_allocator_invariants(cache)
    assert cache.pages_in_use == 0


def test_chaos_rates_never_lose_requests(lm):
    """Rate-driven chaos: whatever the
    dice do, every request terminates and the allocator stays
    consistent."""
    plan = FaultPlan(nan_rate=0.02, cancel_rate=0.02,
                     steal_iters=(3, 7), steal_pages=2, steal_hold=2)
    inj = FaultInjector(plan, seed=7)
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=8, kv_pages=10, admission="optimistic",
                    max_preemptions=6),
        injector=inj,
    )
    for r in _requests(8, max_new=10):
        sched.submit(r, strict=False)
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache, injector=inj)
    assert sched.stats.terminal_requests == 8
    assert {r.rid for r in sched.finished} == set(range(8))
    inj.release_stolen_pages(cache)
    _check_allocator_invariants(cache)


def test_fault_injector_is_deterministic(lm):
    """Same seed + plan + workload → identical statuses, streams, and
    injection ledger across runs."""
    plan = FaultPlan(nan_rate=0.05, cancel_rate=0.03)

    def run_once():
        inj = FaultInjector(plan, seed=11)
        sched, _, _ = build_scheduler(
            lm, ServeConfig(max_seqs=4, max_seq_len=32), injector=inj
        )
        done = sched.run(_requests(6, max_new=8))
        return (
            {r.rid: (r.status, tuple(r.generated)) for r in done},
            inj.summary(),
        )

    a, ca = run_once()
    b, cb = run_once()
    assert a == b
    assert ca == cb
    assert sum(ca.values()) > 0  # the dice actually rolled something


def test_mid_flight_cancellation_via_injector(lm):
    inj = FaultInjector(FaultPlan(cancel_iters={3: [1]}))
    sched, _, cache = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32), injector=inj
    )
    done = sched.run(_requests(4, max_new=10))
    st = {r.rid: r for r in done}
    assert st[1].status == RequestStatus.CANCELLED
    assert 1 <= len(st[1].generated) < 10  # stopped mid-stream
    assert inj.summary() == {"cancel": 1}
    for rid in (0, 2, 3):
        assert st[rid].ok and len(st[rid].generated) == 10


def test_latency_spike_counts_and_goodput(lm):
    inj = FaultInjector(FaultPlan(spike_rate=1.0, spike_s=0.002,
                                  cancel_iters={3: [0]}))
    sched, _, _ = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32), injector=inj
    )
    done = sched.run(_requests(4, max_new=6))
    assert inj.injected["spike"] == sched.stats.iterations
    s = sched.stats
    # the cancelled request's tokens are work but not goodput
    assert s.tokens_finished < s.tokens_generated
    assert 0 < s.goodput_tokens_per_s < s.tokens_per_s


def test_faultplan_validation():
    with pytest.raises(ValueError, match="nan_rate"):
        FaultPlan(nan_rate=1.5)
    with pytest.raises(ValueError, match="spike_s"):
        FaultPlan(spike_s=-0.1)


# -- chaos matrix: every injector site under a chained step / in tree rounds --
#
# The overlapped loop keeps a decode step in flight behind the one being
# committed, and a tree-verify round (spec_branch) moves several logical
# decode steps inside one host sync. Every injector site must keep the
# single-victim contract when its iteration lands inside either regime,
# and the reconcile at the step's or round's boundary must keep
# unaffected streams token-identical. swap_fail and host_down need
# preemption (optimistic admission): they are driven through the
# tree-verify matrix.


def _chaos_run(lm, plan, seed=0, n=4, max_new=10, reqs=None, **cfg_kw):
    inj = FaultInjector(plan, seed=seed)
    sched, engine, cache = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32, **cfg_kw),
        injector=inj,
    )
    for r in (reqs if reqs is not None else _requests(n, max_new=max_new)):
        sched.submit(r, strict=False)
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache, injector=inj)
    return inj, sched, engine, cache, {r.rid: r for r in sched.finished}


@pytest.mark.parametrize("site", ["spike", "cancel", "nan", "kernel",
                                  "steal"])
def test_chaos_site_with_a_chained_step_in_flight(lm, site):
    """Each injectable site fired at an iteration of the default loop
    where a chained decode step is in flight: exactly the planned victim
    is touched, every other stream is token-identical to the fault-free
    run, and steps were actually chained around the fault."""
    base = _baseline(lm, max_new=10,
                     decode_kernel="dense")
    plan = {
        "spike": FaultPlan(spike_rate=1.0, spike_s=0.0005),
        "cancel": FaultPlan(cancel_iters={3: [1]}),
        "nan": FaultPlan(nan_iters={3: [1]}),
        "kernel": FaultPlan(kernel_iters=(3,)),
        "steal": FaultPlan(steal_iters=(3,), steal_pages=64,
                           steal_hold=50),
    }[site]
    inj, sched, engine, cache, st = _chaos_run(
        lm, plan,
        decode_kernel="pallas" if site == "kernel" else "dense",
        kv_page_size=8,
    )
    # the regime was real: steps chained, and the site actually fired
    assert sched.stats.decode_steps_chained > 0
    assert sum(inj.summary().values()) > 0
    # nothing lost: every rid terminal exactly once
    assert set(st) == set(range(4))
    assert all(r.status in TERMINAL_STATUSES for r in st.values())
    assert (sched.stats.terminal_requests
            == sched.stats.submitted_requests == 4)
    if site == "cancel":
        assert st[1].status == RequestStatus.CANCELLED
        # the cancel lands at the next read-back: the cancelled stream
        # is a clean PREFIX of the fault-free stream — the token of the
        # step in flight is discarded, nothing duplicated or invented
        assert st[1].generated == base[1][: len(st[1].generated)]
    elif site == "nan":
        assert st[1].status == RequestStatus.FAILED
        assert "non-finite" in st[1].error
    elif site == "kernel":
        assert engine.kernel_fallbacks == 1
        assert engine.decode_kernel == "dense"
    elif site == "steal":
        failed = [r for r in st.values()
                  if r.status == RequestStatus.FAILED]
        assert failed and all("exhaust" in r.error for r in failed)
        inj.release_stolen_pages(cache)
    # the single-victim contract: untouched streams token-identical
    untouched = [r for r in st.values() if r.ok and r.preemptions == 0]
    assert untouched
    for r in untouched:
        assert r.generated == base[r.rid], r.rid
    _check_allocator_invariants(cache)


_TREE_CFG = dict(kv_page_size=8, spec_draft="ngram",
                 spec_k=3, spec_branch=2)


@pytest.mark.parametrize("site", ["spike", "cancel", "nan", "kernel",
                                  "draft", "steal"])
def test_chaos_site_inside_tree_verify_round(lm, site):
    """The same per-site contract with token-tree verification live:
    a fault landing on a tree-verify iteration touches its one victim,
    degrades the round to plain decode (draft), or falls back the
    kernel — and every unaffected stream still equals the fault-free
    greedy run (tree speculation is exact, so the baseline is the
    plain stream)."""
    base = _baseline(lm, max_new=10,
                     decode_kernel="dense")
    plan = {
        "spike": FaultPlan(spike_rate=1.0, spike_s=0.0005),
        "cancel": FaultPlan(cancel_iters={3: [1]}),
        "nan": FaultPlan(nan_iters={2: [2]}),
        "kernel": FaultPlan(kernel_iters=(3,)),
        "draft": FaultPlan(draft_iters=(2, 3)),
        "steal": FaultPlan(steal_iters=(3,), steal_pages=64,
                           steal_hold=50),
    }[site]
    inj, sched, engine, cache, st = _chaos_run(
        lm, plan,
        decode_kernel="pallas" if site == "kernel" else "dense",
        **_TREE_CFG,
    )
    assert sched.stats.tree_verify_steps > 0
    assert sum(inj.summary().values()) > 0
    assert set(st) == set(range(4))
    assert all(r.status in TERMINAL_STATUSES for r in st.values())
    if site == "cancel":
        assert st[1].status == RequestStatus.CANCELLED
        assert st[1].generated == base[1][: len(st[1].generated)]
    elif site == "nan":
        assert st[2].status == RequestStatus.FAILED
    elif site == "kernel":
        assert engine.kernel_fallbacks == 1
    elif site == "draft":
        assert sched.stats.draft_faults == 2
    elif site == "steal":
        failed = [r for r in st.values()
                  if r.status == RequestStatus.FAILED]
        assert failed
        inj.release_stolen_pages(cache)
    untouched = [r for r in st.values() if r.ok and r.preemptions == 0]
    assert untouched
    for r in untouched:
        assert r.generated == base[r.rid], r.rid
    _check_allocator_invariants(cache)


def test_swap_fail_inside_tree_verify_round(lm):
    """The two preemption-coupled sites (swap_out failure, and — by
    the same recompute fallback — a downed swap host) inside the
    tree-verify regime: optimistic admission over an overcommitted
    pool forces swap-out preemption mid-speculation; the injected
    swap failure downgrades victims to recompute, and every request
    still finishes at full length."""
    plan = FaultPlan(swap_fail_iters=(3, 4, 5))
    inj = FaultInjector(plan, seed=0)
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=8, kv_pages=8, admission="optimistic",
                    max_preemptions=8, kv_swap=True,
                    spec_draft="ngram", spec_k=3, spec_branch=2),
        injector=inj,
    )
    for r in _requests(4, max_new=16):
        sched.submit(r)
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache, injector=inj)
    st = {r.rid: r for r in sched.finished}
    assert sched.stats.tree_verify_steps > 0
    assert sched.stats.preemptions > 0
    assert set(st) == set(range(4))
    for r in st.values():
        assert r.status == RequestStatus.FINISHED
        assert len(r.generated) == 16
    assert cache.pages_in_use == 0
    _check_allocator_invariants(cache)


# -- search-side: reserve vs optimistic capacity + recompute cost -------------


def _search_lm():
    cfg = FFConfig(batch_size=4)
    m = FFModel(cfg)
    tok = m.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(m, tok, vocab_size=128, hidden=64, num_heads=4)
    return m


def test_estimate_max_in_flight_reserve_vs_optimistic():
    from flexflow_tpu.search.auto import estimate_max_in_flight

    m = _search_lm()
    budget = 64 << 20
    kw = dict(mean_prompt_len=16, mean_gen_len=16, max_len=1024,
              page_size=16)
    opt = estimate_max_in_flight(m.graph, budget, **kw)
    # a workload that declares 512 tokens but emits 16: reserve charges
    # the declaration, optimistic the reality
    rsv = estimate_max_in_flight(
        m.graph, budget, admission="reserve", max_new_tokens=512, **kw
    )
    assert rsv < opt
    # declaring exactly what you use collapses the two policies
    same = estimate_max_in_flight(
        m.graph, budget, admission="reserve", max_new_tokens=16, **kw
    )
    assert same == opt
    with pytest.raises(ValueError, match="admission"):
        estimate_max_in_flight(m.graph, budget, admission="bogus", **kw)


def test_optimize_serving_reports_both_capacities():
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import optimize_serving

    m = _search_lm()
    spec = MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e")
    res = optimize_serving(
        m.graph, 1, spec, batch_size=1, kv_len=1024, page_size=16,
        mean_prompt_len=64, mean_gen_len=32, max_len=4096,
        max_new_tokens=1024,
    )
    assert res.max_in_flight is not None
    assert res.max_in_flight_reserve is not None
    assert res.max_in_flight_reserve < res.max_in_flight
    assert "under reserve admission" in res.describe()


def test_estimate_recompute_step_prices_preemption():
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import estimate_recompute_step
    from flexflow_tpu.search.cost_model import CostModel

    m = _search_lm()
    cm = CostModel(MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e"))
    short = estimate_recompute_step(m.graph, cm, 1, 1, resume_len=32,
                                    page_size=16)
    long_ = estimate_recompute_step(m.graph, cm, 1, 1, resume_len=512,
                                    page_size=16)
    assert 0.0 < short.step_time < long_.step_time
    with pytest.raises(ValueError, match="resume_len"):
        estimate_recompute_step(m.graph, cm, 1, 1, resume_len=0)
    # prefill_op_cost is the verify shape against an empty cache
    mha = next(
        n for n in m.graph.nodes.values()
        if n.op_type.name == "MULTIHEAD_ATTENTION"
    )
    pc = cm.prefill_op_cost(mha, 1, 64, page_size=16)
    vc = cm.verify_op_cost(mha, 1, kv_len=0, k=63, page_size=16)
    assert pc.forward_time == vc.forward_time


# -- config wiring ------------------------------------------------------------


def test_admission_flags_parse():
    cfg = FFConfig.parse_args(
        ["--admission", "optimistic", "--max-preemptions", "5"]
    )
    sc = ServeConfig.from_config(cfg)
    assert sc.admission == "optimistic"
    assert sc.max_preemptions == 5
    sc = ServeConfig.from_config(FFConfig.parse_args([]))
    assert (sc.admission, sc.max_preemptions) == ("reserve", 3)
