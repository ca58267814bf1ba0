"""Durable serving (flexflow_tpu/serving/journal.py + the front door's
recovery/overload layers): the write-ahead request journal round-trips
and tolerates exactly one torn tail record, a process crash at ANY
iteration phase — plain decode, a chained step in flight, mid-tree-verify —
restarts into token-identical streams with zero duplicated and zero
lost published tokens (the journal-before-publish ordering, fxlint
FX111), idempotent resubmission dedups on client request-keys across
the restart, a journal write failure degrades durability without
killing serving, journal-referenced KV snapshots restore over the
swap-in path when priced under the recompute, the front door sheds by
weighted class share past its admission bound, and the router's
per-replica circuit breaker opens/half-opens/closes without ever
manufacturing an outage. CPU-fast (tier 1) except the int8+prefix
matrix leg.
"""

import asyncio

import numpy as np
import pytest

from tests.test_resilience import _PROMPTS, _baseline, _lm, _requests

from flexflow_tpu import FFConfig
from flexflow_tpu.serving import (
    FaultInjector,
    FaultPlan,
    FrontDoor,
    JournalCorrupt,
    ProcessCrash,
    ReplicaRouter,
    Request,
    RequestJournal,
    RequestStatus,
    ServeConfig,
    build_restore_decider,
    build_scheduler,
    read_journal,
    readmit,
    recover_journal,
)
from flexflow_tpu.serving.journal import FSYNC_MODES
from flexflow_tpu.telemetry import (
    MetricsRegistry,
    register_durability_metrics,
    series_name,
    validate_durability_metrics,
)

pytestmark = pytest.mark.recovery


@pytest.fixture(scope="module")
def lm():
    return _lm()


def _cfg(path=None, **over):
    base = dict(max_seqs=4, max_seq_len=32)
    if path is not None:
        base.update(journal=str(path), journal_fsync="batch")
    base.update(over)
    return ServeConfig(**base)


def _crash_run(lm, path, plan, n=4, max_new=8, **over):
    """Drive a journaled scheduler into its planned ProcessCrash and
    hand back the dead 'process'. The journal is deliberately NOT
    closed — a crashed process never closes anything; batch-mode
    `_sync` already made every committed record durable."""
    inj = FaultInjector(plan)
    sched, _, _ = build_scheduler(lm, _cfg(path, **over), injector=inj)
    for r in _requests(n=n, max_new=max_new):
        sched.submit(r)
    with pytest.raises(ProcessCrash):
        while sched.queue or sched.running:
            sched.step()
    return sched


def _resume(lm, path, state, decider=None, **over):
    """A fresh process: new scheduler over the same journal path,
    re-admit the recovered live set, drain to completion."""
    sched, _, cache = build_scheduler(lm, _cfg(path, **over))
    resubmitted, completed = readmit(sched, state, decider=decider)
    while sched.queue or sched.running:
        sched.step()
    return sched, cache, resubmitted, completed


def _streams(state, resubmitted, completed):
    """Final per-rid streams across both recovery outcomes: terminal
    records replay their recorded tokens, re-admitted requests carry
    committed + resumed tokens in `generated`."""
    out = {int(r): list(t["tokens"]) for r, t in state.terminals.items()}
    for req in resubmitted + completed:
        out[req.rid] = [int(t) for t in req.generated]
    return out


# -- journal round-trip and framing -------------------------------------------


def test_journal_roundtrip_terminals_and_keys(tmp_path):
    path = tmp_path / "j.wal"
    j = RequestJournal(str(path), fsync="commit")
    a = Request(rid=0, prompt=[1, 2], max_new_tokens=4, request_key="k0")
    b = Request(rid=1, prompt=[3], max_new_tokens=4, request_key="k1")
    j.submitted(a)
    j.submitted(b)
    j.note(0, 7)
    j.note(1, 8)
    j.commit_pending(1)
    j.note(0, 9)
    j.finalize(0, RequestStatus.FINISHED, iteration=2)
    j.close()
    records, torn = read_journal(str(path))
    assert torn == 0
    assert [r["type"] for r in records] == [
        "submit", "submit", "commit", "commit", "commit", "terminal",
    ]
    state = recover_journal(str(path))
    assert set(state.live) == {1}
    assert state.live[1].committed == [8]
    assert state.live[1].key == "k1"
    assert state.terminals[0]["status"] == RequestStatus.FINISHED
    # finalize flushed rid 0's still-buffered run before the terminal
    assert state.terminals[0]["tokens"] == [7, 9]
    assert state.key_to_rid == {"k0": 0, "k1": 1}
    assert state.next_rid == 2
    assert state.replayed_tokens == 1


def test_torn_tail_drops_only_the_torn_record(tmp_path):
    path = tmp_path / "torn.wal"
    j = RequestJournal(str(path), fsync="commit")
    j.submitted(Request(rid=0, prompt=[1, 2], max_new_tokens=4,
                        request_key="k0"))
    j.note(0, 7)
    j.note(0, 8)
    j.commit_pending(1)
    j.close()
    with open(path, "ab") as f:
        f.write(b'deadbeef {"half": tru')  # a crash mid-append
    records, torn = read_journal(str(path))
    assert torn == 1
    assert len(records) == 2  # submit + commit both survive intact
    state = recover_journal(str(path))
    assert state.torn == 1
    assert state.live[0].committed == [7, 8]


def test_interior_corruption_raises(tmp_path):
    path = tmp_path / "corrupt.wal"
    j = RequestJournal(str(path), fsync="commit")
    j.submitted(Request(rid=0, prompt=[1], max_new_tokens=4))
    j.note(0, 5)
    j.commit_pending(1)
    j.finalize(0, RequestStatus.FINISHED)
    j.close()
    lines = open(path, "rb").read().splitlines(keepends=True)
    assert len(lines) >= 3
    lines[1] = b"00000000 {not json}\n"  # break an INTERIOR record
    with open(path, "wb") as f:
        f.writelines(lines)
    with pytest.raises(JournalCorrupt, match="interior"):
        read_journal(str(path))


def test_fsync_mode_validation(tmp_path):
    with pytest.raises(ValueError, match="fsync"):
        RequestJournal(str(tmp_path / "x.wal"), fsync="always")
    with pytest.raises(ValueError, match="journal_fsync"):
        ServeConfig(journal_fsync="always")
    with pytest.raises(ValueError, match="journal_snapshot_every"):
        ServeConfig(journal_snapshot_every=-1)


@pytest.mark.parametrize("mode", FSYNC_MODES)
def test_fsync_modes_all_durable_after_graceful_run(lm, tmp_path, mode):
    """All three fsync policies survive a graceful run byte-identically
    — they differ only in what a HOST power loss could lose."""
    path = tmp_path / f"{mode}.wal"
    sched, _, _ = build_scheduler(
        lm, _cfg(path, journal_fsync=mode))
    for r in _requests(max_new=4):
        sched.submit(r)
    sched.run()
    sched.journal.close()
    state = recover_journal(str(path))
    assert not state.live and state.torn == 0
    base = _baseline(lm, max_new=4)
    assert {r: t["tokens"] for r, t in state.terminals.items()} == base
    assert all(
        t["status"] == RequestStatus.FINISHED
        for t in state.terminals.values()
    )


# -- crash-restart: token-identical resume ------------------------------------


@pytest.mark.parametrize(
    "layout,dtype,prefix",
    [
        ("one_page", "fp32", False),
        ("paged", "fp32", False),
        ("paged", "fp32", True),
        ("paged", "int8", False),
        pytest.param("paged", "int8", True, marks=pytest.mark.slow),
    ],
)
def test_crash_restart_token_identical(lm, tmp_path, layout, dtype, prefix):
    """The headline contract: crash at the WORST phase (tokens emitted,
    commit flush not yet run), restart, and every stream resumes
    token-identically — no duplicated tokens, no gaps, nothing lost."""
    over = dict(kv_dtype=dtype, prefix_cache=prefix,
                kv_page_size=8 if layout == "paged" else 32)
    base = _baseline(lm, max_new=8, **over)
    path = tmp_path / "serve.wal"
    sched = _crash_run(
        lm, path, FaultPlan(crash_iters={3: "commit"}), max_new=8, **over)
    assert sched.journal.records_written > 0
    assert not sched.journal.degraded
    state = recover_journal(str(path))
    assert state.torn == 0
    assert state.replayed_tokens > 0
    assert set(state.live) | set(state.terminals) == {0, 1, 2, 3}
    # commit-phase crash: the host saw MORE tokens than the journal —
    # the durable cursor is a strict prefix the restart recomputes past
    for slot, req in sched.running.items():
        rr = state.live[req.rid]
        assert len(rr.committed) < len(req.generated)
        assert rr.committed == [int(t) for t in
                                req.generated[: len(rr.committed)]]
    _, _, resub, comp = _resume(lm, path, state, **over)
    assert _streams(state, resub, comp) == base


@pytest.mark.parametrize(
    "serve_async,durable",
    [
        # the synchronous loop: iteration 1 committed two tokens per
        # request (admission prefill + same-iteration decode)
        pytest.param(False, 8, id="sync"),
        # the default loop: the prefill's token; the first decode step
        # was dispatched in iteration 2 at the earliest and read back later
        pytest.param(True, 4, id="overlapped"),
    ],
)
def test_crash_at_iteration_begin(lm, tmp_path, serve_async, durable):
    """The benign phase: death at the step boundary, before any new
    work — everything journaled survives, nothing was at risk."""
    over = dict(kv_page_size=8, serve_async=serve_async)
    base = _baseline(lm, max_new=8, kv_page_size=8)
    path = tmp_path / "begin.wal"
    _crash_run(lm, path, FaultPlan(crash_iters={2: "begin"}),
               max_new=8, **over)
    state = recover_journal(str(path))
    # all durable at the begin-phase crash
    assert state.replayed_tokens == durable
    _, _, resub, comp = _resume(lm, path, state, **over)
    assert _streams(state, resub, comp) == base


def test_crash_after_torn_append_still_recovers(lm, tmp_path):
    """Crash + torn tail together: the torn record is dropped, every
    intact record folds, and the resume is still exact."""
    over = dict(kv_page_size=8)
    base = _baseline(lm, max_new=8, kv_page_size=8)
    path = tmp_path / "both.wal"
    _crash_run(lm, path, FaultPlan(crash_iters={4: "commit"}),
               max_new=8, **over)
    with open(path, "ab") as f:
        f.write(b"1234abcd {\"type\": \"com")
    state = recover_journal(str(path))
    assert state.torn == 1
    _, _, resub, comp = _resume(lm, path, state, **over)
    assert _streams(state, resub, comp) == base


def test_crash_with_a_chained_step_in_flight_recovers_token_identical(
    lm, tmp_path
):
    """A commit-phase crash of the default loop dies with a chained
    decode step dispatched and not read back: the rows it wrote and the
    token it sampled were never the host's, the journal knows nothing of
    them, and the restart recomputes them from the last durable cursor."""
    over = dict(kv_page_size=8)
    base = _baseline(lm, max_new=12, kv_page_size=8)
    path = tmp_path / "chained.wal"
    sched = _crash_run(
        lm, path, FaultPlan(crash_iters={4: "commit"}), max_new=12, **over)
    assert sched.stats.decode_steps_chained > 0
    assert [s.kind for s in sched._inflight] == ["decode"]
    records, _ = read_journal(str(path))
    assert any(r["type"] == "commit" for r in records)
    state = recover_journal(str(path))
    assert state.replayed_tokens > 0
    _, _, resub, comp = _resume(lm, path, state, **over)
    assert _streams(state, resub, comp) == base


def test_crash_mid_tree_verify_recovers_token_identical(lm, tmp_path):
    """Same contract through the token-tree path: a verify round's
    accepted run journals as one commit record, and a crash between
    emit and commit flush recomputes it exactly."""
    over = dict(kv_page_size=8,
                spec_draft="ngram", spec_k=3, spec_branch=2)
    base = _baseline(lm, max_new=12, kv_page_size=8,
                     spec_draft="ngram", spec_k=3, spec_branch=2)
    path = tmp_path / "tree.wal"
    sched = _crash_run(
        lm, path, FaultPlan(crash_iters={3: "commit"}), max_new=12, **over)
    assert sched.stats.tree_verify_steps > 0
    state = recover_journal(str(path))
    assert state.replayed_tokens > 0
    _, _, resub, comp = _resume(lm, path, state, **over)
    assert _streams(state, resub, comp) == base


def test_double_crash_recovers_exactly(lm, tmp_path):
    """Re-admitted requests journal fresh submit records CARRYING their
    committed run, so a second crash folds to the full cursor instead
    of resetting it — the recovery is idempotent under repetition."""
    over = dict(kv_page_size=8)
    base = _baseline(lm, max_new=8, kv_page_size=8)
    path = tmp_path / "twice.wal"
    _crash_run(lm, path, FaultPlan(crash_iters={3: "commit"}),
               max_new=8, **over)
    state1 = recover_journal(str(path))
    # second process: resume, then die again
    inj = FaultInjector(FaultPlan(crash_iters={2: "begin"}))
    sched2, _, _ = build_scheduler(lm, _cfg(path, **over), injector=inj)
    readmit(sched2, state1)
    with pytest.raises(ProcessCrash):
        while sched2.queue or sched2.running:
            sched2.step()
    state2 = recover_journal(str(path))
    for rid, rr in state2.live.items():
        # the second fold kept the first recovery's cursor and extended it
        assert len(rr.committed) > len(state1.live[rid].committed)
        assert rr.committed[: len(state1.live[rid].committed)] == (
            state1.live[rid].committed
        )
    _, _, resub, comp = _resume(lm, path, state2, **over)
    assert _streams(state2, resub, comp) == base


def test_journal_write_failure_degrades_not_kills(lm, tmp_path):
    """An injected journal write failure flips the journal to degraded
    (availability over durability) while serving continues untouched —
    every stream still finishes token-identical to the baseline."""
    path = tmp_path / "fail.wal"
    # the synchronous loop commits tokens in iteration 2; the default
    # loop dispatches its first decode step there and commits it in 3.
    # The first failed write degrades the journal: one injection each
    inj = FaultInjector(FaultPlan(journal_fail_iters=(2, 3)))
    sched, _, _ = build_scheduler(
        lm, _cfg(path, kv_page_size=8), injector=inj)
    for r in _requests(max_new=6):
        sched.submit(r)
    done = sched.run()
    assert inj.injected["journal_fail"] == 1
    assert sched.journal.degraded
    assert "injected" in sched.journal.degraded_reason
    base = _baseline(lm, max_new=6, kv_page_size=8)
    assert {r.rid: r.generated for r in done} == base
    assert all(r.status == RequestStatus.FINISHED for r in done)
    # what made it to disk before the failure still parses cleanly
    state = recover_journal(str(path))
    assert state.torn == 0


# -- KV snapshot restore ------------------------------------------------------


@pytest.mark.parametrize("decider_mode", ["always", "never", "priced"])
def test_snapshot_restore_vs_recompute(lm, tmp_path, decider_mode):
    """`journal_snapshot_every` journals KV snapshots; recovery
    restores one over the swap-in path when the decider approves
    (None = always), and falls back to recompute when it refuses —
    token-identical either way."""
    over = dict(kv_page_size=8,
                journal_snapshot_every=2)
    base = _baseline(lm, max_new=8, kv_page_size=8)
    path = tmp_path / f"snap-{decider_mode}.wal"
    _crash_run(lm, path, FaultPlan(crash_iters={5: "commit"}),
               max_new=8, **over)
    state = recover_journal(str(path))
    for rr in state.live.values():
        assert rr.snapshot is not None
        # snapshots ride AFTER the iteration's commit flush, so the
        # latest one always matches the durable cursor exactly
        assert int(rr.snapshot["gen_len"]) == len(rr.committed)
    decider = {
        "always": None,
        "never": (lambda cache, rec, resume_len: False),
        "priced": build_restore_decider(lm),
    }[decider_mode]
    sched, _, cache = build_scheduler(lm, _cfg(path, **over))
    resub, comp = readmit(sched, state, decider=decider)
    # the handle is attached at readmit and consumed by admission
    handles = [r for r in resub if r.swap_handle is not None]
    if decider_mode == "always":
        assert len(handles) == len(resub) == 4
    elif decider_mode == "never":
        assert not handles
    while sched.queue or sched.running:
        sched.step()
    if decider_mode == "always":
        assert getattr(cache, "swap_ins", 0) >= 4  # restored, not recomputed
    elif decider_mode == "never":
        assert getattr(cache, "swap_ins", 0) == 0
    assert _streams(state, resub, comp) == base


# -- front door: recovery adoption, dedup, shedding ---------------------------


def test_front_door_adopts_recovery_state(lm, tmp_path):
    """A fresh FrontDoor built with the RecoveryState replays every
    committed token and resumes the live set — the client-visible
    stream across the crash is exactly the fault-free one."""
    over = dict(kv_page_size=8)
    base = _baseline(lm, max_new=8, kv_page_size=8)
    path = tmp_path / "door.wal"
    _crash_run(lm, path, FaultPlan(crash_iters={3: "commit"}),
               max_new=8, **over)
    state = recover_journal(str(path))

    async def main():
        sched, _, _ = build_scheduler(lm, _cfg(path, **over))
        door = FrontDoor(sched, recovery=state)
        out = {}

        async def consume(rid):
            toks, status = [], None
            async for ev in door.stream(rid):
                if ev.kind == "token":
                    toks.append(ev.token)
                else:
                    status = ev.status
            out[rid] = (toks, status)

        consumers = [
            asyncio.ensure_future(consume(r)) for r in sorted(state.live)
        ]
        await door.drain()
        await asyncio.gather(*consumers)
        return door, out

    door, out = asyncio.run(main())
    assert door.recovered_requests == 4
    assert door.replayed_tokens == state.replayed_tokens > 0
    assert {rid: toks for rid, (toks, _) in out.items()} == base
    assert all(s == RequestStatus.FINISHED for _, s in out.values())


def test_front_door_request_key_dedup_and_replay(lm):
    """Idempotent resubmission: three submits with one request_key are
    ONE engine request; a reconnect after the consumer detached replays
    the full committed stream from token 0, exactly once."""

    async def main():
        sched, _, _ = build_scheduler(
            lm, _cfg(kv_page_size=8))
        door = FrontDoor(sched)
        rid = await door.submit([1, 2, 3], max_new_tokens=6,
                                request_key="alpha")
        dup = await door.submit([1, 2, 3], max_new_tokens=6,
                                request_key="alpha")
        assert dup == rid
        toks = []
        async for ev in door.stream(rid):
            if ev.kind == "token":
                toks.append(ev.token)
        # the consumer detached; a reconnect re-attaches and replays
        again = await door.submit([1, 2, 3], max_new_tokens=6,
                                  request_key="alpha")
        assert again == rid
        replay, status = [], None
        async for ev in door.stream(rid):
            if ev.kind == "token":
                replay.append(ev.token)
            else:
                status = ev.status
        return sched, toks, replay, status

    sched, toks, replay, status = asyncio.run(main())
    assert sched.stats.submitted_requests == 1
    assert len(toks) == 6
    assert replay == toks
    assert status == RequestStatus.FINISHED


def test_request_key_dedup_survives_restart(lm, tmp_path):
    """A retried submit whose key the JOURNAL remembers as finished
    replays the recorded verdict without touching the fresh engine."""
    over = dict(kv_page_size=8)
    path = tmp_path / "dedup.wal"
    sched, _, _ = build_scheduler(lm, _cfg(path, **over))
    reqs = [
        Request(rid=i, prompt=list(_PROMPTS[i]), max_new_tokens=6,
                request_key=f"key-{i}")
        for i in range(4)
    ]
    for r in reqs:
        sched.submit(r)
    done = {r.rid: list(r.generated) for r in sched.run()}
    sched.journal.close()
    state = recover_journal(str(path))
    assert not state.live and len(state.terminals) == 4

    async def main():
        sched2, _, _ = build_scheduler(lm, _cfg(path, **over))
        door = FrontDoor(sched2, recovery=state)
        rid = await door.submit([9, 9], max_new_tokens=6,
                                request_key="key-2")
        toks, status = [], None
        async for ev in door.stream(rid):
            if ev.kind == "token":
                toks.append(ev.token)
            else:
                status = ev.status
        return sched2, rid, toks, status

    sched2, rid, toks, status = asyncio.run(main())
    assert rid == 2
    assert toks == done[2]
    assert status == RequestStatus.FINISHED
    assert sched2.stats.submitted_requests == 0  # engine never touched


def test_front_door_sheds_by_class_share(lm, tmp_path):
    """Past the admission bound the door sheds the class over its
    weighted share (bronze) while the under-share class (gold) keeps
    admitting — overload degrades in priority order, and the shed
    request never reaches the engine or the journal."""
    path = tmp_path / "shed.wal"
    serve = _cfg(path, kv_page_size=8,
                 classes="gold:4,bronze:1",
                 metrics_out=str(tmp_path / "m.prom"))

    async def main():
        sched, _, _ = build_scheduler(lm, serve)
        door = FrontDoor(sched, max_pending=5)
        rids = []
        for i, cls in enumerate(
            ["gold", "gold", "gold", "bronze", "bronze"]
        ):
            rids.append(await door.submit(
                list(_PROMPTS[i % len(_PROMPTS)]), max_new_tokens=4,
                priority_class=cls))
        # backlog at the bound: bronze (share 1, pending 2) sheds...
        shed_rid = await door.submit([1, 2], max_new_tokens=4,
                                     priority_class="bronze")
        events = []
        async for ev in door.stream(shed_rid):
            events.append(ev)
        # ...while gold (share 4, pending 3) still admits
        gold_rid = await door.submit([3, 4], max_new_tokens=4,
                                     priority_class="gold")
        await door.drain()
        statuses = {
            r: door.request(r).status for r in rids + [gold_rid]
        }
        return sched, door, events, statuses

    sched, door, events, statuses = asyncio.run(main())
    assert len(events) == 1 and events[0].kind == "done"
    assert events[0].status == "shed"
    assert events[0].retry_after_s == pytest.approx(0.05)
    assert door.shed_total == {"bronze": 1}
    assert all(s == RequestStatus.FINISHED for s in statuses.values())
    # the shed request never reached the engine or the journal
    assert sched.stats.submitted_requests == 6
    sched.journal.close()
    state = recover_journal(str(serve.journal))
    assert len(state.terminals) == 6
    # telemetry: the pre-registered per-class counters distinguish
    # "gold shed zero" from "gold not instrumented"
    sample = sched.telemetry.registry.sample()
    assert sample[series_name("serve_shed_total", {"class": "bronze"})] == 1
    assert sample[series_name("serve_shed_total", {"class": "gold"})] == 0
    validate_durability_metrics(sample, require_all=True)


# -- router: circuit breaker, cancel-during-evacuation ------------------------


def test_circuit_breaker_state_machine(lm, tmp_path):
    """closed -> open after `breaker_threshold` consecutive failed
    probes (placements excluded), open -> half_open after the cooldown,
    a failed half-open trial reopens immediately, a healthy one
    closes."""
    serve = _cfg(kv_page_size=8,
                 breaker_threshold=2, breaker_cooldown=3,
                 metrics_out=str(tmp_path / "m.prom"))
    flaky = {"healthy": False}
    router = ReplicaRouter(
        [lm, lm], serve,
        health_probe=lambda rep: rep.idx != 0 or flaky["healthy"])
    rep0 = router.replicas[0]
    router.step()
    assert rep0.breaker_state == "closed" and rep0.breaker_failures == 1
    router.step()
    assert rep0.breaker_state == "open"
    assert router.breaker_opens == 1
    # open replicas take no placements
    router.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    assert router._owner[0].idx == 1
    for _ in range(3):  # cooldown expires at iteration 5
        router.step()
    assert rep0.breaker_state == "half_open"
    router.step()  # failed half-open trial: straight back to open
    assert rep0.breaker_state == "open"
    assert router.breaker_opens == 2
    flaky["healthy"] = True
    for _ in range(3):
        router.step()
    assert rep0.breaker_state == "half_open"
    router.step()
    assert rep0.breaker_state == "closed"
    sample = router.telemetry.registry.sample()
    assert sample[series_name("serve_breaker_open_total",
                              {"replica": "0"})] == 2
    done = router.run()
    assert [r.status for r in done] == [RequestStatus.FINISHED]


def test_breaker_never_manufactures_outage(lm):
    """With every alive replica open, the alive set routes anyway —
    availability over protection."""
    serve = _cfg(kv_page_size=8, breaker_threshold=1)
    router = ReplicaRouter([lm], serve, health_probe=lambda rep: False)
    router.step()
    assert router.replicas[0].breaker_state == "open"
    assert router.submit(
        Request(rid=0, prompt=[1, 2], max_new_tokens=4))
    assert router._owner[0].idx == 0
    done = router.run()
    assert [r.status for r in done] == [RequestStatus.FINISHED]


def test_cancel_during_evacuation_window(lm):
    """The satellite regression: a cancel racing `kill_replica` while
    its request sits between schedulers must LAND (finalized CANCELLED
    at the router), not silently fall into the ownership gap."""
    serve = _cfg(kv_page_size=8)
    router = ReplicaRouter([lm, lm], serve)
    for r in _requests(n=4, max_new=8):
        router.submit(r)
    mine = [rid for rid, rep in router._owner.items() if rep.idx == 0]
    assert len(mine) >= 2  # headroom tie-break alternates placements
    router.step()  # get the batch running before the kill
    orig_route = router.route
    fired = {}

    def route_with_racing_cancel(req):
        if router._evacuating and not fired:
            victims = [r for r in router._evacuating if r != req.rid]
            assert victims
            fired["rid"] = victims[0]
            # the client disconnect, landing mid-evacuation
            assert router.cancel(victims[0]) is True
        return orig_route(req)

    router.route = route_with_racing_cancel
    moved = router.kill_replica(0)
    victim = fired["rid"]
    assert victim in [r.rid for r in moved]
    vreq = router.requests[victim]
    assert vreq.status == RequestStatus.CANCELLED
    assert victim not in router._owner  # no scheduler owns it
    router.route = orig_route
    done = {r.rid: r for r in router.run()}
    assert set(done) == {0, 1, 2, 3}  # zero lost requests
    assert done[victim].status == RequestStatus.CANCELLED
    base = _baseline(lm, max_new=8, kv_page_size=8)
    for rid, req in done.items():
        if rid != victim:
            assert req.status == RequestStatus.FINISHED
            assert list(req.generated) == base[rid]


# -- telemetry catalog and config plumbing ------------------------------------


def test_durability_metrics_catalog_and_validation():
    reg = MetricsRegistry()
    register_durability_metrics(
        reg, classes=("gold", "bronze"), replicas=(0, 1))
    sample = reg.sample()
    # a fresh server exposes explicit zeros for the whole catalog
    assert validate_durability_metrics(sample, require_all=True) == []
    assert sample["serve_recovery_total"] == 0
    assert sample[series_name("serve_shed_total", {"class": "gold"})] == 0
    assert sample[series_name("serve_breaker_open_total",
                              {"replica": "1"})] == 0
    bad_label = {series_name("serve_recovery_total", {"replica": "0"}): 1}
    errs = validate_durability_metrics(bad_label, errors="return")
    assert errs and "unlabelled" in errs[0]
    errs = validate_durability_metrics(
        {"serve_journal_bytes": -3}, errors="return")
    assert errs and "negative" in errs[0]
    errs = validate_durability_metrics({}, errors="return",
                                       require_all=True)
    assert any("missing" in e for e in errs)
    wrong_key = {series_name("serve_shed_total", {"tenant": "x"}): 1}
    errs = validate_durability_metrics(wrong_key, errors="return")
    assert errs and "class" in errs[0]


def test_journal_cli_flags_flow_into_serve_config(tmp_path):
    cfg = FFConfig.parse_args([
        "--kv-layout", "paged",
        "--journal", str(tmp_path / "serve.wal"),
        "--journal-fsync", "commit",
        "--journal-snapshot-every", "4",
        "--door-max-pending", "8",
        "--breaker-threshold", "3",
        "--breaker-cooldown", "5",
    ])
    serve = ServeConfig.from_config(cfg)
    assert serve.journal.endswith("serve.wal")
    assert serve.journal_fsync == "commit"
    assert serve.journal_snapshot_every == 4
    assert serve.door_max_pending == 8
    assert serve.breaker_threshold == 3
    assert serve.breaker_cooldown == 5
