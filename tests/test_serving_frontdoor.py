"""Disaggregated serving front door (serving/frontend): routed and
prefill→decode-disaggregated streams are token-identical to the
monolithic engine (the logit-identity reduces to the staged-row
bit-exactness proven at the cache level, extended here ACROSS engine
boundaries via export_swap/import_swap), the prefix-affinity router
co-locates shared-prefix tenants and drains a killed replica with
zero lost requests, the async front door streams tokens as they
commit and maps client disconnect to cancellation, deadlines reap in
every phase (router queue, prefill tier, post-handoff decode), and
the cost-aware prefix eviction policy orders by recompute price where
LRU orders by age. Sync/fp32 legs are tier 1; the async × int8 matrix
legs are tier 2 (slow)."""

import asyncio
import json
import os
import socket
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.serving import (
    DisaggregatedPipeline,
    FaultInjector,
    FaultPlan,
    FrontDoor,
    KVCacheSpec,
    PagedKVCache,
    PrefillOnlyScheduler,
    ReplicaRouter,
    Request,
    RequestStatus,
    ServeConfig,
    build_scheduler,
)

from tests.test_paged_kv import _lm
from tests.test_pressure import _fill_slot, _spec

pytestmark = pytest.mark.serving

VOCAB = 50


@pytest.fixture(scope="module")
def lm():
    # one compiled model serves every engine in this module: replicas
    # and tiers built from the same weights are exactly the
    # "identically built, weight-identical" posture the router assumes
    return _lm()


def _serve(**over):
    base = dict(
        max_seqs=4,
        max_seq_len=32,
        kv_page_size=4,
        kv_pages=48,
        token_budget=8,
        chunk_size=8,
        prefix_cache=True,
        decode_kernel="dense",
    )
    base.update(over)
    return ServeConfig(**base)


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [
        [int(t) for t in rng.integers(1, VOCAB, size=n)]
        for n in (9, 5, 12, 7)
    ]


def _reqs(prompts, max_new=6, **over):
    return [
        Request(rid=i, prompt=list(p), max_new_tokens=max_new, **over)
        for i, p in enumerate(prompts)
    ]


def _tokens(finished):
    return {r.rid: list(r.generated) for r in finished}


def _reference(lm, serve, prompts, max_new=6):
    sched, _, _ = build_scheduler(lm, serve)
    return _tokens(sched.run(_reqs(prompts, max_new)))


# -- identity: routed and disaggregated vs monolithic -------------------------


MATRIX = [
    pytest.param(False, "fp32", True, id="sync-fp32-prefix"),
    pytest.param(False, "fp32", False, id="sync-fp32-noprefix"),
    pytest.param(
        False, "int8", True, id="sync-int8-prefix", marks=pytest.mark.slow
    ),
    pytest.param(
        True, "fp32", True, id="async-fp32-prefix", marks=pytest.mark.slow
    ),
    pytest.param(
        True, "int8", True, id="async-int8-prefix", marks=pytest.mark.slow
    ),
    pytest.param(
        True, "int8", False, id="async-int8-noprefix",
        marks=pytest.mark.slow,
    ),
]


@pytest.mark.parametrize("serve_async,kv_dtype,prefix", MATRIX)
def test_disaggregated_streams_token_identical(
    lm, serve_async, kv_dtype, prefix
):
    """Prefill→decode handoff end to end: every stream's tokens match
    the monolithic engine bit for bit, and every multi-token request
    actually crossed the tier boundary (handoffs counted)."""
    serve = _serve(
        serve_async=serve_async, kv_dtype=kv_dtype, prefix_cache=prefix
    )
    prompts = _prompts()
    ref = _reference(lm, serve, prompts)
    pipe = DisaggregatedPipeline(lm, lm, serve)
    out = _tokens(pipe.run(_reqs(prompts)))
    assert out == ref
    assert pipe.handoffs == len(prompts)
    assert pipe.handoff_fallbacks == 0


@pytest.mark.parametrize("serve_async,kv_dtype,prefix", MATRIX)
def test_routed_streams_token_identical(lm, serve_async, kv_dtype, prefix):
    serve = _serve(
        serve_async=serve_async, kv_dtype=kv_dtype, prefix_cache=prefix
    )
    prompts = _prompts()
    ref = _reference(lm, serve, prompts)
    router = ReplicaRouter([lm, lm], serve)
    out = _tokens(router.run(_reqs(prompts)))
    assert out == ref


def test_handoff_ttft_is_prefill_tier_time(lm):
    """The first token is emitted by the prefill tier and survives the
    decode-tier resubmission: TTFT stamps once, submit_time is the
    client's original clock, and the generated stream never resets."""
    serve = _serve()
    pipe = DisaggregatedPipeline(lm, lm, serve)
    done = pipe.run(_reqs(_prompts()[:2]))
    for req in done:
        assert req.status == RequestStatus.FINISHED
        assert req.first_token_time > req.submit_time > 0.0
        assert req.ttft_s > 0.0
        events = [e[1] for e in req.events]
        assert "handoff" in events
        # first_token logged before the handoff: TTFT belongs to the
        # prefill tier
        assert events.index("first_token") < events.index("handoff")


# -- bit-exact handoff staging (extends test_pressure across engines) --------


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_export_import_restores_rows_bit_exact(kv_dtype):
    """The cross-engine record carries the COMMITTED rows (int8 scale
    slivers included) bit-exactly: export from one cache, import into
    a DIFFERENT cache, restore, and compare every row — the
    logit-identity of a disaggregated stream reduces to this."""
    src = PagedKVCache(_spec(kv_dtype=kv_dtype), jnp.float32)
    dst = PagedKVCache(_spec(kv_dtype=kv_dtype), jnp.float32)
    rng = np.random.default_rng(7)
    slot = src.alloc(10, 20)
    src.lengths[slot] = 10
    pages, expect = _fill_slot(src, slot, rng)
    h = src.swap_out(slot)
    rec = src.export_swap(h)
    assert src._swap_bytes_held == 0  # export surrendered the bytes

    # the source handle is DEAD: a second export is the FX108 bug
    with pytest.raises(KeyError):
        src.export_swap(h)

    new_handle = dst.import_swap(rec)
    assert new_handle is not None
    restored = dst.swap_in(new_handle, total_len=20)
    assert restored is not None
    assert int(dst.lengths[restored]) == 10
    sent = dst.spec.num_pages
    new_pages = [int(p) for p in dst.block_tables[restored] if p != sent]
    assert len(new_pages) == len(pages)
    idx = np.asarray(new_pages, dtype=np.int32)
    for g in dst.spec.layer_guids:
        got_k = np.asarray(dst.k[g])[idx]
        got_v = np.asarray(dst.v[g])[idx]
        np.testing.assert_array_equal(got_k, expect[g][0])
        np.testing.assert_array_equal(got_v, expect[g][1])
        if dst.quantized:
            np.testing.assert_array_equal(
                np.asarray(dst.k_scale[g])[idx], expect[g][2]
            )
            np.testing.assert_array_equal(
                np.asarray(dst.v_scale[g])[idx], expect[g][3]
            )
    dst.check_invariants()
    src.check_invariants()


def test_import_swap_rejects_geometry_mismatch():
    src = PagedKVCache(_spec(), jnp.float32)
    dst = PagedKVCache(_spec(num_heads=4), jnp.float32)
    rng = np.random.default_rng(1)
    slot = src.alloc(8, 12)
    src.lengths[slot] = 8
    _fill_slot(src, slot, rng)
    rec = src.export_swap(src.swap_out(slot))
    with pytest.raises(ValueError, match="geometry"):
        dst.import_swap(rec)


def test_import_swap_respects_budget():
    src = PagedKVCache(_spec(), jnp.float32)
    dst = PagedKVCache(_spec(), jnp.float32, swap_bytes_budget=1)
    rng = np.random.default_rng(2)
    slot = src.alloc(8, 12)
    src.lengths[slot] = 8
    _fill_slot(src, slot, rng)
    rec = src.export_swap(src.swap_out(slot))
    assert dst.import_swap(rec) is None  # refusal, not an error
    dst.check_invariants()


# -- prefix-affinity routing --------------------------------------------------


def test_router_prefers_prefix_affinity(lm):
    """A tenant sharing a served prompt's prefix lands on the replica
    whose cache already holds the published pages — even when the
    other replica has more headroom."""
    serve = _serve()
    router = ReplicaRouter([lm, lm], serve)
    shared = list(range(1, 9))  # 2 full pages
    first = Request(rid=0, prompt=shared + [10], max_new_tokens=2)
    router.submit(first)
    while router.work_pending():
        router.step()
    owner = router._owner[0].idx
    # the served prefix is published on `owner`'s cache only
    follow = Request(rid=1, prompt=shared + [11, 12], max_new_tokens=2)
    target = router.route(follow)
    assert target.idx == owner


def test_router_no_affinity_uses_headroom(lm):
    """Without a prefix hit the router balances by headroom: two
    no-affinity requests split across idle identical replicas."""
    serve = _serve()
    router = ReplicaRouter([lm, lm], serve)
    a = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4)
    b = Request(rid=1, prompt=[7, 8, 9], max_new_tokens=4)
    router.submit(a)
    router.submit(b)
    assert router._owner[0].idx != router._owner[1].idx
    while router.work_pending():
        router.step()
    assert all(r.status == RequestStatus.FINISHED for r in (a, b))


def test_replica_kill_zero_lost_requests(lm):
    """The chaos leg's contract at test scale: a replica dies
    mid-stream (scheduled through the injector), its streams re-route
    and COMPLETE on the survivor, nothing is lost, and the drain is
    visible in replica-labelled metrics."""
    serve = _serve(telemetry=True, prefix_cache=False)
    injector = FaultInjector(
        FaultPlan(replica_down_iters={3: 0}), seed=0
    )
    router = ReplicaRouter([lm, lm], serve, injector=injector)
    reqs = _reqs(_prompts(), max_new=6)
    for r in reqs:
        router.submit(r)
    while router.work_pending():
        router.step()
    assert not router.replicas[0].alive
    assert injector.injected["replica_down"] == 1
    # zero lost: every submitted stream reached a terminal FINISHED
    done = _tokens(router.finished)
    assert sorted(done) == [r.rid for r in reqs]
    assert all(len(t) == 6 for t in done.values())
    assert all(r.status == RequestStatus.FINISHED for r in reqs)
    # the re-route is visible: counter per destination replica
    assert router.rerouted > 0
    text = router.telemetry.registry.render_prometheus()
    assert 'serve_router_replica_down_total{replica="0"}' in text
    assert 'serve_router_reroute_total{replica="1"}' in text


def test_router_refuses_killing_last_replica(lm):
    serve = _serve()
    router = ReplicaRouter([lm], serve)
    assert router.kill_replica(0) == []
    assert router.replicas[0].alive


# -- front door: streaming, disconnect, deadlines ------------------------------


def test_frontdoor_streams_token_identical(lm):
    serve = _serve()
    prompts = _prompts()
    ref = _reference(lm, serve, prompts)

    async def main():
        sched, _, _ = build_scheduler(lm, serve)
        door = FrontDoor(sched)
        rids = [await door.submit(p, max_new_tokens=6) for p in prompts]
        out = {}

        async def consume(rid):
            toks = []
            status = None
            async for ev in door.stream(rid):
                if ev.kind == "token":
                    toks.append(ev.token)
                else:
                    status = ev.status
            out[rid] = (toks, status)

        await asyncio.gather(*(consume(r) for r in rids))
        return out

    out = asyncio.run(main())
    assert {rid: t for rid, (t, _) in out.items()} == ref
    assert all(s == RequestStatus.FINISHED for (_, s) in out.values())


@pytest.mark.parametrize("serve_async", [False, True])
def test_client_disconnect_cancels_request(lm, serve_async):
    """A consumer abandoning its stream mid-flight cancels the routed
    request: the deferred-cancel semantics retire it (CANCELLED), its
    slot frees, and the other stream completes untouched."""
    serve = _serve(serve_async=serve_async)

    async def main():
        sched, _, cache = build_scheduler(lm, serve)
        door = FrontDoor(sched)
        victim = await door.submit(_prompts()[2], max_new_tokens=8)
        bystander = await door.submit(_prompts()[0], max_new_tokens=8)
        got = []

        async def half_consume():
            stream = door.stream(victim)
            async for ev in stream:
                if ev.kind == "token":
                    got.append(ev.token)
                if len(got) >= 2:
                    break  # client walks away mid-stream
            await stream.aclose()  # the disconnect, made deterministic

        async def consume_all():
            toks = []
            async for ev in door.stream(bystander):
                if ev.kind == "token":
                    toks.append(ev.token)
            return toks

        _, full = await asyncio.gather(half_consume(), consume_all())
        await door.drain()
        return sched, cache, door.request(victim), full

    sched, cache, vreq, full = asyncio.run(main())
    assert vreq.status == RequestStatus.CANCELLED
    assert vreq.slot is None  # slot and pages freed at finalize
    assert len(full) == 8  # bystander unaffected
    ref = _reference(lm, serve, [_prompts()[0]], max_new=8)
    assert full == ref[0]


def test_deadline_reaps_in_every_phase(lm):
    """A deadline set at submit fires wherever the request happens to
    be: queued behind a full router replica, mid-chunk in the prefill
    tier, and decoding post-handoff."""
    serve = _serve()

    # (a) queued at the router: fill one replica's slots, then submit
    # a doomed request with a deadline too short to outlive the queue
    router = ReplicaRouter([lm], serve)
    fill = _reqs(_prompts(), max_new=10)
    for r in fill:
        router.submit(r)
    doomed = Request(
        rid=99, prompt=_prompts()[0], max_new_tokens=4, deadline_s=1e-4
    )
    router.submit(doomed)
    while router.work_pending():
        router.step()
    assert doomed.status == RequestStatus.TIMED_OUT
    assert all(r.status == RequestStatus.FINISHED for r in fill)

    # (b) prefilling in the prefill tier: the deadline expires while
    # chunks are still streaming in (long prompt, tiny budget)
    pipe = DisaggregatedPipeline(lm, lm, serve)
    slow = Request(
        rid=0, prompt=_prompts()[2], max_new_tokens=4, deadline_s=1e-6
    )
    pipe.submit(slow)
    while pipe.work_pending():
        pipe.step()
    assert slow.status == RequestStatus.TIMED_OUT
    assert not slow.generated or "handoff" not in [
        e[1] for e in slow.events
    ]

    # (c) decoding post-handoff: generous enough to cross the tiers,
    # too short for the full decode
    pipe2 = DisaggregatedPipeline(lm, lm, serve)
    probe = Request(rid=0, prompt=_prompts()[1], max_new_tokens=8)
    pipe2.submit(probe)
    # step until the handoff lands, then impose an already-expired
    # deadline — the decode tier's reaper must honor it
    while pipe2.prefill_sched._work_pending() or not (
        pipe2.decode_sched._by_rid
    ):
        pipe2.step()
    probe.deadline_s = 1e-6
    while pipe2.work_pending():
        pipe2.step()
    assert probe.status == RequestStatus.TIMED_OUT
    assert "handoff" in [e[1] for e in probe.events]


# -- front door: how many iterations an arrival waits --------------------------


class _SteppedClock:
    """A backend whose `step()` records its index and HOLDS the loop:
    the event loop's clock (`loop.time`, patched in by `_run_stepped`)
    reads the number of steps run, so a timer set at 2.5 expires
    during iteration 2 whatever the machine's speed. A request is
    served one token a step; `submitted_at` is the number of
    iterations that had run when the request reached the backend."""

    def __init__(self, on_step=None, telemetry=None):
        self.iterations = 0
        self.submitted_at = {}
        self.on_step = on_step
        self.telemetry = telemetry
        self._live = []

    def submit(self, req):
        self.submitted_at[req.rid] = self.iterations
        req.status = RequestStatus.RUNNING
        self._live.append(req)

    def cancel(self, rid):
        return False

    def work_pending(self):
        return bool(self._live)

    def step(self):
        if self.on_step is not None:
            self.on_step(self.iterations)
        for req in list(self._live):
            req.generated.append(1)
            if len(req.generated) >= req.max_new_tokens:
                req.status = RequestStatus.FINISHED
                self._live.remove(req)
        self.iterations += 1


def _run_stepped(backend, main):
    """Run `main(door)` on a loop whose clock is the backend's count of
    iterations."""

    async def run():
        asyncio.get_running_loop().time = lambda: float(backend.iterations)
        return await main(FrontDoor(backend))

    return asyncio.run(run())


async def _open_loop_arrival(door, due, max_new_tokens=2):
    """One request started the way the benchmark's generator starts
    one: a task sleeping on a timer wakes, starts a client task, and
    the client awaits `door.submit`."""
    got = {}

    async def client():
        got["rid"] = await door.submit([1], max_new_tokens=max_new_tokens)

    await asyncio.sleep(due)
    await asyncio.ensure_future(client())
    return got["rid"]


@pytest.mark.parametrize("iteration", [0, 2, 5])
def test_timer_arrival_waits_for_the_step_in_progress_only(iteration):
    """A request whose timer expires during iteration i is in the
    backend before iteration i + 1 begins. (A pump that yields one
    pass between two steps submits it before iteration i + 4: every
    hop of the arrival's chain queues behind the pump's resumption.)"""
    backend = _SteppedClock()

    async def main(door):
        arrival = asyncio.ensure_future(
            _open_loop_arrival(door, iteration + 0.5)
        )
        await door.submit([1], max_new_tokens=12)  # keeps a pump running
        rid = await arrival
        await door.drain()
        return rid

    rid = _run_stepped(backend, main)
    assert backend.submitted_at[rid] == iteration + 1
    assert backend.iterations == 12


def test_tcp_arrival_waits_for_the_step_in_progress_only():
    """The same through `serve_tcp`: a submit line written to a
    loopback socket during iteration i (socket readable -> the
    connection's handler wakes -> `door.submit`) is in the backend
    before iteration i + 1 begins (one pass between steps: i + 3)."""
    from flexflow_tpu.serving import serve_tcp

    wire = {}

    def on_step(i):
        if i == 2:
            wire["raw"].sendall(
                json.dumps(
                    {"op": "submit", "prompt": [1], "max_new_tokens": 2}
                ).encode() + b"\n"
            )
            time.sleep(0.002)  # hold the loop: the line is delivered

    backend = _SteppedClock(on_step)

    async def main():
        server = await serve_tcp(backend)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        # a blocking handle on the client's end, for `step()` to write
        wire["raw"] = socket.socket(
            fileno=os.dup(writer.get_extra_info("socket").fileno())
        )
        writer.write(
            json.dumps(
                {"op": "submit", "prompt": [1], "max_new_tokens": 12}
            ).encode() + b"\n"
        )
        await writer.drain()
        events = []
        while sum(e["event"] == "done" for e in events) < 2:
            events.append(json.loads(await reader.readline()))
        wire["raw"].close()
        writer.close()
        server.close()
        return events

    events = asyncio.run(main())
    assert backend.submitted_at == {0: 0, 1: 3}
    tokens = [e["rid"] for e in events if e["event"] == "token"]
    assert tokens.count(0) == 12 and tokens.count(1) == 2
    assert [e["status"] for e in events if e["event"] == "done"] == [
        "finished", "finished",
    ]


def test_door_counts_submissions_by_pass():
    """`submits_by_pass` / `serve_door_submits_total{pass}`: every
    submission handed to the backend is counted once, "idle" when no
    iteration was running, else by the pass of the loop since the last
    `step()`; the open loop's arrivals land in passes 2 and later,
    where a pump yielding one pass could not have served them."""
    from flexflow_tpu.telemetry.registry import MetricsRegistry, series_name

    registry = MetricsRegistry()
    backend = _SteppedClock(
        telemetry=types.SimpleNamespace(enabled=True, registry=registry)
    )

    async def main(door):
        arrivals = [
            asyncio.ensure_future(_open_loop_arrival(door, due))
            for due in (1.5, 1.6, 4.5, 7.5)
        ]
        await door.submit([1], max_new_tokens=10)
        # a client already awake submits in the FIRST pass after a step
        async for ev in door.stream(0):
            if ev.kind == "token":
                direct = await door.submit([1], max_new_tokens=1)
                break
        rids = await asyncio.gather(*arrivals)
        await door.drain()
        assert not door._pump_task or door._pump_task.done()
        # the door stood idle: a submission restarts the pump
        late = await door.submit([1], max_new_tokens=1)
        await door.drain()
        return door, rids + [direct, late]

    door, rids = _run_stepped(backend, main)
    assert set(backend.submitted_at) == {0, *rids}
    counts = door.submits_by_pass
    assert sum(counts.values()) == len(backend.submitted_at) == 7
    assert set(counts) <= {"idle", *(str(n) for n in range(1, door.PASSES + 1))}
    assert counts["idle"] == 2 and counts["1"] == 1
    later = sum(counts.get(str(n), 0) for n in range(2, door.PASSES + 1))
    assert later == 4  # the four open-loop arrivals
    assert later / (sum(counts.values()) - counts["idle"]) > 0.5
    sample = registry.sample()
    for label, n in counts.items():
        key = series_name("serve_door_submits_total", {"pass": label})
        assert sample[key] == n


def test_flooded_loop_cannot_starve_the_engine():
    """The passes are bounded: with callbacks that reschedule
    themselves for ever and tasks that never sleep, the engine still
    steps after `PASSES` passes of the loop, and a request finishes."""
    ticks = {"tasks": 0, "callbacks": 0}
    at_step = []
    backend = _SteppedClock(on_step=lambda i: at_step.append(dict(ticks)))

    async def main():
        loop = asyncio.get_running_loop()
        door = FrontDoor(backend)
        flooding = True

        async def spin():
            while flooding:
                ticks["tasks"] += 1
                await asyncio.sleep(0)

        def again():
            ticks["callbacks"] += 1
            if flooding:
                loop.call_soon(again)

        spinners = [asyncio.ensure_future(spin()) for _ in range(3)]
        loop.call_soon(again)
        rid = await door.submit([1], max_new_tokens=8)
        tokens = [ev.token async for ev in door.stream(rid) if ev.kind == "token"]
        flooding = False
        await asyncio.gather(*spinners)
        return door, tokens

    door, tokens = asyncio.run(main())
    assert len(tokens) == 8 and backend.iterations == 8
    for before, after in zip(at_step, at_step[1:]):
        # one tick a pass for the callback, one a pass for each task
        assert after["callbacks"] - before["callbacks"] == door.PASSES
        assert after["tasks"] - before["tasks"] == 3 * door.PASSES


def test_drain_and_pump_share_one_iteration():
    """`drain()` runs the pump's own iteration: one `step()`, then the
    same bounded passes, so an arrival during a drain crosses the door
    as it does under the pump."""
    backend = _SteppedClock()

    async def main(door):
        req = Request(rid=100, prompt=[1], max_new_tokens=8)
        backend.submit(req)  # work the door's pump knows nothing of
        arrival = asyncio.ensure_future(_open_loop_arrival(door, 2.5))
        await asyncio.sleep(0)  # the arrival's timer is set
        await door.drain()
        return door, await arrival

    door, rid = _run_stepped(backend, main)
    assert door._pump_task is not None  # started by the arrival alone
    assert backend.submitted_at[rid] == 3
    assert door.submits_by_pass == {str(door.PASSES): 1}


# -- cost-aware prefix eviction ------------------------------------------------


def _publish_chain(cache, tokens, total=16):
    slot = cache.alloc(len(tokens), total)
    cache.lengths[slot] = len(tokens)
    cache.register_prefix(slot, tokens, len(tokens))
    ps = cache.spec.page_size
    pages = [int(p) for p in cache.block_tables[slot][: len(tokens) // ps]]
    cache.free(slot)
    return pages


def test_cost_evict_takes_cheapest_not_oldest():
    """LRU evicts by stamp; cost evicts by recompute price. With a
    deep chain published BEFORE a shallow one, the second eviction
    diverges: LRU takes the deep chain's second page (old), cost takes
    the shallow chain's only page (cheap — its span recomputes at
    cursor 0)."""
    def run(policy, pricer=None):
        cache = PagedKVCache(
            _spec(num_pages=16),
            jnp.float32,
            prefix_cache=True,
            prefix_evict=policy,
            evict_pricer=pricer,
        )
        deep = _publish_chain(cache, list(range(1, 13)))  # 3 pages
        shallow = _publish_chain(cache, list(range(31, 35)))  # 1 page
        # pool: 16 pages, 4 retained; a 14-page demand forces exactly
        # two evictions
        assert cache.alloc(32, 32) is not None  # 8 pages
        assert cache.alloc(24, 24) is not None  # 6 pages -> 2 evictions
        assert cache.prefix_evictions == 2
        return cache, deep, shallow

    lru_cache, lru_deep, lru_shallow = run("lru")
    # LRU: the deep chain published first — both evictions hit it
    assert lru_shallow[0] in lru_cache._pub_only
    assert lru_deep[0] not in lru_cache._pub_only
    assert lru_deep[1] not in lru_cache._pub_only

    cost_cache, cost_deep, cost_shallow = run("cost")
    # cost (cursor-proxy pricing): the two cursor-0 pages are cheapest
    # — one from each chain — and the deep chain's SPAN-4 page
    # survives where LRU took it
    assert cost_shallow[0] not in cost_cache._pub_only
    assert cost_deep[0] not in cost_cache._pub_only
    assert cost_deep[1] in cost_cache._pub_only


def test_evict_pricer_drives_the_choice():
    """An injected pricer inverts the order: pricing deep spans as
    CHEAP makes eviction take the deepest page first — the policy is
    the pricer's, not a hardcoded heuristic."""
    cache = PagedKVCache(
        _spec(num_pages=16),
        jnp.float32,
        prefix_cache=True,
        prefix_evict="cost",
        evict_pricer=lambda cursor, chunk: -float(cursor),
    )
    deep = _publish_chain(cache, list(range(1, 13)))  # spans 0, 4, 8
    # 3 retained + 8 + 6 > 16 pages: exactly one eviction
    assert cache.alloc(32, 32) is not None
    assert cache.alloc(24, 24) is not None
    assert cache.prefix_evictions == 1
    assert deep[2] not in cache._pub_only  # deepest went first
    assert deep[0] in cache._pub_only and deep[1] in cache._pub_only
    cache.check_invariants()


def test_cost_evict_end_to_end(lm):
    """ServeConfig accepts prefix_evict='cost'; build_scheduler wires
    the CostModel-backed pricer and the stream still serves
    token-identically (eviction policy is a capacity knob, never a
    correctness one)."""
    serve = _serve()
    ref = _reference(lm, serve, _prompts())
    cost = _serve(prefix_evict="cost", kv_pages=24)
    sched, _, cache = build_scheduler(lm, cost)
    assert cache.evict_pricer is not None  # compiled model: priced
    out = _tokens(sched.run(_reqs(_prompts())))
    assert out == ref


def test_prefix_evict_cost_requires_prefix_cache():
    with pytest.raises(ValueError, match="prefix_evict"):
        ServeConfig(
            max_seqs=2,
            max_seq_len=32,
            prefix_evict="cost",
        )


# -- prefill-only scheduler ----------------------------------------------------


def test_prefill_only_scheduler_never_decodes(lm):
    """The prefill tier emits exactly the first token per stream and
    then parks the request, pages committed, until stage-out."""
    serve = _serve()
    sched, _, cache = build_scheduler(
        lm, serve, scheduler_cls=PrefillOnlyScheduler
    )
    reqs = _reqs(_prompts()[:2], max_new=6)
    for r in reqs:
        sched.submit(r)
    for _ in range(50):
        sched.step()
    ready = sched.ready_for_handoff()
    assert [r.rid for r in ready] == [0, 1]
    assert all(len(r.generated) == 1 for r in ready)
    assert all(int(cache.lengths[r.slot]) == len(r.prompt) for r in ready)


def test_stage_out_detaches_without_terminal(lm):
    serve = _serve()
    sched, _, cache = build_scheduler(
        lm, serve, scheduler_cls=PrefillOnlyScheduler
    )
    req = Request(rid=0, prompt=_prompts()[0], max_new_tokens=4)
    sched.submit(req)
    while not sched.ready_for_handoff():
        sched.step()
    handle = sched.stage_out(0)
    assert handle is not None
    assert req.slot is None and req.swap_handle == handle
    assert req.status == RequestStatus.QUEUED  # NOT terminal
    assert not sched.running and not sched.finished
    assert sched.stage_out(0) is None  # detached: unknown now
    cache.check_invariants()
