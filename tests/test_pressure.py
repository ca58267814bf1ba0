"""Graceful degradation under pressure: KV swap-to-host, published-
prefix eviction, and host-failure drain.

Identity contract: a stream that was swapped to host and restored
resumes token- and logit-identically to a never-swapped run — the
staged pages are the COMMITTED pool rows (bit-exact, including the
int8 scale slivers), so restore is a plain decode, never a re-prefill.
Eviction only ever takes pages whose refcount is publication-only;
live sharers resurrect retained pages untouched. A host partition
dropping mid-run drains to PREEMPTED and every stream completes on
the survivors. Allocator invariants (including the swap ledger and
pub-only conservation) are re-derived every iteration. All CPU-fast
(tier 1)."""

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.serving import (
    FaultInjector,
    FaultPlan,
    KVCacheSpec,
    PagedKVCache,
    Request,
    ServeConfig,
    build_scheduler,
)

from tests.test_paged_kv import _check_allocator_invariants, _lm

pytestmark = pytest.mark.serving

VOCAB = 50


@pytest.fixture(scope="module")
def lm():
    return _lm()


def _spec(**over):
    base = dict(
        layer_guids=(1, 2), max_seqs=4, max_len=32, num_heads=2,
        head_dim=4, buckets=(32,), page_size=4, num_pages=12,
    )
    base.update(over)
    return KVCacheSpec(**base)


def _fill_slot(cache, slot, rng):
    """Write distinct random rows into every page the slot holds (and
    nonzero scale slivers under int8) via the blessed commit path, and
    return the expected per-layer row content keyed by page index."""
    sent = cache.spec.num_pages
    pages = [int(p) for p in cache.block_tables[slot] if p != sent]
    idx = np.asarray(pages, dtype=np.int32)
    nk, nv = dict(cache.k), dict(cache.v)
    nks, nvs = dict(cache.k_scale), dict(cache.v_scale)
    expect = {}
    for g in cache.spec.layer_guids:
        rows_k = rng.integers(-40, 40, size=(len(pages),) + nk[g].shape[1:])
        rows_v = rng.integers(-40, 40, size=(len(pages),) + nv[g].shape[1:])
        nk[g] = nk[g].at[idx].set(jnp.asarray(rows_k, nk[g].dtype))
        nv[g] = nv[g].at[idx].set(jnp.asarray(rows_v, nv[g].dtype))
        expect[g] = (
            np.asarray(rows_k, np.asarray(nk[g]).dtype),
            np.asarray(rows_v, np.asarray(nv[g]).dtype),
        )
        if cache.quantized:
            sk = rng.uniform(0.5, 2.0, size=(len(pages),) + nks[g].shape[1:])
            sv = rng.uniform(0.5, 2.0, size=(len(pages),) + nvs[g].shape[1:])
            nks[g] = nks[g].at[idx].set(jnp.asarray(sk, jnp.float32))
            nvs[g] = nvs[g].at[idx].set(jnp.asarray(sv, jnp.float32))
            expect[g] += (
                np.asarray(sk, np.float32),
                np.asarray(sv, np.float32),
            )
    if cache.quantized:
        cache.commit(nk, nv, nks, nvs)
    else:
        cache.commit(nk, nv)
    return pages, expect


# -- engine-level swap roundtrip ---------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_swap_roundtrip_restores_rows_bit_exact(kv_dtype):
    """swap_out stages the committed K/V rows (and int8 scale slivers);
    swap_in scatters them back bit-exactly — the logit-identity of a
    restored stream reduces to this row equality."""
    cache = PagedKVCache(_spec(kv_dtype=kv_dtype), jnp.float32)
    rng = np.random.default_rng(0)
    slot = cache.alloc(10, 20)
    cache.lengths[slot] = 10
    pages, expect = _fill_slot(cache, slot, rng)
    staged = cache.swap_bytes_for(slot)
    assert staged > 0

    handle = cache.swap_out(slot)
    assert handle is not None
    assert slot not in cache._active  # freed: capacity actually returned
    assert cache.swapped_pages == len(pages)
    assert cache._swap_bytes_held == staged
    _check_allocator_invariants(cache)

    # another tenant dirties the pool while the victim is on host
    other = cache.alloc(12, 12)
    _fill_slot(cache, other, rng)

    restored = cache.swap_in(handle, total_len=20)
    assert restored is not None
    assert int(cache.lengths[restored]) == 10
    assert cache.swapped_pages == 0 and cache._swap_bytes_held == 0
    sent = cache.spec.num_pages
    new_pages = [int(p) for p in cache.block_tables[restored] if p != sent]
    assert len(new_pages) == len(pages)
    idx = np.asarray(new_pages, dtype=np.int32)
    for g in cache.spec.layer_guids:
        np.testing.assert_array_equal(np.asarray(cache.k[g])[idx], expect[g][0])
        np.testing.assert_array_equal(np.asarray(cache.v[g])[idx], expect[g][1])
        if cache.quantized:
            np.testing.assert_array_equal(
                np.asarray(cache.k_scale[g])[idx], expect[g][2]
            )
            np.testing.assert_array_equal(
                np.asarray(cache.v_scale[g])[idx], expect[g][3]
            )
    _check_allocator_invariants(cache)
    cache.check_invariants()


def test_swap_bytes_budget_refuses_and_discard_returns_budget():
    cache = PagedKVCache(_spec(), jnp.float32, swap_bytes_budget=1)
    slot = cache.alloc(10, 20)
    cache.lengths[slot] = 10
    assert cache.swap_out(slot) is None  # over budget -> caller recomputes
    assert slot in cache._active  # refusal leaves the slot untouched

    cache2 = PagedKVCache(_spec(), jnp.float32)
    s2 = cache2.alloc(10, 20)
    cache2.lengths[s2] = 10
    h = cache2.swap_out(s2)
    assert cache2._swap_bytes_held > 0
    cache2.discard_swap(h)
    assert cache2._swap_bytes_held == 0 and cache2.swapped_pages == 0
    cache2.check_invariants()


# -- scheduler-level token identity under forced pressure ---------------------


def _pressure_requests(n=4, prompt_len=10, max_new=8, shared_prefix=False):
    if shared_prefix:
        pref = list(range(1, prompt_len + 1))
        return [
            Request(rid=i, prompt=pref + [20 + i], max_new_tokens=max_new)
            for i in range(n)
        ]
    return [
        Request(
            rid=i,
            prompt=[(i * 7 + j) % VOCAB + 1 for j in range(prompt_len)],
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


def _run_matrix(lm, *, pressured, serve_async=False, mode="plain",
                kv_dtype="fp32", expect_swaps=False):
    over = {}
    if mode == "spec":
        over.update(spec_draft="ngram", spec_k=2)
    elif mode == "chunked":
        over.update(token_budget=16, chunk_size=8)
    elif mode == "prefix":
        over.update(prefix_cache=True)
    serve = ServeConfig(
        max_seqs=4,
        max_seq_len=32,
        kv_page_size=4,
        kv_pages=24 if not pressured else 12,
        admission="optimistic" if pressured else "reserve",
        max_preemptions=32,
        kv_dtype=kv_dtype,
        kv_swap=pressured,
        serve_async=serve_async,
        decode_kernel="dense",
        debug_invariants=True,
        **over,
    )
    injector = None
    if pressured:
        # steal most of the pool mid-decode: _secure_pages comes up dry
        # and preempts — with kv_swap on, via swap-to-host
        injector = FaultInjector(
            FaultPlan(steal_iters=(3, 4), steal_pages=7, steal_hold=3),
            seed=11,
        )
    sched, _, cache = build_scheduler(lm, serve, injector=injector)
    if pressured:
        # benchmark-sized models recompute faster than PCIe; the test
        # targets the swap path itself, so always-swap
        sched.swap_decider = None
    reqs = _pressure_requests(shared_prefix=(mode == "prefix"))
    done = {r.rid: r for r in sched.run(reqs)}
    if injector is not None:
        injector.release_stolen_pages(cache)
    cache.check_invariants()
    assert all(r.status == "finished" for r in done.values()), {
        r.rid: (r.status, r.error) for r in done.values()
    }
    if expect_swaps:
        assert sched.stats.swap_outs > 0
        assert sched.stats.swap_ins > 0
        swapped = [
            r for r in done.values()
            if any("action=swap" in e[2] for e in r.events if e[1] == "preempt")
        ]
        assert swapped, "no stream carries a swap preempt event"
        for r in swapped:
            admits = [e[2] for e in r.events if e[1] == "admit"]
            assert any("swap_in" in a for a in admits)
    return {rid: list(r.generated) for rid, r in done.items()}


# the full {sync,async} x {plain,spec,chunked,prefix} matrix runs in the
# serving-pressure CI job (no "not slow" filter there) — the
# time-budgeted tier-1 sweep keeps only the sync plain leg
@pytest.mark.parametrize(
    "serve_async",
    [False, pytest.param(True, marks=pytest.mark.slow)],
)
@pytest.mark.parametrize(
    "mode",
    [
        "plain",
        pytest.param("spec", marks=pytest.mark.slow),
        pytest.param("chunked", marks=pytest.mark.slow),
        pytest.param("prefix", marks=pytest.mark.slow),
    ],
)
def test_swap_restore_streams_token_identical(lm, serve_async, mode):
    """Forced pool pressure with swap-to-host on: every stream matches
    the unpressured reference token-for-token, across the sync/async
    loops and the spec/chunked/prefix serving features."""
    ref = _run_matrix(lm, pressured=False, serve_async=serve_async, mode=mode)
    got = _run_matrix(
        lm,
        pressured=True,
        serve_async=serve_async,
        mode=mode,
        expect_swaps=(mode == "plain"),
    )
    assert got == ref


@pytest.mark.slow
@pytest.mark.parametrize("serve_async", [False, True])
def test_swap_restore_token_identical_int8(lm, serve_async):
    """Same contract under int8 KV: the scale slivers ride the swap, so
    the pressured int8 run reproduces the unpressured int8 run exactly
    (int8-vs-fp32 stays a tolerance question, NOT swap's problem)."""
    ref = _run_matrix(
        lm, pressured=False, serve_async=serve_async, kv_dtype="int8"
    )
    got = _run_matrix(
        lm,
        pressured=True,
        serve_async=serve_async,
        kv_dtype="int8",
        expect_swaps=True,
    )
    assert got == ref


def test_swap_fail_degrades_to_recompute_never_loses(lm):
    """Every swap attempt fails (seeded rate 1.0): the scheduler must
    degrade each preemption to recompute and every stream still
    finishes identically — a failed swap is a slower path, not a lost
    request."""
    ref = _run_matrix(lm, pressured=False)
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, kv_page_size=4,
        kv_pages=12, admission="optimistic", max_preemptions=32,
        kv_swap=True, decode_kernel="dense", debug_invariants=True,
    )
    injector = FaultInjector(
        FaultPlan(
            steal_iters=(3, 4), steal_pages=7, steal_hold=3,
            swap_fail_rate=1.0,
        ),
        seed=11,
    )
    sched, _, cache = build_scheduler(lm, serve, injector=injector)
    sched.swap_decider = None
    done = {r.rid: r for r in sched.run(_pressure_requests())}
    injector.release_stolen_pages(cache)
    cache.check_invariants()
    assert all(r.status == "finished" for r in done.values())
    assert sched.stats.swap_outs == 0  # every attempt was failed
    assert sched.stats.preemptions > 0
    assert injector.summary().get("swap_fail", 0) > 0
    preempts = [
        e[2] for r in done.values() for e in r.events if e[1] == "preempt"
    ]
    assert preempts and all("action=recompute" in p for p in preempts)
    assert {rid: list(r.generated) for rid, r in done.items()} == ref


# -- published-prefix eviction ------------------------------------------------


def test_pub_only_pages_retained_then_evicted_lru():
    """Pages whose refcount is publication-only are retained for reuse,
    count as available capacity, and are reclaimed oldest-first when
    the free list runs dry — BEFORE any live request is touched."""
    cache = PagedKVCache(
        _spec(), jnp.float32, prefix_cache=True, prefix_evict="lru"
    )
    toks_a = list(range(1, 9))       # 2 full pages
    toks_b = list(range(31, 39))     # 2 full pages, distinct
    a = cache.alloc(len(toks_a), 12)
    cache.lengths[a] = 8
    cache.register_prefix(a, toks_a, 8)
    pages_a = [int(p) for p in cache.block_tables[a][:2]]
    cache.free(a)
    b = cache.alloc(len(toks_b), 12)
    cache.lengths[b] = 8
    cache.register_prefix(b, toks_b, 8)
    pages_b = [int(p) for p in cache.block_tables[b][:2]]
    cache.free(b)
    # both prefixes retained: refcount 0, still matchable
    assert all(cache._refcounts[p] == 0 for p in pages_a + pages_b)
    assert set(pages_a + pages_b) == set(cache._pub_only)
    assert len(cache.match_prefix(toks_a)) == 2
    assert len(cache.match_prefix(toks_b)) == 2
    cache.check_invariants()  # counts the pub-only population

    # pool: 12 pages, 4 retained, 8 on the free list. A 9-page claim
    # must evict exactly ONE retained page — the LRU one (prefix a)
    big = cache.alloc(32, 32)  # 8 pages
    assert big is not None
    small = cache.alloc(4, 4)  # 9th page -> first eviction
    assert small is not None
    assert cache.prefix_evictions == 1
    assert len(cache.match_prefix(toks_b)) == 2  # newer prefix untouched
    assert len(cache.match_prefix(toks_a)) < 2   # oldest page went first
    cache.check_invariants()


def test_eviction_never_takes_live_shared_pages():
    """A retained page resurrected by a live sharer leaves the pub-only
    set; pool exhaustion then refuses (preemption's job) rather than
    evicting under the live request."""
    cache = PagedKVCache(
        _spec(), jnp.float32, prefix_cache=True, prefix_evict="lru"
    )
    toks = list(range(1, 9))
    a = cache.alloc(len(toks), 12)
    cache.lengths[a] = 8
    cache.register_prefix(a, toks, 8)
    shared_pages = [int(p) for p in cache.block_tables[a][:2]]
    cache.free(a)
    assert set(shared_pages) == set(cache._pub_only)

    got = cache.alloc_shared(toks + [40], prompt_len=9, total_len=12)
    assert got is not None
    b, _ = got
    # resurrection: the sharer's incref pulled the pages OUT of the
    # evictable set — they are live again
    assert not cache._pub_only
    assert all(cache._refcounts[p] == 1 for p in shared_pages)

    # drain the rest of the pool; the live shared pages must survive
    filled = []
    while True:
        s = cache.alloc(4, 4)
        if s is None:
            break
        filled.append(s)
    assert cache.prefix_evictions == 0
    assert all(cache._refcounts[p] == 1 for p in shared_pages)
    assert len(cache.match_prefix(toks)) == 2
    cache.check_invariants()


def test_prefix_evict_requires_prefix_cache():
    with pytest.raises(ValueError, match="prefix_evict"):
        ServeConfig(
            max_seqs=2, max_seq_len=32,
            prefix_evict="lru",
        )


# -- host-failure drain -------------------------------------------------------


def _two_host_lm():
    return _lm()


def test_host_down_drains_and_completes_on_survivor(lm):
    """Marking a pod host lost preempts its RUNNING requests (forensics:
    cause=host_down), refuses re-admission to the dead host, and every
    stream completes on the survivor — token-identical to a calm run."""
    ref = _run_matrix(lm, pressured=False)
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, kv_page_size=4,
        kv_pages=24, serve_hosts=2, admission="optimistic",
        max_preemptions=32, kv_swap=True, decode_kernel="dense",
        telemetry=True, debug_invariants=True,
    )
    injector = FaultInjector(
        FaultPlan(host_down_iters={3: 1}, host_down_hold=4), seed=5
    )
    # a fresh model: compile_for_serving pins the two-host placement
    lm2 = _two_host_lm()
    sched, _, cache = build_scheduler(lm2, serve, injector=injector)
    sched.swap_decider = None
    done = {r.rid: r for r in sched.run(_pressure_requests())}
    cache.check_invariants()
    assert all(r.status == "finished" for r in done.values()), {
        r.rid: (r.status, r.error) for r in done.values()
    }
    assert {rid: list(r.generated) for rid, r in done.items()} == ref
    assert sched.stats.host_downs == 1
    assert injector.summary().get("host_down") == 1
    drained = [
        r for r in done.values()
        if any("cause=host_down" in e[2] for e in r.events if e[1] == "preempt")
    ]
    assert drained, "host_down reaped no running request"
    # the drain and the recovery are visible in telemetry
    metrics = sched.telemetry.render_prometheus()
    assert 'serve_host_down_total{host="1"} 1' in metrics
    assert not cache._hosts_down  # hold expired: the host rejoined


@pytest.mark.slow  # runs in the serving-pressure CI job
def test_host_down_drain_is_replayable(lm):
    """Same seed, same plan -> identical drain forensics on a rerun
    (the injector's counter-mode RNG keys by (seed, iteration, site))."""
    def run_once():
        serve = ServeConfig(
            max_seqs=4, max_seq_len=32, kv_page_size=4,
            kv_pages=24, serve_hosts=2, admission="optimistic",
            max_preemptions=32, decode_kernel="dense",
        )
        injector = FaultInjector(
            FaultPlan(host_down_iters={3: 1}, host_down_hold=4), seed=5
        )
        lm2 = _two_host_lm()
        sched, _, _ = build_scheduler(lm2, serve, injector=injector)
        done = {r.rid: r for r in sched.run(_pressure_requests())}
        return {
            rid: [e[1:] for e in r.events if e[1] == "preempt"]
            for rid, r in done.items()
        }

    assert run_once() == run_once()


# -- forensics ----------------------------------------------------------------


def test_hard_fail_after_max_preemptions_carries_cause(lm):
    """A request FAILED by the preemption cap names the cap AND the
    triggering cause in Request.error — post-mortems read the error,
    not the scheduler source."""
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, kv_page_size=4,
        kv_pages=12, admission="optimistic", max_preemptions=0,
        decode_kernel="dense",
    )
    injector = FaultInjector(
        FaultPlan(steal_iters=(3, 4), steal_pages=7, steal_hold=3), seed=3
    )
    sched, _, cache = build_scheduler(lm, serve, injector=injector)
    done = {r.rid: r for r in sched.run(_pressure_requests())}
    injector.release_stolen_pages(cache)
    failed = [r for r in done.values() if r.status == "failed"]
    assert failed, "the steal storm never tripped the preemption cap"
    for r in failed:
        assert "max_preemptions" in (r.error or "")
        assert "cause=" in (r.error or "")
