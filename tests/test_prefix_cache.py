"""Prefix-sharing page cache + int8-quantized KV pools.

Identity contract: a shared-prefix workload streams TOKEN- and
LOGIT-identically to the same workload with sharing off, on both KV
dtypes, across sync/async scheduling, speculative decoding, and
chunked prefill — sharing and quantization change capacity, never
content. Refcount/conservation invariants are re-derived every
iteration (debug_invariants) including under COW, preemption, and
spec-decode rollback. int8 vs fp32 is a numeric-tolerance comparison
(quantization IS lossy; the contract is bounded logits plus bit-exact
shared-vs-unshared within the int8 run). All CPU-fast (tier 1)."""

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.serving import (
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


def _cache(**over):
    return PagedKVCache(_spec(**over), jnp.float32, prefix_cache=True)


def _shared_requests(mnt=(18, 3, 3, 3, 3, 3), pref_len=12):
    """Same 12-token prefix, distinct tails, STAGGERED lifetimes: the
    long request keeps the prefix pages live (refcounted) while the
    short ones churn through the remaining slot — without the stagger
    every sharer retires at once, the pages unpublish at refcount 0,
    and no admission ever overlaps a live prefix."""
    pref = list(range(1, pref_len + 1))
    return [
        Request(rid=i, prompt=pref + [20 + i], max_new_tokens=n)
        for i, n in enumerate(mnt)
    ]


def _run(lm, reqs, **serve_over):
    serve = dict(
        max_seqs=2, max_seq_len=64, kv_page_size=4,
        decode_kernel="dense", debug_invariants=True,
    )
    serve.update(serve_over)
    sched, _, cache = build_scheduler(lm, ServeConfig(**serve))
    done = {r.rid: r for r in sched.run(reqs)}
    assert all(r.status == "finished" for r in done.values()), {
        r.rid: (r.status, r.error) for r in done.values()
    }
    return {rid: r.generated for rid, r in done.items()}, cache, sched


# -- allocator unit tests -----------------------------------------------------


def test_match_prefix_walks_full_pages_only():
    cache = _cache()
    tokens = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    slot = cache.alloc(len(tokens), len(tokens) + 2)
    cache.lengths[slot] = len(tokens)
    cache.register_prefix(slot, tokens, len(tokens))
    # 2 full pages published; the partial third page (tokens 9, 10) is not
    assert len(cache.match_prefix(tokens)) == 2
    assert len(cache.match_prefix(tokens[:8])) == 2
    assert len(cache.match_prefix(tokens[:7])) == 1  # 1 full page of query
    assert cache.match_prefix([1, 2, 3, 99] + tokens[4:]) == []  # diverges
    assert cache.match_prefix([2, 1, 3, 4]) == []
    _check_allocator_invariants(cache)


def test_alloc_shared_maps_pages_and_refcounts():
    cache = _cache()
    tokens = list(range(1, 13))  # 3 full pages
    a = cache.alloc(len(tokens), 16)
    cache.lengths[a] = len(tokens)
    cache.register_prefix(a, tokens, len(tokens))
    got = cache.alloc_shared(tokens + [40], prompt_len=13, total_len=16)
    assert got is not None
    b, cursor = got
    assert cursor == 12  # all 3 full pages shared
    for pi in range(3):
        page = int(cache.block_tables[a, pi])
        assert int(cache.block_tables[b, pi]) == page
        assert cache._refcounts[page] == 2
        assert cache._entry_shared[b, pi]
        assert not cache._entry_shared[a, pi]
    assert cache.prefix_hits == 1
    assert int(cache.lengths[b]) == 12
    _check_allocator_invariants(cache)
    # first divergent write COWs: position 12 lands on a FRESH page
    # (page 3 of the sharer was never shared), but a write into a
    # shared page must copy
    cache.ensure_position(b, 12)
    _check_allocator_invariants(cache)
    cache.free(b)
    for pi in range(3):
        assert cache._refcounts[int(cache.block_tables[a, pi])] == 1
    _check_allocator_invariants(cache)


def test_cow_copies_shared_page_and_sole_owner_takes_over():
    cache = _cache()
    tokens = list(range(1, 9))  # 2 full pages
    a = cache.alloc(len(tokens), 12)
    cache.lengths[a] = len(tokens)
    cache.register_prefix(a, tokens, len(tokens))
    b, cursor = cache.alloc_shared(tokens, prompt_len=8, total_len=12)
    assert cursor == 7  # whole-prompt match recomputes the last token
    shared_page = int(cache.block_tables[b, 1])
    assert cache._refcounts[shared_page] == 2
    # writing position 7 (inside shared page 1) COWs it
    cache.ensure_position(b, 7)
    assert cache.cow_copies == 1
    assert int(cache.block_tables[b, 1]) != shared_page
    assert cache._refcounts[shared_page] == 1
    _check_allocator_invariants(cache)
    # sole-owner takeover: page 0 is still shared by b (refcount 2);
    # retiring the publisher leaves b the only owner but the entry
    # still FLAGGED shared — the next write unmarks in place, no copy
    cache.free(a)
    page0 = int(cache.block_tables[b, 0])
    assert cache._refcounts[page0] == 1 and cache._entry_shared[b, 0]
    before = cache.cow_copies
    cache.ensure_position(b, 2)
    assert cache.cow_copies == before  # takeover, not a device copy
    assert int(cache.block_tables[b, 0]) == page0
    assert not cache._entry_shared[b, 0]
    _check_allocator_invariants(cache)


def test_freed_prefix_unpublishes_and_truncate_decrefs():
    cache = _cache()
    tokens = list(range(1, 9))
    a = cache.alloc(len(tokens), 12)
    cache.lengths[a] = len(tokens)
    cache.register_prefix(a, tokens, len(tokens))
    assert len(cache.match_prefix(tokens)) == 2
    b, _ = cache.alloc_shared(tokens + [30], prompt_len=9, total_len=12)
    # rollback-style truncate on the sharer releases its share refs
    cache.lengths[b] = 9
    cache.truncate(b, 0)
    for pi in range(2):
        assert cache._refcounts[int(cache.block_tables[a, pi])] == 1
    _check_allocator_invariants(cache)
    cache.free(b)
    cache.free(a)
    # every page back, nothing published
    assert cache.match_prefix(tokens) == []
    assert not cache._prefix_index and not cache._page_keys
    _check_allocator_invariants(cache)


def test_alloc_shared_admission_charges():
    """Reserve admission prices shared slots at max_pages minus the
    shared pages (worst case: every shared page COWs); optimistic
    charges only the fresh prompt pages."""
    cache = _cache(num_pages=8)
    tokens = list(range(1, 13))  # 3 pages
    a = cache.alloc(len(tokens), 16)  # holds 3, reserves 1
    cache.lengths[a] = len(tokens)
    cache.register_prefix(a, tokens, len(tokens))
    # reserve: needs 4 total pages against 8 - 3 held - 1 reserved = 4
    got = cache.alloc_shared(tokens, prompt_len=12, total_len=16)
    assert got is not None
    cache.free(got[0])
    # burn free pages so only the fresh-page charge can fit
    burn = cache.alloc(4, 4)
    assert len(cache._free_pages) - cache._reserved == 3
    assert cache.alloc_shared(tokens, prompt_len=12, total_len=32) is None
    opt = cache.alloc_shared(
        tokens, prompt_len=12, total_len=32, optimistic=True
    )
    # whole-prompt match: cursor stops at ntok - 1 (one token is
    # recomputed so prefill has a write to COW and a logit to sample)
    assert opt is not None and opt[1] == 11
    _check_allocator_invariants(cache)
    cache.free(opt[0])
    cache.free(burn)
    cache.free(a)
    _check_allocator_invariants(cache)


# -- end-to-end identity: shared streams == unshared streams ------------------


_MATRIX = [
    ("sync", {}),
    ("async", dict(serve_async=True)),
    ("chunked", dict(token_budget=16, chunk_size=8)),
    ("spec", dict(spec_draft="ngram", spec_k=3)),
    ("async_chunked", dict(serve_async=True, token_budget=16, chunk_size=8)),
]

# tier-1 keeps every mode on fp32 plus the dtype axis itself
# (sync-int8); the int8 × mode cross products and the doubled-up
# async_chunked combo re-prove the same identity at 6-10s apiece, so
# they carry the `slow` marker and run in the dedicated prefix-cache
# CI job (which drops the marker filter) instead of the time-budgeted
# tier-1 sweep
_HEAVY = {
    ("async", "int8"), ("chunked", "int8"), ("spec", "int8"),
    ("async_chunked", "int8"), ("async_chunked", "fp32"),
}


def _matrix_params():
    return [
        pytest.param(
            mode, extra, dt, id=f"{mode}-{dt}",
            marks=[pytest.mark.slow] if (mode, dt) in _HEAVY else [],
        )
        for mode, extra in _MATRIX
        for dt in ("fp32", "int8")
    ]


@pytest.mark.parametrize("mode,extra,kv_dtype", _matrix_params())
def test_shared_stream_identical_to_unshared(lm, mode, extra, kv_dtype):
    """The tentpole identity: prefix sharing changes WHERE prefix K/V
    rows come from (mapped pages vs recompute), never their content —
    so greedy streams are bit-identical with the cache on and off, per
    dtype, across every scheduling mode."""
    base, _, _ = _run(lm, _shared_requests(), kv_dtype=kv_dtype, **extra)
    shared, cache, sched = _run(
        lm, _shared_requests(), kv_dtype=kv_dtype, prefix_cache=True, **extra
    )
    assert shared == base
    assert cache.prefix_hits > 0, "workload never shared a prefix"
    assert sched.stats.prefix_hits == cache.prefix_hits


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_whole_prompt_match_cow_parity(lm, kv_dtype):
    """A prompt that is ENTIRELY covered by a published prefix still
    recomputes one token (cursor = ntok - 1) whose write COWs the last
    shared page — and stays token-identical to the unshared run."""
    pref = list(range(1, 13))
    reqs = lambda: [  # noqa: E731
        Request(rid=0, prompt=pref, max_new_tokens=14),
        Request(rid=1, prompt=pref, max_new_tokens=3),
        Request(rid=2, prompt=pref, max_new_tokens=3),
    ]
    base, _, _ = _run(lm, reqs(), kv_dtype=kv_dtype)
    shared, cache, _ = _run(lm, reqs(), kv_dtype=kv_dtype, prefix_cache=True)
    assert shared == base
    assert cache.prefix_hits >= 1
    assert cache.cow_copies >= 1, "whole-prompt match must COW"


@pytest.mark.slow  # interpret-mode kernels; the prefix-cache CI job runs it
def test_shared_stream_identical_on_pallas_kernel(lm):
    """Kernel-path parity: the int8 Pallas decode kernel (page_size 32
    — the int8 sublane minimum) and the fp32 kernel both stream
    identically with sharing on and off."""
    pref = list(range(1, 37))  # one full 32-token page + tail
    mk = lambda: [  # noqa: E731
        Request(rid=i, prompt=pref + [40 + i], max_new_tokens=n)
        for i, n in enumerate((12, 3, 3, 3))
    ]
    for dt in ("fp32", "int8"):
        kw = dict(
            max_seq_len=128, kv_page_size=32, decode_kernel="pallas",
            kv_dtype=dt,
        )
        base, _, _ = _run(lm, mk(), **kw)
        shared, cache, _ = _run(lm, mk(), prefix_cache=True, **kw)
        assert shared == base, dt
        assert cache.prefix_hits > 0, dt
        dense, _, _ = _run(
            lm, mk(), max_seq_len=128, kv_page_size=32,
            decode_kernel="dense", kv_dtype=dt, prefix_cache=True,
        )
        assert dense == base, dt


def test_cow_under_preemption_invariants(lm):
    """Optimistic admission over an undersized pool: preemptions land
    WHILE prefix pages are shared; every iteration re-derives refcounts
    (debug_invariants) and the final streams still match the unshared
    run on the same pool geometry."""
    mk = lambda: _shared_requests(  # noqa: E731
        mnt=(14, 4, 4, 4, 4, 4), pref_len=8
    )
    kw = dict(
        max_seqs=3, max_seq_len=64, kv_page_size=4, kv_pages=28,
        admission="optimistic",
    )
    base, _, base_sched = _run(lm, mk(), **kw)
    shared, cache, sched = _run(lm, mk(), prefix_cache=True, **kw)
    assert shared == base
    assert cache.prefix_hits > 0
    _check_allocator_invariants(cache)


def test_spec_rollback_keeps_refcounts(lm):
    """Speculative decoding's truncate-on-reject runs against shared
    slots: rejected drafts roll the sharer back (possibly across a page
    boundary into COWed territory) without desynchronizing refcounts —
    probed every iteration by debug_invariants, and the stream stays
    identical to the non-spec shared run."""
    plain, _, _ = _run(lm, _shared_requests(), prefix_cache=True)
    spec, cache, sched = _run(
        lm, _shared_requests(), prefix_cache=True,
        spec_draft="ngram", spec_k=3,
    )
    assert spec == plain
    assert cache.prefix_hits > 0
    _check_allocator_invariants(cache)


# -- int8 numeric tolerance ---------------------------------------------------


def test_int8_logits_within_tolerance(lm):
    """int8 K/V vs fp32: logits agree within the documented tolerance
    (max |Δlogit| under 15% of the fp32 logit range — per-page scales
    bound the element error at scale/2 ≈ amax/127 for a row within twice
    the reach of its page's first row, which claimed the scale:
    `kv_cache.int8_page_scale`. Claimed at the first row's own abs-max,
    every other later row clipped and this decode step read 19%). Token
    streams are NOT compared across dtypes: quantization is lossy and
    argmax near ties legitimately flips; the bit-exact contract is
    shared-vs-unshared WITHIN a dtype (the matrix test above)."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    out = {}
    for dt in ("fp32", "int8"):
        _, engine, cache = build_scheduler(
            lm, ServeConfig(max_seqs=2, max_seq_len=32, kv_page_size=4,
                            kv_dtype=dt, decode_kernel="dense"))
        slot = cache.alloc(len(prompt), len(prompt) + 4)
        nxt, last = engine.prefill(lm.params, [prompt], [slot])
        tokens = np.zeros(cache.spec.max_seqs, dtype=np.int32)
        active = np.zeros(cache.spec.max_seqs, dtype=bool)
        tokens[slot] = int(nxt[0])
        active[slot] = True
        _, dec = engine.decode(lm.params, tokens, active)
        out[dt] = (
            np.asarray(last[0], np.float64), np.asarray(dec[slot], np.float64)
        )
    for i in range(2):
        ref, q = out["fp32"][i], out["int8"][i]
        span = float(ref.max() - ref.min())
        assert float(np.max(np.abs(ref - q))) < 0.15 * span


def test_int8_pool_dtype_and_scales(lm):
    _, _, cache = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32, kv_dtype="int8")
    )
    assert cache.quantized
    g = cache.spec.layer_guids[0]
    assert cache.k[g].dtype == jnp.int8
    assert cache.k_scale[g].dtype == jnp.float32
    assert cache.k_scale[g].shape == (
        cache.spec.num_pages, cache.spec.num_heads
    )
    # fp32 caches carry EMPTY scale pytrees — uniform jit signature,
    # zero overhead
    _, _, f32 = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32)
    )
    assert f32.k_scale == {} and f32.v_scale == {}


def test_prefix_sharing_doubles_concurrency_at_equal_bytes(lm):
    """The count the prefix CI gate held: on a stream whose requests
    share a prompt prefix of whole pages, at the SAME pool (equal HBM
    bytes) and optimistic admission, prefix sharing runs at least twice
    the concurrent requests of the plain paged cache: a sharer is
    charged only its fresh pages."""
    page, pages = 4, 16
    pref = [(j * 11 + 3) % (VOCAB - 1) + 1 for j in range(4 * page)]

    def requests(n):
        # the first request keeps the prefix pages live while the rest churn
        return [
            Request(rid=i, prompt=pref + [(i * 13 + j) % (VOCAB - 1) + 1
                                          for j in range(1 + i % 3)],
                    max_new_tokens=12 if i == 0 else 3)
            for i in range(n)
        ]

    peak = {}
    for prefix in (False, True):
        sched, _, _ = build_scheduler(lm, ServeConfig(
            max_seqs=pages, max_seq_len=32, kv_page_size=page,
            kv_pages=pages, prefix_cache=prefix, admission="optimistic",
            decode_kernel="dense", debug_invariants=True,
        ))
        done = sched.run(requests(2 * pages))
        assert len(done) == 2 * pages
        assert all(r.status == "finished" for r in done)
        peak[prefix] = sched.stats.peak_in_flight
        assert (sched.stats.prefix_hits > 0) == prefix
    assert peak[True] >= 2 * peak[False]


# -- config + flags -----------------------------------------------------------


def test_flag_validation():
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeConfig(kv_dtype="fp16")
    ServeConfig(kv_dtype="int8", prefix_cache=True)


def test_cli_flags_map_to_serve_config():
    cfg = FFConfig.parse_args(["--kv-dtype", "int8", "--prefix-cache"])
    assert cfg.serve_kv_dtype == "int8"
    assert cfg.serve_prefix_cache is True
    sc = ServeConfig.from_config(cfg)
    assert sc.kv_dtype == "int8" and sc.prefix_cache is True
    base = ServeConfig.from_config(FFConfig.parse_args([]))
    assert base.kv_dtype == "fp32" and base.prefix_cache is False


def test_bytes_per_layer_prices_int8_scales():
    q = _spec(itemsize=1, kv_dtype="int8")
    f = _spec()
    rows = q.num_pages * q.page_size
    assert f.bytes_per_layer == 2 * 4 * rows * 2 * 4
    assert q.bytes_per_layer == (
        2 * 1 * rows * 2 * 4 + 2 * 4 * q.num_pages * 2
    )


# -- capacity + cost-model pricing --------------------------------------------


def test_capacity_estimate_prices_dtype_and_hit_rate(lm):
    from flexflow_tpu.search.auto import estimate_max_in_flight

    g = lm.graph
    budget = 8 * 1024 * 1024
    base = estimate_max_in_flight(g, budget, 128, 64, 512, page_size=16)
    q = estimate_max_in_flight(
        g, budget, 128, 64, 512, page_size=16, kv_dtype="int8"
    )
    h = estimate_max_in_flight(
        g, budget, 128, 64, 512, page_size=16, prefix_hit_rate=0.9
    )
    qh = estimate_max_in_flight(
        g, budget, 128, 64, 512, page_size=16, kv_dtype="int8",
        prefix_hit_rate=0.9,
    )
    # int8: just under 4x (scale pools eat a sliver); sharing stacks
    assert 3 * base < q < 4 * base
    assert h > 2 * base
    assert qh > q and qh > h
    # reserve admission ignores the hit rate (worst case: all COW)
    rsv = estimate_max_in_flight(
        g, budget, 128, 64, 512, page_size=16, admission="reserve",
        max_new_tokens=256, prefix_hit_rate=0.9,
    )
    rsv0 = estimate_max_in_flight(
        g, budget, 128, 64, 512, page_size=16, admission="reserve",
        max_new_tokens=256,
    )
    assert rsv == rsv0
    with pytest.raises(ValueError, match="paged"):
        estimate_max_in_flight(g, budget, 128, 64, 512, kv_dtype="int8")
    with pytest.raises(ValueError, match="paged"):
        estimate_max_in_flight(g, budget, 128, 64, 512, prefix_hit_rate=0.5)
    with pytest.raises(ValueError, match="prefix_hit_rate"):
        estimate_max_in_flight(
            g, budget, 128, 64, 512, page_size=16, prefix_hit_rate=1.5
        )


def test_decode_cost_prices_int8_bytes(lm):
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.core.types import OperatorType

    cm = CostModel(MachineSpec(num_nodes=1, chips_per_node=1), measure=False)
    node = next(
        n for n in lm.graph.nodes.values()
        if n.op_type == OperatorType.MULTIHEAD_ATTENTION
    )
    c32 = cm.decode_op_cost(node, 8, 256, page_size=16, kernel="pallas")
    c8 = cm.decode_op_cost(
        node, 8, 256, page_size=16, kernel="pallas", kv_dtype="int8"
    )
    assert c8.forward_time < c32.forward_time
    assert c8.memory < c32.memory
    v32 = cm.verify_op_cost(node, 8, 256, 3, page_size=16)
    v8 = cm.verify_op_cost(node, 8, 256, 3, page_size=16, kv_dtype="int8")
    assert v8.forward_time < v32.forward_time


def test_search_serving_strategy_carries_dtype(lm):
    from flexflow_tpu.search.auto import search_serving_strategy

    lm.config.serve_kv_dtype = "int8"
    lm.config.serve_prefix_cache = True
    try:
        q = search_serving_strategy(
            lm, batch_size=4, mean_prompt_len=64, mean_gen_len=32,
            prefix_hit_rate=0.8,
        )
        lm.config.serve_kv_dtype = "fp32"
        lm.config.serve_prefix_cache = False
        f = search_serving_strategy(
            lm, batch_size=4, mean_prompt_len=64, mean_gen_len=32
        )
    finally:
        lm.config.serve_kv_dtype = "fp32"
        lm.config.serve_prefix_cache = False
    assert q.max_in_flight > f.max_in_flight


# -- telemetry ----------------------------------------------------------------


def test_prefix_telemetry_counters_and_gauges(lm):
    shared, cache, sched = _run(
        lm, _shared_requests(), prefix_cache=True
    )
    counters = cache.telemetry_counters()
    assert counters["kv_prefix_hits_total"] == cache.prefix_hits > 0
    assert counters["kv_cow_copies_total"] == cache.cow_copies
    gauges = cache.telemetry_gauges()
    assert "kv_prefix_pages_shared" in gauges
    assert "kv_pages_live" in gauges
    assert sched.stats.prefix_hits == cache.prefix_hits
    assert sched.stats.cow_copies == cache.cow_copies
