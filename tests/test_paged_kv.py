"""Paged KV cache (flexflow_tpu/serving/kv_cache.py PagedKVCache +
ops/attention.paged_decode_attention): token-for-token equivalence with
the cache-free full forward across admit/finish/re-admit schedules (page
reuse), allocator invariants (no double allocation, free-list
conservation, preemption-free reserve), the capacity win on
short-request workloads at a fixed byte budget, page-geometry config
wiring/validation, and the page-aware decode cost/capacity estimates.
All CPU-fast (tier 1)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import (
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.serving import (
    ContinuousBatchingScheduler,
    KVCacheSpec,
    PagedKVCache,
    Request,
    ServeConfig,
    build_scheduler,
    default_page_size,
)
from tests.conftest import page_geometry, ref_generate

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


def _requests(spec):
    return [
        Request(
            rid=i,
            prompt=[(i * 7 + j) % (VOCAB - 1) + 1 for j in range(1 + i % 5)],
            max_new_tokens=n,
        )
        for i, n in enumerate(spec)
    ]


# -- paged cache vs the cache-free forward -------------------------------------


def test_paged_equals_slot_token_stream(lm):
    """Greedy decode through the paged cache is token-for-token identical
    to recomputing the whole forward for every token, at both page
    geometries, on a schedule that admits, finishes, and
    re-admits requests (forced page reuse: 10 requests through 2 slots)."""
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 2], [7], [11, 12],
               [3, 3, 3], [8, 1], [2], [5, 9, 13], [6, 6]]
    want = [ref_generate(lm, p, 6) for p in prompts]
    for layout in ("one_page", "paged"):
        got = lm.generate(
            prompts,
            max_new_tokens=6,
            serve_config=ServeConfig(
                max_seqs=2, max_seq_len=32, **page_geometry(layout, 32)
            ),
        )
        assert got == want, layout


def test_paged_decode_logits_match_slot(lm):
    """Numeric (not just argmax) agreement: one prefill + one decode at
    each page geometry yields the full forward's logits."""
    prompt = [3, 1, 4, 1, 5]
    for layout in ("one_page", "paged"):
        _, engine, cache = build_scheduler(
            lm,
            ServeConfig(
                max_seqs=2, max_seq_len=32, **page_geometry(layout, 32)
            ),
        )
        slot = cache.alloc(len(prompt), len(prompt) + 2)
        nxt, last = engine.prefill(lm.params, [prompt], [slot])
        tokens = np.zeros(cache.spec.max_seqs, dtype=np.int32)
        active = np.zeros(cache.spec.max_seqs, dtype=bool)
        tokens[slot] = int(nxt[0])
        active[slot] = True
        _, dec = engine.decode(lm.params, tokens, active)
        seq = prompt + [int(nxt[0])]
        full = np.asarray(
            lm.forward({"tokens": np.asarray([seq], dtype=np.int32)})
        )[0]
        np.testing.assert_allclose(last[0], full[len(prompt) - 1], atol=1e-5)
        np.testing.assert_allclose(dec[slot], full[len(prompt)], atol=1e-5)


def test_paged_decode_attention_matches_dense():
    """paged_decode_attention over a shuffled page pool reproduces
    decode_attention over the equivalent contiguous cache."""
    from flexflow_tpu.ops.attention import (
        decode_attention,
        paged_decode_attention,
    )

    rng = np.random.default_rng(0)
    b, max_len, h, d, ps = 3, 32, 4, 8, 8
    mpps = max_len // ps
    num_pages = b * mpps + 2
    k_pool = rng.normal(size=(num_pages, ps, h, d)).astype(np.float32)
    v_pool = rng.normal(size=(num_pages, ps, h, d)).astype(np.float32)
    # each sequence gets a random page walk; sentinel-pad the tail
    perm = rng.permutation(num_pages)
    tables = np.full((b, mpps), num_pages, dtype=np.int32)
    lengths = np.array([5, 17, 31], dtype=np.int32)
    used = 0
    for i in range(b):
        n = -(-int(lengths[i] + 1) // ps)
        tables[i, :n] = perm[used: used + n]
        used += n
    # contiguous view of the same positions
    k_ctg = np.zeros((b, max_len, h, d), np.float32)
    v_ctg = np.zeros((b, max_len, h, d), np.float32)
    for i in range(b):
        for pi in range(mpps):
            if tables[i, pi] < num_pages:
                k_ctg[i, pi * ps:(pi + 1) * ps] = k_pool[tables[i, pi]]
                v_ctg[i, pi * ps:(pi + 1) * ps] = v_pool[tables[i, pi]]
    q = rng.normal(size=(b, 1, h, d)).astype(np.float32)
    want = decode_attention(
        jnp.asarray(q), jnp.asarray(k_ctg), jnp.asarray(v_ctg),
        jnp.asarray(lengths),
    )
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lengths),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# -- allocator invariants -----------------------------------------------------


def _check_allocator_invariants(cache, injector=None):
    """Full allocator consistency probe, shared with the resilience
    suite (tests/test_resilience.py imports it): the cache's own
    check_invariants (per-slot ledger vs table, reserve re-derivation,
    conservation — counting any pages a FaultInjector is deliberately
    holding) plus the historical explicit asserts."""
    spec = cache.spec
    extra = injector.stolen_pages if injector is not None else 0
    cache.check_invariants(extra_free=extra)
    live = [
        int(p)
        for row in cache.block_tables
        for p in row
        if p != spec.num_pages
    ]
    # no double allocation: a page's table multiplicity is exactly its
    # refcount — 1 everywhere unless the prefix cache shared it
    refs = getattr(cache, "_refcounts", None)
    for p in set(live):
        expect = int(refs[p]) if refs is not None else 1
        assert live.count(p) == expect, (p, live.count(p), expect)
    # free-list conservation over UNIQUE pages: free + held + pinned
    # for a step still in flight (released by a retire, back on the free
    # list at that step's reconcile) (+ injector-stolen) = pool
    uniq = set(live)
    pinned = {p for p, _ in cache._limbo}
    assert len(pinned) == cache.pinned_pages
    assert uniq.isdisjoint(cache._free_pages)
    assert pinned.isdisjoint(cache._free_pages) and pinned.isdisjoint(uniq)
    assert (
        len(uniq) + cache.num_free_pages + len(pinned) + extra
        == spec.num_pages
    )
    assert cache.pages_in_use == len(uniq) + len(pinned) + extra
    # the reserve never promises pages the pool doesn't have (a pinned
    # page returns before any claim that needs it: the drain-first rule)
    assert 0 <= cache._reserved <= cache.num_free_pages + len(pinned) + extra


def test_allocator_invariants_through_schedule(lm):
    """Invariants hold at EVERY iteration of a churning schedule (admit /
    grow across page boundaries / retire / re-admit), and the pool drains
    back to empty."""
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=3, max_seq_len=32,
                    kv_page_size=8),
    )
    for r in _requests([2, 9, 4, 1, 7, 3, 5, 8, 2, 6]):
        sched.submit(r)
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache)
    assert len(sched.finished) == 10
    assert cache.num_active == 0
    assert cache.pages_in_use == 0
    assert cache.num_free_pages == cache.spec.num_pages
    assert cache._reserved == 0
    assert np.all(cache.block_tables == cache.spec.num_pages)


def test_reserve_policy_is_preemption_free(lm):
    """Admission reserves each request's worst case, so growth across
    page boundaries never exhausts the pool: a tight pool admits only
    what it can finish, and the queue drains without the allocator ever
    raising."""
    # pool of 8 pages of 8 = 64 rows for max_seqs=4 x max_len=32: half
    # the default capacity, so admission must throttle on pages
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32,
                    kv_page_size=8, kv_pages=8),
    )
    reqs = _requests([20, 20, 20, 20, 20])  # each needs 3 pages worst-case
    done = sched.run(reqs)
    assert len(done) == 5
    for r in done:
        assert len(r.generated) == 20
    assert cache.pages_in_use == 0
    # 8 pages / 3-per-request worst case -> at most 2 concurrent
    assert sched.stats.peak_in_flight == 2


def test_paged_capacity_beats_slot_on_short_requests(lm):
    """The acceptance criterion, deterministically: at the SAME byte
    budget (max_seqs * max_len rows), pages of 16 admit >= 1.5x
    more concurrent short requests than one page of max_len a request."""
    max_seqs, max_len = 2, 32
    ps = default_page_size(max_len)
    budget_pages = max_seqs * max_len // ps  # 4 pages of 16
    peak = {}
    for name, serve in (
        ("one_page", ServeConfig(max_seqs=8, max_seq_len=max_len,
                                 kv_page_size=max_len, kv_pages=max_seqs)),
        ("paged", ServeConfig(max_seqs=8, max_seq_len=max_len,
                              kv_page_size=ps,
                              kv_pages=budget_pages)),
    ):
        sched, _, _ = build_scheduler(lm, serve)
        # short profile: prompt 1-3 + 4 generated << max_len 32
        sched.run(
            [
                Request(rid=i, prompt=[(i + j) % (VOCAB - 1) + 1
                                       for j in range(1 + i % 3)],
                        max_new_tokens=4)
                for i in range(8)
            ]
        )
        peak[name] = sched.stats.peak_in_flight
    assert peak["one_page"] == max_seqs
    assert peak["paged"] >= 1.5 * peak["one_page"]


def test_optimistic_alloc_reserves_nothing():
    """Optimistic admission charges only the pages needed NOW, keeps the
    reserve ledger at zero for its slots, and raises PagePoolExhausted
    (instead of over-promising) when a later claim finds the pool dry —
    the trigger for the scheduler's preemption-by-recompute. Reserve
    accounting for coexisting reserve-admitted slots is untouched."""
    import jax.numpy as jnp

    from flexflow_tpu.serving.kv_cache import PagePoolExhausted

    spec = KVCacheSpec(
        layer_guids=(1,), max_seqs=4, max_len=32, num_heads=2, head_dim=4,
        buckets=(32,), page_size=4, num_pages=10,
    )
    cache = PagedKVCache(spec, jnp.float32)
    # reserve-admitted neighbor: 1 page held, 2 more reserved
    rsv = cache.alloc(4, 12)
    assert cache._reserved == 2
    # optimistic slot: worst case 32 tokens = 8 pages would NOT fit on
    # top of the neighbor's reserve, but its 2 prompt pages do
    assert not cache.can_admit(8, 32)
    opt = cache.alloc(8, 32, optimistic=True)
    assert opt is not None
    assert cache._reserved == 2  # unchanged: no optimistic reserve
    _check_allocator_invariants(cache)
    # grow the optimistic slot until free - reserved hits zero:
    # 10 - 1 - 2 held leaves 7 free, 2 reserved -> 5 more claims succeed
    for pos in range(8, 28, 4):
        cache.ensure_position(opt, pos)
    assert cache.num_free_pages - cache._reserved == 0
    with pytest.raises(PagePoolExhausted, match="optimistic"):
        cache.ensure_position(opt, 28)
    # the reserve-admitted slot's guaranteed claims still succeed
    cache.ensure_position(rsv, 4)
    cache.ensure_position(rsv, 8)
    assert cache._reserved == 0
    _check_allocator_invariants(cache)
    # truncate returns optimistic pages to the COMMON pool (reserve flat)
    cache.truncate(opt, 9)
    assert cache._reserved == 0
    assert int(cache._max_pages[opt]) == int(cache._held[opt]) == 3
    _check_allocator_invariants(cache)
    cache.free(opt)
    cache.free(rsv)
    _check_allocator_invariants(cache)
    assert cache.num_free_pages == spec.num_pages


# -- config wiring / validation ----------------------------------------------


def test_kv_flags_parse():
    cfg = FFConfig.parse_args(["--kv-page-size", "8", "--kv-pages", "64"])
    sc = ServeConfig.from_config(cfg)
    assert sc.kv_page_size == 8
    assert sc.kv_pages == 64
    # defaults: auto geometry
    sc = ServeConfig.from_config(FFConfig.parse_args([]))
    assert (sc.kv_page_size, sc.kv_pages) == (0, 0)


def test_kv_layout_is_not_an_option():
    """One cache layout: the constructor has no such field, the flag
    parser no such flag (it takes an unknown flag for a script's own,
    as it always did, and sets nothing), and the name reads "paged"."""
    with pytest.raises(TypeError, match="kv_layout"):
        ServeConfig(kv_layout="paged")
    assert "kv_layout" not in {f.name for f in dataclasses.fields(ServeConfig)}
    assert ServeConfig().kv_layout == "paged"
    with pytest.raises(AttributeError):
        ServeConfig().kv_layout = "slot"
    cfg = FFConfig.parse_args(["--kv-layout", "slot", "--kv-page-size", "8"])
    assert not hasattr(cfg, "serve_kv_layout")
    assert cfg.serve_kv_page_size == 8
    assert ServeConfig.from_config(cfg).kv_layout == "paged"


def test_page_geometry_validation(lm):
    with pytest.raises(ValueError, match="divisible"):
        ServeConfig(max_seqs=2, max_seq_len=30, kv_page_size=16)
    # a pool too small to hold one max_len sequence is rejected
    with pytest.raises(ValueError, match="num_pages"):
        PagedKVCache.from_model(
            lm, max_seqs=2, max_len=32, page_size=16, num_pages=1
        )


def test_default_geometry_matches_slot_capacity(lm):
    """kv_page_size=0/kv_pages=0 derive a pool in which every slot can
    reach max_seq_len: max_seqs * max_seq_len rows, whatever the page."""
    _, _, paged = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32)
    )
    _, _, one = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32, kv_page_size=32)
    )
    assert paged.spec.total_rows == one.spec.total_rows == 4 * 32
    assert paged.spec.total_bytes == one.spec.total_bytes
    assert paged.spec.total_bytes == (
        2 * 4 * 4 * 32 * paged.spec.num_heads * paged.spec.head_dim
        * len(paged.spec.layer_guids)
    )
    assert paged.spec.page_size == default_page_size(32)
    assert (one.spec.num_pages, one.spec.max_pages_per_seq) == (4, 1)


# -- spec byte accounting (the bytes_per_layer bugfix) ------------------------


def test_bytes_per_layer_uses_dtype_itemsize(lm):
    cache32 = PagedKVCache.from_model(lm, max_seqs=2, max_len=32)
    cache16 = PagedKVCache.from_model(
        lm, max_seqs=2, max_len=32, dtype=jnp.bfloat16
    )
    assert cache32.spec.itemsize == 4
    assert cache16.spec.itemsize == 2
    assert cache32.spec.bytes_per_layer == 2 * cache16.spec.bytes_per_layer
    # 2 (K and V) * itemsize * rows * heads * head_dim
    spec = cache32.spec
    assert spec.bytes_per_layer == (
        2 * 4 * spec.num_pages * spec.page_size * spec.num_heads * spec.head_dim
    )
    assert spec.total_bytes == spec.bytes_per_layer * len(spec.layer_guids)


def test_spec_total_rows_both_layouts():
    base = dict(
        layer_guids=(1, 2), max_seqs=4, max_len=64, num_heads=4, head_dim=8,
        buckets=(64,),
    )
    one = KVCacheSpec(**base, page_size=64, num_pages=4)
    paged = KVCacheSpec(**base, page_size=16, num_pages=10, itemsize=2)
    assert one.total_rows == 4 * 64 and one.max_pages_per_seq == 1
    assert paged.total_rows == 160
    assert paged.max_pages_per_seq == 4
    assert paged.bytes_per_layer == 2 * 2 * 160 * 4 * 8


# -- page-aware decode cost + capacity estimate -------------------------------


def test_decode_cost_rounds_kv_to_page_granularity():
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.cost_model import CostModel

    cfg = FFConfig(batch_size=4)
    m = FFModel(cfg)
    tok = m.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(m, tok, vocab_size=128, hidden=64, num_heads=4)
    cm = CostModel(MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e"))
    mha = next(
        n for n in m.graph.nodes.values()
        if n.op_type.name == "MULTIHEAD_ATTENTION"
    )
    flat = cm.decode_op_cost(mha, batch=1, kv_len=100)
    paged = cm.decode_op_cost(mha, batch=1, kv_len=100, page_size=64)
    aligned = cm.decode_op_cost(
        mha, batch=1, kv_len=128, page_size=64, kernel="pallas"
    )
    exact = cm.decode_op_cost(mha, batch=1, kv_len=128)
    # 100 positions round up to 2 pages of 64 = 128 rows streamed/held
    assert paged.memory == aligned.memory == exact.memory
    assert paged.memory > flat.memory
    # on the kernel path (one page-granular pool read, no gather),
    # page-aligned lengths price identically to the flat layout; the
    # dense fallback additionally pays the gather's write + re-read
    assert aligned.forward_time == exact.forward_time
    dense_aligned = cm.decode_op_cost(mha, batch=1, kv_len=128, page_size=64)
    assert dense_aligned.forward_time > aligned.forward_time
    assert dense_aligned.memory == aligned.memory


def test_max_in_flight_estimate_prefers_paging():
    from flexflow_tpu.search.auto import estimate_max_in_flight

    cfg = FFConfig(batch_size=4)
    m = FFModel(cfg)
    tok = m.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(m, tok, vocab_size=128, hidden=64, num_heads=4)
    budget = 64 << 20
    kw = dict(mean_prompt_len=16, mean_gen_len=16, max_len=1024)
    slot = estimate_max_in_flight(m.graph, budget, **kw)
    paged = estimate_max_in_flight(m.graph, budget, page_size=16, **kw)
    # short requests (32 of 1024 positions): page_size 0 charges max_len
    # rows, pages of 16 charge 2 -> 32x more sequences fit
    assert paged == 32 * slot
    # TP over heads halves per-chip row bytes -> twice the sequences
    assert estimate_max_in_flight(
        m.graph, budget, page_size=16, tp=2, **kw
    ) == 2 * paged


def test_optimize_serving_reports_capacity():
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import optimize_serving

    cfg = FFConfig(batch_size=4)
    m = FFModel(cfg)
    tok = m.create_tensor([4, 128], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        m, tok, vocab_size=512, hidden=256, num_heads=8, num_layers=2,
        ff_dim=1024,
    )
    spec = MachineSpec(num_nodes=1, chips_per_node=4, chip="v5e")
    kw = dict(batch_size=1, kv_len=1024, mean_prompt_len=64, mean_gen_len=64,
              max_len=4096)
    slot = optimize_serving(m.graph, 4, spec, **kw)
    paged = optimize_serving(m.graph, 4, spec, page_size=16, **kw)
    assert slot.max_in_flight is not None
    assert paged.max_in_flight > slot.max_in_flight
    assert paged.page_size == 16
    assert "seqs fit" in paged.describe()


def test_engine_page_boundary_growth(lm):
    """A single long generation crosses several page boundaries: pages are
    claimed lazily (held pages grow during decode) and the output matches
    the one of a single page that never grows."""
    outs = {}
    held = {}
    for layout, page in (("one_page", 32), ("paged", 4)):
        sc = ServeConfig(max_seqs=1, max_seq_len=32, kv_page_size=page)
        sched, _, cache = build_scheduler(lm, sc)
        sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=20))
        held[layout] = []
        while sched.queue or sched.running:
            sched.step()
            if cache.num_active:
                held[layout].append(int(cache._held[0]))
        outs[layout] = sched.finished[0].generated
    assert outs["paged"] == outs["one_page"] == ref_generate(lm, [1, 2, 3], 20)
    # 3-token prompt in pages of 4 starts with 1 page and grows lazily
    assert held["paged"][0] == 1
    assert max(held["paged"]) > 1
    assert set(held["one_page"]) == {1}


def test_one_page_never_grows(lm):
    """One page a slot (kv_page_size = max_seq_len, default pool): after
    every scheduler step the pages in use are the active slots, the
    block table is one wide, and no decode, verify or chunk step claims
    a page (`ensure_position` installs nothing after admission)."""
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, kv_page_size=32, spec_draft="ngram",
        spec_k=3, debug_invariants=True,
    )
    sched, _, cache = build_scheduler(lm, serve)
    assert cache.block_tables.shape == (4, 1)
    assert cache.spec.num_pages == 4
    installs = []
    install = cache._install_page

    def counting(slot, pi, page):
        installs.append((sched.stats.iterations, slot, pi))
        install(slot, pi, page)

    cache._install_page = counting
    for r in _requests([9, 3, 12, 5, 7, 2, 10]):
        sched.submit(r)
    while sched.queue or sched.running:
        sched.step()
        assert cache.pages_in_use == cache.num_active
        assert cache._reserved == 0
        # one install a request, at its admission, of its one page
        assert all(pi == 0 for _, _, pi in installs)
    assert len(sched.finished) == 7 and all(r.ok for r in sched.finished)
    assert len(installs) == 7
    assert sched.stats.verify_steps > 0
    assert cache.pages_in_use == 0
