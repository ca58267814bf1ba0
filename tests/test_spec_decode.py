"""Speculative decoding subsystem (flexflow_tpu/serving/spec.py +
GenerationEngine.verify + cache truncate/rollback): greedy spec decode is
token-for-token identical to plain greedy decode on BOTH kv layouts
(streams and logits), verify logits match sequential decode logits
numerically, cache allocator invariants hold across rollback (no leaked
or double-freed pages), EOS inside an accepted run retires at the EOS
position, the acceptance rule preserves determinism under sampling, and
the acceptance-aware cost family (verify_op_cost / optimize_spec_k)
prices the draft-length trade. Plus the satellites that ride along:
heap-based O(log n) slot/page release, per-(slot, position) PRNG keys,
and TTFT / per-token decode latency stats. All CPU-fast (tier 1)."""

import numpy as np
import pytest

import jax

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
    NGramDraftProposer,
    ModelDraftProposer,
    PagedKVCache,
    Request,
    ServeConfig,
    accept_drafts,
    build_scheduler,
    latency_percentiles,
)
from tests.conftest import page_geometry

pytestmark = pytest.mark.serving

VOCAB = 50


def _lm(seed=0, hidden=32, layers=2, heads=4, ff=64, vocab=VOCAB):
    cfg = FFConfig(batch_size=4, seed=seed)
    model = FFModel(cfg)
    tok = model.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        model, tok, vocab_size=vocab, hidden=hidden, num_heads=heads,
        num_layers=layers, ff_dim=ff,
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


@pytest.fixture(scope="module")
def draft_lm():
    # smaller and differently seeded: a REAL draft (imperfect agreement)
    return _lm(seed=3, hidden=16, layers=1, ff=32)


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 2], [7], [11, 12]]


# -- greedy equivalence (the core contract) -----------------------------------


@pytest.mark.parametrize("layout", ["one_page", "paged"])
@pytest.mark.parametrize("draft", ["ngram", "model"])
def test_greedy_spec_equals_plain(lm, draft_lm, layout, draft):
    """Greedy speculative decode (either proposer) produces EXACTLY the
    plain greedy stream at both page geometries — the draft changes when
    tokens arrive, never which."""
    plain = lm.generate(
        PROMPTS,
        max_new_tokens=8,
        serve_config=ServeConfig(
            max_seqs=2, max_seq_len=32, **page_geometry(layout, 32)
        ),
    )
    spec = lm.generate(
        PROMPTS,
        max_new_tokens=8,
        serve_config=ServeConfig(
            max_seqs=2, max_seq_len=32, **page_geometry(layout, 32),
            spec_draft=draft, spec_k=4,
        ),
        draft_model=draft_lm if draft == "model" else None,
    )
    assert spec == plain


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_verify_logits_match_sequential_decode(lm, layout):
    """The verify step's w-position logits agree NUMERICALLY with w
    sequential decode steps feeding the same tokens — the staircase mask
    reproduces decode's per-position causal view, so acceptance judges
    drafts against the same distributions plain decode samples."""
    prompt = [3, 1, 4, 1, 5]
    # engine A: sequential decodes
    _, eng_a, cache_a = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32, **page_geometry(layout, 32)),
    )
    slot = cache_a.alloc(len(prompt), len(prompt) + 6)
    nxt, _ = eng_a.prefill(lm.params, [prompt], [slot])
    toks = [int(nxt[0])]
    seq_logits = []
    for _ in range(4):
        tokens = np.zeros(cache_a.spec.max_seqs, dtype=np.int32)
        active = np.zeros(cache_a.spec.max_seqs, dtype=bool)
        tokens[slot] = toks[-1]
        active[slot] = True
        step_next, logits = eng_a.decode(lm.params, tokens, active)
        seq_logits.append(logits[slot])
        toks.append(int(step_next[slot]))
    # engine B: ONE verify over the same token sequence
    _, eng_b, cache_b = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32, **page_geometry(layout, 32)),
    )
    slot_b = cache_b.alloc(len(prompt), len(prompt) + 6)
    eng_b.prefill(lm.params, [prompt], [slot_b])
    vt = np.zeros((cache_b.spec.max_seqs, 4), dtype=np.int32)
    vt[slot_b, :] = toks[:4]
    dl = np.zeros(cache_b.spec.max_seqs, dtype=np.int32)
    dl[slot_b] = 4
    vlogits = eng_b.verify(lm.params, vt, dl)
    np.testing.assert_allclose(
        vlogits[slot_b], np.stack(seq_logits), atol=1e-4
    )
    # greedy acceptance over plain decode's own tokens accepts everything
    accepted, emitted = accept_drafts(vlogits[slot_b], toks[1:4])
    assert accepted == 3
    assert emitted == toks[1:5]


def test_verify_rollback_then_continue_matches_plain(lm):
    """After a verify whose drafts are garbage (full rejection), the
    rolled-back cache continues generating the plain greedy stream —
    rejected rows leave no trace."""
    prompt = [3, 1, 4]
    ref = lm.generate(
        [prompt], max_new_tokens=6,
        serve_config=ServeConfig(max_seqs=1, max_seq_len=32,
                                 kv_page_size=4),
    )[0]
    _, engine, cache = build_scheduler(
        lm, ServeConfig(max_seqs=1, max_seq_len=32,
                        kv_page_size=4)
    )
    slot = cache.alloc(len(prompt), len(prompt) + 6)
    nxt, _ = engine.prefill(lm.params, [prompt], [slot])
    assert int(nxt[0]) == ref[0]
    # drafts chosen to disagree with the model (shift the real tokens)
    bad = [(t + 1) % VOCAB for t in ref[1:4]]
    vt = np.zeros((1, 4), dtype=np.int32)
    vt[0, 0] = ref[0]
    vt[0, 1:] = bad
    logits = engine.verify(lm.params, vt, np.array([4], dtype=np.int32))
    accepted, emitted = accept_drafts(logits[0], bad)
    assert accepted == 0 and emitted == [ref[1]]
    cache.truncate(slot, int(cache.lengths[slot]) + 1)
    # continue with plain decode: the stream must pick up exactly
    toks = [ref[1]]
    for _ in range(4):
        tokens = np.array([toks[-1]], dtype=np.int32)
        step_next, _ = engine.decode(lm.params, tokens, np.array([True]))
        toks.append(int(step_next[0]))
    assert [ref[0]] + toks == ref


# -- cache rollback / allocator invariants ------------------------------------


def _check_allocator_invariants(cache):
    spec = cache.spec
    live = [
        int(p)
        for row in cache.block_tables
        for p in row
        if p != spec.num_pages
    ]
    assert len(live) == len(set(live))  # no double allocation
    assert set(live).isdisjoint(cache._free_pages)
    assert len(live) + cache.num_free_pages == spec.num_pages
    assert 0 <= cache._reserved <= cache.num_free_pages


def test_allocator_invariants_through_spec_schedule(lm):
    """Page allocator invariants hold at EVERY iteration of a spec-mode
    schedule (verify claims pages for drafted rows, rollback returns
    them), and the pool drains to empty."""
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=3, max_seq_len=32,
                    kv_page_size=4, spec_draft="ngram", spec_k=4),
    )
    for i, n in enumerate([2, 9, 4, 1, 7, 3, 5, 8, 2, 6]):
        sched.submit(Request(
            rid=i, prompt=[(i * 7 + j) % (VOCAB - 1) + 1 for j in range(1 + i % 5)],
            max_new_tokens=n,
        ))
    while sched.queue or sched.running:
        sched.step()
        _check_allocator_invariants(cache)
    assert len(sched.finished) == 10
    assert all(len(r.generated) == r.max_new_tokens for r in sched.finished)
    assert cache.pages_in_use == 0
    assert cache.num_free_pages == cache.spec.num_pages
    assert cache._reserved == 0
    assert np.all(cache.block_tables == cache.spec.num_pages)


def test_truncate_paged_returns_pages_under_reserve():
    """Paged truncate frees exactly the pages past the kept length and
    returns them UNDER the slot's admission reserve — the preemption-free
    accounting survives rollback and re-growth."""
    spec_kw = dict(
        layer_guids=(1,), max_seqs=2, max_len=32, num_heads=2, head_dim=4,
        buckets=(32,), page_size=4, num_pages=16,
    )
    from flexflow_tpu.serving.kv_cache import KVCacheSpec

    import jax.numpy as jnp

    cache = PagedKVCache(KVCacheSpec(**spec_kw), jnp.float32)
    slot = cache.alloc(10, 24)  # holds 3 pages now, reserves 6 worst-case
    assert int(cache._held[slot]) == 3
    assert cache._reserved == 3
    # grow like a verify writing 6 more rows (positions 10..15 -> page 3)
    for pos in range(10, 16):
        cache.ensure_position(slot, pos)
    assert int(cache._held[slot]) == 4
    assert cache._reserved == 2
    free_before = cache.num_free_pages
    # roll back to 9 tokens: pages 2 and 3 return to the pool
    cache.truncate(slot, 9)
    assert int(cache._held[slot]) == 3
    assert cache.num_free_pages == free_before + 1
    assert cache._reserved == 3  # reserve re-covers the returned page
    assert cache.lengths[slot] == 9
    # truncating below what a length needs is rejected
    with pytest.raises(ValueError, match="holds"):
        cache.truncate(slot, 17)
    cache.free(slot)
    assert cache._reserved == 0
    assert cache.num_free_pages == cache.spec.num_pages


# -- EOS mid-verify (satellite) ----------------------------------------------


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_eos_mid_verify_retires_at_eos(lm, layout):
    """When the accepted run contains EOS, the request retires AT the
    EOS position and emits nothing past it — at both page geometries."""
    base_sc = ServeConfig(max_seqs=1, max_seq_len=32, **page_geometry(layout, 32))
    base = lm.generate([[1, 2, 3]], max_new_tokens=10,
                       serve_config=base_sc)[0]
    # an EOS the verify will accept mid-run: a token whose first
    # occurrence is past position 1 (so at least one token precedes it
    # in some verify window)
    eos = next(t for i, t in enumerate(base) if i >= 2)
    cut = base.index(eos)
    sched, _, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=1, max_seq_len=32, **page_geometry(layout, 32),
                    spec_draft="ngram", spec_k=4),
    )
    done = sched.run([
        Request(rid=0, prompt=[1, 2, 3], max_new_tokens=10, eos_token=eos),
        Request(rid=1, prompt=[5, 6], max_new_tokens=2),
    ])
    r0 = next(r for r in done if r.rid == 0)
    assert r0.generated == base[: cut + 1]  # truncated at eos, eos included
    assert r0.generated[-1] == eos
    assert eos not in r0.generated[:-1]
    # the slot recycled for the next request; no cache state leaked
    r1 = next(r for r in done if r.rid == 1)
    assert len(r1.generated) == 2
    assert cache.num_active == 0
    if layout == "paged":
        assert cache.pages_in_use == 0


# -- satellite: heap-based slot/page release ----------------------------------


def test_slot_release_order_deterministic(lm):
    """Slot release is heap-based (O(log n), no full sort) and reuse
    order stays lowest-id-first no matter the release order."""
    import heapq

    cache = PagedKVCache.from_model(lm, max_seqs=4, max_len=32)
    slots = [cache.alloc(1, 2) for _ in range(4)]
    assert slots == [0, 1, 2, 3]
    for s in (2, 0, 3, 1):  # scrambled release
        cache.free(s)
    free_list = cache._free_slots
    # the free structure is a valid min-heap at all times
    assert free_list[0] == min(free_list)
    assert sorted(free_list) == [0, 1, 2, 3]
    heapq.heappush(free_list, heapq.heappop(free_list))  # heap op works
    assert [cache.alloc(1, 2) for _ in range(4)] == [0, 1, 2, 3]


def test_paged_page_release_is_heap_ordered(lm):
    """Pages freed by retirement re-allocate lowest-id-first (the old
    sort(reverse=True) contract) without any full re-sort."""
    cache = PagedKVCache.from_model(
        lm, max_seqs=2, max_len=32, page_size=8, num_pages=8
    )
    a = cache.alloc(16, 16)  # pages 0, 1
    b = cache.alloc(16, 16)  # pages 2, 3
    pages_a = [int(p) for p in cache.block_tables[a, :2]]
    cache.free(a)
    c = cache.alloc(16, 16)  # must reuse a's pages, lowest first
    assert [int(p) for p in cache.block_tables[c, :2]] == sorted(pages_a)
    cache.free(b)
    cache.free(c)
    assert sorted(cache._free_pages) == list(range(8))


def test_kv_claim_specific_slot(lm):
    """`alloc(slot=)` takes the named slot under the same page
    accounting as any admission; the lowest-free order of unnamed
    admissions goes on round it."""
    cache = PagedKVCache.from_model(lm, max_seqs=3, max_len=32, page_size=8)
    assert cache.alloc(9, 20, slot=1) == 1
    assert cache._held[1] == 2 and cache._reserved == 1
    assert cache.alloc(1, 1) == 0  # lowest remaining
    with pytest.raises(ValueError, match="not a free slot"):
        cache.alloc(1, 1, slot=1)
    with pytest.raises(ValueError, match="not a free slot"):
        cache.alloc(1, 1, slot=3)
    cache.free(1)
    cache.check_invariants()
    assert sorted(cache._free_slots) == [1, 2]
    # a named slot is refused, not granted, when its pages are not there
    tight = PagedKVCache.from_model(
        lm, max_seqs=2, max_len=32, page_size=8, num_pages=4
    )
    assert tight.alloc(32, 32) == 0
    assert tight.alloc(8, 8, slot=1) is None
    assert tight.num_active == 1
    tight.check_invariants()


def test_draft_cache_follows_the_targets_slots(lm, draft_lm):
    """The draft model's paged cache through a request's life: admitted
    into the target's slot (not the lowest free one) with the whole of
    max_len reserved, rolled back and grown again page by page, freed
    with every page returned."""
    from flexflow_tpu.serving.spec import ModelDraftProposer

    prop = ModelDraftProposer(draft_lm, max_seqs=3, max_len=32)
    cache = prop.cache
    ps = cache.spec.page_size
    assert isinstance(cache, PagedKVCache)
    assert cache.spec.num_pages * ps == 3 * 32  # every slot can reach max_len
    prompt = list(range(1, ps + 4))  # two pages
    req = Request(rid=0, prompt=prompt, max_new_tokens=8)
    req.slot = 2
    prop.admit([req])
    assert cache.active_slots() == [2]
    assert int(cache.lengths[2]) == len(prompt)
    assert cache.pages_in_use == 2
    assert cache._reserved == 32 // ps - 2
    # drafting grows the slot through the allocator like any decode
    req.generated = [5]
    drafts = prop.propose({2: req}, k=3)
    assert len(drafts[2]) == 3
    assert int(cache.lengths[2]) == len(prompt) + 3
    # the target accepted nothing past the prompt: roll back into page one
    prop.rollback(2, ps - 1)
    assert int(cache.lengths[2]) == ps - 1
    assert cache.pages_in_use == 1
    cache.check_invariants()
    with pytest.raises(ValueError, match="not a free slot"):
        prop.admit([req])
    prop.retire(req)
    assert cache.num_active == 0 and cache.pages_in_use == 0
    assert cache._reserved == 0
    cache.check_invariants()


# -- satellite: per-slot PRNG keys --------------------------------------------


def test_sampling_independent_of_batch_composition(lm):
    """A request's sampled stream depends only on (seed, slot, its own
    tokens) — running it alone vs after another request (same slot,
    different iteration numbers) yields the identical stream. The old
    shared step-folded key failed exactly this."""
    sc = dict(max_seqs=1, max_seq_len=32, temperature=0.8, seed=7)
    alone = lm.generate(
        [[1, 2, 3]], 6, serve_config=ServeConfig(**sc)
    )[0]
    sched, _, _ = build_scheduler(lm, ServeConfig(**sc))
    done = sched.run([
        Request(rid=0, prompt=[9, 8], max_new_tokens=4),
        Request(rid=1, prompt=[1, 2, 3], max_new_tokens=6),
    ])
    later = next(r for r in done if r.rid == 1).generated
    assert later == alone


def test_sampled_generation_reproducible(lm):
    sc = dict(max_seqs=2, max_seq_len=32, temperature=0.8, seed=11)
    a = lm.generate([[1, 2], [3, 4, 5]], 5, serve_config=ServeConfig(**sc))
    b = lm.generate([[1, 2], [3, 4, 5]], 5, serve_config=ServeConfig(**sc))
    assert a == b
    c = lm.generate(
        [[1, 2], [3, 4, 5]], 5,
        serve_config=ServeConfig(seed=12, **{k: v for k, v in sc.items()
                                             if k != "seed"}),
    )
    assert c != a  # a different seed actually changes the draw


def test_spec_sampling_reproducible(lm):
    """Rejection-sampling verify replays exactly under a fixed seed."""
    sc = dict(max_seqs=2, max_seq_len=32, temperature=0.8, seed=7,
              spec_draft="ngram", spec_k=3)
    a = lm.generate([[1, 2], [3, 4, 5]], 6, serve_config=ServeConfig(**sc))
    b = lm.generate([[1, 2], [3, 4, 5]], 6, serve_config=ServeConfig(**sc))
    assert a == b


# -- acceptance rule ----------------------------------------------------------


def test_accept_drafts_greedy():
    logits = np.zeros((4, 10), dtype=np.float32)
    logits[0, 3] = 5.0  # after t0 -> 3
    logits[1, 7] = 5.0  # after d1=3 -> 7
    logits[2, 2] = 5.0  # after d2=7 -> 2
    logits[3, 9] = 5.0
    acc, em = accept_drafts(logits, [3, 7, 5])
    assert (acc, em) == (2, [3, 7, 2])  # d3=5 != 2: correction emitted
    acc, em = accept_drafts(logits, [3, 7, 2])
    assert (acc, em) == (3, [3, 7, 2, 9])  # full accept + bonus
    acc, em = accept_drafts(logits, [])
    assert (acc, em) == (0, [3])  # no drafts = plain decode


def test_accept_drafts_sampling_preserves_certainty():
    """With a near-delta target distribution, rejection sampling accepts
    a matching draft and replaces a mismatched one with the certain
    token — and is deterministic per (seed, slot, position)."""
    logits = np.full((2, 8), -30.0, dtype=np.float32)
    logits[0, 4] = 30.0
    logits[1, 6] = 30.0
    acc, em = accept_drafts(logits, [4], temperature=1.0, seed=0, slot=0,
                            base_len=5)
    assert (acc, em) == (1, [4, 6])
    acc, em = accept_drafts(logits, [3], temperature=1.0, seed=0, slot=0,
                            base_len=5)
    assert acc == 0 and em == [4]
    # deterministic replay
    again = accept_drafts(logits, [3], temperature=1.0, seed=0, slot=0,
                          base_len=5)
    assert (acc, em) == again


# -- satellite: TTFT + per-token decode latency -------------------------------


def test_ttft_and_decode_latency_stats(lm):
    sched, _, _ = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32)
    )
    done = sched.run([
        Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=6)
        for i in range(4)
    ])
    for r in done:
        assert r.first_token_time >= r.submit_time
        assert 0.0 <= r.ttft_s <= r.latency_s
        assert r.decode_s_per_token >= 0.0
    s = sched.stats
    assert s.finished_requests == 4
    assert s.mean_ttft_s > 0.0
    assert s.mean_decode_s_per_token > 0.0
    p = latency_percentiles(done, (50, 95), metric="ttft")
    q = latency_percentiles(done, (50,), metric="decode_per_token")
    total = latency_percentiles(done, (50,))
    assert 0.0 < p[50] <= total[50]
    assert q[50] > 0.0
    with pytest.raises(ValueError, match="metric"):
        latency_percentiles(done, (50,), metric="bogus")


def test_spec_stats_track_acceptance(lm):
    sched, _, _ = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32, spec_draft="ngram",
                        spec_k=4)
    )
    sched.run([
        Request(rid=i, prompt=[1 + i, 2], max_new_tokens=12)
        for i in range(3)
    ])
    s = sched.stats
    assert s.verify_steps > 0
    assert s.decode_steps == 0  # spec mode replaces decode entirely
    assert s.draft_tokens_accepted <= s.draft_tokens_proposed
    assert 0.0 <= s.acceptance_rate <= 1.0
    # tiny greedy LMs loop; prompt lookup must catch SOME of it
    assert s.draft_tokens_accepted > 0


# -- proposers ----------------------------------------------------------------


def test_ngram_proposer_lookup():
    class R:
        def __init__(self, prompt, generated):
            self.prompt = prompt
            self.generated = generated

    p = NGramDraftProposer(n=2)
    # ...5 6 9 [5 6] -> propose what followed the earlier [5 6]
    out = p.propose({0: R([5, 6, 9], [5, 6])}, k=3)
    assert out == {0: [9, 5, 6]}
    # no earlier occurrence -> no proposal
    assert p.propose({0: R([1, 2, 3], [4])}, k=3) == {}
    # too short -> no proposal
    assert p.propose({0: R([1], [])}, k=3) == {}
    with pytest.raises(ValueError, match="n-gram"):
        NGramDraftProposer(n=0)


def test_model_draft_same_weights_accepts_everything(lm):
    """A draft with the TARGET's own weights agrees on every greedy
    token — acceptance must be 1.0. This exercises the full
    slot-aligned draft-cache lifecycle (alloc/prefill/catch-up/rollback)
    with a draft that makes disagreement impossible."""
    serve = ServeConfig(max_seqs=2, max_seq_len=32, spec_draft="model",
                        spec_k=3)
    sched, _, _ = build_scheduler(lm, serve, draft_model=lm)
    sched.run([
        Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=10)
        for i in range(3)
    ])
    assert sched.stats.draft_tokens_proposed > 0
    assert sched.stats.acceptance_rate == 1.0


def test_model_draft_requires_draft_model(lm):
    with pytest.raises(ValueError, match="draft_model"):
        build_scheduler(
            lm, ServeConfig(spec_draft="model"), draft_model=None
        )


# -- config wiring ------------------------------------------------------------


def test_spec_flags_parse():
    cfg = FFConfig.parse_args(["--spec-draft", "ngram", "--spec-k", "6"])
    sc = ServeConfig.from_config(cfg)
    assert sc.spec_draft == "ngram"
    assert sc.spec_k == 6
    # defaults: off
    sc = ServeConfig.from_config(FFConfig.parse_args([]))
    assert (sc.spec_draft, sc.spec_k) == ("", 4)
    with pytest.raises(ValueError, match="spec_draft"):
        ServeConfig(spec_draft="oracle")
    with pytest.raises(ValueError, match="spec_k"):
        ServeConfig(spec_draft="ngram", spec_k=0)


# -- acceptance-aware cost model ----------------------------------------------


def _graph(hidden=1024, heads=16, layers=4, ff=4096, vocab=512):
    m = FFModel(FFConfig(batch_size=4))
    tok = m.create_tensor([4, 128], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(m, tok, vocab_size=vocab, hidden=hidden,
                     num_heads=heads, num_layers=layers, ff_dim=ff)
    return m.graph


def test_verify_cost_weights_stream_once():
    """verify(k) must cost FAR less than k+1 decode steps — the weight
    read amortizes, which is the whole point of speculation — while
    still costing at least one decode step."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import (
        estimate_decode_step,
        estimate_verify_step,
    )
    from flexflow_tpu.search.cost_model import CostModel

    graph = _graph()
    cm = CostModel(MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e"))
    d = estimate_decode_step(graph, cm, 1, 1, 1, 1024)
    v = estimate_verify_step(graph, cm, 1, 1, 1, 1024, k=4)
    assert d.step_time <= v.step_time < 2.0 * d.step_time
    assert v.step_time < 5 * d.step_time / 2.0
    # page rounding applies to the verify KV term too
    vp = estimate_verify_step(graph, cm, 1, 1, 1, 1000, k=4, page_size=64)
    vflat = estimate_verify_step(graph, cm, 1, 1, 1, 1000, k=4)
    assert vp.step_time >= vflat.step_time


def test_verify_op_cost_scales_with_k():
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.cost_model import CostModel

    graph = _graph(hidden=64, heads=4, layers=1, ff=128, vocab=128)
    cm = CostModel(MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e"))
    mha = next(
        n for n in graph.nodes.values()
        if n.op_type.name == "MULTIHEAD_ATTENTION"
    )
    c1 = cm.verify_op_cost(mha, batch=1, kv_len=512, k=1)
    c8 = cm.verify_op_cost(mha, batch=1, kv_len=512, k=8)
    assert c8.forward_time > c1.forward_time
    tp = cm.verify_op_cost(mha, batch=1, kv_len=512, k=8, tp=4)
    assert tp.forward_time < c8.forward_time


def test_optimize_spec_k_follows_acceptance():
    """Higher measured acceptance -> longer optimal draft and larger
    expected speedup; zero acceptance -> don't speculate (k = 0)."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import (
        expected_accepted_tokens,
        optimize_spec_k,
    )

    graph = _graph()
    spec = MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e")
    none = optimize_spec_k(graph, spec, acceptance_rate=0.0)
    low = optimize_spec_k(graph, spec, acceptance_rate=0.3)
    high = optimize_spec_k(graph, spec, acceptance_rate=0.9)
    assert none.k == 0 and none.speedup == 1.0
    assert 1 <= low.k <= high.k
    assert high.speedup > low.speedup > 1.0
    assert "tokens/step" in high.describe()
    # a model draft charges k draft decode steps against the win
    draft = _graph(hidden=128, heads=4, layers=1, ff=512)
    with_draft = optimize_spec_k(
        graph, spec, acceptance_rate=0.9, draft_graph=draft
    )
    assert with_draft.speedup < high.speedup
    assert with_draft.speedup > 1.0
    # E[accepted] sanity
    assert expected_accepted_tokens(0.5, 4) == pytest.approx(0.9375)
    assert expected_accepted_tokens(1.0, 6) == 6.0
    assert expected_accepted_tokens(0.0, 6) == 0.0
