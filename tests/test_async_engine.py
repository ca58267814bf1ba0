"""Async double-buffered engine (--serve-async;
serving/scheduler.AsyncContinuousBatchingScheduler + the
dispatch/reconcile split in serving/engine.py).

The load-bearing proofs: async greedy streams are TOKEN-IDENTICAL to
the synchronous reference loop at both page geometries, with speculation on
and off, under forced preemption, and through a seeded chaos schedule
whose NaN fault and mid-flight cancel land inside the in-flight window;
the paged allocator pins every page an in-flight step references (limbo)
and its full accounting holds INSIDE the window; and the dispatch/commit
stats split (overlap_fraction, mean_dispatch_gap_s) plus the
verify-cache LRU bound are observable. All CPU-fast (tier 1).
"""

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
from flexflow_tpu.models import (
    build_decoder_lm,
    build_deepseek_v3,
    build_kimi_linear,
    build_olmoe,
    build_ouro,
)
from flexflow_tpu.serving import (
    AsyncContinuousBatchingScheduler,
    ContinuousBatchingScheduler,
    FaultInjector,
    FaultPlan,
    InflightStep,
    KVCacheSpec,
    PagedKVCache,
    Request,
    RequestStatus,
    ServeConfig,
    TERMINAL_STATUSES,
    build_scheduler,
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


_PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [3, 1, 4, 1, 5], [7, 7, 2]]


def _requests(n=6, max_new=8, **kw):
    return [
        Request(rid=i, prompt=list(_PROMPTS[i % len(_PROMPTS)]),
                max_new_tokens=max_new, **kw)
        for i in range(n)
    ]


def _run(lm, serve_async, layout="paged", n=6, max_new=8, reqs=None,
         injector=None, **cfg_kw):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
        serve_async=serve_async, debug_invariants=True, **cfg_kw,
    )
    sched, engine, cache = build_scheduler(lm, serve, injector=injector)
    done = sched.run(reqs if reqs is not None else _requests(n, max_new))
    return sched, engine, cache, {r.rid: r for r in done}


# -- token-identity parity ----------------------------------------------------


@pytest.mark.parametrize(
    "layout,kw",
    [
        ("one_page", {}),
        ("paged", {}),
        ("paged", dict(kv_dtype="int8")),
        ("paged", dict(prefix_cache=True)),
        # the Pallas kernel, interpreted on the CPU
        ("paged", dict(decode_kernel="pallas")),
        ("paged", dict(decode_kernel="pallas", kv_dtype="int8")),
    ],
    ids=["one_page", "paged", "paged-int8", "paged-prefix", "paged-pallas",
         "paged-pallas-int8"],
)
def test_async_matches_sync_greedy_streams(lm, layout, kw):
    _, _, _, sync = _run(lm, False, layout, **kw)
    sched, engine, _, asy = _run(lm, True, layout, **kw)
    assert sched.stats.decode_steps_chained > 0
    assert engine.kernel_fallbacks == 0
    assert set(sync) == set(asy)
    for rid in sync:
        assert sync[rid].ok and asy[rid].ok
        assert sync[rid].generated == asy[rid].generated, rid


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_async_matches_sync_with_speculation(lm, layout):
    kw = dict(spec_draft="ngram", spec_k=3)
    _, _, _, sync = _run(lm, False, layout, max_new=12, **kw)
    sched, _, _, asy = _run(lm, True, layout, max_new=12, **kw)
    for rid in sync:
        assert sync[rid].generated == asy[rid].generated, rid
    # the in-flight window drafted ahead: every verify after the first
    # either reused a pre-proposal or rolled a misprediction back
    s = sched.stats
    assert s.pre_proposal_hits + s.pre_proposal_misses > 0
    assert s.verify_steps > 0 and s.draft_tokens_proposed > 0


def test_async_matches_sync_with_model_draft(lm):
    # a STATEFUL proposer never pre-drafts (its cache feeds have no
    # rollback story) — the async loop must stay token-identical while
    # recording zero pre-proposal traffic
    draft = _lm(seed=1)
    kw = dict(spec_draft="model", spec_k=3)
    serve = ServeConfig(max_seqs=4, max_seq_len=32, **kw)
    sync_sched, _, _ = build_scheduler(lm, serve, draft_model=draft)
    sync_done = {r.rid: r for r in sync_sched.run(_requests(6, 10))}
    serve = ServeConfig(max_seqs=4, max_seq_len=32, serve_async=True, **kw)
    asy_sched, _, _ = build_scheduler(lm, serve, draft_model=draft)
    asy_done = {r.rid: r for r in asy_sched.run(_requests(6, 10))}
    for rid in sync_done:
        assert sync_done[rid].generated == asy_done[rid].generated, rid
    assert asy_sched.stats.pre_proposal_hits == 0
    assert asy_sched.stats.pre_proposal_misses == 0


def test_async_matches_sync_with_eos_mid_stream(lm):
    # find a token the greedy continuation actually emits, then retire
    # on it: the EOS lands mid-window, the in-flight extra step's token
    # must be discarded, and streams must still match the sync loop
    _, _, _, plain = _run(lm, False, n=4, max_new=10)
    eos = int(plain[0].generated[len(plain[0].generated) // 2])
    _, _, _, sync = _run(lm, False, n=4, max_new=10, eos_token=eos)
    _, _, _, asy = _run(lm, True, n=4, max_new=10, eos_token=eos)
    assert any(
        r.generated and r.generated[-1] == eos for r in sync.values()
    )
    for rid in sync:
        assert sync[rid].generated == asy[rid].generated, rid


def test_async_no_wasted_slot_steps_on_budget_streams(lm):
    # without EOS the budget gate predicts every retirement, so the
    # async loop does exactly the sync loop's useful slot-work
    sync_sched, _, _, _ = _run(lm, False, n=8)
    asy_sched, _, _, _ = _run(lm, True, n=8)
    assert asy_sched.stats.busy_slot_steps == sync_sched.stats.busy_slot_steps
    assert asy_sched.stats.tokens_generated == (
        sync_sched.stats.tokens_generated
    )


# -- chunked prefill under the double-buffered loop ---------------------------


_CHUNK_PROMPTS = [
    [(i * 7 + j) % (VOCAB - 1) + 1 for j in range(n)]
    for i, n in enumerate([13, 22, 2, 18, 9])
]


def _chunked_requests(max_new=6, **kw):
    return [
        Request(rid=i, prompt=list(p), max_new_tokens=max_new, **kw)
        for i, p in enumerate(_CHUNK_PROMPTS)
    ]


@pytest.mark.parametrize("layout", ["one_page", "paged"])
@pytest.mark.parametrize(
    "spec_kw", [{}, dict(spec_draft="ngram", spec_k=3)],
    ids=["plain", "spec"],
)
def test_async_chunked_matches_sync_and_unchunked(lm, layout, spec_kw):
    """Chunked prefill commits only at reconcile under --serve-async:
    the async chunked run is token-identical to the sync chunked run
    AND to the unchunked sync reference, at both page geometries, with
    speculation on and off — while actually chunking (chunk_steps > 0)
    and keeping chunk steps in flight alongside decode/verify."""
    chunk_kw = dict(token_budget=8, chunk_size=4, decode_kernel="dense",
                    **spec_kw)
    _, _, _, plain = _run(lm, False, layout, reqs=_chunked_requests(),
                          **spec_kw)
    sync_sched, _, _, sync = _run(lm, False, layout,
                                  reqs=_chunked_requests(), **chunk_kw)
    asy_sched, _, _, asy = _run(lm, True, layout,
                                reqs=_chunked_requests(), **chunk_kw)
    assert set(plain) == set(sync) == set(asy)
    for rid in plain:
        assert plain[rid].ok and sync[rid].ok and asy[rid].ok, rid
        assert plain[rid].generated == sync[rid].generated, rid
        assert plain[rid].generated == asy[rid].generated, rid
    for sched in (sync_sched, asy_sched):
        assert sched.stats.chunk_steps > 0
        assert sched.stats.chunk_tokens == sum(
            len(p) for p in _CHUNK_PROMPTS
        )


def test_async_chunked_with_eos_mid_stream(lm):
    """EOS retirement interacting with partial prefill: streams still
    match the sync chunked loop when requests retire mid-window."""
    kw = dict(token_budget=8, chunk_size=4, decode_kernel="dense")
    _, _, _, plain = _run(lm, False, reqs=_chunked_requests(10), **kw)
    eos = int(plain[0].generated[len(plain[0].generated) // 2])
    _, _, _, sync = _run(
        lm, False, reqs=_chunked_requests(10, eos_token=eos), **kw
    )
    _, _, _, asy = _run(
        lm, True, reqs=_chunked_requests(10, eos_token=eos), **kw
    )
    # the retirement is real: at least one stream truncated at the eos
    assert any(
        len(r.generated) < 10 and r.generated[-1] == eos
        for r in sync.values()
    )
    for rid in sync:
        assert sync[rid].generated == asy[rid].generated, rid


# -- dispatch/commit stats ----------------------------------------------------


def test_overlap_and_dispatch_gap_stats(lm):
    sync_sched, _, _, sync = _run(lm, False)
    asy_sched, _, _, _ = _run(lm, True)
    for sched in (sync_sched, asy_sched):
        s = sched.stats
        assert s.dispatch_count > 0
        assert s.mean_dispatch_gap_s > 0.0
        assert 0.0 <= s.overlap_fraction <= 1.0
        assert s.commit_wait_s >= 0.0
    # the async loop interleaves a full iteration of host work between
    # dispatch and reconcile; the sync loop reconciles immediately
    assert (
        asy_sched.stats.overlapped_host_s
        > sync_sched.stats.overlapped_host_s
    )
    assert (
        asy_sched.stats.overlap_fraction > sync_sched.stats.overlap_fraction
    )
    # TTFT is stamped at commit: every finished request's TTFT is real
    # wall time, never the zero a dispatch-time stamp would produce
    assert all(r.ttft_s > 0.0 for r in sync.values())
    assert asy_sched.stats.mean_ttft_s > 0.0


# -- one-step-stale control events -------------------------------------------


def test_async_cancel_of_running_defers_to_reconcile(lm):
    serve = ServeConfig(max_seqs=4, max_seq_len=32, serve_async=True,
                        debug_invariants=True)
    sched, _, cache = build_scheduler(lm, serve)
    for r in _requests(4, max_new=12):
        sched.submit(r)
    for _ in range(3):  # fill the pipeline
        sched.step()
    assert sched._inflight
    victim = next(iter(sched.running.values()))
    assert sched.cancel(victim.rid) is True
    # deferred: still officially running until the next reconcile
    assert victim.status == RequestStatus.RUNNING
    assert victim.rid in sched._pending_cancels
    sched.run([])
    assert victim.status == RequestStatus.CANCELLED
    assert victim.slot is None
    assert all(
        r.status in (RequestStatus.FINISHED, RequestStatus.CANCELLED)
        for r in sched.finished
    )
    cache.check_invariants()


def test_async_chaos_window_loses_nothing(lm):
    """Seeded chaos whose NaN fault and cancel land INSIDE the in-flight
    window (keyed by dispatch iteration): the hit request fails/cancels,
    every other stream is token-identical to a fault-free async run, no
    request is lost, and the paged accounting holds every iteration."""
    for layout in ("one_page", "paged"):
        _, _, _, clean = _run(lm, True, layout, n=6, max_new=10)
        plan = FaultPlan(
            nan_iters={4: [1]},  # slot 1's step DISPATCHED at iter 4
            cancel_iters={5: [3]},  # rid 3 cancelled mid-window
        )
        injector = FaultInjector(plan, seed=7)
        sched, _, cache, done = _run(
            lm, True, layout, n=6, max_new=10, injector=injector,
        )
        assert injector.injected["nan"] >= 1
        assert injector.injected["cancel"] == 1
        lost = [r for r in done.values() if r.status not in TERMINAL_STATUSES]
        assert not lost
        assert done[3].status == RequestStatus.CANCELLED
        failed = [r.rid for r in done.values()
                  if r.status == RequestStatus.FAILED]
        assert len(failed) == 1
        affected = set(failed) | {3}
        for rid, req in clean.items():
            if rid in affected:
                continue
            assert done[rid].ok
            assert done[rid].generated == req.generated, (layout, rid)
        cache.check_invariants()


def test_async_forced_preemption_completes_all(lm):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32,
        kv_page_size=4, kv_pages=8,  # minimum legal pool: forces preemption
        admission="optimistic", max_preemptions=8,
        serve_async=True, debug_invariants=True,
    )
    sched, _, cache = build_scheduler(lm, serve)
    done = sched.run(_requests(6, max_new=10))
    assert all(r.ok for r in done), [(r.rid, r.status, r.error) for r in done]
    assert sched.stats.preemptions > 0
    # parity against the sync loop under the same pressure
    serve_sync = ServeConfig(
        max_seqs=4, max_seq_len=32,
        kv_page_size=4, kv_pages=8, admission="optimistic",
        max_preemptions=8, debug_invariants=True,
    )
    sync_sched, _, _ = build_scheduler(lm, serve_sync)
    sync_done = {r.rid: r.generated for r in sync_sched.run(_requests(6, 10))}
    for r in done:
        assert sync_done[r.rid] == r.generated, r.rid
    cache.check_invariants()


# -- in-flight page pinning ---------------------------------------------------


def _paged_cache(num_pages=12, page_size=4, max_seqs=3, max_len=16):
    spec = KVCacheSpec(
        layer_guids=(0,), max_seqs=max_seqs, max_len=max_len,
        num_heads=2, head_dim=4, buckets=(max_len,),
        page_size=page_size, num_pages=num_pages,
    )
    import jax.numpy as jnp

    return PagedKVCache(spec, jnp.float32)


def test_inflight_window_pins_released_pages():
    cache = _paged_cache()
    slot = cache.alloc(8, 8)
    free_before = cache.num_free_pages
    cache.begin_inflight()
    cache.free(slot)
    # the window pins the released pages: not free, not allocatable
    assert cache.pinned_pages == 2
    assert cache.num_free_pages == free_before
    cache.check_invariants()  # accounting holds INSIDE the window
    cache.end_inflight()
    assert cache.pinned_pages == 0
    assert cache.num_free_pages == free_before + 2
    cache.check_invariants()


def test_inflight_release_waits_for_the_window_open_at_release():
    """Steady-state pipeline shape: window 1 (step N) open, window 2
    (step N+1) opens, window 1 closes, THEN pages release — they must
    stay pinned until window 2 (whose snapshot tables reference them)
    closes, not drain at window 1's close."""
    cache = _paged_cache()
    s0 = cache.alloc(8, 8)
    cache.begin_inflight()  # window 1 = step N
    cache.begin_inflight()  # window 2 = step N+1 (dispatched first)
    cache.end_inflight()  # step N reconciles
    cache.free(s0)  # retire lands during window 2
    assert cache.pinned_pages == 2
    cache.check_invariants()
    cache.end_inflight()  # step N+1 reconciles
    assert cache.pinned_pages == 0
    cache.check_invariants()


def test_inflight_window_balance_is_enforced():
    cache = _paged_cache()
    with pytest.raises(RuntimeError):
        cache.end_inflight()


def test_reserve_claim_inside_window_names_pinned_pages():
    cache = _paged_cache(num_pages=4, page_size=4, max_seqs=2)
    s0 = cache.alloc(4, 16)  # reserve-mode: worst case 4 pages
    cache.ensure_position(s0, 4)
    cache.begin_inflight()
    cache.truncate(s0, 4)  # page released into limbo
    assert cache.pinned_pages == 1
    from flexflow_tpu.serving import PagePoolExhausted

    # 2 free + 1 limbo; growing back to 16 needs 3 claims — the one
    # that needs the pinned page back must say so (the async
    # scheduler's drain-then-retry path keys off this)
    for pos in (4, 8):
        cache.ensure_position(s0, pos)
    with pytest.raises(PagePoolExhausted, match="pinned by an in-flight"):
        cache.ensure_position(s0, 12)
    cache.end_inflight()
    cache.ensure_position(s0, 12)  # the released page satisfies it
    cache.check_invariants()


# -- verify-cache LRU ---------------------------------------------------------


def test_verify_cache_is_lru_bounded(lm):
    serve = ServeConfig(max_seqs=4, max_seq_len=32)
    _, engine, cache = build_scheduler(lm, serve)
    engine.verify_cache_max = 3
    slot = cache.alloc(2, 32)
    engine.prefill(lm.params, [[1, 2]], [slot])
    draft_lens = np.zeros(4, dtype=np.int32)
    draft_lens[slot] = 1
    for w in (1, 2, 3, 4, 5):
        tokens = np.zeros((4, w), dtype=np.int32)
        engine.verify(lm.params, tokens, draft_lens)
        cache.truncate(slot, 2)
        assert engine.verify_cache_entries <= 3
    # LRU, not FIFO: touching width 4 then adding width 6 evicts 5
    tokens = np.zeros((4, 4), dtype=np.int32)
    engine.verify(lm.params, tokens, draft_lens)
    cache.truncate(slot, 2)
    tokens = np.zeros((4, 6), dtype=np.int32)
    engine.verify(lm.params, tokens, draft_lens)
    cache.truncate(slot, 2)
    assert sorted(engine._verify_cache) == [4, 5, 6] or sorted(
        engine._verify_cache
    ) == [3, 4, 6]
    assert 4 in engine._verify_cache and 6 in engine._verify_cache
    assert engine.verify_cache_entries == 3


def test_verify_cache_entries_stat_flows_to_scheduler(lm):
    sched, _, _, _ = _run(lm, True, max_new=10, spec_draft="ngram", spec_k=3)
    assert sched.stats.verify_cache_entries >= 1


# -- wiring -------------------------------------------------------------------


def test_serve_async_flag_and_builder_wiring(lm):
    # the overlapped loop is the default; the flag names it, and
    # `--serve-async=0` / `serve_async=False` ask for the reference
    assert FFConfig().serve_async is True and ServeConfig().serve_async is True
    for argv, want in (
        ([], True), (["--serve-async"], True), (["--serve-async=1"], True),
        (["--serve-async=0"], False), (["--serve-async=false"], False),
    ):
        cfg = FFConfig.parse_args(argv)
        assert cfg.serve_async is want, argv
        assert ServeConfig.from_config(cfg).serve_async is want, argv
    sched, _, _ = build_scheduler(lm, ServeConfig(
        max_seqs=4, max_seq_len=32))
    assert isinstance(sched, AsyncContinuousBatchingScheduler)
    sched, _, _ = build_scheduler(lm, ServeConfig(
        max_seqs=4, max_seq_len=32, serve_async=False))
    assert not isinstance(sched, AsyncContinuousBatchingScheduler)
    assert isinstance(sched, ContinuousBatchingScheduler)


# -- the default loop, on every served model kind (ISSUE 38) ------------------

_TOY_VOCAB = 97
_TOYS = {
    "decoder_lm": lambda m, tok: build_decoder_lm(
        m, tok, vocab_size=_TOY_VOCAB, hidden=32, num_heads=4, num_layers=2,
        ff_dim=64,
    ),
    "olmoe": lambda m, tok: build_olmoe(
        m, tok, vocab_size=_TOY_VOCAB, hidden=32, num_heads=4, num_layers=2,
        expert_hidden=16, num_experts=4, experts_per_token=2,
    ),
    "deepseek_v3": lambda m, tok: build_deepseek_v3(
        m, tok, experts_held=(0, 2), vocab_size=_TOY_VOCAB, hidden=32,
        num_heads=4, num_layers=2, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, dense_hidden=48, dense_layers=1,
        expert_hidden=16, num_experts=4, experts_per_token=2,
        shared_experts=1, routed_scale=2.0, rope_theta=1e4, eps=1e-6,
    ),
    "ouro": lambda m, tok: build_ouro(
        m, tok, vocab_size=_TOY_VOCAB, hidden=32, num_heads=4, num_layers=2,
        ff_dim=48, loops=3, rope_theta=1e4, eps=1e-6,
    ),
    "kimi_linear": lambda m, tok: build_kimi_linear(
        m, tok, experts_held=(0, 2), vocab_size=_TOY_VOCAB, hidden=32,
        num_heads=4, num_layers=3, kda_layers=(1, 3), full_attn_layers=(2,),
        kda_head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, dense_hidden=48, dense_layers=1,
        expert_hidden=16, num_experts=4, experts_per_token=2,
        shared_experts=1, routed_scale=2.0, eps=1e-6, kda_chunk=8,
    ),
}


@pytest.fixture(scope="module", params=list(_TOYS))
def toy(request):
    model = FFModel(FFConfig(batch_size=4, seed=5))
    tok = model.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    head = _TOYS[request.param](model, tok)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1],
        **({"logits": head} if request.param == "ouro" else {}),
    )
    return model


#: seven requests over four slots, prompts of 2 to 9 tokens and budgets of
#: 1 to 9: slots turn over mid-run, so host-fed and chained steps mix
_MIXED = [
    ([1, 2, 3], 6), ([4, 5, 6, 7, 8, 9, 10, 11, 12], 9), ([9, 8], 1),
    ([11, 12, 13, 14], 7), ([21, 22], 3), ([5, 4, 3, 2, 1, 6], 8),
    ([31, 32, 33], 2),
]


def _mixed_run(model, **kw):
    serve = ServeConfig(max_seqs=4, max_seq_len=32, debug_invariants=True, **kw)
    sched, engine, _ = build_scheduler(model, serve)
    done = sched.run([
        Request(rid=i, prompt=list(p), max_new_tokens=n)
        for i, (p, n) in enumerate(_MIXED)
    ])
    assert all(r.ok for r in done)
    return sched, engine, {r.rid: list(r.generated) for r in done}


def test_default_loop_is_overlapped_and_token_identical(toy):
    sched, _, got = _mixed_run(toy)
    assert isinstance(sched, AsyncContinuousBatchingScheduler)
    ref, _, want = _mixed_run(toy, serve_async=False)
    assert not isinstance(ref, AsyncContinuousBatchingScheduler)
    assert got == want
    assert [len(got[i]) for i in range(len(_MIXED))] == [n for _, n in _MIXED]
    # budgets end every request: the dispatch's gate saw each end coming
    assert sched.stats.busy_slot_steps == ref.stats.busy_slot_steps
    assert sched.stats.decode_slot_steps_discarded == 0


def test_one_decode_program_whoever_feeds_a_slot(toy):
    sched, engine, _ = _mixed_run(toy)
    st = sched.stats
    # both kinds of step ran: a slot's first step after its prefill is fed
    # by the host, the rest from the step in flight
    assert 0 < st.decode_steps_chained < st.decode_steps
    assert engine._decode_jit._cache_size() == 1
    # and its first dispatch alone was forced (`_dispatch`)
    counts = bool(engine._count_fields)
    assert st.device_syncs == (
        (2 + counts) * st.prefill_batches + st.decode_steps + 1
    )


@pytest.mark.parametrize(
    "ending", ["budget", "eos", "sync"],
)
def test_engagement_counters(lm, ending):
    """`decode_steps_chained / decode_steps`: above 0.9 in a steady run,
    0 under the synchronous loop; `decode_slot_steps_discarded`: one for
    a request that ends on EOS, none for one that ends on its budget."""
    _, _, _, plain = _run(lm, False, n=1, max_new=24)
    stream = plain[0].generated
    assert len(stream) == 24
    kw = {}
    if ending == "eos":
        # a token whose first occurrence is late in the greedy stream
        at = max(stream.index(t) for t in set(stream))
        assert at >= 2
        kw["eos_token"] = stream[at]
    sched, _, _, done = _run(
        lm, ending != "sync", reqs=_requests(1, 24, **kw)
    )
    st = sched.stats
    assert done[0].ok
    if ending == "eos":
        assert done[0].generated == stream[: at + 1]
        assert st.decode_slot_steps_discarded == 1
        assert st.busy_slot_steps == at + 1  # one step past the last token
    else:
        assert done[0].generated == stream
        assert st.decode_slot_steps_discarded == 0
        assert st.busy_slot_steps == st.decode_steps == 23
    if ending == "sync":
        assert st.decode_steps_chained == 0
    elif ending == "budget":
        # every step but the first chained on the one in flight
        assert st.decode_steps_chained == st.decode_steps - 1
        assert st.decode_steps_chained / st.decode_steps > 0.9


def test_inflight_step_snapshot_is_immutable_view(lm):
    """The record the reconcile runs against must be HOST COPIES: later
    scheduler mutation of cache.lengths cannot leak into a dispatched
    step's snapshot."""
    serve = ServeConfig(max_seqs=4, max_seq_len=32)
    _, engine, cache = build_scheduler(lm, serve)
    slot = cache.alloc(2, 32)
    engine.prefill(lm.params, [[1, 2]], [slot])
    tokens = np.zeros(4, dtype=np.int32)
    active = np.zeros(4, dtype=bool)
    active[slot] = True
    step = engine.decode_dispatch(lm.params, tokens, active)
    assert isinstance(step, InflightStep)
    pre = int(step.lengths[slot])
    cache.lengths[slot] = 31  # hostile post-dispatch mutation
    assert int(step.lengths[slot]) == pre
    nxt, finite = engine.decode_reconcile(step)
    assert finite.shape == (4,) and finite.dtype == bool and finite[slot]
    assert np.isfinite(np.asarray(step.device_logits)[slot]).all()
    assert nxt.shape == (4,) and nxt.dtype == np.int32


# -- the record of every dispatched step program (ISSUE 54) -------------------


def _closed(rec):
    return rec.t_call <= rec.t_enqueued <= rec.t_read <= rec.t_ready


@pytest.mark.parametrize("serve_async", [True, False], ids=["async", "sync"])
@pytest.mark.parametrize(
    "kind,kw",
    [
        ("prefill", {}),
        ("decode", {}),
        ("verify", dict(spec_draft="ngram", spec_k=3)),
        ("verify_tree", dict(spec_draft="ngram", spec_k=3, spec_branch=2)),
        ("chunk", dict(token_budget=8, chunk_size=4, decode_kernel="dense")),
    ],
    ids=["prefill", "decode", "verify", "verify_tree", "chunk"],
)
def test_step_log_holds_one_record_a_dispatched_program(lm, serve_async, kind, kw):
    """Every program the engine dispatches is one `StepRecord` in
    `engine.step_log`, whichever of the five step bodies and whichever
    loop: the steps the scheduler counts plus the prefill programs, each
    with its four stamps in order and the requests it ran for."""
    sched, engine, _, done = _run(lm, serve_async, max_new=10, **kw)
    log, st = engine.step_log, sched.stats
    assert sched.step_log is log and log.dropped_records == 0
    records = list(log.records)
    assert len(records) == log.dispatched
    assert len(records) == st.dispatch_count + engine.prefill_programs
    assert [r.seq for r in records] == list(range(1, len(records) + 1))
    assert all(_closed(r) for r in records)
    by_kind = {}
    for r in records:
        by_kind.setdefault(r.kind, []).append(r)
    assert kind in by_kind
    assert len(by_kind.get("prefill", ())) == engine.prefill_programs
    assert len(by_kind.get("decode", ())) == st.decode_steps
    assert len(by_kind.get("chunk", ())) == st.chunk_steps
    assert len(by_kind.get("verify", ())) + len(
        by_kind.get("verify_tree", ())
    ) == st.verify_steps
    assert len(by_kind.get("verify_tree", ())) == st.tree_verify_steps
    rids = set(done)
    for r in records:
        assert r.rids and set(r.rids) <= rids, r
        assert r.iteration >= 1 and r.rows >= 1
        assert (r.bucket is not None) == (r.kind == "prefill")
    # a prefill's rows are its prompts' real tokens
    assert sum(r.rows for r in by_kind.get("prefill", ())) == (
        engine.prefill_tokens_real
    )
    # records are in the order of the call, and all are read back
    assert all(a.t_call <= b.t_call for a, b in zip(records, records[1:]))
    assert log.open == 0
    if not serve_async:
        assert not any(r.chained for r in records)
    elif kind == "decode":
        chained = [r for r in by_kind["decode"] if r.chained]
        assert len(chained) >= st.decode_steps_chained > 0


@pytest.mark.parametrize("serve_async", [True, False], ids=["async", "sync"])
def test_dispatch_commit_split_is_advanced_from_the_records(lm, serve_async):
    """`overlapped_host_s`, `commit_wait_s`, `mean_dispatch_gap_s` and
    `overlap_fraction` are what the ring says, recomputed here."""
    sched, engine, _, _ = _run(lm, serve_async)
    st = sched.stats
    steps = [r for r in engine.step_log.records if r.kind != "prefill"]
    assert len(steps) == st.dispatch_count > 1
    overlapped = sum(max(0.0, r.t_read - r.t_enqueued) for r in steps)
    waited = sum(r.t_ready - r.t_read for r in steps)
    gaps = sum(b.t_enqueued - a.t_enqueued for a, b in zip(steps, steps[1:]))
    assert st.overlapped_host_s == pytest.approx(overlapped, abs=1e-9)
    assert st.commit_wait_s == pytest.approx(waited, abs=1e-9)
    assert st.mean_dispatch_gap_s == pytest.approx(
        gaps / (len(steps) - 1), abs=1e-9
    )
    assert st.overlap_fraction == pytest.approx(
        overlapped / (overlapped + waited), abs=1e-9
    )


def test_step_log_is_bounded_and_counts_what_it_drops(lm):
    from collections import deque

    from flexflow_tpu.telemetry.trace import (
        REQUEST_LOG_MAX, STEP_LOG_MAX, request_parts,
    )

    serve = ServeConfig(max_seqs=4, max_seq_len=32)
    sched, engine, _ = build_scheduler(lm, serve)
    log = engine.step_log
    assert log.records.maxlen == STEP_LOG_MAX
    assert log.retired.maxlen == REQUEST_LOG_MAX
    log.records = deque(maxlen=5)
    log.retired = deque(maxlen=2)
    done = sched.run(_requests(6, 8))
    assert all(r.ok for r in done)
    assert len(log.records) == 5 and len(log.retired) == 2
    assert log.dropped_records == log.dispatched - 5 > 0
    assert log.dropped_requests == 4
    assert log.records[-1].seq == log.dispatched
    # what the ring no longer reaches is not accounted, never guessed
    first = next(r for r in done if r.rid == 0)
    from flexflow_tpu.telemetry.trace import lifecycle_stamps

    parts = request_parts(lifecycle_stamps(0, first.events), log.records)
    assert parts.ttft is None and parts.gap is None


def test_direct_engine_calls_are_recorded_too(lm):
    """The synchronous wrappers (`prefill`, `decode`) go through the
    same place: a caller without a scheduler gets records without
    request ids."""
    serve = ServeConfig(max_seqs=4, max_seq_len=32)
    _, engine, cache = build_scheduler(lm, serve)
    slot = cache.alloc(2, 32)
    engine.prefill(lm.params, [[1, 2]], [slot])
    tokens = np.zeros(4, dtype=np.int32)
    active = np.zeros(4, dtype=bool)
    active[slot] = True
    step = engine.decode_dispatch(lm.params, tokens, active)
    pre, dec = engine.step_log.records
    assert (pre.kind, pre.bucket, pre.rows) == ("prefill", cache.spec.bucket(2), 2)
    assert step.record is dec and dec.kind == "decode" and dec.rows == 1
    assert _closed(pre) and not pre.chained and not dec.chained
    assert dec.t_ready is None and dec.rids == ()
    engine.decode_reconcile(step)
    assert _closed(dec)
