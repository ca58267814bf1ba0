"""Device-resident multi-step decode (--decode-multistep;
serving/scheduler._fusable_steps + _decode_multi_dispatch_step +
engine.decode_multi_dispatch/_reconcile — the fused lax.scan window).

The load-bearing proofs: fused K-step windows are TOKEN-identical to
the step-at-a-time reference at both page geometries × {sync, async} ×
{fp32, int8} × {prefix cache on/off} × {chunked on/off} × {dense,
pallas} attention cores, and LOGIT-identical at the engine level (the
scan body IS the single-step core, so parity is exact, not
approximate); an EOS inside the window retires the stream at the right
position and emits nothing past it; deadline/cancel events that land
mid-window defer to the window's reconcile; the paged page-boundary
cap truncates K so a window claims at most one fresh page per slot;
preemption-capable admission never opens a window; and the fused path
is observable (host_syncs_per_token, serve_multistep_* counters, the
bounded scan-program LRU). All CPU-fast (tier 1)."""

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
    Request,
    RequestStatus,
    ServeConfig,
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


def _run(lm, multistep, layout="paged", serve_async=False, n=4, max_new=10,
         reqs=None, **cfg_kw):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
        serve_async=serve_async, debug_invariants=True,
        decode_multistep=multistep, **cfg_kw,
    )
    sched, engine, cache = build_scheduler(lm, serve)
    done = sched.run(reqs if reqs is not None else _requests(n, max_new))
    return sched, engine, cache, {r.rid: r for r in done}


def _assert_parity(plain, fused):
    assert set(plain) == set(fused)
    for rid in plain:
        assert plain[rid].ok and fused[rid].ok, rid
        assert plain[rid].generated == fused[rid].generated, rid


# -- token-identity parity ----------------------------------------------------


# tier-1 keeps one combo per loop; the serving-multistep CI job runs
# the full matrix (this file without the `slow` filter)
@pytest.mark.parametrize(
    "serve_async,layout",
    [
        (False, "one_page"),
        pytest.param(False, "paged", marks=pytest.mark.slow),
        pytest.param(True, "one_page", marks=pytest.mark.slow),
        (True, "paged"),
    ],
)
def test_multistep_matches_plain_streams(lm, layout, serve_async):
    psched, _, _, plain = _run(lm, False, layout, serve_async)
    fsched, _, _, fused = _run(lm, True, layout, serve_async)
    _assert_parity(plain, fused)
    # the fused run actually fused — and every window saved host syncs
    s = fsched.stats
    assert s.multistep_windows > 0
    assert s.multistep_steps > s.multistep_windows
    assert s.host_syncs < psched.stats.host_syncs
    assert s.host_syncs_per_token < psched.stats.host_syncs_per_token


def test_fused_window_cuts_host_syncs_fourfold(lm):
    """The count the multi-step CI gate held: on a quiet stretch (four
    requests, four slots, no admission or retirement for 24 steps) a
    fused window of up to 8 steps takes at most a quarter of the host
    syncs a committed token costs step-at-a-time. One page a slot, so
    no page boundary cuts a window short."""
    kw = dict(n=4, max_new=24, max_fused_steps=8)
    psched, _, _, plain = _run(lm, False, "one_page", **kw)
    fsched, _, _, fused = _run(lm, True, "one_page", **kw)
    _assert_parity(plain, fused)
    assert (
        4 * fsched.stats.host_syncs_per_token
        <= psched.stats.host_syncs_per_token
    )


@pytest.mark.slow  # runs in the serving-multistep CI job
@pytest.mark.parametrize("serve_async", [False, True])
def test_multistep_matches_plain_int8(lm, serve_async):
    kw = dict(kv_dtype="int8")
    _, _, _, plain = _run(lm, False, "paged", serve_async, **kw)
    fsched, _, _, fused = _run(lm, True, "paged", serve_async, **kw)
    _assert_parity(plain, fused)
    assert fsched.stats.multistep_windows > 0


@pytest.mark.slow  # runs in the serving-multistep CI job
def test_multistep_matches_plain_prefix_cache(lm):
    # same 12-token prefix, staggered lifetimes: the long request keeps
    # the prefix pages live (refcounted) so later admission waves map
    # them; after the short churn drains the queue, its solo decode
    # tail fuses into windows
    pref = list(range(1, 13))
    mnt = (14, 3, 3, 3, 3, 3)
    reqs = lambda: [
        Request(rid=i, prompt=pref + [20 + i], max_new_tokens=n)
        for i, n in enumerate(mnt)
    ]
    kw = dict(prefix_cache=True, kv_page_size=4)
    _, _, _, plain = _run(lm, False, "paged", reqs=reqs(), **kw)
    fsched, _, cache, fused = _run(lm, True, "paged", reqs=reqs(), **kw)
    _assert_parity(plain, fused)
    assert fsched.stats.multistep_windows > 0
    assert fsched.stats.prefix_hits > 0
    cache.check_invariants()


@pytest.mark.slow  # runs in the serving-multistep CI job
def test_multistep_matches_plain_chunked(lm):
    # chunk streaming holds fusing (phase changes every iteration);
    # once the prompts land the decode stretch fuses again
    kw = dict(token_budget=16, chunk_size=8)
    _, _, _, plain = _run(lm, False, "paged", max_new=12, **kw)
    fsched, _, _, fused = _run(lm, True, "paged", max_new=12, **kw)
    _assert_parity(plain, fused)
    assert fsched.stats.chunk_steps > 0
    assert fsched.stats.multistep_windows > 0


@pytest.mark.parametrize(
    "kernel",
    # interpret-mode pallas is heavy; the serving-multistep CI job runs it
    ["dense", pytest.param("pallas", marks=pytest.mark.slow)],
)
def test_multistep_matches_plain_kernel(lm, kernel):
    kw = dict(decode_kernel=kernel, kv_page_size=8)
    _, _, _, plain = _run(lm, False, "paged", **kw)
    fsched, _, _, fused = _run(lm, True, "paged", **kw)
    _assert_parity(plain, fused)
    assert fsched.stats.multistep_windows > 0


# -- engine-level logit identity ----------------------------------------------


@pytest.mark.parametrize(
    "layout,dtype",
    [
        pytest.param("one_page", "fp32", marks=pytest.mark.slow),
        ("paged", "fp32"),
        pytest.param("paged", "int8", marks=pytest.mark.slow),
    ],
)
def test_multistep_engine_logit_identity(lm, layout, dtype):
    """The scan body IS the single-step core, sampling is position-
    keyed, so a K-step window must reproduce K sequential decode steps
    EXACTLY — tokens and full logit rows, no tolerance."""
    K = 4
    prompts = [[1, 2, 3], [4, 5, 6, 7]]

    def build():
        serve = ServeConfig(
            max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
            kv_dtype=dtype,
            decode_multistep=True, debug_invariants=True,
        )
        sched, eng, cache = build_scheduler(lm, serve)
        for s, p in enumerate(prompts):
            cache.alloc(s, len(p))
        toks, _ = eng.prefill(sched.params, prompts, list(range(len(prompts))))
        return sched.params, eng, cache, toks

    params, eng1, cache1, toks1 = build()
    params2, eng2, cache2, toks2 = build()
    np.testing.assert_array_equal(toks1, toks2)

    active = np.zeros(4, dtype=bool)
    active[: len(prompts)] = True
    cur = np.zeros(4, dtype=np.int32)
    cur[: len(prompts)] = toks1
    seq_toks, seq_logits = [], []
    for _ in range(K):
        nxt, logits = eng1.decode(params, cur, active)
        seq_toks.append(nxt.copy())
        seq_logits.append(logits.copy())
        cur = nxt.astype(np.int32)

    limits = np.zeros(4, dtype=np.int32)
    limits[: len(prompts)] = K
    start = np.zeros(4, dtype=np.int32)
    start[: len(prompts)] = toks2
    toks_ks, logits_ks, mask_ks = eng2.decode_multi(
        params2, start, active, limits
    )
    assert toks_ks.shape[0] == K
    for i in range(K):
        np.testing.assert_array_equal(
            toks_ks[i][active], seq_toks[i][active], err_msg=f"step {i}"
        )
        np.testing.assert_array_equal(
            logits_ks[i][active], seq_logits[i][active], err_msg=f"step {i}"
        )
        assert mask_ks[i][active].all()
    np.testing.assert_array_equal(
        np.asarray(cache1.lengths), np.asarray(cache2.lengths)
    )
    cache2.check_invariants()


# -- EOS inside the window ----------------------------------------------------


@pytest.mark.parametrize(
    "layout", [pytest.param("one_page", marks=pytest.mark.slow), "paged"]
)
def test_eos_inside_window_retires_at_position(lm, layout):
    """Pick a token the greedy continuation actually emits mid-stream
    and declare it EOS: the scan must retire the slot AT that position
    — the stream ends with the EOS token, nothing emitted past it, and
    both modes agree."""
    _, _, _, free = _run(lm, False, layout, n=1, max_new=12)
    stream = free[0].generated
    assert len(stream) >= 6
    eos = int(stream[len(stream) // 2])
    cut = stream.index(eos) + 1
    reqs = lambda: [
        Request(rid=0, prompt=list(_PROMPTS[0]), max_new_tokens=12,
                eos_token=eos)
    ]
    _, _, _, plain = _run(lm, False, layout, reqs=reqs())
    fsched, _, cache, fused = _run(lm, True, layout, reqs=reqs())
    assert plain[0].generated == stream[:cut]
    assert fused[0].generated == stream[:cut]
    assert fused[0].status == RequestStatus.FINISHED
    # the rolled-back window returned the unused pre-advanced rows
    cache.check_invariants()


# -- mid-window control events ------------------------------------------------


def test_async_cancel_mid_window_defers_to_reconcile(lm):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, serve_async=True,
        decode_multistep=True, max_fused_steps=4, debug_invariants=True,
    )
    sched, _, cache = build_scheduler(lm, serve)
    for r in _requests(4, max_new=16):
        sched.submit(r)
    for _ in range(12):  # admit, then open a fused window
        if any(s.kind == "multistep" for s in sched._inflight):
            break
        sched.step()
    assert any(s.kind == "multistep" for s in sched._inflight)
    victim = next(iter(sched.running.values()))
    assert sched.cancel(victim.rid) is True
    # deferred: still officially running until the window reconciles
    assert victim.status == RequestStatus.RUNNING
    assert victim.rid in sched._pending_cancels
    sched.run([])
    assert victim.status == RequestStatus.CANCELLED
    assert victim.slot is None
    cache.check_invariants()


@pytest.mark.slow  # runs in the serving-multistep CI job
def test_async_deadline_mid_window_reaps_at_reconcile(lm):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, serve_async=True,
        decode_multistep=True, max_fused_steps=4, debug_invariants=True,
    )
    sched, _, cache = build_scheduler(lm, serve)
    reqs = _requests(4, max_new=16, deadline_s=3600.0)
    for r in reqs:
        sched.submit(r)
    for _ in range(12):
        if any(s.kind == "multistep" for s in sched._inflight):
            break
        sched.step()
    assert any(s.kind == "multistep" for s in sched._inflight)
    victim = next(iter(sched.running.values()))
    # expire the deadline while the window is in flight — the reap
    # lands at the window reconcile, never mid-window
    victim.submit_time -= 7200.0
    assert victim.status == RequestStatus.RUNNING
    sched.run([])
    assert victim.status == RequestStatus.TIMED_OUT
    assert victim.slot is None
    cache.check_invariants()


# -- window-depth derivation --------------------------------------------------


def test_page_boundary_truncates_window(lm):
    """With 4-token pages and an 8-step fusing horizon, every window
    must stop at its slot's next page boundary (at most ONE fresh page
    per slot per window) — observable as mean window depth <= page
    size while parity holds."""
    kw = dict(kv_page_size=4, max_fused_steps=8)
    _, _, _, plain = _run(lm, False, "paged", max_new=12, **kw)
    fsched, _, cache, fused = _run(lm, True, "paged", max_new=12, **kw)
    _assert_parity(plain, fused)
    s = fsched.stats
    assert s.multistep_windows > 1
    # no window can cross a page boundary: depth K <= page size
    assert s.multistep_steps <= 4 * s.multistep_windows
    cache.check_invariants()


@pytest.mark.slow  # runs in the serving-multistep CI job
def test_optimistic_admission_never_fuses(lm):
    """Preemption must never coexist with an open K-step window: under
    optimistic admission (preemption-by-recompute) the fusing horizon
    pins to 1 and the run degrades to plain decode — still correct,
    zero windows."""
    kw = dict(
        kv_page_size=4, kv_pages=8, admission="optimistic",
        max_preemptions=8,
    )
    _, _, _, plain = _run(lm, False, "paged", n=6, **kw)
    fsched, _, cache, fused = _run(lm, True, "paged", n=6, **kw)
    _assert_parity(plain, fused)
    assert fsched.stats.preemptions > 0
    assert fsched.stats.multistep_windows == 0
    cache.check_invariants()


def test_speculative_mode_fuses_only_draft_free_iterations(lm):
    """A verify's acceptance is host logic — an iteration carrying a
    draft never fuses. But a dry proposer (no n-gram hit anywhere)
    makes the iteration an ordinary decode step, and those DO fuse:
    spec + multistep interleave fused windows with verify steps, and
    the stream still matches plain decode exactly."""
    kw = dict(spec_draft="ngram", spec_k=3)
    _, _, _, plain = _run(lm, False, **kw)
    fsched, _, _, fused = _run(lm, True, **kw)
    _assert_parity(plain, fused)
    assert fsched.stats.verify_steps > 0
    assert fsched.stats.multistep_windows > 0


# -- flags / config wiring ----------------------------------------------------


def test_flag_wiring_and_validation(lm):
    cfg = FFConfig.parse_args(
        ["--decode-multistep", "--max-fused-steps", "4"]
    )
    assert cfg.serve_decode_multistep is True
    assert cfg.serve_max_fused_steps == 4
    serve = ServeConfig.from_config(cfg)
    assert serve.decode_multistep is True and serve.max_fused_steps == 4
    sched, _, _ = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32, decode_multistep=True,
                        max_fused_steps=4)
    )
    assert sched.decode_multistep is True and sched.max_fused_steps == 4
    with pytest.raises(ValueError):
        ServeConfig(decode_multistep=True, max_fused_steps=0)


# -- observability ------------------------------------------------------------


def test_multistep_cache_is_bounded_and_observable(lm):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=64, decode_multistep=True,
        max_fused_steps=8,
    )
    sched, eng, _ = build_scheduler(lm, serve)
    sched.run(_requests(4, max_new=12))
    assert eng.multistep_cache_entries >= 1
    # the gauge mirrors onto SchedulerStats at every iteration end
    assert sched.stats.multistep_cache_entries == eng.multistep_cache_entries
    # the LRU bound holds even if the horizon churns K buckets
    eng._multistep_cache.max_entries = 1
    eng._multistep_cache.get((4, 2))
    eng._multistep_cache.get((4, 4))
    assert eng.multistep_cache_entries == 1


def test_multistep_telemetry_counters_and_spans(lm):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, serve_async=True, telemetry=True,
        decode_multistep=True, max_fused_steps=4,
    )
    sched, _, _ = build_scheduler(lm, serve)
    sched.run(_requests(4, max_new=10))
    s = sched.stats
    assert s.multistep_windows > 0
    reg = sched.telemetry.registry
    assert reg.get("serve_multistep_windows_total").value == (
        s.multistep_windows
    )
    assert reg.get("serve_multistep_steps_total").value == s.multistep_steps
    hist = reg.get("serve_multistep_window_size")
    assert hist is not None
    # the fused windows render on the device lanes as multistep[K]
    names = {e.get("name") for e in sched.telemetry.tracer.events}
    assert any(
        isinstance(n, str) and n.startswith("inflight:multistep[")
        for n in names
    ), sorted(n for n in names if isinstance(n, str))
    assert 0.0 < s.host_syncs_per_token < 1.0
