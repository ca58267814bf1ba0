"""Serving subsystem (flexflow_tpu.serving): cache-equivalence of KV-cache
decode against full-prefill recompute, scheduler invariants under a
mixed-length request stream (no slot leak, FIFO starvation-freedom, EOS
frees slots, determinism), the continuous-vs-static batching win, chunked
prefill under a per-iteration token budget (chunk==monolithic parity,
budget enforcement, SLO-driven budget selection), and the decode-regime
strategy search. All CPU-fast (tier 1)."""


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
    GenerationEngine,
    PagedKVCache,
    Request,
    RequestStatus,
    ServeConfig,
    build_scheduler,
)
from tests.conftest import page_geometry, ref_generate

pytestmark = pytest.mark.serving

VOCAB = 50


def _lm(seed=0, devices=None, causal=True, batch=4, seq=32):
    cfg = FFConfig(batch_size=batch, seed=seed)
    model = FFModel(cfg)
    tok = model.create_tensor([batch, seq], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        model, tok, vocab_size=VOCAB, hidden=32, num_heads=4, num_layers=2,
        ff_dim=64,
    ) if causal else _non_causal_lm(model, tok)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=devices if devices is not None else jax.devices()[:1],
    )
    return model


def _non_causal_lm(model, tok):
    t = model.embedding(tok, VOCAB, 32)
    t = model.multihead_attention(t, t, t, 32, 4, bias=False)  # causal=False
    return model.dense(t, VOCAB, use_bias=False)


@pytest.fixture(scope="module")
def lm():
    return _lm()


# -- cache equivalence -------------------------------------------------------


def test_cache_equivalence_mixed_length_stream(lm):
    """Greedy generate() through the KV cache, with more requests than
    slots (forced eviction/reuse), matches per-step full-prefill forward
    recompute token-for-token."""
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 2], [7], [11, 12]]
    out = lm.generate(
        prompts,
        max_new_tokens=6,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32),
    )
    for p, got in zip(prompts, out):
        assert got == ref_generate(lm, p, 6)


def test_decode_logits_match_full_forward(lm):
    """One prefill + one decode: the decode step's logits agree with the
    full forward's logits at the same position (numeric, not just argmax)."""
    sched, engine, cache = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32)
    )
    prompt = [3, 1, 4, 1, 5]
    slot = cache.alloc()
    nxt, last = engine.prefill(lm.params, [prompt], [slot])
    full = np.asarray(
        lm.forward({"tokens": np.asarray([prompt], dtype=np.int32)})
    )
    # prefill logits at the last prompt position ARE the forward logits
    np.testing.assert_allclose(last[0], full[0, len(prompt) - 1], atol=1e-5)
    # decode the emitted token and compare against the extended forward
    tokens = np.zeros(cache.spec.max_seqs, dtype=np.int32)
    active = np.zeros(cache.spec.max_seqs, dtype=bool)
    tokens[slot] = int(nxt[0])
    active[slot] = True
    _, dec_logits = engine.decode(lm.params, tokens, active)
    ext = prompt + [int(nxt[0])]
    full2 = np.asarray(
        lm.forward({"tokens": np.asarray([ext], dtype=np.int32)})
    )
    np.testing.assert_allclose(
        dec_logits[slot], full2[0, len(ext) - 1], atol=1e-4
    )


def test_generate_on_default_multichip_mesh():
    """The serving path also runs on a model compiled with the default
    8-virtual-device data-parallel mesh (replicated weights) and produces
    the same tokens as the single-device compile."""
    single = _lm(devices=jax.devices()[:1])
    multi = _lm(devices=None if len(jax.devices()) == 1 else jax.devices())
    prompts = [[2, 4, 6], [1, 3, 5, 7]]
    sc = ServeConfig(max_seqs=2, max_seq_len=32)
    assert single.generate(
        prompts, max_new_tokens=4, serve_config=sc
    ) == multi.generate(prompts, max_new_tokens=4, serve_config=sc)


# -- scheduler invariants ----------------------------------------------------


def _requests(spec):
    return [
        Request(rid=i, prompt=[(i * 7 + j) % (VOCAB - 1) + 1 for j in range(1 + i % 5)],
                max_new_tokens=n)
        for i, n in enumerate(spec)
    ]


def test_no_slot_leak_and_all_finish(lm):
    sched, engine, cache = build_scheduler(
        lm, ServeConfig(max_seqs=3, max_seq_len=32)
    )
    reqs = _requests([2, 9, 4, 1, 7, 3, 5, 8, 2, 6])
    done = sched.run(reqs)
    assert len(done) == len(reqs)
    assert cache.num_active == 0
    assert cache.num_free == cache.spec.max_seqs
    assert np.all(cache.lengths == 0)
    for r in done:
        assert len(r.generated) == r.max_new_tokens


def test_fifo_admission_is_starvation_free(lm):
    sched, _, _ = build_scheduler(lm, ServeConfig(max_seqs=2, max_seq_len=32))
    reqs = _requests([6] * 9)
    sched.run(reqs)
    admits = [r.admit_iter for r in sorted(sched.finished, key=lambda r: r.rid)]
    # strictly FIFO: a later arrival is never admitted before an earlier one
    assert admits == sorted(admits)
    assert all(a >= 0 for a in admits)


def test_eos_frees_slot_early(lm):
    """Pick the token an unconstrained run emits mid-stream as the EOS and
    re-run: generation must stop AT the eos and the slot must recycle."""
    base = lm.generate(
        [[1, 2, 3]], max_new_tokens=8,
        serve_config=ServeConfig(max_seqs=1, max_seq_len=32),
    )[0]
    eos = base[3]
    cut = base.index(eos)  # first occurrence may be before position 3
    sched, _, cache = build_scheduler(
        lm, ServeConfig(max_seqs=1, max_seq_len=32)
    )
    done = sched.run(
        [
            Request(rid=0, prompt=[1, 2, 3], max_new_tokens=8, eos_token=eos),
            Request(rid=1, prompt=[5, 6], max_new_tokens=2),
        ]
    )
    r0 = next(r for r in done if r.rid == 0)
    assert r0.generated == base[: cut + 1]  # truncated at eos, eos included
    assert cache.num_free == 1
    r1 = next(r for r in done if r.rid == 1)
    assert len(r1.generated) == 2  # the freed slot served the next request


def test_deterministic_under_fixed_seed(lm):
    prompts = [[1, 2], [3, 4, 5], [6]]
    sc = dict(max_seqs=2, max_seq_len=32)
    a = lm.generate(prompts, 5, serve_config=ServeConfig(**sc))
    b = lm.generate(prompts, 5, serve_config=ServeConfig(**sc))
    assert a == b
    s1 = lm.generate(
        prompts, 5, serve_config=ServeConfig(temperature=0.8, seed=7, **sc)
    )
    s2 = lm.generate(
        prompts, 5, serve_config=ServeConfig(temperature=0.8, seed=7, **sc)
    )
    assert s1 == s2


def test_prefill_bucketing_bounds_compiles(lm):
    cache = PagedKVCache.from_model(lm, max_seqs=2, max_len=32)
    engine = GenerationEngine(lm, cache)
    sched = ContinuousBatchingScheduler(engine)
    sched.run(_requests([2, 2, 2, 2]))  # prompt lengths 1..5 — one bucket
    assert list(engine._prefill_cache) == [16]


def test_non_causal_model_rejected():
    model = _lm(causal=False, batch=2, seq=8)
    with pytest.raises(ValueError, match="causal"):
        model.generate([[1, 2]], max_new_tokens=2)


def test_serve_config_from_flags():
    cfg = FFConfig.parse_args(
        [
            "--max-seqs", "4", "--max-seq-len", "64", "--eos-token", "7",
        ]
    )
    sc = ServeConfig.from_config(cfg)
    assert (sc.max_seqs, sc.max_seq_len) == (4, 64)
    assert sc.eos_token == 7
    assert sc.debug_invariants is False
    sc = ServeConfig.from_config(FFConfig.parse_args(["--check-invariants"]))
    assert sc.debug_invariants is True


def test_debug_invariants_runs_every_iteration(lm):
    """ServeConfig.debug_invariants / --check-invariants: the scheduler
    re-derives the cache/allocator accounting after EVERY iteration —
    a clean run passes, and corrupted bookkeeping trips the very next
    step instead of steps later."""
    serve = ServeConfig(max_seqs=2, max_seq_len=32, debug_invariants=True)
    sched, _, cache = build_scheduler(lm, serve)
    sched.run(_requests([3, 3, 3]))
    assert all(r.ok for r in sched.finished)
    # corrupt the allocator behind the accounting: the next iteration's
    # invariant probe must catch it
    sched2, _, cache2 = build_scheduler(lm, serve)
    for r in _requests([8]):
        sched2.submit(r)
    sched2.step()
    cache2._free_pages.pop()  # a page vanishes outside the ledger
    with pytest.raises(AssertionError):
        sched2.step()
    # without the flag the same corruption goes unnoticed
    serve_off = ServeConfig(max_seqs=2, max_seq_len=32)
    sched3, _, cache3 = build_scheduler(lm, serve_off)
    for r in _requests([8]):
        sched3.submit(r)
    sched3.step()
    cache3._free_pages.pop()
    sched3.step()


# -- continuous batching ------------------------------------------------------


def test_continuous_batching_recycles_finished_slots(lm):
    """Eight requests of 4 and 40 tokens through four slots: a batch that
    ran until its longest member finished would take 2 x 40 decode
    steps. Iteration-level scheduling hands a short request's slot to
    the next request the step after it finishes, so the whole set takes
    fewer steps at higher occupancy. Counts, not times."""
    serve = ServeConfig(max_seqs=4, max_seq_len=64, prefill_buckets=(8, 64))
    sched, _, _ = build_scheduler(lm, serve)
    done = sched.run(_requests([4, 40, 4, 40, 4, 40, 4, 40]))
    assert all(r.ok for r in done)
    st = sched.stats
    assert st.tokens_generated == 4 * (4 + 40)
    assert st.decode_steps < 2 * 40
    # request-level batching keeps (4 + 40) / (2 * 40) of its slots busy
    assert st.occupancy > (4 + 40) / (2 * 40)


# -- chunked prefill ---------------------------------------------------------


def _chunked_requests(max_new=6):
    """Prompt lengths 22/3/13/2/18: long enough that a token_budget=8 /
    chunk_size=4 run splits the long ones across many iterations, with
    short ones riding along (the round-robin fairness case)."""
    lens = [22, 3, 13, 2, 18]
    return [
        Request(
            rid=i,
            prompt=[(i * 7 + j) % (VOCAB - 1) + 1 for j in range(n)],
            max_new_tokens=max_new,
        )
        for i, n in enumerate(lens)
    ]


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_chunk_steps_reproduce_monolithic_prefill(lm, layout):
    """Engine level: streaming a prompt in as staircase-masked chunk
    steps leaves the same cache state and produces the same sampled token
    and (to 1e-5 of the largest logit) the same final logits as one
    monolithic prefill, at both page geometries. Not bit equality: the two
    are different programs over different shapes (the prompt's ten keys in
    one packed row of 16 against three passes over the slot's pages, all
    32 positions of them), and XLA sums a softmax row and a matmul's
    contraction in an order that follows the shape: the same float32
    terms in another order differ in the last bit (3.6e-7 here)."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    serve = ServeConfig(
        max_seqs=2, max_seq_len=32, **page_geometry(layout, 32)
    )
    _, eng_m, cache_m = build_scheduler(lm, serve)
    slot = cache_m.alloc(len(prompt), len(prompt) + 6)
    nxt_m, last_m = eng_m.prefill(lm.params, [prompt], [slot])
    _, eng_c, cache_c = build_scheduler(lm, serve)
    slot_c = cache_c.alloc(0, len(prompt) + 6)  # chunked: claim nothing yet
    assert slot_c == slot
    nxt = logits = None
    for start in range(0, len(prompt), 4):
        chunk = prompt[start : start + 4]
        tokens = np.zeros((2, len(chunk)), dtype=np.int32)
        tokens[slot_c, : len(chunk)] = chunk
        chunk_lens = np.zeros(2, dtype=np.int32)
        chunk_lens[slot_c] = len(chunk)
        nxt, logits = eng_c.prefill_chunk(lm.params, tokens, chunk_lens)
    assert int(cache_c.lengths[slot_c]) == len(prompt)
    np.testing.assert_allclose(
        logits[slot_c], last_m[0], rtol=0, atol=1e-5 * np.abs(last_m[0]).max()
    )
    assert int(nxt[slot_c]) == int(nxt_m[0])


@pytest.mark.parametrize("layout", ["one_page", "paged"])
@pytest.mark.parametrize(
    "spec_kw", [{}, dict(spec_draft="ngram", spec_k=3)],
    ids=["plain", "spec"],
)
def test_chunked_streams_token_identical(lm, layout, spec_kw):
    """Scheduler level: a token-budgeted chunked run emits exactly the
    unchunked run's token streams — chunking changes WHEN prompt work
    happens, never WHAT is generated — at both page geometries, with
    speculation on and off."""
    base = dict(
        max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
        debug_invariants=True, **spec_kw,
    )
    sched_u, _, _ = build_scheduler(lm, ServeConfig(**base))
    plain = {r.rid: r for r in sched_u.run(_chunked_requests())}
    sched_c, _, _ = build_scheduler(
        lm,
        ServeConfig(token_budget=8, chunk_size=4, decode_kernel="dense",
                    **base),
    )
    chunked = {r.rid: r for r in sched_c.run(_chunked_requests())}
    assert set(plain) == set(chunked)
    for rid in plain:
        assert plain[rid].ok and chunked[rid].ok, rid
        assert plain[rid].generated == chunked[rid].generated, rid
    assert sched_u.stats.chunk_steps == 0
    assert sched_c.stats.chunk_steps > 0
    # every prompt token streamed in through a chunk
    assert sched_c.stats.chunk_tokens == sum(
        len(r.prompt) for r in _chunked_requests()
    )


def test_token_budget_caps_every_iteration(lm):
    """The budget is a hard per-iteration cap: chunk grants + decode
    tokens never exceed it, on any iteration of a run that mixes
    admissions, chunked prefill, and decode."""
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, token_budget=8, chunk_size=4,
        decode_kernel="dense",
    )
    sched, _, _ = build_scheduler(lm, serve)
    used = []
    orig = sched._end_iteration

    def spy():
        used.append(sched._budget_used_iter)
        orig()

    sched._end_iteration = spy
    done = sched.run(_chunked_requests())
    assert all(r.ok for r in done)
    assert used and max(used) <= serve.token_budget
    assert any(u > 0 for u in used)
    assert sched.stats.budget_used == used[-1]


def test_chunked_config_validation():
    base = dict(max_seqs=2, max_seq_len=32)
    with pytest.raises(ValueError, match="token_budget must be >= 0"):
        ServeConfig(token_budget=-1, **base)
    with pytest.raises(ValueError, match="chunk_size >= 1"):
        ServeConfig(token_budget=8, chunk_size=0, **base)
    with pytest.raises(ValueError, match="could never fit"):
        ServeConfig(token_budget=4, chunk_size=8, **base)
    # a kernel-eligible config rejects sublane-misaligned chunk widths
    # (they would silently route every chunk to the dense fallback)...
    with pytest.raises(ValueError, match="multiple of"):
        ServeConfig(token_budget=8, chunk_size=4, **base)
    # ...while the dense path takes any width
    ServeConfig(token_budget=8, chunk_size=4, decode_kernel="dense", **base)
    cfg = FFConfig.parse_args(["--token-budget", "32", "--chunk-size", "8"])
    sc = ServeConfig.from_config(cfg)
    assert (sc.token_budget, sc.chunk_size) == (32, 8)


def test_bad_chunk_config_fails_requests_not_process(lm):
    """A rejected chunked-prefill config parked at scheduler
    construction surfaces per-request: ValueError under strict submit,
    FAILED (not a crash) under the serving-surface contract."""
    cache = PagedKVCache.from_model(lm, max_seqs=2, max_len=32)
    engine = GenerationEngine(lm, cache)
    sched = ContinuousBatchingScheduler(engine, token_budget=4, chunk_size=8)
    with pytest.raises(ValueError, match="could never fit"):
        sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    req = Request(rid=1, prompt=[1, 2, 3], max_new_tokens=2)
    assert sched.submit(req, strict=False) is False
    assert req.status == RequestStatus.FAILED
    assert "chunk" in (req.error or "")


def test_chunked_telemetry_counters_and_spans(lm):
    """The observability satellite: chunk dispatches count into
    `serve_chunks_total`, zero-grant iterations into
    `serve_budget_deferrals_total`, the per-iteration ledger lands on
    the `serve_stats_budget_used` gauge, and each chunk step records a
    `scheduler.step.chunk.dispatch` trace span."""
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, token_budget=4, chunk_size=4,
        decode_kernel="dense", telemetry=True,
    )
    sched, _, _ = build_scheduler(lm, serve)
    done = sched.run(_chunked_requests())
    assert all(r.ok for r in done)
    reg = sched.telemetry.registry
    chunks = reg.get("serve_chunks_total")
    assert chunks is not None and chunks.value >= sched.stats.chunk_steps > 0
    # budget 4 fits ONE chunk while four prompts wait: deferrals are
    # structurally guaranteed, and the stat mirrors the counter
    deferrals = reg.get("serve_budget_deferrals_total")
    assert deferrals is not None and deferrals.value > 0
    assert sched.stats.budget_deferrals == deferrals.value
    assert reg.get("serve_stats_budget_used") is not None
    assert reg.get("serve_stats_chunk_steps").value == (
        sched.stats.chunk_steps
    )
    assert any(
        e.get("name") == "scheduler.step.chunk.dispatch"
        for e in sched.telemetry.tracer.events
    )


def test_optimize_token_budget_prediction_tracks_measured_ttft(lm):
    """Close the loop, by counting: handed a fixed time for one decode
    step, `optimize_token_budget` predicts a TTFT for the chosen budget
    within 2x of the step programs the scheduler dispatched between the
    long prompt's admission and its first token, each at that time (a
    long prompt chunking in while a batch of short requests decodes).
    No clock decides: the model is held to the planner it mirrors."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import optimize_token_budget

    step_s = 1e-3
    cache = PagedKVCache.from_model(lm, max_seqs=4, max_len=32)
    engine = GenerationEngine(lm, cache)
    sched = ContinuousBatchingScheduler(engine, token_budget=11, chunk_size=8)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=[2 + i, 3, 5], max_new_tokens=16))
    for _ in range(6):
        sched.step()  # admit the shorts, settle into steady decode
    long_prompt = [(7 * j) % (VOCAB - 1) + 1 for j in range(24)]
    lr = Request(rid=9, prompt=list(long_prompt), max_new_tokens=4)
    sched.submit(lr)
    st = sched.stats
    chunks, decodes = st.chunk_steps, st.decode_steps
    while not lr.generated:
        sched.step()
    iterations = st.iterations - lr.admit_iter + 1
    chunks, decodes = st.chunk_steps - chunks, st.decode_steps - decodes
    assert [e[1] for e in lr.events] == ["submit", "admit", "first_token"]
    sched.run([])
    assert lr.ok and all(r.ok for r in sched.finished)
    res = optimize_token_budget(
        lm.graph,
        MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e"),
        prompt_len=len(long_prompt), batch=3, kv_len=32, chunk_size=8,
        measured_decode_step_s=step_s,
    )
    # no SLO set: the smallest budget (one chunk row per iteration on
    # top of the decode batch) is already feasible
    assert res.token_budget == 3 + 8
    # the prompt came in as the model lays it out: a chunk an iteration,
    # the three shorts decoding beside every one
    assert res.n_chunks == 3 == chunks == iterations == decodes
    ratio = res.predicted_ttft_s / ((chunks + decodes) * step_s)
    assert 0.5 <= ratio <= 2.0, (res.predicted_ttft_s, chunks, decodes)


# -- decode-regime strategy search -------------------------------------------


def test_serving_search_picks_tp_at_batch_1():
    """The decode cost family's headline verdict: at decode batch 1 the
    weight-read term dominates and TP over heads wins; the training search
    on the SAME graph and machine picks a dp-dominant mesh."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import (
        estimate_decode_step,
        optimize,
        optimize_serving,
    )
    from flexflow_tpu.search.cost_model import CostModel

    cfg = FFConfig(batch_size=64)
    m = FFModel(cfg)
    tok = m.create_tensor([64, 128], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        m, tok, vocab_size=512, hidden=1024, num_heads=16, num_layers=4,
        ff_dim=4096,
    )
    spec = MachineSpec(num_nodes=1, chips_per_node=8, chip="v5e")
    serve = optimize_serving(m.graph, 8, spec, batch_size=1, kv_len=1024)
    assert serve.dp == 1  # dp cannot split a single sequence
    assert serve.tp > 1  # sharded weights beat an idle-chip dp mesh
    cm = CostModel(spec)
    dp_only = estimate_decode_step(m.graph, cm, 1, 1, 1, 1024)
    assert serve.cost.step_time < dp_only.step_time
    train = optimize(m.graph, 8, spec, budget=4)
    assert train.dp > 1  # the training regime's verdict differs
    assert (train.dp, train.tp) != (serve.dp, serve.tp)


def test_decode_cost_scales_with_kv_len():
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
    short = cm.decode_op_cost(mha, batch=1, kv_len=128)
    long = cm.decode_op_cost(mha, batch=1, kv_len=8192)
    assert long.forward_time > short.forward_time  # cache read term
    assert long.memory > short.memory
    sharded = cm.decode_op_cost(mha, batch=1, kv_len=8192, tp=4)
    assert sharded.forward_time < long.forward_time
