"""The packed prefill (`GenerationEngine.prefill`): the admitted prompts
laid end to end in ONE row of `bucket(total)` tokens, attention causal
inside a prompt, pool rows routed token by token.

What a request gets must not depend on what it was packed with: for toy
dense, rotary + QK-norm, latent, expert, held-share, int8 and adapter
engines a joint admission of n prompts gives each the token and (to 1e-5
of the largest logit, exact float32: conftest pins `highest`) the last
logits it gets alone, and writes exactly its own pool rows and no other.
Not bit equality: a lone prompt runs in a smaller bucket's program, and
XLA's order of summation follows the shape. A total over the largest
bucket splits in order, one program a group; the counters advance by the
programs; `moe_choice["prefill"]` indexes rows of the call across a
split; and after the benchmark's warm-up sequence no admission of any
composition traces or compiles a program."""

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm, build_deepseek_v3, build_olmoe
from flexflow_tpu.serving import Request, ServeConfig, build_scheduler
from flexflow_tpu.serving.tenancy import make_lora_weights

VOCAB, SLOTS, SEQ = 97, 6, 64
BUCKETS = (16, 32, 64)
MOE = dict(expert_hidden=24, num_experts=8, experts_per_token=2)
LATENT = dict(
    vocab_size=VOCAB, hidden=64, num_heads=4, num_layers=3, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, dense_hidden=96,
    dense_layers=1, shared_experts=2, **MOE,
)


def _rotary(ff, tok):
    """Rotary positions and QK-norm with a dense MLP: the attention of
    `build_olmoe` without its expert layer."""
    t = ff.embedding(tok, VOCAB, 64)
    for _ in range(2):
        h = ff.rms_norm(t, eps=1e-5)
        a = ff.multihead_attention(
            h, h, h, 64, 4, bias=False, causal=True, rope_theta=10000.0,
            qk_norm=True, qk_norm_eps=1e-5,
        )
        t = ff.add(t, a)
        m = ff.dense(
            ff.rms_norm(t, eps=1e-5), 128, activation=ActiMode.GELU,
            use_bias=False,
        )
        t = ff.add(t, ff.dense(m, 64, use_bias=False))
    return ff.dense(ff.rms_norm(t, eps=1e-5), VOCAB, use_bias=False)


BUILDERS = {
    "dense": lambda ff, tok: build_decoder_lm(
        ff, tok, vocab_size=VOCAB, hidden=64, num_heads=4, num_layers=2,
        ff_dim=128,
    ),
    "rotary": _rotary,
    "expert": lambda ff, tok: build_olmoe(
        ff, tok, vocab_size=VOCAB, hidden=64, num_heads=4, num_layers=2, **MOE
    ),
    "latent": lambda ff, tok: build_deepseek_v3(ff, tok, **LATENT),
    "held": lambda ff, tok: build_deepseek_v3(
        ff, tok, experts_held=(2, 4), **LATENT
    ),
}
#: engine kind -> (model, ServeConfig keywords)
KINDS = {
    "dense": ("dense", {}),
    "rotary": ("rotary", {}),
    "latent": ("latent", {}),
    "expert": ("expert", {}),
    "held": ("held", {}),
    "int8": ("dense", {"kv_dtype": "int8"}),
    "adapter": ("dense", {"adapters": 2, "adapter_rank": 4}),
}
_MODELS = {}


def _model(name):
    if name not in _MODELS:
        cfg = FFConfig(batch_size=SLOTS)
        cfg.seed = 11
        model = FFModel(cfg)
        tok = model.create_tensor([SLOTS, SEQ], dtype=DataType.INT32, name="tokens")
        BUILDERS[name](model, tok)
        model.compile(
            optimizer=SGDOptimizer(lr=0.01),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[], devices=jax.devices()[:1],
        )
        _MODELS[name] = model
    return _MODELS[name]


def _serve(kind, **kw):
    name, serve = KINDS[kind]
    model = _model(name)
    sched, engine, cache = build_scheduler(model, ServeConfig(
        max_seqs=SLOTS, max_seq_len=SEQ, prefill_buckets=BUCKETS,
        decode_kernel="dense", **serve, **kw,
    ))
    if engine.adapters is not None:
        for aid in (0, 1):
            engine.adapters.load(
                aid, make_lora_weights(engine.adapters.spec, 4, seed=aid)
            )
    return model, sched, engine, cache


_ENGINES = {}


def _engines(kind):
    """Two engines of one kind, kept for the module: one admits jointly,
    the other one prompt at a time. Both go through the same allocations,
    so a prompt lands in the same slot and pages in both."""
    if kind not in _ENGINES:
        _ENGINES[kind] = (_serve(kind), _serve(kind))
    return _ENGINES[kind]


def _prompts(lengths, salt=0):
    rng = np.random.default_rng([salt, len(lengths)])
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


SENTINEL = 7


def _admit(served, prompts, groups):
    """Allocate a slot a prompt, fill the pools with a sentinel, prefill
    the `groups` (lists of prompt indices, one `engine.prefill` each), and
    free the slots. Returns per prompt (token, last logits), the pools as
    flat rows, the block tables and the slots."""
    model, _, engine, cache = served
    slots = [cache.alloc(len(p), len(p) + 1) for p in prompts]
    assert None not in slots
    if engine.adapters is not None:
        for i, s in enumerate(slots):
            engine.adapters.attach(s, i % 3 - 1)
    cache.commit(
        {g: jnp.full_like(p, SENTINEL) for g, p in cache.k.items()},
        {g: jnp.full_like(p, SENTINEL) for g, p in cache.v.items()},
        cache.k_scale, cache.v_scale,
    )
    got = {}
    for group in groups:
        nxt, last = engine.prefill(
            model.params, [prompts[i] for i in group], [slots[i] for i in group]
        )
        assert nxt.shape == (len(group),) and last.shape == (len(group), VOCAB)
        for j, i in enumerate(group):
            got[i] = (int(nxt[j]), np.asarray(last[j]))
    assert [int(cache.lengths[s]) for s in slots] == [len(p) for p in prompts]
    tables = np.array(cache.block_tables)
    pools = {
        (name, g): np.asarray(p).reshape(cache.spec.total_rows, -1)
        for name, side in (("k", cache.k), ("v", cache.v))
        for g, p in side.items()
    }
    scales = {
        (name, g): np.asarray(p)
        for name, side in (("k", cache.k_scale), ("v", cache.v_scale))
        for g, p in side.items()
    }
    for s in slots:
        if engine.adapters is not None:
            engine.adapters.detach(s)
        cache.free(s)
    return got, pools, scales, tables, slots


def _rows_of(cache, tables, slot, n):
    ps = cache.spec.page_size
    pos = np.arange(n)
    return tables[slot, pos // ps] * ps + pos % ps


LENGTHS = (9, 3, 14, 6, 11, 5)  # 48 tokens: all six fit the largest bucket


@pytest.mark.parametrize("n", [1, 2, 3, 5, SLOTS])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_joint_admission_gives_each_prompt_what_it_gets_alone(kind, n):
    joint, lone = _engines(kind)
    prompts = _prompts(LENGTHS[:n], salt=n)
    before = joint[2].prefill_programs
    got_j, pools_j, scales_j, tables, slots = _admit(
        joint, prompts, [list(range(n))]
    )
    assert joint[2].prefill_programs - before == 1  # one packed row
    got_l, pools_l, scales_l, tables_l, slots_l = _admit(
        lone, prompts, [[i] for i in range(n)]
    )
    assert slots == slots_l and np.array_equal(tables, tables_l)
    for i in range(n):
        assert got_j[i][0] == got_l[i][0]
        np.testing.assert_allclose(
            got_j[i][1], got_l[i][1], rtol=0,
            atol=1e-5 * np.abs(got_l[i][1]).max(),
        )
    cache = joint[3]
    mine = np.zeros(cache.spec.total_rows, bool)
    for s, p in zip(slots, prompts):
        rows = _rows_of(cache, tables, s, len(p))
        assert not mine[rows].any()  # no two prompts share a row
        mine[rows] = True
    assert mine.sum() == sum(len(p) for p in prompts)
    for key, pool in pools_j.items():
        # exactly the prompts' own rows were written, and no other
        written = np.any(pool != SENTINEL, axis=-1)
        assert np.array_equal(written, mine), key
        if cache.quantized:
            # the int8 of values equal to 1e-5 is equal but for a value
            # that sits on a rounding boundary: one step
            steps = np.abs(
                pool[mine].astype(np.int32) - pools_l[key][mine].astype(np.int32)
            )
            assert steps.max() <= 1 and (steps > 0).mean() < 1e-3
            pages = np.unique(np.nonzero(mine)[0] // cache.spec.page_size)
            np.testing.assert_allclose(
                scales_j[key][pages], scales_l[key][pages], rtol=1e-5
            )
        else:
            np.testing.assert_allclose(
                pool[mine], pools_l[key][mine], rtol=0,
                atol=1e-5 * np.abs(pools_l[key][mine]).max(),
            )


# 116 tokens over a largest bucket of 64: [12] alone (12 + 60 > 64), then
# [60, 3] (63 + 9 > 64), then [9, 30, 2]
SPLIT = (12, 60, 3, 9, 30, 2)
SPLIT_GROUPS = ([0], [1, 2], [3, 4, 5])
SPLIT_BUCKETS = (16, 64, 64)


@pytest.mark.parametrize("kind", ["dense", "expert", "held", "int8", "adapter"])
def test_total_over_the_largest_bucket_splits_in_order(kind):
    joint, lone = _engines(kind)
    engine = joint[2]
    prompts = _prompts(SPLIT, salt=99)
    before = (
        engine.prefill_programs, engine.prefill_tokens_real,
        engine.prefill_tokens_padded, engine.device_syncs,
    )
    got_j, pools_j, _, tables, slots = _admit(
        joint, prompts, [list(range(len(SPLIT)))]
    )
    assert engine.prefill_programs - before[0] == len(SPLIT_GROUPS)
    assert engine.prefill_tokens_real - before[1] == sum(SPLIT)
    assert engine.prefill_tokens_padded - before[2] == sum(SPLIT_BUCKETS)
    # one readback: each program's tokens and last logits (and counts)
    per_program = 2 + bool(engine._count_fields)
    assert engine.device_syncs - before[3] == per_program * len(SPLIT_GROUPS)
    # what each prompt gets is what its own group gives it, in order
    got_l, pools_l, _, _, _ = _admit(lone, prompts, list(SPLIT_GROUPS))
    for i in range(len(SPLIT)):
        assert got_j[i][0] == got_l[i][0]
        np.testing.assert_array_equal(got_j[i][1], got_l[i][1])
    for key in pools_j:
        np.testing.assert_array_equal(pools_j[key], pools_l[key])


@pytest.mark.parametrize("kind", ["dense", "held"])
def test_scheduler_counts_prefill_programs(kind):
    """`SchedulerStats.prefill_batches` advances by the programs an
    admission dispatched (the benchmark scales the engine's prefill
    counters by traced executions of the prefill module over it), and
    mirrors the engine's own count."""
    model, sched, engine, cache = _serve(kind)
    reqs = [
        Request(rid=i, prompt=p, max_new_tokens=2)
        for i, p in enumerate(_prompts(SPLIT, salt=5))
    ]
    sched.run(reqs)
    assert all(r.status == "finished" for r in reqs)
    st = sched.stats
    assert st.prefill_batches == st.prefill_programs == engine.prefill_programs
    assert st.prefill_programs == len(SPLIT_GROUPS)
    assert st.prefill_tokens_real == sum(SPLIT)
    assert st.prefill_tokens_padded == sum(SPLIT_BUCKETS)
    assert st.pool_steps_donated + st.pool_steps_copied == (
        st.prefill_batches + st.decode_steps
    )


@pytest.mark.parametrize("lengths", [LENGTHS, SPLIT], ids=["one_row", "split"])
def test_moe_choice_indexes_rows_of_the_call(lengths):
    """`moe_choice["prefill"]` reads [expert layers, row of THIS call,
    position, k] whatever rows the prompts were packed into, across the
    programs of a split: each row is the choice its prompt gets alone."""
    joint, lone = _engines("held")
    prompts = _prompts(lengths, salt=3)
    _admit(joint, prompts, [list(range(len(prompts)))])
    picked = np.asarray(joint[2].moe_choice["prefill"])
    layers = LATENT["num_layers"] - LATENT["dense_layers"]
    k = MOE["experts_per_token"]
    assert picked.shape == (layers, len(prompts), max(lengths), k)
    assert picked.dtype == np.int32
    for row, prompt in enumerate(prompts):
        _admit(lone, [prompt], [[0]])
        alone = np.asarray(lone[2].moe_choice["prefill"])
        assert alone.shape == (layers, 1, len(prompt), k)
        n = len(prompt)
        assert np.array_equal(picked[:, row, :n], alone[:, 0])
        assert (picked[:, row, :n] >= 0).all() and (picked[:, row, n:] == -1).all()


def test_expert_rows_are_the_packed_rows():
    """An expert layer sorts T x k rows a program, padding included, and a
    held-share layer counts as absent only the live tokens' rows."""
    _, _, engine, cache = joint = _engines("held")[0]
    layers = LATENT["num_layers"] - LATENT["dense_layers"]
    k = MOE["experts_per_token"]
    rows, absent = engine.moe_rows_prefill, engine.moe_rows_absent_prefill
    _admit(joint, _prompts(LENGTHS, salt=8), [list(range(len(LENGTHS)))])
    picked = np.asarray(engine.moe_choice["prefill"])
    away = ((picked >= 0) & ((picked < 2) | (picked >= 6))).sum()
    assert engine.moe_rows_absent_prefill - absent == away > 0
    # computed: the held experts' rows, of the 64 x k the program sorted
    assert 0 < engine.moe_rows_prefill - rows <= 64 * k * layers - away


def test_what_no_program_can_hold_is_refused():
    model, _, engine, _ = _engines("dense")[0]
    with pytest.raises(ValueError, match="at least one"):
        engine.prefill(model.params, [], [])
    with pytest.raises(ValueError, match="max_seqs"):
        engine.prefill(model.params, [[1]] * (SLOTS + 1), list(range(SLOTS + 1)))
    # a prompt longer than the largest configured bucket
    _, short, cache = build_scheduler(model, ServeConfig(
        max_seqs=2, max_seq_len=SEQ, prefill_buckets=(16, 32),
    ))
    slot = cache.alloc(40, 41)
    with pytest.raises(ValueError, match="exceeds"):
        short.prefill(model.params, [[1] * 40], [slot])


# -- no program after the warm-up -------------------------------------------------

_COMPILES = [0]
jax.monitoring.register_event_duration_secs_listener(
    lambda event, seconds, **_: _COMPILES.__setitem__(
        0, _COMPILES[0]
        + (event == "/jax/core/compile/backend_compile_duration"),
    )
)


@pytest.mark.parametrize("kind", ["dense", "expert", "held", "adapter"])
def test_no_admission_traces_a_program_after_the_warm_up(kind):
    """`benchmarks/families/decoder_lm.py:warm_up`'s sequence: one request
    alone per prefill bucket, then n = 2..max_seqs requests of the
    SHORTEST length together. After it no admission of any composition
    (any count, any lengths the traffic holds, totals that split) traces
    the prefill body or compiles any program, large or small: that is
    what keeps `compiles_in_window` at 0."""
    model, sched, engine, cache = _serve(kind)
    traces = [0]
    body = engine._prefill_impl_paged

    def counted(*a, **kw):
        traces[0] += 1
        return body(*a, **kw)

    engine._prefill_impl_paged = counted
    rng = np.random.default_rng(17)
    lengths = sorted(rng.integers(4, 61, size=40).tolist() + [4, 60])
    by_bucket = {}
    for n in lengths:
        by_bucket.setdefault(cache.spec.bucket(n), n)
    assert sorted(by_bucket) == list(BUCKETS)
    rid = 0

    def run(ns, new=2):
        nonlocal rid
        reqs = [
            Request(rid=rid + i, max_new_tokens=new,
                    prompt=rng.integers(1, VOCAB, size=n).tolist())
            for i, n in enumerate(ns)
        ]
        rid += len(reqs)
        sched.run(reqs)
        assert all(r.status == "finished" for r in reqs)

    for _, n in sorted(by_bucket.items()):
        run([n], new=3)
    for n in range(2, SLOTS + 1):
        run([min(lengths)] * n)
    assert traces[0] == len(BUCKETS) == len(engine._prefill_cache)
    programs = engine.prefill_programs
    _COMPILES[0] = 0
    for _ in range(12):
        run(rng.choice(lengths, size=rng.integers(1, SLOTS + 1)).tolist())
    run([60, 60, 60, 4, 4, 60])  # four programs
    assert engine.prefill_programs - programs > 13  # some admissions split
    assert traces[0] == len(BUCKETS)
    assert _COMPILES[0] == 0
