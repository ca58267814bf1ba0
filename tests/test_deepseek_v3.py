"""The `deepseek_v3` block (latent attention over a latent paged cache, a
leading dense gated MLP, a sigmoid-routed expert layer that holds a share
of its experts, a shared expert) through the builder, the trainer and the
serving steps a latent model is served by, each against the plain
reference `benchmarks/reference/deepseek_v3.py` in exact float32 (conftest
pins `highest`), at a small size: 3 layers (1 dense), hidden 64, 4 heads of
16 + 8 rotary, rank 32, 8 experts of width 24 with 2 per token of which
this "chip" holds 4, a shared expert of 48, vocabulary 211, seeded
weights."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import deepseek_v3 as reference  # noqa: E402
from flexflow_tpu import (  # noqa: E402
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.core.types import OperatorType  # noqa: E402
from flexflow_tpu.models import build_deepseek_v3  # noqa: E402
from flexflow_tpu.ops import attention as A  # noqa: E402
from flexflow_tpu.ops import moe  # noqa: E402
from flexflow_tpu.ops.pallas import decode_kernel as dk  # noqa: E402
from flexflow_tpu.serving import ServeConfig, build_scheduler  # noqa: E402

VOCAB, K, SEQ, TOL = 211, 2, 64, 1e-4
EPS, THETA, ROPE, SCALE, HELD = 1e-6, 1e6, 8, 2.448, (0, 4)
SIZES = dict(
    vocab_size=VOCAB, hidden=64, num_heads=4, num_layers=3, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=ROPE, v_head_dim=16, dense_hidden=96,
    dense_layers=1, expert_hidden=24, num_experts=8, experts_per_token=K,
    shared_experts=2, routed_scale=SCALE, rope_theta=THETA, eps=EPS,
)


def _model(held=HELD, lr=0.01, seed=7):
    cfg = FFConfig(batch_size=4)
    cfg.seed = seed
    model = FFModel(cfg)
    tok = model.create_tensor([4, SEQ], dtype=DataType.INT32, name="tokens")
    build_deepseek_v3(model, tok, experts_held=held, **SIZES)
    model.compile(
        optimizer=SGDOptimizer(lr=lr),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1],
    )
    # the routers' choice bias is a trained buffer, zero from the builder:
    # drawn here, small and not zero, so that leaving it out shows
    for node in _nodes(model, OperatorType.SPARSE_MOE):
        ws = model.params[node.guid]
        assert not np.any(np.asarray(ws[4]))
        ws[4] = jax.random.uniform(
            jax.random.PRNGKey(seed + node.guid), ws[4].shape, ws[4].dtype,
            -0.1, 0.1,
        )
    return model


@pytest.fixture(scope="module")
def served():
    return _model()


def _weights(model, params=None):
    params = model.params if params is None else params
    return [list(params[g]) for g in sorted(params)]


def _want(model, seq, positions=None, held=HELD):
    logits, _ = reference.run(
        _weights(model), seq, SEQ, EPS, THETA, ROPE, K, SCALE, held
    )
    return logits if positions is None else logits[np.asarray(positions)]


def _gap(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def _prompt(n, salt=0):
    return [(salt * 31 + 7 * j * j + 3 * j) % (VOCAB - 1) + 1 for j in range(n)]


def _serve(model, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", SEQ)
    return build_scheduler(model, ServeConfig(**kw))


def _one_hot_slot(values, slot, n=4, dtype=np.int32):
    out = np.zeros((n,) + np.shape(values), dtype)
    out[slot] = values
    return out


def _nodes(model, op_type):
    return [n for n in model.graph.nodes.values() if n.op_type == op_type]


def _prefill_then_decode(model, steps=12, **kw):
    """Last-position logits of a prefill of 11 tokens and `steps` cached
    decode steps (positions 11 .. 22: across the page boundary at 16)."""
    _, engine, cache = _serve(model, **kw)
    prompt = _prompt(11)
    slot = cache.alloc(len(prompt), len(prompt) + steps)
    nxt, last = engine.prefill(model.params, [prompt], [slot])
    seq, got, tok = list(prompt), [last[0]], int(nxt[0])
    for _ in range(steps):
        seq.append(tok)
        nxt, logits = engine.decode(
            model.params, _one_hot_slot(tok, slot),
            _one_hot_slot(True, slot, dtype=bool),
        )
        got.append(logits[slot])
        tok = int(nxt[slot])
    return engine, np.stack(got), seq, len(prompt)


def case_forward(model):
    """The operators' plain lowerings: the trainer's forward pass."""
    x = np.stack([_prompt(SEQ, salt=b) for b in range(4)]).astype(np.int32)
    ex = model.executor
    values = ex.forward_values(
        model.params, {"tokens": jnp.asarray(x)}, None, train=False
    )
    got = np.asarray(values[(ex.logits_ref.guid, ex.logits_ref.out_idx)])
    assert max(_gap(got[b], _want(model, x[b])) for b in range(4)) < TOL


def case_prefill_decode(model, kernel="dense"):
    engine, got, seq, n = _prefill_then_decode(model, decode_kernel=kernel)
    assert _gap(got, _want(model, seq, range(n - 1, n + 12))) < TOL
    assert engine.kernel_fallbacks == 0
    # the counters. Expert layers: every (token, choice) row is either
    # computed here or left to the chip that holds its expert; the rows
    # computed count the padding (idle slots, positions past the prompt:
    # the matmuls run them), the rows absent only the one live request's
    layers = SIZES["num_layers"] - SIZES["dense_layers"]
    bucket = engine.cache.spec.bucket(n)
    assert engine.moe_rows_prefill <= 4 * bucket * K * layers
    assert engine.moe_rows_decode <= 12 * 4 * K * layers
    _, chosen = reference.run(
        _weights(model), seq, SEQ, EPS, THETA, ROPE, K, SCALE, HELD
    )
    absent = (chosen < HELD[0]) | (chosen >= HELD[0] + HELD[1])
    assert engine.moe_rows_absent_prefill == int(absent[:, :n].sum())
    assert engine.moe_rows_absent_decode == int(absent[:, n:n + 12].sum())
    assert 0 < engine.moe_rows_absent_decode < 12 * K * layers
    assert engine.moe_experts_touched_decode <= 12 * layers * HELD[1]
    # the routers' choice of the last programs stays on the device
    picked = np.asarray(engine.moe_choice["decode"])
    assert picked.shape == (layers, 4, 1, K)
    slot = int(np.argmax(engine.cache.lengths))
    assert np.array_equal(
        np.sort(picked[:, slot, 0]), np.sort(chosen[:, n + 11])
    )
    picked = np.asarray(engine.moe_choice["prefill"])
    assert np.array_equal(
        np.sort(picked[:, 0, :n], axis=-1), np.sort(chosen[:, :n], axis=-1)
    )
    # latent rows attended: the new row included, over the three layers
    assert engine.mla_rows_read_decode == sum(range(n + 1, n + 13)) * 3


def case_scopes(model):
    """The decode program names what the per-layer metrics read: the
    four `mla.*` scopes, and `moe.shared` around the shared experts (the
    gated MLPs beside an expert layer) and not around layer 0's."""
    _, engine, cache = _serve(model)
    spec = cache.spec

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    n = spec.max_seqs
    text = engine._decode_jit.trace(
        model.params, s((n,)),
        s((n, engine._STATE_COLUMNS + spec.max_pages_per_seq)),
        *cache.pools,
    ).lower().as_text(debug_info=True)
    for scope in ("mla.project", "mla.absorb", "mla.attend", "mla.out",
                  "moe.route", "moe.experts", "moe.shared"):
        assert f"/{scope}/" in text, scope
    gated = [node.guid for node in _nodes(model, OperatorType.GATED_MLP)]
    assert len(gated) == 3 and engine._shared_guids == set(gated[1:])


def case_prefill_decode_kernel(model):
    """The same through the latent Pallas kernel, in the interpreter."""
    case_prefill_decode(model, kernel="pallas")


def case_one_page(model):
    _, got, seq, n = _prefill_then_decode(model, kv_page_size=SEQ)
    assert _gap(got, _want(model, seq, range(n - 1, n + 12))) < TOL


def case_absorbed_equals_decompressed(model):
    """Two computations of one function, on one cache: the rows a prefill
    wrote, attended absorbed (the decode step's way, over the pool) and
    decompressed (keys and values of every head rebuilt from the rows)."""
    _, engine, cache = _serve(model)
    prompt = _prompt(21, salt=4)
    slot = cache.alloc(len(prompt), len(prompt) + 1)
    engine.prefill(model.params, [prompt], [slot])
    node = _nodes(model, OperatorType.LATENT_ATTENTION)[0]
    p, ws = node.params, model.params[node.guid]
    rank, dn, dr = p["kv_lora_rank"], p["qk_nope_head_dim"], p["qk_rope_head_dim"]
    pool = cache.k[node.guid]
    assert pool.shape[-1] == 128 and not cache.v  # 32 + 8, padded; one pool
    n = len(prompt)
    pages = cache.block_tables[slot, : -(-n // 16)]
    rows = np.asarray(pool)[pages].reshape(-1, 128)[:n]
    assert np.all(rows[:, rank + dr:] == 0)
    rng = np.random.default_rng(0)
    q_nope = jnp.asarray(rng.standard_normal((1, 1, 4, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((1, 1, 4, dr)), jnp.float32)
    # decompressed, by hand: the last position's query over all n rows
    kv = jnp.einsum("sr,rhd->shd", rows[:, :rank], ws[3])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(rows[:, None, rank:rank + dr], (n, 4, dr))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)[0, 0]
    probs = jax.nn.softmax(jnp.einsum("hd,shd->hs", q, k) / np.sqrt(dn + dr), -1)
    want = jnp.einsum("hs,shd->hd", probs, kv[..., dn:])
    # absorbed, the engine's helpers over the pool, dense and kernel
    for kernel in ("dense", "pallas"):
        attended = A.paged_latent_decode_attention(
            A.mla_absorb_query(q_nope, q_rope, ws, p, None, 128),
            pool, jnp.asarray(cache.block_tables[slot][None]),
            jnp.asarray([n - 1], jnp.int32), rank, (dn + dr) ** -0.5,
            kernel=kernel,
        )
        got = A.mla_absorb_values(attended, ws, p, None)[0, 0]
        assert _gap(got, np.asarray(want)) < 1e-5, kernel


def case_latent_kernel_against_dense(model):
    """The kernel in the Pallas interpreter against the dense gather, on
    a pool of random rows: ragged lengths, a dead slot, shuffled pages, a
    length on a page's first and last row."""
    del model
    rng = np.random.default_rng(3)
    b, h, row, ps, np_seq, pages = 5, 8, 256, 8, 12, 40
    pool = jnp.asarray(rng.standard_normal((pages, ps, row)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, row)), jnp.float32)
    lengths = np.asarray([0, 37, 8, 95, 15], np.int32)
    tables = np.full((b, np_seq), pages, np.int32)
    free = list(rng.permutation(pages))
    for i, n in enumerate(lengths):
        for j in range(n // ps + 1):
            tables[i, j] = free.pop()
    tables[2] = pages  # a dead slot: no page at all
    args = (pool, jnp.asarray(tables), jnp.asarray(lengths), 128, 0.07)
    want = A.paged_latent_decode_attention(q, *args, kernel="dense")
    got = dk.paged_flash_decode_latent(q, *args)
    live = [0, 1, 3, 4]
    assert _gap(np.asarray(got)[live], np.asarray(want)[live]) < 1e-5
    assert np.all(np.asarray(got)[2] == 0)


def case_routing_against_reference(model):
    """The program's routing function against the reference's: sigmoid
    scores, the bias in the choice and not in the weight, weights that
    sum to the scale."""
    node = _nodes(model, OperatorType.SPARSE_MOE)[0]
    router, bias = model.params[node.guid][0], model.params[node.guid][4]
    assert float(jnp.max(jnp.abs(bias))) > 0.01  # drawn, small, not zero
    x = jnp.asarray(np.random.default_rng(5).standard_normal((96, 64)), jnp.float32)
    got_w, got_e = moe.sparse_moe_route(
        x, router, K, True, scoring="sigmoid", bias=bias, scale=SCALE
    )
    want_w, want_e = reference.route(x, router, bias, K, SCALE)
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_allclose(got_w, want_w, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(got_w, -1), SCALE, rtol=1e-6)
    # the bias moves the choice ...
    _, no_bias_e = moe.sparse_moe_route(x, router, K, True, scoring="sigmoid")
    assert not np.array_equal(np.asarray(no_bias_e), np.asarray(got_e))
    # ... and not the weight: a chosen expert's weight is its own score
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, got_e, axis=-1)
    np.testing.assert_allclose(
        got_w, picked / jnp.sum(picked, -1, keepdims=True) * SCALE, rtol=1e-6
    )
    # and leaving it out of the served model fails the comparison
    zeroed = {
        g: ws[:4] + [jnp.zeros_like(ws[4])] if len(ws) == 5 else ws
        for g, ws in model.params.items()
    }
    x_tok = jnp.asarray(np.asarray([_prompt(SEQ)] * 4, np.int32))
    ex = model.executor
    values = ex.forward_values(zeroed, {"tokens": x_tok}, None, train=False)
    got = np.asarray(values[(ex.logits_ref.guid, ex.logits_ref.out_idx)])
    assert _gap(got[0], _want(model, _prompt(SEQ))) > 1e-2


def case_reference_under_a_given_choice(model):
    """`reference.run(forced=)`: given its own choice the reference is
    itself; given another program's (one expert swapped at one position)
    it differs from that position on and nowhere before it, and reports
    the choice it was given."""
    seq = _prompt(40)
    args = (_weights(model), seq, SEQ, EPS, THETA, ROPE, K, SCALE, HELD)
    logits, chosen = reference.run(*args)
    again, given = reference.run(*args, forced=chosen)
    assert np.array_equal(given, chosen) and _gap(again, logits) < 1e-6
    other = chosen.copy()
    unused = next(e for e in range(8) if e not in chosen[0, 20])
    other[0, 20, 0] = unused
    moved, given = reference.run(*args, forced=other, positions=range(40))
    assert np.array_equal(given, other)
    assert _gap(moved[:20], logits[:20]) < 1e-6 < 1e-3 < _gap(moved[20:], logits[20:])


def case_shares_add_up(model):
    """The two shares' routed parts, plus what every chip computes alike
    (the shared expert) counted once, add up to the uncut reference
    layer: the program's layer told it holds experts 0-3, the same told
    4-7, against the reference given all eight."""
    del model
    whole = _model(held=None)
    node = _nodes(whole, OperatorType.SPARSE_MOE)[0]
    router, w_gate, w_up, w_down, bias = whole.params[node.guid]
    shared = whole.params[_nodes(whole, OperatorType.GATED_MLP)[1].guid]
    m = jnp.asarray(
        np.random.default_rng(9).standard_normal((2, 24, 64)), jnp.float32
    )
    parts, absent = [], 0
    for first in (0, 4):
        params = dict(node.params, experts_held=(first, 4))
        ws = [router] + [w[first:first + 4] for w in (w_gate, w_up, w_down)] + [bias]
        y, counts = moe.sparse_moe(m, ws, params)
        parts.append(y)
        absent += int(counts[2])
        assert int(counts[0]) + int(counts[2]) == 2 * 24 * K
    assert absent == 2 * 24 * K  # a row is absent from exactly one share
    from flexflow_tpu.ops.core_ops import gated_mlp

    got = parts[0] + parts[1] + gated_mlp(m, shared)
    m2 = m.reshape(-1, 64)
    routed, _ = reference.routed_experts(
        m2, router, w_gate, w_up, w_down, bias, K, SCALE, held=None
    )
    want = routed + reference._gated(m2, *shared)
    assert _gap(got.reshape(-1, 64), np.asarray(want)) < 1e-5
    # and one share alone is not the layer
    assert _gap((parts[0] + gated_mlp(m, shared)).reshape(-1, 64), np.asarray(want)) > 1e-2


def case_uncut_model(model):
    """`experts_held=None` is the published layer: every expert here."""
    del model
    whole = _model(held=None)
    seq = _prompt(SEQ, salt=2)
    ex = whole.executor
    values = ex.forward_values(
        whole.params, {"tokens": jnp.asarray([seq] * 4, jnp.int32)}, None,
        train=False,
    )
    got = np.asarray(values[(ex.logits_ref.guid, ex.logits_ref.out_idx)])
    assert _gap(got[0], _want(whole, seq, held=None)) < TOL


def case_cache_bytes(model):
    """One pool a layer, one row of rank + rope floats (padded to whole
    lane tiles) a token: what KVCacheSpec and the capacity estimate
    price."""
    from flexflow_tpu.search.auto import estimate_max_in_flight
    from flexflow_tpu.serving.kv_cache import cache_row

    _, _, cache = _serve(model)
    spec = cache.spec
    node = _nodes(model, OperatorType.LATENT_ATTENTION)[0]
    assert cache_row(node) == (1, 1, 128)
    assert (spec.kv_pools, spec.num_heads, spec.head_dim) == (1, 1, 128)
    assert spec.kv_bytes_per_token == 3 * 128 * 4
    assert spec.bytes_per_layer == spec.num_pages * 16 * 128 * 4
    assert spec.total_bytes == 3 * spec.bytes_per_layer
    assert sum(p.nbytes for p in cache.k.values()) == spec.total_bytes
    assert not cache.v and not cache.k_scale and not cache.v_scale
    # the published row at the published sizes: 512 + 64 -> 640
    assert A.mla_cache_row(dict(
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
    )) == 640
    # sequences of 48 tokens, whole pages of 16, three layers of one pool
    per_seq = 3 * 48 * 128 * 4
    assert estimate_max_in_flight(
        model.graph, 10 * per_seq + 1, 32, 16, SEQ, page_size=16
    ) == 10


def case_swap_round_trip(model):
    """The allocator's page copies go over whatever pools there are."""
    _, engine, cache = _serve(model)
    prompt = _prompt(19, salt=6)
    slot = cache.alloc(len(prompt), len(prompt) + 2)
    nxt, _ = engine.prefill(model.params, [prompt], [slot])
    handle = cache.swap_out(slot)
    assert handle is not None and cache.swapped_pages == 2
    slot = cache.swap_in(handle, len(prompt) + 2)
    _, logits = engine.decode(
        model.params, _one_hot_slot(int(nxt[0]), slot),
        _one_hot_slot(True, slot, dtype=bool),
    )
    want = _want(model, prompt + [int(nxt[0])], [len(prompt)])
    assert _gap(logits[slot][None], want) < TOL


def case_scheduler_streams(model):
    """The overlapped loop's chained decode steps emit what the
    synchronous loop emits, and that is the reference's greedy stream."""
    from flexflow_tpu.serving import Request

    def run(**kw):
        sched, engine, _ = _serve(model, **kw)
        reqs = [
            Request(rid=i, prompt=_prompt(5 + 3 * i, salt=i), max_new_tokens=14)
            for i in range(3)
        ]
        out = {r.rid: r.generated for r in sched.run(reqs)}
        return out, sched.stats

    plain, stats = run(serve_async=False)
    assert run()[0] == plain
    # the plain stream is the reference's greedy stream
    seq = _prompt(5, salt=0)
    for tok in plain[0]:
        assert int(np.argmax(_want(model, seq, [len(seq) - 1])[0])) == tok
        seq.append(tok)
    # and the engine's counters reach the scheduler's stats
    assert stats.mla_rows_read_decode > 0 and stats.moe_rows_absent_decode > 0
    assert stats.pool_steps_copied == 0 or jax.default_backend() == "cpu"


def case_gradients(model):
    """One fit() step under plain SGD at lr 1: before - after is the
    gradient the trainer applied, against jax.grad of the reference."""
    del model
    model = _model(lr=1.0)
    x = np.stack([_prompt(SEQ, salt=b) for b in range(4)]).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    before = [[np.asarray(w) for w in ws] for ws in _weights(model)]

    def loss(weights):
        total = 0.0
        for b in range(4):
            logits, _ = reference.forward(
                weights, jnp.asarray(x[b]), EPS, THETA, ROPE, K, SCALE, HELD
            )
            logp = jax.nn.log_softmax(logits, axis=-1)
            total = total - jnp.mean(logp[jnp.arange(SEQ), y[b]])
        return total / 4

    want = jax.grad(loss)([[jnp.asarray(w) for w in ws] for ws in before])
    model.fit(x, y, epochs=1, batch_size=4, verbose=False)
    worst = 0.0
    for ws_b, ws_a, ws_w in zip(before, _weights(model), want):
        for b, a, w in zip(ws_b, ws_a, ws_w):
            got = np.asarray(b) - np.asarray(a)
            worst = max(worst, float(np.max(np.abs(got - np.asarray(w)))))
    scale = max(float(np.max(np.abs(np.asarray(w)))) for ws in want for w in ws)
    assert worst / scale < TOL, (worst, scale)


REFUSED = {
    "int8": (dict(kv_dtype="int8", kv_page_size=32), "kv_dtype='int8'"),
    "adapters": (dict(adapters=2, adapter_rank=4), "adapters"),
    "prefix_cache": (dict(prefix_cache=True), "prefix_cache"),
    "ngram_draft": (dict(spec_draft="ngram", spec_k=2), "verify"),
    "tree_draft": (dict(spec_draft="ngram", spec_k=2, spec_branch=2), "verify"),
    "chunk": (dict(token_budget=32, chunk_size=16), "chunked-prefill"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_a_latent_model_is_not_served_through_is_refused_in_words(served, what):
    kw, words = REFUSED[what]
    with pytest.raises(ValueError, match=words):
        _serve(served, **kw)


def test_a_latent_draft_model_and_its_step_programs_are_refused(served):
    from flexflow_tpu.serving.spec import ModelDraftProposer

    with pytest.raises(ValueError, match="draft-model steps are not supported"):
        ModelDraftProposer(served, max_seqs=4, max_len=SEQ)
    _, engine, _ = _serve(served)
    for getter, key in (
        (engine._verify_fn, 3), (engine._tree_fn, 3), (engine._chunk_fn, (4, 16)),
    ):
        with pytest.raises(ValueError, match="latent attention"):
            getter(key)


CASES = {
    name[len("case_"):]: fn
    for name, fn in sorted(globals().items()) if name.startswith("case_")
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_deepseek_v3_against_reference(served, case):
    CASES[case](served)


def test_default_expert_layer_parameters_add_nothing():
    """With the new parameters at their defaults `sparse_moe` has the
    weights, the counts and the jaxpr it had: OLMoE's programs do not
    change."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
    ws = [
        jnp.asarray(rng.standard_normal(s), jnp.float32)
        for s in ((16, 4), (4, 16, 8), (4, 16, 8), (4, 8, 16))
    ]
    params = {"num_experts": 4, "k": 2, "expert_hidden": 8}
    text = str(jax.make_jaxpr(lambda a: moe.sparse_moe(a, ws, params))(x))
    # one top_k straight on the softmax; no mask over absent rows (the
    # only selects are the index wrap-arounds of the gathers, on int32)
    import re

    assert text.count("top_k") == 1
    assert not re.search(r":f32\[[^\]]*\] = select_n", text)
    y, counts = moe.sparse_moe(x, ws, params)
    assert counts.shape == (2,) and int(counts[0]) == 12
    held, counts3 = moe.sparse_moe(
        x, [ws[0]] + [w[:2] for w in ws[1:]], dict(params, experts_held=(0, 2))
    )
    assert counts3.shape == (3,) and int(counts3[0] + counts3[2]) == 12
    assert not np.allclose(np.asarray(held), np.asarray(y))


def test_the_latent_kernel_compiles_for_a_v5e_at_the_cells_geometry():
    """Mosaic itself, against a v5e topology description (no device): 16
    slots, 32 heads on one row of 640, pages of 16, 2,048 positions, a
    pool of 32,768 tokens."""
    import functools

    from jax.sharding import SingleDeviceSharding

    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here
        pytest.skip(f"no TPU topology description: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    fn = functools.partial(
        dk.paged_flash_decode_latent, v_width=512, sm_scale=192 ** -0.5,
        interpret=False,
    )
    args = (
        sds((16, 1, 32, 640)), sds((2048, 16, 640)), sds((16, 128), jnp.int32),
        sds((16,), jnp.int32),
    )
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    assert lowered.compile().memory_analysis().temp_size_in_bytes < 1 << 20
    blk = dk.latent_block(32, 640, 512, 16, 128, 4)
    assert (blk.pages, blk.rows, blk.heads) == (8, 128, 32)
