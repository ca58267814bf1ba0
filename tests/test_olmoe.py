"""OLMoE's block (RMSNorm, QK-norm, rotary positions, a dropless top-k
expert layer) through the builder, the trainer and every serving step
kind, each against the plain reference `benchmarks/reference/olmoe.py`
at 1e-5 in exact float32 (conftest pins `highest`), at a small size:
2 layers, hidden 64, 4 heads of 16, 8 experts of width 32 with 2 per
token, vocabulary 211, seeded weights."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import olmoe as reference  # noqa: E402
from flexflow_tpu import (  # noqa: E402
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_olmoe  # noqa: E402
from flexflow_tpu.serving import ServeConfig, build_scheduler  # noqa: E402
from tests.conftest import page_geometry

VOCAB, K, SEQ, TOL = 211, 2, 32, 1e-5
SIZES = dict(
    vocab_size=VOCAB, hidden=64, num_heads=4, num_layers=2, expert_hidden=32,
    num_experts=8, experts_per_token=K,
)


def _model(lr=0.01):
    cfg = FFConfig(batch_size=4)
    cfg.seed = 7
    model = FFModel(cfg)
    tok = model.create_tensor([4, SEQ], dtype=DataType.INT32, name="tokens")
    build_olmoe(model, tok, **SIZES)
    model.compile(
        optimizer=SGDOptimizer(lr=lr),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1],
    )
    return model


@pytest.fixture(scope="module")
def olmoe():
    return _model()


def _weights(model, params=None):
    params = model.params if params is None else params
    return [list(params[g]) for g in sorted(params)]


def _want(model, seq, positions=None):
    logits, _ = reference.run(_weights(model), seq, SEQ, k=K)
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


def _prefill_then_decode(model, layout, steps=8):
    """Last-position logits of a prefill and `steps` cached decode steps
    through the engine, and the token sequence they were computed over."""
    _, engine, cache = _serve(model, **page_geometry(layout, SEQ))
    prompt = _prompt(11)
    slot = cache.alloc(len(prompt), len(prompt) + steps)
    nxt, last = engine.prefill(model.params, [prompt], [slot])
    seq, got, tok = list(prompt), [last[0]], int(nxt[0])
    for _ in range(steps):
        seq.append(tok)
        nxt, logits = engine.decode(
            model.params, _one_hot_slot(tok, slot), _one_hot_slot(True, slot, dtype=bool)
        )
        got.append(logits[slot])
        tok = int(nxt[slot])
    return engine, np.stack(got), seq, len(prompt)


def case_forward(model):
    x = np.stack([_prompt(SEQ, salt=b) for b in range(4)]).astype(np.int32)
    ex = model.executor
    values = ex.forward_values(
        model.params, {"tokens": jnp.asarray(x)}, None, train=False
    )
    got = np.asarray(values[(ex.logits_ref.guid, ex.logits_ref.out_idx)])
    assert max(_gap(got[b], _want(model, x[b])) for b in range(4)) < TOL


def case_prefill_decode(model, layout="paged"):
    engine, got, seq, n = _prefill_then_decode(model, layout)
    assert _gap(got, _want(model, seq, range(n - 1, n + 8))) < TOL
    # the counters: every (token, choice) row computed, the padding
    # behind the prompt in its one packed row too
    layers = SIZES["num_layers"]
    assert engine.moe_rows_prefill == engine.cache.spec.bucket(n) * K * layers
    assert engine.moe_rows_decode == 8 * 4 * K * layers
    assert 8 * layers * K <= engine.moe_experts_touched_decode <= 8 * layers * 8


def case_one_page(model):
    case_prefill_decode(model, layout="one_page")


def case_chunked(model):
    """Chunked prefill against monolithic, and both against the reference."""
    prompt = _prompt(14)
    _, eng_m, cache_m = _serve(model)
    slot = cache_m.alloc(len(prompt), len(prompt) + 2)
    _, last_m = eng_m.prefill(model.params, [prompt], [slot])
    _, eng_c, cache_c = _serve(model)
    assert cache_c.alloc(0, len(prompt) + 2) == slot
    for start in range(0, len(prompt), 4):
        chunk = prompt[start:start + 4]
        tokens = np.zeros((4, len(chunk)), np.int32)
        tokens[slot] = chunk
        _, logits = eng_c.prefill_chunk(
            model.params, tokens, _one_hot_slot(len(chunk), slot)
        )
    want = _want(model, prompt, [len(prompt) - 1])
    assert _gap(last_m, want) < TOL
    assert _gap(logits[slot][None], want) < TOL
    # and the caches they leave serve the same next step
    for engine in (eng_m, eng_c):
        _, nxt = engine.decode(
            model.params, _one_hot_slot(5, slot), _one_hot_slot(True, slot, dtype=bool)
        )
        assert _gap(nxt[slot][None], _want(model, prompt + [5], [len(prompt)])) < TOL


def case_rigged_router(model):
    """A router of zeros ties every expert on every row, and a tie goes to
    the lowest indices: all rows pick experts 0 and 1, each carries four
    times the mean load, and nothing is dropped."""
    rigged = dict(model.params)
    for g, ws in model.params.items():
        if len(ws) == 4 and ws[0].shape == (64, 8):
            rigged[g] = [jnp.zeros_like(ws[0]), *ws[1:]]
    _, engine, cache = _serve(model)
    prompt = _prompt(13, salt=2)
    slot = cache.alloc(len(prompt), len(prompt) + 1)
    _, last = engine.prefill(rigged, [prompt], [slot])
    want, chosen = reference.run(_weights(model, rigged), prompt, SEQ, k=K)
    assert _gap(last, want[[len(prompt) - 1]]) < TOL
    assert {tuple(r) for layer in chosen for r in layer.tolist()} == {(0, 1)}
    assert engine.moe_experts_touched_prefill == 2 * SIZES["num_layers"]


def case_gradients(model):
    """One fit() step under plain SGD at lr 1: before - after is the
    gradient the trainer applied, against jax.grad of the reference."""
    model = _model(lr=1.0)
    x = np.stack([_prompt(SEQ, salt=b) for b in range(4)]).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    # fit() donates the parameters it updates: keep host copies
    before = [[np.asarray(w) for w in ws] for ws in _weights(model)]

    def loss(weights):
        total = 0.0
        for b in range(4):
            logits, _ = reference.forward(weights, jnp.asarray(x[b]), k=K)
            logp = jax.nn.log_softmax(logits, axis=-1)
            total = total - jnp.mean(logp[jnp.arange(SEQ), y[b]])
        return total / 4

    want = jax.grad(loss)([[jnp.asarray(w) for w in ws] for ws in before])
    model.fit(x, y, epochs=1, batch_size=4, verbose=False)
    after = _weights(model)
    worst = 0.0
    for ws_b, ws_a, ws_w in zip(before, after, want):
        for b, a, w in zip(ws_b, ws_a, ws_w):
            got = np.asarray(b) - np.asarray(a)
            worst = max(worst, float(np.max(np.abs(got - np.asarray(w)))))
    scale = max(float(np.max(np.abs(np.asarray(w)))) for ws in want for w in ws)
    assert worst / scale < TOL, (worst, scale)


def case_verify(model):
    """A verify step scores w positions at lengths .. lengths + w - 1."""
    _, engine, cache = _serve(model)
    prompt = _prompt(9, salt=1)
    slot = cache.alloc(len(prompt), len(prompt) + 6)
    nxt, _ = engine.prefill(model.params, [prompt], [slot])
    draft = [int(nxt[0]), 17, 23, 5]
    logits = engine.verify(
        model.params, _one_hot_slot(draft, slot), _one_hot_slot(len(draft), slot)
    )
    n = len(prompt)
    want = _want(model, prompt + draft, range(n, n + len(draft)))
    assert _gap(logits[slot], want) < TOL


def case_verify_tree(model):
    """Two branches below the root: each row stands at its depth, and
    scores as if its root-to-row chain were the only continuation."""
    _, engine, cache = _serve(model)
    prompt = _prompt(9, salt=3)
    slot = cache.alloc(len(prompt), len(prompt) + 6)
    nxt, _ = engine.prefill(model.params, [prompt], [slot])
    rows = [int(nxt[0]), 17, 23, 5, 40]
    parents = [-1, 0, 0, 1, 2]  # 17 -> 5 and 23 -> 40
    table = np.tile(np.arange(-1, len(rows) - 1, dtype=np.int32), (4, 1))
    table[slot] = parents
    logits = engine.verify_tree(
        model.params, _one_hot_slot(rows, slot), _one_hot_slot(len(rows), slot),
        table,
    )
    n = len(prompt)
    for row, chain in ((3, [rows[0], 17, 5]), (4, [rows[0], 23, 40])):
        want = _want(model, prompt + chain, [n + len(chain) - 1])
        assert _gap(logits[slot][row][None], want) < TOL, row


def case_scheduler_streams(model):
    """A chained decode step takes its tokens on the device and its
    positions from the lengths the host reserved a step ahead."""
    from flexflow_tpu.serving import Request

    def run(**kw):
        sched, _, _ = _serve(model, **kw)
        reqs = [
            Request(rid=i, prompt=_prompt(5 + 3 * i, salt=i), max_new_tokens=9)
            for i in range(3)
        ]
        return {r.rid: r.generated for r in sched.run(reqs)}

    plain = run(serve_async=False)
    assert run() == plain
    # and the plain stream is the reference's greedy stream
    seq = _prompt(5, salt=0)
    for tok in plain[0]:
        assert int(np.argmax(_want(model, seq, [len(seq) - 1])[0])) == tok
        seq.append(tok)


def case_expert_parallel(model):
    """The expert dim shards over the model axis under a replicated
    input, as attention's heads do: the search finds the site, the
    stacked experts are sharded, and the sharded model computes what one
    device computes."""
    from flexflow_tpu.core.types import OperatorType
    from flexflow_tpu.parallel.strategy import Strategy, annotate_input_batch
    from flexflow_tpu.runtime.executor import MeshConfig
    from flexflow_tpu.search.rewrites import find_tp_sites

    def apply(g):
        annotate_input_batch(g, 2)
        for site in find_tp_sites(g):
            if site.kind == "sparse_moe":
                assert site.divisible_by(g, 2) and not site.divisible_by(g, 3)
                site.apply(g, 2, 1)

    cfg = FFConfig(batch_size=4)
    cfg.seed = 7
    ep = FFModel(cfg)
    tok = ep.create_tensor([4, SEQ], dtype=DataType.INT32, name="tokens")
    build_olmoe(ep, tok, **SIZES)
    ep.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[],
        strategy=Strategy(MeshConfig(("data", "model"), (2, 2)), apply, name="dp2xep2"),
    )
    layers = [
        n for n in ep.graph.nodes.values() if n.op_type == OperatorType.SPARSE_MOE
    ]
    assert len(layers) == SIZES["num_layers"]
    assert all(n.weight_shapes[1].dims[0].degree == 2 for n in layers)
    x = np.stack([_prompt(SEQ, salt=b) for b in range(4)]).astype(np.int32)
    batch = {"tokens": x, "label": np.roll(x, -1, axis=1)}
    got, _ = ep.executor.eval_step()(ep.params, ep.executor.shard_batch(batch))
    want, _ = model.executor.eval_step()(
        model.params, model.executor.shard_batch(batch)
    )
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)


def case_refuses_adapters(model):
    """LoRA deltas land after q and k are normalised and rotated: not the
    adapted model, so the engine refuses at construction."""
    with pytest.raises(ValueError, match="rotary positions or QK-norm"):
        _serve(model, adapters=2, adapter_rank=4)


CASES = {
    name[len("case_"):]: fn
    for name, fn in sorted(globals().items()) if name.startswith("case_")
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_olmoe_against_reference(olmoe, case):
    CASES[case](olmoe)


def test_default_attention_parameters_add_nothing():
    """With rope_theta None and qk_norm False the attention node has the
    weights and the jaxpr it always had."""
    from flexflow_tpu.models import build_decoder_lm
    from flexflow_tpu.ops.attention import is_positional, mha_project_qkv

    cfg = FFConfig(batch_size=2)
    model = FFModel(cfg)
    tok = model.create_tensor([2, 8], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(model, tok, vocab_size=31, hidden=16, num_heads=2,
                     num_layers=1, ff_dim=32)
    node = next(
        n for n in model.graph.nodes.values() if n.name.startswith("multihead")
    )
    assert not is_positional(node.params) and len(node.weight_shapes) == 4
    x = jnp.ones((2, 8, 16))
    ws = [jnp.ones((16, 2, 8))] * 3
    positions = jnp.arange(8)
    with_params = jax.make_jaxpr(
        lambda x: mha_project_qkv(
            (x, x, x), ws, None, use_bias=False, params=node.params,
            positions=positions,
        )
    )(x)
    without = jax.make_jaxpr(
        lambda x: mha_project_qkv((x, x, x), ws, None, use_bias=False)
    )(x)
    assert str(with_params) == str(without)
