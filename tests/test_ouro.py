"""Ouro's looped stack (layers run several times over one set of weights,
each pass with cache layers of its own, an exit gate after every pass)
through the builder, the trainer and the serving engine, against the
plain reference `benchmarks/reference/ouro.py` at 1e-5 in exact float32
(conftest pins `highest`), at a small size: 2 layers x 3 passes, hidden
64, 4 heads of 16, MLP width 96, vocabulary 211, seeded weights with the
gains and the gate's bias moved off their initial one and zero."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import ouro as reference  # noqa: E402
from flexflow_tpu import (  # noqa: E402
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.core.types import OperatorType  # noqa: E402
from flexflow_tpu.models import build_ouro  # noqa: E402
from flexflow_tpu.serving import Request, ServeConfig, build_scheduler  # noqa: E402
from flexflow_tpu.telemetry import Telemetry  # noqa: E402
from tests.conftest import page_geometry  # noqa: E402

VOCAB, SEQ, LAYERS, LOOPS, EPS, THETA, TOL = 211, 64, 2, 3, 1e-6, 1e6, 1e-5
SIZES = dict(
    vocab_size=VOCAB, hidden=64, num_heads=4, num_layers=LAYERS, ff_dim=96,
    loops=LOOPS, rope_theta=THETA, eps=EPS,
)
LAYER_PARAMETERS = 4 * 64 * 64 + 3 * 64 * 96 + 4 * 64
PARAMETERS = 2 * VOCAB * 64 + LAYERS * LAYER_PARAMETERS + 64 + 64 + 1


def _model(lr=0.01, **config):
    cfg = FFConfig(batch_size=4)
    cfg.seed = 7
    for k, v in config.items():
        setattr(cfg, k, v)
    model = FFModel(cfg)
    tok = model.create_tensor([4, SEQ], dtype=DataType.INT32, name="tokens")
    head = build_ouro(model, tok, **SIZES)
    model.compile(
        optimizer=SGDOptimizer(lr=lr),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1], logits=head,
    )
    key = jax.random.PRNGKey(1)
    for guid in sorted(model.params):
        for i, w in enumerate(model.params[guid]):
            if w.ndim == 1:  # gains and the gate's bias
                key, sub = jax.random.split(key)
                model.params[guid][i] = w + 0.3 * jax.random.normal(sub, w.shape)
    return model


@pytest.fixture(scope="module")
def ouro():
    return _model()


def _weights(model, params=None):
    params = model.params if params is None else params
    return [list(params[g]) for g in sorted(params)]


def _want(model, seq, positions=None):
    return reference.run(_weights(model), seq, SEQ, EPS, THETA, LOOPS, positions)


def _gap(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def _prompt(n, salt=0):
    return [(salt * 31 + 7 * j * j + 3 * j) % (VOCAB - 1) + 1 for j in range(n)]


def _serve(model, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", SEQ)
    return build_scheduler(model, ServeConfig(**kw))


def _decode_together(model, engine, cache, prompts, steps):
    """Prefill `prompts` in one admission, then decode them side by side,
    each slot fed its own greedy token: every last-position logits row and
    the sequences they were computed over."""
    slots = [cache.alloc(len(p), len(p) + steps) for p in prompts]
    nxt, last = engine.prefill(model.params, prompts, slots)
    seqs = [list(p) for p in prompts]
    got = [[np.array(last[i])] for i in range(len(prompts))]
    toks = [int(t) for t in nxt]
    for _ in range(steps):
        tokens = np.zeros(cache.spec.max_seqs, np.int32)
        active = np.zeros(cache.spec.max_seqs, bool)
        for i, slot in enumerate(slots):
            seqs[i].append(toks[i])
            tokens[slot], active[slot] = toks[i], True
        nxt, logits = engine.decode(model.params, tokens, active)
        for i, slot in enumerate(slots):
            got[i].append(np.array(logits[slot]))
            toks[i] = int(nxt[slot])
    for slot in slots:
        cache.free(slot)
    return [np.stack(g) for g in got], seqs


def case_forward_and_exit_distribution(model):
    x = np.stack([_prompt(SEQ, salt=b) for b in range(4)]).astype(np.int32)
    ex = model.executor
    values = ex.forward_values(
        model.params, {"tokens": jnp.asarray(x)}, None, train=False
    )
    got = np.asarray(values[(ex.logits_ref.guid, ex.logits_ref.out_idx)])
    lam = np.stack([
        np.asarray(values[(g, 0)])[..., 0]
        for g in ex.topo if model.graph.nodes[g].name.endswith(".exit")
    ], axis=-1)
    assert lam.shape == (4, SEQ, LOOPS)
    stay = np.cumprod(1.0 - lam, axis=-1)
    exits = np.concatenate(
        [lam[..., :1], lam[..., 1:-1] * stay[..., :-2], stay[..., -2:-1]], axis=-1
    )
    for b in range(4):
        logits, want = _want(model, x[b])
        assert _gap(got[b], logits) < TOL
        assert np.max(np.abs(exits[b] - want)) < TOL
        np.testing.assert_allclose(want.sum(-1), 1.0, atol=1e-6)


def case_prefill_decode(model, layout="paged"):
    """Prompts of 5, 20 and 33 with 24 decode steps: the contexts cross
    one and two page boundaries, in every pass's own cache layers."""
    _, engine, cache = _serve(model, **page_geometry(layout, SEQ))
    prompts = [_prompt(n, salt=n) for n in (5, 20, 33)]
    got, seqs = _decode_together(model, engine, cache, prompts, 24)
    for g, seq, p in zip(got, seqs, prompts):
        want, _ = _want(model, seq, range(len(p) - 1, len(p) + 24))
        assert _gap(g, want) < TOL


def case_prefill_decode_one_page(model):
    case_prefill_decode(model, "one_page")


def case_slots_freed_and_reused(model):
    """A second wave in the slots (and pages) the first wave left, longer
    and shorter than what was there: no pass reads another's rows."""
    _, engine, cache = _serve(model, kv_pages=12)
    for wave, lengths in enumerate([(30, 9, 17, 4), (6, 26, 3, 21)]):
        prompts = [_prompt(n, salt=10 * wave + n) for n in lengths]
        got, seqs = _decode_together(model, engine, cache, prompts, 10)
        for g, seq, p in zip(got, seqs, prompts):
            want, _ = _want(model, seq, range(len(p) - 1, len(p) + 10))
            assert _gap(g, want) < TOL
        assert cache.pages_in_use == 0


def case_scheduler_generates_the_reference_tokens(model):
    sched, _, _ = _serve(model)
    reqs = [
        Request(rid=i, prompt=_prompt(n, salt=i), max_new_tokens=12)
        for i, n in enumerate((7, 19, 31))
    ]
    sched.run(reqs)
    for r in reqs:
        assert r.status == "finished"
        seq = list(r.prompt) + list(r.generated)
        want, _ = _want(model, seq, range(len(r.prompt) - 1, len(seq) - 1))
        assert list(np.argmax(want, -1)) == list(r.generated)


def case_each_shared_weight_is_stored_once(model):
    ex = model.executor
    assert sum(int(w.size) for ws in model.params.values() for w in ws) == PARAMETERS
    weighted = [g for g in ex.topo if model.graph.nodes[g].weight_shapes]
    per_layer, outside = 6, 4  # n1 attn n2 n3 mlp n4; embedding norm gate head
    assert len(weighted) == LOOPS * (LAYERS * per_layer + 2) + 2
    assert len(model.params) == LAYERS * per_layer + outside
    assert len(ex.weight_owner) == (LOOPS - 1) * (LAYERS * per_layer + 2)
    for borrower, owner in ex.weight_owner.items():
        b, o = model.graph.nodes[borrower].name, model.graph.nodes[owner].name
        assert o.startswith("p1.") and b.split(".", 1)[1] == o.split(".", 1)[1]


def case_cache_has_a_layer_for_every_pass(model):
    _, _, cache = _serve(model)
    spec = cache.spec
    names = [model.graph.nodes[g].name for g in spec.layer_guids]
    assert names == [
        f"p{p}.l{i}.attn" for p in range(1, LOOPS + 1) for i in range(1, LAYERS + 1)
    ]
    assert spec.kv_bytes_per_token == LOOPS * LAYERS * 2 * 64 * 4
    assert len(cache.k) == len(cache.v) == LOOPS * LAYERS


def case_weight_walk_gauges(model):
    telemetry = Telemetry()
    _, engine, _ = build_scheduler(
        model, ServeConfig(max_seqs=4, max_seq_len=SEQ), telemetry=telemetry
    )
    applied = PARAMETERS + (LOOPS - 1) * (LAYERS * LAYER_PARAMETERS + 64 + 64 + 1)
    want = {
        "weights_stored_bytes": 4 * PARAMETERS,
        "weights_applied_bytes": 4 * applied,
        "cache_layers": LOOPS * LAYERS,
        "weight_layers": LAYERS,
        "loop_passes": LOOPS,
    }
    assert engine.weight_walk == want
    for name, value in want.items():
        assert telemetry.registry.gauge(f"serve_{name}").value == value


def case_scopes_carry_pass_and_layer(model):
    _, engine, cache = _serve(model)
    texts = []
    run_step = engine._run_step

    def lowering(site, step_fn, params, inputs, adapter_args=(), **kw):
        texts.append(
            step_fn().lower(params, *inputs, *cache.pools, *adapter_args)
            .as_text(debug_info=True)
        )
        return run_step(site, step_fn, params, inputs, adapter_args, **kw)

    engine._run_step = lowering
    slot = cache.alloc(4, 8)
    engine.prefill(model.params, [_prompt(4)], [slot])
    cache.free(slot)
    for kind, part in (
        ("multihead_attention", "attn"), ("gated_mlp", "mlp"),
        ("rmsnorm", "n4"), ("ew_add", "add2"),
    ):
        for p in range(1, LOOPS + 1):
            assert f"{kind}:p{p}.l{LAYERS}.{part}" in texts[0]


def case_gradient_is_the_reference_gradient(model):
    """`jax.grad` through the executor against the gradient of the
    reference's own forward pass, which sums over the passes by itself."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, VOCAB, size=(4, SEQ)).astype(np.int32)
    labels = rng.integers(0, VOCAB, size=(4, SEQ)).astype(np.int32)
    got = model.executor.grad_fn()(
        model.params, {"tokens": jnp.asarray(tokens), "label": jnp.asarray(labels)}
    )

    def loss(weights):
        total = 0.0
        for row, want in zip(tokens, labels):
            logits, _ = reference.forward(weights, jnp.asarray(row), EPS, THETA, LOOPS)
            logp = jax.nn.log_softmax(logits)
            total += -jnp.mean(jnp.take_along_axis(logp, want[:, None], 1))
        return total / len(tokens)

    want = jax.grad(loss)(_weights(model))
    assert sorted(got) == sorted(model.params)
    for ws, refs in zip(_weights(model, got), want):
        for g, r in zip(ws, refs):
            scale = max(float(jnp.max(jnp.abs(r))), 1e-8)
            assert float(jnp.max(jnp.abs(g - r))) / scale < 1e-4


def case_one_sgd_step_moves_a_shared_weight_once(model):
    fresh = _model(lr=0.5)
    rng = np.random.default_rng(4)
    batch = {
        "tokens": jnp.asarray(rng.integers(1, VOCAB, size=(4, SEQ)), jnp.int32),
        "label": jnp.asarray(rng.integers(0, VOCAB, size=(4, SEQ)), jnp.int32),
    }
    grads = fresh.executor.grad_fn()(fresh.params, batch)
    before = jax.tree_util.tree_map(np.array, fresh.params)
    params, _, _, _ = fresh.executor.train_step()(
        fresh.params, fresh.opt_state, batch, jax.random.PRNGKey(0)
    )
    assert sorted(params) == sorted(before)
    for guid in before:
        for new, old, g in zip(params[guid], before[guid], grads[guid]):
            np.testing.assert_allclose(new, old - 0.5 * np.asarray(g), rtol=1e-4, atol=1e-6)


def case_rewrite_passes_keep_the_gates_and_the_tie(model):
    """The default substitution pass and the fusion pass: five sinks stay
    (the head and every pass's gate), every borrower stays a borrower, and
    the served logits are the reference's."""
    rewritten = _model(perform_fusion=True, enable_substitution=True)
    nodes = rewritten.graph.nodes.values()
    assert sorted(n.name for n in nodes if n.name.endswith(".exit")) == [
        f"p{p}.exit" for p in range(1, LOOPS + 1)
    ]
    assert len(rewritten.graph.sinks()) == LOOPS + 1
    assert sum(n.op_type == OperatorType.MULTIHEAD_ATTENTION for n in nodes) == LOOPS * LAYERS
    assert sum(int(w.size) for ws in rewritten.params.values() for w in ws) == PARAMETERS
    case_prefill_decode(rewritten)


def case_checkpoint_restores_into_the_served_model(model, tmp_path_factory=None):
    import tempfile

    fresh = _model()
    with tempfile.TemporaryDirectory() as directory:
        fresh.save_checkpoint(directory, step=3)
        kept = _weights(fresh)
        for guid in fresh.params:
            fresh.params[guid] = [0 * w for w in fresh.params[guid]]
        assert fresh.restore_checkpoint(directory) == 3
    assert sorted(fresh.params) == sorted(model.params)
    for ws, refs in zip(_weights(fresh), kept):
        for w, r in zip(ws, refs):
            np.testing.assert_array_equal(w, r)
    case_prefill_decode(fresh)


CASES = {
    name[len("case_"):]: fn for name, fn in sorted(globals().items())
    if name.startswith("case_")
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_ouro_against_reference(ouro, case):
    CASES[case](ouro)


@pytest.mark.parametrize("control", ["bfloat16", "one_cache"])
def test_the_reference_controls_read_far_from_the_reference(ouro, control):
    """What the benchmark's tolerance is set between: the reference in
    bfloat16, and a server that kept one cache a layer for all passes."""
    seq = _prompt(40, salt=2)
    want, _ = _want(ouro, seq)
    kw = {"dtype": jnp.bfloat16} if control == "bfloat16" else {"one_cache": True}
    got, _ = reference.run(_weights(ouro), seq, SEQ, EPS, THETA, LOOPS, **kw)
    assert _gap(got, want) > 100 * TOL
    if control == "one_cache":  # the first position has no earlier one
        assert _gap(got[:1], want[:1]) < TOL
