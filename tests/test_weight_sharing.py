"""A PCG node that applies another node's weights (`weights_of=`): the
weight is stored once, its gradient sums over the applications, the
optimizer, checkpoints, `get_tensor` / `set_tensor`, graph rewrites, the
search and the cost model see one weight. Small sizes, exact float32
(conftest pins `highest`), seeded weights, on the CPU mesh."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    RecompileState,
    SGDOptimizer,
)
from flexflow_tpu.core.machine import MachineSpec
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.parallel.strategy import site_strategy
from flexflow_tpu.search.cost_model import CostModel
from flexflow_tpu.search.rewrites import TiedSites, find_tp_sites
from flexflow_tpu.search.simulator import estimate_graph_cost

BATCH, WIDTH, OUT, LOOPS, LR = 8, 16, 4, 3, 0.1
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _looped(loops=LOOPS, devices=1, strategy=None, momentum=0.0, **config):
    """x -> [dense(tanh) -> rms_norm] x loops over ONE dense and ONE gain
    -> head: pass 1 owns, the later passes borrow."""
    cfg = FFConfig(batch_size=BATCH)
    cfg.seed = 11
    for k, v in config.items():
        setattr(cfg, k, v)
    model = FFModel(cfg)
    x = model.create_tensor([BATCH, WIDTH], name="x")
    t, layer, norm = x, None, None
    for p in range(loops):
        t = model.dense(
            t, WIDTH, activation=ActiMode.TANH, name=f"p{p}.layer",
            weights_of=layer,
        )
        layer = layer or t
        t = model.rms_norm(t, name=f"p{p}.norm", weights_of=norm)
        norm = norm or t
    model.dense(t, OUT, name="head")
    model.compile(
        optimizer=SGDOptimizer(lr=LR, momentum=momentum),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[],
        devices=jax.devices()[:devices],
        strategy=strategy(model.graph) if strategy else None,
    )
    return model


def _guid(model, name):
    return next(g for g, n in model.graph.nodes.items() if n.name == name)


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "x": rng.standard_normal((BATCH, WIDTH)).astype(np.float32),
        "label": rng.integers(0, OUT, size=(BATCH,)).astype(np.int32),
    }


def _reference_loss(weights, batch, loops=LOOPS):
    """The same model in plain jax.numpy over (w, b, gain, head w, head b)."""
    w, b, gain, hw, hb = weights
    t = batch["x"]
    for _ in range(loops):
        t = jnp.tanh(t @ w + b)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-5) * gain
    logp = jax.nn.log_softmax(t @ hw + hb)
    return -jnp.mean(jnp.take_along_axis(logp, batch["label"][:, None], 1))


def _flat(model, params=None):
    params = model.params if params is None else params
    layer, norm, head = (
        params[_guid(model, n)] for n in ("p0.layer", "p0.norm", "head")
    )
    return [*layer, *norm, *head]


@pytest.fixture(scope="module")
def looped():
    return _looped()


def test_params_hold_a_shared_weight_once(looped):
    ex = looped.executor
    assert sorted(looped.params) == sorted(
        _guid(looped, n) for n in ("p0.layer", "p0.norm", "head")
    )
    assert set(ex.weight_owner.values()) == {
        _guid(looped, "p0.layer"), _guid(looped, "p0.norm")
    }
    assert len(ex.weight_owner) == 2 * (LOOPS - 1)
    count = sum(int(w.size) for ws in looped.params.values() for w in ws)
    assert count == WIDTH * WIDTH + WIDTH + WIDTH + WIDTH * OUT + OUT
    assert set(looped.opt_state) == {"step"}  # plain SGD: no slot at all


def test_forward_applies_the_owner_weights_in_every_pass(looped):
    batch = _batch()
    got = looped.executor.forward_fn()(looped.params, {"x": batch["x"]})
    w, b, gain, hw, hb = _flat(looped)
    t = batch["x"]
    for _ in range(LOOPS):
        t = jnp.tanh(t @ w + b)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-5) * gain
    np.testing.assert_allclose(got, t @ hw + hb, rtol=1e-5, atol=1e-6)


def test_gradient_is_the_sum_over_the_applications(looped):
    batch = {k: jnp.asarray(v) for k, v in _batch().items()}
    got = looped.executor.grad_fn()(looped.params, batch)
    want = jax.grad(_reference_loss)(_flat(looped), batch)
    assert sorted(got) == sorted(looped.params)
    for g, w in zip(_flat(looped, got), want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7)
    # and it is not one application's: a single pass gives another number
    once = jax.grad(_reference_loss)(_flat(looped), batch, loops=1)
    assert float(jnp.max(jnp.abs(once[0] - want[0]))) > 1e-3


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_one_sgd_step_moves_the_shared_weight_once(momentum):
    model = _looped(momentum=momentum)
    batch = {k: jnp.asarray(v) for k, v in _batch().items()}
    before = [np.array(w) for w in _flat(model)]
    want = jax.grad(_reference_loss)([jnp.asarray(w) for w in before], batch)
    step = model.executor.train_step()
    params, state, _, _ = step(
        model.params, model.opt_state, batch, jax.random.PRNGKey(0)
    )
    for new, old, g in zip(_flat(model, params), before, want):
        np.testing.assert_allclose(new, old - LR * np.asarray(g), rtol=1e-4, atol=1e-6)
    slots = [v for v in state.values() if isinstance(v, dict)]
    assert all(sorted(s) == sorted(params) for s in slots)
    assert len(slots) == (1 if momentum else 0)


def test_checkpoint_saves_one_copy_and_a_restore_keeps_the_tie(looped, tmp_path):
    batch = _batch(1)
    fwd = looped.executor.forward_fn()
    want = np.array(fwd(looped.params, {"x": batch["x"]}))
    looped.save_checkpoint(str(tmp_path), step=1)
    saved = looped.executor.export_host_params(looped.params)
    assert sorted(saved) == sorted(looped.params)
    owner = _guid(looped, "p0.layer")
    kept = looped.get_tensor(owner, 0).copy()
    looped.set_tensor(owner, 0, np.zeros_like(kept))
    assert not np.allclose(fwd(looped.params, {"x": batch["x"]}), want)
    looped.restore_checkpoint(str(tmp_path))
    assert sorted(looped.params) == sorted(saved)
    np.testing.assert_array_equal(fwd(looped.params, {"x": batch["x"]}), want)


def test_get_and_set_tensor_through_a_borrower_reach_the_owner(looped):
    owner, borrower = _guid(looped, "p0.layer"), _guid(looped, "p2.layer")
    kept = looped.get_tensor(owner, 1).copy()
    np.testing.assert_array_equal(looped.get_tensor(borrower, 1), kept)
    looped.set_tensor(borrower, 1, kept + 1.0)
    np.testing.assert_array_equal(looped.get_tensor(owner, 1), kept + 1.0)
    assert borrower not in looped.params
    looped.set_tensor(owner, 1, kept)


@pytest.mark.parametrize("what", ["shape", "kind"])
def test_a_mismatched_weights_of_raises(what):
    model = FFModel(FFConfig(batch_size=BATCH))
    x = model.create_tensor([BATCH, WIDTH], name="x")
    first = model.dense(x, WIDTH, name="first")
    with pytest.raises(ValueError, match="weights_of='first'"):
        if what == "shape":
            model.dense(first, WIDTH // 2, weights_of=first)
        else:
            model.rms_norm(first, weights_of=first)


def test_a_borrower_of_a_borrower_names_the_owner():
    model = FFModel(FFConfig(batch_size=BATCH))
    x = model.create_tensor([BATCH, WIDTH], name="x")
    a = model.dense(x, WIDTH, name="a")
    b = model.dense(a, WIDTH, name="b", weights_of=a)
    c = model.dense(b, WIDTH, name="c", weights_of=b)
    assert model.graph.nodes[c.ref.guid].params["weights_of"] == "a"
    assert set(model.graph.weight_owners().values()) == {a.ref.guid}


def test_an_owner_that_is_gone_raises():
    model = FFModel(FFConfig(batch_size=BATCH))
    x = model.create_tensor([BATCH, WIDTH], name="x")
    a = model.dense(x, WIDTH, name="a")
    model.dense(a, WIDTH, name="b", weights_of=a)
    model.graph.nodes[a.ref.guid].name = "renamed"
    with pytest.raises(ValueError, match="0 nodes"):
        model.graph.weight_owners()


def _tp_strategy(on):
    def make(graph):
        sites = [s for s, take in zip(find_tp_sites(graph), on) if take]
        return site_strategy(graph, 4, 2, sites)

    return make


def test_the_search_takes_an_owner_and_its_borrowers_as_one_site(looped):
    sites = find_tp_sites(looped._prestrategy_graph)
    tied = [s for s in sites if isinstance(s, TiedSites)]
    assert len(tied) == 1 and len(tied[0].members) == LOOPS
    assert {s.kind for s in tied[0].members} == {"single_linear"}
    # a graph that shares nothing gets the list it always got
    plain = FFModel(FFConfig(batch_size=BATCH))
    t = plain.create_tensor([BATCH, WIDTH], name="x")
    for _ in range(3):
        t = plain.dense(t, WIDTH)
    assert not any(isinstance(s, TiedSites) for s in find_tp_sites(plain.graph))


def test_a_strategy_gives_owner_and_borrowers_one_weight_sharding():
    """On the four-virtual-device mesh, (data 2 x model 2) with the tied
    site on: every pass's dense holds the column-sharded weight."""
    model = _looped(devices=4, strategy=_tp_strategy([True, True]))
    graph, ex = model.graph, model.executor
    owner = graph.nodes[_guid(model, "p0.layer")]
    assert owner.weight_shapes[0].total_degree > 1
    for borrower, got in ex.weight_owner.items():
        assert graph.nodes[borrower].weight_shapes == graph.nodes[got].weight_shapes
    w = model.params[owner.guid][0]
    assert w.sharding.shard_shape(w.shape) != w.shape
    # the cost model's weight memory is the stored bytes, a chip's share
    cost = estimate_graph_cost(
        graph, CostModel(MachineSpec(1, 4, "v5e")), ex.mesh_config.axis_sizes
    )
    stored = sum(
        int(np.prod(w.sharding.shard_shape(w.shape))) * w.dtype.itemsize
        for ws in model.params.values() for w in ws
    )
    assert cost.weight_bytes == stored
    # and the sharded model computes what the one-device model computes
    batch = _batch(2)
    want = _looped().executor.forward_fn()
    one = _looped()
    np.testing.assert_allclose(
        ex.forward_fn()(model.params, ex.shard_batch({"x": batch["x"]})),
        want(one.params, {"x": batch["x"]}), rtol=1e-5, atol=1e-6,
    )


def test_a_strategy_that_shards_only_a_borrower_is_refused():
    def only_last(graph):
        tied = next(s for s in find_tp_sites(graph) if isinstance(s, TiedSites))
        return site_strategy(graph, 4, 2, [tied.members[-1]])

    with pytest.raises(ValueError, match="one weight sharding"):
        _looped(devices=4, strategy=only_last)


def test_cost_model_keeps_a_shared_weight_once_and_reads_it_every_pass(looped):
    graph = looped.graph
    cm = CostModel(MachineSpec(1, 1, "v5e"))
    cost = estimate_graph_cost(graph, cm, (1,))
    stored = sum(int(w.nbytes) for ws in looped.params.values() for w in ws)
    assert cost.weight_bytes == stored
    owner, borrower = (
        graph.nodes[_guid(looped, n)] for n in ("p0.layer", "p1.layer")
    )
    ins = [graph.shape_of(r) for r in borrower.inputs]
    lent, borrowed = cm.op_cost(owner, ins), cm.op_cost(borrower, ins)
    weight = sum(cm.piece_bytes(s) for s in owner.weight_shapes)
    assert lent.memory - borrowed.memory == weight
    assert borrowed.forward_time == lent.forward_time  # the read is paid again


def test_a_saved_search_result_lists_every_tied_site(looped, tmp_path):
    import json
    import types

    from flexflow_tpu.search.strategy_io import save_search_result

    graph = looped._prestrategy_graph
    sites = find_tp_sites(graph)
    result = types.SimpleNamespace(
        sites=sites, on=[isinstance(s, TiedSites) for s in sites], dp=2, tp=2,
        cost=types.SimpleNamespace(step_time=0.0),
    )
    path = str(tmp_path / "strategy.json")
    save_search_result(result, graph, path)
    with open(path) as f:
        saved = json.load(f)["sites"]
    assert [s["names"] for s in saved] == [[f"p{p}.layer"] for p in range(LOOPS)]


def test_fusion_leaves_tied_nodes_alone_and_recompile_keeps_the_tie():
    model = _looped(perform_fusion=True)
    names = {n.name for n in model.graph.nodes.values()}
    assert {f"p{p}.layer" for p in range(LOOPS)} <= names
    assert {f"p{p}.norm" for p in range(LOOPS)} <= names
    owner = _guid(model, "p0.layer")
    kept = model.get_tensor(owner, 0).copy()
    assert model.recompile_on_condition(
        RecompileState(lambda m: True, lambda m: None)
    )
    assert len(model.executor.weight_owner) == 2 * (LOOPS - 1)
    np.testing.assert_array_equal(model.get_tensor(_guid(model, "p0.layer"), 0), kept)
    np.testing.assert_array_equal(model.get_tensor(_guid(model, "p2.layer"), 0), kept)


def test_pipelined_execution_refuses_shared_weights_in_words():
    from flexflow_tpu.parallel.strategy import pipeline_strategy

    def pipelined(graph):
        return pipeline_strategy(graph, 1, 2, num_microbatches=2)

    with pytest.raises(ValueError, match="weights_of="):
        _looped(loops=5, devices=2, strategy=pipelined)


def _step_jaxpr_digest(model, batch):
    jaxpr = jax.make_jaxpr(model.executor.train_step_fn())(
        model.params, model.opt_state, batch, jax.random.PRNGKey(0)
    )
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()


def test_a_graph_without_sharing_lowers_to_the_jaxpr_it_always_did():
    """`build_decoder_lm`'s train step, string-equal to the one recorded on
    the commit before nodes could share weights
    (tests/data/decoder_lm_step_jaxpr.sha256: `python
    tests/test_weight_sharing.py` prints the digest of the tree it runs in)."""
    with open(os.path.join(DATA, "decoder_lm_step_jaxpr.sha256")) as f:
        want = f.read().split()[0]
    model, batch = _decoder()
    assert model.executor.weight_owner == {}
    assert _step_jaxpr_digest(model, batch) == want


def _decoder():
    cfg = FFConfig(batch_size=4)
    cfg.seed = 5
    model = FFModel(cfg)
    tok = model.create_tensor([4, 16], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(model, tok, vocab_size=64, hidden=32, num_heads=4,
                     num_layers=2, ff_dim=64)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[],
        devices=jax.devices()[:1],
    )
    batch = {
        "tokens": jnp.zeros((4, 16), jnp.int32),
        "label": jnp.zeros((4, 16), jnp.int32),
    }
    return model, batch


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    print(_step_jaxpr_digest(*_decoder()))
