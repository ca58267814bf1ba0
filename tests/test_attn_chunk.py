"""Batch-chunked dense attention (ops/attention.py): numerics vs the
monolithic kernel, chunk-size selection, and gradient equality of the
remat'd scan body."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import attention as A


def _qkv(bs=4, s=64, h=4, d=16, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (bs, s, h, d), dtype),
        jax.random.normal(kk, (bs, s, h, d), dtype),
        jax.random.normal(kv, (bs, s, h, d), dtype),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_matches_monolithic_fwd_and_grad(causal):
    q, k, v = _qkv()
    ref = A.scaled_dot_product_attention(q, k, v, causal=causal)
    out = A._chunked_dense_attention(q, k, v, causal, chunk=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)

    ct = jax.random.normal(jax.random.PRNGKey(7), ref.shape, ref.dtype)

    def loss(fn):
        def f(q, k, v):
            return (fn(q, k, v) * ct).sum()

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_ref = loss(lambda q, k, v: A.scaled_dot_product_attention(q, k, v, causal=causal))
    g_chk = loss(lambda q, k, v: A._chunked_dense_attention(q, k, v, causal, 2))
    for a, b in zip(g_ref, g_chk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_chunk_selection_thresholds():
    h, s = 16, 512
    # flagship bs8: 134 MB score block — past the 96 MB mono cap, chunks
    # to the measured-best 67 MB tile (full step 16.4 vs 23.8 ms on v5e)
    assert A._dense_batch_chunk(8, h, s, s) == 4
    # small models stay monolithic below the cap
    assert A._dense_batch_chunk(4, h, s, s) == 4
    # bs16: 268 MB — chunks to the largest divisor fitting 80 MB (= 4)
    assert A._dense_batch_chunk(16, h, s, s) == 4
    assert A._dense_batch_chunk(32, h, s, s) == 4
    # tiny shapes never chunk
    assert A._dense_batch_chunk(4, 4, 64, 64) == 4
    # odd batch: largest DIVISOR that fits
    assert A._dense_batch_chunk(24, h, s, s) == 4
    assert A._dense_batch_chunk(18, h, s, s) == 3


def test_mha_op_lowers_chunked_under_big_batch():
    """End-to-end through the op registry: a model big enough to cross the
    mono cap still trains and matches a monkey-forced monolithic run."""
    from flexflow_tpu import ActiMode, FFConfig, FFModel, LossType, SGDOptimizer

    def build():
        m = FFModel(FFConfig(batch_size=4))
        x = m.create_tensor([4, 32, 32], name="x")
        t = m.multihead_attention(x, x, x, 32, 4)
        m.dense(t, 1, use_bias=False)
        m.compile(
            optimizer=SGDOptimizer(lr=0.01),
            loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
            metrics=[],
        )
        return m

    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 32, 32)).astype(np.float32)
    y = rng.normal(size=(8, 32, 1)).astype(np.float32)

    saved_mono, saved_chunk = A._DENSE_MONO_SCORE_BYTES, A._DENSE_CHUNK_SCORE_BYTES
    try:
        A._DENSE_MONO_SCORE_BYTES, A._DENSE_CHUNK_SCORE_BYTES = 1, 1 << 20
        m_chunk = build()
        h_chunk = m_chunk.fit(x, y, epochs=2, verbose=False)
    finally:
        A._DENSE_MONO_SCORE_BYTES, A._DENSE_CHUNK_SCORE_BYTES = saved_mono, saved_chunk
    m_mono = build()
    h_mono = m_mono.fit(x, y, epochs=2, verbose=False)
    np.testing.assert_allclose(
        [h["loss_sum"] for h in h_chunk],
        [h["loss_sum"] for h in h_mono],
        rtol=1e-5,
    )


def test_over_cap_band_prefers_memory_safe_chunks():
    """Long-seq/small-batch, below the flash threshold: when even a
    single sample's score block exceeds the chunk cap, selection keeps
    single-sample remat'd chunks — 10-60% slower than one-shot dense in
    isolation, but storing NO per-layer probabilities (a deep model
    would otherwise OOM; _dense_batch_chunk docstring)."""
    h = 16
    # seq 2048, batch 4 (268 MB/sample) and seq 4096, batch 2 (1 GB)
    assert A._dense_batch_chunk(4, h, 2048, 2048) == 1
    assert A._dense_batch_chunk(2, h, 4096, 4096) == 1
    # seq 1024, batch 8: 67 MB single-sample chunks fit -> scan
    # (measured 3.7x FASTER than monolithic as well)
    assert A._dense_batch_chunk(8, h, 1024, 1024) == 1


# -- on a mesh: the chunked core on each device's LOCAL batch (PR 36) -------


@pytest.fixture
def dense_caps():
    """`set_dense_caps`, with the caps this process had put back after."""
    saved = A._DENSE_MONO_SCORE_BYTES, A._DENSE_CHUNK_SCORE_BYTES
    yield A.set_dense_caps
    A._DENSE_MONO_SCORE_BYTES, A._DENSE_CHUNK_SCORE_BYTES = saved


_SEQ, _HID, _HEADS, _LAYERS = 128, 32, 4, 2


def _mesh_model(batch, mesh):
    """Two attention blocks and a dense head, float32. `mesh`: "dp4" is
    the benchmark's x4 strategy (one `data` axis of 4); "dp2_tp2" a
    searched one that shards batch and heads together; "one" a single
    device; "dp2_idle2" a 2 x 2 mesh whose second axis shards nothing of
    attention (a strategy that keeps it for other operators)."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.parallel.strategy import (
        Strategy,
        annotate_input_batch,
        data_parallel_strategy,
        site_strategy,
    )
    from flexflow_tpu.runtime.executor import MeshConfig
    from flexflow_tpu.search.rewrites import AttentionSite, find_tp_sites

    m = FFModel(FFConfig(batch_size=batch, learning_rate=0.05))
    t = m.create_tensor([batch, _SEQ, _HID], name="x")
    for _ in range(_LAYERS):
        t = m.add(t, m.multihead_attention(t, t, t, _HID, _HEADS))
    m.dense(t, 1, use_bias=False)
    if mesh == "dp2_tp2":
        sites = [
            s for s in find_tp_sites(m.graph) if isinstance(s, AttentionSite)
        ]
        assert len(sites) == _LAYERS
        strategy = site_strategy(m.graph, 4, 2, sites)
    elif mesh == "dp2_idle2":
        strategy = Strategy(
            MeshConfig(("data", "model"), (2, 2)),
            lambda g: annotate_input_batch(g, 2),
        )
    else:
        strategy = data_parallel_strategy(1 if mesh == "one" else 4, m.graph)
    m.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[],
        strategy=strategy,
        devices=jax.devices()[: strategy.mesh_config.num_devices],
    )
    return m


def _attention_plans(m):
    from flexflow_tpu.core.types import OperatorType

    ex = m.executor
    return [
        A.mha_core_plan(node.params, ex.node_ctx(node))
        for node in (ex.graph.nodes[g] for g in ex.topo)
        if node.op_type == OperatorType.MULTIHEAD_ATTENTION
    ]


def _one_step(m, data):
    """Loss, outputs, every weight's gradient and every weight after one
    train step from the model's initial state, on the host."""
    ex = m.executor
    placed = ex.shard_batch(data)
    out = ex.forward_fn()(m.params, placed)
    grads = ex.grad_fn()(m.params, placed)
    state = jax.tree_util.tree_map(jnp.copy, (m.params, m.opt_state))
    new_params, _, loss, _ = ex.train_step()(
        *state, placed, jax.random.PRNGKey(0)
    )

    def host(tree):
        return [np.asarray(w) for g in sorted(tree) for w in tree[g]]

    return float(loss), np.asarray(out), host(grads), host(new_params)


def _batch(batch):
    rng = np.random.default_rng(36)
    return {
        "x": rng.normal(size=(batch, _SEQ, _HID)).astype(np.float32),
        "label": rng.normal(size=(batch, _SEQ, 1)).astype(np.float32),
    }


# a sequence's float32 score block is heads x 128 x 128 x 4 bytes: 256 KiB
# with 4 heads, 128 KiB with the 2 a head-sharded device holds. Caps of
# 1 MB take chunks of 4 and of 8 sequences out of a local batch of 8 and 16.
@pytest.mark.parametrize(
    "mesh,batch,plan",
    [
        ("dp4", 32, A.CorePlan("chunked", 4, 8, True)),
        ("dp2_tp2", 32, A.CorePlan("chunked", 8, 16, True)),
        ("dp2_idle2", 16, A.CorePlan("chunked", 4, 8, True)),
    ],
)
def test_mesh_chunks_local_batch_and_matches_one_shot(
    dense_caps, mesh, batch, plan
):
    data = _batch(batch)
    dense_caps(1, 1)
    m = _mesh_model(batch, mesh)
    assert _attention_plans(m) == [plan] * _LAYERS
    chunked = _one_step(m, data)
    dense_caps(1 << 20, 1 << 20)  # the chunking forced off
    m = _mesh_model(batch, mesh)
    assert [p.core for p in _attention_plans(m)] == ["one_shot"] * _LAYERS
    one_shot = _one_step(m, data)
    assert abs(chunked[0] - one_shot[0]) <= 1e-5 * abs(one_shot[0])
    np.testing.assert_allclose(chunked[1], one_shot[1], rtol=1e-5, atol=1e-5)
    assert len(chunked[2]) == len(one_shot[2]) > 8 * _LAYERS
    for got, want in zip(chunked[2] + chunked[3], one_shot[2] + one_shot[3]):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _compiled_step(m, data):
    ex = m.executor
    return ex.train_step().lower(
        m.params, m.opt_state, ex.shard_batch(data), jax.random.PRNGKey(0)
    ).compile().as_text()


@pytest.mark.parametrize("mesh", ["dp4", "dp2_tp2", "dp2_idle2"])
def test_mesh_step_holds_the_scan_and_no_resharding(dense_caps, mesh):
    """The compiled mesh step: each attention node's scan is there,
    forward and backward, and the per-device wrapper costs no collective:
    what crosses chips is what the one-shot lowering sends too, the
    all-reduce of the gradients (and, with the heads sharded, of the
    output projection's partial sums). On "dp2_idle2" that holds because
    the wrapper keeps its varying-axes checker there: without it the
    backward psums q's, k's and v's cotangents over the idle axis."""
    import re

    def collectives(text):
        return sorted(
            re.findall(
                r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
                r"reduce-scatter)(?:-start)?\(",
                text,
            )
        )

    dense_caps(1, 1)
    chunked = _compiled_step(_mesh_model(32, mesh), _batch(32))
    dense_caps(1 << 20, 1 << 20)
    one_shot = _compiled_step(_mesh_model(32, mesh), _batch(32))
    assert len(re.findall(r"\bwhile\(", chunked)) >= 2 * _LAYERS
    assert not re.search(r"\bwhile\(", one_shot)
    assert set(collectives(chunked)) == {"all-reduce"}
    assert collectives(chunked) == collectives(one_shot)


def _ctx(batch, b_deg=1, s_deg=1, h_deg=1, embed=1024, seq=512, **kw):
    """The ctx `forward_values` hands a self-attention node whose input
    [batch, seq, embed] is partitioned b_deg x s_deg, replicated h_deg
    times for the heads, on a mesh with one axis for each."""
    from jax.sharding import Mesh

    from flexflow_tpu.core.parallel_tensor import (
        ParallelDim,
        ParallelTensorShape,
    )
    from flexflow_tpu.core.types import DataType
    from flexflow_tpu.ops.registry import LowerCtx

    degs = (b_deg, s_deg, h_deg)
    n = b_deg * s_deg * h_deg
    mesh = (
        Mesh(np.array(jax.devices()[:n]).reshape(degs), ("data", "seq", "model"))
        if n > 1
        else None
    )
    dims = [ParallelDim(h_deg, h_deg, 2, True)] if h_deg > 1 else []
    dims += [
        ParallelDim(batch, b_deg, 0 if b_deg > 1 else -1),
        ParallelDim(seq, s_deg, 1 if s_deg > 1 else -1),
        ParallelDim(embed),
    ]
    x = ParallelTensorShape(tuple(dims), DataType.FLOAT)
    return LowerCtx(
        mesh=mesh, axis_names=("data", "seq", "model"), in_shapes=[x, x, x],
        **kw,
    )


_FLAGSHIP = {"embed_dim": 1024, "num_heads": 16}


@pytest.fixture
def as_tpu(monkeypatch):
    """The repo's own backend probes answer as they do on the chip (JAX
    itself never calls `jax.default_backend` through the module)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# (params, ctx, the plan off a TPU, the plan on one)
_PLAN_ROWS = [
    # `train_ff_b256_x4`: 64 local sequences, 1 GiB of scores a chip; on
    # the chip the kernel's whole-sequence form, per device
    (_FLAGSHIP, dict(batch=256, b_deg=4), ("chunked", 4, 64, True),
     ("tiled", 64, 64, True)),
    # `train_ff_b64`: the one-chip program, no wrapper
    (_FLAGSHIP, dict(batch=64), ("chunked", 4, 64, False),
     ("tiled", 64, 64, False)),
    # the cells as they run, bf16 matmul operands
    (_FLAGSHIP, dict(batch=64, bf16_matmul=True), ("chunked", 4, 64, False),
     ("tiled", 64, 64, False)),
    # batch and heads sharded together: 8 local heads, chunks of 8
    (_FLAGSHIP, dict(batch=256, b_deg=4, h_deg=2), ("chunked", 8, 64, True),
     ("tiled", 64, 64, True)),
    # heads alone: the global batch is whole on a device, GSPMD
    # partitions the scan's body; the kernel runs per device
    (_FLAGSHIP, dict(batch=64, h_deg=2), ("chunked", 8, 64, False),
     ("tiled", 64, 64, True)),
    # the sequence sharded and no seq-parallel path
    ({**_FLAGSHIP, "seq_parallel": "none"},
     dict(batch=256, b_deg=2, s_deg=2), ("one_shot", 128, 128, False), None),
    # attention-prob dropout
    ({**_FLAGSHIP, "dropout": 0.1},
     dict(batch=256, b_deg=4, train=True, rng=0),
     ("one_shot", 64, 64, False), None),
    # asked for the dense core by name
    ({**_FLAGSHIP, "use_flash": False}, dict(batch=64),
     ("chunked", 4, 64, False), None),
    # a local block under the mono cap (64 MB): every toy mesh test
    (_FLAGSHIP, dict(batch=16, b_deg=4), ("one_shot", 4, 4, False),
     ("tiled", 4, 4, True)),
    # the sequence sharded on q and k alike
    (_FLAGSHIP, dict(batch=8, b_deg=2, s_deg=2), ("ring", 4, 4, True), None),
    # a sequence whose score block is over the kernel's VMEM reckoning
    # and under the chunk cap: the scan, on a TPU too
    (_FLAGSHIP, dict(batch=8, seq=1024, bf16_matmul=False),
     ("chunked", 1, 8, False), "whole"),
    (_FLAGSHIP, dict(batch=2, seq=2048), ("chunked", 1, 2, False),
     ("tiled", 2, 2, False)),
]


@pytest.mark.parametrize("params,ctx,plan,_", _PLAN_ROWS)
def test_core_plan(params, ctx, plan, _):
    """The backend as it is here: the answers before PR 59, every row."""
    assert A.mha_core_plan(params, _ctx(**ctx)) == A.CorePlan(*plan)


@pytest.mark.parametrize("params,ctx,off_tpu,on_tpu", _PLAN_ROWS)
def test_core_plan_on_a_tpu(as_tpu, params, ctx, off_tpu, on_tpu):
    """The same rows with the backend seen as a TPU: `tiled` where the
    hand-tiled kernel takes the per-device shape (the whole-sequence form
    at the cells' 512, the grid form past the chunk cap), the rest as
    off a TPU."""
    if on_tpu == "whole":  # 1,024: taken or not by what the chip said
        from flexflow_tpu.ops.pallas.flash_kernel import supports_whole

        on_tpu = ("tiled", 8, 8, False) if supports_whole(
            1024, 1024, 16, 64, 4
        ) else None
    want = A.CorePlan(*(on_tpu or off_tpu))
    assert A.mha_core_plan(params, _ctx(**ctx)) == want


def _stack(layers, batch=2, seq=128, hidden=128, heads=2):
    """`layers` attention blocks of lane-tile heads (2 of 64) and a dense
    head: the smallest model whose nodes take the kernel's whole form."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer

    m = FFModel(FFConfig(batch_size=batch, learning_rate=0.05))
    t = m.create_tensor([batch, seq, hidden], name="x")
    for _ in range(layers):
        t = m.add(t, m.multihead_attention(t, t, t, hidden, heads))
    m.dense(t, 1, use_bias=False)
    m.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[],
        devices=jax.devices()[:1],
    )
    return m


def test_twelve_nodes_hold_one_kernel_body_a_pass(as_tpu):
    """The step lowered for a TPU: twelve attention nodes call ONE
    forward and ONE backward Mosaic body (the calls sit behind an inner
    jit, so the kernel is traced and lowered once a program), and no
    scan is left."""
    m = _stack(12)
    ex = m.executor
    assert [p.core for p in _attention_plans(m)] == ["tiled"] * 12
    data = {
        "x": jax.ShapeDtypeStruct((2, 128, 128), jnp.float32),
        "label": jax.ShapeDtypeStruct((2, 128, 1), jnp.float32),
    }
    text = (
        jax.jit(ex.train_step_fn())
        .trace(m.params, m.opt_state, data, jax.random.PRNGKey(0))
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    assert text.count("tpu_custom_call") == 2
    assert text.count("call @_whole_fwd") == 12
    assert text.count("call @_whole_bwd") == 12
    assert "stablehlo.while" not in text


def test_the_step_publishes_the_cores_its_nodes_took(dense_caps):
    """`train_attention_core_nodes`: set when the step is built, mirrored
    into the telemetry by `fit()`. On a CPU every node of a step over
    the mono cap reads `chunked`."""
    from flexflow_tpu.telemetry import Telemetry

    dense_caps(0, 0)
    m = _stack(3, batch=4)
    assert m.executor.attention_cores == {}
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 128, 128)).astype(np.float32)
    y = rng.normal(size=(8, 128, 1)).astype(np.float32)
    tele = Telemetry()
    m.fit(x, y, epochs=1, verbose=False, telemetry=tele)
    assert m.executor.attention_cores == {"chunked": 3}
    row = tele.registry.sample()
    cores = {k: v for k, v in row.items() if "train_attention_core_nodes" in k}
    assert list(cores.values()) == [3] and "chunked" in next(iter(cores))


def test_one_device_step_scans_without_shard_map(dense_caps):
    """`b_deg == 1` is left as it was: the scan over the global batch,
    no wrapper round it (on the chip the compiled `train_ff_b64` step is
    the parent's text for text: CHANGES.md, PR 36)."""
    dense_caps(1, 1)
    m = _mesh_model(8, "one")
    assert _attention_plans(m) == [A.CorePlan("chunked", 4, 8, False)] * _LAYERS
    ex = m.executor
    jaxpr = str(
        jax.make_jaxpr(ex.train_step_fn())(
            m.params, m.opt_state, _batch(8), jax.random.PRNGKey(0)
        )
    )
    assert jaxpr.count("scan[") >= 2 * _LAYERS
    assert "shard_map" not in jaxpr
    # and the mesh's step does hold one a node and pass
    m = _mesh_model(32, "dp4")
    jaxpr = str(
        jax.make_jaxpr(m.executor.train_step_fn())(
            m.params, m.opt_state, _batch(32), jax.random.PRNGKey(0)
        )
    )
    assert jaxpr.count("shard_map[") >= 2 * _LAYERS
