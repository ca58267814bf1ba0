"""Profiling + artifact-export tests (reference: --profiling per-kernel
timing, --taskgraph/--compgraph dumps with costs; SURVEY §5)."""

import numpy as np

from flexflow_tpu import ActiMode, FFConfig, FFModel, LossType, SGDOptimizer


def _model(tmp_path=None, **cfg_kw):
    cfg = FFConfig(batch_size=16)
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    model = FFModel(cfg)
    x = model.create_tensor([16, 32], name="x")
    t = model.dense(x, 32, activation=ActiMode.RELU, name="d0")
    t = model.dense(t, 4, name="head")
    model.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    return model


def test_profile_operators_returns_rows():
    model = _model()
    batch = {"x": np.random.RandomState(0).randn(16, 32).astype(np.float32)}
    rows = model.profile_operators(batch, iters=2, verbose=False)
    names = {n for n, _ in rows}
    assert {"d0", "head"} <= names
    assert all(t >= 0 for _, t in rows)
    # sorted slowest-first
    times = [t for _, t in rows]
    assert times == sorted(times, reverse=True)


def test_compgraph_with_costs_and_taskgraph_export(tmp_path):
    comp = tmp_path / "comp.dot"
    task = tmp_path / "task.dot"
    _model(
        computation_graph_file=str(comp),
        task_graph_file=str(task),
        include_costs_dot_graph=True,
    )
    comp_text = comp.read_text()
    assert "digraph PCG" in comp_text
    assert "cost=" in comp_text  # --include-costs-dot-graph
    task_text = task.read_text()
    assert "digraph TaskGraph" in task_text
    assert ".fwd" in task_text and ".bwd" in task_text and ".sync" in task_text


def test_compat_verbs():
    model = _model()
    model.init_operators()  # pre-compiles the step
    model.begin_trace(111)
    model.zero_gradients()
    model.backward()
    model.update()
    model.end_trace(111)


def test_trace_context_manager(tmp_path):
    from flexflow_tpu.utils import profiling

    model = _model()
    batch = {"x": np.random.RandomState(0).randn(16, 32).astype(np.float32)}
    with profiling.trace(str(tmp_path / "trace")):
        model.forward(batch)


def test_xla_cost_analysis():
    from flexflow_tpu.utils.profiling import xla_cost_analysis

    model = _model()
    rng = np.random.RandomState(0)
    batch = {
        "x": rng.randn(16, 32).astype(np.float32),
        "label": rng.randint(0, 4, (16,)).astype(np.int32),
    }
    cost = xla_cost_analysis(model, batch)
    # backend-dependent accounting; the contract is a non-empty dict with
    # a positive flop count
    assert cost.get("flops", 0) > 0


def test_profile_operators_on_pipelined_executor():
    """Per-op profiling reads trunk weights through get_host_param, so it
    works under pipeline strategies (stacked pipe-sharded storage)."""
    import numpy as np

    from flexflow_tpu import LossType, SGDOptimizer
    from flexflow_tpu.parallel.strategy import pipeline_strategy
    from tests.test_pipeline_sharded import _data, _deep_mlp

    m = _deep_mlp()
    s = pipeline_strategy(m.graph, 1, 4, num_microbatches=4)
    m.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        strategy=s,
    )
    x, y = _data()
    rows = m.profile_operators(
        {"x": x[:16], "label": y[:16]}, verbose=False
    )
    assert rows and all(np.isfinite(t) for _, t in rows)


def test_profile_step_on_the_cpu_backend_says_what_is_missing():
    """`profile_step` runs the real compiled step under the profiler. A
    CPU profile holds no `XLA Ops` line, so there is nothing to fold:
    it says so (or, on a backend that writes one, returns rows whose
    sum is the program's device time), and the model's own parameters
    are the ones it had: the steps ran on copies."""
    import pytest

    from flexflow_tpu.utils import profiling

    model = _model()
    rng = np.random.RandomState(0)
    batch = {
        "x": rng.randn(16, 32).astype(np.float32),
        "label": rng.randint(0, 4, (16,)).astype(np.int32),
    }
    before = [np.asarray(w) for ws in model.params.values() for w in ws]
    try:
        profile = model.profile_step(batch, steps=2, verbose=False)
    except profiling.NoDeviceOps as e:
        assert "no device ops in this trace" in str(e)
    else:
        assert profile.rows and 0.9 <= profile.accounted <= 1.0
    after = [np.asarray(w) for ws in model.params.values() for w in ws]
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    with pytest.raises(RuntimeError, match="compile"):
        profiling.profile_step(FFModel(FFConfig(batch_size=16)), batch)
