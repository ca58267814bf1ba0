"""Example-zoo smoke tests: every script imports cleanly, and the small
ones run end-to-end (the reference's integration testing is exactly
"run the example zoo", SURVEY §4.4)."""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EXAMPLES = [
    "alexnet",
    "full_workflow",
    "bert_proxy",
    "candle_uno",
    "dlrm",
    "inception",
    "keras_cnn_cifar10",
    "longctx_transformer",
    "mlp",
    "moe",
    "mt5_encoder",
    "nmt",
    "resnet",
    "resnext",
    "serve_lm",
    "split_test",
    "split_test_2",
    "torch_mlp_import",
    "transformer",
    "xdl",
]


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_imports(name):
    mod = importlib.import_module(f"examples.{name}")
    assert hasattr(mod, "main")


def _run_main(mod_name, argv):
    old = sys.argv
    sys.argv = [mod_name] + argv
    try:
        importlib.import_module(f"examples.{mod_name}").main()
    finally:
        sys.argv = old


def test_split_test_runs():
    _run_main("split_test", ["-b", "8", "-i", "2", "-e", "1"])


def test_split_test_2_runs():
    # budget 10 mirrors split_test_2.cc:59's graph_optimize(10, ...)
    _run_main("split_test_2", ["-b", "8", "-i", "2", "-e", "1"])


def test_candle_uno_runs():
    _run_main("candle_uno", ["-b", "8", "-i", "2", "-e", "1"])


def test_serve_lm_runs():
    _run_main("serve_lm", ["-b", "4", "--max-seqs", "2", "--max-seq-len", "32"])


def test_nmt_runs_and_learns():
    import examples.nmt as nmt

    _run_main("nmt", ["-b", "16", "-i", "2", "-e", "1"])
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu import AdamOptimizer

    params = nmt.init_params(jax.random.PRNGKey(0))
    opt = AdamOptimizer(alpha=0.01)
    state = opt.init_state(params)

    @jax.jit
    def step(params, state, b):
        loss, grads = jax.value_and_grad(nmt.loss_fn)(params, b)
        params, state = opt.update(params, grads, state)
        return params, state, loss

    # memorize one fixed batch: must crush the uniform-vocab baseline
    # ln(VOCAB) ≈ 5.55 — catches any break in the LSTM recurrence/grads
    rng = np.random.RandomState(0)
    b = {k: jnp.asarray(v) for k, v in nmt.synthetic_batch(rng, 16).items()}
    for _ in range(60):
        params, state, loss = step(params, state, b)
    assert float(loss) < 2.0


def test_full_workflow_runs(capsys):
    """search -> export -> import -> train -> checkpoint -> resume."""
    _run_main("full_workflow", ["-b", "64", "--budget", "10"])
    assert "WORKFLOW OK" in capsys.readouterr().out


def test_longctx_transformer_runs_small():
    """The long-context example at a CPU-suite-sized sequence (the real
    seq-8192 run needs the chip)."""
    _run_main("longctx_transformer", ["--seq", "256", "-b", "2", "-i", "1", "-e", "1"])
