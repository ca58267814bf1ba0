"""Interleaved A/B on the full ResNet-50 train step: BN statistics
variants (the CNN family's open MFU hole, VERDICT r3 #1).

Variants (each compiled INSIDE its patch scope — the round-3 monkeypatch
trap):
  two_pass — the pre-round-4 lowering: mean, then E[(x-mean)^2], then
             normalize (3 activation passes + the conv write).
  one_pass — E[x^2] - E[x]^2: both sums accumulate in ONE pass over the
             activation; adopted as core_ops._lower_batchnorm.

The protocol-grade magnitude of the win is the ONE number recorded in
docs/perf_notes.md (the first run of this script read
11.71 -> 3.79 ms under a biased estimator — a contention spike in the
A window faked a 3.1x — and the corrected interleaved A/B measured
5.41 -> 4.36 ms, ~19%; run this script for the current chip's number
rather than quoting any of those).

Usage: ab_resnet_bn.py [bs] [variantA] [variantB]
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.ops import core_ops


def _lower_bn_two_pass(params):
    """The pre-round-4 lowering, kept for regression A/Bs."""
    eps = params.get("eps", 1e-5)
    act = params.get("activation", core_ops.ActiMode.NONE)

    def fn(ins, ws, ctx):
        (x,) = ins
        gamma, beta = ws
        axes = tuple(range(x.ndim - 1))
        xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps) * gamma + beta
        return [core_ops._apply_activation(y.astype(x.dtype), act)]

    return fn


VARIANTS = {
    "two_pass": _lower_bn_two_pass,
    "one_pass": core_ops._lower_batchnorm,
}


def make_runner(model, batch, n):
    step_fn = model.executor.train_step_fn()
    key = jax.random.PRNGKey(0)

    @jax.jit
    def run(p, o):
        def body(c, _):
            cp, co = c
            p2, o2, loss, _ = step_fn(cp, co, batch, key)
            return (p2, o2), loss

        _, losses = lax.scan(body, (p, o), None, length=n)
        return losses[-1]

    return lambda: float(np.asarray(run(model.params, model.opt_state)))


def build(bs, variant, mixed=True):
    from flexflow_tpu.ops.registry import register_op
    from flexflow_tpu.core.types import OperatorType

    saved = core_ops._lower_batchnorm
    register_op(
        OperatorType.BATCHNORM, core_ops._infer_batchnorm, VARIANTS[variant]
    )
    try:
        # bench_configs-style build: bf16 matmul mode like the headline
        # ResNet numbers (BENCH_CONFIGS.json), parameterized batch
        from flexflow_tpu import (
            FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
        )
        from flexflow_tpu.models import build_resnet50 as br

        cfg = FFConfig(batch_size=bs)
        cfg.allow_mixed_precision = bool(mixed)
        model = FFModel(cfg)
        x = model.create_tensor([bs, 224, 224, 3], name="x")
        br(model, x, num_classes=1000)
        model.compile(
            optimizer=SGDOptimizer(lr=0.01),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[MetricsType.ACCURACY],
        )
        rng = np.random.RandomState(0)
        data = {
            "x": rng.randn(bs, 224, 224, 3).astype(np.float32),
            "label": rng.randint(0, 1000, size=(bs,)).astype(np.int32),
        }
        batch = model.executor.shard_batch(data)
        n1, n2 = 10, 40
        r = {n: make_runner(model, batch, n) for n in (n1, n2)}
        for n in (n1, n2):
            r[n]()  # COMPILE inside the patch scope
        return r, (n1, n2)
    finally:
        register_op(
            OperatorType.BATCHNORM, core_ops._infer_batchnorm, saved
        )


def main():
    bs = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    names = sys.argv[2:] or ["two_pass", "one_pass"]
    runners = {}
    for name in names:
        runners[name], (n1, n2) = build(bs, name)
    # the chip ramps its clock over the first ~0.25 s of a burst
    # (docs/perf_notes.md): discard a warm-up burst before each measurement and
    # ALTERNATE the variant order across reps so any residual ramp bias
    # cancels in the mins instead of crediting whichever ran second (the
    # first two runs of this script disagreed for exactly that reason)
    b1 = {n: float("inf") for n in names}
    b2 = dict(b1)
    for rep in range(6):
        if rep:
            time.sleep(2.0)
        order = names if rep % 2 == 0 else list(reversed(names))
        for name in order:
            r = runners[name]
            r[n1]()  # clock warm-up, discarded
            t0 = time.perf_counter(); r[n1]()
            t1 = time.perf_counter(); r[n2]()
            t2 = time.perf_counter()
            b1[name] = min(b1[name], t1 - t0)
            b2[name] = min(b2[name], t2 - t1)
    print(
        json.dumps(
            {
                "bs": bs,
                **{
                    n: round((b2[n] - b1[n]) / (n2 - n1) * 1e3, 2)
                    for n in names
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
