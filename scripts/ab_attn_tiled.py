"""Interleaved A/B in the FULL flagship train step (12 x 1024, 16 heads,
seq 512, mixed precision): the chunked+remat dense attention core against
the hand-tiled kernel's whole-sequence form.

Usage: ab_attn_tiled.py [batch]     (default 64, the training cells')

Both sides are what `mha_core_plan` answers: `tiled` as the tree stands
on a TPU, `chunked` with the whole-sequence form refused
(`flash_kernel.supports_whole` answering False while that side is built:
the parent's choice). Both variants compile INSIDE their scope (jit
compiles lazily; a variant compiled after `finally` restores the patch
silently measures the other lowering — the round-3 trap,
docs/perf_notes.md). Times are the difference of the minima of a 5-step
and a 20-step scan over 6 interleaved repetitions; the losses of both
sides after 20 steps are printed beside them. Exits 1 off a TPU.
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np
import jax
from jax import lax

from examples.transformer import build_transformer, synthetic_batch
from flexflow_tpu import FFConfig
from flexflow_tpu.ops.pallas import flash_kernel

N1, N2 = 5, 20


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


def build(bs, whole):
    saved = flash_kernel.supports_whole
    if not whole:
        flash_kernel.supports_whole = lambda *a, **k: False
    try:
        cfg = FFConfig(batch_size=bs, learning_rate=0.01)
        cfg.allow_mixed_precision = True
        model, _ = build_transformer(
            cfg, batch_size=bs, seq_len=512, hidden=1024,
            num_heads=16, num_layers=12,
        )
        ex = model.executor
        (plan,) = set(ex.attention_plans())
        batch = ex.shard_batch(synthetic_batch(bs, 512, 1024))
        r = {n: make_runner(model, batch, n) for n in (N1, N2)}
        losses = {n: r[n]() for n in (N1, N2)}  # COMPILE inside the scope
        return r, plan, losses[N2]
    finally:
        flash_kernel.supports_whole = saved


def main():
    bs = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": jax.device_count(),
    }
    if dev.platform != "tpu":
        print(json.dumps({"error": "no chip: a time is a chip's", **device}))
        sys.exit(1)
    runners, plans, losses = {}, {}, {}
    for name, whole in (("chunked", False), ("tiled", True)):
        runners[name], plan, losses[name] = build(bs, whole)
        plans[name] = plan._asdict()
        assert plan.core == name, (name, plan)
    b1 = {name: float("inf") for name in runners}
    b2 = dict(b1)
    for rep in range(6):
        if rep:
            time.sleep(2.0)
        for name, r in runners.items():
            t0 = time.perf_counter(); r[N1]()
            t1 = time.perf_counter(); r[N2]()
            t2 = time.perf_counter()
            b1[name] = min(b1[name], t1 - t0)
            b2[name] = min(b2[name], t2 - t1)
    print(
        json.dumps(
            {
                **device,
                "bs": bs,
                "plans": plans,
                "step_ms": {
                    n: round((b2[n] - b1[n]) / (N2 - N1) * 1e3, 3) for n in b1
                },
                "loss_after_20": losses,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
