"""Interleaved A/B: round-3 chunked+remat dense attention vs the
hand-tiled Pallas flash kernel, in the FULL flagship train step.

Usage: ab_attn_tiled.py [bs]     (default 8 — the reference headline config)

Both variants compile INSIDE their patch scope (jit compiles lazily; a
variant compiled after `finally` restores the patch silently measures the
other lowering — the round-3 trap, docs/perf_notes.md).
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
from flexflow_tpu.ops import attention as attn_mod


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


def build(bs, flash_bytes):
    saved = attn_mod._FLASH_SCORE_BYTES
    attn_mod._FLASH_SCORE_BYTES = flash_bytes
    try:
        cfg = FFConfig(batch_size=bs, learning_rate=0.01)
        cfg.allow_mixed_precision = True
        model, _ = build_transformer(
            cfg, batch_size=bs, seq_len=512, hidden=1024,
            num_heads=16, num_layers=12,
        )
        batch = model.executor.shard_batch(synthetic_batch(bs, 512, 1024))
        n1, n2 = 5, 20
        r = {n: make_runner(model, batch, n) for n in (n1, n2)}
        for n in (n1, n2):
            r[n]()  # COMPILE inside the patch scope
        return r, (n1, n2)
    finally:
        attn_mod._FLASH_SCORE_BYTES = saved


def main():
    bs = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    variants = [
        ("chunked", attn_mod._FLASH_SCORE_BYTES),  # round-3 default path
        ("tiled", 1),  # auto-flash always on -> hand-tiled kernel
    ]
    runners = {}
    for name, fb in variants:
        runners[name], (n1, n2) = build(bs, fb)
    b1 = {name: float("inf") for name, _ in variants}
    b2 = dict(b1)
    for rep in range(6):
        if rep:
            time.sleep(2.0)
        for name, _ in variants:
            r = runners[name]
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
                    for n in b1
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
