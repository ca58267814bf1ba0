"""Sanity-check the remat'd chunk result: loss parity with the plain
chunk path on the SAME final loss after N steps, plus longer-window
timing (n1=10, n2=40) to cross-check the suspicious 15.85 ms bs8 step."""

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
from scripts.ab_attn_remat import chunked_remat


def build(bs, remat, mono_mb):
    saved = attn_mod._chunked_dense_attention
    saved_mono = attn_mod._DENSE_MONO_SCORE_BYTES
    attn_mod._DENSE_MONO_SCORE_BYTES = mono_mb << 20
    if remat:
        attn_mod._chunked_dense_attention = chunked_remat
    try:
        cfg = FFConfig(batch_size=bs, learning_rate=0.01)
        cfg.allow_mixed_precision = True
        model, _ = build_transformer(
            cfg, batch_size=bs, seq_len=512, hidden=1024,
            num_heads=16, num_layers=12,
        )
        batch = model.executor.shard_batch(synthetic_batch(bs, 512, 1024))
        step_fn = model.executor.train_step_fn()
        key = jax.random.PRNGKey(0)

        def chain(n):
            @jax.jit
            def run(p, o):
                def body(c, _):
                    cp, co = c
                    p2, o2, loss, _ = step_fn(cp, co, batch, key)
                    return (p2, o2), loss

                _, losses = lax.scan(body, (p, o), None, length=n)
                return losses

            # COMPILE while the monkeypatch is live: tracing reads the
            # patched module attributes, and this function's original
            # version compiled lazily AFTER the finally restored them —
            # silently measuring the unpatched lowering twice (the bug
            # that hid the bs8 chunking win in round 3)
            run.lower(model.params, model.opt_state).compile()
            return run

        runners = {n: chain(n) for n in (10, 40)}
        return model, runners
    finally:
        attn_mod._chunked_dense_attention = saved
        attn_mod._DENSE_MONO_SCORE_BYTES = saved_mono


def main():
    bs = 8
    out = {}
    for name, remat in (("plain", False), ("remat", True)):
        model, runners = build(bs, remat, 64)
        r10, r40 = runners[10], runners[40]
        l10 = np.asarray(r10(model.params, model.opt_state))
        l40 = np.asarray(r40(model.params, model.opt_state))
        # min each window separately, then difference (a spike in the
        # short chain otherwise fakes a speedup)
        b1 = b2 = float("inf")
        for rep in range(4):
            if rep:
                time.sleep(2.0)
            t0 = time.perf_counter()
            _ = np.asarray(r10(model.params, model.opt_state))
            t1 = time.perf_counter()
            _ = np.asarray(r40(model.params, model.opt_state))
            t2 = time.perf_counter()
            b1 = min(b1, t1 - t0)
            b2 = min(b2, t2 - t1)
        best = (b2 - b1) / 30
        out[name] = {
            "losses10": [round(float(x), 6) for x in l10[[0, 4, 9]]],
            "loss40_last": round(float(l40[-1]), 6),
            "step_ms": round(best * 1e3, 2),
        }
        print(json.dumps({name: out[name]}), flush=True)
    d = max(
        abs(a - b)
        for a, b in zip(out["plain"]["losses10"], out["remat"]["losses10"])
    )
    print(json.dumps({"max_loss_diff": d}))


if __name__ == "__main__":
    main()
