"""The attention CORE alone at a training cell's per-device shape
(64 sequences of 512, 16 heads of 64, bf16): the chunked+remat scan
(`_chunked_dense_attention`, 4 sequences a pass) against the hand-tiled
kernel's whole-sequence form at `--block-heads` heads a grid step, and
the grid form at its tuned blocks. Forward alone and forward+backward,
ms a call on the host's clock over `--reps` enqueued calls and one wait
(a call is milliseconds: the dispatch does not show), TFLOP/s of the 2
forward and 7 forward+backward products the algorithm needs, and the
kernel held to the chunked scan (largest absolute difference of the
output and the three gradients).

    chiprun -- python3 scripts/probe_attn_whole.py
    chiprun -- python3 scripts/probe_attn_whole.py --seq 1024 --batch 32 --causal

Exits 1 off a TPU; `--rehearse` runs a tiny shape through the
interpreter and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--block-heads", type=int, nargs="*", default=[8, 16])
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--skip", nargs="*", default=[])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.attention import _chunked_dense_attention
    from flexflow_tpu.ops.pallas.flash_kernel import (
        flash_attention_rows,
        flash_attention_tpu,
    )

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    if args.rehearse:
        args.batch, args.seq, args.heads, args.chunk = 4, 128, 4, 2
        args.block_heads, args.reps = [2, 4], 1
    elif dev.platform != "tpu":
        print(json.dumps({"error": "no chip: a time is a chip's", **device}))
        sys.exit(1)
    b, s, h, d = args.batch, args.seq, args.heads, args.head_dim
    rng = np.random.Generator(np.random.PCG64(0))
    q, k, v, w = (
        jnp.asarray(rng.standard_normal((b, s, h, d), np.float32), jnp.bfloat16)
        for _ in range(4)
    )
    product = 2.0 * b * h * s * s * d * (0.5 if args.causal else 1.0)

    sides = {
        "chunked": lambda q, k, v: _chunked_dense_attention(
            q, k, v, args.causal, args.chunk
        ),
        "grid": lambda q, k, v: flash_attention_tpu(
            q, k, v, causal=args.causal, block_q=min(512, s),
            block_k=min(1024, s),
        ),
    }
    for hb in args.block_heads:
        sides[f"whole_{hb}"] = lambda q, k, v, hb=hb: flash_attention_rows(
            q, k, v, h, causal=args.causal, block_heads=hb
        )
    for name in args.skip:
        sides.pop(name, None)

    # the whole-sequence form is handed ROWS [b, s, h * d], as the
    # lowering hands them: a [b, s, h, d] device array would be relaid
    # inside the timed program
    rows = tuple(a.reshape(b, s, h * d) for a in (q, k, v, w))

    def timed(fn, args3):
        out = fn(*args3)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*args3)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / args.reps * 1e3

    results, ref = {}, None
    for name, core in sides.items():
        qq, kk, vv, ww = rows if name.startswith("whole") else (q, k, v, w)
        fwd = jax.jit(core)
        both = jax.jit(
            jax.grad(
                lambda q, k, v: (core(q, k, v).astype(jnp.float32) * ww).sum(),
                argnums=(0, 1, 2),
            )
        )
        o, fwd_ms = timed(fwd, (qq, kk, vv))
        g, both_ms = timed(both, (qq, kk, vv))
        got = [np.asarray(a, np.float32).reshape(b, s, h, d) for a in (o, *g)]
        if ref is None:
            ref = got
        row = {
            "max_abs_diff_o_dq_dk_dv": [
                float(np.max(np.abs(a - r))) for a, r in zip(got, ref)
            ],
        }
        if not args.rehearse:
            row.update(
                fwd_ms=round(fwd_ms, 3),
                fwd_bwd_ms=round(both_ms, 3),
                fwd_tflops=round(2 * product / fwd_ms / 1e9, 1),
                fwd_bwd_tflops=round(7 * product / both_ms / 1e9, 1),
            )
        results[name] = row
        print(name, json.dumps(row), flush=True)
    print(json.dumps({**device, "shape": [b, s, h, d], "causal": args.causal,
                      "results": results}))


if __name__ == "__main__":
    main()
