"""A/B the attention cores on the real chip: hand-tiled Pallas kernel
(flash_kernel.py) vs the library Pallas kernel vs jnp-blockwise vs dense.

fwd+bwd per step, chained-scan differencing
(flexflow_tpu/utils/benchmark.py). Usage:

    python scripts/bench_flash_kernel.py [seq ...] [--causal] [--bs N]
"""

import argparse
import math
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("seqs", nargs="*", type=int, default=None)
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--bs", type=int, default=1)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--hd", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    seqs = args.seqs or [2048, 4096, 8192]

    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.attention import scaled_dot_product_attention
    from flexflow_tpu.ops.pallas.flash_attention import (
        _blockwise_attention,
        _lib_flash,
    )
    from flexflow_tpu.ops.pallas.flash_kernel import flash_attention_tpu
    from flexflow_tpu.utils.benchmark import measure_fn

    print(f"backend={jax.default_backend()} devices={jax.device_count()}")

    b, h, d = args.bs, args.heads, args.hd
    for seq in seqs:
        rng = np.random.RandomState(0)
        q = jnp.asarray(
            rng.randn(b, seq, h, d).astype(np.float32), jnp.bfloat16
        )
        k = jnp.asarray(
            rng.randn(b, seq, h, d).astype(np.float32), jnp.bfloat16
        )
        v = jnp.asarray(
            rng.randn(b, seq, h, d).astype(np.float32), jnp.bfloat16
        )

        def mk_step(core):
            def loss(q, k, v):
                o = core(q, k, v)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            g = jax.grad(loss, argnums=(0, 1, 2))

            def step(q, k, v):
                dq, dk, dv = g(q, k, v)
                return (
                    jnp.sum(dq.astype(jnp.float32))
                    + jnp.sum(dk.astype(jnp.float32))
                    + jnp.sum(dv.astype(jnp.float32))
                )

            return step

        variants = {
            "tiled": lambda q, k, v: flash_attention_tpu(
                q, k, v, causal=args.causal
            ),
            "library": lambda q, k, v: _lib_flash(q, k, v, args.causal),
            "blockwise": lambda q, k, v: _blockwise_attention(
                q, k, v, args.causal, 512
            ),
        }
        score_gib = b * h * seq * seq * 4 / (1 << 30)
        if score_gib <= 4.1:  # dense compiles/runs below ~4 GiB scores
            variants["dense"] = lambda q, k, v: scaled_dot_product_attention(
                q, k, v, causal=args.causal
            )

        # fwd = qk^T + pv = 4*b*h*s^2*d MACs*2; bwd ~ 2.5x fwd
        flops = 14.0 * b * h * seq * seq * d
        print(f"-- seq {seq} (score {score_gib:.2f} GiB) --")
        for name, core in variants.items():
            try:
                t = measure_fn(
                    mk_step(core), (q, k, v), reps=args.reps
                )
                tf = flops / t / 1e12
                print(f"  {name:10s} {t*1e3:8.2f} ms  ({tf:.1f} TF/s fwd+bwd-ish)")
            except Exception as e:  # noqa: BLE001
                msg = str(e).splitlines()[0][:100] if str(e) else repr(e)
                print(f"  {name:10s} FAILED: {msg}")


if __name__ == "__main__":
    main()
