"""Probe: attention core fwd+bwd across batch sizes and kernels (real chip).

Round-2 finding (the August batch sweep): the flagship transformer drops
from 52-54% MFU at bs8 to 40% at bs16/32, and the dense-attention backward
was named as superlinear (0.58 -> 1.58 ms/layer core from bs8 -> bs16).
This probe isolates the attention core (post-projection q,k,v -> attn out)
and times fwd-only and fwd+bwd for dense vs blockwise vs lib-Pallas flash
at bs in {8, 16, 32}, bf16 operands, seq 512 / 16 heads / head_dim 64
(the flagship shape, reference transformer.cc:79-85).
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from flexflow_tpu.ops.attention import scaled_dot_product_attention
from flexflow_tpu.ops.pallas.flash_attention import flash_attention
from flexflow_tpu.utils.benchmark import measure_fn


def main():
    h, d, s = 16, 64, 512
    results = []
    for bs in (8, 16, 32):
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (bs, s, h, d), dtype=jnp.bfloat16)
        k = jax.random.normal(kk, (bs, s, h, d), dtype=jnp.bfloat16)
        v = jax.random.normal(kv, (bs, s, h, d), dtype=jnp.bfloat16)

        def dense(q, k, v):
            return scaled_dot_product_attention(q, k, v, causal=False)

        def blockwise(q, k, v):
            return flash_attention(q, k, v, causal=False, use_lib=False)

        def lib(q, k, v):
            return flash_attention(q, k, v, causal=False, use_lib="library")

        def grad_of(fn):
            def loss(q, k, v):
                return fn(q, k, v).astype(jnp.float32).sum()

            g = jax.grad(loss, argnums=(0, 1, 2))

            def run(q, k, v):
                gq, gk, gv = g(q, k, v)
                return gq.astype(jnp.float32).sum() + gk.astype(
                    jnp.float32
                ).sum() + gv.astype(jnp.float32).sum()

            return run

        row = {"bs": bs}
        for name, fn in (("dense", dense), ("blockwise", blockwise), ("lib", lib)):
            try:
                fwd = measure_fn(fn, (q, k, v), n1=4, n2=12, reps=3)
            except Exception as e:  # lib kernel may refuse off-TPU
                row[name] = {"error": str(e)[:120]}
                continue
            try:
                fb = measure_fn(grad_of(fn), (q, k, v), n1=4, n2=12, reps=3)
            except Exception as e:
                row[name] = {"fwd_ms": fwd * 1e3, "bwd_error": str(e)[:120]}
                continue
            row[name] = {"fwd_ms": round(fwd * 1e3, 3), "fwdbwd_ms": round(fb * 1e3, 3)}
        results.append(row)
        print(json.dumps(row), flush=True)

    print(json.dumps({"all": results}))


if __name__ == "__main__":
    main()
