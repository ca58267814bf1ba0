"""Plain reference of the flagship encoder stack (transformer.cc:33-45).

float32 `jax.numpy` at `highest` matmul precision: no kernels, no mixed
precision, no chunking of the mathematics (only of the batch, so that the
scores fit). Per layer: multi-head attention over the whole sequence
(biases on q, k, v and the output projection, scale 1/sqrt(head size),
not causal), dense + relu, dense; then dense(1) and the mean squared
error over every element. `weights` is the program's parameter tree
flattened in graph order: per layer [wq, wk, wv, wo, bq, bk, bv, bo],
[w1], [w2]; last [w_out].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _attention(x, wq, wk, wv, wo, bq, bk, bv, bo):
    q = jnp.einsum("bse,ehd->bshd", x, wq) + bq
    k = jnp.einsum("bse,ehd->bshd", x, wk) + bk
    v = jnp.einsum("bse,ehd->bshd", x, wv) + bv
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return jnp.einsum("bshd,hde->bse", ctx, wo) + bo


def forward(weights, x):
    layers = (len(weights) - 1) // 3
    t = x
    for i in range(layers):
        attn, (w1,), (w2,) = weights[3 * i: 3 * i + 3]
        t = _attention(t, *attn)
        t = jax.nn.relu(t @ w1)
        t = t @ w2
    return t @ weights[-1][0]


def loss(weights, x, label, chunk: int = 8) -> float:
    """Mean squared error over the whole batch, computed `chunk`
    sequences at a time (equal chunks, so the mean of means is the
    mean)."""
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda w, a, b: jnp.mean(jnp.square(forward(w, a) - b)))
        n = x.shape[0]
        chunk = min(chunk, n)
        if n % chunk:
            raise ValueError(f"batch {n} is not a multiple of {chunk}")
        parts = [
            fn(weights, jnp.asarray(x[i:i + chunk]), jnp.asarray(label[i:i + chunk]))
            for i in range(0, n, chunk)
        ]
        return float(jnp.mean(jnp.stack(parts)))
