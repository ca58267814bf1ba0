"""Plain reference of the decoder LM the server runs (GPT-2 shaped,
with the program's listed departures: no position embedding, no biases,
untied head, exact GELU).

float32 `jax.numpy` at `highest` matmul precision, one full forward pass
over the whole sequence: no cache, no paging, no batching, no kernels.
`weights` is the program's parameter tree flattened in graph order:
[embedding]; per layer [ln1 gain, bias], [wq, wk, wv, wo], [ln2 gain,
bias], [w_up], [w_down]; then [ln_f gain, bias], [w_head].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def _block(t, layer, causal, eps):
    (g1, b1), (wq, wk, wv, wo), (g2, b2), (w_up,), (w_down,) = layer
    h = _layer_norm(t, g1, b1, eps)
    q = jnp.einsum("se,ehd->shd", h, wq)
    k = jnp.einsum("se,ehd->shd", h, wk)
    v = jnp.einsum("se,ehd->shd", h, wv)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    t = t + jnp.einsum("shd,hde->se", ctx, wo)
    h = _layer_norm(t, g2, b2, eps)
    return t + jax.nn.gelu(h @ w_up, approximate=False) @ w_down


def forward(weights, tokens, eps: float = 1e-5):
    """tokens [t] int32 -> logits [t, vocab]. The layers are one
    `lax.scan` over their stacked weights, so that the compiled program
    holds one block and not one per layer (the machine's compile cache
    is capped; an unrolled 24-layer reference took 24 MiB of it)."""
    layers = [weights[1 + 5 * i: 6 + 5 * i] for i in range((len(weights) - 3) // 5)]
    stacked = jax.tree.map(lambda *ws: jnp.stack(ws), *layers)
    t = weights[0][0][tokens]
    n = tokens.shape[0]
    causal = jnp.tril(jnp.ones((n, n), bool))
    t, _ = jax.lax.scan(
        lambda t, layer: (_block(t, layer, causal, eps), None), t, stacked
    )
    gf, bf = weights[-2]
    return _layer_norm(t, gf, bf, eps) @ weights[-1][0]


def logits_at(weights, tokens, positions, pad_to: int, eps: float = 1e-5):
    """Logits of the full forward pass at `positions`, with the sequence
    padded to `pad_to` so that one compiled program serves every length
    (causal: what follows a position cannot reach it)."""
    padded = np.zeros((pad_to,), np.int32)
    padded[: len(tokens)] = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        out = _forward_jit(weights, jnp.asarray(padded), eps)
        return np.asarray(out[np.asarray(positions)])


@functools.partial(jax.jit, static_argnames=("eps",))
def _forward_jit(weights, tokens, eps):
    return forward(weights, tokens, eps)
