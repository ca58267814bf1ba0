"""Plain reference of Ouro (ByteDance/Ouro-2.6B, arXiv:2510.25741): a
stack of L layers run `loops` times over the SAME weights, a norm and an
exit gate after every pass, an untied head over the last pass. With
`h(0) = E[ids]`, for pass t = 1..loops and `u = h(t-1)`, for layer i = 1..L:

    u = u + N2_i(Wo_i . Attn(rope(Wq_i a), rope(Wk_i a), Wv_i a)),   a = N1_i(u)
    u = u + N4_i(Wd_i (silu(Wg_i m) * (Wu_i m))),                    m = N3_i(u)

then `h(t) = Nf(u)` and `lambda_t = sigmoid(w_g . h(t) + b_g)`;
`logits = W_head h(loops)`. The exit distribution is
`p_t = lambda_t prod_{s<t} (1 - lambda_s)` for t < loops and
`p_loops = prod_{s<loops} (1 - lambda_s)`. Every N is an RMSNorm with a
gain; rotary positions in the rotate-half form; attention is causal
softmax(q k^T / sqrt(d)) v over the keys and values that THE SAME PASS of
the same layer made at earlier positions.

float32 `jax.numpy` at `highest` matmul precision, one full forward pass
over the whole sequence: no cache, no paging, no batching, no kernels. It
shares no code with `flexflow_tpu/`. `weights` is the program's parameter
tree flattened in graph order: [embedding]; per layer [n1 gain],
[wq, wk, wv, wo], [n2 gain], [n3 gain], [gate, up, down], [n4 gain]; then
[final norm gain], [gate weight, gate bias], [head]. wq/wk/wv are
[e, heads, d], wo [heads, d, e], gate/up [e, f], down [f, e], the exit
gate's weight [e, 1].

Two controls, for the limits of the configuration's `tolerance` and never
for a verdict: `dtype=jnp.bfloat16` computes in bfloat16 weights and
activations; `one_cache=True` is a server that kept ONE cache a layer for
all passes, to first order: pass t attends over the LAST pass's keys and
values at earlier positions (what such a cache holds once a position is
done) and over its own at the current one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PER_LAYER = 6  # weight groups a layer


def _rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half rotary embedding over each head: x [t, heads, d] at
    positions 0..t-1."""
    t, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return (x * jnp.cos(angles) + rotated * jnp.sin(angles)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(u, layer, others, eps, theta):
    """One application of one layer. `others`: None, or the (k, v) that
    the control's single cache holds at EARLIER positions. Returns the new
    u and this application's own (k, v)."""
    (g1,), (wq, wk, wv, wo), (g2,), (g3,), (w_gate, w_up, w_down), (g4,) = layer
    n = u.shape[0]
    a = _rms_norm(u, g1, eps)
    q = _rope(jnp.einsum("se,ehd->shd", a, wq), theta)
    k = _rope(jnp.einsum("se,ehd->shd", a, wk), theta)
    v = jnp.einsum("se,ehd->shd", a, wv)
    scale = jnp.sqrt(jnp.float32(q.shape[-1]))
    own = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) / scale
    eye = jnp.eye(n, dtype=bool)
    if others is None:
        scores = own
    else:
        seen = jnp.einsum("qhd,khd->hqk", q, others[0]).astype(jnp.float32)
        scores = jnp.where(eye[None], own, seen / scale)
    scores = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(u.dtype)
    if others is None:
        ctx = jnp.einsum("hqk,khd->qhd", probs, v)
    else:
        ctx = jnp.einsum(
            "hqk,khd->qhd", jnp.where(eye[None], 0, probs), others[1]
        ) + jnp.einsum("hq,qhd->qhd", jnp.diagonal(probs, axis1=1, axis2=2), v)
    u = u + _rms_norm(jnp.einsum("shd,hde->se", ctx, wo), g2, eps)
    m = _rms_norm(u, g3, eps)
    mlp = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
    return u + _rms_norm(mlp, g4, eps), (k, v)


def forward(weights, tokens, eps=1e-6, theta=1e6, loops=4, positions=None,
            dtype=jnp.float32, one_cache=False):
    """tokens [t] int32 -> (logits [n, vocab], exit distribution
    [n, loops]) at `positions` (all t when None). Every application of
    every layer runs the one jitted `_layer`."""
    weights = jax.tree_util.tree_map(lambda w: jnp.asarray(w, dtype), weights)
    n_layers = (len(weights) - 4) // PER_LAYER
    layers = [
        weights[1 + PER_LAYER * i: 1 + PER_LAYER * (i + 1)]
        for i in range(n_layers)
    ]
    (norm,), (w_gate, b_gate), (head,) = weights[-3:]
    last = [None] * n_layers
    if one_cache:  # what the last pass of the true model leaves in a cache
        h = weights[0][0][tokens]
        for t in range(loops):
            for i, layer in enumerate(layers):
                h, last[i] = _layer(h, layer, None, eps, theta)
            h = _rms_norm(h, norm, eps)
    h = weights[0][0][tokens]
    stay, exits = 1.0, []
    for t in range(loops):
        for i, layer in enumerate(layers):
            others = last[i] if one_cache and t < loops - 1 else None
            h, _ = _layer(h, layer, others, eps, theta)
        h = _rms_norm(h, norm, eps)
        lam = jax.nn.sigmoid((h @ w_gate)[:, 0].astype(jnp.float32) + b_gate[0])
        exits.append(stay * lam if t < loops - 1 else stay * jnp.ones_like(lam))
        stay = stay * (1.0 - lam)
    if positions is not None:
        at = jnp.asarray(positions)
        h, exits = h[at], [e[at] for e in exits]
    return (h @ head).astype(jnp.float32), jnp.stack(exits, axis=-1)


def run(weights, tokens, pad_to: int, eps=1e-6, theta=1e6, loops=4,
        positions=None, **control):
    """The full forward pass over `tokens` padded to `pad_to`, so that one
    compiled program serves every length (causal: what follows a position
    cannot reach it). Returns (logits, exit distribution) at `positions`
    (every position of `tokens` when None) as numpy arrays."""
    padded = np.zeros((pad_to,), np.int32)
    padded[: len(tokens)] = np.asarray(tokens, np.int32)
    if positions is None:
        positions = np.arange(len(tokens))
    with jax.default_matmul_precision("highest"):
        logits, exits = forward(
            weights, jnp.asarray(padded), eps, theta, loops,
            np.asarray(positions), **control,
        )
        return np.asarray(logits), np.asarray(exits)


def logits_at(weights, tokens, positions, pad_to: int, eps=1e-6, theta=1e6,
              loops=4):
    """Logits of the full forward pass at `positions`."""
    return run(weights, tokens, pad_to, eps, theta, loops, positions)[0]
