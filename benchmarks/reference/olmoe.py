"""Plain reference of OLMoE (allenai/OLMoE-1B-7B): pre-RMSNorm blocks of
causal attention with QK-norm and rotary positions, then a top-k expert
layer of SiLU-gated MLPs; final RMSNorm, untied head, no biases.

    h   = x + Wo . Attn(rope(rmsnorm_q(Wq a)), rope(rmsnorm_k(Wk a)), Wv a),  a = rmsnorm_1(x)
    p   = softmax(Wg m) over all experts,  m = rmsnorm_2(h);  (w, e) = top-k of p, not renormalised
    out = h + sum_j w_j . Wdown[e_j](silu(Wgate[e_j] m) * (Wup[e_j] m))

float32 `jax.numpy` at `highest` matmul precision, one full forward pass
over the whole sequence: no cache, no paging, no batching, no sort and no
kernels: every expert is applied densely to every row and masked by the
top-k weights. It shares no code with `flexflow_tpu/ops/`. `weights` is
the program's parameter tree flattened in graph order: [embedding]; per
layer [norm1 gain], [wq, wk, wv, wo, q gain, k gain], [norm2 gain],
[router, gate, up, down]; then [final norm gain], [head]. wq/wk/wv are
[e, heads, d], wo [heads, d, e], the q/k gains [heads, d] (one gain over
the whole projection), router [e, E], gate/up [E, e, f], down [E, f, e].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PER_LAYER = 4  # weight groups a layer


def _rms_norm(x, gain, eps, axes=(-1,)):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=axes, keepdims=True) + eps) * gain


def _rope(x, theta):
    """Rotate-half rotary embedding over each head: x [t, heads, d] at
    positions 0..t-1."""
    t, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _experts(m, router, w_gate, w_up, w_down, k):
    """Every expert on every row, masked by the top-k gate weights."""
    probs = jax.nn.softmax(m @ router, axis=-1)  # [t, E]
    top_w, top_e = jax.lax.top_k(probs, k)
    n = router.shape[-1]
    mask = jnp.sum(jax.nn.one_hot(top_e, n, dtype=m.dtype) * top_w[..., None], axis=1)

    def one(carry, expert):
        wg, wu, wd, col = expert
        return carry + col[:, None] * ((jax.nn.silu(m @ wg) * (m @ wu)) @ wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (w_gate, w_up, w_down, mask.T))
    return out, top_e


@functools.partial(jax.jit, static_argnames=("eps", "theta", "k"))
def _block(t, layer, eps, theta, k):
    causal = jnp.tril(jnp.ones((t.shape[0], t.shape[0]), bool))
    (g1,), (wq, wk, wv, wo, gq, gk), (g2,), (router, w_gate, w_up, w_down) = layer
    a = _rms_norm(t, g1, eps)
    q = _rms_norm(jnp.einsum("se,ehd->shd", a, wq), gq, eps, axes=(-2, -1))
    kk = _rms_norm(jnp.einsum("se,ehd->shd", a, wk), gk, eps, axes=(-2, -1))
    v = jnp.einsum("se,ehd->shd", a, wv)
    q, kk = _rope(q, theta), _rope(kk, theta)
    scores = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    t = t + jnp.einsum("shd,hde->se", ctx, wo)
    out, chosen = _experts(_rms_norm(t, g2, eps), router, w_gate, w_up, w_down, k)
    return t + out, chosen


def forward(weights, tokens, eps: float = 1e-5, theta: float = 10000.0, k: int = 8):
    """tokens [t] int32 -> (logits [t, vocab], chosen [layers, t, k] int32:
    the experts each position picked in each layer, best first). Every
    layer runs the one jitted `_block`, and the experts of a layer are a
    `lax.scan`, so the compile cache holds one block and one expert.
    (`reference/decoder_lm.py` scans over stacked layers for the same
    end; stacking copies the weights, and a second copy of these does not
    fit the chip beside the served model.)"""
    n_layers = (len(weights) - 3) // PER_LAYER
    t = weights[0][0][tokens]
    chosen = []
    for i in range(n_layers):
        layer = weights[1 + PER_LAYER * i: 1 + PER_LAYER * (i + 1)]
        t, e = _block(t, layer, eps, theta, k)
        chosen.append(e)
    return _rms_norm(t, weights[-2][0], eps) @ weights[-1][0], jnp.stack(chosen)


def run(weights, tokens, pad_to: int, eps=1e-5, theta=10000.0, k=8):
    """The full forward pass over `tokens` padded to `pad_to`, so that one
    compiled program serves every length (causal: what follows a position
    cannot reach it). Returns (logits [len, vocab], chosen [layers, len, k])
    as numpy arrays."""
    padded = np.zeros((pad_to,), np.int32)
    padded[: len(tokens)] = np.asarray(tokens, np.int32)
    n = len(tokens)
    with jax.default_matmul_precision("highest"):
        logits, chosen = forward(weights, jnp.asarray(padded), eps, theta, k)
        return np.asarray(logits[:n]), np.asarray(chosen[:, :n])


def logits_at(weights, tokens, positions, pad_to: int, eps=1e-5, theta=10000.0, k=8):
    """Logits of the full forward pass at `positions`."""
    logits, _ = run(weights, tokens, pad_to, eps, theta, k)
    return logits[np.asarray(positions)]
