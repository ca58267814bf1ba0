"""Plain reference of the `deepseek_v3` architecture without query
compression (DeepSeek-V3's block as kakaocorp/kanana-2-30b-a3b publishes
it: q_lora_rank null): pre-RMSNorm blocks of latent attention (MLA), then
a SiLU-gated MLP in the leading dense layers and after them a
sigmoid-routed top-k expert layer plus a shared gated MLP; final RMSNorm,
untied head, no biases. With a = rmsnorm_1(x), every norm with a learned
gain:

    q        = Wq a                       h heads of [q_nope | q_rope]
    [c, kr]  = Wkva a;  c = rmsnorm_kv(c)
    q_rope, kr = rope(.)                  interleaved: elements (2i, 2i + 1) turn by pos * theta^(-2i/rope)
    [k_nope_h | v_h] = Wkvb_h c;  k_h = [k_nope_h | kr]   (kr is ONE key, shared by all heads)
    h_       = x + Wo concat_h( causal softmax(q_h k_h^T / sqrt(nope + rope)) v_h )
    dense:   out = h_ + Wdown(silu(Wgate m) * (Wup m)),  m = rmsnorm_2(h_)
    experts: s = sigmoid(Wg m) over ALL experts;  e = top-k of (s + b), b used for the CHOICE only
             w = s[e] / (sum_j s[e_j] + 1e-20) * scale
             out = h_ + sum_j w_j E_{e_j}(m) + S(m),  E, S gated MLPs

float32 `jax.numpy` at `highest` matmul precision, one full forward pass
over the whole sequence by the DECOMPRESSED equations: no cache, no
paging, no batching, no sort, no kernels, no absorbed form: every expert
held is applied densely to every row and masked by the gate weights. It
shares no code with `flexflow_tpu/ops/`.

Departures from the published model, all three the configuration's:
(1) `held` = (first, count): the stacked expert weights are experts
first .. first + count - 1 of the router's width, ONE chip's share of a
layer whose experts are divided over chips. The router, its top-k and the
normalisation are over all experts; what the absent experts would have
added is left out, and that partial result goes on to the next layer
(model-configs guide, section 4). `held=None` is the uncut layer: the
stack holds every expert. (2) The vocabulary is whatever the embedding
and the head hold (a slice is a smaller vocabulary). (3) HF's code
de-interleaves q_rope and kr and then rotates halves; rotating the
interleaved pairs in place gives the same dot products q . k, and kr
never leaves the attention.

`weights` is the program's parameter tree flattened in graph order:
[embedding]; per layer [norm1 gain], [wq, wkva, kv gain, wkvb, wo],
[norm2 gain], then either [gate, up, down] (dense) or [router, gate, up,
down, bias], [shared gate, up, down]; then [final norm gain], [head].
wq [e, h, nope + rope], wkva [e, rank + rope], wkvb [rank, h, nope + v],
wo [h, v, e], router [e, E], expert gate/up [held, e, f], down
[held, f, e], bias [E]. The head sizes are read from the shapes, given
the rotary width `rope`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x [t, heads, d] at positions 0..t-1: pair i = (x[2i], x[2i+1])
    turns by pos * theta^(-2i/d)."""
    t, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv_freq
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(angles) - odd * jnp.sin(angles))
    return out.at[..., 1::2].set(odd * jnp.cos(angles) + even * jnp.sin(angles))


def _gated(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def route(m, router, bias, k, scale, forced=None):
    """m [t, e] -> (weights [t, k], experts [t, k], best first): sigmoid
    scores, the bias in the choice only, weights renormalised over the k
    chosen and scaled. `forced` [t, k]: the choice is given (another
    program's, whose near-ties fell otherwise), the weights are still
    this function's own scores of those experts."""
    scores = jax.nn.sigmoid(m @ router)
    _, top_e = jax.lax.top_k(scores + bias, k)
    if forced is not None:
        top_e = forced
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    return top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * scale, top_e


def routed_experts(
    m, router, w_gate, w_up, w_down, bias, k, scale, held=None, forced=None
):
    """The routed part of an expert layer: every held expert on every
    row, masked by the gate weights of the rows that chose it. Returns
    (sum [t, e], chosen experts [t, k])."""
    top_w, top_e = route(m, router, bias, k, scale, forced)
    n = router.shape[-1]
    mask = jnp.sum(jax.nn.one_hot(top_e, n, dtype=m.dtype) * top_w[..., None], axis=1)
    if held is not None:
        mask = mask[:, held[0]: held[0] + held[1]]

    def one(carry, expert):
        wg, wu, wd, col = expert
        return carry + col[:, None] * _gated(m, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (w_gate, w_up, w_down, mask.T))
    return out, top_e


def _attention(t, g1, attn, eps, theta, rope):
    wq, wkva, gkv, wkvb, wo = attn
    rank = wkvb.shape[0]
    nope = wq.shape[-1] - rope
    a = _rms_norm(t, g1, eps)
    q = jnp.einsum("se,ehd->shd", a, wq)
    kva = a @ wkva
    c = _rms_norm(kva[:, :rank], gkv, eps)
    q_rope = _rope(q[..., nope:], theta)
    kr = _rope(kva[:, None, rank:], theta)  # [t, 1, rope]: one key for all heads
    kv = jnp.einsum("sr,rhd->shd", c, wkvb)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (
        jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
        + jnp.einsum("qhd,kd->hqk", q_rope, kr[:, 0])
    ) / jnp.sqrt(jnp.float32(nope + rope))
    causal = jnp.tril(jnp.ones((t.shape[0], t.shape[0]), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return t + jnp.einsum("shd,hde->se", ctx, wo)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "rope"))
def _dense_block(t, layer, eps, theta, rope):
    (g1,), attn, (g2,), (w_gate, w_up, w_down) = layer
    t = _attention(t, g1, attn, eps, theta, rope)
    return t + _gated(_rms_norm(t, g2, eps), w_gate, w_up, w_down)


@functools.partial(
    jax.jit, static_argnames=("eps", "theta", "rope", "k", "scale", "held")
)
def _expert_block(t, layer, eps, theta, rope, k, scale, held, forced=None):
    (g1,), attn, (g2,), (router, w_gate, w_up, w_down, bias), shared = layer
    t = _attention(t, g1, attn, eps, theta, rope)
    m = _rms_norm(t, g2, eps)
    routed, chosen = routed_experts(
        m, router, w_gate, w_up, w_down, bias, k, scale, held, forced
    )
    return t + routed + _gated(m, *shared), chosen


def forward(weights, tokens, eps, theta, rope, k, scale, held=None, forced=None):
    """tokens [t] int32 -> (logits [t, vocab], chosen [expert layers, t, k]
    int32: the experts each position picked in each expert layer, best
    first, in the router's own numbering; `forced`, of that shape, gives
    the choice instead, `route`). Every layer runs one of two
    jitted blocks, and the experts of a layer are a `lax.scan`, so the
    compile cache holds two blocks and one expert; the weights are used
    where they lie (stacking the layers would copy them, and a second
    copy does not fit the chip beside the served model)."""
    t = weights[0][0][tokens]
    chosen, i = [], 1
    while i < len(weights) - 2:
        if len(weights[i + 3]) == 3:  # [gate, up, down]: a dense layer
            t = _dense_block(t, weights[i: i + 4], eps, theta, rope)
            i += 4
        else:
            t, e = _expert_block(
                t, weights[i: i + 5], eps, theta, rope, k, scale, held,
                None if forced is None else forced[len(chosen)],
            )
            chosen.append(e)
            i += 5
    return _rms_norm(t, weights[-2][0], eps) @ weights[-1][0], jnp.stack(chosen)


def run(
    weights, tokens, pad_to: int, eps, theta, rope, k, scale, held=None,
    forced=None, positions=None,
):
    """The full forward pass over `tokens` padded to `pad_to`, so that one
    compiled program serves every length (causal: what follows a position
    cannot reach it). Returns (logits [len, vocab], or only the rows at
    `positions`; chosen [expert layers, len, k]) as numpy arrays. `forced`
    [expert layers, len, k]: `forward`'s."""
    n = len(tokens)
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = np.asarray(tokens, np.int32)
    if forced is not None:
        given = np.zeros((len(forced), pad_to, int(k)), np.int32)
        given[:, :n] = np.asarray(forced, np.int32)
        forced = jnp.asarray(given)
    held = None if held is None else (int(held[0]), int(held[1]))
    rows = np.arange(n) if positions is None else np.asarray(positions)
    with jax.default_matmul_precision("highest"):
        logits, chosen = forward(
            weights, jnp.asarray(padded), float(eps), float(theta), int(rope),
            int(k), float(scale), held, forced,
        )
        return np.asarray(logits[rows]), np.asarray(chosen[:, :n])


def logits_at(weights, tokens, positions, pad_to: int, *sizes):
    """Logits of the full forward pass at `positions`; `sizes` as `run`'s
    (eps, theta, rope, k, scale[, held])."""
    return run(weights, tokens, pad_to, *sizes, positions=positions)[0]
