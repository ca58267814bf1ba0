"""Plain reference of the `kimi_linear` architecture (moonshotai/
Kimi-Linear-48B-A3B): pre-RMSNorm blocks `h = x + Attn(N1(x))`,
`y = h + FFN(N2(h))`; `Attn` is Kimi Delta Attention (KDA) or latent
attention without positional encoding, by the published pattern; `FFN` a
SiLU-gated MLP in the leading dense layers and after them the
`deepseek_v3` expert layer (sigmoid scores, a choice bias, renormalised
and scaled weights, one shared gated MLP: `reference/deepseek_v3.py`'s
`routed_experts`, imported, since the layer is that model's); final
RMSNorm, untied head, no biases. With u = N1(x), per token t:

KDA (H heads of d):
    q~, k~, v~ = Wq u, Wk u, Wv u
    q'_t[c] = silu(sum_{j=0..K-1} wq[j, c] * q~_{t-K+1+j}[c])   (zero before the first token; k', v' alike)
    q = l2norm(q'_h) * d^-0.5;  k = l2norm(k'_h);  v = v'_h       l2norm(x) = x / sqrt(sum x^2 + 1e-6)
    g = -exp(A_log_h) * softplus(Wfb (Wfa u) + dt_bias)           [H, d]
    beta = sigmoid(Wb u)                                          [H]
    S' = diag(exp g) S_{t-1};  S_t = S' + beta k (v - k^T S')^T;  o = S_t^T q      S_0 = 0
    y_h = rmsnorm(o_h; gain) * sigmoid((Wgb (Wga u))_h);  out = Wo concat_h(y_h)

latent attention, NoPE:
    q = Wq u  [H, nope + rope];  [c, kr] = Wkva u;  c = rmsnorm(c)
    [k_h | v_h] = Wkvb_h c;  score_h(t, s) = (q_h[:nope] . k_h(s) + q_h[nope:] . kr(s)) / sqrt(nope + rope)
    causal softmax;  out = Wo concat_h(sum_s p v_h(s)).  Nothing is rotated.

float32 `jax.numpy` at `highest` matmul precision, one full forward pass
over the whole sequence; the recurrence TOKEN BY TOKEN (`lax.scan` over
tokens: no chunks), no cache, no paging, no batching, no kernels; every
expert held is applied densely to every row. It shares no code with
`flexflow_tpu/ops/`.

Departures from the published model, the configuration's: `held` =
(first, count), this chip's share of each expert layer's experts, and
the vocabulary is whatever the embedding and the head hold
(`reference/deepseek_v3.py`, departures 1 and 2).

`weights` is the program's parameter tree flattened in graph order:
[embedding]; per layer [norm1 gain], the attention's weights, [norm2
gain], then either [gate, up, down] (dense) or [router, gate, up, down,
bias], [shared gate, up, down]; then [final norm gain], [head]. KDA's 15:
Wq, Wk, Wv [e, H d]; wq, wk, wv [K, H d]; Wfa [e, r], Wfb [r, H d],
dt_bias [H d], A_log [H]; Wb [e, H]; Wga [e, r], Wgb [r, H d]; gain [d];
Wo [H d, e]. Latent attention's 5 as in `reference/deepseek_v3.py`. An
attention is told apart by how many weights it has, and the head sizes
are read from the shapes, given the rotary width `rope`.

Two faults can be put in, for the controls of the configuration's
tolerance: `fault=("zero_state", n)` forgets every KDA layer's state
before token n (a decode that started from the zero state instead of the
prefill's), `fault=("no_tails", n)` has tokens n and later see zeros for
the convolution inputs before n (a decode that dropped the tails). And
`bf16=True` rounds every weight to bfloat16 first, layer by layer (the
precision below the configuration's; the arithmetic stays float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import deepseek_v3 as _ds

_rms_norm, _gated, routed_experts = _ds._rms_norm, _ds._gated, _ds.routed_experts


def _short_conv(x, w, cut=None):
    """x [t, c], w [K, c]: out[t] = sum_j w[j] * x[t - K + 1 + j],
    zeros before the first token. `cut`: tokens at or after it see zeros
    for the inputs before it (the `no_tails` fault)."""
    t, kernel = x.shape[0], w.shape[0]
    at = jnp.arange(t)
    out = jnp.zeros_like(x)
    for j in range(kernel):
        back = kernel - 1 - j
        past = jnp.pad(x, ((back, 0), (0, 0)))[:t]
        if cut is not None and back:
            past = jnp.where(((at >= cut) & (at - back < cut))[:, None], 0.0, past)
        out = out + past * w[j]
    return out


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(t, g1, attn, eps, fault):
    (wq, wk, wv, cq, ck, cv, wfa, wfb, dt_bias, a_log, wb, wga, wgb, gain, wo) = attn
    n, h, d = t.shape[0], a_log.shape[0], gain.shape[0]
    kind, at = fault if fault is not None else (None, None)
    cut = at if kind == "no_tails" else None
    u = _rms_norm(t, g1, eps)

    def stream(w, c):
        return jax.nn.silu(_short_conv(u @ w, c, cut)).reshape(n, h, d)

    q = _l2norm(stream(wq, cq)) * d ** -0.5
    k = _l2norm(stream(wk, ck))
    v = stream(wv, cv)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        (u @ wfa) @ wfb + dt_bias
    ).reshape(n, h, d)
    beta = jax.nn.sigmoid(u @ wb)
    forget = (
        jnp.arange(n) == at if kind == "zero_state" else jnp.zeros((n,), bool)
    )

    def one(state, xs):
        qt, kt, vt, gt, bt, zero = xs
        state = jnp.where(zero, 0.0, state)
        decayed = jnp.exp(gt)[..., None] * state  # [h, d_k, d_v]
        seen = jnp.einsum("hk,hkv->hv", kt, decayed)
        state = decayed + jnp.einsum("hk,hv->hkv", kt, bt[:, None] * (vt - seen))
        return state, jnp.einsum("hk,hkv->hv", qt, state)

    _, o = jax.lax.scan(
        one, jnp.zeros((h, d, d), jnp.float32), (q, k, v, g, beta, forget)
    )
    z = ((u @ wga) @ wgb).reshape(n, h, d)
    y = _rms_norm(o, gain, eps) * jax.nn.sigmoid(z)
    return t + y.reshape(n, h * d) @ wo


def _latent(t, g1, attn, eps, rope):
    wq, wkva, gkv, wkvb, wo = attn
    rank = wkvb.shape[0]
    nope = wq.shape[-1] - rope
    a = _rms_norm(t, g1, eps)
    q = jnp.einsum("se,ehd->shd", a, wq)
    kva = a @ wkva
    c = _rms_norm(kva[:, :rank], gkv, eps)
    kv = jnp.einsum("sr,rhd->shd", c, wkvb)
    scores = (
        jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
        + jnp.einsum("qhd,kd->hqk", q[..., nope:], kva[:, rank:])
    ) / jnp.sqrt(jnp.float32(nope + rope))
    causal = jnp.tril(jnp.ones((t.shape[0], t.shape[0]), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return t + jnp.einsum("shd,hde->se", ctx, wo)


def _attention(t, g1, attn, eps, rope, fault):
    if len(attn) == 5:
        return _latent(t, g1, attn, eps, rope)
    return _kda(t, g1, attn, eps, fault)


def _rounded(layer):
    """Every weight of a layer rounded to bfloat16 and back, inside the
    layer's own program: a second copy of the model does not fit the chip
    beside the served one."""
    return jax.tree.map(lambda w: w.astype(jnp.bfloat16).astype(w.dtype), layer)


@functools.partial(jax.jit, static_argnames=("eps", "rope", "fault", "bf16"))
def _dense_block(t, layer, eps, rope, fault, bf16=False):
    layer = _rounded(layer) if bf16 else layer
    (g1,), attn, (g2,), (w_gate, w_up, w_down) = layer
    t = _attention(t, g1, attn, eps, rope, fault)
    return t + _gated(_rms_norm(t, g2, eps), w_gate, w_up, w_down)


@functools.partial(
    jax.jit, static_argnames=("eps", "rope", "k", "scale", "held", "fault", "bf16")
)
def _expert_block(t, layer, eps, rope, k, scale, held, fault, forced=None, bf16=False):
    layer = _rounded(layer) if bf16 else layer
    (g1,), attn, (g2,), (router, w_gate, w_up, w_down, bias), shared = layer
    t = _attention(t, g1, attn, eps, rope, fault)
    m = _rms_norm(t, g2, eps)
    routed, chosen = routed_experts(
        m, router, w_gate, w_up, w_down, bias, k, scale, held, forced
    )
    return t + routed + _gated(m, *shared), chosen


def forward(
    weights, tokens, eps, rope, k, scale, held=None, forced=None, fault=None,
    bf16=False,
):
    """tokens [t] int32 -> (logits [t, vocab], chosen [expert layers, t, k]
    int32), as `reference/deepseek_v3.py:forward`: one jitted block a
    layer, the weights used where they lie. `bf16`: every weight is
    rounded to bfloat16 first (the arithmetic stays float32)."""
    ends = _rounded((weights[0], weights[-2], weights[-1])) if bf16 else (
        weights[0], weights[-2], weights[-1]
    )
    t = ends[0][0][tokens]
    chosen, i = [], 1
    while i < len(weights) - 2:
        if len(weights[i + 3]) == 3:  # [gate, up, down]: a dense layer
            t = _dense_block(t, weights[i: i + 4], eps, rope, fault, bf16)
            i += 4
        else:
            t, e = _expert_block(
                t, weights[i: i + 5], eps, rope, k, scale, held, fault,
                None if forced is None else forced[len(chosen)], bf16,
            )
            chosen.append(e)
            i += 5
    return _rms_norm(t, ends[1][0], eps) @ ends[2][0], jnp.stack(chosen)


def run(
    weights, tokens, pad_to: int, eps, rope, k, scale, held=None, forced=None,
    positions=None, fault=None, bf16=False,
):
    """The full forward pass over `tokens` padded to `pad_to` (causal:
    what follows a position cannot reach it), as
    `reference/deepseek_v3.py:run`. Returns (logits [len, vocab], or only
    the rows at `positions`; chosen [expert layers, len, k]) as numpy
    arrays."""
    n = len(tokens)
    padded = np.zeros((pad_to,), np.int32)
    padded[:n] = np.asarray(tokens, np.int32)
    if forced is not None:
        given = np.zeros((len(forced), pad_to, int(k)), np.int32)
        given[:, :n] = np.asarray(forced, np.int32)
        forced = jnp.asarray(given)
    held = None if held is None else (int(held[0]), int(held[1]))
    fault = None if fault is None else (str(fault[0]), int(fault[1]))
    rows = np.arange(n) if positions is None else np.asarray(positions)
    # rows are read in blocks of 64 (the last repeated) and the choice is
    # cut on the host, so that no program's shape follows a sample's length
    block = np.resize(rows, -(-len(rows) // 64) * 64)
    with jax.default_matmul_precision("highest"):
        logits, chosen = forward(
            weights, jnp.asarray(padded), float(eps), int(rope), int(k),
            float(scale), held, forced, fault, bool(bf16),
        )
        return (
            np.concatenate(
                [np.asarray(logits[block[i: i + 64]]) for i in range(0, len(block), 64)]
            )[: len(rows)],
            np.asarray(chosen)[:, :n],
        )
