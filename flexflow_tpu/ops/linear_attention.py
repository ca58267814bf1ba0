"""Gated delta-rule linear attention (Kimi Delta Attention, KDA: the
linear-attention layer of Kimi-Linear). A sequence keeps a FIXED-SIZE
state instead of rows a token: per head a matrix S [d_k, d_v] that every
token first decays, per head AND per key channel, and then corrects by
the delta rule, and the last `conv_kernel - 1` inputs of three short
causal depthwise convolutions. No reference counterpart. With u the
layer's input (already normalised) and H heads of d = `head_dim`:

    q~, k~, v~ = Wq u, Wk u, Wv u                     each H * d wide
    q', k', v' = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                 conv: kernel K, depthwise, causal, no bias
    q = l2norm(q'_h) * d^-0.5,  k = l2norm(k'_h),  v = v'_h
    g = -exp(A_log_h) * softplus(Wfb (Wfa u) + dt_bias)   [H, d], <= 0
    beta = sigmoid(Wb u)                                  [H]
    S' = diag(exp g) S;  S = S' + beta k (v - k^T S')^T;  o = S^T q
    z = Wgb (Wga u);  y_h = rmsnorm(o_h; gain) * sigmoid(z_h)
    out = Wo concat_h(y_h)

One function's two computations out of shared helpers, as "Latent
attention" (ops/attention.py): CHUNKED (`kda_chunked`: a `lax.scan` over
chunks of C tokens with matmuls inside, the operator's lowering and a
serving prefill) and ONE STEP (`kda_step`: a serving decode step, over
the per-slot state `serving/kv_cache.py` keeps). Weights, in order: Wq,
Wk, Wv [e, H d]; the three convolutions [K, H d] (taps-major: a tap is
one dense row of channels); Wfa [e, r], Wfb
[r, H d], dt_bias [H d], A_log [H]; Wb [e, H]; Wga [e, r], Wgb [r, H d];
the gain [d] of the output norm, one for all heads; Wo [H d, e]. No
biases but dt_bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.core.parallel_tensor import ParallelDim, ParallelTensorShape
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.ops.registry import mm_operands, mm_out_dtype, register_op

_MM = dict(preferred_element_type=jnp.float32)


def _kda_dims(params):
    """(heads, head_dim, conv kernel, rank of the two low-rank pairs)."""
    return (
        params["num_heads"], params["head_dim"], params["conv_kernel"],
        params["gate_rank"],
    )


def kda_state_shapes(params):
    """What one sequence keeps of a node between steps, by name: the
    delta-rule state of every head and the convolutions' last K - 1
    inputs (the q, k and v streams side by side). The one place the
    per-slot state is sized (`serving/kv_cache.py:state_row`)."""
    h, d, kernel, _ = _kda_dims(params)
    return (("S", (h, d, d)), ("conv", (kernel - 1, 3 * h * d)))


def _infer_linear_attention(input_shapes, params):
    (x,) = input_shapes
    e = params["embed_dim"]
    h, d, kernel, r = _kda_dims(params)
    if any(dim.is_replica_dim for dim in x.dims) or any(
        dim.degree > 1 for dim in x.dims[1:]
    ):
        raise ValueError(
            "linear_attention: only the batch dim may be partitioned (the "
            "recurrence runs along the sequence, and the heads are not "
            "sharded yet)"
        )
    if x.dims[-1].size != e or kernel < 2:
        raise ValueError(
            f"linear_attention: input width {x.dims[-1].size} != {e}, or a "
            f"convolution kernel of {kernel} < 2"
        )
    dt = x.dtype

    def shape(*sizes):
        return ParallelTensorShape(tuple(ParallelDim(s) for s in sizes), dt)

    hd = h * d
    weights = (
        shape(e, hd), shape(e, hd), shape(e, hd),
        shape(kernel, hd), shape(kernel, hd), shape(kernel, hd),
        shape(e, r), shape(r, hd), shape(hd), shape(h),
        shape(e, h),
        shape(e, r), shape(r, hd),
        shape(d), shape(hd, e),
    )
    return (x,), weights


def kda_project(x, ws, params, ctx):
    """x [b, s, e] -> (qkv [b, s, 3 H d]: the three streams BEFORE their
    convolutions, side by side; g [b, s, H, d]: the log decay, <= 0;
    beta [b, s, H]; z [b, s, H, d]: the output gate before its sigmoid).
    The decay and the write strength are float32 whatever the model's
    precision: they are exponentiated over a whole sequence."""
    h, d, _, _ = _kda_dims(params)
    with jax.named_scope("kda.project"):
        xm, wq, wk, wv, wfa, wb, wga = mm_operands(
            ctx, x, ws[0], ws[1], ws[2], ws[6], ws[10], ws[11]
        )
        cdt = xm.dtype
        qkv = jnp.concatenate(
            [jnp.matmul(xm, w, **_MM) for w in (wq, wk, wv)], axis=-1
        ).astype(cdt)
        fa, wfb = mm_operands(ctx, jnp.matmul(xm, wfa, **_MM).astype(cdt), ws[7])
        f = jnp.matmul(fa, wfb, **_MM) + ws[8].astype(jnp.float32)
        rate = jnp.exp(ws[9].astype(jnp.float32))[:, None]
        g = -rate * jax.nn.softplus(f).reshape(f.shape[:-1] + (h, d))
        beta = jax.nn.sigmoid(jnp.matmul(xm, wb, **_MM))
        ga, wgb = mm_operands(ctx, jnp.matmul(xm, wga, **_MM).astype(cdt), ws[12])
        z = jnp.matmul(ga, wgb, **_MM).reshape(f.shape[:-1] + (h, d))
        return qkv, g, beta, z.astype(cdt)


def kda_conv(qkv, tails, ws, params, taps=None):
    """The three short convolutions, silu, and the norms of q and k.
    qkv [b, s, 3 H d] (`kda_project`); tails [b, K - 1, 3 H d]: the K - 1
    inputs that stood before the first (zeros at a sequence's start);
    taps bool [b, s, K - 1] or None: whether the input 1 .. K - 1 tokens
    BEFORE each token (nearest first) is its own sequence's (a packed
    row's tokens do not see another prompt's). Returns (q, k, v
    [b, s, H, d], q and k l2-normalised and q scaled by d^-0.5; the new
    tails [b, K - 1, 3 H d]: the last K - 1 inputs)."""
    h, d, kernel, _ = _kda_dims(params)
    with jax.named_scope("kda.conv"):
        s = qkv.shape[1]
        xs = jnp.concatenate([tails.astype(qkv.dtype), qkv], axis=1)
        w = jnp.concatenate([ws[3], ws[4], ws[5]], axis=1).astype(jnp.float32)
        # tap j of the kernel meets the input kernel - 1 - j tokens back
        acc = qkv.astype(jnp.float32) * w[kernel - 1]
        for back in range(1, kernel):
            past = xs[:, kernel - 1 - back: kernel - 1 - back + s]
            past = past.astype(jnp.float32) * w[kernel - 1 - back]
            if taps is not None:
                past = jnp.where(taps[..., back - 1, None], past, 0.0)
            acc = acc + past
        out = jax.nn.silu(acc).reshape(acc.shape[:2] + (3, h, d))
        q, k, v = out[:, :, 0], out[:, :, 1], out[:, :, 2]

        def unit(t):
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        q = (unit(q) * d ** -0.5).astype(qkv.dtype)
        return q, unit(k).astype(qkv.dtype), v.astype(qkv.dtype), xs[:, s:]


def kda_chunked(q, k, v, g, beta, state, reset=None, chunk=64):
    """The recurrence over a sequence, in chunks of `chunk` tokens: q, k, v,
    g [b, s, H, d], beta [b, s, H], state [b, H, d, d] (the state before
    the first token), s a multiple of `chunk`. reset bool [b, s / chunk]
    or None: where set, the chunk starts from the ZERO state whatever was
    carried (a packed row's next prompt). A token with beta = 0, g = 0 and
    k = 0 leaves the state as it was (padding). Returns (o [b, s, H, d],
    states [b, s / chunk, H, d, d]: the state after every chunk).

    Inside a chunk, with G the running sum of g (per channel) and u the
    corrected values, S_t = diag(exp G_t) S_0 + sum_{j <= t}
    diag(exp(G_t - G_j)) k_j u_j^T, so
        (I + diag(beta) tril(A, -1)) u = beta (v - (k exp G) S_0),
        o = (q exp G) S_0 + tril(B) u,
        A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c]),  B likewise with q_t.
    Every decay is the exponential of a NON-POSITIVE difference G_t - G_j
    (j <= t), never a quotient of two exponentiated sums: exp(-G_j)
    overflows float32 after a few tokens at the decays A_log allows.
    Float32 throughout. Differentiable; a chunk's [C, C, d] decays are
    recomputed in the backward pass, not kept for every chunk."""
    b, s, h, d = q.shape
    n = s // chunk
    f32 = jnp.float32

    def chunks(t):  # [b, s, H, ...] -> [n, b, H, C, ...]
        t = t.astype(f32).reshape((b, n, chunk, h) + t.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    resets = (
        jnp.zeros((n, b), bool) if reset is None else jnp.moveaxis(reset, 1, 0)
    )
    low = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    @jax.checkpoint
    def one(carry, xs):
        qc, kc, vc, gc, bc, fresh = xs
        s0 = jnp.where(fresh[:, None, None, None], 0.0, carry)
        run = jnp.cumsum(gc, axis=2)  # G [b, H, C, d]
        # decay[t, j, c] = exp(G_t[c] - G_j[c]) for j <= t (else unused)
        decay = jnp.exp(
            jnp.minimum(run[:, :, :, None, :] - run[:, :, None, :, :], 0.0)
        )
        kd = kc[:, :, None, :, :] * decay
        a = jnp.where(strict, jnp.sum(kc[:, :, :, None, :] * kd, -1), 0.0)
        bm = jnp.where(low, jnp.sum(qc[:, :, :, None, :] * kd, -1), 0.0)
        grown = jnp.exp(run)
        rhs = bc[..., None] * (
            vc - jnp.einsum("bhtc,bhcv->bhtv", kc * grown, s0, **_MM)
        )
        u = jax.scipy.linalg.solve_triangular(
            jnp.eye(chunk, dtype=f32) + bc[..., None] * a, rhs,
            lower=True, unit_diagonal=True,
        )
        o = jnp.einsum("bhtc,bhcv->bhtv", qc * grown, s0, **_MM) + jnp.einsum(
            "bhtj,bhjv->bhtv", bm, u, **_MM
        )
        left = jnp.exp(run[:, :, -1:, :] - run)  # what is left at the end
        s1 = grown[:, :, -1, :, None] * s0 + jnp.einsum(
            "bhtc,bhtv->bhcv", kc * left, u, **_MM
        )
        return s1, (o, s1)

    with jax.named_scope("kda.scan"):
        _, (o, states) = jax.lax.scan(
            one, state.astype(f32),
            (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta), resets),
        )
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, s, h, d)
        return o.astype(q.dtype), jnp.moveaxis(states, 0, 1)


def kda_step(q, k, v, g, beta, state):
    """The recurrence for ONE token a row: q, k, v, g [b, H, d], beta
    [b, H], state [b, H, d, d] -> (o [b, H, d], the new state). Sums over
    the state's own float32 elements, no matmul: a step reads and writes
    the state once, which is all its time."""
    with jax.named_scope("kda.step"):
        f32 = jnp.float32
        kf = k.astype(f32)[..., None]
        decayed = jnp.exp(g.astype(f32))[..., None] * state
        u = beta.astype(f32)[..., None] * (
            v.astype(f32) - jnp.sum(kf * decayed, axis=-2)
        )
        new = decayed + kf * u[..., None, :]
        o = jnp.sum(q.astype(f32)[..., None] * new, axis=-2)
        return o.astype(q.dtype), new


def kda_out(o, z, ws, params, ctx, out_dtype):
    """o, z [b, s, H, d] -> [b, s, e]: each head's output normalised
    (one gain for all heads), gated by sigmoid(z), and projected."""
    from flexflow_tpu.ops.core_ops import rms_normalize

    with jax.named_scope("kda.out"):
        y = rms_normalize(o, ws[13], params.get("eps", 1e-5)) * jax.nn.sigmoid(
            z.astype(jnp.float32)
        ).astype(o.dtype)
        y, wo = mm_operands(ctx, y.reshape(y.shape[:2] + (-1,)), ws[14])
        return jnp.matmul(y, wo, **_MM).astype(mm_out_dtype(ctx, out_dtype))


def kda_pad_to_chunks(arrays, chunk):
    """Each of `arrays` [b, s, ...] with zeros behind it up to a whole
    number of chunks: zeros in k, g and beta are tokens that leave the
    state alone (`kda_chunked`)."""
    pad = -arrays[0].shape[1] % chunk
    if not pad:
        return arrays
    return tuple(
        jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in arrays
    )


def _lower_linear_attention(params):
    h, d, kernel, _ = _kda_dims(params)
    chunk = params["chunk"]

    def fn(ins, ws, ctx):
        (x,) = ins
        b, s = x.shape[:2]
        qkv, g, beta, z = kda_project(x, ws, params, ctx)
        tails = jnp.zeros((b, kernel - 1, qkv.shape[-1]), qkv.dtype)
        q, k, v, _ = kda_conv(qkv, tails, ws, params)
        o, _ = kda_chunked(
            *kda_pad_to_chunks((q, k, v, g, beta), chunk),
            jnp.zeros((b, h, d, d), jnp.float32), chunk=chunk,
        )
        return [kda_out(o[:, :s], z, ws, params, ctx, x.dtype)]

    return fn


def _flops_linear_attention(input_shapes, params):
    (x,) = input_shapes
    b, s, e = x.logical_sizes[-3:]
    h, d, kernel, r = _kda_dims(params)
    hd = h * d
    proj = e * (3 * hd + 2 * r + h) + 2 * r * hd + hd * e + 3 * hd * kernel
    # a token and head of a chunk of C: the decayed Gram rows A and B
    # (2 C d each), the solve (C d), B u (2 C d), three products with
    # the [d, d] state (benchmarks/lib/kda_counts.py counts the same)
    c = params["chunk"]
    scan = h * (7 * c * d + 6 * d * d)
    return b * s * (2.0 * proj + scan)


register_op(
    OperatorType.LINEAR_ATTENTION, _infer_linear_attention,
    _lower_linear_attention, _flops_linear_attention,
)
