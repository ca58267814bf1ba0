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
chunks of C tokens, each cut into sub-blocks of 16 so that all but the
[16, 16, d] decays on a chunk's diagonal is matmuls, its triangular system
included) and ONE STEP (`kda_step`, over the per-slot state
`serving/kv_cache.py` keeps). Which caller takes which form: the
operator's lowering (`_lower_linear_attention`, differentiated under
`jax.checkpoint`) takes `kda_chunked`, always; a serving prefill takes
`kda_chunked_rows`, which is `ops/pallas/kda_scan.py` (forward only: the
same chunked algorithm as one Mosaic call whose output block is the
admitted request's row of the per-slot state) on a TPU at lane-tile heads
and `kda_chunked` with a scatter anywhere else; a serving decode step
takes `kda_step_live`, which is `ops/pallas/kda_step.py` under the same
gate and `kda_step` with a `where` anywhere else. `kda_chunked` is the
scan kernel's reference and `kda_step` both kernels'. Weights, in order: Wq,
Wk, Wv [e, H d]; the three convolutions [K, H d] (taps-major: a tap is
one dense row of channels); Wfa [e, r], Wfb
[r, H d], dt_bias [H d], A_log [H]; Wb [e, H]; Wga [e, r], Wgb [r, H d];
the gain [d] of the output norm, one for all heads; Wo [H d, e]. No
biases but dt_bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.core.parallel_tensor import ParallelDim, ParallelTensorShape
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.ops.registry import mm_operands, mm_out_dtype, register_op

_MM = dict(preferred_element_type=jnp.float32)
# a matmul that stands where the recurrence had an elementwise float32 sum
_EXACT = dict(_MM, precision=jax.lax.Precision.HIGHEST)
_SUB = 16  # tokens of a sub-block of a chunk (`kda_chunked`)


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


def _kda_chunk_terms(qc, kc, vc, gc, bc, sub):
    """What a chunk of `kda_chunked` needs before it meets the carried
    state: qc, kc, vc, gc [..., C, d], bc [..., C] -> (tril(B) [..., C, C];
    w = T (beta k exp G), u_v = T (beta v), q exp G, k exp(G_last - G)
    [..., C, d]; exp G_last [..., d]), with T = (I + diag(beta)
    tril(A, -1))^-1, in sub-blocks of `sub` tokens (C a multiple)."""
    lead, (c, d) = kc.shape[:-2], kc.shape[-2:]
    m = c // sub
    run = jnp.cumsum(gc, axis=-2)  # G

    def blocks(t):  # [..., C, ...] -> [..., m, sub, ...]
        return t.reshape(lead + (m, sub) + t.shape[len(lead) + 1:])

    runb, kb, qb = blocks(run), blocks(kc), blocks(qc)
    # inside a sub-block, elementwise: exp(G_t[c] - G_j[c]) for j <= t
    kd = kb[..., None, :, :] * jnp.exp(
        jnp.minimum(runb[..., :, None, :] - runb[..., None, :, :], 0.0)
    )
    a_in = jnp.sum(kb[..., :, None, :] * kd, -1)  # [..., m, sub, sub]
    b_in = jnp.sum(qb[..., :, None, :] * kd, -1)
    # between sub-blocks, one matmul through G_ref, G at the last token
    # before the row's sub-block: exp(G_t - G_ref) exp(G_ref - G_j)
    ref = jnp.pad(
        runb[..., :-1, -1:, :], [(0, 0)] * len(lead) + [(1, 0), (0, 0), (0, 0)]
    )
    near = jnp.exp(runb - ref)
    far = kc[..., None, :, :] * jnp.exp(
        jnp.minimum(ref - run[..., None, :, :], 0.0)
    )
    out = jnp.einsum(
        "...tc,...jc->...tj", jnp.concatenate([kb * near, qb * near], -2), far,
        **_EXACT,
    )  # [..., m, 2 sub, C]: A's rows over B's
    # masks as constants [m, sub, C]: the compiler computes nothing for them
    row, col = np.arange(c).reshape(m, sub, 1), np.arange(c)
    before, same = col // sub < row // sub, col // sub == row // sub
    bm = jnp.where(
        before, out[..., sub:, :],
        jnp.where(same & (col <= row), jnp.tile(b_in, m), 0.0),
    ).reshape(lead + (c, c))
    # T: each sub-block's own inverse by substitution, row by row, then
    # block forward substitution over the sub-blocks
    beta = blocks(bc)[..., None]
    l_in = beta * jnp.where(np.tril(np.ones((sub, sub), bool), -1), a_in, 0.0)
    l_out = beta * jnp.where(before, out[..., :sub, :], 0.0)
    inv = jnp.broadcast_to(jnp.eye(sub), l_in.shape)
    for r in range(1, sub):
        inv = inv.at[..., r, :].add(
            -jnp.sum(l_in[..., r, :r, None] * inv[..., :r, :], -2)
        )
    eye, t = np.eye(c, dtype=np.float32), jnp.zeros(lead + (c, c))
    for i in range(m):
        rows = slice(i * sub, (i + 1) * sub)
        rest = eye[rows]
        if i:
            rest = rest - jnp.matmul(l_out[..., i, :, :], t, **_EXACT)
        t = t.at[..., rows, :].set(jnp.matmul(inv[..., i, :, :], rest, **_EXACT))
    grown = jnp.exp(run)
    wu = jnp.matmul(
        t, bc[..., None] * jnp.concatenate([kc * grown, vc], -1), **_EXACT
    )
    return (
        bm, wu[..., :d], wu[..., d:], qc * grown,
        kc * jnp.exp(run[..., -1:, :] - run), grown[..., -1, :],
    )


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
    Every decay is the exponential of a NON-POSITIVE difference, never a
    quotient of two exponentiated sums: exp(-G_j) overflows float32 after
    a few tokens at the decays A_log allows. A chunk is cut into
    sub-blocks of `_SUB` tokens (one, where `chunk` is no multiple). Only
    the diagonal [sub, sub] blocks of A and B are elementwise sums over
    [sub, sub, d] decays; the blocks below them are a matmul of
    k_t exp(G_t - G_ref) with k_j exp(G_ref - G_j), G_ref the G of the
    last token before the row's sub-block: it lies between the two, so
    both exponents stay non-positive. The system is solved by matmuls
    too: T = (I + diag(beta) tril(A, -1))^-1 from the diagonal blocks' own
    inverses (by substitution: a series in the powers of tril(A, -1)
    loses every digit where keys repeat) and block forward substitution,
    so u = T beta v - (T beta k exp G) S_0. The matmuls that stand where
    elementwise float32 sums stood (A's and B's blocks, T and its two
    products) run at `HIGHEST` whatever the ambient precision; the three
    products with the state and B u at the ambient one, as they always
    did. A chunk's terms do not read the state, yet stay in the scan:
    computed for all chunks at once they leave the chip's fast memory
    and cost more, and for a few at once no less (PERF.md, PR 41).
    Float32 throughout. Differentiable; a
    chunk's decays are recomputed in the backward pass, not kept."""
    b, s, h, d = q.shape
    n = s // chunk
    sub = _SUB if chunk % _SUB == 0 else chunk
    f32 = jnp.float32

    def chunks(t):  # [b, s, H, ...] -> [n, b, H, C, ...]
        t = t.astype(f32).reshape((b, n, chunk, h) + t.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    resets = (
        jnp.zeros((n, b), bool) if reset is None else jnp.moveaxis(reset, 1, 0)
    )

    @jax.checkpoint
    def one(carry, xs):
        *raw, fresh = xs
        bm, w, uv, qg, left, last = _kda_chunk_terms(*raw, sub)
        s0 = jnp.where(fresh[:, None, None, None], 0.0, carry)
        u = uv - jnp.einsum("bhtc,bhcv->bhtv", w, s0, **_MM)
        o = jnp.einsum("bhtc,bhcv->bhtv", qg, s0, **_MM) + jnp.einsum(
            "bhtj,bhjv->bhtv", bm, u, **_MM
        )
        s1 = last[..., None] * s0 + jnp.einsum("bhtc,bhtv->bhcv", left, u, **_MM)
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


def kda_step_live(q, k, v, g, beta, state, active, ctx=None):
    """`kda_step` as a serving decode step wants it: the rows whose
    `active` flag (bool [b]) is set advance, every other row of the state
    comes back bit-equal -> (o [b, H, d], the new state, whether the
    kernel made it). One algorithm, two makers, and the choice reads what
    it can see: the platform, the state's type, the static shapes and
    whether the arrays are one device's (`kda_step.use_kernel`). On a TPU
    at lane-tile heads `ops/pallas/kda_step.py` visits the live rows
    only, in place; anything else takes `kda_step` over every row and a
    `where`. An idle row's output is the kernel's zeros or `kda_step`'s
    of its stale state: nobody reads it."""
    from flexflow_tpu.ops.pallas import kda_step as kernel

    # a Mosaic kernel is not partitioned over a mesh: one device's arrays
    alone = ctx is None or ctx.mesh is None or ctx.mesh.size == 1
    if alone and kernel.use_kernel(state.shape[1], state.shape[2], state.dtype):
        with jax.named_scope("kda.step"):
            return (*kernel.kda_step_rows(q, k, v, g, beta, state, active), True)
    o, new = kda_step(q, k, v, g, beta, state)
    with jax.named_scope("kda.step"):
        return o, jnp.where(active[:, None, None, None], new, state), False


def kda_chunked_rows(
    q, k, v, g, beta, rows, live, fresh, slots, last_at, chunk, ctx=None
):
    """`kda_chunked` as a serving prefill wants it: ONE packed row of
    requests laid in order, each beginning on a chunk boundary and from
    the zero state, each one's state after its last chunk written whole to
    its slot's row of the per-slot state (what the row held is never
    read) -> (o [1, T, H, d], the new rows, whether the kernel made
    them). q, k, v, g [1, T, H, d], beta [1, T, H]; rows [slots, H, d, d];
    live bool [T]: the tokens that are someone's (every other is made a
    no-op); fresh bool [T / chunk]: the chunks that start a request;
    slots, last_at int32 [requests]: each request's slot (the rows past
    the last name a slot out of range, which is dropped) and where its
    last token stands. One algorithm, two makers, and the choice reads
    what it can see, as `kda_step_live`'s: on a TPU at lane-tile heads
    `ops/pallas/kda_scan.py` carries a head's state in fast memory from
    chunk to chunk and its output block IS the slot's row; anything else
    takes `kda_chunked` (masks over the row, a state a chunk) and a
    scatter."""
    from flexflow_tpu.ops.pallas import kda_scan as kernel

    alone = ctx is None or ctx.mesh is None or ctx.mesh.size == 1
    if alone and kernel.use_kernel(rows.shape[1], rows.shape[2], chunk, rows.dtype):
        with jax.named_scope("kda.scan"):
            o, new = kernel.kda_scan_rows(
                q[0], k[0], v[0], g[0], beta[0], rows, live, fresh[0], slots,
                last_at, chunk=chunk,
            )
            return o[None], new, True
    with jax.named_scope("kda.scan"):
        on = live[None, :, None]
        k = jnp.where(on[..., None], k, 0)
        g = jnp.where(on[..., None], g, 0)
        beta = jnp.where(on, beta, 0)
    o, states = kda_chunked(q, k, v, g, beta, jnp.zeros_like(rows[:1]), fresh, chunk)
    with jax.named_scope("kda.scan"):
        new = rows.at[slots].set(states[0, last_at // chunk], mode="drop")
    return o, new, False


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
