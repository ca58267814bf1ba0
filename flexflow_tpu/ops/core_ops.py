"""Core compute operators: parallel-shape inference + JAX lowerings.

Covers the reference's op set (SURVEY §2.2; reference: src/ops/*.cc):
linear, conv2d, pool2d, batch/layer-norm, embedding, dropout, element-wise
unary/binary, batch-matmul, softmax, concat/split/reshape/transpose/reverse/
flat/cast, reduce/mean. Attention and MoE ops live in sibling modules.

Layout conventions (TPU-idiomatic, diverging from the reference's NCHW):
  * images are NHWC, conv kernels are HWIO — XLA's native TPU layouts;
  * linear kernels are [in_features, out_features].

Tensor-parallel semantics follow the reference's replica-dim trick
(reference: linear.cc:969 LinearParams::solve_dims):
  * a replica dim on a Linear/Conv/Embedding *input* (inserted by a
    Replicate parallel op) becomes output-channel partitioning of the
    weight and a partitioned feature dim on the output;
  * partitioning the contraction dim of the input shards the weight's
    input dim and yields a replica dim on the *output* that a downstream
    Reduction parallel op must sum.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.core.parallel_tensor import ParallelDim, ParallelTensorShape
from flexflow_tpu.core.types import ActiMode, AggrMode, DataType, OperatorType, PoolType
from flexflow_tpu.ops.registry import mm_operands, mm_out_dtype, register_op


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _split_replica(shape: ParallelTensorShape):
    """Split leading replica dims from logical dims."""
    rep = [d for d in shape.dims if d.is_replica_dim]
    logical = [d for d in shape.dims if not d.is_replica_dim]
    return rep, logical


def _apply_activation(x, act: ActiMode):
    if act is None or act == ActiMode.NONE:
        return x
    return {
        ActiMode.RELU: jax.nn.relu,
        ActiMode.SIGMOID: jax.nn.sigmoid,
        ActiMode.TANH: jnp.tanh,
        ActiMode.GELU: lambda v: jax.nn.gelu(v, approximate=False),
    }[act](x)


# ---------------------------------------------------------------------------
# graph sources
# ---------------------------------------------------------------------------


def _infer_noop(input_shapes, params):
    if input_shapes:
        return tuple(input_shapes), ()
    return (params["shape"],), ()


register_op(OperatorType.NOOP, _infer_noop, lambda p: lambda ins, ws, ctx: list(ins))
register_op(OperatorType.INPUT, _infer_noop, lambda p: lambda ins, ws, ctx: list(ins))
register_op(OperatorType.WEIGHT, _infer_noop, lambda p: lambda ins, ws, ctx: list(ins))


# ---------------------------------------------------------------------------
# Linear (reference: src/ops/linear.cc, kernels/linear_kernels.cu)
# ---------------------------------------------------------------------------


def _infer_linear(input_shapes, params):
    (x,) = input_shapes
    out_features = params["out_features"]
    use_bias = params.get("use_bias", True)
    dtype = params.get("dtype", x.dtype)

    rep, logical = _split_replica(x)
    if len(rep) > 1:
        raise ValueError("linear: at most one input replica dim supported")
    in_dim = logical[-1]
    batch_dims = logical[:-1]

    r_deg = rep[0].degree if rep else 1          # -> out-channel parallelism
    r_idx = rep[0].parallel_idx if rep else -1
    k_deg = in_dim.degree                        # -> contraction parallelism
    k_idx = in_dim.parallel_idx

    if out_features % r_deg != 0:
        raise ValueError("linear: replica degree must divide out_features")

    out_dims = []
    if k_deg > 1:
        # partial sums: replica dim a downstream Reduction must fold
        out_dims.append(ParallelDim(k_deg, k_deg, k_idx, True))
    out_dims.extend(batch_dims)
    out_dims.append(ParallelDim(out_features, r_deg, r_idx))
    out = ParallelTensorShape(tuple(out_dims), dtype)

    kernel = ParallelTensorShape(
        (
            ParallelDim(in_dim.size, k_deg, k_idx),
            ParallelDim(out_features, r_deg, r_idx),
        ),
        dtype,
    )
    weights = [kernel]
    if use_bias:
        weights.append(
            ParallelTensorShape((ParallelDim(out_features, r_deg, r_idx),), dtype)
        )
    return (out,), tuple(weights)


def _lower_linear(params):
    act = params.get("activation", ActiMode.NONE)
    use_bias = params.get("use_bias", True)

    def fn(ins, ws, ctx):
        (x,) = ins
        kernel = ws[0]
        xm, km = mm_operands(ctx, x, kernel)
        y = jnp.matmul(xm, km, preferred_element_type=jnp.float32)
        y = y.astype(mm_out_dtype(ctx, kernel.dtype))
        if use_bias:
            y = y + ws[1].astype(y.dtype)
        return [_apply_activation(y, act)]

    return fn


def _flops_linear(input_shapes, params):
    x = input_shapes[0]
    batch = x.volume() // x.logical_sizes[-1]
    return 2.0 * batch * x.logical_sizes[-1] * params["out_features"]


register_op(OperatorType.LINEAR, _infer_linear, _lower_linear, _flops_linear)


# ---------------------------------------------------------------------------
# Conv2D (reference: src/ops/conv_2d.cc) — NHWC / HWIO
# ---------------------------------------------------------------------------


def _pad2(pad):
    """Normalize a padding param: int (symmetric) or (lo, hi) tuple."""
    if isinstance(pad, (tuple, list)):
        lo, hi = pad
        return int(lo), int(hi)
    return int(pad), int(pad)


def _conv_out_size(in_size, kernel, stride, pad):
    lo, hi = _pad2(pad)
    return (in_size + lo + hi - kernel) // stride + 1


def _infer_conv2d(input_shapes, params):
    (x,) = input_shapes
    rep, logical = _split_replica(x)
    n, h, w, c = logical
    kh, kw = params["kernel_h"], params["kernel_w"]
    sh, sw = params["stride_h"], params["stride_w"]
    ph, pw = params["padding_h"], params["padding_w"]
    out_channels = params["out_channels"]
    groups = params.get("groups", 1)
    use_bias = params.get("use_bias", True)
    dtype = params.get("dtype", x.dtype)

    r_deg = rep[0].degree if rep else 1
    r_idx = rep[0].parallel_idx if rep else -1
    if c.degree > 1:
        raise ValueError(
            "conv2d: partitioned input channels need a Reduction rewrite"
        )

    oh = _conv_out_size(h.size, kh, sh, ph)
    ow = _conv_out_size(w.size, kw, sw, pw)
    out = ParallelTensorShape(
        (
            n,
            ParallelDim(oh, h.degree, h.parallel_idx),
            ParallelDim(ow, w.degree, w.parallel_idx),
            ParallelDim(out_channels, r_deg, r_idx),
        ),
        dtype,
    )
    kernel = ParallelTensorShape(
        (
            ParallelDim(kh),
            ParallelDim(kw),
            ParallelDim(c.size // groups),
            ParallelDim(out_channels, r_deg, r_idx),
        ),
        dtype,
    )
    weights = [kernel]
    if use_bias:
        weights.append(
            ParallelTensorShape((ParallelDim(out_channels, r_deg, r_idx),), dtype)
        )
    return (out,), tuple(weights)


def _lower_conv2d(params):
    sh, sw = params["stride_h"], params["stride_w"]
    ph, pw = _pad2(params["padding_h"]), _pad2(params["padding_w"])
    groups = params.get("groups", 1)
    act = params.get("activation", ActiMode.NONE)
    use_bias = params.get("use_bias", True)

    def fn(ins, ws, ctx):
        (x,) = ins
        kernel = ws[0]
        xm, km = mm_operands(ctx, x, kernel)
        # bf16 operands skip preferred_element_type=f32: the conv VJP
        # transposes a f32 cotangent onto the bf16 operand and dies on the
        # dtype mismatch (unlike dot_general's). MXU conv accumulation is
        # f32 internally either way; only the pre-upcast rounding differs.
        pet = jnp.float32 if xm.dtype == jnp.float32 else None
        y = jax.lax.conv_general_dilated(
            xm,
            km,
            window_strides=(sh, sw),
            padding=[ph, pw],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
            preferred_element_type=pet,
        ).astype(mm_out_dtype(ctx, kernel.dtype))
        if use_bias:
            y = y + ws[1].astype(y.dtype)
        return [_apply_activation(y, act)]

    return fn


def _flops_conv2d(input_shapes, params):
    (x,) = input_shapes
    n, h, w, c = x.logical_sizes
    oh = _conv_out_size(h, params["kernel_h"], params["stride_h"], params["padding_h"])
    ow = _conv_out_size(w, params["kernel_w"], params["stride_w"], params["padding_w"])
    groups = params.get("groups", 1)
    return (
        2.0 * n * oh * ow * params["out_channels"]
        * params["kernel_h"] * params["kernel_w"] * (c // groups)
    )


register_op(OperatorType.CONV2D, _infer_conv2d, _lower_conv2d, _flops_conv2d)


# ---------------------------------------------------------------------------
# Pool2D (reference: src/ops/pool_2d.cc)
# ---------------------------------------------------------------------------


def _infer_pool2d(pool_type):
    def infer(input_shapes, params):
        (x,) = input_shapes
        rep, logical = _split_replica(x)
        n, h, w, c = logical
        kh, kw = params["kernel_h"], params["kernel_w"]
        sh, sw = params["stride_h"], params["stride_w"]
        ph, pw = params["padding_h"], params["padding_w"]
        oh = _conv_out_size(h.size, kh, sh, ph)
        ow = _conv_out_size(w.size, kw, sw, pw)
        out = ParallelTensorShape(
            tuple(rep)
            + (n, ParallelDim(oh), ParallelDim(ow), c),
            x.dtype,
        )
        return (out,), ()

    return infer


def _lower_pool2d(pool_type):
    def lower(params):
        kh, kw = params["kernel_h"], params["kernel_w"]
        sh, sw = params["stride_h"], params["stride_w"]
        ph, pw = _pad2(params["padding_h"]), _pad2(params["padding_w"])
        act = params.get("activation", ActiMode.NONE)

        def fn(ins, ws, ctx):
            (x,) = ins
            pad = [(0, 0), ph, pw, (0, 0)]
            window = (1, kh, kw, 1)
            strides = (1, sh, sw, 1)
            if pool_type == PoolType.MAX:
                init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
                y = jax.lax.reduce_window(
                    x, init, jax.lax.max, window, strides, pad,
                )
            else:
                s = jax.lax.reduce_window(
                    x, 0.0, jax.lax.add, window, strides, pad,
                )
                include_pad = params.get("count_include_pad", True)
                if not include_pad and any(p != (0, 0) for p in (ph, pw)):
                    # divide by the in-bounds count only (keras/TF 'same'
                    # and ONNX default avg-pool semantics)
                    ones = jnp.ones(x.shape[1:3], x.dtype)[None, :, :, None]
                    cnt = jax.lax.reduce_window(
                        ones, 0.0, jax.lax.add, window, strides, pad,
                    )
                    y = s / cnt
                else:
                    # full-kernel-area divisor (torch AvgPool2d default)
                    y = s / (kh * kw)
            return [_apply_activation(y, act)]

        return fn

    return lower


register_op(OperatorType.POOL2D_MAX, _infer_pool2d(PoolType.MAX), _lower_pool2d(PoolType.MAX))
register_op(OperatorType.POOL2D_AVG, _infer_pool2d(PoolType.AVG), _lower_pool2d(PoolType.AVG))


# ---------------------------------------------------------------------------
# Normalization (reference: src/ops/batch_norm.cc, layer_norm.cc)
# ---------------------------------------------------------------------------


def _infer_batchnorm(input_shapes, params):
    (x,) = input_shapes
    c = x.dims[-1]
    dtype = x.dtype
    scale = ParallelTensorShape((ParallelDim(c.size, c.degree, c.parallel_idx),), dtype)
    return (x,), (scale, scale)  # gamma, beta


def _lower_batchnorm(params):
    eps = params.get("eps", 1e-5)
    act = params.get("activation", ActiMode.NONE)

    def fn(ins, ws, ctx):
        (x,) = ins
        gamma, beta = ws
        axes = tuple(range(x.ndim - 1))
        # stats accumulate in f32 even when activations flow bf16 (mixed
        # precision): bf16 mean/var over big reductions loses too much.
        # One-pass moments: var = E[(x-c)^2] - E[x-c]^2 with a CHEAP
        # per-channel anchor c (the first sample's mean). Both sums
        # accumulate in a single pass over the activation, where the
        # textbook E[(x-mean)^2] chains a second full HBM read behind the
        # mean (measured on ResNet-50 bs16, one v5e, interleaved A/B with
        # warmed alternating bursts: ~6% whole-step win,
        # scripts/ab_resnet_bn.py). The raw E[x^2]-E[x]^2 form would
        # cancel catastrophically for |mean| >> std inputs; anchoring at
        # c (within a few std of the true mean for any data whose first
        # sample resembles the batch) bounds the cancellation to
        # ((mean-c)/std)^2 relative — exactness vs the two-pass form is
        # pinned by tests/test_alignment.py and the large-offset case in
        # test_bn_large_mean_numerics.
        xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
        c = jax.lax.stop_gradient(
            jnp.mean(xf[:1], axis=axes[1:], keepdims=True)
            if xf.ndim > 1
            else jnp.zeros((1,) * xf.ndim, jnp.float32)
        )
        xs = xf - c
        mean_s = jnp.mean(xs, axis=axes, keepdims=True)
        ex2 = jnp.mean(jnp.square(xs), axis=axes, keepdims=True)
        var = jnp.maximum(ex2 - jnp.square(mean_s), 0.0)
        y = (xs - mean_s) * jax.lax.rsqrt(var + eps) * gamma + beta
        return [_apply_activation(y.astype(x.dtype), act)]

    return fn


register_op(OperatorType.BATCHNORM, _infer_batchnorm, _lower_batchnorm)


def _infer_layernorm(input_shapes, params):
    (x,) = input_shapes
    axes = params.get("axes", (x.ndim - 1,))
    elementwise_affine = params.get("elementwise_affine", True)
    for a in axes:
        if x.dims[a].degree > 1:
            raise ValueError("layernorm: normalized dim may not be partitioned")
    weights = ()
    if elementwise_affine:
        wdims = tuple(ParallelDim(x.dims[a].size) for a in axes)
        w = ParallelTensorShape(wdims, x.dtype)
        weights = (w, w)
    return (x,), weights


def _lower_layernorm(params):
    eps = params.get("eps", 1e-5)
    elementwise_affine = params.get("elementwise_affine", True)

    def fn(ins, ws, ctx):
        (x,) = ins
        axes = params.get("axes", (x.ndim - 1,))
        # f32 statistics under bf16 activation flow (mixed precision)
        xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        if elementwise_affine:
            y = y * ws[0] + ws[1]
        return [y.astype(x.dtype)]

    return fn


register_op(OperatorType.LAYERNORM, _infer_layernorm, _lower_layernorm)


def _infer_rmsnorm(input_shapes, params):
    (x,) = input_shapes
    if x.dims[-1].degree > 1:
        raise ValueError("rmsnorm: normalized dim may not be partitioned")
    gain = ParallelTensorShape((ParallelDim(x.dims[-1].size),), x.dtype)
    return (x,), (gain,)


def rms_normalize(x, gain, eps, axes=(-1,)):
    """x * rsqrt(mean(x^2) + eps) * gain, the mean over `axes`, with
    float32 statistics whatever x's dtype. Shared by the RMSNORM op and
    attention's QK-norm (ops/attention.py)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * gain).astype(x.dtype)


def _lower_rmsnorm(params):
    eps = params.get("eps", 1e-5)

    def fn(ins, ws, ctx):
        return [rms_normalize(ins[0], ws[0], eps)]

    return fn


def _flops_rmsnorm(input_shapes, params):
    return 4.0 * input_shapes[0].volume()


register_op(OperatorType.RMSNORM, _infer_rmsnorm, _lower_rmsnorm, _flops_rmsnorm)


# ---------------------------------------------------------------------------
# GatedMLP: down(silu(gate x) * (up x)), the feed-forward block of the
# decoder LMs people deploy (no reference counterpart), as ONE operator
# ---------------------------------------------------------------------------


def _infer_gated_mlp(input_shapes, params):
    (x,) = input_shapes
    d = x.dims[-1]
    if d.degree > 1:
        raise ValueError("gated_mlp: the feature dim may not be partitioned")
    f = params["width"]
    w_in = ParallelTensorShape((ParallelDim(d.size), ParallelDim(f)), x.dtype)
    w_out = ParallelTensorShape((ParallelDim(f), ParallelDim(d.size)), x.dtype)
    return (x,), (w_in, w_in, w_out)


def gated_mlp(x, ws, ctx=None):
    """x [*lead, d] -> [*lead, d] from gate / up [d, f] and down [f, d]."""
    w_gate, w_up, w_down = ws
    xm, w_gate, w_up = mm_operands(ctx, x, w_gate, w_up)
    mm = dict(preferred_element_type=jnp.float32)
    hidden = (
        jax.nn.silu(jnp.matmul(xm, w_gate, **mm)) * jnp.matmul(xm, w_up, **mm)
    ).astype(x.dtype)
    hidden, w_down = mm_operands(ctx, hidden, w_down)
    return jnp.matmul(hidden, w_down, **mm).astype(mm_out_dtype(ctx, x.dtype))


def _lower_gated_mlp(params):
    return lambda ins, ws, ctx: [gated_mlp(ins[0], ws, ctx)]


def _flops_gated_mlp(input_shapes, params):
    (x,) = input_shapes
    d = x.logical_sizes[-1]
    return 3 * 2.0 * (x.volume() // d) * d * params["width"]


register_op(
    OperatorType.GATED_MLP, _infer_gated_mlp, _lower_gated_mlp,
    _flops_gated_mlp,
)


# ---------------------------------------------------------------------------
# Embedding (reference: src/ops/embedding.cc) — key DLRM op
# ---------------------------------------------------------------------------


def _infer_embedding(input_shapes, params):
    (x,) = input_shapes  # int ids [*batch] or [*batch, bag]
    num_entries = params["num_entries"]
    out_dim = params["out_dim"]
    aggr = params.get("aggr", AggrMode.NONE)
    dtype = params.get("dtype", DataType.FLOAT)

    rep, logical = _split_replica(x)
    r_deg = rep[0].degree if rep else 1
    r_idx = rep[0].parallel_idx if rep else -1

    out_batch = list(logical)
    if aggr != AggrMode.NONE:
        out_batch = out_batch[:-1]  # bag dim folded
    out = ParallelTensorShape(
        tuple(out_batch) + (ParallelDim(out_dim, r_deg, r_idx),), dtype
    )
    weight = ParallelTensorShape(
        (ParallelDim(num_entries), ParallelDim(out_dim, r_deg, r_idx)), dtype
    )
    return (out,), (weight,)


def _lower_embedding(params):
    aggr = params.get("aggr", AggrMode.NONE)

    def fn(ins, ws, ctx):
        (ids,) = ins
        (table,) = ws
        y = jnp.take(table, ids, axis=0)
        if aggr == AggrMode.SUM:
            y = jnp.sum(y, axis=-2)
        elif aggr == AggrMode.AVG:
            y = jnp.mean(y, axis=-2)
        return [y]

    return fn


register_op(OperatorType.EMBEDDING, _infer_embedding, _lower_embedding)


# ---------------------------------------------------------------------------
# Dropout (reference: src/ops/dropout.cc)
# ---------------------------------------------------------------------------


def _infer_same(input_shapes, params):
    return (input_shapes[0],), ()


def _lower_dropout(params):
    rate = params.get("rate", 0.5)
    seed = params.get("seed", 0)

    def fn(ins, ws, ctx):
        (x,) = ins
        if not ctx.train or rate == 0.0 or ctx.rng is None:
            return [x]
        keep = 1.0 - rate
        rng = jax.random.fold_in(ctx.rng, seed) if seed else ctx.rng
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)]

    return fn


register_op(OperatorType.DROPOUT, _infer_same, _lower_dropout)


# ---------------------------------------------------------------------------
# Element-wise unary (reference: src/ops/element_unary.cc)
# ---------------------------------------------------------------------------

_UNARY_FNS = {
    OperatorType.RELU: lambda x, p: jax.nn.relu(x),
    OperatorType.SIGMOID: lambda x, p: jax.nn.sigmoid(x),
    OperatorType.TANH: lambda x, p: jnp.tanh(x),
    OperatorType.ELU: lambda x, p: jax.nn.elu(x),
    # exact (erf) form: matches torch's default and keeps frontend
    # alignment tests tight; XLA lowers erf natively on TPU
    OperatorType.GELU: lambda x, p: jax.nn.gelu(x, approximate=False),
    OperatorType.IDENTITY: lambda x, p: x,
    OperatorType.EXP: lambda x, p: jnp.exp(x),
    OperatorType.SIN: lambda x, p: jnp.sin(x),
    OperatorType.COS: lambda x, p: jnp.cos(x),
    OperatorType.POW: lambda x, p: jnp.power(x, p.get("exponent", 1.0)),
    OperatorType.RSQRT: lambda x, p: jax.lax.rsqrt(x),
    OperatorType.SCALAR_MULTIPLY: lambda x, p: x * p["scalar"],
    OperatorType.SCALAR_ADD: lambda x, p: x + p["scalar"],
    OperatorType.SCALAR_SUB: lambda x, p: x - p["scalar"],
    OperatorType.SCALAR_TRUE_DIV: lambda x, p: x / p["scalar"],
}


def _make_unary_lower(op_type):
    def lower(params):
        f = _UNARY_FNS[op_type]

        def fn(ins, ws, ctx):
            return [f(ins[0], params)]

        return fn

    return lower


for _ut in _UNARY_FNS:
    register_op(_ut, _infer_same, _make_unary_lower(_ut))


# ---------------------------------------------------------------------------
# Element-wise binary (reference: src/ops/element_binary.cc) with broadcast
# ---------------------------------------------------------------------------

_BINARY_FNS = {
    OperatorType.EW_ADD: jnp.add,
    OperatorType.EW_SUB: jnp.subtract,
    OperatorType.EW_MUL: jnp.multiply,
    OperatorType.EW_DIV: jnp.divide,
    OperatorType.EW_MAX: jnp.maximum,
    OperatorType.EW_MIN: jnp.minimum,
}


def _infer_binary(input_shapes, params):
    a, b = input_shapes
    # output shape = numpy broadcast of logical shapes; degrees from the
    # larger-ranked operand (degrees must agree where both partitioned).
    la, lb = list(a.dims), list(b.dims)
    if any(d.is_replica_dim for d in la + lb):
        raise ValueError("binary op on replica-dim tensors not supported")
    out_sizes = tuple(
        jnp.broadcast_shapes(tuple(d.size for d in la), tuple(d.size for d in lb))
    )
    big = la if len(la) >= len(lb) else lb
    small = lb if len(la) >= len(lb) else la
    offset = len(big) - len(small)
    out_dims = []
    for i, size in enumerate(out_sizes):
        d_big = big[i]
        d_small = small[i - offset] if i >= offset else None
        src = d_big
        if d_big.size != size and d_small is not None and d_small.size == size:
            src = d_small
        if (
            d_small is not None
            and d_big.size == d_small.size == size
            and d_big.degree != d_small.degree
        ):
            raise ValueError("binary op: mismatched partition degrees")
        out_dims.append(ParallelDim(size, src.degree, src.parallel_idx))
    return (ParallelTensorShape(tuple(out_dims), a.dtype),), ()


def _make_binary_lower(op_type):
    def lower(params):
        f = _BINARY_FNS[op_type]

        def fn(ins, ws, ctx):
            return [f(ins[0], ins[1])]

        return fn

    return lower


for _bt in _BINARY_FNS:
    register_op(_bt, _infer_binary, _make_binary_lower(_bt))


# ---------------------------------------------------------------------------
# BatchMatmul (reference: src/ops/batch_matmul.cc)
# ---------------------------------------------------------------------------


def _infer_batchmatmul(input_shapes, params):
    a, b = input_shapes
    *ab, m, k1 = a.dims
    *bb, k2, n = b.dims
    if k1.size != k2.size:
        raise ValueError(f"batchmatmul: contraction mismatch {k1.size} vs {k2.size}")
    if tuple(d.size for d in ab) != tuple(d.size for d in bb):
        raise ValueError("batchmatmul: batch dims mismatch")
    out = ParallelTensorShape(
        tuple(ab) + (ParallelDim(m.size, m.degree, m.parallel_idx),
                     ParallelDim(n.size, n.degree, n.parallel_idx)),
        a.dtype,
    )
    return (out,), ()


def _lower_batchmatmul(params):
    # per-iteration dynamic sequence truncation (reference: BatchMatmul's
    # a_seq_length_dim/b_seq_length_dim + FFIterationConfig.seq_length,
    # model.h:461-465; a static slice at trace time — each distinct
    # seq_length is one XLA recompile, the analog of a new Legion trace)
    a_seq_dim = params.get("a_seq_length_dim", -1)
    b_seq_dim = params.get("b_seq_length_dim", -1)

    def _truncate(x, dim, length):
        if dim < 0 or length is None or length >= x.shape[dim]:
            return x
        idx = [slice(None)] * x.ndim
        idx[dim] = slice(0, length)
        return x[tuple(idx)]

    def fn(ins, ws, ctx):
        a, b = ins
        if ctx is not None and ctx.seq_length is not None:
            a = _truncate(a, a_seq_dim, ctx.seq_length)
            b = _truncate(b, b_seq_dim, ctx.seq_length)
        am, bm = mm_operands(ctx, a, b)
        y = jnp.matmul(am, bm, preferred_element_type=jnp.float32)
        return [y.astype(mm_out_dtype(ctx, a.dtype))]

    return fn


def _flops_batchmatmul(input_shapes, params):
    a, b = input_shapes
    return 2.0 * a.volume() * b.logical_sizes[-1]


register_op(
    OperatorType.BATCHMATMUL, _infer_batchmatmul, _lower_batchmatmul, _flops_batchmatmul
)


# ---------------------------------------------------------------------------
# Softmax (reference: src/ops/softmax.cc)
# ---------------------------------------------------------------------------


def _infer_softmax(input_shapes, params):
    (x,) = input_shapes
    dim = params.get("dim", -1) % x.ndim
    if x.dims[dim].degree > 1:
        raise ValueError("softmax: softmax dim may not be partitioned")
    return (x,), ()


def _lower_softmax(params):
    def fn(ins, ws, ctx):
        dim = params.get("dim", -1)
        return [jax.nn.softmax(ins[0], axis=dim)]

    return fn


register_op(OperatorType.SOFTMAX, _infer_softmax, _lower_softmax)


# ---------------------------------------------------------------------------
# Layout ops: concat / split / reshape / transpose / reverse / flat / cast
# ---------------------------------------------------------------------------


def _infer_concat(input_shapes, params):
    axis = params["axis"] % input_shapes[0].ndim
    base = input_shapes[0]
    total = 0
    deg0 = base.dims[axis].degree
    pidx0 = base.dims[axis].parallel_idx
    for s in input_shapes:
        d = s.dims[axis]
        if d.degree != deg0 or (deg0 > 1 and d.parallel_idx != pidx0):
            # a MIX of shardings on the concat axis is not representable;
            # uniform sharding is (the combine-sink rewrite's inception
            # pattern: channel-concat of channel-sharded branches — the
            # executor lowers global arrays, GSPMD realizes the layout)
            raise ValueError(
                "concat: concat-axis sharding must match across inputs"
            )
        if deg0 > 1 and d.size % deg0 != 0:
            raise ValueError(
                "concat: sharded concat axis must divide evenly"
            )
        total += d.size
    out = base.with_dim(axis, ParallelDim(total, deg0, pidx0))
    return (out,), ()


def _lower_concat(params):
    def fn(ins, ws, ctx):
        return [jnp.concatenate(ins, axis=params["axis"])]

    return fn


register_op(OperatorType.CONCAT, _infer_concat, _lower_concat)


def _infer_split(input_shapes, params):
    (x,) = input_shapes
    axis = params["axis"] % x.ndim
    sizes = params["sizes"]
    if x.dims[axis].degree > 1:
        raise ValueError("split: split axis may not be partitioned")
    if sum(sizes) != x.dims[axis].size:
        raise ValueError("split: sizes must sum to axis size")
    outs = tuple(x.with_dim(axis, ParallelDim(s)) for s in sizes)
    return outs, ()


def _lower_split(params):
    def fn(ins, ws, ctx):
        (x,) = ins
        axis = params["axis"]
        idxs = []
        acc = 0
        for s in params["sizes"][:-1]:
            acc += s
            idxs.append(acc)
        return list(jnp.split(x, idxs, axis=axis))

    return fn


register_op(OperatorType.SPLIT, _infer_split, _lower_split)


def _infer_reshape(input_shapes, params):
    (x,) = input_shapes
    new_sizes = tuple(params["shape"])
    if math.prod(new_sizes) != x.volume():
        raise ValueError(
            f"reshape: volume mismatch {x.logical_sizes} -> {new_sizes}"
        )
    dims = []
    for i, s in enumerate(new_sizes):
        # degree survives only on a leading dim of unchanged size
        if i == 0 and x.dims and x.dims[0].size == s and not x.dims[0].is_replica_dim:
            dims.append(ParallelDim(s, x.dims[0].degree, x.dims[0].parallel_idx))
        else:
            dims.append(ParallelDim(s))
    return (ParallelTensorShape(tuple(dims), x.dtype),), ()


def _lower_reshape(params):
    def fn(ins, ws, ctx):
        return [jnp.reshape(ins[0], tuple(params["shape"]))]

    return fn


register_op(OperatorType.RESHAPE, _infer_reshape, _lower_reshape)


def _infer_transpose(input_shapes, params):
    (x,) = input_shapes
    perm = params["perm"]
    dims = tuple(x.dims[p] for p in perm)
    return (ParallelTensorShape(dims, x.dtype),), ()


def _lower_transpose(params):
    def fn(ins, ws, ctx):
        return [jnp.transpose(ins[0], axes=tuple(params["perm"]))]

    return fn


register_op(OperatorType.TRANSPOSE, _infer_transpose, _lower_transpose)


def _infer_reverse(input_shapes, params):
    return (input_shapes[0],), ()


def _lower_reverse(params):
    def fn(ins, ws, ctx):
        return [jnp.flip(ins[0], axis=params["axis"])]

    return fn


register_op(OperatorType.REVERSE, _infer_reverse, _lower_reverse)


def _infer_flat(input_shapes, params):
    (x,) = input_shapes
    n = x.dims[0]
    rest = 1
    for d in x.dims[1:]:
        rest *= d.size
    out = ParallelTensorShape(
        (ParallelDim(n.size, n.degree, n.parallel_idx), ParallelDim(rest)), x.dtype
    )
    return (out,), ()


def _lower_flat(params):
    def fn(ins, ws, ctx):
        (x,) = ins
        return [jnp.reshape(x, (x.shape[0], -1))]

    return fn


register_op(OperatorType.FLAT, _infer_flat, _lower_flat)


def _infer_cast(input_shapes, params):
    (x,) = input_shapes
    return (ParallelTensorShape(x.dims, params["dtype"]),), ()


def _lower_cast(params):
    def fn(ins, ws, ctx):
        return [ins[0].astype(params["dtype"].to_jnp())]

    return fn


register_op(OperatorType.CAST, _infer_cast, _lower_cast)


# ---------------------------------------------------------------------------
# Reductions (reference: src/ops/reduce.cc, mean.cc)
# ---------------------------------------------------------------------------


def _infer_reduce(input_shapes, params):
    (x,) = input_shapes
    axes = tuple(a % x.ndim for a in params["axes"])
    keepdims = params.get("keepdims", False)
    dims = []
    for i, d in enumerate(x.dims):
        if i in axes:
            if d.degree > 1:
                raise ValueError("reduce: reduced dim may not be partitioned")
            if keepdims:
                dims.append(ParallelDim(1))
        else:
            dims.append(d)
    if not dims:
        dims = [ParallelDim(1)]
    return (ParallelTensorShape(tuple(dims), x.dtype),), ()


def _make_reduce_lower(reducer):
    def lower(params):
        def fn(ins, ws, ctx):
            return [
                reducer(
                    ins[0],
                    axis=tuple(params["axes"]),
                    keepdims=params.get("keepdims", False),
                )
            ]

        return fn

    return lower


register_op(OperatorType.REDUCE_SUM, _infer_reduce, _make_reduce_lower(jnp.sum))
register_op(OperatorType.MEAN, _infer_reduce, _make_reduce_lower(jnp.mean))


# ---------------------------------------------------------------------------
# Gather (used by frontends)
# ---------------------------------------------------------------------------


def _infer_gather(input_shapes, params):
    x, idx = input_shapes
    axis = params.get("axis", 0) % x.ndim
    out = x.with_dim(axis, ParallelDim(idx.dims[axis].size))
    return (out,), ()


def _lower_gather(params):
    def fn(ins, ws, ctx):
        x, idx = ins
        return [jnp.take_along_axis(x, idx, axis=params.get("axis", 0))]

    return fn


register_op(OperatorType.GATHER, _infer_gather, _lower_gather)


# ---------------------------------------------------------------------------
# FusedOp (reference: src/ops/fused.cc:437 + fused.cu:918 — one task
# dispatching many inner kernels through indirection tables). Here the
# sub-op list lives in params["sub_ops"]; infer/lower chain the inner
# OpDefs, slicing the flattened weight list per sub-op.
# ---------------------------------------------------------------------------


def _infer_fused(input_shapes, params):
    from flexflow_tpu.ops.registry import infer_shapes as _infer

    shapes = list(input_shapes)
    weights = []
    for sub in params["sub_ops"]:
        outs, ws = _infer(sub["op_type"], shapes, sub["params"])
        if len(outs) != 1:
            raise ValueError("fused sub-ops must be single-output")
        shapes = [outs[0]]
        weights.extend(ws)
    return (shapes[0],), tuple(weights)


def _lower_fused(params):
    import dataclasses as _dc

    from flexflow_tpu.ops.registry import lower_op as _lower

    subs = [
        (_lower(sub["op_type"], sub["params"]), sub["num_weights"])
        for sub in params["sub_ops"]
    ]

    def fn(ins, ws, ctx):
        x = ins[0]
        off = 0
        for i, (sub_fn, nw) in enumerate(subs):
            sub_ctx = ctx
            if ctx is not None and ctx.rng is not None:
                # each sub-op gets an independent stream — the executor
                # folds rng per NODE, and fusion must not make two dropouts
                # in one chain draw identical masks
                sub_ctx = _dc.replace(ctx, rng=jax.random.fold_in(ctx.rng, i))
            (x,) = sub_fn([x], ws[off : off + nw], sub_ctx)
            off += nw
        return [x]

    return fn


def _flops_fused(input_shapes, params):
    from flexflow_tpu.ops.registry import infer_shapes as _infer
    from flexflow_tpu.ops.registry import op_flops as _flops

    shapes = list(input_shapes)
    total = 0.0
    for sub in params["sub_ops"]:
        total += _flops(sub["op_type"], shapes, sub["params"])
        outs, _ = _infer(sub["op_type"], shapes, sub["params"])
        shapes = [outs[0]]
    return total


register_op(OperatorType.FUSED, _infer_fused, _lower_fused, _flops_fused)
