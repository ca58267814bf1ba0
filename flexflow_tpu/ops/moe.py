"""Mixture-of-Experts op family: TopK, GroupBy, Aggregate(Spec), Cache.

Re-design of the reference's MoE ops (reference: src/ops/{topk,group_by,
aggregate,aggregate_spec,cache}.{cc,cu}; SURVEY §2.2): expert routing is
topk → group_by (scatter samples per expert) → expert ops → aggregate
(gather + gate-weighted sum), with a `lambda_bal` load-balancing loss.

TPU-native differences:
  * group_by/aggregate use fixed `capacity = ceil(alpha * k * batch / n)`
    slots per expert so shapes stay static under XLA (the reference sizes
    buffers the same way, group_by.cc), with one-hot-matmul dispatch —
    MXU-friendly, the GShard/Mesh-TF formulation — instead of scatter
    kernels;
  * dropped tokens (over capacity) contribute zeros, matching the
    reference's capacity-overflow behavior.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from flexflow_tpu.core.parallel_tensor import ParallelDim, ParallelTensorShape
from flexflow_tpu.core.types import DataType, OperatorType
from flexflow_tpu.ops.registry import mm_operands, mm_out_dtype, register_op


# ---------------------------------------------------------------------------
# TopK (reference: src/ops/topk.cc)
# ---------------------------------------------------------------------------


def _infer_topk(input_shapes, params):
    (x,) = input_shapes
    k = params["k"]
    last = x.dims[-1]
    if last.degree > 1:
        raise ValueError("topk: topk dim may not be partitioned")
    out_dims = x.dims[:-1] + (ParallelDim(k),)
    values = ParallelTensorShape(out_dims, x.dtype)
    indices = ParallelTensorShape(out_dims, DataType.INT32)
    return (values, indices), ()


def _lower_topk(params):
    k = params["k"]

    def fn(ins, ws, ctx):
        (x,) = ins
        values, indices = jax.lax.top_k(x, k)
        return [values, indices.astype(jnp.int32)]

    return fn


register_op(OperatorType.TOPK, _infer_topk, _lower_topk)


# ---------------------------------------------------------------------------
# GroupBy (reference: src/ops/group_by.cc) — scatter samples to experts
# ---------------------------------------------------------------------------


def _capacity(batch, k, n_experts, alpha):
    return max(1, int(math.ceil(alpha * k * batch / n_experts)))


def _infer_group_by(input_shapes, params):
    # data [*lead, d], assign [*lead, k] int — leading dims are flattened
    # into one token axis (sequence MoE feeds [b, s, d], moe.cc encoder)
    data, assign = input_shapes
    n = params["n"]
    alpha = params.get("alpha", 1.0)
    d = data.dims[-1].size
    tokens = data.volume() // d
    k = assign.dims[-1].size
    cap = _capacity(tokens, k, n, alpha)
    if params.get("stacked", False):
        # one [n, cap, d] tensor whose expert dim may shard (EP)
        out = ParallelTensorShape(
            (ParallelDim(n), ParallelDim(cap), ParallelDim(d)), data.dtype
        )
        return (out,), ()
    out = ParallelTensorShape(
        (ParallelDim(cap), ParallelDim(d)), data.dtype
    )
    return tuple(out for _ in range(n)), ()


def dispatch_slots(assign, n_experts, capacity):
    """Slot assignment shared by group_by and aggregate.

    assign: [b, k] int expert ids. Returns slot_onehot [b*k, n, cap] 0/1
    float: entry (i*k+j, e, c) == 1 iff sample i's j-th choice is expert e
    and it got queue slot c. Tokens past capacity are dropped (all-zero
    row), like the reference's fixed-size expert batches.
    """
    flat = assign.reshape(-1)  # [b*k], sample i -> entries i*k..i*k+k-1
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # [b*k, n]
    # position of each (sample, slot) within its expert queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1  # [b*k, n], -1 where absent
    keep = (pos >= 0) & (pos < capacity)
    pos = jnp.where(keep, pos, 0)
    return jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * keep[..., None]


def dispatch_mask(assign, n_experts, capacity):
    """dispatch [n, cap, b]: dispatch[e, c, i] == 1 iff sample i holds slot
    c of expert e (summed over the k choices)."""
    b, k = assign.shape
    d = dispatch_slots(assign, n_experts, capacity).reshape(
        b, k, n_experts, capacity
    )
    return jnp.transpose(d, (2, 3, 0, 1)).sum(axis=-1)  # [n, cap, b]


def _lower_group_by(params):
    n = params["n"]
    alpha = params.get("alpha", 1.0)
    stacked = params.get("stacked", False)

    def fn(ins, ws, ctx):
        data, assign = ins
        feat = data.shape[-1]
        k = assign.shape[-1]
        data2 = data.reshape(-1, feat)  # [tokens, d]
        assign2 = assign.reshape(-1, k)
        tokens = data2.shape[0]
        cap = _capacity(tokens, k, n, alpha)
        d = dispatch_mask(assign2, n, cap)  # [n, cap, tokens]
        outs = jnp.einsum("ncb,bd->ncd", d.astype(data.dtype), data2)
        if stacked:
            return [outs]
        return [outs[e] for e in range(n)]

    return fn


register_op(OperatorType.GROUP_BY, _infer_group_by, _lower_group_by)


# ---------------------------------------------------------------------------
# ExpertFFN — batched per-expert two-layer MLP, EP-shardable (TPU-native;
# the reference's experts are separate Linear ops the search places on
# different GPUs — here the expert dim shards over the mesh like GShard)
# ---------------------------------------------------------------------------


def _infer_expert_ffn(input_shapes, params):
    (x,) = input_shapes  # [n, cap, d], expert dim may be partitioned
    hidden = params["hidden"]
    e, cap, d = x.dims
    out = ParallelTensorShape(
        (e, cap, ParallelDim(hidden)), x.dtype
    )
    # weights carry the expert dim's partitioning (each chip holds only
    # its experts' parameters — the point of EP)
    w1 = ParallelTensorShape(
        (e, ParallelDim(d.size), ParallelDim(hidden)), x.dtype
    )
    b1 = ParallelTensorShape((e, ParallelDim(hidden)), x.dtype)
    w2 = ParallelTensorShape(
        (e, ParallelDim(hidden), ParallelDim(hidden)), x.dtype
    )
    b2 = ParallelTensorShape((e, ParallelDim(hidden)), x.dtype)
    return (out,), (w1, b1, w2, b2)


def _lower_expert_ffn(params):
    def fn(ins, ws, ctx):
        (x,) = ins
        w1, b1, w2, b2 = ws
        dt = x.dtype
        x, w1, w2 = mm_operands(ctx, x, w1, w2)
        h = jnp.einsum(
            "ecd,edh->ech", x, w1, preferred_element_type=jnp.float32
        ).astype(dt)
        h = jax.nn.relu(h + b1[:, None, :])
        (hm, w2m) = mm_operands(ctx, h, w2)
        y = jnp.einsum(
            "ech,ehf->ecf", hm, w2m, preferred_element_type=jnp.float32
        ).astype(dt)
        return [y + b2[:, None, :]]

    return fn


def _flops_expert_ffn(input_shapes, params):
    (x,) = input_shapes
    n, cap, d = x.logical_sizes
    h = params["hidden"]
    return 2.0 * n * cap * (d * h + h * h)


register_op(
    OperatorType.EXPERT_FFN, _infer_expert_ffn, _lower_expert_ffn,
    _flops_expert_ffn,
)


# ---------------------------------------------------------------------------
# SparseMoE — a dropless top-k expert layer as ONE operator (TPU-native;
# what the open MoE decoders deploy: OLMoE, Mixtral, Qwen-MoE). Unlike the
# reference's family above there is no capacity and no one-hot: the
# tokens x k (token, choice) rows are sorted by expert and a grouped matmul
# runs each expert over its own ragged group, so nothing is ever dropped
# and nothing is computed but the rows.
# ---------------------------------------------------------------------------


def _infer_sparse_moe(input_shapes, params):
    (x,) = input_shapes  # [*lead, d]; a replica dim asks for expert parallelism
    n, f = params["num_experts"], params["expert_hidden"]
    rep = [dim for dim in x.dims if dim.is_replica_dim]
    if len(rep) > 1:
        raise ValueError("sparse_moe: at most one replica dim")
    r_deg = rep[0].degree if rep else 1
    r_idx = rep[0].parallel_idx if rep else -1
    if n % r_deg != 0:
        raise ValueError("sparse_moe: replica degree must divide num_experts")
    d = x.dims[-1]
    if d.degree > 1:
        raise ValueError("sparse_moe: the feature dim may not be partitioned")
    held = params.get("experts_held")
    if held is not None:
        first, count = held
        if r_deg > 1 or not (0 <= first and count >= 1 and first + count <= n):
            raise ValueError(
                f"sparse_moe: experts_held {held} must lie inside the {n} "
                "experts, on a layer that is not sharded over a mesh axis too"
            )
    # the replica-dim protocol of attention's heads: a replicated input
    # shards the stacked experts, each shard sums its own experts' rows,
    # and the output's replica dim is folded by a downstream Reduction.
    # A layer that holds a share stacks only the experts it holds
    expert = ParallelDim(n if held is None else held[1], r_deg, r_idx)
    router = ParallelTensorShape((ParallelDim(d.size), ParallelDim(n)), x.dtype)
    w_in = ParallelTensorShape(
        (expert, ParallelDim(d.size), ParallelDim(f)), x.dtype
    )
    w_out = ParallelTensorShape(
        (expert, ParallelDim(f), ParallelDim(d.size)), x.dtype
    )
    weights = [router, w_in, w_in, w_out]
    if params.get("choice_bias", False):
        weights.append(ParallelTensorShape((ParallelDim(n),), x.dtype))
    return (x,), tuple(weights)


def sparse_moe_route(
    x2, router, k, renormalise, scoring="softmax", bias=None, scale=1.0
):
    """x2 [tokens, d] -> (weights [tokens, k] float32, experts [tokens, k]
    int32). The router matmul and its softmax (or sigmoid) run in float32
    at `highest` whatever the model's precision: a top-k choice flips on
    rounding, and a [d, experts] matmul costs nothing beside the experts.
    `bias` [experts] is added to the scores for the CHOICE only: the
    weights are the chosen experts' own scores, divided by their sum if
    asked and multiplied by `scale`."""
    logits = jnp.dot(
        x2.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"sparse_moe: scoring {scoring!r} is not softmax|sigmoid")
    if bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def expert_products(
    rows, w_gate, w_up, w_down, group_sizes, ctx, grad, rows_per_group
):
    """(silu(rows @ w_gate) * (rows @ w_up)) @ w_down on rows sorted by
    group, each row by its own group's matrices -> (out [rows, d]
    float32, whether the kernel made it). One algorithm, two makers, and
    the choice reads what it can see: the platform, whether a gradient is
    wanted, the operands' type and the static shapes
    (`grouped_matmul.use_kernel`). A serving step on a TPU at lane-tile
    widths takes `ops/pallas/grouped_matmul.py`, whose row tile follows
    `rows_per_group`; training, narrow experts and anything off a TPU
    take three `jax.lax.ragged_dot`, which XLA expands."""
    from flexflow_tpu.ops.pallas import grouped_matmul

    dtype = rows.dtype
    rows, w_gate, w_up = mm_operands(ctx, rows, w_gate, w_up)
    # a Mosaic kernel is not partitioned over a mesh: one device's arrays
    alone = ctx is None or ctx.mesh is None or ctx.mesh.size == 1
    if alone and w_gate.dtype == w_down.dtype == rows.dtype and (
        grouped_matmul.use_kernel(
            rows.shape[0], rows.shape[1], w_gate.shape[2], rows.dtype, grad
        )
    ):
        out = grouped_matmul.expert_mlp(
            rows, w_gate, w_up, w_down, group_sizes,
            rows_per_group=rows_per_group,
        )
        return out, True
    mm = dict(preferred_element_type=jnp.float32)
    gate = jax.lax.ragged_dot(rows, w_gate, group_sizes, **mm)
    up = jax.lax.ragged_dot(rows, w_up, group_sizes, **mm)
    hidden = (jax.nn.silu(gate) * up).astype(dtype)
    hidden, w_down = mm_operands(ctx, hidden, w_down)
    return jax.lax.ragged_dot(hidden, w_down, group_sizes, **mm), False


def sparse_moe(
    x, ws, params, ctx=None, live=None, chosen=None, grad=True, took=None
):
    """The layer on global logical arrays: x [*lead, d] -> (y [*lead, d],
    counts int32). counts is (rows computed, distinct experts with a row),
    and for a layer that holds a share of the experts (`experts_held` =
    (first, count): the stacked weights are those `count` experts) a third:
    the rows routed to experts it does not hold. Such rows are sorted
    behind every group, so the grouped matmuls leave them out, and they
    add nothing to y: what the absent experts would have given is another
    chip's to compute, and nothing here stands in for it. `live` [*lead]
    bool, for such a layer: the tokens that are someone's (a serving
    step's other rows are padding: idle slots, positions past a prompt),
    and only their rows count as absent. `chosen`: a list that receives
    the router's choice, experts [*lead, k] int32 in the router's own
    numbering. `grad`: whether the caller may differentiate the layer
    (the operator's lowering may; a serving step does not), `took`: a
    list that receives whether the experts' products came from the
    grouped-matmul kernel (`expert_products`, decided at trace time)."""
    router, w_gate, w_up, w_down = ws[:4]
    n, k = params["num_experts"], params["k"]
    held = params.get("experts_held")
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d)
    tokens = x2.shape[0]
    with jax.named_scope("moe.route"):
        weights, experts = sparse_moe_route(
            x2, router, k, params.get("renormalise", False),
            params.get("scoring", "softmax"),
            ws[4] if params.get("choice_bias", False) else None,
            params.get("scale", 1.0),
        )
        if chosen is not None:
            chosen.append(experts.reshape(lead + (k,)))
    with jax.named_scope("moe.sort"):
        flat = experts.reshape(-1)  # row r is token r // k, choice r % k
        if held is not None:
            # the held experts' own numbering; every other expert is one
            # group past the last, which `group_sizes` does not have (an
            # out-of-bounds add is dropped)
            n = held[1]
            here = (flat >= held[0]) & (flat < held[0] + n)
            flat = jnp.where(here, flat - held[0], n)
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.zeros((n,), jnp.int32).at[flat].add(1)
        rows = x2[order // k]  # [tokens * k, d], grouped by expert
    with jax.named_scope("moe.experts"):
        # a router spreads its rows over ALL its experts, held or not
        out, kernel = expert_products(
            rows, w_gate, w_up, w_down, group_sizes, ctx, grad,
            tokens * k / params["num_experts"],
        )
        if took is not None:
            took.append(kernel)
    with jax.named_scope("moe.combine"):
        # unsort by the inverse permutation: row r again belongs to token
        # r // k, and a token's k rows are summed under its gate weights
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
        picked = out[back].reshape(tokens, k, d)
        if held is not None:
            # a row no group covers holds whatever the kernel left there
            picked = jnp.where(here.reshape(tokens, k, 1), picked, 0.0)
        y = jnp.sum(picked * weights[..., None], axis=1).astype(
            mm_out_dtype(ctx, x.dtype)
        )
    touched = jnp.sum(group_sizes > 0, dtype=jnp.int32)
    if held is None:
        counts = jnp.stack([jnp.int32(tokens * k), touched])
    else:
        absent = ~here.reshape(tokens, k)
        if live is not None:
            absent = absent & live.reshape(tokens, 1)
        counts = jnp.stack(
            [jnp.sum(group_sizes), touched, jnp.sum(absent, dtype=jnp.int32)]
        )
    return y.reshape(lead + (d,)), counts


def _lower_sparse_moe(params):
    def fn(ins, ws, ctx):
        return [sparse_moe(ins[0], ws, params, ctx)[0]]

    return fn


def _flops_sparse_moe(input_shapes, params):
    (x,) = input_shapes
    d = x.logical_sizes[-1]
    tokens = x.volume() // d
    n, k, f = params["num_experts"], params["k"], params["expert_hidden"]
    held = params.get("experts_held")
    share = 1.0 if held is None else held[1] / n  # of the rows, on average
    return 2.0 * tokens * d * n + 3 * 2.0 * tokens * k * share * d * f


register_op(
    OperatorType.SPARSE_MOE, _infer_sparse_moe, _lower_sparse_moe,
    _flops_sparse_moe,
)


# ---------------------------------------------------------------------------
# Aggregate (reference: src/ops/aggregate.cc) — gate-weighted gather
# ---------------------------------------------------------------------------


def _infer_aggregate(input_shapes, params):
    # inputs: gate_values [*lead,k], gate_assign [*lead,k], then either
    # exp_pred_0..n-1 [cap, d] or one stacked [n, cap, d] -> [*lead, d]
    gate_values = input_shapes[0]
    exp0 = input_shapes[2]
    d_dim = exp0.dims[-1]
    lead = gate_values.dims[:-1]
    out_dims = []
    if params.get("stacked", False):
        e = exp0.dims[0]
        if e.degree > 1:
            # EP: each shard sums only its experts' contributions — the
            # output carries a replica dim a downstream Reduction folds
            # (exactly the Linear contraction-dim protocol)
            out_dims.append(ParallelDim(e.degree, e.degree, e.parallel_idx, True))
    out_dims.extend(lead)
    out_dims.append(ParallelDim(d_dim.size))
    out = ParallelTensorShape(tuple(out_dims), exp0.dtype)
    return (out,), ()


def _lower_aggregate(params):
    n = params["n"]
    stacked = params.get("stacked", False)

    def fn(ins, ws, ctx):
        gate_values, assign = ins[0], ins[1]
        exp_preds = ins[2] if stacked else jnp.stack(ins[2:], axis=0)
        lead = assign.shape[:-1]
        k = assign.shape[-1]
        assign2 = assign.reshape(-1, k)
        b = assign2.shape[0]
        cap = exp_preds.shape[1]
        # combine weights: gate value of the (token, slot) that owns each slot
        slot_onehot = dispatch_slots(assign2, n, cap)  # [b*k, n, cap]
        gates = gate_values.reshape(-1)[:, None, None]  # [b*k,1,1]
        combine = (slot_onehot * gates).reshape(b, k, n, cap).sum(axis=1)
        # combine: [b, n, cap]; output = sum over experts/slots
        y = jnp.einsum("bnc,ncd->bd", combine.astype(exp_preds.dtype), exp_preds)
        return [y.reshape(lead + (y.shape[-1],))]

    return fn


register_op(OperatorType.AGGREGATE, _infer_aggregate, _lower_aggregate)


def _infer_aggregate_spec(input_shapes, params):
    return _infer_aggregate(input_shapes, params)


def _lower_aggregate_spec(params):
    """AggregateSpec = Aggregate that does NOT backprop into the gate
    network (reference: aggregate_spec.cc — the speculative variant's
    backward sends expert gradients but no gate gradient; the reference
    MoE example pairs it with a plain Aggregate that trains the gate)."""
    inner = _lower_aggregate(params)

    def fn(ins, ws, ctx):
        ins2 = [jax.lax.stop_gradient(ins[0])] + list(ins[1:])
        return inner(ins2, ws, ctx)

    return fn


register_op(
    OperatorType.AGGREGATE_SPEC, _infer_aggregate_spec, _lower_aggregate_spec
)


# ---------------------------------------------------------------------------
# load-balancing auxiliary loss (reference: group_by lambda_bal)
# ---------------------------------------------------------------------------


def load_balance_loss(gate_probs, assign, n_experts):
    """GShard-style aux loss: n * sum_e (fraction_tokens_e * mean_prob_e).
    gate_probs [*lead, n] is the FULL gate distribution; assign [*lead, k]."""
    gp = gate_probs.reshape(-1, gate_probs.shape[-1])
    asg = assign.reshape(-1, assign.shape[-1])
    tokens = gp.shape[0]
    counts = jnp.sum(jax.nn.one_hot(asg[:, 0], n_experts), axis=0)
    frac = counts / tokens
    mean_prob = jnp.mean(gp, axis=0)
    return n_experts * jnp.sum(frac * mean_prob)


# ---------------------------------------------------------------------------
# Cache (reference: src/ops/cache.cc) — activation memoization
# ---------------------------------------------------------------------------


def _infer_cache(input_shapes, params):
    return (input_shapes[0],), ()


def _lower_cache(params):
    # In-graph the cache is an identity (reusing stale activations inside
    # a jitted step would silently change training math); the
    # MEMOIZATION lives host-side: the executor surfaces every cache
    # node's input each training step, FFModel keeps the last
    # `num_batches` of them and scores fresh-vs-cached drift with the
    # node's score function (reference: cache.cc score_f), and the score
    # feeds recompile_on_condition triggers — the moe.cc:65-99 pattern of
    # cached expert assignments driving re-sharding.
    def fn(ins, ws, ctx):
        return [ins[0]]

    return fn


def default_cache_score(cached, fresh):
    """Relative L1 drift of the fresh batch vs the rolling cached mean
    (reference: the moe example's score_f compares cached vs new expert
    assignments, moe.cc)."""
    import numpy as np

    if not cached:
        return 1.0
    ref = np.mean([np.asarray(c, dtype=np.float64) for c in cached], axis=0)
    fresh = np.asarray(fresh, dtype=np.float64)
    denom = np.abs(ref).sum() + 1e-12
    return float(np.abs(fresh - ref).sum() / denom)


register_op(OperatorType.CACHE, _infer_cache, _lower_cache)
