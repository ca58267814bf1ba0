"""Multi-head attention.

Re-design of the reference's MultiHeadAttention op (reference:
src/ops/attention.cc:926, attention.cu:35-128 — a monolithic
cudnnMultiHeadAttnForward call). Here attention is expressed in jnp (XLA
fuses it well on TPU) with an optional Pallas flash-attention path
(flexflow_tpu.ops.pallas.flash_attention) selected for long sequences.

Head parallelism follows the reference's substitution semantics
(reference: substitution.cc:1758-1764 create_partition_attention_combine /
create_replicate_attention_reduce): a replica dim on the query input becomes
head partitioning of the QKV/output projections; the output-projection
contraction over partitioned heads yields partial sums, i.e. a replica dim
on the output that a downstream Reduction folds.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from flexflow_tpu.core.parallel_tensor import ParallelDim, ParallelTensorShape
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.ops.registry import mm_operands, mm_out_dtype, register_op


def _infer_mha(input_shapes, params):
    q, k, v = input_shapes
    embed_dim = params["embed_dim"]
    num_heads = params["num_heads"]
    kdim = params.get("kdim", embed_dim)
    vdim = params.get("vdim", embed_dim)
    dtype = params.get("dtype", q.dtype)
    head_dim = embed_dim // num_heads

    rep = [d for d in q.dims if d.is_replica_dim]
    logical = [d for d in q.dims if not d.is_replica_dim]
    if len(rep) > 1:
        raise ValueError("mha: at most one replica dim")
    r_deg = rep[0].degree if rep else 1
    r_idx = rep[0].parallel_idx if rep else -1
    if num_heads % r_deg != 0:
        raise ValueError("mha: replica degree must divide num_heads")

    b, s, _ = logical
    out_dims = []
    if r_deg > 1:
        out_dims.append(ParallelDim(r_deg, r_deg, r_idx, True))
    out_dims.extend(
        [
            ParallelDim(b.size, b.degree, b.parallel_idx),
            ParallelDim(s.size, s.degree, s.parallel_idx),
            ParallelDim(embed_dim),
        ]
    )
    out = ParallelTensorShape(tuple(out_dims), dtype)

    head = ParallelDim(num_heads, r_deg, r_idx)
    wq = ParallelTensorShape((ParallelDim(embed_dim), head, ParallelDim(head_dim)), dtype)
    wk = ParallelTensorShape((ParallelDim(kdim), head, ParallelDim(head_dim)), dtype)
    wv = ParallelTensorShape((ParallelDim(vdim), head, ParallelDim(head_dim)), dtype)
    wo = ParallelTensorShape((head, ParallelDim(head_dim), ParallelDim(embed_dim)), dtype)
    weights = [wq, wk, wv, wo]
    if params.get("bias", True):
        # per-projection biases (reference: cudnnMultiHeadAttn with biases):
        # q/k/v biases live in head space (shard with the heads), output
        # bias is a plain embed_dim vector.
        bqkv = ParallelTensorShape((head, ParallelDim(head_dim)), dtype)
        bo = ParallelTensorShape((ParallelDim(embed_dim),), dtype)
        weights += [bqkv, bqkv, bqkv, bo]
    if params.get("qk_norm", False):
        # learned gains of the q and k RMSNorms, over the whole projection
        # (all heads): stored in head space so that they shard with the heads
        gain = ParallelTensorShape((head, ParallelDim(head_dim)), dtype)
        weights += [gain, gain]
    return (out,), tuple(weights)


def scaled_dot_product_attention(
    q, k, v, causal=False, bias=None, dropout_rate=0.0, dropout_rng=None,
    allowed=None,
):
    """q,k,v: [b, s, h, d] — plain XLA attention; fp32 softmax accumulation.
    dropout is applied to the attention probabilities (reference: cudnn MHA
    attnDropout). `allowed` bool [b or 1, q, k]: the keys each query may
    see, where that is not the lower triangle `causal` stands for (rows
    that hold several sequences end to end); the same -1e30 fill."""
    d = q.shape[-1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    if bias is not None:
        logits = logits + bias
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool))
        logits = jnp.where(mask, logits, -1e30)
    if allowed is not None:
        logits = jnp.where(allowed[:, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = 1.0 - dropout_rate
        mask = jax.random.bernoulli(dropout_rng, keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0).astype(probs.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def rotary_cos_sin(positions, head_dim, theta):
    """cos and sin tables [..., 1, head_dim] of the rotate-half rotary
    embedding at integer `positions` [...]: frequency i of head_dim / 2
    is theta ** (-2 i / head_dim), and both halves of a head carry the
    same angles. float32 whatever the model's dtype."""
    half = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = positions.astype(jnp.float32)[..., None] / (theta ** half)
    angles = jnp.concatenate([freqs, freqs], axis=-1)[..., None, :]
    return jnp.cos(angles), jnp.sin(angles)


def mha_qk_positions(q, k, ws, params, positions):
    """QK-norm and rotary positions of an attention node whose `params`
    ask for them, on projected q and k [b, s, h, d]: the ONE place where
    the trainer's lowering and every serving step make a query or a key
    depend on where it stands. `positions` [s] or [b, s] int32 is the
    position of each row in ITS sequence (the cache row it is written
    to, or for a draft tree its depth below the committed prefix); None
    means 0..s-1, the trainer's. The q/k gains are the last two weights.
    A node with neither parameter returns q and k as they came, and
    adds nothing to the program."""
    if params.get("qk_norm", False):
        from flexflow_tpu.ops.core_ops import rms_normalize

        eps = params.get("qk_norm_eps", 1e-5)
        # statistics over the whole projection (heads x head_dim),
        # before the split into heads means anything
        q = rms_normalize(q, ws[-2], eps, axes=(-2, -1))
        k = rms_normalize(k, ws[-1], eps, axes=(-2, -1))
    theta = params.get("rope_theta")
    if theta is not None:
        if positions is None:
            positions = jnp.arange(q.shape[1])
        cos, sin = rotary_cos_sin(positions, q.shape[-1], float(theta))

        def rotate(x):
            xf = x.astype(jnp.float32)
            x1, x2 = jnp.split(xf, 2, axis=-1)
            turned = jnp.concatenate([-x2, x1], axis=-1)
            return (xf * cos + turned * sin).astype(x.dtype)

        q, k = rotate(q), rotate(k)
    return q, k


def is_positional(params) -> bool:
    """Whether an attention node's q and k depend on positions or on
    weights beyond the projections (rotary, QK-norm)."""
    return params.get("rope_theta") is not None or bool(params.get("qk_norm"))


def mha_project_qkv(
    ins, ws, ctx, use_bias=True, params=None, positions=None, rows=False
):
    """Input projections of the MHA lowering: (xq, xk, xv) [b, s, e] ->
    (q, k, v) [b, s, h, d]. Split out of _lower_mha so the serving engine
    (flexflow_tpu.serving.engine) computes the exact same projections when
    it swaps the attention core for the KV-cache decode path — projection
    numerics must match training bit-for-bit or cache-equivalence breaks.
    With the node's `params`, QK-norm and rotary positions follow the
    projection (mha_qk_positions): the keys a serving step writes to its
    cache are already normalised and rotated.

    `rows`: the projections as ROWS, (q, k, v) [b, s, h * d], for a core
    that reads them as they lie (`_tiled_rows`): the same contraction
    against the weight as an [e, h * d] matrix, the bias added along the
    row. The numbers are the same; what differs is the layout XLA's TPU
    layout assignment gives the result. The `ehd` product, and any
    [b, s, h, d] bfloat16 value that stands alone (the bias add outside
    a `shard_map`), is written sequence-minor ({1,3,2,0}), and a Mosaic
    call, which takes its operands row-major, then costs a 67 MB
    relayout copy an operand at the training cells' shape (eight a
    layer, sixteen on the mesh; AOT compile for a described v5e, PR 59).
    QK-norm and rotary positions work in head space and are applied on a
    [b, s, h, d] view (no cell trains such a model: not measured)."""
    xq, xk, xv = ins
    wq, wk, wv = ws[0], ws[1], ws[2]
    xq, xk, xv, wq, wk, wv = mm_operands(ctx, xq, xk, xv, wq, wk, wv)
    # compute dtype: bf16 under mixed precision (softmax/accumulation
    # stays f32 inside the attention core), else the input dtype
    cdt = xq.dtype
    mm = dict(preferred_element_type=jnp.float32)
    if rows:
        q, k, v = (
            jnp.einsum(
                "bse,ef->bsf", x, w.reshape(w.shape[0], -1), **mm
            ).astype(cdt)
            for x, w in ((xq, wq), (xk, wk), (xv, wv))
        )
    else:
        q = jnp.einsum("bse,ehd->bshd", xq, wq, **mm).astype(cdt)
        k = jnp.einsum("bse,ehd->bshd", xk, wk, **mm).astype(cdt)
        v = jnp.einsum("bse,ehd->bshd", xv, wv, **mm).astype(cdt)
    if use_bias:
        bq, bk, bv = (b.reshape(-1) if rows else b for b in ws[4:7])
        q = q + bq.astype(cdt)
        k = k + bk.astype(cdt)
        v = v + bv.astype(cdt)
    if params is not None and is_positional(params):
        if rows:
            heads = wq.shape[1:]
            q, k = (a.reshape(*a.shape[:2], *heads) for a in (q, k))
        q, k = mha_qk_positions(q, k, ws, params, positions)
        if rows:
            q, k = (a.reshape(*a.shape[:2], -1) for a in (q, k))
    return q, k, v


def mha_project_out(attn, ws, ctx, out_dtype, use_bias=True, rows=False):
    """Output projection of the MHA lowering: attn [b, s, h, d] -> [b, s, e].
    Shared with the serving engine like mha_project_qkv; `rows`: attn
    comes as rows [b, s, h * d], contracted against the weight as a
    matrix."""
    attn_m, wo_m = mm_operands(ctx, attn, ws[3])
    if rows:
        y = jnp.einsum(
            "bsf,fe->bse", attn_m, wo_m.reshape(-1, wo_m.shape[-1]),
            preferred_element_type=jnp.float32,
        )
    else:
        y = jnp.einsum(
            "bshd,hde->bse", attn_m, wo_m, preferred_element_type=jnp.float32
        )
    y = y.astype(mm_out_dtype(ctx, out_dtype))
    if use_bias:
        y = y + ws[7].astype(y.dtype)
    return y


def lora_delta_qkv(x, tbl, a_q, b_q, a_k, b_k, a_v, b_v, owner=None):
    """Batched paged LoRA deltas for the Q/K/V projections (S-LoRA /
    Punica posture): per batch row, gather that row's adapter pages out
    of the pooled A/B factors and compute `(x @ A) @ B` summed over the
    row's pages — exact, because a rank-r LoRA product is a sum over
    rank slices and paging splits exactly along rank.

    x: [b, s, e]; tbl: [b, P] int32 page table (sentinel rows of the
    pool are all-zero, so an unused/base-model row contributes exactly
    0.0). a_*: [NP+1, e, pr]; b_*: [NP+1, pr, h, d]. Returns three
    [b, s, h, d] float32 deltas. Every contraction is per-batch-row
    independent — a mixed-adapter batch computes bit-identically to
    each row running alone, which the identity gates rely on.

    `owner` [s, n] one-hot float32, for ONE row x [1, s, e] that holds
    several requests' tokens end to end (the packed prefill): tbl is then
    [n, P], a page table for each request, and token t takes the delta of
    the request that owns it. The rank activations of every request are
    computed for every token (rank-r work) and all but the owner's zeroed,
    so the sum over requests adds exact zeros to the owner's delta."""
    mm = dict(preferred_element_type=jnp.float32)
    x32 = x.astype(jnp.float32)

    def delta(a_pool, b_pool):
        # u: [b, s, P, pr] rank activations per page, then contract the
        # (page, rank-slice) pair back out through B
        if owner is not None:
            u = jnp.einsum("se,nper->snpr", x32[0], a_pool[tbl], **mm)
            u = u * owner[:, :, None, None]
            return jnp.einsum("snpr,nprhd->shd", u, b_pool[tbl], **mm)[None]
        u = jnp.einsum("bse,bper->bspr", x32, a_pool[tbl], **mm)
        return jnp.einsum("bspr,bprhd->bshd", u, b_pool[tbl], **mm)

    return delta(a_q, b_q), delta(a_k, b_k), delta(a_v, b_v)


def lora_delta_out(attn, tbl, a_o, b_o, owner=None):
    """Paged LoRA delta for the output projection — the post-kernel
    epilogue: the attention core (dense or Pallas) runs unmodified and
    the delta applies to its [b, s, h, d] output. a_o: [NP+1, h, d, pr];
    b_o: [NP+1, pr, e]. Returns a [b, s, e] float32 delta with the same
    per-row independence as lora_delta_qkv, and its `owner`."""
    mm = dict(preferred_element_type=jnp.float32)
    if owner is not None:
        u = jnp.einsum(
            "shd,nphdr->snpr", attn[0].astype(jnp.float32), a_o[tbl], **mm
        ) * owner[:, :, None, None]
        return jnp.einsum("snpr,npre->se", u, b_o[tbl], **mm)[None]
    u = jnp.einsum(
        "bshd,bphdr->bspr", attn.astype(jnp.float32), a_o[tbl], **mm
    )
    return jnp.einsum("bspr,bpre->bse", u, b_o[tbl], **mm)


def _kernel_heads(q, head_shard) -> int:
    """Heads one kernel call sees of q [b, w, h, d]: a shard's under
    `head_shard` = (mesh, axis), as `_kernel_call` splits them."""
    if head_shard is None:
        return q.shape[2]
    mesh, axis = head_shard
    return q.shape[2] // mesh.shape[axis]


def _kernel_call(entry, head_shard, args, head_dims):
    """Call a decode-kernel entry point (pallas/decode_kernel.py).

    A Mosaic call has no GSPMD partitioning rule: inside plain jit with
    sharded operands JAX refuses it ("Mosaic kernels cannot be
    automatically partitioned"). `head_shard` = (mesh, axis) — from
    ServingPlacement.kernel_head_shard() — runs the kernel per head
    shard under shard_map instead: `head_dims[i]` is the heads dim of
    `args[i]` (None = replicated: lengths, block tables, masks), the
    [b, w, h, d] result is sharded on heads, and since attention never
    mixes heads there is no collective. None calls the kernel directly
    (one device)."""
    if head_shard is None:
        return entry(*args)
    from jax.sharding import PartitionSpec as P

    mesh, axis = head_shard

    def spec(ndim, head_dim):
        return P(*[axis if i == head_dim else None for i in range(ndim)])

    return jax.shard_map(
        entry,
        mesh=mesh,
        in_specs=tuple(spec(a.ndim, hd) for a, hd in zip(args, head_dims)),
        out_specs=spec(4, 2),
        check_vma=False,
    )(*args)


def _decode_pallas_hook(q, k_cache, v_cache, lengths, kernel="auto",
                        head_shard=None):
    """Seam for the hand-tiled TPU decode kernel (single-query flash
    against the cache — pallas/decode_kernel.py, the serving analog of
    flash_kernel.py for training). `kernel` is the ServeConfig
    .decode_kernel mode: "auto" takes the kernel on TPU when the
    geometry supports() it, "pallas" forces it (interpret mode off-TPU
    — the CI/test path), "dense" pins the jnp path. None routes
    decode_attention to the dense path below; on CPU "auto" stays dense
    (one query row, no [s, s] score tensor to fear)."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk

    if not dk.use_kernel(kernel, q.shape[1], k_cache.shape[1], q.shape[-1]):
        return None
    return _kernel_call(
        dk.flash_decode, head_shard, (q, k_cache, v_cache, lengths),
        (2, 2, 2, None),
    )


def decode_attention(q, k_cache, v_cache, lengths, kernel="auto",
                     head_shard=None):
    """Serving decode regime: one-query attention against a preallocated
    KV cache. q: [b, 1, h, d]; k_cache/v_cache: [b, max_len, h, d];
    lengths: [b] int32, the cache position the current token was written
    at — positions > lengths[i] (unwritten slots or another request's
    stale rows) are masked out, so a fixed-shape cache serves variable
    sequence lengths without recompiles.

    fp32 score accumulation like scaled_dot_product_attention; the mask
    uses the same -1e30 fill so decode softmax numerics line up with the
    causal prefill path."""
    out = _decode_pallas_hook(
        q, k_cache, v_cache, lengths, kernel, head_shard
    )
    if out is not None:
        return out
    d = q.shape[-1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    klen = k_cache.shape[1]
    mask = jnp.arange(klen)[None, None, None, :] <= lengths[
        :, None, None, None
    ]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)


def tree_ancestor_matrix(parents):
    """Ancestor-or-self closure of a draft tree, threaded AS DATA.

    parents: [b, w] int32 — parents[i, j] is the verify-row index of row
    j's parent within the same w-row window, -1 for the root (row 0, the
    last emitted token; padding rows may use j - 1, which degenerates to
    the linear chain). Parent indices must be < their child's index
    (topological order) — both proposers emit trees that way.

    Returns [b, w, w] bool with anc[i, j, a] = True iff row a is an
    ancestor of row j or j itself. Pointer doubling over the parent
    table: ceil(log2(w)) rounds cover any chain inside a w-row window,
    and the whole computation is data-dependent — one compiled verify
    program serves EVERY tree shape of width w (the mask is an operand,
    not a trace-time constant), which is what lets a future fused
    draft+verify device round rewrite the tree between iterations
    without recompiling."""
    b, w = parents.shape
    anc = jnp.broadcast_to(jnp.eye(w, dtype=bool), (b, w, w))
    if w == 1:
        return anc
    ptr = parents.astype(jnp.int32)
    for _ in range(max(1, math.ceil(math.log2(w)))):
        valid = ptr >= 0
        safe = jnp.clip(ptr, 0, w - 1)
        idx = jnp.broadcast_to(safe[:, :, None], (b, w, w))
        rows = jnp.take_along_axis(anc, idx, axis=1)
        anc = anc | (rows & valid[:, :, None])
        ptr = jnp.where(valid, jnp.take_along_axis(ptr, safe, axis=1), ptr)
    return anc


def tree_allowed_mask(tree_parents, lengths, w, klen):
    """[b, w, klen] bool verify visibility for a draft TREE: query row j
    of sequence i sees cache position p iff p < lengths[i] (the
    committed prefix) or p falls inside the w-row verify window at the
    offset of one of row j's ancestors (or j itself). With chain parents
    (parents[j] = j - 1) this reproduces the staircase
    `p <= lengths[i] + j` exactly, so the tree mask is a strict
    generalization of the linear verify mask."""
    b = tree_parents.shape[0]
    anc = tree_ancestor_matrix(tree_parents)  # [b, w, w]
    kpos = jnp.arange(klen)[None, None, :]
    base = lengths[:, None, None]
    rel = kpos - base  # window offset of each key position
    window = (rel >= 0) & (rel < w)
    idx = jnp.broadcast_to(jnp.clip(rel, 0, w - 1), (b, w, klen))
    in_tree = jnp.take_along_axis(anc, idx, axis=2)
    return (kpos < base) | (window & in_tree)


def _verify_pallas_hook(q, k_cache, v_cache, lengths, kernel="auto",
                        allowed=None, head_shard=None):
    """Seam for the hand-tiled TPU verify kernel (w-query flash against
    the cache — the speculative-decoding scoring pass; decode is its
    w == 1 case, so pallas/decode_kernel.py serves both with one body).
    None routes verify_attention to the dense jnp path; mode semantics
    as in _decode_pallas_hook. `allowed` is the precomputed [b, w, klen]
    tree visibility mask (tree-verify); the tree kernel variant carries
    it as a data operand, gated separately by supports_tree() with the
    same dense fallback contract."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk

    if not dk.use_kernel(kernel, q.shape[1], k_cache.shape[1], q.shape[-1]):
        return None
    if allowed is not None:
        if not dk.supports_tree(q.shape[1]):
            return None
        return _kernel_call(
            dk.flash_verify_tree, head_shard,
            (q, k_cache, v_cache, lengths, allowed),
            (2, 2, 2, None, None),
        )
    return _kernel_call(
        dk.flash_verify, head_shard, (q, k_cache, v_cache, lengths),
        (2, 2, 2, None),
    )


def verify_attention(q, k_cache, v_cache, lengths, kernel="auto",
                     tree_parents=None, head_shard=None):
    """Speculative-decoding verify regime: w query positions per sequence
    (the last emitted token plus the drafted continuation) attend
    against the cache in ONE call. q: [b, w, h, d]; k_cache/v_cache:
    [b, max_len, h, d] — already containing the w fresh K/V rows written
    at positions lengths[i]..lengths[i]+w-1; lengths: [b] int32, the
    cache position the FIRST of the w tokens was written at.

    Query j of sequence i may see cache positions <= lengths[i] + j —
    the staircase mask that makes the verify step causal over the draft
    while still reading the whole prefix. decode_attention is exactly
    the w == 1 special case, and the same fp32 accumulation / -1e30
    fill keeps verify softmax numerics aligned with prefill and decode
    (greedy spec decode must be token-identical to plain decode).

    tree_parents [b, w] int32 (optional) switches the staircase to the
    SpecInfer token-tree mask: row j then sees the prefix plus only its
    ancestor rows' window positions (tree_allowed_mask), so several
    draft branches share one verify call. The tree shape rides as data —
    no recompile per tree — and chain parents reproduce the staircase
    bit-for-bit."""
    allowed_tree = None
    if tree_parents is not None:
        allowed_tree = tree_allowed_mask(
            tree_parents, lengths, q.shape[1], k_cache.shape[1]
        )
    out = _verify_pallas_hook(
        q, k_cache, v_cache, lengths, kernel, allowed=allowed_tree,
        head_shard=head_shard,
    )
    if out is not None:
        return out
    d = q.shape[-1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    w = q.shape[1]
    klen = k_cache.shape[1]
    if allowed_tree is not None:
        allowed = allowed_tree
    else:
        # [b, w, klen]: key position <= lengths + query offset
        allowed = (
            jnp.arange(klen)[None, None, :]
            <= lengths[:, None, None] + jnp.arange(w)[None, :, None]
        )
    logits = jnp.where(allowed[:, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)


def _dequant_pages(pool, tbl, scale, b, heads, d):
    """Gather pages from an int8 pool and dequantize with the per-page
    per-head fp32 scales: pool[tbl] is [b, np_seq, page_size, h, d] and
    scale[tbl] is [b, np_seq, h], broadcast over page positions and
    head_dim. Unwritten pages carry scale 0 and dequantize to exact
    zeros at positions the length mask drops anyway."""
    pages = pool[tbl].astype(jnp.float32).reshape(
        *tbl.shape, -1, heads, d
    )  # [b, np_seq, ps, h, d], whether the pool folds (h, d) or not
    s = scale[tbl][:, :, None, :, None]  # [b, np_seq, 1, h, 1]
    return (pages * s).reshape(b, -1, heads, d)


def _paged_verify_pallas_hook(q, k_pool, v_pool, block_tables, lengths,
                              kernel="auto", k_scale=None, v_scale=None,
                              allowed=None, head_shard=None):
    """Seam for the hand-tiled TPU paged-verify kernel (w-query flash
    walking the block table page by page — the fourth member of the
    pallas/decode_kernel.py family, completing the seam symmetry:
    every cache-attention path now has one). None routes
    paged_verify_attention to the dense gather path; mode semantics as
    in _decode_pallas_hook. int8 pools (scales given) route to the
    quantized kernel variant, gated separately by supports().
    `allowed` is the precomputed [b, w, np_seq * page_size] tree
    visibility mask over LOGICAL positions (the mask tile's index map
    needs no block-table lookup), routing to the tree kernel variants
    under the supports_tree() width gate."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk

    quant = k_scale is not None
    if not dk.use_kernel(
        kernel, q.shape[1], 0, q.shape[-1], page_size=k_pool.shape[1],
        kv_dtype="int8" if quant else "fp32",
        heads=_kernel_heads(q, head_shard),
    ):
        return None
    if allowed is not None:
        if not dk.supports_tree(q.shape[1]):
            return None
        if quant:
            return _kernel_call(
                dk.paged_flash_verify_tree_quant, head_shard,
                (q, k_pool, v_pool, k_scale, v_scale, block_tables,
                 lengths, allowed),
                (2, 2, 2, 1, 1, None, None, None),
            )
        return _kernel_call(
            dk.paged_flash_verify_tree, head_shard,
            (q, k_pool, v_pool, block_tables, lengths, allowed),
            (2, 2, 2, None, None, None),
        )
    if quant:
        return _kernel_call(
            dk.paged_flash_verify_quant, head_shard,
            (q, k_pool, v_pool, k_scale, v_scale, block_tables, lengths),
            (2, 2, 2, 1, 1, None, None),
        )
    return _kernel_call(
        dk.paged_flash_verify, head_shard,
        (q, k_pool, v_pool, block_tables, lengths),
        (2, 2, 2, None, None),
    )


def paged_verify_attention(q, k_pool, v_pool, block_tables, lengths,
                           kernel="auto", k_scale=None, v_scale=None,
                           tree_parents=None, head_shard=None):
    """Verify attention against the block-paged cache. The dense path
    gathers each sequence's pages into a contiguous view (same
    dense-gather strategy as paged_decode_attention, same sentinel
    clamping) and runs the exact verify_attention math; the kernel path
    walks the table with no gather. With int8 pools, k_scale/v_scale
    [num_pages, heads] fp32 dequantize the gathered pages in place —
    the fused-dequant chunk loop of the ISSUE. tree_parents [b, w]
    int32 switches the staircase to the token-tree ancestor mask
    exactly as in verify_attention (the mask is computed over logical
    positions, so it threads unchanged through the page gather)."""
    allowed_tree = None
    if tree_parents is not None:
        allowed_tree = tree_allowed_mask(
            tree_parents, lengths, q.shape[1],
            block_tables.shape[1] * k_pool.shape[1],
        )
    out = _paged_verify_pallas_hook(
        q, k_pool, v_pool, block_tables, lengths, kernel,
        k_scale=k_scale, v_scale=v_scale, allowed=allowed_tree,
        head_shard=head_shard,
    )
    if out is not None:
        return out
    b, _, heads, d = q.shape
    num_pages = k_pool.shape[0]
    tbl = jnp.minimum(block_tables, num_pages - 1)
    if k_scale is not None:
        k = _dequant_pages(k_pool, tbl, k_scale, b, heads, d)
        v = _dequant_pages(v_pool, tbl, v_scale, b, heads, d)
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    else:
        k = k_pool[tbl].reshape(b, -1, heads, d)
        v = v_pool[tbl].reshape(b, -1, heads, d)
    # the gather is this path's choice: the contiguous kernel must not
    # take the gathered view on its own "auto"
    return verify_attention(
        q, k, v, lengths, kernel="dense", tree_parents=tree_parents
    )


def _paged_decode_pallas_hook(q, k_pool, v_pool, block_tables, lengths,
                              kernel="auto", k_scale=None, v_scale=None,
                              head_shard=None):
    """Seam for the hand-tiled TPU paged-decode kernel (single-query
    flash that walks the block table page by page instead of gathering
    the pages into a contiguous [b, max_len] view first — the
    PagedAttention kernel shape, pallas/decode_kernel.py with its
    supports() gate and calibration-table tile sizes). None routes
    paged_decode_attention to the dense gather path below; mode
    semantics as in _decode_pallas_hook. int8 pools (scales given)
    route to the quantized kernel variant, gated separately by
    supports()."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk

    quant = k_scale is not None
    if not dk.use_kernel(
        kernel, q.shape[1], 0, q.shape[-1], page_size=k_pool.shape[1],
        kv_dtype="int8" if quant else "fp32",
        heads=_kernel_heads(q, head_shard),
    ):
        return None
    if quant:
        return _kernel_call(
            dk.paged_flash_decode_quant, head_shard,
            (q, k_pool, v_pool, k_scale, v_scale, block_tables, lengths),
            (2, 2, 2, 1, 1, None, None),
        )
    return _kernel_call(
        dk.paged_flash_decode, head_shard,
        (q, k_pool, v_pool, block_tables, lengths),
        (2, 2, 2, None, None),
    )


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           kernel="auto", k_scale=None, v_scale=None,
                           head_shard=None):
    """Serving decode against a block-paged KV cache. q: [b, 1, h, d];
    k_pool/v_pool: [num_pages, page_size, h, d]; block_tables:
    [b, max_pages_per_seq] int32 page ids (sentinel num_pages for
    unallocated entries); lengths: [b] int32, the cache position the
    current token was written at.

    The dense path gathers each sequence's pages into a contiguous
    [b, max_pages_per_seq * page_size, h, d] view and runs the exact
    decode_attention math: sentinel/unwritten pages land at positions >
    lengths and the same -1e30 mask drops them before softmax. (The gather is a
    per-step temp the size of ONE dense cache view; the capacity win is
    in the persistent pool allocation, not this working set.) With int8
    pools, k_scale/v_scale [num_pages, heads] fp32 dequantize the
    gathered pages in place."""
    out = _paged_decode_pallas_hook(
        q, k_pool, v_pool, block_tables, lengths, kernel,
        k_scale=k_scale, v_scale=v_scale, head_shard=head_shard,
    )
    if out is not None:
        return out
    b, _, heads, d = q.shape
    num_pages = k_pool.shape[0]
    # sentinel entries are clamped to a real page; whatever that page
    # holds sits at masked positions, so the clamp is numerically inert
    tbl = jnp.minimum(block_tables, num_pages - 1)
    if k_scale is not None:
        k = _dequant_pages(k_pool, tbl, k_scale, b, heads, d)
        v = _dequant_pages(v_pool, tbl, v_scale, b, heads, d)
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    else:
        k = k_pool[tbl].reshape(b, -1, heads, d)
        v = v_pool[tbl].reshape(b, -1, heads, d)
    # the gather is this path's choice: the contiguous kernel must not
    # take the gathered view on its own "auto"
    return decode_attention(q, k, v, lengths, kernel="dense")


def _q_mesh_axes(ctx):
    """Mesh axis names (batch_ax, seq_ax, head_ax) of the q input's
    partitioned dims — head sharding comes from a replica dim on q (the
    head-parallel rewrite). None per slot when unsharded; None overall
    when no 3D parallel shape is available. THE one place the
    ParallelDim→axis-name classification lives."""
    if ctx is None or ctx.mesh is None or not ctx.in_shapes:
        return None
    qshape = ctx.in_shapes[0]
    logical = [d for d in qshape.dims if not d.is_replica_dim]
    rep = [d for d in qshape.dims if d.is_replica_dim]
    if len(logical) != 3:
        return None
    b, s, _ = logical
    names = ctx.axis_names
    batch_ax = names[b.parallel_idx] if b.degree > 1 else None
    seq_ax = names[s.parallel_idx] if s.degree > 1 else None
    head_ax = (
        names[rep[0].parallel_idx] if rep and rep[0].degree > 1 else None
    )
    return batch_ax, seq_ax, head_ax


def _seq_parallel_axes(ctx):
    """If the q AND k/v sequence dims are partitioned the same way, return the
    mesh axis names (seq_axis, batch_axis, head_axis) for the ring/Ulysses
    paths; else None (the dense path handles mixed layouts via GSPMD)."""
    axes = _q_mesh_axes(ctx)
    if axes is None:
        return None
    batch_ax, seq_ax, head_ax = axes
    if seq_ax is None:
        return None
    s = [d for d in ctx.in_shapes[0].dims if not d.is_replica_dim][1]
    # cross-attention guard: the ring rotates K/V blocks, so the key/value
    # sequence dims must be sharded on the same axis with the same degree
    for kv in ctx.in_shapes[1:3]:
        kv_logical = [d for d in kv.dims if not d.is_replica_dim]
        if len(kv_logical) != 3:
            return None
        s_kv = kv_logical[1]
        if s_kv.degree != s.degree or s_kv.parallel_idx != s.parallel_idx:
            return None
    return seq_ax, batch_ax, head_ax


# "auto" flash selection: dense attention on TPU beats the blockwise path
# until the [b, h, sq, sk] f32 score tensor threatens HBM (measured on v5e:
# dense fwd+bwd is ~4-5x faster than blockwise at seq 512-2048), so the
# switch is on PER-DEVICE score-tensor BYTES, not sequence length.
_FLASH_SCORE_BYTES = 2 << 30

# Below the flash threshold, dense attention is still kernel-bound by the
# f32 score block's working set: on v5e the fwd+bwd goes superlinear once
# [b, h, sq, sk] f32 exceeds ~VMEM (measured at the flagship shape
# seq512/h16: bs8 0.997 ms -> bs16 2.66 ms -> bs32 5.16 ms monolithic,
# vs 0.783 / 1.98 / 3.89 ms scanned over batch chunks whose score block
# is ~67 MB; scripts/probe_attn_batch.py, probe_attn_chunked2.py). So the
# dense path scans over batch chunks keeping the chunk's score block
# under this cap: the scan engages past _DENSE_MONO_SCORE_BYTES and
# tiles to chunks whose score block is <= _DENSE_CHUNK_SCORE_BYTES (the
# measured-best 67 MB tile admits; the measured-worse 134 MB tile
# rejects). The flagship bs8 config (134 MB scores) chunks too:
# interleaved same-process A/B with the fixed difference-of-mins
# estimator measures the full train step at 16.36 ms chunked vs
# 23.82 ms monolithic (scripts/ab_attn_chunk2.py `8 160,80 1,80`), and a
# chain-length ladder confirms 16.4 ms/step at every burst length
# (scripts/probe_chain_lengths.py — earlier "mono wins at bs8" readings
# came from a biased estimator and a measurement script that traced
# AFTER its monkeypatch was restored). bs8/16/32 now scale linearly:
# 16.4 / 32.1 / 66.7 ms.
_DENSE_MONO_SCORE_BYTES = 96 << 20
_DENSE_CHUNK_SCORE_BYTES = 80 << 20


def set_dense_caps(mono_mb: int, chunk_mb: int) -> None:
    """Install measured dense-attention working-set caps (the calibration
    table's "attn_caps" entry, written by an on-chip probe). The built-in
    defaults are the v5e-measured values; a table measured on another
    chip generation replaces them at compile
    (runtime/model.py compile())."""
    global _DENSE_MONO_SCORE_BYTES, _DENSE_CHUNK_SCORE_BYTES
    _DENSE_MONO_SCORE_BYTES = int(mono_mb) << 20
    _DENSE_CHUNK_SCORE_BYTES = int(chunk_mb) << 20


def _dense_batch_chunk(batch, heads, sq, sk) -> int:
    """Batch-chunk size for the dense path: `batch` (no scan) while the
    monolithic score block stays under the mono cap, else the largest
    divisor of `batch` whose per-chunk score block fits the chunk cap.

    When NO divisor fits (long-seq/small-batch: one sample's score block
    already exceeds the cap), the scan degenerates to single-sample
    chunks — 10-60% slower than the one-shot kernel in ISOLATION
    (scripts/bench_longctx.py: 6.9 vs 6.3 ms at seq 2048, 26.5 vs
    16.4 ms at seq 4096 fwd+bwd) but its remat stores NO probabilities:
    a 24-layer model at seq 4096 would otherwise keep ~12 GB of bf16
    probs resident for the backward and OOM a 16 GB chip. Memory safety
    wins this band, the same reasoning that keeps the >=2 GiB flash
    threshold despite dense beating blockwise just past it."""
    if batch * heads * sq * sk * 4 <= _DENSE_MONO_SCORE_BYTES:
        return batch
    for c in range(batch, 0, -1):
        if batch % c == 0 and c * heads * sq * sk * 4 <= _DENSE_CHUNK_SCORE_BYTES:
            return c
    return 1


def _chunked_dense_attention(q, k, v, causal, chunk):
    """scaled_dot_product_attention scanned over batch chunks — bounds the
    per-step f32 score working set (VMEM) without changing numerics.

    The chunk body is rematerialized: the backward recomputes each
    chunk's scores/probs from its (VMEM-sized) inputs instead of
    streaming stored probabilities from HBM. Measured on v5e at the
    flagship shape (seq 512, 16 heads), full train step, exactly-equal
    losses: bs8 23.8 -> 16.4 ms, bs16 56.96 -> 32.14 ms, bs32 111 ->
    66.7 ms — linear in batch at ~66-70% of bf16 peak
    (scripts/ab_attn_chunk2.py, scripts/probe_chain_lengths.py). Remat
    of the MONOLITHIC kernel does not help — the win needs the chunked
    working set.

    The scan iterates the LEADING axis of the arrays it is given, so
    that axis must be whole on every device: the global batch where
    nothing shards it, each device's own sequences inside `_per_device`
    where a mesh does (`mha_core_plan`). Four v5e chips, data parallel,
    64 sequences a chip, against the one-shot kernel over the 64 (1 GiB
    of scores a chip), which such a mesh took until PR 36: train step
    243.7 -> 136.1 ms, its twelve attention nodes 215.7 -> 108.5 ms
    where one chip's read 103.4, 525.6k -> 923.2k tokens/s
    (`train_ff_b256_x4`; builder's chip runs, PRs 35 and 36)."""
    from jax import lax

    b = q.shape[0]
    n = b // chunk
    qs = q.reshape(n, chunk, *q.shape[1:])
    ks = k.reshape(n, chunk, *k.shape[1:])
    vs = v.reshape(n, chunk, *v.shape[1:])

    @jax.checkpoint
    def body_fn(qq, kk, vv):
        return scaled_dot_product_attention(qq, kk, vv, causal=causal)

    def body(_, blk):
        return _, body_fn(*blk)

    _, out = lax.scan(body, None, (qs, ks, vs))
    return out.reshape(b, *q.shape[1:])


def _q_degrees(ctx):
    """Partition degrees of the q input's (batch, seq, heads) — heads via
    the head-parallel replica dim. (1, 1, 1) when no parallel shape is
    available. Under jit array shapes are GLOBAL; callers divide these out
    to reason about per-device working sets."""
    if ctx is None or not ctx.in_shapes:
        return 1, 1, 1
    qshape = ctx.in_shapes[0]
    logical = [d for d in qshape.dims if not d.is_replica_dim]
    rep = [d for d in qshape.dims if d.is_replica_dim]
    if len(logical) != 3:
        return 1, 1, 1
    b_deg = max(1, logical[0].degree)
    s_deg = max(1, logical[1].degree)
    h_deg = max(1, rep[0].degree) if rep else 1
    return b_deg, s_deg, h_deg


def _auto_flash(batch, heads, sq, sk, ctx=None) -> bool:
    # divide out the sharding so a data-parallel pod doesn't get blockwise
    # where its per-chip slice is tiny
    b_deg, s_deg, h_deg = _q_degrees(ctx)
    batch //= b_deg
    sq //= s_deg
    heads //= h_deg
    # >= : a score tensor exactly AT the threshold must already
    # take the streaming path (a 2 GiB materialization is the
    # failure mode, not the last safe point)
    return batch * heads * sq * sk * 4 >= _FLASH_SCORE_BYTES


def _single_device(ctx) -> bool:
    return ctx is None or ctx.mesh is None or ctx.mesh.size == 1


def _batch_head_specs(ctx):
    """PartitionSpec entries of q/k/v [b, s, h, d] that keep each device
    on its own sequences and heads, the sequence whole."""
    b_ax, _, h_ax = _q_mesh_axes(ctx) or (None, None, None)
    return b_ax, None, h_ax, None


def _tiled_takes(ctx, heads, sq, sk, head_dim, specs=None) -> bool:
    """Whether the hand-tiled Pallas kernel (flash_kernel.py) takes this
    shape here: on a TPU, a shape it `supports`, and on a mesh under
    `specs` (default: q's own batch and head axes)."""
    from flexflow_tpu.ops.pallas.flash_kernel import supports

    if jax.default_backend() != "tpu" or not supports(sq, sk, head_dim):
        return False
    if specs is None:
        if _single_device(ctx):
            return True
        specs = _batch_head_specs(ctx)
    bs_ax, sq_ax, h_ax, _ = specs
    if sq_ax is not None:
        # a sharded seq dim inside shard_map would compute BLOCK-DIAGONAL
        # attention (each device only its own keys) — that layout belongs
        # to ring_attention, not this wrapper
        return False
    if bs_ax is None and h_ax is None:
        # nothing to shard over: a fully-replicated shard_map would
        # all-gather whatever sharding the inputs DO carry (e.g. a seq
        # sharding this call was asked to densify) and recompute the
        # whole attention on every device — let XLA partition the
        # blockwise path instead
        return False
    return h_ax is None or heads % ctx.mesh.shape[h_ax] == 0


def _per_device(core, ctx, specs, check_vma=None):
    """`core(q, k, v)` run by every device on ITS block of q/k/v
    [b, s, h, d] — shard_map, the GSPMD-compatible way to place inside a
    sharded step what jit alone cannot partition: an opaque pallas call,
    or a scan over an axis the mesh shards. `specs` is the PartitionSpec
    for q/k/v and the output; GSPMD reshards inputs to match, so callers
    choose the layout (e.g. Ulysses' seq→head all-to-all is exactly the
    reshard these in_specs induce).

    `check_vma` None keeps the varying-axes checker where it is needed:
    on a mesh with an axis that `specs` leave out (q/k/v replicated over
    it) it is what tells autodiff that their cotangents are replicated
    too, and without it every backward pass psums them over that axis.
    Where `specs` name every axis it has nothing to tell, and tracing
    the x4 train step through it took 1.83 s on the chip's host where
    1.18 s do (12 nodes; builder's chip run, PR 36)."""
    from jax.sharding import PartitionSpec as P

    if check_vma is None:
        check_vma = any(
            size > 1 and axis not in specs
            for axis, size in ctx.mesh.shape.items()
        )
    spec = P(*specs)
    return jax.shard_map(
        core,
        mesh=ctx.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=check_vma,
    )


def _tiled_flash_sharded(q, k, v, ctx, causal, specs):
    """Run the hand-tiled Pallas kernel (flash_kernel.py) per device.
    Returns None when the per-device block doesn't tile
    (`_tiled_takes`)."""
    from flexflow_tpu.ops.pallas.flash_kernel import flash_attention_tpu

    if not _tiled_takes(
        ctx, q.shape[2], q.shape[1], k.shape[1], q.shape[-1], specs
    ):
        return None
    # check_vma off: a pallas_call's out_shape carries no varying-axes
    # annotation, and the checker refuses it
    return _per_device(
        lambda a, b, c: flash_attention_tpu(a, b, c, causal=causal),
        ctx, specs, check_vma=False,
    )(q, k, v)


def _tiled(q, k, v, ctx, causal):
    """The one dispatch point for the hand-tiled kernel outside the
    seq-parallel paths, for a shape `_tiled_takes`: direct call on a
    single device, per device over the batch/head axes on a mesh."""
    if _single_device(ctx):
        from flexflow_tpu.ops.pallas.flash_kernel import flash_attention_tpu

        return flash_attention_tpu(q, k, v, causal=causal)
    return _tiled_flash_sharded(q, k, v, ctx, causal, _batch_head_specs(ctx))


def _whole_form_takes(ctx, shape) -> bool:
    """Whether the hand-tiled kernel runs a device's block of the GLOBAL
    `shape` (batch, sq, sk, heads, head_dim) in its whole-sequence form
    (flash_kernel.supports_whole, at the operands' width under `ctx`)."""
    from flexflow_tpu.ops.pallas.flash_kernel import supports_whole

    _, sq, sk, heads, head_dim = shape
    _, s_deg, h_deg = _q_degrees(ctx)
    itemsize = 2 if getattr(ctx, "bf16_matmul", False) else 4
    return supports_whole(
        max(1, sq // s_deg), sk, max(1, heads // h_deg), head_dim, itemsize
    )


def _tiled_rows(q, k, v, heads, ctx, causal):
    """The hand-tiled kernel's whole-sequence form on the projections'
    rows [b, s, heads * d], for a node `_whole_form_takes`: direct call
    on a single device, per device over the batch/head axes on a mesh
    (a device's share of the row is its own heads, side by side)."""
    from flexflow_tpu.ops.pallas.flash_kernel import flash_attention_rows

    if _single_device(ctx):
        return flash_attention_rows(q, k, v, heads, causal=causal)
    b_ax, _, h_ax, _ = _batch_head_specs(ctx)
    local = heads // (ctx.mesh.shape[h_ax] if h_ax is not None else 1)
    # check_vma off: as in _tiled_flash_sharded
    return _per_device(
        lambda a, b, c: flash_attention_rows(a, b, c, local, causal=causal),
        ctx, (b_ax, None, h_ax), check_vma=False,
    )(q, k, v)


def _drops(params, ctx) -> bool:
    """Whether this lowering applies attention-prob dropout."""
    return bool(
        params.get("dropout", 0.0) > 0.0
        and ctx.train
        and ctx.rng is not None
    )


class CorePlan(NamedTuple):
    """What `mha_core_plan` chose for one attention node."""

    core: str  # one_shot | chunked | tiled | flash | ring | ulysses
    chunk: int  # sequences one pass of the core attends on a device
    local_batch: int  # sequences of the batch a device holds
    per_device: bool  # run under shard_map on each device's own block


def mha_core_plan(params, ctx, shape=None) -> CorePlan:
    """The attention core a `multihead_attention` node with `params`
    lowers to under `ctx`: chosen ONCE, here, from what the lowering can
    see (the node's parameters, the mesh and q's partition degrees in
    `ctx`, the shapes, the measured caps) and nothing a user sets for
    the purpose. `shape` is the GLOBAL (batch, sq, sk, heads, head_dim)
    of the projected q and k; None reads it from `ctx.in_shapes`, so
    that a test or a profile can ask with a ctx alone.

    Sequence sharded and k/v sharded alike: `ring` or `ulysses`. Else by
    the PER-DEVICE float32 score block [b, h, sq, sk]: `flash` (the
    blockwise or library kernel) from _FLASH_SCORE_BYTES up; `tiled`
    (the hand-tiled kernel, on a TPU, if it takes the shape) there,
    where one sequence's block already overflows the chunk cap, and at
    every self-attention short enough for its whole-sequence form
    (`_whole_form_takes`: 512 at the flagship's heads, whatever the
    batch); `chunked` (the rematerialised scan over chunks of the local
    batch) past the mono cap; `one_shot` below it. Off a TPU the last two
    are all there is below the flash threshold. The scan wants its
    leading axis whole
    on a device: the global batch when nothing shards it, and each
    device's LOCAL batch (`per_device`) when the mesh shards the batch
    and not the sequence. Attention-prob dropout keeps the one-shot
    kernel (the rng path); a sequence sharded with no seq-parallel path
    under a sharded batch keeps it too (per device the sequence would
    have to be all-gathered: GSPMD partitions the one-shot einsums)."""
    use_flash = params.get("use_flash", "auto")
    seq_parallel = params.get("seq_parallel", "auto")
    if shape is None:
        q_dims, k_dims = (
            [d.size for d in s.dims if not d.is_replica_dim]
            for s in ctx.in_shapes[:2]
        )
        heads = params["num_heads"]
        shape = (
            q_dims[0], q_dims[1], k_dims[1], heads,
            params["embed_dim"] // heads,
        )
    batch, sq, sk, heads, head_dim = shape
    dropping = _drops(params, ctx)
    b_deg, s_deg, h_deg = _q_degrees(ctx)
    local_b = max(1, batch // b_deg)
    h_loc = max(1, heads // h_deg)
    sq_loc = max(1, sq // s_deg)

    sp = None if seq_parallel == "none" else _seq_parallel_axes(ctx)
    if sp is not None and dropping:
        if seq_parallel in ("ring", "ulysses"):
            # don't silently densify an explicitly requested SP path —
            # dense attention materializes the [s, s] scores SP avoids
            raise ValueError(
                f"seq_parallel={seq_parallel!r} does not support "
                "attention-prob dropout; use dropout=0.0 or "
                "seq_parallel='auto' (which falls back to dense)"
            )
        sp = None
    if sp is not None:
        seq_ax, _, head_ax = sp
        mode = "ring" if seq_parallel == "auto" else seq_parallel
        # Ulysses reshards seq→heads, so it needs the head dim free of
        # TP sharding and divisible by the seq-axis degree
        if mode == "ulysses" and not (
            head_ax is None and heads % ctx.mesh.shape[seq_ax] == 0
        ):
            raise ValueError(
                "seq_parallel='ulysses' needs num_heads divisible by the "
                f"seq-axis degree ({ctx.mesh.shape[seq_ax]}) and heads "
                "free of tensor-parallel sharding; use 'ring'"
            )
        return CorePlan(mode, local_b, local_b, True)

    def tiled_or(plan):
        if use_flash is not False and _tiled_takes(
            ctx, heads, sq, sk, head_dim
        ):
            return CorePlan("tiled", local_b, local_b, not _single_device(ctx))
        return plan

    if dropping:  # no prob-dropout path but the one-shot kernel's
        return CorePlan("one_shot", local_b, local_b, False)
    if use_flash is True or (
        use_flash == "auto" and _auto_flash(batch, heads, sq, sk, ctx)
    ):
        return tiled_or(CorePlan("flash", local_b, local_b, False))
    # batch-chunked dense, sized by the PER-DEVICE score block (seq/head
    # sharding divides out like in _auto_flash). Where the mesh shards
    # the batch the scan runs per device, on the local batch: it cannot
    # iterate a GSPMD-sharded leading axis, and the one-shot kernel such
    # a mesh used to get whatever its local block cost 1.9 times the
    # step (_chunked_dense_attention has the numbers)
    chunk, per_device = local_b, False
    if b_deg == 1:
        chunk = _dense_batch_chunk(batch, h_loc, sq_loc, sk)
    elif s_deg == 1 and _batch_head_specs(ctx)[0] is not None:
        chunk = _dense_batch_chunk(local_b, h_loc, sq, sk)
        per_device = chunk < local_b
    dense = CorePlan(
        "chunked" if chunk < local_b else "one_shot", chunk, local_b,
        per_device,
    )
    # The hand-tiled kernel, where it takes the shape (`tiled_or`: a TPU,
    # the sequence whole on a device), has two bands.
    # (1) A sequence short enough for its WHOLE-SEQUENCE form
    # (flash_kernel.supports_whole: one head's float32 score block and
    # its backward's temporaries in VMEM; 512, and 1,024 in bf16): one
    # Mosaic call a pass over the projections' own [b, s, h * d] rows,
    # where the chunked scan pays for its loops (chunk copies in and out
    # of the stacked buffers, their zero fill) and XLA for a layout of its
    # own. Flagship step (12 x 1024, 16 heads, seq 512, mixed precision),
    # batch 64, "TPU v5 lite", interleaved, losses equal to the last
    # digit: 114.11 against 130.11 ms chunked (scripts/ab_attn_tiled.py
    # 64); the core 30.8 against 50-52 ms a step, 2.62 against 2.86 ms a
    # layer alone (scripts/probe_attn_whole.py; PR 59). This supersedes
    # the "19.0 vs 23.6 ms at batch 8" reading that kept seq 512 on the
    # scan: that was the GRID form, whose [b, h, s, d] transposes and
    # 1,024 small programs a call read 10.08 ms a layer here.
    # (2) Past the chunk cap (seq ~2048-8192, small batch), where the
    # scan degenerates to a stores-nothing single-sample remat, measured
    # 10-60% SLOWER than one-shot dense in isolation: the grid form,
    # 12.4 ms vs 21.8 dense / ~52 blockwise at seq 2048 bs8h16 on v5e
    # (scripts/bench_flash_kernel.py).
    if (
        _whole_form_takes(ctx, shape)
        or h_loc * sq_loc * sk * 4 > _DENSE_CHUNK_SCORE_BYTES
    ):
        return tiled_or(dense)
    return dense


def _lower_mha(params):
    causal = params.get("causal", False)
    use_flash = params.get("use_flash", "auto")
    use_bias = params.get("bias", True)
    dropout = params.get("dropout", 0.0)
    # "ring" | "ulysses" | "auto" | "none" — how attention runs when the
    # sequence dim is partitioned (TPU-native addition; the reference cannot
    # shard the attention sequence dim at all, SURVEY §5)
    seq_parallel = params.get("seq_parallel", "auto")
    if seq_parallel not in ("auto", "ring", "ulysses", "none"):
        raise ValueError(
            f"seq_parallel must be auto|ring|ulysses|none, got {seq_parallel!r}"
        )

    def _ulysses(q, k, v, ctx, seq_ax, batch_ax):
        # Ulysses: all-to-all the seq sharding onto the head dim, attend
        # locally, all-to-all back. On TPU the local attend runs the
        # hand-tiled Pallas kernel under shard_map (whose head-sharded
        # in_specs themselves induce the seq→head all-to-all); otherwise
        # GSPMD emits the all-to-alls from the layout constraints around
        # a jnp core.
        from jax.sharding import NamedSharding, PartitionSpec

        # use_flash=False is an explicit request for the dense core —
        # don't override it with the tiled kernel (the "auto" policy DOES
        # prefer tiled: measured on v5e it beats dense from seq 2048 up
        # and the margin grows with sequence, scripts/bench_flash_kernel)
        tiled = (
            _tiled_flash_sharded(
                q, k, v, ctx, causal, (batch_ax, None, seq_ax, None)
            )
            if use_flash is not False
            else None
        )
        if tiled is not None:
            seq_sp = NamedSharding(
                ctx.mesh, PartitionSpec(batch_ax, seq_ax, None, None)
            )
            return jax.lax.with_sharding_constraint(tiled, seq_sp)

        head_spec = NamedSharding(
            ctx.mesh, PartitionSpec(batch_ax, None, seq_ax, None)
        )
        qh = jax.lax.with_sharding_constraint(q, head_spec)
        kh = jax.lax.with_sharding_constraint(k, head_spec)
        vh = jax.lax.with_sharding_constraint(v, head_spec)
        # per-device geometry after the seq→head reshard: full sequence,
        # heads divided by the seq-axis degree, batch by the data axis
        b, s, h, _ = qh.shape
        sp_deg = ctx.mesh.shape[seq_ax]
        b_local = b // (ctx.mesh.shape[batch_ax] if batch_ax else 1)
        if use_flash is True or (
            use_flash == "auto"
            and _auto_flash(b_local, h // sp_deg, s, kh.shape[1])
        ):
            from flexflow_tpu.ops.pallas.flash_attention import flash_attention

            attn = flash_attention(
                qh, kh, vh, causal=causal,
                # None = auto (backend + device checks inside); a sharded
                # mesh must force the partitionable blockwise path
                use_lib=None if _single_device(ctx) else False,
            )
        else:
            attn = scaled_dot_product_attention(qh, kh, vh, causal=causal)
        seq_spec = NamedSharding(
            ctx.mesh, PartitionSpec(batch_ax, seq_ax, None, None)
        )
        return jax.lax.with_sharding_constraint(attn, seq_spec)

    def fn(ins, ws, ctx):
        dt = ins[0].dtype
        shape = (
            ins[0].shape[0], ins[0].shape[1], ins[1].shape[1],
            *ws[0].shape[1:],
        )
        plan = mha_core_plan(params, ctx, shape)
        # the kernel's whole-sequence form reads the projections' rows
        # as they lie: q, k, v and attn are [b, s, h * d] from here to
        # the output projection (mha_project_qkv)
        rows = plan.core == "tiled" and _whole_form_takes(ctx, shape)
        q, k, v = mha_project_qkv(
            ins, ws, ctx, use_bias=use_bias, params=params, rows=rows
        )
        if plan.core == "ulysses":
            seq_ax, batch_ax, _ = _seq_parallel_axes(ctx)
            attn = _ulysses(q, k, v, ctx, seq_ax, batch_ax)
        elif plan.core == "ring":
            from flexflow_tpu.ops.pallas.ring_attention import ring_attention

            seq_ax, batch_ax, head_ax = _seq_parallel_axes(ctx)
            attn = ring_attention(
                q,
                k,
                v,
                ctx.mesh,
                seq_ax,
                causal=causal,
                batch_axis=batch_ax,
                head_axis=head_ax,
            )
        elif rows:
            attn = _tiled_rows(q, k, v, shape[3], ctx, causal)
        elif plan.core == "tiled":
            attn = _tiled(q, k, v, ctx, causal)
        elif plan.core == "flash":
            from flexflow_tpu.ops.pallas.flash_attention import flash_attention

            # the library kernel (single-device) or the jnp blockwise
            # path, which XLA partitions over batch/heads
            attn = flash_attention(
                q, k, v, causal=causal,
                use_lib=None if _single_device(ctx) else False,
            )
        elif plan.core == "chunked":

            def core(qq, kk, vv):
                return _chunked_dense_attention(qq, kk, vv, causal, plan.chunk)

            if plan.per_device:
                core = _per_device(core, ctx, _batch_head_specs(ctx))
            attn = core(q, k, v)
        else:
            dropping = _drops(params, ctx)
            attn = scaled_dot_product_attention(
                q,
                k,
                v,
                causal=causal,
                dropout_rate=dropout if dropping else 0.0,
                dropout_rng=ctx.rng if dropping else None,
            )
        return [
            mha_project_out(attn, ws, ctx, dt, use_bias=use_bias, rows=rows)
        ]

    return fn


def _flops_mha(input_shapes, params):
    q = input_shapes[0]
    b, s, e = q.logical_sizes[-3:]
    proj = 4 * 2.0 * b * s * e * e
    attn = 2 * 2.0 * b * s * s * e
    return proj + attn


register_op(OperatorType.MULTIHEAD_ATTENTION, _infer_mha, _lower_mha, _flops_mha)


# ---------------------------------------------------------------------------
# Latent attention (MLA: DeepSeek-V2/V3 and the models built on them). A
# token's keys and values of every head are decompressed from ONE latent
# row [c | kr]: c the normalised down-projection (kv_lora_rank wide), kr
# one rotary key shared by all heads. That row is all a cache holds. Two
# computations of one function: DECOMPRESSED (k_h = [Wuk_h c | kr], v_h =
# Wuv_h c, then plain causal attention: the lowering below, and a serving
# prefill), and ABSORBED (Wuk folded into the query and Wuv applied after
# the softmax, so that attention runs over the latent rows themselves: a
# serving decode step, over the paged latent pool). No reference
# counterpart. Weights: Wq [e, h, nope + rope], Wkva [e, rank + rope], the
# gain [rank] of c's RMSNorm, Wkvb [rank, h, nope + v] = [Wuk | Wuv],
# Wo [h, v, e]; no biases, no query compression (q_lora_rank null).
# ---------------------------------------------------------------------------


def _mla_dims(params):
    return (
        params["kv_lora_rank"], params["qk_nope_head_dim"],
        params["qk_rope_head_dim"], params["v_head_dim"],
    )


def mla_cache_row(params) -> int:
    """Width of the cache row a latent-attention node keeps of a token:
    [c | kr] (rank + rope), padded with zeros to whole 128-lane tiles,
    because the paged kernel copies whole rows out of the pool and Mosaic
    takes such a copy only in whole tiles (512 + 64 -> 640)."""
    r, _, dr, _ = _mla_dims(params)
    return -(-(r + dr) // 128) * 128


def _infer_latent_attention(input_shapes, params):
    (x,) = input_shapes
    e, h = params["embed_dim"], params["num_heads"]
    r, dn, dr, dv = _mla_dims(params)
    if any(d.is_replica_dim for d in x.dims) or any(
        d.degree > 1 for d in x.dims[1:]
    ):
        raise ValueError(
            "latent_attention: only the batch dim may be partitioned (the "
            "latent row has one head: there is nothing to shard by heads)"
        )
    if x.dims[-1].size != e or dr % 2:
        raise ValueError(
            f"latent_attention: input width {x.dims[-1].size} != {e}, or an "
            f"odd rotary width {dr}"
        )
    dt = x.dtype

    def shape(*sizes):
        return ParallelTensorShape(tuple(ParallelDim(s) for s in sizes), dt)

    weights = (
        shape(e, h, dn + dr), shape(e, r + dr), shape(r),
        shape(r, h, dn + dv), shape(h, dv, e),
    )
    return (x,), weights


def rope_interleaved(x, positions, theta):
    """Rotary positions in the interleaved form, over the last dim of x
    [b, s, h, d]: elements (2i, 2i + 1) rotate together by positions *
    theta ** (-2 i / d). (The checkpoints' own code de-interleaves and
    then rotates halves: the same dot products q . k.) positions [s] or
    [b, s]; float32 inside."""
    d = x.shape[-1]
    cos, sin = rotary_cos_sin(positions, d, float(theta))
    cos, sin = cos[..., : d // 2], sin[..., : d // 2]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


def mla_project(x, ws, params, ctx, positions=None):
    """x [b, s, e] -> (q_nope [b, s, h, nope], q_rope [b, s, h, rope],
    latent [b, s, rank + rope]): the query of every head, its rotary part
    rotated, and the token's latent row [c | kr], c normalised and kr
    rotated, as a cache keeps it. `positions` as in mha_qk_positions.
    With `rope_theta` None nothing is rotated (a model whose positions
    enter through other layers)."""
    r, dn, _, _ = _mla_dims(params)
    with jax.named_scope("mla.project"):
        xm, wq, wkva = mm_operands(ctx, x, ws[0], ws[1])
        cdt = xm.dtype
        mm = dict(preferred_element_type=jnp.float32)
        q = jnp.einsum("bse,ehd->bshd", xm, wq, **mm).astype(cdt)
        kva = jnp.einsum("bse,er->bsr", xm, wkva, **mm).astype(cdt)
        from flexflow_tpu.ops.core_ops import rms_normalize

        c = rms_normalize(kva[..., :r], ws[2], params.get("eps", 1e-6))
        theta = params["rope_theta"]
        if theta is None:
            # no positional encoding: neither part is rotated, and the
            # row [c | kr] is cached as projected
            return q[..., :dn], q[..., dn:], jnp.concatenate(
                [c, kva[..., r:]], axis=-1
            )
        if positions is None:
            positions = jnp.arange(x.shape[1])
        q_rope = rope_interleaved(q[..., dn:], positions, theta)
        kr = rope_interleaved(kva[..., None, r:], positions, theta)[..., 0, :]
        return q[..., :dn], q_rope, jnp.concatenate([c, kr], axis=-1)


def mla_decompressed(q_nope, q_rope, latent, ws, params, ctx, allowed=None):
    """Causal attention of every position over those before it (or over
    the keys `allowed` [b or 1, s, s] gives it, as in
    scaled_dot_product_attention), keys and values decompressed from
    `latent` [b, s, rank + rope] -> [b, s, h, v]. The scale is
    1 / sqrt(nope + rope)."""
    r, dn, _, _ = _mla_dims(params)
    with jax.named_scope("mla.project"):
        c, wkvb = mm_operands(ctx, latent[..., :r], ws[3])
        kv = jnp.einsum(
            "bsr,rhd->bshd", c, wkvb, preferred_element_type=jnp.float32
        ).astype(q_nope.dtype)
        kr = jnp.broadcast_to(
            latent[:, :, None, r:].astype(q_nope.dtype),
            kv.shape[:3] + (latent.shape[-1] - r,),
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn], kr], axis=-1)
    with jax.named_scope("mla.attend"):
        return scaled_dot_product_attention(
            q, k, kv[..., dn:], causal=allowed is None, allowed=allowed
        )


def mla_absorb_query(q_nope, q_rope, ws, params, ctx, row):
    """The query against latent rows: [Wuk_h^T q_nope_h | q_rope_h | 0]
    [b, s, h, row], `row` the (padded) width of a cache row."""
    r, dn, dr, _ = _mla_dims(params)
    with jax.named_scope("mla.absorb"):
        qn, wuk = mm_operands(ctx, q_nope, ws[3][..., :dn])
        q_lat = jnp.einsum(
            "bshd,rhd->bshr", qn, wuk, preferred_element_type=jnp.float32
        ).astype(q_nope.dtype)
        pad = jnp.zeros(q_rope.shape[:-1] + (row - r - dr,), q_nope.dtype)
        return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def mla_absorb_values(attended, ws, params, ctx):
    """attended [b, s, h, rank] (the softmax-weighted sum of latent c
    rows) -> each head's values Wuv_h (.) [b, s, h, v]."""
    _, dn, _, _ = _mla_dims(params)
    with jax.named_scope("mla.absorb"):
        a, wuv = mm_operands(ctx, attended, ws[3][..., dn:])
        return jnp.einsum(
            "bshr,rhd->bshd", a, wuv, preferred_element_type=jnp.float32
        ).astype(attended.dtype)


def mla_project_out(attn, ws, ctx, out_dtype):
    """attn [b, s, h, v] -> [b, s, e]."""
    with jax.named_scope("mla.out"):
        a, wo = mm_operands(ctx, attn, ws[4])
        return jnp.einsum(
            "bshd,hde->bse", a, wo, preferred_element_type=jnp.float32
        ).astype(mm_out_dtype(ctx, out_dtype))


def paged_latent_decode_attention(
    q, pool, block_tables, lengths, value_width, sm_scale, kernel="auto"
):
    """One query position a slot over a paged LATENT pool: q [b, 1, h, row]
    (mla_absorb_query), pool [num_pages, page_size, row], every head
    reading the same rows; the values are a row's first `value_width`
    lanes. Returns [b, 1, h, value_width]. block_tables and lengths as in
    paged_decode_attention; the Pallas kernel where the geometry takes it
    (ops/pallas/decode_kernel.paged_flash_decode_latent), else the dense
    gather, which is also what the CPU serves."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk

    with jax.named_scope("mla.attend"):
        if dk.supports_latent(kernel, q.shape[-1], pool.shape[1]):
            return dk.paged_flash_decode_latent(
                q, pool, block_tables, lengths, value_width, sm_scale
            )
        b = q.shape[0]
        tbl = jnp.minimum(block_tables, pool.shape[0] - 1)
        rows = pool[tbl].reshape(b, -1, pool.shape[-1])  # [b, max_len, row]
        scores = jnp.einsum(
            "bhr,bkr->bhk", q[:, 0], rows, preferred_element_type=jnp.float32
        ) * sm_scale
        seen = jnp.arange(rows.shape[1])[None, None, :] <= lengths[:, None, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum(
            "bhk,bkr->bhr", probs.astype(q.dtype), rows[..., :value_width]
        )[:, None]


def _lower_latent_attention(params):
    def fn(ins, ws, ctx):
        (x,) = ins
        q_nope, q_rope, latent = mla_project(x, ws, params, ctx)
        attn = mla_decompressed(q_nope, q_rope, latent, ws, params, ctx)
        return [mla_project_out(attn, ws, ctx, x.dtype)]

    return fn


def _flops_latent_attention(input_shapes, params):
    (x,) = input_shapes
    b, s, e = x.logical_sizes[-3:]
    h = params["num_heads"]
    r, dn, dr, dv = _mla_dims(params)
    proj = e * (h * (dn + dr) + r + dr) + r * h * (dn + dv) + h * dv * e
    attn = s * h * (dn + dr + dv)
    return 2.0 * b * s * (proj + attn)


register_op(
    OperatorType.LATENT_ATTENTION, _infer_latent_attention,
    _lower_latent_attention, _flops_latent_attention,
)
