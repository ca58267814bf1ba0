"""Flash attention for TPU.

The reference's attention is one cudnnMultiHeadAttnForward call per shard
(reference: src/ops/attention.cu:35) with no long-context story (SURVEY §5
"no ring attention, no blockwise"). This module provides the TPU-native
upgrade: blockwise-tiled attention that never materializes the [s, s] score
matrix, written with Pallas when running on TPU.

Three lowerings, selected by `use_lib` / shape support:
  * the hand-tiled Pallas kernel (flash_kernel.py — VMEM accumulators,
    custom-VJP backward, lse output for ring merging) on TPU;
  * the library `jax.experimental.pallas.ops.tpu.flash_attention` kernel,
    kept as an A/B reference;
  * the jnp blockwise formulation (online-softmax over key blocks via
    lax.scan, fp32 accumulators) as the portable fallback — CPU tests and
    shapes the tiled kernels cannot take.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _blockwise_attention(q, k, v, causal: bool, block_k: int):
    """Online-softmax attention over key blocks. q,k,v: [b, s, h, d]."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_k = min(block_k, sk)
    nk = (sk + block_k - 1) // block_k
    pad = nk * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    # bf16 inputs keep bf16 MATMUL OPERANDS (MXU-native) with f32
    # accumulation; f32 inputs stay f32 end-to-end for exactness
    cdt = q.dtype if q.dtype == jnp.bfloat16 else jnp.float32
    qs = (q.astype(jnp.float32) * scale).astype(cdt)
    kb = k.reshape(b, nk, block_k, h, d).astype(cdt)
    vb = v.reshape(b, nk, block_k, h, d).astype(cdt)
    kpos = jnp.arange(nk * block_k).reshape(nk, block_k)
    qpos = jnp.arange(sq)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, kp = blk
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", qs, kblk,
            preferred_element_type=jnp.float32,
        )
        mask = kp[None, None, None, :] < sk
        if causal:
            mask = mask & (kp[None, None, None, :] <= qpos[None, None, :, None])
        logits = jnp.where(mask, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        correction = jnp.where(jnp.isfinite(m), correction, 0.0)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(cdt), vblk,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, acc0),
        (
            jnp.moveaxis(kb, 1, 0),
            jnp.moveaxis(vb, 1, 0),
            kpos,
        ),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _lib_supports(sq: int, sk: int, d: int) -> bool:
    """Shapes the library kernel takes at its default 128-row blocks:
    both sequences in whole blocks, and a head_dim that is one lane tile
    or a whole number of them."""
    return sq % 128 == 0 and sk % 128 == 0 and (d <= 128 or d % 128 == 0)


def _lib_flash(q, k, v, causal: bool):
    """The public JAX Pallas TPU flash kernel ([b, h, s, d] layout) — a
    hand-written fwd+bwd, chosen over the autodiff'd blockwise scan at
    long sequence (scripts/bench_longctx.py is the comparison; no reading
    of it is in the ledger, ROADMAP Queue 3 item 8)."""
    import math as _math

    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as pl_flash,
    )

    o = pl_flash(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        sm_scale=1.0 / _math.sqrt(q.shape[-1]),
    )
    return o.transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_k", "use_lib")
)
def flash_attention(
    q, k, v, causal: bool = False, block_k: int = 512, use_lib=None
):
    """q, k, v: [batch, seq, heads, head_dim] -> [batch, seq, heads, head_dim].

    use_lib=None ("auto"): on SINGLE-device TPU the hand-tiled kernel
    (flash_kernel.py) runs when the shape tiles, with the library Pallas
    kernel as the shape fallback (use_lib="library" forces it for A/B).
    Under a multi-device mesh an opaque pallas custom call inside plain
    jit has no GSPMD partitioning rule, so callers either wrap the tiled
    kernel in shard_map themselves (ring/Ulysses, ops/attention.py) or
    pass use_lib=False for the jnp blockwise formulation, which XLA
    shards cleanly over batch/heads. `block_k` tunes only the blockwise
    path; the tiled kernels use their own (calibratable) block sizes."""
    if use_lib is None:
        use_lib = (
            jax.default_backend() == "tpu" and jax.device_count() == 1
        )
    if use_lib:
        from flexflow_tpu.ops.pallas.flash_kernel import (
            flash_attention_tpu,
            supports,
        )

        if use_lib != "library" and supports(
            q.shape[1], k.shape[1], q.shape[-1]
        ):
            return flash_attention_tpu(q, k, v, causal=causal)
        if _lib_supports(q.shape[1], k.shape[1], q.shape[-1]):
            return _lib_flash(q, k, v, causal)
    return _blockwise_attention(q, k, v, causal, block_k)
