"""Ring attention: exact attention under sequence sharding, over ICI.

The reference has no sequence-parallel attention at all — its MHA is one
cudnnMultiHeadAttnForward per shard and the sequence dim of attention is
never partitioned by any substitution (reference: src/ops/attention.cu:35;
SURVEY §5 "no ring attention, no Ulysses, no blockwise"). This module is the
TPU-native capability upgrade: each device holds a `[b, s/N, h, d]` block of
q/k/v; key/value blocks rotate around the mesh's sequence axis with
`jax.lax.ppermute` (one ICI hop per step) while an online-softmax
accumulator folds each visiting block into the local queries' result. The
full `[s, s]` score matrix never exists and no device ever holds more than
`1/N` of the sequence.

Communication pattern: N-1 ppermute steps of the local K/V blocks
(2·b·s/N·h·d elements each) over the ring — bandwidth-optimal for exact
attention, and XLA's latency-hiding scheduler overlaps each hop with the
previous block's compute.

Differentiable as-is: `shard_map` + `ppermute` + `lax.scan` all have
transposes, so `jax.grad` of a ring-attention call yields the matching
reverse ring.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _local_ring_attention(q, k, v, axis_name: str, n_shards: int, causal: bool):
    """Per-device body. q, k, v: local [b, s_loc, h, d] blocks."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    # bf16 inputs keep bf16 MATMUL OPERANDS (MXU-native) with f32
    # accumulation; f32 inputs stay f32 end-to-end for exactness (same
    # scheme as the blockwise kernel, flash_attention.py)
    cdt = q.dtype if q.dtype == jnp.bfloat16 else jnp.float32
    qs = (q.astype(jnp.float32) * scale).astype(cdt)
    my_idx = lax.axis_index(axis_name)
    qpos = my_idx * sq + jnp.arange(sq)  # global query positions [sq]

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    # each step sends the held K/V block to the next device on the ring;
    # after step t device i holds the block that started on (i - t) mod N
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def attend(m, l, acc, kc, vc, t):
        src = jnp.mod(my_idx - t, n_shards)
        kpos = src * sk + jnp.arange(sk)  # global key positions [sk]
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", qs, kc.astype(cdt),
            preferred_element_type=jnp.float32,
        )
        if causal:
            mask = kpos[None, None, None, :] <= qpos[None, None, :, None]
            logits = jnp.where(mask, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(cdt), vc.astype(cdt),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    # fold the local block first, then N-1 rotate+attend steps (permuting
    # before the attend keeps the final rotation out of the loop — no dead
    # ICI hop on the last iteration)
    m, l, acc = attend(m0, l0, acc0, k, v, 0)

    def body(carry, t):
        m, l, acc, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        m, l, acc = attend(m, l, acc, kc, vc, t)
        return (m, l, acc, kc, vc), None

    (m, l, acc, _, _), _ = lax.scan(
        body, (m, l, acc, k, v), jnp.arange(1, n_shards)
    )
    # causal rows always see at least key 0 <= qpos, so l > 0; guard anyway
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _local_ring_attention_pallas(
    q, k, v, axis_name: str, n_shards: int, causal: bool
):
    """Per-device ring body where each visiting K/V block is consumed by
    the hand-tiled Pallas flash kernel (flash_kernel.py) instead of jnp
    einsums — the local compute runs MXU-tiled with VMEM accumulators.

    Per-block partial results (o_t, lse_t) merge exactly by log-sum-exp
    algebra; causality never needs dynamic offsets inside the kernel
    because each visiting block is wholly before (visible), wholly after
    (skipped — no kernel launch, no ICI-wasting compute), or exactly the
    local diagonal block (the kernel's static causal mask)."""
    from flexflow_tpu.ops.pallas.flash_kernel import flash_attention_tpu

    b, sq, h, d = q.shape
    my_idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def flash(qq, kk, vv, diag):
        return flash_attention_tpu(
            qq, kk, vv, causal=diag, return_lse=True
        )

    def skip(qq, kk, vv):
        return (
            jnp.zeros((b, sq, h, d), qq.dtype),
            jnp.full((b, h, sq), -1e30, jnp.float32),
        )

    def attend(kc, vc, src):
        if not causal:
            return flash(q, kc, vc, False)
        return lax.cond(
            src == my_idx,
            lambda: flash(q, kc, vc, True),
            lambda: lax.cond(
                src < my_idx,
                lambda: flash(q, kc, vc, False),
                lambda: skip(q, kc, vc),
            ),
        )

    def merge(o_run, lse_run, o_t, lse_t):
        # exact combine of partial attentions over disjoint key ranges:
        # softmax(concat) = sum_i softmax_i * exp(lse_i - LSE)
        m = jnp.maximum(lse_run, lse_t)
        w_run = jnp.exp(lse_run - m)
        w_t = jnp.exp(lse_t - m)
        denom = w_run + w_t  # >= 1: the max's weight is exactly 1
        a_run = (w_run / denom).transpose(0, 2, 1)[..., None]
        a_t = (w_t / denom).transpose(0, 2, 1)[..., None]
        o = o_run * a_run + o_t.astype(jnp.float32) * a_t
        return o, m + jnp.log(denom)

    o0, lse0 = attend(k, v, my_idx)

    def body(carry, t):
        o_run, lse_run, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        src = jnp.mod(my_idx - t, n_shards)
        o_t, lse_t = attend(kc, vc, src)
        o_run, lse_run = merge(o_run, lse_run, o_t, lse_t)
        return (o_run, lse_run, kc, vc), None

    (o_run, _, _, _), _ = lax.scan(
        body,
        (o0.astype(jnp.float32), lse0, k, v),
        jnp.arange(1, n_shards),
    )
    return o_run.astype(q.dtype)


def _pallas_ok(q, k, n_shards: int) -> bool:
    from flexflow_tpu.ops.pallas.flash_kernel import supports

    if q.shape[1] % n_shards or k.shape[1] % n_shards:
        return False
    return supports(
        q.shape[1] // n_shards, k.shape[1] // n_shards, q.shape[-1]
    )


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    seq_axis: str,
    causal: bool = False,
    batch_axis: Optional[str] = None,
    head_axis: Optional[str] = None,
    use_pallas: Optional[bool] = None,
):
    """Exact attention with q/k/v sequence-sharded over `mesh[seq_axis]`.

    q, k, v: global [b, s, h, d] arrays (sequence dim sharded on `seq_axis`;
    optionally batch on `batch_axis` and heads on `head_axis`). Returns the
    attention output with the same layout as q.

    use_pallas=None (auto): on TPU, tileable per-device blocks run the
    hand-tiled flash kernel per ring step (MXU-tiled, VMEM accumulators);
    otherwise the jnp online-softmax body (which XLA still fuses well,
    and which CPU tests exercise). True forces the kernel path (the
    Pallas interpreter runs it off-TPU).
    """
    n_shards = mesh.shape[seq_axis]
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and _pallas_ok(
            q, k, n_shards
        )
    body = (
        _local_ring_attention_pallas if use_pallas else _local_ring_attention
    )
    spec = P(batch_axis, seq_axis, head_axis, None)
    # replication checking off: the scan carry mixes locally-created
    # accumulators with ring-permuted blocks
    inner = jax.shard_map(
        functools.partial(
            body,
            axis_name=seq_axis,
            n_shards=n_shards,
            causal=causal,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return inner(q, k, v)
