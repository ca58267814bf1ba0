"""Hand-tiled Pallas TPU flash attention: forward + custom-VJP backward.

This is the framework's own MXU-tiled attention kernel — the piece the
reference implements as one opaque cudnnMultiHeadAttnForward call per shard
(reference: src/ops/attention.cu:35). Design:

  * **Forward** — grid (batch, heads, q_blocks, k_blocks), k innermost.
    Each (q_block, k_block) step computes an MXU matmul `q @ k^T` on
    VMEM-resident tiles and folds it into online-softmax accumulators
    (m, l, acc) held in VMEM scratch across the k iterations; the output
    tile and the row log-sum-exp are written once, on the last k step.
    The [s, s] score matrix never exists in HBM.
  * **Backward** — two kernels, both recomputing probabilities from
    (q, k, lse) instead of loading them (flash attention's defining
    trade): a dq kernel accumulating over k blocks and a dk/dv kernel
    accumulating over q blocks. Residuals are just (q, k, v, o, lse) —
    O(s·d), not O(s²).
  * **LSE is a public output** (`return_lse=True`): partial results from
    different key ranges merge exactly via log-sum-exp algebra, which is
    what lets ring attention (pallas/ring_attention.py) run this kernel
    per ppermute step under shard_map and combine blocks across devices —
    the multi-device long-context path runs MXU-tiled compute.
  * **Causal** skips fully-masked k blocks (the index maps redirect the
    skipped block's DMA to a useful one, after the library kernel's
    prefetch idiom) — ~2x at long sequence.

  * **Short sequences** (`supports_whole`: one head's [s, s] float32
    block fits VMEM; 512, and 1,024 in bf16) take the WHOLE-SEQUENCE
    form further down instead: one call a pass over the projections'
    own [b, s, h * d] rows, one-pass softmax, no [b, h, s, d] array.
    The grid form is for the long sequences and for `return_lse`.

Block sizes default to the v5e-measured 512x1024 (a ~2 MB f32 score tile
plus ~128 KB operand tiles at head_dim 64 — comfortable in VMEM) and can
be overridden per-call or process-wide from a measured calibration table
(`set_tuned_blocks`, wired from scripts/calibrate.py --tune-flash).

Shapes are [b, s, h, d] at the API boundary (the layout ops/attention.py
produces); the kernel works on [b, h, s, d].
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops.pallas import compiler_params as _compiler_params
from flexflow_tpu.ops.pallas import mxu_dot, resolve_interpret

LANES = 128
_MASK = -1e30  # finite mask value: keeps exp()=0 without inf-inf NaNs

# process-wide tuned defaults (overridden by set_tuned_blocks). The
# built-ins are the v5e-measured winner of scripts/calibrate.py
# --tune-flash at seq 4096 (4.01 ms vs 5.49 for 512x512: a wider k block
# amortizes each q tile's revisits into more MXU work per program).
_TUNED = {"block_q": 512, "block_k": 1024}


def set_tuned_blocks(block_q: int, block_k: int) -> None:
    """Install measured-best block sizes (scripts/calibrate.py
    --tune-flash persists them to the calibration table; the executor
    installs them at compile when a calibration file is configured)."""
    _TUNED["block_q"] = int(block_q)
    _TUNED["block_k"] = int(block_k)


def _pick_block(pref: int, seq: int) -> Optional[int]:
    """Largest block <= pref that divides seq and is lane-aligned."""
    b = min(pref, seq)
    while b >= LANES:
        if seq % b == 0 and b % LANES == 0:
            return b
        b //= 2
    return None


def supports(sq: int, sk: int, d: int) -> bool:
    """Whether the hand-tiled kernel can run this shape (callers fall
    back to the jnp blockwise formulation otherwise)."""
    return (
        _pick_block(_TUNED["block_q"], sq) is not None
        and _pick_block(_TUNED["block_k"], sk) is not None
        and d % 8 == 0
    )


class _Cfg(NamedTuple):
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    interpret: bool


def _below_or_on_diag(iq, block_q, ik, block_k):
    """True when k block `ik` holds at least one key visible to q block
    `iq` under a causal mask (global positions, same origin)."""
    return ik * block_k < (iq + 1) * block_q


def _causal_guard(cfg, iq, ik):
    """Decorator running the body only on visible blocks: non-causal
    visits every block; causal skips fully-masked ones (their DMAs are
    redirected by the index maps)."""

    def guard(body):
        if cfg.causal:
            pl.when(_below_or_on_diag(iq, cfg.block_q, ik, cfg.block_k))(body)
        else:
            body()

    return guard


def _mask_causal(s, cfg, iq, ik):
    """Apply the causal mask to a (block_q, block_k) score tile at block
    coordinates (iq, ik)."""
    if not cfg.causal:
        return s
    qpos = iq * cfg.block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * cfg.block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, _MASK)


# -- forward ----------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, cfg, nk
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _MASK)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @_causal_guard(cfg, iq, ik)
    def _body():
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bk, d)
        v = v_ref[0, 0]  # (bk, d)
        s = mxu_dot(q, k, (1, 1)) * cfg.sm_scale  # (bq, bk) f32
        s = _mask_causal(s, cfg, iq, ik)
        m_prev = m_scr[:, :1]  # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # masked entries: exp(~-1e30) == 0
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + mxu_dot(
            p.astype(v.dtype), v, (1, 0)
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        lnz = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_scr[...] / lnz).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(lnz), lse_ref.shape[2:]
        )


def _fwd(cfg: _Cfg, q, k, v):
    """q,k,v: [b, h, s, d] -> (o [b,h,sq,d], lse [b,h,sq] f32)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq = sq // cfg.block_q
    nk = sk // cfg.block_k
    grid = (b, h, nq, nk)

    def q_map(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    def kv_map(ib, ih, iq, ik):
        if cfg.causal:
            # skipped (fully-masked) block: prefetch block 0, the first
            # one the NEXT q row-block will need
            ik = lax.select(
                _below_or_on_diag(iq, cfg.block_q, ik, cfg.block_k), ik, 0
            )
        return (ib, ih, ik, 0)

    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, cfg.block_q, d), q_map),
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map),
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, cfg.block_q, d), q_map),
                pl.BlockSpec((1, 1, cfg.block_q, LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((cfg.block_q, LANES), jnp.float32),
                pltpu.VMEM((cfg.block_q, LANES), jnp.float32),
                pltpu.VMEM((cfg.block_q, d), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=cfg.interpret,
    )(q, k, v)
    return o, lse[..., 0]


# -- backward ---------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_scr, *, cfg, nk
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @_causal_guard(cfg, iq, ik)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # (bq, 1)
        delta = dl_ref[0, 0][:, :1]
        s = mxu_dot(q, k, (1, 1)) * cfg.sm_scale
        s = _mask_causal(s, cfg, iq, ik)
        p = jnp.exp(s - lse)  # normalized probabilities
        dp = mxu_dot(do, v, (1, 1))
        ds = p * (dp - delta) * cfg.sm_scale
        dq_scr[...] += mxu_dot(ds.astype(k.dtype), k, (1, 0))

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
    dk_ref, dv_ref, dk_scr, dv_scr, *, cfg, nq,
):
    ik = pl.program_id(2)  # kv outer
    iq = pl.program_id(3)  # q inner (accumulated)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @_causal_guard(cfg, iq, ik)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = dl_ref[0, 0][:, :1]
        s = mxu_dot(q, k, (1, 1)) * cfg.sm_scale
        s = _mask_causal(s, cfg, iq, ik)
        p = jnp.exp(s - lse)  # (bq, bk)
        # dv += p^T @ do  — contract the q (sublane) dim of both
        dv_scr[...] += mxu_dot(p.astype(do.dtype), do, (0, 0))
        dp = mxu_dot(do, v, (1, 1))
        ds = p * (dp - delta) * cfg.sm_scale
        dk_scr[...] += mxu_dot(ds.astype(q.dtype), q, (0, 0))

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# -- custom-VJP wrapper ------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Cfg, q, k, v):
    o, _ = _fwd(cfg, q, k, v)
    return o


def _flash_fwd_rule(cfg, q, k, v):
    o, lse = _fwd(cfg, q, k, v)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(cfg, res, do):
    q, k, v, o, lse = res
    # delta_i = rowsum(dO * O) — the softmax-jacobian correction
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return _bwd_from_delta(cfg, q, k, v, lse, do, delta)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_with_lse(cfg: _Cfg, q, k, v):
    return _fwd(cfg, q, k, v)


def _flash_with_lse_fwd(cfg, q, k, v):
    o, lse = _fwd(cfg, q, k, v)
    return (o, lse), (q, k, v, o, lse)


def _flash_with_lse_bwd(cfg, res, cts):
    """Backward of the (o, lse) pair. The lse cotangent needs no extra
    kernel: d lse / ds_j = p_j (softmax probabilities), so g_lse enters
    ds = p * (dp - delta + g_lse) — i.e. it shifts the delta correction
    stream by -g_lse. dv = p^T dO is unaffected. Ring attention's
    log-sum-exp combine produces exactly this cotangent structure."""
    q, k, v, o, lse = res
    do, dlse = cts
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ) - dlse.astype(jnp.float32)
    return _bwd_from_delta(cfg, q, k, v, lse, do, delta)


def _bwd_from_delta(cfg, q, k, v, lse, do, delta):
    """The two backward pallas_calls, parameterized by an explicit delta
    stream (shared by the plain and with-lse VJPs)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq = sq // cfg.block_q
    nk = sk // cfg.block_k
    lse_b = jnp.broadcast_to(lse[..., None], (b, h, sq, LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (b, h, sq, LANES))

    def q_map(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    def kv_map(ib, ih, iq, ik):
        if cfg.causal:
            ik = lax.select(
                _below_or_on_diag(iq, cfg.block_q, ik, cfg.block_k), ik, 0
            )
        return (ib, ih, ik, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, cfg.block_q, d), q_map),
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map),
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map),
                pl.BlockSpec((1, 1, cfg.block_q, d), q_map),
                pl.BlockSpec((1, 1, cfg.block_q, LANES), q_map),
                pl.BlockSpec((1, 1, cfg.block_q, LANES), q_map),
            ],
            out_specs=[pl.BlockSpec((1, 1, cfg.block_q, d), q_map)],
            scratch_shapes=[pltpu.VMEM((cfg.block_q, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=cfg.interpret,
    )(q, k, v, do, lse_b, delta_b)[0]

    def q_map2(ib, ih, ik, iq):
        if cfg.causal:
            iq = lax.select(
                _below_or_on_diag(iq, cfg.block_q, ik, cfg.block_k),
                iq,
                lax.div(ik * cfg.block_k, cfg.block_q),
            )
        return (ib, ih, iq, 0)

    def kv_map2(ib, ih, ik, iq):
        return (ib, ih, ik, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, nq=nq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, h, nk, nq),
            in_specs=[
                pl.BlockSpec((1, 1, cfg.block_q, d), q_map2),
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map2),
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map2),
                pl.BlockSpec((1, 1, cfg.block_q, d), q_map2),
                pl.BlockSpec((1, 1, cfg.block_q, LANES), q_map2),
                pl.BlockSpec((1, 1, cfg.block_q, LANES), q_map2),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map2),
                pl.BlockSpec((1, 1, cfg.block_k, d), kv_map2),
            ],
            scratch_shapes=[
                pltpu.VMEM((cfg.block_k, d), jnp.float32),
                pltpu.VMEM((cfg.block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=cfg.interpret,
    )(q, k, v, do, lse_b, delta_b)
    return dq, dk, dv


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


# -- whole-sequence form -------------------------------------------------------
#
# The same kernel at sequences short enough that ONE head's [sk, sq]
# float32 score block, and its backward's temporaries, sit in VMEM: what
# the grid form pays for at a long sequence (online softmax, accumulators
# across k blocks, a program a (batch, head), [b, h, s, d] operands) buys
# nothing here. One call forward and one backward; q, k, v, the output and
# the cotangents are the projections' own rows [b, s, h * d]; a block is
# (1, s, block_heads * d) lanes, its heads worked one after another by
# lane slices of d; softmax in one pass; the log-sum-exp is [b, h, s]
# with s on the lanes, and the backward makes delta itself.
#
# Both bodies work on the TRANSPOSED scores [sk, sq]. The softmax's max
# and sum then run down the sublanes (plain vector maxima and adds, no
# cross-lane reduction), the log-sum-exp and delta are ROWS as they lie in
# HBM, and of the seven products only the two that yield [sq, d] results
# (o and dq) are made transposed, [d, sq]: the heads of one lane tile are
# stacked and turned once a tile, a [128, sq] float32 transpose where the
# plain orientation turned a [sq, sk] block a head (measured, v5e, 64 x 512
# x 16 x 64 bf16, the kernel alone on rows: 2.62 ms forward + backward
# against 2.95 in the plain orientation and 2.86 for the chunked scan;
# scripts/probe_attn_whole.py, PR 59).

# what a grid step may hold by `_whole_vmem_bytes`' count, and the scoped
# limit the two calls ask Mosaic for (v5e: 128 MiB of VMEM a core)
_WHOLE_VMEM_BUDGET = 48 << 20
_WHOLE_VMEM_LIMIT = 96 << 20


class _WholeCfg(NamedTuple):
    causal: bool
    sm_scale: float
    heads: int
    block_heads: int
    interpret: bool


def _whole_vmem_bytes(sq, sk, d, itemsize, block_heads) -> int:
    """VMEM a grid step of the backward (the larger call) holds: its eight
    operand blocks (q, o, dO, dq of sq rows; k, v, dk, dv of sk), double
    buffered, and one head's temporaries: the transposed scores,
    probabilities, dP and dS in float32 and the two that are rounded to
    the operands' dtype for the MXU."""
    blocks = 2 * 4 * (sq + sk) * block_heads * d * itemsize
    return blocks + sq * sk * (4 * 4 + 2 * itemsize)


def whole_block_heads(sq, sk, heads, d, itemsize=4) -> Optional[int]:
    """Heads a grid step of the whole-sequence form works on a shape it
    takes, else None: self-attention lengths in whole lane tiles, heads
    that pack whole lane tiles (64 or 128 wide, say), and the FEWEST
    heads whose block Mosaic takes (the log-sum-exp's rows in whole
    sublane tiles, or all of them) within `_WHOLE_VMEM_BUDGET`. Fewest,
    because a body is unrolled a head and every process traces and
    lowers it (16 heads: 1.3 s on the chip machine's host, in OLMoE's
    load check), while 8 and 16 heads a step run alike (4.219 against
    4.251 ms a layer; scripts/probe_attn_whole.py, PR 59)."""
    if sq != sk or sq % LANES or (LANES % d and d % LANES):
        return None
    for hb in range(1, heads + 1):
        if heads % hb or hb * d % LANES or (hb != heads and hb % 8):
            continue
        if _whole_vmem_bytes(sq, sk, d, itemsize, hb) <= _WHOLE_VMEM_BUDGET:
            return hb
    return None


def supports_whole(sq, sk, heads, d, itemsize=4) -> bool:
    """Whether `flash_attention_tpu` runs this shape in its whole-sequence
    form (float32 operands unless told: the larger blocks)."""
    return whole_block_heads(sq, sk, heads, d, itemsize) is not None


def _scale_folds(sm_scale: float) -> bool:
    """A power of two scales q exactly in any float dtype (1/8 at head_dim
    64): [s, d] multiplies in place of [s, s]."""
    return math.frexp(sm_scale)[0] == 0.5


def _whole_tiles(cfg, d):
    """The block's heads by lane tile: (lanes of the tile, [(head of the
    block, its lanes)]), heads narrower than a tile side by side."""
    group = max(1, LANES // d)
    for t in range(cfg.block_heads // group):
        yield slice(t * group * d, (t + 1) * group * d), [
            (j, slice(j * d, (j + 1) * d))
            for j in range(t * group, (t + 1) * group)
        ]


def _whole_scores(q_ref, k_ref, lanes, cfg):
    """One head's q (scaled where the scale folds) and its transposed
    float32 scores [sk, sq], scaled and causally masked."""
    q = q_ref[0, :, lanes]
    if _scale_folds(cfg.sm_scale):
        q = q * cfg.sm_scale
    st = mxu_dot(k_ref[0, :, lanes], q, (1, 1))
    if not _scale_folds(cfg.sm_scale):
        st = st * cfg.sm_scale
    if cfg.causal:
        kpos = lax.broadcasted_iota(jnp.int32, st.shape, 0)
        qpos = lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(qpos >= kpos, st, _MASK)
    return q, st


def _whole_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, cfg, d):
    for tile, heads in _whole_tiles(cfg, d):
        outs = []
        for j, lanes in heads:
            _, st = _whole_scores(q_ref, k_ref, lanes, cfg)  # (sk, sq)
            m = jnp.max(st, axis=0, keepdims=True)  # (1, sq)
            e = jnp.exp(st - m)
            l = jnp.sum(e, axis=0, keepdims=True)
            # normalised BEFORE the rounding to the operands' dtype, where
            # scaled_dot_product_attention rounds its probabilities
            pt = (e * (1.0 / l)).astype(v_ref.dtype)
            outs.append(mxu_dot(v_ref[0, :, lanes], pt, (0, 0)))  # (d, sq)
            lse_ref[0, j : j + 1, :] = m + jnp.log(l)
        ot = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        o_ref[0, :, tile] = ot.T.astype(o_ref.dtype)


def _whole_bwd_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    *, cfg, d,
):
    for tile, heads in _whole_tiles(cfg, d):
        # delta_i = rowsum(dO_i * O_i) of each head, as rows over sq: the
        # tile's products turned once, then summed down the sublanes
        oot = (
            do_ref[0, :, tile].astype(jnp.float32)
            * o_ref[0, :, tile].astype(jnp.float32)
        ).T  # (lanes of the tile, sq)
        dqs = []
        for g, (j, lanes) in enumerate(heads):
            q, st = _whole_scores(q_ref, k_ref, lanes, cfg)
            k, do = k_ref[0, :, lanes], do_ref[0, :, lanes]
            pt = jnp.exp(st - lse_ref[0, j : j + 1, :])  # normalised
            dpt = mxu_dot(v_ref[0, :, lanes], do, (1, 1))  # (sk, sq)
            delta = jnp.sum(oot[g * d : (g + 1) * d], axis=0, keepdims=True)
            dst = (pt * (dpt - delta)).astype(q.dtype)
            dv_ref[0, :, lanes] = mxu_dot(
                pt.astype(do.dtype), do, (1, 0)
            ).astype(dv_ref.dtype)
            # q carries the scale where it folds: dk = dS^T (scale q)
            dk = mxu_dot(dst, q, (1, 0))
            if not _scale_folds(cfg.sm_scale):
                dk = dk * cfg.sm_scale
            dk_ref[0, :, lanes] = dk.astype(dk_ref.dtype)
            dqs.append(mxu_dot(k, dst, (0, 0)))  # (d, sq)
        dqt = dqs[0] if len(dqs) == 1 else jnp.concatenate(dqs, axis=0)
        dq_ref[0, :, tile] = (dqt.T * cfg.sm_scale).astype(dq_ref.dtype)


def _whole_specs(cfg, b, sq, sk, d):
    lanes = cfg.block_heads * d
    rows = lambda s: pl.BlockSpec((1, s, lanes), lambda ib, ih: (ib, 0, ih))
    lse = pl.BlockSpec((1, cfg.block_heads, sq), lambda ib, ih: (ib, ih, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_WHOLE_VMEM_LIMIT,
    )
    return (b, cfg.heads // cfg.block_heads), rows(sq), rows(sk), lse, params


@functools.partial(jax.jit, static_argnums=0)
def _whole_fwd(cfg: _WholeCfg, q, k, v):
    """q, k, v: [b, s, h * d] -> (o [b, sq, h * d], lse [b, h, sq] f32).
    Behind a jit of its own, as `_whole_bwd`: the nodes of a step that
    share a shape trace and lower the kernel once. No `cost_estimate`:
    told a call's bytes, XLA stages operands through fast memory ahead
    of it (PERF.md, PR 48)."""
    b, sq, hd = q.shape
    sk, d = k.shape[1], hd // cfg.heads
    grid, q_rows, k_rows, lse, params = _whole_specs(cfg, b, sq, sk, d)
    return pl.pallas_call(
        functools.partial(_whole_fwd_kernel, cfg=cfg, d=d),
        grid=grid,
        in_specs=[q_rows, k_rows, k_rows],
        out_specs=[q_rows, lse],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, cfg.heads, sq), jnp.float32),
        ],
        compiler_params=params,
        interpret=cfg.interpret,
        name="flash_whole_fwd",
    )(q, k, v)


@functools.partial(jax.jit, static_argnums=0)
def _whole_bwd(cfg: _WholeCfg, q, k, v, o, lse, do):
    b, sq, hd = q.shape
    sk, d = k.shape[1], hd // cfg.heads
    grid, q_rows, k_rows, lse_rows, params = _whole_specs(cfg, b, sq, sk, d)
    return tuple(
        pl.pallas_call(
            functools.partial(_whole_bwd_kernel, cfg=cfg, d=d),
            grid=grid,
            in_specs=[q_rows, k_rows, k_rows, q_rows, q_rows, lse_rows],
            out_specs=[q_rows, k_rows, k_rows],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            compiler_params=params,
            interpret=cfg.interpret,
            name="flash_whole_bwd",
        )(q, k, v, o, do, lse)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_whole(cfg: _WholeCfg, q, k, v):
    return _whole_fwd(cfg, q, k, v)[0]


def _flash_whole_fwd_rule(cfg, q, k, v):
    o, lse = _whole_fwd(cfg, q, k, v)
    return o, (q, k, v, o, lse)


def _flash_whole_bwd_rule(cfg, res, do):
    return _whole_bwd(cfg, *res, do)


_flash_whole.defvjp(_flash_whole_fwd_rule, _flash_whole_bwd_rule)


# -- public API --------------------------------------------------------------


def flash_attention_rows(
    q,
    k,
    v,
    heads: int,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_heads: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """The whole-sequence form on the projections' own rows: q, k, v
    [b, s, heads * d] -> [b, s, heads * d], for a shape `supports_whole`
    takes (`block_heads` None: `whole_block_heads`' choice). A
    caller that holds rows calls this and never makes a [b, s, h, d]
    array: XLA's TPU layout assignment gives such a 4-D bfloat16 value a
    sequence-minor layout wherever it stands alone (a bias add outside a
    `shard_map`), and the call then costs a relayout copy an operand."""
    b, sq, hd = q.shape
    sk, d = k.shape[1], hd // heads
    hb = block_heads or whole_block_heads(
        sq, sk, heads, d, jnp.dtype(q.dtype).itemsize
    )
    if hb is None or sq != sk or heads % hb:
        raise ValueError(
            f"flash_attention_rows: seq ({sq}, {sk}), {heads} heads of {d} "
            f"in blocks of {hb}; ask supports_whole() first"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    cfg = _WholeCfg(causal, sm_scale, heads, hb, resolve_interpret(interpret))
    return _flash_whole(cfg, q, k, v)


def flash_attention_tpu(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    return_lse: bool = False,
    interpret: Optional[bool] = None,
    block_heads: Optional[int] = None,
):
    """Hand-tiled flash attention. q, k, v: [b, s, h, d].

    Returns [b, s, h, d] (and, with return_lse, the row log-sum-exp
    [b, h, s] in f32 — the residual that makes per-device partial results
    mergeable, ring_attention.py). interpret=None auto-selects the Pallas
    interpreter off-TPU so the same code path is testable on CPU.

    A self-attention short enough for `supports_whole` runs in the
    whole-sequence form, `block_heads` heads a grid step (None:
    `whole_block_heads`' choice); a call that names `block_q` / `block_k` or asks for the
    log-sum-exp, and every longer sequence, takes the grid form."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if not (return_lse or block_q or block_k) and (
        block_heads or supports_whole(sq, sk, h, d, jnp.dtype(q.dtype).itemsize)
    ):
        # [b, s, h, d] <-> [b, s, h * d]: the same rows, no copy
        o = flash_attention_rows(
            *(a.reshape(*a.shape[:2], h * d) for a in (q, k, v)), h,
            causal=causal, sm_scale=sm_scale, block_heads=block_heads,
            interpret=interpret,
        )
        return o.reshape(q.shape)
    bq = block_q or _pick_block(_TUNED["block_q"], sq)
    bk = block_k or _pick_block(_TUNED["block_k"], sk)
    if bq is None or bk is None or sq % bq or sk % bk:
        raise ValueError(
            f"flash_attention_tpu: seq ({sq}, {sk}) not tileable by "
            f"({bq}, {bk}); use supports() and fall back to blockwise"
        )
    cfg = _Cfg(causal, sm_scale, bq, bk, resolve_interpret(interpret))
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if return_lse:
        o, lse = _flash_with_lse(cfg, qt, kt, vt)
        return o.transpose(0, 2, 1, 3), lse
    o = _flash(cfg, qt, kt, vt)
    return o.transpose(0, 2, 1, 3)
