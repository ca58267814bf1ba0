"""Hand-tiled Pallas TPU flash-decode kernels against the serving KV cache.

The serving engine's decode regime (flexflow_tpu/serving/engine.py) is
memory-bound on the KV-cache read: one (decode) or a handful (verify)
of query positions per sequence attend against up to max_len cached
rows, so the dense jnp paths in ops/attention.py pay for a full
[b, h, w, max_len] f32 score tensor — and, on the block-paged layout,
for gathering every page into a contiguous cache view first. This
module is the kernel family that fills the Pallas hook seams there,
Flash-Decoding style (Dao et al., 2023):

  * **Split-KV online softmax** — the KV-chunk grid dim is innermost
    ("arbitrary", i.e. sequential): each chunk folds an MXU `q @ k^T`
    score tile into running max / sum-exp / weighted-V accumulators
    held in VMEM scratch, and the output tile is written once on the
    last chunk. No score tensor ever exists in HBM — the same trade
    flash_kernel.py makes for training, restricted to the w-query
    forward (no backward: serving never differentiates through the
    cache).
  * **Length gating per chunk** — `lengths` rides in as a
    scalar-prefetch argument, so whole chunks past
    `lengths[i] + w - 1` are skipped (pl.when) and their DMAs
    redirected to chunk 0, the split-KV analog of the causal-block
    skip in flash_kernel.py.
  * **Decode is the w == 1 case of verify** — one kernel body computes
    the staircase mask `key_pos <= lengths[i] + query_offset`
    (ops/attention.verify_attention's semantics); with w = 1 the
    staircase degenerates to decode_attention's `key_pos <= lengths[i]`
    mask. Sharing the body is what keeps greedy speculative decoding
    token-identical to plain decode on the kernel path.
  * **The paged variant walks the block table** — grid (batch, pages):
    the K/V BlockSpec index maps read the scalar-prefetched block table
    to DMA each logical page straight from the pool (PagedAttention,
    Kwon et al., SOSP'23), so the per-step contiguous gather the dense
    paged path pays disappears. Sentinel entries (num_pages) are
    clamped for the DMA and masked in the score tile, so unallocated
    pages are numerically inert exactly like the dense path's
    clamp-and-mask.

Block shapes and the TPU tiling rule. Mosaic takes a block only when its
last two dims are multiples of the (sublane, lane) tile — (8, 128) for
f32, (32, 128) for int8 — or span the whole array dim. The paged
kernels read a pool as [num_pages, page_size, h*d]:

  * a page is one block with ALL its heads. The serving cache keeps its
    pools in exactly this shape (PagedKVCache), so the reshape below is
    the identity there; handed a [num_pages, page_size, h, d] pool it is
    a reshape, which on a TPU's tiled layouts is a relayout of the whole
    pool and not a view (1.1-1.4 s of a 12 s window before PR 27). The
    block is (1, page_size, h*d) — rows are the sublane dim, the whole
    h*d row the lane dim. One program handles every head of a page
    (a static loop over lane slices), so a page is one contiguous DMA
    instead of h strided ones and the grid has h times fewer steps;
  * the int8 scale pools [num_pages, h] are viewed as
    [num_pages, 1, h], block (1, 1, h);
  * the tree mask [b, w, kv] is regrouped to [b, chunks, w, chunk],
    block (1, 1, w, chunk), for both layouts.

Tile size: the contiguous kernel's KV chunk defaults to the
v5e-calibrated 512 rows (calibration/v5e.json "decode_blocks", installed
at compile like the training kernel's flash_blocks) shrunk to the
largest sublane-aligned divisor of max_len; the paged kernel's chunk is
one page (the block table gives no contiguity beyond a page).

`supports()` gates geometry (callers fall back to the dense paths), and
`interpret=None` selects the Pallas interpreter off-TPU
(ops.pallas.resolve_interpret) so the exact kernel code path runs under
JAX_PLATFORMS=cpu — tier-1 tests (tests/test_decode_kernel.py) assert
parity against the dense paths there, and tests/test_pallas_lowering.py
lowers every entry point for TPU from the CPU sandbox.

Shapes at the API boundary match ops/attention.py: q [b, w, h, d],
contiguous cache [b, max_len, h, d], paged pools
[num_pages, page_size, h, d] with block_tables [b, max_pages_per_seq].

Multi-LoRA posture (serving/tenancy/adapters.py): the kernels are
adapter-oblivious by design. Per-slot LoRA deltas land OUTSIDE the
kernel seam — the QKV delta is applied before the cache row write (so
the pool already holds adapted K/V by the time a kernel reads it) and
the output delta is a post-kernel epilogue on the attention result.
Fusing the rank-r gather into the kernel body would add a second
scalar-prefetch table and a per-slot DMA for a few-percent bandwidth
term (see CostModel.adapter_delta_cost); not worth forking the kernel
family. This is why the adapter identity tests can assert bit-identical
kernel-path tokens with a pool attached but no adapters in use.
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
SUBLANES = 8
_MASK = -1e30  # finite mask fill: exp()=0 without inf-inf NaNs (matches
#               the dense paths' fill, so softmax numerics line up)

# modes the ServeConfig.decode_kernel toggle takes (threaded through
# engine hooks into use_kernel below)
MODES = ("auto", "pallas", "dense")

# draft widths past this don't belong to the decode regime (a verify
# step that wide is prefill-shaped; the training kernel serves it)
_MAX_W = 64

# tree-verify widths past this fall back to the dense path: the
# ancestor mask rides as a [b, w, kv] data operand, so its DMA traffic
# grows with w where the staircase was computed from two iotas in-core
_MAX_TREE_W = 32

# process-wide tuned KV-chunk rows for the contiguous kernel, overridden
# from a measured calibration table ("decode_blocks" entry, installed by
# runtime/model.py compile() like flash_kernel's flash_blocks). The
# built-in default mirrors the flash kernel's v5e-measured preference
# for wide K blocks: 512 rows is a 128 KB f32 chunk at head_dim 64 —
# small next to VMEM, wide enough to amortize the per-chunk rescale.
_TUNED = {"block_k": 512}


def set_tuned_decode_blocks(block_k: int) -> None:
    """Install the measured-best KV chunk size (calibration-table
    "decode_blocks" entry; runtime/model.py installs it at compile when
    a calibration file is configured)."""
    _TUNED["block_k"] = int(block_k)


def _pick_chunk(kv_len: int, pref: Optional[int] = None) -> Optional[int]:
    """Largest KV chunk <= pref that divides kv_len and is
    sublane-aligned (the chunk is the second-minor dim of the (bk, d)
    K tile, so 8-row granularity, not the 128-lane rule the training
    kernel's seq-minor layout needs)."""
    b = min(pref or _TUNED["block_k"], kv_len)
    while b >= SUBLANES:
        if kv_len % b == 0 and b % SUBLANES == 0:
            return b
        b -= SUBLANES
    return None


# int8 native tiles are (32, 128) sublane x lane on TPU — a quantized
# page must pack whole int8 sublanes, so the paged quant variant needs
# 32-row page alignment where fp32 needs only 8
_INT8_SUBLANES = 32


def supports(
    w: int, kv_len: int, head_dim: int, page_size: int = 0,
    kv_dtype: str = "fp32",
) -> bool:
    """Whether the kernel family takes this cache geometry. False routes
    the caller to the dense jnp paths (ops/attention.py) — the explicit
    fallback contract, like flash_kernel.supports for training shapes.

    w: query positions per sequence (1 = decode, k+1 = verify);
    kv_len: max_len of the contiguous cache; page_size > 0 checks the
    paged variant instead (its chunk is one page, so the page must be
    sublane-aligned; kv_len is ignored — the walk is table-driven).
    kv_dtype "int8" selects the quantized paged variant's gate: pages
    must pack whole (32, 128) int8 tiles, and only the paged layout
    carries the per-page scale side pools."""
    if not 1 <= w <= _MAX_W or head_dim % SUBLANES:
        return False
    if kv_dtype == "int8":
        # quantized pools exist only on the paged layout; the page must
        # be int8-sublane-aligned or the dense dequant path takes over
        return page_size > 0 and page_size % _INT8_SUBLANES == 0
    if page_size > 0:
        return page_size % SUBLANES == 0
    return kv_len >= 1 and _pick_chunk(kv_len) is not None


def use_kernel(
    mode: str, w: int, kv_len: int, head_dim: int, page_size: int = 0,
    kv_dtype: str = "fp32",
) -> bool:
    """Resolve a ServeConfig.decode_kernel mode for one geometry:
    "dense" never takes the kernel, "pallas" takes it whenever
    supports() passes (interpret mode runs it off-TPU — the CI/test
    path), "auto" additionally requires a real TPU backend (on CPU the
    dense one-query path is the measured-fast choice; interpreting the
    kernel there is a correctness tool, not a serving config)."""
    if mode not in MODES:
        raise ValueError(f"decode_kernel must be one of {MODES}, got {mode!r}")
    if mode == "dense" or not supports(
        w, kv_len, head_dim, page_size, kv_dtype=kv_dtype
    ):
        return False
    return mode == "pallas" or jax.default_backend() == "tpu"


def supports_tree(w: int) -> bool:
    """Width gate for the tree-verify kernel variants, ON TOP of the
    use_kernel()/supports() geometry gate the caller already passed:
    the tree mask is a per-(query, key) data operand, so wide trees pay
    w x the staircase's mask bandwidth — past _MAX_TREE_W the caller
    falls back to the dense tree path (ops/attention.tree_allowed_mask
    under jnp.where), the explicit fallback contract of the family."""
    return 1 <= w <= _MAX_TREE_W


class _Cfg(NamedTuple):
    w: int
    sm_scale: float
    block_k: int
    interpret: bool


def _stair_mask(s, cfg, length, k_start):
    """Apply the staircase mask to a (w, bk) score tile whose keys start
    at global cache position k_start: query row j sees key positions
    <= length + j. With w == 1 this is exactly decode_attention's
    `key_pos <= lengths[i]` mask."""
    kpos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    qoff = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    return jnp.where(kpos <= length + qoff, s, _MASK)


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _MASK)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_softmax_step(s, v, m_scr, l_scr, acc_scr):
    """Fold one masked score tile (w, bk) and its V chunk (bk, d) into
    the running (m, l, acc) accumulators — the flash_kernel.py forward
    update, minus the LSE output serving never needs."""
    m_prev = m_scr[:, :1]  # (w, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)  # masked entries: exp(~-1e30) == 0
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + mxu_dot(p.astype(v.dtype), v, (1, 0))
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _finish(l_scr, acc_scr, dtype):
    # position 0 is visible to every query row (lengths >= 0), so l > 0
    # for live rows; the max guards the padded scratch lanes
    l = jnp.maximum(l_scr[:, :1], 1e-30)
    return (acc_scr[...] / l).astype(dtype)


def _chunked_mask(allowed, chunk: int):
    """[b, w, kv] tree visibility -> [b, kv/chunk, w, chunk] f32, so a
    (w, chunk) mask tile spans the last two dims whole (a legal TPU
    block at any chunk width, where a (w, chunk) window of [b, w, kv]
    needs chunk % 128 == 0)."""
    b, w, kv = allowed.shape
    return (
        allowed.astype(jnp.float32)
        .reshape(b, w, kv // chunk, chunk)
        .transpose(0, 2, 1, 3)
    )


# -- contiguous cache ---------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *rest, cfg, nk, tree):
    """Staircase (tree=False) or tree-masked (tree=True: one extra
    (1, 1, w, bk) mask tile per chunk) split-KV body."""
    mask_ref = rest[0] if tree else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    ib = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    length = len_ref[ib]

    # chunk visible iff it holds at least one key some query row sees
    # (every tree row lives inside the w-row window at positions
    # lengths..lengths + w - 1, so the gate serves both masks)
    @pl.when(ik * cfg.block_k <= length + (cfg.w - 1))
    def _body():
        q = q_ref[0, 0]  # (w, d)
        k = k_ref[0, 0]  # (bk, d)
        s = mxu_dot(q, k, (1, 1)) * cfg.sm_scale  # (w, bk) f32
        if tree:
            s = jnp.where(mask_ref[0, 0] > 0.0, s, _MASK)
        else:
            s = _stair_mask(s, cfg, length, ik * cfg.block_k)
        _online_softmax_step(s, v_ref[0, 0], m_scr, l_scr, acc_scr)

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[0, 0] = _finish(l_scr, acc_scr, o_ref.dtype)


def _contiguous_call(
    q, k_cache, v_cache, lengths, allowed, sm_scale, block_k, interpret
):
    b, w, h, d = q.shape
    kv_len = k_cache.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    bk = block_k or _pick_chunk(kv_len)
    if bk is None or kv_len % bk:
        raise ValueError(
            f"flash decode: cache length {kv_len} not tileable "
            f"(chunk {bk}); use supports() and fall back to dense"
        )
    cfg = _Cfg(w, sm_scale, bk, resolve_interpret(interpret))
    nk = kv_len // bk
    tree = allowed is not None
    qt = q.transpose(0, 2, 1, 3)  # [b, h, w, d]
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)

    def q_map(ib, ih, ik, lens):
        return (ib, ih, 0, 0)

    def visible(ib, ik, lens):
        # skipped (past-length) chunk: redirect the DMA to chunk 0,
        # which the next (ib, ih) program always needs
        return lax.select(ik * bk <= lens[ib] + (w - 1), ik, 0)

    def kv_map(ib, ih, ik, lens):
        return (ib, ih, visible(ib, ik, lens), 0)

    def mask_map(ib, ih, ik, lens):
        # the mask tile follows K's chunk redirect so a skipped chunk's
        # DMA still lands on resident rows
        return (ib, visible(ib, ik, lens), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, w, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
    ]
    operands = [lengths.astype(jnp.int32), qt, kt, vt]
    if tree:
        in_specs.append(pl.BlockSpec((1, 1, w, bk), mask_map))
        operands.append(_chunked_mask(allowed, bk))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, cfg=cfg, nk=nk, tree=tree),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, w, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((w, LANES), jnp.float32),
                pltpu.VMEM((w, LANES), jnp.float32),
                pltpu.VMEM((w, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, w, d), q.dtype),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")
        ),
        interpret=cfg.interpret,
    )(*operands)
    return out.transpose(0, 2, 1, 3)


def flash_verify(
    q,
    k_cache,
    v_cache,
    lengths,
    sm_scale: Optional[float] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """w-query flash attention against the contiguous cache with the
    staircase mask — ops/attention.verify_attention's semantics on the
    split-KV kernel. q: [b, w, h, d]; k_cache/v_cache:
    [b, max_len, h, d]; lengths: [b] int32. Returns [b, w, h, d].
    interpret=None auto-selects the Pallas interpreter off-TPU."""
    return _contiguous_call(
        q, k_cache, v_cache, lengths, None, sm_scale, block_k, interpret
    )


def flash_decode(q, k_cache, v_cache, lengths, **kw):
    """Single-query flash decode — the w == 1 case of flash_verify
    (ops/attention.decode_attention's semantics)."""
    return flash_verify(q, k_cache, v_cache, lengths, **kw)


def flash_verify_tree(
    q,
    k_cache,
    v_cache,
    lengths,
    allowed,
    sm_scale: Optional[float] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """w-query flash attention against the contiguous cache under an
    arbitrary tree-ancestor mask — ops/attention.verify_attention's
    tree_parents semantics on the split-KV kernel. allowed:
    [b, w, max_len], > 0 where query row j may see the key position
    (tree_allowed_mask over the dispatch's parent table): the tree SHAPE
    is data, so one compiled program serves every tree of width w.
    Other shapes as flash_verify. Gate with supports() AND
    supports_tree() before calling."""
    return _contiguous_call(
        q, k_cache, v_cache, lengths, allowed, sm_scale, block_k, interpret
    )


# -- block-paged cache --------------------------------------------------------


def _paged_kernel(
    len_ref, tbl_ref, q_ref, k_ref, v_ref, *rest,
    cfg, num_pages, np_seq, heads, head_dim, quant, tree,
):
    """One page, all heads. k_ref/v_ref: (1, page_size, h*d) — head ih
    is lanes [ih*d, (ih+1)*d). quant adds the (1, 1, h) per-(page,
    head) scale tiles and dequantizes each head's slice INSIDE the
    chunk loop, so no dequantized cache view ever exists outside VMEM;
    tree swaps the staircase for a (1, 1, w, page_size) mask tile.
    Scratch is per head: m/l (h, w, LANES), acc (h, w, d)."""
    rest = list(rest)
    ks_ref, vs_ref = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    mask_ref = rest.pop(0) if tree else None
    o_ref, m_scr, l_scr, acc_scr = rest
    page_size = cfg.block_k
    ib = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    length = len_ref[ib]

    # a page contributes iff it is inside the staircase AND allocated
    # (sentinel entries sit past the length gate whenever the engine's
    # allocator invariants hold — the table check is defensive, for
    # standalone callers handing the kernel ragged tables)
    @pl.when(
        (ip * page_size <= length + (cfg.w - 1))
        & (tbl_ref[ib, ip] < num_pages)
    )
    def _body():
        k_page = k_ref[0]  # (page_size, h*d)
        v_page = v_ref[0]
        for ih in range(heads):
            lanes = slice(ih * head_dim, (ih + 1) * head_dim)
            q = q_ref[0, ih]  # (w, d)
            k = k_page[:, lanes]  # (page_size, d)
            v = v_page[:, lanes]
            if quant:
                q = q.astype(jnp.float32)
                k = k.astype(jnp.float32) * ks_ref[0, :, ih:ih + 1]
                v = v.astype(jnp.float32) * vs_ref[0, :, ih:ih + 1]
            s = mxu_dot(q, k, (1, 1)) * cfg.sm_scale  # (w, page_size)
            if tree:
                s = jnp.where(mask_ref[0, 0] > 0.0, s, _MASK)
            else:
                s = _stair_mask(s, cfg, length, ip * page_size)
            _online_softmax_step(
                s, v, m_scr.at[ih], l_scr.at[ih], acc_scr.at[ih]
            )

    @pl.when(ip == np_seq - 1)
    def _done():
        for ih in range(heads):
            o_ref[0, ih] = _finish(
                l_scr.at[ih], acc_scr.at[ih], o_ref.dtype
            )


def _paged_call(
    q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale, allowed,
    sm_scale, interpret,
):
    b, w, h, d = q.shape
    num_pages, page_size = k_pool.shape[0], k_pool.shape[1]
    np_seq = block_tables.shape[1]
    quant = k_scale is not None
    tree = allowed is not None
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    align = _INT8_SUBLANES if quant else SUBLANES
    if page_size % align:
        raise ValueError(
            f"paged flash decode{' (int8)' if quant else ''}: page_size "
            f"{page_size} is not sublane-aligned ({align}); use "
            "supports() and fall back to dense"
        )
    cfg = _Cfg(w, sm_scale, page_size, resolve_interpret(interpret))
    qt = q.transpose(0, 2, 1, 3)  # [b, h, w, d]

    def q_map(ib, ip, lens, tbl):
        return (ib, 0, 0, 0)

    def page_of(ib, ip, lens, tbl):
        # skipped pages prefetch the sequence's first page; sentinel
        # entries clamp to a real page (their scores are masked)
        ip = lax.select(ip * page_size <= lens[ib] + (w - 1), ip, 0)
        return jnp.minimum(tbl[ib, ip], num_pages - 1)

    def kv_map(ib, ip, lens, tbl):
        return (page_of(ib, ip, lens, tbl), 0, 0)

    def mask_map(ib, ip, lens, tbl):
        # the mask is over LOGICAL positions: its tile is just the page
        # index — no table lookup, every logical tile is resident
        return (ib, ip, 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, w, d), q_map),
        pl.BlockSpec((1, page_size, h * d), kv_map),
        pl.BlockSpec((1, page_size, h * d), kv_map),
    ]
    operands = [
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        qt,
        k_pool.reshape(num_pages, page_size, h * d),
        v_pool.reshape(num_pages, page_size, h * d),
    ]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, h), kv_map)] * 2
        operands += [
            k_scale.astype(jnp.float32).reshape(num_pages, 1, h),
            v_scale.astype(jnp.float32).reshape(num_pages, 1, h),
        ]
    if tree:
        in_specs.append(pl.BlockSpec((1, 1, w, page_size), mask_map))
        operands.append(_chunked_mask(allowed, page_size))
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel,
            cfg=cfg,
            num_pages=num_pages,
            np_seq=np_seq,
            heads=h,
            head_dim=d,
            quant=quant,
            tree=tree,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, np_seq),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, w, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((h, w, LANES), jnp.float32),
                pltpu.VMEM((h, w, LANES), jnp.float32),
                pltpu.VMEM((h, w, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, w, d), q.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=cfg.interpret,
    )(*operands)
    return out.transpose(0, 2, 1, 3)


def paged_flash_verify(
    q,
    k_pool,
    v_pool,
    block_tables,
    lengths,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """w-query flash attention that walks the block table page by page —
    ops/attention.paged_verify_attention's semantics with NO contiguous
    gather (the PagedAttention kernel shape). q: [b, w, h, d];
    k_pool/v_pool: [num_pages, page_size, h, d]; block_tables:
    [b, max_pages_per_seq] int32 (sentinel num_pages = unallocated);
    lengths: [b] int32. Returns [b, w, h, d].

    Rows whose VISIBLE positions point at sentinel pages return zeros
    (no page contributes), where the dense path softmaxes over the
    clamped page's stale rows instead. Both only happens for dead
    slots — the engine allocates every page inside a live slot's
    lengths + w before the step, so live rows agree exactly — and dead
    rows' outputs are discarded by the scheduler either way."""
    return _paged_call(
        q, k_pool, v_pool, block_tables, lengths, None, None, None,
        sm_scale, interpret,
    )


def paged_flash_decode(q, k_pool, v_pool, block_tables, lengths, **kw):
    """Single-query paged flash decode — the w == 1 case of
    paged_flash_verify (ops/attention.paged_decode_attention's
    semantics)."""
    return paged_flash_verify(q, k_pool, v_pool, block_tables, lengths, **kw)


def paged_flash_verify_quant(
    q,
    k_pool,
    v_pool,
    k_scale,
    v_scale,
    block_tables,
    lengths,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """paged_flash_verify over int8 pools with fp32 per-(page, head)
    scale side pools [num_pages, h]: dequant fuses into the page walk
    (each page's scale rides the same scalar-prefetched table lookup as
    its K/V tile). Semantics match paged_verify_attention's dense
    dequant path on the visible positions."""
    return _paged_call(
        q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale, None,
        sm_scale, interpret,
    )


def paged_flash_decode_quant(
    q, k_pool, v_pool, k_scale, v_scale, block_tables, lengths, **kw
):
    """Single-query int8 paged flash decode — the w == 1 case of
    paged_flash_verify_quant."""
    return paged_flash_verify_quant(
        q, k_pool, v_pool, k_scale, v_scale, block_tables, lengths, **kw
    )


def paged_flash_verify_tree(
    q,
    k_pool,
    v_pool,
    block_tables,
    lengths,
    allowed,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """Tree-masked w-query flash attention walking the block table —
    ops/attention.paged_verify_attention's tree_parents semantics with
    no contiguous gather. allowed: [b, w, max_pages_per_seq * page_size]
    over LOGICAL positions. Other shapes as paged_flash_verify."""
    return _paged_call(
        q, k_pool, v_pool, block_tables, lengths, None, None, allowed,
        sm_scale, interpret,
    )


def paged_flash_verify_tree_quant(
    q,
    k_pool,
    v_pool,
    k_scale,
    v_scale,
    block_tables,
    lengths,
    allowed,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """paged_flash_verify_tree over int8 pools with fp32 per-(page,
    head) scale side pools — dequant fuses into the page walk exactly
    as in paged_flash_verify_quant, the tree mask rides as in
    paged_flash_verify_tree."""
    return _paged_call(
        q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale,
        allowed, sm_scale, interpret,
    )
