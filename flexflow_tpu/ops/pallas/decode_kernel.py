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
  * **The paged variant walks the block table, a block of pages at a
    time** — grid (batch,), the pools left in HBM: for each LIVE block
    of `paged_block(...).pages` logical pages of a slot the kernel
    itself copies the pages the scalar-prefetched block table names,
    straight from the pool (PagedAttention, Kwon et al., SOSP'23), into
    one of two (rows, h*d) VMEM buffers, one block ahead of the one it
    computes, across slots too. So the per-step contiguous gather the
    dense paged path pays disappears, a slot costs its live blocks and
    a dead slot nearly nothing. Sentinel entries (num_pages) are clamped
    for the copy and masked in the score tile, so unallocated pages are
    numerically inert exactly like the dense path's clamp-and-mask.

Block shapes and the TPU tiling rule. Mosaic takes a block, or a copy,
only when its last two dims are multiples of the (sublane, lane) tile —
(8, 128) for f32, (32, 128) for int8 — or span the whole array dim. The
paged kernels read a pool as [num_pages, page_size, h*d]:

  * a page is one contiguous copy with ALL its heads. The serving cache
    keeps its pools in exactly this shape (PagedKVCache), so the reshape
    below is the identity there; handed a [num_pages, page_size, h, d]
    pool it is a reshape, which on a TPU's tiled layouts is a relayout
    of the whole pool and not a view (1.1-1.4 s of a 12 s window before
    PR 27). Compiled, the row of h*d must be whole 128-lane tiles
    (`use_kernel`'s `heads`); the interpreter takes any;
  * heads go through the MXU a group at a time with their queries laid
    block-diagonally (`_paged_kernel`): for decode all heads' scores are
    ONE (h, h*d) x (rows, h*d)^T matmul and the softmax runs on (h,
    rows), not h matmuls of M = 1 a page;
  * the int8 scale pools [num_pages, h] are gathered by the wrapper into
    a slot's [blocks, h, rows] tiles, the layout of a block's scores;
  * the tree mask [b, w, kv] is regrouped to [b, chunks, w, chunk] for
    both layouts (the contiguous kernel takes a (1, 1, w, chunk) block a
    grid step, the paged one a slot's chunks at once).

Tile size: the contiguous kernel's KV chunk defaults to the
v5e-calibrated 512 rows (calibration/v5e.json "decode_blocks", installed
at compile like the training kernel's flash_blocks) shrunk to the
largest sublane-aligned divisor of max_len. The paged kernel's block is
`paged_block()`: from page_size, h*d, the pool's itemsize, w and the
table's width alone, as many pages as fit `_VMEM_BUDGET` up to
`_MAX_BLOCK_ROWS` rows (8 pages of 16 for both serving cells, one page
where page_size == max_seq_len). Nothing sets it. Why copies by hand and
not one BlockSpec a page (PR 29, measured on a v5e): a grid of (slots,
blocks) with the pool handed to `pallas_call` once a page of the block
evaluates 2 x slots x table-width index maps a call whatever the block
size, 90 us a call at the cells' geometry, where this pays for live
pages only (11 us a call with every slot dead).

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
    paged variant instead (its block is whole pages, so the page must be
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
    kv_dtype: str = "fp32", heads: int = 0,
) -> bool:
    """Resolve a ServeConfig.decode_kernel mode for one geometry:
    "dense" never takes the kernel, "pallas" takes it whenever
    supports() passes (interpret mode runs it off-TPU — the CI/test
    path), "auto" additionally requires a real TPU backend (on CPU the
    dense one-query path is the measured-fast choice; interpreting the
    kernel there is a correctness tool, not a serving config).

    `heads` (the heads the kernel will see: a shard's, under a head
    shard) matters to the paged kernel COMPILED: it copies whole cache
    rows of heads * head_dim out of the pool itself, and Mosaic takes
    such a copy only in whole 128-lane tiles. A toy model's narrower row
    is served dense on a TPU; the interpreter has no such rule."""
    if mode not in MODES:
        raise ValueError(f"decode_kernel must be one of {MODES}, got {mode!r}")
    if mode == "dense" or not supports(
        w, kv_len, head_dim, page_size, kv_dtype=kv_dtype
    ):
        return False
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and page_size > 0 and (heads * head_dim) % LANES:
        return False
    return mode == "pallas" or on_tpu


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


def _online_softmax_step(
    s, v, m_scr, l_scr, acc_scr, visible=None, p_scale=None
):
    """Fold one masked score tile (w, bk) and its V chunk (bk, d) into
    the running (m, l, acc) accumulators — the flash_kernel.py forward
    update, minus the LSE output serving never needs. `visible` zeroes
    the masked probabilities outright, for a tile that may hold a row
    with NO visible key (there `s - m_new` is 0, not -1e30); `p_scale`
    multiplies the probabilities on their way into `p @ v` only."""
    m_prev = m_scr[:, :1]  # (w, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)  # masked entries: exp(~-1e30) == 0
    if visible is not None:
        p = jnp.where(visible, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    if p_scale is not None:
        p = p * p_scale
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

# what one grid step may hold in VMEM, scratch and temporaries included:
# three quarters of the 16 MiB Mosaic scopes to a kernel on a v5e by
# default, so the choice below never needs a raised limit
_VMEM_BUDGET = 12 << 20
# blocks of 128 and of 256 rows measured the same on a v5e (PR 29); the
# smaller needs half the VMEM and wastes less of a short sequence's tail
_MAX_BLOCK_ROWS = 128
# query rows a matmul takes (w x the heads folded into it): one MXU tile
_MAX_Q_ROWS = 128


class PagedBlock(NamedTuple):
    """What one turn of the paged kernel's block loop handles: `pages`
    logical pages of one slot (`rows` cache rows), `heads` heads a
    matmul (their queries laid block-diagonally), and the VMEM the
    kernel takes with it."""

    pages: int
    rows: int
    heads: int
    vmem_bytes: int


def _head_group(w: int, heads: int) -> int:
    """Heads folded into one matmul: the most that keep w x heads query
    rows inside one MXU tile. Past one query a group's rows are laid out
    query-major in pieces of `heads` rows, so the group is whole sublane
    tiles or a single head (the plain per-head loop)."""
    for g in range(heads, 1, -1):
        if heads % g == 0 and g * w <= _MAX_Q_ROWS and (
            w == 1 or g % SUBLANES == 0
        ):
            return g
    return 1


def paged_block(
    w: int, heads: int, head_dim: int, page_size: int, np_seq: int,
    itemsize: int,
) -> PagedBlock:
    """The paged kernel's block, from static shapes alone: as many pages
    at a time as fit `_VMEM_BUDGET`, up to `_MAX_BLOCK_ROWS` rows and
    the table's `np_seq` pages, and at least one (`page_size ==
    max_seq_len` gives exactly one)."""
    hd = heads * head_dim
    group = _head_group(w, heads)
    m = w * group
    fixed = 4 * (
        2 * w * heads * group * head_dim  # block-diagonal q, accumulator
        + 2 * w * heads * LANES  # running max and sum
        + 2 * w * hd  # q and output tiles
    )

    def vmem(rows):
        table_rows = -(-np_seq * page_size // rows) * rows
        return (
            fixed
            + rows * (
                2 * 2 * hd * itemsize  # two buffers each of K and V
                + 2 * hd * 4  # the block's K and V as float32 values
                + 4 * 4 * m  # score, probability and mask tiles
            )
            # a slot's tree-mask and scale tiles, double-buffered
            + table_rows * 2 * 4 * (w + 2 * heads)
        )

    pages = max(1, min(np_seq, _MAX_BLOCK_ROWS // page_size))
    while pages > 1 and vmem(pages * page_size) > _VMEM_BUDGET:
        pages //= 2
    rows = pages * page_size
    return PagedBlock(pages, rows, group, vmem(rows))


def _repeat_rows(x, n: int):
    """(w, c) -> (w * n, c), each row n times over (query-major)."""
    if n == 1:
        return x
    return jnp.concatenate(
        [
            jnp.broadcast_to(x[j:j + 1], (n, x.shape[1]))
            for j in range(x.shape[0])
        ],
        axis=0,
    )


def _tile_rows(x, n: int):
    """(g, c) -> (n * g, c), the whole tile n times over."""
    if x.shape[0] == 1:
        return jnp.broadcast_to(x, (n, x.shape[1]))
    return x if n == 1 else jnp.concatenate([x] * n, axis=0)


def _own_head(group: int, head_dim: int):
    """(group, group * head_dim) bool: row r's own head_dim lanes."""
    shape = (group, group * head_dim)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    lo = lax.broadcasted_iota(jnp.int32, shape, 0) * head_dim
    return (lane >= lo) & (lane < lo + head_dim)


def _page_copies(tbl_ref, pools, pages, page_size, num_pages):
    """`copies(slot, block, buf)` of a paged kernel: the async copies of
    a block's pages, page by page as the table names them and pool by
    pool within a page, into buffer `buf`. `pools`: for each pool (the
    pool in HBM, its VMEM buffer [2, rows, row], buf -> its semaphore).
    Sentinel entries clamp to a real page (their keys are masked)."""

    def copies(slot, block, buf):
        out = []
        for j in range(pages):
            page = jnp.minimum(tbl_ref[slot, block * pages + j], num_pages - 1)
            dst = pl.ds(j * page_size, page_size)
            for hbm, vmem, sem_of in pools:
                out.append(pltpu.make_async_copy(
                    hbm.at[page], vmem.at[buf, dst], sem_of(buf)
                ))
        return out

    return copies


def _fetch_ahead(copies, ib, i, n_blocks, before, next_live):
    """Inside slot `ib`'s block loop: start the copies of the block after
    its block `i` into the other buffer (the slot's next block, or the
    next live slot's first), wait for block `i`'s, and return the buffer
    they filled. Blocks alternate buffers across the whole call."""
    buf = (before + i) % 2

    @pl.when(i + 1 < n_blocks)
    def _next_block():
        for c in copies(ib, i + 1, 1 - buf):
            c.start()

    @pl.when((i + 1 == n_blocks) & (next_live < pl.num_programs(0)))
    def _next_slot():
        for c in copies(next_live, 0, 1 - buf):
            c.start()

    for c in copies(ib, i, buf):
        c.wait()
    return buf


def _allocated(tbl_ref, ib, i, col, pages, page_size, num_pages):
    """(1, rows) bool: the columns of slot `ib`'s block `i` whose page is
    allocated (a standalone caller's ragged table can leave a hole inside
    a live block; the engine's unallocated pages sit past the length)."""
    return functools.reduce(
        jnp.logical_or,
        [
            (col >= j * page_size) & (col < (j + 1) * page_size)
            & (tbl_ref[ib, i * pages + j] < num_pages)
            for j in range(pages)
        ],
    )


def _paged_kernel(
    len_ref, tbl_ref, sched_ref, q_ref, k_hbm, v_hbm, *rest,
    cfg, blk, num_pages, heads, head_dim, quant, tree,
):
    """One slot, all heads, its live blocks of `blk.pages` logical pages
    in a loop.

    k_hbm/v_hbm are the whole pools, left where they are: the kernel
    copies a block's pages itself, page by page as the table names them,
    into one of two (rows, h*d) VMEM buffers, so that only LIVE blocks
    cost anything (a grid of (slots, blocks) pays its index maps for
    every page of the table, live or not). Blocks alternate buffers
    across the whole call: while block t is computed, block t + 1 is in
    flight, be it the slot's next or the next live slot's first
    (sched_ref: a slot's live blocks, the live blocks before it, the
    next live slot; all from the wrapper).

    Heads go through the MXU `blk.heads` at a time: the group's w
    queries sit block-diagonally in a (w * group, group * d) tile (row
    j * group + g holds query j's head g in that head's own lanes, zeros
    elsewhere; built once a slot into q_scr), so ONE `q @ k^T` gives
    every head's (w, rows) scores and ONE `p @ v` every head's weighted
    values, whose own-head lanes are picked out when the slot is done.
    With group == 1 that is the plain per-head (w, d) matmul. The
    online-softmax update runs once a block. quant dequantizes through
    the score and probability tiles (a page's scale is per head, so it
    factors out of both matmuls): no dequantized cache view exists
    anywhere; tree swaps the staircase for a (w, rows) mask tile."""
    rest = list(rest)
    ks_ref, vs_ref = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    mask_ref = rest.pop(0) if tree else None
    o_ref, k_buf, v_buf, sem, q_scr, m_scr, l_scr, acc_scr = rest
    pages, rows, group = blk.pages, blk.rows, blk.heads
    w = cfg.w
    page_size = cfg.block_k
    gd = group * head_dim
    m = w * group
    ib = pl.program_id(0)
    n_blocks = sched_ref[0, ib]
    before = sched_ref[1, ib]
    next_live = sched_ref[2, ib]

    def lanes(g):
        return slice(g * gd, (g + 1) * gd)

    # a page's copies: K then V
    copies = _page_copies(
        tbl_ref,
        (
            (k_hbm, k_buf, lambda buf: sem.at[0, buf]),
            (v_hbm, v_buf, lambda buf: sem.at[1, buf]),
        ),
        pages, page_size, num_pages,
    )

    @pl.when(n_blocks == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _live():
        _init_scratch(m_scr, l_scr, acc_scr)
        q = q_ref[0].astype(jnp.float32)  # (w, h*d)
        for g in range(heads // group):
            qg = _repeat_rows(q[:, lanes(g)], group)
            if group > 1:
                own = _tile_rows(_own_head(group, head_dim), w)
                qg = jnp.where(own, qg, 0.0)
            q_scr[g] = qg

        # nobody fetched the call's first live block ahead
        @pl.when(before == 0)
        def _first():
            for c in copies(ib, 0, 0):
                c.start()

        length = len_ref[ib]
        col = lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        if not tree:
            # query-major rows: row r is query r // group
            row = lax.broadcasted_iota(jnp.int32, (m, 1), 0)
            qoff = sum(
                (row >= j * group).astype(jnp.int32) for j in range(1, w)
            )

        def _block(i, carry):
            buf = _fetch_ahead(copies, ib, i, n_blocks, before, next_live)
            k = k_buf[buf]  # (rows, h*d)
            v = v_buf[buf]
            if quant:
                k = k.astype(jnp.float32)
                v = v.astype(jnp.float32)
            if tree:
                visible = _repeat_rows(mask_ref[0, i], group) > 0.0
            else:
                visible = i * rows + col <= length + qoff
            visible = visible & _allocated(
                tbl_ref, ib, i, col, pages, page_size, num_pages
            )
            for g in range(heads // group):
                s = mxu_dot(q_scr[g], k[:, lanes(g)], (1, 1)) * cfg.sm_scale
                p_scale = None
                if quant:
                    rows_g = pl.ds(g * group, group)
                    s = s * _tile_rows(ks_ref[0, i, rows_g], w)
                    p_scale = _tile_rows(vs_ref[0, i, rows_g], w)
                _online_softmax_step(
                    jnp.where(visible, s, _MASK), v[:, lanes(g)],
                    m_scr.at[g], l_scr.at[g], acc_scr.at[g],
                    visible=visible, p_scale=p_scale,
                )
            return carry

        lax.fori_loop(0, n_blocks, _block, None)

        for g in range(heads // group):
            out = _finish(l_scr.at[g], acc_scr.at[g], o_ref.dtype)
            if group == 1:
                o_ref[0, :, lanes(g)] = out
                continue
            own = _own_head(group, head_dim)
            for j in range(w):
                piece = out[j * group:(j + 1) * group]
                o_ref[0, j:j + 1, lanes(g)] = jnp.sum(
                    jnp.where(own, piece, 0.0), axis=0, keepdims=True
                )


def _paged_schedule(live_blocks):
    """[b] live blocks a slot -> [3, b] int32: the same, the live blocks
    before the slot (which buffer its first goes to), and the next slot
    that has any (b if none): what the kernel needs to fetch one block
    ahead across slots."""
    b = live_blocks.shape[0]
    slot = jnp.arange(b, dtype=jnp.int32)
    later = lax.cummin(jnp.where(live_blocks > 0, slot, b), reverse=True)
    next_live = jnp.concatenate([later[1:], jnp.full((1,), b, jnp.int32)])
    before = jnp.cumsum(live_blocks) - live_blocks
    return jnp.stack([live_blocks, before, next_live]).astype(jnp.int32)


def _live_tables(block_tables, lengths, num_pages, page_size, pages, w=1):
    """What a paged kernel prefetches as scalars: (lengths int32 [b], the
    table in whole blocks of `pages` pages (the tail past the table is
    unallocated: `num_pages`), `_paged_schedule` of each slot's live
    blocks). A page counts iff it is inside the staircase of the `w`
    queries AND allocated; a slot's live blocks run up to the last block
    that holds such a page."""
    b, np_seq = block_tables.shape
    nblk = -(-np_seq // pages)
    lens = lengths.astype(jnp.int32)
    tbl = jnp.pad(
        block_tables.astype(jnp.int32),
        ((0, 0), (0, nblk * pages - np_seq)),
        constant_values=num_pages,
    )
    first_row = jnp.arange(nblk * pages, dtype=jnp.int32) * page_size
    live = (first_row[None, :] <= lens[:, None] + (w - 1)) & (tbl < num_pages)
    block_no = jnp.arange(1, nblk + 1, dtype=jnp.int32)
    live_blocks = jnp.max(
        jnp.where(live.reshape(b, nblk, pages).any(axis=2), block_no, 0),
        axis=1,
    )
    return lens, tbl, _paged_schedule(live_blocks)


def _paged_call(
    q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale, allowed,
    sm_scale, interpret,
):
    b, w, h, d = q.shape
    page_size = k_pool.shape[1]
    quant = k_scale is not None
    align = _INT8_SUBLANES if quant else SUBLANES
    if page_size % align:
        raise ValueError(
            f"paged flash decode{' (int8)' if quant else ''}: page_size "
            f"{page_size} is not sublane-aligned ({align}); use "
            "supports() and fall back to dense"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    cfg = _Cfg(w, sm_scale, page_size, resolve_interpret(interpret))
    blk = paged_block(
        w, h, d, page_size, block_tables.shape[1], k_pool.dtype.itemsize
    )
    return _paged_block_call(
        q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale, allowed,
        cfg=cfg, blk=blk,
    )


# jitted so that a step program traces and lowers the kernel ONCE and
# calls it a layer: inline, each of 24 layers traced the body again
# (18 s of every process's set-up on the chip's host, fetched programs
# or not: the trace is what the cache key is made from)
@functools.partial(jax.jit, static_argnames=("cfg", "blk"))
def _paged_block_call(
    q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale, allowed,
    cfg, blk,
):
    b, w, h, d = q.shape
    num_pages, page_size = k_pool.shape[0], k_pool.shape[1]
    np_seq = block_tables.shape[1]
    quant = k_scale is not None
    tree = allowed is not None
    pages, rows = blk.pages, blk.rows
    nblk = -(-np_seq // pages)
    lens, tbl, sched = _live_tables(
        block_tables, lengths, num_pages, page_size, pages, w
    )

    def slot_map(ib, lens, tbl, sched):
        return (ib, 0, 0)

    def slot_tiles_map(ib, lens, tbl, sched):
        return (ib, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, w, h * d), slot_map),
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    operands = [
        lens, tbl, sched, q.reshape(b, w, h * d),
        k_pool.reshape(num_pages, page_size, h * d),
        v_pool.reshape(num_pages, page_size, h * d),
    ]
    if quant:
        # a slot's scales as [blocks, h, rows] tiles, the layout of a
        # block's scores (sentinel entries take a real page's, masked)
        def scale_tiles(scale):
            per_page = scale.astype(jnp.float32)[
                jnp.minimum(tbl, num_pages - 1)
            ]  # [b, nblk * pages, h]
            return jnp.repeat(
                per_page.reshape(b, nblk, pages, h).transpose(0, 1, 3, 2),
                page_size, axis=3,
            )

        in_specs += [pl.BlockSpec((1, nblk, h, rows), slot_tiles_map)] * 2
        operands += [scale_tiles(k_scale), scale_tiles(v_scale)]
    if tree:
        in_specs.append(pl.BlockSpec((1, nblk, w, rows), slot_tiles_map))
        operands.append(
            _chunked_mask(
                jnp.pad(
                    allowed,
                    ((0, 0), (0, 0), (0, nblk * rows - allowed.shape[2])),
                ),
                rows,
            )
        )
    groups = h // blk.heads
    m = w * blk.heads
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel,
            cfg=cfg,
            blk=blk,
            num_pages=num_pages,
            heads=h,
            head_dim=d,
            quant=quant,
            tree=tree,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, w, h * d), slot_map),
            scratch_shapes=[
                pltpu.VMEM((2, rows, h * d), k_pool.dtype),
                pltpu.VMEM((2, rows, h * d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((groups, m, blk.heads * d), jnp.float32),
                pltpu.VMEM((groups, m, LANES), jnp.float32),
                pltpu.VMEM((groups, m, LANES), jnp.float32),
                pltpu.VMEM((groups, m, blk.heads * d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, w, h * d), q.dtype),
        # slots in order: a slot's first block is fetched by the one before
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=cfg.interpret,
    )(*operands)
    return out.reshape(b, w, h, d)


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


# -- block-paged LATENT cache -------------------------------------------------
#
# Latent attention (MLA, ops/attention.py) caches ONE row a token and
# layer, [c | kr | 0], and in the absorbed form every query head reads the
# same rows: keys are the whole row, values its first `v_width` lanes. So
# the paged walk above degenerates to its simplest case, taken here from
# static shapes (one pool, a query of [b, 1, h, row]): a block's scores
# are ONE (h, row) x (rows, row)^T matmul with no block-diagonal tiling,
# its weighted values ONE (h, rows) x (rows, v_width) matmul on the same
# VMEM block, and half the copies (there is no V pool). Pages are fetched
# a block ahead across slots by `_paged_kernel`'s own walk (`_page_copies`,
# `_fetch_ahead`, `_allocated`, `_live_tables`). Decode only (one query a
# slot), fp32 or bf16 rows.


def supports_latent(mode: str, row: int, page_size: int) -> bool:
    """`use_kernel` for the latent pool: "dense" never, "pallas" whenever
    the page is sublane-aligned (the interpreter off a TPU), "auto" on a
    TPU alone, where the row must also be whole 128-lane tiles (the
    kernel copies whole rows out of the pool)."""
    if mode not in MODES:
        raise ValueError(f"decode_kernel must be one of {MODES}, got {mode!r}")
    if mode == "dense" or page_size % SUBLANES:
        return False
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and row % LANES:
        return False
    return mode == "pallas" or on_tpu


def latent_block(
    heads: int, row: int, v_width: int, page_size: int, np_seq: int,
    itemsize: int,
) -> PagedBlock:
    """The latent kernel's block, as `paged_block` chooses the other's:
    as many pages as fit `_VMEM_BUDGET`, up to `_MAX_BLOCK_ROWS` rows and
    the table's `np_seq` pages. `heads` is all of them: they share a row."""

    def vmem(rows):
        return (
            4 * heads * (2 * row + v_width + 2 * LANES)  # q, output, m, l
            + rows * (2 * row * itemsize + row * 4 + 4 * 4 * heads)
        )

    pages = max(1, min(np_seq, _MAX_BLOCK_ROWS // page_size))
    while pages > 1 and vmem(pages * page_size) > _VMEM_BUDGET:
        pages //= 2
    rows = pages * page_size
    return PagedBlock(pages, rows, heads, vmem(rows))


def _latent_kernel(
    len_ref, tbl_ref, sched_ref, q_ref, pool_hbm, o_ref, k_buf, sem, m_scr,
    l_scr, acc_scr, *, cfg, blk, num_pages, v_width,
):
    """One slot, all heads at once, its live blocks in a loop: the walk
    of `_paged_kernel` (`_page_copies`, `_fetch_ahead`) over one pool."""
    pages, rows = blk.pages, blk.rows
    page_size = cfg.block_k
    ib = pl.program_id(0)
    n_blocks = sched_ref[0, ib]
    before = sched_ref[1, ib]
    next_live = sched_ref[2, ib]
    copies = _page_copies(
        tbl_ref, ((pool_hbm, k_buf, lambda buf: sem.at[buf]),),
        pages, page_size, num_pages,
    )

    @pl.when(n_blocks == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _live():
        _init_scratch(m_scr, l_scr, acc_scr)

        @pl.when(before == 0)
        def _first():
            for c in copies(ib, 0, 0):
                c.start()

        length = len_ref[ib]
        col = lax.broadcasted_iota(jnp.int32, (1, rows), 1)

        def _block(i, carry):
            buf = _fetch_ahead(copies, ib, i, n_blocks, before, next_live)
            k = k_buf[buf]  # (rows, row): every head's keys AND values
            visible = (i * rows + col <= length) & _allocated(
                tbl_ref, ib, i, col, pages, page_size, num_pages
            )
            s = mxu_dot(q_ref[0], k, (1, 1)) * cfg.sm_scale  # (h, rows)
            _online_softmax_step(
                jnp.where(visible, s, _MASK), k[:, :v_width],
                m_scr, l_scr, acc_scr, visible=visible,
            )
            return carry

        lax.fori_loop(0, n_blocks, _block, None)
        o_ref[0] = _finish(l_scr, acc_scr, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "blk", "v_width"))
def _latent_block_call(q, pool, block_tables, lengths, cfg, blk, v_width):
    b, _, h, row = q.shape
    num_pages, page_size = pool.shape[0], pool.shape[1]
    operands = _live_tables(
        block_tables, lengths, num_pages, page_size, blk.pages
    )

    def slot_map(ib, lens, tbl, sched):
        return (ib, 0, 0)

    return pl.pallas_call(
        functools.partial(
            _latent_kernel, cfg=cfg, blk=blk, num_pages=num_pages,
            v_width=v_width,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, row), slot_map),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((1, h, v_width), slot_map),
            scratch_shapes=[
                pltpu.VMEM((2, blk.rows, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h, LANES), jnp.float32),
                pltpu.VMEM((h, LANES), jnp.float32),
                pltpu.VMEM((h, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, v_width), q.dtype),
        # slots in order: a slot's first block is fetched by the one before
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=cfg.interpret,
    )(*operands, q[:, 0], pool)[:, None]


def paged_flash_decode_latent(
    q, pool, block_tables, lengths, v_width: int, sm_scale: float,
    interpret: Optional[bool] = None,
):
    """Single-query flash decode over a paged LATENT pool —
    ops/attention.paged_latent_decode_attention's semantics with no
    gather. q: [b, 1, h, row]; pool: [num_pages, page_size, row];
    block_tables, lengths as paged_flash_verify. Returns
    [b, 1, h, v_width]: the softmax-weighted sum of each visible row's
    first `v_width` lanes, per head. `sm_scale` has no default: it is
    the model's 1 / sqrt(nope + rope), not a function of `row`."""
    if q.shape[1] != 1:
        raise ValueError("the latent kernel takes one query a slot (decode)")
    page_size = pool.shape[1]
    cfg = _Cfg(1, float(sm_scale), page_size, resolve_interpret(interpret))
    blk = latent_block(
        q.shape[2], q.shape[3], v_width, page_size, block_tables.shape[1],
        pool.dtype.itemsize,
    )
    return _latent_block_call(
        q, pool, block_tables, lengths, cfg=cfg, blk=blk, v_width=v_width
    )
