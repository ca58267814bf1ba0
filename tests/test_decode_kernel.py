"""Pallas flash-decode kernel family (ops/pallas/decode_kernel.py):
interpret-mode parity of all four kernel entry points against the dense
jnp paths in ops/attention.py, token-identical greedy streams through
GenerationEngine with the kernel forced on (both page geometries, plain and
speculative), sentinel block-table handling, supports() rejection →
dense fallback, the decode-kernel config/flag wiring, and the
kernel-aware decode/verify cost terms. All CPU-fast (tier 1): off-TPU
the kernels run under the Pallas interpreter, which executes the exact
code path the TPU compiles."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import (
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.ops.attention import (
    decode_attention,
    paged_decode_attention,
    paged_verify_attention,
    verify_attention,
)
from flexflow_tpu.ops.pallas import decode_kernel as dk
from flexflow_tpu.serving import ServeConfig, build_scheduler
from tests.conftest import page_geometry

pytestmark = pytest.mark.serving

VOCAB = 50


def _lm(batch=4, seq=32, hidden=32, heads=4, seed=0):
    cfg = FFConfig(batch_size=batch, seed=seed)
    model = FFModel(cfg)
    tok = model.create_tensor(
        [batch, seq], dtype=DataType.INT32, name="tokens"
    )
    build_decoder_lm(
        model, tok, vocab_size=VOCAB, hidden=hidden, num_heads=heads,
        num_layers=2, ff_dim=2 * hidden,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=jax.devices()[:1],
    )
    return model


@pytest.fixture(scope="module")
def lm():
    return _lm()


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 2], [7], [11, 12],
           [3, 3, 3], [8, 1], [2]]


# -- kernel-level parity vs the dense paths -----------------------------------


def _contig_case(rng, b, w, h, d, max_len, lengths):
    q = jnp.asarray(rng.randn(b, w, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, max_len, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, max_len, h, d).astype(np.float32))
    return q, k, v, jnp.asarray(np.asarray(lengths, dtype=np.int32))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("w", [1, 4])
def test_flash_verify_matches_dense(d, w):
    """Contiguous-cache parity across head_dim and draft width, with
    lengths covering 0 (one visible key), mid-cache, and full-cache
    (the last legal write position max_len - w)."""
    rng = np.random.RandomState(0)
    max_len = 64
    lengths = [0, 17, max_len - w]
    q, k, v, lens = _contig_case(rng, 3, w, 2, d, max_len, lengths)
    dense = verify_attention(q, k, v, lens)
    kern = dk.flash_verify(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense), atol=2e-6)
    if w == 1:
        dec = dk.flash_decode(q, k, v, lens)
        np.testing.assert_allclose(
            np.asarray(dec), np.asarray(decode_attention(q, k, v, lens)),
            atol=2e-6,
        )


def test_flash_verify_under_jit_and_odd_chunking():
    """The kernel composes with jit (the engine always jits its steps)
    and tiles a max_len that is sublane- but not lane-aligned."""
    rng = np.random.RandomState(1)
    q, k, v, lens = _contig_case(rng, 2, 4, 2, 64, 48, [0, 44])
    dense = verify_attention(q, k, v, lens)
    kern = jax.jit(dk.flash_verify)(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense), atol=2e-6)


def _paged_case(rng, b, w, h, d, page_size, num_pages, max_pages, lengths):
    """Pool + shuffled block tables where each row's visible prefix is
    allocated (the engine invariant) and everything past it carries the
    sentinel."""
    q = jnp.asarray(rng.randn(b, w, h, d).astype(np.float32))
    kp = jnp.asarray(rng.randn(num_pages, page_size, h, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(num_pages, page_size, h, d).astype(np.float32))
    tbl = np.full((b, max_pages), num_pages, dtype=np.int32)
    perm = rng.permutation(num_pages)
    used = 0
    for i, ln in enumerate(lengths):
        need = -(-(int(ln) + w) // page_size)
        tbl[i, :need] = perm[used : used + need]
        used += need
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(
        np.asarray(lengths, dtype=np.int32)
    )


@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("w", [1, 4])
def test_paged_flash_verify_matches_dense(ps, w):
    """Paged parity across page size and draft width over shuffled pools
    with sentinel-padded tables; lengths cover 0, an exact page
    boundary, and full-cache."""
    rng = np.random.RandomState(2)
    max_len = 64
    lengths = [0, ps, max_len - w]  # ps: first row of the second page
    q, kp, vp, tbl, lens = _paged_case(
        rng, 3, w, 2, 64, ps, 32, max_len // ps, lengths
    )
    dense = paged_verify_attention(q, kp, vp, tbl, lens)
    kern = dk.paged_flash_verify(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense), atol=2e-6)
    if w == 1:
        dec = dk.paged_flash_decode(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(
            np.asarray(dec),
            np.asarray(paged_decode_attention(q, kp, vp, tbl, lens)),
            atol=2e-6,
        )


def test_paged_kernel_ignores_sentinel_pages():
    """Entries past the visible prefix are sentinels pointing nowhere;
    scribbling over every pool page OUTSIDE the tables must not change
    the output (the dense path guarantees this via clamp-and-mask, the
    kernel via the table check + staircase mask)."""
    rng = np.random.RandomState(3)
    ps, num_pages = 8, 16
    q, kp, vp, tbl, lens = _paged_case(
        rng, 2, 4, 2, 64, ps, num_pages, 4, [3, 11]
    )
    base = dk.paged_flash_verify(q, kp, vp, tbl, lens)
    live = set(int(p) for p in np.asarray(tbl).ravel() if p < num_pages)
    dead = [p for p in range(num_pages) if p not in live]
    kp2 = np.asarray(kp).copy()
    vp2 = np.asarray(vp).copy()
    kp2[dead] = 1e6
    vp2[dead] = -1e6
    again = dk.paged_flash_verify(
        q, jnp.asarray(kp2), jnp.asarray(vp2), tbl, lens
    )
    np.testing.assert_allclose(np.asarray(again), np.asarray(base), atol=2e-6)


# -- the paged kernel's block: several pages a grid step ----------------------


def _ref_paged(q, kp, vp, tbl, lens, allowed=None, k_scale=None, v_scale=None):
    """What the paged kernels promise, in numpy float64: every slot's
    allocated pages gathered, the staircase (or `allowed`) mask, keys of
    unallocated pages invisible, a row that sees nothing zero."""
    q, tbl, lens = (np.asarray(a) for a in (q, tbl, lens))
    b, w, h, d = q.shape
    num_pages, ps = kp.shape[0], kp.shape[1]
    kp = np.asarray(kp, np.float64).reshape(num_pages, ps, h, d)
    vp = np.asarray(vp, np.float64).reshape(num_pages, ps, h, d)
    if k_scale is not None:
        kp = kp * np.asarray(k_scale, np.float64)[:, None, :, None]
        vp = vp * np.asarray(v_scale, np.float64)[:, None, :, None]
    kv = tbl.shape[1] * ps
    out = np.zeros(q.shape)
    for i in range(b):
        pages = np.minimum(tbl[i], num_pages - 1)
        k = kp[pages].reshape(kv, h, d)
        v = vp[pages].reshape(kv, h, d)
        pos = np.arange(kv)
        see = (
            np.asarray(allowed[i]) > 0
            if allowed is not None
            else pos[None, :] <= lens[i] + np.arange(w)[:, None]
        ) & np.repeat(tbl[i] < num_pages, ps)[None, :]
        s = np.einsum("whd,khd->hwk", q[i], k) / np.sqrt(d)
        s = np.where(see[None], s, -np.inf)
        top = np.max(s, axis=-1, keepdims=True)
        p = np.where(see[None], np.exp(s - np.where(np.isfinite(top), top, 0)), 0)
        den = p.sum(-1, keepdims=True)
        p = np.divide(p, den, out=np.zeros_like(p), where=den > 0)
        out[i] = np.einsum("hwk,khd->whd", p, v)
    return out


@pytest.mark.parametrize(
    "cell, want",
    [
        # (w, heads, head_dim, page_size, np_seq, itemsize) -> (pages, rows, heads a matmul)
        pytest.param((1, 16, 64, 16, 64, 4), (8, 128, 16), id="gpt2_medium"),
        pytest.param((1, 16, 128, 16, 64, 4), (8, 128, 16), id="olmoe"),
        pytest.param((4, 16, 64, 16, 64, 4), (8, 128, 16), id="verify-w4"),
        pytest.param((13, 16, 64, 16, 64, 4), (8, 128, 8), id="verify-w13"),
        pytest.param((64, 16, 64, 16, 64, 4), (8, 128, 1), id="verify-w64"),
        pytest.param((1, 16, 64, 32, 32, 1), (4, 128, 16), id="int8-ps32"),
        pytest.param((1, 16, 64, 1024, 1, 4), (1, 1024, 16), id="one_page"),
        pytest.param((1, 4, 8, 16, 2, 4), (2, 32, 4), id="short-table"),
    ],
)
def test_paged_block_follows_from_shapes(cell, want):
    """The block is a pure function of static shapes: pages a grid step,
    rows a block, heads a matmul, and VMEM under the stated budget."""
    blk = dk.paged_block(*cell)
    assert (blk.pages, blk.rows, blk.heads) == want
    assert blk.rows == blk.pages * cell[3]
    assert blk.vmem_bytes <= dk._VMEM_BUDGET or blk.pages == 1
    assert cell[1] % blk.heads == 0


def _block_case(rng, w, lengths, h=2, d=64, ps=16, np_seq=20, dead=()):
    """Slots whose pages are allocated up to lengths + w; `dead` slots
    hold length 0 and an all-sentinel table."""
    num_pages = len(lengths) * np_seq
    q, kp, vp, tbl, lens = _paged_case(
        rng, len(lengths), w, h, d, ps, num_pages, np_seq, lengths
    )
    tbl = np.asarray(tbl).copy()
    tbl[list(dead)] = num_pages
    return q, kp, vp, jnp.asarray(tbl), lens


@pytest.mark.parametrize("block_rows", [64, 128])
@pytest.mark.parametrize("w", [1, 4])
def test_paged_blocks_match_dense_across_block_boundaries(
    monkeypatch, w, block_rows
):
    """Lengths on, one before and one after a block boundary, a table
    that is no whole number of blocks (20 pages in blocks of 4 or 16),
    and a dead slot between live ones, which returns zeros."""
    monkeypatch.setattr(dk, "_MAX_BLOCK_ROWS", block_rows)
    rng = np.random.RandomState(5)
    edge = block_rows if block_rows < 320 - 64 else 256
    lengths = [edge - 1, 0, edge, 0, edge + 1, 320 - w]
    q, kp, vp, tbl, lens = _block_case(rng, w, lengths, dead=(1, 3))
    assert dk.paged_block(w, 2, 64, 16, 20, 4).rows == block_rows
    kern = np.asarray(dk.paged_flash_verify(q, kp, vp, tbl, lens))
    dense = np.asarray(paged_verify_attention(q, kp, vp, tbl, lens))
    live = [0, 2, 4, 5]
    np.testing.assert_allclose(kern[live], dense[live], atol=2e-6)
    np.testing.assert_array_equal(kern[[1, 3]], 0.0)
    np.testing.assert_allclose(
        kern, _ref_paged(q, kp, vp, tbl, lens), atol=2e-6
    )


@pytest.mark.parametrize("w", [1, 4])
def test_sentinel_page_inside_a_live_block_is_masked(w):
    """A standalone caller's ragged table: an unallocated entry INSIDE
    the visible prefix. Its keys are invisible (whatever the clamped
    fetch brought), the block's other pages count."""
    rng = np.random.RandomState(6)
    q, kp, vp, tbl, lens = _block_case(rng, w, [100, 40])
    tbl = np.asarray(tbl).copy()
    num_pages = kp.shape[0]
    tbl[0, 2] = num_pages
    tbl[1, 0] = num_pages  # even the first page
    tbl = jnp.asarray(tbl)
    kern = dk.paged_flash_verify(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(
        np.asarray(kern), _ref_paged(q, kp, vp, tbl, lens), atol=2e-6
    )


@pytest.mark.parametrize("block_rows", [64, 128])
@pytest.mark.parametrize("w", [1, 4])
def test_paged_tree_mask_follows_the_block(monkeypatch, w, block_rows):
    """An arbitrary visibility mask over LOGICAL positions, regrouped to
    the block's (w, rows) tiles, a table that is no whole number of
    blocks."""
    monkeypatch.setattr(dk, "_MAX_BLOCK_ROWS", block_rows)
    rng = np.random.RandomState(7)
    lengths = [70, 0, 250]
    q, kp, vp, tbl, lens = _block_case(rng, w, lengths, dead=(1,))
    kv = tbl.shape[1] * kp.shape[1]
    pos = np.arange(kv)[None, None, :]
    window = np.asarray(lengths)[:, None, None] + np.arange(w)[None, :, None]
    allowed = (pos <= window) & (rng.rand(3, w, kv) < 0.7)
    allowed[:, :, 0] = True
    allowed = jnp.asarray(allowed.astype(np.float32))
    kern = dk.paged_flash_verify_tree(q, kp, vp, tbl, lens, allowed)
    np.testing.assert_allclose(
        np.asarray(kern), _ref_paged(q, kp, vp, tbl, lens, allowed),
        atol=2e-6,
    )


@pytest.mark.parametrize("tree", [False, True], ids=["stair", "tree"])
@pytest.mark.parametrize("w", [1, 4])
def test_paged_int8_blocks_match_dense_dequant(w, tree):
    """int8 pools with 32-row pages and per-(page, head) scales: the
    block dequantizes through its score and probability tiles and lands
    where the dense path's dequantized gather does."""
    rng = np.random.RandomState(8)
    b, h, d, ps, np_seq = 3, 2, 64, 32, 10  # blocks of 4 pages, 3 blocks
    num_pages = b * np_seq
    lengths = [255, 0, 300]
    q, _, _, tbl, lens = _paged_case(
        rng, b, w, h, d, ps, num_pages, np_seq, lengths
    )
    kp = jnp.asarray(rng.randint(-127, 128, (num_pages, ps, h, d)).astype(np.int8))
    vp = jnp.asarray(rng.randint(-127, 128, (num_pages, ps, h, d)).astype(np.int8))
    ks = jnp.asarray((rng.rand(num_pages, h) * 0.02 + 0.001).astype(np.float32))
    vs = jnp.asarray((rng.rand(num_pages, h) * 0.02 + 0.001).astype(np.float32))
    assert dk.paged_block(w, h, d, ps, np_seq, 1).pages == 4
    if tree:
        kv = np_seq * ps
        window = np.asarray(lengths)[:, None, None] + np.arange(w)[None, :, None]
        allowed = (np.arange(kv)[None, None, :] <= window) & (
            rng.rand(b, w, kv) < 0.7
        )
        allowed[:, :, 0] = True
        allowed = jnp.asarray(allowed.astype(np.float32))
        kern = dk.paged_flash_verify_tree_quant(
            q, kp, vp, ks, vs, tbl, lens, allowed
        )
    else:
        allowed = None
        kern = dk.paged_flash_verify_quant(q, kp, vp, ks, vs, tbl, lens)
        dense = paged_verify_attention(
            q, kp, vp, tbl, lens, k_scale=ks, v_scale=vs, kernel="dense"
        )
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(dense), atol=1e-5
        )
    np.testing.assert_allclose(
        np.asarray(kern),
        _ref_paged(q, kp, vp, tbl, lens, allowed, ks, vs),
        atol=1e-5,
    )


@pytest.mark.parametrize("d", [64, 128], ids=["hd1024", "hd2048"])
@pytest.mark.parametrize("w", [1, 4])
def test_paged_blocks_at_the_cells_widths(d, w):
    """16 heads of 64 and of 128 at page size 16: the two serving
    cells' rows of 1024 and 2048 floats, all heads in one matmul."""
    rng = np.random.RandomState(9)
    lengths = [130, 0, 270 - w]
    q, kp, vp, tbl, lens = _block_case(
        rng, w, lengths, h=16, d=d, np_seq=17, dead=(1,)
    )
    assert dk.paged_block(w, 16, d, 16, 17, 4).heads == 16
    kern = np.asarray(dk.paged_flash_verify(q, kp, vp, tbl, lens))
    dense = np.asarray(paged_verify_attention(q, kp, vp, tbl, lens))
    np.testing.assert_allclose(kern[[0, 2]], dense[[0, 2]], atol=2e-6)
    np.testing.assert_array_equal(kern[1], 0.0)


def test_paged_one_page_geometry_is_one_block():
    """page_size == max_seq_len: one page a slot, one block, one grid
    step a slot."""
    rng = np.random.RandomState(10)
    q, kp, vp, tbl, lens = _paged_case(rng, 3, 4, 2, 64, 64, 3, 1, [0, 17, 60])
    assert dk.paged_block(4, 2, 64, 64, 1, 4).pages == 1
    np.testing.assert_allclose(
        np.asarray(dk.paged_flash_verify(q, kp, vp, tbl, lens)),
        np.asarray(paged_verify_attention(q, kp, vp, tbl, lens)),
        atol=2e-6,
    )


# -- supports() gate + mode resolution ----------------------------------------


def test_supports_geometry_gate():
    assert dk.supports(1, 64, 64)
    assert dk.supports(4, 48, 128)
    assert dk.supports(5, 256, 64, page_size=16)
    # head_dim must be sublane-aligned
    assert not dk.supports(1, 64, 60)
    # page must be sublane-aligned
    assert not dk.supports(1, 64, 64, page_size=4)
    # a width that wide is prefill-shaped, not decode-shaped
    assert not dk.supports(dk._MAX_W + 1, 64, 64)
    assert not dk.supports(0, 64, 64)


def test_use_kernel_mode_resolution():
    # off-TPU: "auto" stays dense, "pallas" forces the interpreter path
    on_tpu = jax.default_backend() == "tpu"
    assert dk.use_kernel("auto", 1, 64, 64) == on_tpu
    assert dk.use_kernel("pallas", 1, 64, 64)
    assert not dk.use_kernel("dense", 1, 64, 64)
    # rejected geometry never takes the kernel, even forced
    assert not dk.use_kernel("pallas", 1, 64, 60)
    with pytest.raises(ValueError):
        dk.use_kernel("fast", 1, 64, 64)


def test_compiled_paged_kernel_wants_whole_lane_rows(monkeypatch):
    """Compiled, the paged kernel copies whole cache rows of heads x
    head_dim out of the pool and Mosaic takes them in whole 128-lane
    tiles only: a narrower row (a toy model's) is served dense on a TPU
    and by the kernel under the interpreter."""
    narrow = dict(page_size=16, heads=4)  # 4 heads of 8: 32 lanes
    assert dk.use_kernel("pallas", 1, 0, 8, **narrow) == (
        jax.default_backend() != "tpu"
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not dk.use_kernel("pallas", 1, 0, 8, **narrow)
    assert not dk.use_kernel("auto", 4, 0, 8, **narrow)
    assert dk.use_kernel("auto", 1, 0, 32, **narrow)  # one lane tile
    assert dk.use_kernel("auto", 1, 0, 64, page_size=16, heads=16)
    # a head shard's heads count, not the model's
    assert not dk.use_kernel("auto", 1, 0, 64, page_size=16, heads=1)
    # the contiguous kernel takes its rows through BlockSpecs
    assert dk.use_kernel("auto", 1, 64, 8, heads=4)


def test_tuned_chunk_installation():
    before = dict(dk._TUNED)
    try:
        dk.set_tuned_decode_blocks(64)
        assert dk._pick_chunk(256) == 64
        # the chunk still has to divide the cache length
        assert dk._pick_chunk(40) == 40
    finally:
        dk._TUNED.update(before)


# -- engine integration: kernel forced on, both geometries -----------------------


def _generate(lm, layout, mode, spec=False, max_new=6):
    serve = ServeConfig(
        max_seqs=2,
        max_seq_len=32,
        **page_geometry(layout, 32),
        decode_kernel=mode,
        **(dict(spec_draft="ngram", spec_k=3) if spec else {}),
    )
    return lm.generate(PROMPTS, max_new_tokens=max_new, serve_config=serve)


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_greedy_streams_token_identical(lm, layout):
    """With the kernel forced on (interpret mode on CPU), greedy decode
    through the scheduler is token-for-token identical to the dense
    engine on a schedule with slot reuse (8 requests through 2 slots)."""
    assert _generate(lm, layout, "pallas") == _generate(lm, layout, "dense")


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_spec_streams_token_identical(lm, layout):
    """Speculative greedy decode (n-gram drafts, verify through the
    kernel's staircase path) stays token-identical to the dense spec
    engine AND to plain dense decode at both page geometries."""
    spec_kernel = _generate(lm, layout, "pallas", spec=True, max_new=8)
    assert spec_kernel == _generate(lm, layout, "dense", spec=True, max_new=8)
    assert spec_kernel == _generate(lm, layout, "dense", max_new=8)


@pytest.mark.parametrize("layout", ["one_page", "paged"])
def test_verify_logits_match_dense(lm, layout):
    """GenerationEngine.verify logits (the w-query staircase scoring
    pass) agree numerically between the kernel and dense engines."""
    prompt = [3, 1, 4, 1, 5]
    drafts = [9, 2, 6]
    logits = {}
    for mode in ("dense", "pallas"):
        _, engine, cache = build_scheduler(
            lm,
            ServeConfig(
                max_seqs=2, max_seq_len=32, **page_geometry(layout, 32),
                decode_kernel=mode,
            ),
        )
        slot = cache.alloc(len(prompt), len(prompt) + 6)
        nxt, _ = engine.prefill(lm.params, [prompt], [slot])
        tokens = np.zeros((cache.spec.max_seqs, 1 + len(drafts)), np.int32)
        dlens = np.zeros(cache.spec.max_seqs, np.int32)
        tokens[slot] = [int(nxt[0])] + drafts
        dlens[slot] = 1 + len(drafts)
        logits[mode] = engine.verify(lm.params, tokens, dlens)[slot]
    np.testing.assert_allclose(
        logits["pallas"], logits["dense"], atol=1e-4
    )


def test_rejected_geometry_falls_back_to_dense(monkeypatch):
    """A supports()-rejected geometry (head_dim 9, not sublane-aligned)
    demonstrably runs the dense path even with the kernel forced: the
    kernel entry points are poisoned, and the streams still match the
    dense engine's."""
    model = _lm(hidden=36, heads=4)  # head_dim 9 -> supports() False
    dense = model.generate(
        PROMPTS[:4], max_new_tokens=5,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32,
                                 decode_kernel="dense"),
    )

    def boom(*a, **k):
        raise AssertionError("kernel entered on a rejected geometry")

    for fn in ("flash_decode", "flash_verify", "paged_flash_decode",
               "paged_flash_verify"):
        monkeypatch.setattr(dk, fn, boom)
    for layout in ("one_page", "paged"):
        forced = model.generate(
            PROMPTS[:4], max_new_tokens=5,
            serve_config=ServeConfig(max_seqs=2, max_seq_len=32,
                                     **page_geometry(layout, 32),
                                     decode_kernel="pallas"),
        )
        assert forced == dense


def test_page_size_rejection_falls_back(monkeypatch):
    """A sublane-misaligned page size is rejected for the paged kernel
    and served on the dense path."""
    assert not dk.supports(1, 32, 8, page_size=4)
    model = _lm()
    dense = model.generate(
        PROMPTS[:4], max_new_tokens=5,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32,
                                 kv_page_size=4,
                                 decode_kernel="dense"),
    )
    for fn in ("paged_flash_decode", "paged_flash_verify"):
        monkeypatch.setattr(dk, fn, lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("paged kernel entered at page_size 4")))
    forced = model.generate(
        PROMPTS[:4], max_new_tokens=5,
        serve_config=ServeConfig(max_seqs=2, max_seq_len=32,
                                 kv_page_size=4,
                                 decode_kernel="pallas"),
    )
    assert forced == dense


# -- config / flag wiring -----------------------------------------------------


def test_serve_config_validates_mode():
    with pytest.raises(ValueError):
        ServeConfig(decode_kernel="fast")
    assert ServeConfig(decode_kernel="pallas").decode_kernel == "pallas"


def test_decode_kernel_flag_wiring():
    from flexflow_tpu.config import FFConfig as Cfg

    cfg = Cfg.parse_args(["--decode-kernel", "pallas"])
    assert cfg.serve_decode_kernel == "pallas"
    assert ServeConfig.from_config(cfg).decode_kernel == "pallas"
    # default stays auto
    assert ServeConfig.from_config(Cfg.parse_args([])).decode_kernel == "auto"


def test_engine_rejects_bad_mode(lm):
    from flexflow_tpu.serving import GenerationEngine, PagedKVCache

    cache = PagedKVCache.from_model(lm, max_seqs=2, max_len=32)
    with pytest.raises(ValueError):
        GenerationEngine(lm, cache, decode_kernel="fast")


def test_calibration_installs_decode_chunk(tmp_path):
    """A calibration table's decode_blocks entry replaces the built-in
    KV chunk at compile, like flash_blocks for the training kernel."""
    import json

    before = dict(dk._TUNED)
    table = tmp_path / "cal.json"
    table.write_text(json.dumps({
        "version": 1, "chip": "v5e", "ops": {},
        "decode_blocks": {"block_k": 64},
    }))
    try:
        cfg = FFConfig(batch_size=2)
        cfg.calibration_file = str(table)
        m = FFModel(cfg)
        tok = m.create_tensor([2, 16], dtype=DataType.INT32, name="tokens")
        build_decoder_lm(m, tok, vocab_size=32, hidden=16, num_heads=2,
                         num_layers=1, ff_dim=32)
        m.compile(
            optimizer=SGDOptimizer(lr=0.01),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[], devices=jax.devices()[:1],
        )
        assert dk._TUNED["block_k"] == 64
    finally:
        dk._TUNED.update(before)


# -- kernel-aware cost terms --------------------------------------------------


def _mha_node():
    cfg = FFConfig(batch_size=4)
    m = FFModel(cfg)
    tok = m.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(m, tok, vocab_size=128, hidden=64, num_heads=4)
    return m, next(
        n for n in m.graph.nodes.values()
        if n.op_type.name == "MULTIHEAD_ATTENTION"
    )


def test_kernel_cost_drops_gather_tax():
    """On the paged layout the kernel path prices ONE page-granular
    cache read; the dense fallback adds the gather's write + re-read.
    On the contiguous layout the two paths price identically."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.cost_model import CostModel

    _, mha = _mha_node()
    cm = CostModel(MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e"))
    dense = cm.decode_op_cost(mha, batch=1, kv_len=512, page_size=16)
    pallas = cm.decode_op_cost(
        mha, batch=1, kv_len=512, page_size=16, kernel="pallas"
    )
    assert pallas.forward_time < dense.forward_time
    assert pallas.memory == dense.memory  # footprint is layout, not path
    flat_d = cm.decode_op_cost(mha, batch=1, kv_len=512)
    flat_p = cm.decode_op_cost(mha, batch=1, kv_len=512, kernel="pallas")
    assert flat_p.forward_time == flat_d.forward_time
    vd = cm.verify_op_cost(mha, batch=1, kv_len=512, k=4, page_size=16)
    vp = cm.verify_op_cost(
        mha, batch=1, kv_len=512, k=4, page_size=16, kernel="pallas"
    )
    assert vp.forward_time < vd.forward_time


def test_search_resolves_kernel_like_engine():
    """resolve_decode_kernel mirrors the runtime selection: 'pallas'
    prices the kernel wherever use_kernel would run it, 'auto' follows
    the backend, rejected geometry falls back to dense pricing."""
    from flexflow_tpu.search.auto import resolve_decode_kernel

    m, _ = _mha_node()  # head_dim 16: supported
    assert resolve_decode_kernel("pallas", m.graph, 512, 16) == "pallas"
    assert resolve_decode_kernel("dense", m.graph, 512, 16) == "dense"
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_decode_kernel("auto", m.graph, 512, 16) == (
        "pallas" if on_tpu else "dense"
    )
    # rejected geometry: page not sublane-aligned
    assert resolve_decode_kernel("pallas", m.graph, 512, 4) == "dense"


def test_optimize_serving_accepts_kernel_term():
    """optimize_serving ranks under the kernel cost shape without
    changing the feasibility surface; the kernel-priced winner's step
    time is never worse than the dense-priced one at equal mesh."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import optimize_serving

    m, _ = _mha_node()
    spec = MachineSpec(num_nodes=1, chips_per_node=2, chip="v5e")
    dense = optimize_serving(
        m.graph, 2, spec, batch_size=1, kv_len=512, page_size=16
    )
    kern = optimize_serving(
        m.graph, 2, spec, batch_size=1, kv_len=512, page_size=16,
        decode_kernel="pallas",
    )
    assert kern.cost.step_time < dense.cost.step_time
    assert (kern.dp, kern.tp) == (dense.dp, dense.tp)
