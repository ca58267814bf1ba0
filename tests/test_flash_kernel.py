"""Correctness of the hand-tiled Pallas flash kernel (ops/pallas/
flash_kernel.py) against dense attention — forward, lse, and the custom
VJP — via the Pallas interpreter on CPU (the same kernel code the TPU
path compiles; SURVEY §4 simulated-topology strategy)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.pallas.flash_kernel import (
    flash_attention_tpu,
    supports,
)

B, H, D = 2, 2, 32
BQ = BK = 128


def _dense(q, k, v, causal):
    d = q.shape[-1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype), logits


def _rand(seq, dtype=jnp.float32):
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(
        rng.randn(B, seq, H, D).astype(np.float32), dtype
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _rand(256)
    out = flash_attention_tpu(
        q, k, v, causal=causal, block_q=BQ, block_k=BK, interpret=True
    )
    ref, _ = _dense(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_lse_matches_dense():
    q, k, v = _rand(256)
    out, lse = flash_attention_tpu(
        q, k, v, causal=True, block_q=BQ, block_k=BK,
        return_lse=True, interpret=True,
    )
    _, logits = _dense(q, k, v, causal=True)
    ref_lse = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(causal):
    q, k, v = _rand(256)

    def loss_flash(q, k, v):
        o = flash_attention_tpu(
            q, k, v, causal=causal, block_q=BQ, block_k=BK, interpret=True
        )
        return jnp.sum(o * jnp.cos(o.astype(jnp.float32)))

    def loss_dense(q, k, v):
        o, _ = _dense(q, k, v, causal)
        return jnp.sum(o * jnp.cos(o.astype(jnp.float32)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_lse_cotangent():
    """The with-lse VJP folds the lse cotangent through the delta shift;
    compare against autodiff of the dense logsumexp."""
    q, k, v = _rand(128)

    def loss_flash(q, k, v):
        o, lse = flash_attention_tpu(
            q, k, v, causal=False, block_q=BQ, block_k=BK,
            return_lse=True, interpret=True,
        )
        return jnp.sum(o) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        o, logits = _dense(q, k, v, False)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(o) + jnp.sum(jnp.sin(lse))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_uneven_seq_blocks():
    """kv longer than q (cross-attention-like), distinct block sizes."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 128, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 384, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 384, H, D).astype(np.float32))
    out = flash_attention_tpu(
        q, k, v, block_q=128, block_k=128, interpret=True
    )
    ref, _ = _dense(q, k, v, False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# -- the whole-sequence form (PR 59) ------------------------------------------


def _sdpa_loss(fn, w):
    return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()


@pytest.mark.parametrize("sq", [128, 512])
@pytest.mark.parametrize("block_heads", [2, 4, None], ids=["2", "4", "all"])
@pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"]
)
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_whole_form_matches_sdpa(causal, dtype, block_heads, sq):
    """One call forward and one backward over the projections' own rows,
    held to `scaled_dot_product_attention`: values and the three
    gradients, a batch of 3, 2 / 4 / all 8 heads a grid step. bfloat16
    differs by roundings of single values only (both round the
    probabilities before `p v`): a few units of the last place."""
    from flexflow_tpu.ops.attention import scaled_dot_product_attention

    rng = np.random.RandomState(sq)
    q, k, v, w = (
        jnp.asarray(rng.randn(3, sq, 8, 64).astype(np.float32), dtype)
        for _ in range(4)
    )
    whole = functools.partial(
        flash_attention_tpu, causal=causal, block_heads=block_heads,
        interpret=True,
    )
    ref = functools.partial(scaled_dot_product_attention, causal=causal)
    w = w.astype(jnp.float32)
    got = (whole(q, k, v), *jax.grad(_sdpa_loss(whole, w), (0, 1, 2))(q, k, v))
    want = (ref(q, k, v), *jax.grad(_sdpa_loss(ref, w), (0, 1, 2))(q, k, v))
    # bfloat16: values of a few units, 8 mantissa bits
    tol = dict(atol=5e-5, rtol=5e-4) if dtype == jnp.float32 else dict(
        atol=6e-2, rtol=2e-2
    )
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol
        )


@pytest.fixture
def forms(monkeypatch):
    """Which form's forward each `flash_attention_tpu` call traced."""
    from flexflow_tpu.ops.pallas import flash_kernel as fk

    seen = []
    for name in ("_whole_fwd", "_fwd"):
        inner = getattr(fk, name)

        def spy(*a, _inner=inner, _name=name, **kw):
            seen.append(_name)
            return _inner(*a, **kw)

        monkeypatch.setattr(fk, name, spy)
    return seen


@pytest.mark.parametrize(
    "seq,kwargs,form",
    [
        (256, {}, "_whole_fwd"),
        # the ring's residual: the grid form, whatever the length
        (256, {"return_lse": True}, "_fwd"),
        # a caller that names its blocks asks for the grid
        (256, {"block_q": 128, "block_k": 128}, "_fwd"),
        # one head's score block and the backward's temporaries are over
        # the VMEM reckoning
        (2048, {}, "_fwd"),
    ],
    ids=["short", "return_lse", "named_blocks", "over_the_cap"],
)
def test_which_form_a_call_takes(forms, seq, kwargs, form):
    """...and the grid form's results are what they were: against dense."""
    rng = np.random.RandomState(2)
    q, k, v = (
        jnp.asarray(rng.randn(1, seq, 2, 64).astype(np.float32))
        for _ in range(3)
    )
    out = flash_attention_tpu(q, k, v, causal=True, interpret=True, **kwargs)
    assert forms == [form]
    ref, logits = _dense(q, k, v, True)
    if kwargs.get("return_lse"):
        out, lse = out
        np.testing.assert_allclose(
            lse, jax.scipy.special.logsumexp(logits, axis=-1),
            atol=2e-5, rtol=2e-5,
        )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "shape,itemsize,heads",
    [
        ((512, 512, 16, 64), 2, 8),  # the training cells', bf16
        ((512, 512, 16, 64), 4, 8),
        ((256, 256, 12, 64), 2, 12),  # no divisor in sublane tiles: all
        ((1024, 1024, 16, 64), 2, 8),
        ((2048, 2048, 16, 64), 2, None),  # the grid form's
        ((512, 1024, 16, 64), 2, None),  # no self-attention
        ((100, 100, 16, 64), 2, None),  # not in lane tiles
        ((128, 128, 4, 8), 4, None),  # 32 lanes of heads: no whole tile
        ((512, 512, 8, 128), 2, 8),
    ],
)
def test_whole_block_heads(shape, itemsize, heads):
    from flexflow_tpu.ops.pallas import flash_kernel as fk

    assert fk.whole_block_heads(*shape, itemsize) == heads
    assert fk.supports_whole(*shape, itemsize) == (heads is not None)


def test_supports():
    assert supports(4096, 4096, 64)
    assert supports(256, 256, 64)
    assert not supports(100, 100, 64)  # not lane-tileable


def test_compile_installs_calibrated_tiles(tmp_path):
    """compile() with --calibration-file installs the table's measured
    flash block sizes and dense-attention caps (the per-platform
    replacement for hardcoded constants)."""
    import json

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.ops import attention as attn_mod
    from flexflow_tpu.ops.pallas import flash_kernel as fk

    calib = tmp_path / "chip.json"
    calib.write_text(
        json.dumps(
            {
                "flash_blocks": {"block_q": 256, "block_k": 1024},
                "attn_caps": {"mono_mb": 48, "chunk_mb": 40},
            }
        )
    )
    saved_tuned = dict(fk._TUNED)
    saved_caps = (
        attn_mod._DENSE_MONO_SCORE_BYTES,
        attn_mod._DENSE_CHUNK_SCORE_BYTES,
    )
    try:
        cfg = FFConfig(batch_size=4)
        cfg.calibration_file = str(calib)
        m = FFModel(cfg)
        x = m.create_tensor([4, 8], name="x")
        m.dense(x, 4)
        m.compile(
            optimizer=SGDOptimizer(lr=0.1),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[],
        )
        assert fk._TUNED == {"block_q": 256, "block_k": 1024}
        assert attn_mod._DENSE_MONO_SCORE_BYTES == 48 << 20
        assert attn_mod._DENSE_CHUNK_SCORE_BYTES == 40 << 20
    finally:
        fk._TUNED.update(saved_tuned)
        (
            attn_mod._DENSE_MONO_SCORE_BYTES,
            attn_mod._DENSE_CHUNK_SCORE_BYTES,
        ) = saved_caps
