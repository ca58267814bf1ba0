"""Property-style hardening: random small graphs must survive the WHOLE
pipeline — builder → search (both engines) → strategy lowering →
compile (substitution pass included) → one train step with finite loss —
on the 8-device virtual mesh. The reference's equivalent safety net is
its randomized-strategy simulator tests (SURVEY §4); here the property
is end-to-end because the lowering is where round-1 bugs actually hid
(degree stacking, mixed-view collapse, bracket seams)."""

import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.core.types import AggrMode

CLASSES = 4


def random_model(seed: int):
    """A random but shape-valid model: dense/relu trunk with optional
    embedding branches, concat merges, residual adds, dropout."""
    rng = np.random.RandomState(seed)
    batch = int(rng.choice([16, 32, 64]))
    m = FFModel(FFConfig(batch_size=batch, seed=seed))
    feats = []
    data = {}

    in_dim = int(rng.choice([8, 16, 32]))
    x = m.create_tensor([batch, in_dim], name="x")
    data["x"] = rng.randn(batch, in_dim).astype(np.float32)
    t = x
    for li in range(rng.randint(1, 4)):
        width = int(rng.choice([16, 32, 64]))
        act = ActiMode.RELU if rng.rand() < 0.7 else ActiMode.NONE
        t = m.dense(t, width, activation=act, use_bias=bool(rng.rand() < 0.5))
        if rng.rand() < 0.3:
            t2 = m.dense(t, width, activation=ActiMode.NONE, use_bias=False)
            t = m.add(t, t2)  # residual
        if rng.rand() < 0.3:
            t = m.dropout(t, rate=0.1)
    feats.append(t)

    for ei in range(rng.randint(0, 3)):
        vocab = int(rng.choice([128, 1024]))
        dim = int(rng.choice([8, 16]))
        ids = m.create_tensor(
            [batch, 2], dtype=DataType.INT32, name=f"ids{ei}"
        )
        data[f"ids{ei}"] = rng.randint(0, vocab, (batch, 2)).astype(np.int32)
        feats.append(m.embedding(ids, vocab, dim, aggr=AggrMode.SUM))

    t = m.concat(feats, axis=1) if len(feats) > 1 else feats[0]
    m.dense(t, CLASSES, name="head")
    y = rng.randint(0, CLASSES, (batch,)).astype(np.int32)
    return m, data, y


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("engine", ["mesh", "unity", "mcmc"])
def test_random_graph_survives_search_and_training(seed, engine):
    m, data, y = random_model(seed)
    m.config.search_budget = 8
    m.config.search_engine = engine
    m.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    hist = m.fit(data, y, epochs=1, verbose=False)
    assert np.isfinite(hist[-1]["loss_sum"]), (
        seed,
        engine,
        m.strategy.name,
    )


def test_auto_flash_fires_at_threshold_boundary():
    """Regression: a score tensor exactly AT the 2 GiB threshold must take
    the streaming path (it used to take dense with strict >, materializing
    the 2 GiB it exists to avoid)."""
    from flexflow_tpu.ops.attention import _FLASH_SCORE_BYTES, _auto_flash

    # batch 1, heads 8, seq 8192: 1*8*8192*8192*4 == 2 GiB exactly
    assert 1 * 8 * 8192 * 8192 * 4 == _FLASH_SCORE_BYTES
    assert _auto_flash(1, 8, 8192, 8192)
    assert not _auto_flash(1, 8, 8192, 8192 - 512)
