"""Long-context Transformer training (seq 8192 on ONE chip).

The reference cannot run this workload at all: its attention is a
monolithic cuDNN call per shard that materializes the [s, s] scores
(attention.cu:35) — at seq 8192 the f32 score tensor alone is 4.3 GB per
layer and the dense path collapses. Here `use_flash="auto"` switches to the fused streaming kernel
past the 2 GiB score threshold, so the same builder program trains at
seq 8192+ unchanged; across chips the sequence dim shards with ring
attention (sequence_parallel_strategy).

    python examples/longctx_transformer.py [-b 1] [-i 4] [--seq 8192]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from examples.common import run_training  # noqa: E402
from flexflow_tpu import (  # noqa: E402
    ActiMode,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)


def build(cfg: FFConfig, seq: int, hidden: int = 512, heads: int = 8,
          layers: int = 2):
    ff = FFModel(cfg)
    x = ff.create_tensor([cfg.batch_size, seq, hidden], name="x")
    t = x
    for _ in range(layers):
        t = ff.multihead_attention(t, t, t, hidden, heads)
        t = ff.dense(t, hidden, activation=ActiMode.RELU, use_bias=False)
    ff.dense(t, 1, use_bias=False)
    return ff


def main():
    seq = 8192
    if "--seq" in sys.argv:
        i = sys.argv.index("--seq")
        seq = int(sys.argv[i + 1])
        del sys.argv[i : i + 2]
    explicit_batch = "-b" in sys.argv or "--batch-size" in sys.argv
    cfg = FFConfig.parse_args()
    if not explicit_batch:  # the 64 default is far too big at quadratic cost
        cfg.batch_size = 1
    cfg.allow_mixed_precision = True
    hidden = 512
    ff = build(cfg, seq, hidden=hidden)
    ff.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[],
    )
    n = cfg.batch_size * (cfg.iterations or 2)
    rng = np.random.RandomState(0)
    data = {"x": rng.randn(n, seq, hidden).astype(np.float32)}
    y = rng.randn(n, seq, 1).astype(np.float32)
    run_training(ff, data, y, cfg)


if __name__ == "__main__":
    main()
