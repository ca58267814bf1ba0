"""Decompose the transformer@bs8 predicted/measured residual (round-4
BASELINE: 1.41 post-family-correction) into attention vs dense-stack
contributions, on the real chip.

For the flagship and two stripped variants (attention-only, MLP-only)
this prints: predicted step (measured-mode cost model, no family
correction), actual step (pure-device scan differencing), ratio, and the
predicted per-task durations grouped by op kind.

Run:  python scripts/probe_attn_pricing.py [--layers 12] [-b 8]
"""

import argparse
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from flexflow_tpu import ActiMode, FFConfig, FFModel, LossType, SGDOptimizer

CHIP = "v5e"


def _cfg(batch):
    cfg = FFConfig(batch_size=batch, learning_rate=0.01)
    cfg.chip = CHIP
    cfg.allow_mixed_precision = True
    return cfg


def build(batch, seq, hidden, heads, layers, mode):
    model = FFModel(_cfg(batch))
    x = model.create_tensor([batch, seq, hidden], name="x")
    t = x
    for _ in range(layers):
        if mode in ("full", "attn"):
            t = model.multihead_attention(t, t, t, hidden, heads)
        if mode in ("full", "mlp"):
            t = model.dense(t, hidden, activation=ActiMode.RELU, use_bias=False)
            t = model.dense(t, hidden, use_bias=False)
    t = model.dense(t, 1, use_bias=False)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[],
    )
    return model


def predict(model, calib):
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.simulator import estimate_graph_cost

    cm = CostModel(
        MachineSpec(1, 1, chip=CHIP),
        measure=True,
        mixed_precision=True,
        calibration_file=calib,
        family_correction=False,
    )
    export = {}
    cost = estimate_graph_cost(model.graph, cm, (1,), export=export)
    cm.flush_calibration()
    groups = defaultdict(float)
    for name, dur in zip(export["names"], export["duration"]):
        base = name.split(".")[0].rstrip("0123456789_")
        kind = name.rsplit(".", 1)[-1]
        groups[f"{base}.{kind}"] += dur
    return cost.step_time, dict(groups)


def actual(model, data):
    from flexflow_tpu.utils.benchmark import measure_train_step

    batch = model.executor.shard_batch(data)
    return measure_train_step(model, batch, estimates=3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-b", type=int, default=8)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument(
        "--calibration-file", default="calibration/v5e.json"
    )
    ap.add_argument(
        "--modes", nargs="*", default=["full", "attn", "mlp"]
    )
    args = ap.parse_args()

    rng = np.random.RandomState(0)
    data = {
        "x": rng.randn(args.b, args.seq, args.hidden).astype(np.float32),
        "label": rng.randn(args.b, args.seq, 1).astype(np.float32),
    }
    for mode in args.modes:
        model = build(
            args.b, args.seq, args.hidden, args.heads, args.layers, mode
        )
        pred, groups = predict(model, args.calibration_file)
        meas = actual(model, data)
        print(f"\n=== {mode}: predicted {pred*1e3:.2f} ms, "
              f"measured {meas*1e3:.2f} ms, ratio {pred/meas:.2f}")
        for k, v in sorted(groups.items(), key=lambda kv: -kv[1])[:10]:
            print(f"    {k:32s} {v*1e3:8.3f} ms")


if __name__ == "__main__":
    main()
