"""Flagship Transformer batch sweep on the real chip (bs 8/16/32).

Round-2 recorded bs8 21.3 ms (52-54% MFU), bs16 54.7 ms (40%), bs32
109.7 ms (40%). Round 3 adds batch-chunked dense attention; this script
re-measures the full train step at all three batch sizes and prints the
implied MFU against the 107 TF/s raw matmul measured in August.
"""

from __future__ import annotations

import json
import sys

sys.path.insert(0, ".")

from examples.transformer import build_transformer, synthetic_batch
from flexflow_tpu import FFConfig
from flexflow_tpu.utils.benchmark import measure_train_step

# model FLOPs per sample (fwd+bwd ~ 3x fwd) at hidden 1024, seq 512, 12L
HIDDEN, SEQ, HEADS, LAYERS = 1024, 512, 16, 12
ANCHOR_TFLOPS = 107.0  # measured raw bf16 matmul on this chip (54% of peak)


def step_flops(bs):
    e, s = HIDDEN, SEQ
    per_layer = 4 * 2 * s * e * e + 2 * 2 * s * s * e + 2 * 2 * s * e * 4 * e
    return 3.0 * bs * LAYERS * per_layer


def main():
    rows = []
    for bs in (8, 16, 32):
        cfg = FFConfig(batch_size=bs, learning_rate=0.01)
        cfg.allow_mixed_precision = True
        model, _ = build_transformer(
            cfg, batch_size=bs, seq_len=SEQ, hidden=HIDDEN,
            num_heads=HEADS, num_layers=LAYERS,
        )
        batch = model.executor.shard_batch(synthetic_batch(bs, SEQ, HIDDEN))
        per_step = measure_train_step(model, batch, reps=6)
        tfps = step_flops(bs) / per_step / 1e12
        rows.append(
            {
                "bs": bs,
                "step_ms": round(per_step * 1e3, 2),
                "samples_per_s": round(bs / per_step, 1),
                "tflops": round(tfps, 1),
                "mfu_vs_anchor_pct": round(100 * tfps / ANCHOR_TFLOPS * 0.54, 1),
            }
        )
        print(json.dumps(rows[-1]), flush=True)


if __name__ == "__main__":
    main()
