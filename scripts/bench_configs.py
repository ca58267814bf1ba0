"""Real-chip throughput for every reference target config (VERDICT r1
item 3; reference: each examples/cpp binary prints THROUGHPUT, recorded
nowhere — this script records ours).

    python scripts/bench_configs.py [--out BENCH_CONFIGS.json] [--f32]

Times the jitted train step of each config with the shared on-device
lax.scan differencing (flexflow_tpu/utils/benchmark.py — readback and dispatch
constants cancel). Prints one JSON line per config and writes the table.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def _cfg(batch_size, mixed):
    from flexflow_tpu import FFConfig

    cfg = FFConfig(batch_size=batch_size)
    cfg.allow_mixed_precision = mixed
    return cfg


def build_alexnet(mixed):
    """BASELINE config 1: AlexNet on CIFAR-10, bs 64
    (reference: bootcamp_demo/ff_alexnet_cifar10.py)."""
    from flexflow_tpu import FFModel, LossType, MetricsType, SGDOptimizer
    from flexflow_tpu.models import build_alexnet as ba

    bs = 64
    m = FFModel(_cfg(bs, mixed))
    # CIFAR images upscaled to the reference's 229x229 input (alexnet.cc:58)
    x = m.create_tensor([bs, 229, 229, 3], name="x")
    ba(m, x, num_classes=10)
    m.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY],
    )
    rng = np.random.RandomState(0)
    batch = {
        "x": rng.randn(bs, 229, 229, 3).astype(np.float32),
        "label": rng.randint(0, 10, size=(bs,)).astype(np.int32),
    }
    return m, batch, bs


def build_resnet50(mixed):
    """BASELINE config 2: ResNet-50 on synthetic ImageNet
    (reference: examples/python/native/resnet.py)."""
    from flexflow_tpu import FFModel, LossType, MetricsType, SGDOptimizer
    from flexflow_tpu.models import build_resnet50 as br

    bs = 16
    m = FFModel(_cfg(bs, mixed))
    x = m.create_tensor([bs, 224, 224, 3], name="x")
    br(m, x, num_classes=1000)
    m.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY],
    )
    rng = np.random.RandomState(0)
    batch = {
        "x": rng.randn(bs, 224, 224, 3).astype(np.float32),
        "label": rng.randint(0, 1000, size=(bs,)).astype(np.int32),
    }
    return m, batch, bs


def build_mt5(mixed):
    """BASELINE config 4: mT5-small encoder (reference: align/mt5_encoder)."""
    from flexflow_tpu import (
        AdamOptimizer,
        DataType,
        FFModel,
        LossType,
        MetricsType,
    )
    from flexflow_tpu.models import build_mt5_encoder as bm

    bs, vocab, seq, hidden, heads, layers = 8, 32128, 128, 512, 8, 8
    m = FFModel(_cfg(bs, mixed))
    ids = m.create_tensor([bs, seq], dtype=DataType.INT32, name="ids")
    t = bm(m, ids, vocab_size=vocab, hidden=hidden, num_heads=heads,
           num_layers=layers)
    m.dense(t, 1, use_bias=False)
    m.compile(
        optimizer=AdamOptimizer(alpha=1e-4),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[MetricsType.MEAN_SQUARED_ERROR],
    )
    rng = np.random.RandomState(0)
    batch = {
        "ids": rng.randint(0, vocab, size=(bs, seq)).astype(np.int32),
        "label": rng.randn(bs, seq, 1).astype(np.float32),
    }
    return m, batch, bs


def build_dlrm(mixed):
    """BASELINE config 5: DLRM, embedding tables + MLPs
    (reference: examples/cpp/DLRM, --enable-parameter-parallel)."""
    from flexflow_tpu import (
        DataType,
        FFModel,
        LossType,
        MetricsType,
        SGDOptimizer,
    )
    from flexflow_tpu.models import build_dlrm as bd

    bs = 64
    emb_sizes = [1_000_000] * 4
    m = FFModel(_cfg(bs, mixed))
    dense = m.create_tensor([bs, 4], name="dense_features")
    sparse = [
        m.create_tensor([bs, 1], dtype=DataType.INT32, name=f"sparse_{i}")
        for i in range(len(emb_sizes))
    ]
    bd(m, dense, sparse, embedding_sizes=emb_sizes)
    m.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[MetricsType.MEAN_SQUARED_ERROR],
    )
    rng = np.random.RandomState(0)
    batch = {"dense_features": rng.randn(bs, 4).astype(np.float32),
             "label": rng.rand(bs, 2).astype(np.float32)}
    for i, v in enumerate(emb_sizes):
        batch[f"sparse_{i}"] = rng.randint(0, v, size=(bs, 1)).astype(np.int32)
    return m, batch, bs


def build_transformer(mixed):
    """BASELINE config 3 (bench.py's flagship; here for one-table unity)."""
    sys.path.insert(0, ROOT)
    from examples.transformer import build_transformer as bt, synthetic_batch

    bs, seq, hidden, heads, layers = 8, 512, 1024, 16, 12
    cfg = _cfg(bs, mixed)
    model, _ = bt(cfg, batch_size=bs, seq_len=seq, hidden=hidden,
                  num_heads=heads, num_layers=layers)
    batch = synthetic_batch(bs, seq, hidden)
    return model, batch, bs


CONFIGS = {
    "alexnet_cifar10_bs64": build_alexnet,
    "resnet50_224_bs16": build_resnet50,
    "transformer_12L_1024h_seq512_bs8": build_transformer,
    "mt5_encoder_8L_512h_seq128_bs8": build_mt5,
    "dlrm_4x1M_bs64": build_dlrm,
}


def main():
    mixed = "--f32" not in sys.argv
    out_path = "BENCH_CONFIGS.json"
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    only = [a for a in sys.argv[1:] if not a.startswith("-") and a != out_path]

    results = {}
    for name, builder in CONFIGS.items():
        if only and name not in only:
            continue
        model, batch, bs = builder(mixed)
        from flexflow_tpu.utils.benchmark import measure_train_step

        per_step = measure_train_step(
            model, model.executor.shard_batch(batch), reps=4, estimates=3,
        )
        import math as _math

        if not _math.isfinite(per_step) or per_step <= 0:
            row = {
                "metric": name,
                "error": "measurement below the differencing noise floor",
                "precision": "bf16-matmul" if mixed else "f32",
            }
            results[name] = row
            print(json.dumps(row), flush=True)
            continue
        thpt = bs / per_step
        row = {
            "metric": name,
            "value": round(thpt, 2),
            "unit": "samples/s",
            "step_ms": round(per_step * 1e3, 3),
            "precision": "bf16-matmul" if mixed else "f32",
        }
        results[name] = row
        print(json.dumps(row), flush=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    main()
