"""One rank of the 2-process multi-host fit test.

The analog of the reference's multinode CI leg (reference:
.github/workflows/multinode-test.yml:29-74 — `mpirun -np 2` with per-rank
GPU slicing via tests/multinode_helpers/mpi_wrapper1.sh): each process
brings 4 virtual CPU devices, joins a TCP coordinator via
multihost.initialize, and runs the SAME dp=8 fit(); rank 0 prints the
per-epoch losses as JSON for the parent to compare against a
single-process 8-device run.

Env (set by the parent): JAX_PLATFORMS=cpu,
XLA_FLAGS=--xla_force_host_platform_device_count=4.
Args: --coordinator host:port --num-processes N --process-id I
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    args = ap.parse_args()

    # initialize() must run before ANY backend touch (its docstring); the
    # launching test passes JAX_PLATFORMS=cpu in the environment
    import jax

    from flexflow_tpu.runtime import multihost

    multihost.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )

    # match conftest so losses are bit-comparable to the in-process run
    jax.config.update("jax_default_matmul_precision", "highest")

    assert jax.process_count() == args.num_processes, (
        jax.process_count(),
        args.num_processes,
    )
    assert jax.device_count() == 4 * args.num_processes

    import numpy as np

    from flexflow_tpu import ActiMode, FFConfig, FFModel, LossType, SGDOptimizer

    batch, feat, classes = 16, 8, 4
    rng = np.random.default_rng(0)  # identical data on every process
    x = rng.normal(size=(2 * batch, feat)).astype(np.float32)
    y = rng.integers(0, classes, size=(2 * batch,)).astype(np.int32)

    m = FFModel(FFConfig(batch_size=batch))
    t = m.create_tensor([batch, feat], name="x")
    t = m.dense(t, 16, activation=ActiMode.RELU)
    m.dense(t, classes)
    m.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    assert m.executor.mesh.size == 4 * args.num_processes

    history = m.fit(x, y, epochs=3, verbose=False)
    losses = [
        round(h["loss_sum"] / max(h["train_all"], 1), 6) for h in history
    ]
    if multihost.is_primary():
        print(json.dumps({"losses": losses, "devices": jax.device_count()}))


if __name__ == "__main__":
    main()
