"""Multi-host (multi-process) runtime support.

TPU-native replacement for the reference's multi-node stack — GASNet/MPI
process bootstrap (reference: CMake FF_USE_GASNET + conduits,
.github/workflows/multinode-test.yml:29-74 runs `mpirun -np 2`) and the
per-MachineView NCCL communicator setup (reference: model.cc:3115-3153).
Here the collectives are XLA's, compiled from sharding annotations; what
remains host-side is (a) process bootstrap, (b) building ONE global mesh
whose outer axis rides the slow DCN links and whose inner axes ride ICI,
and (c) assembling global device arrays from per-host local batches.

On Cloud TPU pods `initialize()` needs no arguments — JAX discovers the
coordinator from the TPU metadata. On CPU/GPU clusters pass
coordinator_address/num_processes/process_id (the mpirun analog).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from flexflow_tpu.telemetry.trace import span


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
):
    """Bootstrap the JAX distributed runtime (idempotent; single-process
    callers may skip it entirely). The analog of Legion's
    `Runtime::start` under GASNet + the NCCL id exchange.

    MUST run before any other JAX call: even `jax.process_count()`
    initializes the local backend and poisons the distributed bootstrap,
    so idempotency is checked against the distributed client itself."""
    import jax

    if jax.distributed.is_initialized():
        return
    if (
        coordinator_address is None
        and num_processes is None
        and process_id is None
    ):
        try:
            jax.distributed.initialize()
        except (ValueError, RuntimeError):
            # single-process run without a cluster environment: fine
            return
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


def is_primary() -> bool:
    """True on the process that should print/save (reference: Legion
    control replication prints once from node 0)."""
    import jax

    return jax.process_index() == 0


def global_mesh(
    axis_names: Sequence[str],
    axis_sizes: Sequence[int],
    devices=None,
):
    """Build a Mesh over ALL processes' devices with DCN-friendly
    placement: `mesh_utils.create_device_mesh` keeps ICI neighbors
    adjacent on the inner axes, so the OUTERMOST axis (by convention the
    "data" axis — gradient all-reduce tolerates DCN latency, activations
    do not) is the one crossing hosts. The scaling-mesh recipe the
    reference approximates with its node-major MachineViews
    (machine_view.h:62-96). `devices` restricts the mesh to an explicit
    device list (serving meshes may use a subset of the machine);
    `create_device_mesh` requires len(devices) == prod(axis_sizes)."""
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    grid = mesh_utils.create_device_mesh(
        tuple(axis_sizes), devices=devices
    )
    return Mesh(grid, tuple(axis_names))


def place_array(value, sharding=None, multi: Optional[bool] = None):
    """Place ONE host array onto devices — the single-array core of
    `place_batch`, exposed so the serving placement layer
    (serving/distributed.py) routes KV pools and scheduler-assembled
    global batches through the same path. multi defaults to "is this a
    multi-process run"; when true every process passes the SAME global
    value and only the locally-owned shards materialize."""
    import jax

    if multi is None:
        multi = jax.process_count() > 1
    if sharding is None:
        return jax.device_put(value)
    if multi:
        g = np.asarray(value)
        return jax.make_array_from_callback(
            g.shape, sharding, lambda idx: g[idx]
        )
    return jax.device_put(value, sharding)


def place_batch(
    executor, batch: Dict[str, np.ndarray], multi: bool, tracer=None
) -> Dict[str, "np.ndarray"]:
    """THE batch-placement loop (single source of truth for both the
    single- and multi-host paths — Executor.shard_batch delegates here).

    multi=False: plain device_put with each input's searched sharding.
    multi=True: every process passes the SAME GLOBAL batch (fit()'s
    loader yields config.batch_size global rows identically everywhere)
    and `jax.make_array_from_callback` materializes only the shards this
    process's devices own — the analog of the reference's
    SingleDataLoader index-launch shard copies
    (python/flexflow_dataloader.cc: every node sees the whole dataset in
    zero-copy memory; each GPU's task copies out just its slice).
    `tracer`: the caller's Chrome tracer, for the span of each array."""
    import jax

    shapes = executor.input_shapes()
    out = {}
    for name, arr in batch.items():
        with span(f"train.input.shard_batch.{name}", tracer):
            if name in shapes:
                sharding = executor.sharding_for(shapes[name])
                out[name] = place_array(arr, sharding, multi=multi)
            else:
                out[name] = place_array(arr)
    return out


def shard_host_batch(
    executor, batch: Dict[str, np.ndarray]
) -> Dict[str, "np.ndarray"]:
    """Multi-host batch assembly from the global batch (works unchanged at
    process_count == 1; tests/multihost_helpers exercises it at 2)."""
    return place_batch(executor, batch, multi=True)
