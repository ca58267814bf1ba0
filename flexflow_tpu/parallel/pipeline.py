"""Pipeline parallelism: GPipe over a `pipe` mesh axis.

The reference DECLARES pipeline parallelism but never implements it
(reference: OP_PIPELINE enum ffconst.h:151 + PIPELINE_*_TASK_ID
model.h:186-188 with no operator in src/parallel_ops/ — SURVEY §2.3);
this module is the TPU-native implementation that closes the gap.

Design (the idiomatic SPMD pipeline, per the public scaling-book recipe):
each device along the `pipe` mesh axis owns ONE stage's weights (the
stacked stage axis of the parameter pytree is sharded over `pipe`);
`shard_map` runs the same program on every stage; microbatches stream
through a `lax.scan` time loop; activations hop stage→stage via
`lax.ppermute`. One jitted function, XLA collectives over ICI, fully
differentiable (grads flow through ppermute), so the SAME train-step
machinery (jax.value_and_grad + optimizer) works unchanged.

Bubble fraction is the GPipe (S-1)/(T) with T = num_microbatches + S - 1
schedule steps; raise num_microbatches to amortize.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def _shift_right(x, axis_name: str, num_stages: int):
    """ppermute stage i → i+1 (stage 0 receives zeros from nowhere)."""
    perm = [(i, i + 1) for i in range(num_stages - 1)]
    return jax.lax.ppermute(x, axis_name, perm)


def gpipe(
    block_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    *,
    axis_name: str = "pipe",
    num_microbatches: int,
):
    """Run a homogeneous-stage pipeline INSIDE shard_map.

    block_fn(params_leaf_slice, activations) -> activations — one stage's
    computation; must map activations to activations of the same shape.
    stage_params: pytree whose leaves carry THIS stage's slice (shard_map
    has already split the stacked stage axis).
    x: [batch, ...] the microbatch source (meaningful on stage 0).

    Returns [batch, ...] outputs (meaningful on the LAST stage; other
    stages return zeros — psum over `pipe` outside if a replicated result
    is wanted).
    """
    num_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    batch = x.shape[0]
    if batch % num_microbatches != 0:
        raise ValueError(
            f"batch {batch} not divisible by num_microbatches={num_microbatches}"
        )
    mb = batch // num_microbatches
    xs = x.reshape((num_microbatches, mb) + x.shape[1:])
    # pad the microbatch stream with zeros for the drain phase
    pad = jnp.zeros((num_stages - 1, mb) + x.shape[1:], x.dtype)
    stream = jnp.concatenate([xs, pad], axis=0)

    def step(carry, x_t):
        recv = carry
        # stage 0 consumes the next microbatch; others consume the hop
        inp = jnp.where(stage == 0, x_t, recv)
        out = block_fn(stage_params, inp)
        send = _shift_right(out, axis_name, num_stages)
        # emit this step's output (only the last stage's is real)
        return send, out

    # the carry dtype must match the BLOCK's output dtype, not the input's:
    # under mixed precision blocks emit bf16 activations (mm_out_dtype)
    # while the pipeline entry is f32
    out_sd = jax.eval_shape(block_fn, stage_params, xs[0])
    _, outs = jax.lax.scan(
        step, jnp.zeros(out_sd.shape, out_sd.dtype), stream
    )
    # the last stage produced microbatch m at step m + (S-1)
    tail = outs[num_stages - 1 :]
    y = tail.reshape((batch,) + tail.shape[2:])
    is_last = (stage == num_stages - 1).astype(y.dtype)
    return y * is_last


def pipeline_apply(
    mesh: Mesh,
    block_fn: Callable,
    stacked_params,
    x,
    *,
    axis_name: str = "pipe",
    num_microbatches: int = 4,
    data_axis: str | None = None,
    stage_leading_axis: bool = False,
):
    """jit-able entry: shard_map the GPipe loop over `mesh`.

    stacked_params: pytree with a leading stage axis on every leaf
    (stage s's weights at index s), sharded over `axis_name`.
    x: global [batch, ...] input; optionally data-parallel over `data_axis`
    (pipeline × data two-axis meshes compose).

    stage_leading_axis: when each stage runs SEVERAL model blocks (leaves
    stacked [num_stages * blocks_per_stage, ...]), pass True — block_fn
    then receives its slice with the per-stage leading axis intact
    ([blocks_per_stage, ...]) and is responsible for looping over it.

    Returns the global [batch, ...] output, replicated over `axis_name`
    (psum of the last stage's emission).
    """
    def inner(params, xin):
        if stage_leading_axis:
            local = params
        else:
            local = jax.tree_util.tree_map(lambda p: p[0], params)
        y = gpipe(
            block_fn,
            local,
            xin,
            axis_name=axis_name,
            num_microbatches=num_microbatches,
        )
        return jax.lax.psum(y, axis_name)

    p_spec = jax.tree_util.tree_map(
        lambda _: PartitionSpec(axis_name), stacked_params
    )
    x_spec = PartitionSpec(data_axis) if data_axis else PartitionSpec()
    # replication checking off here and in submesh.py / ring_attention.py:
    # the inner functions use psum/all_gather/ppermute collectives the
    # checker cannot always see through
    mapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(p_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return mapped(stacked_params, x)


def pipeline_bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """GPipe bubble overhead: idle step fraction of the schedule."""
    steps = num_microbatches + num_stages - 1
    return (num_stages - 1) / steps
