"""Concurrent branch execution on disjoint device sub-blocks.

The reference executes per-op MachineViews: Unity's nonsequence split
places parallel branches on vertical/horizontal resource sub-blocks and
runs them CONCURRENTLY (reference: graph.cc:252-306 resource splits +
mapper.cc:377-481 per-point placement — Legion is MPMD, every task can
target its own device set). Under one jitted XLA program that freedom
does not exist: GSPMD is SPMD, one program on every device, and two
dataflow-independent ops each sharded over the full mesh execute
sequentially.

This module provides the TPU-native middle ground:
`concurrent_branches` runs K branch functions on K disjoint sub-blocks
of a mesh axis inside ONE jit program, via shard_map + lax.switch on the
block index — each device group executes only its branch's computation,
so the branches genuinely overlap in time. It is the executable
counterpart of the unity DP's sub-block costing
(UnitySearch allow_subblock_views).

SPMD restrictions (vs the reference's full MPMD generality, documented
here once):
  * every branch must return outputs with the SAME shapes/dtypes
    (lax.switch unifies the program across groups);
  * inputs are broadcast to every group (each group reads what it
    needs);
  * the branch axis size must equal the number of branches.

Differentiable end to end (switch + psum have transposes), so it can sit
inside a train step.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def _stack_branch_params(mesh: Mesh, axis_name: str, branch_params):
    """Stack per-branch parameter pytrees on a leading branch axis,
    sharded over `axis_name` — each block's devices hold ONLY their
    branch's slice (the reference's per-op weight placement). Branches
    must share a parameter structure (like unity's template blocks)."""
    flat = [jax.tree_util.tree_flatten(p) for p in branch_params]
    treedef = flat[0][1]
    for _, td in flat[1:]:
        if td != treedef:
            raise ValueError(
                "branches must share a parameter structure "
                f"({td} != {treedef})"
            )
    stacked = [
        jnp.stack([leaves[i] for leaves, _ in flat])
        for i in range(len(flat[0][0]))
    ]
    stacked = [
        jax.device_put(
            s,
            NamedSharding(
                mesh,
                PartitionSpec(axis_name, *([None] * (s.ndim - 1))),
            ),
        )
        for s in stacked
    ]
    return stacked, treedef


def _run_block_mapped(mesh, axis_name, body, stacked, x):
    """Shared shard_map harness for the block axis: `body(local_leaves,
    xin)` runs with this block's parameter slices and the broadcast
    input; outputs gather to a replicated [k, ...] stack."""

    def inner(params_slices, xin):
        out = body([p[0] for p in params_slices], xin)
        return jax.tree_util.tree_map(
            lambda o: jax.lax.all_gather(o, axis_name), out
        )

    specs_p = [
        PartitionSpec(axis_name, *([None] * (s.ndim - 1))) for s in stacked
    ]
    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(tuple(specs_p), PartitionSpec()),
        out_specs=PartitionSpec(),
        check_vma=False,
    )
    return fn(tuple(stacked), x)


def concurrent_template_branches(
    mesh: Mesh,
    axis_name: str,
    template_fn: Callable,
    branch_params: Sequence,
    x,
):
    """Template-identical special case of concurrent_branches: every
    branch runs the SAME function with its own parameters (unity's
    nonsequence splits over repeated structures — Inception towers,
    per-expert stacks). No lax.switch needed: one program, per-block
    weights, which XLA can overlap freely. Returns the [k, ...] stacked
    outputs (branch i at index i, replicated)."""
    k = len(branch_params)
    if mesh.shape[axis_name] != k:
        raise ValueError(
            f"axis {axis_name!r} has size {mesh.shape[axis_name]}, "
            f"need one block per branch ({k})"
        )
    stacked, treedef = _stack_branch_params(mesh, axis_name, branch_params)

    def body(local_leaves, xin):
        return template_fn(
            jax.tree_util.tree_unflatten(treedef, local_leaves), xin
        )

    return _run_block_mapped(mesh, axis_name, body, stacked, x)


def concurrent_branches(
    mesh: Mesh,
    axis_name: str,
    branch_fns: Sequence[Callable],
    branch_params: Sequence,
    x,
):
    """Run branch_fns[i](branch_params[i], x) on sub-block i of
    `axis_name`, concurrently, inside one jitted program.

    branch_params: one pytree per branch; leaves are stacked on a new
    leading axis internally (sharded over `axis_name`), so each group's
    devices hold only their branch's parameters — the per-op weight
    placement of the reference's MachineViews.

    Returns the list of branch outputs (each replicated over the mesh).
    """
    k = len(branch_fns)
    if mesh.shape[axis_name] != k:
        raise ValueError(
            f"axis {axis_name!r} has size {mesh.shape[axis_name]}, "
            f"need one block per branch ({k})"
        )
    stacked, treedef = _stack_branch_params(mesh, axis_name, branch_params)

    def body(local_leaves, xin):
        idx = jax.lax.axis_index(axis_name)

        def make_branch(i):
            def run(args):
                local_p, xb = args
                return branch_fns[i](
                    jax.tree_util.tree_unflatten(treedef, local_p), xb
                )

            return run

        return jax.lax.switch(
            idx, [make_branch(i) for i in range(k)], (local_leaves, xin)
        )

    stacked_out = _run_block_mapped(mesh, axis_name, body, stacked, x)
    return [
        jax.tree_util.tree_map(lambda o: o[i], stacked_out)
        for i in range(k)
    ]
