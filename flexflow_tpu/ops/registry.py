"""Operator registry.

Each OperatorType registers:
  * `infer`  — parallel-shape inference: (input shapes, params) ->
               (output shapes, weight shapes). Degree-aware: it propagates
               input partitioning to outputs the way the reference's
               ParallelDimMappingRecord solver does (reference:
               model.cc:494-647), and raises if an illegal dim is
               partitioned (e.g. the reduction dim of a Linear without a
               Reduction parallel op downstream).
  * `lower`  — returns a pure function over *global logical* jnp arrays:
               fn(inputs, weights, ctx) -> outputs. GSPMD handles the
               distribution; sharding constraints are applied by the
               executor, not here.
  * `flops`  — analytic forward-FLOP estimate for the simulator.

The reference implements these as per-op C++ classes with
init/forward/backward Legion tasks (reference: include/flexflow/operator.h:51,
operator.h:187-193); here backward is `jax.grad` of the lowered function, so
only the forward lowering exists.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
from flexflow_tpu.core.types import OperatorType


@dataclasses.dataclass
class LowerCtx:
    """Execution context threaded through lowered ops."""

    train: bool = True
    rng: object = None  # jax PRNG key or None
    seq_length: Optional[int] = None  # reference: FFIterationConfig.seq_length
    # distribution context: ops whose lowering is sharding-aware (ring
    # attention under a partitioned sequence dim) read the mesh and the
    # node's parallel shapes; plain ops ignore these.
    mesh: object = None  # jax.sharding.Mesh or None
    axis_names: Tuple[str, ...] = ()
    in_shapes: Optional[Sequence[ParallelTensorShape]] = None
    # bf16 matmul operands with f32 accumulation — the MXU-native analog of
    # the reference's --allow-tensor-op-math-conversion (TF32/FP16 tensor
    # cores, model.cc:3668); set from FFConfig.allow_mixed_precision.
    bf16_matmul: bool = False


def mm_operands(ctx, *arrays):
    """Cast f32 matmul operands to bf16 when mixed precision is on.

    Accumulation stays f32 (every call site passes
    preferred_element_type=f32), so this trades mantissa bits on the
    operands for the MXU's native bf16 throughput."""
    if ctx is not None and getattr(ctx, "bf16_matmul", False):
        import jax.numpy as jnp

        return tuple(
            a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
            for a in arrays
        )
    return arrays


def mm_out_dtype(ctx, default_dtype):
    """Matmul OUTPUT dtype: bf16 when mixed precision is on, else the
    weight/input dtype. Keeping activations bf16 between ops halves the
    HBM traffic of every layer boundary (weights stay f32 master copies;
    the operand-cast VJP returns f32 gradients). The loss upcasts logits
    to f32 (runtime/loss.py), so training numerics stay AMP-standard."""
    if ctx is not None and getattr(ctx, "bf16_matmul", False):
        import jax.numpy as jnp

        return jnp.bfloat16
    return default_dtype


@dataclasses.dataclass
class OpDef:
    op_type: OperatorType
    infer: Callable[
        [Sequence[ParallelTensorShape], dict],
        Tuple[Tuple[ParallelTensorShape, ...], Tuple[ParallelTensorShape, ...]],
    ]
    lower: Callable[[dict], Callable]
    flops: Callable[[Sequence[ParallelTensorShape], dict], float] = None
    # dims of each input that may legally carry partitioning through this op
    # without a parallel-op rewrite; None = all dims partitionable.
    partitionable_dims: Optional[Callable] = None


_REGISTRY: Dict[OperatorType, OpDef] = {}


def register_op(
    op_type: OperatorType,
    infer,
    lower,
    flops=None,
):
    _REGISTRY[op_type] = OpDef(op_type, infer, lower, flops or (lambda s, p: 0.0))


def get_op_def(op_type: OperatorType) -> OpDef:
    if op_type not in _REGISTRY:
        raise KeyError(f"no OpDef registered for {op_type}")
    return _REGISTRY[op_type]


def has_op_def(op_type: OperatorType) -> bool:
    return op_type in _REGISTRY


def infer_shapes(op_type, input_shapes, params):
    return get_op_def(op_type).infer(input_shapes, params)


def lower_op(op_type, params) -> Callable:
    return get_op_def(op_type).lower(params)


def op_flops(op_type, input_shapes, params) -> float:
    return get_op_def(op_type).flops(input_shapes, params)


def _ensure_registered():
    """Import op implementation modules for their registration side effects."""
    from flexflow_tpu.ops import core_ops  # noqa: F401
    from flexflow_tpu.ops import attention  # noqa: F401
    from flexflow_tpu.ops import linear_attention  # noqa: F401
    from flexflow_tpu.ops import moe  # noqa: F401
    from flexflow_tpu.parallel import parallel_ops  # noqa: F401
