"""Core enums and type definitions for flexflow_tpu.

TPU-native re-design of the reference's type system
(reference: include/flexflow/ffconst.h:62-232). We keep the *vocabulary*
(operator types, loss/metrics enums, sync types) because the search engine,
substitution rules, and frontends key off it, but the values and layout are
our own.
"""

from __future__ import annotations

import enum

import jax.numpy as jnp


class DataType(enum.Enum):
    """Tensor element types (reference: ffconst.h DataType)."""

    BOOL = "bool"
    INT32 = "int32"
    INT64 = "int64"
    HALF = "float16"
    BFLOAT16 = "bfloat16"
    FLOAT = "float32"
    DOUBLE = "float64"

    def to_jnp(self):
        return {
            DataType.BOOL: jnp.bool_,
            DataType.INT32: jnp.int32,
            DataType.INT64: jnp.int64,
            DataType.HALF: jnp.float16,
            DataType.BFLOAT16: jnp.bfloat16,
            DataType.FLOAT: jnp.float32,
            DataType.DOUBLE: jnp.float64,
        }[self]

    @staticmethod
    def from_jnp(dt) -> "DataType":
        return {
            jnp.dtype("bool"): DataType.BOOL,
            jnp.dtype("int32"): DataType.INT32,
            jnp.dtype("int64"): DataType.INT64,
            jnp.dtype("float16"): DataType.HALF,
            jnp.dtype("bfloat16"): DataType.BFLOAT16,
            jnp.dtype("float32"): DataType.FLOAT,
            jnp.dtype("float64"): DataType.DOUBLE,
        }[jnp.dtype(dt)]

    @property
    def size_bytes(self) -> int:
        return {
            DataType.BOOL: 1,
            DataType.INT32: 4,
            DataType.INT64: 8,
            DataType.HALF: 2,
            DataType.BFLOAT16: 2,
            DataType.FLOAT: 4,
            DataType.DOUBLE: 8,
        }[self]


class OperatorType(enum.Enum):
    """Operator vocabulary (reference: ffconst.h:62-154 OperatorType).

    Grouped as: graph sources, compute ops, MoE ops, parallel (layout) ops.
    """

    # Graph source / structural
    NOOP = enum.auto()
    INPUT = enum.auto()
    WEIGHT = enum.auto()

    # Dense / conv family
    LINEAR = enum.auto()
    CONV2D = enum.auto()
    POOL2D_MAX = enum.auto()
    POOL2D_AVG = enum.auto()
    BATCHNORM = enum.auto()
    LAYERNORM = enum.auto()
    EMBEDDING = enum.auto()
    DROPOUT = enum.auto()

    # Attention
    MULTIHEAD_ATTENTION = enum.auto()

    # Element-wise unary (reference folds these into OP_RELU..OP_RSQRT etc.)
    RELU = enum.auto()
    SIGMOID = enum.auto()
    TANH = enum.auto()
    ELU = enum.auto()
    GELU = enum.auto()
    IDENTITY = enum.auto()
    EXP = enum.auto()
    SIN = enum.auto()
    COS = enum.auto()
    POW = enum.auto()
    RSQRT = enum.auto()
    SCALAR_MULTIPLY = enum.auto()
    SCALAR_ADD = enum.auto()
    SCALAR_SUB = enum.auto()
    SCALAR_TRUE_DIV = enum.auto()

    # Element-wise binary
    EW_ADD = enum.auto()
    EW_SUB = enum.auto()
    EW_MUL = enum.auto()
    EW_DIV = enum.auto()
    EW_MAX = enum.auto()
    EW_MIN = enum.auto()

    # Matmul / reductions
    BATCHMATMUL = enum.auto()
    REDUCE_SUM = enum.auto()
    MEAN = enum.auto()

    # Shape / layout compute ops
    SOFTMAX = enum.auto()
    CONCAT = enum.auto()
    SPLIT = enum.auto()
    RESHAPE = enum.auto()
    TRANSPOSE = enum.auto()
    REVERSE = enum.auto()
    FLAT = enum.auto()
    CAST = enum.auto()

    # MoE family (reference: group_by/aggregate/topk/cache, SURVEY §2.2)
    TOPK = enum.auto()
    GROUP_BY = enum.auto()
    AGGREGATE = enum.auto()
    AGGREGATE_SPEC = enum.auto()
    # TPU-native addition (no reference counterpart): batched expert FFN
    # whose leading expert dim shards over the mesh — GShard-style expert
    # parallelism (the reference's EP is per-expert op placement instead)
    EXPERT_FFN = enum.auto()
    CACHE = enum.auto()
    GATHER = enum.auto()

    # Fused
    FUSED = enum.auto()

    # Parallel ops (layout-only; reference: src/parallel_ops/, SURVEY §2.3)
    REPARTITION = enum.auto()
    COMBINE = enum.auto()
    REPLICATE = enum.auto()
    REDUCTION = enum.auto()
    FUSED_PARALLEL = enum.auto()
    PIPELINE = enum.auto()
    ALLTOALL = enum.auto()  # TPU-native addition: sequence/expert all-to-all

    # Appended, so that every value above stays what it was. The blocks of
    # the decoder LMs people deploy (no reference counterpart): RMSNorm,
    # and a dropless top-k expert layer as ONE operator (router, sort by
    # expert, grouped gated MLP, weighted sum — ops/moe.py)
    RMSNORM = enum.auto()
    SPARSE_MOE = enum.auto()
    # latent attention (MLA: keys and values decompressed from one cached
    # latent row a token, ops/attention.py) and the SiLU-gated MLP as one
    # operator (ops/core_ops.py)
    LATENT_ATTENTION = enum.auto()
    GATED_MLP = enum.auto()
    # gated delta-rule linear attention (Kimi Delta Attention: a fixed-size
    # state a sequence instead of rows a token, ops/linear_attention.py)
    LINEAR_ATTENTION = enum.auto()


PARALLEL_OP_TYPES = frozenset(
    {
        OperatorType.REPARTITION,
        OperatorType.COMBINE,
        OperatorType.REPLICATE,
        OperatorType.REDUCTION,
        OperatorType.FUSED_PARALLEL,
        OperatorType.PIPELINE,
        OperatorType.ALLTOALL,
    }
)


class ActiMode(enum.Enum):
    """Fused-activation modes (reference: ffconst.h ActiMode)."""

    NONE = enum.auto()
    RELU = enum.auto()
    SIGMOID = enum.auto()
    TANH = enum.auto()
    GELU = enum.auto()


class AggrMode(enum.Enum):
    """Embedding aggregation (reference: ffconst.h AggrMode)."""

    NONE = enum.auto()
    SUM = enum.auto()
    AVG = enum.auto()


class PoolType(enum.Enum):
    MAX = enum.auto()
    AVG = enum.auto()


class LossType(enum.Enum):
    """reference: ffconst.h LossType"""

    CATEGORICAL_CROSSENTROPY = enum.auto()
    SPARSE_CATEGORICAL_CROSSENTROPY = enum.auto()
    MEAN_SQUARED_ERROR_AVG_REDUCE = enum.auto()
    MEAN_SQUARED_ERROR_SUM_REDUCE = enum.auto()
    IDENTITY = enum.auto()


class MetricsType(enum.Enum):
    """reference: metrics_functions.h:12-45"""

    ACCURACY = enum.auto()
    CATEGORICAL_CROSSENTROPY = enum.auto()
    SPARSE_CATEGORICAL_CROSSENTROPY = enum.auto()
    MEAN_SQUARED_ERROR = enum.auto()
    ROOT_MEAN_SQUARED_ERROR = enum.auto()
    MEAN_ABSOLUTE_ERROR = enum.auto()


class ParameterSyncType(enum.Enum):
    """Gradient sync mode (reference: ffconst.h ParameterSyncType {NONE,PS,NCCL}).

    On TPU both map to XLA collectives; we keep the enum for API parity.
    PS → host-side aggregation (debug path), ALLREDUCE → psum over mesh.
    """

    NONE = enum.auto()
    PS = enum.auto()
    ALLREDUCE = enum.auto()  # reference's NCCL mode


class CompMode(enum.Enum):
    """reference: ffconst.h CompMode {COMP_MODE_TRAINING, COMP_MODE_INFERENCE}"""

    TRAINING = enum.auto()
    INFERENCE = enum.auto()
