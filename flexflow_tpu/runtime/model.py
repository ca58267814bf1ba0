"""FFModel: the layer-builder + compile/fit API.

Re-design of the reference's FFModel (reference: include/flexflow/model.h:321,
builder methods model.h:331-532; Python mirror python/flexflow/core/
flexflow_cffi.py:815). The builder records PCG nodes; `compile()` picks a
parallelization strategy (data-parallel default, reference:
graph.cc:1588-1613; or the Unity-style search when a budget is given),
propagates parallel shapes, and lowers to a jitted XLA train step through
`runtime.executor.Executor`.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.parallel_tensor import ParallelDim, ParallelTensorShape
from flexflow_tpu.core.pcg import PCGGraph, PCGNode, TensorRef
from flexflow_tpu.core.types import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType,
)
from flexflow_tpu.ops.registry import _ensure_registered, infer_shapes
from flexflow_tpu.runtime.dataloader import SingleDataLoader
from flexflow_tpu.runtime.initializer import (
    ConstantInitializer,
    UniformInitializer,
    ZeroInitializer,
)
from flexflow_tpu.runtime.executor import Executor, MeshConfig, propagate_shapes
from flexflow_tpu.runtime.metrics import PerfMetrics
from flexflow_tpu.runtime.optimizer import Optimizer, SGDOptimizer
from flexflow_tpu.telemetry.trace import span


class Tensor:
    """Handle to one PCG tensor (reference: TensorBase, tensor.h:30-80)."""

    def __init__(self, model: "FFModel", ref: TensorRef):
        self.model = model
        self.ref = ref

    @property
    def shape(self) -> ParallelTensorShape:
        return self.model.graph.shape_of(self.ref)

    @property
    def dims(self):
        return self.shape.logical_sizes

    @property
    def dtype(self) -> DataType:
        return self.shape.dtype

    def __repr__(self):
        return f"Tensor(guid={self.ref.guid}, {self.shape})"


class TensorDataLoader:
    """Handle returned by FFModel.create_data_loader (reference:
    SingleDataLoader, flexflow_cffi.py:2281 — the full dataset bound to
    one tensor; fit() consumes these per-tensor handles)."""

    def __init__(self, name: str, array):
        self.name = name
        self.array = np.asarray(array)
        self.num_samples = int(self.array.shape[0])

    def __repr__(self):
        return (
            f"TensorDataLoader({self.name!r}, {self.array.shape}, "
            f"{self.array.dtype})"
        )


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        _ensure_registered()
        self.config = config or FFConfig()
        self.graph = PCGGraph()
        self._name_counts: Dict[str, int] = {}
        self._input_order: List[str] = []
        self.executor: Optional[Executor] = None
        self.params = None
        self.opt_state = None
        # host-side cache-op memoization (reference: src/ops/cache.cc)
        self._cache_specs: Dict[str, tuple] = {}
        self._cache_state: Dict[str, list] = {}
        self._cache_scores: Dict[str, float] = {}
        self.optimizer: Optional[Optimizer] = None
        self.loss_type: Optional[LossType] = None
        self.metric_types: Sequence[MetricsType] = ()
        self.label_dtype = DataType.INT32
        self._rng = jax.random.PRNGKey(self.config.seed)
        self._logits: Optional[Tensor] = None
        self.strategy = None  # filled by compile()
        self.search_trace = None  # filled by search_strategy (--search-trace)
        # recompile_on_condition fires (runtime/recompile.py) — mirrored
        # into the train_recompiles_total telemetry counter by fit()
        self.recompile_events = 0

    # ------------------------------------------------------------------ util

    def _unique_name(self, base: str, name: Optional[str]) -> str:
        if name:
            return name
        n = self._name_counts.get(base, 0)
        self._name_counts[base] = n + 1
        return f"{base}_{n}" if n else base

    def _add(
        self, op_type, name_base, inputs, params, name=None, weights_of=None
    ) -> List[Tensor]:
        """weights_of: the output of an earlier node of the same kind
        whose weights this node applies instead of owning any (a layer
        run again: one stored array, one gradient that sums over the
        applications, one optimizer slot). Kept in the node's params under
        the owner's stable identity, which graph rewrites carry."""
        name = self._unique_name(name_base, name)
        in_shapes = [self.graph.shape_of(t.ref) for t in inputs]
        outs, weights = infer_shapes(op_type, in_shapes, params)
        if weights_of is not None:
            owner = self.graph.nodes[weights_of.ref.guid]
            if owner.op_type != op_type or owner.weight_shapes != tuple(weights):
                raise ValueError(
                    f"{name}: weights_of='{owner.name}' has weights "
                    f"{[str(s) for s in owner.weight_shapes]} of a "
                    f"{owner.op_type.name}, this {op_type.name} needs "
                    f"{[str(s) for s in weights]}"
                )
            params = dict(
                params,
                weights_of=owner.params.get("weights_of") or owner.weight_key,
            )
        node = self.graph.add_node(
            op_type,
            name,
            [t.ref for t in inputs],
            params,
            outs,
            weights,
        )
        return [Tensor(self, TensorRef(node.guid, i)) for i in range(len(outs))]

    # ----------------------------------------------------------- tensors

    def create_tensor(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.FLOAT,
        name: Optional[str] = None,
    ) -> Tensor:
        """reference: FFModel::create_tensor (model.h); dims in numpy order
        with dims[0] = batch."""
        name = self._unique_name("input", name)
        shape = ParallelTensorShape.make(tuple(dims), dtype)
        node = self.graph.add_node(
            OperatorType.INPUT, name, [], {"shape": shape}, [shape]
        )
        self._input_order.append(name)
        return Tensor(self, TensorRef(node.guid, 0))

    # ----------------------------------------------------------- layers
    # Each method mirrors one reference builder (model.h:331-532).

    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: ActiMode = ActiMode.NONE,
        use_bias: bool = True,
        kernel_initializer=None,
        bias_initializer=None,
        name: Optional[str] = None,
        weights_of: Optional[Tensor] = None,
    ) -> Tensor:
        params = {
            "out_features": out_dim,
            "activation": activation,
            "use_bias": use_bias,
            "initializers": [kernel_initializer, bias_initializer]
            if use_bias
            else [kernel_initializer],
        }
        return self._add(
            OperatorType.LINEAR, "dense", [input], params, name, weights_of
        )[0]

    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int = 1,
        stride_w: int = 1,
        padding_h: int = 0,
        padding_w: int = 0,
        activation: ActiMode = ActiMode.NONE,
        groups: int = 1,
        use_bias: bool = True,
        kernel_initializer=None,
        bias_initializer=None,
        name: Optional[str] = None,
    ) -> Tensor:
        params = {
            "out_channels": out_channels,
            "kernel_h": kernel_h,
            "kernel_w": kernel_w,
            "stride_h": stride_h,
            "stride_w": stride_w,
            "padding_h": padding_h,
            "padding_w": padding_w,
            "activation": activation,
            "groups": groups,
            "use_bias": use_bias,
            "initializers": [kernel_initializer, bias_initializer]
            if use_bias
            else [kernel_initializer],
        }
        return self._add(OperatorType.CONV2D, "conv2d", [input], params, name)[0]

    def pool2d(
        self,
        input: Tensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int = 1,
        stride_w: int = 1,
        padding_h: int = 0,
        padding_w: int = 0,
        pool_type: str = "max",
        activation: ActiMode = ActiMode.NONE,
        count_include_pad: bool = True,
        name: Optional[str] = None,
    ) -> Tensor:
        """count_include_pad: avg-pool divisor semantics — True divides by
        the full kernel area (torch AvgPool2d default), False by the
        in-bounds window count (keras/TF 'same', ONNX default)."""
        params = {
            "kernel_h": kernel_h,
            "kernel_w": kernel_w,
            "stride_h": stride_h,
            "stride_w": stride_w,
            "padding_h": padding_h,
            "padding_w": padding_w,
            "activation": activation,
            "count_include_pad": count_include_pad,
        }
        op = (
            OperatorType.POOL2D_MAX
            if str(pool_type).lower() in ("max", "pool_max")
            else OperatorType.POOL2D_AVG
        )
        return self._add(op, "pool2d", [input], params, name)[0]

    def batch_norm(
        self, input: Tensor, relu: bool = True, name: Optional[str] = None
    ) -> Tensor:
        params = {
            "activation": ActiMode.RELU if relu else ActiMode.NONE,
            # gamma = ones, beta = zeros (reference batch_norm defaults)
            "initializers": [ConstantInitializer(1.0), None],
        }
        return self._add(OperatorType.BATCHNORM, "batch_norm", [input], params, name)[0]

    def layer_norm(
        self,
        input: Tensor,
        axes: Optional[Sequence[int]] = None,
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: Optional[str] = None,
    ) -> Tensor:
        ndim = len(input.dims)
        axes = tuple(a % ndim for a in (axes or (ndim - 1,)))
        params = {
            "axes": axes,
            "elementwise_affine": elementwise_affine,
            "eps": eps,
            "initializers": [ConstantInitializer(1.0), None]
            if elementwise_affine
            else None,
        }
        return self._add(OperatorType.LAYERNORM, "layer_norm", [input], params, name)[0]

    def rms_norm(
        self, input: Tensor, eps: float = 1e-5, name: Optional[str] = None,
        weights_of: Optional[Tensor] = None,
    ) -> Tensor:
        """x * rsqrt(mean(x^2) + eps) * gain over the last dim, float32
        statistics; the gain starts at one."""
        params = {"eps": eps, "initializers": [ConstantInitializer(1.0)]}
        return self._add(
            OperatorType.RMSNORM, "rms_norm", [input], params, name, weights_of
        )[0]

    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.NONE,
        dtype: DataType = DataType.FLOAT,
        kernel_initializer=None,
        name: Optional[str] = None,
    ) -> Tensor:
        params = {
            "num_entries": num_entries,
            "out_dim": out_dim,
            "aggr": aggr,
            "dtype": dtype,
            "initializers": [kernel_initializer],
        }
        return self._add(OperatorType.EMBEDDING, "embedding", [input], params, name)[0]

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = True,
        causal: bool = False,
        seq_parallel: str = "auto",
        rope_theta: Optional[float] = None,
        qk_norm: bool = False,
        qk_norm_eps: float = 1e-5,
        name: Optional[str] = None,
        weights_of: Optional[Tensor] = None,
    ) -> Tensor:
        """rope_theta: rotary positions (rotate-half form over each head)
        on q and k; qk_norm: an RMSNorm with a learned gain over the whole
        q and the whole k projection, before the rotation. Both follow the
        projection and precede any cache write (ops/attention.py
        mha_qk_positions); neither adds anything at its default."""
        params = {
            "embed_dim": embed_dim,
            "num_heads": num_heads,
            "kdim": kdim or embed_dim,
            "vdim": vdim or embed_dim,
            "dropout": dropout,
            "bias": bias,
            "causal": causal,
            "seq_parallel": seq_parallel,
            # 4 projection kernels (Glorot default) + optional 4 zero biases
            "initializers": [None] * 4
            + ([ZeroInitializer()] * 4 if bias else []),
        }
        if rope_theta is not None:
            params["rope_theta"] = float(rope_theta)
        if qk_norm:
            params["qk_norm"] = True
            params["qk_norm_eps"] = qk_norm_eps
            params["initializers"] = params["initializers"] + [
                ConstantInitializer(1.0)
            ] * 2
        return self._add(
            OperatorType.MULTIHEAD_ATTENTION,
            "multihead_attention",
            [query, key, value],
            params,
            name,
            weights_of,
        )[0]

    def latent_attention(
        self,
        input: Tensor,
        hidden: int,
        num_heads: int,
        kv_lora_rank: int,
        qk_nope_head_dim: int,
        qk_rope_head_dim: int,
        v_head_dim: int,
        rope_theta: Optional[float] = 10000.0,
        eps: float = 1e-6,
        name: Optional[str] = None,
    ) -> Tensor:
        """Causal latent self-attention (MLA): every head's keys and
        values are decompressed from one latent row a token, [c | kr] of
        kv_lora_rank + qk_rope_head_dim, which is all a serving cache
        keeps (ops/attention.py, "Latent attention"). Rotary positions in
        the interleaved form over the rope part of q and over kr, or with
        `rope_theta=None` no positional encoding at all (neither is
        rotated); an RMSNorm with a learned gain over c. No biases."""
        params = {
            "embed_dim": hidden,
            "num_heads": num_heads,
            "kv_lora_rank": kv_lora_rank,
            "qk_nope_head_dim": qk_nope_head_dim,
            "qk_rope_head_dim": qk_rope_head_dim,
            "v_head_dim": v_head_dim,
            "rope_theta": None if rope_theta is None else float(rope_theta),
            "eps": eps,
            "causal": True,
            "initializers": [None, None, ConstantInitializer(1.0), None, None],
        }
        return self._add(
            OperatorType.LATENT_ATTENTION, "latent_attention", [input],
            params, name,
        )[0]

    def linear_attention(
        self,
        input: Tensor,
        hidden: int,
        num_heads: int,
        head_dim: int,
        conv_kernel: int = 4,
        eps: float = 1e-5,
        chunk: int = 64,
        name: Optional[str] = None,
    ) -> Tensor:
        """Causal gated delta-rule linear attention (Kimi Delta Attention,
        ops/linear_attention.py): a sequence keeps a fixed-size state, per
        head a [head_dim, head_dim] matrix decayed per key channel and
        corrected by the delta rule, behind three short depthwise
        convolutions of `conv_kernel` taps; a gated RMSNorm over each
        head's output. The decay's and the output gate's low-rank pairs
        are `head_dim` wide inside; `chunk`: the tokens the lowering and
        a serving prefill take at a time. A_log and dt_bias are trained
        buffers, zero from here. No biases."""
        taps = (3.0 / conv_kernel) ** 0.5
        params = {
            "embed_dim": hidden,
            "num_heads": num_heads,
            "head_dim": head_dim,
            "conv_kernel": conv_kernel,
            "gate_rank": head_dim,
            "chunk": chunk,
            "eps": eps,
            "causal": True,
            # the convolutions keep their input's variance (Glorot over
            # [channels, taps] would shrink it a hundredfold)
            "initializers": [None] * 3
            + [UniformInitializer(-taps, taps)] * 3
            + [None] * 7 + [ConstantInitializer(1.0), None],
        }
        return self._add(
            OperatorType.LINEAR_ATTENTION, "linear_attention", [input],
            params, name,
        )[0]

    def gated_mlp(
        self, input: Tensor, width: int, name: Optional[str] = None,
        weights_of: Optional[Tensor] = None,
    ) -> Tensor:
        """down(silu(gate x) * (up x)) with gate / up [d, width] and down
        [width, d], no biases, as one operator."""
        params = {"width": width, "initializers": [None] * 3}
        return self._add(
            OperatorType.GATED_MLP, "gated_mlp", [input], params, name,
            weights_of,
        )[0]

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0, name=None):
        return self._add(
            OperatorType.DROPOUT, "dropout", [input], {"rate": rate, "seed": seed}, name
        )[0]

    # element-wise unary
    def _unary(self, op, base, input, params=None, name=None):
        return self._add(op, base, [input], params or {}, name)[0]

    def relu(self, x, name=None):
        return self._unary(OperatorType.RELU, "relu", x, None, name)

    def sigmoid(self, x, name=None):
        return self._unary(OperatorType.SIGMOID, "sigmoid", x, None, name)

    def tanh(self, x, name=None):
        return self._unary(OperatorType.TANH, "tanh", x, None, name)

    def elu(self, x, name=None):
        return self._unary(OperatorType.ELU, "elu", x, None, name)

    def gelu(self, x, name=None):
        return self._unary(OperatorType.GELU, "gelu", x, None, name)

    def identity(self, x, name=None):
        return self._unary(OperatorType.IDENTITY, "identity", x, None, name)

    def exp(self, x, name=None):
        return self._unary(OperatorType.EXP, "exp", x, None, name)

    def sin(self, x, name=None):
        return self._unary(OperatorType.SIN, "sin", x, None, name)

    def cos(self, x, name=None):
        return self._unary(OperatorType.COS, "cos", x, None, name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(
            OperatorType.POW, "pow", x, {"exponent": exponent}, name
        )

    def rsqrt(self, x, name=None):
        return self._unary(OperatorType.RSQRT, "rsqrt", x, None, name)

    def scalar_multiply(self, x, scalar: float, name=None):
        return self._unary(
            OperatorType.SCALAR_MULTIPLY, "scalar_multiply", x, {"scalar": scalar}, name
        )

    def scalar_add(self, x, scalar: float, name=None):
        return self._unary(
            OperatorType.SCALAR_ADD, "scalar_add", x, {"scalar": scalar}, name
        )

    def scalar_sub(self, x, scalar: float, name=None):
        return self._unary(
            OperatorType.SCALAR_SUB, "scalar_sub", x, {"scalar": scalar}, name
        )

    def scalar_true_divide(self, x, scalar: float, name=None):
        return self._unary(
            OperatorType.SCALAR_TRUE_DIV, "scalar_true_div", x, {"scalar": scalar}, name
        )

    # element-wise binary
    def _binary(self, op, base, a, b, name=None):
        return self._add(op, base, [a, b], {}, name)[0]

    def add(self, a, b, name=None):
        return self._binary(OperatorType.EW_ADD, "add", a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary(OperatorType.EW_SUB, "subtract", a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary(OperatorType.EW_MUL, "multiply", a, b, name)

    def divide(self, a, b, name=None):
        return self._binary(OperatorType.EW_DIV, "divide", a, b, name)

    def max(self, a, b, name=None):
        return self._binary(OperatorType.EW_MAX, "max", a, b, name)

    def min(self, a, b, name=None):
        return self._binary(OperatorType.EW_MIN, "min", a, b, name)

    def batch_matmul(
        self, a: Tensor, b: Tensor, a_seq_length_dim=-1, b_seq_length_dim=-1, name=None
    ):
        params = {
            "a_seq_length_dim": a_seq_length_dim,
            "b_seq_length_dim": b_seq_length_dim,
        }
        return self._add(OperatorType.BATCHMATMUL, "batch_matmul", [a, b], params, name)[0]

    def softmax(self, input: Tensor, dim: int = -1, name=None):
        return self._add(OperatorType.SOFTMAX, "softmax", [input], {"dim": dim}, name)[0]

    def concat(self, tensors: Sequence[Tensor], axis: int, name=None):
        return self._add(OperatorType.CONCAT, "concat", list(tensors), {"axis": axis}, name)[0]

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]], axis: int, name=None):
        if isinstance(sizes, int):
            total = input.dims[axis]
            sizes = [total // sizes] * sizes
        outs = self._add(
            OperatorType.SPLIT, "split", [input], {"axis": axis, "sizes": tuple(sizes)}, name
        )
        return outs

    def reshape(self, input: Tensor, shape: Sequence[int], name=None):
        return self._add(
            OperatorType.RESHAPE, "reshape", [input], {"shape": tuple(shape)}, name
        )[0]

    def transpose(self, input: Tensor, perm: Sequence[int], name=None):
        return self._add(
            OperatorType.TRANSPOSE, "transpose", [input], {"perm": tuple(perm)}, name
        )[0]

    def reverse(self, input: Tensor, axis: int, name=None):
        return self._add(OperatorType.REVERSE, "reverse", [input], {"axis": axis}, name)[0]

    def flat(self, input: Tensor, name=None):
        return self._add(OperatorType.FLAT, "flat", [input], {}, name)[0]

    def cast(self, input: Tensor, dtype: DataType, name=None):
        return self._add(OperatorType.CAST, "cast", [input], {"dtype": dtype}, name)[0]

    def reduce_sum(self, input: Tensor, axes: Sequence[int], keepdims=False, name=None):
        return self._add(
            OperatorType.REDUCE_SUM,
            "reduce_sum",
            [input],
            {"axes": tuple(axes), "keepdims": keepdims},
            name,
        )[0]

    def mean(self, input: Tensor, axes: Sequence[int], keepdims=False, name=None):
        return self._add(
            OperatorType.MEAN, "mean", [input], {"axes": tuple(axes), "keepdims": keepdims}, name
        )[0]

    # parallel ops (reference: FFModel::create_combine/repartition/replicate/
    # reduction builder surface; src/parallel_ops/)
    def repartition(self, input: Tensor, axis: int, degree: int, parallel_idx: int = -1, name=None):
        return self._add(
            OperatorType.REPARTITION,
            "repartition",
            [input],
            {"axis": axis, "degree": degree, "parallel_idx": parallel_idx},
            name,
        )[0]

    def combine(self, input: Tensor, axis: int, degree: int, name=None):
        return self._add(
            OperatorType.COMBINE, "combine", [input], {"axis": axis, "degree": degree}, name
        )[0]

    def replicate(self, input: Tensor, degree: int, parallel_idx: int = -1, name=None):
        return self._add(
            OperatorType.REPLICATE,
            "replicate",
            [input],
            {"degree": degree, "parallel_idx": parallel_idx},
            name,
        )[0]

    def reduction(self, input: Tensor, degree: int, name=None):
        return self._add(
            OperatorType.REDUCTION, "reduction", [input], {"degree": degree}, name
        )[0]

    def pipeline(
        self,
        input: Tensor,
        num_stages: int,
        num_microbatches: int = 4,
        name=None,
    ):
        """Stage-boundary MARKER, pass-through in the PCG executor (the
        reference declares OP_PIPELINE but never implements it either,
        ffconst.h:151). Pipelined execution lives in
        flexflow_tpu.parallel.pipeline.pipeline_apply; compile() warns when
        markers are present so the inert path is never silent."""
        return self._add(
            OperatorType.PIPELINE,
            "pipeline",
            [input],
            {"num_stages": num_stages, "num_microbatches": num_microbatches},
            name,
        )[0]

    def all_to_all(self, input: Tensor, src_axis: int, dst_axis: int, name=None):
        return self._add(
            OperatorType.ALLTOALL,
            "all_to_all",
            [input],
            {"src_axis": src_axis, "dst_axis": dst_axis},
            name,
        )[0]

    # MoE family (reference: model.h:417-439, 487-492)
    def top_k(self, input: Tensor, k: int, sorted: bool = True, name=None):
        return self._add(
            OperatorType.TOPK, "topk", [input], {"k": k, "sorted": sorted}, name
        )

    def group_by(
        self,
        data: Tensor,
        assign: Tensor,
        n: int,
        alpha: float = 1.0,
        stacked: bool = False,
        name=None,
    ):
        out = self._add(
            OperatorType.GROUP_BY,
            "group_by",
            [data, assign],
            {"n": n, "alpha": alpha, "stacked": stacked},
            name,
        )
        return out[0] if stacked else out

    def expert_ffn(self, stacked: Tensor, hidden: int, name=None):
        """Batched per-expert 2-layer MLP on a stacked [n, cap, d] tensor;
        the expert dim shards over the mesh (GShard-style EP — TPU-native,
        no reference counterpart: its experts are separate Linear ops)."""
        return self._add(
            OperatorType.EXPERT_FFN,
            "expert_ffn",
            [stacked],
            {"hidden": hidden},
            name,
        )[0]

    def sparse_moe(
        self,
        input: Tensor,
        num_experts: int,
        k: int,
        expert_hidden: int,
        renormalise: bool = False,
        scoring: str = "softmax",
        choice_bias: bool = False,
        scale: float = 1.0,
        experts_held: Optional[Tuple[int, int]] = None,
        name=None,
    ) -> Tensor:
        """A dropless top-k expert layer as one operator: router [d, E]
        (float32 softmax over all E, top-k, the k weights renormalised to
        sum to one only if asked), then for each token the weighted sum of
        its k experts' gated MLPs down(silu(gate x) * up x), from stacked
        gate / up [E, d, f] and down [E, f, d]. No capacity: every one of
        the tokens x k rows is computed whatever the load (ops/moe.py
        sparse_moe). The expert dim shards under a replicated input, as
        attention's heads do.

        scoring "sigmoid": each expert's score is the sigmoid of its own
        logit. choice_bias: a fifth weight [E] added to the scores for the
        top-k CHOICE only, never to the weights. scale multiplies the
        (renormalised) weights. experts_held (first, count): this layer
        holds experts first .. first + count - 1 of the E (the stacked
        weights are [count, ...]); it routes over all E, computes the rows
        sent to its own experts and leaves the others out: one chip's
        share of a layer whose experts are divided over chips."""
        limit = (6.0 / (input.dims[-1] + expert_hidden)) ** 0.5
        params = {
            "num_experts": num_experts,
            "k": k,
            "expert_hidden": expert_hidden,
            "renormalise": renormalise,
            # Glorot of ONE expert's matrix: the default would count the
            # stacked expert dim into the fan-in and start every expert
            # at a sixty-fourth of its scale
            "initializers": [None]
            + [UniformInitializer(-limit, limit)] * 3,
        }
        if scoring != "softmax":
            params["scoring"] = scoring
        if choice_bias:
            # a trained buffer: zero until a checkpoint (or a test) sets it
            params["choice_bias"] = True
            params["initializers"] = params["initializers"] + [
                ConstantInitializer(0.0)
            ]
        if scale != 1.0:
            params["scale"] = float(scale)
        if experts_held is not None:
            params["experts_held"] = (int(experts_held[0]), int(experts_held[1]))
        return self._add(
            OperatorType.SPARSE_MOE, "sparse_moe", [input], params, name
        )[0]

    def aggregate(
        self,
        gate_values: Tensor,
        gate_assign: Tensor,
        exp_preds,
        n: int,
        lambda_bal: float = 0.0,
        name=None,
    ):
        stacked = isinstance(exp_preds, Tensor)
        preds = [exp_preds] if stacked else list(exp_preds)
        return self._add(
            OperatorType.AGGREGATE,
            "aggregate",
            [gate_values, gate_assign] + preds,
            {"n": n, "lambda_bal": lambda_bal, "stacked": stacked},
            name,
        )[0]

    def aggregate_spec(
        self,
        gate_values: Tensor,
        gate_assign: Tensor,
        exp_preds,
        n: int,
        lambda_bal: float = 0.0,
        name=None,
    ):
        """Speculative aggregate: expert outputs combine like aggregate()
        but the gate network receives no gradient (reference:
        src/ops/aggregate_spec.cc)."""
        stacked = isinstance(exp_preds, Tensor)
        preds = [exp_preds] if stacked else list(exp_preds)
        return self._add(
            OperatorType.AGGREGATE_SPEC,
            "aggregate_spec",
            [gate_values, gate_assign] + preds,
            {"n": n, "lambda_bal": lambda_bal, "stacked": stacked},
            name,
        )[0]

    def cache(
        self,
        input: Tensor,
        num_batches: int = 1,
        score_f=None,
        name=None,
    ) -> Tensor:
        """Activation memoization (reference: FFModel::cache, src/ops/
        cache.cc): keeps the last `num_batches` values of `input` on the
        host and scores fresh-vs-cached drift with `score_f(cached_list,
        fresh) -> float` each training step. Read the rolling score with
        `cache_score(name)` — the moe.cc:65-99 pattern feeds it to
        recompile_on_condition to trigger expert re-sharding."""
        out = self._add(
            OperatorType.CACHE,
            "cache",
            [input],
            {"num_batches": int(num_batches)},
            name,
        )[0]
        node = self.graph.nodes[out.ref.guid]
        if score_f is None:
            from flexflow_tpu.ops.moe import default_cache_score

            score_f = default_cache_score
        self._cache_specs[node.name] = (int(num_batches), score_f)
        return out

    def cache_score(self, name: str) -> float:
        """Latest drift score of a cache op (1.0 until enough batches)."""
        return self._cache_scores.get(name, 1.0)

    def _update_cache(self, name: str, fresh) -> None:
        spec = self._cache_specs.get(name)
        if spec is None:
            return
        num_batches, score_f = spec
        state = self._cache_state.setdefault(name, [])
        if len(state) >= num_batches:
            self._cache_scores[name] = float(score_f(list(state), fresh))
        state.append(fresh)
        del state[: max(0, len(state) - num_batches)]

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
        batched: bool = False,
    ) -> Tensor:
        """MoE sugar (reference: FFModel::moe, model.h:487-492): gate network
        → topk → group_by → experts → aggregate. batched=True uses ONE
        stacked ExpertFFN whose expert dim can shard over the mesh
        (expert parallelism); False mirrors the reference's per-expert
        Linear ops."""
        gate = self.dense(input, num_exp, name=None)
        gate = self.softmax(gate)
        values, assign = self.top_k(gate, num_select)
        if batched:
            stacked = self.group_by(input, assign, num_exp, alpha, stacked=True)
            preds = self.expert_ffn(stacked, expert_hidden_size)
            return self.aggregate(values, assign, preds, num_exp, lambda_bal)
        grouped = self.group_by(input, assign, num_exp, alpha)
        exp_preds = [
            self.dense(
                self.dense(g, expert_hidden_size, activation=ActiMode.RELU),
                expert_hidden_size,
            )
            for g in grouped
        ]
        return self.aggregate(values, assign, exp_preds, num_exp, lambda_bal)

    # ------------------------------------------------------------- compile

    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: LossType = LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics: Sequence[MetricsType] = (MetricsType.ACCURACY,),
        comp_mode: CompMode = CompMode.TRAINING,
        logits: Optional[Tensor] = None,
        devices=None,
        strategy=None,
    ):
        """Pick a strategy, propagate parallel shapes, build the executor
        (reference: FFModel::compile, model.cc:2789-3154; SURVEY §3.2).

        strategy: an explicit parallel.strategy.Strategy to use instead of
        the config-driven choice (the reference's --import-strategy path).
        """
        from flexflow_tpu.core.machine import detect_chip
        from flexflow_tpu.parallel.strategy import choose_strategy
        from flexflow_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
        # before the search prices anything: an unlisted TPU raises here
        self.config.chip = self.config.chip or detect_chip()
        if any(
            n.op_type == OperatorType.PIPELINE for n in self.graph.nodes.values()
        ):
            import warnings

            warnings.warn(
                "PIPELINE markers are pass-through in the PCG executor; for "
                "pipelined execution use flexflow_tpu.parallel.pipeline."
                "pipeline_apply (GPipe over a 'pipe' mesh axis).",
                stacklevel=2,
            )
        # a pre-assigned `ffmodel.optimizer = ...` survives a compile()
        # without an optimizer argument (reference native-python idiom,
        # flexflow_cffi.py — examples/python/pytorch/mnist_mlp.py sets the
        # attribute then calls compile(loss_type=..., metrics=...))
        self.optimizer = optimizer or self.optimizer or SGDOptimizer(
            lr=self.config.learning_rate,
            weight_decay=self.config.weight_decay,
        )
        self.loss_type = loss_type
        self.metric_types = tuple(metrics)

        # measured flash-kernel tile sizes from the calibration table
        # (scripts/calibrate.py --tune-flash) replace the built-in
        # defaults for every attention lowering this compile produces
        if self.config.calibration_file:
            import json as _json
            import os as _os

            if _os.path.exists(self.config.calibration_file):
                try:
                    with open(self.config.calibration_file) as f:
                        _doc = _json.load(f)
                except (OSError, ValueError):
                    _doc = {}
                fb = _doc.get("flash_blocks") or {}
                if fb.get("block_q") and fb.get("block_k"):
                    from flexflow_tpu.ops.pallas.flash_kernel import (
                        set_tuned_blocks,
                    )

                    set_tuned_blocks(fb["block_q"], fb["block_k"])
                db = _doc.get("decode_blocks") or {}
                if db.get("block_k"):
                    from flexflow_tpu.ops.pallas.decode_kernel import (
                        set_tuned_decode_blocks,
                    )

                    set_tuned_decode_blocks(db["block_k"])
                caps = _doc.get("attn_caps") or {}
                if caps.get("mono_mb") and caps.get("chunk_mb"):
                    from flexflow_tpu.ops.attention import set_dense_caps

                    set_dense_caps(caps["mono_mb"], caps["chunk_mb"])

        if logits is None:
            sinks = self.graph.sinks()
            if len(sinks) != 1:
                raise ValueError(
                    "model has multiple sinks; pass logits= to compile()"
                )
            logits = Tensor(self, TensorRef(sinks[0], 0))
        self._logits = logits

        devices = jax.devices() if devices is None else list(devices)
        # pristine builder graph + the caller's compile arguments, restored
        # by the recompile hook so a recompile keeps the user's explicit
        # strategy/devices (reference: RecompileState, recompile.h:26-41)
        self._prestrategy_graph = self.graph.copy()
        self._builder_logits_ref = logits.ref  # pre-substitution identity
        self._compile_devices = devices
        self._compile_strategy = strategy
        self.strategy = strategy or choose_strategy(self, len(devices))
        self.strategy.apply(self.graph)
        propagate_shapes(self.graph)

        # fold adjacent parallel-op chains into FusedParallelOp nodes
        # (reference: fused_parallel_op.cc; enabled with the fusion pass)
        if (
            self.config.perform_fusion
            and getattr(self.strategy, "pipeline", None) is None
        ):
            from flexflow_tpu.parallel.parallel_ops import fold_parallel_ops

            if fold_parallel_ops(self.graph):
                propagate_shapes(self.graph)

        # substitution optimization pass (reference: base_optimize inside
        # GraphSearchHelper::graph_optimize — a core compile phase; the
        # bundled default rules run unless --no-substitution, SURVEY §2.5).
        # A pipelined strategy pins the trunk's guids
        # (PipelineSpec.structure), so graph-rewriting passes are skipped —
        # rewritten guids would dangle in the block template.
        pipelined = getattr(self.strategy, "pipeline", None) is not None
        subst_requested = (
            self.config.enable_substitution
            or self.config.substitution_json
            or self.config.perform_fusion
        )
        if pipelined and (
            self.config.substitution_json or self.config.perform_fusion
        ):
            import warnings

            warnings.warn(
                "substitution/fusion passes are skipped under a pipelined "
                "strategy (the block template pins pre-rewrite node ids)",
                stacklevel=2,
            )
        if not pipelined and subst_requested:
            from flexflow_tpu.search.substitution import apply_substitution_pass

            self.graph, new_ref = apply_substitution_pass(
                self.graph, logits.ref, self.config, self.strategy.mesh_config
            )
            logits = Tensor(self, new_ref)
            self._logits = logits

        # FusedOp pass (reference: apply_fusion, model.cc:2489-2597): fold
        # fusible chains into FUSED nodes; the logits node stays unfused so
        # downstream references (loss, from_logits check) hold.
        if not pipelined and self.config.perform_fusion:
            from flexflow_tpu.runtime.fusion import apply_fusion

            self.graph, fref_map = apply_fusion(
                self.graph, protected={logits.ref.guid}
            )
            if logits.ref in fref_map:
                logits = Tensor(self, fref_map[logits.ref])
                self._logits = logits

        # label tensor matching the final op's batch partitioning
        # (reference: model.cc:3072-3110)
        logits_shape = self.graph.shape_of(logits.ref)
        batch_dims = [
            d for d in logits_shape.dims if not d.is_replica_dim
        ]
        if loss_type == LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            label_dims = tuple(batch_dims[:-1])
            label_dtype = DataType.INT32
        else:
            label_dims = tuple(batch_dims)
            label_dtype = DataType.FLOAT
        label_shape = ParallelTensorShape(label_dims, label_dtype)

        aux = []
        lam_nodes = [
            n
            for n in self.graph.nodes.values()
            if n.op_type
            in (OperatorType.AGGREGATE, OperatorType.AGGREGATE_SPEC)
            and n.params.get("lambda_bal", 0.0) > 0.0
        ]
        if lam_nodes:
            from flexflow_tpu.ops.moe import load_balance_loss

            def moe_aux(values, batch, _nodes=lam_nodes):
                # the balance loss needs the FULL gate distribution [b, n],
                # not the top-k values the aggregate consumes (reference
                # feeds gate_preds into aggregate for exactly this,
                # moe.cc); walk back through the TopK producer.
                total = 0.0
                for n in _nodes:
                    gate_ref, assign_ref = n.inputs[0], n.inputs[1]
                    src = self.graph.nodes[gate_ref.guid]
                    if src.op_type == OperatorType.TOPK:
                        full_ref = src.inputs[0]
                    else:
                        full_ref = gate_ref
                    gp = values[(full_ref.guid, full_ref.out_idx)]
                    asg = values[(assign_ref.guid, assign_ref.out_idx)]
                    total = total + n.params["lambda_bal"] * load_balance_loss(
                        gp, asg, n.params["n"]
                    )
                return total

            aux.append(moe_aux)

        logits_node = self.graph.nodes[logits.ref.guid]
        if logits_node.op_type == OperatorType.FUSED:
            from_logits = (
                logits_node.params["sub_ops"][-1]["op_type"]
                != OperatorType.SOFTMAX
            )
        else:
            from_logits = logits_node.op_type != OperatorType.SOFTMAX
        # strategy validation (analysis/strategy_check.py): re-derive
        # every constraint the lowering relies on — mesh axes exist,
        # degrees are expressible, machine bounds hold — and raise ONE
        # typed StrategyValidationError BEFORE any XLA work, instead of
        # an opaque ValueError from deep inside partition_spec during
        # executor construction. Pipelined strategies lower block
        # weights through their own stacked path, so their findings are
        # informational only.
        from flexflow_tpu.analysis.strategy_check import (
            StrategyValidationError,
            validate_graph_strategy,
        )

        self.strategy_diagnostics = validate_graph_strategy(
            self.graph,
            self.strategy.mesh_config,
            num_devices=len(devices),
        )
        if getattr(self.strategy, "pipeline", None) is None:
            _strategy_errors = [
                d for d in self.strategy_diagnostics if d.severity == "error"
            ]
            if _strategy_errors:
                raise StrategyValidationError(_strategy_errors)

        executor_cls = Executor
        executor_kwargs = {}
        if getattr(self.strategy, "pipeline", None) is not None:
            from flexflow_tpu.runtime.pipeline_executor import (
                PipelinedExecutor,
            )

            pspec = self.strategy.pipeline
            dp = dict(
                zip(
                    self.strategy.mesh_config.axis_names,
                    self.strategy.mesh_config.axis_sizes,
                )
            ).get("data", 1)
            pspec.validate(self.config.batch_size // max(1, dp))
            executor_cls = PipelinedExecutor
            executor_kwargs["pipeline_spec"] = pspec
        self.executor = executor_cls(
            self.graph,
            self.strategy.mesh_config,
            logits.ref,
            label_shape=label_shape,
            loss_type=loss_type,
            metrics=self.metric_types,
            optimizer=self.optimizer,
            devices=devices,
            aux_loss_fns=aux,
            logits_from_logits=from_logits,
            mixed_precision=self.config.allow_mixed_precision,
            seq_length=self.config.seq_length,
            # the GPipe executor has its own forward path; sparse table
            # updates ride the plain executor only
            sparse_embedding_update=(
                self.config.sparse_embedding_update
                and executor_cls is Executor
            ),
            **executor_kwargs,
        )
        self._rng, init_key = jax.random.split(self._rng)
        self.params = self.executor.init_params(init_key)
        self.opt_state = self.executor.commit_opt_state(
            self.optimizer.init_state(self.params)
        )

        if self.config.computation_graph_file or self.config.task_graph_file:
            # cost the artifacts with the SAME machine description the
            # search uses (--chip / --machine-model-*), not defaults
            from flexflow_tpu.core.machine import MachineSpec
            from flexflow_tpu.search.machine_model import build_machine_model

            spec = MachineSpec(
                num_nodes=max(1, self.config.num_nodes),
                chips_per_node=max(
                    1, len(devices) // max(1, self.config.num_nodes)
                ),
                chip=self.config.chip,
            )
            mm = build_machine_model(self.config, spec)
        if self.config.computation_graph_file:
            from flexflow_tpu.utils.dot import export_pcg_dot

            export_pcg_dot(
                self.graph,
                self.config.computation_graph_file,
                include_costs=self.config.include_costs_dot_graph,
                spec=spec,
                machine_model=mm,
            )
        if self.config.task_graph_file:
            from flexflow_tpu.utils.dot import export_task_graph_dot

            export_task_graph_dot(
                self.graph,
                self.config.task_graph_file,
                self.strategy.mesh_config.axis_sizes,
                spec=spec,
                machine_model=mm,
            )

    # ------------------------------------------------------------- training

    def fit(
        self,
        x: Union[Dict[str, np.ndarray], Sequence[np.ndarray], np.ndarray],
        y: np.ndarray,
        epochs: Optional[int] = None,
        batch_size: Optional[int] = None,
        shuffle: bool = False,
        verbose: bool = True,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        callbacks=None,
        telemetry=None,
    ):
        """Training loop (reference: flexflow_cffi.py:1916-1958 fit —
        per-iter begin_trace; next_batch; forward; zero_gradients; backward;
        update; end_trace. Here one jitted step does all of it). Callback
        hooks follow the reference keras loop (base_model.py:374-430):
        set_model, on_train_begin, per-epoch and per-batch hooks; a True
        return from on_epoch_end stops training early.

        telemetry: a flexflow_tpu.telemetry.Telemetry bundle, or None to
        build one from the config's --metrics-out/--metrics-jsonl/
        --trace knobs (the serving flags now drive training too). With
        the bundle attached, fit exports per-iteration train_* series
        (step time, examples/s, loss, recompiles, jit-cache builds) and
        a Chrome trace of iteration/epoch spans; the hot loop pays one
        predicate branch plus two appends per iteration — losses and
        rows are materialized at epoch end, AFTER the existing
        block_until_ready, so telemetry adds no device syncs."""
        if self.executor is None:
            raise RuntimeError("call compile() before fit()")
        epochs = epochs or self.config.epochs
        batch_size = batch_size or self.config.batch_size
        callbacks = list(callbacks or [])
        tele = telemetry
        if tele is None:
            from flexflow_tpu.telemetry import build_telemetry

            tele = build_telemetry(self.config)
        self._telemetry = tele
        # fit's phases: profiler annotations always, Chrome events too
        # when the bundle traces (telemetry/trace.py:span)
        tracer = getattr(tele, "tracer", None)
        train_iters = 0  # global iteration counter across epochs
        for cb in callbacks:
            # the keras frontend pre-binds its own Model wrapper; direct
            # FFModel.fit users get the FFModel itself
            if getattr(cb, "model", None) is None:
                cb.set_model(self)
        for cb in callbacks:
            cb.on_train_begin()

        arrays = self._pack_dataset(x, y)
        loader = SingleDataLoader(arrays, batch_size, shuffle=shuffle)
        step = self.executor.train_step()

        def lease_wait():
            return span("train.input.next_batch.lease_wait", tracer)

        def place_next():
            """The loader's next batch on the device, and its rows. The
            batch is views of the loader's slot, lent until the arrays
            placed from it are ready (dataloader.py)."""
            with span("train.input.next_batch", tracer):
                np_batch = loader.borrow_batch(lease_wait)
            with span("train.input.shard_batch", tracer):
                batch = loader.lend(
                    self.executor.shard_batch(np_batch, tracer)
                )
            return batch, len(next(iter(np_batch.values())))

        history = []
        warm = False
        early_stop = False
        placed = None  # an epoch's first batch, placed before its turn
        for epoch in range(epochs):
            for cb in callbacks:
                cb.on_epoch_begin(epoch)
            # a LearningRateScheduler rebinds the optimizer and drops the
            # cached jitted step; re-fetch so the new LR takes effect
            if callbacks:
                step = self.executor.train_step()
            perf = PerfMetrics()
            with span("train.epoch_end.reset", tracer):
                # a reset for the first epoch. After it the loader's ring
                # has gone on into the epoch by itself, beside the last
                # one's final steps: nothing rewinds, no lease ends
                rolls_on = loader.begin_epoch(follows=epoch + 1 < epochs)
            t0 = time.perf_counter()
            epoch_t0 = t0
            samples = 0
            step_results = []  # device arrays; converted once per epoch so
            # the loop stays async (no per-iteration host sync)
            stamps = []  # host clock at each dispatch (telemetry only)
            sample_counts = []
            for it in range(loader.num_batches):
                for cb in callbacks:
                    cb.on_batch_begin(it)
                batch, rows = placed or place_next()
                placed = None
                with span("train.input.dispatch", tracer):
                    self._rng, key = jax.random.split(self._rng)
                    self.params, self.opt_state, loss, mets = step(
                        self.params, self.opt_state, batch, key
                    )
                if tele is not None:
                    # dispatch-to-dispatch host stamps; rows/spans are
                    # built at epoch end, off the hot loop
                    stamps.append(time.perf_counter())
                    sample_counts.append(rows)
                if self._cache_specs:
                    # surface cache-op inputs to the host memoizer
                    # (syncs; only models that built cache() ops pay it)
                    mets = dict(mets)
                    for mname in [
                        k for k in mets if k.startswith("__cache_")
                    ]:
                        self._update_cache(
                            mname[len("__cache_"):],
                            np.asarray(mets.pop(mname)),
                        )
                if not warm:
                    # exclude compile time from throughput (the reference's
                    # timing also starts after warmup, alexnet.cc:125-135)
                    jax.block_until_ready(loss)
                    t0 = time.perf_counter()
                    warm = True
                else:
                    samples += rows
                # a step's results are final when its program ends: their
                # copies to the host start now, so that the epoch's end
                # reads host values and not a drained device, one by one
                for leaf in jax.tree_util.tree_leaves((loss, mets)):
                    leaf.copy_to_host_async()
                step_results.append((loss, mets))
                for cb in callbacks:
                    cb.on_batch_end(it)
                pf = self.config.print_freq
                if verbose and pf > 0 and (it + 1) % pf == 0:
                    # reference: metrics printed every printFreq iterations
                    # (model.cc printFreq); float() syncs, so only paid on
                    # the requested cadence
                    print(
                        f"iter {it + 1}/{loader.num_batches}: "
                        f"loss = {float(loss):.4f}"
                    )
            if rolls_on:
                # the next epoch's first batch goes to the device beside
                # this epoch's last steps (a transfer, not a step); should
                # a callback stop the run it is dropped, never stepped on
                placed = place_next()
            with span("train.epoch_end.drain", tracer):
                jax.block_until_ready(self.params)
            elapsed = time.perf_counter() - t0
            losses = []
            with span("train.epoch_end.losses", tracer):
                for loss, mets in step_results:
                    fl = float(loss)
                    perf.update(jax.tree_util.tree_map(float, mets), fl)
                    losses.append(fl)
            self._perf_metrics = perf
            thpt = samples / elapsed if elapsed > 0 else 0.0
            if tele is not None:
                train_iters = self._record_training_epoch(
                    tele, epoch, epoch_t0, stamps, sample_counts, losses,
                    train_iters, loader,
                )
            history.append({"epoch": epoch, "throughput": thpt, **perf.__dict__})
            if verbose:
                print(f"epoch {epoch}: {perf.report()}")
                print(f"THROUGHPUT = {thpt:.2f} samples/s")
            if checkpoint_dir and (epoch + 1) % max(1, checkpoint_every) == 0:
                self.save_checkpoint(checkpoint_dir, step=epoch)
            for cb in callbacks:
                if cb.on_epoch_end(epoch) is True:
                    # reference: base_model.py:423-428 — accuracy target
                    # reached, stop early
                    if verbose:
                        print(
                            "Accuracy reaches, now early stop, "
                            f"epoch: {epoch}"
                        )
                    early_stop = True
            if early_stop:
                break
        # every lease ended, a batch placed ahead among them, and the
        # loader's worker joined with whatever it gathered ahead
        loader.close()
        for cb in callbacks:
            cb.on_train_end()
        if tele is not None:
            tele.flush()
        return history

    def _record_training_epoch(
        self, tele, epoch, epoch_t0, stamps, sample_counts, losses,
        train_iters, loader,
    ) -> int:
        """Materialize one epoch's telemetry AFTER the epoch-end device
        sync: per-iteration train_* gauges + counters, one JSONL sample
        row per iteration, iteration/epoch spans on the trace, and the
        recompile/jit-cache mirrors. Returns the advanced global
        iteration counter. Registry handles are get-or-create dict
        lookups — cheap at epoch granularity."""
        reg = tele.registry
        g_loss = reg.gauge("train_loss", help="training loss (last step)")
        g_step = reg.gauge(
            "train_step_time_s",
            help="per-iteration wall time, host dispatch-to-dispatch",
        )
        g_eps = reg.gauge(
            "train_examples_per_s",
            help="instantaneous examples/s of the last iteration",
        )
        g_epoch = reg.gauge("train_epoch", help="current epoch index")
        c_iters = reg.counter(
            "train_iterations_total", help="training iterations run"
        )
        c_examples = reg.counter(
            "train_examples_total", help="training examples consumed"
        )
        c_recompiles = reg.counter(
            "train_recompiles_total",
            help="recompile_on_condition fires (model mutations)",
        )
        g_jit = reg.gauge(
            "train_jit_builds",
            help="step callables built by the executor "
            "(each first call is one XLA compile)",
        )
        g_inval = reg.gauge(
            "train_jit_invalidations",
            help="cached step callables dropped (seq-length change, "
            "LR rebind)",
        )
        # how the epoch's batches reached the device
        c_borrowed = reg.counter(
            "train_input_batches_borrowed",
            help="batches transferred straight out of the loader's slot",
        )
        c_copied = reg.counter(
            "train_input_batches_copied",
            help="batches copied on the host before the transfer "
            "(the backend would have kept the slot's memory)",
        )
        g_lease = reg.gauge(
            "train_input_lease_wait_ms",
            help="mean wait per batch for a lent slot's transfer, "
            "last epoch",
        )
        c_ahead = reg.counter(
            "train_input_batches_gathered_ahead",
            help="batches ready in the loader's ring before their epoch "
            "began (the ring went on across the epoch's turn by itself)",
        )
        for core, nodes in self.executor.attention_cores.items():
            reg.gauge(
                "train_attention_core_nodes",
                help="multihead_attention nodes of the train step by the "
                "core their lowering took (ops.attention.mha_core_plan)",
                labels={"core": core},
            ).set(nodes)
        borrowed, copied, lease_wait_s, ahead = loader.take_counts()
        c_borrowed.inc(borrowed)
        c_copied.inc(copied)
        c_ahead.inc(ahead)
        g_lease.set(1e3 * lease_wait_s / max(len(stamps), 1))
        tracer = tele.tracer
        g_epoch.set(epoch)
        prev = epoch_t0
        for i, t_end in enumerate(stamps):
            fl = losses[i] if i < len(losses) else float("nan")
            dt = t_end - prev
            g_loss.set(fl)
            g_step.set(dt)
            g_eps.set(sample_counts[i] / dt if dt > 0 else 0.0)
            c_iters.inc()
            c_examples.inc(sample_counts[i])
            c_recompiles.set_monotonic(float(self.recompile_events))
            g_jit.set(float(self.executor.jit_builds))
            g_inval.set(float(self.executor.jit_invalidations))
            tracer.complete(
                "iteration", "train", prev, t_end,
                args={"epoch": epoch, "iteration": train_iters,
                      "loss": fl},
            )
            tele.sample(train_iters)
            prev = t_end
            train_iters += 1
        tracer.complete(
            "epoch", "train", epoch_t0, prev if stamps else epoch_t0,
            args={"epoch": epoch},
        )
        return train_iters

    def evaluate(self, x, y, batch_size: Optional[int] = None, callbacks=None):
        batch_size = batch_size or self.config.batch_size
        callbacks = list(callbacks or [])
        for cb in callbacks:
            if getattr(cb, "model", None) is None:
                cb.set_model(self)
        for cb in callbacks:
            cb.on_train_begin()
        arrays = self._pack_dataset(x, y)
        loader = SingleDataLoader(arrays, batch_size)
        estep = self.executor.eval_step()
        perf = PerfMetrics()
        loader.reset()
        for it in range(loader.num_batches):
            for cb in callbacks:
                cb.on_batch_begin(it)
            # lent and placed as in fit()
            b = loader.lend(self.executor.shard_batch(loader.borrow_batch()))
            loss, mets = estep(self.params, b)
            perf.update(jax.tree_util.tree_map(float, mets), float(loss))
            for cb in callbacks:
                cb.on_batch_end(it)
        self._perf_metrics = perf
        for cb in callbacks:
            cb.on_train_end()
        return perf

    def get_perf_metrics(self) -> PerfMetrics:
        """Most recent epoch's accumulated metrics (reference:
        FFModel::get_perf_metrics via flexflow_model_get_perf_metrics —
        the handle VerifyMetrics callbacks read, flexflow_cffi.py:2221)."""
        perf = getattr(self, "_perf_metrics", None)
        return perf if perf is not None else PerfMetrics()

    def _pack_dataset(self, x, y) -> Dict[str, np.ndarray]:
        # reference native-python scripts pass the handles returned by
        # create_data_loader (flexflow_cffi.py fit(x=dataloader_input,
        # y=dataloader_label)); unwrap them to the named arrays
        if isinstance(x, TensorDataLoader):
            x = {x.name: x.array}
        elif isinstance(x, (list, tuple)) and any(
            isinstance(v, TensorDataLoader) for v in x
        ):
            if not all(isinstance(v, TensorDataLoader) for v in x):
                raise TypeError(
                    "fit(x=[...]) mixes create_data_loader handles with "
                    "raw arrays; pass all loaders or all arrays"
                )
            x = {v.name: v.array for v in x}
        if isinstance(y, TensorDataLoader):
            y = y.array
        if isinstance(x, dict):
            arrays = dict(x)
        else:
            xs = list(x) if isinstance(x, (list, tuple)) else [x]
            if len(xs) != len(self._input_order):
                raise ValueError(
                    f"model has {len(self._input_order)} inputs, got {len(xs)}"
                )
            arrays = dict(zip(self._input_order, xs))
        # coerce each input to its declared dtype (embedding ids arriving
        # as floats from generic loaders / the C ABI's single float
        # buffer, flexflow_c.h fit)
        if self.executor is not None:
            shapes = self.executor.input_shapes()
            for name, arr in arrays.items():
                want = shapes.get(name)
                if want is None or want.dtype.value not in (
                    "float32", "int32", "int64", "float64", "bool",
                ):
                    continue  # bf16/f16 inputs: numpy has no such dtype
                np_dt = np.dtype(want.dtype.value)
                if getattr(arr, "dtype", None) != np_dt:
                    arrays[name] = np.asarray(arr).astype(np_dt)
        arrays["label"] = y
        return arrays

    # reference native-python dataloader surface (flexflow_cffi.py:2050
    # create_data_loader → SingleDataLoader; the compat namespace's
    # examples pass these handles straight into fit/evaluate)
    def create_data_loader(self, tensor, array) -> "TensorDataLoader":
        """reference: FFModel.create_data_loader(batch_tensor, numpy) —
        binds a full dataset array to one input tensor; None (the
        label_tensor handle) binds the label slot."""
        if tensor is None:
            return TensorDataLoader("label", array)
        node = (
            self.graph.nodes.get(tensor.ref.guid)
            if getattr(tensor, "ref", None) is not None
            else None
        )
        if node is None or node.op_type != OperatorType.INPUT:
            raise ValueError(
                "create_data_loader takes an INPUT tensor (or None for "
                f"the label), got {tensor!r}"
            )
        return TensorDataLoader(node.name, array)

    @property
    def label_tensor(self):
        """reference: flexflow_model_get_label_tensor — the label tensor
        created at compile to match the final op's shape; here a named
        handle create_data_loader recognizes."""
        if self.executor is None:
            raise RuntimeError("call compile() before label_tensor")
        return None  # create_data_loader(None, y) binds the label slot

    def init_layers(self):
        """reference spelling of init_operators (flexflow_cffi.py)."""
        return self.init_operators()

    # compat verbs (reference training loop: forward/zero_gradients/backward/
    # update — subsumed by the fused jitted step; provided for ported scripts)
    def forward(self, batch: Dict[str, np.ndarray], seq_length: Optional[int] = None):
        """reference: FFModel::forward(seq_length), model.cc:2409 — the
        optional per-iteration sequence truncation reaches BatchMatmul.
        Like the reference (default -1 = full), the truncation applies to
        THIS call only; omitting seq_length restores the config default."""
        self.executor.set_seq_length(
            seq_length if seq_length is not None else self.config.seq_length
        )
        b = self.executor.shard_batch(batch)
        return self.executor.forward_fn()(self.params, b)

    def generate(
        self,
        prompts,
        max_new_tokens: int = 16,
        serve_config=None,
        eos_token=None,
        draft_model=None,
    ):
        """Autoregressive generation with continuous batching (the
        FlexFlow Serve surface grafted onto the training FFModel): token-id
        prompts in, generated token lists out, scheduled by
        serving.scheduler over a preallocated KV cache. Greedy unless the
        ServeConfig sets a temperature. The model must be compiled, take a
        single int token input, and use causal self-attention.
        `serve_config.spec_draft` turns on speculative decoding
        (serving/spec.py); `draft_model` supplies the small draft LM when
        spec_draft is "model"."""
        from flexflow_tpu.serving.api import ServeConfig, generate

        if self.executor is None:
            raise RuntimeError("call compile() before generate()")
        if serve_config is None:
            serve_config = ServeConfig.from_config(self.config)
        return generate(
            self,
            prompts,
            max_new_tokens=max_new_tokens,
            serve=serve_config,
            eos_token=eos_token,
            draft_model=draft_model,
        )

    def compile_for_serving(
        self,
        serve_config=None,
        dp: Optional[int] = None,
        tp: Optional[int] = None,
        num_hosts: Optional[int] = None,
        verbose: bool = False,
    ):
        """Apply a (data, model) SERVING mesh to the compiled model —
        head-sharded attention weights and (via kv_cache.from_model) K/V
        pools as NamedShardings on the mesh `serving/distributed.py`
        builds through `runtime/multihost` (outer axis on DCN, inner on
        ICI) — instead of inheriting the training strategy's sharding.

        Mesh selection: explicit `dp`/`tp` args, else the config's
        ``--serve-mesh dp,tp`` flag, else `search_serving_strategy`'s
        winner (which is then recorded as *applied* rather than
        inherited — the explain/export path reports the mesh the engine
        actually executes). ``--serve-hosts`` (or `num_hosts`) sets the
        scheduler's host-partition count; it defaults to the process
        count on real pods and to dp for simulated-host CPU runs.

        Returns the `ServingPlacement`; also stored as
        `self.serving_placement`, where `serving.api.build_scheduler`
        and `KVCache/PagedKVCache.from_model` pick it up."""
        from flexflow_tpu.core.types import OperatorType
        from flexflow_tpu.serving import distributed as dserve

        if self.executor is None:
            raise RuntimeError("call compile() before compile_for_serving()")
        cfg = self.config
        sc = serve_config  # a ServeConfig overrides the FFConfig mirror

        def knob(sc_name, cfg_name, default):
            if sc is not None:
                return getattr(sc, sc_name)
            return getattr(cfg, cfg_name, default)

        source = "flag"
        sr = None
        if dp is None or tp is None:
            spec = dserve.parse_serve_mesh(knob("serve_mesh", "serve_mesh", ""))
            if spec is not None:
                dp, tp = spec
            else:
                from flexflow_tpu.search.auto import search_serving_strategy

                sr = search_serving_strategy(
                    self,
                    batch_size=max(1, knob("max_seqs", "serve_max_seqs", 8)),
                )
                dp, tp = sr.dp, sr.tp
                source = "searched"
        if num_hosts is None:
            num_hosts = knob("serve_hosts", "serve_hosts", 0) or None
        placement = dserve.build_placement(
            self, dp, tp, num_hosts=num_hosts, mesh_source=source
        )

        # cache geometry (the from_model defaults) — validated here so a
        # bad --serve-mesh fails before any device work, and exported in
        # the placement doc for fxlint strategy-validate
        max_seqs = knob("max_seqs", "serve_max_seqs", 8)
        max_seq_len = knob("max_seq_len", "serve_max_seq_len", 256)
        from flexflow_tpu.serving.kv_cache import default_page_size

        page_size = knob(
            "kv_page_size", "serve_kv_page_size", 0
        ) or default_page_size(max_seq_len)
        num_pages = knob("kv_pages", "serve_kv_pages", 0) or (
            max_seqs * max_seq_len // page_size
        )
        placement.validate_geometry(max_seqs, num_pages)

        def _serving_sharding(node, i, wshape):
            if node.op_type == OperatorType.MULTIHEAD_ATTENTION:
                ndim = sum(1 for d in wshape.dims if not d.is_replica_dim)
                if i in (0, 1, 2):  # wq/wk/wv: (embed, heads, head_dim)
                    return placement.head_sharding(1, ndim)
                if i in (3, 4, 5, 6):  # wo / bq/bk/bv: heads-major
                    return placement.head_sharding(0, ndim)
            return placement.replicated()  # bo + every non-attention op

        self.params = self.executor.reshard_params(
            self.params, _serving_sharding
        )
        self.serving_placement = placement
        if sr is not None:
            sr.mesh_execution = "applied"
            self.serve_search_result = sr
            if verbose or cfg.search_explain:
                print(f"[serve-search] {sr.describe()}")
        if verbose or cfg.search_explain:
            print(f"[serve-mesh] {placement.describe()}")
        export = getattr(cfg, "serve_export_strategy", "")
        if export:
            import json

            doc = placement.to_doc(max_seqs=max_seqs, num_pages=num_pages)
            if sr is not None:
                doc["search"] = sr.to_doc()
            with open(export, "w") as f:
                json.dump(doc, f, indent=2)
        return placement

    def zero_gradients(self):
        pass  # gradients are functional; nothing to zero

    def backward(self):
        """reference: FFModel::backward (model.cc:2432). Subsumed: the
        jitted train step computes grads via jax.value_and_grad."""

    def compute_gradients(self, x, y) -> Dict[int, list]:
        """Per-parameter loss gradients for one batch, as host arrays keyed
        like `params` ({guid: [grad per weight slot]}).

        The alignment harness's window into the backward pass (reference:
        align/align_ff_utils.py run_fwd_bwd reads each op's region gradients
        after backward()); here one jax.grad over the whole compiled program
        yields every weight gradient at once. Dropout is disabled
        (train=False) so results are deterministic."""
        if self.executor is None:
            raise RuntimeError("call compile() before compute_gradients()")
        self.executor.set_seq_length(self.config.seq_length)
        batch = self.executor.shard_batch(self._pack_dataset(x, y))
        grads = self.executor.grad_fn()(self.params, batch)
        return {
            guid: [np.asarray(g) for g in gs] for guid, gs in grads.items()
        }

    def update(self):
        """reference: FFModel::update (model.cc:2463). Subsumed: the jitted
        train step applies the optimizer in the same program."""

    def init_operators(self):
        """reference: FFModel::init_operators (model.cc:2403 — per-op INIT
        index tasks allocating OpMeta). Here it AOT-compiles the train step
        on zero-filled example shapes (jit is lazy, so merely building the
        jitted callable would compile nothing) — the first fit() iteration
        then hits the compile cache instead of stalling."""
        if self.executor is None:
            raise RuntimeError("call compile() before init_operators()")
        step = self.executor.train_step()
        zeros = {
            name: np.zeros(
                tuple(d.size for d in shape.dims if not d.is_replica_dim),
                shape.dtype.to_jnp(),  # jnp scalar types are np-compatible
            )
            for name, shape in self.executor.input_shapes().items()
        }
        sharded = self.executor.shard_batch(zeros)
        step.lower(
            self.params, self.opt_state, sharded, jax.random.PRNGKey(0)
        ).compile()

    def begin_trace(self, trace_id: int = 0):
        """reference: runtime->begin_trace (transformer.cc:192 — Legion
        capture-and-replay). Subsumed by jit compilation caching."""

    def end_trace(self, trace_id: int = 0):
        """See begin_trace."""

    def profile_operators(self, batch, iters: int = 5, verbose: bool = True):
        """Each node's forward jitted ALONE and timed: a cost per node
        before a program exists, for the calibration and the audit. It
        sees no fusion across nodes, no backward, update, collective or
        mesh lowering: `profile_step` is the table of the real step."""
        from flexflow_tpu.utils.profiling import profile_operators

        return profile_operators(self, batch, iters=iters, verbose=verbose)

    def profile_step(self, batch, steps: int = 8, verbose: bool = True):
        """The --profiling table (reference: per-kernel cudaEvent prints,
        kernels/linear_kernels.cu:95-117) read from the compiled train
        step as it runs: device time per PCG node, forward and backward,
        with `loss`, `update` and the collectives
        (`utils.profiling.profile_step`). Needs a device that writes
        `XLA Ops` into its profile (a TPU)."""
        from flexflow_tpu.utils.profiling import profile_step

        return profile_step(self, batch, steps=steps, verbose=verbose)

    def audit_cost_model(self, batch=None, **kwargs):
        """Predicted-vs-measured cost-model audit (search/audit.py):
        price the compiled graph with the search's own CostModel, time
        the real executor step, export cost_model_error_ratio gauges
        per op family, and feed the residuals back through the
        calibration table's read-merge-write path."""
        from flexflow_tpu.search.audit import audit_cost_model

        return audit_cost_model(self, batch=batch, **kwargs)

    def recompile_on_condition(self, state) -> bool:
        """Mid-training model mutation + recompile (reference:
        FFModel::recompile_on_condition, model.cc:2416-2420; MoE expert
        rebalancing, moe.cc:65-99). See runtime.recompile.RecompileState."""
        from flexflow_tpu.runtime.recompile import recompile_on_condition

        return recompile_on_condition(self, state)

    def _live_guid(self, guid: int) -> int:
        """Resolve a builder-graph guid to the compiled graph. Graph
        rewrites (the default substitution pass, fusion) replace builder
        nodes with fresh guids but thread the original identity through
        params['weight_key'] (substitution.py:_dst_params) — the same key
        the recompile hook restores weights by."""
        if guid in self.graph.nodes:
            return guid
        src = (
            self._prestrategy_graph.nodes.get(guid)
            if getattr(self, "_prestrategy_graph", None) is not None
            else None
        )
        if src is not None:
            for g, n in self.graph.nodes.items():
                if n.weight_key == src.weight_key:
                    return g
        raise KeyError(
            f"tensor guid {guid} not in the compiled graph (and no rewrite "
            "carried its weight_key forward)"
        )

    def get_tensor(self, guid: int, idx: int = 0) -> np.ndarray:
        """Pull a weight to host (reference: ParallelTensor get_tensor).
        Pipelined trunks read the one [block] slice of their pipe-sharded
        stack (Executor.get_host_param) — never the whole export view."""
        guid = self._live_guid(guid)
        return np.asarray(
            self.executor.get_host_param(self.params, guid, idx)
        )

    def set_tensor(self, guid: int, idx: int, value: np.ndarray):
        guid = self._live_guid(guid)
        node = self.graph.nodes[guid]
        val = jnp.asarray(value, node.weight_shapes[idx].dtype.to_jnp())
        expect = tuple(
            d.size
            for d in node.weight_shapes[idx].dims
            if not d.is_replica_dim
        )
        if tuple(val.shape) != expect:
            # validate BEFORE any mutation (a stacked [S, ...] write to a
            # pipelined template guid must not silently replace the
            # pipe-sharded stack; use checkpoint restore for bulk loads)
            raise ValueError(
                f"set_tensor for {node.name} expects shape {expect}, "
                f"got {tuple(val.shape)}"
            )
        self.executor.set_host_param(self.params, guid, idx, val)

    # --------------------------------------------------------- checkpointing
    # The reference has no model checkpointing (SURVEY §5); this is the
    # orbax-backed upgrade: params + optimizer state + RNG, step-tagged.

    def save_checkpoint(self, directory: str, step: int, max_to_keep: int = 3):
        """Persist training state under `directory/step_<step>/`."""
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        if self.executor is None:
            raise RuntimeError("call compile() before save_checkpoint()")
        mgr = CheckpointManager(directory, max_to_keep=max_to_keep)
        mgr.save(
            step,
            {
                # on-disk layout is always per-guid (the pipelined
                # executor unstacks its pipe-sharded trunk), so
                # checkpoints restore across strategies — optimizer
                # state subtrees that mirror params convert the same way
                "params": self.executor.export_host_params(self.params),
                "opt_state": self.executor.export_host_opt_state(
                    self.opt_state
                ),
                "rng": self._rng,
            },
        )

    def set_learning_rate(self, lr: float):
        """Mid-training LR change (reference: SGDOptimizer::set_lr /
        flexflow_sgd_optimizer_set_lr — the LR-decay pattern its examples
        use between epochs). The optimizer dataclass is frozen, so the
        model rebinds a replaced copy and drops the cached jitted step;
        optimizer STATE (momentum, Adam moments) is structure-compatible
        and survives."""
        import dataclasses as _dc

        from flexflow_tpu.runtime.optimizer import AdamOptimizer

        if self.optimizer is None:
            raise RuntimeError("call compile() before set_learning_rate()")
        field = "alpha" if isinstance(self.optimizer, AdamOptimizer) else "lr"
        if getattr(self.optimizer, field) == lr:
            return  # unchanged: keep the cached jitted step (a constant
            # schedule must not retrace every epoch)
        self.optimizer = _dc.replace(self.optimizer, **{field: lr})
        if self.executor is not None:
            self.executor.optimizer = self.optimizer
            if self.executor._train_step is not None:
                self.executor.jit_invalidations += 1
            self.executor._train_step = None

    def restore_checkpoint(self, directory: str, step: Optional[int] = None) -> int:
        """Load training state (latest step by default); returns the step.

        Weights are re-placed with the compiled strategy's shardings, so a
        checkpoint written under one mesh restores correctly under another
        (e.g. train data-parallel, resume with a searched dp×tp strategy).
        """
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        if self.executor is None:
            raise RuntimeError("call compile() before restore_checkpoint()")
        mgr = CheckpointManager(directory)
        step, state = mgr.restore(step)
        self.params = self.executor.place_params(state["params"])
        # mirror subtrees (momentum/Adam moments) re-place like weights,
        # so stateful optimizers survive cross-strategy restores too
        self.opt_state = self.executor.place_opt_state(state["opt_state"])
        if "rng" in state:
            self._rng = jnp.asarray(state["rng"])
        return step
