"""PCG → XLA executor.

This is the TPU-native replacement for the reference's entire execution stack
(Legion index launches + FFMapper + CUDA kernels; SURVEY §3.3): the annotated
PCG lowers to ONE pure train-step function, jitted over a
`jax.sharding.Mesh`. Per-op MachineViews/parallel dims become
`with_sharding_constraint`s; GSPMD inserts the collectives the reference's
parallel ops / NCCL allreduce performed explicitly; Legion's begin/end_trace
iteration replay (reference: transformer.cc:192-198) is subsumed by jit
compilation caching.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
from flexflow_tpu.core.pcg import PCGGraph, TensorRef
from flexflow_tpu.core.types import LossType, MetricsType, OperatorType
from flexflow_tpu.ops.registry import LowerCtx, infer_shapes, lower_op
from flexflow_tpu.runtime.initializer import default_weight_initializer
from flexflow_tpu.runtime.loss import compute_loss
from flexflow_tpu.runtime.metrics import compute_metrics
from flexflow_tpu.runtime.optimizer import Optimizer


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """The global device mesh the strategy is expressed over.

    axis i of this mesh is what ParallelDim.parallel_idx == i refers to.
    This is the v1 restriction documented in SURVEY §7: every MachineView
    the search picks must be expressible as sub-axes of one global mesh
    (the reference allows arbitrary per-op device sets).
    """

    axis_names: Tuple[str, ...] = ("data",)
    axis_sizes: Tuple[int, ...] = (1,)

    @property
    def num_devices(self) -> int:
        out = 1
        for s in self.axis_sizes:
            out *= s
        return out

    def build_mesh(self, devices=None) -> Mesh:
        devices = jax.devices() if devices is None else list(devices)
        n = self.num_devices
        if len(devices) < n:
            raise ValueError(
                f"mesh needs {n} devices, have {len(devices)}"
            )
        arr = np.array(devices[:n]).reshape(self.axis_sizes)
        return Mesh(arr, self.axis_names)

    @staticmethod
    def data_parallel(num_devices: int) -> "MeshConfig":
        return MeshConfig(("data",), (num_devices,))


def propagate_shapes(graph: PCGGraph):
    """Re-run parallel-shape inference over the whole graph in topo order.

    Called after a strategy annotates source nodes or inserts parallel ops —
    the equivalent of the reference's per-op output-dim solve at PCG
    construction (reference: model.cc:494-647).
    """
    for guid in graph.topo_order():
        node = graph.nodes[guid]
        if not node.inputs:
            (outs, weights) = infer_shapes(node.op_type, [], node.params)
            node.output_shapes = tuple(outs)
            continue
        in_shapes = [graph.shape_of(r) for r in node.inputs]
        outs, weights = infer_shapes(node.op_type, in_shapes, node.params)
        node.output_shapes = tuple(outs)
        node.weight_shapes = tuple(weights)


def node_scope(node):
    """The named scope every instruction lowered from `node` carries in
    the compiled program (`kind:name`, `kind` the operator type in lower
    case): what `utils.profiling.profile_step` and a profile viewer
    group device time by. HLO metadata only."""
    return jax.named_scope(f"{node.op_type.name.lower()}:{node.name}")


class Executor:
    """Compiles an annotated PCG into jitted step functions."""

    def __init__(
        self,
        graph: PCGGraph,
        mesh_config: MeshConfig,
        logits_ref: TensorRef,
        label_shape: Optional[ParallelTensorShape] = None,
        loss_type: Optional[LossType] = None,
        metrics: Sequence[MetricsType] = (),
        optimizer: Optional[Optimizer] = None,
        devices=None,
        aux_loss_fns=(),
        logits_from_logits: bool = True,
        mixed_precision: bool = False,
        seq_length: Optional[int] = None,
        sparse_embedding_update: bool = False,
    ):
        self.graph = graph
        self.mesh_config = mesh_config
        self.mesh = mesh_config.build_mesh(devices)
        self.logits_ref = logits_ref
        self.label_shape = label_shape
        self.loss_type = loss_type
        self.metric_types = tuple(metrics)
        self.optimizer = optimizer
        self.aux_loss_fns = tuple(aux_loss_fns)
        self.logits_from_logits = logits_from_logits
        self.mixed_precision = mixed_precision
        self.seq_length = seq_length
        self.sparse_embedding_update = sparse_embedding_update
        self.topo = graph.topo_order()
        # nodes that apply another node's weights (FFModel's `weights_of=`):
        # {borrower guid: owner guid}. `params` holds the owner's arrays
        # only, so the optimizer, a checkpoint and a resharding see each
        # shared weight once, and its gradient sums over the applications
        self.weight_owner = graph.weight_owners()
        self._lowered = {
            g: lower_op(graph.nodes[g].op_type, graph.nodes[g].params)
            for g in self.topo
        }
        # cache ops surface their input to the host memoizer each train
        # step (reference: cache.cc forward stores the batch; here the
        # value rides the metrics pytree out of the jitted step)
        self.cache_guids = [
            g
            for g in self.topo
            if graph.nodes[g].op_type == OperatorType.CACHE
        ]
        self._train_step = None
        self._eval_step = None
        self._fwd = None
        self._grad_fn = None
        # jit-cache telemetry: how many step callables this executor
        # built (each is one XLA compile on first call) and how many
        # times a cached step was dropped (seq-length change, LR
        # rebind) — mirrored into train_jit_* series by FFModel.fit
        self.jit_builds = 0
        self.jit_invalidations = 0
        # attention nodes of the train step by the core they took, set
        # when the step is built (`attention_plans`)
        self.attention_cores: Dict[str, int] = {}

    # -- shardings -----------------------------------------------------------

    def sharding_for(self, shape: ParallelTensorShape) -> NamedSharding:
        spec = shape.partition_spec(
            self.mesh_config.axis_names, self.mesh_config.axis_sizes
        )
        return NamedSharding(self.mesh, spec)

    def _constrain(self, x, shape: ParallelTensorShape):
        if shape.total_degree > 1 and any(
            d.degree > 1 and not d.is_replica_dim for d in shape.dims
        ):
            return jax.lax.with_sharding_constraint(x, self.sharding_for(shape))
        return x

    # -- parameters ----------------------------------------------------------

    def init_params(self, rng, skip_guids=frozenset()) -> Dict[int, List[jnp.ndarray]]:
        """Initialize + shard all weights (reference: initializer tasks at
        Op::init, SURVEY §2.1). skip_guids: nodes a subclass stores
        differently (the pipelined executor's stacked trunk)."""
        params: Dict[int, List[jnp.ndarray]] = {}
        for guid in self.topo:
            node = self.graph.nodes[guid]
            if not node.stored_weight_shapes or guid in skip_guids:
                continue
            ws = []
            inits = node.params.get("initializers")
            for i, wshape in enumerate(node.weight_shapes):
                init = (
                    inits[i]
                    if inits is not None and inits[i] is not None
                    else default_weight_initializer(node.name, i, wshape)
                )
                key = jax.random.fold_in(rng, guid * 131 + i)
                arr = init.create(key, wshape)
                arr = jax.device_put(arr, self.sharding_for(wshape))
                ws.append(arr)
            params[guid] = ws
        return params

    def place_params(
        self, host_params: Dict[int, List[np.ndarray]], skip_guids=frozenset()
    ) -> Dict[int, List[jnp.ndarray]]:
        """Re-shard host weights onto the mesh (checkpoint restore path)."""
        params: Dict[int, List[jnp.ndarray]] = {}
        for guid in self.topo:
            node = self.graph.nodes[guid]
            if not node.stored_weight_shapes or guid in skip_guids:
                continue
            if guid not in host_params:
                raise KeyError(
                    f"checkpoint missing weights for node {guid} ({node.name})"
                )
            ws = []
            for wshape, arr in zip(node.weight_shapes, host_params[guid]):
                expect = tuple(d.size for d in wshape.dims if not d.is_replica_dim)
                if tuple(arr.shape) != expect:
                    raise ValueError(
                        f"checkpoint weight for {node.name} has shape "
                        f"{tuple(arr.shape)}, model expects {expect}"
                    )
                ws.append(jax.device_put(jnp.asarray(arr), self.sharding_for(wshape)))
            params[guid] = ws
        return params

    def reshard_params(self, params, sharding_fn):
        """Re-place a live param tree under NEW shardings — the
        compile-for-serving path, where the serving (data, model) mesh
        differs from the training mesh the weights were initialized on.
        `sharding_fn(node, weight_index, wshape)` returns the target
        `jax.sharding.Sharding` for each weight, or None to leave that
        array untouched. Arrays round-trip through host memory (they
        must be addressable: single-process, or restored host-replicated
        checkpoints on pods) and re-place through
        `multihost.place_array` so multi-process runs materialize only
        locally-owned shards."""
        from flexflow_tpu.runtime import multihost

        out: Dict[int, List[jnp.ndarray]] = {}
        for guid, ws in params.items():
            node = self.graph.nodes[guid]
            new_ws = []
            for i, arr in enumerate(ws):
                sh = sharding_fn(node, i, node.weight_shapes[i])
                if sh is None:
                    new_ws.append(arr)
                else:
                    new_ws.append(multihost.place_array(np.asarray(arr), sh))
            out[guid] = new_ws
        return out

    def export_host_params(self, params):
        """Params in the on-disk checkpoint layout (per-guid). The base
        executor's storage IS that layout (copied, so callers can edit
        without touching live state); the pipelined executor overrides to
        unstack its pipe-sharded trunk."""
        return {g: list(ws) for g, ws in params.items()}

    def export_host_opt_state(self, opt_state):
        """Optimizer state in the on-disk layout: subtrees that mirror
        the params pytree (SGD velocity, Adam m/v) go through the same
        per-guid conversion as the params themselves."""
        out = {}
        for k, v in opt_state.items():
            out[k] = self.export_host_params(v) if isinstance(v, dict) else v
        return out

    def commit_opt_state(self, state):
        """Commit to the mesh, replicated, whatever leaves of an optimizer
        state are not placed yet (the step counter `init_state` makes
        from nothing). Left uncommitted on the default device, such a
        leaf comes back from the first train step committed to the mesh —
        a different argument sharding — and the whole step compiles a
        second time on the second iteration (19 s for the 12-layer
        flagship on a v5e)."""
        replicated = NamedSharding(self.mesh, PartitionSpec())
        return jax.tree_util.tree_map(
            lambda a: a if a.committed else jax.device_put(a, replicated),
            state,
        )

    def place_opt_state(self, host_state):
        """Restore optimizer state saved by export_host_opt_state: mirror
        subtrees re-place like weights (same shapes/shardings), scalars
        replicate on the mesh (see commit_opt_state)."""
        out = {}
        for k, v in host_state.items():
            out[k] = (
                self.place_params(v)
                if isinstance(v, dict)
                else self.commit_opt_state(jnp.asarray(v))
            )
        return out

    def get_host_param(self, params, guid: int, idx: int):
        """One weight, in its logical per-guid shape (its owner's array,
        where the node applies another's)."""
        return params[self.weight_owner.get(guid, guid)][idx]

    def set_host_param(self, params, guid: int, idx: int, val):
        """Write one weight in place (val already validated/dtyped);
        through a node that applies another's, the owner's."""
        node = self.graph.nodes[guid]
        params[self.weight_owner.get(guid, guid)][idx] = jax.device_put(
            val, self.sharding_for(node.weight_shapes[idx])
        )

    # -- forward -------------------------------------------------------------

    def lower_node(self, guid, ins, ws, ctx, hook=None, constrain=False):
        """A node's outputs from its registered lowering, or from the
        `hook` that stands in for it, each under its sharding constraint
        if `constrain`; all under the node's scope. The one place any
        step program lowers a node."""
        node = self.graph.nodes[guid]
        with node_scope(node):
            if hook is not None:
                outs = hook(node, ins, ws, ctx)
            else:
                outs = self._lowered[guid](ins, ws, ctx)
            if constrain:
                outs = [
                    self._constrain(out, shape)
                    for out, shape in zip(outs, node.output_shapes)
                ]
        return outs

    def constrain_given(self, node, x):
        """The sharding constraint on a value the step was handed for
        `node` (a batch input, an injected activation), under its scope."""
        with node_scope(node):
            return self._constrain(x, node.output_shapes[0])

    def node_ctx(self, node, train=True, rng=None) -> LowerCtx:
        """The context `node` is lowered under: the mesh, its axis names
        and the parallel shapes of the node's inputs, which is all a
        sharding-aware lowering sees of the strategy (and what a test or
        a profile hands `ops.attention.mha_core_plan` to ask which core
        an attention node gets)."""
        return LowerCtx(
            train=train,
            rng=rng,
            mesh=self.mesh,
            axis_names=self.mesh_config.axis_names,
            in_shapes=[self.graph.shape_of(r) for r in node.inputs],
            bf16_matmul=self.mixed_precision,
            seq_length=self.seq_length,
        )

    def forward_values(
        self,
        params,
        batch,
        rng=None,
        train=True,
        injected=None,
        op_hooks=None,
        constrain=True,
    ):
        """Evaluate the PCG; returns {(guid, out_idx): array}.

        injected: {guid: array} precomputed single-output node values
        (the sparse-embedding fast path differentiates wrt these
        activations instead of the table weights).

        op_hooks: {OperatorType: fn(node, ins, ws, ctx) -> [outs]} —
        per-op-type overrides of the registered lowering. The serving
        engine (flexflow_tpu.serving.engine) re-executes the compiled PCG
        with an attention hook that reads/writes the KV cache; everything
        else runs the normal lowering, so serving reuses this machinery
        instead of growing a second interpreter.

        constrain=False skips the per-tensor sharding constraints — the
        hook path feeds shapes (decode seq length 1) that differ from the
        compiled training shapes, so the recorded PartitionSpecs no
        longer describe the arrays; hooked callers shard their inputs
        explicitly instead."""
        values: Dict[Tuple[int, int], jnp.ndarray] = {}

        def _given(node, x):
            return self.constrain_given(node, x) if constrain else x

        for guid in self.topo:
            node = self.graph.nodes[guid]
            if injected is not None and guid in injected:
                values[(guid, 0)] = _given(node, injected[guid])
                continue
            if node.op_type in (OperatorType.INPUT, OperatorType.NOOP) and not node.inputs:
                if node.name not in batch:
                    raise KeyError(f"batch missing input '{node.name}'")
                values[(guid, 0)] = _given(node, batch[node.name])
                continue
            ins = [values[(r.guid, r.out_idx)] for r in node.inputs]
            ws = params.get(self.weight_owner.get(guid, guid), [])
            ctx = self.node_ctx(
                node, train,
                None if rng is None else jax.random.fold_in(rng, guid),
            )
            hook = op_hooks.get(node.op_type) if op_hooks else None
            outs = self.lower_node(guid, ins, ws, ctx, hook, constrain)
            for i, out in enumerate(outs):
                values[(guid, i)] = out
        return values

    def _loss_and_metrics(self, params, batch, rng, train, injected=None):
        values = self.forward_values(params, batch, rng, train, injected)
        logits = values[(self.logits_ref.guid, self.logits_ref.out_idx)]
        labels = batch["label"]
        with jax.named_scope("loss"):
            loss = compute_loss(
                self.loss_type, logits, labels,
                from_logits=self.logits_from_logits,
            )
            for fn in self.aux_loss_fns:
                loss = loss + fn(values, batch)
            mets = compute_metrics(
                self.metric_types, logits, labels,
                from_logits=self.logits_from_logits,
            )
        if train and self.cache_guids:
            mets = dict(mets)
            for guid in self.cache_guids:
                node = self.graph.nodes[guid]
                r = node.inputs[0]
                mets[f"__cache_{node.name}"] = values[(r.guid, r.out_idx)]
        return loss, mets

    # -- compiled entry points ----------------------------------------------

    def _sparse_embedding_guids(self) -> List[int]:
        """EMBEDDING nodes eligible for the sparse-update fast path:
        optimizer supports sparse rows (SGD incl. momentum/wd, Adam — the
        stateful forms have LAZY semantics, Optimizer.sparse_row_update),
        ids read straight from a batch INPUT. Sharded tables (the searched
        model-parallel DLRM embeddings) are eligible: GSPMD partitions the
        gather/scatter, validated vs the dense path on the 8-device mesh
        (tests/test_sparse_embedding.py).

        Why it matters (beyond-reference): autodiff of jnp.take produces a
        DENSE [vocab, dim] cotangent and the optimizer walks the whole
        table every step — for DLRM-class models the tables dominate the
        step. The fast path differentiates wrt the embedding ACTIVATIONS
        and scatter-applies the update to only the touched rows (the
        reference's embedding bwd scatter-adds into a dense grad region
        either way, embedding_kernels.cu:backward)."""
        opt = self.optimizer
        if not self.sparse_embedding_update or opt is None:
            return []
        if not opt.supports_sparse():
            return []
        from flexflow_tpu.core.pcg import trace_embedding_ids_input

        # a table that another node applies too takes the dense path: its
        # gradient is the sum over both, not rows of one lookup
        tied = set(self.weight_owner) | set(self.weight_owner.values())
        return [
            guid
            for guid in self.topo
            if guid not in tied
            and trace_embedding_ids_input(self.graph, guid) is not None
        ]

    def train_step_fn(self):
        """(params, opt_state, batch, rng) -> (params, opt_state, loss, metrics)"""
        sparse = self._sparse_embedding_guids()
        if not sparse:

            def step(params, opt_state, batch, rng):
                def loss_fn(p):
                    return self._loss_and_metrics(p, batch, rng, train=True)

                (loss, mets), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                with jax.named_scope("update"):
                    new_params, new_state = self.optimizer.update(
                        params, grads, opt_state
                    )
                return new_params, new_state, loss, mets

            return step

        from flexflow_tpu.core.types import AggrMode
        from flexflow_tpu.ops.registry import LowerCtx

        from flexflow_tpu.core.pcg import trace_embedding_ids_input

        ids_name = {
            g: self.graph.nodes[
                trace_embedding_ids_input(self.graph, g).guid
            ].name
            for g in sparse
        }

        def sparse_step(params, opt_state, batch, rng):
            # forward lookups OUTSIDE the grad closure: the activations
            # become the differentiable leaves, the tables constants
            acts = {}
            for g in sparse:
                node = self.graph.nodes[g]
                ctx = LowerCtx(
                    train=True,
                    rng=None,
                    mesh=self.mesh,
                    axis_names=self.mesh_config.axis_names,
                    in_shapes=[self.graph.shape_of(node.inputs[0])],
                    bf16_matmul=self.mixed_precision,
                    seq_length=self.seq_length,
                )
                acts[g] = self.lower_node(
                    g, [batch[ids_name[g]]], [params[g][0]], ctx
                )[0]

            dense = {k: v for k, v in params.items() if k not in sparse}

            def loss_fn(dense_p, acts_in):
                full = dict(dense_p)
                for g in sparse:
                    full[g] = params[g]  # closed-over constant
                return self._loss_and_metrics(
                    full, batch, rng, train=True, injected=acts_in
                )

            (loss, mets), (gd, ga) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(dense, acts)
            with jax.named_scope("update"):
                # split out the tables' optimizer-state entries so the dense
                # update's pytrees line up, then row-update each table with
                # its slot (Optimizer.sparse_row_update: lazy momentum/Adam)
                dense_state, slots = self.optimizer.split_state(
                    opt_state, sparse
                )
                new_params, new_state = self.optimizer.update(
                    dense, gd, dense_state
                )
                for g in sparse:
                    node = self.graph.nodes[g]
                    table = params[g][0]
                    ids = batch[ids_name[g]]
                    gact = ga[g]
                    aggr = node.params.get("aggr", AggrMode.NONE)
                    if aggr == AggrMode.SUM:
                        rows = jnp.broadcast_to(
                            gact[..., None, :], ids.shape + gact.shape[-1:]
                        )
                    elif aggr == AggrMode.AVG:
                        rows = (
                            jnp.broadcast_to(
                                gact[..., None, :], ids.shape + gact.shape[-1:]
                            )
                            / ids.shape[-1]
                        )
                    else:  # NONE: cotangent already one row per id
                        rows = gact
                    dim = rows.shape[-1]
                    new_table, new_slot = self.optimizer.sparse_row_update(
                        table,
                        slots.get(g),
                        ids.reshape(-1),
                        rows.reshape(-1, dim).astype(table.dtype),
                        new_state["step"],
                    )
                    new_params[g] = [new_table]
                    slots[g] = new_slot
                new_state = self.optimizer.merge_state(new_state, slots)
            return new_params, new_state, loss, mets

        return sparse_step

    def set_seq_length(self, seq_length: Optional[int]):
        """Per-iteration dynamic sequence truncation (reference:
        FFIterationConfig.seq_length, config.h:160-165; threaded into
        BatchMatmul). Changing it invalidates the compiled steps — each
        distinct length is one XLA recompile, like a new Legion trace."""
        if seq_length != self.seq_length:
            self.seq_length = seq_length
            self.jit_invalidations += sum(
                f is not None
                for f in (
                    self._train_step, self._eval_step, self._fwd,
                    self._grad_fn,
                )
            )
            self._train_step = None
            self._eval_step = None
            self._fwd = None
            self._grad_fn = None

    def train_step(self):
        if self._train_step is None:
            self._train_step = jax.jit(self.train_step_fn(), donate_argnums=(0, 1))
            self.jit_builds += 1
            self.attention_cores = dict(
                collections.Counter(p.core for p in self.attention_plans())
            )
        return self._train_step

    def attention_plans(self) -> List:
        """The core each `multihead_attention` node of the train step is
        lowered to (`ops.attention.mha_core_plan`, asked as
        `forward_values` asks it in a train step), in graph order; by
        core and count it is `attention_cores`, which `FFModel.fit`
        mirrors into the `train_attention_core_nodes` gauges."""
        from flexflow_tpu.ops.attention import mha_core_plan

        rng = jax.random.PRNGKey(0)
        return [
            mha_core_plan(node.params, self.node_ctx(node, rng=rng))
            for node in (self.graph.nodes[g] for g in self.topo)
            if node.op_type == OperatorType.MULTIHEAD_ATTENTION
        ]

    def eval_step(self):
        if self._eval_step is None:

            def step(params, batch):
                return self._loss_and_metrics(params, batch, None, train=False)

            self._eval_step = jax.jit(step)
            self.jit_builds += 1
        return self._eval_step

    def grad_fn(self):
        """Loss gradients wrt params: (params, batch) -> grads pytree.
        Dropout/rng-free (train=False), jitted and cached like eval_step."""
        if self._grad_fn is None:

            def grads(params, batch):
                def loss_fn(p):
                    loss, _ = self._loss_and_metrics(
                        p, batch, None, train=False
                    )
                    return loss

                return jax.grad(loss_fn)(params)

            self._grad_fn = jax.jit(grads)
            self.jit_builds += 1
        return self._grad_fn

    def forward_fn(self):
        """Inference forward: (params, batch) -> logits."""
        if self._fwd is None:

            def fwd(params, batch):
                values = self.forward_values(params, batch, None, train=False)
                return values[(self.logits_ref.guid, self.logits_ref.out_idx)]

            self._fwd = jax.jit(fwd)
            self.jit_builds += 1
        return self._fwd

    # -- data placement ------------------------------------------------------

    def shard_batch(self, batch: Dict[str, np.ndarray], tracer=None):
        """Host→device transfer with each input's searched sharding
        (the TPU analog of the reference's SingleDataLoader index-launched
        shard copies, python/flexflow_dataloader.cc). On multi-host runs
        every process passes the SAME GLOBAL batch and materializes only
        the shards its devices own; one placement loop serves both paths
        (runtime/multihost.place_batch)."""
        from flexflow_tpu.runtime.multihost import place_batch

        return place_batch(
            self, batch, multi=jax.process_count() > 1, tracer=tracer
        )

    def input_shapes(self) -> Dict[str, ParallelTensorShape]:
        out = {}
        for guid in self.topo:
            node = self.graph.nodes[guid]
            if node.op_type == OperatorType.INPUT and not node.inputs:
                out[node.name] = node.output_shapes[0]
        if self.label_shape is not None:
            out["label"] = self.label_shape
        return out
