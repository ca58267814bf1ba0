"""Graph-level step-time estimation for candidate parallel strategies.

The TPU rebuild of the reference's task-graph simulation
(reference: Simulator::simulate_runtime, src/runtime/simulator.cc:810-1240).
Two modes:

  * **taskgraph** (default): lower the annotated PCG into a SimTask DAG —
    forward/backward compute on a representative chip (one XLA stream;
    SPMD makes all chips symmetric), collectives and per-weight gradient
    all-reduces on an ICI link resource — and replay it event-driven
    through the native simulator (native/src/simulator.cc, pure-Python
    fallback inside flexflow_tpu.native). This captures what the analytic
    sum cannot: gradient syncs overlapping with the remaining backward
    compute, exactly the overlap XLA's async collectives give a real step.
  * **analytic**: the reference's `LogicalTaskgraphBasedSimulator` style
    closed-form sum (simulator.h:776-818) — compute + comm + sync.

Costs come from `CostModel`; parallel ops map to collectives per the
SURVEY §2.3 table:

  Replicate  fwd broadcast(free: GSPMD keeps unsharded axes replicated),
             bwd all-reduce of the grad over the replica group
  Reduction  fwd all-reduce of partial sums, bwd free
  Repartition/Combine/AllToAll  all-to-all / all-gather reshards
  weight update  all-reduce of each weight grad over the mesh axes the
             weight is replicated on (the reference's NCCL allreduce,
             optimizer_kernel.cu:88)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.core.machine import MachineSpec
from flexflow_tpu.core.pcg import PCGGraph
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.search.cost_model import CostModel, OpCost


@dataclasses.dataclass
class GraphCost:
    step_time: float = 0.0
    compute_time: float = 0.0
    comm_time: float = 0.0
    sync_time: float = 0.0
    update_time: float = 0.0  # optimizer HBM traffic (CostModel.update_cost)
    memory_per_chip: int = 0
    weight_bytes: int = 0  # stored weights a chip holds, of memory_per_chip

    def feasible(self, spec: MachineSpec) -> bool:
        return self.memory_per_chip <= spec.hbm_bytes


def _sparse_embedding_rows(graph: PCGGraph, guid: int):
    """Per-chip touched rows per step if this node takes the executor's
    sparse-embedding fast path, else None. Eligibility comes from the
    ONE shared tracer (core.pcg.trace_embedding_ids_input) the executor
    also uses, so search and runtime cannot diverge."""
    from flexflow_tpu.core.pcg import trace_embedding_ids_input

    ref = trace_embedding_ids_input(graph, guid)
    if ref is None:
        return None
    return graph.shape_of(ref).piece_volume()


def _sparse_rows_shard_group(graph: PCGGraph, guid: int) -> int:
    """How many distinct shards the touched-row stream is split into — the
    group every table replica must all-gather over before applying the
    scatter-update (CostModel.sparse_sync_cost). Equals the ids input's
    total sharding degree; 1 (no sync) when the ids are replicated."""
    from flexflow_tpu.core.pcg import trace_embedding_ids_input

    ref = trace_embedding_ids_input(graph, guid)
    if ref is None:
        return 1
    return graph.shape_of(ref).total_degree


def sparse_embedding_node_cost(graph, guid, node, cm):
    """OpCost for a SPARSE-eligible embedding (else None) — the ONE
    compute-pricing site for the fast path, shared by estimate_graph_cost
    and auto._pipeline_candidate (unity derives the same numbers through
    _sparse_embedding_time). The executor's fast path gathers/scatters
    touched rows only, so neither the measured dense-grad kernel nor the
    table-sized roofline applies (the round-4 DLRM 490x finding)."""
    if (
        not cm.sparse_embedding
        or node.op_type != OperatorType.EMBEDDING
        or not node.weight_shapes
    ):
        return None
    rows = _sparse_embedding_rows(graph, guid)
    if rows is None:
        return None
    f, b = cm.sparse_embedding_op_cost(node.weight_shapes[0], rows)
    mem = sum(cm.piece_bytes(s) for s in node.output_shapes)
    mem += sum(cm.piece_bytes(s) for s in node.weight_shapes)
    return OpCost(f, b, 0.0, int(mem))


def _group_size(shape, mesh_sizes) -> int:
    """Mesh axes a tensor is NOT sharded over = its replication group."""
    used = set()
    for d in shape.dims:
        if d.degree > 1 and d.parallel_idx >= 0:
            used.add(d.parallel_idx)
    group = 1
    for i, s in enumerate(mesh_sizes):
        if i not in used:
            group *= s
    return group


def _axis_group_chips(axis: int, degree: int, mesh_sizes) -> range:
    """Device ids of one collective group on a mesh axis. Devices are laid
    out row-major over the mesh, so an axis-i group strides by the product
    of the trailing axis sizes — the geometry a topology-aware machine
    model needs to price cross-node rings correctly."""
    stride = 1
    for s in mesh_sizes[axis + 1:]:
        stride *= s
    return range(0, degree * stride, stride)


def _parallel_op_comm(
    node, in_shapes, cm: CostModel, mesh_sizes=()
) -> Tuple[float, float]:
    """(fwd, bwd) collective seconds for one parallel op (SURVEY §2.3)."""
    x = in_shapes[0]
    y = node.output_shapes[0]
    axis = _collective_axis(node, mesh_sizes)

    _pb = cm.piece_bytes  # wire bytes honor dtype + bf16 mixed precision
    fwd = bwd = 0.0
    if node.op_type == OperatorType.REPLICATE:
        deg = node.params["degree"]
        bwd = cm.all_reduce(
            _pb(x), deg, chips=_axis_group_chips(axis, deg, mesh_sizes)
        )
    elif node.op_type == OperatorType.REDUCTION:
        deg = node.params["degree"]
        fwd = cm.all_reduce(
            _pb(y), deg, chips=_axis_group_chips(axis, deg, mesh_sizes)
        )
    elif node.op_type == OperatorType.REPARTITION:
        deg = node.params["degree"]
        chips = _axis_group_chips(axis, deg, mesh_sizes)
        fwd = cm.all_to_all(_pb(x), deg, chips=chips)
        bwd = cm.all_gather(_pb(y), deg, chips=chips)
    elif node.op_type == OperatorType.COMBINE:
        deg = node.params["degree"]
        chips = _axis_group_chips(axis, deg, mesh_sizes)
        fwd = cm.all_gather(_pb(x), deg, chips=chips)
        bwd = cm.all_to_all(_pb(y), deg, chips=chips)
    elif node.op_type in (OperatorType.ALLTOALL, OperatorType.FUSED_PARALLEL):
        deg = max(x.total_degree, y.total_degree)
        chips = _axis_group_chips(axis, deg, mesh_sizes)
        fwd = cm.all_to_all(_pb(x), deg, chips=chips)
        bwd = cm.all_to_all(_pb(y), deg, chips=chips)
    return fwd, bwd


_CHIP = 0  # compute resource id (one XLA stream per chip; SPMD-symmetric)


def _collective_axis(node, mesh_sizes) -> int:
    """Mesh axis a parallel op's collective rides. Collectives over
    different mesh axes use disjoint ICI torus dimensions and may overlap;
    same-axis collectives serialize on their link resource."""
    idx = node.params.get("parallel_idx", -1)
    if isinstance(idx, int) and 0 <= idx < len(mesh_sizes):
        return idx
    return len(mesh_sizes) - 1  # model axis by convention


def estimate_graph_cost(
    graph: PCGGraph,
    cost_model: CostModel,
    mesh_sizes,
    include_backward: bool = True,
    optimizer_state_factor: float = 3.0,
    mode: str = "taskgraph",
    export: Optional[Dict] = None,
    trace=None,
    trace_label: str = "",
) -> GraphCost:
    """Estimate one training-iteration time for an annotated PCG.

    optimizer_state_factor: weights + grads + momentum ≈ 3× weight bytes
    (Adam: 4×) — feeds the HBM feasibility check.

    export: when a dict is passed, it is filled with the SimTask arrays
    (taskgraph mode) AND a per-node ``node_costs`` list ({guid, name,
    op, family, forward, backward, memory}) — the breakdown the
    predicted-vs-measured audit (search/audit.py) groups by op family.

    trace: an optional telemetry.SearchTrace — records ONE candidate
    row carrying this estimate's full GraphCost breakdown (compute /
    comm / sync / update / memory feasibility), labeled `trace_label`.
    """
    cm = cost_model
    total = GraphCost()
    weight_bytes = 0
    act_bytes = 0
    taskgraph = mode != "analytic"
    # resource ids: chip 0, then one ICI link resource per mesh axis
    num_resources = 1 + max(1, len(mesh_sizes))

    def link(axis: int) -> int:
        return 1 + min(axis, num_resources - 2)

    # SimTask arrays (taskgraph mode)
    resource_of: List[int] = []
    duration: List[float] = []
    names: List[str] = []
    edges: List[Tuple[int, int]] = []
    fwd_task: Dict[int, int] = {}
    bwd_task: Dict[int, int] = {}
    bwd_comm: Dict[int, float] = {}

    def add_task(resource: int, dur: float, name: str = "") -> int:
        if not taskgraph:
            return -1
        resource_of.append(resource)
        duration.append(dur)
        names.append(name)
        return len(resource_of) - 1

    def add_edge(src: int, dst: int):
        if taskgraph:
            edges.append((src, dst))

    topo = graph.topo_order()

    # ---- fusion awareness (measured mode only) ------------------------------
    # Measured kernels are timed in ISOLATION (the reference's
    # inner_measure_operator_cost has the same structural bias,
    # model.cu:38-74): an elementwise op downstream of an MXU op costs a
    # full activation round-trip on its own, but XLA folds it into the
    # producer's epilogue in the real compiled step. Charging it again is
    # why ResNet over-predicted 1.8-2.3x (the round-2 residuals).
    # Under cm.measure, unary elementwise ops whose sole producer is an
    # MXU head (or an op already fused into one) are costed at zero;
    # binary elementwise (residual adds: the skip read is real traffic)
    # and batchnorm (its stats reduction survives fusion) at half.
    fused_free: set = set()
    fused_half: set = set()
    chain_cost: Dict[int, Tuple[float, float]] = {}  # head guid -> (fwd, bwd)
    if cm.measure:
        from flexflow_tpu.search.cost_model import _MXU_OPS

        _free_types = {
            OperatorType.RELU,
            OperatorType.SIGMOID,
            OperatorType.TANH,
            OperatorType.ELU,
            OperatorType.GELU,
            OperatorType.IDENTITY,
            OperatorType.EXP,
            OperatorType.SIN,
            OperatorType.COS,
            OperatorType.POW,
            OperatorType.RSQRT,
            OperatorType.SCALAR_MULTIPLY,
            OperatorType.SCALAR_ADD,
            OperatorType.SCALAR_SUB,
            OperatorType.SCALAR_TRUE_DIV,
            OperatorType.CAST,
            OperatorType.DROPOUT,
        }
        _half_types = {
            OperatorType.EW_ADD,
            OperatorType.EW_SUB,
            OperatorType.EW_MUL,
            OperatorType.EW_DIV,
            OperatorType.EW_MAX,
            OperatorType.EW_MIN,
            OperatorType.BATCHNORM,
            OperatorType.LAYERNORM,
            OperatorType.RMSNORM,
            OperatorType.SOFTMAX,
        }
        _fusable = _free_types | _half_types
        for guid in topo:
            node = graph.nodes[guid]
            if node.op_type not in _fusable:
                continue
            if not any(
                graph.nodes[r.guid].op_type in _MXU_OPS
                or r.guid in fused_free
                or r.guid in fused_half
                for r in node.inputs
            ):
                continue
            if node.op_type in _free_types:
                fused_free.add(guid)
            else:
                fused_half.add(guid)

        # Measure epilogue CHAINS as one kernel where possible (round-3
        # attack on the conv residual: isolated conv + the half-for-bn
        # heuristic left ResNet at 1.40 pred/meas — timing conv→bn→relu
        # together measures what XLA actually compiles). A successful
        # chain measurement replaces the head's cost and zeroes the chain
        # members; failures keep the free/half heuristics above.
        for guid in topo:
            node = graph.nodes[guid]
            if node.op_type not in _MXU_OPS:
                continue
            chain = []
            cur = guid
            while True:
                consumers = list(graph.consumers(cur))
                if len(consumers) != 1:
                    break
                nxt = consumers[0]
                nnode = graph.nodes[nxt]
                if nnode.op_type not in _fusable:
                    break
                if len(nnode.inputs) > 1:
                    # residual adds read a second real activation — that
                    # traffic is not epilogue-free; stop the chain (the
                    # half heuristic above still applies to them)
                    break
                chain.append(nxt)
                cur = nxt
            if not chain:
                continue
            # chain members are single-input by construction, so the
            # chained input index is always 0
            head_ins = [graph.shape_of(r) for r in node.inputs]
            specs = [
                (node.op_type, node.params, head_ins, node.weight_shapes, 0)
            ]
            for g2 in chain:
                n2 = graph.nodes[g2]
                specs.append(
                    (
                        n2.op_type,
                        n2.params,
                        [graph.shape_of(r) for r in n2.inputs],
                        n2.weight_shapes,
                        0,
                    )
                )
            from flexflow_tpu.search.cost_model import shard_batch as _sb

            mt = cm.corrected_times(
                node.op_type, cm.chain_times_floor_adjusted(specs),
                batch=_sb(head_ins),
            )
            if mt is None:
                continue
            chain_cost[guid] = mt
            fused_free.update(chain)
            fused_half.difference_update(chain)

    # ---- forward pass -------------------------------------------------------
    per_node_cost: Dict[int, OpCost] = {}
    for guid in topo:
        node = graph.nodes[guid]
        in_shapes = [graph.shape_of(r) for r in node.inputs]

        if node.op_type == OperatorType.INPUT:
            # stored at true dtype: mixed precision downcasts matmul
            # operands on the fly, not residents (ops/registry.mm_operands)
            act_bytes += sum(s.piece_bytes() for s in node.output_shapes)
            t = add_task(_CHIP, 0.0, f"{node.name}.in")
        elif node.is_parallel_op:
            f, b = _parallel_op_comm(node, in_shapes, cm, mesh_sizes)
            total.comm_time += f + (b if include_backward else 0.0)
            per_node_cost[guid] = OpCost(0.0, 0.0, 0.0, 0)
            t = add_task(
                link(_collective_axis(node, mesh_sizes)), f, f"{node.name}.fwd"
            )
            bwd_comm[guid] = b
        else:
            cost = sparse_embedding_node_cost(graph, guid, node, cm)
            if cost is None:
                # a chain-measured head must not ALSO pay the isolated
                # kernel measurement it would immediately discard
                cost = cm.op_cost(
                    node, in_shapes, skip_measure=guid in chain_cost
                )
            if guid in chain_cost:
                # measured as one fused epilogue chain (the chain's
                # members are in fused_free)
                f, b = chain_cost[guid]
                cost = OpCost(f, b, 0.0, cost.memory)
            if guid in fused_free:
                cost = OpCost(0.0, 0.0, 0.0, cost.memory)
            elif guid in fused_half:
                cost = OpCost(
                    0.5 * cost.forward_time,
                    0.5 * cost.backward_time,
                    0.0,
                    cost.memory,
                )
            per_node_cost[guid] = cost
            total.compute_time += cost.forward_time
            if include_backward:
                total.compute_time += cost.backward_time
            act_bytes += sum(s.piece_bytes() for s in node.output_shapes)
            t = add_task(_CHIP, cost.forward_time, f"{node.name}.fwd")
        fwd_task[guid] = t
        for r in node.inputs:
            if r.guid in fwd_task:
                add_edge(fwd_task[r.guid], t)

    # ---- backward pass ------------------------------------------------------
    if include_backward:
        for guid in reversed(topo):
            node = graph.nodes[guid]
            if node.op_type == OperatorType.INPUT:
                continue
            if node.is_parallel_op:
                t = add_task(
                    link(_collective_axis(node, mesh_sizes)),
                    bwd_comm.get(guid, 0.0),
                    f"{node.name}.bwd",
                )
            else:
                t = add_task(
                    _CHIP, per_node_cost[guid].backward_time, f"{node.name}.bwd"
                )
            bwd_task[guid] = t
            add_edge(fwd_task[guid], t)  # bwd after own fwd
            for c in graph.consumers(guid):
                if c in bwd_task:
                    add_edge(bwd_task[c], t)

    # ---- gradient sync (per-weight all-reduce over replication group) -------
    # Grad all-reduces ride the data axis (axis 0): TP-sharded weights are
    # replicated over "data", DP-replicated weights reduce over it.
    for guid in topo:
        node = graph.nodes[guid]
        if not node.weight_shapes:
            continue
        t_sync = 0.0
        t_update = 0.0
        total_chips = 1
        for s in mesh_sizes:
            total_chips *= s
        sparse_rows = (
            _sparse_embedding_rows(graph, guid)
            if cm.sparse_embedding
            else None
        )
        sparse_group = (
            _sparse_rows_shard_group(graph, guid)
            if sparse_rows is not None
            else 1
        )
        # a weight another node owns is stored, reduced and updated once,
        # with its owner
        for w in node.stored_weight_shapes:
            weight_bytes += w.piece_bytes()
            if include_backward:
                if sparse_rows is not None:
                    # sparse fast path (Executor._sparse_embedding_guids):
                    # no table-sized gradient ever materializes — no
                    # table all-reduce, and the update walks only the
                    # touched rows (the measured 587x DLRM win)
                    t_update += cm.sparse_update_cost(
                        w, sparse_rows, optimizer_state_factor
                    )
                    # replicas must still see each other's touched rows:
                    # batch-sharded ids scattering into a shared table cost
                    # an all-gather of rows x dim over the id shards
                    sg = sparse_group
                    if sg > 1:
                        row_b = (
                            sparse_rows
                            * w.dims[-1].piece_size
                            * w.dtype.size_bytes
                        )
                        chips = (
                            range(total_chips)
                            if sg >= total_chips
                            else _axis_group_chips(0, sg, mesh_sizes)
                        )
                        t_sync += cm.sparse_sync_cost(row_b, sg, chips=chips)
                    continue
                g = _group_size(w, mesh_sizes)
                chips = (
                    range(total_chips)
                    if g >= total_chips
                    else _axis_group_chips(0, g, mesh_sizes)
                )
                t_sync += cm.all_reduce(cm.piece_bytes(w), g, chips=chips)
                t_update += cm.update_cost(w, optimizer_state_factor)
        t = None
        if include_backward and t_sync > 0:
            total.sync_time += t_sync
            t = add_task(link(0), t_sync, f"{node.name}.sync")
            add_edge(bwd_task.get(guid, fwd_task[guid]), t)
        if include_backward and t_update > 0:
            # the update consumes the synced grad: a chip-resource task
            # after both the bwd compute and the sync (reference: per-
            # parameter SGD/ADAM_UPD tasks, optimizer_kernel.cu:88)
            total.update_time += t_update
            tu = add_task(_CHIP, t_update, f"{node.name}.update")
            add_edge(bwd_task.get(guid, fwd_task[guid]), tu)
            if t is not None:
                add_edge(t, tu)

    total.weight_bytes = int(weight_bytes)
    total.memory_per_chip = int(weight_bytes * optimizer_state_factor + act_bytes)

    if export is not None:
        # per-node predicted compute costs keyed for the audit's
        # family grouping (cost_model.op_family); parallel ops carry
        # zero compute and are omitted — their traffic is the comm_time
        # aggregate above
        from flexflow_tpu.search.cost_model import op_family

        export["node_costs"] = [
            {
                "guid": guid,
                "name": graph.nodes[guid].name,
                "op": graph.nodes[guid].op_type.name,
                "family": op_family(graph.nodes[guid].op_type) or "other",
                "forward": per_node_cost[guid].forward_time,
                "backward": per_node_cost[guid].backward_time,
                "memory": per_node_cost[guid].memory,
            }
            for guid in topo
            if guid in per_node_cost
            and not graph.nodes[guid].is_parallel_op
        ]

    def _traced(result: GraphCost) -> GraphCost:
        if trace is not None:
            # scalars only — the GraphCost is rebuilt per candidate, but
            # the discipline (FX104) is uniform: no live state in rows
            trace.candidate(
                "graph_cost",
                name=trace_label or "estimate_graph_cost",
                step_time=result.step_time,
                compute_time=result.compute_time,
                comm_time=result.comm_time,
                sync_time=result.sync_time,
                update_time=result.update_time,
                memory_per_chip=float(result.memory_per_chip),
                feasible=bool(result.feasible(cm.spec)),
            )
        return result

    # the real train step is ONE XLA program and pays one program launch
    # — the same overhead CostModel.dispatch_floor measures and subtracts
    # per-op. Invisible for ms-scale steps; for DLRM-class us-scale steps
    # it IS most of the wall time (the round-5 rank gate read predicted
    # 4 us vs measured 26 us before this term). Applied in BOTH modes and
    # mirrored by every other step-time producer (auto._pipeline_candidate,
    # unity/mcmc totals) so cross-engine comparisons stay on one basis.
    step_floor = cm.dispatch_floor() if cm.measure else 0.0

    if not taskgraph:
        total.step_time = (
            total.compute_time
            + total.comm_time
            + total.sync_time
            + total.update_time
            + step_floor
        )
        return _traced(total)

    if export is not None:
        export.update(
            resource_of=list(resource_of),
            duration=list(duration),
            names=list(names),
            edges=list(edges),
            num_resources=num_resources,
        )

    from flexflow_tpu import native

    sim = native.simulate(resource_of, duration, edges, num_resources)
    if sim is None:  # malformed candidate graph — treat as analytic
        total.step_time = (
            total.compute_time
            + total.comm_time
            + total.sync_time
            + total.update_time
        )
    else:
        total.step_time = sim[0]
    total.step_time += step_floor
    return _traced(total)
