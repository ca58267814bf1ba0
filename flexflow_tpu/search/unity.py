"""Unity-style DP search over per-op MachineViews.

TPU rebuild of the reference's Unity dynamic-programming search
(reference: SearchHelper::graph_cost, src/runtime/graph.cc:1346-1431;
sequence/nonsequence splits graph.cc:93-306; machine-view enumeration
graph.cc:1783-1814; memoization by dp_state_hash graph.cc:1531-1543):

  * **sequence split**: find a bottleneck node (a node on every path from
    the subgraph's sources to its sink, located via immediate
    post-dominators like the reference's find_split_node,
    substitution.cc:1984); enumerate its valid machine views; recurse on
    the two halves with the bottleneck's view fixed at the boundary.
  * **nonsequence split**: no bottleneck ⇒ the subgraph is parallel
    branches; try running the branches concurrently on vertical /
    horizontal resource splits (reference: MachineResource::vertical(i)/
    horizontal(i), graph.cc:252-306) or sequentially on the full
    resources; take the min.
  * **leaf**: one node — roofline op cost on the view's shard + transfer
    cost for re-laying the producer's output onto this view + gradient
    all-reduce over the view's data replicas (the reference's NCCL
    allreduce term, optimizer_kernel.cu:88).
  * memoized by (subgraph, boundary views, resource block).

Views live on the abstract chip grid the way the reference's do
({start, dims, strides}); lowering restricts to mesh-expressible
assignments (SURVEY §7's documented v1 restriction): the per-node views
are reduced to one global (data × model) mesh and the tensor-parallel
rewrite sites whose ops the search gave a 2-D view. The full per-op view
map is still exported via --export-strategy for inspection, mirroring the
reference's per-op ParallelConfig strategy files (strategy.cc:100-197).
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from flexflow_tpu.core.machine import MachineResource, MachineSpec, MachineView
from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
from flexflow_tpu.core.pcg import PCGGraph, trace_embedding_ids_input
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.ops.registry import op_flops
from flexflow_tpu.search.cost_model import CostModel

# ops that may take a 2-D (data × channel) view: the second view dim
# partitions output channels / heads / embedding columns (reference:
# Linear::get_random_parallel_config explores exactly these grids,
# linear.cc:707-744; DLRM shards embedding tables, embedding.cc)
_CHANNEL_OPS = {
    OperatorType.LINEAR,
    OperatorType.MULTIHEAD_ATTENTION,
    OperatorType.EMBEDDING,
    OperatorType.CONV2D,
}


def _node_channel_size(node) -> Optional[int]:
    if node.op_type == OperatorType.LINEAR:
        return node.params.get("out_features")
    if node.op_type == OperatorType.MULTIHEAD_ATTENTION:
        return node.params.get("num_heads")
    if node.op_type == OperatorType.EMBEDDING:
        return node.params.get("out_dim")
    if node.op_type == OperatorType.CONV2D:
        return node.params.get("out_channels")
    return None


def _batch_size(node) -> int:
    shape = node.output_shapes[0] if node.output_shapes else None
    if shape is None:
        return 1
    logical = [d for d in shape.dims if not d.is_replica_dim]
    return logical[0].size if logical else 1


@dataclasses.dataclass(frozen=True)
class ViewOption:
    """A machine view plus its logical factorization: `dp` devices partition
    the sample dim, `ch` partition channels/heads (dp * ch == devices).
    The reference encodes this positionally in ParallelConfig.dim[]
    (machine_view.h:62-96); keeping it explicit avoids conflating the
    device geometry (node-major grid) with the tensor mapping."""

    view: MachineView
    dp: int
    ch: int = 1

    @property
    def num_devices(self) -> int:
        return self.view.num_devices

    def key(self) -> Tuple[int, int, int]:
        return (self.view.hash(), self.dp, self.ch)


@dataclasses.dataclass
class UnityResult:
    cost: float
    views: Dict[int, ViewOption]  # guid -> chosen option

    def describe(self) -> str:
        grids = Counter((v.dp, v.ch) for v in self.views.values())
        return (
            f"unity: simulated step {self.cost * 1e3:.3f} ms, "
            f"(dp, ch) grids {dict(grids)}"
        )


class UnitySearch:
    """One search instance per (graph, machine). Graph must have inferred
    output shapes (propagate_shapes) with NO strategy applied — views carry
    the parallelism."""

    def __init__(
        self,
        graph: PCGGraph,
        spec: MachineSpec,
        resource: Optional[MachineResource] = None,
        include_backward: bool = True,
        machine_model=None,
        mixed_precision: bool = False,
        measure: bool = False,
        calibration_file: str = "",
        sparse_embedding: bool = True,
        allow_subblock_views: bool = False,
        trace=None,
    ):
        """allow_subblock_views: let the nonsequence (parallel-branch)
        recursion place concurrent branches on vertical/horizontal
        resource SUB-blocks (reference: graph.cc:252-306). The v1
        lowering collapses every view to ONE global mesh, which executes
        branches sequentially — so with sub-block views on, the DP can
        return a cost predicated on a placement the executor cannot
        honor (the round-2 search-cost/lowering divergence for branchy
        graphs). Default OFF: the returned cost equals the simulated
        cost of the strategy actually lowered
        (tests/test_branchy_cost.py). Turn on only for search-space
        studies / strategy export. The executable primitive for
        concurrent branches exists (parallel/submesh.concurrent_branches
        — shard_map + lax.switch over a block axis, SPMD-restricted to
        shape-unified branches); wiring it into the PCG lowering is
        future work.

        trace: an optional telemetry.SearchTrace — every (node, view)
        leaf cost the DP evaluates is recorded once (tagged measured /
        analytic / sparse), plus the search phases and the winning
        per-op breakdown (the explain-report artifact)."""
        self.graph = graph
        self.trace = trace
        self._trace_seen = set()
        self.allow_subblock_views = allow_subblock_views
        self.spec = spec
        self.cm = CostModel(
            spec,
            machine_model=machine_model,
            mixed_precision=mixed_precision,
            measure=measure,
            calibration_file=calibration_file,
            sparse_embedding=sparse_embedding,
        )
        self.resource = resource or spec.resource()
        self.include_backward = include_backward
        self._memo: Dict[Tuple, Tuple[float, Dict[int, ViewOption]]] = {}
        self._views_cache: Dict[Tuple[int, Tuple], List[ViewOption]] = {}
        self._ubytes_cache: Dict[int, Tuple[float, bool]] = {}
        self.memo_hits = 0

    # -- view enumeration ----------------------------------------------------

    def _block_view(
        self, resource: MachineResource, n: int
    ) -> Optional[MachineView]:
        """n devices of the resource block in node-major order; None when n
        does not tile the block. Views never spill outside their block —
        MachineResource.is_valid_view holds by construction (the reference
        checks it per view, machine_view.h:51-60)."""
        cpn = resource.chips_per_node
        start = (
            resource.start_node_id * self.spec.chips_per_node
            + resource.start_chip_id
        )
        if n <= cpn:
            return MachineView(start, (n,), (1,))
        if n % cpn == 0 and n // cpn <= resource.num_nodes:
            return MachineView(
                start, (n // cpn, cpn), (self.spec.chips_per_node, 1)
            )
        return None

    def valid_views(
        self, guid: int, resource: MachineResource
    ) -> List[ViewOption]:
        """reference: get_valid_machine_views (graph.cc:503+) filtering
        register_all_machine_views; starts are canonicalized to the resource
        block's origin — TPU slices are symmetric, so shifted views cost the
        same and would only bloat the memo."""
        key = (
            guid,
            (resource.num_nodes, resource.chips_per_node, resource.start_chip_id,
             resource.start_node_id),
        )
        if key in self._views_cache:
            return self._views_cache[key]
        node = self.graph.nodes[guid]
        total = resource.num_chips
        batch = _batch_size(node)
        chan = _node_channel_size(node)
        views: List[ViewOption] = []
        for n in range(1, total + 1):
            if total % n != 0:
                continue
            mv = self._block_view(resource, n)
            if mv is None:
                continue
            if batch % n == 0:
                views.append(ViewOption(mv, dp=n, ch=1))
            if chan is not None and node.op_type in _CHANNEL_OPS:
                for dp in range(1, n + 1):
                    if n % dp != 0:
                        continue
                    ch = n // dp
                    if ch > 1 and batch % dp == 0 and chan % ch == 0:
                        views.append(ViewOption(mv, dp=dp, ch=ch))
        if not views:
            views.append(ViewOption(self._block_view(resource, 1), dp=1, ch=1))
        self._views_cache[key] = views
        return views

    # -- per-(node, view) costs ---------------------------------------------

    def _measured_times(
        self, node, in_shapes, opt: ViewOption
    ) -> Optional[Tuple[float, float]]:
        """(fwd, bwd) of the real jitted kernel on the shard this view
        gives one chip (reference: measure_operator_cost at the search's
        leaves, simulator.cc:532). dp shards the batch dim; ch shards
        Linear output channels exactly (params rewrite + re-infer) and MHA
        heads approximately (full-head shard measured, time / ch — head
        shards are the same matmuls at 1/ch width)."""
        from flexflow_tpu.ops.registry import infer_shapes
        from flexflow_tpu.search.cost_model import _MEASURED_OPS

        if node.op_type not in _MEASURED_OPS:
            return None
        try:
            shard_ins = []
            for s in in_shapes:
                sizes = list(s.logical_sizes)
                if opt.dp > 1:
                    if not sizes or sizes[0] % opt.dp != 0:
                        return None
                    sizes[0] //= opt.dp
                shard_ins.append(
                    ParallelTensorShape.make(sizes, s.dtype)
                )
            params = dict(node.params)
            divide = 1
            if opt.ch > 1:
                if (
                    node.op_type == OperatorType.LINEAR
                    and params.get("out_features", 0) % opt.ch == 0
                ):
                    params["out_features"] //= opt.ch
                elif (
                    node.op_type == OperatorType.EMBEDDING
                    and params.get("out_dim", 0) % opt.ch == 0
                ):
                    params["out_dim"] //= opt.ch
                elif (
                    node.op_type == OperatorType.CONV2D
                    and params.get("out_channels", 0) % opt.ch == 0
                ):
                    params["out_channels"] //= opt.ch
                else:
                    divide = opt.ch
            _, ws = infer_shapes(node.op_type, shard_ins, params)
            # corrected_times: the fitted family residual must divide
            # every raw measurement consumer, or unity/mcmc (and the
            # native DP LUT built from this) would rank cross-family
            # candidates with the bias the correction removes
            from flexflow_tpu.search.cost_model import shard_batch

            times = self.cm.corrected_times(
                node.op_type,
                self.cm.measured_times_floor_adjusted(
                    node.op_type, params, shard_ins, ws
                ),
                batch=shard_batch(shard_ins),
            )
            if times is None:
                return None
            return (times[0] / divide, times[1] / divide)
        except Exception:
            return None

    def _sparse_embedding_time(self, guid, node, opt):
        """Fwd(+bwd) seconds for a SPARSE-eligible embedding under `opt`,
        else None. The executor's fast path gathers/scatters touched rows
        only — neither the measured dense-grad kernel nor the table
        roofline applies (same basis as simulator.estimate_graph_cost and
        _update_bytes; the round-4 DLRM 490x finding). Shared by op_cost
        and the native-solver LUT builder so both engines price it
        identically."""
        if node.op_type != OperatorType.EMBEDDING or not node.weight_shapes:
            return None
        _ub, rows = self._update_bytes(guid)
        if rows is None:
            return None
        # rows shard over dp (batch), the row dim over ch: the rows x dim
        # product divides by dp*ch either way
        f, b = self.cm.sparse_embedding_op_cost(
            node.weight_shapes[0], rows / (opt.dp * opt.ch)
        )
        return f + (b if self.include_backward else 0.0)

    def op_cost(self, guid: int, opt: ViewOption) -> float:
        """Fwd(+bwd) seconds of the node's shard under `opt`: the real
        measured kernel when the cost model is in measured mode
        (reference: simulator.cc:532), the analytic roofline otherwise."""
        node = self.graph.nodes[guid]
        if node.op_type == OperatorType.INPUT or node.is_parallel_op:
            return 0.0
        n = opt.num_devices
        in_shapes = [self.graph.shape_of(r) for r in node.inputs]
        eb = self.cm.elem_bytes
        # sparse-eligible embeddings price compute analytically but FALL
        # THROUGH to the sync/update section below: the no-all-reduce and
        # touched-rows-update terms there (and in the native solver's
        # ubytes arrays) still apply
        t = self._sparse_embedding_time(guid, node, opt)
        source = "sparse" if t is not None else "analytic"
        if t is None and self.cm.measure:
            mt = self._measured_times(node, in_shapes, opt)
            if mt is not None:
                t = mt[0] + (mt[1] if self.include_backward else 0.0)
                source = "measured"
        if t is None:
            flops = op_flops(node.op_type, in_shapes, node.params) / n
            data = sum(s.volume() * eb(s) for s in in_shapes)
            data += sum(s.volume() * eb(s) for s in node.output_shapes)
            data += sum(s.volume() * eb(s) for s in node.weight_shapes)
            t = self.cm._roofline(flops, data / n)
            if self.include_backward:
                mxu = (
                    node.op_type in _CHANNEL_OPS
                    or node.op_type == OperatorType.BATCHMATMUL
                )
                t *= 3.0 if mxu else 2.0
        # gradient sync: weights are sharded ch ways and replicated across
        # the dp data replicas; all-reduce the shards over the actual device
        # ids of one replica group (ids are laid out (dp, ch) row-major, so
        # a group is every ch-th device — possibly crossing nodes)
        if self.include_backward and node.stored_weight_shapes:
            ub, sparse_rows = self._update_bytes(guid)
            group = opt.view.device_ids()[:: opt.ch]
            if sparse_rows is None:
                # the sparse fast path never materializes a table-sized
                # gradient, so eligible tables pay NO grad all-reduce —
                # matching simulator.estimate_graph_cost's basis exactly
                w_bytes = (
                    sum(s.volume() * eb(s) for s in node.weight_shapes)
                    / opt.ch
                )
                t += self.cm.all_reduce(w_bytes, opt.dp, chips=group)
            else:
                # the dp replicas must still exchange touched rows
                # (batch-sharded ids scatter into a shared table): an
                # all-gather of rows x dim over the dp group
                t += self.cm.sparse_sync_cost(
                    ub / (opt.dp * opt.ch), opt.dp, chips=group
                )
            # optimizer update traffic (CostModel.update_time_from_bytes,
            # the same formula/basis as estimate_graph_cost): without it
            # the engines' absolute step times are not comparable to the
            # mesh candidates and weight-heavy dp looks free
            per_chip = ub / opt.ch / (opt.dp if sparse_rows is not None else 1)
            t += self.cm.update_time_from_bytes(per_chip)
        if self.trace is not None:
            self._trace_leaf("op_view", guid, opt, t, source)
        return t

    def _trace_leaf(
        self, kind: str, guid: int, opt: ViewOption, cost: float, source: str
    ) -> None:
        """Record one (node, view) leaf evaluation — once per key (the
        memoless DP re-evaluates leaves constantly). Only precomputed
        scalars cross into the record: trace rows must never hold live
        graph/search state (fxlint FX104)."""
        key = (kind, guid, opt.key())
        if key in self._trace_seen:
            return
        self._trace_seen.add(key)
        node = self.graph.nodes[guid]
        op_name = node.name
        op_type = node.op_type.name
        self.trace.candidate(
            kind,
            source=source,
            guid=guid,
            name=op_name,
            op=op_type,
            dp=opt.dp,
            ch=opt.ch,
            cost=cost,
        )

    def _trace_result(self, result: "UnityResult", path_kind: str) -> None:
        """Record the winning strategy with its per-op breakdown. The
        residual (DP concurrency credit, dispatch floor) is defined as
        total minus the in-order breakdown sum, so the explain report
        reconstructs `result.cost` exactly by inverting the
        subtraction."""
        ops = []
        listed = 0.0
        for guid in sorted(result.views):
            node = self.graph.nodes.get(guid)
            if node is None:
                continue
            v = result.views[guid]
            oc = self.op_cost(guid, v)
            xc = 0.0
            for r in node.inputs:
                src = result.views.get(r.guid)
                if src is not None:
                    xc += self.xfer_cost(r, src, v)
            op_name = node.name
            op_type = node.op_type.name
            ops.append(
                {
                    "guid": guid,
                    "name": op_name,
                    "op": op_type,
                    "dp": v.dp,
                    "ch": v.ch,
                    "op_cost": oc,
                    "xfer_cost": xc,
                }
            )
            listed += oc + xc
        grids = Counter((v.dp, v.ch) for v in result.views.values())
        self.trace.result(
            total_cost=result.cost,
            ops=ops,
            residual=result.cost - listed,
            path=path_kind,
            grids={f"dp{d}xch{c}": n for (d, c), n in sorted(grids.items())},
        )

    def _update_bytes(self, guid: int) -> Tuple[float, Optional[float]]:
        """(bytes basis, touched rows | None) for the optimizer-update
        term: full MASTER-precision weight bytes normally (optimizer state
        is f32 under mixed precision — matching CostModel.update_cost's
        piece_bytes basis); touched-rows bytes for tables on the sparse
        fast path (core.pcg.trace_embedding_ids_input — rows follow the
        batch sharding, hence the dp division). The row count rides along
        so consumers never invert the byte formula (ADVICE r4: one
        formula, not a formula and its hand-written inverse). Per-guid
        constant, cached."""
        hit = self._ubytes_cache.get(guid)
        if hit is not None:
            return hit
        node = self.graph.nodes[guid]
        out: Tuple[float, Optional[float]]
        ref = (
            trace_embedding_ids_input(self.graph, guid)
            if self.cm.sparse_embedding
            else None
        )
        if ref is not None:
            ids_shape = self.graph.shape_of(ref)
            w = node.weight_shapes[0]
            rows = float(ids_shape.volume())
            out = (
                rows * w.dims[-1].size * w.dtype.size_bytes,
                rows,
            )
        else:
            out = (
                float(
                    sum(
                        s.volume() * s.dtype.size_bytes
                        for s in node.weight_shapes
                    )
                ),
                None,
            )
        self._ubytes_cache[guid] = out
        return out

    def xfer_cost(self, ref, src: ViewOption, dst: ViewOption) -> float:
        """Re-layout cost of one tensor between views (reference:
        estimate_xfer_cost, graph.cc:1291 → simulator.cc:617)."""
        if src.key() == dst.key():
            return 0.0
        shape = self.graph.shape_of(ref)
        bytes_total = shape.volume() * self.cm.elem_bytes(shape)
        n = max(src.num_devices, dst.num_devices)
        return self.cm.all_to_all(bytes_total / dst.num_devices, n)

    # -- the DP ---------------------------------------------------------------

    def optimize(self) -> UnityResult:
        """Full-graph entry: enumerate sink views, run the DP
        (reference: Graph::optimal_cost, graph.cc:1433). Single-sink
        graphs on the flat machine model run the NATIVE C++ solver
        (native/src/unity_dp.cc — SURVEY §7's prescription that the
        compute-bound tree search be native); everything else uses the
        Python recursion with identical semantics."""
        result, path_kind = self._optimize_inner()
        if self.cm.measure:
            # one program launch per step — the same basis term
            # estimate_graph_cost adds, so the cross-engine gate in
            # auto.search_strategy compares like with like
            result = UnityResult(
                result.cost + self.cm.dispatch_floor(), result.views
            )
        if self.trace is not None:
            self._trace_result(result, path_kind)
        return result

    def _optimize_inner(self) -> Tuple[UnityResult, str]:
        from contextlib import nullcontext

        from flexflow_tpu import native as native_mod

        def _phase(name):
            return (
                self.trace.phase(name)
                if self.trace is not None
                else nullcontext()
            )

        sinks = self.graph.sinks()
        if (
            len(sinks) == 1
            and self.cm.machine_model is None
            and self.include_backward
            # guard BEFORE the per-node extraction pass: without the
            # library (or past the 256-node bitset cap) the pass would be
            # wasted and redone by the Python path
            and len(self.graph.nodes) <= 256
            and native_mod.get_lib() is not None
        ):
            # measured mode pre-resolves every (node, view) leaf cost with
            # the real calibrated kernels, then hands the table to the
            # native solver — the calibration table and the 33x native
            # solver compose (VERDICT r2 item 9)
            with _phase("unity:measured_lut" if self.cm.measure
                        else "unity:native_prep"):
                lut = self._measured_lut() if self.cm.measure else None
            with _phase("unity:native_dp"):
                native_result = self._optimize_native(sinks[0], measured=lut)
            if native_result is not None:
                return native_result, "native"
        with _phase("unity:python_dp"):
            return self._optimize_python(sinks), "python"

    def _measured_lut(self):
        """{guid: [(dp, ch, fwd+bwd seconds)]} for every node/view the
        solver can choose, from the calibrated kernel measurements
        (reference: simulator.cc:532 measured leaves). Entries that fail
        to measure fall back to the native roofline (absent from the
        LUT)."""
        lut = {}
        full = self.resource
        for guid in self.graph.topo_order():
            node = self.graph.nodes[guid]
            if node.op_type == OperatorType.INPUT or node.is_parallel_op:
                continue
            in_shapes = [self.graph.shape_of(r) for r in node.inputs]
            entries = []
            for opt in self.valid_views(guid, full):
                st = self._sparse_embedding_time(guid, node, opt)
                if st is not None:
                    entries.append((opt.dp, opt.ch, st))
                    if self.trace is not None:
                        self._trace_leaf("lut_entry", guid, opt, st, "sparse")
                    continue
                mt = self._measured_times(node, in_shapes, opt)
                if mt is None:
                    continue
                cost = mt[0] + (mt[1] if self.include_backward else 0.0)
                entries.append((opt.dp, opt.ch, cost))
                if self.trace is not None:
                    self._trace_leaf("lut_entry", guid, opt, cost, "measured")
            if entries:
                lut[guid] = entries
        return lut

    def _optimize_native(
        self, sink: int, measured=None
    ) -> Optional[UnityResult]:
        from flexflow_tpu import native
        from flexflow_tpu.search.cost_model import (
            _DEFAULT_EFFICIENCY as EFF,
            _ICI_LATENCY_S as LAT,
        )

        guids = sorted(self.graph.nodes)
        index = {g: i for i, g in enumerate(guids)}
        batch, chan, flops, bytes_moved, wbytes, bwd = [], [], [], [], [], []
        ubytes, u_dp_scaled, sbytes = [], [], []
        edges = []
        eb = self.cm.elem_bytes  # byte counts reach the solver pre-scaled,
        # so the native path is dtype/mixed-precision aware for free and the
        # Python↔native bit-equivalence is preserved by construction
        for g in guids:
            node = self.graph.nodes[g]
            batch.append(_batch_size(node))
            is_chan = node.op_type in _CHANNEL_OPS
            chan.append(_node_channel_size(node) or -1 if is_chan else -1)
            in_shapes = [self.graph.shape_of(r) for r in node.inputs]
            if node.op_type == OperatorType.INPUT or node.is_parallel_op:
                flops.append(0.0)
                bytes_moved.append(0.0)
                wbytes.append(0.0)
                bwd.append(0.0)
                ubytes.append(0.0)
                u_dp_scaled.append(0)
                sbytes.append(0.0)
            else:
                flops.append(op_flops(node.op_type, in_shapes, node.params))
                data = sum(s.volume() * eb(s) for s in in_shapes)
                data += sum(s.volume() * eb(s) for s in node.output_shapes)
                data += sum(s.volume() * eb(s) for s in node.weight_shapes)
                bytes_moved.append(data)
                mxu = is_chan or node.op_type in (
                    OperatorType.CONV2D,
                    OperatorType.BATCHMATMUL,
                )
                bwd.append(3.0 if mxu else 2.0)
                if node.stored_weight_shapes:
                    ub, sparse_rows = self._update_bytes(g)
                    sparse = sparse_rows is not None
                    ubytes.append(ub)
                    u_dp_scaled.append(1 if sparse else 0)
                    # sparse-eligible tables never materialize a grad:
                    # no all-reduce term (wbytes drives sync in the
                    # native op_cost, unity_dp.cc) — but the dp replicas
                    # all-gather the touched rows (sbytes, same term as
                    # op_cost's sparse_sync_cost)
                    wbytes.append(
                        0.0
                        if sparse
                        else sum(
                            s.volume() * eb(s) for s in node.weight_shapes
                        )
                    )
                    sbytes.append(ub if sparse else 0.0)
                else:
                    ubytes.append(0.0)
                    u_dp_scaled.append(0)
                    wbytes.append(0.0)
                    sbytes.append(0.0)
            for r in node.inputs:
                if r.guid in index:
                    shape = self.graph.shape_of(r)
                    edges.append(
                        (
                            index[r.guid],
                            index[g],
                            shape.volume() * eb(shape),
                        )
                    )
        out = native.unity_dp(
            edges,
            batch,
            chan,
            flops,
            bytes_moved,
            wbytes,
            bwd,
            self.resource.num_nodes,
            self.resource.chips_per_node,
            self.spec.peak_tflops * 1e12 * EFF,
            self.spec.hbm_gbps * 1e9 * EFF,
            self.spec.ici_gbps * 1e9 * EFF,
            LAT,
            index[sink],
            ubytes=ubytes,
            u_dp_scaled=u_dp_scaled,
            sbytes=sbytes,
            update_factor=self.cm.update_traffic_factor(),
            allow_subblock=self.allow_subblock_views,
            measured=[
                (index[g], dp, ch, cost)
                for g, entries in (measured or {}).items()
                for dp, ch, cost in entries
            ],
        )
        if out is None:
            return None
        cost, dps, chs = out
        views: Dict[int, ViewOption] = {}
        for g, dp, ch in zip(guids, dps, chs):
            n = dp * ch
            # canonical full-resource geometry; a count chosen on a
            # concurrent sub-block may not tile the full block — fall back
            # to a plain 1-D strided view (placement detail is dropped; the
            # (dp, ch) factorization, which lowering consumes, is exact)
            mv = self._block_view(self.resource, n) or MachineView(
                0, (n,), (1,)
            )
            views[g] = ViewOption(mv, dp=dp, ch=ch)
        return UnityResult(cost, views)

    def _optimize_python(self, sinks) -> UnityResult:
        if len(sinks) != 1:
            # multiple sinks (rare; metrics heads): cost the largest
            # subgraph first, then only each later sink's EXCLUSIVE nodes —
            # shared-trunk nodes keep their first assignment and are not
            # double-counted. Boundary transfers from the trunk into the
            # exclusive tail are not charged (documented approximation).
            anc_of = {
                s: set(self.graph.ancestors_of([s])) for s in sinks
            }  # ancestors_of includes the start node itself
            order = sorted(sinks, key=lambda s: len(anc_of[s]), reverse=True)
            views: Dict[int, ViewOption] = {}
            total = 0.0
            covered: set = set()
            for s in order:
                anc = anc_of[s]
                # a sink is nobody's ancestor, so s is always in `exclusive`
                exclusive = frozenset(anc - covered)
                best = None
                for view in self.valid_views(s, self.resource):
                    c, v = self._graph_cost(
                        exclusive, None, s, view, self.resource
                    )
                    if best is None or c < best[0]:
                        best = (c, {**v, s: view})
                total += best[0]
                for g, v in best[1].items():
                    views.setdefault(g, v)
                covered |= anc
            return UnityResult(total, views)
        return self._best_for_sink(sinks[0])

    def _best_for_sink(self, sink: int) -> UnityResult:
        sub = frozenset(self.graph.ancestors_of([sink])) | {sink}
        best: Optional[Tuple[float, Dict[int, ViewOption]]] = None
        for view in self.valid_views(sink, self.resource):
            c, v = self._graph_cost(sub, None, sink, view, self.resource)
            if best is None or c < best[0]:
                best = (c, {**v, sink: view})
        assert best is not None
        return UnityResult(best[0], best[1])

    def _res_key(self, r: MachineResource):
        return (r.num_nodes, r.chips_per_node, r.start_node_id, r.start_chip_id)

    def _graph_cost(
        self,
        sub: FrozenSet[int],
        src_pair: Optional[Tuple[int, ViewOption]],
        sink: int,
        sink_view: ViewOption,
        resource: MachineResource,
    ) -> Tuple[float, Dict[int, ViewOption]]:
        """Cost of executing `sub` (sink included, its view fixed) given the
        producer boundary `src_pair`; returns (seconds, views of sub\\{sink}).

        reference: SearchHelper::graph_cost (graph.cc:1346-1431), memoized
        by the analog of dp_state_hash (graph.cc:1531-1543)."""
        key = (
            sub,
            src_pair[0] if src_pair else -1,
            src_pair[1].key() if src_pair else 0,
            sink,
            sink_view.key(),
            self._res_key(resource),
        )
        if key in self._memo:
            self.memo_hits += 1
            return self._memo[key]

        interior = sub - {sink}
        if not interior:
            cost = self.op_cost(sink, sink_view)
            node = self.graph.nodes[sink]
            for r in node.inputs:
                if src_pair is not None and r.guid == src_pair[0]:
                    cost += self.xfer_cost(r, src_pair[1], sink_view)
            out = (cost, {})
            self._memo[key] = out
            return out

        b = self._find_bottleneck(sub, sink, src_pair)
        if b is not None:
            pre = (
                frozenset(g for g in self.graph.ancestors_of([b]) if g in sub)
                | {b}
            )
            post = sub - pre
            best: Optional[Tuple[float, Dict[int, ViewOption]]] = None
            for view in self.valid_views(b, resource):
                c1, v1 = self._graph_cost(pre, src_pair, b, view, resource)
                c2, v2 = self._graph_cost(
                    post | {sink}, (b, view), sink, sink_view, resource
                )
                c = c1 + c2
                if best is None or c < best[0]:
                    best = (c, {**v1, **v2, b: view})
            self._memo[key] = best
            return best

        out = self._nonsequence_cost(sub, src_pair, sink, sink_view, resource)
        self._memo[key] = out
        return out

    def _find_bottleneck(
        self, sub, sink, src_pair
    ) -> Optional[int]:
        """An interior node on every source→sink path within `sub`
        (reference: find_split_node via imm post-dominators,
        substitution.cc:1984)."""
        from flexflow_tpu import native

        nodes = sorted(sub)
        index = {g: i for i, g in enumerate(nodes)}
        edges = []
        for g in nodes:
            for r in self.graph.nodes[g].inputs:
                if r.guid in index:
                    edges.append((index[r.guid], index[g]))
        # virtual source feeding all sub-sources keeps ipdom rooted
        n = len(nodes)
        srcs = [
            i
            for i, g in enumerate(nodes)
            if not any(r.guid in index for r in self.graph.nodes[g].inputs)
        ]
        vs = n
        for i in srcs:
            edges.append((vs, i))
        ipdom = native.imm_post_dominators(n + 1, edges)
        if ipdom is None:
            return None
        # walk the ipdom chain from the virtual source toward the sink; the
        # first interior node on it post-dominates every source
        cur = ipdom[vs]
        while cur is not None and cur >= 0 and cur < n:
            g = nodes[cur]
            if g != sink:
                return g
            cur = ipdom[cur] if ipdom[cur] != cur else -1
        return None

    def _branches(self, sub, sink) -> List[FrozenSet[int]]:
        """Weakly-connected components of sub\\{sink}."""
        rest = set(sub) - {sink}
        comps = []
        while rest:
            seed = min(rest)  # deterministic (matches the native solver)
            comp = {seed}
            frontier = [seed]
            while frontier:
                g = frontier.pop()
                nbrs = [
                    r.guid
                    for r in self.graph.nodes[g].inputs
                    if r.guid in rest
                ]
                nbrs += [c for c in self.graph.consumers(g) if c in rest]
                for nb in nbrs:
                    if nb not in comp:
                        comp.add(nb)
                        frontier.append(nb)
            comps.append(frozenset(comp))
            rest -= comp
        return comps

    def _branch_cost(
        self, branch: FrozenSet[int], src_pair, sink, sink_view, resource
    ) -> Tuple[float, Dict[int, ViewOption]]:
        """Cost of one parallel branch: its terminal's view is enumerated,
        with the transfer onto the (already fixed) sink view charged here."""
        terms = [
            g
            for g in branch
            if not any(c in branch for c in self.graph.consumers(g))
        ]
        if len(terms) != 1:
            return self._multi_terminal_cost(
                branch, src_pair, sink, sink_view, resource
            )
        term = terms[0]
        best: Optional[Tuple[float, Dict[int, ViewOption]]] = None
        for view in self.valid_views(term, resource):
            c, v = self._graph_cost(branch, src_pair, term, view, resource)
            for r in self.graph.nodes[sink].inputs:
                if r.guid == term:
                    c += self.xfer_cost(r, view, sink_view)
            if best is None or c < best[0]:
                best = (c, {**v, term: view})
        return best

    # product cap for the exact multi-terminal solve; beyond it the greedy
    # topological pass runs instead (mirrored by native/src/unity_dp.cc)
    _MT_EXACT_CAP = 4096

    def _branch_topo_order(self, branch: FrozenSet[int]) -> List[int]:
        """Topological order within the branch, smallest guid first.
        Builder guids are already topological, but substitution rewrites
        wire fresh higher-guid producers into existing lower-guid
        consumers, so Kahn it is. Mirrored by the native solver's
        multi_terminal_cost (same smallest-first tie-break)."""
        indeg = {
            g: sum(
                1 for r in self.graph.nodes[g].inputs if r.guid in branch
            )
            for g in branch
        }
        remaining = set(branch)
        order: List[int] = []
        while remaining:
            ready = [g for g in remaining if indeg[g] == 0]
            if not ready:  # cycle (impossible in a PCG): keep guid order
                return sorted(branch)
            g = min(ready)
            order.append(g)
            remaining.remove(g)
            for c in remaining:
                indeg[c] -= sum(
                    1 for r in self.graph.nodes[c].inputs if r.guid == g
                )
        return order

    def _multi_terminal_cost(
        self, branch: FrozenSet[int], src_pair, sink, sink_view, resource
    ) -> Tuple[float, Dict[int, ViewOption]]:
        """Multi-terminal branch (no single node post-dominates it): assign
        views over the whole branch JOINTLY, charging intra-branch
        transfers, the producer boundary, and every terminal→sink transfer.
        Small branches are solved exactly (view-set product ≤ _MT_EXACT_CAP);
        larger ones greedily in topological order, each node taking the view
        minimizing its op cost plus transfers from already-assigned
        producers. Replaces the round-1 independent-minima fallback that
        charged no transfers at all and underestimated real branch costs 2×+
        (bounded by tests/test_unity_exhaustive.py)."""
        import itertools

        order = self._branch_topo_order(branch)
        pos = {g: k for k, g in enumerate(order)}
        opts = [self.valid_views(g, resource) for g in order]
        nk = len(order)

        # cost tables: per-(node, view) op costs; per-edge view-pair
        # transfer tables (producer is always earlier: order is topological)
        opc = [
            [self.op_cost(g, v) for v in cands]
            for g, cands in zip(order, opts)
        ]
        intra = []  # (ks, kd, table[src_view_idx][dst_view_idx])
        src_edges = []  # (kd, cost per dst view) from the fixed src boundary
        for kd, g in enumerate(order):
            for r in self.graph.nodes[g].inputs:
                if r.guid in pos:
                    ks = pos[r.guid]
                    intra.append(
                        (
                            ks,
                            kd,
                            [
                                [self.xfer_cost(r, vs, vd) for vd in opts[kd]]
                                for vs in opts[ks]
                            ],
                        )
                    )
                elif src_pair is not None and r.guid == src_pair[0]:
                    src_edges.append(
                        (
                            kd,
                            [
                                self.xfer_cost(r, src_pair[1], vd)
                                for vd in opts[kd]
                            ],
                        )
                    )
        sink_edges = []  # (ks, cost per src view) onto the fixed sink view
        for r in self.graph.nodes[sink].inputs:
            if r.guid in pos:
                ks = pos[r.guid]
                sink_edges.append(
                    (ks, [self.xfer_cost(r, v, sink_view) for v in opts[ks]])
                )

        def total_cost(idx) -> float:
            c = 0.0
            for k in range(nk):
                c += opc[k][idx[k]]
            for ks, kd, table in intra:
                c += table[idx[ks]][idx[kd]]
            for kd, costs in src_edges:
                c += costs[idx[kd]]
            for ks, costs in sink_edges:
                c += costs[idx[ks]]
            return c

        n_combos = 1
        for o in opts:
            n_combos *= len(o)
        if n_combos <= self._MT_EXACT_CAP:
            best = None
            for idx in itertools.product(*(range(len(o)) for o in opts)):
                c = total_cost(idx)
                if best is None or c < best[0]:
                    best = (c, idx)
            return best[0], {
                g: opts[k][best[1][k]] for k, g in enumerate(order)
            }

        idx: List[int] = []
        for k in range(nk):
            best_j = None
            for j in range(len(opts[k])):
                c = opc[k][j]
                for ks, kd, table in intra:
                    if kd == k:
                        c += table[idx[ks]][j]
                for kd, costs in src_edges:
                    if kd == k:
                        c += costs[j]
                for ks, costs in sink_edges:
                    if ks == k:
                        c += costs[j]
                if best_j is None or c < best_j[0]:
                    best_j = (c, j)
            idx.append(best_j[1])
        return total_cost(idx), {
            g: opts[k][idx[k]] for k, g in enumerate(order)
        }

    def _nonsequence_cost(
        self, sub, src_pair, sink, sink_view, resource
    ) -> Tuple[float, Dict[int, ViewOption]]:
        """No bottleneck ⇒ parallel branches. Try concurrent execution on
        vertical/horizontal resource splits and sequential on full resources
        (reference: find_optimal_nonsequence_graph_time, graph.cc:252-306)."""
        branches = self._branches(sub, sink)
        sink_cost = self.op_cost(sink, sink_view)
        if src_pair is not None:
            for r in self.graph.nodes[sink].inputs:
                if r.guid == src_pair[0]:
                    sink_cost += self.xfer_cost(r, src_pair[1], sink_view)

        # sequential: every branch gets the full resource block, times add
        seq_total = sink_cost
        seq_views: Dict[int, MachineView] = {}
        per_branch = []
        for br in branches:
            c, v = self._branch_cost(br, src_pair, sink, sink_view, resource)
            per_branch.append((br, c, v))
            seq_total += c
            seq_views.update(v)
        best = (seq_total, seq_views)

        # concurrent two-way: branches bundled into {first} vs {rest} on a
        # resource split (the reference enumerates subset splits the same
        # greedy way). Gated: the one-mesh lowering executes branches
        # sequentially, so costing sub-block concurrency would diverge
        # from the executable strategy (ctor docstring).
        if self.allow_subblock_views and len(branches) >= 2:
            first = per_branch[0][0]
            rest = [b for b, _, _ in per_branch[1:]]
            splits: List[Tuple[MachineResource, MachineResource]] = []
            for i in range(1, resource.num_nodes):
                splits.append(resource.vertical_split(i))
            for i in range(1, resource.chips_per_node):
                splits.append(resource.horizontal_split(i))
            for r1, r2 in splits:
                c1, v1 = self._branch_cost(first, src_pair, sink, sink_view, r1)
                c2 = 0.0
                v2: Dict[int, ViewOption] = {}
                for br in rest:
                    c, v = self._branch_cost(br, src_pair, sink, sink_view, r2)
                    c2 += c
                    v2.update(v)
                c = max(c1, c2) + sink_cost
                if c < best[0]:
                    best = (c, {**v1, **v2})
        return best


# -- lowering to an executable Strategy --------------------------------------


def result_to_strategy(
    result: UnityResult, graph: PCGGraph, num_devices: int, engine: str = "unity"
):
    """Reduce the per-op view map to one global mesh + TP rewrite sites
    (SURVEY §7's v1 restriction — per-op device subsets beyond one mesh are
    exported but not lowered).

    When the search's views are HETEROGENEOUS — some compute ops sharded
    on channels while others keep a wider pure-data-parallel view than the
    uniform (data = devices/tp) mesh would grant them — the lowering goes
    through `mixed_site_strategy`: full-width batch sharding outside the
    TP sites, matching what the DP search actually costed per node
    (reference: per-op MachineViews, graph.cc:1346-1431)."""
    from flexflow_tpu.parallel.strategy import (
        mixed_site_strategy,
        site_strategy,
    )
    from flexflow_tpu.search.rewrites import find_tp_sites

    channel = [v for v in result.views.values() if v.ch > 1]
    tp = Counter(v.ch for v in channel).most_common(1)[0][0] if channel else 1
    tp = max(1, min(tp, num_devices))
    while num_devices % tp != 0:
        tp -= 1

    tp_guids = {g for g, v in result.views.items() if v.ch == tp and v.ch > 1}
    sites = [
        s
        for s in find_tp_sites(graph)
        if (set(s.guids) & tp_guids) and s.divisible_by(graph, tp)
    ] if tp > 1 else []
    prefix = f"{engine}(step {result.cost * 1e3:.3f} ms)"
    uniform_dp = max(1, num_devices // tp)
    site_guids = {g for s in sites for g in s.guids}
    wants_full_dp = tp > 1 and any(
        v.ch == 1 and v.dp > uniform_dp
        for g, v in result.views.items()
        if g in graph.nodes
        and g not in site_guids
        and graph.nodes[g].op_type != OperatorType.INPUT
        and not graph.nodes[g].is_parallel_op
    )
    if wants_full_dp:
        return mixed_site_strategy(
            graph, num_devices, tp, sites, name_prefix=prefix
        )
    return site_strategy(
        graph, num_devices, tp, sites, name_prefix=prefix
    )


def save_views(
    result: UnityResult, graph: PCGGraph, path: str, engine: str = "unity"
):
    """Per-op view export (reference: save_strategies_to_file,
    strategy.cc:156 — per-op ParallelConfig maps)."""
    import json

    doc = {
        "version": 1,
        "engine": engine,
        "simulated_step_ms": result.cost * 1e3,
        "ops": {
            graph.nodes[g].name: {
                "start_device_id": v.view.start_device_id,
                "dims": list(v.view.dims),
                "strides": list(v.view.strides),
                "dp": v.dp,
                "ch": v.ch,
            }
            for g, v in sorted(result.views.items())
            if g in graph.nodes
        },
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
