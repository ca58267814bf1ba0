"""Parallel Computation Graph (PCG).

The IR everything else operates on: the builder produces it, the substitution
engine rewrites it, the Unity DP search assigns MachineViews to its nodes, and
the executor lowers it to a jitted XLA program with GSPMD shardings.

Re-design of the reference's PCG (reference: include/flexflow/graph.h:245,
src/runtime/graph.cc) — same concepts (nodes = operators, edges carry tensor
indices, order-independent graph hash for search memoization,
split-at-bottleneck helpers), but a pure-data immutable-ish Python IR rather
than Legion-coupled C++ objects.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from flexflow_tpu.core.machine import MachineView
from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
from flexflow_tpu.core.types import OperatorType, PARALLEL_OP_TYPES


@dataclasses.dataclass(frozen=True)
class TensorRef:
    """A reference to output `out_idx` of node `guid`."""

    guid: int
    out_idx: int = 0


@dataclasses.dataclass
class PCGNode:
    """One operator node.

    params holds the op's static attributes (out_features, strides, activation,
    …) — the equivalent of the reference's per-op `Params` structs used for
    hashing/caching (SURVEY §2.2). weight_shapes lists this op's parameter
    tensors (reference: Op::weights).
    """

    guid: int
    op_type: OperatorType
    name: str
    inputs: Tuple[TensorRef, ...]
    params: Dict[str, object]
    output_shapes: Tuple[ParallelTensorShape, ...]
    weight_shapes: Tuple[ParallelTensorShape, ...] = ()
    machine_view: Optional[MachineView] = None

    @property
    def is_parallel_op(self) -> bool:
        return self.op_type in PARALLEL_OP_TYPES

    @property
    def num_outputs(self) -> int:
        return len(self.output_shapes)

    @property
    def weight_key(self) -> object:
        """The node's identity across graph rewrites, which re-make nodes
        under new guids: the builder's name, or the `weight_key` a rewrite
        stamped on its replacement (substitution.py:_dst_params). What
        weights are carried over by, and what a borrower names its owner by."""
        return self.params.get("weight_key", self.name)

    @property
    def stored_weight_shapes(self) -> Tuple[ParallelTensorShape, ...]:
        """The weights this node keeps in memory: none where it applies
        another node's (`params['weights_of']`, FFModel's `weights_of=`).
        `weight_shapes` is what it reads either way."""
        return () if "weights_of" in self.params else self.weight_shapes

    def params_hash(self) -> int:
        """Hash of (op_type, params) — keys the op-cost cache
        (reference: simulator.cc:532-572 keyed by OperatorParameters)."""
        items = tuple(sorted((k, repr(v)) for k, v in self.params.items()))
        return hash((self.op_type, items))


class PCGGraph:
    """Mutable DAG of PCGNodes.

    Edges are implicit in each node's `inputs` tuple; consumer maps are
    maintained for reverse traversal (reference keeps in/out edge multimaps,
    graph.h:245+).
    """

    def __init__(self):
        self.nodes: Dict[int, PCGNode] = {}
        self._next_guid = 100  # reference starts op guids at a magic base
        self._consumers: Dict[int, Set[int]] = defaultdict(set)

    # -- construction --------------------------------------------------------

    def fresh_guid(self) -> int:
        g = self._next_guid
        self._next_guid += 1
        return g

    def add_node(
        self,
        op_type: OperatorType,
        name: str,
        inputs: Sequence[TensorRef],
        params: Dict[str, object],
        output_shapes: Sequence[ParallelTensorShape],
        weight_shapes: Sequence[ParallelTensorShape] = (),
        guid: Optional[int] = None,
    ) -> PCGNode:
        guid = self.fresh_guid() if guid is None else guid
        node = PCGNode(
            guid=guid,
            op_type=op_type,
            name=name,
            inputs=tuple(inputs),
            params=dict(params),
            output_shapes=tuple(output_shapes),
            weight_shapes=tuple(weight_shapes),
        )
        self.nodes[guid] = node
        for ref in node.inputs:
            self._consumers[ref.guid].add(guid)
        return node

    def weight_owners(self) -> Dict[int, int]:
        """{borrower guid: owner guid} for every node that applies another
        node's weights. `params['weights_of']` names the owner by its
        stable identity (`PCGNode.weight_key`), so the tie survives a rewrite
        that re-makes either node under a new guid. Raises where the owner
        is gone or not unique, or where a strategy gave the two different
        weight shardings."""
        borrowers = [n for n in self.nodes.values() if "weights_of" in n.params]
        if not borrowers:
            return {}
        by_key: Dict[object, List[PCGNode]] = defaultdict(list)
        for n in self.nodes.values():
            if n.stored_weight_shapes:
                by_key[n.weight_key].append(n)
        out = {}
        for n in borrowers:
            owners = by_key.get(n.params["weights_of"], [])
            if len(owners) != 1:
                raise ValueError(
                    f"node '{n.name}' applies the weights of "
                    f"'{n.params['weights_of']}', which {len(owners)} nodes "
                    "of the graph own"
                )
            if owners[0].weight_shapes != n.weight_shapes:
                raise ValueError(
                    f"node '{n.name}' and '{owners[0].name}', whose weights "
                    "it applies, need one weight sharding: "
                    f"{[str(s) for s in n.weight_shapes]} against "
                    f"{[str(s) for s in owners[0].weight_shapes]}"
                )
            out[n.guid] = owners[0].guid
        return out

    def remove_node(self, guid: int):
        node = self.nodes.pop(guid)
        for ref in node.inputs:
            self._consumers[ref.guid].discard(guid)
        self._consumers.pop(guid, None)

    def replace_input(self, guid: int, old: TensorRef, new: TensorRef):
        node = self.nodes[guid]
        new_inputs = tuple(new if r == old else r for r in node.inputs)
        if new_inputs != node.inputs:
            self._consumers[old.guid].discard(guid)
            self._consumers[new.guid].add(guid)
            node.inputs = new_inputs

    def rebuild_consumers(self):
        self._consumers = defaultdict(set)
        for g, node in self.nodes.items():
            for ref in node.inputs:
                self._consumers[ref.guid].add(g)

    # -- queries -------------------------------------------------------------

    def consumers(self, guid: int) -> Set[int]:
        return set(self._consumers.get(guid, set()))

    def producers(self, guid: int) -> List[int]:
        return [r.guid for r in self.nodes[guid].inputs]

    def sources(self) -> List[int]:
        return [g for g, n in self.nodes.items() if not n.inputs]

    def sinks(self) -> List[int]:
        return [g for g in self.nodes if not self._consumers.get(g)]

    def shape_of(self, ref: TensorRef) -> ParallelTensorShape:
        return self.nodes[ref.guid].output_shapes[ref.out_idx]

    def topo_order(self) -> List[int]:
        """Kahn topological sort, deterministic (sorted by guid) so the
        executor's program order is stable (reference: dominators.h:156)."""
        indeg = {g: 0 for g in self.nodes}
        for node in self.nodes.values():
            seen_producers = set()
            for ref in node.inputs:
                if ref.guid in self.nodes and ref.guid not in seen_producers:
                    seen_producers.add(ref.guid)
                    indeg[node.guid] += 1
        ready = sorted(g for g, d in indeg.items() if d == 0)
        order = []
        while ready:
            g = ready.pop(0)
            order.append(g)
            for c in sorted(self._consumers.get(g, ())):
                prods = set(self.producers(c))
                if g in prods:
                    indeg[c] -= 1
                    if indeg[c] == 0:
                        ready.append(c)
            ready.sort()
        if len(order) != len(self.nodes):
            raise ValueError("PCG has a cycle")
        return order

    def hash(self) -> int:
        """Order-independent structural hash for search memoization
        (reference: Graph::hash, graph.cc:1513-1529 — sums per-node hashes
        so node iteration order doesn't matter)."""
        total = 0
        for node in self.nodes.values():
            h = node.params_hash()
            h = h * 31 + hash(tuple(node.output_shapes))
            h = h * 31 + hash(
                tuple((r.guid, r.out_idx) for r in node.inputs)
            )
            if node.machine_view is not None:
                h = h * 31 + node.machine_view.hash()
            total = (total + (h & 0xFFFFFFFFFFFFFFF)) & 0x7FFFFFFFFFFFFFFF
        return total

    def copy(self) -> "PCGGraph":
        g = PCGGraph()
        g._next_guid = self._next_guid
        for guid, node in self.nodes.items():
            g.nodes[guid] = dataclasses.replace(
                node,
                inputs=tuple(node.inputs),
                params=dict(node.params),
            )
        g.rebuild_consumers()
        return g

    # -- analysis helpers used by the search ---------------------------------

    def reachable_from(self, start: Iterable[int]) -> Set[int]:
        seen = set(start)
        stack = list(seen)
        while stack:
            g = stack.pop()
            for c in self._consumers.get(g, ()):
                if c not in seen:
                    seen.add(c)
                    stack.append(c)
        return seen

    def ancestors_of(self, start: Iterable[int]) -> Set[int]:
        seen = set(start)
        stack = list(seen)
        while stack:
            g = stack.pop()
            for p in self.producers(g):
                if p in self.nodes and p not in seen:
                    seen.add(p)
                    stack.append(p)
        return seen

    def split_at_node(self, guid: int) -> Tuple["PCGGraph", "PCGGraph"]:
        """Split into (prefix including guid, suffix) — the Unity sequence
        split (reference: graph.h:297 split_at_node). The bottleneck node is
        duplicated into both halves as the interface: it is the sink of the
        first half and an input source of the second.
        """
        pre_set = self.ancestors_of([guid])
        first = PCGGraph()
        second = PCGGraph()
        first._next_guid = second._next_guid = self._next_guid
        for g, node in self.nodes.items():
            tgt = first if g in pre_set else second
            tgt.nodes[g] = dataclasses.replace(
                node, inputs=tuple(node.inputs), params=dict(node.params)
            )
        # In the second half, the bottleneck appears as a NOOP source with
        # the same outputs.
        boundary = self.nodes[guid]
        needs_boundary = any(
            any(r.guid == guid for r in n.inputs)
            for n in second.nodes.values()
        )
        if needs_boundary:
            second.nodes[guid] = PCGNode(
                guid=guid,
                op_type=OperatorType.NOOP,
                name=boundary.name + ".boundary",
                inputs=(),
                params={},
                output_shapes=tuple(boundary.output_shapes),
                machine_view=boundary.machine_view,
            )
        first.rebuild_consumers()
        second.rebuild_consumers()
        return first, second

    def __len__(self):
        return len(self.nodes)

    def __repr__(self):
        lines = [f"PCGGraph({len(self.nodes)} nodes)"]
        for g in self.topo_order():
            n = self.nodes[g]
            ins = ", ".join(f"{r.guid}:{r.out_idx}" for r in n.inputs)
            outs = ", ".join(str(s) for s in n.output_shapes)
            mv = f" @{n.machine_view.dims}" if n.machine_view else ""
            lines.append(
                f"  {g} {n.op_type.name} '{n.name}' ({ins}) -> {outs}{mv}"
            )
        return "\n".join(lines)


def trace_embedding_ids_input(graph: "PCGGraph", guid: int) -> Optional[TensorRef]:
    """If `guid` is an EMBEDDING whose ids come (through layout-only
    parallel ops) straight from a batch INPUT, return the TensorRef of
    that input, else None.

    This is THE sparse-embedding eligibility tracer — the single source
    shared by the executor's fast path (Executor._sparse_embedding_guids,
    runtime/executor.py) and the search's update costing
    (search/simulator._sparse_embedding_rows), so the two can never
    disagree about which tables take the touched-rows update."""
    node = graph.nodes[guid]
    if node.op_type != OperatorType.EMBEDDING:
        return None
    if len(node.weight_shapes) != 1 or len(node.inputs) != 1:
        return None
    ref = node.inputs[0]
    src = graph.nodes[ref.guid]
    while src.is_parallel_op and len(src.inputs) == 1:
        ref = src.inputs[0]
        src = graph.nodes[ref.guid]
    if src.op_type != OperatorType.INPUT or src.inputs:
        return None
    return ref
