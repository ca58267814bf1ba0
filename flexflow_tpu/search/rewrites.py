"""Parallelization rewrites: the substitution vocabulary of the search.

TPU re-design of the reference's hand-written GraphXfer generators
(reference: src/runtime/substitution.cc:1721-1862). Each rewrite wraps a
matched subgraph in parallel ops so the existing shape-inference protocol
(replica dim -> channel/head sharding; partitioned contraction dim ->
partial-sum replica dim) expresses the strategy:

  * `LinearChainSite` — Megatron column→row pair
    (reference: create_replicate_linear_combine + the reduction variant,
    substitution.cc:1750-1765,1804-1827): Replicate(x) → Linear(out-sharded)
    → …elementwise… → Linear(partial sums) → Reduction.
  * `AttentionSite` — head parallelism
    (reference: create_replicate_attention_reduce, substitution.cc:1758-1764):
    Replicate(q,k,v) → MHA (heads sharded, output partial) → Reduction.
  * `SingleLinearSite` — lone Linear: Replicate → Linear → Combine on the
    feature dim (column-parallel only; reference:
    create_partition_linear_combine).

A "site" is a detected location; `apply(graph, tp, axis)` mutates the graph.
Sites are the unit the search toggles on/off.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.core.pcg import PCGGraph, TensorRef
from flexflow_tpu.core.types import OperatorType

# elementwise ops a sharded feature dim passes through unchanged
_PASSTHROUGH = {
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
    OperatorType.DROPOUT,
}


def _insert_before(
    graph: PCGGraph,
    consumer_guid: int,
    input_ref: TensorRef,
    op_type: OperatorType,
    name: str,
    params: dict,
) -> TensorRef:
    """Insert `op_type(input_ref)` and rewire ONLY consumer_guid to it.

    Output shapes are placeholders (the producer's current shape): upstream
    rewrites may not have re-propagated yet, so real shapes are only
    computable by the caller's final propagate_shapes pass."""
    in_shape = graph.shape_of(input_ref)
    node = graph.add_node(op_type, name, [input_ref], params, [in_shape])
    new_ref = TensorRef(node.guid, 0)
    graph.replace_input(consumer_guid, input_ref, new_ref)
    return new_ref


def _insert_after(
    graph: PCGGraph,
    producer_guid: int,
    op_type: OperatorType,
    name: str,
    params: dict,
) -> TensorRef:
    """Insert `op_type(producer:0)` and rewire ALL other consumers to it.
    Placeholder output shapes, like _insert_before."""
    src = TensorRef(producer_guid, 0)
    consumers = graph.consumers(producer_guid)
    in_shape = graph.shape_of(src)
    node = graph.add_node(op_type, name, [src], params, [in_shape])
    new_ref = TensorRef(node.guid, 0)
    for c in consumers:
        graph.replace_input(c, src, new_ref)
    return new_ref


@dataclasses.dataclass(frozen=True)
class Site:
    kind: str
    guids: Tuple[int, ...]  # nodes involved, in chain order

    def divisible_by(self, graph: PCGGraph, tp: int) -> bool:
        raise NotImplementedError

    def apply(self, graph: PCGGraph, tp: int, axis: int):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class LinearChainSite(Site):
    """linear → elementwise* → linear, all intermediates single-consumer."""

    def divisible_by(self, graph, tp):
        a = graph.nodes[self.guids[0]]
        return a.params["out_features"] % tp == 0

    def apply(self, graph, tp, axis):
        a_guid, b_guid = self.guids[0], self.guids[-1]
        a = graph.nodes[a_guid]
        _insert_before(
            graph,
            a_guid,
            a.inputs[0],
            OperatorType.REPLICATE,
            f"{a.name}.replicate",
            {"degree": tp, "parallel_idx": axis},
        )
        b = graph.nodes[b_guid]
        _insert_after(
            graph,
            b_guid,
            OperatorType.REDUCTION,
            f"{b.name}.reduction",
            {"degree": tp},
        )


@dataclasses.dataclass(frozen=True)
class AttentionSite(Site):
    """One MultiHeadAttention node; q/k/v may be the same tensor."""

    def divisible_by(self, graph, tp):
        node = graph.nodes[self.guids[0]]
        return node.params["num_heads"] % tp == 0

    def apply(self, graph, tp, axis):
        guid = self.guids[0]
        node = graph.nodes[guid]
        # one Replicate per unique input; replace_input rewires every
        # occurrence of a duplicated ref (q=k=v) in one call
        for i, ref in enumerate(dict.fromkeys(node.inputs)):
            _insert_before(
                graph,
                guid,
                ref,
                OperatorType.REPLICATE,
                f"{node.name}.replicate{i}",
                {"degree": tp, "parallel_idx": axis},
            )
        _insert_after(
            graph,
            guid,
            OperatorType.REDUCTION,
            f"{node.name}.reduction",
            {"degree": tp},
        )


@dataclasses.dataclass(frozen=True)
class SparseMoeSite(AttentionSite):
    """One SparseMoE node: attention's bracket (Replicate the input, a
    Reduction after), the replica dim sharding the stacked experts where
    it shards attention's heads: each chip holds its experts' weights and
    sums their rows' part of the result."""

    def divisible_by(self, graph, tp):
        return graph.nodes[self.guids[0]].params["num_experts"] % tp == 0


@dataclasses.dataclass(frozen=True)
class _ColumnParallelSite(Site):
    """Shared column-parallel bracket: Replicate the (single) input, let
    the replica-dim protocol shard the op's width param over the model
    axis, Combine gathers the last (feature/channel) output dim after.
    Subclasses name the width param; one implementation means a protocol
    fix lands everywhere at once."""

    _WIDTH_PARAM = ""  # subclass sets

    def divisible_by(self, graph, tp):
        return graph.nodes[self.guids[0]].params[self._WIDTH_PARAM] % tp == 0

    def apply(self, graph, tp, axis):
        guid = self.guids[0]
        node = graph.nodes[guid]
        _insert_before(
            graph,
            guid,
            node.inputs[0],
            OperatorType.REPLICATE,
            f"{node.name}.replicate",
            {"degree": tp, "parallel_idx": axis},
        )
        # output feature/channel dim comes out sharded; Combine gathers it
        out_ndim = len(node.output_shapes[0].dims)
        _insert_after(
            graph,
            guid,
            OperatorType.COMBINE,
            f"{node.name}.combine",
            {"axis": out_ndim - 1, "degree": tp},
        )


@dataclasses.dataclass(frozen=True)
class SingleLinearSite(_ColumnParallelSite):
    """A lone Linear: column-parallel, gather features after."""

    _WIDTH_PARAM = "out_features"


@dataclasses.dataclass(frozen=True)
class ConvChannelSite(_ColumnParallelSite):
    """One Conv2D: shard the OUTPUT-channel dim over the model axis
    (reference: conv mapping xfers, create_mapping_xfers<Conv2D>,
    substitution.cc:1789 — the conv analog of column-parallel Linear)."""

    _WIDTH_PARAM = "out_channels"

    def divisible_by(self, graph, tp):
        node = graph.nodes[self.guids[0]]
        groups = node.params.get("groups", 1)
        # grouped convs: sharding across group boundaries is not
        # partitionable (XLA SPMD aborts on it); tp must divide the groups
        return (
            node.params["out_channels"] % tp == 0
            and (groups == 1 or groups % tp == 0)
        )


@dataclasses.dataclass(frozen=True)
class EmbeddingSite(_ColumnParallelSite):
    """Model-parallel embedding: shard the table's embedding (out_dim)
    column dim over the model axis — the reference's key DLRM pattern
    ("embedding weight sharded or replicated", embedding.cc; DLRM
    strategies shard tables while the MLPs stay data-parallel)."""

    _WIDTH_PARAM = "out_dim"


@dataclasses.dataclass(frozen=True)
class ExpertParallelSite(Site):
    """Batched ExpertFFN + its Aggregate consumer: shard the expert dim
    over the model axis (GShard-style EP; the reference instead lets the
    search place per-expert Linear ops on different GPUs)."""

    def divisible_by(self, graph, tp):
        ffn = graph.nodes[self.guids[0]]
        n = graph.shape_of(ffn.inputs[0]).dims[0].size
        return n % tp == 0

    def apply(self, graph, tp, axis):
        ffn_guid, agg_guid = self.guids
        ffn = graph.nodes[ffn_guid]
        # scatter the stacked [n, cap, d] tensor's expert dim over the axis
        _insert_before(
            graph,
            ffn_guid,
            ffn.inputs[0],
            OperatorType.REPARTITION,
            f"{ffn.name}.repartition",
            {"axis": 0, "degree": tp, "parallel_idx": axis},
        )
        # aggregate contracts the (sharded) expert dim -> partial sums
        _insert_after(
            graph,
            agg_guid,
            OperatorType.REDUCTION,
            f"{graph.nodes[agg_guid].name}.reduction",
            {"degree": tp},
        )


def find_tp_sites(graph: PCGGraph) -> List[Site]:
    """Detect tensor-parallel rewrite sites (the search's substitution
    candidates). Linear pairs are preferred over two singles; attention
    nodes are always sites."""
    sites: List[Site] = []
    claimed = set()

    for guid in graph.topo_order():
        node = graph.nodes[guid]
        if node.op_type == OperatorType.MULTIHEAD_ATTENTION:
            sites.append(AttentionSite("attention", (guid,)))
            claimed.add(guid)
        elif node.op_type == OperatorType.SPARSE_MOE:
            sites.append(SparseMoeSite("sparse_moe", (guid,)))
            claimed.add(guid)
        elif node.op_type == OperatorType.EMBEDDING:
            sites.append(EmbeddingSite("embedding", (guid,)))
            claimed.add(guid)
        elif node.op_type == OperatorType.CONV2D:
            sites.append(ConvChannelSite("conv_channel", (guid,)))
            claimed.add(guid)
        elif node.op_type == OperatorType.EXPERT_FFN:
            aggs = [
                c
                for c in graph.consumers(guid)
                if graph.nodes[c].op_type
                in (OperatorType.AGGREGATE, OperatorType.AGGREGATE_SPEC)
            ]
            if len(aggs) == 1:
                sites.append(
                    ExpertParallelSite("expert_parallel", (guid, aggs[0]))
                )
                claimed.update({guid, aggs[0]})

    # linear→elementwise*→linear chains
    for guid in graph.topo_order():
        node = graph.nodes[guid]
        if node.op_type != OperatorType.LINEAR or guid in claimed:
            continue
        chain = [guid]
        cur = guid
        ok = False
        while True:
            cons = graph.consumers(cur)
            if len(cons) != 1:
                break
            nxt = next(iter(cons))
            nxt_node = graph.nodes[nxt]
            if nxt_node.op_type == OperatorType.LINEAR and nxt not in claimed:
                chain.append(nxt)
                ok = True
                break
            if nxt_node.op_type in _PASSTHROUGH:
                chain.append(nxt)
                cur = nxt
                continue
            break
        if ok:
            sites.append(LinearChainSite("linear_chain", tuple(chain)))
            claimed.update(chain)

    # leftover lone linears (not the tiny final classifier — searching it
    # is allowed, the cost model will reject unprofitable ones anyway)
    for guid in graph.topo_order():
        node = graph.nodes[guid]
        if node.op_type == OperatorType.LINEAR and guid not in claimed:
            sites.append(SingleLinearSite("single_linear", (guid,)))
            claimed.add(guid)
    return _tie_sites(graph, sites)


@dataclasses.dataclass(frozen=True)
class TiedSites(Site):
    """The sites of nodes that apply one weight (an owner and the nodes
    that borrow from it, FFModel's `weights_of=`), taken together or not
    at all: one stored array has one sharding."""

    members: Tuple[Site, ...] = ()

    def divisible_by(self, graph, tp):
        return all(m.divisible_by(graph, tp) for m in self.members)

    def apply(self, graph, tp, axis):
        for m in self.members:
            m.apply(graph, tp, axis)


def _tie_sites(graph: PCGGraph, sites: List[Site]) -> List[Site]:
    """`sites` with those that touch one shared weight merged into one
    `TiedSites`, at the place of the first; `sites` itself for a graph
    that shares nothing."""
    owner = graph.weight_owners()
    if not owner:
        return sites
    groups: Dict[tuple, List[Site]] = {}
    for s in sites:
        key = tuple(
            owner.get(g, g) for g in s.guids if graph.nodes[g].weight_shapes
        ) or s.guids
        groups.setdefault(key, []).append(s)
    return [
        ms[0] if len(ms) == 1 else TiedSites(
            "tied", tuple(g for m in ms for g in m.guids), tuple(ms)
        )
        for ms in groups.values()
    ]
