"""Operator fusion: the FusedOp pass.

Rebuild of the reference's apply_fusion (reference: model.cc:2489-2597 —
greedily folds ops with the same MachineView into one FusedOp so one
Legion task launch runs many kernels; src/ops/fused.cc dispatches the
inner kernels through input/weight/output indirection tables).

On TPU the kernel-level win is already XLA's (everything under one jit
fuses); what remains is PCG-level: fewer nodes to trace/lower/annotate,
and one unit for the search to cost. The pass folds single-consumer
CHAINS of compute ops whose parallel annotations agree; the FUSED node
keeps the sub-op list in params and its lowering applies the inner
lowered functions in order (the indirection-table analog, flattened
weights sliced per sub-op).
"""

from __future__ import annotations

from typing import Dict, Optional, Set, Tuple

from flexflow_tpu.core.pcg import PCGGraph, TensorRef
from flexflow_tpu.core.types import OperatorType

# ops that may join a fused chain: unary-dataflow compute ops (one input,
# one output). Parallel ops never fuse (they are the view boundaries the
# reference fuses BETWEEN); routing/multi-io ops keep their identity.
_FUSIBLE = {
    OperatorType.LINEAR,
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
    OperatorType.SOFTMAX,
    OperatorType.LAYERNORM,
    OperatorType.RMSNORM,
    OperatorType.RESHAPE,
    OperatorType.TRANSPOSE,
    OperatorType.CAST,
    OperatorType.FLAT,
}


def _chain_from(graph: PCGGraph, start: int, claimed: Set[int]) -> list:
    """Longest fusible chain start → … where every link is the sole
    consumer of a single-output predecessor."""
    chain = [start]
    cur = start
    while True:
        node = graph.nodes[cur]
        if node.num_outputs != 1:
            break
        cons = graph.consumers(cur)
        if len(cons) != 1:
            break
        nxt = next(iter(cons))
        nxt_node = graph.nodes[nxt]
        if (
            nxt_node.op_type not in _FUSIBLE
            or nxt in claimed
            or len(nxt_node.inputs) != 1
        ):
            break
        chain.append(nxt)
        cur = nxt
    return chain


def apply_fusion(
    graph: PCGGraph, protected: Optional[Set[int]] = None
) -> Tuple[PCGGraph, Dict[TensorRef, TensorRef]]:
    """Fold fusible chains into FUSED nodes (reference: apply_fusion,
    model.cc:2489). `protected` guids are never absorbed (the logits node —
    callers hold references to it). Returns (new graph, old→new ref map for
    the outputs of fused chains)."""
    protected = protected or set()
    g = graph.copy()
    # a node that applies another node's weights, or lends its own, keeps
    # its identity: a FUSED node's weights are its chain's, concatenated
    tied = g.weight_owners()
    claimed: Set[int] = set(tied) | set(tied.values())
    ref_map: Dict[TensorRef, TensorRef] = {}

    for start in list(g.topo_order()):
        if start in claimed or start not in g.nodes:
            continue
        node = g.nodes[start]
        if (
            node.op_type not in _FUSIBLE
            or len(node.inputs) != 1
            or start in protected
        ):
            continue
        # a protected node (logits) may END a chain — its output ref is
        # remapped to the fused node — but never sit inside one (its value
        # must stay addressable)
        chain = []
        for c in _chain_from(g, start, claimed):
            chain.append(c)
            if c in protected:
                break
        if len(chain) < 2:
            continue

        nodes = [g.nodes[c] for c in chain]
        sub_ops = [
            {
                "op_type": n.op_type,
                "params": dict(n.params),
                "num_weights": len(n.weight_shapes),
            }
            for n in nodes
        ]
        inits = []
        have_inits = False
        for n in nodes:
            per = n.params.get("initializers")
            if per is not None:
                have_inits = True
                inits.extend(per)
            else:
                inits.extend([None] * len(n.weight_shapes))
        params = {
            "sub_ops": sub_ops,
            "weight_key": "+".join(str(n.weight_key) for n in nodes),
        }
        if have_inits:
            params["initializers"] = inits

        last = nodes[-1]
        fused = g.add_node(
            OperatorType.FUSED,
            "+".join(n.name for n in nodes),
            [nodes[0].inputs[0]],
            params,
            list(last.output_shapes),
            [w for n in nodes for w in n.weight_shapes],
        )
        new_ref = TensorRef(fused.guid, 0)
        old_ref = TensorRef(chain[-1], 0)
        ref_map[old_ref] = new_ref
        for c in list(g.consumers(chain[-1])):
            g.replace_input(c, old_ref, new_ref)
        for c in chain:
            g.remove_node(c)
        claimed.update(chain)
        claimed.add(fused.guid)

    return g, ref_map
