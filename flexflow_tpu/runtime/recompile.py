"""Dynamic-reconfiguration hook: RecompileState.

TPU rebuild of the reference's recompile subsystem (reference:
src/recompile/recompile_state.cc:1-40, include/flexflow/recompile.h:26-41;
used by the MoE example to rebalance experts mid-training,
examples/cpp/mixture_of_experts/moe.cc:65-99). A `RecompileState` pairs a
trigger predicate with a model-mutating alter function;
`FFModel.recompile_on_condition(state)` checks the trigger each time it is
called from the training loop and, when it fires, mutates the model and
recompiles — preserving weights of every surviving layer whose shape is
unchanged, re-initializing the rest, and resetting optimizer state.

Differences from the reference: the reference alters the live Legion op
graph and re-runs compile() in place; here the builder graph is restored to
its pre-strategy form before `alter_func` runs (strategy annotations and
inserted parallel ops are compile artifacts, not user model structure), so
the alter function sees the same graph shape the user built.

Caveat: a recompile re-applies `model._compile_strategy` as-is. An
EXPLICIT pipeline strategy carries its BlockStructure (block guids) from
the original graph — valid across recompiles whose alter leaves the
trunk intact (graph restore preserves guids), but an alter that adds or
removes trunk blocks must pass a freshly built pipeline strategy to
compile() itself; searched strategies re-derive automatically.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np


@dataclasses.dataclass
class RecompileState:
    """reference: RecompileState {trigger_func, alter_func} (recompile.h)."""

    trigger_func: Callable[["FFModel"], bool]
    alter_func: Callable[["FFModel"], None]
    recompiled: int = 0

    def trigger(self, model) -> bool:
        return bool(self.trigger_func(model))

    def alter(self, model) -> None:
        self.alter_func(model)
        self.recompiled += 1


def recompile_on_condition(model, state: RecompileState) -> bool:
    """Check the trigger; on fire, alter + recompile the model
    (reference: FFModel::recompile_on_condition, model.cc:2416-2420).

    Returns True when a recompile happened.
    """
    if model.executor is None:
        raise RuntimeError("call compile() before recompile_on_condition()")
    if not state.trigger(model):
        return False

    # weights to host, keyed by stable node identity — builder name, or the
    # weight_key a substitution stamped on its replacement node (guids are
    # fresh every compile, so they cannot key weights across recompiles)
    def stable_key(node):
        return node.weight_key

    host = {}
    ambiguous = set()
    # per-guid EXPORT view, not raw storage: a pipelined executor keeps
    # trunk weights stacked under the template guid only — harvesting
    # model.params directly would drop every later block's weights and
    # reinitialize the trunk on recompile
    for guid, ws in model.executor.export_host_params(model.params).items():
        node = model.graph.nodes.get(guid)
        if node is None:
            continue
        key = stable_key(node)
        if key in host:
            ambiguous.add(key)
        host[key] = [np.asarray(w) for w in ws]
    for key in ambiguous:
        host.pop(key, None)

    # restore the user-built graph (pre-strategy), then let alter mutate it.
    # Carry the live guid counter forward: strategy/substitution allocated
    # guids past the pristine copy's counter, and reusing them would alias
    # alter-added nodes with stale refs (logits, host-weight keys).
    live_next_guid = model.graph._next_guid
    model.graph = model._prestrategy_graph.copy()
    model.graph._next_guid = max(model.graph._next_guid, live_next_guid)
    state.alter(model)

    # the builder-graph logits ref (pre-substitution) survives the restore
    # because graph copies preserve guids; a substituted _logits ref would not
    from flexflow_tpu.runtime.model import Tensor

    logits_ref = getattr(model, "_builder_logits_ref", model._logits.ref)
    model.compile(
        optimizer=model.optimizer,
        loss_type=model.loss_type,
        metrics=model.metric_types,
        logits=Tensor(model, logits_ref)
        if logits_ref.guid in model.graph.nodes
        else None,
        devices=model._compile_devices,
        strategy=model._compile_strategy,
    )

    # carry over weights whose stable identity + shape survived the
    # alteration — overlaid on the fresh params' export view and placed
    # in ONE pass (per-weight set_tensor would rebuild a pipelined
    # trunk's pipe-sharded stack per block: O(S^2) device copies)
    new_by_key = {}
    for guid, node in model.graph.nodes.items():
        if not node.weight_shapes:
            continue
        key = stable_key(node)
        new_by_key[key] = None if key in new_by_key else guid
    current = model.executor.export_host_params(model.params)
    changed = False
    for key, ws in host.items():
        guid = new_by_key.get(key)
        if guid is None:
            continue
        node = model.graph.nodes[guid]
        if len(node.weight_shapes) != len(ws):
            continue
        ok = all(
            tuple(arr.shape)
            == tuple(d.size for d in shape.dims if not d.is_replica_dim)
            for arr, shape in zip(ws, node.weight_shapes)
        )
        if ok:
            # cast to the NEW node's declared dtype (an alter may rebuild
            # a same-shape layer at a different precision; set_tensor
            # used to guarantee this cast)
            current[guid] = [
                np.asarray(arr, dtype=shape.dtype.to_jnp())
                for arr, shape in zip(ws, node.weight_shapes)
            ]
            changed = True
    if changed:
        model.params = model.executor.place_params(current)
    # opt_state from compile() stays valid: placement preserves shapes,
    # and a recompile resets momenta by design (the reference re-inits
    # optimizer tasks after recompile too)
    model.recompile_events = getattr(model, "recompile_events", 0) + 1
    return True
