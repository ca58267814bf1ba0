"""Parallelization strategies: how an un-annotated PCG gets its parallel dims.

A Strategy bundles the global MeshConfig with the per-tensor degree
annotations. The data-parallel strategy replicates the reference's
`--only-data-parallel` mode (reference: graph.cc:1588-1613 — a 1-D view over
all devices partitioning the sample dim). Searched strategies (Unity DP /
MCMC, flexflow_tpu.search) produce per-op annotations that `apply` writes
into the graph before shape propagation.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
from flexflow_tpu.core.pcg import PCGGraph
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.runtime.executor import MeshConfig


@dataclasses.dataclass
class Strategy:
    mesh_config: MeshConfig
    # callable mutating the graph's source annotations / inserting parallel ops
    _apply: Optional[Callable[[PCGGraph], None]] = None
    name: str = "custom"
    # set on dp×pp strategies: compile() routes the repeated trunk through
    # the GPipe executor (runtime.pipeline_executor.PipelinedExecutor)
    pipeline: Optional[object] = None  # runtime.pipeline_executor.PipelineSpec

    def apply(self, graph: PCGGraph):
        if self._apply is not None:
            self._apply(graph)


def annotate_input_batch(graph: PCGGraph, dp: int, strict: bool = False):
    """Shard every source INPUT's batch (outermost) dim `dp` ways — the one
    place this annotation is written (data-parallel, searched, and imported
    strategies all route here). strict=True raises on a non-dividing batch;
    otherwise the caller is expected to have clamped dp already."""
    if dp <= 1:
        return
    for node in graph.nodes.values():
        if node.op_type == OperatorType.INPUT and not node.inputs:
            shape: ParallelTensorShape = node.params["shape"]
            if shape.dims[0].size % dp != 0:
                if strict:
                    raise ValueError(
                        f"input '{node.name}' batch {shape.dims[0].size} "
                        f"not divisible by dp={dp}"
                    )
                continue
            node.params["shape"] = shape.data_parallel(dp)
            node.output_shapes = (node.params["shape"],)


def effective_dp_degree(graph: PCGGraph, num_devices: int) -> int:
    """Largest degree <= num_devices dividing every input's batch dim.
    The mesh is sized to this degree — a PartitionSpec must shard a dim
    exactly axis-size ways, so degree and mesh axis cannot disagree."""
    batches = [
        n.params["shape"].dims[0].size
        for n in graph.nodes.values()
        if n.op_type == OperatorType.INPUT and not n.inputs
    ]
    if not batches:
        return 1
    for d in range(min(num_devices, min(batches)), 0, -1):
        if all(b % d == 0 for b in batches):
            return d
    return 1


def data_parallel_strategy(num_devices: int, graph: PCGGraph = None) -> Strategy:
    """Partition every input's sample (outermost) dim over the data axis
    (reference: --only-data-parallel, graph.cc:1588-1613)."""
    dp = (
        effective_dp_degree(graph, num_devices)
        if graph is not None
        else num_devices
    )

    def apply(g: PCGGraph):
        annotate_input_batch(g, effective_dp_degree(g, dp))

    return Strategy(
        MeshConfig.data_parallel(max(dp, 1)), apply, name="data-parallel"
    )


def _second_axis_strategy(
    axis_name: str, dp: int, degree: int, axis: int, eligible, name: str
) -> Strategy:
    """Shared builder for (data × <axis>) strategies: batch on "data",
    one more input dim (seq / spatial) on the second mesh axis when the
    eligibility predicate admits it."""

    def apply(g: PCGGraph):
        annotate_input_batch(g, dp)
        for node in g.nodes.values():
            if node.op_type == OperatorType.INPUT and not node.inputs:
                shape: ParallelTensorShape = node.params["shape"]
                if (
                    degree > 1
                    and eligible(shape)
                    and shape.dims[axis].size % degree == 0
                ):
                    shape = shape.with_degree(axis, degree, 1)
                node.params["shape"] = shape
                node.output_shapes = (shape,)

    return Strategy(
        MeshConfig(("data", axis_name), (max(dp, 1), max(degree, 1))),
        apply,
        name=name,
    )


def sequence_parallel_strategy(
    dp: int, sp: int, graph: PCGGraph = None, seq_axis: int = 1,
    seq_mode: str = "ring",
) -> Strategy:
    """dp × sp mesh: inputs' batch dim on the "data" axis and sequence dim on
    the "seq" axis. Attention under the partitioned sequence dim runs the
    ring-attention path (ops/pallas/ring_attention.py) or, with
    seq_mode="ulysses", the all-to-all seq->heads reshard — whichever the
    cost model picked (the long-context capability the reference lacks,
    SURVEY §5)."""
    if seq_mode not in ("ring", "ulysses"):
        raise ValueError(f"seq_mode must be ring|ulysses, got {seq_mode!r}")
    base = _second_axis_strategy(
        "seq",
        dp,
        sp,
        seq_axis,
        # a real sequence is rank-3 [batch, seq, features]; rank-4 images
        # belong to the SPATIAL family (--enable-attribute-parallel), not
        # here — without this split the search's "seq" candidates quietly
        # shard image H dims and the two families double-count
        lambda shape: shape.ndim == seq_axis + 2,
        f"dp{dp}xsp{sp}" + ("-ulysses" if seq_mode == "ulysses" else ""),
    )
    if seq_mode == "ring":
        return base

    base_apply = base.apply

    def apply(g: PCGGraph):
        base_apply(g)
        for node in g.nodes.values():
            if not ulysses_eligible(node, sp):
                continue
            node.params["seq_parallel"] = "ulysses"

    return Strategy(base.mesh_config, apply, name=base.name)


def ulysses_eligible(node, sp: int) -> bool:
    """Whether a node can take the Ulysses seq->heads reshard: an MHA
    whose heads divide sp, without attention-prob dropout (the reshard
    path has no dropout support — ops/attention.py raises), and whose
    seq_parallel the user left on auto (an explicit ring/none choice is
    never clobbered)."""
    if node.op_type != OperatorType.MULTIHEAD_ATTENTION:
        return False
    heads = int(node.params.get("num_heads", 0))
    return (
        heads > 0
        and heads % sp == 0
        and float(node.params.get("dropout", 0.0)) == 0.0
        and node.params.get("seq_parallel", "auto") == "auto"
    )


def spatial_parallel_strategy(
    dp: int, hp: int, graph: PCGGraph = None, spatial_axis: int = 1
) -> Strategy:
    """Attribute/spatial parallelism (reference: --enable-attribute-parallel,
    model.cc:3602 — partition non-sample activation dims): image inputs'
    H dim shards over a "spatial" mesh axis. Convolutions under a sharded
    spatial dim are handled by GSPMD's windowed-op halo exchange — the
    TPU-native replacement for the reference's Legion-partition overlap."""
    return _second_axis_strategy(
        "spatial",
        dp,
        hp,
        spatial_axis,
        lambda shape: shape.ndim == 4,  # NHWC rank-4 images only
        f"dp{dp}xhp{hp}",
    )


def pipeline_strategy(
    graph: PCGGraph,
    dp: int,
    pp: int,
    structure=None,
    num_microbatches: int = 4,
    schedule: str = "gpipe",
    name_prefix: str = "pipeline",
) -> Strategy:
    """dp × pp strategy: batch on "data", the repeated trunk GPipe'd over
    the "pipe" axis with stage weights SHARDED over it (the reference
    declares OP_PIPELINE but never implements it, ffconst.h:151 — this
    closes that gap). `structure` is a search.blocks.BlockStructure;
    detected here when omitted. schedule: "gpipe" | "1f1b"
    (runtime.pipeline_executor.PipelineSpec)."""
    from flexflow_tpu.runtime.pipeline_executor import PipelineSpec
    from flexflow_tpu.search.blocks import find_block_structure

    if structure is None:
        structure = find_block_structure(graph)
    if structure is None:
        raise ValueError("graph has no repeated-block trunk to pipeline")
    if structure.num_blocks % pp != 0:
        raise ValueError(
            f"{structure.num_blocks} blocks not divisible by pp={pp}"
        )
    dp = effective_dp_degree(graph, max(1, dp))

    def apply(g: PCGGraph):
        annotate_input_batch(g, dp)

    mesh = (
        MeshConfig(("data", "pipe"), (dp, pp))
        if dp > 1
        else MeshConfig(("pipe",), (pp,))
    )
    return Strategy(
        mesh,
        apply,
        name=(
            f"{name_prefix}: mesh(data={dp}, pipe={pp}), "
            f"{structure.num_blocks} blocks"
            + (f", {schedule}" if schedule != "gpipe" else "")
        ),
        pipeline=PipelineSpec(pp, num_microbatches, structure, schedule),
    )


def site_strategy(
    graph: PCGGraph,
    num_devices: int,
    tp: int,
    sites,
    name_prefix: str = "searched",
) -> Strategy:
    """Shared lowering for searched strategies: a (data × model) mesh plus
    TP rewrite sites. dp is clamped to the largest feasible batch divisor
    (an infeasible dp would make _annotate_data_parallel raise at compile)."""
    tp = max(1, tp)
    dp = effective_dp_degree(graph, max(1, num_devices // tp))

    def apply(g: PCGGraph):
        annotate_input_batch(g, dp)
        for site in sites:
            site.apply(g, tp, 1)  # model axis = 1
        from flexflow_tpu.search.peephole import sink_combines

        sink_combines(g)  # keep the lowered graph == the costed candidate

    mesh = (
        MeshConfig(("data", "model"), (dp, tp))
        if tp > 1
        else MeshConfig(("data",), (max(dp, 1),))
    )
    return Strategy(
        mesh,
        apply,
        name=(
            f"{name_prefix}: mesh(data={dp}, model={tp}), "
            f"{len(list(sites))} TP sites"
        ),
    )


def mixed_site_strategy(
    graph: PCGGraph,
    num_devices: int,
    tp: int,
    sites,
    name_prefix: str = "searched",
) -> Strategy:
    """Per-op heterogeneous lowering (reference: per-op MachineViews in
    SearchHelper::graph_cost, graph.cc:1346-1431 — e.g. DLRM shards
    embedding tables model-parallel while the MLPs stay data-parallel).

    One (data × model) mesh, two sharding regimes on it: ops OUTSIDE the
    TP sites shard their batch over BOTH axes (full-width data parallelism
    via PartitionSpec spans, ParallelTensorShape.partition_spec), while
    each site shards channels/heads/columns on the model axis. Sites are
    bracketed by batch-Combine (full→data-axis-only) on entry and
    batch-Repartition (back to full width) on exit; GSPMD lowers the
    brackets to the boundary collectives. Falls back to the uniform
    `site_strategy` when the full-width batch shard is infeasible or a
    site kind has no batch-dim-0 bracket semantics."""
    from flexflow_tpu.search.rewrites import _insert_after, _insert_before

    sites = list(sites)
    tp = max(1, tp)
    dp = effective_dp_degree(graph, max(1, num_devices // tp))
    full = dp * tp
    bracketable = {
        "linear_chain", "single_linear", "attention", "embedding",
        "conv_channel", "sparse_moe",
    }
    if (
        tp == 1
        or effective_dp_degree(graph, full) != full
        or any(s.kind not in bracketable for s in sites)
    ):
        return site_strategy(graph, num_devices, tp, sites, name_prefix)

    def apply(g: PCGGraph):
        annotate_input_batch(g, full)
        for site in sites:
            head, tail = site.guids[0], site.guids[-1]
            hnode = g.nodes[head]
            for ref in dict.fromkeys(hnode.inputs):
                _insert_before(
                    g,
                    head,
                    ref,
                    OperatorType.COMBINE,
                    f"{hnode.name}.batch_combine",
                    {"axis": 0, "degree": tp},
                )
            _insert_after(
                g,
                tail,
                OperatorType.REPARTITION,
                f"{g.nodes[tail].name}.batch_repartition",
                {"axis": 0, "degree": tp, "parallel_idx": 0},
            )
            site.apply(g, tp, 1)
        from flexflow_tpu.search.peephole import sink_combines

        sink_combines(g)

    return Strategy(
        MeshConfig(("data", "model"), (dp, tp)),
        apply,
        name=(
            f"{name_prefix}: mixed mesh(data={dp}, model={tp}), "
            f"{len(sites)} TP sites, full-width dp={full} outside them"
        ),
    )


def choose_strategy(model, num_devices: int) -> Strategy:
    """Strategy selection at compile() (reference: model.cc:2789 →
    graph_optimize_task, graph.cc:1545-1613): data-parallel unless a search
    budget asks for the Unity-style search."""
    cfg = model.config
    if cfg.import_strategy_file:
        from flexflow_tpu.search.strategy_io import load_strategy

        return load_strategy(cfg.import_strategy_file, model.graph, num_devices)
    if cfg.only_data_parallel or cfg.search_budget <= 0:
        if (
            cfg.enable_parameter_parallel
            and not cfg.only_data_parallel
            and num_devices > 1
        ):
            # --enable-parameter-parallel without a search budget: shard
            # the embedding tables over the devices deterministically
            # (the reference's DLRM usage — embedding.cc weight sharding
            # driven by the flag + strategy files, no search needed) and
            # keep everything else full-width data-parallel
            from flexflow_tpu.search.rewrites import (
                EmbeddingSite,
                find_tp_sites,
            )

            sites = [
                s
                for s in find_tp_sites(model.graph)
                if isinstance(s, EmbeddingSite)
                and s.divisible_by(model.graph, num_devices)
            ]
            if sites:
                s = mixed_site_strategy(
                    model.graph,
                    num_devices,
                    num_devices,
                    sites,
                    name_prefix="parameter-parallel",
                )
                if "mixed" in s.name:
                    return s
        return data_parallel_strategy(num_devices, model.graph)
    from flexflow_tpu.search.auto import search_strategy

    return search_strategy(model, num_devices)


def export_strategy(strategy: Strategy, path: str):
    from flexflow_tpu.search.strategy_io import save_strategy

    save_strategy(strategy, path)
