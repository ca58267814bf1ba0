"""Strategy import/export.

Rebuild of the reference's strategy file I/O (reference:
src/runtime/strategy.cc:100-197 load/save of per-op ParallelConfig maps,
exposed as --export-strategy / --import-strategy). The on-disk format is
JSON instead of the reference's binary protobuf: the global mesh plus the
enabled rewrite sites, keyed by op *names* (stable across runs of the same
builder program, like the reference's per-op keys).
"""

from __future__ import annotations

import json
from typing import Dict, List

from flexflow_tpu.core.pcg import PCGGraph
from flexflow_tpu.parallel.strategy import Strategy, data_parallel_strategy

_SITE_KINDS = {}


def _register_site_kinds():
    from flexflow_tpu.search.rewrites import (
        AttentionSite,
        ConvChannelSite,
        EmbeddingSite,
        ExpertParallelSite,
        LinearChainSite,
        SingleLinearSite,
        SparseMoeSite,
    )

    _SITE_KINDS.update(
        {
            "attention": AttentionSite,
            "conv_channel": ConvChannelSite,
            "embedding": EmbeddingSite,
            "expert_parallel": ExpertParallelSite,
            "linear_chain": LinearChainSite,
            "single_linear": SingleLinearSite,
            "sparse_moe": SparseMoeSite,
        }
    )


def save_search_result(result, graph: PCGGraph, path: str):
    """Persist a SearchResult (search.auto) for later --import-strategy."""
    sites = []
    for tied, enabled in zip(result.sites, result.on):
        if not enabled:
            continue
        # sites tied by a shared weight are written one by one: all of
        # them are in the file, so a load turns all of them on
        for site in getattr(tied, "members", None) or (tied,):
            sites.append(
                {
                    "kind": site.kind,
                    "names": [graph.nodes[g].name for g in site.guids],
                }
            )
    doc = {
        "version": 1,
        "kind": getattr(result, "kind", "tp"),
        "dp": result.dp,
        "tp": result.tp,
        "extra": getattr(result, "extra", {}),
        "simulated_step_ms": result.cost.step_time * 1e3,
        "sites": sites,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)


def save_strategy(strategy: Strategy, path: str):
    """Persist a plain Strategy (mesh only; site-level detail requires a
    SearchResult — use save_search_result from the search path)."""
    doc = {
        "version": 1,
        "mesh_axes": list(strategy.mesh_config.axis_names),
        "mesh_sizes": list(strategy.mesh_config.axis_sizes),
        "name": strategy.name,
        "sites": [],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)


def _check_second_axis_shards(strategy, graph: PCGGraph, deg: int, path: str):
    """An imported (dp x <axis>) strategy whose second axis shards NOTHING
    on this graph would silently idle those chips — the search path has
    this exact guard (auto._second_axis_candidate); imports need it too."""
    from flexflow_tpu.runtime.executor import propagate_shapes

    g = graph.copy()
    strategy.apply(g)
    propagate_shapes(g)
    from flexflow_tpu.core.types import OperatorType

    if not any(
        d.degree == deg and d.parallel_idx == 1
        for n in g.nodes.values()
        if n.op_type == OperatorType.INPUT
        for d in n.output_shapes[0].dims
    ):
        raise ValueError(
            f"strategy file {path!r}: the second mesh axis (degree {deg}) "
            "shards no input of this graph — the strategy does not apply"
        )


def load_strategy(path: str, graph: PCGGraph, num_devices: int) -> Strategy:
    """Rebuild a Strategy from JSON against the current graph
    (reference: load_strategies_from_file + compile-time map lookup)."""
    _register_site_kinds()
    with open(path) as f:
        doc = json.load(f)

    dp = int(doc.get("dp", doc.get("mesh_sizes", [num_devices])[0]))
    tp = int(doc.get("tp", 1))
    kind = doc.get("kind", "tp")
    extra = doc.get("extra", {})

    if kind == "seq":
        from flexflow_tpu.parallel.strategy import sequence_parallel_strategy

        sp = int(extra.get("sp", 1))
        if dp * sp > num_devices:
            raise ValueError(
                f"strategy file wants {dp * sp} devices, have {num_devices}"
            )
        s = sequence_parallel_strategy(
            dp, sp, graph, seq_mode=extra.get("seq_mode", "ring")
        )
        if sp > 1:
            _check_second_axis_shards(s, graph, sp, path)
        s.name = f"imported:{path}"
        return s
    if kind == "spatial":
        from flexflow_tpu.parallel.strategy import spatial_parallel_strategy

        hp = int(extra.get("hp", 1))
        if dp * hp > num_devices:
            raise ValueError(
                f"strategy file wants {dp * hp} devices, have {num_devices}"
            )
        s = spatial_parallel_strategy(dp, hp, graph)
        if hp > 1:
            _check_second_axis_shards(s, graph, hp, path)
        s.name = f"imported:{path}"
        return s
    if kind == "pipeline":
        from flexflow_tpu.parallel.strategy import pipeline_strategy

        pp = int(extra.get("pp", 1))
        if dp * pp > num_devices:
            raise ValueError(
                f"strategy file wants {dp * pp} devices, have {num_devices}"
            )
        return pipeline_strategy(
            graph,
            dp,
            pp,
            num_microbatches=int(extra.get("mb", 4)),
            schedule=extra.get("schedule", "gpipe"),
            name_prefix=f"imported:{path}",
        )

    if dp * tp > num_devices:
        raise ValueError(
            f"strategy file wants {dp * tp} devices, have {num_devices}"
        )
    if tp <= 1 and not doc.get("sites"):
        # respect the saved dp (an idle-chip dp is a deliberate choice)
        return data_parallel_strategy(dp or num_devices, graph)

    name_to_guid: Dict[str, int] = {
        n.name: g for g, n in graph.nodes.items()
    }
    sites = []
    for entry in doc.get("sites", []):
        cls = _SITE_KINDS.get(entry["kind"])
        if cls is None:
            raise ValueError(f"unknown site kind {entry['kind']!r}")
        try:
            guids = tuple(name_to_guid[nm] for nm in entry["names"])
        except KeyError as e:
            raise ValueError(
                f"strategy file references unknown op {e.args[0]!r}"
            ) from None
        sites.append(cls(entry["kind"], guids))

    if kind == "mixed":
        # heterogeneous lowering: TP sites + full-width dp outside them
        # (falling through to the uniform path would silently import a
        # DIFFERENT strategy than was exported)
        from flexflow_tpu.parallel.strategy import mixed_site_strategy

        if dp * tp > num_devices:
            raise ValueError(
                f"mixed strategy file wants {dp * tp} devices, "
                f"have {num_devices}"
            )
        # honor the FILE's device count (like the seq/spatial import
        # paths): importing on a wider machine must not silently widen
        # the data axis into a different strategy than was exported
        s = mixed_site_strategy(
            graph, dp * tp, tp, sites, name_prefix=f"imported:{path}"
        )
        if "mixed" not in s.name:
            raise ValueError(
                f"strategy file {path!r} is a mixed strategy but the "
                "current graph/device count cannot express it"
            )
        return s

    from flexflow_tpu.runtime.executor import MeshConfig
    from flexflow_tpu.search.auto import _MODEL_AXIS, _annotate_data_parallel

    mesh = (
        MeshConfig(("data", "model"), (dp, tp))
        if tp > 1
        else MeshConfig(("data",), (dp,))
    )

    def apply(g: PCGGraph):
        _annotate_data_parallel(g, dp)
        for site in sites:
            site.apply(g, tp, _MODEL_AXIS)

    return Strategy(mesh, apply, name=f"imported:{path}")
