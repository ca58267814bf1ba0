"""Automatic parallelization search.

TPU rebuild of the reference's two search engines (SURVEY §2.5):

  * the Unity substitution search (reference: GraphSearchHelper::
    graph_optimize, src/runtime/substitution.cc:1884-2194 — priority-queue
    rewrite search ranked by simulated cost) becomes a **mesh × rewrite-site
    search**: enumerate (dp, tp) factorizations of the chip count, detect TP
    rewrite sites (rewrites.find_tp_sites), greedily toggle sites by
    simulated step time, then spend the remaining `--budget` on MCMC
    perturbations (reference: FFModel::mcmc_optimize, model.cc:3271-3342 —
    random flip, accept with exp(-alpha·Δ)).
  * per-candidate cost comes from search.simulator (the reference's
    Simulator::simulate_runtime role).

The v1 restriction documented in SURVEY §7 applies: every strategy lives on
ONE global mesh (data × model axes); per-op device subsets
(start_device_id/strides MachineViews) are not searched.
"""

from __future__ import annotations

import math
import random
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.core.machine import MachineSpec
from flexflow_tpu.core.pcg import PCGGraph, TensorRef
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.parallel.strategy import Strategy, data_parallel_strategy
from flexflow_tpu.search.cost_model import CostModel
from flexflow_tpu.search.rewrites import Site, find_tp_sites
from flexflow_tpu.search.simulator import (
    GraphCost,
    _sparse_embedding_rows,
    estimate_graph_cost,
    sparse_embedding_node_cost,
)

_MODEL_AXIS = 1  # mesh axis index for tensor parallelism ("model")


def _annotate_data_parallel(graph: PCGGraph, dp: int):
    """Shard every input's batch dim exactly dp ways; the mesh data axis is
    dp wide, so a batch dp does not divide makes the candidate infeasible."""
    from flexflow_tpu.parallel.strategy import annotate_input_batch

    annotate_input_batch(graph, dp, strict=True)


def _candidate_graph(
    base: PCGGraph, dp: int, tp: int, sites: Sequence[Site], on: Sequence[bool]
) -> Optional[PCGGraph]:
    from flexflow_tpu.runtime.executor import propagate_shapes

    g = base.copy()
    try:
        _annotate_data_parallel(g, dp)
        for site, enabled in zip(sites, on):
            if enabled:
                site.apply(g, tp, _MODEL_AXIS)
        # partition-move peephole (create_partition_*_combine analogs):
        # must run here AND in the strategy lowering (site_strategy) so
        # the costed candidate is the executed graph
        from flexflow_tpu.search.peephole import sink_combines

        sink_combines(g)
        propagate_shapes(g)
    except (ValueError, KeyError):
        return None
    return g


def _mesh_factorizations(num_devices: int) -> List[Tuple[int, int]]:
    """(dp, tp) pairs with dp*tp == num_devices (reference enumerates
    divisor-sized machine views, graph.cc:1783-1814)."""
    out = []
    for tp in range(1, num_devices + 1):
        if num_devices % tp == 0:
            out.append((num_devices // tp, tp))
    return out


def _second_axis_candidate(
    base: PCGGraph, strategy, dp: int, deg: int, cm: CostModel, spec
) -> Optional[GraphCost]:
    """Cost a (dp, <axis>) mesh strategy (seq or spatial): the second
    axis must actually shard some input dim, else this is pure dp on a
    bigger mesh (idle chips) — never profitable, skip."""
    from flexflow_tpu.runtime.executor import propagate_shapes

    g = base.copy()
    try:
        strategy.apply(g)
        propagate_shapes(g)
    except (ValueError, KeyError):
        return None
    sharded = any(
        d.degree == deg and d.parallel_idx == 1
        for n in g.nodes.values()
        if n.op_type == OperatorType.INPUT
        for d in n.output_shapes[0].dims
    )
    if not sharded:
        return None
    cost = estimate_graph_cost(g, cm, (dp, deg))
    return cost if cost.feasible(spec) else None


def _seq_candidate(
    base: PCGGraph, dp: int, sp: int, cm: CostModel, spec,
    seq_mode: str = "ring",
) -> Optional[GraphCost]:
    """Cost a (dp, sp) sequence-parallel mesh: inputs' seq dim sharded on
    axis 1; attention pays the ring-exchange or Ulysses all-to-all term
    per seq_mode (CostModel.op_cost reads the node's seq_parallel)."""
    from flexflow_tpu.parallel.strategy import sequence_parallel_strategy

    return _second_axis_candidate(
        base,
        sequence_parallel_strategy(dp, sp, seq_mode=seq_mode),
        dp,
        sp,
        cm,
        spec,
    )


def _spatial_candidate(
    base: PCGGraph, dp: int, hp: int, cm: CostModel, spec
) -> Optional[GraphCost]:
    """Cost a (dp, spatial) mesh: image inputs' H dim sharded on axis 1;
    convs ride GSPMD's windowed-op halo exchange (reference:
    --enable-attribute-parallel, model.cc:3602 — partition non-sample
    activation dims)."""
    from flexflow_tpu.parallel.strategy import spatial_parallel_strategy

    return _second_axis_candidate(
        base, spatial_parallel_strategy(dp, hp), dp, hp, cm, spec
    )


def _pipeline_candidate(
    base: PCGGraph, structure, dp: int, pp: int, mb: int, cm: CostModel,
    spec: MachineSpec = None,
) -> Optional[GraphCost]:
    """Analytic GPipe cost of a (dp, pipe) mesh: per-stage compute is the
    trunk's dp-sharded cost / pp, schedule stretch is the GPipe bubble
    (m + pp - 1)/m (parallel/pipeline.pipeline_bubble_fraction), plus
    boundary ppermute hops and the dp gradient all-reduce."""
    from flexflow_tpu.runtime.executor import propagate_shapes

    if structure.num_blocks % pp != 0:
        return None
    # the executor rejects trunks with host/aux hooks (cache memoizer,
    # MoE balance loss — PipelinedExecutor.__init__); don't propose
    # candidates guaranteed to fail compile
    for blk in structure.blocks:
        for gg in blk:
            n = base.nodes[gg]
            if n.op_type == OperatorType.CACHE:
                return None
            if n.op_type in (
                OperatorType.AGGREGATE,
                OperatorType.AGGREGATE_SPEC,
            ) and float(n.params.get("lambda_bal", 0.0)) > 0.0:
                return None
    g = base.copy()
    try:
        _annotate_data_parallel(g, dp)
        propagate_shapes(g)
    except (ValueError, KeyError):
        return None
    block_guids = {gg for blk in structure.blocks for gg in blk}
    trunk = 0.0
    trunk_fwd = 0.0
    rest = 0.0
    sync = 0.0
    update = 0.0
    trunk_weight_bytes = 0.0
    rest_weight_bytes = 0.0
    act_bytes = 0.0
    trunk_act_bytes = 0.0
    for guid, node in g.nodes.items():
        if node.op_type == OperatorType.INPUT or node.is_parallel_op:
            continue
        in_shapes = [g.shape_of(r) for r in node.inputs]
        c = sparse_embedding_node_cost(g, guid, node, cm)
        sparse_table = c is not None
        if c is None:
            c = cm.op_cost(node, in_shapes)
        t = c.forward_time + c.backward_time
        out_bytes = sum(s.piece_bytes() for s in node.output_shapes)
        act_bytes += out_bytes
        if guid in block_guids:
            trunk += t
            trunk_fwd += c.forward_time
            trunk_act_bytes += out_bytes
        else:
            rest += t
        sp_rows = _sparse_embedding_rows(g, guid) if sparse_table else None
        for w in node.weight_shapes:
            # grads only need reducing over the dp replicas that
            # computed them
            if guid in block_guids:
                trunk_weight_bytes += w.piece_bytes()
            else:
                rest_weight_bytes += w.piece_bytes()
            if sparse_table:
                # no table-sized gradient ever materializes: no grad
                # all-reduce, touched-rows update only (same basis as
                # estimate_graph_cost's weight loop) + the touched-row
                # all-gather over the dp replicas (sparse_sync_cost)
                update += cm.sparse_update_cost(w, sp_rows)
                if dp > 1:
                    sync += cm.sparse_sync_cost(
                        sp_rows * w.dims[-1].piece_size * w.dtype.size_bytes,
                        dp,
                    )
                continue
            if dp > 1:
                sync += cm.all_reduce(cm.piece_bytes(w), dp)
            update += cm.update_cost(w)
    stage = trunk / pp
    stretch = (mb + pp - 1) / mb
    exit_shape = g.shape_of(TensorRef(structure.blocks[-1][-1], 0))
    boundary_bytes = exit_shape.piece_volume() * cm.elem_bytes(exit_shape)
    hop_bytes = boundary_bytes / mb
    hops = 2.0 * (mb + pp - 2) * cm._ici_time(hop_bytes) if pp > 1 else 0.0
    # compute and hop transfers overlap in the schedule (a stage sends
    # microbatch i while computing i+1): the trunk is bounded by whichever
    # resource saturates, not their sum
    trunk_time = max(stage * stretch, hops)
    # trunk weights (+grads+opt state, the 3.0) are STACKED and sharded
    # over the pipe axis (runtime/pipeline_executor.py storage), so each
    # chip holds 1/pp of them; prologue/epilogue weights replicate.
    weight_mem = rest_weight_bytes * 3.0 + trunk_weight_bytes * 3.0 / pp
    # activation residuals: gpipe stores each block's internals; 1f1b
    # remats block bodies, keeping only stage-boundary activations per
    # in-flight microbatch (PipelineSpec.schedule)
    mem_gpipe = int(weight_mem + act_bytes / pp)
    mem_1f1b = int(
        weight_mem
        + (act_bytes - trunk_act_bytes) / pp
        + boundary_bytes * (structure.num_blocks / pp)
    )
    schedule = "gpipe"
    memory = mem_gpipe
    if spec is not None:
        probe = GraphCost(0, 0, 0, 0, 0, memory_per_chip=mem_gpipe)
        if not probe.feasible(spec) and mem_1f1b < mem_gpipe:
            schedule = "1f1b"
            memory = mem_1f1b
            # remat recomputes each block's forward during the backward
            # (jax.checkpoint in pipeline_executor._block_fn) — the
            # memory saving is not free
            trunk_time = max((trunk + trunk_fwd) / pp * stretch, hops)
    # one program launch per step, same basis as estimate_graph_cost's
    # step_floor — without it pipeline candidates would carry a
    # one-floor advantage over every simulator-priced candidate
    step_floor = cm.dispatch_floor() if cm.measure else 0.0
    cost = GraphCost(
        step_time=rest + trunk_time + sync + update + step_floor,
        compute_time=rest + trunk,
        comm_time=hops,
        sync_time=sync,
        update_time=update,
        memory_per_chip=memory,
    )
    if spec is not None and not cost.feasible(spec):
        return None
    cost.schedule = schedule
    return cost


def _mixed_candidate(
    base: PCGGraph, num_devices: int, tp: int, sites, cm: CostModel, spec
) -> Optional[GraphCost]:
    """Cost the heterogeneous lowering (parallel.strategy.
    mixed_site_strategy): TP sites on the model axis, everything else
    FULL-width data-parallel — the reference's per-op MachineView pattern
    (graph.cc:1346-1431, e.g. DLRM sharded tables + dp MLPs)."""
    from flexflow_tpu.parallel.strategy import mixed_site_strategy
    from flexflow_tpu.runtime.executor import propagate_shapes

    strategy = mixed_site_strategy(base, num_devices, tp, sites)
    if "mixed" not in strategy.name:
        return None  # fell back to the uniform lowering: already covered
    g = base.copy()
    try:
        strategy.apply(g)
        propagate_shapes(g)
    except (ValueError, KeyError):
        return None
    cost = estimate_graph_cost(g, cm, strategy.mesh_config.axis_sizes)
    return cost if cost.feasible(spec) else None


class SearchResult:
    """One searched configuration. kind ∈ {"tp", "seq", "pipeline",
    "mixed", "spatial"}: which parallel axis family the second mesh axis carries
    (VERDICT r1 item 2 — the search explores pp/sp/ep, not just dp×tp;
    ep rides the "tp" kind through ExpertParallelSite on the model axis;
    "mixed" is the heterogeneous per-op lowering, VERDICT r1 item 8)."""

    def __init__(self, dp, tp, sites, on, cost: GraphCost, kind="tp",
                 extra=None):
        self.dp = dp
        self.tp = tp
        self.sites = list(sites)
        self.on = list(on)
        self.cost = cost
        self.kind = kind
        self.extra = dict(extra or {})

    def describe(self) -> str:
        if self.kind == "mixed":
            return (
                f"mixed mesh(data={self.dp}, model={self.tp}), "
                f"{len(self.sites)} TP sites + full-width dp, simulated "
                f"step {self.cost.step_time * 1e3:.3f} ms"
            )
        if self.kind == "seq":
            mode = self.extra.get("seq_mode", "ring")
            return (
                f"mesh(data={self.dp}, seq={self.extra['sp']}), {mode} "
                f"attention, simulated step {self.cost.step_time * 1e3:.3f} ms"
            )
        if self.kind == "spatial":
            return (
                f"mesh(data={self.dp}, spatial={self.extra['hp']}), "
                f"simulated step {self.cost.step_time * 1e3:.3f} ms"
            )
        if self.kind == "pipeline":
            sched = self.extra.get("schedule", "gpipe")
            return (
                f"mesh(data={self.dp}, pipe={self.extra['pp']}), "
                f"{self.extra['num_blocks']} blocks, "
                f"{self.extra['mb']} microbatches ({sched}), simulated "
                f"step {self.cost.step_time * 1e3:.3f} ms"
            )
        n_on = sum(self.on)
        return (
            f"mesh(data={self.dp}, model={self.tp}), {n_on}/{len(self.on)} "
            f"TP sites, simulated step {self.cost.step_time * 1e3:.3f} ms"
        )


def extra_axis_candidates(
    graph: PCGGraph,
    num_devices: int,
    cm: CostModel,
    spec: MachineSpec,
    attribute_parallel: bool = False,
    verbose: bool = False,
    trace=None,
):
    """The strategy families BEYOND the dp×tp grid — mixed (heterogeneous
    per-op), sequence (ring/Ulysses), spatial, pipeline. Shared by the
    mesh engine's optimize() and by the unity/mcmc entries, so every
    engine covers the whole space its runtime can execute (the reference
    has ONE search over everything its runtime does,
    substitution.cc:1721-1862). Returns (results, evals). `trace`
    (telemetry.SearchTrace) records each feasible candidate with its
    GraphCost breakdown."""
    results = []
    evals = 0

    def _rec(cur: "SearchResult") -> None:
        if trace is None:
            return
        c = cur.cost
        descr = cur.describe()  # fresh string — rows hold no live state
        trace.candidate(
            "extra_axis",
            name=descr,
            dp=cur.dp,
            step_time=c.step_time,
            compute_time=c.compute_time,
            comm_time=c.comm_time,
            sync_time=c.sync_time,
            update_time=c.update_time,
            memory_per_chip=float(c.memory_per_chip),
            feasible=bool(c.feasible(spec)),
        )

    # heterogeneous candidates: TP sites on the model axis, everything
    # else full-width data-parallel (reference: per-op MachineViews,
    # graph.cc:1346-1431 — the DLRM sharded-tables + dp-MLPs pattern)
    for _dp, tp in _mesh_factorizations(num_devices):
        if tp == 1:
            continue
        all_sites = [
            s for s in find_tp_sites(graph) if s.divisible_by(graph, tp)
        ]
        if not all_sites:
            continue
        # try sharding just the weight-heaviest site class (embeddings
        # first — the canonical mixed pattern) and the full site set
        from flexflow_tpu.search.rewrites import EmbeddingSite

        emb_sites = [s for s in all_sites if isinstance(s, EmbeddingSite)]
        for sites in ([emb_sites] if emb_sites else []) + [all_sites]:
            evals += 1
            cost = _mixed_candidate(graph, num_devices, tp, sites, cm, spec)
            if cost is None:
                continue
            cur = SearchResult(
                num_devices // tp, tp, sites, [True] * len(sites), cost,
                kind="mixed",
            )
            if verbose:
                print(f"[search] {cur.describe()}")
            _rec(cur)
            results.append(cur)

    # sequence-parallel candidates: (dp, sp) meshes with ring attention
    # (beyond-reference axis; the reference's seq dim is shardable but no
    # substitution ever exploits it, SURVEY §2.4)
    from flexflow_tpu.parallel.strategy import ulysses_eligible

    for dp, sp in _mesh_factorizations(num_devices):
        if sp == 1:
            continue
        modes = ["ring"]
        if any(ulysses_eligible(n, sp) for n in graph.nodes.values()):
            modes.append("ulysses")
        for seq_mode in modes:
            evals += 1
            cost = _seq_candidate(graph, dp, sp, cm, spec, seq_mode=seq_mode)
            if cost is None:
                continue
            cur = SearchResult(
                dp, 1, [], [], cost, kind="seq",
                extra={"sp": sp, "seq_mode": seq_mode},
            )
            if verbose:
                print(f"[search] {cur.describe()}")
            _rec(cur)
            results.append(cur)

    # attribute/spatial candidates: image H over the second axis
    # (reference: --enable-attribute-parallel opt-in, model.cc:3602)
    if attribute_parallel:
        for dp, hp in _mesh_factorizations(num_devices):
            if hp == 1:
                continue
            evals += 1
            cost = _spatial_candidate(graph, dp, hp, cm, spec)
            if cost is None:
                continue
            cur = SearchResult(
                dp, 1, [], [], cost, kind="spatial", extra={"hp": hp}
            )
            if verbose:
                print(f"[search] {cur.describe()}")
            _rec(cur)
            results.append(cur)

    # pipeline candidates: (dp, pipe) meshes over a repeated-block trunk
    # (reference declares OP_PIPELINE only, ffconst.h:151)
    from flexflow_tpu.search.blocks import find_block_structure

    structure = find_block_structure(graph)
    if structure is not None:
        for dp, pp in _mesh_factorizations(num_devices):
            if pp == 1:
                continue
            for mb in (4, 8):
                evals += 1
                cost = _pipeline_candidate(
                    graph, structure, dp, pp, mb, cm, spec
                )
                if cost is None:
                    continue
                cur = SearchResult(
                    dp, 1, [], [], cost, kind="pipeline",
                    extra={
                        "pp": pp,
                        "mb": mb,
                        "num_blocks": structure.num_blocks,
                        "schedule": getattr(cost, "schedule", "gpipe"),
                    },
                )
                if verbose:
                    print(f"[search] {cur.describe()}")
                _rec(cur)
                results.append(cur)

    return results, evals


def optimize(
    graph: PCGGraph,
    num_devices: int,
    spec: MachineSpec,
    budget: int = 10,
    alpha: float = 1.05,
    measure: bool = False,
    seed: int = 0,
    verbose: bool = False,
    machine_model=None,
    mixed_precision: bool = False,
    calibration_file: str = "",
    attribute_parallel: bool = False,
    sparse_embedding: bool = True,
    _explore_fuse: bool = True,
    trace=None,
) -> SearchResult:
    """Run the search on a PCG; returns the best found configuration.

    _explore_fuse: also search the activation-fused variant of the graph
    (peephole.fuse_linear_activation — create_linear_relu_merge analog)
    and keep whichever graph's best strategy wins; the winning result
    carries extra={"fuse": True} so the lowering fuses before applying
    sites (whose guids were found on the fused graph).

    trace: an optional telemetry.SearchTrace — every candidate the
    mesh × rewrite-site search scores lands in it with its full
    GraphCost breakdown (via estimate_graph_cost's trace hook)."""
    cm = CostModel(
        spec,
        measure=measure,
        machine_model=machine_model,
        mixed_precision=mixed_precision,
        calibration_file=calibration_file,
        sparse_embedding=sparse_embedding,
    )
    rng = random.Random(seed)
    evals = 0
    best: Optional[SearchResult] = None

    def evaluate(dp, tp, sites, on) -> Optional[GraphCost]:
        nonlocal evals
        evals += 1
        g = _candidate_graph(graph, dp, tp, sites, on)
        if g is None:
            return None
        mesh_sizes = (dp, tp) if tp > 1 else (dp,)
        cost = estimate_graph_cost(
            g, cm, mesh_sizes, trace=trace,
            trace_label=f"mesh(dp={dp},tp={tp},sites_on={sum(on)})",
        )
        if not cost.feasible(spec):
            return None
        return cost

    # dp-only candidates that deliberately leave chips idle (a dp smaller
    # than the chip count): with a tiny batch the full mesh may be
    # unusable, and an idle-chip dp baseline must still beat a forced
    # full-mesh candidate (the reference searches device SUBSETS via
    # MachineResource splits, graph.cc:252-306)
    idle_dps = [
        (d, 1)
        for d in range(1, num_devices)
        if num_devices % d == 0
    ]
    for dp, tp in idle_dps + _mesh_factorizations(num_devices):
        sites = [
            s for s in find_tp_sites(graph) if tp == 1 or s.divisible_by(graph, tp)
        ]
        if tp > 1 and not sites:
            continue
        on = [False] * len(sites)
        cost = evaluate(dp, tp, sites, on)
        if cost is None:
            continue
        cur = SearchResult(dp, tp, sites, on, cost)
        if tp > 1:
            # greedy forward pass over sites in graph order
            for i in range(len(sites)):
                trial = list(cur.on)
                trial[i] = True
                c = evaluate(dp, tp, sites, trial)
                if c is not None and c.step_time < cur.cost.step_time:
                    cur = SearchResult(dp, tp, sites, trial, c)
        if verbose:
            print(f"[search] {cur.describe()}")
        if best is None or cur.cost.step_time < best.cost.step_time:
            best = cur

    extra_results, extra_evals = extra_axis_candidates(
        graph, num_devices, cm, spec,
        attribute_parallel=attribute_parallel, verbose=verbose,
    )
    evals += extra_evals
    for cur in extra_results:
        if best is None or cur.cost.step_time < best.cost.step_time:
            best = cur

    if best is None:
        raise RuntimeError("search found no feasible strategy")

    # MCMC refinement with the remaining budget (reference: mcmc_optimize)
    cur = best
    while evals < budget and cur.kind == "tp" and cur.sites:
        i = rng.randrange(len(cur.sites))
        trial = list(cur.on)
        trial[i] = not trial[i]
        c = evaluate(cur.dp, cur.tp, cur.sites, trial)
        if c is None:
            continue
        delta = c.step_time - cur.cost.step_time
        scale = max(cur.cost.step_time, 1e-9)
        if delta < 0 or rng.random() < math.exp(-alpha * delta / scale):
            cur = SearchResult(cur.dp, cur.tp, cur.sites, trial, c)
        if cur.cost.step_time < best.cost.step_time:
            best = cur

    # the fuse rewrite as a searched graph variant (reference: the
    # create_linear_relu_merge xfer competes inside base_optimize)
    if _explore_fuse:
        from flexflow_tpu.search.peephole import fuse_linear_activation

        fused = graph.copy()
        if fuse_linear_activation(fused):
            fbest = optimize(
                fused, num_devices, spec, budget=budget, alpha=alpha,
                measure=measure, seed=seed, verbose=verbose,
                machine_model=machine_model,
                mixed_precision=mixed_precision,
                calibration_file=calibration_file,
                attribute_parallel=attribute_parallel,
                sparse_embedding=sparse_embedding,
                _explore_fuse=False,
                trace=trace,
            )
            if fbest.cost.step_time < best.cost.step_time:
                fbest.extra["fuse"] = True
                best = fbest

    return best


def result_to_strategy(result: SearchResult, graph: PCGGraph) -> Strategy:
    """Lower via the shared searched-strategy builders; the search already
    validated dp feasibility through _candidate_graph, so site_strategy's
    effective-dp clamp resolves to result.dp."""
    from flexflow_tpu.parallel.strategy import (
        pipeline_strategy,
        sequence_parallel_strategy,
        site_strategy,
    )

    if result.extra.get("fuse"):
        # the winning strategy was found on the activation-fused graph:
        # fuse first (guid-stable), then lower the rest of the result
        from flexflow_tpu.search.peephole import fuse_linear_activation

        inner = result_to_strategy(
            SearchResult(
                result.dp, result.tp, result.sites, result.on,
                result.cost, result.kind,
                {k: v for k, v in result.extra.items() if k != "fuse"},
            ),
            graph,
        )
        orig_apply = inner._apply

        def apply(g):
            fuse_linear_activation(g)
            if orig_apply is not None:
                orig_apply(g)

        inner._apply = apply
        inner.name = f"{inner.name} + fused activations"
        return inner

    prefix = f"searched({result.cost.step_time * 1e3:.3f} ms)"
    if result.kind == "mixed":
        from flexflow_tpu.parallel.strategy import mixed_site_strategy

        return mixed_site_strategy(
            graph,
            result.dp * result.tp,
            result.tp,
            result.sites,
            name_prefix=prefix,
        )
    if result.kind == "seq":
        s = sequence_parallel_strategy(
            result.dp,
            result.extra["sp"],
            graph,
            seq_mode=result.extra.get("seq_mode", "ring"),
        )
        s.name = f"{prefix}: {s.name}"
        return s
    if result.kind == "spatial":
        from flexflow_tpu.parallel.strategy import spatial_parallel_strategy

        s = spatial_parallel_strategy(result.dp, result.extra["hp"], graph)
        s.name = f"{prefix}: {s.name}"
        return s
    if result.kind == "pipeline":
        return pipeline_strategy(
            graph,
            result.dp,
            result.extra["pp"],
            num_microbatches=result.extra["mb"],
            schedule=result.extra.get("schedule", "gpipe"),
            name_prefix=prefix,
        )
    sites = [s for s, enabled in zip(result.sites, result.on) if enabled]
    return site_strategy(
        graph,
        result.dp * result.tp,
        result.tp,
        sites,
        name_prefix=prefix,
    )


# -- serving (decode-regime) search -----------------------------------------
#
# The training search above minimizes one TRAIN step; a serving deployment
# minimizes the per-token decode latency of flexflow_tpu.serving's engine,
# which lives in the weight-bandwidth-bound regime CostModel.decode_op_cost
# prices. The two regimes pick different strategies on the same model and
# machine: at decode batch 1 a dp mesh leaves every chip but one idle while
# TP over heads divides the dominant weight-read term, so TP wins — the
# inverse of the training verdict, where dp's gradient all-reduce is cheap
# next to the compute it parallelizes.

# ops whose weights a serving candidate shards on the model axis, with the
# divisibility rule the candidate must satisfy
_DECODE_TP_OPS = {
    OperatorType.LINEAR: lambda n: int(n.params["out_features"]),
    OperatorType.MULTIHEAD_ATTENTION: lambda n: int(n.params["num_heads"]),
    OperatorType.EMBEDDING: lambda n: int(n.params["out_dim"]),
}

class ServingSearchResult:
    """One costed serving configuration (mesh + per-token step time).

    `max_in_flight` (filled when the caller supplies a prompt/generation
    length distribution) is the capacity estimate: how many concurrent
    sequences of that profile the per-chip cache byte budget holds under
    the priced KV layout — the number the paged cache exists to raise.
    It prices each sequence at its steady-state footprint, i.e. the
    capacity OPTIMISTIC admission reaches; `max_in_flight_reserve` is
    the same budget divided by the worst case the preemption-free
    reserve gate charges (prompt + full max_new_tokens budget), so the
    gap between the two numbers is exactly what switching
    `--admission optimistic` buys — at the price of occasional
    preemption-by-recompute (estimate_recompute_step)."""

    def __init__(
        self,
        dp: int,
        tp: int,
        batch: int,
        kv_len: int,
        cost,
        page_size: int = 0,
        max_in_flight: Optional[int] = None,
        max_in_flight_reserve: Optional[int] = None,
    ):
        self.dp = dp
        self.tp = tp
        self.batch = batch
        self.kv_len = kv_len
        self.cost = cost
        self.page_size = page_size
        self.max_in_flight = max_in_flight
        self.max_in_flight_reserve = max_in_flight_reserve
        # Which mesh the engine will ACTUALLY execute. The search alone
        # does not apply anything — serving inherits the training
        # strategy's sharding unless `FFModel.compile_for_serving` flips
        # this to "applied" after placing weights and pools on the
        # searched mesh. Exported docs and --explain carry it so the
        # explain path cannot report a mesh the runtime ignored.
        self.mesh_execution = "inherited"

    @property
    def tokens_per_s(self) -> float:
        return self.batch / self.cost.step_time if self.cost.step_time else 0.0

    def to_doc(self) -> dict:
        """Exportable summary of the search winner (embedded in the
        serving placement doc by compile_for_serving)."""
        return {
            "kind": "serving-search",
            "dp": self.dp,
            "tp": self.tp,
            "batch": self.batch,
            "kv_len": self.kv_len,
            "page_size": self.page_size,
            "step_time_us": self.cost.step_time * 1e6,
            "max_in_flight": self.max_in_flight,
            "max_in_flight_reserve": self.max_in_flight_reserve,
            "mesh_execution": self.mesh_execution,
        }

    def describe(self) -> str:
        layout = f", pages of {self.page_size}" if self.page_size else ""
        fit = (
            f", ~{self.max_in_flight} seqs fit"
            if self.max_in_flight is not None
            else ""
        )
        if self.max_in_flight_reserve is not None:
            fit += f" ({self.max_in_flight_reserve} under reserve admission)"
        return (
            f"serving mesh(data={self.dp}, model={self.tp}) "
            f"[{self.mesh_execution}], batch "
            f"{self.batch}, kv {self.kv_len}{layout}: decode step "
            f"{self.cost.step_time * 1e6:.1f} us, "
            f"{self.tokens_per_s:.0f} tokens/s{fit}"
        )


def _serving_cache_geometry(graph: PCGGraph):
    """(attention guids, pools, heads, head_dim) of the graph's
    attention layers — the cache geometry the capacity estimate needs,
    each layer's row sized by the cache's own `cache_row`."""
    from flexflow_tpu.serving.kv_cache import CACHED_ATTENTION, cache_row

    guids, geom = [], set()
    for g, node in graph.nodes.items():
        if node.op_type not in CACHED_ATTENTION:
            continue
        guids.append(g)
        geom.add(cache_row(node))
    if len(geom) != 1:
        raise ValueError(
            "attention layers disagree on (pools, heads, head_dim): "
            f"{geom or '∅'}"
        )
    return (tuple(guids),) + geom.pop()


def resolve_decode_kernel(
    mode: str, graph: PCGGraph, kv_len: int, page_size: int = 0, w: int = 1
) -> str:
    """Resolve a ServeConfig.decode_kernel mode into the cost term to
    price ("pallas" or "dense") for this graph's cache geometry —
    the search-side mirror of the runtime selection in
    ops/pallas/decode_kernel.use_kernel, so optimize_serving and
    optimize_spec_k rank strategies with the cost shape the engine
    will actually run."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk

    _, _, _, head_dim = _serving_cache_geometry(graph)
    if dk.use_kernel(mode, w, kv_len, head_dim, page_size):
        return "pallas"
    return "dense"


def estimate_max_in_flight(
    graph: PCGGraph,
    cache_bytes: int,
    mean_prompt_len: int,
    mean_gen_len: int,
    max_len: int,
    page_size: int = 0,
    tp: int = 1,
    itemsize: int = 4,
    admission: str = "optimistic",
    max_new_tokens: Optional[int] = None,
    kv_dtype: str = "fp32",
    prefix_hit_rate: float = 0.0,
) -> int:
    """How many concurrent sequences with the measured length profile
    (mean_prompt_len + mean_gen_len cached tokens each) fit in a
    per-chip KV byte budget.

    Prices the cache through KVCacheSpec.total_bytes (one-sequence
    spec): a sequence is charged ceil((prompt + gen) / page_size) whole
    pages; page_size 0 charges it max_len rows, one page as long as the
    sequence may grow — the per-request footprint difference that lets
    small pages admit more short requests at the same budget. TP over
    heads divides the per-chip row size, so a TP mesh fits
    proportionally more.

    `admission` picks WHICH per-sequence charge divides the budget:
    "optimistic" (the default, and the only policy a steady-state
    footprint can reach) charges each sequence the pages its profile
    actually fills; "reserve" charges the worst case the preemption-free
    gate holds back — prompt + the full `max_new_tokens` budget
    (defaulting to mean_gen_len, i.e. a workload that declares exactly
    what it uses). The ratio of the two answers is the concurrency
    headroom `--admission optimistic` unlocks on budget-declaring-but-
    short-finishing traffic (requests that reserve 256 tokens and emit
    20).

    `kv_dtype="int8"` prices the quantized paged pools: 1-byte K/V rows
    plus the fp32 per-(page, head) dequant scales in the side pools —
    just under 4x the sequences at the same budget. `prefix_hit_rate`
    (0..1) discounts the prompt bytes a shared-prefix workload never
    allocates: at hit rate h each admission charges (1-h)·prompt fresh
    tokens; the shared remainder maps refcounted pages another live
    request already paid for. The discount applies only to the
    "optimistic" charge — the reserve gate admits on worst-case
    divergence (every shared page may COW), so sharing buys it
    nothing."""
    from flexflow_tpu.serving.kv_cache import KVCacheSpec, derive_state

    if admission not in ("reserve", "optimistic"):
        raise ValueError(
            f"admission must be 'reserve' or 'optimistic', got {admission!r}"
        )
    if kv_dtype not in ("fp32", "int8"):
        raise ValueError(f"kv_dtype must be 'fp32' or 'int8', got {kv_dtype!r}")
    if kv_dtype == "int8" and page_size <= 0:
        raise ValueError("kv_dtype='int8' requires a paged layout")
    if not 0.0 <= prefix_hit_rate <= 1.0:
        raise ValueError(
            f"prefix_hit_rate must be in [0, 1], got {prefix_hit_rate}"
        )
    if prefix_hit_rate and page_size <= 0:
        raise ValueError("prefix_hit_rate > 0 requires a paged layout")
    guids, pools, heads, head_dim = _serving_cache_geometry(graph)
    state_guids, state_shapes = derive_state(graph, sorted(graph.nodes))
    heads_chip = max(1, heads // max(1, tp))
    if admission == "reserve":
        budget = max_new_tokens if max_new_tokens is not None else mean_gen_len
        seq_len = min(max_len, int(mean_prompt_len) + int(budget))
    else:
        fresh_prompt = int(round(mean_prompt_len * (1.0 - prefix_hit_rate)))
        seq_len = min(max_len, fresh_prompt + int(mean_gen_len))
    if page_size <= 0:
        page_size = max_len
    one = KVCacheSpec(
        layer_guids=guids,
        max_seqs=1,
        max_len=max_len,
        num_heads=heads_chip,
        head_dim=head_dim,
        buckets=(max_len,),
        page_size=page_size,
        num_pages=-(-max(1, seq_len) // page_size),
        itemsize=1 if kv_dtype == "int8" else itemsize,
        kv_dtype=kv_dtype,
        kv_pools=pools,
        state_guids=state_guids,
        state_shapes=state_shapes,
    )
    # a sequence's pages, and its slot's row of every recurrent layer
    per_seq = one.total_bytes
    return int(cache_bytes // per_seq) if per_seq else 0


def estimate_decode_step(
    graph: PCGGraph,
    cm: CostModel,
    dp: int,
    tp: int,
    batch: int,
    kv_len: int,
    page_size: int = 0,
    decode_kernel: str = "dense",
    kv_dtype: str = "fp32",
) -> Optional[GraphCost]:
    """Cost one decode iteration of the whole PCG under a (dp, tp) mesh;
    None when infeasible (dp doesn't divide the batch, tp doesn't divide
    some sharded op's heads/columns, or the footprint overflows HBM).

    TP sync: each TP-sharded matmul chain resolves its partial sums with
    an all-reduce of the [batch/dp, features] activation. We charge one
    per attention node and one per linear node — an over-count of the
    Megatron column→row pairing (which needs one per PAIR), acceptable
    because decode activations are tiny and the verdict is driven by the
    weight-read term; the over-count only biases AGAINST tp, so a tp
    winner is a conservative conclusion."""
    if batch % dp != 0:
        return None
    b_chip = batch // dp
    compute = 0.0
    sync = 0.0
    mem = 0.0
    for node in graph.nodes.values():
        if node.op_type == OperatorType.INPUT or node.is_parallel_op:
            continue
        width = _DECODE_TP_OPS.get(node.op_type)
        node_tp = tp
        if width is not None and tp > 1:
            if width(node) % tp != 0:
                return None
        elif width is None:
            node_tp = 1
        c = cm.decode_op_cost(
            node, b_chip, kv_len, tp=node_tp, page_size=page_size,
            kernel=decode_kernel, kv_dtype=kv_dtype,
        )
        compute += c.forward_time
        mem += c.memory
        if node_tp > 1 and node.output_shapes:
            out = node.output_shapes[0]
            act = b_chip * out.logical_sizes[-1] * cm.elem_bytes(out)
            sync += cm.all_reduce(float(act), node_tp)
    return GraphCost(
        step_time=compute + sync,
        compute_time=compute,
        sync_time=sync,
        memory_per_chip=int(mem),
    )


def estimate_verify_step(
    graph: PCGGraph,
    cm: CostModel,
    dp: int,
    tp: int,
    batch: int,
    kv_len: int,
    k: int,
    page_size: int = 0,
    decode_kernel: str = "dense",
    kv_dtype: str = "fp32",
    tree_nodes: int = 0,
) -> Optional[GraphCost]:
    """Cost one speculative-decoding VERIFY iteration (k+1 scored token
    positions per sequence, serving/engine.verify) of the whole PCG
    under a (dp, tp) mesh — the spec-decode twin of estimate_decode_step
    (same feasibility rules, same conservative one-all-reduce-per-node
    TP sync charge; the synced activation is (k+1)x wider).
    tree_nodes > 0 prices the token-tree verify's 1 + tree_nodes rows
    instead (CostModel.verify_op_cost's tree_nodes)."""
    if batch % dp != 0:
        return None
    b_chip = batch // dp
    compute = 0.0
    sync = 0.0
    mem = 0.0
    for node in graph.nodes.values():
        if node.op_type == OperatorType.INPUT or node.is_parallel_op:
            continue
        width = _DECODE_TP_OPS.get(node.op_type)
        node_tp = tp
        if width is not None and tp > 1:
            if width(node) % tp != 0:
                return None
        elif width is None:
            node_tp = 1
        c = cm.verify_op_cost(
            node, b_chip, kv_len, k, tp=node_tp, page_size=page_size,
            kernel=decode_kernel, kv_dtype=kv_dtype, tree_nodes=tree_nodes,
        )
        compute += c.forward_time
        mem += c.memory
        if node_tp > 1 and node.output_shapes:
            out = node.output_shapes[0]
            w = (1 + tree_nodes) if tree_nodes > 0 else (k + 1)
            act = b_chip * w * out.logical_sizes[-1] * cm.elem_bytes(out)
            sync += cm.all_reduce(float(act), node_tp)
    return GraphCost(
        step_time=compute + sync,
        compute_time=compute,
        sync_time=sync,
        memory_per_chip=int(mem),
    )


def estimate_recompute_step(
    graph: PCGGraph,
    cm: CostModel,
    dp: int,
    tp: int,
    resume_len: int,
    page_size: int = 0,
    decode_kernel: str = "dense",
) -> Optional[GraphCost]:
    """Cost of recovering ONE preempted sequence by recompute: a single
    prefill-shaped pass over its prompt + generated-so-far
    (`resume_len` positions) against an empty cache — what the
    scheduler's preemption-by-recompute path actually runs
    (serving/scheduler.py re-admission). Optimistic admission pays this
    per preemption event where the reserve policy pays nothing; weigh
    it against the extra concurrency estimate_max_in_flight reports and
    the workload's expected preemption rate. Same feasibility rules as
    estimate_decode_step; None when (dp, tp) is infeasible."""
    if resume_len < 1:
        raise ValueError(f"resume_len must be >= 1, got {resume_len}")
    compute = 0.0
    sync = 0.0
    mem = 0.0
    for node in graph.nodes.values():
        if node.op_type == OperatorType.INPUT or node.is_parallel_op:
            continue
        width = _DECODE_TP_OPS.get(node.op_type)
        node_tp = tp
        if width is not None and tp > 1:
            if width(node) % tp != 0:
                return None
        elif width is None:
            node_tp = 1
        c = cm.prefill_op_cost(
            node, 1, resume_len, tp=node_tp, page_size=page_size,
            kernel=decode_kernel,
        )
        compute += c.forward_time
        mem += c.memory
        if node_tp > 1 and node.output_shapes:
            out = node.output_shapes[0]
            act = resume_len * out.logical_sizes[-1] * cm.elem_bytes(out)
            sync += cm.all_reduce(float(act), node_tp)
    return GraphCost(
        step_time=compute + sync,
        compute_time=compute,
        sync_time=sync,
        memory_per_chip=int(mem),
    )


def expected_accepted_tokens(acceptance_rate: float, k: int) -> float:
    """E[accepted drafts] of a k-token draft under a per-token
    acceptance rate α (independence approximation: the verify accepts a
    geometric prefix, so E = Σ_{i=1..k} α^i). The verify then emits one
    MORE token from the target itself (correction or bonus), so
    expected tokens per verify step is this plus one."""
    a = min(max(float(acceptance_rate), 0.0), 1.0)
    if a >= 1.0:
        return float(k)
    return a * (1.0 - a**k) / (1.0 - a)


class SpecKResult:
    """The draft length optimize_spec_k picked, with the priced
    alternatives. k == 0 means speculation does not pay at this
    acceptance rate (the draft/verify overhead exceeds the accepted
    tokens' worth)."""

    def __init__(
        self,
        k: int,
        acceptance_rate: float,
        tokens_per_s: float,
        decode_tokens_per_s: float,
        step_time: float,
        tokens_per_step: float,
    ):
        self.k = k
        self.acceptance_rate = acceptance_rate
        self.tokens_per_s = tokens_per_s
        self.decode_tokens_per_s = decode_tokens_per_s
        self.step_time = step_time
        self.tokens_per_step = tokens_per_step

    @property
    def speedup(self) -> float:
        """Expected decode-throughput ratio over non-speculative decode."""
        if not self.decode_tokens_per_s:
            return 1.0
        return self.tokens_per_s / self.decode_tokens_per_s

    def describe(self) -> str:
        return (
            f"spec-k {self.k} at acceptance {self.acceptance_rate:.2f}: "
            f"{self.tokens_per_step:.2f} tokens/step, expected "
            f"{self.speedup:.2f}x over plain decode"
        )


def optimize_spec_k(
    graph: PCGGraph,
    spec: MachineSpec,
    acceptance_rate: float,
    batch: int = 1,
    kv_len: int = 1024,
    k_max: int = 8,
    draft_graph: Optional[PCGGraph] = None,
    dp: int = 1,
    tp: int = 1,
    page_size: int = 0,
    machine_model=None,
    mixed_precision: bool = False,
    decode_kernel: str = "dense",
) -> SpecKResult:
    """Pick the draft length k that maximizes expected decode throughput
    at a MEASURED per-token acceptance rate (SchedulerStats
    .acceptance_rate from a spec-mode run, or an offline estimate).

    Prices each candidate k as: one verify step of k+1 positions
    (CostModel.verify_op_cost — weights stream once, the spec-decode
    win) plus the draft cost (k decode steps of `draft_graph` when the
    draft is a model; zero for the weight-free n-gram draft), buying
    1 + E[accepted](α, k) tokens. k = 0 (plain decode) is always a
    candidate, so a hopeless acceptance rate yields "don't speculate"
    rather than a forced k."""
    cm = CostModel(
        spec,
        measure=False,
        machine_model=machine_model,
        mixed_precision=mixed_precision,
    )
    base = estimate_decode_step(
        graph, cm, dp, tp, batch, kv_len, page_size=page_size,
        decode_kernel=decode_kernel,
    )
    if base is None:
        raise ValueError(f"(dp={dp}, tp={tp}) is infeasible for this graph")
    draft_step = 0.0
    if draft_graph is not None:
        d = estimate_decode_step(
            draft_graph, cm, dp, tp, batch, kv_len,
            decode_kernel=decode_kernel,
        )
        if d is None:
            raise ValueError(
                f"(dp={dp}, tp={tp}) is infeasible for the draft graph"
            )
        draft_step = d.step_time
    decode_rate = batch / base.step_time if base.step_time else 0.0
    best = SpecKResult(
        0, acceptance_rate, decode_rate, decode_rate, base.step_time, 1.0
    )
    for k in range(1, k_max + 1):
        vcost = estimate_verify_step(
            graph, cm, dp, tp, batch, kv_len, k, page_size=page_size,
            decode_kernel=decode_kernel,
        )
        if vcost is None:
            continue
        step_time = vcost.step_time + k * draft_step
        tokens = 1.0 + expected_accepted_tokens(acceptance_rate, k)
        rate = batch * tokens / step_time if step_time else 0.0
        if rate > best.tokens_per_s:
            best = SpecKResult(
                k, acceptance_rate, rate, decode_rate, step_time, tokens
            )
    return best


def expected_accepted_tree_tokens(
    acceptance_rate: float, depth: int, branch: int
) -> float:
    """E[accepted root-to-leaf path length] of a (depth, branch) token
    tree under a per-token acceptance rate α. A level survives when ANY
    of its `branch` alternatives matches — α_b = 1 - (1-α)^branch under
    the independence approximation — and the accepted path is a
    geometric prefix of levels, so E = Σ_{i=1..depth} α_b^i. branch = 1
    reduces exactly to expected_accepted_tokens."""
    a = min(max(float(acceptance_rate), 0.0), 1.0)
    ab = 1.0 - (1.0 - a) ** max(1, int(branch))
    if ab >= 1.0:
        return float(depth)
    return ab * (1.0 - ab ** int(depth)) / (1.0 - ab)


class SpecTreeResult:
    """The (depth, branch) draft-tree shape optimize_spec_tree picked.
    branch == 1 means a tree does not pay at this acceptance profile
    (the extra verified nodes cost more than the per-level retry is
    worth) — run the linear chain; depth == 0 means speculation itself
    does not pay."""

    def __init__(
        self,
        depth: int,
        branch: int,
        acceptance_rate: float,
        tokens_per_s: float,
        decode_tokens_per_s: float,
        step_time: float,
        tokens_per_step: float,
    ):
        self.depth = depth
        self.branch = branch
        self.acceptance_rate = acceptance_rate
        self.tokens_per_s = tokens_per_s
        self.decode_tokens_per_s = decode_tokens_per_s
        self.step_time = step_time
        self.tokens_per_step = tokens_per_step

    @property
    def nodes(self) -> int:
        """Verify node budget (tree width minus the root row)."""
        return self.depth * self.branch

    @property
    def speedup(self) -> float:
        if not self.decode_tokens_per_s:
            return 1.0
        return self.tokens_per_s / self.decode_tokens_per_s

    def describe(self) -> str:
        return (
            f"spec-tree depth {self.depth} x branch {self.branch} "
            f"({self.nodes} nodes) at acceptance "
            f"{self.acceptance_rate:.2f}: {self.tokens_per_step:.2f} "
            f"tokens/step, expected {self.speedup:.2f}x over plain decode"
        )


def optimize_spec_tree(
    graph: PCGGraph,
    spec: MachineSpec,
    acceptance_rate: float,
    batch: int = 1,
    kv_len: int = 1024,
    depth_max: int = 8,
    branch_max: int = 4,
    draft_graph: Optional[PCGGraph] = None,
    dp: int = 1,
    tp: int = 1,
    page_size: int = 0,
    machine_model=None,
    mixed_precision: bool = False,
    decode_kernel: str = "dense",
) -> SpecTreeResult:
    """Pick the draft-tree shape (depth, branching factor) that
    maximizes expected decode throughput at a MEASURED per-token
    acceptance rate — the tree twin of optimize_spec_k.

    Prices each (d, b) candidate as: one tree verify of 1 + d*b rows
    (estimate_verify_step with tree_nodes — every node is a scored row
    and a fresh cache row, whatever the topology) plus the draft cost
    (d draft decode steps for a model draft: the spine is decoded once
    and the sibling alternates come from the SAME logits, so branching
    is draft-free; zero for the n-gram draft), buying
    1 + E[path](α, d, b) tokens. (d, 1) candidates subsume the linear
    chain and (0, 1) plain decode, so a profile where trees don't pay
    degrades to optimize_spec_k's answer rather than a forced tree."""
    cm = CostModel(
        spec,
        measure=False,
        machine_model=machine_model,
        mixed_precision=mixed_precision,
    )
    base = estimate_decode_step(
        graph, cm, dp, tp, batch, kv_len, page_size=page_size,
        decode_kernel=decode_kernel,
    )
    if base is None:
        raise ValueError(f"(dp={dp}, tp={tp}) is infeasible for this graph")
    draft_step = 0.0
    if draft_graph is not None:
        d = estimate_decode_step(
            draft_graph, cm, dp, tp, batch, kv_len,
            decode_kernel=decode_kernel,
        )
        if d is None:
            raise ValueError(
                f"(dp={dp}, tp={tp}) is infeasible for the draft graph"
            )
        draft_step = d.step_time
    decode_rate = batch / base.step_time if base.step_time else 0.0
    best = SpecTreeResult(
        0, 1, acceptance_rate, decode_rate, decode_rate, base.step_time, 1.0
    )
    for depth in range(1, depth_max + 1):
        for branch in range(1, branch_max + 1):
            vcost = estimate_verify_step(
                graph, cm, dp, tp, batch, kv_len, depth,
                page_size=page_size, decode_kernel=decode_kernel,
                tree_nodes=depth * branch,
            )
            if vcost is None:
                continue
            step_time = vcost.step_time + depth * draft_step
            tokens = 1.0 + expected_accepted_tree_tokens(
                acceptance_rate, depth, branch
            )
            rate = batch * tokens / step_time if step_time else 0.0
            if rate > best.tokens_per_s:
                best = SpecTreeResult(
                    depth,
                    branch,
                    acceptance_rate,
                    rate,
                    decode_rate,
                    step_time,
                    tokens,
                )
    return best


def estimate_chunk_step(
    graph: PCGGraph,
    cm: CostModel,
    dp: int,
    tp: int,
    batch: int,
    cursor: int,
    chunk: int,
    page_size: int = 0,
    decode_kernel: str = "dense",
) -> Optional[GraphCost]:
    """Cost one chunked-prefill step of the whole PCG under a (dp, tp)
    mesh: `chunk` prompt positions appended at cache cursor `cursor`
    for each of `batch` chunking sequences — the chunk twin of
    estimate_verify_step (a chunk IS a verify with nothing to accept),
    priced through CostModel.prefill_chunk_cost. Same feasibility rules
    and conservative one-all-reduce-per-node TP sync charge."""
    if batch % dp != 0:
        return None
    b_chip = batch // dp
    compute = 0.0
    sync = 0.0
    mem = 0.0
    for node in graph.nodes.values():
        if node.op_type == OperatorType.INPUT or node.is_parallel_op:
            continue
        width = _DECODE_TP_OPS.get(node.op_type)
        node_tp = tp
        if width is not None and tp > 1:
            if width(node) % tp != 0:
                return None
        elif width is None:
            node_tp = 1
        c = cm.prefill_chunk_cost(
            node, b_chip, cursor, chunk, tp=node_tp, page_size=page_size,
            kernel=decode_kernel,
        )
        compute += c.forward_time
        mem += c.memory
        if node_tp > 1 and node.output_shapes:
            out = node.output_shapes[0]
            act = b_chip * chunk * out.logical_sizes[-1] * cm.elem_bytes(out)
            sync += cm.all_reduce(float(act), node_tp)
    return GraphCost(
        step_time=compute + sync,
        compute_time=compute,
        sync_time=sync,
        memory_per_chip=int(mem),
    )


class TokenBudgetResult:
    """The per-iteration token budget optimize_token_budget picked,
    with the prediction it was picked on. `meets_slo` reports whether
    the predicted latencies clear the thresholds — False means no
    candidate could, and the returned budget is the least-violating
    one (scheduling cannot beat physics: if one decode iteration
    already exceeds slo_itl_ms, no budget fixes it)."""

    def __init__(
        self,
        token_budget: int,
        chunk_size: int,
        predicted_ttft_s: float,
        predicted_itl_s: float,
        n_chunks: int,
        meets_slo: bool,
        slo_ttft_s: float,
        slo_itl_s: float,
    ):
        self.token_budget = token_budget
        self.chunk_size = chunk_size
        self.predicted_ttft_s = predicted_ttft_s
        self.predicted_itl_s = predicted_itl_s
        self.n_chunks = n_chunks
        self.meets_slo = meets_slo
        self.slo_ttft_s = slo_ttft_s
        self.slo_itl_s = slo_itl_s

    def describe(self) -> str:
        verdict = "meets SLO" if self.meets_slo else "SLO infeasible"
        return (
            f"token-budget {self.token_budget} (chunk {self.chunk_size}, "
            f"{self.n_chunks} chunks): predicted TTFT "
            f"{self.predicted_ttft_s * 1e3:.2f} ms, ITL "
            f"{self.predicted_itl_s * 1e3:.2f} ms — {verdict}"
        )


def optimize_token_budget(
    graph: PCGGraph,
    spec: MachineSpec,
    prompt_len: int,
    batch: int = 1,
    kv_len: int = 1024,
    chunk_size: int = 16,
    slo_ttft_ms: float = 0.0,
    slo_itl_ms: float = 0.0,
    dp: int = 1,
    tp: int = 1,
    page_size: int = 0,
    machine_model=None,
    mixed_precision: bool = False,
    decode_kernel: str = "dense",
    measured_decode_step_s: float = 0.0,
) -> TokenBudgetResult:
    """Pick the smallest per-iteration token budget whose PREDICTED
    p95 latencies meet the SLO thresholds — the enforcement half of the
    SLO story (PR 8's rolling `serve_slo_*` windows are the
    measurement half; `--slo-ttft-ms`/`--slo-itl-ms` feed both).

    The model mirrors the scheduler's fair-share planner: with `batch`
    decodes in flight (1 token each, reserved first), a budget B leaves
    floor((B - batch) / chunk_size) chunk_size-units per iteration for
    a `prompt_len` prompt, so the prompt lands in n_chunks iterations.
    Each iteration is priced as one decode step over the in-flight
    batch (estimate_decode_step) plus one chunk step at the advancing
    cursor (estimate_chunk_step / CostModel.prefill_chunk_cost):
    predicted TTFT = Σ iterations until the last chunk, predicted ITL =
    the widest single iteration a decode waits through. Smaller budgets
    lower ITL and raise TTFT; the smallest feasible budget is the
    SLO-safest point of that trade. When NO budget meets both
    thresholds the least-violating one returns with meets_slo=False.

    `measured_decode_step_s` calibrates the analytic clock against a
    measured per-iteration time (the rolling ITL window's p95 from an
    unchunked run, or SchedulerStats.mean_dispatch_gap_s): every
    predicted time scales by measured / analytic-decode-step, so the
    roofline model contributes the RATIOS between candidate budgets
    while the measurement pins the absolute scale — measure-then-decide
    applied to the scheduler itself."""
    if prompt_len < 1:
        raise ValueError(f"prompt_len must be >= 1, got {prompt_len}")
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    cm = CostModel(
        spec,
        measure=False,
        machine_model=machine_model,
        mixed_precision=mixed_precision,
    )
    dec_batch = max(0, int(batch))
    t_dec = 0.0
    if dec_batch:
        base = estimate_decode_step(
            graph, cm, dp, tp, dec_batch, kv_len, page_size=page_size,
            decode_kernel=decode_kernel,
        )
        if base is None:
            raise ValueError(
                f"(dp={dp}, tp={tp}) is infeasible for this graph"
            )
        t_dec = base.step_time
    scale = 1.0
    if measured_decode_step_s > 0.0 and t_dec > 0.0:
        scale = measured_decode_step_s / t_dec
    slo_ttft_s = slo_ttft_ms / 1e3
    slo_itl_s = slo_itl_ms / 1e3
    n_units_max = -(-prompt_len // chunk_size)
    best: Optional[TokenBudgetResult] = None
    best_score = float("inf")
    for m in range(1, n_units_max + 1):
        c = m * chunk_size  # chunk tokens granted per iteration
        budget = dec_batch + c
        n_chunks = -(-prompt_len // c)
        ttft = 0.0
        itl = t_dec
        for i in range(n_chunks):
            cursor = i * c
            w = min(c, prompt_len - cursor)
            ch = estimate_chunk_step(
                graph, cm, dp, tp, 1, cursor, w, page_size=page_size,
                decode_kernel=decode_kernel,
            )
            if ch is None:
                raise ValueError(
                    f"(dp={dp}, tp={tp}) is infeasible for this graph"
                )
            ttft += t_dec + ch.step_time
            itl = max(itl, t_dec + ch.step_time)
        ttft *= scale
        itl *= scale
        # score: worst normalized SLO ratio (an unset threshold does
        # not constrain); <= 1 means both thresholds are met
        score = 0.0
        if slo_ttft_s:
            score = max(score, ttft / slo_ttft_s)
        if slo_itl_s:
            score = max(score, itl / slo_itl_s)
        cand = TokenBudgetResult(
            token_budget=budget,
            chunk_size=chunk_size,
            predicted_ttft_s=ttft,
            predicted_itl_s=itl,
            n_chunks=n_chunks,
            meets_slo=score <= 1.0,
            slo_ttft_s=slo_ttft_s,
            slo_itl_s=slo_itl_s,
        )
        if cand.meets_slo:
            # smallest feasible budget: the SLO-safest point — later
            # (larger) candidates only raise the per-iteration stall
            return cand
        if score < best_score:
            best, best_score = cand, score
    assert best is not None  # m = 1 always produced a candidate
    return best


def optimize_token_budget_per_class(
    graph: PCGGraph,
    spec: MachineSpec,
    prompt_len: int,
    classes,
    batch: int = 1,
    kv_len: int = 1024,
    chunk_size: int = 16,
    dp: int = 1,
    tp: int = 1,
    page_size: int = 0,
    machine_model=None,
    mixed_precision: bool = False,
    decode_kernel: str = "dense",
    measured_decode_step_s: float = 0.0,
):
    """Per-priority-class `optimize_token_budget`: size ONE shared
    iteration budget against the tightest SLO of every configured class.

    `classes` is the ``{name: PriorityClass}`` mapping from
    ``serving.tenancy.parse_classes`` (duck-typed here — any object with
    ``slo_ttft_ms``/``slo_itl_ms`` works, so search stays import-free of
    serving). Each class is solved independently with its own
    thresholds; the scheduler runs a single planner loop, so the
    returned budget is the max over per-class answers (the class that
    needs the most chunk throughput to hit its TTFT wins) and
    ``meets_slo`` only if every class's own solve met its thresholds at
    that shared operating point. Returns ``(budget, meets_slo,
    {name: TokenBudgetResult})``; classes with no thresholds set are
    observe-only and never constrain."""
    per_class: Dict[str, TokenBudgetResult] = {}
    for name, cls in classes.items():
        per_class[name] = optimize_token_budget(
            graph,
            spec,
            prompt_len,
            batch=batch,
            kv_len=kv_len,
            chunk_size=chunk_size,
            slo_ttft_ms=float(getattr(cls, "slo_ttft_ms", 0.0)),
            slo_itl_ms=float(getattr(cls, "slo_itl_ms", 0.0)),
            dp=dp,
            tp=tp,
            page_size=page_size,
            machine_model=machine_model,
            mixed_precision=mixed_precision,
            decode_kernel=decode_kernel,
            measured_decode_step_s=measured_decode_step_s,
        )
    if not per_class:
        raise ValueError("classes must be a non-empty mapping")
    budget = max(r.token_budget for r in per_class.values())
    meets = all(r.meets_slo for r in per_class.values())
    return budget, meets, per_class


def optimize_serving(
    graph: PCGGraph,
    num_devices: int,
    spec: MachineSpec,
    batch_size: int = 1,
    kv_len: int = 1024,
    mixed_precision: bool = False,
    machine_model=None,
    verbose: bool = False,
    page_size: int = 0,
    mean_prompt_len: Optional[int] = None,
    mean_gen_len: Optional[int] = None,
    max_len: Optional[int] = None,
    decode_kernel: str = "dense",
    max_new_tokens: Optional[int] = None,
    kv_dtype: str = "fp32",
    prefix_hit_rate: float = 0.0,
) -> ServingSearchResult:
    """Pick the decode-latency-optimal (dp, tp) mesh for serving
    `batch_size` concurrent sequences at `kv_len` cache positions.
    Enumerates every (dp, tp) with dp·tp dividing the chip count (idle
    chips allowed, mirroring the training search's idle-dp candidates) and
    keeps the feasible minimum-step-time one.

    page_size > 0 prices the paged KV layout (per-sequence reads round
    up to whole pages); decode_kernel ("pallas" | "dense", resolve a
    ServeConfig mode via resolve_decode_kernel) selects the attention
    core's cost shape — the kernel's single page-granular pool read vs
    the dense fallback's gather. When a measured length profile is
    supplied
    (mean_prompt_len + mean_gen_len), the winner also carries
    `max_in_flight`: how many such sequences fit in the winning mesh's
    leftover HBM (chip capacity minus its weight shard, through
    KVCacheSpec.total_bytes) — the "how many sequences fit" answer that
    turns page geometry into a capacity verdict. Supplying
    `max_new_tokens` (the per-request generation BUDGET, as opposed to
    the mean actually generated) additionally fills
    `max_in_flight_reserve` — the same budget under the preemption-free
    reserve admission gate, so the result compares what
    `--admission optimistic` buys over `reserve` on this workload.
    `kv_dtype` and `prefix_hit_rate` reprice the capacity estimates for
    the quantized pools (--kv-dtype int8) and a shared-prefix workload
    (--prefix-cache at measured hit rate h): see
    estimate_max_in_flight — the decode step-time cost itself also
    shifts under int8 (thinner pool reads, extra scale reads), priced
    through CostModel.decode_op_cost's kv_dtype term."""
    cm = CostModel(
        spec,
        measure=False,  # the measured table times training shapes
        machine_model=machine_model,
        mixed_precision=mixed_precision,
    )
    best: Optional[ServingSearchResult] = None
    for used in range(1, num_devices + 1):
        if num_devices % used != 0:
            continue
        for dp, tp in _mesh_factorizations(used):
            cost = estimate_decode_step(
                graph, cm, dp, tp, batch_size, kv_len,
                page_size=page_size, decode_kernel=decode_kernel,
                kv_dtype=kv_dtype,
            )
            if cost is None or not cost.feasible(spec):
                continue
            cur = ServingSearchResult(
                dp, tp, batch_size, kv_len, cost, page_size=page_size,
            )
            if verbose:
                print(f"[serve-search] {cur.describe()}")
            if best is None or cost.step_time < best.cost.step_time:
                best = cur
    if best is None:
        raise RuntimeError("serving search found no feasible strategy")
    if mean_prompt_len is not None and mean_gen_len is not None:
        horizon = max_len if max_len is not None else kv_len
        weight_bytes = 0.0
        for node in graph.nodes.values():
            if node.op_type == OperatorType.INPUT or node.is_parallel_op:
                continue
            node_tp = best.tp if _DECODE_TP_OPS.get(node.op_type) else 1
            weight_bytes += (
                sum(
                    s.volume() * cm.elem_bytes(s)
                    for s in node.stored_weight_shapes
                )
                / node_tp
            )
        budget = max(0, spec.hbm_bytes - int(weight_bytes))
        best.max_in_flight = estimate_max_in_flight(
            graph,
            budget,
            mean_prompt_len,
            mean_gen_len,
            horizon,
            page_size=page_size,
            tp=best.tp,
            kv_dtype=kv_dtype,
            prefix_hit_rate=prefix_hit_rate,
        )
        if max_new_tokens is not None:
            best.max_in_flight_reserve = estimate_max_in_flight(
                graph,
                budget,
                mean_prompt_len,
                mean_gen_len,
                horizon,
                page_size=page_size,
                tp=best.tp,
                admission="reserve",
                max_new_tokens=max_new_tokens,
                kv_dtype=kv_dtype,
            )
    return best


def search_serving_strategy(
    model,
    batch_size: int = 1,
    kv_len: Optional[int] = None,
    mean_prompt_len: Optional[int] = None,
    mean_gen_len: Optional[int] = None,
    max_new_tokens: Optional[int] = None,
    prefix_hit_rate: Optional[float] = None,
) -> ServingSearchResult:
    """Model-level entry: cost the compiled builder graph's decode regime
    on the config's machine (chip/nodes like the training search). kv_len
    defaults to the config's serving cache length; the page geometry
    comes from the config's --kv-page-size flag, the
    attention core's cost shape from --decode-kernel (resolved against
    the graph's cache geometry exactly like the engine resolves it), and
    a supplied length profile fills the winner's max_in_flight capacity
    estimate. The capacity estimate prices the config's --kv-dtype, and
    `prefix_hit_rate` (workload-measured; defaults to 0, and is only
    honored when --prefix-cache is on) discounts shared prompt bytes."""
    from flexflow_tpu.serving.kv_cache import default_page_size

    cfg = model.config
    page_size = cfg.serve_kv_page_size or default_page_size(
        cfg.serve_max_seq_len
    )
    decode_kernel = resolve_decode_kernel(
        getattr(cfg, "serve_decode_kernel", "auto"),
        model.graph,
        cfg.serve_max_seq_len,
        page_size=page_size,
    )
    n = cfg.num_devices if cfg.workers_per_node > 0 else None
    if n is None:
        import jax

        n = len(jax.devices())
    spec = MachineSpec(
        num_nodes=max(1, cfg.num_nodes),
        chips_per_node=max(1, n // max(1, cfg.num_nodes)),
        chip=cfg.chip,
    )
    return optimize_serving(
        model.graph,
        n,
        spec,
        batch_size=batch_size,
        kv_len=kv_len if kv_len is not None else cfg.serve_max_seq_len,
        mixed_precision=cfg.allow_mixed_precision,
        page_size=page_size,
        mean_prompt_len=mean_prompt_len,
        mean_gen_len=mean_gen_len,
        max_len=cfg.serve_max_seq_len,
        decode_kernel=decode_kernel,
        max_new_tokens=max_new_tokens,
        kv_dtype=getattr(cfg, "serve_kv_dtype", "fp32"),
        prefix_hit_rate=(
            prefix_hit_rate or 0.0
            if getattr(cfg, "serve_prefix_cache", False)
            else 0.0
        ),
    )


def _record_search_result_trace(trace, sr: SearchResult, spec) -> None:
    """Record a SearchResult (mesh / extra-axis winner) as the trace's
    result. Mesh strategies have no per-op view map, so the breakdown is
    the GraphCost aggregate and the whole total rides the residual —
    the explain identity (sum(ops) + residual == total) still holds."""
    c = sr.cost
    descr = sr.describe()  # fresh string — rows hold no live state
    trace.result(
        total_cost=c.step_time,
        ops=[],
        residual=c.step_time,
        kind=sr.kind,
        name=descr,
        dp=sr.dp,
        compute_time=c.compute_time,
        comm_time=c.comm_time,
        sync_time=c.sync_time,
        update_time=c.update_time,
        memory_per_chip=float(c.memory_per_chip),
        feasible=bool(c.feasible(spec)),
    )


def search_strategy(model, num_devices: int) -> Strategy:
    """compile()-time entry (reference: graph_optimize_task,
    graph.cc:1545-1613)."""
    cfg = model.config
    # search-without-hardware overrides (reference: model.cc:3673-3680)
    n = num_devices
    if cfg.search_num_workers > 0:
        n = cfg.search_num_workers * max(1, cfg.search_num_nodes)
    spec = MachineSpec(
        num_nodes=max(1, cfg.search_num_nodes)
        if cfg.search_num_nodes > 0
        else max(1, cfg.num_nodes),
        chips_per_node=max(1, n // max(1, cfg.num_nodes)),
        chip=cfg.chip,
    )
    if n <= 1:
        # nothing to search on one device — but a requested trace must
        # still produce a valid artifact (a silently-missing export
        # breaks explain/CI workflows on single-chip boxes)
        if cfg.search_trace_file or cfg.search_explain:
            from flexflow_tpu.telemetry.search_trace import SearchTrace

            trace = SearchTrace(
                engine=cfg.search_engine, path=cfg.search_trace_file
            )
            trace.header(
                engine=cfg.search_engine, seed=cfg.seed,
                budget=cfg.search_budget, measure=bool(cfg.measure_costs),
            )
            trace.event("search_skipped", reason="single device")
            trace.result(
                total_cost=0.0, ops=[], residual=0.0,
                kind="data-parallel",
                name="data-parallel (single device — search skipped)",
            )
            model.search_trace = trace
            if cfg.search_trace_file:
                trace.save()
            if cfg.search_explain:
                from flexflow_tpu.search.explain import explain_strategy

                print(explain_strategy(trace.rows()).text())
        return data_parallel_strategy(num_devices, model.graph)

    if cfg.search_engine not in ("mesh", "unity", "mcmc"):
        raise ValueError(
            f"unknown --search-engine {cfg.search_engine!r}; "
            "expected mesh | unity | mcmc"
        )
    from flexflow_tpu.search.machine_model import build_machine_model

    mm = build_machine_model(cfg, spec)
    sparse_ok = cfg.sparse_embedding_update and (
        model.optimizer is None or model.optimizer.supports_sparse()
    )
    # search observability (--search-trace / --explain): one SearchTrace
    # threads through whichever engine runs; the exported JSONL +
    # timeline reconstruct every candidate considered, and the explain
    # report reconstructs why the winner won (search/explain.py)
    trace = None
    if cfg.search_trace_file or cfg.search_explain:
        from flexflow_tpu.telemetry.search_trace import SearchTrace

        trace = SearchTrace(
            engine=cfg.search_engine, path=cfg.search_trace_file
        )
        n_nodes = len(model.graph.nodes)  # scalar precomputed: trace
        # rows must not touch live graph state (fxlint FX104)
        trace.header(
            engine=cfg.search_engine,
            seed=cfg.seed,
            budget=cfg.search_budget,
            alpha=cfg.search_alpha,
            measure=bool(cfg.measure_costs),
            machine={
                "num_nodes": spec.num_nodes,
                "chips_per_node": spec.chips_per_node,
                "chip": spec.chip,
            },
            graph={
                "nodes": n_nodes,
                "batch_size": cfg.batch_size,
            },
        )
        model.search_trace = trace

    def _finish_trace() -> None:
        """Export + explain once the winner is known."""
        if trace is None:
            return
        if cfg.search_trace_file:
            trace.save()
        if cfg.search_explain:
            from flexflow_tpu.search.explain import explain_strategy

            print(explain_strategy(trace.rows()).text())
    if cfg.search_engine in ("unity", "mcmc"):
        from flexflow_tpu.search import unity as unity_mod

        if cfg.search_engine == "unity":
            result = unity_mod.UnitySearch(
                model.graph,
                spec,
                machine_model=mm,
                mixed_precision=cfg.allow_mixed_precision,
                measure=cfg.measure_costs,
                calibration_file=cfg.calibration_file,
                sparse_embedding=sparse_ok,
                trace=trace,
            ).optimize()
        else:
            from flexflow_tpu.search.mcmc import mcmc_optimize

            result = mcmc_optimize(
                model.graph,
                spec,
                budget=max(cfg.search_budget, 1),
                alpha=cfg.search_alpha,
                seed=cfg.seed,
                verbose=cfg.profiling,
                machine_model=mm,
                mixed_precision=cfg.allow_mixed_precision,
                measure=cfg.measure_costs,
                calibration_file=cfg.calibration_file,
                sparse_embedding=sparse_ok,
                trace=trace,
            )
        # every engine must cover the whole strategy space the runtime
        # executes (VERDICT r2 item 6; the reference has one search over
        # everything its runtime does, substitution.cc:1721-1862): before
        # answering, compare the engine's (dp, ch)-grid winner against
        # the pipeline/seq/spatial/mixed candidates
        cm_extra = CostModel(
            spec,
            measure=cfg.measure_costs,
            machine_model=mm,
            mixed_precision=cfg.allow_mixed_precision,
            calibration_file=cfg.calibration_file,
            sparse_embedding=sparse_ok,
        )
        extra, _ = extra_axis_candidates(
            model.graph,
            n,
            cm_extra,
            spec,
            attribute_parallel=cfg.enable_attribute_parallel,
            verbose=cfg.profiling,
            trace=trace,
        )
        extra_best = (
            min(extra, key=lambda r: r.cost.step_time) if extra else None
        )
        if (
            extra_best is not None
            and extra_best.cost.step_time < result.cost
        ):
            # reference prints exactly this at the end of its search
            # (substitution.cc:1909, model.cc:3298)
            print(f"Optimal cost: {extra_best.cost.step_time * 1e3:.6f}")
            if cfg.export_strategy_file:
                from flexflow_tpu.search.strategy_io import (
                    save_search_result,
                )

                save_search_result(
                    extra_best, model.graph, cfg.export_strategy_file
                )
            if trace is not None:
                # the extra-axis gate overrode the engine's pick: the
                # result record must describe the strategy actually
                # lowered (the engine's own record is replaced)
                _record_search_result_trace(trace, extra_best, spec)
            _finish_trace()
            s = result_to_strategy(extra_best, model.graph)
            # the audit (search/audit.py) compares this prediction
            # against the executor's measured step after compile()
            s.predicted_step_time = extra_best.cost.step_time
            return s
        print(f"Optimal cost: {result.cost * 1e3:.6f}")
        if cfg.export_strategy_file:
            unity_mod.save_views(
                result,
                model.graph,
                cfg.export_strategy_file,
                engine=cfg.search_engine,
            )
        _finish_trace()
        s = unity_mod.result_to_strategy(
            result, model.graph, num_devices, engine=cfg.search_engine
        )
        s.predicted_step_time = result.cost
        return s

    result = optimize(
        model.graph,
        n,
        spec,
        budget=max(cfg.search_budget, 1),
        alpha=cfg.search_alpha,
        seed=cfg.seed,
        verbose=cfg.profiling,
        machine_model=mm,
        mixed_precision=cfg.allow_mixed_precision,
        measure=cfg.measure_costs,
        calibration_file=cfg.calibration_file,
        attribute_parallel=cfg.enable_attribute_parallel,
        # mirror the executor's full gate: flag AND an optimizer that
        # implements sparse rows (Executor._sparse_embedding_guids)
        sparse_embedding=sparse_ok,
        trace=trace,
    )
    print(f"[flexflow_tpu] search: best strategy = {result.describe()}")
    if cfg.export_strategy_file:
        from flexflow_tpu.search.strategy_io import save_search_result

        save_search_result(result, model.graph, cfg.export_strategy_file)
    if trace is not None:
        _record_search_result_trace(trace, result, spec)
    _finish_trace()
    s = result_to_strategy(result, model.graph)
    s.predicted_step_time = result.cost.step_time
    return s
