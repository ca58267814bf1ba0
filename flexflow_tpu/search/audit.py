"""Predicted-vs-measured cost-model audit.

The paper's whole premise is a simulator accurate enough to rank
strategies (measure, then decide) — but until now nothing ever checked
whether the strategy the search picked was actually fast once executed:
`estimate_graph_cost` predicted a step time at compile, the executor
ran, and the two numbers never met. This module closes that loop:

* **predicted** — the searcher's own `GraphCost` for the COMPILED
  (annotated) graph, re-derived with the same CostModel basis the
  search used, with the per-node breakdown exported by
  `estimate_graph_cost(export=...)` and grouped by cost-model family
  (`cost_model.op_family`);
* **measured** — the real executor: whole-step wall clock via the
  bench methodology (`utils.benchmark.measure_train_step`, on-device
  scan differencing) and per-op forward times via
  `utils.profiling.profile_operators` (isolated-kernel basis — the
  same structural bias the cost model documents, so family ratios are
  compared forward-vs-forward on that shared basis);
* **exported** — `cost_model_error_ratio{family=...}` gauges
  (predicted / measured; 1.0 = calibrated, >1 over-prediction) in a
  MetricsRegistry, plus an ``audit`` entry fed back through the
  existing `update_calibration_doc` read-merge-write path so repeated
  runs accumulate the residual history next to the measured-kernel
  table they judge. `apply_family_scale=True` additionally merges the
  measured family residuals into the ``family_scale`` correction the
  measured-mode search divides out — the full calibration loop
  (calibrate.py --fit-family remains the precision tool; this is the
  in-situ coarse pass).

Entry point: `audit_cost_model(model, ...)` after `compile()` (also
surfaced as `FFModel.audit_cost_model`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

__all__ = ["CostAuditResult", "FamilyAudit", "audit_cost_model"]


@dataclasses.dataclass
class FamilyAudit:
    """One op family's predicted-vs-measured forward-time comparison
    (isolated-kernel basis on the measured side)."""

    family: str
    predicted_s: float
    measured_s: float

    @property
    def error_ratio(self) -> float:
        return (
            self.predicted_s / self.measured_s
            if self.measured_s > 0
            else float("inf")
        )


@dataclasses.dataclass
class CostAuditResult:
    """The full audit: whole-step prediction vs wall clock, per-family
    forward residuals, and the search's own predicted step time when a
    searched strategy produced one."""

    predicted_step_s: float      # estimate_graph_cost on the compiled graph
    measured_step_s: float       # executor wall clock (scan differencing)
    families: Dict[str, FamilyAudit]
    searched_step_s: Optional[float] = None  # strategy.predicted_step_time
    node_costs: List[dict] = dataclasses.field(default_factory=list)

    @property
    def step_error_ratio(self) -> float:
        return (
            self.predicted_step_s / self.measured_step_s
            if self.measured_step_s > 0
            else float("inf")
        )

    def describe(self) -> str:
        lines = [
            "cost-model audit: predicted "
            f"{self.predicted_step_s * 1e3:.3f} ms vs measured "
            f"{self.measured_step_s * 1e3:.3f} ms per step "
            f"(ratio {self.step_error_ratio:.3f})",
        ]
        if self.searched_step_s is not None:
            lines.append(
                f"  search predicted {self.searched_step_s * 1e3:.3f} ms "
                "for the lowered strategy"
            )
        for fam in sorted(
            self.families.values(), key=lambda f: -f.measured_s
        ):
            lines.append(
                f"  {fam.family:<10} predicted {fam.predicted_s * 1e3:8.3f}"
                f" ms, profiled {fam.measured_s * 1e3:8.3f} ms "
                f"(ratio {fam.error_ratio:.3f})"
            )
        return "\n".join(lines)

    def to_doc(self) -> dict:
        """The JSON shape fed back through update_calibration_doc."""
        return {
            "predicted_step_ms": self.predicted_step_s * 1e3,
            "measured_step_ms": self.measured_step_s * 1e3,
            "step_error_ratio": self.step_error_ratio,
            "searched_step_ms": (
                self.searched_step_s * 1e3
                if self.searched_step_s is not None
                else None
            ),
            "families": {
                f.family: {
                    "predicted_ms": f.predicted_s * 1e3,
                    "measured_ms": f.measured_s * 1e3,
                    "error_ratio": f.error_ratio,
                }
                for f in self.families.values()
            },
        }


def _zero_batch(model) -> dict:
    """Zero-filled example batch on the executor's input shapes (the
    init_operators recipe) — the audit must not require real data."""
    import numpy as np

    return {
        name: np.zeros(
            tuple(d.size for d in shape.dims if not d.is_replica_dim),
            shape.dtype.to_jnp(),
        )
        for name, shape in model.executor.input_shapes().items()
    }


def audit_cost_model(
    model,
    batch=None,
    reps: int = 4,
    profile_iters: int = 3,
    registry=None,
    calibration_file: Optional[str] = None,
    apply_family_scale: bool = False,
) -> CostAuditResult:
    """Run the predicted-vs-measured audit on a compiled model.

    batch: host arrays keyed like fit()'s (label included); a
    zero-filled batch on the executor's input shapes is synthesized
    when omitted. registry: a telemetry.MetricsRegistry to export
    `cost_model_error_ratio{family=...}` gauges into (the model's
    attached fit-telemetry registry is used when one exists).
    calibration_file: defaults to the config's --calibration-file;
    pass "" to skip the write-back."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.machine_model import build_machine_model
    from flexflow_tpu.search.simulator import estimate_graph_cost
    from flexflow_tpu.utils.benchmark import measure_train_step
    from flexflow_tpu.utils.profiling import profile_operators

    if model.executor is None:
        raise RuntimeError("call compile() before audit_cost_model()")
    cfg = model.config
    n = int(model.executor.mesh.devices.size)
    spec = MachineSpec(
        num_nodes=max(1, cfg.num_nodes),
        chips_per_node=max(1, n // max(1, cfg.num_nodes)),
        chip=cfg.chip,
    )
    sparse_ok = cfg.sparse_embedding_update and (
        model.optimizer is None or model.optimizer.supports_sparse()
    )
    cm = CostModel(
        spec,
        measure=cfg.measure_costs,
        machine_model=build_machine_model(cfg, spec),
        mixed_precision=cfg.allow_mixed_precision,
        calibration_file=cfg.calibration_file,
        sparse_embedding=sparse_ok,
    )
    # predicted: the SAME annotated graph the executor lowered, priced
    # on the same basis the search ranks candidates with
    export: dict = {}
    predicted = estimate_graph_cost(
        model.graph,
        cm,
        model.strategy.mesh_config.axis_sizes,
        export=export,
    )
    node_costs = export.get("node_costs", [])
    pred_fwd_by_family: Dict[str, float] = {}
    for entry in node_costs:
        fam = entry["family"]
        pred_fwd_by_family[fam] = (
            pred_fwd_by_family.get(fam, 0.0) + entry["forward"]
        )

    # measured: whole-step wall clock + per-op isolated forward profile
    host_batch = batch if batch is not None else _zero_batch(model)
    sharded = model.executor.shard_batch(host_batch)
    measured_step = measure_train_step(model, sharded, reps=reps)
    prof_rows = profile_operators(
        model, host_batch, iters=profile_iters, verbose=False
    )
    name_to_family: Dict[str, str] = {}
    from flexflow_tpu.search.cost_model import op_family

    for node in model.graph.nodes.values():
        name_to_family[node.name] = op_family(node.op_type) or "other"
    meas_fwd_by_family: Dict[str, float] = {}
    for name, seconds in prof_rows:
        fam = name_to_family.get(name, "other")
        meas_fwd_by_family[fam] = meas_fwd_by_family.get(fam, 0.0) + seconds

    families = {
        fam: FamilyAudit(
            fam,
            pred_fwd_by_family.get(fam, 0.0),
            meas_fwd_by_family.get(fam, 0.0),
        )
        for fam in sorted(
            set(pred_fwd_by_family) | set(meas_fwd_by_family)
        )
    }
    result = CostAuditResult(
        predicted_step_s=predicted.step_time,
        measured_step_s=measured_step,
        families=families,
        searched_step_s=getattr(
            model.strategy, "predicted_step_time", None
        ),
        node_costs=node_costs,
    )

    # export gauges: the series the ROADMAP's calibration dashboards
    # scrape — one per family plus the whole-step ratio under _step
    if registry is None:
        tele = getattr(model, "_telemetry", None)
        registry = tele.registry if tele is not None else None
    if registry is not None:
        for fam in families.values():
            if fam.measured_s > 0:
                registry.gauge(
                    "cost_model_error_ratio",
                    help="predicted / measured time (1.0 = calibrated)",
                    labels={"family": fam.family},
                ).set(fam.error_ratio)
        if result.measured_step_s > 0:
            registry.gauge(
                "cost_model_error_ratio",
                help="predicted / measured time (1.0 = calibrated)",
                labels={"family": "_step"},
            ).set(result.step_error_ratio)

    # feed the residuals back through the ONE calibration write path
    if calibration_file is None:
        calibration_file = cfg.calibration_file
    if calibration_file:
        from flexflow_tpu.search.cost_model import update_calibration_doc

        updates: dict = {"audit": result.to_doc()}
        if apply_family_scale:
            # family_scale divides measured costs (corrected = raw /
            # scale), so the residual that would make predicted match
            # measured is predicted/measured on the shared forward
            # basis — merged per family, never wiping siblings
            updates["family_scale"] = {
                f.family: f.error_ratio
                for f in families.values()
                if f.measured_s > 0 and f.predicted_s > 0
            }
        update_calibration_doc(
            calibration_file, updates, chip=cfg.chip
        )
    return result
