"""Profiling utilities.

Rebuild of the reference's --profiling path (reference: FFConfig.profiling
→ Op.profiling → per-kernel cudaEvent timing printed per task,
kernels/linear_kernels.cu:95-117; SURVEY §5.1). Two TPU-native tools:

  * `profile_operators(model, batch)` — time each PCG node's lowered
    forward in isolation (jitted per-op microbench on its shard shapes)
    and return/print a per-op table. Isolated-op times over-count what
    XLA fusion removes from the real step (the same caveat the cost
    model documents), so treat them as relative weights.
  * `trace(dir)` — context manager around jax.profiler for a real XLA
    trace (the analog of `-lg:prof` external profiles, viewable in
    TensorBoard / Perfetto), with the program's own host phases in it.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple


def profile_operators(
    model, batch: Dict, iters: int = 5, verbose: bool = True
) -> List[Tuple[str, float]]:
    """Per-op isolated forward times in seconds, slowest first."""
    import jax

    ex = model.executor
    if ex is None:
        raise RuntimeError("call compile() before profile_operators()")
    sharded = ex.shard_batch(batch)
    values = {}
    rows: List[Tuple[str, float]] = []
    from flexflow_tpu.core.types import OperatorType
    from flexflow_tpu.ops.registry import LowerCtx

    for guid in ex.topo:
        node = ex.graph.nodes[guid]
        if node.op_type in (OperatorType.INPUT, OperatorType.NOOP) and not node.inputs:
            if node.name not in sharded:
                raise KeyError(f"batch missing input '{node.name}'")
            values[(guid, 0)] = sharded[node.name]
            continue
        ins = [values[(r.guid, r.out_idx)] for r in node.inputs]
        # per-weight accessor: pipelined trunks store weights stacked
        # under their template guid (Executor.get_host_param slices out
        # this block's weights; plain executors read params[guid] direct)
        ws = [
            ex.get_host_param(model.params, guid, i)
            for i in range(len(node.weight_shapes))
        ]
        # mirror Executor.forward_values' ctx so profiled shapes match the
        # real step (seq_length truncation included)
        ctx = LowerCtx(
            train=False,
            rng=None,
            mesh=ex.mesh,
            axis_names=ex.mesh_config.axis_names,
            in_shapes=[ex.graph.shape_of(r) for r in node.inputs],
            bf16_matmul=ex.mixed_precision,
            seq_length=ex.seq_length,
        )
        fn = ex._lowered[guid]
        jitted = jax.jit(lambda i, w, _fn=fn, _ctx=ctx: _fn(i, w, _ctx))
        outs = jitted(ins, ws)
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(iters):
            outs = jitted(ins, ws)
        jax.block_until_ready(outs)
        dt = (time.perf_counter() - t0) / iters
        rows.append((node.name, dt))
        for i, out in enumerate(outs):
            values[(guid, i)] = out
    rows.sort(key=lambda r: -r[1])
    if verbose:
        total = sum(t for _, t in rows) or 1e-12
        print(f"{'op':<32} {'time':>12} {'share':>7}")
        for name, t in rows:
            print(f"{name:<32} {t * 1e6:>10.1f}us {t / total:>6.1%}")
    return rows


def xla_cost_analysis(model, batch: Dict) -> Dict[str, float]:
    """XLA's own cost analysis of the compiled train step — flops,
    bytes accessed, and transcendentals as the COMPILER counts them
    after fusion/DCE (the ground truth the analytic cost model
    approximates; the reference has no equivalent, its simulator only
    times kernels). Returns the cost dict of `Compiled.cost_analysis()`.

        model.compile(...); xla_cost_analysis(model, batch)
        # {'flops': 2.1e9, 'bytes accessed': 8.4e8, ...}
    """
    import jax

    ex = model.executor
    if ex is None:
        raise RuntimeError("call compile() before xla_cost_analysis()")
    sharded = ex.shard_batch(batch)
    key = jax.random.PRNGKey(0)
    # reuse the executor's cached jit wrapper (same donation flags, same
    # compiled program the training loop runs; no second full compile)
    lowered = ex.train_step().lower(
        model.params, model.opt_state, sharded, key
    )
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax: one dict per device
        cost = cost[0]
    return dict(cost or {})


@contextlib.contextmanager
def trace(log_dir: str):
    """XLA profiler trace (view in TensorBoard/Perfetto):

        with profiling.trace("/tmp/trace"):
            model.fit(...)

    The session is the one the benchmark's traced runs use: the Python
    tracer off (it would put an event on every call of the host loop)
    and the host tracer at level 1, which keeps `TraceAnnotation`s. So
    the program's own phases (`telemetry.trace.span`: `train.input.*`,
    `train.epoch_end.*`, `scheduler.step.*`) sit on the Python thread's
    line above the device's ops, on one clock
    (docs/observability.md, "Reading a profile")."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
