"""The `--profiling` table of a benchmark cell's programs, on the chip.

Builds the cell's model through its family's own `build`
(`benchmarks/families/`, imported, not edited), runs the real program
under `flexflow_tpu.utils.profiling` and prints the per-scope tables
PERF.md section 5 holds; the same as JSON under `chiprun_out/`.

    chiprun -- python3 scripts/profile_cells.py --cell train_ff_b64
    chiprun --chips 4 -- python3 scripts/profile_cells.py --cell train_ff_b256_x4
    chiprun -- python3 scripts/profile_cells.py --cell serve_olmoe_chat

A training cell's rows come with the attention core each node took
(`ops/attention.py:mha_core_plan`): since PR 59 both cells read `tiled`,
the hand-tiled kernel's whole-sequence form (`flash_whole_fwd` and
`flash_whole_bwd`, one Mosaic call a node and pass), per device on
`train_ff_b256_x4`; off a TPU the same model reads `chunked`.

A serving cell is driven with `--concurrent` requests of `--prompt`
tokens admitted together (one prefill program) that decode `--new`
tokens side by side; the family's own scope shares are read from the
same trace with `benchmarks/lib/scopes.py`, for the cross-check.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def _cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    traffic = os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")
    with open(traffic) as f:
        traffic = json.load(f)
    return cell, config, traffic


def _as_dict(profile):
    out = dataclasses.asdict(profile)
    out["accounted"] = profile.accounted
    out["by_family"] = [dataclasses.asdict(r) for r in profile.by_family()]
    return out


def train(args, cell, config, traffic):
    import jax
    import numpy as np

    from benchmarks.families import ff_transformer
    from flexflow_tpu.utils import profiling

    devices = jax.devices()[: cell["chips"]]
    batch = int(traffic["global_batch"])
    model = ff_transformer.build(config, batch, devices, args.seed)
    rng = np.random.Generator(np.random.PCG64(args.seed))
    data = {
        "x": rng.standard_normal(
            (batch, config["seq_len"], config["hidden_size"]), np.float32
        ),
        "label": np.zeros((batch, config["seq_len"], 1), np.float32),
    }
    # the same program untraced, on the host's clock: what tracing costs
    ex = model.executor
    placed, key = ex.shard_batch(data), jax.random.PRNGKey(0)
    state = jax.tree_util.tree_map(jax.numpy.copy, (model.params, model.opt_state))
    step = ex.train_step()
    *state, loss, _ = step(*state, placed, key)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        *state, loss, _ = step(*state, placed, key)
    jax.block_until_ready(loss)
    untraced_ms = (time.perf_counter() - t0) / args.steps * 1e3
    del state
    t0 = time.perf_counter()
    profile = profiling.profile_step(
        model, data, steps=args.steps, log_dir=args.trace_dir
    )
    print(f"untraced: {untraced_ms:.3f} ms a step on the host's clock "
          f"({args.steps} steps enqueued, one wait)")
    plans = _attention_plans(ex, profile)
    return {"jit_step": _as_dict(profile), "untraced_wall_ms": untraced_ms,
            "attention_plans": plans}


def _attention_plans(ex, profile):
    """Which core each `multihead_attention` node was lowered to
    (`ops/attention.py:mha_core_plan`, asked as `forward_values` asks it
    in a train step), printed beside the node's row of `profile`."""
    import jax

    from flexflow_tpu.core.types import OperatorType
    from flexflow_tpu.ops.attention import mha_core_plan

    rows = {r.scope: r for r in profile.rows}
    plans = {}
    for node in (ex.graph.nodes[g] for g in ex.topo):
        if node.op_type != OperatorType.MULTIHEAD_ATTENTION:
            continue
        plan = mha_core_plan(
            node.params, ex.node_ctx(node, rng=jax.random.PRNGKey(0))
        )
        scope = f"{node.op_type.name.lower()}:{node.name}"
        row = rows.get(scope)
        times = (f"fwd {row.forward_ms:.3f} bwd {row.backward_ms:.3f} ms"
                 if row else "no row")
        print(f"{scope:<44} {plan.core}, {plan.chunk} of {plan.local_batch} "
              f"local sequences a pass"
              f"{', per device' if plan.per_device else ''}: {times}")
        plans[scope] = plan._asdict()
    return plans


def serve(args, cell, config, traffic):
    import jax
    import numpy as np

    from benchmarks.lib import scopes
    from benchmarks.lib.loading import load_module
    from flexflow_tpu.serving import Request
    from flexflow_tpu.utils import profiling

    family = load_module("families", config["family"])
    model, sched, engine, cache = family.build(config, jax.devices()[:1], args.seed)
    rng = np.random.Generator(np.random.PCG64([args.seed, 1]))
    vocab = config["vocab_size"]

    def requests(base):
        return [
            Request(
                rid=base + i, max_new_tokens=args.new,
                prompt=rng.integers(1, vocab, size=args.prompt).tolist(),
            )
            for i in range(args.concurrent)
        ]

    with profiling.step_program_texts(engine) as texts:
        sched.run(requests(0))  # compiles, and records each program's text
    trace_dir = args.trace_dir
    with profiling.trace(trace_dir):
        done = sched.run(requests(1000))
    assert all(r.status == "finished" for r in done), [r.error for r in done]
    for i, (key, text) in enumerate(sorted(texts.items())):
        # beside the trace, for a later fold of a kept one
        name = f"{key.split()[0]}.{i}.hlo.txt"
        with open(os.path.join(trace_dir, name), "w") as f:
            f.write(f"// {key}\n" + text)
    events = profiling.read_device_events(trace_dir)
    out = {"kernel_fallbacks": int(engine.kernel_fallbacks), "programs": {}}
    for key, text in sorted(texts.items()):
        try:
            profile = profiling.fold_step(events, text)
        except (profiling.NoDeviceOps, ValueError) as e:
            print(f"{key}: {e}")
            continue
        print(f"== {key}")
        print(profile.table(by_family=True))
        print(profile.table())
        out["programs"][key] = _as_dict(profile)
    # the scopes the family's own per-layer metrics read, if it has any
    wanted = getattr(family, "MOE_SCOPES", ()) + getattr(family, "MLA_SCOPES", ())
    if wanted:
        path = profiling.newest_xplane(trace_dir)
        got = scopes.scope_seconds(
            path, wanted, ("jit__decode_impl_paged", "jit__prefill_impl_paged"),
            compiler_ops={"ragged-dot": "moe.experts"},
        )
        for program, rec in got.items():
            shares = {
                k: v / max(rec["seconds"], 1e-12) for k, v in rec["scopes"].items()
            }
            print(f"benchmarks/lib/scopes.py, {program}: {rec['count']} executions, "
                  + ", ".join(f"{k} {v:.1%}" for k, v in sorted(shares.items())))
            out.setdefault("family_scopes", {})[program] = {
                "count": rec["count"], "seconds": rec["seconds"], "shares": shares,
            }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, default=3500000001)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--concurrent", type=int, default=3)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--new", type=int, default=96)
    ap.add_argument("--trace-dir", default=None,
                    help="default chiprun_out/trace_<cell>, removed after "
                         "a fold that succeeded unless --keep-trace")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's and the traffic's `rehearse` sizes")
    args = ap.parse_args()
    cell, config, traffic = _cell(args.cell)
    args.trace_dir = args.trace_dir or os.path.join(
        ROOT, "chiprun_out", "trace_" + args.cell
    )
    if args.rehearse:
        config = {**config, **config["rehearse"]}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    result = (train if config["family"] == "ff_transformer" else serve)(
        args, cell, config, traffic
    )
    import jax

    d = jax.devices()[0]
    result["device"] = {"platform": d.platform, "kind": d.device_kind,
                        "count": len(jax.devices())}
    result["args"] = vars(args)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"profile_{args.cell}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote", path)
    if not args.keep_trace:
        import shutil

        shutil.rmtree(args.trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
