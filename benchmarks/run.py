#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips: it builds the model on the device
from the seed, warms exactly the shapes the cell's traffic will use,
checks correctness outside the window, measures, and prints one JSON
object as the last line of its standard output (`correct`, `attempted`,
`failed`, `metrics`, `device`, and with `--trace 1` `breakdown`). With
`--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.

It fails (exit 2, no result line) on a machine without a TPU or with
fewer chips than the cell asks for; it never falls back to the CPU.
`--rehearse` is the harness's own flag for the sandbox: a tiny size on
the CPU backend (four virtual devices for a four-chip cell), ending in a
line with the same keys that carries counts only and no device metric.

A run is measured in a process that fetched its programs from the
persistent compile cache. If the process finds it had to compile one
(the first run of a cell in a checkout), it stops before the window and
starts over: on the v5e the train step runs a quarter faster in the
process that compiled it than in any that fetches it (PERF.md, Findings,
PR 23), and a check makes one compiling run and many that fetch.
Starting over is `os.execve` of this same command in this same process:
the kernel ends every thread, unmaps the device and closes every
descriptor but the standard three, and the new program image finds every
program in the cache. The benchmark starts no process of its own, so
none can outlive a run, however the run ends; the set-up time counts
from the first image's start.

This file holds no name of a cell, configuration, traffic mix or metric:
`BENCHMARK.json` names them, and each is a file found by its name
(`configs/`, `traffic/`, `generators/`, `families/`, `reference/`,
`metrics/`).
"""

from __future__ import annotations

import os
import time

# set-up counts from the start of the process; an image that started over
# (see `start_over`) is told when that was
STARTED_OVER = os.environ.get("BENCH_STARTED_OVER") == str(os.getpid())
T_WALL_START = float(os.environ["BENCH_T0"]) if STARTED_OVER else time.time()
T_PROCESS_START = time.perf_counter() - max(0.0, time.time() - T_WALL_START)

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# this checkout's `benchmarks` first, wherever else one may be found
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

from benchmarks.lib import loading  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU backend; prints no device metric")
    ap.add_argument("--override", action="append", default=[], metavar="KEY=JSON",
                    help="replace one traffic parameter for a sweep (harness "
                         "use; the driver never passes it)")
    return ap.parse_args(argv)


def prepare_environment(chips: int, rehearse: bool) -> None:
    """Before JAX is imported: where the compile cache lives, and for a
    rehearsal the CPU backend with as many virtual devices as chips."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if not rehearse:
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")
        )
    else:
        # CPU programs compile in seconds, and XLA's CPU loader logs a
        # machine-feature mismatch for every executable it reads back
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            flags = os.environ.get("XLA_FLAGS", "")
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}".strip()
            )


def start_over(argv) -> None:
    """Replace this process's program by a new run of the same command:
    same process id, no child. Every descriptor but the standard three
    is marked close-on-exec first, so that the new image opens the chip
    as a new process would. Does not return."""
    sys.stdout.flush()
    sys.stderr.flush()
    for name in os.listdir("/proc/self/fd"):
        if int(name) > 2:
            try:
                os.set_inheritable(int(name), False)
            except OSError:
                pass  # the descriptor of the listing itself, closed by now
    env = dict(os.environ, BENCH_STARTED_OVER=str(os.getpid()),
               BENCH_T0=repr(T_WALL_START))
    os.execve(sys.executable, [sys.executable, os.path.abspath(__file__), *argv], env)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    args = parse_args(argv)
    bench = loading.load_benchmark()
    cell = loading.find_cell(bench, args.workload)
    config = loading.with_rehearsal(
        loading.load_config(bench, cell["config"]), args.rehearse
    )
    traffic = loading.with_rehearsal(
        loading.load_traffic(cell["traffic"]), args.rehearse
    )
    for item in args.override:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    prepare_environment(cell["chips"], args.rehearse)

    import jax

    from benchmarks.lib import peaks, trace, window
    from benchmarks.lib.readers import Run

    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = window.devices_for(cell["chips"], args.rehearse)
    except window.NoAccelerator as e:
        print(e, file=sys.stderr, flush=True)
        return 2
    ctx = window.Ctx(
        cell, config, traffic, args.seed, args.seconds, args.trace,
        args.rehearse, devices, T_PROCESS_START,
        may_start_over=not STARTED_OVER and not args.rehearse,
    )
    family = loading.load_module("families", config["family"])
    ctx.mark("imports_and_devices")
    try:
        record = family.run(ctx)
        ctx.mark("family_returned")
        summary = None
        if args.trace and ctx.tracer.path:
            summary = trace.summarize(
                trace.read_xplane(ctx.tracer.path, record["spans"])
            )
            ctx.mark("trace_reduced")
    except window.CompiledHere:
        # raised before the window, so no trace session is open
        print("benchmarks/run.py: this process compiled "
              f"{ctx.compiles.cache_misses} program(s); it starts over and "
              "measures with all of them fetched", file=sys.stderr, flush=True)
        start_over(argv)
    finally:
        ctx.tracer.cleanup()

    device = window.device_report(devices, record.get("program_temp_bytes", 0))
    on_chip = device["platform"] == "tpu"
    run = Run(
        record=record, trace=summary, device=device,
        peaks=peaks.peaks_for(device["kind"]) if on_chip else None,
        set_up_seconds=record["window_start"] - T_PROCESS_START, notes={},
    )
    group = "per_layer" if args.trace else "end_to_end"
    metrics, rehearsed = {}, []
    for entry in loading.metrics_of(bench, group, cell["name"]):
        value = loading.load_module("metrics", entry["name"]).read(run)
        if value is None:
            continue
        if args.rehearse and entry["source"] != "program_counter":
            rehearsed.append(entry["name"])  # a CPU time is never a metric
            continue
        metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}

    line = {
        "workload": cell["name"],
        "seed": args.seed,
        "seconds": args.seconds,
        "trace": args.trace,
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device,
        "checks": record["checks"],
        "notes": run.notes,
        "window_compiles": record["compiles"],
        "setup_marks": ctx.marks,
        "started_over": STARTED_OVER,
        "observed": record.get("observed"),
    }
    if args.rehearse:
        line["rehearsal"] = {
            "readers_that_ran_but_are_not_metrics_on_a_cpu": rehearsed
        }
    if summary is not None and on_chip:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {
            "device_ops": summary["device_ops"],
            "idle_gaps": summary["idle_gaps"],
        }
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
