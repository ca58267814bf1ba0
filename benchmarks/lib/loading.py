"""Finding things by the names `BENCHMARK.json` gives them.

The harness holds no name of a cell, configuration, traffic mix or
metric: a cell names its configuration and traffic, a configuration
names its family, a traffic mix names its generator kind, and each is a
file that is looked up here. A later PR adds files and entries and
edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkError(Exception):
    """The benchmark's own data is wrong or incomplete."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py` as a module. The name may hold
    dots (`batch_occupancy.chat`), so it is loaded by path."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {kind} file for {name!r}: {path}")
    mod_name = f"benchmarks.{kind}.{name.replace('.', '__')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    names = ", ".join(c["name"] for c in bench["workloads"])
    raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json ({names})")


def load_config(bench: dict, name: str) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return load_json(os.path.join(ROOT, entry["file"]))
    raise BenchmarkError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    path = os.path.join(BENCH_DIR, "traffic", name + ".json")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no traffic file for {name!r}: {path}")
    return load_json(path)


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The entries of `end_to_end` or `per_layer` that this cell
    reports: those with no `workloads` key, or that list the cell."""
    return [
        m for m in bench[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


def with_rehearsal(data: dict, rehearse: bool) -> dict:
    """A config or traffic file may carry a `rehearse` group: the tiny
    sizes the CPU rehearsal runs at. They replace the real ones only
    under `--rehearse`, and never on the chip."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    if rehearse:
        out.update(data.get("rehearse", {}))
    return out
