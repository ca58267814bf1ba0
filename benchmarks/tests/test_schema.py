"""`BENCHMARK.json` against the contract's form, and against the files
it names."""

import os
import re

import pytest

from benchmarks.lib import loading

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return loading.load_benchmark()


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16 and all(PATH.match(p) for p in bench["paths"])
    assert len(bench["command"]) <= 32 and all(one_line(w) for w in bench["command"])
    size = os.path.getsize(os.path.join(loading.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024
    # the full check must fit with 24 cells
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        data = loading.load_json(os.path.join(loading.ROOT, c["file"]))
        assert data["reduced"] == c["reduced"]
        family = data["family"]
        for kind in ("families", "reference"):
            assert os.path.isfile(os.path.join(loading.BENCH_DIR, kind, family + ".py"))


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and one_line(w["why"])
        traffic = loading.load_traffic(w["traffic"])
        assert os.path.isfile(
            os.path.join(loading.BENCH_DIR, "generators", traffic["kind"] + ".py"))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    every = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert one_line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]]
        mine = set(m.get("workloads", cells))
        assert mine <= set(moved.get("workloads", cells)), m["name"]
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(loading.BENCH_DIR, "metrics", m["name"] + ".py"))
        assert callable(loading.load_module("metrics", m["name"]).read)
    for cell in cells:
        mine = loading.metrics_of(bench, "end_to_end", cell)
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert loading.metrics_of(bench, "per_layer", cell)


def test_files_under_paths_are_named_from_allowed_characters(bench):
    for path in bench["paths"]:
        for folder, dirs, files in os.walk(os.path.join(loading.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), loading.ROOT)
                assert PATH.match(rel), rel


def test_run_py_holds_no_name_of_a_cell_config_traffic_or_metric(bench):
    text = open(os.path.join(loading.BENCH_DIR, "run.py")).read()
    names = {w["name"] for w in bench["workloads"]}
    names |= {w["traffic"] for w in bench["workloads"]}
    names |= {c["name"] for c in bench["configs"]}
    names |= {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    names |= {
        loading.load_json(os.path.join(loading.ROOT, c["file"]))["family"]
        for c in bench["configs"]
    }
    found = sorted(n for n in names if re.search(rf"\b{re.escape(n)}\b", text))
    assert not found, found
