"""`run.py --rehearse` end to end on the CPU for every cell (four
virtual devices for the four-chip cell): a last line with the contract's
keys and no device metric. And that a cell, a configuration, a traffic
mix and a metric are added by adding files, with no edit to a file that
is there. And that the chip path refuses a machine without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import loading

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root, workload, trace, extra=(), rehearse=True, seconds="1.5"):
    env = dict(os.environ)
    env["PYTHONPATH"] = loading.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
           "--workload", workload, "--seed", str(2**31 + 77),
           "--seconds", seconds, "--trace", str(trace), *extra]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def counters(bench):
    return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if m["source"] == "program_counter"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize(
    "workload", [w["name"] for w in loading.load_benchmark()["workloads"]])
def test_rehearsal_prints_the_contract_line_and_no_device_metric(workload, trace):
    bench = loading.load_benchmark()
    cell = loading.find_cell(bench, workload)
    line = last_line(run_cell(loading.ROOT, workload, trace))
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell["chips"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert set(line["metrics"]) <= counters(bench)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    ran = set(line["rehearsal"]["readers_that_ran_but_are_not_metrics_on_a_cpu"])
    group = "per_layer" if trace else "end_to_end"
    mine = {m["name"] for m in loading.metrics_of(bench, group, workload)}
    assert (ran | set(line["metrics"])) <= mine
    if not trace:
        assert "setup_s" in ran
    assert line["window_compiles"]["compiles"] == 0


def test_without_a_tpu_the_chip_path_exits_nonzero_and_prints_no_result():
    name = loading.load_benchmark()["workloads"][0]["name"]
    proc = run_cell(loading.ROOT, name, 0, rehearse=False)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_a_cell_config_traffic_and_metric_are_added_by_files_alone(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(loading.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(folder, f)
            before[p] = open(p, "rb").read()
    bench = loading.load_benchmark()
    base = loading.load_json(os.path.join(loading.ROOT, bench["configs"][0]["file"]))
    small = dict(base, name="ff_small")
    small.update(base["rehearse"])
    with open(os.path.join(root, "benchmarks", "configs", "ff_small.json"), "w") as f:
        json.dump(small, f)
    with open(os.path.join(root, "benchmarks", "traffic", "fit_tiny.json"), "w") as f:
        json.dump({"kind": "fit_epochs", "global_batch": 2, "batches_per_epoch": 2,
                   "lead_in_epochs": 1, "trace_epochs": 1}, f)
    with open(os.path.join(root, "benchmarks", "metrics", "epochs_counted.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.record['epoch_stamps']) - 1)\n")
    bench["configs"].append({"name": "ff_small", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/ff_small.json", "why": "test"})
    bench["workloads"].append({"name": "train_small_tiny", "config": "ff_small",
                               "traffic": "fit_tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("train_small_tiny")
    bench["per_layer"].append({"name": "epochs_counted", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "harness", "moves": "train_tokens_per_s",
                               "workloads": ["train_small_tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line = last_line(run_cell(root, "train_small_tiny", 1))
    assert line["correct"] is True
    assert line["metrics"]["epochs_counted"] == {"value": 1.0, "unit": "count"}
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"


def test_a_process_that_compiled_starts_over_in_place(monkeypatch):
    """No child process: the same process id runs the same command again,
    told when it first started, with no descriptor to inherit."""
    from benchmarks import run

    seen = {}

    def fake_execve(path, args, env):
        seen.update(path=path, args=args, env=env)
        raise SystemExit(0)

    monkeypatch.setattr(run.os, "execve", fake_execve)
    r, w = os.pipe()
    os.set_inheritable(r, True)
    argv = ["--workload", "x", "--seed", "1", "--seconds", "1"]
    try:
        with pytest.raises(SystemExit):
            run.start_over(argv)
        assert not os.get_inheritable(r)
    finally:
        os.close(r)
        os.close(w)
    assert seen["path"] == sys.executable
    assert seen["args"][1].endswith("run.py") and seen["args"][2:] == argv
    assert seen["env"]["BENCH_STARTED_OVER"] == str(os.getpid())
    assert float(seen["env"]["BENCH_T0"]) == run.T_WALL_START
    assert not hasattr(run, "subprocess")
