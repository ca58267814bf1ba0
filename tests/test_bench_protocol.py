"""bench_protocol.aggregate: median/spread math over invocation samples
(the pure core of the round-3 benchmark protocol)."""

import importlib.util
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "bench_protocol", os.path.join(_ROOT, "scripts", "bench_protocol.py")
)
bp = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bp)


def _run(step_ms, bs_samples=8):
    return {
        "cfg": {
            "metric": "cfg",
            "step_ms": step_ms,
            "value": bs_samples / (step_ms / 1e3),
            "precision": "bf16-matmul",
        }
    }


def test_median_and_spread():
    runs = [_run(s) for s in (20.0, 30.0, 25.0, 24.0, 26.0)]
    out = bp.aggregate(runs)["cfg"]
    assert out["step_ms_median"] == 25.0
    assert out["spread_pct"] == pytest.approx(40.0)  # (30-20)/25
    # throughput from the median, not any single draw
    assert out["value"] == pytest.approx(8 / 0.025, rel=1e-6)
    assert out["protocol"] == "median of 5 process invocations"


def test_failed_invocations_are_dropped_not_fatal():
    ok = _run(25.0)
    bad = {"cfg": {"metric": "cfg", "error": "noise floor"}}
    out = bp.aggregate([ok, bad, ok])["cfg"]
    assert out["step_ms_median"] == 25.0
    assert out["protocol"] == "median of 2 process invocations"


def test_all_failed_reports_error():
    bad = {"cfg": {"metric": "cfg", "error": "noise floor"}}
    out = bp.aggregate([bad, bad])["cfg"]
    assert out["error"] == "no valid samples"


def test_parent_process_stays_off_jax():
    """The protocol's parent spawns one chip user at a time. A chip
    belongs to one process: a parent that had touched JAX would hold it
    and every child would fail or hang. Importing the launcher must pull
    in neither jax nor the package."""
    import subprocess
    import sys

    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('bp', sys.argv[1])\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'flexflow_tpu')]\n"
        "assert not bad, bad\n"
    )
    subprocess.run(
        [sys.executable, "-c", code,
         os.path.join(_ROOT, "scripts", "bench_protocol.py")],
        check=True,
        timeout=60,
    )
