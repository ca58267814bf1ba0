"""Fixed benchmark protocol (VERDICT r2 item 9): median of N>=5 PROCESS
invocations with the spread reported, replacing best-of-day numbers.

Each invocation of scripts/bench_configs.py is a fresh process, and the
only process that touches JAX: this parent imports none of it, because a
parent that holds the chip leaves none for its children. Within-invocation
noise is already handled by the differencing min. This wrapper aggregates:

    python scripts/bench_protocol.py [-n 5] [config ...]

writes BENCH_CONFIGS.json with {median, spread_pct, samples} per config.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def aggregate(runs):
    """Median + spread per config over N invocation dicts (the pure core,
    unit-tested in tests/test_bench_protocol.py)."""
    results = {}
    names = []
    for r in runs:  # union of configs, first-seen order
        for name in r:
            if name not in names:
                names.append(name)
    for name in names:
        valid = [
            r[name] for r in runs if name in r and "step_ms" in r[name]
        ]
        if not valid:
            results[name] = {"metric": name, "error": "no valid samples"}
            continue
        steps = [v["step_ms"] for v in valid]
        med = statistics.median(steps)
        spread = (max(steps) - min(steps)) / med * 100.0
        ss = sorted(steps)
        # interquartile-style confidence interval: the middle half of the
        # draws (robust to one contended invocation, which the raw
        # max-min spread is not)
        lo = ss[len(ss) // 4]
        hi = ss[-(len(ss) // 4) - 1]
        base = valid[0]
        bs = base["value"] * base["step_ms"] / 1e3  # samples per step
        results[name] = {
            "metric": name,
            "protocol": f"median of {len(steps)} process invocations",
            "step_ms_median": round(med, 3),
            "step_ms_samples": [round(s, 3) for s in steps],
            "spread_pct": round(spread, 1),
            "step_ms_iqr": [round(lo, 3), round(hi, 3)],
            "value": round(bs / (med / 1e3), 2),
            "unit": "samples/s",
            "precision": base["precision"],
        }
    return results


def main():
    args = sys.argv[1:]
    n = 5
    if "-n" in args:
        i = args.index("-n")
        n = int(args[i + 1])
        del args[i : i + 2]
    runs = []
    for rep in range(n):
        with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
            out = f.name
        cmd = [sys.executable, "scripts/bench_configs.py", "--out", out] + args
        try:
            subprocess.run(cmd, cwd=ROOT, check=True)
            with open(out) as fh:
                runs.append(json.load(fh))
        finally:
            os.unlink(out)
        print(f"[protocol] invocation {rep + 1}/{n} done", flush=True)

    results = aggregate(runs)
    for row in results.values():
        print(json.dumps(row), flush=True)
    with open(os.path.join(ROOT, "BENCH_CONFIGS.json"), "w") as f:
        json.dump(results, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    main()
