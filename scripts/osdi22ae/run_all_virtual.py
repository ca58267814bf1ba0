"""Run the full OSDI'22-AE-style searched-vs-DP table on a virtual
8-device CPU mesh (no TPU pod needed — the same trick the test suite
uses; reference: scripts/osdi22ae/*.sh each compare one workload on 4
GPUs).

    python scripts/osdi22ae/run_all_virtual.py [--budget 10] [workload]
"""

from __future__ import annotations

import os
import runpy
import sys

# eight virtual host devices, set before anything imports jax
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

if __name__ == "__main__":
    argv = sys.argv[1:]
    if not argv or argv[0].startswith("-"):
        argv = ["--all"] + argv
    sys.argv = [os.path.join(os.path.dirname(__file__), "compare.py")] + argv
    runpy.run_path(sys.argv[0], run_name="__main__")
