"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware (SURVEY §4's
"simulated-topology" lesson; the driver separately dry-runs the multi-chip
path via __graft_entry__.dryrun_multichip)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")


import shutil
import subprocess
import sys as _sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def has_c_toolchain() -> bool:
    return shutil.which("gcc") is not None and shutil.which("make") is not None


import functools


@functools.lru_cache(maxsize=None)
def build_capi_lib():
    """Build libflexflow_c once per session (cached; shared by test_capi
    and test_capi_client — keeping one make recipe avoids drift)."""
    build = subprocess.run(
        [
            "make",
            "-C",
            os.path.join(_ROOT, "native"),
            f"PYTHON={_sys.executable}",  # embed THIS interpreter's Python
            "capi",
        ],
        capture_output=True,
        text=True,
    )
    assert build.returncode == 0, build.stderr
