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


def page_geometry(layout: str, max_seq_len: int) -> dict:
    """ServeConfig keywords of a test's cache geometry. "paged" is
    ServeConfig()'s own (pages of 16, halved until they divide the
    sequence); "one_page" is one page a slot with the default pool: a
    block table one wide, no growth past the first page, the kernel's
    page as long as the sequence. A boundary to test at these tests'
    32 to 64 positions, not a setting to serve with."""
    return {"paged": {}, "one_page": {"kv_page_size": max_seq_len}}[layout]


def ref_generate(model, prompt, n):
    """Recomputed full-prefill forward per emitted token, with no cache:
    the oracle the serving path must reproduce."""
    import numpy as np

    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = np.asarray(
            model.forward({"tokens": np.asarray([toks], dtype=np.int32)})
        )
        t = int(np.argmax(logits[0, len(toks) - 1]))
        out.append(t)
        toks.append(t)
    return out
