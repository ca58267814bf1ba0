"""The seams PR 21 added so the program cannot look healthy on a chip it
is not running on: where the compile cache goes, which chip the cost
model prices, what a kernel's first dispatch may raise, which native
library is loaded."""

import os
import subprocess
import sys
import types

import jax
import pytest

from flexflow_tpu import FFConfig, MachineSpec
from flexflow_tpu.core import machine
from flexflow_tpu.utils import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache ------------------------------------------------------------


@pytest.fixture
def cache_config():
    """Restore jax_compilation_cache_dir whatever a test sets."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_set_is_left_to_jax(monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_compilation_cache_dir", "/some/dir")  # as JAX does
    assert compile_cache.place_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == "/some/dir"


def test_cache_unset_goes_to_the_checkout_on_an_accelerator(
    monkeypatch, cache_config
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.place_compile_cache() == compile_cache.DEFAULT_DIR
    assert compile_cache.DEFAULT_DIR == os.path.join(_ROOT, ".jax_cache")


def test_cache_unset_on_cpu_places_nothing(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == before


def test_cache_default_is_the_same_path_in_every_process():
    """The directory is part of each cache entry's key: two processes
    must agree on it, whatever their pid, cwd or start time."""
    code = (
        "from flexflow_tpu.utils.compile_cache import DEFAULT_DIR; "
        "print(DEFAULT_DIR)"
    )
    seen = {
        subprocess.run(
            [sys.executable, "-c", code],
            cwd=cwd,
            env={**os.environ, "PYTHONPATH": _ROOT},
            check=True,
            capture_output=True,
            text=True,
            timeout=120,
        ).stdout.strip()
        for cwd in (_ROOT, os.path.join(_ROOT, "tests"))
    }
    assert seen == {compile_cache.DEFAULT_DIR}


# -- device_kind -> chip ------------------------------------------------------


def _fake_devices(monkeypatch, platform, kind):
    device = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [device])


def test_known_tpu_kind_sets_the_chip(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite")
    assert machine.detect_chip() == "v5e"
    spec = MachineSpec(num_nodes=1, chips_per_node=1)
    assert spec.chip == "v5e"
    assert spec.hbm_bytes == 16 << 30 and spec.peak_tflops == 197.0
    # what every MachineSpec(..., chip=cfg.chip) gets from a default config
    assert MachineSpec(chip=FFConfig().chip).chip == "v5e"


def test_unknown_tpu_kind_raises(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v9 mega")
    with pytest.raises(ValueError, match="TPU v9 mega"):
        machine.detect_chip()
    with pytest.raises(ValueError, match="DEVICE_KIND_TO_CHIP"):
        MachineSpec(num_nodes=1, chips_per_node=1)


def test_cpu_keeps_the_search_without_hardware_target():
    assert machine.detect_chip() == "v4"
    assert MachineSpec().chip == "v4"
    assert MachineSpec(chip="v5e").chip == "v5e"  # --chip still wins


def test_every_listed_kind_has_specs():
    assert set(machine.DEVICE_KIND_TO_CHIP.values()) <= set(machine.CHIP_SPECS)


def test_compile_fills_in_the_config_chip():
    from flexflow_tpu import FFModel

    model = FFModel(FFConfig(batch_size=4))
    model.dense(model.create_tensor([4, 8], name="x"), 2)
    assert model.config.chip == ""
    model.compile(devices=jax.devices()[:1])
    assert model.config.chip == "v4"


# -- native library -----------------------------------------------------------


def test_native_build_is_keyed_on_the_sources():
    """A library is loaded only when its stamp matches the sources on
    disk: mtimes (which a copied checkout does not keep) decide nothing."""
    from flexflow_tpu import native

    if not native.available():
        pytest.skip("no native toolchain here")
    assert native.implementation().startswith("native (")
    assert native._built_from(native._source_hash())
    assert not native._built_from("0" * 16)


# -- chip_smoke.py's contract -------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_verdict_has_exactly_the_contract_keys():
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    line = smoke.verdict(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": 0}
    )
    assert "\n" not in line
    out = json.loads(line)
    assert list(out) == ["ok", "device"] and out["ok"] is True
    assert out["device"] == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }


def test_chip_smoke_refuses_a_cpu_and_prints_no_result():
    run = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert run.returncode == 2
    assert run.stdout == ""
    assert "'cpu'" in run.stderr
