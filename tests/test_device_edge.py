"""The device edge (ISSUE 21): no fallback hides the device, one rule places
the compile cache, and chip_smoke.py refuses to run anywhere but on the chip.

What needs the chip itself — the kernels compiled and run, the main path at
full width — is chip_smoke.py's; these are the parts a CPU can check.
"""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from evotorch_tpu.observability import cache_stats
from evotorch_tpu.observability.report import DEVICE_PEAKS, peak_flops
from evotorch_tpu.resilience import device_record, require_devices, setup_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, *, env_changes, timeout=120):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for name, value in env_changes.items():
        if value is None:
            env.pop(name, None)
        else:
            env[name] = value
    args = (
        [sys.executable, code_or_script]
        if code_or_script.endswith(".py")
        else [sys.executable, "-c", code_or_script]
    )
    return subprocess.run(
        args, env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout
    )


# -- no fallback ------------------------------------------------------------


def test_setup_backend_requires_an_accelerator_unless_the_cpu_is_asked_for(monkeypatch):
    # under pytest jax is on the CPU; with the request for it withdrawn,
    # that is a missing accelerator, not a place to carry on
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="accelerator is required"):
        setup_backend()
    with pytest.raises(RuntimeError, match="accelerator is required"):
        require_devices(accelerator=True)
    # asked for, the CPU is a backend like any other
    assert setup_backend(force_cpu=True) is True
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert setup_backend() is True
    assert len(require_devices(accelerator=False)) == jax.device_count()


def test_device_record_is_what_jax_reports():
    first = jax.devices()[0]
    assert device_record() == {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": jax.device_count(),
    }


def test_peak_flops_is_keyed_by_device_kind():
    def device(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    # Google Cloud "TPU v5e": 197 TFLOP/s bf16, 819 GB/s, 16 GB
    assert peak_flops(device("tpu", "TPU v5 lite")) == 197e12
    assert DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_sec"] == 819e9
    assert DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes"] == 16e9
    # no efficiency is claimed for a CPU run; an unknown chip is an error
    assert peak_flops(jax.devices()[0]) is None
    with pytest.raises(KeyError, match="TPU v9"):
        peak_flops(device("tpu", "TPU v9"))


def test_importing_the_package_initializes_no_backend():
    # a hostpool worker and the serving CLI pin the platform AFTER the
    # import; that only works while the import itself touches no device
    out = _run(
        "import evotorch_tpu\n"
        "from jax._src import xla_bridge\n"
        "print(len(xla_bridge._backends))",
        env_changes={},
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "0"


# -- the compile cache: placed from outside, one rule -----------------------


def test_conftest_placed_the_cache_by_the_rule():
    expected = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, "compile_cache", "tests"
    )
    assert jax.config.jax_compilation_cache_dir == expected
    assert cache_stats()["dir"] == expected


_CACHE_PROBE = (
    "import jax\n"
    "from evotorch_tpu.observability import enable_persistent_cache\n"
    "enable_persistent_cache()\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_enable_xla_caches)\n"
)


@pytest.mark.parametrize("placed", [True, False])
def test_cache_rule(tmp_path, placed):
    where = str(tmp_path / "some" / "dir") if placed else None
    out = _run(_CACHE_PROBE, env_changes={"JAX_COMPILATION_CACHE_DIR": where})
    assert out.returncode == 0, out.stderr
    cache_dir, xla_caches = out.stdout.strip().splitlines()[-2:]
    assert cache_dir == (where or os.path.join(REPO, "compile_cache"))
    # jax's default for this option writes <cache dir>/... into the hashed
    # compile options: a cache mounted at another path would never hit
    assert xla_caches == "none"


# -- chip_smoke.py ----------------------------------------------------------


def test_chip_smoke_fails_at_the_device_check_off_the_chip():
    out = _run(
        os.path.join(REPO, "chip_smoke.py"), env_changes={"JAX_PLATFORMS": "cpu"}
    )
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    # its first act: say what jax found — and nothing after it, no result
    assert len(lines) == 1
    assert json.loads(lines[0])["device"]["platform"] == "cpu"
    assert "needs a TPU" in out.stderr and "'platform': 'cpu'" in out.stderr
