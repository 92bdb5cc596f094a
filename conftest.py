"""Root conftest: configure JAX for CPU-mesh testing BEFORE jax initializes.

The reference tests "distributed" code via Ray local mode (reference
tests/conftest.py:24-40); our analog is a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count`` (SURVEY.md §4).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Tests always run on the CPU, whatever the machine offers: they need the
# virtual 8-device mesh and full-f32 matmul numerics. The chip is checked by
# chip_smoke.py and measured by the bench scripts, never by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"

from evotorch_tpu.observability import enable_persistent_cache  # noqa: E402
from evotorch_tpu.resilience import setup_backend  # noqa: E402

setup_backend(force_cpu=True)

# Persistent XLA compile cache for the suite, placed by the one rule of
# observability/compilecache.py: JAX_COMPILATION_CACHE_DIR when set, else the
# fixed compile_cache/tests under the checkout. The fast tier is
# compile-dominated — the same GSPMD programs are rebuilt module after
# module. Entries are keyed on HLO + compile options, so the
# 8-virtual-device test programs never collide with bench/TPU entries; the
# warm-process acceptance test (test_gspmd.py) hands its subprocesses a
# private directory through the same variable. Retrace sentinels count at
# the lowering layer, so a disk hit still registers as a compile and
# steady-state zero-counts are unaffected; the ledger gate bands
# flops/peak_bytes, not compile time (and its capture fixture bypasses the
# cache — deserialized executables report +1408 bytes of peak memory on this
# backend). One behavioral difference a warm run DOES have: a deserialized
# donated program may write outputs in place into the donated input buffer,
# so numpy VIEWS of to-be-donated arrays (np.asarray without .copy()) mutate
# — snapshot with an explicit copy (see test_trunk_delta.py's center_before).
enable_persistent_cache("tests")

import jax  # noqa: E402
import pytest  # noqa: E402


def _memory_mappings(path="/proc/self/maps") -> int:
    try:
        with open(path) as f:
            return sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count, nothing known to run out
        return 0


def _mapping_limit(path="/proc/sys/vm/max_map_count") -> int:
    try:
        with open(path) as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530  # the kernel's default


@pytest.fixture(autouse=True, scope="module")
def _release_executables():
    """Drop jax's executable caches once their memory mappings pile up.

    Each compiled (or cache-loaded) CPU executable holds a few memory
    mappings for as long as its jit-cache entry lives. Over the fast tier
    they add up to the kernel's ``vm.max_map_count`` (65,530 here), and the
    next compile then fails inside mmap: a segmentation fault or an abort in
    XLA at whichever test happens to come next, with the rest of the suite
    never run (ROADMAP D0). Between modules, once the process holds a third
    of the limit, the caches are cleared; the persistent cache makes the
    recompiles that follow cheap."""
    yield
    if _memory_mappings() > _mapping_limit() // 3:
        jax.clear_caches()
