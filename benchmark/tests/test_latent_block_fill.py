"""``mla.latent_block_fill``: positions of the latent cache the lanes could
read over the positions in the blocks the cache pass's kernel fetched, from
the policy's own report. On hand-written reports; the shares were worked out
by hand."""

import os
import types

import pytest

from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def metric():
    return BenchmarkFiles(ROOT).layer_metric("mla.latent_block_fill")


def run_of(counters):
    return types.SimpleNamespace(session=types.SimpleNamespace(policy_counters=lambda: counters))


@pytest.mark.parametrize(
    "counters,share",
    [
        # 512 lanes x 5 layers x 512 steps that no lane ends early: t + 1 readable, ceil((t + 1) / 64) blocks of 64 fetched
        ({"latent_positions_read": 336_199_680, "latent_positions_fetched": 377_487_360}, 89.0625),
        ({"latent_positions_read": 300, "latent_positions_fetched": 384}, 78.125),
        ({"latent_positions_read": 256, "latent_positions_fetched": 256}, 100.0),
        # the plain form ran (the CPU, sizes the kernel does not take): nothing fetched by blocks
        ({"latent_positions_read": 300, "latent_positions_fetched": 0}, 0.0),
        # a library from before the kernel (the parent commit): no such key
        ({"latent_positions_read": 300, "expert_pairs_held": 40}, 0.0),
        # no evaluation yet: nothing to read
        (None, None),
    ],
)
def test_share_of_a_report(metric, counters, share):
    got = metric.measure(run_of(counters))
    assert got is None if share is None else got == pytest.approx(share)


def test_it_is_read_where_the_latent_cache_is(metric):
    files = BenchmarkFiles(ROOT)
    assert metric.applies(files.workload("glm47_flash_ep8.decode512"))
    assert not metric.applies(files.workload("trinity_mini_ep8.decode256"))
    assert not metric.applies(files.workload("humanoid_mlp64.budget"))
