"""``lm.experts_tile_fill``: held (lane, expert) pairs over the rows of the
tiles the grouped product's kernel visited, from the policy's own report. On
hand-written reports; the shares were worked out by hand."""

import os
import types

import pytest

from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def metric():
    return BenchmarkFiles(ROOT).layer_metric("lm.experts_tile_fill")


def run_of(counters):
    return types.SimpleNamespace(session=types.SimpleNamespace(policy_counters=lambda: counters))


@pytest.mark.parametrize(
    "counters,share",
    [
        # 512 lanes x 256 steps x 4 layers, one pair a lane and sixteen tiles a layer-step
        ({"expert_pairs_held": 524_288, "expert_row_tiles": 16_384, "expert_tile_rows": 128}, 25.0),
        ({"expert_pairs_held": 300, "expert_row_tiles": 3, "expert_tile_rows": 128}, 78.125),
        ({"expert_pairs_held": 256, "expert_row_tiles": 2, "expert_tile_rows": 128}, 100.0),
        # the plain form ran (the CPU, widths the kernel does not take): no tile
        ({"expert_pairs_held": 300, "expert_row_tiles": 0, "expert_tile_rows": 128}, 0.0),
        # a library from before the kernel (the parent commit): no such keys
        ({"expert_pairs_held": 300, "expert_pairs_fullest": 40}, 0.0),
        # no evaluation yet: nothing to read
        (None, None),
    ],
)
def test_share_of_a_report(metric, counters, share):
    got = metric.measure(run_of(counters))
    assert got is None if share is None else got == pytest.approx(share)


def test_it_is_read_where_the_experts_layer_is(metric):
    files = BenchmarkFiles(ROOT)
    assert metric.applies(files.workload("trinity_mini_ep8.decode256"))
    assert not metric.applies(files.workload("humanoid_mlp64.budget"))
