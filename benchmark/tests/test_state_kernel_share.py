"""``ssm.state_kernel_share``: the lane-layer matrix states the state pass's
kernel rewrote over those the program rewrote, from the policy's own report.
On hand-written reports; the shares were worked out by hand."""

import os
import types

import pytest

from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def metric():
    return BenchmarkFiles(ROOT).layer_metric("ssm.state_kernel_share")


def run_of(counters):
    return types.SimpleNamespace(session=types.SimpleNamespace(policy_counters=lambda: counters))


@pytest.mark.parametrize(
    "counters,share",
    [
        # 256 lanes x 9 Mamba-2 layers x 256 steps, every pass the kernel's
        ({"ssm_state_updates": 589_824, "ssm_state_kernel_updates": 589_824}, 100.0),
        # three layers of four took the kernel
        ({"ssm_state_updates": 400, "ssm_state_kernel_updates": 300}, 75.0),
        # the plain form ran (the CPU, sizes the kernel does not take)
        ({"ssm_state_updates": 589_824, "ssm_state_kernel_updates": 0}, 0.0),
        # a library from before the kernel (the parent commit): no such key
        ({"ssm_state_updates": 589_824, "ssm_lane_resets": 256}, 0.0),
        # a report that counts no update
        ({"ssm_state_updates": 0, "ssm_state_kernel_updates": 0}, 0.0),
        ({"expert_pairs_held": 40}, 0.0),
        # no evaluation yet: nothing to read
        (None, None),
    ],
)
def test_share_of_a_report(metric, counters, share):
    got = metric.measure(run_of(counters))
    assert got is None if share is None else got == pytest.approx(share)


def test_it_is_read_where_the_matrix_states_are(metric):
    files = BenchmarkFiles(ROOT)
    assert metric.applies(files.workload("granite4_h_micro_pp4.decode256"))
    assert not metric.applies(files.workload("glm47_flash_ep8.decode512"))
    assert not metric.applies(files.workload("trinity_mini_ep8.decode256"))
    assert not metric.applies(files.workload("humanoid_mlp64.budget"))


def test_the_benchmark_lists_it_for_the_granite_cell_alone():
    entry = [m for m in BenchmarkFiles(ROOT).spec["per_layer"] if m["name"] == "ssm.state_kernel_share"]
    assert entry == [
        {
            "name": "ssm.state_kernel_share", "unit": "%", "better": "higher", "source": "program_counter",
            "layer": "ssm state", "moves": "env_steps_per_s", "workloads": ["granite4_h_micro_pp4.decode256"],
        }
    ]
