"""``env.fused_lanes_share``: the fused physics kernel's useful over computed
lanes, read off the compiled evaluation program's text (the kernel's name
carries both). On hand-written texts; the shares were worked out by hand."""

import os
import types

import pytest

from benchmark.harness import trace
from benchmark.harness.loader import BenchmarkFiles
from benchmark.tests.test_scopes import HLO_TEXT, hand_made_trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def kernel_line(useful, computed, rows=143):
    name = f"rigidbody_fused_step_{useful}_of_{computed}"
    return (
        f"  %{name}.1 = f32[{rows},{computed // 128},128]{{2,1,0:T(8,128)}} custom-call(%bitcast.9),"
        f' custom_call_target="tpu_custom_call", operand_layout_constraints={{f32[160,{computed // 128},128]{{2,1,0}}}},'
        f' metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.env_step/cond/branch_0_fun/{name}/pallas_call"}}\n'
    )


def with_kernels(*instances):
    head, body = HLO_TEXT.split("  ROOT %fusion.15", 1)
    return head + "".join(kernel_line(*i) for i in instances) + "  ROOT %fusion.15" + body


@pytest.fixture(scope="module")
def metric():
    return BenchmarkFiles(ROOT).layer_metric("env.fused_lanes_share")


def test_share_of_a_text(metric):
    assert metric.share(HLO_TEXT) == 0.0  # XLA's plain form: no such kernel
    assert metric.share(with_kernels((50_000, 50_176))) == pytest.approx(99.649235, abs=1e-6)
    assert metric.share(with_kernels((12_500, 13_312))) == pytest.approx(93.900240, abs=1e-6)
    assert metric.share(with_kernels((8_192, 8_192))) == 100.0
    # two instances (a program that steps two widths): lanes over lanes
    assert metric.share(with_kernels((8_192, 8_192), (1_000, 1_024))) == pytest.approx(
        100.0 * 9_192 / 9_216
    )
    # another kernel's custom call, or the name outside a custom call, is not one
    other = kernel_line(10, 1024).replace("rigidbody_fused_step", "ranking_kernel")
    assert metric.share(HLO_TEXT + other) == 0.0
    assert metric.share(HLO_TEXT + "// rigidbody_fused_step_10_of_1024\n") == 0.0


def run_of(trace_, session, memo):
    def take_once(key, compute):  # timing.Run.memo
        if key not in memo:
            memo[key] = compute()
        return memo[key]

    return types.SimpleNamespace(trace=trace_, session=session, popsize=8, memo=take_once)


def test_nothing_is_lowered_without_a_device_trace(metric):
    class Problem:
        def lower_evaluation(self, popsize):
            raise AssertionError("lowered without a device trace")

    session = types.SimpleNamespace(problem=Problem())
    assert metric.measure(run_of(trace.Trace([], []), session, {})) is None  # a CPU rehearsal
    assert metric.measure(run_of(None, session, {})) is None
    assert metric.measure(run_of(hand_made_trace(), types.SimpleNamespace(), {})) is None
    assert metric.measure(run_of(hand_made_trace(), types.SimpleNamespace(problem=object()), {})) is None


def test_measure_reads_the_problems_own_text_once(metric):
    lowered = []

    class Problem:
        def __init__(self, text):
            self.text = text

        def lower_evaluation(self, popsize):
            lowered.append(popsize)
            compiled = types.SimpleNamespace(as_text=lambda: self.text)
            return types.SimpleNamespace(compile=lambda: compiled)

    memo = {}
    run = run_of(
        hand_made_trace(), types.SimpleNamespace(problem=Problem(with_kernels((10_000, 10_240)))), memo
    )
    assert metric.measure(run) == pytest.approx(97.65625)
    assert metric.measure(run) == pytest.approx(97.65625)
    assert lowered == [8] and list(memo) == ["scopes.evaluation_text"]  # the text the scope readers join to
    # a program from before the kernel (the parent commit): 0, not nothing
    parent = run_of(hand_made_trace(), types.SimpleNamespace(problem=Problem(HLO_TEXT)), {})
    assert metric.measure(parent) == 0.0
