"""A generation outside the evaluation, by phase (harness/phases.py): on a
hand-built trace, and on one small recording made on the chip by
``record_phases_trace.py`` (``data/phases_1chip.xplane.pb``: two real
generations of ``VecNE`` + dense ``PGPE``). The expected figures were worked
out by hand, or read off a plain listing of the recording's ``XLA Modules``
events, not computed with the code under test.
"""

import json
import os
import types

import pytest

from benchmark.harness import phases, trace
from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = (
    "searcher.ask_ms",
    "searcher.grad_ms",
    "searcher.update_ms",
    "searcher.unnamed_ms",
    "searcher.idle_ms",
    "searcher.dispatches",
)
MS = 1_000_000  # ns

# -- a hand-built trace ----------------------------------------------------------

ROLLOUT = "jit_run_vectorized_rollout(5)"
GRAD = "jit_evotorch_tpu_grad_grads(1)"
ADD = "jit_add(2)"
TELL = "jit_evotorch_tpu_update_trunk_delta_tell(3)"
ASK = "jit_evotorch_tpu_ask_sample(4)"
EXTREMES = "jit_evotorch_tpu_evaluate_batch_extremes(6)"
NANMEAN = "jit_nanmean(7)"
STATUS = "jit_evotorch_tpu_status_mean(8)"


def hand_built_trace(named=True):
    """Two generations of 1,000 ms, [0, 1000] and [2000, 3000]; times in ms.

    The first: grad 50, an eager add 10, the update 30, ask 100, the evaluation
    600, a best/worst program of the ``evaluate`` phase 10, and a ``nanmean`` of
    100 that STRADDLES the span's end (50 inside): busy 850, idle 150, seven
    programs started. The second: an eager add that began before the span (10
    of its 20 inside; not a dispatch of this generation), grad 60, ask 120, the
    evaluation 650, a program of the ``status`` phase 10: busy 850, idle 150,
    four programs started. ``named=False``: the same trace from a library
    that names no program."""
    modules = [
        (10, 60, GRAD),
        (70, 80, ADD),
        (100, 130, TELL),
        (150, 250, ASK),
        (300, 900, ROLLOUT),
        (910, 920, EXTREMES),
        (950, 1050, NANMEAN),
        (1990, 2010, ADD),
        (2020, 2080, GRAD),
        (2100, 2220, ASK),
        (2300, 2950, ROLLOUT),
        (2960, 2970, STATUS),
    ]
    ops = [
        (150, 230, "%broadcast_add_fusion = f32[8,4]{1,0} fusion(f32[4]{0} %mu, f32[4,4]{1,0} %eps), kind=kLoop"),
        (230, 250, "%copy.1 = f32[8,4]{1,0} copy(f32[4,2,4]{2,1,0} %interleaved)"),
        (2100, 2200, "%broadcast_add_fusion = f32[8,4]{1,0} fusion(f32[4]{0} %mu, f32[4,4]{1,0} %eps), kind=kLoop"),
        (2200, 2220, "%copy.1 = f32[8,4]{1,0} copy(f32[4,2,4]{2,1,0} %interleaved)"),
        (10, 60, "%fusion.1 = f32[4]{0} fusion(f32[8,4]{1,0} %samples, f32[8]{0} %weights), kind=kLoop"),
        (300, 900, "%while.7 = (s32[], f32[3,8]{1,0}) while(%tuple.1), body=%body"),
        (70, 80, "%add.1 = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)"),
        (950, 1050, "%reduce.3 = f32[] reduce(f32[8]{0} %evals, f32[] %zero), dimensions={0}"),
    ]
    plane = trace.DevicePlane("/device:TPU:0")
    rename = (lambda name: name) if named else (lambda name: name.replace("evotorch_tpu_", ""))
    plane.modules = [(s * MS, e * MS, rename(name), i) for i, (s, e, name) in enumerate(modules)]
    plane.ops = [(s * MS, e * MS, text) for s, e, text in ops]
    spans = [(0, 1000 * MS, "bench.generation"), (2000 * MS, 3000 * MS, "bench.generation")]
    return trace.Trace([plane], spans)


def run_of(trace_):
    memo = {}

    def take_once(key, compute):
        if key not in memo:
            memo[key] = compute()
        return memo[key]

    return types.SimpleNamespace(trace=trace_, memo=take_once)


def test_split_by_the_phase_in_a_programs_name():
    first, second = phases.split(hand_built_trace())
    assert first["seconds"] == pytest.approx(
        {"grad": 0.050, "unnamed": 0.060, "update": 0.030, "ask": 0.100, "evaluate": 0.010}
    )
    assert (first["idle"], first["dispatches"]) == (pytest.approx(0.150), 7)
    assert second["seconds"] == pytest.approx({"unnamed": 0.010, "grad": 0.060, "ask": 0.120, "status": 0.010})
    assert (second["idle"], second["dispatches"]) == (pytest.approx(0.150), 4)


def test_the_five_times_and_the_small_named_programs_sum_to_outside_eval_ms(capsys):
    trace_ = hand_built_trace()
    run = run_of(trace_)
    assert phases.named_ms(run, "ask") == pytest.approx(110.0)
    assert phases.named_ms(run, "grad") == pytest.approx(55.0)
    assert phases.named_ms(run, "update") == pytest.approx(15.0)
    assert phases.unnamed_ms(run) == pytest.approx(35.0)
    assert phases.idle_ms(run) == pytest.approx(150.0)
    assert phases.dispatches(run) == pytest.approx(5.5)
    assert trace_.outside_eval_ms() == pytest.approx(375.0)
    # 365 + the two programs named for `evaluate` and `status` (10 and 10: median 10)
    assert 110.0 + 55.0 + 15.0 + 35.0 + 150.0 + 10.0 == pytest.approx(trace_.outside_eval_ms())
    # the split is taken once, and goes to stderr for people
    (line,) = [l for l in capsys.readouterr().err.splitlines() if l.startswith("benchmark: phases: ")]
    said = json.loads(line[len("benchmark: phases: ") :])
    assert said["ms"] == pytest.approx(
        {"ask": 110.0, "evaluate": 5.0, "grad": 55.0, "status": 5.0, "unnamed": 35.0, "update": 15.0}
    )
    assert said["top_ops_s"]["ask"] == [["broadcast_add_fusion f32[8,4]", pytest.approx(0.180)], ["copy.1 f32[8,4]", pytest.approx(0.040)]]
    assert said["top_ops_s"]["unnamed programs"] == [["jit_nanmean", pytest.approx(0.100)], ["jit_add", pytest.approx(0.030)]]


def test_a_library_that_names_no_program_reads_no_phase_and_never_a_zero():
    run = run_of(hand_built_trace(named=False))
    assert phases.named_ms(run, "ask") is None
    assert phases.named_ms(run, "grad") is None
    assert phases.named_ms(run, "update") is None
    # what needs no name is read all the same: everything but the evaluation
    assert phases.unnamed_ms(run) == pytest.approx(0.5 * (250.0 + 200.0))
    assert phases.idle_ms(run) == pytest.approx(150.0)
    assert phases.dispatches(run) == pytest.approx(5.5)


@pytest.mark.parametrize("metric", METRICS)
def test_a_cpu_rehearsal_reads_nothing(metric):
    module = BenchmarkFiles(ROOT).layer_metric(metric)
    assert module.measure(run_of(trace.Trace([], []))) is None
    assert module.measure(run_of(trace.Trace([], [(0, 10, "bench.generation")]))) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_six_entries_list_every_accepted_cell(metric):
    spec = BenchmarkFiles(ROOT).spec
    (entry,) = [m for m in spec["per_layer"] if m["name"] == metric]
    assert entry["layer"] == "OO searcher" and entry["moves"] == "generation_s"
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    listed = [w["name"] for w in spec["workloads"] if "OO searcher" in BenchmarkFiles(ROOT).workload(w["name"])["layers"]]
    assert entry["workloads"] == listed[: len(entry["workloads"])] and len(entry["workloads"]) >= 8


# -- the recording ---------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return trace.load(os.path.join(DATA, "phases_1chip.xplane.pb"))


def test_the_recording_splits_as_its_listing_says(recorded, capsys):
    """Read off a listing of the recording's ``XLA Modules`` events (ns, after
    ``load``'s clock shift of 1,240,519): each generation starts 51 programs,
    none straddling an edge: one ``jit_evotorch_tpu_grad_grads`` (4,983 |
    5,211), one ``jit_evotorch_tpu_ask_sample`` (3,178 | 3,181), the rollout
    (26,297 | 26,284), ``jit_evotorch_tpu_evaluate_batch_extremes`` and
    ``_merge_snapshots`` (together 11,366 | 11,273) and 46 eager programs
    without a name (together 37,704 | 37,408), in spans of 20,065,218 |
    18,758,698: a generation this small is all host."""
    run = run_of(recorded)
    assert recorded.evaluation_module().startswith("jit_run_vectorized_rollout(")
    assert phases.named_ms(run, "ask") == pytest.approx(0.5 * (3_178 + 3_181) / MS)
    assert phases.named_ms(run, "grad") == pytest.approx(0.5 * (4_983 + 5_211) / MS)
    assert phases.named_ms(run, "update") == 0.0  # the dense update is eager ops
    assert phases.unnamed_ms(run) == pytest.approx(0.5 * (37_704 + 37_408) / MS)
    assert phases.idle_ms(run) == pytest.approx(0.5 * (19_981_690 + 18_675_341) / MS)
    assert phases.dispatches(run) == 51
    said = json.loads(capsys.readouterr().err.split("benchmark: phases: ")[1].splitlines()[0])
    assert said["ms"]["evaluate"] == pytest.approx(0.5 * (11_366 + 11_273) / MS)
    assert [name for name, _ in said["top_ops_s"]["unnamed programs"]][:1] == ["jit__threefry_split"]


def test_in_the_recording_the_parts_sum_to_outside_eval_ms(recorded):
    run = run_of(recorded)
    five = (
        phases.named_ms(run, "ask")
        + phases.named_ms(run, "grad")
        + phases.named_ms(run, "update")
        + phases.unnamed_ms(run)
        + phases.idle_ms(run)
    )
    named_for_evaluate = 0.5 * (11_366 + 11_273) / MS
    assert recorded.outside_eval_ms() == pytest.approx(19.3856675)
    assert five + named_for_evaluate == pytest.approx(recorded.outside_eval_ms(), rel=1e-9)
    assert abs(five - recorded.outside_eval_ms()) < 1.0  # the criterion a cell is held to: 1% or 1 ms


def test_the_recordings_host_phases_are_siblings_and_ask_does_not_hold_evaluate(recorded):
    generations = recorded.generations()
    for start, end in generations:
        inside = sorted(
            (s, e, name)
            for s, e, name in recorded.spans
            if start <= s and e <= end and name.startswith("evotorch_tpu.") and name != "evotorch_tpu.generation"
        )
        assert [name.split(".")[1] for _, _, name in inside] == [
            "status", "grad", "update", "ask", "evaluate", "status", "status",
        ]
        for (_, before_end, _), (after_start, _, _) in zip(inside, inside[1:]):
            assert before_end <= after_start
