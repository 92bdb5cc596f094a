"""The trace reduction, on plain intervals and on traces recorded on the chip.

``data/1chip.xplane.pb`` and ``data/4chip.xplane.pb`` were made by
``record_trace.py`` on a v5e (one chip; a 2x2 host). The expected figures were
read off a plain listing of the recordings' events, not computed with the code
under test.
"""

import os

import pytest

from benchmark.harness import trace
from benchmark.harness.loader import BenchmarkFiles

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# -- arithmetic ---------------------------------------------------------------


def test_merge_and_length():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert trace.length(trace.merge([(0, 10), (2, 3), (8, 12)])) == 12


def test_clip_and_gaps():
    busy = [(0, 4), (5, 7), (20, 30)]
    assert trace.clip(busy, 2, 6) == [(2, 4), (5, 6)]
    assert trace.gaps(busy, 2, 25) == [(4, 5), (7, 20)]
    assert trace.gaps([], 3, 8) == [(3, 8)]
    assert trace.gaps([(0, 100)], 3, 8) == []


def test_self_times_take_nested_events_out():
    events = [
        (0, 100, "while"),
        (10, 30, "fusion.a"),
        (30, 50, "fusion.b"),
        (60, 90, "inner-while"),
        (65, 75, "fusion.a"),
        (200, 210, "fusion.c"),
    ]
    assert trace.self_times(events) == {
        "while": 30,
        "fusion.a": 30,
        "fusion.b": 20,
        "inner-while": 20,
        "fusion.c": 10,
    }


def test_innermost_span():
    spans = [(0, 100, "bench.generation"), (10, 40, "evotorch_tpu.update"), (90, 100, "bench.block")]
    assert trace.innermost(spans, 20) == "evotorch_tpu.update"
    assert trace.innermost(spans, 50) == "bench.generation"
    assert trace.innermost(spans, 95) == "bench.block"
    assert trace.innermost(spans, 150) is None


def test_op_label():
    text = "%multiply_reduce_fusion.160 = bf16[10000,64]{0,1:T(8,128)(2,1)S(1)} fusion(bf16[10000,64,109] %x)"
    assert trace.op_label(text) == "multiply_reduce_fusion.160 bf16[10000,64]"
    assert trace.op_label("%while.7 = (s32[]{:T(128)}, f32[11,3,10000]) while(%t)") == "while.7"
    assert trace.op_label("%fusion = f32[]{:T(128)} fusion(f32[256,512] %x.1)") == "fusion f32[]"


# -- the evaluation program's ops ---------------------------------------------


def hand_made_trace():
    """Two generations; in each an update program, then the evaluation: a
    ``while`` of two control steps, each a slice of the flat bf16 weights, a
    matrix-vector fusion over a weight block and an env fusion; before the
    loop the float32 population is converted once. HLO texts as a v5e prints
    them (chiprun_out of PR 22), times in ns made up."""
    flat = "bf16[8,21]{0,1:T(8,128)(2,1)}"
    texts = {
        "convert": f"%copy.1 = {flat} copy(f32[8,21]{{1,0:T(8,128)}} %params_batch.1)",
        "while": f"%while.7 = (s32[]{{:T(128)}}, f32[3,8]{{1,0}}, {flat}) while(%tuple.1), body=%body",
        "slice": f"%split.2 = bf16[8,15]{{0,1}} slice({flat} %get-tuple-element.3), slice={{[0:8], [3:18]}}",
        "matvec": "%multiply_reduce_fusion.4 = bf16[8,3]{0,1} fusion(bf16[8,3,5]{0,2,1} %bitcast.5, bf16[8,5]{0,1} %obs), kind=kLoop",
        "env": "%fusion.9 = f32[3,8]{1,0:T(4,128)} fusion(f32[3,8]{1,0} %state, f32[2,8]{1,0} %action), kind=kLoop",
        "update": "%fusion.1 = f32[21]{0} fusion(f32[21]{0} %mu, f32[8,21]{1,0} %samples), kind=kLoop",
    }
    plane = trace.DevicePlane("/device:TPU:0")
    spans = []
    for start in (0, 10_000):
        spans.append((start, start + 9_000, "bench.generation"))
        plane.modules.append((start + 100, start + 400, "jit_update(1)", None))
        plane.ops.append((start + 100, start + 400, texts["update"]))
        plane.modules.append((start + 1_000, start + 8_000, "jit_run_vectorized_rollout(2)", None))
        plane.ops.append((start + 1_000, start + 1_200, texts["convert"]))
        plane.ops.append((start + 2_000, start + 8_000, texts["while"]))
        for step in (start + 2_100, start + 5_000):
            plane.ops.append((step, step + 1_000, texts["slice"]))
            plane.ops.append((step + 1_000, step + 1_400, texts["matvec"]))
            plane.ops.append((step + 1_500, step + 2_500, texts["env"]))
    return trace.Trace([plane], spans), texts


def test_evaluation_ops_are_those_inside_the_longest_program():
    made, texts = hand_made_trace()
    assert made.evaluation_module() == "jit_run_vectorized_rollout(2)"
    ops = made.evaluation_ops()
    assert texts["update"] not in ops  # another program's op
    assert ops[texts["slice"]] == pytest.approx([4_000e-9, 4])
    assert ops[texts["matvec"]] == pytest.approx([1_600e-9, 4])
    assert ops[texts["env"]] == pytest.approx([4_000e-9, 4])
    assert ops[texts["convert"]] == pytest.approx([400e-9, 2])
    # the loop's own time is what its body's ops leave: 2 x (6,000 - 2 x 2,400)
    assert ops[texts["while"]] == pytest.approx([2_400e-9, 2])


def test_executions_and_evaluation_seconds():
    made, texts = hand_made_trace()
    # two control steps in each generation's evaluation, none in the update
    assert made.executions(texts["matvec"], 0, 9_000) == 2 and made.executions(texts["matvec"], 10_000, 19_000) == 2
    assert made.executions(texts["matvec"], 0, 19_000) == 4 and made.executions(texts["update"], 1_000, 9_000) == 0
    # the evaluation program's two runs, 7,000 ns each; the update's are not counted
    assert made.evaluation_seconds() == pytest.approx(2 * 7_000e-9)
    # its ops' self times add up to it but for the gaps between them
    assert sum(s for s, _ in made.evaluation_ops().values()) == pytest.approx(2 * (200 + 6_000) * 1e-9)
    assert trace.Trace([], []).evaluation_seconds() == 0.0


def test_policy_floor():
    """The least time one control step's forward can take on a chip: the lanes
    it runs there, each reading its own bfloat16 parameters, at 819 GB/s."""
    floor_ms = BenchmarkFiles(ROOT).layer_metric("policy.roofline_share").floor_ms
    # 10,000 lanes x 98,321 parameters: 2.40 ms
    assert floor_ms(10_000, 98_321, 2, 819e9) == pytest.approx(2.4010, abs=1e-4)
    # popsize 50,000 over four chips: 12,500 lanes a chip
    assert floor_ms(12_500, 12_305, 2, 819e9) == pytest.approx(0.3756, abs=1e-4)
    # refill's working width: 8,192 lanes a step whatever the popsize
    assert floor_ms(8_192, 12_305, 2, 819e9) == pytest.approx(0.2462, abs=1e-4)


# -- one chip -----------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    return trace.load(os.path.join(DATA, "1chip.xplane.pb"), chips=1)


def test_one_chip_window_and_busy(one_chip):
    # three bench.generation spans: 46,342,329 (+3,142,440), 49,491,299
    # (+2,822,070), 52,321,238 (+2,896,560) ns
    assert one_chip.generations() == [
        (46342329.0, 49484769.0),
        (49491299.0, 52313369.0),
        (52321238.0, 55217798.0),
    ]
    assert one_chip.window_s == pytest.approx(8875469e-9)
    # each program: a copy-start, a copy-done and one fusion, back to back with
    # gaps of a few ns: 13+3+2187, 13+3+2183 and 14+2+2421 ns
    assert one_chip.busy_s == pytest.approx(6839e-9, rel=1e-9)
    assert one_chip.idle_share() == pytest.approx(100.0 * (1.0 - 6839 / 8875469), rel=1e-9)
    assert one_chip.busy_spread() is None and one_chip.collective_share() == 0.0


def test_one_chip_time_by_name(one_chip):
    ops = one_chip.op_seconds()
    assert ops["fusion f32[]"] == pytest.approx((2187 + 2183 + 2421) * 1e-9)
    assert ops["copy-done f32[512,512]"] == pytest.approx(8e-9)
    assert ops["copy-start"] == pytest.approx(40e-9)
    assert one_chip.breakdown()["device_ops"][0][0] == "fusion f32[]"
    (module,) = one_chip.module_seconds()
    assert module.startswith("jit_tiny_generation(")
    assert one_chip.module_seconds()[module] == pytest.approx((2211 + 2206 + 2443) * 1e-9)


def test_one_chip_clocks_are_aligned(one_chip):
    # on the device's own clock the first program starts 1.27 ms, the second
    # 1.30 ms BEFORE the host enqueues it (48,788,974 vs 47,516,433 ns)
    assert 1.25e6 < one_chip.clock_shift_ns < 1.40e6
    # shifted, every program lies inside the generation that launched it
    for (start, end), module in zip(one_chip.generations(), one_chip.planes[0].modules):
        assert start < module[0] and module[1] < end


def test_one_chip_outside_eval_and_gaps(one_chip):
    # span minus the program inside it: 3,140,229 / 2,819,864 / 2,894,117 ns
    assert one_chip.outside_eval_ms() == pytest.approx(2.894117)
    owners = one_chip.gap_seconds()
    # the host sleeps 2 ms inside evotorch_tpu.update in every generation; the
    # wait for the result sits in bench.block; the few ns between the ops of
    # one program fall while evotorch_tpu.evaluate is still open
    assert set(owners) == {"evotorch_tpu.update", "bench.block", "evotorch_tpu.evaluate"}
    assert owners["evotorch_tpu.evaluate"] < 100e-9
    assert owners["evotorch_tpu.update"] > 0.85 * sum(owners.values())
    assert sum(owners.values()) + one_chip.busy_s == pytest.approx(one_chip.window_s)
    assert [name for name, _ in one_chip.breakdown()["idle_gaps"]][:2] == [
        "evotorch_tpu.update",
        "bench.block",
    ]


# -- four chips ---------------------------------------------------------------


@pytest.fixture(scope="module")
def four_chips():
    return trace.load(os.path.join(DATA, "4chip.xplane.pb"), chips=4)


def test_four_chips_busy_and_spread(four_chips):
    assert [plane.name for plane in four_chips.planes] == [f"/device:TPU:{i}" for i in range(4)]
    # 143,610,557 to 151,358,737 + 3,254,940 ns
    assert four_chips.window_s == pytest.approx(11003120e-9)
    # per chip and generation: copy-start + copy-done + fusion + all-reduce, e.g.
    # chip 0: 2+3+2428+4733, 2+3+2184+5046, 2+3+2302+4922 ns
    assert four_chips.busy_by_plane() == pytest.approx([21630e-9, 19070e-9, 19211e-9, 16119e-9])
    assert four_chips.busy_s == pytest.approx(19007.5e-9)
    assert four_chips.busy_spread() == pytest.approx(100.0 * (21630 - 16119) / 21630)
    assert four_chips.idle_share() == pytest.approx(100.0 * (1 - 19007.5 / 11003120))


def test_four_chips_collectives(four_chips):
    # the all-reduce of each generation: 14,701 / 12,249 / 12,388 / 9,316 ns a chip
    assert four_chips.collective_share() == pytest.approx(100.0 * 12163.5 / 11003120)
    ops = four_chips.op_seconds()
    assert ops["all-reduce f32[]"] == pytest.approx(12163.5e-9)


def test_four_chips_clock_shift_pairs_launches_by_device(four_chips):
    # run ids count per device (chip 0 ran 7, 8, 9; the others 2, 3, 4): the
    # largest lead is chip 2's second program, 150,705,036 - 149,399,335 ns
    assert four_chips.clock_shift_ns == pytest.approx(1305701.0)


def test_a_trace_without_device_planes_reads_as_nothing():
    # what a CPU rehearsal records: host annotations and no /device:TPU plane
    empty = trace.Trace([], [(0.0, 10.0, "bench.generation")])
    assert empty.busy_s == 0.0 and empty.window_s == pytest.approx(10e-9)
    assert empty.idle_share() is None and empty.outside_eval_ms() is None
    assert empty.breakdown() == {"device_ops": [], "idle_gaps": []}
