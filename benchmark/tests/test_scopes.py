"""The evaluation program's time by named scope (harness/scopes.py): on
hand-made events joined to a hand-written text, and on one small recording made
on the chip by ``record_scoped_trace.py`` (``data/scoped_1chip.xplane.pb``
beside its program's ``data/scoped_1chip.hlo.txt``). The expected figures were
worked out by hand, or read off a plain listing of the recording's events, not
computed with the code under test.
"""

import os
import re
import types

import pytest

from benchmark.harness import scopes, trace
from evotorch_tpu.observability.scopes import instruction_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))

# -- hand-made events, a hand-written text ---------------------------------------

TEXTS = {  # as a v5e trace names its ops: bare HLO text, no metadata
    "convert": "%copy.1 = bf16[8,21]{0,1:T(8,128)(2,1)} copy(f32[8,21]{1,0:T(8,128)} %params_batch.1)",
    "while": "%while.7 = (s32[]{:T(128)}, f32[3,8]{1,0}, bf16[8,21]{0,1}) while(%tuple.1), body=%body",
    "matvec": "%multiply_reduce_fusion.4 = bf16[8,3]{0,1} fusion(bf16[8,3,5]{0,2,1} %bitcast.5, bf16[8,5]{0,1} %obs), kind=kLoop",
    "tanh": "%fusion.3 = bf16[8,3]{0,1} fusion(bf16[8,3]{0,1} %multiply_reduce_fusion.4), kind=kLoop",
    "env": "%fusion.9 = f32[3,8]{1,0:T(4,128)} fusion(f32[3,8]{1,0} %state, f32[2,8]{1,0} %action), kind=kLoop",
    "reset": "%fusion.12 = f32[3,8]{1,0:T(4,128)} fusion(u32[8,2]{1,0} %keys), kind=kLoop",
    "stats": "%reduce_fusion.2 = f32[3]{0} fusion(f32[3,8]{1,0} %obs_next), kind=kInput",
    "counters": "%fusion.15 = s32[8]{0} fusion(pred[8]{0} %dones, s32[8]{0} %episodes), kind=kLoop",
    "relayout": "%copy.44 = f32[3,8]{0,1:T(8,128)} copy(f32[3,8]{1,0:T(4,128)} %fusion.9)",
    "late": "%fusion.77 = f32[8]{0} fusion(f32[8]{0} %scores), kind=kLoop",
    "update": "%fusion.1 = f32[21]{0} fusion(f32[21]{0} %mu, f32[8,21]{1,0} %samples), kind=kLoop",
}


def scoped(name):
    return f'metadata={{op_name="jit(run_vectorized_rollout)/{name}" source_file="vecrl.py" source_line=1}}'


# the same program as its compiled text prints it: every instruction, with the
# scope it was traced under. %copy.44 is compiler-made and has no metadata (it
# inherits its operand's scope, env_step), the while op names no scope, and
# %fusion.77 is not listed at all.
HLO_TEXT = f"""\
HloModule jit_run_vectorized_rollout, is_scheduled=true, entry_computation_layout={{(f32[8,21]{{1,0}})->f32[8]{{0}}}}

%body (arg: (s32[], f32[3,8], bf16[8,21])) -> (s32[], f32[3,8], bf16[8,21]) {{
  %arg = (s32[], f32[3,8]{{1,0}}, bf16[8,21]{{0,1}}) parameter(0)
  %multiply_reduce_fusion.4 = bf16[8,3]{{0,1}} fusion(%bitcast.5, %obs), kind=kLoop, calls=%fused.4, {scoped("while/body/evotorch_tpu.policy_forward/vmap(dot_general)")}
  %fusion.3 = bf16[8,3]{{0,1}} fusion(%multiply_reduce_fusion.4), kind=kLoop, calls=%fused.3, {scoped("while/body/evotorch_tpu.policy_forward/tanh")}
  %fusion.9 = f32[3,8]{{1,0:T(4,128)}} fusion(%state, %action), kind=kLoop, calls=%fused.9, {scoped("while/body/evotorch_tpu.env_step/add")}
  %copy.44 = f32[3,8]{{0,1:T(8,128)}} copy(%fusion.9)
  %fusion.12 = f32[3,8]{{1,0:T(4,128)}} fusion(%keys), kind=kLoop, calls=%fused.12, {scoped("while/body/evotorch_tpu.env_reset/select_n")}
  %reduce_fusion.2 = f32[3]{{0}} fusion(%obs_next), kind=kInput, calls=%fused.2, {scoped("while/body/evotorch_tpu.obs_norm/reduce_sum")}
  ROOT %fusion.15 = s32[8]{{0}} fusion(%dones, %episodes), kind=kLoop, calls=%fused.15, {scoped("while/body/evotorch_tpu.contract/add")}
}}

ENTRY %main (params_batch.1: f32[8,21]) -> f32[8] {{
  %params_batch.1 = f32[8,21]{{1,0:T(8,128)}} parameter(0)
  %copy.1 = bf16[8,21]{{0,1:T(8,128)(2,1)}} copy(%params_batch.1), {scoped("evotorch_tpu.rollout_edges/convert_element_type")}
  ROOT %while.7 = (s32[]{{:T(128)}}, f32[3,8]{{1,0}}, bf16[8,21]{{0,1}}) while(%tuple.1), condition=%cond, body=%body, {scoped("while")}
}}
"""


def hand_made_trace():
    """Two generations; in each an update program, then the evaluation: the
    cast of the population, a ``while`` of two control steps (forward, env
    substep, a relayout, reset, statistics, counters) and, inside the loop's
    span, one op the text does not list. Times in ns made up."""
    plane = trace.DevicePlane("/device:TPU:0")
    spans = []
    for start in (0, 20_000):
        spans.append((start, start + 19_000, "bench.generation"))
        plane.modules.append((start + 100, start + 400, "jit_update(1)", None))
        plane.ops.append((start + 100, start + 400, TEXTS["update"]))
        plane.modules.append((start + 1_000, start + 18_000, "jit_run_vectorized_rollout(2)", None))
        plane.ops.append((start + 1_000, start + 1_300, TEXTS["convert"]))
        plane.ops.append((start + 2_000, start + 18_000, TEXTS["while"]))
        for step in (start + 2_100, start + 9_000):
            plane.ops.append((step, step + 1_000, TEXTS["matvec"]))
            plane.ops.append((step + 1_000, step + 1_200, TEXTS["tanh"]))
            plane.ops.append((step + 1_300, step + 3_300, TEXTS["env"]))
            plane.ops.append((step + 3_300, step + 3_400, TEXTS["relayout"]))
            plane.ops.append((step + 3_500, step + 5_000, TEXTS["reset"]))
            plane.ops.append((step + 5_000, step + 5_700, TEXTS["stats"]))
            plane.ops.append((step + 5_800, step + 6_100, TEXTS["counters"]))
        plane.ops.append((start + 16_000, start + 16_050, TEXTS["late"]))
    return trace.Trace([plane], spans)


def test_instruction_and_module_names():
    assert scopes.instruction_name(TEXTS["matvec"]) == "multiply_reduce_fusion.4"
    assert scopes.instruction_name("%fusion = f32[]{:T(128)} fusion(f32[256,512] %x.1)") == "fusion"
    assert scopes.module_name("jit_run_vectorized_rollout(1234567890123)") == "jit_run_vectorized_rollout"
    assert scopes.module_name("jit_global_eval") == "jit_global_eval"


def test_seconds_by_scope_on_hand_made_events():
    split = scopes.reduce_trace(hand_made_trace(), HLO_TEXT, instruction_scopes)
    # four control steps in two generations; every op is a leaf, so its self
    # time is its length
    assert split["steps"] == 4 and split["generations"] == 2
    assert split["seconds"] == pytest.approx(
        {
            "policy_forward": 4 * (1_000 + 200) * 1e-9,
            "env_step": 4 * (2_000 + 100) * 1e-9,  # with the compiler-made relayout of its result
            "env_reset": 4 * 1_500e-9,
            "obs_norm": 4 * 700e-9,
            "contract": 4 * 300e-9,
            "rollout_edges": 2 * 300e-9,
        }
    )
    # self times nest under the while: what the body's ops leave of its 16,000 ns
    # is the loop's own, and the loop names no scope; nor does the op the text
    # does not list
    loop = 2 * (16_000 - 2 * (1_200 + 2_000 + 100 + 1_500 + 700 + 300) - 50)
    assert split["unscoped_s"] == pytest.approx((loop + 2 * 50) * 1e-9)
    assert split["unscoped_top"] == [
        ["while.7", pytest.approx(loop * 1e-9)],
        ["fusion.77 f32[8]", pytest.approx(100e-9)],
    ]
    # the scopes and the rest add up to the program's busy time: 2 x (300 + 16,000)
    assert sum(split["seconds"].values()) + split["unscoped_s"] == pytest.approx(2 * 16_300e-9)
    # beside it, what the metadata alone names: the relayout has none
    named = split["by_metadata"]
    assert named["seconds"]["env_step"] == pytest.approx(4 * 2_000e-9)
    assert named["unscoped_s"] == pytest.approx((loop + 4 * 100 + 2 * 50) * 1e-9)
    assert [label for label, _ in named["unscoped_top"]] == ["while.7", "copy.44 f32[3,8]", "fusion.77 f32[8]"]


def test_metric_readers_divide_by_steps_and_generations():
    run = types.SimpleNamespace(
        memo=lambda key, compute: scopes.reduce_trace(hand_made_trace(), HLO_TEXT, instruction_scopes)
    )
    assert scopes.per_step_ms(run, "env_step") == pytest.approx(2_100e-6)
    assert scopes.per_step_ms(run, "policy_forward") == pytest.approx(1_200e-6)
    assert scopes.per_generation_ms(run, "rollout_edges") == pytest.approx(300e-6)
    assert scopes.unscoped_share(run) == pytest.approx(100.0 * (2 * 4_350 + 100) / 32_600)
    nothing = types.SimpleNamespace(memo=lambda key, compute: None)
    assert scopes.per_step_ms(nothing, "env_step") is None
    assert scopes.per_generation_ms(nothing, "rollout_edges") is None
    assert scopes.unscoped_share(nothing) is None


STALE_TEXT = re.sub(r", metadata=\{[^}]*\}", "", HLO_TEXT)  # the same program, no metadata


def test_a_text_without_any_scope_reads_nothing(capsys):
    """Never a row of zeros."""
    assert "evotorch_tpu." not in STALE_TEXT
    assert scopes.reduce_trace(hand_made_trace(), STALE_TEXT, instruction_scopes) is None
    assert "carries a scope" in capsys.readouterr().err


def stale_cache_run(stale):
    """A run whose problem's evaluation, compiled through the persistent
    cache, prints ``stale`` and, compiled past it, ``HLO_TEXT``."""
    import jax

    lowered = []

    def lower_evaluation(popsize):
        lowered.append(popsize)
        cached = jax.config.jax_enable_compilation_cache
        compiled = types.SimpleNamespace(as_text=lambda: stale if cached else HLO_TEXT)
        return types.SimpleNamespace(compile=lambda: compiled)

    memo = {}
    run = types.SimpleNamespace(
        session=types.SimpleNamespace(problem=types.SimpleNamespace(lower_evaluation=lower_evaluation)),
        popsize=8,
        memo=lambda key, compute: memo.setdefault(key, compute()),
    )
    return run, lowered


@pytest.fixture
def cache_on():
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", True)
    yield
    assert jax.config.jax_enable_compilation_cache is True  # put back
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("stale", [STALE_TEXT, HLO_TEXT.replace("evotorch_tpu.env_reset", "select_n")])
def test_a_stale_cache_is_compiled_past(capsys, cache_on, stale):
    """What a compile cache written before a scope was added (by the parent
    commit) hands back lacks it: no metadata at all, or one name missing; its
    key ignores the scopes. The reader says so and takes the text from a
    compile the cache cannot answer, once a run."""
    run, lowered = stale_cache_run(stale)
    assert scopes.evaluation_text(run, scopes.ROLLOUT_READS) == HLO_TEXT and lowered == [8, 8]
    assert "rm -rf compile_cache" in capsys.readouterr().err
    assert scopes.evaluation_text(run, ("env_reset",)) == HLO_TEXT and len(lowered) == 2


def test_a_text_that_carries_every_name_read_is_compiled_once(cache_on):
    """Where the cache's executable carries every name the reader asks for,
    nothing is compiled past it; a name is matched as a whole scope, never as
    the start of a longer one."""
    run, lowered = stale_cache_run(HLO_TEXT)
    assert scopes.evaluation_text(run, ("policy_forward", "env_step", "env_reset")) == HLO_TEXT and lowered == [8]
    assert scopes.carries(HLO_TEXT, "env_reset") and not scopes.carries(HLO_TEXT, "env")
    assert not scopes.carries(STALE_TEXT, "policy_forward")


def test_another_programs_text_is_not_joined(capsys):
    other = HLO_TEXT.replace("HloModule jit_run_vectorized_rollout", "HloModule jit_global_eval")
    assert scopes.reduce_trace(hand_made_trace(), other, instruction_scopes) is None
    assert "jit_global_eval" in capsys.readouterr().err


def test_scope_seconds_lowers_nothing_without_a_device_trace():
    """A CPU rehearsal's trace has no device plane, a session may have no
    problem, a library from before the scopes no ``lower_evaluation``: each
    reads nothing, and the first before anything is lowered."""

    class Problem:
        def lower_evaluation(self, popsize):
            raise AssertionError("lowered without a device trace")

    def run(trace_, session):
        memo = {}
        return types.SimpleNamespace(
            trace=trace_,
            session=session,
            popsize=8,
            memo=lambda key, compute: memo.setdefault(key, compute()),
        )

    rehearsal = run(trace.Trace([], []), types.SimpleNamespace(problem=Problem()))
    assert scopes.scope_seconds(rehearsal) is None
    assert scopes.scope_seconds(run(None, types.SimpleNamespace(problem=Problem()))) is None
    assert scopes.scope_seconds(run(hand_made_trace(), types.SimpleNamespace())) is None
    assert scopes.scope_seconds(run(hand_made_trace(), types.SimpleNamespace(problem=object()))) is None


def test_scope_seconds_joins_the_problems_own_text(capsys):
    class Problem:
        def lower_evaluation(self, popsize):
            assert popsize == 8
            compiled = types.SimpleNamespace(as_text=lambda: HLO_TEXT)
            return types.SimpleNamespace(compile=lambda: compiled)

    memo = {}
    run = types.SimpleNamespace(
        trace=hand_made_trace(),
        session=types.SimpleNamespace(problem=Problem()),
        popsize=8,
        memo=lambda key, compute: memo.setdefault(key, compute()),
    )
    assert scopes.per_step_ms(run, "env_reset") == pytest.approx(1_500e-6)
    assert scopes.per_step_ms(run, "obs_norm") == pytest.approx(700e-6)
    assert set(memo) == {"scopes.scope_seconds", "scopes.evaluation_text"}  # lowered and reduced once
    assert '"lower_compile_s"' in capsys.readouterr().err  # the split and its cost, for PERF.md


# -- one small recording from the chip --------------------------------------------


def test_recorded_trace_joins_its_programs_text():
    """``record_scoped_trace.py`` on a v5e: two generations of five scan steps.
    The listing of the recording's events shows, in each program, a copy-start
    (5 ns), ``%broadcast_multiply_fusion`` (1,424 and 1,157 ns: the doubling,
    ``rollout_edges``), a copy-done (1,032 / 1,363), ``%convert.1`` (112 / 112),
    the ``while`` (20,613 / 20,612) around five ``%copy.9`` (499 / 498 ns in
    all) and five ``%fusion.9`` (20,017 / 20,015), then ``%reduce_sum.7`` (500 /
    498, the last ns of the first cut off by the program's end). The compiler
    fused the whole loop body, matmul, tanh, sine and add, into the ONE
    ``%fusion.9``, whose metadata is its dot's: a fusion has one name, so
    ``env_step`` gets no time of its own here. ``%copy.9`` is compiler-made and
    feeds only that fusion; the prefetch of ``w`` with its convert feeds the
    loop's tuple, and the sum reads the loop's result: plumbing on both sides,
    so they stay unscoped, like the loop's own 97 + 99 ns."""
    recorded = trace.load(os.path.join(DATA, "scoped_1chip.xplane.pb"), chips=1)
    with open(os.path.join(DATA, "scoped_1chip.hlo.txt")) as f:
        text = f.read()
    assert recorded.evaluation_module().startswith("jit_tiny_evaluation(")
    split = scopes.reduce_trace(recorded, text, instruction_scopes)
    assert split["steps"] == 10 and split["generations"] == 2
    assert set(split["seconds"]) == {"policy_forward", "rollout_edges"}
    assert split["seconds"]["policy_forward"] == pytest.approx((20_017 + 20_015 + 499 + 498) * 1e-9, abs=2e-9)
    assert split["seconds"]["rollout_edges"] == pytest.approx((1_424 + 1_157) * 1e-9, abs=2e-9)
    unscoped = (97 + 99) + (5 + 5) + (1_032 + 1_363) + (112 + 112) + (499 + 498)
    assert split["unscoped_s"] == pytest.approx(unscoped * 1e-9, abs=3e-9)
    assert [label for label, _ in split["unscoped_top"]][:2] == ["copy-done f32[512,512]", "reduce_sum.7 f32[]"]
    # by the metadata alone the compiler-made copy inside the loop has no scope either
    named = split["by_metadata"]
    assert named["seconds"]["policy_forward"] == pytest.approx((20_017 + 20_015) * 1e-9, abs=2e-9)
    assert named["unscoped_s"] == pytest.approx((unscoped + 499 + 498) * 1e-9, abs=3e-9)
    # everything adds up to the two programs' busy time
    assert sum(split["seconds"].values()) + split["unscoped_s"] == pytest.approx(recorded.busy_s, abs=3e-9)


def recorded_run(text_edit=lambda text: text, eval_mode="budget", **session):
    """A traced run of the recording: its text, as ``text_edit`` leaves it,
    for the program's; 256 lanes a step, whose first generation's telemetry
    (5 steps x 256 lanes) the window decoded and whose second's it did not."""
    recorded = trace.load(os.path.join(DATA, "scoped_1chip.xplane.pb"), chips=1)
    with open(os.path.join(DATA, "scoped_1chip.hlo.txt")) as f:
        text = text_edit(f.read())
    compiled = types.SimpleNamespace(as_text=lambda: text)
    lowered = types.SimpleNamespace(compile=lambda: compiled)
    memo = {}
    return types.SimpleNamespace(
        trace=recorded,
        session=types.SimpleNamespace(
            problem=types.SimpleNamespace(lower_evaluation=lambda popsize: lowered), **session
        ),
        workload={"traffic": {"eval_mode": eval_mode}},
        popsize=256,
        counts={"capacity_by_call": [5 * 256, None]},
        device_record={"kind": "TPU v5 lite"},
        memo=lambda key, compute: memo.setdefault(key, compute()),
    )


#: the recording's forward, ``fusion.9`` and the copy before it, per control step (ten steps)
RECORDED_FORWARD_NS = (20_017 + 20_015 + 499 + 498) / 10


def test_policy_roofline_share_on_the_recording():
    """The recorded forward reads its 512 x 512 float32 ``w`` once a step: as
    if each of the 256 lanes read its own 1,024 parameters. The lanes a step
    runs come from the telemetry's lane-step slots over the first generation's
    five steps; the floor, 256 x 1,024 x 4 B at 819 GB/s, is 1,280.4 ns."""
    from benchmark.harness.loader import BenchmarkFiles

    run = recorded_run(parameter_count=1_024, compute_dtype=None)
    assert scopes.lanes_per_step(run) == 256
    metric = BenchmarkFiles(ROOT).layer_metric("policy.roofline_share")
    assert metric.measure(run) == pytest.approx(100 * (256 * 1_024 * 4 / 819e9) / (RECORDED_FORWARD_NS * 1e-9), rel=1e-3)
    assert metric.measure(run) == pytest.approx(31.21, abs=0.01)
    # a bfloat16 forward reads half the bytes
    assert metric.measure(recorded_run(parameter_count=1_024, compute_dtype="bfloat16")) == pytest.approx(15.60, abs=0.01)
    # no generation's telemetry decoded in the window: no floor, nothing substituted
    silent = recorded_run(parameter_count=1_024, compute_dtype=None)
    silent.counts = {"capacity_by_call": [None, None]}
    assert scopes.lanes_per_step(silent) is None and metric.measure(silent) is None


@pytest.mark.parametrize(
    "eval_mode, capacity, lanes",
    [
        ("budget", 5 * 256, 256),
        ("episodes", 5 * 256, 256),
        ("budget", 5 * 512, None),  # more slots than the population has lanes
        ("episodes", 5 * 128, None),  # fewer than the population, where every lane runs every step
        ("episodes_refill", 5 * 128, 128),  # the working width under refill
        ("episodes_refill", 5 * 256, 256),
        ("episodes_refill", 5 * 257, None),  # over the population
    ],
)
def test_lanes_per_step_holds_the_capacity_to_the_traffic(capsys, eval_mode, capacity, lanes):
    """The capacity is a count the program makes about itself: it is held to
    what the traffic lets a step run (popsize over chips where every lane runs
    every step, at most popsize under refill), and a count that breaks that
    sets no floor."""
    run = recorded_run(eval_mode=eval_mode, parameter_count=1_024, compute_dtype=None)
    run.counts = {"capacity_by_call": [capacity, None]}
    assert scopes.lanes_per_step(run) == lanes
    assert ("does not match the trace" in capsys.readouterr().err) == (lanes is None)


@pytest.fixture
def kv_cache_declared(monkeypatch):
    """The library as it will be once it declares the attention cache's scope."""
    import evotorch_tpu.observability.scopes as library

    monkeypatch.setattr(library, "FORWARD_SCOPES", library.FORWARD_SCOPES + ("fwd_kv_cache",))


def test_lm_cache_roofline_share_on_the_recording(kv_cache_declared):
    """The recording with its forward's ops renamed into the decoder's cache
    pass (``policy_forward/fwd_attention/fwd_kv_cache``): the cache's time is
    theirs, read by the name alone. One full-attention layer of one KV head of
    128 over five steps: 256 lanes x 3 positions on average x 2 x 128 x 2 B =
    393,216 B a step, 480.1 ns at 819 GB/s."""
    from benchmark.harness import lm_scopes
    from benchmark.harness.loader import BenchmarkFiles

    renamed = "evotorch_tpu.policy_forward/evotorch_tpu.fwd_attention/evotorch_tpu.fwd_kv_cache/"
    sizes = {"layers": [0], "layer_types": ["full_attention"], "kv_heads": 1, "head_dim": 128, "window": 8}
    run = recorded_run(
        lambda text: text.replace("evotorch_tpu.policy_forward/", renamed),
        decode_steps=5, lm_sizes=sizes, compute_dtype="bfloat16",
    )
    split = lm_scopes.forward_seconds(run)
    assert split["cache_by"] == "scope" and split["steps"] == 10
    assert lm_scopes.cache_ms(run) == pytest.approx(RECORDED_FORWARD_NS * 1e-6, abs=3e-7)
    files = BenchmarkFiles(ROOT)
    assert files.layer_metric("lm.cache_ms").measure(run) == lm_scopes.cache_ms(run)
    assert files.layer_metric("lm.cache_roofline_share").measure(run) == pytest.approx(11.70, abs=0.01)
    # lm.attention_ms keeps its meaning: the attention scope with the cache's pass inside it
    assert files.layer_metric("lm.attention_ms").measure(run) == pytest.approx(lm_scopes.cache_ms(run))
    # every op the trace kept of the two programs, against their device time
    assert 99 < split["coverage_percent"] <= 100
