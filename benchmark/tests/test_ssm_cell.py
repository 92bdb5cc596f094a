"""The state-space hybrid cell (``granite4_h_micro_pp4.decode256``): its files
against ``BENCHMARK.json`` and the published row, its floors against the
issue's counts and on a tiny configuration counted by hand, the reader of the
decoder's inner scopes (harness/ssm_scopes.py) on a small hand-written
compiled text joined to hand-made events, the cell traced end to end on the
CPU (``--rehearse``), and its comparison with int8 in the program's place.

The names of the cell's metrics are read from ``BENCHMARK.json``, never
written out here: the next ``ssm.*`` metric does not break this file.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import ssm_floors, ssm_scopes
from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "granite4_h_micro_pp4.decode256"
# granite-4.0-h-micro's config.json as the catalog has it (model-configs guide,
# architectures.jsonl, row 23): what the cell may not change
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}


@pytest.fixture(scope="module")
def files():
    return BenchmarkFiles(ROOT)


def cell_metrics(files, prefix=""):
    """The per-layer entries that list this cell, as ``BENCHMARK.json`` has them."""
    return [m for m in files.spec["per_layer"] if CELL in m.get("workloads", []) and m["name"].startswith(prefix)]


def test_the_cells_files_agree_with_the_benchmark(files):
    workload = files.workload(CELL)
    assert workload["driver"] == "oo_ssm_searcher" and workload["chips"] == 1
    assert workload["traffic"] == {"name": "decode256", "eval_mode": "budget", "num_actors": None, "search_seed": 1}
    assert (workload["warmup_generations"], workload["traced_generations"]) == (3, 2)
    own = cell_metrics(files, "ssm.")
    assert own
    for entry in own:
        module = files.layer_metric(entry["name"])
        assert entry["workloads"] == [CELL] and entry["moves"] == module.MOVES == "env_steps_per_s"
        assert (entry["layer"], entry["unit"], entry["better"], entry["source"]) == (
            module.LAYER, module.UNIT, module.BETTER, module.SOURCE
        )
        assert module.applies(workload) and entry["layer"] in workload["layers"]
    applies = {m["name"] for m in files.metrics("per_layer", CELL) if files.layer_metric(m["name"]).applies(workload)}
    assert {m["name"] for m in own} <= applies
    # the readers without a list of cells read this one too; the other families' do not
    assert {
        "searcher.steady_compiles", "searcher.outside_eval_ms", "contract.occupancy", "cache.misses",
        "device.idle_share", "device.peak_hbm_gb", "contract.bookkeeping_scope_ms", "contract.edges_scope_ms",
        "eval.unscoped_share",
    } <= applies
    # of the policy forward's and the env substep's readers, those that go by scope
    shared = {"policy.forward_scope_ms", "env.substep_scope_ms", "env.reset_scope_ms"}
    assert {"policy forward", "env substep"} <= set(workload["layers"])
    assert {m for m in applies if m.startswith(("policy.", "env."))} == shared
    assert not any(name.startswith(("lm.", "mla.")) for name in applies)
    for entry in files.spec["per_layer"]:
        if not entry["name"].startswith("ssm.") and CELL in entry.get("workloads", []):
            assert entry["name"] in shared or entry["name"].startswith("searcher."), entry["name"]
    listed = [w for w in files.spec["workloads"] if w["name"] == CELL]
    assert listed == [{"name": CELL, "config": "granite4_h_micro_pp4", "traffic": "decode256", "chips": 1, "why": workload["why"]}]
    assert files.spec["workloads"][-1]["name"] == CELL and files.spec["configs"][-1]["name"] == "granite4_h_micro_pp4"


def test_the_configuration_is_the_published_model_cut_by_share(files):
    config = files.config("granite4_h_micro_pp4")
    reduced = {"num_hidden_layers", "vocab_size"}
    assert set(config["reduced"]) == reduced | {"generations"}
    (listed,) = [c for c in files.spec["configs"] if c["name"] == "granite4_h_micro_pp4"]
    assert listed["reduced"] == config["reduced"] and listed["source"] == config["source"]
    # every key of the published row is there, unchanged unless it is under `reduced`
    assert {key: config[key] for key in PUBLISHED if key not in reduced} == {
        key: value for key, value in PUBLISHED.items() if key not in reduced
    }
    assert config["published"] == {key: PUBLISHED[key] for key in reduced}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 25088)
    assert config["layers_held"] == list(range(10)) and config["vocab_held"] == 25088 == PUBLISHED["vocab_size"] // 4
    assert "four pipeline stages" in config["deployment"] and config["assumed"] and config["left_out"]
    assert (config["popsize"], config["prompt_length"], config["decode_steps"]) == (256, 32, 256)
    reference = files.module_at(config["reference"]["forward"])
    sizes = reference.sizes(config)
    assert (sizes["hidden"], sizes["heads"], sizes["kv_heads"], sizes["head_dim"]) == (2048, 32, 8, 64)
    assert (sizes["ssm_heads"], sizes["ssm_head_dim"], sizes["ssm_state"], sizes["conv_width"]) == (64, 64, 128, 4)
    assert (sizes["mlp_width"], sizes["score_scale"], sizes["residual_scale"]) == (8192, 1 / 64, 0.22)
    assert [sizes["kinds"][i] for i in sizes["layers"]].count("attention") == 1 and sizes["kinds"][5] == "attention"
    assert reference.parameter_count(sizes) == config["parameter_count"] == 797_850_560
    # the rehearsal keeps every width: fewer lanes, steps, layers and rows
    small = reference.sizes(config, config["rehearse"])
    widths = ("hidden", "heads", "kv_heads", "head_dim", "mlp_width", "ssm_heads", "ssm_head_dim", "ssm_state", "conv_width")
    assert {k: small[k] for k in widths} == {k: sizes[k] for k in widths}
    assert small["layers"] == [0, 5] and small["vocab"] == 512  # a Mamba-2 layer, then the attention layer


TINY = {
    "hidden": 8, "heads": 2, "kv_heads": 1, "head_dim": 4, "mlp_width": 12, "ssm_heads": 2, "ssm_head_dim": 8,
    "ssm_state": 3, "conv_width": 4, "kinds": ["mamba", "attention", "mamba"], "layers": [0, 1, 2], "vocab": 10,
}


def test_floors_against_the_issues_counts_and_by_hand(files):
    config = files.config("granite4_h_micro_pp4")
    reference = files.module_at(config["reference"]["forward"])
    sizes = reference.sizes(config)
    # the issue's table
    assert ssm_floors.mamba_layer_parameters(sizes) == 76_182_976
    assert ssm_floors.attention_layer_parameters(sizes) == 60_821_504
    assert ssm_floors.parameters(sizes) == reference.parameter_count(sizes) == 797_850_560
    assert ssm_floors.state_bytes(sizes, 2) == 1 << 20  # 1 MiB a state in bfloat16
    assert ssm_floors.ssm_layers(sizes) == 9 and ssm_floors.expected_updates_per_step(sizes, 256) == 9 * 256
    # every step reads and writes all of it: 4.83 GB
    assert ssm_floors.state_bytes_per_step(sizes, 9 * 256, 2) == 2 * 9 * 256 * (1 << 20) == 4_831_838_208
    # a lane's token: about as many multiply-adds as the cut has parameters (the embedding's rows are the head)
    macs = ssm_floors.step_macs_per_lane(sizes, 128.5)
    assert macs == pytest.approx(0.80e9, rel=2e-2) and macs > ssm_floors.parameters(sizes) - 10 * 2 * 2048
    # by hand: inner 16, channels 22; in_proj 8 x (16 + 22 + 2) + out_proj 16 x 8; MLP 3 x 8 x 12
    assert ssm_floors.inner(TINY) == 16 and ssm_floors.conv_channels(TINY) == 22
    assert ssm_floors.mixer_matrix_macs(TINY) == 320 + 128 and ssm_floors.mlp_macs(TINY) == 288
    assert ssm_floors.attention_matrix_macs(TINY) == 2 * 8 * 8 + 2 * 8 * 4
    # a Mamba layer: matrices 448, taps and bias 22 x 5, three vectors of 2, gated norm 16, two norms 16, MLP 288
    assert ssm_floors.mamba_layer_parameters(TINY) == 448 + 110 + 6 + 16 + 16 + 288
    assert ssm_floors.attention_layer_parameters(TINY) == 192 + 16 + 288
    assert ssm_floors.parameters(TINY) == 2 * 884 + 496 + 8 + 80
    assert ssm_floors.state_numbers(TINY) == 48 and ssm_floors.state_bytes_per_step(TINY, 5, 4) == 2 * 5 * 48 * 4
    # head 80; two Mamba layers (448 + 88 taps + 2 x 48 of state + 288); attention 192 + 2 x 3 x 8 + 288
    assert ssm_floors.step_macs_per_lane(TINY, 3) == 80 + 2 * (448 + 88 + 96 + 288) + (192 + 48 + 288)


def named(path):
    return f'metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.policy_forward/{path}"}}'


STATE = "evotorch_tpu.fwd_ssm/evotorch_tpu.fwd_ssm_state"
HLO_TEXT = f"""\
HloModule jit_run_vectorized_rollout, is_scheduled=true

%fused.1 (p: bf16[4,2,8,3]) -> bf16[4,2,8,3] {{
  %p = bf16[4,2,8,3]{{3,2,1,0}} parameter(0)
  ROOT %add.1 = bf16[4,2,8,3]{{3,2,1,0}} add(%p, %p), {named(STATE + "/add")}
}}

%body (arg: (s32[], bf16[4,2,8,3])) -> (s32[], bf16[4,2,8,3]) {{
  %fusion.1 = bf16[4,2,8,3]{{3,2,1,0}} fusion(%state), kind=kLoop, calls=%fused.1, {named(STATE + "/add")}
  %fusion.2 = bf16[4,40]{{1,0}} fusion(%x), kind=kOutput, calls=%fused.2, {named("evotorch_tpu.fwd_ssm/dot_general")}
  %fusion.3 = bf16[4,8]{{1,0}} fusion(%y), kind=kOutput, calls=%fused.3, {named("evotorch_tpu.fwd_attention/dot_general")}
  %fusion.4 = bf16[4,12]{{1,0}} fusion(%y), kind=kOutput, calls=%fused.4, {named("evotorch_tpu.fwd_dense_mlp/dot_general")}
  %fusion.5 = bf16[4,10]{{1,0}} fusion(%h), kind=kLoop, calls=%fused.5, {named("evotorch_tpu.fwd_head/dot_general")}
  %fusion.6 = f32[4]{{0}} fusion(%scores), kind=kLoop, calls=%fused.6, metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.contract/add"}}
}}
"""


def test_inner_scope_reader_on_a_hand_written_text(files):
    ops = {  # HLO text as a trace names an op: [self seconds, executions]
        "%fusion.1 = bf16[4,2,8,3]{3,2,1,0} fusion(%state)": [0.40, 16],
        "%fusion.2 = bf16[4,40]{1,0} fusion(%x)": [0.20, 16],
        "%fusion.3 = bf16[4,8]{1,0} fusion(%y)": [0.04, 16],
        "%fusion.4 = bf16[4,12]{1,0} fusion(%y)": [0.24, 16],
        "%fusion.5 = bf16[4,10]{1,0} fusion(%h)": [0.08, 16],
        "%fusion.6 = f32[4]{0} fusion(%scores)": [0.04, 16],
    }
    trace = types.SimpleNamespace(
        planes=[object()], evaluation_ops=lambda: ops, generations=lambda: [0, 1], evaluation_seconds=lambda: 1.00
    )
    lowered = types.SimpleNamespace(compile=lambda: types.SimpleNamespace(as_text=lambda: HLO_TEXT))
    session = types.SimpleNamespace(
        problem=types.SimpleNamespace(lower_evaluation=lambda popsize: lowered),
        decode_steps=8,
        ssm_sizes=TINY,
        compute_dtype="bfloat16",
        policy_counters=lambda: {"ssm_state_updates": 64, "ssm_state_bytes": 4 * 2 * (48 + 66) * 2},
    )
    memo = {}
    run = types.SimpleNamespace(
        trace=trace, session=session, popsize=4, device_record={"kind": "TPU v5 lite"},
        memo=lambda key, compute: memo.setdefault(key, compute()),
    )
    split = ssm_scopes.forward_seconds(run)
    assert split["steps"] == 16  # 8 decode steps x 2 traced generations, from the session
    # an op under fwd_ssm AND fwd_ssm_state counts under the innermost
    assert split["seconds"] == pytest.approx(
        {"fwd_ssm_state": 0.40, "fwd_ssm": 0.20, "fwd_attention": 0.04, "fwd_dense_mlp": 0.24, "fwd_head": 0.08}
    )
    assert split["policy_forward_s"] == pytest.approx(0.96) and split["evaluation_s"] == pytest.approx(1.00)
    assert split["inner_share_of_policy_forward"] == pytest.approx(1.0)
    assert ssm_scopes.per_step_ms(run, "fwd_ssm_state") == pytest.approx(25.0)
    assert ssm_scopes.updates_per_step(run) == 8  # counted by the program: 64 over 8 steps
    by_name = {m["name"]: files.layer_metric(m["name"]).measure(run) for m in cell_metrics(files, "ssm.")}
    assert all(value is not None for value in by_name.values())  # every metric of the cell finds something to read
    # 8 states of 48 bfloat16 numbers, read and written, at 819 GB/s, over 25 ms
    assert by_name["ssm.state_roofline_share"] == pytest.approx(100 * (2 * 8 * 96 / 819e9) / 25e-3)
    assert by_name["ssm.step_mfu"] == pytest.approx(
        100 * 2 * ssm_floors.step_macs_per_lane(TINY, 4.5) * 4 / 197e12 / (1.00 / 16)
    )
    assert by_name["ssm.state_gb"] == pytest.approx(4 * 2 * 114 * 2 / 1e9)
    # a program that counts no update gets no floor: nothing substituted, nothing raised
    counted = session.policy_counters
    for silent in (lambda: None, lambda: {"ssm_state_bytes": 1}, lambda: {"ssm_state_updates": 0}):
        session.policy_counters = silent
        assert ssm_scopes.updates_per_step(run) is None
        assert files.layer_metric("ssm.state_roofline_share").measure(run) is None
    session.policy_counters = counted
    # a program without the state's scope (another decoder's, the parent's): nothing read, nothing raised
    other = HLO_TEXT.replace("/evotorch_tpu.fwd_ssm_state", "")
    lowered.compile = lambda: types.SimpleNamespace(as_text=lambda: other)
    memo.clear()
    assert ssm_scopes.forward_seconds(run) is None and ssm_scopes.per_step_ms(run, "fwd_ssm") is None
    traced = [m["name"] for m in cell_metrics(files, "ssm.") if m["source"] == "device_trace"]
    assert traced and all(files.layer_metric(name).measure(run) is None for name in traced)
    # no device trace (a CPU rehearsal): nothing is read, nothing is lowered
    run.trace = types.SimpleNamespace(planes=[])
    memo.clear()
    assert ssm_scopes.forward_seconds(run) is None


def test_the_cell_rehearses_traced_on_the_cpu(files):
    """``--rehearse --trace 1``: correct, the counted steps exact, and of the
    per-layer metrics the counters (the CPU's trace has no device plane, so
    the trace's readers find nothing and raise nothing)."""
    done = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload", CELL, "--seed", "2146000011",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    out = done.stdout.strip().splitlines()
    line = json.loads(out[-1])
    (detail,) = [json.loads(text[len("detail: "):]) for text in out[:-1] if text.startswith("detail: ")]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 2
    assert line["device"]["platform"] == "cpu"
    counters = {m["name"] for m in cell_metrics(files) if m["source"] == "program_counter"}
    assert counters and set(line["metrics"]) == counters | {"searcher.steady_compiles", "contract.occupancy", "cache.misses"}
    assert line["metrics"]["searcher.steady_compiles"]["value"] == 0
    assert line["metrics"]["contract.occupancy"]["value"] == 100.0
    # 4 lanes x one Mamba-2 layer x (64 x 64 x 128 + 3 x 4352) bfloat16 numbers
    assert line["metrics"]["ssm.state_gb"]["value"] == pytest.approx(4 * (64 * 64 * 128 + 3 * 4352) * 2 / 1e9)
    assert detail["counts"]["interactions"] == 2 * 4 * 8 and detail["counts"]["compiles_in_window"] == 0
    checks = detail["checks"]
    assert all(check["ok"] for check in checks.values()) and checks["record"]["emitted_tokens"] > 0
    assert checks["record"]["ssm_state_updates"] == 4 * 8  # every lane, every step, the one Mamba-2 layer
    assert checks["record"]["ssm_lane_resets"] >= 4  # every lane's episode ended at the cap at least
    # what the TIMED program's matrix states held at the cap against the reference's recurrence: one layer of 64 x 64
    assert checks["state"]["numbers_per_lane"] == 64 * 64 and 0 < checks["state"]["ended_state_relative_rms_error"] < 0.1


def test_a_lower_precision_in_the_programs_place_reads_several_times_the_systems_error():
    """The cell's comparison, at the rehearsal's scale on the CPU, with the
    reference's weights rounded to int8 standing in for the program: the same
    ``reference_checks``. Two layers over eight positions gather a third of
    the error that ten layers over 256 do, so int8 stays under the CELL's
    bound here (on the chip it does not: PERF.md) and the script says so by
    its exit code; what holds at any scale is the order of the readings."""
    done = subprocess.run(
        [sys.executable, os.path.join("scripts", "lm_ring_wrap_check.py"), "--cpu", "--tiny", "--control", "int8,bfloat16",
         "--cell", CELL],
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
    )
    assert done.returncode in (0, 1), done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["ok"]["system"] is True and line["ok"]["bfloat16"] is True
    assert line["system"]["record"]["emitted_tokens"] > 0
    error = {name: line[name]["logits"]["relative_rms_error"] for name in ("system", "bfloat16", "int8")}
    assert error["bfloat16"] < error["system"] < error["int8"] / 2
    state = {name: line[name]["state"]["ended_state_relative_rms_error"] for name in ("system", "bfloat16", "int8")}
    assert state["bfloat16"] < state["system"] and state["bfloat16"] < state["int8"] / 2.5
