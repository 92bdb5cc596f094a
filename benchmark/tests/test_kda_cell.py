"""The hybrid linear-attention cell (``kimi_linear_ep32.decode256``): its files
against ``BENCHMARK.json`` and the published row, its floors against the
issue's counts and on a tiny configuration counted by hand, the reader of the
decoder's inner scopes (harness/kda_scopes.py) on a small hand-written
compiled text joined to hand-made events (a whole trace and one that lost
steps), the cell traced end to end on the CPU (``--rehearse``), and its
comparison with lower precisions and a changed equation in the program's
place.

The names of the cell's metrics are read from ``BENCHMARK.json``, never
written out here: the next ``kda.*`` metric does not break this file.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import kda_floors, kda_scopes
from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kimi_linear_ep32.decode256"
#: the per-layer entries of other layers that list this cell too: by scope
SHARED = {"policy.forward_scope_ms", "env.substep_scope_ms", "env.reset_scope_ms"}
# Kimi-Linear-48B-A3B-Instruct's config.json as the catalog has it (model-configs
# guide, architectures.jsonl): what the cell may not change
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
    "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26], "num_heads": 32,
        "short_conv_kernel_size": 4,
    },
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}


@pytest.fixture(scope="module")
def files():
    return BenchmarkFiles(ROOT)


def cell_metrics(files, prefix=""):
    """The per-layer entries that list this cell, as ``BENCHMARK.json`` has them."""
    return [m for m in files.spec["per_layer"] if CELL in m.get("workloads", []) and m["name"].startswith(prefix)]


def test_the_cells_files_agree_with_the_benchmark(files):
    workload = files.workload(CELL)
    assert workload["driver"] == "oo_kda_searcher" and workload["chips"] == 1
    assert workload["traffic"] == {"name": "decode256", "eval_mode": "budget", "num_actors": None, "search_seed": 1}
    assert (workload["warmup_generations"], workload["traced_generations"]) == (3, 2)
    assert set(workload["layers"]) == {
        "OO searcher", "eval contract", "policy forward", "env substep", "compile cache", "device", "kda forward",
        "kda state",
    }
    applies = {m["name"] for m in files.metrics("per_layer", CELL) if files.layer_metric(m["name"]).applies(workload)}
    own = {m["name"] for m in cell_metrics(files, "kda.")}
    assert len(own) == 5 and {m for m in applies if m.startswith("kda.")} == own
    # the readers without a list of cells read this one too; the other families' do not
    assert {
        "searcher.steady_compiles", "searcher.outside_eval_ms", "contract.occupancy", "cache.misses",
        "device.idle_share", "device.peak_hbm_gb", "contract.bookkeeping_scope_ms", "contract.edges_scope_ms",
        "eval.unscoped_share",
    } <= applies
    # of the policy forward's and the env substep's readers, those that go by scope
    assert {m for m in applies if m.startswith(("policy.", "env."))} == SHARED
    assert not any(name.startswith(("lm.", "mla.", "ssm.")) for name in applies)
    for entry in files.spec["per_layer"]:
        if entry["name"].startswith("kda."):
            module = files.layer_metric(entry["name"])
            assert entry["workloads"] == [CELL] and entry["moves"] == module.MOVES == "env_steps_per_s"
            assert entry["layer"] in ("kda forward", "kda state")
        elif CELL in entry.get("workloads", []):  # listed with the cells of its own layer
            assert entry["name"] in SHARED or entry["name"].startswith("searcher."), entry["name"]
    listed = [w for w in files.spec["workloads"] if w["name"] == CELL]
    assert listed == [{"name": CELL, "config": "kimi_linear_ep32", "traffic": "decode256", "chips": 1, "why": workload["why"]}]


def test_the_configuration_is_the_published_model_cut_by_share(files):
    config = files.config("kimi_linear_ep32")
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(config["reduced"]) == reduced | {"generations"}
    (listed,) = [c for c in files.spec["configs"] if c["name"] == "kimi_linear_ep32"]
    assert listed["reduced"] == config["reduced"] and listed["source"] == config["source"]
    # every key of the published row is there, unchanged unless it is under `reduced`
    assert {key: config[key] for key in PUBLISHED if key not in reduced} == {
        key: value for key, value in PUBLISHED.items() if key not in reduced
    }
    assert config["published"] == {key: PUBLISHED[key] for key in reduced}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 8, 20480)
    assert config["layers_held"] == [0, 1, 2, 3, 4] and config["experts_held"] == [0, 8]
    assert config["vocab_held"] == 20480 == PUBLISHED["vocab_size"] // 8
    assert "32 chips" in config["deployment"] and config["assumed"] and config["left_out"]
    assert (config["popsize"], config["prompt_length"], config["decode_steps"]) == (512, 32, 256)
    reference = files.module_at(config["reference"]["forward"])
    sizes = reference.sizes(config)
    assert (sizes["hidden"], sizes["kda_heads"], sizes["kda_head_dim"], sizes["conv_width"]) == (2304, 32, 128, 4)
    assert (sizes["heads"], sizes["kv_rank"], sizes["nope"], sizes["rope"], sizes["v"]) == (32, 512, 128, 64, 128)
    assert (sizes["dense_width"], sizes["expert_width"], sizes["top_k"], sizes["route_scale"]) == (9216, 1024, 8, 2.446)
    assert sizes["num_experts"] == 256 and sizes["layers"] == [0, 1, 2, 3, 4] and sizes["num_dense_layers"] == 1
    assert [i for i in sizes["layers"] if i not in sizes["kda_layers"]] == [3]  # the one latent-attention layer
    assert reference.parameter_count(sizes) == config["parameter_count"] == 602_434_432
    # the rehearsal keeps every width: fewer lanes, steps, KDA layers with experts and rows
    small = reference.sizes(config, config["rehearse"])
    widths = ("hidden", "kda_heads", "kda_head_dim", "heads", "kv_rank", "nope", "rope", "v", "expert_width", "num_experts", "top_k")
    assert {k: small[k] for k in widths} == {k: sizes[k] for k in widths}
    assert small["layers"] == [0, 1, 3] and small["vocab"] == 512  # layer 0, one KDA layer with experts, the MLA layer


TINY = {
    "hidden": 8, "kda_heads": 2, "kda_head_dim": 4, "conv_width": 4, "heads": 2, "kv_rank": 6, "nope": 3, "rope": 2,
    "v": 5, "dense_width": 16, "expert_width": 4, "num_experts": 16, "top_k": 4, "shared": 1, "num_dense_layers": 1,
    "kda_layers": [0, 1, 2, 4], "layers": [0, 1, 3], "experts_held": (0, 4), "vocab": 10,
}


def test_floors_against_the_issues_counts_and_by_hand(files):
    config = files.config("kimi_linear_ep32")
    sizes = files.module_at(config["reference"]["forward"]).sizes(config)
    assert kda_floors.state_bytes(sizes, 2) == 1 << 20  # 1 MiB a lane-layer state in bfloat16
    assert kda_floors.kda_layers(sizes) == 4 and kda_floors.expected_updates_per_step(sizes, 512) == 4 * 512
    # every step reads and writes all of it: 4.29 GB
    assert kda_floors.state_bytes_per_step(sizes, 512, 2) == 2 * 4 * 512 * (1 << 20) == 4_294_967_296
    # a lane's token: about as many multiply-adds as the cut has parameters less the embedding and the idle experts
    macs = kda_floors.step_macs_per_lane(sizes, 256)
    assert 0.25e9 < macs < 0.35e9
    # by hand: P 8; W_q, W_k, W_v, W_o 4 x 8 x 8, two gate pairs 2 x (8 x 4 + 4 x 8), W_b 8 x 2
    assert kda_floors.inner(TINY) == 8 and kda_floors.kda_matrix_macs(TINY) == 256 + 128 + 16
    assert kda_floors.state_numbers(TINY) == 32 and kda_floors.kda_layers(TINY) == 2
    assert kda_floors.state_bytes_per_step(TINY, 4, 2) == 2 * 4 * 2 * 64
    # W_q 8 x 2 x 5, W_kva 8 x 8, the absorbed products 2 x (3 + 5) x 6, W_o 2 x 5 x 8; a position 2 x (12 + 2)
    assert kda_floors.attention_macs(TINY) == 80 + 64 + 96 + 80 and kda_floors.cache_macs_per_position(TINY) == 28
    # head 80; layer 0 KDA (400 + taps 96 + state 128) and dense MLP 384; layer 1 KDA 624, router 128, one
    # shared and one held pair of 96; layer 3 latent attention 320 + 4 positions x 28, router 128, pairs 192
    assert kda_floors.step_macs_per_lane(TINY, 7) == 80 + (624 + 384) + (624 + 128 + 192) + (320 + 112 + 128 + 192)


def named(path):
    return f'metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.policy_forward/{path}"}}'


STATE = "evotorch_tpu.fwd_kda/evotorch_tpu.fwd_kda_state"
HLO_TEXT = f"""\
HloModule jit_run_vectorized_rollout, is_scheduled=true

%fused.1 (p: bf16[4,4,2,4]) -> bf16[4,4,2,4] {{
  %p = bf16[4,4,2,4]{{3,2,1,0}} parameter(0)
  ROOT %add.1 = bf16[4,4,2,4]{{3,2,1,0}} add(%p, %p), {named(STATE + "/add")}
}}

%body (arg: (s32[], bf16[4,4,2,4])) -> (s32[], bf16[4,4,2,4]) {{
  %fusion.1 = bf16[4,4,2,4]{{3,2,1,0}} fusion(%state), kind=kLoop, calls=%fused.1, {named(STATE + "/add")}
  %fusion.2 = bf16[4,24]{{1,0}} fusion(%x), kind=kOutput, calls=%fused.2, {named("evotorch_tpu.fwd_kda/dot_general")}
  %fusion.3 = f32[4,2,8]{{2,1,0}} fusion(%c), kind=kOutput, calls=%fused.3, {named("evotorch_tpu.fwd_attention/evotorch_tpu.fwd_latent_cache/dot_general")}
  %fusion.4 = bf16[4,8]{{1,0}} fusion(%y), kind=kOutput, calls=%fused.4, {named("evotorch_tpu.fwd_experts/dot_general")}
  %fusion.5 = bf16[4,10]{{1,0}} fusion(%h), kind=kLoop, calls=%fused.5, {named("evotorch_tpu.fwd_head/dot_general")}
  %fusion.6 = f32[4]{{0}} fusion(%scores), kind=kLoop, calls=%fused.6, metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.contract/add"}}
}}
"""
OPS = {  # HLO text as a trace names an op: [self seconds, executions]
    "%fusion.1 = bf16[4,4,2,4]{3,2,1,0} fusion(%state)": [0.40, 16],
    "%fusion.2 = bf16[4,24]{1,0} fusion(%x)": [0.20, 16],
    "%fusion.3 = f32[4,2,8]{2,1,0} fusion(%c)": [0.04, 16],
    "%fusion.4 = bf16[4,8]{1,0} fusion(%y)": [0.24, 16],
    "%fusion.5 = bf16[4,10]{1,0} fusion(%h)": [0.08, 16],
    "%fusion.6 = f32[4]{0} fusion(%scores)": [0.04, 16],
}


def traced_run(ops, counters):
    """A run as the readers see it: 8 decode steps x 2 traced generations of
    4 lanes on a v5e, the program's counters as given."""
    trace = types.SimpleNamespace(
        planes=[object()], evaluation_ops=lambda: ops, generations=lambda: [0, 1], evaluation_seconds=lambda: 1.00
    )
    lowered = types.SimpleNamespace(compile=lambda: types.SimpleNamespace(as_text=lambda: HLO_TEXT))
    session = types.SimpleNamespace(
        problem=types.SimpleNamespace(lower_evaluation=lambda popsize: lowered),
        decode_steps=8,
        kda_sizes=TINY,
        compute_dtype="bfloat16",
        policy_counters=lambda: counters,
    )
    memo = {}
    return types.SimpleNamespace(
        trace=trace, session=session, popsize=4, device_record={"kind": "TPU v5 lite"},
        memo=lambda key, compute: memo.setdefault(key, compute()), lowered=lowered, memo_store=memo,
    )


def test_inner_scope_reader_on_a_hand_written_text(files):
    run = traced_run(OPS, {"kda_state_updates": 64, "kda_state_bytes": 4 * 2 * (32 + 72) * 2})
    split = kda_scopes.forward_seconds(run)
    assert split["steps"] == split["steps_ran"] == 16  # 8 decode steps x 2 traced generations, from the session
    # an op under fwd_kda AND fwd_kda_state counts under the innermost
    assert split["seconds"] == pytest.approx(
        {"fwd_kda_state": 0.40, "fwd_kda": 0.20, "fwd_latent_cache": 0.04, "fwd_experts": 0.24, "fwd_head": 0.08}
    )
    assert split["policy_forward_s"] == pytest.approx(0.96) and split["evaluation_s"] == pytest.approx(1.00)
    assert split["coverage_percent"] == pytest.approx(100.0)
    assert kda_scopes.per_step_ms(run, "fwd_kda_state") == pytest.approx(25.0)
    by_name = {m["name"]: files.layer_metric(m["name"]).measure(run) for m in cell_metrics(files, "kda.")}
    assert all(value is not None for value in by_name.values())  # every metric of the cell finds something to read
    assert by_name["kda.state_ms"] == pytest.approx(25.0) and by_name["kda.mixer_ms"] == pytest.approx(12.5)
    # 4 lanes x 2 KDA layers of 32 bfloat16 numbers, read and written, at 819 GB/s, over 25 ms
    assert by_name["kda.state_roofline_share"] == pytest.approx(100 * (2 * 4 * 2 * 64 / 819e9) / 25e-3)
    assert by_name["kda.step_mfu"] == pytest.approx(100 * 2 * kda_floors.step_macs_per_lane(TINY, 8) * 4 / 197e12 / (1.00 / 16))
    assert by_name["kda.state_gb"] == pytest.approx(4 * 2 * 104 * 2 / 1e9)
    # a program that rewrote other states than the configuration says gets no floor; the count is never the floor
    for silent in (None, {"kda_state_bytes": 1}, {"kda_state_updates": 63}, {"kda_state_updates": 128}):
        run.session.policy_counters = lambda silent=silent: silent
        assert files.layer_metric("kda.state_roofline_share").measure(run) is None
    # a program without the state's scope (another decoder's, the parent's): nothing read, nothing raised
    other = HLO_TEXT.replace("/evotorch_tpu.fwd_kda_state", "")
    run.lowered.compile = lambda: types.SimpleNamespace(as_text=lambda: other)
    run.memo_store.clear()
    assert kda_scopes.forward_seconds(run) is None and kda_scopes.per_step_ms(run, "fwd_kda") is None
    traced = [m["name"] for m in cell_metrics(files, "kda.") if m["source"] == "device_trace"]
    assert traced and all(files.layer_metric(name).measure(run) is None for name in traced)
    # no device trace (a CPU rehearsal): nothing is read, nothing is lowered
    run.trace = types.SimpleNamespace(planes=[])
    run.memo_store.clear()
    assert kda_scopes.forward_seconds(run) is None


def test_a_trace_that_lost_steps_reads_the_same_per_step(files, capsys):
    """The profiler kept the ops of 7 of the 16 control steps that ran: the
    rest of the program's 1.00 s shows as the loop op's own time. The times
    per step average the 7 steps the trace holds and read as a whole trace's;
    the step's share of the peak takes all the time over all 16; the coverage
    says 7/16."""
    kept = 7 / 16
    ops = {text: [seconds * kept, 7] for text, (seconds, _) in OPS.items()}
    ops["%while.9 = (s32[], bf16[4,4,2,4]{3,2,1,0}) while(%tuple.1), condition=%cond, body=%body"] = [1.00 * (1 - kept), 2]
    whole = traced_run(OPS, {"kda_state_updates": 64})
    run = traced_run(ops, {"kda_state_updates": 64})
    split = kda_scopes.forward_seconds(run)
    assert split["steps"] == 7 and split["steps_ran"] == 16
    assert "holds the ops of 7 of the 16 control steps" in capsys.readouterr().err
    assert split["coverage_percent"] == pytest.approx(100 * kept)
    for entry in cell_metrics(files, "kda."):
        if entry["source"] == "device_trace":
            module = files.layer_metric(entry["name"])
            assert module.measure(run) == pytest.approx(module.measure(whole)), entry["name"]


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
    # 4 lanes x two KDA layers (0 and 1) x (32 x 128 x 128 + 3 x 3 x 4096) bfloat16 numbers
    assert line["metrics"]["kda.state_gb"]["value"] == pytest.approx(4 * 2 * (32 * 128 * 128 + 9 * 4096) * 2 / 1e9)
    assert detail["counts"]["interactions"] == 2 * 4 * 8 and detail["counts"]["compiles_in_window"] == 0
    checks = detail["checks"]
    assert all(check["ok"] for check in checks.values()) and checks["record"]["emitted_tokens"] > 0
    assert checks["record"]["kda_state_updates"] == 2 * 4 * 8  # two KDA layers, every lane, every step
    assert checks["record"]["kda_lane_resets"] >= 4  # every lane's episode ended at the cap at least
    # what the TIMED program's KDA states held at the cap against the reference's recurrence: 2 layers of 32 x 128
    assert checks["state"]["numbers_per_lane"] == 2 * 32 * 128 and 0 < checks["state"]["ended_state_relative_rms_error"] < 0.1


def test_lower_precisions_and_a_changed_equation_in_the_programs_place():
    """The cell's comparison, at the rehearsal's scale on the CPU, with the
    reference standing in for the program: its weights rounded to bfloat16
    or int8, its state rounded to bfloat16 after every step, or the delta
    rule's correction left out. Three layers over eight positions gather
    less error than the cell's five over 256, so what holds at every scale is
    the order of the readings (the verdicts on the chip: PERF.md)."""
    done = subprocess.run(
        [sys.executable, os.path.join("scripts", "lm_ring_wrap_check.py"), "--cpu", "--tiny", "--control",
         "int8,bfloat16,no_correction,bf16_state", "--cell", CELL],
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
    )
    assert done.returncode in (0, 1), done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["ok"]["system"] is True and line["ok"]["bfloat16"] is True and line["ok"]["bf16_state"] is True
    assert line["system"]["record"]["emitted_tokens"] > 0
    error = {name: line[name]["logits"]["relative_rms_error"] for name in ("system", "bfloat16", "int8", "no_correction", "bf16_state")}
    assert error["bf16_state"] < error["bfloat16"] < error["system"] < error["int8"] < error["no_correction"]
