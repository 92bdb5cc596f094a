"""The latent-attention cell (``glm47_flash_ep8.decode512``): its files
against ``BENCHMARK.json`` and the published row, its floors on a tiny
configuration counted by hand, the reader of the decoder's inner scopes
(harness/mla_scopes.py) on a small hand-written compiled text joined to
hand-made events, the cell traced end to end on the CPU (``--rehearse``), and
its comparison with int8 in the program's place.

The names of the cell's metrics are read from ``BENCHMARK.json``, never
written out here: the next ``mla.*`` metric does not break this file.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import mla_floors, mla_scopes
from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "glm47_flash_ep8.decode512"
#: the per-layer entries of other layers that list this cell too: by scope, and a generation's phases
SHARED = {"policy.forward_scope_ms", "env.substep_scope_ms", "env.reset_scope_ms"}
# GLM-4.7-Flash's config.json as the catalog has it (model-configs guide,
# architectures.jsonl): what the cell may not change
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}


@pytest.fixture(scope="module")
def files():
    return BenchmarkFiles(ROOT)


def cell_metrics(files):
    """The per-layer entries that list this cell, as ``BENCHMARK.json`` has them."""
    return [m for m in files.spec["per_layer"] if CELL in m.get("workloads", [])]


def test_the_cells_files_agree_with_the_benchmark(files):
    workload = files.workload(CELL)
    assert workload["driver"] == "oo_mla_searcher" and workload["chips"] == 1
    assert workload["traffic"] == {"name": "decode512", "eval_mode": "budget", "num_actors": None, "search_seed": 1}
    assert (workload["warmup_generations"], workload["traced_generations"]) == (3, 2)
    assert set(workload["layers"]) == {
        "OO searcher", "eval contract", "policy forward", "env substep", "compile cache", "device", "mla forward",
        "mla experts", "mla cache",
    }
    applies = {
        m["name"] for m in files.metrics("per_layer", CELL) if files.layer_metric(m["name"]).applies(workload)
    }
    own = {m["name"] for m in cell_metrics(files) if m["name"].startswith("mla.")}
    assert own and {m for m in applies if m.startswith("mla.")} == own
    # the readers without a list of cells read this one too; Trinity's and the MLPs' do not
    assert {
        "searcher.steady_compiles", "searcher.outside_eval_ms", "contract.occupancy", "cache.misses",
        "device.idle_share", "device.peak_hbm_gb", "contract.bookkeeping_scope_ms", "contract.edges_scope_ms",
        "eval.unscoped_share",
    } <= applies
    # of the policy forward's and the env substep's readers, those that go by scope
    assert {m for m in applies if m.startswith(("policy.", "env."))} == SHARED
    assert not any(name.startswith(("lm.", "ssm.")) for name in applies)
    for entry in files.spec["per_layer"]:
        if entry["name"].startswith("mla."):
            assert entry["workloads"] == [CELL] and entry["moves"] == "env_steps_per_s"
        elif CELL in entry.get("workloads", []):  # listed with the cells of its own layer
            assert entry["name"] in SHARED or entry["name"].startswith("searcher."), entry["name"]
    listed = [w for w in files.spec["workloads"] if w["name"] == CELL]
    assert listed == [{"name": CELL, "config": "glm47_flash_ep8", "traffic": "decode512", "chips": 1, "why": workload["why"]}]


def test_the_configuration_is_the_published_model_cut_by_share(files):
    config = files.config("glm47_flash_ep8")
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(config["reduced"]) == reduced | {"generations"}
    # every key of the published row is there, unchanged unless it is under `reduced`
    assert {key: config[key] for key in PUBLISHED if key not in reduced} == {
        key: value for key, value in PUBLISHED.items() if key not in reduced
    }
    assert config["published"] == {key: PUBLISHED[key] for key in reduced}
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (5, 8, 19360)
    assert config["layers_held"] == [0, 1, 2, 3, 4] and config["experts_held"] == [0, 8]
    assert config["vocab_held"] == 19360 == PUBLISHED["vocab_size"] // 8
    assert "8 chips" in config["deployment"] and config["assumed"] and config["left_out"]
    assert (config["popsize"], config["prompt_length"], config["decode_steps"]) == (512, 64, 512)
    reference = files.module_at(config["reference"]["forward"])
    sizes = reference.sizes(config)
    assert (sizes["hidden"], sizes["heads"], sizes["q_rank"], sizes["kv_rank"]) == (2048, 20, 768, 512)
    assert (sizes["nope"], sizes["rope"], sizes["v"]) == (192, 64, 256)
    assert (sizes["dense_width"], sizes["expert_width"], sizes["top_k"], sizes["route_scale"]) == (10240, 1536, 4, 1.8)
    assert sizes["num_experts"] == 64  # the router's width
    assert sizes["layers"] == [0, 1, 2, 3, 4] and sizes["num_dense_layers"] == 1
    # the issue's table: ends 79,300,608 + layer 0 84,677,888 + 4 x 106,829,120
    assert reference.parameter_count(sizes) == config["parameter_count"] == 79_300_608 + 84_677_888 + 4 * 106_829_120
    # the rehearsal keeps every width: fewer lanes, steps, sparse layers and rows
    small = reference.sizes(config, config["rehearse"])
    widths = ("hidden", "heads", "q_rank", "kv_rank", "nope", "rope", "v", "expert_width", "num_experts", "top_k")
    assert {k: small[k] for k in widths} == {k: sizes[k] for k in widths}
    assert small["layers"] == [0, 1] and small["vocab"] == 512


TINY = {
    "hidden": 8, "heads": 2, "q_rank": 4, "kv_rank": 6, "nope": 3, "rope": 2, "v": 5, "dense_width": 16,
    "expert_width": 4, "num_experts": 16, "top_k": 4, "shared": 1, "num_dense_layers": 1,
    "layers": [0, 1, 2], "experts_held": (0, 4), "vocab": 10,
}


def test_floors_on_a_configuration_counted_by_hand(files):
    # W_qa 8x4, W_qb 4x2x5, W_kva 8x8, the absorbed products 2x(3+5)x6, W_o 2x5x8
    assert mla_floors.attention_macs(TINY) == 32 + 40 + 64 + 96 + 80
    assert mla_floors.cache_row(TINY) == 8 and mla_floors.cache_macs_per_position(TINY) == 2 * (8 + 6)
    # 3 layers x 4 lanes x (1 + 2 + .. + 7) / 7 positions a step
    assert mla_floors.expected_positions_per_step(TINY, 4, 7) == 48
    assert mla_floors.cache_bytes_per_step(TINY, 48, 2) == 768
    assert mla_floors.cache_flops_per_step(TINY, 48) == 2688
    # head 80, cache 12 x 28, attention 3 x 312, dense MLP 384, 2 x (router 128 + (1 shared + 1 held pair) x 96)
    assert mla_floors.step_macs_per_lane(TINY, 12) == 80 + 336 + 936 + 384 + 2 * (128 + 192)
    config = files.config("glm47_flash_ep8")
    sizes = files.module_at(config["reference"]["forward"]).sizes(config)
    assert mla_floors.attention_macs(sizes) == 21_759_232 - 1_280  # the layer's matrices, its two inner norms aside
    assert mla_floors.cache_row(sizes) * 2 == 1152 and mla_floors.cache_macs_per_position(sizes) == 20 * (576 + 512)
    assert mla_floors.expert_bytes_per_step(sizes, 2) == 4 * 9 * 9_437_184 * 2  # 0.68 GB
    assert mla_floors.held_share(sizes) == 0.5  # 32 pairs a held expert at 512 lanes
    # the whole cache of 512 lanes x 512 slots x 5 layers, and half of it read on average
    assert 512 * 512 * 5 * 1152 == 1_509_949_440
    positions = mla_floors.expected_positions_per_step(sizes, 512, 512)
    assert mla_floors.cache_bytes_per_step(sizes, positions, 2) == 512 * 256.5 * 5 * 1152


def named(path):
    return f'metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.policy_forward/{path}"}}'


CACHE = "evotorch_tpu.fwd_attention/evotorch_tpu.fwd_latent_cache"
HLO_TEXT = f"""\
HloModule jit_run_vectorized_rollout, is_scheduled=true

%fused.1 (p: bf16[4,8,6]) -> f32[4,2,8] {{
  %p = bf16[4,8,6]{{2,1,0}} parameter(0)
  ROOT %dot.1 = f32[4,2,8]{{2,1,0}} dot(%q, %p), {named(CACHE + "/nhr,nsr->nhs/dot_general")}
}}

%body (arg: (s32[], bf16[4,8,6])) -> (s32[], bf16[4,8,6]) {{
  %fusion.1 = f32[4,2,8]{{2,1,0}} fusion(%cache), kind=kOutput, calls=%fused.1, {named(CACHE + "/nhr,nsr->nhs/dot_general")}
  %fusion.2 = bf16[4,2,6]{{2,1,0}} fusion(%x), kind=kLoop, calls=%fused.2, {named("evotorch_tpu.fwd_attention/nhr,hri->nhi/dot_general")}
  %fusion.3 = f32[4,16]{{1,0}} fusion(%y), kind=kLoop, calls=%fused.3, {named("evotorch_tpu.fwd_router/dot_general")}
  %custom-call.4 = f32[4,8]{{1,0}} custom-call(%y, %w), custom_call_target="tpu_custom_call", {named("evotorch_tpu.fwd_experts/held_experts_grouped")}
  %fusion.5 = bf16[4,10]{{1,0}} fusion(%h), kind=kLoop, calls=%fused.5, {named("evotorch_tpu.fwd_head/dot_general")}
  %fusion.6 = f32[4]{{0}} fusion(%scores), kind=kLoop, calls=%fused.6, metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.contract/add"}}
  %fusion.7 = bf16[4,8,6]{{2,1,0}} fusion(%cache, %c), kind=kLoop, calls=%fused.7, {named(CACHE + "/dynamic_update_slice")}
}}
"""


def test_inner_scope_reader_on_a_hand_written_text():
    ops = {  # HLO text as a trace names an op: [self seconds, executions]
        "%fusion.1 = f32[4,2,8]{2,1,0} fusion(%cache)": [0.30, 16],
        "%fusion.2 = bf16[4,2,6]{2,1,0} fusion(%x)": [0.10, 16],
        "%fusion.3 = f32[4,16]{1,0} fusion(%y)": [0.02, 16],
        "%custom-call.4 = f32[4,8]{1,0} custom-call(%y, %w)": [0.40, 16],
        "%fusion.5 = bf16[4,10]{1,0} fusion(%h)": [0.08, 16],
        "%fusion.6 = f32[4]{0} fusion(%scores)": [0.10, 16],
        "%fusion.7 = bf16[4,8,6]{2,1,0} fusion(%cache, %c)": [0.06, 16],
    }
    trace = types.SimpleNamespace(
        planes=[object()], evaluation_ops=lambda: ops, generations=lambda: [0, 1], evaluation_seconds=lambda: 1.06
    )
    lowered = types.SimpleNamespace(compile=lambda: types.SimpleNamespace(as_text=lambda: HLO_TEXT))
    session = types.SimpleNamespace(
        problem=types.SimpleNamespace(lower_evaluation=lambda popsize: lowered),
        decode_steps=8,
        mla_sizes=TINY,
        policy_counters=lambda: {"latent_positions_read": 400},
    )
    memo = {}
    run = types.SimpleNamespace(
        trace=trace, session=session, popsize=4,
        memo=lambda key, compute: memo.setdefault(key, compute()),
    )
    split = mla_scopes.forward_seconds(run)
    assert split["steps"] == 16  # 8 decode steps x 2 traced generations, from the session
    # an op under fwd_attention AND fwd_latent_cache counts under the innermost
    assert split["seconds"] == pytest.approx(
        {"fwd_latent_cache": 0.36, "fwd_attention": 0.10, "fwd_router": 0.02, "fwd_experts": 0.40, "fwd_head": 0.08}
    )
    assert split["policy_forward_s"] == pytest.approx(0.96) and split["evaluation_s"] == pytest.approx(1.06)
    assert split["inner_share_of_policy_forward"] == pytest.approx(1.0)
    assert split["coverage_percent"] == pytest.approx(100.0)
    assert mla_scopes.per_step_ms(run, "fwd_latent_cache") == pytest.approx(22.5)
    assert mla_scopes.positions_per_step(run) == 50  # counted by the program: 400 over 8 steps
    session.policy_counters = lambda: None
    assert mla_scopes.positions_per_step(run) == 3 * 4 * 4.5  # else an episode no lane ends early
    # a program without the latent cache's scope (another decoder's): nothing read, nothing raised
    other = HLO_TEXT.replace("/evotorch_tpu.fwd_latent_cache", "")
    lowered.compile = lambda: types.SimpleNamespace(as_text=lambda: other)
    memo.clear()
    assert mla_scopes.forward_seconds(run) is None and mla_scopes.per_step_ms(run, "fwd_attention") is None
    # no device trace (a CPU rehearsal): nothing is read, nothing is lowered
    run.trace = types.SimpleNamespace(planes=[])
    memo.clear()
    assert mla_scopes.forward_seconds(run) is None


def test_a_trace_that_lost_steps_reads_the_same_per_step(files, capsys):
    """The profiler kept the ops of 7 of the 16 control steps that ran (as
    in one traced run of the GLM cell on the chip, 472 of 1,024): the rest
    of the program's 1.06 s shows as the loop op's own time. The times per
    step average the 7 steps the trace holds, and read as a whole trace's
    (above); the step's share of the peak takes all the time over all 16;
    the coverage says 7/16."""
    kept = 7 / 16
    ops = {
        "%fusion.1 = f32[4,2,8]{2,1,0} fusion(%cache)": [0.30 * kept, 7],
        "%fusion.2 = bf16[4,2,6]{2,1,0} fusion(%x)": [0.10 * kept, 7],
        "%fusion.3 = f32[4,16]{1,0} fusion(%y)": [0.02 * kept, 7],
        "%custom-call.4 = f32[4,8]{1,0} custom-call(%y, %w)": [0.40 * kept, 7],
        "%fusion.5 = bf16[4,10]{1,0} fusion(%h)": [0.08 * kept, 7],
        "%fusion.6 = f32[4]{0} fusion(%scores)": [0.10 * kept, 7],
        "%fusion.7 = bf16[4,8,6]{2,1,0} fusion(%cache, %c)": [0.06 * kept, 7],
        "%while.9 = (s32[], bf16[4,8,6]{2,1,0}) while(%tuple.1), condition=%cond, body=%body": [1.06 * (1 - kept), 2],
    }
    trace = types.SimpleNamespace(
        planes=[object()], evaluation_ops=lambda: ops, generations=lambda: [0, 1], evaluation_seconds=lambda: 1.06
    )
    lowered = types.SimpleNamespace(compile=lambda: types.SimpleNamespace(as_text=lambda: HLO_TEXT))
    session = types.SimpleNamespace(
        problem=types.SimpleNamespace(lower_evaluation=lambda popsize: lowered),
        decode_steps=8,
        mla_sizes=TINY,
        compute_dtype="bfloat16",
        policy_counters=lambda: {"latent_positions_read": 400},
    )
    memo = {}
    run = types.SimpleNamespace(
        trace=trace, session=session, popsize=4, device_record={"kind": "TPU v5 lite"},
        memo=lambda key, compute: memo.setdefault(key, compute()),
    )
    split = mla_scopes.forward_seconds(run)
    assert split["steps"] == 7 and split["steps_ran"] == 16
    assert "holds the ops of 7 of the 16 control steps" in capsys.readouterr().err
    assert split["coverage_percent"] == pytest.approx(100 * kept)
    assert mla_scopes.per_step_ms(run, "fwd_latent_cache") == pytest.approx(22.5)  # as in the whole trace
    assert mla_scopes.per_step_ms(run, "fwd_experts") == pytest.approx(25.0)
    step_mfu = files.layer_metric("mla.step_mfu").measure(run)
    # the whole trace's reading: the same 1.06 s over the same 16 steps
    flops = 2.0 * mla_floors.step_macs_per_lane(TINY, 50 / 4) * 4
    assert step_mfu == pytest.approx(100 * flops / 197e12 / (1.06 / 16))


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
    # 4 lanes x 8 slots x 2 layers x (512 + 64) bfloat16 numbers
    assert line["metrics"]["mla.cache_gb"]["value"] == pytest.approx(4 * 8 * 2 * 576 * 2 / 1e9)
    assert line["metrics"]["mla.experts_tile_fill"]["value"] == 0.0  # the plain form runs on the CPU
    assert detail["counts"]["interactions"] == 2 * 4 * 8 and detail["counts"]["compiles_in_window"] == 0
    checks = detail["checks"]
    assert all(check["ok"] for check in checks.values()) and checks["record"]["emitted_tokens"] > 0
    # a lane at position t could read t + 1 rows, in both layers
    assert 0 < checks["record"]["latent_positions_read"] <= 2 * 4 * 36


def test_a_lower_precision_in_the_programs_place_comes_out_not_correct():
    """The cell's comparison, at the rehearsal's scale on the CPU, with the
    reference's weights rounded to int8 standing in for the program: the same
    ``reference_checks``, the same limits, not ok (on the chip: PERF.md)."""
    done = subprocess.run(
        [sys.executable, os.path.join("scripts", "lm_ring_wrap_check.py"), "--cpu", "--tiny", "--control", "int8",
         "--cell", CELL],
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["ok"] == {"system": True, "int8": False}
    assert line["system"]["record"]["emitted_tokens"] > 0
