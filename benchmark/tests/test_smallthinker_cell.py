"""The route-ahead cell (``smallthinker_ep4.decode128``): its files against
``BENCHMARK.json`` and the published row, its floors against the counts of
the configuration and on a tiny one counted by hand, the reader of the
decoder's inner scopes (harness/lm_scopes.py) on a small hand-written
compiled text joined to hand-made events (a whole trace and one that lost
steps), and the cell traced end to end on the CPU (``--rehearse``).

Entries are found by name, never by their place in ``BENCHMARK.json``, and
no other family's metrics are counted: a later cell or metric does not break
this file.
"""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark.harness import ahead_floors, lm_floors, lm_scopes
from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "smallthinker_ep4.decode128"
LAYERS = ("ahead forward", "ahead experts")
#: the attention cache's readers, which read this cell's ring as they read Trinity's
CACHE = ("lm.cache_ms", "lm.cache_roofline_share", "lm.cache_gb")
LAYOUT = [0, 1, 1, 1] * 13
# SmallThinker-21BA3B-Instruct's config.json as the catalog has it (model-configs guide,
# architectures.jsonl): what the cell may not change
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384, "model_name": "smallthinker_21b_instruct",
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000, "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def files():
    return BenchmarkFiles(ROOT)


def own_metrics(files):
    """The per-layer entries of the cell's own layers, as ``BENCHMARK.json`` has them."""
    return [m for m in files.spec["per_layer"] if m["layer"] in LAYERS]


def read_metrics(files):
    """The entries of the cell's own layers and of the cache's readers."""
    return own_metrics(files) + [m for m in files.spec["per_layer"] if m["name"] in CACHE]


def test_the_cells_files_agree_with_the_benchmark(files):
    workload = files.workload(CELL)  # raises where the file and the entry disagree
    (listed,) = [w for w in files.spec["workloads"] if w["name"] == CELL]
    assert listed == {"name": CELL, "config": "smallthinker_ep4", "traffic": "decode128", "chips": 1, "why": workload["why"]}
    assert workload["driver"] == "oo_smallthinker_searcher" and hasattr(files.driver(workload["driver"]), "build")
    assert workload["traffic"] == {"name": "decode128", "eval_mode": "budget", "num_actors": None, "search_seed": 1}
    assert (workload["warmup_generations"], workload["traced_generations"]) == (3, 2)
    assert set(LAYERS) | {"lm cache"} <= set(workload["layers"]) and "mesh" not in workload["layers"]
    for phrase in ("48 pairs a held expert", "28 heads", "no wrap"):
        assert phrase in workload["why"]


def test_the_readers_apply_exactly_where_they_are_listed(files):
    workload = files.workload(CELL)
    own = own_metrics(files)
    assert own and all(m["name"].startswith("ahead.") and m["workloads"] == [CELL] for m in own)
    assert all(CELL in m["workloads"] for m in read_metrics(files) if m["name"] in CACHE)
    for entry in files.spec["per_layer"]:
        module = files.layer_metric(entry["name"])
        applies = module.applies(workload)
        if "workloads" in entry:
            assert applies == (CELL in entry["workloads"]), entry["name"]
        else:
            assert applies == (entry["layer"] in workload["layers"]), entry["name"]
        if entry in own:
            assert entry["moves"] == module.MOVES == "env_steps_per_s"
    # the Humanoid-only floors do not apply where a cell names a forward layer of its own
    for name in ("policy.roofline_share", "env.fused_lanes_share"):
        assert not files.layer_metric(name).applies(workload)


def test_the_configuration_is_the_published_model_cut_by_share(files):
    config = files.config("smallthinker_ep4")
    reduced = {"num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert set(config["reduced"]) == reduced | {"generations"}
    (listed,) = [c for c in files.spec["configs"] if c["name"] == "smallthinker_ep4"]
    assert listed["reduced"] == config["reduced"] and listed["source"] == config["source"]
    # every key of the published row is there, unchanged unless it is under `reduced`
    assert {key: config[key] for key in PUBLISHED if key not in reduced} == {
        key: value for key, value in PUBLISHED.items() if key not in reduced
    }
    assert config["published"] == {key: PUBLISHED[key] for key in reduced}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"], config["vocab_size"]) == (4, 16, 37984)
    assert config["layers_held"] == [0, 1, 2, 3] and config["experts_held"] == [0, 16]
    assert config["vocab_held"] == 37984 == PUBLISHED["vocab_size"] // 4
    assert "4 chips" in config["deployment"] and config["assumed"] and config["left_out"]
    assert (config["popsize"], config["prompt_length"], config["decode_steps"]) == (512, 32, 128)
    reference = files.module_at(config["reference"]["forward"])
    sizes = reference.sizes(config)
    assert (sizes["hidden"], sizes["heads"], sizes["kv_heads"], sizes["head_dim"]) == (2560, 28, 4, 128)
    assert (sizes["expert_width"], sizes["num_experts"], sizes["top_k"], sizes["window"]) == (768, 64, 6, 4096)
    assert sizes["layers"] == [0, 1, 2, 3] and [sizes["rope_layout"][i] for i in sizes["layers"]] == [0, 1, 1, 1]
    assert reference.parameter_count(sizes) == config["parameter_count"] == 656_529_920
    # the rehearsal keeps every width: fewer lanes, steps, layers and rows
    small = reference.sizes(config, config["rehearse"])
    widths = ("hidden", "heads", "kv_heads", "head_dim", "expert_width", "num_experts", "top_k", "window")
    assert {k: small[k] for k in widths} == {k: sizes[k] for k in widths}
    assert small["layers"] == [0, 1] and small["vocab"] == 512  # the NoPE full layer and a RoPE window layer


TINY = {
    "hidden": 8, "heads": 4, "kv_heads": 2, "head_dim": 2, "expert_width": 4, "num_experts": 8, "top_k": 2,
    "rope_layout": [0, 1, 1, 1], "sliding_layout": [0, 1, 1, 1], "window": 3, "layers": [0, 1],
    "experts_held": (0, 4), "vocab": 10,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3,
}


def test_floors_against_the_configurations_counts_and_by_hand(files):
    config = files.config("smallthinker_ep4")
    sizes = files.module_at(config["reference"]["forward"]).sizes(config)
    # 4 layers x 16 experts x 3 x 2,560 x 768 bf16 numbers: 755 MB, and the rank-4 factors 5.1 MB
    weights = 4 * 16 * 3 * 2560 * 768 * 2
    assert weights == 754_974_720
    assert ahead_floors.expert_bytes_per_step(sizes, 2, 4) == weights + 4 * 16 * 3 * (2560 + 768) * 4 * 2
    # 512 lanes x 6 of 64 experts, 16 held: 48 pairs a held expert, 768 a layer
    assert ahead_floors.expert_flops_per_step(sizes, 512) == 2 * 3 * 2560 * 768 * 4 * 768
    # the cache's reader finds the ring by its shape: 4 KV heads x 128 slots in every layer at 128 steps
    sizes = {**sizes, "layer_types": [["full_attention", "sliding_attention"][s] for s in sizes["sliding_layout"]]}
    assert lm_floors.cache_shape(sizes, 512, 128) == {"[512,4,128,128]"}
    # a lane's token: the head 97.2 M, a layer's projections 21.0 M, experts 8.8 M, the cache pass 0.46 M
    assert 215e6 < ahead_floors.step_macs_per_lane(sizes, 128) < 225e6
    # by hand: head 80; a layer: router 64, q and o 2 x 8 x 8, k and v 2 x 8 x 4, experts 3 x 8 x 4 x 2 x 4 / 8;
    # the cache pass 2 x 8 a filled position, 4 steps: (1 + 2 + 3 + 4) / 4 = 2.5 in the full layer,
    # (1 + 2 + 3 + 3) / 4 = 2.25 in layer 1's window of 3
    per_layer = 64 + 128 + 64 + 96
    assert ahead_floors.step_macs_per_lane(TINY, 4) == 80 + 2 * per_layer + 16 * (2.5 + 2.25)
    assert ahead_floors.expert_bytes_per_step(TINY, 2, 1) == 2 * 4 * (96 + 3 * 12) * 2
    # 12 pairs on held experts over 8 (layer, step)s of 2 layers: 3 a step
    assert ahead_floors.pairs_held_per_step({"expert_layer_steps": 8, "expert_pairs_held": 12}, TINY) == 3
    assert ahead_floors.pairs_held_per_step({"expert_layer_steps": 0}, TINY) is None


def named(path):
    return f'metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.policy_forward/{path}"}}'


HLO_TEXT = f"""\
HloModule jit_run_vectorized_rollout, is_scheduled=true

%body (arg: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {{
  %fusion.1 = f32[4,8]{{1,0}} fusion(%x), kind=kOutput, calls=%fused.1, {named("evotorch_tpu.fwd_router/dot_general")}
  %fusion.2 = bf16[4,16]{{1,0}} fusion(%u), kind=kOutput, calls=%fused.2, {named("evotorch_tpu.fwd_attention/dot_general")}
  %fusion.6 = bf16[4,2,3,2]{{3,2,1,0}} fusion(%q), kind=kLoop, calls=%fused.6, {named("evotorch_tpu.fwd_attention/dot_general")}
  %fusion.3 = bf16[4,8]{{1,0}} fusion(%y), kind=kOutput, calls=%fused.3, {named("evotorch_tpu.fwd_experts/held_experts_grouped")}
  %fusion.4 = bf16[4,10]{{1,0}} fusion(%h), kind=kLoop, calls=%fused.4, {named("evotorch_tpu.fwd_head/dot_general")}
  %fusion.5 = f32[4]{{0}} fusion(%scores), kind=kLoop, calls=%fused.5, metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.contract/add"}}
}}
"""
OPS = {  # HLO text as a trace names an op: [self seconds, executions]
    "%fusion.1 = f32[4,8]{1,0} fusion(%x)": [0.04, 8],
    "%fusion.2 = bf16[4,16]{1,0} fusion(%u)": [0.24, 8],
    "%fusion.6 = bf16[4,2,3,2]{3,2,1,0} fusion(%q)": [0.08, 8],
    "%fusion.3 = bf16[4,8]{1,0} fusion(%y)": [0.40, 8],
    "%fusion.4 = bf16[4,10]{1,0} fusion(%h)": [0.20, 8],
    "%fusion.5 = f32[4]{0} fusion(%scores)": [0.04, 8],
}


def traced_run(ops, counters):
    """A run as the readers see it: 4 decode steps x 2 traced generations of
    4 lanes on a v5e, the program's counters as given."""
    trace = types.SimpleNamespace(
        planes=[object()], evaluation_ops=lambda: ops, generations=lambda: [0, 1], evaluation_seconds=lambda: 1.00
    )
    lowered = types.SimpleNamespace(compile=lambda: types.SimpleNamespace(as_text=lambda: HLO_TEXT))
    session = types.SimpleNamespace(
        problem=types.SimpleNamespace(lower_evaluation=lambda popsize: lowered),
        decode_steps=4,
        lm_sizes=TINY,
        cache_bytes=4 * 2 * (4 + 3) * 2 * 2 * 2,
        trunk_delta_rank=1,
        compute_dtype="bfloat16",
        policy_counters=lambda: counters,
    )
    memo = {}
    return types.SimpleNamespace(
        trace=trace, session=session, popsize=4, device_record={"kind": "TPU v5 lite"},
        memo=lambda key, compute: memo.setdefault(key, compute()), lowered=lowered, memo_store=memo,
    )


# 4 lanes x top 2 in 2 layers x 4 steps routed ahead; 12 of those pairs on held experts
COUNTERS = {"expert_pairs_routed_ahead": 64, "expert_layer_steps": 8, "expert_pairs_held": 12}


def test_inner_scope_reader_on_a_hand_written_text(files):
    run = traced_run(OPS, COUNTERS)
    split = lm_scopes.forward_seconds(run)
    assert split["steps"] == split["steps_ran"] == 8  # 4 decode steps x 2 traced generations, from the session
    assert split["seconds"] == pytest.approx({"fwd_router": 0.04, "fwd_attention": 0.32, "fwd_experts": 0.40, "fwd_head": 0.20})
    assert split["policy_forward_s"] == pytest.approx(0.96) and split["coverage_percent"] == pytest.approx(100.0)
    by_name = {m["name"]: files.layer_metric(m["name"]).measure(run) for m in read_metrics(files)}
    assert all(value is not None for value in by_name.values())  # every metric of the cell finds something to read
    assert by_name["ahead.router_ms"] == pytest.approx(5.0) and by_name["ahead.attention_ms"] == pytest.approx(40.0)
    assert by_name["ahead.experts_ms"] == pytest.approx(50.0)
    # the ring's pass, found by its shape inside the attention: 0.08 s over 8 steps; 4 lanes x 2 x 2 x 2 bf16
    # numbers a filled position, (2.5 + 2.25) positions a step, at 819 GB/s
    assert split["cache_by"] == "shape" and by_name["lm.cache_ms"] == pytest.approx(10.0)
    assert by_name["lm.cache_roofline_share"] == pytest.approx(100 * (4 * 16 * 4.75 / 819e9) / 10e-3)
    assert by_name["lm.cache_gb"] == pytest.approx(448 / 1e9)  # 4 lanes x k, v x (4 + 3) slots x 2 x 2 x 2 B
    assert by_name["ahead.routed_ahead_share"] == pytest.approx(100.0)
    # 2 layers x 4 held experts x (96 + 36) bf16 numbers at 819 GB/s over 50 ms (the bytes bound it)
    assert by_name["ahead.experts_roofline_share"] == pytest.approx(100 * (2 * 4 * 132 * 2 / 819e9) / 50e-3)
    assert by_name["ahead.step_mfu"] == pytest.approx(
        100 * 2 * ahead_floors.step_macs_per_lane(TINY, 4) * 4 / 197e12 / (1.00 / 8)
    )
    # half the pairs routed ahead reads 50; a report without the counter (another family's) reads nothing
    run.session.policy_counters = lambda: dict(COUNTERS, expert_pairs_routed_ahead=32)
    assert files.layer_metric("ahead.routed_ahead_share").measure(run) == pytest.approx(50.0)
    run.session.policy_counters = lambda: {"expert_layer_steps": 8}
    assert files.layer_metric("ahead.routed_ahead_share").measure(run) is None
    # a program without the forward's inner scopes: nothing read, nothing raised
    other = re.sub(r"evotorch_tpu\.fwd_\w+/", "", HLO_TEXT)
    run.lowered.compile = lambda: types.SimpleNamespace(as_text=lambda: other)
    run.memo_store.clear()
    assert lm_scopes.forward_seconds(run) is None
    traced = [m["name"] for m in read_metrics(files) if m["source"] == "device_trace"]
    assert traced and all(files.layer_metric(name).measure(run) is None for name in traced)
    # no device trace (a CPU rehearsal): nothing is read, nothing is lowered
    run.trace = types.SimpleNamespace(planes=[])
    run.memo_store.clear()
    assert lm_scopes.forward_seconds(run) is None


def test_a_trace_that_lost_steps_reads_the_same_per_step(files, capsys):
    """The profiler kept the ops of 3 of the 8 control steps that ran: the
    times per step average the 3 the trace holds and read as a whole
    trace's; no share can pass what the whole trace reads."""
    kept = 3 / 8
    ops = {text: [seconds * kept, 3] for text, (seconds, _) in OPS.items()}
    ops["%while.9 = (s32[], bf16[4,8]{1,0}) while(%tuple.1), condition=%cond, body=%body"] = [1.00 * (1 - kept), 2]
    whole, run = traced_run(OPS, COUNTERS), traced_run(ops, COUNTERS)
    split = lm_scopes.forward_seconds(run)
    assert split["steps"] == 3 and split["steps_ran"] == 8
    assert "holds the ops of 3 of the 8 control steps" in capsys.readouterr().err
    for entry in read_metrics(files):
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
    assert line["metrics"]["ahead.routed_ahead_share"]["value"] == 100.0
    assert line["metrics"]["searcher.steady_compiles"]["value"] == 0
    assert detail["counts"]["interactions"] == 2 * 4 * 8 and detail["counts"]["compiles_in_window"] == 0
    checks = detail["checks"]
    assert all(check["ok"] for check in checks.values()) and checks["record"]["emitted_tokens"] > 0
    # top 6 in each of the rehearsal's two layers, 4 lanes, 8 steps
    assert checks["record"]["expert_pairs_routed_ahead"] == 6 * 2 * 4 * 8
