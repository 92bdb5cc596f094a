"""The language-model cell (``trinity_mini_ep8.decode256``): its files, its
floors, and the reader of the decoder's inner scopes (harness/lm_scopes.py)
on a small hand-written compiled text joined to hand-made events. The cell
itself runs end to end on the CPU in test_rehearse.py, with every other cell.
The expected figures were worked out by hand.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import lm_floors, lm_scopes
from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "trinity_mini_ep8.decode256"


@pytest.fixture(scope="module")
def files():
    return BenchmarkFiles(ROOT)


def test_the_cell_lists_no_layer_that_reads_weight_shapes(files):
    """No reader of the policy forward or the env substep looks for a
    weight's shape: they read the scopes ``policy_forward``, ``env_step`` and
    ``env_reset``, which this program carries too, so the cell lists both
    layers. ``policy.roofline_share`` floors the forward at lanes x parameters
    x bytes (722 GB a step here: a share far over 100%), and the physics
    kernel's ``env.fused_lanes_share`` has no kernel to find: neither applies
    in a decoder's cell, nor lists one. The names are those ``BENCHMARK.json``
    lists for the cell."""
    workload = files.workload(CELL)
    assert workload["driver"] == "oo_lm_searcher" and workload["chips"] == 1
    assert {"policy forward", "env substep"} <= set(workload["layers"])
    read = {m["name"] for m in files.metrics("per_layer", CELL) if files.layer_metric(m["name"]).applies(workload)}
    listing = {m["name"] for m in files.spec["per_layer"] if CELL in m.get("workloads", [])}
    assert listing <= read and {m for m in read if m.startswith("lm.")} == {m for m in listing if m.startswith("lm.")}
    assert {"lm.attention_ms", "lm.cache_ms", "lm.cache_roofline_share"} <= listing
    assert {"policy.forward_scope_ms", "env.substep_scope_ms", "env.reset_scope_ms"} <= listing
    entries = {m["name"]: m for m in files.spec["per_layer"]}
    for cell in (CELL, "glm47_flash_ep8.decode512", "granite4_h_micro_pp4.decode256"):
        for name in ("policy.roofline_share", "env.fused_lanes_share"):
            assert not files.layer_metric(name).applies(files.workload(cell)), (name, cell)
            assert cell not in entries[name]["workloads"], (name, cell)
    assert {
        "contract.occupancy", "contract.obs_norm_scope_ms", "contract.bookkeeping_scope_ms",
        "contract.edges_scope_ms", "eval.unscoped_share",
    } <= read
    # in the Humanoid cells the policy forward's and the env substep's readers all apply, no lm.* one
    for cell in ("humanoid_mlp64.budget", "humanoid_mlp64.episodes", "humanoid_mlp256.budget", "humanoid_mlp64.budget.pop4"):
        there = files.workload(cell)
        names = {m["name"] for m in files.metrics("per_layer", cell) if files.layer_metric(m["name"]).applies(there)}
        assert {
            "policy.roofline_share", "policy.forward_scope_ms", "env.substep_scope_ms", "env.reset_scope_ms",
            "env.fused_lanes_share", "contract.occupancy",
        } <= names
        assert not any(name.startswith("lm.") for name in names)


def test_a_lower_precision_in_the_programs_place_comes_out_not_correct():
    """The cell's comparison, at the rehearsal's scale on the CPU, with the
    reference's weights rounded to int8 standing in for the program: the same
    ``reference_checks``, the same limits, not ok (scripts/
    lm_ring_wrap_check.py --control runs it at the cell's size on the chip)."""
    done = subprocess.run(
        [sys.executable, os.path.join("scripts", "lm_ring_wrap_check.py"), "--cpu", "--tiny", "--control", "int8"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["ok"] == {"system": True, "int8": False}
    assert line["int8"]["logits"]["ok"] is False and line["system"]["record"]["emitted_tokens"] > 0


def test_a_record_says_which_tokens_a_lane_emitted(files):
    """By hand: prompt of 2, cap 6. Lane 0 runs one whole episode; lane 1
    emits id 0 at its fourth step and begins again; lane 2 ends at the cap."""
    import numpy as np

    driver = files.driver("oo_lm_searcher")
    positions = np.array([[0, 1, 2, 3, 4, 5], [0, 1, 2, 3, 0, 1], [2, 3, 4, 5, 0, 1]])
    ids = np.array([[7, 8, 3, 4, 5, 6], [9, 9, 2, 5, 7, 7], [1, 2, 3, 4, 8, 8]])
    where, tokens = driver.emitted_tokens(ids, positions, prompt_length=2, max_episode_steps=6)
    # the action of step s is the id consumed at s + 1, once the prompt is through; the last step's is unknown
    assert where.tolist() == [
        [False, True, True, True, True, False],
        [False, True, True, True, False, False],  # step 3 emitted the id 0 that ended the episode
        [True, True, True, False, False, False],  # the episode that ended at the cap says nothing of step 3
    ]
    assert tokens.tolist() == [3, 4, 5, 6, 2, 5, 0, 2, 3, 4]
    lanes = driver.lanes_to_check(np.array([[0, 1, 2, 3]] * 7 + [[0, 1, 0, 1]]), 4, seed=2146000011)
    assert len(set(lanes.tolist())) == 4 and 7 in lanes  # the lane that began an episode midway is among them
    assert lanes.tolist() == driver.lanes_to_check(np.array([[0, 1, 2, 3]] * 7 + [[0, 1, 0, 1]]), 4, seed=2146000011).tolist()


def test_the_configuration_is_the_published_model_cut_by_share(files):
    config = files.config("trinity_mini_ep8")
    reference = files.module_at(config["reference"]["forward"])
    sizes = reference.sizes(config)
    # no width differs from the published row; what is held is under `reduced`
    assert (sizes["hidden"], sizes["heads"], sizes["kv_heads"], sizes["head_dim"]) == (2048, 32, 4, 128)
    assert (sizes["dense_width"], sizes["expert_width"], sizes["top_k"], sizes["window"]) == (6144, 1024, 8, 2048)
    assert sizes["num_experts"] == config["published"]["num_experts"] == 128  # the router's width
    assert config["num_experts"] == 16 and config["vocab_size"] == 25024 and config["num_hidden_layers"] == 5
    assert {"num_experts", "vocab_size", "num_hidden_layers"} <= set(config["reduced"])
    kinds = [config["layer_types"][i] for i in config["layers_held"]]
    assert kinds == ["sliding_attention"] * 4 + ["full_attention"]  # layer 0, then one whole period
    assert reference.parameter_count(sizes) == config["parameter_count"]
    # the rehearsal keeps every width: fewer lanes, steps, sparse layers and rows
    small = reference.sizes(config, config["rehearse"])
    assert {k: small[k] for k in ("hidden", "expert_width", "num_experts")} == {
        k: sizes[k] for k in ("hidden", "expert_width", "num_experts")
    }
    assert small["layers"] == [0, 4] and small["vocab"] == 512


def test_floors(files):
    config = files.config("trinity_mini_ep8")
    sizes = files.module_at(config["reference"]["forward"]).sizes(config)
    # attention 27.26M x 5, dense MLP 37.75M, (router 0.26M + (1 shared + 1 held pair) x 6.29M) x 4, head 51.25M
    assert lm_floors.step_macs_per_lane(sizes) == 5 * 27_262_976 + 37_748_736 + 4 * (262_144 + 2 * 6_291_456) + 51_249_152
    assert lm_floors.expert_flops_per_step(sizes, 512) == 2 * 6_291_456 * (4 * 512 + 4 * 512)
    assert lm_floors.expert_flops_per_step(sizes, 512, pairs_per_step=1000) == 2 * 6_291_456 * (1000 + 2048)
    # 2 KB a position a layer a lane in bf16; 128.5 positions filled on average over 256 steps
    assert lm_floors.cache_bytes_per_step(sizes, 512, 256, 2) == 512 * 128.5 * 2048 * 5


SHAPE = "[4,2,8,16]"  # lanes, kv heads, slots, head_dim


def named(path):
    return f'metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.policy_forward/{path}"}}'


HLO_TEXT = f"""\
HloModule jit_run_vectorized_rollout, is_scheduled=true

%fused.1 (p: bf16{SHAPE}) -> f32[4,2,4,8] {{
  %p = bf16{SHAPE}{{3,2,1,0}} parameter(0)
  ROOT %dot.1 = f32[4,2,4,8]{{3,2,1,0}} dot(%q, %p), {named("evotorch_tpu.fwd_attention/nkgd,nksd->nkgs/dot_general")}
}}

%body (arg: (s32[], bf16{SHAPE})) -> (s32[], bf16{SHAPE}) {{
  %fusion.1 = f32[4,2,4,8]{{3,2,1,0}} fusion(%cache), kind=kOutput, calls=%fused.1, {named("evotorch_tpu.fwd_attention/nkgd,nksd->nkgs/dot_general")}
  %fusion.2 = bf16[4,64]{{1,0}} fusion(%x), kind=kLoop, calls=%fused.2, {named("evotorch_tpu.fwd_attention/dot_general")}
  %fusion.3 = f32[4,8]{{1,0}} fusion(%y), kind=kLoop, calls=%fused.3, {named("evotorch_tpu.fwd_router/dot_general")}
  %ragged-dot.4 = bf16[8,32]{{1,0}} ragged-dot(%rows, %w, %sizes), {named("evotorch_tpu.fwd_experts/ragged_dot")}
  %fusion.5 = bf16[4,48]{{1,0}} fusion(%h), kind=kLoop, calls=%fused.5, {named("evotorch_tpu.fwd_head/dot_general")}
  %fusion.6 = f32[4]{{0}} fusion(%scores), kind=kLoop, calls=%fused.6, metadata={{op_name="jit(run_vectorized_rollout)/while/body/evotorch_tpu.contract/add"}}
}}
"""


def test_inner_scope_reader_on_a_hand_written_text(monkeypatch):
    assert lm_scopes.cache_instructions(HLO_TEXT, {SHAPE}) == {"p", "fusion.1"}
    ops = {  # HLO text as a trace names an op: [self seconds, executions]
        "%fusion.1 = f32[4,2,4,8]{3,2,1,0} fusion(%cache)": [0.30, 16],
        "%fusion.2 = bf16[4,64]{1,0} fusion(%x)": [0.10, 16],
        "%fusion.3 = f32[4,8]{1,0} fusion(%y)": [0.02, 16],
        "%ragged-dot.4 = bf16[8,32]{1,0} ragged-dot(%rows, %w, %sizes)": [0.40, 16],
        "%fusion.5 = bf16[4,48]{1,0} fusion(%h)": [0.08, 16],
        "%fusion.6 = f32[4]{0} fusion(%scores)": [0.10, 16],
    }
    trace = types.SimpleNamespace(
        planes=[object()], evaluation_ops=lambda: ops, generations=lambda: [0, 1], evaluation_seconds=lambda: 1.25
    )
    lowered = types.SimpleNamespace(compile=lambda: types.SimpleNamespace(as_text=lambda: HLO_TEXT))
    session = types.SimpleNamespace(
        problem=types.SimpleNamespace(lower_evaluation=lambda popsize: lowered),
        decode_steps=8,
        lm_sizes={
            "layers": [0], "layer_types": ["full_attention"], "kv_heads": 2, "head_dim": 16, "window": 8,
        },
    )
    memo = {}
    run = types.SimpleNamespace(
        trace=trace, session=session, popsize=4,
        memo=lambda key, compute: memo.setdefault(key, compute()),
    )
    split = lm_scopes.forward_seconds(run)
    assert split["steps"] == 16  # 8 decode steps x 2 traced generations, from the session
    assert split["seconds"] == pytest.approx(
        {"fwd_attention": 0.40, "fwd_router": 0.02, "fwd_experts": 0.40, "fwd_head": 0.08}
    )
    # a library without the cache's scope: the one op that holds the cache's shape
    assert split["cache_ops_s"] == pytest.approx(0.30) and split["cache_by"] == "shape"
    assert split["policy_forward_s"] == pytest.approx(0.90) and split["evaluation_s"] == pytest.approx(1.00)
    assert split["inner_share_of_policy_forward"] == pytest.approx(1.0)
    assert split["coverage_percent"] == pytest.approx(80.0)  # the trace kept ops for 1.00 of the program's 1.25 s
    assert lm_scopes.per_step_ms(run, "fwd_experts") == pytest.approx(25.0)
    assert lm_scopes.cache_ms(run) == pytest.approx(0.30 / 16 * 1e3)
    # a library that declares the cache's scope and wears it inside fwd_attention:
    # the pass is read by the name, no shape looked for
    import evotorch_tpu.observability.scopes as library

    monkeypatch.setattr(library, "FORWARD_SCOPES", library.FORWARD_SCOPES + (lm_scopes.CACHE_SCOPE,))
    scoped = HLO_TEXT.replace("evotorch_tpu.fwd_attention/nkgd", "evotorch_tpu.fwd_attention/evotorch_tpu.fwd_kv_cache/nkgd")
    lowered.compile = lambda: types.SimpleNamespace(as_text=lambda: scoped)
    session.lm_sizes = None  # no shape to look for
    memo.clear()
    split = lm_scopes.forward_seconds(run)
    assert split["cache_by"] == "scope" and split["cache_ops_s"] == pytest.approx(0.30)
    assert split["seconds"] == pytest.approx(
        {"fwd_attention": 0.10, "fwd_kv_cache": 0.30, "fwd_router": 0.02, "fwd_experts": 0.40, "fwd_head": 0.08}
    )
    # lm.attention_ms keeps its meaning: the attention scope with the cache's pass inside it
    assert lm_scopes.per_step_ms(run, "fwd_attention", lm_scopes.CACHE_SCOPE) == pytest.approx(0.40 / 16 * 1e3)
    # no device trace (a CPU rehearsal): nothing is read, nothing is lowered
    run.trace = types.SimpleNamespace(planes=[])
    memo.clear()
    assert lm_scopes.forward_seconds(run) is None
