"""The ``granitemoehybrid`` decoder (``net/decoder.py``: Mamba-2 mixers whose
per-lane state is rewritten whole every step, beside one attention layer's
cache) at a small size on the CPU, seeded random weights, against the
benchmark's plain reference (``benchmark/reference/granitemoehybrid_decoder.py``:
a causal convolution over the sequence, the recurrence in a scan, no lanes).

The published RATIOS kept: ``inner = 2 x hidden``, one group, a window of 4,
4 query heads on 2 key/value heads, the attention layer in the middle of the
held layers (the decoder keeps a lane's position itself), a tied vocabulary:
hidden 32, 4 state-space heads x 16 with a state of 8, MLP width 48.
"""

import importlib.util
import os
import re

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu import SolutionBatch
from evotorch_tpu.envs.tokens import TokenCopyEnv
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.neuroevolution.net.decoder import (
    GraniteMoeHybridDecoder,
    Mamba2Mixer,
    _Dense,
    _Trunk,
    stepwise_logits,
)
from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors
from evotorch_tpu.observability.scopes import FORWARD_SCOPES, instruction_scopes
from evotorch_tpu.tools.lowrank import TrunkDeltaParamsBatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("benchmark/reference/granitemoehybrid_decoder.py", "granitemoehybrid_reference")
_recurrence = ref.recurrence  # the unchanged recurrence, for a mutation that wraps it

MODEL = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=48,
    layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
    mamba_conv_bias=True, mamba_proj_bias=False, num_local_experts=0, attention_bias=False,
    attention_multiplier=0.125, embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    position_embedding_type="nope", tie_word_embeddings=True, rms_norm_eps=1e-5,
)
FULL_VOCAB = 64
STEPS = 14
VOCAB = 16  # a quarter of the vocabulary
LAYERS = (0, 1, 2, 3)


def decoder(*, steps=STEPS, vocab=VOCAB, layers=LAYERS):
    return GraniteMoeHybridDecoder(
        **MODEL, vocab_size=FULL_VOCAB, max_positions=steps, layers_held=list(layers), vocab_held=vocab
    )


def sizes(*, vocab=VOCAB, layers=LAYERS):
    return ref.sizes(dict(MODEL, layers_held=list(layers), kept_mamba_layers=len(layers), vocab_held=vocab))


def seeded(policy, seed=1):
    flat = policy.init_parameters(jax.random.key(seed))
    # norms, D and the transition away from their initial values, so that each matters
    # (0.15: at a hidden size of 32 the projections then feed the recurrence as the published widths do)
    return flat + 0.15 * jax.random.normal(jax.random.key(seed + 1), flat.shape)


def relative_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2)))


def trunk_batch(policy, flat, lanes=3, rank=3, seed=4):
    sigma = jnp.full((policy.parameter_count,), 0.05)
    factors = sample_trunk_delta_factors(jax.random.key(seed), policy, sigma, rank)
    z = jax.random.normal(jax.random.key(seed + 1), (lanes, rank))
    return TrunkDeltaParamsBatch(center=flat, coeffs=z, factors=factors)


def stepwise_dense(net, params, ids):
    @jax.jit
    def run(params, ids):
        def step(state, token):
            logits, state = net.apply(params, token[None], state)
            return state, logits

        return jax.lax.scan(step, net.initial_state(), ids)[1]

    return run(params, ids)


@pytest.fixture(scope="module")
def model():
    net = decoder()
    policy = FlatParamsPolicy(net)
    return net, policy, seeded(policy)


def test_parameter_layout_and_counts_are_the_references(model):
    _, policy, flat = model
    s = sizes()
    assert policy.parameter_count == ref.parameter_count(s)
    mine, theirs = policy.unravel(flat), ref.unflatten(flat, s)
    assert "head" not in mine and set(mine["layers"][0]) == {"ssm", "mlp"} and set(mine["layers"][2]) == {"attn", "mlp"}
    assert set(mine["layers"][2]["attn"]) == {"in_norm", "q", "k", "v", "o"}  # no gate, no per-head norms, no closing norm
    for at, block, leaf in ((0, "ssm", "conv"), (1, "ssm", "A_log"), (3, "ssm", "out_proj"), (2, "attn", "o"), (3, "mlp", "in_norm")):
        assert np.array_equal(mine["layers"][at][block][leaf], theirs["layers"][at][block][leaf])
    assert np.array_equal(mine["layers"][1]["mlp"]["mlp"]["up"], theirs["layers"][1]["mlp"]["mlp"]["up"])
    # at the published widths: the issue's counts for a Mamba layer, the attention layer, one period and a quarter of the rows
    published = dict(
        MODEL, hidden_size=2048, num_attention_heads=32, num_key_value_heads=8, shared_intermediate_size=8192,
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        layer_types=(["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    )
    count = lambda layers, vocab: ref.parameter_count(
        ref.sizes(dict(published, layers_held=layers, kept_mamba_layers=len(layers), vocab_held=vocab))
    )
    ends = 25_088 * 2048 + 2048
    assert count([0], 25_088) - ends == 76_182_976
    assert count([5], 25_088) - ends == 60_821_504
    assert count(list(range(10)), 25_088) == 797_850_560


def test_one_mixer_step_by_step_equals_the_references_scan_through_a_reset():
    """The mixer alone, one lane: stepped through its window and its matrix
    state, an episode ended after 5 entries and its state reset, against the
    reference's convolution and scan over the whole sequence with the
    episodes' positions."""
    mixer = Mamba2Mixer(32, 4, 16, 8, conv_width=4, residual_scale=0.22)
    params = mixer.init(jax.random.key(0))
    params = jax.tree_util.tree_map(lambda p: p + 0.05 * jax.random.normal(jax.random.key(9), p.shape), params)
    h = jax.random.normal(jax.random.key(1), (12, 32))
    positions = np.array([0, 1, 2, 3, 4, 0, 1, 2, 3, 4, 5, 6])
    state, got = mixer.initial_state(), []
    for t in range(12):
        if t and positions[t] == 0:
            state = jax.tree_util.tree_map(
                lambda x: x[0], mixer.reset_state(jax.tree_util.tree_map(lambda x: x[None], state), jnp.asarray([True]))
            )
            assert float(jnp.abs(state["ssm"]).max()) == 0.0 and float(jnp.abs(state["conv"]).max()) == 0.0
        y, state = mixer.apply(params, h[t], state)
        got.append(y)
    s = dict(sizes(), residual_scale=0.22)
    want = ref.mamba(params, h, s, jnp.asarray(positions))
    for t in range(12):  # position by position
        assert relative_rms(got[t], want[t]) < 1e-5, t
    assert int(state["updates"]) == 12 and int(state["resets"]) == 1
    # without the positions the reference carries the first episode's state on: the reset matters
    assert relative_rms(jnp.stack(got)[5:], ref.mamba(params, h, s)[5:]) > 1e-3


@pytest.mark.parametrize("steps", [1, STEPS])
def test_dense_apply_stepwise_equals_the_whole_sequence_reference(model, steps):
    net, policy, flat = model
    ids = jax.random.randint(jax.random.key(3), (steps,), 0, VOCAB)
    got = stepwise_dense(net, policy.unravel(flat), ids)
    want, _ = ref.forward(ref.unflatten(flat, sizes()), ids, sizes())
    assert got.shape == (steps, VOCAB) and relative_rms(got, want) < 1e-5


def test_trunk_delta_forward_equals_dense_apply_on_materialised_rows(model):
    """Equal effective weights, the convolution's leaf and the 1-D
    transition leaves included: every lane's ``A_log``, ``dt_bias``, ``D``
    and taps are its own."""
    net, policy, flat = model
    batch = trunk_batch(policy, flat)
    ids = jax.random.randint(jax.random.key(6), (batch.popsize, STEPS), 0, VOCAB)
    got, routes = jax.jit(lambda b, i: stepwise_logits(policy, b, i))(batch, ids)
    assert routes.size == 0  # no layer routes
    dense = batch.materialize()
    lanes = [policy.unravel(dense[lane]) for lane in range(batch.popsize)]
    for leaf in ("conv", "A_log", "dt_bias", "D"):  # perturbed per lane
        assert not np.allclose(lanes[0]["layers"][0]["ssm"][leaf], lanes[1]["layers"][0]["ssm"][leaf])
    for lane in range(batch.popsize):
        assert relative_rms(got[lane], stepwise_dense(net, lanes[lane], ids[lane])) < 1e-5
        theirs, _ = ref.forward(ref.unflatten(dense[lane], sizes()), ids[lane], sizes())
        assert relative_rms(got[lane], theirs) < 1e-5


def test_the_accessors_small_matrix_is_the_lanes_own():
    center = {"w": jnp.arange(12.0).reshape(4, 3)}
    a, b = jnp.ones((3, 2)), jnp.arange(8.0).reshape(4, 2)
    z = jnp.asarray([[1.0, 0.0], [0.5, -1.0]])
    factors = {"w": type("F", (), {"a": a, "b": b})()}
    got = _Trunk(center, factors, z).mat("w")
    assert got.shape == (2, 4, 3)
    for lane in range(2):
        assert np.allclose(got[lane], center["w"] + (b * z[lane]) @ a.T)
    assert _Dense(center).mat("w").shape == (1, 4, 3)


@pytest.mark.parametrize("mutation", ["no_state", "no_decay", "no_skip", "tap_order", "norm_before_gate", "no_residual_scale", "no_logits_divisor"])
def test_the_comparison_catches_a_changed_equation(model, mutation, monkeypatch):
    net, policy, flat = model
    s = dict(sizes())
    params = ref.unflatten(flat, s)
    if mutation == "no_state":  # the skip alone: what the matrix state adds to the logits
        monkeypatch.setattr(ref, "recurrence", lambda x, b, c, dt, rate, positions: jnp.zeros_like(x))
    elif mutation == "no_decay":
        monkeypatch.setattr(ref, "recurrence", lambda x, b, c, dt, rate, positions: _recurrence(x, b, c, dt, 0.0 * rate, positions))
    elif mutation == "no_skip":
        for layer in params["layers"].values():
            if "ssm" in layer:
                layer["ssm"]["D"] = jnp.zeros_like(layer["ssm"]["D"])
    elif mutation == "tap_order":
        for layer in params["layers"].values():
            if "ssm" in layer:
                layer["ssm"]["conv"] = layer["ssm"]["conv"][::-1]
    elif mutation == "norm_before_gate":
        monkeypatch.setattr(ref, "rms", _norm_then_gate(ref.rms, s))
    elif mutation == "no_residual_scale":
        s["residual_scale"] = 1.0
    else:
        s["logits_divisor"] = 1.0
    ids = jax.random.randint(jax.random.key(3), (STEPS,), 0, VOCAB)
    got = stepwise_dense(net, policy.unravel(flat), ids)
    want, _ = ref.forward(params, ids, s)
    assert relative_rms(got, want) > 5e-3



def _norm_then_gate(rms, s):
    inner = s["ssm_heads"] * s["ssm_head_dim"]

    def changed(x, weight, eps):
        if x.shape[-1] == inner:  # the gated norm: forget the gate's place (norm of a constant-sign copy)
            return rms(jnp.abs(x), weight, eps)
        return rms(x, weight, eps)

    return changed


def test_each_slice_of_the_tied_vocabulary_gives_the_uncut_models_rows():
    """Four processes hold a quarter of the rows each (ids over a slice are
    local to it): with the slice's rows as its embedding, each one's logits
    are the uncut reference's logits at the rows of that slice, for ids of
    that slice. One leaf serves the way in and the way out."""
    whole = FlatParamsPolicy(decoder(vocab=FULL_VOCAB))
    flat = seeded(whole)
    tree = whole.unravel(flat)
    s_whole = sizes(vocab=FULL_VOCAB)
    net = decoder()
    policy = FlatParamsPolicy(net)
    for part in range(4):
        rows = slice(part * VOCAB, (part + 1) * VOCAB)
        ids = jax.random.randint(jax.random.key(20 + part), (STEPS,), 0, VOCAB)
        held = dict(tree, embed=tree["embed"][rows])
        got = stepwise_dense(net, held, ids)
        want, _ = ref.forward(ref.unflatten(flat, s_whole), ids + part * VOCAB, s_whole)
        assert relative_rms(got, want[:, rows]) < 1e-5
        assert policy.parameter_count == whole.parameter_count - (FULL_VOCAB - VOCAB) * 32


def ending(policy, flat):
    """With seeded weights a tied model puts its input first (its embedding
    row, times 12, is in the residual and is the head's row too), so no lane
    would ever emit id 0 and end an episode early. Row 0 = 1.5 x row 1: a
    lane that reads id 1 now emits id 0."""
    tree = policy.unravel(flat)
    tree = dict(tree, embed=tree["embed"].at[0].set(1.5 * tree["embed"][1]))
    return jax.flatten_util.ravel_pytree(tree)[0]


@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16])
def test_budget_counts_state_counters_and_a_reset_lane_starts_clean(compute_dtype):
    steps, lanes = 12, 8
    env = TokenCopyEnv(3, 2, steps)  # prompts of ids 1 and 2: a lane that emits id 1 ends its episode at the next step
    problem = VecNE(env, decoder(steps=steps, vocab=3), eval_mode="budget", episode_length=steps,
                    compute_dtype=compute_dtype, store_solution_stats=False, seed=1)
    flat = ending(problem.policy, seeded(problem.policy))
    batch = SolutionBatch(problem, values=trunk_batch(problem.policy, flat, lanes=lanes, rank=2))
    problem.evaluate(batch)
    assert int(problem.status["total_interaction_count"]) == lanes * steps
    episodes = int(problem.status["total_episode_count"])
    assert episodes > lanes
    report = problem.last_policy_report
    counters = {k: int(v) for k, v in report.items() if v.ndim == 0}
    # by hand: three Mamba layers' states rewritten by every lane at every step; a lane zeroed at every
    # episode's end; a lane holds 3 x (4 x 16 x 8 + 3 x (64 + 16)) numbers
    assert counters["ssm_state_updates"] == 3 * lanes * steps
    assert counters["ssm_lane_resets"] == episodes
    assert counters["ssm_state_bytes"] == lanes * 3 * (4 * 16 * 8 + 3 * 80) * (4 if compute_dtype is None else 2)
    assert counters["cache_slots_written"] == lanes * steps  # the one attention layer
    assert counters["expert_pairs_held"] == 0 and "latent_positions_read" not in counters
    ids, positions = np.asarray(report["ids_seen"]), np.asarray(report["positions_seen"])
    assert ids.shape == positions.shape == (lanes, steps) and (positions[:, 0] == 0).all()
    assert ((positions[:, 1:] == positions[:, :-1] + 1) | (positions[:, 1:] == 0)).all()
    assert (positions[:, 1:] == 0).any()
    # the module's own reset: the ended lane's states, window, cache and position are zero, the others' not
    net = problem.policy.module
    state = jax.tree_util.tree_map(lambda x: jnp.ones((3,) + x.shape, x.dtype), net.initial_state())
    after = net.reset_state(state, jnp.asarray([False, True, False]))
    ssm, attn = after["layers"][0]["ssm"], after["layers"][2]["attn"]
    for name, held in (("ssm", ssm), ("conv", ssm), ("k", attn), ("v", attn)):
        assert float(jnp.abs(held[name][1]).max()) == 0.0
        assert float(held[name][0].min()) == 1.0 and float(held[name][2].min()) == 1.0
    assert ssm["resets"].tolist() == [1, 2, 1] and ssm["updates"].tolist() == [1, 1, 1]
    # what the ended lane's matrix state held is kept, summed over its last axis (8 ones), the others' record stays
    assert ssm["ended"][1].tolist() == np.full((4, 16), 8.0).tolist() and float(ssm["ended"][0].max()) == 1.0
    assert attn["t"].tolist() == [1, 0, 1] and attn["step"].tolist() == [1, 1, 1]
    assert after["t"].tolist() == [1, 0, 1]  # the decoder's own


def test_the_decoder_through_the_budget_contract_equals_the_references_whole_sequence():
    """What an evaluation consumed (``last_policy_report``) replays to the
    tokens it emitted, and the whole-sequence reference, given the episodes'
    positions, gives the replay's logits for a lane that began an episode
    midway: states, windows and cache were reset with the lane."""
    steps, lanes, prompt = 16, 6, 3
    env = TokenCopyEnv(4, prompt, steps)
    problem = VecNE(env, decoder(steps=steps, vocab=4), eval_mode="budget", episode_length=steps,
                    store_solution_stats=False, seed=3)
    policy = problem.policy
    values = trunk_batch(policy, ending(policy, seeded(policy)), lanes=lanes, rank=2)
    problem.evaluate(SolutionBatch(problem, values=values))
    report = problem.last_policy_report
    ids, positions = np.asarray(report["ids_seen"]), np.asarray(report["positions_seen"])
    midway = np.flatnonzero((positions[:, 1:] == 0).any(axis=1))
    assert len(midway) > 0
    logits, _ = jax.jit(lambda b, i, p: stepwise_logits(policy, b, i, positions=p))(values, ids, positions)
    first = np.asarray(jnp.argmax(logits, -1))
    goes_on = (positions[:, 1:] == positions[:, :-1] + 1) & (positions[:, 1:] >= prompt)
    ended = (positions[:, 1:] == 0) & (positions[:, :-1] + 1 >= prompt) & (positions[:, :-1] + 1 < steps)
    assert goes_on.sum() > lanes and ended.sum() > 0
    assert np.array_equal(first[:, :-1][goes_on], ids[:, 1:][goes_on])  # a lane consumes what it emitted
    assert (first[:, :-1][ended] == 0).all()  # id 0 ended those episodes
    s = sizes(vocab=4)
    dense = values.materialize()
    lane = int(midway[0])
    want, _ = ref.forward(ref.unflatten(dense[lane], s), ids[lane], s, positions=positions[lane])
    assert relative_rms(logits[lane], want) < 1e-5
    # without the resets the reference reads otherwise from the second episode on
    carried, _ = ref.forward(ref.unflatten(dense[lane], s), ids[lane], s)
    begun = int(np.flatnonzero(positions[lane][1:] == 0)[0]) + 1
    assert relative_rms(logits[lane][begun:], carried[begun:]) > 1e-2


def _evaluated(net_layers, *, steps=16, lanes=6, prompt=3, vocab=4, seed=3):
    env = TokenCopyEnv(vocab, prompt, steps)
    problem = VecNE(env, decoder(steps=steps, vocab=vocab, layers=net_layers), eval_mode="budget", episode_length=steps,
                    store_solution_stats=False, seed=seed)
    policy = problem.policy
    values = trunk_batch(policy, ending(policy, seeded(policy)), lanes=lanes, rank=2)
    problem.evaluate(SolutionBatch(problem, values=values))
    report = problem.last_policy_report
    return policy, values, np.asarray(report["ids_seen"]), np.asarray(report["positions_seen"]), report


def _reference_ended_states(values, lane, ids, positions, s, end):
    """Every Mamba-2 layer's matrix state of ``lane`` after step ``end``,
    summed over its last axis, from the whole-sequence reference on the lane's
    written-out weights."""
    params = ref.unflatten(values.materialize()[lane], s)
    h, found = ref.embed(params, ids[lane], s), []
    for at, index in enumerate(s["layers"]):
        h, sums = ref.layer(params["layers"][at], h, index, s, None, positions[lane])
        if sums is not None:
            found.append(sums[end])
    return np.stack(found)


@pytest.mark.parametrize("fault", [None, "no_reset", "zeroed", "unchanged"])
def test_what_the_states_held_at_an_episodes_end_is_the_references(fault, monkeypatch):
    """``ssm_ended_state`` is the EVALUATION's own (the engine's carry, its
    resets): what a lane's matrix states held when its last episode ran into
    the cap, summed over their last axis. For a lane that began that episode
    midway it equals the reference's recurrence at the record's last
    position, and a state that the engine left unreset, zeroed or unchanged
    reads otherwise (the benchmark's cell holds the timed program to this)."""
    if fault == "no_reset":
        kept = Mamba2Mixer.reset_state
        monkeypatch.setattr(Mamba2Mixer, "reset_state", lambda self, state, mask: {**kept(self, state, mask), "ssm": state["ssm"]})
    elif fault is not None:
        forward = Mamba2Mixer._forward

        def faulty(self, acc, x, state):
            y, new = forward(self, acc, x, state if fault == "unchanged" else {**state, "ssm": 0 * state["ssm"]})
            return y, ({**new, "ssm": state["ssm"]} if fault == "unchanged" else new)

        monkeypatch.setattr(Mamba2Mixer, "_forward", faulty)
    steps, lanes = 24, 8
    policy, values, ids, positions, report = _evaluated(LAYERS, steps=steps, lanes=lanes, prompt=2, vocab=3, seed=1)
    got = np.asarray(report["ssm_ended_state"])
    assert got.shape == (lanes, 3, 4, 16)  # lanes, the three Mamba-2 layers, heads, head_dim
    # a lane last ended an episode where its record shows the next one begin (it emitted id 0 there): an episode
    # begun midway cannot reach the cap before the budget is spent
    ends = {lane: np.flatnonzero(positions[lane][1:] == 0) for lane in range(lanes) if positions[lane][-1] < steps - 1}
    again = [lane for lane, begun in ends.items() if len(begun) > 1]  # ended a second episode: a reset came before it
    assert len(again) > 0
    s = sizes(vocab=3)
    errors = [relative_rms(got[lane], _reference_ended_states(values, lane, ids, positions, s, int(ends[lane][-1]))) for lane in again]
    if fault is None:
        assert max(errors) < 1e-5
    elif fault == "unchanged":
        assert float(np.abs(got).max()) == 0.0  # a state that never moved from zero
    else:
        assert max(errors) > 0.25


def test_a_stage_of_recurrent_layers_alone_keeps_its_own_positions():
    """A pipeline stage may hold no attention layer: the decoder's position
    and step are its own, and the report's counters read no cache."""
    policy, values, ids, positions, report = _evaluated((0, 1, 3))
    assert all(set(layer) == {"ssm", "mlp"} for layer in policy.module.initial_state()["layers"])
    assert (positions[:, 0] == 0).all() and (positions[:, 1:] == 0).any()
    assert ((positions[:, 1:] == positions[:, :-1] + 1) | (positions[:, 1:] == 0)).all()
    counters = {k: int(v) for k, v in report.items() if v.ndim == 0}
    assert counters["cache_slots_written"] == 0 and counters["expert_layer_steps"] == 0
    assert counters["ssm_state_updates"] == 3 * 6 * 16
    logits, _ = jax.jit(lambda b, i, p: stepwise_logits(policy, b, i, positions=p))(values, ids, positions)
    s = sizes(vocab=4, layers=(0, 1, 3))
    lane = int(np.flatnonzero((positions[:, 1:] == 0).any(axis=1))[0])
    want, _ = ref.forward(ref.unflatten(values.materialize()[lane], s), ids[lane], s, positions=positions[lane])
    assert relative_rms(logits[lane], want) < 1e-5


def test_the_two_new_scopes_sit_inside_policy_forward():
    problem = VecNE(TokenCopyEnv(VOCAB, 3, 6), decoder(steps=6, layers=(1, 2)), eval_mode="budget", episode_length=6,
                    store_solution_stats=False)
    batch = trunk_batch(problem.policy, seeded(problem.policy), lanes=4, rank=2)
    text = problem.lower_evaluation(4, like=batch).compile().as_text()
    outer = instruction_scopes(text, inherit=False)
    inner = instruction_scopes(text, inherit=False, names=FORWARD_SCOPES)
    named = {name: scope for name, scope in inner.items() if scope is not None}
    assert set(named.values()) == {"fwd_ssm", "fwd_ssm_state", "fwd_attention", "fwd_dense_mlp", "fwd_head"}
    assert all(outer[name] == "policy_forward" for name in named)
    paths = re.findall(r'op_name="([^"]*evotorch_tpu\.fwd_ssm_state[^"]*)"', text)
    assert paths and all(re.search(r"evotorch_tpu\.policy_forward/.*evotorch_tpu\.fwd_ssm/.*evotorch_tpu\.fwd_ssm_state", p) for p in paths)


@pytest.mark.parametrize(
    "refused, match",
    [
        (dict(num_local_experts=8), "num_local_experts"),
        (dict(mamba_n_groups=2), "mamba_n_groups"),
        (dict(position_embedding_type="rope"), "position_embedding_type"),
        (dict(mamba_proj_bias=True), "bias"),
        (dict(mamba_expand=4), "mamba_expand"),
    ],
)
def test_the_constructor_refuses_what_it_does_not_implement(refused, match):
    with pytest.raises(ValueError, match=match):
        GraniteMoeHybridDecoder(**{**MODEL, **refused}, vocab_size=FULL_VOCAB, max_positions=8)


# -- the carry at the published widths, compiled for the chip -------------------


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as error:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
def test_the_evaluation_rewrites_the_lanes_state_in_place_on_a_v5e(v5e):
    """The real TPU compiler on the whole evaluation program at the published
    widths (a Mamba-2 layer and the attention layer, 32 lanes): every step
    replaces all of a layer's matrix states, and the loop holds them ONCE: one
    kernel a Mamba layer (``net/ssmstate.py``) takes the carry's ``bf16[lanes,
    128, 4096]`` (a lane's state is stored ``(state_dim, heads x head_dim)``)
    and writes it IN PLACE, its result aliased to that operand; no copy of a
    layer's states, no select over them and no other fusion writes them under
    the state's scope (at the cell's 256 lanes and ten layers the program's
    temporaries are 4.29 GB: the bfloat16 trunk 1.60, the lanes' state 2.61).
    (A leaf whose last axis is 4, the convolution's as published, made the
    compiler reshape the whole flat trunk into rows of 4, padded 32-fold: the
    leaf is held taps first.)"""
    from jax.sharding import SingleDeviceSharding

    from evotorch_tpu.neuroevolution.net.vecrl import run_vectorized_rollout

    lanes, steps, vocab = 32, 8, 512
    published = dict(
        MODEL, hidden_size=2048, num_attention_heads=32, num_key_value_heads=8, shared_intermediate_size=8192,
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128, attention_multiplier=1 / 64,
        layer_types=(["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    )
    net = GraniteMoeHybridDecoder(**published, vocab_size=100_352, max_positions=steps, layers_held=[4, 5], vocab_held=vocab)
    problem = VecNE(TokenCopyEnv(vocab, 4, steps), net, eval_mode="budget", episode_length=steps,
                    compute_dtype=jnp.bfloat16, initial_bounds=None, store_solution_stats=False, seed=1)
    policy, one_chip = problem.policy, SingleDeviceSharding(v5e.devices[0])
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    length = policy.parameter_count
    factors = jax.eval_shape(
        lambda k, s: sample_trunk_delta_factors(k, policy, s, 4), jax.random.key(0), jax.ShapeDtypeStruct((length,), jnp.float32)
    )
    batch = TrunkDeltaParamsBatch(
        center=on_chip(jax.ShapeDtypeStruct((length,), jnp.float32)),
        coeffs=on_chip(jax.ShapeDtypeStruct((lanes, 4), jnp.float32)),
        factors=jax.tree_util.tree_map(on_chip, factors),
    )
    key, stats = jax.tree_util.tree_map(on_chip, (problem._rng_key, problem._obs_norm.stats))
    text = (
        run_vectorized_rollout.trace(problem._env, policy, batch, key, stats, **problem._rollout_kwargs(lanes, None))
        .lower(lowering_platforms=("tpu",))
        .compile()
        .as_text()
    )
    from evotorch_tpu.neuroevolution.net import ssmstate

    state = f"bf16[{lanes},128,4096]"
    # every instruction whose result, alone or in a tuple, is a lane-batched matrix state
    results = [re.match(r"\s*(?:ROOT )?%\S+ = (.*?) [a-z][\w.-]*\(", line) for line in text.splitlines()]
    written = [found.string for found in results if found and state in found.group(1)]
    passes = [line for line in written if "fwd_ssm_state" in line and (" fusion(" in line or " custom-call(" in line)]
    assert len(passes) == 1  # decay, outer product, readout and write-back: one pass over the carry
    (kernel,) = passes
    assert 'custom_call_target="tpu_custom_call"' in kernel and ssmstate.KERNEL_NAME in kernel
    assert "output_to_operand_aliasing={{0}: (1, {})}" in kernel  # the new states lie where the old ones lay
    assert not [line for line in written if " copy(" in line or " select(" in line]
    assert "bf16[%d,64,64,128]" % lanes not in text  # no second form of a layer's states
