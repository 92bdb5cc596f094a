"""The ``kimi_linear`` decoder (``net/decoder.py``: gated delta-rule layers,
KDA, whose per-lane matrix state is decayed a key channel at a time and
corrected by a rank-1 delta rule every step, beside a latent-attention layer
without query LoRA or rotation, and sigmoid-routed experts) at a small size on
the CPU, seeded random weights, against the benchmark's plain reference
(``benchmark/reference/kimi_linear_decoder.py``: convolutions over the
sequence, the recurrence in a scan, latent attention in the plain form, no
lanes).

The published RATIOS kept: KDA heads x head_dim wider than the hidden size,
gate ranks of one head_dim, one latent-attention layer after three KDA
layers, a dense layer 0, experts held as a range of a wider router: hidden 32,
4 KDA heads x 8, latent rank 16, head dims 8 | 4 and 8, 8 of 32 experts held,
top 4, vocabulary 16 of 64.
"""

import hashlib
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
    Glm4MoeLiteDecoder,
    GraniteMoeHybridDecoder,
    KimiDeltaAttention,
    KimiLinearDecoder,
    LatentAttention,
    SparseExperts,
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


ref = _load("benchmark/reference/kimi_linear_decoder.py", "kimi_linear_reference")
_delta_rule = ref.delta_rule  # the unchanged recurrence, for a mutation that wraps it

LINEAR = dict(full_attn_layers=[4, 8], head_dim=8, kda_layers=[1, 2, 3, 5, 6, 7], num_heads=4, short_conv_kernel_size=4)
MODEL = dict(
    hidden_size=32, num_attention_heads=4, q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, mla_use_nope=True, linear_attn_config=LINEAR, intermediate_size=48,
    moe_intermediate_size=16, num_experts_per_token=4, num_shared_experts=1, first_k_dense_replace=1,
    moe_layer_freq=1, moe_renormalize=True, moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    num_expert_group=1, topk_group=1, rope_theta=10000.0, rope_scaling=None, rms_norm_eps=1e-5,
    tie_word_embeddings=False,
)
EXPERTS, HELD, FULL_VOCAB, VOCAB, STEPS = 32, (8, 16), 64, 16, 14
LAYERS = (0, 1, 2, 3, 4)  # KDA + dense, KDA, KDA, latent attention, KDA


def decoder(*, steps=STEPS, vocab=VOCAB, layers=LAYERS):
    return KimiLinearDecoder(
        **MODEL, num_experts=EXPERTS, num_hidden_layers=8, vocab_size=FULL_VOCAB, max_positions=steps,
        layers_held=list(layers), experts_held=range(*HELD), vocab_held=vocab,
    )


def sizes(*, vocab=VOCAB, layers=LAYERS, **changed):
    config = dict(
        MODEL, published={"num_experts": EXPERTS}, layers_held=list(layers), kept_kda_moe_layers=len(layers),
        experts_held=list(HELD), vocab_held=vocab,
    )
    return {**ref.sizes(config), **changed}


def seeded(policy, seed=1):
    flat = policy.init_parameters(jax.random.key(seed))
    # norms, the decay and the gates away from their initial values, so that each matters
    return flat + 0.15 * jax.random.normal(jax.random.key(seed + 1), flat.shape)


def relative_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2)))


def trunk_batch(policy, flat, lanes=3, rank=3, seed=4):
    sigma = jnp.full((policy.parameter_count,), 0.05)
    factors = sample_trunk_delta_factors(jax.random.key(seed), policy, sigma, rank)
    z = jax.random.normal(jax.random.key(seed + 1), (lanes, rank))
    return TrunkDeltaParamsBatch(center=flat, coeffs=z, factors=factors)


def stepwise_dense(net, params, ids, state=None):
    @jax.jit
    def run(params, ids, state):
        def step(state, token):
            logits, state = net.apply(params, token[None], state)
            return state, logits

        return jax.lax.scan(step, state, ids)

    return run(params, ids, net.initial_state() if state is None else state)


@pytest.fixture(scope="module")
def model():
    net = decoder()
    policy = FlatParamsPolicy(net)
    return net, policy, seeded(policy)


def test_parameter_layout_and_counts_are_the_references(model):
    net, policy, flat = model
    s = sizes()
    assert policy.parameter_count == ref.parameter_count(s)
    mine, theirs = policy.unravel(flat), ref.unflatten(flat, s)
    assert [set(layer) for layer in mine["layers"]] == [{"kda", "mlp"}] * 3 + [{"attn", "mlp"}, {"kda", "mlp"}]
    assert set(mine["layers"][3]["attn"]) == {"in_norm", "q", "kv_a", "kv_a_norm", "kv_b", "o"}  # no query LoRA
    for at, block, leaf in ((0, "kda", "A_log"), (1, "kda", "dt_bias"), (2, "kda", "v_conv"), (4, "kda", "g_b"), (3, "attn", "q")):
        assert np.array_equal(mine["layers"][at][block][leaf], theirs["layers"][at][block][leaf])
    assert np.array_equal(mine["layers"][3]["mlp"]["experts"]["down"], theirs["layers"][3]["mlp"]["experts"]["down"])
    assert np.array_equal(mine["head"], theirs["head"])
    # the benchmark's configuration: published layers 0-4, 8 of 256 experts, 20,480 rows
    import json

    with open(os.path.join(ROOT, "benchmark/configs/kimi_linear_ep32.json")) as f:
        config = json.load(f)
    published = ref.sizes(config)
    assert published["layers"] == [0, 1, 2, 3, 4] and published["kda_layers"][:4] == [0, 1, 2, 4]
    assert ref.parameter_count(published) == config["parameter_count"] == 602_434_432


@pytest.mark.parametrize("steps", [1, STEPS])
def test_dense_apply_stepwise_equals_the_whole_sequence_reference(model, steps):
    net, policy, flat = model
    ids = jax.random.randint(jax.random.key(3), (steps,), 0, VOCAB)
    _, got = stepwise_dense(net, policy.unravel(flat), ids)
    want, _ = ref.forward(ref.unflatten(flat, sizes()), ids, sizes())
    assert got.shape == (steps, VOCAB) and relative_rms(got, want) < 1e-5


def test_trunk_delta_forward_equals_dense_apply_on_materialised_rows(model):
    """Equal effective weights, the convolutions' leaves and the 1-D decay
    leaves included: every lane's ``A_log``, ``dt_bias`` and taps are its
    own; the routes are the reference's."""
    net, policy, flat = model
    batch = trunk_batch(policy, flat)
    ids = jax.random.randint(jax.random.key(6), (batch.popsize, STEPS), 0, VOCAB)
    got, routes = jax.jit(lambda b, i: stepwise_logits(policy, b, i))(batch, ids)
    assert routes.shape == (STEPS, 4, batch.popsize, 4)  # four sparse layers, top 4
    dense = batch.materialize()
    lanes = [policy.unravel(dense[lane]) for lane in range(batch.popsize)]
    for leaf in ("q_conv", "A_log", "dt_bias"):  # perturbed per lane
        assert not np.allclose(lanes[0]["layers"][1]["kda"][leaf], lanes[1]["layers"][1]["kda"][leaf])
    for lane in range(batch.popsize):
        assert relative_rms(got[lane], stepwise_dense(net, lanes[lane], ids[lane])[1]) < 1e-5
        theirs, chosen = ref.forward(ref.unflatten(dense[lane], sizes()), ids[lane], sizes())
        assert relative_rms(got[lane], theirs) < 1e-5
        assert np.array_equal(np.sort(routes[:, :, lane], -1), np.sort(np.stack(chosen, 1), -1))


def test_a_lane_reset_midway_reads_as_a_fresh_lane(model):
    """A lane that begins a new episode at step 6 (the record's positions say
    so) is reset lane by lane: from there on its logits are those of a fresh
    lane fed the same ids, and the other lanes are untouched."""
    net, policy, flat = model
    batch = trunk_batch(policy, flat, lanes=3)
    ids = jax.random.randint(jax.random.key(7), (3, STEPS), 0, VOCAB)
    positions = np.tile(np.arange(STEPS), (3, 1))
    positions[1, 6:] = np.arange(STEPS - 6)
    replay = jax.jit(lambda b, i, p: stepwise_logits(policy, b, i, positions=p)[0])
    got, whole = replay(batch, ids, positions), replay(batch, ids, np.tile(np.arange(STEPS), (3, 1)))
    dense = policy.unravel(batch.materialize()[1])
    _, fresh = stepwise_dense(net, dense, ids[1, 6:])
    assert relative_rms(got[1, 6:], fresh) < 1e-5
    assert relative_rms(got[1, :6], whole[1, :6]) < 1e-6 and relative_rms(got[1, 6:], whole[1, 6:]) > 1e-2
    assert relative_rms(got[0], whole[0]) < 1e-6 and relative_rms(got[2], whole[2]) < 1e-6


def test_the_state_pass_is_the_delta_rule_with_a_decay_a_key_channel():
    """``KimiDeltaAttention._state_plain`` on random stored states against
    the rule written out a head at a time in float64: ``S_t = (I - beta k
    k^T) Diag(a) S + beta k v^T``, ``o = S_t^T q``, with a decay that differs
    along the key axis (a per-head scalar decay would not pass)."""
    lanes, heads, dim = 3, 4, 8
    keys = jax.random.split(jax.random.key(11), 6)
    held = jax.random.normal(keys[0], (lanes, dim, heads, dim))  # stored turned: (key, head, value)
    decay = jax.random.uniform(keys[1], (lanes, heads, dim), minval=0.2, maxval=1.0)
    k = jax.random.normal(keys[2], (lanes, heads, dim))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v, q = jax.random.normal(keys[3], (lanes, heads, dim)), jax.random.normal(keys[4], (lanes, heads, dim))
    beta = jax.random.uniform(keys[5], (lanes, heads))
    new, out = KimiDeltaAttention._state_plain(held, decay, k, v, beta, q)
    assert new.shape == held.shape and out.shape == (lanes, heads, dim)
    S, a, k64, v64, q64, b64 = (np.asarray(x, np.float64) for x in (held, decay, k, v, q, beta))
    for lane in range(lanes):
        for h in range(heads):
            state = S[lane, :, h, :]  # (key, value)
            kk = k64[lane, h][:, None]
            rule = (np.eye(dim) - b64[lane, h] * kk @ kk.T) @ np.diag(a[lane, h]) @ state + b64[lane, h] * kk @ v64[lane, h][None]
            assert np.allclose(np.asarray(new)[lane, :, h, :], rule, atol=1e-5)
            assert np.allclose(np.asarray(out)[lane, h], rule.T @ q64[lane, h], atol=1e-5)
    scalar = jnp.broadcast_to(jnp.mean(decay, axis=-1, keepdims=True), decay.shape)
    assert not np.allclose(KimiDeltaAttention._state_plain(held, scalar, k, v, beta, q)[0], new, atol=1e-3)


def test_the_expert_shares_sum_to_the_uncut_layer():
    """A small layer of 256 experts, top 8, cut into the 32 shares of 8 a
    chip: the shares' outputs, less the input each adds back and the shared
    expert each adds, sum to the uncut layer's with the shared expert once."""
    dim, width = 16, 4
    whole = SparseExperts(dim, width, 256, 8, route_scale=2.446, post_norm=False, route_norm_eps=1e-20)
    params = whole.init(jax.random.key(2))
    # outputs of order one beside a small input, so that taking the input back out loses nothing
    params = jax.tree_util.tree_map(lambda leaf: 20.0 * leaf, dict(params, in_norm=params["in_norm"] / 20.0))
    params = dict(params, expert_bias=0.1 * jax.random.normal(jax.random.key(3), (256,)))
    x = 1e-3 * jax.random.normal(jax.random.key(4), (dim,))
    uncut, _ = whole.apply(params, x, whole.initial_state())
    y = jax.lax.rsqrt(jnp.mean(x * x) + 1e-5) * x * params["in_norm"]
    shared, _ = whole.shared.apply(params["shared"], y)
    total = jnp.zeros_like(x)
    for share in range(32):
        held = range(8 * share, 8 * share + 8)
        part = SparseExperts(dim, width, 256, 8, experts_held=held, route_scale=2.446, post_norm=False, route_norm_eps=1e-20)
        piece = dict(params, experts={name: leaf[held.start : held.stop] for name, leaf in params["experts"].items()})
        out, state = part.apply(piece, x, part.initial_state())
        total = total + (out - x - shared)
    assert relative_rms(total + shared, uncut - x) < 1e-5
    assert int(state["hits"]) <= 8


def test_latent_attention_without_lora_or_rotation_equals_the_references():
    """``LatentAttention(q_rank=None, rotary=False)`` stepped over its cache
    in the absorbed form, one lane, against the reference's plain form over
    the whole sequence, through an episode that begins midway."""
    attn = LatentAttention(32, 4, q_rank=None, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, slots=12, rope_theta=1e4, rotary=False)
    params = attn.init(jax.random.key(0))
    assert set(params) == {"in_norm", "q", "kv_a", "kv_a_norm", "kv_b", "o"}
    params = jax.tree_util.tree_map(lambda p: p + 0.05 * jax.random.normal(jax.random.key(9), p.shape), params)
    h = jax.random.normal(jax.random.key(1), (12, 32))
    positions = np.array([0, 1, 2, 3, 4, 0, 1, 2, 3, 4, 5, 6])
    state, got = attn.initial_state(), []
    for t in range(12):
        if t and positions[t] == 0:
            state = jax.tree_util.tree_map(lambda x: x[0], attn.reset_state(jax.tree_util.tree_map(lambda x: x[None], state), jnp.asarray([True])))
        y, state = attn.apply(params, h[t], state)
        got.append(y)
    s = dict(sizes(), heads=4, kv_rank=16, nope=8, rope=4, v=8)
    want = ref.attention(params, h, s, jnp.asarray(positions))
    assert relative_rms(jnp.stack(got), want) < 1e-5
    # the same weights with rotation read otherwise: the option is what is compared
    rotated = LatentAttention(32, 4, q_rank=None, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, slots=12, rope_theta=1e4)
    _, turned = jax.lax.scan(lambda st, x: rotated.apply(params, x, st)[::-1], rotated.initial_state(), h[:5])
    assert relative_rms(turned, want[:5]) > 1e-4


@pytest.mark.parametrize("mutation", ["no_correction", "decay_a_head", "no_l2", "no_output_gate", "tap_order"])
def test_the_comparison_catches_a_changed_equation(model, mutation, monkeypatch):
    net, policy, flat = model
    s = sizes()
    params = ref.unflatten(flat, s)
    if mutation == "no_correction":
        s["correction"] = False
    elif mutation == "decay_a_head":
        monkeypatch.setattr(
            ref, "delta_rule",
            lambda q, k, v, g, beta, positions, *rest: _delta_rule(
                q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), beta, positions, *rest
            ),
        )
    elif mutation == "no_l2":
        monkeypatch.setattr(ref, "l2", lambda x: x)
    elif mutation == "no_output_gate":
        for layer in params["layers"].values():
            if "kda" in layer:
                layer["kda"]["g_a"] = jnp.zeros_like(layer["kda"]["g_a"])  # a gate of one half everywhere
    else:
        for layer in params["layers"].values():
            if "kda" in layer:
                layer["kda"]["q_conv"] = layer["kda"]["q_conv"][::-1]
    ids = jax.random.randint(jax.random.key(3), (STEPS,), 0, VOCAB)
    _, got = stepwise_dense(net, policy.unravel(flat), ids)
    want, _ = ref.forward(params, ids, s)
    assert relative_rms(got, want) > 5e-3


def _evaluated(*, steps=16, lanes=6, prompt=3, vocab=4, seed=3, compute_dtype=None):
    env = TokenCopyEnv(vocab, prompt, steps)
    problem = VecNE(env, decoder(steps=steps, vocab=vocab), eval_mode="budget", episode_length=steps,
                    compute_dtype=compute_dtype, store_solution_stats=False, seed=seed)
    policy = problem.policy
    values = trunk_batch(policy, seeded(policy), lanes=lanes, rank=2)
    problem.evaluate(SolutionBatch(problem, values=values))
    return problem, policy, values


@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16])
def test_budget_counts_the_kda_counters_and_a_reset_lane_starts_clean(compute_dtype):
    steps, lanes = 12, 6
    problem, policy, values = _evaluated(steps=steps, lanes=lanes, compute_dtype=compute_dtype)
    report = problem.last_policy_report
    counters = {k: int(v) for k, v in report.items() if v.ndim == 0}
    episodes = int(problem.status["total_episode_count"])
    # by hand: four KDA layers' states rewritten by every lane at every step; a lane zeroed at every episode's
    # end; a lane holds 4 x (4 x 8 x 8 + 3 x 3 x 32) numbers
    assert counters["kda_state_updates"] == 4 * lanes * steps
    assert counters["kda_lane_resets"] == episodes >= lanes
    assert counters["kda_state_bytes"] == lanes * 4 * (4 * 8 * 8 + 3 * 96) * (4 if compute_dtype is None else 2)
    assert counters["latent_positions_read"] > 0 and counters["expert_pairs_held"] > 0
    assert "ssm_state_updates" not in counters  # the Mamba-2 counters keep their meaning
    assert report["kda_ended_state"].shape == (lanes, 4, 4, 8)
    # the module's own reset: the ended lane's state and window are zero, the others' not; what it held is kept
    net = policy.module
    state = jax.tree_util.tree_map(lambda x: jnp.ones((3,) + x.shape, x.dtype), net.initial_state())
    after = net.reset_state(state, jnp.asarray([False, True, False]))
    kda = after["layers"][0]["kda"]
    for name in ("state", "conv"):
        assert float(jnp.abs(kda[name][1]).max()) == 0.0 and float(kda[name][0].min()) == 1.0
    assert kda["ended"][1].tolist() == np.full((4, 8), 8.0).tolist() and kda["resets"].tolist() == [1, 2, 1]
    assert after["t"].tolist() == [1, 0, 1]


def test_what_the_states_held_at_an_episodes_end_is_the_references():
    """``kda_ended_state`` is the EVALUATION's own (the engine's carry and
    resets): for a lane whose last episode ran into the cap, every KDA
    layer's state summed over its key axis equals the reference's recurrence
    at the record's last position, its episodes' positions given."""
    steps = 16
    problem, policy, values = _evaluated(steps=steps)
    report = problem.last_policy_report
    ids, positions = np.asarray(report["ids_seen"]), np.asarray(report["positions_seen"])
    got = np.asarray(report["kda_ended_state"])
    s = sizes(vocab=4)
    dense = values.materialize()
    # the last step's action is no part of the record: the replay's first token says whether it was id 0
    logits, _ = jax.jit(lambda b, i, p: stepwise_logits(policy, b, i, positions=p))(values, ids, positions)
    closed = (np.asarray(jnp.argmax(logits[:, -1], -1)) == 0) & (positions[:, -1] + 1 >= 3)  # past the prompt
    checked = 0
    for lane in range(ids.shape[0]):
        # the step after which the lane last ended an episode: at the cap or by id 0 at the last step, else
        # before its last episode began (the driver's rule, benchmark/drivers/oo_kda_searcher.py)
        begun = np.flatnonzero(positions[lane][1:] == 0)
        if positions[lane][-1] + 1 >= steps or closed[lane]:
            end = steps - 1
        else:
            end = int(begun[-1]) if len(begun) else None
        if end is None:
            continue
        params = ref.unflatten(dense[lane], s)
        h, found = ref.embed(params, ids[lane], s), []
        for at, index in enumerate(s["layers"]):
            h, _, sums = ref.layer(params["layers"][at], h, index, s, None, positions[lane])
            if sums is not None:
                found.append(sums[end])
        assert relative_rms(got[lane], np.stack(found)) < 1e-5
        checked += 1
    assert checked > 0


def test_the_two_new_scopes_sit_inside_policy_forward():
    problem = VecNE(TokenCopyEnv(VOCAB, 3, 6), decoder(steps=6, layers=(0, 3)), eval_mode="budget", episode_length=6,
                    store_solution_stats=False)
    batch = trunk_batch(problem.policy, seeded(problem.policy), lanes=4, rank=2)
    text = problem.lower_evaluation(4, like=batch).compile().as_text()
    outer = instruction_scopes(text, inherit=False)
    inner = instruction_scopes(text, inherit=False, names=FORWARD_SCOPES)
    named = {name: scope for name, scope in inner.items() if scope is not None}
    assert {"fwd_kda", "fwd_kda_state", "fwd_attention", "fwd_latent_cache", "fwd_router", "fwd_experts"} <= set(named.values())
    assert not {"fwd_ssm", "fwd_ssm_state"} & set(named.values())
    assert all(outer[name] == "policy_forward" for name in named)
    paths = re.findall(r'op_name="([^"]*evotorch_tpu\.fwd_kda_state[^"]*)"', text)
    assert paths and all(re.search(r"evotorch_tpu\.policy_forward/.*evotorch_tpu\.fwd_kda/.*evotorch_tpu\.fwd_kda_state", p) for p in paths)


# sha256 of the lowered evaluation program (StableHLO: no op_name, no location) of a small GLM and a small Granite
# decoder as the library lowered them before the KDA block and the options of LatentAttention were added
OTHER_FAMILIES = {
    "glm4_moe_lite": "ad0773b0db8453cb8209fa63c28c5c5faa5d7f376ee55a1fef0981814a6f10d6",
    "granitemoehybrid": "7afa9c8c5710a0cf65d120e21a66158a24ea914dba996b704dfe742616d45be1",
}


def _other_family(name):
    if name == "glm4_moe_lite":
        return Glm4MoeLiteDecoder(
            hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
            qk_rope_head_dim=4, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32, num_experts_per_tok=4,
            n_shared_experts=1, first_k_dense_replace=1, routed_scaling_factor=1.8, norm_topk_prob=True,
            topk_method="noaux_tc", n_group=1, topk_group=1, rope_theta=1000000.0, rope_scaling=None, rms_norm_eps=1e-5,
            n_routed_experts=64, vocab_size=64, num_hidden_layers=6, max_positions=6, layers_held=[0, 1],
            experts_held=range(8, 16), vocab_held=16,
        )
    return GraniteMoeHybridDecoder(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=48,
        layer_types=["mamba", "mamba", "attention", "mamba"] * 2, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
        mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2, mamba_conv_bias=True, mamba_proj_bias=False,
        num_local_experts=0, attention_bias=False, attention_multiplier=0.125, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8, position_embedding_type="nope", tie_word_embeddings=True,
        rms_norm_eps=1e-5, vocab_size=64, max_positions=6, layers_held=[1, 2], vocab_held=16,
    )


@pytest.mark.parametrize("family", sorted(OTHER_FAMILIES))
def test_the_other_decoders_lower_to_the_same_program(family):
    """``LatentAttention`` and ``_Decoder.state_report`` changed: the
    evaluation programs of the families that do not use the new options are
    the same program, operation for operation (the text lowered before any
    compiler pass carries no name of a scope)."""
    problem = VecNE(TokenCopyEnv(16, 3, 6), _other_family(family), eval_mode="budget", episode_length=6,
                    compute_dtype=jnp.bfloat16, store_solution_stats=False, seed=1)
    policy = problem.policy
    n = policy.parameter_count
    factors = jax.eval_shape(
        lambda k, s: sample_trunk_delta_factors(k, policy, s, 2), jax.random.key(0), jax.ShapeDtypeStruct((n,), jnp.float32)
    )
    batch = TrunkDeltaParamsBatch(
        center=jax.ShapeDtypeStruct((n,), jnp.float32), coeffs=jax.ShapeDtypeStruct((4, 2), jnp.float32), factors=factors
    )
    text = problem.lower_evaluation(4, like=batch).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == OTHER_FAMILIES[family]


@pytest.mark.parametrize(
    "refused, match",
    [
        (dict(num_expert_group=2), "num_expert_group"),
        (dict(moe_layer_freq=2), "moe_layer_freq"),
        (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (dict(linear_attn_config=dict(LINEAR, kda_layers=[1, 2, 3, 4, 5, 6, 7])), "kda_layers"),
        (dict(moe_router_activation_func="softmax"), "score_func"),
    ],
)
def test_the_constructor_refuses_what_it_does_not_implement(refused, match):
    with pytest.raises(ValueError, match=match):
        KimiLinearDecoder(**{**MODEL, **refused}, num_experts=EXPERTS, num_hidden_layers=8, vocab_size=FULL_VOCAB, max_positions=8)
