"""The ``smallthinker`` decoder (``net/decoder.py``: every layer chooses its
experts BEFORE its attention, from the attention's own normed input, with a
softmax router over the chosen logits; ReGLU experts on the post-attention
stream; grouped-query attention, full without positions in one layer of four
and a RoPE window in the others) at a small size on the CPU, seeded random
weights, against the benchmark's plain reference
(``benchmark/reference/smallthinker_decoder.py``: whole sequence, masks on a
``(T, T)`` score matrix, the experts a python loop, no lanes).

The published RATIOS kept: query heads in groups of seven over the KV heads,
one NoPE full layer then three RoPE window layers, a router wider than the
experts held: hidden 32, 14 heads over 2 KV heads of 8, expert width 16, 4 of
8 experts held, top 3, a window of 8 slots over episodes of 20 steps (the
ring wraps), vocabulary 16 of 64.
"""

import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu import SolutionBatch
from evotorch_tpu.envs.tokens import TokenCopyEnv
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.neuroevolution.net import grouped
from evotorch_tpu.neuroevolution.net.decoder import (
    GatedAttention,
    RouteAheadLayer,
    SmallThinkerDecoder,
    SparseExperts,
    stepwise_logits,
)
from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors
from evotorch_tpu.observability.scopes import FORWARD_SCOPES, instruction_scopes
from evotorch_tpu.tools.lowrank import DeltaFactor, TrunkDeltaParamsBatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("benchmark/reference/smallthinker_decoder.py", "smallthinker_reference")

LAYOUT = [0, 1, 1, 1] * 2
MODEL = dict(
    hidden_size=32, num_attention_heads=14, num_key_value_heads=2, head_dim=8, moe_ffn_hidden_size=16,
    moe_num_active_primary_experts=3, moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rope_layout=LAYOUT, sliding_window_layout=LAYOUT, sliding_window_size=8, rope_theta=1.5e6,
    rope_scaling=None, rms_norm_eps=1e-6, tie_word_embeddings=False,
)
EXPERTS, HELD, FULL_VOCAB, VOCAB, STEPS = 8, (4, 8), 64, 16, 20
LAYERS = (0, 1, 2, 3)  # NoPE full, then three RoPE window layers


def decoder(*, steps=STEPS, vocab=VOCAB, layers=LAYERS):
    return SmallThinkerDecoder(
        **MODEL, moe_num_primary_experts=EXPERTS, num_hidden_layers=len(LAYOUT), vocab_size=FULL_VOCAB,
        max_positions=steps, layers_held=list(layers), experts_held=range(*HELD), vocab_held=vocab,
    )


def sizes(*, vocab=VOCAB, layers=LAYERS, **changed):
    config = dict(
        MODEL, published={"moe_num_primary_experts": EXPERTS}, layers_held=list(layers), kept_layers=len(layers),
        experts_held=list(HELD), vocab_held=vocab,
    )
    return {**ref.sizes(config), **changed}


def seeded(policy, seed=1):
    flat = policy.init_parameters(jax.random.key(seed))
    # norms and routers away from their initial values, so that each matters
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
            return state, (logits, [s["mlp"]["chosen"] for s in state["layers"]])

        return jax.lax.scan(step, net.initial_state(), ids)

    return run(params, ids)


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
    # no leaf the published model lacks: no gate, q/k norm or closing norm, no selection bias, no shared expert
    assert [set(layer["attn"]) for layer in mine["layers"]] == [{"in_norm", "q", "k", "v", "o"}] * 4
    assert [set(layer["mlp"]) for layer in mine["layers"]] == [{"in_norm", "router", "experts"}] * 4
    for at, block, leaf in ((0, "attn", "q"), (1, "attn", "in_norm"), (2, "mlp", "router"), (3, "mlp", "in_norm")):
        assert np.array_equal(mine["layers"][at][block][leaf], theirs["layers"][at][block][leaf])
    assert np.array_equal(mine["layers"][3]["mlp"]["experts"]["down"], theirs["layers"][3]["mlp"]["experts"]["down"])
    assert np.array_equal(mine["head"], theirs["head"])
    # the layouts: full layer 0 holds every position, the window layers a ring of 8; RoPE off in layer 0 alone
    assert [layer.attention.slots for layer in net.layers] == [STEPS, 8, 8, 8]
    assert [layer.attention.rope_theta for layer in net.layers] == [None, 1.5e6, 1.5e6, 1.5e6]
    # the benchmark's configuration: layers 0-3, 16 of 64 experts, 37,984 rows
    with open(os.path.join(ROOT, "benchmark/configs/smallthinker_ep4.json")) as f:
        config = json.load(f)
    published = ref.sizes(config)
    assert published["layers"] == [0, 1, 2, 3] and published["num_experts"] == 64
    assert ref.parameter_count(published) == config["parameter_count"] == 656_529_920


@pytest.mark.parametrize("steps", [1, STEPS])
def test_dense_apply_stepwise_equals_the_whole_sequence_reference(model, steps):
    """Over 20 steps the window layers' rings of 8 slots wrap twice."""
    net, policy, flat = model
    ids = jax.random.randint(jax.random.key(3), (steps,), 0, VOCAB)
    _, (got, chosen) = stepwise_dense(net, policy.unravel(flat), ids)
    want, routes = ref.forward(ref.unflatten(flat, sizes()), ids, sizes())
    assert got.shape == (steps, VOCAB) and relative_rms(got, want) < 1e-5
    for mine, theirs in zip(chosen, routes):
        assert np.array_equal(np.sort(mine, -1), np.sort(theirs, -1))


def test_trunk_delta_forward_equals_dense_apply_on_materialised_rows(model):
    net, policy, flat = model
    batch = trunk_batch(policy, flat)
    ids = jax.random.randint(jax.random.key(6), (batch.popsize, STEPS), 0, VOCAB)
    got, routes = jax.jit(lambda b, i: stepwise_logits(policy, b, i))(batch, ids)
    assert routes.shape == (STEPS, 4, batch.popsize, 3)  # four layers, top 3
    dense = batch.materialize()
    for lane in range(batch.popsize):
        params = policy.unravel(dense[lane])
        assert relative_rms(got[lane], stepwise_dense(net, params, ids[lane])[1][0]) < 1e-5
        theirs, chosen = ref.forward(ref.unflatten(dense[lane], sizes()), ids[lane], sizes())
        assert relative_rms(got[lane], theirs) < 1e-5
        assert np.array_equal(np.sort(routes[:, :, lane], -1), np.sort(np.stack(chosen, 1), -1))


def test_a_lane_reset_midway_reads_as_a_fresh_lane(model):
    net, policy, flat = model
    batch = trunk_batch(policy, flat, lanes=3)
    ids = jax.random.randint(jax.random.key(7), (3, STEPS), 0, VOCAB)
    positions = np.tile(np.arange(STEPS), (3, 1))
    positions[1, 9:] = np.arange(STEPS - 9)
    replay = jax.jit(lambda b, i, p: stepwise_logits(policy, b, i, positions=p)[0])
    got, whole = replay(batch, ids, positions), replay(batch, ids, np.tile(np.arange(STEPS), (3, 1)))
    dense = batch.materialize()[1]
    want, _ = ref.forward(ref.unflatten(dense, sizes()), ids[1], sizes(), positions=jnp.asarray(positions[1]))
    assert relative_rms(got[1], want) < 1e-5
    assert relative_rms(got[1, 9:], whole[1, 9:]) > 1e-2
    assert relative_rms(got[0], whole[0]) < 1e-6 and relative_rms(got[2], whole[2]) < 1e-6


def _layer(held=range(64), top_k=6, dim=16, width=4):
    """One route-ahead layer at the published ratios of heads and experts."""
    attention = GatedAttention(dim, 7, 1, 4, slots=4, rope_theta=1.5e6, eps=1e-6, gate=False, qk_norm=False, post_norm=False)
    experts = SparseExperts(dim, width, 64, top_k, experts_held=held, num_shared_experts=0, score_func="softmax",
                            eps=1e-6, post_norm=False, activation="relu")
    return RouteAheadLayer(attention, experts)


def test_the_expert_shares_sum_to_the_uncut_layer():
    """A route-ahead layer of 64 experts, top 6, cut into the deployment's 4
    shares of 16: what every share computes alike (the attention's output,
    which each adds back) counted once, the shares' partial outputs add up
    to the uncut layer's."""
    whole = _layer()
    params = whole.init(jax.random.key(2))
    params = jax.tree_util.tree_map(lambda leaf: leaf + 0.3 * jax.random.normal(jax.random.key(5), leaf.shape), params)
    x = jax.random.normal(jax.random.key(4), (16,))
    uncut, _ = whole.apply(params, x, whole.initial_state())
    h, _ = whole.attention.apply(params["attn"], x, whole.attention.initial_state())
    total = jnp.zeros_like(x)
    for share in range(4):
        held = range(16 * share, 16 * share + 16)
        part = _layer(held)
        experts = {name: leaf[held.start : held.stop] for name, leaf in params["mlp"]["experts"].items()}
        out, state = part.apply({**params, "mlp": {**params["mlp"], "experts": experts}}, x, part.initial_state())
        total = total + (out - h)
        assert int(state["mlp"]["ahead"]) == 6  # every share routes all six ahead, held or not
    assert relative_rms(total + h, uncut) < 1e-5 and relative_rms(h, uncut) > 1e-2


def test_the_routes_are_decided_before_the_attention():
    """Changing a layer's attention weights changes its output and leaves
    the experts it chose unchanged: the router reads the layer's input
    (through the attention's own norm), not the attention's output."""
    layer = _layer()
    params = layer.init(jax.random.key(3))
    params = jax.tree_util.tree_map(lambda leaf: leaf + 0.3 * jax.random.normal(jax.random.key(8), leaf.shape), params)
    x = jax.random.normal(jax.random.key(9), (16,))
    out, state = layer.apply(params, x, layer.initial_state())
    other = dict(params, attn={**params["attn"], **{k: 3.0 * params["attn"][k][::-1] for k in ("q", "k", "v", "o")}})
    moved, moved_state = layer.apply(other, x, layer.initial_state())
    assert relative_rms(moved, out) > 1e-2
    assert np.array_equal(state["mlp"]["chosen"], moved_state["mlp"]["chosen"])
    # the attention's norm weight is what the router reads through
    normed = dict(params, attn={**params["attn"], "in_norm": jnp.roll(params["attn"]["in_norm"], 5)})
    _, normed_state = layer.apply(normed, x, layer.initial_state())
    assert not np.array_equal(state["mlp"]["chosen"], normed_state["mlp"]["chosen"])


@pytest.mark.parametrize("control", ["route_after", "swiglu"])
def test_a_changed_equation_fails_the_comparison(model, control):
    """The reference with the router after the attention (Trinity's
    placement) or with SiLU experts reads far from the system, where the
    unchanged reference reads 1e-6: the comparison tells this model from
    its neighbour."""
    from benchmark.drivers.oo_smallthinker_searcher import EQUATION_CONTROLS

    net, policy, flat = model
    ids = jax.random.randint(jax.random.key(3), (STEPS,), 0, VOCAB)
    _, (got, _) = stepwise_dense(net, policy.unravel(flat), ids)
    s = sizes(**EQUATION_CONTROLS[control])
    want, _ = ref.forward(ref.unflatten(flat, s), ids, s)
    assert relative_rms(got, want) > 5e-2


def test_softmax_top_k_renormalised_is_a_softmax_over_the_chosen_logits():
    experts = SparseExperts(16, 4, 64, 6, score_func="softmax", post_norm=False, num_shared_experts=0)
    params = experts.init(jax.random.key(1))
    y = jax.random.normal(jax.random.key(2), (5, 16))
    acc = type("Acc", (), {"mm": lambda self, name, x, precision=None: x @ params[name].T})()
    chosen, weights = experts.route(acc, y)
    with jax.default_matmul_precision("highest"):
        logits = y @ params["router"].T
    everyone = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(everyone, chosen, axis=-1)
    assert np.allclose(weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    assert np.array_equal(np.sort(chosen, -1), np.sort(jax.lax.top_k(logits, 6)[1], -1))
    unnormed = SparseExperts(16, 4, 64, 6, score_func="softmax", route_norm=False, post_norm=False, num_shared_experts=0)
    assert np.allclose(unnormed.route(acc, y)[1], picked, atol=1e-6)


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_the_grouped_kernel_takes_the_activation(activation):
    """The held experts' kernel (``net/grouped.py``, interpret mode) against
    the plain ``ragged_dot`` form with the same activation; ReLU and SiLU
    give different sums, so the argument is what is compared."""
    lanes, dim, width, held, rank = 130, 128, 128, 4, 4
    keys = jax.random.split(jax.random.key(0), 12)
    center, factors = {}, {}
    for at, (name, fan_in, fan_out) in enumerate((("gate", dim, width), ("up", dim, width), ("down", width, dim))):
        center[name] = fan_in**-0.5 * jax.random.normal(keys[3 * at], (held, fan_in, fan_out))
        factors[name] = DeltaFactor(
            a=jax.random.normal(keys[3 * at + 1], (held, fan_in, rank)),
            b=0.1 * fan_in**-0.5 * jax.random.normal(keys[3 * at + 2], (held, fan_out, rank)),
        )
    z, y = jax.random.normal(keys[9], (lanes, rank)), jax.random.normal(keys[10], (lanes, dim))
    local = jnp.stack([jnp.arange(lanes) % held, jnp.full((lanes,), held + 1)], axis=1).astype(jnp.int32)
    weights = jax.random.uniform(keys[11], (lanes, 2), minval=0.2, maxval=1.0)
    layer = SparseExperts(dim, width, 8, 2, experts_held=range(held), num_shared_experts=0, activation=activation)
    with jax.default_matmul_precision("highest"):
        kernel, sizes_k, _ = grouped.held_experts(center, factors, z, y, local, weights, activation=activation, interpret=True)
        plain, sizes_p, _ = layer._experts_plain(center, factors, z, y, local, weights)
        other, _, _ = grouped.held_experts(
            center, factors, z, y, local, weights, activation="silu" if activation == "relu" else "relu", interpret=True
        )
    assert np.array_equal(sizes_k, sizes_p)
    assert relative_rms(kernel, plain) < 1e-4 and relative_rms(other, plain) > 1e-2


def _evaluated(*, steps=12, lanes=6, seed=3):
    env = TokenCopyEnv(4, 3, steps)
    problem = VecNE(env, decoder(steps=steps, vocab=4, layers=(0, 1)), eval_mode="budget", episode_length=steps,
                    store_solution_stats=False, seed=seed)
    values = trunk_batch(problem.policy, seeded(problem.policy), lanes=lanes, rank=2)
    problem.evaluate(SolutionBatch(problem, values=values))
    return problem


def test_budget_counts_the_pairs_routed_ahead():
    steps, lanes = 12, 6
    report = _evaluated(steps=steps, lanes=lanes).last_policy_report
    counters = {k: int(v) for k, v in report.items() if v.ndim == 0}
    # by hand: top 3 in each of two layers, every lane, every step
    assert counters["expert_pairs_routed_ahead"] == 3 * 2 * lanes * steps
    assert counters["expert_layer_steps"] == 2 * steps and 0 < counters["expert_pairs_held"] <= 3 * 2 * lanes * steps
    # a family that routes after its attention reports no such counter
    from tests.test_decoder import small_decoder

    trinity = VecNE(TokenCopyEnv(16, 3, 6), small_decoder(6), eval_mode="budget", episode_length=6, store_solution_stats=False)
    trinity.evaluate(SolutionBatch(trinity, values=trunk_batch(trinity.policy, seeded(trinity.policy), lanes=2, rank=2)))
    assert "expert_pairs_routed_ahead" not in trinity.last_policy_report


def test_the_router_runs_in_its_scope_before_the_attention():
    problem = VecNE(TokenCopyEnv(VOCAB, 3, 6), decoder(steps=6, layers=(0, 1)), eval_mode="budget", episode_length=6,
                    store_solution_stats=False)
    batch = trunk_batch(problem.policy, seeded(problem.policy), lanes=4, rank=2)
    text = problem.lower_evaluation(4, like=batch).compile().as_text()
    outer = instruction_scopes(text, inherit=False)
    inner = instruction_scopes(text, inherit=False, names=FORWARD_SCOPES)
    named = {name: scope for name, scope in inner.items() if scope is not None}
    assert {"fwd_router", "fwd_attention", "fwd_experts", "fwd_head"} <= set(named.values())
    assert all(outer[name] == "policy_forward" for name in named)
    # the router's ops and the attention's are siblings in the layer: neither scope holds the other
    paths = re.findall(r'op_name="([^"]*evotorch_tpu\.fwd_router[^"]*)"', text)
    assert paths and not any("fwd_attention" in path for path in paths)


# sha256 of the lowered evaluation program (StableHLO: no op_name, no location) of a small Trinity, GLM and
# Kimi decoder as the library lowered them before the softmax router, the ReGLU experts and the route-ahead
# layer were added
OTHER_FAMILIES = {
    "afmoe": "c2e90af4598d3dbf7d17140cceb1575daeb6f468b2b4f10f1243f49e5d3bd321",
    "glm4_moe_lite": "ad0773b0db8453cb8209fa63c28c5c5faa5d7f376ee55a1fef0981814a6f10d6",
    "kimi_linear": "86b1f3d4eda2639eb5bbb20af76687a137447d3f9037b9ca272ba4373e79f2f2",
}


def _other_family(name):
    from tests import test_decoder, test_kimi_linear

    if name == "afmoe":
        return test_decoder.decoder(steps=6, layers=(0, 4))
    if name == "glm4_moe_lite":
        return test_kimi_linear._other_family(name)
    return test_kimi_linear.decoder(steps=6, vocab=16, layers=(0, 3))


@pytest.mark.parametrize("family", sorted(OTHER_FAMILIES))
def test_the_other_expert_decoders_lower_to_the_same_program(family):
    """``SparseExperts``, ``GatedAttention`` and the grouped kernel changed:
    the evaluation programs of the families that route after their
    attention with sigmoid scores and SwiGLU experts are the same program,
    operation for operation."""
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
        (dict(moe_primary_router_apply_softmax=False), "softmax"),
        (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (dict(rope_layout=LAYOUT[:-1]), "rope_layout"),
        (dict(sliding_window_layout=[2] * len(LAYOUT)), "sliding_window_layout"),
    ],
)
def test_the_constructor_refuses_what_it_does_not_implement(refused, match):
    with pytest.raises(ValueError, match=match):
        SmallThinkerDecoder(**{**MODEL, **refused}, moe_num_primary_experts=EXPERTS, num_hidden_layers=len(LAYOUT),
                            vocab_size=FULL_VOCAB, max_positions=8)


@pytest.mark.parametrize(
    "refused, match",
    [(dict(score_func="tanh"), "score_func"), (dict(activation="gelu"), "activation")],
)
def test_the_expert_layer_refuses_what_it_does_not_implement(refused, match):
    with pytest.raises(ValueError, match=match):
        SparseExperts(16, 4, 8, 2, **refused)


@pytest.mark.parametrize("score_func, has_bias", [("sigmoid", True), ("softmax", False)])
def test_the_selection_bias_is_a_leaf_of_the_sigmoid_router_alone(score_func, has_bias):
    """Every sigmoid family selects with a bias; the softmax family has no
    such leaf, so ES perturbs none."""
    params = SparseExperts(16, 4, 8, 2, score_func=score_func, num_shared_experts=0).init(jax.random.key(0))
    assert ("expert_bias" in params) == has_bias


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as error:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
def test_a_route_ahead_layers_step_compiles_for_v5e(v5e):
    """The real TPU compiler, Mosaic included, on one route-ahead layer at
    the cell's widths (hidden 2,560, 28 heads over 4, 16 of 64 ReGLU experts
    of 768, top 6; 512 lanes, rank 4, bfloat16): the step holds the grouped
    kernel once, in the experts' scope, the width walked as one tile, and no
    ``ragged-dot``."""
    from jax.sharding import SingleDeviceSharding

    lanes, rank, bf16 = 512, 4, jnp.bfloat16
    attention = GatedAttention(2560, 28, 4, 128, slots=128, rope_theta=1.5e6, eps=1e-6, gate=False, qk_norm=False, post_norm=False)
    experts = SparseExperts(2560, 768, 64, 6, experts_held=range(16), num_shared_experts=0, score_func="softmax",
                            eps=1e-6, post_norm=False, activation="relu")
    layer = RouteAheadLayer(attention, experts)
    policy = FlatParamsPolicy(layer)
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, dtype or leaf.dtype, sharding=one_chip), tree
        )

    flat = jax.ShapeDtypeStruct((policy.parameter_count,), jnp.float32)
    center = on_chip(jax.eval_shape(policy.unravel, flat), bf16)
    factors = on_chip(
        jax.eval_shape(lambda sigma: sample_trunk_delta_factors(jax.random.key(0), policy, sigma, rank), flat), bf16
    )
    state = on_chip(jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda s: jnp.zeros((lanes,) + s.shape, bf16 if s.dtype == jnp.float32 else s.dtype), layer.initial_state()
    )))
    z, x = on_chip(jax.ShapeDtypeStruct((lanes, rank), bf16)), on_chip(jax.ShapeDtypeStruct((lanes, 2560), bf16))
    step = jax.jit(lambda center, factors, z, x, state: layer.trunk_delta_apply(center, factors, z, x, state))
    text = step.trace(center, factors, z, x, state).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and grouped.KERNEL_NAME in calls[0] and "fwd_experts" in calls[0]
    assert "ragged-dot" not in text
    assert grouped._width_tile(2560, 768, 2) == 768  # the width as one tile
