"""The sparse-expert decoders as policies (``net/decoder.py``), the token
environment, and the factor-only trunk-delta batch, at a small size on the
CPU, seeded random weights, each against the benchmark's own plain reference:

- ``afmoe`` (``benchmark/reference/afmoe_decoder.py``): hidden 64, 4 heads /
  2 KV heads x 16, 8 experts top-2 + 1 shared, window 8, vocabulary 64, 1
  dense + 4 sparse layers ``s, s, s, f``;
- ``glm4_moe_lite`` (``benchmark/reference/glm4_moe_lite_decoder.py``): the
  published RATIOS kept (``qk_nope != v``, ``qk_rope < qk_nope``, 8 of 64
  experts held, top 4): hidden 64, 4 heads, ranks 24 / 16, head dims 12 | 4
  and 16, 1 dense + 2 sparse layers.
"""

import importlib.util
import json
import math
import os
import re
import types

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu import SolutionBatch
from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.algorithms.functional import pgpe, pgpe_ask_trunk_delta, pgpe_tell_trunk_delta
from evotorch_tpu.distributions import SymmetricSeparableGaussian
from evotorch_tpu.envs.tokens import TokenCopyEnv
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.neuroevolution.net import LSTM, Linear, Tanh
from evotorch_tpu.neuroevolution.net import decoder as decoder_module
from evotorch_tpu.neuroevolution.net.decoder import (
    AfmoeDecoder,
    Glm4MoeLiteDecoder,
    LatentAttention,
    SparseExperts,
    _Dense,
    stepwise_logits,
)
from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors
from evotorch_tpu.neuroevolution.net.vecrl import run_vectorized_rollout
from evotorch_tpu.observability.scopes import FORWARD_SCOPES, instruction_scopes
from evotorch_tpu.tools.lowrank import (
    LowRankParamsBatch,
    TrunkDeltaParamsBatch,
    basis_capture,
    factor_leaves,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("benchmark/reference/afmoe_decoder.py", "afmoe_reference")
glm_ref = _load("benchmark/reference/glm4_moe_lite_decoder.py", "glm4_moe_lite_reference")

MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts_per_tok=2,
    num_shared_experts=1, num_dense_layers=1,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    sliding_window=8, rope_theta=10000.0, route_scale=2.826, route_norm=True,
    score_func="sigmoid", rms_norm_eps=1e-5, mup_enabled=True,
)
PUBLISHED = {"num_experts": 8, "vocab_size": 64, "num_hidden_layers": 5}
GLM_MODEL = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts_per_tok=4,
    n_shared_experts=1, first_k_dense_replace=1, routed_scaling_factor=1.8,
    norm_topk_prob=True, topk_method="noaux_tc", n_group=1, topk_group=1,
    rope_theta=1000000.0, rope_scaling=None, rms_norm_eps=1e-5,
)
GLM_PUBLISHED = {"n_routed_experts": 64, "vocab_size": 64, "num_hidden_layers": 6}
STEPS = 20  # afmoe's 8-slot ring wraps twice
VOCAB = 48


def decoder(*, experts_held=(2, 6), steps=STEPS, vocab=VOCAB, layers=(0, 1, 2, 3, 4)):
    return AfmoeDecoder(
        **MODEL, num_experts=8, vocab_size=64, max_positions=steps,
        layers_held=list(layers), experts_held=range(*experts_held), vocab_held=vocab,
    )


def small_decoder(steps):
    """The dense layer and the full-attention sparse layer: what the searcher
    and the engine need of the model, at a quarter of the compile."""
    return decoder(steps=steps, layers=(0, 4))


def sizes(*, experts_held=(2, 6), vocab=VOCAB, **changed):
    config = dict(
        MODEL, published=PUBLISHED, layers_held=[0, 1, 2, 3, 4], kept_sparse_layers=4,
        experts_held=list(experts_held), vocab_held=vocab,
    )
    return {**ref.sizes(config), **changed}


def glm_decoder(*, experts_held=(8, 16), steps=STEPS, vocab=VOCAB, layers=(0, 1, 2)):
    return Glm4MoeLiteDecoder(
        **GLM_MODEL, n_routed_experts=64, vocab_size=64, num_hidden_layers=6, max_positions=steps,
        layers_held=list(layers), experts_held=range(*experts_held), vocab_held=vocab,
    )


def glm_sizes(*, experts_held=(8, 16), vocab=VOCAB, **changed):
    config = dict(
        GLM_MODEL, published=GLM_PUBLISHED, layers_held=[0, 1, 2], kept_sparse_layers=2,
        experts_held=list(experts_held), vocab_held=vocab,
    )
    return {**glm_ref.sizes(config), **changed}


# what a test needs of a family: its decoder and the reference's sizes (both
# take ``experts_held``, ``vocab``; the decoder ``steps``), a two-layer
# decoder, the plain reference, and the counts the assertions speak of
FAMILIES = {
    "afmoe": types.SimpleNamespace(
        name="afmoe", ref=ref, decoder=decoder, sizes=sizes, small_decoder=small_decoder,
        layers=5, sparse_layers=4, top_k=2, caches=("k", "v"),
    ),
    "glm4_moe_lite": types.SimpleNamespace(
        name="glm4_moe_lite", ref=glm_ref, decoder=glm_decoder, sizes=glm_sizes,
        small_decoder=lambda steps: glm_decoder(steps=steps, layers=(0, 1)),
        layers=3, sparse_layers=2, top_k=4, caches=("c", "kr"),
    ),
}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]


def seeded(policy, seed=1):
    flat = policy.init_parameters(jax.random.key(seed))
    # norms away from 1 and expert biases away from 0, so that each matters
    return flat + 0.05 * jax.random.normal(jax.random.key(seed + 1), flat.shape)


def relative_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2)))


def stepwise_dense(net, params, ids):
    @jax.jit
    def run(params, ids):
        def step(state, token):
            logits, state = net.apply(params, token[None], state)
            return state, logits

        state, logits = jax.lax.scan(step, net.initial_state(), ids)
        return logits, state

    return run(params, ids)


@pytest.fixture(scope="module")
def model(family):
    net = family.decoder()
    policy = FlatParamsPolicy(net)
    flat = seeded(policy)
    ids = jax.random.randint(jax.random.key(3), (STEPS,), 0, VOCAB)
    return net, policy, flat, ids


def trunk_batch(policy, flat, lanes=6, rank=3, seed=4):
    sigma = jnp.full((policy.parameter_count,), 0.05)
    factors = sample_trunk_delta_factors(jax.random.key(seed), policy, sigma, rank)
    z = jax.random.normal(jax.random.key(seed + 1), (lanes, rank))
    return TrunkDeltaParamsBatch(center=flat, coeffs=z, factors=factors)


# -- the model against the plain reference -------------------------------------


def test_parameter_layout_is_the_references(family, model):
    _, policy, flat, _ = model
    s = family.sizes()
    assert policy.parameter_count == family.ref.parameter_count(s)
    mine = policy.unravel(flat)
    theirs = family.ref.unflatten(flat, s)
    assert np.array_equal(mine["layers"][2]["mlp"]["experts"]["down"], theirs["layers"][2]["mlp"]["experts"]["down"])
    assert np.array_equal(mine["layers"][0]["mlp"]["mlp"]["gate"], theirs["layers"][0]["mlp"]["mlp"]["gate"])
    assert np.array_equal(mine["head"], theirs["head"])
    if family.name == "glm4_moe_lite":  # a norm before a block only
        assert "post_norm" not in mine["layers"][0]["mlp"] and "post_norm" not in mine["layers"][2]["mlp"]
        assert np.array_equal(mine["layers"][1]["attn"]["kv_b"], theirs["layers"][1]["attn"]["kv_b"])


@pytest.mark.parametrize("steps", [1, STEPS])
def test_dense_apply_stepwise_equals_the_whole_sequence_reference(family, model, steps):
    """One position (no cache yet) and 20 positions through the cache
    (afmoe: the window's ring of 8 slots wraps twice, the full layer holds
    all 20; glm4_moe_lite: the absorbed form over the latent cache against
    the reference's plain form)."""
    net, policy, flat, ids = model
    got, _ = stepwise_dense(net, policy.unravel(flat), ids[:steps])
    want, _ = family.ref.forward(family.ref.unflatten(flat, family.sizes()), ids[:steps], family.sizes())
    assert relative_rms(got, want) < 1e-5


def test_trunk_delta_forward_equals_dense_apply_on_materialised_rows(family, model):
    net, policy, flat, _ = model
    batch = trunk_batch(policy, flat, lanes=3)
    ids = jax.random.randint(jax.random.key(6), (batch.popsize, STEPS), 0, VOCAB)
    got, routes = jax.jit(lambda b, i: stepwise_logits(policy, b, i))(batch, ids)
    assert routes.shape == (STEPS, family.sparse_layers, batch.popsize, family.top_k)
    dense = batch.materialize()
    s = family.sizes()
    for lane in range(batch.popsize):
        want, _ = stepwise_dense(net, policy.unravel(dense[lane]), ids[lane])
        assert relative_rms(got[lane], want) < 1e-5
        theirs, chosen = family.ref.forward(family.ref.unflatten(dense[lane], s), ids[lane], s)
        assert relative_rms(got[lane], theirs) < 1e-5
        assert np.array_equal(np.sort(routes[:, 0, lane], -1), np.sort(chosen[0], -1))


def test_a_bfloat16_run_fails_the_float32_tolerance(family, model):
    _, policy, flat, _ = model
    batch = trunk_batch(policy, flat, lanes=3)
    ids = jax.random.randint(jax.random.key(6), (batch.popsize, STEPS), 0, VOCAB)
    got, _ = jax.jit(lambda b, i: stepwise_logits(policy, b, i, compute_dtype=jnp.bfloat16))(batch, ids)
    s = family.sizes()
    want, _ = family.ref.forward(family.ref.unflatten(batch.materialize()[0], s), ids[0], s)
    assert 1e-3 < relative_rms(got[0], want) < 0.2  # the tolerance bites, the model still agrees


def _afmoe_mutation(mutation, s, params, monkeypatch):
    if mutation == "no_route_scale":
        s["route_scale"] = 1.0
    elif mutation == "one_expert_fewer":
        s["top_k"] = 1
    elif mutation == "no_shared_expert":
        for layer in params["layers"].values():
            layer["mlp"].pop("shared", None)
    elif mutation == "rope_on_the_full_layer":
        s["layer_types"] = ["sliding_attention"] * 5
        s["window"] = STEPS  # positions on every layer, the window aside
    elif mutation == "no_window":
        s["window"] = STEPS


def _glm_mutation(mutation, s, params, monkeypatch):
    """Each changes one equation of the REFERENCE (through its sizes, its
    parameters, or one of its functions wrapped)."""
    r = glm_ref
    if mutation == "scale_from_the_no_position_dims_alone":  # 1/sqrt(192), not 1/sqrt(192 + 64)
        for layer in params["layers"].values():
            layer["attn"]["q_b"] = layer["attn"]["q_b"] * math.sqrt((s["nope"] + s["rope"]) / s["nope"])
    elif mutation == "rope_on_the_no_position_dims":
        queries, keys = r.queries, r.keys_and_values

        def roped_queries(p, x, s, positions):
            q_n, q_r = queries(p, x, s, positions)
            return r.rope(q_n, positions, s["theta"]), q_r

        def roped_keys(p, x, s, positions):
            k_n, k_r, v = keys(p, x, s, positions)
            return r.rope(k_n, positions, s["theta"]), k_r, v

        monkeypatch.setattr(r, "queries", roped_queries)
        monkeypatch.setattr(r, "keys_and_values", roped_keys)
    elif mutation == "no_kv_a_layernorm":
        rms = r.rms
        monkeypatch.setattr(r, "rms", lambda x, w, eps: x if w.shape == (s["kv_rank"],) else rms(x, w, eps))
    elif mutation == "a_rope_key_per_head":  # head h reads the shared key's dims rolled by h
        keys = r.keys_and_values

        def keys_per_head(p, x, s, positions):
            k_n, k_r, v = keys(p, x, s, positions)
            return k_n, jnp.concatenate([jnp.roll(k_r, h, axis=-1) for h in range(s["heads"])], axis=1), v

        monkeypatch.setattr(r, "keys_and_values", keys_per_head)
    elif mutation == "no_routed_scaling_factor":
        s["route_scale"] = 1.0
    elif mutation == "a_bias_that_weighs":
        route = r.route

        def weighed(p, y, s, forced=None):
            chosen, used, _ = route(p, y, s, forced)
            scores = jnp.take_along_axis(jax.nn.sigmoid(y @ p["router"].T) + p["expert_bias"], used, axis=-1)
            return chosen, used, s["route_scale"] * scores / jnp.sum(scores, axis=-1, keepdims=True)

        monkeypatch.setattr(r, "route", weighed)
    elif mutation == "three_experts_instead_of_four":
        s["top_k"] = 3
    elif mutation == "no_shared_expert":
        for layer in params["layers"].values():
            layer["mlp"].pop("shared", None)
    elif mutation == "a_norm_after_the_block":
        attention = r.attention
        monkeypatch.setattr(
            r, "attention", lambda p, h, s, positions=None: h + r.rms(attention(p, h, s, positions) - h, 1.0, s["eps"])
        )


MUTATIONS = [("afmoe", m) for m in (
    "no_route_scale", "one_expert_fewer", "no_shared_expert", "rope_on_the_full_layer", "no_window",
)] + [("glm4_moe_lite", m) for m in (
    "scale_from_the_no_position_dims_alone", "rope_on_the_no_position_dims", "no_kv_a_layernorm",
    "a_rope_key_per_head", "no_routed_scaling_factor", "a_bias_that_weighs", "three_experts_instead_of_four",
    "no_shared_expert", "a_norm_after_the_block",
)]


@pytest.mark.parametrize("family, mutation", MUTATIONS, indirect=["family"])
def test_the_comparison_catches_a_changed_equation(family, model, mutation, monkeypatch):
    """Against 1e-5 where nothing is changed (the tests above)."""
    net, policy, flat, ids = model
    got, _ = stepwise_dense(net, policy.unravel(flat), ids)
    s, params = family.sizes(), family.ref.unflatten(flat, family.sizes())
    {"afmoe": _afmoe_mutation, "glm4_moe_lite": _glm_mutation}[family.name](mutation, s, params, monkeypatch)
    want, _ = family.ref.forward(params, ids, s)
    # glm4_moe_lite has no norm after a block to bring a block's change back to
    # the stream's size, and holds 8 of 64 experts: its mutations read 6e-3
    # (a bias of 0.05 that weighs) to 1.1, still hundreds of times the 1e-5
    # the comparisons above hold
    assert relative_rms(got, want) > (2e-2 if family.name == "afmoe" else 3e-3)


@pytest.mark.parametrize("family", list(FAMILIES), indirect=True)
def test_the_shares_of_a_layers_experts_add_up_to_the_uncut_layer(family):
    """afmoe: four chips hold two experts each of one expert layer of 8;
    glm4_moe_lite: eight chips hold eight each of 64, top 4. What each share
    adds (its held experts' terms), with the shared expert counted once, is
    what the uncut reference layer gives before it joins the residual (afmoe:
    before its closing norm)."""
    r = family.ref
    if family.name == "afmoe":
        experts, top_k, share_of = 8, 2, 2
        kwargs = dict(route_scale=2.826)
    else:
        experts, top_k, share_of = 64, 4, 8
        kwargs = dict(route_scale=1.8, post_norm=False, route_norm_eps=1e-20)
    whole = SparseExperts(64, 32, experts, top_k, num_shared_experts=1, **kwargs)
    params = whole.init(jax.random.key(0))
    params["expert_bias"] = 0.1 * jax.random.normal(jax.random.key(1), (experts,))
    x = jax.random.normal(jax.random.key(2), (5, 64))
    s = family.sizes(experts_held=(0, experts))
    y = r.rms(x, params["in_norm"], s["eps"])
    with jax.default_matmul_precision("highest"):
        chosen, used, weights = r.route(params, y, s)
        uncut = r.held_experts(params["experts"], y, used, weights, 0) + r.swiglu(params["shared"], y)
    total = r.swiglu(params["shared"], y)
    for first in range(0, experts, share_of):
        share = SparseExperts(64, 32, experts, top_k, experts_held=range(first, first + share_of), **kwargs)
        held = {k: v[first : first + share_of] for k, v in params["experts"].items()}
        for token in range(x.shape[0]):
            ids, w = share.route(_Dense({**params}), y[token][None])
            assert np.array_equal(ids[0], chosen[token])  # every share routes over all the experts
            term, _ = share._experts_dense(held, y[token][None], ids, w)
            total = total.at[token].add(term[0])
    assert relative_rms(total, uncut) < 1e-5
    if family.name == "glm4_moe_lite":  # and the layer itself, uncut, is the reference's
        got, _ = jax.vmap(lambda row: whole.apply(params, row, None))(x)
        with jax.default_matmul_precision("highest"):
            want, _ = r.sparse_mlp(params, x, s)
        assert relative_rms(got, want) < 1e-5


# -- what only latent attention has --------------------------------------------


def test_absorbed_attention_equals_the_plain_form():
    """One ``LatentAttention`` stepped through its latent cache, the
    up-projections folded into query and output, against the reference's
    attention, which writes every position's per-head keys and values out."""
    s = glm_sizes()
    layer = LatentAttention(
        s["hidden"], s["heads"], q_rank=s["q_rank"], kv_rank=s["kv_rank"], nope_dim=s["nope"],
        rope_dim=s["rope"], v_dim=s["v"], slots=STEPS, rope_theta=s["theta"], eps=s["eps"],
    )
    params = jax.tree_util.tree_map(
        lambda leaf: leaf + 0.05 * jax.random.normal(jax.random.key(leaf.size), leaf.shape),
        layer.init(jax.random.key(0)),
    )
    h = jax.random.normal(jax.random.key(1), (STEPS, s["hidden"]))

    @jax.jit
    def stepped(params, h):
        def step(state, row):
            out, state = layer.apply(params, row, state)
            return state, out

        return jax.lax.scan(step, layer.initial_state(), h)

    state, got = stepped(params, h)
    with jax.default_matmul_precision("highest"):
        want = glm_ref.attention(params, h, s)
    assert relative_rms(got - h, want - h) < 1e-5
    # the state: one compressed row and one shared RoPE key a position, nothing per head
    shapes = {name: leaf.shape for name, leaf in state.items()}
    assert shapes == {"c": (STEPS, s["kv_rank"]), "kr": (STEPS, s["rope"]), "t": (), "step": (), "read": (), "fetched": ()}
    assert int(state["read"]) == STEPS * (STEPS + 1) // 2


@pytest.mark.parametrize(
    "refused, match",
    [
        (dict(n_group=2), "n_group"),
        (dict(topk_group=2), "topk_group"),
        (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "rope_scaling"),
        (dict(topk_method="greedy"), "topk_method"),
    ],
)
def test_the_glm_constructor_refuses_what_it_does_not_implement(refused, match):
    with pytest.raises(ValueError, match=match):
        Glm4MoeLiteDecoder(
            **{**GLM_MODEL, **refused}, n_routed_experts=64, vocab_size=64, num_hidden_layers=6, max_positions=4
        )


@pytest.mark.parametrize("crowded", [False, True])
@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_the_grouped_product_drops_no_pair(form, crowded, monkeypatch):
    """512 lanes on a chip that holds 1 expert of 8: a quarter of the lanes
    hit it, or (with a router that sends every lane there) all 512, the whole
    of the product's room and four row tiles of the kernel. Either way every
    lane equals its own dense apply, in XLA's plain form (which the CPU gets)
    and in the kernel of ``net/grouped.py`` (interpreted here, at widths it
    takes)."""
    dim, width = (64, 32) if form == "plain" else (128, 128)
    if form == "kernel":
        monkeypatch.setattr(
            decoder_module, "_by_platform", lambda fused, plain, *args: fused(*args, interpret=True)
        )
    layer = SparseExperts(dim, width, 8, 2, experts_held=range(2, 3), route_scale=2.826)
    policy = FlatParamsPolicy(layer)
    flat = seeded(policy)
    params = policy.unravel(flat)
    if crowded:
        params["expert_bias"] = params["expert_bias"].at[2].set(10.0)
        flat = jax.flatten_util.ravel_pytree(params)[0]
    batch = trunk_batch(policy, flat, lanes=512, rank=2)
    x = jax.random.normal(jax.random.key(9), (512, dim))
    got, state = jax.jit(
        lambda b, x: layer.trunk_delta_apply(policy.unravel(b.center), b.factors, b.coeffs, x, None)
    )(batch, x)
    hits = int(jnp.sum(state["hits"]))
    assert (hits == 512) if crowded else (0 < hits <= 256)
    assert int(state["fullest"][0]) == hits  # one held expert: it is the fullest
    # the kernel walks the expert's lanes in tiles of 128 rows; the plain form has none
    assert int(state["tiles"][0]) == (0 if form == "plain" else -(-hits // 128))
    want, _ = jax.jit(jax.vmap(lambda p, x: layer.apply(policy.unravel(p), x, None)))(batch.materialize(), x)
    assert relative_rms(got, want) < 1e-5


# -- the searcher on factors alone ---------------------------------------------


def materialised_basis(batch):
    """The old algebra's ``(L, k)`` basis, column m the concatenation of the
    leaves' rank-1 blocks: kept here, as a test helper, and nowhere else."""
    columns = [f.delta(jnp.eye(batch.rank)).reshape(batch.rank, -1) for _, f in factor_leaves(batch.factors)]
    return jnp.concatenate(columns, axis=1).T


@pytest.mark.parametrize("kind", ["linear", "lstm", "experts", "latent"])
def test_gradients_from_factors_equal_the_materialised_basis_algebra(kind):
    module = {
        "linear": lambda: Linear(7, 5) >> Tanh() >> Linear(5, 3),
        "lstm": lambda: LSTM(6, 4) >> Linear(4, 2),
        "experts": lambda: small_decoder(4),
        "latent": lambda: FAMILIES["glm4_moe_lite"].small_decoder(4),
    }[kind]()
    policy = FlatParamsPolicy(module)
    length = policy.parameter_count
    mu = jax.random.normal(jax.random.key(0), (length,))
    sigma = 0.1 + jax.random.uniform(jax.random.key(1), (length,))
    rank, popsize = 3, 12
    factors = sample_trunk_delta_factors(jax.random.key(2), policy, sigma, rank)
    parameters = {"mu": mu, "sigma": sigma, "divide_mu_grad_by": "num_directions", "divide_sigma_grad_by": "num_directions"}
    batch = SymmetricSeparableGaussian._sample_trunk_delta(jax.random.key(3), parameters, popsize, rank, factors)
    weights = jax.random.normal(jax.random.key(4), (popsize,))
    got = SymmetricSeparableGaussian._compute_gradients(parameters, batch, weights, "raw")
    basis = materialised_basis(batch)
    assert basis.shape == (length, rank)
    old = LowRankParamsBatch(center=mu, basis=basis, coeffs=batch.coeffs)
    want = SymmetricSeparableGaussian._compute_gradients_lowrank(parameters, old, weights, "raw")
    for name in ("mu", "sigma"):
        assert relative_rms(got[name], want[name]) < 1e-5, name
    # and against the dense estimator on the materialised population
    dense = SymmetricSeparableGaussian._compute_gradients(parameters, batch.materialize(), weights, "raw")
    assert relative_rms(got["mu"], dense["mu"]) < 1e-4
    assert relative_rms(got["sigma"], dense["sigma"]) < 1e-3
    # the guardrail and the rows, from factors
    vector = jax.random.normal(jax.random.key(5), (length,))
    assert float(basis_capture(batch, vector)) == pytest.approx(float(basis_capture(basis, vector)), rel=1e-4)
    assert relative_rms(batch.materialize_rows(batch.coeffs[:2]), old.materialize_rows(batch.coeffs[:2])) < 1e-6


def test_functional_tell_and_oo_step_share_the_update(family):
    """The path a researcher calls, ``VecNE(..., eval_mode="budget")`` +
    ``PGPE(lowrank_rank=("trunk_delta", k))`` + ``step()``, for either
    family: the OO searcher's donated update equals the functional pair's on
    the same population and scores."""
    env = TokenCopyEnv(VOCAB, 3, 6)
    problem = VecNE(env, family.small_decoder(6), eval_mode="budget", episode_length=6, seed=2,
                    store_solution_stats=False, initial_bounds=None)
    flat = seeded(problem.policy)
    common = dict(center_learning_rate=0.3, stdev_learning_rate=0.1)
    searcher = PGPE(problem, popsize=8, stdev_init=0.02, center_init=flat, optimizer="clipup",
                    optimizer_config={"max_speed": 0.6}, lowrank_rank=("trunk_delta", 2), **common)
    searcher.step()
    population = searcher.population
    values, evals = population.values, population.evals[:, 0]
    state = pgpe(center_init=jnp.array(flat), stdev_init=0.02, objective_sense="max", optimizer="clipup",
                 optimizer_config={"max_speed": 0.6}, **common)
    told = pgpe_tell_trunk_delta(state, jax.tree_util.tree_map(jnp.array, values), jnp.array(evals))
    searcher.step()
    assert relative_rms(searcher.status["center"], told.optimizer_state.center) < 1e-6
    assert relative_rms(searcher.status["stdev"], told.stdev) < 1e-6
    # the evaluated population's center was donated (the searcher dropped that
    # population); the caller's center_init is the caller's still
    assert values.center.is_deleted() and not flat.is_deleted()


@pytest.mark.parametrize(
    "refused, match",
    [
        (dict(lowrank_rank=("trunk", 2)), "a rank or"),
        (dict(lowrank_rank=("trunk_delta", 0)), ">= 1"),
        (dict(lowrank_rank=("trunk_delta", 2), stdev_max_change=[0.2] * 8), "scalar"),
        (dict(lowrank_rank=("trunk_delta", 2), symmetric=False), "symmetric"),
    ],
)
def test_oo_pgpe_names_the_factored_form_in_one_argument(refused, match):
    """``lowrank_rank`` is the one argument: a rank (the dense basis), or the
    form's name with the rank; the trunk-delta form clamps with scalars."""
    problem = VecNE(TokenCopyEnv(VOCAB, 4, 8), small_decoder(8), eval_mode="budget", episode_length=8)
    with pytest.raises(ValueError, match=match):
        PGPE(problem, popsize=8, center_learning_rate=0.1, stdev_learning_rate=0.1, stdev_init=0.1, **refused)


def test_trunk_delta_batches_of_one_generation_concatenate():
    problem = VecNE(TokenCopyEnv(VOCAB, 4, 8), small_decoder(8), eval_mode="budget", episode_length=8,
                    store_solution_stats=False)
    policy = problem.policy
    flat = seeded(policy)
    dist = SymmetricSeparableGaussian({"mu": flat, "sigma": jnp.full(flat.shape, 0.1)})
    first = dist.sample_trunk_delta(4, 2, policy, key=jax.random.key(0))
    later = dist.sample_trunk_delta(6, 2, policy, key=jax.random.key(1), factors=first.factors)
    merged = SolutionBatch.cat([SolutionBatch(problem, values=first), SolutionBatch(problem, values=later)])
    assert merged.values.popsize == 10 and merged.values.factors is first.factors
    other = dist.sample_trunk_delta(6, 2, policy, key=jax.random.key(2))
    with pytest.raises(TypeError):
        SolutionBatch.cat([SolutionBatch(problem, values=first), SolutionBatch(problem, values=other)])


def test_no_array_of_basis_or_population_size_on_the_evaluation_path(family):
    """The lowered rollout of a trunk-delta population holds no ``(L, k)`` and
    no ``(N, L)`` array: its largest buffer is the flat center. With latent
    attention it also holds no lane's ``kv_b`` (whole, or a head's block of
    it) and no key or value per head: the cache is ``(lanes, slots,
    kv_lora_rank)`` and ``(lanes, slots, qk_rope_head_dim)``."""
    env = TokenCopyEnv(VOCAB, 4, 8)
    problem = VecNE(env, family.small_decoder(8), eval_mode="budget", episode_length=8, store_solution_stats=False)
    flat = seeded(problem.policy)
    batch = trunk_batch(problem.policy, flat, lanes=8, rank=4)
    text = problem.lower_evaluation(8, like=batch).as_text()
    length = problem.solution_length
    largest = max(
        int(np.prod([int(d) for d in dims.split("x")]))
        for dims in re.findall(r"tensor<((?:\d+x)+)\w+>", text)
        for dims in [dims.rstrip("x")]
    )
    assert largest == length
    if family.name == "glm4_moe_lite":
        g = GLM_MODEL
        heads, rank = g["num_attention_heads"], g["kv_lora_rank"]
        nope, rope_dim, v = g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
        shapes = set(re.findall(r"tensor<((?:\d+x)+)\w+>", text))
        written_out = {f"8x{heads * (nope + v)}x{rank}x", f"8x{heads}x{nope + v}x{rank}x",
                       f"8x{heads}x{nope}x{rank}x", f"8x{heads}x{v}x{rank}x"}
        assert not shapes & written_out
        assert {f"8x8x{rank}x", f"8x8x{rope_dim}x"} <= shapes  # lanes x slots x (512 | 64) at the real widths
        per_head = {f"8x8x{heads}x{d}x" for d in (nope, rope_dim, nope + rope_dim, v)}
        per_head |= {f"8x{heads}x8x{d}x" for d in (nope, rope_dim, nope + rope_dim, v)}
        assert not shapes & per_head
    searcher_text = jax.jit(
        lambda s, p, e: pgpe_tell_trunk_delta(s, p, e)
    ).lower(
        pgpe(center_init=flat, stdev_init=0.02, objective_sense="max", center_learning_rate=0.1,
             stdev_learning_rate=0.1, stdev_max_change=None),
        batch, jnp.zeros(8),
    ).as_text()
    assert f"tensor<{length}x4x" not in searcher_text and f"tensor<8x{length}x" not in searcher_text


# -- the environment and the engine --------------------------------------------


def test_token_copy_env_feeds_the_prompt_then_the_lanes_own_tokens():
    env = TokenCopyEnv(VOCAB, 3, 10)
    state, obs = env.reset(jax.random.key(0))
    prompt = np.asarray(state.obs_state)
    assert obs.dtype == jnp.int32 and obs.shape == (1,) and int(obs[0]) == prompt[0] and prompt.min() >= 1
    seen, rewards = [], []
    actions = [7, 7, int(prompt[0]), 5, int(prompt[2]), 0, 9]
    for action in actions:
        state, obs, reward, done = env.step(state, jnp.asarray(action))
        seen.append(int(obs[0])), rewards.append(float(reward))
        if bool(done):
            break
    # two forced tokens whatever the action, then the lane's own; id 0 ends it
    assert seen == [prompt[1], prompt[2], prompt[0], 5, prompt[2], 0]
    assert rewards == [0.0, 0.0, 1.0, 0.0, 1.0, 0.0]


@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16])
def test_budget_counts_are_exact_and_a_reset_lane_starts_clean(family, compute_dtype):
    steps, lanes = 12, 8
    # a vocabulary of two: every other emitted token is id 0 and ends an
    # episode, so lanes reset (cache and all) in the middle of their budget
    env = TokenCopyEnv(2, 3, steps)
    problem = VecNE(env, family.decoder(steps=steps, vocab=2), eval_mode="budget", episode_length=steps,
                    compute_dtype=compute_dtype, store_solution_stats=False, seed=1)
    flat = seeded(problem.policy)
    batch = SolutionBatch(problem, values=trunk_batch(problem.policy, flat, lanes=lanes, rank=2))
    problem.evaluate(batch)
    assert int(problem.status["total_interaction_count"]) == lanes * steps
    assert int(problem.status["total_episode_count"]) > lanes
    report = problem.last_policy_report
    counters = {k: int(v) for k, v in report.items() if v.ndim == 0}
    held = 4 if family.name == "afmoe" else 8
    assert counters["cache_slots_written"] == lanes * steps * family.layers
    assert counters["expert_layer_steps"] == steps * family.sparse_layers
    assert 0 < counters["expert_pairs_held"] <= lanes * steps * family.sparse_layers * family.top_k
    assert counters["expert_pairs_fullest"] * held >= counters["expert_pairs_held"]  # the fullest held >= the mean
    # the module's own reset: the ended lanes' cache and position are zero,
    # the write pointer and the other lanes are not
    net = problem.policy.module
    state = jax.tree_util.tree_map(lambda x: jnp.ones((3,) + x.shape, x.dtype), net.initial_state())
    after = net.reset_state(state, jnp.asarray([False, True, False]))
    attn = after["layers"][1]["attn"]
    for cache in family.caches:
        assert float(jnp.abs(attn[cache][1]).max()) == 0.0
        assert float(attn[cache][0].min()) == 1.0 and float(attn[cache][2].min()) == 1.0
    assert attn["t"].tolist() == [1, 0, 1] and attn["step"].tolist() == [1, 1, 1]
    assert after["t"].tolist() == [1, 0, 1]  # the decoder's own position, reset with the lane
    assert after["seen"]["ids"].tolist() == state["seen"]["ids"].tolist()  # the record outlives an episode
    # what the evaluation recorded: every lane's ids and positions, step by
    # step; a lane begins at 0, goes on by one or begins again; some did
    ids, positions = np.asarray(report["ids_seen"]), np.asarray(report["positions_seen"])
    assert ids.shape == positions.shape == (lanes, steps) and (positions[:, 0] == 0).all()
    assert ((positions[:, 1:] == positions[:, :-1] + 1) | (positions[:, 1:] == 0)).all()
    assert (positions[:, 1:] == 0).any() and set(np.unique(ids)) <= {0, 1}
    assert (ids[positions < 3] == 1).all()  # a prompt holds no id 0, and the vocabulary has two
    if family.name == "glm4_moe_lite":
        # the latent cache's floor is counted, not assumed: a lane at position t
        # could read t + 1 rows, in every layer, resets and all
        assert counters["latent_positions_read"] == int((positions + 1).sum()) * family.layers
        assert attn["read"].tolist() == [1, 1, 1]  # a count of the evaluation, not of an episode
    else:
        assert "latent_positions_read" not in counters


def test_an_evaluations_record_replays_to_the_tokens_it_emitted(family):
    """The ids a lane consumed (``last_policy_report``) are its prompt and
    then its own tokens: replayed teacher-forced with the record's resets,
    the stepwise forward puts first what the evaluation emitted, and the
    whole-sequence reference, given the episodes' positions, gives the same
    logits for a lane that began an episode midway."""
    steps, lanes, prompt = 16, 6, 3
    env = TokenCopyEnv(4, prompt, steps)  # a vocabulary of four: id 0 comes up, episodes end early
    problem = VecNE(env, family.decoder(steps=steps, vocab=4), eval_mode="budget", episode_length=steps,
                    store_solution_stats=False, seed=3)
    policy = problem.policy
    values = trunk_batch(policy, seeded(policy), lanes=lanes, rank=2)
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
    lane = int(midway[0])
    s = family.sizes(vocab=4)
    want, _ = family.ref.forward(
        family.ref.unflatten(values.materialize()[lane], s), ids[lane], s, positions=positions[lane]
    )
    assert relative_rms(logits[lane], want) < 1e-5
    # only some lanes' logits kept: the same numbers
    kept, routes = jax.jit(lambda b, i, p: stepwise_logits(policy, b, i, positions=p, lanes=jnp.asarray([lane])))(
        values, ids, positions
    )
    assert kept.shape == (1, steps, 4) and routes.shape == (steps, family.sparse_layers, 1, family.top_k)
    assert relative_rms(kept[0], logits[lane]) < 1e-6


# -- names inside the forward ---------------------------------------------------


def test_inner_scopes_sit_inside_policy_forward(family):
    """Every forward scope the family wears is there, inside
    ``policy_forward``; ``fwd_latent_cache`` is latent attention's alone and
    sits inside ``fwd_attention``."""
    problem = VecNE(TokenCopyEnv(VOCAB, 3, 6), family.small_decoder(6), eval_mode="budget", episode_length=6,
                    store_solution_stats=False)
    batch = trunk_batch(problem.policy, seeded(problem.policy), lanes=4, rank=2)
    text = problem.lower_evaluation(4, like=batch).compile().as_text()
    outer = instruction_scopes(text, inherit=False)
    inner = instruction_scopes(text, inherit=False, names=FORWARD_SCOPES)
    named = {name: scope for name, scope in inner.items() if scope is not None}
    # a recurrent mixer's (tests/test_decoder_ssm.py) and a gated delta-rule block's (tests/test_kimi_linear.py)
    worn = set(FORWARD_SCOPES) - {"fwd_ssm", "fwd_ssm_state", "fwd_kda", "fwd_kda_state"}
    worn -= {"fwd_latent_cache"} if family.name == "afmoe" else set()
    assert set(named.values()) == worn
    assert all(outer[name] == "policy_forward" for name in named)  # the outermost name stays
    paths = re.findall(r'op_name="([^"]*evotorch_tpu\.fwd_latent_cache[^"]*)"', text)
    assert bool(paths) == (family.name == "glm4_moe_lite")
    assert all(
        re.search(r"evotorch_tpu\.policy_forward/.*evotorch_tpu\.fwd_attention/.*evotorch_tpu\.fwd_latent_cache", path)
        for path in paths
    )


def test_the_floors_count_what_the_configuration_says():
    floors = _load("benchmark/harness/lm_floors.py", "lm_floors")
    with open(os.path.join(ROOT, "benchmark/configs/trinity_mini_ep8.json")) as f:
        config = json.load(f)
    s = ref.sizes(config)
    assert ref.parameter_count(s) == config["parameter_count"] == 705_474_304
    assert floors.step_macs_per_lane(s) == pytest.approx(276.6e6, rel=2e-3)
    assert floors.expert_bytes_per_step(s, 2) == 4 * 17 * 3 * 2048 * 1024 * 2
    assert floors.cache_bytes_per_step(s, 512, 256, 2) == pytest.approx(0.5 * 1.342e9 * 257 / 256, rel=1e-3)
    assert floors.cache_shape(s, 512, 256) == {"[512,4,256,128]"}


def test_the_latent_floors_count_what_the_configuration_says():
    floors = _load("benchmark/harness/mla_floors.py", "mla_floors")
    with open(os.path.join(ROOT, "benchmark/configs/glm47_flash_ep8.json")) as f:
        config = json.load(f)
    s = glm_ref.sizes(config)
    assert glm_ref.parameter_count(s) == config["parameter_count"] == 591_294_976
    # a layer's attention matrices (ISSUE 32's 21,759,232 less its two inner norms' 1,280)
    assert floors.attention_macs(s) == 1_572_864 + 3_932_160 + 1_179_648 + 4_587_520 + 10_485_760
    # 1,152 B and 20 x (576 + 512) multiply-adds a readable position
    assert floors.cache_bytes_per_step(s, 1, 2) == 1152 and floors.cache_flops_per_step(s, 1) == 2 * 20 * 1088
    positions = floors.expected_positions_per_step(s, 512, 512)
    assert positions == 5 * 512 * 256.5
    assert floors.cache_bytes_per_step(s, positions, 2) == pytest.approx(0.5 * 1.51e9 * 513 / 512, rel=1e-3)
    assert floors.expert_bytes_per_step(s, 2) == 4 * 9 * 3 * 2048 * 1536 * 2
    # projections 108.8M, the cache pass 27.9M, layer 0's MLP 62.9M, four sparse layers 57.1M, the head 39.6M
    assert floors.step_macs_per_lane(s, positions / 512) == pytest.approx(296.4e6, rel=2e-3)

