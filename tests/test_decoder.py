"""The sparse-expert decoder as a policy (``net/decoder.py``), the token
environment, and the factor-only trunk-delta batch, at a small size on the
CPU: hidden 64, 4 heads / 2 KV heads x 16, 8 experts top-2 + 1 shared, window
8, vocabulary 64, 1 dense + 4 sparse layers ``s, s, s, f``, seeded random
weights. The plain reference is the benchmark's own copy
(``benchmark/reference/afmoe_decoder.py``)."""

import importlib.util
import json
import os

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
    SparseExperts,
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

MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts_per_tok=2,
    num_shared_experts=1, num_dense_layers=1,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    sliding_window=8, rope_theta=10000.0, route_scale=2.826, route_norm=True,
    score_func="sigmoid", rms_norm_eps=1e-5, mup_enabled=True,
)
PUBLISHED = {"num_experts": 8, "vocab_size": 64, "num_hidden_layers": 5}
STEPS = 20  # the 8-slot ring wraps twice
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
def model():
    net = decoder()
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


def test_parameter_layout_is_the_references(model):
    _, policy, flat, _ = model
    s = sizes()
    assert policy.parameter_count == ref.parameter_count(s)
    mine = policy.unravel(flat)
    theirs = ref.unflatten(flat, s)
    assert np.array_equal(mine["layers"][2]["mlp"]["experts"]["down"], theirs["layers"][2]["mlp"]["experts"]["down"])
    assert np.array_equal(mine["layers"][0]["mlp"]["mlp"]["gate"], theirs["layers"][0]["mlp"]["mlp"]["gate"])
    assert np.array_equal(mine["head"], theirs["head"])


@pytest.mark.parametrize("steps", [1, STEPS])
def test_dense_apply_stepwise_equals_the_whole_sequence_reference(model, steps):
    """One position (no cache yet) and 20 positions through the cache (the
    window's ring of 8 slots wraps twice; the full layer holds all 20)."""
    net, policy, flat, ids = model
    got, _ = stepwise_dense(net, policy.unravel(flat), ids[:steps])
    want, _ = ref.forward(ref.unflatten(flat, sizes()), ids[:steps], sizes())
    assert relative_rms(got, want) < 1e-5


def test_trunk_delta_forward_equals_dense_apply_on_materialised_rows(model):
    net, policy, flat, _ = model
    batch = trunk_batch(policy, flat, lanes=3)
    ids = jax.random.randint(jax.random.key(6), (batch.popsize, STEPS), 0, VOCAB)
    got, routes = jax.jit(lambda b, i: stepwise_logits(policy, b, i))(batch, ids)
    assert routes.shape == (STEPS, 4, batch.popsize, 2)
    dense = batch.materialize()
    for lane in range(batch.popsize):
        want, _ = stepwise_dense(net, policy.unravel(dense[lane]), ids[lane])
        assert relative_rms(got[lane], want) < 1e-5
        theirs, chosen = ref.forward(ref.unflatten(dense[lane], sizes()), ids[lane], sizes())
        assert relative_rms(got[lane], theirs) < 1e-5
        assert np.array_equal(np.sort(routes[:, 0, lane], -1), np.sort(chosen[0], -1))


def test_a_bfloat16_run_fails_the_float32_tolerance(model):
    _, policy, flat, _ = model
    batch = trunk_batch(policy, flat, lanes=3)
    ids = jax.random.randint(jax.random.key(6), (batch.popsize, STEPS), 0, VOCAB)
    got, _ = jax.jit(lambda b, i: stepwise_logits(policy, b, i, compute_dtype=jnp.bfloat16))(batch, ids)
    want, _ = ref.forward(ref.unflatten(batch.materialize()[0], sizes()), ids[0], sizes())
    assert 1e-3 < relative_rms(got[0], want) < 0.2  # the tolerance bites, the model still agrees


@pytest.mark.parametrize(
    "mutation",
    ["no_route_scale", "one_expert_fewer", "no_shared_expert", "rope_on_the_full_layer", "no_window"],
)
def test_the_comparison_catches_a_changed_equation(model, mutation):
    net, policy, flat, ids = model
    got, _ = stepwise_dense(net, policy.unravel(flat), ids)
    s, params = sizes(), ref.unflatten(flat, sizes())
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
    want, _ = ref.forward(params, ids, s)
    assert relative_rms(got, want) > 2e-2


def test_the_shares_of_a_layers_experts_add_up_to_the_uncut_layer():
    """Four chips hold two experts each of one expert layer. What each share
    adds (its held experts' terms), with the shared expert counted once, is
    what the uncut reference layer gives before its closing norm."""
    whole = SparseExperts(64, 32, 8, 2, num_shared_experts=1, route_scale=2.826)
    params = whole.init(jax.random.key(0))
    params["expert_bias"] = 0.1 * jax.random.normal(jax.random.key(1), (8,))
    x = jax.random.normal(jax.random.key(2), (5, 64))
    s = sizes(experts_held=(0, 8))
    y = ref.rms(x, params["in_norm"], s["eps"])
    with jax.default_matmul_precision("highest"):
        chosen, used, weights = ref.route(params, y, s)
        uncut = ref.held_experts(params["experts"], y, used, weights, 0) + ref.swiglu(params["shared"], y)
    total = ref.swiglu(params["shared"], y)
    for first in (0, 2, 4, 6):
        share = SparseExperts(64, 32, 8, 2, experts_held=range(first, first + 2), route_scale=2.826)
        held = {k: v[first : first + 2] for k, v in params["experts"].items()}
        for token in range(x.shape[0]):
            ids, w = share.route(_Dense({**params}), y[token][None])
            assert np.array_equal(ids[0], chosen[token])  # every share routes over all 8
            term, _ = share._experts_dense(held, y[token][None], ids, w)
            total = total.at[token].add(term[0])
    assert relative_rms(total, uncut) < 1e-5


from evotorch_tpu.neuroevolution.net.decoder import _Dense  # noqa: E402  (the one-lane accessor)


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


@pytest.mark.parametrize("kind", ["linear", "lstm", "experts"])
def test_gradients_from_factors_equal_the_materialised_basis_algebra(kind):
    module = {
        "linear": lambda: Linear(7, 5) >> Tanh() >> Linear(5, 3),
        "lstm": lambda: LSTM(6, 4) >> Linear(4, 2),
        "experts": lambda: small_decoder(4),
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


def test_functional_tell_and_oo_step_share_the_update():
    """The OO searcher's donated update equals the functional pair's on the
    same population and scores."""
    env = TokenCopyEnv(VOCAB, 3, 6)
    problem = VecNE(env, small_decoder(6), eval_mode="budget", episode_length=6, seed=2,
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


def test_no_array_of_basis_or_population_size_on_the_evaluation_path():
    """The lowered rollout of a trunk-delta population holds no ``(L, k)`` and
    no ``(N, L)`` array: its largest buffer is the flat center."""
    import re

    env = TokenCopyEnv(VOCAB, 4, 8)
    problem = VecNE(env, small_decoder(8), eval_mode="budget", episode_length=8, store_solution_stats=False)
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
def test_budget_counts_are_exact_and_a_reset_lane_starts_clean(compute_dtype):
    steps, lanes = 12, 8
    # a vocabulary of two: every other emitted token is id 0 and ends an
    # episode, so lanes reset (cache and all) in the middle of their budget
    env = TokenCopyEnv(2, 3, steps)
    problem = VecNE(env, decoder(steps=steps, vocab=2), eval_mode="budget", episode_length=steps,
                    compute_dtype=compute_dtype, store_solution_stats=False, seed=1)
    flat = seeded(problem.policy)
    batch = SolutionBatch(problem, values=trunk_batch(problem.policy, flat, lanes=lanes, rank=2))
    problem.evaluate(batch)
    assert int(problem.status["total_interaction_count"]) == lanes * steps
    assert int(problem.status["total_episode_count"]) > lanes
    report = problem.last_policy_report
    counters = {k: int(v) for k, v in report.items() if v.ndim == 0}
    assert counters["cache_slots_written"] == lanes * steps * 5
    assert counters["expert_layer_steps"] == steps * 4
    assert 0 < counters["expert_pairs_held"] <= lanes * steps * 4 * 2
    assert counters["expert_pairs_fullest"] * 4 >= counters["expert_pairs_held"]  # fullest of 4 held >= mean
    # the module's own reset: the ended lanes' cache and position are zero,
    # the write pointer and the other lanes are not
    net = problem.policy.module
    state = jax.tree_util.tree_map(lambda x: jnp.ones((3,) + x.shape, x.dtype), net.initial_state())
    after = net.reset_state(state, jnp.asarray([False, True, False]))
    attn = after["layers"][1]["attn"]
    assert float(jnp.abs(attn["k"][1]).max()) == 0.0 and float(jnp.abs(attn["v"][1]).max()) == 0.0
    assert attn["t"].tolist() == [1, 0, 1] and attn["step"].tolist() == [1, 1, 1]
    assert float(attn["k"][0].min()) == 1.0 and float(attn["k"][2].min()) == 1.0
    assert after["seen"]["ids"].tolist() == state["seen"]["ids"].tolist()  # the record outlives an episode
    # what the evaluation recorded: every lane's ids and positions, step by
    # step; a lane begins at 0, goes on by one or begins again; some did
    ids, positions = np.asarray(report["ids_seen"]), np.asarray(report["positions_seen"])
    assert ids.shape == positions.shape == (lanes, steps) and (positions[:, 0] == 0).all()
    assert ((positions[:, 1:] == positions[:, :-1] + 1) | (positions[:, 1:] == 0)).all()
    assert (positions[:, 1:] == 0).any() and set(np.unique(ids)) <= {0, 1}
    assert (ids[positions < 3] == 1).all()  # a prompt holds no id 0, and the vocabulary has two


def test_an_evaluations_record_replays_to_the_tokens_it_emitted():
    """The ids a lane consumed (``last_policy_report``) are its prompt and
    then its own tokens: replayed teacher-forced with the record's resets,
    the stepwise forward puts first what the evaluation emitted, and the
    whole-sequence reference, given the episodes' positions, gives the same
    logits for a lane that began an episode midway."""
    steps, lanes, prompt = 16, 6, 3
    env = TokenCopyEnv(4, prompt, steps)  # a vocabulary of four: id 0 comes up, episodes end early
    problem = VecNE(env, decoder(steps=steps, vocab=4), eval_mode="budget", episode_length=steps,
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
    s = sizes(vocab=4)
    want, _ = ref.forward(ref.unflatten(values.materialize()[lane], s), ids[lane], s, positions=positions[lane])
    assert relative_rms(logits[lane], want) < 1e-5
    # only some lanes' logits kept: the same numbers
    kept, routes = jax.jit(lambda b, i, p: stepwise_logits(policy, b, i, positions=p, lanes=jnp.asarray([lane])))(
        values, ids, positions
    )
    assert kept.shape == (1, steps, 4) and routes.shape == (steps, 4, 1, 2)
    assert relative_rms(kept[0], logits[lane]) < 1e-6


# -- names inside the forward ---------------------------------------------------


def test_inner_scopes_sit_inside_policy_forward():
    problem = VecNE(TokenCopyEnv(VOCAB, 3, 6), small_decoder(6), eval_mode="budget", episode_length=6,
                    store_solution_stats=False)
    batch = trunk_batch(problem.policy, seeded(problem.policy), lanes=4, rank=2)
    text = problem.lower_evaluation(4, like=batch).compile().as_text()
    outer = instruction_scopes(text, inherit=False)
    inner = instruction_scopes(text, inherit=False, names=FORWARD_SCOPES)
    named = {name: scope for name, scope in inner.items() if scope is not None}
    assert set(named.values()) == set(FORWARD_SCOPES)
    assert all(outer[name] == "policy_forward" for name in named)  # the outermost name stays


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
