"""Shared-trunk + per-lane low-rank-delta policy form (docs/policies.md).

The contract under test: the trunk-delta forward, every rollout contract,
the PGPE update and the GSPMD sharded evaluator must agree numerically with
materializing the dense population ``theta_i = center + basis @ z_i`` —
and the sharded evaluations must be BIT-identical to the unsharded one
(the model-axis trunk sharding is pure storage layout).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.algorithms.functional import (
    pgpe,
    pgpe_ask_trunk_delta,
    pgpe_tell,
    pgpe_tell_trunk_delta,
)
from evotorch_tpu.envs import CartPole
from evotorch_tpu.neuroevolution.net import (
    RNN,
    FlatParamsPolicy,
    Linear,
    Tanh,
    trunk_delta_forward,
)
from evotorch_tpu.neuroevolution.net.lowrank import (
    prepare_trunk_delta,
    trunk_delta_supported,
)
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.neuroevolution.net.vecrl import (
    run_vectorized_rollout,
    run_vectorized_rollout_compacting,
)
from evotorch_tpu.tools.lowrank import TrunkDeltaParamsBatch, is_factored


def _mlp_policy(in_dim=9, hidden=16, out_dim=4):
    net = Linear(in_dim, hidden) >> Tanh() >> Linear(hidden, out_dim) >> Tanh()
    return FlatParamsPolicy(net)


def _fresh_state(L, stdev=0.5):
    return pgpe(
        center_init=jnp.asarray(
            np.random.default_rng(0).normal(size=L) * 0.2, jnp.float32
        ),
        center_learning_rate=0.2,
        stdev_learning_rate=0.1,
        objective_sense="max",
        stdev_init=stdev,
    )


def _trunk_batch(policy, n=12, k=4, seed=0):
    state = _fresh_state(policy.parameter_count)
    return pgpe_ask_trunk_delta(
        jax.random.key(seed), state, popsize=n, rank=k, policy=policy
    )


def _dense_forward(policy, dense, obs):
    out, _ = jax.vmap(lambda p, o: policy(p, o))(dense, obs)
    return out


def test_trunk_batch_shape_and_factored():
    policy = _mlp_policy()
    params = _trunk_batch(policy, n=10, k=3)
    assert isinstance(params, TrunkDeltaParamsBatch)
    assert is_factored(params)
    assert params.popsize == 10 and params.rank == 3
    assert trunk_delta_supported(policy.module)
    # take() keeps the factor tree (type-preserving per-lane gather)
    sub = params.take(jnp.asarray([1, 3, 5]))
    assert isinstance(sub, TrunkDeltaParamsBatch)
    assert sub.coeffs.shape[0] == 3
    # the materialized view and the factor view describe the same population:
    # basis column m is vec(b_m a_m^T) blockwise (sigma folded)
    assert params.materialize().shape == (10, policy.parameter_count)


def test_trunk_forward_matches_dense_mlp():
    policy = _mlp_policy()
    params = _trunk_batch(policy, n=12, k=4, seed=1)
    obs = jnp.asarray(np.random.default_rng(2).normal(size=(12, 9)), jnp.float32)
    out_td, state = trunk_delta_forward(policy, params, None, obs, None)
    assert state is None
    out_dense = _dense_forward(policy, params.materialize(), obs)
    np.testing.assert_allclose(
        np.asarray(out_td), np.asarray(out_dense), rtol=1e-4, atol=1e-5
    )


def test_trunk_forward_matches_dense_rnn():
    net = RNN(5, 7) >> Tanh() >> Linear(7, 3)
    policy = FlatParamsPolicy(net)
    params = _trunk_batch(policy, n=8, k=3, seed=3)
    obs = jnp.asarray(np.random.default_rng(4).normal(size=(8, 5)), jnp.float32)
    proto = policy.initial_state()
    states = jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (8,) + leaf.shape), proto
    )
    out_td, st_td = trunk_delta_forward(policy, params, None, obs, states)
    out_dense, st_dense = jax.vmap(policy)(params.materialize(), obs, states)
    np.testing.assert_allclose(
        np.asarray(out_td), np.asarray(out_dense), rtol=1e-4, atol=1e-5
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        st_td,
        st_dense,
    )


def test_trunk_forward_blocked_matches_single_block():
    # the blocked forward (static lane blocks through lax.map) runs the SAME
    # per-lane ops; the shared-trunk GEMM over a 4-lane block may accumulate
    # in another order than the one over all 12 lanes, though (XLA's CPU
    # GEMM under jax 0.9.0 does: 1 ulp), so the two agree to float32
    # rounding, not bit for bit
    policy = _mlp_policy()
    params = _trunk_batch(policy, n=12, k=4, seed=5)
    obs = jnp.asarray(np.random.default_rng(6).normal(size=(12, 9)), jnp.float32)
    one, _ = trunk_delta_forward(
        policy, params, prepare_trunk_delta(policy, params), obs, None
    )
    blocked, _ = trunk_delta_forward(
        policy, params, prepare_trunk_delta(policy, params, trunk_block=4), obs, None
    )
    np.testing.assert_allclose(
        np.asarray(one), np.asarray(blocked), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("mode", ["budget", "episodes", "episodes_refill"])
def test_rollout_trunk_matches_dense_rollout(mode):
    env = CartPole(continuous_actions=True)
    net = Linear(env.observation_size, 16) >> Tanh() >> Linear(16, env.action_size)
    policy = FlatParamsPolicy(net)
    params = _trunk_batch(policy, n=16, k=4, seed=7)
    stats = RunningNorm(env.observation_size).stats
    kw = dict(num_episodes=1, episode_length=60, observation_normalization=True)
    r_td = run_vectorized_rollout(
        env, policy, params, jax.random.key(9), stats, eval_mode=mode, **kw
    )
    r_dense = run_vectorized_rollout(
        env, policy, params.materialize(), jax.random.key(9), stats,
        eval_mode=mode, **kw,
    )
    np.testing.assert_allclose(
        np.asarray(r_td.scores), np.asarray(r_dense.scores), rtol=1e-4, atol=1e-4
    )
    assert int(r_td.total_steps) == int(r_dense.total_steps)


def test_compacting_rollout_accepts_trunk_delta():
    env = CartPole(continuous_actions=True)
    net = Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
    policy = FlatParamsPolicy(net)
    params = _trunk_batch(policy, n=16, k=4, seed=8)
    stats = RunningNorm(env.observation_size).stats
    kw = dict(num_episodes=1, episode_length=80)
    mono = run_vectorized_rollout(
        env, policy, params, jax.random.key(2), stats, eval_mode="episodes", **kw
    )
    comp = run_vectorized_rollout_compacting(
        env, policy, params, jax.random.key(2), stats,
        chunk_size=10, allowed_widths=(4, 8), **kw,
    )
    np.testing.assert_allclose(
        np.asarray(comp.scores), np.asarray(mono.scores), rtol=1e-5, atol=1e-5
    )


def test_rollout_trunk_block_bit_identical():
    env = CartPole(continuous_actions=True)
    net = Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
    policy = FlatParamsPolicy(net)
    params = _trunk_batch(policy, n=12, k=4, seed=9)
    stats = RunningNorm(env.observation_size).stats
    kw = dict(num_episodes=1, episode_length=40, eval_mode="budget")
    plain = run_vectorized_rollout(
        env, policy, params, jax.random.key(3), stats, **kw
    )
    blocked = run_vectorized_rollout(
        env, policy, params, jax.random.key(3), stats, trunk_block=4, **kw
    )
    np.testing.assert_array_equal(
        np.asarray(plain.scores), np.asarray(blocked.scores)
    )


def test_pgpe_trunk_tell_matches_dense_tell():
    # the factored gradients flow through the materialized effective basis:
    # the update must equal pgpe_tell on the materialized population
    policy = _mlp_policy()
    L = policy.parameter_count
    state = _fresh_state(L, stdev=0.7)
    params = pgpe_ask_trunk_delta(
        jax.random.key(3), state, popsize=24, rank=6, policy=policy
    )
    # antithetic layout (required by the factored gradient math)
    np.testing.assert_allclose(
        np.asarray(params.coeffs[0::2]), -np.asarray(params.coeffs[1::2])
    )
    evals = jnp.asarray(np.random.default_rng(11).normal(size=24), jnp.float32)
    s_td = pgpe_tell_trunk_delta(state, params, evals)
    s_dense = pgpe_tell(state, params.materialize(), evals)
    np.testing.assert_allclose(
        np.asarray(s_td.stdev), np.asarray(s_dense.stdev), rtol=1e-4, atol=1e-6
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        s_td.optimizer_state,
        s_dense.optimizer_state,
    )


def test_pgpe_trunk_delta_improves_sphere():
    # end-to-end: trunk-delta PGPE optimizes (sphere on the materialized
    # population, mirroring test_pgpe_lowrank_improves_sphere) even though
    # each generation only explores the rank-k structured subspace
    policy = _mlp_policy(in_dim=4, hidden=8, out_dim=2)
    L = policy.parameter_count
    state = pgpe(
        center_init=jnp.full(L, 3.0),
        center_learning_rate=0.5,
        stdev_learning_rate=0.1,
        objective_sense="max",
        stdev_init=0.5,
        optimizer="adam",
    )
    key = jax.random.key(0)

    first = None
    for _ in range(60):
        key, sub = jax.random.split(key)
        params = pgpe_ask_trunk_delta(sub, state, popsize=64, rank=8, policy=policy)
        evals = -jnp.sum(params.materialize() ** 2, axis=-1)
        state = pgpe_tell_trunk_delta(state, params, evals)
        mean_eval = float(jnp.mean(evals))
        if first is None:
            first = mean_eval
    assert mean_eval > first * 0.2  # losses shrink toward 0 (maximizing -||x||^2)
    assert mean_eval > -L  # well below the initial ~ -9L


# -- GSPMD: model-axis trunk sharding is bit-exact ----------------------------


def _mesh_evaluator_scores(env, policy, params, rkey, stats, mesh_shape, **kw):
    from evotorch_tpu.parallel import make_mesh
    from evotorch_tpu.parallel.evaluate import make_sharded_rollout_evaluator

    mesh = make_mesh(mesh_shape)
    evaluator = make_sharded_rollout_evaluator(env, policy, mesh=mesh, **kw)
    result, _ = evaluator(params, rkey, stats)
    return np.asarray(result.scores)


@pytest.mark.parametrize("mode", ["budget", "episodes_refill"])
def test_trunk_mesh_bit_identity(mode):
    # unsharded vs 1-D pop mesh vs 2-D pop x model mesh: the model-axis
    # sharding of center/basis is ZeRO-style storage layout — XLA gathers
    # the exact values at use, so scores must be BIT-identical
    env = CartPole(continuous_actions=True)
    net = Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
    policy = FlatParamsPolicy(net)
    params = _trunk_batch(policy, n=16, k=4, seed=11)
    stats = RunningNorm(env.observation_size).stats
    rkey = jax.random.key(13)
    kw = dict(num_episodes=2, episode_length=24, eval_mode=mode)
    base = run_vectorized_rollout(env, policy, params, rkey, stats, **kw)
    expected = np.asarray(base.scores)
    for mesh_shape in ({"pop": 8}, {"pop": 4, "model": 2}):
        got = _mesh_evaluator_scores(
            env, policy, params, rkey, stats, mesh_shape, **kw
        )
        np.testing.assert_array_equal(got, expected)


def test_trunk_mesh_bit_identity_padded():
    # indivisible popsize exercises the pad+mask path: the padded coeff rows
    # are masked out, the trunk is shared — still bit-identical
    env = CartPole(continuous_actions=True)
    net = Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
    policy = FlatParamsPolicy(net)
    params = _trunk_batch(policy, n=18, k=4, seed=15)
    stats = RunningNorm(env.observation_size).stats
    rkey = jax.random.key(17)
    kw = dict(num_episodes=1, episode_length=16, eval_mode="budget")
    base = run_vectorized_rollout(env, policy, params, rkey, stats, **kw)
    got = _mesh_evaluator_scores(
        env, policy, params, rkey, stats, {"pop": 4, "model": 2}, **kw
    )
    np.testing.assert_array_equal(got, np.asarray(base.scores))


def test_trunk_generation_step_2d_mesh():
    # the whole donated ask->eval->tell program with trunk-delta ask/tell
    # compiles and runs on a pop x model mesh
    from evotorch_tpu.parallel import make_mesh
    from evotorch_tpu.parallel.evaluate import make_generation_step

    env = CartPole(continuous_actions=True)
    net = Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
    policy = FlatParamsPolicy(net)
    state = _fresh_state(policy.parameter_count)
    stats = RunningNorm(env.observation_size).stats

    def ask(k, s):
        return pgpe_ask_trunk_delta(k, s, popsize=16, rank=4, policy=policy)

    step = make_generation_step(
        env, policy, ask=ask, tell=pgpe_tell_trunk_delta, popsize=16,
        mesh=make_mesh({"pop": 4, "model": 2}),
        num_episodes=1, episode_length=16, eval_mode="budget",
    )
    # the step program DONATES the input state: snapshot the center first.
    # The .copy() is load-bearing — np.asarray of a CPU jax array is a
    # zero-copy VIEW of the device buffer, and a donated program may write
    # its output into that very buffer in place (the persistent-compile-cache
    # deserialized executable does; a freshly compiled one happens not to),
    # which would silently turn this "snapshot" into the post-update center.
    center_before = np.asarray(state.optimizer_state.center).copy()
    state2, scores, stats2, steps, _telemetry = step(state, jax.random.key(1), stats)
    assert np.isfinite(np.asarray(scores)).all()
    assert int(np.asarray(steps)) == 16 * 16
    assert not np.allclose(np.asarray(state2.optimizer_state.center), center_before)


# -- autotuner pure core: rank preference inside the throughput band ----------


def test_select_winner_rank_preference_band():
    from evotorch_tpu.observability.autotune import CandidateStats, select_winner

    r4 = CandidateStats(config={"rank": 4}, samples=[100.0, 100.0, 100.0])
    r16 = CandidateStats(config={"rank": 16}, samples=[95.0, 95.0, 95.0])
    r64 = CandidateStats(config={"rank": 64}, samples=[70.0, 70.0, 70.0])
    results = [r4, r16, r64]
    # plain selection: fastest wins
    assert select_winner(results) is r4

    def prefer(config):
        return int(config.get("rank", 0))

    # rank preference inside a 10% band: r16 is within the band, r64 is not
    assert select_winner(results, tolerance=0.1, prefer=prefer) is r16
    # a wide band admits r64
    assert select_winner(results, tolerance=0.5, prefer=prefer) is r64
    # preference ties break on throughput
    r16b = CandidateStats(config={"rank": 16}, samples=[99.0, 99.0, 99.0])
    assert select_winner([r4, r16, r16b], tolerance=0.1, prefer=prefer) is r16b


def test_policy_harness_knobs():
    from evotorch_tpu.observability.autotune import PolicyHarness, TuneShape

    shape = TuneShape(env_name="cartpole", popsize=8, episode_length=10)
    harness = PolicyHarness(shape, ranks=(2, 4), trunk_blocks=(0, 4, 3))
    assert harness.group == "policy"
    specs = {spec.name: spec for spec in harness.knob_group().knobs}
    assert tuple(specs["rank"].values) == (2, 4)
    # trunk_blocks keeps 0 and the divisors of popsize strictly below it
    assert tuple(specs["trunk_block"].values) == (0, 4)
    assert harness.winner_tolerance == 0.1
    assert harness.winner_prefer({"rank": 16}) == 16
    config = {"rank": 4, "trunk_block": 0}
    assert harness.tuned_config(config) == {"rank": 4, "trunk_block": 0}
    assert harness.default_config()["rank"] == 2


# -- SLO: the min_model_efficiency rule ---------------------------------------


def test_slo_min_model_efficiency_rule():
    from evotorch_tpu.observability.slo import Rule, SLOWatchdog

    dog = SLOWatchdog([Rule("min_model_efficiency", threshold=0.5)])
    # no ledger columns: the rule is skipped, not violated
    report = dog.check(None, status={})
    assert report.ok and report.checked == 0
    report = dog.check(None, status={"model_efficiency": 0.62})
    assert report.ok and report.checked == 1
    report = dog.check(None, status={"model_efficiency": 0.31})
    assert not report.ok
    assert "model_efficiency=0.31" in report.violations[0]
