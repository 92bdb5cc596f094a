"""GSPMD named-sharding rewrite (docs/sharding.md): bit-identity, padding,
the one-program donated generation step, mesh-scoped tuned-cache keys, and
the persistent compile cache.

The load-bearing claim of the rewrite is that the mesh is an EXECUTION
DETAIL: the global program is the single-device program, so sharded scores
and counters are bit-identical to unsharded at any mesh shape, and popsizes
that don't divide the device grid are padded + masked without touching the
numbers. These tests pin that contract on the pytest 8-virtual-device CPU
mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from evotorch_tpu.envs import CartPole
from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear, Tanh
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.neuroevolution.net.vecrl import (
    run_vectorized_rollout,
    run_vectorized_rollout_compacting_sharded,
)
from evotorch_tpu.parallel import (
    make_generation_step,
    make_mesh,
    make_sharded_rollout_evaluator,
    mesh_label,
)
from evotorch_tpu.observability import EvalTelemetry, GroupTelemetry
from evotorch_tpu.observability.devicemetrics import GROUP_TELEMETRY_WIDTH


@pytest.fixture(scope="module")
def cartpole_setup():
    env = CartPole()
    policy = FlatParamsPolicy(
        Linear(env.observation_size, 4) >> Tanh() >> Linear(4, env.action_size)
    )
    stats = RunningNorm(env.observation_size).stats
    return env, policy, stats


def _population(policy, popsize, seed=0):
    return 0.1 * jax.random.normal(
        jax.random.key(seed), (popsize, policy.parameter_count)
    )


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------


def test_mesh_label_canonical_forms():
    assert mesh_label(None) == "none"
    assert mesh_label(make_mesh({"pop": 8})) == "pop8"
    assert mesh_label(make_mesh({"pop": 4, "model": 2})) == "pop4.model2"
    # size-1 axes drop: an (8, 1) mesh lays out like the 1-D 8-mesh
    assert mesh_label(make_mesh({"pop": 8, "model": 1})) == "pop8"
    assert mesh_label(make_mesh({"pop": 1, "model": 1})) == "none"


# ---------------------------------------------------------------------------
# bit-identity: the global program IS the single-device program
# ---------------------------------------------------------------------------

# explicit refill knobs so the sharded and unsharded runs cannot diverge
# through the tuned-config cache (override provenance on both sides)
_MODE_KWARGS = {
    "budget": {},
    "episodes": {},
    "episodes_refill": {"refill_width": 4, "refill_period": 1},
}


@pytest.mark.parametrize("eval_mode", sorted(_MODE_KWARGS))
def test_gspmd_bit_identity_2d_mesh(cartpole_setup, eval_mode):
    env, policy, stats = cartpole_setup
    values = _population(policy, 16)
    key = jax.random.key(3)
    kwargs = dict(
        num_episodes=1, episode_length=8, eval_mode=eval_mode,
        **_MODE_KWARGS[eval_mode],
    )

    ref = run_vectorized_rollout(env, policy, values, key, stats, **kwargs)
    ev = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh({"pop": 4, "model": 2}), **kwargs
    )
    result, per_shard = ev(values, key, stats)

    np.testing.assert_array_equal(np.asarray(result.scores), np.asarray(ref.scores))
    assert int(result.total_steps) == int(ref.total_steps)
    assert int(result.total_episodes) == int(ref.total_episodes)
    # GSPMD has no per-shard accounting: the 1-element form carries the total
    assert np.asarray(per_shard).shape == (1,)
    assert int(np.asarray(per_shard)[0]) == int(ref.total_steps)


def test_compacting_sharded_bit_identity_2d_mesh(cartpole_setup):
    # the fourth contract: host-chunked lane compaction, sharded over the
    # pop axis of the same 2-D mesh (the model axis replicates)
    env, policy, stats = cartpole_setup
    values = _population(policy, 16)
    key = jax.random.key(3)
    ref = run_vectorized_rollout(
        env, policy, values, key, stats,
        num_episodes=1, episode_length=8, eval_mode="episodes",
    )
    result = run_vectorized_rollout_compacting_sharded(
        env, policy, values, key, stats,
        mesh=make_mesh({"pop": 4, "model": 2}),
        num_episodes=1, episode_length=8, chunk_size=4,
    )
    np.testing.assert_array_equal(np.asarray(result.scores), np.asarray(ref.scores))
    assert int(result.total_episodes) == int(ref.total_episodes)


# ---------------------------------------------------------------------------
# padding: popsizes that don't divide the mesh
# ---------------------------------------------------------------------------


def test_gspmd_popsize_1000_on_8_device_mesh(cartpole_setup):
    env, policy, stats = cartpole_setup
    values = _population(policy, 1000, seed=5)
    key = jax.random.key(7)
    kwargs = dict(num_episodes=1, episode_length=2, eval_mode="budget")

    ref = run_vectorized_rollout(env, policy, values, key, stats, **kwargs)
    ev = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh({"pop": 8}), **kwargs
    )
    result, _ = ev(values, key, stats)
    assert result.scores.shape == (1000,)
    np.testing.assert_array_equal(np.asarray(result.scores), np.asarray(ref.scores))
    assert int(result.total_steps) == int(ref.total_steps) == 1000 * 2

    # the same 1000 lanes on a 3-device mesh (1000 % 3 != 0): padded to
    # 1002, sliced back, numbers untouched — what used to be an error
    ev3 = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh({"pop": 3}), **kwargs
    )
    result3, _ = ev3(values, key, stats)
    assert result3.scores.shape == (1000,)
    np.testing.assert_array_equal(np.asarray(result3.scores), np.asarray(ref.scores))
    assert int(result3.total_steps) == 1000 * 2


def test_gspmd_padding_masks_counters_and_telemetry(cartpole_setup):
    # 13 lanes on the 8-device grid: padded to 16, the 3 synthetic lanes
    # must contribute NOTHING to scores, counters, or the genuine telemetry
    # slots (capacity/lane_width count PHYSICAL lanes by design — padding
    # is idle capacity you pay for; docs/sharding.md)
    env, policy, stats = cartpole_setup
    values = _population(policy, 13, seed=11)
    key = jax.random.key(13)
    kwargs = dict(num_episodes=1, episode_length=4, eval_mode="budget")

    ref = run_vectorized_rollout(env, policy, values, key, stats, **kwargs)
    ev = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh({"pop": 8}), **kwargs
    )
    result, _ = ev(values, key, stats)
    assert result.scores.shape == (13,)
    np.testing.assert_array_equal(np.asarray(result.scores), np.asarray(ref.scores))
    assert int(result.total_steps) == 13 * 4
    assert int(result.total_episodes) == int(ref.total_episodes)
    telem = EvalTelemetry.from_array(result.telemetry)
    assert telem.env_steps == 13 * 4  # genuine work only
    assert telem.lane_width == 16  # physical (padded) lanes


# ---------------------------------------------------------------------------
# per-group telemetry: the (G, 14) matrix is mesh-invariant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("against", ["unsharded_g2", "sharded_g1"])
def test_gspmd_per_group_matrix_bit_identical_across_meshes(cartpole_setup, against):
    # the per-group matrix is part of the GLOBAL program's output, so it
    # must be BIT-identical unsharded vs 1-D vs 2-D pop x model — including
    # the queue-wait histogram block (refill is the contract that fills it);
    # and over the same mesh the G=2 matrix must column-sum to the G=1
    # globals, its histogram counting every refill
    env, policy, stats = cartpole_setup
    values = _population(policy, 16)
    key = jax.random.key(3)
    groups = np.arange(16, dtype=np.int32) % 2
    kwargs = dict(
        num_episodes=1, episode_length=8, eval_mode="episodes_refill",
        refill_width=8, refill_period=1,
    )
    if against == "sharded_g1":
        mesh = make_mesh({"pop": 8})
        res1, _ = make_sharded_rollout_evaluator(env, policy, mesh=mesh, **kwargs)(
            values, key, stats
        )
        res2, _ = make_sharded_rollout_evaluator(
            env, policy, mesh=mesh, groups=groups, num_groups=2, **kwargs
        )(values, key, stats)
        np.testing.assert_array_equal(np.asarray(res1.scores), np.asarray(res2.scores))
        t2 = GroupTelemetry.from_array(res2.telemetry)
        assert t2.data.shape == (2, GROUP_TELEMETRY_WIDTH)
        s1, s2 = GroupTelemetry.from_array(res1.telemetry).total(), t2.total()
        for field in (
            "env_steps", "episodes", "capacity", "lane_width",
            "refill_events", "queue_wait",
        ):
            assert getattr(s1, field) == getattr(s2, field), field
        assert int(t2.hist.sum()) == s2.refill_events
        return
    kwargs.update(groups=groups, num_groups=2)
    ref = run_vectorized_rollout(env, policy, values, key, stats, **kwargs)
    tref = GroupTelemetry.from_array(ref.telemetry)
    assert tref.data.shape == (2, GROUP_TELEMETRY_WIDTH)
    for mesh_shape in ({"pop": 8}, {"pop": 4, "model": 2}):
        ev = make_sharded_rollout_evaluator(
            env, policy, mesh=make_mesh(mesh_shape), **kwargs
        )
        result, _ = ev(values, key, stats)
        np.testing.assert_array_equal(
            np.asarray(result.scores), np.asarray(ref.scores)
        )
        t = GroupTelemetry.from_array(result.telemetry)
        np.testing.assert_array_equal(t.data, tref.data)


def test_gspmd_per_group_padding_masks_popsize_1000(cartpole_setup):
    # 1000 lanes on the 3-device mesh (1000 % 3 != 0 -> padded to 1002
    # physical lanes): the pad lanes never activate, so the per-group
    # env-step/episode columns match unsharded exactly; capacity/lane_width
    # count physical lanes (the pads charge group 0, the row they were
    # copied from)
    env, policy, stats = cartpole_setup
    values = _population(policy, 1000, seed=5)
    key = jax.random.key(7)
    groups = np.arange(1000, dtype=np.int32) % 2
    kwargs = dict(
        num_episodes=1, episode_length=2, eval_mode="episodes",
        groups=groups, num_groups=2,
    )
    ref = run_vectorized_rollout(env, policy, values, key, stats, **kwargs)
    tref = GroupTelemetry.from_array(ref.telemetry)
    ev = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh({"pop": 3}), **kwargs
    )
    result, _ = ev(values, key, stats)
    np.testing.assert_array_equal(np.asarray(result.scores), np.asarray(ref.scores))
    t = GroupTelemetry.from_array(result.telemetry)
    np.testing.assert_array_equal(t.data[:, 0], tref.data[:, 0])  # env_steps
    np.testing.assert_array_equal(t.data[:, 1], tref.data[:, 1])  # episodes
    assert int(t.data[:, 3].sum()) == 1002  # physical (padded) lanes


def test_compacting_sharded_per_group_counts(cartpole_setup):
    env, policy, stats = cartpole_setup
    values = _population(policy, 16)
    key = jax.random.key(3)
    groups = np.arange(16, dtype=np.int32) % 2
    ref = run_vectorized_rollout_compacting_sharded(
        env, policy, values, key, stats, mesh=make_mesh({"pop": 8}),
        num_episodes=1, episode_length=8, chunk_size=4,
    )
    result = run_vectorized_rollout_compacting_sharded(
        env, policy, values, key, stats, mesh=make_mesh({"pop": 8}),
        num_episodes=1, episode_length=8, chunk_size=4,
        groups=groups, num_groups=2,
    )
    np.testing.assert_array_equal(np.asarray(result.scores), np.asarray(ref.scores))
    t = GroupTelemetry.from_array(result.telemetry)
    assert t.data.shape == (2, GROUP_TELEMETRY_WIDTH)
    tref = GroupTelemetry.from_array(ref.telemetry)
    s, sref = t.total(), tref.total()
    for field in ("env_steps", "episodes", "capacity", "lane_width"):
        assert getattr(s, field) == getattr(sref, field), field


# ---------------------------------------------------------------------------
# one way to shard: nothing reads the variable that selected the other
# ---------------------------------------------------------------------------

#: spelled in halves, so that a search of the tree for it finds no reader
_LEGACY_VARIABLE = "EVOTORCH_" + "SHARD_MAP"


def test_vecne_honors_num_actors_whatever_the_environment_says(monkeypatch):
    # the explicit per-shard form stepped an indivisible popsize down to the
    # largest dividing shard count (1002 on 8 devices: 6); the one form left
    # pads and masks, so the request is honored and the scores are those of
    # one device, bit for bit
    from evotorch_tpu.neuroevolution import VecNE

    monkeypatch.setenv(_LEGACY_VARIABLE, "1")

    def problem(**kwargs):
        return VecNE(
            "cartpole", "Linear(obs_length, act_length)", eval_mode="budget",
            episode_length=4, seed=7, **kwargs,
        )

    sharded, single = problem(num_actors=8), problem()
    assert sharded._num_actors_mesh(1002).devices.size == 8
    batch = sharded.generate_batch(1002)
    sharded.evaluate(batch)
    single_batch = single.generate_batch(1002)
    np.testing.assert_array_equal(
        np.asarray(batch.values), np.asarray(single_batch.values)
    )
    single.evaluate(single_batch)
    np.testing.assert_array_equal(
        np.asarray(batch.evals), np.asarray(single_batch.evals)
    )


def test_grad_estimator_takes_any_popsize_whatever_the_environment_says(monkeypatch):
    from evotorch_tpu.distributions import SeparableGaussian
    from evotorch_tpu.parallel import make_sharded_grad_estimator

    monkeypatch.setenv(_LEGACY_VARIABLE, "1")
    estimator = make_sharded_grad_estimator(
        SeparableGaussian,
        lambda xs: jnp.sum(xs**2, axis=-1),
        objective_sense="min",
        mesh=make_mesh({"pop": 8}),
    )
    params = {
        "mu": jnp.full((4,), 5.0), "sigma": jnp.ones(4),
        "divide_mu_grad_by": "num_solutions", "divide_sigma_grad_by": "num_solutions",
    }
    grads = estimator(jax.random.key(0), 13, params)  # 13 % 8 != 0
    assert all(float(g) < 0 for g in np.asarray(grads["mu"]))


# ---------------------------------------------------------------------------
# the one-program donated generation step
# ---------------------------------------------------------------------------


def test_generation_step_runs_and_donates(cartpole_setup):
    from evotorch_tpu.algorithms.functional import pgpe, pgpe_ask, pgpe_tell
    from evotorch_tpu.observability import ledger
    from evotorch_tpu.observability.programs import abstract_like

    env, policy, stats = cartpole_setup
    popsize = 8

    def ask(k, s):
        return pgpe_ask(k, s, popsize=popsize)

    generation = make_generation_step(
        env, policy, ask=ask, tell=pgpe_tell, popsize=popsize,
        mesh=make_mesh({"pop": 4, "model": 2}),
        num_episodes=1, episode_length=4, eval_mode="budget",
    )
    state = pgpe(
        center_init=jnp.zeros(policy.parameter_count),
        center_learning_rate=0.1,
        stdev_learning_rate=0.1,
        objective_sense="max",
        stdev_init=0.1,
    )

    donated = state
    state, scores, stats_out, total_steps, _telem = generation(
        state, jax.random.key(0), stats
    )
    assert scores.shape == (popsize,)
    assert int(total_steps) == popsize * 4
    # runtime ground truth: jax deletes exactly the donated inputs whose
    # aliasing the executable consumed
    assert donated.stdev.is_deleted()

    # second generation (the committed-layout fixed point) still runs, and
    # donates the first generation's output state in turn
    state2, scores2, _, _, _ = generation(state, jax.random.key(1), stats_out)
    assert scores2.shape == (popsize,)
    assert state.stdev.is_deleted()

    # the ledger's AOT donation verification agrees: every donated
    # parameter is aliased in the compiled module
    record = ledger.capture(
        "test.gspmd.generation",
        generation,
        abstract_like(state2),
        jax.random.key(2),
        abstract_like(stats),
        shape={"popsize": popsize, "mesh": "pop4.model2"},
    )
    assert record.donation is not None
    assert record.donation.missing == ()


# ---------------------------------------------------------------------------
# mesh-scoped tuned-config cache keys (schema v2, backward-compatible read)
# ---------------------------------------------------------------------------


def test_tuned_cache_mesh_scoping_and_legacy_read(tmp_path, monkeypatch):
    import json

    from evotorch_tpu.observability.timings import (
        TunedEntry,
        load_tuned_cache,
        lookup_tuned,
        machine_fingerprint,
        save_tuned_entry,
    )

    path = tmp_path / "tuned.json"
    monkeypatch.setenv("EVOTORCH_TUNED_CACHE", str(path))
    machine = machine_fingerprint()
    base = {"env": "cartpole", "popsize": 8, "episode_length": 8,
            "num_episodes": 1, "params": 10, "dtype": "float32"}

    # a version-1 (pre-mesh) entry, as an already-checked-in cache holds
    legacy = TunedEntry(group="refill", shape=dict(base), machine=machine,
                        config={"width": 4}, evidence={})
    save_tuned_entry(legacy)
    # unsharded consumers (mesh "none") keep hitting it via the fallback
    hit = lookup_tuned("refill", dict(base, mesh="none"))
    assert hit is not None and hit.config["width"] == 4
    # sharded lookups NEVER inherit a mesh-less entry
    assert lookup_tuned("refill", dict(base, mesh="pop8")) is None

    # a mesh-scoped entry serves exactly its own label
    sharded = TunedEntry(group="refill", shape=dict(base, mesh="pop8"),
                         machine=machine, config={"width": 8}, evidence={})
    save_tuned_entry(sharded)
    assert lookup_tuned("refill", dict(base, mesh="pop8")).config["width"] == 8
    assert lookup_tuned("refill", dict(base, mesh="pop4.model2")) is None
    # the "none" lookup still resolves to the legacy entry, not the sharded
    assert lookup_tuned("refill", dict(base, mesh="none")).config["width"] == 4

    # the save path stamps schema version 2
    with open(path) as f:
        payload = json.load(f)
    assert payload["version"] == 2
    assert len(load_tuned_cache(path)) == 2


# ---------------------------------------------------------------------------
# graftlint: MESH_AXES is the canonical axis registry
# ---------------------------------------------------------------------------


def test_graftlint_collects_mesh_axes_declaration():
    from evotorch_tpu.analysis.graftlint import lint_sources

    src_ok = (
        'import jax\n'
        'MESH_AXES = ("pop", "model")\n'
        'def f(x):\n'
        '    return jax.lax.psum(x, "model")\n'
    )
    findings = [f for f in lint_sources({"mod.py": src_ok}) if f.checker == "axis-name"]
    assert findings == []

    # an axis OUTSIDE the declaration fires (the checker needs at least one
    # declaration to know the project's vocabulary)
    src_bad = (
        'import jax\n'
        'MESH_AXES = ("pop", "model")\n'
        'def f(x):\n'
        '    return jax.lax.psum(x, "modell")\n'
    )
    findings = [f for f in lint_sources({"mod.py": src_bad}) if f.checker == "axis-name"]
    assert findings


# ---------------------------------------------------------------------------
# persistent compile cache: warm processes deserialize instead of compiling
# ---------------------------------------------------------------------------

_CACHE_WORKER = """
import json
import jax

from evotorch_tpu.observability import cache_stats, enable_persistent_cache
from evotorch_tpu.resilience import setup_backend

setup_backend(force_cpu=True)
enable_persistent_cache()  # the parent placed it: JAX_COMPILATION_CACHE_DIR

from evotorch_tpu.envs import CartPole
from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear, Tanh
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.observability import ledger
from evotorch_tpu.observability.programs import abstract_like
from evotorch_tpu.parallel import make_mesh, make_sharded_rollout_evaluator

env = CartPole()
policy = FlatParamsPolicy(
    Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
)
stats = RunningNorm(env.observation_size).stats
ev = make_sharded_rollout_evaluator(
    env, policy, mesh=make_mesh({"pop": 4, "model": 2}),
    num_episodes=1, episode_length=16, eval_mode="budget",
)
record = ledger.capture(
    "cache_probe",
    ev.program_builder(False, 64),
    abstract_like(jax.numpy.zeros((64, policy.parameter_count))),
    jax.random.key(0),
    abstract_like(stats),
)
print("CACHE", json.dumps({
    "compile_seconds": record.compile_seconds, **cache_stats()
}))
"""


@pytest.mark.slow
def test_persistent_compile_cache_warm_process(tmp_path):
    # the acceptance criterion: a second process's compile_seconds for the
    # same program is < 25% of the first's (deserialization, not XLA)
    import json
    import os
    import subprocess
    import sys

    worker = tmp_path / "cache_worker.py"
    worker.write_text(_CACHE_WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    # a private cache, placed the one way the rule allows: from outside
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "compile_cache")

    def run():
        out = subprocess.run(
            [sys.executable, str(worker)],
            env=env, capture_output=True, text=True, timeout=240,
        )
        assert out.returncode == 0, f"worker failed:\n{out.stdout}\n{out.stderr}"
        for line in out.stdout.splitlines():
            if line.startswith("CACHE "):
                return json.loads(line[len("CACHE "):])
        raise AssertionError(f"no CACHE line in:\n{out.stdout}")

    cold = run()
    warm = run()
    assert cold["enabled"] and warm["enabled"]
    assert cold["hits"] == 0 and cold["misses"] > 0
    assert warm["misses"] == 0 and warm["hits"] > 0
    assert warm["compile_seconds"] < 0.25 * cold["compile_seconds"], (cold, warm)
