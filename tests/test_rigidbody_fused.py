"""The fused control step of ``envs/rigidbody.py``: one Pallas kernel over
blocks of 1,024 lanes, held here (on the CPU, in interpret mode) to the plain
form that the CPU, small populations and the benchmark's reference run.

Both forms call ONE function for the arithmetic (``_substep_rows``), so what
can differ is what the kernel brings of its own: the relayout into rows of
whole registers and back, the padded tail block, the loop over substeps, its
arctangent, and the ``shard_map`` that keeps every device on its own lanes.
Tolerances are float32's: a control test rounds ONE intermediate (the joint
angle) through bfloat16 and has to fail them.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.envs import make_env
from evotorch_tpu.envs import rigidbody as rb
from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear, Tanh
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.neuroevolution.net.vecrl import run_vectorized_rollout
from evotorch_tpu.parallel import make_mesh, make_sharded_rollout_evaluator

# the rigid-body envs: both actuation modes, a 3-D and two planar body plans
ENVS = {
    "humanoid": ("humanoid", {}),
    "humanoid_torque": ("humanoid", {"act_mode": "torque"}),
    "ant": ("ant", {}),
    "walker2d": ("walker2d", {}),
    "halfcheetah": ("halfcheetah", {}),
}
LANES = (1300, 2048)  # a ragged tail block; two exact blocks
# After ONE control step, from a perturbed state in which joints, limits and
# contacts all act hard: the largest |kernel - plain| of any lane, relative
# to the field's largest entry (at least 1). The two forms differ by float32
# rounding (the arctangent's last bits) through 8 substeps of stiff springs:
# 6e-5 at most over the envs; one bfloat16 intermediate reads 7e-3 to 0.6.
ONE_STEP_TOLERANCE = 2e-4
# After 20 control steps (160 substeps, from the reset state under small
# random actions) the worst lanes have diverged, as chaotic systems do from
# any rounding: the MEDIAN lane's gap decides. The kernel's medians are 1e-6
# to 1.5e-3 by env, one bfloat16 intermediate's 8e-4 to 0.14, 90 to 10,000
# times larger in every env: each tolerance lies between its two readings.
MEDIAN_TOLERANCE_AFTER_20 = {
    "ant": 2e-3,
    "halfcheetah": 5e-5,
    "humanoid": 2e-4,
    "humanoid_torque": 2e-2,
    "walker2d": 5e-5,
}


def _perturbed(env, lanes, seed=0):
    """A population state in which joints, limits and contacts all act."""
    keys = jax.random.split(jax.random.key(seed), 6)
    state, _ = env.batch_reset(jax.random.split(keys[0], lanes))
    st = state.obs_state
    quat = st.quat + 0.1 * jax.random.normal(keys[1], st.quat.shape)
    st = rb.BodyState(
        pos=st.pos + 0.02 * jax.random.normal(keys[2], st.pos.shape),
        quat=quat / jnp.linalg.norm(quat, axis=1, keepdims=True),
        vel=st.vel + 0.5 * jax.random.normal(keys[3], st.vel.shape),
        ang=st.ang + 1.0 * jax.random.normal(keys[4], st.ang.shape),
    )
    actions = jax.random.uniform(
        keys[5], (env.sys.num_act, lanes), minval=-1.0, maxval=1.0
    )
    return state, st, actions


def _control_step(env, form):
    """``env.batch_step``'s physics (the planar projection with it) through
    one of the two forms."""
    h = env.dt / env.substeps

    def step(st, actions):
        if form == "fused":
            st = rb._fused_step(env.sys, st, actions, h, env.substeps, interpret=True)
        else:
            st = rb._plain_step(env.sys, st, actions, h, env.substeps)
        return env._planar_project(st) if env.planar else st

    return jax.jit(step)


def _lane_gaps(a, b):
    """Per lane: the largest |a - b| over bodies, components and fields,
    relative to the field's largest entry (at least 1)."""
    gaps = [
        np.max(np.abs(x - y), axis=(0, 1)) / max(1.0, float(np.max(np.abs(y))))
        for x, y in zip(a, b)
    ]
    return np.max(gaps, axis=0)


def _after(step, st, actions, steps):
    for _ in range(steps):
        st = step(st, actions)
    return jax.tree_util.tree_map(np.asarray, st)


@functools.lru_cache(maxsize=None)
def _both_forms(name, lanes):
    """Kernel and plain form from one state: after one violent control step,
    and after 20 gentle ones."""
    env_name, kwargs = ENVS[name]
    env = make_env(env_name, **kwargs)
    state, perturbed, actions = _perturbed(env, lanes)
    out = {}
    for form in ("fused", "plain"):
        step = _control_step(env, form)
        out[form, 1] = _after(step, perturbed, actions, 1)
        out[form, 20] = _after(step, state.obs_state, 0.1 * actions, 20)
    return out


@pytest.mark.parametrize("steps", (1, 20))
@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("name", sorted(ENVS))
def test_kernel_matches_plain_form(name, lanes, steps):
    out = _both_forms(name, lanes)
    fused, plain = out["fused", steps], out["plain", steps]
    assert all(np.all(np.isfinite(x)) for x in fused)
    assert all(x.shape == y.shape for x, y in zip(fused, plain))
    gaps = _lane_gaps(fused, plain)
    assert gaps.shape == (lanes,)
    if steps == 1:
        assert np.max(gaps) <= ONE_STEP_TOLERANCE
    else:
        assert np.median(gaps) <= MEDIAN_TOLERANCE_AFTER_20[name]


def test_a_bfloat16_intermediate_fails_the_tolerances():
    env = make_env("humanoid")
    state, perturbed, actions = _perturbed(env, 512)
    m, h = rb._Model(env.sys), env.dt / env.substeps

    def rounded_atan2(s, w):  # rows in, a row out
        return rb._Row(jnp.arctan2(s.x, w.x).astype(jnp.bfloat16).astype(jnp.float32))

    @jax.jit
    def coarse(st, actions):
        act = [actions[i] for i in range(actions.shape[0])]

        def substep(_, rows):
            return rb._substep_rows(m, rows, act, h, rounded_atan2)

        return rb._from_rows(jax.lax.fori_loop(0, env.substeps, substep, rb._to_rows(st)))

    plain = _control_step(env, "plain")
    one = _lane_gaps(_after(coarse, perturbed, actions, 1), _after(plain, perturbed, actions, 1))
    assert np.max(one) > 20 * ONE_STEP_TOLERANCE
    gentle = 0.1 * actions
    twenty = _lane_gaps(
        _after(coarse, state.obs_state, gentle, 20), _after(plain, state.obs_state, gentle, 20)
    )
    assert np.median(twenty) > 20 * MEDIAN_TOLERANCE_AFTER_20["humanoid"]


def test_arctangent_within_a_few_ulp_on_its_quadrant():
    # a dense grid of [0, 1] x [0, 1], the tiny and the equal arguments a
    # unit quaternion produces, and the quadrant's edges s = 0 and w = 0
    grid = np.linspace(0.0, 1.0, 1025, dtype=np.float32)
    tiny = np.float32([0.0, 1e-30, 1e-12, 1e-7, 3e-4, 0.41421354, 0.41421357, 1.0])
    axis = np.unique(np.concatenate([grid, tiny]))
    s, w = (x.reshape(-1) for x in np.meshgrid(axis, axis))
    atan2 = jax.jit(lambda s, w: rb._atan2_first_quadrant(rb._Row(s), rb._Row(w)).x)
    got = np.asarray(atan2(s, w))
    want = np.asarray(jnp.arctan2(s, w))
    exact = np.arctan2(s.astype(np.float64), w.astype(np.float64))
    ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
    assert np.max(np.abs(got.astype(np.float64) - want) / ulp) <= 4.0
    # no further from the exact value than XLA's own expansion is, plus one ulp
    ours = np.max(np.abs(got - exact) / ulp)
    theirs = np.max(np.abs(want - exact) / ulp)
    assert ours <= theirs + 1.0
    edge = np.asarray(atan2(np.float32([0.0, 0.0, 0.7, 0.0]), np.float32([0.0, 0.7, 0.0, 1e-30])))
    np.testing.assert_array_equal(edge, np.float32([0.0, 0.0, np.pi / 2, 0.0]))


def test_single_instance_api_is_the_plain_form():
    env = make_env("humanoid")
    _, st, actions = _perturbed(env, 3)
    one = rb.BodyState(*(x[..., 1] for x in st))
    stepped = rb.physics_step(env.sys, one, actions[:, 1], env.dt, env.substeps)
    batched = rb.physics_step_batched(env.sys, st, actions, env.dt, env.substeps)
    for x, y in zip(stepped, batched):
        assert x.shape == y.shape[:-1]
        np.testing.assert_allclose(np.asarray(x), np.asarray(y[..., 1]), rtol=1e-5, atol=1e-5)
    sub = rb.physics_substep(env.sys, one, actions[:, 1], env.dt / env.substeps)
    assert sub.quat.shape == (env.sys.num_bodies, 4)
    # no kernel in a program of fewer lanes than one block, whatever the platform
    state, _ = env.batch_reset(jax.random.split(jax.random.key(0), 3))
    text = str(jax.make_jaxpr(lambda s, a: env.batch_step(s, a))(state, actions.T))
    assert "pallas_call" not in text


def test_the_platform_of_the_lowering_chooses_the_form():
    env = make_env("walker2d")
    _, st, actions = _perturbed(env, rb._BLOCK)
    step = jax.jit(lambda st, a: rb.physics_step_batched(env.sys, st, a, env.dt, env.substeps))
    # from one block of lanes on the traced program holds both forms ...
    jaxpr = str(jax.make_jaxpr(step)(st, actions))
    assert "pallas_call" in jaxpr and "platform_index" in jaxpr
    # ... and a lowering for the CPU keeps the plain one alone: it runs here
    lowered = step.lower(st, actions).as_text()
    assert "tpu_custom_call" not in lowered and "pallas" not in lowered
    want = rb._plain_step(env.sys, st, actions, env.dt / env.substeps, env.substeps)
    assert np.max(_lane_gaps(step(st, actions), want)) == 0.0
    assert rb._fused_lanes(1) == rb._fused_lanes(1024) == 1024
    assert (rb._fused_lanes(12_500), rb._fused_lanes(50_000)) == (13_312, 50_176)


@pytest.fixture
def fused_everywhere(monkeypatch):
    """Steer the dispatch in the test: the kernel, interpreted, on the CPU."""
    fused_step = rb._fused_step
    monkeypatch.setattr(
        rb, "_fused_step", lambda *args: fused_step(*args, interpret=True)
    )
    monkeypatch.setattr(rb, "_by_platform", lambda fused, plain, *args: fused(*args))


def test_sharded_evaluation_keeps_every_device_on_its_own_lanes(fused_everywhere):
    """``pop=4`` on the virtual CPU devices: the partitioner may not gather
    the body state around the kernel, every device steps B/4 lanes, and the
    scores are the unsharded kernel's."""
    env = make_env("walker2d")
    policy = FlatParamsPolicy(
        Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
    )
    stats = RunningNorm(env.observation_size).stats
    popsize, devices = 4 * 1300, 4
    values = 0.1 * jax.random.normal(jax.random.key(0), (popsize, policy.parameter_count))
    key = jax.random.key(7)
    kwargs = dict(num_episodes=1, episode_length=3, eval_mode="budget")

    evaluator = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh({"pop": devices}), **kwargs
    )
    program = evaluator.program_builder("dense", popsize)
    text = program.lower(values, key, stats).compile().as_text()
    rows = 13 * env.sys.num_bodies + env.sys.num_act
    local = f"f32[{rows},{rb._fused_lanes(popsize // devices) // 128},128]"
    whole = f"f32[{rows},{rb._fused_lanes(popsize) // 128},128]"
    assert local in text and whole not in text
    assert not re.search(r"all-gather[^\n]*f32\[\d+,[34],", text)  # no gathered body state
    assert "all-gather" not in "".join(
        line for line in text.splitlines() if "evotorch_tpu.env_step" in line
    )

    result, _ = evaluator(values, key, stats)
    whole_result = run_vectorized_rollout(env, policy, values, key, stats, **kwargs)
    np.testing.assert_allclose(
        np.asarray(result.scores), np.asarray(whole_result.scores), rtol=1e-5, atol=1e-5
    )
    assert int(result.total_steps) == int(whole_result.total_steps) == popsize * 3


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as error:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
@pytest.mark.parametrize("lanes,devices", [(50_000, 1), (8_192, 1), (50_000, 4)])
def test_control_step_compiles_for_v5e(v5e, lanes, devices):
    """The real TPU compiler, Mosaic included, on the benchmark's lane counts:
    one kernel, named with its useful and computed lanes, and on the 2x2 no
    collective around it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    import contextlib

    env = make_env("humanoid")
    if devices == 1:
        lane_sharding = lambda ndim: SingleDeviceSharding(v5e.devices[0])
        traced_on = contextlib.nullcontext()
    else:
        mesh = Mesh(np.asarray(v5e.devices[:devices]), ("pop",))
        lane_sharding = lambda ndim: NamedSharding(
            mesh, PartitionSpec(*([None] * (ndim - 1)), "pop")
        )
        # as parallel/evaluate.py traces its GSPMD programs: the kernel reads
        # the mesh here and wraps itself in a shard_map over the lane axis
        traced_on = jax.sharding.use_abstract_mesh(mesh.abstract_mesh)

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=lane_sharding(len(shape)))

    nb = env.sys.num_bodies
    st = rb.BodyState(
        shaped(nb, 3, lanes), shaped(nb, 4, lanes), shaped(nb, 3, lanes), shaped(nb, 3, lanes)
    )
    step = jax.jit(lambda st, a: rb.physics_step_batched(env.sys, st, a, env.dt, env.substeps))
    with traced_on:
        traced = step.trace(st, shaped(env.sys.num_act, lanes))
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    local = lanes // devices
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"{rb.FUSED_KERNEL_NAME}_{local}_of_{rb._fused_lanes(local)}" in text
    assert "all-gather" not in text and "all-to-all" not in text
