"""Multi-tenant evaluation service (evotorch_tpu/serving, docs/serving.md).

The acceptance spine: (1) items from >= 2 tenants packed into ONE resident
``episodes_refill`` dispatch produce per-tenant scores BIT-IDENTICAL to each
tenant evaluating standalone; (2) tenant admission/departure churn
re-dispatches the same executable — zero steady-state compiles under the
retrace sentinel; (3) the group-id plane credits scores/steps/episodes to
the right tenant whatever the lane rebinding.

Warm-up discipline: VecNE's eager counter bump compiles on its first TWO
evaluations (int+array then array+array), so every retrace-sentinel window
over a VecNE path warms twice first.
"""

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.analysis import track_compiles
from evotorch_tpu.envs import CartPole
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear, Tanh
from evotorch_tpu.neuroevolution.net.vecrl import run_vectorized_rollout
from evotorch_tpu.observability.devicemetrics import GroupTelemetry
from evotorch_tpu.parallel.evaluate import make_resident_rollout_program
from evotorch_tpu.serving import (
    EvalServer,
    FIFOAdmission,
    RemoteEvalBackend,
    StarvationAwareAdmission,
    serve_stdio,
)

pytestmark = pytest.mark.serving


def _env():
    return CartPole(continuous_actions=True)


def _policy(env):
    return FlatParamsPolicy(Linear(env.observation_size, env.action_size) >> Tanh())


def _values(policy, n, seed):
    # numpy, not jax.random.split: a varying n would compile a new split
    # program inside the retrace-sentinel windows below
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, policy.parameter_count)).astype(np.float32)


def _standalone_refill(env, policy, values, key, **kw):
    return run_vectorized_rollout(
        env, policy, values, key, None,
        eval_mode="episodes_refill", num_episodes=1, **kw,
    )


# ---------------------------------------------------------------- engine level


def test_refill_group_rebinding_credits_and_zero_compiles():
    """Satellite: lane rebinding A -> B must credit scores/steps/episodes to
    the right groups with ZERO steady compiles (groups are traced)."""
    env = _env()
    policy = _policy(env)
    n, width, num_groups = 8, 3, 3  # width < n forces lane recycling
    values = jnp.asarray(_values(policy, n, 0))
    solution_keys = jax.random.split(jax.random.key(5), n)

    def run(groups, key):
        return run_vectorized_rollout(
            env, policy, values, key, None,
            eval_mode="episodes_refill", num_episodes=1, refill_width=width,
            groups=groups, num_groups=num_groups,
            solution_keys=solution_keys,
        )

    fn = jax.jit(run)
    groups_a = jnp.asarray([1, 1, 1, 1, 2, 2, 2, 2], dtype=jnp.int32)
    groups_b = jnp.asarray([2, 2, 1, 1, 1, 1, 2, 2], dtype=jnp.int32)
    key = jax.random.key(9)
    res_a = fn(groups_a, key)
    jax.block_until_ready(res_a.scores)
    with track_compiles() as log:
        res_b = fn(groups_b, key)
        jax.block_until_ready(res_b.scores)
    assert log.count == 0, f"group rebinding retraced: {log.names}"

    # group binding is pure accounting: per-item randomness comes from
    # solution_keys, so the scores must be bit-identical across bindings
    np.testing.assert_array_equal(np.asarray(res_a.scores), np.asarray(res_b.scores))

    # credit: cartpole pays reward 1 per step, so each solution's score IS
    # its episode's step count — per-group steps/episodes must match the
    # binding exactly
    scores = np.asarray(res_b.scores)
    gt = GroupTelemetry.from_array(np.asarray(res_b.telemetry))
    binding = np.asarray(groups_b)
    for g in (1, 2):
        row = gt.group(g)
        mask = binding == g
        assert row.episodes == int(mask.sum())
        assert row.env_steps == int(scores[mask].sum())


def test_resident_program_packs_two_tenants_bit_identical():
    """Tentpole acceptance at the substrate layer: one resident program,
    one dispatch, two tenants — per-tenant scores bit-identical to each
    tenant's standalone episodes_refill run with its own key."""
    env = _env()
    policy = _policy(env)
    n1, n2 = 3, 5
    v1, v2 = _values(policy, n1, 1), _values(policy, n2, 2)
    k1, k2 = jax.random.key(11), jax.random.key(22)

    ref1 = _standalone_refill(env, policy, v1, k1, refill_width=2)
    ref2 = _standalone_refill(env, policy, v2, k2, refill_width=3)

    program = make_resident_rollout_program(
        env, policy, num_groups=3, refill_width=4, num_episodes=1,
        seed_stride=n1 + n2,
    )
    slab = np.concatenate([v1, v2])
    lane_ids = np.asarray(list(range(n1)) + list(range(n2)), dtype=np.int32)
    groups = np.asarray([1] * n1 + [2] * n2, dtype=np.int32)
    kd1, kd2 = np.asarray(jax.random.key_data(k1)), np.asarray(jax.random.key_data(k2))
    solution_keys = jax.random.wrap_key_data(np.stack([kd1] * n1 + [kd2] * n2))
    out = program(slab, jax.random.key(0), None, lane_ids, groups, solution_keys)
    packed = np.asarray(out.scores)
    np.testing.assert_array_equal(packed[:n1], np.asarray(ref1.scores))
    np.testing.assert_array_equal(packed[n1:], np.asarray(ref2.scores))
    assert program.dispatches == 1
    assert program.key[2] == "episodes_refill"


# ---------------------------------------------------------------- server level


def test_server_packs_two_tenants_one_dispatch_bit_identical():
    env = _env()
    policy = _policy(env)
    n1, n2 = 3, 5
    v1, v2 = _values(policy, n1, 1), _values(policy, n2, 2)
    k1, k2 = jax.random.key(11), jax.random.key(22)

    server = EvalServer(env, policy, slab_size=n1 + n2, max_tenants=2)
    t1, t2 = server.admit("a"), server.admit("b")
    f1 = server.submit(t1, v1, key=k1)
    f2 = server.submit(t2, v2, key=k2)
    assert not f1.done() and not f2.done()
    server.drain()
    assert server.dispatches == 1  # both tenants rode ONE slab
    assert server.occupancy() == 1.0

    r1, r2 = f1.result(), f2.result()
    ref1 = _standalone_refill(env, policy, v1, k1, refill_width=2)
    ref2 = _standalone_refill(env, policy, v2, k2, refill_width=3)
    np.testing.assert_array_equal(np.asarray(r1.scores), np.asarray(ref1.scores))
    np.testing.assert_array_equal(np.asarray(r2.scores), np.asarray(ref2.scores))
    # per-tenant accounting: cartpole scores count steps 1:1
    assert r1.total_episodes == n1 and r2.total_episodes == n2
    assert r1.total_steps == int(np.asarray(r1.scores).sum())
    assert r2.total_steps == int(np.asarray(r2.scores).sum())


def test_server_padding_rows_stay_in_group_zero():
    env = _env()
    policy = _policy(env)
    server = EvalServer(env, policy, slab_size=8, max_tenants=2)
    tenant = server.admit()
    values = _values(policy, 5, 3)
    future = server.submit(tenant, values, key=jax.random.key(7))
    server.drain()
    result = future.result()
    # 3 idle rows were padded into group 0; the tenant's episode count must
    # not see them, and its scores still match standalone
    assert result.total_episodes == 5
    assert server.occupancy() == 5 / 8
    ref = _standalone_refill(env, policy, values, jax.random.key(7))
    np.testing.assert_array_equal(np.asarray(result.scores), np.asarray(ref.scores))


def test_server_churn_zero_steady_compiles():
    env = _env()
    policy = _policy(env)
    server = EvalServer(env, policy, slab_size=6, max_tenants=3)
    t1, t2 = server.admit("a"), server.admit("b")

    def round_trip(tenant, seed):
        future = server.submit(tenant, _values(policy, 3, seed), key=jax.random.key(seed))
        server.drain()
        return future.result()

    # warm twice: first dispatch compiles the program + the eager host-side
    # key plumbing; the second pins the steady state
    round_trip(t1, 1), round_trip(t2, 2)
    round_trip(t1, 3), round_trip(t2, 4)

    with track_compiles() as log:
        server.depart(t2)
        t3 = server.admit("c")  # reuses t2's group row
        round_trip(t1, 5)
        round_trip(t3, 6)
        # partial slab (padding path) and a multi-request pack
        fa = server.submit(t1, _values(policy, 2, 7), key=jax.random.key(7))
        fb = server.submit(t3, _values(policy, 3, 8), key=jax.random.key(8))
        server.drain()
        fa.result(), fb.result()
    assert log.count == 0, f"tenant churn retraced: {log.names}"


def test_server_obs_norm_slots_are_isolated():
    env = _env()
    policy = _policy(env)
    server = EvalServer(
        env, policy, slab_size=6, max_tenants=2, observation_normalization=True
    )
    t1, t2 = server.admit("a"), server.admit("b")
    f1 = server.submit(t1, _values(policy, 3, 1), key=jax.random.key(1))
    f2 = server.submit(t2, _values(policy, 3, 2), key=jax.random.key(2))
    server.drain()
    f1.result(), f2.result()
    s1, s2 = server.tenant_stats(t1), server.tenant_stats(t2)
    assert float(s1.count) > 0 and float(s2.count) > 0
    # the tenants saw different trajectories, so their slots must differ —
    # shared stats would make them equal
    assert not np.array_equal(np.asarray(s1.sum), np.asarray(s2.sum))
    # departure zeroes the slot; the other tenant's history is untouched
    before = np.asarray(s2.sum)
    server.depart(t1)
    np.testing.assert_array_equal(np.asarray(server.tenant_stats(t2).sum), before)
    # the freed row admits clean
    t3 = server.admit("c")
    assert t3.group == t1.group
    assert float(server.tenant_stats(t3).count) == 0.0


def test_server_slo_suspension_gates_submits_but_drains():
    env = _env()
    policy = _policy(env)
    # occupancy is <= 1.0 by construction, so a floor of 1.5 trips on the
    # first dispatch — a deterministic per-tenant violation
    server = EvalServer(
        env, policy, slab_size=4, max_tenants=2,
        slo=[{"kind": "occupancy_floor", "threshold": 1.5}],
    )
    tenant = server.admit("hot")
    f1 = server.submit(tenant, _values(policy, 4, 1), key=jax.random.key(1))
    f2 = server.submit(tenant, _values(policy, 4, 2), key=jax.random.key(2))
    served = server.step()  # first slab: trips the tenant's watchdog
    assert served == 4 and tenant.suspended
    with pytest.raises(RuntimeError, match="suspended"):
        server.submit(tenant, _values(policy, 4, 3))
    # queued work still drains — suspension never deadlocks futures
    server.drain()
    assert f1.done() and f2.done()
    assert np.isfinite(np.asarray(f2.result().scores)).all()
    status = server.status()["tenants"]["hot"]
    assert status["suspended"] and status["slo_ok"] is False


def test_server_depart_cancel_errors_pending_futures():
    env = _env()
    policy = _policy(env)
    server = EvalServer(env, policy, slab_size=4, max_tenants=2)
    tenant = server.admit()
    future = server.submit(tenant, _values(policy, 4, 1))
    with pytest.raises(RuntimeError, match="pending work"):
        server.depart(tenant)
    server.depart(tenant, cancel=True)
    with pytest.raises(RuntimeError, match="cancelled"):
        future.result()
    # the row is free again
    assert server.admit("next") is not None


def test_server_full_and_bad_submit_shapes():
    env = _env()
    policy = _policy(env)
    server = EvalServer(env, policy, slab_size=4, max_tenants=1)
    tenant = server.admit()
    with pytest.raises(RuntimeError, match="full"):
        server.admit()
    with pytest.raises(ValueError, match="values must be"):
        server.submit(tenant, np.zeros((3, policy.parameter_count + 1), np.float32))
    with pytest.raises(ValueError, match="not admitted"):
        other = EvalServer(env, policy, slab_size=4).admit()
        server.submit(other, _values(policy, 2, 0))


# ----------------------------------------------------------- admission polices


class _FakeTenant:
    def __init__(self, group, oldest, telemetry=None):
        self.group = group
        self._oldest = oldest
        self.telemetry = telemetry

    def oldest_pending_dispatch(self):
        return self._oldest


class _FakeWaits:
    def __init__(self, starvation, p99):
        self._starvation = starvation
        self._p99 = p99

    def starvation_share(self):
        return self._starvation

    def queue_wait_quantile(self, q):
        return self._p99


def test_admission_fifo_orders_by_oldest_pending():
    a = _FakeTenant(1, oldest=7)
    b = _FakeTenant(2, oldest=3)
    c = _FakeTenant(3, oldest=7)
    assert FIFOAdmission().order([a, b, c], None) == [b, a, c]


def test_admission_starvation_prioritizes_starved_tenants():
    fresh = _FakeTenant(1, oldest=0)  # no telemetry yet: FIFO rank
    starved = _FakeTenant(2, oldest=5, telemetry=_FakeWaits(0.5, 64.0))
    healthy = _FakeTenant(3, oldest=1, telemetry=_FakeWaits(0.0, 2.0))
    order = StarvationAwareAdmission().order([fresh, healthy, starved], None)
    assert order[0] is starved
    # tail wait breaks the zero-starvation tie: healthy has histogrammed
    # waits, fresh has none
    assert order == [starved, healthy, fresh]
    # bias floats telemetry-less newcomers over clean incumbents
    biased = StarvationAwareAdmission(bias=1.0).order([healthy, fresh], None)
    assert biased[0] is fresh


# -------------------------------------------------------------- VecNE backend


def _vecne(**kw):
    return VecNE(
        "cartpole",
        "Linear(obs_length, act_length)",
        env_config={"continuous_actions": True},
        seed=13,
        **kw,
    )


def _eval_scores(problem, values):
    batch = problem.generate_batch(len(values))
    batch.set_values(jnp.asarray(values))
    problem.evaluate(batch)
    return np.asarray(batch.evals[:, 0])


def _serving_server(max_tenants=2, slab=8, **kw):
    env = CartPole(continuous_actions=True)
    policy = FlatParamsPolicy(Linear(env.observation_size, env.action_size))
    return EvalServer(env, policy, slab_size=slab, max_tenants=max_tenants, **kw)


def test_remote_backend_bit_identical_to_standalone_vecne():
    """Acceptance: an unmodified VecNE through ``eval_backend=`` scores
    bit-identically to the same VecNE evaluating standalone."""
    server = _serving_server()
    rng = np.random.default_rng(0)
    ref = _vecne()
    values = rng.normal(size=(6, ref.solution_length)).astype(np.float32)
    expected = _eval_scores(ref, values)

    served = _vecne(eval_backend=RemoteEvalBackend(server, name="p1"))
    np.testing.assert_array_equal(_eval_scores(served, values), expected)
    assert served.eval_backend is not None
    assert server.dispatches >= 1

    # a second tenant on the SAME server — also bit-identical, and the
    # resident program keeps its identity (no second program)
    served2 = _vecne(eval_backend=server)  # coercion path: server -> backend
    values2 = rng.normal(size=(5, ref.solution_length)).astype(np.float32)
    expected2 = _eval_scores(_vecne(), values2)
    np.testing.assert_array_equal(_eval_scores(served2, values2), expected2)
    assert len(server.tenants) == 2


def test_remote_backend_rejects_contract_mismatch_and_groups():
    server = _serving_server()
    problem = _vecne(num_episodes=2)
    backend = RemoteEvalBackend(server, name="bad")
    rng = np.random.default_rng(1)
    values = jnp.asarray(rng.normal(size=(4, problem.solution_length)), jnp.float32)
    with pytest.raises(ValueError, match="num_episodes"):
        backend.evaluate(problem, values, jax.random.key(0))
    backend.close()
    with pytest.raises(ValueError, match="solution_groups"):
        _vecne(
            eval_backend=_serving_server(),
            solution_groups=np.zeros(4, dtype=np.int32),
        )
    with pytest.raises(TypeError, match="eval_backend"):
        _vecne(eval_backend=object())


def test_vecne_backend_churn_zero_steady_compiles():
    server = _serving_server(max_tenants=3)
    rng = np.random.default_rng(2)

    def fresh_problem(name):
        return _vecne(eval_backend=RemoteEvalBackend(server, name=name))

    p1 = fresh_problem("a")
    p2 = fresh_problem("b")
    n = 4
    # warm each problem TWICE (module docstring: the eager counter bump
    # compiles on the first two evaluations)
    for problem in (p1, p2):
        for _ in range(2):
            _eval_scores(problem, rng.normal(size=(n, p1.solution_length)).astype(np.float32))
    with track_compiles() as log:
        p2.eval_backend.close()
        p3 = fresh_problem("c")
        warm3 = rng.normal(size=(n, p1.solution_length)).astype(np.float32)
        _eval_scores(p3, warm3)  # new problem: its own counter warm-ups
        _eval_scores(p3, warm3)
    vecne_warmup = log.count  # p3's own eager counter compiles, if any
    with track_compiles() as log:
        _eval_scores(p1, rng.normal(size=(n, p1.solution_length)).astype(np.float32))
        _eval_scores(p3, rng.normal(size=(n, p1.solution_length)).astype(np.float32))
    assert log.count == 0, (
        f"backend churn retraced: {log.names} (warmup had {vecne_warmup})"
    )


# ------------------------------------------------------------------ stdio front


def test_stdio_protocol_roundtrip():
    server = _serving_server(slab=4)
    params = server.policy.parameter_count
    lines = [
        {"op": "admit", "tenant": "cli"},
        {
            "op": "submit", "tenant": "cli", "id": "s1",
            "values": [[0.0] * params for _ in range(3)], "seed": 5,
        },
        {"op": "poll", "request_id": 0},
        {"op": "result", "request_id": 0},
        {"op": "status"},
        {"op": "nope"},
        {"op": "depart", "tenant": "cli"},
        {"op": "shutdown"},
        {"op": "never-reached"},
    ]
    infile = io.StringIO("\n".join(json.dumps(l) for l in lines) + "\n")
    outfile = io.StringIO()
    handled = serve_stdio(server, infile, outfile)
    out = [json.loads(l) for l in outfile.getvalue().splitlines()]
    assert handled == 8  # shutdown consumed, trailing line never read
    admit, submit, poll, result, status, bogus, depart, shutdown = out
    assert admit == {"ok": True, "op": "admit", "tenant": "cli", "group": 1}
    assert submit["ok"] and submit["request_id"] == 0 and submit["id"] == "s1"
    assert poll["done"] is False  # nothing served yet
    assert result["ok"] and len(result["scores"]) == 3
    assert result["env_steps"] > 0 and "queue_wait_p99" in result
    assert status["ok"] and status["tenants"]["cli"]["requests_served"] == 1
    assert bogus["ok"] is False and "unknown op" in bogus["error"]
    assert depart["ok"] and shutdown == {"ok": True, "op": "shutdown"}


def test_stdio_errors_do_not_kill_the_server():
    server = _serving_server(slab=4)
    infile = io.StringIO(
        "not json\n"
        + json.dumps({"op": "submit", "tenant": "ghost", "values": [[0.0]]})
        + "\n"
        + json.dumps({"op": "admit", "tenant": "ok"})
        + "\n"
    )
    outfile = io.StringIO()
    serve_stdio(server, infile, outfile)
    out = [json.loads(l) for l in outfile.getvalue().splitlines()]
    assert out[0]["ok"] is False and out[1]["ok"] is False
    assert out[2]["ok"] is True and out[2]["tenant"] == "ok"


# ----------------------------------------------------------------- SLO plumbing


def test_tuned_cache_writes_are_atomic(tmp_path):
    from evotorch_tpu.observability.timings import (
        TunedEntry,
        lookup_tuned,
        save_tuned_entry,
    )
    from evotorch_tpu.resilience import faults

    cache = tmp_path / "tuned_configs.json"
    machine = {"host": "testbox"}
    entry = TunedEntry(
        group="refill",
        shape={"env": "cartpole", "popsize": 8},
        machine=machine,
        config={"refill_width": 4},
    )
    # one injected write fault: the retry site must absorb it and the final
    # file must be whole (tmp-file + fsync + rename; no partial JSON)
    faults.configure("timings.write:raise@1")
    try:
        save_tuned_entry(entry, path=cache)
    finally:
        faults.configure(None)
    assert json.loads(cache.read_text())  # whole, parseable
    assert not list(tmp_path.glob("*.tmp.*")), "tmp residue left behind"
    loaded = lookup_tuned("refill", entry.shape, machine=machine, path=cache)
    assert loaded is not None and loaded.config == {"refill_width": 4}


# ------------------------------------------------------------------- slow soak


@pytest.mark.slow
def test_server_soak_random_churn_stays_resident():
    """30 rounds of random admit/depart/submit against one server: every
    future completes, occupancy accounting stays consistent, and after the
    warm rounds the resident program never recompiles."""
    env = _env()
    policy = _policy(env)
    server = EvalServer(
        env, policy, slab_size=8, max_tenants=3, admission="starvation"
    )
    rng = np.random.default_rng(0)
    tenants = [server.admit(f"t{i}") for i in range(3)]
    seeds = iter(range(1, 1000))

    def submit_random(tenant):
        n = int(rng.integers(1, 7))
        seed = next(seeds)
        return server.submit(tenant, _values(policy, n, seed), key=jax.random.key(seed))

    # warm rounds
    for _ in range(2):
        futures = [submit_random(t) for t in tenants]
        server.drain()
        assert all(f.done() for f in futures)

    with track_compiles() as log:
        for round_idx in range(30):
            action = rng.integers(0, 4)
            if action == 0 and len(tenants) > 1:
                victim = tenants.pop(int(rng.integers(0, len(tenants))))
                server.depart(victim, cancel=True)
            elif action == 1 and len(tenants) < 3:
                tenants.append(server.admit(f"r{round_idx}"))
            futures = [submit_random(t) for t in tenants]
            server.drain()
            assert all(f.done() for f in futures)
    assert log.count == 0, f"soak churn retraced: {log.names}"
    assert 0.0 < server.occupancy() <= 1.0
    assert server.items_served <= server.dispatches * server.slab_size
