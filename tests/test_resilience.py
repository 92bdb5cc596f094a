"""Fault tolerance (ISSUE 17; docs/resilience.md): durable run bundles,
non-finite score quarantine in every eval contract, retry/backoff at the
host edges, and the deterministic ``EVOTORCH_FAULTS`` harness.

The contract under test is three-legged: a SIGKILL at any instant costs at
most one checkpoint interval (and the resumed trajectory is BIT-IDENTICAL
to the uninterrupted one); one diverged rollout cannot NaN-poison ranking
(scores are scrubbed inside the same jitted program, counted in telemetry,
and the counts are sharding-invariant); and every recovery path stays
exercised because faults are injectable deterministically.
"""

import json
import os
import pickle
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.envs.base import Env, EnvState, Space
from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.neuroevolution.net.vecrl import (
    _quarantine_nonfinite,
    run_vectorized_rollout,
    run_vectorized_rollout_compacting,
)
from evotorch_tpu.observability import GroupTelemetry
from evotorch_tpu.observability.registry import counters
from evotorch_tpu.resilience import (
    BUNDLE_SCHEMA_VERSION,
    CorruptBundleError,
    InjectedFault,
    RunCheckpointer,
    configure,
    fault_point,
    parse_spec,
    retry_call,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CPU_ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    XLA_FLAGS="--xla_force_host_platform_device_count=8",
)


@pytest.fixture(autouse=True)
def _clear_faults():
    # fault rules are process-global; no test may leak its spec
    yield
    configure(None)


# ---------------------------------------------------------------------------
# a deterministic diverging environment: non-finite rewards keyed purely on
# the policy parameters, so specific lanes diverge on purpose
# ---------------------------------------------------------------------------


class DivergingEnv(Env):
    """reward = action; actions above 2 produce NaN, below -2 produce +inf.

    With a ``Linear`` policy over an all-zero observation the action is the
    bias alone, so a population row filled with the constant ``c`` yields
    per-step reward ``c`` (finite) or NaN/inf — the non-finite lanes are
    chosen exactly by the parameter values."""

    max_episode_steps = 4

    def __init__(self):
        self.observation_space = Space(shape=(2,))
        self.action_space = Space(
            shape=(1,), lb=jnp.array([-10.0]), ub=jnp.array([10.0])
        )

    def reset(self, key):
        key, _ = jax.random.split(key)
        obs = jnp.zeros(2)
        return EnvState(obs_state=obs, t=jnp.zeros((), jnp.int32), key=key), obs

    def step(self, state, action):
        from dataclasses import replace

        a = jnp.reshape(action, ())
        reward = jnp.where(a > 2.0, jnp.nan, jnp.where(a < -2.0, jnp.inf, a))
        t = state.t + 1
        obs = jnp.zeros(2)
        done = t >= self.max_episode_steps
        return replace(state, t=t), obs, reward, done


def _diverging_setup(biases):
    env = DivergingEnv()
    policy = FlatParamsPolicy(Linear(env.observation_size, env.action_size))
    values = jnp.stack(
        [jnp.full(policy.parameter_count, b, jnp.float32) for b in biases]
    )
    stats = RunningNorm(env.observation_size).stats
    return env, policy, values, stats


# 3 of 8 lanes diverge (one NaN-high, one inf, one NaN-high); the finite
# lanes' scores are their bias values, so worst-finite == -1.5
_BIASES = (-1.5, 0.5, 3.0, -3.0, 1.5, 3.5, 0.0, -0.5)
_BAD = np.array([b > 2.0 or b < -2.0 for b in _BIASES])


_MODE_KWARGS = {
    "budget": {},
    "episodes": {},
    "episodes_refill": {"refill_width": 2, "refill_period": 1},
}


@pytest.mark.parametrize("eval_mode", sorted(_MODE_KWARGS))
def test_quarantine_scrubs_nonfinite_scores(eval_mode):
    env, policy, values, stats = _diverging_setup(_BIASES)
    kwargs = dict(
        num_episodes=1, episode_length=4, eval_mode=eval_mode,
        **_MODE_KWARGS[eval_mode],
    )
    off = run_vectorized_rollout(
        env, policy, values, jax.random.key(0), stats, **kwargs
    )
    on = run_vectorized_rollout(
        env, policy, values, jax.random.key(0), stats,
        nonfinite_quarantine=True, **kwargs,
    )
    raw = np.asarray(off.scores)
    scrubbed = np.asarray(on.scores)
    assert not np.isfinite(raw[_BAD]).any()  # the env really diverged
    assert np.isfinite(scrubbed).all()
    # finite lanes ride through BIT-identically; bad lanes get worst-finite
    np.testing.assert_array_equal(scrubbed[~_BAD], raw[~_BAD])
    worst = raw[~_BAD].min()
    np.testing.assert_array_equal(scrubbed[_BAD], np.full(_BAD.sum(), worst))
    # counted in the telemetry's nonfinite slot — and only when quarantining
    assert GroupTelemetry.from_array(on.telemetry).total().nonfinite == _BAD.sum()
    assert GroupTelemetry.from_array(off.telemetry).total().nonfinite == 0


def test_quarantine_compacting_contract():
    env, policy, values, stats = _diverging_setup(_BIASES)
    kwargs = dict(num_episodes=1, episode_length=4, chunk_size=2, allowed_widths=(1,))
    off = run_vectorized_rollout_compacting(
        env, policy, values, jax.random.key(0), stats, **kwargs
    )
    on = run_vectorized_rollout_compacting(
        env, policy, values, jax.random.key(0), stats,
        nonfinite_quarantine=True, **kwargs,
    )
    raw, scrubbed = np.asarray(off.scores), np.asarray(on.scores)
    assert not np.isfinite(raw[_BAD]).any()
    assert np.isfinite(scrubbed).all()
    np.testing.assert_array_equal(scrubbed[~_BAD], raw[~_BAD])
    np.testing.assert_array_equal(
        scrubbed[_BAD], np.full(_BAD.sum(), raw[~_BAD].min())
    )
    assert GroupTelemetry.from_array(on.telemetry).total().nonfinite == _BAD.sum()


def test_quarantine_fixed_penalty():
    env, policy, values, stats = _diverging_setup(_BIASES)
    r = run_vectorized_rollout(
        env, policy, values, jax.random.key(0), stats,
        num_episodes=1, episode_length=4, eval_mode="episodes",
        nonfinite_quarantine=True, nonfinite_penalty=-100.0,
    )
    scores = np.asarray(r.scores)
    np.testing.assert_array_equal(scores[_BAD], np.full(_BAD.sum(), -100.0))
    assert np.isfinite(scores).all()


def test_quarantine_identity_on_finite_scores():
    # the default-on contract: an all-finite population is BIT-untouched
    env, policy, values, stats = _diverging_setup((0.5, -0.5, 1.0, -1.0))
    kwargs = dict(num_episodes=1, episode_length=4, eval_mode="episodes")
    off = run_vectorized_rollout(
        env, policy, values, jax.random.key(1), stats, **kwargs
    )
    on = run_vectorized_rollout(
        env, policy, values, jax.random.key(1), stats,
        nonfinite_quarantine=True, **kwargs,
    )
    np.testing.assert_array_equal(np.asarray(on.scores), np.asarray(off.scores))
    assert GroupTelemetry.from_array(on.telemetry).total().nonfinite == 0


def test_quarantine_per_group_counts():
    env, policy, values, stats = _diverging_setup(_BIASES)
    groups = jnp.asarray([0, 0, 0, 1, 1, 1, 0, 1], jnp.int32)
    r = run_vectorized_rollout(
        env, policy, values, jax.random.key(0), stats,
        num_episodes=1, episode_length=4, eval_mode="episodes",
        nonfinite_quarantine=True, groups=groups, num_groups=2,
    )
    t = GroupTelemetry.from_array(r.telemetry)
    per_group = [
        int(np.sum(_BAD[np.asarray(groups) == g])) for g in range(2)
    ]
    assert [t.group(g).nonfinite for g in range(2)] == per_group
    assert t.total().nonfinite == _BAD.sum()
    assert t.nonfinite_share(group=None) > 0.0


def test_quarantine_helper_all_nonfinite_and_valid_mask():
    scores = jnp.asarray([jnp.nan, jnp.inf, -jnp.inf])
    out, bad = _quarantine_nonfinite(scores)
    # no finite score to borrow: the fallback replacement is 0.0
    np.testing.assert_array_equal(np.asarray(out), np.zeros(3))
    assert int(bad.sum()) == 3
    # padding lanes are scrubbed (so downstream stays finite) but NOT counted
    scores = jnp.asarray([1.0, -5.0, jnp.nan, jnp.nan])
    valid = jnp.asarray([True, True, True, False])
    out, bad = _quarantine_nonfinite(scores, valid_mask=valid)
    assert np.isfinite(np.asarray(out)).all()
    assert int(bad.sum()) == 1


# ---------------------------------------------------------------------------
# sharding invariance: quarantined scores AND counts are mesh-independent
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh_shape", [{"pop": 8}, {"pop": 4, "model": 2}])
@pytest.mark.parametrize("eval_mode", ["budget", "episodes_refill"])
def test_quarantine_sharded_bit_identity(mesh_shape, eval_mode):
    from evotorch_tpu.parallel import make_mesh, make_sharded_rollout_evaluator

    biases = _BIASES + (2.5, -2.5, 0.25, -0.25, 5.0, 1.0, -1.0, 0.75)
    bad = np.array([b > 2.0 or b < -2.0 for b in biases])
    env, policy, values, stats = _diverging_setup(biases)
    kwargs = dict(
        num_episodes=1, episode_length=4, eval_mode=eval_mode,
        nonfinite_quarantine=True, **_MODE_KWARGS[eval_mode],
    )
    ref = run_vectorized_rollout(
        env, policy, values, jax.random.key(3), stats, **kwargs
    )
    ev = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh(mesh_shape), **kwargs
    )
    result, _ = ev(values, jax.random.key(3), stats)
    np.testing.assert_array_equal(
        np.asarray(result.scores), np.asarray(ref.scores)
    )
    assert np.isfinite(np.asarray(result.scores)).all()
    n_ref = GroupTelemetry.from_array(ref.telemetry).total().nonfinite
    n_sharded = GroupTelemetry.from_array(result.telemetry).total().nonfinite
    assert n_ref == n_sharded == bad.sum()


# ---------------------------------------------------------------------------
# VecNE integration: default-on quarantine, status keys, score injection
# ---------------------------------------------------------------------------


def _small_vecne(**kwargs):
    from evotorch_tpu.neuroevolution import VecNE

    return VecNE(
        "cartpole",
        "Linear(obs_length, act_length)",
        env_config={"continuous_actions": True},
        episode_length=10,
        eval_mode="episodes",
        seed=11,
        **kwargs,
    )


def test_vecne_quarantine_default_on_and_status_share():
    from evotorch_tpu.core import SolutionBatch

    p = _small_vecne()
    assert p._nonfinite_quarantine is True
    batch = SolutionBatch(p, 8)
    p.evaluate(batch)
    # telemetry-derived status is lag-by-one (one metered fetch per
    # generation): the share of eval #1 surfaces after eval #2
    p.evaluate(SolutionBatch(p, 8))
    assert float(p.status["eval_nonfinite_share"]) == 0.0
    assert np.isfinite(np.asarray(batch.evals)).all()


def test_vecne_injected_nonfinite_scores_are_quarantined():
    from evotorch_tpu.core import SolutionBatch

    configure("eval.scores:nonfinite@1+:0.25")
    before = counters.get("faults.injected_nonfinite")
    p = _small_vecne()
    batch = SolutionBatch(p, 8)
    p.evaluate(batch)
    # the injected NaNs were replaced by the same rule the engines compile
    assert np.isfinite(np.asarray(batch.evals)).all()
    assert counters.get("faults.injected_nonfinite") - before >= 2


def test_injected_nan_quarantine_keeps_improving(monkeypatch):
    # the load-bearing value claim: with 25% of every generation's scores
    # NaN, a quarantined run keeps optimizing while the pre-resilience
    # configuration (no quarantine, unguarded ranking) NaN-poisons the
    # distribution and stalls forever. The rank() guard is disabled for BOTH
    # arms so the contrast isolates the quarantine itself, and the ranking is
    # "raw" — the method where fitness values reach the utilities unshaped
    # (centered/linear argsort any NaN into a finite rank by construction).
    import evotorch_tpu.tools.ranking as ranking_mod
    from evotorch_tpu.algorithms.functional import pgpe, pgpe_ask, pgpe_tell

    monkeypatch.setattr(
        ranking_mod, "_nonfinite_to_worst", lambda x, **kw: x
    )

    def run(quarantined):
        state = pgpe(
            center_init=jnp.full(4, 3.0),
            center_learning_rate=0.3,
            stdev_learning_rate=0.1,
            stdev_init=0.5,
            objective_sense="max",
            ranking_method="raw",
        )
        key = jax.random.key(5)
        first = last = None
        for _ in range(12):
            key, sub = jax.random.split(key)
            pop = pgpe_ask(sub, state, popsize=32)
            fits = -jnp.sum(pop**2, axis=-1)
            clean_mean = float(jnp.mean(fits))
            fits = fits.at[::4].set(jnp.nan)  # every 4th solution diverges
            if quarantined:
                fits, _ = _quarantine_nonfinite(fits)
            state = pgpe_tell(state, pop, fits)
            if first is None:
                first = clean_mean
            last = clean_mean
        return first, last, state

    first_q, last_q, _ = run(quarantined=True)
    assert np.isfinite(last_q) and last_q > first_q  # still optimizing

    _, last_raw, state_raw = run(quarantined=False)
    # NaN utilities poison the center: the unquarantined run is dead
    assert not np.isfinite(np.asarray(state_raw.stdev)).all() or not np.isfinite(
        last_raw
    )


# ---------------------------------------------------------------------------
# ranking guard (defense in depth below the quarantine)
# ---------------------------------------------------------------------------


def test_rank_guard_sanitizes_nonfinite():
    from evotorch_tpu.tools.ranking import rank

    dirty = jnp.asarray([1.0, jnp.nan, 3.0, -jnp.inf, 2.0])
    clean = jnp.asarray([1.0, 1.0, 3.0, 1.0, 2.0])  # worst finite = 1.0
    for method in ("centered", "linear", "raw"):
        np.testing.assert_array_equal(
            np.asarray(rank(dirty, method, higher_is_better=True)),
            np.asarray(rank(clean, method, higher_is_better=True)),
        )
    # minimizing: the worst FINITE value is the max
    clean_min = jnp.asarray([1.0, 3.0, 3.0, 3.0, 2.0])
    np.testing.assert_array_equal(
        np.asarray(rank(dirty, "centered", higher_is_better=False)),
        np.asarray(rank(clean_min, "centered", higher_is_better=False)),
    )
    # the reference's unguarded semantics remain reachable
    unguarded = rank(
        dirty, "raw", higher_is_better=True, guard_nonfinite=False
    )
    assert np.isnan(np.asarray(unguarded)).any()


# ---------------------------------------------------------------------------
# SLO rule: max_nonfinite_share
# ---------------------------------------------------------------------------


def test_slo_max_nonfinite_share_rule():
    from evotorch_tpu.observability.slo import SLOWatchdog

    dog = SLOWatchdog([{"kind": "max_nonfinite_share", "threshold": 0.1}])
    ok = dog.check(None, status={"eval_nonfinite_share": 0.05})
    assert ok.ok and ok.checked == 1
    bad = dog.check(None, status={"eval_nonfinite_share": 0.5})
    assert not bad.ok and "nonfinite_share" in bad.violations[0]
    # no status key + no telemetry: rule skips (missing data is not a fail)
    assert dog.check(None, status={}).checked == 0


# ---------------------------------------------------------------------------
# durable run bundles
# ---------------------------------------------------------------------------


def test_bundle_roundtrip_and_registry_snapshot(tmp_path):
    ck = RunCheckpointer(tmp_path)
    ck.save(3, {"x": np.arange(4), "note": "gen three"})
    ck.save(7, {"x": np.arange(5), "note": "gen seven"})
    gen, state = ck.load_latest()
    assert gen == 7 and state["note"] == "gen seven"
    np.testing.assert_array_equal(state["x"], np.arange(5))
    # the payload carries schema/git/registry metadata beyond the state
    blob = open(ck.bundle_paths()[-1], "rb").read()
    record = pickle.loads(blob[8 + 32 :])
    assert record["schema"] == BUNDLE_SCHEMA_VERSION
    assert isinstance(record["registry"], dict)


def test_bundle_retention_keeps_last_k(tmp_path):
    ck = RunCheckpointer(tmp_path, keep=2)
    for gen in range(1, 6):
        ck.save(gen, {"gen": gen})
    names = [os.path.basename(p) for p in ck.bundle_paths()]
    assert names == ["bundle_00000004.ckpt", "bundle_00000005.ckpt"]


def test_bundle_cadence(tmp_path):
    ck = RunCheckpointer(tmp_path, every=3)
    for gen in range(1, 8):
        ck.maybe_save(gen, {"gen": gen})
    names = [os.path.basename(p) for p in ck.bundle_paths()]
    assert names == ["bundle_00000003.ckpt", "bundle_00000006.ckpt"]


def test_bundle_corrupt_fallback(tmp_path):
    ck = RunCheckpointer(tmp_path)
    ck.save(1, {"gen": 1})
    ck.save(2, {"gen": 2})
    newest = ck.bundle_paths()[-1]
    blob = open(newest, "rb").read()
    open(newest, "wb").write(blob[: len(blob) // 2])  # truncated write
    before = counters.get("checkpoint.corrupt_skipped")
    gen, state = ck.load_latest()
    assert (gen, state["gen"]) == (1, 1)  # one interval lost, not the run
    assert counters.get("checkpoint.corrupt_skipped") == before + 1
    # every bundle corrupt -> None (fresh start), never an exception
    open(ck.bundle_paths()[0], "wb").write(b"garbage")
    assert ck.load_latest() is None


def test_bundle_verification_errors(tmp_path):
    ck = RunCheckpointer(tmp_path)
    path = ck.save(1, {"gen": 1})
    blob = open(path, "rb").read()
    with pytest.raises(CorruptBundleError, match="magic|truncated"):
        bad = tmp_path / "bundle_00000009.ckpt"
        bad.write_bytes(b"NOTMAGIC" + blob[8:])
        RunCheckpointer.read_bundle(str(bad))
    with pytest.raises(CorruptBundleError, match="SHA-256"):
        flipped = bytearray(blob)
        flipped[-1] ^= 0xFF
        bad.write_bytes(bytes(flipped))
        RunCheckpointer.read_bundle(str(bad))
    # a NEWER schema is refused (an older reader cannot know what it means)
    payload = pickle.dumps({"schema": BUNDLE_SCHEMA_VERSION + 1, "generation": 1, "state": {}})
    import hashlib

    bad.write_bytes(b"EVTRUNB1" + hashlib.sha256(payload).digest() + payload)
    with pytest.raises(CorruptBundleError, match="schema"):
        RunCheckpointer.read_bundle(str(bad))


def test_save_searcher_atomic_and_corrupt_message(tmp_path):
    from evotorch_tpu.checkpoint import load_searcher, save_searcher

    path = tmp_path / "searcher.pickle"
    save_searcher(str(path), {"stand-in": "object"})
    assert load_searcher(str(path)) == {"stand-in": "object"}
    assert not os.path.exists(str(path) + ".tmp")  # tmp renamed away
    path.write_bytes(path.read_bytes()[:-4])  # truncated pickle
    with pytest.raises(RuntimeError, match="corrupt or truncated"):
        load_searcher(str(path))


def test_whole_searcher_pickle_roundtrip_with_dsl_activations():
    # jnp.tanh does not pickle by qualified name on this jax; the layer
    # __reduce__ hooks keep the default network DSL checkpointable
    from evotorch_tpu.neuroevolution.net import ReLU, Sigmoid, Softmax, Tanh

    for mod in (Tanh(), ReLU(), Sigmoid(), Softmax(axis=-1)):
        clone = pickle.loads(pickle.dumps(mod))
        x = jnp.asarray([-1.0, 0.5])
        out, _ = clone.apply((), x)
        ref, _ = mod.apply((), x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# fault injection harness
# ---------------------------------------------------------------------------


def test_parse_spec_grammar():
    rules = parse_spec("a.b:raise@2; hostpool.worker:kill@1:3 ;x:nonfinite@4+:0.5")
    assert [(r.site, r.kind, r.at, r.arg, r.sticky) for r in rules] == [
        ("a.b", "raise", 2, None, False),
        ("hostpool.worker", "kill", 1, "3", False),
        ("x", "nonfinite", 4, "0.5", True),
    ]
    assert rules[2].float_arg(0.0) == 0.5
    assert rules[0].float_arg(0.25) == 0.25
    for bad in ("nosite@1", "a:b", "a:b@x"):
        with pytest.raises(ValueError, match="EVOTORCH_FAULTS"):
            parse_spec(bad)


def test_fault_point_fires_at_nth_and_sticky():
    configure("s:raise@2;t:kill@1+")
    assert fault_point("s") is None  # invocation 1: no fire
    with pytest.raises(InjectedFault):
        fault_point("s")  # invocation 2: fires
    assert fault_point("s") is None  # @N (non-sticky) fired once, done
    for _ in range(3):  # sticky fires every time from the N-th on
        rule = fault_point("t")
        assert rule is not None and rule.kind == "kill"
    assert fault_point("unrelated.site") is None


def test_fault_counters_and_clear():
    before = counters.get("faults.fired.c.kill")
    configure("c:kill@1")
    assert fault_point("c").kind == "kill"
    assert counters.get("faults.fired.c.kill") == before + 1
    configure(None)  # back to (empty) env spec
    assert fault_point("c") is None


# ---------------------------------------------------------------------------
# retry / backoff
# ---------------------------------------------------------------------------


def test_retry_succeeds_after_transient_failures():
    calls = {"n": 0}

    def flaky(value):
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return value * 2

    before = counters.snapshot(("retry.t.attempts", "retry.t.retries"))
    out = retry_call(flaky, 21, site="t", retries=3, base_delay=0.001)
    assert out == 42 and calls["n"] == 3
    delta = counters.delta(before)
    assert delta["retry.t.attempts"] == 3
    assert delta["retry.t.retries"] == 2


def test_retry_gives_up_and_reraises_original():
    def always_fails():
        raise OSError("permanent")

    before = counters.get("retry.g.giveups")
    with pytest.raises(OSError, match="permanent"):
        retry_call(always_fails, site="g", retries=2, base_delay=0.001)
    assert counters.get("retry.g.giveups") == before + 1


def test_retry_sites_are_fault_injectable():
    # the harness integration: an injected fault at the site consumes one
    # attempt, then the real call succeeds — no caller cooperation needed
    configure("io.op:raise@1")
    out = retry_call(lambda: "ok", site="io.op", retries=2, base_delay=0.001)
    assert out == "ok"


def test_retry_does_not_catch_unlisted_exceptions():
    with pytest.raises(KeyError):
        retry_call(
            lambda: {}["missing"], site="u", retries=3, base_delay=0.001
        )


# ---------------------------------------------------------------------------
# MetricsHub: nonfinite export + crash-safe feed
# ---------------------------------------------------------------------------


def test_metricshub_exports_nonfinite(tmp_path):
    from evotorch_tpu.observability.metricshub import MetricsHub

    env, policy, values, stats = _diverging_setup(_BIASES)
    r = run_vectorized_rollout(
        env, policy, values, jax.random.key(0), stats,
        num_episodes=1, episode_length=4, eval_mode="episodes",
        nonfinite_quarantine=True,
    )
    telemetry = GroupTelemetry.from_array(r.telemetry)
    path = tmp_path / "feed.jsonl"
    hub = MetricsHub(str(path), manifest={"source": "test"})
    hub.emit({"gen": 1, "mean_eval": 1.0}, telemetry=telemetry)
    rows = [json.loads(line) for line in open(path)]
    assert "manifest" in rows[0]
    data = rows[1]
    assert data["eval_nonfinite"] == int(_BAD.sum())
    # every line the writer produced is complete JSON (fsync'd append path)
    for line in open(path):
        json.loads(line)


# ---------------------------------------------------------------------------
# slow tier: process-level fault tolerance
# ---------------------------------------------------------------------------


_CURVE_ARGS = [
    "--env", "cartpole", "--cpu", "--popsize", "16", "--episode-length", "20",
    "--eval-every", "4", "--eval-episodes", "2", "--checkpoint-every", "2",
]


def _run_curve(tmp_path, tag, generations, wait_then_kill=None):
    out = tmp_path / f"{tag}.jsonl"
    cmd = [
        sys.executable, os.path.join(_REPO, "examples", "locomotion_curve.py"),
        *_CURVE_ARGS, "--generations", str(generations),
        "--checkpoint-dir", str(tmp_path / f"ck_{tag}"), "--out", str(out),
    ]
    if wait_then_kill is None:
        proc = subprocess.run(
            cmd, env=_CPU_ENV, check=True, timeout=600, capture_output=True,
            text=True,
        )
        return out, proc.stdout
    proc = subprocess.Popen(
        cmd, env=_CPU_ENV, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL
    )
    try:
        # SIGKILL the instant the bundle appears: generation wait_then_kill+2
        # is the first --eval-every generation, whose center-eval program
        # compiles for seconds — the kill reliably lands mid-run
        marker = tmp_path / f"ck_{tag}" / f"bundle_{wait_then_kill:08d}.ckpt"
        deadline = time.monotonic() + 540
        while not marker.exists():
            assert proc.poll() is None, "curve process exited before the kill"
            assert time.monotonic() < deadline, "bundle never appeared"
            time.sleep(0.2)
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.wait(timeout=60)
    return out, None


def _curve_rows(*paths):
    rows = {}
    for path in paths:
        for line in open(path):
            row = json.loads(line)
            if "gen" in row:
                rows[row["gen"]] = row  # duplicates after resume: last wins
    return rows


@pytest.mark.slow
def test_sigkill_mid_curve_resume_is_bit_identical(tmp_path):
    # the tentpole acceptance: SIGKILL the curve mid-run, re-launch with the
    # same checkpoint dir, and the completed trajectory matches the never-
    # killed run BIT for bit on every deterministic column
    ref, _ = _run_curve(tmp_path, "ref", generations=8)
    _run_curve(tmp_path, "killed", generations=8, wait_then_kill=2)
    resumed, stdout = _run_curve(tmp_path, "killed", generations=8)  # same dir
    assert "resumed_from_generation" in stdout  # resume really happened
    a, b = _curve_rows(ref), _curve_rows(resumed)
    assert sorted(a) == sorted(b) == list(range(1, 9))
    for gen in a:
        for key in ("mean_eval", "best_eval", "stdev_norm", "clipup_velocity_norm"):
            assert a[gen].get(key) == b[gen].get(key), (gen, key)
        if a[gen].get("center_full") is not None and b[gen].get("center_full") is not None:
            assert a[gen]["center_full"] == b[gen]["center_full"]


def _slow_sphere_row(row):
    # module-level (worker processes unpickle the objective); slow enough
    # that pieces are still in flight when the injected kill lands AND that
    # the result-queue poll times out at least once (the death detector)
    time.sleep(0.3)
    return float(np.sum(np.asarray(row) ** 2))


@pytest.mark.slow
def test_hostpool_worker_death_respawns_and_completes():
    from evotorch_tpu.core import Problem

    sphere = _slow_sphere_row

    configure("hostpool.worker:kill@1")
    before = counters.snapshot(
        ("hostpool.worker_deaths", "hostpool.respawns", "hostpool.redispatched_pieces")
    )
    p = Problem(
        "min", sphere, solution_length=4, initial_bounds=(-1, 1), num_actors=2
    )
    try:
        batch = p.generate_batch(8)
        p.evaluate(batch)  # worker 0 is SIGKILLed right after dispatch
        expected = np.sum(np.asarray(batch.values) ** 2, axis=-1)
        np.testing.assert_allclose(
            np.asarray(batch.evals[:, 0]), expected, atol=1e-5
        )
        delta = counters.delta(before)
        assert delta["hostpool.worker_deaths"] >= 1
        assert delta["hostpool.respawns"] >= 1
        assert p._host_pool.is_alive()
    finally:
        p.kill_actors()


@pytest.mark.slow
def test_quarantine_overhead_refill_contract():
    # acceptance A/B: always-on quarantine must be ~free on the refill
    # contract. Interleaved samples, medians — this box times ±20% run to
    # run (CLAUDE.md), so the assert uses a variance-tolerant ceiling; the
    # measured median ratio is printed for the record.
    from evotorch_tpu.envs import CartPole

    env = CartPole(continuous_actions=True)
    policy = FlatParamsPolicy(Linear(env.observation_size, env.action_size))
    values = 0.1 * jax.random.normal(
        jax.random.key(0), (256, policy.parameter_count)
    )
    stats = RunningNorm(env.observation_size).stats
    kwargs = dict(
        num_episodes=1, episode_length=100, eval_mode="episodes_refill",
        refill_width=32, refill_period=1,
    )

    def run(quarantine):
        r = run_vectorized_rollout(
            env, policy, values, jax.random.key(1), stats,
            nonfinite_quarantine=quarantine, **kwargs,
        )
        jax.block_until_ready(r.scores)
        return r

    run(False), run(True)  # warm both programs
    compile_mark = counters.snapshot(("compiles",))
    samples = {False: [], True: []}
    for _ in range(5):
        for flag in (False, True):  # interleaved: drift hits both arms
            t0 = time.perf_counter()
            run(flag)
            samples[flag].append(time.perf_counter() - t0)
    # the timed loops must be retrace-free or the numbers mean nothing
    assert counters.delta(compile_mark).get("compiles", 0) == 0
    import statistics

    ratio = statistics.median(samples[True]) / statistics.median(samples[False])
    print(f"quarantine overhead ratio (refill contract): {ratio:.4f}")
    assert ratio <= 1.15  # target is 1.02; ceiling absorbs box variance
