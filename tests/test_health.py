"""Search-health plane (docs/observability.md "Search health").

The contracts under test:

- the v4 wire decodes next to every older schema (golden vectors for
  v1 ``(6,)`` / v2 ``(G, 14)`` / v3 ``(G, 15)`` / v4 ``(G, 20)``), and the
  health block combines Chan-style under ``__add__``;
- ``health=False`` compiles a DISTINCT, v3 byte-compatible program, and
  both variants run retrace-free in steady state;
- the per-group health rows are bit-identical unsharded vs 1-D vs 2-D
  mesh, including a padded indivisible popsize;
- the EWMA trend detectors are variance-gated (a noisy-but-progressing
  stream never stalls; a flat one does) and serialize round-trip;
- the plateau / stdev_collapse / score_snr_floor rules trip on injected
  degeneracy with named violations while a healthy run stays ``slo_ok``;
- the ``telemetry-schema`` graftlint checker flags hard-coded column
  literals outside devicemetrics.py.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from evotorch_tpu.analysis import assert_compiles, track_compiles
from evotorch_tpu.envs import CartPole
from evotorch_tpu.neuroevolution.net import (
    FlatParamsPolicy,
    Linear,
    Tanh,
    run_vectorized_rollout,
)
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.observability import (
    EvalTelemetry,
    GroupTelemetry,
    HEALTH_TELEMETRY_WIDTH,
    HEALTH_WIDTH,
    Rule,
    SLOWatchdog,
    append_health_block,
    compute_health_block,
)
from evotorch_tpu.observability.devicemetrics import (
    GROUP_TELEMETRY_WIDTH,
    QUEUE_WAIT_BUCKETS,
    TELEMETRY_WIDTH,
    _LEGACY_GROUP_TELEMETRY_WIDTH,
    _LEGACY_TELEMETRY_WIDTH,
)
from evotorch_tpu.observability.health import EWMATrend, HealthMonitor
from evotorch_tpu.parallel import make_mesh, make_sharded_rollout_evaluator


def _health_matrix(counter_rows, score_rows):
    """Assemble a v4 wire host-side: counter block + bit-cast health."""
    counter = np.asarray(counter_rows, dtype=np.int32)
    health = np.asarray(score_rows, dtype=np.float32)
    return np.concatenate([counter, health.view(np.int32)], axis=1)


# ---------------------------------------------------------------------------
# golden decode: every schema through the one decoder
# ---------------------------------------------------------------------------


def test_golden_decode_v1_vector():
    v1 = np.array([10, 2, 20, 4, 3, 5], dtype=np.int32)
    assert v1.shape == (_LEGACY_TELEMETRY_WIDTH,)
    gt = GroupTelemetry.from_array(v1)
    assert gt.num_groups == 1 and not gt.has_health
    assert gt.score_stats() is None
    t = gt.total()
    assert (t.env_steps, t.episodes, t.nonfinite) == (10, 2, 0)
    assert EvalTelemetry.from_array(v1).env_steps == 10


def test_golden_decode_v2_matrix():
    v2 = np.zeros((2, _LEGACY_GROUP_TELEMETRY_WIDTH), dtype=np.int32)
    v2[0, :_LEGACY_TELEMETRY_WIDTH] = [90, 10, 100, 4, 10, 5]
    v2[1, _LEGACY_TELEMETRY_WIDTH:] = [0, 0, 0, 0, 0, 1, 0, 5]
    gt = GroupTelemetry.from_array(v2)
    assert gt.num_groups == 2 and not gt.has_health
    assert gt.data.shape == (2, GROUP_TELEMETRY_WIDTH)
    assert gt.total().env_steps == 90
    assert gt.total().nonfinite == 0  # missing column decodes as 0
    assert gt.hist.shape == (2, QUEUE_WAIT_BUCKETS)
    assert int(gt.hist[1].sum()) == 6


def test_golden_decode_v3_matrix():
    v3 = np.zeros((2, GROUP_TELEMETRY_WIDTH), dtype=np.int32)
    v3[0, :TELEMETRY_WIDTH] = [90, 10, 100, 4, 10, 5, 1]
    gt = GroupTelemetry.from_array(v3)
    assert gt.num_groups == 2 and not gt.has_health
    assert gt.total().nonfinite == 1
    assert gt.score_stats() is None


def test_golden_decode_v4_matrix_and_stats():
    counter = np.zeros((2, GROUP_TELEMETRY_WIDTH), dtype=np.int32)
    counter[0, :TELEMETRY_WIDTH] = [90, 10, 100, 4, 10, 5, 0]
    counter[1, :TELEMETRY_WIDTH] = [30, 4, 50, 4, 2, 8, 0]
    # g0: scores {1, 2, 3}; g1: scores {-4, -6}
    health = [
        [3.0, 6.0, 14.0, 1.0, 3.0],
        [2.0, -10.0, 52.0, -6.0, -4.0],
    ]
    gt = GroupTelemetry.from_array(_health_matrix(counter, health))
    assert gt.has_health and gt.health.shape == (2, HEALTH_WIDTH)
    s0 = gt.score_stats(group=0)
    assert s0["count"] == 3 and s0["mean"] == pytest.approx(2.0)
    assert s0["std"] == pytest.approx(np.std([1.0, 2.0, 3.0]))
    assert (s0["min"], s0["max"]) == (1.0, 3.0)
    s1 = gt.score_stats(group=1)
    assert s1["mean"] == pytest.approx(-5.0)
    assert (s1["min"], s1["max"]) == (-6.0, -4.0)
    g = gt.score_stats()
    assert g["count"] == 5
    assert g["mean"] == pytest.approx(np.mean([1, 2, 3, -4, -6]))
    assert g["std"] == pytest.approx(np.std([1, 2, 3, -4, -6]))
    assert (g["min"], g["max"]) == (-6.0, 3.0)
    # the counter decoders keep reading the v4 wire unchanged
    assert gt.total().env_steps == 120
    assert EvalTelemetry.from_array(_health_matrix(counter, health)).env_steps == 120


def test_health_block_chan_addition():
    counter = np.zeros((1, GROUP_TELEMETRY_WIDTH), dtype=np.int32)
    a = GroupTelemetry.from_array(
        _health_matrix(counter, [[2.0, 3.0, 5.0, 1.0, 2.0]])  # {1, 2}
    )
    b = GroupTelemetry.from_array(
        _health_matrix(counter, [[2.0, 7.0, 25.0, 3.0, 4.0]])  # {3, 4}
    )
    s = (a + b).score_stats()
    assert s["count"] == 4
    assert s["mean"] == pytest.approx(2.5)
    assert s["std"] == pytest.approx(np.std([1.0, 2.0, 3.0, 4.0]))
    assert (s["min"], s["max"]) == (1.0, 4.0)
    # empty rows (count 0, min/max masked to 0.0) are identity elements
    empty = GroupTelemetry.from_array(
        _health_matrix(counter, [[0.0, 0.0, 0.0, 0.0, 0.0]])
    )
    s2 = (a + empty).score_stats()
    assert (s2["count"], s2["min"], s2["max"]) == (2, 1.0, 2.0)
    # mixed-schema addition degrades to counters-only (no fabricated stats)
    v3_only = GroupTelemetry.from_array(counter.copy())
    assert not (a + v3_only).has_health


def test_compute_health_block_empty_group_masking():
    # group 1 receives no solutions: its row must be all-zero (min/max
    # masked), not +/-inf — inf would poison the int32 psum wire
    scores = jnp.asarray([1.0, 2.0, 3.0])
    groups = jnp.zeros(3, dtype=jnp.int32)
    block = np.asarray(jax.jit(
        lambda s, g: compute_health_block(s, g, 2)
    )(scores, groups))
    assert block.shape == (2, HEALTH_WIDTH)
    np.testing.assert_array_equal(block[1], np.zeros(HEALTH_WIDTH))
    assert block[0, 0] == 3.0 and (block[0, 3], block[0, 4]) == (1.0, 3.0)


def test_append_health_block_width_and_bitcast():
    counter = jnp.zeros((2, GROUP_TELEMETRY_WIDTH), dtype=jnp.int32)
    health = jnp.asarray(
        [[1.0, 2.5, 6.25, 2.5, 2.5], [0.0, 0.0, 0.0, 0.0, 0.0]],
        dtype=jnp.float32,
    )
    wire = np.asarray(jax.jit(append_health_block)(counter, health))
    assert wire.shape == (2, HEALTH_TELEMETRY_WIDTH)
    assert wire.dtype == np.int32
    gt = GroupTelemetry.from_array(wire)
    assert gt.score_stats(group=0)["mean"] == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# compiled programs: health on/off, steady state
# ---------------------------------------------------------------------------


def _rollout_setup(popsize=8):
    env = CartPole()
    policy = FlatParamsPolicy(
        Linear(env.observation_size, 4) >> Tanh() >> Linear(4, env.action_size)
    )
    stats = RunningNorm(env.observation_size).stats
    params = 0.1 * jax.random.normal(
        jax.random.key(0), (popsize, policy.parameter_count)
    )
    return env, policy, stats, params


@pytest.mark.parametrize(
    "eval_mode", ["budget", "episodes", "episodes_refill"]
)
def test_health_toggle_compiles_distinct_steady_programs(eval_mode):
    env, policy, stats, params = _rollout_setup()
    key = jax.random.key(1)
    kwargs = dict(num_episodes=1, episode_length=8, eval_mode=eval_mode)
    if eval_mode == "episodes_refill":
        kwargs.update(refill_width=4, refill_period=1)

    with track_compiles() as log:
        on = run_vectorized_rollout(env, policy, params, key, stats, **kwargs)
    assert log.count > 0
    with track_compiles() as log_off:
        off = run_vectorized_rollout(
            env, policy, params, key, stats, health=False, **kwargs
        )
    assert log_off.count > 0  # health=False is its OWN cached program
    # same scores, v4 vs v3 wire
    np.testing.assert_array_equal(np.asarray(on.scores), np.asarray(off.scores))
    assert np.asarray(on.telemetry).shape[-1] == HEALTH_TELEMETRY_WIDTH
    assert np.asarray(off.telemetry).shape[-1] == GROUP_TELEMETRY_WIDTH
    assert GroupTelemetry.from_array(on.telemetry).has_health
    assert not GroupTelemetry.from_array(off.telemetry).has_health
    # both variants are steady after the first trace
    with assert_compiles(0):
        run_vectorized_rollout(env, policy, params, key, stats, **kwargs)
        run_vectorized_rollout(
            env, policy, params, key, stats, health=False, **kwargs
        )


def test_health_stats_match_scores_per_contract():
    env, policy, stats, params = _rollout_setup(popsize=12)
    key = jax.random.key(2)
    groups = np.arange(12, dtype=np.int32) % 3
    for eval_mode in ("budget", "episodes"):
        r = run_vectorized_rollout(
            env, policy, params, key, stats,
            num_episodes=1, episode_length=8, eval_mode=eval_mode,
            groups=groups, num_groups=3,
        )
        scores = np.asarray(r.scores, dtype=np.float32)
        gt = GroupTelemetry.from_array(r.telemetry)
        g = gt.score_stats()
        assert g["count"] == 12
        assert g["mean"] == pytest.approx(scores.mean(), rel=1e-6)
        assert g["min"] == pytest.approx(scores.min())
        assert g["max"] == pytest.approx(scores.max())
        for gid in range(3):
            s = gt.score_stats(group=gid)
            mine = scores[groups == gid]
            assert s["count"] == len(mine)
            assert s["mean"] == pytest.approx(mine.mean(), rel=1e-6)


# ---------------------------------------------------------------------------
# mesh bit-identity (the GSPMD acceptance clause)
# ---------------------------------------------------------------------------


def test_health_rows_bit_identical_across_meshes():
    env, policy, stats, params = _rollout_setup(popsize=16)
    key = jax.random.key(3)
    groups = np.arange(16, dtype=np.int32) % 2
    kwargs = dict(
        num_episodes=1, episode_length=8, eval_mode="episodes_refill",
        refill_width=8, refill_period=1, groups=groups, num_groups=2,
    )
    ref = run_vectorized_rollout(env, policy, params, key, stats, **kwargs)
    href = GroupTelemetry.from_array(ref.telemetry).health
    assert href is not None
    for mesh_shape in ({"pop": 8}, {"pop": 4, "model": 2}):
        ev = make_sharded_rollout_evaluator(
            env, policy, mesh=make_mesh(mesh_shape), **kwargs
        )
        result, _ = ev(params, key, stats)
        h = GroupTelemetry.from_array(result.telemetry).health
        # BIT-identical: compare the raw float32 words, no tolerance
        np.testing.assert_array_equal(
            h.view(np.int32), href.view(np.int32), err_msg=str(mesh_shape)
        )


def test_health_rows_bit_identical_padded_popsize():
    # 12 % 8 != 0: the GSPMD path pads to 16 physical lanes; pad lanes are
    # masked out of the score fold, so the health block (unlike the
    # capacity/lane-width counter columns, which account physical lanes)
    # matches unsharded EXACTLY
    env, policy, stats, params = _rollout_setup(popsize=12)
    key = jax.random.key(4)
    groups = np.arange(12, dtype=np.int32) % 2
    kwargs = dict(
        num_episodes=1, episode_length=4, eval_mode="episodes",
        groups=groups, num_groups=2,
    )
    ref = run_vectorized_rollout(env, policy, params, key, stats, **kwargs)
    href = GroupTelemetry.from_array(ref.telemetry).health
    ev = make_sharded_rollout_evaluator(
        env, policy, mesh=make_mesh({"pop": 8}), **kwargs
    )
    result, _ = ev(params, key, stats)
    h = GroupTelemetry.from_array(result.telemetry).health
    np.testing.assert_array_equal(h.view(np.int32), href.view(np.int32))


# ---------------------------------------------------------------------------
# EWMA trend detectors
# ---------------------------------------------------------------------------


def test_ewma_trend_progressing_stream_never_stalls():
    rng = np.random.default_rng(0)
    trend = EWMATrend()
    for i in range(60):
        trend.observe(10.0 * i + rng.normal(0.0, 2.0))
    assert trend.warmed_up and trend.significant
    assert trend.stall_streak == 0


def test_ewma_trend_flat_stream_stalls_and_worsening_is_not_plateau():
    rng = np.random.default_rng(1)
    flat = EWMATrend()
    for _ in range(60):
        flat.observe(5.0 + rng.normal(0.0, 2.0))
    assert flat.stall_streak > 0 and not flat.significant
    # a clearly WORSENING stream has a significant (negative) trend — the
    # plateau detector must not call regression a plateau
    down = EWMATrend()
    for i in range(60):
        down.observe(-10.0 * i + rng.normal(0.0, 2.0))
    assert down.significant and down.stall_streak == 0
    assert down.delta_ewma < 0


def test_ewma_trend_nonfinite_observations_are_noops():
    trend = EWMATrend()
    for i in range(10):
        trend.observe(float(i))
    before = trend.state_dict()
    trend.observe(float("nan")).observe(float("inf"))
    assert trend.state_dict() == before


def test_trend_and_monitor_state_roundtrip():
    rng = np.random.default_rng(2)
    a = EWMATrend()
    values = [5.0 + rng.normal(0.0, 2.0) for _ in range(20)]
    for v in values:
        a.observe(v)
    b = EWMATrend()
    b.load_state_dict(a.state_dict())
    tail = [5.0 + rng.normal(0.0, 2.0) for _ in range(20)]
    for v in tail:
        a.observe(v)
        b.observe(v)
    assert a.state_dict() == b.state_dict()
    assert json.loads(json.dumps(a.state_dict())) == a.state_dict()

    m = HealthMonitor()
    m.observe("score_mean", 1.0)
    m.observe("score_mean", 2.0, group=1)
    m.observe("stdev_norm", 3.0)
    m2 = HealthMonitor()
    m2.load_state_dict(json.loads(json.dumps(m.state_dict())))
    assert sorted(m2.keys()) == sorted(m.keys())
    assert m2.baseline("stdev_norm") == 3.0
    assert m2.trend("score_mean", group=1).value == 2.0


# ---------------------------------------------------------------------------
# the three health SLO rules
# ---------------------------------------------------------------------------


def _v4_with_scores(scores, num_groups=1, groups=None):
    scores = np.asarray(scores, dtype=np.float32)
    if groups is None:
        groups = np.zeros(len(scores), dtype=np.int32)
    block = np.asarray(
        compute_health_block(
            jnp.asarray(scores), jnp.asarray(groups), num_groups
        )
    )
    counter = np.zeros((num_groups, GROUP_TELEMETRY_WIDTH), dtype=np.int32)
    return GroupTelemetry.from_array(_health_matrix(counter, block))


def test_plateau_rule_trips_on_flat_scores_with_named_violation():
    rng = np.random.default_rng(3)
    dog = SLOWatchdog([Rule("plateau", threshold=10)])
    tripped = None
    for gen in range(80):
        scores = 5.0 + rng.normal(0.0, 1.0, size=16)
        report = dog.check(_v4_with_scores(scores))
        if not report.ok:
            tripped = (gen, report)
            break
    assert tripped is not None
    assert "plateau global" in tripped[1].violations[0]
    assert tripped[1].as_status()["slo_ok"] is False


def test_plateau_rule_quiet_on_progressing_scores():
    rng = np.random.default_rng(4)
    dog = SLOWatchdog([Rule("plateau", threshold=10)])
    for gen in range(80):
        scores = 10.0 * gen + rng.normal(0.0, 1.0, size=16)
        report = dog.check(_v4_with_scores(scores))
        assert report.ok, report.violations


def test_plateau_rule_status_fallback_for_prev4_feeds():
    # a replayed v3 feed has no health block; the global rule falls back to
    # the score_mean / mean_eval status keys instead of going blind
    dog = SLOWatchdog([Rule("plateau", threshold=5)])
    report = None
    for _ in range(40):
        report = dog.check(None, status={"mean_eval": 5.0})
    assert report is not None and not report.ok


def test_stdev_collapse_rule_vs_first_seen_baseline():
    dog = SLOWatchdog([Rule("stdev_collapse", threshold=0.01)])
    assert dog.check(None, status={"stdev_norm": 1.0}).ok
    assert dog.check(None, status={"stdev_norm": 0.5}).ok
    report = dog.check(None, status={"stdev_norm": 0.001})
    assert not report.ok and "collapse" in report.violations[0]
    # no stdev_norm key -> rule skipped, not failed
    skipped = dog.check(None, status={})
    assert skipped.ok and skipped.checked == 0


def test_score_snr_floor_rule():
    dog = SLOWatchdog([Rule("score_snr_floor", threshold=1e6)])
    # degenerate: every score identical -> std 0 -> SNR inf -> passes the
    # floor
    assert dog.check(_v4_with_scores([5.0] * 8)).ok
    report = dog.check(_v4_with_scores([5.0, 5.1, 4.9, 5.05, 4.95]))
    assert not report.ok and "score_snr" in report.violations[0]
    # fewer than two samples: skipped
    assert dog.check(_v4_with_scores([5.0])).checked == 0


def test_watchdog_health_state_rides_state_dict():
    rng = np.random.default_rng(5)
    rules = [Rule("plateau", threshold=10), Rule("stdev_collapse", threshold=0.01)]
    a = SLOWatchdog(rules)
    history = []
    for _ in range(30):
        scores = 5.0 + rng.normal(0.0, 1.0, size=16)
        history.append(scores)
        a.check(_v4_with_scores(scores), status={"stdev_norm": 1.0})
    b = SLOWatchdog(rules)
    b.load_state_dict(json.loads(json.dumps(a.state_dict())))
    rng2 = np.random.default_rng(6)
    for _ in range(60):
        scores = 5.0 + rng2.normal(0.0, 1.0, size=16)
        ra = a.check(_v4_with_scores(scores), status={"stdev_norm": 1.0})
        rb = b.check(_v4_with_scores(scores), status={"stdev_norm": 1.0})
        assert ra.as_status() == rb.as_status()


def test_healthy_cartpole_run_stays_slo_ok():
    # end-to-end: a healthy searcher on CartPole under the health rules
    # never trips — and the status dict carries the new score keys
    from evotorch_tpu.algorithms import PGPE
    from evotorch_tpu.neuroevolution import VecNE

    problem = VecNE(
        "cartpole",
        "Linear(obs_length, 4) >> Tanh() >> Linear(4, act_length)",
        episode_length=16,
        eval_mode="episodes",
        slo=[
            {"kind": "plateau", "threshold": 3},
            {"kind": "score_snr_floor", "threshold": 1e-6},
            {"kind": "stdev_collapse", "threshold": 0.01},
        ],
        seed=0,
    )
    searcher = PGPE(problem, popsize=8, center_learning_rate=0.1,
                    stdev_learning_rate=0.1, radius_init=0.3)
    for _ in range(6):
        searcher.step()
    status = searcher.status
    assert status["slo_ok"] is True, status.get("slo_detail")
    assert "eval_score_mean" in status and "eval_score_std" in status
    assert status["stdev_norm"] > 0.0
    assert status["center_update_norm"] is not None


# ---------------------------------------------------------------------------
# the telemetry-schema lint checker
# ---------------------------------------------------------------------------


def test_lint_telemetry_schema_checker():
    from evotorch_tpu.analysis import lint_sources

    findings = lint_sources(
        {
            "pkg/bad.py": (
                "def f(telemetry, group_counts, other):\n"
                "    a = telemetry[:, 15]\n"
                "    b = group_counts[0, 6]\n"
                "    c = other[3]\n"           # unrelated array: fine
                "    d = telemetry[:, i]\n"    # no literal: fine
                "    return a, b, c, d\n"
            ),
            # the owner module may spell its own layout
            "evotorch_tpu/observability/devicemetrics.py": (
                "def g(telemetry):\n    return telemetry[:, 15]\n"
            ),
            # allow-comments still apply
            "pkg/allowed.py": (
                "def h(lane_counts):\n"
                "    # graftlint: allow(telemetry-schema): leading axis squeeze\n"
                "    return lane_counts[0]\n"
            ),
        },
        checkers=["telemetry-schema"],
    )
    sigs = sorted(f.signature for f in findings)
    assert len(sigs) == 2
    assert all(s.startswith("pkg/bad.py::telemetry-schema") for s in sigs)
    assert any("telemetry-index:telemetry:[15]" in s for s in sigs)
    assert any("telemetry-index:group_counts:[0,6]" in s for s in sigs)
