"""Zero-sync telemetry: device counters, span tracer, counter registry.

The contracts under test (docs/observability.md):

- the on-device telemetry vector is produced by the SAME jitted program as
  the scores, is additive (sharded evals psum it), and its figures agree
  with the ground-truth counters for every eval contract;
- the Chrome-trace tracer emits schema-valid, properly-nesting events,
  keeps threads on separate tracks, ring-buffers, and is a shared no-op
  when disabled;
- the registry counts compiles/spans/fetches process-wide and surfaces
  per-step deltas in searcher status dicts.
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.envs import CartPole
from evotorch_tpu.neuroevolution.net import (
    FlatParamsPolicy,
    Linear,
    Tanh,
    run_vectorized_rollout,
    run_vectorized_rollout_compacting,
)
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.observability import (
    EvalTelemetry,
    GROUP_TELEMETRY_WIDTH,
    GroupTelemetry,
    MetricsHub,
    QUEUE_WAIT_BUCKETS,
    Rule,
    SLOWatchdog,
    TELEMETRY_SCHEMA_VERSION,
    TELEMETRY_WIDTH,
    counters,
    pack_eval_telemetry,
    tracer,
)

POPSIZE = 8
EPISODE_LENGTH = 16


def _env_policy():
    env = CartPole()
    net = Linear(env.observation_size, env.action_size) >> Tanh()
    return env, FlatParamsPolicy(net)


@pytest.fixture
def fresh_tracer():
    t = tracer.start_tracing()
    yield t
    tracer.stop_tracing(write=False)


# ---------------------------------------------------------------------------
# device telemetry
# ---------------------------------------------------------------------------


def test_pack_decode_roundtrip_and_addition():
    vec = jax.jit(
        lambda: pack_eval_telemetry(
            env_steps=10, episodes=2, capacity=20, lane_width=4,
            refill_events=3, queue_wait=5,
        )
    )()
    assert vec.shape == (TELEMETRY_WIDTH,) and vec.dtype == jnp.int32
    t = EvalTelemetry.from_array(vec)
    assert (t.env_steps, t.episodes, t.capacity, t.lane_width) == (10, 2, 20, 4)
    assert (t.refill_events, t.queue_wait) == (3, 5)
    assert t.occupancy == 0.5
    assert t.mean_item_wait == pytest.approx(5 / 3)
    summed = t + t
    assert summed.env_steps == 20 and summed.capacity == 40
    assert summed.occupancy == 0.5  # additivity preserves the ratio
    with pytest.raises(ValueError):
        EvalTelemetry.from_array(np.zeros(3))


def test_telemetry_figures_per_contract():
    env, policy = _env_policy()
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (POPSIZE, policy.parameter_count))
    key = jax.random.key(1)
    common = dict(num_episodes=1, episode_length=EPISODE_LENGTH)

    budget = run_vectorized_rollout(
        env, policy, params, key, stats, eval_mode="budget", **common
    )
    t = EvalTelemetry.from_array(budget.telemetry)
    # budget: every executed lane-step is a counted interaction, by definition
    assert t.occupancy == 1.0
    assert t.env_steps == int(budget.total_steps) == POPSIZE * EPISODE_LENGTH
    assert t.lane_width == POPSIZE and t.refill_events == 0

    episodes = run_vectorized_rollout(
        env, policy, params, key, stats, eval_mode="episodes", **common
    )
    t = EvalTelemetry.from_array(episodes.telemetry)
    assert t.env_steps == int(episodes.total_steps)
    assert t.episodes == int(episodes.total_episodes) == POPSIZE
    # idle masked lanes burn capacity: occupancy is the waste diagnostic
    assert 0.0 < t.occupancy <= 1.0

    refill = run_vectorized_rollout(
        env, policy, params, key, stats, eval_mode="episodes_refill",
        refill_width=4, **common,
    )
    t = EvalTelemetry.from_array(refill.telemetry)
    assert t.lane_width == 4
    assert t.refill_events == POPSIZE - 4  # every item beyond the seed set
    assert t.env_steps == int(refill.total_steps)

    compact = run_vectorized_rollout_compacting(
        env, policy, params, key, stats, allowed_widths=(4,), **common
    )
    t = EvalTelemetry.from_array(compact.telemetry)
    assert t.env_steps == int(compact.total_steps)
    assert t.episodes == POPSIZE
    # capacity through the width descent never exceeds full-width-forever
    assert t.capacity <= POPSIZE * (EPISODE_LENGTH + 1)


def test_telemetry_off_is_none_and_scores_identical():
    env, policy = _env_policy()
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (POPSIZE, policy.parameter_count))
    key = jax.random.key(1)
    for mode, kw in [
        ("budget", {}),
        ("episodes", {}),
        ("episodes_refill", {"refill_width": 4}),
    ]:
        on = run_vectorized_rollout(
            env, policy, params, key, stats, num_episodes=1,
            episode_length=EPISODE_LENGTH, eval_mode=mode, **kw,
        )
        off = run_vectorized_rollout(
            env, policy, params, key, stats, num_episodes=1,
            episode_length=EPISODE_LENGTH, eval_mode=mode, telemetry=False, **kw,
        )
        assert off.telemetry is None
        assert jnp.array_equal(on.scores, off.scores), mode


def test_sharded_evaluator_psums_telemetry():
    from evotorch_tpu.parallel.evaluate import make_sharded_rollout_evaluator
    from evotorch_tpu.parallel.mesh import default_mesh

    env, policy = _env_policy()
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (POPSIZE, policy.parameter_count))
    mesh = default_mesh(("pop",))
    evaluator = make_sharded_rollout_evaluator(
        env, policy, mesh=mesh, num_episodes=1, episode_length=EPISODE_LENGTH,
        eval_mode="episodes_refill", refill_width=8,
    )
    result, _ = evaluator(params, jax.random.key(1), stats)
    t = EvalTelemetry.from_array(result.telemetry)
    # psum'd across shards: mesh-global figures
    assert t.env_steps == int(result.total_steps)
    assert t.episodes == int(result.total_episodes) == POPSIZE
    assert t.lane_width == 8  # the GLOBAL refill width, summed over shards


def test_refill_queue_wait_counts_gated_idle_lanes():
    env, policy = _env_policy()
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (POPSIZE, policy.parameter_count))
    # refill_period > 1 forces finished lanes to idle masked while the queue
    # still holds work — exactly what queue_wait meters
    r = run_vectorized_rollout(
        env, policy, params, jax.random.key(1), stats, num_episodes=1,
        episode_length=EPISODE_LENGTH, eval_mode="episodes_refill",
        refill_width=2, refill_period=7,
    )
    t = EvalTelemetry.from_array(r.telemetry)
    assert t.refill_events == POPSIZE - 2
    assert t.queue_wait > 0
    assert t.mean_item_wait > 0.0


# ---------------------------------------------------------------------------
# per-group telemetry (the per-group counter wire; health block in test_health.py)
# ---------------------------------------------------------------------------


def _group_matrix():
    """A synthetic two-group matrix: g0 healthy, g1 starved."""
    data = np.zeros((2, GROUP_TELEMETRY_WIDTH), dtype=np.int64)
    data[0, :TELEMETRY_WIDTH] = [90, 10, 100, 4, 10, 5, 1]
    data[1, :TELEMETRY_WIDTH] = [2, 0, 100, 4, 6, 300, 0]
    data[0, TELEMETRY_WIDTH:] = [8, 1, 1, 0, 0, 0, 0, 0]
    data[1, TELEMETRY_WIDTH:] = [0, 0, 0, 0, 0, 1, 0, 5]
    return data


def test_group_telemetry_decode_total_and_quantiles():
    assert TELEMETRY_SCHEMA_VERSION == 4
    gt = GroupTelemetry.from_array(_group_matrix())
    assert gt.num_groups == 2
    assert gt.hist.shape == (2, QUEUE_WAIT_BUCKETS)
    # total() collapses to the v1 global figures
    total = gt.total()
    assert total.env_steps == 92 and total.capacity == 200
    assert gt.group(0).occupancy == 0.9
    # Prometheus-style upper-edge quantiles off the bucketed histogram
    assert gt.queue_wait_quantile(0.5, group=0) == 0.0  # bucket 0 = waits of 0
    assert gt.queue_wait_quantile(0.99) >= gt.queue_wait_quantile(0.5)
    assert gt.queue_wait_quantile(0.99, group=1) == 64.0  # overflow bucket
    # starvation = the overflow bucket's share of refills
    assert gt.starvation_share(group=0) == 0.0
    assert gt.starvation_share(group=1) == pytest.approx(5 / 6)
    # nonfinite (the quarantine column, schema 3) over finished episodes
    assert gt.nonfinite_share(group=0) == pytest.approx(1 / 10)
    assert gt.nonfinite_share(group=1) == 0.0
    # addition pads the shorter matrix (sub-batch additivity)
    summed = gt + GroupTelemetry.from_array(_group_matrix()[:1])
    assert summed.total().env_steps == 92 + 90
    # the v1 decoder reads the same wire (column sums)
    assert EvalTelemetry.from_array(_group_matrix()).env_steps == 92


def test_v1_wire_golden_decode_still_works():
    # the frozen v1 contract: a (6,) vector decodes field-for-field, and
    # GroupTelemetry lifts it into a single-group matrix with empty buckets
    golden = np.array([160, 8, 160, 8, 4, 12], dtype=np.int32)
    t = EvalTelemetry.from_array(golden)
    assert (t.env_steps, t.episodes, t.capacity, t.lane_width) == (160, 8, 160, 8)
    assert (t.refill_events, t.queue_wait) == (4, 12)
    gt = GroupTelemetry.from_array(golden)
    assert gt.num_groups == 1
    assert gt.hist.sum() == 0
    assert gt.total() == t


@pytest.mark.parametrize(
    "mode,kw",
    [
        ("budget", {}),
        ("episodes", {}),
        ("episodes_refill", {"refill_width": 4}),
    ],
)
def test_group_counters_sum_to_global(mode, kw):
    # the acceptance contract: a two-group split of the same population
    # yields identical scores and per-group counters that column-sum
    # EXACTLY to the G=1 globals, on every contract
    env, policy = _env_policy()
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (POPSIZE, policy.parameter_count))
    key = jax.random.key(1)
    groups = np.arange(POPSIZE, dtype=np.int32) % 2
    common = dict(num_episodes=1, episode_length=EPISODE_LENGTH)
    base = run_vectorized_rollout(
        env, policy, params, key, stats, eval_mode=mode, **common, **kw
    )
    split = run_vectorized_rollout(
        env, policy, params, key, stats, eval_mode=mode,
        groups=groups, num_groups=2, **common, **kw,
    )
    assert jnp.array_equal(base.scores, split.scores)
    t1 = GroupTelemetry.from_array(base.telemetry)
    t2 = GroupTelemetry.from_array(split.telemetry)
    assert t1.num_groups == 1 and t2.num_groups == 2
    assert t1.total() == t2.total()


def test_group_counters_sum_to_global_compacting():
    env, policy = _env_policy()
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (POPSIZE, policy.parameter_count))
    key = jax.random.key(1)
    groups = np.arange(POPSIZE, dtype=np.int32) % 2
    common = dict(num_episodes=1, episode_length=EPISODE_LENGTH)
    base = run_vectorized_rollout_compacting(
        env, policy, params, key, stats, allowed_widths=(4,), **common
    )
    split = run_vectorized_rollout_compacting(
        env, policy, params, key, stats, allowed_widths=(4,),
        groups=groups, num_groups=2, **common,
    )
    assert jnp.array_equal(base.scores, split.scores)
    assert (
        GroupTelemetry.from_array(base.telemetry).total()
        == GroupTelemetry.from_array(split.telemetry).total()
    )


def test_refill_group_histogram_counts_every_refill():
    env, policy = _env_policy()
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (POPSIZE, policy.parameter_count))
    groups = np.arange(POPSIZE, dtype=np.int32) % 2
    # refill_period > 1 makes lanes idle before refilling, so waits land in
    # nonzero buckets
    r = run_vectorized_rollout(
        env, policy, params, jax.random.key(1), stats, num_episodes=1,
        episode_length=EPISODE_LENGTH, eval_mode="episodes_refill",
        refill_width=2, refill_period=7, groups=groups, num_groups=2,
    )
    gt = GroupTelemetry.from_array(r.telemetry)
    # every refill lands in exactly one bucket of its group's histogram
    assert int(gt.hist.sum()) == gt.total().refill_events == POPSIZE - 2
    assert gt.queue_wait_quantile(0.99) >= gt.queue_wait_quantile(0.5)


def test_vecne_solution_groups_status_and_slo():
    from evotorch_tpu.algorithms import PGPE
    from evotorch_tpu.neuroevolution import VecNE

    problem = VecNE(
        CartPole(),
        "Linear(obs_length, act_length)",
        episode_length=EPISODE_LENGTH,
        eval_mode="episodes_refill",
        refill_config={"width": 4},
        solution_groups=np.arange(POPSIZE, dtype=np.int32) % 2,
        slo=[
            {"kind": "occupancy_floor", "threshold": 0.01},
            {"kind": "min_progress", "threshold": 1},
        ],
        seed=0,
    )
    searcher = PGPE(
        problem,
        popsize=POPSIZE,
        center_learning_rate=0.1,
        stdev_learning_rate=0.1,
        stdev_init=0.1,
    )
    searcher.step()
    searcher.step()
    status = dict(searcher.status.items())
    # per-group status keys appear at G > 1 (lag-by-one, live by step 2)
    assert 0.0 < status["eval_g0_occupancy"] <= 1.0
    assert 0.0 < status["eval_g1_occupancy"] <= 1.0
    assert (
        status["eval_g0_env_steps"] + status["eval_g1_env_steps"]
        == problem.last_group_telemetry.total().env_steps
    )
    # the watchdog ran and passed (both groups make progress)
    assert status["slo_ok"] is True and status["slo_violations"] == 0
    # mismatched mapping fails loudly
    with pytest.raises(ValueError, match="solution_groups maps"):
        problem._check_solution_groups(POPSIZE + 1)


# ---------------------------------------------------------------------------
# SLO watchdog
# ---------------------------------------------------------------------------


def test_slo_watchdog_flags_starved_group():
    gt = GroupTelemetry.from_array(_group_matrix())
    watchdog = SLOWatchdog([
        Rule("occupancy_floor", threshold=0.5),
        Rule("starvation_ceiling", threshold=0.25, group=1),
        Rule("min_progress", threshold=5),
        Rule("no_steady_compiles"),
    ])
    report = watchdog.check(gt, status={"steady_compiles": 0})
    assert not report.ok
    detail = "; ".join(report.violations)
    # the starved group is named in every violated rule
    assert "g1" in detail and "starvation" in detail and "env_steps" in detail
    status = report.as_status()
    assert status["slo_ok"] is False and status["slo_violations"] == 3
    # the healthy group alone passes the same rules
    healthy = SLOWatchdog([
        Rule("occupancy_floor", threshold=0.5, group=0),
        Rule("starvation_ceiling", threshold=0.25, group=0),
    ]).check(gt)
    assert healthy.ok and healthy.as_status()["slo_ok"] is True
    # a steady-state retrace violates regardless of telemetry
    retrace = SLOWatchdog([Rule("no_steady_compiles")]).check(
        None, status={"steady_compiles": 2}
    )
    assert not retrace.ok
    with pytest.raises(ValueError, match="unknown SLO rule kind"):
        Rule("bogus")


# ---------------------------------------------------------------------------
# MetricsHub
# ---------------------------------------------------------------------------


def test_metricshub_jsonl_stream(tmp_path, monkeypatch):
    gt = GroupTelemetry.from_array(_group_matrix())
    path = tmp_path / "metrics.jsonl"
    hub = MetricsHub(str(path), manifest={"mesh": "none", "env": "cartpole"})
    hub.emit({"gen": 1, "mean_eval": 3.5}, telemetry=gt)
    hub.emit({"gen": 2}, telemetry=gt.total())
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    manifest = lines[0]["manifest"]
    assert manifest["schema_version"] == TELEMETRY_SCHEMA_VERSION
    assert manifest["mesh"] == "none" and "created_unix" in manifest
    row = lines[1]
    assert row["row"] == 0 and row["gen"] == 1
    assert row["eval_env_steps"] == 92 and len(row["groups"]) == 2
    assert "counters" in row and "queue_wait_p99" in row
    # an EvalTelemetry lifts to G=1: no per-group block
    assert lines[2]["row"] == 1 and "groups" not in lines[2]
    # the env knob: unset -> no hub; set -> a hub at that path
    monkeypatch.delenv("EVOTORCH_METRICS", raising=False)
    assert MetricsHub.from_env() is None
    monkeypatch.setenv("EVOTORCH_METRICS", str(tmp_path / "envhub.jsonl"))
    assert MetricsHub.from_env().path.endswith("envhub.jsonl")


def test_metricshub_prometheus_rewrite(tmp_path):
    gt = GroupTelemetry.from_array(_group_matrix())
    path = tmp_path / "metrics.prom"
    hub = MetricsHub(str(path))
    hub.emit({"gen": 7, "mean_eval": 1.25}, telemetry=gt)
    text = path.read_text()
    assert 'evotorch_eval_occupancy{group="1"}' in text
    assert "evotorch_gen 7" in text
    # full rewrite, not append: a second emit leaves ONE copy of each series
    # (count SAMPLE lines — the HELP/TYPE headers also name the metric)
    hub.emit({"gen": 8}, telemetry=gt)
    rows = [l for l in path.read_text().splitlines() if l.startswith("evotorch_gen ")]
    assert rows == ["evotorch_gen 8"]


def test_metricshub_prometheus_help_and_type(tmp_path):
    # textfile-collector contract: every exported metric family carries a
    # `# HELP` and a `# TYPE` header, exactly once, BEFORE its samples;
    # registry counters are typed `counter`, everything else `gauge`
    gt = GroupTelemetry.from_array(_group_matrix())
    path = tmp_path / "metrics.prom"
    hub = MetricsHub(str(path))
    hub.emit({"gen": 7, "mean_eval": 1.25}, telemetry=gt)
    lines = path.read_text().splitlines()
    helps, types, samples = {}, {}, {}
    for i, line in enumerate(lines):
        if line.startswith("# HELP "):
            helps[line.split()[2]] = i
        elif line.startswith("# TYPE "):
            _, _, name, mtype = line.split()
            types[name] = (i, mtype)
        elif line and not line.startswith("#"):
            name = line.split("{")[0].split()[0]
            samples.setdefault(name, i)
    assert samples, lines
    for name, first in samples.items():
        assert name in helps, f"no HELP for {name}"
        assert name in types, f"no TYPE for {name}"
        assert helps[name] < types[name][0] < first
    assert types["evotorch_gen"][1] == "gauge"
    # the per-group family shares ONE header over its labelled samples
    assert "evotorch_eval_occupancy" in types
    grouped = [l for l in lines if l.startswith("evotorch_eval_occupancy{")]
    assert len(grouped) == 2
    assert sum(l.startswith("# TYPE evotorch_eval_occupancy ") for l in lines) == 1
    # registry counters (when present) are typed counter
    counter_types = {
        mtype for _, mtype in types.values()
    }
    assert counter_types <= {"gauge", "counter"}


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_tracer_schema_and_nesting(fresh_tracer):
    with tracer.span("outer", "test", level=1):
        with tracer.span("inner", "test"):
            pass
        tracer.instant("marker", "test")
    events = fresh_tracer.events()
    payload = json.loads(json.dumps(fresh_tracer.to_chrome_trace()))
    assert set(payload.keys()) == {"traceEvents", "displayTimeUnit"}
    by_name = {e["name"]: e for e in events if e.get("ph") != "M"}
    for e in events:
        assert isinstance(e["name"], str)
        assert e["ph"] in ("X", "M", "i")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    outer, inner = by_name["outer"], by_name["inner"]
    # spans NEST: the inner complete event is contained in the outer one
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"level": 1}
    # a thread_name metadata event identifies the track
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)


def test_tracer_threads_get_separate_tracks(fresh_tracer):
    def worker():
        with tracer.span("in_thread", "test"):
            pass

    th = threading.Thread(target=worker, name="test-worker")
    with tracer.span("in_main", "test"):
        pass
    th.start()
    th.join()
    events = fresh_tracer.events()
    main_tid = next(e["tid"] for e in events if e["name"] == "in_main")
    thread_tid = next(e["tid"] for e in events if e["name"] == "in_thread")
    assert main_tid != thread_tid
    names = {
        e["args"]["name"] for e in events if e.get("ph") == "M"
    }
    assert "test-worker" in names


def test_tracer_ring_buffer_bounds_events():
    t = tracer.SpanTracer(capacity=10)
    for i in range(50):
        with t.span(f"s{i}"):
            pass
    events = [e for e in t.events() if e.get("ph") == "X"]
    assert len(events) == 10
    assert events[-1]["name"] == "s49"  # the ring keeps the most recent tail


def test_span_is_shared_noop_when_disabled():
    assert tracer.get_tracer() is None
    before = counters.get("trace_spans")
    s1 = tracer.span("anything", "x", a=1)
    s2 = tracer.span("else")
    assert s1 is s2  # one shared no-op object: no allocation per call
    with s1:
        pass
    tracer.instant("nothing")
    assert counters.get("trace_spans") == before


def test_manual_complete_spans(fresh_tracer):
    t0 = fresh_tracer.now_us()
    fresh_tracer.complete("manual", t0, 123.0, "test", block=2)
    e = [x for x in fresh_tracer.events() if x["name"] == "manual"][0]
    assert e["dur"] == 123.0 and e["args"] == {"block": 2}


# ---------------------------------------------------------------------------
# registry + status surfacing
# ---------------------------------------------------------------------------


def test_tracer_periodic_flush_keeps_partial_trace(tmp_path):
    # EVOTORCH_TRACE_FLUSH_SECS: a killed run keeps the last flushed window
    # instead of losing the whole trace at the missed atexit hook
    path = str(tmp_path / "trace.json")
    tracer.start_tracing(path, flush_secs=0.01)
    try:
        import time as _time

        with tracer.span("first"):
            pass
        _time.sleep(0.02)
        with tracer.span("second"):  # completion past the interval -> flush
            pass
        data = json.loads(open(path).read())
        names = {e["name"] for e in data["traceEvents"] if e.get("ph") == "X"}
        assert {"first", "second"} <= names
    finally:
        tracer.stop_tracing(write=False)
    # flush stays off without the knob: nothing written before stop
    path2 = str(tmp_path / "trace2.json")
    tracer.start_tracing(path2)
    with tracer.span("quiet"):
        pass
    import os as _os

    assert not _os.path.exists(path2)
    assert tracer.stop_tracing() == path2


def test_registry_increment_snapshot_delta_threadsafe():
    from evotorch_tpu.observability import CounterRegistry

    reg = CounterRegistry()
    snap = reg.snapshot(("a", "b"))

    def bump():
        for _ in range(1000):
            reg.increment("a")

    threads = [threading.Thread(target=bump) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    reg.increment("b", 5)
    assert reg.delta(snap) == {"a": 4000, "b": 5}
    assert reg.get("missing") == 0


def test_searcher_status_carries_registry_deltas_and_eval_telemetry():
    from evotorch_tpu.algorithms import PGPE
    from evotorch_tpu.neuroevolution import VecNE

    problem = VecNE(
        CartPole(),
        "Linear(obs_length, act_length)",
        episode_length=EPISODE_LENGTH,
        eval_mode="episodes_refill",
        refill_config={"width": 4},
        seed=0,
    )
    searcher = PGPE(
        problem,
        popsize=POPSIZE,
        center_learning_rate=0.1,
        stdev_learning_rate=0.1,
        stdev_init=0.1,
    )
    searcher.step()
    status = dict(searcher.status.items())
    # registry deltas are status keys from the very first step
    assert status["compiles"] >= 1  # warmup generation compiled
    assert "trace_spans" in status and "telemetry_fetches" in status
    searcher.step()
    searcher.step()
    status = dict(searcher.status.items())
    # eval telemetry lags one generation (device-scalar discipline) — by
    # step 3 it reports the refill contract's figures
    assert 0.0 < status["eval_occupancy"] <= 1.0
    assert status["eval_refill_events"] == POPSIZE - 4
    assert status["eval_queue_wait"] >= 0
    # steady state: nothing recompiles once warm
    assert status["compiles"] == 0


def test_host_pipeline_reports_occupancy():
    from evotorch_tpu.neuroevolution.net.hostvecenv import (
        SyncVectorEnv,
        run_host_pipelined_rollout,
    )

    gym = pytest.importorskip("gymnasium")

    class ToyEnv:
        def __init__(self, horizon=6):
            self.h = horizon
            self.t = 0
            self.observation_space = gym.spaces.Box(-1, 1, (3,))
            self.action_space = gym.spaces.Box(-1, 1, (2,))

        def reset(self, seed=None):
            self.t = 0
            return np.zeros(3, np.float32), {}

        def step(self, action):
            self.t += 1
            return np.zeros(3, np.float32), 1.0, self.t >= self.h, False, {}

    policy = FlatParamsPolicy(Linear(3, 2) >> Tanh())
    vec = SyncVectorEnv(lambda: ToyEnv(), 4)
    params = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, policy.parameter_count)),
        jnp.float32,
    )
    result = run_host_pipelined_rollout(
        vec, policy, params, num_episodes=1, episode_length=10, mode="sync"
    )
    # equal-length toy episodes + work-conserving refill: every executed
    # lane-step is counted
    assert result["occupancy"] == 1.0
    assert result["interactions"] == 8 * 6
