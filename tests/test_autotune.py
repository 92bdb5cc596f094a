"""Autotuner + measured-timing ledger + tuned-config cache.

The contracts under test (docs/observability.md "The autotuner"):

- the SEARCH CORE is pure and deterministic: candidate grids and
  neighborhoods enumerate in stable order, analytic pruning rejects on
  the PR 9 cost model BEFORE any measurement, successive halving selects
  on MEDIANS (robust to the box's ±20% run-to-run noise — injected
  synthetically here, zero wall-clock), and the winner respects the
  occupancy floor;
- the measured-timing ledger keys per (program, shape, machine) and
  ranks configs by median steps/s;
- the tuned-config cache resolves with ONE precedence rule everywhere:
  explicit knobs ("override") > cache hit ("cache") > built-in default
  ("fallback"), and every consumer — VecNE status, the sharded
  evaluator, the host pipeline — reports the branch taken
  as `tuned_config_source`.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from evotorch_tpu.observability.autotune import (
    CandidateStats,
    KnobGroup,
    KnobSpec,
    analytic_prune,
    autotune_search,
    candidate_grid,
    neighborhood,
    select_winner,
    successive_halving,
)
from evotorch_tpu.observability.timings import (
    TimingLedger,
    TimingRecord,
    TunedEntry,
    canonical_env_label,
    load_tuned_cache,
    lookup_tuned,
    machine_fingerprint,
    resolve_knobs,
    save_tuned_entry,
    timing_key,
)

GROUP = KnobGroup(
    "refill",
    (
        KnobSpec("width", (64, 128, 256, 512)),
        KnobSpec("period", (1, 2), refine=False),
    ),
)

#: the synthetic ground truth: a planted optimum at width=256, period=1,
#: with gaps wide enough that ±20% multiplicative noise cannot flip a
#: median-of-3 (max competitor 60*1.2=72 < min optimum 100*0.8=80)
_TRUE_RATE = {64: 40.0, 128: 60.0, 256: 100.0, 512: 55.0}


def _synthetic_measure(noise_rng=None, log=None):
    """A MeasureFn over the planted-optimum landscape; ``log`` collects
    every measured config (for pruned-never-measured assertions)."""

    def measure(configs, trials, round_index):
        out = []
        for config in configs:
            if log is not None:
                log.append(dict(config))
            base = _TRUE_RATE.get(config["width"], 50.0)
            if config.get("period", 1) == 2:
                base *= 0.5
            samples = []
            for _ in range(trials):
                factor = 1.0 if noise_rng is None else noise_rng.uniform(0.8, 1.2)
                samples.append(base * factor)
            out.append(
                {
                    "samples": samples,
                    "occupancies": [0.95] * trials,
                    "steady_compiles": 0,
                }
            )
        return out

    return measure


# ---------------------------------------------------------------------------
# the pure search core
# ---------------------------------------------------------------------------


def test_candidate_grid_order_and_size():
    grid = candidate_grid(GROUP)
    assert len(grid) == 8
    # knob-major deterministic order: first knob varies slowest
    assert grid[0] == {"width": 64, "period": 1}
    assert grid[1] == {"width": 64, "period": 2}
    assert grid[-1] == {"width": 512, "period": 2}


def test_neighborhood_midpoints_skip_unrefinable_knobs():
    nbrs = neighborhood(GROUP, {"width": 256, "period": 1})
    # midpoints toward the adjacent grid rungs, one knob at a time; the
    # period knob (refine=False) must not generate anything
    assert nbrs == [
        {"width": 192, "period": 1},
        {"width": 384, "period": 1},
    ]
    # an edge value refines inward only
    assert neighborhood(GROUP, {"width": 64, "period": 1}) == [
        {"width": 96, "period": 1}
    ]


def test_analytic_prune_hbm_and_flops_bounds():
    def cost_fn(config):
        if config["width"] == 128:
            return None  # no analysis available: must NEVER prune
        return {
            "peak_bytes": config["width"] * 1000,
            "flops": config["width"] * 10.0,
            "compile_seconds": 0.1,
        }

    grid = [{"width": w, "period": 1} for w in (64, 128, 256, 512)]
    kept, pruned, costs = analytic_prune(
        grid, cost_fn, hbm_budget_bytes=300_000
    )
    assert [c["width"] for c in kept] == [64, 128, 256]
    assert len(pruned) == 1 and pruned[0][0]["width"] == 512
    assert "peak_bytes" in pruned[0][1]
    # costs are keyed by KEPT index, skipping the analysis-less candidate
    assert set(costs) == {0, 2} and costs[2]["peak_bytes"] == 256_000

    kept, pruned, _ = analytic_prune(grid, cost_fn, flops_bound=1000.0)
    assert [c["width"] for c in kept] == [64, 128]
    assert {p[0]["width"] for p in pruned} == {256, 512}

    # no cost_fn at all: everything is kept
    kept, pruned, costs = analytic_prune(grid, None, hbm_budget_bytes=1)
    assert len(kept) == 4 and not pruned and not costs


def test_median_selection_is_outlier_robust():
    stats = CandidateStats(config={}, samples=[10.0, 100.0, 11.0])
    assert stats.steps_per_sec == 11.0  # the median, not the lucky max


def test_successive_halving_converges_under_injected_noise():
    rng = np.random.default_rng(7)
    results = successive_halving(
        candidate_grid(GROUP),
        _synthetic_measure(noise_rng=rng),
        trials_per_round=3,
        survivor_frac=0.5,
        max_rounds=3,
    )
    winner = select_winner(results, min_occupancy=0.9)
    assert winner.config == {"width": 256, "period": 1}
    # survivors accumulated more samples than first-round casualties
    assert len(winner.samples) > 3
    casualties = [r for r in results if r.config["width"] == 64]
    assert all(len(r.samples) == 3 for r in casualties)


def test_successive_halving_measures_fewer_candidates_each_round():
    per_round = []

    def measure(configs, trials, round_index):
        per_round.append(len(configs))
        return _synthetic_measure()(configs, trials, round_index)

    successive_halving(
        candidate_grid(GROUP),
        measure,
        trials_per_round=3,
        survivor_frac=0.5,
        min_survivors=2,
        max_rounds=3,
    )
    assert per_round[0] == 8
    assert all(b < a for a, b in zip(per_round, per_round[1:]))


def test_select_winner_occupancy_floor_and_clean_timing_preference():
    fast_starved = CandidateStats(
        config={"width": 512}, samples=[100.0], occupancies=[0.5]
    )
    slower_full = CandidateStats(
        config={"width": 128}, samples=[80.0], occupancies=[0.95]
    )
    assert (
        select_winner([fast_starved, slower_full], min_occupancy=0.9)
        is slower_full
    )
    # no candidate meets the floor: fall back to the throughput winner
    assert (
        select_winner([fast_starved], min_occupancy=0.9) is fast_starved
    )
    # a steady-state compile mid-trial invalidates the timing: the dirty
    # candidate loses to any clean one regardless of its median
    dirty = CandidateStats(
        config={"width": 256},
        samples=[200.0],
        occupancies=[0.99],
        steady_compiles=1,
    )
    assert select_winner([dirty, slower_full], min_occupancy=0.9) is slower_full


def test_autotune_search_prunes_before_measuring_and_refines_around_winner():
    measured = []

    def cost_fn(config):
        return {
            "peak_bytes": config["width"] * 1000,
            "flops": None,
            "compile_seconds": 0.0,
        }

    outcome = autotune_search(
        GROUP,
        _synthetic_measure(log=measured),
        cost_fn=cost_fn,
        hbm_budget_bytes=300_000,  # prunes width 512 analytically
        trials_per_round=3,
        max_rounds=2,
        min_occupancy=0.9,
        refine=True,
    )
    # the grid's 512 AND the refinement midpoint 384 (peak 384k > budget)
    # are both rejected analytically — and neither is ever timed
    assert {p[0]["width"] for p in outcome.pruned} == {512, 384}
    assert all(c["width"] not in (384, 512) for c in measured)
    assert outcome.winner.config["width"] == 256
    # the surviving off-grid midpoint of the winner was measured
    assert 192 in {c["width"] for c in measured}
    assert outcome.winner.cost is not None  # costs attached to grid stats


# ---------------------------------------------------------------------------
# the measured-timing ledger
# ---------------------------------------------------------------------------


def test_timing_key_is_shape_and_machine_scoped():
    machine = {"backend": "cpu", "device_kind": "cpu", "core_count": 1}
    key = timing_key("rollout.episodes_refill", {"popsize": 1024, "env": "humanoid"}, machine)
    assert key == (
        "rollout.episodes_refill@env=humanoid,popsize=1024"
        "|backend=cpu,core_count=1,device_kind=cpu"
    )
    other = timing_key(
        "rollout.episodes_refill",
        {"popsize": 1024, "env": "humanoid"},
        dict(machine, core_count=8),
    )
    assert other != key  # a different box is a different row


def test_timing_ledger_best_roundtrip(tmp_path):
    led = TimingLedger()
    machine = machine_fingerprint()
    shape = {"env": "humanoid", "popsize": 1024}
    led.add(TimingRecord(
        program="p", shape=shape, machine=machine,
        config={"width": 512}, samples=(100.0, 90.0, 110.0), occupancy=0.5,
    ))
    led.add(TimingRecord(
        program="p", shape=shape, machine=machine,
        config={"width": 128}, samples=(80.0, 85.0, 82.0), occupancy=0.97,
    ))
    led.add(TimingRecord(  # pruned: never timed, never "best"
        program="p", shape=shape, machine=machine,
        config={"width": 4096}, pruned="peak_bytes over budget",
    ))
    assert led.best("p", shape).config == {"width": 512}
    assert led.best("p", shape, min_occupancy=0.9).config == {"width": 128}
    path = led.save(tmp_path / "timings.json")
    reloaded = TimingLedger.load(path)
    assert len(reloaded.records()) == 3
    assert reloaded.best("p", shape, min_occupancy=0.9).config == {"width": 128}
    assert reloaded.records("p")[2].pruned == "peak_bytes over budget"


# ---------------------------------------------------------------------------
# the tuned-config cache
# ---------------------------------------------------------------------------


def _cartpole_linear_params() -> int:
    """The parameter count of the Linear(obs→act) cartpole policy every
    consumer in this file builds — part of the cache key."""
    from evotorch_tpu.envs import CartPole
    from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear

    env = CartPole()
    return FlatParamsPolicy(
        Linear(env.observation_size, env.action_size)
    ).parameter_count


def _cartpole_shape(popsize: int = 8) -> dict:
    return {
        "env": "cartpole",
        "popsize": popsize,
        "episode_length": 8,
        "num_episodes": 1,
        "params": _cartpole_linear_params(),
        "dtype": "float32",
    }


@pytest.fixture
def tuned_cache(tmp_path, monkeypatch):
    """An isolated cache file (EVOTORCH_TUNED_CACHE is the supported
    override) preloaded with a cartpole@popsize-8 refill entry for THIS
    machine + policy shape."""
    path = tmp_path / "tuned_configs.json"
    monkeypatch.setenv("EVOTORCH_TUNED_CACHE", str(path))
    entry = TunedEntry(
        group="refill",
        shape=_cartpole_shape(),
        machine=machine_fingerprint(),
        config={"width": 4, "period": 1},
        evidence={"steps_per_sec": 1.0},
    )
    save_tuned_entry(entry)
    return path


@pytest.fixture
def empty_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "EVOTORCH_TUNED_CACHE", str(tmp_path / "no_such_cache.json")
    )


def test_cache_lookup_exact_key_only(tuned_cache):
    shape = _cartpole_shape()
    hit = lookup_tuned("refill", shape)
    assert hit is not None and hit.config["width"] == 4
    assert lookup_tuned("refill", dict(shape, popsize=16)) is None
    assert lookup_tuned("refill", dict(shape, env="hopper")) is None
    assert lookup_tuned("compact", shape) is None
    # a different policy size or compute dtype is a different workload
    assert lookup_tuned("refill", dict(shape, params=999)) is None
    assert lookup_tuned("refill", dict(shape, dtype="bfloat16")) is None
    other_box = dict(machine_fingerprint(), core_count=99)
    assert lookup_tuned("refill", shape, machine=other_box) is None


def test_resolve_knobs_precedence(tuned_cache):
    shape = _cartpole_shape()
    # explicit beats cache, cache is not even consulted
    config, source = resolve_knobs({"width": 2}, "refill", shape)
    assert source == "override" and config == {"width": 2}
    # None-valued knobs do not count as explicit
    config, source = resolve_knobs({"width": None}, "refill", shape)
    assert source == "cache" and config == {"width": 4, "period": 1}
    # a miss is the engine default
    config, source = resolve_knobs({}, "refill", dict(shape, popsize=99))
    assert source == "fallback" and config == {}
    # use_cache=False forces the fallback branch
    config, source = resolve_knobs({}, "refill", shape, use_cache=False)
    assert source == "fallback" and config == {}


def test_corrupt_cache_degrades_to_fallback(tmp_path, monkeypatch):
    path = tmp_path / "corrupt.json"
    path.write_text("{not json")
    monkeypatch.setenv("EVOTORCH_TUNED_CACHE", str(path))
    assert load_tuned_cache(force=True) == {}
    _, source = resolve_knobs({}, "refill", _cartpole_shape())
    assert source == "fallback"


def test_canonical_env_label():
    from evotorch_tpu.envs import CartPole
    from evotorch_tpu.envs.classic import Swimmer2D

    assert canonical_env_label("Humanoid-v5") == "humanoid"
    assert canonical_env_label("gym::Hopper-v5") == "hopper"
    assert canonical_env_label("halfcheetah") == "halfcheetah"
    assert canonical_env_label(CartPole()) == "cartpole"
    # registry ALIASES fold to one identity — entries tuned under one
    # spelling must hit lookups under any other
    assert canonical_env_label("half_cheetah") == "halfcheetah"
    assert canonical_env_label("walker") == canonical_env_label("walker2d")
    assert (
        canonical_env_label("mountaincarcontinuous")
        == canonical_env_label("mountain_car_continuous")
    )
    # a live instance's class name folds too (Swimmer2D registers as
    # "swimmer")
    assert canonical_env_label(Swimmer2D()) == canonical_env_label("swimmer")


def test_seeded_cache_has_the_r8_refill_entries():
    """The checked-in cache ships the r8 CPU-box measurements, so this box
    stops defaulting to the mistuned work/8 width at the bench shapes
    (512 stays the documented no-cache fallback)."""
    import pathlib

    import evotorch_tpu.observability as obs

    machine = {"backend": "cpu", "device_kind": "cpu", "core_count": 1}
    # read the REAL checked-in file regardless of test-env overrides
    checked_in = pathlib.Path(obs.__file__).parent / "tuned_configs.json"
    entries = json.loads(checked_in.read_text())["entries"]
    by_key = {e["key"]: e for e in entries}
    # the (64,64) policy, f32, episode length 100 — the shape the r8 lines
    # were measured at
    shape = {
        "env": "humanoid",
        "episode_length": 100,
        "num_episodes": 1,
        "params": 12305,
        "dtype": "float32",
    }
    k1024 = timing_key("refill", dict(shape, popsize=1024), machine)
    k4096 = timing_key("refill", dict(shape, popsize=4096), machine)
    assert by_key[k1024]["config"]["width"] == 128
    assert by_key[k4096]["config"]["width"] == 256


# ---------------------------------------------------------------------------
# consumers: tuned_config_source provenance end to end
# ---------------------------------------------------------------------------


class _StubHarness:
    """A pure harness over the synthetic landscape — lets tune_group run
    end to end (ledger + cache write policy) with zero jax work."""

    group = "refill"
    program = "rollout.episodes_refill"

    def __init__(self, occupancy: float):
        self._occupancy = occupancy
        from evotorch_tpu.observability.autotune import TuneShape

        self.shape = TuneShape(env_name="cartpole", popsize=8)

        class _Policy:
            parameter_count = 7

        self.policy = _Policy()

    def knob_group(self):
        return KnobGroup("refill", (KnobSpec("width", (64, 128, 256)),))

    def default_config(self):
        return {"width": 128}

    def cost(self, config):
        return None

    def measure(self, configs, trials, round_index):
        return [
            {
                "samples": [float(_TRUE_RATE.get(c["width"], 50.0))] * trials,
                "occupancies": [self._occupancy] * trials,
                "steady_compiles": 0,
            }
            for c in configs
        ]

    def tuned_config(self, config):
        return {"width": config["width"], "period": 1}

    def baseline(self, trials=3):
        return {"steps_per_sec": 50.0, "occupancy": None, "samples": [50.0]}


def test_tune_group_withholds_floor_failing_winner_from_cache(
    tmp_path, monkeypatch
):
    from evotorch_tpu.observability.autotune import tune_group

    monkeypatch.setenv("EVOTORCH_TUNED_CACHE", str(tmp_path / "floor.json"))
    # every candidate starves (occupancy 0.4): select_winner falls back to
    # the throughput winner, but the cache write is withheld — a lucky-run
    # wide rung must not become this machine's persisted schedule
    outcome = tune_group(_StubHarness(occupancy=0.4), min_occupancy=0.9)
    assert outcome.winner is not None
    assert outcome.cache_written is False
    assert lookup_tuned("refill", _stub_shape()) is None
    # with the floor met, the same search persists
    outcome = tune_group(_StubHarness(occupancy=0.95), min_occupancy=0.9)
    assert outcome.cache_written is True
    hit = lookup_tuned("refill", _stub_shape())
    assert hit is not None and hit.config["width"] == 256


def _stub_shape() -> dict:
    # matches _StubHarness's TuneShape defaults (episode_length 100, one
    # episode) + its stub policy; the autotuner measures unsharded, so its
    # saved entries carry the "none" mesh label (ISSUE-13 schema v2)
    return {
        "env": "cartpole",
        "popsize": 8,
        "episode_length": 100,
        "num_episodes": 1,
        "params": 7,
        "dtype": "float32",
        "mesh": "none",
    }


def _tiny_vecne(**kwargs):
    from evotorch_tpu.neuroevolution import VecNE

    return VecNE(
        "cartpole",
        "Linear(obs_length, act_length)",
        eval_mode="episodes_refill",
        num_episodes=1,
        episode_length=8,
        **kwargs,
    )


def test_vecne_applies_cached_width_and_reports_source(tuned_cache):
    prob = _tiny_vecne()
    batch = prob.generate_batch(8)
    prob.evaluate(batch)
    prob.evaluate(batch)  # decode the lag-by-one telemetry
    status = prob._report_counters(batch)
    assert status["tuned_config_source"] == "cache"
    # the tuned width actually reached the engine: the telemetry's
    # lane_width IS the compiled program's fixed width
    assert prob._last_telemetry.lane_width == 4


def test_vecne_override_and_fallback_sources(tuned_cache, empty_cache):
    # note: empty_cache re-points EVOTORCH_TUNED_CACHE after tuned_cache
    # seeded its file, proving the explicit-knob branch never reads a file
    prob = _tiny_vecne(refill_config={"width": 8})
    batch = prob.generate_batch(8)
    prob.evaluate(batch)
    assert prob._report_counters(batch)["tuned_config_source"] == "override"

    prob = _tiny_vecne()
    batch = prob.generate_batch(8)
    prob.evaluate(batch)
    assert prob._report_counters(batch)["tuned_config_source"] == "fallback"


def test_sharded_evaluator_consults_cache_per_popsize(tuned_cache, monkeypatch):
    from jax.sharding import Mesh

    from evotorch_tpu.envs import CartPole
    from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear, Tanh
    from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
    from evotorch_tpu.observability import EvalTelemetry
    from evotorch_tpu.parallel.evaluate import make_sharded_rollout_evaluator

    env = CartPole()
    policy = FlatParamsPolicy(Linear(env.observation_size, env.action_size) >> Tanh())
    mesh = Mesh(np.asarray(jax.devices()[:2]), axis_names=("pop",))
    # sharded lookups are mesh-scoped (ISSUE 13): the fixture's unsharded
    # entry must NOT serve this pop2 evaluation, so seed the pop2 entry
    evaluator = make_sharded_rollout_evaluator(
        env, policy, mesh=mesh,
        num_episodes=1, episode_length=8, eval_mode="episodes_refill",
    )
    assert evaluator.tuned_config_source is None  # nothing dispatched yet
    stats = RunningNorm(env.observation_size).stats
    params = jax.random.normal(jax.random.key(0), (8, policy.parameter_count))
    result, _ = evaluator(params, jax.random.key(1), stats)
    # the fixture entry was tuned UNSHARDED — a pop2 mesh never inherits it
    assert evaluator.tuned_config_source == "fallback"

    save_tuned_entry(
        TunedEntry(
            group="refill",
            shape=dict(_cartpole_shape(), mesh="pop2"),
            machine=machine_fingerprint(),
            config={"width": 4, "period": 1},
            evidence={"steps_per_sec": 1.0},
        )
    )
    evaluator = make_sharded_rollout_evaluator(
        env, policy, mesh=mesh,
        num_episodes=1, episode_length=8, eval_mode="episodes_refill",
    )
    result, _ = evaluator(params, jax.random.key(1), stats)
    assert evaluator.tuned_config_source == "cache"
    # GSPMD: the cached width is GLOBAL and applies undivided (4 mesh-wide)
    assert EvalTelemetry.from_array(result.telemetry).lane_width == 4

    explicit = make_sharded_rollout_evaluator(
        env, policy, mesh=mesh,
        num_episodes=1, episode_length=8, eval_mode="episodes_refill",
        refill_width=8,
    )
    explicit(params, jax.random.key(1), stats)
    assert explicit.tuned_config_source == "override"

    # GROUP-level override semantics (the one precedence rule): an explicit
    # period ALSO disables the cache — the cached width was measured at its
    # cached period, and an unmeasured width/period mix must not wear a
    # "cache" label
    period_only = make_sharded_rollout_evaluator(
        env, policy, mesh=mesh,
        num_episodes=1, episode_length=8, eval_mode="episodes_refill",
        refill_period=2,
    )
    result, _ = period_only(params, jax.random.key(1), stats)
    assert period_only.tuned_config_source == "override"
    # the engine default width applied, not the cached 4
    assert EvalTelemetry.from_array(result.telemetry).lane_width != 4


def test_per_group_occupancy_floors(tmp_path, monkeypatch):
    """Compaction structurally runs ~0.5 occupancy (each chunk pads to its
    slowest survivor), so a refill-style 0.9 floor would make the compact
    winner permanently unpersistable — the floors are per group, and
    ``min_occupancy="auto"`` resolves through the harness."""
    from evotorch_tpu.observability.autotune import (
        CompactHarness,
        HostPipelineHarness,
        RefillHarness,
        tune_group,
    )

    assert RefillHarness.default_min_occupancy == 0.9
    assert CompactHarness.default_min_occupancy is None
    assert HostPipelineHarness.default_min_occupancy is None

    monkeypatch.setenv("EVOTORCH_TUNED_CACHE", str(tmp_path / "auto.json"))
    harness = _StubHarness(occupancy=0.5)
    harness.default_min_occupancy = None  # a floorless group, e.g. compact
    outcome = tune_group(harness)  # min_occupancy="auto"
    assert outcome.cache_written is True
    # the same sub-floor landscape with a refill-style floor is withheld
    harness = _StubHarness(occupancy=0.5)
    harness.default_min_occupancy = 0.9
    outcome = tune_group(harness, cache_path=str(tmp_path / "other.json"))
    assert outcome.cache_written is False


def test_host_pipeline_harness_has_tune_group_surface():
    """tune_group's budget derivation calls harness.default_config() on
    EVERY group under the default hbm_budget_ratio — the host harness must
    provide the full surface (it once lacked default_config and crashed
    `--group host_pipeline` before any trial)."""
    gym = pytest.importorskip("gymnasium")
    from evotorch_tpu.observability.autotune import (
        HostPipelineHarness,
        candidate_grid,
    )

    harness = HostPipelineHarness(env_id="CartPole-v1", num_envs=2, popsize=4)
    assert harness.default_config() is None
    assert harness.cost({"num_blocks": 1}) is None
    grid = candidate_grid(harness.knob_group())
    assert grid and all("num_blocks" in c for c in grid)
    # the anchor expression tune_group evaluates
    anchor = harness.default_config() or grid[0]
    assert anchor in grid


class _FixedLenEnv:
    """Minimal gym-API env: 1-dim obs, 3-step episodes, deterministic."""

    class _Box:
        low = np.asarray([-1.0])
        high = np.asarray([1.0])
        shape = (1,)

    observation_space = _Box()
    action_space = _Box()

    def __init__(self):
        self._t = 0

    def reset(self, seed=None):
        self._t = 0
        return np.asarray([1.0], dtype=np.float32), {}

    def step(self, action):
        self._t += 1
        return np.asarray([1.0], dtype=np.float32), 1.0, self._t >= 3, False, {}

    def close(self):
        pass


def test_host_pipeline_reports_tuned_source(tmp_path, monkeypatch, empty_cache):
    from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, Linear
    from evotorch_tpu.neuroevolution.net.hostvecenv import (
        SyncVectorEnv,
        run_host_pipelined_rollout,
    )

    policy = FlatParamsPolicy(Linear(1, 1))
    params = jnp.zeros((4, policy.parameter_count), dtype=jnp.float32)

    def run(num_blocks=None, **kw):
        vec = SyncVectorEnv(_FixedLenEnv, 2)
        try:
            return run_host_pipelined_rollout(
                vec, policy, params, num_episodes=1, episode_length=5,
                mode="sync", num_blocks=num_blocks,
                rng=np.random.default_rng(0), **kw,
            )
        finally:
            vec.close()

    assert run(num_blocks=2)["tuned_config_source"] == "override"
    assert run()["tuned_config_source"] == "fallback"
    # a caller that resolved the group at its own altitude (GymNE) stamps
    # the TRUE provenance: its cache-sourced concrete block count must not
    # be relabeled "override" here
    out = run(num_blocks=2, use_tuned_cache=False, tuned_config_source="cache")
    assert out["tuned_config_source"] == "cache"

    # a machine-scoped host_pipeline entry flips the auto branch to cache
    monkeypatch.setenv("EVOTORCH_TUNED_CACHE", str(tmp_path / "host.json"))
    save_tuned_entry(
        TunedEntry(
            group="host_pipeline", shape={}, machine=machine_fingerprint(),
            config={"num_blocks": 2}, evidence={},
        )
    )
    out = run()
    assert out["tuned_config_source"] == "cache"
    assert len(out["block_iters"]) == 2  # the cached split was applied

    # an entry measured as a JOINT config (blocks + nthread together) must
    # NOT be half-applied at this altitude (nthread is baked into the
    # vec env) — partial application labeled "cache" would attribute the
    # run to a configuration never measured
    import os

    heuristic = 2 if (os.cpu_count() or 1) > 1 else 1  # the no-cache split
    save_tuned_entry(
        TunedEntry(
            group="host_pipeline", shape={}, machine=machine_fingerprint(),
            config={"num_blocks": 3 - heuristic, "mj_nthread": 2}, evidence={},
        )
    )
    out = run()
    assert out["tuned_config_source"] == "fallback"
    assert len(out["block_iters"]) == heuristic  # not the entry's split


def test_gymne_reports_tuned_source(empty_cache):
    pytest.importorskip("gymnasium")
    from evotorch_tpu.neuroevolution import GymNE

    prob = GymNE(
        env="gym::CartPole-v1",
        network="Linear(obs_length, act_length)",
        num_envs=2,
        episode_length=8,
    )
    batch = prob.generate_batch(2)
    prob.evaluate(batch)
    assert prob._report_counters(batch)["tuned_config_source"] == "fallback"

    prob = GymNE(
        env="gym::CartPole-v1",
        network="Linear(obs_length, act_length)",
        num_envs=2,
        episode_length=8,
        host_pipeline_blocks=1,
    )
    batch = prob.generate_batch(2)
    prob.evaluate(batch)
    assert prob._report_counters(batch)["tuned_config_source"] == "override"
