"""Occupancy-driven autotuner: close the loop from telemetry to knobs.

PR 8's very first occupancy readout proved the default refill width
mistuned on the r8 CPU box (width 128 → occupancy 0.97,
refill_speedup 1.72x, vs 0.83 at the work/8 default); this module is the
loop-closer (ROADMAP item 2, the Podracer discipline of arXiv:2104.06272):
**measured device utilization, not guesses, picks the schedule.**

The loop::

    on-device counters ──► trial harness ──► measured-timing ledger
       (PR 8: occupancy,     (interleaved      (timings.TimingLedger:
        queue_wait,           medians of ≥3)    steps/s + occupancy +
        refill_events)             │            compile_s per machine key)
                                   │                      │
    program ledger ──► analytic pruning            winner persisted
       (PR 9: peak-HBM /   (reject before                 │
        FLOPs bounds)       ever timing)                  ▼
                                            tuned_configs.json ──► consumers
                                              (checked in)    VecNE · GymNE ·
                                                               hostvecenv ·
                                                               parallel.evaluate

Three layers:

- **The pure search core** — :func:`candidate_grid`,
  :func:`neighborhood`, :func:`analytic_prune`,
  :func:`successive_halving`, :func:`autotune_search`. Deterministic,
  zero wall-clock, no jax: unit-testable against a synthetic measurement
  function (tier-1 does exactly that). Selection is always on **medians**
  (this box times ±20% run to run — CLAUDE.md), with an occupancy floor
  on the winner (a config that starves lanes does not win on a lucky
  run).
- **The trial harnesses** — :class:`RefillHarness` /
  :class:`CompactHarness` (the bespoke-sim device knobs) and
  :class:`HostPipelineHarness` (the host-path knobs). Candidates are
  interleaved in ONE process; every timed call runs under the retrace
  sentinel (a mid-loop compile invalidates the sample and shows up as
  ``steady_compiles``), telemetry is decoded after the clock stops, and
  each trial emits an ``autotune.trial`` tracer span carrying the
  candidate config as span args — a tuning run under ``EVOTORCH_TRACE``
  is inspectable in Perfetto next to the ask/eval/tell spans.
- **The CLI** — ``python -m evotorch_tpu.observability.autotune``:
  tunes the requested knob groups at the shape its arguments give
  (``--env``, ``--popsize``, ``--episode-length``, ``--hidden``,
  ``--bf16``), records every candidate in the
  measured-timing ledger, and persists each winner to the tuned-config
  cache (:mod:`~evotorch_tpu.observability.timings`) that the eval stack
  consults at setup time. It requires an accelerator unless the CPU is
  asked for (``--cpu`` / ``JAX_PLATFORMS=cpu``); there is no fallback.
"""

from __future__ import annotations

import itertools
import math
import time
from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
)

from ..resilience.devices import device_record, setup_backend
from . import tracer
from .compilecache import enable_persistent_cache
from .timings import (
    TimingLedger,
    TimingRecord,
    TunedEntry,
    _median,
    dtype_label,
    machine_fingerprint,
    timings,
)

__all__ = [
    "CandidateStats",
    "CompactHarness",
    "HostPipelineHarness",
    "KnobGroup",
    "KnobSpec",
    "PolicyHarness",
    "RefillHarness",
    "SearchOutcome",
    "SpanHarness",
    "analytic_prune",
    "autotune_search",
    "candidate_grid",
    "neighborhood",
    "successive_halving",
]


# ---------------------------------------------------------------------------
# the pure search core (no jax, no clocks — tier-1 tests run it synthetically)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KnobSpec:
    """One tunable knob: a name and its ORDERED value grid. ``refine``
    marks knobs whose neighborhood may propose off-grid midpoints (widths
    and chunk sizes are continuous-ish integers; a boolean or enum knob
    sets it False)."""

    name: str
    values: Tuple[Any, ...]
    refine: bool = True


@dataclass(frozen=True)
class KnobGroup:
    """A named set of knobs tuned together (one cache entry per group)."""

    name: str
    knobs: Tuple[KnobSpec, ...]


def candidate_grid(group: KnobGroup) -> List[Dict[str, Any]]:
    """The full cartesian candidate grid, in deterministic knob-major
    order (the order is load-bearing: ties in the search break toward
    earlier candidates, so grids should list preferred defaults first)."""
    names = [k.name for k in group.knobs]
    return [
        dict(zip(names, combo))
        for combo in itertools.product(*(k.values for k in group.knobs))
    ]


def neighborhood(group: KnobGroup, config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """One-knob-at-a-time refinements around ``config``: for each
    refinable integer knob, the (rounded) midpoints between its current
    value and the adjacent grid values. Off-grid by construction —
    candidates already in the grid were already measured — and
    deterministic (no randomness anywhere in the core)."""
    out: List[Dict[str, Any]] = []
    seen = set()
    for knob in group.knobs:
        if not knob.refine:
            continue
        current = config.get(knob.name)
        if not isinstance(current, int):
            continue
        values = sorted(v for v in knob.values if isinstance(v, int))
        if current not in values:
            continue
        i = values.index(current)
        for j in (i - 1, i + 1):
            if not (0 <= j < len(values)):
                continue
            mid = (current + values[j]) // 2
            if mid in values or mid == current or mid <= 0:
                continue
            candidate = dict(config, **{knob.name: mid})
            key = tuple(sorted(candidate.items()))
            if key not in seen:
                seen.add(key)
                out.append(candidate)
    return out


def analytic_prune(
    candidates: Sequence[Dict[str, Any]],
    cost_fn: Optional[Callable[[Dict[str, Any]], Optional[Dict[str, Any]]]],
    *,
    hbm_budget_bytes: Optional[float] = None,
    flops_bound: Optional[float] = None,
) -> Tuple[List[Dict[str, Any]], List[Tuple[Dict[str, Any], str]], Dict[int, Dict]]:
    """Reject candidates on the PR 9 cost model BEFORE any wall-clock is
    spent on them: a candidate whose captured program analyzes over the
    peak-HBM budget or the FLOPs bound never reaches the trial harness.

    ``cost_fn(config)`` returns ``{"peak_bytes", "flops",
    "compile_seconds"}`` (any field nullable) or ``None`` when no
    analysis is available — unknown cost NEVER prunes (the guarded-
    accessor discipline: missing analysis degrades, it doesn't reject).

    Returns ``(kept, pruned, costs)`` where ``pruned`` carries the
    human-readable reason and ``costs`` maps an index INTO ``kept`` (the
    surviving candidates, in order) to its cost dict, so the caller can
    attach ``compile_seconds`` to the matching measurement records."""
    kept: List[Dict[str, Any]] = []
    pruned: List[Tuple[Dict[str, Any], str]] = []
    costs: Dict[int, Dict] = {}
    for config in candidates:
        cost = cost_fn(config) if cost_fn is not None else None
        if cost is not None:
            peak = cost.get("peak_bytes")
            if (
                hbm_budget_bytes is not None
                and peak is not None
                and peak > hbm_budget_bytes
            ):
                pruned.append(
                    (
                        config,
                        f"peak_bytes {peak:.3g} exceeds HBM budget "
                        f"{hbm_budget_bytes:.3g}",
                    )
                )
                continue
            flops = cost.get("flops")
            if flops_bound is not None and flops is not None and flops > flops_bound:
                pruned.append(
                    (config, f"flops {flops:.3g} exceeds bound {flops_bound:.3g}")
                )
                continue
        if cost is not None:
            costs[len(kept)] = cost
        kept.append(config)
    return kept, pruned, costs


@dataclass
class CandidateStats:
    """Accumulated measurement state of one candidate across rounds."""

    config: Dict[str, Any]
    samples: List[float] = field(default_factory=list)
    occupancies: List[float] = field(default_factory=list)
    steady_compiles: int = 0
    refill_events: Optional[int] = None
    queue_wait: Optional[int] = None
    cost: Optional[Dict[str, Any]] = None

    @property
    def steps_per_sec(self) -> float:
        """The headline figure: the MEDIAN of every timed sample."""
        return _median(self.samples)

    @property
    def occupancy(self) -> Optional[float]:
        return _median(self.occupancies) if self.occupancies else None

    def merge(self, measurement: Dict[str, Any]) -> None:
        self.samples.extend(measurement.get("samples", ()))
        self.occupancies.extend(measurement.get("occupancies", ()))
        self.steady_compiles += int(measurement.get("steady_compiles", 0))
        for key in ("refill_events", "queue_wait"):
            value = measurement.get(key)
            if value is not None:
                setattr(self, key, value)


#: measure(configs, trials, round_index) -> one measurement dict per config,
#: each {"samples": [...], "occupancies": [...], "steady_compiles": int, ...}
MeasureFn = Callable[[List[Dict[str, Any]], int, int], List[Dict[str, Any]]]


def successive_halving(
    candidates: Sequence[Dict[str, Any]],
    measure: MeasureFn,
    *,
    trials_per_round: int = 3,
    survivor_frac: float = 0.5,
    min_survivors: int = 2,
    max_rounds: int = 2,
) -> List[CandidateStats]:
    """Successive halving on MEDIANS: every round measures all surviving
    candidates (``trials_per_round`` more samples each — the harness
    interleaves them in one process), then keeps the top
    ``survivor_frac`` by median steps/s. Survivors accumulate samples
    across rounds, so the final ranking rests on the most-measured
    medians. Deterministic: ties break toward the earlier candidate."""
    results = [CandidateStats(config=dict(c)) for c in candidates]
    alive = list(range(len(results)))
    trials = max(1, int(trials_per_round))
    for round_index in range(max(1, int(max_rounds))):
        if not alive:
            break
        measured = measure(
            [results[i].config for i in alive], trials, round_index
        )
        for i, m in zip(alive, measured):
            results[i].merge(m)
        if len(alive) <= min_survivors:
            break
        ranked = sorted(alive, key=lambda i: (-results[i].steps_per_sec, i))
        keep = max(min_survivors, math.ceil(len(alive) * survivor_frac))
        alive = sorted(ranked[:keep])
    return results


def select_winner(
    results: Sequence[CandidateStats],
    *,
    min_occupancy: Optional[float] = None,
    tolerance: Optional[float] = None,
    prefer: Optional[Callable[[Dict[str, Any]], Any]] = None,
) -> Optional[CandidateStats]:
    """Highest median steps/s among measured candidates meeting the
    occupancy floor — falling back to the unconstrained winner when none
    do (a floor must never select nothing). Candidates that paid a
    steady-state compile mid-trial are untrustworthy timings and lose to
    any clean candidate.

    ``tolerance`` + ``prefer`` select on a SECONDARY objective inside a
    throughput band: among candidates whose median steps/s is within
    ``tolerance`` (a fraction) of the best, the one maximizing
    ``prefer(config)`` wins, with throughput breaking preference ties.
    The policy group uses this — expressivity (rank) is worth a bounded
    throughput haircut, so the highest rank within the band wins rather
    than the outright-fastest rank-4 corner."""
    measured = [r for r in results if r.samples]
    if not measured:
        return None
    clean = [r for r in measured if r.steady_compiles == 0]
    pool = clean or measured
    if min_occupancy is not None:
        eligible = [
            r for r in pool if r.occupancy is not None and r.occupancy >= min_occupancy
        ]
        if eligible:
            pool = eligible
    best = max(pool, key=lambda r: r.steps_per_sec)
    if tolerance is None or prefer is None:
        return best
    floor = best.steps_per_sec * (1.0 - float(tolerance))
    near = [r for r in pool if r.steps_per_sec >= floor]
    return max(near, key=lambda r: (prefer(r.config), r.steps_per_sec))


@dataclass
class SearchOutcome:
    """Everything one group's search produced: ranked candidate stats
    (grid + refinement), the analytically-pruned configs with reasons,
    and the selected winner. ``cache_written`` is stamped by
    :func:`tune_group`: False when the winner was withheld from the cache
    (retrace-dirty timing, occupancy floor not met, or ``write_cache``
    off)."""

    results: List[CandidateStats]
    pruned: List[Tuple[Dict[str, Any], str]]
    winner: Optional[CandidateStats]
    cache_written: bool = False


def autotune_search(
    group: KnobGroup,
    measure: MeasureFn,
    *,
    cost_fn: Optional[Callable[[Dict[str, Any]], Optional[Dict[str, Any]]]] = None,
    hbm_budget_bytes: Optional[float] = None,
    flops_bound: Optional[float] = None,
    trials_per_round: int = 3,
    survivor_frac: float = 0.5,
    min_survivors: int = 2,
    max_rounds: int = 2,
    min_occupancy: Optional[float] = None,
    tolerance: Optional[float] = None,
    prefer: Optional[Callable[[Dict[str, Any]], Any]] = None,
    refine: bool = True,
) -> SearchOutcome:
    """The full (pure) search: grid → analytic prune → successive
    halving → winner → one neighborhood-refinement round around the
    winner (off-grid midpoints, themselves prune-checked) → final
    winner. ``measure``/``cost_fn`` carry all the impurity; everything
    here is deterministic given their outputs. ``tolerance``/``prefer``
    pass through to :func:`select_winner` (secondary-objective
    selection inside a throughput band)."""
    grid = candidate_grid(group)
    kept, pruned, costs = analytic_prune(
        grid, cost_fn, hbm_budget_bytes=hbm_budget_bytes, flops_bound=flops_bound
    )
    results = successive_halving(
        kept,
        measure,
        trials_per_round=trials_per_round,
        survivor_frac=survivor_frac,
        min_survivors=min_survivors,
        max_rounds=max_rounds,
    )
    for index, cost in costs.items():
        results[index].cost = cost
    winner = select_winner(
        results, min_occupancy=min_occupancy, tolerance=tolerance, prefer=prefer
    )
    if refine and winner is not None:
        measured_keys = {tuple(sorted(r.config.items())) for r in results}
        fresh = [
            c
            for c in neighborhood(group, winner.config)
            if tuple(sorted(c.items())) not in measured_keys
        ]
        kept2, pruned2, costs2 = analytic_prune(
            fresh,
            cost_fn,
            hbm_budget_bytes=hbm_budget_bytes,
            flops_bound=flops_bound,
        )
        pruned.extend(pruned2)
        if kept2:
            refined = successive_halving(
                kept2,
                measure,
                trials_per_round=trials_per_round,
                survivor_frac=1.0,  # no halving inside one refinement round
                min_survivors=len(kept2),
                max_rounds=1,
            )
            for index, cost in costs2.items():
                refined[index].cost = cost
            results = results + refined
            winner = select_winner(
                results,
                min_occupancy=min_occupancy,
                tolerance=tolerance,
                prefer=prefer,
            )
    return SearchOutcome(results=results, pruned=pruned, winner=winner)


# ---------------------------------------------------------------------------
# trial harnesses (the impure half: jax programs, clocks, telemetry)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TuneShape:
    """The workload shape a tuning run measures at."""

    env_name: str = "humanoid"
    popsize: int = 1024
    episode_length: int = 100
    hidden: Tuple[int, ...] = (64, 64)
    compute_dtype: Any = None  # e.g. jnp.bfloat16; None = float32
    num_episodes: int = 1

    def as_dict(self) -> Dict[str, Any]:
        return {
            "env": self.env_name,
            "popsize": self.popsize,
            "episode_length": self.episode_length,
        }


class _BespokeHarness:
    """Shared scaffolding of the bespoke-sim (device-program) harnesses:
    one env/policy/population built once, per-call PRNG keys derived by
    ``fold_in`` from a base key (never reused), interleaved timed trials
    under the retrace sentinel, telemetry decoded after the clock stops,
    and an ``autotune.trial`` tracer span per timed call."""

    group = ""  # knob-group / cache-entry name
    program = ""  # timing-ledger program name
    #: per-group winner floor (subclasses override; None = throughput only)
    default_min_occupancy: Optional[float] = None
    #: secondary-objective selection (select_winner's tolerance/prefer):
    #: None on throughput-only groups; the policy group trades a bounded
    #: throughput haircut for rank
    winner_tolerance: Optional[float] = None
    winner_prefer: Optional[Callable[[Dict[str, Any]], Any]] = None

    def __init__(self, shape: TuneShape, *, seed: int = 0):
        import jax
        from functools import partial

        from ..algorithms.functional import pgpe, pgpe_ask
        from ..envs import make_env
        from ..neuroevolution.net import FlatParamsPolicy, tanh_mlp
        from ..neuroevolution.net.runningnorm import RunningNorm

        self.shape = shape
        self.env = make_env(shape.env_name)
        self.policy = FlatParamsPolicy(
            tanh_mlp(self.env.observation_size, self.env.action_size, shape.hidden)
        )
        import jax.numpy as jnp

        state = pgpe(
            center_init=jnp.zeros(self.policy.parameter_count, dtype=jnp.float32),
            center_learning_rate=0.1,
            stdev_learning_rate=0.1,
            objective_sense="max",
            stdev_init=0.1,
        )
        # one fixed population for every candidate and trial: candidates
        # compete on the SAME work list, so schedule quality is the only
        # difference being measured
        ask = jax.jit(partial(pgpe_ask, popsize=shape.popsize))
        self.values = ask(jax.random.key(seed), state)
        jax.block_until_ready(self.values)
        self.stats = RunningNorm(self.env.observation_size).stats
        self._base_key = jax.random.key(seed + 1)
        self._nonce = itertools.count()
        self._episodes_baseline: Optional[Dict[str, Any]] = None
        self._warmed_configs: set = set()

    # -- per-candidate program runners (overridden) -------------------------
    def run_once(self, config: Dict[str, Any], key, *, warmup: bool = False):
        raise NotImplementedError

    def tuned_config(self, config: Dict[str, Any]) -> Dict[str, Any]:
        """Map harness knob names to the cache entry's config keys."""
        return dict(config)

    def default_config(self) -> Optional[Dict[str, Any]]:
        """The built-in-default candidate — the anchor the relative HBM
        budget is derived from (the default is definitionally feasible)."""
        return None

    def knob_group(self) -> KnobGroup:
        raise NotImplementedError

    def cost(self, config: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        return None

    # -- the shared measurement machinery -----------------------------------
    def _next_key(self):
        import jax

        # fold_in with a fresh nonce per timed call: unique per-call keys,
        # no key ever consumed twice (the graftlint prng discipline)
        return jax.random.fold_in(self._base_key, next(self._nonce))

    def _timed_call(self, label: str, config: Dict[str, Any], runner):
        """One timed trial: sentinel around the call, clock stopped at
        ``block_until_ready``, telemetry decoded afterwards. Returns
        ``(steps_per_sec, telemetry, compiles)``."""
        import jax

        from ..analysis import track_compiles
        from . import EvalTelemetry

        key = self._next_key()
        with tracer.span("autotune.trial", "autotune", group=label, **config):
            with track_compiles() as compile_log:
                t0 = time.perf_counter()
                result = runner(key)
                jax.block_until_ready(result.scores)
                elapsed = time.perf_counter() - t0
        steps = int(result.total_steps)
        telemetry = (
            EvalTelemetry.from_array(result.telemetry)
            if result.telemetry is not None
            else None
        )
        return steps / elapsed if elapsed > 0 else 0.0, telemetry, compile_log.count

    def measure(
        self, configs: List[Dict[str, Any]], trials: int, round_index: int
    ) -> List[Dict[str, Any]]:
        """The real MeasureFn: warm every candidate once (compiles land
        outside every clock), then interleave candidates within each
        trial sweep — the CLAUDE.md ±20% rule — so drift hits all
        candidates alike."""
        for config in configs:
            # warm once per candidate PER SEARCH (not per round): a warmup
            # is a full untimed evaluation, and survivors of round 0 are
            # already compiled
            warm_key = tuple(sorted(config.items()))
            if warm_key in self._warmed_configs:
                continue
            self._warmed_configs.add(warm_key)
            self.run_once(config, self._next_key(), warmup=True)
        out = [
            {"samples": [], "occupancies": [], "steady_compiles": 0}
            for _ in configs
        ]
        for _ in range(trials):
            for i, config in enumerate(configs):
                sps, telemetry, compiles = self._timed_call(
                    self.group, config, lambda key, c=config: self.run_once(c, key)
                )
                out[i]["samples"].append(sps)
                out[i]["steady_compiles"] += compiles
                if telemetry is not None:
                    out[i]["occupancies"].append(telemetry.occupancy)
                    out[i]["refill_events"] = telemetry.refill_events
                    out[i]["queue_wait"] = telemetry.queue_wait
        return out

    def baseline(self, trials: int = 3) -> Dict[str, Any]:
        """Median steps/s of the monolithic ``episodes`` contract at the
        same shape — the denominator of ``refill_speedup`` /
        ``compaction_speedup`` (measured in the same process, same
        population)."""
        if self._episodes_baseline is not None:
            return self._episodes_baseline
        from ..neuroevolution.net.vecrl import run_vectorized_rollout

        def runner(key):
            return run_vectorized_rollout(
                self.env,
                self.policy,
                self.values,
                key,
                self.stats,
                eval_mode="episodes",
                num_episodes=self.shape.num_episodes,
                episode_length=self.shape.episode_length,
                compute_dtype=self.shape.compute_dtype,
            )

        import jax

        jax.block_until_ready(runner(self._next_key()).scores)  # warmup
        samples, occupancies = [], []
        for _ in range(max(1, trials)):
            sps, telemetry, _ = self._timed_call(
                "episodes", {"contract": "episodes"}, runner
            )
            samples.append(sps)
            if telemetry is not None:
                occupancies.append(telemetry.occupancy)
        self._episodes_baseline = {
            "steps_per_sec": _median(samples),
            "occupancy": _median(occupancies) if occupancies else None,
            "samples": samples,
        }
        return self._episodes_baseline


def _pow2_menu(values, lo: int, hi: int) -> Tuple[int, ...]:
    return tuple(sorted({int(v) for v in values if lo <= int(v) <= hi}))


class RefillHarness(_BespokeHarness):
    """Tunes the ``episodes_refill`` scheduler: lane width + refill
    period. The width menu brackets the engine's work/8 default with the
    fixed 64..512 rungs the r8 sweep used, so the search always measures
    the default it might replace."""

    group = "refill"
    program = "rollout.episodes_refill"
    #: the r8/acceptance bar: a refill schedule that starves lanes must
    #: not win on a lucky throughput run
    default_min_occupancy: Optional[float] = 0.9

    def __init__(
        self,
        shape: TuneShape,
        *,
        widths: Optional[Sequence[int]] = None,
        periods: Sequence[int] = (1,),
        seed: int = 0,
    ):
        super().__init__(shape, seed=seed)
        from ..neuroevolution.net.vecrl import _default_refill_width

        total_items = shape.popsize * shape.num_episodes
        if widths is None:
            base = _default_refill_width(total_items)
            widths = _pow2_menu(
                (64, 128, 256, 512, base // 2, base, base * 2),
                lo=8,
                hi=total_items,
            )
        self.widths = tuple(int(w) for w in widths)
        if not self.widths:
            raise ValueError(
                f"empty refill width menu for work-list size {total_items} "
                "(the default rungs all fall outside [8, work]); pass "
                "--widths explicitly"
            )
        self.periods = tuple(int(p) for p in periods)
        self._default_width = min(
            _default_refill_width(total_items), max(self.widths)
        )

    def default_config(self):
        return {
            "refill_width": self._default_width,
            "refill_period": self.periods[0],
        }

    def knob_group(self) -> KnobGroup:
        return KnobGroup(
            name=self.group,
            knobs=(
                KnobSpec("refill_width", self.widths),
                KnobSpec("refill_period", self.periods, refine=False),
            ),
        )

    def run_once(self, config, key, *, warmup: bool = False):
        from ..neuroevolution.net.vecrl import run_vectorized_rollout

        result = run_vectorized_rollout(
            self.env,
            self.policy,
            self.values,
            key,
            self.stats,
            eval_mode="episodes_refill",
            refill_width=int(config["refill_width"]),
            refill_period=int(config.get("refill_period", 1)),
            num_episodes=self.shape.num_episodes,
            episode_length=self.shape.episode_length,
            compute_dtype=self.shape.compute_dtype,
        )
        if warmup:
            import jax

            jax.block_until_ready(result.scores)
        return result

    def cost(self, config):
        """PR 9 analytic cost of the candidate's compiled program (one
        AOT capture — outside every timed region; the compile_seconds
        figure lands in the timing record)."""
        import jax

        from .programs import ProgramLedger
        from ..neuroevolution.net.vecrl import run_vectorized_rollout

        led = ProgramLedger()
        record = led.capture(
            self.program,
            run_vectorized_rollout,
            self.env,
            self.policy,
            jax.ShapeDtypeStruct(self.values.shape, self.values.dtype),
            jax.random.key(0),
            self.stats,
            shape=dict(self.shape.as_dict(), **config),
            eval_mode="episodes_refill",
            refill_width=int(config["refill_width"]),
            refill_period=int(config.get("refill_period", 1)),
            num_episodes=self.shape.num_episodes,
            episode_length=self.shape.episode_length,
            compute_dtype=self.shape.compute_dtype,
        )
        return {
            "peak_bytes": record.peak_bytes,
            "flops": record.flops,
            "compile_seconds": record.compile_seconds,
        }

    def tuned_config(self, config):
        return {
            "width": int(config["refill_width"]),
            "period": int(config.get("refill_period", 1)),
        }


class CompactHarness(_BespokeHarness):
    """Tunes the lane-compacting runner: host chunk size × width-menu
    floor."""

    group = "compact"
    program = "rollout.episodes_compact"
    #: compaction STRUCTURALLY runs below full occupancy (~0.5 at the
    #: bench shapes — r8/r11 measurements): the contract pads each chunk
    #: to its slowest survivor by design, so a refill-style 0.9 floor
    #: would make every winner unpersistable. Select on throughput.
    default_min_occupancy: Optional[float] = None

    def __init__(
        self,
        shape: TuneShape,
        *,
        chunks: Sequence[int] = (10, 25, 50),
        min_widths: Sequence[int] = (128, 256, 512),
        seed: int = 0,
    ):
        super().__init__(shape, seed=seed)
        total = shape.popsize * shape.num_episodes
        self.chunks = tuple(int(c) for c in chunks)
        self.min_widths = tuple(w for w in (int(w) for w in min_widths) if w < total)
        if not self.min_widths:
            raise ValueError(
                f"no min_width candidate below the work-list size {total}; "
                "pass --min-widths values smaller than popsize*num_episodes"
            )

    def default_config(self):
        chunk = 25 if 25 in self.chunks else self.chunks[0]
        width = 256 if 256 in self.min_widths else self.min_widths[0]
        return {"chunk_size": chunk, "min_width": width}

    def knob_group(self) -> KnobGroup:
        return KnobGroup(
            name=self.group,
            knobs=(
                KnobSpec("chunk_size", self.chunks),
                KnobSpec("min_width", self.min_widths),
            ),
        )

    def run_once(self, config, key, *, warmup: bool = False):
        from ..neuroevolution.net.vecrl import run_vectorized_rollout_compacting

        # the warmup call (one per candidate — the base class dedups) runs
        # prewarm=True, compiling the candidate's whole width-descent chain
        # (the chunk step count is static in the jitted chunk program), so
        # timed calls stay compile-free
        result = run_vectorized_rollout_compacting(
            self.env,
            self.policy,
            self.values,
            key,
            self.stats,
            chunk_size=int(config["chunk_size"]),
            min_width=int(config["min_width"]),
            prewarm=warmup,
            num_episodes=self.shape.num_episodes,
            episode_length=self.shape.episode_length,
            compute_dtype=self.shape.compute_dtype,
        )
        if warmup:
            import jax

            jax.block_until_ready(result.scores)
        return result

    def cost(self, config):
        """Cost of the full-width chunk program — the dominant compiled
        unit of the host-orchestrated contract (the width descent reruns
        the same program at narrower shapes)."""
        from .inventory import capture_compact_chunk
        from .programs import ProgramLedger

        led = ProgramLedger()
        record = capture_compact_chunk(
            led,
            self.env,
            self.policy,
            self.shape.popsize,
            self.shape.episode_length,
            chunk_size=int(config["chunk_size"]),
            compute_dtype=self.shape.compute_dtype,
            name=self.program + ".chunk",
            shape=dict(self.shape.as_dict(), **config),
        )
        return {
            "peak_bytes": record.peak_bytes,
            "flops": record.flops,
            "compile_seconds": record.compile_seconds,
        }

    def tuned_config(self, config):
        return {
            "chunk_size": int(config["chunk_size"]),
            "min_width": int(config["min_width"]),
        }


class PolicyHarness(_BespokeHarness):
    """Tunes the trunk-delta POLICY FORM knobs: delta rank × lane-block
    size (docs/policies.md). Unlike the schedule groups, each rank
    candidate evaluates its OWN factored population (same trunk, same
    base PRNG key) — rank changes the program being measured, not just
    its schedule — so the harness keeps one ``TrunkDeltaParamsBatch``
    per rank, built once. Selection is throughput-within-tolerance with
    rank as the preference: a higher rank buys expressivity (more
    sampling subspace per generation — the subspace-exhaustion guardrail
    bites later), so the HIGHEST rank within ``winner_tolerance`` of the
    fastest candidate wins rather than the outright-fastest low-rank
    corner."""

    group = "policy"
    program = "rollout.budget.trunk_delta"
    #: the budget contract keeps every lane active; throughput selection
    default_min_occupancy: Optional[float] = None
    #: the rank-preference band: a candidate within 10% of the fastest
    #: median is "as fast" on this box's ±20% timing noise
    winner_tolerance: Optional[float] = 0.1
    winner_prefer = staticmethod(lambda config: int(config.get("rank", 0)))

    def __init__(
        self,
        shape: TuneShape,
        *,
        ranks: Sequence[int] = (4, 16, 64),
        trunk_blocks: Sequence[int] = (0,),
        seed: int = 0,
    ):
        super().__init__(shape, seed=seed)
        self.ranks = tuple(sorted({int(r) for r in ranks if int(r) > 0}))
        if not self.ranks:
            raise ValueError("empty rank menu; pass --ranks with positive ints")
        # the blocked lane path requires popsize % block == 0 (vecrl's
        # trunk_block contract); 0 = unblocked is always valid
        self.trunk_blocks = tuple(
            sorted(
                {
                    int(b)
                    for b in trunk_blocks
                    if int(b) == 0
                    or (0 < int(b) < shape.popsize and shape.popsize % int(b) == 0)
                }
            )
        )
        if not self.trunk_blocks:
            self.trunk_blocks = (0,)
        self._rank_batches: Dict[int, Any] = {}
        self._seed = int(seed)

    def _params_for(self, rank: int):
        """The rank's trunk-delta population, built once per search: every
        candidate at this rank (and every trial) times the SAME batch."""
        rank = int(rank)
        if rank not in self._rank_batches:
            import jax
            import jax.numpy as jnp

            from ..algorithms.functional import pgpe, pgpe_ask_trunk_delta

            state = pgpe(
                center_init=jnp.zeros(
                    self.policy.parameter_count, dtype=jnp.float32
                ),
                center_learning_rate=0.1,
                stdev_learning_rate=0.1,
                objective_sense="max",
                stdev_init=0.1,
            )
            batch = pgpe_ask_trunk_delta(
                jax.random.key(self._seed),
                state,
                popsize=self.shape.popsize,
                rank=rank,
                policy=self.policy,
            )
            jax.block_until_ready(batch.coeffs)
            self._rank_batches[rank] = batch
        return self._rank_batches[rank]

    def default_config(self):
        return {"rank": self.ranks[0], "trunk_block": 0}

    def knob_group(self) -> KnobGroup:
        return KnobGroup(
            name=self.group,
            knobs=(
                # menu-only knobs: a refined off-grid rank would need a
                # fresh population + compile per midpoint, and block sizes
                # off the divisor menu violate the popsize % block contract
                KnobSpec("rank", self.ranks, refine=False),
                KnobSpec("trunk_block", self.trunk_blocks, refine=False),
            ),
        )

    def run_once(self, config, key, *, warmup: bool = False):
        from ..neuroevolution.net.vecrl import run_vectorized_rollout

        result = run_vectorized_rollout(
            self.env,
            self.policy,
            self._params_for(config["rank"]),
            key,
            self.stats,
            eval_mode="budget",
            trunk_block=int(config.get("trunk_block", 0)),
            num_episodes=self.shape.num_episodes,
            episode_length=self.shape.episode_length,
            compute_dtype=self.shape.compute_dtype,
        )
        if warmup:
            import jax

            jax.block_until_ready(result.scores)
        return result

    def cost(self, config):
        """Analytic cost of the candidate's trunk-delta budget program
        (one AOT capture, outside every timed region)."""
        import jax

        from .programs import ProgramLedger, abstract_like
        from ..neuroevolution.net.vecrl import run_vectorized_rollout

        led = ProgramLedger()
        record = led.capture(
            self.program,
            run_vectorized_rollout,
            self.env,
            self.policy,
            abstract_like(self._params_for(config["rank"])),
            jax.random.key(0),
            self.stats,
            shape=dict(self.shape.as_dict(), **config),
            eval_mode="budget",
            trunk_block=int(config.get("trunk_block", 0)),
            num_episodes=self.shape.num_episodes,
            episode_length=self.shape.episode_length,
            compute_dtype=self.shape.compute_dtype,
        )
        return {
            "peak_bytes": record.peak_bytes,
            "flops": record.flops,
            "compile_seconds": record.compile_seconds,
        }

    def baseline(self, trials: int = 3) -> Dict[str, Any]:
        """Median steps/s of the DENSE budget contract at the same shape —
        the policy group's speedup denominator is dense-vs-trunk-delta at
        the same contract, not a contract A/B."""
        if self._episodes_baseline is not None:
            return self._episodes_baseline
        from ..neuroevolution.net.vecrl import run_vectorized_rollout

        def runner(key):
            return run_vectorized_rollout(
                self.env,
                self.policy,
                self.values,
                key,
                self.stats,
                eval_mode="budget",
                num_episodes=self.shape.num_episodes,
                episode_length=self.shape.episode_length,
                compute_dtype=self.shape.compute_dtype,
            )

        import jax

        jax.block_until_ready(runner(self._next_key()).scores)  # warmup
        samples, occupancies = [], []
        for _ in range(max(1, trials)):
            sps, telemetry, _ = self._timed_call(
                "budget_dense", {"contract": "budget_dense"}, runner
            )
            samples.append(sps)
            if telemetry is not None:
                occupancies.append(telemetry.occupancy)
        self._episodes_baseline = {
            "steps_per_sec": _median(samples),
            "occupancy": _median(occupancies) if occupancies else None,
            "samples": samples,
        }
        return self._episodes_baseline

    def tuned_config(self, config):
        return {
            "rank": int(config["rank"]),
            "trunk_block": int(config.get("trunk_block", 0)),
        }


class SpanHarness(_BespokeHarness):
    """Tunes the fused-span length K (``parallel.make_training_span``):
    how many generations one donated device program scans before the
    host fetches results. Each K candidate is its OWN compiled program
    (lax.scan length is a static shape), so the span knob is menu-only —
    an off-grid midpoint would buy nothing but another compile. Every
    candidate keeps a persistent (state, stats) pair rebound after each
    call — the programs donate their search state, exactly like the
    consumers — and the budget contract keeps the per-generation work
    identical across trials, so steps/sec is the only moving part. The
    baseline is the SAME generation body dispatched from the host loop
    (``make_generation_step``, same mesh), making
    ``speedup_vs_baseline`` the span_speedup of docs/sharding.md."""

    group = "span"
    program = "gspmd.training_span"
    #: the budget contract keeps every lane active; throughput selection
    default_min_occupancy: Optional[float] = None

    def __init__(
        self,
        shape: TuneShape,
        *,
        spans: Sequence[int] = (1, 2, 4, 8, 16),
        seed: int = 0,
    ):
        super().__init__(shape, seed=seed)
        self.spans = tuple(sorted({int(s) for s in spans if int(s) >= 1}))
        if not self.spans:
            raise ValueError("empty span menu; pass --spans with ints >= 1")
        from ..parallel import default_mesh

        self._mesh = default_mesh(("pop",))
        self._programs: Dict[int, Any] = {}
        self._span_state: Dict[int, Any] = {}
        self._baseline_step = None
        self._baseline_state = None
        self._seed = int(seed)

    # -- program/state builders --------------------------------------------
    def _ask_tell(self):
        from functools import partial

        from ..algorithms.functional import pgpe_ask, pgpe_tell

        return partial(pgpe_ask, popsize=self.shape.popsize), pgpe_tell

    def _fresh_state(self):
        import jax.numpy as jnp

        from ..algorithms.functional import pgpe
        from ..neuroevolution.net.runningnorm import RunningNorm

        state = pgpe(
            center_init=jnp.zeros(
                self.policy.parameter_count, dtype=jnp.float32
            ),
            center_learning_rate=0.1,
            stdev_learning_rate=0.1,
            objective_sense="max",
            stdev_init=0.1,
        )
        return state, RunningNorm(self.env.observation_size).stats

    def _rollout_kwargs(self):
        return dict(
            eval_mode="budget",
            num_episodes=self.shape.num_episodes,
            episode_length=self.shape.episode_length,
            compute_dtype=self.shape.compute_dtype,
        )

    def _program_for(self, span: int):
        span = int(span)
        if span not in self._programs:
            from ..parallel import make_training_span

            ask, tell = self._ask_tell()
            self._programs[span] = make_training_span(
                self.env,
                self.policy,
                ask=ask,
                tell=tell,
                popsize=self.shape.popsize,
                span=span,
                mesh=self._mesh,
                **self._rollout_kwargs(),
            )
            self._span_state[span] = self._fresh_state()
        return self._programs[span]

    def default_config(self):
        return {"span": self.spans[0]}

    def knob_group(self) -> KnobGroup:
        return KnobGroup(
            name=self.group,
            # menu-only: each span length is a distinct compiled program
            knobs=(KnobSpec("span", self.spans, refine=False),),
        )

    def run_once(self, config, key, *, warmup: bool = False):
        import types

        import jax

        span = int(config["span"])
        fn = self._program_for(span)

        def call(k):
            state, stats = self._span_state[span]
            new_state, scores, new_stats, steps, _ = fn(
                state, jax.random.split(k, span), stats
            )
            self._span_state[span] = (new_state, new_stats)
            return scores, steps

        scores, steps = call(key)
        if warmup:
            # donated GSPMD programs reach the steady-state layout on the
            # SECOND call — run one more untimed so no compile can land
            # inside a timed trial
            jax.block_until_ready(scores)
            scores, steps = call(self._next_key())
            jax.block_until_ready(scores)
        return types.SimpleNamespace(
            scores=scores, total_steps=steps.sum(), telemetry=None
        )

    def cost(self, config):
        """Analytic cost of the candidate's fused-span program (one AOT
        capture, outside every timed region) — the ISSUE's compile-time
        cost surface for long spans, plus the peak-HBM prune input."""
        import jax

        from .programs import ProgramLedger, abstract_like

        span = int(config["span"])
        from ..parallel import make_training_span

        ask, tell = self._ask_tell()
        fn = make_training_span(
            self.env,
            self.policy,
            ask=ask,
            tell=tell,
            popsize=self.shape.popsize,
            span=span,
            mesh=self._mesh,
            donate_state=False,  # AOT analysis only; nothing is consumed
            **self._rollout_kwargs(),
        )
        state, stats = self._fresh_state()
        led = ProgramLedger()
        record = led.capture(
            self.program,
            fn,
            abstract_like(state),
            jax.random.split(jax.random.key(0), span),
            abstract_like(stats),
            shape=dict(self.shape.as_dict(), span=span),
        )
        return {
            "peak_bytes": record.peak_bytes,
            "flops": record.flops,
            "compile_seconds": record.compile_seconds,
        }

    def baseline(self, trials: int = 3) -> Dict[str, Any]:
        """Median steps/s of the host loop: the SAME generation body
        (``make_generation_step``, same mesh, same contract) dispatched
        ``max(spans)`` times per sample from the host — the denominator
        that makes ``speedup_vs_baseline`` the span A/B headline."""
        if self._episodes_baseline is not None:
            return self._episodes_baseline
        import jax

        from ..parallel import make_generation_step

        if self._baseline_step is None:
            ask, tell = self._ask_tell()
            self._baseline_step = make_generation_step(
                self.env,
                self.policy,
                ask=ask,
                tell=tell,
                popsize=self.shape.popsize,
                mesh=self._mesh,
                **self._rollout_kwargs(),
            )
            self._baseline_state = self._fresh_state()
        gens = max(self.spans)

        def runner(key):
            import types

            state, stats = self._baseline_state
            steps_total = 0
            scores = None
            for g in range(gens):
                state, scores, stats, steps, _ = self._baseline_step(
                    state, jax.random.fold_in(key, g), stats
                )
                steps_total += int(steps)
            self._baseline_state = (state, stats)
            return types.SimpleNamespace(
                scores=scores, total_steps=steps_total, telemetry=None
            )

        # two untimed warmups: fresh layout, then steady-state donated layout
        jax.block_until_ready(runner(self._next_key()).scores)
        jax.block_until_ready(runner(self._next_key()).scores)
        samples = []
        for _ in range(max(1, trials)):
            sps, _, _ = self._timed_call(
                "span_hostloop", {"contract": "hostloop"}, runner
            )
            samples.append(sps)
        self._episodes_baseline = {
            "steps_per_sec": _median(samples),
            "occupancy": None,
            "samples": samples,
        }
        return self._episodes_baseline

    def tuned_config(self, config):
        return {"span": int(config["span"])}


class HostPipelineHarness:
    """Tunes the HOST-path knobs: the pipelined scheduler's lane-block
    count and (for MuJoCo backends) the physics thread-pool width. These
    are machine properties — "2 blocks when a second core exists" is the
    heuristic being replaced by a measured fact — so the cache entry is
    machine-scoped (shape ``{}``), and every `GymNE`/host-pipeline run on
    this machine inherits it."""

    group = "host_pipeline"
    program = "host_pipeline.rollout"
    #: host-path occupancy has no device-starvation meaning comparable to
    #: the refill contract's; select on throughput (no floor by default)
    default_min_occupancy: Optional[float] = None

    def __init__(
        self,
        env_id: Optional[str] = None,
        *,
        popsize: int = 64,
        num_envs: int = 16,
        episode_length: int = 200,
        hidden: Tuple[int, ...] = (64, 64),
        seed: int = 0,
    ):
        import gymnasium as gym
        import numpy as np

        from ..neuroevolution.net import FlatParamsPolicy, tanh_mlp

        if env_id is None:
            try:
                from ..envs.mujoco.mjvecenv import MjVecEnv  # noqa: F401

                env_id = "Hopper-v5"
            except ImportError:
                env_id = "CartPole-v1"
        self.env_id = env_id
        self.popsize = int(popsize)
        self.num_envs = int(num_envs)
        self.episode_length = int(episode_length)
        probe = gym.make(env_id)
        obs_dim = int(np.prod(probe.observation_space.shape))
        act_space = probe.action_space
        act_dim = (
            int(act_space.n)
            if hasattr(act_space, "n")
            else int(np.prod(act_space.shape))
        )
        probe.close()
        self.policy = FlatParamsPolicy(tanh_mlp(obs_dim, act_dim, hidden))
        rng = np.random.default_rng(seed)
        import jax.numpy as jnp

        self.params = jnp.asarray(
            rng.normal(size=(self.popsize, self.policy.parameter_count)),
            jnp.float32,
        )
        self._mujoco = self._mujoco_backend()
        self._warmed_splits: set = set()
        self._sync_baseline: Optional[Dict[str, Any]] = None

    def _mujoco_backend(self) -> bool:
        try:
            from ..envs.mujoco.mjvecenv import MjVecEnv

            import gymnasium as gym

            probe = MjVecEnv(lambda: gym.make(self.env_id), 1)
            probe.close()
            return True
        except Exception:  # graftlint: allow(swallow): backend availability probe; False IS the answer
            return False

    def default_config(self) -> Optional[Dict[str, Any]]:
        return None  # no analytic cost model on the host path; grid[0] anchors

    def knob_group(self) -> KnobGroup:
        import os

        blocks = tuple(b for b in (1, 2, 4) if b <= self.num_envs)
        knobs = [KnobSpec("num_blocks", blocks, refine=False)]
        if self._mujoco:
            cores = int(os.cpu_count() or 1)
            nthreads = tuple(sorted({1, 2, cores} & set(range(1, self.num_envs + 1))))
            knobs.append(KnobSpec("mj_nthread", nthreads, refine=False))
        return KnobGroup(name=self.group, knobs=tuple(knobs))

    def cost(self, config):
        return None  # host-orchestrated: no single XLA program to analyze

    def _fresh_vec(self, config):
        import gymnasium as gym

        if self._mujoco:
            from ..envs.mujoco.mjvecenv import MjVecEnv

            vec = MjVecEnv(
                lambda: gym.make(self.env_id),
                self.num_envs,
                nthread=config.get("mj_nthread"),
            )
        else:
            from ..neuroevolution.net.hostvecenv import SyncVectorEnv

            vec = SyncVectorEnv(lambda: gym.make(self.env_id), self.num_envs)
        vec.seed(range(1000, 1000 + self.num_envs))
        return vec

    def _run(self, config, *, episode_length: Optional[int] = None, mode="pipelined"):
        import numpy as np

        from ..neuroevolution.net.hostvecenv import run_host_pipelined_rollout

        vec = self._fresh_vec(config)
        try:
            t0 = time.perf_counter()
            result = run_host_pipelined_rollout(
                vec,
                self.policy,
                self.params,
                num_episodes=1,
                episode_length=(
                    self.episode_length if episode_length is None else episode_length
                ),
                mode=mode,
                num_blocks=config.get("num_blocks"),
                # the tuner must never measure through its own previous
                # output: the sync baseline (and any config with blocks
                # unset) gets the PRISTINE heuristic, not a cached entry
                use_tuned_cache=False,
                rng=np.random.default_rng(0),
            )
            elapsed = time.perf_counter() - t0
        finally:
            vec.close()
        return result["interactions"] / elapsed if elapsed else 0.0, result

    def _warm(self, config):
        """The gathered device forward is jitted per BLOCK WIDTH, so every
        distinct block split must compile OUTSIDE the timed region — a
        one-warmup-for-all approach would hand later candidates a mid-trial
        compile (and with one trial, a compile-contaminated median)."""
        split = (config.get("num_blocks"), config.get("mj_nthread"))
        if split not in self._warmed_splits:
            self._warmed_splits.add(split)
            self._run(config, episode_length=3)

    def measure(self, configs, trials, round_index):
        from ..analysis import track_compiles

        for config in configs:
            self._warm(config)
        out = [
            {"samples": [], "occupancies": [], "steady_compiles": 0}
            for _ in configs
        ]
        for _ in range(trials):
            for i, config in enumerate(configs):
                with tracer.span(
                    "autotune.trial", "autotune", group=self.group, **config
                ):
                    with track_compiles() as compile_log:
                        sps, result = self._run(config)
                out[i]["samples"].append(sps)
                out[i]["occupancies"].append(result["occupancy"])
                out[i]["steady_compiles"] += compile_log.count
        return out

    def baseline(self, trials: int = 3) -> Dict[str, Any]:
        """The sync-mode scheduler (same event order, no worker thread)
        at default blocks — the pipelined/sync A/B denominator."""
        if self._sync_baseline is not None:
            return self._sync_baseline
        samples = []
        self._warm({})
        for _ in range(max(1, trials)):
            with tracer.span("autotune.trial", "autotune", group="host_sync"):
                sps, _ = self._run({}, mode="sync")
            samples.append(sps)
        self._sync_baseline = {
            "steps_per_sec": _median(samples),
            "occupancy": None,
            "samples": samples,
        }
        return self._sync_baseline

    def tuned_config(self, config):
        out = {"num_blocks": int(config["num_blocks"])}
        if "mj_nthread" in config:
            out["mj_nthread"] = int(config["mj_nthread"])
        return out


# ---------------------------------------------------------------------------
# the tuning driver: search a harness, fill the ledger, persist the winner
# ---------------------------------------------------------------------------


def tune_group(
    harness,
    *,
    trials: int = 3,
    max_rounds: int = 2,
    survivor_frac: float = 0.5,
    min_occupancy="auto",
    hbm_budget_bytes: Optional[float] = None,
    hbm_budget_ratio: Optional[float] = 8.0,
    flops_bound: Optional[float] = None,
    refine: bool = True,
    ledger_out: Optional[TimingLedger] = None,
    cache_path=None,
    write_cache: bool = True,
) -> SearchOutcome:
    """Run one knob group end to end: derive the HBM budget from the
    DEFAULT candidate's analyzed peak (``hbm_budget_ratio`` — a
    guardrail against pathological grid corners, generous enough to keep
    every sane rung), search, land every candidate in the measured-timing
    ledger, and persist the winner to the tuned-config cache.

    ``min_occupancy="auto"`` takes the HARNESS's per-group floor
    (``default_min_occupancy``): 0.9 for refill, none for compact —
    whose contract structurally runs ~0.5 — and the host pipeline.
    Secondary-objective selection (``winner_tolerance`` /
    ``winner_prefer`` — the policy group's highest-rank-within-band
    rule) also comes from the harness."""
    if min_occupancy == "auto":
        min_occupancy = getattr(harness, "default_min_occupancy", None)
    tolerance = getattr(harness, "winner_tolerance", None)
    prefer = getattr(harness, "winner_prefer", None)
    led = ledger_out if ledger_out is not None else timings
    group = harness.knob_group()
    machine = machine_fingerprint()
    cost_cache: Dict[Tuple, Optional[Dict]] = {}

    def cost_fn(config):
        key = tuple(sorted(config.items()))
        if key not in cost_cache:
            try:
                cost_cache[key] = harness.cost(config)
            except Exception:  # graftlint: allow(swallow): cost analysis is advisory; None disables pruning for this config
                cost_cache[key] = None  # no analysis never prunes
        return cost_cache[key]

    budget = hbm_budget_bytes
    if budget is None and hbm_budget_ratio is not None:
        anchor = harness.default_config() or candidate_grid(group)[0]
        reference = cost_fn(anchor)
        if reference is not None and reference.get("peak_bytes") is not None:
            budget = float(reference["peak_bytes"]) * float(hbm_budget_ratio)

    outcome = autotune_search(
        group,
        harness.measure,
        cost_fn=cost_fn,
        hbm_budget_bytes=budget,
        flops_bound=flops_bound,
        trials_per_round=trials,
        survivor_frac=survivor_frac,
        max_rounds=max_rounds,
        min_occupancy=min_occupancy,
        tolerance=tolerance,
        prefer=prefer,
        refine=refine,
    )

    shape = harness.shape.as_dict() if hasattr(harness, "shape") else {}
    for stats in outcome.results:
        led.add(
            TimingRecord(
                program=harness.program,
                shape=shape,
                machine=machine,
                config=dict(stats.config),
                samples=tuple(stats.samples),
                occupancy=stats.occupancy,
                refill_events=stats.refill_events,
                queue_wait=stats.queue_wait,
                compile_seconds=(
                    None if stats.cost is None else stats.cost.get("compile_seconds")
                ),
                steady_compiles=stats.steady_compiles,
            )
        )
    for config, reason in outcome.pruned:
        led.add(
            TimingRecord(
                program=harness.program,
                shape=shape,
                machine=machine,
                config=dict(config),
                pruned=reason,
            )
        )

    # NEVER persist an untrustworthy winner: a steady-state compile inside
    # a timed trial means the medians are contaminated (the CLI additionally
    # exits nonzero on this), and a winner that only exists because NO
    # candidate met the occupancy floor (select_winner's unconstrained
    # fallback) is exactly the lucky-run wide rung the floor exists to
    # block — either one landing in the checked-in cache would be silently
    # applied by every consumer
    floor_met = outcome.winner is not None and (
        min_occupancy is None
        or (
            outcome.winner.occupancy is not None
            and outcome.winner.occupancy >= min_occupancy
        )
    )
    if (
        outcome.winner is not None
        and outcome.winner.steady_compiles == 0
        and floor_met
        and write_cache
    ):
        from .timings import save_tuned_entry

        baseline = harness.baseline(trials)
        speedup = None
        if baseline["steps_per_sec"]:
            speedup = outcome.winner.steps_per_sec / baseline["steps_per_sec"]
        cache_shape = _cache_shape(harness)
        entry = TunedEntry(
            group=harness.group,
            shape=cache_shape,
            machine=machine,
            config=harness.tuned_config(outcome.winner.config),
            evidence={
                "steps_per_sec": round(outcome.winner.steps_per_sec, 1),
                "occupancy": (
                    None
                    if outcome.winner.occupancy is None
                    else round(outcome.winner.occupancy, 4)
                ),
                "baseline_steps_per_sec": round(baseline["steps_per_sec"], 1),
                "speedup_vs_baseline": (
                    None if speedup is None else round(speedup, 3)
                ),
                "trials": len(outcome.winner.samples),
                "steady_compiles": outcome.winner.steady_compiles,
                "episode_length": getattr(
                    getattr(harness, "shape", None), "episode_length", None
                ),
                "tuned_at": time.strftime("%Y-%m-%d"),
            },
        )
        save_tuned_entry(entry, cache_path)
        outcome.cache_written = True
    return outcome


def _cache_shape(harness) -> Dict[str, Any]:
    """The cache key's shape dict: (env, popsize, policy parameter count,
    compute dtype) for the device-program groups — params/dtype because a
    width tuned for a 64x64-f32 policy says nothing about a 256x256-bf16
    one (different per-step FLOPs/HBM balance) — and machine-scoped
    (empty) for the host-pipeline group, whose knobs are host properties."""
    from .timings import canonical_env_label

    if isinstance(harness, HostPipelineHarness):
        return {}
    return {
        # canonicalized exactly like every consumer's lookup label — an
        # entry written under "Hopper-v5" would never match "hopper"
        "env": canonical_env_label(harness.shape.env_name),
        "popsize": harness.shape.popsize,
        # the FULL workload identity: episode length/count change the
        # work-list size and refill frequency, and params/dtype change the
        # per-step FLOPs/HBM balance — a schedule measured at one must not
        # be applied to another under a "cache" label
        "episode_length": harness.shape.episode_length,
        "num_episodes": harness.shape.num_episodes,
        "params": harness.policy.parameter_count,
        "dtype": dtype_label(harness.shape.compute_dtype),
        # the autotuner measures on the unsharded single-device program;
        # sharded consumers look up under their own mesh label and never
        # inherit these entries (parallel.mesh.mesh_label)
        "mesh": "none",
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _shape_from_args(args, use_cpu: bool) -> TuneShape:
    """The tuning shape from the CLI's own arguments. A cache hit requires
    exact (env, popsize, params, dtype) equality with the consumer's shape,
    so tune at the shape that will look the entry up."""
    import jax.numpy as jnp

    popsize = args.popsize
    if popsize is None:
        popsize = 1024 if use_cpu else 10_000
    episode_length = args.episode_length
    if episode_length is None:
        episode_length = 100 if use_cpu else 200
    hidden = tuple(int(h) for h in (args.hidden or "64,64").split(",") if h)
    return TuneShape(
        env_name=args.env or "humanoid",
        popsize=popsize,
        episode_length=episode_length,
        hidden=hidden,
        compute_dtype=jnp.bfloat16 if args.bf16 else None,
    )


def _emit(payload: dict) -> None:
    import json as _json

    print(_json.dumps(payload), flush=True)


def main(argv=None) -> int:
    import argparse
    import sys

    parser = argparse.ArgumentParser(
        prog="python -m evotorch_tpu.observability.autotune",
        description="Occupancy-driven autotuner: search the eval-schedule "
        "knobs at the given shape, record measured timings, persist "
        "winners to the tuned-config cache (docs/observability.md).",
    )
    parser.add_argument(
        "--group",
        default="refill",
        help="comma list of knob groups: refill, compact, host_pipeline, "
        "policy, span",
    )
    parser.add_argument("--cpu", action="store_true",
                        help="force the 8-virtual-device CPU backend")
    parser.add_argument("--env", default=None, help="env name (default humanoid)")
    parser.add_argument("--popsize", type=int, default=None)
    parser.add_argument("--episode-length", type=int, default=None)
    parser.add_argument("--hidden", default=None, help="comma list, e.g. 64,64")
    parser.add_argument("--bf16", action="store_true",
                        help="tune at compute_dtype=bfloat16 (default float32)")
    parser.add_argument("--trials", type=int, default=3,
                        help="timed trials per candidate per round (median "
                        "of >=3 — the CLAUDE.md variance rule)")
    parser.add_argument("--max-rounds", type=int, default=2,
                        help="successive-halving rounds")
    parser.add_argument("--min-occupancy", type=float, default=None,
                        help="occupancy floor on the winner (default: each "
                        "group's own floor — 0.9 for refill; none for "
                        "compact, whose contract structurally runs ~0.5, "
                        "and host_pipeline)")
    parser.add_argument("--widths", default=None,
                        help="refill width grid override (comma list)")
    parser.add_argument("--periods", default="1",
                        help="refill period grid (comma list)")
    parser.add_argument("--chunks", default="10,25,50",
                        help="compact chunk-size grid (comma list)")
    parser.add_argument("--min-widths", default="128,256,512",
                        help="compact width-menu-floor grid (comma list)")
    parser.add_argument("--ranks", default="4,16,64",
                        help="policy-group trunk-delta rank grid (comma list)")
    parser.add_argument("--trunk-blocks", default="0",
                        help="policy-group lane-block grid (comma list; 0 = "
                        "unblocked, others must divide the popsize)")
    parser.add_argument("--spans", default="1,2,4,8,16",
                        help="span-group fused-span length grid (comma list; "
                        "each K is its own compiled program)")
    parser.add_argument("--hbm-budget", type=float, default=None,
                        help="absolute peak-HBM prune budget in bytes")
    parser.add_argument("--hbm-budget-ratio", type=float, default=8.0,
                        help="prune budget as a multiple of the default "
                        "candidate's analyzed peak (None-able via 0)")
    parser.add_argument("--flops-bound", type=float, default=None,
                        help="absolute cost-model FLOPs prune bound")
    parser.add_argument("--no-refine", action="store_true",
                        help="skip the neighborhood-refinement round")
    parser.add_argument("--no-write-cache", action="store_true",
                        help="search + ledger only; don't touch "
                        "tuned_configs.json")
    parser.add_argument("--cache", default=None,
                        help="alternate tuned_configs.json path")
    parser.add_argument("--timings-out", default=None,
                        help="write the measured-timing ledger JSON here")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    use_cpu = setup_backend(args.cpu)
    enable_persistent_cache()
    groups = [g.strip() for g in args.group.split(",") if g.strip()]
    unknown = set(groups) - {
        "refill", "compact", "host_pipeline", "policy", "span"
    }
    if unknown:
        parser.error(f"unknown group(s): {sorted(unknown)}")

    shape = _shape_from_args(args, use_cpu)
    ratio = args.hbm_budget_ratio if args.hbm_budget_ratio else None
    session = TimingLedger()
    rc = 0
    for group_name in groups:
        if group_name == "refill":
            widths = (
                [int(w) for w in args.widths.split(",") if w]
                if args.widths
                else None
            )
            periods = [int(p) for p in args.periods.split(",") if p]
            harness = RefillHarness(
                shape, widths=widths, periods=periods, seed=args.seed
            )
        elif group_name == "compact":
            harness = CompactHarness(
                shape,
                chunks=[int(c) for c in args.chunks.split(",") if c],
                min_widths=[int(w) for w in args.min_widths.split(",") if w],
                seed=args.seed,
            )
        elif group_name == "policy":
            harness = PolicyHarness(
                shape,
                ranks=[int(r) for r in args.ranks.split(",") if r],
                trunk_blocks=[int(b) for b in args.trunk_blocks.split(",") if b != ""],
                seed=args.seed,
            )
        elif group_name == "span":
            harness = SpanHarness(
                shape,
                spans=[int(s) for s in args.spans.split(",") if s],
                seed=args.seed,
            )
        else:
            harness = HostPipelineHarness(seed=args.seed)
        print(
            f"[autotune] group={group_name} shape={_cache_shape(harness)} "
            f"machine={machine_fingerprint()}",
            file=sys.stderr,
        )
        outcome = tune_group(
            harness,
            trials=args.trials,
            max_rounds=args.max_rounds,
            min_occupancy=(
                args.min_occupancy if args.min_occupancy is not None else "auto"
            ),
            hbm_budget_bytes=args.hbm_budget,
            hbm_budget_ratio=ratio,
            flops_bound=args.flops_bound,
            refine=not args.no_refine,
            ledger_out=session,
            cache_path=args.cache,
            write_cache=not args.no_write_cache,
        )
        for stats in outcome.results:
            _emit(
                {
                    "metric": "autotune_steps_per_sec",
                    "group": group_name,
                    "config": stats.config,
                    "steps_per_sec": round(stats.steps_per_sec, 1),
                    "occupancy": (
                        None
                        if stats.occupancy is None
                        else round(stats.occupancy, 4)
                    ),
                    "trials": len(stats.samples),
                    "steady_compiles": stats.steady_compiles,
                }
            )
        for config, reason in outcome.pruned:
            _emit(
                {
                    "metric": "autotune_pruned",
                    "group": group_name,
                    "config": config,
                    "reason": reason,
                }
            )
        if outcome.winner is None:
            _emit({"metric": "autotune_winner", "group": group_name,
                   "error": "no candidate produced a timing"})
            rc = 1
            continue
        baseline = harness.baseline(args.trials)
        speedup = (
            outcome.winner.steps_per_sec / baseline["steps_per_sec"]
            if baseline["steps_per_sec"]
            else None
        )
        _emit(
            {
                "metric": "autotune_winner",
                "group": group_name,
                "config": harness.tuned_config(outcome.winner.config),
                "steps_per_sec": round(outcome.winner.steps_per_sec, 1),
                "occupancy": (
                    None
                    if outcome.winner.occupancy is None
                    else round(outcome.winner.occupancy, 4)
                ),
                "baseline_steps_per_sec": round(baseline["steps_per_sec"], 1),
                "speedup_vs_baseline": (
                    None if speedup is None else round(speedup, 3)
                ),
                "steady_compiles": outcome.winner.steady_compiles,
                "cache_written": outcome.cache_written,
                "backend": device_record(),
            }
        )
        # steady-state compiles inside a timed trial invalidate the run's
        # claim to compile-free measurement — surfaced as a nonzero exit
        if outcome.winner.steady_compiles:
            rc = 1
    if args.timings_out:
        session.save(args.timings_out)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
