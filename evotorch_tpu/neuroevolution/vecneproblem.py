"""Fully-vectorized RL neuroevolution — the throughput path.

Parity: reference ``neuroevolution/vecgymne.py:95-1073`` (``VecGymNE``): one
sub-environment per solution, batched policies, masked episode accounting,
GPU-aware observation normalization, env-registry strings, alive bonus,
reward adjustment, ``to_policy``/``save_solution``.

TPU-first: the environment is a pure-JAX env (``evotorch_tpu.envs``; Brax via
the gated adapter), and the whole evaluate is ONE jitted program
(``net/vecrl.py:run_vectorized_rollout``) — no dlpack ping-pong, no Python
stepping. With ``use_sharded_evaluation()``-style meshes, the population axis
shards across devices under GSPMD (the rollout being pure makes that a
one-liner; see ``evaluate_sharded``).
"""

from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import SolutionBatch
from ..envs import Env, make_env
from ..observability.timings import canonical_env_label, resolve_knobs
from ..tools.lowrank import LowRankParamsBatch, is_factored
from ..parallel.mesh import default_mesh
from .neproblem import NEProblem
from .net.layers import Module
from .net.rl import ActClipLayer
from .net.runningnorm import RunningNorm
from .net.vecrl import (
    _params_popsize,
    run_vectorized_rollout,
    run_vectorized_rollout_compacting,
    run_vectorized_rollout_compacting_sharded,
)

__all__ = ["VecNE", "VecGymNE"]


class VecNE(NEProblem):
    """Vectorized neuroevolution over a pure-JAX env."""

    def __init__(
        self,
        env: Union[str, Env],
        network: Union[str, Module, Callable],
        *,
        env_config: Optional[dict] = None,
        max_num_envs: Optional[int] = None,
        network_args: Optional[dict] = None,
        observation_normalization: bool = False,
        decrease_rewards_by: Optional[float] = None,
        alive_bonus_schedule: Optional[tuple] = None,
        action_noise_stdev: Optional[float] = None,
        num_episodes: int = 1,
        episode_length: Optional[int] = None,
        eval_mode: str = "episodes",
        obs_norm_sync: str = "cohort",
        compact_config: Optional[dict] = None,
        refill_config: Optional[dict] = None,
        solution_groups=None,
        slo=None,
        health_telemetry: bool = True,
        nonfinite_quarantine: bool = True,
        nonfinite_penalty: Optional[float] = None,
        eval_backend=None,
        compute_dtype=None,
        initial_bounds=(-0.00001, 0.00001),
        seed: Optional[int] = None,
        num_actors=None,
        **kwargs,
    ):
        if isinstance(env, str):
            self._env: Env = make_env(env, **(env_config or {}))
        else:
            self._env = env
        self._observation_normalization = bool(observation_normalization)
        self._decrease_rewards_by = decrease_rewards_by
        self._alive_bonus_schedule = (
            tuple(alive_bonus_schedule) if alive_bonus_schedule is not None else None
        )
        self._action_noise_stdev = action_noise_stdev
        self._num_episodes = int(num_episodes)
        self._episode_length = None if episode_length is None else int(episode_length)
        # "episodes" = reference VecGymNE semantics (each lane runs
        # num_episodes episodes then idles); "episodes_compact" = the same
        # contract evaluated by the lane-compacting runner (finished lanes are
        # repacked out of the working set between chunks — see
        # net/vecrl.py:run_vectorized_rollout_compacting); "budget" = fixed
        # interaction budget with auto-reset — the throughput-optimal contract
        # where every computed step is a counted interaction.
        #
        # Reproducibility guarantee (user-facing): randomness is a PER-LANE
        # property (each lane carries its own PRNG chain seeded by its
        # original lane index — vecrl.py:_rollout_init), so in every config —
        # multi-episode, action noise — "episodes_compact" scores equal
        # "episodes" scores (bit-identical; with observation_normalization
        # the masked stat reductions may differ in float summation order
        # only), and WITHOUT observation normalization sharded evaluation is
        # bit-identical to unsharded. Over a mesh (GSPMD) the program is the
        # unsharded one, so the obs-norm cohort is always the mesh-GLOBAL
        # population and obs_norm_sync is not read. Only "episodes_compact"
        # over a mesh reads it (its sharded runner is per-shard code): under
        # the default obs_norm_sync="cohort" each lane is normalized by its
        # shard's running statistics (deltas psum-merge only at the end, like
        # the reference's per-actor stats); obs_norm_sync="step" psum-merges
        # the stat deltas EVERY control step, so all shards normalize by the
        # mesh-global cohort and the divergence collapses to float summation
        # order, at the cost of one small collective per step.
        # "episodes_refill" = the same contract again, evaluated by the
        # work-conserving lane-refill scheduler (a fixed lane width kept
        # saturated from an on-device pending-work queue — continuous
        # batching; see net/vecrl.py:_run_refill). One jitted program, so it
        # is sharded like any other (GSPMD). At num_episodes=1
        # WITHOUT observation normalization its scores are bit-identical to
        # "episodes" (same per-lane seeding); with obs-norm on, the refill
        # schedule changes the running statistics each lane sees (late-
        # refilled lanes normalize by more history), so scores differ
        # semantically — schedule-dependent cohort statistics, like the
        # sharding caveat above.
        if eval_mode not in ("episodes", "episodes_compact", "episodes_refill", "budget"):
            raise ValueError(
                "eval_mode must be 'episodes', 'episodes_compact',"
                f" 'episodes_refill' or 'budget', got {eval_mode!r}"
            )
        self._eval_mode = str(eval_mode)
        # tuning knobs for the refill scheduler (width, period); width is the
        # GLOBAL lane count and divides by the shard count on the mesh path,
        # like compact_config's widths
        if refill_config is not None:
            allowed = {"width", "period"}
            unknown = set(refill_config) - allowed
            if unknown:
                raise ValueError(f"Unknown refill_config keys: {sorted(unknown)}")
        self._refill_config = dict(refill_config or {})
        # per-group telemetry (ISSUE 15): one small int id per solution maps
        # it to an accounting group (tenant, island, ...); the rollout
        # engines segment_sum env-steps/episodes/capacity/refill/queue-wait
        # per group INSIDE the same jitted programs, so multi-tenant
        # occupancy/fairness accounting costs no extra host syncs
        if solution_groups is not None:
            g = np.asarray(solution_groups, dtype=np.int32)
            if g.ndim != 1 or g.size == 0:
                raise ValueError(
                    "solution_groups must be a non-empty 1-D array of group ids"
                )
            if int(g.min()) < 0:
                raise ValueError("solution_groups ids must be >= 0")
            self._solution_groups = g
            self._num_groups = int(g.max()) + 1
        else:
            self._solution_groups = None
            self._num_groups = 1
        # non-finite quarantine (ISSUE 17, docs/resilience.md): inside the
        # compiled eval programs, solutions whose mean score came back
        # non-finite (diverged physics, overflowed bf16 reward sums) have
        # their credit replaced — by the worst finite score in the batch
        # (penalty=None) or a fixed penalty — BEFORE anything downstream
        # (centered ranking orders NaN "best") can be poisoned. Identity on
        # all-finite scores, so it defaults ON; quarantined counts surface
        # as eval_nonfinite / eval_nonfinite_share status keys and in the
        # per-group telemetry matrix (max_nonfinite_share SLO rule).
        self._nonfinite_quarantine = bool(nonfinite_quarantine)
        self._nonfinite_penalty = (
            None if nonfinite_penalty is None else float(nonfinite_penalty)
        )
        # search-health plane (docs/observability.md "Search health"): the
        # compiled eval programs append per-group float32 score statistics
        # (count/sum/sumsq/min/max) to the telemetry wire — schema v4.
        # health_telemetry=False compiles the v3 (health-free) programs
        self._health_telemetry = bool(health_telemetry)
        # SLO watchdog (observability/slo.py): declarative rules evaluated
        # against each generation's decoded telemetry; verdicts surface as
        # slo_ok / slo_violations status keys (logger columns for free)
        if slo is not None:
            from ..observability.slo import SLOWatchdog

            self._slo = slo if isinstance(slo, SLOWatchdog) else SLOWatchdog(slo)
        else:
            self._slo = None
        # tuned-config cache wiring (observability/timings.py): when the
        # refill / compaction knobs are NOT passed explicitly, eval setup
        # consults the checked-in tuned_configs.json for this
        # (env, popsize, episode length/count, params, dtype, machine) key — the autotuner's
        # measured winners — and falls back to the engines' built-in
        # defaults on a miss. Explicit knobs always win; the branch taken
        # is published as the `tuned_config_source` status key
        # (override / cache / fallback). An env_config-modified env is NOT
        # the env its cache label names (different dynamics, different
        # episode-length distribution), so the cache is skipped for it —
        # a pre-built Env instance with custom ctor args has the same
        # caveat, which the label cannot detect.
        self._env_label = canonical_env_label(env)
        self._tuned_cacheable = not (isinstance(env, str) and env_config)
        self._tuned_resolution: dict = {}
        self._tuned_config_source: Optional[str] = None
        # layout the last dense population sent to a mesh arrived in, where it
        # was committed to one (lower_evaluation lowers for the same layout)
        self._population_layout = None
        if obs_norm_sync not in ("cohort", "step"):
            raise ValueError(
                f"obs_norm_sync must be 'cohort' or 'step', got {obs_norm_sync!r}"
            )
        self._obs_norm_sync = str(obs_norm_sync)
        # tuning knobs for the lane-compacting runner (chunk_size, min_width,
        # allowed_widths, prewarm); meaningful only with
        # eval_mode="episodes_compact". Widths are GLOBAL population widths:
        # on the sharded path they are divided by the shard count before
        # reaching the (per-shard) runner, so the same config means the same
        # thing whether or not a batch happens to take the mesh path.
        if compact_config is not None:
            allowed = {"chunk_size", "min_width", "allowed_widths", "prewarm"}
            unknown = set(compact_config) - allowed
            if unknown:
                raise ValueError(f"Unknown compact_config keys: {sorted(unknown)}")
        self._compact_config = dict(compact_config or {})
        # prewarm compiles the whole width-descent chain; re-armed per
        # population size so a small warm-up evaluation cannot consume the
        # flag that a later full-population evaluation needed
        self._compact_prewarm = bool(self._compact_config.pop("prewarm", False))
        self._compact_prewarmed_sizes: set = set()
        self._max_num_envs = None if max_num_envs is None else int(max_num_envs)
        # bfloat16 (etc.) policy compute for the MXU fast path
        self._compute_dtype = compute_dtype
        # shared evaluation service (docs/serving.md): with an eval_backend —
        # a serving.RemoteEvalBackend, or a serving.EvalServer to auto-admit
        # into — every rollout dispatch routes through the server's ONE
        # resident multi-tenant program instead of compiling this problem's
        # own; searchers and every consumer downstream of RolloutResult are
        # unaffected. The backend path owns the device program, so it is
        # mutually exclusive with the problem-local mesh request
        # (num_actors) and with solution_groups (the server's group axis IS
        # the tenant axis).
        if eval_backend is not None:
            from ..serving import EvalServer, RemoteEvalBackend

            if isinstance(eval_backend, EvalServer):
                eval_backend = RemoteEvalBackend(eval_backend)
            if not isinstance(eval_backend, RemoteEvalBackend):
                raise TypeError(
                    "eval_backend must be a serving.RemoteEvalBackend or"
                    f" serving.EvalServer, got {type(eval_backend).__name__}"
                )
            if self._solution_groups is not None:
                raise ValueError(
                    "solution_groups cannot combine with eval_backend: the"
                    " server's group axis is the tenant axis"
                )
        self._eval_backend = eval_backend

        self._obs_norm = RunningNorm(self._env.observation_size)
        self._interaction_count = 0
        self._episode_count = 0
        # zero-sync eval telemetry (observability.devicemetrics): the packed
        # device vector of the CURRENT evaluation is only enqueued here; the
        # PREVIOUS one — whose program has retired — is decoded lazily for
        # the status dict (the same lag-by-one device-scalar discipline as
        # basis_capture: the decode is a ~24-byte transfer, never a stall)
        self._pending_telemetry = None
        self._last_policy_report = None
        self._last_telemetry = None
        self._last_group_telemetry = None

        super().__init__(
            "max",
            network,
            network_args=network_args,
            initial_bounds=initial_bounds,
            seed=seed,
            num_actors=num_actors,
            **kwargs,
        )
        self.after_eval_hook.append(self._report_counters)

    # ---------------------------------------------------------------- wiring
    def _network_constants(self) -> dict:
        env = self._env
        return {
            "obs_length": env.observation_size,
            "act_length": env.action_size,
            "obs_shape": tuple(env.observation_space.shape),
            "obs_space": env.observation_space,
            "act_space": env.action_space,
        }

    @property
    def env(self) -> Env:
        return self._env

    @property
    def observation_normalization(self) -> bool:
        return self._observation_normalization

    @property
    def obs_norm(self) -> RunningNorm:
        return self._obs_norm

    @property
    def eval_backend(self):
        """The attached RemoteEvalBackend (None when evaluating locally)."""
        return self._eval_backend

    @property
    def last_group_telemetry(self):
        """The previous generation's decoded per-group telemetry
        (:class:`~evotorch_tpu.observability.GroupTelemetry`; lag-by-one,
        None until telemetry has flowed) — what MetricsHub consumers feed
        to ``emit(..., telemetry=...)``."""
        return self._last_group_telemetry

    def _take_prewarm(self, popsize: int) -> bool:
        """Prewarm once per population size (not once ever): a small warm-up
        evaluation must not consume the prewarm a full-population run needs."""
        if not self._compact_prewarm or popsize in self._compact_prewarmed_sizes:
            return False
        self._compact_prewarmed_sizes.add(popsize)
        return True

    def _tuned_knobs(
        self, group: str, explicit: dict, popsize: int, mesh_label: str = "none"
    ) -> dict:
        """One knob group resolved at eval-setup time with the shared
        precedence rule (``observability.timings.resolve_knobs``):
        explicit config > tuned-config cache hit for this
        (env, popsize, episode length/count, params, dtype, mesh label,
        machine) > the engine's built-in default. Memoized per
        (group, popsize, mesh); the provenance of the LAST resolution is
        what ``tuned_config_source`` reports (shapes are identical
        generation to generation, so it is stable in steady state)."""
        from ..observability.timings import dtype_label

        memo_key = (group, popsize, mesh_label)
        if memo_key not in self._tuned_resolution:
            shape = {
                "env": self._env_label,
                "popsize": popsize,
                # the FULL workload identity is the key: episode
                # length/count set the work-list size and refill
                # frequency; the policy's parameter count + compute dtype
                # set the per-step FLOPs/HBM balance; the mesh label pins
                # the device layout — a schedule tuned for one is not
                # evidence for another
                "episode_length": self._episode_length,
                "num_episodes": self._num_episodes,
                "params": self._policy.parameter_count,
                "dtype": dtype_label(self._compute_dtype),
                "mesh": mesh_label,
            }
            self._tuned_resolution[memo_key] = resolve_knobs(
                explicit, group, shape, use_cache=self._tuned_cacheable
            )
        config, source = self._tuned_resolution[memo_key]
        self._tuned_config_source = source
        return config

    def _compact_kwargs(self, popsize: int) -> dict:
        """The lane-compacting runner's kwargs: explicit compact_config,
        else the tuned cache's (chunk_size, min_width) for this shape."""
        return dict(self._tuned_knobs("compact", self._compact_config, popsize))

    def _sharded_compact_config(
        self, n_shards: int, popsize: int, mesh_label: str = "none"
    ) -> dict:
        """The per-shard form of the (global-width) compact config: widths
        divide by the shard count; chunk_size passes through."""
        cfg = dict(
            self._tuned_knobs("compact", self._compact_config, popsize, mesh_label)
        )
        if cfg.get("min_width") is not None:
            cfg["min_width"] = max(1, int(cfg["min_width"]) // n_shards)
        if cfg.get("allowed_widths") is not None:
            per_shard = sorted({int(w) // n_shards for w in cfg["allowed_widths"] if int(w) >= n_shards})
            cfg["allowed_widths"] = tuple(per_shard)
        return cfg

    def _refill_kwargs(self, popsize: int, n_shards: int = 1) -> dict:
        """Rollout-engine kwargs of the refill scheduler — explicit
        refill_config, else the tuned cache. The (global) lane width
        divides by the shard count, like compact_config's widths —
        flooring, by convention of the convenience knobs (the strict form,
        ``parallel.make_sharded_rollout_evaluator``, raises instead)."""
        cfg = self._tuned_knobs("refill", self._refill_config, popsize)
        kw = {}
        if cfg.get("width") is not None:
            kw["refill_width"] = max(1, int(cfg["width"]) // n_shards)
        if cfg.get("period") is not None:
            kw["refill_period"] = int(cfg["period"])
        return kw

    def _bump_counters(self, steps, episodes):
        # counters accumulate as device scalars: no device->host sync in the
        # hot loop (VERDICT r1 item 6); device_put pins them to one device so
        # rollouts executed on different meshes still add up (async d2d copy)
        dev = jax.devices()[0]
        self._interaction_count = self._interaction_count + jax.device_put(steps, dev)
        self._episode_count = self._episode_count + jax.device_put(episodes, dev)

    def _consume_telemetry(self, telemetry):
        """Enqueue this evaluation's packed telemetry vector and decode the
        previous one (already materialized — see the constructor note).

        A STACKED ``(K, G, C)`` matrix from a fused training span feeds the
        same swap row by row: by the time the span's host fetch happens the
        whole program has retired, so rows ``0..K-2`` decode immediately and
        only the FINAL row stays pending until the next consume — the
        lag-by-one discipline generalized to lag-by-span (docs/observability.md
        "Lag-by-span")."""
        if telemetry is None:
            return
        if getattr(telemetry, "ndim", 0) == 3:
            if telemetry.shape[-1] == 0:  # graftlint: allow(telemetry-schema): width-0 emptiness probe on .shape, not a column read
                return  # stacked telemetry-off wire
            for row in telemetry:
                self._consume_telemetry(row)
            return
        from ..observability import GroupTelemetry

        prev, self._pending_telemetry = self._pending_telemetry, telemetry
        if prev is not None:
            # ONE metered fetch per generation, whatever G is: the per-group
            # matrix is decoded once, and the global figures derive from it
            gt = GroupTelemetry.from_array(prev)
            self._last_group_telemetry = gt
            self._last_telemetry = gt.total()

    def _report_counters(self, batch) -> dict:
        status = {
            "total_interaction_count": self._interaction_count,
            "total_episode_count": self._episode_count,
        }
        if self._last_telemetry is not None:
            # eval_occupancy / eval_refill_events / eval_queue_wait: the
            # previous generation's figures (lag-by-one; shapes are identical
            # generation to generation, so the diagnostics are current)
            status.update(self._last_telemetry.as_status(prefix="eval_"))
            # exact quarantine share: quarantined solutions over the batch
            # size (the telemetry's own denominator is episodes, which
            # differs at num_episodes > 1) — what max_nonfinite_share reads
            status["eval_nonfinite_share"] = float(
                self._last_telemetry.nonfinite
            ) / max(1, len(batch))
        if self._last_group_telemetry is not None:
            # per-group keys (eval_g{g}_occupancy/...), emitted only at G>1
            status.update(self._last_group_telemetry.as_status(prefix="eval_"))
            if self._last_group_telemetry.has_health:
                # search-health plane: previous generation's global score
                # statistics (per-group keys come from as_status at G>1)
                stats = self._last_group_telemetry.score_stats()
                if stats["count"] > 0:
                    status["eval_score_mean"] = round(stats["mean"], 6)
                    status["eval_score_std"] = round(stats["std"], 6)
            if self._slo is not None:
                status.update(
                    self._slo.check(
                        self._last_group_telemetry, status=status
                    ).as_status()
                )
        if self._tuned_config_source is not None:
            # where the schedule knobs came from: "override" (explicit
            # config), "cache" (tuned_configs.json hit) or "fallback"
            # (engine default) — set on the tunable eval modes only
            status["tuned_config_source"] = self._tuned_config_source
        return status

    # ------------------------------------------------------------ evaluation
    def _contract_kwargs(self) -> dict:
        """The eval contract's static configuration, as every rollout entry
        point takes it (the single-device engines, the sharded evaluator, the
        fused training span): the ONE place it is assembled, so the programs
        they build, and the one ``lower_evaluation`` lowers, cannot drift."""
        return dict(
            num_episodes=self._num_episodes,
            episode_length=self._episode_length,
            observation_normalization=self._observation_normalization,
            alive_bonus_schedule=self._alive_bonus_schedule,
            decrease_rewards_by=self._decrease_rewards_by,
            action_noise_stdev=self._action_noise_stdev,
            compute_dtype=self._compute_dtype,
            nonfinite_quarantine=self._nonfinite_quarantine,
            nonfinite_penalty=self._nonfinite_penalty,
            health=self._health_telemetry,
        )

    def _rollout_kwargs(self, popsize: int, groups=None) -> dict:
        """Keyword arguments of the single-device rollout of ``popsize``
        solutions: ``run_vectorized_rollout``'s, or under ``episodes_compact``
        ``run_vectorized_rollout_compacting``'s."""
        kwargs = self._contract_kwargs()
        if groups is not None:
            # num_groups stays the problem-GLOBAL count: sub-batch matrices
            # share the row space, so they stay addable
            kwargs["groups"] = groups
            kwargs["num_groups"] = self._num_groups
        if self._eval_mode == "episodes_compact":
            kwargs.update(self._compact_kwargs(popsize))
            return kwargs
        kwargs["eval_mode"] = self._eval_mode
        if self._eval_mode == "episodes_refill":
            kwargs.update(self._refill_kwargs(popsize))
        return kwargs

    def _rollout_batch(self, values: jnp.ndarray, key, groups=None) -> tuple:
        if self._eval_backend is not None:
            return self._eval_backend.evaluate(self, values, key, groups=groups)
        popsize = _params_popsize(values)
        kwargs = self._rollout_kwargs(popsize, groups)
        if self._eval_mode == "episodes_compact":
            return run_vectorized_rollout_compacting(
                self._env, self._policy, values, key, self._obs_norm.stats,
                prewarm=self._take_prewarm(popsize), **kwargs,
            )
        return run_vectorized_rollout(
            self._env, self._policy, values, key, self._obs_norm.stats, **kwargs
        )

    def lower_evaluation(self, popsize: int, *, like=None):
        """The device program ``evaluate`` dispatches for a dense population
        of ``popsize`` solutions (or, with ``like``, for a factored
        population of that batch's form: a ``LowRankParamsBatch`` or
        ``TrunkDeltaParamsBatch``, concrete or abstract, on one device),
        lowered on ``ShapeDtypeStruct``s
        (``jax.stages.Lowered``): ``run_vectorized_rollout`` with this
        problem's contract on one device, or the memoized sharded evaluator's
        program where ``num_actors`` gives a mesh. Nothing runs and no PRNG
        key is drawn; through the persistent compile cache ``.compile()`` of
        it is a load. ``compile().as_text()`` names every instruction with
        its scope (``observability.scopes.instruction_scopes``), which a
        device trace without the HLO proto does not.

        ``episodes_compact`` (host-orchestrated chunks) and an
        ``eval_backend`` have no single program and raise. Under
        ``max_num_envs`` the program is that of one full sub-batch."""
        if self._eval_backend is not None or self._eval_mode == "episodes_compact":
            raise ValueError(
                "lower_evaluation needs the one compiled rollout program of"
                " eval_mode 'budget', 'episodes' or 'episodes_refill' without an"
                f" eval_backend; got eval_mode={self._eval_mode!r}"
            )

        def abstract(x):
            # an array that came out of a mesh program is committed to its
            # layout, and jit specialises on that; anything else is free
            sharding = x.sharding if getattr(x, "committed", False) else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        popsize = int(popsize)
        key = abstract(self._rng_key)
        stats = jax.tree_util.tree_map(abstract, self._obs_norm.stats)
        mesh = self._num_actors_mesh(popsize)
        if mesh is None and self._max_num_envs is not None:
            popsize = min(popsize, self._max_num_envs)
        shape = (popsize, self.solution_length)
        if mesh is not None:
            # a population that came out of a mesh program arrives committed
            # to a layout (the OO searchers' does from the second generation
            # on), and the evaluator's jit specialises on it
            values = jax.ShapeDtypeStruct(shape, self.dtype, sharding=self._population_layout)
            evaluator = self._sharded_rollout_evaluator(mesh, "pop")
            return evaluator.program_builder("dense", popsize).lower(values, key, stats)
        values = jax.ShapeDtypeStruct(shape, self.dtype)
        if like is not None:
            if mesh is not None or not is_factored(like):
                raise ValueError("like= takes a factored batch, on one device")
            values = jax.tree_util.tree_map(abstract, like)
            popsize = _params_popsize(like)
        return run_vectorized_rollout.lower(
            self._env,
            self._policy,
            values,
            key,
            stats,
            **self._rollout_kwargs(popsize, self._check_solution_groups(popsize)),
        )

    def _resolve_num_actors_request(self):
        """VecNE honors ``num_actors`` through its own sharded path (the
        generic resolver would warn: there is no plain objective_func)."""

    def _num_actors_mesh(self, popsize: int):
        """Mesh for a pending ``num_actors`` request. The GSPMD evaluator
        pads an indivisible popsize to the next mesh multiple (the padding
        lanes are masked), so the request is honored exactly; the sharded
        compact runner still requires divisibility and steps down to the
        largest dividing shard count."""
        request = self._num_actors_requested
        if request is None:
            return None
        if isinstance(request, str):
            if request in ("max", "num_devices", "num_gpus", "num_cpus"):
                n = jax.device_count()
            else:
                raise ValueError(f"Unrecognized num_actors request: {request!r}")
        else:
            n = min(int(request), jax.device_count())
        n = max(1, n)
        if self._eval_mode == "episodes_compact":
            while popsize % n != 0:
                n -= 1
        if n <= 1:
            return None
        return default_mesh(("pop",), devices=jax.devices()[:n])

    def _evaluate_batch(self, batch: SolutionBatch):
        # the backend path owns the device program — the local mesh request
        # does not apply through it (the SERVER may be meshed instead)
        mesh = (
            None if self._eval_backend is not None else self._num_actors_mesh(len(batch))
        )
        if mesh is not None:
            self.evaluate_sharded(batch, mesh=mesh)
            return
        values = batch.values
        if not is_factored(values):
            # a factored population (low-rank or trunk-delta) stays factored
            # all the way into the rollout engine — the dense (N, L) matrix
            # is never built
            values = jnp.asarray(values)
        n = len(batch)
        groups = self._check_solution_groups(n)
        if self._max_num_envs is not None and n > self._max_num_envs:
            # workload splitting (reference vecgymne.py:440-455): evaluate in
            # sub-batches of at most max_num_envs environments
            scores = []
            for start in range(0, n, self._max_num_envs):
                stop = min(start + self._max_num_envs, n)
                piece = (
                    values.take(jnp.arange(start, stop))
                    if is_factored(values)
                    else values[start:stop]
                )
                result = self._rollout_batch(
                    piece,
                    self.next_rng_key(),
                    groups=None if groups is None else groups[start:stop],
                )
                scores.append(result.scores)
                self._consume_rollout_side_effects(result)
            batch.set_evals(self._maybe_inject_nonfinite(jnp.concatenate(scores)))
            return
        result = self._rollout_batch(values, self.next_rng_key(), groups=groups)
        self._consume_rollout_side_effects(result)
        batch.set_evals(self._maybe_inject_nonfinite(result.scores))

    def _maybe_inject_nonfinite(self, scores):
        """Deterministic score corruption (docs/resilience.md):
        ``EVOTORCH_FAULTS="eval.scores:nonfinite@G[:share]"`` NaNs a seeded
        share of this generation's scores at the host boundary — the
        reproducible stand-in for diverged physics that the quarantine
        acceptance tests drive. With quarantine enabled the same
        replacement rule the engines compile (worst-finite / fixed
        penalty) is applied to the corrupted vector, so an injected run
        shows exactly what a quarantined diverging run shows."""
        from ..resilience.faults import fault_point

        rule = fault_point("eval.scores")
        if rule is None or rule.kind != "nonfinite":
            return scores
        from ..observability.registry import counters
        from .net.vecrl import _quarantine_nonfinite

        scores = jnp.asarray(scores)
        n = int(scores.shape[0])
        k = max(1, int(round(rule.float_arg(0.25) * n)))
        idx = np.random.default_rng(1234 + rule.count).choice(n, size=min(k, n), replace=False)
        scores = scores.at[jnp.asarray(idx)].set(jnp.nan)
        counters.increment("faults.injected_nonfinite", len(idx))
        if self._nonfinite_quarantine:
            scores, _ = _quarantine_nonfinite(
                scores, penalty=self._nonfinite_penalty
            )
        return scores

    def _check_solution_groups(self, popsize: int):
        """The configured per-solution group ids, validated against the
        batch size (None when per-group accounting is off)."""
        groups = self._solution_groups
        if groups is not None and len(groups) != popsize:
            raise ValueError(
                f"solution_groups maps {len(groups)} solutions but the batch"
                f" holds {popsize}"
            )
        return groups

    def _consume_rollout_side_effects(self, result):
        # counters accumulate as device scalars: the addition enqueues a tiny
        # async op instead of forcing a device->host sync every generation
        # (VERDICT r1 "what's weak" #3); status readers convert lazily
        if self._observation_normalization:
            self._obs_norm.stats = result.stats
        self._bump_counters(result.total_steps, result.total_episodes)
        self._consume_telemetry(result.telemetry)
        self._last_policy_report = getattr(result, "policy_report", None)

    @property
    def last_policy_report(self):
        """The last evaluation's ``RolloutResult.policy_report``: what a
        stateful policy's final state says of it (the decoder's expert load
        and cache writes, and the ids every lane consumed), as device arrays;
        None for a policy that reports nothing."""
        return self._last_policy_report

    # ------------------------------------------------------- policy exports
    def to_policy(self, solution) -> Module:
        """Wrap a solution as a deployable policy module **carrying the
        solution's evolved weights** (a FrozenModule): obs-norm layer (if any
        statistics were collected) + parameterized network + action clipping
        (reference ``gymne.py:646-672`` / ``vecgymne.py:949-1010``)."""
        from .net.layers import FrozenModule

        values = jnp.asarray(solution.values if hasattr(solution, "values") else solution)
        module: Module = FrozenModule(self._net_module, self._policy.unravel(values))
        if self._observation_normalization and self._obs_norm.count >= 2:
            module = self._obs_norm.to_layer() >> module
        space = self._env.action_space
        if not space.is_discrete and space.lb is not None:
            module = module >> ActClipLayer(space.lb, space.ub)
        return module

    def to_policy_callable(self, solution) -> Callable:
        """A ready closure over the solution's parameters (includes obs-norm
        and action clip)."""
        values = jnp.asarray(solution.values if hasattr(solution, "values") else solution)

        def apply(x, state=None):
            y = x
            if self._observation_normalization and self._obs_norm.count >= 2:
                y = self._obs_norm.normalize(y)
            out, new_state = self._policy(values, y, state)
            space = self._env.action_space
            if space.is_discrete:
                out = jnp.argmax(out, axis=-1)
            elif space.lb is not None:
                out = jnp.clip(out, space.lb, space.ub)
            return out, new_state

        return apply

    def save_solution(self, solution, fname: str):
        """Pickle a solution with its policy and obs stats
        (reference ``gymne.py:674-724``)."""
        import pickle

        values = np.asarray(solution.values if hasattr(solution, "values") else solution)
        payload = {
            "values": values,
            "obs_mean": np.asarray(self._obs_norm.mean) if self._obs_norm.count >= 2 else None,
            "obs_stdev": np.asarray(self._obs_norm.stdev) if self._obs_norm.count >= 2 else None,
            "network_spec": self._network_spec if isinstance(self._network_spec, str) else repr(self._network_spec),
        }
        with open(fname, "wb") as f:
            pickle.dump(payload, f)

    # ------------------------------------------------- sharded evaluation ---
    def _sharded_rollout_evaluator(self, mesh, axis_name: str):
        """The memoized GSPMD evaluator for this problem on ``mesh``
        (``parallel.make_sharded_rollout_evaluator``). Per-mesh memoization
        matters: the helper's compiled-program cache lives in its closure,
        so rebuilding it every evaluation would retrace every generation."""
        from ..parallel.evaluate import make_sharded_rollout_evaluator

        memo = self.__dict__.setdefault("_sharded_evaluator_memo", {})
        evaluator = memo.get(mesh)
        if evaluator is None:
            kwargs = dict(self._contract_kwargs(), eval_mode=self._eval_mode)
            if self._eval_mode == "episodes_refill":
                # explicit knobs pass through GLOBAL (the helper's
                # convention); with none, the helper consults the
                # tuned-config cache per popsize at this mesh label
                if self._refill_config.get("width") is not None:
                    kwargs["refill_width"] = int(self._refill_config["width"])
                if self._refill_config.get("period") is not None:
                    kwargs["refill_period"] = int(self._refill_config["period"])
            if self._solution_groups is not None:
                # the helper pads the ids alongside the population rows;
                # per-mesh memoization is safe — the mapping is fixed at
                # construction
                kwargs["groups"] = self._solution_groups
                kwargs["num_groups"] = self._num_groups
            evaluator = memo[mesh] = make_sharded_rollout_evaluator(
                self._env,
                self._policy,
                mesh=mesh,
                axis_name=axis_name,
                **kwargs,
            )
        return evaluator

    def evaluate_sharded(self, batch: SolutionBatch, mesh=None, axis_name: str = "pop"):
        """Evaluate with the population axis sharded over the mesh
        (``parallel.make_sharded_rollout_evaluator``): the GSPMD form — one
        global program pinned to the mesh layout, bit-identical to the
        unsharded evaluation, popsizes that don't divide the mesh padded
        and masked, and the obs-norm cohort always mesh-GLOBAL. The
        host-orchestrated ``episodes_compact`` contract keeps its dedicated
        sharded runner (strict divisibility, ``obs_norm_sync``)."""
        if mesh is None:
            mesh = default_mesh((axis_name,))
        n_shards = mesh.shape[axis_name]
        values = batch.values
        is_lowrank = is_factored(values)
        if not is_lowrank:
            values = jnp.asarray(values)
            self._population_layout = values.sharding if values.committed else None
        n = len(batch)

        stats = self._obs_norm.stats
        obsnorm = self._observation_normalization
        groups = self._check_solution_groups(n)
        if self._eval_mode == "episodes_compact":
            from ..parallel.mesh import mesh_label

            if n % n_shards != 0:
                raise ValueError(
                    f"Population size {n} must be divisible by mesh size {n_shards}"
                )
            # the sharded compacting runner: jitted chunks shard_mapped over
            # the mesh, host-side width decisions between chunks — each shard
            # narrows its working set as its lanes finish (VERDICT r3 #5)
            result = run_vectorized_rollout_compacting_sharded(
                self._env,
                self._policy,
                values,
                self.next_rng_key(),
                stats,
                mesh=mesh,
                axis_name=axis_name,
                **self._contract_kwargs(),
                prewarm=self._take_prewarm(n),
                stats_sync=(obsnorm and self._obs_norm_sync == "step"),
                groups=groups,
                num_groups=self._num_groups if groups is not None else 1,
                **self._sharded_compact_config(n_shards, n, mesh_label(mesh)),
            )
            if obsnorm:
                self._obs_norm.stats = result.stats
            self._bump_counters(result.total_steps, result.total_episodes)
            self._consume_telemetry(result.telemetry)
            batch.set_evals(self._maybe_inject_nonfinite(result.scores))
            self.update_status(self._report_counters(batch))
            return

        evaluator = self._sharded_rollout_evaluator(mesh, axis_name)
        result, _per_shard = evaluator(values, self.next_rng_key(), stats)
        if evaluator.tuned_config_source is not None:
            # the helper resolved the refill knobs (explicit config >
            # tuned cache at this mesh label > engine default): surface
            # its provenance through the usual status key
            self._tuned_config_source = evaluator.tuned_config_source
        if obsnorm:
            self._obs_norm.stats = result.stats
        self._bump_counters(result.total_steps, result.total_episodes)
        self._consume_telemetry(result.telemetry)
        batch.set_evals(self._maybe_inject_nonfinite(result.scores))
        self.update_status(self._report_counters(batch))

    # --------------------------------------------- fused training spans ---
    def make_training_span(
        self,
        *,
        ask,
        tell,
        popsize: int,
        span: int,
        mesh=None,
        donate_state: bool = True,
        state_metrics=None,
    ):
        """A fused K-generation training program for THIS problem
        (``parallel.make_training_span``): ``lax.scan`` over ``span``
        generations of ask → eval → tell in ONE donated GSPMD program,
        carrying the problem's full eval configuration — contract, episode
        shape, obs-norm, quarantine, per-group ids, health telemetry, and
        (for ``episodes_refill``) the tuned/explicit refill knobs resolved
        exactly as the per-generation path resolves them.

        ``ask``/``tell`` are functional-API callables (the OO searcher shells
        hold host state and cannot ride inside the scan). Feed each result
        back through :meth:`consume_span` so the interaction counters, the
        telemetry swap (lag-by-span) and the obs-norm stats keep flowing into
        the status keys. The host-orchestrated ``episodes_compact`` contract
        cannot be fused — the builder raises."""
        from ..parallel.evaluate import make_training_span as _make_span

        popsize = int(popsize)
        kwargs = dict(self._contract_kwargs(), eval_mode=self._eval_mode)
        if self._eval_mode == "episodes_refill":
            kwargs.update(self._refill_kwargs(popsize))
        groups = self._check_solution_groups(popsize)
        if groups is not None:
            kwargs["groups"] = groups
            kwargs["num_groups"] = self._num_groups
        return _make_span(
            self._env,
            self._policy,
            ask=ask,
            tell=tell,
            popsize=popsize,
            span=span,
            mesh=mesh,
            donate_state=donate_state,
            state_metrics=state_metrics,
            **kwargs,
        )

    def consume_span(self, result):
        """Feed one :meth:`make_training_span` result back into the
        problem's host-side accounting: obs-norm statistics, the device-
        scalar interaction/episode counters (the per-generation step counts
        sum ON DEVICE; episodes come from the stacked telemetry's episodes
        column via ``device_episode_total`` — also on device — with a
        host-arithmetic fallback when telemetry is off), and the telemetry
        swap (rows 0..K-2 decode now, the final row stays pending —
        lag-by-span). Returns the stacked ``(span, popsize)`` scores."""
        state, scores, stats, total_steps, telemetry = result[:5]
        if self._observation_normalization:
            self._obs_norm.stats = stats
        span = int(scores.shape[0])
        if telemetry is not None and getattr(telemetry, "size", 0):
            from ..observability.devicemetrics import device_episode_total

            episodes = device_episode_total(telemetry)
        elif self._eval_mode == "budget":
            episodes = 0  # auto-reset episode counts live only in telemetry
        else:
            episodes = int(scores.shape[-1]) * self._num_episodes * span
        self._bump_counters(
            total_steps.sum() if hasattr(total_steps, "sum") else sum(total_steps),
            episodes,
        )
        self._consume_telemetry(telemetry)
        # refresh the status keys; _report_counters only reads len() of its
        # argument (the nonfinite-share denominator), so the final
        # generation's score row stands in for the batch
        self.update_status(self._report_counters(scores[-1]))
        return scores


# the reference's class name, for drop-in familiarity
VecGymNE = VecNE
