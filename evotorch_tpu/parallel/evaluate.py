"""Sharded population evaluation.

Replaces the reference's actor-pool fitness evaluation
(``core.py:2573-2600``: split batch -> ``ActorPool.map_unordered`` ->
scatter-back) with GSPMD: the evaluation is written ONCE as the global
program, the ``(N, L)`` population is pinned to the mesh's population layout
with ``NamedSharding`` / ``with_sharding_constraint``, and XLA's SPMD
partitioner inserts the collectives — no pickling, no RPC, and no hand-written
per-shard wiring (the per-lane PRNG chains, the obs-stat delta psums and the
counter collectives all become compiler business). The global program IS
the single-device program, so sharded
evaluation is bit-identical to unsharded at any mesh shape (1-D ``pop`` or
2-D ``pop x model``), and popsizes that don't divide the mesh are padded
with first-row copies and masked via the engine's ``num_valid`` contract
(``docs/sharding.md``).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import default_mesh, mesh_label, model_axis_size

# compiled programs kept per (params kind, popsize); matches the spirit of
# vecrl's _ENGINE_CACHE_SIZE bound
_EVALUATOR_CACHE_SIZE = 64

__all__ = [
    "make_generation_step",
    "make_resident_rollout_program",
    "make_sharded_evaluator",
    "make_sharded_rollout_evaluator",
    "make_training_span",
    "population_spec",
    "shard_population",
]


def population_spec(mesh: Mesh) -> P:
    """The canonical ``PartitionSpec`` of a population's leading axis: ALL
    mesh axes flattened onto it — on a 2-D ``pop x model`` mesh the
    population rows spread over the entire device grid (``P(("pop",
    "model"))``), so every device holds whole lanes and the evaluation stays
    bit-identical to the unsharded program (sharding model *parameters*
    across lanes is a different layout with different numerics — see
    docs/sharding.md)."""
    names = tuple(mesh.axis_names)
    return P(names) if len(names) > 1 else P(names[0])


def _population_mesh(mesh: Optional[Mesh]):
    """The context a GSPMD program traces its evaluation in: what is traced
    inside can ask ``jax.sharding.get_abstract_mesh()`` which axes the
    population is spread over. The SPMD partitioner cannot split a Pallas
    kernel and would gather every lane onto every device around one; a
    kernel over population lanes (``envs/rigidbody.py``) therefore wraps
    itself in a ``shard_map`` over those axes. Nothing else reads it."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def shard_population(
    values: jnp.ndarray, mesh: Optional[Mesh] = None, axis_name: Optional[str] = None
) -> jnp.ndarray:
    """Place a population array so its leading (population) axis is sharded
    over the mesh — rows live distributed in HBM across devices. With the
    default ``axis_name=None`` the rows spread over ALL mesh axes
    (``population_spec``); passing a name shards over just that axis (the
    historical 1-D form)."""
    if mesh is None:
        mesh = default_mesh((axis_name,) if axis_name is not None else ("pop",))
    spec = population_spec(mesh) if axis_name is None else P(axis_name)
    return jax.device_put(values, NamedSharding(mesh, spec))


def _mesh_grid_size(mesh: Mesh) -> int:
    size = 1
    for s in mesh.shape.values():
        size *= int(s)
    return size


def _pad_rows(values, padded_n: int):
    """Pad a population's leading axis to ``padded_n`` with copies of the
    first row: always a VALID genome, so fitness functions undefined at
    synthetic points (log/div at the zero vector) and jax_debug_nans stay
    safe. Consumers mask the tail via ``num_valid`` or discard it."""
    from ..tools.lowrank import is_factored

    if is_factored(values):
        # per-lane state is the coefficients alone; _replace is
        # type-preserving, so trunk-delta batches keep their factors
        coeffs = values.coeffs
        pad = jnp.broadcast_to(
            coeffs[:1], (padded_n - coeffs.shape[0],) + coeffs.shape[1:]
        )
        return values._replace(coeffs=jnp.concatenate([coeffs, pad], axis=0))
    pad = jnp.broadcast_to(values[:1], (padded_n - values.shape[0],) + values.shape[1:])
    return jnp.concatenate([values, pad], axis=0)


def _constrain_population(values, mesh: Mesh):
    """Pin a (dense or factored) population to the mesh's population layout
    inside a jitted program. Low-rank batches shard their per-lane
    coefficients and replicate the shared center/basis (the factored analog
    of ``vecrl._params_shard_spec``). Trunk-delta batches additionally pin
    their L-sized trunk array (the flat center)
    to the ``model`` axis when the mesh has one — STORAGE sharding (ZeRO
    style): XLA all-gathers the trunk at its use sites, which is
    value-exact, so scores stay bit-identical to the unsharded program
    while the dominant HBM term divides over the model axis
    (``docs/sharding.md``)."""
    from ..tools.lowrank import LowRankParamsBatch, TrunkDeltaParamsBatch

    spec = population_spec(mesh)
    if isinstance(values, TrunkDeltaParamsBatch):
        rep = NamedSharding(mesh, P())
        trunk = (
            NamedSharding(mesh, P(("model",)))
            if model_axis_size(mesh) > 1
            else rep
        )
        return TrunkDeltaParamsBatch(
            center=jax.lax.with_sharding_constraint(values.center, trunk),
            coeffs=jax.lax.with_sharding_constraint(
                values.coeffs, NamedSharding(mesh, spec)
            ),
            factors=jax.tree_util.tree_map(
                lambda f: jax.lax.with_sharding_constraint(f, rep), values.factors
            ),
        )
    if isinstance(values, LowRankParamsBatch):
        rep = NamedSharding(mesh, P())
        return LowRankParamsBatch(
            center=jax.lax.with_sharding_constraint(values.center, rep),
            basis=jax.lax.with_sharding_constraint(values.basis, rep),
            coeffs=jax.lax.with_sharding_constraint(
                values.coeffs, NamedSharding(mesh, spec)
            ),
        )
    return jax.lax.with_sharding_constraint(values, NamedSharding(mesh, spec))


def make_sharded_evaluator(
    fitness_func: Callable,
    *,
    mesh: Optional[Mesh] = None,
    axis_name: str = "pop",
) -> Callable:
    """Wrap a vectorized fitness function ``f(values (n,L)) -> (n,) | (n,K)``
    into a jitted evaluator that shards the population axis over the mesh.

    Populations whose size is not divisible by the mesh are padded with
    their first row and the padding results are discarded (the analog of the
    reference's uneven ``split_workload``, ``tools/misc.py:1113``).

    GSPMD: the function is traced once globally and the population is
    pinned to ``population_spec(mesh)`` — XLA partitions the computation.
    """
    if mesh is None:
        mesh = default_mesh((axis_name,))

    n_grid = _mesh_grid_size(mesh)
    sharding = NamedSharding(mesh, population_spec(mesh))

    @jax.jit
    def evaluator(values):
        n = values.shape[0]
        padded_n = -(-n // n_grid) * n_grid
        padded = _pad_rows(values, padded_n) if padded_n != n else values
        padded = jax.lax.with_sharding_constraint(padded, sharding)
        result = fitness_func(padded)
        return jax.tree_util.tree_map(lambda r: r[:n], result)

    return evaluator


def _normalize_kind(kind) -> str:
    """Accept the historical boolean ``lowrank`` flag on the
    ``program_builder`` surface and map it onto the kind tags
    (``vecrl._params_kind``): ``False`` -> dense, ``True`` -> lowrank."""
    if isinstance(kind, bool):
        return "lowrank" if kind else "dense"
    return str(kind)


_RESERVED_ROLLOUT_KWARGS = {"lane_ids", "seed_stride", "num_valid"}


def _check_reserved(rollout_kwargs, what: str):
    reserved = _RESERVED_ROLLOUT_KWARGS & set(rollout_kwargs)
    if reserved:
        raise ValueError(
            f"{what} sets {sorted(reserved)} itself (the global lane/seed "
            "wiring and the padding mask are what the helper exists to get "
            "right) — drop them from the rollout kwargs"
        )


def _lookup_refill_config(env, policy, mesh, rollout_kwargs, popsize):
    """Tuned-config cache consult (observability/timings.py) for a
    refill-mode evaluation with no explicit knobs. Returns
    ``(local_kwargs, source)``. Cache widths are GLOBAL lane counts; the
    lookup shape carries the mesh label, so a schedule tuned at one mesh
    shape is never applied under another (docs/observability.md)."""
    from ..observability.timings import (
        SOURCE_CACHE,
        SOURCE_FALLBACK,
        SOURCE_OVERRIDE,
        canonical_env_label,
        dtype_label,
        lookup_tuned,
    )

    local_kwargs = dict(rollout_kwargs)
    # GROUP-level override semantics, same as resolve_knobs everywhere else:
    # ANY explicit refill knob (width OR period) disables the cache for the
    # whole group — a cached width was measured at its cached period, so
    # mixing it with a caller's period would be an unmeasured combination
    # wearing a "cache" label
    if (
        rollout_kwargs.get("refill_width") is not None
        or rollout_kwargs.get("refill_period") is not None
    ):
        return local_kwargs, SOURCE_OVERRIDE
    entry = lookup_tuned(
        "refill",
        {
            "env": canonical_env_label(env),
            "popsize": popsize,
            "episode_length": rollout_kwargs.get("episode_length"),
            "num_episodes": rollout_kwargs.get("num_episodes", 1),
            "params": policy.parameter_count,
            "dtype": dtype_label(rollout_kwargs.get("compute_dtype")),
            "mesh": mesh_label(mesh),
        },
    )
    if entry is not None and entry.config.get("width") is not None:
        local_kwargs["refill_width"] = int(entry.config["width"])
        if entry.config.get("period") is not None:
            local_kwargs["refill_period"] = int(entry.config["period"])
        return local_kwargs, SOURCE_CACHE
    return local_kwargs, SOURCE_FALLBACK


def make_resident_rollout_program(
    env,
    policy,
    *,
    mesh: Optional[Mesh] = None,
    **rollout_kwargs,
):
    """A long-lived handle on ONE compiled ``episodes_refill`` rollout
    program — the serving substrate (``evotorch_tpu.serving``,
    docs/serving.md).

    Everything that would retrace — the env, the policy shape, the eval
    contract, the lane width/period, the group-row count, the mesh layout —
    is fixed here, at handle construction; every per-dispatch quantity that
    changes as tenants come and go — the packed parameter slab, the
    per-solution base keys (``solution_keys``), the owner-local
    ``lane_ids``, the tenant→group binding (``groups``), the obs-norm
    stats — is TRACED, so admission/departure churn re-dispatches the same
    resident executable (steady_compiles == 0; the retrace sentinel
    enforces it in the serving tests).

    With a ``mesh``, the slab is pinned to ``population_spec(mesh)`` inside
    the program (GSPMD — the global program is the unsharded program, so
    packing semantics and scores are mesh-independent). Call as
    ``program(values, key, stats, lane_ids, groups, solution_keys)``;
    ``program.key`` is the residency identity, ``program.dispatches``
    counts calls."""
    from ..neuroevolution.net.vecrl import run_vectorized_rollout

    rollout_kwargs.setdefault("eval_mode", "episodes_refill")
    if rollout_kwargs["eval_mode"] != "episodes_refill":
        raise ValueError(
            "make_resident_rollout_program serves the episodes_refill"
            f" contract only, got eval_mode={rollout_kwargs['eval_mode']!r}"
        )

    def _run(values, key, stats, lane_ids, groups, solution_keys):
        if mesh is not None:
            values = _constrain_population(values, mesh)
        with _population_mesh(mesh):
            return run_vectorized_rollout(
                env,
                policy,
                values,
                key,
                stats,
                lane_ids=lane_ids,
                groups=groups,
                solution_keys=solution_keys,
                **rollout_kwargs,
            )

    # one closure-jitted program: no static arguments at THIS layer means
    # the only thing that can retrace is an aval change — exactly the
    # residency contract (slab shape fixed ⇒ executable fixed)
    fn = jax.jit(_run)

    def program(values, key, stats, lane_ids, groups, solution_keys):
        program.dispatches += 1
        return fn(values, key, stats, lane_ids, groups, solution_keys)

    from ..observability.timings import canonical_env_label, dtype_label

    program.dispatches = 0
    program.key = (
        canonical_env_label(env),
        int(policy.parameter_count),
        str(rollout_kwargs["eval_mode"]),
        rollout_kwargs.get("refill_width"),
        mesh_label(mesh) if mesh is not None else "none",
        dtype_label(rollout_kwargs.get("compute_dtype")),
    )
    return program


def make_sharded_rollout_evaluator(
    env,
    policy,
    *,
    mesh: Optional[Mesh] = None,
    axis_name: str = "pop",
    **rollout_kwargs,
):
    """Shard the monolithic rollout engine
    (``neuroevolution.net.vecrl.run_vectorized_rollout``) over the mesh —
    the reusable form of the sharded-evaluation recipe (``dryrun_multichip``
    and ``VecNE.evaluate_sharded`` call it).

    GSPMD: the GLOBAL rollout program is jitted once, the population
    pinned to ``population_spec(mesh)`` (all mesh axes flattened over the
    population rows), and XLA partitions the loop — the program IS the
    unsharded program, so scores are bit-identical to single-device at any
    mesh shape, the obs-norm cohort is always the mesh-GLOBAL population,
    and popsizes that don't divide the mesh are padded with first-row copies
    whose lanes are masked out of score credit and every counter/telemetry
    slot via the engine's ``num_valid`` contract.

    Refill evaluations with NO explicit knobs consult the tuned-config cache
    (``observability/timings.py``) per popsize — the autotuner's measured
    winner for this (env, popsize, episode length/count, params, dtype,
    mesh label, machine) — and ``evaluator.tuned_config_source`` reports the
    branch taken: override / cache / fallback.

    Accepts dense ``(N, L)`` populations and factored
    ``LowRankParamsBatch``es (coefficients shard; center/basis replicate) or
    ``TrunkDeltaParamsBatch``es (coefficients shard over the population
    layout; the L-sized trunk arrays storage-shard over the ``model`` axis
    when the mesh has one — see ``_constrain_population``). Returns
    ``evaluator(values, key, stats) -> (RolloutResult, per_shard_steps)``.
    """
    _check_reserved(rollout_kwargs, "make_sharded_rollout_evaluator")
    if mesh is None:
        mesh = default_mesh((axis_name,))

    # imported lazily: parallel.* must stay importable before neuroevolution
    from ..neuroevolution.net.vecrl import (
        _params_kind,
        _params_popsize,
        run_vectorized_rollout,
        RolloutResult,
    )
    from ..observability.devicemetrics import (
        append_health_block,
        compute_health_block,
    )

    n_grid = _mesh_grid_size(mesh)
    refill_mode = rollout_kwargs.get("eval_mode") == "episodes_refill"

    def build(kind: str, popsize: int):
        local_kwargs = dict(rollout_kwargs)
        source = None
        if refill_mode:
            local_kwargs, source = _lookup_refill_config(
                env, policy, mesh, rollout_kwargs, popsize
            )
        padded_n = -(-popsize // n_grid) * n_grid
        num_valid = popsize if padded_n != popsize else None
        # per-group telemetry (ISSUE 15): the groups array is a build-time
        # constant (one id per GENUINE solution); padding rows are
        # first-row copies, so they charge row 0's group — and being
        # permanently inactive, their only charge is capacity, exactly the
        # v1 physical-lane accounting
        groups = local_kwargs.pop("groups", None)
        num_groups = int(local_kwargs.pop("num_groups", 1) or 1)
        groups_valid = (
            jnp.asarray(groups, dtype=jnp.int32)[:popsize]
            if groups is not None and num_groups > 1
            else None
        )
        if groups is not None and num_groups > 1:
            g = jnp.asarray(groups, dtype=jnp.int32)
            if padded_n != popsize:
                g = jnp.concatenate(
                    [g, jnp.broadcast_to(g[:1], (padded_n - popsize,))]
                )
            local_kwargs["groups"] = g
            local_kwargs["num_groups"] = num_groups
        # the search-health block is computed HERE, not inside the engine:
        # replicating the final scores first forces every device to run the
        # identical full-population reduction (no per-shard partial sums),
        # which is what keeps the float32 stats bit-identical across mesh
        # shapes (docs/observability.md "Search health")
        health = bool(local_kwargs.pop("health", True))
        local_kwargs["health"] = False

        def global_eval(values, key, stats):
            if padded_n != popsize:
                values = _pad_rows(values, padded_n)
            values = _constrain_population(values, mesh)
            with _population_mesh(mesh):
                result = run_vectorized_rollout(
                    env,
                    policy,
                    values,
                    key,
                    stats,
                    num_valid=num_valid,
                    **local_kwargs,
                )
            if result.telemetry is None:
                telemetry = jnp.zeros((0,), dtype=jnp.int32)
            else:
                telemetry = result.telemetry  # the global program's counters
                if health:
                    rep = jax.lax.with_sharding_constraint(
                        result.scores, NamedSharding(mesh, P())
                    )
                    telemetry = append_health_block(
                        telemetry,
                        compute_health_block(
                            rep[:popsize],
                            groups_valid,
                            num_groups if groups_valid is not None else 1,
                        ),
                    )
            return (
                result.scores[:popsize],
                result.stats,
                result.total_steps,
                result.total_episodes,
                # GSPMD has no per-shard accounting (XLA owns the layout);
                # the 1-element form keeps the (result, per_shard) contract
                result.total_steps[None],
                telemetry,
            )

        return jax.jit(global_eval), source

    # bounded LRU like vecrl's engine caches: an adaptive-popsize caller
    # compiles one program per distinct popsize, and compiled executables
    # must not accumulate without bound over a long run
    build = functools.lru_cache(maxsize=_EVALUATOR_CACHE_SIZE)(build)

    def evaluator(values, key, stats):
        popsize = _params_popsize(values)
        fn, source = build(_params_kind(values), popsize)
        evaluator.tuned_config_source = source
        scores, merged, steps, episodes, per_shard, telemetry = fn(values, key, stats)
        result = RolloutResult(
            scores=scores,
            stats=merged,
            total_steps=steps,
            total_episodes=episodes,
            telemetry=telemetry if telemetry.size else None,
        )
        return result, per_shard

    # the jitted (kind, popsize) -> program factory, exposed so the program
    # ledger can AOT-lower the exact executable the evaluator dispatches
    # (observability/inventory.py); accepts the historical boolean lowrank
    # flag or a kind tag ("dense"/"lowrank"/"trunk_delta")
    evaluator.program_builder = lambda kind, popsize: build(
        _normalize_kind(kind), popsize
    )[0]
    # provenance of the LAST dispatched popsize's refill knobs ("override" /
    # "cache" / "fallback"; None before the first refill-mode dispatch)
    evaluator.tuned_config_source = None
    return evaluator


def _generation_body(
    env,
    policy,
    *,
    ask: Callable,
    tell: Callable,
    popsize: int,
    mesh: Mesh,
    **rollout_kwargs,
):
    """The UNJITTED ``ask -> sharded rollout -> tell`` generation body shared
    by :func:`make_generation_step` (which jits it as-is) and
    :func:`make_training_span` (which ``lax.scan``s it K times inside one
    program). Keeping one body is what makes the span bit-identity guarantee
    structural: the scanned step IS the per-generation step, traced from the
    same closure."""
    from ..neuroevolution.net.vecrl import run_vectorized_rollout
    from ..observability.devicemetrics import (
        append_health_block,
        compute_health_block,
    )

    popsize = int(popsize)
    n_grid = _mesh_grid_size(mesh)
    padded_n = -(-popsize // n_grid) * n_grid
    num_valid = popsize if padded_n != popsize else None
    # per-group telemetry: pad the group-id array exactly like the
    # population rows (first-element copies; see
    # make_sharded_rollout_evaluator)
    groups = rollout_kwargs.pop("groups", None)
    num_groups = int(rollout_kwargs.pop("num_groups", 1) or 1)
    groups_valid = (
        jnp.asarray(groups, dtype=jnp.int32)[:popsize]
        if groups is not None and num_groups > 1
        else None
    )
    if groups is not None and num_groups > 1:
        g = jnp.asarray(groups, dtype=jnp.int32)
        if padded_n != popsize:
            g = jnp.concatenate([g, jnp.broadcast_to(g[:1], (padded_n - popsize,))])
        rollout_kwargs["groups"] = g
        rollout_kwargs["num_groups"] = num_groups
    # health block computed on replicated scores, like
    # make_sharded_rollout_evaluator (mesh-shape bit-identity)
    health = bool(rollout_kwargs.pop("health", True))
    rollout_kwargs["health"] = False

    def generation(state, key, stats):
        k_ask, k_eval = jax.random.split(key)
        values = ask(k_ask, state)
        if padded_n != popsize:
            # `tell` reduces over ALL of these rows; sharded unevenly, the
            # order of that sum is the partitioner's choice, and it chooses
            # differently in a program and in its scanned form
            values = jax.lax.with_sharding_constraint(values, NamedSharding(mesh, P()))
        evald = _pad_rows(values, padded_n) if padded_n != popsize else values
        evald = _constrain_population(evald, mesh)
        with _population_mesh(mesh):
            result = run_vectorized_rollout(
                env,
                policy,
                evald,
                k_eval,
                stats,
                num_valid=num_valid,
                **rollout_kwargs,
            )
        scores = result.scores[:popsize]
        new_state = tell(state, values, scores)
        if result.telemetry is None:
            telemetry = jnp.zeros((0,), dtype=jnp.int32)
        else:
            telemetry = result.telemetry
            if health:
                rep = jax.lax.with_sharding_constraint(
                    result.scores, NamedSharding(mesh, P())
                )
                telemetry = append_health_block(
                    telemetry,
                    compute_health_block(
                        rep[:popsize],
                        groups_valid,
                        num_groups if groups_valid is not None else 1,
                    ),
                )
        return new_state, scores, result.stats, result.total_steps, telemetry

    return generation


def make_generation_step(
    env,
    policy,
    *,
    ask: Callable,
    tell: Callable,
    popsize: int,
    mesh: Optional[Mesh] = None,
    donate_state: bool = True,
    **rollout_kwargs,
):
    """One whole generation — ``ask -> sharded rollout -> tell`` — compiled
    as ONE jitted GSPMD program with the evolution state DONATED: the
    sample buffers, the rollout working set and the updated distribution
    state all reuse the previous generation's HBM, so a training loop's
    steady-state footprint is a single generation's live set (the program
    ledger's donation verification covers this program;
    ``docs/observability.md``).

    ``ask(key, state) -> values`` samples the ``(popsize, L)`` population
    (dense, ``LowRankParamsBatch``, or ``TrunkDeltaParamsBatch`` — e.g.
    ``pgpe_ask_trunk_delta``); ``tell(state, values, scores) -> state``
    applies the update. Both run INSIDE the program — the population is born
    on its shards, evaluated in place, and consumed by the update without
    ever leaving the device grid.

    Returns ``generation(state, key, stats) -> (state, scores, stats,
    total_steps, telemetry)``. With ``donate_state=True`` (default) the
    caller must rebind: ``state, ... = generation(state, key, stats)`` —
    the old state's buffers are invalidated.
    """
    _check_reserved(rollout_kwargs, "make_generation_step")
    if mesh is None:
        mesh = default_mesh(("pop",))
    generation = _generation_body(
        env, policy, ask=ask, tell=tell, popsize=popsize, mesh=mesh,
        **rollout_kwargs,
    )
    return jax.jit(generation, donate_argnums=(0,) if donate_state else ())


def make_training_span(
    env,
    policy,
    *,
    ask: Callable,
    tell: Callable,
    popsize: int,
    span: int,
    mesh: Optional[Mesh] = None,
    donate_state: bool = True,
    state_metrics: Optional[Callable] = None,
    **rollout_kwargs,
):
    """``span`` generations fused into ONE jitted, state-donating GSPMD
    program: a ``lax.scan`` over the :func:`make_generation_step` body, so a
    training loop pays Python dispatch + device sync + telemetry decode once
    per K generations instead of once per generation (the Podracer/Anakin
    move applied to the ES outer loop; ``docs/sharding.md`` "Fused
    multi-generation training spans").

    ``ask``/``tell``/``popsize``/``mesh``/``rollout_kwargs`` mean exactly
    what they mean for :func:`make_generation_step` — the scanned step is the
    SAME traced body, so the result is bit-identical (state pytree, scores,
    telemetry column sums, obs-norm stats) to ``span`` sequential
    ``make_generation_step`` calls fed the same per-generation keys, at any
    mesh shape including padded indivisible popsizes. The obs-norm ``stats``
    ride the scan carry, preserving the sequential update order.

    ``eval_mode="episodes_compact"`` is rejected: lane compaction is
    host-orchestrated (chunked re-dispatch from Python;
    ``docs/eval_contracts.md``), so it cannot live inside a monolithic
    scanned program — use ``episodes_refill`` for the on-device
    work-conserving form.

    ``state_metrics(state) -> pytree`` (optional, e.g.
    ``algorithms.functional.pgpe_health``) is evaluated on the post-``tell``
    state of EVERY generation inside the program; its stacked outputs let
    hosts reconstruct per-generation algorithm-health rows without K extra
    dispatches.

    Returns ``training_span(state, keys, stats) -> (state, scores, stats,
    total_steps, telemetry[, metrics])`` where ``keys`` is a ``(span,)``
    PRNG key array (one per generation — e.g. ``jax.random.split(key,
    span)``; scan raises at trace time on a length mismatch) and the ys are
    stacked per generation: ``scores (span, popsize)``, ``total_steps
    (span,)``, ``telemetry (span, G, C)`` (or ``(span, 0)`` with telemetry
    off — decode row-by-row, see docs/observability.md "Lag-by-span"), and
    ``metrics`` the stacked ``state_metrics`` pytree when provided. With
    ``donate_state=True`` (default) the caller must rebind ``state``.
    """
    _check_reserved(rollout_kwargs, "make_training_span")
    span = int(span)
    if span < 1:
        raise ValueError(f"span must be >= 1, got {span}")
    if rollout_kwargs.get("eval_mode") == "episodes_compact":
        raise ValueError(
            "make_training_span cannot fuse eval_mode='episodes_compact': "
            "lane compaction is host-orchestrated (chunked re-dispatch from "
            "Python) and cannot run inside one scanned device program — use "
            "'episodes_refill' for the on-device work-conserving contract"
        )
    if mesh is None:
        mesh = default_mesh(("pop",))
    generation = _generation_body(
        env, policy, ask=ask, tell=tell, popsize=popsize, mesh=mesh,
        **rollout_kwargs,
    )

    def training_span(state, keys, stats):
        kshape = jnp.shape(keys)
        if not kshape or kshape[0] != span:
            raise ValueError(
                f"training_span expects a (span={span},) PRNG key array — "
                f"one key per generation, e.g. jax.random.split(key, {span}) "
                f"— got key shape {kshape}"
            )

        def body(carry, key):
            state, stats = carry
            state, scores, stats, steps, telemetry = generation(state, key, stats)
            ys = (scores, steps, telemetry)
            if state_metrics is not None:
                ys = ys + (state_metrics(state),)
            return (state, stats), ys

        (state, stats), ys = jax.lax.scan(body, (state, stats), keys, length=span)
        out = (state, ys[0], stats, ys[1], ys[2])
        if state_metrics is not None:
            out = out + (ys[3],)
        return out

    return jax.jit(training_span, donate_argnums=(0,) if donate_state else ())
