"""Vectorized-RL plumbing: batched policies and the jitted rollout engine.

Parity: reference ``net/vecrl.py`` (1912 LoC). What the reference assembles
from dlpack converters (``vecrl.py:53-82``), ``TorchWrapper``
(``vecrl.py:362-613``), a stateful ``Policy`` with auto-vmap forward and
per-env reset (``vecrl.py:1019-1361``), ``reset_tensors``
(``vecrl.py:866-1016``) and eager Python stepping (``vecgymne.py:837-904``)
becomes here ONE jitted program: ``run_vectorized_rollout`` compiles the
entire population x envs x time loop — masked activity, auto-reset,
episode/interaction accounting, obs-norm statistics in the carry — into a
single ``lax.while_loop`` (SURVEY.md §3.4 and §5 long-context note).

``run_vectorized_rollout_compacting`` is the TPU answer to the idle-lane
problem of the reference's evaluation contract (each lane runs its episodes
then idles until the whole population finishes): the loop runs in chunks,
and between chunks the still-active lanes are sorted to the front and the
working width shrinks to the smallest allowed power-of-two that holds them —
so once most of the population has finished, the machine stops paying for
the dead lanes.

``eval_mode="episodes_refill"`` is the work-conserving alternative
(continuous batching for rollouts, after the Podracer always-on device
loops, arXiv:2104.06272): a FIXED lane width ``W <= popsize * num_episodes``,
a pending-work queue carried in the ``lax.while_loop`` state, and an
on-device refill step that reloads a finishing lane with the next pending
(solution, episode) item — fresh env reset from the item's own PRNG seed,
policy parameters gathered into the lane slot, episode return credited to
the right solution by segment reduction. No host round-trip, no re-trace,
no padding to the longest survivor.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ...observability.devicemetrics import (
    QUEUE_WAIT_BUCKETS,
    TELEMETRY_WIDTH,
    append_health_block,
    compute_health_block,
    pack_eval_telemetry,
    pack_group_telemetry,
    queue_wait_bucket_index,
)
from ...observability.scopes import scope
from ...tools.lowrank import is_factored
from ..net.functional import FlatParamsPolicy
from ..net.layers import zero_rows
from ..net.lowrank import (
    LowRankParamsBatch,
    TrunkDeltaParamsBatch,
    lowrank_forward,
    prepare_lowrank,
    prepare_trunk_delta,
    trunk_delta_forward,
)
from ..net.rl import alive_bonus_for_step
from ..net.runningnorm import (
    CollectedStats,
    group_stats_normalize,
    group_stats_update,
    stats_normalize,
    stats_update,
)

__all__ = [
    "Policy",
    "reset_tensors",
    "run_vectorized_rollout",
    "run_vectorized_rollout_compacting",
    "run_vectorized_rollout_compacting_sharded",
    "global_lane_ids",
    "RolloutResult",
]


def _in_scope(name: str):
    """Decorator form of ``scope(name)`` with a fresh context manager per
    call (jax's own decorator form saves the previous name stack on the one
    shared object, so it is neither re-entrant nor safe across threads that
    trace at the same time)."""

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


# ------------------- population-parameter representations -------------------
# The engine accepts a population as a dense (N, L) matrix, a
# LowRankParamsBatch (center + shared basis + per-lane coefficients — the
# augmented-matmul MXU path, net/lowrank.py), or a TrunkDeltaParamsBatch
# (shared trunk + rank-1-per-block deltas — the shared-trunk MXU path,
# docs/policies.md). These helpers are the only places that care which one
# it is; per-lane state lives ONLY in coeffs for both factored forms
# (tools.lowrank.is_factored), so take/popsize generalize.


def _params_popsize(params_batch) -> int:
    if is_factored(params_batch):
        return params_batch.popsize
    return params_batch.shape[0]


def _params_cast(params_batch, dtype):
    if dtype is None:
        return params_batch
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params_batch)


def _params_take(params_batch, idx):
    """Rows ``idx`` of a population: of a factored batch, of the dense matrix,
    or of every leaf of its unravelled tree."""
    if is_factored(params_batch):
        return params_batch.take(idx)
    return jax.tree_util.tree_map(lambda x: x[idx], params_batch)


@_in_scope("rollout_edges")
def _forward_ctx(policy, params_batch, trunk_block: int = 0):
    """The loop-invariant forward context of a population: what a control
    step's forward reads. Call inside jit, OUTSIDE stepping loops.

    - dense ``(N, L)`` matrix: the unravelled population, a per-layer tree
      with a leading lane axis (leaves ``(N, out, in)``, ``(N, out)``, ...).
      Cutting the flat matrix into per-layer blocks is a physical copy on
      the TPU (the tiled minor dimensions change), so it happens here, once
      per program, and no loop body touches the flat matrix;
    - low-rank / trunk-delta: the per-layer center/basis or trunk/factor
      trees. ``trunk_block`` is the static lane-block size of the trunk-delta
      forward (0 = single block; ignored by the other forms)."""
    if isinstance(params_batch, TrunkDeltaParamsBatch):
        return prepare_trunk_delta(policy, params_batch, trunk_block=trunk_block)
    if isinstance(params_batch, LowRankParamsBatch):
        return prepare_lowrank(policy, params_batch)
    return jax.vmap(policy.unravel)(params_batch)


def _batched_forward(policy, params_batch, ctx, obs, states):
    """Whole-population policy forward for any representation: reads
    ``ctx`` (``_forward_ctx`` of ``params_batch``); the dense form reads
    nothing else, so the flat matrix is dead once its context is built."""
    if isinstance(params_batch, TrunkDeltaParamsBatch):
        return trunk_delta_forward(policy, params_batch, ctx, obs, states)
    if isinstance(params_batch, LowRankParamsBatch):
        return lowrank_forward(policy, params_batch, ctx, obs, states)
    return _dense_tree_forward(policy, ctx, obs, states)


def _dense_tree_forward(policy, tree, obs, states):
    """The module applied lane by lane to an unravelled population (or to
    the refill engine's width-``W`` slice of one)."""
    apply = policy.module.apply
    if states is None:
        out, _ = jax.vmap(lambda p, o: apply(p, o, None))(tree, obs)
        return out, None
    return jax.vmap(apply)(tree, obs, states)


def _forward_in_compute_dtype(forward, policy_in, states, compute_dtype):
    """``forward(policy_in, states)`` with its input cast to the compute dtype
    and its raw output cast back to float32: the whole of a control step's
    policy forward, in every engine."""
    with scope("policy_forward"):
        # token ids stay integers: bfloat16 holds no id above 256 exactly
        if compute_dtype is not None and jnp.issubdtype(policy_in.dtype, jnp.floating):
            policy_in = policy_in.astype(compute_dtype)
        raw, new_states = forward(policy_in, states)
        if compute_dtype is not None:
            raw = raw.astype(jnp.float32)
    return raw, new_states


def reset_tensors(tree: Any, mask: jnp.ndarray) -> Any:
    """Zero the rows of every leaf where ``mask`` is True (the reference's
    nested-state resetter, ``vecrl.py:866-1016``), as a pure function. The
    engines reset a policy's state through ``Module.reset_state``, whose
    default is this."""
    return zero_rows(tree, mask)


class Policy:
    """Stateful convenience wrapper over a flat-params policy
    (reference ``Policy``, ``vecrl.py:1019-1361``): give it parameters for one
    solution or a batch of solutions, call it on observations, and it manages
    the recurrent state — including per-env ``reset(indices)``."""

    def __init__(self, net, *, key=None):
        from .functional import FlatParamsPolicy
        from .layers import Module

        if isinstance(net, FlatParamsPolicy):
            self._flat = net
        elif isinstance(net, Module):
            self._flat = FlatParamsPolicy(net, key=key)
        else:
            raise TypeError(f"Policy expects a Module or FlatParamsPolicy, got {type(net)}")
        self._params: Optional[jnp.ndarray] = None
        self._state = None
        self._batched = False

    @property
    def parameter_count(self) -> int:
        return self._flat.parameter_count

    def set_parameters(self, parameters, *, reset: bool = True):
        """Accepts ``(L,)`` for one policy or ``(N, L)`` for a batch of
        policies (reference ``vecrl.py:1191``)."""
        parameters = jnp.asarray(parameters)
        self._params = parameters
        self._batched = parameters.ndim == 2
        if reset:
            self._state = None

    def _fresh_state(self, batch_size: Optional[int]):
        proto = self._flat.initial_state()
        if proto is None:
            return None
        if batch_size is None:
            return proto
        return jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (batch_size,) + leaf.shape), proto
        )

    def __call__(self, obs) -> jnp.ndarray:
        if self._params is None:
            raise RuntimeError("Call set_parameters(...) before using the Policy")
        obs = jnp.asarray(obs)
        if self._batched:
            n = self._params.shape[0]
            if self._state is None:
                self._state = self._fresh_state(n)
            if self._state is None:
                out, _ = jax.vmap(lambda p, o: self._flat(p, o))(self._params, obs)
                return out
            out, self._state = jax.vmap(lambda p, o, s: self._flat(p, o, s))(
                self._params, obs, self._state
            )
            return out
        if self._state is None:
            self._state = self._fresh_state(None)
        out, self._state = self._flat(self._params, obs, self._state)
        return out

    def reset(self, indices=None):
        """Reset recurrent state — fully, or only the rows given by a boolean
        mask / index array (reference ``vecrl.py:1281``)."""
        if self._state is None or indices is None:
            self._state = None
            return
        mask = jnp.asarray(indices)
        if mask.dtype != jnp.bool_:
            n = self._params.shape[0]
            mask = jnp.zeros(n, dtype=bool).at[mask].set(True)
        self._state = reset_tensors(self._state, mask)

    @property
    def h(self):
        return self._state


# telemetry matrix column indices (devicemetrics._SLOTS order)
(
    _COL_ENV_STEPS,
    _COL_EPISODES,
    _COL_CAPACITY,
    _COL_LANE_WIDTH,
    _COL_REFILL,
    _COL_WAIT,
    _COL_NONFINITE,
) = range(TELEMETRY_WIDTH)


def _empty_lane_groups():
    """The lane_groups sentinel when per-group accounting is off: a (0,)
    int32 array (shape-stable, costs nothing in the carry)."""
    return jnp.zeros((0,), dtype=jnp.int32)


def _empty_group_counts():
    """The group_counts sentinel when per-group accounting is off."""
    return jnp.zeros((0, TELEMETRY_WIDTH), dtype=jnp.int32)


def _init_group_counts(lane_groups, num_groups: int):
    """A fresh (G, TELEMETRY_WIDTH) counter block with the lane_width column
    set from the initial lane->group assignment (every other column
    accumulates in the stepping loop)."""
    widths = jax.ops.segment_sum(
        jnp.ones(lane_groups.shape[0], dtype=jnp.int32),
        lane_groups,
        num_segments=num_groups,
    )
    return (
        jnp.zeros((num_groups, TELEMETRY_WIDTH), dtype=jnp.int32)
        .at[:, _COL_LANE_WIDTH]
        .add(widths)
    )


def _fold_lane_counts(
    group_counts, lane_steps, lane_episodes, lane_groups, t_global, num_groups, mask=None
):
    """Fold the per-lane step/episode accumulators into the per-group counter
    block: one segment_sum at a loop boundary instead of one per loop
    iteration. A lane's capacity charge is ``t_global`` — every lane still in
    the carry has been present since t=0 (compaction only ever drops lanes),
    so ``width x iterations`` decomposes into ``t_global`` per present lane.
    ``mask`` (int-castable, per lane) restricts the fold to a subset — the
    lanes being dropped at a compaction boundary; the survivors keep
    accumulating and fold at the next boundary."""
    width = lane_steps.shape[0]
    per_lane = jnp.stack(
        [lane_steps, lane_episodes, jnp.broadcast_to(t_global, (width,))], axis=1
    )
    if mask is not None:
        per_lane = per_lane * mask.astype(jnp.int32)[:, None]
    return group_counts.at[:, :_COL_LANE_WIDTH].add(
        jax.ops.segment_sum(per_lane, lane_groups, num_segments=num_groups)
    )


def _quarantine_nonfinite(scores, *, valid_mask=None, penalty=None, sync_axis=None):
    """Non-finite score quarantine (docs/resilience.md): replace NaN/Inf
    entries of a final per-solution score vector with the WORST finite score
    in the batch (or a fixed ``penalty``) and return the replacement mask.

    Runs once at the very end of an engine, on the ``(N,)`` mean scores —
    one ``isfinite`` plus a select, so the quarantined program is the
    unquarantined one plus a handful of elementwise ops. ``valid_mask``
    excludes padding rows from the worst-finite reduction (their synthetic
    scores are not evidence) and from the returned COUNT mask — but their
    values are still scrubbed finite, so no NaN survives in the full-width
    vector whatever a caller reduces over before slicing. ``sync_axis``
    (shard_map callers) pmins the worst-finite value over the mesh so
    sharded replacement scores stay bit-identical to unsharded; the counts
    are additive and psum with the rest of the telemetry.
    """
    finite = jnp.isfinite(scores)
    bad = ~finite  # replacement mask: every non-finite entry is scrubbed
    consider = finite
    counted = bad
    if valid_mask is not None:
        counted = bad & valid_mask
        consider = consider & valid_mask
    if penalty is not None:
        repl = jnp.asarray(penalty, dtype=scores.dtype)
    else:
        big = jnp.asarray(jnp.finfo(scores.dtype).max, dtype=scores.dtype)
        worst = jnp.min(jnp.where(consider, scores, big))
        if sync_axis is not None:
            worst = jax.lax.pmin(worst, sync_axis)
        # an all-non-finite (or all-padding) batch leaves no worst finite
        # score to charge: quarantine to 0.0 rather than float-max
        repl = jnp.where(worst >= big, jnp.zeros((), scores.dtype), worst)
    return jnp.where(bad, repl, scores), counted


def _nonfinite_group_counts(group_counts, bad, groups, num_groups: int):
    """Fold a quarantine mask into the ``nonfinite`` telemetry column, one
    count per quarantined SOLUTION, charged to the solution's group."""
    return group_counts.at[:, _COL_NONFINITE].add(
        jax.ops.segment_sum(
            bad.astype(jnp.int32), groups, num_segments=int(num_groups)
        )
    )


def _health_telemetry(telemetry, scores, groups, num_groups, num_valid):
    """Append the v4 search-health block to a packed telemetry matrix,
    computed from the final post-quarantine per-solution mean scores. The
    scores (and group ids) are sliced to the static ``num_valid`` BEFORE
    the reductions so padded and unpadded programs reduce over identical
    shapes — the bit-identity contract of docs/observability.md "Search
    health"."""
    if num_valid is not None:
        scores = scores[:num_valid]
        if groups is not None:
            groups = groups[:num_valid]
    return append_health_block(
        telemetry, compute_health_block(scores, groups, num_groups)
    )


class RolloutResult(NamedTuple):
    scores: jnp.ndarray  # (N,) mean episodic return per solution
    stats: CollectedStats  # obs-norm statistics collected during the rollout
    total_steps: jnp.ndarray  # scalar: total env interactions
    total_episodes: jnp.ndarray  # scalar: episodes finished
    # packed on-device eval telemetry (observability.devicemetrics): one
    # (G, GROUP_TELEMETRY_WIDTH) int32 matrix (G=1 without per-group
    # accounting) — or (G, HEALTH_TELEMETRY_WIDTH) with the health plane
    # on — computed inside the same jitted program as the scores; fetching
    # it is part of the same transfer, never a new dispatch. None when the
    # engine ran with telemetry=False.
    telemetry: Any = None
    # what a stateful policy's final state says of the evaluation
    # (``Module.state_report``: a dict of device arrays; the decoder's
    # expert-load and cache counters and the ids every lane consumed), from
    # the monolithic engine; None for a policy that reports nothing
    policy_report: Any = None


class RolloutCarry(NamedTuple):
    """Loop state of the rollout engine. Per-lane leaves are batch-leading
    except ``env_states`` (whose layout belongs to the env; see
    ``Env.batched_native``); ``key`` is the ``(n,)`` array of per-lane PRNG
    chains (randomness is a per-lane property — see ``_rollout_init``);
    ``stats``/counters are global."""

    env_states: Any
    obs: jnp.ndarray
    policy_states: Any
    scores: jnp.ndarray
    episodes_done: jnp.ndarray
    steps_in_episode: jnp.ndarray
    active: jnp.ndarray
    stats: CollectedStats
    key: Any
    total_steps: jnp.ndarray
    t_global: jnp.ndarray
    # lane-step slots executed (working width summed over iterations): the
    # occupancy denominator (observability.devicemetrics); frozen at its
    # initial zero when the engine runs with telemetry off
    capacity: jnp.ndarray
    # per-group accounting (ISSUE 15): lane_groups is the (n,) group id each
    # lane charges its counters to, group_counts the (G, TELEMETRY_WIDTH)
    # per-group counter block. The hot loop only bumps the per-lane
    # accumulators lane_steps/lane_episodes (two elementwise adds); the
    # segment_sum fold into group_counts happens ONCE at a loop boundary
    # (_fold_lane_counts) — lane->group ids never change inside these
    # engines, so the fold commutes with the loop and the per-step cost is
    # G-independent. All four are empty (0-row) sentinels when
    # num_groups == 1 or telemetry is off, so the single-group program
    # carries no group state at all.
    lane_groups: jnp.ndarray
    group_counts: jnp.ndarray
    lane_steps: jnp.ndarray
    lane_episodes: jnp.ndarray


def _policy_to_action(raw, action_space, noise, clip: bool):
    if action_space.is_discrete:
        return jnp.argmax(raw, axis=-1)
    act = raw if noise is None else raw + noise
    if clip and action_space.lb is not None:
        act = jnp.clip(act, action_space.lb, action_space.ub)
    return act


@_in_scope("env_step")
def _env_step(env, env_states, raw, noise_keys, action_noise_stdev):
    """One substep of every lane's env on the policy's raw output: action
    noise from each lane's own chain (the draw is independent of the working
    width / batch composition), clipping, then the env's own step. Returns
    ``(env_states, obs, rewards, dones)``."""
    noise = None
    if action_noise_stdev is not None:
        noise = action_noise_stdev * jax.vmap(
            lambda k: jax.random.normal(k, raw.shape[1:])
        )(noise_keys)
    actions = _policy_to_action(raw, env.action_space, noise, clip=True)
    if getattr(env, "batched_native", False):
        return env.batch_step(env_states, actions)
    return jax.vmap(env.step)(env_states, actions)


@_in_scope("env_reset")
def _env_reset(env, keys):
    if getattr(env, "batched_native", False):
        return env.batch_reset(keys)
    return jax.vmap(env.reset)(keys)


@_in_scope("env_reset")
def _env_state_select(env, mask, a, b):
    """Per-lane env-state select: lane i takes ``a`` where ``mask[i]``."""
    if getattr(env, "batched_native", False):
        return env.batch_where(mask, a, b)

    def select(x, y):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)

    return jax.tree_util.tree_map(select, a, b)


def _lane_select(mask, new, old):
    """Per-lane row select with ``mask`` broadcast over trailing dims."""
    m = mask.reshape(mask.shape + (1,) * (new.ndim - 1))
    return jnp.where(m, new, old)


def _initial_policy_states(policy: FlatParamsPolicy, n: int, compute_dtype):
    """The width-``n`` batch of initial recurrent states (``None`` for a
    stateless policy), in the compute dtype (recurrent state lives in compute
    dtype) — the one definition of a lane's fresh policy state, shared by
    rollout init and the refill engine."""
    proto = policy.initial_state()
    if proto is None:
        return None
    def lanes(leaf):
        # counters and positions stay integers
        if compute_dtype is not None and jnp.issubdtype(leaf.dtype, jnp.floating):
            leaf = leaf.astype(compute_dtype)
        return jnp.broadcast_to(leaf, (n,) + leaf.shape)

    return jax.tree_util.tree_map(lanes, proto)


def _env_state_take(env, states, idx):
    """Gather lanes ``idx`` out of a batched env state (lane compaction)."""
    if getattr(env, "batched_native", False):
        take = getattr(env, "batch_take", None)
        if take is None:
            raise NotImplementedError(
                f"{type(env).__name__} is batched_native but does not implement"
                " batch_take(states, idx); lane compaction needs it"
            )
        return take(states, idx)
    return jax.tree_util.tree_map(lambda x: x[idx], states)


@_in_scope("obs_norm")
def _stats_psum_merge(old: CollectedStats, new: CollectedStats, axis_name: str):
    """Every shard absorbs every shard's stat delta: the per-step form of the
    end-of-rollout delta merge (the accumulators are linear, so delta-psum
    composes exactly)."""
    delta = jax.tree_util.tree_map(lambda n, o: n - o, new, old)
    return jax.tree_util.tree_map(
        lambda o, d: o + jax.lax.psum(d, axis_name), old, delta
    )


@_in_scope("rollout_edges")
def _rollout_init(
    env,
    policy: FlatParamsPolicy,
    params_batch: jnp.ndarray,
    key,
    stats: CollectedStats,
    *,
    observation_normalization: bool,
    compute_dtype,
    lane_ids=None,
    stats_sync_axis=None,
    num_valid=None,
    pad_episodes_done: int = 0,
    groups=None,
    num_groups: int = 1,
):
    """Build the initial carry (full width) and the compute-dtype params.

    Each lane carries its OWN PRNG chain, seeded by ``fold_in(key,
    lane_id)`` — realized randomness is therefore a per-lane property,
    independent of the working width (compaction), the batch composition,
    and the mesh topology (a sharded evaluation passing global ``lane_ids``
    reproduces the unsharded one bit-for-bit).

    ``num_valid`` marks lanes with ``lane_ids >= num_valid`` as PADDING
    (``parallel.make_sharded_rollout_evaluator`` pads an indivisible
    popsize to the next mesh multiple): they start inactive with
    ``episodes_done = pad_episodes_done`` (``num_episodes`` in episodes
    mode, so the exit condition sees them as finished) and are excluded
    from the initial statistics mask — padding never earns score credit
    or counter/telemetry credit."""
    n = _params_popsize(params_batch)
    params_batch = _params_cast(params_batch, compute_dtype)

    if lane_ids is None:
        lane_ids = jnp.arange(n, dtype=jnp.int32)
    valid = (
        jnp.ones(n, dtype=bool)
        if num_valid is None
        else lane_ids < jnp.int32(num_valid)
    )
    lane_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(lane_ids)
    pair = jax.vmap(lambda k: jax.random.split(k, 2))(lane_keys)
    lane_keys, reset_keys = pair[:, 0], pair[:, 1]
    env_states, obs = _env_reset(env, reset_keys)
    if observation_normalization:
        # the initial reset observations are fed to the policy at t=0, so
        # they belong in the normalization statistics (the reference updates
        # stats on every observation the policy consumes)
        new_stats = stats_update(stats, obs, mask=valid)
        if stats_sync_axis is not None:
            new_stats = _stats_psum_merge(stats, new_stats, stats_sync_axis)
        stats = new_stats

    policy_states = _initial_policy_states(policy, n, compute_dtype)

    if groups is not None and num_groups > 1:
        # lane i charges group groups[i]; the lane_width column is set once
        # here (physical lanes per group — padding lanes included, matching
        # the v1 global's physical lane_width), everything else accumulates
        # per lane in the stepping loop and folds at the boundary
        lane_groups = jnp.asarray(groups, dtype=jnp.int32)
        group_counts = _init_group_counts(lane_groups, num_groups)
        lane_steps0 = jnp.zeros(n, dtype=jnp.int32)
        lane_episodes0 = jnp.zeros(n, dtype=jnp.int32)
    else:
        lane_groups = _empty_lane_groups()
        group_counts = _empty_group_counts()
        lane_steps0 = _empty_lane_groups()
        lane_episodes0 = _empty_lane_groups()

    episodes_done0 = (
        jnp.zeros(n, dtype=jnp.int32)
        if num_valid is None
        else jnp.where(valid, 0, jnp.int32(pad_episodes_done))
    )
    carry = RolloutCarry(
        env_states=env_states,
        obs=obs,
        policy_states=policy_states,
        scores=jnp.zeros(n),
        episodes_done=episodes_done0,
        steps_in_episode=jnp.zeros(n, dtype=jnp.int32),
        active=valid,
        stats=stats,
        key=lane_keys,  # (n,) per-lane PRNG chains
        total_steps=jnp.zeros((), dtype=jnp.int32),
        t_global=jnp.zeros((), dtype=jnp.int32),
        capacity=jnp.zeros((), dtype=jnp.int32),
        lane_groups=lane_groups,
        group_counts=group_counts,
        lane_steps=lane_steps0,
        lane_episodes=lane_episodes0,
    )
    return carry, params_batch


# Bounded caches (ADVICE r3): these are keyed on env/policy INSTANCES, so an
# unbounded cache would pin every env/policy ever used (plus their jitted
# closures) for the process lifetime — and unlike jit caches they are not
# freed by jax.clear_caches(). 64 entries comfortably covers the handful of
# long-lived env/policy/config combos a training process realistically holds;
# eviction merely costs a retrace on the next use of an evicted combo.
_ENGINE_CACHE_SIZE = 64


@functools.lru_cache(maxsize=_ENGINE_CACHE_SIZE)
def _make_step(
    env,
    policy: FlatParamsPolicy,
    *,
    num_episodes: int,
    max_t: int,
    observation_normalization: bool,
    alive_bonus_schedule,
    decrease_rewards_by,
    action_noise_stdev,
    compute_dtype,
    budget_mode: bool,
    stats_sync_axis=None,
    collect_telemetry: bool = True,
    masked_width: bool = False,
    num_groups: int = 1,
):
    """One masked control step of the whole population, as a pure function
    ``step(params_batch, carry) -> carry``. Width is taken from the carry, so
    the same step serves the monolithic loop and every compacted width.

    ``collect_telemetry``: accumulate the observability counters (one extra
    int32 scalar add per step — the ``capacity`` carry); False freezes the
    telemetry fields so an A/B against a telemetry-free program is possible.

    ``num_groups > 1``: additionally ``segment_sum`` the per-lane
    env-step/episode/capacity increments into the carry's per-group counter
    block every step (ISSUE 15) — one tiny (n -> G) reduction, still zero
    host syncs.

    ``stats_sync_axis``: inside a ``shard_map`` over that axis, psum-merge
    the per-step observation-statistic deltas so every shard normalizes by
    the MESH-GLOBAL cohort — ``obs_norm_sync="step"`` semantics. The caller
    must guarantee every shard runs the same number of steps (mesh-global
    loop conditions), or the collective deadlocks.

    When no lane can ever need a mid-rollout reset (episodes mode with
    ``num_episodes == 1``), the per-step fresh ``env_reset`` — a per-lane key
    split, reset noise and a full observation build — is skipped entirely and
    finished lanes are *frozen* at their last pre-terminal state instead.
    Frozen lanes keep stepping (masked) from a bounded, healthy state, so no
    numerical blow-up can leak NaN into the masked statistics.
    """
    auto_reset = budget_mode or num_episodes > 1

    def step(params_batch, ctx, c: RolloutCarry) -> RolloutCarry:
        n = c.active.shape[0]
        with scope("contract"):
            # advance each lane's own PRNG chain (only when this config
            # consumes randomness — otherwise the chains stay untouched and
            # XLA drops the splits entirely)
            if auto_reset or action_noise_stdev is not None:
                triple = jax.vmap(lambda k: jax.random.split(k, 3))(c.key)
                lane_keys, noise_keys, reset_keys = triple[:, 0], triple[:, 1], triple[:, 2]
            else:
                lane_keys, noise_keys, reset_keys = c.key, None, None

        policy_in = c.obs
        if observation_normalization:
            with scope("obs_norm"):
                policy_in = stats_normalize(c.stats, c.obs)
        raw, new_policy_states = _forward_in_compute_dtype(
            lambda obs, states: _batched_forward(policy, params_batch, ctx, obs, states),
            policy_in,
            c.policy_states,
            compute_dtype,
        )
        new_env_states, new_obs, rewards, dones = _env_step(
            env, c.env_states, raw, noise_keys, action_noise_stdev
        )

        with scope("contract"):
            steps_in_episode = c.steps_in_episode + 1
            # guaranteed truncation at max_t (gym TimeLimit semantics): even an
            # env that never emits done internally ends its episode here, so
            # per-episode score averaging stays well-defined
            dones = dones | (steps_in_episode >= max_t)

            if decrease_rewards_by is not None:
                rewards = rewards - decrease_rewards_by
            if alive_bonus_schedule is not None:
                rewards = rewards + alive_bonus_for_step(
                    steps_in_episode, alive_bonus_schedule
                ) * (~dones)

            active_f = c.active
            scores = c.scores + jnp.where(active_f, rewards, 0.0)

            finished = dones & active_f
            episodes_done = c.episodes_done + finished.astype(jnp.int32)

        if auto_reset:
            # auto-reset the envs that finished an episode (reset keys come
            # from the per-lane chains: width-independent)
            fresh_states, fresh_obs = _env_reset(env, reset_keys)
            env_states_next = _env_state_select(
                env, finished, fresh_states, new_env_states
            )
            with scope("env_reset"):
                obs_next = _lane_select(finished, fresh_obs, new_obs)
            with scope("contract"):
                steps_in_episode = jnp.where(finished, 0, steps_in_episode)
                if new_policy_states is not None:
                    new_policy_states = policy.module.reset_state(
                        new_policy_states, finished
                    )
                if budget_mode:
                    active = active_f  # every lane runs its full budget
                else:
                    active = episodes_done < num_episodes
        else:
            # freeze finished lanes at their last pre-terminal state: they
            # never run another episode, so no fresh reset is ever needed
            with scope("contract"):
                active = episodes_done < num_episodes
                steps_in_episode = jnp.where(active, steps_in_episode, 0)
            env_states_next = _env_state_select(
                env, active, new_env_states, c.env_states
            )
            with scope("env_reset"):
                obs_next = _lane_select(active, new_obs, c.obs)

        with scope("contract"):
            if budget_mode and not masked_width:
                total_steps = c.total_steps + n
            else:
                # episodes modes, and budget under padding (``masked_width``:
                # some lanes are permanently-inactive pad rows whose slots must
                # not count as genuine interactions)
                total_steps = c.total_steps + jnp.sum(active_f.astype(jnp.int32))
        # normalization statistics come from the observations the policy will
        # actually consume next step: post-reset-selection obs, masked by the
        # envs still running (ADVICE r1: not the pre-reset terminal obs)
        new_stats = c.stats
        if observation_normalization:
            with scope("obs_norm"):
                new_stats = stats_update(c.stats, obs_next, mask=active)
                if stats_sync_axis is not None:
                    new_stats = _stats_psum_merge(c.stats, new_stats, stats_sync_axis)

        with scope("contract"):
            if collect_telemetry and num_groups > 1:
                # per-group accounting: lane i charges its env-step (if active)
                # and episode completion (if it fired this step) to PER-LANE
                # accumulators — two fused elementwise adds; the segment_sum
                # into group_counts happens once at the loop boundary
                # (_fold_lane_counts), so the per-step cost is G-independent.
                # Padding lanes never activate or fire, so their only charge is
                # capacity (t_global at fold time) — the same semantics as the
                # v1 global scalars.
                lane_steps = c.lane_steps + active_f.astype(jnp.int32)
                lane_episodes = c.lane_episodes + finished.astype(jnp.int32)
            else:
                lane_steps = c.lane_steps
                lane_episodes = c.lane_episodes

            return RolloutCarry(
                env_states=env_states_next,
                obs=obs_next,
                policy_states=new_policy_states,
                scores=scores,
                episodes_done=episodes_done,
                steps_in_episode=steps_in_episode,
                active=active,
                stats=new_stats,
                key=lane_keys,
                total_steps=total_steps,
                t_global=c.t_global + 1,
                # telemetry: every iteration executes `n` lane-step slots,
                # whether the lanes are live or idling masked
                capacity=(c.capacity + n) if collect_telemetry else c.capacity,
                lane_groups=c.lane_groups,
                group_counts=c.group_counts,
                lane_steps=lane_steps,
                lane_episodes=lane_episodes,
            )

    return step


@partial(
    jax.jit,
    static_argnames=(
        "env",
        "policy",
        "num_episodes",
        "episode_length",
        "observation_normalization",
        "alive_bonus_schedule",
        "decrease_rewards_by",
        "action_noise_stdev",
        "compute_dtype",
        "eval_mode",
        "refill_width",
        "refill_period",
        "seed_stride",
        "telemetry",
        "health",
        "num_valid",
        "num_groups",
        "trunk_block",
        "nonfinite_quarantine",
        "nonfinite_penalty",
    ),
)
def run_vectorized_rollout(
    env,
    policy: FlatParamsPolicy,
    params_batch: jnp.ndarray,
    key,
    stats: CollectedStats,
    *,
    num_episodes: int = 1,
    episode_length: Optional[int] = None,
    observation_normalization: bool = False,
    alive_bonus_schedule: Optional[tuple] = None,
    decrease_rewards_by: Optional[float] = None,
    action_noise_stdev: Optional[float] = None,
    compute_dtype=None,
    eval_mode: str = "episodes",
    lane_ids=None,
    solution_keys=None,
    refill_width: Optional[int] = None,
    refill_period: int = 1,
    seed_stride: Optional[int] = None,
    telemetry: bool = True,
    health: bool = True,
    num_valid: Optional[int] = None,
    groups=None,
    num_groups: int = 1,
    trunk_block: int = 0,
    nonfinite_quarantine: bool = False,
    nonfinite_penalty: Optional[float] = None,
) -> RolloutResult:
    """Evaluate ``N`` policies on ``N`` environments, fully on-device.

    ``nonfinite_quarantine`` (default off at this primitive layer; ``VecNE``
    turns it on) replaces non-finite final scores with the batch's worst
    FINITE score — or the fixed ``nonfinite_penalty`` when given — inside
    the same jitted program, and counts the quarantined solutions in the
    telemetry's ``nonfinite`` slot (per group at G > 1), so one diverged
    rollout cannot NaN-poison ranking (docs/resilience.md). Under GSPMD
    the worst-finite reduction is global by construction.

    ``trunk_block`` (trunk-delta populations only): static lane-block size
    of the shared-trunk forward — the population batch is chunked into
    blocks of that many lanes per trunk GEMM (``lax.map``), bounding the
    activation working set. 0 (default) runs one full-width GEMM. Tuned by
    the autotuner's ``policy`` knob group; a no-op for dense/low-rank
    populations.

    ``telemetry`` (default on): accumulate the zero-sync observability
    counters in the loop carry and return them packed in
    ``RolloutResult.telemetry`` — a ``(num_groups,
    GROUP_TELEMETRY_WIDTH)`` int32 matrix produced by the same jitted
    program as the scores (zero extra dispatches; see
    ``observability.devicemetrics``). ``telemetry=False`` compiles the
    accumulator-free program — the A/B baseline for measuring that the
    accumulators cost nothing.

    ``health`` (default on, only meaningful with ``telemetry``): append the
    float32 search-health plane — per-group ``count, sum, sumsq, min, max``
    of the final per-solution mean scores, bit-cast into ``HEALTH_WIDTH``
    extra int32 columns — computed ONCE at program end from the
    post-quarantine scores (no loop-carry cost). ``health=False`` keeps
    the pre-v4 ``(G, GROUP_TELEMETRY_WIDTH)`` wire byte-compatible. The
    GSPMD evaluators pass ``health=False`` and append one block computed on
    replicated scores themselves (see ``parallel/evaluate.py``), which keeps
    the float32 statistics bit-identical across mesh shapes.

    ``groups`` / ``num_groups`` (ISSUE 15): per-group telemetry. ``groups``
    is an ``(N,)`` int32 array of group ids in ``[0, num_groups)`` — one per
    SOLUTION — and every telemetry slot is ``segment_sum``-accumulated per
    group inside the same loop carry (the substrate for multi-tenant
    occupancy/fairness accounting and per-island counters). The column sums
    of the per-group matrix equal the single-group global numbers exactly.
    With ``num_groups == 1`` (default) no group state is carried at all. In
    ``episodes_refill`` mode the telemetry additionally carries per-group
    queue-wait histograms (log-spaced buckets; see
    ``devicemetrics.QUEUE_WAIT_BUCKET_EDGES``) fed by each refilled item's
    idle-to-refill wait.

    ``solution_keys`` (``episodes_refill`` only): an optional TRACED ``(N,)``
    typed-key array of per-solution BASE keys. When given, the (solution,
    episode) item seeds fold into ``solution_keys[s]`` instead of the global
    ``key`` — so solutions owned by different requests/tenants packed into
    one program each reproduce the realized randomness of their owner's own
    standalone evaluation (``fold_in(solution_keys[s], lane_ids[s])``
    equals the standalone engine's ``fold_in(key_s, i)`` when the packer
    sets ``lane_ids`` to owner-local indices). Being traced, per-dispatch
    key/owner churn never retraces (the multi-tenant serving substrate,
    docs/serving.md).

    Per-group observation normalization (``episodes_refill`` +
    ``groups``/``num_groups`` only): passing a STACKED stats pytree —
    ``count (G,)``, ``sum (G, n)``, ``sum_of_squares (G, n)``, e.g.
    ``runningnorm.group_stats_init`` — switches every stat touch to the
    per-group form: each lane normalizes by ITS group's slot and updates
    only that slot (per-tenant obs-norm isolation). The stacked form is
    detected by the count's rank, so the same traced signature serves both.

    Randomness is a PER-LANE property: lane ``i``'s PRNG chain is seeded by
    ``fold_in(key, lane_ids[i])`` (default ``lane_ids = arange(N)``) and
    advances with that lane, so realized randomness does not depend on the
    working width, the batch composition, or the mesh topology. A caller
    that evaluates a slice of a population and passes the slice's GLOBAL
    lane ids (and the same ``key``) reproduces those lanes of the whole
    evaluation bit-for-bit — except under online observation normalization,
    where each lane is normalized by its cohort's running statistics and a
    slice is a different cohort.

    The logic mirrors ``VecGymNE._evaluate_subbatch``
    (``vecgymne.py:744-916``): one sub-environment per solution, lockstep
    stepping with an activity mask, auto-reset until each env has finished
    ``num_episodes`` episodes, masked running-norm updates, alive-bonus and
    reward adjustments — but compiled into a single ``lax.while_loop``.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) casts the policy parameters and
    its inputs for the forward pass — the MXU fast path; ES is robust to
    low-precision fitness since ranking is scale-free. Env dynamics, rewards
    and statistics stay in f32.

    ``eval_mode`` selects the evaluation contract:

    - ``"episodes"`` (the reference's ``VecGymNE`` semantics): each lane runs
      exactly ``num_episodes`` episodes, then idles (masked) until every lane
      is finished. The ``lax.while_loop`` exits as soon as all lanes are done,
      but in the worst case the whole population waits on its longest
      survivor — finished lanes burn compute producing nothing. For the
      host-orchestrated variant that reclaims that compute, see
      ``run_vectorized_rollout_compacting``.
    - ``"budget"``: each lane consumes a fixed interaction budget of
      ``num_episodes * max_episode_steps`` steps, auto-resetting whenever an
      episode ends; the score is the average episodic return over the budget
      (completed episodes plus the fractional trailing episode). Every lane
      is active on every step, so the whole program is one fixed-length
      ``lax.fori_loop`` and 100% of computed env steps are genuine, counted
      interactions — on accelerators this is the throughput-optimal contract
      (it also gives low-variance fitness: constant compute per solution, no
      survivorship skew). This is the flagship benchmark path.
    - ``"episodes_refill"``: the same contract as ``"episodes"`` (each
      solution's score is the mean return of exactly ``num_episodes``
      episodes) evaluated by the work-conserving lane-refill scheduler: a
      fixed width ``refill_width`` of lanes is kept saturated by refilling
      each finishing lane with the next pending (solution, episode) item
      from an on-device queue — continuous batching for rollouts. One jitted
      program (usable inside jit/shard_map, unlike the compacting runner),
      no padding to the longest survivor. ``refill_period`` refills only
      every that-many steps (finished lanes wait masked in between),
      amortizing the refill gather/reset; ``seed_stride`` must be the GLOBAL
      popsize on a sharded caller so (solution, episode) seeds stay unique
      across shards. At ``num_episodes=1`` without observation
      normalization the scores are bit-identical to
      ``eval_mode="episodes"`` for the same ``key`` (matched per-lane
      seeding); at ``num_episodes > 1`` each episode runs on its own PRNG
      chain, so scores are distribution-equivalent, not bit-equal. With
      observation normalization ON the refill schedule itself changes the
      running statistics each lane sees mid-rollout (a lane refilled late
      is normalized by more history than its monolithic counterpart), so
      scores differ semantically from ``"episodes"`` — schedule-dependent
      cohort statistics, exactly like sharding under
      ``obs_norm_sync="cohort"``.
    """
    if eval_mode not in ("episodes", "budget", "episodes_refill"):
        raise ValueError(
            "eval_mode must be 'episodes', 'budget' or 'episodes_refill',"
            f" got {eval_mode!r}"
        )
    n_total = _params_popsize(params_batch)
    num_groups = int(num_groups)
    if num_groups > 1 and groups is None:
        raise ValueError("num_groups > 1 requires a groups array of per-solution ids")
    collect_groups = telemetry and num_groups > 1
    if not collect_groups:
        groups, num_groups = None, 1
    if num_valid is not None:
        num_valid = int(num_valid)
        if not (1 <= num_valid <= n_total):
            raise ValueError(
                f"num_valid={num_valid} must be in [1, popsize={n_total}]"
            )
        if num_valid == n_total:
            num_valid = None  # no padding: compile the unmasked program
    max_t = env.max_episode_steps if env.max_episode_steps is not None else 1000
    if episode_length is not None:
        max_t = min(max_t, int(episode_length))
    stacked_stats = stats is not None and getattr(stats.count, "ndim", 0) == 1
    if (solution_keys is not None or stacked_stats) and eval_mode != "episodes_refill":
        raise ValueError(
            "solution_keys and stacked (per-group) stats are"
            " episodes_refill-only features (the serving substrate),"
            f" got eval_mode={eval_mode!r}"
        )
    if eval_mode == "episodes_refill":
        return _run_refill(
            env,
            policy,
            params_batch,
            key,
            stats,
            num_episodes=int(num_episodes),
            max_t=max_t,
            observation_normalization=observation_normalization,
            alive_bonus_schedule=alive_bonus_schedule,
            decrease_rewards_by=decrease_rewards_by,
            action_noise_stdev=action_noise_stdev,
            compute_dtype=compute_dtype,
            lane_ids=lane_ids,
            solution_keys=solution_keys,
            refill_width=refill_width,
            refill_period=refill_period,
            seed_stride=seed_stride,
            telemetry=telemetry,
            health=health,
            num_valid=num_valid,
            groups=groups,
            num_groups=num_groups,
            trunk_block=trunk_block,
            nonfinite_quarantine=nonfinite_quarantine,
            nonfinite_penalty=nonfinite_penalty,
        )
    hard_cap = max_t * int(num_episodes) + 1
    budget_mode = eval_mode == "budget"

    carry, params_batch = _rollout_init(
        env,
        policy,
        params_batch,
        key,
        stats,
        observation_normalization=observation_normalization,
        compute_dtype=compute_dtype,
        lane_ids=lane_ids,
        num_valid=num_valid,
        # episodes-mode padding lanes must look already-finished to the
        # exit condition; budget-mode lanes never finish (masked inactive),
        # so their episodes_done stays 0 and total_episodes needs no fixup
        pad_episodes_done=0 if budget_mode else int(num_episodes),
        groups=groups,
        num_groups=num_groups,
    )
    step = _make_step(
        env,
        policy,
        num_episodes=int(num_episodes),
        max_t=max_t,
        observation_normalization=observation_normalization,
        alive_bonus_schedule=alive_bonus_schedule,
        decrease_rewards_by=decrease_rewards_by,
        action_noise_stdev=action_noise_stdev,
        compute_dtype=compute_dtype,
        budget_mode=budget_mode,
        collect_telemetry=telemetry,
        masked_width=num_valid is not None,
        num_groups=num_groups,
    )

    ctx = _forward_ctx(policy, params_batch, trunk_block=int(trunk_block))
    if budget_mode:
        final = jax.lax.fori_loop(
            0,
            max_t * int(num_episodes),
            lambda _, c: step(params_batch, ctx, c),
            carry,
        )
    else:

        @_in_scope("contract")
        def cond(c: RolloutCarry):
            return jnp.any(c.active) & (c.t_global < hard_cap)

        final = jax.lax.while_loop(cond, lambda c: step(params_batch, ctx, c), carry)
    # everything below runs once per program, after the loop
    with scope("rollout_edges"):
        if budget_mode:
            # average episodic return over the budget: completed episodes plus
            # the fractional trailing one (exactly the episodic mean whenever
            # the budget lands on an episode boundary)
            episodes_frac = (
                final.episodes_done + final.steps_in_episode.astype(jnp.float32) / max_t
            )
            mean_scores = final.scores / jnp.maximum(episodes_frac, 1.0 / max_t)
        else:
            mean_scores = final.scores / jnp.maximum(final.episodes_done, 1)
        nf_bad = None
        if nonfinite_quarantine:
            mean_scores, nf_bad = _quarantine_nonfinite(
                mean_scores,
                valid_mask=(
                    None
                    if num_valid is None
                    else jnp.arange(n_total, dtype=jnp.int32) < num_valid
                ),
                penalty=nonfinite_penalty,
            )
        total_episodes = jnp.sum(final.episodes_done)
        if num_valid is not None and not budget_mode:
            # padding lanes were initialized as already-finished; subtract their
            # synthetic episodes_done so counters/telemetry report genuine work
            total_episodes = total_episodes - jnp.int32(
                (n_total - num_valid) * int(num_episodes)
            )
        if not telemetry:
            eval_telemetry = None
        elif collect_groups:
            # the per-group counter block IS the telemetry (no histograms in the
            # non-refill engines: nothing queues, nothing waits); the per-lane
            # accumulators fold here, once, after the loop
            group_counts = _fold_lane_counts(
                final.group_counts,
                final.lane_steps,
                final.lane_episodes,
                final.lane_groups,
                final.t_global,
                num_groups,
            )
            if nf_bad is not None:
                # lanes == solutions in these engines, so the per-lane group ids
                # charge the quarantine counts to the right rows
                group_counts = _nonfinite_group_counts(
                    group_counts, nf_bad, final.lane_groups, num_groups
                )
            eval_telemetry = pack_group_telemetry(group_counts)
        else:
            eval_telemetry = pack_group_telemetry(
                pack_eval_telemetry(
                    env_steps=final.total_steps,
                    episodes=total_episodes,
                    capacity=final.capacity,
                    lane_width=final.active.shape[0],
                    nonfinite=(
                        0 if nf_bad is None else jnp.sum(nf_bad.astype(jnp.int32))
                    ),
                )[None]
            )
        if eval_telemetry is not None and health:
            eval_telemetry = _health_telemetry(
                eval_telemetry,
                mean_scores,
                final.lane_groups if collect_groups else None,
                num_groups,
                num_valid,
            )
        report = getattr(policy.module, "state_report", None)
        return RolloutResult(
            scores=mean_scores,
            stats=final.stats,
            total_steps=final.total_steps,
            total_episodes=total_episodes,
            telemetry=eval_telemetry,
            policy_report=(
                report(final.policy_states) if telemetry and report is not None else None
            ),
        )


# --------------------------- lane-compacting runner ---------------------------


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


# ---------------------- work-conserving lane-refill engine ----------------------
# Continuous batching for the episodes contract: the whole evaluation is ONE
# lax.while_loop over a fixed width W, kept saturated by refilling finished
# lanes from an on-device pending-work queue. Unlike the compacting runner
# (host-orchestrated chunks, per-width re-traces) this is a single jitted
# program, usable inside jit/shard_map, and never pads a batch to its
# longest survivor — the large-win regime is exactly the flagship
# popsize-10k shape with skewed episode-death times.


class RefillCarry(NamedTuple):
    """Loop state of the refill engine. ``lane_*`` leaves are per-lane
    (width ``W``); ``scores_buf``/``eps_buf`` are per-SOLUTION buffers
    (length ``N``) fed by segment reduction; ``next_item`` is the head of the
    pending-work queue (items are (solution, episode) pairs, encoded
    ``item = episode * N + solution``)."""

    env_states: Any
    obs: jnp.ndarray
    policy_states: Any
    # what the forward reads per lane: the width-W rows of the unravelled
    # population (a per-layer tree, leaves (W, out, in), (W, out), ...) for a
    # dense population, or (W, k) coefficients for the factored forms. Never
    # flat (W, L) rows: those would be cut into layers again in every step
    lane_params: Any
    lane_sol: jnp.ndarray  # (W,) local solution index each lane is running
    lane_score: jnp.ndarray  # (W,) return of the lane's CURRENT episode
    steps_in_episode: jnp.ndarray
    active: jnp.ndarray
    scores_buf: jnp.ndarray  # (N,) summed episodic returns per solution
    eps_buf: jnp.ndarray  # (N,) episodes credited per solution
    next_item: jnp.ndarray  # scalar int32 queue head
    stats: CollectedStats
    key: Any  # (W,) per-lane PRNG chains
    total_steps: jnp.ndarray
    t_global: jnp.ndarray
    # telemetry accumulators (observability.devicemetrics): lane-step slots
    # executed, and lane-steps spent idle while pending work existed (the
    # refill-period / drain-ordering wait — starvation accounting). Frozen at
    # zero when the engine runs with telemetry off.
    capacity: jnp.ndarray
    wait_sum: jnp.ndarray
    # queue-wait histogramming (ISSUE 15): idle_since stamps the loop step
    # at which each lane's episode finished; when a refill reuses the lane,
    # (now - stamp) is the item's wait, bucketed into the (G, B) log-spaced
    # histogram `hist`. lane_groups/group_counts mirror RolloutCarry's
    # per-group accounting (empty sentinels at num_groups == 1); with
    # telemetry off idle_since/hist are empty sentinels too.
    idle_since: jnp.ndarray
    hist: jnp.ndarray
    lane_groups: jnp.ndarray
    group_counts: jnp.ndarray


def _default_refill_width(total_items: int) -> int:
    """W defaults to ~1/8 of the work-list (pow2, floor 128): small enough
    that the queue keeps lanes saturated until near the end, large enough to
    amortize per-step fixed costs."""
    return min(total_items, max(128, _pow2_at_least(max(1, total_items // 8))))


def _refill_forward_setup(policy, params_batch, trunk_block: int = 0):
    """Per-lane parameter storage + forward for the refill engine.

    The loop carries only the PER-LANE slice of the population, so a refill
    gathers O(W x row), never the whole population. Returns ``(store,
    forward)``: ``store`` is the gather source, a pytree whose leaves have a
    leading axis N, and ``forward(lane_params, obs, states)`` runs the
    policy at width W on ``lane_params``, the same pytree at width W
    (``_params_take(store, sol)``).

    - dense population: ``store`` is ``_forward_ctx``'s unravelled tree
      (leaves ``(N, out, in)``, ``(N, out)``, ...), built once; the loop
      gathers and carries per-layer blocks and never sees a flat row;
    - factored forms: ``store`` is the ``(N, k)`` coefficients; the shared
      center/basis/factors stay loop-invariant closures."""
    if isinstance(params_batch, TrunkDeltaParamsBatch):
        from .lowrank import (
            _apply_trunk_delta,
            _apply_trunk_delta_blocked,
            prepare_trunk_delta,
            trunk_delta_supported,
        )

        if trunk_delta_supported(policy.module):
            prepared = prepare_trunk_delta(policy, params_batch)
            blk = int(trunk_block)

            def forward(lane_coeffs, obs, states):
                w = obs.shape[0]
                if blk > 0 and w > blk and w % blk == 0:
                    return _apply_trunk_delta_blocked(
                        policy.module,
                        prepared.center_tree,
                        prepared.factors,
                        lane_coeffs,
                        obs,
                        states,
                        blk,
                    )
                return _apply_trunk_delta(
                    policy.module,
                    prepared.center_tree,
                    prepared.factors,
                    lane_coeffs,
                    obs,
                    states,
                )

        else:
            import warnings

            warnings.warn(
                "trunk-delta refill forward fell back to materializing dense "
                f"per-lane parameter rows (W, {params_batch.center.shape[-1]}) "
                f"every step: {type(policy.module).__name__} has no "
                "structured trunk-delta path (supported: Sequential stacks "
                "of Linear/Bias/RNN/LSTM/parameterless layers)",
                stacklevel=3,
            )

            def forward(lane_coeffs, obs, states):
                dense = params_batch.materialize_rows(lane_coeffs)
                return _dense_tree_forward(
                    policy, jax.vmap(policy.unravel)(dense), obs, states
                )

        return params_batch.coeffs, forward
    if isinstance(params_batch, LowRankParamsBatch):
        from .lowrank import _apply_lowrank, lowrank_supported, prepare_lowrank

        if lowrank_supported(policy.module):
            prepared = prepare_lowrank(policy, params_batch)

            def forward(lane_coeffs, obs, states):
                return _apply_lowrank(
                    policy.module,
                    prepared.center_tree,
                    prepared.basis_tree,
                    lane_coeffs,
                    obs,
                    states,
                )

        else:
            import warnings

            # the same LOUD-fallback contract as net/lowrank.py (VERDICT r3
            # #3): the caller chose the factored representation to avoid
            # dense parameter rows, and here they get rebuilt every step
            warnings.warn(
                "low-rank refill forward fell back to materializing dense "
                f"per-lane parameter rows (W, {params_batch.center.shape[-1]}) "
                f"every step: {type(policy.module).__name__} has no "
                "structured low-rank path (supported: Sequential stacks of "
                "Linear/Bias/RNN/LSTM/parameterless layers)",
                stacklevel=3,
            )

            def forward(lane_coeffs, obs, states):
                dense = params_batch.materialize_rows(lane_coeffs)
                return _dense_tree_forward(
                    policy, jax.vmap(policy.unravel)(dense), obs, states
                )

        return params_batch.coeffs, forward

    return _forward_ctx(policy, params_batch), partial(_dense_tree_forward, policy)


def _run_refill(
    env,
    policy: FlatParamsPolicy,
    params_batch,
    key,
    stats: CollectedStats,
    *,
    num_episodes: int,
    max_t: int,
    observation_normalization: bool,
    alive_bonus_schedule,
    decrease_rewards_by,
    action_noise_stdev,
    compute_dtype,
    lane_ids,
    solution_keys,
    refill_width,
    refill_period,
    seed_stride,
    telemetry=True,
    health=True,
    num_valid=None,
    groups=None,
    num_groups=1,
    trunk_block=0,
    nonfinite_quarantine=False,
    nonfinite_penalty=None,
) -> RolloutResult:
    """The ``episodes_refill`` evaluation: exact ``episodes`` semantics (each
    solution is scored by the mean return of exactly ``num_episodes``
    episodes), evaluated work-conservingly at fixed width. Called inside the
    ``run_vectorized_rollout`` trace."""
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        # legacy raw uint32 keys (jax.random.PRNGKey): wrap into a typed key
        # array so the per-lane chains stay rank-1 and the refill step's
        # jnp.where lane-selects work on them. The monolithic engine only
        # ever vmaps fold_in/split over its keys, so it accepts either form
        # — and wrapping preserves the key bits, so matched-seed
        # bit-identity to it holds for legacy keys too.
        key = jax.random.wrap_key_data(key)
    if solution_keys is not None and not jnp.issubdtype(
        solution_keys.dtype, jax.dtypes.prng_key
    ):
        solution_keys = jax.random.wrap_key_data(solution_keys)
    n = _params_popsize(params_batch)
    # under width padding (num_valid < n) the work queue only enumerates the
    # genuine solutions: padding rows never receive items, so their eps_buf
    # stays 0 and their mean score is an exact 0.0
    nv = int(num_valid) if num_valid is not None else n
    total_items = nv * int(num_episodes)
    width = refill_width if refill_width is not None else _default_refill_width(total_items)
    width = int(min(max(1, int(width)), total_items))
    period = max(1, int(refill_period))
    stride = int(seed_stride) if seed_stride is not None else nv

    # what runs once, before the loop: casts, the forward's loop-invariant
    # context, the first width-many queue items with their resets and statistics
    with scope("rollout_edges"):
        params_batch = _params_cast(params_batch, compute_dtype)
        if lane_ids is None:
            lane_ids = jnp.arange(n, dtype=jnp.int32)
        store, forward = _refill_forward_setup(
            policy, params_batch, trunk_block=int(trunk_block)
        )

        collect_groups = bool(telemetry) and int(num_groups) > 1 and groups is not None
        groups_arr = (
            jnp.asarray(groups, dtype=jnp.int32) if collect_groups else None
        )

        # stacked (per-group) observation-normalization slots: detected by the
        # count's rank so the traced signature is the discriminator (an aval
        # rank change is a different program anyway — no new static argument)
        stacked_stats = stats is not None and getattr(stats.count, "ndim", 0) == 1
        if stacked_stats:
            if not collect_groups:
                raise ValueError(
                    "stacked (per-group) stats require telemetry plus a groups"
                    " array with num_groups > 1 — each slot needs lane->group"
                    " bindings to credit"
                )
            if stats.count.shape[0] != int(num_groups):
                raise ValueError(
                    f"stacked stats carry {stats.count.shape[0]} slots but"
                    f" num_groups={num_groups}"
                )

        def item_keys(items):
            """(chain, reset) PRNG keys + solution index of queue items. Episode
            ``e`` of solution ``s`` is seeded ``fold_in(key, lane_ids[s] +
            e * seed_stride)`` — at e=0 exactly the monolithic runner's per-lane
            seeding, so matched-seed refill reproduces plain ``episodes``
            bit-for-bit at ``num_episodes=1`` (observation normalization off —
            see the ``run_vectorized_rollout`` docstring), for ANY width,
            sharded or not (``seed_stride`` must be the GLOBAL popsize on a
            sharded caller). With ``solution_keys``, each item folds its seed
            into ITS solution's base key instead of the shared ``key`` — the
            per-tenant isolation form (see ``run_vectorized_rollout``)."""
            sol = items % nv
            ep = items // nv
            seeds = lane_ids[sol] + ep * jnp.int32(stride)
            if solution_keys is not None:
                ik = jax.vmap(jax.random.fold_in)(solution_keys[sol], seeds)
            else:
                ik = jax.vmap(lambda s: jax.random.fold_in(key, s))(seeds)
            pair = jax.vmap(lambda k: jax.random.split(k, 2))(ik)
            return pair[:, 0], pair[:, 1], sol

        items0 = jnp.arange(width, dtype=jnp.int32)
        chain0, reset0, sol0 = item_keys(items0)
        env_states0, obs0 = _env_reset(env, reset0)
        if observation_normalization:
            if stacked_stats:
                new_stats = group_stats_update(
                    stats, obs0, groups_arr[sol0], None, int(num_groups)
                )
            else:
                new_stats = stats_update(
                    stats, obs0, mask=jnp.ones(width, dtype=bool)
                )
            stats = new_stats

        policy_states0 = _initial_policy_states(policy, width, compute_dtype)

        if telemetry:
            # the histogram is carried even at G=1 (one row): tail queue wait is
            # a property of the refill schedule, not of multi-tenancy
            hist_groups = int(num_groups) if collect_groups else 1
            hist0 = jnp.zeros((hist_groups, QUEUE_WAIT_BUCKETS), dtype=jnp.int32)
            idle_since0 = jnp.zeros(width, dtype=jnp.int32)
        else:
            hist0 = jnp.zeros((0, QUEUE_WAIT_BUCKETS), dtype=jnp.int32)
            idle_since0 = jnp.zeros((0,), dtype=jnp.int32)
        if collect_groups:
            lane_groups0 = groups_arr[sol0]
            group_counts0 = _init_group_counts(lane_groups0, int(num_groups))
        else:
            lane_groups0 = _empty_lane_groups()
            group_counts0 = _empty_group_counts()

        carry = RefillCarry(
            env_states=env_states0,
            obs=obs0,
            policy_states=policy_states0,
            lane_params=_params_take(store, sol0),
            lane_sol=sol0,
            lane_score=jnp.zeros(width),
            steps_in_episode=jnp.zeros(width, dtype=jnp.int32),
            active=jnp.ones(width, dtype=bool),
            scores_buf=jnp.zeros(n, dtype=jnp.float32),
            eps_buf=jnp.zeros(n, dtype=jnp.int32),
            next_item=jnp.asarray(width, dtype=jnp.int32),
            stats=stats,
            key=chain0,
            total_steps=jnp.zeros((), dtype=jnp.int32),
            t_global=jnp.zeros((), dtype=jnp.int32),
            capacity=jnp.zeros((), dtype=jnp.int32),
            wait_sum=jnp.zeros((), dtype=jnp.int32),
            idle_since=idle_since0,
            hist=hist0,
            lane_groups=lane_groups0,
            group_counts=group_counts0,
        )

    def step(c: RefillCarry) -> RefillCarry:
        # the per-lane chains advance ONLY when this config draws action
        # noise (refill resets use the item's own key, not the lane chain) —
        # the same 3-way split discipline as the monolithic engine, so the
        # realized noise matches it draw-for-draw
        with scope("contract"):
            if action_noise_stdev is not None:
                triple = jax.vmap(lambda k: jax.random.split(k, 3))(c.key)
                lane_keys, noise_keys = triple[:, 0], triple[:, 1]
            else:
                lane_keys, noise_keys = c.key, None

        with scope("obs_norm"):
            if not observation_normalization:
                policy_in = c.obs
            elif stacked_stats:
                # per-group slots: each lane is normalized by ITS group's
                # running statistics (tenant isolation)
                policy_in = group_stats_normalize(c.stats, c.obs, c.lane_groups)
            else:
                policy_in = stats_normalize(c.stats, c.obs)
        raw, new_policy_states = _forward_in_compute_dtype(
            lambda obs, states: forward(c.lane_params, obs, states),
            policy_in,
            c.policy_states,
            compute_dtype,
        )
        new_env_states, new_obs, rewards, dones = _env_step(
            env, c.env_states, raw, noise_keys, action_noise_stdev
        )

        with scope("contract"):
            steps_in_episode = c.steps_in_episode + 1
            dones = dones | (steps_in_episode >= max_t)
            if decrease_rewards_by is not None:
                rewards = rewards - decrease_rewards_by
            if alive_bonus_schedule is not None:
                rewards = rewards + alive_bonus_for_step(
                    steps_in_episode, alive_bonus_schedule
                ) * (~dones)

            active_f = c.active
            lane_score = c.lane_score + jnp.where(active_f, rewards, 0.0)
            finished = dones & active_f
            # segment reduction: credit finished episodes to their solutions
            # (idle lanes contribute an exact 0.0 to whatever row they last ran)
            scores_buf = c.scores_buf.at[c.lane_sol].add(
                jnp.where(finished, lane_score, 0.0)
            )
            eps_buf = c.eps_buf.at[c.lane_sol].add(finished.astype(jnp.int32))
            total_steps = c.total_steps + jnp.sum(active_f.astype(jnp.int32))

            running = active_f & ~finished
        # freeze non-running lanes at their pre-step state (the monolithic
        # engine's no-reset trick: bounded states, no NaN leakage) and reset
        # their per-episode bookkeeping so a later refill starts clean.
        # Policy states return to the policy's INITIAL state — not zeros —
        # so a refilled episode starts exactly like _rollout_init's (the
        # bit-identity contract must hold for stateful policies whose
        # initial_state() is nonzero, not just the built-in RNN/LSTM zeros)
        env_states_base = _env_state_select(env, running, new_env_states, c.env_states)
        with scope("env_reset"):
            obs_base = _lane_select(running, new_obs, c.obs)
        with scope("contract"):
            steps_base = jnp.where(running, steps_in_episode, 0)
            lane_score = jnp.where(running, lane_score, 0.0)
            policy_states_base = (
                None
                if new_policy_states is None
                else jax.tree_util.tree_map(
                    lambda s, init: _lane_select(running, s, init),
                    new_policy_states,
                    policy_states0,
                )
            )

            idle = ~running
            gate = jnp.any(idle) & (c.next_item < total_items)
            if period > 1:
                gate = gate & (((c.t_global + 1) % period) == 0)
            # ranks among idle lanes -> candidate queue items; lanes beyond the
            # queue end stay idle (drained). Computed outside the cond so both
            # branches agree on `take`'s provenance.
            offs = jnp.cumsum(idle.astype(jnp.int32)) - 1
            cand = c.next_item + offs
            take = idle & (cand < total_items) & gate

        def do_refill(op):
            env_states, obs_cur, lane_params, lane_sol, keys = op
            with scope("contract"):  # the queue pop
                chain, reset_k, sol = item_keys(jnp.where(take, cand, 0))
            fresh_states, fresh_obs = _env_reset(env, reset_k)
            env_states = _env_state_select(env, take, fresh_states, env_states)
            with scope("env_reset"):
                obs_cur = _lane_select(take, fresh_obs, obs_cur)
            with scope("contract"):
                lane_sol = jnp.where(take, sol, lane_sol)
                lane_params = jax.tree_util.tree_map(
                    partial(_lane_select, take), _params_take(store, sol), lane_params
                )
                keys = jnp.where(take, chain, keys)
            return env_states, obs_cur, lane_params, lane_sol, keys

        def skip_refill(op):
            return op

        env_states_next, obs_next, lane_params_next, lane_sol_next, keys_next = (
            jax.lax.cond(
                gate,
                do_refill,
                skip_refill,
                (env_states_base, obs_base, c.lane_params, c.lane_sol, lane_keys),
            )
        )
        with scope("contract"):
            active = running | take
            next_item = c.next_item + jnp.sum(take.astype(jnp.int32))

            if telemetry:
                # telemetry: each iteration executes W lane-step slots; lanes
                # idle AFTER this step's refill while the queue still holds work
                # are waiting on the refill gate / drain order (the
                # starvation-accounting numerator)
                capacity = c.capacity + jnp.int32(width)
                wait_sum = c.wait_sum + jnp.where(
                    next_item < total_items,
                    jnp.sum((~active).astype(jnp.int32)),
                    0,
                )
                # queue-wait histogram: a lane's wait is refill step minus the
                # step its previous episode finished (same-step refill = 0 →
                # bucket 0). `take` is all-False when the cond gate is closed,
                # so updating outside the cond adds zeros — no divergence.
                # Lanes drained at queue end never refill → never counted.
                tcur = c.t_global + 1
                idle_since = jnp.where(finished, tcur, c.idle_since)
                waits = jnp.where(take, tcur - idle_since, 0)
                buckets = queue_wait_bucket_index(waits)
                take_i = take.astype(jnp.int32)
                if collect_groups:
                    sol_in = jnp.where(take, cand, 0) % nv
                    g_in = groups_arr[sol_in]
                    hist = c.hist.at[g_in, buckets].add(take_i)
                    lane_groups = jnp.where(take, g_in, c.lane_groups)
                    per_lane = jnp.stack(
                        [
                            active_f.astype(jnp.int32),
                            finished.astype(jnp.int32),
                            jnp.ones(width, dtype=jnp.int32),
                        ],
                        axis=1,
                    )
                    group_counts = c.group_counts.at[:, : _COL_LANE_WIDTH].add(
                        jax.ops.segment_sum(
                            per_lane, c.lane_groups, num_segments=num_groups
                        )
                    )
                    group_counts = group_counts.at[:, _COL_REFILL].add(
                        jax.ops.segment_sum(
                            take_i, g_in, num_segments=num_groups
                        )
                    )
                    # per-step gating matches the scalar wait_sum above (the
                    # UPDATED next_item), so the column sum equals it exactly
                    wait_lane = jnp.where(
                        next_item < total_items, (~active).astype(jnp.int32), 0
                    )
                    group_counts = group_counts.at[:, _COL_WAIT].add(
                        jax.ops.segment_sum(
                            wait_lane, lane_groups, num_segments=num_groups
                        )
                    )
                else:
                    hist = c.hist.at[0, buckets].add(take_i)
                    lane_groups = c.lane_groups
                    group_counts = c.group_counts
            else:
                capacity, wait_sum = c.capacity, c.wait_sum
                idle_since, hist = c.idle_since, c.hist
                lane_groups, group_counts = c.lane_groups, c.group_counts

        # obs-norm statistics count ONLY live-lane observations: the
        # post-refill obs each still-active lane will consume next step
        # (idle/drained lanes are masked out entirely). Stacked slots
        # credit the POST-refill lane groups: a fresh reset observation
        # belongs to the incoming item's group, not the departed one's.
        with scope("obs_norm"):
            if not observation_normalization:
                new_stats = c.stats
            elif stacked_stats:
                new_stats = group_stats_update(
                    c.stats, obs_next, lane_groups, active, num_groups
                )
            else:
                new_stats = stats_update(c.stats, obs_next, mask=active)

        with scope("contract"):
            return RefillCarry(
                env_states=env_states_next,
                obs=obs_next,
                policy_states=policy_states_base,
                lane_params=lane_params_next,
                lane_sol=lane_sol_next,
                lane_score=lane_score,
                steps_in_episode=steps_base,
                active=active,
                scores_buf=scores_buf,
                eps_buf=eps_buf,
                next_item=next_item,
                stats=new_stats,
                key=keys_next,
                total_steps=total_steps,
                t_global=c.t_global + 1,
                capacity=capacity,
                wait_sum=wait_sum,
                idle_since=idle_since,
                hist=hist,
                lane_groups=lane_groups,
                group_counts=group_counts,
            )

    # greedy-scheduling makespan bound (total work / W + longest item) plus
    # the refill-period waiting slack — a safety net, not the exit condition
    hard_cap = (
        (total_items * max_t) // width
        + max_t
        + period * (total_items // width + 1)
        + 2
    )

    @_in_scope("contract")
    def cond(c: RefillCarry):
        # pending queue items keep the loop alive even when every lane is
        # momentarily idle (all lanes can finish on a step whose refill gate
        # is closed by refill_period)
        any_work = jnp.any(c.active) | (c.next_item < total_items)
        return any_work & (c.t_global < hard_cap)

    final = jax.lax.while_loop(cond, step, carry)
    with scope("rollout_edges"):  # once per program, after the loop
        mean_scores = final.scores_buf / jnp.maximum(final.eps_buf, 1).astype(jnp.float32)
        nf_bad = None
        if nonfinite_quarantine:
            mean_scores, nf_bad = _quarantine_nonfinite(
                mean_scores,
                valid_mask=(
                    None
                    if num_valid is None
                    else jnp.arange(n, dtype=jnp.int32) < nv
                ),
                penalty=nonfinite_penalty,
            )
        total_episodes = jnp.sum(final.eps_buf)
        if not telemetry:
            eval_telemetry = None
        elif collect_groups:
            group_counts = final.group_counts
            if nf_bad is not None:
                # scores_buf is per SOLUTION here: charge each quarantined
                # solution's group directly off the per-solution id array
                group_counts = _nonfinite_group_counts(
                    group_counts, nf_bad, groups_arr, num_groups
                )
            eval_telemetry = pack_group_telemetry(group_counts, final.hist)
        else:
            eval_telemetry = pack_group_telemetry(
                pack_eval_telemetry(
                    env_steps=final.total_steps,
                    episodes=total_episodes,
                    capacity=final.capacity,
                    lane_width=width,
                    # items 0..width-1 seeded the lanes; everything past
                    # that entered through the refill gather
                    refill_events=final.next_item - jnp.int32(width),
                    queue_wait=final.wait_sum,
                    nonfinite=(
                        0 if nf_bad is None else jnp.sum(nf_bad.astype(jnp.int32))
                    ),
                )[None],
                final.hist,
            )
        if eval_telemetry is not None and health:
            eval_telemetry = _health_telemetry(
                eval_telemetry,
                mean_scores,
                groups_arr if collect_groups else None,
                num_groups,
                num_valid,
            )
        return RolloutResult(
            scores=mean_scores,
            stats=final.stats,
            total_steps=final.total_steps,
            total_episodes=total_episodes,
            telemetry=eval_telemetry,
        )


@functools.lru_cache(maxsize=_ENGINE_CACHE_SIZE)
def _compacting_fns(
    env,
    policy: FlatParamsPolicy,
    num_episodes: int,
    max_t: int,
    hard_cap: int,
    observation_normalization: bool,
    alive_bonus_schedule,
    decrease_rewards_by,
    action_noise_stdev,
    compute_dtype,
    stats_sync_axis=None,
    collect_telemetry=True,
    health=True,
    num_groups=1,
    nonfinite_quarantine=False,
    nonfinite_penalty=None,
    nonfinite_sync_axis=None,
):
    """Jitted building blocks of the compacting runner, cached per config so
    repeated calls (every generation) hit XLA's compile cache. ``health``
    appends the v4 search-health block in ``finalize_fn``; the sharded
    wrapper passes ``health=False`` and appends a mesh-global block itself
    (``_compacting_sharded_fns``) so the telemetry psum stays exact."""
    num_groups = int(num_groups)
    step = _make_step(
        env,
        policy,
        num_episodes=num_episodes,
        max_t=max_t,
        observation_normalization=observation_normalization,
        alive_bonus_schedule=alive_bonus_schedule,
        decrease_rewards_by=decrease_rewards_by,
        action_noise_stdev=action_noise_stdev,
        compute_dtype=compute_dtype,
        budget_mode=False,
        stats_sync_axis=stats_sync_axis,
        collect_telemetry=collect_telemetry,
        num_groups=num_groups,
    )

    @jax.jit
    def init_fn(params_batch, key, stats, lane_ids=None, groups=None):
        return _rollout_init(
            env,
            policy,
            params_batch,
            key,
            stats,
            observation_normalization=observation_normalization,
            compute_dtype=compute_dtype,
            lane_ids=lane_ids,
            stats_sync_axis=stats_sync_axis,
            groups=groups,
            num_groups=num_groups,
        )

    @partial(jax.jit, static_argnames=("num_steps",))
    def chunk_fn(params_batch, carry, num_steps: int):
        ctx = _forward_ctx(policy, params_batch)  # loop-invariant, per chunk

        @_in_scope("contract")
        def cond(s):
            i, c = s
            any_active = jnp.any(c.active)
            if stats_sync_axis is not None:
                # per-step collectives: every shard must run the same number
                # of iterations (see _make_step)
                any_active = (
                    jax.lax.psum(any_active.astype(jnp.int32), stats_sync_axis) > 0
                )
            return (i < num_steps) & any_active & (c.t_global < hard_cap)

        def body(s):
            i, c = s
            return i + 1, step(params_batch, ctx, c)

        _, out = jax.lax.while_loop(cond, body, (jnp.zeros((), jnp.int32), carry))
        with scope("rollout_edges"):
            return out, jnp.sum(out.active.astype(jnp.int32))

    @partial(jax.jit, static_argnames=("new_width",))
    def compact_fn(carry, params_batch, lane_ids, scores_buf, eps_buf, new_width: int):
        # flush every current lane's (final-so-far) score to the full-width
        # buffers, then gather the still-active lanes to the front
        scores_buf = scores_buf.at[lane_ids].set(carry.scores)
        eps_buf = eps_buf.at[lane_ids].set(carry.episodes_done)
        order = jnp.argsort(jnp.logical_not(carry.active))  # stable: active first
        sel = order[:new_width]
        if num_groups > 1:
            # the lanes dropped here leave the carry for good: fold their
            # per-lane accumulators into the group block now (their capacity
            # charge is t_global — present since t=0); survivors keep
            # accumulating and fold at finalize
            width = carry.active.shape[0]
            dropped = jnp.ones(width, bool).at[sel].set(False)
            group_counts = _fold_lane_counts(
                carry.group_counts,
                carry.lane_steps,
                carry.lane_episodes,
                carry.lane_groups,
                carry.t_global,
                num_groups,
                mask=dropped,
            )
        else:
            group_counts = carry.group_counts
        new_carry = RolloutCarry(
            env_states=_env_state_take(env, carry.env_states, sel),
            obs=carry.obs[sel],
            policy_states=(
                None
                if carry.policy_states is None
                else jax.tree_util.tree_map(lambda x: x[sel], carry.policy_states)
            ),
            scores=carry.scores[sel],
            episodes_done=carry.episodes_done[sel],
            steps_in_episode=carry.steps_in_episode[sel],
            active=carry.active[sel],
            stats=carry.stats,
            key=carry.key[sel],  # per-lane chains travel with their lanes
            total_steps=carry.total_steps,
            t_global=carry.t_global,
            capacity=carry.capacity,  # capacity already paid at prior widths
            # the folded group block survives compaction whole; lane group
            # ids and per-lane accumulators travel with their lanes like the
            # PRNG chains
            lane_groups=(
                carry.lane_groups[sel] if num_groups > 1 else carry.lane_groups
            ),
            group_counts=group_counts,
            lane_steps=(
                carry.lane_steps[sel] if num_groups > 1 else carry.lane_steps
            ),
            lane_episodes=(
                carry.lane_episodes[sel] if num_groups > 1 else carry.lane_episodes
            ),
        )
        return new_carry, _params_take(params_batch, sel), lane_ids[sel], scores_buf, eps_buf

    @jax.jit
    def finalize_fn(carry, lane_ids, scores_buf, eps_buf, groups_full=None):
        scores_buf = scores_buf.at[lane_ids].set(carry.scores)
        eps_buf = eps_buf.at[lane_ids].set(carry.episodes_done)
        mean_scores = scores_buf / jnp.maximum(eps_buf, 1)
        nf_bad = None
        if nonfinite_quarantine:
            mean_scores, nf_bad = _quarantine_nonfinite(
                mean_scores,
                penalty=nonfinite_penalty,
                sync_axis=nonfinite_sync_axis,
            )
        total_episodes = jnp.sum(eps_buf)
        if not collect_telemetry:
            telemetry = None
        elif num_groups > 1:
            # fold the surviving lanes' accumulators (dropped lanes folded at
            # their compaction boundary)
            group_counts = _fold_lane_counts(
                carry.group_counts,
                carry.lane_steps,
                carry.lane_episodes,
                carry.lane_groups,
                carry.t_global,
                num_groups,
            )
            if nf_bad is not None:
                # quarantine is per SOLUTION on the scattered-back buffers:
                # the full-width per-solution group ids do the charging
                group_counts = _nonfinite_group_counts(
                    group_counts, nf_bad, groups_full, num_groups
                )
            telemetry = pack_group_telemetry(group_counts)
        else:
            telemetry = pack_group_telemetry(
                pack_eval_telemetry(
                    env_steps=carry.total_steps,
                    episodes=total_episodes,
                    # carry.capacity summed width x iterations through every
                    # compaction, so occupancy credits the narrowing directly
                    capacity=carry.capacity,
                    lane_width=scores_buf.shape[0],
                    nonfinite=(
                        0 if nf_bad is None else jnp.sum(nf_bad.astype(jnp.int32))
                    ),
                )[None]
            )
        if telemetry is not None and health:
            telemetry = _health_telemetry(
                telemetry,
                mean_scores,
                groups_full if num_groups > 1 else None,
                num_groups,
                None,  # the compacting runner never pads its buffers
            )
        return mean_scores, total_episodes, telemetry

    return init_fn, chunk_fn, compact_fn, finalize_fn


def run_vectorized_rollout_compacting(
    env,
    policy: FlatParamsPolicy,
    params_batch: jnp.ndarray,
    key,
    stats: CollectedStats,
    *,
    num_episodes: int = 1,
    episode_length: Optional[int] = None,
    observation_normalization: bool = False,
    alive_bonus_schedule: Optional[tuple] = None,
    decrease_rewards_by: Optional[float] = None,
    action_noise_stdev: Optional[float] = None,
    compute_dtype=None,
    chunk_size: int = 25,
    min_width: Optional[int] = None,
    allowed_widths: Optional[tuple] = None,
    prewarm: bool = False,
    telemetry: bool = True,
    health: bool = True,
    groups=None,
    num_groups: int = 1,
    nonfinite_quarantine: bool = False,
    nonfinite_penalty: Optional[float] = None,
) -> RolloutResult:
    """Episodes-contract evaluation with **lane compaction** — the
    host-orchestrated fast path for ``eval_mode="episodes"``.

    Semantics are those of ``run_vectorized_rollout(eval_mode="episodes")``
    (the reference's ``VecGymNE`` contract, ``vecgymne.py:837-904``): each
    lane runs exactly ``num_episodes`` episodes and its score is the mean
    episodic return. The difference is purely how the machine spends its
    cycles: the loop runs in ``chunk_size``-step jitted chunks; after each
    chunk the number of still-active lanes is inspected, and when it fits in
    a smaller allowed width the active lanes are sorted to the front,
    gathered, and the loop continues narrow — finished lanes stop consuming
    compute instead of idling masked until the slowest survivor ends.

    Orchestration details:

    - The compaction decision is **pipelined one chunk behind**: the next
      chunk is dispatched before the previous chunk's active-count is read,
      so the device never sits idle waiting on the host round-trip.
    - The working width starts at N and descends through a small fixed menu
      (``allowed_widths``, default: the powers of two in
      ``[max(256, pow2(N/64)), N/2]``), jumping straight to the TIGHTEST
      width that holds the survivors — skewed death-time distributions kill
      most of the population in the first chunks, and stepping one notch per
      chunk would pay several more chunks at wide widths. The expensive
      compilations (the stepping program per width) are bounded by the menu
      size and prewarmed by ``prewarm=True``; a jump adds only a cheap
      (from, to) gather trace.
    - Results are scattered into full-width device buffers keyed by original
      lane id, so scores come back in the caller's order with no host-side
      bookkeeping.

    Scores are numerically identical to the monolithic runner's in every
    configuration — multi-episode, action noise: randomness is a per-lane
    property (each lane carries its own PRNG chain, gathered along with its
    state on compaction — ``_rollout_init``), so compaction reorders lanes
    without touching any lane's dynamics, noise or resets. (With
    observation normalization the masked stat reductions cover the same
    lane set at every width, so scores agree up to float summation order.)

    Not traceable (it syncs lane counts to the host); use the monolithic
    runner inside jit/shard_map.
    """
    n = _params_popsize(params_batch)
    max_t = env.max_episode_steps if env.max_episode_steps is not None else 1000
    if episode_length is not None:
        max_t = min(max_t, int(episode_length))
    hard_cap = max_t * int(num_episodes) + 1

    num_groups = int(num_groups)
    if num_groups > 1 and groups is None:
        raise ValueError("num_groups > 1 requires a groups array of per-solution ids")
    if not (telemetry and num_groups > 1):
        groups, num_groups = None, 1

    init_fn, chunk_fn, compact_fn, finalize_fn = _compacting_fns(
        env,
        policy,
        int(num_episodes),
        max_t,
        hard_cap,
        bool(observation_normalization),
        alive_bonus_schedule,
        decrease_rewards_by,
        action_noise_stdev,
        compute_dtype,
        collect_telemetry=bool(telemetry),
        health=bool(health),
        num_groups=num_groups,
        nonfinite_quarantine=bool(nonfinite_quarantine),
        nonfinite_penalty=nonfinite_penalty,
    )
    groups_full = (
        jnp.asarray(groups, dtype=jnp.int32) if num_groups > 1 else None
    )

    if allowed_widths is None:
        if min_width is None:
            # floor 256 (one full lane tile's worth of sublane batches):
            # deeper menus than the r3 n/16 floor — with the compile set
            # bounded to the descent pairs (prewarmable), the tail of a
            # skewed-death population is worth tracking tightly
            min_width = max(256, _pow2_at_least(max(1, n // 64)))
        widths = []
        w = _pow2_at_least(min_width)
        while w <= n // 2:
            widths.append(w)
            w *= 2
        allowed_widths = tuple(sorted(widths))
    else:
        allowed_widths = tuple(sorted(int(w) for w in allowed_widths if w < n))

    carry, params = init_fn(params_batch, key, stats, groups=groups_full)
    lane_ids = jnp.arange(n, dtype=jnp.int32)
    scores_buf = jnp.zeros(n, dtype=jnp.float32)
    eps_buf = jnp.zeros(n, dtype=jnp.int32)

    if prewarm:
        # compile chunk + finalize at every width and EVERY (from, to)
        # compact pair a runtime jump can hit — the jump policy's first real
        # compaction is typically full-width -> min_width directly, so the
        # adjacent chain alone would leave that trace in the timing loop.
        # O(k^2) tiny gather traces + k stepping programs, on throwaway
        # copies of the initial state
        c0, _ = chunk_fn(params, carry, int(chunk_size))
        finalize_fn(c0, lane_ids, scores_buf, eps_buf, groups_full)
        states = {c0.active.shape[0]: (c0, params, lane_ids, scores_buf, eps_buf)}
        for w in sorted(allowed_widths, reverse=True):
            narrowed = None
            for fw in sorted(states, reverse=True):
                if fw > w:
                    narrowed = compact_fn(*states[fw], w)
            if narrowed is None:
                continue
            c, p, ids, sb, eb = narrowed
            c, _ = chunk_fn(p, c, int(chunk_size))
            finalize_fn(c, ids, sb, eb, groups_full)
            states[w] = (c, p, ids, sb, eb)
        jax.block_until_ready(jax.tree_util.tree_leaves(states)[0])

    max_chunks = -(-hard_cap // int(chunk_size)) + 1
    prev_count = None
    for _ in range(max_chunks):
        carry, count = chunk_fn(params, carry, int(chunk_size))
        if prev_count is not None:
            # reading the PREVIOUS chunk's count: that result is already (or
            # nearly) computed, while the chunk just dispatched keeps the
            # device busy during this host round-trip
            n_active = int(prev_count)
            if n_active == 0:
                break
            width = carry.active.shape[0]
            # jump straight to the TIGHTEST allowed width that holds the
            # survivors: with skewed death-time distributions most of the
            # population dies in the first chunks, and stepping the menu one
            # notch per chunk would pay several more chunks at wide widths.
            # The expensive compile (chunk_fn) is still one per width;
            # jumping only adds cheap (from, to) gather traces
            fits = [w for w in allowed_widths if w < width and n_active <= w]
            if fits:
                carry, params, lane_ids, scores_buf, eps_buf = compact_fn(
                    carry, params, lane_ids, scores_buf, eps_buf, min(fits)
                )
        prev_count = count

    mean_scores, total_episodes, eval_telemetry = finalize_fn(
        carry, lane_ids, scores_buf, eps_buf, groups_full
    )
    return RolloutResult(
        scores=mean_scores,
        stats=carry.stats,
        total_steps=carry.total_steps,
        total_episodes=total_episodes,
        telemetry=eval_telemetry,
    )


# ----------------------- sharded lane-compacting runner -----------------------
# The episodes contract on a device mesh (VERDICT r3 #5): the jitted chunk /
# compact / finalize building blocks above are shard_mapped over a "pop"
# axis, while the host loop — the compaction decision — stays outside,
# exactly as in the single-device runner. The loop carry crosses shard_map
# boundaries between chunks, so it must have a consistent sharded global
# form: per-lane leaves shard over the mesh; per-shard "scalars" (stats,
# key, step counters — which genuinely DIVERGE between shards) get a leading
# shard axis so their global form is a (n_shards, ...) stack. Widths are
# per-shard and uniform across shards (SPMD: one trace), so the compaction
# decision reads the MAX active count over shards.


def _expand_shard_scalars(carry: "RolloutCarry") -> "RolloutCarry":
    """Give the per-shard scalar leaves a leading length-1 axis (the local
    view of a (n_shards, ...) global stack). ``key`` is per-lane state and
    needs no expansion."""
    ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)  # noqa: E731
    return carry._replace(
        stats=ex(carry.stats),
        total_steps=carry.total_steps[None],
        t_global=carry.t_global[None],
        capacity=carry.capacity[None],
        # per-shard PARTIAL per-group sums (psum'd at finalize); lane_groups
        # is a lane leaf and shards like scores
        group_counts=carry.group_counts[None],
    )


def _squeeze_shard_scalars(carry: "RolloutCarry") -> "RolloutCarry":
    sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)  # noqa: E731
    return carry._replace(
        stats=sq(carry.stats),
        total_steps=carry.total_steps[0],
        t_global=carry.t_global[0],
        capacity=carry.capacity[0],
        # graftlint: allow(telemetry-schema): [0] squeezes the leading shard axis, not a wire column
        group_counts=carry.group_counts[0],
    )


def _sharded_carry_specs(env, axis_name: str) -> "RolloutCarry":
    from jax.sharding import PartitionSpec as P

    lane = P(axis_name)
    env_spec = (
        env.batch_shard_spec(axis_name)
        if getattr(env, "batched_native", False)
        else lane
    )
    # stats/counters carry the leading shard axis (see expand above); key is
    # the per-lane chain array, a lane leaf like scores
    return RolloutCarry(
        env_states=env_spec,
        obs=lane,
        policy_states=lane,
        scores=lane,
        episodes_done=lane,
        steps_in_episode=lane,
        active=lane,
        stats=lane,
        key=lane,
        total_steps=lane,
        t_global=lane,
        capacity=lane,
        lane_groups=lane,
        group_counts=lane,
        lane_steps=lane,
        lane_episodes=lane,
    )


def global_lane_ids(axis_name: str, n_local: int) -> jnp.ndarray:
    """This shard's GLOBAL lane indices (inside ``shard_map``): the seeding
    contract of the per-lane PRNG chains — every sharded caller must derive
    ids exactly this way (rank * n_local + local index) for sharded
    evaluation to reproduce the unsharded one."""
    rank = jax.lax.axis_index(axis_name)
    return rank * n_local + jnp.arange(n_local, dtype=jnp.int32)


def _params_kind(params_batch) -> str:
    """Hashable representation tag for the lru-cached sharded builders."""
    if isinstance(params_batch, TrunkDeltaParamsBatch):
        return "trunk_delta"
    if isinstance(params_batch, LowRankParamsBatch):
        return "lowrank"
    return "dense"


def _params_shard_spec(params_kind: str, axis_name: str):
    from jax.sharding import PartitionSpec as P

    if params_kind == "lowrank":
        # coefficients shard; the shared center/basis replicate
        return LowRankParamsBatch(center=P(), basis=P(), coeffs=P(axis_name))
    if params_kind == "trunk_delta":
        # coefficients shard; trunk and factor tree replicate (factors=P()
        # is a pytree-prefix spec over the subtree)
        return TrunkDeltaParamsBatch(center=P(), coeffs=P(axis_name), factors=P())
    return P(axis_name)


@functools.lru_cache(maxsize=_ENGINE_CACHE_SIZE)
def _compacting_sharded_fns(
    env,
    policy: FlatParamsPolicy,
    num_episodes: int,
    max_t: int,
    hard_cap: int,
    observation_normalization: bool,
    alive_bonus_schedule,
    decrease_rewards_by,
    action_noise_stdev,
    compute_dtype,
    mesh,
    axis_name: str,
    params_kind: str,
    stats_sync: bool = False,
    collect_telemetry: bool = True,
    health: bool = True,
    num_groups: int = 1,
    nonfinite_quarantine: bool = False,
    nonfinite_penalty=None,
):
    from jax.sharding import PartitionSpec as P

    num_groups = int(num_groups)
    init_fn, chunk_fn, compact_fn, finalize_fn = _compacting_fns(
        env,
        policy,
        num_episodes,
        max_t,
        hard_cap,
        observation_normalization,
        alive_bonus_schedule,
        decrease_rewards_by,
        action_noise_stdev,
        compute_dtype,
        stats_sync_axis=axis_name if stats_sync else None,
        collect_telemetry=collect_telemetry,
        # the per-shard finalize must NOT append a health block: the
        # telemetry psum below would sum the bit-cast float columns across
        # shards into garbage. sh_finalize_local all_gathers the scores and
        # appends ONE mesh-global block (shard-0 masked) instead.
        health=False,
        num_groups=num_groups,
        nonfinite_quarantine=nonfinite_quarantine,
        nonfinite_penalty=nonfinite_penalty,
        # the worst-finite reduction pmins over the mesh so each shard
        # quarantines to the GLOBAL worst finite score (bit-identity with
        # the unsharded runner); a fixed penalty needs no collective
        nonfinite_sync_axis=(
            axis_name if (nonfinite_quarantine and nonfinite_penalty is None) else None
        ),
    )
    carry_specs = _sharded_carry_specs(env, axis_name)
    params_spec = _params_shard_spec(params_kind, axis_name)
    lane = P(axis_name)

    if num_groups > 1:
        # group ids ride in as a 4th lane-sharded input; each shard seeds
        # its partial per-group sums from its own lanes (psum'd at finalize)
        def sh_init_local(params_shard, groups_shard, key, stats):
            n_local = _params_popsize(params_shard)
            carry, params_cast = init_fn(
                params_shard,
                key,
                stats,
                global_lane_ids(axis_name, n_local),
                groups_shard,
            )
            lane_ids = jnp.arange(n_local, dtype=jnp.int32)  # LOCAL buffer ids
            scores_buf = jnp.zeros(n_local, dtype=jnp.float32)
            eps_buf = jnp.zeros(n_local, dtype=jnp.int32)
            return _expand_shard_scalars(carry), params_cast, lane_ids, scores_buf, eps_buf

        sh_init = jax.jit(
            jax.shard_map(
                sh_init_local,
                mesh=mesh,
                in_specs=(params_spec, lane, P(), P()),
                out_specs=(carry_specs, params_spec, lane, lane, lane),
                check_vma=False,
            )
        )
    else:

        def sh_init_local(params_shard, key, stats):
            # GLOBAL lane ids seed the per-lane PRNG chains (same key on
            # every shard): the sharded evaluation reproduces the unsharded
            # one, whatever the topology
            n_local = _params_popsize(params_shard)
            carry, params_cast = init_fn(
                params_shard, key, stats, global_lane_ids(axis_name, n_local)
            )
            lane_ids = jnp.arange(n_local, dtype=jnp.int32)  # LOCAL buffer ids
            scores_buf = jnp.zeros(n_local, dtype=jnp.float32)
            eps_buf = jnp.zeros(n_local, dtype=jnp.int32)
            return _expand_shard_scalars(carry), params_cast, lane_ids, scores_buf, eps_buf

        sh_init = jax.jit(
            jax.shard_map(
                sh_init_local,
                mesh=mesh,
                in_specs=(params_spec, P(), P()),
                out_specs=(carry_specs, params_spec, lane, lane, lane),
                check_vma=False,
            )
        )

    chunk_cache: dict = {}

    def sh_chunk(params, carry, num_steps: int):
        fn = chunk_cache.get(num_steps)
        if fn is None:

            def local(params_shard, carry):
                c, count = chunk_fn(params_shard, _squeeze_shard_scalars(carry), num_steps)
                return _expand_shard_scalars(c), count[None]

            fn = jax.jit(
                jax.shard_map(
                    local,
                    mesh=mesh,
                    in_specs=(params_spec, carry_specs),
                    out_specs=(carry_specs, lane),
                    check_vma=False,
                )
            )
            chunk_cache[num_steps] = fn
        return fn(params, carry)

    compact_cache: dict = {}

    def sh_compact(carry, params, lane_ids, scores_buf, eps_buf, new_width: int):
        fn = compact_cache.get(new_width)
        if fn is None:

            def local(carry, params_shard, lane_ids, scores_buf, eps_buf):
                c, p, ids, sb, eb = compact_fn(
                    _squeeze_shard_scalars(carry),
                    params_shard,
                    lane_ids,
                    scores_buf,
                    eps_buf,
                    new_width,
                )
                return _expand_shard_scalars(c), p, ids, sb, eb

            fn = jax.jit(
                jax.shard_map(
                    local,
                    mesh=mesh,
                    in_specs=(carry_specs, params_spec, lane, lane, lane),
                    out_specs=(carry_specs, params_spec, lane, lane, lane),
                    check_vma=False,
                )
            )
            compact_cache[new_width] = fn
        return fn(carry, params, lane_ids, scores_buf, eps_buf)

    def sh_finalize_local(carry, lane_ids, scores_buf, eps_buf, groups_shard, stats0):
        c = _squeeze_shard_scalars(carry)
        mean_scores, eps_total_local, telemetry = finalize_fn(
            c, lane_ids, scores_buf, eps_buf, groups_shard
        )
        if telemetry is None:
            telemetry_out = jnp.zeros((0,), dtype=jnp.int32)
        else:
            if health:
                # mesh-global search-health block: gather every shard's
                # final scores into GLOBAL lane order (shards hold
                # contiguous blocks, so tiled all_gather IS the unsharded
                # order), compute the identical full-population reduction
                # on every shard, then zero all but shard 0's copy — the
                # integer psum below then carries the bit-cast float
                # columns through exactly (0.0 bit-casts to 0)
                g_scores = jax.lax.all_gather(
                    mean_scores, axis_name, tiled=True
                )
                g_groups = (
                    jax.lax.all_gather(groups_shard, axis_name, tiled=True)
                    if groups_shard is not None
                    else None
                )
                block = compute_health_block(g_scores, g_groups, num_groups)
                shard0 = (jax.lax.axis_index(axis_name) == 0).astype(
                    block.dtype
                )
                telemetry = append_health_block(telemetry, block * shard0)
            # every slot is additive, so the mesh-global telemetry is one psum
            telemetry_out = jax.lax.psum(telemetry, axis_name)
        if stats_sync:
            # per-step psum already made every shard's stats mesh-global; a
            # final delta merge would count every delta n_shards times
            merged = c.stats
        else:
            # merge per-shard obs-norm stat deltas with a psum (the
            # collective form of the reference's actor delta-sync,
            # gymne.py:524-573)
            delta = jax.tree_util.tree_map(lambda new, old: new - old, c.stats, stats0)
            merged = jax.tree_util.tree_map(
                lambda old, d: old + jax.lax.psum(d, axis_name), stats0, delta
            )
        return (
            mean_scores,
            merged,
            jax.lax.psum(c.total_steps, axis_name),
            jax.lax.psum(eps_total_local, axis_name),
            # per-shard COUNTED interactions (total_steps sums active lanes
            # only, so it is invariant under compaction — compaction saves
            # wall-clock on dead lanes, not counted steps)
            c.total_steps[None],
            telemetry_out,
        )

    if num_groups > 1:
        sh_finalize = jax.jit(
            jax.shard_map(
                sh_finalize_local,
                mesh=mesh,
                in_specs=(carry_specs, lane, lane, lane, lane, P()),
                out_specs=(lane, P(), P(), P(), lane, P()),
                check_vma=False,
            )
        )
    else:
        # no group ids to ship: close over the sentinel so the shard_map
        # signature stays group-free (None is a zero-leaf pytree)
        def sh_finalize_nogroups(carry, lane_ids, scores_buf, eps_buf, stats0):
            return sh_finalize_local(
                carry, lane_ids, scores_buf, eps_buf, None, stats0
            )

        inner = jax.jit(
            jax.shard_map(
                sh_finalize_nogroups,
                mesh=mesh,
                in_specs=(carry_specs, lane, lane, lane, P()),
                out_specs=(lane, P(), P(), P(), lane, P()),
                check_vma=False,
            )
        )

        def sh_finalize(carry, lane_ids, scores_buf, eps_buf, groups, stats0):
            return inner(carry, lane_ids, scores_buf, eps_buf, stats0)

    return sh_init, sh_chunk, sh_compact, sh_finalize


def run_vectorized_rollout_compacting_sharded(
    env,
    policy: FlatParamsPolicy,
    params_batch,
    key,
    stats: CollectedStats,
    *,
    mesh,
    axis_name: str = "pop",
    num_episodes: int = 1,
    episode_length: Optional[int] = None,
    observation_normalization: bool = False,
    alive_bonus_schedule: Optional[tuple] = None,
    decrease_rewards_by: Optional[float] = None,
    action_noise_stdev: Optional[float] = None,
    compute_dtype=None,
    chunk_size: int = 25,
    min_width: Optional[int] = None,
    allowed_widths: Optional[tuple] = None,
    prewarm: bool = False,
    return_per_shard_steps: bool = False,
    stats_sync: bool = False,
    telemetry: bool = True,
    health: bool = True,
    groups=None,
    num_groups: int = 1,
    nonfinite_quarantine: bool = False,
    nonfinite_penalty: Optional[float] = None,
) -> RolloutResult:
    """``run_vectorized_rollout_compacting`` with the population sharded over
    ``mesh[axis_name]``: each device narrows ITS working set as its lanes
    finish, so the episodes contract stops paying for dead lanes on every
    shard — the single-device runner's win, preserved on the hardware the
    framework targets (VERDICT r3 #5).

    ``allowed_widths``/``min_width`` are PER-SHARD widths; the width descent
    is uniform across shards (one SPMD trace per width), driven by the MAX
    per-shard active count so no shard overflows. Per-lane PRNG chains are
    seeded by GLOBAL lane ids with the same base key on every shard, so
    without observation normalization scores/counters are BIT-IDENTICAL to
    the unsharded ``eval_mode="episodes"`` evaluation of the same
    population — the mesh is an execution detail. (With observation
    normalization, each shard's lanes are normalized by their shard-local
    running statistics mid-rollout — cohort semantics, like the reference's
    per-actor stats — so sharded scores differ from unsharded ones; pass
    ``stats_sync=True`` to psum-merge the stat deltas every step instead,
    making every shard normalize by the mesh-global cohort.)

    Not traceable (it syncs lane counts to the host between chunks); call it
    from host code. Returns a :class:`RolloutResult` whose ``stats`` are the
    psum-merged statistics and whose counters are mesh-global."""
    n = _params_popsize(params_batch)
    n_shards = int(mesh.shape[axis_name])
    if n % n_shards != 0:
        raise ValueError(f"Population size {n} must divide the mesh axis {n_shards}")
    n_local = n // n_shards
    max_t = env.max_episode_steps if env.max_episode_steps is not None else 1000
    if episode_length is not None:
        max_t = min(max_t, int(episode_length))
    hard_cap = max_t * int(num_episodes) + 1

    num_groups = int(num_groups)
    if num_groups > 1 and groups is None:
        raise ValueError("num_groups > 1 requires a groups array of per-solution ids")
    if not (telemetry and num_groups > 1):
        groups, num_groups = None, 1

    sh_init, sh_chunk, sh_compact, sh_finalize = _compacting_sharded_fns(
        env,
        policy,
        int(num_episodes),
        max_t,
        hard_cap,
        bool(observation_normalization),
        alive_bonus_schedule,
        decrease_rewards_by,
        action_noise_stdev,
        compute_dtype,
        mesh,
        str(axis_name),
        _params_kind(params_batch),
        bool(stats_sync),
        bool(telemetry),
        health=bool(health),
        num_groups=num_groups,
        nonfinite_quarantine=bool(nonfinite_quarantine),
        nonfinite_penalty=nonfinite_penalty,
    )
    groups_dev = (
        jnp.asarray(groups, dtype=jnp.int32)
        if num_groups > 1
        else jnp.zeros((n,), dtype=jnp.int32)
    )

    if allowed_widths is None:
        if min_width is None:
            # same deeper default floor as the single-device runner
            min_width = max(256, _pow2_at_least(max(1, n_local // 64)))
        widths = []
        w = _pow2_at_least(min_width)
        while w <= n_local // 2:
            widths.append(w)
            w *= 2
        allowed_widths = tuple(sorted(widths))
    else:
        allowed_widths = tuple(sorted(int(w) for w in allowed_widths if w < n_local))

    stats0 = stats
    if num_groups > 1:
        carry, params, lane_ids, scores_buf, eps_buf = sh_init(
            params_batch, jnp.asarray(groups, dtype=jnp.int32), key, stats
        )
    else:
        carry, params, lane_ids, scores_buf, eps_buf = sh_init(params_batch, key, stats)

    if prewarm:
        # compile chunk + finalize at every width and every (from, to)
        # compact pair a runtime jump can hit (mirrors the single-device
        # prewarm), so no trace+compile lands in a timing loop
        c0, _ = sh_chunk(params, carry, int(chunk_size))
        sh_finalize(c0, lane_ids, scores_buf, eps_buf, groups_dev, stats0)
        states = {
            c0.active.shape[0] // n_shards: (c0, params, lane_ids, scores_buf, eps_buf)
        }
        for w in sorted(allowed_widths, reverse=True):
            narrowed = None
            for fw in sorted(states, reverse=True):
                if fw > w:
                    narrowed = sh_compact(*states[fw], w)
            if narrowed is None:
                continue
            c, p, ids, sb, eb = narrowed
            c, _ = sh_chunk(p, c, int(chunk_size))
            sh_finalize(c, ids, sb, eb, groups_dev, stats0)
            states[w] = (c, p, ids, sb, eb)
        jax.block_until_ready(jax.tree_util.tree_leaves(states)[0])

    max_chunks = -(-hard_cap // int(chunk_size)) + 1
    prev_counts = None
    for _ in range(max_chunks):
        carry, counts = sh_chunk(params, carry, int(chunk_size))
        if prev_counts is not None:
            # pipelined one chunk behind, like the single-device runner: the
            # chunk just dispatched keeps all shards busy during this host
            # round-trip. The decision uses the MAX shard count so the new
            # width fits every shard.
            n_active = int(jnp.max(prev_counts))
            if n_active == 0:
                break
            width = carry.active.shape[0] // n_shards
            # jump to the tightest per-shard width that holds every shard's
            # survivors (see the single-device loop for the rationale)
            fits = [w for w in allowed_widths if w < width and n_active <= w]
            if fits:
                carry, params, lane_ids, scores_buf, eps_buf = sh_compact(
                    carry, params, lane_ids, scores_buf, eps_buf, min(fits)
                )
        prev_counts = counts

    mean_scores, merged_stats, total_steps, total_episodes, per_shard, eval_telemetry = (
        sh_finalize(carry, lane_ids, scores_buf, eps_buf, groups_dev, stats0)
    )
    result = RolloutResult(
        scores=mean_scores,
        stats=merged_stats,
        total_steps=total_steps,
        total_episodes=total_episodes,
        telemetry=eval_telemetry if eval_telemetry.size else None,
    )
    if return_per_shard_steps:
        return result, per_shard
    return result
