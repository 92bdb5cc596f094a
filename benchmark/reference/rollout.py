"""Plain reference: the two eval contracts as a ``vmap`` of a ``scan``.

Written from docs/eval_contracts.md, over nothing but ``env.reset`` /
``env.step`` (one lane each, the un-batched API) and the plain policy forward
of tanh_mlp.py, in float32. No masking tricks, no telemetry, no batching
beyond ``vmap``: every lane runs ``episode_length`` scan steps and carries
its own sums.

- ``budget``: the lane spends exactly ``episode_length`` interactions,
  resetting the env when an episode ends (the env says done, or the episode
  reaches ``episode_length`` steps). Score: the sum of rewards over the
  budget divided by the episodes it held, completed ones plus the trailing
  one as the fraction ``steps / episode_length`` (floored at one step's
  worth). Counted interactions: ``episode_length`` per lane.
- ``episodes``: the lane's score is the return up to its first termination
  (one episode; ``num_episodes`` is 1 in every configuration so far); steps
  after it are not counted. Counted interactions: the episode's length.

Actions are clipped to the action space, as the contract says. Observation
normalisation is not part of this reference: the comparison runs both sides
on raw observations. The physics (``envs/rigidbody.py``) is the library's own
on both sides; it has no independent reference here.
"""

import jax
import jax.numpy as jnp


def make_rollout(env, forward, contract, episode_length):
    """``rollout(params (n, L), keys (n,)) -> scores, steps, episodes`` per lane.
    ``contract`` names the semantics above, ``budget`` or ``episodes``; a
    workload's traffic states which one its ``eval_mode`` must agree with
    (``episodes_refill`` schedules lanes differently and scores as ``episodes``)."""
    if contract not in ("budget", "episodes"):
        raise ValueError(f"this file holds no plain contract {contract!r}")
    budget = contract == "budget"
    space = env.action_space
    max_t = int(episode_length)

    def lane(flat, key):
        key, reset_key = jax.random.split(key)
        state, obs = env.reset(reset_key)

        def step(carry, _):
            state, obs, key, alive, total, steps, in_episode, episodes = carry
            action = jnp.clip(forward(flat, obs), space.lb, space.ub)
            next_state, next_obs, reward, done = env.step(state, action)
            in_episode = in_episode + 1
            done = done | (in_episode >= max_t)
            total = total + jnp.where(alive, reward, 0.0)
            steps = steps + alive.astype(jnp.int32)
            ended = done & alive
            episodes = episodes + ended.astype(jnp.int32)
            if budget:
                key, reset_key = jax.random.split(key)
                fresh_state, fresh_obs = env.reset(reset_key)
                next_state = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ended, a, b), fresh_state, next_state
                )
                next_obs = jnp.where(ended, fresh_obs, next_obs)
                in_episode = jnp.where(ended, 0, in_episode)
            else:
                alive = alive & ~done
            return (next_state, next_obs, key, alive, total, steps, in_episode, episodes), None

        carry = (
            state,
            obs,
            key,
            jnp.asarray(True),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        carry, _ = jax.lax.scan(step, carry, None, length=max_t)
        _, _, _, _, total, steps, in_episode, episodes = carry
        if budget:
            held = episodes + in_episode.astype(jnp.float32) / max_t
            score = total / jnp.maximum(held, 1.0 / max_t)
        else:
            score = total / jnp.maximum(episodes, 1)
        return score, steps, episodes

    return jax.jit(jax.vmap(lane))
