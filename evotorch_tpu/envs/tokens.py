"""Token environments: a language model decoded stepwise as a policy.

Evolution strategies on a language model evaluate by generation: each lane
decodes a response under its own perturbed weights and a verifier scores it.
Through the rollout engine that is a recurrent policy whose observation is
the last token id, whose action is the next one (argmax of the logits: the
discrete path of ``net/vecrl.py:_policy_to_action``) and whose state is the
attention cache (``net/decoder.py``).

``TokenCopyEnv`` is the smallest verifiable task of that shape: a seeded
prompt is fed one token a step, then the model's own tokens come back as its
observations and each is rewarded where it repeats the prompt.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .base import Env, EnvState, Space

__all__ = ["TokenCopyEnv"]


class TokenCopyEnv(Env):
    """Copy the prompt. Observations are ``(1,)`` int32 token ids of the held
    vocabulary ``[0, vocab_held)``; the action space is discrete over it.

    ``reset(key)`` draws ``prompt_length`` ids from ``[1, vocab_held)`` (id 0
    ends an episode) and observes the first. The step taken at time ``t``
    (``t`` steps done before it) consumed position ``t`` and its action is
    the model's token for position ``t + 1``:

    - while ``t + 1 < prompt_length`` the prompt is teacher-forced: the next
      observation is ``prompt[t + 1]`` whatever the action, reward 0;
    - afterwards the action is an emitted token: it is the next observation,
      it earns 1 where it equals ``prompt[(t + 1 - prompt_length) mod
      prompt_length]``, and id 0 ends the episode;
    - the episode ends at ``max_episode_steps`` at the latest.
    """

    def __init__(
        self,
        vocab_held: int,
        prompt_length: int,
        max_episode_steps: int,
    ):
        self.vocab_held = int(vocab_held)
        self.prompt_length = int(prompt_length)
        if not 1 <= self.prompt_length <= int(max_episode_steps):
            raise ValueError("prompt_length must lie in [1, max_episode_steps]")
        self.max_episode_steps = int(max_episode_steps)
        self.observation_space = Space(shape=(1,))
        self.action_space = Space(shape=(), n=self.vocab_held)

    # instances are static arguments of the jitted rollout: equal settings
    # must hit the same compiled program
    def _identity(self):
        return (self.vocab_held, self.prompt_length, self.max_episode_steps)

    def __eq__(self, other):
        return type(other) is type(self) and other._identity() == self._identity()

    def __hash__(self):
        return hash((type(self).__name__, self._identity()))

    def reset(self, key) -> Tuple[EnvState, jnp.ndarray]:
        key, sub = jax.random.split(key)
        text = jax.random.randint(sub, (self.prompt_length,), 1, self.vocab_held, dtype=jnp.int32)
        state = EnvState(obs_state=text, t=jnp.zeros((), jnp.int32), key=key)
        return state, text[:1]

    def step(self, state: EnvState, action):
        text = state.obs_state
        action = jnp.reshape(action, ()).astype(jnp.int32)
        nxt = state.t + 1  # the position the action speaks for
        p = self.prompt_length
        emitted = nxt >= p
        obs = jnp.where(emitted, action, text[jnp.minimum(nxt, p - 1)])
        target = text[jnp.mod(nxt - p, p)]
        reward = (emitted & (action == target)).astype(jnp.float32)
        done = (emitted & (action == 0)) | (nxt >= self.max_episode_steps)
        new_state = EnvState(obs_state=text, t=nxt, key=state.key)
        return new_state, obs[None], reward, done

