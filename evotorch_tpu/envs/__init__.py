"""Pure-JAX vectorized environments.

The reference's vectorized-RL layer (``net/vecrl.py``) bridges to Brax/gym
through dlpack conversions and wrapper stacks (``vecrl.py:362-613``
``TorchWrapper``, ``vecrl.py:1366-1490`` ``VectorEnvFromBrax``). On TPU the
right substrate is environments whose ``reset``/``step`` are themselves pure
jittable functions, so whole rollouts compile into one ``lax.scan`` with
auto-reset inside the program (SURVEY.md §3.4 "keep the whole loop inside one
jitted while_loop/scan").

``make_env("cartpole")`` returns such an env; ``"brax::<name>"`` adapts a
brax env when brax is installed (import-gated), mirroring the reference's
``"gym::"``/``"brax::"`` registry strings (``vecgymne.py:496-570``).

The ``mujoco`` subpackage (``envs/mujoco/``, import-gated on the optional
``mujoco`` + ``gymnasium`` packages) is the REAL-physics counterpart: a
batched host rollout engine over real gymnasium ``-v5`` models
(``MjVecEnv``) and the matched-action fidelity harness that measures how
far these native envs diverge from their MuJoCo namesakes
(``docs/neuroevolution.md``).
"""

from .base import Env, EnvState, Space
from .classic import Acrobot, CartPole, MountainCarContinuous, Pendulum, Swimmer2D
from .hopper import Hopper
from .ant import Ant
from .humanoid import Humanoid
from .walker2d import Walker2D
from .halfcheetah import HalfCheetah
from .tokens import TokenCopyEnv
from .registry import make_env, register_env

__all__ = [
    "Env",
    "EnvState",
    "Space",
    "CartPole",
    "Pendulum",
    "Acrobot",
    "MountainCarContinuous",
    "Swimmer2D",
    "Hopper",
    "Humanoid",
    "Ant",
    "Walker2D",
    "HalfCheetah",
    "TokenCopyEnv",
    "make_env",
    "register_env",
]
