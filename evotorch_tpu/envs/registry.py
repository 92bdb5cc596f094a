"""Environment registry.

Parity: the reference resolves env strings like ``"gym::Humanoid-v4"`` or
``"brax::humanoid"`` (``vecgymne.py:496-570``, ``net/vecrl.py:764-860``).
Here plain names resolve to the pure-JAX envs; ``"brax::<name>"`` adapts a
brax env when brax is importable.
"""

from __future__ import annotations

from typing import Callable, Dict

from .base import Env

__all__ = ["canonical_env_key", "make_env", "register_env"]

_REGISTRY: Dict[str, Callable[..., Env]] = {}
#: normalized alias (registered name OR factory class name) -> the ONE
#: canonical key (the first name the factory was registered under), so
#: "walker"/"walker2d"/Walker2D and "halfcheetah"/"half_cheetah" all
#: resolve to a single identity — the tuned-config cache keys on it
_CANONICAL: Dict[str, str] = {}


def register_env(name: str, factory: Callable[..., Env]):
    key = name.lower()
    # aliases of an already-registered factory fold to its first name
    existing = [k for k, f in _REGISTRY.items() if f is factory]
    canonical = _CANONICAL[existing[0]] if existing else key
    _REGISTRY[key] = factory
    _CANONICAL[key] = canonical
    if isinstance(factory, type):
        # a live instance's identity is its class name (Swimmer2D() must
        # hit entries tuned via the registered string "swimmer")
        _CANONICAL.setdefault(factory.__name__.lower(), canonical)


def _normalize(name: str) -> str:
    key = name.lower().replace("-", "_")
    for suffix in ("_v0", "_v1", "_v2", "_v3", "_v4", "_v5"):
        if key.endswith(suffix):
            key = key[: -len(suffix)]
    return key


def canonical_env_key(name: str) -> str:
    """The registry's canonical form of an env name — lowercase, dashes
    folded, gym-style version suffixes stripped (``"CartPole-v1"`` →
    ``"cartpole"``), registry aliases and factory class names folded to
    one key (``"half_cheetah"`` → ``"halfcheetah"``, ``"swimmer2d"`` →
    ``"swimmer"``). THE one normalization: :func:`make_env` resolves with
    it and the tuned-config cache keys on it
    (``observability.timings.canonical_env_label``), so the two cannot
    drift."""
    key = _normalize(name)
    return _CANONICAL.get(key, key)


def make_env(name: str, **kwargs) -> Env:
    """Instantiate an environment by name.

    Plain names (``"cartpole"``, ``"pendulum"``, ``"acrobot"``,
    ``"mountain_car_continuous"``, ``"swimmer"``, ``"hopper"``) resolve to the
    pure-JAX suite. ``"brax::<env>"`` adapts brax (requires brax installed)."""
    if name.startswith("brax::"):
        from .braxenv import BraxEnvAdapter

        return BraxEnvAdapter(name[len("brax::") :], **kwargs)
    key = canonical_env_key(name)
    if key not in _REGISTRY:
        raise ValueError(f"Unknown environment: {name!r} (known: {sorted(_REGISTRY)})")
    return _REGISTRY[key](**kwargs)


def _register_defaults():
    from .classic import Acrobot, CartPole, MountainCarContinuous, Pendulum, Swimmer2D

    register_env("cartpole", CartPole)
    register_env("pendulum", Pendulum)
    register_env("acrobot", Acrobot)
    register_env("mountain_car_continuous", MountainCarContinuous)
    register_env("mountaincarcontinuous", MountainCarContinuous)
    register_env("swimmer", Swimmer2D)

    from .hopper import Hopper

    register_env("hopper", Hopper)

    from .humanoid import Humanoid

    register_env("humanoid", Humanoid)

    from .ant import Ant

    register_env("ant", Ant)

    from .walker2d import Walker2D

    register_env("walker2d", Walker2D)
    register_env("walker", Walker2D)

    from .halfcheetah import HalfCheetah

    register_env("halfcheetah", HalfCheetah)
    register_env("half_cheetah", HalfCheetah)

    from .tokens import TokenCopyEnv

    register_env("token_copy", TokenCopyEnv)


_register_defaults()
