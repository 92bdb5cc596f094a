"""Stateful optimizer adapters exposing ``ascent(grad)``.

Parity: reference ``optimizers.py`` — ``ClipUp`` (``optimizers.py:231-418``),
``Adam``/``SGD`` adapters (``optimizers.py:101-229``), ``get_optimizer_class``
(``optimizers.py:421-456``). Each adapter is a thin host-side wrapper around
the corresponding pure functional step (``algorithms/functional/func*.py``),
so the math is written once and is jit-compiled. An ``OptaxOptimizer`` adapter
plays the role of the reference's generic ``TorchOptimizer``
(``optimizers.py:31-98``), accepting any optax ``GradientTransformation``.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Callable, Optional

import jax.numpy as jnp

from .tools.misc import ensure_array_length_and_dtype, to_jax_dtype

__all__ = ["ClipUp", "Adam", "SGD", "OptaxOptimizer", "get_optimizer_class"]


class _FunctionalWrapper:
    """Base for stateful wrappers: the optimizer state tracks a virtual center
    starting at 0; ``ascent(grad)`` returns the center delta."""

    def __init__(self, *, solution_length: int, dtype="float32"):
        self._dtype = to_jax_dtype(dtype)
        self._length = int(solution_length)

    def _zero_center(self):
        return jnp.zeros(self._length, dtype=self._dtype)

    def _coerce(self, grad):
        return ensure_array_length_and_dtype(
            grad, self._length, self._dtype, about=f"{type(self).__name__}.ascent"
        )

    #: the attributes that hold the optimizer's arrays
    _STATE: tuple = ()

    def state(self) -> tuple:
        return tuple(getattr(self, name) for name in self._STATE)

    def load_state(self, state) -> None:
        for name, value in zip(self._STATE, state):
            setattr(self, name, value)

    def pure_ascent(self, state, grad):
        """``ascent`` as a pure function: ``(state, grad) -> (step, new
        state)``, the object left as it was. For a caller that runs the step
        inside a compiled program of its own, with the state's buffers
        donated, and stores the result with ``load_state``."""
        saved = self.state()
        self.load_state(state)
        try:
            return self.ascent(grad), self.state()
        finally:
            self.load_state(saved)


class ClipUp(_FunctionalWrapper):
    """The ClipUp optimizer (Toklu et al. 2020; reference
    ``optimizers.py:231-418``): normalize the gradient to ``stepsize``,
    momentum-accumulate, clip velocity norm to ``max_speed``
    (default ``2 * stepsize``)."""

    _param_group_items = {"lr": "_stepsize", "max_speed": "_max_speed", "momentum": "_momentum"}
    _param_group_item_lb = {"lr": 0.0, "max_speed": 0.0, "momentum": 0.0}
    _param_group_item_ub = {"momentum": 1.0}
    _STATE = ("_velocity",)

    def __init__(
        self,
        *,
        solution_length: int,
        dtype="float32",
        stepsize: float,
        momentum: float = 0.9,
        max_speed: Optional[float] = None,
    ):
        super().__init__(solution_length=solution_length, dtype=dtype)
        stepsize = float(stepsize)
        momentum = float(momentum)
        max_speed = stepsize * 2.0 if max_speed is None else float(max_speed)
        if stepsize < 0.0:
            raise ValueError(f"Invalid stepsize: {stepsize}")
        if momentum < 0.0 or momentum > 1.0:
            raise ValueError(f"Invalid momentum: {momentum}")
        if max_speed < 0.0:
            raise ValueError(f"Invalid max_speed: {max_speed}")
        self._stepsize = stepsize
        self._momentum = momentum
        self._max_speed = max_speed
        self._velocity = jnp.zeros(self._length, dtype=self._dtype)
        self._param_groups = (ClipUpParameterGroup(self),)

    def ascent(self, globalg, *, cloned_result: bool = True) -> jnp.ndarray:
        grad = self._coerce(globalg)
        from .algorithms.functional.funcclipup import _clipup_step

        velocity, _ = _clipup_step(
            grad,
            jnp.zeros_like(self._velocity),
            self._velocity,
            jnp.asarray(self._stepsize, dtype=self._dtype),
            jnp.asarray(self._momentum, dtype=self._dtype),
            jnp.asarray(self._max_speed, dtype=self._dtype),
        )
        self._velocity = velocity
        return velocity

    @property
    def contained_optimizer(self) -> "ClipUp":
        return self

    @property
    def param_groups(self) -> tuple:
        return self._param_groups


class ClipUpParameterGroup(Mapping):
    """Mapping view over ClipUp hyperparameters, allowing mid-run mutation
    (reference ``optimizers.py:382-418``)."""

    def __init__(self, clipup: ClipUp):
        self.clipup = clipup

    def __getitem__(self, key: str) -> float:
        return getattr(self.clipup, ClipUp._param_group_items[key])

    def __setitem__(self, key: str, value: float):
        attrname = ClipUp._param_group_items[key]
        value = float(value)
        lb = ClipUp._param_group_item_lb.get(key)
        if lb is not None and value < lb:
            raise ValueError(f"Invalid value for {key!r}: {value}")
        ub = ClipUp._param_group_item_ub.get(key)
        if ub is not None and value > ub:
            raise ValueError(f"Invalid value for {key!r}: {value}")
        setattr(self.clipup, attrname, value)

    def __iter__(self):
        return iter(ClipUp._param_group_items)

    def __len__(self):
        return len(ClipUp._param_group_items)

    def __repr__(self):
        return f"<{type(self).__name__}: {dict(self)}>"


class Adam(_FunctionalWrapper):
    """Adam with ``ascent`` semantics (reference ``optimizers.py:101-170``)."""

    _STATE = ("_m", "_v", "_t")

    def __init__(
        self,
        *,
        solution_length: int,
        dtype="float32",
        stepsize: Optional[float] = None,
        beta1: Optional[float] = None,
        beta2: Optional[float] = None,
        epsilon: Optional[float] = None,
        amsgrad: Optional[bool] = None,
    ):
        super().__init__(solution_length=solution_length, dtype=dtype)
        if amsgrad:
            raise NotImplementedError("amsgrad is not supported by the TPU Adam adapter")
        self._stepsize = 0.001 if stepsize is None else float(stepsize)
        self._beta1 = 0.9 if beta1 is None else float(beta1)
        self._beta2 = 0.999 if beta2 is None else float(beta2)
        self._epsilon = 1e-8 if epsilon is None else float(epsilon)
        self._m = jnp.zeros(self._length, dtype=self._dtype)
        self._v = jnp.zeros(self._length, dtype=self._dtype)
        self._t = jnp.zeros((), dtype=self._dtype)

    def ascent(self, globalg, *, cloned_result: bool = True) -> jnp.ndarray:
        grad = self._coerce(globalg)
        from .algorithms.functional.funcadam import _adam_step

        center, m, v, t = _adam_step(
            grad,
            jnp.zeros(self._length, dtype=self._dtype),
            jnp.asarray(self._stepsize, dtype=self._dtype),
            jnp.asarray(self._beta1, dtype=self._dtype),
            jnp.asarray(self._beta2, dtype=self._dtype),
            jnp.asarray(self._epsilon, dtype=self._dtype),
            self._m,
            self._v,
            self._t,
        )
        self._m, self._v, self._t = m, v, t
        return center

    @property
    def contained_optimizer(self) -> "Adam":
        return self


class SGD(_FunctionalWrapper):
    """SGD (optionally with momentum) with ``ascent`` semantics
    (reference ``optimizers.py:173-229``)."""

    _STATE = ("_velocity",)

    def __init__(
        self,
        *,
        solution_length: int,
        dtype="float32",
        stepsize: float,
        momentum: Optional[float] = None,
    ):
        super().__init__(solution_length=solution_length, dtype=dtype)
        self._stepsize = float(stepsize)
        self._momentum = 0.0 if momentum is None else float(momentum)
        self._velocity = jnp.zeros(self._length, dtype=self._dtype)

    def ascent(self, globalg, *, cloned_result: bool = True) -> jnp.ndarray:
        grad = self._coerce(globalg)
        from .algorithms.functional.funcsgd import _sgd_step

        velocity, _ = _sgd_step(
            grad,
            jnp.zeros_like(self._velocity),
            self._velocity,
            jnp.asarray(self._stepsize, dtype=self._dtype),
            jnp.asarray(self._momentum, dtype=self._dtype),
        )
        self._velocity = velocity
        return velocity

    @property
    def contained_optimizer(self) -> "SGD":
        return self


class OptaxOptimizer:
    """Adapter exposing ``ascent(grad)`` over any optax
    ``GradientTransformation`` — the analog of the reference's generic
    ``TorchOptimizer`` (``optimizers.py:31-98``).

    Note: optax transforms *descend*: feeding the ascent gradient directly and
    negating the resulting update preserves ascent semantics (the gradient
    statistics inside the transform are sign-symmetric)."""

    def __init__(self, transformation, *, solution_length: int, dtype="float32"):
        self._dtype = to_jax_dtype(dtype)
        self._length = int(solution_length)
        self._tx = transformation
        self._opt_state = self._tx.init(jnp.zeros(self._length, dtype=self._dtype))

    def ascent(self, globalg, *, cloned_result: bool = True) -> jnp.ndarray:
        grad = ensure_array_length_and_dtype(globalg, self._length, self._dtype, about="OptaxOptimizer.ascent")
        updates, self._opt_state = self._tx.update(grad, self._opt_state)
        return -jnp.asarray(updates)

    @property
    def contained_optimizer(self):
        return self._tx


def get_optimizer_class(s: str, optimizer_config: Optional[dict] = None) -> Callable:
    """String -> optimizer class or configured factory
    (reference ``optimizers.py:421-456``)."""
    if s in ("clipsgd", "clipsga", "clipup"):
        cls = ClipUp
    elif s == "adam":
        cls = Adam
    elif s in ("sgd", "sga"):
        cls = SGD
    else:
        raise ValueError(f"Unknown optimizer: {s!r}")
    if optimizer_config is None:
        return cls

    def factory(*args, **kwargs):
        conf = dict(optimizer_config)
        conf.update(kwargs)
        return cls(*args, **conf)

    return factory
