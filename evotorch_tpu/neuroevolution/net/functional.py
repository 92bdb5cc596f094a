"""Flat-parameter policy interface.

Parity: reference ``net/functional.py:46-259`` — the
``ModuleExpectingFlatParameters`` wrapper that turns a network into a pure
function ``f(flat_params, x, h=None)`` by slicing a flat vector into named
parameters, and ``make_functional_module`` (``functional.py:203``). Also the
parameter-vector helpers of ``net/misc.py:26-116``
(``count_parameters``/``parameter_vector``/``fill_parameters``).

In JAX this is ``ravel_pytree`` rather than meta-device ``functional_call``
tricks: the unravel function is computed once from the module's parameter
template and is jit/vmap-transparent.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from .layers import Module

__all__ = [
    "FlatParamsPolicy",
    "make_functional_module",
    "count_parameters",
    "parameter_vector",
    "fill_parameters",
]


class FlatParamsPolicy:
    """A network exposed through a flat parameter vector
    (reference ``ModuleExpectingFlatParameters``, ``net/functional.py:46``).

    Usage::

        policy = FlatParamsPolicy(module, key=jax.random.key(0))
        flat0 = policy.init_parameters(key)      # (n,) template init
        y, h  = policy(flat, x)                  # stateless / fresh state
        y, h  = policy(flat, x, h)               # recurrent step
    """

    def __init__(self, module: Module, *, key=None):
        self.module = module
        template_key = key if key is not None else jax.random.key(0)
        made = {}

        def template(k):
            # traced for its shapes alone: a policy of hundreds of millions
            # of parameters is not initialised twice over to be measured
            flat, made["unravel"] = ravel_pytree(module.init(k))
            return flat

        self.parameter_count = int(jax.eval_shape(template, template_key).shape[0])
        self._unravel = made["unravel"]

    @property
    def num_parameters(self) -> int:
        return self.parameter_count

    def init_parameters(self, key) -> jnp.ndarray:
        """A freshly initialized flat parameter vector."""
        flat, _ = ravel_pytree(self.module.init(key))
        return flat

    def unravel(self, flat_params: jnp.ndarray) -> Any:
        """The module's parameter tree of one flat vector. The population
        engines (``net/vecrl.py``) ``vmap`` this over a dense ``(N, L)``
        population ONCE per program, at the rollout's edge, and step
        ``module.apply`` on the resulting tree: cutting the flat matrix into
        per-layer blocks is a physical copy on the TPU, not a view."""
        return self._unravel(flat_params)

    def initial_state(self):
        return self.module.initial_state()

    def __call__(self, flat_params, x, state=None) -> Tuple[jnp.ndarray, Any]:
        """The single-solution form: unravel, then apply. Fine where it runs
        once per call (``Policy``, ``to_policy``, the host path); inside a
        stepping loop use ``unravel`` outside it and ``module.apply`` inside."""
        params = self._unravel(flat_params)
        return self.module.apply(params, x, state)


def make_functional_module(module: Module, *, key=None) -> FlatParamsPolicy:
    """Reference ``net/functional.py:203``."""
    return FlatParamsPolicy(module, key=key)


def count_parameters(module: Module, *, key=None) -> int:
    """Reference ``net/misc.py:84``."""
    return FlatParamsPolicy(module, key=key).parameter_count


def parameter_vector(params: Any) -> jnp.ndarray:
    """Flatten a parameter pytree into one vector (reference ``net/misc.py:44``)."""
    flat, _ = ravel_pytree(params)
    return flat


def fill_parameters(template_params: Any, vector: jnp.ndarray) -> Any:
    """Inverse of :func:`parameter_vector` against a template pytree
    (reference ``net/misc.py:26``)."""
    _, unravel = ravel_pytree(template_params)
    return unravel(jnp.asarray(vector))
