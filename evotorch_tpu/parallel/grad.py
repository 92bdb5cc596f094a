"""Sharded ES-gradient estimation: the TPU form of the reference's
distributed mode.

GSPMD: the sample/evaluate/rank/grad pipeline is written ONCE as the
global program — sample the full population, rank GLOBALLY, compute the
gradients — with the sample matrix pinned to the mesh's population layout;
XLA partitions the math and inserts the reductions. Global ranking is the
reference's SINGLE-PROCESS semantics (``gaussian.py:199-272`` without the
actor split), so the estimate is exactly what a one-device run computes, at
any mesh shape and ANY population size (no divisibility constraint — GSPMD
handles uneven layouts). The reference's DISTRIBUTED mode
(``core.py:2762-3073``: every actor ranks its own sub-population) is a
different search, not a layout, and has no form here.
"""

from __future__ import annotations

from typing import Callable, Optional, Type

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..tools.lowrank import dense_values
from ..tools.ranking import rank
from .evaluate import population_spec
from .mesh import default_mesh

__all__ = ["make_sharded_grad_estimator"]


def make_sharded_grad_estimator(
    distribution_class: Type,
    fitness_func: Callable,
    *,
    objective_sense: str,
    ranking_method: str = "centered",
    mesh: Optional[Mesh] = None,
    axis_name: str = "pop",
    with_aux: bool = False,
    lowrank_rank: Optional[int] = None,
) -> Callable:
    """Build ``g(key, num_solutions, parameters) -> grads`` where the
    sample/evaluate/rank/grad pipeline runs sharded over the mesh and the
    returned gradient dict is replicated on all devices.

    Ranking is global (the reference's single-process semantics) and
    ``num_solutions`` may be ANY size.

    With ``with_aux=True`` the estimator returns ``(grads, aux)`` where
    ``aux["mean_eval"]`` is the population-mean fitness (what the
    reference's main process reconstructs from the per-actor ``mean_eval``
    entries, ``gaussian.py:246-272``).

    With ``lowrank_rank`` the population is sampled in factored (low-rank)
    form and the gradients come from the factors in O(L * rank); only the
    fitness evaluation materializes the dense matrix (plain fitness
    functions consume dense rows)."""
    if mesh is None:
        mesh = default_mesh((axis_name,))
    higher_is_better = {"max": True, "min": False}[objective_sense]
    pop_sharding = NamedSharding(mesh, population_spec(mesh))

    # one jitted program per (popsize, static params): repeated calls must
    # hit JAX's dispatch cache instead of retracing every generation
    compiled: dict = {}

    def _build_global(num_solutions: int, static_items: tuple):
        static_params = dict(static_items)

        def fn(key, array_params):
            parameters = {**array_params, **static_params}
            if lowrank_rank is not None:
                samples = distribution_class._sample_lowrank(
                    key, parameters, num_solutions, lowrank_rank
                )
                samples = samples._replace(
                    coeffs=jax.lax.with_sharding_constraint(
                        samples.coeffs, pop_sharding
                    )
                )
                fitnesses = fitness_func(dense_values(samples))
            else:
                samples = distribution_class._sample(key, parameters, num_solutions)
                samples = jax.lax.with_sharding_constraint(samples, pop_sharding)
                fitnesses = fitness_func(samples)
            weights = rank(fitnesses, ranking_method, higher_is_better=higher_is_better)
            grads = distribution_class._compute_gradients(
                parameters, samples, weights, ranking_method
            )
            if with_aux:
                aux = {"mean_eval": jnp.mean(fitnesses)}
                if lowrank_rank is not None:
                    # the global basis, for the caller's subspace-exhaustion
                    # diagnostic (basis_capture)
                    aux["basis"] = samples.basis
                return grads, aux
            return grads

        return jax.jit(fn)

    def estimator(key, num_solutions: int, parameters: dict):
        num_solutions = int(num_solutions)

        # strings ("divide_mu_grad_by", ...) and structural floats
        # ("parenthood_ratio") are not JAX types: close over them statically
        static_params = {
            k: v
            for k, v in parameters.items()
            if isinstance(v, str) or k == "parenthood_ratio"
        }
        array_params = {k: v for k, v in parameters.items() if k not in static_params}

        cache_key = (num_solutions, tuple(sorted(static_params.items())))
        fn = compiled.get(cache_key)
        if fn is None:
            fn = compiled[cache_key] = _build_global(num_solutions, cache_key[1])
        return fn(key, array_params)

    return estimator
