"""MAP-Elites: quality-diversity archive over a feature hypergrid.

Parity: reference ``algorithms/mapelites.py`` — vmapped per-cell
best-solution selection (``mapelites.py:24-67``), fully vectorized ``_step``
(``mapelites.py:380-401``), ``make_feature_grid`` (``mapelites.py:403-505``).
The per-cell selection maps 1:1 onto ``jax.vmap`` and the whole selection step
is jitted.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Iterable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import Problem, SolutionBatch
from ..observability.scopes import phase, phase_jit
from ..tools.misc import to_jax_dtype
from .ga import ExtendedPopulationMixin
from .searchalgorithm import SearchAlgorithm, SinglePopulationAlgorithmMixin

__all__ = ["MAPElites"]


def _best_solution_considering_feature(objective_sense, decision_values, evals, feature_grid):
    """Pick, for one cell, the best solution whose features fall within the
    cell bounds (reference ``mapelites.py:24-53``)."""
    feature_lb = feature_grid[:, 0]
    feature_ub = feature_grid[:, 1]
    penalty = jnp.inf if objective_sense == "min" else -jnp.inf
    argbest = jnp.argmin if objective_sense == "min" else jnp.argmax
    fitnesses = evals[:, 0]
    features = evals[:, 1:]
    suitable = jnp.all(features >= feature_lb, axis=-1) & jnp.all(features <= feature_ub, axis=-1)
    processed = jnp.where(suitable, fitnesses, penalty)
    index = argbest(processed)
    return decision_values[index], evals[index], suitable[index]


@partial(phase_jit, "update", static_argnames=("objective_sense",))
def _best_solutions_for_all_cells(objective_sense, decision_values, evals, feature_grid):
    """vmap over grid cells (reference ``mapelites.py:56-67``)."""
    return jax.vmap(
        lambda grid: _best_solution_considering_feature(
            objective_sense, decision_values, evals, grid
        )
    )(feature_grid)


class MAPElites(SearchAlgorithm, SinglePopulationAlgorithmMixin, ExtendedPopulationMixin):
    """MAP-Elites (reference ``mapelites.py:70``): the population is the
    archive — one solution per feature-grid cell. Requires the problem to be
    single-objective with ``eval_data_length`` equal to the number of
    features."""

    def __init__(
        self,
        problem: Problem,
        *,
        operators: Iterable,
        feature_grid: Iterable,
        re_evaluate: bool = True,
        re_evaluate_parents_first: Optional[bool] = None,
    ):
        problem.ensure_numeric()
        if problem.is_multi_objective:
            raise ValueError("MAPElites supports single-objective problems only")
        if problem.eval_data_length is None or problem.eval_data_length == 0:
            raise ValueError(
                "MAPElites requires eval_data_length >= 1 (the features of each solution)"
            )
        SearchAlgorithm.__init__(self, problem)
        self._sense = problem.senses[0]
        self._feature_grid = jnp.asarray(feature_grid, dtype=problem.eval_dtype)
        if self._feature_grid.ndim != 3 or self._feature_grid.shape[-1] != 2:
            raise ValueError(
                "feature_grid must have shape (num_cells, num_features, 2); "
                f"got {tuple(self._feature_grid.shape)}"
            )
        if self._feature_grid.shape[1] != problem.eval_data_length:
            raise ValueError(
                f"feature_grid declares {self._feature_grid.shape[1]} features but the "
                f"problem's eval_data_length is {problem.eval_data_length}"
            )
        num_cells = self._feature_grid.shape[0]
        self._population = problem.generate_batch(num_cells)
        self._filled = jnp.zeros(num_cells, dtype=bool)
        ExtendedPopulationMixin.__init__(
            self,
            re_evaluate=re_evaluate,
            re_evaluate_parents_first=re_evaluate_parents_first,
            operators=operators,
        )
        SinglePopulationAlgorithmMixin.__init__(self)

    @property
    def population(self) -> SolutionBatch:
        return self._population

    @property
    def filled(self) -> jnp.ndarray:
        """Boolean mask: ``filled[i]`` is True when the solution stored in the
        i-th cell genuinely satisfies that cell's feature bounds
        (reference ``mapelites.py:352-378``)."""
        return self._filled

    def _step(self):
        extended = self._make_extended_population(split=False)
        with phase("update"):
            values, evals, suitable = _best_solutions_for_all_cells(
                self._sense,
                jnp.asarray(extended.values),
                extended.evals,
                self._feature_grid,
            )
            self._population.set_values(values, keep_evals=True)
            self._population.set_evals(evals)
            self._filled = suitable

    @staticmethod
    def make_feature_grid(
        lower_bounds: Iterable,
        upper_bounds: Iterable,
        num_bins: Union[int, Iterable[int]],
        *,
        dtype=None,
        device=None,  # accepted for API parity; placement is via shardings
    ) -> jnp.ndarray:
        """Uniform hypergrid of (num_cells, num_features, 2) bounds; outermost
        bins extend to +-inf (reference ``mapelites.py:403-505``)."""
        dtype = to_jax_dtype(dtype) if dtype is not None else jnp.float32
        lower_bounds = np.asarray(lower_bounds, dtype=np.float64)
        upper_bounds = np.asarray(upper_bounds, dtype=np.float64)
        if lower_bounds.ndim != 1 or lower_bounds.shape != upper_bounds.shape:
            raise ValueError("lower_bounds / upper_bounds must be 1-D and equal-length")
        n_features = lower_bounds.shape[0]
        if np.isscalar(num_bins) or np.asarray(num_bins).ndim == 0:
            num_bins = [int(num_bins)] * n_features
        num_bins = [int(b) for b in num_bins]
        per_feature = []
        for lb, ub, bins in zip(lower_bounds, upper_bounds, num_bins):
            edges = np.concatenate([[-np.inf], np.linspace(lb, ub, bins - 1), [np.inf]])
            intervals = np.stack([edges[:-1], edges[1:]], axis=1)  # (bins, 2)
            per_feature.append(intervals)
        cells = [
            np.stack(combo, axis=0) for combo in itertools.product(*per_feature)
        ]  # each (n_features, 2)
        return jnp.asarray(np.stack(cells), dtype=dtype)
