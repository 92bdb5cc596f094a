"""Functional PGPE: ``pgpe`` / ``pgpe_ask`` / ``pgpe_tell``.

Parity: reference ``algorithms/functional/funcpgpe.py:29-384``: symmetric
(antithetic) sampling by default, 0-centered ranking, a composed functional
optimizer (ClipUp by default) for the center, and a controlled stdev update
(``stdev_max_change``). JAX-ism: ``pgpe_ask`` takes an explicit PRNG key.
"""

from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp

from ...distributions import (
    SeparableGaussian,
    SymmetricSeparableGaussian,
    make_functional_grad_estimator,
)
from ...tools.misc import modify_vector, stdev_from_radius
from ...tools.pytree import pytree_dataclass, replace, static_field
from .misc import as_vector_like, get_functional_optimizer

__all__ = [
    "PGPEState",
    "pgpe",
    "pgpe_ask",
    "pgpe_tell",
    "pgpe_ask_lowrank",
    "pgpe_tell_lowrank",
    "pgpe_ask_trunk_delta",
    "pgpe_tell_trunk_delta",
    "pgpe_health",
]


@pytree_dataclass
class PGPEState:
    optimizer_state: tuple
    stdev: jnp.ndarray
    stdev_learning_rate: jnp.ndarray
    stdev_min: jnp.ndarray
    stdev_max: jnp.ndarray
    stdev_max_change: jnp.ndarray
    optimizer: Union[str, tuple] = static_field()
    ranking_method: str = static_field()
    maximize: bool = static_field()
    symmetric: bool = static_field()


def _dist_class(symmetric: bool):
    return SymmetricSeparableGaussian if symmetric else SeparableGaussian


def _grad_divisors(symmetric: bool) -> dict:
    denominator = "num_directions" if symmetric else "num_solutions"
    return {"divide_mu_grad_by": denominator, "divide_sigma_grad_by": denominator}


def pgpe(
    *,
    center_init,
    center_learning_rate,
    stdev_learning_rate,
    objective_sense: str,
    ranking_method: str = "centered",
    optimizer: Union[str, tuple] = "clipup",
    optimizer_config: Optional[dict] = None,
    stdev_init: Optional[Union[float, jnp.ndarray]] = None,
    radius_init: Optional[Union[float, jnp.ndarray]] = None,
    stdev_min: Optional[Union[float, jnp.ndarray]] = None,
    stdev_max: Optional[Union[float, jnp.ndarray]] = None,
    stdev_max_change: Optional[Union[float, jnp.ndarray]] = 0.2,
    symmetric: bool = True,
) -> PGPEState:
    """Initial PGPE state (reference ``funcpgpe.py:67-301``)."""
    center_init = jnp.asarray(center_init)
    if objective_sense not in ("min", "max"):
        raise ValueError(f"objective_sense must be 'min' or 'max', got {objective_sense!r}")
    if (stdev_init is None) == (radius_init is None):
        raise ValueError("Exactly one of stdev_init / radius_init must be provided")
    if radius_init is not None:
        stdev_init = stdev_from_radius(float(radius_init), center_init.shape[-1])
    stdev = jnp.broadcast_to(as_vector_like(stdev_init, center_init, 0.0), center_init.shape)

    opt_init, _, _ = get_functional_optimizer(optimizer)
    optimizer_state = opt_init(
        center_init=center_init,
        center_learning_rate=center_learning_rate,
        **(optimizer_config or {}),
    )

    return PGPEState(
        optimizer_state=optimizer_state,
        stdev=stdev,
        stdev_learning_rate=jnp.asarray(stdev_learning_rate, dtype=center_init.dtype),
        stdev_min=as_vector_like(stdev_min, center_init, 0.0),
        stdev_max=as_vector_like(stdev_max, center_init, float("inf")),
        stdev_max_change=as_vector_like(stdev_max_change, center_init, float("inf")),
        optimizer=optimizer,
        ranking_method=str(ranking_method),
        maximize=(objective_sense == "max"),
        symmetric=bool(symmetric),
    )


def pgpe_ask(key, state: PGPEState, *, popsize: int) -> jnp.ndarray:
    """Sample a population around the optimizer's current center
    (reference ``funcpgpe.py:303-320``)."""
    _, opt_ask, _ = get_functional_optimizer(state.optimizer)
    center = opt_ask(state.optimizer_state)
    return _dist_class(state.symmetric).functional_sample(
        int(popsize), {"mu": center, "sigma": state.stdev}, key=key
    )


def pgpe_tell(state: PGPEState, values, evals) -> PGPEState:
    """Estimate gradients from the evaluated population and update both the
    optimizer (center) and the controlled stdev (reference
    ``funcpgpe.py:333-384``)."""
    _, opt_ask, opt_tell = get_functional_optimizer(state.optimizer)
    dist = _dist_class(state.symmetric)
    grad_fn = make_functional_grad_estimator(
        dist,
        objective_sense=("max" if state.maximize else "min"),
        ranking_method=state.ranking_method,
    )
    grads = grad_fn(
        values,
        evals,
        {
            "mu": opt_ask(state.optimizer_state),
            "sigma": state.stdev,
            **_grad_divisors(state.symmetric),
        },
    )
    new_optimizer_state = opt_tell(state.optimizer_state, follow_grad=grads["mu"])
    target_stdev = state.stdev + state.stdev_learning_rate[..., None] * grads["sigma"]
    new_stdev = modify_vector(
        state.stdev,
        target_stdev,
        lb=state.stdev_min,
        ub=state.stdev_max,
        max_change=state.stdev_max_change,
    )
    return replace(state, optimizer_state=new_optimizer_state, stdev=new_stdev)


def pgpe_health(state: PGPEState) -> dict:
    """Algorithm-health scalars for the search-health plane
    (docs/observability.md "Search health").

    Pure and jit-safe: returns DEVICE scalars (``stdev_norm`` always;
    ``velocity_norm`` when the optimizer state carries a velocity, i.e.
    ClipUp or momentum SGD), so callers can compute them inside a compiled
    generation step and apply the usual lag-by-one host read."""
    out = {"stdev_norm": jnp.linalg.norm(state.stdev)}
    velocity = getattr(state.optimizer_state, "velocity", None)
    if velocity is not None:
        out["velocity_norm"] = jnp.linalg.norm(velocity)
    return out


def pgpe_ask_trunk_delta(key, state: PGPEState, *, popsize: int, rank: int, policy):
    """Sample a shared-trunk + per-lane low-rank-delta population around the
    current center (docs/policies.md).

    ``policy`` is the ``FlatParamsPolicy`` being evolved — the delta factors
    are structured per parameter leaf (rank-1 per 2-D weight block), so the
    sampler needs the policy's parameter tree. Returns a
    ``TrunkDeltaParamsBatch`` the vectorized rollout engine evaluates with
    ONE shared-trunk GEMM per layer; the PGPE update is
    :func:`pgpe_tell_trunk_delta` (the factored gradients of low-rank mode,
    leaf by leaf from the factors: the batch holds no basis)."""
    import jax

    if not state.symmetric:
        raise ValueError(
            "pgpe_ask_trunk_delta requires symmetric=True (the PGPE default)"
        )
    # lazy import: algorithms (L2) must not import neuroevolution (L3) at
    # module scope
    from ...neuroevolution.net.lowrank import sample_trunk_delta_factors

    _, opt_ask, _ = get_functional_optimizer(state.optimizer)
    center = opt_ask(state.optimizer_state)
    key_factors, key_coeffs = jax.random.split(key)
    factors = sample_trunk_delta_factors(key_factors, policy, state.stdev, int(rank))
    return SymmetricSeparableGaussian._sample_trunk_delta(
        key_coeffs, {"mu": center, "sigma": state.stdev}, int(popsize), int(rank), factors
    )


# ----------------------- low-rank perturbation mode -------------------------
# The MXU path for wide policies (VERDICT r2 #2): the population is
# theta_i = c + (sigma * B) z_i with a shared per-generation basis B and
# per-lane coefficients z_i. The sampling and factored-gradient math live on
# SymmetricSeparableGaussian (distributions.py) so the OO API shares ONE
# implementation with this functional form; see the commentary there for the
# variance-calibration caveat at small rank.


def pgpe_ask_lowrank(key, state: PGPEState, *, popsize: int, rank: int):
    """Sample a low-rank-structured population around the current center.

    Returns a ``LowRankParamsBatch`` the vectorized rollout engine accepts in
    place of a dense ``(popsize, L)`` matrix. Requires symmetric mode (the
    PGPE default) and an even ``popsize``."""
    if not state.symmetric:
        raise ValueError("pgpe_ask_lowrank requires symmetric=True (the PGPE default)")
    _, opt_ask, _ = get_functional_optimizer(state.optimizer)
    center = opt_ask(state.optimizer_state)
    return SymmetricSeparableGaussian._sample_lowrank(
        key, {"mu": center, "sigma": state.stdev}, int(popsize), int(rank)
    )


def pgpe_tell_lowrank(state: PGPEState, params, evals) -> PGPEState:
    """The PGPE update from a low-rank-evaluated population (the gradients
    read only the shared effective basis and the per-lane coefficients):
    identical math to ``pgpe_tell`` on the materialized population, computed
    in O(L * rank) without building it."""
    from ...tools.ranking import rank as rank_fn

    if not state.symmetric:
        raise ValueError("pgpe_tell_lowrank requires symmetric=True (the PGPE default)")
    _, opt_ask, opt_tell = get_functional_optimizer(state.optimizer)
    weights = rank_fn(
        jnp.asarray(evals), state.ranking_method, higher_is_better=state.maximize
    )
    grads = SymmetricSeparableGaussian._compute_gradients_lowrank(
        {
            "mu": opt_ask(state.optimizer_state),
            "sigma": state.stdev,
            **_grad_divisors(True),
        },
        params,
        weights,
        state.ranking_method,
    )
    new_optimizer_state = opt_tell(state.optimizer_state, follow_grad=grads["mu"])
    target_stdev = state.stdev + state.stdev_learning_rate[..., None] * grads["sigma"]
    new_stdev = modify_vector(
        state.stdev,
        target_stdev,
        lb=state.stdev_min,
        ub=state.stdev_max,
        max_change=state.stdev_max_change,
    )
    return replace(state, optimizer_state=new_optimizer_state, stdev=new_stdev)


def pgpe_tell_trunk_delta(state: PGPEState, params, evals) -> PGPEState:
    """The PGPE update from a trunk-delta-evaluated population: the same
    math, leaf by leaf from the batch's factors (it holds no basis). The
    center's part runs to its end before the stdev's starts and the stdev is
    rewritten leaf by leaf, so under ``jax.jit(..., donate_argnums=0)`` the
    update needs one vector of the parameters' length beside the state (the
    OO ``PGPE(lowrank_rank=("trunk_delta", k))`` runs the same two gradient
    functions in the same order)."""
    import jax

    from ...tools.ranking import rank as rank_fn

    if not state.symmetric:
        raise ValueError("pgpe_tell_trunk_delta requires symmetric=True (the PGPE default)")
    _, opt_ask, opt_tell = get_functional_optimizer(state.optimizer)
    weights = rank_fn(
        jnp.asarray(evals), state.ranking_method, higher_is_better=state.maximize
    )
    dist = SymmetricSeparableGaussian
    parameters = {
        "mu": opt_ask(state.optimizer_state),
        "sigma": state.stdev,
        **_grad_divisors(True),
    }
    mu_grad = dist._trunk_delta_mu_gradient(parameters, params, weights, state.ranking_method)
    new_optimizer_state = opt_tell(state.optimizer_state, follow_grad=mu_grad)
    new_optimizer_state, stdev = jax.lax.optimization_barrier((new_optimizer_state, state.stdev))
    rate = state.stdev_learning_rate[..., None]
    target_stdev = dist._trunk_delta_sigma_gradient(
        {**parameters, "sigma": stdev},
        params,
        weights,
        state.ranking_method,
        into=lambda leaf, grad_leaf: leaf + rate * grad_leaf,
    )
    new_stdev = modify_vector(
        stdev,
        target_stdev,
        lb=state.stdev_min,
        ub=state.stdev_max,
        max_change=state.stdev_max_change,
    )
    return replace(state, optimizer_state=new_optimizer_state, stdev=new_stdev)
