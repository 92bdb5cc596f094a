"""Distribution-based searchers: the shared Gaussian engine and
PGPE / SNES / CEM / XNES.

Parity: reference ``algorithms/distributed/gaussian.py`` —
``GaussianSearchAlgorithm`` (``gaussian.py:35-500``: non-distributed step
``gaussian.py:274-367``, distributed step ``gaussian.py:199-272``, controlled
sigma update ``gaussian.py:369-419``), ``PGPE`` (``gaussian.py:503-743``),
``SNES`` (``gaussian.py:746-983``), ``CEM`` (``gaussian.py:986-1180``),
``XNES`` (``gaussian.py:1183-1405``).

TPU notes: "distributed" here no longer means Ray actors — with
``distributed=True`` the step calls ``problem.sample_and_compute_gradients``
whose sharded form runs the sample/eval/rank/grad pipeline over the device
mesh with a ``pmean`` reduction (see ``evotorch_tpu.parallel.grad``). The
adaptive-popsize loop driven by ``num_interactions`` (``gaussian.py:296-349``)
is host-side control flow around jitted evaluations, exactly as the reference
runs it around torch kernels.
"""

from __future__ import annotations

import math
from copy import deepcopy
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import Problem, SolutionBatch
from ..observability.scopes import phase, phase_jit
from ..distributions import (
    _split_params,
    Distribution,
    ExpGaussian,
    ExpSeparableGaussian,
    SeparableGaussian,
    SymmetricSeparableGaussian,
)
from ..optimizers import get_optimizer_class
from ..tools.misc import modify_tensor, to_stdev_init
from .searchalgorithm import SearchAlgorithm, SinglePopulationAlgorithmMixin

__all__ = ["GaussianSearchAlgorithm", "PGPE", "SNES", "CEM", "XNES"]


class GaussianSearchAlgorithm(SearchAlgorithm, SinglePopulationAlgorithmMixin):
    """Shared engine for PGPE/SNES/CEM/XNES (reference ``gaussian.py:35``)."""

    DISTRIBUTION_TYPE = NotImplemented
    DISTRIBUTION_PARAMS: Optional[dict] = None

    def __init__(
        self,
        problem: Problem,
        *,
        popsize: int,
        center_learning_rate: float,
        stdev_learning_rate: float,
        stdev_init=None,
        radius_init=None,
        num_interactions: Optional[int] = None,
        popsize_max: Optional[int] = None,
        optimizer=None,
        optimizer_config: Optional[dict] = None,
        ranking_method: Optional[str] = None,
        center_init=None,
        stdev_min=None,
        stdev_max=None,
        stdev_max_change=None,
        obj_index: Optional[int] = None,
        distributed: bool = False,
        popsize_weighted_grad_avg: Optional[bool] = None,
        ensure_even_popsize: bool = False,
        lowrank_rank=None,
    ):
        problem.ensure_numeric()
        problem.ensure_unbounded()

        SearchAlgorithm.__init__(
            self,
            problem,
            center=self._get_mu,
            stdev=self._get_sigma,
            mean_eval=self._get_mean_eval,
        )

        self._ensure_even_popsize = bool(ensure_even_popsize)
        if self._ensure_even_popsize and popsize % 2 != 0:
            raise ValueError(f"popsize must be even, got {popsize}")

        if not distributed and num_interactions is not None:
            self.add_status_getters({"popsize": self._get_popsize})

        if center_init is None:
            mu = problem.generate_values(1).reshape(-1)
        else:
            mu = problem.ensure_tensor_length_and_dtype(
                center_init, allow_scalar=False, about="center_init"
            )

        # one argument names the factored population's form and its rank
        lowrank_rank, trunk_delta_rank = _factored_form(lowrank_rank)
        if trunk_delta_rank is not None:
            # the trunk-delta update donates the center's buffer: the searcher
            # owns a copy, never the caller's array
            mu = jnp.array(mu, copy=True)

        stdev_init = to_stdev_init(
            solution_length=problem.solution_length, stdev_init=stdev_init, radius_init=radius_init
        )
        sigma = problem.ensure_tensor_length_and_dtype(stdev_init, about="stdev_init")

        dist_cls = self.DISTRIBUTION_TYPE
        dist_params = deepcopy(self.DISTRIBUTION_PARAMS) if self.DISTRIBUTION_PARAMS is not None else {}
        dist_params.update({"mu": mu, "sigma": sigma})
        self._distribution: Distribution = dist_cls(dist_params, dtype=problem.dtype)

        # factored (low-rank) population mode: the MXU path for wide policies
        # (tools/lowrank.py; sampling + gradients on the distribution class)
        self._lowrank_rank = lowrank_rank
        if self._lowrank_rank is not None:
            if not hasattr(dist_cls, "_sample_lowrank"):
                raise ValueError(
                    f"{dist_cls.__name__} has no factored sampler; "
                    "lowrank_rank requires symmetric PGPE "
                    "(SymmetricSeparableGaussian)"
                )
            # subspace-exhaustion guardrail (tools.lowrank.basis_capture):
            # every factored gradient estimate is confined to its
            # generation's rank-k basis span, so we track how much of the
            # ACCUMULATED gradient direction (an EMA over many bases — a
            # proxy for the dense gradient) each fresh basis can express.
            # A random basis captures ~sqrt(k/L) of any fixed direction;
            # persistently tiny capture means the search is mostly blind to
            # the direction it has been following — the measured failure
            # mode of the HalfCheetah rank-32 stall
            # (bench_curves/halfcheetah_lowrank_cpu_r5.jsonl).
            self._basis_capture_dev = None  # device scalar: stays lazy
            self._grad_direction_ema = None
            self._low_capture_streak = 0
            self._capture_warned = False
            # the device->host sync happens on status READ (like _mean_eval),
            # never inside the step's dispatch path
            self.add_status_getters(
                {
                    "basis_capture": lambda: (
                        None
                        if self._basis_capture_dev is None
                        else float(self._basis_capture_dev)
                    )
                }
            )

        # the shared-trunk form of the factored population (docs/policies.md):
        # the factors are structured by the problem's policy and the batch
        # holds no basis, so the update is one donated device program that
        # writes center, stdev and optimizer state leaf by leaf
        self._trunk_delta_rank = trunk_delta_rank
        self._trunk_delta_tell = None
        if self._trunk_delta_rank is not None:
            if not hasattr(dist_cls, "_sample_trunk_delta") or distributed:
                raise ValueError(
                    "lowrank_rank=('trunk_delta', k) requires symmetric, non-distributed"
                    " PGPE (SymmetricSeparableGaussian)"
                )
            if not hasattr(problem, "policy"):
                raise ValueError(
                    "lowrank_rank=('trunk_delta', k) needs a problem with a policy (the"
                    " factors follow its parameter tree): a neuroevolution problem"
                )

        self._popsize = int(popsize)
        self._popsize_max = None if popsize_max is None else int(popsize_max)
        self._num_interactions = None if num_interactions is None else int(num_interactions)

        self._center_learning_rate = float(center_learning_rate)
        self._stdev_learning_rate = float(stdev_learning_rate)
        self._optimizer = self._initialize_optimizer(self._center_learning_rate, optimizer, optimizer_config)
        self._ranking_method = None if ranking_method is None else str(ranking_method)

        # algorithm-health scalars (docs/observability.md "Search health"):
        # same device-scalar discipline as _mean_eval / basis_capture — the
        # update step only ENQUEUES device scalars; the host float
        # materializes when the status key is actually read
        self._center_update_norm_dev = None
        # bound methods, not lambdas: the curve runner's checkpoint bundles
        # pickle the whole searcher, and a lambda getter would break that
        self.add_status_getters(
            {
                "stdev_norm": self._get_stdev_norm,
                "center_update_norm": self._get_center_update_norm,
                "clipup_velocity_norm": self._get_clipup_velocity_norm,
            }
        )

        def ensure(value, about):
            # the trunk-delta form clamps leaf by leaf with scalars: at the
            # sizes it exists for, one more vector of the solution's length is
            # memory the update lacks
            if self._trunk_delta_rank is not None:
                if np.ndim(value) != 0:
                    raise ValueError(f"with lowrank_rank=('trunk_delta', k), {about} is a scalar")
                return jnp.asarray(value, dtype=problem.dtype)
            return problem.ensure_tensor_length_and_dtype(value, about=about)

        self._stdev_min = None if stdev_min is None else ensure(stdev_min, about="stdev_min")
        self._stdev_max = None if stdev_max is None else ensure(stdev_max, about="stdev_max")
        self._stdev_max_change = (
            None if stdev_max_change is None else ensure(stdev_max_change, about="stdev_max_change")
        )

        self._obj_index = problem.normalize_obj_index(obj_index)
        self._distributed = bool(distributed)

        if distributed:
            self._step = self._step_distributed
        else:
            self._step = self._step_non_distributed
            if popsize_weighted_grad_avg is not None:
                raise ValueError(
                    "popsize_weighted_grad_avg is only meaningful in distributed mode"
                )

        if popsize_weighted_grad_avg is None:
            self._popsize_weighted_grad_avg = num_interactions is None
        else:
            self._popsize_weighted_grad_avg = bool(popsize_weighted_grad_avg)

        self._mean_eval: Optional[float] = None
        self._population: Optional[SolutionBatch] = None
        self._first_iter = True

        SinglePopulationAlgorithmMixin.__init__(
            self, exclude={"mean_eval"}, enable=(not distributed)
        )

    # ------------------------------------------------------------ properties
    @property
    def population(self) -> SolutionBatch:
        if self._population is None:
            raise RuntimeError("The population is not ready yet; take a step first")
        return self._population

    @property
    def distribution(self) -> Distribution:
        return self._distribution

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def obj_index(self) -> int:
        return self._obj_index

    def _get_mu(self):
        return self._distribution.parameters["mu"]

    def _get_sigma(self):
        sigma = self._distribution.parameters["sigma"]
        return sigma

    def _get_mean_eval(self):
        # _mean_eval is kept as a device scalar (no sync in the hot loop);
        # the host float materializes only when the status is actually read
        return None if self._mean_eval is None else float(self._mean_eval)

    def _get_stdev_norm(self):
        # computed on READ from the current distribution parameters — no
        # per-step bookkeeping, and value-identical to the host-side
        # jnp.linalg.norm(status["stdev"]) it replaces in the examples
        return float(jnp.linalg.norm(self._distribution.parameters["sigma"]))

    def _get_center_update_norm(self):
        return (
            None
            if self._center_update_norm_dev is None
            else float(self._center_update_norm_dev)
        )

    def _get_clipup_velocity_norm(self):
        velocity = getattr(self._optimizer, "_velocity", None)
        return None if velocity is None else float(jnp.linalg.norm(velocity))

    def _get_popsize(self):
        return 0 if self._population is None else len(self._population)

    # -------------------------------------------------------------- plumbing
    def _initialize_optimizer(self, learning_rate, optimizer, optimizer_config):
        if optimizer is None:
            return None
        if isinstance(optimizer, str):
            cls = get_optimizer_class(optimizer, optimizer_config)
            return cls(
                stepsize=float(learning_rate),
                dtype=self._distribution.dtype,
                solution_length=self._distribution.solution_length,
            )
        return optimizer

    def _step(self):  # replaced in __init__
        raise NotImplementedError

    # -------------------------------------------------------- non-distributed
    def _sample_population(self, popsize: int, *, basis=None) -> SolutionBatch:
        """``basis``: what later rounds of one generation share with its
        first (the low-rank basis; the trunk-delta factors)."""
        if self._trunk_delta_rank is not None:
            samples = self._distribution.sample_trunk_delta(
                popsize,
                self._trunk_delta_rank,
                self._problem.policy,
                key=self._problem.next_rng_key(),
                factors=basis,
            )
            return SolutionBatch(self._problem, values=samples)
        if self._lowrank_rank is not None:
            samples = self._distribution.sample_lowrank(
                popsize,
                self._lowrank_rank,
                key=self._problem.next_rng_key(),
                basis=basis,
            )
            return SolutionBatch(self._problem, values=samples)
        samples = self._distribution.sample(popsize, key=self._problem.next_rng_key())
        return SolutionBatch(self._problem, samples.shape[0], values=samples)

    def _fill_and_eval_pop(self):
        """Sample + evaluate, with the adaptive-popsize loop when
        ``num_interactions`` is configured (reference ``gaussian.py:276-349``).
        In factored (low-rank) mode the generation's first round draws the
        basis and every later round samples fresh coefficients against it, so
        the per-round batches stay concatenable (SolutionBatch.cat of
        shared-basis factored batches). ``ask`` is the sampling alone: the
        evaluation wears its own phase, the loop's reading of the interaction
        counter (a wait for the evaluation) is ``status``."""
        problem = self._problem
        if self._num_interactions is None:
            with phase("ask"):
                self._population = self._sample_population(self._popsize)
            problem.evaluate(self._population)
            return
        with phase("status"):
            first_count = int(problem.status.get("total_interaction_count", 0))
        batches = []
        total_popsize = 0
        prev_made = -1
        gen_basis = None
        while True:
            with phase("ask"):
                batch = self._sample_population(self._popsize, basis=gen_basis)
                if gen_basis is None:
                    if self._lowrank_rank is not None:
                        gen_basis = batch.values.basis
                    elif self._trunk_delta_rank is not None:
                        gen_basis = batch.values.factors
            problem.evaluate(batch)
            batches.append(batch)
            total_popsize += len(batch)
            if self._popsize_max is not None and total_popsize >= self._popsize_max:
                break
            with phase("status"):
                interactions_made = int(problem.status.get("total_interaction_count", 0)) - first_count
            if interactions_made > self._num_interactions:
                break
            if "total_interaction_count" not in problem.status:
                break  # the problem does not report interactions; avoid looping forever
            if interactions_made <= prev_made:
                break  # counter stopped advancing; the budget is unreachable
            prev_made = interactions_made
        with phase("ask"):
            self._population = batches[0] if len(batches) == 1 else SolutionBatch.cat(batches)

    # capture below this for _CAPTURE_WARN_STREAK consecutive generations =>
    # subspace exhaustion warning. 0.1 sits between sqrt(k/L) of configs
    # measured to stall (HalfCheetah rank 32 at L~5.8k: 0.074) and configs
    # measured to train through (rank 64: 0.105).
    _CAPTURE_WARN_THRESHOLD = 0.1
    _CAPTURE_WARN_STREAK = 3

    def _update_basis_capture(self, basis, mu_grad):
        """Track the fraction of the accumulated gradient direction the
        CURRENT generation's basis spans, and warn once on persistent
        subspace exhaustion (see the constructor commentary).

        Device-scalar discipline (VERDICT r1 item 6: no device->host sync in
        the hot loop): each generation ENQUEUES its capture as a device
        scalar and host-processes the PREVIOUS generation's — that scalar's
        dispatch has retired behind the current generation's work, so the
        ``float()`` is a cheap transfer, not a pipeline stall. The streak
        bookkeeping and the warning therefore lag one generation."""
        import warnings

        from ..tools.lowrank import basis_capture

        prev = self._basis_capture_dev
        if prev is not None:
            capture = float(prev)
            if capture < self._CAPTURE_WARN_THRESHOLD:
                self._low_capture_streak += 1
            else:
                self._low_capture_streak = 0
            if (
                self._low_capture_streak >= self._CAPTURE_WARN_STREAK
                and not self._capture_warned
            ):
                self._capture_warned = True
                L = int(self._distribution.solution_length)
                warnings.warn(
                    "factored (low-rank) search subspace exhaustion: the "
                    f"rank-{self._lowrank_rank} basis captures only "
                    f"{capture:.1%} of the estimated dense gradient "
                    f"direction over {self._low_capture_streak} consecutive "
                    f"generations (random-basis expectation at L={L}: "
                    f"~{math.sqrt(self._lowrank_rank / max(L, 1)):.1%}). "
                    "Most of the gradient signal is not expressible in the "
                    "subspace and progress is likely to stall — consider "
                    "increasing lowrank_rank (status key: basis_capture).",
                    stacklevel=3,
                )
        if self._grad_direction_ema is not None:
            # enqueued lazily; read back on the NEXT generation (or on
            # status read, whichever comes first)
            self._basis_capture_dev = basis_capture(basis, self._grad_direction_ema)
        norm = jnp.linalg.norm(mu_grad)
        direction = mu_grad / jnp.maximum(norm, 1e-30)
        if self._grad_direction_ema is None:
            self._grad_direction_ema = direction
        else:
            # device-side EMA: no host sync beyond the one scalar capture read
            self._grad_direction_ema = (
                0.8 * self._grad_direction_ema + 0.2 * direction
            )

    def _step_non_distributed(self):
        """Reference ``gaussian.py:274-367``: from generation 1 on, compute
        gradients from the previous population, update the distribution, then
        resample and evaluate. The phases (``observability/scopes.py``) are
        siblings: ``grad``, ``update``, ``ask``, ``evaluate`` (worn by
        ``Problem.evaluate``), ``status``."""
        if self._first_iter:
            self._first_iter = False
        else:
            pop = self._population
            obj_sense = self._problem.senses[self._obj_index]
            if self._trunk_delta_rank is not None:
                # rank, gradient and update are one donated program
                with phase("update"):
                    self._update_trunk_delta(pop.values, pop.evals[:, self._obj_index], obj_sense)
            else:
                with phase("grad"):
                    samples = pop.values
                    grads = self._distribution.compute_gradients(
                        samples,
                        pop.evals[:, self._obj_index],
                        objective_sense=obj_sense,
                        ranking_method=self._ranking_method if self._ranking_method is not None else "raw",
                    )
                    if self._lowrank_rank is not None:
                        # basis_capture guardrail: measured against the basis the
                        # gradient was just estimated in, BEFORE that gradient
                        # enters the direction EMA
                        self._update_basis_capture(samples.basis, grads["mu"])
                with phase("update"):
                    self._update_distribution(grads)
        self._fill_and_eval_pop()
        with phase("status"):
            self._mean_eval = jnp.nanmean(self._population.evals[:, self._obj_index])

    # ------------------------------------------------------------ distributed
    def _step_distributed(self):
        """Reference ``gaussian.py:199-272``: gather per-shard gradient dicts
        and average them (weighted by sub-population size when configured).
        ``sample_and_compute_gradients`` wears ``ask``, ``evaluate`` and
        ``grad`` itself."""
        results = self._problem.sample_and_compute_gradients(
            self._distribution,
            self._popsize,
            popsize_max=self._popsize_max,
            num_interactions=self._num_interactions,
            ranking_method=self._ranking_method if self._ranking_method is not None else "raw",
            obj_index=self._obj_index,
            lowrank_rank=self._lowrank_rank,
        )
        with phase("grad"):
            grads_list = [r["gradients"] for r in results]
            nums = np.asarray([r["num_solutions"] for r in results], dtype=np.float64)
            rel = nums / nums.sum()  # population-size weighting (host-side floats)
            weights = rel if self._popsize_weighted_grad_avg else np.full(
                len(results), 1.0 / len(results)
            )
            avg = {}
            for k in grads_list[0]:
                avg[k] = sum(w * g[k] for w, g in zip(weights, grads_list))
            if self._lowrank_rank is not None and results[0].get("basis") is not None:
                # same guardrail as the non-distributed step; the sharded
                # estimator surfaces shard 0's basis as a representative iid
                # draw (capture statistics are exchangeable across shards)
                self._update_basis_capture(results[0]["basis"], avg["mu"])
        with phase("update"):
            self._update_distribution(avg)
        with phase("status"):
            # mean_eval stays a device scalar until the status is read
            self._mean_eval = sum(w * r["mean_eval"] for w, r in zip(rel, results))

    # --------------------------------------------------------------- updates
    def _update_trunk_delta(self, samples, fitnesses, obj_sense: str):
        """Gradient, optimizer step and stdev update of the trunk-delta form
        as ONE device program with center, stdev and optimizer state donated
        (``_make_trunk_delta_tell``): at a policy of hundreds of millions of
        parameters those three are most of the device's memory and a second
        copy of any does not fit. The evaluated population shares its
        ``center`` with the distribution, so it is dropped first."""
        if self._trunk_delta_tell is None:
            if self._optimizer is not None and not hasattr(self._optimizer, "pure_ascent"):
                raise TypeError(
                    "the trunk-delta form needs an optimizer with a pure step;"
                    f" {type(self._optimizer).__name__} has none"
                )
            self._trunk_delta_tell = _make_trunk_delta_tell(
                type(self._distribution),
                _split_params(self._distribution.parameters)[1],
                self._optimizer,
                center_learning_rate=self._center_learning_rate,
                stdev_learning_rate=self._stdev_learning_rate,
                ranking_method=self._ranking_method if self._ranking_method is not None else "raw",
                higher_is_better=(obj_sense == "max"),
                clamped=tuple(
                    x is not None
                    for x in (self._stdev_min, self._stdev_max, self._stdev_max_change)
                ),
            )
        self._population = None
        parameters = self._distribution.parameters
        state = () if self._optimizer is None else self._optimizer.state()
        mu, sigma, state, update_norm = self._trunk_delta_tell(
            parameters["mu"],
            parameters["sigma"],
            state,
            samples.coeffs,
            samples.factors,
            fitnesses,
            (self._stdev_min, self._stdev_max, self._stdev_max_change),
        )
        if self._optimizer is not None:
            self._optimizer.load_state(state)
        self._center_update_norm_dev = update_norm
        self._distribution = self._distribution.modified_copy(mu=mu, sigma=sigma)

    def _update_distribution(self, gradients: dict):
        """Distribution update + controlled sigma clamping
        (reference ``gaussian.py:369-419``)."""
        learning_rates = {"mu": self._center_learning_rate, "sigma": self._stdev_learning_rate}
        optimizers = {"mu": self._optimizer} if self._optimizer is not None else None
        old_sigma = self._distribution.parameters["sigma"]
        old_mu = self._distribution.parameters["mu"]
        new_dist = self._distribution.update_parameters(
            gradients, learning_rates=learning_rates, optimizers=optimizers
        )
        # enqueued as a device scalar; synced on status read (lag-free here
        # because the read happens after the step's dispatch has retired)
        self._center_update_norm_dev = jnp.linalg.norm(
            new_dist.parameters["mu"] - old_mu
        )
        if (
            self._stdev_min is not None
            or self._stdev_max is not None
            or self._stdev_max_change is not None
        ):
            clamped = modify_tensor(
                old_sigma,
                new_dist.parameters["sigma"],
                lb=self._stdev_min,
                ub=self._stdev_max,
                max_change=self._stdev_max_change,
            )
            new_dist = new_dist.modified_copy(sigma=clamped)
        self._distribution = new_dist


def _make_trunk_delta_tell(
    dist_cls,
    static_items,
    optimizer,
    *,
    center_learning_rate: float,
    stdev_learning_rate: float,
    ranking_method: str,
    higher_is_better: bool,
    clamped: tuple,
):
    """The trunk-delta update as one jitted program that donates center,
    stdev and optimizer state: ``tell(mu, sigma, optimizer_state, coeffs,
    factors, fitnesses, clamps) -> (mu, sigma, optimizer_state, norm of the
    center's step)``. The center's part runs to its end before the stdev's
    starts (an optimization barrier), so the two gradients of the parameters'
    length never live together; the stdev is rewritten leaf by leaf in
    place. The functional ``pgpe_tell_trunk_delta`` orders its work the same
    way."""
    from ..tools.lowrank import TrunkDeltaParamsBatch
    from ..tools.ranking import rank

    def trunk_delta_tell(mu, sigma, optimizer_state, coeffs, factors, fitnesses, clamps):
        parameters = {"mu": mu, "sigma": sigma, **dict(static_items)}
        samples = TrunkDeltaParamsBatch(center=mu, coeffs=coeffs, factors=factors)
        weights = rank(fitnesses, ranking_method, higher_is_better=higher_is_better)
        grad = dist_cls._trunk_delta_mu_gradient(parameters, samples, weights, ranking_method)
        if optimizer is None:
            step = jnp.asarray(center_learning_rate, grad.dtype) * grad
        else:
            step, optimizer_state = optimizer.pure_ascent(optimizer_state, grad)
        update_norm = jnp.linalg.norm(step)
        mu = mu + step
        mu, optimizer_state, update_norm, sigma = jax.lax.optimization_barrier(
            (mu, optimizer_state, update_norm, sigma)
        )
        rate = jnp.asarray(stdev_learning_rate, sigma.dtype)
        lb, ub, max_change = clamps  # scalars: applied leaf by leaf, in place

        def follow(leaf, grad_leaf):
            target = leaf + rate * grad_leaf
            if any(clamped):
                target = modify_tensor(leaf, target, lb=lb, ub=ub, max_change=max_change)
            return target

        new_sigma = dist_cls._trunk_delta_sigma_gradient(
            {**parameters, "sigma": sigma}, samples, weights, ranking_method, into=follow
        )
        return mu, new_sigma, optimizer_state, update_norm

    return phase_jit("update", trunk_delta_tell, donate_argnums=(0, 1, 2))


def _factored_form(lowrank_rank):
    """``lowrank_rank`` as ``(basis rank, trunk-delta rank)``, one of them
    None at least: ``k`` is the factored population with a dense ``(L, k)``
    basis (``tools/lowrank.py``), ``("trunk_delta", k)`` the shared-trunk form
    whose factors follow the policy's parameter tree and which builds no
    array of the solution's length times ``k``."""
    if lowrank_rank is None:
        return None, None
    trunk_delta = isinstance(lowrank_rank, (tuple, list))
    if trunk_delta:
        form, rank = lowrank_rank
        if form != "trunk_delta":
            raise ValueError(f"lowrank_rank is a rank or ('trunk_delta', rank), got {lowrank_rank!r}")
    else:
        rank = lowrank_rank
    if int(rank) < 1:
        raise ValueError(f"lowrank_rank must be >= 1, got {rank}")
    return (None, int(rank)) if trunk_delta else (int(rank), None)


class PGPE(GaussianSearchAlgorithm):
    """PGPE with 0-centered ranking and ClipUp, the configuration of
    Toklu et al. (2020) (reference ``gaussian.py:503-743``).

    ``lowrank_rank`` asks for a factored population and names its form:
    ``k`` samples a dense ``(L, k)`` basis;
    ``("trunk_delta", k)`` samples rank-``k`` factors that follow the
    parameter tree of the problem's policy, builds nothing of ``L x k``, and
    updates center, stdev and optimizer state in one donated program (what a
    policy of hundreds of millions of parameters needs; stdev clamps are
    scalars there)."""

    DISTRIBUTION_TYPE = NotImplemented  # set per instance (symmetric or not)
    DISTRIBUTION_PARAMS = NotImplemented

    def __init__(
        self,
        problem: Problem,
        *,
        popsize: int,
        center_learning_rate: float,
        stdev_learning_rate: float,
        stdev_init=None,
        radius_init=None,
        num_interactions: Optional[int] = None,
        popsize_max: Optional[int] = None,
        optimizer="clipup",
        optimizer_config: Optional[dict] = None,
        ranking_method: Optional[str] = "centered",
        center_init=None,
        stdev_min=None,
        stdev_max=None,
        stdev_max_change=0.2,
        symmetric: bool = True,
        obj_index: Optional[int] = None,
        distributed: bool = False,
        popsize_weighted_grad_avg: Optional[bool] = None,
        lowrank_rank=None,
    ):
        if lowrank_rank is not None and not symmetric:
            raise ValueError("lowrank_rank requires symmetric=True (the PGPE default)")
        if symmetric:
            self.DISTRIBUTION_TYPE = SymmetricSeparableGaussian
            divide_by = "num_directions"
        else:
            self.DISTRIBUTION_TYPE = SeparableGaussian
            divide_by = "num_solutions"
        self.DISTRIBUTION_PARAMS = {
            "divide_mu_grad_by": divide_by,
            "divide_sigma_grad_by": divide_by,
        }
        super().__init__(
            problem,
            popsize=popsize,
            center_learning_rate=center_learning_rate,
            stdev_learning_rate=stdev_learning_rate,
            stdev_init=stdev_init,
            radius_init=radius_init,
            popsize_max=popsize_max,
            num_interactions=num_interactions,
            optimizer=optimizer,
            optimizer_config=optimizer_config,
            ranking_method=ranking_method,
            center_init=center_init,
            stdev_min=stdev_min,
            stdev_max=stdev_max,
            stdev_max_change=stdev_max_change,
            obj_index=obj_index,
            distributed=distributed,
            popsize_weighted_grad_avg=popsize_weighted_grad_avg,
            ensure_even_popsize=symmetric,
            lowrank_rank=lowrank_rank,
        )


class SNES(GaussianSearchAlgorithm):
    """Separable NES (Schaul et al. 2011; reference ``gaussian.py:746-983``)."""

    DISTRIBUTION_TYPE = ExpSeparableGaussian
    DISTRIBUTION_PARAMS = None

    def __init__(
        self,
        problem: Problem,
        *,
        stdev_init=None,
        radius_init=None,
        popsize: Optional[int] = None,
        center_learning_rate: Optional[float] = None,
        stdev_learning_rate: Optional[float] = None,
        scale_learning_rate: bool = True,
        num_interactions: Optional[int] = None,
        popsize_max: Optional[int] = None,
        optimizer=None,
        optimizer_config: Optional[dict] = None,
        ranking_method: Optional[str] = "nes",
        center_init=None,
        stdev_min=None,
        stdev_max=None,
        stdev_max_change=None,
        obj_index: Optional[int] = None,
        distributed: bool = False,
        popsize_weighted_grad_avg: Optional[bool] = None,
    ):
        if popsize is None:
            popsize = int(4 + math.floor(3 * math.log(problem.solution_length)))
        if center_learning_rate is None:
            center_learning_rate = 1.0

        def default_stdev_lr():
            n = problem.solution_length
            return 0.2 * (3 + math.log(n)) / math.sqrt(n)

        if stdev_learning_rate is None:
            stdev_learning_rate = default_stdev_lr()
        else:
            stdev_learning_rate = float(stdev_learning_rate)
            if scale_learning_rate:
                stdev_learning_rate *= default_stdev_lr()

        super().__init__(
            problem,
            popsize=popsize,
            center_learning_rate=center_learning_rate,
            stdev_learning_rate=stdev_learning_rate,
            stdev_init=stdev_init,
            radius_init=radius_init,
            popsize_max=popsize_max,
            num_interactions=num_interactions,
            optimizer=optimizer,
            optimizer_config=optimizer_config,
            ranking_method=ranking_method,
            center_init=center_init,
            stdev_min=stdev_min,
            stdev_max=stdev_max,
            stdev_max_change=stdev_max_change,
            obj_index=obj_index,
            distributed=distributed,
            popsize_weighted_grad_avg=popsize_weighted_grad_avg,
        )


class CEM(GaussianSearchAlgorithm):
    """Cross-entropy method, Duan et al. (2016) variant
    (reference ``gaussian.py:986-1180``)."""

    DISTRIBUTION_TYPE = SeparableGaussian
    DISTRIBUTION_PARAMS = NotImplemented  # set per instance

    def __init__(
        self,
        problem: Problem,
        *,
        popsize: int,
        parenthood_ratio: float,
        stdev_init=None,
        radius_init=None,
        num_interactions: Optional[int] = None,
        popsize_max: Optional[int] = None,
        center_init=None,
        stdev_min=None,
        stdev_max=None,
        stdev_max_change=None,
        obj_index: Optional[int] = None,
        distributed: bool = False,
        popsize_weighted_grad_avg: Optional[bool] = None,
    ):
        self.DISTRIBUTION_PARAMS = {"parenthood_ratio": float(parenthood_ratio)}
        super().__init__(
            problem,
            popsize=popsize,
            center_learning_rate=1.0,
            stdev_learning_rate=1.0,
            stdev_init=stdev_init,
            radius_init=radius_init,
            popsize_max=popsize_max,
            num_interactions=num_interactions,
            optimizer=None,
            optimizer_config=None,
            ranking_method=None,
            center_init=center_init,
            stdev_min=stdev_min,
            stdev_max=stdev_max,
            stdev_max_change=stdev_max_change,
            obj_index=obj_index,
            distributed=distributed,
            popsize_weighted_grad_avg=popsize_weighted_grad_avg,
        )


class XNES(GaussianSearchAlgorithm):
    """Exponential NES with full covariance (Glasmachers et al. 2010;
    reference ``gaussian.py:1183-1405``)."""

    DISTRIBUTION_TYPE = ExpGaussian
    DISTRIBUTION_PARAMS = None

    def __init__(
        self,
        problem: Problem,
        *,
        stdev_init=None,
        radius_init=None,
        popsize: Optional[int] = None,
        center_learning_rate: Optional[float] = None,
        stdev_learning_rate: Optional[float] = None,
        scale_learning_rate: bool = True,
        num_interactions: Optional[int] = None,
        popsize_max: Optional[int] = None,
        optimizer=None,
        optimizer_config: Optional[dict] = None,
        ranking_method: Optional[str] = "nes",
        center_init=None,
        obj_index: Optional[int] = None,
        distributed: bool = False,
        popsize_weighted_grad_avg: Optional[bool] = None,
    ):
        if popsize is None:
            popsize = int(4 + math.floor(3 * math.log(problem.solution_length)))
        if center_learning_rate is None:
            center_learning_rate = 1.0

        def default_stdev_lr():
            n = problem.solution_length
            return 0.6 * (3 + math.log(n)) / (n * math.sqrt(n))

        if stdev_learning_rate is None:
            stdev_learning_rate = default_stdev_lr()
        else:
            stdev_learning_rate = float(stdev_learning_rate)
            if scale_learning_rate:
                stdev_learning_rate *= default_stdev_lr()

        super().__init__(
            problem,
            popsize=popsize,
            center_learning_rate=center_learning_rate,
            stdev_learning_rate=stdev_learning_rate,
            stdev_init=stdev_init,
            radius_init=radius_init,
            popsize_max=popsize_max,
            num_interactions=num_interactions,
            optimizer=optimizer,
            optimizer_config=optimizer_config,
            ranking_method=ranking_method,
            center_init=center_init,
            stdev_min=None,
            stdev_max=None,
            stdev_max_change=None,
            obj_index=obj_index,
            distributed=distributed,
            popsize_weighted_grad_avg=popsize_weighted_grad_avg,
        )
