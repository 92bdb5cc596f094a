"""Search distributions (L4): the gradient-estimation heart of the ES family.

Parity with the reference's ``distributions.py``:

- ``Distribution`` base (``distributions.py:40-410``): parameter dict, sample,
  ``compute_gradients`` (fitness ranking + delegation), ``update_parameters``,
  ``_follow_gradient`` (learning-rate or optimizer ``ascent``),
  ``modified_copy``, ``functional_sample``.
- ``SeparableGaussian`` (``distributions.py:413-613``): PGPE non-symmetric
  score-function gradients with configurable divisors; CEM-style elite update
  when ``parenthood_ratio`` is present; KL divergence.
- ``SymmetricSeparableGaussian`` (``distributions.py:616-773``): antithetic
  pairs interleaved as ``[+e0, -e0, +e1, -e1, ...]``; gradients from
  ``(f+ - f-)/2`` and ``(f+ + f-)/2``.
- ``ExpSeparableGaussian`` (``distributions.py:776-810``): SNES natural
  gradient, ``sigma <- sigma * exp(0.5 * lr * grad)``.
- ``ExpGaussian`` (``distributions.py:813-1016``): XNES full covariance via
  ``A`` with tracked ``A_inv``; updates through ``expm``.

TPU-first design: every distribution's math lives in pure classmethods over a
parameter dict (a pytree), so it jits/vmaps natively; the class instances are
thin stateful conveniences. ``make_functional_sampler`` /
``make_functional_grad_estimator`` (``distributions.py:1023-1623``) expose the
batched pure-functional API.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

from .observability.scopes import phase_jit
from .tools.cloning import Serializable
from .tools.lowrank import LowRankParamsBatch, TrunkDeltaParamsBatch, is_factored, write_leaves
from .tools.misc import to_jax_dtype
from .tools.ranking import rank
from .tools.recursiveprintable import RecursivePrintable
from .tools.tensormaker import TensorMakerMixin

__all__ = [
    "Distribution",
    "SeparableGaussian",
    "SymmetricSeparableGaussian",
    "ExpSeparableGaussian",
    "ExpGaussian",
    "make_functional_sampler",
    "make_functional_grad_estimator",
]

# per-class jitted kernels for the stateful (OO) API: the math lives in pure
# classmethods, so one compiled executable per (class, static-config) pair
# serves every instance and every generation
_JITTED_SAMPLE_CACHE: dict = {}
_JITTED_SAMPLE_LOWRANK_CACHE: dict = {}
_JITTED_GRADS_CACHE: dict = {}


def _split_params(parameters: dict):
    """Separate array parameters from static (string/structural) ones."""
    static = tuple(
        sorted(
            (k, v)
            for k, v in parameters.items()
            if isinstance(v, (str, type(None))) or k == "parenthood_ratio"
        )
    )
    arrays = {k: v for k, v in parameters.items() if k not in dict(static)}
    return arrays, static


def _jitted_sample_for(cls):
    # keyed on the fused-sampling flag as well as the class: _sample reads
    # EVOTORCH_TPU_FUSED_SAMPLING at trace time, so a cache hit after the env
    # var changed would silently keep serving the stale executable
    import os

    cache_key = (cls, os.environ.get("EVOTORCH_TPU_FUSED_SAMPLING", "0"))
    fn = _JITTED_SAMPLE_CACHE.get(cache_key)
    if fn is None:

        def sample(key, array_params, static_items, num_solutions):
            params = dict(array_params)
            params.update(dict(static_items))
            return cls._sample(key, params, num_solutions)

        fn = phase_jit("ask", sample, static_argnames=("static_items", "num_solutions"))
        _JITTED_SAMPLE_CACHE[cache_key] = fn
    return fn


def _jitted_sample_lowrank_for(cls):
    fn = _JITTED_SAMPLE_LOWRANK_CACHE.get(cls)
    if fn is None:

        def sample_lowrank(key, array_params, static_items, num_solutions, rank, basis=None):
            params = dict(array_params)
            params.update(dict(static_items))
            return cls._sample_lowrank(key, params, num_solutions, rank, basis)

        # basis=None and basis=<array> trace as distinct jit signatures
        fn = phase_jit(
            "ask", sample_lowrank, static_argnames=("static_items", "num_solutions", "rank")
        )
        _JITTED_SAMPLE_LOWRANK_CACHE[cls] = fn
    return fn


@functools.lru_cache(maxsize=64)
def _jitted_sample_trunk_delta(cls, policy, num_solutions, rank, draw_factors):
    # lazy import: distributions (L1) must not import neuroevolution (L3) at
    # module scope
    from .neuroevolution.net.lowrank import sample_trunk_delta_factors

    def sample_trunk_delta(key, sigma, factors):
        key_factors, key_coeffs = jax.random.split(key)
        if draw_factors:
            factors = sample_trunk_delta_factors(key_factors, policy, sigma, rank)
        batch = cls._sample_trunk_delta(
            key_coeffs, {"mu": sigma, "sigma": sigma}, num_solutions, rank, factors
        )
        return batch.coeffs, batch.factors

    return phase_jit("ask", sample_trunk_delta)


def _jitted_grads_for(cls):
    # keyed on the fused-rank flag as well as the class: rank() reads
    # EVOTORCH_TPU_FUSED_RANK at trace time (tools/ranking.py), so a cache
    # hit after the env var changed would silently keep the stale executable
    import os

    cache_key = (cls, os.environ.get("EVOTORCH_TPU_FUSED_RANK", "auto"))
    fn = _JITTED_GRADS_CACHE.get(cache_key)
    if fn is None:

        def grads(array_params, samples, fitnesses, static_items, ranking_method, higher_is_better):
            params = dict(array_params)
            params.update(dict(static_items))
            weights = rank(fitnesses, ranking_method, higher_is_better=higher_is_better)
            return cls._compute_gradients(params, samples, weights, ranking_method)

        fn = phase_jit(
            "grad", grads, static_argnames=("static_items", "ranking_method", "higher_is_better")
        )
        _JITTED_GRADS_CACHE[cache_key] = fn
    return fn


class Distribution(TensorMakerMixin, Serializable, RecursivePrintable):
    """Base class for search distributions (reference ``distributions.py:40``)."""

    MANDATORY_PARAMETERS: set = set()
    OPTIONAL_PARAMETERS: set = set()
    PARAMETER_NDIMS: dict = {}
    #: antithetic distributions require an even sample count per draw; the
    #: sharded grad estimator uses this to round shard-local popsizes
    SAMPLES_MUST_BE_EVEN: bool = False

    functional_sample: Optional[Callable] = None

    def __init__(
        self,
        *,
        solution_length: int,
        parameters: dict,
        dtype=None,
        seed: Optional[int] = None,
    ):
        self.solution_length = int(solution_length)
        self.dtype = to_jax_dtype(dtype) if dtype is not None else jnp.float32
        self._parameters = {}
        for k, v in parameters.items():
            if (k not in self.MANDATORY_PARAMETERS) and (k not in self.OPTIONAL_PARAMETERS):
                raise ValueError(f"{type(self).__name__} got an unrecognized parameter: {k!r}")
            if isinstance(v, (str, type(None))):
                self._parameters[k] = v
            elif isinstance(v, (int, float)) and k in ("parenthood_ratio",):
                self._parameters[k] = float(v)
            else:
                self._parameters[k] = jnp.asarray(v, dtype=self.dtype)
        for k in self.MANDATORY_PARAMETERS:
            if k not in self._parameters:
                raise ValueError(f"{type(self).__name__} is missing mandatory parameter {k!r}")
        self._rng_key = jax.random.key(0 if seed is None else seed)

    # -- PRNG plumbing ------------------------------------------------------
    def manual_seed(self, seed: int):
        self._rng_key = jax.random.key(int(seed))

    def next_rng_key(self):
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    # -- parameters ----------------------------------------------------------
    @property
    def parameters(self) -> dict:
        return self._parameters

    def modified_copy(self, *, dtype=None, **overrides) -> "Distribution":
        """Copy with some parameters replaced (reference ``distributions.py:328``)."""
        params = dict(self._parameters)
        params.update(overrides)
        result = type(self)(
            parameters=params,
            solution_length=self.solution_length,
            dtype=dtype if dtype is not None else self.dtype,
        )
        result._rng_key = self._rng_key
        return result

    # -- sampling ------------------------------------------------------------
    def sample(self, num_solutions: int, *, key=None) -> jnp.ndarray:
        """Draw ``num_solutions`` samples (reference ``distributions.py:155-216``).
        ``key`` is an explicit JAX PRNG key; when omitted, the distribution's
        internal key state advances (stateful convenience)."""
        if key is None:
            key = self.next_rng_key()
        arrays, static = _split_params(self._parameters)
        return _jitted_sample_for(type(self))(key, arrays, static, int(num_solutions))

    @classmethod
    def _sample(cls, key, parameters: dict, num_solutions: int) -> jnp.ndarray:
        raise NotImplementedError

    # -- gradients -----------------------------------------------------------
    def compute_gradients(
        self,
        samples: jnp.ndarray,
        fitnesses: jnp.ndarray,
        *,
        objective_sense: str,
        ranking_method: str = "raw",
    ) -> dict:
        """Rank fitnesses then delegate (reference ``distributions.py:236-299``)."""
        if objective_sense not in ("min", "max"):
            raise ValueError(f"objective_sense must be 'min' or 'max', got {objective_sense!r}")
        higher_is_better = objective_sense == "max"
        arrays, static = _split_params(self._parameters)
        if not is_factored(samples):
            samples = jnp.asarray(samples)  # structured samples are pytrees already
        return _jitted_grads_for(type(self))(
            arrays, samples, jnp.asarray(fitnesses), static, ranking_method, higher_is_better
        )

    @classmethod
    def _compute_gradients(cls, parameters: dict, samples, weights, ranking_used) -> dict:
        raise NotImplementedError

    # -- updates -------------------------------------------------------------
    def _follow_gradient(
        self,
        param_name: str,
        grad: jnp.ndarray,
        *,
        learning_rates: Optional[dict] = None,
        optimizers: Optional[dict] = None,
    ) -> jnp.ndarray:
        """Learning-rate step or optimizer ``ascent`` (reference
        ``distributions.py:372-392``)."""
        if optimizers is not None and param_name in optimizers:
            return optimizers[param_name].ascent(grad)
        if learning_rates is not None and param_name in learning_rates:
            return jnp.asarray(learning_rates[param_name], dtype=grad.dtype) * grad
        return grad

    def update_parameters(
        self,
        gradients: dict,
        *,
        learning_rates: Optional[dict] = None,
        optimizers: Optional[dict] = None,
    ) -> "Distribution":
        raise NotImplementedError

    # -- misc ----------------------------------------------------------------
    def relative_entropy(self, other: "Distribution") -> float:
        raise NotImplementedError(
            f"KL divergence is not defined for {type(self).__name__}"
        )

    def _printable_items(self):
        return {"solution_length": self.solution_length, "parameters": self._parameters}


def _zero_center_weights(weights: jnp.ndarray, ranking_used: Optional[str]) -> jnp.ndarray:
    """Weights must be 0-centered for the score-function estimators unless the
    ranking already guarantees it (reference ``distributions.py:560-563``)."""
    if ranking_used not in ("centered", "normalized"):
        weights = weights - jnp.mean(weights)
    return weights


def _divide_grad(parameters: dict, param_name: str, grad, weights):
    """Configurable gradient divisor (reference ``distributions.py:517-536``)."""
    option = f"divide_{param_name}_grad_by"
    div_by_what = parameters.get(option, None)
    if div_by_what is None:
        return grad
    if div_by_what == "num_solutions":
        return grad / weights.shape[0]
    if div_by_what == "num_directions":
        return grad / (weights.shape[0] // 2)
    if div_by_what == "total_weight":
        return grad / jnp.sum(jnp.abs(weights))
    if div_by_what == "weight_stdev":
        return grad / jnp.std(weights, ddof=1)
    raise ValueError(f"The parameter {option} has an unrecognized value: {div_by_what}")


class SeparableGaussian(Distribution):
    """Separable multivariate Gaussian, as used by PGPE (non-symmetric) and —
    with ``parenthood_ratio`` — CEM (reference ``distributions.py:413-613``)."""

    MANDATORY_PARAMETERS = {"mu", "sigma"}
    OPTIONAL_PARAMETERS = {"divide_mu_grad_by", "divide_sigma_grad_by", "parenthood_ratio"}
    PARAMETER_NDIMS = {"mu": 1, "sigma": 1}

    def __init__(self, parameters: dict, *, solution_length: Optional[int] = None, dtype=None, seed=None):
        mu = jnp.asarray(parameters["mu"])
        if solution_length is None:
            solution_length = mu.shape[-1]
        elif solution_length != mu.shape[-1]:
            raise ValueError(
                f"solution_length={solution_length} does not match len(mu)={mu.shape[-1]}"
            )
        sigma = jnp.asarray(parameters["sigma"])
        if sigma.shape[-1] != mu.shape[-1]:
            raise ValueError(
                f"mu and sigma have mismatching lengths: {mu.shape[-1]} vs {sigma.shape[-1]}"
            )
        super().__init__(solution_length=solution_length, parameters=parameters, dtype=dtype, seed=seed)

    @property
    def mu(self) -> jnp.ndarray:
        return self._parameters["mu"]

    @property
    def sigma(self) -> jnp.ndarray:
        return self._parameters["sigma"]

    @classmethod
    def _sample(cls, key, parameters, num_solutions):
        mu = parameters["mu"]
        sigma = parameters["sigma"]
        eps = jax.random.normal(key, (num_solutions, mu.shape[-1]), dtype=mu.dtype)
        return mu + sigma * eps

    @classmethod
    def _compute_gradients_via_parenthood_ratio(cls, parameters, samples, weights) -> dict:
        """CEM-style elite update (reference ``distributions.py:538-546``):
        gradient = (elite mean/std) - current (mu/sigma). Uses top-k by weight,
        fixed elite count, so it stays jit-friendly."""
        num_samples = samples.shape[0]
        num_elites = int(num_samples * float(parameters["parenthood_ratio"]))
        _, elite_indices = jax.lax.top_k(weights, num_elites)
        elites = samples[elite_indices, :]
        return {
            "mu": jnp.mean(elites, axis=0) - parameters["mu"],
            "sigma": jnp.std(elites, axis=0, ddof=1) - parameters["sigma"],
        }

    @classmethod
    def _compute_gradients(cls, parameters, samples, weights, ranking_used) -> dict:
        if "parenthood_ratio" in parameters:
            return cls._compute_gradients_via_parenthood_ratio(parameters, samples, weights)
        mu = parameters["mu"]
        sigma = parameters["sigma"]
        scaled_noises = samples - mu
        weights = _zero_center_weights(weights, ranking_used)
        mu_grad = _divide_grad(parameters, "mu", weights @ scaled_noises, weights)
        sigma_grad = _divide_grad(
            parameters,
            "sigma",
            weights @ ((scaled_noises**2 - sigma**2) / sigma),
            weights,
        )
        return {"mu": mu_grad, "sigma": sigma_grad}

    def update_parameters(self, gradients, *, learning_rates=None, optimizers=None):
        new_mu = self.mu + self._follow_gradient(
            "mu", gradients["mu"], learning_rates=learning_rates, optimizers=optimizers
        )
        new_sigma = self.sigma + self._follow_gradient(
            "sigma", gradients["sigma"], learning_rates=learning_rates, optimizers=optimizers
        )
        return self.modified_copy(mu=new_mu, sigma=new_sigma)

    def relative_entropy(self, other: "SeparableGaussian") -> float:
        """KL(self || other) for diagonal Gaussians (reference
        ``distributions.py:598-613``)."""
        cov0 = self.sigma**2
        cov1 = other.sigma**2
        mu_delta = other.mu - self.mu
        trace_cov = jnp.sum(cov0 / cov1)
        k = self.solution_length
        scaled_mu = jnp.sum(mu_delta**2 / cov1)
        log_det = jnp.sum(jnp.log(cov1)) - jnp.sum(jnp.log(cov0))
        return float(0.5 * (trace_cov - k + scaled_mu + log_det))


def _make_class_functional_sample(cls):
    """Key-splitting batched sampler: batch dims on the parameters produce
    *independent* noise per batch lane (keys are split in
    make_functional_sampler, unlike a naive vmap with a broadcast key)."""

    def functional_sample(num_solutions: int, parameters: dict, *, key):
        return make_functional_sampler(cls)(key, int(num_solutions), parameters)

    return functional_sample


def _use_fused_sampling() -> bool:
    """Opt-in dispatch of antithetic sampling to the fused on-chip-PRNG
    kernel (``ops/sampling.py``). Off by default: the kernel draws from a
    different random stream than XLA's threefry, so enabling it changes
    sampled values (not just speed); set ``EVOTORCH_TPU_FUSED_SAMPLING=1``
    after micro-benching (``bench_ops.py``) shows a win on your shapes.
    TPU only — the on-chip PRNG primitives have no lowering elsewhere, so
    with the flag set off the chip, sampling fails to compile (an error, not
    a quiet switch to the XLA sampler).

    Read at trace time, like ``EVOTORCH_TPU_FUSED_RANK``: the OO samplers key
    their jit cache on the flag's value, so toggling the env var takes effect
    on the next ``sample()``; user-jitted functional samplers bake the value
    at their own first trace."""
    import os

    return os.environ.get("EVOTORCH_TPU_FUSED_SAMPLING", "0") == "1"


def _threefry_key_words(key):
    """The two ``uint32`` words of a threefry key whose draws are a pure
    function of the key and the draw's row-major index
    (``jax_threefry_partitionable``, the default of this jax); ``None`` for
    any other generator, whose stream only ``jax.random`` can reproduce."""
    if not jax.config.jax_threefry_partitionable:
        return None
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(key)  # a raw key is of the default implementation
    if jax.random.key_impl(key) != "threefry2x32":
        return None
    words = jax.random.key_data(key)
    return words[0], words[1]


def _mul_wide_u32(a, b):
    """``(hi, lo)`` words of the 64-bit product of two ``uint32`` arrays, by
    16-bit halves: jax has no 64-bit integers unless ``jax_enable_x64``."""
    half, mask = jnp.uint32(16), jnp.uint32(0xFFFF)
    a_lo, a_hi, b_lo, b_hi = a & mask, a >> half, b & mask, b >> half
    low, high = a_lo * b_lo, a_hi * b_hi
    mid_a, mid_b = a_lo * b_hi, a_hi * b_lo
    mid = mid_a + mid_b  # may wrap: a carry of 2**32, that is 2**16 into `hi`
    lo = low + (mid << half)
    hi = (
        high
        + (mid >> half)
        + ((mid < mid_a).astype(jnp.uint32) << half)
        + (lo < low).astype(jnp.uint32)
    )
    return hi, lo


def _linear_index_words(row, col, row_length: int):
    """``(hi, lo)`` words of ``row * row_length + col``: the counter that
    ``jax.random.bits(key, (rows, row_length))`` gives draw ``[row, col]``
    under ``jax_threefry_partitionable`` (``jax._src.prng.iota_2x32_shape``:
    the row-major index as two 32-bit words). Two words because a dense
    population's index passes 2**32 within reach (80,000 x 98,321 / 2 is
    3.9e9). ``row`` and ``col`` are ``uint32`` arrays that broadcast; the
    product is taken on ``row`` alone, so with a column of rows and a row of
    columns the wide multiply runs over a vector, not over the matrix."""
    base_hi, base_lo = _mul_wide_u32(row, jnp.uint32(row_length))
    lo = base_lo + col
    return base_hi + (lo < col).astype(jnp.uint32), lo


def _float32_bits_to_normal(bits):
    """``jax.random.normal``'s map from 32 random bits to a float32 standard
    normal, step for step (``jax._src.random._uniform`` on ``(-1, 1)``, then
    ``sqrt(2) * erf_inv``): 23 bits as the mantissa of a float in ``[1, 2)``,
    shifted and scaled into the open interval, through the inverse error
    function. ``tests/test_distributions.py`` holds it to ``jax.random.normal``
    bit for bit, so a jax that changes its map fails there, not silently."""
    one = np.float32(1.0)
    mantissa = (bits >> jnp.uint32(32 - 23)) | jnp.uint32(one.view(np.uint32))
    floats = jax.lax.bitcast_convert_type(mantissa, jnp.float32) - one
    low = np.nextafter(np.float32(-1.0), np.float32(0.0))
    uniform = jax.lax.max(low, floats * (one - low) + low)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(uniform)


class SymmetricSeparableGaussian(SeparableGaussian):
    """Antithetic separable Gaussian, the PGPE default
    (reference ``distributions.py:616-773``)."""

    SAMPLES_MUST_BE_EVEN = True

    @classmethod
    def _sample(cls, key, parameters, num_solutions):
        """``[mu + e0, mu - e0, mu + e1, mu - e1, ...]`` with ``e =
        jax.random.normal(key, (N / 2, L)) * sigma``, computed ELEMENTWISE ON
        THE RESULT'S INDEX: ``out[r, j] = mu[j] + s(r) sigma[j] z[r // 2, j]``,
        every ``z`` from threefry at the counter ``(r // 2) L + j`` that
        ``jax.random.normal`` gives that draw, so each normal is computed
        twice and the population is the one the plain form (draw, stack the
        pair, reshape) gives: the same normals bit for bit, the samples to a
        unit in the last place (a compiler may fuse the multiply into the add
        in one form alone).

        Why: a dense population is gigabytes (50,000 x 12,305 float32: 2.46
        GB) and the TPU's default layout of an array follows its SHAPE:
        ``f32[50000,12305]`` lives with the population index in the lanes
        (``{0,1:T(8,128)}``: 12,305 is no multiple of 128), ``f32[10000,98321]``
        row-major. Through the plain form XLA pushed the stack's unit axis
        into the generator (tiles of one sublane: an eighth of every vector
        register, 77.7 ms where full tiles take 9.6), then relaid the result
        three times beside 4.97 GB of temporaries. An elementwise program has
        no layout of its own: one fusion writes each sample once into
        whatever layout the result has, on whole registers, with no
        population-sized temporary (104.2 -> about 20 ms a generation at the
        flagship's shape on a v5e: ``PERF.md`` §6, PR 38).

        It relies on ``jax_threefry_partitionable`` (this jax's default): a
        draw's bits are a function of the key and the draw's row-major index
        alone (``jax._src.prng.iota_2x32_shape``), and on ``jax.random.normal``'s
        float32 map from bits to a normal; ``tests/test_distributions.py``
        holds both to ``jax.random.normal`` bit for bit, ``tests/test_ops.py``
        compiles the program for a v5e at the benchmark's shapes and refuses a
        relayout, a narrow tile or a temporary. Another generator (``rbg``),
        the flag off, or another dtype than float32 is something the code
        observes in its input: those take the plain form, same stream as ever.

        The result sits behind ``optimization_barrier``: traced into a larger
        program (a fused generation, ``make_training_span``) XLA would
        otherwise recompute this elementwise producer inside every consumer's
        fusion, each free to round ``mu + eps`` its own way; the searcher's
        own program, which only returns it, is unchanged by the barrier."""
        if num_solutions % 2 != 0:
            raise ValueError(
                f"Number of solutions sampled from {cls.__name__} must be even, got {num_solutions}"
            )
        mu = parameters["mu"]
        sigma = parameters["sigma"]
        if _use_fused_sampling():
            # opt-in fused TPU kernel (ops/sampling.py): on-chip PRNG +
            # scale/antithetic blocks in VMEM. Distribution-equivalent but a
            # DIFFERENT random stream than the XLA threefry path — hence
            # opt-in via EVOTORCH_TPU_FUSED_SAMPLING=1, never a silent swap
            from .ops.sampling import sample_symmetric_gaussian

            return sample_symmetric_gaussian(
                key, mu, sigma, num_solutions, use_pallas=True
            )
        words = _threefry_key_words(key) if mu.dtype == jnp.float32 else None
        if words is None:
            # another generator or dtype: draw, then interleave
            eps = jax.random.normal(key, (num_solutions // 2, mu.shape[-1]), dtype=mu.dtype) * sigma
            pairs = jnp.stack([mu + eps, mu - eps], axis=1)
            return pairs.reshape(num_solutions, mu.shape[-1])
        # row r of the RESULT holds direction r // 2 under the sign of r's parity
        row = jax.lax.iota(jnp.uint32, num_solutions)[:, None]
        col = jax.lax.iota(jnp.uint32, mu.shape[-1])[None, :]
        hi, lo = _linear_index_words(row >> jnp.uint32(1), col, mu.shape[-1])
        bits_hi, bits_lo = threefry2x32_p.bind(*words, hi, lo)
        eps = _float32_bits_to_normal(bits_hi ^ bits_lo) * sigma
        sign = 1.0 - 2.0 * (row & jnp.uint32(1)).astype(mu.dtype)
        # ONE array whatever program this is traced into (the docstring's last
        # paragraph): a fitness and the gradient after it in one program cost
        # 26 + 28 M cycles on a v5e recomputing the draws, 25 + 11 + 11
        # drawing once and reading twice
        return jax.lax.optimization_barrier(mu + sign * eps)

    @classmethod
    def _compute_gradients(cls, parameters, samples, weights, ranking_used) -> dict:
        """PGPE's antithetic estimate: ``mu`` follows ``sum_i (f+_i - f-_i) / 2
        e_i``, ``sigma`` follows ``sum_i (f+_i + f-_i) / 2 (e_i^2 - sigma^2) /
        sigma``. The dense branch reads ``samples`` WHOLE, each row weighted
        by its sign: row ``2i`` holds ``mu + e_i`` and row ``2i + 1`` ``mu -
        e_i``, so the pairwise sums are sums over all rows with ``+-(f+ -
        f-) / 4`` and ``(f+ + f-) / 4`` on a pair's two rows (twice the
        terms: equal to the pairwise form to float32 rounding, not bit for
        bit). ``samples[0::2]`` would be a stride along the LANES where the
        device keeps the population index there (``_sample``): XLA relaid all
        2.46 GB to take it. This is one fusion that reads the population once
        and holds no temporary. ``samples - mu`` stays inside it: ``c @
        samples`` alone cancels badly though ``sum(c) = 0``."""
        if isinstance(samples, TrunkDeltaParamsBatch):
            # the same algebra leaf by leaf from the factors: no (L, k) basis
            return cls._compute_gradients_trunk_delta(parameters, samples, weights, ranking_used)
        if is_factored(samples):
            return cls._compute_gradients_lowrank(parameters, samples, weights, ranking_used)
        if "parenthood_ratio" in parameters:
            return cls._compute_gradients_via_parenthood_ratio(parameters, samples, weights)
        mu = parameters["mu"]
        sigma = parameters["sigma"]
        weights = _zero_center_weights(weights, ranking_used)
        # per ROW: +-(f+ - f-) / 4 and (f+ + f-) / 4 on a pair's two rows
        pairs = weights.reshape(-1, 2)
        quarter_diff = (pairs[:, 0] - pairs[:, 1]) / 4
        mu_weights = jnp.stack([quarter_diff, -quarter_diff], axis=1).reshape(-1)
        sigma_weights = jnp.repeat((pairs[:, 0] + pairs[:, 1]) / 4, 2)
        scaled_noises = samples - mu
        mu_grad = _divide_grad(parameters, "mu", mu_weights @ scaled_noises, weights)
        sigma_grad = _divide_grad(
            parameters,
            "sigma",
            sigma_weights @ ((scaled_noises**2 - sigma**2) / sigma),
            weights,
        )
        return {"mu": mu_grad, "sigma": sigma_grad}

    # ------------------- factored (low-rank) population mode -----------------
    # The MXU path for wide policies (tools/lowrank.py): the population is
    # theta_i = mu + (sigma * B) z_i with a shared per-generation basis
    # B (L, rank) and per-lane coefficients z_i — and both the sampling and
    # the gradient estimate factor through the basis, so the dense (N, L)
    # population matrix is never materialized. With B entries ~ N(0, 1/rank)
    # the per-coordinate marginal variance of a perturbation is sigma^2 in
    # expectation over the basis (for a fixed per-generation basis the
    # per-coordinate variance fluctuates with relative stddev ~sqrt(2/rank),
    # so sigma-adaptation calibration is noisier at small rank).
    #
    # No reference counterpart (the reference evaluates dense populations
    # only); the math below is this class's dense symmetric gradient
    # rewritten in factored form:
    #   scaled_noises = B_eff Z^T            (never built)
    #   mu_grad    = B_eff @ (((f+ - f-)/2) @ Z)
    #   sigma_grad = (rowquad(B_eff, Z^T diag((f+ + f-)/2) Z)
    #                 - sum((f+ + f-)/2) sigma^2) / sigma
    # which equal the dense formulas exactly (tested in test_lowrank.py).

    @classmethod
    def _sample_lowrank(cls, key, parameters, num_solutions, rank, basis=None):
        """Draw a ``LowRankParamsBatch``: antithetic coefficient pairs
        interleaved ``[+z0, -z0, +z1, -z1, ...]`` (the dense sampler's
        direction layout above), sigma folded into the basis.

        With ``basis`` given, only fresh coefficients are drawn against that
        (already sigma-folded) basis — the shared-per-generation-basis mode
        that makes factored batches concatenable, so the adaptive-popsize
        loop (``num_interactions``) can keep sampling rounds within one
        generation's subspace (reference ``core.py:3239-3282`` concatenates
        dense rounds the same way)."""
        if num_solutions % 2 != 0:
            raise ValueError(
                f"Number of solutions sampled from {cls.__name__} must be even,"
                f" got {num_solutions}"
            )
        mu = parameters["mu"]
        sigma = parameters["sigma"]
        rank = int(rank)
        key_basis, key_coeffs = jax.random.split(key)
        if basis is None:
            basis = jax.random.normal(
                key_basis, (mu.shape[-1], rank), dtype=mu.dtype
            ) / jnp.sqrt(jnp.asarray(float(rank), mu.dtype))
            basis = sigma[..., None] * basis  # sigma folded in: delta = basis @ z
        elif basis.shape[-1] != rank:
            # fail fast: a rank/basis mismatch would otherwise surface as an
            # opaque dot_general shape error deep inside a jitted forward
            raise ValueError(
                f"basis has rank {basis.shape[-1]} but rank={rank} was requested"
            )
        num_directions = num_solutions // 2
        z = jax.random.normal(key_coeffs, (num_directions, rank), dtype=mu.dtype)
        coeffs = jnp.stack([z, -z], axis=1).reshape(num_solutions, rank)
        return LowRankParamsBatch(center=mu, basis=basis, coeffs=coeffs)

    def sample_lowrank(
        self, num_solutions: int, rank: int, *, key=None, basis=None
    ) -> LowRankParamsBatch:
        """Stateful-API counterpart of :meth:`_sample_lowrank` (jitted per
        class like :meth:`sample`). ``basis`` reuses an existing sigma-folded
        basis (shared-per-generation-basis mode)."""
        if key is None:
            key = self.next_rng_key()
        arrays, static = _split_params(self._parameters)
        out = _jitted_sample_lowrank_for(type(self))(
            key, arrays, static, int(num_solutions), int(rank), basis
        )
        # the jitted call returns fresh output buffers even for passed-through
        # arrays; restoring the original objects keeps SolutionBatch.cat's
        # shared-basis check on the `is` fast path (center is always a mu
        # passthrough; basis only when the caller supplied one)
        out = out._replace(center=self._parameters["mu"])
        if basis is not None:
            out = out._replace(basis=basis)
        return out

    @classmethod
    def _compute_gradients_lowrank(cls, parameters, samples: LowRankParamsBatch, weights, ranking_used) -> dict:
        """The dense symmetric gradients computed in O(L * rank) from the
        factored population — numerically identical to running
        ``_compute_gradients`` on ``samples.materialize()``."""
        sigma = parameters["sigma"]
        weights = _zero_center_weights(weights, ranking_used)
        z = samples.coeffs[0::2]  # (D, rank): the +z of each antithetic pair
        basis = samples.basis  # sigma-folded effective basis (L, rank)
        fdplus = weights[0::2]
        fdminus = weights[1::2]
        mu_grad = _divide_grad(
            parameters, "mu", basis @ (((fdplus - fdminus) / 2) @ z), weights
        )
        w_s = (fdplus + fdminus) / 2
        m = z.T @ (w_s[:, None] * z)  # (rank, rank)
        rowquad = jnp.einsum("lm,mn,ln->l", basis, m, basis)
        sigma_grad = _divide_grad(
            parameters, "sigma", (rowquad - jnp.sum(w_s) * sigma**2) / sigma, weights
        )
        return {"mu": mu_grad, "sigma": sigma_grad}

    # ------------------- the shared-trunk (trunk-delta) form -----------------
    # theta_i = mu + basis z_i as above, with the basis STRUCTURED per
    # parameter leaf (tools.lowrank.DeltaFactor: rank-1 blocks b_m a_m^T) and
    # never built: at the sizes this form exists for (a 700M-parameter
    # decoder) an (L, k) basis is several times the device's memory. The
    # gradients are the factored formulas above, leaf by leaf:
    #   mu_grad[leaf]    = B diag(c) A^T,           c = ((f+ - f-)/2) @ Z
    #   rowquad[leaf]    = sum_mn M_mn (b_m*b_n)(a_m*a_n)^T,  M = Z^T diag((f+ + f-)/2) Z
    # each leaf written into place (tools.lowrank.write_leaves), so one
    # leaf's temporaries live at a time. The factor structure is the
    # policy's (neuroevolution/net/lowrank.py:sample_trunk_delta_factors).

    @classmethod
    def _sample_trunk_delta(cls, key, parameters, num_solutions, rank, factors) -> TrunkDeltaParamsBatch:
        """Draw a ``TrunkDeltaParamsBatch`` against one generation's
        ``factors``: antithetic coefficient pairs in :meth:`_sample_lowrank`'s
        layout ``[+z0, -z0, +z1, -z1, ...]``. Nothing of the parameters'
        length is drawn or copied: ``center`` IS ``mu``."""
        if num_solutions % 2 != 0:
            raise ValueError(
                f"Number of solutions sampled from {cls.__name__} must be even,"
                f" got {num_solutions}"
            )
        mu = parameters["mu"]
        z = jax.random.normal(key, (num_solutions // 2, int(rank)), dtype=mu.dtype)
        coeffs = jnp.stack([z, -z], axis=1).reshape(num_solutions, int(rank))
        return TrunkDeltaParamsBatch(center=mu, coeffs=coeffs, factors=factors)

    def sample_trunk_delta(
        self, num_solutions: int, rank: int, policy, *, key=None, factors=None
    ) -> TrunkDeltaParamsBatch:
        """Stateful-API counterpart: draws the generation's factors from
        ``policy``'s parameter structure (or reuses ``factors``: later rounds
        of one generation stay concatenable) and fresh coefficients."""
        if key is None:
            key = self.next_rng_key()
        coeffs, factors = _jitted_sample_trunk_delta(
            type(self), policy, int(num_solutions), int(rank), factors is None
        )(key, self._parameters["sigma"], factors)
        # ``center`` is the mu object itself (see sample_lowrank), and never
        # leaves the jitted sampler as an output: that would be a copy of it
        return TrunkDeltaParamsBatch(center=self._parameters["mu"], coeffs=coeffs, factors=factors)

    @staticmethod
    def _trunk_delta_weights(samples: TrunkDeltaParamsBatch, weights, ranking_used):
        """``c`` ``(k,)``, ``M`` ``(k, k)`` and ``sum((f+ + f-)/2)`` of the
        formulas above."""
        weights = _zero_center_weights(weights, ranking_used)
        z = samples.coeffs[0::2]
        fdplus, fdminus = weights[0::2], weights[1::2]
        w_s = (fdplus + fdminus) / 2
        return ((fdplus - fdminus) / 2) @ z, z.T @ (w_s[:, None] * z), jnp.sum(w_s), weights

    @classmethod
    def _trunk_delta_mu_gradient(cls, parameters, samples, weights, ranking_used):
        c, _, _, weights = cls._trunk_delta_weights(samples, weights, ranking_used)
        grad = write_leaves(
            jnp.zeros_like(parameters["sigma"]),
            samples.factors,
            lambda factor, _: factor.delta(c[None])[0],
        )
        return _divide_grad(parameters, "mu", grad, weights)

    @classmethod
    def _trunk_delta_sigma_gradient(cls, parameters, samples, weights, ranking_used, into=None):
        """The sigma gradient; with ``into`` (a function ``(sigma_leaf,
        grad_leaf) -> new leaf``) the result of applying it leaf by leaf to
        ``sigma`` instead, written in place: the update without a second
        vector of sigma's length."""
        _, m, total, weights = cls._trunk_delta_weights(samples, weights, ranking_used)
        scale = _divide_grad(parameters, "sigma", jnp.ones((), m.dtype), weights)

        def leaf(factor, sigma_leaf):
            grad = scale * (factor.quadratic(m) - total * sigma_leaf**2) / sigma_leaf
            return grad if into is None else into(sigma_leaf, grad)

        return write_leaves(parameters["sigma"], samples.factors, leaf)

    @classmethod
    def _compute_gradients_trunk_delta(cls, parameters, samples, weights, ranking_used) -> dict:
        """Equal to ``_compute_gradients_lowrank`` on the materialised basis
        (tests/test_trunk_delta.py keeps that algebra as a helper)."""
        return {
            "mu": cls._trunk_delta_mu_gradient(parameters, samples, weights, ranking_used),
            "sigma": cls._trunk_delta_sigma_gradient(parameters, samples, weights, ranking_used),
        }


class ExpSeparableGaussian(SeparableGaussian):
    """Exponential separable Gaussian, as used by SNES
    (reference ``distributions.py:776-810``)."""

    MANDATORY_PARAMETERS = {"mu", "sigma"}
    OPTIONAL_PARAMETERS: set = set()
    PARAMETER_NDIMS = {"mu": 1, "sigma": 1}

    @classmethod
    def _compute_gradients(cls, parameters, samples, weights, ranking_used) -> dict:
        if ranking_used != "nes":
            weights = weights / jnp.sum(jnp.abs(weights))
        mu = parameters["mu"]
        sigma = parameters["sigma"]
        scaled_noises = samples - mu
        raw_noises = scaled_noises / sigma
        mu_grad = weights @ scaled_noises
        sigma_grad = weights @ (raw_noises**2 - 1)
        return {"mu": mu_grad, "sigma": sigma_grad}

    def update_parameters(self, gradients, *, learning_rates=None, optimizers=None):
        new_mu = self.mu + self._follow_gradient(
            "mu", gradients["mu"], learning_rates=learning_rates, optimizers=optimizers
        )
        new_sigma = self.sigma * jnp.exp(
            0.5
            * self._follow_gradient(
                "sigma", gradients["sigma"], learning_rates=learning_rates, optimizers=optimizers
            )
        )
        return self.modified_copy(mu=new_mu, sigma=new_sigma)





class ExpGaussian(Distribution):
    """Exponential full-covariance Gaussian, as used by XNES
    (reference ``distributions.py:813-1016``). ``sigma`` is ``A``, the square
    root of the covariance; ``sigma_inv`` is tracked independently for
    numerical stability."""

    MANDATORY_PARAMETERS = {"mu", "sigma"}
    OPTIONAL_PARAMETERS = {"sigma_inv"}
    PARAMETER_NDIMS = {"mu": 1, "sigma": 2, "sigma_inv": 2}

    def __init__(self, parameters: dict, *, solution_length: Optional[int] = None, dtype=None, seed=None):
        parameters = dict(parameters)
        mu = jnp.asarray(parameters["mu"])
        sigma = jnp.asarray(parameters["sigma"])
        if sigma.ndim == 1:
            sigma = jnp.diag(sigma)
        parameters["sigma"] = sigma
        if "sigma_inv" not in parameters:
            parameters["sigma_inv"] = jnp.linalg.inv(sigma)
        if solution_length is None:
            solution_length = mu.shape[-1]
        elif solution_length != mu.shape[-1]:
            raise ValueError(
                f"solution_length={solution_length} does not match len(mu)={mu.shape[-1]}"
            )
        if sigma.shape[-1] != mu.shape[-1]:
            raise ValueError(
                f"mu and sigma have mismatching lengths: {mu.shape[-1]} vs {sigma.shape[-1]}"
            )
        super().__init__(solution_length=solution_length, parameters=parameters, dtype=dtype, seed=seed)

    @property
    def mu(self) -> jnp.ndarray:
        return self._parameters["mu"]

    @property
    def sigma(self) -> jnp.ndarray:
        return self._parameters["sigma"]

    @property
    def A(self) -> jnp.ndarray:
        return self.sigma

    @property
    def sigma_inv(self) -> jnp.ndarray:
        return self._parameters["sigma_inv"]

    @property
    def A_inv(self) -> jnp.ndarray:
        return self.sigma_inv

    @property
    def cov(self) -> jnp.ndarray:
        return self.sigma.T @ self.sigma

    @classmethod
    def _to_global(cls, parameters, z):
        # x = mu + A z  (batched: z @ A^T) — reference distributions.py:928
        return parameters["mu"] + z @ parameters["sigma"].T

    @classmethod
    def _to_local(cls, parameters, x):
        # z = A_inv (x - mu) — reference distributions.py:940
        return (x - parameters["mu"]) @ parameters["sigma_inv"].T

    def to_global_coordinates(self, z: jnp.ndarray) -> jnp.ndarray:
        return self._to_global(self._parameters, z)

    def to_local_coordinates(self, x: jnp.ndarray) -> jnp.ndarray:
        return self._to_local(self._parameters, x)

    @classmethod
    def _sample(cls, key, parameters, num_solutions):
        mu = parameters["mu"]
        z = jax.random.normal(key, (num_solutions, mu.shape[-1]), dtype=mu.dtype)
        return cls._to_global(parameters, z)

    @classmethod
    def _compute_gradients(cls, parameters, samples, weights, ranking_used) -> dict:
        z = cls._to_local(parameters, samples)
        weights = _zero_center_weights(weights, ranking_used)
        d_grad = weights @ z
        eye = jnp.eye(z.shape[-1], dtype=z.dtype)
        outer = z[:, :, None] * z[:, None, :]
        M_grad = jnp.sum(weights[:, None, None] * (outer - eye), axis=0)
        return {"d": d_grad, "M": M_grad}

    def update_parameters(self, gradients, *, learning_rates=None, optimizers=None):
        learning_rates = dict(learning_rates) if learning_rates is not None else {}
        if "d" not in learning_rates and "mu" in learning_rates:
            learning_rates["d"] = learning_rates["mu"]
        if "M" not in learning_rates and "sigma" in learning_rates:
            learning_rates["M"] = learning_rates["sigma"]
        update_d = self._follow_gradient("d", gradients["d"], learning_rates=learning_rates, optimizers=optimizers)
        update_M = self._follow_gradient("M", gradients["M"], learning_rates=learning_rates, optimizers=optimizers)
        new_mu = self.mu + self.A @ update_d
        expm = jax.scipy.linalg.expm
        new_A = self.A @ expm(0.5 * update_M)
        new_A_inv = expm(-0.5 * update_M) @ self.A_inv
        return self.modified_copy(mu=new_mu, sigma=new_A, sigma_inv=new_A_inv)





# ---------------------------------------------------------------------------
# Functional factories (reference distributions.py:1023-1623)
# ---------------------------------------------------------------------------


def make_functional_sampler(distribution_class: Type[Distribution]) -> Callable:
    """Return a stateless, vmap-batchable sampler
    ``f(key, num_solutions, parameters) -> samples``
    (reference ``distributions.py:1023-1193`` ``FunctionalSampler``).

    Batch dims on the parameter arrays produce batched sample populations; the
    key is split across the batch automatically."""

    param_ndims = distribution_class.PARAMETER_NDIMS

    def sampler(key, num_solutions: int, parameters: dict) -> jnp.ndarray:
        # normalized ONCE on the host side: num_solutions must never look
        # like a traced value inside the vmapped `one` below (graftlint
        # `host-sync` — int() under trace is a concretization hazard)
        num_solutions = int(num_solutions)
        array_params = {
            k: jnp.asarray(v)
            for k, v in parameters.items()
            if k in param_ndims and not isinstance(v, str)
        }
        other_params = {k: v for k, v in parameters.items() if k not in array_params}
        batch_shape = ()
        for k, v in array_params.items():
            nd = param_ndims[k]
            batch_shape = jnp.broadcast_shapes(batch_shape, v.shape[: v.ndim - nd])
        if batch_shape == ():
            return distribution_class._sample(key, {**array_params, **other_params}, num_solutions)

        import math as _math

        bsize = _math.prod(batch_shape)
        flat_params = {}
        for k, v in array_params.items():
            nd = param_ndims[k]
            core = v.shape[v.ndim - nd :]
            flat_params[k] = jnp.broadcast_to(v, batch_shape + core).reshape((bsize,) + core)
        keys = jax.random.split(key, bsize)

        def one(key, params):
            return distribution_class._sample(key, {**params, **other_params}, num_solutions)

        out = jax.vmap(one)(keys, flat_params)
        return out.reshape(batch_shape + out.shape[1:])

    sampler.__name__ = f"functional_sampler_of_{distribution_class.__name__}"
    return sampler


def make_functional_grad_estimator(
    distribution_class: Type[Distribution],
    *,
    function: Optional[Callable] = None,
    objective_sense: str,
    ranking_method: str = "raw",
    return_samples: bool = False,
    return_fitnesses: bool = False,
) -> Callable:
    """Return a stateless gradient estimator
    (reference ``distributions.py:1196-1623`` ``FunctionalGradEstimator``).

    Without ``function``: ``g(samples, fitnesses, parameters) -> grads``.
    With a bound fitness ``function``: ``g(key, num_solutions, parameters,
    *fn_args) -> grads`` (samples internally, evaluates, estimates). Extra
    outputs are appended when ``return_samples``/``return_fitnesses``."""

    higher_is_better = {"max": True, "min": False}[objective_sense]
    sampler = make_functional_sampler(distribution_class)
    param_ndims = distribution_class.PARAMETER_NDIMS

    def _estimate(parameters: dict, samples, fitnesses) -> dict:
        array_params = {
            k: jnp.asarray(v)
            for k, v in parameters.items()
            if k in param_ndims and not isinstance(v, str)
        }
        other_params = {k: v for k, v in parameters.items() if k not in array_params}
        batch_shape = ()
        for k, v in array_params.items():
            nd = param_ndims[k]
            batch_shape = jnp.broadcast_shapes(batch_shape, v.shape[: v.ndim - nd])
        batch_shape = jnp.broadcast_shapes(batch_shape, jnp.asarray(fitnesses).shape[:-1])

        def one(params, samples, fitnesses):
            weights = rank(fitnesses, ranking_method, higher_is_better=higher_is_better)
            return distribution_class._compute_gradients(
                {**params, **other_params}, samples, weights, ranking_method
            )

        if batch_shape == ():
            return one(array_params, jnp.asarray(samples), jnp.asarray(fitnesses))

        import math as _math

        bsize = _math.prod(batch_shape)
        flat_params = {}
        for k, v in array_params.items():
            nd = param_ndims[k]
            core = v.shape[v.ndim - nd :]
            flat_params[k] = jnp.broadcast_to(v, batch_shape + core).reshape((bsize,) + core)
        samples = jnp.asarray(samples)
        fitnesses = jnp.asarray(fitnesses)
        samples = jnp.broadcast_to(samples, batch_shape + samples.shape[-2:]).reshape(
            (bsize,) + samples.shape[-2:]
        )
        fitnesses = jnp.broadcast_to(fitnesses, batch_shape + fitnesses.shape[-1:]).reshape(
            (bsize,) + fitnesses.shape[-1:]
        )
        out = jax.vmap(one)(flat_params, samples, fitnesses)
        return jax.tree_util.tree_map(lambda leaf: leaf.reshape(batch_shape + leaf.shape[1:]), out)

    if function is None:

        def estimator(samples, fitnesses, parameters: dict):
            return _estimate(parameters, samples, fitnesses)

    else:

        def estimator(key, num_solutions: int, parameters: dict, *fn_args, **fn_kwargs):
            samples = sampler(key, num_solutions, parameters)
            fitnesses = function(samples, *fn_args, **fn_kwargs)
            grads = _estimate(parameters, samples, fitnesses)
            extras = []
            if return_samples:
                extras.append(samples)
            if return_fitnesses:
                extras.append(fitnesses)
            if extras:
                return (grads, *extras)
            return grads

    estimator.__name__ = f"functional_grad_estimator_of_{distribution_class.__name__}"
    return estimator


for _cls in (SeparableGaussian, SymmetricSeparableGaussian, ExpSeparableGaussian, ExpGaussian):
    _cls.functional_sample = staticmethod(_make_class_functional_sample(_cls))
del _cls
