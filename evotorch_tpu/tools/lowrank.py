"""Factored (low-rank) population representation.

``LowRankParamsBatch`` expresses a population as ``theta_i = center +
basis @ coeffs[i]`` — a shared per-generation basis with per-lane
coefficients — so the dense ``(N, L)`` population matrix is never
materialized. It is the population currency of the MXU path for wide
policies (see ``neuroevolution/net/lowrank.py`` for the policy-forward
machinery and ``distributions.py`` for the factored PGPE gradients).

The container lives here (L1 tools) because the layers above it all
speak it: ``core.SolutionBatch`` can hold one, ``distributions`` samples
and differentiates one, and ``neuroevolution.net`` rolls one out. It is
a NamedTuple, hence a JAX pytree: it passes through ``jit`` /
``shard_map`` boundaries like any array.

No reference counterpart: the reference evaluates dense populations only
(reference ``distributions.py:616-773`` samples full vectors); this is a
TPU-first framework feature (VERDICT r2 #2).
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "DeltaFactor",
    "FACTORED_BATCH_TYPES",
    "LowRankParamsBatch",
    "TrunkDeltaParamsBatch",
    "basis_capture",
    "dense_values",
    "factor_leaves",
    "is_factored",
    "write_leaves",
]


class LowRankParamsBatch(NamedTuple):
    """A population expressed as ``theta_i = center + basis @ coeffs[i]``.

    ``basis`` is the *effective* basis: per-generation direction matrix with
    any per-parameter scale (e.g. PGPE's sigma) already folded in.
    """

    center: jnp.ndarray  # (L,)
    basis: jnp.ndarray  # (L, k)
    coeffs: jnp.ndarray  # (N, k)

    @property
    def popsize(self) -> int:
        return self.coeffs.shape[0]

    @property
    def rank(self) -> int:
        return self.basis.shape[-1]

    def take(self, idx) -> "LowRankParamsBatch":
        """Gather lanes (the rollout engine's compaction); center/basis are
        shared across lanes and ride along untouched."""
        return LowRankParamsBatch(self.center, self.basis, self.coeffs[idx])

    def materialize(self) -> jnp.ndarray:
        """The dense ``(N, L)`` population (the correctness fallback — avoid
        on the hot path; this is exactly the matrix the representation
        exists to not build)."""
        return self.center + self.coeffs @ self.basis.T

    def materialize_rows(self, coeff_rows: jnp.ndarray) -> jnp.ndarray:
        """Densify specific coefficient rows ``(K, k)`` into parameter rows
        ``(K, L)`` — for cheaply extracting a handful of winners without
        building the full population."""
        return self.center + coeff_rows @ self.basis.T


class DeltaFactor(NamedTuple):
    """The rank-``k`` delta factors of one parameter leaf, sigma folded into
    ``b``. By the leaf's rank:

    - 1-D ``(size,)``: ``a`` an empty ``(0, k)`` placeholder, ``b`` the dense
      direction matrix ``(size, k)``: a low-rank bias basis;
    - 2-D ``(out, in)``: ``a`` ``(in, k)``, ``b`` ``(out, k)``: column ``m`` of
      the leaf's basis is ``b_m a_m^T``;
    - 3-D ``(group, in, out)``, a stack of matrices in the grouped product's
      right-hand layout (stacked experts): ``a`` ``(group, in, k)``, ``b``
      ``(group, out, k)``: column ``m`` of member ``e`` is ``a_em b_em^T``.
    """

    a: jnp.ndarray
    b: jnp.ndarray

    @property
    def _stacked(self) -> bool:
        return self.a.ndim == 3

    @property
    def _vector(self) -> bool:
        return self.a.ndim == 2 and self.a.shape[0] == 0

    @property
    def leaf_shape(self) -> tuple:
        if self._stacked:
            return (self.a.shape[0], self.a.shape[1], self.b.shape[1])
        if self._vector:
            return (self.b.shape[0],)
        return (self.b.shape[0], self.a.shape[0])

    def delta(self, rows: jnp.ndarray) -> jnp.ndarray:
        """The leaf's dense deltas ``(K, *leaf_shape)`` of the coefficient
        rows ``(K, k)``."""
        if self._stacked:
            return jnp.einsum("nm,eim,eom->neio", rows, self.a, self.b)
        if self._vector:
            return rows @ self.b.T
        return jnp.einsum("nm,om,im->noi", rows, self.b, self.a)

    def quadratic(self, m: jnp.ndarray) -> jnp.ndarray:
        """``sum_mn basis[l, m] M[m, n] basis[l, n]`` for every entry ``l`` of
        the leaf, from the ``k x k`` terms ``(b_m * b_n)(a_m * a_n)^T``: the
        sigma gradient's row quadratic, never through the ``(L, k)`` basis."""
        if self._vector:
            return jnp.einsum("lm,mn,ln->l", self.b, m, self.b)
        k = m.shape[0]
        bb = (self.b[..., :, None] * self.b[..., None, :]).reshape(self.b.shape[:-1] + (k * k,))
        aa = (self.a[..., :, None] * self.a[..., None, :] * m).reshape(self.a.shape[:-1] + (k * k,))
        if self._stacked:
            return jnp.einsum("eip,eop->eio", aa, bb)
        return bb @ aa.T

    def gram(self) -> jnp.ndarray:
        """``basis^T basis`` of the leaf, ``(k, k)``."""
        if self._vector:
            return self.b.T @ self.b
        bb = jnp.einsum("...om,...on->...mn", self.b, self.b)
        aa = jnp.einsum("...im,...in->...mn", self.a, self.a)
        prod = bb * aa
        return prod.sum(axis=0) if prod.ndim == 3 else prod

    def project(self, leaf: jnp.ndarray) -> jnp.ndarray:
        """``basis^T v`` for the leaf-shaped piece ``leaf`` of a vector, ``(k,)``."""
        if self._stacked:
            return jnp.einsum("eio,eim,eom->m", leaf, self.a, self.b)
        if self._vector:
            return self.b.T @ leaf
        return jnp.sum((leaf @ self.a) * self.b, axis=0)


def factor_leaves(factors) -> list:
    """The :class:`DeltaFactor` nodes of a factor tree with their offsets in
    the flat parameter vector: ``[(offset, factor), ...]`` in the order
    ``ravel_pytree`` lays the parameter leaves out."""
    nodes = jax.tree_util.tree_leaves(factors, is_leaf=lambda x: isinstance(x, DeltaFactor))
    out, offset = [], 0
    for node in nodes:
        out.append((offset, node))
        offset += math.prod(node.leaf_shape)
    return out


def write_leaves(vector: jnp.ndarray, factors, leaf_fn) -> jnp.ndarray:
    """``vector`` with every parameter leaf's stretch replaced by
    ``leaf_fn(factor, stretch reshaped to the leaf)``, one leaf after the
    other through ``dynamic_update_slice``: under ``jit`` the stretches are
    written in place (into a donated or a fresh buffer) and only one leaf's
    temporaries live at a time, which is what lets a 700M-entry vector be
    updated beside three others of its size."""
    for offset, factor in factor_leaves(factors):
        shape = factor.leaf_shape
        size = math.prod(shape)
        piece = jax.lax.dynamic_slice(vector, (offset,), (size,)).reshape(shape)
        new = leaf_fn(factor, piece).reshape(size).astype(vector.dtype)
        vector = jax.lax.dynamic_update_slice(vector, new, (offset,))
    return vector


class TrunkDeltaParamsBatch(NamedTuple):
    """A population ``theta_i = center + basis @ coeffs[i]`` whose basis is
    STRUCTURED and never materialised: per parameter leaf, column ``m`` is a
    rank-1 block (:class:`DeltaFactor`), so the policy forward needs only the
    shared-trunk matmul ``x @ W_c^T`` plus two thin shared GEMMs ``((x @ A) *
    z_i) @ B^T`` per layer: the MXU-efficient shared-trunk + per-lane delta
    form (docs/policies.md).

    The batch holds the factors alone. Gradients
    (``distributions.py``), the subspace-exhaustion guardrail
    (:func:`basis_capture`), ``materialize_rows`` and concatenation all work
    leaf by leaf from ``factors``: at the sizes this form exists for, an
    ``(L, k)`` basis is several times the device's memory. Build batches
    through the samplers, not by hand.
    """

    center: jnp.ndarray  # (L,)
    coeffs: jnp.ndarray  # (N, k)
    factors: Any  # per-leaf factor tree (DeltaFactor nodes), sigma folded

    @property
    def popsize(self) -> int:
        return self.coeffs.shape[0]

    @property
    def rank(self) -> int:
        return self.coeffs.shape[-1]

    def take(self, idx) -> "TrunkDeltaParamsBatch":
        """Gather lanes; center and factors are shared and ride along."""
        return self._replace(coeffs=self.coeffs[idx])

    def materialize(self) -> jnp.ndarray:
        """The dense ``(N, L)`` population (correctness fallback only)."""
        return self.materialize_rows(self.coeffs)

    def materialize_rows(self, coeff_rows: jnp.ndarray) -> jnp.ndarray:
        """Densify specific coefficient rows ``(K, k)`` -> ``(K, L)``."""
        rows = coeff_rows.shape[0]
        deltas = [f.delta(coeff_rows).reshape(rows, -1) for _, f in factor_leaves(self.factors)]
        return self.center + jnp.concatenate(deltas, axis=1)


#: every factored population representation: ``theta_i = center +
#: basis @ coeffs[i]`` (the basis a matrix, or implied by factors) with
#: per-lane state living ONLY in ``coeffs``.
#: Code that relies on exactly that algebra (gradients, compaction,
#: padding, dense boundaries) should test ``is_factored`` rather than
#: pinning one concrete class.
FACTORED_BATCH_TYPES = (LowRankParamsBatch, TrunkDeltaParamsBatch)


def is_factored(values) -> bool:
    """True for any factored population batch (low-rank or trunk-delta)."""
    return isinstance(values, FACTORED_BATCH_TYPES)


def basis_capture(basis, vector: jnp.ndarray) -> jnp.ndarray:
    """Fraction of ``vector``'s norm captured by ``span(basis)``:
    ``||P_B v|| / ||v||`` in ``[0, 1]`` (returns 1.0 for a zero vector).
    ``basis`` is an ``(L, k)`` matrix, or a :class:`TrunkDeltaParamsBatch`
    (or its factor tree), whose Gram matrix and projection are summed leaf by
    leaf from the factors.

    The subspace-exhaustion diagnostic of factored search: a rank-``k``
    random basis in ``L`` dimensions captures ~``sqrt(k/L)`` of ANY fixed
    direction in expectation — every per-generation gradient estimate is
    confined to its basis's span, so when the (accumulated) dense gradient
    direction's capture stays far below ~1, most of the signal the dense
    estimator would follow is simply not expressible and progress stalls
    (measured: the HalfCheetah rank-32 stall,
    ``bench_curves/halfcheetah_lowrank_cpu_r5.jsonl``). Cost: one ``k x k``
    solve — O(L k^2).
    """
    v_sq = jnp.sum(vector * vector)
    if isinstance(basis, TrunkDeltaParamsBatch):
        basis = basis.factors
    if hasattr(basis, "shape"):
        gram = basis.T @ basis  # (k, k)
        proj = basis.T @ vector  # (k,)
    else:
        gram = proj = 0.0
        for offset, factor in factor_leaves(basis):
            shape = factor.leaf_shape
            piece = vector[offset : offset + math.prod(shape)].reshape(shape)
            gram = gram + factor.gram()
            proj = proj + factor.project(piece)
    # ridge-regularized normal equations: the basis columns are random and
    # can be near-collinear at high rank
    eye = jnp.eye(gram.shape[0], dtype=gram.dtype)
    ridge = 1e-12 * jnp.maximum(jnp.trace(gram), 1e-30)
    coef = jnp.linalg.solve(gram + ridge * eye, proj)
    captured_sq = jnp.clip(proj @ coef, 0.0, None)
    frac = jnp.sqrt(captured_sq / jnp.maximum(v_sq, 1e-30))
    return jnp.where(v_sq > 0, jnp.clip(frac, 0.0, 1.0), jnp.asarray(1.0, frac.dtype))


def dense_values(values):
    """The dense-boundary rule in one place: materialize a factored
    population into its ``(N, L)`` matrix; pass anything else through.
    Evaluators that only understand dense parameter vectors (plain fitness
    functions, host pools, per-network evals) call this at their entry."""
    if is_factored(values):
        return values.materialize()
    return values
