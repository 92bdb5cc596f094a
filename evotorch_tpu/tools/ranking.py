"""Fitness-shaping (ranking) kernels.

Parity with the reference's ``tools/ranking.py:24-216`` (methods ``centered``,
``linear``, ``nes``, ``normalized``, ``raw`` and the dispatcher ``rank``), but
written as pure jnp functions over the *last* axis so they are `jit`/`vmap`
friendly by construction. All methods return utilities where **higher is
better**, regardless of the objective sense of the raw fitnesses.
"""

from __future__ import annotations

import os
from typing import Callable, Dict

import jax.numpy as jnp

__all__ = [
    "centered",
    "linear",
    "nes",
    "normalized",
    "raw",
    "rank",
    "rankers",
]


def _ascending_ranks(fitnesses: jnp.ndarray) -> jnp.ndarray:
    """Integer ranks along the last axis: 0 for the lowest fitness, n-1 for the
    highest. Ties receive distinct ranks (argsort-of-argsort), matching the
    reference's torch ``argsort`` behavior."""
    order = jnp.argsort(fitnesses, axis=-1)
    idx = jnp.broadcast_to(jnp.arange(fitnesses.shape[-1]), fitnesses.shape)
    return jnp.put_along_axis(jnp.zeros_like(order), order, idx, axis=-1, inplace=False)


def _float_dtype_like(x: jnp.ndarray):
    return x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32


def _use_fused_centered(n: int) -> bool:
    """Dispatch ``centered`` to the fused Pallas kernel (``ops/ranking.py``)?
    Default: **off** — the kernel ships opt-in until an on-chip micro-bench
    (``bench_ops.py``) records a win over ``centered_xla`` at representative
    population sizes; an unmeasured default in every TPU PGPE generation is
    risk with no evidence. Opt in with ``EVOTORCH_TPU_FUSED_RANK=1`` (on the
    TPU, any n that fits VMEM; off the chip the kernel is an error); ``=0``
    pins it off. Read at trace time: jitted callers bake the decision into
    their compiled executable."""
    flag = os.environ.get("EVOTORCH_TPU_FUSED_RANK", "auto")
    if flag != "1":
        return False
    # 1024^2 * (4B f32 + 1B bool + 8B iotas) comparison block stays well
    # inside the ~16 MB/core VMEM budget; 2048 would already exceed it
    return 2 <= n <= 1024


def centered_xla(fitnesses: jnp.ndarray, *, higher_is_better: bool = True) -> jnp.ndarray:
    """The plain double-argsort implementation of :func:`centered` — the
    non-dispatching form the fused kernel falls back to."""
    x = fitnesses if higher_is_better else -fitnesses
    n = x.shape[-1]
    ranks = _ascending_ranks(x).astype(_float_dtype_like(jnp.asarray(fitnesses)))
    if n == 1:
        return jnp.zeros_like(ranks)
    return ranks / (n - 1) - 0.5


def centered(fitnesses: jnp.ndarray, *, higher_is_better: bool = True) -> jnp.ndarray:
    """Centered ranks in ``[-0.5, +0.5]`` (reference ``ranking.py:24``)."""
    if _use_fused_centered(jnp.asarray(fitnesses).shape[-1]):
        from ..ops.ranking import fused_centered_rank

        return fused_centered_rank(
            jnp.asarray(fitnesses), higher_is_better=higher_is_better, use_pallas=True
        )
    return centered_xla(fitnesses, higher_is_better=higher_is_better)


def linear(fitnesses: jnp.ndarray, *, higher_is_better: bool = True) -> jnp.ndarray:
    """Linearly spaced ranks in ``[0, 1]`` (reference ``ranking.py:56``)."""
    return centered(fitnesses, higher_is_better=higher_is_better) + 0.5


def nes(fitnesses: jnp.ndarray, *, higher_is_better: bool = True) -> jnp.ndarray:
    """NES utility weights (reference ``ranking.py:84``): for the k-th best of n
    solutions, ``u_k = max(0, ln(n/2+1) - ln(k))``, normalized to sum 1, then
    shifted by ``-1/n`` so the weights sum to 0."""
    x = fitnesses if higher_is_better else -fitnesses
    n = x.shape[-1]
    asc = _ascending_ranks(x)
    # k = 1 for the best solution, n for the worst
    k = (n - asc).astype(_float_dtype_like(jnp.asarray(fitnesses)))
    u = jnp.maximum(0.0, jnp.log(n / 2.0 + 1.0) - jnp.log(k))
    u = u / jnp.sum(u, axis=-1, keepdims=True)
    return u - 1.0 / n


def normalized(fitnesses: jnp.ndarray, *, higher_is_better: bool = True) -> jnp.ndarray:
    """Z-score normalization (reference ``ranking.py:127``; unbiased stdev,
    ddof=1, matching torch.std)."""
    x = fitnesses if higher_is_better else -fitnesses
    mean = jnp.mean(x, axis=-1, keepdims=True)
    std = jnp.std(x, axis=-1, keepdims=True, ddof=1) if x.shape[-1] > 1 else jnp.ones_like(mean)
    return (x - mean) / jnp.where(std == 0, 1.0, std)


def raw(fitnesses: jnp.ndarray, *, higher_is_better: bool = True) -> jnp.ndarray:
    """Raw fitnesses, sign-adjusted so higher is better (reference ``ranking.py:163``)."""
    x = jnp.asarray(fitnesses)
    x = x if higher_is_better else -x
    return x.astype(_float_dtype_like(x))


rankers: Dict[str, Callable] = {
    "centered": centered,
    "linear": linear,
    "nes": nes,
    "normalized": normalized,
    "raw": raw,
}


def _nonfinite_to_worst(x: jnp.ndarray, *, higher_is_better: bool) -> jnp.ndarray:
    """Non-finite fitnesses replaced by the worst finite one (per batch row).

    Without this, argsort's total order places NaN LAST — i.e. a diverged
    rollout ranks "best" and every utility-weighted update chases it; under
    ``normalized``/``raw`` a single NaN poisons the whole utility vector.
    Defense in depth behind the engines' score quarantine
    (docs/resilience.md): identity on all-finite input, so guarded ranking
    is bit-identical to unguarded whenever nothing is wrong. An
    all-non-finite row falls back to 0 utility everywhere.
    """
    finite = jnp.isfinite(x)
    big = jnp.asarray(jnp.finfo(x.dtype).max, dtype=x.dtype)
    if higher_is_better:
        worst = jnp.min(jnp.where(finite, x, big), axis=-1, keepdims=True)
        worst = jnp.where(worst >= big, jnp.zeros((), x.dtype), worst)
    else:
        worst = jnp.max(jnp.where(finite, x, -big), axis=-1, keepdims=True)
        worst = jnp.where(worst <= -big, jnp.zeros((), x.dtype), worst)
    return jnp.where(finite, x, worst)


def rank(
    fitnesses,
    ranking_method: str = "raw",
    *,
    higher_is_better: bool,
    guard_nonfinite: bool = True,
) -> jnp.ndarray:
    """Dispatcher (reference ``ranking.py:189``). Works along the last axis so
    leading batch dimensions (batched searches) are supported natively.

    ``guard_nonfinite`` (default on) sanitizes NaN/Inf fitnesses to the
    worst finite value before shaping — see :func:`_nonfinite_to_worst`;
    pass False for the reference's unguarded argsort semantics."""
    try:
        fn = rankers[ranking_method]
    except KeyError:
        raise ValueError(
            f"Unknown ranking method {ranking_method!r}; expected one of {sorted(rankers)}"
        )
    x = jnp.asarray(fitnesses)
    if guard_nonfinite and jnp.issubdtype(x.dtype, jnp.floating):
        x = _nonfinite_to_worst(x, higher_is_better=higher_is_better)
    return fn(x, higher_is_better=higher_is_better)
