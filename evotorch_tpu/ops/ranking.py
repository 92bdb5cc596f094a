"""Fused centered-rank utility kernel.

Transforms a fitness vector into centered utilities (``tools/ranking.py``
semantics) with the rank computation fused in one kernel. The XLA fallback is
the library implementation. The Pallas path materializes an O(n^2) comparison
block in VMEM, so it targets *mid-sized* populations (n up to ~2000, i.e.
n^2 * 4 bytes within the ~16 MB VMEM budget); for larger populations use the
default XLA path, whose argsorts scale O(n log n).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..tools.ranking import centered_xla as _xla_centered

__all__ = ["fused_centered_rank"]


def _pallas_kernel(fit_ref, out_ref):
    fit = fit_ref[0]  # one fitness vector, held as a (1, n) row
    n = fit.shape[-1]
    # rank of each element = number of strictly-smaller elements plus the
    # number of equal elements appearing earlier (stable tie-break), computed
    # as one O(n^2) comparison block living entirely in VMEM — beats the
    # double argsort's three HBM round-trips for mid-sized populations.
    # NaNs order LAST (argsort semantics: jnp.argsort places NaN at the end),
    # so a NaN fitness ranks "best" exactly as in the XLA path — the total
    # order is lexicographic on (isnan, value, index)
    col = fit[:, None]
    row = fit[None, :]
    col_nan = jnp.isnan(col)
    row_nan = jnp.isnan(row)
    idx = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    value_smaller = (row < col) | (~row_nan & col_nan)  # non-NaN < NaN
    equal = (row == col) | (row_nan & col_nan)  # NaN == NaN for the tie-break
    smaller = value_smaller | (equal & (jdx < idx))
    ranks = jnp.sum(smaller.astype(jnp.float32), axis=-1)
    out_ref[0] = ranks / (n - 1) - 0.5


@functools.partial(jax.jit, static_argnames=("higher_is_better", "use_pallas", "interpret"))
def fused_centered_rank(
    fitnesses: jnp.ndarray,
    *,
    higher_is_better: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Centered ranks in ``[-0.5, 0.5]`` along the last axis.

    ``use_pallas=True`` compiles the kernel for the TPU and is an error
    anywhere else, unless ``interpret=True`` (the tests' CPU form) is passed
    explicitly — the backend never picks the mode."""
    x = jnp.asarray(fitnesses)
    if not use_pallas or x.dtype not in (
        jnp.float32,
        jnp.bfloat16,
        jnp.float16,
        jnp.int16,
        jnp.int8,
        jnp.uint16,
        jnp.uint8,
    ):
        # the kernel ranks in f32, so only dtypes whose values embed in f32
        # exactly may take it; f64 (and int32/int64 values >= 2^24) would
        # collide distinct fitnesses in f32, get index tie-breaks, and
        # diverge from centered_xla (which ranks in the input dtype)
        return _xla_centered(x, higher_is_better=higher_is_better)

    from jax.experimental import pallas as pl

    if x.shape[-1] == 1:
        # degenerate population: match the XLA fallback (zeros, no 0/0)
        return jnp.zeros_like(x)

    signed = (x if higher_is_better else -x).astype(jnp.float32)
    batch_shape = signed.shape[:-1]
    n = signed.shape[-1]
    # (B, 1, n): vmap squeezes the batch axis out of the block, and a block
    # whose last two dimensions are the whole (1, n) row is one Mosaic accepts
    # (a squeezed 1-D (n,) block of a (B, n) array is not)
    call = pl.pallas_call(
        _pallas_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )
    out = jax.vmap(call)(signed.reshape((-1, 1, n))).reshape(batch_shape + (n,))
    return out.astype(x.dtype) if jnp.issubdtype(x.dtype, jnp.floating) else out
