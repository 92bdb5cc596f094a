"""Fused antithetic-Gaussian sampling kernel.

Computes the PGPE `ask` population
``[mu + sigma*e0, mu - sigma*e0, mu + sigma*e1, ...]`` with the noise
generated on-chip (``pltpu.prng_random_bits`` + Box-Muller) and scaled in
VMEM — the noise tensor never exists in HBM. Mirrors
``SymmetricSeparableGaussian._sample`` (evotorch_tpu/distributions.py), whose
XLA form is the fallback.

The kernel runs on a grid of ``(_BLOCK_ROWS, _BLOCK_LANES)`` blocks aligned
to the f32 ``(8, 128)`` tiling, so one step's working set (the two output
planes of the block, double-buffered, plus the Box-Muller temporaries) stays
a few MiB whatever the population: an ungridded call put the whole
``(2, half, L)`` output in one VMEM window and was refused at the PGPE
flagship shape (10,000 x 12,305 = 497 MB against 128 MiB of VMEM).

TPU only: the on-chip PRNG has no lowering elsewhere, and Pallas's TPU
interpreter stubs it with zeros, so there is no interpret mode — the kernel
is compiled for a v5e in tests/test_ops.py and run on the chip by
chip_smoke.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["sample_symmetric_gaussian"]

_TWO_PI = 2.0 * math.pi

#: directions x parameters per grid step (multiples of the f32 (8, 128) tile)
_BLOCK_ROWS = 256
_BLOCK_LANES = 1024


def _xla_fallback(key, mu, sigma, num_directions):
    eps = jax.random.normal(key, (num_directions, mu.shape[-1]), dtype=mu.dtype) * sigma
    return jnp.stack([mu + eps, mu - eps], axis=1).reshape(2 * num_directions, mu.shape[-1])


def _bits_to_unit_float(bits):
    """Random bits -> float32 in [1, 2) via the mantissa trick (Mosaic has
    no integer->float cast; ``prng_random_bits`` yields int32)."""
    bits = jax.lax.bitcast_convert_type(bits, jnp.uint32)
    mantissa = jax.lax.shift_right_logical(bits, jnp.uint32(9))
    return jax.lax.bitcast_convert_type(
        jax.lax.bitwise_or(mantissa, jnp.uint32(0x3F800000)), jnp.float32
    )


def _box_muller(bits_a, bits_b):
    """Standard-normal noise from two random-bit draws (runs inside the
    kernel)."""
    u1 = 2.0 - _bits_to_unit_float(bits_a)  # in (0, 1]: log never sees 0
    u2 = _bits_to_unit_float(bits_b) - 1.0  # in [0, 1)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(_TWO_PI * u2)


def _pallas_kernel(seed_ref, mu_ref, sigma_ref, out_ref):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # one stream per block: the call's seed (a scalar, prefetched into SMEM)
    # and the block's linear grid index (Mosaic seeds with at most 2 values)
    pltpu.prng_seed(
        seed_ref[0], pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    )
    block = out_ref.shape[1:]
    eps = _box_muller(pltpu.prng_random_bits(block), pltpu.prng_random_bits(block))
    scaled = eps * sigma_ref[:]
    # plane 0 = mu+scaled, plane 1 = mu-scaled (Mosaic cannot lower strided
    # interleaved stores; the caller interleaves the two contiguous planes)
    out_ref[0] = mu_ref[:] + scaled
    out_ref[1] = mu_ref[:] - scaled


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


@functools.partial(jax.jit, static_argnames=("num_solutions", "use_pallas"))
def sample_symmetric_gaussian(
    key,
    mu: jnp.ndarray,
    sigma: jnp.ndarray,
    num_solutions: int,
    *,
    use_pallas: bool = False,
) -> jnp.ndarray:
    """Sample an antithetic population of ``num_solutions`` (even) solutions.

    ``use_pallas=True`` runs the fused TPU kernel (an error off the chip);
    the default is the XLA path, which produces the same distribution
    (different streams: XLA threefry vs on-chip PRNG)."""
    if num_solutions % 2 != 0:
        raise ValueError(f"num_solutions must be even, got {num_solutions}")
    half = num_solutions // 2
    if not use_pallas:
        return _xla_fallback(key, mu, sigma, half)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    length = mu.shape[-1]
    # aligned blocks; Pallas masks the part of an edge block past the array
    rows = min(_BLOCK_ROWS, _round_up(half, 8))
    lanes = min(_BLOCK_LANES, _round_up(length, 128))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(half, rows), pl.cdiv(length, lanes)),
        in_specs=[
            pl.BlockSpec((1, lanes), lambda i, j, seed: (0, j)),
            pl.BlockSpec((1, lanes), lambda i, j, seed: (0, j)),
        ],
        out_specs=pl.BlockSpec((2, rows, lanes), lambda i, j, seed: (0, i, j)),
    )
    seed = jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
    planes = pl.pallas_call(
        _pallas_kernel,
        out_shape=jax.ShapeDtypeStruct((2, half, length), jnp.float32),
        grid_spec=grid_spec,
    )(seed, mu.astype(jnp.float32)[None], sigma.astype(jnp.float32)[None])
    # (2, half, L) -> interleaved (2*half, L): [mu+e0, mu-e0, mu+e1, ...]
    return planes.transpose(1, 0, 2).reshape(num_solutions, length).astype(mu.dtype)
