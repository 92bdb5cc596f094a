"""The held experts' grouped product as one weight-streaming TPU kernel.

``decoder.py:SparseExperts`` sends every lane to ``top_k`` experts and holds
some of them. What the held ones add is, per (lane, expert) pair,

    w * down_e(act(gate_e(y)) * up_e(y)),    m_e(x) = x @ W_e + ((x @ a_e) * z) @ b_e^T

with ``W_e`` the trunk's matrix of expert ``e``, ``a_e``, ``b_e`` the
rank-``k`` factors of the lanes' deltas (``net/lowrank.py``) and ``act`` the
family's activation, a static argument (``ACTIVATIONS``: ``silu`` for SwiGLU
experts, ``relu`` for ReGLU ones). The plain form
(``SparseExperts._experts_plain``) sorts the pairs by expert and hands XLA's
``ragged_dot`` the stacked matrices, nine times a layer, with one-hot matmuls
around them; on the v5e each of them walks the experts' weights at a third of
the memory's rate whatever the rows (PERF.md, PR 28).

Here one kernel a layer has a grid over (held expert, tile of the experts'
width) and **streams every expert's three matrices through VMEM exactly
once**, double-buffered by the ``BlockSpec`` pipeline in blocks of megabytes.
While expert ``e``'s block is resident the kernel walks the lanes that chose
``e`` in row tiles of ``ROW_TILE``, with a trip count read from the expert's
size (scalar prefetch): no pair is dropped, there is no capacity factor, an
empty expert costs its weights' DMA and nothing else, and rows past an
expert's last pair are never visited. Everything between the three products
stays in VMEM: the rows are gathered from the resident ``y`` and summed back
into the resident float32 result by 0/1 matrices built from each lane's rank
within the expert (a ``top_k`` picks an expert at most once per lane, so an
expert's rows are a subset of the lanes, in lane order: a ``cumsum`` over a
``(held, lanes)`` mask takes the place of the sort); the rank-``k``
companions, ``act(gate) * up`` and the router's weight ride in the same
visit. Operands in the compute dtype, float32 accumulation on the MXU.

Rounding, against the plain form in bfloat16: ``hidden`` is rounded once (it
is the third product's operand), a pair's weighted output once (the operand
of the 0/1 sum), the lanes' sums once at the end; the plain form rounds every
one of its nine products and their sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["ROW_TILE", "KERNEL_NAME", "ACTIVATIONS", "fits", "held_experts", "held_experts_for"]

#: rows of one visit: the MXU's height, so a thinner tile would cost the same
ROW_TILE = 128
#: the kernel's name in a compiled program's text
KERNEL_NAME = "held_experts_grouped"
#: the experts' activation, ``act(gate) * up``, by name
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}

_LANES = 128
#: bytes of one matrix's block in VMEM (three matrices, two buffers each)
_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT = 96 << 20
#: what may stay resident beside the weights' blocks: the lanes' inputs and sums
_RESIDENT_BYTES = 24 << 20


def _padded(n: int) -> int:
    return -(-n // _LANES) * _LANES


def fits(n: int, dim: int, width: int, dtype, k: int) -> bool:
    """Whether the kernel takes these sizes: a compute dtype the MXU
    multiplies, whole registers along both widths, a rank within one
    register, and the lanes' inputs and float32 sums small enough to stay in
    VMEM beside the weights' blocks."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if dim % _LANES or width % _LANES or not 0 < k <= _LANES:
        return False
    itemsize = jnp.dtype(dtype).itemsize
    resident = _padded(n) * dim * (4 + itemsize) * 2  # y and the float32 sums, two buffers each
    return resident <= _RESIDENT_BYTES and dim * _LANES * itemsize <= _BLOCK_BYTES


def _width_tile(dim: int, width: int, itemsize: int) -> int:
    """The widest tile of whole registers that divides ``width`` and keeps a
    matrix's block within ``_BLOCK_BYTES``."""
    tiles = [t for t in range(_LANES, width + 1, _LANES) if width % t == 0]
    return max(t for t in tiles if t == _LANES or dim * t * itemsize <= _BLOCK_BYTES)


def _kernel(sizes_ref, rank_row_ref, rank_col_ref, weight_ref, y_ref, z_ref,
            gate_ref, up_ref, down_ref, gate_a_ref, gate_b_ref, up_a_ref, up_b_ref,
            down_a_ref, down_b_ref, out_ref, *, k, activation):  # fmt: skip
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    n, dtype = y_ref.shape[0], y_ref.dtype
    expert = pl.program_id(0)

    @pl.when((expert == 0) & (pl.program_id(1) == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=f32)

    def visit(tile, carry):
        first = tile * ROW_TILE
        # row r of the tile is the lane whose rank within the expert is first + r
        rows = jax.lax.broadcasted_iota(jnp.int32, (ROW_TILE, n), 0) + first
        taken = rank_row_ref[0] == rows  # (ROW_TILE, n)
        gather = taken.astype(dtype)
        x = dot(gather, y_ref[...]).astype(dtype)  # exact: one 1 a row
        z = dot(gather, z_ref[...])[:, :k]
        weight = jnp.sum(jnp.where(taken, weight_ref[0], 0.0), axis=1, keepdims=True)

        def product(x, w_ref, a_ref, b_ref):
            thin = jax.lax.dot_general(
                x, a_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=f32
            )
            return dot(x, w_ref[0]) + dot((thin * z).astype(dtype), b_ref[0])

        gate = product(x, gate_ref, gate_a_ref, gate_b_ref)
        up = product(x, up_ref, up_a_ref, up_b_ref)
        hidden = (ACTIVATIONS[activation](gate) * up).astype(dtype)
        out = product(hidden, down_ref, down_a_ref, down_b_ref) * weight
        columns = jax.lax.broadcasted_iota(jnp.int32, (n, ROW_TILE), 1) + first
        scatter = (rank_col_ref[0] == columns).astype(dtype)  # (n, ROW_TILE)
        out_ref[...] += dot(scatter, out.astype(dtype))
        return carry

    tiles = (sizes_ref[expert] + ROW_TILE - 1) // ROW_TILE
    jax.lax.fori_loop(0, tiles, visit, None)


def held_experts(center, factors, z, y, local, weights, *, activation="silu", interpret=False):
    """What the held experts add to every lane, the lanes on each of them,
    and the row tiles visited.

    ``center``: the trunk's ``gate`` / ``up`` ``(held, dim, width)`` and
    ``down`` ``(held, width, dim)``; ``factors``: the matching ``DeltaFactor``
    nodes (``a`` ``(held, in, k)``, ``b`` ``(held, out, k)``); ``z`` ``(n, k)``
    the lanes' coefficients; ``y`` ``(n, dim)`` in the compute dtype;
    ``local`` ``(n, top_k)`` the chosen experts counted from the first held
    one (anything outside ``[0, held)`` is not held) and ``weights`` their
    float32 router weights. Returns ``(n, dim)`` in ``y``'s dtype, ``(held,)``
    int32 and an int32 scalar. ``activation``: a name of ``ACTIVATIONS``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = y.shape
    held, _, width = center["gate"].shape
    k, dtype = z.shape[-1], y.dtype
    lanes = _padded(n)
    tile = _width_tile(dim, width, jnp.dtype(dtype).itemsize)

    hit = local[None] == jnp.arange(held)[:, None, None]  # (held, n, top_k)
    chose = jnp.any(hit, axis=-1)
    weight = jnp.sum(jnp.where(hit, weights[None], 0.0), axis=-1, dtype=jnp.float32)
    rank = jnp.where(chose, jnp.cumsum(chose, axis=1, dtype=jnp.int32) - 1, -1)
    sizes = jnp.sum(chose, axis=1, dtype=jnp.int32)
    pad = ((0, 0), (0, lanes - n))
    rank = jnp.pad(rank, pad, constant_values=-1)
    weight = jnp.pad(weight, pad)

    def transposed(name, side):
        return jnp.swapaxes(getattr(factors[name], side), 1, 2).astype(dtype)  # (held, k, .)

    # blocks: the same for every step; one expert's; one expert's columns of a width tile
    whole = lambda *shape: pl.BlockSpec(shape, lambda e, j, sizes: (0,) * len(shape))
    per_expert = lambda *shape: pl.BlockSpec((1,) + shape, lambda e, j, sizes: (e, 0, 0))
    columns = lambda rows: pl.BlockSpec((1, rows, tile), lambda e, j, sizes: (e, 0, j))
    operands = [  # in the order of _kernel's arguments, each beside its block
        (per_expert(1, lanes), rank[:, None, :]),
        (per_expert(lanes, 1), rank[:, :, None]),
        (per_expert(1, lanes), weight[:, None, :]),
        (whole(lanes, dim), jnp.pad(y, ((0, lanes - n), (0, 0)))),
        (whole(lanes, _LANES), jnp.pad(z.astype(dtype), ((0, lanes - n), (0, _LANES - k)))),
        (columns(dim), center["gate"].astype(dtype)),
        (columns(dim), center["up"].astype(dtype)),
        (pl.BlockSpec((1, tile, dim), lambda e, j, sizes: (e, j, 0)), center["down"].astype(dtype)),
        (per_expert(k, dim), transposed("gate", "a")),
        (columns(k), transposed("gate", "b")),
        (per_expert(k, dim), transposed("up", "a")),
        (columns(k), transposed("up", "b")),
        (columns(k), transposed("down", "a")),
        (per_expert(k, dim), transposed("down", "b")),
    ]
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(held, width // tile),
            in_specs=[block for block, _ in operands],
            out_specs=whole(lanes, dim),
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        name=KERNEL_NAME,
        interpret=interpret,
    )(sizes, *(array for _, array in operands))
    tiles = jnp.sum((sizes + ROW_TILE - 1) // ROW_TILE, dtype=jnp.int32)
    return out[:n].astype(dtype), sizes, tiles


@functools.cache
def held_experts_for(activation):
    """``held_experts`` with its ``activation`` bound: ONE callable a name, so
    that every layer's call is the same function to jax's caches (a fresh
    ``partial`` a call is not, and lowers the program under other private
    names)."""
    return functools.partial(held_experts, activation=activation)
