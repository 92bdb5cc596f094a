"""The pass over the latent cache as one TPU kernel a layer.

``decoder.py:LatentAttention`` keeps, per lane, a ring of compressed rows
``c`` ``(slots, kv_rank)`` and one shared RoPE key ``kr`` ``(slots, rope)`` a
position. Between the cache write and ``W_UV`` a step computes, per lane and
for all heads at once,

    s = (q_lat c^T + q_r kr^T) * scale,  p = softmax(s over the readable slots),  o = p c

The plain form (``LatentAttention._cache_plain``) hands XLA three ``einsum``s
and a softmax; on the v5e they read every lane's WHOLE cache under the mask
``age <= t``, ``c`` twice (PERF.md, PR 32).

Here one kernel a layer has a grid over (group of lanes, block of ``BLOCK``
positions) and **brings a block of a lane's rows into VMEM once**,
double-buffered by the ``BlockSpec`` pipeline, for both products: the scores
of all heads over it, a running maximum and sum in float32 (the blockwise
softmax), and the weighted sum into a resident float32 accumulator, rounded
once to the caller's dtype when the walk ends (a float32 result that XLA
converts afterwards costs a pass of its own over 25 MB a layer). Operands in
the cache's dtype, float32 accumulation on the MXU. A grid step is ONE
batched product over its group's lanes, a softmax over all of them and one
more batched product: with 20 query rows a lane, a lane at a time is a chain
of two short matmuls with a softmax between them, and its latency, not the
MXU or the memory, set the pace (PERF.md, PR 33: 0.35 us a lane and block
against 0.19 of DMA). Queries come in and the result goes out HEADS FIRST,
``(heads, n, .)`` in memory, as XLA's per-head products on both sides of the
kernel leave and take them (``attend`` swaps the axes, which costs XLA
nothing); the kernel turns a group's block lanes first once, in VMEM, where
a lanes-first operand cost a relayout of ``(n, heads, .)`` in HBM on each
side (0.32 ms a step against 0.16 for the turns; PERF.md, PR 33).

**It stops at what a lane has filled.** The write pointer is one number for
all lanes, so a lane's readable slots are one contiguous segment of the ring
that ends at ``slot`` and is ``min(t + 1, slots)`` long. The blocks a group
walks are counted BACK from the block that holds ``slot``; the trip count is
read from the group's largest ``t`` (scalar prefetch), the tail is masked by
``age <= t``, and a block nobody in the group can read is neither fetched
nor computed (the pipeline fetches a block only when its index changes, and
a grid step past the trip count holds the next group's first block). The
ring wrapping is the same walk modulo ``slots // BLOCK``. The first block of
the walk holds ``slot``, which every lane can read (``t >= 0``), so every
lane's running maximum is finite from its first block on and a block that is
all masked for a lane adds exact zeros.

Rounding, against the plain form in bfloat16: the plain form divides the
exponentials by their sum and then rounds the weights to bfloat16; the
kernel rounds the exponentials (they are the second product's operand) and
divides the float32 result by the float32 sum of the unrounded ones. Both
round a weight once, to eight bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["BLOCK", "KERNEL_NAME", "fits", "lane_group", "trips", "attend"]

#: positions of one block: the grain at which the walk stops (measured: PERF.md, PR 33)
BLOCK = 64
#: the kernel's name in a compiled program's text
KERNEL_NAME = "latent_cache_attend"

_LANES = 128
#: bytes of one block of compressed rows in VMEM (two buffers of it)
_BLOCK_BYTES = 2 << 20
_VMEM_LIMIT = 64 << 20


def lane_group(n: int, kv_rank: int, itemsize: int) -> int:
    """Lanes a grid step takes: the most, of whole sublane tiles, that divide
    ``n`` and keep a block of compressed rows within ``_BLOCK_BYTES`` (a grid
    step costs a third of a microsecond whatever it moves); 0 where none
    does."""
    groups = [g for g in (64, 32, 16, 8) if n % g == 0 and g * BLOCK * kv_rank * itemsize <= _BLOCK_BYTES]
    return groups[0] if groups else 0


def fits(n: int, kv_rank: int, slots: int, dtype) -> bool:
    """Whether the kernel takes these sizes: a cache dtype the MXU
    multiplies, whole registers along the compressed row, whole blocks of
    positions, lanes that fill a group (the one-lane dense form does not),
    and no mesh that spreads the lanes (the partitioner cannot split a
    kernel)."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if kv_rank % _LANES or slots % BLOCK:
        return False
    if not lane_group(n, kv_rank, jnp.dtype(dtype).itemsize):
        return False
    return all(size == 1 for size in jax.sharding.get_abstract_mesh().shape.values())


def trips(t, slot, slots: int, group: int):
    """Blocks each group of ``group`` lanes walks: the block that holds
    ``slot`` and as many before it as the group's longest readable segment
    (``min(t + 1, slots)``) reaches, at most all of them. ``(n / group,)``
    int32."""
    longest = jnp.max(jnp.minimum(t + 1, slots).reshape(-1, group), axis=1)
    before = jnp.maximum(longest - (slot % BLOCK + 1), 0)  # readable positions in earlier blocks
    return jnp.minimum(1 + (before + BLOCK - 1) // BLOCK, slots // BLOCK).astype(jnp.int32)


def _block_held(slot_ref, trips_ref, g, j, blocks):
    """The ``(group, block of positions)`` grid step ``(g, j)`` holds in
    VMEM. Within the group's trip count: its own ``j``-th block back from the
    one that holds ``slot``. Past it the step computes nothing and holds the
    NEXT group's first block, so that block's DMA runs under this group's
    last pass (the pipeline fetches a step ahead, and only when the index
    changes); the last group's idle steps repeat its last block."""
    groups = trips_ref.shape[0]
    live, more = j < trips_ref[g], g + 1 < groups
    group = jnp.where(live | ~more, g, g + 1)
    back = jnp.where(live, j, jnp.where(more, 0, trips_ref[g] - 1))
    return group, jax.lax.rem(slot_ref[0] // BLOCK - back + blocks, blocks)


def _kernel(slot_ref, trips_ref, t_ref, q_ref, qr_ref, c_ref, kr_ref, out_ref, m_ref, l_ref, acc_ref,
            q_lanes_ref, qr_lanes_ref, *, scale, slots):  # fmt: skip
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    g, j = pl.program_id(0), pl.program_id(1)
    blocks = slots // BLOCK

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the queries arrive heads first, as the products before the kernel leave them
        q_lanes_ref[...] = jnp.swapaxes(q_ref[...], 0, 1)
        qr_lanes_ref[...] = jnp.swapaxes(qr_ref[...], 0, 1)

    @pl.when(j < trips_ref[g])
    def _():
        slot = slot_ref[0]
        first = _block_held(slot_ref, trips_ref, g, j, blocks)[1] * BLOCK
        behind = slot - (first + jax.lax.broadcasted_iota(jnp.int32, (1, 1, BLOCK), 2))
        age = jnp.where(behind < 0, behind + slots, behind)  # (slot - position) mod slots
        c = c_ref[...]
        s = jnp.einsum("ghr,gsr->ghs", q_lanes_ref[...], c, preferred_element_type=f32)
        s = (s + jnp.einsum("ghd,gsd->ghs", qr_lanes_ref[...], kr_ref[...], preferred_element_type=f32)) * scale
        s = jnp.where(age <= t_ref[...], s, -jnp.inf)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        shrink = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = shrink * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = shrink * acc_ref[...] + jnp.einsum(
            "ghs,gsr->ghr", p.astype(c.dtype), c, preferred_element_type=f32
        )
        m_ref[...] = m_new

    @pl.when(j == blocks - 1)
    def _():
        out_ref[...] = jnp.swapaxes(acc_ref[...] / l_ref[...], 0, 1).astype(out_ref.dtype)


def attend(q_lat, q_r, c, kr, t, slot, *, scale, out_dtype=jnp.float32, interpret=False):
    """Every lane's heads over its readable rows, and the positions fetched
    for it.

    ``q_lat`` ``(n, heads, kv_rank)`` and ``q_r`` ``(n, heads, rope)`` in the
    cache's dtype; ``c`` ``(n, slots, kv_rank)`` and ``kr`` ``(n, slots,
    rope)`` the written caches as they lie in memory; ``t`` ``(n,)`` the
    lanes' positions, ``slot`` the one write pointer (already ``mod slots``).
    Returns ``o`` ``(n, heads, kv_rank)``, accumulated in float32 and
    rounded once to ``out_dtype``, and, per lane, the positions in the blocks
    fetched for its group, int32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, heads, kv_rank = q_lat.shape
    slots, rope = c.shape[1], kr.shape[2]
    group = lane_group(n, kv_rank, c.dtype.itemsize)
    blocks = slots // BLOCK
    slot = jnp.asarray(slot, jnp.int32).reshape(1)
    t = t.astype(jnp.int32)
    walk = trips(t, slot[0], slots, group)

    per_group = lambda *shape: pl.BlockSpec((group,) + shape, lambda g, j, *_: (g, 0, 0))
    heads_first = lambda width: pl.BlockSpec((heads, group, width), lambda g, j, *_: (0, g, 0))
    rows = lambda width: pl.BlockSpec(
        (group, BLOCK, width), lambda g, j, slot, walk: (*_block_held(slot, walk, g, j, blocks), 0)
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, slots=slots),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // group, blocks),
            in_specs=[per_group(1, 1), heads_first(kv_rank), heads_first(rope), rows(kv_rank), rows(rope)],
            out_specs=heads_first(kv_rank),
            scratch_shapes=[pltpu.VMEM((group, heads, 1), jnp.float32)] * 2
            + [pltpu.VMEM((group, heads, kv_rank), jnp.float32)]
            + [pltpu.VMEM((group, heads, kv_rank), c.dtype), pltpu.VMEM((group, heads, rope), c.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((heads, n, kv_rank), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        name=KERNEL_NAME,
        interpret=interpret,
    )(slot, walk, t.reshape(n, 1, 1), jnp.swapaxes(q_lat, 0, 1), jnp.swapaxes(q_r, 0, 1), c, kr)
    return jnp.swapaxes(out, 0, 1), jnp.repeat(walk * BLOCK, group)
