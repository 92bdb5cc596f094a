"""The pass over a Mamba-2 layer's matrix states as one in-place TPU kernel.

``decoder.py:Mamba2Mixer`` keeps, per lane, one matrix state a head, ``heads x
head_dim x state_dim`` numbers in all, and rewrites ALL of it every step:

    S_t = a S_{t-1} + (dt x_t) (outer) B_t,    y = S_t C_t

``a`` and ``dt`` one number a head, ``x_t`` one a (head, row), ``B_t`` and
``C_t`` ``state_dim`` numbers a lane. The plain form
(``Mamba2Mixer._state_plain``) hands XLA three lines of ``jax.numpy``; on the
v5e they are one fusion a layer that reads and writes a layer's states at
three quarters of the memory's nominal rate (PERF.md, PR 34).

**That fusion was bound by the memory, not by the vector unit** (PERF.md, PR
35): a bare copy of the states through a ``BlockSpec`` pipeline, nothing
computed, takes 96% of its time, because the pipeline keeps the read of one
block and the write of another in flight TOGETHER, and this chip's memory moves
5% more a second in one direction at a time. So here one kernel a layer walks
the lanes in blocks of ``_BLOCK_BYTES`` through three slots of VMEM with DMAs
of its own: while block ``i`` is updated in its slot, block ``i + 1`` is read
into the next slot and THEN block ``i - 1`` written back from the third; the
read and the write never overlap each other, the float32 work runs under
both. A lane's state comes into VMEM once, is updated there and goes back to
where it lay (the state operand is aliased to the state result: the compiled
loop goes on holding each state once).

**The stored layout** is ``(state_dim, heads x head_dim)`` a lane: ``state_dim``
runs along the sublanes of a register and (head, row) along its 128 lanes. The
readout ``S_t C_t`` is then a sum of REGISTERS (sixteen of them a column of
128 (head, row) pairs, then one fold of sublanes), ``a`` and ``dt x`` are row
vectors that a register takes as they lie, and only ``B`` and ``C`` (128
numbers a lane) have to be spread along lanes, once a lane. With ``state_dim``
last, as ``mamba_ssm`` has it, the readout is a reduction ACROSS the lanes of
every one of a layer's 131,072 registers. The lanes' small operands travel as
ONE array of rows of 128, ``decay | dt x | B | C`` (the decays one a HEAD, as
they are computed: XLA keeps the lanes' activations lane axis last, so what
the kernel takes a lane at a time costs a relayout of its bytes first), one
DMA a block, and the readouts leave in rows of 128 too: a lane is then a
leading index, a row a static one.

**What XLA is told.** XLA prefetches the next matrices' blocks into VMEM while
a long operation runs, and it takes a custom call for a short one: without a
``cost_estimate`` it started those fetches only when the kernel had ended, and
the waits for them cost the step a third of what the kernel had gained
(PERF.md, PR 35). The kernel also asks for the VMEM of its three slots and
little more (``vmem_limit_bytes``): what a kernel reserves, XLA cannot
prefetch into.

The arithmetic is the plain form's: the stored state converted to float32,
times the float32 decay, plus the float32 product of ``dt x`` and ``B``; the
readout from that UNROUNDED float32 state; one rounding to the stored dtype on
the way out. Only the order of the readout's additions over ``state_dim``
differs from XLA's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["KERNEL_NAME", "fits", "lane_group", "state_pass"]

#: the kernel's name in a compiled program's text
KERNEL_NAME = "ssm_state_pass"

F32 = jnp.float32
_LANES = 128
#: sublanes of one visit: a packed bfloat16 register's, two float32 registers
_ROWS = 16
#: bytes of one block of states in VMEM (three slots of it): the memory's rate
#: grows with the length of a one-way phase (measured: PERF.md, PR 35)
_BLOCK_BYTES = 8 << 20
#: VMEM beyond the slots: the small operands' and readouts' slots, the spread vectors, Mosaic's own
_VMEM_BESIDE = 4 << 20


def lane_group(n: int, state_dim: int, inner: int, itemsize: int) -> int:
    """Lanes of one block: the most that divide ``n`` and keep a block of
    whole states within ``_BLOCK_BYTES``; 0 where one state does not fit."""
    most = _BLOCK_BYTES // (state_dim * inner * itemsize)
    return max((g for g in range(1, min(most, n) + 1) if n % g == 0), default=0)


def fits(n: int, heads: int, head_dim: int, state_dim: int, dtype) -> bool:
    """Whether the kernel takes these sizes: a stored dtype it converts
    (bfloat16 or float32), whole registers both ways (``state_dim`` and
    ``heads x head_dim`` multiples of 128), heads that tile a register's
    lanes or are tiled by them, a lane's state within a block, more than one
    lane (the one-lane dense form under ``vmap`` has one) and no mesh that
    spreads the lanes (the partitioner cannot split a kernel)."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    inner = heads * head_dim
    if state_dim % _LANES or inner % _LANES or (_LANES % head_dim and head_dim % _LANES):
        return False
    if n < 2 or not lane_group(n, state_dim, inner, jnp.dtype(dtype).itemsize):
        return False
    return all(size == 1 for size in jax.sharding.get_abstract_mesh().shape.values())


def _spread(rows):
    """``state_dim`` numbers in rows of 128, ``(state_dim / 128, 128)``, to
    ``(state_dim, 128)``: entry ``[s, j]`` is number ``s``, one a sublane, the
    same in all lanes. A broadcast along sublanes and a transpose of whole
    ``(128, 128)`` squares."""
    squares = [jnp.transpose(jnp.broadcast_to(rows[at : at + 1, :], (_LANES, _LANES))) for at in range(rows.shape[0])]
    return jnp.concatenate(squares, axis=0)


def _lane(l, small, state, y, a_spread, b_spread, c_spread, *, heads):
    """One lane's pass, in place, all in VMEM: ``small`` ``(group, rows,
    128)`` holds the lanes' ``decay | dt x | B | C`` in rows of 128 (``heads``
    decays in whole rows, then ``inner / 128`` and twice ``state_dim / 128``
    rows), ``state`` ``(group, state_dim, inner)`` their states, ``y``
    ``(group, inner / 128, 128)`` takes the readouts. A column of 128 (head,
    row) pairs at a time, ``_ROWS`` sublanes of it at a time: two loops that
    the lowering unrolls whole (their indices are constants there), so the
    kernel's jaxpr holds ONE visit and not the 256 of a lane: tracing one a
    Mamba layer and program cost a process tens of seconds (PERF.md, PR 35)."""
    from jax.experimental import pallas as pl

    state_dim, inner = state.shape[1:]
    wide, tall, head_dim = inner // _LANES, state_dim // _LANES, inner // heads
    first = small.shape[1] - wide - 2 * tall  # the decays' rows
    a_spread[...] = _spread(small[l, :first, :])
    b_spread[...] = _spread(small[l, first + wide : first + wide + tall, :])
    c_spread[...] = _spread(small[l, first + wide + tall :, :])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def column(j, carry):
        cols = pl.ds(j * _LANES, _LANES)
        head = j * _LANES // head_dim  # the first head of this column, and those beside it in its 128 lanes
        decay = a_spread[pl.ds(head, 1), :]
        for beside in range(1, _LANES // head_dim):
            decay = jnp.where(lane >= beside * head_dim, a_spread[pl.ds(head + beside, 1), :], decay)
        decay = jnp.broadcast_to(decay, (_ROWS, _LANES))
        fed = jnp.broadcast_to(small[l, pl.ds(first + j, 1), :], (_ROWS, _LANES))

        def visit(s, read):
            rows = pl.ds(s * _ROWS, _ROWS)
            new = state[l, rows, cols].astype(F32) * decay + b_spread[rows, :] * fed
            state[l, rows, cols] = new.astype(state.dtype)
            return read + new * c_spread[rows, :]

        read = jax.lax.fori_loop(0, state_dim // _ROWS, visit, jnp.zeros((_ROWS, _LANES), F32), unroll=True)
        y[l, pl.ds(j, 1), :] = jnp.sum(read, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, wide, column, 0, unroll=True)


def _kernel(small_hbm, state_hbm, out_hbm, y_hbm, states, smalls, ys, a_spread, b_spread, c_spread, sems, *, group, heads):
    """Blocks of ``group`` lanes through three slots: block ``i`` is updated
    in slot ``i mod 3`` while block ``i + 1`` is read and then, from the
    block's middle lane on, block ``i - 1`` written: one direction at a time
    (the small operands and the readouts ride with their block, through two
    slots each)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blocks = state_hbm.shape[0] // group
    lanes = lambda i: pl.ds(i * group, group)

    def read(i):
        return (
            pltpu.make_async_copy(state_hbm.at[lanes(i)], states.at[i % 3], sems.at[0, i % 3]),
            pltpu.make_async_copy(small_hbm.at[lanes(i)], smalls.at[i % 2], sems.at[1, i % 2]),
        )

    def write(i):
        return (
            pltpu.make_async_copy(states.at[i % 3], out_hbm.at[lanes(i)], sems.at[2, i % 3]),
            pltpu.make_async_copy(ys.at[i % 2], y_hbm.at[lanes(i)], sems.at[3, i % 2]),
        )

    def begin(copies):
        for copy in copies:
            copy.start()

    def finish(copies):
        for copy in copies:
            copy.wait()

    begin(read(0))
    finish(read(0))

    def block(i, carry):
        more, behind = i + 1 < blocks, i > 0
        pl.when(more)(lambda: begin(read(i + 1)))

        def lane(l, carry):
            @pl.when(l == group // 2)
            def _():  # the read has had half the block's arithmetic to end in; now the other direction
                pl.when(more)(lambda: finish(read(i + 1)))
                pl.when(behind)(lambda: begin(write(i - 1)))

            _lane(l, smalls.at[i % 2], states.at[i % 3], ys.at[i % 2], a_spread, b_spread, c_spread, heads=heads)
            return carry

        jax.lax.fori_loop(0, group, lane, 0)
        pl.when(behind)(lambda: finish(write(i - 1)))
        return carry

    jax.lax.fori_loop(0, blocks, block, 0)
    begin(write(blocks - 1))
    finish(write(blocks - 1))


def state_pass(state, decay, fed, b, c, *, interpret=False):
    """``Mamba2Mixer._state_plain`` as one kernel: ``state`` ``(n, state_dim,
    inner)`` as stored, ``decay`` ``(n, heads)``, ``fed`` (``dt x``) ``(n,
    inner)``, ``b`` and ``c`` ``(n, state_dim)``. Returns the new state,
    rewritten in place, the float32 readout ``(n, inner)`` of the unrounded
    state and, per lane, the states the kernel rewrote: one."""
    group = lane_group(state.shape[0], *state.shape[1:], state.dtype.itemsize)
    return _state_pass(state, decay, fed, b, c, group=group, interpret=interpret)


# a function of its own under ``jit``: a decoder's Mamba layers are alike, so a process traces the
# kernel once and a program lowers it once, whatever the number of layers (the state is donated for
# a caller outside any program; inside one the call is inlined and the aliasing below decides)
@functools.partial(jax.jit, static_argnames=("group", "interpret"), donate_argnums=(0,))
def _state_pass(state, decay, fed, b, c, *, group, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, state_dim, inner = state.shape
    heads = decay.shape[1]
    decay = jnp.pad(decay.astype(F32), ((0, 0), (0, -heads % _LANES)))  # whole rows of 128
    small = jnp.concatenate([decay] + [x.astype(F32) for x in (fed, b, c)], axis=1).reshape(n, -1, _LANES)
    y_rows = (inner // _LANES, _LANES)
    spread = lambda numbers: pltpu.VMEM((numbers, _LANES), F32)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    new, y = pl.pallas_call(
        functools.partial(_kernel, group=group, heads=heads),
        in_specs=[anywhere, anywhere],
        out_specs=[anywhere, anywhere],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype), jax.ShapeDtypeStruct((n,) + y_rows, F32)],
        scratch_shapes=[
            pltpu.VMEM((3, group, state_dim, inner), state.dtype),
            pltpu.VMEM((2, group) + small.shape[1:], F32),
            pltpu.VMEM((2, group) + y_rows, F32),
            spread(decay.shape[1]),
            spread(state_dim),
            spread(state_dim),
            pltpu.SemaphoreType.DMA((4, 3)),
        ],
        input_output_aliases={1: 0},
        # what XLA's scheduler takes the call's length from: without it little is prefetched across the call
        cost_estimate=pl.CostEstimate(
            flops=5 * state.size, transcendentals=0, bytes_accessed=2 * state.nbytes + small.nbytes + 4 * n * inner
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=3 * group * state_dim * inner * state.dtype.itemsize + _VMEM_BESIDE
        ),
        name=KERNEL_NAME,
        interpret=interpret,
    )(small, state)
    return new, y.reshape(n, inner), jnp.ones((n,), jnp.int32)
