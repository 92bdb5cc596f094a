"""The pass over a KDA layer's matrix states as one in-place TPU kernel.

``decoder.py:KimiDeltaAttention`` keeps, per lane, one matrix state a head,
``(key, value)``, and rewrites ALL of it every step with the gated delta rule:

    S' = Diag(a) S,    S_t = S' + k (beta (v - S'^T k))^T,    o = S_t^T q

``a``, ``k`` and ``q`` one number a (head, key channel), ``v`` one a (head,
value channel), ``beta`` one a head. XLA's plain form
(``KimiDeltaAttention._state_plain``) is two fusions a layer on the v5e: one
reads the state for ``S'^T k``, the other reads it AGAIN, writes it back and
reads out ``S_t^T q`` (PERF.md, PR 42). Here one kernel a layer brings a
lane's state into VMEM once, updates it there and writes it back to where it
lay, through ``net/ssmstate.py``'s walk (blocks of lanes through three slots,
the read of one block and the write of another never in flight together, the
state operand aliased to the state result).

**The stored layout** is ``(key, heads, value)`` a lane, in the TPU's tiles
of 16 heads x 128 values (bfloat16): a register holds one key channel of 16
heads, the value along its lanes. ``v``, ``S'^T k`` and the readout lie along
the lanes as they are; the factors of a (head, key) pair, ``a``, ``k`` and
``q``, lie along the SUBLANES and have to be spread along the lanes. XLA turns
them first, ``(key, head)`` in rows of 128 (four key channels of 32 heads a
row), and the kernel spreads a row at a time as ``ssmstate._spread`` does: a
transpose on the cross-lane unit, which a broadcast from one lane of a turned
block costs twice over (PERF.md, PR 43).

**The order of the work**, a lane at a time: the first sweep over the
VMEM-resident state spreads a row's ``a``, ``k`` and ``q``, decays the stored
state in float32 and keeps ``S'`` in VMEM (and the spread ``k``, for the
second sweep), and reduces ``S'^T k`` and ``S'^T q`` over the whole key axis;
then ``u = beta (v - S'^T k)`` and the readout ``S_t^T q = S'^T q + (k . q)
u`` (``k . q`` is XLA's, in float32); the second sweep writes ``S' + k u^T``,
rounded once to the stored dtype. Every product is a float32 multiply on the
vector unit; nothing runs on the matrix unit. Keeping ``S'`` rather than
decaying the state twice is 9% of the kernel's time (PERF.md, PR 43).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ssmstate
from .ssmstate import _LANES, F32, _spread

__all__ = ["KERNEL_NAME", "fits", "state_pass"]

#: the kernel's name in a compiled program's text
KERNEL_NAME = "kda_state_pass"
#: heads of one visit: the sublanes of a packed bfloat16 register (two float32 registers)
_HEADS = 16


def fits(n: int, heads: int, head_dim: int, dtype) -> bool:
    """Whether the kernel takes these sizes: a stored dtype it converts
    (bfloat16 or float32), whole registers along the value (``head_dim`` a
    multiple of 128), heads in whole visits of 16 that tile a row of 128
    (16, 32, 64 or 128 heads), a lane's state within a block, more than one
    lane (the one-lane dense form under ``vmap`` has one) and no mesh that
    spreads the lanes (the partitioner cannot split a kernel)."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if head_dim % _LANES or heads % _HEADS or _LANES % heads:
        return False
    if n < 2 or not ssmstate.lane_group(n, head_dim, heads * head_dim, jnp.dtype(dtype).itemsize):
        return False
    return all(size == 1 for size in jax.sharding.get_abstract_mesh().shape.values())


def _lane(l, small, state, y, decayed, k_spread, *, rows, unroll):
    """One lane's pass, in place, all in VMEM: ``state`` ``(group, key,
    heads, value)`` as stored, ``small`` ``(group, rows, 128)`` the lanes'
    operands in rows of 128 at the offsets ``rows`` names (``a``, ``k``,
    ``q``: ``(key, head)`` pairs; ``v``: ``(value block, head)``; ``beta``,
    ``kq``: a head's number), ``y`` ``(group, heads, value)`` takes the
    readouts; ``decayed`` ``(key x heads, value)`` and ``k_spread`` ``(key x
    heads, 128)`` hold ``S'`` and the spread ``k`` between the sweeps. A row
    of 128 (key, head) pairs at a time and, within it, ``_HEADS`` heads of
    one key channel and 128 values a visit: loops that Mosaic's lowering
    unrolls whole (``unroll``; its indices are constants there), so the
    kernel's jaxpr holds one row's visits and not the lane's. The interpreter
    runs them rolled: the same steps, and a compile of seconds where the
    unrolled loops take a minute."""
    from jax.experimental import pallas as pl

    keys, heads, width = state.shape[1:]
    groups, blocks, per_row = heads // _HEADS, width // _LANES, _LANES // heads
    sums = [(g, b) for g in range(groups) for b in range(blocks)]
    at = lambda kl, g: slice(kl * heads + g * _HEADS, kl * heads + (g + 1) * _HEADS)  # a visit's rows of a spread row
    pairs = lambda c, kl, g: pl.ds(c * _LANES + kl * heads + g * _HEADS, _HEADS)  # ... of the lane's (key, head) pairs
    stored = lambda c, kl, g, b: (l, c * per_row + kl, pl.ds(g * _HEADS, _HEADS), pl.ds(b * _LANES, _LANES))
    row = lambda name, c: _spread(small[l, pl.ds(rows[name] + c, 1), :])
    zero = jnp.zeros((_HEADS, _LANES), F32)

    def reduce(c, carry):
        """``S'`` kept, and ``S'^T k`` and ``S'^T q`` summed, over the key channels of row ``c``."""
        a, k, q = row("a", c), row("k", c), row("q", c)
        k_spread[pl.ds(c * _LANES, _LANES), :] = k
        r, o = dict(zip(sums, carry[0])), dict(zip(sums, carry[1]))
        for kl in range(per_row):
            for g, b in sums:
                s = state[stored(c, kl, g, b)].astype(F32) * a[at(kl, g)]
                decayed[pairs(c, kl, g), pl.ds(b * _LANES, _LANES)] = s
                r[g, b] = r[g, b] + s * k[at(kl, g)]
                o[g, b] = o[g, b] + s * q[at(kl, g)]
        return tuple(r.values()), tuple(o.values())

    start = tuple(zero for _ in sums)
    r, o = (dict(zip(sums, x)) for x in jax.lax.fori_loop(0, keys // per_row, reduce, (start, start), unroll=unroll))
    beta, kq = row("beta", 0), row("kq", 0)  # a head's number along the lanes
    u = {}
    for g, b in sums:
        heads_at = slice(g * _HEADS, (g + 1) * _HEADS)
        v = small[l, pl.ds(rows["v"] + b * heads + g * _HEADS, _HEADS), :]
        u[g, b] = beta[heads_at] * (v - r[g, b])
        y[l, pl.ds(g * _HEADS, _HEADS), pl.ds(b * _LANES, _LANES)] = o[g, b] + kq[heads_at] * u[g, b]

    def update(c, carry):
        """``S' + k u^T`` over the key channels of row ``c``, rounded once."""
        for kl in range(per_row):
            for g, b in sums:
                new = decayed[pairs(c, kl, g), pl.ds(b * _LANES, _LANES)] + k_spread[pairs(c, kl, g), :] * u[g, b]
                state[stored(c, kl, g, b)] = new.astype(state.dtype)
        return carry

    jax.lax.fori_loop(0, keys // per_row, update, 0, unroll=unroll)


def state_pass(held, decay, k, v, beta, q, *, interpret=False):
    """``KimiDeltaAttention._state_plain`` as one kernel: ``held`` ``(n, key,
    heads, value)`` as stored; ``decay`` (``a``), ``k`` and ``q`` ``(n,
    heads, key)``, ``v`` ``(n, heads, value)``, ``beta`` ``(n, heads)``.
    Returns the new state, rewritten in place, the float32 readout ``(n,
    heads, value)`` of the unrounded state and, per lane, the states the
    kernel rewrote: one."""
    n, keys, heads, width = held.shape
    group = ssmstate.lane_group(n, keys, heads * width, held.dtype.itemsize)
    return _state_pass(held, decay, k, v, beta, q, group=group, interpret=interpret)


# a function of its own under ``jit``: a decoder's KDA layers are alike, so a process traces the kernel
# once and a program lowers it once, whatever the number of layers (the state is donated for a caller
# outside any program; inside one the call is inlined and the aliasing decides)
@functools.partial(jax.jit, static_argnames=("group", "interpret"), donate_argnums=(0,))
def _state_pass(held, decay, k, v, beta, q, *, group, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, keys, heads, width = held.shape
    decay, k, v, beta, q = (x.astype(F32) for x in (decay, k, v, beta, q))
    turned = lambda x: jnp.swapaxes(x, 1, 2).reshape(n, -1, _LANES)  # (n, heads, key) -> rows of (key, head)
    by_head = lambda x: jnp.pad(x, ((0, 0), (0, -heads % _LANES)))[:, None, :]  # a head's number, a row of 128
    parts = {
        "a": turned(decay),
        "k": turned(k),
        "q": turned(q),
        "v": jnp.swapaxes(v.reshape(n, heads, width // _LANES, _LANES), 1, 2).reshape(n, -1, _LANES),
        "beta": by_head(beta),
        "kq": by_head(jnp.sum(k * q, axis=-1)),
    }
    rows, first = {}, 0
    for name, part in parts.items():
        rows[name], first = first, first + part.shape[1]
    small = jnp.concatenate(list(parts.values()), axis=1)
    kept = [pltpu.VMEM((keys * heads, width), F32), pltpu.VMEM((keys * heads, _LANES), F32)]
    new, y = ssmstate.call(
        functools.partial(_lane, rows=rows, unroll=not interpret),
        small,
        held,
        (heads, width),
        kept,
        group=group,
        flops=7 * held.size,
        beside=2 * group * (small.nbytes // n + 4 * heads * width) + 4 * keys * heads * (width + _LANES)
        + ssmstate._VMEM_BESIDE,
        name=KERNEL_NAME,
        interpret=interpret,
    )
    return new, y, jnp.ones((n,), jnp.int32)
