"""The pass over a Mamba-2 layer's matrix states on the chip, outside the
benchmark: what one layer's pass costs as XLA's plain form, as the plain form
WITHOUT the readout, as a bare copy of the states through a kernel (read once,
written once, nothing computed; ``bare_copy`` with the read of one block and
the write of another in flight together, as a ``BlockSpec`` pipeline has
them, ``bare_copy_phased`` with one direction at a time) and as the kernel of
``net/ssmstate.py``; the
bytes a second each moves against the device's peak; and how far each lies
from a float64 evaluation of the same steps.

    python scripts/ssm_state_check.py [--lanes 256] [--heads 64] [--head-dim 64]
        [--state 128] [--dtype bfloat16] [--repeats 64] [--steps 4]
        [--sweep 4,16] [--no-kernel] [--cpu --tiny]

Seeded states and operands at the benchmark's sizes: decays of a head in
(0.2, 1), ``dt x``, ``B`` and ``C`` of unit scale. Two stored layouts: ``rows``,
``(lanes, heads, head_dim, state_dim)`` as ``mamba_ssm`` has it and the library
had it before PR 35 (the readout reduces over a register's lanes), and
``turned``, ``(lanes, state_dim, heads x head_dim)`` as the library stores it now
(the readout adds registers). Each form runs ``--repeats`` passes inside one
jitted loop whose carry is the (donated) state and the sum of the readouts.
One JSON line an entry: the form, its layout, for a kernel the lanes of one
block (``--sweep <lanes>,...``, the module's own first), microseconds a layer, bytes a second (a state read once and written
once) against the device's peak, and the relative RMS difference of the state
and of the last readout from the float64 evaluation of ``--steps`` steps on
the first eight lanes. ``--no-kernel`` leaves the library's kernel out (the
first chip call of PR 35). ``--cpu --tiny`` rehearses (kernels in interpret
mode, times that mean nothing).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CHECKED_LANES = 8


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=256)
    parser.add_argument("--heads", type=int, default=64)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--state", type=int, default=128)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--repeats", type=int, default=64)
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--sweep", default="")
    parser.add_argument("--no-kernel", action="store_true")
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.tiny:
        args.lanes, args.heads, args.head_dim, args.state, args.repeats = 8, 8, 64, 128, 2

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from evotorch_tpu.neuroevolution.net import ssmstate
    from evotorch_tpu.neuroevolution.net.decoder import Mamba2Mixer
    from evotorch_tpu.resilience import device_record, setup_backend

    setup_backend(force_cpu=args.cpu)
    n, heads, head_dim, state_dim = args.lanes, args.heads, args.head_dim, args.state
    inner, dtype, f32 = heads * head_dim, jnp.dtype(args.dtype), jnp.float32
    state_bytes = state_dim * inner * dtype.itemsize
    keys = jax.random.split(jax.random.key(args.seed), 5)
    start = jax.random.normal(keys[0], (n, state_dim, inner)).astype(dtype)  # turned
    decay = jax.random.uniform(keys[1], (n, heads), f32, 0.2, 1.0)
    fed = jax.random.normal(keys[2], (n, inner))
    b, c = jax.random.normal(keys[3], (n, state_dim)), jax.random.normal(keys[4], (n, state_dim))
    operands = (decay, fed, b, c)

    to_rows = lambda turned: jnp.swapaxes(turned, 1, 2).reshape(n, heads, head_dim, state_dim)
    as_turned = {"turned": lambda s: s, "rows": lambda s: jnp.swapaxes(s.reshape(s.shape[0], inner, state_dim), 1, 2)}

    def rows_plain(state, decay, fed, b, c, readout=True):
        """The library's plain form before PR 35: ``state_dim`` last."""
        new = state.astype(f32) * decay[:, :, None, None] + fed.reshape(n, heads, head_dim, 1) * b[:, None, None, :]
        y = jnp.sum(new * c[:, None, None, :], axis=-1).reshape(n, inner) if readout else jnp.zeros((n, inner), f32)
        return new.astype(state.dtype), y

    def turned_plain(state, decay, fed, b, c, readout=True):
        if readout:
            return Mamba2Mixer._state_plain(state, decay, fed, b, c)[:2]
        new = state.astype(f32) * jnp.repeat(decay, head_dim, axis=1)[:, None, :] + b[:, :, None] * fed[:, None, :]
        return new.astype(state.dtype), jnp.zeros((n, inner), f32)

    def copy_kernel(group):
        whole = pl.BlockSpec((group, state_dim, inner), lambda g: (g, 0, 0))

        def body(state_ref, out_ref):
            out_ref[...] = state_ref[...]

        def form(state, *_):
            new = pl.pallas_call(
                body,
                grid=(n // group,),
                in_specs=[whole],
                out_specs=whole,
                out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
                input_output_aliases={0: 0},
                compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=100 << 20),
                name="ssm_state_bare_copy",
                interpret=args.cpu,
            )(state)
            return new, jnp.zeros((n, inner), f32)

        return form

    def phased_copy(group):
        """Blocks of ``group`` lanes read into VMEM and written back, the read
        and the write never in flight together."""
        blocks = n // group

        def body(state_hbm, out_hbm, held, sems):
            def block(i, carry):
                lanes = pl.ds(i * group, group)
                for copy in (
                    pltpu.make_async_copy(state_hbm.at[lanes], held, sems.at[0]),
                    pltpu.make_async_copy(held, out_hbm.at[lanes], sems.at[1]),
                ):
                    copy.start()
                    copy.wait()
                return carry

            jax.lax.fori_loop(0, blocks, block, 0)

        def form(state, *_):
            anywhere = pl.BlockSpec(memory_space=pl.ANY)
            new = pl.pallas_call(
                body,
                in_specs=[anywhere],
                out_specs=anywhere,
                out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
                scratch_shapes=[pltpu.VMEM((group, state_dim, inner), state.dtype), pltpu.SemaphoreType.DMA((2,))],
                input_output_aliases={0: 0},
                compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
                name="ssm_state_phased_copy",
                interpret=args.cpu,
            )(state)
            return new, jnp.zeros((n, inner), f32)

        return form

    def kernel(state, *small):  # reads the module's sizes when it is traced
        return ssmstate.state_pass(state, *small, interpret=args.cpu)[:2]

    def looped(form):
        def loop(state, total, *small):
            def body(_, carry):
                state, total = carry
                state, y = form(state, *small)
                return state, total + y

            return jax.lax.fori_loop(0, args.repeats, body, (state, total))

        return jax.jit(loop, donate_argnums=(0, 1))

    def us(form, first):
        run = looped(form)
        carry = jax.block_until_ready(run(first, jnp.zeros((n, inner), f32), *operands))
        times = []
        for _ in range(3):
            began = time.perf_counter()
            carry = jax.block_until_ready(run(*carry, *operands))
            times.append(time.perf_counter() - began)
        return 1e6 * sorted(times)[1] / args.repeats

    # the same steps in float64, on the first lanes: no rounding of the state between steps
    few = min(CHECKED_LANES, n)
    exact = np.asarray(start[:few], np.float64)
    wide = [np.asarray(x[:few], np.float64) for x in operands]
    for _ in range(args.steps):
        exact = exact * np.repeat(wide[0], head_dim, axis=1)[:, None, :] + wide[2][:, :, None] * wide[1][:, None, :]
    exact_y = np.sum(exact * wide[3][:, :, None], axis=1)

    def rel(got, want):
        got = np.asarray(got, np.float64)
        return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))

    def stepped(form):
        """``--steps`` passes in a loop: its carry is the stored state, so every step rounds
        (unrolled, XLA keeps the plain form's state in float32 from step to step)."""

        def steps(state, *small):
            body = lambda _, carry: form(carry[0], *small)
            return jax.lax.fori_loop(0, args.steps, body, (state, jnp.zeros((n, inner), f32)))

        return jax.jit(steps, donate_argnums=(0,))

    peak = None
    if not args.cpu:
        from benchmark.harness import device

        peak = device.peaks(device_record()["kind"])["hbm_bytes_per_s"]

    def measure(name, layout, form, *, accuracy=True, **sizes):
        fresh = lambda: jnp.copy(start) if layout == "turned" else to_rows(start)  # every run donates its state
        line = {
            "form": name,
            "layout": layout,
            **sizes,
            "device": device_record(),
            "sizes": {"lanes": n, "heads": heads, "head_dim": head_dim, "state": state_dim, "dtype": dtype.name},
        }
        if accuracy:
            state, y = stepped(form)(fresh(), *operands)
            line["state_rel_rms_diff_from_float64"] = rel(as_turned[layout](state[:few]), exact)
            if name != "plain_without_readout":
                line["readout_rel_rms_diff_from_float64"] = rel(y[:few], exact_y)
        line["us_a_layer"] = us(form, fresh())
        rate = 2 * n * state_bytes / line["us_a_layer"] * 1e6
        line["gb_per_s"] = rate / 1e9
        if peak:
            line["share_of_peak_bytes_per_s"] = rate / peak
        print(json.dumps(line), flush=True)

    measure("plain", "rows", rows_plain)
    measure("plain_without_readout", "rows", lambda *a: rows_plain(*a, readout=False))
    measure("plain", "turned", turned_plain)
    measure("plain_without_readout", "turned", lambda *a: turned_plain(*a, readout=False))
    own = ssmstate.lane_group(n, state_dim, inner, dtype.itemsize)
    swept = [own] + [int(e) for e in args.sweep.split(",") if e]
    for group in swept:
        measure("bare_copy", "turned", copy_kernel(group), accuracy=False, lane_group=group)
        measure("bare_copy_phased", "turned", phased_copy(group), accuracy=False, lane_group=group)
    if args.no_kernel:
        return
    for group in swept:
        ssmstate._BLOCK_BYTES = group * state_bytes  # set on the module before anything of this entry is traced
        assert ssmstate.lane_group(n, state_dim, inner, dtype.itemsize) == group, (group, n)
        measure("kernel", "turned", kernel, lane_group=group)  # traced anew an entry: ``looped`` makes a new function

if __name__ == "__main__":
    main()
