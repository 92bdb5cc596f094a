"""The pass over a KDA layer's matrix states on the chip, outside the
benchmark: what one layer's pass costs as XLA's plain form, as a bare copy
of the states through a kernel (read once, written once, nothing computed,
one direction at a time: ``bare_copy_phased``) and as the kernel of
``net/kdastate.py``; the bytes a second each moves against the device's peak;
and how far each lies from a float64 evaluation of the same steps.

    python scripts/kda_state_check.py [--lanes 512] [--heads 32] [--head-dim 128]
        [--dtype bfloat16] [--repeats 32] [--steps 4] [--sweep 4,16] [--cpu --tiny]

Seeded states and operands at the benchmark's sizes: decays of a (head, key
channel) in (0.2, 1), ``k`` and ``q`` L2-normalised a head (``q`` times
``head_dim ^ -1/2``), ``v`` of unit scale, ``beta`` in (0, 1). The state is
stored ``(lanes, key, heads, value)`` as the library stores it. Each form runs
``--repeats`` passes inside one jitted loop whose carry is the (donated)
state and the sum of the readouts. One JSON line an entry: the form, for a
kernel the lanes of one block (``--sweep <lanes>,...``, the module's own
first), microseconds a layer, bytes a second (a state read once and written
once) against the device's peak, and the relative RMS difference of the state
and of the last readout from the float64 evaluation of ``--steps`` steps on
the first eight lanes. ``--cpu --tiny`` rehearses (kernels in interpret mode,
times that mean nothing).
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
    parser.add_argument("--lanes", type=int, default=512)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--repeats", type=int, default=32)
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--sweep", default="")
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.tiny:
        args.lanes, args.heads, args.repeats, args.steps = 4, 16, 1, 2

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from evotorch_tpu.neuroevolution.net import kdastate, ssmstate
    from evotorch_tpu.neuroevolution.net.decoder import KimiDeltaAttention
    from evotorch_tpu.resilience import device_record, setup_backend

    setup_backend(force_cpu=args.cpu)
    n, heads, dim = args.lanes, args.heads, args.head_dim
    dtype, f32 = jnp.dtype(args.dtype), jnp.float32
    state_bytes = dim * heads * dim * dtype.itemsize
    keys = jax.random.split(jax.random.key(args.seed), 6)
    start = jax.random.normal(keys[0], (n, dim, heads, dim)).astype(dtype)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    decay = jax.random.uniform(keys[1], (n, heads, dim), f32, 0.2, 1.0)
    k = unit(jax.random.normal(keys[2], (n, heads, dim)))
    q = unit(jax.random.normal(keys[3], (n, heads, dim))) * dim**-0.5
    v = jax.random.normal(keys[4], (n, heads, dim))
    beta = jax.random.uniform(keys[5], (n, heads), f32)
    operands = (decay, k, v, beta, q)
    readout = (n, heads, dim)

    def plain(state, *small):
        return KimiDeltaAttention._state_plain(state, *small)

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
                scratch_shapes=[pltpu.VMEM((group,) + state.shape[1:], state.dtype), pltpu.SemaphoreType.DMA((2,))],
                input_output_aliases={0: 0},
                compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
                name="kda_state_phased_copy",
                interpret=args.cpu,
            )(state)
            return new, jnp.zeros(readout, f32)

        return form

    def kernel(state, *small):  # reads the module's sizes when it is traced
        return kdastate.state_pass(state, *small, interpret=args.cpu)[:2]

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
        carry = jax.block_until_ready(run(first, jnp.zeros(readout, f32), *operands))
        times = []
        for _ in range(3):
            began = time.perf_counter()
            carry = jax.block_until_ready(run(*carry, *operands))
            times.append(time.perf_counter() - began)
        return 1e6 * sorted(times)[1] / args.repeats

    # the same steps in float64, on the first lanes: no rounding of the state between steps
    few = min(CHECKED_LANES, n)
    exact = np.asarray(start[:few], np.float64)  # (lanes, key, heads, value)
    a64, k64, v64, b64, q64 = (np.asarray(x[:few], np.float64) for x in operands)
    turned = lambda x: np.swapaxes(x, 1, 2)[..., None]  # (lanes, heads, key) -> (lanes, key, heads, 1)
    for _ in range(args.steps):
        exact = exact * turned(a64)
        read = np.sum(exact * turned(k64), axis=1)
        exact = exact + turned(k64) * (b64[:, :, None] * (v64 - read))[:, None]
    exact_y = np.sum(exact * turned(q64), axis=1)

    def rel(got, want):
        got = np.asarray(got, np.float64)
        return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))

    def stepped(form):
        """``--steps`` passes in a loop: its carry is the stored state, so every step rounds."""

        def steps(state, *small):
            body = lambda _, carry: form(carry[0], *small)
            return jax.lax.fori_loop(0, args.steps, body, (state, jnp.zeros(readout, f32)))

        return jax.jit(steps, donate_argnums=(0,))

    peak = None
    if not args.cpu:
        from benchmark.harness import device

        peak = device.peaks(device_record()["kind"])["hbm_bytes_per_s"]

    def measure(name, form, *, accuracy=True, **sizes):
        line = {
            "form": name,
            **sizes,
            "device": device_record(),
            "sizes": {"lanes": n, "heads": heads, "head_dim": dim, "dtype": dtype.name},
        }
        if accuracy:
            state, y = stepped(form)(jnp.copy(start), *operands)  # every run donates its state
            line["state_rel_rms_diff_from_float64"] = rel(state[:few], exact)
            line["readout_rel_rms_diff_from_float64"] = rel(y[:few], exact_y)
        line["us_a_layer"] = us(form, jnp.copy(start))
        rate = 2 * n * state_bytes / line["us_a_layer"] * 1e6
        line["gb_per_s"] = rate / 1e9
        if peak:
            line["share_of_peak_bytes_per_s"] = rate / peak
        print(json.dumps(line), flush=True)

    own = ssmstate.lane_group(n, dim, heads * dim, dtype.itemsize)
    swept = [own] + [int(e) for e in args.sweep.split(",") if e]
    for group in swept:
        measure("bare_copy_phased", phased_copy(group), accuracy=False, lane_group=group)
    measure("plain", plain)
    for group in swept:
        ssmstate._BLOCK_BYTES = group * state_bytes  # set on the module before anything of this entry is traced
        assert ssmstate.lane_group(n, dim, heads * dim, dtype.itemsize) == group, (group, n)
        measure("kernel", kernel, lane_group=group)  # traced anew an entry: ``looped`` makes a new function


if __name__ == "__main__":
    main()
