"""The held experts' grouped product on the chip, outside the benchmark: what
one sparse layer's product costs as the kernel of ``net/grouped.py`` and as
the plain ``ragged_dot`` form, what share of the memory's rate the kernel
reaches, and how far each lies from a float32 evaluation of the same pairs.

    python scripts/grouped_experts_check.py [--lanes 512] [--dim 2048]
        [--width 1024] [--held 16] [--experts 128] [--top-k 8] [--rank 4]
        [--repeats 64] [--gmm] [--cpu --tiny]

Seeded weights, factors and routes at the benchmark's sizes (a uniform router
over ``--experts``, so about ``lanes * top_k * held / experts`` pairs hit the
held ones); each form runs ``--repeats`` times inside one jitted loop whose
carry feeds the next product's input. One JSON line: milliseconds a product of
each form, the bytes the kernel's DMAs move and their rate against the
device's peak, the pairs, the tiles visited, and each form's relative RMS
difference from the float32 evaluation. ``--gmm`` adds the three trunk
products alone (no rank-k companions, no gather, no sum) through
``jax.experimental.pallas.ops.tpu.megablox.gmm`` over the sorted rows.
``--cpu --tiny`` rehearses (kernel in interpret mode, times that mean
nothing).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=512)
    parser.add_argument("--dim", type=int, default=2048)
    parser.add_argument("--width", type=int, default=1024)
    parser.add_argument("--held", type=int, default=16)
    parser.add_argument("--experts", type=int, default=128)
    parser.add_argument("--top-k", type=int, default=8)
    parser.add_argument("--rank", type=int, default=4)
    parser.add_argument("--repeats", type=int, default=64)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--gmm", action="store_true")
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.tiny:
        args.lanes, args.dim, args.width, args.held, args.experts, args.top_k = 200, 256, 128, 4, 8, 2
        args.repeats = 2

    import jax
    import jax.numpy as jnp

    from evotorch_tpu.neuroevolution.net import grouped
    from evotorch_tpu.neuroevolution.net.decoder import SparseExperts
    from evotorch_tpu.resilience import device_record, setup_backend
    from evotorch_tpu.tools.lowrank import DeltaFactor

    setup_backend(force_cpu=args.cpu)
    n, dim, width, held, k = args.lanes, args.dim, args.width, args.held, args.rank
    dtype = jnp.bfloat16
    layer = SparseExperts(dim, width, args.experts, args.top_k, experts_held=range(held))
    keys = jax.random.split(jax.random.key(args.seed), 12)
    shapes = {"gate": (dim, width), "up": (dim, width), "down": (width, dim)}
    center, factors = {}, {}
    for at, (name, (fan_in, fan_out)) in enumerate(shapes.items()):
        std = fan_in**-0.5
        center[name] = (std * jax.random.normal(keys[3 * at], (held, fan_in, fan_out))).astype(dtype)
        factors[name] = DeltaFactor(
            a=jax.random.normal(keys[3 * at + 1], (held, fan_in, k)),
            b=0.1 * std * jax.random.normal(keys[3 * at + 2], (held, fan_out, k)),
        )
    z = jax.random.normal(keys[9], (n, k)).astype(dtype)
    y = jax.random.normal(keys[10], (n, dim)).astype(dtype)
    scores = jax.random.uniform(keys[11], (n, args.experts))
    weights, chosen = jax.lax.top_k(scores, args.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def kernel(y):
        return grouped.held_experts(center, factors, z, y, chosen, weights, interpret=args.cpu)

    def plain(y):
        return layer._experts_plain(center, factors, z, y, chosen, weights)

    def exact(y):
        f = lambda t: jnp.asarray(t, jnp.float32)
        with jax.default_matmul_precision("highest"):
            out = jnp.zeros(y.shape, jnp.float32)
            for e in range(held):
                def m(name, x):
                    thin = (x @ f(factors[name].a[e])) * f(z)
                    return x @ f(center[name][e]) + thin @ f(factors[name].b[e]).T
                hidden = jax.nn.silu(m("gate", f(y))) * m("up", f(y))
                out = out + m("down", hidden) * jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)[:, None]
        return out

    def looped(form):
        def body(_, carry):
            y, total = carry
            out = form(y)[0]
            return (y + 1e-3 * out).astype(dtype), total + jnp.sum(out.astype(jnp.float32))

        def loop(y):
            return jax.lax.fori_loop(0, args.repeats, body, (y, jnp.zeros(())))

        return jax.jit(loop)

    def ms(run, *inputs):
        jax.block_until_ready(run(*inputs))
        times = []
        for _ in range(3):
            start = time.perf_counter()
            jax.block_until_ready(run(*inputs))
            times.append(time.perf_counter() - start)
        return 1e3 * sorted(times)[1] / args.repeats

    want = jax.jit(exact)(y)

    def distance(got):
        return float(jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))

    got_kernel, sizes, tiles = jax.jit(kernel)(y)
    got_plain = jax.jit(plain)(y)[0]
    itemsize = jnp.dtype(dtype).itemsize
    lanes = grouped._padded(n)
    moved = (
        3 * held * dim * width * itemsize  # the experts' matrices, once
        + 3 * held * (dim + width) * k * itemsize  # their factors
        + lanes * (dim + 128) * itemsize  # y and the padded z
        + 3 * held * lanes * 4  # ranks (twice) and router weights
        + lanes * dim * 4  # the float32 sums
    )
    line = {
        "device": device_record(),
        "sizes": {"lanes": n, "dim": dim, "width": width, "held": held, "rank": k, "dtype": "bfloat16"},
        "pairs_held": int(jnp.sum(sizes)),
        "pairs_fullest": int(jnp.max(sizes)),
        "row_tiles": int(tiles),
        "tile_rows": grouped.ROW_TILE,
        "kernel_ms": ms(looped(kernel), y),
        "plain_ms": ms(looped(plain), y),
        "kernel_bytes": moved,
        "kernel_rel_diff_from_float32": distance(got_kernel),
        "plain_rel_diff_from_float32": distance(got_plain),
    }
    if not args.cpu:
        from benchmark.harness import device

        peak = device.peaks(line["device"]["kind"])["hbm_bytes_per_s"]
        line["kernel_gb_per_s"] = moved / line["kernel_ms"] / 1e6
        line["kernel_share_of_peak_bytes_per_s"] = line["kernel_gb_per_s"] * 1e9 / peak
    if args.gmm:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        rows = -(-n * min(args.top_k, held) // 128) * 128

        def three(x):
            tiling = (128, min(dim, 2048), min(width, 512))
            g = gmm(x, center["gate"], sizes, dtype, tiling, interpret=args.cpu)
            u = gmm(x, center["up"], sizes, dtype, tiling, interpret=args.cpu)
            hidden = jax.nn.silu(g) * u
            tiling = (128, min(width, 2048), min(dim, 512))
            return (gmm(hidden, center["down"], sizes, dtype, tiling, interpret=args.cpu),)

        x_rows = jnp.zeros((rows, dim), dtype).at[:n].set(y)

        def three_ragged(x):
            g = jax.lax.ragged_dot(x, center["gate"], sizes)
            u = jax.lax.ragged_dot(x, center["up"], sizes)
            return (jax.lax.ragged_dot(jax.nn.silu(g) * u, center["down"], sizes),)

        line["gmm_three_products_ms"] = ms(looped(three), x_rows)
        line["ragged_dot_three_products_ms"] = ms(looped(three_ragged), x_rows)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
