"""The pass over the latent cache on the chip, outside the benchmark: what one
layer's pass costs as the kernel of ``net/latent.py`` and as the plain
``einsum`` form (``LatentAttention._cache_plain``), how far each lies from a
float32 evaluation of the same rows, and what the kernel fetches for what the
lanes can read.

    python scripts/latent_cache_check.py [--lanes 512] [--heads 20]
        [--kv-rank 512] [--rope 64] [--slots 512] [--repeats 64]
        [--sweep 4M:128,2M:32,4M:256] [--cpu --tiny]

Seeded queries and caches at the benchmark's sizes, bfloat16. The cases are
points of an episode: every lane at ``t`` = 0, 127, 128, 300, 511 (``slot =
t``: the ring has not wrapped), a ragged mix (``slot`` 511, ``t`` uniform
over 0..511 within every group of lanes, as if lanes had restarted), and the
ring wrapped (``slot`` 200 after 712 steps, every slot readable). Each form
runs ``--repeats`` times inside one jitted loop whose carry feeds the next
pass's queries. One JSON line a sweep entry (``<bytes of a block of
compressed rows>:<positions of a block>``, the module's own first): per case
microseconds a layer of each form, the largest absolute difference between
them, each form's relative RMS difference from the float32 evaluation, bytes
fetched over bytes readable, and the kernel's bytes a second against the
device's peak. ``--cpu --tiny`` rehearses (kernel in interpret mode, times
that mean nothing).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

UNITS = {"K": 1 << 10, "M": 1 << 20}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=512)
    parser.add_argument("--heads", type=int, default=20)
    parser.add_argument("--kv-rank", type=int, default=512)
    parser.add_argument("--rope", type=int, default=64)
    parser.add_argument("--slots", type=int, default=512)
    parser.add_argument("--repeats", type=int, default=64)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--sweep", default="")
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.tiny:
        args.lanes, args.heads, args.kv_rank, args.slots, args.repeats = 16, 4, 128, 512, 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    from evotorch_tpu.neuroevolution.net import latent
    from evotorch_tpu.neuroevolution.net.decoder import LatentAttention
    from evotorch_tpu.resilience import device_record, setup_backend

    setup_backend(force_cpu=args.cpu)
    n, heads, kv_rank, rope, slots = args.lanes, args.heads, args.kv_rank, args.rope, args.slots
    dtype = jnp.bfloat16
    layer = LatentAttention(
        2048, heads, q_rank=768, kv_rank=kv_rank, nope_dim=192, rope_dim=rope, v_dim=256, slots=slots, rope_theta=1e6
    )
    scale = (layer.nope + layer.rope) ** -0.5
    keys = jax.random.split(jax.random.key(args.seed), 5)
    # queries and rows as the norms leave them: unit RMS rows, scores of a few units
    q_lat = (jax.random.normal(keys[0], (n, heads, kv_rank)) * 0.5).astype(dtype)
    q_r = jax.random.normal(keys[1], (n, heads, rope)).astype(dtype)
    c = jax.random.normal(keys[2], (n, slots, kv_rank)).astype(dtype)
    kr = jax.random.normal(keys[3], (n, slots, rope)).astype(dtype)
    everyone = lambda t: jnp.full((n,), t, jnp.int32)
    ragged = jnp.asarray(np.random.default_rng(args.seed).permutation(n) * slots // n, jnp.int32)
    cases = {f"t{t}": (everyone(t), t) for t in (0, 127, 128, 300, slots - 1)}
    cases["ragged"] = (ragged, slots - 1)
    cases["wrapped"] = (everyone(slots + 200), 200)

    def plain(q_lat, t, slot):
        return layer._cache_plain(q_lat, q_r, c, kr, t, slot)

    def exact(t, slot):
        f = lambda x: x.astype(jnp.float32)
        age = jnp.mod(slot - jnp.arange(slots), slots)
        with jax.default_matmul_precision("highest"):
            s = (jnp.einsum("nhr,nsr->nhs", f(q_lat), f(c)) + jnp.einsum("nhd,nsd->nhs", f(q_r), f(kr))) * scale
            s = jnp.where((age[None, :] <= t[:, None])[:, None, :], s, -jnp.inf)
            return jnp.einsum("nhs,nsr->nhr", jax.nn.softmax(s, axis=-1), f(c))

    def looped(form):
        def loop(q, t, slot):
            def body(_, carry):
                q, total = carry
                out = form(q, t, slot)[0]
                return (q + 1e-3 * out).astype(dtype), total + jnp.sum(out)

            return jax.lax.fori_loop(0, args.repeats, body, (q, jnp.zeros(())))

        return jax.jit(loop)

    def us(run, *inputs):
        jax.block_until_ready(run(*inputs))
        times = []
        for _ in range(3):
            start = time.perf_counter()
            jax.block_until_ready(run(*inputs))
            times.append(time.perf_counter() - start)
        return 1e6 * sorted(times)[1] / args.repeats

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    peak = None
    if not args.cpu:
        from benchmark.harness import device

        peak = device.peaks(device_record()["kind"])["hbm_bytes_per_s"]
    itemsize = jnp.dtype(dtype).itemsize
    row_bytes = (kv_rank + max(rope, 128)) * itemsize  # the RoPE key lies padded to a register's width
    loop_plain, once_plain, once_exact = looped(plain), jax.jit(plain), jax.jit(exact)
    plain_us = {name: us(loop_plain, q_lat, *case) for name, case in cases.items()}

    def measure(entry):
        """One line for one pair of sizes, set on the module before anything of it is traced."""
        block_bytes, block = entry.split(":")
        latent._BLOCK_BYTES = int(block_bytes[:-1]) * UNITS[block_bytes[-1]] if block_bytes[-1] in UNITS else int(block_bytes)
        latent.BLOCK = int(block)
        line = {
            "device": device_record(),
            "sizes": {"lanes": n, "heads": heads, "kv_rank": kv_rank, "rope": rope, "slots": slots, "dtype": "bfloat16"},
            "block_positions": latent.BLOCK,
            "lane_group": latent.lane_group(n, kv_rank, itemsize),
            "cases": {},
        }

        def kernel(q_lat, t, slot):  # a new function an entry: jit's cache is keyed on it, not on the module's sizes
            return latent.attend(q_lat, q_r, c, kr, t, slot, scale=scale, interpret=args.cpu)

        loop_kernel, once_kernel = looped(kernel), jax.jit(kernel)
        for name, (t, slot) in cases.items():
            got, fetched = once_kernel(q_lat, t, slot)
            want, close = once_plain(q_lat, t, slot)[0], once_exact(t, slot)
            readable = int(jnp.sum(jnp.minimum(t + 1, slots)))
            kernel_us = us(loop_kernel, q_lat, t, slot)
            line["cases"][name] = {
                "kernel_us": kernel_us,
                "plain_us": plain_us[name],
                "max_abs_diff": float(jnp.max(jnp.abs(got - want))),
                "kernel_rel_diff_from_float32": rel(got, close),
                "plain_rel_diff_from_float32": rel(want, close),
                "positions_readable": readable,
                "positions_fetched": int(jnp.sum(fetched)),
                "bytes_fetched_over_readable": int(jnp.sum(fetched)) / readable,
            }
            if peak:
                rate = int(jnp.sum(fetched)) * row_bytes / kernel_us * 1e6
                line["cases"][name]["kernel_gb_per_s"] = rate / 1e9
                line["cases"][name]["kernel_share_of_peak_bytes_per_s"] = rate / peak
        print(json.dumps(line), flush=True)

    own = f"{latent._BLOCK_BYTES}:{latent.BLOCK}"
    for entry in [own] + [e for e in args.sweep.split(",") if e]:
        measure(entry)


if __name__ == "__main__":
    main()
