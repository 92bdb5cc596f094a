"""The dense antithetic sampler and its gradient on the chip, outside the
benchmark: what ``SymmetricSeparableGaussian._sample`` and the dense branch of
``_compute_gradients`` cost as the library has them and as the plain forms
they replaced, what each program holds beside its result, and how far the
forms lie from one another.

    python scripts/dense_sampler_check.py [--shapes 50000x12305,10000x98321]
        [--repeats 5] [--seed 0] [--cpu --tiny]

Sampler forms: ``library`` (one elementwise pass over the result's own index:
every normal from threefry at the counter ``jax.random.normal`` would have
given it), ``plain`` (draw ``(N/2, L)``, stack ``[mu + eps, mu - eps]``,
reshape: the library's form until PR 38 and still its form for another
generator or dtype), ``barrier`` (the plain form with the draw behind
``optimization_barrier``, so the interleave cannot reach into the generator).
Gradient forms: ``library`` (all rows, weighted by sign) and ``pairwise``
(``samples[0::2]``). The forms run in turn, ``--repeats`` times each; a time
is the host's clock around one call ended by ``block_until_ready`` (programs
of 5-100 ms). One JSON line a shape: the median and all readings in ms of each
form, ``temp_mb`` of each compiled program (``memory_analysis()`` of what ran,
so the chip's own buffer assignment), the largest distance between the
``library`` and the ``plain`` samples in units in the last place of the larger
of sample and centre, with the share of samples that differ at all, whether
the directions are the same normals bit for bit at ``mu = 0, sigma = 1``, and
the gradients' largest relative difference.
``--cpu --tiny`` rehearses (times that mean nothing).
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="50000x12305,10000x98321")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.tiny:
        args.shapes, args.repeats = "48x1231,20x9833", 2

    import jax
    import jax.numpy as jnp

    from evotorch_tpu.distributions import SymmetricSeparableGaussian as Gaussian
    from evotorch_tpu.resilience import device_record, setup_backend
    from evotorch_tpu.tools.ranking import rank

    setup_backend(force_cpu=args.cpu)

    def plain(key, mu, sigma, n, barrier=False):
        eps = jax.random.normal(key, (n // 2, mu.shape[-1]), dtype=mu.dtype)
        if barrier:
            eps = jax.lax.optimization_barrier(eps)
        eps = eps * sigma
        return jnp.stack([mu + eps, mu - eps], axis=1).reshape(n, mu.shape[-1])

    def pairwise(mu, sigma, samples, weights):
        noises = samples[0::2] - mu
        plus, minus = weights[0::2], weights[1::2]
        return ((plus - minus) / 2) @ noises, ((plus + minus) / 2) @ ((noises**2 - sigma**2) / sigma)

    def library_grad(mu, sigma, samples, weights):
        grads = Gaussian._compute_gradients({"mu": mu, "sigma": sigma}, samples, weights, "centered")
        return grads["mu"], grads["sigma"]

    def ulps(a, b, mu):
        # in units in the last place of the larger of sample and centre: where
        # `mu + eps` cancels, one rounding of `eps` is many places of the sum
        scale = jnp.maximum(jnp.abs(a), jnp.abs(mu))
        gap = jnp.abs(a - b) / (jnp.nextafter(scale, jnp.inf) - scale)
        return jnp.max(gap), jnp.mean((a != b).astype(jnp.float32))

    def library(key, mu, sigma, n):
        return Gaussian._sample(key, {"mu": mu, "sigma": sigma}, n)

    def barrier(key, mu, sigma, n):
        return plain(key, mu, sigma, n, barrier=True)

    def same(samples, normals):
        return jnp.all(samples[0::2] == normals) & jnp.all(samples[1::2] == -normals)

    samplers = {name: jax.jit(fn, static_argnums=3) for name, fn in (("library", library), ("plain", plain), ("barrier", barrier))}
    grads = {"library": jax.jit(library_grad), "pairwise": jax.jit(pairwise)}
    ulps, same = jax.jit(ulps), jax.jit(same)

    def clock(call):
        start = time.perf_counter()
        out = jax.block_until_ready(call())
        return out, (time.perf_counter() - start) * 1e3

    def summary(readings):
        return {name: {"median_ms": statistics.median(ms), "ms": ms} for name, ms in readings.items()}

    for shape in args.shapes.split(","):
        n, length = (int(side) for side in shape.split("x"))
        keys = jax.random.split(jax.random.key(args.seed), 4)
        mu = jax.random.normal(keys[0], (length,))
        sigma = jnp.abs(jax.random.normal(keys[1], (length,))) * 0.1 + 0.05
        weights = rank(jax.random.normal(keys[2], (n,)), "centered", higher_is_better=True)
        line = {"shape": [n, length], "device": device_record()}

        # compiled ahead of time: one compile a form, and `memory_analysis()` of what runs
        temp_mb = {}
        asks = {name: fn.lower(keys[3], mu, sigma, n).compile() for name, fn in samplers.items()}
        for name, compiled in asks.items():
            temp_mb["ask." + name] = compiled.memory_analysis().temp_size_in_bytes / 1e6
        readings = {name: [] for name in asks}
        for repeat in range(args.repeats + 1):
            for name, compiled in asks.items():
                out, ms = clock(lambda: compiled(keys[3], mu, sigma))
                del out
                if repeat:  # the first call warms
                    readings[name].append(ms)
        line["ask"] = summary(readings)

        # the plain form first: its temporaries are gone before the second population is held
        reference = jax.block_until_ready(asks["plain"](keys[3], mu, sigma))
        samples = jax.block_until_ready(asks["library"](keys[3], mu, sigma))
        worst, share = ulps(samples, reference, mu)
        line["samples_max_ulp"], line["samples_differing_share"] = float(worst), float(share)
        del reference
        normals = asks["library"](keys[3], jnp.zeros_like(mu), jnp.ones_like(sigma))
        line["same_normals"] = bool(same(normals, jax.random.normal(keys[3], (n // 2, length))))
        del normals

        tells = {name: fn.lower(mu, sigma, samples, weights).compile() for name, fn in grads.items()}
        for name, compiled in tells.items():
            temp_mb["grad." + name] = compiled.memory_analysis().temp_size_in_bytes / 1e6
        readings = {name: [] for name in tells}
        results = {}
        for repeat in range(args.repeats + 1):
            for name, compiled in tells.items():
                results[name], ms = clock(lambda: compiled(mu, sigma, samples, weights))
                if repeat:
                    readings[name].append(ms)
        line["grad"] = summary(readings)
        line["grad_max_rel_diff"] = [
            float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(results["library"], results["pairwise"])
        ]
        line["temp_mb"] = temp_mb
        stats = jax.devices()[0].memory_stats() or {}
        line["peak_gb"] = stats.get("peak_bytes_in_use", 0) / 1e9
        del samples
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
