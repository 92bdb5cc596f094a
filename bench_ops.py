"""Micro-bench for the fused Pallas kernels (ops/) vs their XLA forms.

Runs on the TPU only: the kernels have no other compiled form, and an
interpret-mode timing would say nothing. Prints one JSON line per comparison,
each naming the device it ran on; the opt-in flags ``EVOTORCH_TPU_FUSED_RANK``
and ``EVOTORCH_TPU_FUSED_SAMPLING`` (both kernels ship off by default until a
chip win is recorded) are justified or refuted by these numbers. A kernel
that fails to compile fails the run. The sweep times XLA beyond the fused
VMEM bound (n <= 1024) for context; the fused kernel is only timed inside the
bound, where the flag would select it.
"""

import json
import time
from functools import partial

from evotorch_tpu.resilience import device_record, setup_backend


def _time(fn, *args, iters=200):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    if setup_backend():
        raise SystemExit("bench_ops.py times compiled TPU kernels; it has no CPU form")
    import jax
    import jax.numpy as jnp

    from evotorch_tpu.observability import enable_persistent_cache
    from evotorch_tpu.ops.ranking import fused_centered_rank
    from evotorch_tpu.ops.sampling import sample_symmetric_gaussian
    from evotorch_tpu.tools.ranking import centered_xla

    enable_persistent_cache()
    backend = device_record()
    key = jax.random.key(0)

    # jitted once, outside the timing loops (graftlint `retrace`: a jit built
    # per iteration discards its trace cache every time)
    xla = jax.jit(partial(centered_xla, higher_is_better=True))
    # fused_centered_rank is itself jitted (ops/ranking.py): partial only
    fused = partial(fused_centered_rank, higher_is_better=True, use_pallas=True)

    for n in (256, 512, 1024, 2048):
        # each size draws from its own subkey (graftlint `prng`: reusing the
        # base key across iterations would replay the same stream)
        key, sub = jax.random.split(key)
        fit = jax.random.normal(sub, (n,))
        t_xla = _time(xla, fit)
        # only time the fused kernel where the dispatch would select it
        # (n <= 1024: the O(n^2) comparison block fits VMEM; 2048 would not)
        t_fused = _time(fused, fit) if n <= 1024 else None
        print(
            json.dumps(
                {
                    "metric": "fused_centered_rank_us",
                    "n": n,
                    "xla_us": round(t_xla * 1e6, 2),
                    "pallas_us": None if t_fused is None else round(t_fused * 1e6, 2),
                    "speedup": None if t_fused is None else round(t_xla / t_fused, 3),
                    "backend": backend,
                }
            )
        )

    for popsize, length in ((10_000, 12_305), (1_024, 66_048)):
        mu = jnp.zeros(length)
        sigma = jnp.full(length, 0.1)
        # sample_symmetric_gaussian is itself jitted (ops/sampling.py);
        # re-wrapping it in a per-iteration jit(lambda) would rebuild the
        # trace cache every loop pass
        times = {
            use_pallas: _time(
                partial(
                    sample_symmetric_gaussian,
                    mu=mu, sigma=sigma, num_solutions=popsize, use_pallas=use_pallas,
                ),
                key,
                iters=20,
            )
            for use_pallas in (False, True)
        }
        print(
            json.dumps(
                {
                    "metric": "fused_antithetic_sampling_ms",
                    "popsize": popsize,
                    "solution_length": length,
                    "xla_ms": round(times[False] * 1e3, 3),
                    "pallas_ms": round(times[True] * 1e3, 3),
                    "speedup": round(times[False] / times[True], 3),
                    "backend": backend,
                }
            )
        )


if __name__ == "__main__":
    main()
