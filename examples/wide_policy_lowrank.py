"""Wide policies at full speed: factored (low-rank) populations.

The MXU cannot amortize weights across ES lanes when every lane carries its
own parameters — growing the policy 64x64 -> 256x256 costs ~3.4x throughput
on a v5e (r2 chip run, ROADMAP S4). ``PGPE(..., lowrank_rank=k)`` restructures the
perturbation instead of the hardware: the population is
``theta_i = center + B z_i`` with a shared per-generation basis, evaluated
with (k+1) large shared-weight matmuls, and the dense ``(N, L)`` population
matrix is never materialized (for this 256x256 policy at popsize 10k it
would be ~3.9 GB).

Run: ``python wide_policy_lowrank.py --cpu --generations 5`` (scaled-down)
or on the TPU at full scale with no flags.
"""

import jax.numpy as jnp

from _common import setup_platform

args = setup_platform()

from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.logging import StdOutLogger
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.tools.lowrank import LowRankParamsBatch


def main():
    on_cpu = bool(args.cpu)
    problem = VecNE(
        "humanoid",
        # a WIDE policy: 256x256 hidden (≈98k parameters) — the regime where
        # the dense per-lane forward collapses MXU utilization
        "Linear(obs_length, 256) >> Tanh() >> Linear(256, 256) >> Tanh()"
        " >> Linear(256, act_length)",
        observation_normalization=True,
        episode_length=25 if on_cpu else 200,
        eval_mode="budget",
        compute_dtype=None if on_cpu else jnp.bfloat16,
        seed=0,
    )
    searcher = PGPE(
        problem,
        popsize=64 if on_cpu else 10_000,
        center_learning_rate=0.06,
        stdev_learning_rate=0.1,
        radius_init=0.27,
        optimizer="clipup",
        optimizer_config={"max_speed": 0.12},
        ranking_method="centered",
        lowrank_rank=32,  # the whole difference: factored perturbations
    )
    StdOutLogger(searcher, interval=1 if on_cpu else 10)
    searcher.run(args.generations or (2 if on_cpu else 50))

    pop = searcher.population
    assert isinstance(pop.values, LowRankParamsBatch)  # never densified
    print(
        f"population held factored: coeffs {pop.values.coeffs.shape} + "
        f"basis {pop.values.basis.shape} instead of a dense "
        f"({len(pop)}, {problem.solution_length}) matrix; "
        f"best_eval={float(searcher.status['best_eval']):.2f}"
    )


if __name__ == "__main__":
    main()
