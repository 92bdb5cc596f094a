"""Fixture driver: SNES on a black-box objective, several generations per
``generation()`` call. It has no policy, no environment and no telemetry, and
brings a reference check of its own (the protocol: drivers/oo_searcher.py)."""

import jax
import jax.numpy as jnp
import numpy as np

from evotorch_tpu import Problem, SolutionBatch
from evotorch_tpu.algorithms import SNES


def sphere(x):
    return jnp.sum(x * x, axis=-1)


class Session:
    def __init__(self, files, config, workload, seed, scale):
        self.popsize = int(scale["popsize"])
        self._per_call = int(workload["traffic"]["generations_per_call"])
        self.problem = Problem(
            "min",
            sphere,
            solution_length=int(config["solution_length"]),
            initial_bounds=tuple(config["initial_bounds"]),
            seed=int(seed),
        )
        self.searcher = SNES(self.problem, popsize=self.popsize, stdev_init=float(config["stdev_init"]))
        self.devices = jax.devices()[: int(workload["chips"])]
        evaluations = self._per_call * self.popsize
        self.per_call = {
            "generations": self._per_call,
            "interactions": evaluations,
            "interactions_max": evaluations,
            "episodes": None,
            "telemetry_lag": 0,
        }
        self._reference = files.module_at(config["reference"]["objective"])
        self._points = int(scale["points"])
        self._evaluations = 0

    def generation(self):
        for _ in range(self._per_call):
            self.searcher.step()
            self._evaluations += len(self.searcher.population)

    def block(self):
        jax.block_until_ready(self.searcher.population.evals)

    def mark(self):
        finite = self._evaluations == 0 or bool(jnp.isfinite(self.searcher.population.evals).all())
        return {"interactions": self._evaluations, "episodes": 0, "finite": finite, "telemetry": None}

    def reference_checks(self, seed):
        points = jax.random.normal(
            jax.random.key(seed), (self._points, self.problem.solution_length), jnp.float32
        )
        batch = SolutionBatch(self.problem, values=points)
        self.problem.evaluate(batch)
        got = np.asarray(batch.evals[:, 0], dtype=np.float64)
        want = self._reference.objective(np.asarray(points))
        error = float(np.max(np.abs(got - want) / want))
        return {"objective": {"ok": bool(error <= 1e-5), "max_relative_error": error}}


def build(files, config, workload, seed, scale):
    return Session(files, config, workload, seed, scale)
