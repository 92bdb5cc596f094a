"""Driver: the path a researcher calls: ``VecNE(...)`` + ``PGPE(...)`` +
``searcher.step()``, one whole generation per call.

A driver turns a configuration file and a workload's traffic parameters into
a *session*: ``build(files, config, workload, seed, scale)``, where ``files``
is the loader (a driver loads the reference files its cell names), ``seed`` is
``--seed`` and ``scale`` the popsize and the reference's sample sizes as run
(the configuration's, or its rehearsal's). The harness asks a session for
nothing but:

- ``generation()``: start one call's work: one whole generation here (ask,
  evaluate, tell); a fused or scanned driver may run several per call;
- ``block()``: return once that work's evaluations are ready;
- ``per_call``: what ONE ``generation()`` call runs and counts, as plain
  numbers: ``generations``; ``interactions`` and ``episodes`` (exact, or None
  where the contract fixes no exact figure); ``interactions_max``;
  ``telemetry_lag`` (how many calls later ``mark()`` carries a call's
  telemetry). harness/check.py holds every call of the window to it;
- ``mark()``: after the clock has stopped, the cumulative counts the run is
  checked by: ``interactions``, ``episodes``, whether every evaluation of the
  last population is ``finite``, and ``telemetry``: the on-device counters
  (``env_steps``, ``episodes``, ``capacity``, ``nonfinite``) of the call
  ``telemetry_lag`` calls back, or None (the library decodes a generation's
  telemetry one generation late);
- ``reference_checks(seed)``: the comparison with the plain reference, run in
  set-up after the warm-up generations: ``{name: {"ok": bool, ...}}``;
- ``devices``: the devices the cell uses.

Everything else is for the per-layer metric readers of the layers this
session has (a reader asks with ``getattr`` and reads nothing where the
attribute is missing): ``problem`` (whose ``lower_evaluation`` gives the
text the trace's ops are joined to by scope), ``compute_dtype``,
``parameter_count``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from evotorch_tpu import SolutionBatch
from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.neuroevolution import VecNE

DTYPES = {"bfloat16": jnp.bfloat16, "float32": None}  # None: the library's default


class Session:
    def __init__(self, files, config, workload, seed, scale):
        traffic = workload["traffic"]
        self.popsize = int(scale["popsize"])
        self.eval_mode = traffic["eval_mode"]
        self.episode_length = int(config["episode_length"])
        self.compute_dtype = DTYPES[config["compute_dtype"]]
        self._problem_args = dict(
            env=config["env"],
            network=config["network"],
            episode_length=self.episode_length,
            num_episodes=int(config["num_episodes"]),
            eval_mode=self.eval_mode,
            compute_dtype=self.compute_dtype,
            num_actors=traffic["num_actors"],
        )
        # ``traffic.search_seed``: where the work a generation does depends on
        # the trajectories (``episodes``: the loop runs until the longest
        # survivor ends), the search starts from a seed fixed in the workload
        # file, so that every run of a commit does the same work; ``--seed``
        # then seeds only the comparison with the reference (PERF.md section 4)
        search_seed = traffic.get("search_seed")
        self.problem = VecNE(
            **self._problem_args,
            observation_normalization=bool(config["observation_normalization"]),
            seed=int(seed if search_seed is None else search_seed),
        )
        recipe = dict(config["searcher"])
        if recipe.pop("class") != "PGPE":
            raise ValueError("this driver runs PGPE")
        self.searcher = PGPE(self.problem, popsize=self.popsize, **recipe)
        if self.problem.solution_length != int(config["parameter_count"]):
            raise ValueError(
                f"the network has {self.problem.solution_length} parameters, the"
                f" configuration says {config['parameter_count']}"
            )
        chips = int(workload["chips"])
        self.devices = jax.devices()[:chips]

        # -- what one call counts, by the eval contract docs/eval_contracts.md
        # states: ``budget`` spends exactly popsize x episode_length counted
        # interactions; an ``episodes`` contract runs popsize x num_episodes
        # episodes and counts at most as many interactions
        most = self.popsize * self.episode_length * int(config["num_episodes"])
        budget = traffic["reference"]["contract"] == "budget"
        self.per_call = {
            "generations": 1,
            "interactions": most if budget else None,
            "interactions_max": most,
            "episodes": None if budget else self.popsize * int(config["num_episodes"]),
            "telemetry_lag": 1,
        }

        # -- the plain reference: the forward the configuration names, the
        # rollout the traffic names
        self._forward = files.module_at(config["reference"]["forward"])
        self._rollout = files.module_at(traffic["reference"]["rollout"])
        self._contract = traffic["reference"]["contract"]
        self._sizes = self._forward.sizes(config)
        self.parameter_count = self.problem.solution_length
        if self._forward.parameter_count(self._sizes) != int(config["parameter_count"]):
            raise ValueError("the configuration's parameter_count does not follow from its sizes")
        self._samples = (int(scale["forward_pairs"]), int(scale["rollout_lanes"]))
        self._first_population = None
        self._seed = int(seed)

    # -- the measured path ---------------------------------------------------
    def generation(self):
        self.searcher.step()
        if self.searcher.step_count == 1:  # the first warm-up generation's, for the reference
            self._first_population = jnp.array(self.searcher.population.values[: self._samples[1]])

    def block(self):
        jax.block_until_ready(self.searcher.population.evals)

    def mark(self):
        if self.searcher.step_count == 0:  # nothing has run yet
            return {"interactions": 0, "episodes": 0, "finite": True, "telemetry": None}
        status = self.searcher.status
        telemetry = self.problem.last_group_telemetry
        return {
            "interactions": int(status["total_interaction_count"]),
            "episodes": int(status["total_episode_count"]),
            "finite": bool(jnp.isfinite(self.searcher.population.evals).all()),
            "telemetry": None if telemetry is None else self._counters(telemetry.total()),
        }

    @staticmethod
    def _counters(total):
        return {
            name: int(getattr(total, name))
            for name in ("env_steps", "episodes", "capacity", "nonfinite")
        }

    # -- the comparison with the plain reference -----------------------------
    def reference_checks(self, seed):
        from benchmark.harness import check

        pairs, _ = self._samples
        forward = check.forward_against_reference(
            self.problem.policy, self.compute_dtype, self._forward, self._sizes, pairs, seed
        )
        rollout = self._rollout.make_rollout(
            self.problem.env,
            lambda flat, obs: self._forward.forward(flat, obs, self._sizes),
            self._contract,
            self.episode_length,
        )
        contract = check.contract_against_reference(
            self.evaluate_frozen,
            rollout,
            self._first_population,
            contract=self._contract,
            episode_length=self.episode_length,
            seed=seed,
        )
        self._first_population = None
        return {"forward": forward, "contract": contract}

    def evaluate_frozen(self, values):
        """The cell's eval contract on a second problem with observation
        normalisation off (the library has no frozen mode)."""
        problem = VecNE(**self._problem_args, observation_normalization=False, seed=self._seed + 1)
        batch = SolutionBatch(problem, values=values)
        problem.evaluate(batch)
        return {
            "scores": np.asarray(batch.evals[:, 0], dtype=np.float64),
            "interactions": int(problem.status["total_interaction_count"]),
            "episodes": int(problem.status["total_episode_count"]),
        }


def build(files, config, workload, seed, scale):
    return Session(files, config, workload, seed, scale)
