"""Search-algorithm base machinery.

Parity: reference ``algorithms/searchalgorithm.py`` — ``LazyReporter``
(``searchalgorithm.py:34-238``), ``SearchAlgorithm`` with hooks and
``step()``/``run()`` orchestration (``searchalgorithm.py:240-447``), and
``SinglePopulationAlgorithmMixin`` auto status (``searchalgorithm.py:450-584``).
"""

from __future__ import annotations

from datetime import datetime
from functools import partial
from typing import Optional

import numpy as np

from ..core import Problem
from ..observability import counters, ensure_compile_counter, ensure_compile_timer
from ..observability.scopes import phase
from ..tools.hook import Hook
from ..tools.lazyreporter import LazyReporter, LazyStatusDict

__all__ = [
    "LazyReporter",
    "LazyStatusDict",
    "SearchAlgorithm",
    "SinglePopulationAlgorithmMixin",
]


class SearchAlgorithm(LazyReporter):
    """Base class of all search algorithms (reference
    ``searchalgorithm.py:240``): hooks, step orchestration, run loop."""

    def __init__(self, problem: Problem, **kwargs):
        super().__init__(**kwargs)
        # session-wide compile accounting (observability.registry): from the
        # first searcher on, every XLA compile in the process increments the
        # `compiles` counter and accumulates its wall time into
        # `compile_seconds` — step() publishes the per-generation deltas, so
        # a steady-state retrace is visible (count AND cost) in every logger
        # for free
        ensure_compile_counter()
        ensure_compile_timer()
        self._problem = problem
        self._before_step_hook = Hook()
        self._after_step_hook = Hook()
        self._log_hook = Hook()
        self._end_of_run_hook = Hook()
        self._steps_count = 0
        self._first_step_datetime: Optional[datetime] = None
        self._problem_status_keys: tuple = ()

    # ---- problem-status passthrough (lazy; lowest precedence) --------------
    # The problem's status merges into the algorithm's WITHOUT materializing
    # device-resident entries. Precedence: _computed (update_status results,
    # incl. after-step hooks) > _getters (algorithm getters) > problem keys —
    # so hooks can still override problem-reported values. Reads memoize into
    # _computed, pinning the value for the rest of the step.
    def get_status_value(self, key: str):
        try:
            return super().get_status_value(key)
        except KeyError:
            if key in self._problem_status_keys:
                value = self._problem.get_status_value(key)
                self._computed[key] = value
                return value
            raise

    def has_status_key(self, key: str) -> bool:
        return super().has_status_key(key) or key in self._problem_status_keys

    def iter_status_keys(self):
        seen = set()
        for k in super().iter_status_keys():
            seen.add(k)
            yield k
        for k in self._problem_status_keys:
            if k not in seen:
                yield k

    @property
    def problem(self) -> Problem:
        return self._problem

    @property
    def before_step_hook(self) -> Hook:
        return self._before_step_hook

    @property
    def after_step_hook(self) -> Hook:
        return self._after_step_hook

    @property
    def log_hook(self) -> Hook:
        return self._log_hook

    @property
    def end_of_run_hook(self) -> Hook:
        return self._end_of_run_hook

    @property
    def step_count(self) -> int:
        return self._steps_count

    @property
    def steps_count(self) -> int:  # legacy alias (reference keeps both)
        return self._steps_count

    @property
    def first_step_datetime(self) -> Optional[datetime]:
        return self._first_step_datetime

    @property
    def is_terminated(self) -> bool:
        """Overridable termination criterion (reference
        ``searchalgorithm.py:445``)."""
        return False

    def _step(self):
        raise NotImplementedError

    def step(self):
        """One generation (reference ``searchalgorithm.py:380-397``).
        Beyond the reference, per-generation wall-clock is published as
        ``step_seconds``, and the observability registry's per-step deltas
        as ``compiles`` / ``trace_spans`` / ``telemetry_fetches`` /
        ``compile_seconds`` (compile-pipeline wall time this generation) —
        a nonzero ``compiles`` after warmup IS a steady-state retrace, and
        ``compile_seconds`` says what it cost. ``peak_hbm_bytes`` is the
        program ledger's high-water gauge (the largest analyzed peak
        footprint captured so far; 0 until something is captured —
        docs/observability.md "Program ledger")."""
        import time

        # `generation` encloses the step's phases (observability/scopes.py):
        # the subclass's `_step` wears grad / update / ask, `Problem.evaluate`
        # its own, and everything this method does itself is `status`
        with phase("generation", n=self._steps_count + 1):
            with phase("status"):
                self._before_step_hook()
                self.clear_status()
                if self._first_step_datetime is None:
                    self._first_step_datetime = datetime.now()
                meters = counters.snapshot(
                    ("compiles", "trace_spans", "telemetry_fetches", "compile_seconds")
                )
            t0 = time.perf_counter()
            self._step()
            step_seconds = time.perf_counter() - t0
            with phase("status"):
                self._steps_count += 1
                self.update_status({"iter": self._steps_count, "step_seconds": step_seconds})
                self.update_status(counters.delta(meters))
                # absolute gauges (not per-step deltas): the ledger's peak-footprint
                # high-water mark, so every logger row carries the memory figure
                self.update_status({"peak_hbm_bytes": counters.get("peak_hbm_bytes")})
                # refresh the lazy problem-status passthrough (see get_status_value)
                self._problem_status_keys = tuple(self._problem.iter_status_keys())
                extra = self._after_step_hook.accumulate_dict()
                if extra:
                    self.update_status(extra)
                if len(self._log_hook) >= 1:
                    self._log_hook(dict(self.status.items()))

    def run(
        self,
        num_generations: int,
        *,
        reset_first_step_datetime: bool = True,
        profile_dir: Optional[str] = None,
    ):
        """Run ``num_generations`` steps (reference ``searchalgorithm.py:409``).

        ``profile_dir`` captures a ``jax.profiler`` device trace of the whole
        run (SURVEY.md §5: the reference has no tracing; on TPU this is how
        you see MXU/HBM utilization and host<->device gaps). View with
        ``tensorboard --logdir <profile_dir>`` or xprof."""
        if reset_first_step_datetime:
            self.reset_first_step_datetime()

        def _run():
            for _ in range(int(num_generations)):
                self.step()
                if self.is_terminated:
                    break

        if profile_dir is not None:
            import jax

            with jax.profiler.trace(str(profile_dir)):
                _run()
        else:
            _run()
        if len(self._end_of_run_hook) >= 1:
            self._end_of_run_hook(dict(self.status.items()))

    def reset_first_step_datetime(self):
        self._first_step_datetime = None


class SinglePopulationAlgorithmMixin:
    """Auto status getters over ``.population``
    (reference ``searchalgorithm.py:450-584``): ``pop_best``,
    ``pop_best_eval``, ``mean_eval``, ``median_eval`` (prefixed per objective
    in the multi-objective case)."""

    def __init__(self, *, exclude: Optional[set] = None, enable: bool = True):
        if not enable:
            return
        exclude = exclude or set()
        problem = self.problem

        from functools import partial

        def make_getters(obj_index: int, prefix: str):
            # partials over bound methods (not closures) keep searchers
            # picklable for whole-object checkpointing
            return {
                f"{prefix}pop_best": partial(self._status_pop_best, obj_index),
                f"{prefix}pop_best_eval": partial(self._status_pop_best_eval, obj_index),
                f"{prefix}mean_eval": partial(self._status_mean_eval, obj_index),
                f"{prefix}median_eval": partial(self._status_median_eval, obj_index),
            }

        # algorithms focused on a single objective (via their obj_index)
        # report unprefixed stats for that objective even on multi-objective
        # problems (reference searchalgorithm.py:563-574); only truly
        # multi-objective algorithms get per-objective prefixes
        algo_obj_index = getattr(self, "obj_index", None)
        if problem.is_multi_objective and algo_obj_index is None:
            getters = {}
            for i in range(problem.num_objectives):
                getters.update(make_getters(i, f"obj{i}_"))
        else:
            getters = make_getters(0 if algo_obj_index is None else int(algo_obj_index), "")
        self.update_status_getters({k: v for k, v in getters.items() if k not in exclude})

    def _status_pop_best(self, obj_index: int):
        batch = self.population
        i = int(np.asarray(batch.argbest(obj_index)))
        return batch[i].clone()

    def _status_pop_best_eval(self, obj_index: int) -> float:
        batch = self.population
        i = int(np.asarray(batch.argbest(obj_index)))
        return float(np.asarray(batch.evals[i, obj_index]))

    def _status_mean_eval(self, obj_index: int) -> float:
        return float(np.nanmean(np.asarray(self.population.evals[:, obj_index])))

    def _status_median_eval(self, obj_index: int) -> float:
        return float(np.nanmedian(np.asarray(self.population.evals[:, obj_index])))
