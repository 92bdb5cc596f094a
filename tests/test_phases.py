"""A generation's phases (``observability/scopes.py``): one ``phase()`` call
feeds the profiler and the host span tracer, the phases tile a step as
siblings inside ``generation``, and the programs a phase dispatches carry the
phase in their name and nothing else new in their lowered text."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from evotorch_tpu import core, distributions
from evotorch_tpu.algorithms import CMAES, PGPE, gaussian, mapelites
from evotorch_tpu.analysis import track_compiles
from evotorch_tpu.distributions import SymmetricSeparableGaussian
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.observability import tracer
from evotorch_tpu.observability.scopes import (
    PROGRAM_PREFIX,
    SCOPE_PREFIX,
    SEARCHER_PHASES,
    phase,
    phase_jit,
)
from evotorch_tpu.optimizers import ClipUp

NETWORK = "Linear(obs_length, 8) >> Tanh() >> Linear(8, act_length)"
PGPE_FORMS = {
    "dense": {},
    "lowrank": {"lowrank_rank": 2},
    "trunk_delta": {"lowrank_rank": ("trunk_delta", 2), "stdev_max_change": 0.2},
}
# the trunk-delta tell and cmaes_tell rank inside their update: no `grad`
PHASES_OF = {
    "dense": set(SEARCHER_PHASES),
    "lowrank": set(SEARCHER_PHASES),
    "trunk_delta": set(SEARCHER_PHASES) - {"grad"},
    "cmaes": set(SEARCHER_PHASES) - {"grad"},
}


def warm_searcher(form):
    problem = VecNE("cartpole", NETWORK, eval_mode="budget", episode_length=10, seed=1)
    if form == "cmaes":
        searcher = CMAES(problem, stdev_init=0.1, popsize=8)
    else:
        searcher = PGPE(
            problem,
            popsize=8,
            center_learning_rate=0.1,
            stdev_learning_rate=0.1,
            stdev_init=0.1,
            **PGPE_FORMS[form],
        )
    for _ in range(3):
        searcher.step()
    return searcher


def test_phase_refuses_an_undeclared_name():
    with pytest.raises(ValueError, match="SEARCHER_PHASES"):
        phase("tell")
    with pytest.raises(ValueError, match="SEARCHER_PHASES"):
        phase_jit("generation", lambda x: x)  # encloses the phases; dispatches nothing itself
    for name in SEARCHER_PHASES + ("generation",):
        with phase(name):
            pass


def test_phase_is_the_bare_annotation_when_the_host_tracer_is_off():
    assert tracer.get_tracer() is None
    assert isinstance(phase("ask"), jax.profiler.TraceAnnotation)


def inside(inner, outer):
    return outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


@pytest.mark.parametrize("form", list(PHASES_OF))
def test_a_warm_step_records_its_phases_as_siblings_inside_generation(form):
    searcher = warm_searcher(form)
    recorder = tracer.start_tracing()
    try:
        searcher.step()
        events = [e for e in recorder.events() if e["ph"] == "X"]
    finally:
        tracer.stop_tracing(write=False)
    (generation,) = [e for e in events if e["name"] == SCOPE_PREFIX + "generation"]
    assert generation["args"] == {"n": 4}
    names = {SCOPE_PREFIX + name for name in SEARCHER_PHASES}
    phases = sorted((e for e in events if e["name"] in names), key=lambda e: e["ts"])
    assert {e["name"][len(SCOPE_PREFIX) :] for e in phases} == PHASES_OF[form]
    assert all(inside(e, generation) for e in phases)
    # siblings: each ends before the next starts, so none encloses another
    # (`ask` does not hold `evaluate`)
    for before, after in zip(phases, phases[1:]):
        assert before["ts"] + before["dur"] <= after["ts"], (before["name"], after["name"])
    order = [e["name"][len(SCOPE_PREFIX) :] for e in phases]
    assert order.index("ask") < order.index("evaluate") < len(order) - 1
    assert order[0] == order[-1] == "status"
    # nothing else the step recorded lies outside all of them
    for event in events:
        if event is not generation and event not in phases:
            assert any(inside(event, p) for p in phases), event["name"]


@pytest.mark.parametrize("form", list(PGPE_FORMS))
def test_a_warm_step_compiles_nothing(form):
    searcher = warm_searcher(form)
    with track_compiles() as log:
        searcher.step()
    assert log.count == 0, log.names


def test_one_call_feeds_the_profiler_too(tmp_path):
    searcher = warm_searcher("dense")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        searcher.step()
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(found)
    spans = [
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for plane in profile.planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
        if e.name.startswith(SCOPE_PREFIX)
    ]
    (generation,) = [s for s in spans if s[2] == SCOPE_PREFIX + "generation"]
    phases = sorted(s for s in spans if s is not generation)
    assert {name[len(SCOPE_PREFIX) :] for _, _, name in phases} == set(SEARCHER_PHASES)
    assert all(generation[0] <= start and end <= generation[1] for start, end, _ in phases)
    for (_, end, _), (start, _, _) in zip(phases, phases[1:]):
        assert end <= start


# -- the programs a phase dispatches ---------------------------------------------


def _gaussian(length=6):
    return SymmetricSeparableGaussian(
        {"mu": jnp.zeros(length), "sigma": jnp.ones(length), "divide_mu_grad_by": "num_directions",
         "divide_sigma_grad_by": "num_directions"}
    )


def _sample():
    arrays, static = distributions._split_params(_gaussian().parameters)
    program = distributions._jitted_sample_for(SymmetricSeparableGaussian)
    return program, (jax.random.key(0), arrays, static, 8), {"static_argnames": ("static_items", "num_solutions")}


def _sample_lowrank():
    arrays, static = distributions._split_params(_gaussian().parameters)
    program = distributions._jitted_sample_lowrank_for(SymmetricSeparableGaussian)
    return (
        program,
        (jax.random.key(0), arrays, static, 8, 2),
        {"static_argnames": ("static_items", "num_solutions", "rank")},
    )


def _policy():
    return VecNE("cartpole", NETWORK, eval_mode="budget", episode_length=10, seed=1).policy


def _sample_trunk_delta():
    policy = _policy()
    program = distributions._jitted_sample_trunk_delta(SymmetricSeparableGaussian, policy, 8, 2, True)
    return program, (jax.random.key(0), jnp.ones(policy.parameter_count), None), {}


def _grads():
    arrays, static = distributions._split_params(_gaussian().parameters)
    program = distributions._jitted_grads_for(SymmetricSeparableGaussian)
    return (
        program,
        (arrays, jnp.ones((8, 6)), jnp.arange(8.0), static, "centered", True),
        {"static_argnames": ("static_items", "ranking_method", "higher_is_better")},
    )


def _trunk_delta_tell():
    policy = _policy()
    length = policy.parameter_count
    dist = _gaussian(length)
    optimizer = ClipUp(solution_length=length, stepsize=0.1)
    program = gaussian._make_trunk_delta_tell(
        SymmetricSeparableGaussian,
        distributions._split_params(dist.parameters)[1],
        optimizer,
        center_learning_rate=0.1,
        stdev_learning_rate=0.1,
        ranking_method="centered",
        higher_is_better=True,
        clamped=(False, False, True),
    )
    samples = dist.sample_trunk_delta(8, 2, policy, key=jax.random.key(0))
    args = (
        dist.parameters["mu"],
        dist.parameters["sigma"],
        optimizer.state(),
        samples.coeffs,
        samples.factors,
        jnp.arange(8.0),
        (None, None, jnp.asarray(0.2)),
    )
    return program, args, {"donate_argnums": (0, 1, 2)}


def _batch_extremes():
    args = (jnp.ones((8, 6)), jnp.arange(8.0)[:, None], ("max",))
    return core._batch_extremes, args, {"static_argnames": ("senses",)}


def _merge_snapshots():
    rows, evals = jnp.ones((1, 6)), jnp.ones((1, 1))
    args = (rows, evals, rows, evals, rows, evals, rows, evals, ("max",))
    return core._merge_snapshots, args, {"static_argnames": ("senses",)}


def _best_solutions_for_all_cells():
    grid = mapelites.MAPElites.make_feature_grid([0.0], [1.0], 4)
    args = ("max", jnp.ones((8, 6)), jnp.ones((8, 2)), grid)
    return mapelites._best_solutions_for_all_cells, args, {"static_argnames": ("objective_sense",)}


RENAMED = {
    "ask_sample": _sample,
    "ask_sample_lowrank": _sample_lowrank,
    "ask_sample_trunk_delta": _sample_trunk_delta,
    "grad_grads": _grads,
    "update_trunk_delta_tell": _trunk_delta_tell,
    "evaluate_batch_extremes": _batch_extremes,
    "evaluate_merge_snapshots": _merge_snapshots,
    "update_best_solutions_for_all_cells": _best_solutions_for_all_cells,
}


def lowered_texts(name):
    """The renamed program's lowered text and the text of the function it
    wraps under plain ``jax.jit`` with the same arguments, each with its
    module's name masked."""
    program, args, jit_kwargs = RENAMED[name]()
    renamed = program.lower(*args).as_text()
    assert renamed.startswith(f"module @jit_{PROGRAM_PREFIX}{name} "), renamed.splitlines()[0]
    function = program.__wrapped__.__wrapped__  # under jit, under the renaming wrapper
    plain = jax.jit(function, **jit_kwargs).lower(*args).as_text()
    return (
        renamed.replace(f"@jit_{PROGRAM_PREFIX}{name} ", "@jit_ "),
        plain.replace(f"@jit_{function.__name__} ", "@jit_ "),
    )


@pytest.mark.parametrize("name", list(RENAMED))
def test_a_renamed_program_differs_from_the_plain_one_by_its_name_alone(name):
    assert name.split("_")[0] in SEARCHER_PHASES
    renamed, plain = lowered_texts(name)
    assert renamed == plain
