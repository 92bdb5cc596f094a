"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once, through the entry points
a user calls, at the full width of the flagship the repo supports: Humanoid,
``Linear(obs, 64) >> Tanh >> Linear(64, 64) >> Tanh >> Linear(64, act)``
(12,305 parameters), popsize 10,000, episode length 200, observation
normalisation on. Depth is cut to a few generations and the weights are
PGPE's seeded initial distribution. The legs, in order:

- sentinel: the retrace sentinel counts a compile that is known to happen
  (``steady_compiles == 0`` is worthless if its log line ever stops matching);
- kernels: each Pallas kernel, compiled, at the ends of what its dispatcher
  admits, against its XLA form;
- trainer: ``VecNE`` + ``PGPE(optimizer="clipup")`` + ``searcher.run()`` in
  ``budget``, ``episodes`` and ``episodes_refill``: exact interaction counts,
  finite scores, nothing compiled once a contract's first update has run;
- fused: one ``parallel.make_generation_step`` and one
  ``parallel.make_training_span`` call with the donated state consumed, and
  the program ledger's cost, memory and donation analyses present;
- server: one ``serving.EvalServer`` answers ``submit()`` requests from two
  tenants; per-tenant scores equal the standalone ``episodes_refill``
  evaluation of the same rows and keys, and the second round compiles nothing;
- mesh (when jax sees more than one device): the trainer and the fused step
  over every device, on ``pop=N`` and on ``pop=N/2 x model=2``, at popsize
  10,000 and at 10,002 (padded): counts exact, scores against the one-device
  run of the same key, every device shown to have worked.

It runs in one process, has no CPU form and catches nothing: the first thing
it does is read ``jax.devices()`` and exit non-zero unless the platform is
``tpu``, and any failed check is an uncaught exception. The last line of its
standard output is ``{"ok": true, "device": {...}}`` with the device as jax
reports it. The compile cache goes where observability/compilecache.py's one
rule puts it, so a second run finds the first one's programs.
"""

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.algorithms.functional import pgpe, pgpe_ask, pgpe_tell
from evotorch_tpu.analysis import track_compiles
from evotorch_tpu.envs import make_env
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.neuroevolution.net import FlatParamsPolicy, tanh_mlp
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.neuroevolution.net.vecrl import run_vectorized_rollout
from evotorch_tpu.observability import cache_stats, enable_persistent_cache
from evotorch_tpu.observability import ledger as program_ledger
from evotorch_tpu.observability.programs import abstract_like
from evotorch_tpu.observability.report import peak_flops
from evotorch_tpu.ops import fused_centered_rank, sample_symmetric_gaussian
from evotorch_tpu.ops.sampling import _BLOCK_LANES, _BLOCK_ROWS
from evotorch_tpu.parallel import (
    make_generation_step,
    make_mesh,
    make_training_span,
)
from evotorch_tpu.serving import EvalServer
from evotorch_tpu.tools.ranking import centered_xla

ENV_NAME = "humanoid"
HIDDEN = (64, 64)
NETWORK = (
    "Linear(obs_length, 64) >> Tanh() >> Linear(64, 64) >> Tanh()"
    " >> Linear(64, act_length)"
)
POPSIZE = 10_000
PADDED_POPSIZE = 10_002  # pads to 10,004 on four devices
EPISODE_LENGTH = 200
SPAN = 2
# examples/humanoid_pgpe.py's searcher, shared by the OO and functional forms
PGPE_RECIPE = dict(
    center_learning_rate=0.06,
    stdev_learning_rate=0.1,
    radius_init=0.27,
    optimizer="clipup",
    optimizer_config={"max_speed": 0.12},
)
# the second shape is bench_ops.py's: few, very long solutions
SAMPLING_SHAPES = ((POPSIZE, 12_305), (1_024, 66_048))
# the ends of tools.ranking._use_fused_centered's 2 <= n <= 1024, and one
# length that is not a multiple of the 128-lane tile
RANK_SIZES = (2, 1_000, 1_024)
# Sharded scores against the one-device run of the same key (measured on a
# v5e 2x2, PERF.md PR 21). Without observation normalisation every lane's
# arithmetic is its own, and sharding must not change which policy met which
# episode: 9,988 of 10,000 lanes came out bit-identical in every layout, so at
# least MESH_MIN_IDENTICAL_SHARE of them must. With it (the flagship trainer),
# the two programs reduce the observation statistics over the population in
# another order, every lane's normalised observation moves in its last bits,
# and 200 steps of contact dynamics amplify that until the lanes decorrelate
# (median |difference| 10 on scores of ~750, lane-by-lane correlation 0.73).
# What must agree then is the population: its mean and its 1%..99% quantiles,
# relative to the largest score. Whether scores are bit-identical, and their
# correlation, is printed either way.
MESH_MIN_IDENTICAL_SHARE = 0.99
MESH_MEAN_RTOL = 0.01
MESH_QUANTILE_RTOL = 0.05


T0 = time.perf_counter()


def check(condition, message):
    if not condition:
        raise AssertionError(message)


def report(leg, **facts):
    stats = cache_stats()
    print(
        json.dumps(
            {
                "leg": leg,
                **facts,
                "cache_hits": stats["hits"],
                "cache_misses": stats["misses"],
                "seconds_since_start": round(time.perf_counter() - T0, 1),
            }
        ),
        flush=True,
    )


def fresh_state(parameter_count):
    """The functional twin of the trainer's searcher."""
    return pgpe(
        center_init=jnp.zeros(parameter_count, dtype=jnp.float32),
        objective_sense="max",
        **PGPE_RECIPE,
    )


def all_deleted(tree):
    return all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------


def sentinel_leg():
    with track_compiles() as log:
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    check(log.count >= 1, f"the retrace sentinel missed a known compile: {log.names}")
    report("sentinel", compiles_seen=log.count)


def kernels_leg():
    key = jax.random.key(0)
    for n in RANK_SIZES:
        key, sub = jax.random.split(key)
        fit = jax.random.normal(sub, (n,), dtype=jnp.float32)
        # ties and a diverged rollout's NaN take the index tie-break path
        if n >= 8:
            fit = fit.at[3].set(fit[5]).at[1].set(jnp.nan)
        for higher_is_better in (True, False):
            got = fused_centered_rank(
                fit, higher_is_better=higher_is_better, use_pallas=True
            )
            want = centered_xla(fit, higher_is_better=higher_is_better)
            check(
                np.array_equal(np.asarray(got), np.asarray(want)),
                f"fused_centered_rank != centered_xla at n={n}",
            )
    batched = jax.random.normal(key, (3, 1_024), dtype=jnp.float32)
    check(
        np.array_equal(
            np.asarray(fused_centered_rank(batched, use_pallas=True)),
            np.asarray(centered_xla(batched)),
        ),
        "fused_centered_rank != centered_xla on a batch",
    )
    report("kernels.rank", sizes=list(RANK_SIZES), exact=True)

    for popsize, length in SAMPLING_SHAPES:
        key, k_mu, k_sample = jax.random.split(key, 3)
        mu = jax.random.normal(k_mu, (length,), dtype=jnp.float32)
        sigma = jnp.linspace(0.05, 2.0, length, dtype=jnp.float32)
        out = sample_symmetric_gaussian(k_sample, mu, sigma, popsize, use_pallas=True)
        check(out.shape == (popsize, length), f"sampling shape {out.shape}")
        # reduce on the device: the flagship population is 492 MB
        plus, minus = out[0::2], out[1::2]
        z = (plus - mu) / sigma  # the standard-normal draws, one per direction
        rows, lanes = _BLOCK_ROWS, _BLOCK_LANES
        facts = {
            "finite": bool(jnp.isfinite(out).all()),
            "pair_error": float(jnp.max(jnp.abs((plus + minus) * 0.5 - mu))),
            "z_mean": float(jnp.mean(z)),
            "z_std": float(jnp.std(z)),
            # per-parameter means over the directions: global moments alone
            # would pass a kernel whose noise depends on the column only
            "column_mean_max": float(jnp.max(jnp.abs(jnp.mean(z, axis=0)))),
            # neighbouring grid blocks draw from their own streams: a kernel
            # that seeded every block alike would correlate them fully
            "row_block_correlation": float(jnp.mean(z[:rows] * z[rows : 2 * rows])),
            "lane_block_correlation": float(
                jnp.mean(z[:, :lanes] * z[:, lanes : 2 * lanes])
            ),
        }
        directions = popsize // 2
        check(facts["finite"], "fused sampling produced non-finite values")
        check(facts["pair_error"] < 1e-5, f"antithetic pairing broken: {facts}")
        check(abs(facts["z_mean"]) < 1e-3, f"noise mean off: {facts}")
        check(abs(facts["z_std"] - 1.0) < 1e-3, f"noise std off: {facts}")
        check(
            facts["column_mean_max"] < 6.0 / directions**0.5,
            f"per-parameter noise mean off: {facts}",
        )
        check(
            abs(facts["row_block_correlation"]) < 0.01
            and abs(facts["lane_block_correlation"]) < 0.01,
            f"grid blocks share a stream: {facts}",
        )
        report("kernels.sampling", popsize=popsize, length=length, **facts)


def trainer(eval_mode, *, warmup=2, num_actors=None):
    """The recipe of examples/humanoid_pgpe.py in float32: ``warmup``
    generations, then one under the retrace sentinel. Returns the searcher,
    its final status and the first generation's scores.

    The OO searcher's first update runs at the start of its SECOND step (a
    step is: tell the previous population, ask, evaluate), so the gradient
    and ClipUp programs compile there and the steady state begins with the
    third generation."""
    problem = VecNE(
        ENV_NAME,
        NETWORK,
        observation_normalization=True,
        episode_length=EPISODE_LENGTH,
        eval_mode=eval_mode,
        seed=0,
        num_actors=num_actors,
    )
    searcher = PGPE(problem, popsize=POPSIZE, ranking_method="centered", **PGPE_RECIPE)
    searcher.run(1)  # ask + evaluate: compiles the contract's rollout
    first_evals = np.asarray(searcher.population.evals)[:, 0]
    searcher.run(warmup - 1)
    with track_compiles() as log:
        searcher.run(1)
        jax.block_until_ready(searcher.population.evals)
    generations = warmup + 1
    status = {
        "mean_eval": float(searcher.status["mean_eval"]),
        "best_eval": float(searcher.status["best_eval"]),
        "total_interaction_count": int(searcher.status["total_interaction_count"]),
        "total_episode_count": int(searcher.status["total_episode_count"]),
    }
    check(
        log.count == 0,
        f"{eval_mode}: compiled in the steady state: {log.names}",
    )
    check(
        np.isfinite([status["mean_eval"], status["best_eval"]]).all(),
        f"{eval_mode}: non-finite scores: {status}",
    )
    check(
        bool(jnp.isfinite(searcher.population.evals).all()),
        f"{eval_mode}: non-finite evaluations in the last population",
    )
    if eval_mode == "budget":
        check(
            status["total_interaction_count"] == generations * POPSIZE * EPISODE_LENGTH,
            f"budget: interaction count {status}",
        )
    else:
        check(
            status["total_episode_count"] == generations * POPSIZE,
            f"{eval_mode}: episode count {status}",
        )
        check(
            0 < status["total_interaction_count"] <= generations * POPSIZE * EPISODE_LENGTH,
            f"{eval_mode}: interaction count {status}",
        )
    return searcher, {"generations": generations, **status}, first_evals


def trainer_leg():
    """Returns the budget run's first-generation scores (the mesh leg's
    one-device reference)."""
    first_evals = {}
    for eval_mode in ("budget", "episodes", "episodes_refill"):
        searcher, status, first_evals[eval_mode] = trainer(eval_mode)
        check(
            searcher.problem.solution_length == 12_305,
            f"parameter count {searcher.problem.solution_length}",
        )
        report(f"trainer.{eval_mode}", **status)
    return first_evals["budget"]


def generation_step(env, policy, mesh, popsize, name, *, observation_normalization=True):
    """One ``make_generation_step`` call at the flagship shape on ``mesh``:
    donation consumed, counts exact, ledger analyses present. Returns the
    scores and the ledger record."""
    stats = RunningNorm(env.observation_size).stats
    step = make_generation_step(
        env, policy, mesh=mesh, **fused_kwargs(popsize, observation_normalization)
    )
    state = fresh_state(policy.parameter_count)
    new_state, scores, _, steps, _ = step(state, jax.random.key(1), stats)
    jax.block_until_ready(scores)
    check(all_deleted(state), f"{name}: the donated state was not consumed")
    check(int(steps) == popsize * EPISODE_LENGTH, f"{name}: {int(steps)} steps")
    check(scores.shape == (popsize,), f"{name}: scores {scores.shape}")
    check(bool(jnp.isfinite(scores).all()), f"{name}: non-finite scores")
    record = program_ledger.capture(
        f"chip_smoke.{name}",
        step,
        abstract_like(new_state),
        jax.random.key(1),
        abstract_like(stats),
        shape={
            "popsize": popsize,
            "mesh": dict(mesh.shape),
            "obs_norm": observation_normalization,
        },
    )
    check_record(record)
    return scores, record


def fused_kwargs(popsize, observation_normalization=True):
    return dict(
        ask=partial(pgpe_ask, popsize=popsize),
        tell=pgpe_tell,
        popsize=popsize,
        num_episodes=1,
        episode_length=EPISODE_LENGTH,
        observation_normalization=observation_normalization,
        eval_mode="budget",
    )


def fused_leg(env, policy, devices):
    """One generation step and one span on a one-device mesh."""
    mesh = make_mesh({"pop": 1}, devices=devices[:1])
    _, record = generation_step(env, policy, mesh, POPSIZE, "generation_step")
    report("fused.generation_step", **record_facts(record))

    stats = RunningNorm(env.observation_size).stats
    span = make_training_span(env, policy, span=SPAN, mesh=mesh, **fused_kwargs(POPSIZE))
    state = fresh_state(policy.parameter_count)
    keys = jax.random.split(jax.random.key(2), SPAN)
    _, span_scores, _, span_steps, _ = span(state, keys, stats)
    jax.block_until_ready(span_scores)
    check(all_deleted(state), "training span: the donated state was not consumed")
    check(
        np.asarray(span_steps).tolist() == [POPSIZE * EPISODE_LENGTH] * SPAN,
        f"training span: steps {np.asarray(span_steps).tolist()}",
    )
    check(span_scores.shape == (SPAN, POPSIZE), f"training span: {span_scores.shape}")
    check(bool(jnp.isfinite(span_scores).all()), "training span: non-finite scores")
    record = program_ledger.capture(
        "chip_smoke.training_span",
        span,
        abstract_like(fresh_state(policy.parameter_count)),
        keys,
        abstract_like(stats),
        shape={"popsize": POPSIZE, "span": SPAN},
    )
    check_record(record)
    report("fused.training_span", **record_facts(record))


def check_record(record):
    check(record.platform == "tpu", f"ledger platform {record.platform}")
    check(record.flops and record.flops > 0, f"{record.key}: no cost analysis")
    check(record.peak_bytes and record.peak_bytes > 0, f"{record.key}: no memory analysis")
    check(
        record.donation.donated and record.donation.verified,
        f"{record.key}: donation not honoured: {record.donation}",
    )


def record_facts(record):
    return {
        "flops": record.flops,
        "memory": record.memory,
        "donated_params": len(record.donation.donated),
    }


def server_leg(env, policy):
    tenants = 2
    tenant_pop = POPSIZE // tenants
    server = EvalServer(
        env,
        policy,
        slab_size=POPSIZE,
        max_tenants=tenants,
        num_episodes=1,
        episode_length=EPISODE_LENGTH,
    )
    handles = [server.admit(f"tenant{t}") for t in range(tenants)]
    # numpy parameter matrices: what a host-side search hands the server
    values = [
        np.asarray(
            0.1 * jax.random.normal(
                jax.random.key(10 + t), (tenant_pop, policy.parameter_count)
            ),
            dtype=np.float32,
        )
        for t in range(tenants)
    ]
    keys = [jax.random.key(20 + t) for t in range(tenants)]

    def served_round():
        futures = [
            server.submit(handles[t], values[t], key=keys[t]) for t in range(tenants)
        ]
        server.drain()
        return [np.asarray(future.result().scores) for future in futures]

    first = served_round()
    with track_compiles() as log:
        second = served_round()
    check(log.count == 0, f"server: the second round compiled: {log.names}")

    @jax.jit
    def standalone(rows, key):
        return run_vectorized_rollout(
            env, policy, rows, key, None,
            eval_mode="episodes_refill",
            num_episodes=1,
            episode_length=EPISODE_LENGTH,
            telemetry=True,
        ).scores

    for t in range(tenants):
        want = np.asarray(standalone(values[t], keys[t]))
        check(np.isfinite(want).all(), f"server: tenant {t} standalone scores not finite")
        for round_scores in (first, second):
            check(
                np.array_equal(round_scores[t], want),
                f"server: tenant {t} packed scores differ from the standalone"
                " evaluation of the same rows and keys",
            )
    report(
        "server",
        tenants=tenants,
        requests=2 * tenants,
        dispatches=server.dispatches,
        occupancy=round(server.occupancy(), 4),
    )


def device_peaks(devices):
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


def compare(name, got, want, *, lanes_are_independent):
    """Sharded scores ``got`` against the one-device ``want``, to the
    tolerance stated at the top of the file."""
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want)))
    diff = np.abs(got - want)
    percent = np.linspace(1, 99, 99)
    facts = {
        "bit_identical": bool(np.array_equal(got, want)),
        "lanes_identical": int(np.count_nonzero(diff == 0)),
        "abs_diff_quantiles_50_90_99_100": [
            float(q) for q in np.quantile(diff, [0.5, 0.9, 0.99, 1.0])
        ],
        "correlation": float(np.corrcoef(got, want)[0, 1]),
        "mean_diff": float(abs(got.mean() - want.mean())),
        "quantile_diff": float(
            np.max(np.abs(np.percentile(got, percent) - np.percentile(want, percent)))
        ),
        "score_scale": scale,
    }
    if lanes_are_independent:
        agrees = facts["lanes_identical"] >= MESH_MIN_IDENTICAL_SHARE * got.size
    else:
        agrees = (
            facts["mean_diff"] <= MESH_MEAN_RTOL * scale
            and facts["quantile_diff"] <= MESH_QUANTILE_RTOL * scale
        )
    check(agrees, f"{name}: scores differ from the one-device run: {facts}")
    return facts


def mesh_leg(env, policy, devices, trainer_reference):
    """The same path over every device jax sees. The generation steps run
    without observation normalisation, each against the one-device run of
    the same key and popsize, lane by lane; the trainer runs the flagship
    against the trainer leg's first budget generation (same seed), as a
    population. The steps come first so that the devices' memory high-water
    marks, which only ever rise, can be read leg by leg."""
    n = len(devices)
    peaks = partial(device_peaks, devices)
    layouts = [({"pop": n}, f"pop{n}")]
    if n % 2 == 0 and n > 2:
        layouts.append(({"pop": n // 2, "model": 2}, f"pop{n // 2}.model2"))
    # the one-device run at the same popsize pads nothing, so it is also the
    # reference for the pad-and-mask path (10,002 divides by neither 4 nor 2)
    for popsize, suffix in ((POPSIZE, ""), (PADDED_POPSIZE, ".padded")):
        want, base = generation_step(
            env, policy, make_mesh({"pop": 1}, devices=devices[:1]), popsize,
            f"generation_step.pop1{suffix}", observation_normalization=False,
        )
        for mesh_shape, label in layouts:
            scores, record = generation_step(
                env, policy, make_mesh(mesh_shape), popsize,
                f"generation_step.{label}{suffix}", observation_normalization=False,
            )
            report(
                f"mesh.generation_step.{label}{suffix}",
                popsize=popsize,
                **compare(label + suffix, scores, want, lanes_are_independent=True),
                per_device_memory=record.memory,
                one_device_memory=base.memory,
                peaks=peaks(),
            )

    # one more warm-up generation than on one device: the first sharded
    # update hands back a distribution laid out over the mesh, and the
    # update programs specialise once more to that layout
    _, status, first_evals = trainer("budget", warmup=3, num_actors="max")
    report(
        "mesh.trainer",
        mesh={"pop": n},
        **status,
        **compare(
            "VecNE(num_actors='max')", first_evals, trainer_reference,
            lanes_are_independent=False,
        ),
        peaks=peaks(),
    )
    check(all(peak > 0 for peak in peaks()), f"an idle device: {peaks()}")


# ---------------------------------------------------------------------------


def main():
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"device": device}), flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax found {device}")
    check(peak_flops(devices[0]) > 0, "no published peak for this device")
    print(json.dumps({"compile_cache_dir": enable_persistent_cache()}), flush=True)

    env = make_env(ENV_NAME)
    policy = FlatParamsPolicy(tanh_mlp(env.observation_size, env.action_size, HIDDEN))
    check(policy.parameter_count == 12_305, f"parameter count {policy.parameter_count}")

    sentinel_leg()
    kernels_leg()
    trainer_reference = trainer_leg()
    fused_leg(env, policy, devices)
    server_leg(env, policy)
    if len(devices) > 1:
        mesh_leg(env, policy, devices, trainer_reference)
    report("done", peaks=device_peaks(devices))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
