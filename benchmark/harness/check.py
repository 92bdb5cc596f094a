"""The comparison that decides ``correct``.

Three parts, all outside the measured window:

1. counts (``check_counts``, called by the harness for every cell): every
   ``generation()`` call of the window counted exactly what the session says
   one call counts (``session.per_call``), every evaluation was finite, the
   telemetry agrees with the searcher's status, and nothing compiled inside
   the window;
2. a policy forward against a plain one (``forward_against_reference``);
3. an eval contract against a plain rollout (``contract_against_reference``).

Parts 2 and 3 are tools: a driver whose session runs a policy through an
environment calls them from its ``reference_checks`` with the plain forward
its configuration names and the plain rollout its workload's traffic names; a
driver that runs something else brings a comparison of its own.

Tolerances (each measured on the chip, PERF.md Findings PR 22, and written
here with its reason):

- FORWARD_RTOL: relative RMS error of the population-wide forward against the
  float32 "highest" reference, over 256 seeded (parameters, observation)
  pairs with parameters ~ N(0, 0.1) (pre-activations of order 1, so tanh is
  well off its linear part). bfloat16 keeps 8 bits of mantissa: three layers
  of bf16 inputs and outputs measured 4.3e-3 to 4.6e-3 (64 wide) and 4.6e-3 to
  4.8e-3 (256 wide) on the v5e, over a dozen seeds. int8 weights scaled to the
  largest of a layer (error max|w|/254 per weight against bf16's |w|/512)
  give about ten times that, and a dropped layer gives order 1; the bound
  sits at three times the measured bf16 figure. float32 on the chip's default
  matmul precision (bf16 passes) is the same order; a float32 configuration
  is held to the same bound.
- BAND_SIGMAS, BAND_FLOOR: the two sides cannot be compared lane by lane
  (PERF.md PR 21: a changed reduction order decorrelates lanes within 200
  steps of contact dynamics, and the reference draws its own reset noise), so
  the population is compared: the mean score and a figure of episode length
  (steps per episode; under ``budget`` the episodes a lane's budget completed)
  over the same 1,024 parameter vectors. Two means of independent samples of
  one distribution lie within 5 standard errors of their difference but once
  in a million runs; the floor of 1% of the reference's mean keeps the band
  open where lanes barely differ. What the band was and how far apart the
  sides lay on the v5e is in PERF.md, Findings PR 22. A contract that scored
  past the first termination, or dropped the trailing episode, moves these
  means by tens of percent.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

FORWARD_RTOL = 1.5e-2
BAND_SIGMAS = 5.0
BAND_FLOOR = 0.01


def band(reference_mean, standard_error):
    """How far apart two sample means of one distribution may lie, given the
    standard error of their difference."""
    return max(BAND_SIGMAS * standard_error, BAND_FLOOR * abs(reference_mean))


def check_counts(marks, compiles, per_call):
    """One verdict per ``generation()`` call of the window. ``marks[0]`` is the
    mark taken before the window's first call; ``compiles[i]`` the compilations
    seen during call ``i``. ``per_call`` is the session's statement of what one
    call counts: ``interactions`` and ``episodes`` (exact, or None where the
    contract fixes no exact figure), ``interactions_max`` (the most it may
    count), ``telemetry_lag`` (the mark that carries call ``i``'s decoded
    telemetry is mark ``i + 1 + lag``). Returns (failed call indices, facts)."""
    lag = int(per_call["telemetry_lag"])
    failed = []
    telemetry_checked = 0
    executed = counted = 0
    steps_by_call, capacity_by_call = [], []
    for i in range(len(marks) - 1):
        before, after = marks[i], marks[i + 1]
        steps = after["interactions"] - before["interactions"]
        episodes = after["episodes"] - before["episodes"]
        steps_by_call.append(steps)
        ok = after["finite"] and compiles[i] == 0
        ok = ok and 0 < steps <= per_call["interactions_max"]
        if per_call["interactions"] is not None:
            ok = ok and steps == per_call["interactions"]
        if per_call["episodes"] is not None:
            ok = ok and episodes == per_call["episodes"]
        at = i + 1 + lag
        decoded = at < len(marks) and marks[at]["telemetry"] is not None
        capacity_by_call.append(marks[at]["telemetry"]["capacity"] if decoded else None)
        if decoded:
            telemetry = marks[at]["telemetry"]
            telemetry_checked += 1
            ok = ok and telemetry["env_steps"] == steps and telemetry["nonfinite"] == 0
            if per_call["episodes"] is not None:
                ok = ok and telemetry["episodes"] == episodes
            counted += telemetry["env_steps"]
            executed += telemetry["capacity"]
        if not ok:
            failed.append(i)
    facts = {
        "calls": len(marks) - 1,
        "interactions": marks[-1]["interactions"] - marks[0]["interactions"],
        "interactions_by_call": steps_by_call,
        "episodes": marks[-1]["episodes"] - marks[0]["episodes"],
        "telemetry_checked": telemetry_checked,
        "occupancy": counted / executed if executed else None,
        # lane-step slots each call's loop executed; None where its telemetry
        # came after the window
        "capacity_by_call": capacity_by_call,
        "compiles_in_window": int(sum(compiles)),
    }
    return failed, facts


def forward_against_reference(policy, dtype, reference, sizes, pairs, seed):
    """``policy`` (the library's, ``policy(flat, observation) -> (action, state)``)
    at the compute ``dtype`` (None: float32) against ``reference.forward`` on
    ``pairs`` seeded (parameters, observation) pairs."""
    k_params, k_obs = jax.random.split(jax.random.key(seed))
    params = 0.1 * jax.random.normal(k_params, (pairs, policy.parameter_count), jnp.float32)
    obs = jax.random.normal(k_obs, (pairs, sizes[0]), jnp.float32)

    @jax.jit
    def system(params, obs):
        if dtype is not None:
            params, obs = params.astype(dtype), obs.astype(dtype)
        out, _ = jax.vmap(lambda p, o: policy(p, o))(params, obs)
        return out.astype(jnp.float32)

    @jax.jit
    def plain(params, obs):
        return jax.vmap(lambda p, o: reference.forward(p, o, sizes))(params, obs)

    got = np.asarray(system(params, obs), dtype=np.float64)
    want = np.asarray(plain(params, obs), dtype=np.float64)
    error = float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2)))
    return {
        "ok": bool(got.shape == want.shape and np.isfinite(got).all() and error <= FORWARD_RTOL),
        "relative_rms_error": error,
        "bound": FORWARD_RTOL,
        "pairs": pairs,
    }


def contract_against_reference(evaluate, rollout, values, *, contract, episode_length, seed):
    """The system's eval contract (``evaluate(values) -> {scores, interactions,
    episodes}``, normalisation off) against the plain ``rollout(values, keys)
    -> scores, steps, episodes`` on the same parameter vectors: counts exact,
    population means in band. ``contract`` is the plain rollout's name for the
    semantics, ``budget`` or ``episodes``."""
    lanes = int(values.shape[0])
    max_t = int(episode_length)
    started = time.perf_counter()
    system = evaluate(values)
    system_s = time.perf_counter() - started

    keys = jax.random.split(jax.random.key(seed), lanes)
    scores, steps, episodes = rollout(values, keys)
    scores = np.asarray(scores, dtype=np.float64)
    reference_s = time.perf_counter() - started - system_s
    ref_steps, ref_episodes = int(np.sum(steps)), int(np.sum(episodes))

    if contract == "budget":
        counts_ok = system["interactions"] == ref_steps == lanes * max_t
        # how long episodes were shows in how many a lane's budget completed:
        # one, and rarely more, so the extra ones are counts of rare events
        # whose difference has the standard error sqrt(sum of the counts)
        figure = "completed episodes per lane"
        sys_length, ref_length = system["episodes"] / lanes, ref_episodes / lanes
        extra = max(system["episodes"] - lanes, 0) + max(ref_episodes - lanes, 0)
        length_error = float(np.sqrt(extra)) / lanes
    else:
        counts_ok = (
            system["episodes"] == ref_episodes == lanes
            and 0 < system["interactions"] <= lanes * max_t
        )
        figure = "steps per episode"
        sys_length, ref_length = system["interactions"] / lanes, ref_steps / lanes
        # the system reports totals only: the reference's spread stands for both
        length_error = float(np.sqrt(2.0 / lanes) * np.std(np.asarray(steps, dtype=np.float64)))
    sys_mean, ref_mean = float(np.mean(system["scores"])), float(np.mean(scores))
    score_error = float(np.sqrt((np.var(system["scores"]) + np.var(scores)) / lanes))
    score_band = band(ref_mean, score_error)
    length_band = band(ref_length, length_error)
    score_diff = abs(sys_mean - ref_mean)
    length_diff = abs(sys_length - ref_length)
    return {
        "ok": bool(
            counts_ok
            and np.isfinite(system["scores"]).all()
            and np.isfinite(scores).all()
            and score_diff <= score_band
            and length_diff <= length_band
        ),
        "lanes": lanes,
        "seconds": {"system": system_s, "reference": reference_s},
        "counts_ok": bool(counts_ok),
        "system": {
            "mean_score": sys_mean,
            "std_score": float(np.std(system["scores"])),
            "length_figure": sys_length,
            "interactions": system["interactions"],
            "episodes": system["episodes"],
        },
        "reference": {
            "mean_score": ref_mean,
            "std_score": float(np.std(scores)),
            "length_figure": ref_length,
            "interactions": ref_steps,
            "episodes": ref_episodes,
        },
        "length_figure": figure,
        "score_difference": score_diff,
        "length_difference": length_diff,
        "bands": {"score": score_band, "length": length_band},
    }
