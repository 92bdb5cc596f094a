"""Benchmark: PGPE + fully-vectorized neuroevolution rollout throughput.

Runs on the TPU (an accelerator is required unless ``JAX_PLATFORMS=cpu``
asks for the CPU, where the numbers check correctness and counts, not speed)
and prints a single JSON line to stdout, whose ``backend`` field is the
device as jax reports it. Metric: environment steps per second through the flagship
path — ``run_vectorized_rollout`` (one jitted program containing the whole
population x env x time loop) driven by PGPE, popsize 10k, MLP policy on the
pure-JAX Humanoid locomotion env (17 actuated DOF, 109-dim obs, contact
dynamics on an 11-body maximal-coordinates sim — the Humanoid-class flagship
matching the reference's Brax Humanoid north star; see BASELINE.md:
>1M env-steps/sec). ``BENCH_ENV`` selects any registered env
(e.g. ``hopper`` reproduces the round-1 SLIP-hopper numbers).

ALL FOUR evaluation contracts are measured every run (VERDICT r2 #1): the
throughput-optimal ``budget`` contract and the reference's own ``episodes``
contract three ways — monolithic (paid in full), through the lane-compacting
runner (``episodes_compact``), and through the work-conserving lane-refill
scheduler (``episodes_refill``, continuous batching). ``BENCH_EVAL_MODE``
picks which one is the line's primary ``value``; ``compaction_speedup`` and
``refill_speedup`` are the in-run A/Bs against monolithic ``episodes``.

``vs_baseline`` = env_steps_per_sec / 1_000_000 (the north-star target).

The line also carries the zero-sync eval telemetry (docs/observability.md):
``occupancy`` (counted interactions / executed lane-step slots, primary
contract; per-mode values inside ``modes``), ``refill_events`` (items the
refill scheduler recycled lanes for) and ``steady_compiles`` (retrace
sentinel count over every timed loop — anything but 0 is a retrace bug).
``BENCH_TELEMETRY=0`` compiles the accumulator-free programs (the overhead
A/B baseline). Each mode also reports ``queue_wait_p50``/``queue_wait_p99``
(decoded from the on-device queue-wait histograms); ``BENCH_GROUPS=G``
round-robins group ids across the population and switches the wire to the
per-group matrix (the per-group accounting overhead shape);
``EVOTORCH_METRICS=path`` streams the line + decoded per-group telemetry +
counter registry through the MetricsHub (JSONL manifest-first, or
Prometheus text with a ``.prom`` suffix).

The SEARCH-HEALTH plane (docs/observability.md "Search health") rides the
same wire: per-mode ``score_mean``/``score_std`` decoded from the on-device
float32 score-statistics block (per-group lists
``score_mean_by_group``/``score_std_by_group`` at ``BENCH_GROUPS>1``), with
the primary contract's pair hoisted top-level — what ``slo --check-bench
--max-score-collapse`` / ``--min-score-snr`` read. ``BENCH_HEALTH=0``
compiles the health-free (schema v3) programs — both the overhead A/B
baseline and the byte-compat escape hatch.

The program LEDGER (docs/observability.md "Program ledger") adds, per
contract and hoisted top-level for the primary one: ``compile_seconds``
(AOT compile wall-time of the contract's program), ``flops_per_step``
(cost-model FLOPs per counted env-step), ``peak_hbm_bytes`` (analyzed peak
footprint — donation-aware, a dropped ``donate_argnums`` inflates it) and
``model_efficiency`` (MFU-style: achieved MODEL FLOP rate —
2 x param_count useful FLOPs per counted env-step — vs the device's
published peak, observability.report.DEVICE_PEAKS; null on the CPU; see
bench_common.ledger_columns for why the cost-model FLOPs are NOT the
numerator). ``BENCH_LEDGER=0`` skips the capture
(one extra untimed trace+compile per contract) and keeps the line
byte-compatible with pre-ledger rounds.

The refill / compaction schedules resolve through the TUNED-CONFIG cache
(docs/observability.md "The autotuner"): explicit ``BENCH_REFILL_*`` /
``BENCH_COMPACT_*`` knobs override, else a cache hit for this
(env, popsize, episode length/count, params, dtype, machine) applies the autotuner's measured winner, else the
engine defaults. The line carries ``tuned_config_source``
(override / cache / fallback; per-contract copies and the effective
refill width/period inside ``modes``). ``BENCH_TUNED=0`` disables both
the consult and the new keys — the line is then byte-compatible with
r9/r10 output.

``BENCH_TRUNK_DELTA=1`` evaluates the shared-trunk + per-lane
low-rank-delta policy form (docs/policies.md) through all four contracts
and ALSO times an interleaved dense-vs-trunk-delta A/B of the primary
contract (``BENCH_TRUNK_AB_REPEATS`` samples each, default 3, medians):
``trunk_delta_speedup`` / ``dense_value`` land on the line together with
the effective ``trunk_rank`` / ``trunk_block`` (explicit
``BENCH_TRUNK_RANK`` / ``BENCH_TRUNK_BLOCK`` override, else the tuned
``policy`` group's winner for this shape, else rank 4 unblocked). With the
ledger on, every line also self-describes with ``hidden`` /
``param_count`` / ``policy_form`` (dense / lowrank / trunk_delta).

``BENCH_SERVE=1`` runs the multi-tenant SERVING A/B (evotorch_tpu/serving,
docs/serving.md): ``BENCH_SERVE_TENANTS`` (default 4) concurrent searches,
each popsize/T solutions per generation, packed through ONE
``EvalServer``'s resident ``episodes_refill`` program vs the same searches
dispatched sequentially standalone — interleaved median of
``BENCH_SERVE_AB_REPEATS`` samples (default 3), per-tenant packed scores
asserted bit-identical to the standalone leg during warmup. Adds
``serve_speedup`` / ``serve_value`` / ``sequential_value`` /
``serve_occupancy`` and the served queue-wait quantiles
(``serve_queue_wait_p50``/``p99``, ``*_by_tenant`` lists — what
``slo --check-bench --max-queue-wait-p99`` reads). Off by default; line
byte-compatible.

The persistent XLA compilation cache is always on
(observability/compilecache.py: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/compile_cache``); the line's ``compile_cache`` block carries
hit/miss counters and cold/warm provenance, so a recorded
``compile_seconds`` can be attributed to a real compile vs a cache
deserialize.

``BENCH_BACKEND=mujoco`` additionally measures the REAL-MuJoCo host path
(``MjVecEnv`` over ``mujoco.rollout``): the PR-2 synchronous fixed-chunk loop
vs the Sebulba-style pipelined refill scheduler, reported as
``mj_sync_steps_per_sec`` / ``mj_steps_per_sec`` / ``mj_pipeline_speedup``
columns on the same JSON line (knobs: ``BENCH_MJ_ENV``, ``BENCH_MJ_POPSIZE``,
``BENCH_MJ_NUM_ENVS``, ``BENCH_MJ_EPISODE_LENGTH``, ``BENCH_MJ_BLOCKS``,
``BENCH_MJ_REPEATS`` — median of N, this box times ±20% run-to-run —
``EVOTORCH_MJ_NTHREAD``). Off by default: the bespoke-sim line is unchanged.
"""

import json
import os
import statistics
import sys
import time
from functools import partial

from bench_common import (
    bench_config,
    bench_hidden,
    build_policy,
    device_record,
    fresh_pgpe_state,
    ledger_columns,
    measure_mujoco,
    setup_backend,
    tuned_compact,
    tuned_policy,
    tuned_refill,
)


def main():
    use_cpu = setup_backend()
    import jax
    import jax.numpy as jnp

    from evotorch_tpu.algorithms.functional import (
        pgpe_ask,
        pgpe_ask_lowrank,
        pgpe_ask_trunk_delta,
        pgpe_tell,
        pgpe_tell_lowrank,
        pgpe_tell_trunk_delta,
    )
    from evotorch_tpu.analysis import track_compiles
    from evotorch_tpu.envs import make_env
    from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
    from evotorch_tpu.neuroevolution.net.vecrl import (
        run_vectorized_rollout,
        run_vectorized_rollout_compacting,
    )
    from evotorch_tpu.observability import (
        GroupTelemetry,
        MetricsHub,
        cache_stats,
        enable_persistent_cache,
    )
    from evotorch_tpu.observability import ledger as program_ledger
    from evotorch_tpu.observability.inventory import capture_compact_chunk
    from evotorch_tpu.observability.programs import abstract_like

    cfg = bench_config(use_cpu)
    # persistent XLA compile cache: a second process deserializes instead of
    # recompiling; the line's `compile_cache` block says which happened
    enable_persistent_cache()
    popsize = cfg["popsize"]
    episode_length = cfg["episode_length"]
    generations = cfg["generations"]
    compute_dtype = cfg["compute_dtype"]
    eval_mode = cfg["eval_mode"]
    lowrank = cfg["lowrank"]
    trunk_delta = cfg["trunk_delta"]
    if trunk_delta and lowrank:
        raise SystemExit("BENCH_TRUNK_DELTA=1 and BENCH_LOWRANK are exclusive")
    env = make_env(cfg["env_name"], **cfg["env_kwargs"])
    policy = build_policy(env)
    trunk_cfg, trunk_src = {}, None
    if trunk_delta:
        # rank / lane blocking resolve like the schedules: explicit
        # BENCH_TRUNK_* knobs override, else the tuned-config cache's
        # `policy` group (autotune --group policy), else rank 4 unblocked
        trunk_cfg, trunk_src = tuned_policy(cfg, params=policy.parameter_count)
        ask = partial(
            pgpe_ask_trunk_delta, rank=trunk_cfg["rank"], policy=policy
        )
        tell = pgpe_tell_trunk_delta
    elif lowrank:
        ask = partial(pgpe_ask_lowrank, rank=lowrank)
        tell = pgpe_tell_lowrank
    else:
        ask, tell = pgpe_ask, pgpe_tell
    print(
        f"devices={jax.devices()} popsize={popsize} params={policy.parameter_count} "
        f"episode_length={episode_length} compute_dtype={compute_dtype or 'float32'}",
        file=sys.stderr,
    )

    stats = RunningNorm(env.observation_size).stats

    # the refill / compaction schedules, resolved ONCE with provenance:
    # explicit BENCH_* knobs override, else (BENCH_TUNED=1, the default) the
    # autotuner's tuned-config cache for this (env, popsize, episode length/count, params, dtype, machine), else
    # the engine defaults (docs/observability.md "The autotuner")
    compact_cfg, compact_src = tuned_compact(cfg, params=policy.parameter_count)
    refill_cfg, refill_src = tuned_refill(cfg, params=policy.parameter_count)

    rollout_kwargs = dict(
        num_episodes=1,
        episode_length=episode_length,
        compute_dtype=compute_dtype,
        telemetry=cfg["telemetry"],
        # BENCH_HEALTH=0: compile the health-plane-free (schema v3)
        # programs — the overhead A/B baseline for the score-statistics
        # block (docs/observability.md "Search health")
        health=cfg["health"],
    )
    num_groups = cfg["num_groups"] if cfg["telemetry"] else 0
    if num_groups > 1:
        # BENCH_GROUPS=G: round-robin group ids over the population — the
        # telemetry wire becomes the per-group (G, 14) matrix, the overhead
        # A/B shape for the segment-summed accounting
        rollout_kwargs["groups"] = jnp.arange(popsize, dtype=jnp.int32) % num_groups
        rollout_kwargs["num_groups"] = num_groups

    def measure_mode(mode, key):
        """Run warmup + ``generations`` timed generations of one contract;
        returns (steps_per_sec, generations_per_sec, key, telemetry,
        steady_compiles). Each mode gets a fresh optimizer state: the jitted
        generation DONATES it (``donate_argnums``), so the ask-tell hot loop
        reuses the state and population buffers in place instead of
        allocating per generation — sharing one state object across modes
        would hand a donated (invalidated) buffer to the next mode's first
        call. The telemetry vector rides out of the same jitted program as
        the scores (zero extra dispatches) and is decoded once, after the
        clock stops; the timed loop runs under the retrace sentinel, so a
        steady-state recompile shows up as a nonzero ``steady_compiles``."""
        state = fresh_pgpe_state(policy.parameter_count)
        if mode == "episodes_compact":
            ask_jit = jax.jit(partial(ask, popsize=popsize))
            # donate the state like the monolithic modes' jitted generation
            # below: tell is state-in/state-out, so the update runs in place
            tell_jit = jax.jit(tell, donate_argnums=(0,))
            ckw = compact_cfg

            def gen(state, key, prewarm=False):
                k1, k2 = jax.random.split(key)
                values = ask_jit(k1, state)
                result = run_vectorized_rollout_compacting(
                    env, policy, values, k2, stats, prewarm=prewarm,
                    **ckw, **rollout_kwargs,
                )
                state = tell_jit(state, values, result.scores)
                return state, result.total_steps, result.scores, result.telemetry

            key, sub = jax.random.split(key)
            state, steps, scores, telemetry = gen(state, sub, prewarm=True)
            jax.block_until_ready(scores)
        else:
            extra = dict(refill_cfg) if mode == "episodes_refill" else {}
            if trunk_delta:
                # static lane-block size of the trunk-delta forward (0 = one
                # block); monolithic modes only — the compacting runner's
                # width descent already rules out a fixed lane blocking
                extra["trunk_block"] = trunk_cfg["trunk_block"]

            def generation(state, key):
                k1, k2 = jax.random.split(key)
                values = ask(k1, state, popsize=popsize)
                result = run_vectorized_rollout(
                    env, policy, values, k2, stats, eval_mode=mode,
                    **extra, **rollout_kwargs,
                )
                state = tell(state, values, result.scores)
                return state, result.total_steps, result.scores, result.telemetry

            # donate the optimizer state: ask/tell and the rollout carry run
            # allocation-free generation to generation
            gen = jax.jit(generation, donate_argnums=(0,))
            key, sub = jax.random.split(key)
            state, steps, scores, telemetry = gen(state, sub)
            jax.block_until_ready(scores)
        print(f"[{mode}] compiled; warmup steps={int(steps)}", file=sys.stderr)

        with track_compiles() as compile_log:
            t0 = time.perf_counter()
            total_steps = 0
            for _ in range(generations):
                key, sub = jax.random.split(key)
                state, steps, scores, telemetry = gen(state, sub)
                jax.block_until_ready(scores)
                total_steps += int(steps)
            elapsed = time.perf_counter() - t0
        gdec = (
            GroupTelemetry.from_array(telemetry) if telemetry is not None else None
        )
        decoded = gdec.total() if gdec is not None else None
        print(
            f"[{mode}] {generations} generations, {total_steps} env-steps in "
            f"{elapsed:.2f}s; mean score {float(jnp.mean(scores)):.3f}"
            + (f"; {decoded.summary()}" if decoded is not None else "")
            + (
                f"; STEADY-STATE COMPILES: {compile_log.names}"
                if compile_log.count
                else ""
            ),
            file=sys.stderr,
        )
        # program ledger (BENCH_LEDGER=1, the default): AOT-capture the
        # contract's compiled program — compile wall-time, cost-model FLOPs,
        # analyzed peak memory, donation verification — OUTSIDE every timed
        # region (lowering on ShapeDtypeStructs, so the donated state is
        # never consumed; costs one extra trace+compile per contract)
        record = None
        if cfg["ledger"]:
            shape = {
                "env": cfg["env_name"],
                "popsize": popsize,
                "episode_length": episode_length,
            }
            if trunk_delta:
                shape["rank"] = trunk_cfg["rank"]
            if mode == "episodes_compact" and trunk_delta:
                # capture_compact_chunk builds a DENSE params batch — its
                # record would mislabel the trunk-delta chunk program's
                # FLOPs/memory, so the compact columns stay null here
                record = None
            elif mode == "episodes_compact":
                record = capture_compact_chunk(
                    program_ledger, env, policy, popsize, episode_length,
                    chunk_size=ckw["chunk_size"],
                    compute_dtype=compute_dtype,
                    telemetry=cfg["telemetry"],
                    name="bench.compact_chunk",
                    shape=dict(shape, chunk=ckw["chunk_size"]),
                )
            else:
                record = program_ledger.capture(
                    f"bench.generation[{mode}]",
                    gen,
                    abstract_like(fresh_pgpe_state(policy.parameter_count)),
                    jax.random.key(0),
                    shape=shape,
                )
        return (
            total_steps / elapsed,
            generations / elapsed,
            key,
            gdec,
            compile_log.count,
            record,
        )

    key = jax.random.key(0)
    modes = {}
    # ALL FOUR contracts, every run (VERDICT r3 weak #3): budget (the
    # throughput-optimal contract), monolithic episodes (the reference's
    # contract, paid in full), episodes_compact (lane compaction) and
    # episodes_refill (the work-conserving refill scheduler) — so both
    # episodes-contract optimizations are in-run A/Bs against the monolith
    all_modes = [eval_mode] + [
        m
        for m in ("budget", "episodes", "episodes_compact", "episodes_refill")
        if m != eval_mode
    ]
    telemetry_by_mode = {}
    group_telemetry_by_mode = {}
    steady_compiles = 0
    for mode in all_modes:
        sps, gps, key, mode_groups, mode_compiles, record = measure_mode(
            mode, key
        )
        mode_telemetry = mode_groups.total() if mode_groups is not None else None
        telemetry_by_mode[mode] = mode_telemetry
        group_telemetry_by_mode[mode] = mode_groups
        steady_compiles += mode_compiles
        modes[mode] = {
            "value": round(sps, 1),
            "vs_baseline": round(sps / 1_000_000, 4),
            "generations_per_sec": round(gps, 3),
        }
        if mode_telemetry is not None:
            modes[mode]["occupancy"] = round(mode_telemetry.occupancy, 4)
            # queue-wait tail decoded from the on-device histograms — refill
            # is the only contract whose lanes wait, so the other modes read
            # 0.0 (absent entirely under BENCH_TELEMETRY=0)
            modes[mode]["queue_wait_p50"] = mode_groups.queue_wait_quantile(0.5)
            modes[mode]["queue_wait_p99"] = mode_groups.queue_wait_quantile(0.99)
        if mode_groups is not None and mode_groups.has_health:
            # search-health plane (schema v4): the contract's score
            # statistics, decoded from the same wire — absent entirely
            # under BENCH_HEALTH=0 so those lines stay byte-compatible
            # NOT named `stats`: that local is the RunningNorm stats every
            # rollout closure reads — shadowing it here hands a dict to the
            # next mode's trace
            sstats = mode_groups.score_stats()
            if sstats["count"] > 0:
                modes[mode]["score_mean"] = round(sstats["mean"], 6)
                modes[mode]["score_std"] = round(sstats["std"], 6)
            if mode_groups.num_groups > 1:
                rows = mode_groups.to_rows()
                modes[mode]["score_mean_by_group"] = [
                    round(r["score_mean"], 6) for r in rows
                ]
                modes[mode]["score_std_by_group"] = [
                    round(r["score_std"], 6) for r in rows
                ]
        if record is not None:
            # the compact record covers ONE full-width chunk, not a whole
            # generation: its per-step denominator is the chunk's executed
            # lane-step slots (docs/observability.md "Program ledger")
            if mode == "episodes_compact":
                steps_per_gen = compact_cfg["chunk_size"] * popsize
                modes[mode].update(
                    ledger_columns(
                        record,
                        steps_per_sec=sps,
                        steps_per_generation=steps_per_gen,
                        param_count=policy.parameter_count,
                    )
                )
            else:
                modes[mode].update(
                    ledger_columns(
                        record,
                        steps_per_sec=sps,
                        steps_per_generation=(sps / gps if gps else None),
                        param_count=policy.parameter_count,
                    )
                )

    trunk_ab = {}
    if trunk_delta:
        # BENCH_TRUNK_DELTA=1: the headline policy-form A/B — dense per-lane
        # vs shared-trunk + delta on the primary contract (budget when the
        # primary is the host-orchestrated compact runner), INTERLEAVED
        # median-of-N samples (this box times ±20% run-to-run;
        # BENCH_TRUNK_AB_REPEATS, default 3). Both programs compile once,
        # outside every timed loop, and run under the retrace sentinel.
        ab_mode = eval_mode if eval_mode != "episodes_compact" else "budget"
        ab_extra = dict(refill_cfg) if ab_mode == "episodes_refill" else {}
        # the dense leg ignores trunk_block (net/vecrl.py _forward_ctx)
        ab_extra["trunk_block"] = trunk_cfg["trunk_block"]

        def build_ab_gen(ask_fn, tell_fn):
            def generation(state, key):
                k1, k2 = jax.random.split(key)
                values = ask_fn(k1, state)
                result = run_vectorized_rollout(
                    env, policy, values, k2, stats, eval_mode=ab_mode,
                    **ab_extra, **rollout_kwargs,
                )
                state = tell_fn(state, values, result.scores)
                return state, result.total_steps, result.scores

            return jax.jit(generation, donate_argnums=(0,))

        ab_runs = {}
        for form, ask_fn, tell_fn in (
            ("dense", lambda k, s: pgpe_ask(k, s, popsize=popsize), pgpe_tell),
            ("trunk_delta", lambda k, s: ask(k, s, popsize=popsize), tell),
        ):
            gen_ab = build_ab_gen(ask_fn, tell_fn)
            st = fresh_pgpe_state(policy.parameter_count)
            key, sub = jax.random.split(key)
            st, _, scores = gen_ab(st, sub)
            jax.block_until_ready(scores)
            ab_runs[form] = {"gen": gen_ab, "state": st, "samples": []}
        ab_repeats = int(os.environ.get("BENCH_TRUNK_AB_REPEATS", "3"))
        for _ in range(ab_repeats):
            for form, run in ab_runs.items():
                gen_ab, st = run["gen"], run["state"]
                with track_compiles() as compile_log:
                    t0 = time.perf_counter()
                    sample_steps = 0
                    for _ in range(generations):
                        key, sub = jax.random.split(key)
                        st, steps, scores = gen_ab(st, sub)
                        jax.block_until_ready(scores)
                        sample_steps += int(steps)
                    elapsed = time.perf_counter() - t0
                steady_compiles += compile_log.count
                run["state"] = st
                run["samples"].append(sample_steps / elapsed)
        med = {f: statistics.median(r["samples"]) for f, r in ab_runs.items()}
        print(
            f"[trunk_ab/{ab_mode}] {ab_repeats} interleaved samples: dense "
            f"{med['dense']:.0f} vs trunk_delta {med['trunk_delta']:.0f} "
            f"steps/s ({med['trunk_delta'] / med['dense']:.2f}x)",
            file=sys.stderr,
        )
        trunk_ab = {
            "dense_value": round(med["dense"], 1),
            "trunk_delta_speedup": round(med["trunk_delta"] / med["dense"], 3),
            "trunk_ab_mode": ab_mode,
        }

    span_ab = {}
    span_record = None
    if cfg["span"] is not None:
        # BENCH_SPAN: the fused-span headline A/B — K generations scanned
        # into ONE donated GSPMD program (parallel.make_training_span) vs
        # the SAME generation body dispatched K times from the host loop
        # (parallel.make_generation_step, same default mesh), on the primary
        # contract (budget when the primary is the host-orchestrated compact
        # runner, which cannot be fused). INTERLEAVED median-of-N samples of
        # one span each (BENCH_SPAN_AB_REPEATS, default 3); both programs
        # warm up TWICE before the clock — with donation the first call
        # compiles the fresh-layout program and the second the steady-state
        # layout-committed one — and every timed loop runs under the retrace
        # sentinel.
        from bench_common import tuned_span
        from evotorch_tpu.parallel import (
            default_mesh,
            make_generation_step,
            make_training_span,
        )

        span_k, span_src = tuned_span(cfg, params=policy.parameter_count)
        span_ab_mode = eval_mode if eval_mode != "episodes_compact" else "budget"
        span_kwargs = dict(rollout_kwargs)
        span_kwargs["eval_mode"] = span_ab_mode
        if span_ab_mode == "episodes_refill":
            span_kwargs.update(refill_cfg)
        if trunk_delta:
            span_kwargs["trunk_block"] = trunk_cfg["trunk_block"]
        span_mesh = default_mesh(("pop",))

        def span_ask(k, s):
            return ask(k, s, popsize=popsize)

        gen_step = make_generation_step(
            env, policy, ask=span_ask, tell=tell, popsize=popsize,
            mesh=span_mesh, **span_kwargs,
        )
        span_fn = make_training_span(
            env, policy, ask=span_ask, tell=tell, popsize=popsize,
            span=span_k, mesh=span_mesh, **span_kwargs,
        )
        ab_stats = RunningNorm(env.observation_size).stats

        def host_sample(state, key):
            steps_total = 0
            out = None
            for _ in range(span_k):
                key, sub = jax.random.split(key)
                state, scores, _, steps, _ = gen_step(state, sub, ab_stats)
                steps_total += int(steps)
                out = scores
            jax.block_until_ready(out)
            return state, key, steps_total

        def span_sample(state, key):
            key, sub = jax.random.split(key)
            state, scores, _, steps, _ = span_fn(
                state, jax.random.split(sub, span_k), ab_stats
            )
            jax.block_until_ready(scores)
            return state, key, int(steps.sum())

        span_runs = {}
        for leg, sampler in (("hostloop", host_sample), ("span", span_sample)):
            st = fresh_pgpe_state(policy.parameter_count)
            key, leg_key = jax.random.split(key)
            st, leg_key, _ = sampler(st, leg_key)  # compile (fresh layout)
            st, leg_key, _ = sampler(st, leg_key)  # steady-state layout
            span_runs[leg] = {
                "sampler": sampler, "state": st, "key": leg_key, "samples": [],
            }
        for _ in range(cfg["span_ab_repeats"]):
            for leg, run in span_runs.items():
                with track_compiles() as compile_log:
                    t0 = time.perf_counter()
                    run["state"], run["key"], sample_steps = run["sampler"](
                        run["state"], run["key"]
                    )
                    elapsed = time.perf_counter() - t0
                steady_compiles += compile_log.count
                run["samples"].append(sample_steps / elapsed)
                run["steps"] = sample_steps
        med_span = {
            leg: statistics.median(r["samples"]) for leg, r in span_runs.items()
        }
        print(
            f"[span_ab/{span_ab_mode}] span={span_k}, "
            f"{cfg['span_ab_repeats']} interleaved samples: hostloop "
            f"{med_span['hostloop']:.0f} vs span {med_span['span']:.0f} "
            f"steps/s ({med_span['span'] / med_span['hostloop']:.2f}x)",
            file=sys.stderr,
        )
        span_ab = {
            "span": span_k,
            "span_speedup": round(med_span["span"] / med_span["hostloop"], 3),
            "span_value": round(med_span["span"], 1),
            "hostloop_value": round(med_span["hostloop"], 1),
            "span_ab_mode": span_ab_mode,
        }
        if cfg["tuned"]:
            span_ab["span_config_source"] = span_src
        if cfg["ledger"]:
            # AOT-capture the span program itself (outside every timed
            # region; the key array must be concrete — lowering folds it)
            span_record = program_ledger.capture(
                "bench.training_span",
                span_fn,
                abstract_like(fresh_pgpe_state(policy.parameter_count)),
                jax.random.split(jax.random.key(0), span_k),
                abstract_like(ab_stats),
                shape={
                    "env": cfg["env_name"],
                    "popsize": popsize,
                    "episode_length": episode_length,
                    "span": span_k,
                },
            )

    serve_ab = {}
    if cfg["serve"]:
        # BENCH_SERVE=1: the multi-tenant serving A/B (docs/serving.md) —
        # BENCH_SERVE_TENANTS concurrent searches, each popsize/T solutions
        # per generation, packed through ONE EvalServer's resident
        # episodes_refill program (the telemetry group id is the tenant id)
        # vs the SAME searches dispatched sequentially standalone. The
        # warmup round asserts per-tenant packed scores bit-identical to
        # the standalone leg (same work — the speedup is pure packing and
        # dispatch amortization). INTERLEAVED median-of-N samples
        # (BENCH_SERVE_AB_REPEATS, default 3); both legs warm twice before
        # the clock and every timed loop runs under the retrace sentinel —
        # per-generation submits re-dispatch the resident program, so any
        # steady-state compile is a retrace bug.
        import numpy as np

        from evotorch_tpu.serving import EvalServer

        serve_tenants = cfg["serve_tenants"]
        tenant_pop = max(1, popsize // serve_tenants)
        server = EvalServer(
            env,
            policy,
            slab_size=tenant_pop * serve_tenants,
            max_tenants=serve_tenants,
            refill_width=refill_cfg.get("refill_width"),
            refill_period=refill_cfg.get("refill_period") or 1,
            num_episodes=1,
            episode_length=episode_length,
            compute_dtype=compute_dtype,
            health=cfg["health"],
        )
        handles = [server.admit(f"bench{t}") for t in range(serve_tenants)]
        key, vkey, skey = jax.random.split(key, 3)
        # numpy parameter matrices: what a host-side search hands the
        # server (and ~3x cheaper per jitted dispatch than device arrays)
        tenant_values = [
            np.asarray(
                jax.random.normal(
                    jax.random.fold_in(vkey, t),
                    (tenant_pop, policy.parameter_count),
                ),
                dtype=np.float32,
            )
            for t in range(serve_tenants)
        ]
        tenant_keys = [jax.random.fold_in(skey, t) for t in range(serve_tenants)]

        def standalone_run(values, k):
            result = run_vectorized_rollout(
                env, policy, values, k, None,
                eval_mode="episodes_refill",
                num_episodes=1,
                episode_length=episode_length,
                compute_dtype=compute_dtype,
                telemetry=True,
                health=cfg["health"],
            )
            return result.scores, result.total_steps

        standalone_fn = jax.jit(standalone_run)

        def serve_sample():
            futures = [
                server.submit(handles[t], tenant_values[t], key=tenant_keys[t])
                for t in range(serve_tenants)
            ]
            server.drain()
            results = [f.result() for f in futures]
            steps = sum(int(r.total_steps) for r in results)
            return steps, [np.asarray(r.scores) for r in results]

        def sequential_sample():
            steps = 0
            all_scores = []
            for t in range(serve_tenants):
                scores, st = standalone_fn(tenant_values[t], tenant_keys[t])
                jax.block_until_ready(scores)
                steps += int(st)
                all_scores.append(np.asarray(scores))
            return steps, all_scores

        serve_runs = {"serve": serve_sample, "sequential": sequential_sample}
        warm_scores = {}
        for leg, sampler in serve_runs.items():
            sampler()  # compile
            _, warm_scores[leg] = sampler()  # steady state
        for t in range(serve_tenants):
            if not np.array_equal(
                warm_scores["serve"][t], warm_scores["sequential"][t]
            ):
                raise SystemExit(
                    f"serve A/B: tenant {t} packed scores diverged from the"
                    " standalone leg — tenant isolation bug"
                )
        serve_samples = {leg: [] for leg in serve_runs}
        for _ in range(cfg["serve_ab_repeats"]):
            for leg, sampler in serve_runs.items():
                with track_compiles() as compile_log:
                    t0 = time.perf_counter()
                    sample_steps, _ = sampler()
                    elapsed = time.perf_counter() - t0
                steady_compiles += compile_log.count
                serve_samples[leg].append(sample_steps / elapsed)
        med_serve = {
            leg: statistics.median(s) for leg, s in serve_samples.items()
        }
        print(
            f"[serve_ab] {serve_tenants} tenants x {tenant_pop},"
            f" {cfg['serve_ab_repeats']} interleaved samples: sequential"
            f" {med_serve['sequential']:.0f} vs served"
            f" {med_serve['serve']:.0f} steps/s"
            f" ({med_serve['serve'] / med_serve['sequential']:.2f}x),"
            f" occupancy {server.occupancy():.3f}",
            file=sys.stderr,
        )
        tenant_rows = [h.telemetry for h in handles]
        merged_row = tenant_rows[0]
        for row in tenant_rows[1:]:
            merged_row = merged_row + row
        serve_ab = {
            "serve_tenants": serve_tenants,
            "serve_speedup": round(
                med_serve["serve"] / med_serve["sequential"], 3
            ),
            "serve_value": round(med_serve["serve"], 1),
            "sequential_value": round(med_serve["sequential"], 1),
            "serve_occupancy": round(server.occupancy(), 4),
            "serve_queue_wait_p50": merged_row.queue_wait_quantile(0.5),
            "serve_queue_wait_p99": merged_row.queue_wait_quantile(0.99),
            "serve_queue_wait_p50_by_tenant": [
                row.queue_wait_quantile(0.5) for row in tenant_rows
            ],
            "serve_queue_wait_p99_by_tenant": [
                row.queue_wait_quantile(0.99) for row in tenant_rows
            ],
        }

    primary = modes[eval_mode]
    # the episodes-contract headline is the best runner of that contract
    episodes_runners = [
        m
        for m in ("episodes", "episodes_compact", "episodes_refill")
        if m in modes
    ]
    episodes_key = max(episodes_runners, key=lambda m: modes[m]["value"])

    def speedup_vs_episodes(mode):
        if mode not in modes or modes.get("episodes", {}).get("value", 0) <= 0:
            return None
        return round(modes[mode]["value"] / modes["episodes"]["value"], 3)

    line = {
        "metric": "pgpe_vectorized_rollout_env_steps_per_sec",
        "value": primary["value"],
        "unit": "env_steps/sec",
        "vs_baseline": primary["vs_baseline"],
        "generations_per_sec": primary["generations_per_sec"],
        "episodes_mode_value": modes[episodes_key]["value"],
        "episodes_mode_vs_baseline": modes[episodes_key]["vs_baseline"],
        "compaction_speedup": speedup_vs_episodes("episodes_compact"),
        "refill_speedup": speedup_vs_episodes("episodes_refill"),
        # on-device eval telemetry (observability.devicemetrics): the primary
        # contract's occupancy, the refill scheduler's refill/wait accounting,
        # and the retrace sentinel's steady-state compile count across every
        # timed loop (anything but 0 is a retrace bug)
        "occupancy": (
            round(telemetry_by_mode[eval_mode].occupancy, 4)
            if telemetry_by_mode.get(eval_mode) is not None
            else None
        ),
        "refill_events": (
            telemetry_by_mode["episodes_refill"].refill_events
            if telemetry_by_mode.get("episodes_refill") is not None
            else None
        ),
        "steady_compiles": steady_compiles,
        "modes": modes,
        "env": cfg["env_name"],
        "env_args": cfg["env_kwargs"],
        "popsize": popsize,
        "episode_length": episode_length,
        "eval_mode": eval_mode,
        "lowrank": lowrank,
        "compute_dtype": str(compute_dtype.__name__ if compute_dtype else "float32"),
        "backend": device_record(),
    }
    primary_groups = group_telemetry_by_mode.get(eval_mode)
    if primary_groups is not None and primary_groups.has_health:
        # the primary contract's score statistics hoisted top-level (what
        # `slo --check-bench --max-score-collapse/--min-score-snr` reads);
        # absent entirely under BENCH_HEALTH=0 / BENCH_TELEMETRY=0 so
        # those lines stay byte-compatible
        line["score_mean"] = modes[eval_mode].get("score_mean")
        line["score_std"] = modes[eval_mode].get("score_std")
    if cfg["tuned"]:
        # schedule provenance (absent entirely under BENCH_TUNED=0 so the
        # line stays byte-compatible with pre-autotuner rounds): the
        # headline `tuned_config_source` is the refill contract's — the
        # knob the r8 occupancy readout proved mistuned — with per-contract
        # sources and the EFFECTIVE refill schedule inside `modes`
        from evotorch_tpu.neuroevolution.net.vecrl import _default_refill_width

        line["tuned_config_source"] = refill_src
        modes["episodes_refill"]["tuned_config_source"] = refill_src
        # the EFFECTIVE schedule: on the fallback branch the engine runs
        # its work/8 default width, not "null" — the tuned-vs-fallback A/B
        # needs both lines to say what actually ran
        modes["episodes_refill"]["refill_width"] = refill_cfg.get(
            "refill_width", _default_refill_width(popsize)
        )
        modes["episodes_refill"]["refill_period"] = refill_cfg.get("refill_period")
        modes["episodes_compact"]["tuned_config_source"] = compact_src
    if trunk_delta:
        # BENCH_TRUNK_DELTA=1 only: the policy-form A/B columns and the
        # effective rank / lane blocking (absent by default, so the
        # default line stays byte-compatible)
        line.update(trunk_ab)
        line["trunk_rank"] = trunk_cfg["rank"]
        line["trunk_block"] = trunk_cfg["trunk_block"]
        if cfg["tuned"]:
            line["trunk_config_source"] = trunk_src
    if cfg["serve"]:
        # BENCH_SERVE=1 only: the multi-tenant serving A/B columns
        # (absent by default, so the default line stays byte-compatible)
        line.update(serve_ab)
    if cfg["span"] is not None:
        # BENCH_SPAN only: the fused-span A/B columns (absent by default,
        # so the default line stays byte-compatible with PR-18 output)
        line.update(span_ab)
        if span_record is not None:
            # the span program's own ledger figures: its cost-model FLOPs
            # cover the WHOLE K-generation scan, so the per-step
            # denominator is the span's counted env-steps
            line["span_program"] = ledger_columns(
                span_record,
                steps_per_sec=span_ab["span_value"],
                steps_per_generation=span_runs["span"].get("steps"),
                param_count=policy.parameter_count,
            )
    if cfg["ledger"]:
        # the primary contract's program-ledger figures, hoisted next to
        # `value` (per-contract copies live inside `modes`); absent entirely
        # under BENCH_LEDGER=0 so the line stays byte-compatible
        for column in (
            "compile_seconds",
            "flops_per_step",
            "peak_hbm_bytes",
            "model_efficiency",
        ):
            line[column] = primary.get(column)
        # self-description for bench_curves/ policy-shape sweeps (rides the
        # ledger gate so BENCH_LEDGER=0 lines stay byte-compatible)
        line["hidden"] = bench_hidden()
        line["param_count"] = policy.parameter_count
        line["policy_form"] = (
            "trunk_delta" if trunk_delta else "lowrank" if lowrank else "dense"
        )
    # hit/miss counters from the persistent compile cache plus the derived
    # provenance: "warm" = every program this process compiled was
    # deserialized from the cache (a prior process paid the compiles),
    # "cold" = at least one real compile, "mixed" otherwise
    stats_cc = cache_stats()
    hits, misses = stats_cc["hits"], stats_cc["misses"]
    line["compile_cache"] = {
        "provenance": (
            "warm" if misses == 0 and hits > 0
            else "cold" if hits == 0
            else "mixed"
        ),
        "hits": hits,
        "misses": misses,
        "dir": stats_cc["dir"],
    }
    if cfg["mj_backend"]:
        # BENCH_BACKEND=mujoco: append the real-MuJoCo host-path columns
        # (sync chunked loop vs pipelined refill scheduler over MjVecEnv);
        # off by default so the line above stays byte-compatible
        line.update(measure_mujoco(cfg))
    hub = MetricsHub.from_env(
        manifest={
            "source": "bench",
            "mesh": "none",
            "env": cfg["env_name"],
            "popsize": popsize,
            "num_groups": num_groups,
            "tuned_config_source": line.get("tuned_config_source"),
        }
    )
    if hub is not None:
        # EVOTORCH_METRICS=path: the same line (plus the primary contract's
        # decoded per-group telemetry and the counter registry) as one
        # schema-versioned stream record
        hub.emit(line, telemetry=group_telemetry_by_mode.get(eval_mode))
    print(json.dumps(line))


if __name__ == "__main__":
    main()
