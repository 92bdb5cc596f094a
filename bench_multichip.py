"""Multi-chip benchmark: the exact `bench.py` program, population-sharded.

The generation program is identical to ``bench.py`` (PGPE ask -> fully
vectorized Humanoid rollout -> tell); the only difference is that the
population axis is laid out over a named device mesh. Two SPMD forms are
supported (``BENCH_SPMD``, docs/sharding.md):

- ``gspmd`` (default): ONE global jitted generation
  (``parallel.make_generation_step``) with the population pinned to the
  mesh via ``NamedSharding`` — XLA's SPMD partitioner inserts the
  collectives, the evolution state is donated end-to-end, popsizes that
  don't divide the mesh are padded+masked, and 2-D ``pop x model`` meshes
  work (``BENCH_MESH=4x2``).
- ``shard_map``: the pre-GSPMD explicit per-shard form (global lane ids,
  psum'd stat deltas and counters, per-shard refill queues) — kept as the
  measured A/B baseline.
- ``ab``: BOTH, interleaved on the same process (this box times ±20%
  run-to-run; ``BENCH_AB_REPEATS`` samples each, default 3, medians
  reported) with ``spmd_speedup`` = gspmd / shard_map median steps/s.

With an accelerator the mesh spans the real chips (four on a v5e 2x2
host); ``JAX_PLATFORMS=cpu python bench_multichip.py`` exercises the same
program on the 8-virtual-device CPU mesh (correctness and counts, not speed).

Knobs: the same BENCH_* env vars as bench.py, plus ``BENCH_MESH`` (``"8"``
= 1-D pop mesh of 8, ``"4x2"`` / ``"pop=4,model=2"`` = 2-D; default all
local devices on ``pop``) and ``BENCH_SPMD`` above. ``BENCH_TRUNK_DELTA=1``
evaluates the shared-trunk + per-lane low-rank-delta form (GSPMD path only:
the evaluator pins the trunk to the ``model`` axis, the per-lane
coefficients to ``pop`` — docs/policies.md). The refill schedule
resolves through the tuned-config cache under THIS mesh's label (a width
tuned unsharded is not evidence for a sharded layout). With BENCH_LEDGER
on (default), the generation program is AOT-captured into the program
ledger — the line carries ``compile_seconds`` / ``flops_per_step`` /
``peak_hbm_bytes`` / ``model_efficiency`` plus ``donation_verified``
(runtime-checked ``donate_argnums`` aliasing; null for the
host-orchestrated episodes_compact path, which has no single
whole-generation program). ``steady_compiles`` is the retrace-sentinel
count over every timed loop — anything but 0 is a retrace bug.
"""

import json
import os
import statistics
import sys
import time

from bench_common import (
    bench_config,
    build_policy,
    compact_kwargs,
    device_record,
    fresh_pgpe_state,
    ledger_columns,
    refill_kwargs,
    setup_backend,
    tuned_policy,
    tuned_refill,
)


def main():
    use_cpu = setup_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from evotorch_tpu.algorithms.functional import (
        pgpe_ask,
        pgpe_ask_trunk_delta,
        pgpe_tell,
        pgpe_tell_trunk_delta,
    )
    from evotorch_tpu.analysis import track_compiles
    from evotorch_tpu.envs import make_env
    from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
    from evotorch_tpu.neuroevolution.net.vecrl import (
        global_lane_ids,
        run_vectorized_rollout,
        run_vectorized_rollout_compacting_sharded,
    )
    from evotorch_tpu.observability import enable_persistent_cache
    from evotorch_tpu.parallel import make_generation_step, make_mesh, parse_mesh_shape
    from evotorch_tpu.parallel import mesh_label as mesh_label_of

    cfg = bench_config(use_cpu, cpu_episode_length=50)
    enable_persistent_cache()
    popsize = cfg["popsize"]
    episode_length = cfg["episode_length"]
    generations = cfg["generations"]
    compute_dtype = cfg["compute_dtype"]
    eval_mode = cfg["eval_mode"]

    spmd = os.environ.get("BENCH_SPMD", "gspmd")
    if spmd not in ("gspmd", "shard_map", "ab"):
        raise SystemExit(f"BENCH_SPMD must be gspmd|shard_map|ab, got {spmd!r}")
    n_devices = len(jax.devices())
    mesh_shape = parse_mesh_shape(os.environ.get("BENCH_MESH", n_devices))
    mesh = make_mesh(mesh_shape)
    mesh_size = int(np.prod([int(s) for s in mesh_shape.values()]))

    if eval_mode == "episodes_compact":
        # the lane-compacting runner is host-orchestrated over shard_map
        # building blocks — there is no GSPMD monolith to A/B against
        if spmd == "ab":
            raise SystemExit("BENCH_SPMD=ab has no GSPMD form for episodes_compact")
        variants = ["host_compact"]
    else:
        variants = {"gspmd": ["gspmd"], "shard_map": ["shard_map"],
                    "ab": ["gspmd", "shard_map"]}[spmd]

    trunk_delta = cfg["trunk_delta"]
    needs_legacy = any(v in ("shard_map", "host_compact") for v in variants)
    if trunk_delta and needs_legacy:
        # the trunk-delta population shards through the GSPMD evaluator's
        # pytree-aware constraints (trunk over `model`, coeffs over `pop`);
        # the explicit shard_map / host-compact harnesses here are dense-only
        raise SystemExit(
            "BENCH_TRUNK_DELTA=1 needs the GSPMD path (BENCH_SPMD=gspmd, "
            f"eval_mode != episodes_compact); got spmd variants {variants}"
        )
    if needs_legacy:
        sharded_axes = [n for n, s in mesh.shape.items() if int(s) > 1]
        if sharded_axes not in ([], ["pop"]):
            raise SystemExit(
                f"the shard_map path needs a 1-D pop mesh, got {dict(mesh.shape)}"
            )
        if popsize % mesh_size != 0:
            raise SystemExit(
                f"popsize {popsize} must be divisible by the mesh size "
                f"{mesh_size} on the shard_map path (GSPMD pads instead)"
            )
        mesh_1d = Mesh(np.asarray(jax.devices()[:mesh_size]), axis_names=("pop",))

    env = make_env(cfg["env_name"], **cfg["env_kwargs"])
    policy = build_policy(env)
    print(
        f"mesh={dict(mesh.shape)} ({mesh_label_of(mesh)}) devices={mesh_size} "
        f"popsize={popsize} params={policy.parameter_count} "
        f"episode_length={episode_length} eval_mode={eval_mode} spmd={variants}",
        file=sys.stderr,
    )

    stats0 = RunningNorm(env.observation_size).stats

    # every variant's generation has the same host contract:
    #   gen(state, key, stats) -> (state, stats, per_shard_steps, scores)
    # build_* returns (gen, capture_target) — capture_target is the jitted
    # whole-generation program for the ledger, or None (host_compact)
    refill_src = None

    trunk_cfg, trunk_src = {}, None
    if trunk_delta:
        trunk_cfg, trunk_src = tuned_policy(
            cfg, params=policy.parameter_count, mesh_label=mesh_label_of(mesh)
        )

    def gspmd_recipe():
        """The GSPMD ask/tell callables + rollout knobs, shared by the
        per-generation program and the fused-span program so the two legs
        of the BENCH_SPAN A/B cannot silently diverge."""
        nonlocal refill_src
        rkw = {}
        if eval_mode == "episodes_refill":
            # GLOBAL width (the GSPMD program is the unsharded program),
            # looked up under THIS mesh's label
            rkw, refill_src = tuned_refill(
                cfg, params=policy.parameter_count, mesh_label=mesh_label_of(mesh)
            )
        if trunk_delta:
            # shared-trunk + per-lane delta population: the evaluator pins
            # the trunk (center + effective basis) to the `model` axis and
            # the per-lane coefficients to `pop` (parallel/evaluate.py)
            def ask_fn(k, s):
                return pgpe_ask_trunk_delta(
                    k, s, popsize=popsize, rank=trunk_cfg["rank"], policy=policy
                )

            tell_fn = pgpe_tell_trunk_delta
            rkw["trunk_block"] = trunk_cfg["trunk_block"]
        else:
            def ask_fn(k, s):
                return pgpe_ask(k, s, popsize=popsize)

            tell_fn = pgpe_tell
        return ask_fn, tell_fn, rkw

    def build_gspmd():
        ask_fn, tell_fn, rkw = gspmd_recipe()
        step = make_generation_step(
            env,
            policy,
            ask=ask_fn,
            tell=tell_fn,
            popsize=popsize,
            mesh=mesh,
            num_episodes=1,
            episode_length=episode_length,
            compute_dtype=compute_dtype,
            eval_mode=eval_mode,
            **rkw,
        )

        def gen(state, key, stats):
            state, scores, stats, total_steps, _telemetry = step(state, key, stats)
            # one global program: per-shard accounting is XLA's business,
            # the 1-element form keeps the harness contract
            return state, stats, total_steps[None], scores

        return gen, step

    def build_shard_map():
        # per-shard refill queues: the width knob is global, divided across
        # the mesh; the seed stride is the global popsize (unique
        # (solution, episode) seeds across shards)
        rkw = (
            dict(
                refill_kwargs(
                    cfg,
                    n_shards=mesh_size,
                    params=policy.parameter_count,
                    mesh_label=mesh_label_of(mesh_1d),
                ),
                seed_stride=popsize,
            )
            if eval_mode == "episodes_refill"
            else {}
        )

        def local_rollout(values_shard, key, stats):
            # per-lane PRNG chains seeded by GLOBAL lane ids (same key on
            # every shard): the sharded program's realized randomness is
            # identical to the unsharded one. Stat deltas and step counters
            # merge across the pop axis with psums (the collective form of
            # the reference's actor delta-sync, gymne.py:524-573)
            ids = global_lane_ids("pop", values_shard.shape[0])
            result = run_vectorized_rollout(
                env,
                policy,
                values_shard,
                key,
                stats,
                lane_ids=ids,
                num_episodes=1,
                episode_length=episode_length,
                compute_dtype=compute_dtype,
                eval_mode=eval_mode,
                **rkw,
            )
            delta = jax.tree_util.tree_map(
                lambda new, old: new - old, result.stats, stats
            )
            merged = jax.tree_util.tree_map(
                lambda old, d: old + jax.lax.psum(d, "pop"), stats, delta
            )
            local_steps = result.total_steps[None]  # P("pop") per-shard array
            return result.scores, merged, local_steps

        sharded_rollout = jax.shard_map(
            local_rollout,
            mesh=mesh_1d,
            in_specs=(P("pop"), P(), P()),
            out_specs=(P("pop"), P(), P("pop")),
            check_vma=False,
        )
        pop_sharding = NamedSharding(mesh_1d, P("pop"))

        def generation(state, key, stats):
            k1, k2 = jax.random.split(key)
            values = pgpe_ask(k1, state, popsize=popsize)
            values = jax.lax.with_sharding_constraint(values, pop_sharding)
            scores, stats, per_shard_steps = sharded_rollout(values, k2, stats)
            state = pgpe_tell(state, values, scores)
            return state, stats, per_shard_steps, scores

        gen = jax.jit(generation, donate_argnums=(0,))
        return gen, gen

    def build_host_compact():
        # the sharded lane-compacting runner (host-orchestrated chunks over
        # shard_mapped building blocks): ask and tell stay jitted programs
        # around it, with the population pinned to the pop sharding
        pop_sharding = NamedSharding(mesh_1d, P("pop"))

        def sharded_ask(k, s):
            return jax.lax.with_sharding_constraint(
                pgpe_ask(k, s, popsize=popsize), pop_sharding
            )

        ask_jit = jax.jit(sharded_ask)
        tell_jit = jax.jit(pgpe_tell, donate_argnums=(0,))
        first_gen = [True]
        ckw = compact_kwargs(
            cfg,
            n_shards=mesh_size,
            params=policy.parameter_count,
            mesh_label=mesh_label_of(mesh_1d),
        )

        def gen(state, key, stats):
            k1, k2 = jax.random.split(key)
            values = ask_jit(k1, state)
            result, per_shard_steps = run_vectorized_rollout_compacting_sharded(
                env, policy, values, k2, stats,
                mesh=mesh_1d,
                num_episodes=1,
                episode_length=episode_length,
                compute_dtype=compute_dtype,
                **ckw,
                # compile the full width-descent chain during the warmup
                # generation so no compile lands in the timed loop
                prewarm=first_gen[0],
                return_per_shard_steps=True,
            )
            first_gen[0] = False
            state = tell_jit(state, values, result.scores)
            return state, result.stats, per_shard_steps, result.scores

        return gen, None

    builders = {
        "gspmd": build_gspmd,
        "shard_map": build_shard_map,
        "host_compact": build_host_compact,
    }

    key = jax.random.key(0)
    runs = {}  # variant -> mutable harness state
    for name in variants:
        gen, capture_target = builders[name]()
        state = fresh_pgpe_state(policy.parameter_count)
        # TWO warmup generations: the first compiles for the fresh
        # (uncommitted) state layout, and — under GSPMD donation — returns a
        # state committed to the compiler's chosen sharding, which the second
        # call compiles the steady-state program for. Timing starts only once
        # the layouts have reached their fixed point (the retrace sentinel
        # keeps this honest: steady_compiles must stay 0).
        stats = stats0
        for _ in range(2):
            key, sub = jax.random.split(key)
            state, stats, per_shard, scores = gen(state, sub, stats)
            jax.block_until_ready(scores)
        print(
            f"[{name}] compiled; warmup per-shard steps="
            f"{np.asarray(per_shard).tolist()}",
            file=sys.stderr,
        )
        runs[name] = {
            "gen": gen,
            "capture": capture_target,
            "state": state,
            "stats": stats,
            "shard_steps": np.zeros(np.asarray(per_shard).shape[0], dtype=np.int64),
            "samples": [],  # steps/s per timed sample
            "total_steps": 0,
            "scores": scores,
        }

    # program ledger (BENCH_LEDGER, like bench.py): AOT-capture each
    # variant's whole-generation program — compile wall-time, FLOPs, peak
    # memory, runtime donation verification — outside the timed loop. The
    # compact path is host-orchestrated (no single program): columns null.
    records = {}
    if cfg["ledger"]:
        from evotorch_tpu.observability import ledger as program_ledger
        from evotorch_tpu.observability.programs import abstract_like

        for name, run in runs.items():
            if run["capture"] is None:
                continue
            records[name] = program_ledger.capture(
                f"bench_multichip.generation[{eval_mode}][{name}]",
                run["capture"],
                abstract_like(fresh_pgpe_state(policy.parameter_count)),
                jax.random.key(0),
                abstract_like(stats0),
                shape={
                    "env": cfg["env_name"],
                    "popsize": popsize,
                    "episode_length": episode_length,
                    "mesh": mesh_label_of(mesh),
                    "spmd": name,
                },
            )

    # timed samples, INTERLEAVED across variants (±20% run-to-run on this
    # box: back-to-back blocks would hand one variant the quiet half)
    repeats = int(os.environ.get("BENCH_AB_REPEATS", "3")) if spmd == "ab" else 1
    steady_compiles = 0
    for _ in range(repeats):
        for name in variants:
            run = runs[name]
            gen = run["gen"]
            state, stats = run["state"], run["stats"]
            with track_compiles() as compile_log:
                t0 = time.perf_counter()
                sample_steps = 0
                for _ in range(generations):
                    key, sub = jax.random.split(key)
                    state, stats, per_shard, scores = gen(state, sub, stats)
                    jax.block_until_ready(scores)
                    run["shard_steps"] += np.asarray(per_shard)
                    sample_steps += int(np.sum(np.asarray(per_shard)))
                elapsed = time.perf_counter() - t0
            steady_compiles += compile_log.count
            if compile_log.count:
                print(
                    f"[{name}] STEADY-STATE COMPILES: {compile_log.names}",
                    file=sys.stderr,
                )
            run.update(state=state, stats=stats, scores=scores)
            run["total_steps"] += sample_steps
            run["samples"].append(sample_steps / elapsed)

    medians = {name: statistics.median(run["samples"]) for name, run in runs.items()}
    for name, run in runs.items():
        print(
            f"[{name}] {repeats}x{generations} generations, "
            f"{run['total_steps']} env-steps; median "
            f"{medians[name]:.0f} steps/s; mean score "
            f"{float(jnp.mean(run['scores'])):.3f}; per-shard steps "
            f"{run['shard_steps'].tolist()}",
            file=sys.stderr,
        )

    span_ab = {}
    if cfg["span"] is not None and "gspmd" in variants:
        # BENCH_SPAN on the sharded path: K generations of the SAME GSPMD
        # recipe scanned into one donated program (parallel.make_training_span
        # at THIS mesh) vs the per-generation program dispatched K times from
        # the host loop — interleaved median-of-N samples of one span each.
        # Absent for episodes_compact (host-orchestrated, cannot be fused)
        # and for the legacy shard_map-only runs.
        from bench_common import tuned_span
        from evotorch_tpu.parallel import make_training_span

        span_k, span_src = tuned_span(
            cfg, params=policy.parameter_count, mesh_label=mesh_label_of(mesh)
        )
        ask_fn, tell_fn, rkw = gspmd_recipe()
        span_fn = make_training_span(
            env,
            policy,
            ask=ask_fn,
            tell=tell_fn,
            popsize=popsize,
            span=span_k,
            mesh=mesh,
            num_episodes=1,
            episode_length=episode_length,
            compute_dtype=compute_dtype,
            eval_mode=eval_mode,
            **rkw,
        )
        # two warmups (fresh layout, then the steady-state layout-committed
        # program under donation); the hostloop leg reuses the gspmd
        # generation already at ITS layout fixed point from the loop above
        sp_state, sp_stats = fresh_pgpe_state(policy.parameter_count), stats0
        for _ in range(2):
            key, sub = jax.random.split(key)
            sp_state, scores, sp_stats, steps, _ = span_fn(
                sp_state, jax.random.split(sub, span_k), sp_stats
            )
            jax.block_until_ready(scores)
        host_gen = runs["gspmd"]["gen"]
        hl_state, hl_stats = runs["gspmd"]["state"], runs["gspmd"]["stats"]
        span_samples = {"hostloop": [], "span": []}
        for _ in range(cfg["span_ab_repeats"]):
            with track_compiles() as compile_log:
                t0 = time.perf_counter()
                sample_steps = 0
                for _ in range(span_k):
                    key, sub = jax.random.split(key)
                    hl_state, hl_stats, per_shard, scores = host_gen(
                        hl_state, sub, hl_stats
                    )
                    jax.block_until_ready(scores)
                    sample_steps += int(np.sum(np.asarray(per_shard)))
                span_samples["hostloop"].append(
                    sample_steps / (time.perf_counter() - t0)
                )
            steady_compiles += compile_log.count
            with track_compiles() as compile_log:
                t0 = time.perf_counter()
                key, sub = jax.random.split(key)
                sp_state, scores, sp_stats, steps, _ = span_fn(
                    sp_state, jax.random.split(sub, span_k), sp_stats
                )
                jax.block_until_ready(scores)
                span_samples["span"].append(
                    int(np.sum(np.asarray(steps))) / (time.perf_counter() - t0)
                )
            steady_compiles += compile_log.count
        med_hl = statistics.median(span_samples["hostloop"])
        med_sp = statistics.median(span_samples["span"])
        print(
            f"[span_ab/{eval_mode}] span={span_k}, "
            f"{cfg['span_ab_repeats']} interleaved samples: hostloop "
            f"{med_hl:.0f} vs span {med_sp:.0f} steps/s "
            f"({med_sp / med_hl:.2f}x)",
            file=sys.stderr,
        )
        span_ab = {
            "span": span_k,
            "span_speedup": round(med_sp / med_hl, 3),
            "span_value": round(med_sp, 1),
            "hostloop_value": round(med_hl, 1),
        }
        if cfg["tuned"]:
            span_ab["span_config_source"] = span_src

    primary = variants[0]
    steps_per_sec = medians[primary]
    record = records.get(primary)
    ledger_cols = {}
    if cfg["ledger"]:
        ledger_cols = (
            ledger_columns(
                record,
                steps_per_sec=steps_per_sec,
                steps_per_generation=runs[primary]["total_steps"]
                / (repeats * generations),
                param_count=policy.parameter_count,
            )
            if record is not None
            else {
                "compile_seconds": None,
                "flops_per_step": None,
                "peak_hbm_bytes": None,
                "model_efficiency": None,
            }
        )
        # runtime-verified donation of the donated evolution state: True
        # iff every donate_argnums buffer was actually aliased by XLA
        ledger_cols["donation_verified"] = (
            (not record.donation.missing) if record is not None
            and record.donation is not None else None
        )

    line = {
        "metric": "pgpe_sharded_rollout_env_steps_per_sec",
        "value": round(steps_per_sec, 1),
        "unit": "env_steps/sec",
        "vs_baseline": round(steps_per_sec / 1_000_000, 4),
        **ledger_cols,
        "spmd": primary,
        "steady_compiles": steady_compiles,
        "mesh": {name: int(size) for name, size in mesh.shape.items()},
        "mesh_label": mesh_label_of(mesh),
        "per_shard_steps": runs[primary]["shard_steps"].tolist(),
        "env": cfg["env_name"],
        "popsize": popsize,
        "episode_length": episode_length,
        "eval_mode": eval_mode,
        "compute_dtype": str(compute_dtype.__name__ if compute_dtype else "float32"),
        "backend": device_record(),
    }
    if cfg["tuned"] and eval_mode == "episodes_refill" and refill_src is not None:
        line["tuned_config_source"] = refill_src
    if trunk_delta:
        # BENCH_TRUNK_DELTA=1 only (default line stays byte-compatible)
        line["policy_form"] = "trunk_delta"
        line["trunk_rank"] = trunk_cfg["rank"]
        line["trunk_block"] = trunk_cfg["trunk_block"]
        if cfg["tuned"]:
            line["trunk_config_source"] = trunk_src
    if span_ab:
        # BENCH_SPAN only (default line stays byte-compatible)
        line.update(span_ab)
    if spmd == "ab":
        line["spmd_speedup"] = round(medians["gspmd"] / medians["shard_map"], 3)
        line["shard_map_value"] = round(medians["shard_map"], 1)
        line["ab_samples"] = {
            name: [round(s, 1) for s in run["samples"]] for name, run in runs.items()
        }
    print(json.dumps(line))


if __name__ == "__main__":
    main()
