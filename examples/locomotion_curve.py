"""Sustained learning-curve runner for the locomotion envs.

Produces the evidence a reference user recognizes (VERDICT r3 #7): a long
PGPE run whose per-generation population stats AND periodic center
evaluations are appended to a JSONL file. For envs with an alive bonus
(Humanoid), the center is additionally evaluated on a zero-bonus copy of the
env, so the report separates actual locomotion (velocity - ctrl cost) from
the survival plateau. HalfCheetah has no alive bonus at all (reward =
forward velocity - ctrl cost, ``envs/halfcheetah.py``), so any sustained
improvement there is real forward progress by construction.

Recipe follows the reference's ClipUp configurations
(reference ``examples/scripts/rl_clipup.py:170-206``).

    python locomotion_curve.py --env halfcheetah --cpu \
        --popsize 256 --generations 250 --out halfcheetah_curve.jsonl
"""

import argparse
import json
import os
import sys
import time

# run from anywhere: the package lives one directory up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--env", default="halfcheetah")
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--popsize", type=int, default=256)
    p.add_argument("--generations", type=int, default=250)
    p.add_argument("--episode-length", type=int, default=250)
    p.add_argument("--eval-every", type=int, default=10)
    p.add_argument("--eval-episodes", type=int, default=8)
    p.add_argument("--bf16", action="store_true")
    # ClipUp recipe (reference rl_clipup.py:110-114): lr = 0.75 * max_speed,
    # radius_init = 15 * max_speed; pass --center-lr / --radius-init to
    # override the derivation
    p.add_argument("--max-speed", type=float, default=0.12)
    p.add_argument("--center-lr", type=float, default=None)
    p.add_argument("--radius-init", type=float, default=None)
    p.add_argument("--stdev-lr", type=float, default=0.1)
    # the flagship-recipe knobs (reference rl_clipup.py:184-206): subtract
    # the per-step alive bonus from the SEARCH signal so standing still
    # isn't a local optimum ("auto" = the env's own alive_bonus), and grow
    # the population adaptively under an interaction budget
    p.add_argument("--decrease-rewards-by", default=None,
                   help="per-step reward decrement; 'auto' = env.alive_bonus")
    p.add_argument("--num-interactions", type=int, default=None)
    p.add_argument("--popsize-max", type=int, default=None)
    p.add_argument("--lowrank-rank", type=int, default=None)
    p.add_argument("--network", default=None,
                   help="policy DSL; default: 2x64-tanh MLP")
    p.add_argument("--out", default=None)
    p.add_argument("--seed", type=int, default=0)
    # fused training spans (docs/sharding.md "Fused multi-generation
    # training spans"): K generations of the SAME ClipUp recipe — run
    # through the functional PGPE state — scanned into ONE donated device
    # program per block (VecNE.make_training_span); the per-generation JSONL
    # rows are reconstructed host-side from the program's stacked outputs,
    # so the curve schema matches the host-loop path. Per-generation PRNG
    # keys derive from the ABSOLUTE generation index, so checkpoint resume
    # replays the exact uninterrupted trajectory.
    p.add_argument("--span", type=int, default=None,
                   help="fuse K generations per device dispatch; "
                        "--checkpoint-every rounds UP to the next span "
                        "boundary (the program only yields between blocks)")
    # durable checkpoint/resume (resilience.RunCheckpointer,
    # docs/resilience.md): with --checkpoint-dir the run saves a bundle
    # every --checkpoint-every generations and AUTO-RESUMES from the newest
    # valid bundle on restart — a SIGKILL costs at most one interval, and
    # the resumed trajectory is bit-identical to the uninterrupted one
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=10)
    p.add_argument("--checkpoint-keep", type=int, default=3)
    p.add_argument("--no-resume", action="store_true",
                   help="ignore existing bundles; start fresh (still saves)")
    return p.parse_args()


def span_checkpoint_every(every: int, span: int) -> int:
    """``--checkpoint-every`` aligned to fused-span boundaries: the scanned
    program only hands control back between K-generation blocks, so the
    cadence rounds UP to the next multiple of ``span`` (never down — down
    would checkpoint MORE often than asked). With the cadence a span
    multiple, ``maybe_save`` fires exactly at block ends and resume restarts
    on a block boundary — the resumed trajectory stays bit-identical."""
    return -(-int(every) // int(span)) * int(span)


def main():
    args = parse_args()
    from evotorch_tpu.observability import enable_persistent_cache
    from evotorch_tpu.resilience import setup_backend

    # --cpu (or JAX_PLATFORMS=cpu) asks for the 8-virtual-device CPU; anything
    # else requires an accelerator — hours of curve never run on a CPU that
    # nobody asked for
    setup_backend(args.cpu)
    enable_persistent_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from evotorch_tpu.algorithms import PGPE
    from evotorch_tpu.envs import make_env
    from evotorch_tpu.neuroevolution import VecNE
    from evotorch_tpu.neuroevolution.net.vecrl import run_vectorized_rollout

    if args.span and (
        args.num_interactions or args.popsize_max or args.lowrank_rank
    ):
        raise SystemExit(
            "--span fuses a fixed-shape program; the adaptive "
            "--num-interactions/--popsize-max knobs and --lowrank-rank need "
            "the per-generation host loop"
        )

    out_path = args.out or f"{args.env}_curve.jsonl"
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    center_lr = args.center_lr if args.center_lr is not None else 0.75 * args.max_speed
    radius_init = args.radius_init if args.radius_init is not None else 15 * args.max_speed

    decrease = args.decrease_rewards_by
    if decrease == "auto":
        decrease = float(getattr(make_env(args.env), "alive_bonus", 0.0)) or None
    elif decrease is not None:
        decrease = float(decrease)

    problem = VecNE(
        args.env,
        args.network
        or "Linear(obs_length, 64) >> Tanh() >> Linear(64, 64) >> Tanh()"
        " >> Linear(64, act_length)",
        observation_normalization=True,
        episode_length=args.episode_length,
        eval_mode="episodes",
        compute_dtype=compute_dtype,
        decrease_rewards_by=decrease,
        seed=args.seed,
    )
    searcher = None
    if not args.span:
        searcher = PGPE(
            problem,
            popsize=args.popsize,
            center_learning_rate=center_lr,
            stdev_learning_rate=args.stdev_lr,
            radius_init=radius_init,
            optimizer="clipup",
            optimizer_config={"max_speed": args.max_speed},
            ranking_method="centered",
            num_interactions=args.num_interactions,
            popsize_max=args.popsize_max,
            lowrank_rank=args.lowrank_rank,
        )

    # search-health watchdog (docs/observability.md "Search health"):
    # variance-gated plateau detection on the on-device score statistics
    # plus stdev-collapse vs the run's own starting spread; verdicts ride
    # the MetricsHub stream only (the curve JSONL stays byte-compatible)
    from evotorch_tpu.observability import Rule, SLOWatchdog

    watchdog = SLOWatchdog(
        [Rule("plateau", threshold=25), Rule("stdev_collapse", threshold=0.01)]
    )

    # durable resume: restore the whole searcher (functional state + PRNG
    # chain + obs-norm stats + counters ride inside its pickle) from the
    # newest valid bundle, then continue from the next generation appending
    # to the same JSONL — bit-identical to the run that was never killed
    ckpt = None
    start_gen = 1
    span_resume = None
    if args.checkpoint_dir:
        from evotorch_tpu.resilience import RunCheckpointer

        every = args.checkpoint_every
        if args.span:
            # the fused program only yields between K-generation blocks:
            # round the cadence UP to the next span boundary (documented on
            # the --span flag) so maybe_save fires exactly at block ends
            every = span_checkpoint_every(every, args.span)
        ckpt = RunCheckpointer(
            args.checkpoint_dir,
            keep=args.checkpoint_keep,
            every=every,
        )
        if not args.no_resume:
            loaded = ckpt.load_latest()
            if loaded is not None:
                gen_done, state = loaded
                if args.span:
                    # functional-state bundle; rehydrated in the span loop
                    span_resume = state
                else:
                    searcher = state["searcher"]
                    problem = searcher.problem
                start_gen = gen_done + 1
                # the bundle carries the health-detector window state, so
                # the resumed run's verdict timing is bit-identical to the
                # uninterrupted one (old bundles without it start fresh)
                if state.get("health"):
                    watchdog.load_state_dict(state["health"])
                print(
                    json.dumps({"resumed_from_generation": gen_done}),
                    flush=True,
                )

    # center-evaluation envs: the full reward, and (when the env pays an
    # alive bonus) a zero-bonus copy so the velocity term reports separately
    eval_env = problem.env
    try:
        nobonus_env = (
            make_env(args.env, alive_bonus=0.0)
            if getattr(eval_env, "alive_bonus", 0.0) != 0.0
            else None
        )
    except TypeError:
        nobonus_env = None

    def eval_center(center, step_count):
        # numpy, not jnp: the replicated center goes straight into the
        # jitted rollout dispatch, and a numpy argument is ~3x cheaper per
        # dispatch than a committed device array (CLAUDE.md r7 note)
        batch = np.repeat(np.asarray(center)[None], args.eval_episodes, axis=0)
        stats = problem.obs_norm.stats
        outs = {}
        for name, env in (("full", eval_env), ("no_alive_bonus", nobonus_env)):
            if env is None:
                continue
            r = run_vectorized_rollout(
                env,
                problem._policy,
                batch,
                jax.random.fold_in(jax.random.key(args.seed + 1), step_count),
                stats,
                num_episodes=1,
                episode_length=args.episode_length,
                eval_mode="episodes",
                # the center trained on normalized observations and must be
                # evaluated on them too (the stats argument is ignored
                # without the flag); eval-time stat updates are discarded
                observation_normalization=True,
                compute_dtype=compute_dtype,
            )
            outs[name] = float(jnp.mean(r.scores))
        return outs

    # EVOTORCH_METRICS=path: stream every per-generation row (plus the
    # lag-by-one decoded per-group telemetry and the counter registry)
    # through the MetricsHub — JSONL with a schema-versioned manifest first
    # line, or Prometheus text with a .prom suffix (docs/observability.md)
    from evotorch_tpu.observability import MetricsHub

    hub = MetricsHub.from_env(
        manifest={
            "source": "locomotion_curve",
            "env": args.env,
            "popsize": args.popsize,
            "episode_length": args.episode_length,
        }
    )

    t_start = time.time()
    if args.span:
        # --span K: blocks of K generations fused into one donated device
        # program; the host fetches the stacked (scores, telemetry, health,
        # center) outputs ONCE per block and reconstructs the per-generation
        # rows from them. Telemetry decodes per ROW from the same fetched
        # wire, so occupancy stays per-generation accurate; the block's
        # compile-count delta lands on its first row (nonzero on a warm
        # block is a retrace, exactly like the host-loop column).
        from evotorch_tpu.algorithms.functional import (
            get_functional_optimizer,
            pgpe,
            pgpe_ask,
            pgpe_health,
            pgpe_tell,
        )
        from evotorch_tpu.observability import GroupTelemetry
        from evotorch_tpu.observability.registry import counters

        span = int(args.span)
        state = pgpe(
            center_init=jnp.zeros(
                problem._policy.parameter_count, dtype=jnp.float32
            ),
            center_learning_rate=center_lr,
            stdev_learning_rate=args.stdev_lr,
            objective_sense="max",
            radius_init=radius_init,
            optimizer="clipup",
            optimizer_config={"max_speed": args.max_speed},
            ranking_method="centered",
        )
        best_eval = None
        if span_resume is not None:
            state = jax.tree_util.tree_map(jnp.asarray, span_resume["state"])
            problem.obs_norm.stats = jax.tree_util.tree_map(
                jnp.asarray, span_resume["obs_stats"]
            )
            problem._interaction_count = int(span_resume["interactions"])
            problem._episode_count = int(span_resume["episodes"])
            best_eval = span_resume.get("best_eval")

        def metrics_fn(s):
            # stdev/velocity norms AND the post-tell center of every
            # generation ride the scan ys, so the periodic center
            # evaluations need no extra device round trips
            m = dict(pgpe_health(s))
            m["center"] = get_functional_optimizer(s.optimizer)[1](
                s.optimizer_state
            )
            return m

        programs = {}

        def span_program(length):
            # one compile per distinct block length: every full block is
            # `span`; only a trailing remainder block compiles a second form
            if length not in programs:
                programs[length] = problem.make_training_span(
                    ask=lambda k, s: pgpe_ask(k, s, popsize=args.popsize),
                    tell=pgpe_tell,
                    popsize=args.popsize,
                    span=length,
                    state_metrics=metrics_fn,
                )
            return programs[length]

        base_key = jax.random.key(args.seed)
        centers_np = None
        with open(out_path, "a") as f:
            gen = start_gen
            while gen <= args.generations:
                length = min(span, args.generations - gen + 1)
                fn = span_program(length)
                # ABSOLUTE generation indices fold into the keys: a resumed
                # run regenerates the identical per-generation randomness
                keys = jax.vmap(lambda g: jax.random.fold_in(base_key, g))(
                    jnp.arange(gen, gen + length)
                )
                meters = counters.snapshot(("compiles",))
                result = fn(state, keys, problem.obs_norm.stats)
                state, scores, _stats, _steps, telemetry, health = result
                problem.consume_span(result[:5])
                block_compiles = counters.delta(meters)["compiles"]
                scores_np = np.asarray(scores)
                health_np = {k: np.asarray(v) for k, v in health.items()}
                centers_np = health_np.pop("center")
                telemetry_np = (
                    np.asarray(telemetry)
                    if telemetry is not None and telemetry.size
                    else None
                )
                for i in range(length):
                    g = gen + i
                    row_scores = scores_np[i]
                    gen_best = float(row_scores.max())
                    best_eval = (
                        gen_best
                        if best_eval is None
                        else max(best_eval, gen_best)
                    )
                    gt = (
                        GroupTelemetry.from_array(telemetry_np[i])
                        if telemetry_np is not None
                        else None
                    )
                    dec = gt.total() if gt is not None else None
                    row = {
                        "gen": g,
                        "mean_eval": float(row_scores.mean()),
                        "best_eval": best_eval,
                        "stdev_norm": float(health_np["stdev_norm"][i]),
                        "elapsed_s": round(time.time() - t_start, 1),
                        "occupancy": (
                            round(dec.occupancy, 4) if dec is not None else None
                        ),
                        "refill_events": (
                            dec.refill_events if dec is not None else None
                        ),
                        "steady_compiles": block_compiles if i == 0 else 0,
                    }
                    if "velocity_norm" in health_np:
                        row["clipup_velocity_norm"] = float(
                            health_np["velocity_norm"][i]
                        )
                    if g % args.eval_every == 0 or g == args.generations:
                        center_scores = eval_center(centers_np[i], g)
                        row["center_full"] = center_scores.get("full")
                        if "no_alive_bonus" in center_scores:
                            row["center_no_alive_bonus"] = center_scores[
                                "no_alive_bonus"
                            ]
                            row["center_bonus_term"] = (
                                center_scores["full"]
                                - center_scores["no_alive_bonus"]
                            )
                        print(json.dumps(row), flush=True)
                    f.write(json.dumps(row) + "\n")
                    f.flush()
                    report = watchdog.check(
                        gt, status={"stdev_norm": row["stdev_norm"]}
                    )
                    if hub is not None:
                        hub.emit({**row, **report.as_status()}, telemetry=gt)
                gen += length
                if ckpt is not None:
                    # save AFTER the block's rows are durably in the JSONL
                    # (same discipline as the host loop); the functional
                    # bundle carries everything a resume needs to replay
                    # the uninterrupted trajectory bit-identically
                    ckpt.maybe_save(
                        gen - 1,
                        {
                            "state": jax.tree_util.tree_map(np.asarray, state),
                            "obs_stats": jax.tree_util.tree_map(
                                np.asarray, problem.obs_norm.stats
                            ),
                            "interactions": int(problem._interaction_count),
                            "episodes": int(problem._episode_count),
                            "best_eval": best_eval,
                            "health": watchdog.state_dict(),
                        },
                    )
        print(
            json.dumps(
                {
                    "done": True,
                    "env": args.env,
                    "popsize": args.popsize,
                    "generations": args.generations,
                    "episode_length": args.episode_length,
                    "interactions": int(
                        problem.status["total_interaction_count"]
                    ),
                    "elapsed_s": round(time.time() - t_start, 1),
                    "final_center": eval_center(
                        centers_np[-1], args.generations
                    ),
                }
            ),
            flush=True,
        )
        return

    with open(out_path, "a") as f:
        for gen in range(start_gen, args.generations + 1):
            searcher.step()
            row = {
                "gen": gen,
                "mean_eval": float(searcher.status["mean_eval"]),
                "best_eval": float(searcher.status["best_eval"]),
                # plateau diagnostics (VERDICT r5 weak #4): a collapsing
                # stdev norm = premature convergence; a pinned ClipUp
                # velocity norm (== max_speed) = step-size ceiling — both
                # now published by the searcher itself (same values the
                # bespoke host-side norms here used to compute)
                "stdev_norm": searcher.status["stdev_norm"],
                "elapsed_s": round(time.time() - t_start, 1),
                # zero-sync eval telemetry (docs/observability.md): lane
                # occupancy + refill accounting of the previous generation's
                # evaluation, and this step's compile count from the always-on
                # registry — nonzero steady_compiles after gen 2 is a retrace
                "occupancy": searcher.status.get("eval_occupancy"),
                "refill_events": searcher.status.get("eval_refill_events"),
                "steady_compiles": searcher.status.get("compiles"),
            }
            velocity_norm = searcher.status.get("clipup_velocity_norm")
            if velocity_norm is not None:
                row["clipup_velocity_norm"] = velocity_norm
            if args.num_interactions is not None:
                row["popsize"] = int(searcher.status["popsize"])
            if args.lowrank_rank is not None:
                # subspace-exhaustion diagnostic (tools.lowrank.basis_capture):
                # persistently << 1 at a stalling rank (the rank-32 curve)
                row["basis_capture"] = searcher.status.get("basis_capture")
            if gen % args.eval_every == 0 or gen == args.generations:
                center_scores = eval_center(
                    searcher.status["center"], searcher.step_count
                )
                row["center_full"] = center_scores.get("full")
                if "no_alive_bonus" in center_scores:
                    # the velocity/bonus reward split: no_alive_bonus IS the
                    # velocity term (locomotion = velocity - ctrl cost); the
                    # bonus term is the survival plateau's share of the score
                    row["center_no_alive_bonus"] = center_scores["no_alive_bonus"]
                    row["center_bonus_term"] = (
                        center_scores["full"] - center_scores["no_alive_bonus"]
                    )
                print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
            # health verdicts: plateau on the on-device score statistics
            # (lag-by-one telemetry) + stdev collapse vs the first-seen
            # baseline; surfaced on the hub stream, never in the curve row
            report = watchdog.check(
                problem.last_group_telemetry,
                status={"stdev_norm": row["stdev_norm"]},
            )
            if hub is not None:
                hub.emit(
                    {**row, **report.as_status()},
                    telemetry=problem.last_group_telemetry,
                )
            if ckpt is not None:
                # save AFTER the row is durably in the JSONL so a resume
                # never replays an already-written generation; the bundle
                # carries the health-detector window state alongside
                ckpt.maybe_save(
                    gen,
                    {"searcher": searcher, "health": watchdog.state_dict()},
                )
    print(
        json.dumps(
            {
                "done": True,
                "env": args.env,
                "popsize": args.popsize,
                "generations": args.generations,
                "episode_length": args.episode_length,
                "interactions": int(problem.status["total_interaction_count"]),
                "elapsed_s": round(time.time() - t_start, 1),
                "final_center": eval_center(
                    searcher.status["center"], searcher.step_count
                ),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
