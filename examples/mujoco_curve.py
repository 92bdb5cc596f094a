"""Learning-curve runner for REAL gymnasium MuJoCo envs.

The real-physics counterpart of ``locomotion_curve.py``: PGPE + ClipUp over a
``GymNE`` problem whose lanes are stepped by the batched MuJoCo engine
(``envs.mujoco.MjVecEnv`` over ``mujoco.rollout`` — one device forward + one
threaded physics call per timestep for the whole lane block). Appends one
JSONL row per generation (population stats + stdev norm + ClipUp velocity
norm) and a periodic deterministic center evaluation, so the curve grounds
the framework's locomotion claims in the canonical benchmark rather than the
bespoke rigid-body simulator.

Defaults are sized for a 1-core box (popsize <= 64):

    python mujoco_curve.py --env InvertedPendulum-v5 --popsize 48 \
        --generations 40 --episode-length 200 --out ip_curve.jsonl

    python mujoco_curve.py --env Hopper-v5 --popsize 64 --generations 200
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
    p.add_argument("--cpu", action="store_true",
                   help="accepted for smoke-tier uniformity; this runner"
                   " always uses the CPU backend (host-physics workload)")
    p.add_argument("--env", default="InvertedPendulum-v5")
    p.add_argument("--popsize", type=int, default=48)
    p.add_argument("--generations", type=int, default=40)
    p.add_argument("--episode-length", type=int, default=200)
    p.add_argument("--num-envs", type=int, default=None,
                   help="lane-block width (default: popsize, capped at 64)")
    p.add_argument("--eval-every", type=int, default=5)
    p.add_argument("--eval-episodes", type=int, default=4)
    # ClipUp recipe (reference rl_clipup.py:110-114)
    p.add_argument("--max-speed", type=float, default=0.15)
    p.add_argument("--center-lr", type=float, default=None)
    p.add_argument("--radius-init", type=float, default=None)
    p.add_argument("--stdev-lr", type=float, default=0.1)
    p.add_argument("--network", default=None,
                   help="policy DSL; default: linear obs->act")
    p.add_argument("--backend", default="auto", choices=("auto", "mujoco", "sync"),
                   help="lane engine (auto = MjVecEnv for supported -v5 envs)")
    p.add_argument("--out", default=None)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()
    # host-physics workload: the policy forward is tiny, so JAX always runs
    # on the CPU here
    from evotorch_tpu.resilience import setup_backend

    setup_backend(force_cpu=True)
    import jax.numpy as jnp

    from evotorch_tpu.algorithms import PGPE
    from evotorch_tpu.neuroevolution import GymNE

    out_path = args.out or f"{args.env.lower().replace('-', '_')}_curve.jsonl"
    center_lr = args.center_lr if args.center_lr is not None else 0.75 * args.max_speed
    radius_init = args.radius_init if args.radius_init is not None else 15 * args.max_speed
    num_envs = args.num_envs if args.num_envs is not None else min(args.popsize, 64)

    problem = GymNE(
        args.env,
        args.network or "Linear(obs_length, act_length)",
        observation_normalization=True,
        episode_length=args.episode_length,
        num_envs=num_envs,
        vector_env_backend=args.backend,
        seed=args.seed,
    )
    searcher = PGPE(
        problem,
        popsize=args.popsize,
        center_learning_rate=center_lr,
        stdev_learning_rate=args.stdev_lr,
        radius_init=radius_init,
        optimizer="clipup",
        optimizer_config={"max_speed": args.max_speed},
        ranking_method="centered",
    )

    vec_env = problem._make_vector_env()
    t_start = time.time()
    with open(out_path, "a") as f:
        header = {
            "env": args.env,
            "backend": type(vec_env).__name__,
            "popsize": args.popsize,
            "num_envs": num_envs,
            "episode_length": args.episode_length,
            "network": args.network or "Linear(obs_length, act_length)",
            "seed": args.seed,
        }
        f.write(json.dumps(header) + "\n")
        for gen in range(1, args.generations + 1):
            searcher.step()
            opt = searcher.optimizer
            row = {
                "gen": gen,
                "mean_eval": float(searcher.status["mean_eval"]),
                "best_eval": float(searcher.status["best_eval"]),
                "stdev_norm": float(jnp.linalg.norm(searcher.status["stdev"])),
                "interactions": int(problem.status["total_interaction_count"]),
                "elapsed_s": round(time.time() - t_start, 1),
            }
            if hasattr(opt, "_velocity"):
                row["clipup_velocity_norm"] = float(jnp.linalg.norm(opt._velocity))
            if gen % args.eval_every == 0 or gen == args.generations:
                center = jnp.asarray(searcher.status["center"])
                row["center_eval"] = problem.run_solution(
                    center, num_episodes=args.eval_episodes
                )
                print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    print(
        json.dumps(
            {
                "done": True,
                **header,
                "generations": args.generations,
                "interactions": int(problem.status["total_interaction_count"]),
                "episodes": int(problem.status["total_episode_count"]),
                "elapsed_s": round(time.time() - t_start, 1),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
