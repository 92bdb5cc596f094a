"""``python -m evotorch_tpu.serving`` — the stdio evaluation service.

Builds one :class:`EvalServer` from CLI flags and speaks the JSONL
protocol on stdin/stdout (docs/serving.md "The JSONL protocol"). The
policy form is a tanh MLP over ``--hidden`` (empty = linear), matching
the bench/locomotion policy builder convention.
"""

from __future__ import annotations

import argparse
import sys


def _build_policy(env, hidden: str):
    from ..neuroevolution.net import FlatParamsPolicy, Linear, Tanh

    sizes = [int(h) for h in hidden.split(",") if h.strip()] if hidden else []
    widths = [env.observation_size, *sizes, env.action_size]
    net = None
    for n_in, n_out in zip(widths[:-1], widths[1:]):
        layer = Linear(n_in, n_out) >> Tanh()
        net = layer if net is None else net >> layer
    return FlatParamsPolicy(net)


def _main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m evotorch_tpu.serving",
        description="JSONL-over-stdio multi-tenant evaluation service",
    )
    parser.add_argument("--env", required=True, help="registry env name")
    parser.add_argument("--hidden", default="", help="MLP hidden sizes, e.g. 64,64")
    parser.add_argument("--slab", type=int, required=True, help="slab size (rows/dispatch)")
    parser.add_argument("--width", type=int, default=None, help="refill lane width")
    parser.add_argument("--max-tenants", type=int, default=4)
    parser.add_argument("--num-episodes", type=int, default=1)
    parser.add_argument("--episode-length", type=int, default=None)
    parser.add_argument("--obs-norm", action="store_true")
    parser.add_argument(
        "--admission", default="fifo", choices=("fifo", "starvation")
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--cpu", action="store_true", help="force the CPU backend (8 virtual devices)"
    )
    args = parser.parse_args(argv)

    # the server is the one process that holds the chip; its clients speak
    # JSONL over the pipe and never import a backend
    from ..resilience import setup_backend

    setup_backend(args.cpu)

    from ..envs import make_env
    from .server import EvalServer
    from .stdio import serve_stdio

    env = make_env(args.env)
    server = EvalServer(
        env,
        _build_policy(env, args.hidden),
        slab_size=args.slab,
        max_tenants=args.max_tenants,
        refill_width=args.width,
        num_episodes=args.num_episodes,
        episode_length=args.episode_length,
        observation_normalization=args.obs_norm,
        admission=args.admission,
        seed=args.seed,
    )
    print(
        f"serving {args.env} slab={args.slab} max_tenants={args.max_tenants}"
        f" program={server.program.key}",
        file=sys.stderr,
    )
    serve_stdio(server, sys.stdin, sys.stdout)
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
