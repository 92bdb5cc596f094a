"""Multi-host (DCN) initialization and the multi-host dry-run entry.

The reference documents cluster attach via ``ray start --head`` +
``ray.init(address=...)`` (``docs/advanced_usage/ray_cluster.md:1-40``). The
TPU-native equivalent is ``jax.distributed.initialize``: after it, every host
sees the global device set and the same SPMD programs (GSPMD jit/shard_map)
span hosts, with collectives riding ICI within a slice and DCN across slices.

``dryrun_multihost`` is the runnable proof: each participating process runs
the SAME GSPMD generation program (``parallel.make_generation_step``) over a
mesh spanning every host's devices and prints one JSON line of mesh-global
reductions — identical on every host, and identical to a single-host run of
the same global shape (``tests/test_multihost.py`` spawns 2x4-virtual-device
CPU processes and checks both). CLI form::

    python -m evotorch_tpu.parallel.distributed \
        --coordinator localhost:9999 --num-processes 2 --process-id 0
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["dryrun_multihost", "init_distributed"]

# reductions of SHARDED generation outputs (the scores) must happen on
# device under multi-host — their replicated results are then fetchable on
# every host (device_get refuses arrays spanning non-addressable devices)
_mean_fn = jax.jit(jnp.mean)
_norm_fn = jax.jit(jnp.linalg.norm)


def init_distributed(
    coordinator_address: Optional[str] = None,
    *,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize multi-host JAX if the environment calls for it.

    With no arguments, initialization is attempted only when the standard
    cluster environment variables are present (e.g. on Cloud TPU pods, GKE
    with the JAX plugin, or SLURM); single-host runs return False untouched.
    """
    if jax.distributed.is_initialized():
        return True
    # Multi-process SPMD on the CPU backend needs a cross-process
    # collectives implementation; the default ("none") makes EVERY
    # multiprocess computation fail to compile ("Multiprocess computations
    # aren't implemented on the CPU backend"). gloo needs the distributed
    # client, so the flag may only be set when initialize() will actually
    # run (with it set but no client, CPU backend creation itself fails) —
    # and it must be set before the first backend use, which is why it
    # lives here and not in callers. Inert on TPU.
    def _enable_cpu_collectives():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    # the handshake retries with bounded backoff (resilience.retry): the
    # usual first-boot race — this process dials before the coordinator
    # binds its port — is a transient RuntimeError/OSError, not a config
    # error, and should not kill a pod job that would succeed 200ms later
    from ..resilience.retry import retry_call

    if coordinator_address is not None:
        _enable_cpu_collectives()
        retry_call(
            jax.distributed.initialize,
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            site="distributed.init",
            retries=5,
            base_delay=0.2,
            max_delay=5.0,
            exceptions=(OSError, RuntimeError),
        )
        return True
    cluster_hints = ("COORDINATOR_ADDRESS", "SLURM_JOB_ID", "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS")
    if any(h in os.environ for h in cluster_hints):
        _enable_cpu_collectives()
        retry_call(
            jax.distributed.initialize,
            site="distributed.init",
            retries=5,
            base_delay=0.2,
            max_delay=5.0,
            exceptions=(OSError, RuntimeError),
        )
        return True
    return False


def dryrun_multihost(
    *,
    popsize: int = 64,
    episode_length: int = 20,
    generations: int = 2,
    env_name: str = "cartpole",
    eval_mode: str = "budget",
    seed: int = 0,
) -> dict:
    """Run a few GSPMD generations over the GLOBAL (multi-host) mesh and
    return the mesh-global scalars every host agrees on.

    Must be called AFTER ``init_distributed`` (or on a single host, where it
    degrades to the local device set). The mesh spans ``jax.devices()`` —
    the global device list — so the jitted generation program is one SPMD
    computation across all hosts; per-host Python only feeds keys and reads
    back fully-replicated reductions.
    """
    import numpy as np

    from ..algorithms.functional import pgpe, pgpe_ask, pgpe_tell
    from ..envs import make_env
    from ..neuroevolution.net import FlatParamsPolicy, Linear, Tanh
    from ..neuroevolution.net.runningnorm import RunningNorm
    from .evaluate import make_generation_step
    from .mesh import default_mesh, mesh_label

    def replicated(x):
        # a fully-replicated output is the same on every shard, so the
        # first addressable one IS the global value
        if hasattr(x, "addressable_data"):
            return np.asarray(x.addressable_data(0))
        return np.asarray(x)

    env = make_env(env_name)
    net = Linear(env.observation_size, 8) >> Tanh() >> Linear(8, env.action_size)
    policy = FlatParamsPolicy(net)
    mesh = default_mesh(("pop",))  # jax.devices() is the GLOBAL list

    generation = make_generation_step(
        env,
        policy,
        ask=lambda k, s: pgpe_ask(k, s, popsize=popsize),
        tell=pgpe_tell,
        popsize=popsize,
        mesh=mesh,
        num_episodes=1,
        episode_length=episode_length,
        eval_mode=eval_mode,
    )

    state = pgpe(
        center_init=jax.numpy.zeros(policy.parameter_count),
        center_learning_rate=0.1,
        stdev_learning_rate=0.1,
        objective_sense="max",
        stdev_init=0.1,
    )
    stats = RunningNorm(env.observation_size).stats
    key = jax.random.key(seed)
    total_steps = 0
    mean_score = 0.0
    for _ in range(int(generations)):
        key, sub = jax.random.split(key)
        state, scores, stats, steps, _telemetry = generation(state, sub, stats)
        total_steps += int(replicated(steps))
        mean_score = float(replicated(_mean_fn(scores)))
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "mesh": mesh_label(mesh),
        "devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
        "popsize": popsize,
        "generations": int(generations),
        "total_steps": total_steps,
        "mean_score": round(mean_score, 6),
        # the updated distribution rides fully replicated: its norm is a
        # cheap cross-host agreement probe on the whole tell pipeline
        "stdev_norm": round(float(replicated(_norm_fn(state.stdev))), 6),
    }


def _main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--coordinator", default=None, help="host:port of process 0")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--popsize", type=int, default=64)
    parser.add_argument("--episode-length", type=int, default=20)
    parser.add_argument("--generations", type=int, default=2)
    parser.add_argument("--env", default="cartpole")
    parser.add_argument("--eval-mode", default="budget")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    init_distributed(
        args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    out = dryrun_multihost(
        popsize=args.popsize,
        episode_length=args.episode_length,
        generations=args.generations,
        env_name=args.env,
        eval_mode=args.eval_mode,
        seed=args.seed,
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
