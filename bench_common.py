"""Shared scaffolding for bench.py and bench_multichip.py: backend setup
(``evotorch_tpu.resilience.setup_backend`` — the CPU on explicit request, else
an accelerator or an error), BENCH_* env-var parsing, and the policy builder —
one place, so the two benchmarks cannot silently diverge."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from evotorch_tpu.resilience import device_record, setup_backend  # noqa: E402,F401


def bench_config(use_cpu: bool, *, cpu_episode_length: int = 100) -> dict:
    """Parse the BENCH_* knobs (on the CPU, defaults shrink: a CPU run
    checks correctness and counts, never speed).

    The popsize / episode-length / hidden / env defaults are mirrored by
    the autotuner CLI (observability/autotune.py:_shape_from_args) — KEEP
    THEM IN SYNC: tuned-config cache hits require exact shape equality,
    so a drifted default silently downgrades every lookup to fallback."""
    import jax.numpy as jnp

    return {
        "popsize": int(os.environ.get("BENCH_POPSIZE", 1024 if use_cpu else 10_000)),
        "episode_length": int(
            os.environ.get(
                "BENCH_EPISODE_LENGTH", cpu_episode_length if use_cpu else 200
            )
        ),
        "generations": int(os.environ.get("BENCH_GENERATIONS", 3)),
        # opt-in bf16: changes the measured compute dtype, so the default
        # stays comparable with previously recorded f32 baselines
        "compute_dtype": (
            jnp.bfloat16 if os.environ.get("BENCH_BF16", "0") == "1" else None
        ),
        "eval_mode": os.environ.get("BENCH_EVAL_MODE", "budget"),
        # BENCH_TELEMETRY=0 compiles the accumulator-free rollout programs —
        # the A/B baseline proving the zero-sync telemetry costs nothing
        # (docs/observability.md); default on
        "telemetry": os.environ.get("BENCH_TELEMETRY", "1") != "0",
        # BENCH_HEALTH=0 compiles the health-plane-free (schema v3) eval
        # programs and drops the score_mean/score_std columns — both the
        # overhead A/B baseline for the search-health plane and the
        # byte-compat escape hatch (docs/observability.md "Search health");
        # default on (meaningful only with telemetry on)
        "health": os.environ.get("BENCH_HEALTH", "1") != "0",
        # BENCH_GROUPS=G (with telemetry on) assigns round-robin group ids
        # across the population and switches the telemetry wire to the
        # per-group (G, 14) matrix — the per-group accounting overhead A/B
        # (docs/observability.md "Per-group telemetry & SLOs"); 0/1 = off
        "num_groups": int(os.environ.get("BENCH_GROUPS", "0")),
        # BENCH_LEDGER=0 skips the program-ledger capture (one extra AOT
        # trace+compile per contract, outside every timed region) and with
        # it the compile_seconds / flops_per_step / peak_hbm_bytes /
        # model_efficiency columns — the output line is then byte-compatible
        # with pre-ledger rounds (docs/observability.md "Program ledger")
        "ledger": os.environ.get("BENCH_LEDGER", "1") != "0",
        # BENCH_LOWRANK=k: evaluate a low-rank-structured population of rank k
        # (the MXU path for wide policies, net/lowrank.py); 0 = dense
        "lowrank": int(os.environ.get("BENCH_LOWRANK", "0")),
        # BENCH_TRUNK_DELTA=1: evaluate a shared-trunk + per-lane
        # low-rank-delta population (docs/policies.md) — the per-lane forward
        # becomes ONE shared-weight GEMM over the whole popsize x obs batch
        # plus a cheap rank-k correction — and run the in-process interleaved
        # dense A/B (`trunk_delta_speedup` on the line). Rank / lane blocking
        # resolve like the refill schedule: explicit knobs override, else the
        # tuned-config cache's `policy` group, else rank 4 / no blocking.
        "trunk_delta": os.environ.get("BENCH_TRUNK_DELTA", "0") == "1",
        "trunk_rank": (
            int(os.environ["BENCH_TRUNK_RANK"])
            if "BENCH_TRUNK_RANK" in os.environ
            else None
        ),
        "trunk_block": (
            int(os.environ["BENCH_TRUNK_BLOCK"])
            if "BENCH_TRUNK_BLOCK" in os.environ
            else None
        ),
        # BENCH_SPAN=K fuses K generations per device dispatch
        # (parallel.make_training_span — ask→eval→tell scanned into ONE
        # donated program) and runs the in-process interleaved span-vs-
        # host-loop A/B (`span_speedup` on the line); "auto" consults the
        # tuned-config cache's `span` group (the `--group span` autotuner
        # winner, fallback 8). Unset = no span measurement, line
        # byte-compatible. episodes_compact is host-orchestrated and cannot
        # be fused; its span A/B runs on the budget contract instead
        # (`span_ab_mode` says which contract was measured).
        "span": os.environ.get("BENCH_SPAN"),
        "span_ab_repeats": int(os.environ.get("BENCH_SPAN_AB_REPEATS", "3")),
        # BENCH_SERVE=1 runs the multi-tenant serving A/B
        # (evotorch_tpu/serving, docs/serving.md): BENCH_SERVE_TENANTS
        # concurrent searches packed through ONE EvalServer's resident
        # episodes_refill program vs the same searches dispatched
        # sequentially standalone (`serve_speedup` on the line, plus
        # `serve_occupancy` and the per-tenant queue-wait quantiles).
        # Off by default, line byte-compatible.
        "serve": os.environ.get("BENCH_SERVE", "0") == "1",
        "serve_tenants": int(os.environ.get("BENCH_SERVE_TENANTS", "4")),
        "serve_ab_repeats": int(os.environ.get("BENCH_SERVE_AB_REPEATS", "3")),
        "env_name": os.environ.get("BENCH_ENV", "humanoid"),
        "env_kwargs": json.loads(os.environ.get("BENCH_ENV_ARGS", "{}")),
        # lane-compaction tuning (episodes_compact only): chunk size between
        # host width-decisions, and the width-menu floor — the knobs to sweep
        # on real hardware
        "compact_chunk": int(os.environ.get("BENCH_COMPACT_CHUNK", "25")),
        "compact_chunk_explicit": "BENCH_COMPACT_CHUNK" in os.environ,
        "compact_min_width": (
            int(os.environ["BENCH_COMPACT_MINWIDTH"])
            if "BENCH_COMPACT_MINWIDTH" in os.environ
            else None
        ),
        # lane-refill tuning (episodes_refill only): the fixed lane width W
        # (default: engine picks ~work/8) and the refill period (refill every
        # k-th step; >1 amortizes the refill gather/reset at the cost of
        # finished lanes idling up to k-1 steps)
        "refill_width": (
            int(os.environ["BENCH_REFILL_WIDTH"])
            if "BENCH_REFILL_WIDTH" in os.environ
            else None
        ),
        "refill_period": int(os.environ.get("BENCH_REFILL_PERIOD", "1")),
        "refill_period_explicit": "BENCH_REFILL_PERIOD" in os.environ,
        # BENCH_TUNED=0 disables the tuned-config cache consult (and the
        # tuned_config_source column), keeping the line AND the measured
        # configs byte-compatible with pre-autotuner rounds. Default on:
        # with no explicit BENCH_REFILL_*/BENCH_COMPACT_* knobs the refill /
        # compaction schedules come from observability/tuned_configs.json
        # when this (env, popsize, machine) was tuned
        # (docs/observability.md "The autotuner").
        "tuned": os.environ.get("BENCH_TUNED", "1") != "0",
        # BENCH_BACKEND=mujoco: ALSO measure the real-MuJoCo host path (sync
        # chunked loop vs the pipelined refill scheduler) and append the
        # mj_* columns to the JSON line. Default off: the four bespoke-sim
        # contracts and their output stay byte-compatible.
        "mj_backend": os.environ.get("BENCH_BACKEND", "") == "mujoco",
        "mj_env": os.environ.get("BENCH_MJ_ENV", "Hopper-v5"),
        # 512 is past the refill crossover on this box (the drain tail — one
        # straggler's worth of low-occupancy rounds per eval — amortizes with
        # popsize; bench_curves/hopper_v5_pipeline_r7.json has 256 vs 512)
        "mj_popsize": int(os.environ.get("BENCH_MJ_POPSIZE", "512")),
        "mj_num_envs": int(os.environ.get("BENCH_MJ_NUM_ENVS", "32")),
        # the env's own -v5 horizon (1000): no artificial cap — straggler
        # episodes are exactly what separates the two schedulers
        "mj_episode_length": int(os.environ.get("BENCH_MJ_EPISODE_LENGTH", "1000")),
        # None = the scheduler's auto block split (2 when >1 core, else 1)
        "mj_blocks": (
            int(os.environ["BENCH_MJ_BLOCKS"]) if "BENCH_MJ_BLOCKS" in os.environ else None
        ),
        "mj_repeats": int(os.environ.get("BENCH_MJ_REPEATS", "1")),
    }


def _use_tuned_cache(cfg: dict, params) -> bool:
    # BENCH_ENV_ARGS mutates the env without changing its cache label, so a
    # tuned entry for the plain env would be wrong evidence — skip the
    # cache; likewise when the caller cannot say which policy size the
    # schedule would serve (params is part of the cache key)
    return cfg["tuned"] and not cfg["env_kwargs"] and params is not None


def _tuned_shape(cfg: dict, params, mesh_label: str = "none") -> dict:
    from evotorch_tpu.observability.timings import canonical_env_label, dtype_label

    return {
        "env": canonical_env_label(cfg["env_name"]),
        "popsize": cfg["popsize"],
        "episode_length": cfg["episode_length"],
        "num_episodes": 1,  # every bench contract evaluates one episode
        "params": params,
        "dtype": dtype_label(cfg["compute_dtype"]),
        # "none" for the single-device bench; bench_multichip looks up
        # under its own mesh label (a schedule tuned unsharded is not
        # evidence for a sharded layout — parallel.mesh.mesh_label)
        "mesh": mesh_label,
    }


def tuned_compact(cfg: dict, *, n_shards: int = 1, params=None, mesh_label: str = "none"):
    """Lane-compaction runner kwargs + ``tuned_config_source`` provenance:
    explicit ``BENCH_COMPACT_*`` knobs override; else (``BENCH_TUNED=1``,
    the default) the tuned-config cache entry for this
    (env, popsize, params, dtype, machine); else the runner defaults.
    ``params`` is the bench policy's parameter count (part of the cache
    key — a schedule tuned for one policy size is not evidence for
    another). Width knobs are GLOBAL; pass ``n_shards`` to translate for
    the per-shard runner."""
    from evotorch_tpu.observability.timings import resolve_knobs

    explicit = {
        "chunk_size": cfg["compact_chunk"] if cfg["compact_chunk_explicit"] else None,
        "min_width": cfg["compact_min_width"],
    }
    config, source = resolve_knobs(
        explicit,
        "compact",
        _tuned_shape(cfg, params, mesh_label),
        use_cache=_use_tuned_cache(cfg, params),
    )
    kwargs = {"chunk_size": int(config.get("chunk_size", cfg["compact_chunk"]))}
    if config.get("min_width") is not None:
        kwargs["min_width"] = max(1, int(config["min_width"]) // n_shards)
    return kwargs, source


def compact_kwargs(cfg: dict, *, n_shards: int = 1, params=None, mesh_label: str = "none") -> dict:
    """The kwargs half of :func:`tuned_compact` (kept for callers that
    don't report provenance)."""
    return tuned_compact(cfg, n_shards=n_shards, params=params, mesh_label=mesh_label)[0]


def tuned_refill(cfg: dict, *, n_shards: int = 1, params=None, mesh_label: str = "none"):
    """Lane-refill engine kwargs + ``tuned_config_source`` provenance —
    same precedence and cache key as :func:`tuned_compact`. The width
    knob is GLOBAL; pass ``n_shards`` to translate (flooring, like the
    other convenience knobs) for a per-shard sharded rollout."""
    from evotorch_tpu.observability.timings import resolve_knobs

    explicit = {
        "width": cfg["refill_width"],
        "period": cfg["refill_period"] if cfg["refill_period_explicit"] else None,
    }
    config, source = resolve_knobs(
        explicit,
        "refill",
        _tuned_shape(cfg, params, mesh_label),
        use_cache=_use_tuned_cache(cfg, params),
    )
    kwargs = {
        "refill_period": int(config.get("period") or cfg["refill_period"])
    }
    if config.get("width") is not None:
        kwargs["refill_width"] = max(1, int(config["width"]) // n_shards)
    return kwargs, source


def refill_kwargs(cfg: dict, *, n_shards: int = 1, params=None, mesh_label: str = "none") -> dict:
    """The kwargs half of :func:`tuned_refill` (kept for callers that
    don't report provenance)."""
    return tuned_refill(cfg, n_shards=n_shards, params=params, mesh_label=mesh_label)[0]


def tuned_policy(cfg: dict, *, params=None, mesh_label: str = "none"):
    """Trunk-delta policy-form knobs (``rank``, ``trunk_block``) +
    ``tuned_config_source`` provenance — same precedence and cache key as
    the schedule knobs, under the autotuner's ``policy`` group
    (observability/autotune.py ``PolicyHarness``). Fallback: rank 4 (the
    harness's cheapest candidate) and no lane blocking."""
    from evotorch_tpu.observability.timings import resolve_knobs

    explicit = {"rank": cfg["trunk_rank"], "trunk_block": cfg["trunk_block"]}
    config, source = resolve_knobs(
        explicit,
        "policy",
        _tuned_shape(cfg, params, mesh_label),
        use_cache=_use_tuned_cache(cfg, params),
    )
    return {
        "rank": int(config.get("rank") or 4),
        "trunk_block": int(config.get("trunk_block") or 0),
    }, source


def tuned_span(cfg: dict, *, params=None, mesh_label: str = "none"):
    """The fused-span length K + ``tuned_config_source`` provenance —
    same precedence and cache key as the schedule knobs, under the
    autotuner's ``span`` group (observability/autotune.py ``SpanHarness``).
    ``BENCH_SPAN=K`` overrides; ``BENCH_SPAN=auto`` consults the cache;
    fallback 8 (the acceptance shape's measured sweet spot)."""
    from evotorch_tpu.observability.timings import resolve_knobs

    raw = cfg["span"]
    explicit = {"span": None if raw in (None, "auto") else int(raw)}
    config, source = resolve_knobs(
        explicit,
        "span",
        _tuned_shape(cfg, params, mesh_label),
        use_cache=_use_tuned_cache(cfg, params),
    )
    return max(1, int(config.get("span") or 8)), source


def bench_hidden() -> list:
    """The BENCH_HIDDEN layer widths as a list of ints (default ``[64, 64]``)
    — also the ``hidden`` column bench.py stamps on ledger-carrying lines so
    bench_curves/ files are self-describing across policy-shape sweeps."""
    return [int(h) for h in os.environ.get("BENCH_HIDDEN", "64,64").split(",") if h]


def _bench_mlp(obs_dim: int, act_dim: int):
    """The BENCH_HIDDEN-sized MLP, shared by every bench policy builder so
    the bespoke-sim contracts, the real-MuJoCo A/B and the program ledger's
    gate programs cannot silently bench different architectures."""
    from evotorch_tpu.neuroevolution.net import tanh_mlp

    return tanh_mlp(obs_dim, act_dim, bench_hidden())


def build_policy(env):
    """The benchmark policy: an MLP sized by BENCH_HIDDEN (default "64,64" —
    the MXU-headroom knob; ES rollouts are env-bound, so the policy can grow
    orders of magnitude before it shows up in steps/s)."""
    from evotorch_tpu.neuroevolution.net import FlatParamsPolicy

    return FlatParamsPolicy(_bench_mlp(env.observation_size, env.action_size))


def measure_mujoco(cfg: dict) -> dict:
    """Real-MuJoCo host-path A/B: env-steps/sec of the PR-2 synchronous
    fixed-chunk loop vs the pipelined refill scheduler, same `MjVecEnv`,
    same population (aggressive random linear policies — the skewed
    episode-length regime evaluation actually sees at init). Returns the
    ``mj_*`` columns bench.py appends behind ``BENCH_BACKEND=mujoco``."""
    import time

    import gymnasium as gym
    import numpy as np

    from evotorch_tpu.envs.mujoco.mjvecenv import MjVecEnv
    from evotorch_tpu.neuroevolution.net import FlatParamsPolicy
    from evotorch_tpu.neuroevolution.net.hostvecenv import (
        run_host_pipelined_rollout,
        run_host_vectorized_rollout,
    )

    env_id = cfg["mj_env"]
    popsize = cfg["mj_popsize"]
    num_envs = cfg["mj_num_envs"]
    episode_length = cfg["mj_episode_length"]
    num_blocks = cfg["mj_blocks"]

    probe = gym.make(env_id)
    obs_dim = int(np.prod(probe.observation_space.shape))
    act_dim = int(np.prod(probe.action_space.shape))
    probe.close()
    policy = FlatParamsPolicy(_bench_mlp(obs_dim, act_dim))
    rng = np.random.default_rng(0)
    # numpy, NOT jnp: the rollout loops slice this matrix right before every
    # jitted forward dispatch, and a numpy argument is ~3x cheaper per
    # dispatch than a committed device array on this jax (CLAUDE.md r7 note)
    params = rng.normal(size=(popsize, policy.parameter_count)).astype(np.float32)

    def fresh_vec():
        vec = MjVecEnv(lambda: gym.make(env_id), num_envs)
        vec.seed(range(1000, 1000 + num_envs))
        return vec

    def run_sync_chunked(vec):
        total = 0
        for start in range(0, popsize, num_envs):
            result = run_host_vectorized_rollout(
                vec,
                policy,
                params[start : start + num_envs],
                num_episodes=1,
                episode_length=episode_length,
            )
            total += result["interactions"]
        return total

    def run_pipelined(vec):
        result = run_host_pipelined_rollout(
            vec,
            policy,
            params,
            num_episodes=1,
            episode_length=episode_length,
            mode="pipelined",
            num_blocks=num_blocks,
            # honor BENCH_TUNED=0 at this layer too: with it the measured
            # mj_* configs stay byte-compatible with pre-autotuner rounds
            use_tuned_cache=cfg["tuned"],
        )
        return result["interactions"]

    # warmup: compile every jit signature the TIMED runs will hit. The
    # gathered forward is keyed on the FULL (popsize, L) params shape, so the
    # pipelined warmup must pass the whole matrix; the chunked loop's forward
    # is keyed on chunk width, so warm the full chunk and (if popsize is not
    # a multiple of num_envs) the short final chunk too.
    vec = fresh_vec()
    run_host_vectorized_rollout(
        vec, policy, params[:num_envs], num_episodes=1, episode_length=3
    )
    if popsize % num_envs:
        run_host_vectorized_rollout(
            vec, policy, params[: popsize % num_envs], num_episodes=1, episode_length=3
        )
    run_host_pipelined_rollout(
        vec,
        policy,
        params,
        num_episodes=1,
        episode_length=3,
        mode="pipelined",
        num_blocks=num_blocks,
        use_tuned_cache=cfg["tuned"],
    )
    vec.close()

    out = {}
    repeats = cfg.get("mj_repeats", 1)
    for name, runner in (("sync", run_sync_chunked), ("pipelined", run_pipelined)):
        rates = []
        for _ in range(repeats):
            vec = fresh_vec()
            t0 = time.perf_counter()
            steps = runner(vec)
            elapsed = time.perf_counter() - t0
            vec.close()
            rates.append(steps / elapsed)
            print(
                f"[mujoco/{name}] {steps} env-steps in {elapsed:.2f}s "
                f"({steps / elapsed:.0f} steps/s)",
                file=sys.stderr,
            )
        out[name] = {"steps_per_sec": sorted(rates)[len(rates) // 2]}

    return {
        "mj_env": env_id,
        "mj_popsize": popsize,
        "mj_num_envs": num_envs,
        "mj_episode_length": episode_length,
        "mj_blocks": num_blocks,
        "mj_sync_steps_per_sec": round(out["sync"]["steps_per_sec"], 1),
        "mj_steps_per_sec": round(out["pipelined"]["steps_per_sec"], 1),
        "mj_pipeline_speedup": round(
            out["pipelined"]["steps_per_sec"] / out["sync"]["steps_per_sec"], 3
        ),
    }


def ledger_columns(record, *, steps_per_sec, steps_per_generation, param_count=None):
    """The per-contract program-ledger columns bench.py/bench_multichip.py
    append when BENCH_LEDGER is on. Nullable by design: a backend whose
    cost/memory analysis is unavailable emits nulls, never crashes
    (observability.programs guarded accessors).

    ``flops_per_step`` is the cost model's FLOPs per counted env-step — a
    program-cost fingerprint, NOT a utilization proxy: XLA's HloCostAnalysis
    counts a while-loop body ONCE (the rollout loop is undercounted by its
    trip count) while one-shot tensor work like a dense ask's (N, L)
    materialization is counted in full, so comparing policy FORMS on it
    inverts the truth. ``model_efficiency`` is therefore MFU-style: the
    achieved MODEL FLOP rate — 2 * param_count useful FLOPs per counted
    env-step (every lane-step runs the policy once; overhead and redundant
    work count AGAINST utilization) — over the device's published peak
    (observability.report.DEVICE_PEAKS, keyed by ``device_kind``; null on
    the CPU, an error on a device the table does not know). Needs
    ``param_count``; callers without it get a null column."""
    import jax

    from evotorch_tpu.observability.report import peak_flops

    flops_per_step = None
    if record.flops and steps_per_generation:
        flops_per_step = record.flops / steps_per_generation
    efficiency = None
    peak = peak_flops(jax.devices()[0])
    if param_count and steps_per_sec and peak:
        efficiency = 2.0 * param_count * steps_per_sec / peak
    return {
        "compile_seconds": round(record.compile_seconds, 3),
        "flops_per_step": (
            None if flops_per_step is None else round(flops_per_step, 2)
        ),
        "peak_hbm_bytes": record.peak_bytes,
        "model_efficiency": (
            None if efficiency is None else round(efficiency, 6)
        ),
    }


def fresh_pgpe_state(parameter_count: int):
    import jax.numpy as jnp

    from evotorch_tpu.algorithms.functional import pgpe

    return pgpe(
        center_init=jnp.zeros(parameter_count, dtype=jnp.float32),
        center_learning_rate=0.1,
        stdev_learning_rate=0.1,
        objective_sense="max",
        stdev_init=0.1,
    )
