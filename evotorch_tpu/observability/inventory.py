"""The registered program inventory of the stack's jitted entry points.

One place declares *which* compiled programs constitute the framework —
the four eval-contract rollout programs (plus their trunk-delta policy
forms, ``docs/policies.md``), the sharded evaluator, the gaussian
functional ask/tell, the batched functional search, and the
functional and GSPMD whole-generation steps (dense and trunk-delta) —
so the program ledger
(:mod:`~evotorch_tpu.observability.programs`), the report CLI and the
fast-tier perf-regression gate all see the same surface.

Everything here builds programs at a configurable *gate shape*
(:class:`GateConfig`, tiny by default so a full capture costs seconds of
compile on the CPU mesh, not minutes). FLOPs and per-lane memory scale
~linearly in ``popsize``/``episode_length`` for fixed program structure,
so a structural regression at the gate shape is a flagship regression too
— the gate catches it in tier-1 (``report --flagship`` captures the same
inventory at the flagship shape on the chip).

Heavy imports stay inside the builders: ``observability`` is imported by
``algorithms`` at class-definition time, so importing envs/algorithms at
module scope here would cycle.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .programs import (
    ProgramLedger,
    ProgramRecord,
    abstract_like as _abstract,
    ledger,
    program_key,
)

__all__ = [
    "GateConfig",
    "ProgramSpec",
    "build_specs",
    "capture_compact_chunk",
    "capture_inventory",
    "donated_programs",
    "inventory_keys",
]


@dataclass(frozen=True)
class GateConfig:
    """Shape configuration for an inventory capture. The defaults are the
    fast-tier gate shapes (checked into ``ledger_baseline.json``); the
    report CLI's ``--flagship`` swaps in benchmark-scale values."""

    env_name: str = "cartpole"
    popsize: int = 8
    episode_length: int = 16
    hidden: Tuple[int, ...] = (8,)
    refill_width: int = 4
    chunk_size: int = 8
    trunk_rank: int = 4
    batched_searches: int = 4
    batched_dim: int = 8
    batched_popsize: int = 8
    batched_generations: int = 3
    span: int = 3


@dataclass(frozen=True)
class ProgramSpec:
    """One registered program: a stable (name, shape) identity plus a
    thunk that captures it into a ledger."""

    name: str
    shape: Dict[str, Any] = field(compare=False, default_factory=dict)
    capture: Callable[[ProgramLedger], ProgramRecord] = field(
        compare=False, default=None
    )

    @property
    def key(self) -> str:
        return program_key(self.name, self.shape)


# ---------------------------------------------------------------------------
# jitted program builders (lru_cached: one wrapper per config, never per call)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _env_policy(env_name: str, hidden: Tuple[int, ...]):
    """Cached: env/policy identity keys the jitted-program lru_caches below
    (and vecrl's engine caches), so repeated build_specs/donated_programs
    calls reuse compiled programs instead of retracing per call."""
    from ..envs import make_env
    from ..neuroevolution.net import FlatParamsPolicy, tanh_mlp

    env = make_env(env_name)
    net = tanh_mlp(env.observation_size, env.action_size, hidden)
    return env, FlatParamsPolicy(net)


def _fresh_pgpe_state(parameter_count: int):
    import jax.numpy as jnp

    from ..algorithms.functional import pgpe

    return pgpe(
        center_init=jnp.zeros(parameter_count, dtype=jnp.float32),
        center_learning_rate=0.1,
        stdev_learning_rate=0.1,
        objective_sense="max",
        stdev_init=0.1,
    )


@functools.lru_cache(maxsize=1)
def _gaussian_programs():
    import jax

    from ..algorithms.functional import pgpe_ask, pgpe_tell

    ask = jax.jit(pgpe_ask, static_argnames=("popsize",))
    tell = jax.jit(pgpe_tell, donate_argnums=(0,))
    return ask, tell


@functools.lru_cache(maxsize=8)
def _batched_search_program(num_searches: int, dim: int, popsize: int):
    """The examples/functional_batched_search.py program shape: N
    independent CEM searches scanned as ONE jitted, state-donating
    program (batch dims on the state) — built on the shared
    scanned-generations idiom (``algorithms.functional.make_search_span``),
    the same helper the example itself uses."""
    import functools as ft

    import jax.numpy as jnp

    from ..algorithms.functional import cem_ask, cem_tell, make_search_span

    return make_search_span(
        lambda pop: jnp.sum(pop**2, axis=-1),
        ask=ft.partial(cem_ask, popsize=popsize),
        tell=cem_tell,
        metrics=lambda pop, fit: jnp.min(fit, axis=-1),
    )


@functools.lru_cache(maxsize=8)
def _trunk_delta_batch(policy, popsize: int, rank: int):
    """One concrete trunk-delta population at the gate shape (cached: the
    rollout captures only need its ShapeDtypeStruct skeleton, but the
    skeleton must carry the REAL pytree structure — factors treedef
    included — for the capture to lower the dispatched program)."""
    import jax

    from ..algorithms.functional import pgpe_ask_trunk_delta

    state = _fresh_pgpe_state(policy.parameter_count)
    return pgpe_ask_trunk_delta(
        jax.random.key(0), state, popsize=popsize, rank=rank, policy=policy
    )


@functools.lru_cache(maxsize=8)
def _trunk_generation_program(
    env, policy, popsize: int, episode_length: int, rank: int
):
    """The trunk-delta analog of ``bench.generation``: factored ask ->
    budget rollout (shared-trunk + per-lane delta forward) -> factored
    tell, one jitted program donating the optimizer state."""
    import jax

    from ..algorithms.functional import (
        pgpe_ask_trunk_delta,
        pgpe_tell_trunk_delta,
    )
    from ..neuroevolution.net.vecrl import run_vectorized_rollout

    def _generation(state, key, stats):
        k1, k2 = jax.random.split(key)
        values = pgpe_ask_trunk_delta(
            k1, state, popsize=popsize, rank=rank, policy=policy
        )
        result = run_vectorized_rollout(
            env,
            policy,
            values,
            k2,
            stats,
            num_episodes=1,
            episode_length=episode_length,
            eval_mode="budget",
        )
        new_state = pgpe_tell_trunk_delta(state, values, result.scores)
        return new_state, result.total_steps, result.scores

    return jax.jit(_generation, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _bench_generation_program(env, policy, popsize: int, episode_length: int):
    """A functional generation on one device: PGPE ask -> budget rollout ->
    tell, one jitted program donating the optimizer state."""
    import jax

    from ..algorithms.functional import pgpe_ask, pgpe_tell
    from ..neuroevolution.net.vecrl import run_vectorized_rollout

    def _generation(state, key, stats):
        k1, k2 = jax.random.split(key)
        values = pgpe_ask(k1, state, popsize=popsize)
        result = run_vectorized_rollout(
            env,
            policy,
            values,
            k2,
            stats,
            num_episodes=1,
            episode_length=episode_length,
            eval_mode="budget",
        )
        new_state = pgpe_tell(state, values, result.scores)
        return new_state, result.total_steps, result.scores

    return jax.jit(_generation, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _gspmd_generation_program(env, policy, mesh_size, popsize, episode_length):
    """parallel.make_generation_step at the gate shape: ask -> GSPMD-sharded
    rollout -> tell compiled as ONE global program over a ("pop",) mesh with
    the evolution state donated end-to-end (docs/sharding.md)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..algorithms.functional import pgpe_ask, pgpe_tell
    from ..parallel.evaluate import make_generation_step

    mesh = Mesh(np.asarray(jax.devices()[:mesh_size]), axis_names=("pop",))
    return make_generation_step(
        env,
        policy,
        ask=lambda k, s: pgpe_ask(k, s, popsize=popsize),
        tell=pgpe_tell,
        popsize=popsize,
        mesh=mesh,
        num_episodes=1,
        episode_length=episode_length,
        eval_mode="budget",
    )


@functools.lru_cache(maxsize=8)
def _gspmd_span_program(env, policy, mesh_size, popsize, episode_length, span):
    """parallel.make_training_span at the gate shape: ``span`` generations
    of the GSPMD ask -> rollout -> tell body scanned into ONE donated
    program (docs/sharding.md "Fused multi-generation training spans")."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..algorithms.functional import pgpe_ask, pgpe_tell
    from ..parallel.evaluate import make_training_span

    mesh = Mesh(np.asarray(jax.devices()[:mesh_size]), axis_names=("pop",))
    return make_training_span(
        env,
        policy,
        ask=lambda k, s: pgpe_ask(k, s, popsize=popsize),
        tell=pgpe_tell,
        popsize=popsize,
        span=span,
        mesh=mesh,
        num_episodes=1,
        episode_length=episode_length,
        eval_mode="budget",
    )


def capture_compact_chunk(
    led: ProgramLedger,
    env,
    policy,
    popsize: int,
    episode_length: int,
    *,
    chunk_size: int,
    compute_dtype=None,
    telemetry: bool = True,
    name: str = "rollout.episodes_compact.chunk",
    shape: Optional[Dict[str, Any]] = None,
) -> ProgramRecord:
    """Capture the lane-compacting runner's full-width chunk program — the
    dominant cost of the host-orchestrated ``episodes_compact`` contract
    (the width-descent runs the SAME program at narrower shapes)."""
    import jax
    import jax.numpy as jnp

    from ..neuroevolution.net.runningnorm import RunningNorm
    from ..neuroevolution.net.vecrl import _compacting_fns

    max_t = env.max_episode_steps if env.max_episode_steps is not None else 1000
    max_t = min(max_t, int(episode_length))
    hard_cap = max_t + 1
    init_fn, chunk_fn, _, _ = _compacting_fns(
        env,
        policy,
        1,
        max_t,
        hard_cap,
        False,
        None,
        None,
        None,
        compute_dtype,
        collect_telemetry=bool(telemetry),
    )
    params = jnp.zeros((popsize, policy.parameter_count), dtype=jnp.float32)
    stats = RunningNorm(env.observation_size).stats
    carry, fwd_params = init_fn(params, jax.random.key(0), stats)
    return led.capture(
        name,
        chunk_fn,
        _abstract(fwd_params),
        _abstract(carry),
        shape=shape,
        num_steps=int(chunk_size),
    )


# ---------------------------------------------------------------------------
# the inventory
# ---------------------------------------------------------------------------


def _mesh_size(popsize: int) -> int:
    """The largest usable ("pop",) mesh for this process: every device when
    the popsize divides evenly, else the largest divisor of popsize."""
    import jax

    n = len(jax.devices())
    while n > 1 and popsize % n != 0:
        n -= 1
    return n


def build_specs(cfg: Optional[GateConfig] = None) -> List[ProgramSpec]:
    """The registered program list at ``cfg``'s shapes. Building specs is
    cheap (host objects only); compiles happen in each spec's capture."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from ..neuroevolution.net.runningnorm import RunningNorm
    from ..neuroevolution.net.vecrl import run_vectorized_rollout
    from ..parallel.evaluate import make_sharded_rollout_evaluator

    cfg = cfg if cfg is not None else GateConfig()
    env, policy = _env_policy(cfg.env_name, cfg.hidden)
    L = policy.parameter_count
    params_sds = jax.ShapeDtypeStruct((cfg.popsize, L), jnp.float32)
    stats = RunningNorm(env.observation_size).stats
    base_shape = {
        "env": cfg.env_name,
        "popsize": cfg.popsize,
        "episode_length": cfg.episode_length,
        "params": L,
    }
    specs: List[ProgramSpec] = []

    def add(name, shape, capture):
        specs.append(ProgramSpec(name=name, shape=shape, capture=capture))

    def rollout_capture(mode, shape, **extra):
        def _capture(led):
            return led.capture(
                f"rollout.{mode}",
                run_vectorized_rollout,
                env,
                policy,
                params_sds,
                jax.random.key(0),
                stats,
                shape=shape,
                num_episodes=1,
                episode_length=cfg.episode_length,
                eval_mode=mode,
                **extra,
            )

        return _capture

    for mode in ("budget", "episodes"):
        add(f"rollout.{mode}", base_shape, rollout_capture(mode, base_shape))
    refill_shape = dict(base_shape, width=cfg.refill_width)
    add(
        "rollout.episodes_refill",
        refill_shape,
        rollout_capture("episodes_refill", refill_shape, refill_width=cfg.refill_width),
    )

    trunk_shape = dict(base_shape, rank=cfg.trunk_rank)

    def trunk_rollout_capture(mode, name, shape, **extra):
        def _capture(led):
            batch = _trunk_delta_batch(policy, cfg.popsize, cfg.trunk_rank)
            return led.capture(
                name,
                run_vectorized_rollout,
                env,
                policy,
                _abstract(batch),
                jax.random.key(0),
                stats,
                shape=shape,
                num_episodes=1,
                episode_length=cfg.episode_length,
                eval_mode=mode,
                **extra,
            )

        return _capture

    add(
        "rollout.budget.trunk_delta",
        trunk_shape,
        trunk_rollout_capture("budget", "rollout.budget.trunk_delta", trunk_shape),
    )
    trunk_refill_shape = dict(trunk_shape, width=cfg.refill_width)
    add(
        "rollout.episodes_refill.trunk_delta",
        trunk_refill_shape,
        trunk_rollout_capture(
            "episodes_refill",
            "rollout.episodes_refill.trunk_delta",
            trunk_refill_shape,
            refill_width=cfg.refill_width,
        ),
    )

    compact_shape = dict(base_shape, chunk=cfg.chunk_size)

    def compact_capture(led):
        return capture_compact_chunk(
            led,
            env,
            policy,
            cfg.popsize,
            cfg.episode_length,
            chunk_size=cfg.chunk_size,
            shape=compact_shape,
        )

    add("rollout.episodes_compact.chunk", compact_shape, compact_capture)

    mesh_size = _mesh_size(cfg.popsize)
    sharded_shape = dict(base_shape, mesh=mesh_size)

    def sharded_capture(led):
        # the SAME mesh the shape metadata records: every popsize keeps a
        # valid (divisible) pop axis, not just multiples of the device count
        mesh = Mesh(np.asarray(jax.devices()[:mesh_size]), axis_names=("pop",))
        evaluator = make_sharded_rollout_evaluator(
            env,
            policy,
            mesh=mesh,
            num_episodes=1,
            episode_length=cfg.episode_length,
            eval_mode="budget",
        )
        fn = evaluator.program_builder(False, cfg.popsize)
        return led.capture(
            "sharded_evaluator",
            fn,
            params_sds,
            jax.random.key(0),
            stats,
            shape=sharded_shape,
        )

    add("sharded_evaluator", sharded_shape, sharded_capture)

    ask_shape = {"popsize": cfg.popsize, "params": L}

    def ask_capture(led):
        ask, _ = _gaussian_programs()
        return led.capture(
            "gaussian.ask",
            ask,
            jax.random.key(0),
            _abstract(_fresh_pgpe_state(L)),
            shape=ask_shape,
            popsize=cfg.popsize,
        )

    def tell_capture(led):
        _, tell = _gaussian_programs()
        return led.capture(
            "gaussian.tell",
            tell,
            _abstract(_fresh_pgpe_state(L)),
            params_sds,
            jax.ShapeDtypeStruct((cfg.popsize,), jnp.float32),
            shape=ask_shape,
        )

    add("gaussian.ask", ask_shape, ask_capture)
    add("gaussian.tell", ask_shape, tell_capture)

    batched_shape = {
        "searches": cfg.batched_searches,
        "dim": cfg.batched_dim,
        "popsize": cfg.batched_popsize,
        "generations": cfg.batched_generations,
    }

    def batched_capture(led):
        fn = _batched_search_program(
            cfg.batched_searches, cfg.batched_dim, cfg.batched_popsize
        )
        state, keys = _batched_search_args(cfg)
        return led.capture(
            "functional_batched_search",
            fn,
            _abstract(state),
            _abstract(keys),
            shape=batched_shape,
        )

    add("functional_batched_search", batched_shape, batched_capture)

    def bench_capture(led):
        fn = _bench_generation_program(env, policy, cfg.popsize, cfg.episode_length)
        return led.capture(
            "bench.generation",
            fn,
            _abstract(_fresh_pgpe_state(L)),
            jax.random.key(0),
            stats,
            shape=base_shape,
        )

    add("bench.generation", base_shape, bench_capture)

    def trunk_bench_capture(led):
        fn = _trunk_generation_program(
            env, policy, cfg.popsize, cfg.episode_length, cfg.trunk_rank
        )
        return led.capture(
            "bench.generation.trunk_delta",
            fn,
            _abstract(_fresh_pgpe_state(L)),
            jax.random.key(0),
            stats,
            shape=trunk_shape,
        )

    add("bench.generation.trunk_delta", trunk_shape, trunk_bench_capture)

    def gspmd_capture(led):
        fn = _gspmd_generation_program(
            env, policy, mesh_size, cfg.popsize, cfg.episode_length
        )
        return led.capture(
            "gspmd.generation",
            fn,
            _abstract(_fresh_pgpe_state(L)),
            jax.random.key(0),
            stats,
            shape=sharded_shape,
        )

    add("gspmd.generation", sharded_shape, gspmd_capture)

    span_shape = dict(sharded_shape, span=cfg.span)

    def span_capture(led):
        fn = _gspmd_span_program(
            env, policy, mesh_size, cfg.popsize, cfg.episode_length, cfg.span
        )
        return led.capture(
            "gspmd.training_span",
            fn,
            _abstract(_fresh_pgpe_state(L)),
            jax.random.split(jax.random.key(0), cfg.span),
            stats,
            shape=span_shape,
        )

    add("gspmd.training_span", span_shape, span_capture)
    return specs


def _batched_search_args(cfg: GateConfig):
    import jax

    from ..algorithms.functional import cem

    centers = (
        jax.random.normal(
            jax.random.key(0), (cfg.batched_searches, cfg.batched_dim)
        )
        * 3.0
    )
    state = cem(
        center_init=centers,
        parenthood_ratio=0.5,
        objective_sense="min",
        stdev_init=2.0,
        stdev_max_change=0.2,
    )
    keys = jax.random.split(jax.random.key(1), cfg.batched_generations)
    return state, keys


def inventory_keys(cfg: Optional[GateConfig] = None) -> List[str]:
    return [spec.key for spec in build_specs(cfg)]


def capture_inventory(
    cfg: Optional[GateConfig] = None,
    led: Optional[ProgramLedger] = None,
    *,
    strict: bool = True,
) -> Tuple[List[ProgramRecord], Dict[str, str]]:
    """Capture every registered program into ``led`` (the process ledger by
    default). Returns ``(records, errors)``; with ``strict`` (the default)
    the first capture failure raises instead."""
    led = led if led is not None else ledger
    records: List[ProgramRecord] = []
    errors: Dict[str, str] = {}
    for spec in build_specs(cfg):
        try:
            records.append(spec.capture(led))
        except Exception as e:  # pragma: no cover - strict re-raises
            if strict:
                raise
            errors[spec.key] = f"{type(e).__name__}: {e}"
    return records, errors


# ---------------------------------------------------------------------------
# the runtime donation sweep surface
# ---------------------------------------------------------------------------


def donated_programs(cfg: Optional[GateConfig] = None):
    """``(name, fn, args, donate_argnums)`` for every ``donate_argnums``
    entry point the repo registers — the gaussian tell, the one-device and
    GSPMD generation steps, the GSPMD training span, and the batched
    functional search. Each call builds
    FRESH concrete arguments (the verification executes the program and
    consumes the donated buffers). The dynamic complement of graftlint's
    static ``donation`` checker: these assert XLA *applied* the aliasing."""
    import jax
    import jax.numpy as jnp

    from ..neuroevolution.net.runningnorm import RunningNorm

    cfg = cfg if cfg is not None else GateConfig()
    env, policy = _env_policy(cfg.env_name, cfg.hidden)
    L = policy.parameter_count
    stats = RunningNorm(env.observation_size).stats
    _, tell = _gaussian_programs()
    mesh_size = _mesh_size(cfg.popsize)
    values = jnp.zeros((cfg.popsize, L), dtype=jnp.float32)
    fitnesses = jnp.zeros((cfg.popsize,), dtype=jnp.float32)
    batched_state, batched_keys = _batched_search_args(cfg)
    return [
        (
            "gaussian.tell",
            tell,
            (_fresh_pgpe_state(L), values, fitnesses),
            (0,),
        ),
        (
            "bench.generation",
            _bench_generation_program(env, policy, cfg.popsize, cfg.episode_length),
            (_fresh_pgpe_state(L), jax.random.key(0), stats),
            (0,),
        ),
        (
            "bench.generation.trunk_delta",
            _trunk_generation_program(
                env, policy, cfg.popsize, cfg.episode_length, cfg.trunk_rank
            ),
            (_fresh_pgpe_state(L), jax.random.key(0), stats),
            (0,),
        ),
        (
            "gspmd.generation",
            _gspmd_generation_program(
                env, policy, mesh_size, cfg.popsize, cfg.episode_length
            ),
            (_fresh_pgpe_state(L), jax.random.key(0), stats),
            (0,),
        ),
        (
            "gspmd.training_span",
            _gspmd_span_program(
                env, policy, mesh_size, cfg.popsize, cfg.episode_length, cfg.span
            ),
            (
                _fresh_pgpe_state(L),
                jax.random.split(jax.random.key(0), cfg.span),
                stats,
            ),
            (0,),
        ),
        (
            "functional_batched_search",
            _batched_search_program(
                cfg.batched_searches, cfg.batched_dim, cfg.batched_popsize
            ),
            (batched_state, batched_keys),
            (0,),
        ),
    ]
