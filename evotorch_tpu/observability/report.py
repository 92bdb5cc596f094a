"""Program-ledger report CLI.

``python -m evotorch_tpu.observability.report`` captures the registered
program inventory (:mod:`~evotorch_tpu.observability.inventory`) and
prints the per-program accounting table: compile wall-time, cost-model
FLOPs / bytes accessed, analyzed peak memory, the runtime-verified
donation map, and — for the rollout contracts — measured env-steps/s next
to the cost-model ceiling (analytic efficiency).

Modes:

- (default) capture at the fast-tier gate shapes and print the table;
- ``--flagship`` capture at benchmark scale (Humanoid, popsize 10,000 x 200
  steps unless ``--popsize`` / ``--episode-length`` say otherwise);
  with ``--json`` it snapshots flagship-shape peak HBM + compile seconds;
- ``--check`` assert the capture against ``ledger_baseline.json``
  (exit 1 on violations/stale — the CLI form of the tier-1 gate in
  ``tests/test_program_ledger.py``);
- ``--write-baseline`` refresh the checked-in baseline (refuses partial
  captures; run under ``--cpu`` so the values match the pytest mesh).

The cost-model ceiling divides the program's analyzed FLOPs by the
device's published peak (:data:`DEVICE_PEAKS`, keyed by ``device_kind``);
efficiency is achieved-FLOPs-rate / peak. On the CPU, which has no entry,
the efficiency column is ``-``; an accelerator the table does not know is
an error, not a default.

Without ``--cpu`` (or ``JAX_PLATFORMS=cpu``) the CLI requires an
accelerator and fails without one.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional

from ..resilience.devices import device_record, setup_backend
from .compilecache import enable_persistent_cache
from .inventory import GateConfig, capture_inventory, inventory_keys
from .programs import (
    ProgramLedger,
    compare_to_baseline,
    load_ledger_baseline,
    save_ledger_baseline,
)

#: published peaks per chip, keyed by ``jax.devices()[0].device_kind`` — the
#: one table every efficiency and roofline figure divides by
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s per chip
    "TPU v5 lite": {
        "flops_per_sec": 197e12,
        "hbm_bytes_per_sec": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak_flops(device) -> Optional[float]:
    """Published peak FLOP/s of ``device`` (a ``jax.Device``). ``None`` on
    the CPU — a CPU run has no device efficiency to report; raises on an
    accelerator that is not in :data:`DEVICE_PEAKS`."""
    if device.platform == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]["flops_per_sec"]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device.device_kind!r}; add "
            "it, with its source, to observability.report.DEVICE_PEAKS"
        ) from None


def _gate_config(args) -> GateConfig:
    from dataclasses import replace

    if args.flagship:
        base = GateConfig(
            env_name="humanoid",
            popsize=10_000,
            episode_length=200,
            hidden=(64, 64),
            chunk_size=25,
        )
    else:
        base = GateConfig()
    overrides = {}
    if args.env is not None:
        overrides["env_name"] = args.env
    if args.popsize is not None:
        overrides["popsize"] = args.popsize
    if args.episode_length is not None:
        overrides["episode_length"] = args.episode_length
    if args.hidden is not None:
        overrides["hidden"] = tuple(int(h) for h in args.hidden.split(",") if h)
    cfg = replace(base, **overrides) if overrides else base
    if args.flagship:
        # width derives from the EFFECTIVE popsize (CLI overrides included)
        # so the refill record's width= label matches the compiled program
        cfg = replace(cfg, refill_width=max(1, cfg.popsize // 8))
    return cfg


def _measure_rollouts(cfg: GateConfig, generations: int = 2) -> dict:
    """Measured env-steps/s per monolithic rollout contract at ``cfg``'s
    shapes (warmup + ``generations`` timed calls; tiny at gate shapes)."""
    import jax
    import jax.numpy as jnp

    from ..neuroevolution.net.runningnorm import RunningNorm
    from ..neuroevolution.net.vecrl import run_vectorized_rollout
    from .inventory import _env_policy

    env, policy = _env_policy(cfg.env_name, cfg.hidden)
    stats = RunningNorm(env.observation_size).stats
    params = jnp.zeros((cfg.popsize, policy.parameter_count), dtype=jnp.float32)
    measured = {}
    for mode, extra in (
        ("budget", {}),
        ("episodes", {}),
        ("episodes_refill", {"refill_width": cfg.refill_width}),
    ):
        def once(key):
            result = run_vectorized_rollout(
                env, policy, params, key, stats,
                num_episodes=1, episode_length=cfg.episode_length,
                eval_mode=mode, **extra,
            )
            jax.block_until_ready(result.scores)
            return int(result.total_steps)

        once(jax.random.key(0))  # warmup: compile outside the clock
        t0 = time.perf_counter()
        steps = 0
        for g in range(generations):
            steps += once(jax.random.key(g + 1))
        elapsed = time.perf_counter() - t0
        measured[f"rollout.{mode}"] = {
            "steps_per_call": steps / generations,
            "steps_per_sec": steps / elapsed,
            "calls_per_sec": generations / elapsed,
        }
    return measured


def _fmt(value, spec="{:g}") -> str:
    return "-" if value is None else spec.format(value)


def _donation_cell(record) -> str:
    if record.donation is None or record.donation.verified is None:
        return "-"
    if record.donation.verified:
        return f"ok({len(record.donation.donated)})"
    return f"DROPPED{list(record.donation.missing)}"


def print_table(records, measured, platform_peak) -> None:
    cols = (
        f"{'program':58s} {'compile_s':>9s} {'flops':>12s} {'bytes_acc':>12s} "
        f"{'peak_bytes':>11s} {'donation':>12s} {'steps/s':>11s} {'efficiency':>10s}"
    )
    print(cols)
    print("-" * len(cols))
    for record in sorted(records, key=lambda r: r.key):
        meas = measured.get(record.name)
        steps_per_sec = None if meas is None else meas["steps_per_sec"]
        efficiency = None
        if (
            meas is not None
            and record.flops is not None
            and platform_peak is not None
        ):
            efficiency = record.flops * meas["calls_per_sec"] / platform_peak
        print(
            f"{record.key:58s} {record.compile_seconds:9.3f} "
            f"{_fmt(record.flops):>12s} {_fmt(record.bytes_accessed):>12s} "
            f"{_fmt(record.peak_bytes):>11s} {_donation_cell(record):>12s} "
            f"{_fmt(steps_per_sec, '{:.1f}'):>11s} "
            f"{_fmt(efficiency, '{:.2%}'):>10s}"
        )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m evotorch_tpu.observability.report",
        description="Program-ledger capture: XLA cost/memory accounting, "
        "donation verification, perf-regression baseline workflow.",
    )
    parser.add_argument("--cpu", action="store_true",
                        help="force the 8-virtual-device CPU backend (use for "
                        "baseline writes: matches the pytest mesh)")
    parser.add_argument("--flagship", action="store_true",
                        help="benchmark-scale shapes (Humanoid, popsize 10,000)")
    parser.add_argument("--env", default=None)
    parser.add_argument("--popsize", type=int, default=None)
    parser.add_argument("--episode-length", type=int, default=None)
    parser.add_argument("--hidden", default=None, help="comma list, e.g. 64,64")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON line instead of the table")
    parser.add_argument("--check", action="store_true",
                        help="assert against ledger_baseline.json; exit 1 on "
                        "violations or stale entries")
    parser.add_argument("--write-baseline", action="store_true",
                        help="refresh ledger_baseline.json (refuses partial runs)")
    parser.add_argument("--baseline", default=None, help="alternate baseline path")
    parser.add_argument("--no-measure", action="store_true",
                        help="skip the timed rollout runs (table loses the "
                        "steps/s and efficiency columns)")
    args = parser.parse_args(argv)

    setup_backend(args.cpu)
    import jax

    if not (args.check or args.write_baseline):
        # the gate's fingerprints come from freshly compiled programs: an
        # executable deserialized from the cache reports a slightly larger
        # peak memory on the CPU backend (tests/test_program_ledger.py)
        enable_persistent_cache()

    cfg = _gate_config(args)
    led = ProgramLedger()
    expected = inventory_keys(cfg)
    records, errors = capture_inventory(cfg, led, strict=False)
    for key, err in sorted(errors.items()):
        print(f"capture failed: {key}: {err}", file=sys.stderr)

    measure = not args.no_measure and not args.flagship
    measured = _measure_rollouts(cfg) if measure else {}
    platform = jax.devices()[0].platform
    peak = peak_flops(jax.devices()[0])
    if args.json:
        payload = led.to_json()
        payload["measured"] = measured
        payload["peak_flops"] = peak
        payload["backend"] = device_record()
        print(json.dumps(payload))
    else:
        print_table(records, measured, peak)

    rc = 0
    if args.write_baseline:
        path = save_ledger_baseline(
            records, args.baseline, expected_keys=expected
        )
        print(f"wrote {len(records)} programs to {path}", file=sys.stderr)
    if args.check:
        baseline = load_ledger_baseline(args.baseline)
        base_platform = baseline.get("platform")
        if base_platform not in (None, platform):
            print(
                f"warning: baseline platform {base_platform!r} != "
                f"this run's {platform!r} — bands may not be comparable",
                file=sys.stderr,
            )
        violations, stale = compare_to_baseline(records, baseline)
        for message in violations:
            print(f"VIOLATION: {message}", file=sys.stderr)
        for message in stale:
            print(f"STALE: {message}", file=sys.stderr)
        if violations or stale:
            rc = 1
    if errors:
        rc = max(rc, 2)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
