"""One run of one cell: set-up, window, checks, the result line.

The harness knows nothing of policies, environments or searchers. It finds
the cell's files by name (loader.py), asks the cell's driver for a *session*
(drivers/oo_searcher.py documents the protocol), runs and times the session's
``generation()`` calls, and holds every call to the counts the session says
one call makes (check.py). The comparison with the plain reference is the
session's own (``reference_checks``): a driver knows what it runs.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import time

from benchmark.harness.loader import BenchmarkFiles

MIN_GENERATIONS = 3  # a window holds at least this many whole generations


def parse(argv):
    parser = argparse.ArgumentParser(prog="benchmark/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument(
        "--rehearse",
        action="store_true",
        help="run on the CPU at the configuration's rehearsal popsize (widths"
        " unchanged); the line names the device cpu and measures nothing",
    )
    return parser.parse_args(argv)


def main(argv, *, root, t0):
    args = parse(argv)
    phases = {}  # seconds since process start at the end of each part of set-up

    def phase(name):
        phases[name] = time.perf_counter() - t0

    files = BenchmarkFiles(root)
    workload = files.workload(args.workload)
    config = files.config(workload["config"])
    chips = int(workload["chips"])

    if args.rehearse:  # before jax is imported: the CPU, one virtual device per chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    import jax  # noqa: F401  (the backend is chosen here, after the variables above)

    import evotorch_tpu

    if not os.path.abspath(evotorch_tpu.__file__).startswith(root + os.sep):
        raise SystemExit(f"evotorch_tpu was imported from outside this checkout: {evotorch_tpu.__file__}")

    from benchmark.harness import check, device, timing
    from evotorch_tpu.analysis import track_compiles
    from evotorch_tpu.observability import cache_stats, enable_persistent_cache

    try:
        devices = device.require_chips(chips, rehearse=args.rehearse)
    except device.NoAccelerator as error:
        print(f"benchmark: {error}", file=sys.stderr)
        return 3
    cache_dir = enable_persistent_cache()  # <checkout>/compile_cache, or where the variable says
    phase("imports_and_backend")

    # the configuration's ``rehearse`` block names its keys of scale (popsize,
    # the reference's sample sizes; never a width) with their rehearsal values
    scale = {key: (value if args.rehearse else config[key]) for key, value in config["rehearse"].items()}
    driver = files.driver(workload["driver"])
    run = timing.Run(
        session=driver.build(files, config, workload, args.seed, scale),
        files=files,
        workload=workload,
        config=config,
        scale=scale,
        rehearse=args.rehearse,
    )
    session = run.session
    per_call = session.per_call  # what one generation() call runs and counts
    phase("build")

    with track_compiles() as compile_log:
        run.compile_log = compile_log
        # -- set-up: warm up this cell's own programs, then the reference checks
        for _ in range(int(workload["warmup_generations"])):
            run.generation()
        phase("warmup_generations")
        checks = session.reference_checks(args.seed)
        phase("reference_checks")
        run.cache_at_setup_end = dict(cache_stats())
        run.setup_s = time.perf_counter() - t0

        # -- the window: whole generations only
        first = len(run.times)
        if args.trace:
            from benchmark.harness import trace

            trace_dir = os.path.join(root, "benchmark_out", "trace", args.workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            with trace.recording(trace_dir):
                for _ in range(int(workload["traced_generations"])):
                    run.generation()
        else:
            deadline = time.perf_counter() + args.seconds
            while (
                time.perf_counter() < deadline
                or (len(run.times) - first) * per_call["generations"] < MIN_GENERATIONS
            ):
                run.generation()
    # before anything else allocates: the cell's own high-water mark
    run.device_record = device.device_record(devices, session.devices)

    # -- after the clock: counts, verdict
    failed, run.counts = check.check_counts(run.marks[first:], run.compiles[first:], per_call)
    calls = len(run.times) - first
    attempted = calls * per_call["generations"]
    correct = bool(attempted > 0 and not failed and all(c["ok"] for c in checks.values()))
    window_s = sum(run.times[first:])
    summary = timing.summarize([t / per_call["generations"] for t in run.times[first:]])
    measured = {
        # counted interactions of the window's whole generations over their
        # wall time: a stalled generation shows here, not in generation_s
        "env_steps_per_s": run.counts["interactions"] / window_s if window_s > 0 else 0.0,
        "generation_s": summary["median"],
        "setup_s": run.setup_s,
    }

    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": len(failed) * per_call["generations"],
    }
    if args.trace:
        (found,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        run.trace = trace.load(found, chips=chips)
        shutil.rmtree(trace_dir)  # tens to hundreds of MB, and reduced by now
        metrics = {}
        for entry in files.metrics("per_layer", args.workload):
            module = files.layer_metric(entry["name"])
            if not module.applies(workload):
                continue
            value = module.measure(run)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(run.device_record)
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    else:
        result["metrics"] = {
            entry["name"]: {"value": float(measured[entry["name"]]), "unit": entry["unit"]}
            for entry in files.metrics("end_to_end", args.workload)
        }
        result["device"] = dict(run.device_record)
    # what the driver does not read, for PERF.md and for whoever debugs a run:
    # on the lines BEFORE the last
    detail = {
        "workload": args.workload,
        "seed": args.seed,
        "rehearse": args.rehearse,
        "scale": scale,
        "per_call": per_call,
        "measured": measured,
        "generation_times": summary,
        "call_times": run.times[first:],
        "counts": run.counts,
        "failed_calls": failed,
        "checks": checks,
        "cache": {"dir": cache_dir, **{k: run.cache_at_setup_end[k] for k in ("hits", "misses")}},
        "compiles_in_setup": int(sum(run.compiles[:first])),
        "setup_phases_s": phases,
        "run_s": time.perf_counter() - t0,
    }
    print("detail: " + json.dumps(detail))
    print(
        f"{args.workload}: {summary['count']} generations, median {summary['median']:.4f} s,"
        f" slowest {summary['max']:.4f} s"
        + (
            f", p{summary['tail_percentile']} {summary['tail']:.4f} s"
            if summary["tail"] is not None
            else ", too few samples for a tail percentile"
        )
        + f", set-up {run.setup_s:.1f} s, cache misses {run.cache_at_setup_end['misses']}"
    )
    print(json.dumps(result))
    return 0
