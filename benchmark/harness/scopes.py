"""The evaluation program's device time by named scope.

The library names the work inside the compiled rollout
(``evotorch_tpu/observability/scopes.py``: policy forward, env substep, env
reset, observation statistics, the contract's bookkeeping, what runs once per
program), but a trace recorded without the HLO proto (harness/trace.py) does
not carry the names: an ``XLA Ops`` event is named by its bare HLO text,
``%fusion.12 = ...``. The compiled program's text carries both, so the
INSTRUCTION NAME is the join key: the scope of ``%fusion.12`` from
``compiled.as_text()``, its self seconds from the trace.

The text is that of ``session.problem.lower_evaluation(popsize).compile()``:
the library lowers the program ``evaluate`` dispatches, and in the process
that ran it nothing is left to compile. Self times (a ``while`` op's time
excludes its body's ops) add up to the program's busy time, so the scopes and
the unscoped rest add up to it. A reader finds its work by the scope the
program gives it, not by an array's shape (one fallback is left, in
harness/lm_scopes.py, until the library names the attention cache's pass).

jax's persistent compile cache ignores scope names in its key (it strips
debug info from the module it hashes), so an executable cached before a scope
was added or moved (by the parent commit, where two checkouts share
``JAX_COMPILATION_CACHE_DIR``) comes back without it. It is the same program,
instruction for instruction, so where a name a reader asks for is missing
from the text, the reader says so on stderr and compiles it once more, past
the cache, for the text alone; the trace still joins. Where even that names
no scope it reads nothing: never a row of zeros.
"""

import json
import re
import sys
import time

#: the rollout's scopes that every engine's program carries (``obs_norm``
#: only where observations are normalised)
ROLLOUT_READS = ("policy_forward", "env_step", "env_reset", "contract", "rollout_edges")


def say(message):
    print(f"benchmark: scopes: {message}", file=sys.stderr)


def instruction_name(hlo_text):
    """``%fusion.12 = bf16[8,64]{...} fusion(...)`` -> ``fusion.12``."""
    return hlo_text.partition(" = ")[0].strip().lstrip("%")


def module_name(name):
    """``jit_run_vectorized_rollout(123456)`` -> ``jit_run_vectorized_rollout``."""
    return re.sub(r"\(\d+\)$", "", name)


def carries(text, name):
    """Whether some ``op_name`` of the compiled ``text`` has the scope ``name``."""
    return re.search(rf"[/(\"]evotorch_tpu\.{re.escape(name)}[/)\"]", text) is not None


def evaluation_text(run, reads):
    """The compiled text of the run's evaluation program, compiled once a run;
    where a name of ``reads`` is missing from it, compiled once more from a
    compile that the persistent cache and jax's in-process caches cannot
    answer."""
    held = run.memo("scopes.evaluation_text", dict)
    lower = run.session.problem.lower_evaluation
    if "text" not in held:
        held["text"], held["past_cache"] = lower(run.popsize).compile().as_text(), False
    missing = [name for name in reads if not carries(held["text"], name)]
    if missing and not held["past_cache"]:
        say(
            f"no instruction of the compiled evaluation program carries {missing}: the"
            " executable in the compile cache may predate them (the cache key ignores"
            " scope names; rm -rf compile_cache cures it); compiling once more, past"
            " the cache, for the text"
        )
        import jax

        from evotorch_tpu.observability.compilecache import past_persistent_cache

        with past_persistent_cache():
            jax.clear_caches()  # or lower() hands back the executable the run already holds
            held["text"], held["past_cache"] = lower(run.popsize).compile().as_text(), True
    return held["text"]


def split_by_scope(ops, scopes, label):
    """``ops``: ``{HLO text: [self seconds, executions]}`` of the evaluation
    program (trace.evaluation_ops); ``scopes``: ``{instruction name: scope or
    None}`` of its compiled text (``instruction_scopes``); ``label``:
    trace.op_label. Seconds by scope, the seconds of ops with no scope (an op
    the text does not list among them) with their ten largest labels, and the
    control steps: the executions of the most-executed policy-forward op
    (``step_op``). None where no instruction has a scope."""
    by_name = {name.lstrip("%"): scope for name, scope in scopes.items()}
    if not any(by_name.values()):
        return None
    seconds, unscoped, steps, step_op = {}, {}, 0.0, None
    for text, (self_seconds, executions) in ops.items():
        scope = by_name.get(instruction_name(text))
        if scope is None:
            name = label(text)
            unscoped[name] = unscoped.get(name, 0.0) + self_seconds
            continue
        seconds[scope] = seconds.get(scope, 0.0) + self_seconds
        if scope == "policy_forward" and executions > steps:
            steps, step_op = executions, text
    top = sorted(unscoped.items(), key=lambda item: -item[1])[:10]
    return {
        "seconds": seconds,
        "unscoped_s": sum(unscoped.values()),
        "unscoped_top": [[name, s] for name, s in top],
        "steps": steps,
        "step_op": step_op,
    }


def reduce_trace(trace, text, instruction_scopes):
    """The split for a loaded trace and the compiled text of its evaluation
    program, with the traced generations; None (and a line on stderr) where
    the text is another program's or names no scope."""
    from benchmark.harness.trace import op_label

    traced = trace.evaluation_module()
    compiled = re.match(r"HloModule ([^\s,]+)", text)
    if traced is None or compiled is None or module_name(traced) != compiled.group(1):
        say(
            f"the trace's evaluation program is {traced!r}, lower_evaluation() gave"
            f" {compiled.group(1) if compiled else None!r}: not joined"
        )
        return None
    ops = trace.evaluation_ops()
    split = split_by_scope(ops, instruction_scopes(text), op_label)
    if split is None:
        say("no instruction of the compiled evaluation program carries a scope: nothing read")
        return None
    if split["steps"] <= 0:
        say("no policy_forward op ran inside the traced window: no control step to divide by")
        return None
    split["generations"] = len(trace.generations())
    # beside it, for PERF.md: what the metadata alone names; the difference is
    # the compiler-made ops (async copies, relayouts, fusion roots) that took
    # their neighbours' scope
    named = split_by_scope(ops, instruction_scopes(text, inherit=False), op_label)
    split["by_metadata"] = {key: named[key] for key in ("seconds", "unscoped_s", "unscoped_top")}
    return split


def lowers(run):
    """Whether the run has a device trace and a problem that lowers its
    evaluation (``VecNE.lower_evaluation``): not a CPU rehearsal (nothing is
    lowered there), nor a driver without such a problem."""
    problem = getattr(run.session, "problem", None)
    return run.trace is not None and bool(run.trace.planes) and hasattr(problem, "lower_evaluation")


def scope_seconds(run):
    """``reduce_trace`` for a traced run that ``lowers``; None where it does
    not, or where there is nothing to join."""

    def compute():
        if not lowers(run):
            return None
        from evotorch_tpu.observability.scopes import instruction_scopes

        started = time.perf_counter()
        text = evaluation_text(run, ROLLOUT_READS)
        loaded = time.perf_counter()
        split = reduce_trace(run.trace, text, instruction_scopes)
        if split is not None:  # for PERF.md: the split itself, and what reading it cost
            cost = {"lower_compile_s": loaded - started, "reduce_s": time.perf_counter() - loaded}
            say(json.dumps({**split, **cost}))
        return split

    return run.memo("scopes.scope_seconds", compute)


def per_step_ms(run, scope):
    """Device milliseconds of ``scope`` per population-wide control step."""
    split = scope_seconds(run)
    return None if split is None else 1e3 * split["seconds"].get(scope, 0.0) / split["steps"]


def per_generation_ms(run, scope):
    split = scope_seconds(run)
    if split is None or split["generations"] <= 0:
        return None
    return 1e3 * split["seconds"].get(scope, 0.0) / split["generations"]


def unscoped_share(run):
    """Seconds of the evaluation program's ops that no scope names, over its
    self seconds, in percent."""
    split = scope_seconds(run)
    if split is None:
        return None
    total = sum(split["seconds"].values()) + split["unscoped_s"]
    return 100.0 * split["unscoped_s"] / total if total > 0 else None


def lanes_per_step(run):
    """The lanes one control step runs on a chip: the lane-step slots the
    evaluation loop executed (the telemetry's ``capacity``, the denominator of
    ``contract.occupancy``) over its control steps (the executions of the
    most-executed policy-forward op), in the traced generations whose
    telemetry the window decoded, over the chips. None where no traced
    generation's telemetry was decoded, and where the count is not what the
    traffic lets a step run, known without the program: popsize over chips in
    ``budget`` and ``episodes``, at most popsize (the working width) under
    refill. A program that reported more slots than it ran would otherwise
    raise ``policy.roofline_share`` with no faster forward."""
    split = scope_seconds(run)
    capacities = (run.counts or {}).get("capacity_by_call") or []
    generations = run.trace.generations() if split is not None else []
    if len(generations) != len(capacities):
        return None
    lane_steps = steps = 0.0
    for (low, high), capacity in zip(generations, capacities):
        if capacity is not None:
            lane_steps += capacity
            steps += run.trace.executions(split["step_op"], low, high)
    if steps <= 0:
        return None
    chips = len(run.trace.planes)
    lanes = lane_steps / steps / chips
    mode = run.workload["traffic"]["eval_mode"]
    fixed = mode in ("budget", "episodes")
    if (fixed and abs(lanes - run.popsize / chips) > 1e-6 * lanes) or lanes > run.popsize:
        say(
            f"{lanes} lanes a control step on a chip, where eval_mode {mode!r} runs"
            f" {'exactly' if fixed else 'at most'} {run.popsize / chips if fixed else run.popsize}:"
            " the telemetry's capacity does not match the trace; no floor"
        )
        return None
    return lanes


#: an op whose own time is the time of the ops it runs that the trace lacks
_CONTROL_FLOW = re.compile(r" = .*?(?<![\w.-])(?:while|conditional|call)\(")


def coverage(run, ops):
    """The self seconds of the evaluation program's traced ``ops``
    (trace.evaluation_ops), less those of its control-flow ops, as a
    percentage of the program's own device time in the traced window. Where
    the profiler dropped the ops of some control steps, their time shows as
    the loop op's own: under 100 by that much. None where the program took no
    time."""
    total = run.trace.evaluation_seconds()
    kept = sum(seconds for text, (seconds, _) in ops.items() if not _CONTROL_FLOW.search(text))
    return 100.0 * kept / total if total > 0 else None


def kept_steps(ran, most_executed):
    """The control steps whose ops a decoder's split holds: ``ran``, the
    steps the session says ran, unless the trace holds the ops of fewer (the
    executions of the most-executed ``policy_forward`` op, once a step in
    every complete trace of the decoder cells; the profiler dropped the
    rest). Then those, so that a time per step averages the steps the trace
    holds and a share of a roofline stays one."""
    if 0 < most_executed < ran:
        say(
            f"the trace holds the ops of {most_executed:g} of the {ran} control steps that"
            " ran (the rest were dropped by the profiler): per-step times divide by those"
        )
        return most_executed
    return ran
