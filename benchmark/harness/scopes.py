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
the unscoped rest add up to what harness/layers.py splits by weight shapes;
for one PR the two sources check each other.

jax's persistent compile cache ignores scope names in its key (it strips
debug info from the module it hashes), so an executable cached before the
scopes were added or moved (by the parent commit, where two checkouts share
``JAX_COMPILATION_CACHE_DIR``) comes back without them. It is the same
program, instruction for instruction, so the reader says so on stderr and
compiles it once more, past the cache, for the text alone; the trace still
joins. Where even that names no scope it reads nothing: never a row of zeros.
"""

import json
import re
import sys
import time


def say(message):
    print(f"benchmark: scopes: {message}", file=sys.stderr)


def instruction_name(hlo_text):
    """``%fusion.12 = bf16[8,64]{...} fusion(...)`` -> ``fusion.12``."""
    return hlo_text.partition(" = ")[0].strip().lstrip("%")


def module_name(name):
    """``jit_run_vectorized_rollout(123456)`` -> ``jit_run_vectorized_rollout``."""
    return re.sub(r"\(\d+\)$", "", name)


def split_by_scope(ops, scopes, label):
    """``ops``: ``{HLO text: [self seconds, executions]}`` of the evaluation
    program (trace.evaluation_ops); ``scopes``: ``{instruction name: scope or
    None}`` of its compiled text (``instruction_scopes``); ``label``:
    trace.op_label. Seconds by scope, the seconds of ops with no scope (an op
    the text does not list among them) with their ten largest labels, and the
    control steps: the executions of the most-executed policy-forward op, as
    layers.split_ops counts them. None where no instruction has a scope."""
    by_name = {name.lstrip("%"): scope for name, scope in scopes.items()}
    if not any(by_name.values()):
        return None
    seconds, unscoped, steps = {}, {}, 0.0
    for text, (self_seconds, executions) in ops.items():
        scope = by_name.get(instruction_name(text))
        if scope is None:
            name = label(text)
            unscoped[name] = unscoped.get(name, 0.0) + self_seconds
            continue
        seconds[scope] = seconds.get(scope, 0.0) + self_seconds
        if scope == "policy_forward":
            steps = max(steps, executions)
    top = sorted(unscoped.items(), key=lambda item: -item[1])[:10]
    return {
        "seconds": seconds,
        "unscoped_s": sum(unscoped.values()),
        "unscoped_top": [[name, s] for name, s in top],
        "steps": steps,
    }


def compiled_text(lower, popsize, instruction_scopes):
    """``lower(popsize).compile().as_text()``; where no instruction of it
    carries a scope, once more from a compile that the persistent cache and
    jax's in-process caches cannot answer."""
    text = lower(popsize).compile().as_text()
    if any(instruction_scopes(text, inherit=False).values()):
        return text
    say(
        "no instruction of the compiled evaluation program carries a scope: the"
        " executable in the compile cache predates the scopes (the cache key"
        " ignores them; rm -rf compile_cache cures it); compiling once more, past"
        " the cache, for the text"
    )
    import jax

    from evotorch_tpu.observability.compilecache import past_persistent_cache

    with past_persistent_cache():
        jax.clear_caches()  # or lower() hands back the executable the run already holds
        return lower(popsize).compile().as_text()


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


def scope_seconds(run):
    """``reduce_trace`` for a traced run whose session has a problem that can
    lower its evaluation (``VecNE.lower_evaluation``); None where there is no
    device trace (a CPU rehearsal: nothing is lowered), no such problem (a
    driver without one; a library from before the scopes), or nothing to join."""

    def compute():
        if run.trace is None or not run.trace.planes:
            return None
        problem = getattr(run.session, "problem", None)
        lower = getattr(problem, "lower_evaluation", None)
        if lower is None:
            return None
        from evotorch_tpu.observability.scopes import instruction_scopes

        started = time.perf_counter()
        text = compiled_text(lower, run.popsize, instruction_scopes)
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
