"""The decoder's forward by inner scope: the evaluation program's device
seconds under ``fwd_attention``, ``fwd_kv_cache``, ``fwd_router``,
``fwd_experts``, ``fwd_dense_mlp``, ``fwd_head``
(``evotorch_tpu/observability/scopes.py:FORWARD_SCOPES``, names INSIDE
``policy_forward``; an op under two of them counts under the innermost),
joined by instruction name as harness/scopes.py joins the rollout's scopes.

The attention cache's pass (its write, the scores over it, the weighted sum)
is read under ``fwd_kv_cache``, inside ``fwd_attention``, as latent
attention's is under ``fwd_latent_cache``, once the library declares that
name among its FORWARD_SCOPES. Until then the one reader by SHAPE that is
left reads it: the ``fwd_attention`` ops whose line or fused computation holds
the cache's shape (``lm_floors.cache_shape``). The split says on stderr which
of the two it read. The change that declares the name adds it to ``READS``
and deletes the fallback (PERF.md, Open questions).

Control steps are ``session.decode_steps`` times the traced generations: the
session says what it ran; a time per step divides by as many of them as the
trace holds the ops of (``scopes.kept_steps``). Everything here returns None where there is no
device trace, no session that lowers its evaluation, or a library without the
inner scopes.
"""

import json
import re

from benchmark.harness import lm_floors, scopes

CACHE_SCOPE = "fwd_kv_cache"
#: the names the ``lm.*`` metrics read that the library declares
READS = ("fwd_attention", "fwd_router", "fwd_experts", "fwd_head")

_CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (.*)$")


def cache_instructions(text, shapes):
    """Names of the instructions whose line, or whose fused computation,
    holds an array of one of ``shapes``: the cache's ops of a library without
    ``fwd_kv_cache``."""
    bodies, members, lines = {}, None, {}
    for line in text.splitlines():
        instruction = _INSTRUCTION.match(line)
        if instruction is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                members = bodies.setdefault(header.group(1), [])
            continue
        lines[instruction.group(1)] = instruction.group(2)
        if members is not None:
            members.append(instruction.group(2))
    found = set()
    for name, rest in lines.items():
        called = _CALLS.search(rest)
        body = bodies.get(called.group(1), []) if called else []
        if any(shape in part for shape in shapes for part in [rest, *body]):
            found.add(name.lstrip("%"))
    return found


def forward_seconds(run):
    def compute():
        session = run.session
        steps = getattr(session, "decode_steps", None)
        if not scopes.lowers(run) or steps is None:
            return None
        try:
            from evotorch_tpu.observability.scopes import FORWARD_SCOPES, instruction_scopes
        except ImportError:
            return None
        text = scopes.evaluation_text(run, READS)
        ops = run.trace.evaluation_ops()
        generations = len(run.trace.generations())
        if not ops or generations <= 0:
            return None
        inner = {name.lstrip("%"): scope for name, scope in instruction_scopes(text, names=FORWARD_SCOPES).items()}
        outer = {name.lstrip("%"): scope for name, scope in instruction_scopes(text).items()}
        if not any(inner.values()):
            scopes.say("no instruction of the evaluation program carries a forward scope: nothing read")
            return None
        by_scope = CACHE_SCOPE in inner.values()
        cache_ops = (
            set()
            if by_scope
            else cache_instructions(text, lm_floors.cache_shape(session.lm_sizes, run.popsize, session.decode_steps))
        )
        seconds, forward_s, cache_s, total_s, most_executed = {}, 0.0, 0.0, 0.0, 0.0
        for hlo, (self_seconds, executions) in ops.items():
            name = scopes.instruction_name(hlo)
            total_s += self_seconds
            if outer.get(name) == "policy_forward":
                forward_s += self_seconds
                most_executed = max(most_executed, executions)
            scope = inner.get(name)
            if scope is not None:
                seconds[scope] = seconds.get(scope, 0.0) + self_seconds
                if scope == CACHE_SCOPE or (scope == "fwd_attention" and name in cache_ops):
                    cache_s += self_seconds
        split = {
            "seconds": seconds,
            "policy_forward_s": forward_s,
            "inner_share_of_policy_forward": sum(seconds.values()) / forward_s if forward_s else None,
            "cache_ops_s": cache_s,
            "cache_by": "scope" if by_scope else "shape",
            "evaluation_s": total_s,
            "coverage_percent": scopes.coverage(run, ops),
            "steps": scopes.kept_steps(steps * generations, most_executed),
            "steps_ran": steps * generations,
        }
        scopes.say("lm forward: " + json.dumps(split))
        return split

    return run.memo("lm_scopes.forward_seconds", compute)


def per_step_ms(run, *names):
    """Device milliseconds per control step under the scopes ``names``."""
    split = forward_seconds(run)
    return None if split is None else 1e3 * sum(split["seconds"].get(n, 0.0) for n in names) / split["steps"]


def cache_ms(run):
    """Device milliseconds per control step of the cache's pass; None where
    nothing of it was found."""
    split = forward_seconds(run)
    return None if split is None or split["cache_ops_s"] <= 0 else 1e3 * split["cache_ops_s"] / split["steps"]


def peaks(run):
    from benchmark.harness import device

    return device.peaks(run.device_record["kind"])


def dtype_bytes(run):
    return 2 if run.session.compute_dtype is not None else 4
