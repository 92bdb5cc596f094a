"""The decoder's forward by inner scope: the evaluation program's device
seconds under ``fwd_attention``, ``fwd_router``, ``fwd_experts``,
``fwd_dense_mlp``, ``fwd_head`` (``evotorch_tpu/observability/scopes.py:
FORWARD_SCOPES``, names INSIDE ``policy_forward``), joined by instruction
name as harness/scopes.py joins the rollout's scopes, and the seconds of the
ops that touch the attention cache (recognised by the cache's shape in their
own line or in the computation they fuse).

Control steps are ``session.decode_steps`` times the traced generations: the
session says what it ran. Everything here returns None where there is no
device trace, no session that lowers its evaluation, or a library without the
inner scopes.
"""

import json
import re

from benchmark.harness import lm_floors, scopes

_CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (.*)$")


def cache_instructions(text, shapes):
    """Names of the instructions whose line, or whose fused computation,
    holds an array of one of ``shapes``."""
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
        problem = getattr(session, "problem", None)
        lower = getattr(problem, "lower_evaluation", None)
        steps = getattr(session, "decode_steps", None)
        if run.trace is None or not run.trace.planes or lower is None or steps is None:
            return None
        try:
            from evotorch_tpu.observability.scopes import FORWARD_SCOPES, instruction_scopes
        except ImportError:
            return None
        text = scopes.compiled_text(lower, run.popsize, instruction_scopes)
        ops = run.trace.evaluation_ops()
        generations = len(run.trace.generations())
        if not ops or generations <= 0:
            return None
        inner = {
            name.lstrip("%"): scope
            for name, scope in instruction_scopes(text, names=FORWARD_SCOPES).items()
        }
        outer = {name.lstrip("%"): scope for name, scope in instruction_scopes(text).items()}
        if not any(inner.values()):
            scopes.say("no instruction of the evaluation program carries a forward scope: nothing read")
            return None
        cache_ops = cache_instructions(
            text, lm_floors.cache_shape(session.lm_sizes, run.popsize, session.decode_steps)
        )
        seconds, forward_s, cache_s, total_s = {}, 0.0, 0.0, 0.0
        for hlo, (self_seconds, _) in ops.items():
            name = scopes.instruction_name(hlo)
            total_s += self_seconds
            if outer.get(name) == "policy_forward":
                forward_s += self_seconds
            scope = inner.get(name)
            if scope is not None:
                seconds[scope] = seconds.get(scope, 0.0) + self_seconds
                if scope == "fwd_attention" and name in cache_ops:
                    cache_s += self_seconds
        split = {
            "seconds": seconds,
            "policy_forward_s": forward_s,
            "inner_share_of_policy_forward": sum(seconds.values()) / forward_s if forward_s else None,
            "cache_ops_s": cache_s,
            "evaluation_s": total_s,
            "steps": steps * generations,
        }
        scopes.say("lm forward: " + json.dumps(split))
        return split

    return run.memo("lm_scopes.forward_seconds", compute)


def per_step_ms(run, scope):
    split = forward_seconds(run)
    return None if split is None else 1e3 * split["seconds"].get(scope, 0.0) / split["steps"]


def peaks(run):
    from benchmark.harness import device

    return device.peaks(run.device_record["kind"])


def dtype_bytes(run):
    return 2 if run.session.compute_dtype is not None else 4
