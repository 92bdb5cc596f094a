"""The hybrid linear-attention decoder's forward by inner scope: the
evaluation program's device seconds under ``fwd_kda`` (a KDA block outside its
state's span), ``fwd_kda_state`` (whatever touches the matrix state),
``fwd_attention`` and ``fwd_latent_cache``, ``fwd_router``, ``fwd_experts``,
``fwd_dense_mlp``, ``fwd_head``
(``evotorch_tpu/observability/scopes.py:FORWARD_SCOPES``, names INSIDE
``policy_forward``; an op under two of them counts under the innermost),
joined by instruction name as harness/scopes.py joins the rollout's scopes. By
SCOPE alone: no array's shape is looked for.

Control steps are ``session.decode_steps`` times the traced generations: the
session says what it ran; a time per step divides by as many of them as the
trace holds the ops of (``scopes.kept_steps``). Everything here returns None
where there is no device trace, no session that lowers its evaluation, or a
library without the KDA state's scope (a checkout from before the block).
"""

import json

from benchmark.harness import kda_floors, scopes

STATE_SCOPE = "fwd_kda_state"
#: the names the ``kda.*`` metrics read that the library declares
READS = (STATE_SCOPE, "fwd_kda")


def forward_seconds(run):
    def compute():
        steps = getattr(run.session, "decode_steps", None)
        if not scopes.lowers(run) or steps is None:
            return None
        try:
            from evotorch_tpu.observability.scopes import FORWARD_SCOPES, instruction_scopes
        except ImportError:
            return None
        if STATE_SCOPE not in FORWARD_SCOPES:
            return None
        text = scopes.evaluation_text(run, READS)
        ops = run.trace.evaluation_ops()
        generations = len(run.trace.generations())
        if not ops or generations <= 0:
            return None
        inner = {name.lstrip("%"): scope for name, scope in instruction_scopes(text, names=FORWARD_SCOPES).items()}
        outer = {name.lstrip("%"): scope for name, scope in instruction_scopes(text).items()}
        if STATE_SCOPE not in inner.values():
            scopes.say("no instruction of the evaluation program carries the KDA state's scope: nothing read")
            return None
        seconds, forward_s, total_s, most_executed = {}, 0.0, 0.0, 0.0
        for hlo, (self_seconds, executions) in ops.items():
            name = scopes.instruction_name(hlo)
            total_s += self_seconds
            if outer.get(name) == "policy_forward":
                forward_s += self_seconds
                most_executed = max(most_executed, executions)
            scope = inner.get(name)
            if scope is not None:
                seconds[scope] = seconds.get(scope, 0.0) + self_seconds
        split = {
            "seconds": seconds,
            "policy_forward_s": forward_s,
            "inner_share_of_policy_forward": sum(seconds.values()) / forward_s if forward_s else None,
            "evaluation_s": total_s,
            "coverage_percent": scopes.coverage(run, ops),
            "steps": scopes.kept_steps(steps * generations, most_executed),
            "steps_ran": steps * generations,
        }
        scopes.say("kda forward: " + json.dumps(split))
        return split

    return run.memo("kda_scopes.forward_seconds", compute)


def per_step_ms(run, scope):
    """Device milliseconds per control step under ``scope``."""
    split = forward_seconds(run)
    return None if split is None else 1e3 * split["seconds"].get(scope, 0.0) / split["steps"]


def updates_as_configured(run):
    """Whether the program rewrote exactly the states the configuration
    says, every lane's in every held KDA layer at every step, over the last
    evaluation (``kda_state_updates``). It decides only whether a floor is
    read, never what the floor is."""
    session = run.session
    counters = session.policy_counters()
    expected = kda_floors.expected_updates_per_step(session.kda_sizes, run.popsize) * session.decode_steps
    return bool(counters) and counters.get("kda_state_updates") == expected


def peaks(run):
    from benchmark.harness import device

    return device.peaks(run.device_record["kind"])


def dtype_bytes(run):
    return 2 if run.session.compute_dtype is not None else 4
