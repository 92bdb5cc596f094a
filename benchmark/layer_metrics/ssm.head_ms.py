"""Device milliseconds per control step under the decoder's inner scope
``fwd_head``: the gather of the tied embedding's rows on the way in, the
final norm and the product with the same leaf on the way out
(harness/ssm_scopes.py)."""

LAYER = "ssm forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ssm_scopes

    return ssm_scopes.per_step_ms(run, "fwd_head")
