"""Device milliseconds per control step under the decoder's inner scope
``fwd_experts`` (the grouped product over the 8 held experts of width 1,536,
the shared expert), summed over the held layers (harness/mla_scopes.py)."""

LAYER = "mla forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import mla_scopes

    return mla_scopes.per_step_ms(run, "fwd_experts")
