"""Device milliseconds per control step under ``fwd_experts`` in the
route-ahead decoder: the post-attention norm and the grouped product of the
held ReGLU experts under the routes decided ahead, summed over the held
layers (harness/lm_scopes.py)."""

LAYER = "ahead experts"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_scopes

    return lm_scopes.per_step_ms(run, "fwd_experts")
