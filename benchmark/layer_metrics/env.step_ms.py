"""Device time of the rest of the evaluation program, per population-wide
control step: every op that touches no per-lane weight (harness/layers.py):
the env substep together with the eval contract's bookkeeping and the
observation statistics, which the trace cannot tell apart until the compiled
generation has named scopes."""

LAYER = "env substep"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import layers

    split = layers.split_evaluation(run)
    return None if split is None else 1e3 * split["rest_s"] / split["steps"]
