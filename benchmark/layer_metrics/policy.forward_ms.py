"""Device time of the policy forward inside the evaluation program, per
population-wide control step: the self time of every op that touches per-lane
weights (the per-step slicing and relayout of the flat parameter matrix, and
the matrix-vector fusions), from the trace (harness/layers.py), over the
control steps traced. Gives way to a named scope's share once there are names."""

LAYER = "policy forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import layers

    split = layers.split_evaluation(run)
    return None if split is None else 1e3 * split["forward_s"] / split["steps"]
