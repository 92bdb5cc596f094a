"""Device time of the ``rollout_edges`` scope per generation: what the
evaluation program runs once, outside its loop: the cast of the population to
the compute dtype, the first reset and statistics, score averaging,
quarantine, telemetry (harness/scopes.py)."""

LAYER = "eval contract"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import scopes

    return scopes.per_generation_ms(run, "rollout_edges")
