"""Device time of the ``obs_norm`` scope per population-wide control step:
normalising the observations and updating (across chips: merging) their
running statistics (harness/scopes.py)."""

LAYER = "eval contract"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import scopes

    return scopes.per_step_ms(run, "obs_norm")
