"""Device time of the ``env_reset`` scope per population-wide control step:
the fresh reset built inside the loop (``budget`` builds one for every lane
in every step) and the per-lane select between fresh and stepped state
(harness/scopes.py)."""

LAYER = "env substep"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import scopes

    return scopes.per_step_ms(run, "env_reset")
