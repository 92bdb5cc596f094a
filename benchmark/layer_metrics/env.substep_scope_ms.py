"""Device time of the ``env_step`` scope per population-wide control step:
the env's own substep and the mapping of the policy's output to an action
(noise, clipping), by the names the compiled program carries
(harness/scopes.py). What ROADMAP S2 has to shorten."""

LAYER = "env substep"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import scopes

    return scopes.per_step_ms(run, "env_step")
