"""Device time of the ``contract`` scope per population-wide control step:
PRNG chains, done flags, reward adjustments, scores, episode and step
counters, activity masks, the loop's condition (harness/scopes.py)."""

LAYER = "eval contract"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import scopes

    return scopes.per_step_ms(run, "contract")
