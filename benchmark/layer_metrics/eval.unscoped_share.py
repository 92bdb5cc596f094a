"""Share of the evaluation program's device time in ops that no scope
names: the loop's own overhead and compiler-made ops without metadata
(harness/scopes.py prints the ten largest on stderr). Large means a scope is
missing or the compile cache predates the scopes."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import scopes

    return scopes.unscoped_share(run)
