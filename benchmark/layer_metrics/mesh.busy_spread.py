"""(busiest - least busy device) over the busiest, from the trace: how unevenly
the chips were loaded."""

LAYER = "mesh"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.trace.busy_spread()
