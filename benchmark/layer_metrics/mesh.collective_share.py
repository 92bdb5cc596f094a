"""Device time of all-reduce / all-gather / collective-permute / reduce-scatter /
all-to-all ops over the traced window, averaged over the chips."""

LAYER = "mesh"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.trace.collective_share()
