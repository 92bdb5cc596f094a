"""1 - union of device-op intervals over the traced steady window of a few
generations, averaged over the chips used."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.trace.idle_share()
