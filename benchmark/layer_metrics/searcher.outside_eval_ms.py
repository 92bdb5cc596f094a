"""Median per generation of the ``bench.generation`` host span minus the device
time of the evaluation program inside it (the XLA module with most device
time): ask, gradient, update, eager ops and gaps together."""

LAYER = "OO searcher"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.trace.outside_eval_ms()
