"""Device programs a generation starts on the first device (median over the
traced generations; ``XLA Modules`` events that begin inside the
``bench.generation`` span), the evaluation included: what a short generation
pays per program (harness/phases.py)."""

LAYER = "OO searcher"
UNIT = "count"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import phases

    return phases.dispatches(run)
