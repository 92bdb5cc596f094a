"""Ms a generation (median) of the ``bench.generation`` span during which NO
program ran on the first device: the gaps between the ``XLA Modules`` events.
``device.idle_share`` goes by ops and also counts the gaps inside a program;
this one makes the split of ``searcher.outside_eval_ms`` exact
(harness/phases.py)."""

LAYER = "OO searcher"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import phases

    return phases.idle_ms(run)
