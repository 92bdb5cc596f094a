"""Device ms a generation (median, first device) of the programs named
``jit_evotorch_tpu_update_*``: the trunk-delta form's donated ``tell`` whole; of
the dense form's update only what is jitted (its eager ops are
``searcher.unnamed_ms``). Nothing where the library names no program
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

    return phases.named_ms(run, "update")
