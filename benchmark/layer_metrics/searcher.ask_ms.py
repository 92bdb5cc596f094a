"""Device ms a generation (median over the traced generations, first device) of
the programs the searcher's ``ask`` phase dispatches, found by their name
``jit_evotorch_tpu_ask_*`` on the trace's ``XLA Modules`` line: sampling the
population (dense, low-rank, trunk-delta). Nothing where the library names no
program (harness/phases.py)."""

LAYER = "OO searcher"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import phases

    return phases.named_ms(run, "ask")
