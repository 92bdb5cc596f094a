"""Device ms a generation (median, first device) of the programs named
``jit_evotorch_tpu_grad_*``: ranking the fitnesses and the gradient estimate.
0 where the update ranks inside its own program (the trunk-delta ``tell``);
nothing where the library names no program (harness/phases.py)."""

LAYER = "OO searcher"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import phases

    return phases.named_ms(run, "grad")
