"""Device ms a generation (median, first device) of programs that are neither
the evaluation program nor named for a phase: eager ops (each its own
``jit_<primitive>``: the dense update, ``nanmean``, counters), anything
``phase_jit`` missed. Says how far ``searcher.ask_ms`` / ``grad_ms`` /
``update_ms`` can be trusted, as ``eval.unscoped_share`` does for the scopes;
the largest such programs go to stderr (harness/phases.py)."""

LAYER = "OO searcher"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import phases

    return phases.unnamed_ms(run)
