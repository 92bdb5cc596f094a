"""Compilations seen by the library's retrace sentinel (``analysis.track_compiles``)
inside the measured generations. A warm searcher compiles nothing."""

LAYER = "OO searcher"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "generation_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.counts["compiles_in_window"]
