"""Persistent-compile-cache misses (``observability.cache_stats``) at the end of
set-up. 0 in every run of a cell after its first in a checkout."""

LAYER = "compile cache"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.cache_at_setup_end["misses"]
