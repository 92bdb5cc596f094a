"""Fixture metric of a layer only the fixture's cell has: evaluations counted
per ``generation()`` call of the window."""

LAYER = "black-box objective"
UNIT = "count"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.counts["interactions"] / run.counts["calls"]
