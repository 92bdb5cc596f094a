"""Bytes of KDA state the population carries as policy state (the
convolutions' windows and the matrix states of every held KDA layer and
lane, as stored), counted by the program (``kda_state_bytes`` of
``VecNE.last_policy_report``). Nothing where the report has no such key."""

LAYER = "kda state"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    counters = run.session.policy_counters()
    if not counters or "kda_state_bytes" not in counters:
        return None
    return counters["kda_state_bytes"] / 1e9
