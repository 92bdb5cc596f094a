"""Pairs on the fullest held expert over the mean pairs per held expert, summed
over steps and sparse layers of the last generation (the decoder counts both
in its state; ``VecNE.last_policy_report``). 1 is an even load."""

LAYER = "lm experts"
UNIT = "x"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    counters = run.session.policy_counters()
    if not counters or not counters["expert_pairs_held"]:
        return None
    first, past = run.session.lm_sizes["experts_held"]
    return counters["expert_pairs_fullest"] / (counters["expert_pairs_held"] / (past - first))
