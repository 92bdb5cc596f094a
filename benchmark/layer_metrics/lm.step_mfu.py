"""Share of the chip's bf16 peak that a whole control step reaches: the
multiply-adds every lane's token needs (harness/lm_floors.py, from the
configuration's keys) over the evaluation program's device time per step."""

LAYER = "lm forward"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_floors, lm_scopes

    split = lm_scopes.forward_seconds(run)
    if split is None or split["evaluation_s"] <= 0:
        return None
    flops = 2.0 * lm_floors.step_macs_per_lane(run.session.lm_sizes) * run.popsize
    step_s = split["evaluation_s"] / split["steps_ran"]  # all the time, the loop op's own too
    return 100.0 * flops / lm_scopes.peaks(run)["bf16_flops_per_s"] / step_s
