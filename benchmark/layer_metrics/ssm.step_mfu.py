"""Share of the chip's bf16 peak that a whole control step reaches: the
multiply-adds every lane's token needs (harness/ssm_floors.py, from the
configuration's keys: the mixers' and the attention layer's matrices, the
convolution, the state's update and readout, attention over an episode nobody
ends early, the MLPs, the tied head) over the evaluation program's device time
per step."""

LAYER = "ssm forward"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ssm_floors, ssm_scopes

    split = ssm_scopes.forward_seconds(run)
    if split is None or split["evaluation_s"] <= 0:
        return None
    readable = (run.session.decode_steps + 1) / 2.0  # t + 1 at step t, averaged over an episode
    flops = 2.0 * ssm_floors.step_macs_per_lane(run.session.ssm_sizes, readable) * run.popsize
    step_s = split["evaluation_s"] / split["steps_ran"]  # all the time, the loop op's own too
    return 100.0 * flops / ssm_scopes.peaks(run)["bf16_flops_per_s"] / step_s
