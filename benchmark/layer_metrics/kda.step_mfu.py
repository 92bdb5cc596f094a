"""Share of the chip's bf16 peak that a whole control step reaches: the
multiply-adds every lane's token needs (harness/kda_floors.py, from the
configuration's keys: the KDA blocks' matrices, convolutions and state pass,
latent attention's projections and its pass over an episode nobody ends
early, MLPs at the expected held pairs, routers, the head) over the
evaluation program's device time per step."""

LAYER = "kda forward"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import kda_floors, kda_scopes

    split = kda_scopes.forward_seconds(run)
    if split is None or split["evaluation_s"] <= 0:
        return None
    flops = 2.0 * kda_floors.step_macs_per_lane(run.session.kda_sizes, run.session.decode_steps) * run.popsize
    step_s = split["evaluation_s"] / split["steps_ran"]  # all the time, the loop op's own too
    return 100.0 * flops / kda_scopes.peaks(run)["bf16_flops_per_s"] / step_s
