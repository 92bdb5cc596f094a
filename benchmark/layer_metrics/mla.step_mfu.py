"""Share of the chip's bf16 peak that a whole control step reaches: the
multiply-adds every lane's token needs (harness/mla_floors.py, from the
configuration's keys: projections with the absorbed products, the pass over
the latent cache at the positions the program counted, MLPs, router, head)
over the evaluation program's device time per step."""

LAYER = "mla forward"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import mla_floors, mla_scopes

    split = mla_scopes.forward_seconds(run)
    if split is None or split["evaluation_s"] <= 0:
        return None
    per_lane = mla_scopes.positions_per_step(run) / run.popsize
    flops = 2.0 * mla_floors.step_macs_per_lane(run.session.mla_sizes, per_lane) * run.popsize
    step_s = split["evaluation_s"] / split["steps_ran"]  # all the time, the loop op's own too
    return 100.0 * flops / mla_scopes.peaks(run)["bf16_flops_per_s"] / step_s
