"""The on-device telemetry's ``env_steps / capacity`` over the measured
generations whose telemetry was decoded: counted interactions over executed
lane-step slots. 100 in a budget cell; under it where lanes idle masked."""

LAYER = "eval contract"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    occupancy = run.counts["occupancy"]
    return None if occupancy is None else 100.0 * occupancy
