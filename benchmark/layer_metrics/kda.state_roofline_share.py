"""The pass over the lanes' KDA matrix states against its floor, from the
CONFIGURATION: every lane's state in every held KDA layer (32 x 128 x 128
numbers, 1 MiB in bfloat16) read once and written once a step, over the
memory bandwidth, over ``kda.state_ms``. Read only where the program
rewrote exactly those states (``kda_state_updates`` of
``VecNE.last_policy_report`` is decode steps x lanes x KDA layers): the count
decides whether there is a floor, never what it is."""

LAYER = "kda state"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import kda_floors, kda_scopes

    split = kda_scopes.forward_seconds(run)
    if split is None or split["seconds"].get(kda_scopes.STATE_SCOPE, 0.0) <= 0:
        return None
    if not kda_scopes.updates_as_configured(run):
        return None
    moved = kda_floors.state_bytes_per_step(run.session.kda_sizes, run.popsize, kda_scopes.dtype_bytes(run))
    floor_s = moved / kda_scopes.peaks(run)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (split["seconds"][kda_scopes.STATE_SCOPE] / split["steps"])
