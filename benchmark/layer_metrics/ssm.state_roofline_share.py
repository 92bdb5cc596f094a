"""The pass over the lanes' matrix states against its floor: the lane-layer
states the program itself counted as rewritten (``ssm_state_updates`` of
``VecNE.last_policy_report``) times a state's bytes as stored (1 MiB in
bfloat16), once read and once written, over the memory bandwidth, over
``ssm.state_ms``."""

LAYER = "ssm state"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ssm_floors, ssm_scopes

    split = ssm_scopes.forward_seconds(run)
    if split is None or split["seconds"].get(ssm_scopes.STATE_SCOPE, 0.0) <= 0:
        return None
    updates = ssm_scopes.updates_per_step(run)
    if updates is None:
        return None
    moved = ssm_floors.state_bytes_per_step(run.session.ssm_sizes, updates, ssm_scopes.dtype_bytes(run))
    floor_s = moved / ssm_scopes.peaks(run)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (split["seconds"][ssm_scopes.STATE_SCOPE] / split["steps"])
