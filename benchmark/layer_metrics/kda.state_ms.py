"""Device milliseconds per control step under ``fwd_kda_state`` (whatever
touches the lanes' KDA matrix states: the decay, ``S'^T k``, the rank-1
update, the readout, the write back), summed over the held KDA layers
(harness/kda_scopes.py)."""

LAYER = "kda state"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import kda_scopes

    return kda_scopes.per_step_ms(run, kda_scopes.STATE_SCOPE)
