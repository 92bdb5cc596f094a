"""Device milliseconds per control step under ``fwd_ssm_state`` (whatever
touches the lanes' matrix states: the decay, the outer product that feeds
them, the readout, the write back), summed over the held Mamba-2 layers
(harness/ssm_scopes.py)."""

LAYER = "ssm state"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ssm_scopes

    return ssm_scopes.per_step_ms(run, ssm_scopes.STATE_SCOPE)
