"""Device milliseconds per control step under ``fwd_ssm`` OUTSIDE the matrix
state's span (the norm, ``in_proj``, the convolution over the lane's window
and its shift, softplus and the per-lane transition, the gated norm,
``out_proj``), summed over the held Mamba-2 layers (harness/ssm_scopes.py)."""

LAYER = "ssm forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ssm_scopes

    return ssm_scopes.per_step_ms(run, "fwd_ssm")
