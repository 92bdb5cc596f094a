"""Device milliseconds per control step under ``fwd_dense_mlp`` (every held
layer's shared MLP: norm, the fused input matrix's two halves, the output
matrix) (harness/ssm_scopes.py)."""

LAYER = "ssm forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ssm_scopes

    return ssm_scopes.per_step_ms(run, "fwd_dense_mlp")
