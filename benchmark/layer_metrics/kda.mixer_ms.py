"""Device milliseconds per control step under ``fwd_kda`` OUTSIDE the state's
span (the norm, the q, k, v projections, their convolutions over the lane's
window and its shift, the L2 norms, the decay's and beta's projections, the
output's gated norm and its gate, ``o_proj``), summed over the held KDA
layers (harness/kda_scopes.py)."""

LAYER = "kda forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import kda_scopes

    return kda_scopes.per_step_ms(run, "fwd_kda")
