"""Device milliseconds per control step under the decoder's inner scope
``fwd_attention`` (projections, norms, RoPE, the cache write, scores and values over the cache, the output gate), summed over the held layers
(harness/lm_scopes.py)."""

LAYER = "lm forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_scopes

    return lm_scopes.per_step_ms(run, "fwd_attention")
