"""Device milliseconds per control step under ``fwd_attention`` in the
route-ahead decoder: the projections ``q``, ``k``, ``v``, ``o``, RoPE in the
window layers, the cache's write and the pass over it (scores, softmax,
weighted sum; ``lm.cache_ms`` is that pass alone), summed over the held
layers (harness/lm_scopes.py)."""

LAYER = "ahead forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_scopes

    return lm_scopes.per_step_ms(run, "fwd_attention")
