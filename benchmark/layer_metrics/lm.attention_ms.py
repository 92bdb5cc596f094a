"""Device milliseconds per control step under the decoder's inner scope
``fwd_attention`` (projections, norms, RoPE, the output gate) with the cache's
pass inside it (``fwd_kv_cache``: the cache write, scores and values over the
cache), summed over the held layers (harness/lm_scopes.py)."""

LAYER = "lm forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_scopes

    return lm_scopes.per_step_ms(run, "fwd_attention", lm_scopes.CACHE_SCOPE)
