"""Device milliseconds per control step under ``fwd_attention`` (the one
attention layer of a period: norm, the four projections, the cache write,
scores and weighted sum over the lane's key/value ring, the last three under
``fwd_kv_cache`` once the library declares it) (harness/ssm_scopes.py)."""

LAYER = "ssm forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ssm_scopes

    return ssm_scopes.per_step_ms(run, "fwd_attention", ssm_scopes.CACHE_SCOPE)
