"""Device milliseconds per control step under ``fwd_latent_cache`` (the write
of the compressed row and the RoPE key, the scores of all heads over them,
the softmax, the weighted sum over the compressed rows), summed over the held
layers (harness/mla_scopes.py)."""

LAYER = "mla cache"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import mla_scopes

    return mla_scopes.per_step_ms(run, mla_scopes.CACHE_SCOPE)
