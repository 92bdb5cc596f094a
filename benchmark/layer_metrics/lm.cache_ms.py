"""Device milliseconds per control step of the pass over the attention cache:
the cache write, the scores of every head over the lane's slots, the mask, the
softmax and the weighted sum, summed over the held layers: the ops under
``fwd_kv_cache``, inside ``fwd_attention``, once the library declares that
name, and until then the ``fwd_attention`` ops that hold the cache's shape
(harness/lm_scopes.py). What a kernel over the cache has to shorten."""

LAYER = "lm cache"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_scopes

    return lm_scopes.cache_ms(run)
