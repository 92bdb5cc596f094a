"""Device milliseconds per control step under ``fwd_router`` in the
route-ahead decoder: the norm that the router and the attention both read,
the router's logits, the top-k and the softmax over the chosen, decided
BEFORE each layer's attention; summed over the held layers
(harness/lm_scopes.py, by scope alone)."""

LAYER = "ahead forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_scopes

    return lm_scopes.per_step_ms(run, "fwd_router")
