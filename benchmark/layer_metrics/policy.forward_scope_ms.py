"""Device time of the ``policy_forward`` scope per population-wide control
step: the forward with its casts into and out of the compute dtype, by the
names the compiled program carries (harness/scopes.py). In the decoder cells
it is the whole decoder step that the ``lm.*``, ``mla.*`` and ``ssm.*``
metrics split."""

LAYER = "policy forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import scopes

    return scopes.per_step_ms(run, "policy_forward")
