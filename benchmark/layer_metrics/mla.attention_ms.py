"""Device milliseconds per control step under the decoder's inner scope
``fwd_attention`` OUTSIDE the latent cache's span (norms, the down- and
up-projections of queries, the compression, RoPE, the absorbed products ``q_n
W_UK[h]`` and ``W_UV[h] o``, the output projection), summed over the held
layers (harness/mla_scopes.py)."""

LAYER = "mla forward"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import mla_scopes

    return mla_scopes.per_step_ms(run, "fwd_attention")
