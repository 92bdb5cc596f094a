"""Bytes of the latent cache the population carries as policy state (the
compressed rows and the shared RoPE keys of every held layer and lane, at the
compute dtype)."""

LAYER = "mla cache"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    return run.session.cache_bytes / 1e9
