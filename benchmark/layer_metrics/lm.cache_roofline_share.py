"""The attention cache against its floor: the bytes of keys and values a step
must read (the positions filled, averaged over the episode) over the memory
bandwidth, over the device time of the attention ops that touch the cache."""

LAYER = "lm cache"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_floors, lm_scopes

    split = lm_scopes.forward_seconds(run)
    if split is None or split["cache_ops_s"] <= 0:
        return None
    session = run.session
    must_read = lm_floors.cache_bytes_per_step(
        session.lm_sizes, run.popsize, session.decode_steps, lm_scopes.dtype_bytes(run)
    )
    floor_s = must_read / lm_scopes.peaks(run)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (split["cache_ops_s"] / split["steps"])
