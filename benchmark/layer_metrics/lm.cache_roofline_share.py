"""The attention cache against its floor: the bytes of keys and values a step
must read (the positions filled, averaged over the episode) over the memory
bandwidth, over ``lm.cache_ms``: the device time of the cache's pass, under
``fwd_kv_cache`` once the library declares it, by the cache's shape until
then (harness/lm_scopes.py)."""

LAYER = "lm cache"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_floors, lm_scopes

    taken_ms = lm_scopes.cache_ms(run)
    if taken_ms is None:
        return None
    session = run.session
    must_read = lm_floors.cache_bytes_per_step(
        session.lm_sizes, run.popsize, session.decode_steps, lm_scopes.dtype_bytes(run)
    )
    return 100.0 * 1e3 * must_read / lm_scopes.peaks(run)["hbm_bytes_per_s"] / taken_ms
