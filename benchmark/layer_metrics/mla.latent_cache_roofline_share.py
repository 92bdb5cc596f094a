"""The pass over the latent cache against its floor: the larger of the bytes
a step must read (the readable positions the program counted, 1,152 B each in
bfloat16) over the memory bandwidth and the operations of all heads' scores
and weighted sums over them (``20 x (576 + 512)`` multiply-adds a position)
over the bf16 peak, over ``mla.latent_cache_ms``."""

LAYER = "mla cache"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import mla_floors, mla_scopes

    split = mla_scopes.forward_seconds(run)
    if split is None or split["seconds"].get(mla_scopes.CACHE_SCOPE, 0.0) <= 0:
        return None
    sizes, peaks = run.session.mla_sizes, mla_scopes.peaks(run)
    positions = mla_scopes.positions_per_step(run)
    floor_s = max(
        mla_floors.cache_bytes_per_step(sizes, positions, mla_scopes.dtype_bytes(run)) / peaks["hbm_bytes_per_s"],
        mla_floors.cache_flops_per_step(sizes, positions) / peaks["bf16_flops_per_s"],
    )
    return 100.0 * floor_s / (split["seconds"][mla_scopes.CACHE_SCOPE] / split["steps"])
