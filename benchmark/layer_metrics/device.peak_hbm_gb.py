"""``memory_stats()["peak_bytes_in_use"]`` after the window, the highest over the
cell's devices: the allocator's high-water mark. Guards popsize headroom."""

LAYER = "device"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    peak = run.device_record["memory_peak_bytes"]
    return peak / 1e9 if peak else None
