"""The expert scope against its floor, at 8 held experts of width 1,536 (the
kernel of ``net/grouped.py`` walks each in two width tiles): the larger of
the held experts' (and the shared expert's) weight bytes over the memory
bandwidth and the pairs' FLOPs (pairs counted by the program) over the bf16
peak, over ``mla.experts_ms``."""

LAYER = "mla experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import mla_floors, mla_scopes

    split = mla_scopes.forward_seconds(run)
    if split is None or split["seconds"].get("fwd_experts", 0.0) <= 0:
        return None
    sizes, peaks = run.session.mla_sizes, mla_scopes.peaks(run)
    counters = run.session.policy_counters()
    pairs = None
    if counters and counters["expert_layer_steps"]:
        # pairs per control step, summed over the sparse layers
        pairs = (
            counters["expert_pairs_held"] / counters["expert_layer_steps"] * mla_floors.sparse_layers(sizes)
        )
    floor_s = max(
        mla_floors.expert_bytes_per_step(sizes, mla_scopes.dtype_bytes(run)) / peaks["hbm_bytes_per_s"],
        mla_floors.expert_flops_per_step(sizes, run.popsize, pairs) / peaks["bf16_flops_per_s"],
    )
    return 100.0 * floor_s / (split["seconds"]["fwd_experts"] / split["steps"])
