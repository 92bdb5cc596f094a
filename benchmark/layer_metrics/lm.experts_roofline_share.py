"""The expert scope against its floor: the larger of the held experts' (and the
shared expert's) weight bytes over the memory bandwidth and the pairs' FLOPs
(pairs counted by the program) over the bf16 peak, over ``lm.experts_ms``."""

LAYER = "lm experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import lm_floors, lm_scopes

    split = lm_scopes.forward_seconds(run)
    if split is None or split["seconds"].get("fwd_experts", 0.0) <= 0:
        return None
    sizes, peaks = run.session.lm_sizes, lm_scopes.peaks(run)
    counters = run.session.policy_counters()
    pairs = None
    if counters and counters["expert_layer_steps"]:
        # pairs per control step, summed over the sparse layers
        pairs = (
            counters["expert_pairs_held"] / counters["expert_layer_steps"] * lm_floors.sparse_layers(sizes)
        )
    floor_s = max(
        lm_floors.expert_bytes_per_step(sizes, lm_scopes.dtype_bytes(run)) / peaks["hbm_bytes_per_s"],
        lm_floors.expert_flops_per_step(sizes, run.popsize, pairs) / peaks["bf16_flops_per_s"],
    )
    return 100.0 * floor_s / (split["seconds"]["fwd_experts"] / split["steps"])
