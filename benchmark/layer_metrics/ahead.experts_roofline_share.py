"""The ReGLU experts' scope against its floor: the larger of the held
experts' weight and delta-factor bytes over the memory bandwidth and the
pairs' FLOPs (pairs counted by the program) over the bf16 peak, from the
configuration's keys (harness/ahead_floors.py), over ``ahead.experts_ms``."""

LAYER = "ahead experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import ahead_floors, lm_scopes

    split = lm_scopes.forward_seconds(run)
    if split is None or split["seconds"].get("fwd_experts", 0.0) <= 0:
        return None
    session, peaks = run.session, lm_scopes.peaks(run)
    sizes = session.lm_sizes
    moved = ahead_floors.expert_bytes_per_step(sizes, lm_scopes.dtype_bytes(run), session.trunk_delta_rank)
    pairs = ahead_floors.pairs_held_per_step(session.policy_counters(), sizes)
    flops = ahead_floors.expert_flops_per_step(sizes, run.popsize, pairs)
    floor_s = max(moved / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s / (split["seconds"]["fwd_experts"] / split["steps"])
