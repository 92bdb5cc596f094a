"""(Lane, expert) pairs on held experts over the rows of the tiles the grouped
product's kernel visited for them, in percent, summed over steps and sparse
layers of the last generation (the decoder counts pairs and tiles in its own
state and says how many rows a tile has: ``VecNE.last_policy_report``'s
``expert_pairs_held``, ``expert_row_tiles``, ``expert_tile_rows``). 100 is a
kernel whose every visited row holds a pair. 0 where the report has no such
keys (a library from before the kernel) or counts no tile (XLA's plain form
ran)."""

LAYER = "lm experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    counters = run.session.policy_counters()
    if not counters:
        return None
    rows = counters.get("expert_row_tiles", 0) * counters.get("expert_tile_rows", 0)
    return 100.0 * counters["expert_pairs_held"] / rows if rows else 0.0
