"""(Lane, expert) pairs on held experts over the rows of the tiles the grouped
product's kernel visited for them, in percent, summed over steps and sparse
layers of the last generation (``VecNE.last_policy_report``'s
``expert_pairs_held``, ``expert_row_tiles``, ``expert_tile_rows``). With 32
pairs expected on each of 8 held experts and tiles of 128 rows, a quarter is
what an even load gives. 0 where no tile is counted (XLA's plain form ran)."""

LAYER = "mla experts"
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
