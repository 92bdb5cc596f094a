"""Positions of the latent cache the lanes could read over the positions in
the blocks the cache pass's kernel fetched for them, in percent, summed over
steps, lanes and layers of the last generation
(``VecNE.last_policy_report``'s ``latent_positions_read`` over
``latent_positions_fetched``). With blocks of 64 positions and ``t`` running
from 0 to 511 a lane fetches 288 positions a step for its 256.5 readable
ones: about 89 (80 at blocks of 128). 50 would mean every slot is still
fetched. 0 where the report
has no such key (a library from before the kernel) or counts no fetched
position (XLA's plain form ran)."""

LAYER = "mla cache"
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
    fetched = counters.get("latent_positions_fetched", 0)
    return 100.0 * counters.get("latent_positions_read", 0) / fetched if fetched else 0.0
