"""Lane-layer KDA matrix states the state pass's kernel rewrote over the states
the program rewrote, in percent, summed over steps, lanes and layers of the
last generation (``VecNE.last_policy_report``'s ``kda_state_kernel_updates``
over ``kda_state_updates``). 100 says every pass of the timed program was the
kernel of ``net/kdastate.py``. Nothing where the report has no such key (a
library from before the kernel: nothing to read); 0 where it counts no state
the kernel rewrote (XLA's plain form ran) or no update at all."""

LAYER = "kda state"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    counters = run.session.policy_counters()
    if not counters or "kda_state_kernel_updates" not in counters:
        return None
    updates = counters.get("kda_state_updates", 0)
    return 100.0 * counters.get("kda_state_kernel_updates", 0) / updates if updates else 0.0
