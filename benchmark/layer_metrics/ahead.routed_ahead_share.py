"""(lane, expert) pairs whose routing the program decided BEFORE the layer's
attention, over the pairs a step routes (``top_k`` a lane in every layer),
in percent, over the last evaluation (``expert_pairs_routed_ahead`` of
``VecNE.last_policy_report`` over ``expert_layer_steps`` x lanes x
``top_k``). The layer adds ``top_k`` a lane a step whatever the data flow,
so the reading is 100 by construction wherever ``RouteAheadLayer`` is the
class built: it confirms which class the timed program ran, not the order of
its computation (the comparison's ``route_after`` control tells that), and
it cannot move ``env_steps_per_s``. Nothing where the report has no such
key."""

LAYER = "ahead experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    counters = run.session.policy_counters()
    if not counters or "expert_pairs_routed_ahead" not in counters:
        return None
    routed = counters.get("expert_layer_steps", 0) * run.popsize * run.session.lm_sizes["top_k"]
    return 100.0 * counters["expert_pairs_routed_ahead"] / routed if routed else 0.0
