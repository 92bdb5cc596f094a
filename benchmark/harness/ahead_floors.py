"""What a step of the route-ahead decoder must do, from the configuration's
keys alone: the multiply-adds of a lane's token, the bytes the held experts
must stream. Whatever implements them, these are the numerators of
``ahead.step_mfu`` and ``ahead.experts_roofline_share``; the denominators are
device seconds from the trace, read by harness/lm_scopes.py. No count the
program reports about itself enters a floor but the pairs that hit a held
expert, which ``lm.experts_roofline_share`` counts the same way.

``sizes`` is the session's ``lm_sizes``:
``reference/smallthinker_decoder.py:sizes(config, scale)`` (the published
widths, the layers, experts and rows held here) with the layers' kinds as
``harness/lm_floors.py`` names them. An expert's multiply-adds, a token's
expected pairs on the held experts and a layer's cache slots are
``lm_floors``'s, imported.
"""

from benchmark.harness.lm_floors import cache_slots, expert_macs, held_share


def held_experts(sizes):
    first, past = sizes["experts_held"]
    return past - first


def expert_layers(sizes):
    """Every held layer has experts: the family has no dense layer."""
    return len(sizes["layers"])


def expert_bytes_per_step(sizes, dtype_bytes, rank):
    """Bytes the expert scope must stream in one step: every held expert's
    three matrices (each expert is hit at these populations: 48 pairs an
    expert at 512 lanes) and their rank-``rank`` delta factors (``a`` over a
    matrix's input, ``b`` over its output), in every layer. 755 MB and 5 MB
    at the cell's sizes in bfloat16."""
    h, w = sizes["hidden"], sizes["expert_width"]
    per_expert = expert_macs(sizes) + 3 * (h + w) * rank
    return expert_layers(sizes) * held_experts(sizes) * per_expert * dtype_bytes


def expert_flops_per_step(sizes, lanes, pairs_per_step=None):
    """FLOPs of the expert scope in one step: the pairs that hit held experts
    (counted by the program where given, summed over the layers; else
    expected) through gate, up and down."""
    if pairs_per_step is None:
        pairs_per_step = expert_layers(sizes) * lanes * held_share(sizes)
    return 2 * expert_macs(sizes) * pairs_per_step


def step_macs_per_lane(sizes, decode_steps):
    """Multiply-adds of one lane's token through the held layers and the
    head: in every layer the router, the projections ``q``, ``k``, ``v``,
    ``o``, the pass over the positions a lane has filled in the cache,
    averaged over an episode of ``decode_steps`` that no lane ends early
    (every query head's score and weighted sum), the held experts at the
    expected number of pairs; the head (the embedding is a gather)."""
    h, hd = sizes["hidden"], sizes["head_dim"]
    wide, narrow = sizes["heads"] * hd, sizes["kv_heads"] * hd
    total = sizes["vocab"] * h
    for index in sizes["layers"]:
        total += h * sizes["num_experts"]  # router
        total += h * (2 * wide + 2 * narrow)  # q, o; k, v
        slots = cache_slots(sizes, index, decode_steps)
        total += 2 * wide * sum(min(t + 1, slots) for t in range(decode_steps)) / decode_steps
        total += expert_macs(sizes) * held_share(sizes)
    return total


def pairs_held_per_step(counters, sizes):
    """(lane, expert) pairs on the held experts a step, summed over the
    layers, as the program counted them over its last evaluation; None
    where it counted none."""
    if not counters or not counters.get("expert_layer_steps"):
        return None
    return counters["expert_pairs_held"] / counters["expert_layer_steps"] * expert_layers(sizes)
