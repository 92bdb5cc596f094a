"""What a step of the hybrid linear-attention decoder must do, from the
configuration's keys alone: the multiply-adds of a lane's token and the bytes
of KDA state a step must move. Whatever implements them, these are the
numerators of ``kda.step_mfu`` and ``kda.state_roofline_share``; the
denominators are device seconds from the trace (harness/kda_scopes.py). No
count the program reports about itself enters a floor.

``sizes`` is ``reference/kimi_linear_decoder.py:sizes(config, scale)``: the
published widths, the layers, experts and rows held here. The expert layer is
the one ``harness/lm_floors.py`` counts (the same keys of ``sizes``), so its
pieces are imported, not written again.
"""

from benchmark.harness.lm_floors import expert_macs, held_share


def inner(sizes):
    """``P``: a KDA layer's heads times their size."""
    return sizes["kda_heads"] * sizes["kda_head_dim"]


def kda_layers(sizes):
    return sum(1 for index in sizes["layers"] if index in sizes["kda_layers"])


def kda_matrix_macs(sizes):
    """``W_q``, ``W_k``, ``W_v`` and ``W_o``, the decay's and the output
    gate's two low-rank pairs (rank ``head_dim``) and ``W_b`` of one KDA
    block."""
    h, p, rank = sizes["hidden"], inner(sizes), sizes["kda_head_dim"]
    return 4 * h * p + 2 * (h * rank + rank * p) + h * sizes["kda_heads"]


def state_numbers(sizes):
    """Numbers in one lane's matrix state of one KDA layer: ``(heads, key,
    value)``."""
    return sizes["kda_heads"] * sizes["kda_head_dim"] ** 2


def state_bytes(sizes, dtype_bytes):
    """One lane-layer state as it is stored: 1 MiB at the published widths in
    bfloat16."""
    return state_numbers(sizes) * dtype_bytes


def state_bytes_per_step(sizes, lanes, dtype_bytes):
    """Bytes a step must move for every lane's state in every held KDA
    layer: each read once and written once."""
    return 2 * lanes * kda_layers(sizes) * state_bytes(sizes, dtype_bytes)


def expected_updates_per_step(sizes, lanes):
    """Every lane rewrites every KDA layer's state at every step: what
    ``kda_state_updates`` counts over a step."""
    return kda_layers(sizes) * lanes


def attention_macs(sizes):
    """Multiply-adds of one token's projections in a latent-attention layer:
    ``W_q`` (no LoRA), ``W_kva``, the absorbed products in the place of
    ``W_kvb``'s (every head's row block of ``W_kvb`` once) and ``W_o``."""
    h, heads = sizes["hidden"], sizes["heads"]
    return (
        h * heads * (sizes["nope"] + sizes["rope"])
        + h * (sizes["kv_rank"] + sizes["rope"])
        + heads * (sizes["nope"] + sizes["v"]) * sizes["kv_rank"]
        + heads * sizes["v"] * h
    )


def cache_macs_per_position(sizes):
    """Multiply-adds one readable position of the latent cache costs one lane:
    every head's score over the compressed row and the shared key, and every
    head's weighted sum over the compressed row."""
    return sizes["heads"] * (2 * sizes["kv_rank"] + sizes["rope"])


def step_macs_per_lane(sizes, decode_steps):
    """Multiply-adds of one lane's token through the held layers and the
    head, averaged over an episode of ``decode_steps`` that no lane ends
    early: the matrices; in a KDA layer the three convolutions' taps and the
    state pass (one multiply-add a number each for the decay, ``S'^T k``, the
    rank-1 update and the readout); in the latent-attention layer the pass
    over ``(decode_steps + 1) / 2`` readable positions; MLPs at the expected
    number of held pairs, routers, the head."""
    h = sizes["hidden"]
    total = sizes["vocab"] * h  # the head (the embedding is a gather)
    for index in sizes["layers"]:
        if index in sizes["kda_layers"]:
            total += kda_matrix_macs(sizes) + 3 * inner(sizes) * sizes["conv_width"] + 4 * state_numbers(sizes)
        else:
            total += attention_macs(sizes) + (decode_steps + 1) / 2.0 * cache_macs_per_position(sizes)
        if index < sizes["num_dense_layers"]:
            total += 3 * h * sizes["dense_width"]
        else:
            total += h * sizes["num_experts"]  # router
            total += expert_macs(sizes) * (sizes["shared"] + held_share(sizes))
    return total
