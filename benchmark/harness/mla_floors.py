"""What a step of the latent-attention decoder must do, from the
configuration's keys alone: the multiply-adds of a lane's token, the bytes
and operations of the latent cache a step must touch, the bytes of the held
experts. Whatever implements the kernels, these are the numerators of
``mla.step_mfu``, ``mla.latent_cache_roofline_share`` and
``mla.experts_roofline_share``; the denominators are device seconds from the
trace (harness/mla_scopes.py).

``sizes`` is ``reference/glm4_moe_lite_decoder.py:sizes(config, scale)``: the
published widths, the layers, experts and rows held here. The expert layer is
the one ``harness/lm_floors.py`` counts (the same keys of ``sizes``), so its
floors are imported, not written again.
"""

from benchmark.harness.lm_floors import (  # noqa: F401  (the readers take them from here)
    expert_bytes_per_step,
    expert_flops_per_step,
    expert_macs,
    held_share,
    sparse_layers,
)


def attention_macs(sizes):
    """Multiply-adds of one token's projections in one layer: ``W_qa``,
    ``W_qb``, ``W_kva``, the absorbed products in the place of ``W_kvb``'s
    (``q_n W_UK[h]`` into the latent space and ``W_UV[h] o`` out of it: every
    head's row block of ``W_kvb`` once, so as many as ``W_kvb`` itself), and
    ``W_o``."""
    h, heads = sizes["hidden"], sizes["heads"]
    return (
        h * sizes["q_rank"]
        + sizes["q_rank"] * heads * (sizes["nope"] + sizes["rope"])
        + h * (sizes["kv_rank"] + sizes["rope"])
        + heads * (sizes["nope"] + sizes["v"]) * sizes["kv_rank"]
        + heads * sizes["v"] * h
    )


def cache_row(sizes):
    """Numbers a position keeps in one layer's cache: the compressed row and
    the one shared RoPE key."""
    return sizes["kv_rank"] + sizes["rope"]


def cache_macs_per_position(sizes):
    """Multiply-adds one readable position costs one lane in one layer: every
    head's score over the compressed row and the RoPE key, and every head's
    weighted sum over the compressed row."""
    return sizes["heads"] * (cache_row(sizes) + sizes["kv_rank"])


def expected_positions_per_step(sizes, lanes, decode_steps):
    """Readable positions of one step, summed over lanes and layers, averaged
    over an episode of ``decode_steps`` that no lane ends early (``t + 1`` at
    step ``t``): what ``latent_positions_read`` counts where it is given."""
    return len(sizes["layers"]) * lanes * (decode_steps + 1) / 2.0


def cache_bytes_per_step(sizes, positions_per_step, dtype_bytes):
    """Bytes of latent cache a step must read: every readable position's row,
    once (for all heads, for scores and weighted sum alike)."""
    return positions_per_step * cache_row(sizes) * dtype_bytes


def cache_flops_per_step(sizes, positions_per_step):
    return 2.0 * positions_per_step * cache_macs_per_position(sizes)


def step_macs_per_lane(sizes, positions_per_lane_step):
    """Multiply-adds of one lane's token through the held layers and the
    head: projections, the pass over the latent cache at
    ``positions_per_lane_step`` readable positions (summed over the layers),
    MLPs at the expected number of held pairs, router, head."""
    h = sizes["hidden"]
    total = sizes["vocab"] * h  # the head (the embedding is a gather)
    total += positions_per_lane_step * cache_macs_per_position(sizes)
    for index in sizes["layers"]:
        total += attention_macs(sizes)
        if index < sizes["num_dense_layers"]:
            total += 3 * h * sizes["dense_width"]
        else:
            total += h * sizes["num_experts"]  # router
            total += expert_macs(sizes) * (sizes["shared"] + held_share(sizes))
    return total
