"""What a decoder step must do, from the configuration's keys alone: the
multiply-adds of a lane's token, the bytes of the held experts, the bytes of
the cache a step must read. Whatever implements the kernels, these are the
numerators of ``lm.step_mfu``, ``lm.experts_roofline_share`` and
``lm.cache_roofline_share``; the denominators are device seconds from the
trace (harness/lm_scopes.py).

``sizes`` is ``reference/afmoe_decoder.py:sizes(config, scale)``: the
published widths, the layers, experts and rows held here.
"""


def expert_macs(sizes):
    """Multiply-adds of one (token, expert) pair: gate, up and down."""
    return 3 * sizes["hidden"] * sizes["expert_width"]


def held_share(sizes):
    """Expected pairs a token puts on the held experts: ``top_k`` of
    ``num_experts``, of which this chip holds some."""
    first, past = sizes["experts_held"]
    return sizes["top_k"] * (past - first) / sizes["num_experts"]


def step_macs_per_lane(sizes):
    """Multiply-adds of one lane's token through the held layers and the
    head: projections, MLPs at the expected number of held pairs, router,
    head. Attention over the cache is left out (1% to 2% more at these
    lengths: a floor stays a floor)."""
    h, hd = sizes["hidden"], sizes["head_dim"]
    wide, narrow = sizes["heads"] * hd, sizes["kv_heads"] * hd
    attention = h * (3 * wide + 2 * narrow)  # q, g, o; k, v
    total = sizes["vocab"] * h  # the head (the embedding is a gather)
    for index in sizes["layers"]:
        total += attention
        if index < sizes["num_dense_layers"]:
            total += 3 * h * sizes["dense_width"]
        else:
            total += h * sizes["num_experts"]  # router
            total += expert_macs(sizes) * (sizes["shared"] + held_share(sizes))
    return total


def sparse_layers(sizes):
    return sum(1 for index in sizes["layers"] if index >= sizes["num_dense_layers"])


def expert_bytes_per_step(sizes, dtype_bytes):
    """Bytes of weights the expert scope must touch in one step: every held
    expert (each is hit at these populations) and the shared expert, in every
    sparse layer."""
    first, past = sizes["experts_held"]
    return sparse_layers(sizes) * (past - first + sizes["shared"]) * expert_macs(sizes) * dtype_bytes


def expert_flops_per_step(sizes, lanes, pairs_per_step=None):
    """FLOPs of the expert scope in one step: the pairs that hit held experts
    (counted by the program where given, else expected) and every lane
    through the shared expert, over the sparse layers. ``pairs_per_step`` is
    the sum over the sparse layers."""
    if pairs_per_step is None:
        pairs_per_step = sparse_layers(sizes) * lanes * held_share(sizes)
    shared_pairs = sparse_layers(sizes) * lanes * sizes["shared"]
    return 2 * expert_macs(sizes) * (pairs_per_step + shared_pairs)


def cache_slots(sizes, index, decode_steps):
    """Slots of layer ``index``'s cache: the window's ring in a sliding layer
    (no longer than the episode), the episode in a full one."""
    sliding = sizes["layer_types"][index] == "sliding_attention"
    return min(sizes["window"], decode_steps) if sliding else decode_steps


def cache_bytes_per_step(sizes, lanes, decode_steps, dtype_bytes):
    """Bytes of cache a step must read, averaged over an episode of
    ``decode_steps``: the positions a lane has filled (``t + 1`` at step
    ``t``, at most the layer's slots), keys and values, in every layer."""
    per_position = 2 * sizes["kv_heads"] * sizes["head_dim"] * dtype_bytes
    total = 0.0
    for index in sizes["layers"]:
        slots = cache_slots(sizes, index, decode_steps)
        filled = sum(min(t + 1, slots) for t in range(decode_steps)) / decode_steps
        total += lanes * filled * per_position
    return total


def cache_shape(sizes, lanes, decode_steps):
    """The ``[lanes, kv heads, slots, head_dim]`` shapes of the cache's
    arrays, as they appear in the compiled program's text."""
    return {
        f"[{lanes},{sizes['kv_heads']},{cache_slots(sizes, index, decode_steps)},{sizes['head_dim']}]"
        for index in sizes["layers"]
    }
