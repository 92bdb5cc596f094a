"""What a step of the state-space hybrid decoder must do, from the
configuration's keys alone: the parameters of its layers, the multiply-adds of
a lane's token, the bytes of recurrent state a step must move. Whatever
implements them, these are the numerators of ``ssm.step_mfu`` and
``ssm.state_roofline_share``; the denominators are device seconds from the
trace (harness/ssm_scopes.py).

``sizes`` is ``reference/granitemoehybrid_decoder.py:sizes(config, scale)``:
the published widths, the layers and rows held here.
"""


def inner(sizes):
    return sizes["ssm_heads"] * sizes["ssm_head_dim"]


def conv_channels(sizes):
    """What the convolution runs over: ``x`` and one group of ``B`` and ``C``."""
    return inner(sizes) + 2 * sizes["ssm_state"]


def mlp_macs(sizes):
    """The shared MLP's three matrices (the fused input matrix is two of them)."""
    return 3 * sizes["hidden"] * sizes["mlp_width"]


def mixer_matrix_macs(sizes):
    """``in_proj`` (``z | xBC | dt``) and ``out_proj`` of one Mamba-2 mixer."""
    h = sizes["hidden"]
    return h * (inner(sizes) + conv_channels(sizes) + sizes["ssm_heads"]) + inner(sizes) * h


def attention_matrix_macs(sizes):
    h, hd = sizes["hidden"], sizes["head_dim"]
    return 2 * h * sizes["heads"] * hd + 2 * h * sizes["kv_heads"] * hd


def mamba_layer_parameters(sizes):
    """A Mamba-2 layer: the mixer's two matrices, the convolution's taps and
    bias, ``dt_bias`` / ``A_log`` / ``D``, the gated norm, the two norms
    before the blocks, the shared MLP."""
    return (
        mixer_matrix_macs(sizes)
        + conv_channels(sizes) * (sizes["conv_width"] + 1)
        + 3 * sizes["ssm_heads"]
        + inner(sizes)
        + 2 * sizes["hidden"]
        + mlp_macs(sizes)
    )


def attention_layer_parameters(sizes):
    return attention_matrix_macs(sizes) + 2 * sizes["hidden"] + mlp_macs(sizes)


def parameters(sizes):
    """Every held layer, the final norm and the held rows of the tied embedding."""
    kinds = [sizes["kinds"][index] for index in sizes["layers"]]
    return (
        sum(attention_layer_parameters(sizes) if kind == "attention" else mamba_layer_parameters(sizes) for kind in kinds)
        + sizes["hidden"]
        + sizes["vocab"] * sizes["hidden"]
    )


def ssm_layers(sizes):
    return sum(sizes["kinds"][index] != "attention" for index in sizes["layers"])


def state_numbers(sizes):
    """Numbers in one lane's matrix state of one layer: ``(heads, head_dim,
    state)``."""
    return sizes["ssm_heads"] * sizes["ssm_head_dim"] * sizes["ssm_state"]


def state_bytes(sizes, dtype_bytes):
    """One lane-layer state as it is stored: 1 MiB at the published widths in
    bfloat16."""
    return state_numbers(sizes) * dtype_bytes


def state_bytes_per_step(sizes, updates_per_step, dtype_bytes):
    """Bytes a step must move for ``updates_per_step`` lane-layer states
    rewritten: each read once and written once."""
    return 2 * updates_per_step * state_bytes(sizes, dtype_bytes)


def expected_updates_per_step(sizes, lanes):
    """Every lane rewrites every Mamba-2 layer's state at every step: what
    ``ssm_state_updates`` counts, over the steps."""
    return ssm_layers(sizes) * lanes


def step_macs_per_lane(sizes, positions_per_lane_step):
    """Multiply-adds of one lane's token through the held layers and the tied
    head: the matrices, the convolution's taps, the state's update and readout
    (one multiply-add a number each), attention over
    ``positions_per_lane_step`` readable positions (scores and weighted sum),
    the MLPs."""
    total = sizes["vocab"] * sizes["hidden"]  # the head (the embedding is a gather)
    for index in sizes["layers"]:
        total += mlp_macs(sizes)
        if sizes["kinds"][index] == "attention":
            total += attention_matrix_macs(sizes)
            total += 2 * positions_per_lane_step * sizes["heads"] * sizes["head_dim"]
        else:
            total += mixer_matrix_macs(sizes) + conv_channels(sizes) * sizes["conv_width"] + 2 * state_numbers(sizes)
    return total
