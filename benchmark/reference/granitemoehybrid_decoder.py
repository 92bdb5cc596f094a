"""Plain reference: the ``granitemoehybrid`` decoder (Granite 4.0-H) over a
WHOLE sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no library code, no cache, no
lanes, no carried state: the convolution is a causal depthwise convolution
over the sequence, the state-space layer the recurrence in a ``lax.scan`` over
positions, attention a ``jnp.where`` on a ``(T, T)`` score matrix. It is given
the share the system holds (``layers_held``, ``vocab_held``) like the system.

The equations, for hidden state ``h`` at position ``t`` of a lane's episode
(the items marked + have no key in the catalog's copy of the published config
and follow the family's Mamba-2 mixer, ``GraniteMoeHybridMambaLayer`` / Bamba;
the configuration lists them under ``assumed``):

- ends: ``h_0 = embedding_multiplier * E[token]``; ``logits = E RMSNorm(h_L)
  / logits_scaling`` (``tie_word_embeddings``: the embedding's own rows);
- every layer: ``h = h + residual_multiplier * mixer(RMSNorm(h))``, then ``h
  = h + residual_multiplier * MLP(RMSNorm(h))``, ``MLP(y) = W_down(silu(W_gate
  y) * W_up y)`` of width ``shared_intermediate_size`` (``num_local_experts``
  0: the shared MLP alone; ``[W_gate; W_up]`` are the two halves of the
  published fused ``input_linear`` +);
- ``"mamba"`` mixer (``inner = mamba_n_heads x mamba_d_head``, one group):
  ``[z | xBC | dt] = W_in x`` of ``inner | inner + 2 mamba_d_state |
  mamba_n_heads`` (+ the order); ``xBC_t = silu(sum_{k < d_conv} w[k]
  xBC_{t - d_conv + 1 + k} + b)`` over the positions of the entry's own
  episode; ``[x | B | C] = xBC_t``; ``dt = softplus(dt + dt_bias)`` (no clamp:
  ``time_step_limit`` (0, inf) +); ``a = exp(-dt exp(A_log))`` a head; ``S_t =
  a S_{t-1} + dt x_t (outer) B_t`` a head, ``S = 0`` where an episode begins;
  ``y = S_t C_t + D x_t``; ``y = RMSNorm_inner(y * silu(z))`` (the gate first,
  then one norm over all of ``inner`` +); out ``W_out y``;
- ``"attention"`` mixer: ``q, k, v = W_q x, W_k x, W_v x`` (grouped-query
  heads of ``hidden_size / num_attention_heads``), no positions
  (``position_embedding_type`` ``"nope"``), ``score = q . k *
  attention_multiplier`` over ``s <= t`` of the entry's own episode, softmax,
  ``W_o`` of the weighted values. No bias, no gate, no per-head norm.

Departures from the published modelling code (matters of form): RMSNorm
multiplies by its weight in float32 before the result is cast; the gated
norm's weight is named ``norm``, the convolution's ``conv`` ``(d_conv,
channels)``, taps first, and ``conv_bias``; the fused ``input_linear`` is held as ``gate``
and ``up``; the attention mask is built here from positions;
``mamba_chunk_size`` (the chunked scan) is no part of a position-by-position
recurrence.

The parameter layout is the library's (``jax.flatten_util.ravel_pytree`` over
nested dicts with sorted keys and a tuple of layers): ``unflatten`` lists it
by hand, so if the library ever lays parameters out otherwise the comparison
fails, as it should.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes(config, scale=None):
    """What the equations need, from a configuration file (and the run's
    ``scale``: the rehearsal may hold fewer layers and rows). The file's
    ``vocab_size`` and ``num_hidden_layers`` are what is HELD here (they are
    under ``reduced``)."""
    scale = scale or {}
    kinds = list(config["layer_types"])
    held = [int(i) for i in config["layers_held"]]
    kept = int(scale.get("kept_mamba_layers", config["kept_mamba_layers"]))
    mamba = [i for i in held if kinds[i] != "attention"][:kept]  # the first of them; every attention layer stays
    layers = [i for i in held if kinds[i] == "attention" or i in mamba]
    hidden, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "hidden": hidden,
        "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": hidden // heads,
        "mlp_width": int(config["shared_intermediate_size"]),
        "ssm_heads": int(config["mamba_n_heads"]),
        "ssm_head_dim": int(config["mamba_d_head"]),
        "ssm_state": int(config["mamba_d_state"]),
        "conv_width": int(config["mamba_d_conv"]),
        "kinds": kinds,
        "embed_scale": float(config["embedding_multiplier"]),
        "residual_scale": float(config["residual_multiplier"]),
        "score_scale": float(config["attention_multiplier"]),
        "logits_divisor": float(config["logits_scaling"]),
        "eps": float(config["rms_norm_eps"]),
        "layers": layers,
        "num_dense_layers": len(config["layer_types"]),  # no layer routes
        "vocab": int(scale.get("vocab_held", config["vocab_held"])),
    }


def leaf_shapes(s):
    """``[(path, shape), ...]`` in the order of the flat parameter vector."""
    h, width = s["hidden"], s["mlp_width"]
    inner, state = s["ssm_heads"] * s["ssm_head_dim"], s["ssm_state"]
    channels = inner + 2 * state
    wide, narrow = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    attn = [("in_norm", (h,)), ("k", (narrow, h)), ("o", (h, wide)), ("q", (wide, h)), ("v", (narrow, h))]
    ssm = [  # sorted as python sorts them: capitals first
        ("A_log", (s["ssm_heads"],)),
        ("D", (s["ssm_heads"],)),
        ("conv", (s["conv_width"], channels)),
        ("conv_bias", (channels,)),
        ("dt_bias", (s["ssm_heads"],)),
        ("in_norm", (h,)),
        ("in_proj", (2 * inner + 2 * state + s["ssm_heads"], h)),
        ("norm", (inner,)),
        ("out_proj", (h, inner)),
    ]
    mlp = [
        (("mlp", "in_norm"), (h,)),
        (("mlp", "mlp", "down"), (h, width)),
        (("mlp", "mlp", "gate"), (width, h)),
        (("mlp", "mlp", "up"), (width, h)),
    ]
    out = [(("embed",), (s["vocab"], h)), (("final_norm",), (h,))]
    for at, index in enumerate(s["layers"]):
        base = ("layers", at)
        if s["kinds"][index] == "attention":  # "attn" sorts before "mlp", "ssm" after it
            out += [(base + ("attn", name), shape) for name, shape in attn]
            out += [(base + path, shape) for path, shape in mlp]
        else:
            out += [(base + path, shape) for path, shape in mlp]
            out += [(base + ("ssm", name), shape) for name, shape in ssm]
    return out


def parameter_count(s):
    return sum(math.prod(shape) for _, shape in leaf_shapes(s))


def unflatten(flat, s):
    """The nested parameter dict of one flat vector."""
    tree, at = {}, 0
    for path, shape in leaf_shapes(s):
        size = math.prod(shape)
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[at : at + size].reshape(shape).astype(F32)
        at += size
    if at != flat.shape[0]:
        raise ValueError(f"{flat.shape[0]} parameters given, the sizes take {at}")
    return tree


def rms(x, weight, eps):
    return weight * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def episode_positions(steps, positions):
    index = jnp.arange(steps)
    return index if positions is None else jnp.asarray(positions)


def causal_conv(p, xbc, s, positions):
    """The depthwise convolution over the sequence: tap ``k`` of a channel
    reads the entry ``d_conv - 1 - k`` positions back, where that entry lies
    in the same episode (an episode begins with an empty window)."""
    steps, width = xbc.shape[0], s["conv_width"]
    total = jnp.zeros_like(xbc)
    for back in range(width):
        shifted = jnp.pad(xbc, ((back, 0), (0, 0)))[:steps]
        total = total + jnp.where((positions >= back)[:, None], shifted, 0.0) * p["conv"][width - 1 - back]
    return jax.nn.silu(total + p["conv_bias"])


def scan_states(x, b, c, dt, rate, positions):
    """``S_t = exp(dt_t rate) S_{t-1} + dt_t x_t (outer) B_t`` a head, ``S =
    0`` before an episode's first entry: ``x`` ``(T, heads, head_dim)``, ``b``
    and ``c`` ``(T, state)``, ``dt`` ``(T, heads)``, ``rate`` ``(heads,)``.
    Returns, for every position, the readout ``S_t C_t`` and the state summed
    over its last axis ``sum_s S_t[h, p, s]``, ``(T, heads, head_dim)``
    each."""

    def step(state, entry):
        x_t, b_t, c_t, dt_t, position = entry
        state = jnp.where(position == 0, 0.0, state)
        state = jnp.exp(dt_t * rate)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, (jnp.einsum("hps,s->hp", state, c_t), jnp.sum(state, axis=-1))

    first = jnp.zeros(x.shape[1:] + (b.shape[-1],), F32)
    return jax.lax.scan(step, first, (x, b, c, dt, positions))[1]


def recurrence(x, b, c, dt, rate, positions):
    """The recurrence's readouts ``S_t C_t``, ``(T, heads, head_dim)``."""
    return scan_states(x, b, c, dt, rate, positions)[0]


def mamba(p, h, s, positions=None):
    return mixer(p, h, s, positions)[0]


def mixer(p, h, s, positions=None):
    """The hidden state after a Mamba-2 mixer, and its matrix state at every
    position, summed over the state's last axis ``(T, heads, head_dim)``."""
    steps, heads, state = h.shape[0], s["ssm_heads"], s["ssm_state"]
    inner = heads * s["ssm_head_dim"]
    positions = episode_positions(steps, positions)
    proj = rms(h, p["in_norm"], s["eps"]) @ p["in_proj"].T
    z, xbc, dt = proj[:, :inner], proj[:, inner : 2 * inner + 2 * state], proj[:, 2 * inner + 2 * state :]
    xbc = causal_conv(p, xbc, s, positions)
    x = xbc[:, :inner].reshape(steps, heads, s["ssm_head_dim"])
    b, c = xbc[:, inner : inner + state], xbc[:, inner + state :]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    rate = -jnp.exp(p["A_log"])
    y = recurrence(x, b, c, dt, rate, positions) + p["D"][:, None] * x
    y = rms(y.reshape(steps, inner) * jax.nn.silu(z), p["norm"], s["eps"])
    return h + s["residual_scale"] * (y @ p["out_proj"].T), scan_states(x, b, c, dt, rate, positions)[1]


def attention(p, h, s, positions=None):
    """``positions``: every entry's position in its own episode, where the
    sequence holds several episodes end to end: an entry sees its own
    episode only."""
    steps, heads, kv, hd = h.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    index = jnp.arange(steps)
    positions = episode_positions(steps, positions)
    begun = index - positions  # where the entry's episode began
    x = rms(h, p["in_norm"], s["eps"])
    q = (x @ p["q"].T).reshape(steps, kv, heads // kv, hd)
    k = (x @ p["k"].T).reshape(steps, kv, hd)
    v = (x @ p["v"].T).reshape(steps, kv, hd)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * s["score_scale"]
    seen = (positions[None, :] <= positions[:, None]) & (begun[:, None] == begun[None, :])
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    mixed = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, axis=-1), v)
    return h + s["residual_scale"] * (mixed.reshape(steps, heads * hd) @ p["o"].T)


def mlp(p, h, s):
    y = rms(h, p["in_norm"], s["eps"])
    inner = p["mlp"]
    return h + s["residual_scale"] * ((jax.nn.silu(y @ inner["gate"].T) * (y @ inner["up"].T)) @ inner["down"].T)


def embed(params, ids, s):
    return s["embed_scale"] * params["embed"][ids]


def layer(p, h, index, s, forced=None, positions=None):
    """One held layer (``index`` into the published stack): the hidden state
    after it, and what the layer hands back beside it, where the other
    references give a sparse layer's routes (no layer of this family routes;
    ``forced`` is theirs): a Mamba-2 layer its matrix state at every
    position, summed over the state's last axis ``(T, heads, head_dim)``, the
    attention layer None."""
    with jax.default_matmul_precision("highest"):
        if s["kinds"][index] == "attention":
            h, read = attention(p["attn"], h, s, positions), None
        else:
            h, read = mixer(p["ssm"], h, s, positions)
        return mlp(p["mlp"], h, s), read


def head(params, h, s):
    with jax.default_matmul_precision("highest"):
        return rms(h, params["final_norm"], s["eps"]) @ params["embed"].T / s["logits_divisor"]


def forward(params, ids, s, forced=None, positions=None):
    """Logits ``(T, vocab)`` of the id sequence ``ids`` ``(T,)`` under the
    parameter dict ``params`` (``unflatten`` of a flat vector), and an empty
    list where the other references give their routes. ``positions`` as in
    ``attention``."""
    h = embed(params, ids, s)
    for at, index in enumerate(s["layers"]):
        h, _ = layer(params["layers"][at], h, index, s, None, positions)
    return head(params, h, s), []
