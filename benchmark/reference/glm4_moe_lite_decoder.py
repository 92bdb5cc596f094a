"""Plain reference: the ``glm4_moe_lite`` decoder (GLM-4.7-Flash) over a
WHOLE sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no library code, no cache, no
grouping, and the PLAIN form of latent attention: every position's per-head
keys and values are written out through ``W_kvb`` (the system never does: it
folds ``W_kvb`` into the query and output paths). The causal mask is
``jnp.where`` on a ``(T, T)`` score matrix and the experts are a python loop.
It is given the share the system holds (``experts_held``, ``vocab_held``,
``layers_held``) like the system and leaves out what absent experts would
add, like the system.

The equations, for hidden state ``h`` at position ``t`` of a lane's episode
(the items marked + have no key in the catalog's copy of the published config
and follow the DeepSeek-V3 attention and GLM-4-MoE blocks that
``glm4_moe_lite`` is made of; the configuration lists them under ``assumed``):

- latent attention (every layer): ``x = RMSNorm(h)``; ``c_q = RMSNorm_q(W_qa
  x)`` (``q_lora_rank``; ``q_a_layernorm`` +); ``[q_n | q_r]_h = (W_qb c_q)_h``
  (per head ``qk_nope_head_dim | qk_rope_head_dim``); ``[c | k_r] = W_kva x``
  (``kv_lora_rank | qk_rope_head_dim``); ``c = RMSNorm_kv(c)``
  (``kv_a_layernorm`` +); ``k_r`` is ONE key shared by all heads +; RoPE
  (``rotate_half`` pairs over all of ``qk_rope_head_dim`` +) on ``q_r`` of
  every head and on ``k_r``, with the position in the episode; ``[k_n |
  v]_{h,s} = (W_kvb c_s)_h`` (per head ``qk_nope_head_dim | v_head_dim``);
  ``score_{h,s} = (q_n . k_n + q_r . k_r) / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)`` over ``s <= t``; softmax; ``a_h = sum_s p_{h,s}
  v_{h,s}``; ``h = h + W_o [a_1 .. a_H]``. No bias, no output gate, no
  per-head norm, no norm after the block, every layer full attention;
- MLP: ``y = RMSNorm(h)``; the first ``first_k_dense_replace`` layers: ``h =
  h + W_down(silu(W_gate y) * W_up y)``; the others: ``s = sigmoid(W_r y)``
  (``n_routed_experts`` scores), the ``num_experts_per_tok`` experts with the
  largest ``s + e_score_correction_bias`` (the bias selects and does not
  weigh; ``n_group = topk_group = 1``: no group limit), weights ``s_e / (sum
  of the selected s + 1e-20)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``, ``h = h + sum_e w_e E_e(y) + S(y)`` over the HELD
  experts ``E_e`` and the shared ``S``;
- ends: ``h_0 = E[token]`` (no scale); ``logits = W_head RMSNorm(h_L)``.

Departures from the published modelling code (each a matter of form, not of
value in float32): RMSNorm multiplies by its weight in float32 before the
result is cast (upstream casts first); RoPE pairs the two halves of the 64
rotary inputs (``rotate_half``) where the family's flag interleaves them,
which only permutes the 64 inputs of a seeded matrix; the bias that selects is
named ``expert_bias`` and the experts are stacked ``(expert, in, out)``
(upstream: ``e_score_correction_bias``, one ``nn.Linear`` ``(out, in)`` per
expert per projection); the attention mask is built here from positions
(upstream receives it); the next-token-prediction block
(``num_nextn_predict_layers``) is not built: the published causal forward
does not run it.

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
    ``n_routed_experts`` and ``vocab_size`` are what is HELD here (they are
    under ``reduced``); the router's width is the published count."""
    scale = scale or {}
    layers = list(config["layers_held"])
    kept_sparse = int(scale.get("kept_sparse_layers", config["kept_sparse_layers"]))
    first_sparse = int(config["first_k_dense_replace"])
    dense = [i for i in layers if i < first_sparse]
    sparse = [i for i in layers if i >= first_sparse][:kept_sparse]
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "num_experts": int(config["published"]["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["n_shared_experts"]),
        "num_dense_layers": first_sparse,
        "theta": float(config["rope_theta"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "route_norm": bool(config["norm_topk_prob"]),
        "eps": float(config["rms_norm_eps"]),
        "layers": dense + sparse,
        "experts_held": tuple(config["experts_held"]),  # (first id, one past the last)
        "vocab": int(scale.get("vocab_held", config["vocab_held"])),
    }


def leaf_shapes(s):
    """``[(path, shape), ...]`` in the order of the flat parameter vector."""
    h, heads = s["hidden"], s["heads"]
    held = s["experts_held"][1] - s["experts_held"][0]
    attn = [
        ("in_norm", (h,)),
        ("kv_a", (s["kv_rank"] + s["rope"], h)),
        ("kv_a_norm", (s["kv_rank"],)),
        ("kv_b", (heads * (s["nope"] + s["v"]), s["kv_rank"])),
        ("o", (h, heads * s["v"])),
        ("q_a", (s["q_rank"], h)),
        ("q_a_norm", (s["q_rank"],)),
        ("q_b", (heads * (s["nope"] + s["rope"]), s["q_rank"])),
    ]

    def swiglu(width):  # keys sorted: down, gate, up
        return [("down", (h, width)), ("gate", (width, h)), ("up", (width, h))]

    out = [(("embed",), (s["vocab"], h)), (("final_norm",), (h,)), (("head",), (s["vocab"], h))]
    for at, index in enumerate(s["layers"]):
        base = ("layers", at)
        out += [(base + ("attn", name), shape) for name, shape in attn]
        if index < s["num_dense_layers"]:
            out.append((base + ("mlp", "in_norm"), (h,)))
            out += [(base + ("mlp", "mlp", n), shape) for n, shape in swiglu(s["dense_width"])]
        else:
            w = s["expert_width"]
            out.append((base + ("mlp", "expert_bias"), (s["num_experts"],)))
            out += [
                (base + ("mlp", "experts", "down"), (held, w, h)),
                (base + ("mlp", "experts", "gate"), (held, h, w)),
                (base + ("mlp", "experts", "up"), (held, h, w)),
                (base + ("mlp", "in_norm"), (h,)),
                (base + ("mlp", "router"), (s["num_experts"], h)),
            ]
            if s["shared"]:
                out += [(base + ("mlp", "shared", n), shape) for n, shape in swiglu(s["shared"] * w)]
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


def rope(x, positions, theta):
    """``x`` ``(T, heads, dim)``; the pairs are the two halves."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[:, None, None] * inv_freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(angle) - x2 * jnp.sin(angle), x2 * jnp.cos(angle) + x1 * jnp.sin(angle)],
        axis=-1,
    )


def swiglu(p, y):
    return (jax.nn.silu(y @ p["gate"].T) * (y @ p["up"].T)) @ p["down"].T


def queries(p, x, s, positions):
    """``q_n`` ``(T, heads, qk_nope)`` and ``q_r`` ``(T, heads, qk_rope)``,
    the latter with its positions."""
    c_q = rms(x @ p["q_a"].T, p["q_a_norm"], s["eps"])
    q = (c_q @ p["q_b"].T).reshape(x.shape[0], s["heads"], s["nope"] + s["rope"])
    return q[..., : s["nope"]], rope(q[..., s["nope"] :], positions, s["theta"])


def keys_and_values(p, x, s, positions):
    """``k_n`` ``(T, heads, qk_nope)``, ``k_r`` ``(T, 1, qk_rope)`` (ONE key,
    with its position, for all heads) and ``v`` ``(T, heads, v_head_dim)``:
    every position's latent row written out through ``W_kvb``."""
    kv = x @ p["kv_a"].T
    c = rms(kv[:, : s["kv_rank"]], p["kv_a_norm"], s["eps"])
    k_r = rope(kv[:, None, s["kv_rank"] :], positions, s["theta"])
    up = (c @ p["kv_b"].T).reshape(x.shape[0], s["heads"], s["nope"] + s["v"])
    return up[..., : s["nope"]], k_r, up[..., s["nope"] :]


def attention(p, h, s, positions=None):
    """``positions``: every entry's position in its own episode, where the
    sequence holds several episodes end to end (a lane that ended one early
    and began the next): an entry sees its own episode only."""
    steps, heads = h.shape[0], s["heads"]
    index = jnp.arange(steps)
    positions = index if positions is None else positions
    begun = index - positions  # where the entry's episode began
    x = rms(h, p["in_norm"], s["eps"])
    q_n, q_r = queries(p, x, s, positions)
    k_n, k_r, v = keys_and_values(p, x, s, positions)
    k_r = jnp.broadcast_to(k_r, (steps, heads, s["rope"]))
    scores = jnp.einsum("thd,shd->hts", q_n, k_n) + jnp.einsum("thd,shd->hts", q_r, k_r)
    scores = scores / math.sqrt(s["nope"] + s["rope"])
    seen = (positions[None, :] <= positions[:, None]) & (begun[:, None] == begun[None, :])
    scores = jnp.where(seen[None], scores, -jnp.inf)
    mixed = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return h + mixed.reshape(steps, heads * s["v"]) @ p["o"].T


def route(p, y, s, forced=None):
    """The experts this router chooses ``(T, top_k)``, the ids the layer
    goes on with and their weights. ``forced``: ids to go on with instead of
    the router's own (the weights are then this router's scores at THOSE
    ids): with random weights the fourth and fifth of 64 scores lie within a
    lower precision's rounding of each other every few positions, and one
    swapped expert moves that position's output by tens of percent, so a
    comparison of logits across precisions fixes the choice and counts the
    disagreements separately."""
    scores = jax.nn.sigmoid(y @ p["router"].T)
    _, chosen = jax.lax.top_k(scores + p["expert_bias"], s["top_k"])
    used = chosen if forced is None else forced
    weights = jnp.take_along_axis(scores, used, axis=-1)
    if s["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, used, weights * s["route_scale"]


def held_experts(p, y, chosen, weights, first):
    """``sum_e w_e E_e(y)`` over the experts stacked in ``p`` (ids ``first``,
    ``first + 1``, ...), one after the other."""
    total = jnp.zeros_like(y)
    for at in range(p["gate"].shape[0]):
        weight = jnp.sum(jnp.where(chosen == first + at, weights, 0.0), axis=-1)
        out = (jax.nn.silu(y @ p["gate"][at]) * (y @ p["up"][at])) @ p["down"][at]
        total = total + weight[:, None] * out
    return total


def sparse_mlp(p, h, s, forced=None):
    """The layer's output and the experts its router chose at every position."""
    y = rms(h, p["in_norm"], s["eps"])
    chosen, used, weights = route(p, y, s, forced)
    mixed = held_experts(p["experts"], y, used, weights, s["experts_held"][0])
    if "shared" in p:
        mixed = mixed + swiglu(p["shared"], y)
    return h + mixed, chosen


def dense_mlp(p, h, s):
    return h + swiglu(p["mlp"], rms(h, p["in_norm"], s["eps"]))


def embed(params, ids, s):
    return params["embed"][ids]


def layer(p, h, index, s, forced=None, positions=None):
    """One held layer (``index`` into the published stack): the hidden state
    after it and the experts its router chose (None for a dense layer);
    ``forced`` as in ``route``, ``positions`` as in ``attention``."""
    with jax.default_matmul_precision("highest"):
        h = attention(p["attn"], h, s, positions)
        if index < s["num_dense_layers"]:
            return dense_mlp(p["mlp"], h, s), None
        return sparse_mlp(p["mlp"], h, s, forced)


def head(params, h, s):
    with jax.default_matmul_precision("highest"):
        return rms(h, params["final_norm"], s["eps"]) @ params["head"].T


def forward(params, ids, s, forced=None, positions=None):
    """Logits ``(T, vocab)`` of the id sequence ``ids`` ``(T,)`` under the
    parameter dict ``params`` (``unflatten`` of a flat vector), and the
    experts every sparse layer's router chose ``(T, top_k)``, in order.
    ``forced``: one ``(T, top_k)`` id array per sparse layer, as in ``route``;
    ``positions`` as in ``attention``."""
    h = embed(params, ids, s)
    routes = []
    for at, index in enumerate(s["layers"]):
        force = None if forced is None or index < s["num_dense_layers"] else forced[len(routes)]
        h, chosen = layer(params["layers"][at], h, index, s, force, positions)
        if chosen is not None:
            routes.append(chosen)
    return head(params, h, s), routes
