"""Plain reference: the ``afmoe`` decoder (Trinity) over a WHOLE sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no library code, no cache, no
grouping: causal and window masks are ``jnp.where`` on a ``(T, T)`` score
matrix and the experts are a python loop. It is given the share the system
holds (``experts_held``, ``vocab_held``, ``layers_held``) like the system and
leaves out what absent experts would add, like the system.

The equations, for hidden state ``h`` at position ``t`` (the items marked +
have no key in the catalog's copy of the published config and follow the
family's ``modeling_afmoe``; the configuration lists them under ``assumed``):

- attention: ``x = RMSNorm(h)``; ``q = W_q x`` (heads x head_dim), ``k = W_k
  x``, ``v = W_v x`` (kv heads x head_dim), ``g = W_g x`` +; per-head RMSNorm
  of ``q`` and ``k`` +; RoPE (all of head_dim, ``rotate_half`` pairs) on ``q,
  k`` in sliding layers only, no positions in full layers +; scores ``q.k /
  sqrt(head_dim)`` over ``s <= t``, and ``s > t - sliding_window`` in sliding
  layers; softmax; ``heads / kv heads`` query heads share a KV head; ``a =
  (softmax . v) * sigmoid(g)`` +; ``h = h + RMSNorm(W_o a)`` (a norm before
  AND after the block +);
- MLP: ``y = RMSNorm(h)``; dense: ``m = W_down(silu(W_gate y) * W_up y)``;
  sparse: ``r = W_r y`` (``num_experts`` logits), ``s = sigmoid(r)``, the
  ``num_experts_per_tok`` experts with the largest ``s + expert_bias`` + (the
  bias selects and does not weigh), weights ``s_e / sum of the selected s``
  (``route_norm``) times ``route_scale``, ``m = sum_e w_e E_e(y) + S(y)`` over
  the HELD experts ``E_e`` and the shared ``S``; ``h = h + RMSNorm(m)`` +;
- ends: ``h_0 = E[token] * sqrt(hidden)`` (``mup_enabled``) +; ``logits =
  W_head RMSNorm(h_L)``.

Departures from ``modeling_afmoe`` (each is a matter of form, not of value in
float32): RMSNorm multiplies by its weight in float32 before the result is
cast (upstream casts first); the router's scores are computed from float32
activations here and upstream alike; experts are stacked ``(expert, in, out)``
(upstream: one ``nn.Linear`` ``(out, in)`` per expert per projection); the
attention mask is built here from positions (upstream receives it).

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
    ``num_experts`` and ``vocab_size`` are what is HELD here (they are under
    ``reduced``); the router's width is the published count."""
    scale = scale or {}
    layers = list(config["layers_held"])
    kept_sparse = int(scale.get("kept_sparse_layers", config["kept_sparse_layers"]))
    dense = [i for i in layers if i < config["num_dense_layers"]]
    sparse = [i for i in layers if i >= config["num_dense_layers"]][:kept_sparse]
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "num_experts": int(config["published"]["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["num_shared_experts"]),
        "num_dense_layers": int(config["num_dense_layers"]),
        "layer_types": list(config["layer_types"]),
        "window": int(config["sliding_window"]),
        "theta": float(config["rope_theta"]),
        "route_scale": float(config["route_scale"]),
        "route_norm": bool(config["route_norm"]),
        "eps": float(config["rms_norm_eps"]),
        "mup": bool(config["mup_enabled"]),
        "layers": dense + sparse,
        "experts_held": tuple(config["experts_held"]),  # (first id, one past the last)
        "vocab": int(scale.get("vocab_held", config["vocab_held"])),
    }


def leaf_shapes(s):
    """``[(path, shape), ...]`` in the order of the flat parameter vector."""
    h, hd = s["hidden"], s["head_dim"]
    wide, narrow = s["heads"] * hd, s["kv_heads"] * hd
    held = s["experts_held"][1] - s["experts_held"][0]
    attn = [
        ("g", (wide, h)), ("in_norm", (h,)), ("k", (narrow, h)), ("k_norm", (hd,)),
        ("o", (h, wide)), ("post_norm", (h,)), ("q", (wide, h)), ("q_norm", (hd,)),
        ("v", (narrow, h)),
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
            out.append((base + ("mlp", "post_norm"), (h,)))
        else:
            w = s["expert_width"]
            out.append((base + ("mlp", "expert_bias"), (s["num_experts"],)))
            out += [
                (base + ("mlp", "experts", "down"), (held, w, h)),
                (base + ("mlp", "experts", "gate"), (held, h, w)),
                (base + ("mlp", "experts", "up"), (held, h, w)),
                (base + ("mlp", "in_norm"), (h,)),
                (base + ("mlp", "post_norm"), (h,)),
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
    """``x`` ``(T, heads, head_dim)``; the pairs are the two halves."""
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


def attention(p, h, sliding, s, positions=None):
    """``positions``: every entry's position in its own episode, where the
    sequence holds several episodes end to end (a lane that ended one early
    and began the next): an entry sees its own episode only."""
    steps, hd = h.shape[0], s["head_dim"]
    index = jnp.arange(steps)
    positions = index if positions is None else positions
    begun = index - positions  # where the entry's episode began
    x = rms(h, p["in_norm"], s["eps"])
    q = (x @ p["q"].T).reshape(steps, s["heads"], hd)
    k = (x @ p["k"].T).reshape(steps, s["kv_heads"], hd)
    v = (x @ p["v"].T).reshape(steps, s["kv_heads"], hd)
    gate = x @ p["g"].T
    q, k = rms(q, p["q_norm"], s["eps"]), rms(k, p["k_norm"], s["eps"])
    if sliding:
        q, k = rope(q, positions, s["theta"]), rope(k, positions, s["theta"])
    share = s["heads"] // s["kv_heads"]  # query heads j*share .. (j+1)*share-1 read KV head j
    k, v = jnp.repeat(k, share, axis=1), jnp.repeat(v, share, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    t, at = positions[:, None], positions[None, :]
    seen = (at <= t) & (begun[:, None] == begun[None, :])
    if sliding:
        seen = seen & (at > t - s["window"])
    scores = jnp.where(seen[None], scores, -jnp.inf)
    mixed = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    mixed = mixed.reshape(steps, s["heads"] * hd) * jax.nn.sigmoid(gate)
    return h + rms(mixed @ p["o"].T, p["post_norm"], s["eps"])


def route(p, y, s, forced=None):
    """The experts this router chooses ``(T, top_k)``, the ids the layer
    goes on with and their weights. ``forced``: ids to go on with instead of
    the router's own (the weights are then this router's scores at THOSE
    ids): with random weights the eighth and ninth of 128 scores lie within
    a lower precision's rounding of each other every few positions, and one
    swapped expert moves that position's output by tens of percent, so a
    comparison of logits across precisions fixes the choice and counts the
    disagreements separately."""
    scores = jax.nn.sigmoid(y @ p["router"].T)
    _, chosen = jax.lax.top_k(scores + p["expert_bias"], s["top_k"])
    used = chosen if forced is None else forced
    weights = jnp.take_along_axis(scores, used, axis=-1)
    if s["route_norm"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
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
    return h + rms(mixed, p["post_norm"], s["eps"]), chosen


def dense_mlp(p, h, s):
    y = rms(h, p["in_norm"], s["eps"])
    return h + rms(swiglu(p["mlp"], y), p["post_norm"], s["eps"])


def embed(params, ids, s):
    scale = math.sqrt(s["hidden"]) if s["mup"] else 1.0
    return params["embed"][ids] * scale


def layer(p, h, index, s, forced=None, positions=None):
    """One held layer (``index`` into the published stack): the hidden state
    after it and the experts its router chose (None for a dense layer);
    ``forced`` as in ``route``, ``positions`` as in ``attention``."""
    with jax.default_matmul_precision("highest"):
        h = attention(p["attn"], h, s["layer_types"][index] == "sliding_attention", s, positions)
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
