"""Plain reference: the ``smallthinker`` decoder (SmallThinker-21BA3B) over a
WHOLE sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no library code, no cache, no
lanes, no grouping: causal and window masks are ``jnp.where`` on a ``(T, T)``
score matrix and the experts are a python loop. It is given the share the
system holds (``layers_held``, ``experts_held``, ``vocab_held``) like the
system and leaves out what absent experts would add, like the system.

The equations, for hidden state ``x`` entering layer ``l`` at position ``t``
(the items marked + have no key in the catalog's copy of the published config
and follow the family's description, arXiv:2507.20984; the configuration
lists them under ``assumed``):

- ``u = RMSNorm(x; w_in)``;
- route AHEAD, from ``u`` +: ``r = W_r u`` (``moe_num_primary_experts``
  logits); the ``moe_num_active_primary_experts`` largest; weights the
  softmax over the chosen logits (``moe_primary_router_apply_softmax`` with
  ``norm_topk_prob``: a softmax over all of them renormalised over the
  chosen); no shared expert, no selection bias;
- attention on ``u``: ``q = W_q u`` (heads x head_dim), ``k = W_k u``, ``v =
  W_v u`` (kv heads x head_dim), ``heads / kv heads`` query heads a KV head;
  no q/k norm, no bias, no output gate +; RoPE (``rope_theta``, all of
  head_dim, ``rotate_half`` pairs +) where ``rope_layout[l]`` is 1, no
  positions where it is 0; scores ``q.k / sqrt(head_dim)`` over ``s <= t`` of
  the entry's own episode, and ``s > t - sliding_window_size`` where
  ``sliding_window_layout[l]`` is 1; softmax; ``h = x + W_o a``;
- experts on the post-attention stream: ``y = RMSNorm(h; w_post)``; ``out = h
  + sum_e w_e W_down,e(relu(W_gate,e y) * W_up,e y)`` over the chosen HELD
  experts (ReGLU +);
- ends: ``h_0 = E[token]`` (no scale); ``logits = W_head RMSNorm(h_L)``
  (untied).

Departures from the published description (each a matter of form, not of
value in float32): RMSNorm multiplies by its weight in float32 before the
result is cast; the experts are stacked ``(expert, in, out)`` (upstream: one
linear layer ``(out, in)`` a projection and expert); the router's weights are
the softmax over the chosen logits, which is what a softmax over all of them
renormalised over the chosen equals; the attention mask is built here from
positions. The family's "secondary experts" have no key in the config and are
not built, here or in the system.

Two controls, never the cell's, change ``sizes``: ``route="after"`` routes
from ``RMSNorm(h; w_post)`` after the attention (Trinity's placement), and
``activation="silu"`` makes the experts SwiGLU.

The parameter layout is the library's (``jax.flatten_util.ravel_pytree`` over
nested dicts with sorted keys and a tuple of layers): ``unflatten`` lists it
by hand, so if the library ever lays parameters out otherwise the comparison
fails, as it should.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def sizes(config, scale=None):
    """What the equations need, from a configuration file (and the run's
    ``scale``: the rehearsal may hold fewer layers and rows). The file's
    ``moe_num_primary_experts`` and ``vocab_size`` are what is HELD here
    (they are under ``reduced``); the router's width is the published
    count."""
    scale = scale or {}
    kept = int(scale.get("kept_layers", config["kept_layers"]))
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "expert_width": int(config["moe_ffn_hidden_size"]),
        "num_experts": int(config["published"]["moe_num_primary_experts"]),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "route_norm": bool(config["norm_topk_prob"]),
        "shared": 0,
        "num_dense_layers": 0,
        "rope_layout": list(config["rope_layout"]),
        "sliding_layout": list(config["sliding_window_layout"]),
        "window": int(config["sliding_window_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "layers": list(config["layers_held"])[:kept],
        "experts_held": tuple(config["experts_held"]),  # (first id, one past the last)
        "vocab": int(scale.get("vocab_held", config["vocab_held"])),
        "route": "ahead",
        "activation": "relu",
    }


def leaf_shapes(s):
    """``[(path, shape), ...]`` in the order of the flat parameter vector."""
    h, hd, w = s["hidden"], s["head_dim"], s["expert_width"]
    wide, narrow = s["heads"] * hd, s["kv_heads"] * hd
    held = s["experts_held"][1] - s["experts_held"][0]
    attn = [("in_norm", (h,)), ("k", (narrow, h)), ("o", (h, wide)), ("q", (wide, h)), ("v", (narrow, h))]
    mlp = [
        (("experts", "down"), (held, w, h)),
        (("experts", "gate"), (held, h, w)),
        (("experts", "up"), (held, h, w)),
        (("in_norm",), (h,)),
        (("router",), (s["num_experts"], h)),
    ]
    out = [(("embed",), (s["vocab"], h)), (("final_norm",), (h,)), (("head",), (s["vocab"], h))]
    for at, _ in enumerate(s["layers"]):
        base = ("layers", at)
        out += [(base + ("attn", name), shape) for name, shape in attn]
        out += [(base + ("mlp",) + names, shape) for names, shape in mlp]
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


def attention(p, h, u, index, s, positions=None):
    """``h + W_o a`` with the attention reading ``u``, the normed input.
    ``positions``: every entry's position in its own episode, where the
    sequence holds several episodes end to end (a lane that ended one early
    and began the next): an entry sees its own episode only."""
    steps, hd = h.shape[0], s["head_dim"]
    index_t = jnp.arange(steps)
    positions = index_t if positions is None else positions
    begun = index_t - positions  # where the entry's episode began
    q = (u @ p["q"].T).reshape(steps, s["heads"], hd)
    k = (u @ p["k"].T).reshape(steps, s["kv_heads"], hd)
    v = (u @ p["v"].T).reshape(steps, s["kv_heads"], hd)
    if s["rope_layout"][index]:
        q, k = rope(q, positions, s["theta"]), rope(k, positions, s["theta"])
    share = s["heads"] // s["kv_heads"]  # query heads j*share .. (j+1)*share-1 read KV head j
    k, v = jnp.repeat(k, share, axis=1), jnp.repeat(v, share, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    t, at = positions[:, None], positions[None, :]
    seen = (at <= t) & (begun[:, None] == begun[None, :])
    if s["sliding_layout"][index]:
        seen = seen & (at > t - s["window"])
    scores = jnp.where(seen[None], scores, -jnp.inf)
    mixed = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return h + mixed.reshape(steps, s["heads"] * hd) @ p["o"].T


def route(p, r_in, s, forced=None):
    """The experts this router chooses ``(T, top_k)`` from ``r_in``, the ids
    the layer goes on with and their weights. ``forced``: ids to go on with
    instead of the router's own (the weights are then this router's softmax
    at THOSE ids): with random weights the sixth and seventh of 64 logits lie
    within a lower precision's rounding of each other every few positions,
    and one swapped expert moves that position's output by tens of percent,
    so a comparison of logits across precisions fixes the choice and counts
    the disagreements separately."""
    logits = r_in @ p["router"].T
    _, chosen = jax.lax.top_k(logits, s["top_k"])
    used = chosen if forced is None else forced
    if s["route_norm"]:  # a softmax over all, renormalised over the chosen
        weights = jax.nn.softmax(jnp.take_along_axis(logits, used, axis=-1), axis=-1)
    else:
        weights = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), used, axis=-1)
    return chosen, used, weights


def held_experts(p, y, chosen, weights, first, activation):
    """``sum_e w_e E_e(y)`` over the experts stacked in ``p`` (ids ``first``,
    ``first + 1``, ...), one after the other."""
    act = ACTIVATIONS[activation]
    total = jnp.zeros_like(y)
    for at in range(p["gate"].shape[0]):
        weight = jnp.sum(jnp.where(chosen == first + at, weights, 0.0), axis=-1)
        out = (act(y @ p["gate"][at]) * (y @ p["up"][at])) @ p["down"][at]
        total = total + weight[:, None] * out
    return total


def embed(params, ids, s):
    return params["embed"][ids]


def layer(p, x, index, s, forced=None, positions=None):
    """One held layer (``index`` into the published stack): the hidden state
    after it and the experts its router chose; ``forced`` as in ``route``,
    ``positions`` as in ``attention``."""
    with jax.default_matmul_precision("highest"):
        u = rms(x, p["attn"]["in_norm"], s["eps"])
        if s["route"] == "ahead":
            chosen, used, weights = route(p["mlp"], u, s, forced)
        h = attention(p["attn"], x, u, index, s, positions)
        y = rms(h, p["mlp"]["in_norm"], s["eps"])
        if s["route"] == "after":
            chosen, used, weights = route(p["mlp"], y, s, forced)
        mixed = held_experts(p["mlp"]["experts"], y, used, weights, s["experts_held"][0], s["activation"])
        return h + mixed, chosen


def head(params, h, s):
    with jax.default_matmul_precision("highest"):
        return rms(h, params["final_norm"], s["eps"]) @ params["head"].T


def forward(params, ids, s, forced=None, positions=None):
    """Logits ``(T, vocab)`` of the id sequence ``ids`` ``(T,)`` under the
    parameter dict ``params`` (``unflatten`` of a flat vector), and the
    experts every layer's router chose ``(T, top_k)``, in order.
    ``forced``: one ``(T, top_k)`` id array per layer, as in ``route``;
    ``positions`` as in ``attention``."""
    h = embed(params, ids, s)
    routes = []
    for at, index in enumerate(s["layers"]):
        h, chosen = layer(params["layers"][at], h, index, s, None if forced is None else forced[at], positions)
        routes.append(chosen)
    return head(params, h, s), routes
