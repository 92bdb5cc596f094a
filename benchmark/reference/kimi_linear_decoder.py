"""Plain reference: the ``kimi_linear`` decoder (Kimi-Linear) over a WHOLE
sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no library code, no cache, no
lanes, no carried state: each convolution is a causal depthwise convolution
over the sequence, the gated delta rule a ``lax.scan`` over positions with
the state in the published ``(key, value)`` orientation a head (the system
stores it turned), latent attention in the PLAIN form (every position's
per-head keys and values written out through ``W_kvb``; the system folds
``W_kvb`` into the query and output paths) with a ``jnp.where`` on a ``(T,
T)`` score matrix, the experts a python loop. It is given the share the
system holds (``layers_held``, ``experts_held``, ``vocab_held``) like the
system and leaves out what absent experts would add, like the system.

The equations, for hidden state ``h`` at position ``t`` of a lane's episode
(the items marked + have no key in the catalog's copy of the published config
and follow the Kimi Linear technical report, arXiv:2510.26692, and the
family's modelling code; the configuration lists them under ``assumed``):

- ends: ``h_0 = E[token]`` (no scale); ``logits = W_head RMSNorm(h_L)``
  (untied);
- every layer: ``h = h + block(RMSNorm(h))``, then ``h = h + MLP(RMSNorm(h))``;
  a layer's block is KDA where its 1-based id is in ``kda_layers`` and latent
  attention where it is in ``full_attn_layers``;
- KDA (``num_heads`` H x ``head_dim`` D, ``P = H D``): ``q, k, v =
  silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))``, each a
  depthwise causal convolution of ``short_conv_kernel_size`` taps over the
  positions of the entry's own episode, no bias +; per head ``q`` and ``k``
  divided by ``sqrt(sum of squares + 1e-6)`` +, ``q`` times ``D^-1/2`` +;
  ``g = -exp(A_log[h]) softplus(W_fb W_fa x + dt_bias)`` a (head, key
  channel) (gate rank D +); ``beta = sigmoid(W_b x)`` a head; a head's state
  ``S`` ``(D key, D value)``, zero where an episode begins: ``S' =
  Diag(exp(g)) S``, ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q``; out
  ``W_o [RMSNorm_D(o) * sigmoid(W_gb W_ga x)]``, one norm weight of D for all
  heads (gate rank D +);
- latent attention: ``q = W_q x`` (``q_lora_rank`` null: no LoRA, no norm),
  per head ``[q_n | q_r]``; ``[c | k_r] = W_kva x``, ``c = RMSNorm(c)``,
  ``k_r`` ONE key for all heads; no rotary embedding (``mla_use_nope``: both
  stay as they are); ``[k_n | v]_{h,s} = (W_kvb c_s)_h``; ``score =
  (q_n . k_n + q_r . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim)`` over
  ``s <= t`` of the entry's own episode; softmax; out ``W_o`` of the weighted
  values;
- MLP: the first ``first_k_dense_replace`` layers ``W_down(silu(W_gate y) *
  W_up y)``; the others ``s = sigmoid(W_r y)`` (``num_experts`` scores), the
  ``num_experts_per_token`` experts with the largest ``s +
  e_score_correction_bias`` (the bias selects and does not weigh; one group),
  weights ``s_e / (sum of the selected s + 1e-20)`` + (``moe_renormalize``)
  times ``routed_scaling_factor``, ``sum_e w_e E_e(y) + S(y)`` over the HELD
  experts ``E_e`` and the ``num_shared_experts`` shared ``S``.

Departures from the published modelling code (each a matter of form, not of
value in float32): RMSNorm multiplies by its weight in float32 before the
result is cast; the recurrence runs position by position where the published
code runs the chunked form (the same states in exact arithmetic); the
convolutions' weights are held taps first, ``(width, P)``, and named
``q_conv``, ``k_conv``, ``v_conv``; the decay's projections are ``f_a`` and
``f_b``, the output gate's ``g_a`` and ``g_b``, ``beta``'s ``b``; the bias
that selects is ``expert_bias`` and the experts are stacked ``(expert, in,
out)``; the attention mask is built here from positions.

Two controls, never the cell's, change ``sizes``: ``correction=False``
leaves out the delta rule's correction (``S = S' + beta k v^T``), ``stored=
"bfloat16"`` rounds the state to bfloat16 after every position, as the
system stores it.

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
    ``reduced``); the router's width is the published count. Of the held
    layers the first ``kept_kda_moe_layers`` KDA layers with experts stay,
    with every dense and every latent-attention layer."""
    scale = scale or {}
    linear = config["linear_attn_config"]
    kda = sorted(int(i) - 1 for i in linear["kda_layers"])  # published 1-based
    held = [int(i) for i in config["layers_held"]]
    first_sparse = int(config["first_k_dense_replace"])
    kept = int(scale.get("kept_kda_moe_layers", config["kept_kda_moe_layers"]))
    kda_moe = [i for i in held if i in kda and i >= first_sparse][:kept]
    if config["q_lora_rank"] is not None or not config["mla_use_nope"]:
        raise ValueError("the reference's latent attention has no query LoRA and no rotary embedding")
    return {
        "hidden": int(config["hidden_size"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_head_dim": int(linear["head_dim"]),
        "conv_width": int(linear["short_conv_kernel_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "num_experts": int(config["published"]["num_experts"]),
        "top_k": int(config["num_experts_per_token"]),
        "shared": int(config["num_shared_experts"]),
        "num_dense_layers": first_sparse,
        "route_scale": float(config["routed_scaling_factor"]),
        "route_norm": bool(config["moe_renormalize"]),
        "eps": float(config["rms_norm_eps"]),
        "kda_layers": kda,
        "layers": [i for i in held if i < first_sparse or i not in kda or i in kda_moe],
        "experts_held": tuple(config["experts_held"]),  # (first id, one past the last)
        "vocab": int(scale.get("vocab_held", config["vocab_held"])),
        "correction": True,
    }


def leaf_shapes(s):
    """``[(path, shape), ...]`` in the order of the flat parameter vector."""
    h, heads = s["hidden"], s["heads"]
    inner, rank = s["kda_heads"] * s["kda_head_dim"], s["kda_head_dim"]
    held = s["experts_held"][1] - s["experts_held"][0]
    kda = [  # sorted as python sorts them: capitals first
        ("A_log", (s["kda_heads"],)),
        ("b", (s["kda_heads"], h)),
        ("dt_bias", (inner,)),
        ("f_a", (rank, h)),
        ("f_b", (inner, rank)),
        ("g_a", (rank, h)),
        ("g_b", (inner, rank)),
        ("in_norm", (h,)),
        ("k", (inner, h)),
        ("k_conv", (s["conv_width"], inner)),
        ("o", (h, inner)),
        ("o_norm", (s["kda_head_dim"],)),
        ("q", (inner, h)),
        ("q_conv", (s["conv_width"], inner)),
        ("v", (inner, h)),
        ("v_conv", (s["conv_width"], inner)),
    ]
    attn = [
        ("in_norm", (h,)),
        ("kv_a", (s["kv_rank"] + s["rope"], h)),
        ("kv_a_norm", (s["kv_rank"],)),
        ("kv_b", (heads * (s["nope"] + s["v"]), s["kv_rank"])),
        ("o", (h, heads * s["v"])),
        ("q", (heads * (s["nope"] + s["rope"]), h)),
    ]

    def swiglu(width):  # keys sorted: down, gate, up
        return [("down", (h, width)), ("gate", (width, h)), ("up", (width, h))]

    out = [(("embed",), (s["vocab"], h)), (("final_norm",), (h,)), (("head",), (s["vocab"], h))]
    for at, index in enumerate(s["layers"]):
        base = ("layers", at)
        if index in s["kda_layers"]:  # "kda" sorts before "mlp"
            out += [(base + ("kda", name), shape) for name, shape in kda]
        else:  # so does "attn"
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


def episode_positions(steps, positions):
    return jnp.arange(steps) if positions is None else jnp.asarray(positions)


def causal_conv(taps, x, positions):
    """A depthwise convolution over the sequence, then silu: tap ``j`` of a
    channel reads the entry ``width - 1 - j`` positions back, where that
    entry lies in the same episode (an episode begins with an empty window)."""
    steps, width = x.shape[0], taps.shape[0]
    total = jnp.zeros_like(x)
    for back in range(width):
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[:steps]
        total = total + jnp.where((positions >= back)[:, None], shifted, 0.0) * taps[width - 1 - back]
    return jax.nn.silu(total)


def l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, positions, correction=True, stored=None):
    """The gated delta rule a head, position by position: ``q``, ``k``, ``g``
    ``(T, H, D)``, ``v`` ``(T, H, D)``, ``beta`` ``(T, H)``. Returns, for
    every position, the readout ``S^T q`` ``(T, H, D)`` and the state summed
    over its key axis ``(T, H, D)``."""

    def step(state, entry):
        q_t, k_t, v_t, g_t, beta_t, position = entry
        state = jnp.where(position == 0, 0.0, state)  # (H, key, value)
        state = jnp.exp(g_t)[:, :, None] * state
        fed = v_t - jnp.einsum("hkv,hk->hv", state, k_t) if correction else v_t
        state = state + beta_t[:, None, None] * k_t[:, :, None] * fed[:, None, :]
        out = jnp.einsum("hkv,hk->hv", state, q_t)
        if stored is not None:  # a rounding the compiler keeps (a round trip of casts it may drop)
            info = jnp.finfo(stored)
            state = jax.lax.reduce_precision(state, exponent_bits=info.nexp, mantissa_bits=info.nmant)
        return state, (out, jnp.sum(state, axis=1))

    first = jnp.zeros(k.shape[1:] + (v.shape[-1],), F32)
    return jax.lax.scan(step, first, (q, k, v, g, beta, positions))[1]


def kda(p, h, s, positions=None):
    """The hidden state after a KDA block, and its state at every position,
    summed over the key axis ``(T, heads, head_dim)``."""
    steps, heads, dim = h.shape[0], s["kda_heads"], s["kda_head_dim"]
    positions = episode_positions(steps, positions)
    x = rms(h, p["in_norm"], s["eps"])
    q, k, v = (causal_conv(p[n + "_conv"], x @ p[n].T, positions).reshape(steps, heads, dim) for n in "qkv")
    q, k = l2(q) * dim**-0.5, l2(k)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus((x @ p["f_a"].T) @ p["f_b"].T + p["dt_bias"]).reshape(
        steps, heads, dim
    )
    beta = jax.nn.sigmoid(x @ p["b"].T)
    o, sums = delta_rule(q, k, v, g, beta, positions, s.get("correction", True), s.get("stored"))
    gate = jax.nn.sigmoid(((x @ p["g_a"].T) @ p["g_b"].T).reshape(steps, heads, dim))
    out = (rms(o, p["o_norm"], s["eps"]) * gate).reshape(steps, heads * dim)
    return h + out @ p["o"].T, sums


def attention(p, h, s, positions=None):
    """Latent attention in the plain form; ``positions``: every entry's
    position in its own episode, where the sequence holds several episodes
    end to end: an entry sees its own episode only."""
    steps, heads = h.shape[0], s["heads"]
    index = jnp.arange(steps)
    positions = episode_positions(steps, positions)
    begun = index - positions  # where the entry's episode began
    x = rms(h, p["in_norm"], s["eps"])
    q = (x @ p["q"].T).reshape(steps, heads, s["nope"] + s["rope"])
    kv = x @ p["kv_a"].T
    c = rms(kv[:, : s["kv_rank"]], p["kv_a_norm"], s["eps"])
    k_r = jnp.broadcast_to(kv[:, None, s["kv_rank"] :], (steps, heads, s["rope"]))
    up = (c @ p["kv_b"].T).reshape(steps, heads, s["nope"] + s["v"])
    scores = jnp.einsum("thd,shd->hts", q[..., : s["nope"]], up[..., : s["nope"]]) + jnp.einsum(
        "thd,shd->hts", q[..., s["nope"] :], k_r
    )
    scores = scores / math.sqrt(s["nope"] + s["rope"])
    seen = (positions[None, :] <= positions[:, None]) & (begun[:, None] == begun[None, :])
    scores = jnp.where(seen[None], scores, -jnp.inf)
    mixed = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), up[..., s["nope"] :])
    return h + mixed.reshape(steps, heads * s["v"]) @ p["o"].T


def swiglu(p, y):
    return (jax.nn.silu(y @ p["gate"].T) * (y @ p["up"].T)) @ p["down"].T


def route(p, y, s, forced=None):
    """The experts this router chooses ``(T, top_k)``, the ids the layer goes
    on with (``forced`` where given: a comparison of logits fixes the choice
    and counts the disagreements apart) and their weights."""
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


def embed(params, ids, s):
    return params["embed"][ids]


def layer(p, h, index, s, forced=None, positions=None):
    """One held layer (``index`` into the published stack): the hidden state
    after it, the experts its router chose (None for a dense layer) and, for
    a KDA layer, its state at every position summed over the key axis ``(T,
    heads, head_dim)`` (None for latent attention); ``forced`` as in
    ``route``, ``positions`` as in ``attention``."""
    with jax.default_matmul_precision("highest"):
        if index in s["kda_layers"]:
            h, sums = kda(p["kda"], h, s, positions)
        else:
            h, sums = attention(p["attn"], h, s, positions), None
        if index < s["num_dense_layers"]:
            return h + swiglu(p["mlp"]["mlp"], rms(h, p["mlp"]["in_norm"], s["eps"])), None, sums
        h, chosen = sparse_mlp(p["mlp"], h, s, forced)
        return h, chosen, sums


def head(params, h, s):
    with jax.default_matmul_precision("highest"):
        return rms(h, params["final_norm"], s["eps"]) @ params["head"].T


def forward(params, ids, s, forced=None, positions=None):
    """Logits ``(T, vocab)`` of the id sequence ``ids`` ``(T,)`` under the
    parameter dict ``params`` (``unflatten`` of a flat vector), and the
    experts every sparse layer's router chose ``(T, top_k)``, in order.
    ``forced``: one ``(T, top_k)`` id array per sparse layer, as in
    ``route``; ``positions`` as in ``attention``."""
    h = embed(params, ids, s)
    routes = []
    for at, index in enumerate(s["layers"]):
        force = None if forced is None or index < s["num_dense_layers"] else forced[len(routes)]
        h, chosen, _ = layer(params["layers"][at], h, index, s, force, positions)
        if chosen is not None:
            routes.append(chosen)
    return head(params, h, s), routes
