"""A language-model decoder as an evolvable policy, decoded stepwise.

The modules of five families: ``afmoe`` (Trinity: gated grouped-query
attention, a norm before and after every block, sigmoid-routed experts),
``glm4_moe_lite`` (GLM-4.7-Flash: latent attention, a norm before a block
only), ``granitemoehybrid`` (Granite 4.0-H: Mamba-2 mixers with one
grouped-query attention layer in ten, dense MLPs, a tied head, four
multipliers), ``kimi_linear`` (Kimi-Linear: gated delta-rule layers, KDA,
with one latent-attention layer in four, sigmoid-routed experts) and
``smallthinker`` (SmallThinker: grouped-query attention, full and without
positions in one layer of four, a RoPE window in the others, and experts
chosen BEFORE the attention by a softmax router: ReGLU experts). Token
embedding, RMSNorm, an attention with a per-lane cache or a recurrence with a
per-lane matrix state as the policy's recurrent state, SwiGLU, and an expert
layer (sigmoid- or softmax-routed; SwiGLU or ReGLU experts) that is told
which experts it holds. They follow the ``Module`` protocol of ``layers.py`` (``init`` /
``initial_state`` / ``apply(params, x, state)``), so a decoder is a policy
like any other: the observation is one token id, the output the logits over
the held vocabulary, the state what its layers carry from step to step.

Two forwards per module, one set of equations (``_forward`` of each class
takes the two accessors that differ):

- ``apply(params, x, state)``: one lane with its own weights: the dense form
  (``vmap`` over a population), for tests and for the comparison with the
  plain reference (``benchmark/reference/afmoe_decoder.py``);
- ``trunk_delta_apply(center, factors, z, x, state)``: every lane at once in
  the shared-trunk form (``net/lowrank.py``): each projection is ``x @ W_c^T
  + ((x @ A) * z) @ B^T`` over all lanes, the held experts are ONE grouped
  product over the (lane, expert) pairs that hit them, attention reads the
  lanes' caches. ``net/lowrank.py:_apply_trunk_delta`` dispatches to it.

**Shares of a deployment.** ``experts_held`` (a range of expert ids) and
``vocab_held`` (rows of embedding and head) say which part of the published
model this process holds. The expert layer routes over ALL ``num_experts``,
normalises over the selected ``num_experts_per_tok``, and adds only its held
experts' terms and the shared expert: what absent experts would add is left
out, and that partial result goes on. Nothing stands in for absent chips.

**The cache.** ``sliding_attention`` layers hold a ring of
``min(sliding_window, max_positions)`` slots, ``full_attention`` layers
``max_positions`` slots, laid out ``(kv_heads, slots, head_dim)`` per lane in
the compute dtype. A lane's state holds its position ``t`` (reset with the
lane) and a write pointer ``step`` that counts the steps since the state was
made and is NOT reset: every lane of a population steps once per control
step, so ``step`` is one number for all of them and the population-wide
forward writes the new key and value of every lane with one
``dynamic_update_slice`` at slot ``step mod slots`` (a per-lane slot would be
a 512-row scatter per layer per step). A slot's age is ``(step - slot) mod
slots`` and a lane reads the slots no older than its own ``t``: its own
episode, at most ``slots`` back, whatever the other lanes did. RoPE is
applied to a key with its lane's position when it is written.
``reset_state`` zeroes the cache rows and ``t`` of the lanes that ended an
episode, lane by lane, and leaves ``step``.

**The latent cache.** ``LatentAttention`` keeps, per lane and layer, one
compressed row ``c`` ``(slots, kv_lora_rank)`` (after its norm) and one RoPE
key ``kr`` ``(slots, qk_rope_head_dim)`` shared by all heads, and nothing per
head: written, aged and reset as above, ``slots = max_positions`` (every
layer is full attention). It decodes in the **absorbed** form: with
``kv_b`` viewed ``(heads, qk_nope + v, kv_lora_rank)`` = ``[W_UK[h];
W_UV[h]]``, a head's no-position query goes INTO the latent space (``q_n
W_UK[h]``), scores and the weighted sum run over ``c`` once for all heads,
and the result comes back through ``W_UV[h]``. In the shared-trunk form
every lane's ``kv_b`` is its own, so the accessors carry the product with a
head's ROW BLOCK of a leaf, untransposed or transposed (``head_mm``); no
lane's ``kv_b`` is written out and no per-head key or value exists.
Between the write and ``W_UV`` the pass over the cache (``_cache_pass``) is
ONE kernel a layer where the program is lowered for a TPU, the sizes are the
kernel's and no mesh spreads the lanes (``net/latent.py``: a block of a lane's
rows comes into VMEM once for scores, blockwise softmax and weighted sum, and
the walk stops at what the lane has filled, counted back from the write
pointer), and XLA's plain form everywhere else (``_cache_plain``: every slot
under the mask; the statement of the equations). The state counts what a lane
could read (``read``) and the positions in the blocks fetched for it
(``fetched``: 0 where the plain form ran); neither is ever reset.

**The recurrent state.** ``Mamba2Mixer`` keeps, per lane and layer, the
window of its convolution's last ``width - 1`` inputs and one matrix state,
stored turned, ``(state_dim, heads x head_dim)`` (1 MiB in bfloat16 at
Granite's widths), in the compute dtype like the caches. Where a cache is
written one slot a step and read under a mask, ALL of this state is rewritten
every step: decay, outer product, readout and write-back are one pass over it
in float32 (``fwd_ssm_state``): ONE kernel a layer that updates a lane's
state in VMEM and writes it back in place where the program is lowered for a
TPU and the sizes are the kernel's (``net/ssmstate.py``), XLA's plain form
everywhere else (``_state_plain``: the statement of the equations). Either
way the loop holds each state once and no step copies or selects over one.
The state counts the steps it was rewritten (``updates``) and those of them
the kernel rewrote (``kernel_updates``). ``reset_state`` zeroes the lanes that ended an
episode lane by lane, as the caches are. Each lane's transition is its own:
``A_log``, ``dt_bias`` and ``D`` are 1-D leaves, perturbed like any other.
A layer's state sits under its first block's kind (``"attn"``, ``"ssm"`` or
``"kda"``). ``KimiDeltaAttention`` keeps the same two things in the same
layout (windows of ``q``, ``k`` and ``v``; a matrix state a head, ``(key,
value)``, stored turned), decayed by one factor a KEY CHANNEL and corrected by
a rank-1 delta rule before its outer product is added, so the pass reduces
over the whole state before it can write any of it (``fwd_kda_state``): ONE
kernel a layer that holds a lane's state in VMEM for both (``net/kdastate.py``,
on ``net/ssmstate.py``'s walk) where the program is lowered for a TPU and the
sizes are the kernel's, XLA's plain form everywhere else, counted as the
mixer's are (``updates``, ``kernel_updates``).
When a lane ends an episode the mixer keeps, before it zeroes the lane, what
its matrix states held, summed over ``state_dim`` (``ended``): nothing a
step pays for, and what an evaluation's report holds against a reference's
recurrence.

**What a lane consumed.** The decoder's state keeps each lane's position in
its episode (``t``, its own: reset with the lane, whatever kinds of layer it
holds) and, per lane, the token id and that position of each of the last
``max_positions`` steps (``seen``; written like the cache, never reset): the
generated text of an evaluation is its observations, so ``state_report``
hands back what every lane read and wrote, and ``stepwise_logits`` replays
such a record.

No reference counterpart: the reference evaluates MLP and small recurrent
policies only.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...envs.rigidbody import _by_platform
from ...observability.scopes import scope
from . import grouped, kdastate, latent, ssmstate
from .layers import Module

__all__ = [
    "Embedding",
    "RMSNorm",
    "SwiGLU",
    "GatedAttention",
    "LatentAttention",
    "Mamba2Mixer",
    "KimiDeltaAttention",
    "SparseExperts",
    "DecoderLayer",
    "RouteAheadLayer",
    "AfmoeDecoder",
    "Glm4MoeLiteDecoder",
    "GraniteMoeHybridDecoder",
    "KimiLinearDecoder",
    "SmallThinkerDecoder",
    "stepwise_logits",
]

F32 = jnp.float32
INIT_STD = 0.02  # the family's ``initializer_range``


def _normal(key, shape, std=INIT_STD):
    return std * jax.random.normal(key, shape, F32)


def rms_norm(x, weight, eps):
    """``weight * x / sqrt(mean(x^2) + eps)`` over the last axis, computed in
    float32 and returned in ``x``'s dtype."""
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(F32)).astype(x.dtype)


def rope(x, positions, theta):
    """Rotary embedding over all of the last axis (the ``rotate_half``
    convention: the two halves of a head are the pairs). ``positions``
    broadcasts against ``x``'s leading axes."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.asarray(positions, F32)[..., None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(F32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _closed(module, acc, out):
    """A block's output as it joins the residual: through the block's closing
    norm where the family has one."""
    return rms_norm(out, acc.vec("post_norm"), module.eps) if module.post_norm else out


def _joined(module, x, out):
    """``x + out``, ``out`` times the family's residual multiplier where it
    has one."""
    return x + (out if module.residual_scale == 1.0 else out * module.residual_scale)


# -- the two accessors ---------------------------------------------------------
# A module's equations read its parameters through ``mm(name, x)`` (x times
# the transposed ``(out, in)`` weight), ``head_mm(name, x, heads, rows,
# into=...)`` (x ``(n, heads, .)`` times each head's row block of the leaf
# viewed ``(heads, out / heads, in)``: ``into`` the leaf's input space, ``x @
# W[h, rows]``, or out of it, ``x @ W[h, rows]^T``), ``vec(name)`` (a 1-D
# parameter, broadcast against the lanes), ``mat(name)`` (a SMALL ``(out,
# in)`` leaf written out per lane, for elementwise use: the depthwise
# convolution's taps) and ``rows(name, ids)`` (rows of a table). Dense: one
# lane, its own weights, a lane axis of one. Trunk-delta: all lanes, shared
# trunk plus per-lane rank-k delta ``B diag(z) A^T``, whose row block is
# ``B[rows] diag(z) A^T``.


def _head_block(leaf, heads, rows, dtype):
    return leaf.reshape(heads, leaf.shape[0] // heads, leaf.shape[1])[:, rows].astype(dtype)


class _Dense:
    def __init__(self, params):
        self.p = params
        self.z = None

    def sub(self, name):
        return _Dense(self.p[name])

    def mm(self, name, x, precision=None):
        return jnp.matmul(x, self.p[name].T.astype(x.dtype), precision=precision)

    def head_mm(self, name, x, heads, rows, *, into):
        w = _head_block(self.p[name], heads, rows, x.dtype)
        return jnp.einsum("nhr,hri->nhi" if into else "nhi,hri->nhr", x, w)

    def vec(self, name):
        return self.p[name][None]

    def mat(self, name):
        return self.p[name][None]

    def rows(self, name, ids):
        return self.p[name][ids]


class _Trunk:
    def __init__(self, center, factors, z):
        self.p, self.f, self.z = center, factors, z

    def sub(self, name):
        return _Trunk(self.p[name], self.f[name], self.z)

    def mm(self, name, x, precision=None):
        w, f = self.p[name], self.f[name]
        z = self.z.astype(x.dtype)
        a, b = f.a.astype(x.dtype), f.b.astype(x.dtype)
        trunk = jnp.matmul(x, w.T.astype(x.dtype), precision=precision)
        thin = jnp.matmul(x, a, precision=precision) * z
        return trunk + jnp.matmul(thin, b.T, precision=precision)

    def head_mm(self, name, x, heads, rows, *, into):
        f = self.f[name]
        w = _head_block(self.p[name], heads, rows, x.dtype)
        a, b = f.a.astype(x.dtype), _head_block(f.b, heads, rows, x.dtype)
        z = self.z.astype(x.dtype)[:, None, :]
        if into:  # x @ W[rows] = x @ W_c[rows] + ((x @ B[rows]) * z) @ A^T
            thin = jnp.einsum("nhr,hrk->nhk", x, b) * z
            return jnp.einsum("nhr,hri->nhi", x, w) + jnp.einsum("nhk,ik->nhi", thin, a)
        thin = jnp.einsum("nhi,ik->nhk", x, a) * z
        return jnp.einsum("nhi,hri->nhr", x, w) + jnp.einsum("nhk,hrk->nhr", thin, b)

    def vec(self, name):
        return self.p[name] + self.z @ self.f[name].b.T

    def mat(self, name):
        f = self.f[name]
        return self.p[name] + jnp.einsum("ok,nk,ik->noi", f.b, self.z, f.a)

    def rows(self, name, ids):
        f = self.f[name]
        return self.p[name][ids] + (f.b[ids] * self.z) @ f.a.T


def _lane_form(apply_lanes, params, x, state):
    """The one-lane ``apply`` of a module from its all-lanes equations."""
    batched = None if state is None else jax.tree_util.tree_map(lambda s: s[None], state)
    y, new_state = apply_lanes(_Dense(params), x[None], batched)
    if new_state is not None:
        new_state = jax.tree_util.tree_map(lambda s: s[0], new_state)
    return y[0], new_state


class _LaneModule(Module):
    """A module written once, over a leading lane axis, against an accessor."""

    def _forward(self, acc, x, state):
        raise NotImplementedError

    def apply(self, params, x, state=None):
        if state is None:
            state = self.initial_state()
        return _lane_form(self._forward, params, x, state)

    def trunk_delta_apply(self, center, factors, z, x, state):
        """All lanes at once in the shared-trunk form: ``center`` the trunk's
        parameter tree, ``factors`` the matching tree of ``_Factor`` nodes,
        ``z`` the ``(n, k)`` coefficients, ``x`` and ``state`` with a leading
        lane axis."""
        if state is None and self.is_stateful:
            state = _fresh_lanes(self.initial_state(), x.shape[0])
        return self._forward(_Trunk(center, factors, z), x, state)


def _fresh_lanes(proto, n):
    return jax.tree_util.tree_map(lambda s: jnp.broadcast_to(s, (n,) + s.shape), proto)


class Embedding(_LaneModule):
    """Rows of a ``(rows, dim)`` table, times ``scale``. The input is a token
    id, as a scalar or as the ``(1,)`` observation of a token environment."""

    def __init__(self, rows: int, dim: int, *, scale: float = 1.0):
        self.rows, self.dim, self.scale = int(rows), int(dim), float(scale)

    def init(self, key):
        return {"weight": _normal(key, (self.rows, self.dim))}

    def _forward(self, acc, x, state):
        ids = x.astype(jnp.int32).reshape(x.shape[0])
        return acc.rows("weight", ids) * self.scale, state


class RMSNorm(_LaneModule):
    def __init__(self, dim: int, *, eps: float = 1e-5):
        self.dim, self.eps = int(dim), float(eps)

    def init(self, key):
        return {"weight": jnp.ones((self.dim,), F32)}

    def _forward(self, acc, x, state):
        return rms_norm(x, acc.vec("weight"), self.eps), state


class SwiGLU(_LaneModule):
    """``W_down(silu(W_gate x) * W_up x)``."""

    def __init__(self, dim: int, width: int):
        self.dim, self.width = int(dim), int(width)

    def init(self, key):
        kg, ku, kd = jax.random.split(key, 3)
        return {
            "gate": _normal(kg, (self.width, self.dim)),
            "up": _normal(ku, (self.width, self.dim)),
            "down": _normal(kd, (self.dim, self.width)),
        }

    def _forward(self, acc, x, state):
        h = jax.nn.silu(acc.mm("gate", x)) * acc.mm("up", x)
        return acc.mm("down", h), state


class GatedAttention(_LaneModule):
    """``x + RMSNorm(W_o((softmax(q.k / sqrt(d)) . v) * sigmoid(W_g xn)))``
    with ``xn = RMSNorm(x)``, per-head RMSNorm of ``q`` and ``k``, RoPE where
    ``rope_theta`` is given (the sliding layers), grouped-query heads, and the
    cache of the module docstring as its state. What a family lacks is left
    out: the output gate (``gate``), the per-head norms (``qk_norm``), the
    closing norm (``post_norm``); ``score_scale`` takes the place of ``1 /
    sqrt(d)`` and ``residual_scale`` multiplies what joins the residual."""

    block_key = "attn"  # a layer's first block in parameters and state

    def __init__(
        self,
        dim: int,
        num_heads: int,
        num_kv_heads: int,
        head_dim: int,
        *,
        slots: int,
        rope_theta: Optional[float],
        eps: float = 1e-5,
        gate: bool = True,
        qk_norm: bool = True,
        post_norm: bool = True,
        score_scale: Optional[float] = None,
        residual_scale: float = 1.0,
    ):
        self.dim, self.heads, self.kv_heads = int(dim), int(num_heads), int(num_kv_heads)
        self.head_dim, self.slots, self.eps = int(head_dim), int(slots), float(eps)
        self.rope_theta = None if rope_theta is None else float(rope_theta)
        self.gate, self.qk_norm, self.post_norm = bool(gate), bool(qk_norm), bool(post_norm)
        self.score_scale = None if score_scale is None else float(score_scale)
        self.residual_scale = float(residual_scale)
        if self.heads % self.kv_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")

    def init(self, key):
        kq, kk, kv, kg, ko = jax.random.split(key, 5)
        wide, narrow = self.heads * self.head_dim, self.kv_heads * self.head_dim
        params = {
            "in_norm": jnp.ones((self.dim,), F32),
            "q": _normal(kq, (wide, self.dim)),
            "k": _normal(kk, (narrow, self.dim)),
            "v": _normal(kv, (narrow, self.dim)),
            "o": _normal(ko, (self.dim, wide)),
        }
        if self.gate:
            params["g"] = _normal(kg, (wide, self.dim))
        if self.qk_norm:
            params["q_norm"] = jnp.ones((self.head_dim,), F32)
            params["k_norm"] = jnp.ones((self.head_dim,), F32)
        if self.post_norm:
            params["post_norm"] = jnp.ones((self.dim,), F32)
        return params

    def initial_state(self):
        cache = jnp.zeros((self.kv_heads, self.slots, self.head_dim), F32)
        zero = jnp.zeros((), jnp.int32)
        return {"k": cache, "v": cache, "t": zero, "step": zero}

    def reset_state(self, state, mask):
        """Zero ``t`` and the cache rows of the lanes in ``mask``, one lane
        at a time (a select over the whole cache would read and write all of
        it in every control step; an episode's end is rare). ``step`` stays."""
        n = mask.shape[0]
        ended = jnp.nonzero(mask, size=n, fill_value=0)[0]
        blank = jnp.zeros((1,) + state["k"].shape[1:], state["k"].dtype)

        def zero_lane(i, caches):
            at = (ended[i], 0, 0, 0)
            return tuple(jax.lax.dynamic_update_slice(c, blank, at) for c in caches)

        k, v = jax.lax.fori_loop(
            0, jnp.sum(mask.astype(jnp.int32)), zero_lane, (state["k"], state["v"])
        )
        return {"k": k, "v": v, "t": jnp.where(mask, 0, state["t"]), "step": state["step"]}

    def _forward(self, acc, x, state):
        with scope("fwd_attention"):
            return self._attended(acc, x, rms_norm(x, acc.vec("in_norm"), self.eps), state)

    def attend(self, acc, x, xn, state):
        """The block on ``xn``, ``x`` already normed by the block's
        ``in_norm``: the entry of a layer that reads the same norm before the
        block (``RouteAheadLayer``)."""
        with scope("fwd_attention"):
            return self._attended(acc, x, xn, state)

    def _attended(self, acc, x, xn, state):
        n, kv, hd, slots = x.shape[0], self.kv_heads, self.head_dim, self.slots
        group = self.heads // kv
        q = acc.mm("q", xn).reshape(n, kv, group, hd)
        k = acc.mm("k", xn).reshape(n, kv, hd)
        v = acc.mm("v", xn).reshape(n, kv, hd)
        if self.gate:
            gate = acc.mm("g", xn)
        if self.qk_norm:
            q = rms_norm(q, acc.vec("q_norm")[:, None, None, :], self.eps)
            k = rms_norm(k, acc.vec("k_norm")[:, None, :], self.eps)
        t = state["t"]
        if self.rope_theta is not None:
            q = rope(q, t[:, None, None], self.rope_theta)
            k = rope(k, t[:, None], self.rope_theta)
        cache_dtype = state["k"].dtype
        slot = jnp.mod(state["step"][0], slots)
        at = (0, 0, slot, 0)
        kc = jax.lax.dynamic_update_slice(state["k"], k[:, :, None, :].astype(cache_dtype), at)
        vc = jax.lax.dynamic_update_slice(state["v"], v[:, :, None, :].astype(cache_dtype), at)
        age = jnp.mod(slot - jnp.arange(slots, dtype=jnp.int32), slots)
        readable = age[None, :] <= t[:, None]  # (n, slots)
        scores = jnp.einsum("nkgd,nksd->nkgs", q.astype(cache_dtype), kc, preferred_element_type=F32)
        scores = scores / math.sqrt(hd) if self.score_scale is None else scores * self.score_scale
        scores = jnp.where(readable[:, None, None, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(cache_dtype)
        mixed = jnp.einsum("nkgs,nksd->nkgd", weights, vc, preferred_element_type=F32)
        mixed = mixed.reshape(n, self.heads * hd)
        if self.gate:
            mixed = mixed * jax.nn.sigmoid(gate.astype(F32))
        y = _joined(self, x, _closed(self, acc, acc.mm("o", mixed.astype(x.dtype))))
        return y, {"k": kc, "v": vc, "t": t + 1, "step": state["step"] + 1}


class LatentAttention(_LaneModule):
    """``x + W_o [a_1 .. a_H]`` with latent attention (MLA) over ``xn =
    RMSNorm(x)``: ``c_q = RMSNorm(W_qa xn)``, ``[q_n | q_r]_h = (W_qb c_q)_h``;
    ``[c | k_r] = W_kva xn``, ``c = RMSNorm(c)``, ``k_r`` ONE key for all
    heads; RoPE on ``q_r`` and ``k_r``; ``[k_n | v]_{h,s} = (W_kvb c_s)_h``;
    ``score_{h,s} = (q_n . k_n + q_r . k_r) / sqrt(qk_nope + qk_rope)``;
    softmax in float32; ``a_h = sum_s p_{h,s} v_{h,s}``. No bias, no gate, no
    norm after the block. Computed in the absorbed form over the latent
    cache of the module docstring, which is its state. ``q_rank=None``: the
    query straight from ``xn`` (``[q_n | q_r]_h = (W_q xn)_h``, no LoRA and
    no norm); ``rotary=False``: ``q_r`` and ``k_r`` are kept, cached and
    scored unrotated."""

    block_key = "attn"

    def __init__(
        self,
        dim: int,
        num_heads: int,
        *,
        q_rank: Optional[int],
        kv_rank: int,
        nope_dim: int,
        rope_dim: int,
        v_dim: int,
        slots: int,
        rope_theta: float,
        eps: float = 1e-5,
        rotary: bool = True,
    ):
        self.dim, self.heads = int(dim), int(num_heads)
        self.q_rank, self.kv_rank = None if q_rank is None else int(q_rank), int(kv_rank)
        self.nope, self.rope, self.v = int(nope_dim), int(rope_dim), int(v_dim)
        self.slots, self.rope_theta, self.eps = int(slots), float(rope_theta), float(eps)
        self.rotary = bool(rotary)

    def init(self, key):
        kqa, kqb, kva, kvb, ko = jax.random.split(key, 5)
        wide = self.heads * (self.nope + self.rope)
        if self.q_rank is None:  # the query straight from xn
            query = {"q": _normal(kqa, (wide, self.dim))}
        else:
            query = {
                "q_a": _normal(kqa, (self.q_rank, self.dim)),
                "q_a_norm": jnp.ones((self.q_rank,), F32),
                "q_b": _normal(kqb, (wide, self.q_rank)),
            }
        return {
            "in_norm": jnp.ones((self.dim,), F32),
            **query,
            "kv_a": _normal(kva, (self.kv_rank + self.rope, self.dim)),
            "kv_a_norm": jnp.ones((self.kv_rank,), F32),
            "kv_b": _normal(kvb, (self.heads * (self.nope + self.v), self.kv_rank)),
            "o": _normal(ko, (self.dim, self.heads * self.v)),
        }

    def initial_state(self):
        """The latent cache, the lane's position, the write pointer, and two
        counters that never reset: the positions the lane could read, summed
        over its steps (``min(t + 1, slots)`` a step), and the positions in
        the blocks the cache pass's kernel fetched for it (none where the
        plain form runs)."""
        zero = jnp.zeros((), jnp.int32)
        return {
            "c": jnp.zeros((self.slots, self.kv_rank), F32),
            "kr": jnp.zeros((self.slots, self.rope), F32),
            "t": zero,
            "step": zero,
            "read": zero,
            "fetched": zero,
        }

    def reset_state(self, state, mask):
        """As ``GatedAttention.reset_state``: ``t`` and the cache rows of the
        lanes in ``mask``, one lane at a time; ``step`` and ``read`` stay."""
        n = mask.shape[0]
        ended = jnp.nonzero(mask, size=n, fill_value=0)[0]
        blanks = tuple(jnp.zeros((1,) + state[name].shape[1:], state[name].dtype) for name in ("c", "kr"))

        def zero_lane(i, caches):
            at = (ended[i], 0, 0)
            return tuple(jax.lax.dynamic_update_slice(c, blank, at) for c, blank in zip(caches, blanks))

        c, kr = jax.lax.fori_loop(
            0, jnp.sum(mask.astype(jnp.int32)), zero_lane, (state["c"], state["kr"])
        )
        return {**state, "c": c, "kr": kr, "t": jnp.where(mask, 0, state["t"])}

    def _cache_plain(self, q_lat, q_r, cc, kc, t, slot, *, out_dtype=F32):
        """The pass over the written caches in XLA's own operations: all
        heads' scores over every slot, the slots older than the lane's ``t``
        masked, softmax in float32, the weighted sum over ``c`` accumulated
        in float32. The statement of the equations, and what runs off the
        TPU and at sizes the kernel does not take. No block is fetched."""
        age = jnp.mod(slot - jnp.arange(self.slots, dtype=jnp.int32), self.slots)
        readable = age[None, :] <= t[:, None]  # (n, slots)
        scores = jnp.einsum("nhr,nsr->nhs", q_lat, cc, preferred_element_type=F32) + jnp.einsum(
            "nhd,nsd->nhs", q_r, kc, preferred_element_type=F32
        )
        scores = scores / math.sqrt(self.nope + self.rope)
        scores = jnp.where(readable[:, None, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(cc.dtype)
        o_lat = jnp.einsum("nhs,nsr->nhr", weights, cc, preferred_element_type=F32)
        return o_lat.astype(out_dtype), jnp.zeros(t.shape, jnp.int32)

    def _cache_pass(self, q_lat, q_r, cc, kc, t, slot, out_dtype):
        """``o = softmax((q_lat c^T + q_r kr^T) / sqrt(d)) c`` over each
        lane's readable slots, rounded once to ``out_dtype``, and the
        positions fetched for it. One kernel that reads a lane's filled blocks
        once (``net/latent.py``) where the program is lowered for a TPU and
        the sizes are the kernel's; the plain form everywhere else."""
        plain = functools.partial(self._cache_plain, out_dtype=out_dtype)
        if not latent.fits(q_lat.shape[0], self.kv_rank, self.slots, cc.dtype):
            return plain(q_lat, q_r, cc, kc, t, slot)
        kernel = functools.partial(
            latent.attend, scale=1.0 / math.sqrt(self.nope + self.rope), out_dtype=out_dtype
        )
        return _by_platform(kernel, plain, q_lat, q_r, cc, kc, t, slot)

    def _forward(self, acc, x, state):
        n, heads, slots = x.shape[0], self.heads, self.slots
        nope, v_rows = slice(0, self.nope), slice(self.nope, self.nope + self.v)
        with scope("fwd_attention"):
            xn = rms_norm(x, acc.vec("in_norm"), self.eps)
            if self.q_rank is None:
                q = acc.mm("q", xn).reshape(n, heads, self.nope + self.rope)
            else:
                cq = rms_norm(acc.mm("q_a", xn), acc.vec("q_a_norm"), self.eps)
                q = acc.mm("q_b", cq).reshape(n, heads, self.nope + self.rope)
            kv = acc.mm("kv_a", xn)
            c = rms_norm(kv[:, : self.kv_rank], acc.vec("kv_a_norm"), self.eps)
            t = state["t"]
            turned = functools.partial(rope, theta=self.rope_theta) if self.rotary else lambda x, positions: x
            q_r = turned(q[..., self.nope :], t[:, None])
            k_r = turned(kv[:, self.kv_rank :], t)
            q_lat = acc.head_mm("kv_b", q[..., nope], heads, nope, into=True)  # q_n W_UK[h]
            with scope("fwd_latent_cache"):
                cache_dtype = state["c"].dtype
                slot = jnp.mod(state["step"][0], slots)
                at = (0, slot, 0)
                cc = jax.lax.dynamic_update_slice(state["c"], c[:, None, :].astype(cache_dtype), at)
                kc = jax.lax.dynamic_update_slice(state["kr"], k_r[:, None, :].astype(cache_dtype), at)
                o_lat, fetched = self._cache_pass(
                    q_lat.astype(cache_dtype), q_r.astype(cache_dtype), cc, kc, t, slot, x.dtype
                )
            mixed = acc.head_mm("kv_b", o_lat, heads, v_rows, into=False)  # W_UV[h] o~
            y = x + acc.mm("o", mixed.reshape(n, heads * self.v))
        return y, {
            "c": cc,
            "kr": kc,
            "t": t + 1,
            "step": state["step"] + 1,
            "read": state["read"] + jnp.minimum(t + 1, slots),
            "fetched": state["fetched"] + fetched,
        }


def _end_lanes(state, mask, matrix):
    """A recurrent block's ``reset_state``: the window ``conv`` and the
    matrix state ``state[matrix]`` of the lanes in ``mask`` zeroed one lane
    at a time, what the matrix state held kept first, summed over its first
    axis after the lane's (``ended``), and the lanes' resets counted."""
    conv, held, kept = state["conv"], state[matrix], state["ended"]
    ended = jnp.nonzero(mask, size=mask.shape[0], fill_value=0)[0]
    no_window = jnp.zeros((1,) + conv.shape[1:], conv.dtype)
    no_state = jnp.zeros((1,) + held.shape[1:], held.dtype)
    at = lambda lane, array: (lane,) + (0,) * (array.ndim - 1)

    def end_lane(i, carried):
        conv, held, kept = carried
        lane = ended[i]
        was = jax.lax.dynamic_slice(held, at(lane, held), no_state.shape)
        summed = jnp.sum(was.astype(F32), axis=1).astype(kept.dtype).reshape((1,) + kept.shape[1:])
        return (
            jax.lax.dynamic_update_slice(conv, no_window, at(lane, conv)),
            jax.lax.dynamic_update_slice(held, no_state, at(lane, held)),
            jax.lax.dynamic_update_slice(kept, summed, at(lane, kept)),
        )

    conv, held, kept = jax.lax.fori_loop(0, jnp.sum(mask.astype(jnp.int32)), end_lane, (conv, held, kept))
    return {**state, "conv": conv, matrix: held, "ended": kept, "resets": state["resets"] + mask.astype(jnp.int32)}


class Mamba2Mixer(_LaneModule):
    """``x + r W_out(RMSNorm(y * silu(z)))`` with the Mamba-2 recurrence over
    ``xn = RMSNorm(x)``: ``[z | xBC | dt] = W_in xn`` (``inner | inner + 2
    state_dim | heads``, ``inner = heads x head_dim``); ``xBC = silu(sum_k
    w[k] xBC_{t-width+1+k} + b)``, a depthwise causal convolution over the
    lane's window of its last inputs; ``[x | B | C] = xBC``; ``dt =
    softplus(dt + dt_bias)``, ``a = exp(-dt exp(A_log))`` a head; ``S_t = a
    S_{t-1} + dt x_t (outer) B_t`` a head, ``(head_dim, state_dim)``; ``y =
    S_t C_t + D x_t``; the gated norm over all of ``inner``. One group of
    ``B`` and ``C`` for all heads; no bias but the convolution's; no clamp on
    ``dt``. ``A_log``, ``dt_bias`` and ``D`` are 1-D leaves, so every lane's
    transition is its own, and the convolution's ``(width, channels)`` leaf
    (taps first: a leaf whose last axis is 4 would pad 32-fold in the TPU's
    tiles, and the compiler reshapes the whole flat trunk around it) is
    written out per lane and used elementwise (``mat``).

    **The state** is what the lane carries from step to step, and unlike a
    cache ALL of it is rewritten every step: the window ``(width - 1,
    channels)`` of the convolution's last inputs and the matrix state,
    stored TURNED, ``(state_dim, heads x head_dim)`` (``S[h, p, s]`` lies at
    ``[s, h head_dim + p]``: (head, row) runs along a register's lanes, so
    the readout adds registers and the decay is a row vector), in the compute
    dtype and updated in float32 (decay, outer product and readout are one
    pass over it, under ``fwd_ssm_state``: ``_state_pass``). A lane that
    starts an episode has both zero. Kept beyond an episode: what the matrix
    state held when the lane last ENDED an episode, summed over ``state_dim``
    (``ended``: ``sum_s S[h, p, s]``, ``(heads, head_dim)``, written by
    ``reset_state`` before it zeroes the lane), the steps the lane's state
    was rewritten, those of them the kernel rewrote, and the times it was
    zeroed."""

    block_key = "ssm"

    def __init__(
        self,
        dim: int,
        num_heads: int,
        head_dim: int,
        state_dim: int,
        *,
        conv_width: int = 4,
        eps: float = 1e-5,
        residual_scale: float = 1.0,
    ):
        self.dim, self.heads, self.head_dim = int(dim), int(num_heads), int(head_dim)
        self.state_dim, self.width, self.eps = int(state_dim), int(conv_width), float(eps)
        self.inner = self.heads * self.head_dim
        self.channels = self.inner + 2 * self.state_dim  # what the convolution runs over: x, B, C
        self.residual_scale = float(residual_scale)

    def init(self, key):
        """Matrices as the family draws them; the convolution and the
        transition as ``mamba_ssm``'s Mamba-2 does: taps and bias uniform
        within ``1 / sqrt(width)`` (``nn.Conv1d``), ``A`` uniform in [1, 16],
        ``dt`` log-uniform in [1e-3, 1e-1] with ``dt_bias`` its inverse
        softplus, ``D`` ones."""
        ki, ko, kw, kb, ka, kd = jax.random.split(key, 6)
        bound = 1.0 / math.sqrt(self.width)
        dt = jnp.exp(jax.random.uniform(kd, (self.heads,), F32, math.log(1e-3), math.log(1e-1)))
        return {
            "in_norm": jnp.ones((self.dim,), F32),
            "in_proj": _normal(ki, (2 * self.inner + 2 * self.state_dim + self.heads, self.dim)),
            "conv": jax.random.uniform(kw, (self.width, self.channels), F32, -bound, bound),
            "conv_bias": jax.random.uniform(kb, (self.channels,), F32, -bound, bound),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(ka, (self.heads,), F32, 1.0, 16.0)),
            "D": jnp.ones((self.heads,), F32),
            "norm": jnp.ones((self.inner,), F32),
            "out_proj": _normal(ko, (self.dim, self.inner)),
        }

    def initial_state(self):
        zero = jnp.zeros((), jnp.int32)
        return {
            "conv": jnp.zeros((self.width - 1, self.channels), F32),
            "ssm": jnp.zeros((self.state_dim, self.inner), F32),  # turned: (head, row) runs along a register's lanes
            "ended": jnp.zeros((self.heads, self.head_dim), F32),
            "updates": zero,
            "kernel_updates": zero,
            "resets": zero,
        }

    def reset_state(self, state, mask):
        """As ``GatedAttention.reset_state``: the window and the matrix state
        of the lanes in ``mask`` are zeroed one lane at a time (a select over
        a whole state would read and write all of it in every control step;
        an episode's end is rare). What the lane's matrix state held is kept
        first, summed over ``state_dim`` (``ended``)."""
        return _end_lanes(state, mask, "ssm")

    @staticmethod
    def _state_plain(ssm, decay, fed, b, c):
        """The pass over the matrix states in XLA's own operations: ``S_t = a
        S_{t-1} + (dt x_t) (outer) B_t``, ``y = S_t C_t``. ``ssm`` ``(n,
        state_dim, inner)`` as stored; ``decay`` (``a``) ``(n, heads)``,
        ``fed`` (``dt x_t``) ``(n, inner)``, ``b`` and ``c`` ``(n,
        state_dim)``, all float32. The stored state converted to
        float32, one multiply-add an entry, the readout of the UNROUNDED
        state, one rounding to the stored dtype. The statement of the
        equations, and what runs off the TPU and at sizes the kernel does not
        take. Returns the new state, the float32 readout ``(n, inner)`` and,
        per lane, the states a kernel rewrote: none."""
        decay = jnp.repeat(decay, fed.shape[1] // decay.shape[1], axis=1)  # a head's, for each of its rows
        new = ssm.astype(F32) * decay[:, None, :] + b[:, :, None] * fed[:, None, :]
        y = jnp.sum(new * c[:, :, None], axis=1)
        return new.astype(ssm.dtype), y, jnp.zeros(ssm.shape[:1], jnp.int32)

    def _state_pass(self, ssm, decay, fed, b, c):
        """``_state_plain``'s results: one kernel that brings a lane's state
        through VMEM once and rewrites it in place (``net/ssmstate.py``) where
        the program is lowered for a TPU and the sizes are the kernel's; the
        plain form everywhere else."""
        if not ssmstate.fits(ssm.shape[0], self.heads, self.head_dim, self.state_dim, ssm.dtype):
            return self._state_plain(ssm, decay, fed, b, c)
        return _by_platform(ssmstate.state_pass, self._state_plain, ssm, decay, fed, b, c)

    def _forward(self, acc, x, state):
        n, heads, inner = x.shape[0], self.heads, self.inner
        with scope("fwd_ssm"):
            xn = rms_norm(x, acc.vec("in_norm"), self.eps)
            gate, xbc, dt = jnp.split(acc.mm("in_proj", xn), [inner, inner + self.channels], axis=-1)
            held = state["conv"]
            taps = jnp.concatenate([held, xbc[:, None, :].astype(held.dtype)], axis=1)  # oldest first
            weights = acc.mat("conv").astype(F32)  # (lanes or 1, width, channels)
            xbc = jnp.sum(taps.astype(F32) * weights, axis=1) + acc.vec("conv_bias").astype(F32)
            xs, b, c = jnp.split(jax.nn.silu(xbc), [inner, inner + self.state_dim], axis=-1)
            xs = xs.reshape(n, heads, self.head_dim)
            dt = jax.nn.softplus(dt.astype(F32) + acc.vec("dt_bias").astype(F32))  # (n, heads)
            rate = -jnp.exp(acc.vec("A_log").astype(F32))
            with scope("fwd_ssm_state"):
                fed = (dt[:, :, None] * xs).reshape(n, inner)
                ssm, y, rewrote = self._state_pass(state["ssm"], jnp.exp(dt * rate), fed, b, c)
            y = y.reshape(xs.shape) + acc.vec("D").astype(F32)[:, :, None] * xs
            y = y.reshape(n, inner) * jax.nn.silu(gate.astype(F32))
            y = rms_norm(y, acc.vec("norm"), self.eps).astype(x.dtype)
            y = _joined(self, x, acc.mm("out_proj", y))
        return y, {
            "conv": taps[:, 1:],
            "ssm": ssm,
            "ended": state["ended"],
            "updates": state["updates"] + 1,
            "kernel_updates": state["kernel_updates"] + rewrote,
            "resets": state["resets"],
        }


def l2_normalized(x, eps=1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class KimiDeltaAttention(_LaneModule):
    """``x + W_o[RMSNorm(o) * sigmoid(W_gb W_ga xn)]`` with the gated delta
    rule (KDA) over ``xn = RMSNorm(x)``: ``q, k, v = silu(conv(W_q xn)),
    silu(conv(W_k xn)), silu(conv(W_v xn))``, each a depthwise causal
    convolution of ``width`` taps, no bias, over the lane's window of its last
    inputs; per head ``q`` and ``k`` L2-normalised, ``q`` times ``head_dim ^
    -1/2``; ``log a = -exp(A_log[h]) softplus(W_fb W_fa xn + dt_bias)``, ONE
    DECAY A KEY CHANNEL of each head; ``beta = sigmoid(W_b xn)`` a head; the
    gates' low ranks are ``head_dim``; a head's state ``S`` ``(head_dim key,
    head_dim value)``: ``S' = Diag(a) S_{t-1}``, ``S_t = S' + beta k (v -
    S'^T k)^T`` (``(I - beta k k^T) Diag(a) S_{t-1} + beta k v^T``), ``o =
    S_t^T q``; the output norm is over a head's ``head_dim`` with one weight
    for all heads. ``A_log`` (a head)
    and ``dt_bias`` (a key channel of a head) are 1-D leaves, so every lane's
    decay is its own; the three convolutions' leaves are held taps first, as
    ``Mamba2Mixer``'s, and written out per lane (``mat``).

    **The state** is what the lane carries: the window ``(width - 1, 3 x
    heads x head_dim)`` of the convolutions' last inputs (``q | k | v``) and
    the matrix state, stored TURNED, ``(head_dim key, heads, head_dim
    value)`` (``S[h, k, v]`` lies at ``[k, h, v]``: in row-major order the
    bytes of ``Mamba2Mixer``'s ``(state_dim, heads x head_dim)``, with the
    head split from the value so that a (head, key) factor, the decay, ``k``
    or ``q``, broadcasts along the value axis alone: over a merged ``heads x
    value`` axis XLA writes each such factor out at the state's size in
    float32, 1 GB a factor at 512 lanes), in the compute dtype and updated in
    float32. All of it is rewritten every step; the decay, ``S'^T k``, the
    rank-1 update, the readout and the write-back are one span
    (``fwd_kda_state``: ``_state_pass``, one in-place kernel a layer on the
    TPU, ``_state_plain`` elsewhere). A lane that starts an episode has both
    zero. Kept beyond an episode, as the mixer keeps them: what the matrix
    state held when the lane last ENDED an episode, summed over the key axis
    (``ended``, ``(heads, head_dim)``), the steps the state was rewritten,
    those of them the kernel rewrote, and the times it was zeroed."""

    block_key = "kda"

    def __init__(
        self,
        dim: int,
        num_heads: int,
        head_dim: int,
        *,
        conv_width: int = 4,
        eps: float = 1e-5,
    ):
        self.dim, self.heads, self.head_dim = int(dim), int(num_heads), int(head_dim)
        self.width, self.eps = int(conv_width), float(eps)
        self.inner = self.heads * self.head_dim

    def init(self, key):
        """Matrices as the family draws them; the convolutions' taps uniform
        within ``1 / sqrt(width)`` (``nn.Conv1d``), ``A`` uniform in [1, 16],
        ``dt`` log-uniform in [1e-3, 1e-1] with ``dt_bias`` its inverse
        softplus (Mamba-2's initial values)."""
        keys = jax.random.split(key, 12)
        bound = 1.0 / math.sqrt(self.width)
        dt = jnp.exp(jax.random.uniform(keys[11], (self.inner,), F32, math.log(1e-3), math.log(1e-1)))
        params = {
            "in_norm": jnp.ones((self.dim,), F32),
            "A_log": jnp.log(jax.random.uniform(keys[10], (self.heads,), F32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "b": _normal(keys[0], (self.heads, self.dim)),
            "f_a": _normal(keys[1], (self.head_dim, self.dim)),
            "f_b": _normal(keys[2], (self.inner, self.head_dim)),
            "g_a": _normal(keys[3], (self.head_dim, self.dim)),
            "g_b": _normal(keys[4], (self.inner, self.head_dim)),
            "o_norm": jnp.ones((self.head_dim,), F32),
            "o": _normal(keys[5], (self.dim, self.inner)),
        }
        for at, name in enumerate("qkv"):
            params[name] = _normal(keys[6 + at], (self.inner, self.dim))
            params[name + "_conv"] = jax.random.uniform(
                jax.random.fold_in(keys[9], at), (self.width, self.inner), F32, -bound, bound
            )
        return params

    def initial_state(self):
        zero = jnp.zeros((), jnp.int32)
        return {
            "conv": jnp.zeros((self.width - 1, 3 * self.inner), F32),
            "state": jnp.zeros((self.head_dim, self.heads, self.head_dim), F32),  # turned: (key, head, value)
            "ended": jnp.zeros((self.heads, self.head_dim), F32),
            "updates": zero,
            "kernel_updates": zero,
            "resets": zero,
        }

    def reset_state(self, state, mask):
        """As ``Mamba2Mixer.reset_state``: the window and the matrix state of
        the lanes in ``mask``, one lane at a time, what the matrix state held
        kept first, summed over the key axis (``ended``)."""
        return _end_lanes(state, mask, "state")

    @staticmethod
    def _state_plain(held, decay, k, v, beta, q):
        """The pass over the matrix states in XLA's own operations: ``S' =
        Diag(a) S``, ``S_t = S' + beta k (v - S'^T k)^T``, ``o = S_t^T q``.
        ``held`` ``(n, key, heads, value)`` as stored; ``decay`` (``a``),
        ``k`` and ``q`` ``(n, heads, key)``, ``v`` ``(n, heads, value)``,
        ``beta`` ``(n, heads)``, all float32. The stored state converted to
        float32, both products over the key axis as multiplies and sums (no
        matrix unit, no pass of lower precision), the readout of the
        UNROUNDED state, one rounding to the stored dtype: the statement of
        the equations, and what runs off the TPU and at sizes the kernel does
        not take. Returns the new state and the float32 readout ``(n, heads,
        value)``."""
        turned = lambda a: jnp.swapaxes(a, 1, 2)[..., None]  # (n, heads, key) -> (n, key, heads, 1)
        s = held.astype(F32) * turned(decay)
        read = jnp.sum(s * turned(k), axis=1)  # S'^T k: (n, heads, value)
        s = s + turned(k) * (beta[:, :, None] * (v - read))[:, None]
        out = jnp.sum(s * turned(q), axis=1)
        return s.astype(held.dtype), out

    def _state_pass(self, held, decay, k, v, beta, q):
        """``_state_plain``'s results and, per lane, the states a kernel
        rewrote: one kernel that brings a lane's state through VMEM once and
        rewrites it in place (``net/kdastate.py``) where the program is
        lowered for a TPU and the sizes are the kernel's; the plain form
        (none rewritten by a kernel) everywhere else."""
        plain = lambda *args: (*self._state_plain(*args), jnp.zeros(held.shape[:1], jnp.int32))
        if not kdastate.fits(held.shape[0], self.heads, self.head_dim, held.dtype):
            return plain(held, decay, k, v, beta, q)
        return _by_platform(kdastate.state_pass, plain, held, decay, k, v, beta, q)

    def _forward(self, acc, x, state):
        n, heads, hd, inner = x.shape[0], self.heads, self.head_dim, self.inner
        with scope("fwd_kda"):
            xn = rms_norm(x, acc.vec("in_norm"), self.eps)
            fresh = jnp.concatenate([acc.mm(name, xn) for name in "qkv"], axis=-1)
            window = state["conv"]
            taps = jnp.concatenate([window, fresh[:, None, :].astype(window.dtype)], axis=1)  # oldest first
            weights = jnp.concatenate([acc.mat(name + "_conv") for name in "qkv"], axis=-1).astype(F32)
            q, k, v = jnp.split(jax.nn.silu(jnp.sum(taps.astype(F32) * weights, axis=1)), 3, axis=-1)
            q = l2_normalized(q.reshape(n, heads, hd)) * hd**-0.5
            k = l2_normalized(k.reshape(n, heads, hd))
            v = v.reshape(n, heads, hd)
            fed = acc.mm("f_b", acc.mm("f_a", xn)).astype(F32) + acc.vec("dt_bias").astype(F32)
            rate = -jnp.exp(acc.vec("A_log").astype(F32))[:, :, None]  # (lanes or 1, heads, 1)
            beta = jax.nn.sigmoid(acc.mm("b", xn).astype(F32))
            with scope("fwd_kda_state"):
                decay = jnp.exp(rate * jax.nn.softplus(fed).reshape(n, heads, hd))
                held, o, rewrote = self._state_pass(state["state"], decay, k, v, beta, q)
            o = rms_norm(o, acc.vec("o_norm")[:, None, :], self.eps)
            gate = acc.mm("g_b", acc.mm("g_a", xn)).astype(F32).reshape(n, heads, hd)
            o = (o * jax.nn.sigmoid(gate)).reshape(n, inner).astype(x.dtype)
            y = x + acc.mm("o", o)
        return y, {
            "conv": taps[:, 1:],
            "state": held,
            "ended": state["ended"],
            "updates": state["updates"] + 1,
            "kernel_updates": state["kernel_updates"] + rewrote,
            "resets": state["resets"],
        }


class SparseExperts(_LaneModule):
    """``RMSNorm``-wrapped routed experts with a shared expert: ``x +
    RMSNorm(sum_e w_e E_e(y) + S(y))``, ``y = RMSNorm(x)`` (``x + sum_e w_e
    E_e(y) + S(y)`` without ``post_norm``, no ``S`` without shared experts);
    ``E_e(y) = W_down(act(W_gate y) * W_up y)`` with ``act`` the family's
    ``activation`` (``"silu"``: SwiGLU; ``"relu"``: ReGLU). Router logits,
    scores and top-k in float32. ``score_func="sigmoid"``: the scores are
    the logits' sigmoids, and the ``expert_bias`` leaf, which every sigmoid
    family has, selects and does not weigh; ``score_func="softmax"`` (no
    bias leaf): the scores are the softmax over all logits and the ``top_k``
    largest logits are chosen. The weights are the chosen scores, normalised
    over the chosen (``route_norm``; their sum plus ``route_norm_eps``: for
    the softmax, the softmax over the chosen logits alone), times
    ``route_scale``. ``experts_held``:
    the range of expert ids whose weights this module holds; the others'
    terms are left out. Held experts are stacked ``(held, in, out)``: the
    grouped product's right-hand side. ``routed`` is the entry of a layer
    that decides the routes itself (``RouteAheadLayer``)."""

    def __init__(
        self,
        dim: int,
        width: int,
        num_experts: int,
        experts_per_token: int,
        *,
        experts_held: Optional[range] = None,
        num_shared_experts: int = 1,
        route_scale: float = 1.0,
        route_norm: bool = True,
        score_func: str = "sigmoid",
        eps: float = 1e-5,
        post_norm: bool = True,
        route_norm_eps: float = 0.0,
        activation: str = "silu",
    ):
        if score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {score_func!r}: only the sigmoid and softmax routers are implemented")
        if activation not in grouped.ACTIVATIONS:
            raise ValueError(f"activation {activation!r}: one of {sorted(grouped.ACTIVATIONS)}")
        self.score_func, self.activation = score_func, activation
        self.dim, self.width = int(dim), int(width)
        self.num_experts, self.top_k = int(num_experts), int(experts_per_token)
        held = range(self.num_experts) if experts_held is None else experts_held
        if held.step != 1 or not 0 <= held.start < held.stop <= self.num_experts:
            raise ValueError(f"experts_held {held!r} is not a range of ids within {self.num_experts}")
        self.held = held
        self.shared = SwiGLU(dim, int(num_shared_experts) * width) if num_shared_experts else None
        self.route_scale, self.route_norm, self.eps = float(route_scale), bool(route_norm), float(eps)
        self.post_norm, self.route_norm_eps = bool(post_norm), float(route_norm_eps)

    def init(self, key):
        kr, kg, ku, kd, ks = jax.random.split(key, 5)
        e = len(self.held)
        params = {
            "in_norm": jnp.ones((self.dim,), F32),
            "router": _normal(kr, (self.num_experts, self.dim)),
            "experts": {
                "gate": _normal(kg, (e, self.dim, self.width)),
                "up": _normal(ku, (e, self.dim, self.width)),
                "down": _normal(kd, (e, self.width, self.dim)),
            },
        }
        if self.score_func == "sigmoid":
            params["expert_bias"] = jnp.zeros((self.num_experts,), F32)
        if self.post_norm:
            params["post_norm"] = jnp.ones((self.dim,), F32)
        if self.shared is not None:
            params["shared"] = self.shared.init(ks)
        return params

    def initial_state(self):
        """What the last step chose, and three counters that never reset:
        the lane's (lane, expert) pairs that hit a held expert, the pairs on
        the fullest held expert of each step, and the row tiles the grouped
        product's kernel visited (the last two one number for all lanes of a
        population; no tile where the plain form runs)."""
        zero = jnp.zeros((), jnp.int32)
        chosen = jnp.zeros((self.top_k,), jnp.int32)
        return {"chosen": chosen, "hits": zero, "fullest": zero, "tiles": zero}

    def reset_state(self, state, mask):
        return state  # nothing of an episode lives here

    def route(self, acc, y):
        """Expert ids ``(n, top_k)`` and their float32 weights."""
        logits = acc.mm("router", y.astype(F32), precision=jax.lax.Precision.HIGHEST)
        if self.score_func == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            _, chosen = jax.lax.top_k(logits, self.top_k)
        else:
            scores = jax.nn.sigmoid(logits)
            _, chosen = jax.lax.top_k(scores + acc.vec("expert_bias").astype(F32), self.top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.route_norm:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            if self.route_norm_eps:
                total = total + self.route_norm_eps
            weights = weights / total
        return chosen, weights * self.route_scale

    def _act(self, gate):
        return grouped.ACTIVATIONS[self.activation](gate)

    def _experts_dense(self, params, y, chosen, weights):
        """One lane's held experts, all computed and weighed by the router's
        weight (zero where not chosen): the dense form's plain sum."""
        ids = jnp.arange(self.held.start, self.held.stop)
        per_expert = jnp.sum(
            jnp.where(chosen[0][None, :] == ids[:, None], weights[0][None, :], 0.0), axis=-1
        )
        x = y[0]
        gate = jnp.einsum("i,eiw->ew", x, params["gate"].astype(x.dtype))
        up = jnp.einsum("i,eiw->ew", x, params["up"].astype(x.dtype))
        out = jnp.einsum("ew,ewo->eo", self._act(gate) * up, params["down"].astype(x.dtype))
        mixed = jnp.sum(out.astype(F32) * per_expert[:, None], axis=0)[None].astype(y.dtype)
        return mixed, (per_expert > 0).astype(jnp.int32)

    def _experts_plain(self, center, factors, z, y, local, weights):
        """The grouped product in XLA's own operations: the (lane, expert)
        pairs that hit a held expert, sorted by expert, against the stacked
        weights (``jax.lax.ragged_dot``), with room for every lane hitting
        ``min(top_k, held)`` experts; rows are gathered and summed back by
        one-hot matmuls. What runs off the TPU and at widths the kernel does
        not take."""
        n, held = y.shape[0], len(self.held)
        rows = n * min(self.top_k, held)
        key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
        order = jnp.argsort(key, stable=True)[:rows]  # the held pairs come first, by expert
        key = key[order]
        lane = order // self.top_k
        hit = key < held
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
        place = ((lane[:, None] == jnp.arange(n)[None, :]) & hit[:, None]).astype(y.dtype)
        x_rows = place @ y
        z_rows = place @ z.astype(y.dtype)

        def grouped(name, x):
            w, f = center[name].astype(x.dtype), factors[name]
            thin = jax.lax.ragged_dot(x, f.a.astype(x.dtype), sizes) * z_rows
            return jax.lax.ragged_dot(x, w, sizes) + jax.lax.ragged_dot(
                thin, jnp.swapaxes(f.b, 1, 2).astype(x.dtype), sizes
            )

        hidden = self._act(grouped("gate", x_rows)) * grouped("up", x_rows)
        out = grouped("down", hidden).astype(F32) * weights.reshape(-1)[order][:, None]
        out = jnp.where(hit[:, None], out, 0.0)  # rows past the last group hold no pair
        return place.T @ out.astype(y.dtype), sizes, jnp.zeros((), jnp.int32)

    def _experts_grouped(self, center, factors, z, y, chosen, weights):
        """All lanes' held experts as one grouped product over the (lane,
        expert) pairs that hit a held expert. No pair is dropped and there is
        no capacity factor. One kernel that streams each expert's matrices
        once (``net/grouped.py``) where the program is lowered for a TPU, the
        sizes are the kernel's and no mesh spreads the lanes; the plain form
        everywhere else. Returns the lanes' sums, the pairs on each held
        expert and the row tiles the kernel visited."""
        local = chosen - self.held.start
        mesh = jax.sharding.get_abstract_mesh()
        if not grouped.fits(*y.shape, self.width, y.dtype, z.shape[-1]) or any(
            size > 1 for size in mesh.shape.values()
        ):
            return self._experts_plain(center, factors, z, y, local, weights)
        kernel = grouped.held_experts_for(self.activation)
        return _by_platform(kernel, self._experts_plain, center, factors, z, y, local, weights)

    def _forward(self, acc, x, state):
        with scope("fwd_router"):
            y = rms_norm(x, acc.vec("in_norm"), self.eps)
            chosen, weights = self.route(acc, y)
        return self.routed(acc, x, y, chosen, weights, state)

    def routed(self, acc, x, y, chosen, weights, state):
        """``x`` and what the experts add for ``y``, the block's normed input,
        under routes decided by the caller (``chosen`` ``(n, top_k)`` and
        their float32 ``weights``, as ``route`` gives them), with the state
        the block carries on."""
        with scope("fwd_experts"):
            if acc.z is None:
                mixed, load = self._experts_dense(acc.p["experts"], y, chosen, weights)
                tiles = 0
            else:
                mixed, load, tiles = self._experts_grouped(
                    acc.p["experts"], acc.f["experts"], acc.z, y, chosen, weights
                )
            if self.shared is not None:
                mixed = mixed + self.shared._forward(acc.sub("shared"), y, None)[0]
            out = x + _closed(self, acc, mixed)
            local = chosen - self.held.start
            hits = jnp.sum((local >= 0) & (local < len(self.held)), axis=-1, dtype=jnp.int32)
            state = {
                "chosen": chosen.astype(jnp.int32),
                "hits": state["hits"] + hits,
                "fullest": state["fullest"] + jnp.max(load).astype(jnp.int32),
                "tiles": state["tiles"] + tiles,
            }
        return out, state


class _DenseMLP(_LaneModule):
    """``x + RMSNorm(SwiGLU(RMSNorm(x)))`` (``x + SwiGLU(RMSNorm(x))``
    without ``post_norm``; the block's output times ``residual_scale``): the
    MLP of the leading dense layers, and of a family whose every MLP is
    dense."""

    def __init__(self, dim: int, width: int, *, eps: float = 1e-5, post_norm: bool = True, residual_scale: float = 1.0):
        self.inner, self.dim, self.eps = SwiGLU(dim, width), int(dim), float(eps)
        self.post_norm, self.residual_scale = bool(post_norm), float(residual_scale)

    def init(self, key):
        params = {"in_norm": jnp.ones((self.dim,), F32), "mlp": self.inner.init(key)}
        if self.post_norm:
            params["post_norm"] = jnp.ones((self.dim,), F32)
        return params

    def _forward(self, acc, x, state):
        with scope("fwd_dense_mlp"):
            y = rms_norm(x, acc.vec("in_norm"), self.eps)
            out, _ = self.inner._forward(acc.sub("mlp"), y, None)
            return _joined(self, x, _closed(self, acc, out)), state


class DecoderLayer(_LaneModule):
    """The layer's first block (an attention over a cache, or a recurrence),
    then its MLP (dense or sparse). Parameters and state hold the first block
    under its kind's name (``block_key``: ``"attn"``, ``"ssm"`` or
    ``"kda"``)."""

    def __init__(self, attention: _LaneModule, mlp: _LaneModule):
        self.attention, self.mlp, self.first = attention, mlp, attention.block_key

    def init(self, key):
        ka, km = jax.random.split(key)
        return {self.first: self.attention.init(ka), "mlp": self.mlp.init(km)}

    def initial_state(self):
        return {self.first: self.attention.initial_state(), "mlp": self.mlp.initial_state()}

    def reset_state(self, state, mask):
        mlp = state["mlp"]
        return {
            self.first: self.attention.reset_state(state[self.first], mask),
            "mlp": None if mlp is None else self.mlp.reset_state(mlp, mask),
        }

    def _forward(self, acc, x, state):
        x, first = self.attention._forward(acc.sub(self.first), x, state[self.first])
        x, mlp = self.mlp._forward(acc.sub("mlp"), x, state["mlp"])
        return x, {self.first: first, "mlp": mlp}


class RouteAheadLayer(DecoderLayer):
    """A layer that chooses its experts BEFORE its attention runs: ``u =
    RMSNorm(x)`` with the attention's ``in_norm``, read once by the router
    and by the attention alike; the routes decided from ``u``
    (``fwd_router``, which holds that norm too); the attention on ``u``
    (``GatedAttention.attend``); then the experts on the post-attention
    stream, ``y = RMSNorm(h)`` with the expert block's own ``in_norm``, under
    the routes decided ahead (``SparseExperts.routed``, ``fwd_experts``).
    Parameters as ``DecoderLayer``'s; the expert block's state also counts,
    per lane, the (lane, expert) pairs routed ahead (``ahead``: ``top_k`` a
    step, never reset)."""

    def initial_state(self):
        state = super().initial_state()
        return {**state, "mlp": {**state["mlp"], "ahead": jnp.zeros((), jnp.int32)}}

    def _forward(self, acc, x, state):
        attention, experts = self.attention, self.mlp
        first, mlp = acc.sub(self.first), acc.sub("mlp")
        with scope("fwd_router"):
            u = rms_norm(x, first.vec("in_norm"), attention.eps)
            chosen, weights = experts.route(mlp, u)
        h, attended = attention.attend(first, x, u, state[self.first])
        with scope("fwd_experts"):
            y = rms_norm(h, mlp.vec("in_norm"), experts.eps)
        out, routed = experts.routed(mlp, h, y, chosen, weights, state["mlp"])
        routed = {**routed, "ahead": state["mlp"]["ahead"] + experts.top_k}
        return out, {self.first: attended, "mlp": routed}


class _Decoder(_LaneModule):
    """What every family's decoder is around its layers: embedding rows
    (times ``embed_scale``), the held ``layers``, the final norm and the head
    over the held vocabulary (``tie_embeddings``: the embedding's own leaf,
    gathered by ``rows`` on the way in and multiplied by ``mm`` on the way
    out, one factor pair for both; the logits over ``logits_divisor``); the
    ``seen`` record, ``state_report`` and ``reset_state``. Input: one token
    id; output: float logits over the held vocabulary. A family's constructor
    builds its layers from its published keys and hands them over.

    A lane's position in its episode (``t``) and the steps since the state
    was made (``seen["step"]``) are the decoder's own: it looks into no
    layer's state for them, so a set of held layers needs no attention."""

    def __init__(
        self,
        *,
        hidden_size,
        eps,
        vocab_size,
        vocab_held,
        max_positions,
        layers,
        embed_scale=1.0,
        tie_embeddings=False,
        logits_divisor=1.0,
    ):
        self.hidden_size, self.eps = int(hidden_size), float(eps)
        self.vocab_held = int(vocab_size if vocab_held is None else vocab_held)
        if not 0 < self.vocab_held <= int(vocab_size):
            raise ValueError("vocab_held must lie in (0, vocab_size]")
        self.max_positions = int(max_positions)
        self.layers = tuple(layers)
        self.embedding = Embedding(self.vocab_held, hidden_size, scale=embed_scale)
        self.tie_embeddings, self.logits_divisor = bool(tie_embeddings), float(logits_divisor)

    def init(self, key):
        ke, kh, *kl = jax.random.split(key, 2 + len(self.layers))
        params = {
            "embed": self.embedding.init(ke)["weight"],
            "layers": tuple(layer.init(k) for layer, k in zip(self.layers, kl)),
            "final_norm": jnp.ones((self.hidden_size,), F32),
        }
        if not self.tie_embeddings:
            params["head"] = _normal(kh, (self.vocab_held, self.hidden_size))
        return params

    def initial_state(self):
        slots, zero = jnp.zeros((self.max_positions,), jnp.int32), jnp.zeros((), jnp.int32)
        return {
            "layers": tuple(layer.initial_state() for layer in self.layers),
            "seen": {"ids": slots, "positions": slots, "step": zero},
            "t": zero,
        }

    def reset_state(self, state, mask):
        layers = tuple(layer.reset_state(s, mask) for layer, s in zip(self.layers, state["layers"]))
        # the record outlives an episode
        return {"layers": layers, "seen": state["seen"], "t": jnp.where(mask, 0, state["t"])}

    def state_report(self, state) -> dict:
        """What the lane-batched ``state`` says of the steps since it was
        made (the rollout engine returns it beside its telemetry).
        Whole-population counters: (lane, expert) pairs that hit held
        experts, the pairs on the fullest held expert summed over steps and
        layers, the row tiles the grouped product's kernel visited (with a
        tile's rows; 0 tiles where the plain form ran), the expert-layer
        steps they are sums over, the cache slots written and, where the
        layers keep a latent cache, the positions the lanes could read in it
        (``latent_positions_read``: summed over steps, lanes and layers, the
        count its floor is taken from) and the positions in the blocks the
        cache pass's kernel fetched for them (``latent_positions_fetched``; 0
        where the plain form ran); where layers carry a recurrence, the
        lane-layer states rewritten, summed over steps
        (``ssm_state_updates``), those of them the state pass's kernel
        rewrote (``ssm_state_kernel_updates``; 0 where the plain form ran),
        the lanes zeroed at an episode's end
        (``ssm_lane_resets``), the bytes of windows and matrix states the
        lanes hold (``ssm_state_bytes``, a float) and, per lane, what every
        recurrent layer's matrix state held when the lane last ended an
        episode, summed over ``state_dim`` (``ssm_ended_state``, ``(n,
        recurrent layers, heads, head_dim)``, as stored; zeros for a lane
        that ended none); where layers carry the gated delta rule, the same
        five under ``kda_``: ``kda_state_updates``,
        ``kda_state_kernel_updates``, ``kda_lane_resets``, ``kda_state_bytes``
        and ``kda_ended_state`` (each state summed over its key axis, ``(n,
        KDA layers, heads, head_dim)``); where layers route ahead of their
        attention (``RouteAheadLayer``), the pairs so routed, summed over
        steps, lanes and layers (``expert_pairs_routed_ahead``). Per lane, in
        step order (the last
        ``max_positions`` steps): the id each step consumed and the lane's
        position in its episode there, ``(n, steps)``; the model's token for
        position ``t`` of an episode is the id consumed at ``t + 1``."""
        layers = state["layers"]
        sparse = [s["mlp"] for s in layers if s["mlp"] is not None]
        zero = jnp.zeros((), jnp.int32)
        seen = state["seen"]
        # the ring's oldest step comes first
        first = jnp.where(seen["step"][0] > self.max_positions, seen["step"][0] % self.max_positions, 0)
        caches = [s["attn"] for s in layers if "attn" in s]
        latent_layers = [c for c in caches if "read" in c]
        counted = {"latent_positions_read": "read", "latent_positions_fetched": "fetched"} if latent_layers else {}
        report = {key: sum((jnp.sum(s[name]) for s in latent_layers), zero) for key, name in counted.items()}
        for kind, matrix in (("ssm", "ssm"), ("kda", "state")):  # a recurrent block's kind, its matrix state
            blocks = [s[kind] for s in layers if kind in s]
            if not blocks:
                continue
            steps = {"state_updates": "updates", "state_kernel_updates": "kernel_updates"}
            report.update(
                {f"{kind}_{key}": sum((jnp.sum(m[name]) for m in blocks), zero) for key, name in steps.items() if name in blocks[0]}
            )
            held = sum(math.prod(m[name].shape) * m[name].dtype.itemsize for m in blocks for name in ("conv", matrix))
            report.update({
                f"{kind}_lane_resets": jnp.sum(blocks[0]["resets"]),
                f"{kind}_state_bytes": jnp.asarray(held, F32),  # past int32 at the cell's size
                f"{kind}_ended_state": jnp.stack([m["ended"] for m in blocks], axis=1),
            })
        ahead = [m["ahead"] for m in sparse if "ahead" in m]
        if ahead:
            report["expert_pairs_routed_ahead"] = sum((jnp.sum(a) for a in ahead), zero)
        return {
            **report,
            "expert_pairs_held": sum((jnp.sum(m["hits"]) for m in sparse), zero),
            "expert_pairs_fullest": sum((m["fullest"][0] for m in sparse), zero),
            "expert_row_tiles": sum((m["tiles"][0] for m in sparse), zero),
            "expert_tile_rows": jnp.asarray(grouped.ROW_TILE, jnp.int32),
            "expert_layer_steps": sum((seen["step"][0] for s in layers if s["mlp"] is not None), zero),
            "cache_slots_written": sum((jnp.sum(c["step"]) for c in caches), zero),
            "ids_seen": jnp.roll(seen["ids"], -first, axis=1),
            "positions_seen": jnp.roll(seen["positions"], -first, axis=1),
        }

    def _forward(self, acc, x, state):
        with scope("fwd_head"):
            ids = x.astype(jnp.int32).reshape(x.shape[0])
            h = acc.rows("embed", ids) * self.embedding.scale
            if acc.z is not None:
                h = h.astype(acc.z.dtype)  # the lanes' compute dtype (ids carry none)
            seen = state["seen"]
            at = (0, jnp.mod(seen["step"][0], self.max_positions))
            positions = state["t"]
            seen = {
                "ids": jax.lax.dynamic_update_slice(seen["ids"], ids[:, None], at),
                "positions": jax.lax.dynamic_update_slice(seen["positions"], positions[:, None], at),
                "step": seen["step"] + 1,
            }
        new_state = []
        layers = acc.sub("layers")
        for i, layer in enumerate(self.layers):
            h, s = layer._forward(layers.sub(i), h, state["layers"][i])
            new_state.append(s)
        with scope("fwd_head"):
            logits = acc.mm("embed" if self.tie_embeddings else "head", rms_norm(h, acc.vec("final_norm"), self.eps))
            if self.logits_divisor != 1.0:
                logits = logits / self.logits_divisor
        return logits, {"layers": tuple(new_state), "seen": seen, "t": positions + 1}


class AfmoeDecoder(_Decoder):
    """The ``afmoe`` decoder (Trinity) under the published configuration's
    own keys, plus the share this process holds: ``layers_held`` (ids into
    the published stack; a layer's kind follows from its id),
    ``experts_held`` (a range of expert ids, every sparse layer's),
    ``vocab_held`` (rows of embedding and head), and ``max_positions``, the
    longest episode the cache must hold."""

    def __init__(
        self,
        *,
        hidden_size: int,
        num_attention_heads: int,
        num_key_value_heads: int,
        head_dim: int,
        intermediate_size: int,
        moe_intermediate_size: int,
        num_experts: int,
        num_experts_per_tok: int,
        num_shared_experts: int,
        num_dense_layers: int,
        layer_types: Sequence[str],
        sliding_window: int,
        rope_theta: float,
        route_scale: float,
        route_norm: bool,
        score_func: str,
        rms_norm_eps: float,
        vocab_size: int,
        mup_enabled: bool,
        max_positions: int,
        layers_held: Optional[Sequence[int]] = None,
        experts_held: Optional[range] = None,
        vocab_held: Optional[int] = None,
    ):
        if score_func != "sigmoid":
            raise ValueError(f"score_func {score_func!r}: the family selects with a bias, under the sigmoid router only")
        self.layers_held = tuple(range(len(layer_types)) if layers_held is None else layers_held)
        eps, max_positions = float(rms_norm_eps), int(max_positions)
        layers = []
        for index in self.layers_held:
            kind = layer_types[index]
            if kind not in ("sliding_attention", "full_attention"):
                raise ValueError(f"layer_types[{index}] = {kind!r}")
            sliding = kind == "sliding_attention"
            attention = GatedAttention(
                hidden_size,
                num_attention_heads,
                num_key_value_heads,
                head_dim,
                slots=min(int(sliding_window), max_positions) if sliding else max_positions,
                rope_theta=rope_theta if sliding else None,  # no positions in full layers
                eps=eps,
            )
            if index < int(num_dense_layers):
                mlp = _DenseMLP(hidden_size, intermediate_size, eps=eps)
            else:
                mlp = SparseExperts(
                    hidden_size,
                    moe_intermediate_size,
                    num_experts,
                    num_experts_per_tok,
                    experts_held=experts_held,
                    num_shared_experts=num_shared_experts,
                    route_scale=route_scale,
                    route_norm=route_norm,
                    score_func=score_func,
                    eps=eps,
                )
            layers.append(DecoderLayer(attention, mlp))
        super().__init__(
            hidden_size=hidden_size,
            eps=eps,
            vocab_size=vocab_size,
            vocab_held=vocab_held,
            max_positions=max_positions,
            layers=layers,
            embed_scale=math.sqrt(hidden_size) if mup_enabled else 1.0,
        )


class Glm4MoeLiteDecoder(_Decoder):
    """The ``glm4_moe_lite`` decoder (GLM-4.7-Flash) under the published
    configuration's own keys, plus the share this process holds, as
    ``AfmoeDecoder`` takes it: latent attention in every layer, a norm before
    a block only, a dense MLP in the first ``first_k_dense_replace`` layers
    and ``noaux_tc``-routed experts with a shared one after them, no
    embedding scale. The next-token-prediction block
    (``num_nextn_predict_layers``) is no part of the causal forward and is
    not built."""

    def __init__(
        self,
        *,
        hidden_size: int,
        num_attention_heads: int,
        q_lora_rank: int,
        kv_lora_rank: int,
        qk_nope_head_dim: int,
        qk_rope_head_dim: int,
        v_head_dim: int,
        intermediate_size: int,
        moe_intermediate_size: int,
        n_routed_experts: int,
        num_experts_per_tok: int,
        n_shared_experts: int,
        first_k_dense_replace: int,
        num_hidden_layers: int,
        routed_scaling_factor: float,
        norm_topk_prob: bool,
        topk_method: str,
        n_group: int,
        topk_group: int,
        rope_theta: float,
        rope_scaling: Optional[dict],
        rms_norm_eps: float,
        vocab_size: int,
        max_positions: int,
        layers_held: Optional[Sequence[int]] = None,
        experts_held: Optional[range] = None,
        vocab_held: Optional[int] = None,
    ):
        if topk_method != "noaux_tc":
            raise ValueError(f"topk_method {topk_method!r}: only noaux_tc is implemented")
        if int(n_group) != 1 or int(topk_group) != 1:
            raise ValueError("n_group and topk_group other than 1: group-limited routing is not implemented")
        if rope_scaling is not None:
            raise ValueError("rope_scaling is not implemented")
        self.layers_held = tuple(range(int(num_hidden_layers)) if layers_held is None else layers_held)
        if not all(0 <= index < int(num_hidden_layers) for index in self.layers_held):
            raise ValueError(f"layers_held {self.layers_held!r} is not within {num_hidden_layers} layers")
        eps, max_positions = float(rms_norm_eps), int(max_positions)
        layers = []
        for index in self.layers_held:
            attention = LatentAttention(
                hidden_size,
                num_attention_heads,
                q_rank=q_lora_rank,
                kv_rank=kv_lora_rank,
                nope_dim=qk_nope_head_dim,
                rope_dim=qk_rope_head_dim,
                v_dim=v_head_dim,
                slots=max_positions,  # every layer is full attention: no ring
                rope_theta=rope_theta,
                eps=eps,
            )
            if index < int(first_k_dense_replace):
                mlp = _DenseMLP(hidden_size, intermediate_size, eps=eps, post_norm=False)
            else:
                mlp = SparseExperts(
                    hidden_size,
                    moe_intermediate_size,
                    n_routed_experts,
                    num_experts_per_tok,
                    experts_held=experts_held,
                    num_shared_experts=n_shared_experts,
                    route_scale=routed_scaling_factor,
                    route_norm=norm_topk_prob,
                    eps=eps,
                    post_norm=False,
                    route_norm_eps=1e-20,
                )
            layers.append(DecoderLayer(attention, mlp))
        super().__init__(
            hidden_size=hidden_size,
            eps=eps,
            vocab_size=vocab_size,
            vocab_held=vocab_held,
            max_positions=max_positions,
            layers=layers,
        )


class GraniteMoeHybridDecoder(_Decoder):
    """The ``granitemoehybrid`` decoder (Granite 4.0-H) under the published
    configuration's own keys, plus the share this process holds
    (``layers_held``, ``vocab_held``, ``max_positions``, as ``AfmoeDecoder``
    takes them). A layer's kind follows from ``layer_types``: ``"mamba"`` a
    Mamba-2 mixer, ``"attention"`` grouped-query attention without positions
    (``position_embedding_type`` ``"nope"``), without gate or per-head norms,
    its scores times ``attention_multiplier``. Every layer's MLP is the shared
    one (``num_local_experts`` 0: no routed experts) of width
    ``shared_intermediate_size``; a norm before a block only, a block's
    output times ``residual_multiplier``; embedding rows times
    ``embedding_multiplier``; the head is the embedding's own leaf
    (``tie_word_embeddings``) and the logits are over ``logits_scaling``.
    ``mamba_chunk_size`` belongs to the whole-sequence scan and is no part of
    a stepwise forward."""

    def __init__(
        self,
        *,
        hidden_size: int,
        num_attention_heads: int,
        num_key_value_heads: int,
        shared_intermediate_size: int,
        layer_types: Sequence[str],
        mamba_n_heads: int,
        mamba_d_head: int,
        mamba_d_state: int,
        mamba_n_groups: int,
        mamba_d_conv: int,
        mamba_expand: int,
        mamba_conv_bias: bool,
        mamba_proj_bias: bool,
        num_local_experts: int,
        attention_bias: bool,
        attention_multiplier: float,
        embedding_multiplier: float,
        residual_multiplier: float,
        logits_scaling: float,
        position_embedding_type: str,
        tie_word_embeddings: bool,
        rms_norm_eps: float,
        vocab_size: int,
        max_positions: int,
        layers_held: Optional[Sequence[int]] = None,
        vocab_held: Optional[int] = None,
    ):
        if int(num_local_experts) != 0:
            raise ValueError("num_local_experts other than 0: routed experts are not implemented in this family")
        if int(mamba_n_groups) != 1:
            raise ValueError("mamba_n_groups other than 1 is not implemented")
        if int(mamba_n_heads) * int(mamba_d_head) != int(mamba_expand) * int(hidden_size):
            raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
        if not mamba_conv_bias or mamba_proj_bias or attention_bias:
            raise ValueError("only the convolution carries a bias (mamba_conv_bias alone)")
        if position_embedding_type != "nope":
            raise ValueError(f"position_embedding_type {position_embedding_type!r}: only 'nope' is implemented")
        self.layers_held = tuple(range(len(layer_types)) if layers_held is None else layers_held)
        eps, max_positions, scale = float(rms_norm_eps), int(max_positions), float(residual_multiplier)
        layers = []
        for index in self.layers_held:
            kind = layer_types[index]
            if kind == "mamba":
                first = Mamba2Mixer(
                    hidden_size, mamba_n_heads, mamba_d_head, mamba_d_state,
                    conv_width=mamba_d_conv, eps=eps, residual_scale=scale,
                )
            elif kind == "attention":
                first = GatedAttention(
                    hidden_size,
                    num_attention_heads,
                    num_key_value_heads,
                    int(hidden_size) // int(num_attention_heads),
                    slots=max_positions,
                    rope_theta=None,
                    eps=eps,
                    gate=False,
                    qk_norm=False,
                    post_norm=False,
                    score_scale=attention_multiplier,
                    residual_scale=scale,
                )
            else:
                raise ValueError(f"layer_types[{index}] = {kind!r}")
            mlp = _DenseMLP(hidden_size, shared_intermediate_size, eps=eps, post_norm=False, residual_scale=scale)
            layers.append(DecoderLayer(first, mlp))
        super().__init__(
            hidden_size=hidden_size,
            eps=eps,
            vocab_size=vocab_size,
            vocab_held=vocab_held,
            max_positions=max_positions,
            layers=layers,
            embed_scale=embedding_multiplier,
            tie_embeddings=tie_word_embeddings,
            logits_divisor=logits_scaling,
        )


class KimiLinearDecoder(_Decoder):
    """The ``kimi_linear`` decoder (Kimi-Linear) under the published
    configuration's own keys, plus the share this process holds
    (``layers_held``, ``experts_held``, ``vocab_held``, ``max_positions``, as
    ``Glm4MoeLiteDecoder`` takes them). A layer's kind follows from
    ``linear_attn_config``: the 1-based ids of ``kda_layers`` are gated
    delta-rule layers (``KimiDeltaAttention``, ``num_heads`` x ``head_dim``,
    convolutions of ``short_conv_kernel_size``, gate ranks of ``head_dim``),
    those of ``full_attn_layers`` latent attention (``LatentAttention``, the
    query straight from the input where ``q_lora_rank`` is null, no rotary
    embedding under ``mla_use_nope``); a dense MLP in the first
    ``first_k_dense_replace`` layers and sigmoid-routed experts with a
    selection bias, renormalised, times ``routed_scaling_factor``, and
    ``num_shared_experts`` after them; a norm before a block only; an untied
    head."""

    def __init__(
        self,
        *,
        hidden_size: int,
        num_attention_heads: int,
        q_lora_rank: Optional[int],
        kv_lora_rank: int,
        qk_nope_head_dim: int,
        qk_rope_head_dim: int,
        v_head_dim: int,
        mla_use_nope: bool,
        linear_attn_config: dict,
        intermediate_size: int,
        moe_intermediate_size: int,
        num_experts: int,
        num_experts_per_token: int,
        num_shared_experts: int,
        first_k_dense_replace: int,
        moe_layer_freq: int,
        moe_renormalize: bool,
        moe_router_activation_func: str,
        routed_scaling_factor: float,
        num_expert_group: int,
        topk_group: int,
        num_hidden_layers: int,
        rope_theta: float,
        rope_scaling: Optional[dict],
        rms_norm_eps: float,
        tie_word_embeddings: bool,
        vocab_size: int,
        max_positions: int,
        layers_held: Optional[Sequence[int]] = None,
        experts_held: Optional[range] = None,
        vocab_held: Optional[int] = None,
    ):
        if int(num_expert_group) != 1 or int(topk_group) != 1:
            raise ValueError("num_expert_group and topk_group other than 1: group-limited routing is not implemented")
        if int(moe_layer_freq) != 1:
            raise ValueError("moe_layer_freq other than 1 is not implemented")
        if moe_router_activation_func != "sigmoid":
            raise ValueError(
                f"moe_router_activation_func {moe_router_activation_func!r}: the family selects with a bias,"
                " under the sigmoid score_func only"
            )
        if rope_scaling is not None:
            raise ValueError("rope_scaling is not implemented")
        kda = {int(i) - 1 for i in linear_attn_config["kda_layers"]}  # published 1-based
        full = {int(i) - 1 for i in linear_attn_config["full_attn_layers"]}
        if kda & full or kda | full != set(range(int(num_hidden_layers))):
            raise ValueError("kda_layers and full_attn_layers do not split the 1-based layers 1..num_hidden_layers")
        self.layers_held = tuple(range(int(num_hidden_layers)) if layers_held is None else layers_held)
        if not all(0 <= index < int(num_hidden_layers) for index in self.layers_held):
            raise ValueError(f"layers_held {self.layers_held!r} is not within {num_hidden_layers} layers")
        eps, max_positions = float(rms_norm_eps), int(max_positions)
        layers = []
        for index in self.layers_held:
            if index in kda:
                first = KimiDeltaAttention(
                    hidden_size,
                    linear_attn_config["num_heads"],
                    linear_attn_config["head_dim"],
                    conv_width=linear_attn_config["short_conv_kernel_size"],
                    eps=eps,
                )
            else:
                first = LatentAttention(
                    hidden_size,
                    num_attention_heads,
                    q_rank=q_lora_rank,
                    kv_rank=kv_lora_rank,
                    nope_dim=qk_nope_head_dim,
                    rope_dim=qk_rope_head_dim,
                    v_dim=v_head_dim,
                    slots=max_positions,  # every such layer is full attention: no ring
                    rope_theta=rope_theta,
                    eps=eps,
                    rotary=not mla_use_nope,
                )
            if index < int(first_k_dense_replace):
                mlp = _DenseMLP(hidden_size, intermediate_size, eps=eps, post_norm=False)
            else:
                mlp = SparseExperts(
                    hidden_size,
                    moe_intermediate_size,
                    num_experts,
                    num_experts_per_token,
                    experts_held=experts_held,
                    num_shared_experts=num_shared_experts,
                    route_scale=routed_scaling_factor,
                    route_norm=moe_renormalize,
                    score_func=moe_router_activation_func,
                    eps=eps,
                    post_norm=False,
                    route_norm_eps=1e-20,
                )
            layers.append(DecoderLayer(first, mlp))
        super().__init__(
            hidden_size=hidden_size,
            eps=eps,
            vocab_size=vocab_size,
            vocab_held=vocab_held,
            max_positions=max_positions,
            layers=layers,
            tie_embeddings=tie_word_embeddings,
        )


class SmallThinkerDecoder(_Decoder):
    """The ``smallthinker`` decoder (SmallThinker-21BA3B) under the published
    configuration's own keys, plus the share this process holds
    (``layers_held``, ``experts_held``, ``vocab_held``, ``max_positions``, as
    ``AfmoeDecoder`` takes them). Every layer routes AHEAD of its attention
    (``RouteAheadLayer``): a softmax router over ``moe_num_primary_experts``
    (``moe_primary_router_apply_softmax``) reads the attention's normed
    input, keeps the ``moe_num_active_primary_experts`` largest logits and
    renormalises their weights over the chosen (``norm_topk_prob``); no
    shared expert, no selection bias; ReGLU experts (``relu(gate) * up``) of
    width ``moe_ffn_hidden_size`` on the post-attention stream. Attention is
    grouped-query without gate, per-head norms or a norm after the block:
    RoPE where ``rope_layout`` is 1 and no positions where it is 0, a ring
    of ``sliding_window_size`` slots where ``sliding_window_layout`` is 1 and
    full attention where it is 0. A norm before a block only; no embedding
    scale; the head untied unless ``tie_word_embeddings``. The family's
    "secondary experts" have no key in the configuration and are not built."""

    def __init__(
        self,
        *,
        hidden_size: int,
        num_attention_heads: int,
        num_key_value_heads: int,
        head_dim: int,
        moe_ffn_hidden_size: int,
        moe_num_primary_experts: int,
        moe_num_active_primary_experts: int,
        moe_primary_router_apply_softmax: bool,
        norm_topk_prob: bool,
        num_hidden_layers: int,
        rope_layout: Sequence[int],
        sliding_window_layout: Sequence[int],
        sliding_window_size: int,
        rope_theta: float,
        rope_scaling: Optional[dict],
        rms_norm_eps: float,
        tie_word_embeddings: bool,
        vocab_size: int,
        max_positions: int,
        layers_held: Optional[Sequence[int]] = None,
        experts_held: Optional[range] = None,
        vocab_held: Optional[int] = None,
    ):
        if not moe_primary_router_apply_softmax:
            raise ValueError("moe_primary_router_apply_softmax false: only the softmax router is implemented here")
        if rope_scaling is not None:
            raise ValueError("rope_scaling is not implemented")
        count = int(num_hidden_layers)
        for name, layout in (("rope_layout", rope_layout), ("sliding_window_layout", sliding_window_layout)):
            if len(layout) != count or not set(layout) <= {0, 1}:
                raise ValueError(f"{name} must give a 0 or a 1 for each of the {count} layers")
        self.layers_held = tuple(range(count) if layers_held is None else layers_held)
        if not all(0 <= index < count for index in self.layers_held):
            raise ValueError(f"layers_held {self.layers_held!r} is not within {count} layers")
        eps, max_positions = float(rms_norm_eps), int(max_positions)
        layers = []
        for index in self.layers_held:
            attention = GatedAttention(
                hidden_size,
                num_attention_heads,
                num_key_value_heads,
                head_dim,
                slots=min(int(sliding_window_size), max_positions) if sliding_window_layout[index] else max_positions,
                rope_theta=rope_theta if rope_layout[index] else None,  # no positions where the layout says 0
                eps=eps,
                gate=False,
                qk_norm=False,
                post_norm=False,
            )
            experts = SparseExperts(
                hidden_size,
                moe_ffn_hidden_size,
                moe_num_primary_experts,
                moe_num_active_primary_experts,
                experts_held=experts_held,
                num_shared_experts=0,
                route_norm=norm_topk_prob,
                score_func="softmax",
                eps=eps,
                post_norm=False,
                activation="relu",
            )
            layers.append(RouteAheadLayer(attention, experts))
        super().__init__(
            hidden_size=hidden_size,
            eps=eps,
            vocab_size=vocab_size,
            vocab_held=vocab_held,
            max_positions=max_positions,
            layers=layers,
            tie_embeddings=tie_word_embeddings,
        )


def stepwise_logits(policy, params_batch, ids, *, positions=None, lanes=None, compute_dtype=None):
    """Decode the id sequences ``ids`` ``(n, T)`` one token a step,
    teacher-forced, through the population-wide forward the rollout engine
    steps (the same ``_batched_forward`` on the same forward context, the
    cache as carried state): float32 logits ``(n, T, vocab)`` and the experts
    chosen ``(T, sparse layers, n, top_k)``. Scoring a population on given
    text, and replaying what an evaluation consumed (``state_report``'s
    ``ids_seen`` and ``positions_seen``): with ``positions`` ``(n, T)``, a
    lane whose position reads 0 after the first step starts an episode there
    and its state is reset first, as the engine resets it at an episode's
    end. ``lanes``: indices of the lanes whose logits and experts are
    returned (all of them step; default all). Call under ``jit``. ``policy``
    wraps a decoder of this module."""
    from .vecrl import _batched_forward, _forward_ctx, _initial_policy_states, _params_cast

    params_batch = _params_cast(params_batch, compute_dtype)
    ctx = _forward_ctx(policy, params_batch)
    states = _initial_policy_states(policy, ids.shape[0], compute_dtype)
    lanes = jnp.arange(ids.shape[0]) if lanes is None else jnp.asarray(lanes)
    if positions is None:
        restarts = jnp.zeros(ids.shape, bool)
    else:
        restarts = (jnp.asarray(positions) == 0).at[:, 0].set(False)

    def body(states, column):
        column, restart = column
        states = policy.module.reset_state(states, restart)
        logits, states = _batched_forward(policy, params_batch, ctx, column[:, None], states)
        chosen = [s["mlp"]["chosen"][lanes] for s in states["layers"] if s["mlp"] is not None]
        routes = jnp.stack(chosen) if chosen else jnp.zeros((0,), jnp.int32)
        return states, (logits[lanes].astype(F32), routes)

    _, (logits, routes) = jax.lax.scan(body, states, (ids.T, restarts.T))
    return jnp.swapaxes(logits, 0, 1), routes
