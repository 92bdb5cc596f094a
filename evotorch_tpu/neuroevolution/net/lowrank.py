"""Low-rank-perturbation policy evaluation: the MXU path for wide policies.

The defining cost of ES evaluation is that every population lane carries its
OWN parameter vector, so the policy forward is a batch of N tiny per-lane
matvecs — the MXU cannot amortize weight loads across lanes, and throughput
collapses as the policy grows (r2 chip run, ROADMAP S4: 8x params ->
3.4x slower). The classic low-rank answer (the LM-MA-ES / random-subspace ES
family) restructures the perturbation instead of the hardware:

    theta_i = c + B z_i          B: (L, k) shared basis,  z_i: (k,) per lane

Then every Linear layer's effective weight is ``W_c + sum_m z_im D_m`` with
shared direction matrices ``D_m``, and the whole population's forward is

    Y_aug = X @ [W_c; D_1; ...; D_k]^T        one LARGE dense matmul (MXU)
    y_i   = Y_aug[i, :o] + sum_m z_im Y_aug[i, o*m:o*(m+1)]   (VPU epilogue)

(k+1) dense shared-weight matmuls instead of N tiny per-lane matvecs — and
the (N, L) population matrix is never materialized at all (for a 256x256
policy at popsize 10k that matrix alone is 3.9 GB).

Recurrent cells get the same treatment: an RNN/LSTM step is two matmuls
(input-to-hidden and hidden-to-hidden), each of which augments exactly like
a Linear — so recurrent policies run the MXU path at full speed too, with
the per-lane hidden state threaded through unchanged (VERDICT r3 #4).

``LowRankParamsBatch`` is the population representation (defined in
``tools/lowrank.py`` so core/distributions can speak it too); the rollout
engine (``vecrl.py``) accepts it anywhere it accepts a dense ``(N, L)``
matrix. Modules without a structured path (custom/unstructured) fall back to
materializing the dense population — correct everywhere, fast where it
matters, and LOUD (a trace-time warning) when the fallback fires.

No reference counterpart: the reference evaluates dense populations only
(``distributions.py:616-773`` samples full vectors); this is a TPU-first
framework feature (VERDICT r2 #2).
"""

from __future__ import annotations

import warnings
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ...tools.lowrank import DeltaFactor as _Factor
from ...tools.lowrank import LowRankParamsBatch, TrunkDeltaParamsBatch
from .layers import LSTM, RNN, Bias, Linear, Module, Sequential

__all__ = [
    "LowRankParamsBatch",
    "TrunkDeltaParamsBatch",
    "lowrank_supported",
    "prepare_lowrank",
    "lowrank_forward",
    "trunk_delta_supported",
    "sample_trunk_delta_factors",
    "prepare_trunk_delta",
    "trunk_delta_forward",
]


def lowrank_supported(module: Module) -> bool:
    """True when the module stack has a structured low-rank forward:
    Sequential pipelines of Linear / Bias / RNN / LSTM / parameterless
    layers."""
    if isinstance(module, Sequential):
        return all(lowrank_supported(m) for m in module.modules)
    if isinstance(module, (Linear, Bias, RNN, LSTM)):
        return True
    # parameterless layers (activations, Clip, Slice, ...) pass through
    return _is_parameterless(module)


def _is_parameterless(module: Module) -> bool:
    try:
        params = module.init(jax.random.key(0))
    except Exception:  # graftlint: allow(swallow): probe: a module that cannot init is simply not parameterless
        return False
    return len(jax.tree_util.tree_leaves(params)) == 0 and not module.is_stateful


class _Prepared(NamedTuple):
    """Per-layer center/basis parameter trees, precomputed once per rollout
    (loop-invariant): ``basis_tree`` leaves carry a trailing ``k`` axis."""

    center_tree: Any
    basis_tree: Any
    coeffs: jnp.ndarray


def prepare_lowrank(policy, params: LowRankParamsBatch) -> _Prepared:
    """Split the flat center/basis into per-layer trees. Cheap (slices and
    reshapes); call once per rollout, outside the stepping loop."""
    center_tree = policy.unravel(params.center)
    basis_tree = jax.vmap(policy.unravel, in_axes=1, out_axes=-1)(params.basis)
    return _Prepared(center_tree, basis_tree, params.coeffs)


def _augmented_matmul(W_c, W_b, z, x):
    """``x`` (B, in) times the per-lane effective weight
    ``W_i = W_c + sum_m z_im W_b[..., m]``, computed as ONE augmented dense
    matmul: the center weight and the k direction matrices stacked row-wise,
    so the MXU sees a single (B, in) @ (in, (k+1)*out) contraction; the
    per-lane combination is a cheap VPU epilogue. Returns (B, out)."""
    out_f, in_f = W_c.shape
    k = W_b.shape[-1]
    # (k, out, in) -> (k*out, in); stack center on top -> ((k+1)*out, in)
    W_dirs = jnp.moveaxis(W_b, -1, 0).reshape(k * out_f, in_f)
    W_aug = jnp.concatenate([W_c, W_dirs], axis=0)
    y_aug = x @ W_aug.T  # (B, (k+1)*out)
    y = y_aug[:, :out_f]
    corr = y_aug[:, out_f:].reshape(-1, k, out_f)
    return y + jnp.einsum("bko,bk->bo", corr, z)


def _lane_bias(cp_bias, bp_bias, z):
    """Per-lane effective bias ``b_c + sum_m z_im b_b[:, m]`` -> (B, out)."""
    return cp_bias + z @ bp_bias.T


def _linear_lowrank(layer: Linear, cp, bp, z, x):
    y = _augmented_matmul(cp["weight"], bp["weight"], z, x)
    if layer.bias:
        y = y + _lane_bias(cp["bias"], bp["bias"], z)
    return y


def _bias_lowrank(layer: Bias, cp, bp, z, x):
    return x + _lane_bias(cp["bias"], bp["bias"], z)


def _rnn_lowrank(layer: RNN, cp, bp, z, x, state):
    """Elman cell (layers.py:309): both matmuls augment like Linear; the
    per-lane hidden state is just another (B, hidden) activation."""
    if state is None:
        state = jnp.zeros(x.shape[:-1] + (layer.hidden_size,), dtype=x.dtype)
    pre = (
        _augmented_matmul(cp["W_ih"], bp["W_ih"], z, x)
        + _augmented_matmul(cp["W_hh"], bp["W_hh"], z, state)
        + _lane_bias(cp["b_ih"], bp["b_ih"], z)
        + _lane_bias(cp["b_hh"], bp["b_hh"], z)
    )
    h = jnp.tanh(pre) if layer.nonlinearity == "tanh" else jax.nn.relu(pre)
    return h, h


def _lstm_lowrank(layer: LSTM, cp, bp, z, x, state):
    """LSTM cell (layers.py:350): the (4h, in) and (4h, h) gate matmuls
    augment like Linear; gate nonlinearities are the same VPU epilogue as
    the dense path."""
    if state is None:
        h = jnp.zeros(x.shape[:-1] + (layer.hidden_size,), dtype=x.dtype)
        c = jnp.zeros(x.shape[:-1] + (layer.hidden_size,), dtype=x.dtype)
    else:
        h, c = state
    gates = (
        _augmented_matmul(cp["W_ih"], bp["W_ih"], z, x)
        + _augmented_matmul(cp["W_hh"], bp["W_hh"], z, h)
        + _lane_bias(cp["b_ih"], bp["b_ih"], z)
        + _lane_bias(cp["b_hh"], bp["b_hh"], z)
    )
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c = f * c + i * g
    h = o * jnp.tanh(c)
    return h, (h, c)


def _apply_lowrank(module: Module, cp, bp, z, x, state):
    """Structured whole-population forward, threading per-lane recurrent
    state exactly like ``Sequential.apply`` threads it in the dense path.
    Returns ``(y, new_state)``."""
    if isinstance(module, Sequential):
        if state is None:
            state = tuple(None for _ in module.modules)
        new_states = []
        for m, c, b, s in zip(module.modules, cp, bp, state):
            x, ns = _apply_lowrank(m, c, b, z, x, s)
            new_states.append(ns)
        out_state = tuple(new_states)
        if all(s is None for s in out_state):
            out_state = None
        return x, out_state
    if isinstance(module, Linear):
        return _linear_lowrank(module, cp, bp, z, x), state
    if isinstance(module, Bias):
        return _bias_lowrank(module, cp, bp, z, x), state
    if isinstance(module, RNN):
        return _rnn_lowrank(module, cp, bp, z, x, state)
    if isinstance(module, LSTM):
        return _lstm_lowrank(module, cp, bp, z, x, state)
    # parameterless layer: batched apply is the plain apply
    return module.apply(cp, x, state)


def lowrank_forward(
    policy, params: LowRankParamsBatch, prepared: Optional[_Prepared], obs, states
) -> Tuple[jnp.ndarray, Any]:
    """Whole-population forward: ``obs`` (B, obs_dim) -> (B, act_dim).
    ``prepared`` may be None (computed on the fly — only sensible outside
    hot loops). ``states`` is the batched per-lane state pytree (leading
    axis B) for recurrent stacks, or None."""
    module = policy.module
    if lowrank_supported(module):
        if prepared is None:
            prepared = prepare_lowrank(policy, params)
        return _apply_lowrank(
            module, prepared.center_tree, prepared.basis_tree, prepared.coeffs, obs, states
        )
    # fallback: materialize the dense population and vmap (correct for any
    # module). Loud, not silent: the caller chose the low-rank representation
    # to AVOID this matrix (VERDICT r3 #3) — the warning fires at trace time,
    # once per compile
    warnings.warn(
        f"low-rank forward fell back to materializing the dense "
        f"({params.popsize}, {params.center.shape[-1]}) population: "
        f"{type(module).__name__} has no structured low-rank path "
        "(supported: Sequential stacks of Linear/Bias/RNN/LSTM/"
        "parameterless layers)",
        stacklevel=2,
    )
    dense = params.materialize()
    if states is None:
        return jax.vmap(lambda p, o: policy(p, o))(dense, obs)
    return jax.vmap(policy)(dense, obs, states)


# ---------------------------------------------------------------------------
# the shared-trunk + per-lane low-rank-delta form (docs/policies.md)
#
# The augmented matmul above still pays (k+1) trunk-sized matmuls per layer.
# Structuring each basis column as a RANK-1 block per 2-D weight —
# ``D_m = b_m a_m^T`` — collapses the per-layer forward to
#
#     y = x @ W_c^T + ((x @ A) * z) @ B^T        A: (in, k), B: (out, k)
#
# ONE trunk GEMM over the whole population batch (the weight is loaded once
# for every lane — real MXU arithmetic intensity) plus two thin shared
# GEMMs; per-lane cost drops from (k+1)·in·out to in·out + k·(in+out).
# ---------------------------------------------------------------------------


def trunk_delta_supported(module: Module) -> bool:
    """The trunk-delta path covers the structured stacks of the
    augmented-matmul path (Sequential pipelines of Linear / Bias / RNN / LSTM
    / parameterless layers) and every module that brings its own
    ``trunk_delta_apply(center, factors, z, x, state)`` (the decoder modules
    of ``net/decoder.py``)."""
    if isinstance(module, Sequential):
        return all(trunk_delta_supported(m) for m in module.modules)
    return hasattr(module, "trunk_delta_apply") or lowrank_supported(module)


def sample_trunk_delta_factors(key, policy, sigma: jnp.ndarray, rank: int):
    """Draw one generation's delta factors: a pytree mirroring the policy's
    parameter tree with a ``tools.lowrank.DeltaFactor`` at every leaf. The
    population is ``theta_i = center + basis @ z_i`` with the column ``m`` of
    the basis the concatenation of the leaves' rank-1 blocks; the basis is
    implied, never built (gradients, guardrail and ``materialize_rows`` read
    the factors leaf by leaf).

    Sigma folding: 1-D leaves fold the per-parameter sigma exactly; matrix
    leaves (2-D ``(out, in)``; 3-D ``(group, in, out)`` stacks, one block per
    member) fold the block's RMS sigma (a per-parameter scale would break the
    rank-1 structure the fast forward depends on). Per-entry delta variance
    is ``sigma^2`` (blockwise for matrices), matching the default low-rank
    basis scaling at equal rank.
    """
    sigma_tree = policy.unravel(sigma)
    leaves, treedef = jax.tree_util.tree_flatten(sigma_tree)
    factor_nodes = []
    inv_sqrt_k = 1.0 / jnp.sqrt(jnp.asarray(float(rank), sigma.dtype))
    for i, sigma_leaf in enumerate(leaves):
        k_a = jax.random.fold_in(key, 2 * i)
        k_b = jax.random.fold_in(key, 2 * i + 1)
        dtype = sigma_leaf.dtype
        if sigma_leaf.ndim == 2:
            out_f, in_f = sigma_leaf.shape
            a = jax.random.normal(k_a, (in_f, rank), dtype)
            block_rms = jnp.sqrt(jnp.mean(sigma_leaf * sigma_leaf))
            b = jax.random.normal(k_b, (out_f, rank), dtype) * (block_rms * inv_sqrt_k)
        elif sigma_leaf.ndim == 3:
            group, in_f, out_f = sigma_leaf.shape
            a = jax.random.normal(k_a, (group, in_f, rank), dtype)
            block_rms = jnp.sqrt(jnp.mean(sigma_leaf * sigma_leaf, axis=(1, 2)))
            b = jax.random.normal(k_b, (group, out_f, rank), dtype) * (
                block_rms[:, None, None] * inv_sqrt_k
            )
        elif sigma_leaf.ndim == 1:
            a = jnp.zeros((0, rank), dtype)
            b = (
                jax.random.normal(k_b, sigma_leaf.shape + (rank,), dtype)
                * inv_sqrt_k
                * sigma_leaf[:, None]
            )
        else:
            raise ValueError(
                "trunk-delta factors need 1-D, 2-D or stacked 3-D parameter leaves;"
                f" got shape {sigma_leaf.shape} (leaf {i})"
            )
        factor_nodes.append(_Factor(a=a, b=b))
    return jax.tree_util.tree_unflatten(treedef, factor_nodes)


class _TrunkPrepared(NamedTuple):
    """Loop-invariant forward context of a trunk-delta rollout: the
    unraveled trunk tree, the factor tree, the per-lane coefficients, and
    the static lane-block size (0 = single block; the autotuner's ``policy``
    knob group searches it)."""

    center_tree: Any
    factors: Any
    coeffs: jnp.ndarray
    trunk_block: int = 0


def prepare_trunk_delta(
    policy, params: TrunkDeltaParamsBatch, *, trunk_block: int = 0
) -> _TrunkPrepared:
    """Split the flat trunk into its per-layer tree. Cheap; call once per
    rollout, outside the stepping loop."""
    return _TrunkPrepared(
        policy.unravel(params.center), params.factors, params.coeffs, int(trunk_block)
    )


def _trunk_matmul(W_c, fac: _Factor, z, x):
    """``x`` (B, in) times the per-lane effective weight
    ``W_i = W_c + sum_m z_im b_m a_m^T``: one shared trunk GEMM plus the
    thin delta GEMMs. Returns (B, out)."""
    return x @ W_c.T + ((x @ fac.a) * z) @ fac.b.T


def _linear_trunk(layer: Linear, cp, fx, z, x):
    y = _trunk_matmul(cp["weight"], fx["weight"], z, x)
    if layer.bias:
        y = y + _lane_bias(cp["bias"], fx["bias"].b, z)
    return y


def _bias_trunk(layer: Bias, cp, fx, z, x):
    return x + _lane_bias(cp["bias"], fx["bias"].b, z)


def _rnn_trunk(layer: RNN, cp, fx, z, x, state):
    if state is None:
        state = jnp.zeros(x.shape[:-1] + (layer.hidden_size,), dtype=x.dtype)
    pre = (
        _trunk_matmul(cp["W_ih"], fx["W_ih"], z, x)
        + _trunk_matmul(cp["W_hh"], fx["W_hh"], z, state)
        + _lane_bias(cp["b_ih"], fx["b_ih"].b, z)
        + _lane_bias(cp["b_hh"], fx["b_hh"].b, z)
    )
    h = jnp.tanh(pre) if layer.nonlinearity == "tanh" else jax.nn.relu(pre)
    return h, h


def _lstm_trunk(layer: LSTM, cp, fx, z, x, state):
    if state is None:
        h = jnp.zeros(x.shape[:-1] + (layer.hidden_size,), dtype=x.dtype)
        c = jnp.zeros(x.shape[:-1] + (layer.hidden_size,), dtype=x.dtype)
    else:
        h, c = state
    gates = (
        _trunk_matmul(cp["W_ih"], fx["W_ih"], z, x)
        + _trunk_matmul(cp["W_hh"], fx["W_hh"], z, h)
        + _lane_bias(cp["b_ih"], fx["b_ih"].b, z)
        + _lane_bias(cp["b_hh"], fx["b_hh"].b, z)
    )
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c = f * c + i * g
    h = o * jnp.tanh(c)
    return h, (h, c)


def _apply_trunk_delta(module: Module, cp, fx, z, x, state):
    """Whole-population trunk-delta forward, threading per-lane recurrent
    state exactly like ``_apply_lowrank``. Returns ``(y, new_state)``."""
    if isinstance(module, Sequential):
        if state is None:
            state = tuple(None for _ in module.modules)
        new_states = []
        for m, c, f, s in zip(module.modules, cp, fx, state):
            x, ns = _apply_trunk_delta(m, c, f, z, x, s)
            new_states.append(ns)
        out_state = tuple(new_states)
        if all(s is None for s in out_state):
            out_state = None
        return x, out_state
    if isinstance(module, Linear):
        return _linear_trunk(module, cp, fx, z, x), state
    if isinstance(module, Bias):
        return _bias_trunk(module, cp, fx, z, x), state
    if isinstance(module, RNN):
        return _rnn_trunk(module, cp, fx, z, x, state)
    if isinstance(module, LSTM):
        return _lstm_trunk(module, cp, fx, z, x, state)
    if hasattr(module, "trunk_delta_apply"):  # a module with a form of its own
        return module.trunk_delta_apply(cp, fx, z, x, state)
    # parameterless layer: batched apply is the plain apply
    return module.apply(cp, x, state)


def _apply_trunk_delta_blocked(module, cp, fx, z, obs, states, block: int):
    """The same forward with the LANE axis chunked into static blocks of
    ``block`` via ``lax.map`` — bounds the per-GEMM activation working set
    (the autotuner's trunk-blocking knob). Per-lane results are independent,
    so blocking changes scheduling, and values only by the summation order
    of a GEMM over ``block`` lanes instead of all of them (float32 rounding)."""
    n = obs.shape[0]
    nb = n // block

    def _split(t):
        return t.reshape((nb, block) + t.shape[1:])

    xs = (
        _split(obs),
        _split(z),
        None
        if states is None
        else jax.tree_util.tree_map(_split, states),
    )

    def _body(args):
        o, zz, ss = args
        return _apply_trunk_delta(module, cp, fx, zz, o, ss)

    y_b, ns_b = jax.lax.map(_body, xs)
    y = y_b.reshape((n,) + y_b.shape[2:])
    if ns_b is not None:
        ns_b = jax.tree_util.tree_map(
            lambda t: t.reshape((n,) + t.shape[2:]), ns_b
        )
    return y, ns_b


def trunk_delta_forward(
    policy,
    params: TrunkDeltaParamsBatch,
    prepared: Optional[_TrunkPrepared],
    obs,
    states,
) -> Tuple[jnp.ndarray, Any]:
    """Whole-population shared-trunk forward: ``obs`` (B, obs_dim) ->
    (B, act_dim). Mirrors :func:`lowrank_forward`'s contract, including the
    LOUD materializing fallback for unstructured modules."""
    module = policy.module
    if trunk_delta_supported(module):
        if prepared is None:
            prepared = prepare_trunk_delta(policy, params)
        z = prepared.coeffs
        block = int(prepared.trunk_block)
        n = obs.shape[0]
        if block > 0 and n > block and n % block == 0:
            return _apply_trunk_delta_blocked(
                module, prepared.center_tree, prepared.factors, z, obs, states, block
            )
        return _apply_trunk_delta(
            module, prepared.center_tree, prepared.factors, z, obs, states
        )
    warnings.warn(
        f"trunk-delta forward fell back to materializing the dense "
        f"({params.popsize}, {params.center.shape[-1]}) population: "
        f"{type(module).__name__} has no structured trunk-delta path "
        "(supported: Sequential stacks of Linear/Bias/RNN/LSTM/"
        "parameterless layers)",
        stacklevel=2,
    )
    dense = params.materialize()
    if states is None:
        return jax.vmap(lambda p, o: policy(p, o))(dense, obs)
    return jax.vmap(policy)(dense, obs, states)
