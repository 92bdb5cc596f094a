"""Functional neural-network layers for evolvable policies.

Parity: reference ``neuroevolution/net/layers.py`` (568 LoC) — utility layers
``Clip, Bin, Slice, Round, Apply`` (``layers.py:24-159``), **single-step**
RNN/LSTM cells with explicit hidden state (``layers.py:161-281``),
``FeedForwardNet`` (``layers.py:283-374``), ``StructuredControlNet``
(``layers.py:377-467``), ``LocomotorNet`` (``layers.py:470-568``).

TPU-first design: instead of torch ``nn.Module`` objects with implicit
parameter storage, every layer here is a lightweight *combinator* with three
pure methods::

    params = layer.init(key)          # parameter pytree
    state  = layer.initial_state()    # recurrent-state pytree (None if stateless)
    y, new_state = layer.apply(params, x, state)

Composition uses ``>>`` exactly like the reference's ``str_to_net`` DSL.
Because apply is pure, policies vmap over both population (batched params) and
environments (batched observations) natively — what the reference builds from
``torch.func.functional_call`` + vmap (``net/functional.py:46-259``).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Module",
    "Sequential",
    "Linear",
    "Bias",
    "Apply",
    "Tanh",
    "ReLU",
    "Sigmoid",
    "Softmax",
    "Clip",
    "Bin",
    "Slice",
    "Round",
    "RNN",
    "LSTM",
    "FeedForwardNet",
    "StructuredControlNet",
    "LocomotorNet",
]


class Module:
    """Base combinator."""

    def init(self, key) -> Any:
        return ()

    def initial_state(self) -> Any:
        return None

    def apply(self, params, x, state=None) -> Tuple[jnp.ndarray, Any]:
        raise NotImplementedError

    def reset_state(self, state: Any, mask: jnp.ndarray) -> Any:
        """The lane-batched ``state`` with the lanes in ``mask`` back at
        their start (an episode ended there). The default zeroes those lanes'
        rows of every leaf; a module whose state is large, or not all
        per-lane, brings its own."""
        return zero_rows(state, mask)

    @property
    def is_stateful(self) -> bool:
        return self.initial_state() is not None

    def __rshift__(self, other: "Module") -> "Sequential":
        mine = list(self.modules) if isinstance(self, Sequential) else [self]
        theirs = list(other.modules) if isinstance(other, Sequential) else [other]
        return Sequential(mine + theirs)

    def __call__(self, params, x, state=None):
        return self.apply(params, x, state)


def zero_rows(tree: Any, mask: jnp.ndarray) -> Any:
    """Zero the rows of every leaf where ``mask`` is True."""

    def zero(leaf):
        m = mask.reshape(mask.shape + (1,) * (leaf.ndim - mask.ndim))
        return jnp.where(m, jnp.zeros_like(leaf), leaf)

    return jax.tree_util.tree_map(zero, tree)


class Sequential(Module):
    """Sequence of layers threading hidden state through the stateful ones —
    the analog of the reference's ``net/multilayered.py`` container."""

    def __init__(self, modules: Sequence[Module]):
        self.modules = list(modules)

    def init(self, key):
        keys = jax.random.split(key, max(len(self.modules), 1))
        return tuple(m.init(k) for m, k in zip(self.modules, keys))

    def initial_state(self):
        states = tuple(m.initial_state() for m in self.modules)
        if all(s is None for s in states):
            return None
        return states

    def apply(self, params, x, state=None):
        if state is None:
            state = tuple(m.initial_state() for m in self.modules)
        new_states = []
        for m, p, s in zip(self.modules, params, state):
            x, ns = m.apply(p, x, s)
            new_states.append(ns)
        out_state = tuple(new_states)
        if all(s is None for s in out_state):
            out_state = None
        return x, out_state

    def reset_state(self, state, mask):
        if state is None:
            return None
        return tuple(
            None if s is None else m.reset_state(s, mask)
            for m, s in zip(self.modules, state)
        )

    def __repr__(self):
        return " >> ".join(repr(m) for m in self.modules)


class FrozenModule(Module):
    """A module with its parameters baked in: ``init`` returns an empty
    parameter pytree and ``apply`` ignores the params argument. Used by
    ``to_policy`` exports so a deployable policy carries its evolved weights
    (the analog of the reference's parameterized-net wrappers,
    ``gymne.py:646-672``)."""

    def __init__(self, module: Module, params):
        self._module = module
        self._params = params

    def init(self, key):
        return ()

    def initial_state(self):
        return self._module.initial_state()

    def apply(self, params, x, state=None):
        return self._module.apply(self._params, x, state)

    @property
    def wrapped_module(self) -> Module:
        return self._module

    @property
    def wrapped_params(self):
        return self._params

    def __repr__(self):
        return f"FrozenModule({self._module!r})"


class Linear(Module):
    """Dense layer; initialization mirrors torch's ``nn.Linear`` default
    (uniform +-1/sqrt(fan_in)), keeping evolved-policy scales comparable to
    the reference."""

    def __init__(self, in_features: int, out_features: int, bias: bool = True):
        self.in_features = int(in_features)
        self.out_features = int(out_features)
        self.bias = bool(bias)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        bound = 1.0 / math.sqrt(self.in_features)
        W = jax.random.uniform(
            k1, (self.out_features, self.in_features), minval=-bound, maxval=bound
        )
        if self.bias:
            b = jax.random.uniform(k2, (self.out_features,), minval=-bound, maxval=bound)
            return {"weight": W, "bias": b}
        return {"weight": W}

    def apply(self, params, x, state=None):
        y = x @ params["weight"].T
        if self.bias:
            y = y + params["bias"]
        return y, state

    def __repr__(self):
        return f"Linear({self.in_features}, {self.out_features}, bias={self.bias})"


class Bias(Module):
    """Learnable additive bias vector."""

    def __init__(self, num_features: int):
        self.num_features = int(num_features)

    def init(self, key):
        return {"bias": jnp.zeros(self.num_features)}

    def apply(self, params, x, state=None):
        return x + params["bias"], state

    def __repr__(self):
        return f"Bias({self.num_features})"


class Apply(Module):
    """Apply an arbitrary elementwise function, optionally with kwargs
    (reference ``layers.py:129-159``)."""

    def __init__(self, fn: Callable, **kwargs):
        self._fn = fn
        self._kwargs = kwargs

    def init(self, key):
        return ()

    def apply(self, params, x, state=None):
        return self._fn(x, **self._kwargs), state

    def __repr__(self):
        name = getattr(self._fn, "__name__", repr(self._fn))
        return f"Apply({name})"


class Tanh(Apply):
    def __init__(self):
        super().__init__(jnp.tanh)

    def __reduce__(self):
        # the stored jax ufunc object does not pickle by qualified name on
        # this jax; rebuilding from the (argless) constructor does — keeps
        # whole-searcher checkpoints (resilience.RunCheckpointer) working
        return (Tanh, ())

    def __repr__(self):
        return "Tanh()"


class ReLU(Apply):
    def __init__(self):
        super().__init__(jax.nn.relu)

    def __reduce__(self):
        return (ReLU, ())

    def __repr__(self):
        return "ReLU()"


class Sigmoid(Apply):
    def __init__(self):
        super().__init__(jax.nn.sigmoid)

    def __reduce__(self):
        return (Sigmoid, ())

    def __repr__(self):
        return "Sigmoid()"


class Softmax(Apply):
    def __init__(self, axis: int = -1):
        super().__init__(jax.nn.softmax, axis=axis)

    def __reduce__(self):
        return (Softmax, (self._kwargs.get("axis", -1),))

    def __repr__(self):
        return "Softmax()"


class Clip(Module):
    """Clip into [lb, ub] (reference ``layers.py:24-52``)."""

    def __init__(self, lb: float, ub: float):
        self.lb = float(lb)
        self.ub = float(ub)

    def init(self, key):
        return ()

    def apply(self, params, x, state=None):
        return jnp.clip(x, self.lb, self.ub), state

    def __repr__(self):
        return f"Clip({self.lb}, {self.ub})"


class Bin(Module):
    """Binarize: values map to lb or ub by sign (reference ``layers.py:55-88``)."""

    def __init__(self, lb: float, ub: float):
        self.lb = float(lb)
        self.ub = float(ub)

    def init(self, key):
        return ()

    def apply(self, params, x, state=None):
        return jnp.where(x <= 0, self.lb, self.ub), state

    def __repr__(self):
        return f"Bin({self.lb}, {self.ub})"


class Slice(Module):
    """Take ``x[..., from_index:to_index]`` (reference ``layers.py:91-121``)."""

    def __init__(self, from_index: int, to_index: int):
        self.from_index = int(from_index)
        self.to_index = int(to_index)

    def init(self, key):
        return ()

    def apply(self, params, x, state=None):
        return x[..., self.from_index : self.to_index], state

    def __repr__(self):
        return f"Slice({self.from_index}, {self.to_index})"


class Round(Module):
    """Round to n decimal digits (reference ``layers.py:124-126``)."""

    def __init__(self, ndigits: int = 0):
        self.ndigits = int(ndigits)
        self._scale = 10.0**self.ndigits

    def init(self, key):
        return ()

    def apply(self, params, x, state=None):
        return jnp.round(x * self._scale) / self._scale, state

    def __repr__(self):
        return f"Round({self.ndigits})"


class RNN(Module):
    """Single-step Elman RNN cell with explicit hidden state in/out
    (reference ``layers.py:161-218``)."""

    def __init__(self, input_size: int, hidden_size: int, nonlinearity: str = "tanh"):
        self.input_size = int(input_size)
        self.hidden_size = int(hidden_size)
        if nonlinearity not in ("tanh", "relu"):
            raise ValueError(f"Unsupported nonlinearity: {nonlinearity}")
        self.nonlinearity = nonlinearity

    def init(self, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        bound = 1.0 / math.sqrt(self.hidden_size)
        u = lambda k, shape: jax.random.uniform(k, shape, minval=-bound, maxval=bound)  # noqa: E731
        return {
            "W_ih": u(k1, (self.hidden_size, self.input_size)),
            "W_hh": u(k2, (self.hidden_size, self.hidden_size)),
            "b_ih": u(k3, (self.hidden_size,)),
            "b_hh": u(k4, (self.hidden_size,)),
        }

    def initial_state(self):
        return jnp.zeros(self.hidden_size)

    def apply(self, params, x, state=None):
        if state is None:
            state = jnp.zeros(x.shape[:-1] + (self.hidden_size,), dtype=x.dtype)
        pre = (
            x @ params["W_ih"].T
            + params["b_ih"]
            + state @ params["W_hh"].T
            + params["b_hh"]
        )
        h = jnp.tanh(pre) if self.nonlinearity == "tanh" else jax.nn.relu(pre)
        return h, h

    def __repr__(self):
        return f"RNN({self.input_size}, {self.hidden_size})"


class LSTM(Module):
    """Single-step LSTM cell with explicit (h, c) state
    (reference ``layers.py:221-281``)."""

    def __init__(self, input_size: int, hidden_size: int):
        self.input_size = int(input_size)
        self.hidden_size = int(hidden_size)

    def init(self, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        bound = 1.0 / math.sqrt(self.hidden_size)
        u = lambda k, shape: jax.random.uniform(k, shape, minval=-bound, maxval=bound)  # noqa: E731
        return {
            "W_ih": u(k1, (4 * self.hidden_size, self.input_size)),
            "W_hh": u(k2, (4 * self.hidden_size, self.hidden_size)),
            "b_ih": u(k3, (4 * self.hidden_size,)),
            "b_hh": u(k4, (4 * self.hidden_size,)),
        }

    def initial_state(self):
        return (jnp.zeros(self.hidden_size), jnp.zeros(self.hidden_size))

    def apply(self, params, x, state=None):
        if state is None:
            h = jnp.zeros(x.shape[:-1] + (self.hidden_size,), dtype=x.dtype)
            c = jnp.zeros(x.shape[:-1] + (self.hidden_size,), dtype=x.dtype)
        else:
            h, c = state
        gates = x @ params["W_ih"].T + params["b_ih"] + h @ params["W_hh"].T + params["b_hh"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        g = jnp.tanh(g)
        o = jax.nn.sigmoid(o)
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return h, (h, c)

    def __repr__(self):
        return f"LSTM({self.input_size}, {self.hidden_size})"


class FeedForwardNet(Module):
    """MLP from ``(size, activation)`` layer specs
    (reference ``layers.py:283-374``)."""

    LengthActTuple = Tuple[int, Callable]

    def __init__(self, input_size: int, layers: Sequence):
        self.input_size = int(input_size)
        modules = []
        in_size = self.input_size
        for layer in layers:
            if isinstance(layer, (tuple, list)):
                size, act = (layer[0], layer[1]) if len(layer) >= 2 else (layer[0], None)
            else:
                size, act = layer, None
            modules.append(Linear(in_size, int(size)))
            if act is not None:
                modules.append(act if isinstance(act, Module) else Apply(act))
            in_size = int(size)
        self._seq = Sequential(modules)

    def init(self, key):
        return self._seq.init(key)

    def apply(self, params, x, state=None):
        return self._seq.apply(params, x, state)

    def __repr__(self):
        return f"FeedForwardNet({self._seq!r})"


def tanh_mlp(input_size: int, output_size: int, hidden: Sequence) -> Module:
    """The ``Linear >> Tanh >> ... >> Linear`` policy stack the program
    ledger's gate-shape programs, the autotuner, ``chip_smoke.py`` and the
    examples share — ONE builder, so the architecture the perf gate
    measures cannot drift from the one the chip runs."""
    sizes = [int(h) for h in hidden]
    if not sizes:
        return Linear(int(input_size), int(output_size))
    net = Linear(int(input_size), sizes[0])
    for a, b in zip(sizes, sizes[1:] + [None]):
        net = net >> Tanh()
        net = net >> Linear(a, b if b is not None else int(output_size))
    return net


class StructuredControlNet(Module):
    """Structured Control Net (Srouji, Zhang, Salakhutdinov 2018): the sum of
    a linear module and a nonlinear MLP module
    (reference ``layers.py:377-467``)."""

    def __init__(
        self,
        *,
        in_features: int,
        out_features: int,
        num_layers: int,
        hidden_size: int,
        bias: bool = True,
        nonlinearity: Callable = jnp.tanh,
    ):
        self.in_features = int(in_features)
        self.out_features = int(out_features)
        self._linear = Linear(self.in_features, self.out_features, bias=bias)
        modules = []
        in_size = self.in_features
        for _ in range(int(num_layers)):
            modules.append(Linear(in_size, int(hidden_size), bias=bias))
            modules.append(Apply(nonlinearity))
            in_size = int(hidden_size)
        modules.append(Linear(in_size, self.out_features, bias=bias))
        self._nonlinear = Sequential(modules)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"linear": self._linear.init(k1), "nonlinear": self._nonlinear.init(k2)}

    def apply(self, params, x, state=None):
        y1, _ = self._linear.apply(params["linear"], x)
        y2, _ = self._nonlinear.apply(params["nonlinear"], x)
        return y1 + y2, state

    def __repr__(self):
        return f"StructuredControlNet(in={self.in_features}, out={self.out_features})"


class LocomotorNet(Module):
    """Locomotor Net (Liu, Ostrow, Srouji et al.): linear module plus a
    sinusoidal nonlinear module ``sum_i sin(Wx + b) * amplitude``
    (reference ``layers.py:470-568``)."""

    def __init__(self, *, in_features: int, out_features: int, bias: bool = True, num_sinusoids: int = 16):
        self.in_features = int(in_features)
        self.out_features = int(out_features)
        self.num_sinusoids = int(num_sinusoids)
        self._linear = Linear(self.in_features, self.out_features, bias=bias)
        self._sinusoids = [
            Linear(self.in_features, self.out_features, bias=bias)
            for _ in range(self.num_sinusoids)
        ]

    def init(self, key):
        keys = jax.random.split(key, self.num_sinusoids + 2)
        return {
            "linear": self._linear.init(keys[0]),
            "sinusoids": tuple(m.init(k) for m, k in zip(self._sinusoids, keys[1:])),
            "amplitudes": jax.random.normal(keys[-1], (self.num_sinusoids,)) * 0.1,
        }

    def apply(self, params, x, state=None):
        y, _ = self._linear.apply(params["linear"], x)
        for i, m in enumerate(self._sinusoids):
            s, _ = m.apply(params["sinusoids"][i], x)
            y = y + jnp.sin(s) * params["amplitudes"][i]
        return y, state

    def __repr__(self):
        return f"LocomotorNet(in={self.in_features}, out={self.out_features}, S={self.num_sinusoids})"
