"""Plain reference: the tanh MLP policy, ``tanh(x @ W + b)`` layer by layer.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul runs in
lower precision without it), no library code. It follows the network string of
the configuration, ``Linear(obs, h1) >> Tanh() >> ... >> Linear(hk, act)``:
tanh after every layer but the last.

The flat parameter layout is the library's (``jax.flatten_util.ravel_pytree``
over a tuple of per-layer dicts, keys sorted): for each Linear layer in order,
its ``bias`` (out,) and then its ``weight`` (out, in), row-major. If the
library ever lays parameters out otherwise, the comparison fails, as it should.
"""

import jax
import jax.numpy as jnp


def sizes(config):
    """The layer sizes of a configuration file: observation, hidden..., action."""
    return [
        int(config["observation_size"]),
        *map(int, config["hidden_sizes"]),
        int(config["action_size"]),
    ]


def parameter_count(sizes):
    return sum(n_out + n_out * n_in for n_in, n_out in zip(sizes[:-1], sizes[1:]))


def unflatten(flat, sizes):
    layers, at = [], 0
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        bias = flat[at : at + n_out]
        at += n_out
        weight = flat[at : at + n_out * n_in].reshape(n_out, n_in)
        at += n_out * n_in
        layers.append((weight, bias))
    if at != flat.shape[0]:
        raise ValueError(f"{flat.shape[0]} parameters given, the sizes {sizes} take {at}")
    return layers


def forward(flat, observation, sizes):
    """One observation through one parameter vector, float32."""
    x = observation.astype(jnp.float32)
    layers = unflatten(flat.astype(jnp.float32), sizes)
    with jax.default_matmul_precision("highest"):
        for i, (weight, bias) in enumerate(layers):
            x = x @ weight.T + bias
            if i < len(layers) - 1:
                x = jnp.tanh(x)
    return x
