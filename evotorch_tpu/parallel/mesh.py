"""Device-mesh helpers.

The mesh is the TPU analog of the reference's actor pool size
(``num_actors``, reference ``core.py:1302-1595``): instead of asking "how many
Ray actors", you ask "which mesh axes". The default is a 1-D mesh named
``"pop"`` over all local devices, used to shard the population axis; 2-D
``pop x model`` meshes add a model axis for sharding wide-policy parameters
(docs/sharding.md).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = [
    "MESH_AXES",
    "default_mesh",
    "device_count",
    "make_mesh",
    "mesh_label",
    "model_axis_size",
]

#: the named mesh axes of the parallel layer (docs/sharding.md): ``"pop"``
#: shards the population axis, ``"model"`` shards model parameters (wide
#: policies) — graftlint's axis-name checker validates collective /
#: PartitionSpec string literals against this declaration
MESH_AXES = ("pop", "model")


def device_count() -> int:
    return jax.device_count()


def default_mesh(axis_names: Sequence[str] = ("pop",), devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices."""
    if devices is None:
        devices = jax.devices()
    if len(axis_names) != 1:
        raise ValueError("default_mesh creates 1-D meshes; use make_mesh for N-D")
    return Mesh(np.asarray(devices), axis_names=tuple(axis_names))


def make_mesh(axis_shape: dict, devices=None) -> Mesh:
    """N-D mesh from ``{axis_name: size}``; e.g.
    ``make_mesh({"pop": 4, "model": 2})`` lays population-parallel shards over
    4 device groups with 2-way model sharding inside each."""
    if devices is None:
        devices = jax.devices()
    names = tuple(axis_shape.keys())
    shape = tuple(int(s) for s in axis_shape.values())
    total = int(np.prod(shape))
    if total > len(devices):
        raise ValueError(f"Mesh needs {total} devices, but only {len(devices)} are available")
    grid = np.asarray(devices[:total]).reshape(shape)
    return Mesh(grid, axis_names=names)


def mesh_label(mesh: Optional[Mesh]) -> str:
    """The canonical mesh-shape label used in timing-ledger / tuned-config
    cache keys (``observability.timings``): ``"none"`` for an unsharded
    evaluation, ``"pop8"`` for a 1-D 8-way pop mesh, ``"pop4.model2"`` for a
    2-D mesh, with a ``"hosts{n}."`` prefix under multi-host
    (``jax.distributed``). Size-1 axes are dropped — a ``(8, 1)``
    ``pop x model`` mesh lays out identically to a 1-D ``pop`` 8-mesh, so
    measurements transfer — and an all-1 mesh IS the unsharded layout
    (``"none"``). A schedule tuned at one label is never applied under
    another (ISSUE 13 satellite; a width tuned on the 1-D 8-mesh says
    nothing about a 2-D or multi-host layout)."""
    if mesh is None:
        return "none"
    parts = [f"{name}{size}" for name, size in mesh.shape.items() if int(size) > 1]
    label = ".".join(parts) if parts else "none"
    n_hosts = jax.process_count()
    if n_hosts > 1:
        label = f"hosts{n_hosts}.{label}"
    return label


def model_axis_size(mesh: Optional[Mesh]) -> int:
    """Size of the mesh's ``model`` axis, 1 when absent (or no mesh): the
    storage-sharding divisor for a trunk-delta population's L-sized trunk
    arrays (``parallel.evaluate._constrain_population``;
    docs/policies.md)."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])

