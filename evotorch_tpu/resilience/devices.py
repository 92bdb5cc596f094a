"""The device edge: which backend a run is on, said once and never guessed.

A run that measures or claims the chip must be on the chip. There is no
fallback here: an explicit request for the CPU (``JAX_PLATFORMS=cpu`` or a
script's ``--cpu`` flag) gets the 8-virtual-device CPU the tests use, and
anything else requires an accelerator and fails without one. Every
``backend`` field a script prints comes from :func:`device_record`, i.e.
from ``jax.devices()[0]``, never from a flag.
"""

from __future__ import annotations

import os
from typing import List

__all__ = ["device_record", "require_devices", "setup_backend"]


def require_devices(*, accelerator: bool = True) -> List:
    """``jax.devices()``; raises when ``accelerator`` is required and the
    platform jax picked is the CPU."""
    import jax

    devices = jax.devices()
    if accelerator and devices[0].platform == "cpu":
        raise RuntimeError(
            "an accelerator is required but jax found only "
            f"{devices[0].device_kind!r} devices; ask for the CPU explicitly "
            "(JAX_PLATFORMS=cpu or --cpu) to run there"
        )
    return devices


def setup_backend(force_cpu: bool = False) -> bool:
    """Settle the backend before jax's first device use; returns ``use_cpu``.

    ``force_cpu`` or ``JAX_PLATFORMS=cpu`` is a request for the CPU with 8
    virtual devices (the mesh the test suite runs on). Otherwise an
    accelerator is required: :func:`require_devices` raises without one.
    """
    use_cpu = force_cpu or os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if use_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    require_devices(accelerator=not use_cpu)
    return use_cpu


def device_record() -> dict:
    """``{"platform", "kind", "count"}`` as jax reports them — the device a
    printed number came from."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
