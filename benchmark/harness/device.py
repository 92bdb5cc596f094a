"""The device a number came from, its published peaks, and its memory.

Kept with the benchmark so that no later PR can move the denominators. The
program's own table (``evotorch_tpu/observability/report.py:DEVICE_PEAKS``) is
the original; this is the benchmark's copy.
"""

# Published peaks per chip, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. A device that is not here is
# an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class NoAccelerator(RuntimeError):
    pass


def peaks(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to"
            " benchmark/harness/device.py:DEVICE_PEAKS with their source"
        ) from None


def require_chips(chips, *, rehearse):
    """``jax.devices()`` when the run may go on: a TPU with at least ``chips``
    devices, or (only under ``--rehearse``) the CPU with as many virtual
    devices."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise NoAccelerator(f"--rehearse runs on the CPU, jax picked {platform!r}")
    elif platform != "tpu":
        raise NoAccelerator(
            f"this cell is measured on a TPU; jax found {devices[0].device_kind!r}"
            " (--rehearse asks for a CPU run that measures nothing)"
        )
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, jax sees {len(devices)}")
    return devices


def device_record(devices, used):
    """The device as jax reports it, with the peak memory of the fullest of
    the ``used`` devices (``peak_bytes_in_use``: the allocator's high-water
    mark over the process's life; 0 where the backend reports none)."""
    peak = 0
    for device in used:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
