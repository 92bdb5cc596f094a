"""The least time a chip could take for one control step's policy forward over
the time the ``policy_forward`` scope takes per control step
(``policy.forward_scope_ms``, harness/scopes.py). Every lane multiplies by its
OWN weights, so the forward is HBM-bound: the floor is ``floor_ms`` of the
lanes a control step runs on a chip (``scopes.lanes_per_step``: the
telemetry's executed lane-step slots over the traced control steps) at the
published bytes/s of harness/device.py. Not in a decoder's cell, which names
its forward's own layer (``lm forward``, ...): there the lanes share one
trunk, and that layer's readers floor it."""

LAYER = "policy forward"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    layers = workload["layers"]
    return LAYER in layers and not any(layer.endswith(" forward") and layer != LAYER for layer in layers)


def floor_ms(lanes, parameter_count, dtype_bytes, hbm_bytes_per_s):
    """Every one of ``lanes`` reads its own parameters once (observations and
    actions are under 1% of that); the FLOP floor, 2 x lanes x parameters
    over 197 TFLOP/s, is 50 times lower."""
    return 1e3 * lanes * parameter_count * dtype_bytes / hbm_bytes_per_s


def measure(run):
    import numpy as np

    from benchmark.harness import device, scopes

    forward_ms = scopes.per_step_ms(run, "policy_forward")
    lanes = scopes.lanes_per_step(run)
    if not forward_ms or lanes is None:
        return None
    session = run.session
    floor = floor_ms(
        lanes,
        session.parameter_count,
        np.dtype(session.compute_dtype or np.float32).itemsize,
        device.peaks(run.device_record["kind"])["hbm_bytes_per_s"],
    )
    return 100.0 * floor / forward_ms
