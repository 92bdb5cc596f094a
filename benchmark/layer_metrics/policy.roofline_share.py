"""The least time the chips could take for one population-wide forward over the
time it takes inside the evaluation program (``policy.forward_ms``, from the
trace, relayout included). Every lane multiplies by its OWN weights, so the
forward is HBM-bound: harness/layers.py:policy_floor_ms over the published
bytes/s of harness/device.py."""

LAYER = "policy forward"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "env_steps_per_s"


def applies(workload):
    return LAYER in workload["layers"]


def measure(run):
    from benchmark.harness import device, layers

    split = layers.split_evaluation(run)
    if split is None:
        return None
    session = run.session
    floor_ms = layers.policy_floor_ms(
        run.popsize,
        session.parameter_count,
        layers.dtype_name(session.compute_dtype),
        device.peaks(run.device_record["kind"])["hbm_bytes_per_s"],
        len(session.devices),
    )
    return 100.0 * floor_ms * split["steps"] / (1e3 * split["forward_s"])
