"""The evaluation program's device time, split into the policy forward and
the rest, from the trace alone.

The compiled generation has no named scopes yet, so the trace cannot say which
op belongs to which layer. What it does print is every op's HLO text, shapes
included, and the policy's per-lane weights have shapes nothing else in the
program has: the flat matrix ``dtype[lanes, parameters]`` and, for each layer,
``dtype[lanes, out, in]`` or ``dtype[lanes, out*in]``. An op of the evaluation
program whose result or operands have such a shape touches per-lane weights:
the slices and relayouts that cut the flat matrix up, and the matrix-vector
fusions that read the blocks. Those are the POLICY FORWARD (its bias adds and
tanh, a few percent of it, carry no such shape and stay with the rest); every
other op of the evaluation program is THE REST: the env substep with the eval
contract's bookkeeping and the observation statistics, which no shape tells
apart. Both are self times (a ``while`` op's time excludes its body's ops;
what is left of it is the loop's own overhead and counts with the rest), so
they add up to the program's busy time.

A control step is one execution of the loop body: the ops of the forward run
once in each, so the most-executed weight op counts the steps.

PERF.md section 5 summed the same ops by hand in PR 22; this is that sum. It
goes when the ``tracing`` issue gives the compiled generation named scopes.
"""

import re

CONTROL_FLOW = re.compile(r"\s(?:while|conditional|call)\(")  # carries every shape, computes nothing
HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def dtype_name(compute_dtype):
    """The session's ``compute_dtype`` (a jax dtype, or None for the library's
    float32) by name."""
    import numpy as np

    return "float32" if compute_dtype is None else np.dtype(compute_dtype).name


def weight_shape_pattern(weight_blocks, parameter_count, dtype):
    """Matches the HLO text of an op that touches per-lane weights."""
    trailing = {str(int(parameter_count))}
    for n_out, n_in in weight_blocks:
        trailing.add(f"{int(n_out)},{int(n_in)}")
        trailing.add(str(int(n_out) * int(n_in)))
    return re.compile(rf"\b{HLO_DTYPE[dtype]}\[\d+,(?:{'|'.join(sorted(trailing))})\]")


def split_ops(ops, pattern):
    """``ops``: ``{HLO text: [self seconds, executions]}`` of the evaluation
    program. Returns the forward's seconds, the rest's, and the control steps."""
    forward = rest = steps = 0.0
    for text, (seconds, executions) in ops.items():
        if pattern.search(text) and not CONTROL_FLOW.search(text):
            forward += seconds
            steps = max(steps, executions)
        else:
            rest += seconds
    return {"forward_s": forward, "rest_s": rest, "steps": steps}


def split_evaluation(run):
    """The split for a traced run whose session has a policy with per-lane
    weights (``weight_blocks``, ``parameter_count``, ``compute_dtype``); None
    where there is no device trace, no such session, or no weight op."""

    def compute():
        session = run.session
        blocks = getattr(session, "weight_blocks", None)
        if blocks is None or run.trace is None or not run.trace.planes:
            return None
        pattern = weight_shape_pattern(
            blocks, session.parameter_count, dtype_name(session.compute_dtype)
        )
        split = split_ops(run.trace.evaluation_ops(), pattern)
        return split if split["steps"] > 0 else None

    return run.memo("layers.split_evaluation", compute)


def policy_floor_ms(popsize, parameter_count, dtype, hbm_bytes_per_s, chips):
    """The least time one population-wide forward can take: every lane reads
    its OWN parameters once (observations and actions are under 1% of that),
    so the floor is popsize x parameters x bytes of the compute dtype over the
    bytes per second of the chips the population is spread over. The FLOP
    floor (2 x popsize x parameters over 197 TFLOP/s) is 50 times lower."""
    return 1e3 * popsize * parameter_count * DTYPE_BYTES[dtype] / (hbm_bytes_per_s * chips)
