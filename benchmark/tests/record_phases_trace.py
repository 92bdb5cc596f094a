"""How ``data/phases_1chip.xplane.pb`` was made (on the chip).

``python benchmark/tests/record_phases_trace.py <out_dir>`` runs two small but
real generations under the harness's own recording and its two host spans:
``VecNE("cartpole", ...)`` + ``PGPE`` (dense) + ``searcher.step()``, popsize 16,
8 counted steps a lane. The evaluation is ``jit_run_vectorized_rollout``;
``ask`` dispatches ``jit_evotorch_tpu_ask_sample``, ``grad``
``jit_evotorch_tpu_grad_grads``, ``evaluate`` the two best/worst programs beside
the rollout, and the dense update, ``nanmean`` and the counters are eager ops
without a name. ``test_phases.py`` reduces the recording with
``harness/phases.py`` and checks the result against figures read off a plain
listing of the recording's ``XLA Modules`` events.

The profiler also writes a plane ``/host:metadata`` with the HLO of every
program the process compiled (0.7 MB here, of 0.94); no reader opens it, so the
recording is kept without it (``without_plane``: the file is a sequence of
length-prefixed planes, protobuf field 1, and a plane's name is its field 2).
"""

import glob
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark.harness import trace  # noqa: E402
from evotorch_tpu.algorithms import PGPE  # noqa: E402
from evotorch_tpu.neuroevolution import VecNE  # noqa: E402

GENERATIONS = 2
WARMUP = 3


def varint(raw, at):
    value, shift = 0, 0
    while True:
        byte = raw[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def without_plane(raw, name):
    """The serialized ``XSpace`` without its planes called ``name``."""
    out, at = bytearray(), 0
    while at < len(raw):
        start = at
        tag, at = varint(raw, at)
        if tag & 7 != 2:
            raise ValueError(f"a top-level field that is not length-prefixed: tag {tag}")
        size, at = varint(raw, at)
        body, at = raw[at : at + size], at + size
        if tag >> 3 == 1 and b"\x12" + bytes([len(name)]) + name.encode() in body[: len(name) + 16]:
            continue
        out += raw[start:at]
    return bytes(out)


def main(out_dir):
    problem = VecNE(
        "cartpole",
        "Linear(obs_length, 16) >> Tanh() >> Linear(16, act_length)",
        eval_mode="budget",
        episode_length=8,
        seed=1,
    )
    searcher = PGPE(
        problem, popsize=16, center_learning_rate=0.1, stdev_learning_rate=0.1, stdev_init=0.1
    )

    def generation():
        with jax.profiler.TraceAnnotation("bench.generation"):
            searcher.step()
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(searcher.population.evals)

    for _ in range(WARMUP):
        generation()
    with tempfile.TemporaryDirectory(dir=out_dir) as trace_dir:
        with trace.recording(trace_dir):
            for _ in range(GENERATIONS):
                generation()
        (found,) = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
        with open(found, "rb") as f:
            raw = f.read()
    with open(os.path.join(out_dir, "phases_1chip.xplane.pb"), "wb") as f:
        f.write(without_plane(raw, "/host:metadata"))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
