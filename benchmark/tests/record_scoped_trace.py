"""How ``data/scoped_1chip.xplane.pb`` and ``data/scoped_1chip.hlo.txt`` were
made (on the chip).

``python benchmark/tests/record_scoped_trace.py <out_dir>`` runs two tiny
"generations" under the harness's own recording (no HLO proto, so the trace
names its ops by bare HLO text) and leaves the trace beside the compiled
program's text, which carries the scope of every instruction. Each generation
runs one small jitted "evaluation": a doubling under ``rollout_edges``, a
``scan`` of five steps (a matmul and a tanh under ``policy_forward``, a sine
and an add under ``env_step``), and a sum that no scope names.
``test_scopes.py`` joins the two files by instruction name and checks the
result against figures read off a plain listing of the recording's events.
"""

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.harness import trace  # noqa: E402
from evotorch_tpu.observability.scopes import scope  # noqa: E402

STEPS = 5
GENERATIONS = 2


@jax.jit
def tiny_evaluation(x, w):
    def step(carry, _):
        with scope("policy_forward"):
            raw = jnp.tanh(carry @ w)
        with scope("env_step"):
            return jnp.sin(raw) + 0.5 * carry, None

    with scope("rollout_edges"):
        x = 2.0 * x
    out, _ = jax.lax.scan(step, x, None, length=STEPS)
    return out.sum()


def main(out_dir):
    x = jnp.ones((256, 512), jnp.float32)
    w = jnp.full((512, 512), 0.01, jnp.float32)
    tiny_evaluation(x, w).block_until_ready()
    with tempfile.TemporaryDirectory(dir=out_dir) as trace_dir:
        with trace.recording(trace_dir):
            for _ in range(GENERATIONS):
                with jax.profiler.TraceAnnotation("bench.generation"):
                    with jax.profiler.TraceAnnotation("evotorch_tpu.evaluate"):
                        y = tiny_evaluation(x, w)
                    with jax.profiler.TraceAnnotation("bench.block"):
                        y.block_until_ready()
        (found,) = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
        shutil.copy(found, os.path.join(out_dir, "scoped_1chip.xplane.pb"))
    with open(os.path.join(out_dir, "scoped_1chip.hlo.txt"), "w") as f:
        f.write(tiny_evaluation.lower(x, w).compile().as_text())


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
