"""How the recorded traces beside the tests were made (on the chip).

``python benchmark/tests/record_trace.py <out_dir>`` runs three tiny
"generations" under the profiler with the same options and the same host
annotations as the harness, and leaves ``<out_dir>/<n>chip.xplane.pb``. Each
generation is one small jitted program (a matmul, a tanh and a sum; over
several devices the rows are sharded, so the sum is an all-reduce), a host
sleep of 2 ms inside ``evotorch_tpu.update`` (an idle gap with a known owner)
and a wait inside ``bench.block``. ``test_trace.py`` reduces the recordings
and checks the result against figures worked out independently.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out_dir):
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(devices, ("pop",))
    rows = NamedSharding(mesh, P("pop"))
    x = jax.device_put(jnp.ones((n * 256, 512), jnp.float32), rows)
    w = jax.device_put(jnp.full((512, 512), 0.01, jnp.float32), NamedSharding(mesh, P()))

    @jax.jit
    def tiny_generation(x, w):
        return jnp.tanh(x @ w).sum()

    tiny_generation(x, w).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    with tempfile.TemporaryDirectory(dir=out_dir) as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.generation"):
                with jax.profiler.TraceAnnotation("evotorch_tpu.update"):
                    time.sleep(0.002)
                with jax.profiler.TraceAnnotation("evotorch_tpu.evaluate"):
                    y = tiny_generation(x, w)
                with jax.profiler.TraceAnnotation("bench.block"):
                    y.block_until_ready()
        jax.profiler.stop_trace()
        (found,) = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
        shutil.copy(found, os.path.join(out_dir, f"{n}chip.xplane.pb"))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
