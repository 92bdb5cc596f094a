"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout. One run is one new process: it sets the cell
up (imports, backend, compile or cache load, warm-up generations, the
reference check), measures whole generations for ``--seconds`` seconds and at
least three (``--trace 0``: the end-to-end metrics) or traces a few
(``--trace 1``: the per-layer metrics), checks the outputs, and prints one JSON object as the last
line of its standard output. Without a TPU that holds the chips the cell asks
for it prints no result and exits non-zero; ``--rehearse`` is the explicit
request for a CPU run at a shrunken popsize, whose line names the device
``cpu``. See benchmark/README.md.
"""

import os
import sys
import time

T0 = time.perf_counter()  # process start, as near as python lets us see it

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # the library and `benchmark` come from THIS checkout

from benchmark.harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], root=ROOT, t0=T0))
