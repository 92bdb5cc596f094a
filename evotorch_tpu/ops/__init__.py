"""Pallas TPU kernels for the hot ops.

The compute-heavy paths of this framework (population matmuls, rollouts) are
already MXU-shaped through XLA; these kernels cover the ops where explicit
VMEM scheduling wins:

- ``sample_symmetric_gaussian``: fused on-chip sampling of antithetic
  populations (PRNG + scale + interleave without HBM round-trips for the
  noise tensor) — the `ask` hot-op of PGPE at popsize 10k+.
- ``fused_centered_rank``: rank -> centered-utility transform fused over a
  fitness vector.

Every kernel has an XLA form (the default path); the sampling kernel's is
distributionally equivalent but not bit-identical (different PRNG streams).
Both kernels are compiled for a TPU v5e in tests/test_ops.py (the installed
libtpu compiles for a chip the host lacks) and run compiled on the chip,
against their XLA forms, by chip_smoke.py. Asked for off the chip they are an
error; the ranking kernel alone has an interpret mode, for the CPU tests, and
only when the caller passes ``interpret=True``.
"""

from .sampling import sample_symmetric_gaussian
from .ranking import fused_centered_rank

__all__ = ["sample_symmetric_gaussian", "fused_centered_rank"]
