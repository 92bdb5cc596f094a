"""evotorch_tpu: a TPU-native (JAX/XLA/pjit/shard_map) evolutionary
computation framework with the capabilities of EvoTorch (nnaisense/evotorch).

Design stance (SURVEY.md §7): the pure-functional ask/tell layer is the core —
pytree states, ``jit``/``vmap``/``shard_map`` everywhere — and thin stateful
wrappers reproduce the reference's OO ergonomics (Problem / SearchAlgorithm /
status / loggers) on top. Ray actors are replaced by SPMD over the device mesh.

Package entry parity: reference ``src/evotorch/__init__.py:29-38`` re-exports
``Problem, Solution, SolutionBatch, ProblemBoundEvaluator`` and subpackages.
"""

from . import algorithms, checkpoint, decorators, distributions, envs, logging, models, neuroevolution, operators, ops, optimizers, parallel, testing, tools, utils
from .core import Problem, ProblemBoundEvaluator, Solution, SolutionBatch, SolutionBatchPieces
from .decorators import expects_ndim, on_aux_device, on_cuda, on_device, pass_info, rowwise, vectorized

__all__ = [
    "algorithms",
    "Problem",
    "ProblemBoundEvaluator",
    "Solution",
    "SolutionBatch",
    "SolutionBatchPieces",
    "checkpoint",
    "decorators",
    "distributions",
    "envs",
    "models",
    "ops",
    "testing",
    "utils",
    "logging",
    "neuroevolution",
    "operators",
    "optimizers",
    "parallel",
    "tools",
    "expects_ndim",
    "on_aux_device",
    "on_cuda",
    "on_device",
    "pass_info",
    "rowwise",
    "vectorized",
]

__version__ = "0.1.0"
