"""Parallel execution layer (L3): SPMD over the TPU device mesh.

This module replaces the reference's entire Ray actor layer
(``core.py:115-356`` ``EvaluationActor``, ``core.py:1977-2052``
``Problem._parallelize`` + ``ActorPool``, ``core.py:2762-3073`` distributed
gradient sampling, and the main<->actor sync protocol ``core.py:2239-2332``)
with GSPMD over a ``jax.sharding.Mesh`` (``docs/sharding.md``):

- population evaluation  -> the GLOBAL program jitted once, population rows
  pinned to the mesh with ``NamedSharding`` / ``with_sharding_constraint``;
  XLA's SPMD partitioner inserts the collectives;
- whole generations      -> ``make_generation_step``: ask -> rollout -> tell
  as ONE donated-buffer program (steady-state HBM = one generation's live
  set, verified by the program ledger);
- ES-gradient estimation -> global sample/rank/grad under GSPMD (the
  reference's single-process semantics at any popsize);
- obs-norm stat merging  -> the global program's cohort IS the mesh-global
  population — see ``neuroevolution.net.runningnorm``;
- multi-host             -> ``jax.distributed.initialize`` over DCN +
  ``dryrun_multihost`` (the 2-process CPU proof in tests/test_multihost.py).

For objectives that are *not* jax-traceable (arbitrary Python fitness
functions, classic gym rollouts), ``hostpool.HostEvaluatorPool`` provides the
reference's actor-pool behavior with plain worker processes.
"""

from .mesh import (
    MESH_AXES,
    default_mesh,
    device_count,
    make_mesh,
    mesh_label,
)
from .evaluate import (
    make_generation_step,
    make_sharded_evaluator,
    make_sharded_rollout_evaluator,
    make_training_span,
    population_spec,
    shard_population,
)
from .grad import make_sharded_grad_estimator
from .hostpool import HostEvaluatorPool
from .distributed import dryrun_multihost, init_distributed

__all__ = [
    "MESH_AXES",
    "default_mesh",
    "device_count",
    "make_mesh",
    "mesh_label",
    "make_generation_step",
    "make_sharded_evaluator",
    "make_sharded_rollout_evaluator",
    "make_training_span",
    "population_spec",
    "shard_population",
    "make_sharded_grad_estimator",
    "HostEvaluatorPool",
    "init_distributed",
    "dryrun_multihost",
]
