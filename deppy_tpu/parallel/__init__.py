"""Device-mesh scale-out: batch sharding and clause sharding.

The reference has no distributed runtime at all (SURVEY.md §2.7) — its only
concurrency is two TODO comments and controller leader election, which
serializes work.  This package is therefore new, tpu-first design, with two
orthogonal parallelism axes:

  * **Batch axis** (:mod:`.mesh`) — N independent problems sharded over a
    ``jax.sharding.Mesh`` with ``NamedSharding``; XLA partitions the
    vmapped solve with zero steady-state cross-device traffic (the only
    collective is the implicit final gather of outcome tensors).  The
    fleet-scale path.
  * **Clause axis** (:mod:`.clause_shard`) — ONE problem's clause rows
    sharded over the mesh via ``shard_map``, replicated control flow, one
    OR all-gather of forced-literal masks per propagation round.  The
    giant-problem path (the honest analog of sequence parallelism,
    SURVEY.md §5).

Both scale to multi-host DCN fleets via ``jax.distributed`` initialization.
"""

from .clause_shard import clause_mesh, solve_one_sharded, solve_sharded
from .mesh import (BATCH_AXIS, batch_sharding, default_mesh,
                   initialize_distributed, mesh_devices_from_env,
                   replicated_sharding, serving_mesh, shard_batch)

__all__ = [
    "BATCH_AXIS", "batch_sharding", "default_mesh",
    "initialize_distributed", "mesh_devices_from_env",
    "replicated_sharding", "serving_mesh", "shard_batch",
    "clause_mesh", "solve_one_sharded", "solve_sharded",
]
