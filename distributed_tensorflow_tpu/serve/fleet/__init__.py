"""Multi-replica serving fleet: router, hot reload, per-shard KV pools.

``FleetRouter`` owns the public ``submit()`` over N :class:`Replica`
engines with load-aware dispatch (queue depth, slot occupancy, free KV
blocks) and sticky re-dispatch of sheds; ``CheckpointWatcher`` polls the
checkpoint directory and hot-swaps generation-tagged params without
dropping in-flight requests.  Per-shard paged KV pools live in the
scheduler/allocator layer (``per_shard_kv=True``).

Placement: the N replicas are N engines in ONE process (a chip belongs to one
process), each built on the mesh it is handed — ``serve.driver._make_fleet``
hands every replica the driver's whole mesh, so on one chip they share it and
on a four-chip host each replica spans all four; nothing here pins a replica
to device 0.  One replica per chip means one single-device mesh per engine.
"""

from distributed_tensorflow_tpu.serve.fleet.reload import CheckpointWatcher
from distributed_tensorflow_tpu.serve.fleet.router import (
    FleetRouter,
    Replica,
    replica_load_score,
)

__all__ = [
    "CheckpointWatcher",
    "FleetRouter",
    "Replica",
    "replica_load_score",
]
