"""Scale-out: device meshes, sharded NFA state, event routing.

The reference is a single-JVM library with no distributed backend
(SURVEY.md §2.3); its scale axis is key-partitioned parallelism
(``partition with (key of S)`` — per-key cloned state behind
ThreadLocals, partition/PartitionStreamReceiver.java:82-118).  The
TPU-native equivalent implemented here:

- the **partition axis is sharded over a ``jax.sharding.Mesh``** —
  per-key NFA/window/aggregator state rows live in HBM, each device
  owning a contiguous range of keys;
- the compiled step runs under ``jax.shard_map``: shard-local gathers/
  scatters (a shard owns its keys, so the hot path needs **no
  cross-device collectives**), with ``psum``/``all_gather`` only for
  global match counts / global emission;
- events are **routed host-side to their owning shard** (the DCN-ingest
  analog: multi-host deployments feed each host the key range it owns);
- multi-host initialization wraps ``jax.distributed`` (ICI within a
  slice, DCN across hosts).
"""

from siddhi_tpu.parallel.device_shard import ShardedDeviceQueryEngine
from siddhi_tpu.parallel.mesh import (
    ShardedPatternEngine,
    distributed_initialize,
    make_mesh,
    route_to_shards,
)

__all__ = [
    "ShardedDeviceQueryEngine",
    "ShardedPatternEngine",
    "distributed_initialize",
    "make_mesh",
    "route_to_shards",
]
