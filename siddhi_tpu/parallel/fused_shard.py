"""Fused chains sharded over the device mesh: fuse + shard composed.

The annotation-era gates treated fusion and mesh sharding as rivals —
`@app:fuse` chains always ran single-device.  For all-filter chains
(stateless elementwise stages, no dense tail) the two compose exactly:
every stage's step is a per-row map with no cross-row state, so
splitting the BATCH axis over the mesh and psum-ing the emit count is
bit-identical to the single-device chain — each row's output depends
only on that row's lanes, and the emit materialization path already
orders rows by their batch position.

Stateful stages (running / sliding / dense tails) do NOT compose this
way: their state update order couples rows across the batch, and the
per-kind shard layouts of ``device_shard.py`` (group axis, replicated
ring) have no fused-chain formulation yet.  The cost model enumerates
those compositions and rejects them with a counted reason
(planner/costmodel.py), and the fusion planner falls back to the plain
single-device fused engine with a counted ``shardedFallbackReason``.

``ShardedFusedGraphEngine`` is a subclass, not a proxy: the runtime
(core/fused_graph.py FusedChainRuntime) and the deferred-emit path read
``graph.stages`` / ``graph.dense`` / ``graph.output_names`` / per-stage
snapshots directly, and filter stages carry EMPTY state dicts — so the
only seams are ``make_step`` (wrap the raw fused step in shard_map over
the batch axis) and ``_pad_batch`` (round the chunk width up to a
shard-count multiple).
"""

from __future__ import annotations

from typing import Callable, List

import numpy as np

from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.ops.device_query import _pow2
from siddhi_tpu.ops.fused_graph import FusedGraphEngine


class ShardedFusedGraphEngine(FusedGraphEngine):
    """An all-filter fused chain with its batch axis split over an
    N-device mesh; emit counts psum to one replicated scalar so the
    async-emit count gate is unchanged."""

    #: cycle-tracer span label (engine_kind of the single-device chain
    #: is implicit 'fused'; sharded dispatches must be distinguishable)
    engine_kind = "fused_shard"

    def __init__(self, stages: List, mesh, axis_name: str = "p"):
        for eng in stages:
            if eng.kind != "filter":
                raise SiddhiAppCreationError(
                    f"fuse+shard covers all-filter chains (stateless "
                    f"elementwise stages); stage kind '{eng.kind}' "
                    "couples rows through window state — single-device "
                    "fused engine used")
        super().__init__(stages, None, None)
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_shards = int(np.prod(mesh.devices.shape))

    def _pad_batch(self, n: int) -> int:
        B = _pow2(n)
        if B % self.n_shards:
            B = -(-B // self.n_shards) * self.n_shards
        return B

    def make_step(self) -> Callable:
        if self._fused_step is not None:
            return self._fused_step
        import jax
        from jax.sharding import PartitionSpec as P

        shard_map = jax.shard_map
        raw = self._build_fused()
        a = self.axis_name

        def sharded(states, buf):
            states2, emitmask, out, fwd, n_local = raw(states, buf)
            # one replicated count scalar for the async-emit gate
            total = jax.lax.psum(n_local, axis_name=a)
            return states2, emitmask, out, fwd, total

        # pytree-prefix specs: filter stages hold EMPTY state dicts
        # (nothing to place), the packed buffer shards along its batch
        # (second) axis as every output lane and mask does along its
        # first, and the count comes back replicated
        self._fused_step = jax.jit(shard_map(
            sharded,
            mesh=self.mesh,
            in_specs=(P(), P(None, a)),
            out_specs=(P(), P(a), P(a), P(a), P()),
        ), donate_argnums=(0,))
        return self._fused_step
