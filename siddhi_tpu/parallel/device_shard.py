"""Device-query engine sharded over a mesh.

``ShardedDeviceQueryEngine`` wraps a stateful
:class:`siddhi_tpu.ops.device_query.DeviceQueryEngine` of any kind:

- ``running`` — per-group accumulator rows ([G, A] sum/cnt/min/...)
  laid out shard-major along the group axis with one scratch row per
  shard; events route host-side to their owning shard.
- ``tumbling`` (lengthBatch/timeBatch) — the same group-axis layout for
  the pane accumulators; pane open/close bookkeeping (``_pane_end``,
  lengthBatch fill counts) stays host-side on the base engine and is
  kept consistent by psum-ing the per-shard passing counts at every
  accumulate step, so both paths place boundaries identically.  Pane
  flushes run a shard-local flush step and ride the count-gated async
  emit queue as "flush" chunks — a zero-match pane transfers nothing.
- ``sliding`` (length/time) — the GLOBAL ring buffer cannot shard by
  key, so the window state is replicated and the batch axis is sharded
  instead: every shard advances the ring identically (cheap, O(B + W))
  while computing the O(B·W) window gather/reduction only for its
  contiguous block of output rows.
- ``keyed_sliding`` (partitioned length/time) — per-key [W] ring rows
  shard along the window-group (partition-key) axis, same shard-major
  bijection as the group axis.  minForever/maxForever accumulate per
  composed (key, group) id, which does not co-locate with the key
  axis, so that combination is rejected (the planner falls back to a
  single device and reports it).

Group/window-group ids intern host-side exactly as in the unsharded
engine; a round-robin bijection (``gid -> (gid % n_shards) *
rows_per_shard + gid // n_shards``) spreads sequentially-allocated ids
across shards so early ids don't pile onto shard 0.  Events route
host-side to their owning shard (:func:`route_to_shards`) — same-group
rows keep their relative order inside one shard bucket, so the step's
within-batch same-group masks are unaffected.

The wrapper exposes the engine's host surface (``process_batch``,
snapshots, purge, introspection) so ``DeviceQueryRuntime`` holds it
exactly like an unsharded engine, and every emission path is
bit-identical to the single-device engine's.

No reference analog: the reference scales group-by state with
ThreadLocal-keyed maps on one JVM (config/SiddhiAppContext.java:55-109).
"""

from __future__ import annotations

import logging
import math
from typing import Dict, Optional

import numpy as np

from siddhi_tpu.core.exceptions import (
    SiddhiAppCreationError,
    SiddhiAppRuntimeError,
)
from siddhi_tpu.core.ingest_stage import staged_put
from siddhi_tpu.parallel.mesh import _pow2, route_to_shards

log = logging.getLogger("siddhi_tpu.shard")

#: kinds the wrapper accepts ('filter' is stateless — there is nothing
#: to shard, and a single device already saturates on H2D transfer)
SHARDED_KINDS = ("running", "tumbling", "sliding", "keyed_sliding")


class ShardedDeviceQueryEngine:
    """A stateful DeviceQueryEngine with its windowed state sharded
    across the mesh (group axis, key axis, or batch axis — see the
    module docstring for the per-kind layout)."""

    #: cycle-tracer span label: sharded dispatches trace as 'shard' so
    #: mesh overlap is distinguishable from single-device cycles
    engine_kind = "shard"

    def __init__(self, engine, mesh, axis_name: str = "p"):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if engine.kind not in SHARDED_KINDS:
            raise SiddhiAppCreationError(
                f"mesh sharding of the device query engine covers the "
                f"{'/'.join(SHARDED_KINDS)} kinds; kind="
                f"'{engine.kind}' is stateless and runs single-device")
        if engine.kind == "tumbling" and engine.bare_attrs:
            raise SiddhiAppCreationError(
                "sharded tumbling: bare select attributes "
                f"{engine.bare_attrs} need the single-device engine's "
                "last-row bookkeeping; runs single-device")
        # the wrapper drives the per-pane sweep with its own steps
        engine.pane_batched = False
        host = engine.init_state_host()
        if engine.kind == "keyed_sliding" and (
                "acc_minf" in host or "acc_maxf" in host):
            raise SiddhiAppCreationError(
                "sharded keyed_sliding: minForever/maxForever accumulate "
                "per composed (key, group) id, which does not co-locate "
                "with the partition-key shard axis; runs single-device")
        self.engine = engine
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_shards = int(np.prod(mesh.devices.shape))
        # the sharded axis: window groups for keyed_sliding, groups for
        # running/tumbling; sliding replicates its global ring and
        # shards the batch axis instead (per_shard stays 0)
        if engine.kind == "keyed_sliding":
            axis_len, axis_what = engine.n_wgroups, "window groups"
        else:
            axis_len, axis_what = engine.n_groups, "groups"
        if engine.kind != "sliding" and axis_len % self.n_shards:
            # unreachable via @app:execution (the annotation parser
            # enforces partitions % devices == 0 at app creation);
            # guards direct-API construction
            raise SiddhiAppCreationError(
                f"{axis_len} {axis_what} not divisible by "
                f"{self.n_shards} shards")
        if engine.kind == "sliding":
            self.per_shard = 0
            self.rows_per_shard = 0
        else:
            self.per_shard = axis_len // self.n_shards
            self.rows_per_shard = self.per_shard + 1  # +1 scratch row
        # hot-pane flush batching: empty tumbling panes skip the
        # shard-mapped flush dispatch entirely (a zero-fill pane's
        # accumulators are already at their reset values, so the step
        # would be a state no-op emitting nothing) — a batch that jumps
        # K pane boundaries costs ONE dispatch, not K
        self.flush_skips = 0

        jnp = engine.jnp
        a = axis_name
        if engine.kind == "sliding":
            # replicated ring: every shard holds (and identically
            # advances) the full window state
            self.state_specs = {k: P() for k in host}
        else:
            self.state_specs = {
                k: P(a, *([None] * (v.ndim - 1))) for k, v in host.items()
            }
        specs = self.state_specs
        col_keys = list(engine.host_lane_cols({}, 0))
        out_names = [nm for kind, _v, nm in engine.out_spec
                     if kind == "expr"]
        shard_map = jax.shard_map
        self._P = P
        self._NamedSharding = NamedSharding
        self._jax = jax

        if engine.kind == "running":
            raw = engine.make_step(jit=False)

            def sharded_step(state, cols, ts, grp, valid):
                wgrp = jnp.zeros_like(grp)  # running kind ignores wgrp
                new_state, ov, out, n_local = raw(state, cols, ts, grp,
                                                  wgrp, valid)
                # count gate for the async emit pipeline: one replicated
                # scalar the host can fetch without touching the columns
                total = jax.lax.psum(n_local, axis_name=a)
                return new_state, ov, out, total

            self._step = jax.jit(shard_map(
                sharded_step,
                mesh=mesh,
                in_specs=(specs, {k: P(a) for k in col_keys},
                          P(a), P(a), P(a)),
                out_specs=(specs, P(a), {nm: P(a) for nm in out_names},
                           P()),
            ), donate_argnums=(0,))
        elif engine.kind == "keyed_sliding":
            raw = engine.make_step(jit=False)

            def sharded_kstep(state, cols, ts, grp, wgrp, valid):
                # wgrp is the routed LOCAL ring-row index; grp keeps the
                # global composed id (the step only ever compares grp
                # values for equality, never indexes state with them)
                new_state, ov, out, n_local = raw(state, cols, ts, grp,
                                                  wgrp, valid)
                total = jax.lax.psum(n_local, axis_name=a)
                return new_state, ov, out, total

            self._step = jax.jit(shard_map(
                sharded_kstep,
                mesh=mesh,
                in_specs=(specs, {k: P(a) for k in col_keys},
                          P(a), P(a), P(a), P(a)),
                out_specs=(specs, P(a), {nm: P(a) for nm in out_names},
                           P()),
            ), donate_argnums=(0,))
        elif engine.kind == "sliding":

            def sharded_sliding(state, cols, ts, grp, valid):
                # replicated inputs; each shard owns the contiguous
                # output-row block [r0, r0 + b_loc) of the O(B·W)
                # window reduction while the ring advance (replicated,
                # O(B + W)) is recomputed identically everywhere
                B = ts.shape[0]
                b_loc = B // self.n_shards  # host pads to a multiple
                env = engine._base_env(cols, ts, B)
                fmask = engine._filter_mask(env, valid)
                r0 = jax.lax.axis_index(a) * b_loc
                new_state, ov, out = engine._sliding_step(
                    state, env, fmask, ts, grp, B, r0=r0, nb=b_loc)
                n_local = jnp.sum((ov.astype(bool)).astype(jnp.int32))
                total = jax.lax.psum(n_local, axis_name=a)
                return new_state, ov, out, total

            self._step = jax.jit(shard_map(
                sharded_sliding,
                mesh=mesh,
                in_specs=(specs, {k: P() for k in col_keys},
                          P(), P(), P()),
                out_specs=(specs, P(a), {nm: P(a) for nm in out_names},
                           P()),
            ), donate_argnums=(0,))
        else:  # tumbling
            acc_raw = engine.make_acc_step(jit=False)

            def sharded_acc(state, cols, ts, grp, gkv, valid):
                new_state, n_pass = acc_raw(state, cols, ts, grp, gkv,
                                            valid)
                # the all-reduce that keeps host pane bookkeeping
                # (lengthBatch fill counts) consistent: every shard
                # contributes its local passing count
                total = jax.lax.psum(n_pass, axis_name=a)
                return new_state, total

            self._acc = jax.jit(shard_map(
                sharded_acc,
                mesh=mesh,
                in_specs=(specs, {k: P(a) for k in col_keys},
                          P(a), P(a), P(a), P(a)),
                out_specs=(specs, P()),
            ), donate_argnums=(0,))
            flush_raw = engine.make_flush_step(
                jit=False, n_rows=self.rows_per_shard)

            def sharded_flush(state):
                new_state, ov, out, n_match = flush_raw(state)
                total = jax.lax.psum(n_match, axis_name=a)
                return new_state, ov, out, total

            self._flush = jax.jit(shard_map(
                sharded_flush,
                mesh=mesh,
                in_specs=(specs,),
                out_specs=(specs, P(a), {nm: P(a) for nm in out_names},
                           P()),
            ), donate_argnums=(0,))

    # -- engine-surface proxy (host bookkeeping, snapshots, purge) ----------

    def __getattr__(self, name):
        return getattr(self.engine, name)

    # -- sharded state -------------------------------------------------------

    def _put(self, x, spec):
        # the shared staged_put owns the ingest.put fault site + the
        # bounded retry-with-backoff ladder (core/ingest_stage.py)
        return staged_put(
            x, self._NamedSharding(self.mesh, spec),
            faults=getattr(self, "faults", None),
            stats=getattr(self, "ingest_stats", None))

    def init_state(self):
        host = self.engine.init_state_host()
        if self.engine.kind == "sliding":
            return {k: self._put(np.asarray(v), self.state_specs[k])
                    for k, v in host.items()}
        n_rows = self.n_shards * self.rows_per_shard
        state = {}
        for k, v in host.items():
            arr = np.zeros((n_rows,) + v.shape[1:], dtype=v.dtype)
            arr[...] = v[0] if len(v) else 0  # per-row init is uniform
            state[k] = self._put(arr, self.state_specs[k])
        return state

    def put_state(self, host_state: Dict[str, np.ndarray]):
        """Numpy state (a snapshot) -> sharded device arrays.  For
        axis-sharded kinds the snapshot must carry THIS layout's row
        count — a snapshot taken under a different device count has a
        different shard-major bijection, and restoring it silently
        cross-wires groups.  The sliding kind's replicated state keeps
        the single-device layout and restores under any device count."""
        if self.engine.kind == "sliding":
            expect = {k: v.shape
                      for k, v in self.engine.init_state_host().items()}
            for k, v in host_state.items():
                shape = np.asarray(v).shape
                if k in expect and shape != expect[k]:
                    raise SiddhiAppCreationError(
                        f"sliding device-query snapshot '{k}' has shape "
                        f"{shape}; this query needs {expect[k]}")
        else:
            n_rows = self.n_shards * self.rows_per_shard
            for k, v in host_state.items():
                v = np.asarray(v)
                if v.shape[0] != n_rows:
                    raise SiddhiAppCreationError(
                        f"sharded device-query snapshot '{k}' has "
                        f"{v.shape[0]} rows; this {self.n_shards}-device "
                        f"layout needs {n_rows} — persist and restore "
                        "must use the same @app:execution devices count")
        return {
            k: self._put(np.asarray(v), self.state_specs[k])
            for k, v in host_state.items()
        }

    def _remap(self, gid: np.ndarray) -> np.ndarray:
        """Sequential id -> shard-major row id, round-robin across
        shards WITH the per-shard scratch row accounted for."""
        owner = gid % self.n_shards
        local = gid // self.n_shards
        return owner * self.rows_per_shard + local

    # -- host entry point (mirrors DeviceQueryEngine.process_batch) ---------

    def process_batch(self, state, cols: Dict[str, np.ndarray],
                      ts: np.ndarray,
                      part_keys: Optional[np.ndarray] = None):
        """Synchronous wrapper over the deferred path — one count-gated,
        coalesced fetch per call (mirrors DeviceQueryEngine)."""
        eng = self.engine
        state, pending = self.process_batch_deferred(state, cols, ts,
                                                     part_keys)
        if pending is not None and pending.resolve() == 0:
            pending = None
        if pending is None:
            eng.last_group_keys = (
                [] if eng.group_exprs and not eng.partition_mode else None)
            return state, eng._empty_cols(), np.empty(0, dtype=np.int64)
        from siddhi_tpu.core.emit_queue import fetch_coalesced

        out_cols, out_ts, keys = pending.materialize(
            fetch_coalesced(pending.device_arrays()))
        eng.last_group_keys = keys
        return state, out_cols, out_ts

    def process_batch_deferred(self, state, cols: Dict[str, np.ndarray],
                               ts: np.ndarray,
                               part_keys: Optional[np.ndarray] = None):
        """Async-emit entry point: the psum'd match count is the only
        scalar fetched here; match columns stay sharded on device until
        the pending-emit queue drains them (core/emit_queue.py)."""
        from siddhi_tpu.ops.device_query import (
            MAX_DEVICE_BATCH,
            DeferredDeviceEmit,
        )

        eng = self.engine
        ts = np.asarray(ts, dtype=np.int64)
        n = len(ts)
        if n == 0:
            return state, None
        pk_all = np.asarray(part_keys) if part_keys is not None else None
        pending = DeferredDeviceEmit(eng)
        if eng.kind == "tumbling":
            # no [B, B] batch masks: pane sweeps segment the batch
            # themselves (same contract as the unsharded engine)
            state = self._deferred_chunk(state, cols, ts, pk_all, pending)
            return state, (pending if pending.chunks else None)
        # same chunk bound as the unsharded engine: the per-event steps
        # build [B, B] same-group masks per shard
        for i in range(0, n, MAX_DEVICE_BATCH):
            sl = slice(i, i + MAX_DEVICE_BATCH)
            state = self._deferred_chunk(
                state, {k: np.asarray(v)[sl] for k, v in cols.items()},
                ts[sl], pk_all[sl] if pk_all is not None else None, pending)
        return state, (pending if pending.chunks else None)

    def _deferred_chunk(self, state, cols, ts, pk, pending):
        eng = self.engine
        n = len(ts)
        if eng.base_ts is None:
            eng.base_ts = int(ts[0]) - 1
        rel64 = ts - eng.base_ts
        if int(rel64.max()) >= eng._REL_LIMIT:
            # the engine's re-anchor: shifts live window entries / the
            # open pane boundary with the new anchor (replicated or
            # row-sharded arrays shift elementwise either way)
            state, rel64 = eng._re_anchor(state, rel64)
        rel = rel64.astype(np.int32)
        now = int(ts.max())
        if eng.partition_mode:
            if pk is None:
                raise SiddhiAppRuntimeError(
                    "partitioned device query needs per-row partition keys")
            # wgroup interning runs unconditionally: _wgrp_last drives
            # the idle-key purge even when composed groups carry state
            wgrp = eng._intern_wgroups(pk, now)
            grp = (eng._intern_groups(cols, ts, n, pk=pk, now=now)
                   if eng.group_exprs else wgrp)
        else:
            wgrp = None
            grp = eng._intern_groups(cols, ts, n)
        fi = getattr(eng, "faults", None)
        if eng.kind == "tumbling":
            return self._tumbling_chunk(state, cols, rel, grp, n, pending)
        if eng.kind == "sliding":
            return self._sliding_chunk(state, cols, rel, grp, n, ts,
                                       pending, fi)
        lanes = eng.host_lane_cols(cols, n)
        P, a = self._P, self.axis_name
        if eng.kind == "keyed_sliding":
            # route by the OWNING ring row (the partition key); the
            # composed group id rides along as a pseudo-lane column
            lanes["__grp"] = grp.astype(np.int32)
            local, rcols, rts, valid, pos = route_to_shards(
                self.n_shards, self.per_shard, self._route_part(wgrp),
                lanes, rel)
            rgrp = rcols.pop("__grp")
            args = (
                {k: self._put(v, P(a)) for k, v in rcols.items()},
                self._put(rts.astype(np.int32), P(a)),
                self._put(rgrp, P(a)),
                self._put(local, P(a)),
                self._put(valid, P(a)),
            )
        else:
            local, rcols, rts, valid, pos = route_to_shards(
                self.n_shards, self.per_shard, self._route_part(grp),
                lanes, rel)
            args = (
                {k: self._put(v, P(a)) for k, v in rcols.items()},
                self._put(rts.astype(np.int32), P(a)),
                self._put(local, P(a)),
                self._put(valid, P(a)),
            )
        if fi is not None:
            fi.check("step.shard")
        state, ov, out, total = self._step(state, *args)
        # count gate deferred: the psum'd scalar stays on device until
        # DeferredDeviceEmit.resolve() (driven by the ingest stage);
        # group ids are kept host-side so resolve can capture key values
        # before any gid could be recycled (purges flush the stage first)
        pending.chunks.append({
            "kind": "device", "ov": ov, "out": dict(out),
            "names": list(out), "n": n, "pos": pos, "count": total,
            "gids": (grp.copy() if eng.group_exprs else None),
            "ts": ts, "cols": {k: np.asarray(v) for k, v in cols.items()},
        })
        return state

    def _sliding_chunk(self, state, cols, rel, grp, n, ts, pending, fi):
        """Batch-axis sharded sliding step: pad the batch (pow-2, then
        to a shard-count multiple) and replicate it; the step returns
        ov/out as the concatenation of per-shard row blocks — the
        original row order, so no slot map is needed."""
        eng = self.engine
        B = _pow2(n)
        B *= self.n_shards // math.gcd(B, self.n_shards)
        valid = np.zeros(B, dtype=bool)
        valid[:n] = True
        lanes = eng.host_lane_cols(cols, n)
        c = {}
        for k, v in lanes.items():
            col = np.zeros(B, dtype=v.dtype)
            col[:n] = v
            c[k] = col
        t = np.zeros(B, dtype=np.int32)
        t[:n] = rel[:n]
        g = np.zeros(B, dtype=np.int32)
        g[:n] = grp[:n]
        P = self._P
        args = (
            {k: self._put(v, P()) for k, v in c.items()},
            self._put(t, P()),
            self._put(g, P()),
            self._put(valid, P()),
        )
        if fi is not None:
            fi.check("step.shard")
        state, ov, out, total = self._step(state, *args)
        pending.chunks.append({
            "kind": "device", "ov": ov, "out": dict(out),
            "names": list(out), "n": n, "count": total,
            "gids": (grp[:n].copy() if eng.group_exprs else None),
            "ts": ts, "cols": {k: np.asarray(v) for k, v in cols.items()},
        })
        return state

    # -- tumbling panes ------------------------------------------------------

    def _tumbling_chunk(self, state, cols, rel, grp, n, pending):
        """Drive the base engine's pane sweep (host ``_pane_end`` /
        fill-count bookkeeping, shared code) with the sharded
        accumulate/flush steps; closed panes become deferred "flush"
        chunks on the async emit queue."""
        eng = self.engine

        def flush_pane(st, when):
            return self._flush_pane_chunk(st, when, pending)

        return eng._pane_sweep(state, cols, rel, grp, n,
                               self._acc_segment, flush_pane)

    def _acc_segment(self, state, cols, rel, grp, idx):
        """Sharded analog of the engine's ``_acc_segment``: route the
        segment's events (and their numeric group-key values, as
        pseudo-lane columns) to the owning shards, run the shard-local
        accumulate step, and return the PSUM'd passing count — the
        all-reduce that keeps lengthBatch pane fills consistent."""
        eng = self.engine
        n = len(idx)
        lanes = eng.host_lane_cols(
            {k: np.asarray(v)[idx] for k, v in cols.items()}, n)
        K = max(len(eng._numeric_group_keys), 1)
        gkv = eng._gk_vals(grp[idx], n)  # [n, K] float32
        for ki in range(K):
            lanes[f"__gk{ki}"] = gkv[:, ki]
        local, rcols, rts, valid, pos = route_to_shards(
            self.n_shards, self.per_shard, self._route_part(grp[idx]),
            lanes, rel[idx])
        gkv_r = np.stack([rcols.pop(f"__gk{ki}") for ki in range(K)],
                         axis=1)
        P, a = self._P, self.axis_name
        args = (
            {k: self._put(v, P(a)) for k, v in rcols.items()},
            self._put(rts.astype(np.int32), P(a)),
            self._put(local, P(a)),
            self._put(np.ascontiguousarray(gkv_r, dtype=np.float32),
                      P(a)),
            self._put(valid, P(a)),
        )
        fi = getattr(eng, "faults", None)
        if fi is not None:
            fi.check("step.shard")
        state, total = self._acc(state, *args)
        # blocking count fetch — the same synchronization point the
        # single-device _acc_segment has (pane placement needs it);
        # explicit device_get so transfer_guard('disallow') stays happy
        import jax

        return state, int(jax.device_get(total))

    def _flush_pane_chunk(self, state, when, pending):
        """Close the open pane: shard-local flush step, result deferred
        as a "flush" chunk (count-gated — an all-empty pane's columns
        are never transferred)."""
        eng = self.engine
        if eng.window_name == "timeBatch" and not eng._pane_fill:
            # no passing event touched this pane: every accumulator is
            # already at its reset value and the flush would emit zero
            # rows — skip the device dispatch, keep host bookkeeping.
            # timeBatch only: its fill count is final when the pane
            # closes, while lengthBatch increments AFTER the closing
            # flush (and only ever closes full panes anyway)
            self.flush_skips += 1
            return state
        fi = getattr(eng, "faults", None)
        if fi is not None:
            fi.check("step.shard")
        state, ov, out, total = self._flush(state)
        pending.chunks.append({
            "kind": "flush", "ov": ov, "out": dict(out),
            "names": list(out), "count": total, "stamp": int(when),
            "rows_per_shard": self.rows_per_shard,
            "n_shards": self.n_shards,
        })
        return state

    def flush_due(self, state, now: int):
        """Timer-driven pane flush: close every pane whose boundary <=
        now with the shard-local flush step (the base engine's loop
        would trace the full-G flush over shard-major rows).  Resolves
        synchronously — the runtime's ``fire`` emits the result
        immediately."""
        eng = self.engine
        if eng.kind != "tumbling":
            return self.engine.flush_due(state, now)
        from siddhi_tpu.ops.device_query import DeferredDeviceEmit

        pending = DeferredDeviceEmit(eng)
        while True:
            w = eng.pane_wakeup()
            if w is None or w > now:
                break
            state = self._flush_pane_chunk(state, w, pending)
            eng._advance_pane()
        if not pending.chunks or pending.resolve() == 0:
            eng.last_group_keys = [] if eng.group_exprs else None
            return state, eng._empty_cols(), np.empty(0, dtype=np.int64)
        from siddhi_tpu.core.emit_queue import fetch_coalesced

        out_cols, out_ts, keys = pending.materialize(
            fetch_coalesced(pending.device_arrays()))
        eng.last_group_keys = keys
        return state, out_cols, out_ts

    def _route_part(self, gid: np.ndarray) -> np.ndarray:
        """Global id -> the 'global partition id' route_to_shards
        expects (owner * parts_per_shard + local), with parts_per_shard
        = per_shard usable rows (scratch handled by route_to_shards
        itself)."""
        owner = gid % self.n_shards
        local = gid // self.n_shards
        return owner * self.per_shard + local

    def process(self, state, cols, ts, part_keys=None):
        state, out_cols, out_ts = self.process_batch(state, cols, ts,
                                                     part_keys)
        names = self.engine.output_names
        rows = [
            {nm: out_cols[nm][i] for nm in names}
            for i in range(len(out_ts))
        ]
        return state, rows

    def purge_idle_keys(self, state, now: int, idle_ms):
        """Partition-mode purge: the engine's own purge with dead
        logical ids remapped to this layout's shard-major rows (group
        rows and keyed_sliding ring rows shard independently, so both
        remaps apply)."""
        return self.engine.purge_idle_keys(state, now, idle_ms,
                                           remap=self._remap,
                                           wremap=self._remap)
