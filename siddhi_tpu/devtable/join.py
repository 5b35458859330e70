"""Stream-table joins against device-resident tables.

``DevTableJoinRuntime`` replaces the host ``JoinRuntime`` +
``JoinStreamReceiver`` pair for eligible queries (inner join, one
``DeviceTable`` side, windowless/filterless stream side, a primary-key
equality conjunct): the host resolves each event's key to its table
slot (``DeviceTable.probe_view``: one vectorised lookup in the table's
array-form index), the arriving micro-batch ships its key lane, that
slot lane and the condition-referenced attribute lanes to the device
once, a jitted probe gathers the table row at each slot — no plane over
the capacity: the work is the batch's — checks on the device that the
slot is live and still holds the event's key (the guard: a wrong slot
can only ever read as a miss, never as another key's row), evaluates
the FULL join condition on device lanes, and matched pairs ride the
existing async emit pipeline — zero host materialization between ingest
and emit.

Snapshot consistency: the probe closes over the table's CURRENT column
references at dispatch, taken with the slot lane in ONE hold of the
table lock (``probe_view``), so lane and arrays are of one revision.
JAX arrays are immutable and no scatter donates its inputs, so
mutations landing while the probe is in flight produce NEW arrays and
never tear the probed view — the probe reads exactly the
revision-in-progress it dispatched against, the device analog of the
host path's lock-ordered probe.

Because the eligibility gate requires a primary-key equality conjunct,
at most ONE table row matches each event, so output shapes are fixed
``[B]`` lanes and matched pairs emit in arrival order — bit-identical
to the host ``JoinRuntime._join``'s row-major ``np.nonzero`` order.

The runtime mirrors ``DeviceQueryRuntime``'s pipeline discipline:
``IngestStage`` staging for the count gate, ``EmitQueue`` for deferred
materialization, per-batch fault isolation through ``on_fault``, cycle
tokens for observability: a chunk's way in is ``convert`` (the key
expression; then the slot lookup and the lane padding), ``put`` and
``dispatch`` inside ``ingest``.  A batch is ONE chunk — one put, one
call, one gate, one fetch — unless it holds more than ``MAX_CHUNK``
events: then it is as many chunks, every one's spans its batch's
cycle's, and a chunk the gate finishes inline is finished (gate, fetch,
rows) before the next is put.  The probe's phases on the device are the
``siddhi.devtable.*`` scopes.  A demoted table (or a null-carrying
batch) falls back per batch to the exact host cross-product semantics —
after a pipeline drain, so emit order holds.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.device_pipeline import CountGate, DevicePipeline
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.ingest_stage import staged_put
from siddhi_tpu.observability.trace import (
    SCOPE_DEVTABLE_CONDITION,
    SCOPE_DEVTABLE_GATHER,
    SCOPE_DEVTABLE_PROBE,
    STAGE_CONVERT,
    STAGE_DISPATCH,
    STAGE_INGEST,
    reopen,
    span,
)
from siddhi_tpu.planner.expr import N_KEY, TS_KEY

log = logging.getLogger("siddhi_tpu")


def _pow2(n: int, floor: int = 16) -> int:
    return max(1 << (max(n, 1) - 1).bit_length(), floor)


class DevTableJoinRuntime:
    """One stream-table join lowered onto the device batch cycle."""

    # a chunk's lanes and gathered columns, not the table, bound it:
    # 65,536 events of thirteen 4-byte lanes are 3 MB on the device
    MAX_CHUNK = 65536

    def __init__(self, name: str, stream_side, table_side, stream_is_left: bool,
                 condition, key_expr, cond_stream_lanes: Dict[str, Tuple[str, np.dtype]],
                 out_stream_id: str, emit, app_context):
        import jax

        self.name = name
        self.stream_side = stream_side
        self.table_side = table_side
        self.table = table_side.table
        self.stream_is_left = stream_is_left
        self.condition = condition
        self.key_expr = key_expr
        # condition-referenced stream attrs riding device lanes:
        # env key -> (attribute name, lane dtype)
        self._cond_lanes = cond_stream_lanes
        self.out_stream_id = out_stream_id
        self.emit = emit
        self.step_invocations = 0
        self.probe_invocations = 0
        self.host_fallback_batches = 0
        # events whose key the host resolved to a slot, or did not
        self.slot_hits = 0
        self.slot_misses = 0
        # count gate, emit queue, drain(), fault isolation
        # (core/device_pipeline.py); the table owns its own puts
        self.pipeline = DevicePipeline(app_context, "devtable_join")
        self.pipeline.attach(self)
        left, right = ((stream_side, table_side) if stream_is_left
                       else (table_side, stream_side))
        self._out_names = [
            left.qualified_key(a.name) for a in left.definition.attributes
        ] + [right.qualified_key(a.name) for a in right.definition.attributes]
        self._tbl_names = [a.name for a in self.table.definition.attributes]
        tbl_env = {table_side.qualified_key(a.name): a.name
                   for a in self.table.definition.attributes}
        cond_fn = condition.fn

        def probe(keys, slots, ev_mask, ev_lanes, pk_col, tcols, valid):
            import jax.numpy as jnp

            def at(lane):  # ``s`` is clipped: no bounds handling needed
                return lane.at[s].get(mode="promise_in_bounds")

            with jax.named_scope(SCOPE_DEVTABLE_PROBE):
                s = jnp.clip(slots, 0, valid.shape[0] - 1)
                matched = ((slots >= 0) & at(valid) & (at(pk_col) == keys)
                           & ev_mask)
            with jax.named_scope(SCOPE_DEVTABLE_GATHER):
                gathered = {nm: at(c) for nm, c in tcols.items()}
            with jax.named_scope(SCOPE_DEVTABLE_CONDITION):
                env = dict(ev_lanes)
                for qk, nm in tbl_env.items():
                    env[qk] = gathered[nm]
                env[N_KEY] = keys.shape[0]
                ok = jnp.broadcast_to(
                    jnp.asarray(cond_fn(env)).astype(bool), matched.shape)
                mask = matched & ok
                count = jnp.sum(mask.astype(jnp.int32))
            return mask, gathered, count

        self._probe = jax.jit(probe)

    @property
    def state(self):
        """Where the join's state lives: the table's current ``(columns,
        validity lane)`` device references, the very arrays the next
        probe would read.  A table demoted to the host holds none."""
        return () if self.table.demoted else self.table.device_state()

    # -- batch entry ------------------------------------------------------

    def process_stream_batch(self, batch: EventBatch):
        cur = batch.only(ev.CURRENT)
        n = len(cur)
        if n == 0:
            return
        now = self.pipeline.now()  # sampled at receive time
        host_reason = self._host_only_reason(cur)
        if host_reason is not None:
            # pipeline barrier first so the synchronous host emit cannot
            # overtake queued device emits from earlier batches
            self.drain()
            self.host_fallback_batches += 1
            self._host_join(cur, now)
            return
        with self.pipeline.cycle(n) as tok:
            with span(STAGE_CONVERT):  # counted by the chunks' own
                keys = self._event_keys(cur)
            self.ingest_stats.device_chunks += -(-n // self.MAX_CHUNK)
            for lo in range(0, n, self.MAX_CHUNK):
                hi = min(n, lo + self.MAX_CHUNK)
                if not self._dispatch_chunk(cur, keys, lo, hi, now, tok):
                    # another thread's mutation demoted the table since
                    # the check above: the rest of the batch joins on
                    # the host, behind what is queued
                    if tok is not None:
                        tok.aborted(STAGE_INGEST)
                    self.drain()
                    self.host_fallback_batches += 1
                    self._host_join(cur.take(np.arange(lo, n)), now)
                    return

    def _host_only_reason(self, cur: EventBatch) -> Optional[str]:
        if self.table.demoted:
            return "table demoted to host"
        for _, (attr, _dt) in self._cond_lanes.items():
            if cur.columns[attr].dtype.kind == "O":
                return f"nulls in condition attribute '{attr}'"
        return None

    def _event_keys(self, cur: EventBatch) -> np.ndarray:
        env = {self.stream_side.qualified_key(a.name): cur.columns[a.name]
               for a in self.stream_side.definition.attributes}
        env[TS_KEY] = cur.timestamps
        env[N_KEY] = len(cur)
        return np.broadcast_to(self.key_expr.fn(env), (len(cur),))

    def _dispatch_chunk(self, cur, keys, lo, hi, now, tok) -> bool:
        """Put and dispatch events ``lo:hi`` and stage their gate; False,
        with nothing sent, if the table has demoted to the host."""
        cn = hi - lo
        if lo and tok is not None:
            # the chunk before was finished inside its submit, which
            # closed the cycle's way in: this chunk's opens where it starts
            reopen(tok)
            tok.ingest_begins()
        with span(STAGE_CONVERT, cn):
            B = _pow2(cn)
            klane = np.zeros(B, dtype=np.int32)
            klane[:cn] = keys[lo:hi].astype(np.int32, copy=False)
            # snapshot-consistent: the slots and the CURRENT immutable
            # refs from one hold of the table lock
            view = self.table.probe_view(klane[:cn])
            if view is None:
                return False
            slots, tcols, tvalid = view
            slane = np.full(B, -1, dtype=np.int32)
            slane[:cn] = slots
            hits = int(np.count_nonzero(slots >= 0))
            self.slot_hits += hits
            self.slot_misses += cn - hits
            mlane = np.zeros(B, dtype=bool)
            mlane[:cn] = True
            lanes = {}
            for ek, (attr, dt) in self._cond_lanes.items():
                col = np.zeros(B, dtype=dt)
                col[:cn] = cur.columns[attr][lo:hi].astype(dt, copy=False)
                lanes[ek] = col
        k_d, s_d, m_d, l_d = staged_put(
            (klane, slane, mlane, lanes),
            faults=self.faults, stats=self.ingest_stats)
        with span(STAGE_DISPATCH, 1):
            mask_d, gathered_d, count_d = self._probe(
                k_d, s_d, m_d, l_d, tcols[self.table.pk], tcols, tvalid)
        self.step_invocations += 1
        self.probe_invocations += 1
        self.pipeline.submit(
            tok,
            CountGate(count_d, [mask_d] + [gathered_d[nm]
                                           for nm in self._tbl_names]),
            lambda host: self._materialize(host, cur, lo, now),
            self.emit)
        return True

    def slot_metrics(self) -> Dict[str, int]:
        """``Queries.<q>.slotHits`` / ``slotMisses`` of ``statistics()``:
        probed events whose key the host's index resolved to a slot, or
        did not (those read as misses without a look at the table)."""
        return {"slotHits": self.slot_hits, "slotMisses": self.slot_misses}

    # -- deferred materialization (runs on fetched HOST arrays) -----------

    def _materialize(self, host: List[np.ndarray], cur: EventBatch,
                     lo: int, now: int):
        mask = host[0]
        sel = np.flatnonzero(mask)
        rows = sel + lo
        cols: Dict[str, np.ndarray] = {}
        for a in self.stream_side.definition.attributes:
            cols[self.stream_side.qualified_key(a.name)] = \
                cur.columns[a.name][rows]
        for i, nm in enumerate(self._tbl_names):
            cols[self.table_side.qualified_key(nm)] = host[1 + i][sel]
        out = EventBatch(
            self.out_stream_id,
            self._out_names,
            {k: cols[k] for k in self._out_names},
            cur.timestamps[rows],
            np.full(len(rows), ev.CURRENT, dtype=np.int8),
        )
        out.aux["emit_now"] = now
        return out

    # -- per-batch host fallback (exact host-join semantics) ---------------

    def _host_join(self, cur: EventBatch, now: int):
        buf = self.table.rows_batch()
        n_a, n_b = len(cur), len(buf)
        if n_b == 0:
            return
        env: Dict[str, np.ndarray] = {}
        for a in self.stream_side.definition.attributes:
            env[self.stream_side.qualified_key(a.name)] = np.repeat(
                cur.columns[a.name], n_b)
        for a in self.table.definition.attributes:
            env[self.table_side.qualified_key(a.name)] = np.tile(
                buf.columns[a.name], n_a)
        env[TS_KEY] = np.repeat(cur.timestamps, n_b)
        env[N_KEY] = n_a * n_b
        mask2 = np.broadcast_to(
            self.condition.fn(env), (n_a * n_b,)).reshape(n_a, n_b)
        ai, bi = np.nonzero(mask2)
        if len(ai) == 0:
            return
        cols: Dict[str, np.ndarray] = {}
        for a in self.stream_side.definition.attributes:
            cols[self.stream_side.qualified_key(a.name)] = \
                cur.columns[a.name][ai]
        for a in self.table.definition.attributes:
            cols[self.table_side.qualified_key(a.name)] = buf.columns[a.name][bi]
        out = EventBatch(
            self.out_stream_id,
            self._out_names,
            {k: cols[k] for k in self._out_names},
            cur.timestamps[ai],
            np.full(len(ai), ev.CURRENT, dtype=np.int8),
        )
        out.aux["emit_now"] = now
        self.emit(out)

    # -- barrier contract ---------------------------------------------------

    def snapshot(self) -> Dict:
        self.drain()
        return {}

    def restore(self, state: Dict):
        self.drain()


class DevTableJoinReceiver:
    """Junction subscriber replacing ``JoinStreamReceiver`` for the
    stream side of a devtable-lowered join."""

    def __init__(self, runtime: DevTableJoinRuntime):
        self.runtime = runtime

    def receive(self, batch: EventBatch):
        self.runtime.process_stream_batch(batch)
