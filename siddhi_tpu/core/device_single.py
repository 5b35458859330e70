"""Device (jitted TPU) execution of general single-stream queries inside
the product engine.

The glue the planner uses to route `SiddhiManager`-created
filter/window/group-by queries through the jitted device pipeline
(ops/device_query.py) instead of the host columnar chain — the analog of
the reference planner wiring ProcessStreamReceiver -> FilterProcessor ->
WindowProcessor -> QuerySelector
(util/parser/QueryParser.java:90, query/input/ProcessStreamReceiver.java:99-179,
query/selector/QuerySelector.java:76-99), re-designed so the hot path is
one jit-compiled step over columnar micro-batches with per-group state
rows in device memory.

Activation: ``@app:execution('tpu')``.  The planner attempts device
lowering for every eligible single-stream query and falls back to the
host engine — logging the reason — when the query is outside the device
subset (unsupported windows/aggregators, non-traceable expressions,
LONG-typed device operands, order-by/limit, non-CURRENT output event
types, ...).  See ops/device_query.py's module docstring for the full
subset contract, including the float32 precision stance.

Emission subset: the device path emits CURRENT events only (the default
``insert into``/callback contract).  Queries whose output event type is
'expired' or 'all' — i.e. consumers of window-expiry events — keep the
host engine, as do queries reading named windows (whose CURRENT+EXPIRED
feed drives add/remove aggregation).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.device_pipeline import DevicePipeline
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError
from siddhi_tpu.durability.capture import note_fetched
from siddhi_tpu.observability.trace import STAGE_CONVERT, span


class DeviceQueryRuntime:
    """Product-side wrapper of one DeviceQueryEngine: converts junction
    batches to device columns, advances per-group state with the jitted
    step, and emits output batches into the query's output chain.

    Emission runs through the async emit pipeline (core/emit_queue.py):
    each junction batch fetches ONE match-count scalar; zero-match
    batches transfer nothing, matched batches stay device-resident in a
    bounded pending-emit queue (``@app:execution('tpu',
    emit.depth='N')``; default 1 drains immediately) until a coalesced
    drain.  Every host-observable point — snapshot/restore, timer
    fires, pull queries, shutdown — calls :meth:`drain` first, so
    callback content and order are bit-identical to the synchronous
    path.

    Also a scheduler task: ``next_wakeup``/``fire`` drive timer-based
    timeBatch pane flushes so tumbling panes close on watermark time
    even when no further events arrive (the host TimeBatchWindow's
    scheduler contract)."""

    def __init__(self, engine, out_stream_id: str,
                 emit: Callable[[EventBatch], None], app_context=None):
        self.engine = engine
        self.out_stream_id = out_stream_id
        self.emit_cb = emit
        self.state = engine.init_state()
        self.step_invocations = 0  # proof the jitted path ran (tests)
        self.rows_emitted = 0
        # count gate, emit queue, drain(), fault isolation, poison
        # quarantine (core/device_pipeline.py); the engine kind labels
        # this runtime's spans
        self.pipeline = DevicePipeline(
            app_context, getattr(engine, "engine_kind", "device"))
        self.pipeline.attach(self, engine)

    def _put_back(self, host_state):
        """A host copy of the state back onto the device (quarantine,
        restore)."""
        eng = self.engine
        if hasattr(eng, "put_state"):  # sharded: restore placement
            return eng.put_state(host_state)
        jnp = eng.jnp
        return {k: jnp.asarray(v) for k, v in host_state.items()}

    # -- event path ----------------------------------------------------------

    def process_stream_batch(self, batch: EventBatch, keys=None):
        """Advance the device pipeline with a junction batch.  Only
        CURRENT rows drive it (control events — TIMER/RESET — have no
        device meaning; RESET cannot reach a device query because batch
        windows, their only producer, are ineligible upstream).
        ``keys`` (partition mode): raw partition-key value per row,
        already aligned to the batch's CURRENT rows by the partition
        receiver."""
        cur = batch.only(ev.CURRENT)
        n = len(cur)
        if n == 0:
            return
        with self.pipeline.cycle(n) as tok:
            self._advance(cur, n, keys, tok)

    def _advance(self, cur: EventBatch, n: int, keys, tok):
        eng = self.engine
        pipe = self.pipeline
        with span(STAGE_CONVERT, n):
            cols = {
                a: np.asarray(cur.columns[a])
                for a in eng.all_attrs if a in cur.columns
            }
            ts = np.asarray(cur.timestamps, dtype=np.int64)
        self.state, pending = eng.process_batch_deferred(
            self.state, cols, ts, part_keys=keys)
        self.step_invocations += 1
        self.state, poisoned = pipe.quarantine(
            self.state, eng.init_state, self._put_back)
        if poisoned:
            # corrupted step: state was re-materialized from the last
            # clean copy; this batch's device outputs are quarantined
            pipe.drop_poisoned(tok)
            return
        now = pipe.now()  # sampled at receive time, bound into deliver
        pipe.submit(
            tok, pending,
            lambda host: self._batch(*pending.materialize(host), now=now),
            self.emit_cb)

    def purge_idle(self, now: int, idle_ms) -> int:
        """Partition-mode idle-key purge (the dense analog of dropping
        idle PartitionInstances).  Drains first: purged keys' pending
        emits must reach per-key selector state before it is dropped."""
        self.drain()
        self.state, n = self.engine.purge_idle_keys(self.state, now, idle_ms)
        return n

    def _batch(self, out_cols: Dict[str, np.ndarray], out_ts: np.ndarray,
               keys=None, now=None) -> Optional[EventBatch]:
        """The rows a step (or a pane flush) yielded as the
        ``EventBatch`` for the output chain; None where there is none."""
        if len(out_ts) == 0:
            return None
        self.rows_emitted += len(out_ts)
        mb = EventBatch(
            self.out_stream_id, self.engine.output_names, out_cols,
            out_ts, np.full(len(out_ts), ev.CURRENT, dtype=np.int8),
        )
        if keys is not None:
            if len(keys) != len(mb):
                # a misaligned side channel is a wiring bug: degrading
                # to one global group would be silently wrong per-group
                # output (the host limiter's loud-failure contract,
                # core/query.py GroupBy*RateLimiter)
                raise SiddhiAppRuntimeError(
                    f"device query emitted {len(mb)} rows but "
                    f"{len(keys)} group keys")
            # group-key side channel: per-group/snapshot rate limiters
            # read it exactly like the host selector's
            mb.aux["group_keys"] = list(keys)
        if now is not None:
            mb.aux["emit_now"] = now
        return mb

    def stats(self) -> Dict:
        """Ops introspection: the engine's kind, the rows this runtime
        handed to its output chain and, for a tumbling window, the panes
        it closed."""
        eng = self.engine
        out = {"engine": eng.kind, "rows_emitted": self.rows_emitted}
        if eng.kind == "tumbling":
            out["panes_closed"] = eng.panes_closed
        return out

    # -- scheduler task (timeBatch pane flushes) -----------------------------

    def next_wakeup(self) -> Optional[int]:
        return self.engine.pane_wakeup()

    def fire(self, now: int):
        # barrier BEFORE the pane flush: batches processed before this
        # timer tick must emit first (the synchronous order)
        self.drain()
        self.state, out_cols, out_ts = self.engine.flush_due(self.state, now)
        mb = self._batch(out_cols, out_ts,
                         getattr(self.engine, "last_group_keys", None),
                         now=now)
        if mb is not None:
            self.emit_cb(mb)

    def on_start(self, now: int):
        pass

    def on_time(self, now: int):
        pass

    # -- snapshot contract ---------------------------------------------------

    def snapshot(self) -> Dict:
        self.drain()
        # the state crosses to the host here: under the caller's barrier
        # where a persist calls (the steps donate it, so a reference
        # would not outlive the next batch; the dense pattern engine
        # snapshots on the device instead, core/dense_pattern.py)
        host = {k: np.asarray(v) for k, v in self.state.items()}
        note_fetched(sum(a.nbytes for a in host.values()))
        return {"device_state": host, "host": self.engine.host_snapshot()}

    def restore(self, state: Dict):
        self.drain()
        self.pipeline.forget_clean_copy()
        eng = self.engine
        if not hasattr(eng, "put_state"):
            # row-count guard: a snapshot persisted under a SHARDED
            # layout (@app:execution devices='N') has N extra scratch
            # rows and a shard-major row bijection — restoring it here
            # would silently cross-wire group rows
            expect = {k: v.shape for k, v in eng.init_state_host().items()}
            for k, v in state["device_state"].items():
                if k in expect and np.asarray(v).shape != expect[k]:
                    raise SiddhiAppRuntimeError(
                        f"device-query snapshot '{k}' has shape "
                        f"{np.asarray(v).shape}; this engine expects "
                        f"{expect[k]} — persist and restore must use "
                        "the same @app:execution devices count")
        self.state = self._put_back(state["device_state"])
        eng.host_restore(state["host"])


class _DeviceQueryReceiver:
    """Junction subscriber feeding one device-lowered query."""

    def __init__(self, runtime: DeviceQueryRuntime):
        self.runtime = runtime

    def receive(self, batch: EventBatch):
        self.runtime.process_stream_batch(batch)
