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
from siddhi_tpu.core.emit_queue import EmitQueue, EmitStats, PendingEmit
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.ingest_stage import IngestStage, IngestStats
from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError
from siddhi_tpu.observability.trace import STAGE_CONVERT, span
from siddhi_tpu.util.faults import notify_listeners

import logging

log = logging.getLogger("siddhi_tpu")


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
                 emit: Callable[[EventBatch], None], emit_depth=1,
                 clock: Optional[Callable[[], int]] = None, faults=None,
                 ingest_depth=1, tracer=None,  # depths: int or 'auto'
                 listeners=None):
        self.engine = engine
        self._listeners = listeners  # the app's exception listeners
        self.out_stream_id = out_stream_id
        self.emit_cb = emit
        self.state = engine.init_state()
        # cycle-correlated span tracer (observability/trace.py), wired by
        # the planner; the engine kind labels this runtime's spans
        self.tracer = tracer
        self.engine_kind = getattr(engine, "engine_kind", "device")
        self.step_invocations = 0  # proof the jitted path ran (tests)
        self.emit_stats = EmitStats()
        # @app:faults(...) injector: arms the emit.drain/state.poison
        # sites.  The isolation hook below works with or without it: a
        # failing drain batch is logged + fed to exception listeners
        # instead of killing the app
        self.faults = faults
        self.emit_queue = EmitQueue(depth=emit_depth, stats=self.emit_stats,
                                    faults=faults, on_fault=self._on_fault)
        # ingest staging window (@app:execution('tpu', ingest.depth='N')):
        # depth 2 defers each batch's count-gate fetch until the NEXT
        # batch's H2D put + step dispatch are in flight, overlapping
        # transfer with compute; depth 1 (default) finishes inline —
        # identical timing to synchronous ingest.  The engine carries the
        # stats ref so staged_put (ops layer) counts its device puts.
        self.ingest_stats = IngestStats()
        engine.ingest_stats = self.ingest_stats
        self.ingest_stage = IngestStage(
            depth=ingest_depth, stats=self.ingest_stats, faults=faults,
            on_fault=self._on_fault)
        # last known-poison-free host copy of the device state, kept
        # only while a state.poison fault is armed (quarantine source)
        self._last_good = None
        # app clock sampled at ENQUEUE time: deferred emits replay with
        # the `now` the synchronous path would have used (time-based
        # rate limiters key their period grid off it)
        self.clock = clock

    def _on_fault(self, e: BaseException):
        # a batch just died in isolation (@OnError route): freeze the
        # span ring so the post-mortem shows the cycles leading up to it
        if self.tracer is not None:
            self.tracer.dump(f"onerror-isolation:{type(e).__name__}")
        notify_listeners(self._listeners, e)

    def _poison_guard(self) -> bool:
        """NaN/Inf quarantine, active only while a ``state.poison``
        fault is armed.  Poisons the state when the fault trips, then
        scans it; on detection, re-materializes from the last clean host
        copy (or re-initializes) and reports True so the caller drops
        the corrupted batch's outputs."""
        fi = self.faults
        if fi is None or not fi.watches("state.poison"):
            return False
        from siddhi_tpu.util import faults as _faults

        if fi.poisoned("state.poison"):
            self.state = _faults.poison_state(self.state)
        if not _faults.state_has_poison(self.state):
            self._last_good = _faults.host_copy(self.state)
            return False
        fi.stats.poison_quarantines += 1
        eng = self.engine
        if self._last_good is not None:
            log.error("device state poisoned (NaN/Inf); quarantining "
                      "batch and re-materializing last clean state")
            if hasattr(eng, "put_state"):  # sharded: restore placement
                self.state = eng.put_state(self._last_good)
            else:
                jnp = eng.jnp
                self.state = {
                    k: jnp.asarray(v) for k, v in self._last_good.items()
                }
        else:
            log.error("device state poisoned (NaN/Inf) with no clean "
                      "copy; quarantining batch and re-initializing")
            self.state = eng.init_state()
        return True

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
        # one sampled-or-None cycle token per junction batch: ingest
        # span starts here, at receive time
        tok = (self.tracer.begin_cycle(self.engine_kind, n)
               if self.tracer is not None else None)
        try:
            self._advance(cur, n, keys, tok)
        except BaseException:
            if tok is not None:
                tok.raised()
            raise

    def _advance(self, cur: EventBatch, n: int, keys, tok):
        eng = self.engine
        with span(STAGE_CONVERT, n):
            cols = {
                a: np.asarray(cur.columns[a])
                for a in eng.all_attrs if a in cur.columns
            }
            ts = np.asarray(cur.timestamps, dtype=np.int64)
        self.state, pending = eng.process_batch_deferred(
            self.state, cols, ts, part_keys=keys)
        self.step_invocations += 1
        if self._poison_guard():
            # corrupted step: state was re-materialized from the last
            # clean copy; this batch's device outputs are quarantined
            if tok is not None:
                tok.aborted("step")
            if self.tracer is not None:
                self.tracer.dump("poison-quarantine")
            return
        # `now` is the clock the SYNCHRONOUS path would have read; the
        # finish step may run a batch later (ingest.depth > 1), so it is
        # captured here, at receive time
        now = self.clock() if self.clock is not None else None

        def _finish(p=pending, t=now, tk=tok):
            if p is None:
                c = 0
            elif tk is None:
                c = p.resolve()
            else:
                with tk.step_wait():
                    c = p.resolve()
            if tk is not None:
                # count gate resolved: the jitted step finished
                tk.step_done(c)
            if c == 0:
                self.emit_queue.skip()
                return
            self.emit_queue.push(PendingEmit(
                p.device_arrays(),
                lambda host, pp=p, tt=t: self._emit_deferred(pp, host, tt),
                trace=tk))

        # the count-gate fetch (resolve) is what blocks on the device;
        # staging it lets batch N+1's H2D put + step dispatch go out
        # before batch N's scalar is fetched
        self.ingest_stage.submit(
            pending.probe() if pending is not None else None, _finish,
            trace=tok)

    def drain(self):
        """Flush barrier: materialize and emit every queued batch (one
        coalesced transfer).  Called wherever host code could observe
        emit timing — snapshot/restore, timer fires, rate-limiter
        decisions, pull queries, shutdown, debugger.  The ingest stage
        flushes first: staged batches must enqueue (or skip) before the
        emit queue drains, preserving the synchronous callback order."""
        self.ingest_stage.flush()
        self.emit_queue.drain()

    def _emit_deferred(self, pending, host_arrays, now=None):
        out_cols, out_ts, keys = pending.materialize(host_arrays)
        self._emit(out_cols, out_ts, keys, now=now)

    def purge_idle(self, now: int, idle_ms) -> int:
        """Partition-mode idle-key purge (the dense analog of dropping
        idle PartitionInstances).  Drains first: purged keys' pending
        emits must reach per-key selector state before it is dropped."""
        self.drain()
        self.state, n = self.engine.purge_idle_keys(self.state, now, idle_ms)
        return n

    def _emit(self, out_cols: Dict[str, np.ndarray], out_ts: np.ndarray,
              keys=None, now=None):
        if len(out_ts) == 0:
            return
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
        self.emit_cb(mb)

    # -- scheduler task (timeBatch pane flushes) -----------------------------

    def next_wakeup(self) -> Optional[int]:
        return self.engine.pane_wakeup()

    def fire(self, now: int):
        # barrier BEFORE the pane flush: batches processed before this
        # timer tick must emit first (the synchronous order)
        self.drain()
        self.state, out_cols, out_ts = self.engine.flush_due(self.state, now)
        self._emit(out_cols, out_ts,
                   getattr(self.engine, "last_group_keys", None), now=now)

    def on_start(self, now: int):
        pass

    def on_time(self, now: int):
        pass

    # -- snapshot contract ---------------------------------------------------

    def snapshot(self) -> Dict:
        self.drain()
        return {
            "device_state": {k: np.asarray(v) for k, v in self.state.items()},
            "host": self.engine.host_snapshot(),
        }

    def restore(self, state: Dict):
        self.drain()
        self._last_good = None
        eng = self.engine
        if hasattr(eng, "put_state"):  # sharded: restore the placement
            self.state = eng.put_state(state["device_state"])
        else:
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
            jnp = eng.jnp
            self.state = {
                k: jnp.asarray(v) for k, v in state["device_state"].items()
            }
        eng.host_restore(state["host"])


class _DeviceQueryReceiver:
    """Junction subscriber feeding one device-lowered query."""

    def __init__(self, runtime: DeviceQueryRuntime):
        self.runtime = runtime

    def receive(self, batch: EventBatch):
        self.runtime.process_stream_batch(batch)
