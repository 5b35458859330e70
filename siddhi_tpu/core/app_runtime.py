"""SiddhiAppRuntime: lifecycle + user API surface of one running app.

Mirrors the reference SiddhiAppRuntime/SiddhiAppRuntimeImpl
(SiddhiAppRuntimeImpl.java:99 — start :440, shutdown :543, callbacks,
input handlers).  Snapshot/restore and on-demand queries are wired in by
their subsystems.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Union

from siddhi_tpu.core.context import SiddhiAppContext
from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError
from siddhi_tpu.core.stream import (
    FunctionQueryCallback,
    FunctionStreamCallback,
    InputHandler,
    InputManager,
    QueryCallback,
    StreamCallback,
    StreamJunction,
)
from siddhi_tpu.observability.trace import (
    STAGE_PERSIST_CAPTURE,
    STAGE_PERSIST_DRAIN,
    span,
)


class SiddhiAppRuntime:
    def __init__(
        self,
        name: str,
        siddhi_app,
        app_context: SiddhiAppContext,
        junctions: Dict[str, StreamJunction],
        query_runtimes: Dict[str, object],
        input_manager: InputManager,
        scheduler,
        tables: Optional[Dict[str, object]] = None,
        named_windows: Optional[Dict[str, object]] = None,
        partitions: Optional[Dict[str, object]] = None,
        aggregations: Optional[Dict[str, object]] = None,
        sources: Optional[List] = None,
        sinks: Optional[List] = None,
        functions: Optional[Dict[str, object]] = None,
        handler_registrations: Optional[List] = None,
    ):
        self.name = name
        self.siddhi_app = siddhi_app
        self.app_context = app_context
        self.junctions = junctions
        self.query_runtimes = query_runtimes
        self.input_manager = input_manager
        self.scheduler = scheduler
        self.tables = tables or {}
        self.named_windows = named_windows or {}
        self.partitions = partitions or {}
        self.aggregations = aggregations or {}
        self.sources = sources or []
        self.sinks = sinks or []
        self.functions = functions or {}
        self._handler_registrations = handler_registrations or []
        self._on_demand_cache: Dict[str, object] = {}
        self.running = False
        self._manager = None  # back-ref set by SiddhiManager
        # raw app source (set by AppPlanner.build) — a live re-plan
        # rebuilds the whole engine set from a fresh parse of this
        self._app_string = ""
        # (target, callback) pairs as the user registered them, so a
        # re-plan can re-attach them to the replacement runtimes; the
        # ledger keys ("stream", id) / ("query", name) / ("sink", ...)
        # are structural, so replay suppression carries across
        self._user_callbacks: List = []
        self._apply_statistics_level(self.app_context.root_metrics_level)
        # fault-injection / recovery counters register UNGATED by the
        # metrics level: when @app:faults is armed, its evidence must be
        # visible in statistics()/REST even with statistics 'off'
        sm = self.app_context.statistics_manager
        fi = self.app_context.fault_injector
        if sm is not None and fi is not None:
            sm.fault_tracker("injector", fi.stats)
        # @app:limits counters register ungated too: shed/breaker/
        # watchdog evidence must survive statistics level 'off' — the
        # health endpoint and the metrics feed read the SAME object
        rb = self.app_context.robustness
        if sm is not None and rb is not None:
            sm.robustness_tracker("overload", rb)

    # -- async emit pipeline barriers ---------------------------------------

    def _device_runtimes(self):
        """Every device/dense runtime holding a pending-emit queue
        (core/emit_queue.py), across top-level queries and dense
        partitions."""
        for qr in self.query_runtimes.values():
            for attr in ("device_runtime", "pattern_processor"):
                rt = getattr(qr, attr, None)
                if rt is not None and hasattr(rt, "drain"):
                    yield rt
        for pr in self.partitions.values():
            for qr in getattr(pr, "dense_query_runtimes", {}).values():
                for attr in ("device_runtime", "pattern_processor"):
                    rt = getattr(qr, attr, None)
                    if rt is not None and hasattr(rt, "drain"):
                        yield rt

    def drain_device_emits(self):
        """App-wide flush barrier of the async emit pipeline: every
        device runtime's queued match batches materialize and emit (in
        the synchronous order) before host code observes state —
        snapshot/persist/restore, pull queries, shutdown.  Device
        tables drain LAST: an emit drain can trigger mutation callbacks,
        and the table barrier (compaction + revision advance + pinning)
        must see them."""
        for rt in self._device_runtimes():
            rt.drain()
        for t in self.tables.values():
            if hasattr(t, "drain"):
                t.drain()

    # -- overload gauges (robustness/watchdog.py reads these) ---------------

    def _pending_work(self) -> int:
        """Units of accepted-but-undelivered work: queued async-junction
        batches plus staged ingest probes and deferred device emits.
        Zero means a frozen beat is just idleness, not a stall."""
        n = 0
        for j in self.junctions.values():
            if j.is_async and j._queue is not None:
                n += j._queue.qsize()
        for rt in self._device_runtimes():
            eq = getattr(rt, "emit_queue", None)
            if eq is not None:
                n += len(eq)
            stage = getattr(rt, "ingest_stage", None)
            if stage is not None:
                n += len(stage)
        return n

    # -- lifecycle ----------------------------------------------------------

    def debug(self):
        """Start in debug mode: returns a SiddhiDebugger wired to every
        query terminal (reference: SiddhiAppRuntimeImpl.debug:657)."""
        from siddhi_tpu.debugger import SiddhiDebugger

        debugger = SiddhiDebugger(self)
        for qr in self.query_runtimes.values():
            if hasattr(qr, "debugger"):
                qr.debugger = debugger
        # breakpoints must observe every emit at its own batch: force
        # the pending-emit queue to drain after each step (and pin it —
        # an auto controller would re-deepen it), and collapse the
        # ingest staging window back to synchronous
        for rt in self._device_runtimes():
            eq = getattr(rt, "emit_queue", None)
            if eq is not None:
                eq.depth = 1
                eq.controller = None
            stage = getattr(rt, "ingest_stage", None)
            if stage is not None:
                stage.pin(1)
        self.start()
        return debugger

    def start(self):
        if self.running:
            return
        for j in self.junctions.values():
            j.start()
        self.scheduler.start()
        for t in self.tables.values():
            if hasattr(t, "start"):
                t.start()  # record tables connect their stores
        # sinks connect before sources so output paths exist when events
        # flow; the running gate opens BEFORE sources connect — a source
        # may deliver on its transport thread the instant it subscribes.
        # Rolled back if a transport start raises, so a failed start()
        # leaves the InputHandler gate closed.
        self.app_context.app_running = True
        try:
            for s in self.sinks:
                s.start()
            for s in self.sources:
                s.start()
        except Exception as e:
            import logging

            # the rollback re-raises, but the failure must also leave a
            # trace in the error log (the no-silent-fault contract)
            logging.getLogger("siddhi_tpu").error(
                "app '%s': transport start failed, rolling back the "
                "running gate: %s", self.name, e)
            self.app_context.app_running = False
            raise
        from siddhi_tpu.util.statistics import Level

        sm = self.app_context.statistics_manager
        if sm is not None and Level.at_least(self.app_context.root_metrics_level, Level.BASIC):
            sm.start_reporting()
        self.running = True
        tracer = self.app_context.tracer
        if tracer is not None:
            # a stall's record reads the gates in flight and the emits
            # pending (observability/stall.py); the watch's thread is
            # started by the sends themselves
            tracer.watch.pending_work = self._pending_work
            tracer.watch.resume()
        if self.app_context.playback and self.app_context.playback_idle_ms > 0:
            self._start_playback_heartbeat()
        if self.app_context.persist_interval_ms > 0:
            self._start_persist_daemon()
        if (self.app_context.plan_auto
                and self.app_context.plan_interval_ms > 0):
            from siddhi_tpu.planner.monitor import PlanMonitor

            # @app:plan(auto, interval): online refinement daemon — reads
            # the observability feed and re-lowers when the active plan's
            # observed cost exceeds a cheaper alternative by the
            # hysteresis margin
            self._plan_monitor = PlanMonitor(self)
            self._plan_monitor.start()
        if (self.app_context.watchdog_deadline_ms > 0
                and getattr(self, "_watchdog", None) is None):
            from siddhi_tpu.robustness import Watchdog

            # @app:limits(watchdog='...'): stall detector + self-heal
            # daemon.  replan() restarts it through here, with the
            # transplanted stats so counters survive the heal.
            self._watchdog = Watchdog(
                self, self.app_context.robustness,
                self.app_context.watchdog_deadline_ms)
            self._watchdog.start()

    def _start_playback_heartbeat(self):
        """@app:playback(idle.time, increment): when no events arrive for
        idle.time, advance event time by increment so event-time windows
        and schedulers keep draining (reference:
        TimestampGeneratorImpl idle-time timer)."""
        import threading
        import time as _time

        idle_s = self.app_context.playback_idle_ms / 1000.0
        tg = self.app_context.timestamp_generator
        stop = threading.Event()

        def loop():
            while not stop.wait(idle_s):
                if _time.monotonic() - tg.last_update_wall >= idle_s:
                    with self.app_context.process_lock:
                        now = tg.advance_idle()
                        self.scheduler.advance(now)

        t = threading.Thread(target=loop, name=f"playback-{self.name}", daemon=True)
        self._playback_stop = stop
        self._playback_thread = t
        t.start()

    def _start_persist_daemon(self, clock=None, wait=None):
        """@app:persist(interval, mode): periodic checkpoint daemon — a
        persist() at a fixed rate from ``start()``, in the annotation's
        mode: tick ``k`` is due at ``start + k * interval`` whatever the
        persists before it took (upstream schedules at a fixed rate; a
        period of interval plus stall would drift with the stall).  A
        tick that comes due while the last persist is still inside its
        call, its capture under the barrier, is skipped and counted
        (``Durability.<app>.persist_ticks_skipped``).  ``clock`` and
        ``wait`` are ``time.monotonic`` and the stop event's ``wait``
        unless a test hands in its own."""
        import logging
        import threading
        import time as _time

        log = logging.getLogger("siddhi_tpu")
        interval_s = self.app_context.persist_interval_ms / 1000.0
        stop = threading.Event()
        clock = clock or _time.monotonic
        wait = wait or stop.wait
        stats = self._durability_stats()
        t_start = clock()

        def loop():
            k = 1
            while not wait(max(0.0, t_start + k * interval_s - clock())):
                try:
                    self.persist()
                except Exception as e:
                    log.error("app '%s': periodic persist failed: %s",
                              self.name, e)
                    for lst in self.app_context.exception_listeners:
                        try:
                            lst(e)
                        except Exception:
                            log.exception("exception listener failed")
                except BaseException as e:
                    # simulated crash on the daemon thread: record and
                    # stop ticking — the harness kills the app elsewhere
                    log.error("app '%s': persist daemon stopped: %s",
                              self.name, e)
                    break
                due = max(k + 1, int((clock() - t_start) / interval_s) + 1)
                stats.persist_ticks_skipped += due - (k + 1)
                k = due

        t = threading.Thread(target=loop, name=f"persist-{self.name}",
                             daemon=True)
        self._persist_stop = stop
        self._persist_thread = t
        t.start()

    def shutdown(self):
        # the watchdog stops FIRST: a daemon that can force a replan
        # must not race an intentional teardown
        wd = getattr(self, "_watchdog", None)
        if wd is not None:
            wd.stop()
            self._watchdog = None
        mon = getattr(self, "_plan_monitor", None)
        if mon is not None:
            mon.stop()
            self._plan_monitor = None
        tracer = self.app_context.tracer
        if tracer is not None:
            tracer.watch.stop()
        stop = getattr(self, "_persist_stop", None)
        if stop is not None:
            stop.set()
            self._persist_thread.join(timeout=2)
            self._persist_stop = None
        stop = getattr(self, "_playback_stop", None)
        if stop is not None:
            stop.set()
            self._playback_thread.join(timeout=2)
            self._playback_stop = None
        # in-flight async checkpoint reaches a terminal state, then the
        # writer thread exits — shutdown must not strand a half-written
        # revision mid-commit (the store's atomic-manifest protocol makes
        # even a stranded one recoverable, but exiting clean is free)
        w = self._durability_writer(create=False)
        if w is not None:
            w.shutdown()
        sm = self.app_context.statistics_manager
        if sm is not None:
            sm.stop_reporting()
        for s in self.sources:
            s.shutdown()
        # barrier: queued device emits reach their callbacks/sinks
        # before the scheduler and junctions stop accepting output
        self.drain_device_emits()
        # nothing is staged any more: the idle finisher's thread (there
        # is one only if a stage ever left a batch in flight) goes
        self.app_context.idle_finisher.stop()
        for s in self.sinks:
            s.shutdown()
        self.scheduler.stop()
        for j in self.junctions.values():
            j.stop()
        # final dense-overflow check so short-lived apps still surface
        # dropped-instance warnings
        for qr in self.query_runtimes.values():
            pp = getattr(qr, "pattern_processor", None)
            if pp is not None and hasattr(pp, "close"):
                pp.close()
            # multiplexed tenants free their shared-engine seat here
            dr = getattr(qr, "device_runtime", None)
            if dr is not None and hasattr(dr, "close"):
                dr.close()
        for pr in self.partitions.values():
            for qr in getattr(pr, "dense_query_runtimes", {}).values():
                pp = getattr(qr, "pattern_processor", None)
                if pp is not None and hasattr(pp, "close"):
                    pp.close()
        for t in self.tables.values():
            if hasattr(t, "shutdown"):
                t.shutdown()
        for mgr, element_id in self._handler_registrations:
            mgr.unregister(element_id)
        self._handler_registrations = []
        self.running = False
        self.app_context.app_running = False
        if self._manager is not None:
            # identity-guarded: an unregistered or replaced runtime must
            # not evict a different runtime registered under this name
            if self._manager._app_runtimes.get(self.name) is self:
                self._manager._app_runtimes.pop(self.name, None)

    # -- I/O ----------------------------------------------------------------

    def get_input_handler(self, stream_id: str) -> InputHandler:
        return self.input_manager.get_input_handler(stream_id)

    def add_callback(
        self,
        target: str,
        callback: Union[StreamCallback, QueryCallback, Callable],
    ):
        """Attach a callback to a stream (StreamCallback / function taking
        events list) or to a query by name (QueryCallback / function taking
        (ts, in_events, out_events))."""
        if target in self.junctions:
            if callable(callback) and not isinstance(callback, StreamCallback):
                callback = FunctionStreamCallback(callback)
            self.junctions[target].add_callback(callback)
            self._user_callbacks.append((target, callback))
            return
        if target in self.query_runtimes:
            if callable(callback) and not isinstance(callback, QueryCallback):
                callback = FunctionQueryCallback(callback)
            self.query_runtimes[target].add_callback(callback)
            self._user_callbacks.append((target, callback))
            return
        raise SiddhiAppRuntimeError(
            f"no stream or query named '{target}' in app '{self.name}'"
        )

    def add_exception_listener(self, listener) -> None:
        """Register a runtime exception listener invoked with every
        error the engine logs instead of raising — @OnError LOG mode,
        sink publish failures, scheduler task errors (reference:
        SiddhiAppRuntimeImpl.handleRuntimeExceptionWith:827)."""
        self.app_context.exception_listeners.append(listener)

    # Java-style aliases for drop-in familiarity
    addCallback = add_callback
    getInputHandler = get_input_handler
    handleRuntimeExceptionWith = add_exception_listener

    # -- statistics ---------------------------------------------------------

    def _apply_statistics_level(self, level: str):
        """(Un)install throughput/latency/buffer trackers to match `level`
        (reference: SiddhiAppRuntimeImpl.setStatisticsLevel:859,
        registerForBufferedEvents:802-821)."""
        from siddhi_tpu.util.statistics import Level

        sm = self.app_context.statistics_manager
        if sm is None:
            return
        self.app_context.root_metrics_level = level
        basic = Level.at_least(level, Level.BASIC)
        detail = Level.at_least(level, Level.DETAIL)
        if not basic:
            # downgrade: drop trackers from the manager so statistics()
            # stops reporting stale metrics
            sm.throughput.clear()
            sm.latency.clear()
            sm.lowering.clear()
            sm.transfers.clear()
            sm.ingests.clear()
        else:
            sm.lowering.update(self.lowering())
            # async pipeline counters, one gauge pair per device-lowered
            # query: emit side (emitTransfers / deferredBatches /
            # zeroMatchSkips / maxPendingDepth / autoEffectiveDepth /
            # earlyCopyBatches / earlyCopyHits / earlyCopyWastedBytes) and
            # ingest side (stagedBatches / devicePuts / putLeaves /
            # deviceChunks / steppedLanes / steppedStateBytes /
            # plannedRepeats /
            # batchesByStream.<stream> / fusedHops /
            # ingestStalls / overlappedBatches / flushSyncs /
            # maxStagingDepth)
            for name, qr in list(self.query_runtimes.items()) + [
                (n, q)
                for pr in self.partitions.values()
                for n, q in getattr(pr, "dense_query_runtimes", {}).items()
            ]:
                for attr in ("device_runtime", "pattern_processor"):
                    rt = getattr(qr, attr, None)
                    if rt is not None and hasattr(rt, "emit_stats"):
                        sm.transfer_tracker(name, rt.emit_stats)
                    if rt is not None and hasattr(rt, "ingest_stats"):
                        sm.ingest_tracker(name, rt.ingest_stats)
        if not detail:
            sm.buffers.clear()
        for j in self.junctions.values():
            j.throughput_tracker = sm.throughput_tracker(j.stream_id) if basic else None
        for qname, qr in self.query_runtimes.items():
            if hasattr(qr, "latency_tracker"):
                qr.latency_tracker = sm.latency_tracker(qname) if basic else None
        if detail:
            for j in self.junctions.values():
                if j.is_async:
                    sm.buffer_tracker(j.stream_id, j)

    def set_statistics_level(self, level: str):
        """Runtime-switchable metrics level OFF/BASIC/DETAIL."""
        from siddhi_tpu.util.statistics import Level

        self._apply_statistics_level(level)
        sm = self.app_context.statistics_manager
        if sm is not None and self.running:
            if Level.at_least(level, Level.BASIC):
                sm.start_reporting()
            else:
                sm.stop_reporting()

    def statistics(self) -> Dict[str, float]:
        sm = self.app_context.statistics_manager
        return sm.stats() if sm is not None else {}

    def lowering(self) -> Dict[str, str]:
        """Per-query engine placement: ``'host'`` (columnar numpy
        chain), ``'dense'`` (jitted dense NFA), or ``'device'`` (jitted
        device query engine) — so an ``execution('tpu')`` user can see
        WHICH queries actually lowered instead of silently getting host
        execution (the dense path's capacity introspection analog for
        the general query path).  A ``'devtable'`` join whose table has
        demoted itself to the host mid-run answers every batch by the
        host join from then on, and reads ``'host'``."""
        out = {}
        for name, qr in self.query_runtimes.items():
            to = getattr(qr, "lowered_to", "host")
            if to == "devtable" and qr.device_runtime.table.demoted:
                to = "host"
            out[name] = to
        for pr in self.partitions.values():
            if hasattr(pr, "query_lowering"):
                out.update(pr.query_lowering())
        return out

    # -- live re-planning ---------------------------------------------------

    def replan(self, pins: Optional[Dict[str, str]] = None,
               forced: bool = True, reason: str = "") -> Dict[str, str]:
        """Re-lower the RUNNING app under a new plan, bit-exact across
        the switch.

        Protocol (all under the process lock): pause ingest and drain
        the async emit pipeline; build a COMPLETE replacement engine set
        from a fresh parse with ``pins`` as per-query exact-path
        overrides (``{'q': 'fuse+shard'}``; absent queries re-plan by
        cost); cross the ``replan.reseat`` crash point (a kill there
        abandons the replacement and leaves the old engines fully
        operational); tear the old engines down; adopt the new
        internals onto this SAME runtime object (manager registry,
        handles, and REST routes keep working); re-attach user
        callbacks; then rebuild all engine state by replaying the input
        journal's FULL history with the output ledger suppressing every
        event each callback/sink already received — the observable
        sequence is identical to an uninterrupted run on either plan.

        Requires ``@app:faults(journal='N')`` with the whole input
        history still in memory; refused with a counted
        ``plannerFallbackReason`` otherwise.  Returns the new per-query
        lowering map."""
        import logging

        from siddhi_tpu.planner.app_planner import AppPlanner

        log = logging.getLogger("siddhi_tpu")
        sm = self.app_context.statistics_manager

        def refuse(why: str):
            if sm is not None:
                sm.record_planner_fallback(self.name,
                                           f"replan refused: {why}")
            log.warning("app '%s': replan refused (%s)", self.name, why)
            raise SiddhiAppRuntimeError(
                f"app '{self.name}': replan refused — {why}")

        if not self.running:
            refuse("app is not running")
        jr = self.app_context.input_journal
        if jr is None:
            refuse("no input journal — @app:faults(journal='N') is the "
                   "replay substrate a live re-plan rebuilds state from")
        with self.app_context.process_lock:
            old_sources = list(self.sources)
            for s in old_sources:
                s.pause()
            committed = False
            try:
                self.drain_device_emits()
                self._flush_persists()
                if not jr.covers_from_start():
                    refuse("journal no longer holds the full input "
                           "history (overflowed or spilled); raise the "
                           "journal depth to re-plan live")
                old_lowering = self.lowering()
                entries = jr.all_entries()

                app_str = getattr(self, "_app_string", "") or ""
                if app_str:
                    from siddhi_tpu.compiler.compiler import SiddhiCompiler

                    ast = SiddhiCompiler.parse(app_str)
                else:
                    ast = self.siddhi_app
                planner = AppPlanner(
                    ast, app_str, self.app_context.siddhi_context)
                planner.app_context.plan_pins = dict(pins or {})
                # robustness continuity (BEFORE build, so breakers and
                # trackers bind to the carried objects): shed/breaker
                # counters and token-bucket levels survive a self-heal
                # exactly like the journal does
                rb = self.app_context.robustness
                if rb is not None and planner.app_context.robustness is not None:
                    planner.app_context.robustness = rb
                    ac = self.app_context.admission
                    if ac is not None:
                        ac.app_context = planner.app_context
                        ac.stats = rb
                        planner.app_context.admission = ac
                new_rt = planner.build()

                fi = self.app_context.fault_injector
                try:
                    if fi is not None:
                        # crash point: replacement built, old engines not
                        # yet torn down — a kill here must leave the old
                        # runtime fully operational
                        fi.check("replan.reseat")
                except BaseException:
                    # abandon the replacement; drop its registrations so
                    # the old runtime keeps exclusive ownership
                    try:
                        new_rt._manager = None
                        new_rt.shutdown()
                    except Exception:
                        log.warning(
                            "replan: abandoned replacement engines did "
                            "not tear down cleanly", exc_info=True)
                    raise

                # ---- point of no return: adopt the replacement --------
                new_ctx = new_rt.app_context
                # ONE lock serializes both incarnations: transports of
                # the new sources must block on the lock this thread
                # holds until the replay below finishes
                new_ctx.process_lock = self.app_context.process_lock
                new_sm = new_ctx.statistics_manager
                if sm is not None and new_sm is not None:
                    # app-wide re-plan history survives the switch
                    new_sm.replans.extend(sm.replans)
                mgr = self._manager
                self._manager = None  # identity-guarded pop must not fire
                try:
                    self.shutdown()
                finally:
                    self._manager = mgr
                committed = True
                self.siddhi_app = new_rt.siddhi_app
                self.app_context = new_ctx
                self.junctions = new_rt.junctions
                self.query_runtimes = new_rt.query_runtimes
                # keep the OLD InputManager object (user code holds
                # InputHandlers it created): re-point it and every cached
                # handler at the replacement junctions/context in place
                old_im = self.input_manager
                new_im = new_rt.input_manager
                old_im.app_context = new_ctx
                old_im._junctions = new_im._junctions
                for sid, h in list(old_im._handlers.items()):
                    nj = new_im._junctions.get(sid)
                    if nj is None:  # pragma: no cover - defs are static
                        old_im._handlers.pop(sid)
                        continue
                    h.junction = nj
                    h.app_context = new_ctx
                    h.definition = nj.definition
                self.scheduler = new_rt.scheduler
                self.tables = new_rt.tables
                self.named_windows = new_rt.named_windows
                self.partitions = new_rt.partitions
                self.aggregations = new_rt.aggregations
                self.sources = new_rt.sources
                self.sinks = new_rt.sinks
                self.functions = new_rt.functions
                self._handler_registrations = new_rt._handler_registrations
                self._on_demand_cache = {}
                self._snapshot_svc = None
                self._ckpt_writer = None
                self._durab_stats = None
                cbs, self._user_callbacks = self._user_callbacks, []
                for target, cb in cbs:
                    self.add_callback(target, cb)

                # restart under the new plan, then rebuild engine state
                # by replaying the full journaled history through the
                # suppressing output ledger
                self.start()
                jr.begin_replay_from_start()
                try:
                    for stream_id, batch in entries:
                        self.input_manager.get_input_handler(
                            stream_id).send_batch(batch)
                        if jr.stats is not None:
                            jr.stats.replayed_batches += 1
                    # barrier INSIDE the replay window (same contract as
                    # _replay_journal): deferred emits must flow through
                    # the suppressing ledger, not escape as duplicates
                    self.drain_device_emits()
                finally:
                    jr.end_replay()
                new_lowering = self.lowering()
                rsm = self.app_context.statistics_manager
                if rsm is not None:
                    changed = False
                    for q, p in sorted(new_lowering.items()):
                        o = old_lowering.get(q, "")
                        if o != p:
                            changed = True
                            rsm.record_replan(q, o, p, forced, reason)
                    if not changed:
                        rsm.record_replan("*", "", "", forced,
                                          reason or "no lowering change")
                log.info("app '%s': re-planned (%s); lowering now %s",
                         self.name, reason or "forced", new_lowering)
                return new_lowering
            finally:
                if not committed:
                    for s in old_sources:
                        try:
                            s.resume()
                        except Exception:  # pragma: no cover - best effort
                            log.exception("replan: source resume failed")

    # -- health -------------------------------------------------------------

    def health(self) -> Dict:
        """Overload-protection health report (``GET /siddhi-health``).

        ``healthy`` is the roll-up verdict: running, not shedding within
        the admission window, no OPEN breaker, watchdog not wedged.  All
        counters come off the live ``RobustnessStats`` object — the same
        one the statistics feed wraps, so the two can never disagree.
        Lock-free by design: a health probe must answer even while the
        app is wedged."""
        ctx = self.app_context
        ac = ctx.admission
        rb = ctx.robustness
        wd = getattr(self, "_watchdog", None)
        breakers = []
        for s in list(self.sinks) + list(self.sources):
            for t in [s] + list(getattr(s, "children", None) or []):
                b = getattr(t, "_breaker", None)
                if b is not None:
                    breakers.append(b.describe())
        shedding = ac.shedding_now() if ac is not None else False
        wedged = wd.wedged if wd is not None else False
        healthy = (self.running and not shedding and not wedged
                   and not any(b["state"] == "open" for b in breakers))
        return {
            "app": self.name,
            "healthy": healthy,
            "running": self.running,
            "shedding": shedding,
            "wedged": wedged,
            "admission": ac.snapshot() if ac is not None else None,
            "breakers": breakers,
            "watchdog": wd.describe() if wd is not None else None,
            "counters": rb.as_dict() if rb is not None else {},
        }

    def pattern_state(self) -> Dict[str, Dict]:
        """Ops introspection of every pattern/sequence query's engine
        state (dense: partition/instance occupancy + overflow; host:
        live instance count) — parity for the TPU path with the
        reference's runtime inspection surface
        (reference: core/query/OnDemandQueryRuntime.java for the pull
        model; the dense counters have no Java analog).

        Takes the app lock: dense state buffers are DONATED to the
        jitted step mid-batch, so an unlocked read from another thread
        (the REST server) could touch deleted device buffers."""
        with self.app_context.process_lock:
            out: Dict[str, Dict] = {}
            for name, qr in self.query_runtimes.items():
                pp = getattr(qr, "pattern_processor", None)
                if pp is not None and hasattr(pp, "stats"):
                    out[name] = pp.stats()
            for pr in self.partitions.values():
                for qname, qr in getattr(pr, "dense_query_runtimes", {}).items():
                    pp = getattr(qr, "pattern_processor", None)
                    if pp is not None and hasattr(pp, "stats"):
                        out[qname] = pp.stats()
            return out

    # -- on-demand (pull) queries -------------------------------------------

    def table_resolver(self, table_name: str, obj: bool = False):
        table = self.tables.get(table_name)
        if table is None:
            raise SiddhiAppRuntimeError(f"'IN {table_name}': table is not defined")
        return table if obj else table.contains_fn()

    def query(self, on_demand_query: str):
        """Execute a pull query against a table / named window / aggregation
        and return the matching events
        (reference: SiddhiAppRuntimeImpl.query:304, cache cap 50)."""
        from siddhi_tpu.compiler.compiler import SiddhiCompiler
        from siddhi_tpu.core.on_demand import OnDemandQueryRuntime

        # barrier: a pull query reads tables/windows/aggregations that
        # queued device emits may still feed — flush them first so the
        # result matches the synchronous path
        self.drain_device_emits()
        rt = self._on_demand_cache.get(on_demand_query)
        if rt is None:
            odq = SiddhiCompiler.parse_on_demand_query(on_demand_query)
            rt = OnDemandQueryRuntime(odq, self)
            if len(self._on_demand_cache) >= 50:
                self._on_demand_cache.pop(next(iter(self._on_demand_cache)))
            self._on_demand_cache[on_demand_query] = rt
        return rt.execute()

    # -- persistence --------------------------------------------------------

    def _snapshot_service(self):
        from siddhi_tpu.util.snapshot import SnapshotService

        # cached: incremental mode tracks per-element digests across persists
        svc = getattr(self, "_snapshot_svc", None)
        if svc is None:
            svc = self._snapshot_svc = SnapshotService(self)
        return svc

    def _persistence_store(self):
        """The app's own store (``@app:persist(location=...)``) or the
        manager's; never both, so there is no precedence to know."""
        from siddhi_tpu.core.exceptions import NoPersistenceStoreError

        own = self.app_context.persistence_store
        store = getattr(self.app_context.siddhi_context, "persistence_store", None)
        if own is not None and store is not None:
            raise SiddhiAppRuntimeError(
                f"app '{self.name}': @app:persist names a location and the "
                "manager has a persistence store; an app has one store")
        store = own if own is not None else store
        if store is None:
            raise NoPersistenceStoreError(
                f"app '{self.name}': no persistence store configured "
                "(@app:persist(location='...') or "
                "SiddhiManager.set_persistence_store)"
            )
        return store

    def _durability_stats(self):
        from siddhi_tpu.durability.writer import DurabilityStats

        st = getattr(self, "_durab_stats", None)
        if st is None:
            st = self._durab_stats = DurabilityStats()
            sm = self.app_context.statistics_manager
            if sm is not None:
                # ungated like the fault counters: checkpoint health must
                # be visible even at statistics level 'off'
                sm.durability_tracker(self.name, st)
        return st

    def _durability_writer(self, create: bool = True):
        from siddhi_tpu.durability.writer import AsyncCheckpointWriter

        w = getattr(self, "_ckpt_writer", None)
        if w is None and create:
            w = self._ckpt_writer = AsyncCheckpointWriter(
                self.name, stats=self._durability_stats(),
                fault_injector=self.app_context.fault_injector,
                listeners=self.app_context.exception_listeners,
                tracer=self.app_context.tracer)
        return w

    def _flush_persists(self, timeout: float = 30.0):
        """Barrier: any in-flight async checkpoint reaches a terminal
        state before host code reads or replaces persisted state."""
        w = self._durability_writer(create=False)
        if w is not None:
            w.wait(timeout=timeout)

    def wait_for_persist(self, revision: Optional[str] = None,
                         timeout: Optional[float] = None) -> Optional[str]:
        """Block until an async persist finishes.  Returns the terminal
        status ('committed' / 'failed' / 'superseded' / 'crashed' /
        'idle') or None on timeout.  No-op ('idle') when nothing was
        ever submitted."""
        w = self._durability_writer(create=False)
        if w is None:
            return "idle"
        return w.wait(revision=revision, timeout=timeout)

    def _persist_write(self, store, revision: str, capture):
        """Serialize + store + commit one captured checkpoint.  Runs on
        the checkpoint writer thread (async) or inline (sync)."""
        from siddhi_tpu.durability.capture import fetch_tally

        fi = self.app_context.fault_injector
        st = self._durability_stats()
        # what the capture kept by reference is fetched in here
        with fetch_tally() as deferred:
            if hasattr(store, "save_tree"):
                blobs = capture.materialize_blobs()
                written = store.save_tree(
                    self.name, revision, blobs,
                    checker=fi.check if fi is not None else None,
                    version=capture.version, clock=capture.clock)
                st.blobs_written += len(blobs)
            else:
                data = capture.tree_bytes()
                store.save(self.name, revision, data)
                written = len(data)
        st.bytes_written += written
        st.persist_deferred_bytes += deferred[0]
        if fi is not None:
            # crash point: revision durable, journal mark not committed
            fi.check("persist.post_manifest")
        jr = self.app_context.input_journal
        if jr is not None:
            jr.commit_revision(revision)

    def persist(self, mode: Optional[str] = None) -> str:
        """Snapshot all state and save it under a new revision
        (reference: SiddhiAppRuntimeImpl.persist:677).  Returns the
        revision id.

        Both modes capture under the barrier: the app's process lock
        from the emit drain to the end of every element's
        ``snapshot()`` and its freeze (durability/capture.py).  What
        that costs the batch loop is each engine's: the dense pattern
        engine dispatches a snapshot program on the device and hands
        out references (tens of milliseconds at a million
        partitions); an engine whose ``snapshot()`` hands out numpy
        holds the barrier for its own fetch and one copy
        (``Durability.<app>.persist_fetch_bytes`` against
        ``persist_deferred_bytes`` says which ran).

        ``mode='sync'`` (historical default) then fetches, serializes
        and writes inside the call; ``mode='async'`` (or
        ``@app:persist(mode='async')``) hands all of that to the
        checkpoint writer thread (durability/writer.py) with
        single-in-flight coalescing backpressure: the wait for the
        transfer, the store's write and the SHA-256 let the interpreter
        go, and what is pickled is an element's skeleton, its arrays
        out of band.  Incremental stores force the sync path (their
        digest chain cannot interleave with background writes) with a
        counted ``persistFallbackReason``."""
        from siddhi_tpu.util.persistence import IncrementalPersistenceStore
        from siddhi_tpu.util.snapshot import SnapshotService

        store = self._persistence_store()
        svc = self._snapshot_service()
        if mode is None:
            mode = self.app_context.persist_mode
        if mode not in ("sync", "async"):
            raise SiddhiAppRuntimeError(
                f"app '{self.name}': persist mode {mode!r} must be "
                "'sync' or 'async'")
        sm = self.app_context.statistics_manager
        st = self._durability_stats()
        if mode == "async" and isinstance(store, IncrementalPersistenceStore):
            if sm is not None:
                sm.record_persist_fallback(self.name,
                                           "incremental-store-sync-only")
            mode = "sync"
        revision = SnapshotService.new_revision(self.name)
        jr = self.app_context.input_journal
        if mode == "sync" and isinstance(store, IncrementalPersistenceStore):
            # historical incremental path, unchanged
            for s in self.sources:
                s.pause()
            self.drain_device_emits()
            try:
                kind, data = svc.incremental_snapshot()
                store.save(self.name, revision, kind, data)
            finally:
                for s in self.sources:
                    s.resume()
            st.persists_sync += 1
            if jr is not None:
                jr.mark_revision(revision)
            return revision

        def on_fallback(element, reason):
            st.capture_fallback_elements += 1
            if sm is not None:
                sm.record_persist_fallback(f"{self.name}.{element}", reason)

        # quiesce external input around the capture
        # (reference: SiddhiAppRuntimeImpl.persist:677-691 pauses sources)
        for s in self.sources:
            s.pause()
        tracer = self.app_context.tracer
        try:
            # the barrier: the app's process lock from the drain to the
            # end of the capture.  A batch sent from another thread
            # waits here; one that slipped in between a drain outside
            # the lock and the capture would have its rows delivered
            # into elements captured before its own engine's state
            with self.app_context.process_lock, (
                    tracer.free_span(STAGE_PERSIST_CAPTURE, "persist")
                    if tracer is not None else contextlib.nullcontext()):
                # queued device emits must land in downstream state
                # (selectors, windows, tables) before it is captured
                with span(STAGE_PERSIST_DRAIN):
                    self.drain_device_emits()
                capture = svc.capture(on_fallback=on_fallback)
            st.persist_fetch_bytes += capture.fetched_bytes
            if jr is not None:
                # watermark + ledger counts at the capture point; the
                # prune happens at commit, AFTER the store write lands
                jr.note_capture(revision)
        finally:
            for s in self.sources:
                s.resume()
        if mode == "async":
            writer = self._durability_writer()
            writer.submit(
                revision,
                lambda: self._persist_write(store, revision, capture),
                on_abandon=jr.drop_mark if jr is not None else None)
            return revision
        fi = self.app_context.fault_injector
        try:
            if fi is not None:
                fi.check("persist.write")
            self._persist_write(store, revision, capture)
        except Exception:
            # failed sync persist: abandon the journal mark so a later
            # commit cannot prune uncovered entries.  A simulated crash
            # (BaseException) keeps the mark — the journal models a log
            # that survives the process, marks included.
            if jr is not None:
                jr.drop_mark(revision)
            raise
        st.persists_sync += 1
        return revision

    def snapshot(self) -> bytes:
        """Raw snapshot bytes without a store (reference:
        SiddhiAppRuntimeImpl.snapshot)."""
        self.drain_device_emits()
        return self._snapshot_service().full_snapshot()

    def restore(self, snapshot: bytes):
        self._flush_persists()
        # barrier: pending emits flush into the PRE-restore state (the
        # synchronous path delivered them before restore was called)
        self.drain_device_emits()
        self._snapshot_service().restore(snapshot)
        jr = self.app_context.input_journal
        if jr is not None:
            # raw-bytes restore: the journal's revision mark and output
            # ledger no longer correspond to the restored state
            jr.reset()

    def _replay_journal(self, revision: str):
        """Restore-and-replay second half: re-send every input batch the
        journal recorded after ``revision`` was persisted, with the
        output ledger suppressing already-delivered callback/sink events
        — the observable sequence ends up bit-identical to an
        uninterrupted run (util/faults.py InputJournal)."""
        import logging

        log = logging.getLogger("siddhi_tpu")
        jr = self.app_context.input_journal
        if jr is None:
            return
        entries = jr.entries_after(revision)
        if entries is None:
            log.warning(
                "app '%s': input journal cannot replay after revision "
                "'%s' (unmarked revision or journal overflow); restored "
                "state only — post-checkpoint input is lost", self.name,
                revision)
            jr.reset()
            return
        if not self.app_context.app_running:
            if entries:
                log.warning(
                    "app '%s': %d journaled batch(es) pending but the "
                    "app is not running; start() it before restoring to "
                    "replay", self.name, len(entries))
            return
        jr.begin_replay(revision)
        try:
            for stream_id, batch in entries:
                self.input_manager.get_input_handler(stream_id).send_batch(
                    batch)
                if jr.stats is not None:
                    jr.stats.replayed_batches += 1
            # barrier INSIDE the replay window: deferred emits produced
            # by replayed batches must flow through the suppressing
            # ledger, not escape after end_replay as duplicates
            self.drain_device_emits()
        finally:
            jr.end_replay()
        if entries:
            log.info("app '%s': replayed %d journaled batch(es) after "
                     "revision '%s'", self.name, len(entries), revision)

    def restore_revision(self, revision: str):
        from siddhi_tpu.util.persistence import IncrementalPersistenceStore

        self._flush_persists()
        store = self._persistence_store()
        if isinstance(store, IncrementalPersistenceStore):
            chain = store.load_chain(self.name, until_revision=revision)
            if chain is None:
                raise SiddhiAppRuntimeError(
                    f"app '{self.name}': no base snapshot at or before "
                    f"revision '{revision}'")
            _, base_bytes, incs = chain
            self._snapshot_service().restore_incremental(
                base_bytes, [b for _, b in incs])
            self._replay_journal(revision)
            return
        data = store.load(self.name, revision)
        if data is None:
            raise SiddhiAppRuntimeError(
                f"app '{self.name}': revision '{revision}' not found"
            )
        # inline (not self.restore): the journal must survive the state
        # restore so the post-checkpoint batches can replay after it
        self.drain_device_emits()
        self._snapshot_service().restore(data)
        self._replay_journal(revision)

    def restore_last_revision(self) -> Optional[str]:
        """Restore the newest saved revision; returns its id (None when no
        revision exists — reference: SiddhiAppRuntimeImpl.restoreLastRevision).
        With an incremental store, replays newest base + later increments.
        A corrupted newest revision (truncated file, bad unpickle) is
        skipped with a warning and the walk falls back to older ones."""
        import logging

        from siddhi_tpu.core.exceptions import (
            CannotRestoreSiddhiAppStateError,
        )
        from siddhi_tpu.util.persistence import IncrementalPersistenceStore

        log = logging.getLogger("siddhi_tpu")
        # crash-restore post-mortem: freeze the pre-restore span ring
        # BEFORE state is replaced — it is the last evidence of what the
        # pipeline was doing when the previous incarnation died
        tracer = self.app_context.tracer
        if tracer is not None:
            tracer.dump("crash-restore")
        self._flush_persists()
        store = self._persistence_store()
        if isinstance(store, IncrementalPersistenceStore):
            chain = store.load_chain(self.name)
            if chain is None:
                return None
            base_rev, base_bytes, incs = chain
            self._snapshot_service().restore_incremental(
                base_bytes, [b for _, b in incs]
            )
            rev = incs[-1][0] if incs else base_rev
            self._replay_journal(rev)
            return rev
        revs = store.revisions(self.name)
        if not revs:
            return None
        last_error = None
        for rev in reversed(revs):
            try:
                self.restore_revision(rev)
                return rev
            except Exception as e:
                last_error = e
                log.warning(
                    "app '%s': revision '%s' failed to restore (%s); "
                    "falling back to the previous revision", self.name,
                    rev, e)
        raise CannotRestoreSiddhiAppStateError(
            f"app '{self.name}': all {len(revs)} persisted revisions "
            f"failed to restore (last error: {last_error})")

    def applied_time(self) -> int:
        """The app's clock as its snapshots record it: under
        @app:playback the event time of the last batch the state has
        applied; after a restore, the restored revision's.  A process
        that recovers sends the events after it."""
        return self.app_context.applied_time()

    def clear_all_revisions(self):
        self._flush_persists()
        self._persistence_store().clear_all_revisions(self.name)

    # Java-style aliases
    restoreRevision = restore_revision
    restoreLastRevision = restore_last_revision

    def get_stream_definitions(self):
        return self.siddhi_app.stream_definitions

    def query_names(self) -> List[str]:
        return list(self.query_runtimes)
