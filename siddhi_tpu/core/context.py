"""Contexts: per-manager and per-app shared services.

Mirrors the reference ``core/config/`` (SiddhiContext / SiddhiAppContext,
SURVEY.md §2.2 Contexts) minus JVM thread machinery: the TPU build is
deterministic batch processing, so ThreadBarrier becomes a simple
processing lock and partition/group-by flow ids become explicit keyed-state
indices rather than ThreadLocals.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from .ingest_stage import IdleFinisher


class TimestampGenerator:
    """Event/wall time source.  In playback mode (@app:playback) current
    time derives from event timestamps (reference:
    util/timestamp/TimestampGeneratorImpl.java:31, currentTime :78)."""

    def __init__(self, playback: bool = False, increment_ms: int = 0):
        self.playback = playback
        self.increment_ms = increment_ms
        self._event_time: int = -1
        self.last_update_wall: float = time.monotonic()

    def current_time(self) -> int:
        if self.playback:
            return self._event_time + self.increment_ms if self._event_time >= 0 else 0
        return int(time.time() * 1000)

    def set_event_time(self, ts: int):
        self.last_update_wall = time.monotonic()
        if ts > self._event_time:
            self._event_time = ts

    def advance_idle(self) -> int:
        """Idle heartbeat: push event time forward by the increment when no
        events arrive (reference: TimestampGeneratorImpl idle-time timer)."""
        self.last_update_wall = time.monotonic()
        if self._event_time >= 0:
            self._event_time += self.increment_ms
        return self.current_time()


class ProgressBeat:
    """Monotone liveness counter for the watchdog (robustness/).

    Bumped on every journaled ingest and every junction dispatch — one
    integer increment per BATCH, not per event, so the hot path cost is
    negligible and behavior stays bit-identical.  The watchdog reads it
    against the pending-work gauges: beats frozen + work pending =
    stalled batch cycle.
    """

    __slots__ = ("beats",)

    def __init__(self):
        self.beats = 0

    def beat(self):
        self.beats += 1


class SiddhiContext:
    """Per-manager shared state: extensions, persistence stores, config
    (reference: config/SiddhiContext)."""

    def __init__(self):
        from siddhi_tpu.extension.registry import default_registry

        self.extensions = default_registry()
        self.persistence_store = None
        self.config: Dict[str, str] = {}
        from siddhi_tpu.util.config import InMemoryConfigManager

        self.config_manager = InMemoryConfigManager()
        self.attributes: Dict[str, object] = {}
        self.data_sources: Dict[str, object] = {}
        self.source_handler_manager = None
        self.sink_handler_manager = None
        self.record_table_handler_manager = None
        # Crash-recovery journals keyed by app name: the journal lives on
        # the MANAGER context so it survives a simulated runtime crash —
        # a fresh runtime for the same app picks it up and replays
        # post-checkpoint batches (util/faults.py InputJournal).
        self.input_journals: Dict[str, object] = {}
        # Multiplex groups live on the MANAGER context because grouping
        # is cross-app: distinct apps under one manager share engines
        # when their queries fingerprint alike (multiplex/registry.py).
        # Lazily created by the planner on first @app:multiplex app.
        self.multiplex_registry = None


class SiddhiAppContext:
    """Per-app shared state: name, time, scheduler, snapshot service,
    statistics (reference: config/SiddhiAppContext)."""

    def __init__(self, siddhi_context: SiddhiContext, name: str):
        self.siddhi_context = siddhi_context
        self.name = name
        self.playback = False
        self.playback_idle_ms = 0
        self.enforce_order = False
        self.root_metrics_level = "off"
        # @app:execution('tpu' | 'host'): 'tpu' routes eligible queries
        # through the jitted device paths with host fallback (the
        # BASELINE.json north-star gate); tpu_partitions sizes the
        # partition axis of dense pattern state, tpu_instances its
        # per-(partition, node) pending-instance capacity
        # reference contract: InputHandler.send before start()/after
        # shutdown() raises "app is not running" (InputHandler.java:50)
        self.app_running = False
        self.execution_mode = "host"
        self.tpu_partitions = 65536
        self.tpu_instances = 4
        # @app:execution('tpu', devices='N'): shard the dense partition
        # axis over an N-device jax.sharding.Mesh (None = single device)
        self.tpu_devices = None
        # @app:execution('tpu', emit.depth='N'): pending-emit queue
        # depth of the async emit pipeline (core/emit_queue.py) — device
        # runtimes hold up to N matched batches device-resident before
        # one coalesced drain.  1 (default) drains after every batch.
        # 'auto' derives the effective depth at runtime from observed
        # transfer RTT vs batch cadence (EmitDepthController).
        self.tpu_emit_depth = 1
        # ingest staging window (core/ingest_stage.py).  None (unset, or
        # ingest.depth='auto'): the stage chooses per batch.  send_batch
        # returns with the batch's callbacks delivered (count gate
        # finished inline) unless the stage has seen, four batches
        # running, a gate that kept the host for milliseconds and a
        # caller straight back for more: then ONE batch stays in flight
        # past send_batch's return, finished by the next batch's submit,
        # any flush barrier or, within about a cycle, the idle finisher
        # below.  @app:execution('tpu', ingest.depth='N') pins the
        # window: '1' always inline, 'N' > 1 each gate deferred until
        # N-1 later batches have dispatched (or a barrier).
        self.tpu_ingest_depth = None
        # @app:execution('tpu', agg.device.min.batch='N'): minimum batch
        # size before incremental aggregation uses the jitted device
        # segment-reduce instead of the host np.add.at path
        self.tpu_agg_min_batch = 512
        # @app:multiplex(slots='N'): pack this app's eligible queries
        # into manager-wide shared device engines (multiplex/) so ONE
        # jitted step serves every compatible tenant per cycle.  Off by
        # default; slots bounds the tenant axis of each shared engine.
        self.multiplex = False
        self.multiplex_slots = 8
        # @app:fuse: fuse chains of device-lowered queries linked by
        # `insert into` streams into ONE jitted program per chain, with
        # intermediate event columns kept in HBM (planner/fusion.py).
        # Off by default; ineligible chains fall back to the junction
        # path with counted reasons.
        self.fuse = False
        # @app:hotkeys(k='8', promote='0.25', demote='0.10'): skew-aware
        # hot-key routing (core/hotkey_router.py) — partitioned dense
        # pattern queries watch the junction's key histogram with a
        # space-saving sketch and route keys whose decayed traffic share
        # crosses `promote` onto the batched associative-scan engine
        # (k slots); they return to the dense path below `demote`
        # (hysteresis: demote < promote or thrash).  Off by default;
        # ineligible queries fall back with counted reasons.
        self.hotkeys = False
        self.hotkey_k = 8
        self.hotkey_promote = 0.25
        self.hotkey_demote = 0.10
        # @app:devtables(capacity='N'): store eligible tables as
        # device-resident columnar arrays (siddhi_tpu/devtable/) — one
        # [capacity] device column per attribute + validity lane, jitted
        # scatter mutations, [B,C] masked join probes.  Off by default;
        # ineligible tables/queries keep the host path with counted
        # devtableFallbackReasons.  capacity is the per-table slot count.
        self.devtables = False
        self.devtable_capacity = 1024
        # @app:plan(auto='true', hysteresis='0.3', interval='5 sec'):
        # cost-based unified lowering (planner/costmodel.py).  auto turns
        # the model on for un-annotated queries — it enumerates every
        # eligible lowering, scores them statically and picks the
        # cheapest; legacy annotations stay pins that override it.
        # hysteresis is the margin an alternative's predicted cost must
        # beat the active plan's observed cost by before the PlanMonitor
        # re-lowers the live query; interval (0 = no daemon) paces the
        # monitor's background sweep.
        self.plan_auto = False
        self.plan_hysteresis = 0.3
        self.plan_interval_ms = 0
        # Per-query path pins ('device', 'dense+hotkey', ...) that
        # override BOTH the annotations and the cost model — the replan
        # machinery rebuilds an app through these so the new runtime
        # lands on the exact target path (core/app_runtime.py replan()).
        self.plan_pins: Dict[str, str] = {}
        # @app:persist(interval='30 sec', mode='async'): default persist()
        # mode ('sync' keeps the historical stop-the-world behavior;
        # 'async' captures under the barrier and writes on the checkpoint
        # writer thread — durability/) and the optional periodic-persist
        # daemon interval (0 = no daemon).
        self.persist_mode = "sync"
        self.persist_interval_ms = 0
        # @app:persist(location='...', revisions.to.keep='N'): the app's
        # own durable store (durability/store.py ``open_store``), for a
        # deployment whose text has to name it; None = the manager's
        self.persistence_store = None
        # event time of the newest batch the state has applied (set
        # under the process lock, after the junction took the batch):
        # what a snapshot's ``clock`` says under @app:playback.  The
        # timestamp generator runs ahead of it: ``send_batch`` advances
        # that before it takes the lock, so a capture that wins the
        # lock would read a clock one batch ahead of the state.
        self.applied_event_time = -1
        # @app:limits(rate='N/s', burst='M', shed='drop|oldest|block',
        # block.max='1 sec', watchdog='2 sec', breaker='3',
        # breaker.cooldown='1 sec'): overload protection (robustness/).
        # All off by default — without the annotation the
        # admission/watchdog/breaker hooks are None and behavior is
        # bit-identical to an unprotected app.
        self.limits_rate = 0.0          # events/s per stream (0 = off)
        self.limits_burst = 0.0         # bucket depth (default = rate)
        self.limits_shed = "drop"
        self.limits_block_max_ms = 1000
        self.watchdog_deadline_ms = 0   # 0 = watchdog off
        self.breaker_threshold = 0      # 0 = breakers off
        self.breaker_cooldown_ms = 1000
        # live robustness handles: counters, admission controller.
        # Created by the planner when @app:limits is present; replan()
        # re-adopts BOTH onto the replacement context so budgets and
        # shed accounting survive a self-heal like the journal does.
        self.robustness = None
        self.admission = None
        # watchdog liveness counter — always present, always beating
        self.progress = ProgressBeat()
        self.timestamp_generator = TimestampGenerator()
        # one re-entrant lock quiesces the whole app for snapshot/restore —
        # the ThreadBarrier analog (reference: util/ThreadBarrier.java:30)
        self.process_lock = threading.RLock()
        # finishes, under that lock, a staged count gate that no later
        # batch came for; its thread starts when a stage first leaves a
        # batch in flight and is joined at shutdown
        self.idle_finisher = IdleFinisher(self)
        self.scheduler = None  # set by app runtime
        self.snapshot_service = None  # set by app runtime
        self.statistics_manager = None
        self.exception_listeners: List = []
        # @app:faults(...) fault-injection harness (util/faults.py).
        # None when chaos testing is off — every hook site no-ops.
        self.fault_injector = None
        # Cycle-correlated span tracer + flight recorder
        # (observability/trace.py), created unconditionally by the
        # planner (default-on at 1-in-64 sampling; @app:trace tunes or
        # disables it).  None only for hand-built contexts in tests.
        self.tracer = None
        # Bounded input journal for restore-and-replay (util/faults.py
        # InputJournal); shared through siddhi_context.input_journals so
        # it outlives a crashed runtime.  None = journaling disabled.
        self.input_journal = None

    def applied(self, event_time: int) -> None:
        """A batch whose newest event carries ``event_time`` has been
        applied (the caller holds the process lock)."""
        if event_time > self.applied_event_time:
            self.applied_event_time = event_time

    def applied_time(self) -> int:
        """The app's clock as a snapshot records it: under
        @app:playback the event time of the last batch the state has
        applied (-1 before the first), else the wall clock."""
        if self.playback:
            return self.applied_event_time
        return self.timestamp_generator.current_time()

    def restore_time(self, clock) -> None:
        """Set the clock a restored tree carries (None: a tree from
        before it was kept).  Under @app:playback event time goes back
        to it, or forward: the state is the revision's, so is its time."""
        if clock is None or not self.playback:
            return
        self.applied_event_time = self.timestamp_generator._event_time = clock

    def set_playback(self, enabled: bool, increment_ms: int = 0):
        self.playback = enabled
        self.timestamp_generator.playback = enabled
        self.timestamp_generator.increment_ms = increment_ms
