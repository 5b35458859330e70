"""Stream bus: junctions, input handlers, callbacks.

Re-design of the reference ``core/stream/`` (StreamJunction.java:61,
InputManager.java:33).  A junction is the per-stream pub/sub hub.  The
default mode is synchronous depth-first fan-out of columnar batches (the
reference's sync mode, StreamJunction.java:166-178); ``@async`` marks a
junction for host-side micro-batching: a queue + worker that coalesces
small sends into larger device-friendly batches (the Disruptor analog,
StreamJunction.java:276-313).

``@OnError(action='stream')`` routes failures to an auto-defined fault
stream ``!name`` with the original attributes plus ``_error``
(reference: StreamJunction.handleError:368-430).
"""

from __future__ import annotations

import logging
import queue
import threading
import traceback
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.context import SiddhiAppContext
from siddhi_tpu.core.event import (
    Event,
    EventBatch,
    batch_from_events,
    batch_from_rows,
    events_from_batch,
)
from siddhi_tpu.core.exceptions import OnErrorAction, SiddhiAppRuntimeError
from siddhi_tpu.observability.stall import WAITS_LOCK
from siddhi_tpu.query_api.definition import StreamDefinition

log = logging.getLogger("siddhi_tpu")


class StreamCallback:
    """User subscriber on a stream (reference:
    stream/output/StreamCallback.java).  Subclass and override
    ``receive`` or wrap a plain function via ``FunctionStreamCallback``."""

    stream_id: Optional[str] = None

    def receive(self, events: List[Event]):
        raise NotImplementedError

    def receive_batch(self, batch: EventBatch):
        """Columnar fast path; default converts to row events."""
        self.receive(events_from_batch(batch))


class FunctionStreamCallback(StreamCallback):
    def __init__(self, fn: Callable[[List[Event]], None]):
        self.fn = fn

    def receive(self, events: List[Event]):
        self.fn(events)


class QueryCallback:
    """Per-query subscriber receiving (timestamp, current, expired)
    (reference: query/output/callback/QueryCallback)."""

    def receive(self, timestamp: int, in_events: Optional[List[Event]], out_events: Optional[List[Event]]):
        raise NotImplementedError


class FunctionQueryCallback(QueryCallback):
    def __init__(self, fn):
        self.fn = fn

    def receive(self, timestamp, in_events, out_events):
        self.fn(timestamp, in_events, out_events)


class StreamJunction:
    """Per-stream pub/sub hub carrying columnar batches."""

    def __init__(
        self,
        definition: StreamDefinition,
        app_context: SiddhiAppContext,
        is_async: bool = False,
        buffer_size: int = 1024,
        batch_size_max: Optional[int] = None,
        on_error: str = OnErrorAction.LOG,
        fault_junction: Optional["StreamJunction"] = None,
    ):
        self.definition = definition
        self.stream_id = definition.id
        self.app_context = app_context
        self.receivers: List = []  # objects with .receive(EventBatch)
        self.callbacks: List[StreamCallback] = []
        self.on_error = on_error
        self.fault_junction = fault_junction
        self.is_async = is_async
        self.batch_size_max = batch_size_max or buffer_size
        self._queue: Optional[queue.Queue] = queue.Queue(maxsize=buffer_size) if is_async else None
        self._worker: Optional[threading.Thread] = None
        self._running = False
        self.throughput_tracker = None  # set when statistics enabled
        # dispatch cycles through this junction (host hop accounting:
        # fused chains keep this at 0 on intermediate streams — the
        # bench/test `junctionHops` counter)
        self.dispatches = 0

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        self._running = True
        if self.is_async:
            self._worker = threading.Thread(
                target=self._drain, name=f"junction-{self.stream_id}", daemon=True
            )
            self._worker.start()

    def stop(self):
        self._running = False
        if self._worker is not None:
            # the worker exits via the _running flag after its current
            # dispatch; the sentinel only matters when it is parked in
            # get() on an EMPTY queue — so never block on a FULL one
            # (a blocking put here deadlocks: the flagged worker stops
            # consuming and the queue never drains)
            try:
                self._queue.put_nowait(None)
            except queue.Full:
                pass
            self._worker.join(timeout=5)
            self._worker = None
            # free ring slots so producer threads blocked in put() on a
            # full queue complete their (discarded — pending batches are
            # dropped at stop) send instead of blocking forever
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass

    def subscribe(self, receiver):
        if receiver not in self.receivers:
            self.receivers.append(receiver)

    def add_callback(self, callback: StreamCallback):
        callback.stream_id = self.stream_id
        self.callbacks.append(callback)

    # -- send paths ---------------------------------------------------------

    def send(self, batch: EventBatch):
        if len(batch) == 0:
            return
        if self.throughput_tracker is not None:
            self.throughput_tracker.add(len(batch))
        if self.is_async and self._running:
            jr = getattr(self.app_context, "input_journal", None)
            if jr is not None and jr.replaying:
                # journal replay (replan / restore) runs single-threaded
                # under the process lock on FRESH junctions whose queues
                # are empty: dispatch inline so every re-delivery crosses
                # the suppressing ledger INSIDE the replay window — a
                # queued batch the worker dispatches after end_replay()
                # would escape suppression and double-emit
                self._dispatch(batch)
                return
            self._queue.put(batch)
            return
        self._dispatch(batch)

    def _drain(self):
        """Async worker: coalesce queued batches up to batch_size_max —
        micro-batching for device efficiency (the StreamHandler batching
        analog, util/event/handler/StreamHandler.java:57)."""
        while self._running:
            item = self._queue.get()
            if item is None:
                break
            batches = [item]
            total = len(item)
            while total < self.batch_size_max:
                try:
                    nxt = self._queue.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    self._running = False
                    break
                batches.append(nxt)
                total += len(nxt)
            # the worker is this batch's entry: on the tracer's clock as
            # InputHandler.send_batch is (observability/stall.py)
            tracer = self.app_context.tracer
            stamp = tracer.send_begins() if tracer is not None else None
            try:
                self._dispatch(EventBatch.concat(batches))
            finally:
                if stamp is not None:
                    tracer.send_ends(stamp, total)

    def _dispatch(self, batch: EventBatch):
        self.dispatches += 1
        # watchdog liveness: one beat per dispatched batch (robustness/)
        self.app_context.progress.beat()
        for r in self.receivers:
            try:
                r.receive(batch)
            except Exception as e:  # noqa: BLE001 — fault-stream contract
                self._handle_error(batch, e)
        if self.callbacks:
            # crash-recovery output ledger: receivers (query chains)
            # always reprocess during replay — they rebuild state — but
            # user-visible callbacks get the already-delivered prefix
            # suppressed so the observable sequence never duplicates
            jr = getattr(self.app_context, "input_journal", None)
            cb_batch = batch
            if jr is not None:
                cb_batch = jr.deliver(("stream", self.stream_id), batch)
                if cb_batch is None:
                    return
            fi = getattr(self.app_context, "fault_injector", None)
            for cb in self.callbacks:
                try:
                    if fi is not None:
                        fi.check("callback")
                    cb.receive_batch(cb_batch)
                except Exception as e:  # noqa: BLE001
                    self._handle_error(cb_batch, e)

    def route_fault(self, batch: EventBatch, e: Exception) -> bool:
        """Send ``batch`` + the error into this stream's ``!stream``
        fault junction (the @OnError(action='STREAM') contract); False
        when no STREAM fault route is configured.  Shared by the
        processing chain (_handle_error) and sink publish failures
        (Sink.on_error)."""
        if self.on_error != OnErrorAction.STREAM or self.fault_junction is None:
            return False
        fd = self.fault_junction.definition
        err = np.empty(len(batch), dtype=object)
        err[:] = e
        cols = dict(batch.columns)
        cols["_error"] = err
        self.fault_junction.send(
            EventBatch(fd.id, fd.attribute_names, cols, batch.timestamps, batch.types)
        )
        return True

    def _handle_error(self, batch: EventBatch, e: Exception):
        if self.route_fault(batch, e):
            return
        log.error(
            "error processing events on stream '%s' in app '%s': %s",
            self.stream_id,
            self.app_context.name,
            e,
            exc_info=e,
        )
        for listener in self.app_context.exception_listeners:
            listener(e)


class InputHandler:
    """External event entry for one stream (reference:
    stream/input/InputHandler.java:50-97).  Accepts single events, rows,
    or lists; stamps timestamps from the app clock when absent."""

    def __init__(self, junction: StreamJunction, app_context: SiddhiAppContext):
        self.junction = junction
        self.app_context = app_context
        self.definition = junction.definition

    def _check_running(self):
        # reference: InputHandler.send throws when the app is not
        # running (InputHandler.java:50-97 "cannot send event")
        if not getattr(self.app_context, "app_running", True):
            raise SiddhiAppRuntimeError(
                f"Siddhi app '{self.app_context.name}' is not running, "
                "cannot send events")

    def send(self, data: Union[Event, Sequence, List[Event]], timestamp: Optional[int] = None):
        self._check_running()
        # on the tracer's clock from here to the return, whatever the
        # sample (observability/stall.py): None with tracing off
        tracer = self.app_context.tracer
        stamp = tracer.send_begins() if tracer is not None else None
        n = 0
        try:
            tsgen = self.app_context.timestamp_generator
            if isinstance(data, Event):
                events = [data]
            elif isinstance(data, list) and data and isinstance(data[0], Event):
                events = data
            else:
                ts = timestamp if timestamp is not None else tsgen.current_time()
                events = [Event(ts, list(data))]
            n = len(events)
            for e in events:
                if e.timestamp < 0:
                    e.timestamp = tsgen.current_time()
                tsgen.set_event_time(e.timestamp)
            batch = batch_from_events(self.definition, events)
            batch = self._admit(batch)
            if batch is None:
                return
            if stamp is not None:
                stamp.waits = WAITS_LOCK   # what a stall's samples read
            with self.app_context.process_lock:
                if stamp is not None:
                    stamp.waits = None
                self._journal_and_check(batch)
                scheduler = self.app_context.scheduler
                if scheduler is not None:
                    scheduler.advance(tsgen.current_time())
                self.junction.send(batch)
                self.app_context.applied(max(e.timestamp for e in events))
        finally:
            if stamp is not None:
                tracer.send_ends(stamp, n)

    def send_batch(self, batch: EventBatch):
        self._check_running()
        tracer = self.app_context.tracer
        stamp = tracer.send_begins() if tracer is not None else None
        n = len(batch)
        try:
            newest = -1
            if n:
                # event time is monotone-max; one update per batch suffices
                newest = int(batch.timestamps.max())
                self.app_context.timestamp_generator.set_event_time(newest)
            batch = self._admit(batch)
            if batch is None:
                return
            if stamp is not None:
                stamp.waits = WAITS_LOCK
            with self.app_context.process_lock:
                if stamp is not None:
                    stamp.waits = None
                self._journal_and_check(batch)
                scheduler = self.app_context.scheduler
                if scheduler is not None:
                    scheduler.advance(self.app_context.timestamp_generator.current_time())
                self.junction.send(batch)
                self.app_context.applied(newest)
        finally:
            if stamp is not None:
                tracer.send_ends(stamp, n)

    def _admit(self, batch: EventBatch) -> Optional[EventBatch]:
        """Admission control (@app:limits, robustness/admission.py):
        trim the batch to the per-stream token budget BEFORE journaling
        — the journal records only admitted events, so a replay
        reproduces exactly the admitted set.  Replay itself bypasses
        admission (the decision was already made and journaled); apps
        without the annotation take the None fast path unchanged."""
        ac = getattr(self.app_context, "admission", None)
        if ac is None:
            return batch
        jr = getattr(self.app_context, "input_journal", None)
        if jr is not None and jr.replaying:
            return batch
        return ac.admit(self.junction.stream_id, batch)

    def _journal_and_check(self, batch: EventBatch):
        """Crash-recovery hook (under the process lock): journal the
        batch for restore-and-replay, then give the ``ingest`` injection
        site its shot.  A crash injected here fires AFTER the record —
        the batch is committed to the journal but never delivered, the
        exact state replay exists to repair."""
        jr = getattr(self.app_context, "input_journal", None)
        if jr is not None:
            jr.record(self.junction.stream_id, batch)
        # watchdog liveness: ingest accepted work (robustness/)
        self.app_context.progress.beat()
        fi = getattr(self.app_context, "fault_injector", None)
        if fi is not None:
            fi.check("ingest")


class InputManager:
    """Registry of input handlers (reference: stream/input/InputManager.java:33)."""

    def __init__(self, app_context: SiddhiAppContext):
        self.app_context = app_context
        self._handlers: Dict[str, InputHandler] = {}
        self._junctions: Dict[str, StreamJunction] = {}

    def register(self, junction: StreamJunction):
        self._junctions[junction.stream_id] = junction

    def get_input_handler(self, stream_id: str) -> InputHandler:
        if stream_id not in self._handlers:
            if stream_id not in self._junctions:
                raise SiddhiAppRuntimeError(f"stream '{stream_id}' is not defined")
            self._handlers[stream_id] = InputHandler(self._junctions[stream_id], self.app_context)
        return self._handlers[stream_id]
