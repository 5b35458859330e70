"""Cycle-correlated span tracing for the device batch pipeline.

One :class:`Tracer` per app runtime hands out a monotonically
increasing cycle id per device-engine batch (``begin_cycle``).  The id
rides a :class:`CycleToken` through the existing async machinery:

    DevicePipeline.cycle (a runtime's batch)    -> begin_cycle (t0)
    IngestStage.submit (put + step dispatched)  -> tok.dispatched()  [ingest span]
    DevicePipeline.submit (count gate resolved) -> tok.step_done(n)  [step span]
      (a gate left staged behind the next dispatch: tok.step_begins() first,
       so the step span is always the host blocked on the gate)
    EmitQueue.drain (batch materialized)        -> tok.emitted(t0)   [emit span]

Inside those three, one flat vocabulary tiles the rest of a batch's
time in ``send_batch``.  The way in, inside (or, for ``intern`` on the
partitioned path, ahead of) ``ingest``: ``intern`` (keys to engine
rows), ``convert`` (host columns to padded device lanes), ``plan`` (the
dense engine's round plan), ``pane`` (the open tumbling pane's carried
rows joined to the batch, lengthBatch queries only), ``route``
(bucketing by shard, sharded engines only), ``put`` (one per H2D
transfer), ``dispatch`` (the call of the jitted step) and, every 256th
step of a dense pattern runtime, ``poll`` (the overflow total fetched:
it waits for the step just dispatched).  The way back, three siblings
that tile ``emit`` in this order: the coalesced ``fetch``, the
``build`` of the ``EventBatch`` from the fetched host arrays
(``materialize``, the column casts, the key side channels) and the
``deliver`` of it to the output chain and the user's callback.  Where
the output chain ends in a device table, the batch the table's callback
hands to it is one ``mutate`` inside that ``deliver``: the host's passes
over the key map, the slot allocation, the write lanes, their put and
the call of the scatter, whole and with no child (``convert``, ``put``
and ``dispatch`` stay the names of the way in).  A
span's parent is the span of the same cycle whose interval contains
it; siblings never overlap, so a stage's time is the plain sum of its
spans.

Two intervals are histograms only (``Stages.staged``,
``Stages.cycle``), with no tuple in the ring and no annotation, because
either would lie over other batches' spans and a reader that takes the
union of the ring (or puts a device's idle gap down to the innermost
host span) would read nothing of them: ``staged``, from a cycle's
dispatch to the start of its count-gate fetch where the gate was left
behind the next batch's dispatch (in the ring: the start of ``step``
less the end of ``ingest``, by cycle id; 0 for a gate finished
inline), and ``cycle``, from ``begin_cycle`` to the end of ``emit``:
how soon a batch's matches reach the callback (in the ring: the end of
the cycle's ``admit``, or its first span's start where it took none, to
its ``emit``'s end).

Three counts are tuples in the ring and no intervals (:func:`counted`):
``lanes``, the lanes the dense engine's programs step for the batch,
which it knows from the round plan and fetches nothing for;
``state_bytes``, the bytes of resident rows those programs gather (and
write back): a row of the layout's width a lane, whatever the row
holds; and ``stream``, the place of the batch's input stream among the
streams its pattern reads (0 for the first, and for every batch of a
pattern over one stream).

The code that does that work lives in engines that know no tracer
(``ops/``, ``parallel/``, ``core/ingest_stage.py``, the runtime
shells).  It reaches the cycle through :func:`span`: ``begin_cycle``
leaves the token open on the calling thread until its ingest span
ends, ``EmitQueue.drain`` opens each entry's again while its rows are
built and delivered (:func:`reopen`), and ``span`` reads it there.
Every sampled span is also a
``jax.profiler.TraceAnnotation('siddhi.<stage>')`` around the work, so
a profiler trace of the process carries the program's spans in its
host plane beside the device's operations (``step`` appears as
``siddhi.step_wait``, around the blocking count-gate fetch).  The
``siddhi.*`` scopes below name the phases of the jitted steps on the
device side of the same trace.

Free-running ``persist.capture`` / ``persist.write`` spans from the
checkpoint path (``Tracer.free_span``) draw ids from the same counter,
so a capture and its async write stay ordered against the batch cycles
around them.  Each is the open cycle of its own thread while it lasts,
so what the checkpoint does inside it (the drain, the state's fetch and
unpack, the freeze; the pickle, the hash, the store's write) records
its child spans through :func:`span`, as a batch's stages do.

Everything here is host-side bookkeeping OUTSIDE jit: a span is a
six-tuple appended to the flight recorder's deque (GIL-atomic) plus a
histogram bucket increment — no device arrays are touched, fetched or
materialized, which is what keeps the ``jit-purity`` and
``host-sync-hazard`` analysis rules clean with zero allowlist entries.

Sampling (``@app:trace(sample='1/64')``) gates token creation: an
unsampled cycle pays one ``itertools.count`` tick, a modulo and two
stores on the thread's local (``None`` as its open cycle, its send's
lead taken); every downstream hook short-circuits on ``token is None``
and every ``span`` site on one thread-local read — no token, span or
annotation is allocated.

The entry is on the clock whatever the sample (``sample='off'`` alone
turns it off with the rest): ``InputHandler.send_batch`` / ``send`` and
an async junction's worker call ``Tracer.send_begins`` and
``send_ends`` round every batch.  An unsampled, unstalled send pays for
them a read of its thread's :class:`~.stall.Sender` on the tracer's
thread-local, two clock reads, four stores on the ``Sender`` and one on
the thread's local (the send's lead, for the first cycle to take), the
update of the thread's typical send and four compares, and allocates
nothing: 0.72 us a send on the chip's host (1.72 at ``sample='1'``,
where the entry also opens the ``siddhi.admit`` annotation; 0.10 with
tracing off; PERF.md section 6, PR 55), against the benchmark's
shortest send of 2,295 us; ``InputHandler`` adds two stores round the
process lock's acquire (``Sender.waits``).  A send that runs far past
the typical one leaves a stall record that says why
(``observability/stall.py``), whose watching thread wakes twenty times
a second and reads one clock a sending thread.  For a sampled cycle the time from the
send's entry to ``begin_cycle`` is the ``admit`` span (the entry's own
work ahead of the cycle: the newest timestamp, admission, the process
lock, the journal, the scheduler, the junction and the receiver), and
the whole send goes into ``Stages.send``, a histogram alone like
``cycle`` and ``staged``: a tuple over the send would cover what
``host_unattributed`` is there to show.  With the cycle's spans and
these the default-on cost is whole.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, Optional

from .histograms import LatencyHistogram
from .recorder import FlightRecorder
from .stall import (SEED_SENDS, STALL_FACTOR, STALL_FLOOR_S, TYPICAL_WEIGHT,
                    Sender, StallWatch, log)

#: batch-cycle stages in pipeline order; the n_events slot of each
#: span carries the count named beside it
STAGE_ADMIT = "admit"        # events: send_batch's entry to begin_cycle
STAGE_INTERN = "intern"      # keys interned
STAGE_INGEST = "ingest"      # events
STAGE_CONVERT = "convert"    # events
STAGE_PLAN = "plan"          # rounds in the batch (its longest run of one key)
STAGE_PANE = "pane"          # tumbling panes the batch closed
STAGE_ROUTE = "route"        # events
STAGE_PUT = "put"            # bytes handed to device_put
STAGE_DISPATCH = "dispatch"  # 1 per call of a jitted step
STAGE_POLL = "poll"          # 1 per overflow poll (every 256th dense step)
STAGE_STEP = "step"          # events
STAGE_EMIT = "emit"          # rows
STAGE_FETCH = "fetch"        # bytes fetched
STAGE_BUILD = "build"        # rows built into the EventBatch
STAGE_DELIVER = "deliver"    # rows delivered
STAGE_MUTATE = "mutate"      # keys written to a device table (in deliver)
CYCLE_STAGES = (STAGE_ADMIT, STAGE_INTERN, STAGE_INGEST, STAGE_CONVERT,
                STAGE_PLAN, STAGE_PANE, STAGE_ROUTE, STAGE_PUT, STAGE_DISPATCH,
                STAGE_POLL, STAGE_STEP, STAGE_EMIT, STAGE_FETCH,
                STAGE_BUILD, STAGE_DELIVER, STAGE_MUTATE)
#: a count of a cycle that is no interval: one zero-width tuple in the
#: ring (:func:`counted`), no annotation and no histogram
STAGE_LANES = "lanes"        # lanes the batch's programs step (dense engine)
STAGE_STREAM = "stream"      # the batch's stream, as its place in stream_keys
STAGE_STATE_BYTES = "state_bytes"  # bytes of resident rows those lanes gather
CYCLE_COUNTS = (STAGE_LANES, STAGE_STREAM, STAGE_STATE_BYTES)
#: intervals of a cycle kept as histograms alone (module docstring)
STAGE_STAGED = "staged"      # dispatch to the start of a deferred gate's fetch
STAGE_CYCLE = "cycle"        # begin_cycle to the end of emit
STAGE_SEND = "send"          # a sampled send, entry to exit
#: what a further round of one batch repeats where rounds are stepped
#: from the host (the sharded engine; a device chunk of the window
#: path).  The dense engine runs its rounds on the device: whatever the
#: batch's longest run, it repeats them once, for all the rounds past
#: the first together.
ROUND_STAGES = (STAGE_CONVERT, STAGE_ROUTE, STAGE_PUT, STAGE_DISPATCH)
#: most spans one batch cycle records on a served path: every stage
#: once, the host preparation ahead of the rounds in two more pieces
#: (the runtime's column views, the engine's lane conversion), and a
#: second host-stepped round or, on the dense engine, the rounds
#: program's lanes, put and dispatch.  The tracer sizes the recorder's
#: ring from it.
SPANS_PER_CYCLE = (len(CYCLE_STAGES) + len(CYCLE_COUNTS) + 2
                   + len(ROUND_STAGES))
#: checkpoint-path stages (free-running, engine kind 'persist')
STAGE_PERSIST_CAPTURE = "persist.capture"
STAGE_PERSIST_WRITE = "persist.write"
#: inside ``persist.capture``, under the barrier: the emit drain, the
#: copy of every element (durability/capture.py ``freeze``: a device
#: array by reference), a tenant's rows unpacked into the logical form
#: on the host (multiplex/dense_group.py ``snapshot_tenant``; the dense
#: pattern engine makes that form on the device and records none)
STAGE_PERSIST_DRAIN = "persist.drain"
STAGE_PERSIST_UNPACK = "persist.unpack"
STAGE_PERSIST_FREEZE = "persist.freeze"
#: the wait for one captured device array's transfer (count: bytes),
#: wherever its host value is asked for (durability/capture.py
#: ``materialize``): inside ``persist.write`` for an async persist
STAGE_PERSIST_FETCH = "persist.fetch"
#: inside ``persist.write``, on the writer thread (count: bytes): an
#: element's pickle (what it holds in band; its arrays of a page or
#: more are left out), then a file's write and fsync and its SHA-256,
#: for the pickle and for each buffer left out of it
STAGE_PERSIST_PICKLE = "persist.pickle"
STAGE_PERSIST_HASH = "persist.hash"
STAGE_PERSIST_STORE = "persist.store"
PERSIST_STAGES = (
    STAGE_PERSIST_CAPTURE, STAGE_PERSIST_WRITE, STAGE_PERSIST_DRAIN,
    STAGE_PERSIST_FETCH, STAGE_PERSIST_UNPACK, STAGE_PERSIST_FREEZE,
    STAGE_PERSIST_PICKLE, STAGE_PERSIST_HASH, STAGE_PERSIST_STORE)
#: watchdog self-heal (robustness/watchdog.py): one span per trip,
#: covering the replan-driven restore-and-replay — recovery time is a
#: latency distribution like any other stage
STAGE_WATCHDOG_HEAL = "watchdog.heal"

_STAGES = CYCLE_STAGES + (
    STAGE_STAGED, STAGE_CYCLE, STAGE_SEND) + PERSIST_STAGES + (
    STAGE_WATCHDOG_HEAL,)

#: host spans on the profiler's clock are named ANNOTATION_PREFIX + stage;
#: the ``step`` stage appears as the blocking part of it, ``step_wait``
ANNOTATION_PREFIX = "siddhi."
ANNOTATION_STEP_WAIT = "step_wait"

#: ``jax.named_scope`` names of the jitted steps' phases: they reach the
#: ``op_name`` metadata of every HLO operation traced under them, so a
#: device trace groups operations by phase whatever XLA names them
SCOPE_DENSE_GATHER = "siddhi.dense.gather"      # ops/dense_nfa.py make_step
SCOPE_DENSE_ADVANCE = "siddhi.dense.advance"
# inside advance, a count node's part: the capture and count update, the
# `every` re-arm at the minimum, an open count's via-path clone
SCOPE_DENSE_KLEENE = "siddhi.dense.kleene"
# inside advance, a logical node's part: its sides' captures and side
# bits, an and-not's kill, the completion and the lane's release
SCOPE_DENSE_LOGICAL = "siddhi.dense.logical"
SCOPE_DENSE_SCATTER = "siddhi.dense.scatter"
SCOPE_DENSE_COUNT = "siddhi.dense.count"
# make_rounds: the wide rounds past a batch's first, and the run of
# narrow ones on resident rows; the step's four scopes nest under them
SCOPE_DENSE_ROUNDS = "siddhi.dense.rounds"
SCOPE_DENSE_RUN = "siddhi.dense.run"
SCOPE_SHARD_COUNT_PSUM = "siddhi.shard.count_psum"  # parallel/mesh.py
SCOPE_WINDOW_FILTER = "siddhi.window.filter"    # ops/device_query.py make_step
SCOPE_WINDOW_SLOT = "siddhi.window.slot"
SCOPE_WINDOW_AGGREGATE = "siddhi.window.aggregate"
SCOPE_WINDOW_EMIT = "siddhi.window.emit"
SCOPE_WINDOW_UPDATE = "siddhi.window.update"
SCOPE_WINDOW_COUNT = "siddhi.window.count"
# make_pane_step: every lengthBatch pane a batch closes, in one program
SCOPE_PANE_ASSIGN = "siddhi.pane.assign"        # lanes tiled [L, panes]
SCOPE_PANE_REDUCE = "siddhi.pane.reduce"        # per (pane, group) segment
SCOPE_PANE_EMIT = "siddhi.pane.emit"            # a group's last row, select
SCOPE_PANE_COUNT = "siddhi.pane.count"
# ops/fused_graph.py: a fused chain's program by the place of the stage.
# A stage's scope holds the hop that feeds it (the valid mask, the wired
# lanes) and its filter and select; a window stage's own
# ``siddhi.window.*`` scopes stay innermost inside it
SCOPE_FUSED_HEAD = "siddhi.fused.head"          # stage 0
SCOPE_FUSED_INTERIOR = "siddhi.fused.interior"  # every stage between
SCOPE_FUSED_TAIL = "siddhi.fused.tail"          # the last stage
SCOPE_FUSED_COUNT = "siddhi.fused.count"        # the emit count
# devtable/join.py, the probe of a stream-table join: the guard on the
# host's slot lane (the slot live and still holding the event's key: two
# gathers and a compare), the row gathers by that slot, the full join
# condition on the gathered lanes and the count
SCOPE_DEVTABLE_PROBE = "siddhi.devtable.probe"
SCOPE_DEVTABLE_GATHER = "siddhi.devtable.gather"
SCOPE_DEVTABLE_CONDITION = "siddhi.devtable.condition"
# devtable/storage.py ``_scatter_body``: a mutation batch's indexed
# writes, one a column and the validity lane, and its kills
SCOPE_DEVTABLE_SCATTER = "siddhi.devtable.scatter"
DEVICE_SCOPES = (
    SCOPE_DENSE_GATHER, SCOPE_DENSE_ADVANCE, SCOPE_DENSE_KLEENE,
    SCOPE_DENSE_LOGICAL, SCOPE_DENSE_SCATTER, SCOPE_DENSE_COUNT,
    SCOPE_DENSE_ROUNDS, SCOPE_DENSE_RUN, SCOPE_SHARD_COUNT_PSUM,
    SCOPE_WINDOW_FILTER, SCOPE_WINDOW_SLOT, SCOPE_WINDOW_AGGREGATE,
    SCOPE_WINDOW_EMIT, SCOPE_WINDOW_UPDATE, SCOPE_WINDOW_COUNT,
    SCOPE_PANE_ASSIGN, SCOPE_PANE_REDUCE, SCOPE_PANE_EMIT, SCOPE_PANE_COUNT,
    SCOPE_FUSED_HEAD, SCOPE_FUSED_INTERIOR, SCOPE_FUSED_TAIL,
    SCOPE_FUSED_COUNT, SCOPE_DEVTABLE_PROBE, SCOPE_DEVTABLE_GATHER,
    SCOPE_DEVTABLE_CONDITION, SCOPE_DEVTABLE_SCATTER)

# the calling thread's open cycle: set by begin_cycle (None for an
# unsampled cycle), cleared when the cycle's ingest span ends; set
# again by the emit drain round each entry's build and delivery
_open = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, bound on first use


def annotation(stage: str):
    """``jax.profiler.TraceAnnotation('siddhi.<stage>')``: outside a
    profiler session a flag test.  Made for sampled cycles only."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(ANNOTATION_PREFIX + stage)


class Span:
    """One sampled span in flight: a ``TraceAnnotation`` on the
    profiler's clock and, on exit, a tuple in the ring.  ``count`` may
    be set inside the ``with`` body, where the work is what yields it."""

    __slots__ = ("tok", "stage", "count", "t0", "_ann")

    def __init__(self, tok: "CycleToken", stage: str, count: int):
        self.tok = tok
        self.stage = stage
        self.count = count

    def __enter__(self) -> "Span":
        self._ann = annotation(self.stage)
        self._ann.__enter__()
        self.t0 = self.tok.tracer.clock()
        return self

    def __exit__(self, *exc) -> bool:
        now = self.tok.tracer.clock()
        self._ann.__exit__(*exc)
        self.tok.record(self.stage, self.t0, now, self.count)
        return False


class _NoSpan:
    """What :func:`span` hands an unsampled cycle: one shared object,
    nothing allocated; ``with span(...) as sp`` binds ``sp`` to None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


def span(stage: str, count: int = 0):
    """Context manager for one stage of the calling thread's open
    cycle; the shared no-op when that cycle is unsampled (or none is
    open).  For code that does a batch's work and knows no tracer."""
    tok = getattr(_open, "tok", None)
    return _NO_SPAN if tok is None else Span(tok, stage, count)


def counted(stage: str, count: int) -> None:
    """A count of the calling thread's open cycle that took no time of
    its own (``CYCLE_COUNTS``): nothing for an unsampled cycle."""
    tok = getattr(_open, "tok", None)
    if tok is not None:
        now = tok.tracer.clock()
        tok.record(stage, now, now, count)


def reopen(tok: Optional["CycleToken"]) -> Optional["CycleToken"]:
    """Make ``tok`` (a sampled cycle's token, or None) the calling
    thread's open cycle and hand back the one it replaces.  For the
    emit drain: an entry's rows are built and delivered long after its
    ingest span closed, by shells that reach the cycle through
    :func:`span` alone; a callback may re-enter ``send_batch``, whose
    ``begin_cycle`` takes the thread's open cycle, so the drain opens
    its own afresh for each entry and puts back what it found."""
    prev = getattr(_open, "tok", None)
    _open.tok = tok
    return prev


class CycleToken:
    """One sampled batch cycle's identity + in-flight timestamps.

    Created by ``Tracer.begin_cycle`` and threaded through
    ``IngestStage.submit`` and ``PendingEmit`` — each hook records its
    span and stamps the start of the next."""

    __slots__ = ("tracer", "cycle", "engine", "n_events", "n_emit",
                 "t_begin", "t0", "t_dispatch")

    def __init__(self, tracer: "Tracer", cycle: int, engine: str,
                 n_events: int, t0: float):
        self.tracer = tracer
        self.cycle = cycle
        self.engine = engine
        self.n_events = n_events
        self.n_emit = 0
        # the cycle's own start: ``ingest_begins`` moves ``t0`` past the
        # interning, ``Stages.cycle`` counts from here
        self.t_begin = t0
        self.t0 = t0
        self.t_dispatch = t0

    def record(self, stage: str, t_start: float, t_end: float,
               count: int) -> None:
        """A stage of this cycle whose interval the caller clocked."""
        self.tracer.record(self.cycle, stage, self.engine, t_start, t_end,
                           count)

    def ingest_begins(self) -> None:
        """Restart the ingest span: the partitioned receiver begins the
        cycle ahead of interning, and ingest starts after it."""
        self.t0 = self.t_dispatch = self.tracer.clock()

    def dispatched(self) -> None:
        """Receive-time work done: conversion + H2D put + jitted step
        dispatch are all queued.  Ends the ingest span, and with it the
        time this cycle is the thread's open one."""
        _open.tok = None
        now = self.tracer.clock()
        self.record(STAGE_INGEST, self.t0, now, self.n_events)
        self.t_dispatch = now

    def step_begins(self) -> None:
        """The count gate was left staged behind a later dispatch and is
        fetched only now: the step span, the host blocked on this
        cycle's gate, starts here and not at the dispatch.  How long it
        was left goes into ``Stages.staged`` alone: a span over it would
        lie over the next batch's whole way in."""
        now = self.tracer.clock()
        self.tracer.stage_hist[STAGE_STAGED].record_s(now - self.t_dispatch)
        self.t_dispatch = now

    def step_wait(self):
        """``siddhi.step_wait`` on the profiler's clock, for the caller
        to hold around the blocking count-gate fetch; the ring's
        ``step`` span (from dispatch, or from ``step_begins`` for a gate
        that was deferred, to ``step_done``) is the record."""
        return annotation(ANNOTATION_STEP_WAIT)

    def step_done(self, n_emit: int) -> None:
        """Count gate resolved: the jitted step (and the H2D transfer
        it waited on) finished on device.  Ends the step span."""
        now = self.tracer.clock()
        self.n_emit = n_emit
        self.record(STAGE_STEP, self.t_dispatch, now, self.n_events)

    def emitted(self, t_fetch_start: float) -> None:
        """This cycle's batch materialized on the host (post coalesced
        fetch + callback).  Ends the emit span, and the cycle: its whole
        life, arrival to callback, goes into ``Stages.cycle``."""
        now = self.tracer.clock()
        self.record(STAGE_EMIT, t_fetch_start, now, self.n_emit)
        self.tracer.stage_hist[STAGE_CYCLE].record_s(now - self.t_begin)

    def aborted(self, stage: str) -> None:
        """The cycle died inside ``stage`` (isolated fault): leave a
        zero-width tombstone span so the flight recorder shows where
        the batch was lost instead of a silent gap."""
        if getattr(_open, "tok", None) is self:
            _open.tok = None
        now = self.tracer.clock()
        self.record(f"{stage}.aborted", now, now, self.n_events)

    def raised(self) -> None:
        """An exception is leaving the batch path.  If this is still
        the thread's open cycle it died inside ingest: close it, so no
        later ``span`` of code that opens no cycle lands in it."""
        if getattr(_open, "tok", None) is self:
            self.aborted(STAGE_INGEST)


class Tracer:
    """Per-app cycle-id source, span sink and flight-recorder owner."""

    #: default: record every 64th cycle.  Every cycle (``sample='1'``)
    #: costs 5.4 ms window batches of 10 spans 3.2% on the chip (four
    #: pairs, -1.0% to -6.3%) and host-bound 14 ms pattern batches of 13
    #: spans 1.8% (three pairs, -1.4% to -3.7%): 17-20 us a span, the
    #: ring and the histogram a tenth of it (PERF.md section 6, PR 37;
    #: PR 26 read 2.6% on 12 ms window batches of 18 spans).  Since
    #: PR 55 a sampled cycle holds one span more, ``admit``: traced
    #: against traced, 2.3-2.4 ms window batches read ``send`` 0.8-1.0%
    #: longer than the parent's.  Whatever the sample, the stamp pair
    #: round every send costs 0.72 us (module docstring)
    DEFAULT_SAMPLE = 64
    #: default flight-recorder depth in cycles
    DEFAULT_CYCLES = 64

    def __init__(self, app_name: str, sample: int = DEFAULT_SAMPLE,
                 cycles: int = DEFAULT_CYCLES,
                 dump_dir: Optional[str] = None):
        self.app_name = app_name
        # 0 = tracing off; 1 = every cycle; N = every Nth cycle
        self.sample = max(0, int(sample))
        # the two persist spans can interleave with a batch's own
        self.recorder = FlightRecorder(
            app_name, cycles=cycles, spans_per_cycle=SPANS_PER_CYCLE + 2,
            dump_dir=dump_dir)
        self.clock = time.perf_counter
        self._ids = itertools.count(1)
        # pre-created so hot-path record() never mutates the dict
        self.stage_hist: Dict[str, LatencyHistogram] = {
            stage: LatencyHistogram() for stage in _STAGES}
        # the sends on the clock and the thread that watches them
        # (observability/stall.py); the free spans now open, by id, for
        # a stall's record to name a checkpoint still under way
        self.watch = StallWatch(self)
        self._mine = threading.local()   # .st: the thread's Sender
        self.free_open: Dict[int, tuple] = {}
        self.recorder.in_flight = self.watch.in_flight

    # -- the entry -----------------------------------------------------------

    def send_begins(self) -> Optional[Sender]:
        """Entry of ``InputHandler.send_batch`` / ``send`` and of an
        async junction's worker: stamp the calling thread's send.  None
        with tracing off, and for a send that a callback makes inside a
        send of its own thread: the outer send's stamp stands, the inner
        one is its callback's time."""
        if not self.sample:
            return None
        st = getattr(self._mine, "st", None)
        if st is None:
            st = self._mine.st = self.watch.sender()
        elif st.t_in > st.t_out:
            return None
        st.t_in = self.clock()
        # the send's lead is the first cycle's to take (``begin_cycle``)
        _open.lead = st
        if self.sample == 1:
            # every cycle is sampled: the lead is a span from here
            st.sampled = True
            st.ann = annotation(STAGE_ADMIT)
            st.ann.__enter__()
        return st

    def send_ends(self, st: Sender, n_events: int) -> None:
        """Exit of the send ``send_begins`` stamped: judge it against
        the thread's typical send, which it then joins unless it was a
        stall (a stall joins as the threshold it passed, so that a
        change of regime is learned and one stall moves little)."""
        now = self.clock()
        took = now - st.t_in
        # closed from here, whatever the record's writing does
        left, st.t_out = st.t_out, now
        st.n += 1
        if st.n <= SEED_SENDS:
            # compiles only ever add: the shortest seeds the mean
            st.typical = took if st.n == 1 else min(st.typical, took)
            if st.n == SEED_SENDS:
                self.watch.start()
        elif took < STALL_FLOOR_S or took < STALL_FACTOR * st.typical:
            st.typical += (took - st.typical) * TYPICAL_WEIGHT
        else:
            try:
                self.watch.stalled(st, left, n_events)
            except Exception:  # noqa: BLE001 — the send's result stands
                log.exception("app '%s': a stall's record failed",
                              self.app_name)
            st.typical += (STALL_FACTOR - 1) * st.typical * TYPICAL_WEIGHT
        if st.sampled:
            self.stage_hist[STAGE_SEND].record_s(took)
            if st.ann is not None:   # no cycle began: a host query's send
                st.ann.__exit__(None, None, None)
                st.ann = None
            st.sampled, st.cycle = False, 0

    # -- cycle ids -----------------------------------------------------------

    def begin_cycle(self, engine: str, n_events: int) -> Optional[CycleToken]:
        """Start one batch cycle; None when this cycle is unsampled
        (every downstream hook no-ops on a None token).  Either way it
        becomes the calling thread's open cycle, which :func:`span`
        reads, so no span of this batch lands in an older cycle."""
        if not self.sample or (cid := next(self._ids)) % self.sample:
            _open.tok = None
            _open.lead = None
            return None
        now = self.clock()
        tok = _open.tok = CycleToken(self, cid, engine, n_events, now)
        st = getattr(_open, "lead", None)
        if st is not None:
            # the first cycle of the thread's open send takes the lead
            # as its ``admit``; a later one's lies in the cycles before
            _open.lead = None
            if st.watch is self.watch and st.t_in > st.t_out:
                if st.ann is not None:
                    st.ann.__exit__(None, None, None)
                    st.ann = None
                st.sampled, st.cycle = True, cid
                self.record(cid, STAGE_ADMIT, engine, st.t_in, now, n_events)
        return tok

    # -- span sink -----------------------------------------------------------

    def record(self, cycle: int, stage: str, engine: str,
               t_start: float, t_end: float, n_events: int) -> None:
        self.recorder.record((cycle, stage, engine, t_start, t_end,
                              n_events))
        hist = self.stage_hist.get(stage)
        if hist is not None:
            hist.record_s(t_end - t_start)

    def record_span(self, stage: str, engine: str, t_start: float,
                    t_end: float, n_events: int = 0,
                    cycle: Optional[int] = None) -> int:
        """Free-running span with no children (the watchdog's heal):
        allocates its own cycle id from the shared counter unless the
        caller correlates one."""
        cid = cycle if cycle is not None else next(self._ids)
        self.record(cid, stage, engine, t_start, t_end, n_events)
        return cid

    @contextlib.contextmanager
    def free_span(self, stage: str, engine: str):
        """One free-running span (a checkpoint's capture or write) as
        the calling thread's open cycle: :func:`span` inside the body
        records its children under the same id.  The span itself is
        recorded when the body returns; one that raises leaves its
        children and the counters."""
        tok = CycleToken(self, next(self._ids), engine, 0, self.clock())
        found = reopen(tok)
        self.free_open[tok.cycle] = (stage, tok.t_begin)
        try:
            with annotation(stage):
                yield tok
        finally:
            reopen(found)
            del self.free_open[tok.cycle]
        tok.record(stage, tok.t_begin, self.clock(), 0)

    # -- read-out ------------------------------------------------------------

    def stage_stats(self) -> Dict[str, Dict[str, float]]:
        """stage -> quantile read-out, only for stages that recorded
        (an app with no device engines reports nothing)."""
        out: Dict[str, Dict[str, float]] = {}
        for stage, hist in self.stage_hist.items():
            if hist.count == 0:
                continue
            out[stage] = {
                "spans": hist.count,
                "p50Ms": hist.p50_ms(),
                "p95Ms": hist.p95_ms(),
                "p99Ms": hist.p99_ms(),
                "maxMs": hist.max_ms,
            }
        return out

    def histograms(self):
        """(stage, LatencyHistogram) pairs with data — the Prometheus
        exposition's histogram families."""
        return [(stage, hist) for stage, hist in self.stage_hist.items()
                if hist.count]

    def dump(self, reason: str) -> dict:
        return self.recorder.dump(reason)

    def reset(self) -> None:
        for hist in self.stage_hist.values():
            hist.reset()
