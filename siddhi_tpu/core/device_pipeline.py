"""One device pipeline: the path from "step dispatched" to "rows at the
callback", written once for every device runtime.

Each runtime shell (core/device_single.py, core/dense_pattern.py,
core/fused_graph.py, core/hotkey_router.py, devtable/join.py) owns what
is particular to it — converting a junction batch to columns, interning,
routing, chunking, building the output ``EventBatch`` — and holds one
``DevicePipeline`` for the protocol they all share:

- the sampled-or-None cycle token of a batch, closed as raised when an
  exception leaves the batch path (:meth:`DevicePipeline.cycle`);
- the count gate: the blocking fetch of a step's match count, finished
  inline or left staged behind the next batch's dispatch as the stage
  decides from what it observes (core/ingest_stage.py), then either a
  counted skip or a device-resident ``PendingEmit`` in the bounded emit
  queue (core/emit_queue.py) — :meth:`DevicePipeline.submit`;
- the early copies: the step's counts, and the emit arrays of every
  chunk position whose last batch owed rows and has kept foretelling
  the next, start for the host when the step is dispatched, so the
  drain's fetch finds them there (:meth:`DevicePipeline._start_copies`,
  ``_Position``);
- the flush barrier, ingest stage before emit queue
  (:meth:`DevicePipeline.drain`); once the stage has ever left a
  batch in flight it takes the app's ``process_lock`` itself, so a
  bare ``drain()`` from a client thread is safe against the idle
  finisher;
- fault isolation: a batch that dies in the gate, the drain or the
  callback freezes the span ring and reaches the app's exception
  listeners (:meth:`DevicePipeline.on_fault`);
- the whole-state NaN/Inf quarantine, armed only while a
  ``state.poison`` fault is watched (:meth:`DevicePipeline.quarantine`).

A change to this seam — where the host waits for the count gate, what
rides its fetch — is a change for every runtime and every benchmark
cell.  The two multiplex groups (multiplex/) have no ingest stage and
guard poison per seat; they keep their own wiring.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional, Sequence

from siddhi_tpu.core.emit_queue import (
    EmitQueue,
    EmitStats,
    PendingEmit,
    fetch_coalesced,
)
from siddhi_tpu.core.ingest_stage import IngestStage, IngestStats
from siddhi_tpu.observability.stall import waits_on_device
from siddhi_tpu.observability.trace import STAGE_BUILD, STAGE_DELIVER, span
from siddhi_tpu.util import faults as _faults

log = logging.getLogger("siddhi_tpu")


class CountGate:
    """The ``pending`` of a step whose outputs are one device count
    scalar and a fixed list of device arrays (the hot-key scan, the
    devtable probe).  The engines' own deferred emits
    (``DeferredDenseEmit``, ``DeferredDeviceEmit``, the fused graph's)
    carry the same four methods."""

    __slots__ = ("count", "arrays")

    def __init__(self, count, arrays: Sequence):
        self.count = count
        self.arrays = arrays

    def probe(self):
        return self.count

    @waits_on_device
    def resolve(self) -> int:
        return int(fetch_coalesced([self.count])[0])

    def gates(self) -> Sequence:
        return [(self.count, self.arrays)]

    def device_arrays(self) -> Sequence:
        return self.arrays


class _Position:
    """What the pipeline has seen of one chunk position of one kind of
    pending: the evidence ``_start_copies`` goes by.  The forecast is
    the plainest there is: a position that owed rows on the last batch
    the pipeline has resolved will owe rows on this one.  It is scored
    on every batch, acted on or not: ``right`` counts the forecasts in
    a row that came true, and the arrays are started only once
    ``right`` has reached ``need``.  A copy that was started and never
    read (the gate came back 0) doubles ``need``; one that was read
    takes one off, down to 1.  So a stream that matches batch after
    batch is started early from its third batch on and stays so; one
    whose matches come and go in a pattern (a pass of batches of which
    some owe rows) pays a few wasted copies, each buying twice the
    patience, and is then left to fetch on demand as it always did;
    and one that settles down wins its way back.  Nothing here is a
    size or a time: a wasted copy of 20 MB and one of 70 kB count alike,
    since what a started copy saves is a round trip whatever it
    carries."""

    __slots__ = ("owed", "right", "need")

    def __init__(self):
        self.owed = False
        self.right = 0
        self.need = 1

    def start(self) -> bool:
        return self.owed and self.right >= self.need

    def saw(self, forecast: bool, started: bool, owed: bool) -> None:
        """The batch is resolved: ``owed`` rows or not, where
        ``forecast`` was this position's ``owed`` when the batch was
        dispatched and ``started`` whether its arrays were."""
        if forecast:
            self.right = self.right + 1 if owed else 0
            if started:
                self.need = max(1, self.need - 1) if owed else self.need * 2
        self.owed = owed


class _Cycle:
    """A sampled cycle's ``with`` block: hands out the token and closes
    it as raised when an exception leaves the batch path."""

    __slots__ = ("tok",)

    def __init__(self, tok):
        self.tok = tok

    def __enter__(self):
        return self.tok

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.tok.raised()
        return False


class _NoCycle:
    """What an unsampled cycle gets: one shared object, nothing
    allocated; ``with pipeline.cycle(n) as tok`` binds ``tok`` to None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_CYCLE = _NoCycle()


class DevicePipeline:
    """Count gate, emit push, fault isolation and poison quarantine of
    one device runtime.  Everything comes from the ``app_context``: the
    tracer, the ``@app:faults`` injector, the exception listeners, the
    clock, the idle finisher, the ``emit.depth`` and a pinned
    ``ingest.depth`` of ``@app:execution``.  Without a context (a
    runtime built by hand in a test) it is depth 1, untraced, with no
    injector, listeners, clock or finisher, so always inline.
    """

    def __init__(self, app_context=None, engine_kind: str = "device"):
        self._ctx = app_context
        # labels this runtime's spans (observability/trace.py)
        self.engine_kind = engine_kind
        self.tracer = getattr(app_context, "tracer", None)
        # @app:faults(...) injector: arms the ingest.put / emit.drain /
        # state.poison sites.  Isolation works with or without it.
        self.faults = getattr(app_context, "fault_injector", None)
        self.emit_stats = EmitStats()
        self.emit_queue = EmitQueue(
            depth=getattr(app_context, "tpu_emit_depth", 1),
            stats=self.emit_stats, faults=self.faults,
            on_fault=self.on_fault)
        # ingest staging window: unset (None) the stage chooses per
        # batch between finishing the count gate inline and leaving one
        # batch in flight behind the next dispatch (PipelineRule);
        # @app:execution('tpu', ingest.depth='N') pins it
        self.ingest_stats = IngestStats()
        self.ingest_stage = IngestStage(
            depth=getattr(app_context, "tpu_ingest_depth", 1),
            stats=self.ingest_stats, faults=self.faults,
            on_fault=self.on_fault,
            finisher=getattr(app_context, "idle_finisher", None))
        self.emit_queue.step_in_flight = self.ingest_stage.__len__
        # kind of pending -> its chunk positions' records (the hot-key
        # shell hands one pipeline two kinds: cold rows and hot)
        self._positions: dict = {}
        # last known-poison-free host copy of the quarantined state,
        # kept only while a state.poison fault is watched
        self._last_good = None

    def attach(self, shell, engine=None) -> None:
        """Wire a runtime shell to this pipeline.  The shell keeps, as
        plain aliases, the attributes the statistics feed, the planner
        and the tests read off a runtime; the engine carries the stats
        ref so ``staged_put`` (ops layer) counts its device puts, and
        the injector its step hook reads."""
        for name in ("engine_kind", "tracer", "faults", "emit_stats",
                     "emit_queue", "ingest_stats", "ingest_stage", "drain"):
            setattr(shell, name, getattr(self, name))
        if engine is not None:
            engine.ingest_stats = self.ingest_stats
            if self.faults is not None:
                engine.faults = self.faults

    def now(self) -> Optional[int]:
        """The app clock, for the shell to sample at RECEIVE time and
        bind into its ``deliver``: a deferred emit replays with the
        ``now`` the synchronous path would have read (time-based rate
        limiters key their period grid off it)."""
        tg = getattr(self._ctx, "timestamp_generator", None)
        return tg.current_time() if tg is not None else None

    # -- the batch path ------------------------------------------------------

    def cycle(self, n: int, kind: Optional[str] = None):
        """``with pipeline.cycle(n) as tok``: one sampled-or-None cycle
        token per junction batch, its ingest span starting here."""
        self.ingest_stage.arrive()
        if self.tracer is None:
            return _NO_CYCLE
        tok = self.tracer.begin_cycle(kind or self.engine_kind, n)
        return _NO_CYCLE if tok is None else _Cycle(tok)

    def submit(self, tok, pending, build: Optional[Callable],
               emit: Optional[Callable]) -> None:
        """Stage one dispatched step.  ``pending`` has ``probe()``,
        ``resolve() -> int``, ``gates()`` and ``device_arrays()`` (None:
        the batch made no device work); once its arrays are fetched,
        ``build(host_arrays)`` makes the batch's ``EventBatch`` of them
        (None or empty: nothing to hand on) and ``emit(batch)`` hands it
        to the output chain and the user's callback.  The two are the
        ``build`` and ``deliver`` spans of the cycle the drain has open
        (core/emit_queue.py): with the ``fetch`` before them they tile
        ``emit``, whatever the shell.

        The count-gate fetch (``resolve``) is what blocks on the device;
        staging it lets batch N+1's H2D put + step dispatch go out
        before batch N's scalar is fetched.  ``finish`` returns how
        long it kept the host: what the stage's rule goes by."""
        queue, stage = self.emit_queue, self.ingest_stage
        gates, plan, started = ((), (), ()) if pending is None else (
            self._start_copies(pending))

        def deliver(host_arrays):
            with span(STAGE_BUILD) as sp:
                batch = build(host_arrays)
                n = 0 if batch is None else len(batch)
                if sp is not None:
                    sp.count = n
            if n:
                with span(STAGE_DELIVER, n):
                    emit(batch)

        def finish():
            blocked = None
            if pending is None:
                c = 0
            else:
                t0 = stage.clock()
                if tok is None:
                    c = pending.resolve()
                else:
                    with tok.step_wait():
                        c = pending.resolve()
                blocked = stage.clock() - t0
            if tok is not None:
                # count gate resolved: the jitted step finished
                tok.step_done(c)
            kept = pending.device_arrays() if c else ()
            early = (frozenset() if pending is None else
                     self._copies_used(pending, gates, plan, started, kept))
            if c == 0:
                queue.skip()
            else:
                queue.push(PendingEmit(kept, deliver, trace=tok,
                                       started=early))
            return blocked

        stage.submit(
            pending.probe() if pending is not None else None, finish,
            trace=tok, may_defer=self._in_lock())

    def _start_copies(self, pending):
        """Start, behind the step just dispatched, the copies to the
        host of what its gate and its drain will ask for: every chunk's
        count, then the emit arrays of each chunk position whose record
        says so (``_Position``).  A fetch on demand is a round trip
        after the step has ended; a copy queued here travels as soon as
        the step ends.  A position that owed nothing on the last
        resolved batch starts nothing, so a stream that matches nothing
        moves no column, and the first batches and those after a zero
        are fetched on demand as before.  Returns the gates, per gate
        ``(forecast, started)``, and the arrays started."""
        gates = pending.gates()
        seen = self._positions.setdefault(type(pending), [])
        while len(seen) < len(gates):
            seen.append(_Position())
        for count, _arrays in gates:
            count.copy_to_host_async()
        plan, started = [], []
        for pos, (_count, arrays) in zip(seen, gates):
            plan.append((pos.owed, pos.start()))
            if plan[-1][1]:
                for a in arrays:
                    a.copy_to_host_async()
                started.extend(arrays)
        if started:
            self.emit_stats.early_copy_batches += 1
        return gates, plan, started

    def _copies_used(self, pending, gates, plan, started, kept) -> frozenset:
        """The gate is resolved: tell every chunk position whether it
        owed rows (the counts are on the host), and count what the early
        copies were good for.  Returns the ids of the arrays in ``kept``
        (what the drain will fetch) whose copy was started."""
        for pos, (count, _arrays), (forecast, began) in zip(
                self._positions[type(pending)], gates, plan):
            pos.saw(forecast, began, int(count) != 0)
        if not started:
            return frozenset()
        ids = frozenset(map(id, started)) & frozenset(map(id, kept))
        if ids:
            self.emit_stats.early_copy_hits += 1
        self.emit_stats.early_copy_wasted_bytes += sum(
            a.nbytes for a in started if id(a) not in ids)
        return ids

    def drain(self) -> None:
        """Flush barrier: materialize and emit every queued batch (one
        coalesced transfer).  Called wherever host code could observe
        emit timing — snapshot/restore, timer fires, rate-limiter
        decisions, pull queries, purges, shutdown, debugger.  The ingest
        stage flushes first: staged batches must enqueue (or skip)
        before the emit queue drains, preserving the synchronous
        callback order.

        A stage that has ever left a batch in flight may be in the idle
        finisher's hands, which finishes under ``process_lock``: from
        then on a drain takes that lock too (re-entrant for a caller
        inside it), so a bare ``drain()`` from a client thread is safe.
        A stage that never did has no finisher to meet and drains as it
        always has, without it: a junction's async worker, which holds
        no lock and never defers, must not wait here for the lock of a
        sender that waits for the worker's queue."""
        lock = getattr(self._ctx, "process_lock", None)
        if lock is not None and self.ingest_stats.pipeline_entries:
            with lock:
                self.ingest_stage.flush()
                self.emit_queue.drain()
        else:
            self.ingest_stage.flush()
            self.emit_queue.drain()

    def _in_lock(self) -> bool:
        """Does the calling thread hold the app's ``process_lock``
        (``send_batch``, a scheduler tick, a snapshot; read from the
        context when used: a replan swaps it)?  Only then may a batch
        stay in flight: the finisher, which takes that lock, cannot meet
        a submit made inside it.  ``RLock._is_owned`` is CPython's own
        (``threading.Condition`` uses it); without it nothing defers."""
        owned = getattr(getattr(self._ctx, "process_lock", None),
                        "_is_owned", None)
        return owned is not None and owned()

    # -- faults --------------------------------------------------------------

    def notify(self, e: BaseException) -> None:
        """Feed a handled failure to the app's exception listeners."""
        _faults.notify_listeners(
            getattr(self._ctx, "exception_listeners", None), e)

    def on_fault(self, e: BaseException) -> None:
        """A batch just died in isolation (count gate, drain or
        callback): freeze the span ring so the post-mortem shows the
        cycles leading up to it, then tell the listeners."""
        if self.tracer is not None:
            self.tracer.dump(f"onerror-isolation:{type(e).__name__}")
        self.notify(e)

    def quarantine(self, state, init: Callable,
                   put_back: Optional[Callable] = None):
        """NaN/Inf quarantine of a whole device state, active only while
        a ``state.poison`` fault is watched.  Poisons ``state`` when the
        fault trips, then scans it; on detection puts the last clean
        host copy back (``put_back(host_state)``; by default every leaf
        through ``jnp.asarray``) or, when there is none, starts from
        ``init()``.  Returns ``(state, poisoned)``: the caller drops the
        corrupted batch's outputs when ``poisoned``
        (:meth:`drop_poisoned`)."""
        fi = self.faults
        if fi is None or not fi.watches("state.poison"):
            return state, False
        if fi.poisoned("state.poison"):
            state = _faults.poison_state(state)
        if not _faults.state_has_poison(state):
            self._last_good = _faults.host_copy(state)
            return state, False
        fi.stats.poison_quarantines += 1
        if self._last_good is not None:
            log.error("%s state poisoned (NaN/Inf); quarantining batch "
                      "and re-materializing last clean state",
                      self.engine_kind)
            if put_back is not None:
                return put_back(self._last_good), True
            import jax
            import jax.numpy as jnp

            return jax.tree_util.tree_map(jnp.asarray, self._last_good), True
        log.error("%s state poisoned (NaN/Inf) with no clean copy; "
                  "quarantining batch and re-initializing",
                  self.engine_kind)
        return init(), True

    def drop_poisoned(self, tok) -> None:
        """The shell drops the quarantined batch's outputs: tombstone
        its cycle in the step and freeze the ring."""
        if tok is not None:
            tok.aborted("step")
        if self.tracer is not None:
            self.tracer.dump("poison-quarantine")

    def forget_clean_copy(self) -> None:
        """A restore replaced the state: the quarantine's copy is of a
        state that no longer exists."""
        self._last_good = None
