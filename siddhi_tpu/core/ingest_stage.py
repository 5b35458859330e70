"""Ingest-side staging pipeline: the count gate behind the next dispatch.

A ``device_put`` and the call of a jitted step return at once (JAX
enqueues both); what keeps the host is the count-gate fetch every
engine performs after dispatching its step, which blocks until the H2D
transfer AND the step have finished.  Finished inline, host and device
take turns: the host waits for the step, then the device waits while
the host prepares the next batch.

This module holds the pieces every device runtime shares:

- ``IngestStats``: per-runtime staging counters surfaced through
  ``util/statistics.py`` (``stagedBatches`` / ``devicePuts`` /
  ``putLeaves`` / ``deviceChunks`` / ``steppedLanes`` /
  ``steppedStateBytes`` /
  ``plannedRepeats`` / ``batchesByStream.<stream>`` / ``fusedHops`` /
  ``ingestStalls`` / ``overlappedBatches`` / ``flushSyncs`` /
  ``maxStagingDepth``, and how often the window opened:
  ``gatesBySubmit`` / ``gatesByIdle`` / ``pipelineEntries`` /
  ``pipelineExits``).
- ``IngestStage``: a bounded staging window.  ``submit(probe, finish)``
  records one dispatched batch whose count gate has NOT been fetched
  yet; the oldest entry's ``finish`` (fetch count, enqueue/skip its
  emit) runs once the window holds ``depth`` entries.

  **When ``send_batch`` returns with its callbacks delivered.**  At
  window 1 always: the gate is finished inline, as synchronous ingest
  did.  The stage opens the window to 2 by itself (``PipelineRule``,
  below) only while it observes a caller that comes straight back for
  more and a gate that keeps the host waiting: ``ENGAGE_RUN`` batches
  running whose inline gate took ``BLOCKED_MIN_S`` or more and whose
  sender was back within ``THINK_SHARE`` of it.  On the chip that is
  every closed loop over a device query (a window, a fused chain, a
  table join's probe, a pattern); it is never a handful of batches, a
  paced source or a console.  Then ONE batch stays in flight past
  ``send_batch``'s return, its callbacks owed, its gate fetched after
  the next batch's conversion, ``device_put`` and step dispatch are
  out, so the step of batch N runs while the host prepares N+1.  What
  bounds it: never more than one batch; the next ``submit``, any flush
  barrier or,
  when neither comes within about a cycle, the app's ``IdleFinisher``
  thread finishes it, in submit order; one slow arrival, a barrier or
  an idle finish returns the stage to inline.  An explicit
  ``ingest.depth='N'`` pins the window at N and switches the rule off
  (``'1'``: always inline; ``'2'`` and up: always staged, finished by
  the next submits or a barrier only).
- ``IdleFinisher``: the one daemon thread of an app that finishes a
  staged gate no arrival came for.
- ``staged_put``: the single sanctioned ``jax.device_put`` wrapper for
  ingest paths — arms the ``ingest.put`` fault-injection site with the
  same bounded retry-with-backoff the sharded engine used, so the
  crash-recovery journal semantics of the fault harness hold on every
  engine (tests/test_ingest_guard.py enforces that no ingest path
  bypasses it).

Exactness contract: state advancement, key interning and timer
bookkeeping all still happen at receive time — ONLY the count fetch and
the emit enqueue defer, and those already have barrier discipline from
the emit queue.  Runtimes flush the stage at every point the emit queue
drains (snapshot/restore, pull queries, timer fires, shutdown,
debugger), and always BEFORE draining the emit queue, so callback
content and order stay bit-identical to synchronous ingest.  A batch
stays in flight only if its submit came from inside the app's
``process_lock`` (``send_batch``, a scheduler tick), the finisher
finishes under that lock, and a drain of a stage that has ever deferred
takes it (core/device_pipeline.py): so an entry is finished once, by
one of the three, oldest first.  A junction's async worker holds no
lock: its submits never defer and its stage never meets the finisher.

What defers with the emit is whatever the emit does: a query whose
rows end in a table or a named window would leave its last batch
unwritten past ``send_batch``'s return, and a reader on another stream
would miss it.  The planner, which knows where a query's rows go, pins
such a query's stage at 1 (planner/app_planner.py
``_pin_state_writers``); nothing here knows it.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, Optional, Tuple

from ..observability.trace import STAGE_PUT, span
from .exceptions import TransferFaultError

log = logging.getLogger("siddhi_tpu.ingest")


class IngestStats:
    """Staging counters for one device runtime (host-side ints, same
    thin-gauge style as ``EmitStats``)."""

    __slots__ = ("staged_batches", "device_puts", "put_leaves",
                 "device_chunks",
                 "stepped_lanes", "stepped_state_bytes", "planned_repeats",
                 "batches_by_stream",
                 "fused_hops",
                 "ingest_stalls",
                 "overlapped_batches", "flush_syncs",
                 "dropped_batches",
                 "max_staging_depth", "auto_depth", "gates_by_submit",
                 "gates_by_idle", "pipeline_entries", "pipeline_exits")

    def __init__(self):
        self.staged_batches = 0
        self.device_puts = 0
        # arrays those puts handed to ``device_put``: a leaf costs the
        # host about 0.2 ms on the chip whatever its size (PERF.md
        # section 6), so an engine whose batch crosses as one packed
        # buffer reads ``put_leaves == device_puts``
        self.put_leaves = 0
        # chunks the batches were cut into for the device (the window
        # path, ops/device_query.py: a chunk is a put and a dispatch)
        self.device_chunks = 0
        # lanes the dense engine's programs stepped, padding and all: a
        # batch's first program at its padded width, every later round
        # at the width its loop slices it at (ops/dense_nfa.py
        # ``rounds_lanes``); over the events sent, the lanes an event
        # costs the device.  0 on every other engine
        self.stepped_lanes = 0
        # bytes of resident rows those lanes gathered (and wrote back):
        # a lane is one row of the layout's width, 4 bytes a word
        # (ops/dense_layout.py).  0 on every other engine
        self.stepped_state_bytes = 0
        # events past their partition's first in their batch: what the
        # dense engine's round plan sorts, and what goes through a
        # second call of the step or the rounds program
        # (ops/dense_nfa.py ``round_plan``).  0 on every other engine
        self.planned_repeats = 0
        # non-empty batches the dense engine took, by the input stream
        # they came on (ops/dense_nfa.py ``process_deferred``): a
        # pattern over two streams steps another program for each.
        # Empty on every other engine
        self.batches_by_stream = {}
        # junction hops a fused chain kept on the device: stages - 1 a
        # batch (core/fused_graph.py); 0 on every other runtime
        self.fused_hops = 0
        # staged batches whose finish (the count-gate fetch, where XLA
        # reports an asynchronous step failure) raised and was isolated
        self.dropped_batches = 0
        # was the probe ready when the host got to the gate (inline or
        # behind a later submit)?  ready: overlapped; not: a stall
        self.ingest_stalls = 0
        self.overlapped_batches = 0
        # who finished a gate that outlived its own submit: a later
        # submit, a flush barrier (flush_syncs), the idle finisher
        self.gates_by_submit = 0
        self.flush_syncs = 0
        self.gates_by_idle = 0
        # switches of the rule into and out of the pipelined regime
        self.pipeline_entries = 0
        self.pipeline_exits = 0
        self.max_staging_depth = 0
        # the window the rule runs at now (0 = pinned: ingest.depth, the
        # debugger, or the planner for a query that writes state)
        self.auto_depth = 0

    def note_depth(self, depth: int):
        if depth > self.max_staging_depth:
            self.max_staging_depth = depth

    def as_dict(self) -> dict:
        return {
            "stagedBatches": self.staged_batches,
            "devicePuts": self.device_puts,
            "putLeaves": self.put_leaves,
            "deviceChunks": self.device_chunks,
            "steppedLanes": self.stepped_lanes,
            "steppedStateBytes": self.stepped_state_bytes,
            "plannedRepeats": self.planned_repeats,
            "fusedHops": self.fused_hops,
            "ingestStalls": self.ingest_stalls,
            "overlappedBatches": self.overlapped_batches,
            "gatesBySubmit": self.gates_by_submit,
            "flushSyncs": self.flush_syncs,
            "gatesByIdle": self.gates_by_idle,
            "pipelineEntries": self.pipeline_entries,
            "pipelineExits": self.pipeline_exits,
            "droppedBatches": self.dropped_batches,
            "maxStagingDepth": self.max_staging_depth,
            "autoIngestDepth": self.auto_depth,
            **{f"batchesByStream.{stream}": n
               for stream, n in self.batches_by_stream.items()},
        }


def host_nbytes(x) -> int:
    """Bytes of the host arrays in a pytree: what a put of it hands to
    ``device_put`` (the ``put`` span's count)."""
    import jax

    return sum(getattr(a, "nbytes", 0) for a in jax.tree_util.tree_leaves(x))


def staged_put(x, sharding=None, faults=None, stats: Optional[IngestStats] = None):
    """H2D ``device_put`` behind the ``ingest.put`` injection site.

    The one sanctioned ingest-path transfer primitive: arms the fault
    injector's ``ingest.put`` site (when a harness is configured) with
    the same bounded retry-with-backoff ladder the emit drain uses, so
    transient transfer faults recover and sticky ones propagate.  Counts
    one ``device_puts`` and the pytree's leaves (``put_leaves``) per
    call when ``stats`` is supplied, and is one ``put`` span of the
    calling thread's open cycle (retries included).
    """
    import jax

    if stats is not None:
        stats.device_puts += 1
        stats.put_leaves += len(jax.tree_util.tree_leaves(x))
    with span(STAGE_PUT) as sp:
        if sp is not None:
            sp.count = host_nbytes(x)
        if faults is None:
            return (jax.device_put(x, sharding) if sharding is not None
                    else jax.device_put(x))
        fi = faults
        attempts = fi.transfer_retry_attempts
        backoff = None
        attempt = 0
        while True:
            try:
                fi.check("ingest.put")
                out = (jax.device_put(x, sharding) if sharding is not None
                       else jax.device_put(x))
                if attempt:
                    fi.stats.drains_recovered += 1
                return out
            except TransferFaultError:
                if attempt >= attempts:
                    raise
                attempt += 1
                fi.stats.transfer_retries += 1
                if backoff is None:
                    from ..transport.retry import BackoffRetryCounter

                    backoff = BackoffRetryCounter(scale=fi.transfer_retry_scale)
                wait_s = backoff.get_time_interval_ms() / 1000.0
                backoff.increment()
                log.warning("ingest put: transient device_put fault; "
                            "retry %d/%d in %.3fs", attempt, attempts, wait_s)
                if wait_s > 0:
                    time.sleep(wait_s)


# -- when the window opens ----------------------------------------------------
#
# The thresholds of ``PipelineRule``, each with its reason.

#: An inline gate is worth hiding only if it kept the host this long:
#: only then is there a step behind it.  The floor sits between the
#: longest gate that is the count's way back alone (0.9 ms on the v5e)
#: and the shortest with a step behind it (1.5 ms): PERF.md section 6,
#: PR 50, has the per-arrival log.
BLOCKED_MIN_S = 1.25e-3
#: ... and only if the caller was back for more within this share of
#: that wait (a closed loop: 0.06-0.19 of it; a paced source: a hundred
#: times it).  Leaving takes a gap LONGER than the wait, so between a
#: quarter of the wait and the whole of it the stage keeps the regime
#: it has: it cannot flap on a gap that hovers.
THINK_SHARE = 0.25
#: Consecutive batches that have to qualify before the window opens.  A
#: caller who sends a handful of batches and reads the result straight
#: after the last (a console, most tests) never engages whatever its
#: gates; a stream does with its ninth batch.
ENGAGE_RUN = 8
#: The idle finisher takes a staged gate nothing has come for within
#: this many observed cycles (submit to submit).  One cycle is when the
#: next submit is due, so at one it would race every batch of a steady
#: stream for the lock; at one and a half it leaves a stream alone and
#: a burst's last batch waits half a cycle past its step.
IDLE_CYCLES = 1.5
#: ... but never longer than this, whatever the cycles were.
IDLE_MAX_S = 0.25


class PipelineRule:
    """Inline, or one batch in flight behind the next dispatch: decided
    per arrival from two observations.  ``gate_s``: the seconds the last
    INLINE ``resolve()`` kept the host (what hiding it would buy; a
    hidden gate's own wait says nothing).  ``think_s``: the seconds
    from the stage handing control back to the next batch entering the
    pipeline (whether the caller comes back while the device works)."""

    __slots__ = ("pipelined", "run", "gate_s")

    def __init__(self):
        self.pipelined = False
        self.run = 0
        self.gate_s = 0.0

    def arrival(self, think_s: float) -> None:
        """A batch arrived ``think_s`` after the last hand-back: open
        the window, close it, or (mostly) leave ``pipelined`` as it is."""
        if self.pipelined:
            if think_s > self.gate_s:
                self.leave()
        elif (self.gate_s >= BLOCKED_MIN_S
                and think_s <= self.gate_s * THINK_SHARE):
            self.run += 1
            if self.run >= ENGAGE_RUN:
                self.pipelined = True
                self.run = 0
        else:
            self.run = 0

    def leave(self) -> None:
        """A barrier or an idle finish: back to inline, the run anew."""
        self.pipelined = False
        self.run = 0


class IdleFinisher:
    """The one daemon thread of an app that finishes staged count gates
    no arrival came for: the last batch of a burst reaches its callback
    when the device has finished it, not at the next ``send_batch``.

    Started when a stage first leaves a batch in flight (an app whose
    stages stay inline never has the thread), joined by ``stop()`` at
    the app's shutdown.  It sleeps until the oldest staged gate is
    overdue (``IngestStage.idle_wait``), waits for that step OFF the
    lock, then takes the app's ``process_lock`` — the lock
    ``send_batch``, the scheduler's tick and snapshots hold — and
    finishes what is still staged: callbacks from a second thread under
    that lock are what a processing-time scheduler tick already does.
    A stream whose submits keep coming never sees it take the lock."""

    def __init__(self, app_context=None):
        self._ctx = app_context
        self._own_lock = threading.RLock()
        # leaf lock: guards the stage list and the thread's sleep, never
        # held while the process lock is taken
        self._cond = threading.Condition(threading.Lock())
        self._stages: List["IngestStage"] = []
        self._thread: Optional[threading.Thread] = None
        self._stopped = False

    def lock(self):
        """The lock every finish runs under.  Read when used: a replan
        hands the replacement context its predecessor's lock."""
        return getattr(self._ctx, "process_lock", None) or self._own_lock

    def watch(self, stage: "IngestStage") -> None:
        """``stage`` just left a batch in flight with none before it."""
        with self._cond:
            if self._stopped:
                return
            if stage not in self._stages:
                self._stages.append(stage)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name=f"ingest-idle-{getattr(self._ctx, 'name', '')}")
                self._thread.start()
            self._cond.notify()

    def alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def stop(self, timeout: float = 5.0) -> None:
        """Shutdown: the thread ends and is joined.  An app started
        again may stage again: the next ``watch`` starts a new one."""
        with self._cond:
            self._stopped = True
            self._cond.notify()
            t, self._thread = self._thread, None
        if t is threading.current_thread():
            return      # shut down from a callback it delivers: it ends
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                return  # waiting on a step that does not end: stays off
        with self._cond:
            self._stopped = False

    def _loop(self) -> None:
        while True:
            with self._cond:
                if self._stopped:
                    return
                waits = [(w, s) for s in self._stages
                         if (w := s.idle_wait()) is not None]
                if not waits:
                    self._cond.wait()       # until a stage stages
                    continue
                soonest = min(w for w, _s in waits)
                if soonest > 0:
                    self._cond.wait(soonest)
                    continue
                overdue = [s for w, s in waits if w <= 0]
            for stage in overdue:
                try:
                    stage.finish_idle(self._acquire)
                except Exception:  # noqa: BLE001 — the thread serves on
                    log.exception("idle finisher: finishing a staged "
                                  "batch failed")

    def _acquire(self):
        """The process lock, or None when the app is stopping (a stop
        that holds the lock must not wait on this thread for it)."""
        lock = self.lock()
        while not lock.acquire(timeout=0.05):
            if self._stopped:
                return None
        return lock


_SUBMIT, _BARRIER, _IDLE = "submit", "barrier", "idle"


class IngestStage:
    """Bounded per-runtime staging window (FIFO, depth >= 1).

    Each entry is one junction batch whose jitted step has been
    DISPATCHED but whose count gate has not been fetched: ``probe`` is a
    device scalar whose readiness marks step completion (None when the
    batch produced no device work) and ``finish()`` fetches the count
    and enqueues or skips the batch's emit, returning the seconds the
    fetch kept the host (or None).  ``submit`` finishes the
    oldest entries until at most ``depth - 1`` remain in flight, so the
    blocking fetch for batch N runs only after batch N+1's transfer and
    dispatch are already queued on the device stream.

    ``depth`` None or ``'auto'``: the window is ``PipelineRule``'s, 1 or
    2, and needs a ``finisher`` to ever be 2 (a staged gate never waits
    for an arrival that does not come).  A number pins it.

    ``on_fault(exc)`` mirrors the emit queue's isolation hook: a finish
    failure is logged and routed there instead of killing the runtime
    (and instead of surfacing under an unrelated later batch).
    """

    def __init__(self, depth=None, stats: Optional[IngestStats] = None,
                 faults=None, on_fault: Optional[Callable] = None,
                 finisher: Optional[IdleFinisher] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.rule = None
        if depth is None or depth == "auto":
            self.rule = PipelineRule()
            depth = 1
        self.depth = max(1, int(depth))
        self.stats = stats or IngestStats()
        self.stats.auto_depth = 0 if self.rule is None else 1
        self.faults = faults
        self.on_fault = on_fault
        self.finisher = finisher
        self.clock = clock
        self._entries: List[Tuple[int, object, Callable, object]] = []
        self._seq = 0
        self._t_arrive: Optional[float] = None
        self._t_handback: Optional[float] = None
        self._cycle_s = 0.0
        # (seq, due, probe) of the newest batch left in flight under the
        # rule: what the idle finisher reads, one reference, no lock
        self._idle_mark: Optional[Tuple[int, float, object]] = None

    def __len__(self) -> int:
        return len(self._entries)

    def arrive(self) -> None:
        """A batch enters the pipeline (``DevicePipeline.cycle``): the
        clock read that ends ``think``."""
        self._t_arrive = self.clock()

    def submit(self, probe, finish: Callable, trace=None,
               may_defer: bool = True):
        """Stage one dispatched batch; finish entries past the window.

        ``trace`` is the batch's sampled cycle token (observability/
        trace.py CycleToken, or None): submit time is the boundary where
        receive-time work — conversion, the H2D put, the jitted step
        dispatch — is all queued, so the token's ingest span ends here
        and its step span starts.  ``may_defer`` False (the caller is
        outside the lock the idle finisher takes): the rule is not
        asked and nothing stays in flight."""
        if self.rule is not None:
            if may_defer and self.finisher is not None:
                self._decide()
            elif self.rule.pipelined:
                self._leave()
        self.stats.staged_batches += 1
        if trace is not None:
            trace.dispatched()
        self._seq += 1
        self._entries.append((self._seq, probe, finish, trace))
        self.stats.note_depth(len(self._entries))
        while len(self._entries) >= self.depth:
            self._finish_oldest(_SUBMIT)
        now = self.clock()
        if self._t_handback is not None:
            self._cycle_s = now - self._t_handback
        self._t_handback = now
        if self.rule is not None:
            self._mark_idle(now)

    def _decide(self) -> None:
        """Ask the rule, with this arrival's ``think``."""
        back, came = self._t_handback, self._t_arrive
        # no hand-back yet, or a submit whose arrival was not clocked
        think = (came - back if back is not None and came is not None
                 and came >= back else float("inf"))
        was = self.rule.pipelined
        self.rule.arrival(think)
        if self.rule.pipelined and not was:
            self.stats.pipeline_entries += 1
        elif was and not self.rule.pipelined:
            self.stats.pipeline_exits += 1
        self.depth = self.stats.auto_depth = 2 if self.rule.pipelined else 1

    def _mark_idle(self, now: float) -> None:
        """Tell the idle finisher what this submit left in flight."""
        if not self._entries:
            self._idle_mark = None
            return
        first = self._idle_mark is None
        seq, probe = self._entries[-1][0], self._entries[-1][1]
        # (no cycle observed yet: the longest grace, not none)
        grace = min(IDLE_CYCLES * self._cycle_s or IDLE_MAX_S, IDLE_MAX_S)
        self._idle_mark = (seq, now + grace, probe)
        if first:
            self.finisher.watch(self)

    def _leave(self) -> None:
        """A barrier or an idle finish emptied the window: inline."""
        self._idle_mark = None
        if self.rule is not None and self.rule.pipelined:
            self.rule.leave()
            self.stats.pipeline_exits += 1
            self.depth = self.stats.auto_depth = 1

    def flush(self):
        """Barrier: finish every in-flight batch in submit order.
        Called wherever host code could observe ingest/emit timing —
        always BEFORE the owning runtime drains its emit queue."""
        while self._entries:
            self._finish_oldest(_BARRIER)
        self._leave()

    def pin(self, depth: int) -> None:
        """Finish what is staged and hold the window at ``depth`` from
        here on, the rule off (the debugger: every emit at its batch;
        the planner: a query whose rows end in a table)."""
        self.flush()
        self.rule = None
        self.depth = max(1, int(depth))
        self.stats.auto_depth = 0

    # -- the idle finisher's side --------------------------------------------

    def idle_wait(self) -> Optional[float]:
        """Seconds until the batch left in flight is overdue (0 or less:
        it is), None when nothing is in flight.  Read by the finisher
        thread, lock-free."""
        mark = self._idle_mark
        return None if mark is None else mark[1] - self.clock()

    def finish_idle(self, acquire: Callable) -> None:
        """Finisher thread: wait for the overdue step off the lock, then
        finish, under it, what no submit or barrier took meanwhile."""
        mark = self._idle_mark
        if mark is None:
            return
        seq, _due, probe = mark
        wait = getattr(probe, "block_until_ready", None)
        if wait is not None:
            try:
                wait()
            except Exception as err:  # noqa: BLE001 — a failed step
                # resolve() raises it again inside finish(), where it is
                # counted, isolated and handed to the listeners
                log.debug("idle finisher: the awaited step failed: %s", err)
        lock = acquire()
        if lock is None:
            return
        try:
            took = False
            while self._entries and self._entries[0][0] <= seq:
                took = True
                self._finish_oldest(_IDLE)
            if self._idle_mark is mark:
                self._idle_mark = None      # whoever took it, it is taken
            if took and not self._entries:
                self._leave()
        finally:
            lock.release()

    def _finish_oldest(self, by: str):
        seq, probe, finish, trace = self._entries.pop(0)
        # the batch this very submit staged: finished inline
        inline = by is _SUBMIT and seq == self._seq
        # overlap evidence: if the step's count scalar is already
        # resident when we get around to fetching it, the device did the
        # work while the host staged the next batch (overlap); if not,
        # the host is about to block on it (stall).  Barrier-forced
        # finishes are counted separately — a flush right after submit
        # says nothing about steady-state overlap — and the idle
        # finisher waited for the probe itself.
        if probe is not None and by is _SUBMIT:
            is_ready = getattr(probe, "is_ready", None)
            if is_ready is not None:
                try:
                    if is_ready():
                        self.stats.overlapped_batches += 1
                    else:
                        self.stats.ingest_stalls += 1
                except Exception:  # pragma: no cover - probe died
                    self.stats.ingest_stalls += 1
        if not inline:
            if by is _SUBMIT:
                self.stats.gates_by_submit += 1
            elif by is _BARRIER:
                self.stats.flush_syncs += 1
            else:
                self.stats.gates_by_idle += 1
            if trace is not None:
                # the step span is the host blocked on this gate: for a
                # deferred one that starts here, not at its dispatch
                trace.step_begins()
        try:
            blocked = finish()
        except Exception as err:
            self.stats.dropped_batches += 1
            log.error("ingest finish failed; dropping one staged "
                      "batch's emit: %s", err)
            if trace is not None:
                trace.aborted("step")
            if self.on_fault is not None:
                self.on_fault(err)
            blocked = None
        if inline and self.rule is not None:
            # what this gate cost the host, for the rule to go by
            self.rule.gate_s = blocked or 0.0
