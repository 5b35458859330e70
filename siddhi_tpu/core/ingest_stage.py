"""Ingest-side staging pipeline: double-buffered H2D puts.

The emit (D2H) side has been queued and coalesced since the async emit
pipeline landed (core/emit_queue.py); the input side still paid one
synchronous round trip per batch — not on the ``device_put`` itself
(JAX enqueues transfers asynchronously) but on the ``int(n_match)``
count-gate fetch that every engine performed right after dispatching
its jitted step.  That fetch blocks until the H2D transfer AND the step
finish, so transfer and compute for consecutive batches were fully
serialized.

This module holds the pieces every device runtime shares:

- ``IngestStats``: per-runtime staging counters surfaced through
  ``util/statistics.py`` (``stagedBatches`` / ``devicePuts`` /
  ``ingestStalls`` / ``overlappedBatches`` / ``flushSyncs`` /
  ``maxStagingDepth``).
- ``IngestStage``: a bounded staging window.  ``submit(probe, finish)``
  records one dispatched batch whose count gate has NOT been fetched
  yet; the oldest entry's ``finish`` (fetch count, enqueue/skip its
  emit) runs only once the window exceeds ``depth - 1`` entries.  With
  ``ingest.depth='2'`` the count fetch for batch N happens strictly
  AFTER batch N+1's conversion, ``device_put`` and step dispatch have
  been issued — H2D for N+1 overlaps the step for N.  Depth 1 (the
  default) finishes inline, byte-identical in timing to the
  pre-pipeline path.
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
content and order stay bit-identical to synchronous ingest.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, List, Optional, Tuple

from ..observability.trace import STAGE_PUT, span
from .exceptions import TransferFaultError

log = logging.getLogger("siddhi_tpu.ingest")


class IngestStats:
    """Staging counters for one device runtime (host-side ints, same
    thin-gauge style as ``EmitStats``)."""

    __slots__ = ("staged_batches", "device_puts", "ingest_stalls",
                 "overlapped_batches", "flush_syncs", "dropped_batches",
                 "max_staging_depth", "auto_depth")

    def __init__(self):
        self.staged_batches = 0
        self.device_puts = 0
        # staged batches whose finish (the count-gate fetch, where XLA
        # reports an asynchronous step failure) raised and was isolated
        self.dropped_batches = 0
        self.ingest_stalls = 0
        self.overlapped_batches = 0
        self.flush_syncs = 0
        self.max_staging_depth = 0
        # effective window when ingest.depth='auto' (0 = fixed depth)
        self.auto_depth = 0

    def note_depth(self, depth: int):
        if depth > self.max_staging_depth:
            self.max_staging_depth = depth

    def as_dict(self) -> dict:
        return {
            "stagedBatches": self.staged_batches,
            "devicePuts": self.device_puts,
            "ingestStalls": self.ingest_stalls,
            "overlappedBatches": self.overlapped_batches,
            "flushSyncs": self.flush_syncs,
            "droppedBatches": self.dropped_batches,
            "maxStagingDepth": self.max_staging_depth,
            "autoIngestDepth": self.auto_depth,
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
    one ``device_puts`` per call when ``stats`` is supplied, and is one
    ``put`` span of the calling thread's open cycle (retries included).
    """
    import jax

    if stats is not None:
        stats.device_puts += 1
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


class IngestStage:
    """Bounded per-runtime staging window (FIFO, depth >= 1).

    Each entry is one junction batch whose jitted step has been
    DISPATCHED but whose count gate has not been fetched: ``probe`` is a
    device scalar whose readiness marks step completion (None when the
    batch produced no device work) and ``finish()`` fetches the count
    and enqueues or skips the batch's emit.  ``submit`` finishes the
    oldest entries until at most ``depth - 1`` remain in flight, so the
    blocking fetch for batch N runs only after batch N+1's transfer and
    dispatch are already queued on the device stream.

    ``on_fault(exc)`` mirrors the emit queue's isolation hook: a finish
    failure is logged and routed there instead of killing the runtime
    (and instead of surfacing under an unrelated later batch).
    """

    def __init__(self, depth=1, stats: Optional[IngestStats] = None,
                 faults=None, on_fault: Optional[Callable] = None):
        # depth 'auto': bounded self-tuning with the SAME controller the
        # emit queue uses (core/emit_queue.py EmitDepthController) — the
        # staging window re-derives its depth each submit from the
        # observed count-fetch round trip vs the batch arrival cadence,
        # so slow fetches widen the window (more H2D/step overlap) and
        # fast ones shrink it back toward the depth-1 latency profile.
        self.controller = None
        if depth == "auto":
            from .emit_queue import EmitDepthController

            self.controller = EmitDepthController()
            depth = 1
        self.depth = max(1, int(depth))
        self.stats = stats or IngestStats()
        self.faults = faults
        self.on_fault = on_fault
        self._entries: List[Tuple[object, Callable, object]] = []

    def __len__(self) -> int:
        return len(self._entries)

    def submit(self, probe, finish: Callable, trace=None):
        """Stage one dispatched batch; finish entries past the window.

        ``trace`` is the batch's sampled cycle token (observability/
        trace.py CycleToken, or None): submit time is the boundary where
        receive-time work — conversion, the H2D put, the jitted step
        dispatch — is all queued, so the token's ingest span ends here
        and its step span starts."""
        if self.controller is not None:
            self.controller.note_push()
            self.depth = self.controller.effective_depth
            self.stats.auto_depth = self.depth
        self.stats.staged_batches += 1
        if trace is not None:
            trace.dispatched()
        self._entries.append((probe, finish, trace))
        self.stats.note_depth(len(self._entries))
        while len(self._entries) >= self.depth:
            self._finish_oldest(barrier=False)

    def flush(self):
        """Barrier: finish every in-flight batch in submit order.
        Called wherever host code could observe ingest/emit timing —
        always BEFORE the owning runtime drains its emit queue."""
        while self._entries:
            self.stats.flush_syncs += 1
            self._finish_oldest(barrier=True)

    def _finish_oldest(self, barrier: bool):
        probe, finish, trace = self._entries.pop(0)
        # overlap evidence: if the step's count scalar is already
        # resident when we get around to fetching it, the device did the
        # work while the host staged the next batch (overlap); if not,
        # the host is about to block on it (stall).  Barrier-forced
        # finishes are counted separately — a flush right after submit
        # says nothing about steady-state overlap.
        if probe is not None and not barrier:
            is_ready = getattr(probe, "is_ready", None)
            if is_ready is not None:
                try:
                    if is_ready():
                        self.stats.overlapped_batches += 1
                    else:
                        self.stats.ingest_stalls += 1
                except Exception:  # pragma: no cover - probe died
                    self.stats.ingest_stalls += 1
        # RTT sample for depth='auto': the wall time of finish() is
        # dominated by the blocking count-gate fetch when the batch had
        # device work (probe is not None)
        t0 = (time.monotonic()
              if self.controller is not None and probe is not None
              else None)
        try:
            finish()
        except Exception as err:
            self.stats.dropped_batches += 1
            log.error("ingest finish failed; dropping one staged "
                      "batch's emit: %s", err)
            if trace is not None:
                trace.aborted("step")
            if self.on_fault is not None:
                self.on_fault(err)
            return
        if t0 is not None:
            self.controller.note_drain(time.monotonic() - t0)
