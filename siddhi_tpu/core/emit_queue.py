"""Async emit pipeline: count-gated, double-buffered device→host emits.

Every device→host fetch of jit outputs is a synchronous round trip on
the served path, so emits are count-gated and coalesced (what a fetch
costs on the chip: PERF.md, section 6).  This module holds the pieces
every device runtime shares:

- ``EmitStats``: per-runtime transfer counters surfaced through
  ``util/statistics.py`` (``emitTransfers`` / ``deferredBatches`` /
  ``zeroMatchSkips`` / ``maxPendingDepth``, ``droppedInstances``, the
  dense pattern runtime's overflow total as of its last poll, and the
  early copies' ``earlyCopyBatches`` / ``earlyCopyHits`` /
  ``earlyCopyWastedBytes``).
- ``EmitQueue``: a bounded pending-emit queue.  Each entry is one
  junction batch whose match outputs are still resident on the device;
  when the queue reaches its configured depth (``emit.depth`` on
  ``@app:execution``), ALL queued outputs are drained with one
  coalesced transfer.  Depth 1 (the default) drains right after each
  batch — emit timing is then identical to the synchronous path while
  still benefiting from count-gating and the one ``device_get`` a
  batch (one entry's arrays are fetched as they are: no concatenation).
- ``fetch_coalesced``: groups device arrays by (dtype, trailing shape),
  concatenates each group on device along axis 0, fetches everything in
  a single ``jax.device_get``, and splits back host-side — one transfer
  round trip instead of one per column per batch.  An array whose copy
  to the host was started when its step was dispatched
  (``DevicePipeline.submit``) is fetched as it is: the bytes are on
  their way or there, and a concatenation would only send them again.

Exactness contract: entries drain strictly FIFO and each entry
materializes into exactly the EventBatch the synchronous path would
have emitted, so callback content AND order are bit-identical; the
runtimes insert explicit ``drain()`` barriers wherever host code could
observe emit timing (snapshot/restore, timer fires, rate-limiter
decisions, pull queries, shutdown, debugger).
"""

from __future__ import annotations

import logging
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..observability.stall import waits_on_device
from ..observability.trace import STAGE_FETCH, annotation, reopen
from .exceptions import TransferFaultError

log = logging.getLogger("siddhi_tpu.emit")


class EmitStats:
    """Transfer counters for one device runtime (host-side ints; one
    increment per batch, matching the micro-batched tracker style of
    util/statistics.py)."""

    __slots__ = ("emit_transfers", "deferred_batches", "zero_match_skips",
                 "dropped_batches", "max_pending_depth", "auto_depth",
                 "dropped_instances", "early_copy_batches",
                 "early_copy_hits", "early_copy_wasted_bytes")

    def __init__(self):
        self.emit_transfers = 0
        self.deferred_batches = 0
        self.zero_match_skips = 0
        # pending batches lost to a failed drain fetch or a failed
        # materialize (isolated, reported through on_fault): a broken
        # device step surfaces here, so it is counted on every app, not
        # only under the @app:faults harness
        self.dropped_batches = 0
        self.max_pending_depth = 0
        # effective depth the 'auto' controller is currently running at
        # (0 = static emit.depth, no controller)
        self.auto_depth = 0
        # pending pattern instances dropped because every lane of their
        # node was taken, as of the dense runtime's last overflow poll
        # (core/dense_pattern.py _check_overflow): rows the host engine
        # would have emitted may be missing once this is not 0
        self.dropped_instances = 0
        # batches some of whose emit arrays started for the host at
        # their dispatch (core/device_pipeline.py), those of them whose
        # drain took such an array, and the bytes started for a chunk
        # whose gate then came back 0: copied, never read
        self.early_copy_batches = 0
        self.early_copy_hits = 0
        self.early_copy_wasted_bytes = 0

    def note_depth(self, depth: int):
        if depth > self.max_pending_depth:
            self.max_pending_depth = depth

    def as_dict(self) -> dict:
        return {
            "emitTransfers": self.emit_transfers,
            "deferredBatches": self.deferred_batches,
            "zeroMatchSkips": self.zero_match_skips,
            "droppedBatches": self.dropped_batches,
            "maxPendingDepth": self.max_pending_depth,
            "autoEffectiveDepth": self.auto_depth,
            "droppedInstances": self.dropped_instances,
            "earlyCopyBatches": self.early_copy_batches,
            "earlyCopyHits": self.early_copy_hits,
            "earlyCopyWastedBytes": self.early_copy_wasted_bytes,
        }


def _is_device_array(a) -> bool:
    return not isinstance(a, (np.ndarray, np.generic, int, float, bool))


@waits_on_device
def fetch_coalesced(arrays: Sequence, on_device: bool = True,
                    started=frozenset()) -> List[np.ndarray]:
    """One device→host round trip for a list of arrays.

    Device arrays are grouped by (dtype, trailing shape), each group is
    concatenated ON DEVICE along axis 0, the concatenated buffers are
    fetched with a single ``jax.device_get``, and the result is split
    back host-side in input order.  Host numpy arrays pass through
    untouched.  Counts as ONE emit transfer.

    ``on_device`` False fetches every array as it is, still in the one
    ``device_get`` (its copies are all started before any is awaited):
    a concatenation is a program, and a program dispatched while a step
    is in flight runs after that step.  So is every array in
    ``started`` (by ``id``) fetched: its copy was started when its step
    was dispatched, and ``device_get`` finds it there.
    """
    if not arrays:
        return []
    out: List[Optional[np.ndarray]] = [None] * len(arrays)
    groups: dict = {}  # (dtype, trailing shape) -> [index]
    for i, a in enumerate(arrays):
        if not _is_device_array(a):
            out[i] = np.asarray(a)
            continue
        shape = getattr(a, "shape", ())
        if len(shape) == 0 or not on_device or id(a) in started:
            key = ("alone", i)  # 0-d: no concat axis; fetch alone
        else:
            key = (str(a.dtype), tuple(shape[1:]))
        groups.setdefault(key, []).append(i)
    if not groups:
        return [a for a in out]  # all host already
    import jax
    import jax.numpy as jnp

    keys = list(groups)
    staged = []
    for key in keys:
        idxs = groups[key]
        if len(idxs) == 1:
            staged.append(arrays[idxs[0]])
        else:
            try:
                staged.append(jnp.concatenate(
                    [arrays[i] for i in idxs], axis=0))
            except Exception as e:
                # heterogeneous placements (e.g. differently-sharded
                # chunks) can refuse to concatenate — fall back to
                # fetching the group members individually in the same
                # device_get call
                log.debug("fetch_coalesced: device concat refused for "
                          "group %s (%s); fetching %d members "
                          "individually", key, e, len(idxs))
                staged.append(None)
    fetch = []
    for key, s in zip(keys, staged):
        if s is None:
            fetch.extend(arrays[i] for i in groups[key])
        else:
            fetch.append(s)
    host = jax.device_get(fetch)
    pos = 0
    for key, s in zip(keys, staged):
        idxs = groups[key]
        if s is None:
            for i in idxs:
                out[i] = host[pos]
                pos += 1
        elif len(idxs) == 1:
            out[idxs[0]] = host[pos]
            pos += 1
        else:
            cat = host[pos]
            pos += 1
            off = 0
            for i in idxs:
                n = arrays[i].shape[0]
                out[i] = cat[off:off + n]
                off += n
    return out  # type: ignore[return-value]


class PendingEmit:
    """One deferred junction batch: device refs + a materializer that
    turns the fetched host arrays into the exact synchronous emit."""

    __slots__ = ("arrays", "materialize", "trace", "started")

    def __init__(self, arrays: Sequence, materialize: Callable, trace=None,
                 started=frozenset()):
        # materialize(host_arrays) -> None (runs the emit callback);
        # trace is the batch's sampled cycle token (observability/
        # trace.py CycleToken, or None) — the drain stamps its emit span;
        # started: ids of the arrays whose copy to the host is under way
        self.arrays = list(arrays)
        self.materialize = materialize
        self.trace = trace
        self.started = started


class EmitDepthController:
    """Adaptive queue depth for ``emit.depth='auto'``.

    The right static depth is "how many junction batches arrive during
    one device→host drain round trip": deeper coalesces more transfers
    per RTT, but anything past that only delays callbacks.  Both inputs
    drift at runtime (transfer RTT is load-dependent, batch cadence is the
    workload's), so the controller keeps decaying averages of the
    inter-push gap (sampled at ``note_push``) and the drain fetch time
    (``note_drain``) and re-derives

        effective_depth = clamp(ceil(rtt_ema / gap_ema), 1, AUTO_DEPTH_MAX)

    after every sample.  The EMA weight makes old samples decay with a
    ~1/ALPHA-sample window, so a match-rate or RTT shift re-converges
    within a few drains.  AUTO_DEPTH_MAX bounds the queue exactly like a
    hand-written ``emit.depth`` would — auto can never grow the pending
    window past it.
    """

    AUTO_DEPTH_MAX = 32
    ALPHA = 0.2  # decaying-window weight (newest sample's share)

    __slots__ = ("_gap_ema", "_rtt_ema", "_last_push", "effective_depth")

    def __init__(self):
        self._gap_ema: Optional[float] = None
        self._rtt_ema: Optional[float] = None
        self._last_push: Optional[float] = None
        self.effective_depth = 1

    def _ema(self, old: Optional[float], sample: float) -> float:
        if old is None:
            return sample
        return old + self.ALPHA * (sample - old)

    def note_push(self, t: Optional[float] = None):
        """One queued batch; ``t`` (monotonic seconds) is injectable
        for tests."""
        if t is None:
            t = time.monotonic()
        if self._last_push is not None:
            self._gap_ema = self._ema(self._gap_ema, t - self._last_push)
        self._last_push = t
        self._recompute()

    def note_drain(self, seconds: float):
        """Observed fetch wall time of one coalesced drain."""
        self._rtt_ema = self._ema(self._rtt_ema, seconds)
        self._recompute()

    def _recompute(self):
        if not self._gap_ema or self._rtt_ema is None:
            return  # no cadence yet (first batch) — stay at current depth
        import math

        depth = math.ceil(self._rtt_ema / self._gap_ema)
        self.effective_depth = max(1, min(depth, self.AUTO_DEPTH_MAX))


class EmitQueue:
    """Bounded per-runtime pending-emit queue (FIFO, depth >= 1).

    ``faults`` (a ``util.faults.FaultInjector`` or None) arms the
    ``emit.drain`` injection site and supplies the transfer retry knobs;
    ``on_fault(exc)`` is the owning runtime's isolation hook — a drain or
    materialize failure is routed there (fault stream / error log /
    exception listeners) instead of propagating and killing the runtime.
    """

    def __init__(self, depth=1, stats: Optional[EmitStats] = None,
                 faults=None, on_fault: Optional[Callable] = None):
        # depth 'auto': bounded self-tuning — a controller re-derives
        # the effective depth from observed drain RTT vs push cadence
        # (never past its AUTO_DEPTH_MAX bound).  The debugger disables
        # the controller when it forces depth 1.
        self.controller: Optional[EmitDepthController] = None
        if depth == "auto":
            self.controller = EmitDepthController()
            depth = 1
        self.depth = max(1, int(depth))
        self.stats = stats or EmitStats()
        self.faults = faults
        self.on_fault = on_fault
        # is a later step dispatched and not yet waited for (the ingest
        # stage holds a batch in flight)?  Then a drain concatenates
        # nothing on the device: that program would queue behind the
        # step and the fetch wait it out.  The pipeline wires it.  Nor
        # does a drain of one entry: a concatenation joins the same
        # column of several batches into one transfer, and one batch
        # has each column once (_drain).
        self.step_in_flight: Callable[[], bool] = lambda: False
        self._entries: List[PendingEmit] = []

    def __len__(self) -> int:
        return len(self._entries)

    def push(self, entry: PendingEmit):
        if self.controller is not None:
            self.controller.note_push()
            self.depth = self.controller.effective_depth
            self.stats.auto_depth = self.depth
        self._entries.append(entry)
        self.stats.note_depth(len(self._entries))
        if len(self._entries) >= self.depth:
            self.drain()
        else:
            self.stats.deferred_batches += 1

    def skip(self):
        """Record a zero-match batch that transferred nothing."""
        self.stats.zero_match_skips += 1

    def _fetch(self, arrays: Sequence, on_device: bool = True,
               started=frozenset()) -> List[np.ndarray]:
        """``fetch_coalesced`` behind the ``emit.drain`` injection site,
        with bounded retry-with-backoff on transient transfer faults
        (sticky device loss and other errors propagate immediately).
        A copy started early that failed raises here too, from the
        ``device_get`` that awaits it."""
        fi = self.faults
        if fi is None:
            return fetch_coalesced(arrays, on_device, started)
        attempts = fi.transfer_retry_attempts
        backoff = None
        attempt = 0
        while True:
            try:
                fi.check("emit.drain")
                host = fetch_coalesced(arrays, on_device, started)
                if attempt:
                    fi.stats.drains_recovered += 1
                return host
            except TransferFaultError:
                if attempt >= attempts:
                    raise
                attempt += 1
                fi.stats.transfer_retries += 1
                if backoff is None:
                    from ..transport.retry import BackoffRetryCounter

                    backoff = BackoffRetryCounter(
                        scale=fi.transfer_retry_scale)
                wait_s = backoff.get_time_interval_ms() / 1000.0
                backoff.increment()
                log.warning("emit drain: transient transfer fault; "
                            "retry %d/%d in %.3fs", attempt, attempts,
                            wait_s)
                if wait_s > 0:
                    time.sleep(wait_s)

    def drain(self):
        """Flush barrier: materialize every pending entry in FIFO order
        with one coalesced transfer.  Re-entrant pushes from emit
        callbacks land in a fresh list and drain after the current
        entries — the same order the synchronous path produces.

        Fault isolation: a failed fetch drops only THIS drain's entries
        and a failing materializer only its own entry; both are counted
        in ``EmitStats.dropped_batches`` and routed through ``on_fault``
        on every app (the ``@app:faults`` harness keeps its own
        ``drains_failed`` / ``callback_faults_isolated`` beside them).
        Either way the queue stays usable and the runtime stays
        alive."""
        if not self._entries:
            return
        # the thread's open cycle (observability/trace.py) is each
        # entry's own while its rows are built and delivered, so that
        # the shells' ``span`` sites record there; a callback that
        # re-enters ``send_batch`` leaves another (or none) open, so it
        # is set afresh for every entry, and what the drain found is
        # put back when it ends
        found = reopen(None)
        try:
            self._drain()
        finally:
            reopen(found)

    def _drain(self):
        while self._entries:
            entries, self._entries = self._entries, []
            arrays: List = []
            spans: List[int] = []
            started = frozenset().union(*(e.started for e in entries))
            # several batches' columns are worth a concatenation program
            # a kind; one batch's are fetched as they are, in the one
            # device_get (on the v5e a program costs more than the
            # transfers it saves: PERF.md, section 6, PR 44), and the
            # default depth then compiles no program on the way back
            coalesce = len(entries) > 1 and not self.step_in_flight()
            for e in entries:
                spans.append(len(e.arrays))
                arrays.extend(e.arrays)
            had_device = any(_is_device_array(a) for a in arrays)
            t0 = (time.monotonic()
                  if self.controller is not None and had_device else None)
            # emit-span clock for sampled cycle tokens (their tracer's:
            # one app, one tracer): one coalesced fetch serves every
            # entry in this round, so they share the fetch start and
            # each stamps its own materialize end
            clock = next((e.trace.tracer.clock for e in entries
                          if e.trace is not None), None)
            try:
                if clock is not None:
                    t_fetch = clock()
                    with annotation(STAGE_FETCH):
                        host = self._fetch(arrays, coalesce, started)
                    t_fetched = clock()
                else:
                    host = self._fetch(arrays, coalesce, started)
            except Exception as err:
                fi = self.faults
                if fi is not None:
                    fi.stats.drains_failed += 1
                self.stats.dropped_batches += len(entries)
                log.error("emit drain failed; dropping %d pending "
                          "batch(es): %s", len(entries), err)
                for e in entries:
                    if e.trace is not None:
                        e.trace.aborted("emit")
                if self.on_fault is not None:
                    self.on_fault(err)
                continue
            if t0 is not None:
                self.controller.note_drain(time.monotonic() - t0)
            if had_device:
                self.stats.emit_transfers += 1
            off = 0
            for e, n in zip(entries, spans):
                seg = host[off:off + n]
                off += n
                reopen(e.trace)
                try:
                    if e.trace is not None:
                        # one fetch serves the round: each sampled
                        # entry records it with its own bytes; ``build``
                        # and ``deliver`` are the materializer's
                        # (DevicePipeline.submit)
                        e.trace.record(STAGE_FETCH, t_fetch, t_fetched,
                                       sum(a.nbytes for a in seg))
                    e.materialize(seg)
                except Exception as err:
                    fi = self.faults
                    if fi is not None:
                        fi.stats.callback_faults_isolated += 1
                    self.stats.dropped_batches += 1
                    log.error("emit materialize failed; dropping one "
                              "pending batch: %s", err)
                    if e.trace is not None:
                        e.trace.aborted("emit")
                    if self.on_fault is not None:
                        self.on_fault(err)
                    continue
                if e.trace is not None:
                    e.trace.emitted(t_fetch)
