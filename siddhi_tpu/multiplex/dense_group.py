"""Shared dense-NFA device engine for N compatible pattern tenants.

The dense engine is ALREADY batched over a partition axis — the
multiplex group simply makes that axis the tenant axis: one
:class:`~siddhi_tpu.ops.dense_nfa.DensePatternEngine` is built with
``n_partitions = slots`` and tenant ``t`` owns partition row ``t``
(every event of tenant ``t`` routes there; the scratch row at index
``slots`` keeps absorbing pad lanes).  Eligible queries are
unpartitioned, so each dedicated engine would have run its whole
stream through one state row anyway — the packed layout is the same
automaton replicated per tenant, and per-row arithmetic is identical,
so match sets are bit-identical.

The win: T dedicated engines dispatch T jitted steps per batch cycle,
and an unpartitioned dedicated engine degenerates to one COLLISION
ROUND PER EVENT (every event shares partition row 0).  The group
concatenates the staged sub-batches tenant-major — partitions are
disjoint across tenants — so each collision round now carries up to T
events, collapsing ``sum(n_i)`` rounds into ``max(n_i)`` rounds of one
shared step.

Timestamps are anchored to ONE group ``base_ts`` (min over the first
dispatch − 1).  `within` checks compare per-partition relative
differences, so the shared anchor is invisible per tenant; a late
tenant whose events predate the anchor triggers a group-wide host
down-shift via ``engine.shift_row_ts`` (rare), and the int32-horizon
re-anchor rides the engine's own ``maybe_re_anchor`` over the combined
batch.

Matches come back through the count-gated emit queue: zero-match
dispatch cycles transfer nothing; a non-empty match set is fetched
once (coalesced) and demultiplexed back to per-tenant callback queues
by splitting the ev-index-major match rows at the tenant-major batch
offsets.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.emit_queue import EmitQueue, EmitStats, PendingEmit
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError, TransferFaultError
from siddhi_tpu.core.ingest_stage import IngestStats
from siddhi_tpu.multiplex.common import retry_guard
from siddhi_tpu.observability.stall import waits_on_device
from siddhi_tpu.observability.trace import STAGE_PERSIST_UNPACK, span
from siddhi_tpu.util import faults as _faults

log = logging.getLogger(__name__)


class _DenseSeat:
    __slots__ = ("slot", "adapter", "staged", "pending_out", "last_good")

    def __init__(self, slot: int):
        self.slot = slot
        self.adapter = None
        self.staged = None  # (stream_key, cols, ts, now)
        self.pending_out = deque()  # (out_cols, out_ts, now)
        self.last_good = None  # {key: host rows [1, ...]}


class DenseMultiplexGroup:
    """One dense engine, ``slots`` tenants on the partition axis."""

    fingerprint = ""

    def __init__(self, engine, out_dtypes: List[np.dtype], slots: int):
        self.engine = engine
        self.slots = int(slots)
        self._out_dtypes = out_dtypes
        self.lock = threading.RLock()
        self.seats: List[Optional[_DenseSeat]] = [None] * self.slots
        self._free = list(range(self.slots - 1, -1, -1))
        self.ingest_stats = IngestStats()
        self.emit_stats = EmitStats()
        engine.ingest_stats = self.ingest_stats
        engine.faults = None  # per-tenant injection lives in the adapters
        self.emit_queue = EmitQueue(depth=1, stats=self.emit_stats,
                                    faults=None, on_fault=self._on_fault)
        self.state = engine.init_state()
        self.dispatches = 0
        self.combined_steps = 0
        self._ovf_warned = 0

    def _on_fault(self, e: BaseException) -> None:
        """A failed group drain loses the cycle's matches of every
        seated tenant: each one's exception listeners hear of it."""
        with self.lock:
            adapters = [s.adapter for s in self.seats
                        if s is not None and s.adapter is not None]
        for a in adapters:
            a._on_fault(e)

    # -- seat lifecycle ----------------------------------------------------

    def try_alloc_seat(self) -> Optional[int]:
        with self.lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self.seats[slot] = _DenseSeat(slot)
            return slot

    def bind(self, slot: int, adapter) -> None:
        with self.lock:
            self.seats[slot].adapter = adapter

    def free_seat(self, slot: int) -> None:
        with self.lock:
            seat = self.seats[slot]
            if seat is None:
                return
            # parity with DensePatternRuntime.close(): short-lived
            # tenants still surface dropped-instance warnings
            self._check_overflow()
            self.seats[slot] = None
            self._free.append(slot)
            self._set_tenant_rows(slot, self.engine.layout.init_logical(1))

    def occupied_count(self) -> int:
        with self.lock:
            return sum(1 for s in self.seats if s is not None)

    # -- staging + dispatch -------------------------------------------------

    def stage(self, adapter, stream_key: str, cols, ts: np.ndarray,
              now) -> None:
        with self.lock:
            seat = self.seats[adapter.slot]
            if seat.staged is not None:
                # a second sub-batch (same or other source stream) must
                # observe the first's transitions: dispatch in between
                self._dispatch_locked()
            seat.staged = (stream_key, cols, ts, now)
            adapter.ingest_stats.staged_batches += 1
            adapter.ingest_stats.note_depth(1)
            if all(s is None or s.staged is not None for s in self.seats):
                self._dispatch_locked()

    def dispatch_staged(self) -> None:
        with self.lock:
            self._dispatch_locked()

    def _dispatch_locked(self) -> None:
        staged = [s for s in self.seats if s is not None and s.staged is not None]
        if not staged:
            return
        eng = self.engine
        by_stream: Dict[str, list] = {}
        for seat in staged:
            stream_key, cols, ts, now = seat.staged
            seat.staged = None
            by_stream.setdefault(stream_key, []).append((seat, cols, ts, now))
        self._anchor_base(by_stream)
        self.dispatches += 1
        for stream_key, items in by_stream.items():
            self._dispatch_stream(stream_key, items)
        for seat in staged:
            if seat.adapter is not None:
                seat.adapter.ingest_stats.device_puts += 1
            self._poison_guard(seat)
        # matches must be host-visible before tenants deliver: drain the
        # count-gated queue (zero-match cycles transferred nothing)
        self.emit_queue.drain()
        if self.dispatches % 256 == 0:
            self._check_overflow()

    def _anchor_base(self, by_stream) -> None:
        eng = self.engine
        ts_min = min(int(ts.min())
                     for items in by_stream.values()
                     for _s, _c, ts, _n in items)
        if eng.base_ts is None:
            eng.base_ts = ts_min - 1
        elif ts_min - eng.base_ts <= 0:
            # late tenant with events older than the group anchor:
            # shift the shared base down so relative ts stay positive
            # (host round trip; rare — admission-time skew only)
            delta = (ts_min - eng.base_ts) - 1
            host = eng.shift_row_ts(eng.layout.unpack(self.state), delta)
            jnp = eng.jnp
            self.state = {k: jnp.asarray(v)
                          for k, v in eng.layout.pack(host).items()}
            eng.base_ts += delta

    def _dispatch_stream(self, stream_key: str, items) -> None:
        """ONE engine dispatch for every tenant staged on this source
        stream: tenant-major concat with each tenant's events routed to
        its own partition row."""
        eng = self.engine
        cat_cols = {
            k: np.concatenate([np.asarray(cols[k]) for _s, cols, _t, _n in items])
            for k in items[0][1]
        }
        cat_ts = np.concatenate([ts for _s, _c, ts, _n in items])
        part = np.concatenate([
            np.full(len(ts), seat.slot, dtype=np.int32)
            for seat, _c, ts, _n in items
        ])
        offsets = np.cumsum([0] + [len(ts) for _s, _c, ts, _n in items])
        self.state, pending = eng.process_deferred(
            self.state, stream_key, part, cat_cols, cat_ts)
        self.combined_steps += 1
        if pending is None or pending.resolve() == 0:
            self.emit_queue.skip()
            return
        seats = [seat for seat, _c, _t, _n in items]
        nows = [now for _s, _c, _t, now in items]
        self.emit_queue.push(PendingEmit(
            pending.device_arrays(),
            lambda host, p=pending, o=offsets, s=seats, t=cat_ts, n=nows:
                self._demux(p, host, o, s, t, n)))

    def _demux(self, pending, host_arrays, offsets, seats, cat_ts, nows):
        """Split the ev-index-sorted match rows back per tenant (the
        combined batch is tenant-major, so one searchsorted per seat)."""
        ev_idx, out = pending.materialize(host_arrays)
        if len(ev_idx) == 0:
            return
        eng = self.engine
        names = eng.output_names
        bounds = np.searchsorted(ev_idx, offsets)
        for si, seat in enumerate(seats):
            lo, hi = bounds[si], bounds[si + 1]
            if lo == hi:
                continue
            out_cols = {
                name: out[lo:hi, oi].astype(self._out_dtypes[oi])
                for oi, name in enumerate(names)
            }
            seat.pending_out.append(
                (out_cols, cat_ts[ev_idx[lo:hi]], nows[si]))

    # -- per-tenant fault isolation ----------------------------------------

    def _poison_guard(self, seat: _DenseSeat) -> None:
        adapter = seat.adapter
        fi = adapter.faults if adapter is not None else None
        if fi is None or not fi.watches("state.poison"):
            return
        t = seat.slot
        layout = self.engine.layout
        # the tenant's row in the logical form: the float leaf (`regs`)
        # is where a NaN/Inf can live, and the physical row is int32
        rows = layout.unpack({k: self.state[k][t:t + 1] for k in self.state})
        if fi.poisoned("state.poison"):
            rows = _faults.poison_state(rows)
            self._set_tenant_rows(t, rows)
        if not _faults.state_has_poison(rows):
            seat.last_good = rows
            return
        fi.stats.poison_quarantines += 1
        log.warning(
            "multiplex: poisoned state in dense tenant slot %d "
            "quarantined; restoring last known good rows", t)
        self._set_tenant_rows(
            t, seat.last_good if seat.last_good is not None
            else layout.init_logical(1))

    def _set_tenant_rows(self, t: int, logical: Dict) -> None:
        """Write one tenant's row, given in the logical form."""
        jnp = self.engine.jnp
        rows = self.engine.layout.pack(logical)
        self.state = {
            k: self.state[k].at[t:t + 1].set(jnp.asarray(rows[k]))
            for k in self.state
        }

    @waits_on_device
    def _check_overflow(self) -> None:
        total = int(self.engine.jnp.sum(self.state["overflow"]))
        if total > self._ovf_warned:
            log.warning(
                "dense multiplex group: %d pending instance(s) dropped — "
                "instance lanes full; matches may be missing.  Raise "
                "@app:execution('tpu', instances='N') (current %d).",
                total, self.engine.I)
            self._ovf_warned = total

    # -- snapshot / restore -------------------------------------------------

    def snapshot_tenant(self, adapter) -> Dict:
        with self.lock:
            self._dispatch_locked()
            t = adapter.slot
            # one tenant's row, fetched and unpacked here: under the
            # caller's barrier where a persist calls
            with span(STAGE_PERSIST_UNPACK):
                logical = self.engine.layout.unpack(
                    {k: v[t:t + 1] for k, v in self.state.items()})
            return {"dense_state": logical, "base_ts": self.engine.base_ts}

    def restore_tenant(self, adapter, snap: Dict) -> None:
        eng = self.engine
        with self.lock:
            self._dispatch_locked()
            t = adapter.slot
            seat = self.seats[t]
            seat.pending_out.clear()
            seat.last_good = None
            rows = {k: np.asarray(v) for k, v in snap["dense_state"].items()}
            for k, want in eng.layout.logical_shapes(1).items():
                got = rows.get(k)
                if got is None or got.shape != want:
                    raise SiddhiAppRuntimeError(
                        f"cannot restore: tenant snapshot key '{k}' has "
                        f"shape {None if got is None else got.shape}, this "
                        f"group needs {want} (snapshot taken under a "
                        "different @app:execution/@app:multiplex setting)")
            b_snap = snap.get("base_ts")
            if eng.base_ts is None:
                eng.base_ts = b_snap
            elif b_snap is not None and b_snap != eng.base_ts:
                # the snapshot's relative anchors were taken against its
                # own base; re-express them against the group base
                rows = eng.shift_row_ts(rows, eng.base_ts - b_snap)
            self._set_tenant_rows(t, rows)


class DenseMultiplexTenantRuntime:
    """One tenant's runtime over a shared :class:`DenseMultiplexGroup`.

    Presents the ``DensePatternRuntime`` surface the planner, scheduler
    barriers, app_runtime stats discovery and crash recovery expect
    (process_stream_batch / drain / fire / stats / snapshot / restore /
    close + emit/ingest stats)."""

    def __init__(self, group: DenseMultiplexGroup, slot: int,
                 out_stream_id: str, emit,
                 clock=None, faults=None, registry=None, listeners=None):
        self.group = group
        self._listeners = listeners  # the app's exception listeners
        self.slot = slot
        self.engine = group.engine
        self.out_stream_id = out_stream_id
        self.emit_cb = emit
        self.clock = clock
        self.faults = faults
        self.registry = registry
        self.emit_stats = EmitStats()
        self.ingest_stats = IngestStats()
        self.step_invocations = 0
        self._closed = False
        group.bind(slot, self)

    # -- ingest -------------------------------------------------------------

    def process_stream_batch(self, stream_key: str, batch: EventBatch,
                             part=None, keys=None) -> None:
        cur = batch.only(ev.CURRENT)
        n = len(cur)
        if n == 0:
            return
        eng = self.engine
        cols = {}
        for a in eng.numeric_stream_attrs(stream_key):
            col = cur.columns.get(a)
            if col is not None:
                cols[a] = np.asarray(col)
        ts = np.asarray(cur.timestamps, dtype=np.int64)
        # per-tenant chaos hooks: the dedicated engine checks step.dense
        # once per batch and retries transient ingest.put transfers
        if self.faults is not None:
            self.faults.check("step.dense")
        retry_guard(self.faults, "ingest.put")
        now = self.clock() if self.clock is not None else None
        self.group.stage(self, stream_key, cols, ts, now)
        self.step_invocations += 1
        self._deliver_pending()

    # -- delivery -----------------------------------------------------------

    def _deliver_pending(self) -> None:
        while True:
            with self.group.lock:
                seat = self.group.seats[self.slot]
                if seat is None or not seat.pending_out:
                    return
                out_cols, out_ts, now = seat.pending_out.popleft()
            try:
                retry_guard(self.faults, "emit.drain")
            except TransferFaultError as e:
                self.faults.stats.drains_failed += 1
                self._on_fault(e)
                log.error("multiplex: emit drain failed for %s after "
                          "retries; dropping batch: %s",
                          self.out_stream_id, e)
                continue
            mb = EventBatch(
                self.out_stream_id, self.engine.output_names, out_cols,
                out_ts, np.full(len(out_ts), ev.CURRENT, dtype=np.int8))
            if now is not None:
                mb.aux["emit_now"] = now
            self.emit_stats.emit_transfers += 1
            self.emit_cb(mb)

    def _on_fault(self, e: BaseException) -> None:
        _faults.notify_listeners(self._listeners, e)

    # -- barriers / scheduler ----------------------------------------------

    def drain(self) -> None:
        self.group.dispatch_staged()
        self._deliver_pending()

    def next_wakeup(self) -> Optional[int]:
        with self.group.lock:
            seat = self.group.seats[self.slot]
            if seat is not None and (seat.staged is not None
                                     or seat.pending_out):
                return 0
        return None

    def fire(self, now: int) -> None:
        # group dispatch only for this tenant's own staged cycle; a fire
        # woken by pending_out just delivers (see the tumbling adapter)
        with self.group.lock:
            seat = self.group.seats[self.slot]
            mine_staged = seat is not None and seat.staged is not None
        if mine_staged:
            self.group.dispatch_staged()
        self._deliver_pending()

    def on_time(self, now: int) -> None:
        pass

    def on_start(self, now: int) -> None:
        pass

    def stats(self) -> Dict:
        active = self.engine.layout.field(
            self.group.state, "active", self.slot)
        return {
            "engine": "dense-multiplex",
            "partitions_in_use": 1,
            "partition_capacity": 1,
            "instance_lanes": self.engine.I,
            "active_instances": int(active.sum()),
            "dropped_instances": int(
                np.asarray(self.group.state["overflow"])[self.slot]),
            "step_invocations": self.step_invocations,
        }

    # -- persistence --------------------------------------------------------

    def snapshot(self) -> Dict:
        self.drain()
        return self.group.snapshot_tenant(self)

    def restore(self, state: Dict) -> None:
        self.drain()
        self.group.restore_tenant(self, state)

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self.drain()
        finally:
            if self.registry is not None:
                self.registry.release(self.group, self.slot)
            else:
                self.group.free_seat(self.slot)
