"""Shared tumbling-window device engine for N compatible tenants.

One :class:`~siddhi_tpu.ops.device_query.DeviceQueryEngine` — compiled
from the FIRST tenant's query, which the fingerprint guarantees is
byte-identical to what every member would have compiled — serves up to
``slots`` tenants.  The packed device state stacks the tenant axis onto
the group axis: every ``[G, ...]`` accumulator array becomes
``[T*G, ...]``, with tenant ``t`` owning rows ``[t*G, (t+1)*G)``.  The
engine's jitted accumulate step is shape-polymorphic in the group axis
(``G = state["grp_keys"].shape[0]``), so the SAME compiled step runs
over the packed bank — group ids are simply offset by ``t*G`` and the
overflow/dump row moves to ``T*G``.

Host-side pane bookkeeping (group interning tables, pane anchor/fill,
last emitted keys) is PER TENANT: each seat owns a full copy, and a
``_borrow`` context swaps it onto the engine's attributes under the
group lock so the engine's own host machinery (``_intern_groups``,
``_pane_sweep``, ``_flush_cols``, ``_concat_chunks``, ``flush_due``
mirror, ``host_snapshot``/``host_restore``) runs verbatim against the
calling tenant's view.  Only ``base_ts`` — the int32 relative-time
anchor — is shared group-wide; pane anchors are stored relative to it,
and all emitted timestamps are absolute (``base + rel``), so sharing
the anchor is invisible in tenant output.

The hot path: each tenant stages at most one sub-batch; when every
occupied seat has staged (or a barrier / re-stage forces it) the group
concatenates the sub-batches tenant-major, offsets group ids, adds a
tenant-id lane, and dispatches ONE jitted accumulate over the shared
``staged_put`` ingest path — T tenants, one device step.  Per-tenant
pane fills come back as a ``[T]`` count vector from the same step.
Sub-batches that would close a pane (or overflow a lengthBatch pane)
take the engine's exact ``_pane_sweep`` slow path against the packed
state instead, so flush ordering inside the batch matches the
dedicated engine bit for bit.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.emit_queue import EmitStats
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError, TransferFaultError
from siddhi_tpu.core.ingest_stage import IngestStats
from siddhi_tpu.multiplex.common import retry_guard
from siddhi_tpu.util import faults as _faults

log = logging.getLogger(__name__)


class _TenantSeat:
    """Per-tenant host state: interning tables, pane bookkeeping, the
    staged sub-batch, pending host-side outputs, and the last known
    clean device rows (poison quarantine restore point)."""

    __slots__ = (
        "slot", "adapter", "gids", "gvals", "gfree", "glast",
        "pane_end", "pane_fill", "prev_pane_fill", "last_group_keys",
        "staged", "pending_out", "last_good",
    )

    def __init__(self, slot: int):
        self.slot = slot
        self.adapter = None
        self.gids: Dict = {}
        self.gvals: List = []
        self.gfree: List[int] = []
        self.glast: Dict[int, int] = {}
        self.pane_end: Optional[int] = None
        self.pane_fill = 0
        self.prev_pane_fill = 0
        self.last_group_keys: Optional[List] = None
        self.staged = None  # (cols, ts, now) or None
        self.pending_out = deque()  # (out_cols, out_ts, keys, now)
        self.last_good = None  # {key: host rows [G, ...]}


class TumblingMultiplexGroup:
    """Packed [T*G] tumbling accumulator bank shared by up to ``slots``
    structurally identical queries."""

    fingerprint = ""

    def __init__(self, engine, slots: int):
        self.engine = engine
        self.slots = int(slots)
        self.G = int(engine.n_groups)
        self.lock = threading.RLock()
        self.seats: List[Optional[_TenantSeat]] = [None] * self.slots
        self._free = list(range(self.slots - 1, -1, -1))
        # group-wide ingest stats: staged_put counts every combined put
        self.ingest_stats = IngestStats()
        engine.ingest_stats = self.ingest_stats
        engine.faults = None  # fault injection is per tenant, not group
        self._init_host = engine.init_state_host()  # [G, ...] reference
        jnp = engine.jnp
        self.state = {
            k: jnp.asarray(np.tile(v, (self.slots,) + (1,) * (v.ndim - 1)))
            for k, v in self._init_host.items()
        }
        self.base_ts: Optional[int] = None
        # dispatch counters (bench + differential tests)
        self.dispatches = 0       # device dispatch cycles
        self.combined_steps = 0   # one-step-for-all-fast-seats dispatches
        self.slow_steps = 0       # per-tenant pane-sweep dispatches
        self.flush_skips = 0      # empty-pane flushes skipped device-side
        self._mux_acc = self._build_mux_acc()

    # -- seat lifecycle ----------------------------------------------------

    def try_alloc_seat(self) -> Optional[int]:
        with self.lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self.seats[slot] = _TenantSeat(slot)
            return slot

    def bind(self, slot: int, adapter) -> None:
        with self.lock:
            self.seats[slot].adapter = adapter

    def free_seat(self, slot: int) -> None:
        with self.lock:
            seat = self.seats[slot]
            if seat is None:
                return
            self.seats[slot] = None
            self._free.append(slot)
            # reset the freed rows so a future occupant starts clean
            off = slot * self.G
            jnp = self.engine.jnp
            self.state = {
                k: self.state[k].at[off:off + self.G].set(
                    jnp.asarray(self._init_host[k]))
                for k in self.state
            }

    def occupied_count(self) -> int:
        with self.lock:
            return sum(1 for s in self.seats if s is not None)

    # -- host-bookkeeping borrow -------------------------------------------

    @contextmanager
    def _borrow(self, seat: _TenantSeat):
        """Swap ``seat``'s host bookkeeping onto the engine so the
        engine's own pane/intern/flush machinery runs against this
        tenant's view.  Caller must hold the group lock."""
        eng = self.engine
        eng._group_ids = seat.gids
        eng._group_vals = seat.gvals
        eng._group_free = seat.gfree
        eng._group_last = seat.glast
        eng._pane_end = seat.pane_end
        eng._pane_fill = seat.pane_fill
        eng._prev_pane_fill = seat.prev_pane_fill
        eng.last_group_keys = seat.last_group_keys
        eng.base_ts = self.base_ts
        try:
            yield eng
        finally:
            # capture rebinds too (host_restore replaces the dicts)
            seat.gids = eng._group_ids
            seat.gvals = eng._group_vals
            seat.gfree = eng._group_free
            seat.glast = eng._group_last
            seat.pane_end = eng._pane_end
            seat.pane_fill = eng._pane_fill
            seat.prev_pane_fill = eng._prev_pane_fill
            seat.last_group_keys = eng.last_group_keys

    # -- jitted combined accumulate ----------------------------------------

    def _build_mux_acc(self):
        eng = self.engine
        jnp = eng.jnp
        raw = eng.make_acc_step(jit=False)
        slots = self.slots

        def _mux(state, c, t, g, gkv, valid, tid):
            st2, _ = raw(state, c, t, g, gkv, valid)
            # recompute the filter mask per row (XLA CSEs this against
            # the accumulate) and bucket passing counts by tenant lane;
            # pad rows carry tid == slots and fall into the dump bucket.
            fmask = eng._filter_mask(eng._base_env(c, t, t.shape[0]), valid)
            counts = jnp.zeros((slots + 1,), jnp.int32).at[tid].add(
                fmask.astype(jnp.int32))
            return st2, counts[:slots]

        return eng.jax.jit(_mux, donate_argnums=(0,))

    # -- staging + dispatch -------------------------------------------------

    def stage(self, adapter, cols, ts: np.ndarray, now) -> None:
        """Stage one tenant sub-batch; dispatch when the cycle is full
        (every occupied seat staged) or this tenant re-stages."""
        with self.lock:
            seat = self.seats[adapter.slot]
            if seat.staged is not None:
                self._dispatch_locked()
            seat.staged = (cols, ts, now)
            adapter.ingest_stats.staged_batches += 1
            adapter.ingest_stats.note_depth(1)
            if all(s is None or s.staged is not None for s in self.seats):
                self._dispatch_locked()

    def dispatch_staged(self) -> None:
        """Barrier: dispatch whatever is staged (drain/fire/snapshot)."""
        with self.lock:
            self._dispatch_locked()

    def _dispatch_locked(self) -> None:
        staged = [s for s in self.seats if s is not None and s.staged is not None]
        if not staged:
            return
        eng = self.engine
        batches = []
        for seat in staged:
            cols, ts, now = seat.staged
            seat.staged = None
            batches.append((seat, cols, ts, now))
        self._anchor_base(batches)
        self.dispatches += 1

        fast, slow = [], []
        for seat, cols, ts, now in batches:
            n = len(ts)
            rel = (ts - self.base_ts).astype(np.int32)
            with self._borrow(seat):
                grp = eng._intern_groups(cols, ts, n)
            entry = self._classify(seat, cols, rel, grp, n)
            (fast if entry[0] else slow).append((seat, cols, ts, rel, grp, n, now, entry))

        if fast:
            self._dispatch_fast(fast)
        for item in slow:
            self._dispatch_slow(item)
        for seat, _c, _t, _r, _g, _n, _now, _e in fast + slow:
            if seat.adapter is not None:
                seat.adapter.ingest_stats.device_puts += 1
            self._poison_guard(seat)

    def _anchor_base(self, batches) -> None:
        """Establish / shift the shared relative-time anchor.

        Pane anchors are stored relative to ``base_ts`` and every
        emitted timestamp is absolute, so shifting the base (down for a
        late tenant with older events, up at the int32 horizon exactly
        like the dedicated ``_re_anchor``) moves every seat's
        ``pane_end`` by the opposite delta and changes nothing a tenant
        can observe."""
        eng = self.engine
        ts_min = min(int(ts.min()) for _s, _c, ts, _n in batches)
        ts_max = max(int(ts.max()) for _s, _c, ts, _n in batches)
        if self.base_ts is None:
            self.base_ts = ts_min - 1
            return
        delta = 0
        if ts_min - self.base_ts <= 0:
            delta = (ts_min - self.base_ts) - 1  # negative: shift down
        elif ts_max - self.base_ts >= eng._REL_LIMIT:
            horizon = int(eng.window_param) if eng.window_name == "timeBatch" else 0
            delta = (ts_min - self.base_ts) - 1 - horizon
            if delta <= 0 or (ts_max - self.base_ts) - delta >= 2**31:
                raise SiddhiAppRuntimeError(
                    "device query: timestamp span of one batch plus the "
                    "window horizon exceeds the int32 relative-time range")
        if delta:
            self.base_ts += delta
            for s in self.seats:
                if s is not None and s.pane_end is not None:
                    s.pane_end -= delta

    def _classify(self, seat: _TenantSeat, cols, rel, grp, n):
        """Fast-path eligibility: the sub-batch must not close a pane.

        Returns ``(fast, npass_host)``.  The timeBatch pane anchor is
        committed here exactly as ``_pane_sweep`` would (first passing
        batch pins ``pane_end = rel[0] + T``)."""
        eng = self.engine
        if eng.window_name == "timeBatch":
            if seat.pane_end is None:
                seat.pane_end = int(rel[0]) + int(eng.window_param)
                seat.pane_fill = 0
                seat.prev_pane_fill = 0
            return (int(rel.max()) < seat.pane_end, None)
        # lengthBatch: pane closes when passing events reach L
        with self._borrow(seat):
            fmask = eng._host_filter_mask(cols, rel, n)
        npass = int(np.count_nonzero(fmask))
        remaining = int(eng.window_param) - seat.pane_fill
        return (npass < remaining, npass)

    def _dispatch_fast(self, fast) -> None:
        """ONE jitted accumulate for every pane-interior sub-batch:
        tenant-major concat, group ids offset by slot*G, tenant-id lane
        for the per-seat passing counts."""
        eng = self.engine
        jnp = eng.jnp
        K = max(len(eng._numeric_group_keys), 1)
        cat_cols = {
            k: np.concatenate([np.asarray(cols[k])[:n] for _s, cols, _t, _r, _g, n, _now, _e in fast])
            for k in fast[0][1]
        }
        cat_rel = np.concatenate([rel[:n] for _s, _c, _t, rel, _g, n, _now, _e in fast])
        cat_grp = np.concatenate([
            (grp[:n] + seat.slot * self.G).astype(np.int32)
            for seat, _c, _t, _r, grp, n, _now, _e in fast
        ])
        gkv_parts, tid_parts = [], []
        for seat, _cols, _ts, _rel, grp, n, _now, _entry in fast:
            with self._borrow(seat):
                gkv_parts.append(eng._gk_vals(grp[:n], n))
            tid_parts.append(np.full(n, seat.slot, dtype=np.int32))
        ntot = len(cat_rel)
        c, t, g, _wg, valid, B = eng._pad(cat_cols, cat_rel, cat_grp, ntot)
        gkv = np.zeros((B, K), dtype=np.float32)
        gkv[:ntot] = np.concatenate(gkv_parts)
        tid = np.full(B, self.slots, dtype=np.int32)
        tid[:ntot] = np.concatenate(tid_parts)
        self.state, counts = self._mux_acc(
            self.state, c, t, g, jnp.asarray(gkv), valid, jnp.asarray(tid))
        self.combined_steps += 1
        counts_h = np.asarray(eng.jax.device_get(counts))
        for seat, _cols, _ts, _rel, _grp, _n, _now, entry in fast:
            # timeBatch mirrors the dedicated device-count derivation;
            # lengthBatch mirrors its host fmask count
            npass = entry[1]
            seat.pane_fill += int(counts_h[seat.slot]) if npass is None else npass

    def _dispatch_slow(self, item) -> None:
        """Pane-closing sub-batch: run the engine's exact
        ``_pane_sweep`` against this tenant's packed rows."""
        seat, cols, ts, rel, grp, n, now, _entry = item
        eng = self.engine
        self.slow_steps += 1
        chunks = []

        def acc_segment(state, cols_, rel_, grp_, idx):
            return self._acc_rows(seat, state, cols_, rel_, grp_, idx)

        def flush_pane(st, when):
            st, fcols, nf, keys = self._flush_slice(st, seat)
            chunks.append((fcols, when, nf, keys))
            return st

        with self._borrow(seat):
            self.state = eng._pane_sweep(
                self.state, cols, rel, grp, n, acc_segment, flush_pane)
            out_cols, out_ts = eng._concat_chunks(chunks)
        if len(out_ts):
            seat.pending_out.append(
                (out_cols, out_ts, seat.last_group_keys, now))

    def _acc_rows(self, seat: _TenantSeat, state, cols, rel, grp, idx):
        """``_acc_segment`` against the packed bank: device group ids
        offset by slot*G, group-key values from the tenant's LOCAL ids
        (the borrow is active — ``_gk_vals`` reads the seat tables)."""
        eng = self.engine
        acc = eng.make_acc_step()
        n = len(idx)
        c, t, g, _wg, valid, B = eng._pad(
            {k: np.asarray(v)[idx] for k, v in cols.items()},
            rel[idx], (grp[idx] + seat.slot * self.G).astype(np.int32), n)
        gkv = np.zeros((B, max(len(eng._numeric_group_keys), 1)),
                       dtype=np.float32)
        gkv[:n] = eng._gk_vals(grp[idx], n)
        state, n_pass = acc(state, c, t, g, eng.jnp.asarray(gkv), valid)
        return state, int(eng.jax.device_get(n_pass))

    # -- flush --------------------------------------------------------------

    def _flush_slice(self, state, seat: _TenantSeat):
        """Flush the tenant's [G] row slice.  A pane with zero passing
        events left every accumulator at its reset value (misses
        scatter identity values and dump into the dropped row), so the
        device dispatch is skipped entirely — same state, no output.
        timeBatch only: its fill count is final at flush time, while
        lengthBatch increments AFTER the closing flush (and only ever
        closes full panes anyway)."""
        eng = self.engine
        if eng.window_name == "timeBatch" and eng._pane_fill == 0:
            self.flush_skips += 1
            return (state, eng._empty_cols(), 0,
                    [] if eng.group_exprs else None)
        off = seat.slot * self.G
        sl = {k: state[k][off:off + self.G] for k in state}
        sl, fcols, nf, keys, _stamps = eng._flush_cols(sl)
        state = {k: state[k].at[off:off + self.G].set(sl[k]) for k in state}
        return state, fcols, nf, keys

    def flush_due_for(self, adapter, now: int) -> None:
        """Timer flush for one tenant: mirror of ``engine.flush_due``
        over the tenant's row slice (caller dispatched staged first)."""
        eng = self.engine
        with self.lock:
            seat = self.seats[adapter.slot]
            chunks = []
            with self._borrow(seat):
                while True:
                    w = eng.pane_wakeup()
                    if w is None or w > now:
                        break
                    self.state, fcols, nf, keys = self._flush_slice(
                        self.state, seat)
                    chunks.append((fcols, w, nf, keys))
                    eng._advance_pane()
                out_cols, out_ts = eng._concat_chunks(chunks)
            if len(out_ts):
                seat.pending_out.append(
                    (out_cols, out_ts, seat.last_group_keys, now))

    def pane_wakeup_for(self, adapter) -> Optional[int]:
        with self.lock:
            seat = self.seats[adapter.slot]
            if seat is None:
                return None
            with self._borrow(seat):
                return self.engine.pane_wakeup()

    # -- per-tenant fault isolation ----------------------------------------

    def _poison_guard(self, seat: _TenantSeat) -> None:
        """Quarantine a poisoned tenant's rows without touching the
        other seats — the packed-bank analog of
        ``DevicePipeline.quarantine``."""
        adapter = seat.adapter
        fi = adapter.faults if adapter is not None else None
        if fi is None or not fi.watches("state.poison"):
            return
        eng = self.engine
        off = seat.slot * self.G
        rows = {k: self.state[k][off:off + self.G] for k in self.state}
        if fi.poisoned("state.poison"):
            rows = _faults.poison_state(rows)
            self.state = {
                k: self.state[k].at[off:off + self.G].set(rows[k])
                for k in self.state
            }
        if not _faults.state_has_poison(rows):
            seat.last_good = _faults.host_copy(rows)
            return
        fi.stats.poison_quarantines += 1
        log.warning(
            "multiplex: poisoned state in tenant slot %d quarantined; "
            "restoring last known good rows", seat.slot)
        good = seat.last_good if seat.last_good is not None else self._init_host
        jnp = eng.jnp
        self.state = {
            k: self.state[k].at[off:off + self.G].set(jnp.asarray(good[k]))
            for k in self.state
        }

    # -- snapshot / restore -------------------------------------------------

    def snapshot_tenant(self, adapter) -> Dict:
        """Dedicated-shape snapshot of one tenant (device rows [G,...] +
        host bookkeeping), interchangeable with a dedicated runtime's."""
        with self.lock:
            self._dispatch_locked()
            seat = self.seats[adapter.slot]
            off = adapter.slot * self.G
            dev = {k: np.asarray(self.state[k][off:off + self.G])
                   for k in self.state}
            with self._borrow(seat):
                host = self.engine.host_snapshot()
            return {"device_state": dev, "host": host}

    def restore_tenant(self, adapter, snap: Dict) -> None:
        eng = self.engine
        with self.lock:
            self._dispatch_locked()
            seat = self.seats[adapter.slot]
            seat.pending_out.clear()
            seat.last_good = None
            dev = snap["device_state"]
            for k, ref in self._init_host.items():
                got = dev.get(k)
                if got is None or tuple(np.shape(got)) != ref.shape:
                    raise SiddhiAppRuntimeError(
                        f"restored device state key '{k}' has shape "
                        f"{None if got is None else tuple(np.shape(got))}, "
                        f"engine expects {ref.shape}")
            off = adapter.slot * self.G
            jnp = eng.jnp
            self.state = {
                k: self.state[k].at[off:off + self.G].set(jnp.asarray(dev[k]))
                for k in self.state
            }
            with self._borrow(seat):
                eng.host_restore(snap["host"])
                # the snapshot's pane anchor is relative to ITS base;
                # re-express it against the group's shared base
                b_snap = eng.base_ts
                if self.base_ts is None:
                    self.base_ts = b_snap
                elif b_snap is not None and eng._pane_end is not None:
                    eng._pane_end += b_snap - self.base_ts


class MultiplexTenantRuntime:
    """One tenant's runtime over a shared :class:`TumblingMultiplexGroup`.

    Presents the same surface as ``core/device_single.DeviceQueryRuntime``
    (process_stream_batch / drain / fire / next_wakeup / snapshot /
    restore / emit+ingest stats), so planner wiring, scheduler barriers,
    statistics discovery and crash recovery treat it identically."""

    def __init__(self, group: TumblingMultiplexGroup, slot: int,
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

    def process_stream_batch(self, batch: EventBatch, keys=None) -> None:
        cur = batch.only(ev.CURRENT)
        n = len(cur)
        if n == 0:
            return
        eng = self.engine
        cols = {a: np.asarray(cur.columns[a]) for a in eng.all_attrs
                if a in cur.columns}
        ts = np.asarray(cur.timestamps, dtype=np.int64)
        # per-tenant transient ingest faults retry/exhaust here, before
        # any group state is touched — a failing tenant never wedges
        # the shared engine
        retry_guard(self.faults, "ingest.put")
        now = self.clock() if self.clock is not None else None
        self.group.stage(self, cols, ts, now)
        self.step_invocations += 1
        self._deliver_pending()

    # -- delivery -----------------------------------------------------------

    def _deliver_pending(self) -> None:
        """Emit this tenant's demultiplexed outputs OUTSIDE the group
        lock (lock order is app -> group, never group -> app)."""
        while True:
            with self.group.lock:
                seat = self.group.seats[self.slot]
                if seat is None or not seat.pending_out:
                    return
                out_cols, out_ts, gkeys, now = seat.pending_out.popleft()
            try:
                retry_guard(self.faults, "emit.drain")
            except TransferFaultError as e:
                self.faults.stats.drains_failed += 1
                self._on_fault(e)
                log.error("multiplex: emit drain failed for %s after "
                          "retries; dropping batch: %s",
                          self.out_stream_id, e)
                continue
            self._emit(out_cols, out_ts, gkeys, now)

    def _emit(self, out_cols, out_ts, keys, now) -> None:
        if len(out_ts) == 0:
            return
        eng = self.engine
        mb = EventBatch(
            self.out_stream_id, eng.output_names, out_cols, out_ts,
            np.full(len(out_ts), ev.CURRENT, dtype=np.int8))
        if keys is not None:
            if len(keys) != len(mb):
                raise SiddhiAppRuntimeError(
                    f"device query emitted {len(mb)} rows but "
                    f"{len(keys)} group keys")
            mb.aux["group_keys"] = list(keys)
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
            if seat is None:
                return None
            if seat.staged is not None or seat.pending_out:
                return 0
        return self.group.pane_wakeup_for(self)

    def fire(self, now: int) -> None:
        # dispatch the group only when THIS tenant's seat is staged (its
        # previous cycle — a re-send or a processing-time tick must not
        # leave it parked).  A fire woken purely by pending_out would
        # otherwise flush OTHER tenants' half-staged cycles through the
        # slow path and defeat the packing (each app runs its own
        # scheduler, so these fires interleave mid-cycle).
        with self.group.lock:
            seat = self.group.seats[self.slot]
            mine_staged = seat is not None and seat.staged is not None
        if mine_staged:
            self.group.dispatch_staged()
        self.group.flush_due_for(self, now)
        self._deliver_pending()

    def on_start(self, now: int) -> None:
        pass

    def on_time(self, now: int) -> None:
        pass

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
