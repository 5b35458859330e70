"""Hybrid skew router: hot partition keys ride the associative scan.

The partition axis cannot split ONE key's event stream — the dense
engine (ops/dense_nfa.py) advances a partition's events through
sequential collision rounds, so a single hot key throttles the whole
batch cycle (the canonical skew failure under the ROADMAP's
millions-of-users north star).  ``HotKeyRouterRuntime`` wraps a
partitioned ``DensePatternRuntime`` and, per junction cycle:

1. feeds a host-side space-saving heavy-hitter sketch (O(k) state,
   deterministic — crash replay reproduces every routing decision)
   with the cycle's key histogram;
2. applies promote/demote hysteresis (``@app:hotkeys(k, promote,
   demote)`` knobs): keys whose decayed share crosses ``promote`` move
   onto a ``HotKeyScanEngine`` slot (ops/hotkey_scan.py), keys that
   cool below ``demote`` move back;
3. converts pending-match state EXACTLY at each boundary — a dense
   partition row's instance lanes to/from the scan's per-lane
   (youngest start, count) pair — so routing never alters emissions;
4. splits the batch: cold keys take the unchanged dense path, hot
   keys are packed on the scan's ``[H, n_pad]`` slot axis and advance
   in O(log n) scan depth via ONE jitted step.

The hot path rides the dense runtime's OWN ``DevicePipeline``
(core/device_pipeline.py): its ``IngestStage`` (``staged_put`` H2D +
count-gate staging), its count-gated async ``EmitQueue`` (the only
device→host path — state handoffs at promote/demote fetch through a
queued ``PendingEmit`` + drain barrier, so the fault harness's
``emit.drain`` retry ladder and isolation cover them), and its
``state.poison`` quarantine, here over the scan state.  Emission
content is bit-identical to the host engine on the eligible class;
within one cycle the cold sub-batch's rows emit before the hot
sub-batch's (each internally in event order, carrying
``aux["event_indices"]`` for consumers that need the interleaved
order).

Snapshot/restore demotes every hot key first, so the persisted tree is
a plain dense snapshot (plus sketch counters) — restorable by older
readers and by apps with different ``@app:hotkeys`` settings.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.event import EventBatch

log = logging.getLogger("siddhi_tpu")


class HotKeyStats:
    """Router decision counters (host ints, thin-gauge style — the
    statistics manager reads them live)."""

    __slots__ = ("promotions", "demotions", "routed_events",
                 "routed_cycles", "handoff_aborts")

    def __init__(self):
        self.promotions = 0
        self.demotions = 0
        self.routed_events = 0
        self.routed_cycles = 0
        # state-handoff fetches dropped by a fault (the key kept its
        # previous placement — routing stayed correct, only later)
        self.handoff_aborts = 0


class SpaceSavingSketch:
    """Space-saving heavy hitters: at most ``cap`` counters; a new key
    arriving at capacity evicts the minimum counter and inherits its
    count (the classic overestimate bound).  ``decay`` ages counts each
    cycle so share tracks the recent mix, not all history.  Entirely
    deterministic: same input sequence, same estimates."""

    __slots__ = ("cap", "decay", "counts", "total")

    def __init__(self, cap: int, decay: float = 0.9):
        self.cap = int(cap)
        self.decay = float(decay)
        self.counts: Dict = {}
        self.total = 0.0

    def update(self, keys: np.ndarray, counts: np.ndarray):
        """One cycle's key histogram (np.unique output)."""
        self.total = self.total * self.decay + float(counts.sum())
        for k in list(self.counts):
            v = self.counts[k] * self.decay
            if v < 0.5:
                del self.counts[k]
            else:
                self.counts[k] = v
        for k, c in zip(keys.tolist(), counts.tolist()):
            cur = self.counts.get(k)
            if cur is not None:
                self.counts[k] = cur + c
            elif len(self.counts) < self.cap:
                self.counts[k] = float(c)
            else:
                mk = min(self.counts, key=self.counts.get)
                mv = self.counts.pop(mk)
                self.counts[k] = mv + c

    def share(self, key) -> float:
        if self.total <= 0:
            return 0.0
        return self.counts.get(key, 0.0) / self.total

    def heavy(self, threshold: float) -> List:
        """Keys at or above ``threshold`` share, heaviest first
        (deterministic tie-break on the printable key)."""
        floor = threshold * self.total
        out = [(v, k) for k, v in self.counts.items() if v >= floor]
        out.sort(key=lambda vk: (-vk[0], repr(vk[1])))
        return [k for _v, k in out]


class HotKeyRouterRuntime:
    """Junction-facing wrapper of one partitioned DensePatternRuntime
    plus one HotKeyScanEngine.  Presents the full pattern-processor
    surface; everything not routing-specific delegates to the dense
    runtime (``__getattr__``), so the partition receiver, scheduler,
    snapshot and stats wiring see one runtime."""

    def __init__(self, dense, scan_engine, *, promote: float,
                 demote: float, query_name: str = ""):
        self._dense = dense
        self._scan = scan_engine
        # the hot path rides the dense runtime's pipeline: one ingest
        # stage and one emit queue keep cold and hot emits FIFO.  Its
        # quarantine guards the scan state (the dense state is int32).
        self._pipe = dense.pipeline
        self._promote_at = float(promote)
        self._demote_at = float(demote)
        self.query_name = query_name
        self.hot_stats = HotKeyStats()
        self.sketch = SpaceSavingSketch(
            cap=max(16, 4 * scan_engine.n_slots))
        # key -> {"slot": int, "row": dense logical row}
        self._slots: Dict = {}
        self._free_slots: List[int] = list(
            range(scan_engine.n_slots))[::-1]
        self._state = scan_engine.init_state()
        self.lowered_to = "hotkey"

    # everything not overridden IS the dense runtime's behavior —
    # intern_keys, engine, emit_stats, overflow_total, on_time,
    # next_wakeup, fire, on_start, step_invocations, ...
    def __getattr__(self, name):
        return getattr(self._dense, name)

    @property
    def on_purge_keys(self):
        return self._dense.on_purge_keys

    @on_purge_keys.setter
    def on_purge_keys(self, cb):
        self._dense.on_purge_keys = cb

    # -- metrics -------------------------------------------------------------

    def hot_metrics(self) -> Dict[str, float]:
        """Stats-feed gauges (util/statistics.py HotKeyTracker)."""
        s = self.hot_stats
        return {
            "hotkeyPromotions": s.promotions,
            "hotkeyDemotions": s.demotions,
            "hotkeyRoutedEvents": s.routed_events,
            "hotkeyActiveKeys": len(self._slots),
        }

    def stats(self) -> Dict:
        d = self._dense.stats()
        d["engine"] = "hotkey"
        d["hot_slots"] = self._scan.n_slots
        d["hot_keys"] = [rec["slot"] for rec in self._slots.values()]
        d.update(self.hot_metrics())
        return d

    # -- state handoff -------------------------------------------------------

    def _fetch_rows(self, arrays) -> Optional[List[np.ndarray]]:
        """Barrier-fetch small device slices through the sanctioned
        emit-queue path (FIFO with pending emissions, ``emit.drain``
        fault site + bounded retry).  Returns None when a fault dropped
        the drain — the caller aborts the handoff and the key keeps its
        current placement (graceful: only WHEN it routes changes)."""
        from siddhi_tpu.core.emit_queue import PendingEmit

        got: Dict[str, List[np.ndarray]] = {}

        def grab(host):
            got["host"] = list(host)

        self._pipe.emit_queue.push(PendingEmit(list(arrays), grab))
        self._pipe.drain()
        if "host" not in got:
            self.hot_stats.handoff_aborts += 1
            return None
        return got["host"]

    def _promote(self, key, row: int) -> bool:
        if not self._free_slots:
            return False
        dense, scan = self._dense, self._scan
        jnp = scan.jnp
        phys = int(dense._phys_rows(np.int64(row)))
        st = dense.state
        layout = dense.engine.layout
        host = self._fetch_rows(
            [layout.words(st, "active", phys),
             layout.words(st, "first_ts", phys)])
        if host is None:
            return False
        host = [layout.decode("active", host[0]),
                layout.decode("first_ts", host[1])]
        dense_base = dense.engine.base_ts or 0
        if scan.base_ts is None:
            scan.base_ts = dense_base
        v_row, c_row = scan.dense_row_to_slot(
            host[0], host[1], dense_base, scan.base_ts)
        slot = self._free_slots.pop()
        self._state = {
            "v": self._state["v"].at[slot].set(jnp.asarray(v_row)),
            "c": self._state["c"].at[slot].set(jnp.asarray(c_row)),
        }
        # clear the dense row to its init template (the pending chains
        # moved); the row stays interned to the key — demotion writes
        # back into it.  `overflow` is a durable drop counter, keep it.
        init = dense.engine.layout.init_physical(1)
        new_state = dict(st)
        for k, arr in new_state.items():
            if k == "overflow":
                continue
            new_state[k] = arr.at[phys].set(jnp.asarray(init[k][0]))
        dense.state = new_state
        self._slots[key] = {"slot": slot, "row": row}
        self.hot_stats.promotions += 1
        log.info("hotkey router '%s': promoted key %r (share %.3f) to "
                 "scan slot %d", self.query_name, key,
                 self.sketch.share(key), slot)
        return True

    def _demote(self, key) -> bool:
        rec = self._slots.pop(key)
        slot, row = rec["slot"], rec["row"]
        dense, scan = self._dense, self._scan
        jnp = scan.jnp
        host = self._fetch_rows(
            [self._state["v"][slot], self._state["c"][slot]])
        if host is None:
            self._slots[key] = rec  # keep hot; retry next cycle
            return False
        active, first_ts, dropped = scan.slot_to_dense_row(
            host[0], host[1], scan.base_ts or 0,
            dense.engine.base_ts or 0, dense.engine.I)
        phys = int(dense._phys_rows(np.int64(row)))
        layout = dense.engine.layout
        st = layout.with_field(dense.state, "active", phys, active)
        st = layout.with_field(st, "first_ts", phys, first_ts)
        if dropped:
            st["overflow"] = st["overflow"].at[phys].add(
                np.int32(dropped))
        dense.state = st
        v0, c0 = scan.slot_init_rows()
        self._state = {
            "v": self._state["v"].at[slot].set(jnp.asarray(v0)),
            "c": self._state["c"].at[slot].set(jnp.asarray(c0)),
        }
        self._free_slots.append(slot)
        self.hot_stats.demotions += 1
        log.info("hotkey router '%s': demoted key %r (share %.3f) back "
                 "to dense row %d", self.query_name, key,
                 self.sketch.share(key), row)
        return True

    def demote_all(self):
        for key in list(self._slots):
            self._demote(key)

    # -- routing decisions ---------------------------------------------------

    def _route_cycle(self, keys: np.ndarray, part: np.ndarray):
        """Update the sketch with this cycle's histogram and apply the
        promote/demote hysteresis.  Promotion needs the key's dense row,
        so only keys present in this cycle promote (hot keys are, by
        definition)."""
        try:
            uniq, counts = np.unique(keys, return_counts=True)
        except TypeError:  # mixed-type keys cannot histogram — stay dense
            return
        self.sketch.update(uniq, counts)
        for key in list(self._slots):
            if self.sketch.share(key) < self._demote_at:
                self._demote(key)
        if self._free_slots:
            hot_now = self.sketch.heavy(self._promote_at)
            if hot_now:
                in_cycle = {k: i for i, k in enumerate(uniq.tolist())}
                for key in hot_now:
                    if not self._free_slots:
                        break
                    if key in self._slots or key not in in_cycle:
                        continue
                    pos = np.flatnonzero(keys == key)
                    self._promote(key, int(part[pos[0]]))

    # -- event path ----------------------------------------------------------

    def receive_keyed(self, stream_key: str, cur: EventBatch, keys):
        self.process_stream_batch(
            stream_key, cur, part=self.intern_keys(keys), keys=keys)

    def process_stream_batch(self, stream_key: str, batch: EventBatch,
                             part: Optional[np.ndarray] = None,
                             keys=None):
        cur = batch.only(ev.CURRENT)
        n = len(cur)
        if n == 0:
            return
        if (part is None or keys is None
                or getattr(keys, "dtype", None) is None
                or len(part) != n):
            # no key side channel (or misaligned) — the whole batch
            # stays on the dense path, no routing this cycle
            self._dense.process_stream_batch(
                stream_key, cur, part=part, keys=keys)
            return
        self._route_cycle(keys, part)
        if not self._slots:
            self._dense.process_stream_batch(
                stream_key, cur, part=part, keys=keys)
            return
        hot_mask = np.zeros(n, dtype=bool)
        slot_pos: Dict[int, np.ndarray] = {}
        for key, rec in self._slots.items():
            pos = np.flatnonzero(keys == key)
            if len(pos):
                hot_mask[pos] = True
                slot_pos[rec["slot"]] = pos
        if not slot_pos:
            self._dense.process_stream_batch(
                stream_key, cur, part=part, keys=keys)
            return
        cold_mask = ~hot_mask
        if cold_mask.any():
            self._dense.process_stream_batch(
                stream_key, cur.mask(cold_mask),
                part=part[cold_mask], keys=keys[cold_mask])
        # hot keys stay "in use" for the idle-purge clock even though
        # their dense rows see no events while promoted
        np.maximum.at(self._dense._row_last_used, part[hot_mask],
                      cur.timestamps[hot_mask])
        self._process_hot(slot_pos, cur, keys)

    def _process_hot(self, slot_pos: Dict[int, np.ndarray],
                     cur: EventBatch, keys):
        # hot-path batches get their own cycle tokens (engine kind
        # 'hotkey'); the cold remainder traced under 'dense' already
        with self._pipe.cycle(len(cur), kind="hotkey") as tok:
            self._advance_hot(slot_pos, cur, keys, tok)

    def _advance_hot(self, slot_pos, cur: EventBatch, keys, tok):
        from siddhi_tpu.core.device_pipeline import CountGate
        from siddhi_tpu.core.ingest_stage import staged_put

        dense, scan, pipe = self._dense, self._scan, self._pipe
        cols = {a: c for a, c in cur.columns.items()
                if a in scan.base._lane_dtype}
        ts = cur.timestamps
        put, meta = scan.pack_cycle(slot_pos, cols, ts)
        put_dev = staged_put(put, faults=pipe.faults,
                             stats=pipe.ingest_stats)
        self._state, emit_dev, n_rows = scan.dispatch(
            self._state, put_dev)
        # the scan's emit counts were computed before the poison: the
        # state is put back, the cycle's outputs still go out
        self._state, _poisoned = pipe.quarantine(
            self._state, scan.init_state)
        n_routed = int(sum(len(p) for p in slot_pos.values()))
        self.hot_stats.routed_events += n_routed
        self.hot_stats.routed_cycles += 1
        dense.step_invocations += 1
        now = pipe.now()  # sampled at receive time, bound into deliver
        out_cols = {attr: cur.columns[attr]
                    for _nm, attr in self._out_pairs()}
        pipe.submit(
            tok, CountGate(n_rows, [emit_dev]),
            lambda host: self._build_hot(host, meta, out_cols, ts, keys,
                                         now),
            self._dense.emit_cb)

    def _out_pairs(self):
        """(output name, final-node attribute) pairs — eligibility
        guarantees every dense out_spec source is ('cand', attr)."""
        return [(nm, src[1]) for nm, src in self._dense.engine.out_spec]

    def _build_hot(self, host, meta, out_cols, ts, keys, now):
        emit_h = host[0]  # [H, n_pad] f32 per-event row counts
        parts = []
        for slot, pos in meta["slot_pos"].items():
            cnt = np.rint(emit_h[slot, :len(pos)]).astype(np.int64)
            if cnt.any():
                parts.append(np.repeat(pos, cnt))
        if not parts:
            return None
        rep = np.sort(np.concatenate(parts))
        pairs = self._out_pairs()
        names = [nm for nm, _a in pairs]
        mb = EventBatch(
            self._dense.out_stream_id, names,
            {nm: out_cols[attr][rep] for nm, attr in pairs},
            ts[rep], np.full(len(rep), ev.CURRENT, dtype=np.int8),
        )
        mb.aux["partition_keys"] = keys[rep].tolist()
        mb.aux["event_indices"] = rep
        if now is not None:
            mb.aux["emit_now"] = now
        return mb

    # -- barriers / lifecycle ------------------------------------------------

    def drain(self):
        self._dense.drain()

    def purge_idle(self, now: int, idle_ms: int):
        """Hot rows' activity clocks advance every routed cycle, so a
        promoted key only looks idle when it IS idle — demote it first
        so its pending chains survive in the recycled-row protocol."""
        for key in list(self._slots):
            row = self._slots[key]["row"]
            if now - int(self._dense._row_last_used[row]) >= idle_ms:
                self._demote(key)
        self._dense.purge_idle(now, idle_ms)

    def snapshot(self) -> Dict:
        """Demote-all first: the persisted tree is a plain dense
        snapshot (restorable under different @app:hotkeys settings);
        the sketch rides along so routing warmth survives restore."""
        self.demote_all()
        tree = self._dense.snapshot()
        tree["hotkey_sketch"] = {
            "counts": dict(self.sketch.counts),
            "total": self.sketch.total,
        }
        return tree

    def restore(self, state: Dict):
        self._slots.clear()
        self._free_slots = list(range(self._scan.n_slots))[::-1]
        self._state = self._scan.init_state()
        self._scan.base_ts = None
        self._pipe.forget_clean_copy()
        sk = state.get("hotkey_sketch")
        self.sketch = SpaceSavingSketch(cap=self.sketch.cap,
                                        decay=self.sketch.decay)
        if sk:
            self.sketch.counts = dict(sk["counts"])
            self.sketch.total = float(sk["total"])
        self._dense.restore(
            {k: v for k, v in state.items() if k != "hotkey_sketch"})

    def close(self):
        self._dense.close()
