"""A device table addresses a row by its slot.

The host resolves key -> slot (``DeviceTable.probe_view`` over the
array-form index kept beside ``_pk_map``), the probe gathers by that
slot and checks on the device that the slot is live and holds the key,
the scatter writes by index.  Held here:

* a seeded history of every mutation shape, probed after every step, is
  bit-identical to the host ``InMemoryTable`` twin, at a capacity that
  is no power of two and with batches past the (patched) chunk bound;
* pad lanes and -1 slots touch nothing, duplicates resolve to the last
  writer, a kill wins over the same step's write;
* neither lowered program holds an operand with both a batch and a
  capacity dimension, and both have the same operations at 1,024 slots
  as at 65,536;
* the index is stale after every path that unmaps a key, is rebuilt
  once before the next lookup, and both are counted;
* a probe dispatched before a scatter reads the arrays it was
  dispatched against (nothing is donated);
* lookups beside threads that insert and delete never lose a key.
"""

import re

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.devtable import DeviceTable
from siddhi_tpu.devtable.join import DevTableJoinRuntime

BODY = (
    "define stream S (k int, x float); "
    "define stream Ins (k int, v float, f bool); "
    "define stream Del (k int); "
    "define stream DelV (v float); "
    "define stream Upd (k int, v float); "
    "define stream Ups (k int, v float, f bool); "
    "define stream Ren (k int, nk int); "
    "@PrimaryKey('k') define table T (k int, v float, f bool); "
    "from Ins insert into T; "
    "from Del delete T on T.k == k; "
    "from DelV delete T on T.v == v; "          # no pk: delete_slots
    "from Upd update T set T.v = v on T.k == k; "
    "from Ups update or insert into T set T.v = v, T.f = f on T.k == k; "
    "from Ren update T set T.k = nk on T.k == k; "   # pk rewrite
    "@info(name='j') from S join T as t on S.k == t.k and S.x > t.v "
    "select S.k as k, S.x as x, t.v as v, t.f as f insert into Out;")

COLS = {
    "S": (("k", np.int32), ("x", np.float32)),
    "Ins": (("k", np.int32), ("v", np.float32), ("f", np.bool_)),
    "Del": (("k", np.int32),),
    "DelV": (("v", np.float32),),
    "Upd": (("k", np.int32), ("v", np.float32)),
    "Ups": (("k", np.int32), ("v", np.float32), ("f", np.bool_)),
    "Ren": (("k", np.int32), ("nk", np.int32)),
}


class App:
    """The app on a device table (``capacity``) or on the host table
    (None), fed whole junction batches."""

    def __init__(self, capacity=37):
        self.manager = SiddhiManager()
        header = "@app:name('slots') @app:playback @app:execution('tpu') "
        if capacity is not None:
            header += f"@app:devtables(capacity='{capacity}') "
        self.rt = self.manager.create_siddhi_app_runtime(header + BODY)
        self.rows = []
        self.rt.add_callback("Out", lambda evs: self.rows.extend(
            (e.timestamp,) + tuple(e.data) for e in evs))
        self.rt.start()
        self.table = self.rt.tables["T"]
        self.join = getattr(self.rt.query_runtimes["j"], "device_runtime",
                            None)
        self.ts = 1000

    def send(self, stream, rows):
        names = [nm for nm, _dt in COLS[stream]]
        cols = {nm: np.asarray([r[i] for r in rows], dtype=dt)
                for i, (nm, dt) in enumerate(COLS[stream])}
        ts = self.ts + np.arange(len(rows), dtype=np.int64)
        self.ts += len(rows)
        self.rt.get_input_handler(stream).send_batch(
            EventBatch(stream, names, cols, ts))

    def probe(self, keys, x=50.0):
        """The rows one probe batch delivers.  The join's own pipeline
        is flushed, not the app's barrier: tombstones stay until a
        ``restore_own_snapshot`` or an insert that would overflow."""
        before = len(self.rows)
        self.send("S", [(int(k), x) for k in keys])
        if self.join is not None:
            self.join.drain()
        return self.rows[before:]

    def restore_own_snapshot(self):
        self.rt.drain_device_emits()        # pins the newest revision
        self.table.restore(self.table.snapshot())

    def table_rows(self):
        b = self.table.rows_batch()
        return sorted(tuple(b.columns[nm][i] for nm in b.attribute_names)
                      for i in range(len(b)))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.manager.shutdown()
        return False


# -- (a) a seeded history against the host twin --------------------------------

N_KEYS = 30     # under the capacity of 37: the table never demotes


def history(seed, steps=60):
    """``(stream, rows)`` or ``("restore", None)`` steps.  The prelude
    re-inserts a deleted key (it lands in another slot) and rewrites a
    primary key onto a live one; the churn that follows runs the
    high-water mark into the capacity, so slots are compacted and
    reused."""
    rng = np.random.default_rng(seed)
    val = lambda: float(np.float32(rng.uniform(0, 100)))
    flag = lambda: bool(rng.integers(0, 2))
    key = lambda: int(rng.integers(0, N_KEYS))
    out = [
        ("Ins", [(k, val(), flag()) for k in range(12)]),
        ("Del", [(5,), (7,)]),
        ("Ins", [(5, val(), flag())]),
        ("Ren", [(3, 4)]),           # 4's row dies, 3's row becomes 4
        ("Ren", [(6, 40)]),          # onto a key the table never held
        ("restore", None),
    ]
    for _ in range(steps):
        roll = rng.random()
        n = int(rng.integers(1, 12))
        if roll < 0.22:
            out.append(("Ins", [(key(), val(), flag()) for _ in range(n)]))
        elif roll < 0.40:
            out.append(("Del", [(key(),) for _ in range(n)]))
        elif roll < 0.52:
            out.append(("Upd", [(key(), val()) for _ in range(n)]))
        elif roll < 0.80:
            out.append(("Ups", [(key(), val(), flag()) for _ in range(n)]))
        elif roll < 0.88:
            out.append(("Ren", [(key(), key())]))
        elif roll < 0.94:
            # by value: the generic callback, delete_slots
            out.append(("DelV", [(val(),), (50.0,)]))
        else:
            out.append(("restore", None))
    return out


def replay(app, steps, seed, after_step=lambda i: None):
    """Every probe's rows, a probe of 20 keys (known, deleted and never
    seen) after every step."""
    rng = np.random.default_rng(seed + 1)
    seen = []
    for i, (stream, rows) in enumerate(steps):
        if stream == "restore":
            app.restore_own_snapshot()
        else:
            app.send(stream, rows)
        after_step(i)
        seen.append(app.probe(rng.integers(0, N_KEYS + 12, size=20),
                              x=float(np.float32(rng.uniform(20, 100)))))
    return seen


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_a_history_probed_after_every_step_equals_the_host_twin(
        seed, monkeypatch):
    monkeypatch.setattr(DevTableJoinRuntime, "MAX_CHUNK", 8)
    steps = history(seed)
    with App(capacity=None) as host:
        want = replay(host, steps, seed)
        want_rows = host.table_rows()
    with App(capacity=37) as dev:
        assert isinstance(dev.table, DeviceTable)
        slot_of_5, compactions = [], []

        def note(i):
            if i in (0, 2):     # inserted; deleted and inserted again
                slot_of_5.append(dev.table._pk_map[5])
            compactions.append(dev.table.compactions)

        got = replay(dev, steps, seed, note)
        assert slot_of_5[0] != slot_of_5[1]     # it landed in another slot
        # an insert that would have overflowed compacted the tombstones
        # and took their slots (a restore compacts at its barrier)
        assert any(b > a and steps[i + 1][0] != "restore" for i, (a, b)
                   in enumerate(zip(compactions, compactions[1:])))
        for i, (g, w) in enumerate(zip(got, want)):
            assert g == w, (i, steps[i])
        assert sum(len(g) for g in got) > 100
        assert dev.table_rows() == want_rows
        t = dev.table
        assert not t.demoted and t.demotions == 0
        assert t.index_rebuilds >= 3
        assert dev.rt.lowering()["j"] == "devtable"
        assert dev.join.host_fallback_batches == 0
        # three chunks a probe batch of 20 under the patched bound
        assert dev.join.ingest_stats.device_chunks == 3 * len(steps)
        assert dev.join.slot_hits + dev.join.slot_misses == 20 * len(steps)
        assert dev.join.slot_misses > 0


# -- (b) pad lanes and -1 slots touch nothing ----------------------------------


def column(table, nm):
    return np.asarray(table.device_state()[0][nm])


def test_a_scatter_of_three_rows_padded_to_eight_leaves_the_last_slot():
    with App(capacity=5) as app:
        app.send("Ins", [(k, float(k - 10), True)
                         for k in (10, 11, 12, 13, 14)])
        t = app.table
        assert t._pk_map[14] == 4               # the last slot
        steps = t.scatter_steps
        app.send("Upd", [(10, 70.0), (11, 71.0), (12, 72.0)])
        assert t.scatter_steps == steps + 1
        assert list(column(t, "k")) == [10, 11, 12, 13, 14]
        assert list(column(t, "v")) == [70.0, 71.0, 72.0, 3.0, 4.0]
        assert np.asarray(t.device_state()[1]).all()
        # a delete's write lanes are all pad, its kill lanes 1 of 8
        app.send("Del", [(11,)])
        assert list(np.asarray(t.device_state()[1])) == [
            True, False, True, True, True]
        assert list(column(t, "v")) == [70.0, 71.0, 72.0, 3.0, 4.0]
        assert (t._pad_slots(np.arange(3), 8) >= [0, 1, 2, 5, 5, 5, 5, 5]
                ).all()


def test_a_minus_one_slot_and_a_pad_lane_never_emit():
    with App(capacity=5) as app:
        app.send("Ins", [(k, 1.0, True) for k in (10, 11, 12, 13, 14)])
        tcols, valid = app.table.device_state()
        B = 16
        keys = np.full(B, 14, np.int32)         # the last slot's key
        other = np.full(B, 13, np.int32)

        def hits(slots, mask, keys=keys):
            lanes = {"S.k": keys, "S.x": np.full(B, 50.0, np.float32)}
            m, _g, count = app.join._probe(
                keys, np.asarray(slots, np.int32), np.asarray(mask, bool),
                lanes, tcols["k"], tcols, valid)
            assert int(count) == int(np.asarray(m).sum())
            return list(np.flatnonzero(np.asarray(m)))

        live = [True] * 3 + [False] * 13
        assert hits([4] * B, live) == [0, 1, 2]     # pad lanes: masked out
        assert hits([-1] * B, [True] * B) == []     # -1 is not the last slot
        # the guard: a wrong slot (live, another key's) reads as a miss,
        # one past the capacity too (clipped onto the last slot)
        assert hits([3, 4, 5] + [99] * 13, live, other) == [0]
        # end to end: a key the table never held, pad lanes beside it
        assert app.probe([14, 99, 14]) == [
            (app.ts - 3, 14, 50.0, 1.0, True), (app.ts - 1, 14, 50.0, 1.0, True)]
        assert (app.join.slot_hits, app.join.slot_misses) == (2, 1)


# -- (c) duplicates in one batch -------------------------------------------------


def test_the_last_writer_of_a_slot_wins_and_a_kill_wins_over_a_write():
    with App(capacity=6) as app:
        app.send("Ups", [(1, 1.0, False), (2, 2.0, False), (1, 3.0, True),
                         (1, 4.0, False), (2, 5.0, True)])
        assert app.table_rows() == [(1, 4.0, False), (2, 5.0, True)]
        assert app.table.scatter_steps == 2     # the inserts, the updates
        app.send("Upd", [(2, 6.0), (1, 7.0), (2, 8.0), (2, 9.0)])
        assert app.table_rows() == [(1, 7.0, False), (2, 9.0, True)]
        t = app.table
        s1, s2 = t._pk_map[1], t._pk_map[2]
        with t._lock:
            t._apply_scatter(
                [s1, s2, s1], {"v": np.asarray([10, 11, 12], np.float32)},
                [s2])
        assert column(t, "v")[s1] == 12.0
        assert list(np.asarray(t.device_state()[1])[[s1, s2]]) == [True, False]


# -- (d) no operand with a batch and a capacity dimension -------------------------

B, N = 48, 24       # lanes no capacity below is a multiple of


def lowered_programs(capacity):
    with App(capacity=capacity) as app:
        t, j = app.table, app.join
        tcols, valid = t.device_state()
        lanes = {"S.k": np.zeros(B, np.int32), "S.x": np.zeros(B, np.float32)}
        probe = j._probe.lower(
            np.zeros(B, np.int32), np.zeros(B, np.int32), np.ones(B, bool),
            lanes, tcols["k"], tcols, valid).as_text()
        vals = {"v": np.zeros(N, np.float32), "f": np.zeros(N, bool)}
        scatter = t._scatter.lower(
            tcols, valid, vals, t._pad_slots(np.arange(3), N),
            t._pad_slots(np.arange(0), 8)).as_text()
    return probe, scatter


def operations(text):
    return sorted(re.findall(r"= \"?(stablehlo\.[a-z_]+)", text))


@pytest.mark.parametrize("which", [0, 1], ids=["probe", "scatter"])
def test_the_lowered_work_does_not_grow_with_the_table(which):
    small, large = (lowered_programs(c)[which] for c in (1024, 65536))
    for cap, text in ((1024, small), (65536, large)):
        shapes = {tuple(int(d) for d in dims.split("x") if d)
                  for dims in re.findall(r"tensor<((?:\d+x)+)", text)}
        assert any(cap in s for s in shapes)    # the columns are there
        for s in shapes:
            if cap in s:
                assert all(d in (cap, 1) for d in s), s
    assert operations(small) == operations(large)
    assert len(operations(small)) > 8
    # the same program but for the capacity's own digits
    assert small.replace("1024", "C").replace("1023", "C-1") == \
        large.replace("65536", "C").replace("65535", "C-1")


# -- (e) the index: stale by flag, rebuilt once, counted --------------------------


def unmap(app, path):
    if path == "delete_keys":
        app.send("Del", [(3,)])
    elif path == "delete_slots":
        app.send("DelV", [(3.0,)])
    elif path == "primary_key_rewrite":
        app.send("Ren", [(3, 30)])
    elif path == "restore":
        app.restore_own_snapshot()


@pytest.mark.parametrize("path", ["delete_keys", "delete_slots",
                                  "primary_key_rewrite", "restore"])
def test_the_index_is_stale_after_a_path_that_unmaps_and_rebuilt_once(path):
    with App(capacity=16) as app:
        app.send("Ins", [(k, float(k), True) for k in range(8)])
        t, j = app.table, app.join
        keys = list(range(10))
        assert [r[1] for r in app.probe(keys)] == list(range(8))
        assert (t.index_rebuilds, t._index_stale) == (0, False)
        unmap(app, path)
        assert t._index_stale and t.index_rebuilds == 0
        # keys inserted while it is stale are picked up by the rebuild
        app.send("Ins", [(20, 1.0, True)])
        alive = [r[1] for r in app.probe(keys + [20, 30])]
        assert (t.index_rebuilds, t._index_stale) == (1, False)
        want = {"restore": list(range(8)) + [20],
                "primary_key_rewrite": [0, 1, 2, 4, 5, 6, 7, 20, 30]}.get(
                    path, [0, 1, 2, 4, 5, 6, 7, 20])
        assert alive == want
        app.send("Ins", [(21, 1.0, True)])      # fresh again: goes in
        assert [r[1] for r in app.probe([21, 3 if path != "restore" else 99])
                ] == [21]
        assert t.index_rebuilds == 1
        assert j.slot_hits + j.slot_misses == 10 + 12 + 2
        assert j.slot_misses == 24 - len(alive) - 8 - 1
        stats = app.rt.statistics()
        pre = "io.siddhi.SiddhiApps.slots.Siddhi."
        assert stats[pre + "Tables.T.devtableIndexRebuilds"] == 1
        assert stats[pre + "Queries.j.slotHits"] == j.slot_hits
        assert stats[pre + "Queries.j.slotMisses"] == j.slot_misses
        assert j.ingest_stats.device_chunks == 3


def test_a_demotion_marks_the_index_stale_and_hands_out_no_view():
    with App(capacity=4) as app:
        app.send("Ins", [(k, 1.0, True) for k in range(4)])
        assert len(app.probe(range(4))) == 4
        app.send("Ins", [(k, 1.0, True) for k in range(4, 8)])
        t = app.table
        assert t.demoted and t._index_stale
        assert t.probe_view(np.arange(4, dtype=np.int32)) is None
        assert len(app.probe(range(8))) == 8
        assert app.join.host_fallback_batches == 1
        assert t.index_rebuilds == 0


def test_a_table_demoted_beneath_a_batch_joins_the_rest_on_the_host(
        monkeypatch):
    """Another thread's mutation may demote the table between the
    batch's check and its lookup: the lookup, under the table's lock,
    says so, and the batch's rows come from the host join."""
    with App(capacity=4) as app:
        app.send("Ins", [(k, 1.0, True) for k in range(4)])
        view = app.table.probe_view

        def demote_first(keys):
            app.table._demote("test: beneath a batch")
            return view(keys)

        monkeypatch.setattr(app.table, "probe_view", demote_first)
        assert [r[1] for r in app.probe([3, 9, 0])] == [3, 0]
        assert app.join.host_fallback_batches == 1
        assert app.rt.lowering()["j"] == "host"


# -- (f) a probe in flight keeps its revision --------------------------------------


def test_a_probe_dispatched_before_a_scatter_reads_its_own_revision():
    with App(capacity=16) as app:
        app.send("Ins", [(k, float(k), False) for k in range(8)])
        t, j = app.table, app.join
        keys = np.arange(16, dtype=np.int32)
        slots, tcols, valid = t.probe_view(keys[:8])
        slane = np.full(16, -1, np.int32)
        slane[:8] = slots
        lanes = {"S.k": keys, "S.x": np.full(16, 50.0, np.float32)}
        mask = np.arange(16) < 8
        m, g, count = j._probe(keys, slane, mask, lanes, tcols["k"], tcols,
                               valid)
        # the table moves on: every row rewritten, one deleted
        app.send("Upd", [(k, 99.0) for k in range(8)])
        app.send("Del", [(0,)])
        assert int(count) == 8 and list(np.flatnonzero(np.asarray(m))) == \
            list(range(8))
        assert list(np.asarray(g["v"])[:8]) == [float(k) for k in range(8)]
        # the arrays it read are whole and unchanged: nothing was donated
        for nm, col in tcols.items():
            assert not col.is_deleted(), nm
        assert not valid.is_deleted()
        assert list(np.asarray(tcols["v"])[:8]) == [float(k) for k in range(8)]
        assert np.asarray(valid)[:8].all()
        new_cols, new_valid = t.device_state()
        assert new_cols["v"] is not tcols["v"] and new_valid is not valid
        assert list(np.asarray(new_cols["v"])[:8]) == [99.0] * 8
        # the same view, probed again after the mutations, still answers
        # as of its own revision; a new view sees them
        m2, _g2, _c2 = j._probe(keys, slane, mask, lanes, tcols["k"], tcols,
                                valid)
        assert np.asarray(m2).sum() == 8
        assert [r[1] for r in app.probe(range(8), x=100.0)] == list(range(1, 8))


# -- (g) lookups beside mutations on other threads ---------------------------------


def test_lookups_beside_mutating_threads_never_lose_a_key():
    """The index is shared by the probing thread and the mutating ones
    (an ``@async`` junction's workers, an on-demand query) under the
    table's lock alone: while other threads insert and delete around
    them, sixteen keys nobody touches resolve to their slots in every
    view, and the index ends equal to ``_pk_map``."""
    import sys
    import threading
    import time

    with App(capacity=64) as app:
        app.send("Ins", [(k, float(k), True) for k in range(16)])
        t = app.table
        stable = np.arange(16, dtype=np.int32)
        want = np.asarray([t._pk_map[k] for k in range(16)], np.int32)
        stop = time.monotonic() + 1.0
        wrong = []

        def batch(keys):
            n = len(keys)
            cols = {"k": np.asarray(keys, np.int32),
                    "v": np.zeros(n, np.float32), "f": np.zeros(n, bool)}
            return EventBatch("Ins", ["k", "v", "f"], cols,
                              np.zeros(n, np.int64))

        def mutate(base):
            keys = list(range(base, base + 10))
            while time.monotonic() < stop:
                t.insert(batch(keys))
                t.delete_keys(np.asarray(keys[::2], np.int32))
                t.delete_keys(np.asarray(keys[1::2], np.int32))

        def look():
            while time.monotonic() < stop:
                slots, _cols, _valid = t.probe_view(stable)
                if not (slots == want).all():
                    wrong.append(slots)

        threads = [threading.Thread(target=mutate, args=(100 + 10 * i,))
                   for i in range(3)] + [threading.Thread(target=look)
                                         for _ in range(3)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=30)
        finally:
            sys.setswitchinterval(interval)
        assert not any(th.is_alive() for th in threads)
        assert not wrong
        assert t.index_rebuilds > 0 and not t.demoted
        every = np.arange(140, dtype=np.int32)
        slots = t.probe_view(every)[0]
        assert list(slots) == [t._pk_map.get(int(k), -1) for k in every]
