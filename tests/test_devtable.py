"""Device-resident tables: differential + lifecycle suite.

``@app:devtables`` stores eligible tables as device-resident columnar
arrays (``siddhi_tpu/devtable/``): one ``[capacity]`` device column per
attribute plus a validity lane, mutations lowered to jitted indexed
last-writer-wins scatters, and stream-table joins lowered to a
slot-addressed probe that keeps matched pairs device-resident from ingest
to the coalesced emit drain.  The contracts pinned here:

* **Differential exactness** — every mutation shape (insert, delete,
  update, update-or-insert, duplicate keys inside one batch, mutations
  straddling join batches) and the join output are bit-identical to the
  host ``InMemoryTable`` path, event for event.
* **Fault transparency** — transient ``ingest.put`` / ``emit.drain``
  faults retry without losing or duplicating rows; a simulated crash +
  journal replay reproduces the uninterrupted run.
* **MVCC pinning** — ``persist(mode='async')`` captures the revision
  pinned at the barrier even while later mutations land, and
  ``restore_last_revision`` + replay is bit-exact.
* **Graceful degradation** — capacity overflow first compacts
  tombstones in-barrier (counted), then demotes the table to the host
  path with a WARNING and a counted ``devtable_demotions`` stat;
  ineligible tables/queries never lower and are counted, never wrong.
* **TableCache honesty** — the host path the devtable differential
  compares against must itself be correct: a primary-key-rewriting
  update through the callbacks invalidates the DESTINATION key too
  (regression for a stale-cache read in ``table/record.py``).
"""

import contextlib

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import Event
from siddhi_tpu.core.exceptions import SimulatedCrashError
from siddhi_tpu.devtable import DeviceTable
from siddhi_tpu.durability import DurableFileSystemPersistenceStore

pytestmark = pytest.mark.faults


BODY = (
    "define stream S (k int, x float); "
    "define stream Ins (k int, v float, f bool); "
    "define stream Del (k int); "
    "define stream Upd (k int, v float); "
    "define stream Ups (k int, v float, f bool); "
    "@PrimaryKey('k') define table T (k int, v float, f bool); "
    "from Ins insert into T; "
    "from Del delete T on T.k == k; "
    "from Upd update T set T.v = v on T.k == k; "
    "from Ups update or insert into T set T.v = v, T.f = f "
    "on T.k == k; "
    "@info(name='j') from S join T as t on S.k == t.k "
    "select S.k as k, S.x as x, t.v as v, t.f as f insert into Out;"
)


def ops_series(n, seed, n_keys=6):
    """Random interleaved mutation + probe series (stream, row) pairs."""
    rng = np.random.default_rng(seed)
    ops = []
    for _ in range(n):
        k = int(rng.integers(0, n_keys))
        v = float(np.float32(rng.uniform(0, 100)))
        roll = rng.random()
        if roll < 0.25:
            ops.append(("Ins", [k, v, bool(rng.integers(0, 2))]))
        elif roll < 0.40:
            ops.append(("Del", [k]))
        elif roll < 0.55:
            ops.append(("Upd", [k, v]))
        elif roll < 0.75:
            ops.append(("Ups", [k, v, bool(rng.integers(0, 2))]))
        else:
            ops.append(("S", [k, v]))
    return ops


def run(ops, devtables=True, capacity=64, faults=None, header_extra="",
        transfer_guard=False, batches=None):
    """Playback run of the mixed series -> (emitted tuples, sorted table
    rows, lowering map, stats dict).  ``batches``: list of (stream,
    [rows]) groups sent as ONE junction batch each (dup-key coverage)."""
    header = "@app:name('dt') @app:playback @app:execution('tpu') "
    if devtables:
        header += f"@app:devtables(capacity='{capacity}') "
    if faults is not None:
        header += f"@app:faults({faults}) "
    header += header_extra
    guard = contextlib.nullcontext()
    if transfer_guard:
        import jax

        # no-op on the CPU backend (host<->cpu crossings are free), but
        # wires the zero-host-materialization contract for TPU CI — the
        # static twin is the host-sync-hazard rule over devtable/
        guard = jax.transfer_guard("disallow")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + BODY)
        got = []
        rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                      for e in evs))
        rt.start()
        handlers = {s: rt.get_input_handler(s)
                    for s in ("S", "Ins", "Del", "Upd", "Ups")}
        ts = 1000
        with guard:
            if batches is None:
                for stream, row in ops:
                    handlers[stream].send(list(row), timestamp=ts)
                    ts += 10
            else:
                for stream, rows in batches:
                    handlers[stream].send(
                        [Event(ts + i, list(r)) for i, r in enumerate(rows)])
                    ts += 10 * (len(rows) + 1)
            rt.drain_device_emits()
        t = rt.tables["T"]
        b = t.rows_batch()
        rows = sorted(tuple(b.columns[nm][i] for nm in b.attribute_names)
                      for i in range(len(b)))
        lowering = rt.lowering()
        stats = rt.statistics()
        rt.shutdown()
        return got, rows, lowering, stats
    finally:
        m.shutdown()


def host_reference(ops, batches=None):
    """The same series through the host table path (no @app:devtables)."""
    return run(ops, devtables=False, batches=batches)


class TestDevTableDifferential:
    def test_lowering_reports_devtable(self):
        ops = [("Ins", [1, 5.0, True]), ("S", [1, 0.5])]
        got, rows, lowering, stats = run(ops)
        assert lowering["j"] == "devtable"
        assert got == [(1, np.float32(0.5), np.float32(5.0), True)]
        key = [k for k in stats if k.endswith("devtableScatterSteps")]
        assert key and stats[key[0]] >= 1

    @pytest.mark.parametrize("seed", [3, 17, 41])
    def test_mixed_mutations_and_joins_bit_identical(self, seed):
        ops = ops_series(60, seed)
        ref_got, ref_rows, ref_low, _ = host_reference(ops)
        got, rows, lowering, _ = run(ops)
        assert lowering["j"] == "devtable"
        assert ref_low["j"] != "devtable"
        assert got == ref_got, f"seed {seed}: join outputs diverged"
        assert rows == ref_rows, f"seed {seed}: table contents diverged"
        assert any(s == "S" for s, _ in ops) and len(ref_got) > 0, (
            "series too tame; differential is vacuous")

    def test_duplicate_keys_in_one_batch_lww(self):
        # several writers hit the SAME slot inside one scatter: last
        # writer (by arrival order) must win, exactly like the host's
        # sequential application
        batches = [
            ("Ups", [[1, 10.0, True], [1, 11.0, False], [2, 20.0, True],
                     [1, 12.0, True], [2, 21.0, False]]),
            ("S", [[1, 0.5], [2, 0.25]]),
            ("Del", [[1], [1]]),          # double-delete of one key
            ("Ups", [[1, 13.0, False], [3, 30.0, True], [3, 31.0, False]]),
            ("S", [[1, 0.75], [3, 0.125]]),
        ]
        ref_got, ref_rows, _, _ = host_reference([], batches=batches)
        got, rows, lowering, _ = run([], batches=batches)
        assert lowering["j"] == "devtable"
        assert got == ref_got
        assert rows == ref_rows

    def test_batch_straddling_mutations(self):
        # probes interleaved between mutation batches must observe each
        # barrier-pinned revision in order: probe -> update -> probe ->
        # delete -> probe sees three different table states
        ops = [
            ("Ins", [7, 1.0, True]),
            ("S", [7, 0.1]),
            ("Upd", [7, 2.0]),
            ("S", [7, 0.2]),
            ("Del", [7]),
            ("S", [7, 0.3]),
            ("Ups", [7, 3.0, False]),
            ("S", [7, 0.4]),
        ]
        ref_got, ref_rows, _, _ = host_reference(ops)
        got, rows, _, _ = run(ops)
        assert got == ref_got
        assert rows == ref_rows
        assert [np.float32(g[2]) for g in got] == [
            np.float32(1.0), np.float32(2.0), np.float32(3.0)]

    def test_zero_host_materialization_under_transfer_guard(self):
        ops = ops_series(40, seed=23)
        ref_got, ref_rows, _, _ = host_reference(ops)
        got, rows, lowering, _ = run(ops, transfer_guard=True)
        assert lowering["j"] == "devtable"
        assert got == ref_got
        assert rows == ref_rows


class TestDevTableFaults:
    def test_transient_ingest_and_emit_faults_recovered(self):
        ops = ops_series(50, seed=29)
        ref_got, ref_rows, _, _ = host_reference(ops)
        got, rows, lowering, stats = run(
            ops, faults=("transfer.retry.scale='0.0001', "
                         "ingest.put='transient:count=2', "
                         "emit.drain='transient:count=2'"))
        assert lowering["j"] == "devtable"
        assert got == ref_got, "retried transfers must not lose/dup rows"
        assert rows == ref_rows

    def test_crash_and_journal_replay_bit_identical(self, tmp_path):
        ops = ops_series(40, seed=37)
        ref_got, ref_rows, _, _ = host_reference(ops)
        header = ("@app:name('dt') @app:playback @app:execution('tpu') "
                  "@app:devtables(capacity='64') "
                  "@app:faults(journal='256') ")
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(header + BODY)
            got = []
            rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            hs = {s: rt.get_input_handler(s)
                  for s in ("S", "Ins", "Del", "Upd", "Ups")}
            ts = 1000
            for stream, row in ops[:12]:
                hs[stream].send(list(row), timestamp=ts)
                ts += 10
            rt.persist()
            for stream, row in ops[12:25]:
                hs[stream].send(list(row), timestamp=ts)
                ts += 10
            rt.app_context.fault_injector.configure("ingest", "crash",
                                                    count=1)
            with pytest.raises(SimulatedCrashError):
                hs[ops[25][0]].send(list(ops[25][1]), timestamp=ts)
            ts += 10
            rt.shutdown()  # the crashed runtime is gone

            rt2 = m.create_siddhi_app_runtime(header + BODY)
            rt2.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                           for e in evs))
            rt2.start()
            assert rt2.restore_last_revision() is not None
            hs2 = {s: rt2.get_input_handler(s)
                   for s in ("S", "Ins", "Del", "Upd", "Ups")}
            # the crashed send was journaled before the crash fired, so
            # replay already delivered it — continue after it
            for stream, row in ops[26:]:
                hs2[stream].send(list(row), timestamp=ts)
                ts += 10
            rt2.drain_device_emits()
            t = rt2.tables["T"]
            b = t.rows_batch()
            rows = sorted(tuple(b.columns[nm][i]
                                for nm in b.attribute_names)
                          for i in range(len(b)))
            rt2.shutdown()
            assert got == ref_got, "crash+replay diverged"
            assert rows == ref_rows
        finally:
            m.shutdown()


class TestDevTableDurability:
    def test_async_persist_pins_barrier_revision_mid_mutation(
            self, tmp_path):
        """persist(mode='async') while mutations keep landing must
        capture the revision pinned AT the barrier — later scatters make
        new device arrays and cannot retroactively change the capture —
        and restore + journal replay is bit-exact."""
        ops = ops_series(40, seed=43)
        ref_got, ref_rows, _, _ = host_reference(ops)
        header = ("@app:name('dt') @app:playback @app:execution('tpu') "
                  "@app:devtables(capacity='64') "
                  "@app:faults(journal='256') ")
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(header + BODY)
            got = []
            rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            hs = {s: rt.get_input_handler(s)
                  for s in ("S", "Ins", "Del", "Upd", "Ups")}
            ts = 1000
            for stream, row in ops[:15]:
                hs[stream].send(list(row), timestamp=ts)
                ts += 10
            rev = rt.persist(mode="async")
            # keep mutating BEFORE the async write commits: the writer
            # must still persist the barrier-pinned revision
            for stream, row in ops[15:]:
                hs[stream].send(list(row), timestamp=ts)
                ts += 10
            assert rt.wait_for_persist(rev, timeout=30) == "committed"
            rt.shutdown()

            rt2 = m.create_siddhi_app_runtime(header + BODY)
            rt2.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                           for e in evs))
            rt2.start()
            assert rt2.restore_last_revision() == rev
            # journal replay re-delivers ops[15:]; any emissions it
            # produces re-enter `got` — the restored run must converge
            # to the same table state as the uninterrupted reference
            rt2.drain_device_emits()
            t = rt2.tables["T"]
            assert isinstance(t, DeviceTable) and not t.demoted
            b = t.rows_batch()
            rows = sorted(tuple(b.columns[nm][i]
                                for nm in b.attribute_names)
                          for i in range(len(b)))
            rt2.shutdown()
            assert rows == ref_rows, "restored+replayed table diverged"
        finally:
            m.shutdown()


class TestCapacityLifecycle:
    def test_overflow_compacts_then_demotes_counted(self, caplog):
        import logging

        # capacity 4: churn one key (tombstones) -> compaction keeps the
        # table device-resident; then 5 distinct live keys overflow ->
        # demotion with a WARNING + counted stat, results still exact
        ops = []
        for i in range(6):
            ops.append(("Ups", [1, float(i), True]))
            ops.append(("Del", [1]))
        for k in range(5):
            ops.append(("Ins", [k, float(k) * 10.0, False]))
        ops += [("S", [k, 0.5]) for k in range(5)]
        ref_got, ref_rows, _, _ = host_reference(ops)
        with caplog.at_level(logging.WARNING, logger="siddhi_tpu"):
            got, rows, lowering, stats = run(ops, capacity=4)
        assert got == ref_got
        assert rows == ref_rows

        def stat(suffix):
            keys = [k for k in stats if k.endswith(suffix)]
            return stats[keys[0]] if keys else None

        assert stat("devtableCompactions") >= 1
        assert stat("devtableDemotions") == 1
        assert stat("devtableDemoted") is True
        assert any("demot" in r.message.lower() for r in caplog.records), (
            "demotion must be surfaced with a WARNING")

    def test_ineligible_table_stays_host_counted(self):
        # STRING attribute -> no device lane -> the table never lowers;
        # the reason is counted and everything still runs on host
        body = (
            "define stream S (sym string, x float); "
            "define stream Ins (sym string, v float); "
            "@PrimaryKey('sym') define table T (sym string, v float); "
            "from Ins insert into T; "
            "@info(name='j') from S join T as t on S.sym == t.sym "
            "select S.sym as sym, t.v as v insert into Out;")
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:name('dt2') @app:playback @app:execution('tpu') "
                "@app:devtables(capacity='8') " + body)
            got = []
            rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            assert not isinstance(rt.tables["T"], DeviceTable)
            assert rt.lowering()["j"] != "devtable"
            sm = rt.app_context.statistics_manager
            assert sm.devtable_fallback_reasons, (
                "ineligibility must be counted, not silent")
            rt.get_input_handler("Ins").send(["IBM", 9.0], timestamp=1000)
            rt.get_input_handler("S").send(["IBM", 0.5], timestamp=1010)
            rt.shutdown()
            assert got == [("IBM", np.float32(9.0))]
        finally:
            m.shutdown()

    def test_bad_annotation_rejected(self):
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError

        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError):
                m.create_siddhi_app_runtime(
                    "@app:devtables define stream S (k int); "
                    "from S insert into Out;")  # needs @app:execution('tpu')
            with pytest.raises(SiddhiAppCreationError):
                m.create_siddhi_app_runtime(
                    "@app:execution('tpu') @app:devtables(capacity='0') "
                    "define stream S (k int); from S insert into Out;")
        finally:
            m.shutdown()


class TestTableCacheInvalidation:
    """Regression: a primary-key-rewriting update through the callbacks
    must invalidate the DESTINATION key's cache entry too — a stale
    single-row entry under the new key otherwise keeps answering pk
    probes after the store already holds two rows for that key."""

    APP = (
        "define stream Ins (symbol string, price float); "
        "define stream Ren (old string, new string); "
        "define stream Chk (symbol string); "
        "@store(type='memory', @cache(size='10', cache.policy='LRU')) "
        "@PrimaryKey('symbol') "
        "define table T (symbol string, price float); "
        "from Ins insert into T; "
        "from Ren update T set T.symbol = new on T.symbol == old; "
        "@info(name='chk') from Chk join T as t on Chk.symbol == t.symbol "
        "select t.symbol as symbol, t.price as price insert into Out;")

    def test_pk_rewrite_invalidates_destination_key(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:name('cache') @app:playback " + self.APP)
            rt.start()
            rt.get_input_handler("Ins").send(["B", 9.0], timestamp=1000)
            # prime the cache under key 'B'
            assert [e.data for e in rt.query(
                "from T on symbol == 'B' select price")] == [[9.0]]
            rt.get_input_handler("Ins").send(["A", 1.0], timestamp=1010)
            # rewrite A's primary key to 'B': the store now holds two
            # 'B' rows; the cached single-row entry for 'B' is stale
            rt.get_input_handler("Ren").send(["A", "B"], timestamp=1020)
            events = rt.query("from T on symbol == 'B' select price")
            assert sorted(e.data[0] for e in events) == [1.0, 9.0], (
                "stale TableCache entry under the rewritten key")
            rt.shutdown()
        finally:
            m.shutdown()

    def test_update_or_insert_then_probe_sees_fresh_row(self):
        app = (
            "define stream Ups (symbol string, price float); "
            "@store(type='memory', @cache(size='10', cache.policy='LRU')) "
            "@PrimaryKey('symbol') "
            "define table T (symbol string, price float); "
            "from Ups update or insert into T set T.price = price "
            "on T.symbol == symbol;")
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:name('cache2') @app:playback " + app)
            rt.start()
            h = rt.get_input_handler("Ups")
            h.send(["IBM", 1.0], timestamp=1000)
            assert [e.data for e in rt.query(
                "from T on symbol == 'IBM' select price")] == [[1.0]]
            h.send(["IBM", 2.0], timestamp=1010)  # update branch
            assert [e.data for e in rt.query(
                "from T on symbol == 'IBM' select price")] == [[2.0]]
            rt.shutdown()
        finally:
            m.shutdown()
