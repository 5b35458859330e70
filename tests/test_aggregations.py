"""Incremental aggregation conformance tests.

Modeled on the reference aggregation test corpus
(modules/siddhi-core/src/test/java/io/siddhi/core/aggregation/
AggregationTestCase): define aggregation every sec...year, events in with
explicit timestamps, per-duration buckets asserted via joins / find.
"""

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import events_from_batch


@pytest.fixture
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


BASE = 1_496_289_720_000  # 2017-06-01 04:02:00 UTC


def test_aggregation_sum_avg_per_seconds(manager):
    app = (
        "define stream Stock (symbol string, price double, volume long, ts long); "
        "define aggregation StockAgg "
        "from Stock select symbol, sum(price) as total, avg(price) as avgPrice, "
        "count() as n group by symbol "
        "aggregate by ts every sec, min, hour;"
    )
    rt = manager.create_siddhi_app_runtime(app)
    rt.start()
    h = rt.get_input_handler("Stock")
    # two events in second 0, one in second 1 — same symbol
    h.send(["WSO2", 50.0, 10, BASE])
    h.send(["WSO2", 70.0, 20, BASE + 500])
    h.send(["WSO2", 60.0, 30, BASE + 1000])

    agg = rt.aggregations["StockAgg"]
    b = agg.find("seconds")
    rows = {
        int(b.columns["AGG_TIMESTAMP"][i]): (
            b.columns["symbol"][i],
            float(b.columns["total"][i]),
            float(b.columns["avgPrice"][i]),
            int(b.columns["n"][i]),
        )
        for i in range(len(b))
    }
    assert rows[BASE] == ("WSO2", 120.0, 60.0, 2)
    assert rows[BASE + 1000] == ("WSO2", 60.0, 60.0, 1)


def test_aggregation_rollup_minutes(manager):
    app = (
        "define stream Stock (symbol string, price double, ts long); "
        "define aggregation A "
        "from Stock select symbol, sum(price) as total, min(price) as lo, "
        "max(price) as hi group by symbol "
        "aggregate by ts every sec, min;"
    )
    rt = manager.create_siddhi_app_runtime(app)
    rt.start()
    h = rt.get_input_handler("Stock")
    # spread across two minutes
    h.send(["IBM", 10.0, BASE])
    h.send(["IBM", 30.0, BASE + 30_000])
    h.send(["IBM", 20.0, BASE + 60_000])

    agg = rt.aggregations["A"]
    b = agg.find("minutes")
    rows = {
        int(b.columns["AGG_TIMESTAMP"][i]): (
            float(b.columns["total"][i]),
            float(b.columns["lo"][i]),
            float(b.columns["hi"][i]),
        )
        for i in range(len(b))
    }
    minute0 = BASE - BASE % 60_000
    assert rows[minute0] == (40.0, 10.0, 30.0)
    assert rows[minute0 + 60_000] == (20.0, 20.0, 20.0)


def test_aggregation_group_isolation(manager):
    app = (
        "define stream S (symbol string, price double, ts long); "
        "define aggregation A from S "
        "select symbol, sum(price) as total group by symbol "
        "aggregate by ts every sec;"
    )
    rt = manager.create_siddhi_app_runtime(app)
    rt.start()
    h = rt.get_input_handler("S")
    h.send(["A", 1.0, BASE])
    h.send(["B", 2.0, BASE])
    h.send(["A", 3.0, BASE])
    b = rt.aggregations["A"].find("seconds")
    got = sorted(
        (b.columns["symbol"][i], float(b.columns["total"][i])) for i in range(len(b))
    )
    assert got == [("A", 4.0), ("B", 2.0)]


def test_aggregation_join_within_per(manager):
    app = (
        "define stream Stock (symbol string, price double, ts long); "
        "define stream Probe (symbol string, startT long, endT long); "
        "define aggregation A from Stock "
        "select symbol, sum(price) as total group by symbol "
        "aggregate by ts every sec, min; "
        "@info(name='q') "
        "from Probe as p join A as a "
        "on p.symbol == a.symbol "
        "within p.startT, p.endT "
        "per 'seconds' "
        "select a.AGG_TIMESTAMP as bucket, a.symbol as symbol, a.total as total "
        "insert into Out;"
    )
    rt = manager.create_siddhi_app_runtime(app)
    rt.start()
    outs = []
    rt.add_callback("q", lambda ts, ins, rem: outs.extend(ins or []))
    sh = rt.get_input_handler("Stock")
    sh.send(["WSO2", 50.0, BASE])
    sh.send(["WSO2", 70.0, BASE + 500])
    sh.send(["IBM", 10.0, BASE])
    sh.send(["WSO2", 60.0, BASE + 1000])
    rt.get_input_handler("Probe").send(["WSO2", BASE, BASE + 1000])
    assert len(outs) == 1
    assert outs[0].data == [BASE, "WSO2", 120.0]


def test_aggregation_out_of_order_event(manager):
    app = (
        "define stream S (v double, ts long); "
        "define aggregation A from S select sum(v) as total "
        "aggregate by ts every sec, min;"
    )
    rt = manager.create_siddhi_app_runtime(app)
    rt.start()
    h = rt.get_input_handler("S")
    h.send([1.0, BASE])
    h.send([2.0, BASE + 5000])  # watermark passes BASE's second bucket
    h.send([4.0, BASE + 100])  # late: belongs to the BASE bucket
    b = rt.aggregations["A"].find("seconds")
    rows = {int(b.columns["AGG_TIMESTAMP"][i]): float(b.columns["total"][i]) for i in range(len(b))}
    assert rows[BASE] == 5.0
    assert rows[BASE + 5000] == 2.0


def test_aggregation_months_buckets(manager):
    app = (
        "define stream S (v double, ts long); "
        "define aggregation A from S select sum(v) as total "
        "aggregate by ts every day, month;"
    )
    rt = manager.create_siddhi_app_runtime(app)
    rt.start()
    h = rt.get_input_handler("S")
    jun1 = 1_496_275_200_000  # 2017-06-01 00:00:00 UTC
    jul1 = 1_498_867_200_000  # 2017-07-01 00:00:00 UTC
    h.send([1.0, jun1 + 1000])
    h.send([2.0, jun1 + 86_400_000])
    h.send([10.0, jul1 + 5])
    b = rt.aggregations["A"].find("months")
    rows = {int(b.columns["AGG_TIMESTAMP"][i]): float(b.columns["total"][i]) for i in range(len(b))}
    assert rows[jun1] == 3.0
    assert rows[jul1] == 10.0


class TestAggregationPurge:
    APP = (
        "@app:playback "
        "define stream S (sym string, v long); "
        "@purge(enable='true', interval='1 sec', "
        "@retentionPeriod(sec='120 sec', min='1 day')) "
        "define aggregation Agg from S select sym, sum(v) as total "
        "group by sym aggregate every sec...min;"
    )

    def test_purges_old_finished_buckets(self, manager):
        rt = manager.create_siddhi_app_runtime(self.APP)
        rt.start()
        h = rt.get_input_handler("S")
        h.send(["A", 1], timestamp=1_000)
        # jump 10 minutes: second-buckets older than 120s purge on the
        # next batch; minute retention (1 day) keeps the rollup
        h.send(["A", 2], timestamp=600_000)
        agg = rt.aggregations["Agg"]
        sec_finished = agg.stores["seconds"].finished
        # the early second-bucket was purged; later state remains
        assert all(k[0] >= 600_000 - 120_000 for k in sec_finished), sec_finished
        assert len(agg.stores["minutes"].finished) >= 1
        # the minute rollup still answers historical queries incl. the
        # purged range's value
        events = rt.query(
            "from Agg within 0L, 999999999L per 'minutes' select sym, total")
        assert any(e.data[0] == "A" and e.data[1] == 1 for e in events), [
            e.data for e in events]
        rt.shutdown()

    def test_invalid_retention_below_minimum(self, manager):
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError

        import pytest as _pytest
        with _pytest.raises(SiddhiAppCreationError, match="retention"):
            manager.create_siddhi_app_runtime(
                "define stream S (v long); "
                "@purge(enable='true', @retentionPeriod(sec='10 sec')) "
                "define aggregation A from S select sum(v) as t "
                "aggregate every sec...min;"
            )

    def test_purge_disabled_retains(self, manager):
        rt = manager.create_siddhi_app_runtime(
            "@app:playback define stream S (v long); "
            "@purge(enable='false') "
            "define aggregation A from S select sum(v) as t aggregate every sec...min;"
        )
        rt.start()
        h = rt.get_input_handler("S")
        h.send([1], timestamp=1_000)
        h.send([2], timestamp=100_000_000)
        agg = rt.aggregations["A"]
        assert len(agg.stores["seconds"].finished) >= 1
        rt.shutdown()


class TestStdDevDeviceBank:
    """stdDev decomposes to sum + sumsq + count; in tpu mode ALL three
    base fields must ride the device bucket bank (the sumsq row is a
    DOUBLE "sum"-op field, and the shared count denominator banks for
    stdDev exactly as it does for avg), so stdDev-bearing ingest skips
    the host reduction entirely."""

    APP = (
        "{mode}@app:playback "
        "define stream S (sym string, price double, ts long); "
        "define aggregation A from S select sym, stdDev(price) as sd "
        "group by sym aggregate by ts every sec...min;"
    )

    def _run(self, manager, mode, probe=False):
        import numpy as np

        rt = manager.create_siddhi_app_runtime(self.APP.format(mode=mode))
        rt.start()
        agg = rt.aggregations["A"]
        if probe:
            assert agg._bank is not None
            assert set(agg._bank.names) == {f.name for f in agg.base_fields}
        rng = np.random.default_rng(7)
        n = 400
        ts = np.sort(BASE + rng.integers(0, 5_000, n)).astype(np.int64)
        for i in range(0, n, 50):
            for j in range(i, min(i + 50, n)):
                h = rt.get_input_handler("S")
                h.send([f"s{int(rng.integers(0, 8))}",
                        float(rng.uniform(1, 100)), int(ts[j])])
        out = rt.query(
            f"from A within {BASE - 1000}, {BASE + 100_000} per 'seconds' "
            "select sym, sd;")
        rt.shutdown()
        return sorted([list(e.data) for e in out], key=lambda r: r[0])

    def test_stddev_banks_count_and_matches_host(self, manager):
        host = self._run(manager, "")
        m2 = SiddhiManager()
        try:
            dev = self._run(m2, "@app:execution('tpu') ", probe=True)
        finally:
            m2.shutdown()
        assert len(host) == len(dev) > 0
        for a, b in zip(host, dev):
            assert a[0] == b[0]
            # float32 device lanes + sum/sumsq decomposition tolerance
            assert b[1] == pytest.approx(a[1], abs=5e-3, rel=1e-3), (a, b)


class TestIntMinMaxDeviceBank:
    """min/max over an INT argument ride the device bucket bank as
    single int32 rows at native width (INT is exactly int32, identities
    the int32 extrema) — exact, no pair split; a count in the same
    select banks as a float32 add row, so this ingest shape performs no
    host reduction at all."""

    APP = (
        "{mode}@app:playback "
        "define stream S (sym string, v int, ts long); "
        "define aggregation A from S select sym, min(v) as lo, "
        "max(v) as hi, count() as n group by sym "
        "aggregate by ts every sec...min;"
    )

    def _run(self, manager, mode, vals, probe=False):
        import numpy as np

        rt = manager.create_siddhi_app_runtime(self.APP.format(mode=mode))
        rt.start()
        agg = rt.aggregations["A"]
        if probe:
            assert agg._bank is not None
            # every base field banks: INT extrema + the bare count
            assert set(agg._bank.names) == {f.name for f in agg.base_fields}
            assert any(kind == "i32" for _op, kind in agg._bank._lanes)
        rng = np.random.default_rng(17)
        n = len(vals)
        ts = np.sort(BASE + rng.integers(0, 5_000, n)).astype(np.int64)
        h = rt.get_input_handler("S")
        for j in range(n):
            h.send([f"s{int(rng.integers(0, 6))}", int(vals[j]), int(ts[j])])
        if probe:
            # the bank must actually absorb the batches on device
            assert agg._bank.scatters > 0
        out = rt.query(
            f"from A within {BASE - 1000}, {BASE + 100_000} per 'seconds' "
            "select sym, lo, hi, n;")
        rt.shutdown()
        return sorted([list(e.data) for e in out], key=lambda r: r[0])

    def _diff(self, manager, vals):
        host = self._run(manager, "", vals)
        m2 = SiddhiManager()
        try:
            dev = self._run(m2, "@app:execution('tpu') ", vals, probe=True)
        finally:
            m2.shutdown()
        assert len(host) == len(dev) > 0
        # int32 rows and the count barrier are exact — no tolerance
        assert host == dev, (host[:4], dev[:4])

    def test_int_min_max_exact_on_bank_path(self, manager):
        import numpy as np

        rng = np.random.default_rng(19)
        self._diff(manager, rng.integers(-100_000, 100_000, 500))

    def test_int_extrema_at_type_bounds_exact(self, manager):
        import numpy as np

        # values spanning the full int32 range hit the identity edges
        rng = np.random.default_rng(23)
        vals = rng.integers(-(2**31), 2**31 - 1, 300)
        vals[0], vals[1] = -(2**31), 2**31 - 1
        self._diff(manager, vals)


class TestCountOnlyDeviceBank:
    """A count-only select (no avg/stdDev rewrite) banks its bare count
    as a float32 add row under the 2**24 overflow barrier — previously
    it forced the host reduction every batch."""

    APP = (
        "{mode}@app:playback "
        "define stream S (sym string, v int, ts long); "
        "define aggregation A from S select sym, count() as n "
        "group by sym aggregate by ts every sec...min;"
    )

    def _run(self, manager, mode, probe=False):
        import numpy as np

        rt = manager.create_siddhi_app_runtime(self.APP.format(mode=mode))
        rt.start()
        agg = rt.aggregations["A"]
        if probe:
            assert agg._bank is not None
            assert [f.op for f in agg._bank.fields] == ["count"]
        rng = np.random.default_rng(29)
        n = 400
        ts = np.sort(BASE + rng.integers(0, 5_000, n)).astype(np.int64)
        h = rt.get_input_handler("S")
        for j in range(n):
            h.send([f"s{int(rng.integers(0, 8))}",
                    int(rng.integers(-100, 100)), int(ts[j])])
        if probe:
            assert agg._bank.scatters > 0
        out = rt.query(
            f"from A within {BASE - 1000}, {BASE + 100_000} per 'seconds' "
            "select sym, n;")
        rt.shutdown()
        return sorted([list(e.data) for e in out], key=lambda r: r[0])

    def test_count_only_banks_and_matches_host(self, manager):
        host = self._run(manager, "")
        m2 = SiddhiManager()
        try:
            dev = self._run(m2, "@app:execution('tpu') ", probe=True)
        finally:
            m2.shutdown()
        assert len(host) == len(dev) > 0
        assert host == dev, (host[:4], dev[:4])


class TestLongSumDeviceBank:
    """sum(intcol) widens INT→LONG; in tpu mode LONG sums ride the
    device bucket bank as hi/lo int32 pair rows (hi += v >> 16,
    lo += v & 0xFFFF, flush merge hi * 65536 + lo) — EXACTLY, unlike
    the float32 lanes.  An avg over the same int argument shares the
    banked _SUM numerator and banks its count denominator too."""

    APP = (
        "{mode}@app:playback "
        "define stream S (sym string, v int, ts long); "
        "define aggregation A from S select sym, sum(v) as total, "
        "avg(v) as mean group by sym aggregate by ts every sec...min;"
    )

    def _run(self, manager, mode, vals, probe=False):
        import numpy as np

        rt = manager.create_siddhi_app_runtime(self.APP.format(mode=mode))
        rt.start()
        agg = rt.aggregations["A"]
        if probe:
            assert agg._bank is not None
            # the LONG _SUM field owns a pair lane; count banks with it
            assert agg._bank.long_names, agg._bank.names
            assert set(agg._bank.names) == {f.name for f in agg.base_fields}
        rng = np.random.default_rng(11)
        n = len(vals)
        ts = np.sort(BASE + rng.integers(0, 5_000, n)).astype(np.int64)
        h = rt.get_input_handler("S")
        for j in range(n):
            h.send([f"s{int(rng.integers(0, 6))}", int(vals[j]), int(ts[j])])
        out = rt.query(
            f"from A within {BASE - 1000}, {BASE + 100_000} per 'seconds' "
            "select sym, total, mean;")
        rt.shutdown()
        return sorted([list(e.data) for e in out], key=lambda r: r[0])

    def _diff(self, manager, vals):
        host = self._run(manager, "", vals)
        m2 = SiddhiManager()
        try:
            dev = self._run(m2, "@app:execution('tpu') ", vals, probe=True)
        finally:
            m2.shutdown()
        assert len(host) == len(dev) > 0
        for a, b in zip(host, dev):
            assert a[0] == b[0], (a, b)
            # hi/lo int32 pair rows are exact — no tolerance
            assert a[1] == b[1], ("LONG sum must be exact", a, b)
            assert b[2] == pytest.approx(a[2], rel=1e-6), (a, b)

    def test_long_sum_exact_on_bank_path(self, manager):
        import numpy as np

        rng = np.random.default_rng(3)
        self._diff(manager, rng.integers(-100_000, 100_000, 500))

    def test_long_sum_negative_heavy_exact(self, manager):
        import numpy as np

        # all-negative sums exercise the signed two's-complement split
        # (hi goes negative while lo stays in [0, 65535])
        rng = np.random.default_rng(5)
        self._diff(manager, rng.integers(-(2**31), -1, 300))

    def test_overflow_risk_forces_flush_or_host_path(self):
        from siddhi_tpu.aggregation.device_bank import DeviceBucketBank
        from siddhi_tpu.aggregation.runtime import BaseField
        from siddhi_tpu.query_api import AttrType
        import numpy as np

        f = BaseField("_SUM0", "sum", None, AttrType.LONG)
        bank = DeviceBucketBank([f], cap=8)
        v = np.asarray([2**40, -(2**40)], dtype=np.int64)
        assert not bank.long_overflow_risk({"_SUM0": v}, 2)
        # a batch whose per-event hi magnitude alone nears int32 must
        # report risk even on an empty bank (host-path fallback)
        hot = np.asarray([2**50], dtype=np.int64)
        assert bank.long_overflow_risk({"_SUM0": hot}, 1)
        # accumulated moderate batches eventually trip the barrier too
        bank.rows[(0, ())] = 0
        bank.scatter(np.zeros(2, dtype=np.int32), {"_SUM0": v})
        assert bank._long_hi_used["_SUM0"] > 0
        bank._long_hi_used["_SUM0"] = (1 << 31) - 10
        assert bank.long_overflow_risk({"_SUM0": v}, 2)
        bank.clear()
        assert not bank.long_overflow_risk({"_SUM0": v}, 2)


class TestBankScatter:
    """The bank's scatter (``.at[rows].add/min/max`` in one jitted
    program) against numpy's unbuffered ufuncs on the same events."""

    @pytest.mark.parametrize("op", ["sum", "min", "max"])
    def test_matches_numpy_reference_int32(self, op):
        from siddhi_tpu.aggregation.device_bank import DeviceBucketBank
        from siddhi_tpu.aggregation.runtime import BaseField
        from siddhi_tpu.query_api import AttrType
        import numpy as np

        rng = np.random.default_rng(11)
        # 700 events pad to 1,024 lanes: the padding targets the dump
        # row and leaves the 40 assigned rows alone
        n, r = 700, 40
        rows = rng.integers(0, r, n).astype(np.int32)
        vals = rng.integers(-1000, 1000, n).astype(np.int32)
        # a sum rides the exact hi/lo pair, an extremum one int32 lane
        ftype = AttrType.LONG if op == "sum" else AttrType.INT
        bank = DeviceBucketBank([BaseField("_F0", op, None, ftype)], cap=256)
        keys = [(0, (i,)) for i in range(r)]
        assert bank.assign(keys)
        bank.scatter(
            np.asarray([bank.rows[keys[i]] for i in rows], dtype=np.int32),
            {"_F0": vals})
        ident = {"sum": 0, "min": np.iinfo(np.int32).max,
                 "max": np.iinfo(np.int32).min}[op]
        want = np.full(r, ident, dtype=np.int64)
        getattr(np, {"sum": "add", "min": "minimum", "max": "maximum"}[op]
                ).at(want, rows, vals)
        got = bank.flush()
        assert [got[k]["_F0"] for k in keys] == want.tolist()

    def test_collision_stress_all_events_one_key(self):
        """The scatter's worst case: every event on ONE row."""
        from siddhi_tpu.aggregation.device_bank import DeviceBucketBank
        from siddhi_tpu.aggregation.runtime import BaseField
        from siddhi_tpu.query_api import AttrType
        import numpy as np

        fields = [
            BaseField("_SUM0", "sum", None, AttrType.LONG),
            BaseField("_MIN1", "min", None, AttrType.LONG),
            BaseField("_MAX2", "max", None, AttrType.LONG),
            BaseField("_SUM3", "sum", None, AttrType.DOUBLE),
        ]
        rng = np.random.default_rng(13)
        n = 2048
        fvals = {
            # sums ride the 16-bit hi/lo split: keep 2048 summands small
            # enough that the int32 hi lane cannot overflow
            "_SUM0": rng.integers(-(2**20), 2**20, n),
            "_MIN1": rng.integers(-(2**60), 2**60, n),
            "_MAX2": rng.integers(-(2**60), 2**60, n),
            # integer-valued floats: f32 sum reassociation cannot bite
            "_SUM3": rng.integers(0, 100, n).astype(np.float64),
        }
        bank = DeviceBucketBank(fields, cap=8)
        assert bank.assign([(0, ())])
        bank.scatter(np.full(n, bank.rows[(0, ())], dtype=np.int32), fvals)
        out = bank.flush()[(0, ())]
        assert out["_SUM0"] == int(fvals["_SUM0"].sum())
        assert out["_MIN1"] == int(fvals["_MIN1"].min())
        assert out["_MAX2"] == int(fvals["_MAX2"].max())
        assert out["_SUM3"] == float(fvals["_SUM3"].sum())


class TestLongExtremaDeviceBank:
    """LONG min/max ride the bank as lexicographic hi/lo int32 pairs —
    the signed 64-bit compare must be exact at full width."""

    def test_unit_differential_negative_heavy(self):
        from siddhi_tpu.aggregation.device_bank import DeviceBucketBank
        from siddhi_tpu.aggregation.runtime import BaseField
        from siddhi_tpu.query_api import AttrType
        import numpy as np

        fields = [BaseField("_MIN0", "min", None, AttrType.LONG),
                  BaseField("_MAX1", "max", None, AttrType.LONG)]
        bank = DeviceBucketBank(fields, cap=16)
        rng = np.random.default_rng(17)
        keys = [(0, ("a",)), (0, ("b",)), (1, ("a",))]
        assert bank.assign(keys)
        ref = {k: [None, None] for k in keys}
        for _batch in range(3):
            n = 200
            ks = rng.integers(0, len(keys), n)
            # negative-heavy incl. values whose hi word ties but lo
            # differs (the lexicographic second pass must decide)
            v = rng.integers(-(2**62), 2**20, n)
            v[::7] = -(2**62) + rng.integers(0, 3, len(v[::7]))
            rows = np.asarray([bank.rows[keys[k]] for k in ks],
                              dtype=np.int32)
            bank.scatter(rows, {"_MIN0": v, "_MAX1": v.copy()})
            for k, x in zip(ks, v):
                cur = ref[keys[k]]
                cur[0] = int(x) if cur[0] is None else min(cur[0], int(x))
                cur[1] = int(x) if cur[1] is None else max(cur[1], int(x))
        got = bank.flush()
        for k in keys:
            assert got[k]["_MIN0"] == ref[k][0], (k, got[k], ref[k])
            assert got[k]["_MAX1"] == ref[k][1], (k, got[k], ref[k])

    APP = (
        "{mode}@app:playback "
        "define stream S (sym string, v long, ts long); "
        "define aggregation A from S select sym, min(v) as lo, "
        "max(v) as hi group by sym aggregate by ts every sec...min;"
    )

    def _run(self, manager, mode, vals, probe=False):
        import numpy as np

        rt = manager.create_siddhi_app_runtime(self.APP.format(mode=mode))
        rt.start()
        agg = rt.aggregations["A"]
        rng = np.random.default_rng(11)
        n = len(vals)
        ts = np.sort(BASE + rng.integers(0, 5_000, n)).astype(np.int64)
        h = rt.get_input_handler("S")
        for j in range(n):
            h.send([f"s{int(rng.integers(0, 6))}", int(vals[j]), int(ts[j])])
        if probe:
            assert agg._bank is not None, "LONG extrema did not bank"
            assert agg._bank.scatters > 0
            # extrema pairs are excluded from the sum-overflow guard
            assert not agg._bank.long_names
        out = rt.query(
            f"from A within {BASE - 1000}, {BASE + 100_000} per 'seconds' "
            "select sym, lo, hi;")
        rt.shutdown()
        return sorted([list(e.data) for e in out], key=lambda r: r[0])

    @pytest.mark.parametrize("seed,low,high", [
        (3, -(2**40), 2**40),
        # every value negative, down to -(2**62)
        (5, -(2**62), -1),
    ], ids=["mixed", "negative_heavy"])
    def test_app_level_exact_vs_host(self, manager, seed, low, high):
        import numpy as np

        vals = np.random.default_rng(seed).integers(low, high, 300)
        host = self._run(manager, "", vals)
        m2 = SiddhiManager()
        try:
            dev = self._run(m2, "@app:execution('tpu') ", vals, probe=True)
        finally:
            m2.shutdown()
        assert len(host) == len(dev) > 0
        assert host == dev, (host[:3], dev[:3])
