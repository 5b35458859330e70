"""Tumbling group-by with bare select attributes on the device path.

Upstream's group-by sample (performance-samples
``GroupByWindowSingleQueryPerformance.java:35``) selects the bare
attribute ``timestamp`` beside its aggregates: its value is that of the
group's last row in the pane (the host engine's batch selector,
``core/query.py``).  Every case runs the same app through
``SiddhiManager`` twice — host engine and ``@app:execution('tpu')`` —
and holds the device rows to the host's: values (floats within the
suite's norm), bare attributes and row timestamps exact, order equal.
``lengthBatch`` closes all the panes of a batch in one device program
(``ops/device_query.py`` ``make_pane_step``); ``timeBatch`` and what
that program does not hold keep the per-pane sweep, with per-group
last-row registers.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.device_single import DeviceQueryRuntime
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.util.persistence import InMemoryPersistenceStore

NAMES = ["symbol", "price", "volume", "timestamp", "note", "k"]
DEFINE = ("define stream S (symbol string, price float, volume int, "
          "timestamp long, note string, k int); ")
HOST = "@app:playback "
DEVICE = "@app:playback @app:execution('tpu', partitions='256') "
UPSTREAM = ("@info(name='q0') from S#window.lengthBatch({L}) "
            "select symbol, sum(price) as total, avg(volume) as avgVolume, "
            "timestamp group by symbol insert into Out;")


def ticks(sizes, seed=5, n_symbols=5, step_ms=1):
    """Batches of the upstream stream: LONG ``timestamp`` above 2^24
    (float32 could not hold it), a string and a float beside it."""
    rng = np.random.default_rng(seed)
    out, t = [], 1_000
    for n in sizes:
        ts = t + np.arange(n, dtype=np.int64) * step_ms
        t = int(ts[-1]) + step_ms
        out.append(({
            "symbol": np.asarray([f"S{int(s)}" for s in rng.integers(
                0, n_symbols, n)], dtype=object),
            "price": rng.uniform(100.0, 1000.0, n).astype(np.float32),
            "volume": rng.integers(0, 300, n).astype(np.int32),
            "timestamp": ts + (1 << 40) + 17,
            "note": np.asarray([f"n{int(x)}" for x in rng.integers(
                0, 1000, n)], dtype=object),
            "k": rng.integers(0, 4, n).astype(np.int32),
        }, ts))
    return out


def run(app, sends, store=None, upto=None):
    """Rows as ``(event timestamp, *data)`` in delivery order, and what
    the runtime said of itself.  ``upto``: send only the first batches,
    then persist."""
    m = SiddhiManager()
    try:
        if store is not None:
            m.set_persistence_store(store)
        rt = m.create_siddhi_app_runtime(app)
        got = []
        rt.add_callback("Out", lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for cols, ts in sends[:upto]:
            h.send_batch(EventBatch(
                "S", NAMES, {k: v.copy() for k, v in cols.items()},
                ts.copy()))
        # the barrier before anything is read: a closed loop of nine
        # batches may have left the last one's gate staged
        rt.drain_device_emits()
        rev = rt.persist() if upto is not None else None
        lowering = rt.lowering()
        dr = getattr(rt.query_runtimes["q0"], "device_runtime", None)
        stats = dr.stats() if dr is not None else None
        sm = rt.app_context.statistics_manager
        fallbacks = (dict(sm.device_fallbacks), dict(sm.sharded_fallbacks))
        rt.shutdown()
        return got, {"lowering": lowering, "stats": stats, "rev": rev,
                     "fallbacks": fallbacks, "runtime": dr}
    finally:
        m.shutdown()


def assert_same_rows(host, dev):
    assert len(dev) == len(host)
    for i, (a, b) in enumerate(zip(host, dev)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            if isinstance(x, float):
                assert y == pytest.approx(x, rel=1e-5), f"row {i}: {a} {b}"
            else:
                assert x == y and type(x) is type(y), f"row {i}: {a} {b}"


def differential(query, sends, program=None):
    host, h = run(HOST + DEFINE + query, sends)
    dev, d = run(DEVICE + DEFINE + query, sends)
    assert h["lowering"] == {"q0": "host"}
    assert d["lowering"] == {"q0": "device"}
    assert d["fallbacks"] == ({}, {})
    assert isinstance(d["runtime"], DeviceQueryRuntime)
    assert d["runtime"].step_invocations > 0
    if program is not None:
        assert d["runtime"].engine.pane_batched == (program == "batch")
    assert d["stats"]["rows_emitted"] == len(dev)
    assert_same_rows(host, dev)
    assert len(host) > 0, "vacuous: the host engine emitted nothing"
    return host, d


# batches that are and are not multiples of the pane: they straddle,
# under-fill, exactly fill and fill a pane many times over
SIZES = (37, 64, 5, 128, 3, 3, 3, 3, 20, 1, 100)
# the host engine closes timeBatch panes at a batch's watermark, the
# device engine row by row: they agree on events sent one at a time
ONE_BY_ONE = (1,) * 160


class TestUpstreamShape:
    @pytest.mark.parametrize("L", [1, 2, 7, 10, 100, 200])
    def test_pane_lengths(self, L):
        """The upstream select list at panes of 1 row to more than any
        batch holds (200 against batches of at most 128)."""
        host, d = differential(UPSTREAM.format(L=L), ticks(SIZES), "batch")
        assert d["stats"]["panes_closed"] == sum(SIZES) // L

    def test_pane_of_a_thousand_rows(self):
        """Long panes take the one program too (the crossover measured
        on the chip is beside ``PANE_MAX_LENGTH``)."""
        host, d = differential(UPSTREAM.format(L=1000),
                               ticks((600, 900, 1700)), "batch")
        assert d["stats"]["panes_closed"] == 3

    @pytest.mark.parametrize("sizes", [(40, 40, 40), (10,) * 9,
                                       (7, 13, 29, 31), (1,) * 45])
    def test_batches_against_the_pane(self, sizes):
        differential(UPSTREAM.format(L=10), ticks(sizes), "batch")

    def test_long_above_2_24_is_bit_exact(self):
        host, _ = differential(UPSTREAM.format(L=10), ticks((64, 64)))
        stamps = [r[4] for r in host]
        assert min(stamps) > 1 << 40
        # float32 would have merged neighbours: all are distinct here
        assert len(set(stamps)) == len(stamps)

    def test_filter_before_the_window(self):
        """Boundaries fall on passing rows only; a row the filter drops
        is never a group's last."""
        q = ("@info(name='q0') from S[price > 400.0 and volume < 250]"
             "#window.lengthBatch(7) select symbol, sum(price) as total, "
             "count() as c, timestamp group by symbol insert into Out;")
        differential(q, ticks(SIZES), "batch")

    def test_no_group_by(self):
        q = ("@info(name='q0') from S#window.lengthBatch(10) "
             "select sum(price) as total, timestamp, symbol insert into Out;")
        host, _ = differential(q, ticks(SIZES), "batch")
        assert len(host) == sum(SIZES) // 10


class TestBareAttributes:
    def test_three_bare_attributes(self):
        """LONG above 2^24, STRING and FLOAT side by side, each at its
        declared type."""
        q = ("@info(name='q0') from S#window.lengthBatch(10) "
             "select symbol, timestamp, note, price, max(price) as top, "
             "min(volume) as low group by symbol insert into Out;")
        differential(q, ticks(SIZES), "batch")

    def test_two_bare_attributes_integer_key(self):
        q = ("@info(name='q0') from S#window.lengthBatch(7) "
             "select k, note, timestamp, sum(price) as total, "
             "max(volume) as top group by k insert into Out;")
        differential(q, ticks(SIZES), "batch")

    def test_two_group_keys(self):
        q = ("@info(name='q0') from S#window.lengthBatch(10) "
             "select symbol, k, timestamp, avg(volume) as v "
             "group by symbol, k insert into Out;")
        differential(q, ticks(SIZES), "batch")

    def test_group_seen_once_and_group_filling_a_pane(self):
        sends = ticks((30, 30))
        # the second pane is all one symbol; "ONCE" comes a single time
        sends[0][0]["symbol"][10:20] = "FULL"
        sends[1][0]["symbol"][7] = "ONCE"
        host, _ = differential(UPSTREAM.format(L=10), sends, "batch")
        assert sum(r[1] == "FULL" for r in host) == 1
        assert sum(r[1] == "ONCE" for r in host) == 1

    def test_having(self):
        q = ("@info(name='q0') from S#window.lengthBatch(10) "
             "select symbol, sum(price) as total, timestamp "
             "group by symbol having total > 1200.0 insert into Out;")
        host, _ = differential(q, ticks(SIZES), "batch")
        assert len(host) < sum(SIZES) // 10 * 5

    def test_select_expression_over_key_and_aggregate(self):
        q = ("@info(name='q0') from S#window.lengthBatch(10) "
             "select k, k + 0.5 as kk, sum(price) / count() as mean, note "
             "group by k insert into Out;")
        differential(q, ticks(SIZES), "batch")


class TestSweepPath:
    """What the one-program path does not hold keeps the per-pane
    sweep; bare attributes work there from per-group registers."""

    def test_time_batch_with_a_bare_attribute(self):
        q = ("@info(name='q0') from S#window.timeBatch(50 ms) "
             "select symbol, sum(price) as total, timestamp, note "
             "group by symbol insert into Out;")
        differential(q, ticks(ONE_BY_ONE, step_ms=3), "sweep")

    def test_time_batch_filtered(self):
        q = ("@info(name='q0') from S[volume > 100]#window.timeBatch(40 ms) "
             "select k, count() as c, timestamp group by k insert into Out;")
        differential(q, ticks(ONE_BY_ONE, step_ms=3), "sweep")

    def test_pane_longer_than_the_program_tiles(self):
        from siddhi_tpu.ops.device_query import PANE_MAX_LENGTH
        L = PANE_MAX_LENGTH + 4
        host, d = differential(
            UPSTREAM.format(L=L),
            ticks((L // 2, L // 2 + 50, L // 2, L // 2)), "sweep")
        assert d["stats"]["panes_closed"] == 2

    def test_forever_aggregate_keeps_the_sweep(self):
        q = ("@info(name='q0') from S#window.lengthBatch(10) "
             "select symbol, maxForever(price) as top, timestamp "
             "group by symbol insert into Out;")
        differential(q, ticks(SIZES), "sweep")

    def test_sharded_declines_bare_attributes_counted(self):
        """The sharded wrapper has no last-row registers: it declines,
        counted, and the single-device engine serves the query."""
        header = ("@app:playback @app:execution('tpu', partitions='256', "
                  "devices='4') ")
        host, _ = run(HOST + DEFINE + UPSTREAM.format(L=10), ticks(SIZES))
        dev, d = run(header + DEFINE + UPSTREAM.format(L=10), ticks(SIZES))
        assert d["lowering"] == {"q0": "device"}
        assert d["fallbacks"] == ({}, {"q0": 1})
        assert d["runtime"].engine.pane_batched
        assert_same_rows(host, dev)


@pytest.mark.parametrize("window", ["lengthBatch(5)", "timeBatch(50 ms)"])
@pytest.mark.parametrize("filt", ["", "[price > 400.0]"])
@pytest.mark.parametrize("select", ["symbol, timestamp, price", "*"])
def test_without_aggregate_or_group_by_every_row_of_a_pane(
        window, filt, select):
    """A batch window with neither an aggregate nor a group-by owes
    every row of a pane, not a group's last (``core/query.py``): the
    device engine declines, counted, and the host engine's rows come."""
    q = (f"@info(name='q0') from S{filt}#window.{window} "
         f"select {select} insert into Out;")
    sends = ticks(ONE_BY_ONE, step_ms=3)
    host, _ = run(HOST + DEFINE + q, sends)
    dev, d = run(DEVICE + DEFINE + q, sends)
    assert d["lowering"] == {"q0": "host"}
    assert d["fallbacks"][0] == {"q0": 1}
    assert len(host) > len(ONE_BY_ONE) // 3, "more than a row a pane"
    assert_same_rows(host, dev)


@pytest.mark.parametrize("window", ["lengthBatch(10)", "timeBatch(50 ms)"])
def test_group_by_without_aggregate(window):
    """A group-by alone already makes the host keep a group's last row."""
    q = (f"@info(name='q0') from S#window.{window} "
         "select symbol, timestamp, note group by symbol insert into Out;")
    differential(q, ticks(ONE_BY_ONE, step_ms=3))


def test_calls_and_fetches_do_not_grow_with_the_panes(monkeypatch):
    """One batch of 100 panes and one of 800: the same number of jitted
    calls and of blocking device fetches."""
    import jax

    from siddhi_tpu.ops import device_query

    counts = {"jit": 0, "get": 0}
    real_jit, real_get = jax.jit, jax.device_get

    def counting_jit(fn, *a, **kw):
        compiled = real_jit(fn, *a, **kw)

        def call(*args, **kwargs):
            counts["jit"] += 1
            return compiled(*args, **kwargs)
        return call

    def counting_get(x):
        counts["get"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "jit", counting_jit)
    monkeypatch.setattr(jax, "device_get", counting_get)
    per_batch = {}
    for panes in (100, 800):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                DEVICE + DEFINE + UPSTREAM.format(L=10))
            got = []
            rt.add_callback("Out", lambda evs: got.extend(evs))
            rt.start()
            h = rt.get_input_handler("S")
            (warm, wts), (cols, ts) = ticks((panes * 10 + 3, panes * 10))
            h.send_batch(EventBatch("S", NAMES, warm, wts))  # compiles
            dr = rt.query_runtimes["q0"].device_runtime
            dr.drain()
            before, counts["jit"], counts["get"] = len(got), 0, 0
            h.send_batch(EventBatch("S", NAMES, cols, ts))
            dr.drain()
            per_batch[panes] = dict(counts)
            assert dr.engine.panes_closed == 2 * panes
            assert len(got) - before > panes
            rt.shutdown()
        finally:
            m.shutdown()
    assert per_batch[100] == per_batch[800]
    assert per_batch[100]["jit"] == 1
    assert 1 <= per_batch[100]["get"] <= 2    # the count, the columns
    assert device_query.PANE_MAX_LENGTH >= 10


@pytest.mark.parametrize("query", [
    UPSTREAM.format(L=10),
    "@info(name='q0') from S#window.timeBatch(50 ms) select symbol, "
    "sum(price) as total, timestamp group by symbol insert into Out;",
], ids=["lengthBatch", "timeBatch"])
def test_snapshot_restore_in_the_middle_of_a_pane(query):
    """Persist with the pane part full (carried rows on the one-program
    path, last-row registers on the sweep), restore into a new runtime:
    the same rows as an uninterrupted run."""
    app = "@app:name('tumblingsnap') " + DEVICE + DEFINE + query
    sends = ticks((23, 14, 31, 9, 40), step_ms=3)
    ref, _ = run(app, sends, store=InMemoryPersistenceStore())
    assert len(ref) > 10
    store = InMemoryPersistenceStore()
    first, d = run(app, sends, store=store, upto=2)
    assert (23 + 14) % 10 != 0
    m = SiddhiManager()
    try:
        m.set_persistence_store(store)
        rt = m.create_siddhi_app_runtime(app)
        got = list(first)
        rt.add_callback("Out", lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        rt.restore_revision(d["rev"])
        h = rt.get_input_handler("S")
        for cols, ts in sends[2:]:
            h.send_batch(EventBatch("S", NAMES, cols, ts))
        rt.shutdown()
    finally:
        m.shutdown()
    assert_same_rows(ref, got)
