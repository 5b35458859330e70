"""Device-query differential sweep: a scenario matrix of general
single-stream queries run under @app:execution('tpu') AND on the host
engine, asserting identical outputs and that the jitted device step
actually ran.  Complements test_device_single_integration with broader
shapes (arithmetic filters, batch windows + having, min/max over
expiry, multi-query apps, null handling).
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.device_single import DeviceQueryRuntime

DEFS = "define stream S (k long, v double, w long); "


def drive(app, sends, out="O"):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime("@app:playback " + app)
        got = []
        rt.add_callback(out, lambda evs: got.extend(list(e.data) for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        runtimes = [getattr(qr, "device_runtime", None)
                    for qr in rt.query_runtimes.values()]
        rt.shutdown()
        return got, runtimes
    finally:
        m.shutdown()


def differential(query, sends, expect_device=True, out="O"):
    host, _ = drive(query, sends, out)
    dev, runtimes = drive("@app:execution('tpu') " + query, sends, out)
    if expect_device:
        dr = [r for r in runtimes if isinstance(r, DeviceQueryRuntime)]
        assert dr, "no query lowered to the device path"
        assert all(r.step_invocations > 0 for r in dr)
    assert len(dev) == len(host)
    for i, (a, b) in enumerate(zip(host, dev)):
        for x, y in zip(a, b):
            if isinstance(x, float):
                assert y == pytest.approx(x, rel=1e-5), f"row {i}: {a} != {b}"
            else:
                assert x == y, f"row {i}: {a} != {b}"
    return host


def mk_sends(n=40, seed=9):
    rng = np.random.default_rng(seed)
    return [([int(rng.integers(0, 5)), float(rng.integers(0, 100)),
              int(rng.integers(0, 1000))], 1000 + i * 37)
            for i in range(n)]


class TestDeviceDifferentialSweep:
    def test_arithmetic_filter_projection(self):
        q = (DEFS + "@info(name='q') from S[v * 2.0 + 1.0 > 50.0] "
             "select k, v * 10.0 as sv, v - 1.0 as d insert into O;")
        got = differential(q, mk_sends())
        assert len(got) > 0

    def test_length_window_running_aggregates(self):
        q = (DEFS + "@info(name='q') from S#window.length(5) "
             "select sum(v) as s, count() as c, avg(v) as a, "
             "min(v) as mn, max(v) as mx insert into O;")
        differential(q, mk_sends())

    def test_time_window_group_by(self):
        q = (DEFS + "@info(name='q') from S#window.time(1 sec) "
             "select k, sum(v) as total, count() as n group by k "
             "insert into O;")
        differential(q, mk_sends())

    def test_length_batch_having(self):
        # batch flushes emit one row per group; host orders groups by
        # arrival, the device engine by group slot — compare as sets
        q = (DEFS + "@info(name='q') from S#window.lengthBatch(8) "
             "select k, sum(v) as total group by k having total > 50.0 "
             "insert into O;")
        host, _ = drive(q, mk_sends())
        dev, runtimes = drive("@app:execution('tpu') " + q, mk_sends())
        assert any(isinstance(r, DeviceQueryRuntime) for r in runtimes)
        assert sorted((k, round(t, 4)) for k, t in host) == \
            sorted((k, round(t, 4)) for k, t in dev)
        assert len(host) > 0

    def test_time_batch_min_max(self):
        q = (DEFS + "@info(name='q') from S#window.timeBatch(1 sec) "
             "select min(v) as mn, max(v) as mx, count() as n "
             "insert into O;")
        differential(q, mk_sends())

    def test_filterless_passthrough_projection(self):
        q = (DEFS + "@info(name='q') from S select k, v insert into O;")
        differential(q, mk_sends(12))

    def test_multi_query_app_mixed_paths(self):
        # two device-eligible queries plus one host-only (string attr)
        q = (DEFS +
             "define stream T (name string, x long); "
             "@info(name='q1') from S[v > 50.0] select k, v insert into O; "
             "@info(name='q2') from S#window.length(3) "
             "select sum(v) as sv insert into O2; "
             "@info(name='q3') from T[name == 'a'] select x insert into O3;")
        host, _ = drive(q, mk_sends(20))
        dev, runtimes = drive("@app:execution('tpu') " + q, mk_sends(20))
        assert len(host) == len(dev)
        for i, (a, b) in enumerate(zip(host, dev)):
            assert a == [pytest.approx(x) for x in b], f"row {i}: {a} != {b}"
        assert sum(isinstance(r, DeviceQueryRuntime) for r in runtimes) >= 2

    def test_chained_inserts_cross_engines(self):
        # a device query feeding a second query through a mid stream
        q = (DEFS +
             "@info(name='q1') from S[v > 20.0] select k, v insert into Mid; "
             "@info(name='q2') from Mid#window.length(4) "
             "select k, sum(v) as total group by k insert into O;")
        differential(q, mk_sends())


class TestDeviceQueryFuzz:
    """Seeded random (filter, window, selector) combinations — each
    (shape, seed) pair pins the device engine against the host across
    thousands of window transitions."""

    WINDOWS = ["", "#window.length({n})", "#window.lengthBatch({n})",
               "#window.time({t} sec)", "#window.timeBatch({t} sec)"]
    SELECTS = [
        "k, v",
        "sum(v) as s, count() as c",
        "k, sum(v) as s group by k",
        "k, avg(v) as a, min(v) as mn, max(v) as mx group by k",
    ]

    @pytest.mark.parametrize("win", WINDOWS[2::2])
    @pytest.mark.parametrize("filt", ["", "[v > 40.0]"])
    @pytest.mark.parametrize("sel", ["k, v", "*"])
    def test_batch_window_without_aggregate_runs_on_the_host(
            self, win, filt, sel):
        """The pairing ``test_random_combination`` redraws: a pane's
        every row is owed, not a group's last, so the device engine
        declines and the rows are the host engine's."""
        q = (DEFS + f"@info(name='q') from S{filt}{win.format(n=5, t=1)} "
             f"select {sel} insert into O;")
        sends = mk_sends(60, seed=231)
        host, _ = drive(q, sends)
        dev, runtimes = drive("@app:execution('tpu') " + q, sends)
        assert not any(isinstance(r, DeviceQueryRuntime) for r in runtimes)
        assert len(host) > 20 and dev == host

    @pytest.mark.parametrize("seed", range(8))
    def test_random_combination(self, seed):
        rng = np.random.default_rng(100 + seed)
        win = self.WINDOWS[rng.integers(0, len(self.WINDOWS))].format(
            n=int(rng.integers(2, 7)), t=int(rng.integers(1, 3)))
        sel = self.SELECTS[rng.integers(0, len(self.SELECTS))]
        if "Batch" in win and "(" not in sel:
            # a batch window whose select has neither an aggregate nor
            # a group-by emits every row of a pane: the device engine
            # declines it and the host engine runs it
            # (test_batch_window_without_aggregate_runs_on_the_host) —
            # the draw pairs batch windows with aggregating selects
            sel = self.SELECTS[1 + rng.integers(0, len(self.SELECTS) - 1)]
        thr = float(rng.integers(10, 80))
        filt = f"[v > {thr}]" if rng.integers(0, 2) else ""
        q = (DEFS + f"@info(name='q') from S{filt}{win} "
             f"select {sel} insert into O;")
        sends = mk_sends(60, seed=200 + seed)
        host, _ = drive(q, sends)
        dev, runtimes = drive("@app:execution('tpu') " + q, sends)
        assert any(isinstance(r, DeviceQueryRuntime) for r in runtimes), (
            f"seed {seed}: {q} did not lower")
        batchy = "Batch" in win and "group by" in sel
        if batchy:
            # batch flushes order groups differently (see
            # test_length_batch_having); compare per-row multisets
            ha = sorted(tuple(round(x, 4) if isinstance(x, float) else x
                              for x in r) for r in host)
            da = sorted(tuple(round(x, 4) if isinstance(x, float) else x
                              for x in r) for r in dev)
            assert ha == da, f"seed {seed}: {q}"
        else:
            assert len(host) == len(dev), (
                f"seed {seed}: {q}: {len(host)} vs {len(dev)}")
            for i, (a, b) in enumerate(zip(host, dev)):
                assert a == [pytest.approx(x, rel=1e-4, abs=1e-6)
                             for x in b], f"seed {seed} row {i}: {a} != {b}"
