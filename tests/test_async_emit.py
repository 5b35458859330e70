"""Async emit pipeline: count-gated, queued device→host transfers.

Every device engine now emits through ``core/emit_queue.py``: the jitted
step returns a scalar match count (zero-match batches transfer NOTHING),
matched batches stay device-resident in a bounded pending-emit queue
(``@app:execution('tpu', emit.depth='N')``), and explicit drain barriers
keep callback content/order bit-identical to the synchronous path.

These tests pin the exactness contract differentially — the same app and
event series at ``emit.depth='1'`` (sync timing) vs a deeper queue must
produce identical callbacks across every flush trigger (queue-full,
timer fire, snapshot mid-stream, pull query, shutdown) on the
device-single, partitioned, dense, and sharded paths — and assert the
transfer counters: zero-match batches perform no column transfer.

The drain's fault handling (the ``emit.drain`` site, its retry, the
dropped drain) is held with the emit arrays' copies started at dispatch
(core/device_pipeline.py) and with every fetch on demand
(``no_early_copies`` of conftest.py): the same rows either way.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.dense_pattern import DensePatternRuntime
from siddhi_tpu.core.device_single import DeviceQueryRuntime

DEFINE = "define stream S (k long, v double); "


def series(n, seed, n_keys=4, t0=1000, dt_max=400):
    rng = np.random.default_rng(seed)
    ts = t0 + np.cumsum(rng.integers(1, dt_max, size=n))
    keys = rng.integers(0, n_keys, size=n)
    vals = rng.integers(1, 100, size=n).astype(float)
    return [([int(k), float(v)], int(t)) for k, v, t in zip(keys, vals, ts)]


def run_app(app, sends, out="OutputStream", exec_opts=None,
            want_runtime=False):
    """Playback run -> list of data tuples.  ``exec_opts`` is the option
    tail of @app:execution('tpu'...), e.g. ", emit.depth='4'"; None runs
    the host engine."""
    header = "@app:playback "
    if exec_opts is not None:
        header += f"@app:execution('tpu'{exec_opts}) "
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + app)
        got = []
        rt.add_callback(out, lambda evs: got.extend(tuple(e.data)
                                                    for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        qr = next(iter(rt.query_runtimes.values()))
        runtime = (getattr(qr, "device_runtime", None)
                   or getattr(qr, "pattern_processor", None))
        rt.shutdown()
        if want_runtime:
            return got, runtime
        return got
    finally:
        m.shutdown()


def depth_differential(app, sends, deep=4, ordered=True, out="OutputStream",
                       extra=""):
    """host == depth-1 == depth-N rows; asserts the deep run deferred."""
    host = run_app(app, sends, out=out)
    d1, rt1 = run_app(app, sends, out=out, exec_opts=extra, want_runtime=True)
    dN, rtN = run_app(app, sends, out=out,
                      exec_opts=f"{extra}, emit.depth='{deep}'",
                      want_runtime=True)
    assert rt1 is not None, "query did not lower to a device engine"
    assert rt1.step_invocations > 0
    assert rtN.emit_queue.depth == deep
    if not ordered:
        host, d1, dN = sorted(host), sorted(d1), sorted(dN)
    assert d1 == host, "depth-1 device path diverged from host"
    assert dN == host, "deferred emits changed callback content/order"
    return rtN


class TestDeviceSingleDifferential:
    def test_filter_projection_deferred(self):
        app = DEFINE + ("from S[v > 20.0] select k, v, v * 2.0 as dbl "
                        "insert into OutputStream;")
        rt = depth_differential(app, series(120, seed=1))
        assert isinstance(rt, DeviceQueryRuntime)
        # most batches match -> the deep queue actually deferred and
        # coalesced: strictly fewer transfers than matching batches
        assert rt.emit_stats.deferred_batches > 0
        assert rt.emit_stats.max_pending_depth == 4
        matched = rt.emit_stats.emit_transfers + rt.emit_stats.deferred_batches
        assert rt.emit_stats.emit_transfers < matched

    def test_grouped_window_deferred(self):
        app = DEFINE + ("from S#window.length(8) select k, sum(v) as s, "
                        "max(v) as hi group by k insert into OutputStream;")
        rt = depth_differential(app, series(150, seed=2, n_keys=5))
        assert rt.emit_stats.deferred_batches > 0

    def test_timer_fire_tumbling_pane(self):
        # timeBatch emits happen on pane close (timer fire) — the drain
        # barrier in fire() must keep deferred content exact
        app = DEFINE + ("from S#window.timeBatch(1 sec) select k, "
                        "sum(v) as s group by k insert into OutputStream;")
        depth_differential(app, series(150, seed=3), ordered=False)

    def test_rate_limiter_decision_barrier(self):
        # time-based output rate: the limiter's on_time decision must see
        # every deferred row first (fire() drains device_runtime)
        app = DEFINE + ("from S select k, sum(v) as s group by k "
                        "output last every 1 sec insert into OutputStream;")
        depth_differential(app, series(200, seed=4), deep=8)

    def test_string_group_keys_survive_deferred_drain(self):
        # gvals are captured at enqueue time — a deep queue must not
        # alias or reorder the key side channel
        app = ("define stream S (sym string, v double); "
               "from S select sym, sum(v) as s group by sym "
               "insert into OutputStream;")
        sends = [(["IBM", 10.0], 1000), (["MSFT", 20.0], 1100),
                 (["IBM", 5.0], 1200), (["MSFT", 1.0], 1300),
                 (["ORCL", 2.0], 1400)]
        dN, rt = run_app(app, sends, exec_opts=", emit.depth='8'",
                         want_runtime=True)
        assert isinstance(rt, DeviceQueryRuntime)
        assert rt.emit_stats.deferred_batches > 0
        assert [r[0] for r in dN] == ["IBM", "MSFT", "IBM", "MSFT", "ORCL"]
        assert dN == run_app(app, sends)


class TestFlushTriggers:
    APP = DEFINE + "from S[v > 0.0] select k, v insert into OutputStream;"
    HDR = "@app:playback @app:execution('tpu', emit.depth='{d}') "

    def _start(self, depth, app=None):
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(
            self.HDR.format(d=depth) + (app or self.APP))
        got = []
        rt.add_callback("OutputStream",
                        lambda evs: got.extend(tuple(e.data) for e in evs))
        rt.start()
        return m, rt, got

    def test_queue_full_drains_mid_stream(self):
        m, rt, got = self._start(2)
        try:
            h = rt.get_input_handler("S")
            h.send([1, 10.0], timestamp=1000)
            assert got == []  # first match deferred
            h.send([2, 20.0], timestamp=1100)
            assert len(got) == 2  # queue reached depth -> drained
            h.send([3, 30.0], timestamp=1200)
            assert len(got) == 2  # third pending again
            rt.shutdown()
            assert got == [(1, 10.0), (2, 20.0), (3, 30.0)]
        finally:
            m.shutdown()

    def test_shutdown_flushes_pending(self):
        m, rt, got = self._start(16)
        try:
            h = rt.get_input_handler("S")
            for i in range(5):
                h.send([i, float(i + 1)], timestamp=1000 + i)
            assert got == []  # all five below depth
            rt.shutdown()
            assert got == [(i, float(i + 1)) for i in range(5)]
        finally:
            m.shutdown()

    def test_snapshot_mid_stream_flushes_pending(self):
        m, rt, got = self._start(16)
        try:
            h = rt.get_input_handler("S")
            for i in range(4):
                h.send([i, 1.0], timestamp=1000 + i)
            assert got == []
            blob = rt.snapshot()
            assert len(got) == 4  # snapshot barrier drained first
            # and the blob restores into a runtime that continues exactly
            m2 = SiddhiManager()
            try:
                rt2 = m2.create_siddhi_app_runtime(
                    self.HDR.format(d=16) + self.APP)
                got2 = []
                rt2.add_callback(
                    "OutputStream",
                    lambda evs: got2.extend(tuple(e.data) for e in evs))
                rt2.start()
                rt2.restore(blob)
                rt2.get_input_handler("S").send([9, 9.0], timestamp=2000)
                rt2.shutdown()
                assert got2 == [(9, 9.0)]
            finally:
                m2.shutdown()
            rt.shutdown()
        finally:
            m.shutdown()

    def test_persist_flushes_pending(self):
        from siddhi_tpu.util.persistence import InMemoryPersistenceStore

        m = SiddhiManager()
        try:
            m.set_persistence_store(InMemoryPersistenceStore())
            rt = m.create_siddhi_app_runtime(
                self.HDR.format(d=16) + self.APP)
            got = []
            rt.add_callback(
                "OutputStream",
                lambda evs: got.extend(tuple(e.data) for e in evs))
            rt.start()
            h = rt.get_input_handler("S")
            for i in range(3):
                h.send([i, 1.0], timestamp=1000 + i)
            assert got == []
            rt.persist()
            assert len(got) == 3  # persist barrier drained first
            rt.shutdown()
        finally:
            m.shutdown()

    def test_pull_query_flushes_pending(self):
        app = (DEFINE + "define table T (k long, v double); "
               "from S[v > 0.0] select k, v insert into OutputStream; "
               "from S select k, v insert into T;")
        m, rt, got = self._start(16, app=app)
        try:
            h = rt.get_input_handler("S")
            for i in range(3):
                h.send([i, 2.0], timestamp=1000 + i)
            assert got == []
            rows = rt.query("from T select k, v;")
            assert len(got) == 3  # pull-query barrier drained first
            assert len(rows) == 3
            rt.shutdown()
        finally:
            m.shutdown()

    def test_debugger_forces_depth_one(self):
        m, rt, got = self._start(8)
        try:
            qr = next(iter(rt.query_runtimes.values()))
            assert qr.device_runtime.emit_queue.depth == 8
            rt.debug()
            assert qr.device_runtime.emit_queue.depth == 1
            h = rt.get_input_handler("S")
            h.send([1, 1.0], timestamp=1000)
            assert len(got) == 1  # no deferral under the debugger
            rt.shutdown()
        finally:
            m.shutdown()


class TestZeroMatchGating:
    def test_no_transfer_on_zero_match_batches(self):
        app = DEFINE + ("from S[v > 1000000.0] select k, v "
                        "insert into OutputStream;")
        sends = series(40, seed=5)  # vals < 100: nothing ever matches
        got, rt = run_app(app, sends, exec_opts="", want_runtime=True)
        assert got == []
        assert isinstance(rt, DeviceQueryRuntime)
        assert rt.step_invocations == 40  # the jitted step DID run
        assert rt.emit_stats.zero_match_skips == 40
        assert rt.emit_stats.emit_transfers == 0  # no column fetched
        assert rt.emit_stats.max_pending_depth == 0

    def test_zero_match_dense_pattern(self):
        app = DEFINE + ("from every e1=S[v > 1000000.0] -> "
                        "e2=S[v > e1.v] within 10 sec "
                        "select e1.v as a, e2.v as b "
                        "insert into OutputStream;")
        got, rt = run_app(app, series(40, seed=6), exec_opts="",
                          want_runtime=True)
        assert got == []
        assert isinstance(rt, DensePatternRuntime)
        assert rt.step_invocations == 40
        assert rt.emit_stats.zero_match_skips == 40
        assert rt.emit_stats.emit_transfers == 0

    def test_counters_ride_statistics_feed(self):
        app = ("@app:name('emitApp') @app:statistics('true') "
               "@app:playback @app:execution('tpu', emit.depth='2') "
               + DEFINE +
               "@info(name='q') from S[v > 50.0] select k, v "
               "insert into OutputStream;")
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(app)
            rt.start()
            h = rt.get_input_handler("S")
            for i, v in enumerate([60.0, 70.0, 10.0, 80.0]):
                h.send([i, v], timestamp=1000 + i)
            stats = rt.statistics()
            pre = "io.siddhi.SiddhiApps.emitApp.Siddhi.Queries.q."
            assert stats[pre + "zeroMatchSkips"] == 1  # the 10.0 batch
            assert stats[pre + "emitTransfers"] >= 1
            assert stats[pre + "deferredBatches"] >= 1
            assert stats[pre + "maxPendingDepth"] == 2
            rt.shutdown()
        finally:
            m.shutdown()


PATTERN_APP = DEFINE + (
    "from every e1=S[v > 50.0] -> e2=S[v > e1.v] within 10 sec "
    "select e1.v as a, e2.v as b insert into OutputStream;")

PART_APP = (
    "define stream S (card string, v double); "
    "partition with (card of S) begin "
    "@info(name='q') "
    "from every a=S[v > 100.0] -> b=S[v > a.v] within 10 min "
    "select a.v as base, b.v as bv insert into Alerts; "
    "end;")


def part_sends(n_keys=12, rounds=6, seed=7):
    rng = np.random.default_rng(seed)
    sends, t = [], 1000
    for _ in range(rounds):
        for k in range(n_keys):
            t += int(rng.integers(1, 50))
            sends.append(([f"c{k}", float(rng.integers(50, 400))], t))
    return sends


def run_part(header, sends, out="Alerts"):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + PART_APP)
        got = []
        rt.add_callback(out, lambda evs: got.extend(tuple(e.data)
                                                    for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        pr = rt.partitions.get("partition_0")
        runtime = (next(iter(pr.dense_query_runtimes.values()))
                   .pattern_processor
                   if pr is not None and pr.is_dense else None)
        rt.shutdown()
        return got, runtime
    finally:
        m.shutdown()


class TestDenseAndShardedDifferential:
    def test_dense_pattern_deferred(self):
        # instances='32': `every` on a dense 120-event series overflows
        # the default 4 pending lanes, which drops matches vs host —
        # orthogonal to emit deferral
        rt = depth_differential(PATTERN_APP, series(120, seed=8),
                                extra=", instances='32'")
        assert isinstance(rt, DensePatternRuntime)
        assert rt.emit_stats.deferred_batches > 0

    def test_partitioned_dense_deferred(self):
        sends = part_sends()
        host, _ = run_part("@app:playback ", sends)
        d1, rt1 = run_part(
            "@app:playback @app:execution('tpu', partitions='64') ", sends)
        dN, rtN = run_part(
            "@app:playback @app:execution('tpu', partitions='64', "
            "emit.depth='4') ", sends)
        assert isinstance(rt1, DensePatternRuntime)
        assert rtN.emit_queue.depth == 4
        assert rtN.emit_stats.deferred_batches > 0
        assert d1 == host
        assert dN == host

    def test_sharded_dense_deferred(self):
        sends = part_sends(n_keys=16)
        host, _ = run_part("@app:playback ", sends)
        dN, rtN = run_part(
            "@app:playback @app:execution('tpu', partitions='64', "
            "devices='8', emit.depth='4') ", sends)
        assert isinstance(rtN, DensePatternRuntime)
        assert rtN._sharded is not None and rtN.n_shards == 8
        assert rtN.emit_stats.deferred_batches > 0
        assert dN == host


class TestShardedBigBatchRegression:
    def test_group_keys_aligned_past_2048_rows_deferred(self):
        """>MAX_DEVICE_BATCH sharded batches chunk internally; the
        group-key side channel must stay row-aligned across chunks AND
        survive a deferred (depth>1) drain — per-group FIRST rate
        limiting collapses to one global row if keys alias."""
        from siddhi_tpu.core.event import EventBatch

        for depth in ("1", "4"):
            m = SiddhiManager()
            try:
                rt = m.create_siddhi_app_runtime(
                    "@app:playback "
                    f"@app:execution('tpu', partitions='16', devices='8', "
                    f"emit.depth='{depth}') "
                    "define stream S (sym string, v double, k int); "
                    "@info(name='gq') from S select k, sum(v) as s "
                    "group by k output first every 5000 events "
                    "insert into Out;")
                got = []
                rt.add_callback("Out", lambda evs: got.extend(
                    tuple(e.data) for e in evs))
                rt.start()
                n = 3000
                rng = np.random.default_rng(0)
                ks = rng.integers(0, 4, n).astype(np.int32)
                rt.get_input_handler("S").send_batch(EventBatch(
                    "S", ["sym", "v", "k"],
                    {"sym": np.asarray(["x"] * n, dtype=object),
                     "v": np.ones(n), "k": ks},
                    1000 + np.arange(n, dtype=np.int64)))
                rt.shutdown()
                assert len(got) == 4, (depth, got)
                assert sorted(g[0] for g in got) == [0, 1, 2, 3]
            finally:
                m.shutdown()


class TestEmitDepthKnob:
    def test_depth_parses_onto_runtime(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:execution('tpu', emit.depth='3') " + DEFINE +
                "from S[v > 0.0] select k insert into Out;")
            assert rt.app_context.tpu_emit_depth == 3
            qr = next(iter(rt.query_runtimes.values()))
            assert qr.device_runtime.emit_queue.depth == 3
        finally:
            m.shutdown()

    @pytest.mark.parametrize("bad", ["0", "-2", "abc", "1.5"])
    def test_invalid_depth_rejected(self, bad):
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError

        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError):
                m.create_siddhi_app_runtime(
                    f"@app:execution('tpu', emit.depth='{bad}') " + DEFINE +
                    "from S[v > 0.0] select k insert into Out;")
        finally:
            m.shutdown()


class TestIsolatedFailuresAreVisible:
    """A device error surfaces at the count-gate fetch or at the drain
    fetch; both are isolated (the runtime lives on) but must reach the
    app's exception listeners and a counter on EVERY app — not only
    under the @app:faults harness."""

    APP = ("@app:playback @app:execution('tpu') " + DEFINE +
           "@info(name='q') from every e1=S[v > 50.0] -> e2=S[v > e1.v] "
           "within 10 sec select e1.v as a, e2.v as b "
           "insert into OutputStream;")

    def _run(self, monkeypatch, target, name):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(self.APP)
            assert rt.app_context.fault_injector is None  # no harness
            errors, got = [], []
            rt.add_exception_listener(errors.append)
            rt.add_callback("OutputStream",
                            lambda evs: got.extend(e.data for e in evs))
            rt.start()
            runtime = rt.query_runtimes["q"].pattern_processor
            h = rt.get_input_handler("S")
            h.send([1, 60.0], timestamp=1000)
            real = getattr(target, name)
            calls = []

            def boom(*a, **kw):
                if not calls:
                    calls.append(1)
                    raise RuntimeError("device step failed")
                return real(*a, **kw)

            monkeypatch.setattr(target, name, boom)
            h.send([1, 70.0], timestamp=1001)  # this match is lost
            assert got == []
            assert [str(e) for e in errors] == ["device step failed"]
            h.send([1, 80.0], timestamp=1002)  # the runtime lives on
            rt.shutdown()
            assert got == [[70.0, 80.0]]
            return runtime
        finally:
            m.shutdown()

    def test_drain_failure_reaches_listener_and_counter(self, monkeypatch):
        from siddhi_tpu.core import emit_queue

        runtime = self._run(monkeypatch, emit_queue, "fetch_coalesced")
        assert runtime.emit_stats.dropped_batches == 1
        assert runtime.emit_stats.as_dict()["droppedBatches"] == 1

    def test_count_gate_failure_reaches_listener_and_counter(
            self, monkeypatch):
        from siddhi_tpu.ops.dense_nfa import DeferredDenseEmit

        runtime = self._run(monkeypatch, DeferredDenseEmit, "resolve")
        assert runtime.ingest_stats.dropped_batches == 1


class TestDrainFaultsWithEarlyCopies:
    """A copy started at dispatch is awaited inside ``EmitQueue._fetch``:
    the injection site, the retry ladder and the drop-this-drain
    isolation sit where they sat, and ``emitTransfers`` still counts one
    a drain."""

    APP = DEFINE + ("@info(name='q') from S[v > 0.0] select k, v "
                    "insert into OutputStream;")
    SENDS = [[i, float(i + 1)] for i in range(8)]

    def _run(self, faults=""):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:playback " + faults + "@app:execution('tpu') "
                + self.APP)
            got, errors = [], []
            rt.add_callback("OutputStream", lambda evs: got.extend(
                tuple(e.data) for e in evs))
            rt.add_exception_listener(errors.append)
            rt.start()
            h = rt.get_input_handler("S")
            for i, row in enumerate(self.SENDS):
                h.send(list(row), timestamp=1000 + i)
            runtime = rt.query_runtimes["q"].device_runtime
            rt.shutdown()
            return got, runtime, rt.app_context.fault_injector, errors
        finally:
            m.shutdown()

    def _started(self, runtime, copies):
        st = runtime.emit_stats
        if copies == "early":
            # every batch matches: started from the third on
            assert st.early_copy_batches == len(self.SENDS) - 2
        else:
            assert st.early_copy_batches == 0
        return st

    def test_clean_run_counts_one_transfer_a_drain(self, copies):
        got, runtime, _fi, errors = self._run()
        assert got == [tuple(r) for r in self.SENDS] and not errors
        st = self._started(runtime, copies)
        assert st.emit_transfers == len(self.SENDS)
        assert st.early_copy_hits == st.early_copy_batches
        assert st.early_copy_wasted_bytes == 0

    def test_transient_fault_is_retried_and_bit_exact(self, copies):
        clean, _rt, _fi, _errors = self._run()
        got, runtime, fi, errors = self._run(
            "@app:faults(seed='3', transfer.retry.scale='0.0001', "
            "emit.drain='transient:count=3:after=3') ")
        assert got == clean and not errors
        # the fourth drain (its arrays started early, where they are)
        # trips three times and succeeds on the fourth attempt
        assert fi.stats.faults_injected == 3
        assert fi.stats.transfer_retries == 3
        assert fi.stats.drains_recovered == 1
        assert fi.stats.drains_failed == 0
        st = self._started(runtime, copies)
        assert st.emit_transfers == len(self.SENDS)
        assert st.dropped_batches == 0

    def test_exhausted_retries_drop_that_drain_alone(self, copies):
        got, runtime, fi, errors = self._run(
            "@app:faults(transfer.retry.attempts='1', "
            "transfer.retry.scale='0.0001', "
            "emit.drain='transient:count=2:after=3') ")
        want = [tuple(r) for i, r in enumerate(self.SENDS) if i != 3]
        assert got == want
        assert fi.stats.drains_failed == 1 and len(errors) == 1
        st = self._started(runtime, copies)
        assert st.dropped_batches == 1
        assert st.emit_transfers == len(self.SENDS) - 1
        if copies == "early":
            # the dropped drain's arrays were handed on all the same
            assert st.early_copy_hits == st.early_copy_batches

    def test_sticky_loss_fails_every_drain_and_the_runtime_lives(
            self, copies):
        got, runtime, fi, errors = self._run(
            "@app:faults(emit.drain='sticky') ")
        assert got == []
        assert fi.stats.drains_failed == len(self.SENDS) == len(errors)
        assert fi.stats.drains_recovered == 0
        st = self._started(runtime, copies)
        assert st.dropped_batches == len(self.SENDS)
        assert st.emit_transfers == 0
        assert runtime.step_invocations == len(self.SENDS)

    def test_failing_fetch_without_a_harness_drops_one_drain(
            self, copies, monkeypatch):
        from siddhi_tpu.core import emit_queue

        real, calls = emit_queue.fetch_coalesced, []

        def boom(arrays, *a, **kw):
            calls.append(len(arrays))
            if len(calls) == 4:
                raise RuntimeError("device step failed")
            return real(arrays, *a, **kw)

        monkeypatch.setattr(emit_queue, "fetch_coalesced", boom)
        got, runtime, fi, errors = self._run()
        assert fi is None
        assert got == [tuple(r) for i, r in enumerate(self.SENDS) if i != 3]
        assert [str(e) for e in errors] == ["device step failed"]
        st = self._started(runtime, copies)
        assert st.dropped_batches == 1
