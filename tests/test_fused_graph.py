"""Device-resident stream-graph fusion differential suite.

``@app:fuse`` (planner/fusion.py) lowers `insert into` chains whose
intermediate streams have exactly one device producer and one device
consumer into ONE jitted multi-stage program (ops/fused_graph.py +
core/fused_graph.py): intermediate event columns stay in HBM, no
EventBatch is built and no junction dispatch happens between stages.

The contract under test is bit-identical callbacks versus the same app
running per-query engines with junction hops — across chain shapes
(filter→filter, filter→window→filter, filter→window→dense-pattern),
under transient ingest/emit faults, crash + journal replay, and
persist/restore mid-chain — plus dispatch accounting (one jitted step
per batch cycle, zero intermediate dispatches, zero intermediate
EventBatches) and counted, readable fallback reasons for unfusable
chains.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import (
    SiddhiAppCreationError,
    SimulatedCrashError,
)
from siddhi_tpu.util.persistence import InMemoryPersistenceStore


def _collector(res):
    return lambda events: res.extend(
        (e.timestamp, tuple(e.data)) for e in events)


def _sends(n, seed):
    rng = np.random.default_rng(seed)
    out = []
    ts = 1000
    for _ in range(n):
        out.append(([int(rng.integers(0, 5)),
                     float(np.float32(rng.uniform(0, 30))),
                     int(rng.integers(1, 100))], ts))
        ts += 3
    return out


TWO_STAGE = """
@app:name('f2{tag}') @app:playback @app:execution('tpu') {fuse}
define stream SIn (sym int, price float, vol int);

@info(name='q1') from SIn[price > 10.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid[vol > 50]
select sym, price insert into Out;
"""

THREE_STAGE = """
@app:name('f3{tag}') @app:playback @app:execution('tpu') {fuse}{faults}
define stream SIn (sym int, price float, vol int);
define stream Mid (sym int, price float, vol int);
define stream Win (sym int, total double);

@info(name='q1') from SIn[price > 10.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid#window.length(8)
select sym, sum(price) as total insert into Win;
@info(name='q3') from Win[total > 50.0]
select sym, total insert into Out;
"""

DENSE_TAIL = """
@app:name('fd{tag}') @app:playback @app:execution('tpu') {fuse}
define stream SIn (sym int, price float, vol int);
define stream Mid (sym int, price float, vol int);
define stream Win (sym int, total double);

@info(name='q1') from SIn[price > 5.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid#window.length(4)
select sym, sum(price) as total insert into Win;
@info(name='q3') from every e1=Win[total > 30.0] -> e2=Win[total > e1.total]
select e1.sym as s1, e1.total as t1, e2.total as t2 insert into Out;
"""


def _run_app(app_text, fuse, sends, tag_extra="", faults="", mgr=None):
    own = mgr is None
    if own:
        mgr = SiddhiManager()
    try:
        rt = mgr.create_siddhi_app_runtime(app_text.format(
            tag=("F" if fuse else "J") + tag_extra,
            fuse="@app:fuse" if fuse else "", faults=faults))
        got = []
        rt.add_callback("Out", _collector(got))
        rt.start()
        h = rt.get_input_handler("SIn")
        for row, ts in sends:
            h.send(list(row), timestamp=ts)
        low = rt.lowering()
        junc = {k: j.dispatches for k, j in rt.junctions.items()}
        fi = rt.app_context.fault_injector
        fstats = fi.stats.as_dict() if fi else {}
        rt.shutdown()
        return got, low, junc, fstats
    finally:
        if own:
            mgr.shutdown()


class TestFusedDifferential:
    """Fused chains == junction hops, bit for bit, per chain shape."""

    def test_two_stage_filter_filter_undeclared_intermediate(self):
        # Mid is never declared: the planner synthesizes its schema from
        # the producer's output spec
        sends = _sends(60, 3)
        gf, lf, jf, _ = _run_app(TWO_STAGE, True, sends)
        gj, lj, _, _ = _run_app(TWO_STAGE, False, sends)
        assert lf == {"q1": "fused", "q2": "fused"}
        assert "fused" not in lj.values()
        assert len(gf) > 0 and gf == gj
        assert jf.get("Mid", 0) == 0

    def test_three_stage_filter_window_filter(self):
        sends = _sends(90, 0)
        gf, lf, jf, _ = _run_app(THREE_STAGE, True, sends)
        gj, lj, jjn, _ = _run_app(THREE_STAGE, False, sends)
        assert lf == {"q1": "fused", "q2": "fused", "q3": "fused"}
        assert len(gf) > 0 and gf == gj
        # intermediate junctions never dispatch on the fused path; the
        # junction path hops through both
        assert jf.get("Mid", 0) == 0 and jf.get("Win", 0) == 0
        assert jjn["Mid"] > 0 and jjn["Win"] > 0

    def test_three_stage_dense_pattern_tail(self):
        sends = _sends(75, 7)
        gf, lf, jf, _ = _run_app(DENSE_TAIL, True, sends)
        gj, lj, _, _ = _run_app(DENSE_TAIL, False, sends)
        assert lf == {"q1": "fused", "q2": "fused", "q3": "fused"}
        assert lj["q3"] == "dense"
        assert len(gf) > 0 and gf == gj
        assert jf.get("Mid", 0) == 0 and jf.get("Win", 0) == 0

    def test_large_batches_chunked_bit_identical(self):
        # many-row junction batches exercise the chunked ingest path
        rng = np.random.default_rng(21)
        sends = []
        for b in range(6):
            rows = [[int(rng.integers(0, 5)),
                     float(np.float32(rng.uniform(0, 30))),
                     int(rng.integers(1, 100))] for _ in range(64)]
            sends.append((rows, 1000 + 50 * b))

        def run(fuse):
            mgr = SiddhiManager()
            try:
                rt = mgr.create_siddhi_app_runtime(THREE_STAGE.format(
                    tag="BF" if fuse else "BJ",
                    fuse="@app:fuse" if fuse else "", faults=""))
                got = []
                rt.add_callback("Out", _collector(got))
                rt.start()
                h = rt.get_input_handler("SIn")
                from siddhi_tpu.core.event import Event
                for rows, ts in sends:
                    h.send([Event(ts + i, list(r))
                            for i, r in enumerate(rows)])
                rt.shutdown()
                return got
            finally:
                mgr.shutdown()

        gf, gj = run(True), run(False)
        assert len(gf) > 0 and gf == gj


class TestFusedDispatchAccounting:
    """One jitted program per batch cycle; intermediates stay in HBM."""

    def test_one_jit_per_cycle_and_hop_counters(self):
        n = 40
        sends = _sends(n, 5)
        mgr = SiddhiManager()
        try:
            rt = mgr.create_siddhi_app_runtime(THREE_STAGE.format(
                tag="A", fuse="@app:fuse", faults=""))
            rt.add_callback("Out", lambda e: None)
            rt.start()
            h = rt.get_input_handler("SIn")
            for row, ts in sends:
                h.send(list(row), timestamp=ts)
            dr = rt.query_runtimes["q3"].device_runtime
            st = dr.stats()
            # the WHOLE 3-stage chain advances with ONE fused dispatch
            # per batch cycle — not one per stage
            assert st["engine"] == "fused" and st["stages"] == 3
            assert st["step_invocations"] == n
            assert st["fused_hops"] == 2 * n  # (stages - 1) per dispatch
            assert rt.junctions["SIn"].dispatches == n
            assert rt.junctions["Mid"].dispatches == 0
            assert rt.junctions["Win"].dispatches == 0
            rt.shutdown()
        finally:
            mgr.shutdown()

    def test_no_intermediate_eventbatches(self, monkeypatch):
        """The fused path must never materialize an EventBatch on an
        intermediate stream — its columns live in HBM between stages."""
        built = []
        orig = EventBatch.__init__

        def counting(self, stream_id, *a, **k):
            built.append(stream_id)
            orig(self, stream_id, *a, **k)

        sends = _sends(50, 9)
        monkeypatch.setattr(EventBatch, "__init__", counting)
        _run_app(THREE_STAGE, True, sends, tag_extra="NB")
        fused_built = list(built)
        built.clear()
        _run_app(THREE_STAGE, False, sends, tag_extra="NB")
        junction_built = list(built)
        assert "Mid" not in fused_built and "Win" not in fused_built
        assert "Mid" in junction_built and "Win" in junction_built
        assert len(fused_built) < len(junction_built)


class TestFusedFaults:
    pytestmark = pytest.mark.faults

    def test_transient_ingest_emit_faults_bit_identical(self):
        sends = _sends(80, 13)
        ref, _, _, _ = _run_app(THREE_STAGE, True, sends, tag_extra="T0")
        got, low, junc, st = _run_app(
            THREE_STAGE, True, sends, tag_extra="T1",
            faults="@app:faults(transfer.retry.scale='0.001', "
                   "ingest.put='transient:count=3', "
                   "emit.drain='transient:count=2') ")
        assert low == {"q1": "fused", "q2": "fused", "q3": "fused"}
        assert st["faults_injected"] >= 5
        assert st["transfer_retries"] >= 3 and st["drains_recovered"] >= 2
        assert junc.get("Mid", 0) == 0 and junc.get("Win", 0) == 0
        assert got == ref

    def test_crash_and_journal_replay(self):
        """Checkpoint, crash mid-run, restore + journal replay on a
        fresh runtime — bit-identical to a run that never crashed."""
        sends = _sends(30, 17)
        ref, _, _, _ = _run_app(THREE_STAGE, True, sends, tag_extra="C0")

        mgr = SiddhiManager()
        mgr.set_persistence_store(InMemoryPersistenceStore())
        try:
            faults = "@app:faults(journal='256') "
            app = THREE_STAGE.format(tag="FC1", fuse="@app:fuse",
                                     faults=faults)
            rt = mgr.create_siddhi_app_runtime(app)
            got = []
            rt.add_callback("Out", _collector(got))
            rt.start()
            h = rt.get_input_handler("SIn")
            for j, (row, ts) in enumerate(sends):
                if j == 10:
                    rt.persist()
                if j == 20:
                    rt.app_context.fault_injector.configure(
                        "ingest", "crash", count=1)
                    with pytest.raises(SimulatedCrashError):
                        h.send(list(row), timestamp=ts)
                    rt.shutdown()
                    rt = mgr.create_siddhi_app_runtime(app)
                    rt.add_callback("Out", _collector(got))
                    rt.start()
                    # the crashed send WAS journaled: replay covers it
                    assert rt.restore_last_revision() is not None
                    h = rt.get_input_handler("SIn")
                    continue
                h.send(list(row), timestamp=ts)
            assert rt.lowering() == {
                "q1": "fused", "q2": "fused", "q3": "fused"}
            rt.shutdown()
        finally:
            mgr.shutdown()
        assert got == ref


class TestFusedPersistence:
    def test_persist_restore_forgets_post_persist_event(self):
        """restore() rewinds the WHOLE chain's device state mid-window
        (q2's accumulator is partially filled at the checkpoint)."""

        def run(fuse):
            mgr = SiddhiManager()
            mgr.set_persistence_store(InMemoryPersistenceStore())
            try:
                rt = mgr.create_siddhi_app_runtime(THREE_STAGE.format(
                    tag="PF" if fuse else "PJ",
                    fuse="@app:fuse" if fuse else "", faults=""))
                got = []
                rt.add_callback("Out", _collector(got))
                rt.start()
                h = rt.get_input_handler("SIn")
                sends = _sends(40, 19)
                for row, ts in sends[:20]:
                    h.send(list(row), timestamp=ts)
                rt.persist()
                # stray event lands in q2's window, then is rolled back
                h.send([0, 29.0, 99], timestamp=5000)
                rt.restore_last_revision()
                for row, ts in sends[20:]:
                    h.send(list(row), timestamp=ts)
                rt.shutdown()
                return got
            finally:
                mgr.shutdown()

        gf, gj = run(True), run(False)
        assert len(gf) > 0 and gf == gj


class TestFusedFallback:
    """Unfusable chains drop to junction dispatch with a counted,
    readable reason — never silently."""

    def _stats(self, app_text, out_streams=("Out",), sends=None):
        mgr = SiddhiManager()
        try:
            rt = mgr.create_siddhi_app_runtime(app_text)
            for s in out_streams:
                rt.add_callback(s, lambda e: None)
            rt.start()
            if sends:
                h = rt.get_input_handler("SIn")
                for row, ts in sends:
                    h.send(list(row), timestamp=ts)
            low = rt.lowering()
            st = rt.statistics()
            rt.shutdown()
            return low, st
        finally:
            mgr.shutdown()

    def test_async_intermediate_falls_back(self):
        APP = """
@app:name('fba') @app:execution('tpu') @app:fuse @app:statistics('basic')
define stream SIn (sym int, price float);
@async(buffer.size='16')
define stream Mid (sym int, price float);
@info(name='q1') from SIn[price > 1.0] select sym, price insert into Mid;
@info(name='q2') from Mid select sym, price insert into Out;
"""
        low, st = self._stats(APP)
        assert "fused" not in low.values()
        pre = "io.siddhi.SiddhiApps.fba.Siddhi.Queries."
        assert st[pre + "q1.fusedFallbacks"] == 1
        assert "@async" in st[pre + "q1.fusedFallbackReason"]

    def test_table_hop_falls_back(self):
        APP = """
@app:name('fbt') @app:execution('tpu') @app:fuse @app:statistics('basic')
define stream SIn (sym int, price float);
define table T (sym int, price float);
@info(name='q1') from SIn[price > 1.0] select sym, price insert into T;
"""
        low, st = self._stats(APP, out_streams=())
        assert "fused" not in low.values()
        pre = "io.siddhi.SiddhiApps.fbt.Siddhi.Queries."
        assert st[pre + "q1.fusedFallbacks"] == 1
        assert "table" in st[pre + "q1.fusedFallbackReason"]

    def test_multi_consumer_intermediate_falls_back(self):
        APP = """
@app:name('fbm') @app:execution('tpu') @app:fuse @app:statistics('basic')
define stream SIn (sym int, price float);
define stream Mid (sym int, price float);
@info(name='q1') from SIn[price > 1.0] select sym, price insert into Mid;
@info(name='q2') from Mid select sym, price insert into Out;
@info(name='q3') from Mid[price > 2.0] select sym, price insert into Out2;
"""
        low, st = self._stats(APP, out_streams=("Out", "Out2"))
        assert "fused" not in low.values()
        pre = "io.siddhi.SiddhiApps.fbm.Siddhi.Queries."
        assert st[pre + "q1.fusedFallbacks"] == 1
        assert "one consumer" in st[pre + "q1.fusedFallbackReason"]

    def test_host_only_interior_stage_falls_back(self):
        # a STRING intermediate attribute has no device-resident lane
        APP = """
@app:name('fbs') @app:execution('tpu') @app:fuse @app:statistics('basic')
define stream SIn (sym string, price float);
@info(name='q1') from SIn[price > 1.0] select sym, price insert into Mid;
@info(name='q2') from Mid[price > 2.0] select sym, price insert into Out;
"""
        low, st = self._stats(APP)
        assert "fused" not in low.values()
        pre = "io.siddhi.SiddhiApps.fbs.Siddhi.Queries."
        assert st[pre + "q1.fusedFallbacks"] >= 1
        assert "lane" in st[pre + "q1.fusedFallbackReason"]

    def test_unfusable_tail_truncates_chain_prefix_still_fuses(self):
        """A group-by tail cannot fuse, but the q1→q2 prefix must still
        lower — per-chain truncation, not all-or-nothing."""
        APP = """
@app:name('fbg') @app:playback @app:execution('tpu') @app:fuse
@app:statistics('basic')
define stream SIn (sym int, price float, vol int);
define stream Mid (sym int, price float, vol int);
define stream Win (sym int, total double);
@info(name='q1') from SIn[price > 5.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid#window.length(4)
select sym, sum(price) as total insert into Win;
@info(name='q3') from Win select sym, sum(total) as s
group by sym insert into Out;
"""
        low, st = self._stats(APP, sends=_sends(30, 23))
        assert low["q1"] == "fused" and low["q2"] == "fused"
        assert low["q3"] != "fused"
        pre = "io.siddhi.SiddhiApps.fbg.Siddhi.Queries."
        assert st[pre + "q3.fusedFallbacks"] >= 1
        assert "group-by" in st[pre + "q3.fusedFallbackReason"]

    def test_truncated_prefix_bit_identical(self):
        APP = """
@app:name('ftr{tag}') @app:playback @app:execution('tpu') {fuse}{faults}
define stream SIn (sym int, price float, vol int);
define stream Mid (sym int, price float, vol int);
define stream Win (sym int, total double);
@info(name='q1') from SIn[price > 5.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid#window.length(4)
select sym, sum(price) as total insert into Win;
@info(name='q3') from Win select sym, sum(total) as s
group by sym insert into Out;
"""
        sends = _sends(45, 29)
        gf, lf, _, _ = _run_app(APP, True, sends)
        gj, _, _, _ = _run_app(APP, False, sends)
        assert lf["q1"] == "fused" and lf["q2"] == "fused"
        assert len(gf) > 0 and gf == gj

    def test_fuse_requires_tpu_mode(self):
        with pytest.raises(SiddhiAppCreationError, match="tpu"):
            SiddhiManager().create_siddhi_app_runtime(
                "@app:fuse define stream S (v double); "
                "@info(name='q') from S select v insert into Out;")


# -- the benchmark's chained deployment (benchmark/configs/cse_chain3.json) ---

def _cse_chain3():
    """The configuration ``cse_chain3``, its traffic mix and its cell's
    generator."""
    from test_cse_chain3_reference import cell_files

    return cell_files()[:3]


class TestCseChain3:
    """The cell's own app, as the harness deploys it: the fused form
    against the junction-hopped one, and what the fused engine records
    of a batch."""

    def _deploy(self, mgr, fuse, tag, extra=""):
        config, _traffic, _gen = _cse_chain3()
        header = config["header"].format(**config["rehearsal"])
        if not fuse:
            header = header.replace("@app:fuse", "")
        return config, mgr.create_siddhi_app_runtime(
            f"@app:name('c3{tag}') {header} {extra} {config['app']}")

    def _rows(self, fuse, schedule, n_batches):
        mgr = SiddhiManager()
        try:
            config, rt = self._deploy(mgr, fuse, "F" if fuse else "J")
            got = []
            rt.add_callback(config["output"], lambda evs: got.extend(
                (e.timestamp, *e.data) for e in evs))
            rt.start()
            h = rt.get_input_handler(config["stream"])
            for n in range(-schedule.warmup, n_batches - schedule.warmup):
                h.send_batch(schedule.batch(n))
            low = rt.lowering()
            fallbacks = dict(
                rt.app_context.statistics_manager.fused_fallbacks)
            rt.shutdown()
            return got, low, fallbacks
        finally:
            mgr.shutdown()

    @pytest.mark.parametrize("rehearsal", [True, False],
                             ids=["512-row", "8192-row"])
    def test_fused_equals_junction_hopped_at_the_cells_batches(
            self, rehearsal):
        """Rows, values and event timestamps bit-equal at the
        rehearsal's batch and at the cell's (one 8,192-row chunk a
        batch on the fused side, one 8,192-row step a stage on the
        other); the window is carried over every batch boundary."""
        config, traffic, gen = _cse_chain3()
        schedule = gen.make(2**31 + 39, config, traffic, rehearsal)
        assert schedule.batch_events == (512 if rehearsal else 8192)
        gf, lf, ff = self._rows(True, schedule, 5)
        gj, lj, _ = self._rows(False, schedule, 5)
        assert lf == config["expect"]["lowering"] and not ff
        assert lj == {"q1": "device", "q2": "device", "q3": "device"}
        # a third of the events: two thirds pass q1, half of those q3
        assert 0.25 < len(gf) / (5 * schedule.batch_events) < 0.42
        assert gf == gj

    def test_the_four_fused_scopes_are_on_the_lowered_program(self):
        """Declared in ``DEVICE_SCOPES`` and on the operations: the head
        and tail filters under their place alone, the window's own
        phases innermost inside ``interior``."""
        import re

        from siddhi_tpu.observability import trace as trace_mod

        fused = {sc for sc in trace_mod.DEVICE_SCOPES if ".fused." in sc}
        assert fused == {"siddhi.fused.head", "siddhi.fused.interior",
                         "siddhi.fused.tail", "siddhi.fused.count"}
        mgr = SiddhiManager()
        try:
            _config, rt = self._deploy(mgr, True, "S")
            graph = rt.query_runtimes["q3"].device_runtime.graph
            B = 64
            buf = graph._lanes(
                list(graph.init_state()),
                {"price": np.linspace(100.0, 999.0, B).astype(np.float32),
                 "volume": np.arange(B, dtype=np.int32)},
                1000 + np.arange(B, dtype=np.int64), B, B)
            text = graph.make_step().lower(
                graph.init_state(), buf).compile().as_text()
            rt.shutdown()
        finally:
            mgr.shutdown()
        paths = set(re.findall(r'op_name="([^"]*)"', text))

        def innermost(path):
            named = [p for p in path.split("/") if p.startswith("siddhi.")]
            return named[-1] if named else None

        owned = {innermost(p) for p in paths}
        assert fused <= owned
        # a window keeps its phases, and only inside the interior stage
        for p in paths:
            if "siddhi.window." in p:
                assert "siddhi.fused.interior/" in p.split(
                    "siddhi.window.")[0]
        assert {"siddhi.window.slot", "siddhi.window.aggregate"} <= owned
        # the stateless stages open no window scope of their own
        assert not any("siddhi.window." in p for p in paths
                       if "siddhi.fused.head" in p
                       or "siddhi.fused.tail" in p)

    def test_convert_put_dispatch_tile_ingest_chunk_by_chunk(self):
        """On a clock that ticks at every reading: a chunk is a
        ``convert``, a ``put`` and a ``dispatch``, each starting at the
        next reading after the one before ended, the first of them at
        the next after ``ingest`` began and ``ingest`` ending at the
        next after the last; the counters say the same.  The chain's
        bound is its window's (131,072 rows): a batch is one chunk."""
        from test_way_back import Ticks

        config, traffic, gen = _cse_chain3()
        schedule = gen.make(7, config, dict(
            traffic, rehearsal={"batch": 5000, "warmup": 1}), True)
        mgr = SiddhiManager()
        try:
            _config, rt = self._deploy(
                mgr, True, "T", "@app:trace(sample='1', cycles='64') "
                "@app:statistics(reporter='none')")
            tracer = rt.app_context.tracer
            tracer.clock = Ticks()
            rt.add_callback(config["output"], lambda evs: None)
            rt.start()
            h = rt.get_input_handler(config["stream"])
            for n in range(-1, 2):
                h.send_batch(schedule.batch(n))
            groups = tracer.recorder.cycle_groups()
            assert len(groups) == 3
            for spans in groups.values():
                assert {s[2] for s in spans} == {"fused"}
                (ingest,) = [s for s in spans if s[1] == "ingest"]
                inside = [s for s in spans
                          if ingest[3] < s[3] and s[4] < ingest[4]]
                # 5,000 rows: one chunk, one put of one leaf, one call
                assert [s[1] for s in inside] == [
                    "convert", "put", "dispatch"]
                assert [s[5] for s in inside if s[1] == "convert"] == [
                    5000]
                assert all(s[5] == 1 for s in inside
                           if s[1] == "dispatch")
                # price, volume, q2's timestamps, valid, padded to 8,192
                assert [s[5] for s in inside if s[1] == "put"] == [
                    4 * 8192 * 4]
                edges = [ingest[3]] + [t for s in inside
                                       for t in (s[3], s[4])] + [ingest[4]]
                assert all(b - a == 1 for a, b in zip(edges, edges[1:]))
                assert ingest[5] == 5000
            st = rt.statistics()
            pre = "io.siddhi.SiddhiApps.c3T.Siddhi.Queries.q3."
            assert st[pre + "deviceChunks"] == 3
            assert st[pre + "devicePuts"] == 3
            assert st[pre + "fusedHops"] == 6    # two hops a batch
            dr = rt.query_runtimes["q3"].device_runtime
            assert dr.stats()["fused_hops"] == 6
            assert dr.step_invocations == 3
            rt.shutdown()
        finally:
            mgr.shutdown()

    def test_a_junction_hopped_query_counts_no_fused_hop(self):
        mgr = SiddhiManager()
        try:
            config, rt = self._deploy(
                mgr, False, "H", "@app:statistics(reporter='none')")
            rt.add_callback(config["output"], lambda evs: None)
            rt.start()
            _c, traffic, gen = _cse_chain3()
            schedule = gen.make(3, config, traffic, True)
            rt.get_input_handler(config["stream"]).send_batch(
                schedule.batch(0))
            st = rt.statistics()
            pre = "io.siddhi.SiddhiApps.c3H.Siddhi.Queries."
            for q in ("q1", "q2", "q3"):
                assert st[pre + q + ".fusedHops"] == 0
                assert st[pre + q + ".deviceChunks"] == 1
            rt.shutdown()
        finally:
            mgr.shutdown()
