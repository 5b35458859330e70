"""Observability layer: cycle tracing, flight recorder, histograms,
Prometheus exposition, and the statistics-manager hardening that rides
along.

The differential acceptance test kills a device app mid-stream with the
fault injector and asserts the flight-recorder dump holds complete,
correctly ordered ingest -> step -> emit spans for the final cycles —
the black-box post-mortem the recorder exists for.
"""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
from loop_clock import PacedClock

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import (
    SiddhiAppCreationError,
    SimulatedCrashError,
)
from siddhi_tpu.observability import (
    FlightRecorder,
    LatencyHistogram,
    Tracer,
    render_prometheus,
)
from siddhi_tpu.observability import trace as trace_mod
from siddhi_tpu.observability.prometheus import CONTENT_TYPE
from siddhi_tpu.service import SiddhiService
from siddhi_tpu.util.statistics import (
    LatencyTracker,
    StatisticsManager,
    ThroughputTracker,
)

PATTERN_BODY = (
    "define stream S (k long, v double); "
    "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
    "select b.v as bv insert into Out;")


def device_app(name, trace="", faults=""):
    return (f"@app:name('{name}') @app:playback @app:execution('tpu') "
            f"{trace}{faults}" + PATTERN_BODY)


def make_batch(i, n=32, seed=3):
    rng = np.random.default_rng(seed + i)
    return EventBatch(
        "S", ["k", "v"],
        {"k": np.arange(n, dtype=np.int64) % 4,
         "v": rng.uniform(0.0, 20.0, n)},
        np.full(n, 1_000 + i * 10, dtype=np.int64))


# -- histograms ---------------------------------------------------------------


def test_histogram_quantiles():
    h = LatencyHistogram()
    for _ in range(100):
        h.record_ms(0.75)  # lands in the (0.5, 1.0] bucket
    assert h.count == 100
    assert h.sum_ms == pytest.approx(75.0)
    assert h.max_ms == pytest.approx(0.75)
    # every quantile interpolates inside the landing bucket
    assert 0.5 < h.p50_ms() <= 1.0
    assert 0.5 < h.p99_ms() <= 1.0
    h.reset()
    assert h.count == 0 and h.sum_ms == 0.0 and h.p50_ms() == 0.0


def test_histogram_spread_and_overflow():
    h = LatencyHistogram()
    for v in (0.06, 0.06, 0.06, 200.0, 200.0, 9_999.0):
        h.record_ms(v)
    # p50 lands among the 0.06ms samples, p99 in the tail
    assert h.p50_ms() <= 0.25
    assert h.p95_ms() > 100.0
    # overflow bucket (beyond the last bound) reports the observed max
    assert h.quantile_ms(0.999) == pytest.approx(9_999.0)
    bounds, counts, sum_ms, count = h.snapshot()
    assert count == 6 and sum(counts) == 6
    assert len(bounds) == len(LatencyHistogram.BOUNDS_MS)


def test_histogram_record_s_converts():
    h = LatencyHistogram()
    h.record_s(0.002)
    assert h.max_ms == pytest.approx(2.0)


# -- throughput tracker: windowed rate fix ------------------------------------


def test_throughput_windowed_rate_tracks_recent_traffic():
    now = [0.0]
    t = ThroughputTracker("S", clock=lambda: now[0])
    # 1000 ev/s for the first window
    for _ in range(5):
        t.add(1000)
        now[0] += 1.0
    first = t.events_per_second()
    assert first == pytest.approx(1000.0, rel=0.05)
    # then 45s of silence: the windowed rate decays toward zero while
    # the lifetime rate only divides by the longer elapsed time
    now[0] += 45.0
    assert t.events_per_second() < t.lifetime_events_per_second()
    assert t.events_per_second() < first * 0.1
    assert t.lifetime_events_per_second() == pytest.approx(
        5000.0 / 50.0, rel=0.01)
    assert t.count == 5000


def test_throughput_young_tracker_matches_lifetime():
    now = [0.0]
    t = ThroughputTracker("S", clock=lambda: now[0])
    t.add(100)
    now[0] += 1.0  # window not yet closed
    assert t.events_per_second() == pytest.approx(
        t.lifetime_events_per_second())


def test_throughput_reset():
    now = [0.0]
    t = ThroughputTracker("S", clock=lambda: now[0])
    t.add(100)
    now[0] += 10.0
    t.events_per_second()
    t.reset()
    assert t.count == 0
    assert t.events_per_second() == 0.0
    assert t.lifetime_events_per_second() == 0.0


# -- latency tracker percentiles ----------------------------------------------


def test_latency_tracker_percentiles_ride_along():
    lt = LatencyTracker("q")
    for _ in range(10):
        lt.mark_in(4)
        lt.mark_out(4)
    # existing keys keep their semantics
    assert lt.batches == 10 and lt.events == 40
    assert lt.avg_ms() >= 0.0 and lt.max_ms() >= lt.avg_ms()
    # new percentile read-outs come from the histogram
    assert lt.hist.count == 10
    assert lt.p50_ms() >= 0.0
    assert lt.p99_ms() >= lt.p50_ms()
    lt.reset()
    assert lt.hist.count == 0 and lt.p50_ms() == 0.0


def test_statistics_feed_has_percentile_keys():
    sm = StatisticsManager("app")
    lt = sm.latency_tracker("q")
    lt.mark_in(2)
    lt.mark_out(2)
    st = sm.stats()
    base = "io.siddhi.SiddhiApps.app.Siddhi.Queries.q."
    for metric in ("latencyAvgMs", "latencyMaxMs", "latencyP50Ms",
                   "latencyP95Ms", "latencyP99Ms", "events"):
        assert base + metric in st


# -- tracer sampling ----------------------------------------------------------


def test_tracer_sampling_strides():
    t = Tracer("app", sample=4)
    toks = [t.begin_cycle("device", 1) for _ in range(8)]
    sampled = [tok for tok in toks if tok is not None]
    # ids 1..8: only 4 and 8 hit the 1-in-4 stride
    assert [tok.cycle for tok in sampled] == [4, 8]
    assert Tracer("app", sample=0).begin_cycle("device", 1) is None
    every = Tracer("app", sample=1)
    assert all(every.begin_cycle("device", 1) is not None
               for _ in range(5))


def test_tracer_stage_stats_only_reports_recorded_stages():
    t = Tracer("app", sample=1)
    assert t.stage_stats() == {}
    tok = t.begin_cycle("device", 8)
    tok.dispatched()
    assert sorted(t.stage_stats()) == ["ingest"]
    assert t.stage_stats()["ingest"]["spans"] == 1


def test_trace_annotation_parse_errors():
    m = SiddhiManager()
    try:
        for ann in ("@app:trace(sample='2/3') ",
                    "@app:trace(sample='bogus') ",
                    "@app:trace(sample='0') ",
                    "@app:trace(cycles='0') ",
                    "@app:trace(cycles='99999') "):
            with pytest.raises(SiddhiAppCreationError):
                m.create_siddhi_app_runtime(
                    device_app("badtrace", trace=ann), register=False)
    finally:
        m.shutdown()


def test_trace_annotation_configures_tracer(tmp_path):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(device_app(
            "anntrace",
            trace=f"@app:trace(sample='1/8', cycles='16', "
                  f"dir='{tmp_path}') "), register=False)
        tr = rt.app_context.tracer
        assert tr.sample == 8
        assert tr.recorder.cycles == 16
        assert tr.recorder.dump_dir == str(tmp_path)
        assert rt.app_context.statistics_manager.tracer is tr
        rt.shutdown()
        # default-on: no annotation still builds a sampled tracer
        rt2 = m.create_siddhi_app_runtime(
            device_app("anntrace2"), register=False)
        assert rt2.app_context.tracer.sample == Tracer.DEFAULT_SAMPLE
        rt2.shutdown()
    finally:
        m.shutdown()


# -- flight recorder ----------------------------------------------------------


def test_recorder_ring_evicts_to_newest_cycles():
    """The ring at cycles=N holds the last N complete cycles: every
    stage once, the dense engine's counts of lanes, of the resident
    bytes they gather and of the batch's stream, the host
    preparation in two more pieces, a second host-stepped round, and
    the two persist spans interleaving."""
    one_cycle = (trace_mod.CYCLE_STAGES + trace_mod.CYCLE_COUNTS
                 + ("convert", "convert") + trace_mod.ROUND_STAGES)
    assert len(one_cycle) == trace_mod.SPANS_PER_CYCLE
    n = 3
    r = FlightRecorder("app", cycles=n,
                       spans_per_cycle=len(one_cycle) + 2)
    for c in range(1, 9):
        for stage in one_cycle:
            r.record((c, stage, "shard", 0.0, 1.0, 1))
        r.record((100 + c, "persist.capture", "persist", 0.0, 1.0, 0))
        r.record((100 + c, "persist.write", "persist", 0.0, 1.0, 0))
    groups = r.cycle_groups()
    # oldest cycles evicted, the newest N complete
    for c in (6, 7, 8):
        assert [s[1] for s in groups[c]] == list(one_cycle), c
    assert 5 not in groups
    assert len(r.spans()) == r.ring.maxlen


def test_recorder_dump_writes_json(tmp_path):
    r = FlightRecorder("app", cycles=4, spans_per_cycle=4,
                       dump_dir=str(tmp_path))
    r.record((1, "ingest", "device", 0.0, 1.0, 8))
    payload = r.dump("unit-test")
    assert r.last_dump is payload
    assert payload["reason"] == "unit-test"
    files = list(tmp_path.glob("app-*-unit-test.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk["spans"][0]["stage"] == "ingest"
    assert on_disk["spans"][0]["n_events"] == 8


def test_recorder_dump_file_cap(tmp_path):
    r = FlightRecorder("app", cycles=4, spans_per_cycle=4,
                       dump_dir=str(tmp_path))
    for i in range(FlightRecorder.MAX_DUMP_FILES + 5):
        r.dump(f"r{i}")
    assert len(list(tmp_path.glob("*.json"))) == FlightRecorder.MAX_DUMP_FILES
    # in-memory dump keeps updating past the file cap
    assert r.last_dump["reason"] == f"r{FlightRecorder.MAX_DUMP_FILES + 4}"


def test_chrome_trace_export():
    t = Tracer("app", sample=1)
    tok = t.begin_cycle("device", 8)
    tok.dispatched()
    tok.step_done(3)
    tok.emitted(t.clock())
    ch = t.recorder.chrome_trace()
    events = ch["traceEvents"]
    assert [e["ph"] for e in events] == ["X", "X", "X"]
    assert all(e["dur"] >= 0.0 and e["ts"] > 0.0 for e in events)
    # stages map to distinct tids (stacked tracks)
    assert len({e["tid"] for e in events}) == 3
    assert events[0]["args"]["cycle"] == 1
    assert ch["otherData"]["app"] == "app"


# -- differential: fault-injector kill dumps ordered cycles -------------------


def test_crash_dump_has_complete_ordered_final_cycles(tmp_path):
    """Kill the app mid-stream; the flight recorder must hold complete
    ingest -> step -> emit span triples for the final cycles, correctly
    ordered within and across cycles."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(device_app(
            "crashbox",
            trace=f"@app:trace(sample='1', cycles='8', dir='{tmp_path}') ",
            faults="@app:faults(step.dense='crash:after=6') "))
        rt.start()
        h = rt.get_input_handler("S")
        with pytest.raises(SimulatedCrashError):
            for i in range(20):
                h.send_batch(make_batch(i))
        dump = rt.app_context.tracer.recorder.last_dump
        assert dump is not None
        assert dump["reason"].startswith("fault-injector-crash:")
        spans = dump["spans"]
        assert spans, "crash dump must carry the span ring"
        by_cycle = {}
        for s in spans:
            by_cycle.setdefault(s["cycle"], []).append(s)
        cycles = list(by_cycle)
        assert cycles == sorted(cycles), "cycles must appear in order"
        # every cycle except the one the crash interrupted is a
        # complete, ordered ingest -> step -> emit triple among its spans
        assert len(cycles) >= 2
        for cid in cycles[:-1]:
            group = [s for s in by_cycle[cid]
                     if s["stage"] in ("ingest", "step", "emit")]
            assert [s["stage"] for s in group] == ["ingest", "step",
                                                   "emit"], cid
            starts = [s["t_start"] for s in group]
            assert starts == sorted(starts), cid
            assert all(s["t_end"] >= s["t_start"] for s in by_cycle[cid])
            assert group[0]["n_events"] == 32
        # the dump also survived to disk
        files = list(tmp_path.glob("crashbox-*.json"))
        assert files and json.loads(files[0].read_text())["spans"]
    finally:
        m.shutdown()


# -- the span vocabulary on the served paths ----------------------------------

PARTITIONED_BODY = (
    "define stream S (k long, v double); partition with (k of S) begin "
    "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
    "select b.v as bv insert into Out; end;")
WINDOW_BODY = (
    "define stream S (symbol string, price float, volume int); "
    "@info(name='q') from S#window.length(10) select symbol, "
    "sum(price) as total, avg(volume) as av %sinsert into Out;")


def keyed_batch(i, n=32):
    rng = np.random.default_rng(50 + i)
    # 24 keys over 32 rows: eight keys come twice, so every batch has a
    # second collision round
    return EventBatch(
        "S", ["k", "v"],
        {"k": np.arange(n, dtype=np.int64) % 24,
         "v": rng.uniform(0.0, 20.0, n)},
        np.full(n, 1_000 + i * 10, dtype=np.int64))


def window_batch(i, n=32):
    rng = np.random.default_rng(70 + i)
    return EventBatch(
        "S", ["symbol", "price", "volume"],
        {"symbol": np.array(["a", "b"] * (n // 2)),
         "price": rng.uniform(0.0, 20.0, n).astype(np.float32),
         "volume": np.arange(n, dtype=np.int32)},
        np.full(n, 1_000 + i * 10, dtype=np.int64))


# path -> (execution options, body, batch maker, stages per cycle with
# the least number of each, engine kind)
SERVED_PATHS = {
    "dense": ("partitions='64'", PARTITIONED_BODY, keyed_batch,
              {"intern": 1, "convert": 4, "plan": 1, "lanes": 1,
               "state_bytes": 1, "stream": 1, "put": 2, "dispatch": 2},
              "dense"),
    "shard": ("partitions='64', devices='4'", PARTITIONED_BODY, keyed_batch,
              {"intern": 1, "convert": 4, "plan": 1, "route": 2, "put": 2,
               "dispatch": 2}, "shard"),
    # a chunk is three spans (convert, put, dispatch) and, only where
    # keys are interned, a fourth; the runtime's column views are one
    # more convert
    "window": ("", WINDOW_BODY % "", window_batch,
               {"convert": 2, "put": 1, "dispatch": 1}, "device"),
    "window_grouped": ("", WINDOW_BODY % "group by symbol ", window_batch,
                       {"intern": 1, "convert": 2, "put": 1, "dispatch": 1},
                       "device"),
}
EVERY_CYCLE = ("admit", "ingest", "step", "emit", "fetch", "build",
               "deliver")


def covered(spans, lo, hi):
    """Seconds of [lo, hi] that the union of ``spans`` covers."""
    total, at = 0.0, lo
    for a, b in sorted((s[3], s[4]) for s in spans):
        a, b = max(a, at), min(b, hi)
        if b > a:
            total += b - a
            at = b
    return total


@pytest.mark.parametrize("path", list(SERVED_PATHS))
def test_spans_tile_send_batch(path, monkeypatch):
    """At sample='1' every batch yields every span its path has, under
    one cycle id, children inside parents, ``put`` counting the bytes
    put; together they cover ``send_batch`` from its entry: ``admit``
    is the entry's own work ahead of the cycle (PR 55), and what no
    span covers is the way out and the microseconds between spans."""
    import jax

    opts, body, make, owed, kind = SERVED_PATHS[path]
    put_bytes = []
    real_put = jax.device_put

    def counting_put(x, *a, **kw):
        put_bytes.append(sum(leaf.nbytes
                             for leaf in jax.tree_util.tree_leaves(x)))
        return real_put(x, *a, **kw)

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            f"@app:name('tile_{path}') @app:playback "
            f"@app:execution('tpu'{', ' + opts if opts else ''}) "
            "@app:trace(sample='1', cycles='16') " + body)
        rows = []
        rt.add_callback("Out", rows.extend)
        rt.start()
        # the structure held below is that of a cycle whose gate is
        # finished inside its own send (a staged gate's is
        # tests/test_device_pipeline.py's): the stage's rule is left on
        # and shown a sender it never admits, where the host's clock
        # would let the ninth of these sends leave a batch in flight
        for dr in rt._device_runtimes():
            dr.ingest_stage.clock = PacedClock()
        h = rt.get_input_handler("S")

        def tile(sends, cycles, remainders, shares, leads):
            """Hold each batch's spans to the structure of its path;
            note what share of ingest its children cover, what of the
            send no span covers and how far behind the caller's stamp
            the send's own entry stamp lies."""
            for (t0, t1, nbytes), spans in zip(sends, cycles):
                assert {s[2] for s in spans} == {kind}
                by = {}
                for s in spans:
                    assert s[4] >= s[3]
                    by.setdefault(s[1], []).append(s)
                assert set(by) == set(owed) | set(EVERY_CYCLE), set(by)
                for stage, least in owed.items():
                    assert len(by[stage]) >= least, (stage, len(by[stage]))
                for stage in EVERY_CYCLE:
                    assert len(by[stage]) == 1, stage
                if path == "dense":
                    # the rounds run on the device: a put and a dispatch
                    # for the first round and for all the rest, however
                    # often a key repeats
                    assert (len(by["put"]), len(by["dispatch"])) == (2, 2)
                if "plan" in by:
                    assert [s[5] for s in by["plan"]] == [2]  # rounds
                ingest, step = by["ingest"][0], by["step"][0]
                emit = by["emit"][0]
                # admit: from the send's entry stamp (inside the send,
                # microseconds behind the caller's own where the host
                # left the thread alone between the two clock reads:
                # ``leads``, held in the least disturbed batch) to
                # begin_cycle, ahead of every other span of its cycle;
                # the count is the batch's events
                admit = by["admit"][0]
                assert t0 <= admit[3] and admit[5] == 32
                leads.append(admit[3] - t0)
                assert admit[4] <= min(s[3] for s in spans if s is not admit)
                if "lanes" in by:
                    # once a batch, and no span of time: 24 keys and 8
                    # of them again, stepped 32 and 16 lanes wide
                    assert [(s[5], s[4] - s[3]) for s in by["lanes"]] == [
                        (32 + 16, 0.0)]
                    assert ingest[3] <= by["lanes"][0][3] <= ingest[4]
                    # the resident rows those lanes gather, in bytes: a
                    # row of 128 words (2 nodes of 4 lanes: 32 words,
                    # padded to a vector) a lane
                    assert [(s[5], s[4] - s[3])
                            for s in by["state_bytes"]] == [
                        ((32 + 16) * 128 * 4, 0.0)]
                    assert (ingest[3] <= by["state_bytes"][0][3]
                            <= ingest[4])
                    # the batch's stream, once a batch and of no width
                    # either: 0 on an app of one input stream
                    assert [(s[5], s[4] - s[3]) for s in by["stream"]] == [
                        (0, 0.0)]
                    assert ingest[3] <= by["stream"][0][3] <= ingest[4]
                # ingest, step and emit start and end where they always
                # did: ingest closes on the dispatch, step runs from there
                # to the count gate, emit from the fetch to the delivery
                assert step[3] == ingest[4] and step[4] <= emit[3]
                assert by["fetch"][0][3] == emit[3]
                assert (emit[3] <= by["fetch"][0][4] <= by["build"][0][3]
                        <= by["build"][0][4] <= by["deliver"][0][3])
                assert by["deliver"][0][4] <= emit[4]
                inside = [s for st in ("convert", "plan", "route", "put",
                                       "dispatch")
                          for s in by.get(st, [])]
                if kind == "device":   # interning lies inside ingest there
                    inside += by.get("intern", [])
                    assert len(spans) == len(owed) + 1 + len(EVERY_CYCLE)
                else:       # and ahead of it on the partitioned path
                    assert by["intern"][0][4] <= ingest[3]
                assert all(s[5] == 32 for s in by.get("intern", []))
                assert all(ingest[3] <= s[3] and s[4] <= ingest[4]
                           for s in inside)
                # siblings never overlap: a stage's time is a plain sum
                inside.sort(key=lambda s: s[3])
                assert all(a[4] <= b[3] for a, b in zip(inside, inside[1:]))
                shares.append(covered(inside, ingest[3], ingest[4])
                              / (ingest[4] - ingest[3]))
                assert sum(s[5] for s in by["put"]) == nbytes > 0
                assert (sum(s[5] for s in by["dispatch"])
                        == len(by["dispatch"]))
                assert by["deliver"][0][5] == emit[5] > 0
                assert by["build"][0][5] == emit[5]
                assert by["fetch"][0][5] > 0
                assert all(t0 <= s[3] and s[4] <= t1 for s in spans)
                remainders.append((t1 - t0) - covered(spans, t0, t1))

        h.send_batch(make(0))   # compiles
        monkeypatch.setattr(jax, "device_put", counting_put)
        remainders, shares, leads, sent = [], [], [], 0

        def timings_hold():
            # A busy host only ever adds to a batch's remainder and only
            # ever takes from its share, and the batches are alike (32
            # events, the same keys), so work that no span covers is in
            # every one of them: the least disturbed batch measures it.
            # (The medians of eight this replaces failed under six
            # workers on an eight-core host; alone they read 0.93 and
            # 0.4 ms on the sharded path.)  The entry's stamp behind
            # the caller's is held the same way: a preemption between
            # the two clock reads is the host's, and held in every
            # batch it failed ``window_grouped`` under six workers
            # (the driver's run of PR 59's tree).
            return (max(shares) >= 0.7 and min(remainders) < 0.5e-3
                    and min(leads) < 0.5e-3)

        # eight batches, and up to three more eights while the host has
        # not left one of them alone; every batch is held to the
        # structure below
        while sent < 8 or (sent < 32 and not timings_hold()):
            sends = []
            for i in range(sent + 1, sent + 9):
                del put_bytes[:]
                t0 = time.perf_counter()
                h.send_batch(make(i))
                sends.append((t0, time.perf_counter(), sum(put_bytes)))
            sent += 8
            groups = rt.app_context.tracer.recorder.cycle_groups()
            # cycles='16': the ring is sized in spans (16 cycles of the
            # longest kind), so a path of fewer spans a cycle keeps more
            assert len(groups) >= min(sent + 1, 16)
            tile(sends, list(groups.values())[-8:], remainders, shares,
                 leads)
        monkeypatch.undo()
        assert rows
        # the children cover ingest: seven tenths of it in the least
        # disturbed batch (a batch of 32 events is short enough for one
        # preemption to be a fifth of it; since PR 44 the calls that
        # start the emit arrays' copies lie in ingest behind the last
        # dispatch with no span of their own, and the window path, whose
        # ingest is the shortest, read 0.77-0.80 on every batch of 32
        # on a slow host, at the seed as after it)
        assert max(shares) >= 0.7
        # the stated remainder: the return through receiver, junction
        # and InputHandler after the last span, and the microseconds
        # between spans (the way in ahead of the cycle is ``admit``
        # now): under half a millisecond where the host left a batch
        # alone
        assert min(remainders) < 0.5e-3
        # and the entry's stamp within half a millisecond of the
        # caller's there
        assert min(leads) < 0.5e-3
        # every send was sampled, so every one is on the histogram, and
        # none is a tuple: a span over the send would hide the remainder
        tracer = rt.app_context.tracer
        assert tracer.stage_hist["send"].count == sent + 1
        assert tracer.stage_hist["admit"].count == sent + 1
        assert not [s for s in tracer.recorder.spans() if s[1] == "send"]
    finally:
        m.shutdown()


def test_unsampled_cycles_allocate_nothing(monkeypatch):
    """An unsampled cycle makes no token, no span and no annotation,
    and leaves nothing in the ring."""
    made = {"token": 0, "span": 0, "annotation": 0}

    def counted(cls, what):
        init = cls.__init__

        def counting(self, *a, **kw):
            made[what] += 1
            init(self, *a, **kw)
        monkeypatch.setattr(cls, "__init__", counting)

    counted(trace_mod.CycleToken, "token")
    counted(trace_mod.Span, "span")
    real = trace_mod.annotation

    def counting_annotation(stage):
        made["annotation"] += 1
        return real(stage)
    monkeypatch.setattr(trace_mod, "annotation", counting_annotation)
    import siddhi_tpu.core.emit_queue as eq

    monkeypatch.setattr(eq, "annotation", counting_annotation)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('unsampled') @app:playback "
            "@app:execution('tpu', partitions='64') "
            "@app:trace(sample='1/4', cycles='16') " + PARTITIONED_BODY)
        rows = []
        rt.add_callback("Out", rows.extend)
        rt.start()
        h = rt.get_input_handler("S")
        tracer = rt.app_context.tracer
        for i in range(3):      # cycle ids 1..3: none on the stride
            h.send_batch(keyed_batch(i))
        assert rows and made == {"token": 0, "span": 0, "annotation": 0}
        assert tracer.recorder.spans() == []
        h.send_batch(keyed_batch(3))    # cycle 4 is sampled
        assert made["token"] == 1
        spans = tracer.recorder.spans()
        assert spans and {s[0] for s in spans} == {4}
        # every Span and the two spans clocked by hand (step_wait, fetch)
        # made one annotation each
        assert made["annotation"] == made["span"] + 2
        # ingest, step, emit, fetch; lanes, state_bytes and stream,
        # counts and no Spans; and
        # admit, clocked from the send's entry stamp (at a sample under
        # 1 nobody knows at the entry that the cycle will be sampled:
        # no annotation)
        assert made["span"] == len(spans) - 8
        assert spans[0][1] == "admit"
        assert [s[1] for s in spans].count("lanes") == 1
        assert [s[1] for s in spans].count("state_bytes") == 1
        assert [s[1] for s in spans].count("stream") == 1
        before = dict(made)
        for i in range(4, 7):   # 5..7 unsampled again
            h.send_batch(keyed_batch(i))
        assert made == before and len(tracer.recorder.spans()) == len(spans)
    finally:
        m.shutdown()


def test_a_dead_cycle_takes_no_later_span():
    """A cycle that died before its ingest span closed is not the
    thread's open cycle for the batches after it."""
    t = Tracer("app", sample=2)
    assert t.begin_cycle("dense", 1) is None
    tok = t.begin_cycle("dense", 1)             # cycle 2, sampled, dies
    with trace_mod.span("put", 8):
        pass
    assert t.begin_cycle("dense", 1) is None    # cycle 3, unsampled
    with trace_mod.span("put", 8) as sp:
        assert sp is None
    tok4 = t.begin_cycle("dense", 1)
    tok4.dispatched()
    with trace_mod.span("put", 8) as sp:        # after ingest closed
        assert sp is None
    assert [(s[0], s[1]) for s in t.recorder.spans()] == [
        (tok.cycle, "put"), (tok4.cycle, "ingest")]


@pytest.mark.parametrize("path,site", [("dense", "step.dense"),
                                       ("window", "step.device")])
def test_a_raising_batch_closes_its_cycle(path, site, tmp_path):
    """An exception that leaves the batch path (no ``dispatched()``, no
    ``aborted()`` ran) closes the thread's open cycle: the ring shows
    where the batch died, and a later ``span`` of code that opens no
    cycle records nothing."""
    opts, body, make, _owed, _kind = SERVED_PATHS[path]
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            f"@app:name('raise_{path}') @app:playback "
            f"@app:execution('tpu'{', ' + opts if opts else ''}) "
            f"@app:trace(sample='1', cycles='8', dir='{tmp_path}') "
            f"@app:faults({site}='crash:after=2') " + body)
        rt.start()
        h = rt.get_input_handler("S")
        with pytest.raises(SimulatedCrashError):
            for i in range(4):
                h.send_batch(make(i))
        assert getattr(trace_mod._open, "tok", None) is None
        tracer = rt.app_context.tracer
        before = tracer.recorder.spans()
        dead = before[-1]
        assert dead[1] == "ingest.aborted" and dead[0] == max(
            s[0] for s in before)
        with trace_mod.span("put", 8) as sp:
            assert sp is None
        assert tracer.recorder.spans() == before
    finally:
        m.shutdown()


def test_jitted_steps_carry_the_device_scopes():
    """The compiled HLO of the dense step, the sharded step and the
    window step names each phase in its ``op_name`` metadata."""
    from siddhi_tpu.ops.dense_nfa import compile_pattern
    from siddhi_tpu.parallel.mesh import ShardedPatternEngine, make_mesh

    def scopes_in(lowered):
        text = lowered.compile().as_text()
        return {sc for sc in trace_mod.DEVICE_SCOPES
                if re.search(r'op_name="[^"]*/' + re.escape(sc) + r'[/"]',
                             text)}

    n = 16
    idx = np.arange(n, dtype=np.int32)
    eng = compile_pattern(PATTERN_BODY, n_partitions=64)
    sk = eng.default_stream
    cols = eng.prepare_cols(sk, {"k": idx.astype(np.int64),
                                 "v": np.linspace(0.0, 20.0, n)})
    dense = {sc for sc in trace_mod.DEVICE_SCOPES if ".dense." in sc}
    assert len(dense) == 8
    rounds = {trace_mod.SCOPE_DENSE_ROUNDS, trace_mod.SCOPE_DENSE_RUN}
    # a chain of plain stream nodes has no count node's part and no
    # logical node's
    dense -= rounds | {trace_mod.SCOPE_DENSE_KLEENE,
                       trace_mod.SCOPE_DENSE_LOGICAL}
    # the jitted programs take the one packed buffer of their lane table
    assert scopes_in(eng.make_step(sk).lower(
        eng.init_state(), eng._pad_lanes(
            eng.lane_table(sk), idx, cols, idx, idx))) == dense
    # the rounds program: wide enough to have wide rounds beside the run
    wide = np.arange(4 * eng.RUN_WIDTH, dtype=np.int32) % 64
    text = eng.make_rounds(sk).lower(
        eng.init_state(), eng._pad_lanes(
            eng.lane_table(sk, offsets=True), wide,
            eng.prepare_cols(sk, {"k": wide.astype(np.int64),
                                  "v": np.linspace(0.0, 20.0, len(wide))}),
            wide, wide, wide[:-1])).compile().as_text()
    for outer in rounds:
        # the wide rounds gather, advance and scatter round by round;
        # the run gathers once and scatters once, and between them this
        # app's chain is the Pallas kernel (a chain outside its class
        # loops over `advance` there)
        for inner in dense - {trace_mod.SCOPE_DENSE_COUNT} - (
                {trace_mod.SCOPE_DENSE_ADVANCE}
                if outer == trace_mod.SCOPE_DENSE_RUN else set()):
            assert re.search(r'op_name="[^"]*/' + re.escape(outer)
                             + r'/[^"]*' + re.escape(inner) + r'[/"]', text)
    sharded = ShardedPatternEngine(
        compile_pattern(PATTERN_BODY, n_partitions=64), make_mesh(4))
    args, _pos = sharded.route(idx, cols, idx)
    assert scopes_in(sharded._step.lower(
        sharded.init_state(), *args)) == dense | {
            trace_mod.SCOPE_SHARD_COUNT_PSUM}
    m = SiddhiManager()
    try:
        # a filter and a having, so that no phase of the step is empty
        rt = m.create_siddhi_app_runtime(
            "@app:name('scopes') @app:playback @app:execution('tpu') "
            "define stream S (symbol string, price float, volume int); "
            "@info(name='q') from S[price > 1.0]#window.length(10) "
            "select symbol, sum(price) * 2.0 as total, avg(volume) as av "
            "having total > 3.0 insert into Out;", register=False)
        weng = rt.query_runtimes["q"].device_runtime.engine
        buf = weng._pad_lanes(
            {"price": np.linspace(0.0, 9.0, n).astype(np.float32),
             "volume": idx}, idx, np.zeros(n, np.int32), n)
        window = {sc for sc in trace_mod.DEVICE_SCOPES if ".window." in sc}
        assert len(window) == 6
        assert scopes_in(weng.make_step().lower(
            weng.init_state(), buf)) == window
        rt.shutdown()
    finally:
        m.shutdown()


def test_a_count_nodes_part_of_advance_has_a_scope_of_its_own():
    """``siddhi.dense.kleene`` nests in ``siddhi.dense.advance`` and
    names the count node's capture, the re-arm at the minimum and the
    via-path clone; a plain node's operations stay ``advance``'s."""
    from siddhi_tpu.ops.dense_nfa import compile_pattern

    eng = compile_pattern(
        "define stream Login (user long, ok int, ip int); "
        "from every e1=Login[ok == 0]<3:> -> e2=Login[ok == 1] "
        "within 10 min select e1[0].ip as firstIp, e1[last].ip as lastIp, "
        "e2.ip as okIp insert into Alerts;", n_partitions=64)
    n = 16
    idx = np.arange(n, dtype=np.int32)
    sk = eng.default_stream
    cols = eng.prepare_cols(sk, {"user": idx.astype(np.int64),
                                 "ok": idx % 2, "ip": idx})
    text = eng.make_step(sk).lower(
        eng.init_state(), eng._pad_lanes(
            eng.lane_table(sk), idx, cols, idx, idx)).as_text(
                debug_info=True)
    nested = (trace_mod.SCOPE_DENSE_ADVANCE + "/"
              + trace_mod.SCOPE_DENSE_KLEENE)
    assert nested in text
    assert trace_mod.SCOPE_DENSE_KLEENE in trace_mod.DEVICE_SCOPES
    # the scope is never opened outside advance
    assert text.count(trace_mod.SCOPE_DENSE_KLEENE) == text.count(nested)
    # and the cumulative sum that finds the head's free lane is under it
    assert re.search(r'cumsum[^\n]*' + re.escape(nested) + r'|'
                     + re.escape(nested) + r'[^\n]*cumsum', text)


def test_a_logical_nodes_part_of_advance_has_a_scope_of_its_own():
    """``siddhi.dense.logical`` nests in ``siddhi.dense.advance`` on
    the programs of BOTH input streams of a two-stream ``and``, and
    names the side's capture, its side bit and the completion; the
    expiry and the filters stay ``advance``'s."""
    from siddhi_tpu.ops.dense_nfa import compile_pattern

    eng = compile_pattern(
        "define stream StockTick (symbol long, price float, volume int); "
        "define stream NewsEvent (symbol long, sentiment float, source int);"
        " from every (t=StockTick[price > 0.0] and "
        "n=NewsEvent[sentiment > 0.0]) within 5 sec select t.price as "
        "price, n.sentiment as sentiment insert into Alerts;",
        n_partitions=64)
    assert eng.stream_keys == ["StockTick", "NewsEvent"]
    n = 16
    idx = np.arange(n, dtype=np.int32)
    nested = (trace_mod.SCOPE_DENSE_ADVANCE + "/"
              + trace_mod.SCOPE_DENSE_LOGICAL)
    assert trace_mod.SCOPE_DENSE_LOGICAL in trace_mod.DEVICE_SCOPES
    for sk, value in (("StockTick", "price"), ("NewsEvent", "sentiment")):
        cols = eng.prepare_cols(sk, {"symbol": idx.astype(np.int64),
                                     value: np.linspace(0.5, 2.0, n)})
        text = eng.make_step(sk).lower(
            eng.init_state(), eng._pad_lanes(
                eng.lane_table(sk), idx, cols, idx, idx)).as_text(
                    debug_info=True)
        assert nested in text
        # the scope is never opened outside advance, and no count node's
        assert text.count(trace_mod.SCOPE_DENSE_LOGICAL) == text.count(nested)
        assert trace_mod.SCOPE_DENSE_KLEENE not in text
        # the side bit's ``or`` into counts is under it
        assert re.search(r'\bor\b[^\n]*' + re.escape(nested) + r'|'
                         + re.escape(nested) + r'[^\n]*\bor\b', text)


# -- prometheus exposition ----------------------------------------------------

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"            # metric name
    r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\",?)*)\})?"
    r" (-?(?:[0-9.]+(?:[eE][+-]?[0-9]+)?|\+Inf|NaN))$")


def assert_valid_exposition(body):
    """Minimal text-format 0.0.4 validator: every line is a well-formed
    comment or sample, each family's # TYPE appears exactly once before
    its samples, histogram series are cumulative and consistent."""
    typed = {}
    seen_families = set()
    hist_buckets = {}
    hist_counts = {}
    for line in body.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split()
            assert parts[1] == "TYPE", line
            family, kind = parts[2], parts[3]
            assert family not in typed, f"duplicate TYPE for {family}"
            assert kind in ("gauge", "counter", "histogram"), line
            typed[family] = kind
            continue
        mm = _SAMPLE.match(line)
        assert mm, f"malformed sample line: {line!r}"
        name, labels, value = mm.group(1), mm.group(2) or "", mm.group(3)
        family = re.sub(r"_(bucket|sum|count)$", "", name)
        base = name if name in typed else family
        assert base in typed, f"sample {name} precedes its # TYPE"
        seen_families.add(base)
        if typed[base] == "histogram":
            if name.endswith("_bucket"):
                series = re.sub(r',?le="[^"]*"', "", labels)
                le = re.search(r'le="([^"]*)"', labels).group(1)
                hist_buckets.setdefault((base, series), []).append(
                    (le, float(value)))
            elif name.endswith("_count"):
                series = labels
                hist_counts[(base, series)] = float(value)
    for key, buckets in hist_buckets.items():
        counts = [c for _le, c in buckets]
        assert counts == sorted(counts), f"non-cumulative buckets: {key}"
        assert buckets[-1][0] == "+Inf", f"missing +Inf bucket: {key}"
        assert hist_counts.get(key) == buckets[-1][1], key
    return seen_families


def test_render_prometheus_shapes():
    h = LatencyHistogram()
    h.record_ms(0.7)
    stats = {
        "io.siddhi.SiddhiApps.a.Siddhi.Streams.S.throughput": 12.5,
        "io.siddhi.SiddhiApps.a.Siddhi.Queries.q.loweredTo": "dense",
        "weird.key": 1,
    }
    body = render_prometheus(
        [("a", stats, [("siddhi_query_latency_ms", {"app": "a",
                                                    "name": "q"}, h)])])
    fams = assert_valid_exposition(body)
    assert "siddhi_streams_throughput" in fams
    assert "siddhi_queries_lowered_to_info" in fams  # string -> _info gauge
    assert "siddhi_metric" in fams                   # catch-all
    assert "siddhi_query_latency_ms" in fams
    assert 'value="dense"' in body


def test_render_prometheus_empty():
    assert render_prometheus([]) == "\n"


def test_service_metrics_and_trace_endpoints():
    svc = SiddhiService()
    svc.start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        # no apps yet: /metrics still serves a valid (empty) page
        resp = urllib.request.urlopen(f"{base}/metrics")
        assert resp.headers["Content-Type"] == CONTENT_TYPE
        req = urllib.request.Request(
            f"{base}/siddhi-artifact-deploy",
            data=device_app("svcapp",
                            trace="@app:trace(sample='1') ").encode(),
            method="POST")
        assert json.load(urllib.request.urlopen(req))["status"] == "OK"
        rt = svc.get_runtime("svcapp")
        h = rt.get_input_handler("S")
        for i in range(4):
            h.send_batch(make_batch(i))
        rt.drain_device_emits()

        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        fams = assert_valid_exposition(body)
        assert "siddhi_stage_duration_ms" in fams
        assert 'app="svcapp"' in body

        tr = json.load(urllib.request.urlopen(
            f"{base}/siddhi-trace/svcapp"))
        assert tr["status"] == "OK" and tr["sample"] == 1
        stages = [s["stage"] for s in tr["trace"]["spans"]]
        assert {"ingest", "step", "emit"} <= set(stages)

        ch = json.load(urllib.request.urlopen(
            f"{base}/siddhi-trace/svcapp?format=chrome"))
        assert ch["traceEvents"] and ch["traceEvents"][0]["ph"] == "X"
    finally:
        svc.stop()


def test_service_404_paths():
    svc = SiddhiService()
    svc.start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        for path in ("/siddhi-trace/nope", "/siddhi-statistics/nope",
                     "/siddhi-pattern-state/nope", "/nonsense"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + path)
            assert ei.value.code == 404, path
    finally:
        svc.stop()


# -- statistics manager reporting loop ----------------------------------------


def _stats_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("stats-")]


def test_reporting_loop_start_stop_idempotent():
    sm = StatisticsManager("looper", interval_s=0.05)
    before = len(_stats_threads())
    sm.start_reporting()
    sm.start_reporting()  # second start is a no-op
    assert len(_stats_threads()) == before + 1
    reporter = sm._reporter
    sm.stop_reporting()
    sm.stop_reporting()  # second stop is a no-op
    reporter.join(timeout=2.0)
    assert not reporter.is_alive(), "reporter thread must exit on stop"
    # restart spins up a fresh generation, old thread stays dead
    sm.start_reporting()
    assert sm._reporter is not reporter
    sm.stop_reporting()
    sm._reporter.join(timeout=2.0)
    assert len(_stats_threads()) == before


def test_reporting_loop_survives_stats_error():
    sm = StatisticsManager("angry", interval_s=0.01)
    sm.throughput["boom"] = None  # stats() raises AttributeError
    sm.start_reporting()
    try:
        time.sleep(0.1)
        assert sm._reporter.is_alive(), "reporter must survive bad stats"
    finally:
        sm.stop_reporting()
        sm._reporter.join(timeout=2.0)


def test_statistics_manager_reset_clears_trackers():
    sm = StatisticsManager("resetme")
    tt = sm.throughput_tracker("S")
    lt = sm.latency_tracker("q")
    tt.add(100)
    lt.mark_in(4)
    lt.mark_out(4)
    sm.reset()
    assert tt.count == 0
    assert lt.batches == 0 and lt.hist.count == 0
    # reset is idempotent and leaves the feed serviceable
    sm.reset()
    assert isinstance(sm.stats(), dict)
