"""The benchmark's readers of a batch's way back, on hand-made records.

``benchmark/layers/way_back.py``, ``benchmark/layers/streams.py`` and
``benchmark/layers/state_bytes.py`` on hand-made rings and
``benchmark/layers/round_trip.py`` on a hand-made ``Trace``
(``benchmark/lib/xplane.py``): known intervals in, known per-batch
values out; a busy plane gives 0; a program without the spans yields
nothing.  And once through ``benchmark/run.py`` itself, as a rehearsal
on the CPU: a traced run of a cell prints every new metric the ring
feeds, and the three children of ``emit`` do not exceed it.
"""

import gc
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

_added = [p for p in (BENCH, os.path.join(BENCH, "layers"))
          if p not in sys.path]
sys.path[:0] = _added   # the readers import program_spans and lib.xplane
try:
    import round_trip   # noqa: E402  (benchmark/layers/round_trip.py)
    import state_bytes  # noqa: E402  (benchmark/layers/state_bytes.py)
    import streams      # noqa: E402  (benchmark/layers/streams.py)
    import way_back     # noqa: E402  (benchmark/layers/way_back.py)
    from lib import xplane  # noqa: E402  (benchmark/lib/xplane.py)
finally:
    for _p in _added:
        sys.path.remove(_p)

MS = 1e-3
RING_METRICS = [*way_back.STAGE_OF, way_back.STAGED_MS,
                way_back.STAGED_SHARE, way_back.MATCH_DELAY]
NAMES = ["events." + n for n in RING_METRICS] + [
    "events.emit_ms_per_batch", "events.no_such_metric"]


def span(cycle, stage, start_ms, end_ms, count=0):
    return (cycle, stage, "dense", start_ms * MS, end_ms * MS, count)


def cycle(cid, t, staged=0.0, poll=False, back=True, new=True):
    """One batch sent at ``t`` ms: interning from ``t + 0.5``, ingest to
    ``t + 7`` (two dispatches of half a millisecond, a poll of 2 ms
    where asked), the gate left staged for ``staged`` ms and then
    waited on for 3, and the way back: 1 ms of fetch, 1.5 of build, 0.5
    of delivery.  ``new`` False: the ring of a program from before
    ``build`` and ``poll``."""
    s = [
        span(cid, "intern", t + 0.5, t + 2.5, 100),
        span(cid, "convert", t + 2.5, t + 3.0, 100),
        span(cid, "dispatch", t + 3.0, t + 3.5, 1),
        span(cid, "dispatch", t + 4.0, t + 4.5, 1),
    ]
    if poll and new:
        s.append(span(cid, "poll", t + 4.5, t + 6.5, 1))
    g = t + 7.0 + staged
    s += [span(cid, "ingest", t + 2.5, t + 7.0, 100),
          span(cid, "step", g, g + 3.0, 100)]
    if back:
        s.append(span(cid, "fetch", g + 3.0, g + 4.0, 4096))
        if new:
            s += [span(cid, "build", g + 4.0, g + 5.5, 7),
                  span(cid, "deliver", g + 5.5, g + 6.0, 7)]
        else:
            s.append(span(cid, "deliver", g + 4.0, g + 6.0, 7))
        s.append(span(cid, "emit", g + 3.0, g + 6.0, 7))
    return s


def make_run(ring, n_sends=4, clean=3, wanted=NAMES):
    sends = [(100 * MS * n, (100 * n + 40) * MS) for n in range(n_sends)]
    window = types.SimpleNamespace(t0=0.0, sends=sends, clean=clean)
    return types.SimpleNamespace(wanted=wanted, window=window,
                                 ring_spans=ring)


def test_known_spans_give_known_values():
    # batch 3 is the profiler's: three clean batches are read; the
    # first gate is finished inline, the next two left staged for 20 ms;
    # the second batch emits nothing; the third polls
    ring = (cycle(1, -100) + cycle(2, 0)
            + cycle(3, 100, staged=20.0, back=False)
            + cycle(4, 200, staged=20.0, poll=True) + cycle(5, 300))
    got = way_back.read(make_run(ring))
    assert got == {
        "events.fetch_ms_per_batch": pytest.approx(2 / 3),
        "events.d2h_bytes_per_batch": pytest.approx(2 * 4096 / 3),
        "events.build_ms_per_batch": pytest.approx(1.0),
        "events.deliver_ms_per_batch": pytest.approx(1 / 3),
        "events.dispatch_ms_per_batch": pytest.approx(1.0),
        "events.poll_ms_per_batch": pytest.approx(2 / 3),
        "events.staged_ms_per_batch": pytest.approx(40 / 3),
        "events.staged_share": pytest.approx(200 / 3),
        # first span (``intern`` at t + 0.5) to the end of ``emit``:
        # 12.5 ms inline, 32.5 ms staged; the median of the two
        "events.match_delay_ms_p50": pytest.approx(22.5),
    }


def test_inline_gates_read_zero_and_not_nothing():
    ring = [s for n in range(4) for s in cycle(2 + n, 100 * n)]
    got = way_back.read(make_run(ring))
    assert got["events.staged_ms_per_batch"] == 0.0
    assert got["events.staged_share"] == 0.0
    assert got["events.match_delay_ms_p50"] == pytest.approx(12.5)
    assert "events.poll_ms_per_batch" not in got


def test_a_poll_outside_the_clean_batches_reads_zero():
    # the ring holds a poll, in the batch the profiler took: the clean
    # batches had none, and the metric says so
    ring = ([s for n in range(3) for s in cycle(2 + n, 100 * n)]
            + cycle(5, 300, poll=True))
    got = way_back.read(make_run(ring))
    assert got["events.poll_ms_per_batch"] == 0.0


def test_a_program_without_the_spans_yields_nothing_for_them():
    old = [s for n in range(4) for s in cycle(2 + n, 100 * n, poll=True,
                                              new=False)]
    got = way_back.read(make_run(old))
    assert "events.build_ms_per_batch" not in got
    assert "events.poll_ms_per_batch" not in got
    # what it does record is read: ``deliver`` there is build and
    # delivery in one
    assert got["events.deliver_ms_per_batch"] == pytest.approx(2.0)
    assert got["events.fetch_ms_per_batch"] == pytest.approx(1.0)
    assert got["events.staged_share"] == 0.0
    bare = [s for s in old if s[1] in ("ingest", "emit")]
    assert way_back.read(make_run(bare)) == {
        "events.match_delay_ms_p50": pytest.approx(10.5)}
    assert way_back.read(make_run([])) == {}


def test_an_evicting_ring_reads_only_whole_cycles():
    # the ring lost the first batch's way in: its ``emit`` has no start
    # to count from, the other two have
    ring = cycle(2, 0)[5:] + cycle(3, 100) + cycle(4, 200, staged=10.0)
    got = way_back.read(make_run(ring))
    assert got["events.match_delay_ms_p50"] == pytest.approx(
        (12.5 + 22.5) / 2)


# -- the second stream's batches (layers/streams.py) ---------------------------

STREAM2 = ["events." + streams.ROWS, "events." + streams.EMIT_MS,
           "events.rows_per_batch"]


def stream_of(cid, t, place):
    """The dense engine's ``stream`` count of a cycle: a tuple of no
    width inside its ``ingest``."""
    return span(cid, "stream", t + 3.0, t + 3.0, place)


def test_the_second_streams_cycles_are_read_alone():
    # four clean batches: a tick batch that delivered 7 rows, a news
    # batch that delivered 7 (1 ms of fetch, 1.5 of build, 0.5 of
    # delivery), a news batch that owed nothing, a tick batch; the
    # profiler's batch is a news batch too, and is not read
    ring = (cycle(1, -100) + [stream_of(1, -100, 1)]
            + cycle(2, 0) + [stream_of(2, 0, 0)]
            + cycle(3, 100) + [stream_of(3, 100, 1)]
            + cycle(4, 200, back=False) + [stream_of(4, 200, 1)]
            + cycle(5, 300) + [stream_of(5, 300, 0)]
            + cycle(6, 400) + [stream_of(6, 400, 1)])
    got = streams.read(make_run(ring, n_sends=5, clean=4, wanted=STREAM2))
    assert got == {
        "events.stream2_rows_per_batch": pytest.approx(7 / 2),
        "events.stream2_emit_ms_per_batch": pytest.approx(3.0 / 2)}
    # a reader asked for neither reads nothing
    assert streams.read(make_run(ring, wanted=NAMES)) == {}


def test_a_window_of_one_stream_and_an_older_ring_yield_nothing():
    # every batch on the first stream: no cycle to average over
    first = [s for n in range(4) for s in cycle(2 + n, 100 * n)
             + [stream_of(2 + n, 100 * n, 0)]]
    assert streams.read(make_run(first, wanted=STREAM2)) == {}
    # a program from before the count (an older commit): nothing, never 0
    old = [s for n in range(4) for s in cycle(2 + n, 100 * n)]
    assert streams.read(make_run(old, wanted=STREAM2)) == {}
    assert streams.read(make_run([], wanted=STREAM2)) == {}


def test_second_stream_cycles_that_owe_nothing_read_zero():
    ring = [s for n in range(4) for s in cycle(2 + n, 100 * n, back=False)
            + [stream_of(2 + n, 100 * n, n % 2)]]
    assert streams.read(make_run(ring, wanted=STREAM2)) == {
        "events.stream2_rows_per_batch": 0.0,
        "events.stream2_emit_ms_per_batch": 0.0}


# -- the resident bytes a batch's steps gather (layers/state_bytes.py) ---------

GATHERED = ["events." + state_bytes.NAME, "events.rows_per_batch"]


def bytes_of(cid, t, lanes, width=512):
    """The dense engine's ``state_bytes`` count of a cycle: a tuple of
    no width inside its ``ingest``, ``lanes`` rows of ``width`` words."""
    return span(cid, "state_bytes", t + 3.0, t + 3.0, lanes * width * 4)


def test_the_gathered_bytes_are_the_mean_over_the_clean_batches():
    # three clean batches: two of 1,024 + 512 lanes, one of 1,024 alone;
    # the batch before the window and the profiler's are not read
    ring = (cycle(1, -100) + [bytes_of(1, -100, 9_999)]
            + cycle(2, 0) + [bytes_of(2, 0, 1_536)]
            + cycle(3, 100, back=False) + [bytes_of(3, 100, 1_024)]
            + cycle(4, 200) + [bytes_of(4, 200, 1_536)]
            + cycle(5, 300) + [bytes_of(5, 300, 9_999)])
    assert state_bytes.read(make_run(ring, wanted=GATHERED)) == {
        "events.gathered_bytes_per_batch": pytest.approx(
            (1_536 + 1_024 + 1_536) * 2_048 / 3)}
    # the flagship's row is half as wide: half the bytes a lane
    narrow = [s for n in range(3) for s in cycle(2 + n, 100 * n)
              + [bytes_of(2 + n, 100 * n, 1_536, width=256)]]
    assert state_bytes.read(make_run(narrow, wanted=GATHERED)) == {
        "events.gathered_bytes_per_batch": 1_536 * 1_024}
    # a reader asked for another name reads nothing
    assert state_bytes.read(make_run(ring, wanted=NAMES)) == {}


def test_a_ring_without_the_count_yields_no_gathered_bytes():
    # a program from before the count (the parent): nothing, never 0
    old = [s for n in range(4) for s in cycle(2 + n, 100 * n)]
    assert state_bytes.read(make_run(old, wanted=GATHERED)) == {}
    assert state_bytes.read(make_run([], wanted=GATHERED)) == {}


# -- the shared clock ----------------------------------------------------------

US = 1_000   # the trace's clock is nanoseconds
TRIP = ["rows.launch_lag_ms_per_batch", "rows.gate_return_ms_per_batch",
        "rows.fetch_ms_per_batch"]


def op(start_us, end_us):
    return (start_us * US, end_us * US, "fusion", None)


def host(name, start_us, end_us):
    return (start_us * US, end_us * US, name)


def trip(device, spans, batches=2):
    mark = host(xplane.MARK, 0, 10_000)
    return types.SimpleNamespace(
        wanted=TRIP, trace=xplane.Trace(device, [mark] + spans, batches))


def test_known_intervals_give_known_round_trips():
    # two inline batches: dispatched at 1,000 and 5,000 us on an idle
    # plane that starts 300 and 500 us later and runs 1,000 us; the host
    # has the gate 200 and 400 us after the last operation ended
    device = {"/device:TPU:0": [op(1_300, 1_800), op(1_800, 2_300),
                                op(5_500, 6_500)]}
    spans = [host("siddhi.dispatch", 1_000, 1_100),
             host("siddhi.step_wait", 1_150, 2_500),
             host("siddhi.dispatch", 5_000, 5_100),
             host("siddhi.step_wait", 5_150, 6_900),
             host("siddhi.fetch", 6_900, 7_400)]
    got = round_trip.read(trip(device, spans))
    assert got == {
        "rows.launch_lag_ms_per_batch": pytest.approx((0.3 + 0.5) / 2),
        "rows.gate_return_ms_per_batch": pytest.approx((0.2 + 0.4) / 2)}


def test_a_busy_plane_gives_zero():
    # one batch in flight: the second dispatch comes while the first
    # step runs, and the first gate resolves while the second step does
    device = {"/device:TPU:0": [op(1_300, 4_000), op(4_000, 7_000)]}
    spans = [host("siddhi.dispatch", 1_000, 1_100),
             host("siddhi.dispatch", 3_000, 3_100),
             host("siddhi.step_wait", 3_200, 4_300)]
    got = round_trip.read(trip(device, spans))
    assert got["rows.launch_lag_ms_per_batch"] == pytest.approx(0.3 / 2)
    assert got["rows.gate_return_ms_per_batch"] == 0.0


def test_a_wait_that_began_after_the_device_ended_is_all_return():
    device = {"/device:TPU:0": [op(1_000, 2_000)]}
    spans = [host("siddhi.step_wait", 2_400, 3_000)]
    got = round_trip.read(trip(device, spans, batches=1))
    assert got == {"rows.gate_return_ms_per_batch": pytest.approx(0.6)}


def test_planes_are_averaged_and_spans_outside_the_mark_left_out():
    device = {"/device:TPU:0": [op(1_200, 2_000)],
              "/device:TPU:1": [op(1_400, 2_200)]}
    spans = [host("siddhi.dispatch", 1_000, 1_100),
             host("siddhi.step_wait", 1_100, 2_400),
             host("siddhi.dispatch", 11_000, 11_100),     # past the mark
             host("siddhi.step_wait", 9_900, 10_100)]     # straddles it
    got = round_trip.read(trip(device, spans, batches=1))
    assert got == {
        "rows.launch_lag_ms_per_batch": pytest.approx((0.2 + 0.4) / 2),
        "rows.gate_return_ms_per_batch": pytest.approx((0.4 + 0.2) / 2)}


def test_without_annotations_or_a_device_plane_nothing_is_read():
    device = {"/device:TPU:0": [op(1_200, 2_000)]}
    assert round_trip.read(trip(device, [host("siddhi.put", 0, 10)])) == {}
    assert round_trip.read(trip({}, [
        host("siddhi.dispatch", 1_000, 1_100)])) == {}
    assert round_trip.read(types.SimpleNamespace(wanted=TRIP,
                                                 trace=None)) == {}


# -- through the harness -------------------------------------------------------

def test_a_traced_rehearsal_prints_the_ring_fed_metrics(capsys):
    """``benchmark/run.py`` finds the two readers by glob; on the CPU
    the trace holds no device plane, so only what the ring feeds is
    owed.  (No poll in two seconds: it comes every 256th step.)"""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = "cse_groupby.saturated"
    owed = {m["name"] for m in spec["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["source"] == "program_span"
            and m["name"].split(".", 1)[-1] in RING_METRICS}
    assert len(owed) == 6
    sys.path[:0] = [BENCH]
    try:
        import run as bench_run   # noqa: E402  (benchmark/run.py)
        assert bench_run.main([
            "--workload", cell, "--seed", str(2**31 + 37), "--seconds", "2",
            "--trace", "1", "--rehearsal"]) == 0
    finally:
        sys.path.remove(BENCH)
        gc.unfreeze()   # run.py freezes the heap ahead of its window
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert owed <= set(got)
    children = sum(got[f"rows.{part}_ms_per_batch"]
                   for part in ("fetch", "build", "deliver"))
    assert 0 < children <= got["rows.emit_ms_per_batch"]
    assert got["rows.d2h_bytes_per_batch"] > 0
    assert got["rows.match_delay_ms_p50"] > got["rows.emit_ms_per_batch"]
