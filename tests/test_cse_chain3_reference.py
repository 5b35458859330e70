"""The benchmark's plain reference of ``cse_chain3`` tied to the engine.

``benchmark/references/filter_window_filter.py`` imports nothing of the
program; here the configuration's own three-query app runs on the HOST
engine over the cell's generator at the rehearsal size, and every number
the reference compares comes out at 0 or under its limit: the window is
carried over every batch boundary.  A hand-made stream holds a total
inside the free band and a batch that owes no row.  One altered
``total``, one dropped row, one row of a tick the head dropped and one
swapped pair each make it not correct.
"""

import collections
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N_SENT = 12


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def host_rows(config, schedule, n_sent):
    """What the host engine emits for the warm-up and ``n_sent`` window
    batches: ``(event timestamp, total, avgVolume)``."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime("@app:playback " + config["app"])
        got = []
        rt.add_callback(config["output"], lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        assert set(rt.lowering().values()) == {"host"}
        h = rt.get_input_handler(config["stream"])
        for n in range(-schedule.warmup, n_sent):
            h.send_batch(schedule.batch(n))
        rt.shutdown()
    finally:
        m.shutdown()
    return got


def cell_files():
    """The cell's configuration and traffic mix, its generator and its
    reference (``tests/test_fused_graph.py`` deploys the same files)."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added   # the reference imports lib.check
    try:
        ref = load(os.path.join(
            BENCH, "references", "filter_window_filter.py"), "_ref_chain3")
        gen = load(os.path.join(BENCH, "generators", "cse_ticks.py"),
                   "_gen_cse_ticks")
    finally:
        for p in added:
            sys.path.remove(p)
    with open(os.path.join(BENCH, "configs", "cse_chain3.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "cse_ticks_saturated.json")) as f:
        traffic = json.load(f)
    return config, traffic, gen, ref


@pytest.fixture(scope="module")
def bench():
    """The configuration, its schedule, the reference, and the host
    engine's rows."""
    config, traffic, gen, ref = cell_files()
    schedule = gen.make(2**31 + 39, config, traffic, True)
    return types.SimpleNamespace(
        ref=ref, gen=gen, config=config, spec=config["reference"],
        schedule=schedule, rows=host_rows(config, schedule, N_SENT))


def collector_of(schedule, rows):
    """What ``lib/deploy.py``'s collector would hold of ``rows``: all of
    them kept, the count of rows stamped in each batch."""
    cols = {"total": np.asarray([r[1] for r in rows], dtype=np.float64),
            "avgVolume": np.asarray([r[2] for r in rows], dtype=np.float64),
            "_ts": np.asarray([r[0] for r in rows], dtype=np.int64)}
    cols["_n"] = schedule.batch_of(cols["_ts"])
    counts = collections.Counter(cols["_n"].tolist())
    return types.SimpleNamespace(
        rows=lambda: cols if rows else None, counts=counts)


def judge(bench, rows, schedule=None, n_sent=N_SENT, spec=None):
    schedule = schedule or bench.schedule
    bad, compared = bench.ref.reference(
        spec or bench.spec, schedule, collector_of(schedule, rows), n_sent,
        0, True)
    return bad, {name.split(" (")[0]: (value, limit)
                 for name, value, limit in compared}


ERR = "worst relative error of sum(price), avg(volume)"
FREE = ("windows within 64 eps32 of the threshold, free to stand on either "
        "side")
UNEVEN = "batches whose row count is outside what the seed owes"


def test_the_host_engine_agrees_with_the_reference(bench):
    bad, compared = judge(bench, bench.rows)
    assert not bad
    assert len(compared) == 7
    for name, (value, limit) in compared.items():
        assert value <= limit, (name, value, limit)
    worst, limit = compared[ERR]
    assert limit == pytest.approx(64 * 2.0**-23) and worst < limit / 8
    # a third of the events owe a row, in every batch of the window
    per_batch = collections.Counter(
        bench.schedule.batch_of(np.asarray([r[0] for r in bench.rows])))
    B = bench.schedule.batch_events
    assert all(0.2 * B < per_batch[n] < 0.45 * B for n in range(N_SENT))


def test_the_window_is_carried_across_a_batch_boundary(bench):
    """The first kept tick of a batch closes a window whose other nine
    ticks lie in the batch before: the reference re-makes that batch,
    and a reference that began every batch with an empty window would
    owe that tick nothing (a lone price is under the threshold)."""
    spec, schedule = bench.spec, bench.schedule
    ref = bench.ref.owed(spec, schedule, 3)
    prev = schedule.batch(2).columns["price"]
    cur = schedule.batch(3).columns["price"]
    nine = prev[prev < 700][-9:].astype(np.float64)
    first = cur[cur < 700][0].astype(np.float64)
    assert ref["total"][0] == pytest.approx(nine.sum() + first, rel=1e-12)
    assert ref["total"][0] > 2000 > first
    # the host engine's rows of batch 3 begin inside those first windows
    owed_ts = ref["_ts"][ref["class"] == bench.ref.OWED]
    got_ts = [r[0] for r in bench.rows if schedule.batch_of(r[0]) == 3]
    assert got_ts == owed_ts.tolist()
    assert owed_ts[0] < ref["_ts"][9]   # one of the straddling windows


def crafted(bench):
    """Three 32-row batches under one warm-up batch: every price 400.0
    (every full window totals 4,000.0 exactly: inside the free band),
    then every price 100.0 (no window reaches the threshold), then
    prices the head drops but for twelve at 650.0."""
    def ring_batch(prices):
        return {"symbol": np.asarray(["S0"] * 32, dtype=object),
                "price": np.asarray(prices, dtype=np.float32),
                "volume": np.arange(32, dtype=np.int32)}

    last = [800.0] * 20 + [650.0] * 12
    ring = [ring_batch([400.0] * 32), ring_batch([400.0] * 32),
            ring_batch([100.0] * 32), ring_batch(last)]
    return bench.gen.RingSchedule(bench.config["stream"], ring, 1, 0)


def test_a_total_inside_the_free_band_may_stand_on_either_side(bench):
    schedule = crafted(bench)
    rows = host_rows(bench.config, schedule, 3)
    loose = dict(bench.spec, free_share=1.0)
    ref0 = bench.ref.owed(bench.spec, schedule, 0)
    assert (ref0["total"] == 4000.0).all()
    assert (ref0["class"] == bench.ref.FREE).all()
    # the host engine holds 4000.0 > 4000.0 false: no row of batch 0
    assert not [r for r in rows if schedule.batch_of(r[0]) == 0]
    bad, compared = judge(bench, rows, schedule, 3, loose)
    assert not bad and compared[UNEVEN] == (0, 0)
    # a float32 sum that came out a bit above owes the row, and may
    with_free = sorted(rows + [(int(ref0["_ts"][5]), 4000.0005,
                                float(ref0["avgVolume"][5]))])
    bad, compared = judge(bench, with_free, schedule, 3, loose)
    assert not bad
    assert compared[ERR][0] <= compared[ERR][1]
    # ... but the band is no hiding place: so many free windows are
    # over the configuration's own limit
    bad, compared = judge(bench, rows, schedule, 3)
    value, limit = compared[FREE]
    assert value == 32 and limit == 1 and value > limit


def test_a_batch_that_owes_no_row(bench):
    schedule = crafted(bench)
    rows = host_rows(bench.config, schedule, 3)
    loose = dict(bench.spec, free_share=1.0)
    ref1 = bench.ref.owed(bench.spec, schedule, 1)
    # nine windows still hold a 400.0: the first is 9 x 400 + 100
    assert ref1["total"][0] == 3700.0 and ref1["total"][-1] == 1000.0
    assert (ref1["class"] == bench.ref.FORBIDDEN).all()
    assert not [r for r in rows if schedule.batch_of(r[0]) == 1]
    # batch 2: the head keeps twelve ticks; the last three windows are
    # ten 650.0s, 6,500 each, the nine before them still hold 100.0s
    ref2 = bench.ref.owed(bench.spec, schedule, 2)
    assert len(ref2["_ts"]) == 12
    assert ref2["total"].tolist() == [
        1000.0 + 550.0 * k for k in range(1, 10)] + [6500.0] * 3
    got = [r for r in rows if schedule.batch_of(r[0]) == 2]
    assert [r[0] for r in got] == ref2["_ts"][
        ref2["class"] == bench.ref.OWED].tolist()
    assert len(got) == 7    # 4,300 at the sixth 650.0 and on
    bad, _compared = judge(bench, rows, schedule, 3, loose)
    assert not bad
    # a row in the batch that owes none is one too many
    extra = sorted(rows + [(int(ref1["_ts"][4]), 4100.0, 3.0)])
    bad, compared = judge(bench, extra, schedule, 3, loose)
    assert bad == {1} and compared[UNEVEN] == (1, 0)


def window_row(bench, k=40):
    """Index of a row stamped well inside the window."""
    first = next(i for i, r in enumerate(bench.rows)
                 if bench.schedule.batch_of(r[0]) >= 1)
    return first + k


def test_an_altered_total_is_not_correct(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    rows[i] = (rows[i][0], rows[i][1] * (1 + 1e-4), rows[i][2])
    bad, compared = judge(bench, rows)
    value, limit = compared[ERR]
    assert value > limit
    assert bad == {int(bench.schedule.batch_of(rows[i][0]))}


def test_a_dropped_row_is_not_correct(bench):
    rows = list(bench.rows)
    gone = rows.pop(window_row(bench))
    bad, compared = judge(bench, rows)
    assert bad == {int(bench.schedule.batch_of(gone[0]))}
    assert compared[UNEVEN] == (1, 0)


def test_a_row_of_a_dropped_tick_and_a_swapped_pair(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    n = int(bench.schedule.batch_of(rows[i][0]))
    batch = bench.schedule.batch(n)
    dropped = int(batch.timestamps[np.flatnonzero(
        batch.columns["price"] >= 700)[3]])
    # in place of an owed row, so that the batch's count still fits
    rows[i] = (dropped, rows[i][1], rows[i][2])
    bad, compared = judge(bench, rows)
    assert compared["rows delivered and forbidden"] == (1, 0)
    assert compared["rows owed and not delivered"] == (1, 0)
    assert bad == {n}
    rows = list(bench.rows)
    assert bench.schedule.batch_of(rows[i][0]) == bench.schedule.batch_of(
        rows[i + 1][0])
    rows[i], rows[i + 1] = rows[i + 1], rows[i]
    bad, compared = judge(bench, rows)
    assert compared["rows out of order"] == (1, 0)
    assert bad == {n}
