"""The benchmark's plain reference of ``cse_groupby`` tied to the engine.

``benchmark/references/groupby_length_batch.py`` imports nothing of the
program; here the configuration's own app runs on the HOST engine over
the cell's generator at the rehearsal size, and every number the
reference compares comes out at 0 or under its limit.  One altered
``price``, one dropped row and one swapped pair of rows each make it
not correct.
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


@pytest.fixture(scope="module")
def bench():
    """The configuration, its schedule, the reference, and the rows the
    host engine emits for the warm-up and ``N_SENT`` window batches."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added   # the reference imports lib.check
    try:
        ref = load(os.path.join(
            BENCH, "references", "groupby_length_batch.py"), "_ref_groupby")
        gen = load(os.path.join(BENCH, "generators", "cse_ticks.py"),
                   "_gen_cse_ticks")
    finally:
        for p in added:
            sys.path.remove(p)
    with open(os.path.join(BENCH, "configs", "cse_groupby.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "cse_ticks_saturated.json")) as f:
        traffic = json.load(f)
    schedule = gen.make(2**31 + 5, config, traffic, True)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime("@app:playback " + config["app"])
        got = []
        rt.add_callback(config["output"], lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        assert rt.lowering() == {"q0": "host"}
        h = rt.get_input_handler(config["stream"])
        for n in range(-schedule.warmup, N_SENT):
            h.send_batch(schedule.batch(n))
        rt.shutdown()
    finally:
        m.shutdown()
    return types.SimpleNamespace(
        ref=ref, spec=config["reference"], schedule=schedule, rows=got)


def collector_of(bench, rows):
    """What ``lib/deploy.py``'s collector would hold of ``rows``: all of
    them kept, the count of rows stamped in each batch."""
    cols = {name: np.asarray([r[i + 1] for r in rows], dtype=dtype)
            for i, (name, dtype) in enumerate((
                ("symbol", object), ("total", np.float64),
                ("avgVolume", np.float64), ("timestamp", np.int64)))}
    cols["_ts"] = np.asarray([r[0] for r in rows], dtype=np.int64)
    cols["_n"] = bench.schedule.batch_of(cols["_ts"])
    counts = collections.Counter(cols["_n"].tolist())
    return types.SimpleNamespace(rows=lambda: cols, counts=counts)


def judge(bench, rows):
    bad, compared = bench.ref.reference(
        bench.spec, bench.schedule, collector_of(bench, rows), N_SENT,
        0, True)
    return bad, {name.split(" (")[0]: (value, limit)
                 for name, value, limit in compared}


def test_the_host_engine_agrees_with_the_reference(bench):
    bad, compared = judge(bench, bench.rows)
    assert not bad
    assert len(compared) == 7
    for name, (value, limit) in compared.items():
        assert value <= limit, (name, value, limit)
    worst, limit = compared["worst relative error of sum(price), avg(volume)"]
    assert limit == pytest.approx(64 * 2.0**-23) and worst < limit / 8
    # every batch of the window was checked in full, and the pane that
    # straddles two batches on both of its sides
    assert bench.schedule.batch_events % bench.spec["length"] != 0


def window_row(bench, k=40):
    """Index of a row stamped well inside the window."""
    first = next(i for i, r in enumerate(bench.rows)
                 if bench.schedule.batch_of(r[0]) >= 1)
    return first + k


def test_an_altered_price_is_not_correct(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    rows[i] = (rows[i][0], rows[i][1], rows[i][2] * (1 + 1e-4), *rows[i][3:])
    bad, compared = judge(bench, rows)
    value, limit = compared[
        "worst relative error of sum(price), avg(volume)"]
    assert value > limit
    assert bad == {int(bench.schedule.batch_of(rows[i][0]))}


def test_a_dropped_row_is_not_correct(bench):
    rows = list(bench.rows)
    gone = rows.pop(window_row(bench))
    bad, compared = judge(bench, rows)
    n = int(bench.schedule.batch_of(gone[0]))
    assert bad == {n}
    assert compared["batches whose row count is not the count the seed "
                    "owes"] == (1, 0)
    # its batch is no longer whole in what was kept: it is not compared
    # row by row, the count alone condemns it


def test_a_swapped_pair_is_not_correct(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    assert bench.schedule.batch_of(rows[i][0]) == bench.schedule.batch_of(
        rows[i + 1][0])
    rows[i], rows[i + 1] = rows[i + 1], rows[i]
    bad, compared = judge(bench, rows)
    assert compared["rows out of order"] == (1, 0)
    assert bad == {int(bench.schedule.batch_of(rows[i][0]))}


def test_a_wrong_symbol_and_a_row_not_owed(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    rows[i] = (rows[i][0], "S_other", *rows[i][2:])
    j = i + 3
    rows[j] = (*rows[j][:4], rows[j][4] + 10**9)   # names no event
    bad, compared = judge(bench, rows)
    assert compared["rows whose symbol or event timestamp differs"] == (1, 0)
    assert compared["rows delivered and not owed"] == (1, 0)
    assert compared["rows owed and not delivered"] == (1, 0)
    assert bad
