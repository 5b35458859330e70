"""The benchmark's plain reference of ``iot32_1250k`` tied to the engine.

``benchmark/references/pattern_chain_band.py`` imports nothing of the
program; here the configuration's own app runs on the HOST engine and
the chain of the reference owes exactly the rows it emits: on hand-made
logs (each script of the traffic, the missed beat, a reading equal to
its threshold, the edges of the head's band, an arm that ``within``
drops 30 states deep, the last state at ``within`` and one millisecond
past it, two arms of one device alive at once, a device twice in a
batch), on seeded logs, and, through ``reference()`` itself, on the
cell's generator at 4,096 devices.  A row dropped, a ``t1`` one ulp off,
a ``t32`` one ulp off, a stray row of a swept device and one device's
rows out of order each make it not correct.
"""

import collections
import itertools
import types

import numpy as np
import pytest

from iot32_bench import (CONFIG, GEN, REF, SPEC, TIER1, head_of, make_batch,
                         run_app)

WITHIN, STATES, BAND = SPEC["within_ms"], SPEC["states"], SPEC["head_band"]
HEAD, HEAD2 = "head", "head2"     # a case's head readings, by its device


def host_rows(batches):
    """The configuration's app on the host engine over ``batches``."""
    got, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    return got


def rising(lo, hi, step=10, t=0):
    """Readings ``lo + 0.5 .. hi - 0.5``, ``step`` ms apart from ``t``."""
    return [(j + 0.5, t + step * i) for i, j in enumerate(range(lo, hi))]


# name -> [(temp, ms since the case began)]
CASES = {
    "the_silent_script": [(HEAD, 0)] + rising(1, 32, t=10) + [
        (GEN.QUIET, 320), (GEN.QUIET, 330)],
    "the_rising_script": [(HEAD, 0)] + rising(1, 34, t=10),
    "the_missed_beat": [(HEAD, 0)] + rising(1, 7, t=10) + [(6.5, 70)]
    + rising(7, 33, t=80),
    "the_late_script": [(GEN.QUIET, 10 * i) for i in range(4)]
    + [(HEAD, 40)] + rising(1, 30, t=50),
    "a_reading_equal_to_its_threshold": [(HEAD, 0), (1.5, 10), (2.0, 20)]
    + rising(2, 32, t=30),
    "a_reading_over_several_thresholds_advances_one_state":
    [(HEAD, 0), (31.5, 10)] + rising(2, 32, t=20),
    "one_on_the_bands_upper_edge_opens_no_arm": [(1.0, 0)]
    + rising(1, 33, t=10),
    "zero_on_the_bands_lower_edge_opens_no_arm": [(0.0, 0)]
    + rising(1, 33, t=10),
    "an_arm_that_within_drops_at_depth_30": [(HEAD, 0)] + rising(1, 30, t=10)
    + [(30.5, WITHIN + 1), (31.5, WITHIN + 2)],
    "the_last_state_at_within": [(HEAD, 0)] + rising(1, 31, t=10)
    + [(31.5, WITHIN)],
    "the_last_state_past_within": [(HEAD, 0)] + rising(1, 31, t=10)
    + [(31.5, WITHIN + 1)],
    "two_arms_of_one_device_alive_at_once": [(HEAD, 0), (1.5, 10),
                                             (HEAD2, 20)]
    + rising(2, 34, t=30),
    "every_arms_again_after_a_row": [(HEAD, 0)] + rising(1, 32, t=10)
    + [(HEAD2, 400)] + rising(1, 32, t=410),
    "a_device_twice_in_a_batch": [(HEAD, 0), (1.5, 0), (2.5, 10), (3.5, 10)]
    + rising(4, 32, t=20),
}
for _seed in range(8):
    # a rising run disturbed: a reading again, a reading at its
    # threshold, a second head, a gap to within and past it
    _rng = np.random.default_rng(600 + _seed)
    _log, _t, _j, _heads = [(HEAD, 0)], 0, 1, [HEAD2]
    while _j < 36:
        _t += int(_rng.choice([0, 1, 10, 150_000, WITHIN],
                              p=[.1, .1, .75, .03, .02]))
        what = _rng.choice(["up", "up", "up", "up", "again", "at", "head"])
        if what == "up":
            _log.append((_j + 0.5, _t))
            _j += 1
        elif what == "again":   # (0.5 would be a head reading)
            _log.append((max(_j - 0.5, 1.5), _t))
        elif what == "at":
            _log.append((float(_j), _t))
        elif _heads:
            _log.append((_heads.pop(), _t))
    CASES[f"seeded_log_{_seed}"] = _log
# rows owed, as (which head, e32.temp)
ROWS_OWED = {
    "the_silent_script": [(HEAD, 31.5)],
    "the_rising_script": [(HEAD, 31.5)],
    "the_missed_beat": [(HEAD, 31.5)],
    "the_late_script": [],
    "a_reading_equal_to_its_threshold": [(HEAD, 31.5)],
    "a_reading_over_several_thresholds_advances_one_state": [(HEAD, 31.5)],
    "one_on_the_bands_upper_edge_opens_no_arm": [],
    "zero_on_the_bands_lower_edge_opens_no_arm": [],
    "an_arm_that_within_drops_at_depth_30": [],
    "the_last_state_at_within": [(HEAD, 31.5)],
    "the_last_state_past_within": [],
    "two_arms_of_one_device_alive_at_once": [(HEAD, 31.5), (HEAD2, 32.5)],
    "every_arms_again_after_a_row": [(HEAD, 31.5), (HEAD2, 31.5)],
    "a_device_twice_in_a_batch": [(HEAD, 31.5)],
}
T0 = 1_000


def events_of(name):
    """``(n, ts, temp)`` of a case's events, as ``_band_rows`` takes
    them, under the case's own device.  ``n``, which the reference
    stamps a row with, is the event's timestamp."""
    device = 1 + list(CASES).index(name)
    heads = {HEAD: head_of(device), HEAD2: head_of(device, 1)}
    return device, heads, [
        (T0 + at, T0 + at, float(np.float32(heads.get(temp, temp))))
        for temp, at in CASES[name]]


@pytest.fixture(scope="module")
def host_by_device():
    """Every case through ONE host runtime, a device each, in the order
    of their timestamps; events of one timestamp share a batch, so a
    device comes twice in some."""
    evs = sorted((ts, i, device, temp) for name in CASES
                 for device, _heads, es in [events_of(name)]
                 for i, (_n, ts, temp) in enumerate(es))
    batches = []
    for ts, run in itertools.groupby(evs, key=lambda e: e[0]):
        run = list(run)
        batches.append(make_batch([e[2] for e in run], [e[3] for e in run],
                                  ts))
    assert max(len(b.timestamps) for b in batches) > len(CASES)
    by_device = collections.defaultdict(list)
    for ts, t1, t32 in host_rows(batches):
        device = int(np.rint(float(t1) * (1 << GEN.FRAC_BITS))) - 1
        by_device[device].append((ts, ts, float(t1), float(t32)))
    return by_device


@pytest.mark.parametrize("name", list(CASES))
def test_the_reference_owes_what_the_host_engine_emits(host_by_device, name):
    device, heads, evs = events_of(name)
    want = REF._band_rows(evs, STATES, WITHIN, BAND)
    assert want == host_by_device[device]
    if name in ROWS_OWED:
        assert [r[2:] for r in want] == [
            (float(heads[h]), t32) for h, t32 in ROWS_OWED[name]]


def test_the_seeded_logs_owe_rows_and_hold_two_arms():
    owed = [len(REF._band_rows(events_of(name)[2], STATES, WITHIN, BAND))
            for name in CASES if name.startswith("seeded_log")]
    assert 0 in owed and 1 in owed and 2 in owed


def test_a_head_reading_names_its_device_at_full_size():
    """21 bits of device in a float32 below 1, and a quarter of a step
    above it (a second arm's head reading, in the hand-made logs) still
    rounds to the device."""
    ids = np.array([0, 1, 4_095, 1_249_999, (1 << GEN.FRAC_BITS) - 2])
    key_of = np.arange(1 << GEN.FRAC_BITS)
    sch = types.SimpleNamespace(key_of=key_of)
    for arm in (0, 1):
        t1 = np.array([head_of(i, arm) for i in ids])
        assert t1.dtype == np.float32 and (0 < t1).all() and (t1 < 1).all()
        assert (GEN.IotSchedule.row_keys(sch, {"t1": t1}) == ids).all()
    assert [float(head_of(i)) for i in ids] == [
        (i + 1) / 2**21 for i in ids.tolist()]


# -- reference() itself, on the cell's generator -----------------------------

N_SENT = 26     # a pass and a half


@pytest.fixture(scope="module")
def bench():
    schedule = GEN.make(2**31 + 5, CONFIG, TIER1, True)
    assert len(schedule.all_keys) == 4_096
    rows = host_rows(map(schedule.batch, range(-schedule.warmup, N_SENT)))
    return types.SimpleNamespace(schedule=schedule, rows=rows)


def judge(bench, rows, n_sent=N_SENT):
    cols = {name: np.asarray([r[i + 1] for r in rows], dtype=np.float32)
            for i, name in enumerate(SPEC["row"])}
    cols["_ts"] = np.asarray([r[0] for r in rows], dtype=np.int64)
    cols["_n"] = bench.schedule.batch_of(cols["_ts"])
    collector = types.SimpleNamespace(
        rows=lambda: cols if rows else None,
        counts=collections.Counter(cols["_n"].tolist()))
    bad, compared = REF.reference(SPEC, bench.schedule, collector, n_sent,
                                  0, True)
    return bad, {name.split(" (")[0]: (value, limit)
                 for name, value, limit in compared}


DIFFER = "sampled rows that differ from the reference"
SWEPT = "rows of devices that were only swept"
ORDER = "rows of one device out of event-time order"
UNEVEN = "batches whose row count differs from the first pass's"


def window_row(bench, k=5):
    """Index of a row stamped inside the window's first pass."""
    return k + next(i for i, r in enumerate(bench.rows)
                    if bench.schedule.batch_of(r[0]) >= 0)


def test_the_host_engine_agrees_with_the_reference(bench):
    bad, compared = judge(bench, bench.rows)
    assert not bad and len(compared) == 5
    assert all(value <= limit for value, limit in compared.values())
    # three scripts of four owe a row a pass, on its batches 15 and 16:
    # the warm-up's and the first window pass's, none in the half pass
    per_batch = collections.Counter(
        int(bench.schedule.batch_of(r[0])) for r in bench.rows)
    assert per_batch == {-2: 164, -1: 82, 15: 164, 16: 82}


def test_a_row_dropped_is_not_correct(bench):
    rows = list(bench.rows)
    gone = rows.pop(window_row(bench))
    bad, compared = judge(bench, rows)
    assert compared[DIFFER] == (1, 0)
    assert bad == {int(bench.schedule.batch_of(gone[0]))}


def test_a_row_dropped_from_a_later_pass_is_not_correct(bench):
    """Two passes and a half: whichever pass the seed checks beside the
    first, the other one's batch no longer has its twin's count."""
    sch = bench.schedule
    first = [r for r in bench.rows if sch.batch_of(r[0]) >= 0]
    gap = sch.ts_of(sch.per_pass) - sch.ts_of(0)
    later = [(ts + p * gap, t1, t32)
             for p in (1, 2) for ts, t1, t32 in first]
    bad, compared = judge(bench, first + later, n_sent=3 * sch.per_pass)
    assert not bad and compared[UNEVEN] == (0, 0)
    for p in (1, 2):
        rows = first + later
        del rows[len(first) * p + 7]
        bad, compared = judge(bench, rows, n_sent=3 * sch.per_pass)
        assert compared[UNEVEN] == (1, 0) and len(bad) == 1
        assert min(bad) // sch.per_pass == p


@pytest.mark.parametrize("column", [1, 2])
def test_a_payload_one_ulp_off_is_not_correct(bench, column):
    rows = list(bench.rows)
    i = window_row(bench)
    row = list(rows[i])
    row[column] = np.nextafter(np.float32(row[column]), np.float32(64))
    rows[i] = tuple(row)
    bad, compared = judge(bench, rows)
    # the row delivered is not owed, the row owed is not delivered
    assert compared[DIFFER] == (2, 0)
    assert bad == {int(bench.schedule.batch_of(rows[i][0]))}


def test_a_stray_row_of_a_swept_device_is_not_correct(bench):
    sch = bench.schedule
    hot = set(sch.active_keys.tolist())
    ids = [i for i, k in enumerate(sch.key_of.tolist()) if k not in hot]
    ts = sch.ts_of(15)
    for device in ids[:3]:      # sampled by the reference or not
        rows = list(bench.rows)
        rows.insert(window_row(bench, 0),
                    (ts, head_of(device), np.float32(31.5)))
        bad, compared = judge(bench, rows)
        assert compared[SWEPT] == (1, 0) and 15 in bad


def test_a_swapped_pair_of_one_device_is_not_correct(bench):
    """A device owes one row a pass: its rows of two passes swapped."""
    sch = bench.schedule
    first = [r for r in bench.rows if sch.batch_of(r[0]) >= 0]
    gap = sch.ts_of(sch.per_pass) - sch.ts_of(0)
    later = [(ts + gap, t1, t32) for ts, t1, t32 in first]
    rows = first + later
    rows[3], rows[len(first) + 3] = rows[len(first) + 3], rows[3]
    _bad, compared = judge(bench, rows, n_sent=2 * sch.per_pass)
    assert compared[ORDER] == (1, 0)


def test_a_run_that_owes_nothing_is_not_correct(bench):
    """Fifteen batches: no run is 32 readings long yet, nothing is owed,
    nothing is checked, and the run says so."""
    bad, compared = judge(bench, [], n_sent=15)
    assert compared["rows owed on the sample: none"] == (1, 0)
    assert bad == set(range(15))
