"""The benchmark's plain reference of ``cardfraud_100k`` tied to the engine.

``benchmark/references/pattern_count_capture.py`` imports nothing of
the program; here the configuration's own app runs on the HOST engine
and the automaton of the reference owes exactly the rows it emits: on
hand-made cases (rising runs of 1 to 9 charges, a tie, a dip, the third
counted charge inside and outside ``within``, which charges a row
captures), on the generator's three scripts, on seeded random logs,
and, through ``reference()`` itself, on the cell's generator at the
rehearsal size.  One altered capture, one dropped row, one row of a
normal card and one card's rows out of order each make it not correct.
"""

import collections
import types

import numpy as np
import pytest

from cardfraud_bench import (CONFIG, GEN, REF, TRAFFIC, amount_of, run_app,
                             txn_batch)

WITHIN = CONFIG["reference"]["within_ms"]
COUNT = CONFIG["reference"]["count"]


def host_rows(batches):
    """The configuration's app on the host engine over ``batches``."""
    got, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    return got


# name -> [(whole amount, ms since the case began)]; a bare list is a
# millisecond an event
CASES = {f"rising_run_of_{n}": list(range(1, n + 1)) for n in range(1, 10)}
CASES.update({
    "a_tie_does_not_count": [5, 5, 6, 7],
    "two_instances_emit_at_one_event": [5, 5, 6, 7, 8],
    "a_dip_does_not_reset_the_count": [5, 9, 3, 12, 13],
    "b0_and_b2_are_the_first_and_third_counted": [1, 5, 3, 2, 7],
    "a_falling_run_owes_nothing": [9, 8, 7, 6, 5, 4],
    "third_inside_within": [(1, 0), (2, 1), (3, 2), (4, WITHIN - 1)],
    "third_on_the_edge_of_within": [(1, 0), (2, 1), (3, 2), (4, WITHIN)],
    "third_past_within": [(1, 0), (2, 1), (3, 2), (4, WITHIN + 1)],
    "the_older_instance_expires_the_younger_emits": [
        (7, 0), (8, 10), (9, 20), (1, 1_000), (2, 2_000), (3, 3_000),
        (10, WITHIN + 500)],
    "an_expired_instance_counts_nothing": [
        (1, 0), (2, 1), (3, WITHIN + 10), (4, WITHIN + 11),
        (5, WITHIN + 12), (6, WITHIN + 13)],
})
CASES.update({f"script_{i}": list(script)
              for i, script in enumerate(GEN.SCRIPTS)})
for _seed in range(6):
    _rng = np.random.default_rng(100 + _seed)
    _whole = _rng.integers(0, 12, size=40)
    _at = np.cumsum(_rng.choice([1, 50, 40_000, 250_000], size=40,
                                p=[0.5, 0.3, 0.15, 0.05]))
    CASES[f"seeded_log_{_seed}"] = list(zip(_whole.tolist(), _at.tolist()))
ROWS_OWED = {
    **{f"rising_run_of_{n}": max(n - 3, 0) for n in range(1, 10)},
    "a_tie_does_not_count": 0, "two_instances_emit_at_one_event": 2,
    "a_dip_does_not_reset_the_count": 1,
    "b0_and_b2_are_the_first_and_third_counted": 1,
    "a_falling_run_owes_nothing": 0,
    "third_inside_within": 1, "third_on_the_edge_of_within": 1,
    "third_past_within": 0,
    "the_older_instance_expires_the_younger_emits": 1,
    "an_expired_instance_counts_nothing": 1,
    **{f"script_{i}": n for i, n in enumerate(GEN.ROWS_OWED)},
}


def events_of(name):
    """``(n, ts, amount)`` of a case's events, as ``_count_rows`` takes
    them, under the case's own card.  ``n``, which the reference stamps
    a row with, is the event's timestamp: what the engine stamps it
    with."""
    card = 1 + list(CASES).index(name)
    evs = [e if isinstance(e, tuple) else (e, i)
           for i, e in enumerate(CASES[name])]
    return card, [(1_000 + at, 1_000 + at, float(amount_of(card, whole)))
                  for whole, at in evs]


@pytest.fixture(scope="module")
def host_by_card():
    """Every case through ONE host runtime, a card each, an event a
    batch in the order of their timestamps."""
    evs = sorted((ts, i, card, amount) for name in CASES
                 for card, es in [events_of(name)]
                 for i, (_n, ts, amount) in enumerate(es))
    rows = host_rows(txn_batch([card], [amount], [ts])
                     for ts, _i, card, amount in evs)
    by_card = collections.defaultdict(list)
    for ts, a0, b0, b2 in rows:
        by_card[round((a0 % 1) * (1 << GEN.FRAC_BITS)) - 1].append(
            (ts, a0, b0, b2))
    return by_card


@pytest.mark.parametrize("name", list(CASES))
def test_the_reference_owes_what_the_host_engine_emits(host_by_card, name):
    card, evs = events_of(name)
    want = REF._count_rows(evs, COUNT, WITHIN)
    assert want == host_by_card[card]
    assert len(want) == ROWS_OWED.get(name, len(want))


def test_a_row_holds_the_first_and_the_third_counted_charge(host_by_card):
    card, _evs = events_of("b0_and_b2_are_the_first_and_third_counted")
    ((_ts, a0, b0, b2),) = host_by_card[card]
    assert (int(a0), int(b0), int(b2)) == (1, 5, 2)
    # two instances that reach their count at one event: each its own
    # a and first, the one shared third; the older first
    card, _evs = events_of("two_instances_emit_at_one_event")
    (t1, a1, f1, l1), (t2, a2, f2, l2) = host_by_card[card]
    assert t1 == t2 and a1 == a2 and int(a1) == 5      # a tie of two a's
    assert (int(f1), int(l1), int(f2), int(l2)) == (6, 8, 6, 8)


# -- reference() itself, on the cell's generator -----------------------------

N_SENT = 3      # a pass and a batch of the next


@pytest.fixture(scope="module")
def bench():
    schedule = GEN.make(2**31 + 5, CONFIG, TRAFFIC, True)
    rows = host_rows(map(schedule.batch, range(-schedule.warmup, N_SENT)))
    return types.SimpleNamespace(schedule=schedule, rows=rows)


def judge(bench, rows):
    cols = {name: np.asarray([r[i + 1] for r in rows], dtype=np.float32)
            for i, name in enumerate(REF.ROW)}
    cols["_ts"] = np.asarray([r[0] for r in rows], dtype=np.int64)
    cols["_n"] = bench.schedule.batch_of(cols["_ts"])
    collector = types.SimpleNamespace(
        rows=lambda: cols, counts=collections.Counter(cols["_n"].tolist()))
    bad, compared = REF.reference(CONFIG["reference"], bench.schedule,
                                  collector, N_SENT, 0, True)
    return bad, {name.split(" (")[0]: (value, limit)
                 for name, value, limit in compared}


def window_row(bench, k=5):
    """Index of a row stamped inside the window's first pass."""
    return k + next(i for i, r in enumerate(bench.rows)
                    if bench.schedule.batch_of(r[0]) >= 0)


def test_the_host_engine_agrees_with_the_reference(bench):
    bad, compared = judge(bench, bench.rows)
    assert not bad and len(compared) == 5
    assert all(value <= limit for value, limit in compared.values())
    per_pass = sum(GEN.ROWS_OWED[s] for s in bench.schedule.script_of.values())
    in_window = [r for r in bench.rows if bench.schedule.batch_of(r[0]) >= 0]
    assert per_pass == 84 * 1 + 42 * 5
    assert per_pass < len(in_window) < 2 * per_pass


def test_an_altered_capture_is_not_correct(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    rows[i] = (*rows[i][:3], rows[i][3] + 1)    # b[last].amount
    bad, compared = judge(bench, rows)
    # the row delivered is not owed, the row owed is not delivered
    assert compared["sampled rows that differ from the reference"] == (2, 0)
    assert bad == {int(bench.schedule.batch_of(rows[i][0]))}


def test_a_dropped_row_is_not_correct(bench):
    rows = list(bench.rows)
    gone = rows.pop(window_row(bench))
    bad, compared = judge(bench, rows)
    assert compared["sampled rows that differ from the reference"] == (1, 0)
    assert int(bench.schedule.batch_of(gone[0])) in bad
    # and its twin in the second pass no longer has its count
    assert compared["batches whose row count differs from the first "
                    "pass's"][0] >= 1


def test_a_row_of_a_normal_card_is_not_correct(bench):
    sch = bench.schedule
    normal = int(np.flatnonzero(~np.isin(sch.all_keys, sch.active_keys))[0])
    rows = list(bench.rows)
    i = window_row(bench)
    amount = float(amount_of(normal, 3))
    rows.insert(i, (rows[i][0], amount, amount + 1, amount + 3))
    bad, compared = judge(bench, rows)
    assert compared["rows of normal cards"] == (1, 0)
    assert int(sch.batch_of(rows[i][0])) in bad


def test_a_swapped_pair_of_one_card_is_not_correct(bench):
    sch = bench.schedule
    keys = sch.row_keys({"a0": [r[1] for r in bench.rows]})
    rows = list(bench.rows)
    # two rows of one card at different events (script 1 owes one in a
    # pass's first batch and four in its second)
    card = next(k for k, s in sch.script_of.items() if s == 1)
    mine = [i for i, k in enumerate(keys) if k == card
            and sch.batch_of(rows[i][0]) >= 0]
    i, j = mine[0], mine[1]
    assert rows[i][0] < rows[j][0]
    rows[i], rows[j] = rows[j], rows[i]
    _bad, compared = judge(bench, rows)
    assert compared["rows of one card out of event-time order"] == (1, 0)
