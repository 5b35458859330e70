"""The benchmark's plain reference of ``ticknews_1m`` tied to the engine.

``benchmark/references/pattern_logical_and.py`` imports nothing of the
program; here the configuration's own app runs on the HOST engine and
the automaton of the reference owes exactly the rows it emits: on
hand-made logs (the tick first, the headline first, a filled side that
ignores a second event, either kind of arm at ``within`` and one
millisecond past it, one event of each stream at one timestamp, a symbol
twice in a batch, ``every`` arming again), on seeded random logs, and,
through ``reference()`` itself, on the cell's generator at the rehearsal
size.  A lost row, a row from an expired arm, a swapped side, a payload
one ulp off and one symbol's rows out of order each make it not correct.
"""

import collections
import itertools
import types

import numpy as np
import pytest

from ticknews_bench import (CONFIG, GEN, NEWS, REF, TICK, TRAFFIC,
                            make_batch, price_of, run_app)

WITHIN = CONFIG["reference"]["within_ms"]
T, N = TICK, NEWS


def host_rows(batches):
    """The configuration's app on the host engine over ``batches``."""
    got, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    return got


# name -> [(side, value, ms since the case began)]: a tick's value is
# its price's whole part (1..7), a headline's its sentiment (k / 8, exact)
CASES = {
    "the_tick_first": [(T, 1, 0), (N, .5, 100)],
    "the_headline_first": [(N, .5, 0), (T, 1, 100)],
    "a_tick_alone_owes_nothing": [(T, 1, 0), (T, 2, 6_000)],
    "a_headline_alone_owes_nothing": [(N, .5, 0), (N, .625, 6_000)],
    "a_filled_tick_side_ignores_a_second_tick": [
        (T, 1, 0), (T, 2, 10), (N, .5, 20)],
    "a_filled_news_side_ignores_a_second_headline": [
        (N, .5, 0), (N, .625, 10), (T, 1, 20)],
    "an_ignored_event_does_not_renew_the_arm": [
        (T, 1, 0), (T, 2, 4_000), (N, .5, WITHIN + 1), (T, 3, WITHIN + 2)],
    "a_tick_arm_at_within": [(T, 1, 0), (N, .5, WITHIN)],
    "a_tick_arm_past_within": [(T, 1, 0), (N, .5, WITHIN + 1)],
    "a_news_arm_at_within": [(N, .5, 0), (T, 1, WITHIN)],
    "a_news_arm_past_within": [(N, .5, 0), (T, 1, WITHIN + 1)],
    "an_expired_arm_gives_way_to_the_event_that_found_it": [
        (T, 1, 0), (N, .5, WITHIN + 1), (T, 2, WITHIN + 2)],
    "both_at_one_timestamp_tick_first": [(T, 1, 0), (N, .5, 0)],
    "both_at_one_timestamp_headline_first": [(N, .5, 0), (T, 1, 0)],
    "every_arms_again_after_a_row": [
        (T, 1, 0), (N, .5, 10), (T, 2, 20), (N, .625, 30)],
    "the_second_headline_opens_the_next_arm": [
        (T, 1, 0), (N, .5, 10), (N, .625, 20), (T, 2, 30)],
    "a_symbol_twice_in_a_tick_batch": [(T, 1, 0), (T, 2, 0), (N, .5, 10)],
    "a_symbol_twice_in_a_news_batch": [(N, .5, 0), (N, .625, 0), (T, 1, 10)],
    "a_hot_symbols_three_batches": [
        (T, 1, 0), (T, 2, 0), (N, .5, 1_500), (N, .625, 1_500),
        (T, 3, 3_000), (T, 4, 3_000)],
}
for _seed in range(8):
    _rng = np.random.default_rng(200 + _seed)
    _side = _rng.integers(0, 2, size=40)
    _at = np.cumsum(_rng.choice([0, 1, 50, 1_500, 2_600, WITHIN, WITHIN + 1],
                                size=40))
    CASES[f"seeded_log_{_seed}"] = [
        (int(s), 1 + i % 7 if s == T else (2 + i % 8) / 8, int(at))
        for i, (s, at) in enumerate(zip(_side, _at))]
# rows owed, and for some the whole parts of the prices they pair
ROWS_OWED = {
    "the_tick_first": [1], "the_headline_first": [1],
    "a_tick_alone_owes_nothing": [], "a_headline_alone_owes_nothing": [],
    "a_filled_tick_side_ignores_a_second_tick": [1],
    "a_filled_news_side_ignores_a_second_headline": [1],
    "an_ignored_event_does_not_renew_the_arm": [3],
    "a_tick_arm_at_within": [1], "a_tick_arm_past_within": [],
    "a_news_arm_at_within": [1], "a_news_arm_past_within": [],
    "an_expired_arm_gives_way_to_the_event_that_found_it": [2],
    "both_at_one_timestamp_tick_first": [1],
    "both_at_one_timestamp_headline_first": [1],
    "every_arms_again_after_a_row": [1, 2],
    "the_second_headline_opens_the_next_arm": [1, 2],
    "a_symbol_twice_in_a_tick_batch": [1],
    "a_symbol_twice_in_a_news_batch": [1],
    "a_hot_symbols_three_batches": [1, 3],
}
SENTIMENTS_OWED = {
    "a_filled_news_side_ignores_a_second_headline": [.5],
    "a_symbol_twice_in_a_news_batch": [.5],
    "the_second_headline_opens_the_next_arm": [.5, .625],
    "a_hot_symbols_three_batches": [.5, .625],
}
T0 = 1_000


def events_of(name):
    """``(n, ts, side, value)`` of a case's events, as ``_and_rows``
    takes them, under the case's own symbol.  ``n``, which the
    reference stamps a row with, is the event's timestamp."""
    symbol = 1 + list(CASES).index(name)
    return symbol, [
        (T0 + at, T0 + at, side,
         float(price_of(symbol, v)) if side == T else float(np.float32(v)))
        for side, v, at in CASES[name]]


@pytest.fixture(scope="module")
def host_by_symbol():
    """Every case through ONE host runtime, a symbol each, in the order
    of their timestamps; events of one stream at one timestamp share a
    batch, so a symbol comes twice in some."""
    evs = sorted((ts, i, symbol, side, value) for name in CASES
                 for symbol, es in [events_of(name)]
                 for i, (_n, ts, side, value) in enumerate(es))
    batches = []
    for (ts, side), run in itertools.groupby(evs, key=lambda e: (e[0], e[3])):
        run = list(run)
        batches.append(make_batch(side, [e[2] for e in run],
                                [e[4] for e in run], ts))
    assert max(len(b.timestamps) for b in batches) > 2
    by_symbol = collections.defaultdict(list)
    for ts, price, sentiment in host_rows(batches):
        symbol = (round((price % 1) * (1 << GEN.FRAC_BITS)) >> 1) - 1
        by_symbol[symbol].append((ts, ts, price, sentiment))
    return by_symbol


@pytest.mark.parametrize("name", list(CASES))
def test_the_reference_owes_what_the_host_engine_emits(host_by_symbol, name):
    symbol, evs = events_of(name)
    want, _arm = REF._and_rows(evs, WITHIN)
    assert want == host_by_symbol[symbol]
    if name in ROWS_OWED:
        assert [int(r[2]) for r in want] == ROWS_OWED[name]
    if name in SENTIMENTS_OWED:
        assert [r[3] for r in want] == SENTIMENTS_OWED[name]
    if name.startswith("seeded_log"):
        assert want


def test_the_arm_a_log_leaves_is_the_arm_the_next_begins_with():
    """``_owed`` carries the arm across passes: a tick in a pass's last
    second pairs the headline of the next pass's first."""
    events = [(0, 0, N, .5), (15, 22_500, T, 3.0)]
    owed = REF._owed(events, WITHIN, 24_000, {0, 1, 2, 7})
    assert owed[0] == []                 # the headline's arm expired
    assert owed[1] == owed[2] == owed[7] == [(0, 0, 3.0, .5)]
    # and what a pass owes is a function of that arm alone
    assert REF._and_rows(events, WITHIN, (-1_500, 3.0, None)) == (
        [(0, 0, 3.0, .5)], (22_500, 3.0, None))


# -- reference() itself, on the cell's generator -----------------------------

N_SENT = 24     # a pass and a half


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


DIFFER = "sampled rows that differ from the reference"


def window_row(bench, k=5):
    """Index of a row stamped inside the window's first pass."""
    return k + next(i for i, r in enumerate(bench.rows)
                    if bench.schedule.batch_of(r[0]) >= 0)


def test_the_host_engine_agrees_with_the_reference(bench):
    bad, compared = judge(bench, bench.rows)
    assert not bad and len(compared) == 4
    assert all(value <= limit for value, limit in compared.values())
    in_window = [r for r in bench.rows if bench.schedule.batch_of(r[0]) >= 0]
    # a pass's rows, and the next pass's up to its eighth batch: all
    # but the last of its four batches that owe any
    per_pass = len(bench.rows) - len(in_window)
    assert 800 < per_pass < len(in_window) < 2 * per_pass


def test_a_lost_row_is_not_correct(bench):
    rows = list(bench.rows)
    gone = rows.pop(window_row(bench))
    bad, compared = judge(bench, rows)
    assert compared[DIFFER] == (1, 0)
    assert int(bench.schedule.batch_of(gone[0])) in bad
    # and its twin in the second pass no longer has its count
    assert compared["batches whose row count differs from the first "
                    "pass's"][0] >= 1


def test_a_row_from_an_expired_arm_is_not_correct(bench):
    """A swept symbol's tick four batches before the news batch is 6 s
    old at its headline: an engine that kept the arm would pair them."""
    sch = bench.schedule
    news = sch.batch(sch.news_at)
    early = sch.batch(sch.news_at - 4)
    hot = sch.active_keys
    both = np.setdiff1d(np.intersect1d(news.columns["symbol"],
                                       early.columns["symbol"]), hot)
    symbol = int(both[0])
    price = early.columns["price"][early.columns["symbol"] == symbol][0]
    sentiment = news.columns["sentiment"][
        news.columns["symbol"] == symbol][0]
    ts = int(news.timestamps[0])
    assert ts - int(early.timestamps[0]) == 6_000 > WITHIN
    assert (ts, price, sentiment) not in bench.rows
    rows = list(bench.rows)
    rows.insert(window_row(bench, 0), (ts, price, sentiment))
    bad, compared = judge(bench, rows)
    assert compared[DIFFER] == (1, 0) and sch.news_at in bad


def test_a_swapped_side_is_not_correct(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    ts, price, sentiment = rows[i]
    rows[i] = (ts, sentiment, price)
    bad, compared = judge(bench, rows)
    # the row delivered is not owed, the row owed is not delivered
    assert compared[DIFFER] == (2, 0)
    assert bad == {int(bench.schedule.batch_of(ts))}


@pytest.mark.parametrize("column", [1, 2])
def test_a_payload_one_ulp_off_is_not_correct(bench, column):
    rows = list(bench.rows)
    i = window_row(bench)
    row = list(rows[i])
    # a price's last bit says which of two events it was: two ulps
    # off, so that the row still names its symbol
    off = np.float32(row[column])
    for _ in range(column == 1 and 2 or 1):
        off = np.nextafter(off, np.float32(8))
    row[column] = off
    rows[i] = tuple(row)
    bad, compared = judge(bench, rows)
    assert compared[DIFFER] == (2, 0)
    assert bad == {int(bench.schedule.batch_of(rows[i][0]))}


def test_a_swapped_pair_of_one_symbol_is_not_correct(bench):
    sch = bench.schedule
    keys = sch.row_keys({"price": [r[1] for r in bench.rows]})
    rows = list(bench.rows)
    # a hot symbol owes a row on the news batch and one on the next
    symbol = int(sch.active_keys[0])
    mine = [i for i, k in enumerate(keys) if k == symbol
            and sch.batch_of(rows[i][0]) >= 0]
    i, j = mine[0], mine[1]
    assert rows[i][0] < rows[j][0]
    rows[i], rows[j] = rows[j], rows[i]
    _bad, compared = judge(bench, rows)
    assert compared["rows of one symbol out of event-time order"] == (1, 0)


def test_the_collectors_older_tail_batch_is_no_disorder(bench):
    """The collector hands the reference the kept passes' rows by their
    place in the run and then its newest batch of a pass that was NOT
    kept; where the run ends in a kept pass that batch is the older
    one.  A hot symbol has a row in both, and that is no disorder."""
    sch = bench.schedule
    per_pass = sch.per_pass
    kept = next(p for p in range(2, 400) if sch.keep(p * per_pass))
    skipped = next(p for p in range(1, kept) if not sch.keep(p * per_pass))
    first = [r for r in bench.rows if 0 <= sch.batch_of(r[0]) < per_pass]

    def shifted(p, rows):
        return [(ts + p * sch.pass_ms, *rest) for ts, *rest in rows]

    tail = [r for r in first if sch.batch_of(r[0]) == sch.news_at]
    rows = first + shifted(kept, first) + shifted(skipped, tail)
    cols = {name: np.asarray([r[i + 1] for r in rows], dtype=np.float32)
            for i, name in enumerate(REF.ROW)}
    cols["_ts"] = np.asarray([r[0] for r in rows], dtype=np.int64)
    cols["_n"] = sch.batch_of(cols["_ts"])
    a_pass = collections.Counter(sch.batch_of(r[0]).item() for r in first)
    counts = collections.Counter({p * per_pass + n: c for n, c in
                                  a_pass.items() for p in range(kept + 1)})
    collector = types.SimpleNamespace(rows=lambda: cols, counts=counts)
    bad, compared = REF.reference(CONFIG["reference"], sch, collector,
                                  (kept + 1) * per_pass, 0, True)
    assert not bad and all(v <= limit for _n, v, limit in compared)
    assert f"passes [0, {kept}]" in compared[0][0]
    # the same rows with two of a hot symbol's swapped INSIDE the kept
    # pass: that is one
    keys = sch.row_keys({"price": cols["price"]})
    i, j = [k for k in np.flatnonzero(keys == sch.active_keys[0])
            if cols["_n"][k] // per_pass == kept][:2]
    for name in cols:
        cols[name][[i, j]] = cols[name][[j, i]]
    _bad, compared = REF.reference(CONFIG["reference"], sch, collector,
                                   (kept + 1) * per_pass, 0, True)
    assert compared[1] == ("rows of one symbol out of event-time order", 1, 0)


def test_a_run_that_owes_nothing_is_not_correct(bench):
    """Three batches hold no headline: nothing is owed, nothing is
    checked, and the run says so."""
    cols = {"price": np.zeros(0, np.float32),
            "sentiment": np.zeros(0, np.float32),
            "_ts": np.zeros(0, np.int64), "_n": np.zeros(0, np.int64)}
    collector = types.SimpleNamespace(rows=lambda: cols,
                                      counts=collections.Counter())
    bad, compared = REF.reference(CONFIG["reference"], bench.schedule,
                                  collector, 3, 0, True)
    assert compared[-1] == ("rows owed on the sample: none", 1, 0)
    assert bad == {0, 1, 2}
