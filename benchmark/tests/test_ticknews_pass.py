"""The tick-and-news mix's own promises (``generators/ticknews_pass.py``),
on the CPU with no device work: fifteen tick batches to one news batch
at its place, an event clock of 1,500 ms a batch with contiguous passes,
hot symbols twice in every batch of either stream, a sweep that starts
anew with each pass, the three shares of a pass's headlines inside their
ranges at both sizes, rows owed on four batches of sixteen and the same
in every pass, prices exact in float32 that name their symbol, and a
control that changes every owed row.  Then the cell through ``run.py``
at its rehearsal size: its line, its control, a planted wrong answer
(``test_benchmark.py`` runs these for every cell of ``BENCHMARK.json``
too; here the wrong answer is a row that goes missing).  The reference
against the host engine is tier-1's ``tests/test_ticknews_reference.py``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import collections
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import ml_dtypes
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH, os.path.join(BENCH, "generators")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402  (benchmark/run.py)
import ticknews_pass  # noqa: E402
from references import pattern_logical_and  # noqa: E402

CELL = "ticknews_1m.saturated"
TICK, NEWS = ticknews_pass.TICK, ticknews_pass.NEWS


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


SPEC = _load(ROOT, "BENCHMARK.json")
CONFIG = _load(BENCH, "configs", "ticknews_1m.json")
TRAFFIC = _load(BENCH, "traffic", "ticknews_pass_saturated.json")
REF = CONFIG["reference"]
# size -> (symbols, events a batch, hot symbols)
SIZES = {"full": (1_000_000, 131_072, 4_096), "rehearsal": (4_096, 1_024, 276)}
MADE = [("full", 2**31 + 5)] + [("rehearsal", s) for s in (0, 1, 2, 2**31 + 5)]


@pytest.fixture(scope="module", params=MADE, ids=lambda p: f"{p[0]}-{p[1]}")
def made(request):
    size, seed = request.param
    return size, ticknews_pass.make(seed, CONFIG, TRAFFIC,
                                    size == "rehearsal")


def a_pass(schedule, p=0):
    return [schedule.batch(n) for n in range(p * schedule.per_pass,
                                             (p + 1) * schedule.per_pass)]


def test_the_cell_names_this_mix():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ticknews_1m", "ticknews_pass_saturated", 1)
    assert TRAFFIC["generator"] == "ticknews_pass"
    assert TRAFFIC["loop"] == "closed"
    assert (TRAFFIC["batches_per_pass"], TRAFFIC["news_at"],
            TRAFFIC["batch_gap_ms"]) == (16, 5, 1_500)
    assert CONFIG["stream"] == ["StockTick", "NewsEvent"]
    assert list(CONFIG["reduced"]) == ["chips"]
    assert CONFIG["full"]["partitions"] == 1_000_000
    assert "within 5 sec" in CONFIG["app"] and REF["within_ms"] == 5_000
    # a pass outlasts within, so an arm never meets its own twin
    assert REF["within_ms"] < 16 * TRAFFIC["batch_gap_ms"]
    assert CELL in next(m for m in SPEC["end_to_end"]
                        if m["name"] == "events_per_s")["workloads"]
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    on_bruteforce = {m["name"] for m in SPEC["per_layer"]
                     if "bruteforce_1m.saturated" in m.get("workloads", [])}
    new = {"events.logical_ms_per_batch", "events.stream2_rows_per_batch",
           "events.stream2_emit_ms_per_batch"}
    # no count node; and PERF.md Open question 14 for the poll
    left_out = {"events.kleene_ms_per_batch", "events.poll_ms_per_batch"}
    assert listed == (on_bruteforce - left_out) | new | {"setup_compile_s"}
    for m in SPEC["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["moves"] == "events_per_s"


def test_a_pass_is_fifteen_tick_batches_and_one_news_batch(made):
    size, schedule = made
    n_keys, batch, n_hot = SIZES[size]
    assert schedule.per_pass == schedule.warmup == 16
    assert schedule.batch_events == batch
    assert len(schedule.all_keys) == n_keys == len(set(
        schedule.all_keys.tolist()))
    streams = [b.stream_id for b in a_pass(schedule)]
    assert streams == ["StockTick"] * 5 + ["NewsEvent"] + ["StockTick"] * 10
    assert schedule.news_at == 5
    for b, stream in zip(a_pass(schedule), streams):
        side = NEWS if stream == "NewsEvent" else TICK
        assert b.attribute_names == list(ticknews_pass.COLUMNS[side])
        assert len(b.timestamps) == batch
        for name, dtype in zip(b.attribute_names,
                               (np.int64, np.float32, np.int32)):
            assert b.columns[name].dtype == dtype


def test_hot_symbols_come_twice_a_batch_and_the_rest_are_swept(made):
    size, schedule = made
    n_keys, batch, n_hot = SIZES[size]
    hot = np.sort(schedule.active_keys)
    assert len(hot) == n_hot == len(set(hot.tolist()))
    n_bulk = batch - 2 * n_hot
    ticks = collections.Counter()
    last_tick = {}
    for place, b in enumerate(a_pass(schedule)):
        symbols, counts = np.unique(b.columns["symbol"], return_counts=True)
        assert (np.sort(symbols[counts == 2]) == hot).all()
        assert counts.max() == 2 and len(symbols) == n_bulk + n_hot
        if b.stream_id == "NewsEvent":
            continue
        swept = symbols[counts == 1]
        ticks.update(swept.tolist())
        # never twice inside within: a swept symbol's ticks lie more
        # than three batches apart
        again = [place - last_tick[s] for s in swept.tolist()
                 if s in last_tick]
        assert not again or min(again) > 3
        last_tick.update(dict.fromkeys(swept.tolist(), place))
    # the sweep reaches every symbol once or twice a pass, one in about
    # eight tick batches
    assert len(ticks) == n_keys - n_hot
    assert set(ticks.values()) == {1, 2}
    assert 7.5 < (n_keys - n_hot) / n_bulk < 8.5


def test_event_time_advances_a_batch_and_passes_are_contiguous(made):
    _size, schedule = made
    gap = TRAFFIC["batch_gap_ms"]
    assert schedule.ts_of(0) == ticknews_pass.WINDOW_T0_MS > 1_000_000
    assert schedule.ts_of(-1) < 1_000_000   # test_benchmark plants by this
    for n in (-16, -1, 0, 5, 15, 16, 37, 1_000):
        b = schedule.batch(n)
        assert set(b.timestamps.tolist()) == {schedule.ts_of(n)}
        assert schedule.ts_of(n + 1) - schedule.ts_of(n) == gap
        assert (schedule.batch_of(b.timestamps) == n).all()
        assert (schedule.batch_of(b.timestamps + gap - 1) == n).all()
        assert schedule.twin(n + 16) == (n + 16) % 16
        twin = schedule.batch(n + 16)
        assert twin.stream_id == b.stream_id
        for name in b.attribute_names:
            assert (twin.columns[name] == b.columns[name]).all()
        assert set((twin.timestamps - b.timestamps).tolist()) == {16 * gap}
    assert schedule.pass_ms == 24_000 > REF["within_ms"]


def test_only_some_passes_are_kept(made):
    _size, schedule = made
    per_pass = schedule.per_pass
    assert all(schedule.keep(n) for n in range(-per_pass, per_pass))
    kept = [p for p in range(1, 161) if schedule.keep(p * per_pass)]
    assert len(kept) == 20          # one in eight, by the seed
    for p in range(1, 161):         # a pass is kept whole or not at all
        assert len({schedule.keep(n) for n in range(
            p * per_pass, (p + 1) * per_pass)}) == 1


def owed_by_symbol(schedule, symbols, passes=(1, 2, 3)):
    """``symbol -> {pass: rows}`` by the reference's automaton run from
    the warm-up's first event (pass 0)."""
    return {s: pattern_logical_and._owed(
        evs, REF["within_ms"], schedule.pass_ms, set(passes))
        for s, evs in pattern_logical_and._pass_events(
            schedule, symbols).items()}


def test_the_three_shares_of_the_headlines_and_the_bursts(made):
    size, schedule = made
    n_keys, batch, n_hot = SIZES[size]
    news = schedule.batch(schedule.news_at)
    headline = np.setdiff1d(news.columns["symbol"], schedule.active_keys)
    assert len(headline) == batch - 2 * n_hot
    # only a symbol with a headline can owe a row
    owed = owed_by_symbol(schedule, np.union1d(headline,
                                               schedule.active_keys))
    kinds, per_batch = collections.Counter(), collections.Counter()
    hot = set(schedule.active_keys.tolist())
    for s, by_pass in owed.items():
        # every pass owes what the first window pass owes, at the same
        # places and with the same payloads
        assert by_pass[1] == by_pass[2] == by_pass[3]
        places = [r[0] for r in by_pass[1]]
        per_batch.update(places)
        if s in hot:
            # the first headline completes a tick's arm, the second
            # opens one that the next batch's first tick completes
            assert places == [5, 6]
        else:
            assert len(places) <= 1
            kinds["completes" if places == [5] else
                  "completed" if places else "expires"] += 1
    share = {k: 100.0 * v / len(headline) for k, v in kinds.items()}
    assert 30 <= share["completes"] <= 42
    assert 30 <= share["completed"] <= 42
    assert 20 <= share["expires"] <= 35
    # rows on the news batch and the three tick batches after it, none
    # on the other twelve
    assert sorted(per_batch) == [5, 6, 7, 8]
    rows = sum(per_batch.values())
    assert 0.040 < rows / (16 * batch) < 0.060
    assert per_batch[5] > per_batch[6] > max(per_batch[7], per_batch[8])
    if size == "full":
        assert 45_000 < per_batch[5] < 52_000
        assert all(14_000 < per_batch[b] < 20_000 for b in (6, 7, 8))
        assert 90_000 < rows < 105_000
    # tick-opened arms expire too: most ticks belong to no headline
    ticks = sum(int((~np.isin(b.columns["symbol"], news.columns["symbol"]))
                    .sum()) for b in a_pass(schedule)
                if b.stream_id == "StockTick")
    assert ticks > 10 * len(headline)


def test_the_warm_up_pass_owes_as_many_rows_at_the_same_places(made):
    """The first pass begins with no arm, every other with what its
    predecessor left: a hot symbol pairs another tick, not another
    number of rows."""
    _size, schedule = made
    owed = owed_by_symbol(schedule, schedule.active_keys[:64], (0, 1))
    for by_pass in owed.values():
        assert [r[:2] for r in by_pass[0]] == [r[:2] for r in by_pass[1]]
    assert any(by_pass[0] != by_pass[1] for by_pass in owed.values())


def test_prices_are_exact_in_float32_and_name_their_symbol(made):
    _size, schedule = made
    k = 0
    for b in a_pass(schedule):
        symbols = b.columns["symbol"]
        if b.stream_id == "NewsEvent":
            s = b.columns["sentiment"]
            assert 0.25 <= s.min() and s.max() < 1.25
            continue
        price = b.columns["price"]
        assert (schedule.row_keys({"price": price}) == symbols).all()
        whole = np.floor(price.astype(np.float64))
        # which tick batch of the pass it came in
        assert set(whole.tolist()) == {1 + k % 7}
        frac = (price.astype(np.float64) - whole) * (
            1 << ticknews_pass.FRAC_BITS)
        assert (frac == np.rint(frac)).all() and frac.min() >= 2
        # the fraction's last bit: a symbol's second event in the batch
        second = frac.astype(np.int64) & 1
        order = np.argsort(symbols, kind="stable")
        twice = symbols[order][1:] == symbols[order][:-1]
        assert second.sum() == twice.sum() == len(schedule.active_keys)
        assert (second[order][1:][twice] == 1).all()
        assert (second[order][:-1][twice] == 0).all()
        k += 1
    assert k == 15


def test_the_control_changes_every_owed_row():
    """Rounded to bfloat16 a price loses the fraction that names its
    symbol and a sentiment most of its digits."""
    schedule = ticknews_pass.make(7, CONFIG, TRAFFIC, rehearsal=True)
    assert CONFIG["control"]["round_bf16"] == ["price", "sentiment"]
    exact = {r[2:] for by_pass in owed_by_symbol(
        schedule, schedule.all_keys, (1,)).values() for r in by_pass[1]}
    assert len(exact) > 800
    rounded = {tuple(float(np.float32(v).astype(ml_dtypes.bfloat16))
                     for v in row) for row in exact}
    assert not exact & rounded


# -- the cell through run.py ---------------------------------------------------


def rehearse(capsys, *extra, seed=2**31 + 11, seconds=1):
    assert bench_run.main(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearsal",
                           *extra]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_the_rehearsal_is_correct_on_its_path(capsys):
    line, out = rehearse(capsys)
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 16 * 1_024
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}
    compared = [ln for ln in out if ln.startswith("compared: ")]
    assert any("sampled rows that differ from the reference (4096 symbols"
               in ln and ln.endswith(": 0 (limit 0)") for ln in compared)
    assert any("off its path: none" in ln for ln in compared)
    assert any("instance-lane overflow: 0" in ln for ln in compared)
    assert any("programs compiled in the window: 0" in ln for ln in out)


def test_a_traced_rehearsal_reads_the_news_batches_alone(capsys):
    line, out = rehearse(capsys, "--trace", "1", seconds=2)
    assert line["correct"] is True, [
        ln for ln in out if ln.startswith(("compared", "send of"))
        and not ln.endswith(": 0 (limit 0)")]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # no device plane on the CPU: the scope's reader returns nothing
    assert "events.logical_ms_per_batch" not in metrics
    # a news batch owes some 440 rows at this size, a batch of the pass
    # a sixteenth of the pass's 880
    assert 380 < metrics["events.stream2_rows_per_batch"] < 500
    assert 40 < metrics["events.rows_per_batch"] < 70
    assert (metrics["events.stream2_emit_ms_per_batch"]
            > metrics["events.fetch_ms_per_batch"]
            + metrics["events.build_ms_per_batch"]
            + metrics["events.deliver_ms_per_batch"])


def test_the_control_is_not_correct(capsys):
    line, _out = rehearse(capsys, "--control", "bf16")
    assert line["control"] == "bf16"
    assert line["correct"] is False and line["failed"] > 0


def test_a_row_that_goes_missing_is_not_correct(capsys, monkeypatch):
    """One row cut out of one delivered batch of the window where the
    engine hands it to the callback."""
    from siddhi_tpu.core.event import EventBatch

    orig, planted = EventBatch.__init__, []

    def init(self, stream_id, names, columns, timestamps, types=None):
        if (stream_id == CONFIG["output"] and not planted
                and len(timestamps) > 1
                and timestamps[0] >= ticknews_pass.WINDOW_T0_MS):
            columns = {k: np.asarray(v)[1:] for k, v in columns.items()}
            timestamps = np.asarray(timestamps)[1:]
            planted.append(stream_id)
        orig(self, stream_id, names, columns, timestamps, types)

    monkeypatch.setattr(EventBatch, "__init__", init)
    line, out = rehearse(capsys)
    assert planted
    assert line["correct"] is False
    assert 0 < line["failed"] < line["attempted"]
    assert any("sampled rows that differ from the reference" in ln
               and ": 1 (limit 0)" in ln for ln in out)
