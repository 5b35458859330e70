"""``cardfraud_100k``'s app on the dense engine at 4,096 partitions, on
the cell's own generator at its rehearsal size, against the host engine.

What the 64-partition unit tests of the count node do not reach: batches
with more events than there are cards, cut into four rounds or into
eight, through ``make_rounds``' wide loop at both widths of its ladder
and through a narrow tail; a counted last node whose filter reads a
float capture with all four instance lanes live; the overflow accounting
where a card opens a fifth instance; ``steppedLanes``, the lanes the
engine's programs step for a batch, against widths worked out by hand;
and ``plannedRepeats``, the events past their card's first in a batch.
"""

import logging

import numpy as np
import pytest

from cardfraud_bench import (COLUMNS, CONFIG, GEN, TRAFFIC, amount_of,
                             run_app, txn_batch)
from siddhi_tpu import SiddhiManager

DENSE = ("@app:statistics('true') "
         + CONFIG["header"].format(**CONFIG["rehearsal"]))
N_BATCHES = 2       # a pass past the warm-up


def inspect(rt):
    """``pattern_state()`` of the one query, and which programs its
    engine built."""
    (pr,) = rt.partitions.values()
    engine = pr.dense_query_runtimes["bench"].pattern_processor.engine
    return {**rt.pattern_state()["bench"],
            "programs": {k[1] for k in engine._step_cache}}


def stat(stats, name):
    (key,) = [k for k in stats if k.endswith("Queries.bench." + name)]
    return stats[key]


def run(batches):
    """The app on the dense engine: rows, the runtime's view of itself,
    ``statistics()`` after the shutdown, the listener's errors."""
    got, errors, lowering, state, stats = run_app(DENSE, batches, inspect)
    assert lowering == CONFIG["expect"]["lowering"]
    return got, state, stats, errors


def cut(batch, pick):
    return txn_batch(batch.columns["card"][pick],
                     batch.columns["amount"][pick], batch.timestamps[pick])


def join(a, b):
    return txn_batch(*(np.concatenate([a.columns[c], b.columns[c]])
                       for c in COLUMNS[:2]),
                     np.concatenate([a.timestamps, b.timestamps]))


@pytest.fixture(scope="module")
def schedule():
    return GEN.make(2**31 + 7, CONFIG, TRAFFIC, True)


@pytest.fixture(scope="module")
def batches(schedule):
    return [schedule.batch(n) for n in range(-schedule.warmup, N_BATCHES)]


@pytest.fixture(scope="module")
def host(batches):
    got, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    return got


def occurrence(batch):
    """How many events of its card stand before each event."""
    cards = batch.columns["card"]
    order = np.argsort(cards, kind="stable")
    _, start, count = np.unique(cards[order], return_index=True,
                                return_counts=True)
    nth = np.empty(len(cards), dtype=np.int64)
    nth[order] = np.arange(len(cards)) - np.repeat(start, count)
    return nth


# shape -> (the widths of a batch's rounds, the lanes its programs step)
# a batch as sent: 4,096 lanes for the first round, 1,929 more events in
# a rounds program of 2,048, its ladder (2048, 256): two rounds in the
# wide loop at 2,048, the last a link of the run.  A pass as one batch:
# 6,642 more events in a program of 8,192, its ladder (8192, 1024): two
# rounds at 8,192, one at 1,024, four links of the run
SHAPES = {
    "four_rounds": ([3440, 1599, 288, 42], 4096 + 2 * 2048 + 128),
    "one_round": (None, 4096 + 2048 + 512 + 64),
    "eight_rounds": ([4096, 4096, 2210, 168, 42, 42, 42, 42],
                     4096 + 2 * 8192 + 1024 + 4 * 128),
}


def shaped(batches, shape):
    """The same events in the same order a card, cut another way: as
    sent (four collision rounds a batch), each batch's first, second,
    third and fourth occurrences as batches of their own (one round
    each), or a pass as one batch (eight rounds)."""
    if shape == "four_rounds":
        return batches
    if shape == "one_round":
        return [cut(b, nth == k) for b in batches
                for nth in [occurrence(b)] for k in range(nth.max() + 1)]
    return [join(a, b) for a, b in zip(batches[::2], batches[1::2])]


def by_card(rows):
    return sorted(rows, key=lambda r: r[1] % 1)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_dense_rows_equal_the_host_engines(schedule, batches, host, shape):
    sent = shaped(batches, shape)
    got, state, stats, errors = run(sent)
    # stable by card: the same rows, each card's in the host's order
    assert by_card(got) == by_card(host) and not errors
    per_pass = sum(GEN.ROWS_OWED[s] for s in schedule.script_of.values())
    assert len(got) == 2 * per_pass          # the warm-up pass and one more
    assert state["instance_lanes"] == 4
    assert state["partitions_in_use"] == 4096
    assert state["dropped_instances"] == 0
    assert stat(stats, "droppedInstances") == 0
    # only a third round builds the rounds program, and a counted node
    # is outside the run kernel's class: its narrow rounds are the XLA
    # loop
    assert ("rounds" in state["programs"]) == (shape != "one_round")
    widths, lanes = SHAPES[shape]
    if widths is not None:
        _, counts = np.unique(sent[0].columns["card"], return_counts=True)
        assert [int((counts > r).sum()) for r in range(len(widths))] == widths
        assert counts.max() == len(widths)
    passes = len(batches) // 2
    assert stat(stats, "steppedLanes") == passes * lanes * (
        2 if shape != "eight_rounds" else 1)
    # the events behind a batch's first round: 1,929 of a batch as sent
    assert stat(stats, "plannedRepeats") == passes * {
        "four_rounds": 2 * (1599 + 288 + 42), "one_round": 0,
        "eight_rounds": sum(SHAPES["eight_rounds"][0][1:])}[shape]


def test_all_four_lanes_are_live_and_a_row_names_its_charges(schedule, host):
    """What the scripts promise of the captures: a row's three amounts
    are one card's, the opening charge under the first counted under
    the third."""
    rows = np.asarray(host, dtype=np.float64)[:, 1:]
    frac = rows % 1
    assert (frac[:, 0] == frac[:, 1]).all() and (frac[:, 0] == frac[:, 2]).all()
    whole = np.floor(rows).astype(int)
    assert (whole[:, 0] + 1 == whole[:, 1]).all()
    assert (whole[:, 1] + 2 == whole[:, 2]).all()
    # script 1: the first five charges each count their three
    card = next(k for k, s in schedule.script_of.items() if s == 1)
    mine = whole[schedule.row_keys({"a0": rows[:, 0]}) == card][:5]
    assert mine.tolist() == [[k, k + 1, k + 3] for k in range(1, 6)]


def falling_batches(n):
    """One card: ``n`` falling charges, two a batch: every one opens an
    instance and none counts for another."""
    whole = list(range(40, 40 - n, -1)) + [0] * (n % 2)
    amounts = [amount_of(77, w) if w else 0.0 for w in whole]
    return [txn_batch([77, 77], amounts[i:i + 2], [1_000 + i, 1_000 + i])
            for i in range(0, len(amounts), 2)]


@pytest.mark.parametrize("n, dropped", [(4, 0), (5, 1), (6, 2)])
def test_a_fifth_pending_charge_counts_its_overflow(caplog, n, dropped):
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu"):
        got, state, stats, errors = run(falling_batches(n))
    assert not got
    assert state["dropped_instances"] == dropped
    assert stat(stats, "droppedInstances") == dropped
    advice = [r.getMessage() for r in caplog.records
              if "instances='N'" in r.getMessage()]
    assert len(errors) == len(advice) == (1 if dropped else 0)
    if dropped:
        assert f"{dropped} pending instance(s) dropped" in advice[0]
        assert "current 4 per partition/node" in advice[0]


def keyed(times):
    """A batch in which card ``c`` comes ``times[c]`` times, its events
    apart, every amount 0: no instance opens, only the rounds count."""
    cards = np.concatenate([np.flatnonzero(np.asarray(times) > r)
                            for r in range(max(times))])
    return txn_batch(cards, np.zeros(len(cards)), np.full(len(cards), 1_000))


# name -> (events a card, the lanes stepped, worked out by hand)
LANES = {
    # 120 cards, 20 of them twice: the step twice, padded to 128 and 32
    "two_rounds": ([2] * 20 + [1] * 100, 128 + 32),
    # 84 / 39 / 7 / 1: 84 lanes padded to 128; 47 more events in a
    # rounds program of 64, no wider than the run: three links
    "four_rounds": ([4] + [3] * 6 + [2] * 32 + [1] * 45, 128 + 3 * 128),
    # one card 300 times, 600 twice, 1,000 once: 1,601 lanes padded to
    # 2,048; 899 more events in a program of 1,024, whose ladder is
    # (1024,) alone (1024 // 8 is the run's width): the round of 601 at
    # 1,024, then 298 links of the run
    "skewed": ([300] + [2] * 600 + [1] * 1000, 2048 + 1024 + 298 * 128),
}


def test_stepped_lanes_are_the_widths_the_programs_were_cut_at():
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(DENSE + " " + CONFIG["app"])
        rt.start()
        h = rt.get_input_handler(CONFIG["stream"])
        total = 0
        for name, (times, lanes) in LANES.items():
            h.send_batch(keyed(times))
            total += lanes
            assert stat(rt.statistics(), "steppedLanes") == total, name
        rt.shutdown()
    finally:
        m.shutdown()


# name -> (events a card, the events past their card's first)
REPEATS = {
    "even": ([1] * 120, 0),
    "five_of_one_partition": ([5], 4),
    "two_rounds": (LANES["two_rounds"][0], 20),
    "skewed": (LANES["skewed"][0], 299 + 600),
}


def test_planned_repeats_are_the_events_past_their_cards_first():
    """(The cell's own batch at its rehearsal size, 1,929 of 5,369:
    ``test_dense_rows_equal_the_host_engines[four_rounds]``.)"""
    from siddhi_tpu.core.ingest_stage import IngestStats

    assert IngestStats().as_dict()["plannedRepeats"] == 0
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(DENSE + " " + CONFIG["app"])
        rt.start()
        h = rt.get_input_handler(CONFIG["stream"])
        total = 0
        for name, (times, repeats) in REPEATS.items():
            h.send_batch(keyed(times))
            total += repeats
            assert stat(rt.statistics(), "plannedRepeats") == total, name
        rt.shutdown()
    finally:
        m.shutdown()
