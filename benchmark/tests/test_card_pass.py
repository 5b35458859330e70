"""The card mix's own promises (``generators/card_pass.py``), on the CPU
with no device work: the same four round widths in every batch of every
seed, every card's events a pass as the traffic file's table says, rows
owed as the scripts say, amounts exact in float32 that name their card,
passes that repeat, and a control that changes an owed row.  The cell's
rehearsal, its control and a planted wrong answer are cases of
``test_benchmark.py``, which runs every cell of ``BENCHMARK.json``; the
reference against the host engine is tier-1's
``tests/test_cardfraud_reference.py``.

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

import card_pass  # noqa: E402
from fraud_pass import PASS_GAP_MS  # noqa: E402
from references import pattern_count_capture  # noqa: E402

CELL = "cardfraud_100k.saturated"


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


SPEC = _load(ROOT, "BENCHMARK.json")
CONFIG = _load(BENCH, "configs", "cardfraud_100k.json")
TRAFFIC = _load(BENCH, "traffic", "card_pass_saturated.json")
REF = CONFIG["reference"]
# size -> (cards, events a batch, the widths of a batch's four rounds,
# rows owed a pass)
SIZES = {"full": (100_000, 131_072, [84_000, 39_024, 7_024, 1_024], 7_168),
         "rehearsal": (4_096, 5_369, [3_440, 1_599, 288, 42], 294)}
SEEDS = [0, 1, 2, 3, 4, 2**31 + 5]


@pytest.fixture(scope="module", params=[
    (size, seed) for size in SIZES for seed in SEEDS],
    ids=lambda p: f"{p[0]}-{p[1]}")
def made(request):
    size, seed = request.param
    return size, card_pass.make(seed, CONFIG, TRAFFIC, size == "rehearsal")


def a_pass(schedule, p=0):
    return [schedule.batch(n) for n in range(p * schedule.per_pass,
                                             (p + 1) * schedule.per_pass)]


def test_the_cell_names_this_mix():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cardfraud_100k", "card_pass_saturated", 1)
    assert TRAFFIC["generator"] == "card_pass" and TRAFFIC["loop"] == "closed"
    assert CELL in next(m for m in SPEC["end_to_end"]
                        if m["name"] == "events_per_s")["workloads"]
    assert CONFIG["reduced"] == {} and REF["within_ms"] < PASS_GAP_MS
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"events.stepped_lanes_per_batch", "events.rounds_per_batch",
            "events.plan_ms_per_batch", "events.kleene_ms_per_batch",
            "events.rounds_ms_per_batch"} <= listed
    assert "events.run_kernel_ms_per_batch" not in listed


def test_every_batch_is_cut_into_the_same_four_rounds(made):
    size, schedule = made
    n_keys, batch, widths, _rows = SIZES[size]
    assert schedule.per_pass == schedule.warmup == 2
    assert schedule.batch_events == batch > n_keys
    for b in a_pass(schedule):
        _, counts = np.unique(b.columns["card"], return_counts=True)
        assert [int((counts > r).sum()) for r in range(5)] == widths + [0]


def test_every_cards_events_a_pass_are_as_the_table_says(made):
    size, schedule = made
    n_keys, _batch, _widths, _rows = SIZES[size]
    a, b = (collections.Counter(x.columns["card"].tolist())
            for x in a_pass(schedule))
    assert len(set(a) | set(b)) == n_keys == len(schedule.all_keys)
    tested = set(schedule.script_of)
    splits = collections.Counter(
        (a[k], b[k]) for k in schedule.all_keys.tolist() if k not in tested)
    table = TRAFFIC[size]
    assert splits == {
        (1, 1): table["normal_1_1"],
        (2, 1): table["normal_2_1"], (1, 2): table["normal_2_1"],
        (3, 0): table["normal_3_0"], (0, 3): table["normal_3_0"],
        (2, 0): table["normal_2_0"], (0, 2): table["normal_2_0"]}
    by_script = collections.Counter(schedule.script_of.values())
    assert [by_script[s] for s in range(3)] == table["tested"]
    for k, s in schedule.script_of.items():
        half = len(card_pass.SCRIPTS[s]) // 2
        assert (a[k], b[k]) == (half, half)


def by_card(batches):
    """``card -> [(n, ts, amount)]`` in arrival order, as the reference
    takes them."""
    out = collections.defaultdict(list)
    for n, b in enumerate(batches):
        for c, amount, ts in zip(b.columns["card"].tolist(),
                                 b.columns["amount"].tolist(),
                                 b.timestamps.tolist()):
            out[c].append((n, ts, amount))
    return out


def owed(events):
    return {c: pattern_count_capture._count_rows(
        evs, REF["count"], REF["within_ms"]) for c, evs in events.items()}


def test_the_scripts_owe_what_they_say_and_no_other_card_owes(made):
    size, schedule = made
    events = by_card(a_pass(schedule))
    rows = owed(events)
    for c, mine in rows.items():
        s = schedule.script_of.get(c)
        assert len(mine) == (card_pass.ROWS_OWED[s] if s is not None else 0)
    assert sum(map(len, rows.values())) == SIZES[size][3]
    # a tested card's whole amounts are its script's, in arrival order
    for c, s in list(schedule.script_of.items())[::97]:
        assert [int(e[2]) for e in events[c]] == list(card_pass.SCRIPTS[s])
    # a normal card never has four events inside ten minutes, so it
    # neither completes nor fills four instance lanes
    assert max(len(evs) for c, evs in events.items()
               if c not in schedule.script_of) == 3


def test_amounts_are_exact_in_float32_and_name_their_card(made):
    _size, schedule = made
    for b in a_pass(schedule):
        amount, cards = b.columns["amount"], b.columns["card"]
        assert amount.dtype == np.float32 and cards.dtype == np.int64
        assert b.columns["merchant"].dtype == np.int32
        assert (schedule.row_keys({"a0": amount}) == cards).all()
        whole = np.floor(amount.astype(np.float64))
        assert whole.min() >= 0 and whole.max() < card_pass.WHOLE_MAX
        # the float32 holds whole + (id + 1) / 2**17 with no rounding
        frac = (amount.astype(np.float64) - whole) * (1 << card_pass.FRAC_BITS)
        assert (frac == np.rint(frac)).all() and frac.min() >= 1
        assert amount.min() > 0     # every transaction passes a's filter
    normal = ~np.isin(cards, schedule.active_keys)
    assert set(np.unique(whole[normal]).tolist()) == set(
        range(card_pass.NORMAL_WHOLE))


def test_passes_repeat_past_within(made):
    _size, schedule = made
    for n in (-2, -1, 0, 1, 5):
        a, b = schedule.batch(n), schedule.batch(n + schedule.per_pass)
        for c in card_pass.COLUMNS:
            assert (a.columns[c] == b.columns[c]).all()
        assert set((b.timestamps - a.timestamps).tolist()) == {PASS_GAP_MS}
        assert schedule.twin(n + 2) == (n + 2) % 2
        assert (schedule.batch_of(a.timestamps) == n).all()
    span = schedule.batch(1).timestamps[0] - schedule.batch(0).timestamps[0]
    assert span + REF["within_ms"] < PASS_GAP_MS


def test_the_control_changes_an_owed_row():
    """Rounded to bfloat16 an amount loses the fraction that names its
    card, and more: every owed row differs."""
    schedule = card_pass.make(7, CONFIG, TRAFFIC, rehearsal=True)
    assert CONFIG["control"]["round_bf16"] == ["amount"]
    exact = owed(by_card(a_pass(schedule)))
    rounded = []
    for b in a_pass(schedule):
        cols = dict(b.columns)
        cols["amount"] = cols["amount"].astype(ml_dtypes.bfloat16).astype(
            np.float32)
        rounded.append(type(b)(b.stream_id, b.attribute_names, cols,
                               b.timestamps))
    lossy = owed(by_card(rounded))
    want = {r for rows in exact.values() for r in rows}
    got = {r for rows in lossy.values() for r in rows}
    assert len(want) == 294 and not want & got
