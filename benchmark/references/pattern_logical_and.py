"""The plain reference of a logical pattern over two streams: ``every
(t=StockTick[price > 0] and n=NewsEvent[sentiment > 0]) within`` per
symbol, in plain Python over the batches the schedule re-makes from the
seed.  Imports nothing of the program.

It follows upstream's ``LogicalPreStateProcessor`` and
``LogicalPostStateProcessor`` for ``and`` (``LogicalPatternTestCase``):
a side that has its event leaves that side's pending list, so a second
event of the stream is not taken, and ``every`` arms anew only when the
node completes.  Where it departs: an arm's age is counted from its
first event (upstream reads the start state's timestamp, which for a
start node is the same event); two events of one timestamp are taken in
arrival order; an event that fails its side's filter is as if it had not
come (the traffic has none)."""

from __future__ import annotations

import collections

import numpy as np

ROW = ("price", "sentiment")
TICK, NEWS = 0, 1


def _and_rows(events, within_ms: int, arm=None):
    """The automaton over one symbol's ``(n, ts, side, value)`` events in
    arrival order, from ``arm``: None, or ``(ts of its first event, the
    tick's price or None, the headline's sentiment or None)``.  An event
    first drops an arm older than ``within_ms``; then it fills its own
    side if that is empty, opening the arm if there is none (a filled
    side ignores the event); an arm with both sides owes ``(n, ts,
    t.price, n.sentiment)`` at that event and goes.  Returns the rows
    and the arm left."""
    rows = []
    for n, ts, side, value in events:
        if arm is not None and ts - arm[0] > within_ms:
            arm = None
        if value <= 0:
            continue
        if arm is None:
            arm = (ts, None, None)
        if arm[1 + side] is None:
            arm = (arm[0], value, arm[2]) if side == TICK else (
                arm[0], arm[1], value)
            if arm[1] is not None and arm[2] is not None:
                rows.append((n, ts, arm[1], arm[2]))
                arm = None
    return rows, arm


def _pass_events(schedule, sample):
    """``symbol -> [(place, ms since the pass began, side, value)]`` of
    one pass, for the symbols of ``sample``: read off the warm-up pass,
    which every pass repeats (``schedule.twin``)."""
    first = -schedule.warmup
    t0 = int(schedule.batch(first).timestamps[0])
    out = {}
    for place in range(schedule.per_pass):
        b = schedule.batch(first + place)
        side = NEWS if "sentiment" in b.columns else TICK
        symbols, value = b.columns["symbol"], b.columns[ROW[side]]
        for i in np.flatnonzero(np.isin(symbols, sample)):
            out.setdefault(int(symbols[i]), []).append(
                (place, int(b.timestamps[i]) - t0, side, float(value[i])))
    return out


def _owed(events, within_ms: int, pass_ms: int, passes):
    """Rows one symbol owes in each pass of ``passes`` (0 the warm-up),
    as ``{pass: rows}`` with a row's ``n`` and ``ts`` counted from its
    pass's beginning.  The arm a pass leaves is the arm the next begins
    with, ``pass_ms`` older; a pass is the same events every time, so
    what it owes is a function of the arm it begins with, computed by
    the automaton once for each arm met and remembered."""
    seen, arm, out = {}, None, {}
    for p in range(max(passes) + 1):
        if arm not in seen:
            seen[arm] = _and_rows(events, within_ms, arm)
        rows, left = seen[arm]
        if p in passes:
            out[p] = rows
        arm = None if left is None else (left[0] - pass_ms, *left[1:])
    return out


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """What ``pattern_kleene`` compares, through ``_and_rows``: every hot
    symbol and a seeded sample of the others over the first window pass
    and one seeded other pass of those the schedule kept, payloads and
    timestamps compared exactly and with multiplicity; every other batch
    must deliver as many rows as its twin in the first pass; one
    symbol's rows of the kept passes arrive in event-time order.  The
    automaton runs from the warm-up's first event: arms live across
    passes."""
    rng = np.random.default_rng(seed + 1)
    per_pass = schedule.per_pass
    hot = schedule.active_keys
    others = np.setdiff1d(schedule.all_keys, hot)
    k = spec["rehearsal_sampled_symbols" if rehearsal else "sampled_symbols"]
    sample = np.concatenate([hot, rng.choice(
        others, min(k, len(others)), replace=False)])
    n_passes = -(-n_sent // per_pass)
    kept = [p for p in range(1, n_passes) if schedule.keep(p * per_pass)]
    passes = [0] + ([int(rng.choice(kept))] if kept else [])
    checked = [n for p in passes
               for n in range(p * per_pass, min((p + 1) * per_pass, n_sent))]

    t0 = schedule.ts_of(0)
    pass_ms = schedule.ts_of(per_pass) - t0
    want = []
    for events in _pass_events(schedule, sample).values():
        # pass p of the window is pass p + 1 of the run: the warm-up is 0
        owed = _owed(events, spec["within_ms"], pass_ms,
                     {p + 1 for p in passes})
        want += [(p * per_pass + n, t0 + p * pass_ms + ts, price, sentiment)
                 for p in passes for n, ts, price, sentiment in owed[p + 1]
                 if p * per_pass + n < n_sent]

    rows = collector.rows()
    bad = set()
    if rows is None:
        got, disorder = [], 0
    else:
        keys = schedule.row_keys(rows)
        pick = np.isin(rows["_n"], checked) & np.isin(keys, sample)
        # float32 payloads, widened exactly: equal or not, no tolerance
        got = list(zip(rows["_n"][pick].tolist(), rows["_ts"][pick].tolist(),
                       *(rows[c][pick].astype(np.float64).tolist()
                         for c in ROW)))
        # the collector holds the kept batches by their place in the run
        # and, behind them, its newest batch of a pass not kept, which
        # may be older than the last kept pass: order is judged on the
        # kept ones
        ns, inverse = np.unique(rows["_n"], return_inverse=True)
        held = np.array([schedule.keep(int(n)) for n in ns],
                        dtype=bool)[inverse]
        order = np.flatnonzero(held)[np.argsort(keys[held], kind="stable")]
        back = (np.diff(rows["_ts"][order]) < 0) & (np.diff(keys[order]) == 0)
        disorder = int(back.sum())
        bad |= set(rows["_n"][order][1:][back].tolist())
    want_c, got_c = collections.Counter(want), collections.Counter(got)
    differ = (want_c - got_c) + (got_c - want_c)   # rows, with multiplicity
    bad |= {r[0] for r in differ}
    uneven = [n for n in range(n_sent)
              if collector.counts.get(n, 0)
              != collector.counts.get(schedule.twin(n), 0)]
    bad |= set(uneven)
    compared = [
        (f"sampled rows that differ from the reference ({len(sample)} "
         f"symbols, passes {passes}, {len(want)} rows owed)",
         sum(differ.values()), 0),
        ("rows of one symbol out of event-time order", disorder, 0),
        (f"batches whose row count differs from the first pass's "
         f"({n_sent} batches)", len(uneven), 0),
        # a run that owes nothing checks nothing: limit is at least one row
        ("rows owed on the sample: none", int(not want), 0)]
    if not want:
        bad |= set(checked)
    return bad, compared
