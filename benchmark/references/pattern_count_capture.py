"""The plain reference of a counted pattern whose filter reads a
capture: ``every a=Txn[amount > 0] -> b=Txn[amount > a.amount]<count>
within`` per card, in plain Python over the batches the schedule
re-makes from the seed.  Imports nothing of the program."""

from __future__ import annotations

import collections

import numpy as np

ROW = ("a0", "b0", "b2")


def _count_rows(events, count: int, within_ms: int):
    """The automaton over one card's ``(n, ts, amount)`` events in
    arrival order.  An *instance* is one pending ``a``.  An event first
    drops the instances older than ``within_ms``; then every pending
    instance whose ``a.amount`` is under the event's amount counts it
    (the first counted is its ``b[0]``, the newest its ``b[last]``), and
    one that reaches ``count`` owes ``(n, a.amount, b[0].amount,
    b[last].amount)`` at this event and goes, oldest first; then the
    event itself opens an instance if its amount is over 0 (``every``:
    each transaction is an ``a``).  A tie counts for nothing (``>`` is
    strict) and an amount under ``a``'s resets nothing: a pattern's
    count does not ask for events in a row."""
    rows, pending = [], []  # an instance: [ts of a, a.amount, count, b0, b_last]
    for n, ts, amount in events:
        pending = [p for p in pending if ts - p[0] <= within_ms]
        for p in pending:
            if amount > p[1]:
                p[2] += 1
                if p[2] == 1:
                    p[3] = amount
                p[4] = amount
        rows += [(n, p[1], p[3], p[4]) for p in pending if p[2] >= count]
        pending = [p for p in pending if p[2] < count]
        if amount > 0:
            pending.append([ts, amount, 0, None, None])
    return rows


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """What ``pattern_kleene`` compares, through ``_count_rows``: every
    tested card and a seeded sample of the normal ones over the first
    window pass and one seeded other pass, payloads compared exactly;
    every other batch must deliver as many rows as its twin in the first
    pass; no row may belong to a normal card; one card's rows arrive in
    event-time order."""
    rng = np.random.default_rng(seed + 1)
    per_pass = schedule.per_pass
    active = schedule.active_keys
    sample = np.concatenate([active, rng.choice(
        np.setdiff1d(schedule.all_keys, active),
        spec["rehearsal_sampled_cards" if rehearsal else "sampled_cards"],
        replace=False)])
    n_passes = -(-n_sent // per_pass)
    passes = {0} | ({int(rng.integers(1, n_passes))} if n_passes > 1 else set())
    checked = [n for p in sorted(passes)
               for n in range(p * per_pass, min((p + 1) * per_pass, n_sent))]

    by_card = {}
    for n in checked:
        b = schedule.batch(n)
        cards, amount = b.columns["card"], b.columns["amount"]
        for i in np.flatnonzero(np.isin(cards, sample)):
            by_card.setdefault(int(cards[i]), []).append(
                (n, int(b.timestamps[i]), float(amount[i])))
    want = [r for evs in by_card.values() for r in _count_rows(
        evs, spec["count"], spec["within_ms"])]

    rows = collector.rows()
    bad = set()
    if rows is None:
        got, strays, disorder = [], 0, 0
    else:
        keys = schedule.row_keys(rows)
        pick = np.isin(rows["_n"], checked) & np.isin(keys, sample)
        # float32 payloads, widened exactly: equal or not, no tolerance
        got = list(zip(rows["_n"][pick].tolist(), *(
            rows[c][pick].astype(np.float64).tolist() for c in ROW)))
        stray = ~np.isin(keys, active)
        strays = int(stray.sum())
        bad |= set(rows["_n"][stray].tolist())
        order = np.argsort(keys, kind="stable")
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
        (f"sampled rows that differ from the reference ({len(sample)} cards,"
         f" passes {sorted(passes)}, {len(want)} rows owed)",
         sum(differ.values()), 0),
        ("rows of normal cards", strays, 0),
        ("rows of one card out of event-time order", disorder, 0),
        (f"batches whose row count differs from the first pass's "
         f"({n_sent} batches)", len(uneven), 0),
        # a run that owes nothing checks nothing: limit is at least one row
        ("rows owed on the sample: none", int(not want), 0)]
    if not want:
        bad |= set(checked)
    return bad, compared
