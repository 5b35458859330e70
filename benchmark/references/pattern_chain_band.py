"""The plain reference of a followed-by chain whose head has a band:
``every e1=[lo < temp < hi] -> e2=[temp > 1 and temp > e1.temp] -> ...
-> e<states>=[temp > states - 1 and temp > e1.temp] within`` per device,
in plain Python over the batches the schedule re-makes from the seed.
Imports nothing of the program.

Per device a list of pending arms ``(e1.temp, e1.ts, states matched)``.
An event first drops the arms older than ``within``; then it advances
every arm whose next threshold and whose ``e1.temp`` it passes, one
state an event (``->`` ignores an event that does not: the arm waits),
and the arm that matches its last state owes a row and goes; then the
event opens an arm of its own if it lies inside the head's band (``every``:
the head stays armed).  Two readings of one timestamp are taken in
arrival order."""

from __future__ import annotations

import collections

import numpy as np


def _band_rows(events, states: int, within_ms: int, band):
    """The chain over one device's ``(n, ts, temp)`` events in arrival
    order: a row is ``(n, ts, e1.temp, e<states>.temp)``."""
    lo, hi = band
    rows, pending = [], []   # pending: (e1.temp, e1.ts, states matched)
    for n, ts, temp in events:
        nxt = []
        for t1, ts1, k in pending:
            if ts - ts1 > within_ms:
                continue
            if temp > k and temp > t1:
                if k + 1 == states:
                    rows.append((n, ts, t1, temp))
                    continue
                k += 1
            nxt.append((t1, ts1, k))
        if lo < temp < hi:
            nxt.append((temp, ts, 1))
        pending = nxt
    return rows


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """Every hot device and a seeded sample of the swept ones through
    ``_band_rows`` over the first window pass and one seeded other
    pass, payloads and timestamps compared exactly and with
    multiplicity (``spec["row"]`` names the two payload columns); every
    other batch must deliver as many rows as its twin in the first
    pass; no row may name a device that was only swept; one device's
    rows arrive in event-time order."""
    rng = np.random.default_rng(seed + 1)
    per_pass = schedule.per_pass
    hot = schedule.active_keys
    others = np.setdiff1d(schedule.all_keys, hot)
    k = spec["rehearsal_swept_devices" if rehearsal else "swept_devices"]
    sample = np.concatenate([hot, rng.choice(others, min(k, len(others)),
                                             replace=False)])
    n_passes = -(-n_sent // per_pass)
    passes = [0] + ([int(rng.integers(1, n_passes))] if n_passes > 1 else [])
    checked = [n for p in passes
               for n in range(p * per_pass, min((p + 1) * per_pass, n_sent))]

    by_device = {}
    for n in checked:
        b = schedule.batch(n)
        devices, temp = b.columns["device"], b.columns["temp"]
        for i in np.flatnonzero(np.isin(devices, sample)):
            by_device.setdefault(int(devices[i]), []).append(
                (n, int(b.timestamps[i]), float(temp[i])))
    want = [r for events in by_device.values() for r in _band_rows(
        events, spec["states"], spec["within_ms"], spec["head_band"])]

    rows = collector.rows()
    bad = set()
    if rows is None:
        got, strays, disorder = [], 0, 0
    else:
        keys = schedule.row_keys(rows)
        pick = np.isin(rows["_n"], checked) & np.isin(keys, sample)
        # float32 payloads, widened exactly: equal or not, no tolerance
        got = list(zip(rows["_n"][pick].tolist(), rows["_ts"][pick].tolist(),
                       *(rows[c][pick].astype(np.float64).tolist()
                         for c in spec["row"])))
        stray = ~np.isin(keys, hot)
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
        (f"sampled rows that differ from the reference ({len(sample)} "
         f"devices, passes {passes}, {len(want)} rows owed)",
         sum(differ.values()), 0),
        ("rows of devices that were only swept", strays, 0),
        ("rows of one device out of event-time order", disorder, 0),
        (f"batches whose row count differs from the first pass's "
         f"({n_sent} batches)", len(uneven), 0),
        # a run that owes nothing checks nothing: limit is at least one row
        ("rows owed on the sample: none", int(not want), 0)]
    if not want:
        bad |= set(checked)
    return bad, compared
