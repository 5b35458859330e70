"""The plain reference of a counting pattern cell: ``every e1=Login[ok
== 0]<min:> -> e2=Login[ok == 1] within`` per user, in plain Python over
the batches the schedule re-makes from the seed.  Imports nothing of the
program."""

from __future__ import annotations

import collections

import numpy as np

FAIL, SUCCESS = 0, 1
ROW = ("firstIp", "lastIp", "okIp")


def _kleene_rows(events, min_count: int, within_ms: int):
    """The counting automaton over one user's ``(n, ts, ok, ip)`` events
    in arrival order.  An *arm* is one pending ``e1``: it begins at a
    fail when no arm is still under ``min_count`` (``every`` re-arms the
    head when a count reaches its minimum), captures every later fail
    too (an open count stays pending for the success while it goes on
    counting), and is dropped once it is older than ``within_ms``.  A
    success takes every arm at or over the minimum and owes a row for
    each, oldest first: ``(n, e1[0].ip, e1[last].ip, e2.ip)``, its own
    first fail and the last one, which they share.  An arm under the
    minimum outlives the success; any other outcome moves nothing."""
    rows, arms = [], []     # an arm: [ts of its first fail, its ip, count]
    last = None             # ip of the newest fail: every arm's e1[last]
    for n, ts, ok, ip in events:
        arms = [a for a in arms if ts - a[0] <= within_ms]
        if ok == FAIL:
            if all(a[2] >= min_count for a in arms):
                arms.append([ts, ip, 0])
            for a in arms:
                a[2] += 1
            last = ip
        elif ok == SUCCESS:
            rows += [(n, a[1], last, ip) for a in arms if a[2] >= min_count]
            arms = [a for a in arms if a[2] < min_count]
    return rows


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """What ``pattern_chain`` compares, through ``_kleene_rows``: every
    attacked user and a seeded sample of the swept ones over the first
    window pass and one seeded other pass, payloads compared exactly;
    every other batch must deliver as many rows as its twin in the first
    pass; no row may belong to a user that was only swept; one user's
    rows arrive in event-time order."""
    rng = np.random.default_rng(seed + 1)
    per_pass = schedule.per_pass
    active = schedule.active_keys
    sample = np.concatenate([active, rng.choice(
        np.setdiff1d(schedule.all_keys, active),
        spec["rehearsal_swept_keys" if rehearsal else "swept_keys"],
        replace=False)])
    n_passes = -(-n_sent // per_pass)
    passes = {0} | ({int(rng.integers(1, n_passes))} if n_passes > 1 else set())
    checked = [n for p in sorted(passes)
               for n in range(p * per_pass, min((p + 1) * per_pass, n_sent))]

    by_key = {}
    for n in checked:
        b = schedule.batch(n)
        users, ok, ip = (b.columns[c] for c in ("user", "ok", "ip"))
        for i in np.flatnonzero(np.isin(users, sample)):
            by_key.setdefault(int(users[i]), []).append(
                (n, int(b.timestamps[i]), int(ok[i]), int(ip[i])))
    want = [r for evs in by_key.values() for r in _kleene_rows(
        evs, spec["min_count"], spec["within_ms"])]

    rows = collector.rows()
    bad = set()
    if rows is None:
        got, strays, disorder = [], 0, 0
    else:
        keys = schedule.row_keys(rows)
        pick = np.isin(rows["_n"], checked) & np.isin(keys, sample)
        got = list(zip(rows["_n"][pick].tolist(), *(
            rows[c][pick].astype(np.int64).tolist() for c in ROW)))
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
        (f"sampled rows that differ from the reference ({len(sample)} users,"
         f" passes {sorted(passes)}, {len(want)} rows owed)",
         sum(differ.values()), 0),
        ("rows of users that were only swept", strays, 0),
        ("rows of one user out of event-time order", disorder, 0),
        (f"batches whose row count differs from the first pass's "
         f"({n_sent} batches)", len(uneven), 0),
        # a run that owes nothing checks nothing: limit is at least one row
        ("rows owed on the sample: none", int(not want), 0)]
    if not want:
        bad |= set(checked)
    return bad, compared
