"""The plain reference of a pattern cell: ``every e1 -> ... -> e<states>
within`` per key, in plain Python over the batches the schedule re-makes
from the seed.  Imports nothing of the program."""

from __future__ import annotations

import collections

import numpy as np


def _chain_rows(events, states: int, within_ms: int):
    """``every e1=[v>0] -> e2=[v>1 and v>e1.v] -> ... -> e<states>
    within`` over one key's ``(n, ts, v)`` events in arrival order: a
    match is ``(n, e1.v, e<states>.v)``."""
    rows, pending = [], []   # pending: (e1.v, e1.ts, states matched)
    for n, ts, v in events:
        nxt = []
        for v1, t1, k in pending:
            if ts - t1 > within_ms:
                continue
            if v > k and v > v1:
                if k + 1 == states:
                    rows.append((n, v1, v))
                    continue
                k += 1
            nxt.append((v1, t1, k))
        if v > 0.0:
            nxt.append((v, ts, 1))
        pending = nxt
    return rows


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """Every active key and a seeded sample of the swept ones through
    ``_chain_rows`` over the first window pass and one seeded other
    pass, payloads compared exactly; every other batch must deliver as
    many rows as its twin in the first pass; no row may belong to a key
    that was only swept; one key's rows arrive in event-time order."""
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
        keys, v = b.columns["key"], b.columns["v"]
        for i in np.flatnonzero(np.isin(keys, sample)):
            by_key.setdefault(int(keys[i]), []).append(
                (n, int(b.timestamps[i]), float(v[i])))
    want = [r for evs in by_key.values() for r in _chain_rows(
        evs, spec["states"], spec["within_ms"])]

    rows = collector.rows()
    bad = set()
    if rows is None:
        got, strays, disorder = [], 0, 0
    else:
        keys = schedule.row_keys(rows)
        pick = np.isin(rows["_n"], checked) & np.isin(keys, sample)
        got = list(zip(rows["_n"][pick].tolist(),
                       rows["v1"][pick].astype(np.float64).tolist(),
                       rows["v16"][pick].astype(np.float64).tolist()))
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
        (f"sampled rows that differ from the reference ({len(sample)} keys,"
         f" passes {sorted(passes)}, {len(want)} rows owed)",
         sum(differ.values()), 0),
        ("rows of keys that were only swept", strays, 0),
        ("rows of one key out of event-time order", disorder, 0),
        (f"batches whose row count differs from the first pass's "
         f"({n_sent} batches)", len(uneven), 0),
        # a run that owes nothing checks nothing: limit is at least one row
        ("rows owed on the sample: none", int(not want), 0)]
    if not want:
        bad |= set(checked)
    return bad, compared
