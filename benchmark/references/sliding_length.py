"""The plain reference of a sliding length window: float64 prefix sums
over the batches the schedule re-makes from the seed.  Imports nothing
of the program."""

from __future__ import annotations

import numpy as np

from lib.check import EPS32


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """``#window.length(L) select symbol, sum(price), avg(volume),
    timestamp`` in float64 by prefix sums, on the batches whose rows
    were kept (the first, the last, a seeded sample between); every
    other batch must deliver one row per tick."""
    L, B = spec["length"], schedule.batch_events
    limit = spec["rtol_eps32"] * EPS32
    rows = collector.rows()
    bad = {n for n in range(n_sent) if collector.counts.get(n, 0) != B}
    uneven = len(bad)
    worst, exact_off, checked = 0.0, 0, 0
    for n in (np.unique(rows["_n"]).tolist() if rows is not None else []):
        if n in bad or n >= n_sent:
            continue
        prev, cur = schedule.batch(n - 1).columns, schedule.batch(n).columns
        got = {k: v[rows["_n"] == n] for k, v in rows.items()}
        checked += 1
        off = int((got["symbol"] != cur["symbol"]).sum()
                  + (got["timestamp"] != cur["timestamp"]).sum())
        err = 0.0
        for out, col, div in (("total", spec["sum"], 1.0),
                              ("avgVolume", spec["avg"], float(L))):
            x = np.concatenate([prev[col][-(L - 1):], cur[col]]).astype(
                np.float64)
            c = np.concatenate([[0.0], np.cumsum(x)])
            ref = (c[L:] - c[:-L]) / div
            err = max(err, float(np.max(
                np.abs(got[out].astype(np.float64) - ref)
                / np.abs(ref).clip(1.0))))
        worst = max(worst, err)
        exact_off += off
        if off or not err <= limit:
            bad.add(n)
    if not checked:
        bad |= set(range(n_sent))
    compared = [
        (f"worst relative error of sum(price), avg(volume) "
         f"({checked} batches of {n_sent})", worst, limit),
        ("symbols and timestamps that differ", exact_off, 0),
        ("batches that did not deliver one row per tick", uneven, 0),
        ("batches checked against the reference: none", int(not checked), 0)]
    return bad, compared
