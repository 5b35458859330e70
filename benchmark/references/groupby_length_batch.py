"""The plain reference of a group-by over tumbling length panes: the
stream position of every row is fixed by the seed, so a pane's place is
too; each (pane, group) segment is summed in float64 over the batches
the schedule re-makes.  Imports nothing of the program."""

from __future__ import annotations

import numpy as np

from lib.check import EPS32


class _Codes:
    """Group keys as small integers.  The schedule hands the same
    column object again when it sends a ring batch again: coded once."""

    def __init__(self):
        self.table, self.seen = {}, {}

    def of(self, column):
        hit = self.seen.get(id(column))
        if hit is None or hit[0] is not column:
            codes = np.fromiter(
                (self.table.setdefault(k, len(self.table))
                 for k in column.tolist()), dtype=np.int64,
                count=len(column))
            hit = self.seen[id(column)] = (column, codes)
        return hit[1]


def owed(spec, schedule, n, rows_sent, codes, values=True):
    """What batch ``n`` owes: one row per pane and group whose last
    event of the pane lies in the batch, in the order of those events.
    A pane is ``length`` consecutive rows of the stream (``(n + warmup)
    * batch`` rows precede batch ``n``); a pane that the ``rows_sent``
    rows of the run leave open owes nothing.  The neighbouring batches
    are re-made for the panes that straddle.  Returns the number of
    rows and, with ``values``, their columns in float64."""
    L, B = spec["length"], schedule.batch_events
    start = (n + schedule.warmup) * B
    lo = start // L * L
    hi = min(-(-(start + B) // L) * L, rows_sent // L * L)
    if hi <= lo:
        return 0, None
    want = [spec["group"]] + (
        [spec["sum"], spec["avg"], spec["last"]] if values else [])
    parts = {k: [] for k in want + ["_ts"]}
    for m, a, b in ((n - 1, B - (start - lo), B), (n, 0, min(B, hi - start)),
                    (n + 1, 0, hi - start - B)):
        if b <= a:
            continue
        batch = schedule.batch(m)
        for k in want:
            col = batch.columns[k]
            parts[k].append((codes.of(col) if k == spec["group"]
                             else np.asarray(col))[a:b])
        parts["_ts"].append(np.asarray(batch.timestamps)[a:b])
    cols = {k: np.concatenate(v).reshape(-1, L) for k, v in parts.items()}
    g = cols[spec["group"]]
    same = g[:, :, None] == g[:, None, :]            # [pane, i, j]
    later = np.triu(np.ones((L, L), dtype=bool), k=1)
    pos = lo + np.arange(hi - lo).reshape(-1, L)
    pick = ~(same & later).any(axis=2) & (pos >= start) & (pos < start + B)
    if not values:
        return int(pick.sum()), None
    cnt = same.sum(axis=2)
    out = {"total": (same * cols[spec["sum"]].astype(
               np.float64)[:, None, :]).sum(axis=2)[pick],
           "avgVolume": ((same * cols[spec["avg"]].astype(
               np.float64)[:, None, :]).sum(axis=2) / cnt)[pick],
           "timestamp": cols[spec["last"]][pick], "_ts": cols["_ts"][pick],
           "group": g[pick]}
    return int(pick.sum()), out


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """``#window.lengthBatch(L) select symbol, sum(price), avg(volume),
    timestamp group by symbol``.  In every batch of the window the
    count of rows stamped in it against the count the seed owes; on the
    batches whose rows were all kept (the first, the last) every row,
    matched by its ``timestamp`` column, which names one event."""
    limit = spec["rtol_eps32"] * EPS32
    codes = _Codes()
    rows_sent = (n_sent + schedule.warmup) * schedule.batch_events
    rows = collector.rows()
    bad = set()
    for n in range(n_sent):
        if owed(spec, schedule, n, rows_sent, codes, values=False)[0] \
                != collector.counts.get(n, 0):
            bad.add(n)
    uneven = len(bad)
    worst, exact_off, missing, extra, disorder, checked = 0.0, 0, 0, 0, 0, 0
    for n in (np.unique(rows["_n"]).tolist() if rows is not None else []):
        at = rows["_n"] == n
        if not 0 <= n < n_sent or int(at.sum()) != collector.counts.get(n):
            continue    # a warm-up batch, or one whose rows were not all kept
        checked += 1
        got = {k: v[at] for k, v in rows.items()}
        _count, ref = owed(spec, schedule, n, rows_sent, codes)
        place = {int(t): i for i, t in enumerate(ref["timestamp"])} \
            if ref else {}
        hit = np.asarray([place.get(int(t), -1) for t in got["timestamp"]],
                         dtype=np.int64)
        found = hit >= 0
        lost = len(place) - len(set(hit[found].tolist()))
        spare = int((~found).sum())
        swapped = int((np.diff(got["timestamp"].astype(np.int64)) <= 0).sum())
        off, err = 0, 0.0
        if found.any():
            r = {k: v[hit[found]] for k, v in ref.items()}
            off = int((got["_ts"][found] != r["_ts"]).sum() + (
                codes.of(got["symbol"])[found] != r["group"]).sum())
            for out in ("total", "avgVolume"):
                err = max(err, float(np.max(
                    np.abs(got[out][found].astype(np.float64) - r[out])
                    / np.abs(r[out]).clip(1.0))))
        worst = max(worst, err)
        exact_off, missing, extra = exact_off + off, missing + lost, extra + spare
        disorder += swapped
        if off or lost or spare or swapped or not err <= limit:
            bad.add(n)
    if not checked:
        bad |= set(range(n_sent))
    compared = [
        (f"worst relative error of sum(price), avg(volume) "
         f"({checked} batches of {n_sent})", worst, limit),
        ("rows whose symbol or event timestamp differs", exact_off, 0),
        ("rows owed and not delivered", missing, 0),
        ("rows delivered and not owed", extra, 0),
        ("rows out of order", disorder, 0),
        (f"batches whose row count is not the count the seed owes "
         f"(all {n_sent})", uneven, 0),
        ("batches checked against the reference: none", int(not checked), 0)]
    return bad, compared
