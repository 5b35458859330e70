"""The plain reference of a stream-table join under upserts: per key the
table's current row, in arrays over all keys, replayed from the load
phase through every batch in send order.  An upsert batch is applied
whole, its last writer of each key winning (the event at the greatest
arrival index: what applying the events one by one leaves); a probe
batch is answered against the table as it stands at that point, event
by event in arrival order: an event whose key is live and whose
``greater`` column exceeds the row's ``than`` column owes one row, its
own columns beside the row's, under its own timestamp.  Imports nothing
of the program."""

from __future__ import annotations

import numpy as np


def find(sorted_keys, wanted):
    """Where each of ``wanted`` lies in ``sorted_keys``: ``(index,
    found)``; the index is a valid one even where nothing was found."""
    at = np.searchsorted(sorted_keys, wanted).clip(
        0, max(len(sorted_keys) - 1, 0))
    found = (sorted_keys[at] == wanted) if len(sorted_keys) else np.zeros(
        len(wanted), dtype=bool)
    return at, found


class Table:
    """Current row per key: a liveness lane and one array a column."""

    def __init__(self, rows: int, first):
        self.live = np.zeros(rows, dtype=bool)
        self.cols = {k: np.zeros(rows, dtype=np.asarray(v).dtype)
                     for k, v in first.items()}

    def last_writers(self, keys):
        """Of each key an upsert batch writes, the arrival index of its
        last event: the first occurrence in the reversed batch."""
        uniq, first_rev = np.unique(keys[::-1], return_index=True)
        return uniq, len(keys) - 1 - first_rev

    def upsert(self, keys, cols):
        """Apply the batch; hand back what it overwrote and what it
        wrote, for the snapshot alarms: ``(keys, was_live, before,
        after)``."""
        uniq, last = self.last_writers(keys)
        was_live = self.live[uniq]
        before = {k: a[uniq] for k, a in self.cols.items()}
        after = {k: np.asarray(cols[k])[last] for k in self.cols}
        for k, a in self.cols.items():
            a[uniq] = after[k]
        self.live[uniq] = True
        return uniq, was_live, before, after

    def view(self, keys, patch=None):
        """The rows a probe of ``keys`` reads: liveness and columns per
        event.  ``patch`` = ``(patched_keys, live, cols)`` answers for
        those keys from the patch instead (another snapshot)."""
        live = self.live[keys]
        cols = {k: a[keys] for k, a in self.cols.items()}
        if patch is not None:
            pkeys, plive, pcols = patch
            at, hit = find(pkeys, keys)
            live[hit] = plive[at[hit]]
            for k in cols:
                cols[k][hit] = pcols[k][at[hit]]
        return live, cols


def answer(spec, batch, live, cols):
    """The rows a probe batch owes against the rows its events read:
    ``{column: values, "_ts": timestamps}`` in arrival order."""
    mine = {k: np.asarray(batch.columns[k]) for k in spec["stream_columns"]}
    owes = live & (mine[spec["greater"]] > cols[spec["than"]])
    out = {k: v[owes] for k, v in mine.items()}
    out.update({k: cols[k][owes] for k in spec["table_columns"]})
    out["_ts"] = np.asarray(batch.timestamps, dtype=np.int64)[owes]
    return out


def same(a, b) -> bool:
    return all(len(a[k]) == len(b[k]) and bool(np.all(a[k] == b[k]))
               for k in a)


def differing(a, b) -> int:
    """Rows of ``a`` that ``b`` does not hold alike, matched by their
    timestamp (one event, one millisecond)."""
    at, alike = find(b["_ts"], a["_ts"])
    for k in a:
        alike[alike] = b[k][at[alike]] == a[k][alike]
    return int((~alike).sum())


def judge_rows(ref, got):
    """Delivered rows of one probe batch against the rows it owes:
    ``(owed and not delivered, delivered and not owed, payload or
    timestamp differs, pairs out of arrival order)``.  Rows are matched
    by their timestamp; a timestamp delivered twice is once not owed."""
    ts_ref, ts_got = ref["_ts"], got["_ts"]
    at, found = find(ts_ref, ts_got)
    first = np.zeros(len(ts_got), dtype=bool)
    first[np.unique(ts_got, return_index=True)[1]] = True
    owed_seen = found & first
    stray = int((~owed_seen).sum())
    missing = len(ts_ref) - int(owed_seen.sum())
    differs = np.zeros(int(owed_seen.sum()), dtype=bool)
    for k in ref:
        differs |= np.asarray(got[k])[owed_seen] != ref[k][at[owed_seen]]
    disorder = int((np.diff(ts_got) <= 0).sum())
    return missing, stray, int(differs.sum()), disorder


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """``from Stream join Table on Stream.key == Table.key and
    Stream.greater > Table.than`` under ``update or insert into Table``.
    In every probe batch of the window the count of rows stamped in it
    against the rows the replay owes, and 0 rows in every upsert batch;
    on the probe batches whose rows were all kept (the first, the last,
    the seeded sample between) every row: owed and delivered, payload
    and timestamp, arrival order.  And the two alarms that hold the
    snapshot guarantee to something: of the kept probe batches directly
    after an upsert batch, how many a snapshot from before that upsert
    would answer alike; of those directly before one, how many a
    snapshot from after it would."""
    rows = collector.rows()
    whole = set(np.unique(rows["_n"]).tolist()) if rows is not None else set()
    key = spec["key"]
    table = None
    bad, uneven, checked = set(), 0, 0
    missing = stray = differs = disorder = 0
    after_upsert = stale_alike = stale_rows = 0
    before_upsert = early_alike = early_rows = 0
    undone = None        # the batch before, if an upsert: what it overwrote
    pending = None       # the batch before, if a kept probe: (batch, owed)
    for n in range(-schedule.warmup, n_sent):
        batch = schedule.batch(n)
        cards = np.asarray(batch.columns[key])
        if batch.stream_id == schedule.upsert_stream:
            if table is None:
                table = Table(schedule.rows, {
                    k: v for k, v in batch.columns.items() if k != key})
            uniq, was_live, before, after = table.upsert(cards, batch.columns)
            if pending is not None:     # a snapshot taken too late
                pbatch, powed = pending
                early = answer(spec, pbatch, *table.view(
                    np.asarray(pbatch.columns[key])))
                before_upsert += 1
                early_alike += same(early, powed)
                early_rows += differing(powed, early)
            undone, pending = (uniq, was_live, before), None
            if n >= 0 and collector.counts.get(n, 0):
                bad.add(n)
                uneven += 1
            continue
        owed = answer(spec, batch, *table.view(cards))
        stale_from, undone, pending = undone, None, None
        if n < 0:
            continue
        count = collector.counts.get(n, 0)
        if count != len(owed["_ts"]):
            bad.add(n)
            uneven += 1
        at_n = rows["_n"] == n if n in whole else None
        if at_n is not None and int(at_n.sum()) == count:
            checked += 1
            got = {k: v[at_n] for k, v in rows.items()}
            m, s, d, o = judge_rows(owed, got)
            missing, stray = missing + m, stray + s
            differs, disorder = differs + d, disorder + o
            if m or s or d or o:
                bad.add(n)
            pending = (batch, owed)
            if stale_from is not None:  # a snapshot taken too early
                stale = answer(spec, batch, *table.view(cards, stale_from))
                after_upsert += 1
                stale_alike += same(stale, owed)
                stale_rows += differing(owed, stale)
    if not checked:
        bad |= set(range(n_sent))
    compared = [
        (f"rows owed and not delivered ({checked} probe batches of "
         f"{n_sent} batches)", missing, 0),
        ("rows delivered and not owed", stray, 0),
        ("rows whose payload or timestamp differs", differs, 0),
        ("pairs of rows out of arrival order", disorder, 0),
        (f"batches whose row count is not what the replay owes (all "
         f"{n_sent}; an upsert batch owes none)", uneven, 0),
        (f"kept probe batches after an upsert that a stale snapshot would "
         f"answer alike (of {after_upsert}; it would change {stale_rows} of "
         f"their rows)", stale_alike, after_upsert - 1),
        (f"kept probe batches before an upsert that a snapshot taken after "
         f"it would answer alike (of {before_upsert}; it would change "
         f"{early_rows} of their rows)", early_alike, before_upsert - 1),
        ("batches checked against the reference: none", int(not checked), 0)]
    return bad, compared
