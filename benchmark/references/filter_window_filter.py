"""The plain reference of a three-query chain, filter -> sliding length
window -> filter: the ticks the head keeps are fixed by the seed, so is
every window over them; each is summed in float64 over the batches the
schedule re-makes.  Imports nothing of the program."""

from __future__ import annotations

import numpy as np

from lib.check import EPS32

OWED, FREE, FORBIDDEN = 1, 0, -1


def _kept(spec, batch):
    """The ticks of a batch the head filter keeps: ``keep_below >
    price`` on the float32 the stream carries, exact on both sides."""
    price = np.asarray(batch.columns[spec["keep"]]).astype(np.float32)
    return price < np.float32(spec["keep_below"])


def owed(spec, schedule, n):
    """What batch ``n`` owes.  Over the kept ticks by stream position,
    the window of a tick is the last ``length`` of them ending at it,
    carried across batches: the batches before ``n`` are re-made from
    the seed as far back as the window reaches (never past the stream's
    first batch, ``-warmup``, where a window holds what there is).
    Returns, for every kept tick of the batch in stream order, its event
    timestamp, the float64 ``total`` and ``avgVolume`` of its window and
    its class: a row is OWED where the total exceeds the threshold by
    more than the float32 contract, FORBIDDEN where it is under it by
    more, FREE in between (a float32 sum may stand on either side)."""
    L = spec["length"]
    batch = schedule.batch(n)
    keep = _kept(spec, batch)
    want = {spec["sum"], spec["avg"]}
    parts = {k: [np.asarray(batch.columns[k])[keep]] for k in want}
    before, m = 0, n - 1
    while before < L - 1 and m >= -schedule.warmup:
        prev = schedule.batch(m)
        tail = np.flatnonzero(_kept(spec, prev))[-(L - 1 - before):]
        for k in want:
            parts[k].insert(0, np.asarray(prev.columns[k])[tail])
        before += len(tail)
        m -= 1
    at = before + np.arange(int(keep.sum()))        # place in the joined rows
    lo = np.maximum(at + 1 - L, 0)
    sums = {}
    for k in want:
        c = np.concatenate([[0.0], np.cumsum(
            np.concatenate(parts[k]).astype(np.float64))])
        sums[k] = c[at + 1] - c[lo]
    total = sums[spec["sum"]]
    band = spec["rtol_eps32"] * EPS32 * abs(spec["threshold"])
    cls = np.where(total > spec["threshold"] + band, OWED,
                   np.where(total < spec["threshold"] - band, FORBIDDEN,
                            FREE))
    return {"_ts": np.asarray(batch.timestamps, dtype=np.int64)[keep],
            "total": total, "avgVolume": sums[spec["avg"]] / (at + 1 - lo),
            "class": cls}


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    """``[keep_below > price] -> #window.length(L) select sum, avg ->
    [total > threshold]``.  In every batch of the window the count of
    rows stamped in it against the rows the seed owes, within the
    batch's free windows; on the batches whose rows were all kept (the
    first, the last, the seeded sample between) every row, matched by
    its event timestamp, which names the tick that closed its window."""
    limit = spec["rtol_eps32"] * EPS32
    rows = collector.rows()
    whole = set(np.unique(rows["_n"]).tolist()) if rows is not None else set()
    bad, uneven, windows, free, checked = set(), 0, 0, 0, 0
    worst, missing, forbidden, disorder = 0.0, 0, 0, 0
    for n in range(n_sent):
        ref = owed(spec, schedule, n)
        n_owed = int((ref["class"] == OWED).sum())
        n_free = int((ref["class"] == FREE).sum())
        windows += len(ref["class"])
        free += n_free
        count = collector.counts.get(n, 0)
        if not n_owed <= count <= n_owed + n_free:
            bad.add(n)
            uneven += 1
        at_n = rows["_n"] == n if n in whole else None
        if at_n is None or int(at_n.sum()) != count:
            continue    # its rows were not all kept: the count alone
        checked += 1
        got = {k: v[at_n] for k, v in rows.items()}
        # event timestamps rise along the stream: a row's tick by search
        hit = np.searchsorted(ref["_ts"], got["_ts"]).clip(
            0, max(len(ref["_ts"]) - 1, 0))
        found = (ref["_ts"][hit] == got["_ts"]) if len(ref["_ts"]) \
            else np.zeros(len(hit), dtype=bool)
        stray = int((~found).sum()
                    + (ref["class"][hit[found]] == FORBIDDEN).sum())
        lost = n_owed - len(set(hit[found][
            ref["class"][hit[found]] == OWED].tolist()))
        swapped = int((np.diff(got["_ts"]) <= 0).sum())
        err = 0.0
        for out in ("total", "avgVolume"):
            if found.any():
                r = ref[out][hit[found]]
                err = max(err, float(np.max(
                    np.abs(got[out][found].astype(np.float64) - r)
                    / np.abs(r).clip(1.0))))
        worst = max(worst, err)
        missing, forbidden = missing + lost, forbidden + stray
        disorder += swapped
        if lost or stray or swapped or not err <= limit:
            bad.add(n)
    if not checked:
        bad |= set(range(n_sent))
    compared = [
        (f"worst relative error of sum(price), avg(volume) "
         f"({checked} batches of {n_sent})", worst, limit),
        ("rows owed and not delivered", missing, 0),
        ("rows delivered and forbidden (under the threshold, or of no "
         "kept tick)", forbidden, 0),
        ("rows out of order", disorder, 0),
        (f"batches whose row count is outside what the seed owes "
         f"(all {n_sent})", uneven, 0),
        (f"windows within {spec['rtol_eps32']} eps32 of the threshold, "
         f"free to stand on either side (of {windows})", free,
         int(np.ceil(spec["free_share"] * windows))),
        ("batches checked against the reference: none", int(not checked), 0)]
    return bad, compared
