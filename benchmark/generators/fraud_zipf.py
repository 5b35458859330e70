"""Skewed pattern traffic: every event's key drawn independently from
Zipf(s) over all the configuration's partitions, scrambled (YCSB's
``requestdistribution=zipfian``, ``ZIPFIAN_CONSTANT`` 0.99, hashed over
the key space): rank ``k`` has weight ``k ** -s``, and a seeded
permutation maps ranks to keys.  At s = 0.99 over 1,000,000 keys the
first rank takes 6.5% of all events, so a 16,384-event batch holds a run
of about 1,065 events of one key, about 1,150 keys that repeat and about
8,500 distinct keys.  Arrival order within a batch is the order drawn.

Within a pass a key's values rise one step an event: ``v`` is the key's
occurrence index in the pass plus 0.5 (exact in float32), so each chain
node holds at most one instance, no instance lane overflows, and every
event of a key from its sixteenth in a pass on completes a chain.  The
hot key's events are no no-ops that a filter could drop: each moves
fifteen instances and emits a row.

Every event has a millisecond of its own, rising through the batch and
the pass, so a match row's timestamp names the event that completed it:
``batch_of`` and ``row_keys`` read the batch and the key from it,
exactly.  A pass repeats the same keys and values ``PASS_GAP_MS`` later,
past the pattern's ``within`` plus the pass's own span, so state expires
between passes and every pass owes the same rows.
"""

from __future__ import annotations

import numpy as np

from fraud_pass import PASS_GAP_MS

T0_MS = 1_000


def zipf_ranks(rng, n_keys: int, s: float, n: int) -> np.ndarray:
    """``n`` independent draws of a rank in [0, n_keys), rank ``k``
    with weight ``(k + 1) ** -s``, by inversion of the exact CDF."""
    cdf = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64) ** -s)
    return np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")


class ZipfSchedule:
    """Batch ``n`` of the run is batch ``n % per_pass`` of pass
    ``n // per_pass``; the warm-up is pass 0 (``n`` from ``-per_pass``).
    Event ``i`` of batch ``b`` of pass ``p`` carries the timestamp
    ``T0_MS + p * PASS_GAP_MS + b * batch_events + i``."""

    def __init__(self, stream, key_of, ids, batch: int):
        self.stream = stream
        self.batch_events = batch
        self.per_pass = len(ids) // batch
        self.warmup = self.per_pass    # one pass interns every key sent
        if self.per_pass * batch >= PASS_GAP_MS - 600_000:
            raise ValueError("a pass must end before the next one's state "
                             "could still be live")
        # a key's occurrence index within the pass, in arrival order
        order = np.argsort(ids, kind="stable")
        first = np.ones(len(ids), dtype=bool)
        first[1:] = ids[order][1:] != ids[order][:-1]
        start = np.maximum.accumulate(np.where(first, np.arange(len(ids)), 0))
        occ = np.empty(len(ids), dtype=np.int64)
        occ[order] = np.arange(len(ids)) - start
        self._keys = key_of[ids]                       # of every pass event
        self._v = (occ + 0.5).astype(np.float32).astype(np.float64)
        counts = np.bincount(ids, minlength=len(key_of))
        self.active_keys = key_of[np.flatnonzero(counts >= 16)]
        self.all_keys = key_of

    def _span(self, n: int):
        p, b = divmod(n + self.warmup, self.per_pass)
        at = b * self.batch_events
        return p, slice(at, at + self.batch_events)

    def batch(self, n: int):
        from siddhi_tpu.core.event import EventBatch

        p, sl = self._span(n)
        ts = T0_MS + p * PASS_GAP_MS + np.arange(
            sl.start, sl.stop, dtype=np.int64)
        return EventBatch(self.stream, ["key", "v"],
                          {"key": self._keys[sl], "v": self._v[sl]}, ts)

    def _event_of(self, ts):
        return np.divmod(np.asarray(ts, dtype=np.int64) - T0_MS, PASS_GAP_MS)

    def batch_of(self, ts):
        """Run index of the batch that holds the event stamped ``ts``."""
        p, e = self._event_of(ts)
        return p * self.per_pass + e // self.batch_events - self.warmup

    def keep(self, n: int) -> bool:
        return True   # every row is kept: the reference checks them all

    def twin(self, n: int) -> int:
        """The batch of the first window pass that owes what ``n`` owes."""
        return n % self.per_pass

    def row_keys(self, rows) -> np.ndarray:
        """Key of each match row: that of the event its timestamp names."""
        _p, e = self._event_of(rows["_ts"])
        return self._keys[np.clip(e, 0, len(self._keys) - 1)]


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    n_keys = config["rehearsal" if rehearsal else "full"]["partitions"]
    rng = np.random.default_rng(seed)
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    scramble = rng.permutation(n_keys)             # rank -> key id
    ids = scramble[zipf_ranks(
        rng, n_keys, traffic_spec["zipf_s"],
        traffic_spec["batches_per_pass"] * size["batch"])]
    return ZipfSchedule(config["stream"], key_of, ids, size["batch"])
