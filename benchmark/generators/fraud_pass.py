"""Saturating pattern traffic: passes of nine full batches over every key.

Copied from ``chip_smoke.py`` ``pattern_traffic`` (PR 21), which proved
it on the chip; the program's copy may change, this one may not.

``batch // 32`` *active* keys get two events in every batch (so every
batch runs a second collision round) with values that rise by one per
event, ``v = j + frac(key)``: at most one pending instance per chain
node, so no instance lane overflows.  Three classes: rising for 16
events then idle; rising throughout; rising with one missed beat.  The
other keys are swept once a pass, one event a batch, uniform values in
[0, 20).  Every value is a float32.  ``frac(key) = (id + 1) / 2**20``
survives beside an integer part below 32, so a match's ``e1.v`` names
its key.  A pass repeats the same ids and values ``PASS_GAP_MS`` later,
past the pattern's ``within``, so every pass owes the same rows.
"""

from __future__ import annotations

import numpy as np

PASS_GAP_MS = 1_000_000  # > `within 10 min`: each pass starts from scratch
BATCH_GAP_MS = 10
FRAC_BITS = 20


class PassSchedule:
    """Batch ``n`` of the run is batch ``n % per_pass`` of pass
    ``n // per_pass``; the warm-up is pass 0 (``n`` from ``-per_pass``)."""

    def __init__(self, stream, key_of, batches, active):
        self.stream = stream
        self.key_of = key_of
        self.per_pass = len(batches)
        self.warmup = self.per_pass  # one pass interns every key
        self.batch_events = len(batches[0][0])
        self._cols = [{"key": key_of[ids], "v": v} for ids, v in batches]
        self.active_keys = key_of[active]
        self.all_keys = key_of

    def ts_of(self, n: int) -> int:
        p, b = divmod(n + self.warmup, self.per_pass)
        return 1_000 + p * PASS_GAP_MS + BATCH_GAP_MS * b

    def batch(self, n: int):
        from siddhi_tpu.core.event import EventBatch

        cols = self._cols[(n + self.warmup) % self.per_pass]
        return EventBatch(self.stream, ["key", "v"], cols, np.full(
            self.batch_events, self.ts_of(n), dtype=np.int64))

    def batch_of(self, ts):
        """Run index of the batch whose events carry timestamp ``ts``."""
        p, r = np.divmod(np.asarray(ts, dtype=np.int64) - 1_000, PASS_GAP_MS)
        return p * self.per_pass + r // BATCH_GAP_MS - self.warmup

    def keep(self, n: int) -> bool:
        return True  # match rows are few: every one is kept

    def twin(self, n: int) -> int:
        """The batch of the first window pass that owes what ``n`` owes."""
        return n % self.per_pass

    def row_keys(self, rows) -> np.ndarray:
        """Key of each match row, read back from its ``e1.v`` payload."""
        v = np.asarray(rows["v1"], dtype=np.float64)
        ids = np.rint((v - np.floor(v)) * (1 << FRAC_BITS)).astype(
            np.int64) - 1
        return self.key_of[np.clip(ids, 0, len(self.key_of) - 1)]


def traffic(rng, n_keys: int, batch: int, n_batches: int):
    n_active = batch // 32
    n_bulk = batch - 2 * n_active
    if not (n_keys < (1 << FRAC_BITS) and n_bulk <= n_keys - n_active
            and n_batches * n_bulk >= n_keys - n_active):
        raise ValueError("a pass must sweep every key once")
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    ids = rng.permutation(n_keys)
    active, bulk = ids[:n_active], ids[n_active:]
    klass = rng.integers(0, 3, n_active)
    frac = (active + 1) / float(1 << FRAC_BITS)
    sweep = np.resize(bulk, n_batches * n_bulk)

    def active_v(j):
        idle = (klass == 0) & (j >= 16)
        missed = (klass == 2) & (j == 7)
        return np.where(idle | missed, 0.25, j + frac).astype(np.float32)

    batches = []
    for b in range(n_batches):
        slots = rng.permutation(batch)
        s1, s2 = slots[n_bulk:n_bulk + n_active], slots[n_bulk + n_active:]
        first, second = np.minimum(s1, s2), np.maximum(s1, s2)
        ev_ids = np.empty(batch, dtype=np.int64)
        ev_v = np.empty(batch, dtype=np.float64)
        ev_ids[slots[:n_bulk]] = sweep[b * n_bulk:(b + 1) * n_bulk]
        ev_v[slots[:n_bulk]] = rng.uniform(0.0, 20.0, n_bulk).astype(
            np.float32)
        ev_ids[first] = ev_ids[second] = active
        ev_v[first] = active_v(2 * b)
        ev_v[second] = active_v(2 * b + 1)
        batches.append((ev_ids, ev_v))
    return key_of, batches, active


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    n_keys = config["rehearsal" if rehearsal else "full"]["partitions"]
    key_of, batches, active = traffic(
        np.random.default_rng(seed), n_keys, size["batch"],
        traffic_spec["batches_per_pass"])
    return PassSchedule(config["stream"], key_of, batches, active)
