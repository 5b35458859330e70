"""YCSB core workload B against a profile table: 95% reads by primary
key (a ``Txn`` event probing the table), 5% updates (a ``ProfileUpdate``
event, upserted), keys of both drawn independently from Zipf(s) over all
the configuration's rows and scrambled by a seeded permutation
(``requestdistribution=zipfian``, ``ZIPFIAN_CONSTANT`` 0.99:
``fraud_zipf.zipf_ranks``, the sampler of the skewed pattern cell).  At
s = 0.99 over 1,000,000 cards the first rank takes 6.5% of all events:
an 8,192-event batch holds some 530 events of one card, so an upsert
batch writes one key hundreds of times (the last writer wins) and a
probe batch reads it hundreds of times.  The record is YCSB's: a key and
``fieldcount`` = 10 fields, each one device lane wide.

Operations come in whole batches, as the served path takes them: a pass
is ``batches_per_pass`` batches, one of them (at a seeded place that
stays the same from pass to pass) an upsert batch, the others probe
batches.

**Nothing is drawn inside the measured window.**  ``make`` draws, before
it, a ring of ``RING_PASSES`` passes of keys, amounts and merchants, and
a pool of ``POOL_BATCHES`` batches of values for each of the ten fields;
``batch(n)`` hands out slices of them (views, no copy) and adds the
batch's timestamps: window batch ``n`` takes the ring's batch ``n`` mod
its length, and an upsert batch its ten fields from the pool at a place
hashed from the seed **and the batch's own index**, so two upsert
batches never write the same values and a probe's answer says which
upserts it saw.  After ``RING_PASSES`` passes the keys come round again;
the values written to them do not.

The warm-up is YCSB's load phase through the same upsert query: every
card once, in a seeded order, in batches of the cell's size (the last
one is what is left over), then one probe batch and one upsert batch so
that every shape the window uses is compiled before it.  In the window
every update hits a live row: workload B inserts nothing.

Every event has a millisecond of its own.  The warm-up's start at
``T0_MS``, the window's at ``WINDOW_T0_MS``, past any warm-up: a row of
the output carries its ``Txn`` event's timestamp, and ``batch_of`` reads
the batch from it, exactly.
"""

from __future__ import annotations

import numpy as np

from fraud_zipf import zipf_ranks

T0_MS = 1_000
WINDOW_T0_MS = 2_000_000
RING_PASSES = 8
POOL_BATCHES = 64
PROBE = ["card", "amount", "merchant"]
# the record's ten fields: how each is drawn, and its lane
FIELDS = {
    "creditLimit": (lambda rng, m: rng.uniform(500.0, 1000.0, m), np.float32),
    "avgAmount": (lambda rng, m: rng.uniform(0.0, 500.0, m), np.float32),
    "dailyLimit": (lambda rng, m: rng.uniform(100.0, 5000.0, m), np.float32),
    "monthSpend": (lambda rng, m: rng.uniform(0.0, 20000.0, m), np.float32),
    "riskScore": (lambda rng, m: rng.random(m), np.float32),
    "tier": (lambda rng, m: rng.integers(0, 4, m), np.int32),
    "homeRegion": (lambda rng, m: rng.integers(0, 50, m), np.int32),
    "txnCount": (lambda rng, m: rng.integers(0, 100_000, m), np.int32),
    "lastMerchant": (lambda rng, m: rng.integers(0, 10_000, m), np.int32),
    "blocked": (lambda rng, m: rng.random(m) < 0.01, np.bool_),
}
UPSERT = ["card", *FIELDS]


class YcsbSchedule:
    """Batch ``n`` of the run: ``n < 0`` the warm-up (the load batches,
    then a probe batch, then an upsert batch), ``n >= 0`` the window, in
    which batch ``n`` is an upsert batch where ``n % per_pass ==
    upsert_at`` and a probe batch otherwise."""

    def __init__(self, seed, streams, rows, batch, per_pass, zipf_s):
        self.seed = seed
        self.probe_stream, self.upsert_stream = streams
        self.rows = rows
        self.batch_events = batch
        self.per_pass = per_pass
        rng = np.random.default_rng([seed, 0])
        self._load_order = rng.permutation(rows).astype(np.int32)
        scramble = rng.permutation(rows).astype(np.int32)  # rank -> card
        self.upsert_at = int(rng.integers(0, per_pass))
        self._hash = int(rng.integers(0, 16))
        self.load_batches = -(-rows // batch)
        self.warmup = self.load_batches + 2
        if T0_MS + self.warmup * batch >= WINDOW_T0_MS:
            raise ValueError("the warm-up must end before the window's "
                             "first timestamp")
        self.ring_batches = RING_PASSES * per_pass
        ring = self.ring_batches * batch
        self._cards = scramble[zipf_ranks(rng, rows, zipf_s, ring)]
        self._amount = rng.uniform(0.0, 1000.0, ring).astype(np.float32)
        self._merchant = rng.integers(0, 10_000, ring).astype(np.int32)
        self._pool = {k: draw(rng, POOL_BATCHES * batch).astype(dt)
                      for k, (draw, dt) in FIELDS.items()}
        self._lane = np.arange(batch, dtype=np.int64)

    # -- what a batch is ------------------------------------------------

    def is_upsert(self, n: int) -> bool:
        if n >= 0:
            return n % self.per_pass == self.upsert_at
        return n != -2              # the load, and the warm-up's last

    def _profile(self, n: int, cards) -> dict:
        """The ten fields written by upsert batch ``n``: the pool from a
        place hashed from the seed and the batch's index."""
        m = len(cards)
        at = ((n + self.warmup) * 2654435761 + self._hash) % (
            (POOL_BATCHES - 1) * self.batch_events + 1)
        return {"card": cards,
                **{k: v[at:at + m] for k, v in self._pool.items()}}

    def ts_of(self, n: int) -> int:
        if n >= 0:
            return WINDOW_T0_MS + n * self.batch_events
        return T0_MS + (n + self.warmup) * self.batch_events

    def batch(self, n: int):
        from siddhi_tpu.core.event import EventBatch

        B = self.batch_events
        load = n + self.warmup
        if load < self.load_batches:        # the load phase: inserts
            cards = self._load_order[load * B:(load + 1) * B]
        else:
            at = n % self.ring_batches * B
            ring = slice(at, at + B)
            cards = self._cards[ring]
        if self.is_upsert(n):
            cols, names, stream = (self._profile(n, cards), UPSERT,
                                   self.upsert_stream)
        else:
            cols = {"card": cards, "amount": self._amount[ring],
                    "merchant": self._merchant[ring]}
            names, stream = PROBE, self.probe_stream
        return EventBatch(stream, names, cols,
                          self.ts_of(n) + self._lane[:len(cards)])

    # -- reading the output back ----------------------------------------

    def batch_of(self, ts):
        """Run index of the batch that holds the event stamped ``ts``."""
        ts = np.asarray(ts, dtype=np.int64)
        return np.where(
            ts >= WINDOW_T0_MS, (ts - WINDOW_T0_MS) // self.batch_events,
            (ts - T0_MS) // self.batch_events - self.warmup)

    def keep(self, n: int) -> bool:
        """Rows are kept for the first 32 batches (a whole pass and more:
        an upsert batch and the probe batches on both sides of it) and a
        seeded one in sixteen after them (the collector keeps the last
        32 itself)."""
        return n < 32 or (n * 2654435761 + self._hash) % 16 == 0


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    which = "rehearsal" if rehearsal else "full"
    return YcsbSchedule(seed, config["stream"], config[which]["rows"],
                        traffic_spec[which]["batch"],
                        traffic_spec["batches_per_pass"],
                        traffic_spec["zipf_s"])
