"""Saturating card traffic with more events a batch than there are
cards: every batch is cut into four collision rounds.

A pass is two full batches, A and B, over every card; at full size
131,072 events each over 100,000 cards, 1.31 events a card a batch.  A
uniform draw would give rounds of about 82,000 / 39,600 / 8,000 / 850
lanes and a tail, within a percent of a step of the engine's width
ladder on some seeds and not on others.  So, as ``fraud_pass`` fixes
its 4,096 keys twice a batch, the multiplicities are fixed (the traffic
file's table, ``parts`` below) and the seed decides which card plays
which part and where in a batch its events stand: every batch of every
seed has the same four round widths.

*Tested* cards follow one of three scripts of whole amounts,
``SCRIPTS``, half of it in either batch:

0. 1, 2, 3, 4: strictly rising; the first charge counts the three
   after it; owes 1 row.
1. 1 .. 8: strictly rising; every charge opens an instance and counts
   for every pending one, so four instance lanes are live from the
   fourth event on; the first five charges each count their three;
   owes 5 rows.
2. 5, 9, 3, 12: a dip; the count of the first charge stops at 2, four
   instances stay pending to the end of the pass and are dropped by
   ``within`` at the next; owes none.

*Normal* cards come two or three times a pass, (events in A, events in
B) as their part says, with whole amounts uniform in 0..19.  None has
four events inside ten minutes, so none completes and none overflows
whatever its amounts; their counts still move.

``amount = whole + (id + 1) / 2**17`` with ``whole`` below 64 is exact
in float32; within a card every comparison is decided by the whole
parts (ties stay ties), and a row's ``a0`` names its card.  ``merchant``
is the event's slot in its batch and nothing reads it.  Every event of
a batch carries the batch's timestamp.  A pass repeats the same cards
and amounts ``PASS_GAP_MS`` later, past the pattern's ``within``, so
every pass owes the same rows.
"""

from __future__ import annotations

import numpy as np

from fraud_pass import PassSchedule

FRAC_BITS = 17
WHOLE_MAX = 64          # whole + frac stays exact in float32's 24 bits
NORMAL_WHOLE = 20       # a normal card's whole amounts: 0..19
SCRIPTS = ((1, 2, 3, 4), (1, 2, 3, 4, 5, 6, 7, 8), (5, 9, 3, 12))
ROWS_OWED = (1, 5, 0)   # a pass, by script
COLUMNS = ("card", "amount", "merchant")
# normal parts: the traffic file's key -> (events in batch A, in B)
NORMAL = {"normal_1_1": ((1, 1),), "normal_2_1": ((2, 1), (1, 2)),
          "normal_3_0": ((3, 0), (0, 3)), "normal_2_0": ((2, 0), (0, 2))}


def parts(size: dict):
    """``[(cards, events in A, events in B, script or -1)]`` of a size
    of the traffic file: the three scripts, then the normal parts, an
    uneven one mirrored."""
    out = [(n, len(s) // 2, len(s) // 2, i)
           for i, (n, s) in enumerate(zip(size["tested"], SCRIPTS))]
    for key, splits in NORMAL.items():
        out += [(size[key], a, b, -1) for a, b in splits]
    return out


class CardSchedule(PassSchedule):
    """``fraud_pass``'s schedule (pass and batch arithmetic, timestamps)
    over the columns of ``Txn``."""

    def __init__(self, stream, key_of, batches, active, script):
        self.stream = stream
        self.key_of = key_of
        self.per_pass = len(batches)
        self.warmup = self.per_pass  # one pass interns every card
        self.batch_events = len(batches[0][0])
        self._cols = [{"card": key_of[ids], "amount": amount,
                       "merchant": np.arange(len(ids), dtype=np.int32)}
                      for ids, amount in batches]
        self.active_keys = key_of[active]
        self.all_keys = key_of
        self.script_of = dict(zip(self.active_keys.tolist(),
                                  script.tolist()))

    def batch(self, n: int):
        from siddhi_tpu.core.event import EventBatch

        cols = self._cols[(n + self.warmup) % self.per_pass]
        return EventBatch(self.stream, list(COLUMNS), cols, np.full(
            self.batch_events, self.ts_of(n), dtype=np.int64))

    def row_keys(self, rows) -> np.ndarray:
        """Card of each alert row, read back from its ``a.amount``."""
        v = np.asarray(rows["a0"], dtype=np.float64)
        ids = np.rint((v - np.floor(v)) * (1 << FRAC_BITS)).astype(
            np.int64) - 1
        return self.key_of[np.clip(ids, 0, len(self.key_of) - 1)]


def traffic(rng, n_keys: int, batch: int, size: dict):
    table = parts(size)
    if not (sum(p[0] for p in table) == n_keys <= 1 << FRAC_BITS
            and all(sum(p[0] * p[side] for p in table) == batch
                    for side in (1, 2))):
        raise ValueError("the parts must hold every card once and fill "
                         "either batch of a pass exactly")
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    ids = rng.permutation(n_keys)   # which card plays which part
    n_cards = np.array([p[0] for p in table])
    script_of_id = np.full(n_keys, -1)
    script_of_id[ids] = np.repeat([p[3] for p in table], n_cards)
    n_active = int(sum(size["tested"]))
    longest = max(map(len, SCRIPTS))
    whole_of = np.array([s + (0,) * (longest - len(s)) for s in SCRIPTS])

    batches, seen = [], np.zeros(n_keys, dtype=np.int64)
    for side in (1, 2):
        times = np.zeros(n_keys, dtype=np.int64)
        times[ids] = np.repeat([p[side] for p in table], n_cards)
        # a card's events at seeded slots, counted in arrival order
        ev_ids = rng.permutation(np.repeat(np.arange(n_keys), times))
        order = np.argsort(ev_ids, kind="stable")
        starts = np.cumsum(times) - times
        nth = np.empty(batch, dtype=np.int64)
        nth[order] = np.arange(batch) - np.repeat(starts, times)
        nth += seen[ev_ids]         # its place in the card's pass
        script = script_of_id[ev_ids]
        whole = np.where(script >= 0, whole_of[script, nth],
                         rng.integers(0, NORMAL_WHOLE, batch))
        amount = (whole + (ev_ids + 1) / float(1 << FRAC_BITS)).astype(
            np.float32)
        batches.append((ev_ids, amount))
        seen += times
    return key_of, batches, ids[:n_active], script_of_id[ids[:n_active]]


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    n_keys = config["rehearsal" if rehearsal else "full"]["partitions"]
    key_of, batches, active, script = traffic(
        np.random.default_rng(seed), n_keys, size["batch"], size)
    return CardSchedule(config["stream"], key_of, batches, active, script)
