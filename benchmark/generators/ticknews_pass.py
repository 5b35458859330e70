"""Saturating traffic of two streams over one pattern state: quotes and
headlines of one universe of symbols, every batch of one stream.

A pass is ``batches_per_pass`` batches (16): one ``NewsEvent`` batch, at
place ``news_at`` of every pass, and ``StockTick`` batches at every
other place.  Event time advances ``batch_gap_ms`` (1,500 ms) a batch,
every event of a batch at the batch's timestamp, and the passes are
contiguous: a pass is 24 s of event time, longer than the pattern's
``within 5 sec``, so an arm lives three batches past the one that
opened it and is gone at the fourth, all through the stream.

*Hot* symbols (``hot`` of the traffic file's size) have two events in
every batch of either stream, as ``fraud_pass``'s active keys have: a
second collision round in every batch.  The other symbols are *swept* by
the tick batches, ``batch - 2 * hot`` a batch in a seeded order that
starts anew with each pass, so a swept symbol ticks once in about eight
tick batches and never twice inside ``within``; the news batch draws its
other symbols from the swept ones by the seed, without replacement.
Of a pass's headlines the ones whose symbol ticked in the three batches
before the news batch complete an arm a tick opened (on the news batch);
the ones whose symbol ticks in the three batches after it open an arm
that a tick completes (a third on each of those batches); the rest open
an arm that expires.  Every tick of a symbol with no headline near opens
an arm that expires too.  The other twelve batches owe nothing.

Every pass repeats the same symbols, values and places, so every pass
owes the rows its twin owes.  The window's first batch carries
``WINDOW_T0_MS`` and the warm-up pass the 24 s before it.

``price = whole + (2 * (id + 1) + nth) / 2**21`` with ``whole`` in
1..7 is exact in float32: the fraction names the symbol (``row_keys``)
and whether the event was its symbol's second in the batch, the whole
part which tick batch of the pass it came in (``1 + k % 7``), so a row
says which tick it paired.  ``sentiment`` is uniform in [0.25, 1.25), a
float32 drawn for each headline.  ``volume`` and ``source`` are the
event's slot in its batch and nothing reads them.
"""

from __future__ import annotations

import numpy as np

from fraud_pass import PassSchedule

FRAC_BITS = 21          # twice a symbol's id + 1, and the event's turn
WHOLE_MAX = 8           # whole + frac stays exact in float32's 24 bits
WINDOW_T0_MS = 1_001_000
TICK, NEWS = 0, 1       # a batch's stream, as its place among the streams
COLUMNS = (("symbol", "price", "volume"), ("symbol", "sentiment", "source"))


class TickNewsSchedule(PassSchedule):
    """``fraud_pass``'s pass arithmetic over two streams and a clock
    that does not jump between passes."""

    def __init__(self, streams, key_of, batches, hot, gap_ms, keep_seed):
        self.streams = tuple(streams)
        self.key_of = key_of
        self.per_pass = len(batches)
        self.warmup = self.per_pass  # one pass interns every symbol
        self.batch_events = len(batches[0][1])
        self.gap_ms = gap_ms
        self.pass_ms = gap_ms * self.per_pass
        self.stream_of = [s for s, _ids, _v in batches]
        self.news_at = self.stream_of.index(NEWS)
        slot = np.arange(self.batch_events, dtype=np.int32)
        self._cols = [dict(zip(COLUMNS[s], (key_of[ids], v, slot)))
                      for s, ids, v in batches]
        self.active_keys = key_of[hot]
        self.all_keys = key_of
        self._keep_seed = keep_seed

    def ts_of(self, n: int) -> int:
        return WINDOW_T0_MS + n * self.gap_ms

    def batch(self, n: int):
        from siddhi_tpu.core.event import EventBatch

        place = n % self.per_pass
        s = self.stream_of[place]
        return EventBatch(self.streams[s], list(COLUMNS[s]),
                          self._cols[place], np.full(
                              self.batch_events, self.ts_of(n),
                              dtype=np.int64))

    def batch_of(self, ts):
        """Run index of the batch whose events carry timestamp ``ts``."""
        return (np.asarray(ts, dtype=np.int64) - WINDOW_T0_MS) // self.gap_ms

    def keep(self, n: int) -> bool:
        """Rows are kept for the first window pass and a seeded pass in
        eight after it: a pass owes a tenth of a batch in rows, and the
        reference checks two passes."""
        p = n // self.per_pass
        return p <= 0 or (p * 2654435761 + self._keep_seed) % 8 == 0

    def row_keys(self, rows) -> np.ndarray:
        """Symbol of each alert row, read back from its ``t.price``."""
        v = np.asarray(rows["price"], dtype=np.float64)
        ids = (np.rint((v - np.floor(v)) * (1 << FRAC_BITS)).astype(
            np.int64) >> 1) - 1
        return self.key_of[np.clip(ids, 0, len(self.key_of) - 1)]


def traffic(rng, n_keys: int, batch: int, n_hot: int, n_batches: int,
            news_at: int):
    n_bulk = batch - 2 * n_hot
    n_swept = n_keys - n_hot
    if not (2 * (n_keys + 1) < 1 << FRAC_BITS and 0 <= news_at < n_batches
            and 0 < n_bulk <= n_swept <= (n_batches - 1) * n_bulk):
        raise ValueError("a pass's tick batches must sweep every symbol "
                         "once, and a price must name its symbol")
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    ids = rng.permutation(n_keys)
    hot, swept = ids[:n_hot], ids[n_hot:]
    sweep = np.resize(swept, (n_batches - 1) * n_bulk)

    batches, k = [], 0      # k: the tick batches so far this pass
    for b in range(n_batches):
        slots = rng.permutation(batch)
        s1, s2 = slots[n_bulk:n_bulk + n_hot], slots[n_bulk + n_hot:]
        second = np.maximum(s1, s2)
        ev_ids = np.empty(batch, dtype=np.int64)
        ev_ids[s1] = ev_ids[s2] = hot
        if b == news_at:
            ev_ids[slots[:n_bulk]] = rng.choice(swept, n_bulk, replace=False)
            values = rng.uniform(0.25, 1.25, batch).astype(np.float32)
            values[values >= 1.25] = 0.25   # the rounding's closed end
            batches.append((NEWS, ev_ids, values))
            continue
        ev_ids[slots[:n_bulk]] = sweep[k * n_bulk:(k + 1) * n_bulk]
        nth = np.zeros(batch, dtype=np.int64)
        nth[second] = 1
        price = 1 + k % (WHOLE_MAX - 1) + (2 * (ev_ids + 1) + nth) / float(
            1 << FRAC_BITS)
        batches.append((TICK, ev_ids, price.astype(np.float32)))
        k += 1
    return key_of, batches, hot


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    n_keys = config["rehearsal" if rehearsal else "full"]["partitions"]
    rng = np.random.default_rng(seed)
    key_of, batches, hot = traffic(
        rng, n_keys, size["batch"], size["hot"],
        traffic_spec["batches_per_pass"], traffic_spec["news_at"])
    return TickNewsSchedule(config["stream"], key_of, batches, hot,
                            traffic_spec["batch_gap_ms"],
                            int(rng.integers(0, 8)))
