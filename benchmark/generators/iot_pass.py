"""Saturating telemetry of a fleet of devices: passes of seventeen full
batches of one stream, ``Reading (device long, temp float)``.

``hot`` devices (a batch's thirty-second at full size) have two
readings in every batch, at seeded places, as ``fraud_pass``'s active
keys have: a second collision round in every batch.  A pass is
``2 * batches_per_pass`` readings of a hot device (34).  Reading 0 of a
run is the *head reading* ``(id + 1) / 2**21``: inside the head's band
``0 < temp < 1``, exact in float32, and it names the device, so a row's
``t1`` says whose it is (``row_keys``).  Reading ``j >= 1`` of a run is
``j + 0.5``, over the threshold ``j`` of state ``j + 1`` and over every
head reading.  Four scripts, a quarter of the hot devices each, dealt
by the seed:

- ``SILENT``: 32 readings, then ``QUIET`` (under every filter): one row,
  at reading 31;
- ``RISING``: 34 rising readings: one row, at reading 31, and two
  readings that find no arm;
- ``MISSED``: at a seeded place ``m`` in 2..31 the last reading again
  (``m - 0.5``, under its threshold ``m``), which ``->`` ignores; the
  run goes on one reading behind: one row, at reading 32;
- ``LATE``: four ``QUIET`` readings, then the run: 30 states deep at
  the pass's end, no row, and ``within`` drops the arm before the next
  pass.

A run holds one arm a device at a time, so no instance lane overflows.
The other devices are *swept*: ``batch - 2 * hot`` a batch, round-robin
over a seeded order that wraps inside the pass, so a device comes once
or twice a pass; ``temp`` uniform float32 in [0, 40): one in forty
opens an arm, a second reading may take it to state 2, all expire.

A pass repeats the same devices, readings and places ``PASS_GAP_MS``
later, past the pattern's ``within``, so every pass owes the same rows:
those of ``SILENT`` and ``RISING`` on the pass's batch 15, those of
``MISSED`` on batch 16.
"""

from __future__ import annotations

import numpy as np

from fraud_pass import PassSchedule

FRAC_BITS = 21          # a head reading: (id + 1) / 2**21, below 1
QUIET = -1.0            # a reading under every filter
LATE_BY = 4             # readings a LATE run begins behind the pass
SILENT, RISING, MISSED, LATE = range(4)
COLUMNS = ("device", "temp")


class IotSchedule(PassSchedule):
    """``fraud_pass``'s pass arithmetic over ``Reading``'s columns."""

    def __init__(self, stream, key_of, batches, hot, script, missed_at):
        super().__init__(stream, key_of, batches, hot)
        self._cols = [dict(zip(COLUMNS, (c["key"], c["v"])))
                      for c in self._cols]
        # of each hot device, in ``active_keys``' order
        self.script = script
        self.missed_at = missed_at

    def batch(self, n: int):
        from siddhi_tpu.core.event import EventBatch

        cols = self._cols[(n + self.warmup) % self.per_pass]
        return EventBatch(self.stream, list(COLUMNS), cols, np.full(
            self.batch_events, self.ts_of(n), dtype=np.int64))

    def row_keys(self, rows) -> np.ndarray:
        """Device of each row, read back from its ``e1.temp``."""
        t1 = np.asarray(rows["t1"], dtype=np.float64)
        ids = np.rint(t1 * (1 << FRAC_BITS)).astype(np.int64) - 1
        return self.key_of[np.clip(ids, 0, len(self.key_of) - 1)]


def reading(j, head, script, missed_at, states: int):
    """Reading ``j`` of the pass of every hot device, float32."""
    at = j - np.where(script == LATE, LATE_BY, 0)   # place in the run
    behind = (script == MISSED) & (j >= missed_at)  # m - 0.5 at m, then
    temp = np.where(at == 0, head, at - behind + 0.5)  # one behind
    quiet = (at < 0) | ((script == SILENT) & (j >= states))
    return np.where(quiet, QUIET, temp).astype(np.float32)


def traffic(rng, n_keys: int, batch: int, n_hot: int, n_batches: int,
            states: int):
    n_bulk = batch - 2 * n_hot
    n_swept = n_keys - n_hot
    readings = 2 * n_batches
    if not (n_keys < (1 << FRAC_BITS) and n_hot >= 4
            and 0 < n_bulk <= n_swept <= n_batches * n_bulk <= 2 * n_swept
            and states + 1 <= readings < states + LATE_BY):
        raise ValueError(
            "a pass must sweep every device once or twice, hold a run of "
            "every script, and a head reading must name its device")
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    ids = rng.permutation(n_keys)
    hot, swept = ids[:n_hot], ids[n_hot:]
    script = rng.permutation(np.arange(n_hot) % 4)
    missed_at = rng.integers(2, states, n_hot)
    head = (hot + 1) / float(1 << FRAC_BITS)
    sweep = np.resize(swept, n_batches * n_bulk)

    batches = []
    for b in range(n_batches):
        slots = rng.permutation(batch)
        s1, s2 = slots[n_bulk:n_bulk + n_hot], slots[n_bulk + n_hot:]
        first, second = np.minimum(s1, s2), np.maximum(s1, s2)
        ev_ids = np.empty(batch, dtype=np.int64)
        ev_temp = np.empty(batch, dtype=np.float32)
        ev_ids[slots[:n_bulk]] = sweep[b * n_bulk:(b + 1) * n_bulk]
        other = rng.uniform(0.0, 40.0, n_bulk).astype(np.float32)
        other[other >= 40.0] = 0.0      # the rounding's closed end
        ev_temp[slots[:n_bulk]] = other
        ev_ids[first] = ev_ids[second] = hot
        ev_temp[first] = reading(2 * b, head, script, missed_at, states)
        ev_temp[second] = reading(2 * b + 1, head, script, missed_at, states)
        batches.append((ev_ids, ev_temp))
    return key_of, batches, hot, script, missed_at


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    n_keys = config["rehearsal" if rehearsal else "full"]["partitions"]
    key_of, batches, hot, script, missed_at = traffic(
        np.random.default_rng(seed), n_keys, size["batch"], size["hot"],
        traffic_spec["batches_per_pass"], config["reference"]["states"])
    return IotSchedule(config["stream"], key_of, batches, hot, script,
                       missed_at)
