"""Saturating login traffic: ``fraud_pass``'s shape over an
authentication log, so that the two cells differ by the automaton and
not by the load.

Passes of nine full batches over every user.  ``batch // 32`` *attacked*
users get two events in every batch (so every batch runs a second
collision round, here through a counted node); every other user is
swept once a pass with one event, a success nine times in ten and a lone
fail otherwise (the nine batches hold more slots than there are users,
so the sweep wraps as ``fraud_pass``'s does and a tenth of the users
come a second time in the pass's last batch: two lone fails stay under
the count).

``ok`` is 0 for a failed login, 1 for a success and 2 for any other
outcome (a challenge, a lock-out notice), which neither filter of the
pattern takes.  The 18 events a pass of an attacked user follow one of
four scripts, ``SCRIPTS``; the first three share nineteen twentieths of
the attacked users evenly, the fourth has the last twentieth:

0. 5 fails, a success, then successes: one arm over its minimum and one
   under it at the success; owes 1 row.
1. 12 fails, a success, 2 fails, a success, 2 successes: four arms, all
   four lanes of the node, emit at the first success with their own
   first and the one shared last fail; the two fails after it stay
   under the count; owes 4 rows.
2. 2 fails and a success, six times: a pattern's count does not ask for
   fails in a row, so the successes between do not reset it; two arms
   at most, four rows a pass, each at another success; owes 4 rows.
3. 12 fails, then 6 other outcomes and no success: four lanes full to
   the end of the pass, dropped by ``within`` at the next; owes none.

No burst is longer than 12 fails: ``every`` re-arms the head each time
a count reaches its minimum of 3, so a burst of F fails holds
``ceil(F / 3)`` arms, and 12 is what 4 instance lanes hold.

``ip = (id + 1) * 32 + ordinal`` names the user and the event's place
in the pass (0..17 for an attacked user, the batch for a swept one), so
a row names its key and which fails it captured.  Every event of a
batch carries the batch's timestamp.  A pass repeats the same users and
outcomes ``PASS_GAP_MS`` later, past the pattern's ``within``, so every
pass owes the same rows.
"""

from __future__ import annotations

import numpy as np

from fraud_pass import PassSchedule

ORDINAL_BITS = 5
FAIL, SUCCESS, OTHER = 0, 1, 2
SCRIPTS = np.array([
    [FAIL] * 5 + [SUCCESS] * 13,
    [FAIL] * 12 + [SUCCESS] + [FAIL] * 2 + [SUCCESS] * 3,
    [FAIL, FAIL, SUCCESS] * 6,
    [FAIL] * 12 + [OTHER] * 6], dtype=np.int32)
ROWS_OWED = (1, 4, 4, 0)     # a pass, by script
LONGEST_BURST = 12
COLUMNS = ("user", "ok", "ip")


class LoginSchedule(PassSchedule):
    """``fraud_pass``'s schedule (pass and batch arithmetic, timestamps)
    over the columns of ``Login``."""

    def __init__(self, stream, key_of, batches, active, script):
        self.stream = stream
        self.key_of = key_of
        self.per_pass = len(batches)
        self.warmup = self.per_pass  # one pass interns every user
        self.batch_events = len(batches[0][0])
        self._cols = [{"user": key_of[ids], "ok": ok, "ip": ip}
                      for ids, ok, ip in batches]
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
        """User of each alert row, read back from its ``e1[0].ip``."""
        ids = (np.asarray(rows["firstIp"], dtype=np.int64)
               >> ORDINAL_BITS) - 1
        return self.key_of[np.clip(ids, 0, len(self.key_of) - 1)]


def traffic(rng, n_keys: int, batch: int, n_batches: int):
    n_active = batch // 32
    n_bulk = batch - 2 * n_active
    if not (2 * n_batches == SCRIPTS.shape[1]
            and (n_keys + 1) << ORDINAL_BITS < 1 << 31
            and n_bulk <= n_keys - n_active
            and n_batches * n_bulk >= n_keys - n_active):
        raise ValueError("a pass must sweep every user once and give "
                         "an attacked one the 18 events of its script")
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    ids = rng.permutation(n_keys)
    active, bulk = ids[:n_active], ids[n_active:]
    script = np.arange(n_active) % 3
    script[n_active - n_active // 20:] = 3
    sweep = np.resize(bulk, n_batches * n_bulk)

    batches = []
    for b in range(n_batches):
        slots = rng.permutation(batch)
        s1, s2 = slots[n_bulk:n_bulk + n_active], slots[n_bulk + n_active:]
        first, second = np.minimum(s1, s2), np.maximum(s1, s2)
        ev_ids = np.empty(batch, dtype=np.int64)
        ev_ok = np.empty(batch, dtype=np.int32)
        ordinal = np.full(batch, b, dtype=np.int64)
        ev_ids[slots[:n_bulk]] = sweep[b * n_bulk:(b + 1) * n_bulk]
        ev_ok[slots[:n_bulk]] = np.where(rng.random(n_bulk) < 0.9,
                                         SUCCESS, FAIL)
        ev_ids[first] = ev_ids[second] = active
        ev_ok[first] = SCRIPTS[script, 2 * b]
        ev_ok[second] = SCRIPTS[script, 2 * b + 1]
        ordinal[first], ordinal[second] = 2 * b, 2 * b + 1
        ev_ip = (((ev_ids + 1) << ORDINAL_BITS) + ordinal).astype(np.int32)
        batches.append((ev_ids, ev_ok, ev_ip))
    return key_of, batches, active, script


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    n_keys = config["rehearsal" if rehearsal else "full"]["partitions"]
    key_of, batches, active, script = traffic(
        np.random.default_rng(seed), n_keys, size["batch"],
        traffic_spec["batches_per_pass"])
    return LoginSchedule(config["stream"], key_of, batches, active, script)
