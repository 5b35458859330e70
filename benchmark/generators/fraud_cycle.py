"""Paced pattern traffic: passes of 72 small batches, and from the
eighth batch of a pass on every batch completes chains and owes matches.

Active keys come in cohorts of ``cohort`` keys.  Cohort ``k`` starts at
batch ``k`` of the pass and gets two rising events (``v = j + frac(key)``
as in ``fraud_pass``) in each of eight batches: sixteen events, so its
chains complete in batch ``k + 7``.  Half of each cohort's keys miss one
beat (event 7 reads 0.25) and complete nothing.  A batch carries up to
eight cohorts, so every batch runs a second collision round.  The other
keys fill each batch to its fixed size, swept once a pass with uniform
values in [0, 20).  At most one pending instance per chain node: no
lane overflows.  Passes repeat ``PASS_GAP_MS`` apart, so state expires
between them and every pass owes the same rows.
"""

from __future__ import annotations

import numpy as np

from fraud_pass import FRAC_BITS, PassSchedule

BEATS = 8  # batches a cohort is active in, two events each


def traffic(rng, n_keys: int, batch: int, n_batches: int, cohort: int):
    n_cohorts = n_batches - BEATS + 1
    n_active = n_cohorts * cohort
    if not (n_keys < (1 << FRAC_BITS) and n_active < n_keys):
        raise ValueError("too many keys for the payload fraction")
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    ids = rng.permutation(n_keys)
    active, bulk = ids[:n_active], ids[n_active:]
    cohorts = active.reshape(n_cohorts, cohort)
    frac = (cohorts + 1) / float(1 << FRAC_BITS)
    missed = np.arange(cohort) % 2 == 1  # odd members miss event 7
    live = [[k for k in range(n_cohorts) if k <= b < k + BEATS]
            for b in range(n_batches)]
    fill = [batch - 2 * cohort * len(ks) for ks in live]
    if sum(fill) < len(bulk) or max(fill) > len(bulk):
        raise ValueError("a pass must sweep every key once")
    sweep = np.resize(bulk, sum(fill))
    at = np.concatenate([[0], np.cumsum(fill)])

    batches = []
    for b, ks in enumerate(live):
        slots = rng.permutation(batch)
        ev_ids = np.empty(batch, dtype=np.int64)
        ev_v = np.empty(batch, dtype=np.float64)
        ev_ids[slots[:fill[b]]] = sweep[at[b]:at[b + 1]]
        ev_v[slots[:fill[b]]] = rng.uniform(0.0, 20.0, fill[b]).astype(
            np.float32)
        pairs = np.sort(slots[fill[b]:].reshape(2, -1), axis=0)
        for i, k in enumerate(ks):
            sl = slice(i * cohort, (i + 1) * cohort)
            for half in (0, 1):
                j = 2 * (b - k) + half
                v = np.where(missed & (j == 7), 0.25, j + frac[k])
                ev_ids[pairs[half, sl]] = cohorts[k]
                ev_v[pairs[half, sl]] = v.astype(np.float32)
        batches.append((ev_ids, ev_v))
    return key_of, batches, active


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    n_keys = config["rehearsal" if rehearsal else "full"]["partitions"]
    key_of, batches, active = traffic(
        np.random.default_rng(seed), n_keys, size["batch"],
        traffic_spec["batches_per_pass"], size["cohort"])
    return PassSchedule(config["stream"], key_of, batches, active)
