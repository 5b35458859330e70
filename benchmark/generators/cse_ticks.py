"""Stock ticks for the upstream window samples: a seeded ring of
pre-built batches, sent round and round under rising timestamps.

50 symbols uniform, float32 prices in [100, 1000) (bf16 cannot hold
them), int32 volumes in [0, 300).  The batch maker is the windows
phase's of ``chip_smoke.py`` (PR 21), copied.
"""

from __future__ import annotations

import numpy as np

NAMES = ["symbol", "price", "volume", "timestamp"]


class RingSchedule:
    def __init__(self, stream, ring, warmup, keep_seed):
        self.stream = stream
        self.ring = ring
        self.warmup = warmup
        self.batch_events = len(ring[0]["price"])
        self._lane = np.arange(self.batch_events, dtype=np.int64)
        self._keep_seed = keep_seed

    def ts_of(self, n: int) -> int:
        return 1_000 + (n + self.warmup) * self.batch_events

    def batch(self, n: int):
        from siddhi_tpu.core.event import EventBatch

        ts = self.ts_of(n) + self._lane
        cols = dict(self.ring[(n + self.warmup) % len(self.ring)],
                    timestamp=ts)
        return EventBatch(self.stream, NAMES, cols, ts)

    def batch_of(self, ts):
        return ((np.asarray(ts, dtype=np.int64) - 1_000)
                // self.batch_events - self.warmup)

    def keep(self, n: int) -> bool:
        """Rows are kept for the first 32 batches and a seeded one in
        sixteen after them (the collector keeps the last 32 itself)."""
        return n < 32 or (n * 2654435761 + self._keep_seed) % 16 == 0


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    size = traffic_spec["rehearsal" if rehearsal else "full"]
    rng = np.random.default_rng(seed)
    B = size["batch"]
    ring = []
    for _ in range(traffic_spec["ring"]):
        ring.append({
            "symbol": np.asarray(
                [f"S{int(s)}" for s in rng.integers(
                    0, traffic_spec["symbols"], B)], dtype=object),
            "price": rng.uniform(100.0, 1000.0, B).astype(np.float32),
            "volume": rng.integers(0, 300, B).astype(np.int32)})
    return RingSchedule(config["stream"], ring, size["warmup"],
                        int(rng.integers(0, 16)))
