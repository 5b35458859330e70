"""What the tier-1 tests of ``cardfraud_100k`` share: the benchmark's
files of the deployment and its app over batches (``bench_app.py``)."""

import functools

import numpy as np

import bench_app
from siddhi_tpu.core.event import EventBatch

COLUMNS = ["card", "amount", "merchant"]
CONFIG, TRAFFIC, REF, GEN = bench_app.files(
    "cardfraud_100k", "card_pass_saturated", "pattern_count_capture",
    "card_pass")
run_app = functools.partial(bench_app.run_app, CONFIG)


def amount_of(card, whole):
    """``card_pass``'s amounts: the whole part decides every comparison
    within a card, the fraction names the card."""
    return np.float32(whole + (card + 1) / float(1 << GEN.FRAC_BITS))


def txn_batch(cards, amounts, ts):
    return EventBatch(
        CONFIG["stream"], COLUMNS,
        {"card": np.asarray(cards, dtype=np.int64),
         "amount": np.asarray(amounts, dtype=np.float32),
         "merchant": np.zeros(len(cards), dtype=np.int32)},
        np.asarray(ts, dtype=np.int64))
