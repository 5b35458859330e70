"""What the tier-1 tests of ``ticknews_1m`` share: the benchmark's
files of the deployment and its app over batches (``bench_app.py``)."""

import functools

import numpy as np

import bench_app
from siddhi_tpu.core.event import EventBatch

CONFIG, TRAFFIC, REF, GEN = bench_app.files(
    "ticknews_1m", "ticknews_pass_saturated", "pattern_logical_and",
    "ticknews_pass")
run_app = functools.partial(bench_app.run_app, CONFIG)
TICK, NEWS = GEN.TICK, GEN.NEWS


def price_of(symbol, whole, nth=0):
    """``ticknews_pass``'s prices: the fraction names the symbol and the
    event's turn in its batch, the whole part which tick it was."""
    return np.float32(whole + (2 * (symbol + 1) + nth)
                      / float(1 << GEN.FRAC_BITS))


def make_batch(side, symbols, values, ts):
    """A batch of ``StockTick`` (``side`` 0: ``values`` are prices) or
    of ``NewsEvent`` (1: sentiments)."""
    names = GEN.COLUMNS[side]
    return EventBatch(
        CONFIG["stream"][side], list(names),
        {names[0]: np.asarray(symbols, dtype=np.int64),
         names[1]: np.asarray(values, dtype=np.float32),
         names[2]: np.zeros(len(symbols), dtype=np.int32)},
        np.broadcast_to(np.asarray(ts, dtype=np.int64),
                        (len(symbols),)).copy())
