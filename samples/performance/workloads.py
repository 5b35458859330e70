"""The reference performance-sample workloads, host vs device.

Ports every workload of the reference harness
(`/root/reference/modules/siddhi-samples/performance-samples/src/main/
java/io/siddhi/performance/`) onto this engine, runs each on the host
path AND — where the query is device-eligible — under
``@app:execution('tpu')``, and prints one JSON array of
``{workload, host_events_per_sec, device_events_per_sec, speedup,
lowered}`` rows (BASELINE.md's "workloads to re-measure").

| workload                  | reference file                                   |
|---------------------------|--------------------------------------------------|
| simple_filter             | SimpleFilterSingleQueryPerformance.java:51       |
| filter_multi_4q           | SimpleFilterMultipleQueryPerformance.java:57     |
| filter_async              | SimpleFilterSyncPerformance.java:73 (@async)     |
| sliding_window            | SimpleWindowSingleQueryPerformance.java:35       |
| groupby_length_batch      | GroupByWindowSingleQueryPerformance.java:35      |
| partitioned_filter        | SimplePartitionedFilterQueryPerformance.java:39  |
| partitioned_double_filter | SimplePartitionedDoubleFilterQueryPerformance.java:61 |
| partition_scaling_<N>     | PartitionPerformance.java (N symbol keys)        |
| table_noindex             | NoIndexingTablePerformance.java:80               |

Run: python samples/performance/workloads.py [seconds-per-run]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch

CSE_DEF = ("define stream cseEventStream (symbol string, price float, "
           "volume int, timestamp long); ")
B = 8192

# SimpleWindowSingleQueryPerformance.java:35
SLIDING_WINDOW_Q = (
    CSE_DEF + "@info(name='q0') from cseEventStream#window.length(10) "
    "select symbol, sum(price) as total, avg(volume) as avgVolume, "
    "timestamp insert into outputStream;")

# GroupByWindowSingleQueryPerformance.java:35 without its bare
# `timestamp` select item: group keys + aggregates only (the faithful
# shape lowers to the device too since PR 33)
GROUPBY_LENGTH_BATCH_AGG_ONLY_Q = (
    CSE_DEF + "@info(name='q0') from cseEventStream"
    "#window.lengthBatch(10) select symbol, sum(price) as total, "
    "avg(volume) as avgVolume group by symbol "
    "insert into outputStream;")


def cse_batch(n_symbols: int, seed: int = 7) -> EventBatch:
    rng = np.random.default_rng(seed)
    return EventBatch(
        "cseEventStream",
        ["symbol", "price", "volume", "timestamp"],
        {
            "symbol": np.asarray(
                [f"S{int(i)}" for i in rng.integers(0, n_symbols, B)],
                dtype=object),
            "price": rng.uniform(100.0, 1000.0, B).astype(np.float32),
            "volume": rng.integers(0, 300, B).astype(np.int32),
            "timestamp": np.zeros(B, dtype=np.int64),
        },
        np.zeros(B, dtype=np.int64),
    )


def measure(app: str, batch: EventBatch, seconds: float,
            out_streams=("outputStream",), expect_lowered=None):
    """Pump `batch` repeatedly for `seconds`; returns (events/sec,
    lowering-map)."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app)
        n_out = [0]
        from siddhi_tpu.core.stream import StreamCallback

        class Counter(StreamCallback):
            def receive_batch(self, b):
                n_out[0] += len(b)

        for out in out_streams:
            rt.add_callback(out, Counter())
        rt.start()
        lowering = rt.lowering()
        if expect_lowered is not None:
            for q, where in expect_lowered.items():
                assert lowering.get(q) == where, (q, lowering)
        h = rt.get_input_handler(batch.stream_id)
        for _ in range(3):  # warmup (jit compiles on the device path)
            h.send_batch(batch)
        sent = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            h.send_batch(batch)
            sent += len(batch)
        dt = time.perf_counter() - t0
        rt.shutdown()
        return sent / dt, lowering
    finally:
        m.shutdown()


def workloads(seconds: float):
    tpu = "@app:execution('tpu', partitions='65536') "
    out = []

    def row(name, host_app, dev_app, batch, out_streams=("outputStream",),
            dev_expect=None):
        host_rate, _ = measure(host_app, batch, seconds, out_streams)
        dev_rate = None
        lowered = None
        if dev_app is not None:
            dev_rate, lowering = measure(dev_app, batch, seconds,
                                         out_streams, dev_expect)
            lowered = sorted(set(lowering.values()))
        out.append({
            "workload": name,
            "host_events_per_sec": round(host_rate, 1),
            "device_events_per_sec": (round(dev_rate, 1)
                                      if dev_rate is not None else None),
            "speedup": (round(dev_rate / host_rate, 3)
                        if dev_rate is not None else None),
            "lowered": lowered,
        })
        print(json.dumps(out[-1]), file=sys.stderr)

    b = cse_batch(50)

    # SimpleFilterSingleQueryPerformance.java:51
    q = (CSE_DEF + "@info(name='q0') from cseEventStream[volume < 150] "
         "select symbol, price insert into outputStream;")
    row("simple_filter", q, tpu + q, b, dev_expect={"q0": "device"})

    # SimpleFilterMultipleQueryPerformance.java:57 — 4-query fan-out
    q = CSE_DEF + " ".join(
        f"@info(name='q{i}') from cseEventStream[volume > 90] select * "
        "insert into outputStream;" for i in range(4))
    row("filter_multi_4q", q, tpu + q, b,
        dev_expect={f"q{i}": "device" for i in range(4)})

    # SimpleFilterSyncPerformance.java:73 — @async junction
    q = ("@async(buffer.size='1024', batch.size.max='4096') " + CSE_DEF +
         "@info(name='q0') from cseEventStream[volume < 150] "
         "select symbol, price insert into outputStream;")
    row("filter_async", q, tpu + q, b)

    q = SLIDING_WINDOW_Q
    row("sliding_window", q, tpu + q, b, dev_expect={"q0": "device"})

    # GroupByWindowSingleQueryPerformance.java:35 (faithful shape: the
    # bare `timestamp` select item is its group's last row's in the
    # pane, gathered host-side at native width — since PR 33 the query
    # lowers to the device, all the panes of a batch in one program;
    # the benchmark's cell cse_groupby.saturated runs it)
    q = (CSE_DEF + "@info(name='q0') from cseEventStream"
         "#window.lengthBatch(10) select symbol, sum(price) as total, "
         "avg(volume) as avgVolume, timestamp group by symbol "
         "insert into outputStream;")
    row("groupby_length_batch", q, tpu + q, b, dev_expect={"q0": "device"})

    q = GROUPBY_LENGTH_BATCH_AGG_ONLY_Q
    row("groupby_length_batch_agg_only", q, tpu + q, b,
        dev_expect={"q0": "device"})

    # SimplePartitionedFilterQueryPerformance.java:39
    q = (CSE_DEF + "partition with (symbol of cseEventStream) begin "
         "@info(name='q0') from cseEventStream[700 > price] select * "
         "insert into outputStream; end;")
    row("partitioned_filter", q, tpu + q, b, dev_expect={"q0": "device"})

    # SimplePartitionedDoubleFilterQueryPerformance.java:61
    q = (CSE_DEF + "partition with (symbol of cseEventStream) begin "
         "@info(name='q0') from cseEventStream[700 > price] select * "
         "insert into outputStream; "
         "@info(name='q1') from cseEventStream[price >= 700] select * "
         "insert into outputStream; end;")
    row("partitioned_double_filter", q, tpu + q, b,
        dev_expect={"q0": "device", "q1": "device"})

    # PartitionPerformance.java — partition-count scaling
    for n_keys in (10, 1_000, 50_000):
        q = (CSE_DEF + "partition with (symbol of cseEventStream) begin "
             "@info(name='q0') from cseEventStream[700 > price] "
             "select symbol, count() as c insert into outputStream; end;")
        row(f"partition_scaling_{n_keys}", q, tpu + q, cse_batch(n_keys),
            dev_expect={"q0": "device"})

    # NoIndexingTablePerformance.java:80 — un-indexed table insert+join
    # (joins run host-side; no device variant yet)
    q = ("define stream StockInputStream (symbol string, company string, "
         "price float, volume long); "
         "define stream StockCheckStream (symbol string, company string, "
         "timestamp long); "
         "define table StockTable (symbol string, company string, "
         "price float, volume long); "
         "from StockInputStream select symbol, company, price, volume "
         "insert into StockTable; "
         "from StockCheckStream join StockTable "
         "on StockCheckStream.symbol == StockTable.symbol "
         "select StockCheckStream.timestamp, StockCheckStream.symbol, "
         "StockCheckStream.company as company, StockTable.price as price "
         "insert into OutputStream;")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(q)
        n_out = [0]
        rt.add_callback("OutputStream",
                        lambda evs: n_out.__setitem__(0, n_out[0] + len(evs)))
        rt.start()
        hi = rt.get_input_handler("StockInputStream")
        hc = rt.get_input_handler("StockCheckStream")
        rng = np.random.default_rng(3)
        n_rows = 1_000
        syms = np.asarray([f"S{i}" for i in range(n_rows)], dtype=object)
        hi.send_batch(EventBatch(
            "StockInputStream",
            ["symbol", "company", "price", "volume"],
            {"symbol": syms, "company": syms,
             "price": rng.uniform(1, 100, n_rows).astype(np.float32),
             "volume": rng.integers(1, 100, n_rows).astype(np.int64)},
            np.zeros(n_rows, dtype=np.int64)))
        bc = EventBatch(
            "StockCheckStream", ["symbol", "company", "timestamp"],
            {"symbol": np.asarray(
                [f"S{int(i)}" for i in rng.integers(0, n_rows, 512)],
                dtype=object),
             "company": np.asarray(["c"] * 512, dtype=object),
             "timestamp": np.zeros(512, dtype=np.int64)},
            np.zeros(512, dtype=np.int64))
        sent = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            hc.send_batch(bc)
            sent += len(bc)
        dt = time.perf_counter() - t0
        rt.shutdown()
        out.append({
            "workload": "table_noindex",
            "host_events_per_sec": round(sent / dt, 1),
            "device_events_per_sec": None,
            "speedup": None,
            "lowered": None,
        })
        print(json.dumps(out[-1]), file=sys.stderr)
    finally:
        m.shutdown()
    return out


def main(seconds: float = 2.0):
    print(json.dumps(workloads(seconds)))


if __name__ == "__main__":
    main(float(sys.argv[1]) if len(sys.argv) > 1 else 2.0)
