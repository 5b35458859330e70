"""A window batch reaches the device in one transfer.

The per-event device kinds (``ops/device_query.py``) cut a batch into
chunks only as far as their own working set asks, put only the lanes
their expressions read, and put them as one packed ``int32 [k, B]``
buffer.  Held here: the answers do not depend on the cut (against the
host engine, and bit for bit against the same engine held to
2,048-row chunks), the kinds with a ``[B, B]`` mask keep their bound,
the buffer carries bit patterns, and the ``ingest.put`` fault site is
armed once a batch.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.ingest_stage import IngestStats
from siddhi_tpu.core.stream import StreamCallback
from siddhi_tpu.ops.device_query import (
    GRP_KEY,
    MAX_DEVICE_BATCH,
    TS_KEY,
    VALID_KEY,
    WGRP_KEY,
    compile_query,
)

DEFINE = ("define stream S (symbol string, price float, volume int, "
          "timestamp long); ")
CELL_QUERY = ("@info(name='q') from S#window.length(10) select symbol, "
              "sum(price) as total, avg(volume) as avgVolume, timestamp "
              "insert into O;")


def batches(rows: int, seed: int):
    """A batch of ``rows`` ticks and a short one behind it, so that the
    ring a cut leaves behind is read too."""
    rng = np.random.default_rng(seed)
    out, t = [], 1_000
    for n in (rows, 300):
        ts = t + np.cumsum(rng.integers(1, 4, n)).astype(np.int64)
        t = int(ts[-1])
        cols = {
            "symbol": np.asarray([f"S{i}" for i in rng.integers(0, 5, n)],
                                 dtype=object),
            "price": rng.uniform(100.0, 1000.0, n).astype(np.float32),
            "volume": rng.integers(0, 300, n).astype(np.int32),
            "timestamp": ts.copy()}
        out.append(EventBatch("S", list(cols), cols, ts))
    return out


class Rows(StreamCallback):
    def __init__(self):
        self.got = []

    def receive_batch(self, batch):
        self.got.append({k: np.asarray(v).copy()
                         for k, v in batch.columns.items()})

    def columns(self):
        return {k: np.concatenate([g[k] for g in self.got])
                for k in self.got[0]}


def run(query, sent, device, chunk_rows=None, header="", by_event=False):
    """The app's output columns, and for a device run its statistics."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('w') @app:statistics('true') @app:playback "
            + header + ("@app:execution('tpu') " if device else "")
            + DEFINE + query)
        rows = Rows()
        rt.add_callback("O", rows)
        rt.start()
        if device:
            engine = rt.query_runtimes["q"].device_runtime.engine
            assert engine.kind == "sliding"
            if chunk_rows is not None:
                engine.chunk_rows = chunk_rows
        h = rt.get_input_handler("S")
        for b in sent:
            if not by_event:
                h.send_batch(b)
                continue
            c = b.columns
            for i, ts in enumerate(b.timestamps.tolist()):
                h.send([c["symbol"][i], float(c["price"][i]),
                        int(c["volume"][i]), int(c["timestamp"][i])],
                       timestamp=ts)
        stats = {k.rsplit(".", 1)[1]: v for k, v in rt.statistics().items()
                 if ".Queries.q." in k}
        faults = rt.app_context.fault_injector
        rt.shutdown()
        return rows.columns(), stats, faults
    finally:
        m.shutdown()


WINDOWS = {
    # two aggregates over 10 entries: a chunk of 131,072 rows
    "length": ("window.length(10)", "sum(price) as total, "
               "avg(volume) as avgVolume", 131_072),
    # one aggregate over the 1,024 entries a time window may hold: 4,096
    "time": ("window.time(60)", "sum(price) as total", 4_096),
}


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["unfiltered", "filtered"])
@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("rows", [2_048, 2_049, 8_192, 8_193, 20_000])
def test_sliding_answers_do_not_depend_on_the_cut(rows, window, filtered):
    handler, select, chunk = WINDOWS[window]
    query = ("@info(name='q') from S" + ("[volume > 50]" if filtered else "")
             + f"#{handler} select symbol, {select}, timestamp "
             "insert into O;")
    sent = batches(rows, seed=rows)
    # the host engine expires a time window at a batch's watermark, the
    # device row by row: its reference is the host fed event by event
    host, _, _ = run(query, sent, device=False, by_event=window == "time")
    whole, stats, _ = run(query, sent, device=True)
    cut, cut_stats, _ = run(query, sent, device=True,
                            chunk_rows=MAX_DEVICE_BATCH)
    assert stats["deviceChunks"] == -(-rows // chunk) + 1
    assert stats["devicePuts"] == stats["deviceChunks"]
    assert cut_stats["deviceChunks"] == -(-rows // MAX_DEVICE_BATCH) + 1
    assert sorted(whole) == sorted(host) == sorted(cut)
    for name, want in host.items():
        assert len(whole[name]) == len(want) > rows // 2
        if want.dtype.kind == "f":
            # each row reduces the same window entries however the
            # batch was cut: bit for bit on the CPU backend
            assert np.array_equal(whole[name], cut[name])
            np.testing.assert_allclose(whole[name], want, rtol=1e-5)
        else:
            assert np.array_equal(whole[name], want)
            assert np.array_equal(cut[name], want)


def _engine(body, partitioned=False):
    head = "define stream S (k int, v float, w long); @info(name='q') from S"
    eng = compile_query(head + body, partition_mode=partitioned,
                        n_groups=64)
    eng.ingest_stats = IngestStats()
    return eng


BOUND = {
    # kind's name -> (query body, partitioned, kind, rows a chunk)
    "running": (" select k, sum(v) as s group by k insert into O;",
                False, "running", MAX_DEVICE_BATCH),
    "keyed_sliding": ("#window.length(5) select k, sum(v) as s "
                      "insert into O;", True, "keyed_sliding",
                      MAX_DEVICE_BATCH),
    "sliding_min_forever": ("#window.length(5) select k, sum(v) as s, "
                            "minForever(v) as lo insert into O;",
                            False, "sliding", MAX_DEVICE_BATCH),
    # 4,096 entries x two aggregates: the gather is over the budget at
    # any more rows than the [B, B] kinds take
    "sliding_length_4096": ("#window.length(4096) select k, sum(v) as s, "
                            "avg(v) as a insert into O;", False, "sliding",
                            MAX_DEVICE_BATCH),
    "sliding_length_10": ("#window.length(10) select k, sum(v) as s, "
                          "avg(v) as a insert into O;", False, "sliding",
                          131_072),
    "sliding_length_1000": ("#window.length(1000) select k, sum(v) as s "
                            "insert into O;", False, "sliding", 4_096),
    "filter": ("[v > 0.5] select k, v * 2.0 as d insert into O;",
               False, "filter", None),
}


@pytest.mark.parametrize("name", sorted(BOUND))
def test_rows_a_chunk_come_from_the_kinds_working_set(name):
    body, partitioned, kind, chunk_rows = BOUND[name]
    eng = _engine(body, partitioned)
    assert (eng.kind, eng.chunk_rows) == (kind, chunk_rows)
    n = 8_192
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 8, n).astype(np.int32)
    cols = {"k": keys, "v": rng.uniform(0.0, 1.0, n).astype(np.float32),
            "w": np.arange(n, dtype=np.int64)}
    state, out, _ts = eng.process_batch(
        eng.init_state(), cols, 1_000 + np.arange(n, dtype=np.int64),
        part_keys=keys if partitioned else None)
    want = 1 if chunk_rows is None else -(-n // chunk_rows)
    assert eng.ingest_stats.device_chunks == want
    assert eng.ingest_stats.device_puts == want
    assert len(out["k"]) > n // 4


def test_only_the_lanes_the_step_reads_are_put():
    # an ungrouped window: no group row; a LONG passed through: no pair
    eng = compile_query(DEFINE + CELL_QUERY)
    assert eng.lane_rows == ["price", "volume", TS_KEY, VALID_KEY]
    # a stateless filter reads no timestamp either
    assert _engine(BOUND["filter"][0]).lane_rows == ["v", VALID_KEY]
    # a LONG that a filter compares rides as its hi/lo pair
    eng = _engine("[w > 5]#window.length(3) select k, sum(v) as s "
                  "group by k insert into O;")
    assert eng.lane_rows == ["v", "w|hi", "w|lo", TS_KEY, GRP_KEY,
                             VALID_KEY]
    assert _engine(BOUND["keyed_sliding"][0], True).lane_rows == [
        "v", TS_KEY, GRP_KEY, WGRP_KEY, VALID_KEY]


def test_bit_patterns_survive_the_pack_and_the_unpack():
    import jax

    eng = _engine("[w > 5]#window.length(3) select k, sum(v) as s, "
                  "max(k) as m group by k insert into O;")
    nan_payload = np.array([0x7FC12345], dtype=np.uint32).view(np.float32)[0]
    denormal = np.array([1], dtype=np.uint32).view(np.float32)[0]
    v = np.array([nan_payload, -0.0, denormal, 1.5, -np.inf],
                 dtype=np.float32)
    k = np.array([np.iinfo(np.int32).min, np.iinfo(np.int32).max, 0, -1, 7],
                 dtype=np.int32)
    w = np.array([-2**63, 2**63 - 1, 0, -1, 2**32 + 5], dtype=np.int64)
    n = len(v)
    buf = eng._pad_lanes({"k": k, "v": v, "w": w},
                         np.arange(1, n + 1, dtype=np.int32),
                         np.arange(n, dtype=np.int32), n)
    assert buf.dtype == np.int32 and buf.shape == (len(eng.lane_rows), 16)
    cols, ts, grp, wgrp, valid = jax.device_get(
        jax.jit(eng._unpack_lanes)(jax.device_put(buf)))
    assert cols["v"].dtype == np.float32
    assert np.array_equal(cols["v"][:n].view(np.uint32), v.view(np.uint32))
    assert np.array_equal(cols["k"][:n], k)
    hi = (cols["w|hi"][:n].astype(np.int64)) << 32
    lo = cols["w|lo"][:n].astype(np.int64) + 2**31
    assert np.array_equal(hi + lo, w)
    assert np.array_equal(ts[:n], np.arange(1, n + 1))
    assert np.array_equal(grp[:n], np.arange(n))
    assert not wgrp.any()   # no row of its own: zeros made on the device
    assert valid.dtype == np.bool_
    assert valid.tolist() == [True] * n + [False] * (16 - n)
    for lane in (cols["v"], cols["k"], ts, grp):
        assert not lane[n:].view(np.uint32).any()


def test_one_put_and_one_chunk_a_batch_in_statistics():
    sent = batches(8_192, seed=1)[:1] * 3
    _, stats, _ = run(CELL_QUERY, sent, device=True)
    assert stats["stagedBatches"] == 3
    assert stats["devicePuts"] == 3
    assert stats["deviceChunks"] == 3


def test_ingest_put_fault_is_retried_once_a_batch():
    sent = batches(8_192, seed=2)[:1]
    clean, _, _ = run(CELL_QUERY, sent, device=True)
    shaken, stats, faults = run(
        CELL_QUERY, sent, device=True,
        header="@app:faults(transfer.retry.scale='0.0001', "
               "ingest.put='transient:count=1') ")
    # one batch, one armed put: one fault, one retry, the same rows
    assert faults.stats.faults_injected == 1
    assert faults.stats.transfer_retries == 1
    assert faults.stats.drains_recovered == 1
    assert (stats["devicePuts"], stats["deviceChunks"]) == (1, 1)
    for name, want in clean.items():
        assert np.array_equal(shaken[name], want)
