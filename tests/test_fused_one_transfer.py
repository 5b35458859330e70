"""A fused chain's batch reaches the device in one transfer and one call.

``FusedGraphEngine`` (``ops/fused_graph.py``) cuts a batch only as far
as its stages ask (the smallest ``chunk_rows`` among them), and a chunk
crosses as one packed ``int32 [k, B]`` buffer with a row for each head
lane the chain consumes, a relative-timestamp row for each stage that
keeps or reads one, and the valid mask.  Held here: the benchmark's
three-query chain against its junction-hopped form at batches that were
cut before, the cut not showing in the answers, a ``running`` stage
keeping its 2,048-row bound, bit patterns through the fused program,
the counters, and the sharded and dense-tail chains on the same path.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.stream import StreamCallback
from siddhi_tpu.ops.device_query import MAX_DEVICE_BATCH, VALID_KEY
from test_cse_chain3_reference import cell_files

STATS = "@app:statistics(reporter='none')"


class Rows(StreamCallback):
    """Every delivered batch's columns and event timestamps, as sent."""

    def __init__(self):
        self.got = []

    def receive_batch(self, batch):
        cols = {k: np.asarray(v).copy() for k, v in batch.columns.items()}
        cols["__ts"] = np.asarray(batch.timestamps).copy()
        self.got.append(cols)

    def columns(self):
        return {k: np.concatenate([g[k] for g in self.got])
                for k in self.got[0]}


def run(app, stream, out, sent, tail, chunk_rows=None):
    """The app's output columns, the tail query's counters and its
    fused engine (None on the junction path)."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app)
        rows = Rows()
        rt.add_callback(out, rows)
        rt.start()
        graph = getattr(getattr(rt.query_runtimes[tail], "device_runtime",
                                None), "graph", None)
        if chunk_rows is not None:
            graph.chunk_rows = chunk_rows
        h = rt.get_input_handler(stream)
        for b in sent:
            h.send_batch(b)
        low = dict(rt.lowering())
        stats = {k.rsplit(".", 1)[1]: v for k, v in rt.statistics().items()
                 if f".Queries.{tail}." in k}
        rt.shutdown()
        return rows.columns(), stats, graph, low
    finally:
        m.shutdown()


def same_bits(got, want):
    assert sorted(got) == sorted(want)
    for name, col in want.items():
        assert got[name].dtype == col.dtype
        assert len(got[name]) == len(col) > 0
        assert got[name].tobytes() == col.tobytes(), name


def pieces(batch, rows):
    """``batch`` as the sender would cut it: ``rows`` at a time."""
    n = len(batch.timestamps)
    return [EventBatch(batch.stream_id, list(batch.columns),
                       {k: v[i:i + rows] for k, v in batch.columns.items()},
                       batch.timestamps[i:i + rows])
            for i in range(0, n, rows)]


# -- the benchmark's chain (benchmark/configs/cse_chain3.json) ---------------

def chain3(fuse, tag):
    config = cell_files()[0]
    header = config["header"].format(**config["rehearsal"])
    if not fuse:
        header = header.replace("@app:fuse", "")
    return config, (f"@app:name('o3{tag}') {header} {STATS} "
                    f"{config['app']}")


def chain3_batches(rows, n_batches, seed=2**31 + 41):
    config, traffic, gen = cell_files()[:3]
    schedule = gen.make(seed, config, dict(
        traffic, rehearsal={"batch": rows, "warmup": 1}), True)
    assert schedule.batch_events == rows
    return [schedule.batch(n) for n in range(-1, n_batches - 1)]


def run_chain3(fuse, sent, tag, chunk_rows=None):
    config, app = chain3(fuse, tag)
    return run(app, config["stream"], config["output"], sent, "q3",
               chunk_rows)


@pytest.mark.parametrize("rows", [5_000, 8_192])
def test_chain3_fused_equals_junction_hopped_one_chunk_a_batch(rows):
    sent = chain3_batches(rows, 4)
    fused, stats, graph, low = run_chain3(True, sent, f"F{rows}")
    hopped, _, none, low_h = run_chain3(False, sent, f"J{rows}")
    assert low == {"q1": "fused", "q2": "fused", "q3": "fused"}
    assert low_h == {"q1": "device", "q2": "device", "q3": "device"}
    assert none is None
    # same rows, same order, same timestamps; the window is carried
    # over every batch boundary
    assert 0.25 < len(fused["__ts"]) / (4 * rows) < 0.42
    same_bits(fused, hopped)
    # the chain's bound is its window's: a batch is one chunk, one put
    assert graph.chunk_rows == 131_072
    assert stats["deviceChunks"] == stats["devicePuts"] == 4
    assert stats["fusedHops"] == 8


def test_chain3_buffer_has_only_the_rows_the_chain_reads():
    _, _, graph, _ = run_chain3(True, chain3_batches(64, 1), "B")
    # price and volume; the one stage that keeps timestamps (q2's
    # window); valid.  No LONG pair, no group row, no timestamp row
    # for the two filters
    assert graph.head_rows == ["price", "volume"]
    assert graph.ts_rows == {1: "__ts|1"}
    assert graph.buf_rows == ["price", "volume", "__ts|1", VALID_KEY]
    assert not [r for r in graph.buf_rows if r.startswith("timestamp")]
    n, B = 5, 8
    ts = 1_000 + np.arange(n, dtype=np.int64)
    buf = graph._lanes(
        list(graph.init_state()),
        {"price": np.arange(n, dtype=np.float32) + 0.5,
         "volume": np.arange(n, dtype=np.int32) - 2,
         "timestamp": ts, "symbol": np.asarray(["a"] * n, dtype=object)},
        ts, n, B)
    assert buf.dtype == np.int32 and buf.shape == (4, B)
    assert np.array_equal(buf[0, :n].view(np.float32),
                          np.arange(n, dtype=np.float32) + 0.5)
    assert buf[1].tolist() == [-2, -1, 0, 1, 2, 0, 0, 0]
    base = graph.stages[1].base_ts
    assert buf[2].tolist() == [*(ts - base).tolist(), 0, 0, 0]
    assert buf[3].tolist() == [1] * n + [0] * (B - n)


@pytest.mark.parametrize("cut_by", ["engine", "sender"])
def test_chain3_the_cut_does_not_show(cut_by):
    sent = chain3_batches(8_192, 3)
    whole, stats, _, _ = run_chain3(True, sent, "W" + cut_by)
    if cut_by == "engine":
        cut, cut_stats, _, _ = run_chain3(True, sent, "Ce",
                                          chunk_rows=MAX_DEVICE_BATCH)
    else:
        cut, cut_stats, _, _ = run_chain3(
            True, [p for b in sent for p in pieces(b, MAX_DEVICE_BATCH)],
            "Cs")
    # two hops a cycle: a piece the sender cut is a cycle of its own
    assert cut_stats["fusedHops"] == (6 if cut_by == "engine" else 24)
    assert cut_stats["deviceChunks"] == cut_stats["devicePuts"] == 12
    assert stats["deviceChunks"] == stats["devicePuts"] == 3
    # each output row reduces the same ten entries however the batch
    # was cut: bit for bit on the CPU backend
    same_bits(whole, cut)


# -- other chain shapes -------------------------------------------------------

DEFINE = "define stream SIn (sym int, price float, vol int, w long); "

RUNNING = """
@app:name('or{tag}') @app:playback @app:execution('tpu') {fuse} {stats}
""" + DEFINE + """
@info(name='q1') from SIn[price > 10.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid select sym, sum(price) as total, max(vol) as top
insert into Run;
@info(name='q3') from Run[total > 50.0] select sym, total, top
insert into Out;
"""

PASSTHROUGH = """
@app:name('op{tag}') @app:playback @app:execution('tpu') {fuse} {stats}
""" + DEFINE + """
@info(name='q1') from SIn[w != 7] select sym, price, vol insert into Mid;
@info(name='q2') from Mid[sym >= 0] select price, vol insert into Out;
"""

FILTERS = """
@app:name('os{tag}') @app:playback @app:execution('tpu'{dev}) {fuse} {stats}
""" + DEFINE + """
@info(name='q1') from SIn[price > 10.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid[vol > 50] select sym, price insert into Out;
"""

DENSE_TAIL = """
@app:name('od{tag}') @app:playback @app:execution('tpu') {fuse} {stats}
""" + DEFINE + """
define stream Mid (sym int, price float, vol int);
define stream Win (sym int, total double);
@info(name='q1') from SIn[price > 5.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid#window.length(4)
select sym, sum(price) as total insert into Win;
@info(name='q3') from every e1=Win[total > 60.0] -> e2=Win[total > e1.total]
select e1.sym as s1, e1.total as t1, e2.total as t2 insert into Out;
"""


def sin_batches(sizes, seed, whole_prices=False):
    rng = np.random.default_rng(seed)
    out, t = [], 1_000
    for n in sizes:
        ts = t + 3 * np.arange(1, n + 1, dtype=np.int64)
        t = int(ts[-1])
        price = rng.uniform(0.0, 30.0, n).astype(np.float32)
        cols = {"sym": rng.integers(0, 5, n).astype(np.int32),
                # whole numbers: a float32 sum of them is exact whatever
                # the order, so two cuts of a running sum agree
                "price": np.floor(price) if whole_prices else price,
                "vol": rng.integers(1, 100, n).astype(np.int32),
                "w": rng.integers(0, 10, n).astype(np.int64)}
        out.append(EventBatch("SIn", list(cols), cols, ts))
    return out


def run_sin(app, fuse, sent, tail, dev="", plan=None):
    text = app.format(tag="F" if fuse else "J", stats=STATS, dev=dev,
                      fuse=(plan or "@app:fuse") if fuse else "")
    return run(text, "SIn", "Out", sent, tail)


def test_a_running_stage_keeps_its_bound():
    sent = sin_batches([5_000, 300, 2_049], seed=5, whole_prices=True)
    fused, stats, graph, low = run_sin(RUNNING, True, sent, "q3")
    hopped, _, _, _ = run_sin(RUNNING, False, sent, "q3")
    assert low == {"q1": "fused", "q2": "fused", "q3": "fused"}
    assert [e.kind for e in graph.stages] == ["filter", "running", "filter"]
    # the running kind's [B, B] mask bounds the whole chain's chunk
    assert graph.chunk_rows == MAX_DEVICE_BATCH
    want = sum(-(-len(b.timestamps) // MAX_DEVICE_BATCH) for b in sent)
    assert want == 3 + 1 + 2
    assert stats["deviceChunks"] == stats["devicePuts"] == want
    # no stage of this chain reads a timestamp
    assert graph.buf_rows == ["price", "sym", "vol", VALID_KEY]
    same_bits(fused, hopped)


def test_bit_patterns_survive_the_fused_program():
    bits = np.array([0x7FC12345, 0xFFC00001, 0x80000000, 0x00000001,
                     0x807FFFFF, 0x7F800000, 0xFF800000, 0x3FC00000],
                    dtype=np.uint32)
    price = bits.view(np.float32)    # NaN payloads, -0.0, denormals, inf
    n = len(price)
    vol = np.array([np.iinfo(np.int32).min, np.iinfo(np.int32).max, 0, -1,
                    1, 2**24 + 1, -2**24 - 1, 7], dtype=np.int32)
    w = np.array([-2**63, 2**63 - 1, 7, -1, 2**32 + 7, 7, 0, -7],
                 dtype=np.int64)
    keep = w != 7
    cols = {"sym": np.arange(n, dtype=np.int32), "price": price, "vol": vol,
            "w": w}
    sent = [EventBatch("SIn", list(cols), cols,
                       1_000 + np.arange(n, dtype=np.int64))]
    got, stats, graph, low = run_sin(PASSTHROUGH, True, sent, "q2")
    assert low == {"q1": "fused", "q2": "fused"}
    # the LONG the head compares rides as its hi/lo pair; what the wire
    # carries downstream rides too, though the head reads none of it
    assert graph.buf_rows == ["w|hi", "w|lo", "sym", "price", "vol",
                              VALID_KEY]
    assert stats["deviceChunks"] == stats["devicePuts"] == 1
    assert got["price"].dtype == np.float32
    assert np.array_equal(got["price"].view(np.uint32), bits[keep])
    assert np.array_equal(got["vol"], vol[keep])
    assert np.array_equal(got["__ts"], sent[0].timestamps[keep])
    hopped, _, _, _ = run_sin(PASSTHROUGH, False, sent, "q2")
    same_bits(got, hopped)


def test_the_sharded_all_filter_chain_takes_the_one_buffer():
    sent = sin_batches([5_000, 300, 8_192], seed=9)
    ref, ref_stats, ref_graph, low_ref = run_sin(FILTERS, True, sent, "q2")
    got, stats, graph, low = run_sin(
        FILTERS, True, sent, "q2", dev=", devices='8'",
        plan="@app:plan(auto='true')")
    assert low_ref == {"q1": "fused", "q2": "fused"}
    assert low == {"q1": "fuse+shard", "q2": "fuse+shard"}
    assert graph.n_shards == 8 and graph.engine_kind == "fused_shard"
    # filters never cut a batch
    assert graph.chunk_rows is ref_graph.chunk_rows is None
    assert graph.buf_rows == ["price", "sym", "vol", VALID_KEY]
    assert stats["deviceChunks"] == stats["devicePuts"] == 3
    assert ref_stats["deviceChunks"] == ref_stats["devicePuts"] == 3
    same_bits(got, ref)
    hopped, _, _, _ = run_sin(FILTERS, False, sent, "q2")
    same_bits(got, hopped)


def test_a_dense_tail_chain_follows_its_stages():
    sent = sin_batches([2_500, 100], seed=13)
    fused, stats, graph, low = run_sin(DENSE_TAIL, True, sent, "q3")
    hopped, _, _, low_h = run_sin(DENSE_TAIL, False, sent, "q3")
    assert low == {"q1": "fused", "q2": "fused", "q3": "fused"}
    assert low_h["q3"] == "dense"
    # the scan a row adds no bound of its own: the window's holds
    # (length(4), one aggregate), and 2,500 rows are one chunk
    assert graph.dense is not None
    assert graph.chunk_rows == graph.stages[1].chunk_rows == 1_048_576
    assert graph.ts_rows == {1: "__ts|1", 2: "__ts|2"}
    assert stats["deviceChunks"] == stats["devicePuts"] == 2
    same_bits(fused, hopped)
