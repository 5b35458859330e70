"""The benchmark's plain reference of ``profile_join_1m`` tied to the engine.

``benchmark/references/table_join_upsert.py`` imports nothing of the
program; here the configuration's own app runs through ``SiddhiManager``
over the cell's generator at the rehearsal size, **with
``@app:devtables`` and without it** (the host table under
``@app:execution('tpu')``, whose join condition is the jitted ``[B, W]``
device probe, and under the plain host engine): the device table, the
host table and the reference agree bit for bit, and every number the reference
compares comes out at 0 or under its limit.  A hot key written hundreds
of times in one upsert batch ends on its last writer; a probe batch
directly after an upsert batch sees it whole and the one before sees
none of it.  A dropped row, an altered payload, a swapped pair, a stale
and a too-new snapshot each make the run not correct.
"""

import collections
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.stream import StreamCallback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N_SENT = 44          # two passes and the start of a third
FIELDS = ["creditLimit", "avgAmount", "dailyLimit", "monthSpend", "riskScore",
          "tier", "homeRegion", "txnCount", "lastMerchant", "blocked"]
OUT = ["card", "amount", "merchant"] + FIELDS     # a read returns the record


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_files():
    added = [p for p in (BENCH, os.path.join(BENCH, "generators"))
             if p not in sys.path]
    sys.path[:0] = added
    try:
        ref = load(os.path.join(BENCH, "references", "table_join_upsert.py"),
                   "_ref_table_join")
        gen = load(os.path.join(BENCH, "generators", "profile_ycsb.py"),
                   "_gen_profile_ycsb")
        zipf = load(os.path.join(BENCH, "generators", "fraud_zipf.py"),
                    "_gen_fraud_zipf")
    finally:
        for p in added:
            sys.path.remove(p)
    with open(os.path.join(BENCH, "configs", "profile_join_1m.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "profile_ycsb_b_saturated.json")) as f:
        traffic = json.load(f)
    return config, traffic, gen, ref, zipf


class Rows(StreamCallback):
    def __init__(self):
        self.batches = []

    def receive_batch(self, batch):
        self.batches.append(batch)


HEADERS = {"devtable": None,      # the configuration's own header
           "device_probe": "@app:playback @app:execution('tpu')",
           "host": "@app:playback"}


def served_rows(config, schedule, n_sent, path: str):
    """What the app emits for the warm-up and ``n_sent`` window batches
    through ``SiddhiManager``, on the device table or a host table:
    ``{column: values, "_ts": timestamps}`` in delivery order, and the
    runtime's ``lowering()``."""
    devtables = path == "devtable"
    header = HEADERS[path] or config["header"].format(**config["rehearsal"])
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + " " + config["app"])
        out = Rows()
        rt.add_callback(config["output"], out)
        rt.start()
        lowering = rt.lowering()
        handlers = {s: rt.get_input_handler(s) for s in config["stream"]}
        for n in range(-schedule.warmup, n_sent):
            batch = schedule.batch(n)
            handlers[batch.stream_id].send_batch(batch)
        rt.drain_device_emits()
        if devtables:
            join = rt.query_runtimes["probe"].device_runtime
            assert not join.table.demoted
            assert join.host_fallback_batches == 0
        rt.shutdown()
    finally:
        m.shutdown()
    cols = {k: np.concatenate([np.asarray(b.columns[k])
                               for b in out.batches]) for k in OUT}
    cols["_ts"] = np.concatenate([b.timestamps for b in out.batches])
    return cols, lowering


@pytest.fixture(scope="module")
def bench():
    config, traffic, gen, ref, zipf = cell_files()
    schedule = gen.make(2**31 + 42, config, traffic, True)
    rows, lowering = {}, {}
    for path in HEADERS:
        rows[path], lowering[path] = served_rows(config, schedule, N_SENT,
                                                 path)
    return types.SimpleNamespace(
        ref=ref, gen=gen, zipf=zipf, config=config, traffic=traffic,
        spec=config["reference"], schedule=schedule, rows=rows,
        device=rows["devtable"], lowering=lowering)


def collector_of(schedule, cols, keep=None):
    """What ``lib/deploy.py``'s collector would hold of ``cols``: the
    count of rows stamped in each batch, and the rows of the batches
    ``keep`` wants (all of them by default)."""
    cols = dict(cols, _n=schedule.batch_of(cols["_ts"]))
    counts = collections.Counter(cols["_n"].tolist())
    if keep is not None:
        kept = np.isin(cols["_n"], sorted(keep))
        cols = {k: v[kept] for k, v in cols.items()}
    in_window = cols["_n"] >= 0
    cols = {k: v[in_window] for k, v in cols.items()}
    return types.SimpleNamespace(
        rows=lambda: cols if len(cols["_ts"]) else None, counts=counts)


def judge(bench, cols, keep=None, n_sent=N_SENT):
    bad, compared = bench.ref.reference(
        bench.spec, bench.schedule, collector_of(bench.schedule, cols, keep),
        n_sent, 0, True)
    return bad, {name.split(" (")[0]: (value, limit)
                 for name, value, limit in compared}


MISSING = "rows owed and not delivered"
STRAY = "rows delivered and not owed"
DIFFERS = "rows whose payload or timestamp differs"
DISORDER = "pairs of rows out of arrival order"
UNEVEN = "batches whose row count is not what the replay owes"
STALE = ("kept probe batches after an upsert that a stale snapshot would "
         "answer alike")
EARLY = ("kept probe batches before an upsert that a snapshot taken after "
         "it would answer alike")


# -- device table, host table and reference agree ----------------------------


def test_the_paths_are_what_the_cell_expects(bench):
    assert bench.lowering["devtable"] == bench.config["expect"]["lowering"]
    assert bench.lowering["device_probe"]["probe"] == "device_probe"
    assert set(bench.lowering["host"].values()) == {"host"}


@pytest.mark.parametrize("path", ["device_probe", "host"])
def test_device_table_and_host_table_agree_bit_for_bit(bench, path):
    assert len(bench.device["_ts"]) > 1000
    for k in OUT + ["_ts"]:
        assert bench.device[k].dtype == bench.rows[path][k].dtype, k
        assert bench.device[k].tobytes() == bench.rows[path][k].tobytes(), k


@pytest.mark.parametrize("path", list(HEADERS))
def test_the_served_path_agrees_with_the_reference(bench, path):
    bad, compared = judge(bench, bench.rows[path])
    assert not bad
    assert len(compared) == 8
    for name, (value, limit) in compared.items():
        assert value <= limit, (name, value, limit)
    # both snapshot alarms had batches to look at, and each would ring
    # (the warm-up ends on an upsert batch: the window's first probe
    # batch follows one too)
    s = bench.schedule
    after = sum(s.is_upsert(n - 1) and not s.is_upsert(n)
                for n in range(N_SENT))
    before = sum(s.is_upsert(n + 1) and not s.is_upsert(n)
                 for n in range(N_SENT - 1))
    assert after >= 2 and before >= 2
    assert compared[STALE] == (0, after - 1)
    assert compared[EARLY] == (0, before - 1)


def test_a_quarter_of_the_probes_match(bench):
    per_batch = collections.Counter(
        bench.schedule.batch_of(bench.device["_ts"]).tolist())
    B = bench.schedule.batch_events
    for n in range(N_SENT):
        if bench.schedule.is_upsert(n):
            assert per_batch[n] == 0
        else:
            assert 0.15 * B < per_batch[n] < 0.35 * B, n


# -- the traffic is what the issue says ----------------------------------------


def test_the_schedule_is_ycsb_b_in_whole_batches(bench):
    s = bench.schedule
    assert s.per_pass == 20 and s.load_batches == 12 and s.warmup == 14
    kinds = [s.is_upsert(n) for n in range(60)]
    assert sum(kinds) == 3        # one in twenty, the same place each pass
    assert kinds[:20] == kinds[20:40] == kinds[40:]
    load = np.concatenate([s.batch(n).columns["card"]
                           for n in range(-s.warmup, -2)])
    assert sorted(load.tolist()) == list(range(s.rows))   # every card once
    assert s.batch(-2).stream_id == "Txn"
    assert s.batch(-1).stream_id == "ProfileUpdate"
    # the record is YCSB's: a key and ten fields, written whole
    a, b = s.batch(s.upsert_at), s.batch(s.upsert_at + 20)
    assert a.attribute_names == ["card"] + FIELDS
    assert s.batch(0 if s.upsert_at else 1).attribute_names == OUT[:3]
    # a batch is re-made from the seed and its index, and no two alike
    again = s.batch(s.upsert_at)
    for k in a.attribute_names:
        assert np.array_equal(a.columns[k], again.columns[k])
        assert k == "blocked" or not np.array_equal(a.columns[k],
                                                    b.columns[k]), k
    # timestamps name the batch, warm-up and window alike
    for n in (-s.warmup, -3, -2, -1, 0, 1, 57):
        assert set(s.batch_of(s.batch(n).timestamps).tolist()) == {n}
    # the first 32 batches are kept: an upsert and both its neighbours
    assert all(s.keep(n) for n in range(32))
    assert 0 < sum(s.keep(n) for n in range(32, 32 + 160)) < 30


def test_the_keys_are_fraud_zipfs_sampler_drawn_before_the_window(bench):
    """The cell's keys are ``fraud_zipf.zipf_ranks`` draws, scrambled,
    made once by ``make``: a window batch is slices of that ring and of
    the field pool, and draws nothing."""
    s, gen = bench.schedule, bench.gen
    rng = np.random.default_rng([s.seed, 0])
    rng.permutation(s.rows)                       # the load order
    scramble = rng.permutation(s.rows).astype(np.int32)
    rng.integers(0, s.per_pass), rng.integers(0, 16)
    ring = s.ring_batches * s.batch_events
    assert s.ring_batches == gen.RING_PASSES * s.per_pass
    assert np.array_equal(s._cards, scramble[bench.zipf.zipf_ranks(
        rng, s.rows, bench.traffic["zipf_s"], ring)])
    for n in (0, 1, s.upsert_at, 57, 3 * s.ring_batches + 5):
        batch = s.batch(n)
        lanes = ([s._cards] + list(s._pool.values()) if s.is_upsert(n)
                 else [s._cards, s._amount, s._merchant])
        for col, lane in zip(batch.columns.values(), lanes):
            assert np.shares_memory(col, lane)
    # the keys come round after the ring; what is written to them does not
    u = s.upsert_at
    a, b = s.batch(u), s.batch(u + s.ring_batches)
    assert np.array_equal(a.columns["card"], b.columns["card"])
    assert not np.array_equal(a.columns["creditLimit"],
                              b.columns["creditLimit"])
    assert np.array_equal(s.batch(3).columns["amount"],
                          s.batch(3 + s.ring_batches).columns["amount"])


def test_the_full_size_is_the_issues(bench):
    full = bench.gen.make(1, {"stream": ["Txn", "ProfileUpdate"],
                              "full": {"rows": 1_000_000}},
                          bench.traffic, False)
    assert full.load_batches == 123 and full.warmup == 125
    assert full.batch_events == 8192 and full.ring_batches == 160
    cards = full.batch(full.upsert_at).columns["card"]
    hot = collections.Counter(cards.tolist()).most_common(1)[0][1]
    assert 400 < hot < 680        # 6.5% of 8,192, give or take
    # the load phase writes every card once, each with ten fields
    last = full.batch(-3)
    assert len(last) == 1_000_000 - 122 * 8192
    assert all(len(v) == len(last) for v in last.columns.values())


# -- last writer wins, and the snapshot is the one at the probe ---------------


def hot_key_app(devtables: bool):
    body = ("define stream S (k int, x float); "
            "define stream U (k int, v float); "
            "@PrimaryKey('k') define table T (k int, v float); "
            "from U select k, v update or insert into T on T.k == k; "
            "@info(name='j') from S join T on S.k == T.k and S.x > T.v "
            "select S.k as k, S.x as x, T.v as v insert into Out;")
    header = "@app:playback @app:execution('tpu') " + (
        "@app:devtables(capacity='64') " if devtables else "")
    return header + body


@pytest.mark.parametrize("devtables", [True, False])
def test_a_hot_key_written_hundreds_of_times_ends_on_its_last_writer(
        bench, devtables):
    from siddhi_tpu.core.event import EventBatch

    rng = np.random.default_rng(5)
    keys = np.where(rng.random(512) < 0.7, 7, rng.integers(0, 40, 512)
                    ).astype(np.int32)
    vals = rng.uniform(0.0, 100.0, 512).astype(np.float32)
    assert (keys == 7).sum() > 300
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(hot_key_app(devtables))
        out = Rows()
        rt.add_callback("Out", out)
        rt.start()
        rt.get_input_handler("U").send_batch(EventBatch(
            "U", ["k", "v"], {"k": keys, "v": vals},
            1000 + np.arange(512, dtype=np.int64)))
        probe = np.arange(40, dtype=np.int32)
        rt.get_input_handler("S").send_batch(EventBatch(
            "S", ["k", "x"], {"k": probe,
                              "x": np.full(40, 1000.0, dtype=np.float32)},
            2000 + np.arange(40, dtype=np.int64)))
        rt.drain_device_emits()
        rt.shutdown()
    finally:
        m.shutdown()
    got = {int(k): v for b in out.batches
           for k, v in zip(b.columns["k"], b.columns["v"])}
    want = {int(k): v for k, v in zip(keys, vals)}   # the last stays
    assert got == want and got[7] == vals[np.flatnonzero(keys == 7)[-1]]
    # and the reference's own upsert keeps the same writer
    table = bench.ref.Table(40, {"v": vals})
    table.upsert(keys, {"v": vals})
    assert all(table.cols["v"][k] == v for k, v in want.items())
    assert table.live.sum() == len(want)


def test_a_probe_sees_the_upsert_before_it_whole_and_none_after(bench):
    """On the served device path: the probe batch directly after the
    window's first upsert batch answers for every card that batch wrote
    from what it wrote; the probe batch directly before it answers from
    what stood before."""
    s, spec = bench.schedule, bench.spec
    u = s.upsert_at if s.upsert_at > 0 else s.upsert_at + s.per_pass
    ups = s.batch(u)
    cards = ups.columns["card"]
    last = {int(c): float(v) for c, v in zip(cards, ups.columns["creditLimit"])}
    n_of = s.batch_of(bench.device["_ts"])
    seen_new = seen_old = 0
    for n, fresh in ((u + 1, True), (u - 1, False)):
        at = n_of == n
        for c, lim in zip(bench.device["card"][at],
                          bench.device["creditLimit"][at]):
            if int(c) in last:
                if fresh:
                    assert float(lim) == last[int(c)]
                    seen_new += 1
                elif float(lim) != last[int(c)]:
                    seen_old += 1
    assert seen_new > 10 and seen_old > 10
    assert spec["than"] == "creditLimit"


# -- the reference's own alarms -------------------------------------------------


def test_a_dropped_row_is_missing_and_uneven(bench):
    cols = {k: np.delete(v, 1500) for k, v in bench.device.items()}
    bad, compared = judge(bench, cols)
    assert compared[MISSING] == (1, 0) and compared[UNEVEN] == (1, 0)
    assert len(bad) == 1


def test_an_altered_payload_differs(bench):
    cols = {k: v.copy() for k, v in bench.device.items()}
    cols["tier"][1500] += 1
    bad, compared = judge(bench, cols)
    assert compared[DIFFERS] == (1, 0) and compared[MISSING] == (0, 0)
    assert len(bad) == 1


def test_a_row_nobody_owes_is_stray(bench):
    n_of = bench.schedule.batch_of(bench.device["_ts"])
    at = int(np.flatnonzero(n_of == 3)[0])
    free = bench.schedule.batch(3).timestamps
    unowed = next(int(t) for t in free
                  if t not in set(bench.device["_ts"][n_of == 3].tolist()))
    cols = {k: np.insert(v, at, v[at]) for k, v in bench.device.items()}
    cols["_ts"][at] = unowed
    bad, compared = judge(bench, cols)
    assert compared[STRAY][0] == 1 and bad == {3}


def test_a_swapped_pair_is_out_of_order(bench):
    cols = {k: v.copy() for k, v in bench.device.items()}
    for v in cols.values():
        v[[1500, 1501]] = v[[1501, 1500]]
    bad, compared = judge(bench, cols)
    assert compared[DISORDER] == (1, 0) and compared[DIFFERS] == (0, 0)
    assert len(bad) == 1


def test_rows_in_an_upsert_batch_are_uneven(bench):
    s = bench.schedule
    cols = {k: np.append(v, v[-1]) for k, v in bench.device.items()}
    cols["_ts"][-1] = s.batch(s.upsert_at).timestamps[0]
    bad, compared = judge(bench, cols)
    assert compared[UNEVEN] == (1, 0) and s.upsert_at in bad


@pytest.mark.parametrize("late", [True, False])
def test_a_stale_and_a_too_new_snapshot_are_caught(bench, late):
    """The app's rows re-made with every upsert batch applied one batch
    late (a stale snapshot) or one batch early (too new): rows differ,
    go missing or stray on the probe batch beside an upsert batch."""
    s, ref, spec = bench.schedule, bench.ref, bench.spec
    table, parts, held = None, [], None
    batches = [s.batch(n) for n in range(-s.warmup, N_SENT)]
    for i, batch in enumerate(batches):
        n = i - s.warmup
        if batch.stream_id == s.upsert_stream:
            if table is None:
                table = ref.Table(s.rows, {k: v for k, v in
                                           batch.columns.items()
                                           if k != "card"})
            if n < 0 or not late:
                if n < 0 or held is None:
                    table.upsert(batch.columns["card"], batch.columns)
                held = None
            else:
                held = batch        # applied after the next probe batch
            continue
        if not late and n >= 0 and i + 1 < len(batches) and batches[
                i + 1].stream_id == s.upsert_stream:
            nxt = batches[i + 1]    # applied one batch early
            table.upsert(nxt.columns["card"], nxt.columns)
            held = nxt
        cards = batch.columns["card"]
        parts.append(ref.answer(spec, batch, *table.view(cards)))
        if late and held is not None:
            table.upsert(held.columns["card"], held.columns)
            held = None
    cols = {k: np.concatenate([p[k] for p in parts]) for k in OUT + ["_ts"]}
    bad, compared = judge(bench, cols)
    wrong = (compared[DIFFERS][0] + compared[MISSING][0]
             + compared[STRAY][0])
    assert wrong > 0 and bad
    beside = {n + (1 if late else -1) for n in range(N_SENT)
              if s.is_upsert(n)}
    assert bad <= beside


def test_no_kept_batch_beside_an_upsert_proves_nothing(bench):
    """With no kept probe batch directly after an upsert batch the stale
    alarm's limit is -1: the run is not correct."""
    s = bench.schedule
    keep = {n for n in range(N_SENT)
            if not s.is_upsert(n) and not s.is_upsert(n - 1)}
    bad, compared = judge(bench, bench.device, keep=keep)
    assert not bad
    assert compared[STALE] == (0, -1)
