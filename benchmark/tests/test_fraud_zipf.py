"""The skewed mix's own invariants (``generators/fraud_zipf.py``), on
the CPU with no device work: the key popularity it promises, values that
rise within a key, timestamps that name batch and key exactly, passes
that owe the same rows, and the plain reference against the host engine
on this traffic.  The cell's rehearsal, its control and a planted wrong
answer are cases of ``test_benchmark.py``, which runs every cell of
``BENCHMARK.json``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import collections
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH, os.path.join(BENCH, "generators")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import fraud_zipf  # noqa: E402
from references import pattern_chain  # noqa: E402

CELL = "fraud16_1m_zipf.saturated"


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


SPEC = _load(ROOT, "BENCHMARK.json")
CONFIG = _load(BENCH, "configs", "fraud16_1m_zipf.json")
TRAFFIC = _load(BENCH, "traffic", "fraud_zipf_saturated.json")
REF = CONFIG["reference"]


@pytest.fixture(scope="module")
def full():
    return fraud_zipf.make(2**31 + 5, CONFIG, TRAFFIC, rehearsal=False)


@pytest.fixture(scope="module")
def small():
    return fraud_zipf.make(7, CONFIG, TRAFFIC, rehearsal=True)


def _owed(schedule, first, last):
    """Rows the plain reference owes over batches ``first..last`` of the
    run: ``(batch, key, timestamp, e1.v, e16.v)``."""
    by_key = {}
    for n in range(first, last):
        b = schedule.batch(n)
        for k, ts, v in zip(b.columns["key"].tolist(), b.timestamps.tolist(),
                            b.columns["v"].tolist()):
            by_key.setdefault(k, []).append(((n, ts), ts, v))
    return [(n, k, ts, v1, v16) for k, evs in by_key.items()
            for (n, ts), v1, v16 in pattern_chain._chain_rows(
                evs, REF["states"], REF["within_ms"])]


def test_the_cell_is_what_the_issue_names():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fraud16_1m_zipf", "fraud_zipf_saturated", 1)
    assert TRAFFIC["loop"] == "closed" and TRAFFIC["zipf_s"] == 0.99
    assert TRAFFIC["full"]["batch"] == 16_384
    assert TRAFFIC["batches_per_pass"] == 9
    assert CONFIG["full"]["partitions"] == 1_000_000
    flagship = _load(BENCH, "configs", "fraud16_1m.json")
    for same in ("app", "header", "stream", "output", "full", "rehearsal",
                 "expect", "control", "precision", "reference"):
        assert CONFIG[same] == flagship[same], same
    assert "hotkeys" not in CONFIG["header"]
    reported = {m["name"] for kind in ("end_to_end", "per_layer")
                for m in SPEC[kind] if CELL in m.get("workloads", [CELL])}
    assert {"events_per_s", "setup_s", "events.rounds_per_batch",
            "events.dispatches_per_batch", "events.plan_ms_per_batch",
            "events.rows_per_batch", "events.device_idle_share"} <= reported
    assert "events.route_ms_per_batch" not in reported   # four chips only


def test_key_popularity_is_zipf_099(full):
    """Head share, longest run, repeating and distinct keys of every
    batch within sampling error of Zipf(0.99) over 1,000,000 keys."""
    n_keys, batch = CONFIG["full"]["partitions"], full.batch_events
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -0.99
    p = w / w.sum()
    assert 0.064 < p[0] < 0.066                       # 6.5% of all events
    # expected keys seen once or more, and twice or more, in a batch
    none = np.exp(batch * np.log1p(-p))
    distinct = float((1 - none).sum())
    repeating = float((1 - none - batch * p * none / (1 - p)).sum())
    assert 8_300 < distinct < 8_700 and 1_100 < repeating < 1_200
    sd_head = (batch * p[0] * (1 - p[0])) ** 0.5      # 31.6 events
    heads = []
    for n in range(full.per_pass):
        keys = full.batch(n).columns["key"]
        counts = np.unique(keys, return_counts=True)[1]
        heads.append(int(counts.max()))
        assert abs(counts.max() - batch * p[0]) < 5 * sd_head
        assert abs(len(counts) - distinct) < 5 * distinct ** 0.5
        assert abs((counts > 1).sum() - repeating) < 5 * repeating ** 0.5
    # the head of every batch is one key, the same through the pass
    hot = collections.Counter(
        int(k) for n in range(full.per_pass)
        for k in full.batch(n).columns["key"][:64]).most_common(1)[0][0]
    assert all((full.batch(n).columns["key"] == hot).sum() == h
               for n, h in enumerate(heads))
    assert len(np.unique(full.all_keys)) == n_keys
    # about 700 keys owe rows: those with sixteen events or more a pass
    assert 600 < len(full.active_keys) < 800


def test_values_rise_and_timestamps_are_distinct(full):
    last_v, last_ts = {}, 0
    for n in range(-full.warmup, 0):
        b = full.batch(n)
        assert (np.diff(b.timestamps) == 1).all() and b.timestamps[0] > last_ts
        last_ts = int(b.timestamps[-1])
        v = b.columns["v"]
        assert (v.astype(np.float32) == v).all()      # exact in float32
        for k, x in zip(b.columns["key"].tolist(), v.tolist()):
            assert x == last_v.get(k, -0.5) + 1.0     # one step an event
            last_v[k] = x
    assert max(last_v.values()) < 2 ** 14             # far inside float32
    # the next pass starts over, past `within` and the pass's own span
    nxt = full.batch(0)
    assert nxt.timestamps[0] - last_ts > REF["within_ms"]
    assert nxt.columns["v"][0] == 0.5


def test_row_keys_and_batch_of_are_exact_on_every_owed_row(full):
    rows = _owed(full, 0, full.per_pass)
    assert 55_000 < len(rows) < 66_000                 # about 0.41 an event
    n, keys, ts, _v1, _v16 = map(np.asarray, zip(*rows))
    assert (full.batch_of(ts) == n).all()
    assert (full.row_keys({"_ts": ts}) == keys).all()
    assert set(keys.tolist()) == set(full.active_keys.tolist())
    # every event of an active key from its sixteenth on owes one row
    counts = collections.Counter(keys.tolist())
    sent = collections.Counter(
        k for b in range(full.per_pass)
        for k in full.batch(b).columns["key"].tolist())
    assert all(counts[k] == sent[k] - 15 for k in counts)


def test_twin_batches_owe_equal_counts(small):
    """State expires between passes: a batch owes what its twin in the
    first window pass owes, through a third pass."""
    per = collections.Counter(
        r[0] for r in _owed(small, -small.warmup, 2 * small.per_pass))
    assert sum(per.values()) > 0
    for n in range(-small.warmup, 2 * small.per_pass):
        assert per[n] == per[small.twin(n)], n
        assert small.twin(n) == n % small.per_pass and small.keep(n)
    assert small.batch_events == TRAFFIC["rehearsal"]["batch"]


def test_the_chain_reference_equals_the_host_engine_on_this_traffic(small):
    """``references/pattern_chain.py``'s plain-Python chain against
    ``ops/nfa.py``."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.event import EventBatch

    keep = np.concatenate([small.active_keys, small.all_keys[:64]])
    last = small.per_pass + 3                          # into a second pass
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime("@app:playback " + CONFIG["app"])
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(
            (int(small.batch_of(e.timestamp)), int(e.timestamp), *e.data)
            for e in evs))
        rt.start()
        assert set(rt.lowering().values()) == {"host"}
        for n in range(last):
            b = small.batch(n)
            mine = np.isin(b.columns["key"], keep)
            rt.get_input_handler("Txn").send_batch(EventBatch(
                "Txn", ["key", "v"],
                {k: v[mine] for k, v in b.columns.items()},
                b.timestamps[mine]))
        rt.shutdown()
    finally:
        m.shutdown()
    want = sorted((n, ts, v1, v16) for n, k, ts, v1, v16 in _owed(
        small, 0, last) if k in set(keep.tolist()))
    assert len(want) > 1_000 and sorted(got) == want
