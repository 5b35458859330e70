"""The hash index for integer partition keys (core/key_index.py).

The reference is the sorted index, which the parent used for every key
family and which stays in the tree for strings and floats: a runtime
whose ``index_for`` always hands out ``SortedKeyIndex`` is driven with
the same script as one that chooses by dtype kind, and the rows must be
equal lane for lane (row numbers are what checkpoints hold).  Against
the dict intern, which allocates in arrival order, only one row a key,
one key a row and stability are required.
"""

import logging

import numpy as np
import pytest

from siddhi_tpu.compiler import SiddhiCompiler
from siddhi_tpu.core import dense_pattern
from siddhi_tpu.core.dense_pattern import (
    DensePatternRuntime, build_dense_engine)
from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError
from siddhi_tpu.core.key_index import HashKeyIndex, SortedKeyIndex

I64 = np.iinfo(np.int64)
APP = ("define stream S (k long, v double); "
       "from every e1=S[v > 5.0] -> e2=S[v > e1.v] within 10 sec "
       "select e1.v as a, e2.v as b insert into Out;")


def make_runtime(capacity=64, mesh=None):
    app = SiddhiCompiler.parse(APP)
    q = app.queries[0]
    defs = app.stream_definitions
    eng = build_dense_engine(
        q, q.input_stream, lambda s: defs[s.stream_id], capacity)
    return DensePatternRuntime(eng, "#m", emit=lambda b: None, mesh=mesh)


def colliding_keys(capacity, n, dtype=np.int64):
    """``n`` keys whose home slot is the same in a table of
    ``capacity`` rows: one probe chain of length ``n``."""
    index = HashKeyIndex(capacity, dtype)
    pool = np.arange(1, 400_000, dtype=np.int64)
    homes = index.home(pool)
    slot = np.bincount(homes).argmax()
    keys = pool[homes == slot][:n]
    assert len(keys) == n
    return keys.astype(dtype)


def run_script(script, capacity=64, mesh=None, sorted_only=False,
               monkeypatch=None):
    """Drive one runtime with ``script`` (a list of steps); the rows of
    every intern step, in order, and the runtime."""
    with monkeypatch.context() as m:
        if sorted_only:
            m.setattr(dense_pattern, "index_for",
                      lambda keys, rows, cap: SortedKeyIndex(keys, rows))
        rt = make_runtime(capacity, mesh)
        out = []
        for step in script:
            if step[0] == "intern":
                out.append(rt.intern_keys(step[1]).tolist())
            elif step[0] == "full":  # one key too many
                with pytest.raises(SiddhiAppRuntimeError,
                                   match="cardinality exceeded"):
                    rt.intern_keys(step[1])
            elif step[0] == "purge":  # keys idle since before step[1]
                for k, r in rt._key_rows.items():
                    rt._row_last_used[r] = 0 if k in step[1] else 1000
                rt.purge_idle(now=1000, idle_ms=500)
            elif step[0] == "roundtrip":
                blob = rt.snapshot()
                rt = make_runtime(capacity, mesh)
                rt.restore(blob)
        return out, rt


def a(values, dtype=np.int64):
    return np.asarray(values, dtype=dtype)


RNG = np.random.default_rng(31)
SCRAMBLED = RNG.choice(10 ** 12, size=48, replace=False).astype(np.int64)

SCRIPTS = {
    "first_seen_then_warm": [
        ("intern", SCRAMBLED[:20]), ("intern", SCRAMBLED[:20][::-1]),
        ("intern", SCRAMBLED[10:40]), ("intern", SCRAMBLED[:40]),
    ],
    "key_twice_in_its_first_batch": [
        ("intern", a([9, 3, 9, 3, 3, 1])), ("intern", a([1, 1, 9, 4, 4])),
    ],
    "descending_first_batch_rows_ascend_by_key": [
        ("intern", a([50, 40, 30, 20, 10])), ("intern", a([10, 50])),
    ],
    "int32_after_int64": [
        ("intern", a([7, 1 << 40, -2])),
        ("intern", a([7, -2, 5], np.int32)), ("intern", a([5, 7])),
    ],
    "int64_after_int32": [
        ("intern", a([7, -2, 5], np.int32)),
        ("intern", a([7, 1 << 40, 5])), ("intern", a([5, -2], np.int32)),
    ],
    "uint32_widens_to_int64": [
        ("intern", a([7, 4_000_000_000], np.uint32)),
        ("intern", a([-7, 7, 4_000_000_000])),
    ],
    "int16_then_uint8_cast_to_the_index": [
        ("intern", a([-3, 200, 7], np.int16)),
        ("intern", a([200, 7, 9], np.uint8)),
    ],
    "extreme_values": [
        ("intern", a([0, -1, I64.min, I64.max, 1, -1, 0])),
        ("intern", a([I64.max, I64.min, -1, 0, I64.min + 1, I64.max - 1])),
        ("intern", a([0])), ("intern", a([-1])),
    ],
    "zero_first": [("intern", a([0])), ("intern", a([0, 0, 1]))],
    "uint64_past_int64_max": [
        ("intern", a([0, (1 << 64) - 1, 1 << 63, 5], np.uint64)),
        ("intern", a([(1 << 63) - 1, 1 << 63, 5, 0], np.uint64)),
        ("intern", a([5, 6], np.uint32)),
    ],
    "one_probe_chain_of_twelve": [
        ("intern", colliding_keys(64, 12)[:5]),
        ("intern", colliding_keys(64, 12)),
        ("intern", colliding_keys(64, 12)[::-1]),
    ],
    "probe_chain_interleaved_with_other_keys": [
        ("intern", np.concatenate([colliding_keys(64, 10), SCRAMBLED[:20]])),
        ("intern", np.concatenate([SCRAMBLED[:30], colliding_keys(64, 12)])),
    ],
    "fill_to_capacity_and_one_more": [  # run at 32 partitions
        ("intern", SCRAMBLED[:20]),
        ("full", SCRAMBLED[10:33]),  # 13 new keys where 12 rows are left
        ("intern", SCRAMBLED[:20]),  # the index left usable
        ("intern", SCRAMBLED[:32]),  # exactly full
        ("full", SCRAMBLED[32:33]),
        ("intern", SCRAMBLED[:32][::-1]),
        ("purge", set(SCRAMBLED[:3].tolist())),
        ("full", SCRAMBLED[32:36]),  # four new keys, three freed rows
        ("intern", SCRAMBLED[33:36]),
    ],
    "purge_then_reintern": [
        ("intern", a([50, 10, 40, 20, 30])),
        ("purge", {10, 30, 50}),
        ("intern", a([20, 40])),
        ("intern", a([60, 10, 5, 70])),  # three freed rows, one fresh
        ("intern", a([30, 50, 60, 5])),
    ],
    "purge_everything_then_adopt_int32": [
        ("intern", a([3, 1, 2])), ("purge", {1, 2, 3}),
        ("intern", a([9, 8], np.int32)), ("intern", a([8, 1, 9])),
    ],
    "snapshot_restore_intern": [
        ("intern", SCRAMBLED[:20]), ("roundtrip",),
        ("intern", SCRAMBLED[:20][::-1]), ("intern", SCRAMBLED[10:30]),
    ],
    "snapshot_restore_with_freed_rows": [
        ("intern", a([5, 4, 3, 2, 1])), ("purge", {2, 4}),
        ("roundtrip",), ("intern", a([1, 9, 3, 8, 5, 7])),
    ],
    "restore_int32_keys_then_int64": [
        ("intern", a([3, 9], np.int32)), ("roundtrip",),
        ("intern", a([9, 1 << 40, 3])),
    ],
    "int_keys_widen_to_float64": [
        ("intern", a([7, 8], np.int32)),
        ("intern", a([7.0, 8.5, 8.0], np.float64)),
        ("intern", a([8, 7, 9], np.int32)),
    ],
    "float_keys_sorted_index": [
        ("intern", a([2.5, -1.0, 2.5], np.float64)),
        ("intern", a([3, -1], np.int32)),
    ],
    "string_keys_sorted_index": [
        ("intern", np.asarray(["b", "a", "b"])),
        ("intern", np.asarray(["a", "longer", "b"])),
    ],
    "empty_batch": [
        ("intern", a([])), ("intern", a([4, 2])), ("intern", a([])),
        ("intern", a([2, 4])),
    ],
}


CAPACITY = {"fill_to_capacity_and_one_more": 32}


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_rows_equal_the_sorted_index_lane_for_lane(name, monkeypatch):
    script, capacity = SCRIPTS[name], CAPACITY.get(name, 64)
    want, ref = run_script(script, capacity, sorted_only=True,
                           monkeypatch=monkeypatch)
    got, rt = run_script(script, capacity, monkeypatch=monkeypatch)
    assert ref.stats()["intern_index"] in ("sorted", None)
    assert got == want
    assert rt._key_rows == ref._key_rows
    assert rt._row_keys == ref._row_keys
    assert rt._free_rows == ref._free_rows
    assert rt._next_row == ref._next_row
    assert rt.stats()["intern_new_keys"] == ref.stats()["intern_new_keys"]


INDEX_KIND = {
    "first_seen_then_warm": "hash", "extreme_values": "hash",
    "uint64_past_int64_max": "hash", "int64_after_int32": "hash",
    "uint32_widens_to_int64": "hash", "purge_then_reintern": "hash",
    "snapshot_restore_intern": "hash",
    "restore_int32_keys_then_int64": "hash",
    "purge_everything_then_adopt_int32": "hash",
    "int_keys_widen_to_float64": "sorted",
    "float_keys_sorted_index": "sorted",
    "string_keys_sorted_index": "sorted",
}


@pytest.mark.parametrize("name", sorted(INDEX_KIND))
def test_index_follows_the_key_dtype_kind(name, monkeypatch):
    _rows, rt = run_script(SCRIPTS[name], monkeypatch=monkeypatch)
    assert rt.stats()["intern_index"] == INDEX_KIND[name]
    assert rt._vector_intern


@pytest.mark.parametrize("seed", range(6))
def test_random_batches_equal_the_sorted_index(seed, monkeypatch):
    """Batches over a small key universe (so chains form and keys
    repeat), purges and round trips at random."""
    rng = np.random.default_rng(seed)
    universe = rng.integers(I64.min, I64.max, size=60, dtype=np.int64)
    script = []
    for _ in range(30):
        roll = rng.random()
        if roll < 0.15:
            script.append(("purge", set(
                rng.choice(universe, size=12).tolist())))
        elif roll < 0.25:
            script.append(("roundtrip",))
        else:
            script.append(("intern", rng.choice(
                universe, size=rng.integers(1, 40))))
    want, ref = run_script(script, 64, sorted_only=True,
                           monkeypatch=monkeypatch)
    got, rt = run_script(script, 64, monkeypatch=monkeypatch)
    assert got == want
    assert rt._key_rows == ref._key_rows
    assert rt._free_rows == ref._free_rows


@pytest.mark.parametrize("n_shards", [1, 4])
def test_sharded_runtime_deals_the_same_rows(n_shards, monkeypatch):
    """The sharded runtime interns through the same index and deals
    rows across shards after it (``_deal_rows``)."""
    from siddhi_tpu.parallel.mesh import make_mesh

    script = SCRIPTS["first_seen_then_warm"] + SCRIPTS["purge_then_reintern"]
    want, ref = run_script(script, 64, make_mesh(n_shards),
                           sorted_only=True, monkeypatch=monkeypatch)
    got, rt = run_script(script, 64, make_mesh(n_shards),
                         monkeypatch=monkeypatch)
    assert rt._sharded is not None and rt.n_shards == n_shards
    assert rt.stats()["intern_index"] == "hash"
    assert got == want
    # key #k of the allocation order lives on shard k % n_shards
    first = got[0]
    order = np.argsort(np.argsort(SCRAMBLED[:20]))
    assert [r // rt.parts_per_shard for r in first] == (
        order % n_shards).tolist()


def test_probe_counter_and_new_key_counter():
    rt = make_runtime(64)
    assert rt.stats()["intern_index"] is None
    chain = colliding_keys(64, 12)
    rt.intern_keys(chain)
    s = rt.stats()
    assert (s["intern_index"], s["intern_new_keys"]) == ("hash", 12)
    assert s["intern_probe_lanes"] == 0  # the first batch met no index
    rt.intern_keys(chain)
    # all but the key that sits in the home slot probe past it
    assert rt.stats()["intern_probe_lanes"] == 11
    rt.intern_keys(chain[:1].repeat(5))
    assert rt.stats()["intern_new_keys"] == 12


def test_hash_table_size_is_fixed_by_the_capacity():
    """A power of two, a full index loaded to a quarter at most: no
    rehash, and every probe chain ends at an empty slot."""
    for capacity, slots in ((1, 16), (4, 16), (5, 32), (64, 256),
                            (65, 512), (1_000_000, 1 << 22)):
        index = HashKeyIndex(capacity, np.int64)
        assert len(index._tab) == slots
    index = HashKeyIndex(64, np.int64)
    keys = np.arange(64, dtype=np.int64) * 7919
    index.insert(keys, np.arange(64))
    rows, new_keys, _probed = index.lookup(keys[::-1])
    assert rows.tolist() == list(range(64))[::-1] and len(new_keys) == 0
    # a lane of a key not held names its place among the new keys
    rows, new_keys, _probed = index.lookup(a([5, -5, 7919, 5]))
    assert new_keys.tolist() == [-5, 5]
    assert rows.tolist() == [-2, -1, 1, -2]


@pytest.mark.parametrize("first,then", [
    (a([7, 8, 7]), np.asarray(["seven", "eight"])),
    (np.asarray(["seven", "eight"]), a([7, 8, 7])),
    (a([1, 2], np.uint64), a([-1, 2])),
    (a([1 << 60, 2]), a([2.0, 0.5], np.float32)),
], ids=["str_after_int", "int_after_str", "int64_after_uint64",
        "float32_after_int64"])
def test_mixed_families_degrade_to_the_dict_intern(first, then, caplog):
    rt = make_runtime(64)
    r1 = rt.intern_keys(first)
    with caplog.at_level(logging.WARNING, logger=dense_pattern.log.name):
        r2 = rt.intern_keys(then)
    assert "falling back to the exact dict intern" in caplog.text
    assert rt.stats()["intern_index"] == "dict" and not rt._vector_intern
    # stability: every key keeps its row, one row a key, one key a row
    assert rt.intern_keys(first).tolist() == r1.tolist()
    assert rt.intern_keys(then).tolist() == r2.tolist()
    assert len(set(rt._key_rows.values())) == len(rt._key_rows)
    assert {r: k for k, r in rt._key_rows.items()} == rt._row_keys
    # dict mode is for good, a purge included
    rt.purge_idle(now=10 ** 9, idle_ms=1)
    assert rt.stats()["intern_index"] == "dict"


def test_dict_intern_agrees_with_the_hash_index_up_to_row_order():
    batches = [SCRAMBLED[:20], SCRAMBLED[10:40], SCRAMBLED[:40][::-1]]
    hashed, exact = make_runtime(64), make_runtime(64)
    exact._vector_intern = False
    for b in batches:
        rh, rd = hashed.intern_keys(b), exact.intern_keys(b)
        # the same lanes share a row in both
        assert (rh[:, None] == rh[None, :]).tolist() == (
            rd[:, None] == rd[None, :]).tolist()
    assert sorted(hashed._key_rows) == sorted(exact._key_rows)
    assert sorted(hashed._key_rows.values()) == sorted(
        exact._key_rows.values())


PARTITIONED = (
    "@app:playback @app:execution('tpu', partitions='64') "
    "define stream Txn (card {type}, amount double); "
    "partition with (card of Txn) begin "
    "@info(name='q') "
    "from every a=Txn[amount > 100.0] -> b=Txn[amount > a.amount] "
    "within 10 min "
    "select a.amount as base, b.amount as bv insert into Alerts; "
    "end;")


@pytest.mark.parametrize("key_type,keys,kind", [
    ("long", [10 ** 11 + 7, -3, 10 ** 11 + 7, 0], "hash"),
    ("int", [5, -3, 5, 0], "hash"),
    ("string", ["c1", "c0", "c1", "c2"], "sorted"),
])
def test_pattern_state_names_the_index_of_a_served_app(key_type, keys, kind):
    """Through the product: a partitioned pattern app's batches reach
    ``intern_keys`` with the key attribute's dtype, the matches are
    per key, and ``runtime.pattern_state()`` says which index served."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.event import Event

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(PARTITIONED.format(type=key_type))
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("Txn")
        h.send([Event(1000 + i, [k, 150.0 + i]) for i, k in enumerate(keys)])
        state = rt.pattern_state()["q"]
        rt.shutdown()
    finally:
        m.shutdown()
    # only keys[0] comes twice: one match, its two amounts
    assert got == [[150.0, 152.0]]
    assert state["intern_index"] == kind
    assert state["intern_new_keys"] == state["partitions_in_use"] == 3
