"""What a device table and its join show of themselves.

``DevTableJoinRuntime.state`` names the table's device arrays and
follows a mutation; ``lowering()`` says ``host`` once the table has
demoted itself.  On an injected ``tracer.clock`` that advances by one at
each reading ("adjacent" is an equality): a probe chunk's ``convert``,
``put`` and ``dispatch`` tile its ``ingest``, a batch under ``MAX_CHUNK``
is one chunk (one ``ingest``, ``step`` and ``emit``), one of more than
``MAX_CHUNK`` events is as many such ways in inside one cycle, a
mutation is one ``mutate`` span inside the writing query's ``deliver``,
childless, with the keys handed to the table as its count, and ``fetch``, ``build`` and
``deliver`` still tile ``emit``.  The four ``siddhi.devtable.*`` scopes
are declared and on the lowered programs' operations.
"""

import re

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.devtable.join import DevTableJoinRuntime
from siddhi_tpu.observability import trace as trace_mod

from test_way_back import Ticks

BODY = (
    "define stream S (k int, x float, m int); "
    "define stream U (k int, v float, f bool); "
    "define stream D (k int); "
    "@PrimaryKey('k') define table T (k int, v float, f bool); "
    "@info(name='ups') from U select k, v, f update or insert into T "
    "on T.k == k; "
    "@info(name='del') from D delete T on T.k == k; "
    "@info(name='j') from S join T on S.k == T.k and S.x > T.v "
    "select S.k as k, S.x as x, S.m as m, T.v as v, T.f as f "
    "insert into Out;")


class App:
    def __init__(self, capacity=64, traced=True):
        self.manager = SiddhiManager()
        self.rt = self.manager.create_siddhi_app_runtime(
            "@app:name('dt_obs') @app:playback @app:execution('tpu') "
            f"@app:devtables(capacity='{capacity}') "
            + ("@app:trace(sample='1', cycles='64') " if traced else "")
            + BODY)
        self.tracer = self.rt.app_context.tracer
        if traced:
            self.tracer.clock = Ticks()
        self.rows = []
        self.rt.add_callback("Out", self.rows.extend)
        self.rt.start()
        self.join = self.rt.query_runtimes["j"].device_runtime
        self.table = self.join.table
        self.ts = 1000

    def send(self, stream, **cols):
        names = list(cols)
        n = len(cols[names[0]])
        ts = self.ts + np.arange(n, dtype=np.int64)
        self.ts += n
        self.rt.get_input_handler(stream).send_batch(
            EventBatch(stream, names, cols, ts))

    def upsert(self, keys, vals):
        keys = np.asarray(keys, dtype=np.int32)
        self.send("U", k=keys, v=np.asarray(vals, dtype=np.float32),
                  f=np.zeros(len(keys), dtype=bool))

    def probe(self, keys, x=1000.0):
        keys = np.asarray(keys, dtype=np.int32)
        self.send("S", k=keys, x=np.full(len(keys), x, dtype=np.float32),
                  m=np.arange(len(keys), dtype=np.int32))

    def cycles(self, engine):
        """In ring order, the cycles of one engine kind:
        ``{stage: [span, ...]}`` each."""
        out = []
        for spans in self.tracer.recorder.cycle_groups().values():
            if spans[0][2] != engine:
                continue
            by = {}
            for s in spans:
                by.setdefault(s[1], []).append(s)
            out.append(by)
        return out

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.manager.shutdown()
        return False


# -- where the state lives, and what lowering() says ---------------------------


def test_state_names_the_tables_device_arrays_and_follows_a_mutation():
    with App(traced=False) as app:
        assert isinstance(app.join, DevTableJoinRuntime)
        cols, valid = app.join.state
        assert cols is app.table._dcols and valid is app.table._dvalid
        leaves = jax.tree_util.tree_leaves(app.join.state)
        assert len(leaves) == 4      # three columns and the validity lane
        assert all(leaf.shape == (64,) for leaf in leaves)
        assert {d.platform for leaf in leaves for d in leaf.devices()} == {
            jax.default_backend()}
        assert not np.asarray(valid).any()
        app.upsert([3, 5, 3], [1.0, 2.0, 3.0])
        cols2, valid2 = app.join.state
        assert valid2 is not valid and cols2["v"] is not cols["v"]
        assert np.asarray(valid2).sum() == 2
        assert sorted(np.asarray(cols2["v"])[np.asarray(valid2)]) == [2.0, 3.0]
        # the very arrays the next probe reads
        assert app.join.state[1] is app.table.device_state()[1]


def test_lowering_says_host_once_the_table_has_demoted():
    with App(capacity=4, traced=False) as app:
        app.upsert([1, 2, 3], [1.0, 2.0, 3.0])
        assert app.rt.lowering()["j"] == "devtable"
        app.upsert([4, 5, 6], [4.0, 5.0, 6.0])      # six keys, four slots
        assert app.table.demoted
        assert app.rt.lowering() == {"ups": "device", "del": "device",
                                     "j": "host"}
        # after a demotion the join claims no device state
        assert jax.tree_util.tree_leaves(app.join.state) == []
        app.probe([1, 6])
        assert app.join.host_fallback_batches == 1
        assert sorted(e.data[0] for e in app.rows) == [1, 6]


# -- the probe's way in ------------------------------------------------------------


def tiles(ingest, parts):
    """``parts`` in order, the first starting at the reading after
    ``ingest``'s start, each at the reading after the one before ends,
    ``ingest`` ending at the reading after the last."""
    at = ingest[3]
    for p in parts:
        assert p[3] == at + 1, (p, at)
        assert p[4] > p[3]
        at = p[4]
    assert ingest[4] == at + 1


def test_convert_put_dispatch_tile_a_probes_ingest():
    with App() as app:
        app.upsert(np.arange(40), np.arange(40, dtype=np.float32))
        for _ in range(3):
            app.probe(np.arange(30))
        probes = app.cycles("devtable_join")
        assert len(probes) == 3
        for by in probes:
            (ingest,), (put,), (dispatch,) = (by["ingest"], by["put"],
                                              by["dispatch"])
            keys, lanes = by["convert"]     # the key expression, the lanes
            tiles(ingest, [keys, lanes, put, dispatch])
            # the events are counted once, by the chunk's convert
            assert (keys[5], lanes[5], ingest[5]) == (0, 30, 30)
            assert dispatch[5] == 1
            # key lane, slot lane, mask lane and the condition's two
            # lanes (the key again, and x), padded to 32; m rides none
            assert put[5] == 32 * (4 + 4 + 1 + 4 + 4)
            assert by["step"][0][3] == ingest[4]
            assert {s[2] for spans in by.values() for s in spans} == {
                "devtable_join"}
        assert len(app.rows) == 90
        st = app.join.ingest_stats
        assert (st.device_chunks, st.device_puts) == (3, 3)
        stats = app.tracer.stage_stats()
        assert stats["dispatch"]["spans"] >= 3 and "mutate" in stats


def test_a_batch_under_max_chunk_is_one_ordinary_cycle():
    """8,192 events under the real bound: one chunk, so one ``ingest``
    (one ``put`` of five leaves, one ``dispatch``), one ``step``, one
    ``emit``; the host resolved every key to its slot."""
    assert DevTableJoinRuntime.MAX_CHUNK >= 65536
    with App(capacity=12000) as app:
        keys = np.arange(8192, dtype=np.int32)
        app.upsert(keys, np.zeros(8192, dtype=np.float32))
        app.probe(keys)
        (by,) = app.cycles("devtable_join")
        assert len(by["ingest"]) == len(by["step"]) == len(by["emit"]) == 1
        (put,), (dispatch,), (fetch,) = by["put"], by["dispatch"], by["fetch"]
        key_expr, lanes = by["convert"]
        tiles(by["ingest"][0], [key_expr, lanes, put, dispatch])
        assert (key_expr[5], lanes[5], dispatch[5]) == (0, 8192, 1)
        assert put[5] == 8192 * (4 + 4 + 1 + 4 + 4)
        assert [e.data[2] for e in app.rows] == list(range(8192))
        st = app.join.ingest_stats
        assert (st.device_chunks, st.device_puts) == (1, 1)
        stats = app.rt.statistics()
        assert stats["io.siddhi.SiddhiApps.dt_obs.Siddhi.Queries.j.slotHits"] == 8192
        assert stats["io.siddhi.SiddhiApps.dt_obs.Siddhi.Queries.j.slotMisses"] == 0


def test_a_batch_past_max_chunk_is_as_many_ways_in_in_one_cycle(monkeypatch):
    """Each chunk is finished inside its submit (gate, fetch, rows)
    before the next is put: the cycle holds one ``ingest``, ``step`` and
    ``emit`` a chunk, in turn, every one tiled by its own parts."""
    monkeypatch.setattr(DevTableJoinRuntime, "MAX_CHUNK", 16)
    with App() as app:
        app.upsert(np.arange(40), np.arange(40, dtype=np.float32))
        app.probe(np.arange(40))
        (by,) = app.cycles("devtable_join")
        assert len(by["ingest"]) == len(by["step"]) == len(by["emit"]) == 3
        assert len(by["convert"]) == 4 and len(by["put"]) == 3
        keys, *lanes = by["convert"]
        assert keys[5] == 0 and [c[5] for c in lanes] == [16, 16, 8]
        tiles(by["ingest"][0], [keys, lanes[0], by["put"][0],
                                by["dispatch"][0]])
        for i in (1, 2):
            tiles(by["ingest"][i], [lanes[i], by["put"][i],
                                    by["dispatch"][i]])
            # it starts after the chunk before has reached the callback
            assert by["ingest"][i][3] > by["emit"][i - 1][4]
        for i in range(3):
            (ing, step, emit) = (by["ingest"][i], by["step"][i],
                                 by["emit"][i])
            assert step[3] == ing[4] and emit[3] > step[4]
            fetch, build, deliver = (by["fetch"][i], by["build"][i],
                                     by["deliver"][i])
            assert fetch[3] == emit[3]
            assert build[3] == fetch[4] + 1 and deliver[3] == build[4] + 1
            assert emit[4] == deliver[4] + 1
        assert [e.data[2] for e in app.rows] == list(range(40))
        st = app.join.ingest_stats
        assert (st.device_chunks, st.device_puts) == (3, 3)


# -- a mutation ----------------------------------------------------------------------


def test_a_mutation_is_one_mutate_span_inside_deliver_with_its_keys():
    with App() as app:
        app.upsert([1, 2, 3], [1.0, 2.0, 3.0])    # three inserts
        # 9 inserted, then updated as 2 and 1 are: two scatters
        app.upsert([2, 9, 2, 9, 1], [5.0] * 5)
        app.send("D", k=np.asarray([3, 77], dtype=np.int32))   # one hit
        ups1, ups2, dele = app.cycles("device")
        for by, keys in ((ups1, 3), (ups2, 5), (dele, 2)):
            (mutate,), (deliver,), (emit,) = (by["mutate"], by["deliver"],
                                              by["emit"])
            (fetch,), (build,) = by["fetch"], by["build"]
            assert mutate[5] == keys
            assert deliver[3] < mutate[3] and mutate[4] < deliver[4]
            # fetch, build and deliver still tile emit
            assert fetch[3] == emit[3]
            assert build[3] == fetch[4] + 1 and deliver[3] == build[4] + 1
            assert emit[4] == deliver[4] + 1
            # it is whole: the scatter's lanes, put and call are no
            # convert, put or dispatch (those are the way in's names)
            assert not [s for spans in by.values() for s in spans
                        if mutate[3] < s[3] < mutate[4]]
            assert len(by["put"]) == 1 and by["put"][0][4] < emit[3]
        assert app.table.scatter_steps == 4
        assert len(app.table) == 3      # 1, 2, 9
        hist = app.tracer.stage_hist[trace_mod.STAGE_MUTATE]
        assert hist.count == 3
        assert trace_mod.STAGE_MUTATE in trace_mod.CYCLE_STAGES


def test_an_untraced_mutation_records_nothing_and_writes_the_same():
    with App(traced=False) as app:
        app.upsert([1, 2, 2], [1.0, 2.0, 3.0])
        app.probe([1, 2])
        assert sorted((e.data[0], e.data[3]) for e in app.rows) == [
            (1, 1.0), (2, 3.0)]
        assert app.table.scatter_steps == 2      # an insert, an update


# -- the device's side ---------------------------------------------------------------


def scopes_in(lowered):
    text = lowered.compile().as_text()
    return {sc for sc in trace_mod.DEVICE_SCOPES
            if re.search(r'op_name="[^"]*/' + re.escape(sc) + r'[/"]', text)}


def test_the_four_devtable_scopes_are_on_the_lowered_programs():
    devtable = {sc for sc in trace_mod.DEVICE_SCOPES if ".devtable." in sc}
    assert devtable == {"siddhi.devtable.probe", "siddhi.devtable.gather",
                        "siddhi.devtable.condition",
                        "siddhi.devtable.scatter"}
    with App(traced=False) as app:
        tcols, valid = app.table.device_state()
        B = 16
        lanes = {ek: np.zeros(B, dtype=dt)
                 for ek, (_attr, dt) in app.join._cond_lanes.items()}
        assert set(lanes) == {"S.k", "S.x"}     # m rides no lane
        # probe: the guard's gathers (validity, the key at the slot) and
        # its compare; gather: the other columns by the same slot
        probe = app.join._probe.lower(
            np.zeros(B, np.int32), np.zeros(B, np.int32), np.ones(B, bool),
            lanes, tcols["k"], tcols, valid)
        assert scopes_in(probe) == devtable - {"siddhi.devtable.scatter"}
        vals = {"v": np.zeros(8, np.float32), "f": np.zeros(8, bool)}
        scatter = app.table._scatter.lower(
            tcols, valid, vals, app.table._pad_slots(np.arange(3), 8),
            app.table._pad_slots(np.arange(0), 8))
        assert scopes_in(scatter) == {"siddhi.devtable.scatter"}


def test_the_compile_cache_keys_on_the_scope_names():
    """Two programs that differ only in a scope's name must not share a
    cache entry (PR 35): the setting that holds that is global."""
    from siddhi_tpu.util import compile_cache

    assert compile_cache._SETTINGS[
        "jax_compilation_cache_include_metadata_in_key"] is True
