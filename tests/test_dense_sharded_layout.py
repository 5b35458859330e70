"""The resident shape of a wide row follows the write-back
(``ops/dense_layout.py`` ``row_shape``): ``[N, 2, 128]`` on one chip,
where the row-scatter kernel writes the flagship's 256-word rows, and
``[N, 256]`` where the state is sharded over a mesh, whose step keeps
XLA's scatter under ``shard_map``.  (PR 58 gave every engine the
kernel's shape and the sharded step XLA's scatter on it, the slower
one: its four-chip cell lost 6%.)  Held here: which engine gets which
shape, that no program of the sharded engine holds the kernel or a
rank-3 state, even where the kernel is allowed off a TPU, that the
one-chip step of the same app does hold it, and that the logical state
(a snapshot) moves between the two shapes unchanged, at the layout and
between two runtimes.
"""

import re

import jax
import numpy as np
import pytest

import bench_app
from test_dense_one_transfer import fraud_cols, pattern_of

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.kernels import row_scatter
from siddhi_tpu.ops.dense_layout import OVERFLOW, ROWS
from siddhi_tpu.ops.dense_nfa import compile_pattern
from siddhi_tpu.parallel.mesh import ShardedPatternEngine, make_mesh

P = 256          # partitions, four shards of 64
N_DEV = 4


@pytest.fixture
def kernel_allowed(monkeypatch):
    """The kernel wherever ``eligible`` reads the operand as its own,
    interpreted: the CPU stands in for the TPU's choice."""
    monkeypatch.setattr(row_scatter, "INTERPRET_OFF_TPU", True)


def _engines():
    mesh = make_mesh(N_DEV, devices=jax.devices("cpu")[:N_DEV])
    one = compile_pattern(pattern_of("fraud16_1m"), "bench", n_partitions=P)
    inner = compile_pattern(pattern_of("fraud16_1m"), "bench",
                            n_partitions=P)
    return one, inner, ShardedPatternEngine(inner, mesh)


def _events(rng, n, t0):
    key = rng.permutation(P)[:n].astype(np.int64)
    return (key.astype(np.int32),
            fraud_cols(key, rng.integers(0, 3, size=n)),
            t0 + np.arange(n, dtype=np.int64))


def test_which_engine_gets_which_shape():
    one, inner, sharded = _engines()
    assert one.layout.width == inner.layout.width == 256
    assert one.layout.row_shape == (2, 128)
    assert inner.layout.row_shape == (256,)
    assert one.init_state()[ROWS].shape == (P + 1, 2, 128)
    state = sharded.init_state()
    assert state[ROWS].ndim == 2
    assert state[ROWS].shape == (N_DEV * (P // N_DEV + 1), 256)
    assert sharded.state_specs[ROWS] == jax.sharding.PartitionSpec("p", None)
    # built for its mesh, the engine is made flat where the layout is made
    meshed = compile_pattern(pattern_of("fraud16_1m"), "bench",
                             n_partitions=P, mesh=sharded.mesh)
    assert meshed.layout.row_shape == (256,)
    # a row of one vector is flat on any engine
    card = compile_pattern(pattern_of("cardfraud_100k"), "bench",
                           n_partitions=P)
    assert card.layout.row_shape == (128,)


def test_the_sharded_programs_hold_no_kernel_and_no_rank3_state(
        kernel_allowed):
    one, inner, sharded = _engines()
    state = sharded.init_state()
    buf = jax.ShapeDtypeStruct((2 + len(sharded.col_keys), N_DEV * 64),
                               np.int32)
    traced = sharded._step.trace(state, buf)
    lowered = traced.lower().as_text()
    assert "pallas_call" not in str(traced.jaxpr)
    assert "pallas" not in lowered and "tpu_custom_call" not in lowered
    assert not re.search(r"x2x128xi32", lowered)
    assert re.search(r"tensor<\d+x256xi32>", lowered)
    assert inner.layout.scatter_path == "xla"
    # the one-chip step of the same app, the kernel allowed: it is there
    step = one.make_step(one.default_stream)
    table = one.lane_table(one.default_stream)
    traced = step.trace(one.init_state(),
                        jax.ShapeDtypeStruct((len(table), 128), np.int32))
    assert "pallas_call" in str(traced.jaxpr)
    assert re.search(r"x2x128xi32", traced.lower().as_text())
    assert one.layout.scatter_path == "kernel"


def test_the_logical_state_moves_between_the_shapes():
    """A sharded engine's state after a few batches, as its snapshot's
    logical fields, packed by the one-chip layout and read back; and the
    other way round."""
    one, inner, sharded = _engines()
    rng = np.random.default_rng(59)
    s_state, o_state = sharded.init_state(), one.init_state()
    for b in range(3):
        part, cols, ts = _events(rng, 100, 1_000 + 1_000 * b)
        s_state, _ev, _out, _n = sharded.process(s_state, part, cols, ts)
        o_state, _ev, _out = one.process(o_state, one.default_stream, part,
                                         cols, ts)
    for src, src_state, dst in ((inner, s_state, one), (one, o_state, inner)):
        snap = {k: np.asarray(v) for k, v in
                src.snapshot_state(src_state).items()}
        assert snap["active"].any()
        physical = dst.layout.pack(snap)
        assert physical[ROWS].shape[1:] == dst.layout.row_shape
        on_device = {k: jax.numpy.asarray(v) for k, v in physical.items()}
        back = dst.snapshot_state(on_device)
        assert back.keys() == snap.keys()
        for k, v in snap.items():
            assert np.array_equal(np.asarray(back[k]), v), k
        for k, v in dst.layout.unpack(on_device).items():
            assert np.array_equal(v, snap[k]), k
    # the same events: the partitions' rows are equal, shard by shard
    per = P // N_DEV
    s_log, o_log = inner.layout.unpack(s_state), one.layout.unpack(o_state)
    for k in s_log:
        rows = s_log[k].reshape((N_DEV, per + 1) + s_log[k].shape[1:])
        assert np.array_equal(
            rows[:, :per].reshape((P,) + rows.shape[2:]), o_log[k][:P]), k


APP = bench_app._json("configs", "fraud16_1m.json")
HEADER = "@app:playback @app:execution('tpu', partitions='64'{devices})"


def _run(devices, batches, restore_from=None, snapshot_after=None):
    """The flagship's app on one chip (``devices`` empty) or sharded:
    its alerts, its dense runtime's ``stats()`` and, after batch
    ``snapshot_after``, its snapshot as host arrays."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            HEADER.replace("{devices}", devices) + " " + APP["app"])
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        dense = next(iter(rt.partitions["partition_0"]
                          .dense_query_runtimes.values())).pattern_processor
        if restore_from is not None:
            dense.restore(restore_from)
        send = rt.get_input_handler("Txn").send_batch
        snap = None
        for i, b in enumerate(batches):
            send(b)
            if i == snapshot_after:
                snap = dense.snapshot()
                snap["dense_state"] = {k: np.array(v) for k, v in
                                       snap["dense_state"].items()}
        rt.drain_device_emits()
        stats = dense.stats()
        rt.shutdown()
    finally:
        m.shutdown()
    return got, stats, snap


def _batches(n):
    """Every key once a batch, its value rising: a key completes the
    chain at its sixteenth event."""
    keys = np.arange(48, dtype=np.int64)
    return [EventBatch("Txn", ["key", "v"],
                       {"key": keys, "v": j + 1.5 + keys / 4096.0},
                       1_000 * (j + 1) + np.arange(48, dtype=np.int64))
            for j in range(n)]


def test_a_sharded_app_is_built_flat_from_the_start(kernel_allowed,
                                                   monkeypatch):
    """The planner knows the mesh before it builds the engine, so the
    engine's own traceability check traces the flat rows and no kernel
    (on the chip: no import of Pallas in a four-chip app's set-up),
    where a one-chip app's check traces the kernel."""
    calls = []
    real = row_scatter.row_scatter
    monkeypatch.setattr(
        row_scatter, "row_scatter",
        lambda rows, idx, new: calls.append(rows.shape) or real(rows, idx,
                                                                new))
    for devices, traced in ((", devices='4'", []), ("", [(65, 2, 128)])):
        del calls[:]
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                HEADER.replace("{devices}", devices) + " " + APP["app"])
            dense = next(iter(rt.partitions["partition_0"]
                              .dense_query_runtimes.values())
                         ).pattern_processor
            assert (dense.engine.mesh is not None) == bool(devices)
            assert dense.stats()["state_row_shape"] == (
                (256,) if devices else (2, 128))
        finally:
            m.shutdown()
        assert calls == traced


@pytest.mark.parametrize("first,second", [("", ", devices='1'"),
                                          (", devices='1'", "")],
                         ids=["one_chip_to_sharded", "sharded_to_one_chip"])
def test_a_snapshot_restores_into_the_other_shape(first, second):
    """A runtime's snapshot after ten batches, restored into a runtime
    of the other resident shape (a mesh of one device shards nothing
    away: the rows are the same rows, flat), which owes the alerts of
    the last ten batches as the first runtime does."""
    batches = _batches(20)
    want, stats_a, snap = _run(first, batches, snapshot_after=9)
    got, stats_b, _ = _run(second, batches[10:], restore_from=snap)
    assert len(want) == 5 * 48 and got == want
    shapes = {"": (2, 128), ", devices='1'": (256,)}
    assert stats_a["state_row_shape"] == shapes[first]
    assert stats_b["state_row_shape"] == shapes[second]
    # off a TPU either shape is written by XLA's scatter
    assert stats_a["scatter_path"] == stats_b["scatter_path"] == "xla"


WIDE_TIMER = (
    "@info(name='q') from every e1=S[v > 1.0] -> e2=S[v > e1.v] -> "
    "e3=S[n >= e1.n] -> not T[v > e1.v] for 300 millisec "
    "select e1.v as a, e2.v as b, e1.n as c, e3.v as d insert into Out;")


def test_a_wide_row_under_the_timer_in_both_shapes():
    """A trailing absent node's deadline in a 256-word row: the steps of
    both streams and the timer step (which runs over every row: slices of
    the resident rows alone, never a reshape) on the one-chip shape and
    on a sharded state give the same partitions' rows and the same
    fired matches."""
    import dense_layout_cases as C

    n_dev = 2
    per = C.P // n_dev
    mesh = make_mesh(n_dev, devices=jax.devices("cpu")[:n_dev])
    one = compile_pattern(C.STREAMS + WIDE_TIMER, "q", n_partitions=C.P)
    inner = compile_pattern(C.STREAMS + WIDE_TIMER, "q", n_partitions=C.P)
    sharded = {sk: ShardedPatternEngine(inner, mesh, stream_key=sk)
               for sk in ("S", "T")}
    assert one.has_deadlines and one.layout.width == 256
    assert one.layout.row_shape == (2, 128)
    assert inner.layout.row_shape == (256,)
    o_state, s_state = one.init_state(), sharded["S"].init_state()
    rng = np.random.default_rng(7)
    fired_any = 0
    ts0 = C.BASE_TS
    for b in range(6):
        sk = "T" if b == 3 else "S"
        part, cols, ts = C._batch(rng, 40, ts0)
        o_state, o_ev, o_out = one.process(o_state, sk, part, cols, ts)
        s_state, s_ev, s_out, _n = sharded[sk].process(s_state, part, cols,
                                                       ts)
        assert np.array_equal(np.asarray(o_ev), np.asarray(s_ev))
        assert np.array_equal(np.asarray(o_out), np.asarray(s_out))
        ts0 += 250
        o_state, o_fired = one.on_time_state(o_state, ts0 - 20)
        s_state, s_fired = inner.on_time_state(s_state, ts0 - 20)
        assert (o_fired is None) == (s_fired is None)
        if o_fired is not None:
            fired_any += len(o_fired[0])
            rows = np.asarray(s_fired[2])
            assert np.array_equal(rows // (per + 1) * per + rows % (per + 1),
                                  np.asarray(o_fired[2]))
            for a, b_ in zip(o_fired[:2], s_fired[:2]):
                assert np.array_equal(np.asarray(a), np.asarray(b_))
        assert s_state[ROWS].ndim == 2 and o_state[ROWS].ndim == 3
        o_log, s_log = one.layout.unpack(o_state), inner.layout.unpack(s_state)
        for k in o_log:
            rows = s_log[k].reshape((n_dev, per + 1) + s_log[k].shape[1:])
            assert np.array_equal(
                rows[:, :per].reshape((C.P,) + rows.shape[2:]),
                o_log[k][:C.P]), (b, k)
    assert fired_any > 0
