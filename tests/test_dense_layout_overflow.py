"""``DenseStateLayout.scatter`` writes the ``overflow`` vector only when
a lane of the batch dropped an instance (``ops/dense_layout.py``, PR 61).

A batch inside its ``instances`` owes the vector nothing: the step reads
the reduction of its increments and hands the donated vector on.  A
batch that overfills a node's lanes adds exactly what the unconditional
add added, row for row: held here against the same programs traced with
the conditional's add taken always (the parent's formula), in the first
program, in the rounds programs' wide loops at both widths of their
ladder and in the run, and under ``shard_map`` on a mesh of four CPU
devices, where the predicate is the shard's own.  The runtime's view of
the vector (``overflow_total``, the poll's warning, a snapshot restored
into a second runtime) carries the count.

Through the chip's own compiler, with no chip: the 131,072-lane step
over ``[N, 4, 128]`` and over ``[N, 2, 128]`` holds the conditional, its
quiet branch holds its parameter and nothing else, and nothing outside
it scatters into the vector.  The rows' join is the parent's, byte for
byte (PR 61 measured its other forms and kept it), so nothing here
holds it.
"""

import logging
import re

import jax
import numpy as np
import pytest

from cardfraud_bench import CONFIG, amount_of, txn_batch
from test_dense_layout import _computations
from test_dense_one_transfer import pattern_of

from siddhi_tpu import SiddhiManager
from siddhi_tpu.ops.dense_layout import OVERFLOW
from siddhi_tpu.ops.dense_nfa import compile_pattern
from siddhi_tpu.parallel.mesh import ShardedPatternEngine, make_mesh

CARD = pattern_of("cardfraud_100k")
T0 = 1_000_000


def charges(cards, whole):
    """One charge a card: ``whole`` above 0 opens an instance (and, the
    amounts of a card falling, counts for none); 0 opens nothing."""
    cards = np.asarray(cards, dtype=np.int64)
    whole = np.broadcast_to(np.asarray(whole), cards.shape)
    return {"card": cards,
            "amount": np.where(whole > 0, amount_of(cards, whole),
                               0.0).astype(np.float32),
            "merchant": np.zeros(len(cards), dtype=np.int32)}


def falling(times, loud=True):
    """A batch in which card ``c`` comes ``times[c]`` times, its charges
    falling (every one opens an instance, the fifth and later find no
    lane) or, quiet, all 0: the same rounds and nothing to drop."""
    times = np.asarray(times)
    cards = np.concatenate([np.flatnonzero(times > r)
                            for r in range(times.max())])
    nth = np.concatenate([np.full((times > r).sum(), r)
                          for r in range(times.max())])
    return cards, charges(cards, (40 - nth) if loud else 0)


class Engine:
    """The card app's engine on one device or, ``mesh`` set, behind
    ``shard_map`` on four CPU devices: ``step`` sends a batch and
    returns the ``overflow`` vector as the host sees it."""

    def __init__(self, partitions, mesh=False):
        self.inner = compile_pattern(CARD, "bench", n_partitions=partitions)
        self.engine = self.inner if not mesh else ShardedPatternEngine(
            self.inner, make_mesh(4, devices=jax.devices("cpu")[:4]))
        self.state = self.engine.init_state()

    def step(self, part, cols, n):
        batch = (np.asarray(part, dtype=np.int32), cols,
                 np.full(len(part), T0 + n, dtype=np.int64))
        if self.engine is self.inner:
            batch = (self.inner.default_stream,) + batch
        self.state, *_rows = self.engine.process(self.state, *batch)
        return np.array(self.state[OVERFLOW])


def _one_a_batch(partitions):
    """Seven batches of every card once: cards under 20 charge falling
    amounts six times (the fifth and the sixth find no lane), the others
    and the last batch charge nothing."""
    cards = np.arange(partitions)
    return [(cards, charges(cards, np.where(cards < 20, 40 - n, 0)
                            if n < 6 else 0), n >= 4 and n < 6)
            for n in range(7)]


def _first_program():
    owed = np.zeros(65, dtype=np.int32)
    owed[:20] = 2
    return (lambda: Engine(64)), _one_a_batch(64), owed, False


def _rounds_programs():
    # 300 cards six times, 200 eight times, 50 ten times: rounds of 550
    # (six), 250 (two) and 50 (two); 3,350 events past the first round
    # in a rounds program of 4,096, its ladder (4096, 512): the rounds
    # of 550 in the wide loop at 4,096, those of 250 at 512, those of 50
    # links of the run.  A card's fifth and later charges are dropped.
    times = [6] * 300 + [8] * 200 + [10] * 50 + [0] * 474
    owed = np.zeros(1025, dtype=np.int32)
    owed[:300], owed[300:500], owed[500:550] = 2, 4, 6
    batches = [(*falling(times, loud=False), False),
               (*falling(times), True),
               (*falling(times, loud=False), False)]
    return (lambda: Engine(1024)), batches, owed, True


def _mesh_of_4():
    # cards under 20 lie on the first shard: the other three take the
    # quiet branch in every batch
    owed = np.zeros(4 * 65, dtype=np.int32)
    owed[:20] = 2
    return (lambda: Engine(256, mesh=True)), _one_a_batch(256), owed, None


CASES = {"first_program": _first_program,
         "rounds_wide_narrow_and_run": _rounds_programs,
         "mesh_of_4": _mesh_of_4}


@pytest.mark.parametrize("case", list(CASES))
def test_the_vector_is_written_where_owed_and_only_then(case, monkeypatch):
    make, batches, owed, rounds = CASES[case]()
    # the parent's formula: the add, whatever the batch dropped
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "cond",
                  lambda _pred, add, _keep, *operands: add(*operands))
        parent = make()
        want = [parent.step(part, cols, n)
                for n, (part, cols, _loud) in enumerate(batches)]
    subject = make()
    before = np.array(subject.state[OVERFLOW])
    assert not before.any()
    for n, (part, cols, loud) in enumerate(batches):
        got = subject.step(part, cols, n)
        assert got.dtype == np.int32 and np.array_equal(got, want[n]), n
        assert (got != before).any() == loud, n
        before = got
    assert np.array_equal(before, owed)
    engine = subject.engine
    if rounds is None:
        # the predicate is the shard's own: no collective but the count's
        lowered = engine._step.trace(subject.state, jax.ShapeDtypeStruct(
            (2 + len(engine.col_keys), 4 * 64), np.int32)).lower().as_text()
        assert lowered.count("all_reduce") == 1
        assert "stablehlo.case" in lowered
    else:
        assert ("rounds" in {k[1] for k in engine._step_cache}) == rounds
        assert engine.rounds_ladder(4096) == [(4096, 512), (512, 128)]


DENSE = ("@app:statistics('true') "
         + CONFIG["header"].format(**CONFIG["rehearsal"]))


def _runtime(m):
    rt = m.create_siddhi_app_runtime(DENSE + " " + CONFIG["app"])
    rt.start()
    (pr,) = rt.partitions.values()
    return rt, pr.dense_query_runtimes["bench"].pattern_processor


def test_the_total_the_poll_and_a_restored_snapshot_carry_the_count(
        caplog, monkeypatch):
    """Card 77's six falling charges, one a batch, under a poll every
    step: the total and the warning follow the fifth and the sixth; the
    snapshot holds 2 for the card's row, and a second runtime restored
    from it drops the card's next charge on top."""
    m = SiddhiManager()
    try:
        rt, dense = _runtime(m)
        monkeypatch.setattr(type(dense), "_OVF_POLL", 1)
        send = rt.get_input_handler(CONFIG["stream"]).send_batch
        totals = []
        with caplog.at_level(logging.WARNING, logger="siddhi_tpu"):
            for n in range(6):
                send(txn_batch([77], [amount_of(77, 40 - n)], [T0 + n]))
                rt.drain_device_emits()
                totals.append(dense.overflow_total())
        assert totals == [0, 0, 0, 0, 1, 2]
        advice = [r.getMessage() for r in caplog.records
                  if "instances='N'" in r.getMessage()]
        assert advice and "pending instance(s) dropped" in advice[0]
        snap = dense.snapshot()
        snap["dense_state"] = {k: np.array(v) for k, v in
                               snap["dense_state"].items()}
        assert snap["dense_state"][OVERFLOW].sum() == 2
        assert (snap["dense_state"][OVERFLOW] == 2).sum() == 1
        rt.shutdown()
        rt2, dense2 = _runtime(m)
        dense2.restore(snap)
        assert dense2.overflow_total() == 2
        send2 = rt2.get_input_handler(CONFIG["stream"]).send_batch
        send2(txn_batch([77], [amount_of(77, 30)], [T0 + 7]))
        rt2.drain_device_emits()
        assert dense2.overflow_total() == 3
        # a batch that drops nothing leaves it there
        send2(txn_batch([78], [0.0], [T0 + 8]))
        rt2.drain_device_emits()
        assert dense2.overflow_total() == 3
        rt2.shutdown()
    finally:
        m.shutdown()


# -- the wide rows through the chip's own compiler, with no chip -----------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


LANES = 131072


@pytest.mark.parametrize("config, row_shape", [("iot32_1250k", (4, 128)),
                                               ("fraud16_1m", (2, 128))])
def test_the_compiled_step_adds_behind_a_conditional_and_nowhere_else(
        one_chip, monkeypatch, config, row_shape):
    from siddhi_tpu.kernels import probe

    monkeypatch.setattr(probe, "interpret_mode", lambda: False)
    engine = compile_pattern(pattern_of(config), "bench", n_partitions=4096)
    assert engine.layout.row_shape == row_shape
    sk = engine.default_stream
    state = {k: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
             for k, s in engine.layout.physical_shapes(4097).items()}
    buf = jax.ShapeDtypeStruct((len(engine.lane_table(sk)), LANES), np.int32,
                               sharding=one_chip)
    text = engine.make_step(sk).trace(state, buf).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert engine.layout.scatter_path == "kernel"
    comps = _computations(text)
    ((where, cond),) = [(c, line) for c, instrs in comps.items()
                        for _n, _t, op, line in instrs if op == "conditional"]
    assert "siddhi.dense.scatter" in cond
    branches = re.search(r"branch_computations=\{([^}]*)\}", cond).group(1)
    quiet, loud = sorted((comps[b.strip().lstrip("%")]
                          for b in branches.split(",")), key=len)
    # the quiet branch is its parameter handed on (no scatter, no copy,
    # no fusion), the loud one the scatter-add
    assert [op for _n, _t, op, _l in quiet] == ["parameter"]
    assert any("scatter" in line for _n, _t, _op, line in loud)
    # outside the conditional's branch nothing scatters into the vector
    assert not any(re.match(r"s32\[4097\]", t) and op in ("fusion", "scatter")
                   for _n, t, op, _l in comps[where])
    # (the rows' join stands as it was: PR 61 found no form of it in
    # XLA worth half a millisecond, ``ops/dense_layout.py``)
