"""A run of 1,000 events of one key in one batch (test_dense_skew.py
holds the shorter runs and the helpers): every engine kind on one
device, where the run is a device loop, and one kind over the 4-device
CPU mesh, where the rounds are stepped from the host, a thousand
dispatches."""

from __future__ import annotations

import pytest

from dense_layout_cases import ENGINES
from test_dense_skew import check_against_one_event_at_a_time
from test_dense_skew import run_kernel  # noqa: F401  (fixture)


@pytest.mark.parametrize("eng_name,n_dev",
                         [(e, 1) for e in ENGINES] + [("every_r2", 4)])
def test_run_of_1000_equals_one_event_at_a_time(eng_name, n_dev):
    check_against_one_event_at_a_time(eng_name, n_dev, "run_of_1000")


def test_run_of_1000_through_the_run_kernel(run_kernel):
    """The same through the Pallas kernel (interpreted here)."""
    check_against_one_event_at_a_time("every_r2", 1, "run_of_1000")


# -- the run kernel through the chip's own compiler, with no chip --------------

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


def test_the_run_kernel_goes_through_mosaic(one_chip, monkeypatch):
    """The north-star chain's run kernel (16 nodes, captures, `within`)
    compiles for a v5e: dynamic lane rotation, scalar prefetch, resident
    output blocks.  What Mosaic refuses it refuses here."""
    import jax
    import numpy as np

    from siddhi_tpu.kernels import dense_run, probe
    from siddhi_tpu.ops.dense_nfa import compile_pattern

    chain = " -> ".join(
        ["e1=Txn[v > 0.0]"] + [f"e{i}=Txn[v > {i - 1}.0 and v > e1.v]"
                               for i in range(2, 17)])
    eng = compile_pattern(
        "define stream Txn (key long, v double); @info(name='bench') "
        f"from every {chain} within 10 min "
        "select e1.v as v1, e16.v as v16 insert into Alerts;",
        "bench", n_partitions=64)
    eng.reset_on_emit = False   # as the runtime builds an `every` chain
    assert dense_run.eligible(eng, "Txn")
    assert eng.device_col_keys("Txn") == ["v"]   # `key` stays on the host
    monkeypatch.setattr(probe, "interpret_mode", lambda: False)
    run = dense_run.build_run(eng, "Txn")

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    fields = {name: shape((dense_run.LANES,) + s, dt)
              for name, (dt, s) in eng.layout.fields.items()}
    tiles = lambda dt: shape((65, dense_run.LANES), dt)
    links = shape((2048,), np.int32)
    compiled = jax.jit(run).trace(
        fields, {"v": tiles(np.float32)}, tiles(np.int32), links, links,
        shape((), np.int32)).lower(lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("row_shape", [(2, 128), (128,)])
def test_the_row_scatter_kernel_goes_through_mosaic(one_chip, monkeypatch,
                                                    row_shape):
    """The write-back of a 131,072-event batch into a million rows, at
    both shapes a resident row takes: one DMA a row (Mosaic takes a
    single row only where it lies on end: ``[N, 256]`` it refuses), the
    index scalar-prefetched, and the state aliased: no second one."""
    import jax
    import numpy as np

    from siddhi_tpu.kernels import probe, row_scatter

    monkeypatch.setattr(probe, "interpret_mode", lambda: False)
    def shape(s):
        return jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)

    n, b = 1_000_001, 131_072
    # (a row of one vector compiles too; the engine keeps XLA's there)
    assert row_scatter.eligible(shape((n,) + row_shape), b) == (
        len(row_shape) == 2)
    compiled = jax.jit(row_scatter.row_scatter, donate_argnums=(0,)).trace(
        shape((n,) + row_shape), shape((b,)), shape((b,) + row_shape)
    ).lower(lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state = 4 * n * int(np.prod(row_shape))
    assert mem.alias_size_in_bytes >= state and mem.temp_size_in_bytes < 2**20


def test_the_sharded_step_keeps_xlas_scatter(topo, monkeypatch):
    """Inside ``shard_map`` the step keeps XLA's scatter and the flat
    ``[N, 256]`` rows XLA's scatter is fastest on (``DenseStateLayout
    .row_shape``: the kernel was never run on four chips, and a shard
    of ``[N, 2, 128]`` cost the four-chip cell 6%, PR 58): a shard's
    rows as at the commit before the kernel, the state still donated."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from siddhi_tpu.kernels import probe
    from siddhi_tpu.ops.dense_nfa import compile_pattern
    from siddhi_tpu.parallel.mesh import ShardedPatternEngine
    from test_dense_one_transfer import pattern_of

    monkeypatch.setattr(probe, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("p",))
    eng = compile_pattern(pattern_of("fraud16_1m"), "bench",
                          n_partitions=65_536)
    sharded = ShardedPatternEngine(eng, mesh, "p")
    rows = sharded.n_shards * sharded.rows_per_shard
    state = {k: jax.ShapeDtypeStruct(s, np.int32, sharding=NamedSharding(
        mesh, sharded.state_specs[k]))
        for k, s in eng.layout.physical_shapes(rows).items()}
    buf = jax.ShapeDtypeStruct(
        (2 + len(sharded.col_keys), sharded.n_shards * 4096), np.int32,
        sharding=NamedSharding(mesh, PartitionSpec(None, "p")))
    compiled = sharded._step.trace(state, buf).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert eng.layout.row_shape == (256,)
    assert "s32[16385,256]" in text and ",2,128]" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        4 * int(np.prod(s.shape)) for s in state.values()) // 4


def test_the_device_tables_programs_fit_a_v5e_at_a_million_slots(one_chip):
    """``profile_join_1m``'s two programs at the cell's size, through the
    chip's own compiler (kept in this file: one worker holds libtpu):
    the slot-addressed probe of one 8,192-event batch and the indexed
    scatter of 8,192 rows at C = 1,048,576 hold no temporaries and no
    plane (a gather a column, a ``scatter`` a lane written); the scopes
    the benchmark reads are on the compiled programs."""
    import json
    import os

    import jax
    import numpy as np

    from siddhi_tpu import SiddhiManager

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "profile_join_1m.json")) as f:
        config = json.load(f)
    C, B, N = config["full"]["capacity"], 8192, 8192

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            config["header"].format(capacity=64) + " " + config["app"])
        join = rt.query_runtimes["probe"].device_runtime
        table = join.table
        assert B <= join.MAX_CHUNK      # the cell's batch is one chunk
        tcols = {nm: shape((C,), dt) for nm, dt in table._dtypes.items()}
        valid = shape((C,), np.bool_)
        lanes = {ek: shape((B,), dt)
                 for ek, (_attr, dt) in join._cond_lanes.items()}
        probe = join._probe.trace(
            shape((B,), np.int32), shape((B,), np.int32),
            shape((B,), np.bool_), lanes, tcols[table.pk], tcols,
            valid).lower(lowering_platforms=("tpu",)).compile()
        vals = {nm: shape((N,), dt) for nm, dt in table._dtypes.items()
                if nm != table.pk}
        scatter = table._scatter.trace(
            tcols, valid, vals, shape((N,), np.int32),
            shape((8,), np.int32)).lower(
                lowering_platforms=("tpu",)).compile()
    finally:
        m.shutdown()
    row = sum(dt.itemsize for dt in table._dtypes.values()) + 1
    assert row == 42 and C * row == 44_040_192    # a key, ten fields, validity
    for compiled, scopes in ((probe, ("probe", "gather", "condition")),
                             (scatter, ("scatter",))):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes == 0
        assert mem.argument_size_in_bytes < 2 * C * row
        text = compiled.as_text()
        for sc in scopes:
            assert f"siddhi.devtable.{sc}" in text
        # no operand with a batch and a capacity dimension
        assert f"{B},{C}" not in text and f"{C},{B}" not in text
    # the matched lanes alone come back from a probe
    assert probe.memory_analysis().output_size_in_bytes < 64 * B
    # a whole new table an upsert batch (nothing is donated), and no more
    out = scatter.memory_analysis().output_size_in_bytes
    assert C * row <= out < C * row + 4096


def test_the_snapshot_program_fits_a_v5e_at_a_million_partitions(one_chip):
    """A checkpoint's snapshot of the flagship's state (``fraud16_1m``:
    16 nodes, 4 lanes, one register; 1,000,001 rows of 256 words),
    through the chip's own compiler (kept in this file: one worker
    holds libtpu).  Every field leaves as ONE dimension, so its copy to
    the host is the buffer as it lies (a ``[N, S, I]`` result gets the
    partition axis on its lanes here, and the transfer would transpose
    it); the fields are buffers of their own, none an alias of the rows
    the next step donates; and the program fits beside the state."""
    import jax
    import numpy as np

    from siddhi_tpu.ops.dense_layout import DenseStateLayout

    layout = DenseStateLayout(16, 4, 1, 0, False, False)
    n = 1_000_001
    assert layout.width == 256
    state = {name: jax.ShapeDtypeStruct(shape, np.int32, sharding=one_chip)
             for name, shape in layout.physical_shapes(n).items()}
    compiled = jax.jit(layout.logical).trace(state).lower(
        lowering_platforms=("tpu",)).compile()
    logical = sum(int(np.prod(shape)) * np.dtype(dt).itemsize
                  for shape, dt in (
                      [((n,) + s, dt) for dt, s in layout.fields.values()]
                      + [((n,), np.int32)]))
    assert logical == 836_000_836    # what a revision holds of the state
    mem = compiled.memory_analysis()
    assert logical <= mem.output_size_in_bytes < logical + (1 << 20)
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 1.1 * n * layout.width * 4
    text = compiled.as_text()
    assert "input_output_alias" not in text
    (entry,) = [ln for ln in text.splitlines() if ln.startswith("ENTRY")]
    result = entry.split("->", 1)[1]
    for dt, count in (("pred", 64 * n), ("s32", 64 * n), ("f32", 64 * n),
                      ("s32", n)):
        assert f"{dt}[{count}]" in result, result
    assert f"{n},16" not in result and f"{n},64" not in result
