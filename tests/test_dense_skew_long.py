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
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
