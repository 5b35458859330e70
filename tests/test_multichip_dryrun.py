"""The driver's multi-chip dryrun, exercised in CI.

This is exactly what the driver runs with N virtual CPU devices — it
failed unnoticed in rounds 1 and 2 because nothing in `pytest tests/`
covered it.  The conftest already forces an 8-device CPU mesh, so the
entry point must work in-process here.
"""

import numpy as np


def test_dryrun_multichip_8():
    from __graft_entry__ import dryrun_multichip

    dryrun_multichip(8)


def test_entry_compiles_and_steps():
    import jax

    from __graft_entry__ import entry

    fn, args = entry()
    out_state, emit, out_vals, emit_anchor, n_emit = jax.jit(fn)(*args)
    assert set(out_state) == {"rows", "overflow"}
    assert np.asarray(emit).dtype == bool
    # async emit pipeline: the step returns a scalar match count so the
    # host can skip all column transfers on zero-match batches
    assert np.asarray(n_emit).shape == ()
    assert np.asarray(n_emit).dtype == np.int32


def test_sharded_engine_init_is_host_only(monkeypatch):
    """init_state of the sharded wrapper must not allocate via the
    engine's device init (the round-2 crash path)."""
    from siddhi_tpu.ops.dense_nfa import compile_pattern
    from siddhi_tpu.parallel import ShardedPatternEngine, make_mesh

    from __graft_entry__ import FRAUD_APP

    eng = compile_pattern(FRAUD_APP, "fraud", n_partitions=64 * 8)

    def boom():
        raise AssertionError("device init_state called during sharded init")

    monkeypatch.setattr(eng, "init_state", boom)
    mesh = make_mesh(8)
    sharded = ShardedPatternEngine(eng, mesh)
    state = sharded.init_state()
    assert state["rows"].shape[0] == 8 * (64 + 1)
