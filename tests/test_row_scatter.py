"""The row-scatter kernel (``kernels/row_scatter.py``), interpreted, held
bit for bit to the ``rows.at[part_idx].set(new)`` it stands in for:
alone over the row shapes the resident state takes (one vector of
lanes, the flagship's two and ``iot32_1250k``'s four), with no padded
lane, some, and all of them; and inside the engines' programs (the
step, ``make_rounds``' wide loops and the run's write-back) on the
flagship's and the card app's patterns.  Off a TPU the engine keeps
XLA's scatter: the tests here say otherwise, as ``tests/
test_dense_skew.py`` does for the run kernel.  (A state sharded over a
mesh keeps its rows flat and XLA's scatter: ``tests/
test_dense_sharded_layout.py``; that the kernel goes through Mosaic at
the flagship's size is held in ``tests/test_dense_skew_long.py``, with
the chip's compiler.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_dense_one_transfer import APPS, P, batches, pattern_of

from siddhi_tpu.kernels import row_scatter
from siddhi_tpu.ops.dense_nfa import compile_pattern, round_plan

N = 5_001           # rows, the last one the scratch row


@pytest.mark.parametrize("padded", ["none", "some", "all"])
@pytest.mark.parametrize("B", [128, 4096])
@pytest.mark.parametrize("W", [128, 256, 512])
def test_the_kernel_is_at_set(W, B, padded):
    """Distinct real rows in any order; a lane on the scratch row
    writes nothing; the state donated and every other row untouched."""
    rng = np.random.default_rng([W, B, len(padded)])
    row_shape = (W,) if W == 128 else (W // 128, 128)
    rows = rng.integers(-2**31, 2**31, (N,) + row_shape).astype(np.int32)
    new = rng.integers(-2**31, 2**31, (B,) + row_shape).astype(np.int32)
    idx = rng.permutation(N - 1)[:B].astype(np.int32)
    pad = {"none": np.zeros(B, bool), "some": rng.random(B) < 0.38,
           "all": np.ones(B, bool)}[padded]
    idx[pad] = N - 1
    want = rows.copy()
    want[idx[~pad]] = new[~pad]
    state = jnp.asarray(rows)
    got = jax.jit(row_scatter.row_scatter, donate_argnums=(0,))(
        state, jnp.asarray(idx), jnp.asarray(new))
    assert state.is_deleted()
    assert np.array_equal(np.asarray(got), want)


def test_what_takes_the_kernel(monkeypatch):
    """A TPU, rows of more than one vector of lanes (the shape the
    layout gives a one-chip state), a lane count the loop's stride
    divides: read from the traced operand alone."""
    monkeypatch.setattr(row_scatter, "INTERPRET_OFF_TPU", True)
    wide = jax.ShapeDtypeStruct((N, 2, 128), np.int32)
    four = jax.ShapeDtypeStruct((N, 4, 128), np.int32)   # 32 nodes' row
    narrow = jax.ShapeDtypeStruct((N, 128), np.int32)
    flat = jax.ShapeDtypeStruct((N, 256), np.int32)      # a sharded state
    assert row_scatter.eligible(wide, 131_072)
    assert row_scatter.eligible(wide, 128)       # the run's write-back
    assert row_scatter.eligible(four, 131_072)
    assert row_scatter.eligible(four, 4_096)     # a second round
    assert not row_scatter.eligible(wide, 131_072 + 1)
    assert not row_scatter.eligible(narrow, 131_072)
    assert not row_scatter.eligible(flat, 131_072)
    monkeypatch.setattr(row_scatter, "INTERPRET_OFF_TPU", False)
    assert not row_scatter.eligible(wide, 131_072)   # the CPU: .at[].set


@pytest.mark.parametrize("seed", range(4))
def test_a_round_never_holds_a_real_row_twice(seed):
    """The kernel's precondition, on random batches of every skew."""
    rng = np.random.default_rng(seed)
    n_keys = int(rng.integers(1, 400))
    part = rng.zipf(1.0 + rng.random(), size=3_000) % n_keys
    plan = round_plan(part.astype(np.int32))
    for r in range(plan.n_rounds):
        keys = part[plan.round(r)]
        assert len(np.unique(keys)) == len(keys)


def _drive(config, cols_of, shape, with_kernel, monkeypatch):
    """The engine of a benchmark app over ``shape``'s batches: the state
    after each, every program's emit arrays, and how many write-backs
    were traced through the kernel."""
    calls = []
    real = row_scatter.row_scatter

    def counted(rows, part_idx, new):
        calls.append(part_idx.shape[0])
        return real(rows, part_idx, new)

    with monkeypatch.context() as m:
        m.setattr(row_scatter, "INTERPRET_OFF_TPU", with_kernel)
        m.setattr(row_scatter, "row_scatter", counted)
        eng = compile_pattern(pattern_of(config), "bench", n_partitions=P,
                              n_instances=4)
        state = eng.init_state()
        seen = []
        for part, cols, ts in batches(shape, cols_of, seed=58):
            state, pending = eng.process_deferred(
                state, eng.default_stream, part, cols, ts)
            seen.append([{k: np.asarray(chunk[k]) for k in
                          ("emit", "f", "i", "anchor", "count")}
                         for chunk in pending.chunks])
            seen.append(eng.layout.unpack(state))
    return seen, calls


@pytest.mark.parametrize("shape", ["two_rounds", "five_rounds"])
@pytest.mark.parametrize("app", ["fraud", "card"])
def test_the_engines_programs_with_the_kernel(app, shape, monkeypatch):
    """State, emits, payloads, anchors, counts and ``overflow`` of the
    step (two rounds: the step twice) and of ``make_rounds``' program
    (five: its wide loops and its run) with the kernel in them, equal
    to the same programs with XLA's scatter."""
    config, cols_of = APPS[app]
    want, none = _drive(config, cols_of, shape, False, monkeypatch)
    got, calls = _drive(config, cols_of, shape, True, monkeypatch)
    assert not none
    # the flagship's 256-word rows: the step at 1,024 lanes and, the
    # second round, at 512; the rounds program's loops at 2,048 and 256
    # and its run at 128.  The card app's 128-word rows keep XLA's.
    assert set(calls) == (set() if app == "card" else
                          {1024, 512} if shape == "two_rounds"
                          else {1024, 2048, 256, 128})
    emitted = 0
    for w, g in zip(want, got):
        if isinstance(w, dict):             # a state, overflow with it
            assert w.keys() == g.keys()
            for k in w:
                assert np.array_equal(w[k], g[k]), k
            continue
        for cw, cg in zip(w, g):
            emitted += int(cw["count"])
            for k in cw:
                assert np.array_equal(cw[k], cg[k]), k
    assert emitted > 0

