"""The dense pattern state's resident layout (ops/dense_layout.py).

(i)   Step, timer step, purge and re-anchor under the layout give state
      (read through the layout's accessor) and emissions bit-identical to
      arrays recorded from the commit before the layout changed
      (tests/dense_layout_cases.py says how they were recorded).
(ii)  The snapshot format is the logical one: a snapshot in the parent's
      format, built by hand here, restores and continues with the same
      matches, and a snapshot taken now has the parent's keys, shapes,
      dtypes and values.
(iii) The optimised HLO of the jitted step holds no instruction with a
      whole-state result but the parameters, the in-place scatters and
      the root tuple.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np
import pytest

import dense_layout_cases as cases

FIXTURE = Path(__file__).parent / "fixtures" / "dense_layout_parent.npz"


@pytest.fixture(scope="module")
def parent():
    with np.load(FIXTURE) as z:
        return {k: z[k] for k in z.files}


def _same(got: np.ndarray, want: np.ndarray, what: str):
    assert got.shape == want.shape, f"{what}: {got.shape} != {want.shape}"
    assert got.dtype == want.dtype, f"{what}: {got.dtype} != {want.dtype}"
    # bit-identical, NaN payloads included
    assert got.tobytes() == want.tobytes(), what


# -- (i) ---------------------------------------------------------------------

@pytest.mark.parametrize("name", cases.scenario_names())
def test_scenario_is_bit_identical_to_the_parent(name, parent):
    rec = cases.drive(name)
    want = {k[len(name) + 1:]: v for k, v in parent.items()
            if k.startswith(name + "/")}
    assert sorted(rec) == sorted(want)
    assert any(len(v) for k, v in want.items()
               if k.endswith(("/ev", "/fired_out")))  # something matched
    for k in sorted(want):
        _same(np.asarray(rec[k]), want[k], f"{name}/{k}")


def test_scenarios_cover_what_they_claim(parent):
    """The recorded traffic really has overflowing lanes, timer
    emissions, a re-anchor and rows with several collision rounds."""
    def total(suffix, pred=lambda k: True):
        return sum(int(np.sum(v)) for k, v in parent.items()
                   if k.endswith(suffix) and pred(k))

    assert total("state/overflow") > 0
    assert total("/fired", lambda k: k.startswith("absent_last")) > 0
    assert all(int(v) > cases.BASE_TS + 2 ** 30 for k, v in parent.items()
               if k.endswith("/base_ts") and not k.startswith("app"))
    assert total("state/iregs") != 0 and total("state/deadline") > 0


# -- (ii) --------------------------------------------------------------------

def _parent_snapshot(parent, app):
    """A snapshot in the parent's format, built by hand from the
    recorded arrays: ``dense_state`` holds one ``[rows, S, I]`` /
    ``[rows, S, I, R]`` array per field under the field's name."""
    pre = f"{app}/dense_state/"
    return {
        "dense_state": {k[len(pre):]: v for k, v in parent.items()
                        if k.startswith(pre)},
        "base_ts": int(parent[f"{app}/base_ts"]),
        "key_rows": {str(k): int(r) for k, r in zip(
            parent[f"{app}/keys"], parent[f"{app}/key_rows"])},
        "next_row": int(parent[f"{app}/next_row"]),
        "free_rows": [int(r) for r in parent[f"{app}/free_rows"]],
        "row_last_used": parent[f"{app}/row_last_used"],
    }


def _matches(parent, app):
    return [([float(x) for x in row[:-1]], int(row[-1]))
            for row in parent[f"{app}/matches"]]


@pytest.mark.parametrize("n_dev", cases.MESHES)
def test_parent_format_snapshot_restores_and_continues(n_dev, parent):
    app = f"app-d{n_dev}"
    snap = _parent_snapshot(parent, app)
    assert set(snap["dense_state"]) == {
        "active", "first_ts", "counts", "regs", "overflow"}
    assert snap["dense_state"]["active"].any()  # chains are pending
    got, _ = cases.drive_app(n_dev, restore_from=snap)
    assert got == _matches(parent, app)
    assert len(got) == 8


@pytest.mark.parametrize("n_dev", cases.MESHES)
def test_snapshot_has_the_parent_format(n_dev, parent):
    app = f"app-d{n_dev}"
    got, snap = cases.drive_app(n_dev)
    assert got == _matches(parent, app)
    want = _parent_snapshot(parent, app)
    assert sorted(snap["dense_state"]) == sorted(want["dense_state"])
    for k, v in want["dense_state"].items():
        _same(np.asarray(snap["dense_state"][k]), v, f"{app}/{k}")
    for k in ("base_ts", "next_row"):
        assert snap[k] == want[k], k
    # the index's two vectors since PR 49 (the parent's dict restores
    # too: the test above), free rows an array
    keys, rows = snap["key_rows"]
    assert dict(zip(keys.tolist(), rows.tolist())) == want["key_rows"]
    # the same rows; a purge frees them in row order since PR 49 (the
    # parent freed them in its dict's order), so that a runtime restored
    # from the vectors recycles as the one that was never interrupted
    assert snap["free_rows"].tolist() == sorted(want["free_rows"])
    # and it restores under the change as it does under the parent
    again, snap2 = cases.drive_app(n_dev, restore_from=snap)
    assert again == got
    for k, v in snap["dense_state"].items():
        _same(np.asarray(snap2["dense_state"][k]), np.asarray(v), k)


def test_restore_refuses_a_snapshot_of_another_shape(parent):
    from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError

    snap = _parent_snapshot(parent, "app-d1")
    snap["dense_state"] = dict(snap["dense_state"])
    snap["dense_state"]["regs"] = snap["dense_state"]["regs"][..., :1]
    with pytest.raises(SiddhiAppRuntimeError, match="regs"):
        cases.drive_app(1, restore_from=snap)


@pytest.mark.parametrize("eng_name", list(cases.ENGINES))
def test_pack_unpack_round_trip(eng_name):
    """Random logical state -> rows -> logical state, bit for bit; the
    row is one lane-aligned int32 vector per partition."""
    from siddhi_tpu.ops.dense_nfa import compile_pattern

    query, inst, _streams = cases.ENGINES[eng_name]
    eng = compile_pattern(cases.STREAMS + query, "q", n_partitions=7,
                          n_instances=inst)
    lay = eng.layout
    rng = np.random.default_rng(3)
    logical = {}
    for k, shape in lay.logical_shapes(8).items():
        dt = lay.fields[k][0] if k in lay.fields else np.dtype(np.int32)
        raw = rng.integers(-2 ** 31, 2 ** 31, size=shape, dtype=np.int64)
        logical[k] = ((raw & 1).astype(bool) if dt == np.bool_ else
                      raw.astype(np.int32).view(dt) if dt == np.float32
                      else raw.astype(np.int32))
    phys = lay.pack(logical)
    assert set(phys) == {"rows", "overflow"}
    assert phys["rows"].dtype == np.int32
    assert phys["rows"].shape == (8,) + lay.row_shape and lay.width % 128 == 0
    assert lay.width - lay.used < 128
    back = lay.unpack(phys)
    for k, v in logical.items():
        _same(back[k], v, k)
    # the engine's own init is the packed logical init
    init = lay.unpack(eng.init_state_host())
    for k, v in lay.init_logical(8).items():
        _same(init[k], v, k)
    assert bool(init["active"][:, 0, 0].all()) == (not eng.every_start)
    # single fields of a few rows, through the accessor
    state = {k: eng.jnp.asarray(v) for k, v in phys.items()}
    rows = np.asarray([5, 2])
    _same(lay.field(state, "first_ts", rows), logical["first_ts"][rows], "f")
    state = lay.with_field(state, "regs", 3, logical["regs"][6])
    _same(lay.field(state, "regs", 3), logical["regs"][6], "regs")
    _same(lay.field(state, "regs", 4), logical["regs"][4], "regs kept")


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_chip", "sharded"])
@pytest.mark.parametrize("dims", [(16, 4, 1, 0, False, False),
                                  (5, 4, 3, 1, True, False),
                                  (7, 4, 4, 2, True, True),
                                  (32, 4, 1, 0, False, False)])
def test_a_row_wider_than_one_vector(dims, sharded):
    """A row of more than 128 words is resident as ``[W // 128, 128]``
    on one chip (what one DMA can name, ``kernels/row_scatter.py``) and
    flat, ``[W]``, where the state is sharded over a mesh (XLA's
    scatter writes it): the flagship's 256 words with every field inside
    a vector, two layouts whose fields cross from one vector into the
    next, and the 512 words of a chain at the engine's limit of 32
    nodes (``iot32_1250k``), a field a vector and not one word of
    padding.  In either shape pack and unpack, the accessors of single
    fields, ``split`` / ``join`` inside a jitted program and ``logical``
    give what the words of a flat row give."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu.ops.dense_layout import OVERFLOW, ROWS, DenseStateLayout

    lay = DenseStateLayout(*dims, sharded=sharded)
    assert lay.width > 128 and lay.row_shape == (
        (lay.width,) if sharded else (lay.width // 128, 128))
    assert lay.pspecs("p")[ROWS] == jax.sharding.PartitionSpec(
        "p", *(None,) * len(lay.row_shape))
    crossing = [k for k, (off, w) in lay.offsets.items()
                if off // 128 != (off + w - 1) // 128]
    assert bool(crossing) == (dims[0] not in (16, 32))
    if dims[0] == 32:
        assert lay.used == lay.width == 512
        assert lay.row_shape == ((512,) if sharded else (4, 128))
    rng = np.random.default_rng(dims[0])
    logical = {}
    for k, shape in lay.logical_shapes(9).items():
        dt = lay.fields[k][0] if k in lay.fields else np.dtype(np.int32)
        raw = rng.integers(-2 ** 31, 2 ** 31, size=shape, dtype=np.int64)
        logical[k] = ((raw & 1).astype(bool) if dt == np.bool_ else
                      raw.astype(np.int32).view(dt) if dt == np.float32
                      else raw.astype(np.int32))
    phys = lay.pack(logical)
    assert phys[ROWS].shape == (9,) + lay.row_shape
    assert lay.physical_shapes(9)[ROWS] == phys[ROWS].shape
    assert lay.init_physical(9)[ROWS].shape == phys[ROWS].shape
    for k, v in lay.init_device(9).items():      # the same, made there
        assert np.array_equal(np.asarray(v), lay.init_physical(9)[k]), k
    flat = phys[ROWS].reshape(9, lay.width)
    for k, (off, w) in lay.offsets.items():
        _same(lay.decode(k, flat[:, off:off + w]), logical[k], k)
    for k, v in lay.unpack(phys).items():
        _same(v, logical[k], k)
    state = {k: jnp.asarray(v) for k, v in phys.items()}
    for k in lay.fields:
        _same(lay.field(state, k), logical[k], k)                # every row
        _same(lay.field(state, k, np.asarray([5, 2])), logical[k][[5, 2]], k)
        _same(lay.field(state, k, 7), logical[k][7], k)          # one row
        moved = lay.with_field(state, k, 3, logical[k][6])
        _same(lay.field(moved, k, 3), logical[k][6], k)
        want = flat.copy()
        off, w = lay.offsets[k]
        want[3, off:off + w] = flat[6, off:off + w]
        assert np.array_equal(
            np.asarray(moved[ROWS]).reshape(9, lay.width), want), k
    # inside a program: the fields of the rows, and the rows of the fields
    again = jax.jit(lambda r: lay.join(lay.split(r)))(state[ROWS])
    assert again.shape == phys[ROWS].shape
    assert np.array_equal(np.asarray(again), phys[ROWS])
    snap = jax.jit(lay.logical)(state)
    for k, field in lay.snapshot_fields(snap).items():
        _same(np.asarray(field), logical[k], k)
    _same(np.asarray(snap[OVERFLOW]), logical[OVERFLOW], OVERFLOW)


# -- (iii) -------------------------------------------------------------------

def _computations(hlo: str):
    """name -> [(instruction, result type, opcode, line)]."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%?[\w.\-]+) .*\{\s*$", line)
        if head and " = " not in line:
            cur = comps.setdefault(head.group(1).lstrip("%"), [])
            continue
        m = re.match(
            r"\s*(?:ROOT )?(%?[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", line)
        if m and cur is not None:
            cur.append((m.group(1), m.group(2), m.group(3), line))
    return comps


@pytest.mark.parametrize("eng_name", list(cases.ENGINES))
def test_step_has_no_whole_state_instruction(eng_name):
    """On the backend at hand.  Guards against a whole-state `where`,
    reshape or copy creeping into the step; the chip's own guard is
    `*.device_busy_ms_per_batch` in the ledger."""
    import jax

    from siddhi_tpu.ops.dense_nfa import compile_pattern

    P, B = 1236, 64
    query, inst, streams = cases.ENGINES[eng_name]
    eng = compile_pattern(cases.STREAMS + query, "q", n_partitions=P,
                          n_instances=inst)
    whole = re.compile(rf"\[{P + 1}[,\]]")
    rows = re.compile(rf"\[{P + 1},")
    for sk in streams:
        host = eng.init_state_host()
        state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in host.items()}
        # the jitted step takes its lane table's one packed buffer
        buf = jax.ShapeDtypeStruct((len(eng.lane_table(sk)), B), np.int32)
        hlo = eng.make_step(sk).lower(state, buf).compile().as_text()
        comps = _computations(hlo)
        assert comps and any(whole.search(t) for c in comps.values()
                             for _n, t, _o, _l in c)
        for cname, instrs in comps.items():
            for name, rtype, op, line in instrs:
                if not whole.search(rtype):
                    continue
                if op in ("parameter", "scatter", "dynamic-update-slice"):
                    continue
                if op == "tuple" and "ROOT" in line:
                    continue
                # the overflow vector, and nothing that carries the rows,
                # on its way through the conditional that adds to it
                # (``DenseStateLayout.scatter``): handed on by name, its
                # branches are computations checked here
                if (op in ("tuple", "get-tuple-element", "conditional")
                        and not rows.search(rtype)):
                    continue
                if op == "fusion":
                    # an in-place scatter wrapped in a fusion: every
                    # whole-state instruction inside is a parameter or
                    # the scatter itself
                    called = re.search(r"calls=(%?[\w.\-]+)", line)
                    inner = comps[called.group(1).lstrip("%")]
                    ops = {o for _n, t, o, _l in inner if whole.search(t)}
                    if ops <= {"parameter", "scatter",
                               "dynamic-update-slice"} and ops - {
                                   "parameter"}:
                        continue
                pytest.fail(
                    f"{eng_name}/{sk}: {cname}: whole-state result in "
                    f"{name} = {rtype} {op}")
