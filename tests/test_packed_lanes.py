"""The shared form of the packed put (``ops/packed_lanes.py``): a lane
table, one ``int32 [k, B]`` host buffer, static slices in the program.
What crosses is each lane's bit pattern: the round trip is held bit for
bit, for the float32 values a cast would change and for 64-bit integers
through their engine's hi/lo words."""

import jax
import numpy as np
import pytest

from siddhi_tpu.ops.dense_nfa import _i64_join
from siddhi_tpu.ops.packed_lanes import LaneTable

#: float32 bit patterns a value-preserving copy is free to change: quiet
#: and signalling NaNs with payloads, both signs; the zeros; the
#: infinities; the smallest and the largest denormal; the normal range's
#: ends
F32_BITS = np.array([
    0x7FC00000, 0x7FC00001, 0xFFC12345, 0x7F800001, 0xFFBFFFFF, 0x7FFFFFFF,
    0x00000000, 0x80000000, 0x7F800000, 0xFF800000,
    0x00000001, 0x80000001, 0x007FFFFF, 0x807FFFFF,
    0x00800000, 0x7F7FFFFF, 0xFF7FFFFF, 0x3F800000], dtype=np.uint32)
I64 = np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0, 1,
                -2**32, 2**32 - 1, 2**31, -2**31 - 1, 0x0123456789ABCDEF],
               dtype=np.int64)


def split_i64(v):
    """``DensePatternEngine.prepare_cols``' words of a 64-bit integer."""
    return ((v >> 32).astype(np.int32),
            ((v & 0xFFFFFFFF) - 2**31).astype(np.int32))


def round_trip(table, lanes, take, width, pad=None):
    buf = table.pack(lanes, take, width, pad)
    assert buf.dtype == np.int32 and buf.shape == (len(table), width)
    assert buf.flags.c_contiguous
    return buf, jax.device_get(jax.jit(table.unpack)(jax.device_put(buf)))


@pytest.mark.parametrize("width", [16, 131_072])
def test_round_trip_is_bit_for_bit(width):
    rng = np.random.default_rng(width)
    n = width - 3
    f = np.resize(F32_BITS, n).view(np.float32)
    w = np.resize(I64, n)
    rng.shuffle(w)
    hi, lo = split_i64(w)
    part = rng.integers(0, 1000, n).astype(np.int32)
    take = rng.permutation(n)
    table = LaneTable([("part", np.int32), ("f", np.float32),
                       ("absent", np.float32), ("w|hi", np.int32),
                       ("w|lo", np.int32), ("gone", np.int32)])
    buf, rows = round_trip(
        table, {"part": part, "f": f, "w|hi": hi, "w|lo": lo, "gone": None},
        take, width, pad={"part": 1000, "gone": 7})
    assert sorted(rows) == sorted(table.names)
    assert [rows[k].dtype for k in table.names] == list(table.dtypes)
    # the bit pattern, not the value: NaN payloads, -0.0, denormals
    assert np.array_equal(rows["f"][:n].view(np.uint32),
                          f[take].view(np.uint32))
    assert np.array_equal(buf[1, :n].view(np.uint32), f[take].view(np.uint32))
    assert np.array_equal(_i64_join(rows["w|hi"][:n], rows["w|lo"][:n]),
                          w[take])
    assert np.array_equal(rows["part"][:n], part[take])
    # past the events: the lane's padding, zeros by default
    assert (rows["part"][n:] == 1000).all()
    for name in ("f", "w|hi", "w|lo"):
        assert not rows[name][n:].view(np.uint32).any()
    # a lane the batch does not bring: its padding in every entry
    assert not rows["absent"].view(np.uint32).any()
    assert (rows["gone"] == 7).all()


def test_a_full_width_and_a_single_event():
    table = LaneTable([("a", np.int32), ("b", np.float32)])
    a = np.arange(16, dtype=np.int32)[::-1].copy()
    b = np.linspace(-1.0, 1.0, 16).astype(np.float32)
    _, rows = round_trip(table, {"a": a, "b": b}, np.arange(16), 16,
                         pad={"a": -1})
    assert np.array_equal(rows["a"], a) and np.array_equal(rows["b"], b)
    _, rows = round_trip(table, {"a": a, "b": b}, np.array([5]), 16,
                         pad={"a": -1})
    assert rows["a"].tolist() == [a[5]] + [-1] * 15
    assert rows["b"].tolist() == [b[5]] + [0.0] * 15


@pytest.mark.parametrize("case", ["float64_lane", "int64_lane", "bool_lane",
                                  "named_twice"])
def test_a_table_refuses_what_is_no_32_bit_word(case):
    rows = {"float64_lane": [("v", np.float64)],
            "int64_lane": [("v", np.int64)],
            "bool_lane": [("v", np.bool_)],
            "named_twice": [("v", np.int32), ("v", np.float32)]}[case]
    with pytest.raises(ValueError):
        LaneTable(rows)


@pytest.mark.parametrize("case", ["cast_needed", "too_wide", "wrong_rows"])
def test_pack_and_unpack_refuse_what_would_change_a_value(case):
    table = LaneTable([("v", np.float32)])
    if case == "cast_needed":
        # a float64 column would be rounded, an int32 one reinterpreted
        with pytest.raises(ValueError, match="float64"):
            table.pack({"v": np.ones(4)}, np.arange(4), 16)
    elif case == "too_wide":
        with pytest.raises(ValueError, match="17"):
            table.pack({"v": np.ones(17, np.float32)}, np.arange(17), 16)
    else:
        with pytest.raises(ValueError, match="2 rows"):
            table.unpack(np.zeros((2, 16), np.int32))
