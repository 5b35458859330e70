"""A routed round as one packed buffer and one sharded put.

``pack_round`` (parallel/mesh.py) against ``route_to_shards``' five
arrays, lane for lane; the errors both raise; and on a four-device CPU
mesh (conftest.py) the served path's one ``put`` span and one
``devicePuts`` a round, behind the ``ingest.put`` fault site.
"""

import numpy as np
import pytest

from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.parallel.mesh import pack_round, route_to_shards

PPS = 64          # partitions a shard


def _round(seed, n_shards, n, owners=None):
    """``n`` events of distinct partitions (a round's contract), over
    ``owners`` only where given; float, integer-pair and ts lanes."""
    rng = np.random.default_rng(seed)
    owners = np.arange(n_shards) if owners is None else np.asarray(owners)
    pool = (owners[:, None] * PPS + np.arange(PPS)[None, :]).ravel()
    part = rng.permutation(pool)[:n].astype(np.int64)
    big = rng.integers(-2**62, 2**62, n)
    cols = {
        # bit patterns a value copy could lose: -0.0, a denormal, a NaN
        # with a payload, infinities
        "v": np.concatenate([
            np.array([-0.0, 1e-45, np.inf, -np.inf], np.float32),
            np.array([0x7FC00123], np.uint32).view(np.float32),
            rng.normal(size=n).astype(np.float32)])[:n],
        "k|hi": (big >> 32).astype(np.int32),
        "k|lo": ((big & 0xFFFFFFFF) - 2**31).astype(np.int32),
    }
    ts = rng.integers(0, 2**31 - 1, n).astype(np.int32)
    return part, cols, ts


CASES = {
    # name: (n_shards, events, owners or None, batch_per_shard or None)
    "one_shard": (1, 40, None, None),
    "two_shards": (2, 70, None, None),
    "four_shards": (4, 150, None, None),
    "eight_shards": (8, 300, None, None),
    "empty_shard": (4, 90, [0, 1, 3], None),
    "all_in_the_last_shard": (4, 33, [3], None),
    "single_event": (4, 1, [2], None),
    "full_bucket": (4, 4 * PPS, None, None),     # B == count in every shard
    "no_events": (4, 0, None, None),
    "batch_per_shard_given": (4, 100, None, 128),
    "batch_per_shard_exact": (2, 2 * PPS, None, PPS),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("col_keys", [("v",), ("k|hi", "k|lo"),
                                      ("v", "k|hi", "k|lo"), ()],
                         ids=["float", "int_pair", "both", "no_cols"])
def test_packed_buffer_equals_the_five_arrays(case, col_keys):
    n_shards, n, owners, bps = CASES[case]
    part, cols, ts = _round(list(CASES).index(case), n_shards, n, owners)
    cols = {k: cols[k] for k in col_keys}
    lp, rc, rts, valid, pos = route_to_shards(
        n_shards, PPS, part, cols, ts, bps)
    buf, ppos = pack_round(n_shards, PPS, part, cols, ts, list(col_keys), bps)
    assert buf.dtype == np.int32 and buf.flags.c_contiguous
    assert buf.shape == (2 + len(col_keys), len(lp))
    assert np.array_equal(ppos, pos) and ppos.dtype == pos.dtype
    assert np.array_equal(buf[0], lp)
    assert np.array_equal(buf[1], rts)
    # valid is what the step derives: not the shard's scratch row
    assert np.array_equal(buf[0] != PPS, valid)
    for row, k in zip(buf[2:], col_keys):
        assert np.array_equal(row, rc[k].view(np.int32)), k   # the bits
    # every event sits in its own slot, inside its owner's slice
    B = len(lp) // n_shards
    assert np.array_equal(pos // B, part // PPS)
    assert len(set(pos.tolist())) == n


@pytest.mark.parametrize("n_shards", [1, 2, 4, 8, 300])
def test_rank_is_arrival_order_within_a_shard(n_shards):
    """The radix rank (uint8 owner; uint16 past 256 shards) is stable:
    a shard's events keep their arrival order, slots ``s*B + 0..c-1``."""
    rng = np.random.default_rng(n_shards)
    part = rng.permutation(n_shards * PPS)[:n_shards * 20].astype(np.int32)
    _, _, _, valid, pos = route_to_shards(
        n_shards, PPS, part, {}, np.zeros(len(part), np.int32))
    B = len(valid) // n_shards
    for s in np.unique(part // PPS):
        mine = np.flatnonzero(part // PPS == s)
        assert pos[mine].tolist() == list(range(s * B, s * B + len(mine)))


@pytest.mark.parametrize("fn", ["route_to_shards", "pack_round"])
@pytest.mark.parametrize("bad,match", [
    ([4 * PPS], "out of range for 4 x 64"),
    ([-1], "out of range for 4 x 64"),
    (list(range(20)), "overflow: 20 events for one shard > batch_per_shard=16"),
], ids=["too_high", "negative", "overflow"])
def test_errors_unchanged(fn, bad, match):
    part = np.asarray(bad)
    ts = np.zeros(len(part), np.int32)
    bps = 16 if "overflow" in match else None
    with pytest.raises(SiddhiAppCreationError, match=match):
        if fn == "pack_round":
            pack_round(4, PPS, part, {}, ts, [], bps)
        else:
            route_to_shards(4, PPS, part, {}, ts, bps)


# -- the served path on a four-device mesh ------------------------------------

BODY = (
    "define stream S (k long, v double); partition with (k of S) begin "
    "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
    "select a.v as av, b.v as bv insert into Out; end;")


def _batch(i, rounds, n=32):
    from siddhi_tpu.core.event import EventBatch

    rng = np.random.default_rng(90 + i)
    # n // rounds keys, each `rounds` times: that many collision rounds
    return EventBatch(
        "S", ["k", "v"],
        {"k": np.arange(n, dtype=np.int64) % (n // rounds),
         "v": rng.uniform(0.0, 20.0, n)},
        np.full(n, 1_000 + i * 10, dtype=np.int64))


def _serve(header, batches, rounds):
    from siddhi_tpu import SiddhiManager

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            f"@app:name('packed') @app:statistics('true') @app:playback "
            f"{header} " + BODY)
        rows = []
        rt.add_callback("Out", lambda evs: rows.extend(
            tuple(e.data) for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        puts = []
        for i in range(batches):
            h.send_batch(_batch(i, rounds))
            puts.append(rt.statistics()[
                "io.siddhi.SiddhiApps.packed.Siddhi.Queries.q.devicePuts"])
        rt.drain_device_emits()
        groups = rt.app_context.tracer.recorder.cycle_groups()
        return rows, puts, list(groups.values()), rt
    finally:
        m.shutdown()


@pytest.mark.parametrize("rounds", [1, 2, 4])
def test_one_put_span_and_one_device_put_a_round(rounds, monkeypatch):
    import jax

    leaves = []
    real_put = jax.device_put

    def counting_put(x, *a, **kw):
        leaves.append([(leaf.shape, leaf.dtype)
                       for leaf in jax.tree_util.tree_leaves(x)])
        return real_put(x, *a, **kw)

    header = ("@app:execution('tpu', partitions='64', devices='4') "
              "@app:trace(sample='1', cycles='8')")
    _serve(header, 1, rounds)            # compiles; nothing counted
    monkeypatch.setattr(jax, "device_put", counting_put)
    rows, puts, cycles, _rt = _serve(header, 4, rounds)
    monkeypatch.undo()
    assert rows
    # devicePuts grows by one a round
    assert np.diff([0] + puts).tolist() == [rounds] * 4
    # ... each one leaf, int32 [part, ts, v] x (4 shards * B): no
    # `valid`, no column the automaton does not read (`k`)
    # (the state's rows are placed once, 4 x (16 + 1) of them: not ours)
    leaves = [put for put in leaves if put[0][0][0] != 4 * 17]
    assert len(leaves) == 4 * rounds
    for put in leaves:
        (shape, dtype), = put
        assert dtype == np.int32 and shape[0] == 3 and shape[1] % 4 == 0
    for spans in cycles:
        by = {}
        for s in spans:
            by.setdefault(s[1], []).append(s)
        assert len(by["route"]) == len(by["put"]) == rounds
        assert len(by["dispatch"]) == rounds
        # a put's count is the bytes of its one buffer
        assert all(s[5] == 3 * 4 * 16 * 4 for s in by["put"])
        # a round's route, put and dispatch follow one another
        order = sorted(by["route"] + by["put"] + by["dispatch"],
                       key=lambda s: s[3])
        assert [s[1] for s in order] == ["route", "put", "dispatch"] * rounds


def test_sharded_rows_equal_the_one_device_rows():
    one, _, _, _ = _serve("@app:execution('tpu', partitions='64')", 6, 2)
    four, _, _, _ = _serve(
        "@app:execution('tpu', partitions='64', devices='4')", 6, 2)
    assert one and sorted(four) == sorted(one)


@pytest.mark.parametrize("devices", ["", ", devices='4'"],
                         ids=["dense", "sharded"])
def test_transient_ingest_put_fault_is_retried(devices):
    header = f"@app:execution('tpu', partitions='64'{devices})"
    clean, _, _, _ = _serve(header, 4, 2)
    chaotic, _, _, rt = _serve(
        "@app:faults(transfer.retry.scale='0.0001', "
        "ingest.put='transient:count=2') " + header, 4, 2)
    assert clean and chaotic == clean
    fi = rt.app_context.fault_injector
    assert (fi.stats.faults_injected, fi.stats.transfer_retries) == (2, 2)
    assert fi.stats.drains_recovered == 1


@pytest.mark.parametrize("devices", ["", ", devices='4'"],
                         ids=["dense", "sharded"])
def test_exhausted_ingest_put_fault_propagates(devices):
    """More consecutive faults than the ladder retries: the put raises
    out of the engine, and the runtime isolates the batch as it does on
    the dense engine (reported, counted, the later batches served)."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.exceptions import TransferFaultError

    header = (f"@app:faults(transfer.retry.attempts='1', "
              "transfer.retry.scale='0.0001', "
              "ingest.put='transient:count=2') "
              f"@app:execution('tpu', partitions='64'{devices})")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('packed') @app:playback " + header + " " + BODY)
        seen = []
        rt.add_exception_listener(seen.append)
        rows = []
        rt.add_callback("Out", rows.extend)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(3):
            h.send_batch(_batch(i, 2))
        rt.drain_device_emits()
        fi = rt.app_context.fault_injector
        assert (fi.stats.faults_injected, fi.stats.transfer_retries) == (2, 1)
        assert any(isinstance(e, TransferFaultError) for e in seen)
        assert rows        # the batches after the lost one were served
    finally:
        m.shutdown()
