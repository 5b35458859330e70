"""``iot32_1250k``'s app on the dense engine at 4,096 partitions, on
the cell's own generator at 1,024 events a batch, against the host
engine and the benchmark's plain reference.

What no other test reaches: a chain of 32 nodes, the engine's limit
(``ops/dense_nfa.py``: ``S > 32`` is refused), whose row of 512 words
has not one word of padding and is resident as four vectors of lanes,
``[N, 4, 128]``, where the row-scatter kernel writes it, and flat where
the state is sharded; a head with a band; a second collision round of
328 lanes in every batch through the step again, with the hot devices'
second readings at the places three seeds give them; all four scripts
of the traffic and the swept devices' short arms over two passes a
``within`` apart; ``steppedStateBytes`` and the cycle's ``state_bytes``
count against counts worked out by hand; an app of 33 states refused
with the engine's message and counted as a fallback.
"""

import numpy as np
import pytest

from iot32_bench import (CONFIG, GEN, REF, SPEC, TIER1, head_of, make_batch,
                         run_app)
from siddhi_tpu import SiddhiManager

DENSE = ("@app:statistics('true') "
         + CONFIG["header"].format(**CONFIG["rehearsal"]))
PASSES = 2          # the warm-up pass and one more


def inspect(rt):
    """``pattern_state()`` of the one query, its engine's shape and
    which programs it built."""
    (pr,) = rt.partitions.values()
    dense = pr.dense_query_runtimes["bench"].pattern_processor
    engine = dense.engine
    return {**rt.pattern_state()["bench"],
            "S": engine.S, "I": engine.I,
            "used": engine.layout.used, "width": engine.layout.width,
            "row_shape": engine.layout.row_shape,
            "state_row_shape": dense.stats()["state_row_shape"],
            "rows": engine.layout.physical_shapes(
                engine.n_partitions + 1)["rows"],
            "programs": {k[:2] for k in engine._step_cache
                         if k[1] in (False, "rounds")}}


def stat(stats, name):
    (key,) = [k for k in stats if k.endswith("Queries.bench." + name)]
    return stats[key]


def reference_rows(schedule, n):
    """What the plain chain owes over the run's first ``n`` batches,
    every device, as the engine's ``(ts, t1, t32)``."""
    by_device = {}
    for i in range(-schedule.warmup, n - schedule.warmup):
        b = schedule.batch(i)
        for d, temp, ts in zip(b.columns["device"].tolist(),
                               b.columns["temp"].tolist(),
                               b.timestamps.tolist()):
            by_device.setdefault(d, []).append((i, ts, temp))
    return sorted(
        (ts, np.float32(t1), np.float32(t32))
        for events in by_device.values()
        for _n, ts, t1, t32 in REF._band_rows(
            events, SPEC["states"], SPEC["within_ms"], SPEC["head_band"]))


@pytest.mark.parametrize("seed", [2**31 + 7, 11, 60])
def test_dense_rows_equal_the_host_engines_and_the_references(seed):
    schedule = GEN.make(seed, CONFIG, TIER1, True)
    n = PASSES * schedule.per_pass
    batches = [schedule.batch(i) for i in range(-schedule.warmup,
                                                n - schedule.warmup)]
    # the second round's lanes: the later of a hot device's two places
    # in a batch, wherever the seed put them
    devices = batches[0].columns["device"]
    second = [int(np.flatnonzero(devices == k)[1])
              for k in schedule.active_keys[:8]]
    assert len(set(second)) == 8
    host, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    got, errors, lowering, state, stats = run_app(DENSE, batches, inspect)
    assert lowering == CONFIG["expect"]["lowering"] and not errors
    assert sorted(got) == sorted(host) == reference_rows(schedule, n)
    # 82 devices a script, three scripts of four owe a row a pass
    assert len(got) == PASSES * 3 * 82
    assert {t32 for _ts, _t1, t32 in got} == {31.5}
    # the engine at its limit, and a row with no word to spare
    assert (state["S"], state["I"]) == (32, 4)
    assert state["used"] == state["width"] == 512
    assert state["row_shape"] == state["state_row_shape"] == (4, 128)
    assert state["rows"] == (4097, 4, 128)
    assert state["instance_lanes"] == 4
    assert state["partitions_in_use"] == 4096
    assert state["dropped_instances"] == 0
    assert stat(stats, "droppedInstances") == 0
    # two rounds a batch: the step twice, no rounds program
    assert state["programs"] == {("Reading", False)}
    # 696 first occurrences padded to 1,024 lanes, 328 second ones to 512
    lanes = n * (1024 + 512)
    assert stat(stats, "steppedLanes") == lanes
    assert stat(stats, "steppedStateBytes") == lanes * 2048
    assert stat(stats, "plannedRepeats") == n * 328
    assert stat(stats, "putLeaves") == stat(stats, "devicePuts") == 2 * n


def test_a_state_sharded_over_a_mesh_keeps_the_row_flat():
    import jax

    from siddhi_tpu.ops.dense_nfa import compile_pattern
    from siddhi_tpu.parallel.mesh import make_mesh
    from test_dense_one_transfer import pattern_of

    pattern = pattern_of("iot32_1250k")
    one = compile_pattern(pattern, "bench", n_partitions=64)
    assert [n.kind for n in one.nodes] == ["stream"] * 32
    assert one.within_ms == SPEC["within_ms"]
    assert one.layout.row_shape == (4, 128)
    assert {name: off for name, (off, _w) in one.layout.offsets.items()} == {
        "active": 0, "first_ts": 128, "counts": 256, "regs": 384}
    mesh = make_mesh(4, devices=jax.devices("cpu")[:4])
    meshed = compile_pattern(pattern, "bench", n_partitions=64, mesh=mesh)
    assert meshed.layout.row_shape == (512,)
    assert meshed.layout.used == meshed.layout.width == 512


def test_the_cycles_state_bytes_are_the_lanes_times_the_rows_bytes():
    """Once a batch, of no width, inside ``ingest``: the lanes the
    batch's programs step times 2,048 bytes, padding and all."""
    sent = [(5, 0), (40, 3), (0, 0), (300, 17)]   # devices, of them twice
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            DENSE + " @app:trace(sample='1', cycles='64') " + CONFIG["app"])
        rt.start()
        send = rt.get_input_handler("Reading").send_batch
        for i, (n, twice) in enumerate(sent):
            devices = list(range(n)) + list(range(twice))
            send(make_batch(devices, [head_of(d) for d in devices],
                            1_000 + i))
        rt.drain_device_emits()
        spans = rt.app_context.tracer.recorder.spans()
        lanes = [s[5] for s in spans if s[1] == "lanes"]
        counts = [s for s in spans if s[1] == "state_bytes"]
        # a batch's rounds are padded to powers of two, 16 at least
        assert lanes == [16, 64 + 16, 512 + 32]
        assert [s[5] for s in counts] == [2048 * n for n in lanes]
        assert all(s[4] == s[3] for s in counts)
        ingest = {s[0]: s for s in spans if s[1] == "ingest"}
        for s in counts:
            assert ingest[s[0]][3] <= s[3] <= ingest[s[0]][4]
        assert stat(rt.statistics(), "steppedStateBytes") == 2048 * sum(lanes)
        rt.shutdown()
    finally:
        m.shutdown()


def test_a_chain_of_33_states_is_refused_and_counted_as_a_fallback():
    from siddhi_tpu.core.exceptions import SiddhiAppCreationError
    from siddhi_tpu.ops.dense_nfa import compile_pattern
    from test_dense_one_transfer import pattern_of

    longer = CONFIG["app"].replace(
        " within 10 min", " -> e33=Reading[temp > 32.0 and temp > e1.temp]"
        " within 10 min")
    assert longer.count("->") == 32
    with pytest.raises(SiddhiAppCreationError,
                       match="dense NFA supports at most 32 chain nodes"):
        head, rest = longer.split("partition with", 1)
        compile_pattern(head + rest.split("begin", 1)[1].rsplit("end;", 1)[0],
                        "bench", n_partitions=64)
    assert pattern_of("iot32_1250k").count("->") == 31
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(DENSE + " " + longer)
        got = []
        rt.add_callback(CONFIG["output"], lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        assert set(rt.lowering().values()) == {"host"}
        sm = rt.app_context.statistics_manager
        (reason,) = sm.device_fallback_reasons.values()
        assert sum(sm.device_fallbacks.values()) == 1
        assert "at most 32 chain nodes" in reason
        # and the host engine runs the longer chain
        send = rt.get_input_handler("Reading").send_batch
        send(make_batch([7], [head_of(7)], 1_000))
        for j in range(1, 34):
            send(make_batch([7], [j + 0.5], 1_000 + j))
        # (at e33's event; the select still reads e32.temp)
        assert got == [(1_032, head_of(7), np.float32(31.5))]
        rt.shutdown()
    finally:
        m.shutdown()
