"""``ticknews_1m``'s app on the dense engine at 4,096 partitions, on
the cell's own generator at its rehearsal size, against the host engine
and the benchmark's plain reference.

What the 64-partition unit tests of the logical node do not reach: two
input streams over one pattern state with the key index shared by both
receivers, a step program a stream alternating on one donated state, a
second collision round of either stream through the step, ``within``
expiring arms of both kinds in every batch, a burst of hundreds of rows
on one batch in sixteen; the news batch at three places of the pass;
``batchesByStream`` and the cycle's ``stream`` count against counts
worked out by hand.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from ticknews_bench import (CONFIG, GEN, NEWS, REF, TICK, TRAFFIC,
                            make_batch, price_of, run_app)

DENSE = ("@app:statistics('true') "
         + CONFIG["header"].format(**CONFIG["rehearsal"]))
WITHIN = CONFIG["reference"]["within_ms"]
PASSES = 3          # the warm-up pass and two more


def inspect(rt):
    """``pattern_state()`` of the one query, its engine's streams and
    which programs it built."""
    (pr,) = rt.partitions.values()
    engine = pr.dense_query_runtimes["bench"].pattern_processor.engine
    return {**rt.pattern_state()["bench"],
            "stream_keys": engine.stream_keys,
            "programs": {k[:2] for k in engine._step_cache
                         if k[1] in (False, "rounds")}}


def stat(stats, name):
    (key,) = [k for k in stats if k.endswith("Queries.bench." + name)]
    return stats[key]


def reference_rows(schedule):
    """What the plain automaton owes over ``PASSES`` passes from the
    warm-up's first event, as the engine's ``(ts, price, sentiment)``."""
    t0 = schedule.ts_of(-schedule.warmup)
    return sorted(
        (t0 + p * schedule.pass_ms + ts, np.float32(price),
         np.float32(sentiment))
        for events in REF._pass_events(schedule, schedule.all_keys).values()
        for p, rows in REF._owed(events, WITHIN, schedule.pass_ms,
                                 set(range(PASSES))).items()
        for _n, ts, price, sentiment in rows)


@pytest.mark.parametrize("news_at", [0, 5, 15])
def test_dense_rows_equal_the_host_engines_and_the_references(news_at):
    schedule = GEN.make(2**31 + 7, CONFIG, {**TRAFFIC, "news_at": news_at},
                        True)
    assert schedule.news_at == news_at
    n = PASSES * schedule.per_pass
    batches = [schedule.batch(i) for i in range(-schedule.warmup,
                                                n - schedule.warmup)]
    host, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    got, errors, lowering, state, stats = run_app(DENSE, batches, inspect)
    assert lowering == CONFIG["expect"]["lowering"] and not errors
    assert sorted(got) == sorted(host) == reference_rows(schedule)
    # a pass owes some 890 rows wherever its news batch stands: fewer
    # in a run's first pass where it stands first (no tick before it)
    # and in its last where it stands last (no tick after it)
    assert len(got) > 2_000
    # delivery keeps a symbol's rows in event-time order
    by_symbol = {}
    for ts, price, _s in got:
        assert by_symbol.setdefault(price % 1, ts) <= ts
        by_symbol[price % 1] = ts
    assert state["stream_keys"] == CONFIG["stream"]
    assert state["instance_lanes"] == 4
    assert state["partitions_in_use"] == 4096
    assert state["dropped_instances"] == 0
    assert stat(stats, "droppedInstances") == 0
    # two rounds a batch: the step of either stream twice, no rounds
    # program, and the run kernel's class holds no logical node
    assert state["programs"] == {("StockTick", False), ("NewsEvent", False)}
    assert stat(stats, "batchesByStream.StockTick") == PASSES * 15
    assert stat(stats, "batchesByStream.NewsEvent") == PASSES
    # 748 first occurrences padded to 1,024 lanes, 276 second ones to 512
    assert stat(stats, "steppedLanes") == n * (1024 + 512)
    assert stat(stats, "plannedRepeats") == n * 276
    assert stat(stats, "putLeaves") == stat(stats, "devicePuts") == 2 * n


def test_the_run_kernel_refuses_a_logical_node_and_a_second_stream():
    from siddhi_tpu.kernels import dense_run
    from siddhi_tpu.ops.dense_nfa import compile_pattern

    eng = compile_pattern(
        CONFIG["app"].split("partition with")[0]
        + CONFIG["app"].split("begin")[1].split("end;")[0].replace(
            "@info(name='bench')", ""), n_partitions=64)
    assert [(n.kind, n.logical_op) for n in eng.nodes] == [("logical", "and")]
    assert eng.within_ms == WITHIN
    for sk in eng.stream_keys:
        assert not dense_run.eligible(eng, sk)


# what is sent, in order -> batchesByStream by hand
SENT = [(TICK, 3), (TICK, 5), (NEWS, 2), (TICK, 0), (NEWS, 4), (NEWS, 0),
        (TICK, 1)]


def test_batches_by_stream_and_the_cycles_stream_count():
    """A batch counts for the stream it came on once it holds an event;
    the ring's ``stream`` count is the stream's place among the
    engine's ``stream_keys``, once a batch, of no width, inside
    ``ingest``."""
    from siddhi_tpu.core.ingest_stage import IngestStats

    assert not [k for k in IngestStats().as_dict()
                if k.startswith("batchesByStream")]
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            DENSE + " @app:trace(sample='1', cycles='64') " + CONFIG["app"])
        rt.start()
        send = {side: rt.get_input_handler(CONFIG["stream"][side]).send_batch
                for side in (TICK, NEWS)}
        assert not [k for k in rt.statistics() if "batchesByStream" in k]
        for i, (side, n) in enumerate(SENT):
            symbols = np.arange(n)
            values = ([price_of(s, 1) for s in symbols] if side == TICK
                      else np.full(n, 0.5))
            send[side](make_batch(side, symbols, values, 1_000 + i))
        rt.drain_device_emits()
        stats = rt.statistics()
        assert stat(stats, "batchesByStream.StockTick") == 3
        assert stat(stats, "batchesByStream.NewsEvent") == 2
        spans = rt.app_context.tracer.recorder.spans()
        counts = [s for s in spans if s[1] == "stream"]
        assert [s[5] for s in counts] == [0, 0, 1, 1, 0]
        assert all(s[4] == s[3] for s in counts)
        ingest = {s[0]: s for s in spans if s[1] == "ingest"}
        for s in counts:
            assert ingest[s[0]][3] <= s[3] <= ingest[s[0]][4]
        rt.shutdown()
    finally:
        m.shutdown()
