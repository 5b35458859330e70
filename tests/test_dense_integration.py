"""Dense (jitted) pattern path integrated in the product engine.

`@app:execution('tpu')` routes eligible pattern queries created through
the public SiddhiManager API onto the dense NFA (ops/dense_nfa.py) —
asserted via the runtime's step-invocation counter — with host-engine
fallback for queries outside the dense subset.  Reference analog: the
planner wiring the pattern hot path
(util/parser/StateInputStreamParser.java:76-146).
"""

import contextlib

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.dense_pattern import DensePatternRuntime

TPU = "@app:execution('tpu') "


@pytest.fixture
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def run_app(manager, app, sends, out="Alerts", stream="Txn",
            transfer_guard=False):
    rt = manager.create_siddhi_app_runtime(app)
    got = []
    rt.add_callback(out, lambda evs: got.extend(e.data for e in evs))
    rt.start()
    h = rt.get_input_handler(stream)
    # transfer_guard: device↔host crossings in the event loop must be
    # explicit (staged_put in, device_get on the drain) — the dynamic
    # twin of the host-sync-hazard analysis rule.  No-op on the CPU
    # backend; bites on real accelerator runs.
    guard = contextlib.nullcontext()
    if transfer_guard:
        import jax

        guard = jax.transfer_guard("disallow")
    with guard:
        for row, ts in sends:
            h.send(row, timestamp=ts)
    rt.shutdown()
    return rt, got


PATTERN_APP = (
    "define stream Txn (card long, amount double); "
    "@info(name='q') "
    "from every a=Txn[amount > 100.0] -> b=Txn[amount > a.amount] "
    "within 10 min "
    "select a.amount as base, b.amount as bv insert into Alerts;"
)

SENDS = [
    ([1, 150.0], 1000),
    ([1, 90.0], 1500),    # matches neither filter
    ([1, 200.0], 2000),   # completes a->b
    ([1, 300.0], 3000),   # next every cycle: 200 armed? (host semantics)
]


class TestDensePath:
    def test_dense_path_executes_jitted_step(self, manager):
        rt, got = run_app(manager, TPU + PATTERN_APP, SENDS)
        qr = rt.query_runtimes["q"]
        assert isinstance(qr.pattern_processor, DensePatternRuntime)
        assert qr.pattern_processor.step_invocations == len(SENDS)
        assert got  # matches flowed through selector/output to callback

    def test_dense_matches_host_output(self, manager):
        # non-`every` pattern: dense and host semantics coincide exactly
        # (overlapping-`every` instances are the multi-instance work —
        # see test_dense_nfa for the dense-subset contract)
        app = PATTERN_APP.replace("from every a=", "from a=")
        _rt, dense = run_app(manager, TPU + app, SENDS)
        m2 = SiddhiManager()
        _rt2, host = run_app(m2, app, SENDS)
        m2.shutdown()
        assert dense == host == [[150.0, 200.0]]

    def test_dense_every_rearm_matches_host(self, manager):
        # `every`: a match must consume only the matched instance — the
        # completing event re-arms the start in the SAME step, so the
        # next event completes again (reset-on-emit would lose it)
        _rt, dense = run_app(manager, TPU + PATTERN_APP, SENDS,
                             transfer_guard=True)
        m2 = SiddhiManager()
        _rt2, host = run_app(m2, PATTERN_APP, SENDS)
        m2.shutdown()
        assert dense == host == [[150.0, 200.0], [200.0, 300.0]]

    @pytest.mark.parametrize("ann", ["kernels('nfa')", "nosuchoption('x')"])
    def test_an_app_annotation_the_planner_does_not_know_is_ignored(
            self, manager, ann):
        # the planner looks `@app:` annotations up by name and lists
        # none it does not know: a text that carries one (a removed
        # option, a misspelling) builds, lowers and answers as the text
        # without it
        rt, got = run_app(
            manager, f"@app:{ann} " + TPU + PATTERN_APP, SENDS)
        assert rt.lowering() == {"q": "dense"}
        assert got == [[150.0, 200.0], [200.0, 300.0]]

    def test_fallback_on_long_filter_operand(self, manager):
        # LONG filter comparisons ride the bit-exact hi/lo int32 pair
        # bank — values one apart above 2^24 (where float32 would
        # collide) still distinguish, ON the dense path
        app = TPU + (
            "define stream Txn (card long, amount double); "
            "@info(name='q') "
            "from a=Txn[card == 16777217] -> b=Txn[amount > a.amount] "
            "select a.amount as base, b.amount as bv insert into Alerts;"
        )
        rt, got = run_app(manager, app, [
            ([16777216, 150.0], 1000),   # NOT the filtered card value
            ([16777217, 140.0], 1500),
            ([16777217, 200.0], 2000),
        ])
        assert isinstance(
            rt.query_runtimes["q"].pattern_processor, DensePatternRuntime)
        assert got == [[140.0, 200.0]]  # exact dense comparison

    def test_host_mode_untouched(self, manager):
        rt, _ = run_app(manager, PATTERN_APP, SENDS)
        assert not isinstance(
            rt.query_runtimes["q"].pattern_processor, DensePatternRuntime)

    def test_trailing_absent_lowers_dense(self, manager):
        # round 4: `not X for t` rides deadline registers + the jitted
        # timer step (see tests/test_dense_absent.py for the semantics
        # corpus); only leading/sequence absent still falls back
        app = TPU + (
            "define stream A (v double); define stream B (v double); "
            "@info(name='q') from A -> not B for 1 sec "
            "select a.v as av insert into Alerts;"
        ).replace("from A ->", "from a=A ->")
        rt = manager.create_siddhi_app_runtime(app)
        proc = rt.query_runtimes["q"].pattern_processor
        assert isinstance(proc, DensePatternRuntime)
        assert proc.engine.has_deadlines

    def test_fallback_on_string_capture(self, manager):
        app = TPU + (
            "define stream Txn (card string, amount double); "
            "@info(name='q') "
            "from every a=Txn[amount > 100.0] -> b=Txn[card == a.card] "
            "select a.amount as base insert into Alerts;"
        )
        rt = manager.create_siddhi_app_runtime(app)
        assert not isinstance(
            rt.query_runtimes["q"].pattern_processor, DensePatternRuntime)

    def test_aggregating_selector_lowers_dense_with_host_selector(self, manager):
        """Group-by/aggregating pattern selectors lower densely: the
        engine emits raw capture columns and the host QuerySelector
        aggregates the (sparse) match rows — output matches host mode."""
        app = (
            "define stream Txn (card long, amount double); "
            "@info(name='q') "
            "from every a=Txn[amount > 100.0] -> b=Txn[amount > a.amount] "
            "within 10 min "
            "select a.amount as base, sum(b.amount) as total "
            "group by a.amount insert into Alerts;"
        )
        sends = [([1, 150.0], 1000), ([1, 200.0], 2000),
                 ([1, 300.0], 3000), ([1, 120.0], 3500),
                 ([1, 400.0], 4000)]
        rt, dense = run_app(manager, TPU + app, sends)
        assert isinstance(
            rt.query_runtimes["q"].pattern_processor, DensePatternRuntime)
        m2 = SiddhiManager()
        _rt2, host = run_app(m2, app, sends)
        m2.shutdown()
        assert dense == host and len(host) > 0

    def test_partitioned_aggregating_selector_per_key_sums(self, manager):
        """Round-4: the partitioned aggregating form runs dense with ONE
        shared selector keyed by the partition-key side channel — sums
        must stay per key, never pooled (host parity)."""
        app = (
            "define stream Txn (card string, amount double); "
            "partition with (card of Txn) begin "
            "@info(name='q') from every a=Txn[amount > 100.0] "
            "-> b=Txn[amount > a.amount] within 10 min "
            "select sum(b.amount) as t insert into Alerts; end;")
        sends = [(["c1", 150.0], 1000), (["c2", 500.0], 1100),
                 (["c1", 200.0], 2000), (["c2", 600.0], 2100)]
        _rt, dense_mode = run_app(
            manager, "@app:execution('tpu', partitions='64') " + app, sends)
        m2 = SiddhiManager()
        _rt2, host = run_app(m2, app, sends)
        m2.shutdown()
        # per-key sums: c1 gets 200, c2 gets 600 — never pooled
        assert dense_mode == host == [[200.0], [600.0]]

    def test_dense_persist_restore(self, manager):
        rt = manager.create_siddhi_app_runtime(TPU + PATTERN_APP)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("Txn")
        h.send([1, 150.0], timestamp=1000)      # arms a=150
        snap = rt.snapshot()
        h.send([1, 200.0], timestamp=2000)      # completes
        assert got == [[150.0, 200.0]]
        rt.restore(snap)                         # back to armed-only
        h.send([1, 180.0], timestamp=3000)
        assert got == [[150.0, 200.0], [150.0, 180.0]]
        rt.shutdown()


PARTITIONED_APP = (
    "@app:execution('tpu', partitions='64') "
    "define stream Txn (card string, amount double); "
    "partition with (card of Txn) begin "
    "@info(name='q') "
    "from every a=Txn[amount > 100.0] -> b=Txn[amount > a.amount] "
    "within 10 min "
    "select a.amount as base, b.amount as bv insert into Alerts; "
    "end;"
)


class TestDensePartition:
    def test_partition_lowered_to_one_engine(self, manager):
        rt, got = run_app(manager, PARTITIONED_APP, [
            (["c1", 150.0], 1000),
            (["c2", 500.0], 1100),
            (["c1", 200.0], 2000),   # completes c1
            (["c2", 400.0], 2100),   # not b for 500; arms its own 'every'
            (["c2", 600.0], 2200),   # completes BOTH c2 arms (500 and 400)
        ])
        pr = rt.partitions["partition_0"]
        assert pr.is_dense
        # host-exact since the instance axis: overlapping every arms both
        # match (arming-age order), where the old engine dropped [400, 600]
        assert got == [[150.0, 200.0], [500.0, 600.0], [400.0, 600.0]]
        runtime = next(iter(pr.dense_query_runtimes.values())).pattern_processor
        assert runtime.step_invocations == 5
        assert len(runtime._key_rows) == 2

    def test_partition_matches_host_instances(self, manager):
        # per-key isolation with non-`every` patterns: each key matches
        # once independently, identical to per-key host instances
        sends = [
            (["c1", 150.0], 1000),
            (["c2", 500.0], 1100),
            (["c1", 90.0], 1500),    # c1: matches neither filter
            (["c1", 200.0], 2000),   # completes c1
            (["c2", 600.0], 2200),   # completes c2
            (["c3", 90.0], 2300),    # never arms
        ]
        app = PARTITIONED_APP.replace("from every a=", "from a=")
        _rt, dense = run_app(manager, app, sends)
        m2 = SiddhiManager()
        host_app = app.replace("@app:execution('tpu', partitions='64') ", "")
        _rt2, host = run_app(m2, host_app, sends)
        m2.shutdown()
        assert sorted(map(tuple, dense)) == sorted(map(tuple, host))
        assert len(dense) == 2

    def test_partition_key_capacity_enforced(self, manager):
        app = PARTITIONED_APP.replace("partitions='64'", "partitions='2'")
        rt = manager.create_siddhi_app_runtime(app)
        rt.start()
        h = rt.get_input_handler("Txn")
        h.send(["c1", 150.0], timestamp=1000)
        h.send(["c2", 150.0], timestamp=1001)
        errors = []
        rt.app_context.exception_listeners.append(
            lambda e: errors.append(e))
        h.send(["c3", 150.0], timestamp=1002)  # third key exceeds cap 2
        rt.shutdown()
        assert errors  # routed to the app's exception listeners

    def test_partition_general_query_lowers_to_device(self, manager):
        # round 5: general (non-pattern) partition bodies lower to the
        # device query engine with the key composed into the group axis
        # (previously they fell back to per-key instances)
        app = (
            "@app:execution('tpu') "
            "define stream S (k string, v double); "
            "partition with (k of S) begin "
            "@info(name='q') from S select k, sum(v) as total "
            "insert into Out; end;"
        )
        rt, got = run_app(manager, app, [
            (["a", 1.0], 10), (["a", 2.0], 20), (["b", 5.0], 30),
        ], out="Out", stream="S")
        pr = rt.partitions["partition_0"]
        assert pr.is_dense
        assert pr.query_lowering() == {"q": "device"}
        assert got == [["a", 1.0], ["a", 3.0], ["b", 5.0]]

    def test_partition_dense_persist_restore(self, manager):
        rt = manager.create_siddhi_app_runtime(PARTITIONED_APP)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("Txn")
        h.send(["c1", 150.0], timestamp=1000)
        snap = rt.snapshot()
        h.send(["c1", 200.0], timestamp=2000)
        assert got == [[150.0, 200.0]]
        rt.restore(snap)
        h.send(["c1", 180.0], timestamp=3000)
        assert got == [[150.0, 200.0], [150.0, 180.0]]
        rt.shutdown()


class TestReviewRegressions:
    def test_long_capture_lowers_dense_and_exact(self, manager):
        """LONG captures/selects ride the hi/lo int32 pair bank: the
        card-number query lowers densely and round-trips bit-exact far
        above 2^24 (round-3 verdict item 6's done-criterion)."""
        app = TPU + (
            "define stream Txn (card long, amount double); "
            "@info(name='q') "
            "from a=Txn[amount > 100.0] -> b=Txn[amount > a.amount] "
            "select a.card as card, b.amount as bv insert into Alerts;"
        )
        rt, got = run_app(manager, app, [
            ([4111111111111111, 150.0], 1000),
            ([4111111111111111, 200.0], 2000),
        ])
        assert isinstance(
            rt.query_runtimes["q"].pattern_processor, DensePatternRuntime)
        assert got == [[4111111111111111, 200.0]]  # exact on the dense path

    def test_partitions_element_validated(self, manager):
        import pytest as _pytest

        from siddhi_tpu.core.exceptions import SiddhiAppCreationError

        for bad in ("0", "-5", "abc"):
            with _pytest.raises(SiddhiAppCreationError):
                manager.create_siddhi_app_runtime(
                    f"@app:execution('tpu', partitions='{bad}') "
                    "define stream S (v double); "
                    "@info(name='q') from a=S -> b=S "
                    "select a.v as av insert into Out;")

    def test_purge_reclaims_idle_key_rows(self, manager):
        """@purge on a dense partition recycles idle key rows, so key
        churn beyond capacity keeps working (host analog: idle
        PartitionInstance purge)."""
        app = (
            "@app:playback "
            "@app:execution('tpu', partitions='4') "
            "define stream Txn (card string, amount double); "
            "@purge(enable='true', interval='1 sec', idle.period='2 sec') "
            "partition with (card of Txn) begin "
            "@info(name='q') "
            "from a=Txn[amount > 100.0] -> b=Txn[amount > a.amount] "
            "select a.amount as base, b.amount as bv insert into Alerts; "
            "end;"
        )
        rt = manager.create_siddhi_app_runtime(app)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("Txn")
        # 4 distinct keys fill capacity
        for i, k in enumerate(["a", "b", "c", "d"]):
            h.send([k, 150.0], timestamp=1000 + i)
        pr = rt.partitions["partition_0"]
        runtime = next(iter(pr.dense_query_runtimes.values())).pattern_processor
        assert len(runtime._key_rows) == 4
        # playback time advances far past idle.period; purge fires on the
        # watermark advance
        h.send(["a", 90.0], timestamp=20_000)  # keeps 'a' alive, no arm
        rt.scheduler.advance(20_001)
        assert len(runtime._key_rows) < 4
        # a fresh key now fits again and completes a match
        h.send(["e", 150.0], timestamp=21_000)
        h.send(["e", 250.0], timestamp=21_500)
        assert [150.0, 250.0] in got
        rt.shutdown()


class TestPartitionedAggregatingSelector:
    """Round-4: partitioned aggregating pattern selectors run dense with
    ONE shared QuerySelector keeping per-(key, group) state via the
    partition-key side channel (host analog: per-key selector
    instances)."""

    APP_BODY = (
        "define stream Txn (card string, amount double); "
        "partition with (card of Txn) begin "
        "@info(name='q') from every a=Txn[amount > 100.0] -> "
        "b=Txn[amount > a.amount] "
        "select count() as n, sum(b.amount) as total "
        "having n >= 1 insert into Alerts; "
        "end;"
    )

    def _drive(self, header, sends):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(header + self.APP_BODY)
            got = []
            rt.add_callback(
                "Alerts", lambda evs: got.extend(list(e.data) for e in evs))
            rt.start()
            h = rt.get_input_handler("Txn")
            for row, ts in sends:
                h.send(row, timestamp=ts)
            pr = rt.partitions.get("partition_0")
            runtime = (next(iter(pr.dense_query_runtimes.values()))
                       .pattern_processor
                       if pr is not None and getattr(pr, "is_dense", False)
                       else None)
            rt.shutdown()
            return got, runtime
        finally:
            m.shutdown()

    def test_per_key_aggregation_matches_host(self):
        rng = np.random.default_rng(23)
        sends = []
        t = 1000
        for _ in range(50):
            k = f"c{int(rng.integers(0, 5))}"
            t += int(rng.integers(1, 30))
            sends.append(([k, float(rng.integers(50, 400))], t))
        host, hproc = self._drive("@app:playback ", sends)
        dense, dproc = self._drive(
            "@app:playback @app:execution('tpu', partitions='16') ", sends)
        assert hproc is None
        assert isinstance(dproc, DensePatternRuntime)
        assert dproc.step_invocations > 0
        # equality against the host's PER-KEY selector instances proves
        # the shared selector isolates state per partition key (pooled
        # counts/sums would diverge immediately)
        assert dense == host
        assert len(host) > 0
        assert max(n for n, _t in dense) > 1  # some key aggregated twice


class TestPartitionedAggregatingPurge:
    def test_purged_key_selector_state_resets(self):
        # idle purge must reset a key's AGGREGATION state too: after the
        # purge, count() restarts at 1 exactly like the host per-key
        # instance form (review finding r4)
        app = (
            "@app:playback "
            "define stream Txn (card string, amount double); "
            "@purge(enable='true', interval='1 sec', idle.period='2 sec') "
            "partition with (card of Txn) begin "
            "@info(name='q') from every a=Txn[amount > 100.0] -> "
            "b=Txn[amount > a.amount] "
            "select count() as n insert into Alerts; "
            "end;"
        )
        sends = [
            (["c1", 150.0], 1000), (["c1", 200.0], 1100),   # match: n=1
            (["c1", 150.0], 6000),                          # purged; re-arm
            (["c1", 200.0], 6100),                          # match: n=1 again
        ]

        def drive(header):
            m = SiddhiManager()
            try:
                rt = m.create_siddhi_app_runtime(header + app)
                got = []
                rt.add_callback(
                    "Alerts", lambda evs: got.extend(list(e.data) for e in evs))
                rt.start()
                h = rt.get_input_handler("Txn")
                for row, ts in sends:
                    h.send(row, timestamp=ts)
                rt.shutdown()
                return got
            finally:
                m.shutdown()

        host = drive("")
        dense = drive("@app:execution('tpu', partitions='16') ")
        assert dense == host == [[1], [1]]

    def test_partitioned_rate_limit_falls_back(self, manager):
        # per-key limiters cannot share one dense limiter — host used
        app = (
            "@app:execution('tpu', partitions='16') "
            "define stream Txn (card string, amount double); "
            "partition with (card of Txn) begin "
            "@info(name='q') from every a=Txn[amount > 100.0] -> "
            "b=Txn[amount > a.amount] "
            "select a.amount as av output every 2 events "
            "insert into Alerts; end;")
        rt = manager.create_siddhi_app_runtime(app)
        pr = rt.partitions.get("partition_0")
        assert pr is not None and not getattr(pr, "is_dense", False)


class TestGroupEveryDense:
    def test_whole_chain_group_every_lowers(self, manager):
        # `every (e1 -> e2)`: one arm at a time, re-armed at completion
        # and after within-expiry (WithinPatternTestCase.testQuery4/6)
        app = TPU + (
            "define stream T (v double, w long); "
            "@info(name='q') from every (a=T[v > 1.0] -> "
            "b=T[w == a.w]) within 5 sec "
            "select a.v as av, b.v as bv insert into Alerts;")
        rt, got = run_app(manager, app, [
            ([5.0, 7], 1000),
            ([6.0, 7], 7000),    # first arm expired; fresh arm
            ([7.0, 7], 7500),    # completes (6, 7)
            ([8.0, 7], 7510),    # new arm
        ], stream="T")
        proc = rt.query_runtimes["q"].pattern_processor
        assert isinstance(proc, DensePatternRuntime)
        assert proc.engine.group_every and proc.engine.I == 1
        assert got == [[6.0, 7.0]]

    def test_partial_chain_group_every_falls_back(self, manager):
        app = TPU + (
            "define stream T (v double, w long); "
            "@info(name='q') from every (a=T[v > 1.0] -> b=T[v > a.v]) "
            "-> c=T[v > b.v] "
            "select a.v as av, c.v as cv insert into Alerts;")
        rt = manager.create_siddhi_app_runtime(app)
        assert not isinstance(
            rt.query_runtimes["q"].pattern_processor, DensePatternRuntime)


class TestOverflowSignal:
    def test_dropped_instances_reach_exception_listeners(self, manager):
        """Instance-lane overflow (real matches possibly lost) must be a
        USER-VISIBLE signal — a WARNING log plus the app's exception
        listeners — not just an internal counter (the overflow policy
        is documented at ops/dense_nfa.py:39-47)."""
        import logging

        app = (
            "@app:playback @app:execution('tpu', instances='1') "
            "define stream S (k string, v double); "
            "@info(name='q') from every a=S[v > 0.0] -> b=S[v > 100.0] "
            "within 10 min select a.v as av, b.v as bv insert into Out;"
        )
        rt = manager.create_siddhi_app_runtime(app)
        seen = []
        rt.add_exception_listener(seen.append)
        rt.start()
        h = rt.get_input_handler("S")
        logger = logging.getLogger("siddhi_tpu")
        records = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r)
        logger.addHandler(handler)
        try:
            # 'every' arms a new pending instance per event; with a
            # single lane, the second arm drops a pending instance
            for i in range(400):
                h.send(["u", 1.0 + i], timestamp=1000 + i)
            rt.shutdown()  # close() runs the final overflow check
        finally:
            logger.removeHandler(handler)
        qr = rt.query_runtimes["q"]
        stats = qr.pattern_processor.stats()
        assert stats["dropped_instances"] > 0  # overflow really happened
        assert seen, "exception listeners must observe dropped matches"
        assert "dropped" in str(seen[0])
        assert any("dropped" in r.getMessage() for r in records)
