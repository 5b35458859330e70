"""Overload protection and self-healing (robustness/) suite.

The contract under test, end to end:

- ``@app:limits(rate=...)`` admission control sheds EXACTLY what the
  token-bucket arithmetic says it must (a reference bucket is
  reimplemented here as an independent oracle), per stream, with the
  admitted events' outputs bit-identical to an unthrottled run fed
  only the admitted set — including under a Zipf-skewed multi-tenant
  chaos soak with transient ingest/emit faults.
- The watchdog detects a wedged async batch cycle and self-heals by
  forcing a replan: engines rebuilt, journal history replayed through
  the suppressing output ledger, outputs bit-identical to an
  uninterrupted run.  Without a journal the heal is REFUSED and
  counted, never attempted.
- Circuit breakers on sinks spool output while open (bounded) and
  flush exactly once on close — no duplicates, order preserved.
- ``GET /siddhi-health/<app>`` reports the same counters the
  statistics feed carries; overloaded apps answer 503 with a JSON body
  instead of blocking on the app lock.
- Zero behavior change without the annotation.
"""

import time
import urllib.error
import urllib.request

import json

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.exceptions import (
    InjectedFaultError,
    SiddhiAppCreationError,
    SimulatedCrashError,
)
from siddhi_tpu.robustness import RobustnessStats, TokenBucket


def _collector(res):
    return lambda events: res.extend(
        (e.timestamp, tuple(e.data)) for e in events)


def _norm(rows):
    """DOUBLE attrs ride float32 device lanes (documented precision
    subset): one-decimal inputs are exact at 4dp."""
    return [(ts, tuple(round(v, 4) if isinstance(v, float) else v
                       for v in r)) for ts, r in rows]


class RefBucket:
    """Independent oracle: the token-bucket arithmetic reimplemented
    from the paper's spec (NOT imported from robustness/) — the exact
    float ops the controller must match, event time in seconds."""

    def __init__(self, rate, burst, now):
        self.rate = float(rate)
        self.burst = float(burst)
        self.tokens = float(burst)
        self.last = now

    def take(self, n, now):
        if now > self.last:
            self.tokens = min(self.burst,
                              self.tokens + (now - self.last) * self.rate)
            self.last = now
        k = int(min(n, self.tokens))
        self.tokens -= k
        return k


class TestTokenBucket:
    def test_burst_then_refill(self):
        b = TokenBucket(rate=10.0, burst=5.0, now=0.0)
        assert b.take(8, 0.0) == 5          # burst drained
        assert b.take(3, 0.0) == 0
        assert b.take(3, 0.2) == 2          # 0.2 s * 10/s = 2 tokens
        assert b.take(100, 10.0) == 5       # refill caps at burst

    def test_refill_never_rewinds(self):
        b = TokenBucket(rate=10.0, burst=5.0, now=1.0)
        b.take(5, 1.0)
        b.refill(0.5)                       # stale clock: no-op
        assert b.tokens == 0.0

    def test_eta_to_next_token(self):
        b = TokenBucket(rate=4.0, burst=1.0, now=0.0)
        assert b.eta_s(0.0) == 0.0
        b.take(1, 0.0)
        assert b.eta_s(0.0) == pytest.approx(0.25)


class TestLimitsAnnotation:
    @pytest.mark.parametrize("ann, msg", [
        ("@app:limits()", "at least one"),
        ("@app:limits(burst='5')", "burst needs rate"),
        ("@app:limits(rate='0/s')", "positive"),
        ("@app:limits(rate='5/s', shed='weird')", "drop, oldest, block"),
        ("@app:limits(rate='5/s', burst='0')", "burst"),
        ("@app:limits(breaker='0')", "breaker"),
    ])
    def test_invalid_annotations_refused(self, ann, msg):
        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError, match=msg):
                m.create_siddhi_app_runtime(
                    ann + " define stream S (k long);")
        finally:
            m.shutdown()

    def test_an_element_the_annotation_does_not_know_is_ignored(self):
        # a key @app:limits never looks up (a removed element, a
        # misspelling) is read by nothing: alone it is still "none of
        # rate, watchdog, breaker", beside a known one it changes nothing
        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError, match="at least one"):
                m.create_siddhi_app_runtime(
                    "@app:limits(ladder='true') define stream S (k long);")
            rt = m.create_siddhi_app_runtime(
                "@app:limits(watchdog='60 sec', ladder='true', nosuch='1') "
                "define stream S (k long);")
            rt.start()
            doc = rt.health()
            assert doc["watchdog"]["deadline_ms"] == 60_000
            assert set(doc) == {
                "app", "healthy", "running", "shedding", "wedged",
                "admission", "breakers", "watchdog", "counters"}
            assert not any("ladder" in k for k in doc["counters"])
            rt.shutdown()
        finally:
            m.shutdown()

    def test_no_annotation_means_zero_machinery(self):
        """Zero behavior change without @app:limits: no controller, no
        stats object, no watchdog, no breaker, no Robustness metrics."""
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime("""
@app:name('plain0') @app:playback
define stream S (k long, v double);
@info(name='q') from S[v > 0.0] select k, v insert into OutS;
""")
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            ctx = rt.app_context
            assert ctx.admission is None
            assert ctx.robustness is None
            assert getattr(rt, "_watchdog", None) is None
            assert rt.sinks == [] or all(
                s._breaker is None for s in rt.sinks)
            h = rt.get_input_handler("S")
            for i in range(50):
                h.send([i, 1.0], timestamp=1000 + i)
            assert len(got) == 50
            assert not any("Robustness" in k for k in rt.statistics())
            hd = rt.health()
            assert hd["healthy"] and hd["admission"] is None
            rt.shutdown()
        finally:
            m.shutdown()


SHED_APP = """
@app:name('sh{tag}') @app:playback
@app:limits(rate='{rate}/s', burst='{burst}', shed='{shed}')
define stream S (k long, v double);
@info(name='q') from S[v >= 0.0] select k, v insert into OutS;
"""


class TestShedPolicies:
    def _run(self, tag, shed, sends, rate=5, burst=5):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(SHED_APP.format(
                tag=tag, rate=rate, burst=burst, shed=shed))
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            h = rt.get_input_handler("S")
            for row, ts in sends:
                h.send(list(row), timestamp=ts)
            rb = rt.app_context.robustness
            snap = rt.app_context.admission.snapshot()
            rt.shutdown()
            return got, rb, snap
        finally:
            m.shutdown()

    def test_drop_keeps_arrival_order_prefix(self):
        # 12 events inside one event-time second, budget = burst 5
        sends = [([i, float(i)], 1_000_000 + i) for i in range(12)]
        got, rb, snap = self._run("d0", "drop", sends)
        assert [r[1] for ts, r in got] == [0.0, 1.0, 2.0, 3.0, 4.0]
        assert (rb.events_admitted, rb.events_shed) == (5, 7)
        assert rb.shed_drop == 7 and rb.shed_oldest == 0
        assert snap["streams"]["S"] == {
            "admitted": 5, "shed": 7,
            "tokens": snap["streams"]["S"]["tokens"]}

    def test_oldest_keeps_the_freshest_rows(self):
        # one BATCH of 12: 'oldest' sheds the head, the newest 5 survive
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(SHED_APP.format(
                tag="o0", rate=5, burst=5, shed="oldest"))
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            from siddhi_tpu.core.event import Event

            h = rt.get_input_handler("S")
            h.send([Event(1_000_000 + i, [i, float(i)])
                    for i in range(12)])
            rb = rt.app_context.robustness
            assert [r[1] for ts, r in got] == [7.0, 8.0, 9.0, 10.0, 11.0]
            assert rb.shed_oldest == 7 and rb.events_admitted == 5
            rt.shutdown()
        finally:
            m.shutdown()

    def test_block_in_playback_is_an_immediate_counted_timeout(self):
        # event time cannot advance while the sender parks: block
        # degrades to a deterministic timeout shed
        sends = [([i, float(i)], 1_000_000 + i) for i in range(12)]
        got, rb, _ = self._run("b0", "block", sends)
        assert len(got) == 5
        assert rb.shed_block_timeout == 7
        assert rb.block_waits == 0

    def test_block_backpressures_the_sender_wall_clock(self):
        # live clock: rate 200/s refills fast enough that every send
        # eventually admits — the sender just waits for its budget
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime("""
@app:name('blk1')
@app:limits(rate='200/s', burst='1', shed='block', block.max='2 sec')
define stream S (k long, v double);
@info(name='q') from S[v >= 0.0] select k, v insert into OutS;
""")
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            h = rt.get_input_handler("S")
            for i in range(6):
                h.send([i, float(i)], timestamp=1000 + i)
            rb = rt.app_context.robustness
            assert len(got) == 6                  # nothing shed
            assert rb.events_shed == 0
            assert rb.block_waits >= 1            # backpressure happened
            rt.shutdown()
        finally:
            m.shutdown()

    def test_block_max_expiry_sheds_and_counts(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime("""
@app:name('blk2')
@app:limits(rate='5/s', burst='1', shed='block', block.max='40 ms')
define stream S (k long, v double);
@info(name='q') from S[v >= 0.0] select k, v insert into OutS;
""")
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            h = rt.get_input_handler("S")
            for i in range(5):
                h.send([i, float(i)], timestamp=1000 + i)
            rb = rt.app_context.robustness
            assert rb.shed_block_timeout >= 1
            assert rb.events_admitted + rb.events_shed == 5
            assert len(got) == rb.events_admitted
            rt.shutdown()
        finally:
            m.shutdown()

    def test_admission_shed_fault_site_fires_on_the_drop(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:faults " + SHED_APP.format(
                    tag="f0", rate=5, burst=5, shed="drop")[1:])
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            rt.app_context.fault_injector.configure(
                "admission.shed", "error", count=1)
            h = rt.get_input_handler("S")
            for i in range(5):
                h.send([i, float(i)], timestamp=1_000_000 + i)
            with pytest.raises(InjectedFaultError):
                h.send([5, 5.0], timestamp=1_000_000 + 5)   # first shed
            h.send([6, 6.0], timestamp=1_000_000 + 6)       # next is fine
            rb = rt.app_context.robustness
            assert rb.events_shed == 2      # both sheds counted
            assert len(got) == 5
            rt.shutdown()
        finally:
            m.shutdown()


SOAK_LIMITS = "@app:limits(rate='100/s', burst='20', shed='drop')"

SOAK_APP = """
@app:name('soak{tag}') @app:playback @app:execution('tpu') {faults} {limits}
define stream T0 (sym int, price float, vol int);
define stream T1 (sym int, price float, vol int);
define stream T2 (sym int, price float, vol int);
@info(name='q0') from T0[price > 5.0]
select sym, price, vol insert into OutA;
@info(name='q1') from T1[price > 5.0]
select sym, price, vol insert into OutA;
@info(name='q2') from T2[vol > 20] select sym, price insert into OutB;
"""


def _soak_traffic(n=900, seed=101):
    """Zipf-skewed multi-tenant traffic: tenant T0 takes ~60% of a
    ~300 ev/s aggregate (≈1.8x its 100/s budget), T1 ~27%, T2 ~13%
    (comfortably under budget).  Strictly increasing event time."""
    rng = np.random.default_rng(seed)
    weights = np.array([1.0, 1 / 2.2, 1 / 4.5])
    weights /= weights.sum()
    sends, ts = [], 1_000_000
    for _ in range(n):
        ts += int(rng.integers(2, 5))  # ~3.3 ms mean -> ~300 ev/s
        tenant = int(rng.choice(3, p=weights))
        row = [int(rng.integers(0, 50)),
               float(np.float32(rng.uniform(0, 30))),
               int(rng.integers(1, 100))]
        sends.append((f"T{tenant}", row, ts))
    return sends


def _expected_admission(sends, rate=100.0, burst=20.0):
    """Run the oracle buckets over the traffic: the exact admitted
    subset and per-stream shed counts the engine must reproduce."""
    buckets, admitted, shed = {}, [], {}
    for sid, row, ts in sends:
        now = ts / 1000.0
        b = buckets.get(sid)
        if b is None:
            b = buckets[sid] = RefBucket(rate, burst, now)
        if b.take(1, now):
            admitted.append((sid, row, ts))
        else:
            shed[sid] = shed.get(sid, 0) + 1
    return admitted, shed


class TestChaosSoak:
    pytestmark = pytest.mark.faults

    def test_zipf_multitenant_shed_is_exact_and_bit_identical(self):
        sends = _soak_traffic()
        admitted, shed = _expected_admission(sends)
        # the skew actually exercises both regimes
        assert shed.get("T0", 0) > 100      # heavy tenant sheds hard
        assert "T2" not in shed             # light tenant untouched

        def run(tag, faults, limits, traffic):
            m = SiddhiManager()
            try:
                rt = m.create_siddhi_app_runtime(SOAK_APP.format(
                    tag=tag, faults=faults, limits=limits))
                a, b = [], []
                rt.add_callback("OutA", _collector(a))
                rt.add_callback("OutB", _collector(b))
                rt.start()
                for sid, row, ts in traffic:
                    rt.get_input_handler(sid).send(list(row), timestamp=ts)
                rb = rt.app_context.robustness
                snap = (rt.app_context.admission.snapshot()
                        if rt.app_context.admission else None)
                rt.shutdown()
                return a, b, rb, snap
            finally:
                m.shutdown()

        # unthrottled reference fed ONLY the oracle-admitted subset
        ref_a, ref_b, _, _ = run("r", "", "", admitted)
        # throttled chaos run fed EVERYTHING, with transient faults on
        # the ingest and emit paths
        faults = ("@app:faults(journal='16384', "
                  "transfer.retry.scale='0.001', "
                  "ingest.put='transient:count=3', "
                  "emit.drain='transient:count=2')")
        got_a, got_b, rb, snap = run("c", faults, SOAK_LIMITS, sends)

        # exact shed accounting, per tenant, against the oracle
        assert rb.events_shed == sum(shed.values())
        assert rb.events_admitted == len(admitted)
        for sid in ("T0", "T1", "T2"):
            assert snap["streams"].get(sid, {}).get("shed", 0) == \
                shed.get(sid, 0)
        # admitted outputs bit-identical to the unthrottled reference
        assert len(ref_a) > 100 and len(ref_b) > 20
        assert _norm(got_a) == _norm(ref_a)
        assert _norm(got_b) == _norm(ref_b)


WD_APP = """
@app:name('wd{tag}') {faults}
@app:limits(watchdog='200 ms')
@async(buffer.size='64', batch.size.max='16')
define stream S (k long, v double);
@info(name='q') from S[v > 0.0] select k, v insert into OutS;
"""


class _Wedge:
    """Junction receiver whose BaseException kills the async worker
    mid-dispatch — batches journal and queue but never deliver, the
    exact wedge the watchdog exists to heal."""

    def receive(self, batch):
        raise SimulatedCrashError("wedged worker")


class TestWatchdog:
    pytestmark = pytest.mark.faults

    def test_wedge_heals_and_journal_tail_replays_bit_exactly(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(WD_APP.format(
                tag="h0", faults="@app:faults(journal='8192')"))
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            rt.junctions["S"].subscribe(_Wedge())
            h = rt.get_input_handler("S")
            for i in range(1, 6):
                h.send([i, float(i)], timestamp=1000 + i)
            time.sleep(0.05)   # worker is dead by now
            for i in range(6, 11):
                h.send([i, float(i)], timestamp=1000 + i)
            rb = rt.app_context.robustness
            deadline = time.time() + 15
            while rb.watchdog_recoveries == 0 \
                    and rb.watchdog_recovery_failures == 0 \
                    and time.time() < deadline:
                time.sleep(0.05)
            time.sleep(0.3)    # let the post-heal dispatches settle
            assert rb.watchdog_trips >= 1
            assert rb.watchdog_recoveries == 1
            assert rb.watchdog_recovery_failures == 0
            # the tail keeps flowing through the rebuilt engines (the
            # cached InputHandler was re-pointed in place)
            for i in range(11, 16):
                h.send([i, float(i)], timestamp=1000 + i)
            time.sleep(0.3)
            expect = sorted((1000 + i, (i, float(i)))
                            for i in range(1, 16))
            assert sorted(got) == expect    # bit-identical, no dupes
            hd = rt.health()
            assert not hd["wedged"]
            assert hd["watchdog"]["recoveries"] == 1
            # the heal left a latency span on the live tracer
            tr = rt.app_context.tracer
            assert tr is not None
            assert tr.stage_stats().get("watchdog.heal", {}).get(
                "spans", 0) >= 1
            rt.shutdown()
        finally:
            m.shutdown()

    def test_heal_without_journal_is_refused_and_counted(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(WD_APP.format(
                tag="r0", faults=""))
            got = []
            rt.add_callback("OutS", _collector(got))
            rt.start()
            rt.junctions["S"].subscribe(_Wedge())
            h = rt.get_input_handler("S")
            for i in range(1, 6):
                h.send([i, float(i)], timestamp=1000 + i)
            time.sleep(0.05)   # worker is dead by now
            # a second wave piles up behind the dead worker: the queue
            # stays pending, which is what makes the stall visible
            for i in range(6, 11):
                h.send([i, float(i)], timestamp=1000 + i)
            rb = rt.app_context.robustness
            deadline = time.time() + 15
            while rb.watchdog_recovery_failures == 0 \
                    and time.time() < deadline:
                time.sleep(0.05)
            assert rb.watchdog_recovery_failures >= 1
            assert rb.watchdog_recoveries == 0
            hd = rt.health()
            assert hd["wedged"] and not hd["healthy"]
            rt.shutdown()
        finally:
            m.shutdown()


class TestCircuitBreaker:
    def setup_method(self):
        from siddhi_tpu.transport.broker import InMemoryBroker

        InMemoryBroker.clear()

    def test_state_machine_counts_every_transition(self):
        from siddhi_tpu.robustness import CircuitBreaker

        clock = [0.0]
        rb = RobustnessStats()
        b = CircuitBreaker("t", threshold=2, cooldown_ms=100, stats=rb,
                           clock=lambda: clock[0])
        assert b.allow() and b.state == "closed"
        b.record_failure()
        assert b.state == "closed"          # below threshold
        b.record_failure()
        assert b.state == "open" and rb.breaker_opens == 1
        assert not b.allow()                # short-circuited
        assert rb.breaker_short_circuits == 1
        clock[0] = 0.2                      # past cooldown
        assert b.allow()                    # half-open probe
        assert b.state == "half-open" and rb.breaker_half_opens == 1
        assert not b.allow()                # only ONE probe in flight
        b.record_failure()                  # probe failed -> reopen
        assert b.state == "open" and rb.breaker_opens == 2
        clock[0] = 0.4
        assert b.allow()
        assert b.record_success() is True   # this close flushes spools
        assert b.state == "closed" and rb.breaker_closes == 1
        assert b.record_success() is False  # already closed

    def test_open_breaker_spools_and_flushes_exactly_once(self):
        from siddhi_tpu.transport.broker import (
            FunctionSubscriber,
            InMemoryBroker,
        )

        m = SiddhiManager()
        sub = None
        try:
            rt = m.create_siddhi_app_runtime("""
@app:name('cb1')
@app:faults(sink.connect='conn:count=4')
@app:limits(breaker='2', breaker.cooldown='60 ms')
@sink(type='inMemory', topic='tcb1', retry.scale='0.004')
define stream S (k long, v double);
""")
            published = []
            sub = FunctionSubscriber("tcb1", published.append)
            InMemoryBroker.subscribe(sub)
            rt.start()
            sink = rt.sinks[0]
            rb = rt.app_context.robustness
            assert sink._breaker is not None
            # wait for the failed connects to OPEN the breaker
            deadline = time.time() + 10
            while rb.breaker_opens == 0 and time.time() < deadline:
                time.sleep(0.01)
            assert sink._breaker.state == "open"
            # everything sent while open spools — no publish attempts
            h = rt.get_input_handler("S")
            for i in range(4):
                h.send([i, float(i)], timestamp=1000 + i)
            assert rb.breaker_spooled_batches == 4
            assert len(published) == 0
            # cooldown elapses, the retry chain's probe connects, the
            # breaker closes and the spool flushes IN ORDER, exactly once
            deadline = time.time() + 10
            while (not sink.connected or sink._spool) \
                    and time.time() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)
            assert sink.connected and sink._breaker.state == "closed"
            assert rb.breaker_closes >= 1
            assert rb.breaker_flushed_batches == 4
            assert rb.breaker_spool_dropped == 0
            assert [e.data[0] for e in published] == [0, 1, 2, 3]
            assert rb.breaker_short_circuits >= 1
            hd = rt.health()
            assert hd["breakers"] and \
                hd["breakers"][0]["state"] == "closed"
            rt.shutdown()
        finally:
            m.shutdown()
            if sub is not None:
                InMemoryBroker.unsubscribe(sub)

    def test_spool_overflow_evicts_oldest_and_counts(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime("""
@app:name('cb2')
@app:faults(sink.connect='conn:count=999')
@app:limits(breaker='1', breaker.cooldown='60 sec')
@sink(type='inMemory', topic='tcb2', retry.scale='0.0001')
define stream S (k long, v double);
""")
            rt.start()
            sink = rt.sinks[0]
            sink.attach_breaker(sink._breaker, spool_cap=2)  # tiny spool
            rb = rt.app_context.robustness
            deadline = time.time() + 10
            while rb.breaker_opens == 0 and time.time() < deadline:
                time.sleep(0.01)
            h = rt.get_input_handler("S")
            for i in range(5):
                h.send([i, float(i)], timestamp=1000 + i)
            assert len(sink._spool) == 2
            assert rb.breaker_spool_dropped == 3   # oldest 3 evicted
            rt.shutdown()
        finally:
            m.shutdown()

    def test_shutdown_flushes_deliverable_spool(self):
        """Regression (found by the barrier-flush-completeness rule):
        ``Sink.shutdown`` used to warn-and-drop batches still spooled
        behind the breaker even when the transport was up and the
        cooldown had elapsed — the shutdown barrier never reached a
        flush of the ``_spool`` queue it owns.  It now attempts one
        final breaker-gated flush before declaring the loss."""
        from siddhi_tpu.transport.broker import (
            FunctionSubscriber,
            InMemoryBroker,
        )

        m = SiddhiManager()
        sub = None
        try:
            rt = m.create_siddhi_app_runtime("""
@app:name('cb3')
@app:limits(breaker='2', breaker.cooldown='40 ms')
@sink(type='inMemory', topic='tcb3')
define stream S (k long, v double);
""")
            published = []
            sub = FunctionSubscriber("tcb3", published.append)
            InMemoryBroker.subscribe(sub)
            rt.start()
            sink = rt.sinks[0]
            assert sink.connected and sink._breaker is not None
            # trip the breaker while connected (publish-side failures)
            sink._breaker.record_failure()
            sink._breaker.record_failure()
            assert sink._breaker.is_open()
            h = rt.get_input_handler("S")
            for i in range(3):
                h.send([i, float(i)], timestamp=1000 + i)
            assert len(sink._spool) == 3 and published == []
            time.sleep(0.08)  # cooldown elapses; no further traffic
            rt.shutdown()
            # the final barrier flush delivered everything, in order
            assert [e.data[0] for e in published] == [0, 1, 2]
            assert not sink._spool
            rb = rt.app_context.robustness
            assert rb.breaker_flushed_batches == 3
        finally:
            m.shutdown()
            if sub is not None:
                InMemoryBroker.unsubscribe(sub)

    def test_half_open_flush_does_not_self_deadlock(self):
        """Regression (found by the lock-order-deadlock rule's
        reentrancy audit): flushing through a HALF-OPEN breaker closes
        it on the first successful publish, and
        ``publish_with_reconnect`` then re-enters ``_flush_spool`` on
        the same thread — with a non-reentrant ``_spool_lock`` that
        path self-deadlocked.  The lock is an RLock now; the nested
        flush drains the remainder and the outer loop exits empty."""
        import threading

        from siddhi_tpu.transport.broker import (
            FunctionSubscriber,
            InMemoryBroker,
        )

        m = SiddhiManager()
        sub = None
        try:
            rt = m.create_siddhi_app_runtime("""
@app:name('cb4')
@app:limits(breaker='2', breaker.cooldown='40 ms')
@sink(type='inMemory', topic='tcb4')
define stream S (k long, v double);
""")
            published = []
            sub = FunctionSubscriber("tcb4", published.append)
            InMemoryBroker.subscribe(sub)
            rt.start()
            sink = rt.sinks[0]
            sink._breaker.record_failure()
            sink._breaker.record_failure()
            assert sink._breaker.is_open()
            h = rt.get_input_handler("S")
            h.send([0, 0.0], timestamp=1000)
            h.send([1, 1.0], timestamp=1001)
            assert len(sink._spool) == 2
            time.sleep(0.08)  # past cooldown: next send probes half-open
            t = threading.Thread(
                target=lambda: h.send([2, 2.0], timestamp=1002),
                daemon=True)
            t.start()
            t.join(timeout=5)
            assert not t.is_alive(), (
                "send through a half-open breaker with a non-empty "
                "spool deadlocked in the nested flush")
            assert [e.data[0] for e in published] == [0, 1, 2]
            assert sink._breaker.state == "closed"
            rt.shutdown()
        finally:
            m.shutdown()
            if sub is not None:
                InMemoryBroker.unsubscribe(sub)


class TestRetryShutdownRace:
    def test_arm_after_shutdown_is_a_gated_noop(self):
        """Regression: a connect failure racing ``shutdown()`` used to
        arm a fresh backoff Timer AFTER ``_shutdown_retry()`` had
        cancelled the old one — a zombie firing into a dead (or worse,
        restarted) transport.  The arm is now gated on ``_shutdown``
        under ``_retry_lock``."""
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@sink(type='inMemory', topic='trace1') "
                "define stream S (k long, v double);")
            rt.start()
            sink = rt.sinks[0]
            sink._shutdown_retry()
            # the racing failure path tries to arm the next interval
            with sink._retry_lock:
                sink._retrying = True
            sink._arm_retry_timer(60_000)
            assert sink._retry_timer is None      # no zombie armed
            assert sink._retrying is False        # chain marked dead
            # and the mixin stays restartable
            sink.start()
            assert sink.connected
            rt.shutdown()
        finally:
            m.shutdown()


class TestHealthEndpoint:
    def test_health_rest_matches_statistics_feed(self):
        from siddhi_tpu.service import SiddhiService

        svc = SiddhiService()
        svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        try:
            # the per-request socket timeout satellite is on the handler
            assert svc._server.RequestHandlerClass.timeout == 10
            app = """
@app:name('hrest') @app:playback
@app:limits(rate='5/s', burst='5', shed='drop')
define stream S (k long, v double);
@info(name='q') from S[v > 0.0] select k, v insert into OutS;
"""
            req = urllib.request.Request(
                f"{base}/siddhi-artifact-deploy", data=app.encode(),
                method="POST")
            with urllib.request.urlopen(req) as r:
                assert r.status == 200
            with urllib.request.urlopen(
                    f"{base}/siddhi-health/hrest") as r:
                doc = json.loads(r.read())
            assert r.status == 200 and doc["status"] == "OK"
            assert doc["healthy"] and not doc["shedding"]

            # push past the budget: shedding -> 503 with a JSON body
            rt = svc.manager.get_siddhi_app_runtime("hrest")
            h = rt.get_input_handler("S")
            for i in range(12):
                h.send([i, 1.0], timestamp=1_000_000 + i)
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/siddhi-health/hrest")
            assert e.value.code == 503
            doc = json.loads(e.value.read())
            assert doc["status"] == "UNHEALTHY" and doc["shedding"]
            assert doc["counters"]["events_shed"] == 7
            # the REST counters ARE the statistics feed's counters
            st = rt.statistics()
            key = ("io.siddhi.SiddhiApps.hrest.Siddhi."
                   "Robustness.overload.events_shed")
            assert st[key] == doc["counters"]["events_shed"]
            # lock-taking ops answer 503-overloaded instead of queueing
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    f"{base}/siddhi-pattern-state/hrest")
            assert e.value.code == 503
            assert json.loads(e.value.read())["status"] == "ERROR"

            # unknown app -> 404
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/siddhi-health/ghost")
            assert e.value.code == 404

            # window passes -> healthy again, ops unblocked
            time.sleep(1.1)
            with urllib.request.urlopen(
                    f"{base}/siddhi-health/hrest") as r:
                assert json.loads(r.read())["healthy"]
            with urllib.request.urlopen(
                    f"{base}/siddhi-pattern-state/hrest") as r:
                assert r.status == 200
        finally:
            svc.stop()

    def test_manager_wide_rollup(self):
        m = SiddhiManager()
        try:
            m.create_siddhi_app_runtime(
                "@app:name('ra') define stream S (k long);")
            m.create_siddhi_app_runtime(
                "@app:name('rb') @app:limits(rate='5/s') "
                "define stream S (k long);")
            hd = m.health()
            assert set(hd) == {"ra", "rb"}
            assert hd["ra"]["admission"] is None
            assert hd["rb"]["admission"]["rate_per_s"] == 5.0
        finally:
            m.shutdown()
