"""Ingest-side pipelining: double-buffered H2D staging differentials.

Every device engine now routes its host→device transfers through
``core/ingest_stage.py``: batch conversion, ``staged_put`` and the
jitted step dispatch happen at receive time, but the blocking count-gate
fetch (and the emit enqueue it gates) defers behind a bounded staging
window (``@app:execution('tpu', ingest.depth='N')``).  With depth 2 the
count fetch for batch N runs only after batch N+1's H2D transfer and
step dispatch are already queued — transfer and compute overlap.

These tests pin the exactness contract differentially: the same app and
series at synchronous ingest (depth 1, the default) vs a staged window
must produce identical callbacks on the device-single, dense, and
sharded paths — including under ``transient`` faults on the
``ingest.put`` site and across a simulated crash + journal replay — and
assert the IngestStats evidence that staging actually happened
(``staged_batches``, ``max_staging_depth``, overlap/stall counters,
barrier ``flush_syncs``).  ``emit.depth='auto'`` rides along: the
controller's effective depth must track rtt/cadence and never exceed
its bound, with output still bit-identical to host.

Left alone (no ``ingest.depth``) the stage chooses the window itself
(``PipelineRule``): the rule is held to its promises on an injected
clock, and the fault and crash differentials run a second time with the
rule forced on (``pipelined`` fixture) in place of the pinned depth 2.
"""

import threading

import numpy as np
import pytest
from loop_clock import LoopClock

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.dense_pattern import DensePatternRuntime
from siddhi_tpu.core.device_single import DeviceQueryRuntime
from siddhi_tpu.core.emit_queue import EmitDepthController
from siddhi_tpu.core.exceptions import (
    SiddhiAppCreationError,
    SimulatedCrashError,
)
from siddhi_tpu.core.ingest_stage import (
    BLOCKED_MIN_S,
    ENGAGE_RUN,
    IdleFinisher,
    IngestStage,
    THINK_SHARE,
)
from siddhi_tpu.util.persistence import InMemoryPersistenceStore

pytestmark = pytest.mark.faults

DEFINE = "define stream S (k long, v double); "
FILTER_APP = DEFINE + ("from S[v > 20.0] select k, v, v * 2.0 as dbl "
                       "insert into OutputStream;")
AGG_APP = DEFINE + ("@info(name='q') from S#window.length(4) "
                    "select k, sum(v) as s group by k "
                    "insert into OutputStream;")
PATTERN_APP = DEFINE + (
    "@info(name='q') from every e1=S[v > 50.0] -> e2=S[v > e1.v] "
    "within 10 sec select e1.v as a, e2.v as b insert into OutputStream;")

# engine -> (@app:execution tail WITHOUT ingest.depth, body)
ENGINES = {
    "device_single": ("", AGG_APP),
    "dense_nfa": (", instances='32'", PATTERN_APP),
    "sharded": (", partitions='16', devices='8'", AGG_APP),
}


# how the staged cases open the window: pinned by the annotation, or
# left to the rule with the rule forced on
WINDOWS = {"depth2": ", ingest.depth='2'", "rule": ""}


@pytest.fixture(params=sorted(WINDOWS))
def window(request, force_pipelined):
    """The @app:execution tail that opens the staging window."""
    if request.param == "rule":
        force_pipelined()
    return WINDOWS[request.param]


def series(n, seed, n_keys=4, t0=1000, dt_max=400):
    rng = np.random.default_rng(seed)
    ts = t0 + np.cumsum(rng.integers(1, dt_max, size=n))
    keys = rng.integers(0, n_keys, size=n)
    vals = rng.integers(1, 100, size=n).astype(float)
    return [([int(k), float(v)], int(t)) for k, v, t in zip(keys, vals, ts)]


def run_app(app, sends, out="OutputStream", exec_opts=None,
            faults=None, want_runtime=False):
    """Playback run -> list of data tuples.  ``exec_opts`` is the option
    tail of @app:execution('tpu'...), e.g. ", ingest.depth='2'"; None
    runs the host engine.  ``faults`` is an @app:faults option string.
    want_runtime additionally returns (device_runtime, app_runtime)."""
    header = "@app:playback "
    if faults is not None:
        header += f"@app:faults({faults}) "
    if exec_opts is not None:
        header += f"@app:execution('tpu'{exec_opts}) "
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + app)
        got = []
        rt.add_callback(out, lambda evs: got.extend(tuple(e.data)
                                                    for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        qr = next(iter(rt.query_runtimes.values()))
        runtime = (getattr(qr, "device_runtime", None)
                   or getattr(qr, "pattern_processor", None))
        rt.shutdown()
        if want_runtime:
            return got, runtime, rt
        return got
    finally:
        m.shutdown()


def staged_differential(app, sends, out="OutputStream", extra="", depth=2,
                        ordered=True):
    """host == sync ingest == staged ingest; returns the staged runtime."""
    host = run_app(app, sends, out=out)
    sync, rt1, _ = run_app(app, sends, out=out, exec_opts=extra,
                           want_runtime=True)
    staged, rtS, _ = run_app(app, sends, out=out,
                             exec_opts=f"{extra}, ingest.depth='{depth}'",
                             want_runtime=True)
    assert rt1 is not None, "query did not lower to a device engine"
    assert rt1.ingest_stage.depth == 1
    assert rtS.ingest_stage.depth == depth
    assert len(rtS.ingest_stage) == 0, "shutdown left staged batches behind"
    if not ordered:
        host, sync, staged = sorted(host), sorted(sync), sorted(staged)
    assert sync == host, "synchronous-ingest device path diverged from host"
    assert staged == host, "staged ingest changed callback content/order"
    return rtS


class TestStagedIngestDifferential:
    def test_device_single_filter(self):
        rt = staged_differential(FILTER_APP, series(120, seed=21))
        assert isinstance(rt, DeviceQueryRuntime)
        st = rt.ingest_stats
        assert st.staged_batches > 0
        assert st.max_staging_depth == 2
        assert st.device_puts > 0
        # overlap evidence: every non-barrier finish happened with the
        # NEXT batch already dispatched — each one is either an overlap
        # (count scalar already resident) or a stall (host blocked)
        assert st.overlapped_batches + st.ingest_stalls > 0
        # shutdown drains through the stage: the last in-flight batch
        # finishes under a flush barrier
        assert st.flush_syncs > 0

    def test_device_single_grouped_window(self):
        rt = staged_differential(AGG_APP, series(150, seed=22, n_keys=5))
        assert isinstance(rt, DeviceQueryRuntime)
        assert rt.ingest_stats.staged_batches > 0

    def test_staging_composes_with_deep_emit(self):
        sends = series(160, seed=23)
        host = run_app(FILTER_APP, sends)
        got, rt, _ = run_app(
            FILTER_APP, sends,
            exec_opts=", ingest.depth='3', emit.depth='4'",
            want_runtime=True)
        assert got == host
        assert rt.ingest_stats.max_staging_depth == 3
        assert rt.emit_stats.deferred_batches > 0

    def test_dense_pattern_staged(self):
        rt = staged_differential(PATTERN_APP, series(120, seed=24),
                                 extra=", instances='32'")
        assert isinstance(rt, DensePatternRuntime)
        st = rt.ingest_stats
        assert st.staged_batches > 0
        assert st.device_puts > 0
        assert st.overlapped_batches + st.ingest_stalls > 0

    def test_sharded_staged(self):
        # windowless running aggregation: the one kind the planner
        # shards over the device mesh
        app = DEFINE + ("from S select k, sum(v) as s group by k "
                        "insert into OutputStream;")
        rt = staged_differential(app, series(200, seed=25, n_keys=8),
                                 extra=", partitions='16', devices='8'")
        assert isinstance(rt, DeviceQueryRuntime)
        assert rt.engine.n_shards == 8
        st = rt.ingest_stats
        assert st.staged_batches > 0
        # every dispatched batch went through the shared staged_put
        # (one coalesced pytree put per dispatch)
        assert st.device_puts >= st.staged_batches

    def test_timer_fire_barrier_staged(self):
        # timeBatch panes close on timer fires — the fire() path must
        # flush the ingest stage before the emit drain or pane contents
        # shift by up to depth-1 batches
        app = DEFINE + ("from S#window.timeBatch(1 sec) select k, "
                        "sum(v) as s group by k insert into OutputStream;")
        staged_differential(app, series(150, seed=26), depth=3,
                            ordered=False)


class TestIngestFlushBarriers:
    def test_snapshot_midstream_is_a_barrier(self):
        m = SiddhiManager()
        try:
            m.set_persistence_store(InMemoryPersistenceStore())
            rt = m.create_siddhi_app_runtime(
                "@app:playback @app:execution('tpu', ingest.depth='4') "
                + FILTER_APP)
            got = []
            rt.add_callback("OutputStream",
                            lambda evs: got.extend(tuple(e.data)
                                                   for e in evs))
            rt.start()
            h = rt.get_input_handler("S")
            for i in range(3):
                h.send([i, 50.0], timestamp=1000 + i)
            drt = next(iter(rt.query_runtimes.values())).device_runtime
            # window depth 4: all three batches still staged, no emits
            assert len(drt.ingest_stage) == 3
            assert got == []
            rt.persist()  # snapshot barrier: flush stage, drain emits
            assert len(drt.ingest_stage) == 0
            assert drt.ingest_stats.flush_syncs >= 3
            assert got == [(i, 50.0, 100.0) for i in range(3)]
            rt.shutdown()
        finally:
            m.shutdown()


class TestIngestFaultDifferential:
    @pytest.mark.parametrize("engine", sorted(ENGINES))
    def test_transient_ingest_put_recovered_staged(self, engine, window):
        extra, body = ENGINES[engine]
        sends = series(40, seed=31, n_keys=4)
        clean, stage_rt, _ = run_app(body, sends,
                                     exec_opts=f"{extra}{window}",
                                     want_runtime=True)
        assert stage_rt.ingest_stats.max_staging_depth == 2
        assert clean == run_app(body, sends,
                                exec_opts=f"{extra}, ingest.depth='1'")
        chaotic, _, rt = run_app(
            body, sends, exec_opts=f"{extra}{window}",
            faults=("transfer.retry.scale='0.0001', "
                    "ingest.put='transient:count=2'"),
            want_runtime=True)
        assert chaotic == clean, (
            f"{engine}: retried ingest puts must not lose or dup rows")
        fi = rt.app_context.fault_injector
        assert fi.stats.faults_injected == 2
        assert fi.stats.transfer_retries == 2
        assert fi.stats.drains_recovered >= 1
        assert fi.stats.drains_failed == 0

    @pytest.mark.parametrize("engine", sorted(ENGINES))
    def test_crash_recovery_staged_bit_identical(self, engine, window):
        """Crash mid-stream with batches in the staging window: the
        journal replay on a fresh runtime must reproduce the exact
        uninterrupted sequence (staged ingest defers only EMISSION —
        journal + checkpoint semantics are untouched)."""
        extra, body = ENGINES[engine]
        exec_opts = f"{extra}{window}"
        sends = series(30, seed=32, n_keys=3)
        ref = run_app(body, sends, exec_opts=exec_opts)
        assert len(ref) > 4, "series too tame; differential is vacuous"
        assert ref == run_app(body, sends,
                              exec_opts=f"{extra}, ingest.depth='1'")

        header = ("@app:name('ingestcrash') @app:playback "
                  "@app:faults(journal='256') "
                  f"@app:execution('tpu'{exec_opts}) ")
        m = SiddhiManager()
        try:
            m.set_persistence_store(InMemoryPersistenceStore())
            rt = m.create_siddhi_app_runtime(header + body)
            got = []
            rt.add_callback("OutputStream",
                            lambda evs: got.extend(tuple(e.data)
                                                   for e in evs))
            rt.start()
            h = rt.get_input_handler("S")
            for row, ts in sends[:10]:
                h.send(list(row), timestamp=ts)
            rt.persist()
            for row, ts in sends[10:20]:
                h.send(list(row), timestamp=ts)
            rt.app_context.fault_injector.configure("ingest", "crash",
                                                    count=1)
            with pytest.raises(SimulatedCrashError):
                h.send(list(sends[20][0]), timestamp=sends[20][1])
            rt.shutdown()  # the crashed runtime is gone

            rt2 = m.create_siddhi_app_runtime(header + body)
            rt2.add_callback("OutputStream",
                             lambda evs: got.extend(tuple(e.data)
                                                    for e in evs))
            rt2.start()
            assert rt2.restore_last_revision() is not None
            h2 = rt2.get_input_handler("S")
            # the crashed send WAS journaled (crash fires after the
            # record), so replay already delivered it — continue after
            for row, ts in sends[21:]:
                h2.send(list(row), timestamp=ts)
            rt2.shutdown()
            assert got == ref, (
                f"{engine}: crash+recover with staged ingest diverged "
                "from the uninterrupted run")
        finally:
            m.shutdown()


# -- the rule, on an injected clock -------------------------------------------


class Watcher:
    """What a stage needs of the app's idle finisher, with no thread."""

    def __init__(self):
        self.watched = []

    def watch(self, stage):
        self.watched.append(stage)


class Driven:
    """A stage driven batch by batch: the caller thinks, the host
    prepares, the device steps; an inline gate keeps the host for what
    is left of the step, a deferred one for what the next batch's
    preparation did not cover."""

    HOST_S = 0.002

    def __init__(self, depth=None):
        self.clock = LoopClock()
        self.finisher = Watcher()
        self.stage = IngestStage(depth, finisher=self.finisher,
                                 clock=self.clock)
        self.finished = []      # batches in the order their gates resolved
        self.n = 0

    def batch(self, think_s, step_s):
        n, clock = self.n, self.clock
        self.n += 1
        clock.arrives(self.stage.arrive, think_s, self.HOST_S)
        done_at = clock.dispatched(step_s)

        def finish():
            self.finished.append(n)
            return clock.resolved(done_at)

        self.stage.submit(None, finish)
        return self.stage.depth

    def stream(self, n, think_s, step_s):
        return [self.batch(think_s, step_s) for _ in range(n)]


class TestPipelineRule:
    STEP = 3 * BLOCKED_MIN_S

    # (gate, think) of the closed loops the rule is for: the pattern
    # cells' 6.7-12 ms, then what the v5e shows on the window cells and
    # the fused chain (2.5 ms, back in 0.3) and on the table join (1.9)
    @pytest.mark.parametrize("step_s, think_s", [
        (3 * BLOCKED_MIN_S, 3 * BLOCKED_MIN_S / 20),
        (2.5e-3, 0.3e-3),
        (1.9e-3, 0.3e-3),
    ], ids=["long_gate", "gate_2.5ms", "gate_1.9ms"])
    def test_engages_after_the_run_and_not_before(self, step_s, think_s):
        d = Driven()
        depths = d.stream(ENGAGE_RUN + 4, think_s=think_s, step_s=step_s)
        # batch 0 has no think; ENGAGE_RUN arrivals qualify after it
        assert depths == [1] * ENGAGE_RUN + [2] * 4
        st = d.stage.stats
        assert (st.pipeline_entries, st.pipeline_exits) == (1, 0)
        assert st.auto_depth == 2 and st.max_staging_depth == 2
        # one batch in flight, its gate finished by the next submit
        assert len(d.stage) == 1
        assert d.finished == list(range(d.n - 1))
        assert st.gates_by_submit == 3
        assert d.finisher.watched == [d.stage]

    @pytest.mark.parametrize("think_s, step_s", [
        (40 * BLOCKED_MIN_S, 3 * BLOCKED_MIN_S),   # a slow cadence
        (3 * BLOCKED_MIN_S, 3 * BLOCKED_MIN_S),    # back as late as the wait
        (0.0, BLOCKED_MIN_S / 2),                  # a wait not worth hiding
        (0.0, 0.9 * BLOCKED_MIN_S),                # ... just under the floor
        (0.0, 0.0),                                # no wait at all
        # a paced source, a hundred gates between its batches: at the
        # paced cell's gate, at a window cell's, at the flagship's
        (100 * 1.2e-3, 1.2e-3),
        (100 * 2.5e-3, 2.5e-3),
        (100 * 12e-3, 12e-3),
    ], ids=["slow_cadence", "think_equals_wait", "short_wait",
            "under_the_floor", "no_wait", "paced_1.2ms", "paced_2.5ms",
            "paced_12ms"])
    def test_never_engages(self, think_s, step_s):
        d = Driven()
        assert set(d.stream(200, think_s, step_s)) == {1}
        st = d.stage.stats
        assert (st.pipeline_entries, st.gates_by_submit) == (0, 0)
        assert d.finished == list(range(200)) and len(d.stage) == 0
        assert d.finisher.watched == []

    def test_a_run_broken_once_starts_anew(self):
        d = Driven()
        d.stream(ENGAGE_RUN, think_s=0.0, step_s=self.STEP)
        assert d.batch(think_s=self.STEP, step_s=self.STEP) == 1
        assert set(d.stream(ENGAGE_RUN - 1, 0.0, self.STEP)) == {1}
        assert d.batch(0.0, self.STEP) == 2

    def test_leaves_on_one_long_gap_staged_batch_first(self):
        d = Driven()
        d.stream(ENGAGE_RUN + 3, think_s=0.0, step_s=self.STEP)
        assert d.stage.depth == 2 and len(d.stage) == 1
        staged = d.n - 1
        assert d.batch(think_s=2 * self.STEP, step_s=self.STEP) == 1
        # the staged batch's gate, then the arriving batch's, inline
        assert d.finished[-2:] == [staged, staged + 1]
        assert len(d.stage) == 0
        assert d.stage.stats.pipeline_exits == 1
        # and the way back in is a whole run again
        assert d.stream(ENGAGE_RUN, 0.0, self.STEP) == (
            [1] * (ENGAGE_RUN - 1) + [2])

    @pytest.mark.parametrize("step_s", [3 * BLOCKED_MIN_S, 2.5e-3],
                             ids=["long_gate", "gate_2.5ms"])
    @pytest.mark.parametrize("engaged", [False, True])
    def test_a_gap_between_the_thresholds_changes_nothing(self, engaged,
                                                          step_s):
        """Hysteresis: in needs a think under ``THINK_SHARE`` of the
        wait, out one over the whole of it; between them the regime
        stays, so a think that hovers cannot make the stage flap."""
        d = Driven()
        if engaged:
            d.stream(ENGAGE_RUN + 1, 0.0, step_s)
        want = 2 if engaged else 1
        for i in range(100):
            think = step_s * (THINK_SHARE + 0.05 if i % 2 else 0.95)
            assert d.batch(think, step_s) == want
        st = d.stage.stats
        assert st.pipeline_entries == int(engaged) and st.pipeline_exits == 0

    def test_a_leave_once_a_pass_comes_back_after_the_run(self):
        """The table join's loop: nineteen probe batches come straight
        back, then the sender is away for a whole upsert batch.  The
        stage leaves at that arrival (the staged batch first), is back
        after the run, and hides the rest of the pass."""
        d, step, per_pass = Driven(), 1.9e-3, 20
        d.stream(ENGAGE_RUN + 1, 0.3e-3, step)
        for p in range(1, 6):
            assert d.batch(think_s=12.7e-3, step_s=step) == 1
            depths = d.stream(per_pass - 2, 0.3e-3, step)
            assert depths == [1] * (ENGAGE_RUN - 1) + [2] * (
                per_pass - 1 - ENGAGE_RUN)
            st = d.stage.stats
            assert (st.pipeline_entries, st.pipeline_exits) == (p + 1, p)
        assert d.finished == list(range(d.n - 1)) and len(d.stage) == 1

    def test_a_barrier_returns_the_stage_to_inline(self):
        d = Driven()
        d.stream(ENGAGE_RUN + 2, 0.0, self.STEP)
        assert d.stage.depth == 2 and len(d.stage) == 1
        d.stage.flush()
        st = d.stage.stats
        assert (d.stage.depth, len(d.stage)) == (1, 0)
        assert (st.flush_syncs, st.pipeline_exits, st.auto_depth) == (1, 1, 1)
        assert d.finished == list(range(d.n))
        assert d.stage.idle_wait() is None
        assert d.batch(0.0, self.STEP) == 1

    def test_a_hidden_gate_is_a_short_wait(self):
        """In the regime the host pays its own work a batch, not its
        work plus the step: what the rule is for."""
        d = Driven()
        d.stream(ENGAGE_RUN + 1, 0.0, self.STEP)
        t0 = d.clock.t
        d.stream(50, 0.0, self.STEP)
        per_batch = (d.clock.t - t0) / 50
        assert per_batch == pytest.approx(self.STEP, rel=1e-6)
        assert per_batch < d.HOST_S + self.STEP

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_a_pinned_depth_knows_no_rule(self, depth):
        d = Driven(depth)
        assert set(d.stream(40, 0.0, self.STEP)) == {depth}
        st = d.stage.stats
        assert (st.pipeline_entries, st.auto_depth) == (0, 0)
        assert len(d.stage) == depth - 1
        assert d.finisher.watched == [] and d.stage.idle_wait() is None

    def test_without_a_finisher_the_window_stays_shut(self):
        """A staged gate never waits for an arrival that does not come:
        no finisher (a runtime built by hand), no deferral."""
        d = Driven()
        d.stage.finisher = None
        assert set(d.stream(3 * ENGAGE_RUN, 0.0, self.STEP)) == {1}
        assert d.stage.stats.pipeline_entries == 0 and len(d.stage) == 0

    def test_auto_means_the_rule(self):
        assert IngestStage("auto").rule is not None
        assert IngestStage(None).rule is not None
        assert IngestStage(2).rule is None


class TestOutsideTheLock:
    def test_an_async_junctions_worker_never_defers(self, force_pipelined):
        """The worker of an ``@async`` junction holds no ``process_lock``
        (a sender blocked on its full queue may): its submits finish
        inline whatever the rule says, its stage never meets the
        finisher, and its drains take no lock."""
        force_pipelined()
        sends = series(60, seed=51)
        host = run_app(FILTER_APP, sends)
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:playback @app:execution('tpu') "
                "@async(buffer.size='64', batch.size.max='4') "
                + FILTER_APP)
            got = []
            rt.add_callback("OutputStream",
                            lambda evs: got.extend(tuple(e.data)
                                                   for e in evs))
            rt.start()
            h = rt.get_input_handler("S")
            for row, ts in sends:
                h.send(row, timestamp=ts)
            drt = next(iter(rt.query_runtimes.values())).device_runtime
            served = threading.Event()
            for _ in range(2000):       # the worker serves its queue
                if len(got) >= len(host):
                    break
                served.wait(0.005)
            rt.shutdown()
            st = drt.ingest_stats
            assert got == host
            assert st.staged_batches > 0
            assert (st.pipeline_entries, st.gates_by_submit,
                    st.gates_by_idle, st.max_staging_depth) == (0, 0, 0, 1)
            assert not rt.app_context.idle_finisher.alive()
        finally:
            m.shutdown()


class TestIdleFinisher:
    """The thread alone, on stages whose clock is the real one."""

    def staged(self, finisher, done, grace_s=0.01):
        stage = IngestStage(2, finisher=finisher)
        stage.submit(None, lambda: done.append(len(done)))
        # what a submit under the rule leaves behind it
        stage._idle_mark = (stage._seq, stage.clock() + grace_s, None)
        finisher.watch(stage)
        return stage

    def test_finishes_what_nothing_came_for_and_stops(self):
        fin, done = IdleFinisher(), []
        stage = self.staged(fin, done)
        assert fin.alive()
        deadline = stage.clock() + 10.0
        while not done and stage.clock() < deadline:
            threading.Event().wait(0.005)
        assert done == [0] and len(stage) == 0
        assert stage.stats.gates_by_idle == 1
        assert stage.idle_wait() is None
        fin.stop()
        assert not fin.alive()

    def test_never_started_without_a_staged_batch(self):
        fin = IdleFinisher()
        assert not fin.alive()
        fin.stop()
        assert not fin.alive()

    def test_a_held_lock_holds_it_off_and_stop_does_not_hang(self):
        fin, done = IdleFinisher(), []
        with fin.lock():
            stage = self.staged(fin, done, grace_s=0.0)
            threading.Event().wait(0.2)
            assert done == [] and len(stage) == 1
            fin.stop(timeout=10.0)   # holding the lock it wants
            assert not fin.alive()
        assert done == []
        stage.flush()
        assert done == [0]

    def test_a_barrier_from_another_thread_wins_once(self):
        fin = IdleFinisher()
        for _round in range(20):
            done = []
            stage = self.staged(fin, done, grace_s=0.002)

            def barrier():
                with fin.lock():
                    stage.flush()

            t = threading.Thread(target=barrier)
            threading.Event().wait(0.002)
            t.start()
            t.join(10.0)
            assert not t.is_alive()
            deadline = stage.clock() + 10.0
            while not done and stage.clock() < deadline:
                threading.Event().wait(0.001)
            assert done == [0], "finished once, by one of the two"
            st = stage.stats
            assert st.gates_by_idle + st.flush_syncs == 1
        fin.stop()
        assert not fin.alive()


class TestAutoEmitDepth:
    def test_controller_converges_to_rtt_over_cadence(self):
        # deterministic: injected timestamps, constant cadence and rtt
        c = EmitDepthController()
        t = 0.0
        for _ in range(50):
            c.note_push(t)
            t += 0.001
            c.note_drain(0.0042)
        assert c.effective_depth == 5  # ceil(4.2ms rtt / 1ms gap)

    def test_controller_never_exceeds_bound(self):
        c = EmitDepthController()
        c.note_push(0.0)
        c.note_push(0.001)
        c.note_drain(60.0)  # pathological rtt: clamp, don't grow
        assert c.effective_depth == EmitDepthController.AUTO_DEPTH_MAX

    def test_controller_floors_at_sync(self):
        c = EmitDepthController()
        c.note_push(0.0)
        c.note_push(10.0)  # slow cadence, instant fetch -> depth 1
        c.note_drain(0.0001)
        assert c.effective_depth == 1

    def test_auto_depth_runtime_differential(self):
        sends = series(150, seed=41)
        host = run_app(FILTER_APP, sends)
        auto, rt, _ = run_app(FILTER_APP, sends,
                              exec_opts=", emit.depth='auto'",
                              want_runtime=True)
        assert auto == host, "auto emit depth changed callback content"
        assert rt.emit_queue.controller is not None
        assert 1 <= rt.emit_queue.depth <= EmitDepthController.AUTO_DEPTH_MAX
        assert rt.emit_stats.auto_depth >= 1  # controller engaged
        # the bounded-queue contract: auto can never grow the pending
        # window past its own ceiling
        assert (rt.emit_stats.max_pending_depth
                <= EmitDepthController.AUTO_DEPTH_MAX)

    def test_auto_depth_with_staged_ingest(self):
        sends = series(150, seed=42, n_keys=5)
        host = run_app(AGG_APP, sends)
        got, rt, _ = run_app(
            AGG_APP, sends,
            exec_opts=", ingest.depth='2', emit.depth='auto'",
            want_runtime=True)
        assert got == host
        assert rt.ingest_stats.staged_batches > 0
        assert rt.emit_queue.controller is not None
        assert (rt.emit_stats.max_pending_depth
                <= EmitDepthController.AUTO_DEPTH_MAX)


class TestAnnotationValidation:
    @pytest.mark.parametrize("opt", ["ingest.depth='0'",
                                     "ingest.depth='-2'",
                                     "ingest.depth='fast'",
                                     "agg.device.min.batch='0'",
                                     "agg.device.min.batch='many'",
                                     "emit.depth='turbo'"])
    def test_bad_values_rejected_at_build(self, opt):
        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError,
                               match="must be a positive integer"):
                m.create_siddhi_app_runtime(
                    f"@app:execution('tpu', {opt}) " + FILTER_APP)
        finally:
            m.shutdown()

    def test_statistics_expose_ingest_counters(self):
        app = ("@app:name('ingestApp') @app:statistics('true') "
               "@app:playback @app:execution('tpu', ingest.depth='2') "
               + DEFINE +
               "@info(name='q') from S[v > 50.0] select k, v "
               "insert into OutputStream;")
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(app)
            rt.start()
            h = rt.get_input_handler("S")
            for i, v in enumerate([60.0, 70.0, 10.0, 80.0]):
                h.send([i, v], timestamp=1000 + i)
            stats = rt.statistics()
            pre = "io.siddhi.SiddhiApps.ingestApp.Siddhi.Queries.q."
            assert stats[pre + "stagedBatches"] == 4
            assert stats[pre + "devicePuts"] >= 1
            assert stats[pre + "maxStagingDepth"] == 2
            assert (stats[pre + "overlappedBatches"]
                    + stats[pre + "ingestStalls"]) >= 1
            rt.shutdown()
        finally:
            m.shutdown()

    def test_statistics_expose_the_rules_counters(self, force_pipelined):
        """Who finished the gates, and how often the window opened, ride
        ``statistics()`` beside ``overlappedBatches`` / ``ingestStalls``."""
        force_pipelined(idle=False)
        app = ("@app:name('ruleApp') @app:statistics('true') "
               "@app:playback @app:execution('tpu') " + DEFINE +
               "@info(name='q') from S[v > 50.0] select k, v "
               "insert into OutputStream;")
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(app)
            got = []
            rt.add_callback("OutputStream", got.extend)
            rt.start()
            h = rt.get_input_handler("S")
            for i, v in enumerate([60.0, 70.0, 10.0, 80.0]):
                h.send([i, v], timestamp=1000 + i)
            pre = "io.siddhi.SiddhiApps.ruleApp.Siddhi.Queries.q."
            stats = rt.statistics()
            assert len(got) == 2, "the third gate skips, the fourth is staged"
            assert stats[pre + "stagedBatches"] == 4
            assert stats[pre + "gatesBySubmit"] == 3
            assert stats[pre + "gatesByIdle"] == 0
            assert stats[pre + "flushSyncs"] == 0
            assert stats[pre + "pipelineEntries"] == 1
            assert stats[pre + "pipelineExits"] == 0
            assert stats[pre + "autoIngestDepth"] == 2
            assert stats[pre + "maxStagingDepth"] == 2
            assert (stats[pre + "overlappedBatches"]
                    + stats[pre + "ingestStalls"]) == 3
            rt.drain_device_emits()     # a barrier
            stats = rt.statistics()
            assert len(got) == 3
            assert stats[pre + "flushSyncs"] == 1
            assert stats[pre + "pipelineExits"] == 1
            assert stats[pre + "autoIngestDepth"] == 1
            rt.shutdown()
        finally:
            m.shutdown()

