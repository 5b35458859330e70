"""The device pipeline's contract, over every runtime that holds one.

``core/device_pipeline.py`` owns the path from "step dispatched" to
"rows at the callback" for the five device runtimes — window
(core/device_single.py), dense (core/dense_pattern.py), fused
(core/fused_graph.py), hot-key (core/hotkey_router.py) and devtable
(devtable/join.py).  Each case below drives one of them through
``SiddhiManager`` on a small app and holds it to the same four
promises:

- a failing count gate drops that batch and nothing else: it is counted
  as ``droppedBatches``, reaches the exception listener, and the runtime
  goes on serving;
- ``drain()`` finishes staged batches before it drains emits, so the
  callbacks at ``ingest.depth='2'``, ``emit.depth='4'`` equal those at
  depth 1, in order;
- a sampled cycle holds one ``step_wait`` and one ``step`` span;
- an exception that leaves the shell's own work closes the cycle's
  token as raised.

And, with no ``ingest.depth`` and the stage's rule forced on
(``force_pipelined`` of conftest.py), to what holds of a batch left in
flight behind the next dispatch: the same callbacks in the same order
whoever finishes its gate (the next submit, a barrier, the idle
finisher with or without a ``drain()`` racing it from another thread);
a failing deferred gate drops that batch only; its ``step`` span starts
at its ``resolve()``; the counters say who finished what; ``shutdown()``
leaves no thread behind.

And, on the window, the fused chain and the table join, to the rule
itself (nothing forced: ``ChipModel`` gives the stage's clock the
timing the v5e shows on those cells, a gate of 2.5 ms and a sender back
in 0.3): a closed loop of twenty batches and more engages, and its rows
are those of ``ingest.depth='1'``, in order; on the join, whose loop
holds an upsert batch, every probe answers from the table as the last
upsert sent before it left it.

And to the early copies (``DevicePipeline._start_copies``): a step's
counts, then the emit arrays of the chunk positions whose last batch
owed rows and has kept foretelling the next, start for the host inside
``submit``, before the gate is resolved; the drain then concatenates
nothing; the rows are those of the same app with the copies off
(``no_early_copies`` of conftest.py); a stream that matches nothing
starts no array; a copy that was wasted doubles the run of right
forecasts the position has to show, a copy that was used takes one off;
and ``statistics()`` says how often.
"""

import sys
import threading
import time

import numpy as np
import pytest
from loop_clock import LoopClock, PacedClock

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.device_pipeline import DevicePipeline
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.observability import trace as trace_mod


def _batch(attrs, cols, i):
    n = len(next(iter(cols.values())))
    return EventBatch("S", attrs, cols,
                      np.full(n, 1_000 + i * 10, dtype=np.int64))


# (``quiet``: a batch that owes no row — every value under the filters,
# every probed key absent from the table)


def window_batch(i, n=16, quiet=False):
    rng = np.random.default_rng(10 + i)
    v = rng.uniform(0.0, 20.0, n).astype(np.float32)
    return _batch(["k", "v"], {
        "k": (np.arange(n) % 4).astype(np.int32),
        "v": v - 30.0 if quiet else v}, i)


def keyed_batch(i, n=32, quiet=False):
    rng = np.random.default_rng(20 + i)
    # 24 keys over 32 rows: eight keys come twice in every batch
    return _batch(["k", "v"], {
        "k": np.arange(n, dtype=np.int64) % 24,
        "v": rng.uniform(0.0, 20.0, n) * (not quiet)}, i)


def skewed_batch(i, n=40, quiet=False):
    rng = np.random.default_rng(30 + i)
    # key 7 takes four rows in five: promoted by the second batch
    k = np.where(np.arange(n) % 5 < 4, 7, np.arange(n) % 24)
    return _batch(["k", "v"], {
        "k": k.astype(np.int64),
        "v": rng.uniform(0.0, 20.0, n) * (not quiet)}, i)


def probe_batch(i, n=16, quiet=False):
    rng = np.random.default_rng(40 + i)
    return _batch(["k", "x"], {
        # keys 8..11 miss
        "k": (np.arange(n) % 12 + 100 * quiet).astype(np.int32),
        "x": rng.uniform(0.0, 20.0, n).astype(np.float32)}, i)


def fill_table(rt):
    rt.get_input_handler("Ins").send_batch(EventBatch(
        "Ins", ["k", "v"],
        {"k": np.arange(8, dtype=np.int32),
         "v": np.arange(8, dtype=np.float32) * 1.5},
        np.full(8, 900, dtype=np.int64)))
    # the insert query is a device runtime of its own: at ingest.depth 2
    # its rows reach the table at its next batch or a barrier
    rt.drain_device_emits()


PATTERN = ("define stream S (k long, v double); partition with (k of S) "
           "begin @info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
           "select b.v as bv insert into Out; end;")

# kind -> (execution options, other annotations, body, batch maker,
#          set-up after start, engine kind of its spans, lowering,
#          where the shell's own work is made to raise)
CASES = {
    "window": (
        "", "",
        "define stream S (k int, v float); @info(name='q') "
        "from S[v >= 0.0]#window.length(4) select k, sum(v) as s "
        "insert into Out;",
        window_batch, None, "device", "device",
        lambda shell: (shell.engine, "process_batch_deferred")),
    "dense": (
        "partitions='64'", "", PATTERN, keyed_batch, None, "dense", "dense",
        lambda shell: (shell.engine, "process_deferred")),
    "fused": (
        "", "@app:fuse ",
        "define stream S (k int, v float); "
        "@info(name='q1') from S[v > 4.0] select k, v insert into Mid; "
        "@info(name='q') from Mid[v > 8.0] select k, v insert into Out;",
        window_batch, None, "fused", "fused",
        lambda shell: (shell.graph, "process_batch_deferred")),
    "hotkey": (
        "partitions='64', instances='16'",
        "@app:hotkeys(k='4', promote='0.3', demote='0.1') ",
        PATTERN, skewed_batch, None, "hotkey", "hotkey",
        lambda shell: (shell._scan, "pack_cycle")),
    "devtable": (
        "", "@app:devtables(capacity='64') ",
        "define stream S (k int, x float); "
        "define stream Ins (k int, v float); "
        "@PrimaryKey('k') define table T (k int, v float); "
        "from Ins insert into T; "
        "define stream Ups (k int, v float); "
        "@info(name='ups') from Ups select k, v "
        "update or insert into T on T.k == k; "
        "@info(name='q') from S join T as t on S.k == t.k "
        "select S.k as k, S.x as x, t.v as v insert into Out;",
        probe_batch, fill_table, "devtable_join", "devtable",
        lambda shell: (shell, "_event_keys")),
}
KINDS = list(CASES)
N_BATCHES = 6
# the reference of every case: the window pinned at 1
INLINE = "ingest.depth='1'"


class Deployed:
    """One case's app, started, with its rows, its listener and its
    runtime shell at hand."""

    def __init__(self, kind, depths="", trace="", paced=False):
        (opts, extra, body, self.make, setup, self.engine_kind, lowering,
         self.breaks) = CASES[kind]
        opts = ", ".join(o for o in (opts, depths) if o)
        self.manager = SiddhiManager()
        self.rt = rt = self.manager.create_siddhi_app_runtime(
            f"@app:name('pipe_{kind}') @app:playback "
            f"@app:execution('tpu'{', ' + opts if opts else ''}) "
            + extra + trace + body)
        self.rows, self.errors = [], []
        rt.add_callback("Out", lambda evs: self.rows.extend(
            (e.timestamp, tuple(e.data)) for e in evs))
        rt.add_exception_listener(self.errors.append)
        rt.start()
        assert rt.lowering()["q"] == lowering, rt.lowering()
        if setup is not None:
            setup(rt)
        self.handler = rt.get_input_handler("S")
        queries = dict(rt.query_runtimes)
        for pr in rt.partitions.values():
            queries.update(getattr(pr, "dense_query_runtimes", {}))
        self.shell = (getattr(queries["q"], "device_runtime", None)
                      or queries["q"].pattern_processor)
        self.pipe = self.shell.pipeline
        assert isinstance(self.pipe, DevicePipeline)
        if paced:
            # a case that holds a batch's rows to its own ``send_batch``
            # runs under the rule, with a sender the rule never admits:
            # left to the host's clock, nine arrivals whose gates a busy
            # host stretched past the floor would leave one in flight
            # (the hot-key shell submits twice a batch)
            self.pipe.ingest_stage.clock = PacedClock()

    def send(self, i, quiet=False):
        """Batch ``i``; returns the rows it delivered at once."""
        before = len(self.rows)
        self.handler.send_batch(self.make(i, quiet=quiet))
        return self.rows[before:]

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.manager.shutdown()
        return False


def reference(kind):
    """Rows per batch at depth 1, where every batch delivers inline."""
    with Deployed(kind, depths=INLINE) as app:
        per_batch = [app.send(i) for i in range(N_BATCHES)]
        if kind == "hotkey":
            assert app.shell.hot_stats.routed_cycles > 0
    assert all(per_batch[2:]), "every batch past warm-up owes rows"
    return per_batch


class _Gate:
    """A step's ``pending`` passed through, for a case to change what
    its ``resolve()`` does."""

    def __init__(self, pending):
        self.pending = pending

    def probe(self):
        return self.pending.probe()

    def resolve(self):
        return self.pending.resolve()

    def gates(self):
        return self.pending.gates()

    def device_arrays(self):
        return self.pending.device_arrays()


class _BrokenGate(_Gate):
    """A pending whose count-gate fetch fails, as XLA reports an
    asynchronous step failure."""

    def resolve(self):
        raise RuntimeError("injected count-gate failure")


@pytest.mark.parametrize("kind", KINDS)
def test_failing_count_gate_drops_one_batch(kind):
    want = reference(kind)
    with Deployed(kind, paced=True) as app:
        got = [app.send(i) for i in range(3)]
        real, broken = app.pipe.submit, []

        def breaking(tok, pending, build, emit):
            broken.append(pending)
            real(tok, _BrokenGate(pending), build, emit)

        app.pipe.submit = breaking
        got.append(app.send(3))
        del app.pipe.submit
        got += [app.send(i) for i in range(4, N_BATCHES)]
        assert broken, "the batch never reached the pipeline"
        assert got[3] == [] and want[3]
        assert got[:3] + got[4:] == want[:3] + want[4:]
        assert app.pipe.ingest_stats.dropped_batches == len(broken)
        assert app.shell.ingest_stats is app.pipe.ingest_stats
        assert [str(e) for e in app.errors] == [
            "injected count-gate failure"] * len(broken)
        assert len(app.pipe.ingest_stage) == 0
        assert len(app.pipe.emit_queue) == 0


@pytest.mark.parametrize("window", ["depth2", "rule"])
@pytest.mark.parametrize("kind", KINDS)
def test_drain_finishes_staged_batches_before_emits(kind, window,
                                                    force_pipelined):
    want = [r for rows in reference(kind) for r in rows]
    depths = "emit.depth='4'"
    if window == "rule":
        force_pipelined(idle=False)
    else:
        depths = "ingest.depth='2', " + depths
    with Deployed(kind, depths=depths) as app:
        for i in range(N_BATCHES):
            app.send(i)
        stage, queue = app.pipe.ingest_stage, app.pipe.emit_queue
        assert stage.depth == 2 and queue.depth == 4
        assert len(stage) == 1, "the last batch's count gate is staged"
        assert len(app.rows) < len(want)
        app.shell.drain()
        assert len(stage) == 0 and len(queue) == 0
        assert app.rows == want
        assert app.pipe.ingest_stats.dropped_batches == 0 and not app.errors


def idle_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("ingest-idle-")]


def wait_for(cond, seconds=20.0):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.002)
    return cond()


@pytest.mark.parametrize("racing_drain", [False, True],
                         ids=["alone", "racing_drain"])
@pytest.mark.parametrize("kind", KINDS)
def test_burst_ends_and_its_last_batch_is_delivered(kind, racing_drain,
                                                    force_pipelined):
    """No further send, no barrier: the idle finisher hands the last
    batch of a burst to the callback, in order, once; a bare ``drain()``
    from another thread all through the burst changes nothing."""
    want = [r for rows in reference(kind) for r in rows]
    force_pipelined()
    with Deployed(kind) as app:
        if CASES[kind][4] is None:   # (a set-up sends a batch of its own)
            assert not idle_threads(), "a thread before a batch was staged"
        stop, raised = threading.Event(), []

        def drains():
            while not stop.is_set():
                try:
                    app.shell.drain()   # bare: the client holds no lock
                except Exception as e:  # noqa: BLE001 — the test's verdict
                    raised.append(e)
                    return

        interval = sys.getswitchinterval()
        racer = threading.Thread(target=drains)
        try:
            app.send(0)     # the stage has left a batch in flight:
            if racing_drain:    # from here on every drain takes the lock
                sys.setswitchinterval(1e-5)
                racer.start()
            for i in range(1, N_BATCHES):
                app.send(i)
            stop.set()
            if racing_drain:
                racer.join(20.0)
                assert not racer.is_alive()
        finally:
            stop.set()
            sys.setswitchinterval(interval)
        assert wait_for(lambda: len(app.rows) >= len(want)), (
            len(app.rows), len(want))
        assert wait_for(lambda: len(app.pipe.ingest_stage) == 0)
        with app.rt.app_context.process_lock:   # a finish in progress ends
            assert app.rows == want and not raised and not app.errors
        st = app.pipe.ingest_stats
        assert st.dropped_batches == 0
        assert st.pipeline_entries >= 1
        assert st.pipeline_exits in (st.pipeline_entries,
                                     st.pipeline_entries - 1)
        if not racing_drain:
            assert st.flush_syncs == 0
            assert st.gates_by_idle >= 1
            # nothing arrived after the last batch: the stage is inline
            assert wait_for(lambda: app.pipe.ingest_stage.depth == 1)
        assert st.gates_by_submit + st.gates_by_idle + st.flush_syncs >= 1
        assert len(idle_threads()) == 1
        finisher = app.rt.app_context.idle_finisher
        assert finisher.alive()
        app.rt.shutdown()
        assert not finisher.alive() and not idle_threads()
        assert app.rows == want


@pytest.mark.parametrize("kind", KINDS)
def test_inline_app_never_starts_the_finisher(kind):
    """A sender that is away between its batches never opens the
    window, whatever their gates took, and the app has no thread."""
    with Deployed(kind, paced=True) as app:
        for i in range(N_BATCHES):
            app.send(i)
        st = app.pipe.ingest_stats
        assert (st.pipeline_entries, st.gates_by_submit, st.gates_by_idle,
                st.max_staging_depth) == (0, 0, 0, 1)
        assert st.as_dict()["autoIngestDepth"] == 1
        assert not app.rt.app_context.idle_finisher.alive()
        assert not idle_threads()


@pytest.mark.parametrize("kind", KINDS)
def test_failing_deferred_gate_drops_one_batch(kind, force_pipelined):
    per_batch = reference(kind)
    want = [r for i, rows in enumerate(per_batch) if i != 3 for r in rows]
    force_pipelined(idle=False)
    with Deployed(kind) as app:
        for i in range(3):
            app.send(i)
        real, broken = app.pipe.submit, []

        def breaking(tok, pending, build, emit):
            broken.append(pending)
            real(tok, _BrokenGate(pending), build, emit)

        app.pipe.submit = breaking
        app.send(3)
        del app.pipe.submit
        if kind != "hotkey":    # (submits twice a batch: cold rows, hot)
            assert not app.errors, "the gate is staged: nothing failed yet"
        for i in range(4, N_BATCHES):
            app.send(i)
        app.shell.drain()
        assert broken and per_batch[3]
        assert app.rows == want
        assert app.pipe.ingest_stats.dropped_batches == len(broken)
        assert [str(e) for e in app.errors] == [
            "injected count-gate failure"] * len(broken)
        assert len(app.pipe.ingest_stage) == 0
        assert len(app.pipe.emit_queue) == 0


@pytest.mark.parametrize("kind", KINDS)
def test_deferred_step_span_starts_at_its_resolve(kind, force_pipelined):
    """``step`` is the host blocked on the cycle's count gate: from the
    dispatch when the gate is finished inline, from the start of its
    ``resolve()``, after the NEXT cycle's dispatch, when it was left
    staged."""
    force_pipelined(idle=False)
    with Deployed(kind, trace="@app:trace(sample='1', cycles='32') ") as app:
        for i in range(N_BATCHES):
            app.send(i)
        app.shell.drain()
        # every cycle that went through this stage, in submit order (the
        # hot-key shell's cold rows are cycles of kind 'dense')
        groups = {cid: {s[1]: s for s in spans}
                  for cid, spans in app.rt.app_context.tracer.recorder
                  .cycle_groups().items()}
        on_stage = {app.engine_kind} | ({"dense"} if kind == "hotkey"
                                        else set())
        ours = [g for _cid, g in sorted(groups.items())
                if "step" in g and g["step"][2] in on_stage]
        assert any(g["step"][2] == app.engine_kind for g in ours)
        assert len(ours) >= N_BATCHES - 1
        st = app.pipe.ingest_stats
        assert st.gates_by_submit >= N_BATCHES - 2 and st.flush_syncs >= 1
        inline = deferred = 0
        for cur, nxt in zip(ours, ours[1:]):
            ingest, step = cur["ingest"], cur["step"]
            if step[3] == ingest[4]:
                inline += 1
                continue
            deferred += 1
            # left staged: fetched after the next cycle's dispatch
            assert step[3] >= nxt["ingest"][4] > ingest[4]
        # the first arrival has no think and is finished inline
        assert inline <= 1 and deferred >= N_BATCHES - 2


@pytest.mark.parametrize("kind", KINDS)
def test_sampled_cycle_holds_one_step_wait_and_one_step(kind, monkeypatch):
    waits = []
    real = trace_mod.annotation

    def counting(stage):
        if stage == trace_mod.ANNOTATION_STEP_WAIT:
            waits.append(stage)
        return real(stage)

    with Deployed(kind, trace="@app:trace(sample='1', cycles='16') ") as app:
        for i in range(3):
            app.send(i)
        recorder = app.rt.app_context.tracer.recorder
        seen = set(recorder.cycle_groups())
        monkeypatch.setattr(trace_mod, "annotation", counting)
        assert app.send(3)
        monkeypatch.undo()
        # (a table probe is a free-running span under a cycle id of its
        # own: not a batch cycle)
        cycles = {cid: spans for cid, spans in recorder.cycle_groups().items()
                  if cid not in seen
                  and {s[1] for s in spans} != {"table.probe"}}
        ours = [spans for spans in cycles.values()
                if {s[2] for s in spans} == {app.engine_kind}]
        assert ours, {s[2] for spans in cycles.values() for s in spans}
        for spans in cycles.values():
            assert [s[1] for s in spans].count("step") == 1
            assert [s[1] for s in spans].count("ingest") == 1
        assert len(waits) == len(cycles)


@pytest.mark.parametrize("kind", KINDS)
def test_exception_in_the_shell_closes_the_token_as_raised(kind, monkeypatch):
    want = reference(kind)
    with Deployed(kind, trace="@app:trace(sample='1', cycles='16') ") as app:
        got = [app.send(i) for i in range(3)]
        owner, name = app.breaks(app.shell)

        def raising(*a, **kw):
            raise ValueError("injected conversion failure")

        monkeypatch.setattr(owner, name, raising)
        app.send(3)
        monkeypatch.undo()
        # the junction handed the error to the listeners; the cycle is
        # closed where it died and is no longer the thread's open one
        assert [str(e) for e in app.errors] == ["injected conversion failure"]
        assert getattr(trace_mod._open, "tok", None) is None
        recorder = app.rt.app_context.tracer.recorder
        dead = recorder.spans()[-1]
        assert dead[1] == "ingest.aborted" and dead[2] == app.engine_kind
        assert dead[0] == max(s[0] for s in recorder.spans())
        with trace_mod.span("put", 8) as sp:
            assert sp is None
        if kind in ("fused", "devtable"):
            # stateless per batch: the runtime serves on as if batch 3
            # had never come
            got += [app.send(i) for i in range(4, N_BATCHES)]
            assert got == want[:3] + want[4:]
        else:
            assert app.send(4), "the runtime serves on"


# -- the rule itself, on the chip's timing ------------------------------------


class _TimedGate(_Gate):
    """A pending whose step is done at ``done_at`` on the model's
    clock: resolving it earlier keeps the host until then."""

    def __init__(self, pending, clock, done_at):
        super().__init__(pending)
        self.clock, self.done_at = clock, done_at

    def resolve(self):
        self.clock.resolved(self.done_at)
        return self.pending.resolve()


class ChipModel:
    """The timing of a closed loop on the chip, on the injected clock of
    one pipeline's stage (the steps themselves run on the CPU as they
    are).  The window cells and the fused chain on the v5e: a gate of
    2.5 ms, a sender back in 0.3 (PERF.md, PR 50)."""

    def __init__(self, pipe, think_s=0.3e-3, host_s=1.0e-3, step_s=2.5e-3):
        self.clock = clock = LoopClock()
        self.think_s = think_s
        self.away_s = 0.0       # the sender's next gap is longer by this
        stage, submit = pipe.ingest_stage, pipe.submit
        arrive = stage.arrive
        stage.clock = clock

        def arrives():
            clock.arrives(arrive, self.think_s + self.away_s, host_s)
            self.away_s = 0.0

        def submits(tok, pending, build, emit):
            if pending is not None:
                pending = _TimedGate(pending, clock,
                                     clock.dispatched(step_s))
            submit(tok, pending, build, emit)

        stage.arrive, pipe.submit = arrives, submits


def upsert_batch(j, n=6):
    """Upsert batch ``j``: six of the table's keys, two of them new the
    first time, every value one that names the batch."""
    k = (np.arange(n) * 2 + j) % 12
    return EventBatch("Ups", ["k", "v"], {
        "k": k.astype(np.int32),
        "v": (1000.0 * (j + 1) + k).astype(np.float32)},
        np.full(n, 950 + j, dtype=np.int64))


LOOP = 36           # batches of the closed loop, an upsert every twelfth


def closed_loop(app, kind, model=None):
    """The loop's rows, and on the join the table's ``v`` of every key
    as the upserts sent so far have left it, probe batch by probe
    batch."""
    table, seen = {k: k * 1.5 for k in range(8)}, []
    ups = (app.rt.get_input_handler("Ups") if kind == "devtable" else None)
    for i in range(LOOP):
        if ups is not None and i % 12 == 11:
            b = upsert_batch(i // 12)
            ups.send_batch(b)
            table.update(zip(b.columns["k"].tolist(),
                             b.columns["v"].tolist()))
            if model is not None:
                model.away_s = 12.7e-3      # what an upsert batch takes
        else:
            app.send(i)
            seen.append(dict(table))
    app.shell.drain()
    return list(app.rows), seen


@pytest.mark.parametrize("kind", ["window", "fused", "devtable"])
def test_a_closed_loop_engages_by_the_rule_and_keeps_its_rows(kind):
    with Deployed(kind, depths=INLINE) as app:
        want, _ = closed_loop(app, kind)
        assert app.pipe.ingest_stats.max_staging_depth == 1
    assert want
    with Deployed(kind) as app:
        got, seen = closed_loop(app, kind, ChipModel(app.pipe))
        st = app.pipe.ingest_stats
        assert st.pipeline_entries >= 1 and st.gates_by_submit >= 1
        assert st.max_staging_depth == 2, "never more than one in flight"
        assert st.dropped_batches == 0 and not app.errors
        assert len(app.pipe.ingest_stage) == 0
        assert got == want
        if kind != "devtable":
            return
        # the upsert query's own stage is the planner's: inline
        ups = app.rt.query_runtimes["ups"].device_runtime.ingest_stats
        assert (ups.pipeline_entries, ups.max_staging_depth) == (0, 1)
        # the sender's absence closed the window, the run reopened it
        assert st.pipeline_entries == 3 and st.pipeline_exits == 3
        # every probe row carries its key's value as the upserts SENT
        # before that probe left it: none missed, none from a later one
        probes = [i for i in range(LOOP) if i % 12 != 11]
        by_ts = {1_000 + i * 10: table for i, table in zip(probes, seen)}
        assert len(got) > 10 * len(probes)
        for ts, (k, _x, v) in got:
            assert v == pytest.approx(by_ts[ts][k]), (ts, k)


def test_a_table_writer_stays_inline_whatever_its_gates():
    """A bulk load in a closed loop whose every gate is one the rule
    admits (the chip's timing on the WRITER's stage: 2.5 ms, back in
    0.3), then a probe from the other stream straight after the last
    upsert, no barrier between: the planner pinned the writer's stage
    because its rows end in a table, so the probe answers from the
    table as the last upsert left it."""
    with Deployed("devtable") as app:
        writer = app.rt.query_runtimes["ups"].device_runtime
        stage, st = writer.ingest_stage, writer.ingest_stats
        assert stage.rule is None and st.as_dict()["autoIngestDepth"] == 0
        ChipModel(writer.pipeline)
        ups = app.rt.get_input_handler("Ups")
        table = {k: k * 1.5 for k in range(8)}
        for j in range(20):
            b = upsert_batch(j)
            ups.send_batch(b)
            table.update(zip(b.columns["k"].tolist(),
                             b.columns["v"].tolist()))
        got = app.send(0)
        assert (st.staged_batches, st.pipeline_entries,
                st.max_staging_depth, len(stage)) == (20, 0, 1, 0)
        assert len(got) == 16 and not app.errors
        for _ts, (k, _x, v) in got:
            assert v == pytest.approx(table[k]), k
        # the probe query, whose rows go to a stream nothing reads into
        # a table, keeps its rule
        assert app.pipe.ingest_stage.rule is not None


@pytest.mark.parametrize("body, pinned", [
    # a table's writer, and the query whose stream feeds it
    ("define table T (k int, v double); "
     "@info(name='a') from S#window.length(4) select k, sum(v) as v "
     "insert into Mid; "
     "@info(name='b') from Mid#window.length(2) select k, v insert into T;",
     {"a": True, "b": True}),
    # a named window's writer; a stream that only a callback reads
    ("define window W (k int, v double) length(4); "
     "@info(name='a') from S#window.length(4) select k, sum(v) as v "
     "insert into W; "
     "@info(name='b') from S#window.length(2) select k, sum(v) as v "
     "insert into Out;",
     {"a": True, "b": False}),
    # the app's own pin is the app's
    ("define table T (k int, v double); "
     "@info(name='a') from S#window.length(4) select k, sum(v) as v "
     "insert into T;",
     {"a": 2}),
], ids=["table_through_a_stream", "named_window", "pinned_by_the_app"])
def test_the_planner_pins_the_stage_of_a_query_that_writes_state(
        body, pinned):
    m = SiddhiManager()
    try:
        depth = "ingest.depth='2'" if 2 in pinned.values() else ""
        rt = m.create_siddhi_app_runtime(
            f"@app:execution('tpu'{', ' + depth if depth else ''}) "
            "define stream S (k int, v double); " + body)
        for name, want in pinned.items():
            stage = rt.query_runtimes[name].device_runtime.ingest_stage
            if want is True:
                assert stage.rule is None and stage.depth == 1, name
            elif want is False:
                assert stage.rule is not None, name
            else:
                assert stage.rule is None and stage.depth == want, name
    finally:
        m.shutdown()


# -- the early copies ---------------------------------------------------------


class CopyLog:
    """Every ``copy_to_host_async`` made outside a ``device_get`` (which
    starts its own), every ``jax.device_get`` and every device
    concatenation, in call order."""

    def __init__(self, monkeypatch):
        import jax
        import jax.numpy as jnp
        from jax._src.array import ArrayImpl

        self.calls = calls = []
        real_copy = ArrayImpl.copy_to_host_async
        real_get, real_cat = jax.device_get, jnp.concatenate

        getting = []

        def copy(arr):
            if not getting:
                calls.append(("copy", id(arr), arr.shape))
            return real_copy(arr)

        def get(tree):
            calls.append(("get", None, None))
            getting.append(1)
            try:
                return real_get(tree)
            finally:
                getting.pop()

        def cat(arrays, *a, **kw):
            calls.append(("concatenate", None, None))
            return real_cat(arrays, *a, **kw)

        monkeypatch.setattr(ArrayImpl, "copy_to_host_async", copy)
        monkeypatch.setattr(jax, "device_get", get)
        monkeypatch.setattr(jnp, "concatenate", cat)

    def before_first_get(self, since=0):
        """The copies started before anything was fetched."""
        out = []
        for what, ident, shape in self.calls[since:]:
            if what == "get":
                break
            if what == "copy":
                out.append((ident, shape))
        return out


def submitted(app):
    """Spy on ``submit``: every pending's gates as they stood at its
    dispatch, and every entry the pipeline pushed for the drain."""
    seen, pushed = [], []
    real_submit, real_push = app.pipe.submit, app.pipe.emit_queue.push

    def submit(tok, pending, build, emit):
        if pending is not None:
            seen.append(pending.gates())
        real_submit(tok, pending, build, emit)

    def push(entry):
        pushed.append((list(entry.arrays), entry.started))
        real_push(entry)

    app.pipe.submit = submit
    app.pipe.emit_queue.push = push
    return seen, pushed


@pytest.mark.parametrize("kind", KINDS)
def test_copies_start_inside_submit_counts_first(kind, monkeypatch):
    """Once a batch has owed rows, the next one's counts and then its
    emit arrays start for the host inside ``submit``, before the gate
    is resolved, and its drain dispatches no concatenation."""
    with Deployed(kind, paced=True) as app:
        for i in range(4):
            assert app.send(i) or i < 2
        log = CopyLog(monkeypatch)
        seen, pushed = submitted(app)
        assert app.send(4)
        gates = seen[-1]    # (the hot-key shell: the hot rows' submit)
        counts = [id(c) for c, _arrays in gates]
        arrays = [id(a) for _c, arrs in gates for a in arrs]
        # the last submit's copies: from its first count on
        at = next(n for n, call in enumerate(log.calls)
                  if call[:2] == ("copy", counts[0]))
        early = log.before_first_get(at)
        assert [ident for ident, _shape in early[:len(counts)]] == counts
        assert all(shape == () for _ident, shape in early[:len(counts)])
        started = [ident for ident, _shape in early[len(counts):]]
        assert started and set(started) <= set(arrays)
        # in the order the drain will hand them on
        assert started == [a for a in arrays if a in set(started)]
        kept, ids = pushed[-1]
        assert ids and ids <= set(started)
        if len(gates) == 1:
            # one chunk: every array of the drain was started
            assert ids == {id(a) for a in kept} == set(arrays)
            tail = log.calls[at:]
            assert ("concatenate", None, None) not in tail
        st = app.pipe.emit_stats
        assert st.early_copy_batches >= 1 and st.early_copy_hits >= 1


@pytest.mark.parametrize("kind", KINDS)
def test_no_concatenation_once_every_array_is_started(kind, monkeypatch,
                                                      request):
    """A drain of two batches whose arrays were all started fetches them
    as they are; the twin with the copies off concatenates every group
    of like arrays, as before.  A drain of one batch concatenates
    nothing either way."""
    def concatenations(off, depths):
        if off:
            request.getfixturevalue("no_early_copies")
        with Deployed(kind, depths=depths) as app:
            for i in range(4):
                app.send(i)
            app.shell.drain()
            with monkeypatch.context() as mp:
                log = CopyLog(mp)
                seen, pushed = submitted(app)
                for i in range(4, 8):
                    app.send(i)
                app.shell.drain()
            assert all((ids == {id(a) for a in kept}) is not off
                       for kept, ids in pushed[1:] if len(kept) > 1)
            return sum(1 for c in log.calls if c[0] == "concatenate")

    deep = "emit.depth='2'"
    on = concatenations(False, deep)
    # (hot-key, dense: a drain's two entries are not always alike, and
    # the first batch after the barrier may be fetched on demand)
    assert on <= 1
    assert concatenations(False, "") == 0
    # (the fixture holds to the end of the test: the twins come last)
    # (the hot-key shell's two entries a drain, cold rows and hot, hold
    # no two arrays alike)
    assert concatenations(True, deep) > on or kind == "hotkey"
    assert concatenations(True, "") == 0


@pytest.mark.parametrize("kind", KINDS)
def test_rows_are_those_of_the_app_without_early_copies(kind, request):
    on = reference(kind)
    with Deployed(kind) as app:
        for i in range(N_BATCHES):
            app.send(i)
        assert app.pipe.emit_stats.early_copy_hits >= N_BATCHES - 4
    request.getfixturevalue("no_early_copies")
    off = reference(kind)
    assert on == off
    with Deployed(kind) as app:
        for i in range(N_BATCHES):
            app.send(i)
        st = app.pipe.emit_stats
        assert (st.early_copy_batches, st.early_copy_hits,
                st.early_copy_wasted_bytes) == (0, 0, 0)


@pytest.mark.parametrize("kind", KINDS)
def test_a_stream_that_matches_nothing_starts_no_array(kind, monkeypatch):
    with Deployed(kind) as app:
        log = CopyLog(monkeypatch)
        seen, pushed = submitted(app)
        for i in range(N_BATCHES):
            assert app.send(i, quiet=True) == []
        app.shell.drain()
        assert len(seen) >= N_BATCHES and not app.rows
        # the counts travel (the gate needs them); no column does
        copies = [shape for what, _id, shape in log.calls if what == "copy"]
        assert copies and set(copies) == {()}
        st = app.pipe.emit_stats
        assert st.zero_match_skips == len(seen)
        assert (st.early_copy_batches, st.early_copy_hits,
                st.early_copy_wasted_bytes) == (0, 0, 0)
        # (a promotion fetches the key's dense row through the queue)
        assert st.emit_transfers == (1 if kind == "hotkey" else 0)


@pytest.mark.parametrize("kind", KINDS)
def test_a_wasted_copy_doubles_the_run_a_position_has_to_show(kind):
    """The forecast is "owed rows last time, will owe rows now"; the
    arrays start once it has come true ``need`` times running.  The
    quiet batch's arrays were started and never read: ``need`` goes from
    1 to 2, so the first matching batch after it is fetched on demand
    (no forecast), the next two as well (the forecast right once, then
    twice), and the fourth is started early again and takes ``need``
    back to 1."""
    want = reference(kind)
    with Deployed(kind, paced=True) as app:
        st = app.pipe.emit_stats

        def counters():
            return (st.early_copy_batches, st.early_copy_hits,
                    st.early_copy_wasted_bytes, st.zero_match_skips)

        got = [app.send(i) for i in range(5)]
        assert got == want[:5]
        b0, h0, w0, z0 = counters()
        assert b0 >= 1 and h0 >= 1
        assert app.send(9, quiet=True) == []
        b1, h1, w1, z1 = counters()
        assert b1 > b0 and h1 == h0 and w1 > w0 and z1 > z0
        for i in (10, 11, 12):
            assert app.send(i)
            assert counters()[:3] == (b1, h1, w1), i
        assert app.send(13)
        b2, h2, w2, _z = counters()
        assert b2 > b1 and h2 > h1 and w2 == w1
        # one record a chunk position of each kind of pending
        records = [pos for seen in app.pipe._positions.values()
                   for pos in seen]
        assert records and all(pos.need in (1, 2) for pos in records)
        assert any(pos.right >= 3 for pos in records)


@pytest.mark.parametrize("kind", KINDS)
def test_early_copy_counters_reach_statistics(kind):
    with Deployed(kind, trace="@app:statistics('true') ") as app:
        for i in range(5):
            app.send(i)
        app.send(9, quiet=True)
        stats = app.rt.statistics()
        st = app.pipe.emit_stats
        for name, value in (("earlyCopyBatches", st.early_copy_batches),
                            ("earlyCopyHits", st.early_copy_hits),
                            ("earlyCopyWastedBytes",
                             st.early_copy_wasted_bytes)):
            (key,) = [k for k in stats if k.endswith(f".q.{name}")]
            assert stats[key] == value > 0, (key, stats[key], value)
        assert st.early_copy_hits < st.early_copy_batches


# -- the quarantine, on the pipeline alone ------------------------------------


class _Poisoner:
    """The two calls the quarantine makes of an ``@app:faults``
    injector: the site is watched, and the second batch trips it."""

    def __init__(self, trips):
        self.trips = list(trips)
        self.stats = type("S", (), {"poison_quarantines": 0})()

    def watches(self, site):
        return site == "state.poison"

    def poisoned(self, site):
        return self.trips.pop(0)


def _chain(scale):
    import jax.numpy as jnp

    return ({"acc": jnp.arange(4, dtype=jnp.float32) * scale,
             "n": jnp.arange(4, dtype=jnp.int32)},
            {"ring": jnp.ones((2, 3), dtype=jnp.float32) * scale})


@pytest.mark.parametrize("shape", ["dict", "tuple_of_dicts", "put_back"])
def test_quarantine_puts_the_last_clean_state_back(shape):
    """The shells' states: one dict of arrays (window, hot-key scan), a
    tuple of them (fused chain), or whatever a ``put_back`` places (the
    sharded engine's ``put_state``)."""
    import jax

    class Ctx:
        fault_injector = _Poisoner([False, True, True])

    pipe = DevicePipeline(Ctx(), "unit")
    make = (lambda s: _chain(s)[0]) if shape == "dict" else _chain
    placed = []
    put_back = None
    if shape == "put_back":
        def put_back(host):
            placed.append(host)
            return make(1.0)

    def fresh():
        return make(0.0)

    clean, poisoned = pipe.quarantine(make(1.0), fresh, put_back)
    assert not poisoned and Ctx.fault_injector.stats.poison_quarantines == 0
    state, poisoned = pipe.quarantine(make(2.0), fresh, put_back)
    assert poisoned and Ctx.fault_injector.stats.poison_quarantines == 1
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(clean))
    for got, want in zip(jax.tree_util.tree_leaves(state),
                         jax.tree_util.tree_leaves(clean)):
        assert isinstance(got, jax.Array) and got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert len(placed) == (1 if shape == "put_back" else 0)
    # a restore replaced the state: nothing clean to go back to
    pipe.forget_clean_copy()
    state, poisoned = pipe.quarantine(make(3.0), fresh, put_back)
    assert poisoned and Ctx.fault_injector.stats.poison_quarantines == 2
    assert float(np.asarray(jax.tree_util.tree_leaves(state)[0]).sum()) == 0.0
