"""A batch's way back in the program's own record.

Every case runs on an injected ``tracer.clock`` that advances by one at
each reading, so that "adjacent" is an equality and no case holds a
wall time to a limit: ``fetch``, ``build`` and ``deliver`` tile ``emit``
on every served path; a gate left staged is a ``Stages.staged`` sample
and no tuple in the ring; the overflow poll is ``poll``, under its own
name on the profiler's clock too; a cycle's whole life reaches
``Stages.cycle`` and ``/metrics``; and the drain's open cycle survives
a callback that sends.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import dense_pattern
from siddhi_tpu.core.emit_queue import EmitQueue, PendingEmit
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.observability import trace as trace_mod
from siddhi_tpu.observability.prometheus import (
    app_histogram_entries,
    render_prometheus,
)
from siddhi_tpu.observability.trace import Tracer

from test_observability import (
    PARTITIONED_BODY,
    WINDOW_BODY,
    keyed_batch,
    window_batch,
)

PANE_BODY = (
    "define stream S (symbol string, price float, volume int); "
    "@info(name='q') from S#window.lengthBatch(10) select symbol, "
    "sum(price) as total, avg(volume) as av group by symbol "
    "insert into Out;")

# path -> (execution options, body, batch maker, engine kind)
PATHS = {
    "dense": ("partitions='64'", PARTITIONED_BODY, keyed_batch, "dense"),
    "sliding": ("", WINDOW_BODY % "", window_batch, "device"),
    "pane": ("", PANE_BODY, window_batch, "device"),
    "sharded": ("partitions='64', devices='4'", PARTITIONED_BODY,
                keyed_batch, "shard"),
}


class Ticks:
    """A clock that advances by one at every reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class App:
    """One traced app on the ticking clock."""

    def __init__(self, path, sample="1", extra=""):
        opts, body, self.make, self.kind = PATHS[path]
        self.manager = SiddhiManager()
        self.rt = self.manager.create_siddhi_app_runtime(
            f"@app:name('back_{path}') @app:playback "
            f"@app:execution('tpu'{', ' + opts if opts else ''}) "
            f"@app:trace(sample='{sample}', cycles='64') " + extra + body)
        self.tracer = self.rt.app_context.tracer
        self.tracer.clock = Ticks()
        self.rows = []
        self.rt.add_callback("Out", self.rows.extend)
        self.rt.start()
        self.handler = self.rt.get_input_handler("S")

    def send(self, i):
        self.handler.send_batch(self.make(i))

    def cycles(self):
        """cycle id -> {stage: [span, ...]}, in ring order."""
        out = {}
        for cid, spans in self.tracer.recorder.cycle_groups().items():
            by = out[cid] = {}
            for s in spans:
                by.setdefault(s[1], []).append(s)
        return out

    def shell(self):
        queries = dict(self.rt.query_runtimes)
        for pr in self.rt.partitions.values():
            queries.update(getattr(pr, "dense_query_runtimes", {}))
        return (getattr(queries["q"], "device_runtime", None)
                or queries["q"].pattern_processor)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.manager.shutdown()
        return False


@pytest.mark.parametrize("path", list(PATHS))
def test_fetch_build_deliver_tile_emit(path, copies):
    """Three siblings in this order, none overlapping another, nothing
    between them but the clock's own readings: ``fetch`` starts where
    ``emit`` does, ``build`` at the next reading after ``fetch`` ends,
    ``deliver`` at the next after ``build``, and ``emit`` ends at the
    next after ``deliver``.  So with the arrays' copies started at the
    dispatch as with every fetch on demand, and ``fetch`` counts the
    bytes of the arrays its entry kept either way."""
    with App(path) as app:
        queue = app.shell().emit_queue
        kept, push = {}, queue.push

        def pushing(entry):
            kept[entry.trace.cycle] = sum(a.nbytes for a in entry.arrays)
            push(entry)

        queue.push = pushing
        for i in range(6):
            app.send(i)
        assert app.rows
        st = app.shell().emit_stats
        assert (st.early_copy_hits >= 2) is (copies == "early")
        cycles = app.cycles()
        assert kept and all(cycles[cid]["fetch"][0][5] == nbytes
                            for cid, nbytes in kept.items())
        emitted = [by for by in cycles.values() if "emit" in by]
        assert len(emitted) >= 3
        for by in emitted:
            (emit,), (fetch,) = by["emit"], by["fetch"]
            (build,), (deliver,) = by["build"], by["deliver"]
            assert {s[2] for s in (emit, fetch, build, deliver)} == {
                app.kind}
            assert fetch[3] == emit[3] and fetch[4] > fetch[3]
            assert build[3] == fetch[4] + 1
            assert deliver[3] == build[4] + 1
            assert emit[4] == deliver[4] + 1
            # each counts what it handles: bytes, then the batch's rows
            assert fetch[5] > 0
            assert build[5] == deliver[5] == emit[5] > 0
        delivered = sum(by["deliver"][0][5] for by in emitted)
        assert delivered == len(app.rows)
        # the three are histograms and stage statistics like the others
        stats = app.tracer.stage_stats()
        assert (stats["build"]["spans"] == stats["deliver"]["spans"]
                == stats["fetch"]["spans"] == len(emitted))


def test_a_staged_gate_is_a_histogram_sample_and_no_ring_tuple(
        force_pipelined):
    """``Stages.staged`` counts exactly the gates that were finished not
    inline; the ring holds no tuple for them, and by cycle id ``step``
    starts after ``ingest`` ends for those gates."""
    force_pipelined(idle=False)
    with App("dense") as app:
        for i in range(6):
            app.send(i)
        app.shell().drain()
        st = app.shell().ingest_stats
        not_inline = st.gates_by_submit + st.flush_syncs + st.gates_by_idle
        assert st.gates_by_submit >= 4 and st.flush_syncs == 1
        staged = app.tracer.stage_hist[trace_mod.STAGE_STAGED]
        assert staged.count == not_inline
        cycles = app.cycles()
        stages = {stage for by in cycles.values() for stage in by}
        assert not stages & {trace_mod.STAGE_STAGED, trace_mod.STAGE_CYCLE}
        waits = [by["step"][0][3] - by["ingest"][0][4]
                 for by in cycles.values() if "step" in by]
        # (the forced rule leaves every gate staged: the last one falls
        # to the barrier; the inline gate is the next test's)
        assert len(waits) == 6
        assert sum(1 for w in waits if w > 0) == not_inline
        # the histogram holds the very intervals the ring yields
        assert staged.sum_ms == pytest.approx(1e3 * sum(waits))
        assert app.tracer.stage_stats()["staged"]["spans"] == not_inline


def test_an_inline_gate_is_no_staged_sample():
    """... and at the end of ``ingest`` for a gate finished inline."""
    with App("dense") as app:
        for i in range(4):
            app.send(i)
        assert app.tracer.stage_hist[trace_mod.STAGE_STAGED].count == 0
        assert "staged" not in app.tracer.stage_stats()
        for by in app.cycles().values():
            assert by["step"][0][3] == by["ingest"][0][4]


def test_the_overflow_poll_is_a_span_of_its_own(monkeypatch):
    """On the ``_OVF_POLL``-th step the poll is a ``poll`` span inside
    ``ingest`` and a ``siddhi.poll`` annotation; ``siddhi.step_wait`` is
    made once a cycle, round the count gate, and not round the poll."""
    monkeypatch.setattr(dense_pattern.DensePatternRuntime, "_OVF_POLL", 2)
    made = []
    real = trace_mod.annotation

    def counting(stage):
        made.append(stage)
        return real(stage)

    monkeypatch.setattr(trace_mod, "annotation", counting)
    with App("dense") as app:
        for i in range(4):
            app.send(i)
        cycles = list(app.cycles().values())
        assert len(cycles) == 4
        assert ["poll" in by for by in cycles] == [False, True, False, True]
        for by in cycles:
            for poll in by.get("poll", []):
                (ingest,) = by["ingest"]
                assert ingest[3] < poll[3] < poll[4] < ingest[4]
                assert poll[5] == 1
                # after the dispatches it waits for, a sibling of theirs
                assert all(d[4] < poll[3] for d in by["dispatch"])
        assert made.count(trace_mod.STAGE_POLL) == 2
        assert made.count(trace_mod.ANNOTATION_STEP_WAIT) == 4
        assert app.tracer.stage_stats()["poll"]["spans"] == 2


def test_a_cycles_life_reaches_statistics_and_prometheus():
    """``Stages.cycle``: from ``begin_cycle`` (ahead of the interning on
    the partitioned path, where ``ingest`` starts later) to the end of
    ``emit``, one sample a cycle that emitted, in ``statistics()`` and
    in the Prometheus exposition."""
    with App("dense") as app:
        for i in range(5):
            app.send(i)
        emitted = [by for by in app.cycles().values() if "emit" in by]
        hist = app.tracer.stage_hist[trace_mod.STAGE_CYCLE]
        assert hist.count == len(emitted) >= 3
        # the ring yields the same interval, joined by cycle id: the
        # cycle's first span is its ``admit`` (PR 55), the send's lead
        # ahead of the cycle, which ends at ``begin_cycle``'s reading:
        # a cycle's life counts from there as it did
        assert all(by["admit"][0][3] == min(s[3] for spans in by.values()
                                            for s in spans)
                   for by in emitted)
        lives = [by["emit"][0][4] - by["admit"][0][4] for by in emitted]
        assert all(by["intern"][0][3] < by["ingest"][0][3] for by in emitted)
        assert hist.sum_ms == pytest.approx(1e3 * sum(lives))
        stats = app.rt.statistics()
        (key,) = [k for k in stats if k.endswith("Stages.cycle.spans")]
        assert stats[key] == len(emitted)
        sm = app.rt.app_context.statistics_manager
        body = render_prometheus([(
            "back_dense", stats, app_histogram_entries("back_dense", sm))])
        for stage in ("cycle", "build", "deliver", "fetch"):
            assert (f'siddhi_stage_duration_ms_count{{app="back_dense",'
                    f'stage="{stage}"}} {len(emitted)}') in body


def test_the_drain_opens_each_entrys_cycle_and_restores_what_it_found():
    """A callback may send: its ``begin_cycle`` takes the thread's open
    cycle and its ``dispatched`` clears it.  The drain opens the next
    entry's own cycle all the same, an unsampled entry's spans land
    nowhere, and when the drain ends the cycle it found open is open
    again."""
    t = Tracer("app", sample=1)
    t.clock = Ticks()
    queue = EmitQueue(depth=8)
    inner = []

    def gated(n_emit):
        tok = t.begin_cycle("dense", 4)
        tok.dispatched()
        tok.step_done(n_emit)
        return tok

    def sends(seg):
        with trace_mod.span("build", 2):
            pass
        with trace_mod.span("deliver", 2):
            tok = t.begin_cycle("dense", 4)     # the callback sends
            inner.append(tok.cycle)
            with trace_mod.span("put", 8):
                pass
            tok.dispatched()

    def plain(seg):
        with trace_mod.span("build", 3):
            pass
        with trace_mod.span("deliver", 3):
            pass

    a, b = gated(2), gated(3)
    queue.push(PendingEmit([np.zeros(2)], sends, trace=a))
    queue.push(PendingEmit([np.zeros(2)], plain, trace=None))
    queue.push(PendingEmit([np.zeros(3)], plain, trace=b))
    outer = t.begin_cycle("dense", 4)           # open when the drain runs
    queue.drain()
    with trace_mod.span("convert", 4):
        pass
    outer.dispatched()
    got = [(s[0], s[1]) for s in t.recorder.spans() if s[1] != "ingest"
           and s[1] != "step"]
    (sent,) = inner
    assert got == [
        (a.cycle, "fetch"), (a.cycle, "build"), (sent, "put"),
        (a.cycle, "deliver"), (a.cycle, "emit"),
        (b.cycle, "fetch"), (b.cycle, "build"), (b.cycle, "deliver"),
        (b.cycle, "emit"), (outer.cycle, "convert")]
    assert getattr(trace_mod._open, "tok", None) is None


def test_a_failing_materializer_leaves_the_found_cycle_open():
    t = Tracer("app", sample=1)
    faults = []
    queue = EmitQueue(depth=8, on_fault=faults.append)

    def breaks(seg):
        with trace_mod.span("build", 1):
            raise RuntimeError("no rows")

    tok = t.begin_cycle("dense", 1)
    tok.dispatched()
    tok.step_done(1)
    queue.push(PendingEmit([np.zeros(1)], breaks, trace=tok))
    outer = t.begin_cycle("dense", 1)
    queue.drain()
    assert len(faults) == 1 and queue.stats.dropped_batches == 1
    assert getattr(trace_mod._open, "tok", None) is outer
    outer.dispatched()
    assert [s[1] for s in t.recorder.spans() if s[0] == tok.cycle] == [
        "ingest", "step", "fetch", "build", "emit.aborted"]


def test_a_timer_flush_outside_a_drain_records_no_span():
    """``DeviceQueryRuntime.fire`` hands a pane flush's rows to the
    callback with no cycle open: no ``build`` and no ``deliver`` lands
    in whatever cycle was sampled last."""
    body = ("define stream S (symbol string, price float, volume int); "
            "@info(name='q') from S#window.timeBatch(1 sec) select symbol, "
            "sum(price) as total group by symbol insert into Out;")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('back_timer') @app:playback @app:execution('tpu') "
            "@app:trace(sample='1', cycles='16') " + body)
        rows = []
        rt.add_callback("Out", rows.extend)
        rt.start()
        h = rt.get_input_handler("S")
        h.send_batch(window_batch(0))
        before = len(rt.app_context.tracer.recorder.spans())
        # a batch a second and a half later closes the first pane
        late = window_batch(1)
        h.send_batch(EventBatch("S", late.attribute_names, late.columns,
                                late.timestamps + 1_500))
        assert rows
        spans = rt.app_context.tracer.recorder.spans()[before:]
        assert len({s[0] for s in spans}) == 1   # the second batch's alone
    finally:
        m.shutdown()
