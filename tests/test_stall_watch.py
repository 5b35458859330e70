"""The stall watch (``observability/stall.py``): every send is on the
tracer's clock, and one that runs far past its thread's typical send
leaves a record that says why.  On a ticking clock: each planted cause
is named by its record, a send under the threshold leaves none, the
first eight sends leave none, a re-entrant send keeps the outer stamp,
an unsampled and unstalled send allocates nothing, and the record is in
``dump()``, ``statistics()`` and ``/metrics``.  And the benchmark's
reader of the two entry metrics (``benchmark/layers/entry.py``) on
hand-made rings.
"""

import gc
import logging
import os
import sys
import threading
import time
import types

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.observability import stall
from siddhi_tpu.observability import trace as trace_mod
from siddhi_tpu.observability.prometheus import render_prometheus
from siddhi_tpu.util.persistence import InMemoryPersistenceStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
_added = [p for p in (BENCH, os.path.join(BENCH, "layers"))
          if p not in sys.path]
sys.path[:0] = _added   # the reader imports program_spans
try:
    import entry        # noqa: E402  (benchmark/layers/entry.py)
finally:
    for _p in _added:
        sys.path.remove(_p)

APP = ("@app:name('{name}') define stream S (v double); "
       "define stream T (v double); "
       "@info(name='q') from S select v insert into Out; "
       "@info(name='r') from T select v insert into Out2;")


def batch(i, stream="S"):
    return EventBatch(stream, ["v"], {"v": np.full(4, float(i))},
                      np.full(4, 1_000 + i, dtype=np.int64))


class Fed:
    """A host app whose ``Out`` callback runs ``self.plant`` once armed:
    what a planted send does inside ``send_batch``."""

    def __init__(self, name, every=None, trace=""):
        self.m = SiddhiManager()
        self.m.set_persistence_store(InMemoryPersistenceStore())
        self.rt = self.m.create_siddhi_app_runtime(
            trace + APP.format(name=name))
        self.plant, self.every = None, every
        self.rt.add_callback("Out", self._out)
        self.rt.start()
        self.h = self.rt.get_input_handler("S")
        self.tracer = self.rt.app_context.tracer
        self.sent = 0

    def _out(self, _events):
        if self.every is not None:
            self.every()
        plant, self.plant = self.plant, None
        if plant is not None:
            plant()

    def send(self, n=1):
        for _ in range(n):
            self.sent += 1
            self.h.send_batch(batch(self.sent))

    def stalls(self):
        return list(self.tracer.recorder.stalls)

    def mine(self):
        """The calling thread's ``Sender``."""
        return self.tracer._mine.st

    def close(self):
        self.rt.shutdown()
        self.m.shutdown()


@pytest.fixture
def fed(request):
    made = []

    def make(**kw):
        made.append(Fed(request.node.name.replace("[", "_").replace(
            "]", ""), **kw))
        return made[-1]
    yield make
    for f in made:
        f.close()


# -- each planted cause is named by its record --------------------------------

def plant_gc(f):
    # a graph of garbage made ahead of the send; the callback lets go of
    # it and collects: the full collection is the send
    junk = []
    for _ in range(400_000):
        a = []
        a.append(a)
        junk.append(a)
    holder = [junk]
    del junk

    def collect():
        holder.clear()
        gc.collect()
    return collect, None


def sleeper():
    time.sleep(0.2)


def plant_host(f):
    return sleeper, None


def plant_compile(f):
    import jax
    import jax.numpy as jnp

    def chain(x):
        for i in range(150):
            x = jnp.sin(x) * (i + 1.0) + x
        return x

    def first_call():
        jax.jit(chain)(jnp.ones(8)).block_until_ready()
    return first_call, None


def plant_lock(f):
    held, lock = threading.Event(), f.rt.app_context.process_lock

    def hold():
        with lock:
            held.set()
            time.sleep(0.25)
    t = threading.Thread(target=hold, name="lock-holder")
    t.start()
    assert held.wait(5)
    return None, t


def plant_persist(f):
    # a checkpoint whose capture (the barrier: the process lock from the
    # emit drain on) lasts a quarter of a second, begun ahead of the send
    began, drain = threading.Event(), f.rt.drain_device_emits

    def slow_drain():
        began.set()
        time.sleep(0.25)
        drain()
    f.rt.drain_device_emits = slow_drain
    t = threading.Thread(target=f.rt.persist, name="persister")
    t.start()
    assert began.wait(5)
    return None, t


@stall.waits_on_device
def gate_fetch():
    """Marked as the count gates' ``resolve()`` are: where a sender
    waits for the device."""
    time.sleep(0.2)


def plant_device_wait(f):
    return gate_fetch, None


PLANTS = {"gc": plant_gc, "host": plant_host, "compile": plant_compile,
          "lock": plant_lock, "persist": plant_persist,
          "device_wait": plant_device_wait}


@pytest.mark.parametrize("cause", list(PLANTS))
def test_a_planted_cause_is_named_by_its_record(cause, fed, caplog):
    f = fed()
    f.send(stall.SEED_SENDS + 4)
    assert f.stalls() == []
    f.plant, thread = PLANTS[cause](f)
    t0 = time.perf_counter()
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu.observability"):
        f.send()
    t1 = time.perf_counter()
    if thread is not None:
        thread.join(5)
        assert not thread.is_alive()
    (rec,) = f.stalls()
    assert rec["cause"] == cause, rec
    # the interval is the send's: inside the caller's own stamps, and
    # short of them by the batch's making and the record's writing
    assert t0 <= rec["t_start"] and rec["t_end"] <= t1
    assert (t1 - t0) * 1e3 - 100.0 <= rec["ms"] <= (t1 - t0) * 1e3
    assert rec["ms"] >= 1e3 * stall.STALL_FLOOR_S
    assert rec["events"] == 4 and rec["send"] == f.sent
    assert rec["thread"] == threading.current_thread().name
    assert rec["client_gap_ms"] is not None and rec["pending_work"] == 0
    # the process's figures are from the watch's first notice on: a
    # stall it never woke inside has none, and says so
    if rec["noticed"]:
        assert 0 < rec["process"]["wall_ms"] <= rec["ms"]
        assert set(rec["process"]) == {
            "wall_ms", "cpu_ms", "involuntary_switches", "major_faults"}
    else:
        assert rec["process"] is None and rec["samples"] == []
    stacks = [s["sender"] for s in rec["samples"]]
    if cause == "gc":
        # a full collection holds the interpreter: the watch may not wake
        assert rec["gc_ms"] >= rec["ms"] / 2 and rec["gc"]
    if cause == "compile":
        assert rec["compile_ms"] >= rec["ms"] / 2
        assert any(c["event"].endswith("backend_compile_duration")
                   for c in rec["compile"])
    if cause == "persist":
        assert [p["stage"] for p in rec["persist"]] == ["persist.capture"]
    if cause == "host":
        assert rec["noticed"] and len(stacks) >= 2
        assert all(s[0].endswith(" sleeper") for s in stacks)
        assert len(stacks[0]) == stall.SENDER_FRAMES
        assert {s["waits"] for s in rec["samples"]} == {None}
    if cause == "lock":
        # marked by the entry itself, round the acquire
        assert {s["waits"] for s in rec["samples"]} == {"lock"}
        assert all(s[0].endswith(" InputHandler.send_batch") for s in stacks)
        # and which thread had what the sender waited for
        assert any(s["others"].get("lock-holder", "").endswith(".hold")
                   for s in rec["samples"])
    if cause == "device_wait":
        # by the function's mark, not by its name
        assert {s["waits"] for s in rec["samples"]} == {"device"}
        assert all(s[0].endswith(" gate_fetch") for s in stacks)
    # one WARNING line a stall: cause, milliseconds, where the sender was
    lines = [r.getMessage() for r in caplog.records if "stalled" in
             r.getMessage()]
    assert len(lines) == 1 and f": {cause}; sender at " in lines[0]
    assert f"send {f.sent} of thread" in lines[0]
    # and the next send is an ordinary one again
    f.send(3)
    assert len(f.stalls()) == 1


def test_a_stall_with_late_wakeups_and_an_idle_process_is_descheduled(fed):
    """The watch's wake-ups late by half of the stall and the sender's
    own thread off a processor for half of it: neither ran."""
    f = fed()
    real = f.tracer.watch._stop

    class LateWaker:
        def wait(self, timeout):
            return real.wait(timeout + 0.3)

        def __getattr__(self, name):
            return getattr(real, name)
    f.tracer.watch._stop = LateWaker()
    f.send(stall.SEED_SENDS + 2)
    f.plant = lambda: time.sleep(0.5)
    f.send()
    (rec,) = f.stalls()
    assert rec["late_ms"] >= rec["ms"] / 2
    # the sender thread's own CPU-time clock, from the watch's last
    # wake-up before the stall: it slept
    cpu_ms, over_ms = rec["sender_cpu"]
    assert over_ms >= rec["ms"] and cpu_ms < rec["ms"] / 2
    assert rec["cause"] == "descheduled", rec


def test_a_stall_in_the_making_keeps_the_reading_from_before_it(fed):
    """The watch reads a sender's CPU clock at every wake-up, but not
    inside a send already past the floor: a freeze that holds the watch
    too lets it wake late, inside the stall and under the thread's
    threshold (8 typical sends of 16 ms: the chip's 134 ms on the
    brute-force cell), and the reading from before is what the record
    differences."""
    f = fed()
    f.send(stall.SEED_SENDS + 1)
    watch, st = f.tracer.watch, f.mine()
    watch.stop()                      # the wake-ups are this test's
    st.typical = 0.016
    now = time.perf_counter()
    st.cpu_seen = before = (now - 0.15, 5.0)
    st.t_in, st.t_out = now - 0.122, now - 0.2     # open for 122 ms
    watch._wake(now, 0.089)
    assert st.cpu_seen is before and st.notice is None
    st.t_in = now - 0.03                           # a young send: read
    watch._wake(now, 0.0)
    assert st.cpu_seen[0] == now and st.cpu_seen[1] >= 0.0
    st.t_in, st.t_out = now - 0.03, now            # no send open: read
    watch._wake(now + 0.05, 0.0)
    assert st.cpu_seen[0] == now + 0.05
    # past the threshold: noticed, sampled, and the reading stays
    seen = st.cpu_seen
    st.t_in, st.t_out = now - 0.2, now - 0.3
    watch._wake(now, 0.1)
    assert st.cpu_seen is seen and st.notice.late == 0.1
    assert len(st.notice.samples) == 1
    st.t_out = now                                 # closed again


RULES = [
    # ms, gc, compile, persist, what the sender waits for, late, the
    # process's (wall, cpu) from the notice on, the sender's own (ms on a
    # processor, of this many) -> cause
    (100, 60, 90, True, "lock", 90, (50, 1), (1, 120), "gc"),
    (100, 40, 90, True, "lock", 90, (50, 1), (1, 120), "compile"),
    (100, 40, 40, True, "lock", 90, (50, 1), (1, 120), "persist"),
    (100, 0, 0, False, "lock", 90, (50, 1), (1, 120), "lock"),
    (100, 0, 0, False, "device", 90, (50, 1), (1, 120), "device_wait"),
    (100, 0, 0, False, None, 90, (50, 1), (1, 120), "descheduled"),
    # never noticed: no samples, no figures
    (100, 0, 0, False, False, 90, None, (1, 120), "descheduled"),
    # the runtime's other threads ran, the sender did not
    (100, 0, 0, False, False, 90, (50, 150), (5, 120), "descheduled"),
    (100, 0, 0, False, False, 90, None, (40, 120), "descheduled"),
    (100, 0, 0, False, False, 90, None, (60, 120), "host"),
    # a reading that covers the stall's last 30 ms shows nothing
    (100, 0, 0, False, False, 90, None, (1, 30), "host"),
    # no thread clock: the process's processor time decides
    (100, 0, 0, False, False, 90, (50, 1), None, "descheduled"),
    (100, 0, 0, False, False, 90, (50, 25), None, "host"),
    (100, 0, 0, False, False, 90, None, None, "host"),
    # the watch woke on time
    (100, 0, 0, False, False, 40, (50, 1), (1, 120), "host"),
    (100, 0, 0, False, None, 1, (50, 49), (99, 120), "host"),
]


@pytest.mark.parametrize("case", RULES, ids=[
    f"{i}_{c[-1]}" for i, c in enumerate(RULES)])
def test_one_cause_by_the_first_rule_that_holds(case):
    ms, gc_ms, compile_ms, persist, waits, late, process, cpu, cause = case
    samples = [] if waits is False else [
        {"at_ms": 60.0, "others": {}, "waits": waits, "sender": [
            "x/y.py:1 f", "core/stream.py:9 InputHandler.send_batch"]}
    ] * 2
    if process is not None:
        process = {"wall_ms": process[0], "cpu_ms": process[1]}
    assert stall.cause_of(
        ms, gc_ms, compile_ms,
        [{"stage": "persist.capture"}] if persist else [], samples, late,
        process, cpu) == cause


# -- what leaves no record ----------------------------------------------------

def sleeps(ms):
    return lambda: time.sleep(ms * 1e-3)


@pytest.mark.parametrize("every_ms, planted_ms", [
    (None, 40),   # under the floor of 50 ms, whatever the typical send
    (10, 60),     # six times the typical send, under the factor of 8
], ids=["40ms", "6_times"])
def test_a_send_under_the_threshold_leaves_no_record(every_ms, planted_ms,
                                                     fed):
    f = fed(every=sleeps(every_ms) if every_ms else None)
    f.send(stall.SEED_SENDS + 8)
    f.plant = sleeps(planted_ms)
    f.send()
    f.send(2)
    assert f.stalls() == [] and f.tracer.watch.stats() == {}
    assert not [s for s in f.tracer.recorder.spans()
                if s[1].startswith(stall.STAGE_STALL)]
    if every_ms:
        st = f.mine()
        assert every_ms * 1e-3 <= st.typical < 2 * every_ms * 1e-3


def test_the_first_eight_sends_seed_the_typical_send_and_leave_no_record(
        fed):
    f = fed()
    st = None
    for n in range(1, stall.SEED_SENDS + 1):
        if n == 3:
            f.plant = sleeper    # a compile, as first sends hold
        # the watch's thread is started by the send that ends the seeding
        assert f.tracer.watch._thread is None
        f.send()
        st = st or f.mine()
        assert st.n == n
    assert f.tracer.watch._thread.is_alive()
    assert f.stalls() == []
    # the shortest of them seeds the mean: a compile only ever adds
    assert st.typical < 0.05
    f.plant = sleeper
    f.send()
    assert [r["cause"] for r in f.stalls()] == ["host"]
    # a stall joins the mean as the threshold it passed, no more
    assert st.typical < 0.05
    f.rt.shutdown()
    assert f.tracer.watch._thread is None
    # and a runtime started again is watched again, at once: its
    # thread's seeding is behind it
    f.rt.start()
    assert f.tracer.watch._thread.is_alive()
    f.plant = sleeper
    f.send()
    assert [r["cause"] for r in f.stalls()] == ["host", "host"]
    f.close()
    assert f.tracer.watch._thread is None


def test_a_reentrant_send_keeps_the_outer_stamp(fed):
    f = fed()
    h2 = f.rt.get_input_handler("T")
    seen = {}

    def inner(_events):
        st = f.mine()
        seen["inner"] = (st.t_in, st.n, f.tracer.send_begins())

    def outer():
        st = f.mine()
        seen["outer"] = (st.t_in, st.n)
        h2.send_batch(batch(1, "T"))
        seen["after"] = (st.t_in, st.n)
    f.rt.add_callback("Out2", inner)
    f.send(2)
    f.plant = outer
    t0 = time.perf_counter()
    f.send()
    t_in, n = seen["outer"]
    assert t0 <= t_in and n == 2
    # the stamp and the ordinal are the outer send's inside the inner
    # one and after it; a send inside a send is not stamped at all
    assert seen["inner"] == (t_in, 2, None) and seen["after"] == (t_in, 2)
    st = f.mine()
    assert st.n == 3 and st.t_out > st.t_in == t_in


def test_an_unsampled_unstalled_send_allocates_nothing(fed, monkeypatch):
    """No token, span, tuple, sender or notice, and the ring as it was;
    the thread's one ``Sender`` is made by its first send."""
    made = {"token": 0, "span": 0, "sender": 0, "notice": 0}

    def counted(cls, what):
        init = cls.__init__

        def counting(self, *a, **kw):
            made[what] += 1
            init(self, *a, **kw)
        monkeypatch.setattr(cls, "__init__", counting)

    counted(trace_mod.CycleToken, "token")
    counted(trace_mod.Span, "span")
    counted(stall.Sender, "sender")
    counted(stall._Notice, "notice")
    f = fed(trace="@app:trace(sample='1/4') ")
    f.send()
    assert made == {"token": 0, "span": 0, "sender": 1, "notice": 0}
    f.send(stall.SEED_SENDS + 20)
    assert made == {"token": 0, "span": 0, "sender": 1, "notice": 0}
    assert f.tracer.recorder.spans() == [] and f.stalls() == []
    # a host query begins no cycle: nothing is sampled, no histogram
    assert f.tracer.stage_hist["send"].count == 0
    assert f.mine().n == f.sent


def test_tracing_off_turns_the_stamps_off_with_the_rest(fed):
    f = fed(trace="@app:trace(sample='off') ")
    f.send(stall.SEED_SENDS + 2)
    f.plant = sleeper
    f.send()
    assert f.tracer.watch.senders == [] and f.stalls() == []
    assert f.tracer.watch._thread is None


# -- the sending threads come and go ------------------------------------------

def test_a_dead_threads_sender_goes_with_it(fed):
    """A ``Sender`` is its thread's: the watch drops a dead thread's at
    its next wake-up and reads no clock of it, and a thread that comes
    after (under the same ident, as the system hands them out again)
    starts with a typical send of its own."""
    f = fed()
    f.send(stall.SEED_SENDS + 1)          # the watch's thread runs
    mine = f.mine()

    def feed(n, keep):
        for i in range(n):
            f.h.send_batch(batch(i))
        keep.append(f.mine())
    first = []
    t = threading.Thread(target=feed, args=(stall.SEED_SENDS + 3, first),
                         name="feeder")
    t.start()
    t.join(5)
    (st,) = first
    assert st is not mine and st.n == stall.SEED_SENDS + 3
    deadline = time.perf_counter() + 5
    while st in f.tracer.watch.senders:
        assert time.perf_counter() < deadline
        time.sleep(0.02)
    assert f.tracer.watch.senders == [mine]
    # the dead thread's clock is a failed system call, not a read of
    # freed memory: None (or another thread's reading, had the system
    # handed the id out again)
    assert st.cpu_ms() is None or st.cpu_ms() >= 0.0
    second = []
    t = threading.Thread(target=feed, args=(2, second), name="feeder")
    t.start()
    t.join(5)
    assert second[0] is not st and second[0].n == 2
    assert second[0].thread is t and mine.n == stall.SEED_SENDS + 1


def test_a_record_that_fails_is_not_the_senders_fault(fed, caplog,
                                                      monkeypatch):
    """``send_ends`` runs in the send's ``finally``: a stall's record
    that raises is logged, the send returns as it would have, it is
    closed, and the thread's later sends are stamped and judged."""
    f = fed()
    f.send(stall.SEED_SENDS + 2)
    real = f.tracer.watch._evidence

    def broken(*a):
        raise RuntimeError("no evidence")
    monkeypatch.setattr(f.tracer.watch, "_evidence", broken)
    f.plant = sleeper
    with caplog.at_level(logging.ERROR, logger="siddhi_tpu.observability"):
        f.send()                           # does not raise
    assert "a stall's record failed" in caplog.text
    st = f.mine()
    assert st.t_out > st.t_in and st.n == f.sent and f.stalls() == []
    monkeypatch.setattr(f.tracer.watch, "_evidence", real)
    f.send(2)
    f.plant = sleeper
    f.send()
    assert [r["cause"] for r in f.stalls()] == ["host"]
    assert f.stalls()[0]["send"] == f.sent == st.n


def test_a_send_into_another_app_keeps_its_own_lead():
    """A callback of one app that feeds another, on the sender's own
    thread: each app's send is on that app's clock, and a cycle takes
    the lead of its own app's send alone, once."""
    m = SiddhiManager()
    try:
        rts = [m.create_siddhi_app_runtime(
            f"@app:name('lead_{x}') @app:playback @app:execution('tpu') "
            "@app:trace(sample='1') define stream S (v double); "
            "@info(name='q') from S#window.length(4) select sum(v) as t "
            "insert into Out;") for x in "ab"]
        ha, hb = (rt.get_input_handler("S") for rt in rts)
        rts[0].add_callback("Out", lambda _e: hb.send_batch(batch(7)))
        got = []
        rts[1].add_callback("Out", got.extend)
        for rt in rts:
            rt.start()
        for i in range(3):
            ha.send_batch(batch(i))
        for rt in rts:
            rt.drain_device_emits()
        assert len(got) == 12
        ta, tb = (rt.app_context.tracer for rt in rts)
        sa, sb = ta._mine.st, tb._mine.st
        assert sa is not sb and sa.n == sb.n == 3
        assert sa.watch is ta.watch and sb.watch is tb.watch
        for tracer in (ta, tb):
            spans = tracer.recorder.spans()
            admits = [s for s in spans if s[1] == "admit"]
            assert len(admits) == 3 == len({s[0] for s in admits})
            # each ahead of its own cycle's spans, inside its own send
            for ad in admits:
                assert ad[4] <= min(s[3] for s in spans
                                    if s[0] == ad[0] and s is not ad)
            assert tracer.stage_hist["send"].count == 3
        # b's sends lie inside a's, behind a's admits
        for (a_ad, b_ad) in zip(*[[s for s in t.recorder.spans()
                                   if s[1] == "admit"] for t in (ta, tb)]):
            assert a_ad[4] <= b_ad[3]
    finally:
        m.shutdown()


# -- where a record goes ------------------------------------------------------

def test_the_record_is_in_the_dump_the_statistics_and_the_metrics(fed):
    f = fed(trace="@app:trace(sample='1') ")
    f.send(stall.SEED_SENDS + 2)
    f.plant = sleeper
    f.send()
    f.plant = gate_fetch
    f.send()
    name = f.rt.name
    dump = f.tracer.dump("test")
    assert [r["cause"] for r in dump["stalls"]] == ["host", "device_wait"]
    assert f.tracer.recorder.last_dump["stalls"] == dump["stalls"]
    # one zero-width tuple a stall, the count its microseconds, at the
    # send's entry; at sample='1' every send is on the histogram
    tuples = [s for s in f.tracer.recorder.spans()
              if s[1].startswith("stall.")]
    assert [s[1] for s in tuples] == ["stall.host", "stall.device_wait"]
    for s, r in zip(tuples, dump["stalls"]):
        assert s[3] == s[4] == r["t_start"]
        assert s[5] == int(1e3 * r["ms"]) and 190_000 < s[5] < 400_000
    assert f.tracer.stage_hist["send"].count == f.sent
    assert not [s for s in f.tracer.recorder.spans() if s[1] == "send"]
    stats = f.rt.statistics()
    pre = f"io.siddhi.SiddhiApps.{name}.Siddhi."
    assert stats[pre + "Stalls.all.count"] == 2
    assert stats[pre + "Stalls.host.count"] == 1
    assert stats[pre + "Stalls.device_wait.count"] == 1
    assert 0.38 < stats[pre + "Stalls.all.seconds"] < 0.8
    assert stats[pre + "Stalls.all.longestMs"] == max(
        r["ms"] for r in dump["stalls"])
    assert stats[pre + "Stages.send.spans"] == f.sent
    text = render_prometheus([(name, stats, [])])
    assert "# TYPE siddhi_stalls_total counter" in text
    assert f'siddhi_stalls_total{{app="{name}",cause="host"}} 1' in text
    assert (f'siddhi_stalls_total{{app="{name}",cause="device_wait"}} 1'
            in text)
    assert "# TYPE siddhi_stall_seconds_total counter" in text
    assert f'siddhi_stall_seconds_total{{app="{name}",cause="host"}} 0.2' \
        in text
    assert f'siddhi_stall_longest_ms{{app="{name}"}}' in text
    assert 'cause="all"' not in text


def test_a_dump_says_where_an_open_stall_stands(fed):
    """A send that has not ended is in the payload with the watch's
    samples so far: a watchdog's trip dump says where the wedge is."""
    f = fed()
    go, parked = threading.Event(), threading.Event()

    def wedge():
        parked.set()
        go.wait(10)

    def feed():     # a thread's typical send is its own
        f.send(stall.SEED_SENDS + 2)
        f.plant = wedge
        f.send()
    t = threading.Thread(target=feed, name="wedged-sender")
    t.start()
    try:
        assert parked.wait(5)
        deadline = time.perf_counter() + 5
        while not (found := f.tracer.recorder.payload("live")["stalls"]):
            assert time.perf_counter() < deadline
            time.sleep(0.02)
        (rec,) = found
        assert rec["open"] and rec["thread"] == "wedged-sender"
        assert any(fr.endswith(".wedge")
                   for fr in rec["samples"][0]["sender"])
        assert rec["samples"][0]["others"]["MainThread"]
    finally:
        go.set()
        t.join(5)
    assert not t.is_alive()
    (rec,) = f.tracer.recorder.payload("live")["stalls"]
    assert rec["cause"] == "host" and "open" not in rec


def test_an_async_junctions_worker_is_an_entry_too():
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('async_entry') "
            "@async(buffer.size='64', batch.size.max='4') "
            "define stream S (v double); "
            "from S select v insert into Out;")
        got = []
        rt.add_callback("Out", got.extend)
        rt.start()
        h = rt.get_input_handler("S")
        for i in range(6):
            h.send_batch(batch(i))
        deadline = time.perf_counter() + 5
        while len(got) < 24 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert len(got) == 24
        senders = rt.app_context.tracer.watch.senders
        by_name = {st.thread.name: st for st in senders}
        assert by_name[threading.current_thread().name].n == 6
        assert 1 <= by_name["junction-S"].n <= 6
    finally:
        m.shutdown()


# -- the benchmark's reader ---------------------------------------------------

def ring_span(cycle, stage, start_s, end_s, count=0):
    return (cycle, stage, "dense", start_s, end_s, count)


def make_run(ring, clean_s=20.0, wanted=None):
    sends = [(0.0, 0.01), (clean_s, clean_s + 0.01)]
    return types.SimpleNamespace(
        wanted=wanted or ["events.admit_ms_per_batch",
                          "events.send_steady_share", "events.other"],
        window=types.SimpleNamespace(t0=0.0, sends=sends, clean=1),
        ring_spans=ring)


def cycles(n, admit_ms=0.5, period=0.1):
    ring = []
    for c in range(n):
        t = c * period
        ring += [ring_span(c + 1, "admit", t, t + admit_ms * 1e-3, 100),
                 ring_span(c + 1, "ingest", t + admit_ms * 1e-3, t + 0.01,
                           100)]
    return ring


def test_the_reader_takes_an_admit_per_cycle():
    got = entry.read(make_run(cycles(100)))
    assert got["events.admit_ms_per_batch"] == pytest.approx(0.5)
    assert got["events.send_steady_share"] == 100.0
    assert set(got) == {"events.admit_ms_per_batch",
                        "events.send_steady_share"}
    # cycles past the clean part (under the profiler) are not read
    late = cycles(100) + [ring_span(500, "admit", 20.5, 20.6, 100)]
    assert entry.read(make_run(late))["events.admit_ms_per_batch"] == \
        pytest.approx(0.5)


@pytest.mark.parametrize("prefix", ["events", "rows", "latency"])
def test_the_reader_takes_a_stall_from_the_steady_share(prefix):
    # a stall.gc tuple of 2,000,000 us in a clean part of 20 s: 90.0; under
    # the send's own cycle id, so the count of cycles is what it was
    ring = cycles(100) + [ring_span(50, "stall.gc", 4.9, 4.9, 2_000_000)]
    got = entry.read(make_run(ring, wanted=[
        f"{prefix}.admit_ms_per_batch", f"{prefix}.send_steady_share"]))
    assert got[f"{prefix}.send_steady_share"] == pytest.approx(90.0)
    assert got[f"{prefix}.admit_ms_per_batch"] == pytest.approx(0.5)
    # one that began under the profiler is not the clean part's
    ring = cycles(100) + [ring_span(500, "stall.host", 20.2, 20.2,
                                    4_000_000)]
    assert entry.read(make_run(ring))["events.send_steady_share"] == 100.0
    # 82.6: one stall of 4 s in a clean part of 23 s
    ring = cycles(100) + [ring_span(7, "stall.host", 3.0, 3.0, 4_000_000)]
    assert entry.read(make_run(ring, clean_s=23.0))[
        "events.send_steady_share"] == pytest.approx(82.6, abs=0.05)


def test_the_reader_yields_nothing_for_a_program_without_the_span():
    ring = [s for s in cycles(100) if s[1] != "admit"]
    assert entry.read(make_run(ring)) == {}
    assert entry.read(make_run([])) == {}
    assert entry.read(make_run(cycles(10), wanted=["events.other"])) == {}
