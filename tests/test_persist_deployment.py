"""The deployment ``fraud16_1m_ckpt`` (PR 48) tied to the program, at a
few hundred partitions on the CPU.

What the app text alone turns on: ``@app:persist(location,
revisions.to.keep)`` opens the app's own durable store, a daemon that
ticks at a fixed rate, a state tree that carries the app's clock.  And
the guarantee the cell's ``correct`` holds at 1,000,000 partitions on
the chip: a revision holds the state after exactly the batches sent
before its capture, so a runtime that restores it and is sent the
batches that followed delivers exactly the rows the plain chain
automaton (``benchmark/references/pattern_chain.py``, which imports
nothing of the program) owes from there on: with the capture after each
of a pass's nine batches, a gate staged behind the barrier or none, one
device or a mesh of four.
"""

import collections
import copy
import importlib.util
import json
import os
import pickle
import shutil
import sys
import threading
import time

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.core.stream import StreamCallback
from siddhi_tpu.durability import DurableFileSystemPersistenceStore
from siddhi_tpu.durability.store import open_store

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(*path):
    spec = importlib.util.spec_from_file_location(
        "_ckpt_" + os.path.splitext(path[-1])[0], os.path.join(BENCH, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


with open(os.path.join(BENCH, "configs", "fraud16_1m_ckpt.json")) as _f:
    CONFIG = json.load(_f)
CHAIN = _load("references", "pattern_chain.py")
sys.path.insert(0, os.path.join(BENCH, "generators"))
try:    # fraud_pass_ckpt imports fraud_pass, its neighbour
    GEN = _load("generators", "fraud_pass_ckpt.py")
finally:
    sys.path.pop(0)

BODY = ("define stream S (k long, v double); "
        "@info(name='q') from S#window.length(4) "
        "select k, sum(v) as s group by k insert into Out;")
PARTITIONS, BATCH, PER_PASS = 1024, 256, 9
ENGINES = {"dense": "", "staged": "", "devices4": ", devices='4'"}


def config_for(engine, keep="2"):
    """The configuration with the engine's ``devices`` element in both
    headers, and a store that keeps ``keep`` revisions."""
    config = copy.deepcopy(CONFIG)
    for h in ("header", "recover_header"):
        config[h] = config[h].replace(
            "partitions='{partitions}'",
            "partitions='{partitions}'" + ENGINES[engine]).replace(
            "revisions.to.keep='2'", f"revisions.to.keep='{keep}'")
    return config


def schedule_of(config, location, seed=48, limit_s=120):
    size = {"partitions": PARTITIONS, "interval": "1 sec",
            "location": str(location)}
    return GEN.CheckpointedPasses(
        config, size, limit_s, *GEN.fraud_pass.traffic(
            np.random.default_rng(seed), PARTITIONS, BATCH, PER_PASS))


class Rows(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive_batch(self, batch):
        self.rows += zip(batch.timestamps.tolist(),
                         np.asarray(batch.columns["v1"], float).tolist(),
                         np.asarray(batch.columns["v16"], float).tolist())


def runtime(config, schedule, header="recover_header"):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        config[header].format(**schedule.size) + " " + config["app"])
    got, errors = Rows(), []
    rt.add_callback(config["output"], got)
    rt.add_exception_listener(errors.append)
    rt.start()
    return m, rt, got, errors


def engine_of(rt):
    """The app's one dense engine."""
    (partition,) = rt.partitions.values()
    (query,) = partition.dense_query_runtimes.values()
    return query.pattern_processor


def owed(schedule, first, last, after):
    """What the plain automaton owes for the batches past ``after``,
    every key run from batch ``first`` to ``last``: ``(ts, v1, v16)``."""
    by_key = collections.defaultdict(list)
    for n in range(first, last + 1):
        b = schedule.batch(n)
        for k, v, ts in zip(b.columns["key"].tolist(),
                            b.columns["v"].tolist(), b.timestamps.tolist()):
            by_key[k].append((n, ts, float(v)))
    return sorted((schedule.ts_of(n), v1, v16) for evs in by_key.values()
                  for n, v1, v16 in CHAIN._chain_rows(
                      evs, CONFIG["reference"]["states"],
                      CONFIG["reference"]["within_ms"]) if n > after)


def in_order_by_key(schedule, rows):
    ts, v1, _v16 = map(np.asarray, zip(*rows))
    keys = schedule.row_keys({"v1": v1})
    order = np.argsort(keys, kind="stable")
    return not ((np.diff(ts[order]) < 0) & (np.diff(keys[order]) == 0)).any()


# -- the annotation ----------------------------------------------------------


def test_location_opens_the_apps_own_durable_store(tmp_path):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('own') @app:playback @app:persist(mode='sync', "
            f"location='{tmp_path}', revisions.to.keep='2') " + BODY)
        store = rt.app_context.persistence_store
        assert isinstance(store, DurableFileSystemPersistenceStore)
        assert (store.base_dir, store.revisions_to_keep) == (str(tmp_path), 2)
        assert m.siddhi_context.persistence_store is None
        rt.start()
        h = rt.get_input_handler("S")
        revisions = []
        for i in range(3):
            h.send([i, 1.0], timestamp=1_000 + i)
            revisions.append(rt.persist())
            time.sleep(0.002)    # a revision is named by its millisecond
        # the annotation's store, through the function a recovering
        # process opens it with: the two newest, committed
        assert open_store(str(tmp_path), 2).revisions("own") == revisions[1:]
        assert rt.restore_last_revision() == revisions[-1]
        rt.shutdown()
    finally:
        m.shutdown()


def test_a_manager_with_a_store_refuses_an_app_that_names_one(tmp_path):
    m = SiddhiManager()
    m.set_persistence_store(
        DurableFileSystemPersistenceStore(str(tmp_path / "manager")))
    try:
        with pytest.raises(SiddhiAppCreationError, match="one store"):
            m.create_siddhi_app_runtime(
                "@app:name('two') @app:persist(mode='async', "
                f"location='{tmp_path / 'app'}') " + BODY)
        # and one given a store after its app was built, at the persist
        m.siddhi_context.persistence_store = None
        rt = m.create_siddhi_app_runtime(
            "@app:name('two') @app:persist(mode='sync', "
            f"location='{tmp_path / 'app'}') " + BODY)
        m.set_persistence_store(
            DurableFileSystemPersistenceStore(str(tmp_path / "manager")))
        rt.start()
        with pytest.raises(Exception, match="one store"):
            rt.persist()
        rt.shutdown()
    finally:
        m.shutdown()


@pytest.mark.parametrize("elements, why", [
    ("location='{loc}', revisions.to.keep='0'", "1 or more"),
    ("location='{loc}', revisions.to.keep='-2'", "1 or more"),
    ("location='{loc}', revisions.to.keep='two'", "1 or more"),
    ("location='{loc}', revisions.to.keep='1.5'", "1 or more"),
    ("location=' '", "directory"),
    ("revisions.to.keep='2'", "location"),
], ids=["keep_0", "keep_negative", "keep_word", "keep_fraction",
        "location_blank", "keep_without_location"])
def test_a_bad_value_is_refused_at_creation(tmp_path, elements, why):
    m = SiddhiManager()
    try:
        with pytest.raises(SiddhiAppCreationError, match=why):
            m.create_siddhi_app_runtime(
                "@app:name('bad') @app:persist(mode='async', "
                + elements.format(loc=tmp_path) + ") " + BODY)
    finally:
        m.shutdown()


def test_a_location_needs_the_apps_name(tmp_path):
    m = SiddhiManager()
    try:
        with pytest.raises(SiddhiAppCreationError, match="app:name"):
            m.create_siddhi_app_runtime(
                f"@app:persist(mode='async', location='{tmp_path}') " + BODY)
    finally:
        m.shutdown()


# -- the daemon --------------------------------------------------------------


class FakeTime:
    """The daemon's clock and its wait: a wait passes at once and moves
    the clock by its timeout; ``persist()`` is one of ``stalls`` long."""

    def __init__(self, stalls):
        self.now, self.stalls, self.ticks = 100.0, list(stalls), []

    def clock(self):
        return self.now

    def wait(self, timeout):
        if not self.stalls:
            return True     # the stop event
        self.now += timeout
        return False

    def persist(self):
        self.ticks.append(round(self.now - 100.0, 6))
        self.now += self.stalls.pop(0)


@pytest.mark.parametrize("stalls, ticks, skipped", [
    # the period does not drift with the stall: 1, 2, 3, not 1, 2.3, 3.6
    ([0.3, 0.3, 0.3, 0.3], [1.0, 2.0, 3.0, 4.0], 0),
    # ticks 2 and 3 come due under the first one's barrier: skipped,
    # counted, and the next is on the grid again
    ([2.5, 0.1, 0.1], [1.0, 4.0, 5.0], 2),
    # a persist that ends on a tick's instant: that tick is past
    ([1.0, 0.0, 0.0], [1.0, 3.0, 4.0], 1),
], ids=["fixed_rate", "two_skipped", "ends_on_a_tick"])
def test_the_daemon_ticks_at_a_fixed_rate(tmp_path, stalls, ticks, skipped):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('ticks') @app:playback @app:persist(interval='1 sec', "
            f"mode='async', location='{tmp_path}') " + BODY)
        assert rt.app_context.persist_interval_ms == 1_000
        fake = FakeTime(stalls)
        rt.persist = fake.persist
        rt._start_persist_daemon(clock=fake.clock, wait=fake.wait)
        rt._persist_thread.join(10)
        assert not rt._persist_thread.is_alive()
        assert fake.ticks == ticks
        assert rt.statistics()[
            "io.siddhi.SiddhiApps.ticks.Siddhi.Durability.ticks."
            "persist_ticks_skipped"] == skipped
    finally:
        m.shutdown()


# -- the clock in the tree ---------------------------------------------------


def test_the_tree_carries_the_clock_of_the_state_not_the_senders(tmp_path):
    """``send_batch`` moves the timestamp generator before it takes the
    process lock: a capture that wins the lock then reads a generator
    one batch ahead of the state it captures.  The tree's clock is the
    last batch the state has APPLIED."""
    from siddhi_tpu.core.event import EventBatch

    def batch(ts):
        return EventBatch("S", ["k", "v"], {
            "k": np.arange(4, dtype=np.int64), "v": np.ones(4)},
            np.full(4, ts, dtype=np.int64))

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('clock') @app:playback @app:persist(mode='sync', "
            f"location='{tmp_path}') " + BODY)
        rt.start()
        ctx, h = rt.app_context, rt.get_input_handler("S")
        assert rt.applied_time() == -1
        h.send_batch(batch(5_000))
        assert rt.applied_time() == 5_000
        with ctx.process_lock:      # the capture has won the lock
            sender = threading.Thread(target=h.send_batch,
                                      args=(batch(6_000),))
            sender.start()
            deadline = time.monotonic() + 10
            while ctx.timestamp_generator.current_time() < 6_000:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            tree = pickle.loads(rt._snapshot_service().full_snapshot())
            revision = rt.persist()
        sender.join(10)
        assert not sender.is_alive()
        assert tree["clock"] == 5_000 and rt.applied_time() == 6_000
        with open(os.path.join(tmp_path, "clock", revision + ".ckpt",
                               "MANIFEST.json")) as f:
            assert json.load(f)["clock"] == 5_000
        # restore sets it, backwards too: the state is the revision's
        assert rt.restore_last_revision() == revision
        assert rt.applied_time() == 5_000
        assert ctx.timestamp_generator.current_time() == 5_000
        rt.shutdown()
        # a tree from before the clock was kept restores none
        rt2 = m.create_siddhi_app_runtime(
            "@app:name('clock2') @app:playback " + BODY)
        rt2.start()
        del tree["clock"]
        tree["app"] = "clock2"
        rt2.restore(pickle.dumps(tree))
        assert rt2.applied_time() == -1
        rt2.shutdown()
    finally:
        m.shutdown()


# -- restore and replay against the plain automaton --------------------------


@pytest.fixture(scope="module", params=sorted(ENGINES))
def captured(request, tmp_path_factory):
    """One runtime sent the warm-up pass and a pass of nine batches,
    persisting after each: nine revisions, ``n_c`` 0 to 8.  ``staged``:
    the ingest stage keeps a gate behind every ``send_batch``'s return
    (PR 34's rule, forced), so each barrier falls on a batch in
    flight.  And one recovering runtime for the cases to share."""
    engine = request.param
    config = config_for(engine, keep="9")
    schedule = schedule_of(config, tmp_path_factory.mktemp("ckpt_" + engine))
    patch = pytest.MonkeyPatch()
    if engine == "staged":
        from siddhi_tpu.core import ingest_stage

        patch.setattr(ingest_stage.PipelineRule, "arrival",
                      lambda self, think_s: setattr(self, "pipelined", True))
        patch.setattr(ingest_stage, "IDLE_CYCLES", 1e9)
        patch.setattr(ingest_stage, "IDLE_MAX_S", 3600.0)
    m, rt, got, errors = runtime(config, schedule)
    try:
        send = rt.get_input_handler(config["stream"]).send_batch
        for n in range(-PER_PASS, 0):
            send(schedule.batch(n))
        revisions = []
        for n in range(PER_PASS):
            send(schedule.batch(n))
            revisions.append(rt.persist())
            assert rt.wait_for_persist(revisions[-1], 60) == "committed"
        for n in range(PER_PASS, 2 * PER_PASS):
            send(schedule.batch(n))
        rt.drain_device_emits()
        if engine == "staged":
            assert engine_of(rt).ingest_stats.pipeline_entries > 0
        assert rt.lowering() == {"bench": "dense"} and not errors
        rt.shutdown()
    finally:
        m.shutdown()
        patch.undo()
    m2, rt2, got2, errors2 = runtime(config, schedule)
    yield config, schedule, revisions, got.rows, (rt2, got2, errors2)
    rt2.shutdown()
    m2.shutdown()


@pytest.mark.parametrize("n_c", range(PER_PASS))
def test_a_revision_restored_and_replayed_owes_what_the_automaton_owes(
        captured, n_c):
    config, schedule, revisions, unbroken, (rt, got, errors) = captured
    last = schedule.last_of_replay(n_c)
    assert last == 2 * PER_PASS - 1
    del got.rows[:]
    rt.restore_revision(revisions[n_c])
    assert schedule.batch_of(rt.applied_time()) == n_c
    send = rt.get_input_handler(config["stream"]).send_batch
    for n in range(n_c + 1, last + 1):
        send(schedule.batch(n))
    rt.drain_device_emits()
    want = owed(schedule, 0, last, after=n_c)
    assert len(want) > 10 and not errors
    assert sorted(got.rows) == want
    assert in_order_by_key(schedule, got.rows)
    # and what the runtime that was never interrupted delivered
    assert sorted(r for r in unbroken
                  if r[0] > schedule.ts_of(n_c)) == want


def test_the_schedules_recover_restores_the_newest_into_a_fresh_runtime(
        captured, tmp_path):
    """``recover`` as the cell's reference calls it, on a copy of the
    store: a fresh manager and runtime, the newest revision, the
    batches to the end of the next pass; the revisions gone after."""
    config, schedule, revisions, _unbroken, _shared = captured
    location = str(tmp_path / "copy")
    shutil.copytree(schedule.location, location)
    n_c, rows = schedule.recover(location)
    assert n_c == PER_PASS - 1 and schedule.restore_s > 0
    got = sorted(zip(rows["ts"].tolist(), rows["v1"].tolist(),
                     rows["v16"].tolist()))
    assert got == owed(schedule, 0, 2 * PER_PASS - 1, after=n_c)
    assert open_store(location, 9).revisions(config["name"]) == []
    # nothing left to restore: not an answer, None
    assert schedule.recover(location) is None


# -- spans and counters ------------------------------------------------------


def logical_state(pattern):
    """The dense runtime's state in its logical form, host copies."""
    return pattern.engine.layout.unpack(pattern.state)


def test_a_checkpoints_child_spans_and_counters_carry_its_bytes(tmp_path):
    """What the barrier holds and what the writer does, by their spans:
    under ``persist.capture`` the drain and the freeze of the one
    element, and no fetch (the dense engine snapshots on the device);
    under ``persist.write`` the wait for each logical field's transfer,
    the pickle of what stays in band, and a write and a hash a file."""
    config = config_for("dense")
    config["recover_header"] += " @app:trace(sample='1')"
    schedule = schedule_of(config, tmp_path)
    m, rt, _got, errors = runtime(config, schedule)
    try:
        send = rt.get_input_handler(config["stream"]).send_batch
        for n in range(-PER_PASS, 0):
            send(schedule.batch(n))
        revision = rt.persist()
        assert rt.wait_for_persist(revision, 60) == "committed"
        spans = list(rt.app_context.tracer.recorder.spans())
        logical = logical_state(engine_of(rt))
        stats = {k.rsplit(".", 1)[-1]: v for k, v in rt.statistics().items()
                 if ".Durability." in k}
        rt.shutdown()
    finally:
        m.shutdown()
    assert not errors
    by_stage = collections.defaultdict(list)
    for s in spans:
        if s[1].startswith("persist."):
            by_stage[s[1]].append(s)
    (capture,), (write,) = by_stage["persist.capture"], by_stage["persist.write"]
    assert capture[2] == write[2] == "persist"
    assert capture[0] < write[0] and capture[4] <= write[3]
    rev_dir = os.path.join(tmp_path, config["name"], revision + ".ckpt")
    with open(os.path.join(rev_dir, "MANIFEST.json")) as f:
        files = [el["file"] for el in json.load(f)["elements"]]  # as written
    for parent, children in ((capture, {"drain": 1, "freeze": 1}),
                             (write, {"fetch": len(logical), "pickle": 1,
                                      "store": len(files),
                                      "hash": len(files)})):
        for child, n in children.items():
            found = by_stage["persist." + child]
            assert len(found) == n, child
            for c in found:
                # the parent's cycle id, and inside its interval
                assert c[0] == parent[0]
                assert parent[3] <= c[3] <= c[4] <= parent[4]
    assert not by_stage["persist.unpack"]
    # the fetch is the writer's: every logical field, by reference
    state_bytes = sum(a.nbytes for a in logical.values())
    assert sorted(c[5] for c in by_stage["persist.fetch"]) == sorted(
        a.nbytes for a in logical.values())
    assert stats["persist_deferred_bytes"] == state_bytes > 0
    assert stats["persist_fetch_bytes"] == 0
    assert stats["capture_fallback_elements"] == 0
    sizes = [os.path.getsize(os.path.join(rev_dir, f)) for f in files]
    assert files[0] == "0000.blob" and len(files) > len(logical)
    assert by_stage["persist.pickle"][0][5] == sizes[0]
    assert ([c[5] for c in by_stage["persist.store"]] == sizes
            == [c[5] for c in by_stage["persist.hash"]])
    assert stats["bytes_written"] == sum(sizes) > state_bytes
    assert stats["persist_ticks_skipped"] == 0
    assert stats["persist_commits"] == 1 and stats["persist_failures"] == 0


@pytest.mark.parametrize("engine", ["dense", "devices4"])
def test_a_capture_outlives_the_steps_that_donate_the_state(engine, tmp_path):
    """The steps donate the resident rows, so what a capture keeps by
    reference has to be buffers of its own.  The writer held before it
    materialises anything, the batches that follow change the captured
    keys' rows; the revision then committed restores, exactly, the
    state at the capture, and the fetch was all the writer's."""
    config = config_for(engine)
    config["recover_header"] += " @app:faults(seed='1')"
    schedule = schedule_of(config, tmp_path)
    n_c = 2
    m, rt, got, errors = runtime(config, schedule)
    try:
        send = rt.get_input_handler(config["stream"]).send_batch
        for n in range(-PER_PASS, n_c + 1):
            send(schedule.batch(n))
        rt.drain_device_emits()
        pattern = engine_of(rt)
        at_capture = logical_state(pattern)
        keys_at_capture = dict(pattern._key_rows)
        used_at_capture = pattern._row_last_used.copy()

        fi = rt.app_context.fault_injector
        check, held, go = fi.check, threading.Event(), threading.Event()

        def hold_the_writer(site):
            if site == "persist.write":
                held.set()
                assert go.wait(60)
            check(site)

        fi.check = hold_the_writer
        revision = rt.persist()
        assert held.wait(60)
        for n in range(n_c + 1, 2 * PER_PASS):
            send(schedule.batch(n))
        rt.drain_device_emits()
        later = logical_state(pattern)
        assert any((later[k] != v).any() for k, v in at_capture.items())
        durability = rt._durability_stats()
        assert durability.persist_deferred_bytes == 0     # nothing fetched
        go.set()
        assert rt.wait_for_persist(revision, 60) == "committed"
        assert durability.persist_fetch_bytes == 0
        assert durability.persist_deferred_bytes == sum(
            a.nbytes for a in at_capture.values())
        assert durability.capture_fallback_elements == 0 and not errors
        unbroken = sorted(got.rows)
        rt.shutdown()
    finally:
        m.shutdown()

    m, rt, got, errors = runtime(config, schedule)
    try:
        assert rt.restore_last_revision() == revision
        assert schedule.batch_of(rt.applied_time()) == n_c
        pattern = engine_of(rt)
        restored = logical_state(pattern)
        assert sorted(restored) == sorted(at_capture)
        for k, v in at_capture.items():
            assert restored[k].dtype == v.dtype and (restored[k] == v).all(), k
        assert pattern._key_rows == keys_at_capture
        assert pattern._index is not None
        assert (pattern._row_last_used == used_at_capture).all()
        send = rt.get_input_handler(config["stream"]).send_batch
        for n in range(n_c + 1, 2 * PER_PASS):
            send(schedule.batch(n))
        rt.drain_device_emits()
        want = owed(schedule, 0, 2 * PER_PASS - 1, after=n_c)
        assert sorted(got.rows) == want and len(want) > 10 and not errors
        assert [r for r in unbroken if r[0] > schedule.ts_of(n_c)] == want
        rt.shutdown()
    finally:
        m.shutdown()


# -- the daemon beside a sending thread --------------------------------------


def test_the_daemons_drain_delivers_in_order_beside_a_sending_thread(
        tmp_path, force_pipelined):
    """The app's own daemon checkpoints every few milliseconds while
    another thread sends, a gate staged behind every send: the drain
    under the barrier runs on the daemon's thread, and every row still
    reaches the callback once, a key's rows in event-time order, and
    the newest revision restores and replays like any other."""
    force_pipelined(idle=False)
    config = config_for("dense")
    schedule = schedule_of(config, tmp_path, seed=49)
    schedule.size["interval"] = "5 millisec"
    m, rt, got, errors = runtime(config, schedule, header="header")
    passes = 6
    try:
        send = rt.get_input_handler(config["stream"]).send_batch

        def work():
            for n in range(-PER_PASS, passes * PER_PASS):
                send(schedule.batch(n))

        sender = threading.Thread(target=work)
        sender.start()
        sender.join(120)
        assert not sender.is_alive()
        rt.shutdown()
        stats = {k.rsplit(".", 1)[-1]: v for k, v in rt.statistics().items()
                 if ".Durability." in k}
    finally:
        m.shutdown()
    assert not errors and stats["persist_failures"] == 0
    assert stats["persist_commits"] >= 2
    assert (stats["persists_async"]
            == stats["persist_commits"] + stats["persists_coalesced"])
    want = [r for p in range(-1, passes) for r in owed(
        schedule, p * PER_PASS, (p + 1) * PER_PASS - 1, after=-PER_PASS - 1)]
    assert sorted(got.rows) == sorted(want) and len(want) > 100
    assert in_order_by_key(schedule, got.rows)
    n_c, rows = schedule.recover(str(tmp_path))
    assert -PER_PASS <= n_c < passes * PER_PASS
    first = n_c - (n_c + PER_PASS) % PER_PASS
    assert sorted(zip(rows["ts"].tolist(), rows["v1"].tolist(),
                      rows["v16"].tolist())) == owed(
        schedule, first, schedule.last_of_replay(n_c), after=n_c)


# -- the barrier -------------------------------------------------------------

TWO_ELEMENTS = (
    "define stream Txn (key long, v double); "
    "partition with (key of Txn) begin @info(name='bench') "
    "from every e1=Txn[v > 0.0] -> e2=Txn[v > 1.0 and v > e1.v] "
    "select e1.v as v1, e2.v as v2 insert into Alerts; end; "
    "@info(name='tally') from Alerts#window.length(4096) "
    "select count() as n insert into Tally;")


def test_no_batch_slips_between_the_drain_and_the_capture(
        tmp_path, force_pipelined):
    """The barrier is the process lock from the emit drain to the end
    of the capture.  With the drain outside it, a batch sent from
    another thread in between leaves its gate staged; the pattern's own
    ``snapshot()`` then drains its rows into a window query the walk
    has captured already: the revision holds the pattern after the
    batch and the window before it, and a runtime restored from it
    counts short for ever after."""
    from siddhi_tpu.core.event import EventBatch

    force_pipelined(idle=False)
    header = ("@app:name('barrier') @app:playback @app:execution('tpu', "
              f"partitions='256') @app:persist(mode='sync', "
              f"location='{tmp_path}') ")

    def batch(j):
        return EventBatch("Txn", ["key", "v"], {
            "key": np.arange(64, dtype=np.int64),
            "v": np.full(64, j + 0.5)}, np.full(64, 1_000 + 10 * j,
                                                dtype=np.int64))

    def settle(rt):
        # the pattern's staged rows reach the window query's own device
        # runtime, whose rows the second pass delivers
        rt.drain_device_emits()
        rt.drain_device_emits()

    def build():
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(header + TWO_ELEMENTS)
        tally = []
        rt.add_callback("Tally", lambda evs: tally.extend(
            (e.timestamp, e.data[0]) for e in evs))
        rt.start()
        return m, rt, tally, rt.get_input_handler("Txn").send_batch

    m, rt, tally, send = build()
    try:
        for j in range(3):
            send(batch(j))
        drain, slipped = rt.drain_device_emits, []

        def drain_then_a_sender_tries():
            drain()
            if not slipped:
                slipped.append(threading.Thread(target=send,
                                                args=(batch(3),)))
                slipped[0].start()
                slipped[0].join(0.5)    # it waits at the barrier

        rt.drain_device_emits = drain_then_a_sender_tries
        revision = rt.persist()
        slipped[0].join(30)
        assert not slipped[0].is_alive()
        rt.drain_device_emits = drain
        for j in (4, 5):
            send(batch(j))
        settle(rt)
        rt.shutdown()
    finally:
        m.shutdown()
    assert [n for _ts, n in tally][-1] == 5 * 64

    m, rt, replayed, send = build()
    try:
        assert rt.restore_last_revision() == revision
        clock = rt.applied_time()
        assert clock == 1_020    # the three batches sent before it
        for j in range(6):
            if 1_000 + 10 * j > clock:
                send(batch(j))
        settle(rt)
        rt.shutdown()
    finally:
        m.shutdown()
    assert replayed == [r for r in tally if r[0] > clock] and replayed
