"""What lets a deployment land as new files only: references looked up
by file, a batch sent to its own stream, and the trace the layer
readers are handed (scopes, host spans, idle gaps).  CPU, seconds; the
one test that starts a child runs the benchmark's own command on a
temporary copy of ``benchmark/``, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import ast
import glob
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(BENCH, "layers")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import scopes  # noqa: E402  (benchmark/layers/scopes.py)
from lib import check, deploy, xplane  # noqa: E402

US = 1_000   # the trace's clock is nanoseconds
REFERENCES = sorted(glob.glob(os.path.join(BENCH, "references", "*.py")))


# --- references by file -----------------------------------------------------

@pytest.mark.parametrize("path", REFERENCES, ids=os.path.basename)
def test_a_reference_imports_nothing_of_the_program(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names]
    imported += [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
    assert imported and not [m for m in imported
                             if m.split(".")[0] == "siddhi_tpu"]
    assert callable(check.load_reference(
        os.path.splitext(os.path.basename(path))[0]))


def test_every_configuration_names_a_reference_file():
    kinds = {os.path.splitext(os.path.basename(p))[0] for p in REFERENCES}
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        with open(path) as f:
            assert json.load(f)["reference"]["kind"] in kinds, path
    with open(os.path.join(BENCH, "lib", "check.py")) as f:
        assert "def ref_" not in f.read()
    with open(os.path.join(BENCH, "run.py")) as f:
        assert "getattr(check" not in f.read()


# A deployment as a later PR brings it: two input streams, a reference
# kind, a generator and a mix that the benchmark does not have.
TWO_STREAMS = {
    "configs/two_streams.json": json.dumps({
        "source": "a test", "reduced": {},
        "app": "define stream A (key long, v double); define stream B (key "
               "long, v double); partition with (key of A, key of B) begin "
               "@info(name='p') from every e1=A[v > 0.0] -> e2=B[v > e1.v] "
               "within 10 min select e1.v as v1, e2.v as v2 insert into Out;"
               " end;",
        "header": "@app:playback @app:execution('tpu', "
                  "partitions='{partitions}')",
        "stream": ["A", "B"], "output": "Out", "chips": 1,
        "full": {"partitions": 4096}, "rehearsal": {"partitions": 4096},
        "expect": {"lowering": {"p": "dense"}, "sharded": False,
                   "state_devices": 1},
        "control": {"round_bf16": ["v"]},
        "reference": {"kind": "pairs"}}),
    "traffic/pairs_closed.json": json.dumps({
        "generator": "pairs", "loop": "closed", "full": {"batch": 256},
        "rehearsal": {"batch": 256}}),
    "generators/pairs.py": '''
import numpy as np


class Pairs:
    """Batch by batch A, B, A, B: every key once in each, so every B
    batch owes one row a key."""
    warmup = 2

    def __init__(self, batch):
        self.batch_events = batch
        self.lane = np.arange(batch, dtype=np.int64)

    def stream_of(self, n):
        return "AB"[(n + self.warmup) % 2]

    def batch(self, n):
        from siddhi_tpu.core.event import EventBatch

        stream = self.stream_of(n)
        ts = 1_000 + (n + self.warmup) * self.batch_events + self.lane
        return EventBatch(stream, ["key", "v"], {
            "key": self.lane.copy(),
            "v": np.full(self.batch_events, 1.0 + (stream == "B"))}, ts)

    def batch_of(self, ts):
        return ((np.asarray(ts, dtype=np.int64) - 1_000)
                // self.batch_events - self.warmup)

    def keep(self, n):
        return True


def make(seed, config, traffic, rehearsal):
    return Pairs(traffic["rehearsal" if rehearsal else "full"]["batch"])
''',
    "references/pairs.py": '''
import numpy as np


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    owed = {n: schedule.batch_events * (schedule.stream_of(n) == "B")
            for n in range(n_sent)}
    bad = {n for n, c in owed.items() if collector.counts.get(n, 0) != c}
    rows = collector.rows()
    off = 0 if rows is None else int(
        (rows["v1"] != 1.0).sum() + (rows["v2"] != 2.0).sum())
    return bad, [("batches whose row count differs", len(bad), 0),
                 ("rows whose payload differs", off, 0),
                 ("rows owed: none", int(not sum(owed.values())), 0)]
''',
}


def _copy_with(tmp_path, files, kind):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` with ``files``
    added, a configuration whose reference is ``kind`` and a cell
    ``two_streams.closed``; no file of the original is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", ".pytest_cache"))
    before = {p: open(p, "rb").read() for p in glob.glob(
        str(root / "benchmark" / "**" / "*.*"), recursive=True)}
    for rel, text in files.items():
        if rel.startswith("configs/"):
            text = json.dumps({**json.loads(text), "reference": {"kind": kind}})
        (root / "benchmark" / rel).write_text(text)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "two_streams", "source": "a test", "reduced": [],
        "file": "benchmark/configs/two_streams.json", "why": "a test"})
    spec["workloads"].append({
        "name": "two_streams.closed", "config": "two_streams",
        "traffic": "pairs_closed", "chips": 1, "why": "a test"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "events_per_s")["workloads"].append(
             "two_streams.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(open(p, "rb").read() == was for p, was in before.items())
    return root


def _rehearse_copy(root, tmp_path, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "two_streams.closed", "--seed", "3", "--seconds", "1",
         "--rehearsal", *extra],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)


def test_a_deployment_lands_as_new_files(tmp_path):
    """A reference kind, a generator, a mix and a two-stream
    configuration dropped into a copy of ``benchmark/`` run to a result
    line, and both streams' batches reach the app."""
    root = _copy_with(tmp_path, TWO_STREAMS, "pairs")
    done = _rehearse_copy(root, tmp_path)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True, lines[-12:]
    assert line["failed"] == 0 and line["attempted"] >= 4 * 256
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}
    assert any(ln.startswith("compared: rows whose payload differs: 0")
               for ln in lines)
    # the control's wrapper routes by stream too: v = 2.0 survives
    # bfloat16, so the rounded run is still sound
    ctl = json.loads(_rehearse_copy(
        root, tmp_path, "--control", "bf16").stdout.strip().splitlines()[-1])
    assert ctl["control"] == "bf16" and ctl["attempted"] >= 4 * 256


def test_a_kind_with_no_file_stops_the_run_before_anything_is_built(tmp_path):
    files = {k: v for k, v in TWO_STREAMS.items()
             if not k.startswith("references/")}
    root = _copy_with(tmp_path, files, "no_such_kind")
    done = _rehearse_copy(root, tmp_path)
    assert done.returncode != 0 and not done.stdout.strip()
    assert os.path.join("references", "no_such_kind.py") in done.stderr
    assert "Traceback" not in done.stderr
    assert not os.path.exists(tmp_path / "jax_cache")   # JAX never came up


# --- a batch goes to its own stream -----------------------------------------

class Handler:
    def __init__(self):
        self.got = []

    def send_batch(self, batch):
        self.got.append(batch)


def test_one_stream_gets_the_bound_send_batch_itself():
    h = Handler()
    send = deploy.sender({"S": h})
    assert send.__self__ is h and send.__func__ is Handler.send_batch
    assert send == h.send_batch


def test_a_two_stream_schedule_reaches_both_handlers():
    a, b = Handler(), Handler()
    send = deploy.sender({"A": a, "B": b})
    batches = [types.SimpleNamespace(stream_id="AB"[n % 2], n=n)
               for n in range(5)]
    for batch in batches:
        send(batch)
    assert [x.n for x in a.got] == [0, 2, 4] and [x.n for x in b.got] == [1, 3]
    with pytest.raises(KeyError):
        send(types.SimpleNamespace(stream_id="C"))


# --- the trace --------------------------------------------------------------

def test_scope_of_takes_the_innermost_siddhi_scope():
    assert xplane.scope_of(
        "jit(rounds)/jit(main)/siddhi.dense.rounds/while/body/"
        "siddhi.dense.run/siddhi.dense.gather/gather") == "siddhi.dense.gather"
    assert xplane.scope_of("jit(step)/siddhi.window.slot/add") == (
        "siddhi.window.slot")
    assert xplane.scope_of("jit(step)/jit(main)/mul") is None
    # a kernel's own call is set apart from the copies that bear its name
    run = "jit(rounds)/siddhi.dense.run/while/body/pallas_call:"
    assert xplane.scope_of(run, "custom-call") == (
        "siddhi.dense.run/pallas_call")
    assert xplane.scope_of(run, "data formatting") == "siddhi.dense.run"
    assert xplane.scope_of("jit(f)/pallas_call:", "custom-call") is None
    assert xplane.scope_of("") is None


def _trace(ops, host=(), batches=2, planes=1):
    device = {f"/device:TPU:{i}": list(ops) for i in range(planes)}
    return xplane.Trace(device, [(0, 100 * US, xplane.MARK), *host], batches)


NESTED = [   # a while of the rounds program round its body's operations
    (0, 40 * US, "while", "siddhi.dense.rounds"),
    (5 * US, 15 * US, "gather", "siddhi.dense.gather"),
    (15 * US, 30 * US, "fusion", "siddhi.dense.advance"),
    # an asynchronous copy beside a fusion, both under one scope
    (50 * US, 70 * US, "copy-start", "siddhi.dense.scatter"),
    (60 * US, 80 * US, "fusion.2", "siddhi.dense.scatter"),
    (90 * US, 95 * US, "convert", None)]


def test_scopes_give_self_time_that_adds_up_to_busy():
    trace = _trace(NESTED)
    got = trace.scope_seconds()
    assert got == {
        "siddhi.dense.rounds": pytest.approx(15e-6),   # 40 less 10 and 15
        "siddhi.dense.gather": pytest.approx(10e-6),
        "siddhi.dense.advance": pytest.approx(15e-6),
        "siddhi.dense.scatter": pytest.approx(30e-6),  # 50-80, once
        None: pytest.approx(5e-6)}
    busy = xplane.reduce(trace)["busy_s"]
    assert busy == pytest.approx(75e-6) == pytest.approx(sum(got.values()))


def test_operations_of_one_start_and_end_are_told_apart_without_their_scope():
    # a scope may be None beside a string: the two are never compared
    ops = [(10 * US, 10 * US, "copy-done", None),
           (10 * US, 10 * US, "bitcast", "siddhi.dense.gather"),
           (-5 * US, 20 * US, "fusion", None),       # both clipped to start
           (-9 * US, 20 * US, "fusion.1", "siddhi.dense.scatter"),   # at lo
           (30 * US, 40 * US, "all-reduce", "siddhi.shard.count_psum"),
           (30 * US, 40 * US, "all-reduce.1", None)]
    got = _trace(ops).scope_seconds()
    assert sum(got.values()) == pytest.approx(30e-6)
    assert sum(got.values()) == pytest.approx(
        xplane.reduce(_trace(ops))["busy_s"])


def test_scopes_are_clipped_to_the_window_and_averaged_over_planes():
    ops = [(-10 * US, 10 * US, "fusion", "siddhi.window.slot"),
           (95 * US, 120 * US, "fusion.1", "siddhi.window.emit")]
    trace = xplane.Trace({"/device:TPU:0": ops, "/device:TPU:1": ops[:1],
                          "/device:TPU:2": []},
                         [(0, 100 * US, xplane.MARK)], 1)
    assert trace.scope_seconds() == {
        "siddhi.window.slot": pytest.approx(10e-6),
        "siddhi.window.emit": pytest.approx(2.5e-6)}
    assert xplane.reduce(trace)["busy_s"] == pytest.approx(12.5e-6)


def test_the_scope_reader_names_scopes_and_returns_nothing_for_none():
    wanted = ["events." + n + "_ms_per_batch" for n in (
        "gather", "advance", "scatter", "rounds", "run", "count_psum",
        "unscoped", "send", "device_busy", "window_slot")] + [
        "events.rounds_per_batch"]
    run = types.SimpleNamespace(wanted=wanted, trace=_trace(NESTED))
    assert scopes.read(run) == {
        "events.gather_ms_per_batch": pytest.approx(5e-3),
        "events.advance_ms_per_batch": pytest.approx(7.5e-3),
        "events.scatter_ms_per_batch": pytest.approx(15e-3),
        "events.rounds_ms_per_batch": pytest.approx(7.5e-3),
        "events.unscoped_ms_per_batch": pytest.approx(2.5e-3)}
    ops = [(0, 10 * US, "fusion", "siddhi.window.slot"),
           (20 * US, 30 * US, "all-reduce", "siddhi.shard.count_psum")]
    run = types.SimpleNamespace(
        wanted=["rows.window_slot_ms_per_batch", "rows.slot_ms_per_batch",
                "events.count_psum_ms_per_batch",
                "rows.unscoped_ms_per_batch"],
        trace=_trace(ops, batches=1))
    assert scopes.read(run) == {   # all of it scoped: no unscoped value
        "rows.window_slot_ms_per_batch": pytest.approx(10e-3),
        "events.count_psum_ms_per_batch": pytest.approx(10e-3)}
    # a scope's time holds its kernel's own call, which is read alone too
    ops = [(0, 30 * US, "while", "siddhi.dense.run"),
           (5 * US, 15 * US, "body.7", "siddhi.dense.run/pallas_call"),
           (15 * US, 20 * US, "copy", "siddhi.dense.run")]
    run = types.SimpleNamespace(
        wanted=["events.run_ms_per_batch", "events.run_kernel_ms_per_batch",
                "events.gather_kernel_ms_per_batch"],
        trace=_trace(ops, batches=1))
    assert scopes.read(run) == {
        "events.run_ms_per_batch": pytest.approx(30e-3),
        "events.run_kernel_ms_per_batch": pytest.approx(10e-3)}
    # an executable older than the scopes: nothing, not even unscoped
    bare = [(s, e, name, None) for s, e, name, _scope in NESTED]
    for trace in (_trace(bare), _trace([]), _trace(NESTED, batches=0), None):
        assert scopes.read(types.SimpleNamespace(
            wanted=wanted, trace=trace)) == {}
    assert _trace(bare).scope_seconds() == {}
    assert xplane.reduce(_trace(bare))["busy_s"] == pytest.approx(75e-6)


def test_idle_gaps_go_to_the_innermost_span_siddhi_spans_included():
    host = [(0, 90 * US, "bench.send_batch"),
            (2 * US, 20 * US, "siddhi.intern"),
            (20 * US, 50 * US, "siddhi.ingest"),
            (30 * US, 45 * US, "siddhi.put"),
            (50 * US, 80 * US, "siddhi.step_wait")]
    ops = [(50 * US, 80 * US, "fusion", "siddhi.dense.scatter")]
    r = xplane.reduce(_trace(ops, host))
    assert dict(r["idle_gaps"]) == {   # the gaps: 0-50 and 80-100
        "bench.send_batch": pytest.approx(12e-6),      # 0-2 and 80-90
        "siddhi.intern": pytest.approx(18e-6),
        "siddhi.ingest": pytest.approx(15e-6),         # 20-30 and 45-50
        "siddhi.put": pytest.approx(15e-6),
        "none": pytest.approx(10e-6)}
    assert r["device_ops"] == [["siddhi.dense.scatter fusion",
                                pytest.approx(30e-6)]]
    assert xplane.attribute([(0, 100 * US)], host)["siddhi.step_wait"] == (
        pytest.approx(30e-6))


def test_the_file_is_read_into_operations_scopes_and_host_spans(tmp_path):
    """A hand-made ``.xplane.pb``: the framework op name as a string and
    as a reference to a stat's name, an operation with no such stat,
    lines that are not operations, host events that are not spans."""
    space = xplane._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for sid, name in {1: "tf_op", 3: "hlo_category",
                      2: "jit(step)/siddhi.dense.rounds/while"}.items():
        dev.stat_metadata.add(key=sid).value.name = name
    named = {7: "jit(step)/siddhi.dense.gather/gather:", 8: None, 9: "",
             10: "jit(rounds)/siddhi.dense.run/while/body/pallas_call:"}
    for mid, op_name in named.items():
        meta = dev.event_metadata.add(key=mid).value
        meta.name = f"%op.{mid} = s32[8]"
        meta.stats.add(metadata_id=3, str_value=(
            "custom-call" if mid == 10 else "data formatting"))
        if op_name is None:
            meta.stats.add(metadata_id=1, ref_value=2)
        elif op_name:
            meta.stats.add(metadata_id=1, str_value=op_name)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1_000)
    for mid, (off_ns, dur_ns) in zip(named, [(0, 500), (500, 250.5),
                                             (900, 100), (800, 50)]):
        ops.events.add(metadata_id=mid, offset_ps=int(off_ns * 1e3),
                       duration_ps=int(dur_ns * 1e3))
    dev.lines.add(name="XLA Modules", timestamp_ns=1_000).events.add(
        metadata_id=7, offset_ps=0, duration_ps=10**9)
    host = space.planes.add(name="/host:CPU")
    for mid, name in {1: "bench.window", 2: "siddhi.put", 3: "PjitFunction"}.items():
        host.event_metadata.add(key=mid).value.name = name
    for tid, mids in (("main", (1, 2, 3)), ("emit", (2,))):
        ln = host.lines.add(name=tid, timestamp_ns=900)
        for mid in mids:
            ln.events.add(metadata_id=mid, offset_ps=mid * 10**5,
                          duration_ps=10**6)
    (tmp_path / "plugins").mkdir()
    (tmp_path / "plugins" / "t.xplane.pb").write_bytes(
        space.SerializeToString())
    trace = xplane.read_dir(str(tmp_path), batches=3)
    assert trace.device == {"/device:TPU:0": [
        (1_000, 1_500, "%op.7 = s32[8]", "siddhi.dense.gather"),
        (1_500, 1_750, "%op.8 = s32[8]", "siddhi.dense.rounds"),
        (1_900, 2_000, "%op.9 = s32[8]", None),
        (1_800, 1_850, "%op.10 = s32[8]", "siddhi.dense.run/pallas_call")]}
    assert sorted(trace.host) == [
        (1_000, 2_000, "bench.window"), (1_100, 2_100, "siddhi.put"),
        (1_100, 2_100, "siddhi.put")]
    assert (trace.lo, trace.hi, trace.batches) == (1_000, 2_000, 3)
    assert xplane.read_dir(str(tmp_path / "plugins" / "none")) is None
