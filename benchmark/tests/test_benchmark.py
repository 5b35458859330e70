"""Rehearsal tests of the benchmark: tiny sizes, CPU, in-process.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They check answers, counts and the shape of the result line; never a
time.  No test starts a child process or describes a TPU topology.
"""

import itertools
import json
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "jax" not in sys.modules and "host_platform_device_count" not in (
        os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH, os.path.join(BENCH, "generators")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402  (benchmark/run.py)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
PACED = [w["name"] for w in SPEC["workloads"] if w["traffic"].endswith(
    "_paced")]


def rehearse(capsys, cell, *extra, seed=3, seconds=1):
    assert bench_run.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearsal",
                           *extra]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line_is_well_formed_and_correct(capsys, cell, trace):
    line, out = rehearse(capsys, cell, "--trace", str(trace),
                         seed=2**31 + 11, seconds=2)
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    kind = "per_layer" if trace else "end_to_end"
    # no device plane on the CPU: the readers of the trace return nothing
    owed = {m["name"]: m["unit"] for m in SPEC[kind]
            if cell in m.get("workloads", [cell])
            and m["source"] != "device_trace"}
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    assert got == owed
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert any(ln.startswith("compared: ") and "(limit " in ln for ln in out)
    assert any("programs compiled in the window: 0" in ln for ln in out)


@pytest.mark.parametrize("cell", PACED)
def test_counts_do_not_move_with_the_clock(capsys, monkeypatch, cell):
    """PR 23's fault: a host stall near the end of a run moved `failed`."""
    from lib import loops

    calm, _ = rehearse(capsys, cell)
    orig, sent = loops.run, itertools.count()

    def stalled(dep, schedule, traffic, seconds, send, profile=None):
        def slow_send(batch):
            if next(sent) % 8 == 7:
                time.sleep(0.15)      # more than five batch periods
            send(batch)
        return orig(dep, schedule, traffic, seconds, slow_send, profile)

    monkeypatch.setattr(loops, "run", stalled)
    late, _ = rehearse(capsys, cell)
    assert next(sent) > 8
    assert (late["attempted"], late["failed"], late["correct"]) == (
        calm["attempted"], 0, True) == (
        calm["attempted"], calm["failed"], calm["correct"])


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, cell):
    """The timed path broken underneath: one value of one emitted batch
    altered where the engine hands it to the callback."""
    from siddhi_tpu.core.event import EventBatch

    orig, planted = EventBatch.__init__, []
    window_from = {"Alerts": 1_000_000, "outputStream": 2_100}

    def init(self, stream_id, names, columns, timestamps, types=None):
        if (stream_id in window_from and not planted and len(timestamps)
                and timestamps[0] > window_from[stream_id]):
            name = names[1]
            columns = dict(columns)
            columns[name] = np.array(columns[name], copy=True)
            columns[name][0] += 1.0
            planted.append(name)
        orig(self, stream_id, names, columns, timestamps, types)

    monkeypatch.setattr(EventBatch, "__init__", init)
    line, _ = rehearse(capsys, cell)
    assert planted
    assert line["correct"] is False and line["failed"] > 0
    assert line["failed"] < line["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_lower_precision_control_is_not_correct(capsys, cell):
    line, out = rehearse(capsys, cell, "--control", "bf16")
    assert line["control"] == "bf16"
    assert line["correct"] is False and line["failed"] > 0


def test_the_chain_reference_equals_the_host_engine():
    """`references/pattern_chain.py`'s plain-Python chain against
    `ops/nfa.py`."""
    import fraud_cycle
    from references import pattern_chain
    from siddhi_tpu import SiddhiManager

    with open(os.path.join(BENCH, "configs", "fraud16_1m.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "fraud_cycle_paced.json")) as f:
        traffic = json.load(f)
    schedule = fraud_cycle.make(5, config, traffic, rehearsal=True)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime("@app:playback " + config["app"])
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(
            (int(schedule.batch_of(e.timestamp)), *e.data) for e in evs))
        rt.start()
        assert set(rt.lowering().values()) == {"host"}
        from siddhi_tpu.core.event import EventBatch

        keep = np.concatenate([schedule.active_keys, schedule.all_keys[:64]])
        by_key = {}
        for n in range(schedule.per_pass + 16):   # into a second pass
            b = schedule.batch(n)
            mine = np.isin(b.columns["key"], keep)
            rt.get_input_handler("Txn").send_batch(EventBatch(
                "Txn", ["key", "v"], {k: v[mine] for k, v in b.columns.items()},
                b.timestamps[mine]))
            for k, v in zip(b.columns["key"][mine], b.columns["v"][mine]):
                by_key.setdefault(int(k), []).append(
                    (n, int(b.timestamps[0]), float(v)))
        rt.shutdown()
    finally:
        m.shutdown()
    want = sorted(r for evs in by_key.values()
                  for r in pattern_chain._chain_rows(evs, 16, 600_000))
    assert len(want) > 100 and sorted(got) == want


def test_xplane_reduction_on_known_intervals():
    from lib import xplane

    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    us = 1_000
    device = {
        "/device:TPU:0": [(0, 20 * us, "copy", None),
                          (10 * us, 30 * us, "fusion", None),
                          (60 * us, 80 * us, "copy", None)],
        "/device:TPU:1": [(0, 10 * us, "copy", None)]}
    host = [(0, 100 * us, xplane.MARK), (0, 70 * us, "bench.send_batch"),
            (40 * us, 50 * us, "bench.callback")]
    r = xplane.reduce(xplane.Trace(device, host))
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx((50e-6 + 10e-6) / 2)
    assert r["device_ops"][0] == ["copy", pytest.approx(50e-6 / 2)]
    # the first plane's gaps: 30-60 us (send_batch, 10 us of it inside
    # the callback) and 80-100 us (no span open)
    assert dict(r["idle_gaps"]) == {
        "bench.send_batch": pytest.approx(20e-6),
        "bench.callback": pytest.approx(10e-6),
        "none": pytest.approx(20e-6)}
    assert xplane.reduce(xplane.Trace({"/device:TPU:0": []}, host)) is None


def test_benchmark_json_names_files_and_readers():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in SPEC["configs"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    assert all(name.match(n) for n in names), names
    assert all(unit.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert set(c["reduced"]) == set(conf["reduced"])
    for w in SPEC["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "generators", mix["generator"] + ".py"))
        assert len(w["why"]) <= 200
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
