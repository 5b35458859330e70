"""The IoT mix's own promises (``generators/iot_pass.py``), on the CPU
with no device work: seventeen batches a pass 10 ms apart and passes
1,000,000 ms apart, hot devices twice in every batch and the rest swept
once or twice a pass, the four scripts a quarter of the hot devices each
with their 34 readings, rows owed on the pass's batches 15 and 16 alone
and the same in every pass, head readings exact in float32 that name
their device, swept readings of which one in forty arms and none
completes, and a control that changes the owed rows.  Then the cell
through ``run.py`` at its rehearsal size: its line, the bytes its steps
gather, its control, a planted wrong answer (``test_benchmark.py`` runs
these for every cell of ``BENCHMARK.json`` too; here the wrong answer is
a row that goes missing).  The reference against the host engine is
tier-1's ``tests/test_iot32_reference.py``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import collections
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import ml_dtypes
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH, os.path.join(BENCH, "generators")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import fraud_pass  # noqa: E402
import iot_pass  # noqa: E402
import run as bench_run  # noqa: E402  (benchmark/run.py)
from references import pattern_chain_band  # noqa: E402

CELL = "iot32_1250k.saturated"
FLAGSHIP = "fraud16_1m.saturated"


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


SPEC = _load(ROOT, "BENCHMARK.json")
CONFIG = _load(BENCH, "configs", "iot32_1250k.json")
TRAFFIC = _load(BENCH, "traffic", "iot_pass_saturated.json")
REF = CONFIG["reference"]
# size -> (devices, events a batch, hot devices)
SIZES = {"full": (1_250_000, 131_072, 4_096), "rehearsal": (4_096, 268, 12)}
MADE = [("full", 2**31 + 5)] + [("rehearsal", s) for s in (0, 1, 2, 2**31 + 5)]


@pytest.fixture(scope="module", params=MADE, ids=lambda p: f"{p[0]}-{p[1]}")
def made(request):
    size, seed = request.param
    return size, iot_pass.make(seed, CONFIG, TRAFFIC, size == "rehearsal")


def a_pass(schedule, p=0):
    return [schedule.batch(n) for n in range(p * schedule.per_pass,
                                             (p + 1) * schedule.per_pass)]


def owed_by_device(schedule, devices, batches):
    """``device -> rows`` by the reference's chain over ``batches``."""
    by_device = {}
    for n, b in batches:
        d, temp = b.columns["device"], b.columns["temp"]
        for i in np.flatnonzero(np.isin(d, devices)):
            by_device.setdefault(int(d[i]), []).append(
                (n, int(b.timestamps[i]), float(temp[i])))
    return {d: pattern_chain_band._band_rows(
        evs, REF["states"], REF["within_ms"], REF["head_band"])
        for d, evs in by_device.items()}


def test_the_cell_names_this_mix():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "iot32_1250k", "iot_pass_saturated", 1)
    assert SPEC["workloads"][-1] is cell      # appended, as the config is
    assert SPEC["configs"][-1]["name"] == "iot32_1250k"
    assert SPEC["configs"][-1]["reduced"] == ["chips", "partitions"]
    assert TRAFFIC["generator"] == "iot_pass" and TRAFFIC["loop"] == "closed"
    assert TRAFFIC["batches_per_pass"] == 17
    assert TRAFFIC["full"] == {"batch": 131_072, "hot": 131_072 // 32}
    assert CONFIG["stream"] == "Reading" and CONFIG["output"] == "Alerts"
    assert set(CONFIG["reduced"]) == {"chips", "partitions"}
    assert CONFIG["full"]["partitions"] == 10_000_000 // 8
    assert CONFIG["app"].count("->") == 31 == REF["states"] - 1
    assert "e32=Reading[temp > 31.0 and temp > e1.temp] within 10 min" in (
        CONFIG["app"])
    assert "e1=Reading[temp > 0.0 and temp < 1.0]" in CONFIG["app"]
    assert REF["within_ms"] == 600_000 < fraud_pass.PASS_GAP_MS
    assert REF["head_band"] == [0.0, 1.0] and REF["row"] == ["t1", "t32"]
    assert CONFIG["control"]["round_bf16"] == ["temp"]
    assert CELL in next(m for m in SPEC["end_to_end"]
                        if m["name"] == "events_per_s")["workloads"]
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    on_flagship = {m["name"] for m in SPEC["per_layer"]
                   if FLAGSHIP in m.get("workloads", [])}
    new = "events.gathered_bytes_per_batch"
    # PERF.md Open question 14 for the poll
    assert listed == (on_flagship - {"events.poll_ms_per_batch"}) | {
        "setup_compile_s"}
    assert new in listed and "events.scatter_kernel_ms_per_batch" in listed
    entry = next(m for m in SPEC["per_layer"] if m["name"] == new)
    assert entry["workloads"] == [CELL, FLAGSHIP]
    assert (entry["moves"], entry["source"]) == ("events_per_s",
                                                 "program_span")


def test_a_pass_is_seventeen_batches_of_readings(made):
    size, schedule = made
    n_keys, batch, _n_hot = SIZES[size]
    assert schedule.per_pass == schedule.warmup == 17
    assert schedule.batch_events == batch
    assert len(schedule.all_keys) == n_keys == len(set(
        schedule.all_keys.tolist()))
    for b in a_pass(schedule):
        assert b.stream_id == "Reading"
        assert b.attribute_names == ["device", "temp"]
        assert len(b.timestamps) == batch
        assert b.columns["device"].dtype == np.int64
        assert b.columns["temp"].dtype == np.float32


def test_event_time_and_the_passes_twins(made):
    _size, schedule = made
    assert schedule.ts_of(0) == 1_001_000
    assert schedule.ts_of(-1) < 1_000_000   # test_benchmark plants by this
    for n in (-17, -1, 0, 5, 16, 17, 40, 1_000):
        b = schedule.batch(n)
        assert set(b.timestamps.tolist()) == {schedule.ts_of(n)}
        assert (schedule.batch_of(b.timestamps) == n).all()
        gap = schedule.ts_of(n + 1) - schedule.ts_of(n)
        assert gap == (10 if (n + 17) % 17 != 16 else 1_000_000 - 160)
        assert schedule.twin(n + 17) == (n + 17) % 17
        twin = schedule.batch(n + 17)
        for name in b.attribute_names:
            assert (twin.columns[name] == b.columns[name]).all()
        assert set((twin.timestamps - b.timestamps).tolist()) == {1_000_000}
    assert all(schedule.keep(n) for n in (-17, 0, 16, 17, 999))


def test_hot_devices_come_twice_a_batch_and_the_rest_are_swept(made):
    size, schedule = made
    n_keys, batch, n_hot = SIZES[size]
    hot = np.sort(schedule.active_keys)
    assert len(hot) == n_hot == len(set(hot.tolist()))
    n_bulk = batch - 2 * n_hot
    swept_count = collections.Counter()
    firsts, arms = [], 0
    for b in a_pass(schedule):
        devices, inverse, counts = np.unique(
            b.columns["device"], return_inverse=True, return_counts=True)
        assert (np.sort(devices[counts == 2]) == hot).all()
        assert counts.max() == 2 and len(devices) == n_bulk + n_hot
        swept_count.update(devices[counts == 1].tolist())
        # where a hot device's two readings lie: anywhere in the batch
        places = np.flatnonzero(counts[inverse] == 2)
        firsts.append(places[0])
        temps = b.columns["temp"][counts[inverse] == 1]
        assert 0.0 <= temps.min() and temps.max() < 40.0
        arms += int(((temps > 0.0) & (temps < 1.0)).sum())
    # one swept reading in forty lies inside the head's band
    assert 0.6 < arms / (17 * n_bulk / 40) < 1.5
    assert len(set(firsts)) > 8
    # the sweep wraps inside the pass: every device once or twice
    assert len(swept_count) == n_keys - n_hot
    assert set(swept_count.values()) == {1, 2}
    assert sum(swept_count.values()) == 17 * n_bulk
    if size == "full":
        assert n_bulk == 122_880 and n_keys - n_hot == 1_245_904
        assert 1.6 < 17 * n_bulk / (n_keys - n_hot) < 1.7


def test_the_four_scripts_and_the_rows_they_owe(made):
    size, schedule = made
    _n_keys, _batch, n_hot = SIZES[size]
    per_script = collections.Counter(schedule.script.tolist())
    assert per_script == {s: n_hot // 4 for s in range(4)}
    ids = {int(k): i for i, k in enumerate(schedule.key_of.tolist())} if (
        size == "rehearsal") else None
    # a hot device's 34 readings, in arrival order
    readings = collections.defaultdict(list)
    for b in a_pass(schedule):
        d, temp = b.columns["device"], b.columns["temp"]
        for i in np.flatnonzero(np.isin(d, schedule.active_keys)):
            readings[int(d[i])].append(float(temp[i]))
    up = [j + 0.5 for j in range(1, 34)]
    for k, script, m in zip(schedule.active_keys.tolist(),
                            schedule.script.tolist(),
                            schedule.missed_at.tolist()):
        r = readings[k]
        assert len(r) == 34
        head = r[iot_pass.LATE_BY if script == iot_pass.LATE else 0]
        assert 0.0 < head < 1.0 and head * 2**21 == round(head * 2**21)
        if ids is not None:
            assert ids[k] == round(head * 2**21) - 1
        if script == iot_pass.SILENT:
            assert r[1:] == up[:31] + [iot_pass.QUIET] * 2
        elif script == iot_pass.RISING:
            assert r[1:] == up
        elif script == iot_pass.MISSED:
            assert 2 <= m <= 31
            assert r[1:] == up[:m - 1] + [m - 0.5] + up[m - 1:32]
        else:
            assert r[:4] == [iot_pass.QUIET] * 4 and r[5:] == up[:29]
    # what the reference's chain owes on them: a row a device of three
    # scripts, on the pass's batch 15 (reading 31) or 16 (reading 32)
    owed = owed_by_device(schedule, schedule.active_keys,
                          enumerate(a_pass(schedule)))
    at = {iot_pass.SILENT: [15], iot_pass.RISING: [15],
          iot_pass.MISSED: [16], iot_pass.LATE: []}
    for k, script in zip(schedule.active_keys.tolist(),
                         schedule.script.tolist()):
        assert [r[0] for r in owed[k]] == at[script]
        assert all(r[3] == 31.5 for r in owed[k])
    rows = sum(len(r) for r in owed.values())
    assert rows == 3 * (n_hot // 4)
    if size == "full":
        assert rows == 3_072 and rows / (17 * 131_072) < 0.0014
    # every pass owes the same: two passes on end owe each pass's rows
    # (a LATE run's arm, 30 states deep, is dropped by within)
    two = owed_by_device(
        schedule, schedule.active_keys[:64],
        ((n, schedule.batch(n)) for n in range(2 * schedule.per_pass)))
    for k in schedule.active_keys[:64].tolist():
        assert [r[0] for r in two[k]] == [r[0] for r in owed[k]] + [
            r[0] + 17 for r in owed[k]]


def test_a_rows_t1_names_its_device(made):
    _size, schedule = made
    hot = schedule.active_keys
    b = schedule.batch(0)
    # a pass's first batch holds readings 0 and 1: a head reading and 1.5
    mine = np.isin(b.columns["device"], hot[
        schedule.script != iot_pass.LATE]) & (b.columns["temp"] < 1)
    t1 = b.columns["temp"][mine]
    assert (0 < t1).all() and len(t1) == 3 * (len(hot) // 4)
    assert (schedule.row_keys({"t1": t1}) == b.columns["device"][mine]).all()


def test_swept_devices_arm_and_never_complete():
    """At the rehearsal's size, every device through the reference: the
    swept ones owe nothing, and some of them armed."""
    schedule = iot_pass.make(3, CONFIG, TRAFFIC, rehearsal=True)
    swept = np.setdiff1d(schedule.all_keys, schedule.active_keys)
    batches = list(enumerate(a_pass(schedule)))
    owed = owed_by_device(schedule, swept, batches)
    assert len(owed) == len(swept) and not any(owed.values())
    armed = {int(d) for _n, b in batches for d, t in zip(
        b.columns["device"].tolist(), b.columns["temp"].tolist())
        if 0.0 < t < 1.0} - set(schedule.active_keys.tolist())
    assert len(armed) > 50


def test_the_control_changes_the_owed_rows():
    """Rounded to bfloat16 a head reading keeps 8 of its bits: it names
    another device or none, unless the device's number has no more (one
    in five of 4,096 devices, one in 690 of 1,250,000)."""
    schedule = iot_pass.make(7, CONFIG, {**TRAFFIC, "rehearsal": {
        "batch": 1024, "hot": 328}}, rehearsal=True)
    owed = owed_by_device(schedule, schedule.active_keys,
                          enumerate(a_pass(schedule)))
    exact = {r[2] for rows in owed.values() for r in rows}
    assert len(exact) == 3 * 82
    rounded = {float(np.float32(v).astype(ml_dtypes.bfloat16)) for v in exact}
    assert len(exact - rounded) > 0.7 * len(exact)
    heads = ((np.arange(1_250_000) + 1) / 2.0**21).astype(np.float32)
    kept = heads.astype(ml_dtypes.bfloat16).astype(np.float32) == heads
    assert kept.mean() < 1 / 650
    # and the rising readings cross exactly, so the control is a change
    # of the rows' t1 and not of how many there are
    for j in range(1, 34):
        assert float(np.float32(j + 0.5).astype(ml_dtypes.bfloat16)) == j + .5


# -- the cell through run.py ---------------------------------------------------


def rehearse(capsys, *extra, seed=2**31 + 11, seconds=1):
    assert bench_run.main(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearsal",
                           *extra]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_the_rehearsal_is_correct_on_its_path(capsys):
    line, out = rehearse(capsys)
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 17 * 268
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}
    compared = [ln for ln in out if ln.startswith("compared: ")]
    assert any("sampled rows that differ from the reference (76 devices"
               in ln and "9 rows owed" in ln
               and ln.endswith(": 0 (limit 0)") for ln in compared)
    assert any("rows of devices that were only swept: 0" in ln
               for ln in compared)
    assert any("off its path: none" in ln for ln in compared)
    assert any("instance-lane overflow: 0" in ln for ln in compared)
    assert any("programs compiled in the window: 0" in ln for ln in out)


def test_a_traced_rehearsal_reads_the_bytes_its_steps_gather(capsys):
    line, out = rehearse(capsys, "--trace", "1", seconds=2)
    assert line["correct"] is True, [
        ln for ln in out if ln.startswith(("compared", "send of"))
        and not ln.endswith(": 0 (limit 0)")]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # 256 first occurrences and 12 second ones padded to 16 lanes, a
    # row of 2,048 bytes a lane
    assert metrics["events.stepped_lanes_per_batch"] == 256 + 16
    assert metrics["events.gathered_bytes_per_batch"] == (256 + 16) * 2048
    assert metrics["events.rounds_per_batch"] == 2
    assert metrics["events.dispatches_per_batch"] == 2
    # no device plane on the CPU: the scopes' reader returns nothing
    assert "events.scatter_kernel_ms_per_batch" not in metrics
    # 9 rows a pass of 17 batches
    assert 0.3 < metrics["events.rows_per_batch"] < 0.8


def test_the_flagships_traced_rehearsal_reads_them_too(capsys):
    assert bench_run.main(["--workload", FLAGSHIP, "--seed", "5",
                           "--seconds", "2", "--rehearsal",
                           "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # 992 first occurrences padded to 1,024 lanes and 32 second ones,
    # a row of 1,024 bytes a lane
    assert metrics["events.stepped_lanes_per_batch"] == 1024 + 32
    assert metrics["events.gathered_bytes_per_batch"] == (1024 + 32) * 1024


def test_the_control_is_not_correct(capsys):
    line, _out = rehearse(capsys, "--control", "bf16")
    assert line["control"] == "bf16"
    assert line["correct"] is False and line["failed"] > 0


def test_a_row_that_goes_missing_is_not_correct(capsys, monkeypatch):
    """One row cut out of one delivered batch of the window where the
    engine hands it to the callback."""
    from siddhi_tpu.core.event import EventBatch

    orig, planted = EventBatch.__init__, []

    def init(self, stream_id, names, columns, timestamps, types=None):
        if (stream_id == CONFIG["output"] and not planted
                and len(timestamps) > 1 and timestamps[0] >= 1_001_000):
            columns = {k: np.asarray(v)[1:] for k, v in columns.items()}
            timestamps = np.asarray(timestamps)[1:]
            planted.append(stream_id)
        orig(self, stream_id, names, columns, timestamps, types)

    monkeypatch.setattr(EventBatch, "__init__", init)
    line, out = rehearse(capsys)
    assert planted
    assert line["correct"] is False
    assert 0 < line["failed"] < line["attempted"]
    assert any("sampled rows that differ from the reference" in ln
               and ": 1 (limit 0)" in ln for ln in out)
