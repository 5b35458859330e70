"""The checkpointed flagship's own promises (``generators/
fraud_pass_ckpt.py``, ``references/pattern_chain_ckpt.py``), on the CPU
at the rehearsal size: its batches are ``fraud_pass``'s, its store
starts empty, and its reference is not ``correct`` when a revision has
lost a key's pending instances, when none was committed, and when the
one on disk is an earlier run's.  The checkpoints of these cases are
made by hand at chosen batches (the daemon's interval is set out of
reach), so that what crosses the capture is known; the daemon's own, at
its own times, are the rehearsal's, whose line is held well formed with
and without ``--trace``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import copy
import json
import os
import pickle
import shutil
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH, os.path.join(BENCH, "generators"),
           os.path.join(BENCH, "layers")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import fraud_pass  # noqa: E402
import fraud_pass_ckpt  # noqa: E402
import run as bench_run  # noqa: E402  (benchmark/run.py)
from lib import check  # noqa: E402

CELL = "fraud16_1m_ckpt.saturated"
PERSIST = ("persist_capture_ms_per_checkpoint",
           "persist_fetch_ms_per_checkpoint",
           "persist_write_ms_per_checkpoint", "persist_bytes_per_checkpoint",
           "persist_stall_share")


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


SPEC = _load(ROOT, "BENCHMARK.json")
CONFIG = _load(BENCH, "configs", "fraud16_1m_ckpt.json")
TWIN = _load(BENCH, "configs", "fraud16_1m.json")
TRAFFIC = _load(BENCH, "traffic", "fraud_pass_ckpt_saturated.json")
TWIN_TRAFFIC = _load(BENCH, "traffic", "fraud_pass_saturated.json")
APP = CONFIG["name"]


def config_at(location, interval="1 hour"):
    config = copy.deepcopy(CONFIG)
    config["rehearsal"].update(location=str(location), interval=interval)
    # no daemon's commit to wait for: these checkpoints are made by hand
    config["await_commit_s"] = {"full": 0, "rehearsal": 0}
    return config


def test_the_cell_is_the_flagships_with_the_checkpoint_alone():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    # four chips for the host's sake (on one the runs spread past the
    # bound): the deployment itself keeps its state on one
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fraud16_1m_ckpt", "fraud_pass_ckpt_saturated", 4)
    assert CONFIG["chips"] == CONFIG["expect"]["state_devices"] == 1
    for key in ("app", "stream", "output", "expect", "control", "precision"):
        assert CONFIG[key] == TWIN[key]
    assert CONFIG["full"]["partitions"] == TWIN["full"]["partitions"]
    assert CONFIG["guarantees"][:4] == TWIN["guarantees"]
    assert CONFIG["header"].startswith("@app:name('fraud16_1m_ckpt') "
                                       + TWIN["header"])
    assert ("@app:persist(interval='{interval}', mode='async', "
            "location='{location}', revisions.to.keep='2')"
            ) in CONFIG["header"]
    # the recovering runtime is the same app with the daemon off
    assert CONFIG["recover_header"] == CONFIG["header"].replace(
        "interval='{interval}', ", "")
    for key in ("loop", "batches_per_pass", "full", "rehearsal"):
        assert TRAFFIC[key] == TWIN_TRAFFIC[key]
    for m in SPEC["per_layer"]:
        if "fraud16_1m.saturated" in m.get("workloads", []):
            assert CELL in m["workloads"], m["name"]
    assert {m["name"] for m in SPEC["per_layer"]
            if m.get("workloads") == [CELL]} == {"events." + p for p in PERSIST}


@pytest.mark.parametrize("rehearsal", [True, False],
                         ids=["rehearsal", "full"])
def test_batch_n_is_fraud_pass_s_batch_n_at_one_seed(tmp_path, rehearsal):
    seed = 2**31 + 48
    config = copy.deepcopy(CONFIG)
    which = "rehearsal" if rehearsal else "full"
    config[which]["location"] = str(tmp_path / "store")
    mine = fraud_pass_ckpt.make(seed, config, TRAFFIC, rehearsal)
    assert mine.await_commit_s == CONFIG["await_commit_s"][which] > 0
    mine.await_commit_s = 0     # no app is deployed here: nothing commits
    twin = fraud_pass.make(seed, TWIN, TWIN_TRAFFIC, rehearsal)
    assert (mine.per_pass, mine.warmup, mine.batch_events) == (
        twin.per_pass, twin.warmup, twin.batch_events)
    for n in (-9, -1, 0, 4, 8, 9, 40):
        a, b = mine.batch(n), twin.batch(n)
        assert a.stream_id == b.stream_id
        assert a.attribute_names == b.attribute_names
        assert (a.timestamps == b.timestamps).all()
        for name in a.attribute_names:
            assert a.columns[name].dtype == b.columns[name].dtype
            assert (a.columns[name] == b.columns[name]).all()
    assert (mine.active_keys == twin.active_keys).all()
    assert (mine.all_keys == twin.all_keys).all()


def test_make_resolves_the_location_and_empties_it(tmp_path, monkeypatch):
    monkeypatch.setattr(fraud_pass_ckpt.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    config = copy.deepcopy(CONFIG)
    stale = tmp_path / "siddhi_tpu_bench" / (
        f"fraud16_1m_ckpt_rehearsal_{os.getpid()}") / APP / "1_x.ckpt"
    stale.mkdir(parents=True)
    (stale / "MANIFEST.json").write_text("{}")
    schedule = fraud_pass_ckpt.make(3, config, TRAFFIC, True)
    assert schedule.location == str(stale.parent.parent)
    assert not os.path.exists(schedule.location)
    # the harness formats the header with the block ``make`` resolved
    assert config["rehearsal"]["location"] == schedule.location
    assert "$" not in config["header"].format(**config["rehearsal"])


def test_the_last_warm_up_batch_waits_for_the_next_commit(tmp_path, capsys):
    """The window's place among the checkpoints is not the set-up's
    length: batch -1 is made when a revision newer than the newest on
    disk has its manifest in place, once, and no other batch waits; past
    its limit it says so and the run goes on."""
    import threading
    import time

    config = copy.deepcopy(CONFIG)
    config["rehearsal"]["location"] = str(tmp_path / "store")
    config["await_commit_s"]["rehearsal"] = 30
    schedule = fraud_pass_ckpt.make(5, config, TRAFFIC, True)
    app_dir = tmp_path / "store" / APP
    older = app_dir / "1000000000001_x.ckpt"
    older.mkdir(parents=True)
    (older / "MANIFEST.json").write_text("{}")
    torn = app_dir / "1000000000002_x.ckpt"     # blobs, no manifest yet
    torn.mkdir()

    def commit():
        time.sleep(0.3)
        (torn / "MANIFEST.json.tmp").write_text("{}")
        os.rename(torn / "MANIFEST.json.tmp", torn / "MANIFEST.json")

    t0 = time.perf_counter()
    for n in (-9, -2, 0):
        schedule.batch(n)
    assert time.perf_counter() - t0 < 0.25
    threading.Thread(target=commit).start()
    schedule.batch(-1)
    assert 0.3 <= time.perf_counter() - t0 < 5
    assert "1000000000002_x.ckpt" in capsys.readouterr().out
    t0 = time.perf_counter()
    schedule.batch(-1)                           # once
    assert time.perf_counter() - t0 < 0.25

    config["await_commit_s"]["rehearsal"] = 0.2
    schedule = fraud_pass_ckpt.make(5, config, TRAFFIC, True)
    t0 = time.perf_counter()
    schedule.batch(-1)
    assert 0.2 <= time.perf_counter() - t0 < 5
    assert "no revision committed in 0.2 s" in capsys.readouterr().out


# -- the reference, with the checkpoints made by hand ------------------------


def run_cell(tmp_path, monkeypatch, persist_after=(3, 13), n_sent=27,
             before=None, seed=2**31 + 7, between=None,
             ends=("committed",)):
    """The cell's deployment at the rehearsal size through the harness's
    own pieces, ``persist()`` called after the batches named (3: the
    first pass, with a third of every active key's events applied; 13:
    the second), the reference and the judgement; ``before(store)`` runs
    inside ``recover`` before it restores, ``between(schedule)`` after
    the last batch and before the reference."""
    from lib import deploy

    config = config_at(tmp_path / "store")
    schedule = fraud_pass_ckpt.make(seed, config, TRAFFIC, True)
    if before is not None:
        recover = schedule.recover
        monkeypatch.setattr(
            schedule, "recover",
            lambda location: recover(location, before=before))
    dep = deploy.Deployment(config, schedule, True, False)
    try:
        for n in range(-schedule.warmup, n_sent):
            dep.send(schedule.batch(n))
            if n in persist_after:
                revision = dep.rt.persist()
                assert dep.rt.wait_for_persist(revision, 60) in ends
        dep.drain()
        if between is not None:
            between(schedule)
        window = types.SimpleNamespace(n_sent=n_sent, raised=[])
        answers = check.load_reference(config["reference"]["kind"])(
            config["reference"], schedule, dep.collector, n_sent, seed, True)
        correct, _attempted, failed, compared = check.judge(
            dep, schedule, window, answers, "cpu")
    finally:
        dep.shutdown()
    assert not os.path.exists(os.path.join(schedule.location, APP)) or (
        os.listdir(os.path.join(schedule.location, APP)) == [])
    return correct, failed, Compared(
        (name, value) for name, value, _limit in compared), schedule


class Compared(dict):
    """The numbers compared, by the beginning of their name."""

    def __call__(self, begins):
        (value,) = [v for k, v in self.items() if k.startswith(begins)]
        return value


def test_the_sound_run_is_correct_and_the_replay_owes_rows(tmp_path,
                                                           monkeypatch):
    correct, failed, compared, schedule = run_cell(tmp_path, monkeypatch)
    assert correct and not failed, compared
    assert schedule.restore_s > 0
    assert all(v == 0 for v in compared.values()), compared
    assert compared("rows of the replay (batches 14..26") == 0


def test_a_lost_pending_instance_is_not_correct(tmp_path, monkeypatch):
    """One sampled key's pending instances removed from the newest
    revision, the blob and the manifest written again so that every
    checksum holds: only the replay can tell."""
    lost = []

    def forget_one_key(store):
        revision = store.revisions(APP)[-1]
        tree = pickle.loads(store.load(APP, revision))
        pattern = tree["partitions"]["partition_0"]["__dense__"]["bench"][
            "pattern"]
        active = pattern["dense_state"]["active"]
        # a key with instances pending at the capture: an active one
        row = next(r for r in pattern["key_rows"].values()
                   if active[r].sum() >= 3)
        lost.append(int(active[row].sum()))
        active[row] = False
        kinds = ("queries", "tables", "named_windows", "partitions",
                 "aggregations")
        store.save_tree(APP, revision, [
            (kind, name, pickle.dumps(state)) for kind in kinds
            for name, state in tree[kind].items()],
            version=tree["version"], clock=tree["clock"])

    correct, failed, compared, _s = run_cell(tmp_path, monkeypatch,
                                             before=forget_one_key)
    assert lost and not correct and failed > 0
    # the rows its pending instances owed
    assert 1 <= compared("rows of the replay (batches") <= lost[0]
    assert compared("blobs whose SHA-256 differs") == 0


def test_no_committed_revision_is_not_correct(tmp_path, monkeypatch):
    correct, failed, compared, _s = run_cell(tmp_path, monkeypatch,
                                             persist_after=())
    assert not correct and failed > 0
    assert compared("revisions committed inside the window, of 1 owed") == 1
    assert compared("the newest revision restored and replayed") == 1


def test_a_checkpoint_that_failed_is_not_correct(tmp_path, monkeypatch):
    """The second checkpoint's write fails (a full disk): the first
    revision is committed, restores and replays soundly, and the run is
    still not ``correct``: the failure reached the exception listener,
    which the harness counts."""
    from siddhi_tpu.durability.store import DurableFileSystemPersistenceStore

    save, calls = DurableFileSystemPersistenceStore.save_tree, []

    def full_disk(self, *args, **kwargs):
        if args[1] not in calls:
            calls.append(args[1])
        if args[1] != calls[0]:     # every try of the second revision
            raise OSError(28, "No space left on device")
        return save(self, *args, **kwargs)

    monkeypatch.setattr(DurableFileSystemPersistenceStore, "save_tree",
                        full_disk)
    correct, failed, compared, _s = run_cell(
        tmp_path, monkeypatch, ends=("committed", "failed"))
    assert len(calls) == 2 and not correct and failed > 0
    assert compared("batches dropped or reported to the exception") == 1
    assert compared("rows of the replay (batches 4..17") == 0


def test_a_revision_of_an_earlier_run_is_not_correct(tmp_path, monkeypatch):
    """The store emptied at ``make`` is the first defence; were a
    revision of an earlier run left there all the same (another seed's
    keys and values, an older millisecond in its name), it is counted,
    and its replay does not match."""
    earlier = tmp_path / "earlier"
    _c, _f, _compared, first = run_cell(
        earlier, monkeypatch, persist_after=(13,), seed=2**31 + 8,
        before=lambda store: shutil.copytree(store.base_dir,
                                             tmp_path / "kept"))

    def plant(schedule):
        shutil.copytree(tmp_path / "kept", schedule.location,
                        dirs_exist_ok=True)

    correct, failed, compared, second = run_cell(
        tmp_path / "later", monkeypatch, persist_after=(), between=plant)
    assert first.made_ms <= second.made_ms
    assert not correct and failed > 0
    assert compared("revisions on disk that an earlier run committed") == 1
    assert compared("rows of the replay (batches") > 0


# -- the rehearsal: the daemon's own checkpoints -----------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_line_is_well_formed(capsys, trace):
    assert bench_run.main(["--workload", CELL, "--seed", str(2**31 + 11),
                           "--seconds", "2", "--rehearsal", "--trace",
                           str(trace)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    kind = "per_layer" if trace else "end_to_end"
    owed = {m["name"]: m["unit"] for m in SPEC[kind]
            if CELL in m.get("workloads", [CELL])
            and m["source"] != "device_trace"}
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    assert got.items() <= owed.items()
    if trace:   # the five of this deployment, each above 0
        assert all(line["metrics"]["events." + p]["value"] > 0
                   for p in PERSIST)
        assert line["metrics"]["events.persist_stall_share"]["value"] < 100
    else:
        assert got == owed
        assert all(v["value"] > 0 for v in line["metrics"].values())
    assert any(ln.startswith("compared: rows of the replay")
               and ln.endswith(": 0 (limit 0)") for ln in out)
    assert any(ln.startswith("recover: revision ") for ln in out)
    assert any("programs compiled in the window: 0" in ln for ln in out)
    assert np.isfinite([v["value"] for v in line["metrics"].values()]).all()
