"""Crash-consistent async durability: matrix, checksums, spill, stats.

The durability/ package adds a non-blocking persist pipeline: capture
under the barrier (device-array references + cheap host copies), then a
checkpoint writer thread does the D2H fetch, per-element pickle +
SHA-256, and an atomic-manifest store commit.  The contracts pinned
here:

* **Crash matrix** — a simulated crash (``SimulatedCrashError``, a
  BaseException that tears through every hardening layer like SIGKILL)
  at EVERY durability step (post-blob, pre-manifest, mid-manifest,
  post-manifest-before-journal-mark, mid-spill) leaves either the
  previous or the new revision fully restorable, and restore + journal
  replay is bit-identical to an uninterrupted run — across the
  device-single, sharded, fused, multiplexed, dense-pattern and
  hotkey engines.
* **Checksummed manifests** — a flipped byte anywhere in a revision
  (blob or manifest) fails validation and the restore walk falls back
  to the previous revision with a warning.
* **Journal spill** — a full journal spills cold segments to the
  persistence store; replay stitches spilled + in-memory segments.
* **Async == sync** — both modes route through the same capture, so
  the persisted state trees are byte-identical.
* **No silent degradation** — unfreezable elements (host NFA instance
  lists), forced-sync fallbacks, coalesced persists, retries, and
  failures are all counted and surfaced through the statistics feed.
"""

import os
import pickle
import threading
import time

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.exceptions import SimulatedCrashError
from siddhi_tpu.durability import (
    AsyncCheckpointWriter,
    DurableFileSystemPersistenceStore,
)
from siddhi_tpu.util.persistence import (
    InMemoryPersistenceStore,
    IncrementalFileSystemPersistenceStore,
)

pytestmark = pytest.mark.faults


# -- engine matrix ----------------------------------------------------------

AGG_BODY = ("define stream S (k long, v double); "
            "@info(name='q') from S#window.length(4) "
            "select k, sum(v) as s group by k insert into Out;")

FUSED_BODY = """
define stream SIn (sym int, price float, vol int);
define stream Mid (sym int, price float, vol int);
define stream Win (sym int, total double);
@info(name='q1') from SIn[price > 10.0]
select sym, price, vol insert into Mid;
@info(name='q2') from Mid#window.length(8)
select sym, sum(price) as total insert into Win;
@info(name='q3') from Win[total > 50.0]
select sym, total insert into Out;
"""

MUX_BODY = ("define stream S (k long, v double); "
            "@info(name='qw') from S#window.lengthBatch(4) "
            "select k, sum(v) as s, count() as c group by k "
            "insert into Out;")

HOTKEY_BODY = (
    "define stream S (k long, u double, v double); "
    "partition with (k of S) begin "
    "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
    "select b.v as bv insert into Out; end;")


# a partitioned pattern on the dense engine, at a size whose logical
# fields leave the element's pickle (durability/capture.py
# OUT_OF_BAND_BYTES): a revision of it is a skeleton and raw buffers
DENSE_BODY = (
    "define stream S (k long, u double, v double); "
    "partition with (k of S) begin "
    "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0 and v > a.v] "
    "select a.v as av, b.v as bv insert into Out; end;")


def kv_series(n, seed=11, n_keys=3):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, size=n)
    vals = rng.integers(1, 100, size=n).astype(float)
    ts = 1000 + np.arange(n) * 250
    return [([int(k), float(v)], int(t)) for k, v, t in zip(keys, vals, ts)]


def fused_series(n, seed=7):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        out.append(([int(rng.integers(0, 4)),
                     round(float(rng.uniform(5.0, 20.0)), 1),
                     int(rng.integers(0, 100))], 1000 + i * 100))
    return out


def hk_series(n, seed=5):
    rng = np.random.default_rng(seed)
    out, t = [], 1000
    for _ in range(n):
        t += int(rng.integers(1, 40))
        k = 7 if rng.random() < 0.5 else int(rng.integers(0, 20))
        out.append(([k, round(float(rng.uniform(0, 20)), 1),
                     round(float(rng.uniform(0, 20)), 1)], t))
    return out


ENGINES = {
    "device_single": ("@app:execution('tpu') ", AGG_BODY, "S",
                      kv_series(30)),
    "sharded": ("@app:execution('tpu', partitions='16', devices='8') ",
                AGG_BODY, "S", kv_series(30)),
    "fused": ("@app:execution('tpu') @app:fuse ", FUSED_BODY, "SIn",
              fused_series(30)),
    "multiplex": ("@app:execution('tpu') @app:multiplex(slots='8') ",
                  MUX_BODY, "S", kv_series(30)),
    "dense": ("@app:execution('tpu', partitions='4096') ", DENSE_BODY, "S",
              hk_series(60)),
    "hotkey": ("@app:execution('tpu', instances='16') "
               "@app:hotkeys(k='4', promote='0.3', demote='0.1') ",
               HOTKEY_BODY, "S", hk_series(60)),
}

#: crash site -> which revision must survive ('prev' = the torn write is
#: invisible, 'new' = the write landed, only the journal mark is behind)
CRASH_SITES = {
    "persist.post_blob": "prev",
    "persist.pre_manifest": "prev",
    "persist.mid_manifest": "prev",
    "persist.post_manifest": "new",
}

_REFERENCE_CACHE = {}


def _app(engine, journal=256):
    exec_opts, body, _stream, _sends = ENGINES[engine]
    return ("@app:name('dur') @app:playback "
            f"@app:faults(journal='{journal}') " + exec_opts + body)


def _reference(engine):
    """Uninterrupted-run output of the engine's send series (cached —
    the matrix replays it once per crash site)."""
    if engine in _REFERENCE_CACHE:
        return _REFERENCE_CACHE[engine]
    exec_opts, body, stream, sends = ENGINES[engine]
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('dur') @app:playback " + exec_opts + body)
        got = []
        rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                      for e in evs))
        rt.start()
        h = rt.get_input_handler(stream)
        for row, ts in sends:
            h.send(list(row), timestamp=ts)
        rt.shutdown()
    finally:
        m.shutdown()
    assert len(got) > 2, f"{engine}: series too tame; matrix is vacuous"
    _REFERENCE_CACHE[engine] = got
    return got


class TestCrashMatrix:
    """Kill the durability pipeline between every step, on every
    engine; recovery must be bit-exact."""

    @pytest.mark.parametrize("engine", sorted(ENGINES))
    @pytest.mark.parametrize("site", sorted(CRASH_SITES))
    def test_async_crash_site_recovers_bit_exact(self, engine, site,
                                                 tmp_path):
        ref = _reference(engine)
        _exec, _body, stream, sends = ENGINES[engine]
        persist_at, crash_at = 10, 20
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(_app(engine))
            got = []
            rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            h = rt.get_input_handler(stream)
            for row, ts in sends[:persist_at]:
                h.send(list(row), timestamp=ts)
            rev1 = rt.persist(mode="async")
            assert rt.wait_for_persist(rev1, timeout=30) == "committed"
            for row, ts in sends[persist_at:crash_at]:
                h.send(list(row), timestamp=ts)
            rt.app_context.fault_injector.configure(site, "crash", count=1)
            rev2 = rt.persist(mode="async")
            assert rt.wait_for_persist(rev2, timeout=30) == "crashed"
            rt.shutdown()  # the crashed runtime is gone

            rt2 = m.create_siddhi_app_runtime(_app(engine))
            rt2.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                           for e in evs))
            rt2.start()
            restored = rt2.restore_last_revision()
            expected = rev2 if CRASH_SITES[site] == "new" else rev1
            assert restored == expected, (
                f"{engine}/{site}: restored '{restored}', "
                f"expected '{expected}'")
            h2 = rt2.get_input_handler(stream)
            for row, ts in sends[crash_at:]:
                h2.send(list(row), timestamp=ts)
            rt2.shutdown()
            assert got == ref, (
                f"{engine}/{site}: crash+recover diverged from the "
                "uninterrupted run")
        finally:
            m.shutdown()

    @pytest.mark.parametrize("site", sorted(CRASH_SITES))
    def test_sync_crash_site_recovers_bit_exact(self, site, tmp_path):
        # the same matrix through the blocking path: the crash surfaces
        # in the persist() call itself
        engine = "device_single"
        ref = _reference(engine)
        _exec, _body, stream, sends = ENGINES[engine]
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(_app(engine))
            got = []
            rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            h = rt.get_input_handler(stream)
            for row, ts in sends[:10]:
                h.send(list(row), timestamp=ts)
            rev1 = rt.persist(mode="sync")
            for row, ts in sends[10:20]:
                h.send(list(row), timestamp=ts)
            rt.app_context.fault_injector.configure(site, "crash", count=1)
            with pytest.raises(SimulatedCrashError):
                rt.persist(mode="sync")
            store = m.siddhi_context.persistence_store
            revs = store.revisions("dur")
            rt.shutdown()

            rt2 = m.create_siddhi_app_runtime(_app(engine))
            rt2.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                           for e in evs))
            rt2.start()
            restored = rt2.restore_last_revision()
            if CRASH_SITES[site] == "prev":
                assert restored == rev1
            else:
                assert restored == revs[-1] != rev1
            h2 = rt2.get_input_handler(stream)
            for row, ts in sends[20:]:
                h2.send(list(row), timestamp=ts)
            rt2.shutdown()
            assert got == ref
        finally:
            m.shutdown()

    def test_mid_spill_crash_recovers_bit_exact(self, tmp_path):
        # kill the process in the middle of a journal-segment spill: the
        # written segment is durable, the in-memory journal is gone, and
        # recovery stitches segments + journal into a gapless replay
        engine = "device_single"
        ref = _reference(engine)
        _exec, _body, stream, sends = ENGINES[engine]
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(_app(engine, journal=4))
            got = []
            rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            h = rt.get_input_handler(stream)
            for row, ts in sends[:6]:
                h.send(list(row), timestamp=ts)
            rt.persist()
            crash_at = 16  # > depth-4 journal: spills before the crash
            for row, ts in sends[6:crash_at]:
                h.send(list(row), timestamp=ts)
            rt.app_context.fault_injector.configure(
                "journal.spill.mid", "crash", count=1)
            with pytest.raises(SimulatedCrashError):
                h.send(list(sends[crash_at][0]),
                       timestamp=sends[crash_at][1])
            rt.shutdown()

            rt2 = m.create_siddhi_app_runtime(_app(engine, journal=4))
            rt2.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                           for e in evs))
            rt2.start()
            assert rt2.restore_last_revision() is not None
            jr2 = rt2.app_context.input_journal
            assert jr2.stats.replayed_spilled_batches > 0
            h2 = rt2.get_input_handler(stream)
            # the crashed send was journaled before the spill crash, so
            # replay already delivered it — continue after it
            for row, ts in sends[crash_at + 1:]:
                h2.send(list(row), timestamp=ts)
            rt2.shutdown()
            assert got == ref, "mid-spill crash diverged"
        finally:
            m.shutdown()


class TestChecksummedManifests:
    def _persist_twice(self, m, tmp_path, engine="device_single"):
        _exec, _body, stream, sends = ENGINES[engine]
        m.set_persistence_store(
            DurableFileSystemPersistenceStore(str(tmp_path)))
        rt = m.create_siddhi_app_runtime(_app(engine))
        rt.start()
        h = rt.get_input_handler(stream)
        for row, ts in sends[:8]:
            h.send(list(row), timestamp=ts)
        rev1 = rt.persist(mode="sync")
        for row, ts in sends[8:16]:
            h.send(list(row), timestamp=ts)
        rev2 = rt.persist(mode="sync")
        rt.shutdown()
        return rev1, rev2

    @pytest.mark.parametrize("victim", ["blob", "buffer", "manifest"])
    def test_flipped_byte_walks_back_to_previous_revision(
            self, victim, tmp_path, caplog):
        import logging

        engine = "dense" if victim == "buffer" else "device_single"
        m = SiddhiManager()
        try:
            rev1, rev2 = self._persist_twice(m, tmp_path, engine)
            rev_dir = tmp_path / "dur" / f"{rev2}.ckpt"
            if victim == "manifest":
                target = rev_dir / "MANIFEST.json"
            else:
                # an element's pickle, or an array it left out of band
                ends = ".blob" if victim == "blob" else ".buf"
                target = sorted(p for p in rev_dir.iterdir()
                                if p.name.endswith(ends))[0]
            raw = bytearray(target.read_bytes())
            raw[len(raw) // 2] ^= 0xFF
            target.write_bytes(bytes(raw))

            rt2 = m.create_siddhi_app_runtime(_app(engine))
            rt2.start()
            with caplog.at_level(logging.WARNING, logger="siddhi_tpu"):
                assert rt2.restore_last_revision() == rev1
            assert any(rev2 in r.message for r in caplog.records), (
                "the skipped corrupt revision must be surfaced")
            rt2.shutdown()
        finally:
            m.shutdown()

    def test_torn_revision_without_manifest_is_invisible(self, tmp_path):
        m = SiddhiManager()
        try:
            rev1, rev2 = self._persist_twice(m, tmp_path)
            store = m.siddhi_context.persistence_store
            # simulate a crash that wrote blobs but no manifest
            torn = tmp_path / "dur" / "9999999999999_dur.ckpt"
            torn.mkdir()
            (torn / "0000.blob").write_bytes(b"half a checkpoint")
            assert store.revisions("dur") == [rev1, rev2]
            assert store.get_last_revision("dur") == rev2
        finally:
            m.shutdown()

    def test_eviction_keeps_newest_committed(self, tmp_path):
        store = DurableFileSystemPersistenceStore(
            str(tmp_path), revisions_to_keep=2)
        for i in range(5):
            store.save("a", f"{1000 + i}_a", pickle.dumps({"i": i}))
        assert store.revisions("a") == ["1003_a", "1004_a"]
        assert pickle.loads(store.load("a", "1004_a")) == {"i": 4}


def _plain_manifest(rev_dir):
    """A revision as ``benchmark/references/pattern_chain_ckpt.py`` reads
    it, in plain ``json`` / ``hashlib``: the manifest, and per entry of
    its ``elements`` whether the file hashes to it."""
    import hashlib
    import json

    with open(os.path.join(rev_dir, "MANIFEST.json")) as f:
        manifest = json.load(f)
    sound = []
    for el in manifest["elements"]:
        h = hashlib.sha256()
        with open(os.path.join(rev_dir, el["file"]), "rb") as f:
            while chunk := f.read(1 << 16):
                h.update(chunk)
        sound.append(h.hexdigest() == el["sha256"]
                     and os.path.getsize(os.path.join(
                         rev_dir, el["file"])) == el["size"])
    return manifest, sound


class TestManifestFormats:
    """Format 2 (an element's pickle and the buffers it left out of
    band, a file each, every file an entry of ``elements``) and format 1
    (one pickle an element, arrays in band), which still loads."""

    def _run(self, m, n, header="", persist=None):
        _exec, _body, stream, sends = ENGINES["dense"]
        rt = m.create_siddhi_app_runtime(
            _app("dense").replace("@app:playback", "@app:playback " + header))
        got = []
        rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                      for e in evs))
        rt.start()
        h = rt.get_input_handler(stream)
        for row, ts in sends[:n]:
            h.send(list(row), timestamp=ts)
        return rt, got, h

    def test_every_file_of_a_revision_is_an_entry_that_hashes(
            self, tmp_path):
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt, _got, _h = self._run(m, 30, "@app:trace(sample='1')")
            rev = rt.persist(mode="async")
            assert rt.wait_for_persist(rev, timeout=30) == "committed"
            spans = list(rt.app_context.tracer.recorder.spans())
            rt.shutdown()
        finally:
            m.shutdown()
        rev_dir = str(tmp_path / "dur" / f"{rev}.ckpt")
        manifest, sound = _plain_manifest(rev_dir)
        assert manifest["format"] == 2 and all(sound)
        elements = manifest["elements"]
        assert all({"kind", "name", "file", "sha256", "size"} <= set(el)
                   for el in elements)
        files = [el["file"] for el in elements]
        assert sorted(files + ["MANIFEST.json"]) == sorted(
            os.listdir(rev_dir))
        # the partition's pickle, then its buffers in their order: the
        # four logical fields, the overflow vector, the last-used stamps
        (blob,) = [el for el in elements if "buffer" not in el]
        buffers = [el for el in elements if "buffer" in el]
        assert blob["file"] == "0000.blob" and elements[0] is blob
        assert [el["buffer"] for el in buffers] == list(range(len(buffers)))
        assert len(buffers) >= 6
        assert all(el["kind"] == "partitions" and el["name"] == blob["name"]
                   and el["size"] >= 4096 for el in buffers)
        # what is still pickled in band is under a hundredth of what
        # the store wrote
        by_stage = {}
        for sp in spans:
            by_stage.setdefault(sp[1], []).append(sp[5])
        (in_band,) = by_stage["persist.pickle"]
        stored = sum(by_stage["persist.store"])
        assert in_band == blob["size"]
        assert stored == sum(el["size"] for el in elements)
        assert stored == sum(by_stage["persist.hash"])
        assert in_band < 0.01 * stored

    def test_a_format_1_revision_still_restores(self, tmp_path):
        """A revision in the parent's layout, written here by hand in
        plain ``pickle`` / ``json`` / ``hashlib``: one ``.blob`` an
        element with its arrays in band, ``key_rows`` a dict,
        ``free_rows`` a list, no ``buffer`` entry, ``format`` 1."""
        import hashlib
        import json

        ref = _reference("dense")
        _exec, _body, stream, sends = ENGINES["dense"]
        m = SiddhiManager()
        try:
            rt, got, _h = self._run(m, 30)
            tree = pickle.loads(rt.snapshot())
            rt.shutdown()
            (partition,) = tree["partitions"].values()
            (pattern,) = partition["__dense__"].values()
            pattern = pattern["pattern"]
            assert not isinstance(pattern["key_rows"], dict)
            keys, rows = pattern["key_rows"]
            pattern["key_rows"] = dict(zip(keys.tolist(), rows.tolist()))
            pattern["free_rows"] = pattern["free_rows"].tolist()
            assert pattern["dense_state"]["active"].ndim == 3

            rev = "1700000000000_dur"
            rev_dir = tmp_path / "dur" / (rev + ".ckpt")
            rev_dir.mkdir(parents=True)
            elements = []
            kinds = ("queries", "tables", "named_windows", "partitions",
                     "aggregations")
            blobs = [(kind, name, state) for kind in kinds
                     for name, state in tree[kind].items()]
            for idx, (kind, name, state) in enumerate(blobs):
                data = pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)
                (rev_dir / f"{idx:04d}.blob").write_bytes(data)
                elements.append({
                    "kind": kind, "name": name, "file": f"{idx:04d}.blob",
                    "sha256": hashlib.sha256(data).hexdigest(),
                    "size": len(data)})
            manifest = {"format": 1, "app": "dur", "revision": rev,
                        "version": tree["version"], "elements": elements,
                        "clock": tree["clock"]}
            manifest["checksum"] = hashlib.sha256(json.dumps(
                manifest, sort_keys=True,
                separators=(",", ":")).encode("utf-8")).hexdigest()
            (rev_dir / "MANIFEST.json").write_text(json.dumps(manifest))

            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt2 = m.create_siddhi_app_runtime(_app("dense"))
            rt2.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                           for e in evs))
            rt2.start()
            assert rt2.restore_last_revision() == rev
            h2 = rt2.get_input_handler(stream)
            for row, ts in sends[30:]:
                h2.send(list(row), timestamp=ts)
            rt2.shutdown()
            assert got == ref
        finally:
            m.shutdown()


    def test_an_evicted_revision_keeps_its_files_for_one_more_commit(
            self, tmp_path):
        """A reader that has a committed revision's manifest when the
        next commit evicts that revision still finds every file (the
        benchmark's reference hashes the revisions of a store whose
        daemon goes on ticking); the commit after sweeps them."""
        import hashlib
        import json

        from siddhi_tpu.durability.capture import dumps_out_of_band

        store = DurableFileSystemPersistenceStore(
            str(tmp_path), revisions_to_keep=2)

        def save(i):
            state = {"i": i, "v": np.arange(4096, dtype=np.int64) + i}
            store.save_tree("a", f"{1000 + i}_a",
                            [("queries", "q", *dumps_out_of_band(state))])

        save(0)
        save(1)
        oldest = tmp_path / "a" / "1000_a.ckpt"
        with open(oldest / "MANIFEST.json") as f:
            save(2)     # evicts the revision whose manifest is open
            manifest = json.load(f)
        assert store.revisions("a") == ["1001_a", "1002_a"]
        assert store.load("a", "1000_a") is None
        assert not (oldest / "MANIFEST.json").exists()
        assert len(manifest["elements"]) == 2
        for el in manifest["elements"]:
            data = (oldest / el["file"]).read_bytes()
            assert hashlib.sha256(data).hexdigest() == el["sha256"]
        save(3)
        assert not oldest.exists()
        assert store.revisions("a") == ["1002_a", "1003_a"]
        assert sorted(os.listdir(tmp_path / "a")) == [
            "1001_a.ckpt", "1002_a.ckpt", "1003_a.ckpt"]
        assert pickle.loads(store.load("a", "1003_a"))["queries"]["q"][
            "i"] == 3
        store.clear_all_revisions("a")
        assert os.listdir(tmp_path / "a") == []


class TestAsyncSyncEquivalence:
    def test_async_and_sync_state_trees_are_byte_identical(self, tmp_path):
        _exec, _body, stream, sends = ENGINES["device_single"]
        trees = {}
        for mode in ("sync", "async"):
            m = SiddhiManager()
            try:
                store = DurableFileSystemPersistenceStore(
                    str(tmp_path / mode))
                m.set_persistence_store(store)
                rt = m.create_siddhi_app_runtime(_app("device_single"))
                rt.start()
                h = rt.get_input_handler(stream)
                for row, ts in sends[:12]:
                    h.send(list(row), timestamp=ts)
                rev = rt.persist(mode=mode)
                assert rt.wait_for_persist(rev, timeout=30) in (
                    "committed", "idle")
                trees[mode] = store.load("dur", rev)
                rt.shutdown()
            finally:
                m.shutdown()
        assert trees["sync"] is not None
        assert trees["sync"] == trees["async"], (
            "async capture must persist the exact state the blocking "
            "path persists")


class TestDegradationCounters:
    def test_unfreezable_host_state_falls_back_counted(self, tmp_path):
        # host NFA instance lists cannot freeze-by-reference: they are
        # pickled in-barrier, the persist still commits, and the
        # degradation is counted — never silent
        body = ("define stream S (k long, v double); "
                "@info(name='q') from every e1=S[v > 50.0] "
                "-> e2=S[v > e1.v] within 10 sec "
                "select e1.v as a, e2.v as b insert into Out;")
        app = ("@app:name('hostpat') @app:playback "
               "@app:faults(journal='64') " + body)
        ref_m = SiddhiManager()
        try:
            rt = ref_m.create_siddhi_app_runtime(
                "@app:name('hostpat') @app:playback " + body)
            ref = []
            rt.add_callback("Out", lambda evs: ref.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            h = rt.get_input_handler("S")
            for row, ts in kv_series(24, seed=3):
                h.send(list(row), timestamp=ts)
            rt.shutdown()
        finally:
            ref_m.shutdown()
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(app)
            got = []
            rt.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                          for e in evs))
            rt.start()
            h = rt.get_input_handler("S")
            sends = kv_series(24, seed=3)
            for row, ts in sends[:12]:
                h.send(list(row), timestamp=ts)
            rev = rt.persist(mode="async")
            assert rt.wait_for_persist(rev, timeout=30) == "committed"
            assert rt._durability_stats().capture_fallback_elements > 0
            sm = rt.app_context.statistics_manager
            assert any(r.startswith("unfreezable")
                       for r in sm.persist_fallback_reasons.values())
            rt.shutdown()

            rt2 = m.create_siddhi_app_runtime(app)
            rt2.add_callback("Out", lambda evs: got.extend(tuple(e.data)
                                                           for e in evs))
            rt2.start()
            assert rt2.restore_last_revision() == rev
            h2 = rt2.get_input_handler("S")
            for row, ts in sends[12:]:
                h2.send(list(row), timestamp=ts)
            rt2.shutdown()
            assert got == ref, "prepickled-fallback restore diverged"
        finally:
            m.shutdown()

    def test_incremental_store_forces_counted_sync(self, tmp_path):
        _exec, _body, stream, sends = ENGINES["device_single"]
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                IncrementalFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(_app("device_single"))
            rt.start()
            h = rt.get_input_handler(stream)
            for row, ts in sends[:8]:
                h.send(list(row), timestamp=ts)
            rt.persist(mode="async")  # degrades to sync, counted
            sm = rt.app_context.statistics_manager
            assert sm.persist_fallback_reasons.get("dur") == (
                "incremental-store-sync-only")
            assert rt._durability_stats().persists_sync == 1
            assert rt._durability_stats().persists_async == 0
            rt.shutdown()
        finally:
            m.shutdown()

    def test_statistics_feed_reports_durability_metrics(self, tmp_path):
        _exec, _body, stream, sends = ENGINES["device_single"]
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                DurableFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(_app("device_single"))
            rt.start()
            h = rt.get_input_handler(stream)
            for row, ts in sends[:8]:
                h.send(list(row), timestamp=ts)
            rev = rt.persist(mode="async")
            assert rt.wait_for_persist(rev, timeout=30) == "committed"
            stats = rt.statistics()
            key = [k for k in stats if "Durability" in k
                   and k.endswith("persist_commits")]
            assert key and stats[key[0]] == 1
            assert stats[key[0].replace(
                "persist_commits", "persists_async")] == 1
            rt.shutdown()
        finally:
            m.shutdown()


class TestIncrementalChainHygiene:
    def test_restore_resets_digest_chain_to_base(self, tmp_path):
        # regression: an increment diffed against PRE-restore digests
        # poisons the chain — after any restore the next incremental
        # snapshot must be a full base
        _exec, _body, stream, sends = ENGINES["device_single"]
        m = SiddhiManager()
        try:
            m.set_persistence_store(
                IncrementalFileSystemPersistenceStore(str(tmp_path)))
            rt = m.create_siddhi_app_runtime(_app("device_single"))
            rt.start()
            h = rt.get_input_handler(stream)
            for row, ts in sends[:6]:
                h.send(list(row), timestamp=ts)
            rt.persist()  # base
            for row, ts in sends[6:12]:
                h.send(list(row), timestamp=ts)
            rt.persist()  # inc
            rt.restore_last_revision()
            svc = rt._snapshot_service()
            assert svc._digests == {} and svc._incs_since_base == 0
            kind, _data = svc.incremental_snapshot()
            assert kind == "base"
            rt.shutdown()
        finally:
            m.shutdown()


class TestBoundedInMemoryStore:
    def test_eviction_keeps_newest(self):
        store = InMemoryPersistenceStore(revisions_to_keep=5)
        for i in range(8):
            store.save("a", f"rev{i:02d}", b"x%d" % i)
        assert store.revisions("a") == [f"rev{i:02d}" for i in range(3, 8)]
        assert store.load("a", "rev02") is None
        assert store.load("a", "rev07") == b"x7"


class TestWriterUnit:
    def test_coalescing_supersedes_queued_not_inflight(self):
        w = AsyncCheckpointWriter("t")
        gate = threading.Event()
        abandoned = []
        w.submit("r1", lambda: gate.wait(10))
        deadline = time.monotonic() + 5
        while w.status("r1") != "inflight":
            assert time.monotonic() < deadline
            time.sleep(0.005)
        w.submit("r2", lambda: None, on_abandon=abandoned.append)
        w.submit("r3", lambda: None, on_abandon=abandoned.append)
        assert w.status("r2") == "superseded"
        assert abandoned == ["r2"]
        gate.set()
        assert w.wait("r1", timeout=10) == "committed"
        assert w.wait("r3", timeout=10) == "committed"
        assert w.stats.persists_coalesced == 1
        assert w.stats.persist_commits == 2
        w.shutdown()

    def test_a_committed_job_is_let_go(self):
        """The job holds the capture, and the capture its device arrays
        (0.84 GB of HBM at the flagship's size): the idle writer keeps
        no name on it until the next checkpoint comes."""
        import gc
        import weakref

        class Capture:
            pass

        w = AsyncCheckpointWriter("t")
        capture = Capture()
        held = weakref.ref(capture)
        w.submit("r1", lambda c=capture: None)
        del capture
        assert w.wait("r1", timeout=10) == "committed"
        assert w.wait(timeout=10) == "idle"
        deadline = time.monotonic() + 5
        while held() is not None and time.monotonic() < deadline:
            gc.collect()
            time.sleep(0.005)
        assert held() is None
        w.shutdown()

    def test_retryable_fault_retries_then_commits(self):
        w = AsyncCheckpointWriter("t")
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("disk hiccup")

        w.submit("r", flaky)
        assert w.wait("r", timeout=10) == "committed"
        assert len(calls) == 3
        assert w.stats.persist_retries == 2
        w.shutdown()

    def test_non_retryable_failure_abandons_mark(self):
        w = AsyncCheckpointWriter("t")
        abandoned = []

        def broken():
            raise ValueError("cannot serialize")

        w.submit("r", broken, on_abandon=abandoned.append)
        assert w.wait("r", timeout=10) == "failed"
        assert abandoned == ["r"]
        assert w.stats.persist_failures == 1
        w.shutdown()

    def test_crashed_writer_rejects_new_submits(self):
        w = AsyncCheckpointWriter("t")

        def die():
            raise SimulatedCrashError("persist.write")

        w.submit("r", die)
        assert w.wait("r", timeout=10) == "crashed"
        with pytest.raises(SimulatedCrashError):
            w.submit("r2", lambda: None)


class TestPersistAnnotationAndService:
    def test_persist_interval_daemon_checkpoints(self, tmp_path):
        app = ("@app:name('periodic') @app:playback "
               "@app:persist(interval='50 millisec', mode='async') "
               + AGG_BODY)
        m = SiddhiManager()
        try:
            store = DurableFileSystemPersistenceStore(str(tmp_path))
            m.set_persistence_store(store)
            rt = m.create_siddhi_app_runtime(app)
            assert rt.app_context.persist_mode == "async"
            assert rt.app_context.persist_interval_ms == 50
            rt.start()
            h = rt.get_input_handler("S")
            for row, ts in kv_series(8):
                h.send(list(row), timestamp=ts)
            deadline = time.monotonic() + 10
            while not store.revisions("periodic"):
                assert time.monotonic() < deadline, "daemon never persisted"
                time.sleep(0.02)
            rt.shutdown()
            assert not getattr(rt, "_persist_stop", None)
        finally:
            m.shutdown()

    def test_bad_persist_annotation_rejected(self):
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError

        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError):
                m.create_siddhi_app_runtime(
                    "@app:name('bad') @app:persist(mode='turbo') "
                    + AGG_BODY)
        finally:
            m.shutdown()

    def test_service_persist_and_restore_endpoints(self, tmp_path):
        from siddhi_tpu.service import SiddhiService

        m = SiddhiManager()
        m.set_persistence_store(
            DurableFileSystemPersistenceStore(str(tmp_path)))
        svc = SiddhiService(manager=m)
        try:
            code, payload = svc.deploy(
                "@app:name('rest') @app:playback " + AGG_BODY)
            assert code == 200
            rt = svc.get_runtime("rest")
            h = rt.get_input_handler("S")
            for row, ts in kv_series(8):
                h.send(list(row), timestamp=ts)
            code, payload = svc.persist("rest")
            assert code == 200 and payload["revision"]
            code, payload = svc.restore_last("rest")
            assert code == 200 and payload["revision"]
            code, _ = svc.persist("nope")
            assert code == 404
        finally:
            svc.stop()
            m.shutdown()


class TestFileStoreJournalSegments:
    def test_segments_roundtrip_and_prune(self, tmp_path):
        from siddhi_tpu.util.persistence import FileSystemPersistenceStore

        store = FileSystemPersistenceStore(str(tmp_path))
        store.save_journal_segment("a", 1, 4, b"cold")
        store.save_journal_segment("a", 5, 8, b"warm")
        assert store.load_journal_segments("a") == [
            (1, 4, b"cold"), (5, 8, b"warm")]
        # the journal dir must not masquerade as a revision
        store.save("a", "100_a", b"snap")
        assert store.revisions("a") == ["100_a"]
        store.prune_journal_segments("a", 4)
        assert store.load_journal_segments("a") == [(5, 8, b"warm")]
        store.clear_journal("a")
        assert store.load_journal_segments("a") == []
