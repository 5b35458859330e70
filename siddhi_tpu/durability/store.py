"""Durable persistence store: files per element + checksummed manifest.

Revision layout (under ``<base>/<app>/``), manifest format 2::

    <revision>.ckpt/
        0000.blob           element 0: its pickle (protocol 5) without
        0000.000.buf ...    the buffers it left out of band, one file
        0001.blob ...       each, as they lay in memory; all fsynced
        MANIFEST.json       committed LAST: tmp + fsync + rename

**Every file is an entry of the manifest's** ``elements`` with its own
``file``, ``sha256`` and ``size``, in the order written; an entry with a
``buffer`` number is that buffer of the element entry before it (so a
reader that only hashes files needs to know nothing of this).  An
element's arrays of a page or more (``durability/capture.py``
``dumps_out_of_band``) are written and hashed from the buffer the
device transfer filled: no ``bytes`` of the element is ever built, and
``write`` and ``sha256`` let the interpreter go.  **Format 1** (one
``.blob`` an element, its arrays in band; no ``buffer`` entries) is the
same layout with nothing left out, and loads as it always did.

The manifest carries a SHA-256 per file plus a self-checksum over its
canonical JSON, so ``load`` detects torn files, bit flips, and partial
manifests: a revision without a valid manifest simply does not exist
(``revisions()`` skips it) and ``restore_last_revision()`` walks back to
the previous one.  Crash at ANY point mid-save therefore leaves either
the previous or the new revision fully restorable.  A commit evicts the
revisions past ``revisions_to_keep`` by withdrawing their manifests; their
files go one commit later (``_evict_locked``), so a reader that is
hashing a committed revision when the next one lands is not cut short.

``save_tree`` threads an optional ``checker(site)`` callable (the fault
injector's ``check``) through the commit sequence so the crash-point
matrix can kill the writer between every durability step:
``persist.post_blob`` (every file durable) / ``persist.pre_manifest`` /
``persist.mid_manifest`` (tmp manifest durable, rename pending).

Journal spill segments live beside the revisions under
``<base>/<app>/journal/`` (util/persistence.py FileJournalSegmentMixin).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import shutil
import threading
from typing import Callable, Dict, List, Optional, Tuple

from siddhi_tpu.observability.trace import (
    STAGE_PERSIST_HASH,
    STAGE_PERSIST_STORE,
    span,
)
from siddhi_tpu.util.persistence import (
    FileJournalSegmentMixin,
    PersistenceStore,
    fsync_dir,
)

log = logging.getLogger("siddhi_tpu.durability")

MANIFEST_NAME = "MANIFEST.json"
MANIFEST_FORMAT = 2
# what an evicted revision's manifest is renamed to until its files go
_EVICTED_NAME = "EVICTED.json"
_SUFFIX = ".ckpt"
# monolithic fallback: PersistenceStore.save bytes wrapped as one blob
_TREE_KIND = "__tree__"


def _manifest_checksum(manifest: Dict) -> str:
    body = {k: v for k, v in manifest.items() if k != "checksum"}
    canon = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode("utf-8")).hexdigest()


def open_store(location: Optional[str],
               revisions_to_keep=None) -> "DurableFileSystemPersistenceStore":
    """The store ``@app:persist(location='...', revisions.to.keep='N')``
    names: the durable one, under ``location``.  The planner builds an
    app's store through this, and so does a process that recovers one:
    the two then agree on the layout.  ``ValueError`` on a bad value."""
    if not location or not str(location).strip():
        raise ValueError("location must name a directory")
    if revisions_to_keep is None:
        return DurableFileSystemPersistenceStore(str(location))
    try:
        keep = int(revisions_to_keep)
    except (TypeError, ValueError):
        keep = 0
    if keep < 1:
        raise ValueError(f"revisions.to.keep {revisions_to_keep!r} must be "
                         "a whole number of 1 or more")
    return DurableFileSystemPersistenceStore(str(location), keep)


class DurableFileSystemPersistenceStore(FileJournalSegmentMixin,
                                        PersistenceStore):
    """Crash-consistent filesystem store (one directory per revision)."""

    def __init__(self, base_dir: str, revisions_to_keep: int = 3):
        self.base_dir = base_dir
        self.revisions_to_keep = revisions_to_keep
        self._lock = threading.Lock()

    def _app_dir(self, app_name: str) -> str:
        return os.path.join(self.base_dir, app_name)

    def _rev_dir(self, app_name: str, revision: str) -> str:
        return os.path.join(self._app_dir(app_name), revision + _SUFFIX)

    # -- save ---------------------------------------------------------------

    def _write(self, path: str, data) -> Dict:
        """One file of a revision, durable: ``data`` (bytes or a
        buffer) written as it lies, and its manifest fields."""
        size = memoryview(data).nbytes
        with span(STAGE_PERSIST_STORE, size):
            with open(path, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
        with span(STAGE_PERSIST_HASH, size):
            digest = hashlib.sha256(data).hexdigest()
        return {"file": os.path.basename(path), "sha256": digest,
                "size": size}

    def save_tree(self, app_name: str, revision: str, blobs: List[Tuple],
                  checker: Optional[Callable[[str], None]] = None,
                  version: int = 1, clock: Optional[int] = None) -> int:
        """Write per-element ``blobs`` [(kind, name, pickle)] or
        [(kind, name, pickle, buffers left out of band)] and commit the
        revision by atomically publishing its manifest; returns the
        bytes written.  Idempotent: a retry after a partial failure
        overwrites and re-commits.  ``clock`` is the tree's
        (util/snapshot.py): the app's time at the barrier, kept in the
        manifest as ``version`` is."""
        with self._lock:
            rev_dir = self._rev_dir(app_name, revision)
            os.makedirs(rev_dir, exist_ok=True)
            elements = []
            for idx, (kind, name, data, *rest) in enumerate(blobs):
                whose = {"kind": kind, "name": name}
                elements.append({**whose, **self._write(
                    os.path.join(rev_dir, f"{idx:04d}.blob"), data)})
                for k, buf in enumerate(rest[0] if rest else ()):
                    elements.append({**whose, **self._write(
                        os.path.join(rev_dir, f"{idx:04d}.{k:03d}.buf"),
                        buf), "buffer": k})
            if checker is not None:
                checker("persist.post_blob")
            manifest = {"format": MANIFEST_FORMAT, "app": app_name,
                        "revision": revision, "version": version,
                        "elements": elements}
            if clock is not None:
                manifest["clock"] = clock
            manifest["checksum"] = _manifest_checksum(manifest)
            if checker is not None:
                checker("persist.pre_manifest")
            tmp = os.path.join(rev_dir, MANIFEST_NAME + ".tmp")
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            if checker is not None:
                # tmp manifest durable, rename pending: the one crash
                # point where the revision exists but is not committed
                checker("persist.mid_manifest")
            os.replace(tmp, os.path.join(rev_dir, MANIFEST_NAME))
            fsync_dir(rev_dir)
            fsync_dir(self._app_dir(app_name))
            self._evict_locked(app_name)
            return sum(el["size"] for el in elements)

    def save(self, app_name: str, revision: str, snapshot: bytes):
        """PersistenceStore SPI: monolithic bytes become one blob."""
        self.save_tree(app_name, revision,
                       [(_TREE_KIND, _TREE_KIND, snapshot)])

    def _evict_locked(self, app_name: str):
        """Eviction takes two commits.  A revision past
        ``revisions_to_keep`` first loses its manifest (renamed aside,
        atomically): it is committed no longer, ``revisions()`` does not
        list it and no walk restores it.  Its files stay until the NEXT
        commit sweeps them with the torn directories, so a reader that
        has a revision's manifest open (a backup, a plain reference that
        hashes every file) finishes on files that are still there;
        removed under it, they left it a revision short.  The price is
        one revision's files on disk for one more interval."""
        committed = self._committed_locked(app_name)
        if not committed:
            return
        app_dir = self._app_dir(app_name)
        newest_ts = int(committed[-1].split("_", 1)[0])
        try:
            names = os.listdir(app_dir)
        except OSError:
            return
        live = {r + _SUFFIX for r in committed}
        for d in names:
            if not d.endswith(_SUFFIX) or d in live:
                continue
            rev = d[: -len(_SUFFIX)]
            try:
                ts = int(rev.split("_", 1)[0])
            except ValueError:
                continue
            if ts < newest_ts:
                if not os.path.isfile(os.path.join(app_dir, d, _EVICTED_NAME)):
                    # a crash's leftover, never restorable
                    log.warning("durability: removing torn revision %r of "
                                "app %r (no valid manifest)", rev, app_name)
                shutil.rmtree(os.path.join(app_dir, d), ignore_errors=True)
        for old in committed[: max(0, len(committed)
                                   - self.revisions_to_keep)]:
            rev_dir = self._rev_dir(app_name, old)
            try:
                os.replace(os.path.join(rev_dir, MANIFEST_NAME),
                           os.path.join(rev_dir, _EVICTED_NAME))
            except OSError:
                shutil.rmtree(rev_dir, ignore_errors=True)

    # -- load ---------------------------------------------------------------

    def _read_manifest(self, app_name: str, revision: str) -> Optional[Dict]:
        path = os.path.join(self._rev_dir(app_name, revision), MANIFEST_NAME)
        try:
            with open(path, "r", encoding="utf-8") as f:
                manifest = json.load(f)
        except (OSError, ValueError) as e:
            log.warning("durability: revision %r of app %r has no "
                        "readable manifest (%s)", revision, app_name, e)
            return None
        if manifest.get("checksum") != _manifest_checksum(manifest):
            log.warning("durability: manifest checksum mismatch on "
                        "revision %r of app %r", revision, app_name)
            return None
        return manifest

    def _read_blobs(self, app_name: str, revision: str) -> Optional[
            List[Tuple[str, str, bytearray, List[bytearray]]]]:
        """[(kind, name, pickle, its out-of-band buffers)] of a revision
        of either format, every file held to its manifest entry."""
        manifest = self._read_manifest(app_name, revision)
        if manifest is None:
            return None
        rev_dir = self._rev_dir(app_name, revision)
        out: List[Tuple[str, str, bytearray, List[bytearray]]] = []
        for el in manifest.get("elements", []):
            try:
                with open(os.path.join(rev_dir, el["file"]), "rb") as f:
                    # a buffer comes back writable: what restores from
                    # it may keep the array and write to it
                    data = bytearray(os.fstat(f.fileno()).st_size)
                    f.readinto(data)
            except OSError as e:
                log.warning("durability: blob %r missing from revision "
                            "%r of app %r (%s)", el.get("file"), revision,
                            app_name, e)
                return None
            if hashlib.sha256(data).hexdigest() != el.get("sha256"):
                log.warning("durability: blob %r of revision %r of app "
                            "%r fails its checksum", el.get("file"),
                            revision, app_name)
                return None
            if "buffer" not in el:
                out.append((el["kind"], el["name"], data, []))
            elif not out or el["buffer"] != len(out[-1][3]):
                log.warning("durability: buffer %r of revision %r of app "
                            "%r is out of place", el.get("file"), revision,
                            app_name)
                return None
            else:
                out[-1][3].append(data)
        return out

    def load(self, app_name: str, revision: str) -> Optional[bytes]:
        """Checksum-validated revision bytes, reassembled into the
        monolithic tree pickle ``SnapshotService.restore`` expects.
        ``None`` on any corruption — the restore walk falls back."""
        blobs = self._read_blobs(app_name, revision)
        if blobs is None:
            return None
        if len(blobs) == 1 and blobs[0][0] == _TREE_KIND:
            return bytes(blobs[0][2])
        tree: Dict = {"queries": {}, "tables": {}, "named_windows": {},
                      "partitions": {}, "aggregations": {}}
        try:
            for kind, name, data, buffers in blobs:
                tree[kind][name] = pickle.loads(data, buffers=buffers)
        except Exception as e:
            log.warning("durability: revision %r of app %r holds an "
                        "unreadable element (%s)", revision, app_name, e)
            return None
        manifest = self._read_manifest(app_name, revision) or {}
        tree["version"] = manifest.get("version", 1)
        tree["app"] = app_name
        if "clock" in manifest:
            tree["clock"] = manifest["clock"]
        return pickle.dumps(tree, protocol=pickle.HIGHEST_PROTOCOL)

    # -- revisions ----------------------------------------------------------

    def _committed_locked(self, app_name: str) -> List[str]:
        """Revisions with a manifest file present, oldest first (manifest
        VALIDITY is checked at load; presence defines existence)."""
        d = self._app_dir(app_name)
        try:
            names = os.listdir(d)
        except OSError:
            return []
        revs = []
        for f in names:
            if not f.endswith(_SUFFIX):
                continue
            rev = f[: -len(_SUFFIX)]
            try:
                int(rev.split("_", 1)[0])
            except ValueError:
                continue
            if os.path.isfile(os.path.join(d, f, MANIFEST_NAME)):
                revs.append(rev)
        return sorted(revs, key=lambda r: int(r.split("_", 1)[0]))

    def get_last_revision(self, app_name: str) -> Optional[str]:
        with self._lock:
            revs = self._committed_locked(app_name)
            return revs[-1] if revs else None

    def revisions(self, app_name: str) -> List[str]:
        with self._lock:
            return self._committed_locked(app_name)

    def clear_all_revisions(self, app_name: str):
        with self._lock:
            d = self._app_dir(app_name)
            try:
                names = os.listdir(d)
            except OSError:
                return
            for f in names:
                if f.endswith(_SUFFIX):
                    shutil.rmtree(os.path.join(d, f), ignore_errors=True)
