"""The plain reference of a pattern cell whose deployment checkpoints
itself: everything ``pattern_chain.py`` checks, on rows that were
delivered with checkpoints falling between them, and then the
durability guarantee as far as a run can show it.

The revisions the run committed are counted from the store's manifests,
each blob's SHA-256 against its manifest's, in plain ``json`` and
``hashlib``.  The newest is restored into a second runtime by the
schedule (``schedule.recover``: the program's own restore, the only
part of this that is not plain) and sent the batches that followed its
capture; this file runs its own automaton over the same keys from the
start of the capture's pass and compares the rows of those batches
exactly, payloads and timestamps.  A capture that tore a batch, lost a
key's pending instances or kept a later batch's, and a restore that
mis-packs a field, all fail it.  Imports nothing of the program."""

from __future__ import annotations

import collections
import glob
import hashlib
import importlib.util
import json
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "references.pattern_chain", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "pattern_chain.py"))
pattern_chain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(pattern_chain)


def committed(location: str, app: str, since_ms: int):
    """``(revisions, stale, torn)`` from the manifests under
    ``location``: the revisions committed since ``since_ms`` (a
    revision's name begins with the millisecond of its capture), how
    many are older, and how many blobs do not hash to what their
    manifest says.  A revision the store pruned meanwhile is skipped."""
    revisions, stale, torn = [], 0, 0
    for path in sorted(glob.glob(os.path.join(
            location, app, "*.ckpt", "MANIFEST.json"))):
        try:
            with open(path) as f:
                manifest = json.load(f)
            digests = []
            for el in manifest["elements"]:
                h = hashlib.sha256()
                with open(os.path.join(os.path.dirname(path),
                                       el["file"]), "rb") as f:
                    while chunk := f.read(1 << 24):
                        h.update(chunk)
                digests.append(h.hexdigest() == el["sha256"])
        except FileNotFoundError:
            continue
        if int(manifest["revision"].split("_", 1)[0]) < since_ms:
            stale += 1
            continue
        revisions.append(manifest)
        torn += digests.count(False)
    return revisions, stale, torn


def reference(spec, schedule, collector, n_sent, seed, rehearsal):
    # the durability part first: the deployment's daemon goes on ticking
    # after the clock has stopped, and the store keeps two revisions
    revisions, stale, torn = committed(
        schedule.location, schedule.app_name, schedule.made_ms)
    clocks = [int(schedule.batch_of(m["clock"])) for m in revisions
              if "clock" in m]
    recovered = schedule.recover(schedule.location)
    bad, compared = pattern_chain.reference(
        spec, schedule, collector, n_sent, seed, rehearsal)

    want_revs = spec["rehearsal_revisions" if rehearsal else "revisions"]
    in_window = sum(0 <= n < n_sent for n in clocks)
    compared += [
        (f"revisions committed inside the window, of {want_revs} owed: "
         f"too few (on disk {len(revisions)}, their clocks name batches "
         f"{clocks})", int(in_window < want_revs), 0),
        ("revisions on disk that an earlier run committed", stale, 0),
        ("blobs whose SHA-256 differs from their manifest's", torn, 0),
        ("the newest revision restored and replayed within "
         f"{schedule.limit_s} s: no", int(recovered is None), 0)]
    if recovered is None:
        return bad | set(range(n_sent)), compared

    n_c, rows = recovered
    last = schedule.last_of_replay(n_c)
    first = n_c - (n_c + schedule.warmup) % schedule.per_pass
    rng = np.random.default_rng(seed + 1)
    active = schedule.active_keys
    sample = np.concatenate([active, rng.choice(
        np.setdiff1d(schedule.all_keys, active),
        spec["rehearsal_swept_keys" if rehearsal else "swept_keys"],
        replace=False)])
    by_key = {}
    for n in range(first, last + 1):
        b = schedule.batch(n)
        keys, v = b.columns["key"], b.columns["v"]
        for i in np.flatnonzero(np.isin(keys, sample)):
            by_key.setdefault(int(keys[i]), []).append(
                (n, int(b.timestamps[i]), float(v[i])))
    want = collections.Counter(
        (schedule.ts_of(n), v1, v16) for evs in by_key.values()
        for n, v1, v16 in pattern_chain._chain_rows(
            evs, spec["states"], spec["within_ms"]) if n > n_c)
    keys = schedule.row_keys(rows)
    stray = ~np.isin(keys, active)
    got = collections.Counter(zip(
        rows["ts"][~stray].tolist(), rows["v1"][~stray].tolist(),
        rows["v16"][~stray].tolist()))
    differ = (want - got) + (got - want)
    owed = sum(want.values())
    compared += [
        (f"the restored revision's clock names batch {n_c}, outside the "
         f"window's {n_sent} batches", int(not 0 <= n_c < n_sent), 0),
        (f"rows of the replay (batches {n_c + 1}..{last} into the restored "
         f"state) that differ from the reference ({len(sample)} keys, "
         f"{owed} rows owed)", sum(differ.values()), 0),
        ("rows of the replay that belong to keys that were only swept",
         int(stray.sum()), 0),
        ("rows owed by the replay: none", int(not owed), 0)]
    if differ or stray.any() or not owed or not 0 <= n_c < n_sent:
        bad |= {min(max(n_c, 0), n_sent - 1)}
    return bad, compared
