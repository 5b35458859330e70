"""The arithmetic of ``correct``, ``attempted`` and ``failed``, and how a
configuration's plain reference is found.  Nothing here imports the
program.

A reference is a file of its own, ``references/<reference.kind>.py``,
with one function ``reference(spec, schedule, collector, n_sent, seed,
rehearsal)``: ``spec`` is the configuration's ``reference`` block,
``schedule`` re-makes every batch from the seed, ``collector`` holds the
rows the callback kept and the row count of every batch.  It is numpy
and plain Python, imports nothing of the program, and returns ``(bad,
compared)``: the set of window batch indices whose answers differ, and
the numbers compared, each as ``(name, value, limit)`` — a number over
its limit makes the run not correct.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
REFERENCES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "references")


def load_reference(kind: str):
    """``references/<kind>.py``'s ``reference``; a kind with no file is
    an error that names the file looked for."""
    path = os.path.join(REFERENCES, kind + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"reference kind {kind!r}: no file {path}")
    spec = importlib.util.spec_from_file_location("references." + kind, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.reference


def judge(dep, schedule, window, reference, platform: str):
    """``failed`` counts events of batches whose send raised, that the
    engine reports dropped (or that reached the exception listener), or
    whose answers differ.  Nothing else: a late batch is not failed."""
    bad, compared = reference
    raised = {n for n, _e in window.raised}
    lost = dep.dropped_batches() + len(dep.errors)
    off_path = dep.off_path(platform)
    compared = compared + [
        ("batches whose send raised", len(raised), 0),
        ("batches dropped or reported to the exception listener", lost, 0),
        ("instance-lane overflow", dep.overflow(), 0),
        ("reasons the deployment is off its path: "
         + ("; ".join(off_path) or "none"), len(off_path), 0)]
    attempted = window.n_sent * schedule.batch_events
    failed = min(attempted,
                 (len(bad | raised) + lost) * schedule.batch_events)
    correct = all(value <= limit for _name, value, limit in compared)
    return correct and not failed, attempted, failed, compared
