"""``layers/panes.py`` on a hand-made ring: known ``pane`` spans in,
known per-batch values out; nothing from a program that records none."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(BENCH, "layers")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import panes  # noqa: E402  (benchmark/layers/panes.py)

MS = 1e-3
NAMES = ["rows.panes_per_batch", "rows.pane_ms_per_batch",
         "rows.pane_reduce_ms_per_batch", "rows.put_ms_per_batch"]


def span(cycle, stage, start_ms, end_ms, count=0):
    return (cycle, stage, "device", start_ms * MS, end_ms * MS, count)


def cycle(cid, t, closed):
    return [span(cid, "intern", t + 0.5, t + 1.0, 8192),
            span(cid, "pane", t + 1.0, t + 1.25, closed),
            span(cid, "put", t + 1.5, t + 2.0, 98_000),
            span(cid, "ingest", t + 0.5, t + 3.0, 8192)]


def make_run(ring, n_sends=4, clean=3):
    sends = [(100 * MS * n, (100 * n + 20) * MS) for n in range(n_sends)]
    window = types.SimpleNamespace(t0=0.0, sends=sends, clean=clean)
    return types.SimpleNamespace(wanted=NAMES, window=window,
                                 ring_spans=ring)


def test_known_pane_spans_give_known_values():
    # 8,192 rows over panes of 10: 819 or 820 close; the fourth batch
    # is the profiler's and is not read
    ring = cycle(1, -100, 777) + [
        s for n, closed in enumerate((819, 819, 820, 5))
        for s in cycle(2 + n, 100 * n, closed)]
    assert panes.read(make_run(ring)) == {
        "rows.panes_per_batch": pytest.approx((819 + 819 + 820) / 3),
        "rows.pane_ms_per_batch": pytest.approx(0.25)}


def test_a_program_without_the_span_yields_nothing():
    ring = [s for n in range(4) for s in cycle(1 + n, 100 * n, 819)
            if s[1] != "pane"]
    assert panes.read(make_run(ring)) == {}
    assert panes.read(make_run([])) == {}
