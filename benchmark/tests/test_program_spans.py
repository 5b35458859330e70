"""``layers/program_spans.py`` on a hand-made ring: known spans in,
known per-batch values out; nothing for what the ring does not hold."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH, os.path.join(BENCH, "layers")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import program_spans  # noqa: E402  (benchmark/layers/program_spans.py)

MS = 1e-3
NAMES = ["events." + n for n in (*program_spans.STAGE_OF, program_spans.UNATTRIBUTED)] + [
    "events.send_ms_per_batch", "events.no_such_metric"]


def span(cycle, stage, start_ms, end_ms, count=0):
    return (cycle, stage, "dense", start_ms * MS, end_ms * MS, count)


def cycle(cid, t):
    """One 20 ms batch sent at ``t`` ms: interning ahead of ingest, two
    collision rounds, a wait on the device, an emit; 1.5 ms of it under
    no span (0.5 ahead of the cycle, 0.5 between step and emit, 0.5
    after the emit)."""
    return [
        span(cid, "intern", t + 0.5, t + 2.5, 100),
        span(cid, "convert", t + 2.5, t + 4.0, 100),
        span(cid, "put", t + 4.0, t + 5.0, 4096),
        span(cid, "dispatch", t + 5.0, t + 5.5, 1),
        span(cid, "convert", t + 5.5, t + 6.0, 4),
        span(cid, "put", t + 6.0, t + 6.5, 1024),
        span(cid, "dispatch", t + 6.5, t + 7.0, 1),
        span(cid, "ingest", t + 2.5, t + 7.0, 100),
        span(cid, "step", t + 7.0, t + 17.0, 100),
        span(cid, "fetch", t + 17.5, t + 18.5, 64),
        span(cid, "deliver", t + 18.5, t + 19.5, 2),
        span(cid, "emit", t + 17.5, t + 19.5, 2),
    ]


def make_run(ring, n_sends=4, clean=3):
    sends = [(100 * MS * n, (100 * n + 20) * MS) for n in range(n_sends)]
    window = types.SimpleNamespace(t0=0.0, sends=sends, clean=clean)
    return types.SimpleNamespace(wanted=NAMES, window=window,
                                 ring_spans=ring)


def test_known_spans_give_known_values():
    warm = cycle(1, -100)                  # before the window opened
    ring = warm + [s for n in range(4) for s in cycle(2 + n, 100 * n)]
    got = program_spans.read(make_run(ring))
    # batch 3 is the profiler's: three clean batches are read
    assert got == {
        "events.intern_ms_per_batch": pytest.approx(2.0),
        "events.convert_ms_per_batch": pytest.approx(2.0),
        "events.put_ms_per_batch": pytest.approx(1.5),
        "events.h2d_bytes_per_batch": pytest.approx(5120),
        "events.step_wait_ms_per_batch": pytest.approx(10.0),
        "events.host_unattributed_ms_per_batch": pytest.approx(1.5),
    }


def test_an_evicting_ring_reads_only_what_it_still_holds():
    # the ring lost the warm-up and the window's first batch, and with
    # them half of the second batch's spans
    ring = cycle(3, 100)[6:] + cycle(4, 200) + cycle(5, 300)
    got = program_spans.read(make_run(ring))
    assert got["events.host_unattributed_ms_per_batch"] == pytest.approx(1.5)
    assert got["events.step_wait_ms_per_batch"] == pytest.approx(10.0)


def test_a_program_without_the_spans_yields_nothing_for_them():
    old = [s for n in range(4) for s in cycle(1 + n, 100 * n - 100)
           if s[1] in ("ingest", "step", "emit")]
    got = program_spans.read(make_run(old))
    assert set(got) == {"events.step_wait_ms_per_batch",
                        "events.host_unattributed_ms_per_batch"}
    # 20 ms less ingest 4.5, step 10, emit 2
    assert got["events.host_unattributed_ms_per_batch"] == pytest.approx(3.5)
    assert program_spans.read(make_run([])) == {}
