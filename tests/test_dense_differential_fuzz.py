"""Randomized host-vs-dense differential testing of the pattern engines.

For a grid of pattern shapes (every-chains, counts, logical nodes,
sequences, within windows, integer id-joins) and seeded random event
streams, the SAME app runs through SiddhiManager twice — host mode and
@app:execution('tpu') — and the emitted rows must be IDENTICAL (values
and order).  This is the breadth play the hand-written corpora cannot
match: each (shape, seed) pair pins thousands of engine transitions.

The dense path must actually engage (asserted via the runtime type), so
a silent fallback cannot hollow the test out.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.dense_pattern import DensePatternRuntime

DEFINE = "define stream S (k long, u double, v double); "


def run(app, sends, mode_tpu, instances=16):
    m = SiddhiManager()
    try:
        header = "@app:playback "
        if mode_tpu:
            header += f"@app:execution('tpu', instances='{instances}') "
        rt = m.create_siddhi_app_runtime(header + DEFINE + app)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        qr = next(iter(rt.query_runtimes.values()), None)
        runtime = getattr(qr, "pattern_processor", None)
        overflow = (runtime.overflow_total()
                    if isinstance(runtime, DensePatternRuntime) else 0)
        rt.shutdown()
        return got, runtime, overflow
    finally:
        m.shutdown()


def gen_stream(seed, n=60, v_lo=0.0, v_hi=20.0, dt_max=400):
    rng = np.random.default_rng(seed)
    ts = 1000 + np.cumsum(rng.integers(1, dt_max, size=n))
    ks = rng.integers(0, 3, size=n)
    us = rng.uniform(v_lo, v_hi, size=n).round(1)
    vs = rng.uniform(v_lo, v_hi, size=n).round(1)
    return [([int(k), float(u), float(v)], int(t))
            for k, u, v, t in zip(ks, us, vs, ts)]


def norm(rows):
    """Round float values: DOUBLE attrs ride float32 dense lanes (the
    documented precision subset) — one-decimal inputs are exact at 4dp."""
    return [
        [round(v, 4) if isinstance(v, float) else v for v in r] for r in rows
    ]


def differential(app, seed, n=60, approx=False, **stream_kw):
    sends = gen_stream(seed, n=n, **stream_kw)
    host, _, _ = run(app, sends, mode_tpu=False)
    dense, runtime, overflow = run(app, sends, mode_tpu=True)
    assert isinstance(runtime, DensePatternRuntime), "did not lower densely"
    if overflow:
        # capacity-dropped instances legitimately diverge; with 16 lanes
        # over these streams this should stay rare — surface it
        pytest.skip(f"instance overflow ({overflow}) — not comparable")
    if approx:
        # aggregated outputs (sum over float32-quantized captures) carry
        # accumulated lane error — 4dp rounding could flip at a boundary,
        # so compare with a relative tolerance instead
        assert len(dense) == len(host), (
            f"seed {seed}: {len(dense)} dense vs {len(host)} host rows")
        for dr, hr in zip(dense, host):
            assert dr == pytest.approx(hr, rel=1e-4, abs=1e-3), (dr, hr)
        return host
    assert norm(dense) == norm(host), (
        f"seed {seed}: dense {len(dense)} rows != host {len(host)} rows\n"
        f"dense: {dense[:6]}...\nhost:  {host[:6]}...")
    return host


SHAPES = {
    "every_pair": (
        "@info(name='q') from every a=S[v > 10.0] -> b=S[v > a.v] "
        "within 3 sec select a.v as av, b.v as bv insert into Alerts;"),
    "every_triple": (
        "@info(name='q') from every a=S[v > 5.0] -> b=S[v > a.v] "
        "-> c=S[v > b.v] within 5 sec "
        "select a.v as av, b.v as bv, c.v as cv insert into Alerts;"),
    "every_two_filters": (
        "@info(name='q') from every a=S[u > 10.0 and v > 10.0] "
        "-> b=S[v < a.v and u > a.u] within 4 sec "
        "select a.u as au, a.v as av, b.u as bu, b.v as bv "
        "insert into Alerts;"),
    "exact_count": (
        "@info(name='q') from every a=S[v > 8.0]<2> -> b=S[v < 4.0] "
        "within 5 sec select a[0].v as a0, a[last].v as a1, b.v as bv "
        "insert into Alerts;"),
    "open_count": (
        "@info(name='q') from every a=S[v > 12.0]<1:> -> b=S[v < 4.0] "
        "within 5 sec select a[0].v as a0, b.v as bv insert into Alerts;"),
    "bounded_count": (
        "@info(name='q') from a=S[v > 8.0]<2:4> -> b=S[v < 4.0] "
        "within 5 sec select a[0].v as a0, b.v as bv insert into Alerts;"),
    "sequence_pair": (
        "@info(name='q') from every a=S[v > 10.0], b=S[v > a.v] "
        "select a.v as av, b.v as bv insert into Alerts;"),
    "non_every": (
        "@info(name='q') from a=S[v > 10.0] -> b=S[v > a.v] "
        "select a.v as av, b.v as bv insert into Alerts;"),
    "int_id_join": (
        "@info(name='q') from every a=S[v > 10.0] -> b=S[k == a.k] "
        "within 3 sec select a.v as av, b.v as bv insert into Alerts;"),
    # no filter reads a capture: the chain carries no register
    "capture_free": (
        "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
        "within 3 sec select b.v as bv insert into Alerts;"),
    "no_within": (
        "@info(name='q') from every a=S[v > 15.0] -> b=S[v > a.v] "
        "select a.v as av, b.v as bv insert into Alerts;"),
    "aggregating_selector": (
        "@info(name='q') from every a=S[v > 10.0] -> b=S[v > a.v] "
        "within 3 sec select a.v as av, sum(b.v) as t, count() as c "
        "group by a.v insert into Alerts;"),
    "having_over_aggregate": (
        "@info(name='q') from every a=S[v > 10.0] -> b=S[v > a.v] "
        "within 3 sec select a.v as av, sum(b.v) as t "
        "group by a.v having t > 20.0 insert into Alerts;"),
    # absent deadlines fire from the jitted timer step; the randomized
    # stream's watermark advances drive both engines' schedulers
    "trailing_absent": (
        "@info(name='q') from every a=S[v > 12.0] -> "
        "not S[v > a.v] for 500 millisec "
        "select a.v as av insert into Alerts;"),
    "group_every": (
        # whole-chain group-every: ONE arm at a time (virgin forms only
        # while the partition is empty), re-armed at completion/expiry
        "@info(name='q') from every (a=S[v > 8.0] -> b=S[v > a.v]) "
        "within 2 sec select a.v as av, b.v as bv insert into Alerts;"),
    "mid_chain_absent": (
        "@info(name='q') from every a=S[v > 14.0] -> "
        "not S[v > a.v] for 400 millisec -> c=S[v < 5.0] "
        "select a.v as av, c.v as cv insert into Alerts;"),
}


APPROX_SHAPES = {"aggregating_selector", "having_over_aggregate"}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_shape_matches_host(shape, seed):
    differential(SHAPES[shape], seed, approx=shape in APPROX_SHAPES)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_dense_stream_high_match_rate(seed):
    # low thresholds -> many overlapping arms and frequent completions
    app = ("@info(name='q') from every a=S[v > 2.0] -> b=S[v > a.v] "
           "within 2 sec select a.v as av, b.v as bv insert into Alerts;")
    differential(app, seed, n=40)


@pytest.mark.parametrize("seed", [21, 22])
def test_long_stream_within_churn(seed):
    # long stream with tight within: constant arm expiry churn
    app = ("@info(name='q') from every a=S[v > 6.0] -> b=S[v > a.v] "
           "within 1 sec select a.v as av, b.v as bv insert into Alerts;")
    differential(app, seed, n=120, dt_max=700)


def test_partitioned_fuzz_matches_host():
    app = ("partition with (k of S) begin "
           "@info(name='q') from every a=S[v > 8.0] -> b=S[v > a.v] "
           "within 3 sec select a.v as av, b.v as bv insert into Alerts; "
           "end;")
    sends = gen_stream(seed=31, n=80)
    host, _, _ = run(app, sends, mode_tpu=False)
    dense, _, _ = run(app, sends, mode_tpu=True)
    assert norm(dense) == norm(host)


def gen_skewed_stream(seed, n=360, hot_key=7, dt_max=60):
    """Three skew phases: the hot key takes ~85% of traffic, then the
    stream goes uniform (the router must demote and hand pending state
    back), then the same key heats up again (re-promotion)."""
    rng = np.random.default_rng(seed)
    out, t = [], 1000
    for i in range(n):
        t += int(rng.integers(1, dt_max))
        phase = (3 * i) // n
        hot = phase != 1 and rng.random() < 0.85
        k = hot_key if hot else int(rng.integers(0, 30))
        out.append(([int(k), float(round(rng.uniform(0, 20), 1)),
                     float(round(rng.uniform(0, 20), 1))], int(t)))
    return out


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_hotkey_skewed_fuzz_matches_host(seed):
    """Skewed keys crossing the promote/demote thresholds mid-run under
    @app:hotkeys: routing (dense rows <-> scan slots, exact state
    handoff both ways) must never alter detections."""
    from siddhi_tpu.core.hotkey_router import HotKeyRouterRuntime

    app = ("partition with (k of S) begin "
           "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
           "select b.v as bv insert into Alerts; "
           "end;")
    sends = gen_skewed_stream(seed)
    host, _, _ = run(app, sends, mode_tpu=False)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:playback @app:execution('tpu', instances='16') "
            "@app:hotkeys(k='4', promote='0.3', demote='0.1') "
            + DEFINE + app)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        router = None
        for pr in rt.partitions.values():
            for qr in pr.dense_query_runtimes.values():
                router = qr.pattern_processor
        assert isinstance(router, HotKeyRouterRuntime), "did not wrap"
        hot = router.hot_metrics()
        rt.shutdown()
    finally:
        m.shutdown()
    # the phased skew must actually exercise both decision edges
    assert hot["hotkeyPromotions"] >= 1, hot
    assert hot["hotkeyDemotions"] >= 1, hot
    assert norm(got) == norm(host)


def test_sharded_fuzz_matches_host():
    app = ("partition with (k of S) begin "
           "@info(name='q') from every a=S[v > 8.0] -> b=S[v > a.v] "
           "within 3 sec select a.v as av, b.v as bv insert into Alerts; "
           "end;")
    sends = gen_stream(seed=41, n=80)
    host, _, _ = run(app, sends, mode_tpu=False)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:playback @app:execution('tpu', partitions='64', "
            "devices='8', instances='8') " + DEFINE + app)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        rt.shutdown()
    finally:
        m.shutdown()
    assert norm(got) == norm(host)
