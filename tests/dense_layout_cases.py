"""Seeded scenarios for the dense state layout tests (test_dense_layout.py).

Each scenario drives one dense engine — on one device or over a 4-device
CPU mesh — through event steps with collision rounds and padded rows, a
purge of some rows, the deadline timer step where the engine has one,
and an int32 re-anchor, and after every operation takes the emissions
and the state in its LOGICAL form (``[rows, S, I]`` arrays per field).

``python tests/dense_layout_cases.py <out.npz>`` records those arrays.
``tests/fixtures/dense_layout_parent.npz`` was recorded that way with
``PYTHONPATH`` set to a checkout of the commit before the layout changed
(PR 26's tree, state as separate ``[P+1, S, I]`` arrays): only arrays
crossed, none of that commit's code.  ``logical()`` below is the one
place that differs between the two trees.
"""

from __future__ import annotations

import numpy as np

STREAMS = ("define stream S (k long, v double, n long); "
           "define stream T (k long, v double, n long); ")

# name -> (query, instances, source streams in the order they are fed)
ENGINES = {
    # `every`, two float captures (R = 2), within
    "every_r2": (
        "@info(name='q') from every e1=S[v > 1.0] -> e2=S[v > e1.v] -> "
        "e3=S[v > e2.v] within 2 sec "
        "select e1.v as a, e2.v as b, e3.v as c insert into Out;", 4, ["S"]),
    # integer captures (iregs bank) beside a float one
    "iregs": (
        "@info(name='q') from every e1=S[v > 1.0] -> e2=S[n > e1.n] -> "
        "e3=S[v > e1.v] within 2 sec "
        "select e1.n as a, e2.n as b, e3.v as c insert into Out;", 2, ["S"]),
    # absent node with a deadline: the timer step
    "deadline": (
        "@info(name='q') from every e1=S[v > 1.0] -> "
        "not T[v > e1.v] for 300 millisec -> e3=S[v > e1.v] within 2 sec "
        "select e1.v as a, e3.v as c insert into Out;", 4, ["S", "T"]),
    # a trailing absent node: the timer step emits, integer lane included
    "absent_last": (
        "@info(name='q') from every e1=S[v > 6.0] -> "
        "not T[v > e1.v] for 300 millisec "
        "select e1.v as a, e1.n as b insert into Out;", 4, ["S", "T"]),
    # no `every`: node 0 pre-armed in every row, one instance lane
    "non_every": (
        "@info(name='q') from e1=S[v > 1.0] -> e2=S[v > e1.v] "
        "select e1.v as a, e2.v as b insert into Out;", 4, ["S"]),
    # a counting node with first and last captures, lanes that overflow
    "count": (
        "@info(name='q') from every e1=S[v > 1.0]<2:3> -> e2=S[v > e1[last].v] "
        "within 2 sec "
        "select e1[0].v as a, e1[last].v as b, e2.v as c insert into Out;",
        2, ["S"]),
}
MESHES = (1, 4)
P = 24           # partitions (divisible by 4)
N_BATCHES = 6
BASE_TS = 1_000_000


def scenario_names():
    return [f"{e}-d{d}" for e in ENGINES for d in MESHES]


def logical(eng, state):
    """The state as ``{field: [rows, S, I(, R)]}`` host arrays."""
    if hasattr(eng, "layout"):
        return eng.layout.unpack(state)
    return {k: np.asarray(v) for k, v in state.items()}


def _batch(rng, n, ts0):
    """n events over few partitions: most partitions repeat inside the
    batch (collision rounds), and n is no power of two (padded rows)."""
    hot = rng.choice(P, size=5, replace=False)
    part = np.where(rng.random(n) < 0.6, rng.choice(hot, size=n),
                    rng.integers(0, P, size=n)).astype(np.int64)
    cols = {
        "k": part.copy(),
        "v": rng.integers(1, 12, size=n).astype(np.float64) + 0.5,
        # past int32: the hi word of the pair lanes matters
        "n": rng.integers(0, 6, size=n).astype(np.int64) * (2 ** 31 + 7),
    }
    ts = ts0 + np.sort(rng.integers(0, 200, size=n)).astype(np.int64)
    return part, cols, ts


def drive(name):
    """Run one scenario; returns ``{label: array}`` (the record)."""
    import jax

    from siddhi_tpu.ops.dense_nfa import compile_pattern

    eng_name, dev = name.rsplit("-d", 1)
    n_dev = int(dev)
    query, inst, streams = ENGINES[eng_name]
    eng = compile_pattern(STREAMS + query, "q", n_partitions=P,
                          n_instances=inst)
    sharded = None
    if n_dev > 1:
        from siddhi_tpu.parallel.mesh import ShardedPatternEngine, make_mesh

        mesh = make_mesh(n_dev, devices=jax.devices("cpu")[:n_dev])
        sharded = {sk: ShardedPatternEngine(eng, mesh, stream_key=sk)
                   for sk in streams}
        state = sharded[streams[0]].init_state()
    else:
        state = eng.init_state()
    rng = np.random.default_rng(
        [n_dev] + [ord(c) for c in eng_name])  # seeded per scenario
    rec = {}

    def keep(label, state):
        for k, v in logical(eng, state).items():
            rec[f"{label}/state/{k}"] = np.asarray(v)

    def step(label, sk, part, cols, ts):
        nonlocal state
        if sharded is not None:
            state, ev, out, _total = sharded[sk].process(
                state, part, cols, ts)
        else:
            state, ev, out = eng.process(state, sk, part, cols, ts)
        rec[f"{label}/ev"] = np.asarray(ev)
        rec[f"{label}/out"] = np.asarray(out, dtype=np.float64)
        keep(label, state)

    def tick(label, now):
        nonlocal state
        state, fired = eng.on_time_state(state, now)
        if fired is not None:
            out, fire_ts, rows = fired
            rec[f"{label}/fired_out"] = np.asarray(out, dtype=np.float64)
            rec[f"{label}/fired_ts"] = np.asarray(fire_ts)
            rec[f"{label}/fired_rows"] = np.asarray(rows)
        rec[f"{label}/fired"] = np.asarray(fired is not None)
        keep(label, state)

    ts0 = BASE_TS
    for b in range(N_BATCHES):
        sk = streams[b % len(streams)] if b % 3 == 2 else streams[0]
        part, cols, ts = _batch(rng, int(rng.integers(20, 45)), ts0)
        step(f"b{b}", sk, part, cols, ts)
        ts0 += 250
        if eng.has_deadlines:
            tick(f"t{b}", ts0 - 20)
        if b == 2:
            # purge, as DensePatternRuntime.purge_idle does it: rows set
            # back to the init template (every init row is identical)
            rows = np.asarray([1, 5, P // 2 + 3], dtype=np.int32)
            init = eng.init_state_host()
            state = {k: arr.at[rows].set(np.asarray(init[k][0]))
                     for k, arr in state.items()}
            keep("purge", state)

    # re-anchor: relative ms close to the int32 horizon shift base_ts
    far = eng.base_ts + 2 ** 31 - 2 ** 23
    for b in range(2):
        part, cols, ts = _batch(rng, 33, far + 150 * b)
        step(f"far{b}", streams[0], part, cols, ts)
    rec["base_ts"] = np.asarray(eng.base_ts)
    if eng.has_deadlines:
        tick("tfar", far + 900)
    return rec


# -- a runtime-level scenario: partitioned app, purge, snapshot --------------

APP = (
    "@app:playback @app:execution('tpu', partitions='8'{devices}) "
    "define stream Txn (card string, amount double); "
    "@purge(enable='true', interval='1 sec', idle.period='2 sec') "
    "partition with (card of Txn) begin "
    "@info(name='q') from every a=Txn[amount > 100.0] -> "
    "b=Txn[amount > a.amount] -> c=Txn[amount > b.amount] "
    "select a.amount as x, b.amount as y, c.amount as z insert into Alerts; "
    "end;"
)
SENDS_1 = [(k, 110.0 + 10 * i + j, 1000 + 10 * i + j)
           for i in range(2) for j, k in enumerate("abcdef")]
SENDS_2 = [(k, 400.0 + 5 * i, 5_000 + 10 * i + j)
           for i in range(3) for j, k in enumerate("abxy")]


def drive_app(n_dev=1, restore_from=None):
    """The app (on one device, or its partition axis over ``n_dev``)
    through SENDS_1, a purge by the playback clock, a snapshot, then
    SENDS_2.  Returns ``(matches, snapshot)`` where ``snapshot`` is the
    dense runtime's own.  With ``restore_from`` the first half is
    skipped and that snapshot restored instead."""
    from siddhi_tpu import SiddhiManager

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(APP.replace(
            "{devices}", f", devices='{n_dev}'" if n_dev > 1 else ""))
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(
            (list(e.data), e.timestamp) for e in evs))
        rt.start()
        h = rt.get_input_handler("Txn")
        pr = rt.partitions["partition_0"]
        runtime = next(iter(
            pr.dense_query_runtimes.values())).pattern_processor
        if restore_from is None:
            for k, amount, ts in SENDS_1:
                h.send([k, amount], timestamp=ts)
            # 'a' and 'b' stay alive with their pending chains, the
            # others fall idle and are purged
            for ts in (2_500, 4_000):
                h.send(["a", 50.0], timestamp=ts)
                h.send(["b", 50.0], timestamp=ts)
            rt.scheduler.advance(4_001)
        else:
            runtime.restore(restore_from)
        snap = runtime.snapshot()
        n_before = len(got)
        for k, amount, ts in SENDS_2:
            h.send([k, amount], timestamp=ts)
        rt.shutdown()
        return got[n_before:], snap
    finally:
        m.shutdown()


def record_all():
    rec = {}
    for name in scenario_names():
        for k, v in drive(name).items():
            rec[f"{name}/{k}"] = v
    for n_dev in MESHES:
        matches, snap = drive_app(n_dev)
        app = f"app-d{n_dev}"
        rec[f"{app}/matches"] = np.asarray(
            [row + [ts] for row, ts in matches], dtype=np.float64)
        for k, v in snap["dense_state"].items():
            rec[f"{app}/dense_state/{k}"] = np.asarray(v)
        rec[f"{app}/base_ts"] = np.asarray(snap["base_ts"])
        rec[f"{app}/row_last_used"] = np.asarray(snap["row_last_used"])
        key_rows = dict(zip(*(a.tolist() for a in snap["key_rows"])))
        keys = sorted(key_rows)
        rec[f"{app}/keys"] = np.asarray(keys)
        rec[f"{app}/key_rows"] = np.asarray([key_rows[k] for k in keys])
        rec[f"{app}/next_row"] = np.asarray(snap["next_row"])
        rec[f"{app}/free_rows"] = np.asarray(snap["free_rows"],
                                             dtype=np.int64)
    return rec


if __name__ == "__main__":
    import os
    import sys

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    np.savez_compressed(sys.argv[1], **record_all())
