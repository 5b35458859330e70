"""Skewed batches through the dense engine: one key many times in one
batch.  ``round_plan`` orders the events (held, element for element,
to the sort it replaced: ``_plan_by_sort``), the first occurrences go
through the plain step and every later one through ``make_rounds``'
device loop.  Whatever the skew, the result must be what the same events
give one at a time: state and emissions exact, for every engine kind of
``dense_layout_cases.ENGINES``, on one device and over the 4-device CPU
mesh; and through ``SiddhiManager`` what the host engine (``ops/nfa.py``)
and a plain chain automaton give."""

from __future__ import annotations

import numpy as np
import pytest

from dense_layout_cases import ENGINES, MESHES, STREAMS, logical

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.ops.dense_nfa import RoundPlan, compile_pattern, round_plan

P = 24


# -- the plan -----------------------------------------------------------------

def _skewed(rng, runs, n_once, n_keys=10_000):
    """A shuffled batch in which key ``i`` appears ``runs[i]`` times and
    ``n_once`` further keys once each."""
    keys = rng.choice(n_keys, size=len(runs) + n_once, replace=False)
    part = np.concatenate([np.repeat(keys[:len(runs)], runs),
                           keys[len(runs):]])
    rng.shuffle(part)
    return part.astype(np.int32)


PLAN_CASES = {
    "empty": ([], 0),
    "runs_of_1": ([], 40),
    "run_of_2": ([2], 30),
    "run_of_17": ([17], 30),
    "run_of_1000": ([1000], 100),
    "one_key_only": ([64], 0),
    "several_hot_keys": ([300, 300, 120, 17, 5, 2, 2, 2], 200),
    "equal_runs": ([4, 4, 4, 4], 0),
    # tests/test_dense_nfa.py::test_batch_collision_rounds and
    # tests/test_parallel.py::test_collision_rounds_same_partition drive
    # these two shapes through the engines
    "five_of_one_partition": ([5], 0),
    "four_of_one_partition": ([4], 0),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_round_plan(case):
    runs, n_once = PLAN_CASES[case]
    part = _skewed(np.random.default_rng(len(case)), runs, n_once)
    plan = round_plan(part)
    n = len(part)
    assert sorted(plan.lanes.tolist()) == list(range(n))
    assert plan.off[0] == 0 and plan.off[-1] == n
    assert plan.n_rounds == (max(runs + [1]) if n else 0)
    widths = np.diff(plan.off)
    assert (widths > 0).all() and (np.diff(widths) <= 0).all()
    seen = {}
    for r in range(plan.n_rounds):
        ev = plan.round(r)
        keys = part[ev]
        assert len(set(keys.tolist())) == len(keys)   # once a round
        if r:
            # the partitions of a round are a prefix of the one before
            assert (keys == part[plan.round(r - 1)][:len(keys)]).all()
        for k, e in zip(keys.tolist(), ev.tolist()):
            assert seen.get(k, -1) < e                # arrival order kept
            seen[k] = e
    if plan.n_rounds:
        # more events first, ties by first arrival, then the keys that
        # come once in arrival order
        first = part[plan.round(0)]
        count = {k: int((part == k).sum()) for k in first.tolist()}
        arrival = {k: int(np.flatnonzero(part == k)[0])
                   for k in first.tolist()}
        assert first.tolist() == sorted(
            first.tolist(),
            key=lambda k: (-count[k], arrival[k]) if count[k] > 1
            else (0, arrival[k]))


# -- the plan against the sort it replaced -------------------------------------

def _plan_by_sort(part_idx: np.ndarray) -> RoundPlan:
    """``round_plan`` as it stood until PR 52: one sort of the batch,
    one ``lexsort`` of the repeated events.  The oracle: the plan is a
    contract (the device's gathers, scatters and resident rows follow
    it), so the planner returns it element for element."""
    part_idx = np.asarray(part_idx)
    n = len(part_idx)
    if n == 0:
        return RoundPlan(np.empty(0, dtype=np.int64),
                         np.zeros(1, dtype=np.int64))
    # (partition, arrival) packed into one word: the keys are distinct,
    # so the plain sort, several times faster than a stable one, orders
    # each partition's events by arrival
    key = (part_idx.astype(np.int64) << 32) | np.arange(n, dtype=np.int64)
    key.sort()
    order = key & 0xFFFFFFFF
    sorted_parts = key >> 32
    is_new = np.ones(n, dtype=bool)
    is_new[1:] = sorted_parts[1:] != sorted_parts[:-1]
    starts = np.flatnonzero(is_new)            # of each partition's group
    if len(starts) == n:                       # no partition repeats
        return RoundPlan(np.arange(n, dtype=np.int64),
                         np.asarray([0, n], dtype=np.int64))
    cnt = np.diff(starts, append=n)            # events per group
    group = np.cumsum(is_new) - 1              # group of each sorted event
    pos = np.flatnonzero(cnt[group] > 1)       # sorted events that repeat
    g = group[pos]
    occ = pos - starts[g]                      # occurrence within the group
    # a group's first arrival is its first sorted event
    ranked = order[pos[np.lexsort((order[starts[g]], -cnt[g], occ))]]
    widths = np.bincount(occ)
    repeated = np.zeros(n, dtype=bool)
    repeated[ranked] = True
    once = np.flatnonzero(~repeated)           # arrival order
    lanes = np.concatenate([ranked[:widths[0]], once, ranked[widths[0]:]])
    widths[0] += len(once)
    return RoundPlan(lanes, np.concatenate([[0], np.cumsum(widths)]))


def _same_plan(got: RoundPlan, want: RoundPlan):
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (a == b).all()


def _zipf(rng, n, n_rows, theta=0.99):
    """``n`` rows of ``n_rows`` by Zipf(``theta``), scattered over the
    row space as the skewed cell's are."""
    p = 1.0 / np.arange(1, n_rows + 1) ** theta
    ranks = rng.choice(n_rows, size=n, p=p / p.sum())
    return (ranks * 2654435761 % n_rows).astype(np.int32)


def _card_batch(rehearsal, widths):
    """A batch of ``cardfraud_100k.saturated``'s own generator under
    ``benchmark/traffic/card_pass_saturated.json``, its cards as rows."""
    from cardfraud_bench import CONFIG, GEN, TRAFFIC

    cards = GEN.make(2**31 + 52, CONFIG, TRAFFIC, rehearsal).batch(
        0).columns["card"]
    keys, part = np.unique(cards, return_inverse=True)
    assert [int((np.bincount(part) > r).sum())
            for r in range(len(widths) + 1)] == widths + [0]
    return len(keys), part


# name -> (rows of the engine, the batch's partition rows)
SHAPES = {
    # 131,072 events, 4,096 keys twice, 1,000,000 rows
    "flagship": lambda rng: (1_000_000, _skewed(
        rng, [2] * 4_096, 131_072 - 2 * 4_096, 1_000_000)),
    # more events than rows: cardfraud_100k's batch, and its rehearsal
    "card_full": lambda rng: _card_batch(
        False, [84_000, 39_024, 7_024, 1_024]),
    "card_rehearsal": lambda rng: _card_batch(True, [3_440, 1_599, 288, 42]),
    "zipf": lambda rng: (1_000_000, _zipf(rng, 16_384, 1_000_000)),
    "one_key_only": lambda rng: (1_000, np.full(5_000, 77, dtype=np.int32)),
    # a run past what the counting pass over uint16 holds
    "one_key_70000_times": lambda rng: (64, np.concatenate(
        [np.full(70_000, 5), rng.integers(0, 64, 300)]).astype(np.int64)),
    "no_repeat": lambda rng: (1_000_000, _skewed(
        rng, [], 131_072, 1_000_000)),
    "the_last_row_and_the_scratch_row": lambda rng: (
        8, np.asarray([7, 8, 7, 0, 8, 8], dtype=np.int32)),
}


@pytest.mark.parametrize("case", list(PLAN_CASES) + list(SHAPES)
                         + [f"random_{s}" for s in range(50)])
def test_round_plan_matches_sort(case):
    if case in PLAN_CASES:
        runs, n_once = PLAN_CASES[case]
        n_rows = 10_000
        part = _skewed(np.random.default_rng(len(case)), runs, n_once)
    elif case in SHAPES:
        n_rows, part = SHAPES[case](np.random.default_rng(52))
    else:
        rng = np.random.default_rng(int(case.split("_")[1]))
        n_rows = int(rng.integers(1, 501))
        part = rng.integers(0, n_rows, size=int(rng.integers(1, 3_001)))
    want = _plan_by_sort(part)
    _same_plan(round_plan(part), want)
    # over a vector of the row space that is not clean
    first = np.full(n_rows + 1, 3, dtype=np.int32)
    _same_plan(round_plan(part, first), want)


class _FirstWriteStays(np.ndarray):
    """A vector on which the FIRST write of a repeated index stays: the
    other order numpy could write in."""

    def __setitem__(self, idx, value):
        if np.ndim(value):
            idx, value = idx[::-1], value[::-1]
        super().__setitem__(idx, value)


@pytest.mark.parametrize("case", ["several_hot_keys", "run_of_1000",
                                  "equal_runs"])
def test_round_plan_whichever_write_stays(case):
    """numpy does not say which value stays where an index repeats in
    ``a[idx] = v``; ``round_plan`` reads the entry back and lets an
    earlier event take it, so the plan is the same either way."""
    runs, n_once = PLAN_CASES[case]
    part = _skewed(np.random.default_rng(len(case)), runs, n_once)
    first = np.zeros(10_001, dtype=np.int32).view(_FirstWriteStays)
    probe = np.zeros(2, dtype=np.int32).view(_FirstWriteStays)
    probe[np.asarray([1, 1])] = np.asarray([5, 6], dtype=np.int32)
    assert probe[1] == 5
    _same_plan(round_plan(part, first), _plan_by_sort(part))


def test_the_last_write_of_a_repeated_index_stays():
    """What ``round_plan`` is fast by and not right by: numpy writes
    ``first[idx[::-1]] = arrival[::-1]`` in index order, so a row's
    first arrival, written last, stays, and the loop under that line in
    ``siddhi_tpu/ops/dense_nfa.py`` (``while (lead > arrival).any()``)
    never runs.  Were this to fail, the plan would still be right (the
    test above) and a pass slower for each time round."""
    rng = np.random.default_rng(7)
    for n, n_rows in ((10, 3), (5_000, 40), (131_072, 100_000)):
        idx = rng.integers(0, n_rows, size=n)
        arrival = np.arange(n, dtype=np.int32)
        first = np.empty(n_rows, dtype=np.int32)
        first[idx[::-1]] = arrival[::-1]
        rows, firsts = np.unique(idx, return_index=True)
        assert (first[rows] == firsts).all()
        assert not (first[idx] > arrival).any()


# -- the engine's vector over the row space ------------------------------------

def _plain_engine(n_partitions):
    return compile_pattern(STREAMS + ENGINES["every_r2"][0], "q",
                           n_partitions=n_partitions, n_instances=4)


def test_one_engine_plans_batch_after_batch():
    """What a batch wrote into the engine's vector is nothing to the
    next: all of the first's keys again, a part of them, none twice."""
    from siddhi_tpu.core.ingest_stage import IngestStats

    eng = _plain_engine(10_000)
    eng.ingest_stats = IngestStats()
    assert eng._plan_first is None          # made by the first batch
    rng = np.random.default_rng(3)
    first = _skewed(rng, [300, 300, 120, 17, 5, 2, 2, 2], 200)
    repeats = 0
    for part in (first, first[::-1].copy(), first[first % 3 == 0],
                 rng.permutation(10_000)[:500].astype(np.int32), first):
        _same_plan(eng.plan_rounds(part), _plan_by_sort(part))
        repeats += len(part) - len(np.unique(part))
        assert eng.ingest_stats.planned_repeats == repeats
    vector = eng._plan_first
    assert vector.dtype == np.int32 and vector.shape == (10_001,)
    eng.plan_rounds(first[:0])              # an empty batch counts nothing
    assert eng._plan_first is vector        # made once and kept
    assert eng.ingest_stats.planned_repeats == repeats


def test_two_engines_plan_in_turn():
    """Each engine's vector is its own, as long as its own rows."""
    small, large = _plain_engine(24), _plain_engine(5_000)
    rng = np.random.default_rng(4)
    for _ in range(3):
        a = rng.integers(0, 24, size=200).astype(np.int32)
        b = _skewed(rng, [40, 9, 2], 300, n_keys=5_000)
        _same_plan(small.plan_rounds(a), _plan_by_sort(a))
        _same_plan(large.plan_rounds(b), _plan_by_sort(b))
    assert small._plan_first.shape == (25,)
    assert large._plan_first.shape == (5_001,)


@pytest.fixture
def planned(monkeypatch):
    """Every ``round_plan`` the program calls while the test runs, as
    ``(events, rounds, length of the vector it was given)``, each plan
    held to ``_plan_by_sort``'s."""
    import siddhi_tpu.ops.dense_nfa as dense_nfa

    calls, real = [], dense_nfa.round_plan

    def spy(part_idx, first=None):
        plan = real(part_idx, first)
        _same_plan(plan, _plan_by_sort(part_idx))
        calls.append((len(part_idx), plan.n_rounds,
                      None if first is None else len(first)))
        return plan

    monkeypatch.setattr(dense_nfa, "round_plan", spy)
    return calls


@pytest.mark.parametrize("devices", ["", ", devices='4'"],
                         ids=["one_device", "four_devices"])
def test_the_served_engines_plan_with_their_own_vector(planned, devices):
    """The dense engine and the sharded one (which plans with its inner
    engine's) through ``SiddhiManager``: tests/test_parallel.py's
    collision case, one key four times in a batch, then a batch of
    other keys once each."""
    keys = np.asarray([5, 5, 5, 5, 9], dtype=np.int64)
    batches = [
        EventBatch("Txn", ["key", "v"], {"key": keys, "v": np.arange(5) + 1.5},
                   1_000 + np.arange(5, dtype=np.int64)),
        EventBatch("Txn", ["key", "v"],
                   {"key": np.arange(20, 30, dtype=np.int64),
                    "v": np.full(10, 0.5)},
                   2_000 + np.arange(10, dtype=np.int64))]
    got, lowering, overflow = _run_app(
        f"@app:playback @app:execution('tpu', partitions='64'{devices}) ",
        batches)
    assert lowering == {"bench": "dense"} and overflow == 0
    assert len(got) == 1                    # 1.5 -> 2.5 -> 3.5 -> 4.5 of key 5
    assert planned == [(5, 4, 64 + 1), (10, 1, 64 + 1)]


def test_a_multiplexed_group_plans_with_its_engines_vector(planned):
    """``multiplex/dense_group.py`` goes through the shared engine's
    ``process_deferred``: a tenant's batch of five events is five rounds
    of its one row."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('t0') @app:execution('tpu') @app:playback "
            "@app:multiplex(slots='8') define stream A (v double); "
            "define stream B (w double); "
            "@info(name='qp') from every e1=A[v > 2] -> e2=B[w > e1.v] "
            "select e1.v as v1, e2.w as w2 insert into OutP;")
        rt.start()
        assert rt.lowering() == {"qp": "multiplex"}
        rt.get_input_handler("A").send_batch(EventBatch(
            "A", ["v"], {"v": np.arange(5) + 3.0},
            1_000 + np.arange(5, dtype=np.int64)))
        rt.shutdown()
    finally:
        m.shutdown()
    assert planned == [(5, 5, 8 + 1)]


# -- every engine kind: a skewed batch against one event at a time ------------

def _events(rng, scenario, n_part=P):
    """``(part, cols, ts)`` of one skewed batch."""
    # every scenario's later rounds pad to 64 lanes: one program each
    if scenario == "runs_1_2_17":
        runs, n_once, span = [17, 17, 2, 2], 8, 600
    elif scenario == "several_hot_keys":
        runs, n_once, span = [24, 24, 9, 3, 2], 7, 1_500
    elif scenario == "hot_key_meets_padded_row":
        # 9 + 39 events: round 0 and the rest are both padded, and the
        # hot key's partition is the last one, next to the scratch row
        runs, n_once, span = [40], 8, 400
    elif scenario == "run_crosses_within":
        runs, n_once, span = [60, 5], 4, 9_000   # within is 2 sec
    elif scenario == "run_of_1000":
        runs, n_once, span = [1000, 30, 2], 12, 1_900
    else:
        raise KeyError(scenario)
    keys = rng.choice(n_part - 1, size=len(runs) + n_once, replace=False)
    if scenario == "hot_key_meets_padded_row":
        keys[0] = n_part - 1
    part = np.concatenate([np.repeat(keys[:len(runs)], runs),
                           keys[len(runs):]]).astype(np.int64)
    rng.shuffle(part)
    n = len(part)
    # mostly rising within a key, with some falls: chains advance, some
    # lanes fill up and overflow
    occ = np.zeros(n, dtype=np.int64)
    seen = {}
    for i, k in enumerate(part.tolist()):
        occ[i] = seen.get(k, 0)
        seen[k] = occ[i] + 1
    v = 1.5 + (occ % 7) + rng.integers(0, 3, size=n)
    cols = {"k": part.copy(), "v": v.astype(np.float64),
            "n": (occ % 5 + rng.integers(0, 2, size=n)).astype(np.int64)
            * (2 ** 31 + 7)}
    ts = 1_000_000 + np.sort(rng.integers(0, span, size=n)).astype(np.int64)
    return part, cols, ts


def _engine(eng_name, n_dev):
    import jax

    query, inst, streams = ENGINES[eng_name]
    eng = compile_pattern(STREAMS + query, "q", n_partitions=P,
                          n_instances=inst)
    if n_dev == 1:
        return eng, eng.init_state(), lambda st, sk, *a: eng.process(
            st, sk, *a)
    from siddhi_tpu.parallel.mesh import ShardedPatternEngine, make_mesh

    mesh = make_mesh(n_dev, devices=jax.devices("cpu")[:n_dev])
    sharded = {sk: ShardedPatternEngine(eng, mesh, stream_key=sk)
               for sk in streams}
    return eng, sharded[streams[0]].init_state(), (
        lambda st, sk, *a: sharded[sk].process(st, *a)[:3])


SCENARIOS = ["runs_1_2_17", "several_hot_keys", "hot_key_meets_padded_row",
             "run_crosses_within"]


def check_against_one_event_at_a_time(eng_name, n_dev, what):
    streams = ENGINES[eng_name][2]
    scenarios = SCENARIOS if what == "skewed" else [what]
    got, want = [], []
    states = []
    for sink, whole in ((got, True), (want, False)):
        eng, state, process = _engine(eng_name, n_dev)
        rng = np.random.default_rng(              # the same events twice
            [ord(c) for c in eng_name])
        # one batch after another: each meets the state the last left
        for b, scenario in enumerate(scenarios):
            sk = streams[b % len(streams)]
            part, cols, ts = _events(rng, scenario)
            ts = ts + 700 * b
            pieces = ([np.arange(len(part))] if whole
                      else [np.asarray([i]) for i in range(len(part))])
            for ev in pieces:
                state, idx, out = process(
                    state, sk, part[ev], {k: c[ev] for k, c in cols.items()},
                    ts[ev])
                sink.extend(
                    (b, int(ev[i]), tuple(np.asarray(o, dtype=np.float64)))
                    for i, o in zip(np.asarray(idx), np.asarray(out)))
        states.append(logical(eng, state))
    # same-event matches come ordered by arming age on both paths
    assert got == want
    for field, value in states[1].items():
        assert np.array_equal(states[0][field], value), field
    if eng_name == "every_r2":
        # lanes do run out here, and are counted as they always were
        assert got and states[0]["overflow"].sum() > 0


# the run of 1,000 is in test_dense_skew_long.py, a file of its own so
# that another worker takes it
@pytest.mark.parametrize("eng_name,n_dev",
                         [(e, d) for e in ENGINES for d in MESHES])
def test_skewed_batch_equals_one_event_at_a_time(eng_name, n_dev):
    check_against_one_event_at_a_time(eng_name, n_dev, "skewed")


def test_kernels_are_interpreted_off_tpu():
    from siddhi_tpu.kernels import probe

    assert probe.interpret_mode()  # tests are CPU-only by contract


@pytest.fixture
def run_kernel(monkeypatch):
    """The Pallas kernel for the run, interpreted: off a TPU the engine
    keeps the XLA loop unless told otherwise."""
    from siddhi_tpu.kernels import dense_run

    monkeypatch.setattr(dense_run, "INTERPRET_OFF_TPU", True)
    return dense_run


@pytest.mark.parametrize("links_a_call", [2048, 8])
def test_the_run_kernel_equals_one_event_at_a_time(run_kernel, monkeypatch,
                                                   links_a_call):
    """``every_r2`` is in the run kernel's class (the others are not):
    the kernel gives the state, rows and overflow of the XLA step, also
    where the runs of 17 to 60 take several calls of eight links."""
    from siddhi_tpu.ops.dense_nfa import DensePatternEngine

    eng = _engine("every_r2", 1)[0]
    assert run_kernel.eligible(eng, "S")
    assert eng._make_run_kernel("S") is not None
    assert not any(run_kernel.eligible(_engine(e, 1)[0], ENGINES[e][2][0])
                   for e in ENGINES if e != "every_r2")
    monkeypatch.setattr(DensePatternEngine, "RUN_LINKS", links_a_call)
    check_against_one_event_at_a_time("every_r2", 1, "skewed")


def test_two_puts_and_one_count_gate_a_batch(monkeypatch):
    """A batch with a run of 24 takes two H2D puts and two dispatches
    (the first round; all the rest) and one fetch of its count gates."""
    import jax

    eng = compile_pattern(STREAMS + ENGINES["every_r2"][0], "q",
                          n_partitions=P, n_instances=4)
    state = eng.init_state()
    part, cols, ts = _events(np.random.default_rng(5), "several_hot_keys")
    state, pending = eng.process_deferred(state, "S", part, cols, ts)
    pending.resolve()       # compiled; now count
    calls = {"put": 0, "get": 0}
    real_put, real_get = jax.device_put, jax.device_get

    def put(*a, **kw):
        calls["put"] += 1
        return real_put(*a, **kw)

    def get(*a, **kw):
        calls["get"] += 1
        return real_get(*a, **kw)

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(jax, "device_get", get)
    state, pending = eng.process_deferred(state, "S", part, cols, ts + 5_000)
    assert calls == {"put": 2, "get": 0}
    assert len(pending.chunks) == 2
    assert pending.resolve() > 0
    assert calls == {"put": 2, "get": 1}
    assert round_plan(part).n_rounds == 24


# -- through SiddhiManager: the host engine and a plain automaton -------------

CHAIN = (
    "define stream Txn (key long, v double); "
    "partition with (key of Txn) begin @info(name='bench') "
    "from every e1=Txn[v > 0.0] -> e2=Txn[v > 1.0 and v > e1.v] -> "
    "e3=Txn[v > 2.0 and v > e1.v] -> e4=Txn[v > 3.0 and v > e1.v] "
    "within 2 sec select e1.v as v1, e4.v as v4 insert into Alerts; end;")


def _chain_rows(events, states, within_ms):
    """``every e1=[v>0] -> e2=[v>1 and v>e1.v] -> ...`` over one key's
    ``(ts, v)`` events: a match is ``(ts, e1.v, e<states>.v)``."""
    rows, pending = [], []
    for ts, v in events:
        nxt = []
        for v1, t1, k in pending:
            if ts - t1 > within_ms:
                continue
            if v > k and v > v1:
                if k + 1 == states:
                    rows.append((ts, v1, v))
                    continue
                k += 1
            nxt.append((v1, t1, k))
        if v > 0.0:
            nxt.append((v, ts, 1))
        pending = nxt
    return rows


def _zipf_batches(rng, n_keys, batch, n_batches):
    """Keys Zipf(0.99) over ``n_keys``, values rising one step an event
    within a key (no lane overflows), a millisecond an event."""
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -0.99
    ids = rng.permutation(n_keys)[rng.choice(
        n_keys, size=batch * n_batches, p=w / w.sum())]
    occ = np.zeros(len(ids), dtype=np.int64)
    seen = {}
    for i, k in enumerate(ids.tolist()):
        occ[i] = seen.get(k, 0)
        seen[k] = occ[i] + 1
    ts = 1_000 + np.arange(len(ids), dtype=np.int64)
    return [EventBatch("Txn", ["key", "v"],
                       {"key": ids[s:s + batch].astype(np.int64) * 7 + 3,
                        "v": occ[s:s + batch] + 0.5}, ts[s:s + batch])
            for s in range(0, len(ids), batch)]


def _run_app(header, batches, one_by_one=False):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + CHAIN)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(
            (e.timestamp, float(e.data[0]), float(e.data[1])) for e in evs))
        rt.start()
        h = rt.get_input_handler("Txn")
        for b in batches:
            if one_by_one:
                for i in range(len(b)):
                    h.send([int(b.columns["key"][i]),
                            float(b.columns["v"][i])],
                           timestamp=int(b.timestamps[i]))
            else:
                h.send_batch(b)
        lowering = rt.lowering() if not one_by_one else None
        overflow = sum(
            q.pattern_processor.overflow_total()
            for pr in rt.partitions.values()
            for q in getattr(pr, "dense_query_runtimes", {}).values())
        rt.shutdown()
        return got, lowering, overflow
    finally:
        m.shutdown()


@pytest.fixture(scope="module")
def traced_chain():
    """The chain app at ``sample='1'``: ``send(runs)`` sends one batch
    in which key ``i`` comes ``runs[i]`` times and returns the batch's
    ``plan`` counts, its numbers of ``put`` and ``dispatch`` spans, and
    whether the engine built its rounds program for it."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:name('paths') @app:playback @app:execution('tpu', "
        "partitions='64') @app:trace(sample='1', cycles='4') " + CHAIN)
    rt.start()
    h = rt.get_input_handler("Txn")
    engine = next(q.pattern_processor.engine
                  for pr in rt.partitions.values()
                  for q in pr.dense_query_runtimes.values())
    sent = [0]

    def has_rounds():
        return any(k[1] == "rounds" for k in engine._step_cache)

    def send(runs):
        keys = np.repeat(np.arange(len(runs), dtype=np.int64), runs)
        n = len(keys)
        had = has_rounds()
        h.send_batch(EventBatch(
            "Txn", ["key", "v"], {"key": keys, "v": np.arange(n) + 0.5},
            1_000 + sent[0] + np.arange(n, dtype=np.int64)))
        sent[0] += n
        spans = list(rt.app_context.tracer.recorder.cycle_groups()
                     .values())[-1]
        return ([s[5] for s in spans if s[1] == "plan"],
                [sum(s[1] == st for s in spans)
                 for st in ("put", "dispatch")],
                has_rounds() != had)

    send.engine_has_rounds = has_rounds
    yield send
    m.shutdown()


@pytest.mark.parametrize("longest,dispatches", [(1, 1), (2, 2), (3, 2),
                                                (40, 2)])
def test_the_round_count_alone_chooses_the_path(traced_chain, longest,
                                                dispatches):
    """One round: the step once.  Two: the step twice, and no rounds
    program is built for it.  Three or more: the step and the rounds
    program, two dispatches however long the run.  A put a dispatch."""
    built = traced_chain.engine_has_rounds()
    for _again in range(2):
        # (the engine builds the program with the first batch that
        # needs it and keeps it)
        first_use = longest >= 3 and not built
        assert traced_chain([longest, 1, 1, min(longest, 2)]) == (
            [longest], [dispatches, dispatches], first_use)
        built = built or first_use


@pytest.mark.parametrize("devices", MESHES + ("kernel",))
def test_zipf_keys_through_the_manager(devices, request):
    """The benchmark's skew at a small size: the dense path (one device
    with the run as the XLA loop and as the Pallas kernel, and sharded
    over four) delivers the rows of the host engine and of a plain chain
    automaton, in each key's event-time order."""
    if devices == "kernel":
        # captures in filters and select, `within`, no restart on
        # emission: the north-star app's class
        request.getfixturevalue("run_kernel")
        devices = 1
    batches = _zipf_batches(np.random.default_rng(28), 256, 512, 3)
    assert max(round_plan(b.columns["key"]).n_rounds for b in batches) > 30
    opts = "partitions='256'" + (f", devices='{devices}'"
                                 if devices > 1 else "")
    got, lowering, overflow = _run_app(
        f"@app:playback @app:execution('tpu', {opts}) ", batches)
    assert lowering == {"bench": "dense"} and overflow == 0
    by_key = {}
    for b in batches:
        for k, v, ts in zip(b.columns["key"].tolist(),
                            b.columns["v"].tolist(), b.timestamps.tolist()):
            by_key.setdefault(k, []).append((ts, v))
    plain = sorted(r for evs in by_key.values()
                   for r in _chain_rows(evs, 4, 2_000))
    assert len(plain) > 500
    assert sorted(got) == plain
    # one key's rows arrive in event-time order
    key_at = {ts: k for b in batches for k, ts in zip(
        b.columns["key"].tolist(), b.timestamps.tolist())}
    last = {}
    for ts, _v1, _v4 in got:
        assert last.get(key_at[ts], -1) <= ts
        last[key_at[ts]] = ts
    if devices == 1:
        host, _l, _o = _run_app("@app:playback ", batches, one_by_one=True)
        assert sorted(host) == plain
