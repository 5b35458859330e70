"""A dense pattern batch reaches the device as one packed buffer a
dispatched program (``ops/dense_nfa.py`` over ``ops/packed_lanes.py``).

Held here: the packed programs, ``(state, buf)``, give what the
un-jitted ``(state, part_idx, cols, ts, valid)`` step gives when it is
fed the same lanes round by round at the widths the programs step them
at (rows, payloads, timestamps, the state after every batch and
``steppedLanes``), on the benchmark's three pattern apps at two rounds
a batch (the step twice) and at five (the step and ``make_rounds``'
program, its wide loops and its run); a put is one leaf
(``putLeaves == devicePuts``); a column the batch does not bring keys a
program of its own; and the ``ingest.put`` fault site is armed once a
program.
"""

import jax
import numpy as np
import pytest

import bench_app
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.emit_queue import fetch_coalesced
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.ingest_stage import IngestStats
from siddhi_tpu.ops.dense_layout import OVERFLOW, ROWS
from siddhi_tpu.ops.dense_nfa import (LANE_OFF, LANE_PART, LANE_REL,
                                      DeferredDenseEmit, compile_pattern,
                                      round_plan)

P = 1024


def pattern_of(config: str) -> str:
    """A benchmark deployment's query without its partition wrapper:
    the engine's rows are the partitions."""
    app = bench_app._json("configs", config + ".json")["app"]
    head, rest = app.split("partition with", 1)
    return head + rest.split("begin", 1)[1].rsplit("end;", 1)[0]


def fraud_cols(key, j):
    """Rising by one an event of its key: every node's threshold and
    ``v > e1.v`` pass, the sixteenth event completes the chain."""
    return {"key": key.astype(np.int64), "v": j + 1.5 + key / 4096.0}


def card_cols(key, j):
    """Four rising amounts, then a dip: ``<3>`` on the float capture
    completes at a card's fourth event and starts over at its fifth."""
    return {"card": key.astype(np.int64),
            "amount": ((j % 5) + 1.0 + key / 4096.0).astype(np.float32),
            "merchant": (key % 7).astype(np.int32)}


def login_cols(key, j):
    """Four fails and a success: ``<3:>`` emits at every fifth event of
    a user, the captured ``ip`` of either sign."""
    key = key.astype(np.int64)
    return {"user": key, "ok": (j % 5 == 4).astype(np.int32),
            "ip": ((key * 2654435761 + j * 40503) % 2**32 - 2**31).astype(
                np.int32)}


APPS = {"fraud": ("fraud16_1m", fraud_cols),
        "card": ("cardfraud_100k", card_cols),
        "bruteforce": ("bruteforce_1m", login_cols)}

# rounds -> (how many keys come at least 1, 2, ... times in a batch,
# batches): at five rounds the later ones are a rounds program of 2,048
# padded lanes, whose wide loops step the rounds of 600 and 400 lanes
# at 2,048 and the one of 200 at 256, and whose run takes the one of 40
SHAPES = {"two_rounds": ([600, 300], 10),
          "five_rounds": ([900, 600, 400, 200, 40], 5)}


def batches(shape, cols_of, seed):
    """``(part_idx, cols, ts)`` a batch: key ``k`` comes as often as
    ``at_least`` says, shuffled; an event's columns follow from its key
    and how many events of the key came before it."""
    at_least, n_batches = SHAPES[shape]
    rng = np.random.default_rng(seed)
    seen = np.zeros(P, dtype=np.int64)
    t = 1_000
    for _ in range(n_batches):
        key = np.concatenate([np.arange(n) for n in at_least])
        rng.shuffle(key)
        j = np.empty(len(key), dtype=np.int64)
        for i, k in enumerate(key):        # a key's events in arrival order
            j[i] = seen[k]
            seen[k] += 1
        ts = t + np.arange(len(key), dtype=np.int64)
        t += 10_000
        yield key.astype(np.int32), cols_of(key, j), ts


def sliced_at(eng, R, width):
    """The static width ``make_rounds``' program of ``R`` padded lanes
    steps a round of ``width`` lanes at."""
    for w, narrower in eng.rounds_ladder(R):
        if width > narrower:
            return w
    return eng.RUN_WIDTH


def pow2(n):
    return max(1 << (n - 1).bit_length(), 16)


class ByTheStep:
    """The un-jitted step, round by round from the host: the form every
    program had before the packed buffer."""

    def __init__(self, eng):
        self.eng = eng
        self.sk = eng.default_stream
        self.step = jax.jit(eng.make_step(self.sk, jit=False))
        self.state = eng.init_state()
        self.stepped = 0

    def process(self, part, cols, ts):
        eng = self.eng
        rel = eng.rel_ts64(ts).astype(np.int32)
        prepared = eng.prepare_cols(self.sk, cols)
        plan = round_plan(part)
        R = pow2(len(part) - int(plan.off[1])) if plan.n_rounds > 1 else 0
        pending = DeferredDenseEmit(eng)
        for r in range(plan.n_rounds):
            ev = plan.round(r)
            b = len(ev)
            w = (pow2(b) if r == 0 or plan.n_rounds == 2
                 else sliced_at(eng, R, b))
            pi = np.full(w, eng.n_partitions, dtype=np.int32)
            pi[:b] = part[ev]
            tb = np.zeros(w, dtype=np.int32)
            tb[:b] = rel[ev]
            cb = {}
            for k, v in prepared.items():
                cb[k] = np.zeros(w, dtype=v.dtype)
                cb[k][:b] = v[ev]
            valid = np.arange(w) < b
            self.state, emit, outs, anchor, count = self.step(
                self.state, pi, cb, tb, valid)
            self.stepped += w
            pending.chunks.append({
                "emit": emit, "f": outs["f"], "i": outs["i"],
                "anchor": anchor, "sel": slice(0, b), "ridx": ev,
                "count": count})
        pending.resolve()
        return pending.materialize(fetch_coalesced(pending.device_arrays()))


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("app", list(APPS))
def test_the_packed_programs_give_what_the_step_gives(app, shape):
    config, cols_of = APPS[app]
    text = pattern_of(config)
    eng = compile_pattern(text, n_partitions=P)
    eng.ingest_stats = IngestStats()
    by_step = ByTheStep(compile_pattern(text, n_partitions=P))
    sk = eng.default_stream
    state = eng.init_state()
    rows, n_batches = 0, SHAPES[shape][1]
    for part, cols, ts in batches(shape, cols_of, seed=len(app)):
        state, ev, out = eng.process(state, sk, part, cols, ts)
        want_ev, want_out = by_step.process(part, cols, ts)
        assert np.array_equal(ev, want_ev)
        assert np.array_equal(ts[ev], ts[want_ev])
        assert out.dtype == want_out.dtype and np.array_equal(out, want_out)
        for k in (ROWS, OVERFLOW):
            assert np.array_equal(np.asarray(state[k])[:P],
                                  np.asarray(by_step.state[k])[:P])
        rows += len(ev)
    assert rows > 100
    stats = eng.ingest_stats
    assert stats.stepped_lanes == by_step.stepped == n_batches * {
        "two_rounds": 1024 + 512, "five_rounds": 1024 + 2 * 2048 + 256 + 128
    }[shape]
    # two programs a batch, each one put of one leaf
    assert stats.device_puts == stats.put_leaves == 2 * n_batches
    programs = {k[1] for k in eng._step_cache}
    assert ("rounds" in programs) == (shape == "five_rounds")


def test_the_buffer_holds_a_row_a_lane_and_the_offsets_ride_in_it():
    eng = compile_pattern(pattern_of("bruteforce_1m"), n_partitions=P)
    sk = eng.default_stream
    # `user` is the partition key and is never read: hi/lo pairs of the two
    # columns the automaton reads, between the partition row and the time
    cols = ("ok|hi", "ok|lo", "ip|hi", "ip|lo")
    assert eng.lane_table(sk).names == (LANE_PART, *cols, LANE_REL)
    assert eng.lane_table(sk, offsets=True).names == (
        LANE_PART, *cols, LANE_REL, LANE_OFF)
    assert eng.lane_table(sk) is eng.lane_table(sk, cols)
    n = 20
    part = (np.arange(n, dtype=np.int32) * 7) % P
    prepared = eng.prepare_cols(sk, login_cols(part, np.arange(n)))
    rel = np.arange(1, n + 1, dtype=np.int32)
    ev = np.arange(n)[::-1]
    buf = eng._pad_lanes(eng.lane_table(sk, offsets=True), part, prepared,
                         rel, ev, np.array([12, 17]))
    assert buf.dtype == np.int32 and buf.shape == (7, 32)
    assert buf[0].tolist() == part[ev].tolist() + [P] * 12   # scratch row
    for row, k in zip(buf[1:5], cols):
        assert row.tolist() == prepared[k][ev].tolist() + [0] * 12
    assert buf[5].tolist() == rel[ev].tolist() + [0] * 12
    # starts of the rounds, then the number of events in every entry
    assert buf[6].tolist() == [12, 17] + [n] * 30
    part_d, cols_d, ts_d, off_d = jax.device_get(jax.jit(
        lambda b: eng._unpack_lanes(eng.lane_table(sk, offsets=True), b))(
            buf))
    assert sorted(cols_d) == sorted(cols)
    assert np.array_equal(part_d, buf[0]) and np.array_equal(ts_d, buf[5])
    assert np.array_equal(off_d, buf[6])


def test_a_column_the_batch_does_not_bring_keys_a_program_of_its_own():
    """A capture of an absent column keeps its register, an output of
    one reads zero: as the pytree put had it, which left the column out
    of the program's arguments."""
    text = ("define stream S (k long, a float, b float); "
            "from every e1=S[a > 0.0] -> e2=S[a > e1.a] "
            "select e1.a as a1, e2.b as b2 insert into O;")
    eng = compile_pattern(text, n_partitions=64)
    sk = eng.default_stream
    part = np.arange(8, dtype=np.int32)
    ts = np.arange(1_000, 1_008, dtype=np.int64)
    a = np.linspace(1.0, 2.0, 8).astype(np.float32)
    state = eng.init_state()
    state, ev, out = eng.process(state, sk, part, {"a": a, "b": a * 10}, ts)
    assert len(ev) == 0
    state, ev, out = eng.process(state, sk, part, {"a": a + 1}, ts + 100)
    assert ev.tolist() == list(range(8))
    assert np.array_equal(out[:, 0], a) and not out[:, 1].any()
    assert {k[2] for k in eng._step_cache if k[1] is True} == {
        (LANE_PART, "a", "b", LANE_REL), (LANE_PART, "a", LANE_REL)}


APP = ("@app:name('packed') @app:statistics('true') @app:playback "
       "{faults}@app:execution('tpu', partitions='64') "
       "define stream Login (user long, ok int, ip int); "
       "partition with (user of Login) begin @info(name='q') "
       "from every e1=Login[ok == 0]<3:> -> e2=Login[ok == 1] within 10 min "
       "select e1[0].ip as firstIp, e1[last].ip as lastIp, e2.ip as okIp "
       "insert into Alerts; end;")


def login_batch(runs, t0):
    """User ``u`` comes ``runs[u]`` times, fails and then a success."""
    user = np.repeat(np.arange(len(runs)), runs)
    j = np.concatenate([np.arange(r) for r in runs])
    ok = (j == np.asarray(runs)[user] - 1).astype(np.int32)
    cols = {"user": user.astype(np.int64), "ok": ok,
            "ip": (user * 1000 + j).astype(np.int32)}
    return EventBatch("Login", list(cols), cols,
                      t0 + np.arange(len(user), dtype=np.int64))


def run(sent, faults=""):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(APP.format(faults=faults))
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        h = rt.get_input_handler("Login")
        for b in sent:
            h.send_batch(b)
        rt.drain_device_emits()
        assert rt.lowering() == {"q": "dense"}
        injector = rt.app_context.fault_injector
        rt.shutdown()
        stats = {k.rsplit(".", 1)[1]: v for k, v in rt.statistics().items()
                 if ".Queries.q." in k}
    finally:
        m.shutdown()
    return got, stats, injector


def test_a_put_is_one_leaf_in_statistics():
    # one round: one program; two rounds: the step twice; five: the step
    # and the rounds program, whose offsets ride in its buffer
    sent = [login_batch([1] * 20, 1_000), login_batch([2] * 6 + [1] * 9,
                                                      2_000),
            login_batch([5, 4, 4] + [1] * 5, 3_000)]
    got, stats, _ = run(sent)
    assert len(got) == 3
    assert stats["stagedBatches"] == 3
    assert stats["devicePuts"] == 1 + 2 + 2
    assert stats["putLeaves"] == stats["devicePuts"]


@pytest.mark.parametrize("after", [0, 1])
def test_ingest_put_fault_is_retried_once_a_program(after):
    """Each of a batch's two puts is armed on its own: the fault falls
    on the step's or on the rounds program's, is retried there, and the
    retry is no further put."""
    sent = [login_batch([5, 4, 4] + [1] * 5, 1_000)]
    clean, _, _ = run(sent)
    shaken, stats, injector = run(
        sent, faults="@app:faults(transfer.retry.scale='0.0001', "
                     f"ingest.put='transient:count=1:after={after}') ")
    assert injector.stats.faults_injected == 1
    assert injector.stats.transfer_retries == 1
    assert injector.stats.drains_recovered == 1
    assert stats["devicePuts"] == stats["putLeaves"] == 2
    assert len(clean) == 3 and shaken == clean
