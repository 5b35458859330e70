"""``bruteforce_1m``'s app on the dense engine at 4,096 partitions, on
the cell's own generator at its rehearsal size, against the host engine.

What the 64-partition unit tests of the count node do not reach: a
batch whose second collision round runs the counted node, the same
events as one round a batch and as four rounds through
``make_rounds``' program, all four instance lanes full and expired
together, integer captures in every row, and the overflow accounting
where a burst is one fail too long.
"""

import logging

import numpy as np
import pytest

from bruteforce_bench import (COLUMNS, CONFIG, GEN, TRAFFIC, login_batch,
                              run_app)

DENSE = ("@app:statistics('true') "
         + CONFIG["header"].format(**CONFIG["rehearsal"]))
N_BATCHES = 18      # two passes past the warm-up


def inspect(rt):
    """``pattern_state()`` of the one query, and which programs its
    engine built."""
    (pr,) = rt.partitions.values()
    engine = pr.dense_query_runtimes["bench"].pattern_processor.engine
    return {**rt.pattern_state()["bench"],
            "programs": {k[1] for k in engine._step_cache}}


def run(batches):
    """The app on the dense engine: rows, the runtime's view of itself,
    ``Queries.bench.droppedInstances`` of ``statistics()`` (the engine's
    overflow total as of its last poll), the listener's errors."""
    got, errors, lowering, state, stats = run_app(DENSE, batches, inspect)
    assert lowering == CONFIG["expect"]["lowering"]
    (key,) = [k for k in stats if k.endswith("Queries.bench.droppedInstances")]
    return got, state, stats[key], errors


def cut(batch, pick):
    return login_batch(*(batch.columns[c][pick] for c in COLUMNS),
                       batch.timestamps[pick])


def join(a, b):
    return login_batch(*(np.concatenate([a.columns[c], b.columns[c]])
                         for c in COLUMNS),
                       np.concatenate([a.timestamps, b.timestamps]))


@pytest.fixture(scope="module")
def schedule():
    return GEN.make(2**31 + 7, CONFIG, TRAFFIC, True)


@pytest.fixture(scope="module")
def batches(schedule):
    return [schedule.batch(n) for n in range(-schedule.warmup, N_BATCHES)]


@pytest.fixture(scope="module")
def host(batches):
    got, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    return got


def first_occurrence(batch):
    _, first = np.unique(batch.columns["user"], return_index=True)
    mask = np.zeros(len(batch.timestamps), dtype=bool)
    mask[first] = True
    return mask


def shaped(batches, shape):
    """The same events in the same order a user, cut another way: as
    sent (two collision rounds a batch), a batch's first and second
    occurrences as batches of their own (one round each), or two
    batches as one (four rounds, so ``make_rounds``' program)."""
    if shape == "two_rounds":
        return batches
    if shape == "one_round":
        return [cut(b, m) for b in batches
                for m in [first_occurrence(b)] for m in (m, ~m)]
    assert shape == "four_rounds"
    return [join(a, b) for a, b in zip(batches[::2], batches[1::2])] + (
        batches[-1:] if len(batches) % 2 else [])


def by_user(rows):
    return sorted(rows, key=lambda r: r[1] >> GEN.ORDINAL_BITS)


@pytest.mark.parametrize("shape", ["two_rounds", "one_round", "four_rounds"])
def test_dense_rows_equal_the_host_engines(schedule, batches, host, shape):
    got, state, dropped, errors = run(shaped(batches, shape))
    # stable by user: the same rows, each user's in the host's order
    assert by_user(got) == by_user(host) and not errors
    per_pass = sum(GEN.ROWS_OWED[s] for s in schedule.script_of.values())
    assert len(got) == 3 * per_pass          # the warm-up pass and two more
    assert state["instance_lanes"] == 4
    assert state["partitions_in_use"] == 4096
    assert state["dropped_instances"] == dropped == 0
    # only a third round builds the rounds program, and a counted node
    # is outside the run kernel's class: its rounds are the XLA loop
    assert ("rounds" in state["programs"]) == (shape == "four_rounds")


def test_a_row_names_its_users_fails(schedule, host):
    """What the scripts promise of the captures: a row's three ips are
    one user's, first fail before last fail before the success."""
    rows = np.asarray(host, dtype=np.int64)[:, 1:]
    users, ordinal = rows >> GEN.ORDINAL_BITS, rows & 31
    assert (users[:, 0] == users[:, 1]).all()
    assert (users[:, 0] == users[:, 2]).all()
    assert (ordinal[:, 0] + 2 <= ordinal[:, 1]).all()
    assert (ordinal[:, 1] < ordinal[:, 2]).all()
    # script 1's first success: four arms, their own firsts, one last
    user = next(k for k, s in schedule.script_of.items() if s == 1)
    mine = rows[schedule.row_keys({"firstIp": rows[:, 0]}) == user][:4] & 31
    assert mine.tolist() == [[0, 11, 12], [3, 11, 12], [6, 11, 12],
                             [9, 11, 12]]


def burst_batches(n_fails):
    """One user: ``n_fails`` fails and a success, two events a batch."""
    ok = [0] * n_fails + [1]
    ok += [2] * (len(ok) % 2)
    return [login_batch([77, 77], ok[i:i + 2], [i, i + 1],
                        [1_000 + i, 1_000 + i])
            for i in range(0, len(ok), 2)]


@pytest.mark.parametrize("n_fails, rows, dropped", [(12, 4, 0), (13, 4, 1),
                                                    (14, 4, 2)])
def test_a_burst_past_twelve_fails_counts_its_overflow(caplog, n_fails, rows,
                                                       dropped):
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu"):
        got, state, polled, errors = run(burst_batches(n_fails))
    assert len(got) == rows
    assert [r[1] for r in got] == [0, 3, 6, 9]       # the arm of fail 13: gone
    assert {r[2] for r in got} == {n_fails - 1}
    assert state["dropped_instances"] == polled == dropped
    advice = [r.getMessage() for r in caplog.records
              if "instances='N'" in r.getMessage()]
    assert len(errors) == len(advice) == (1 if dropped else 0)
    if dropped:
        assert f"{dropped} pending instance(s) dropped" in advice[0]
        assert "current 4 per partition/node" in advice[0]
