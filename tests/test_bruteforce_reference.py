"""The benchmark's plain reference of ``bruteforce_1m`` tied to the engine.

``benchmark/references/pattern_kleene.py`` imports nothing of the
program; here the configuration's own app runs on the HOST engine and
the counting automaton of the reference owes exactly the rows it emits:
on hand-made cases (bursts of 1 to 12 fails, a success inside and
outside ``within``, arms that share a last fail), on the generator's
four scripts, on seeded random logs, and, through ``reference()``
itself, on the cell's generator at the rehearsal size.  One altered
capture, one dropped row and one row of a swept user each make it not
correct.
"""

import collections
import types

import numpy as np
import pytest

from bruteforce_bench import CONFIG, GEN, REF, TRAFFIC, login_batch, run_app

WITHIN = 600_000
F, S, X = 0, 1, 2     # a fail, a success, any other outcome


def host_rows(batches):
    """The configuration's app on the host engine over ``batches``."""
    got, errors, lowering, *_ = run_app("@app:playback", batches)
    assert set(lowering.values()) == {"host"} and not errors
    return got


def burst(n_fails, *tail):
    return [F] * n_fails + list(tail)


# name -> [(ok, ms since the case began)]; a bare list is a millisecond
# an event
CASES = {f"burst_of_{n}": burst(n, S) for n in range(1, 13)}
CASES.update({
    "two_arms_share_the_last_fail": burst(6, S),
    "an_arm_under_the_minimum_outlives_the_success": burst(5, S, F, S),
    "a_success_between_does_not_reset_the_count": [F, F, S, F, S, S],
    "another_outcome_moves_nothing": [F, X, F, X, F, X, S, X, S],
    "a_second_success_owes_nothing": burst(3, S, S),
    "success_on_the_edge_of_within": [(F, 0), (F, 1), (F, 2), (S, WITHIN)],
    "success_past_within": [(F, 0), (F, 1), (F, 2), (S, WITHIN + 1)],
    "the_older_arm_expires_the_younger_emits": [
        (F, 0), (F, 1), (F, 2), (F, 1_000), (F, 1_001), (F, 1_002),
        (S, WITHIN + 500)],
    "an_expired_arm_frees_the_head": [
        (F, 0), (F, 1), (F, WITHIN + 10), (F, WITHIN + 11),
        (F, WITHIN + 12), (S, WITHIN + 13)],
})
CASES.update({f"script_{i}": list(map(int, script))
              for i, script in enumerate(GEN.SCRIPTS)})
for _seed in range(6):
    _rng = np.random.default_rng(100 + _seed)
    _ok = _rng.choice([F, F, F, S, X], size=60)
    _at = np.cumsum(_rng.choice([1, 50, 40_000, 250_000], size=60,
                                p=[0.5, 0.3, 0.15, 0.05]))
    CASES[f"seeded_log_{_seed}"] = list(zip(_ok.tolist(), _at.tolist()))
ROWS_OWED = {
    **{f"burst_of_{n}": n // 3 for n in range(1, 13)},
    "two_arms_share_the_last_fail": 2,
    "an_arm_under_the_minimum_outlives_the_success": 2,
    "a_success_between_does_not_reset_the_count": 1,
    "another_outcome_moves_nothing": 1, "a_second_success_owes_nothing": 1,
    "success_on_the_edge_of_within": 1, "success_past_within": 0,
    "the_older_arm_expires_the_younger_emits": 1,
    "an_expired_arm_frees_the_head": 1,
    **{f"script_{i}": n for i, n in enumerate(GEN.ROWS_OWED)},
}


def events_of(name):
    """``(n, ts, ok, ip)`` of a case's events, as ``_kleene_rows`` takes
    them, under the case's own user: ``ip`` names user and ordinal."""
    user = 1 + list(CASES).index(name)
    evs = [e if isinstance(e, tuple) else (e, i)
           for i, e in enumerate(CASES[name])]
    return user, [(0, 1_000 + at, ok, user * 1_000 + i)
                  for i, (ok, at) in enumerate(evs)]


@pytest.fixture(scope="module")
def host_by_user():
    """Every case through ONE host runtime, a user each, an event a
    batch in the order of their timestamps."""
    evs = sorted((ts, user, ok, ip) for name in CASES
                 for user, es in [events_of(name)] for _n, ts, ok, ip in es)
    rows = host_rows(login_batch([u], [ok], [ip], [ts])
                     for ts, u, ok, ip in evs)
    by_user = collections.defaultdict(list)
    for ts, first, last, ok_ip in rows:
        by_user[first // 1_000].append((ts, first, last, ok_ip))
    return by_user


@pytest.mark.parametrize("name", list(CASES))
def test_the_reference_owes_what_the_host_engine_emits(host_by_user, name):
    user, evs = events_of(name)
    ts_of = {ip: ts for _n, ts, _ok, ip in evs}
    want = [(ts_of[ok_ip], first, last, ok_ip) for _n, first, last, ok_ip
            in REF._kleene_rows(evs, CONFIG["reference"]["min_count"],
                                CONFIG["reference"]["within_ms"])]
    assert want == host_by_user[user]
    if name in ROWS_OWED:
        assert len(want) == ROWS_OWED[name]


def test_two_arms_have_their_own_first_and_one_last(host_by_user):
    user, _evs = events_of("two_arms_share_the_last_fail")
    (_, f1, l1, s1), (_, f2, l2, s2) = host_by_user[user]
    assert (f1 % 1_000, f2 % 1_000) == (0, 3)       # arms of fails 1 and 4
    assert l1 == l2 and l1 % 1_000 == 5 and s1 == s2


# -- reference() itself, on the cell's generator -----------------------------

N_SENT = 13     # a pass and four batches of the next


@pytest.fixture(scope="module")
def bench():
    schedule = GEN.make(2**31 + 5, CONFIG, TRAFFIC, True)
    rows = host_rows(map(schedule.batch, range(-schedule.warmup, N_SENT)))
    return types.SimpleNamespace(schedule=schedule, rows=rows)


def judge(bench, rows):
    cols = {name: np.asarray([r[i + 1] for r in rows], dtype=np.int32)
            for i, name in enumerate(REF.ROW)}
    cols["_ts"] = np.asarray([r[0] for r in rows], dtype=np.int64)
    cols["_n"] = bench.schedule.batch_of(cols["_ts"])
    collector = types.SimpleNamespace(
        rows=lambda: cols, counts=collections.Counter(cols["_n"].tolist()))
    bad, compared = REF.reference(CONFIG["reference"], bench.schedule,
                                  collector, N_SENT, 0, True)
    return bad, {name.split(" (")[0]: (value, limit)
                 for name, value, limit in compared}


def window_row(bench, k=5):
    """Index of a row stamped inside the window's first pass."""
    return k + next(i for i, r in enumerate(bench.rows)
                    if bench.schedule.batch_of(r[0]) >= 0)


def test_the_host_engine_agrees_with_the_reference(bench):
    bad, compared = judge(bench, bench.rows)
    assert not bad and len(compared) == 5
    assert all(value <= limit for value, limit in compared.values())
    per_pass = sum(GEN.ROWS_OWED[s] for s in bench.schedule.script_of.values())
    in_window = [r for r in bench.rows if bench.schedule.batch_of(r[0]) >= 0]
    assert per_pass == 91 and per_pass < len(in_window) < 2 * per_pass


def test_an_altered_capture_is_not_correct(bench):
    rows = list(bench.rows)
    i = window_row(bench)
    rows[i] = (*rows[i][:2], rows[i][2] + 1, rows[i][3])    # e1[last].ip
    bad, compared = judge(bench, rows)
    # the row delivered is not owed, the row owed is not delivered
    assert compared["sampled rows that differ from the reference"] == (2, 0)
    assert bad == {int(bench.schedule.batch_of(rows[i][0]))}


def test_a_dropped_row_is_not_correct(bench):
    rows = list(bench.rows)
    gone = rows.pop(window_row(bench))
    bad, compared = judge(bench, rows)
    assert compared["sampled rows that differ from the reference"] == (1, 0)
    assert int(bench.schedule.batch_of(gone[0])) in bad
    # and its twin in the second pass no longer has its count
    assert compared["batches whose row count differs from the first "
                    "pass's"][0] >= 1


def test_a_row_of_a_swept_user_is_not_correct(bench):
    sch = bench.schedule
    swept = int(np.flatnonzero(~np.isin(sch.all_keys, sch.active_keys))[0])
    rows = list(bench.rows)
    i = window_row(bench)
    ip = (swept + 1) << GEN.ORDINAL_BITS
    rows.insert(i, (rows[i][0], ip, ip, ip))
    bad, compared = judge(bench, rows)
    assert compared["rows of users that were only swept"] == (1, 0)
    assert int(sch.batch_of(rows[i][0])) in bad


def test_a_swapped_pair_of_one_user_is_not_correct(bench):
    sch = bench.schedule
    keys = sch.row_keys({"firstIp": [r[1] for r in bench.rows]})
    rows = list(bench.rows)
    # two rows of one user at different successes (script 2 owes four)
    user = next(k for k, s in sch.script_of.items() if s == 2)
    mine = [i for i, k in enumerate(keys) if k == user
            and sch.batch_of(rows[i][0]) >= 0]
    i, j = mine[0], mine[1]
    assert rows[i][0] < rows[j][0]
    rows[i], rows[j] = rows[j], rows[i]
    _bad, compared = judge(bench, rows)
    assert compared["rows of one user out of event-time order"] == (1, 0)
