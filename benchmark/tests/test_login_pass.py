"""The login mix's own promises (``generators/login_pass.py``), on the
CPU with no device work: who is attacked and how often, a sweep over
everybody else, no burst that four instance lanes cannot hold, rows owed
as the scripts say, payloads that name their user and event, and passes
that repeat.  The cell's rehearsal, its control and a planted wrong
answer are cases of ``test_benchmark.py``, which runs every cell of
``BENCHMARK.json``; the reference against the host engine is tier-1's
``tests/test_bruteforce_reference.py``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import collections
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH, os.path.join(BENCH, "generators")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import login_pass  # noqa: E402
from fraud_pass import PASS_GAP_MS  # noqa: E402
from references import pattern_kleene  # noqa: E402

CELL = "bruteforce_1m.saturated"


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


SPEC = _load(ROOT, "BENCHMARK.json")
CONFIG = _load(BENCH, "configs", "bruteforce_1m.json")
TRAFFIC = _load(BENCH, "traffic", "login_pass_saturated.json")
REF = CONFIG["reference"]
SIZES = {"full": (1_000_000, 131_072), "rehearsal": (4_096, 1_024)}


@pytest.fixture(scope="module", params=list(SIZES))
def made(request):
    rehearsal = request.param == "rehearsal"
    return request.param, login_pass.make(2**31 + 5, CONFIG, TRAFFIC,
                                          rehearsal)


def a_pass(schedule, p=0):
    return [schedule.batch(n) for n in range(p * schedule.per_pass,
                                             (p + 1) * schedule.per_pass)]


def test_the_cell_names_this_mix():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bruteforce_1m", "login_pass_saturated", 1)
    assert TRAFFIC["generator"] == "login_pass" and TRAFFIC["loop"] == "closed"
    assert CELL in next(m for m in SPEC["end_to_end"]
                        if m["name"] == "events_per_s")["workloads"]
    assert REF["within_ms"] < PASS_GAP_MS


def test_attacked_users_come_twice_a_batch_and_the_rest_are_swept(made):
    size, schedule = made
    n_keys, batch = SIZES[size]
    assert schedule.per_pass == 9 and schedule.batch_events == batch
    attacked = np.sort(schedule.active_keys)
    assert len(attacked) == batch // 32 == len(set(attacked.tolist()))
    seen = collections.Counter()
    for b in a_pass(schedule):
        users, counts = np.unique(b.columns["user"], return_counts=True)
        twice = users[counts == 2]
        assert (np.sort(twice) == attacked).all()   # a second round, always
        assert counts.max() == 2
        seen.update(dict(zip(users.tolist(), counts.tolist())))
    assert len(seen) == n_keys == len(schedule.all_keys)
    swept = np.array([c for u, c in seen.items()
                      if u not in schedule.script_of])
    assert all(seen[u] == 18 for u in schedule.script_of)
    # nine batches hold more slots than users: the sweep wraps
    spare = 9 * (batch - 2 * len(attacked)) - len(swept)
    assert swept.sum() == len(swept) + spare
    if size == "full":
        assert swept.min() == 1 and swept.max() == 2
        assert (swept == 2).sum() == spare == 110_016
    else:
        assert swept.min() == 2 and swept.max() == 3


def by_user(schedule, batches):
    """``user -> [(n, ts, ok, ip)]`` in arrival order, as the reference
    takes them."""
    out = collections.defaultdict(list)
    for n, b in enumerate(batches):
        for u, ok, ip, ts in zip(*(b.columns[c].tolist()
                                   for c in login_pass.COLUMNS),
                                 b.timestamps.tolist()):
            out[u].append((n, ts, ok, ip))
    return out


@pytest.fixture(scope="module")
def small():
    schedule = login_pass.make(7, CONFIG, TRAFFIC, rehearsal=True)
    return schedule, by_user(schedule, a_pass(schedule))


def test_an_attacked_user_follows_its_script(made):
    _size, schedule = made
    n = len(schedule.active_keys)
    shares = collections.Counter(schedule.script_of.values())
    assert shares[3] == n // 20
    assert max(shares[s] for s in range(3)) - min(
        shares[s] for s in range(3)) <= 1
    ok = collections.defaultdict(list)
    for b in a_pass(schedule):
        mine = np.isin(b.columns["user"], schedule.active_keys)
        for u, o in zip(b.columns["user"][mine].tolist(),
                        b.columns["ok"][mine].tolist()):
            ok[u].append(o)
    for u, s in schedule.script_of.items():
        assert ok[u] == login_pass.SCRIPTS[s].tolist()


def test_no_burst_is_longer_than_four_lanes_hold(made):
    """Fails since the last success, ``every`` re-arming at each third:
    never more than 12, so never a fifth arm."""
    _size, schedule = made
    for script in login_pass.SCRIPTS:
        run = longest = 0
        for o in script.tolist():
            run = 0 if o == login_pass.SUCCESS else run + (
                o == login_pass.FAIL)
            longest = max(longest, run)
        assert longest <= login_pass.LONGEST_BURST == 4 * REF["min_count"]
    assert max(map(max, map(list, login_pass.SCRIPTS))) == login_pass.OTHER
    # a swept user comes at most three times a pass (twice at full
    # size): its fails alone never reach the count
    swept_ok = np.concatenate([
        b.columns["ok"][~np.isin(b.columns["user"], schedule.active_keys)]
        for b in a_pass(schedule)])
    assert set(np.unique(swept_ok).tolist()) == {0, 1}
    assert 0.88 < swept_ok.mean() < 0.92


def test_the_scripts_owe_what_they_say(small):
    schedule, events = small
    owed = collections.Counter()
    for u, evs in events.items():
        rows = pattern_kleene._kleene_rows(evs, REF["min_count"],
                                           REF["within_ms"])
        if u in schedule.script_of:
            assert len(rows) == login_pass.ROWS_OWED[schedule.script_of[u]]
            owed[schedule.script_of[u]] += len(rows)
        else:
            assert not rows     # swept: two or three events, never a row
    assert sum(owed.values()) == 11 * 1 + 10 * 4 + 10 * 4 + 0


def test_a_rows_ip_names_its_user_and_its_events(small):
    schedule, events = small
    rows, users = [], []
    for u in schedule.script_of:
        mine = pattern_kleene._kleene_rows(events[u], REF["min_count"],
                                           REF["within_ms"])
        rows += mine
        users += [u] * len(mine)
    cols = dict(zip(pattern_kleene.ROW, np.asarray(rows)[:, 1:].T))
    assert (schedule.row_keys(cols) == np.asarray(users)).all()
    # and each of the three names an event of that user, by its ordinal
    for u, (_n, first, last, ok_ip) in zip(users, rows):
        ips = [e[3] for e in events[u]]
        assert [ip & 31 for ip in ips] == list(range(18))
        assert ips.index(first) < ips.index(last) < ips.index(ok_ip)


def test_ips_fit_an_int_at_full_size(made):
    _size, schedule = made
    for b in a_pass(schedule):
        ip = b.columns["ip"]
        assert ip.dtype == np.int32 and ip.min() >= 32
        assert b.columns["user"].dtype == np.int64
        assert b.columns["ok"].dtype == np.int32
        ids = (ip.astype(np.int64) >> login_pass.ORDINAL_BITS) - 1
        assert (schedule.key_of[ids] == b.columns["user"]).all()


def test_passes_repeat_past_within(made):
    _size, schedule = made
    for n in (-9, -1, 0, 4, 8):
        a, b = schedule.batch(n), schedule.batch(n + schedule.per_pass)
        for c in login_pass.COLUMNS:
            assert (a.columns[c] == b.columns[c]).all()
        assert set((b.timestamps - a.timestamps).tolist()) == {PASS_GAP_MS}
        assert schedule.twin(n + 9) == (n + 9) % 9
        assert (schedule.batch_of(a.timestamps) == n).all()
    span = schedule.batch(8).timestamps[0] - schedule.batch(0).timestamps[0]
    assert span + REF["within_ms"] < PASS_GAP_MS
