"""Fixture tests for every ``siddhi_tpu.analysis`` rule.

Each rule gets a BAD snippet it must fire on and a GOOD snippet it must
stay quiet on — the rules' false-positive/false-negative contract, pinned
so heuristic refinements can't silently weaken a guard.  Allowlist
mechanics (mandatory justifications, suppression, expiry) and baseline
round-tripping are covered at the end.
"""

import textwrap
from pathlib import Path

import pytest

from siddhi_tpu.analysis import (Allowlist, ModuleIndex, get_rule,
                                 run_rules)
from siddhi_tpu.analysis import reporting


def _check(rule_name, rel, src):
    """Raw findings from one rule over one fixture module (no
    allowlist, no cross-module finish hooks)."""
    rule = get_rule(rule_name)
    rule.begin()
    idx = ModuleIndex(Path("fixture.py"), rel, source=textwrap.dedent(src))
    return list(rule.check(idx))


# -- host-sync-hazard -------------------------------------------------------

DEVICE_RT = "siddhi_tpu/ops/device_query.py"  # a scanned device module


def test_host_sync_fires_on_materializer_in_device_module():
    hits = _check("host-sync-hazard", DEVICE_RT, """
        import numpy as np
        class E:
            def process(self, out):
                return np.asarray(out)   # implicit sync fetch
    """)
    assert [(f.line, f.scope) for f in hits] == [(5, "E.process")]
    assert hits[0].key == f"{DEVICE_RT}:E.process"  # line-number-free


def test_host_sync_sees_through_self_receivers():
    hits = _check("host-sync-hazard", DEVICE_RT, """
        class E:
            def process(self, out):
                return self.jax.device_get(out)
    """)
    assert len(hits) == 1


def test_host_sync_quiet_outside_device_modules_and_on_clean_code():
    clean = """
        import numpy as np
        class E:
            def process(self, q, out):
                q.push(out)  # device ref stays on device
    """
    assert _check("host-sync-hazard", DEVICE_RT, clean) == []
    # host-side modules are free to use numpy
    hot = "import numpy as np\ndef f(x):\n    return np.asarray(x)\n"
    assert _check("host-sync-hazard", "siddhi_tpu/core/event.py", hot) == []


# -- ingest-put-bypass ------------------------------------------------------

def test_ingest_put_fires_anywhere_in_the_package():
    hits = _check("ingest-put-bypass", "siddhi_tpu/core/anything.py", """
        import jax
        def ingest(cols):
            return jax.device_put(cols)
    """)
    assert [(f.line, f.scope) for f in hits] == [(4, "ingest")]


def test_ingest_put_quiet_on_staged_put():
    hits = _check("ingest-put-bypass", "siddhi_tpu/core/anything.py", """
        from siddhi_tpu.core.ingest_stage import staged_put
        def ingest(self, cols):
            return staged_put(self.stage, cols)
    """)
    assert hits == []


# -- broad-except-swallow ---------------------------------------------------

def test_broad_except_fires_on_silent_swallow_in_core():
    hits = _check("broad-except-swallow", "siddhi_tpu/core/x.py", """
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    assert len(hits) == 1 and hits[0].scope == "f"


def test_broad_except_quiet_on_narrow_or_logged_handlers():
    narrow = """
        import queue
        def f(q):
            try:
                return q.get_nowait()
            except queue.Empty:
                pass
    """
    logged = """
        def f(log):
            try:
                g()
            except Exception as e:
                log.warning("probe failed: %s", e)
    """
    assert _check("broad-except-swallow", "siddhi_tpu/core/x.py", narrow) == []
    assert _check("broad-except-swallow", "siddhi_tpu/core/x.py", logged) == []
    # layers outside core/ and transport/ are not scanned
    bad = "try:\n    g()\nexcept Exception:\n    pass\n"
    assert _check("broad-except-swallow", "siddhi_tpu/util/x.py", bad) == []


# -- lock-discipline --------------------------------------------------------

def test_lock_discipline_fires_on_unlocked_cross_thread_write():
    hits = _check("lock-discipline", "siddhi_tpu/core/x.py", """
        import threading
        class Worker:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()
            def _loop(self):
                self.count += 1          # thread side, unlocked
            def reset(self):
                self.count = 0           # main side, unlocked
    """)
    assert [f.scope for f in hits] == ["Worker.count"]
    assert hits[0].key == "siddhi_tpu/core/x.py:Worker.count"


def test_lock_discipline_quiet_when_writes_are_locked():
    hits = _check("lock-discipline", "siddhi_tpu/core/x.py", """
        import threading
        class Worker:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()
            def _loop(self):
                with self._lock:
                    self.count += 1
            def reset(self):
                with self._lock:
                    self.count = 0
    """)
    assert hits == []


def test_lock_discipline_excludes_constructors_and_follows_timers():
    # __init__ writes happen-before thread start: not a conflict; but a
    # Timer chain (transport retry style) IS a thread entry.
    hits = _check("lock-discipline", "siddhi_tpu/core/x.py", """
        import threading
        class Retry:
            def __init__(self):
                self.failed = False      # constructor: excluded
            def arm(self):
                t = threading.Timer(1.0, self._fire)
                t.start()
            def _fire(self):
                self.failed = True       # thread side
            def reset(self):
                self.failed = False      # main side -> conflict
    """)
    assert [f.scope for f in hits] == ["Retry.failed"]


def test_lock_discipline_locked_call_site_does_not_extend_closure():
    # Scheduler pattern: the thread loop calls advance() under the
    # process lock, so advance()'s writes are lock-protected.
    hits = _check("lock-discipline", "siddhi_tpu/core/x.py", """
        import threading
        class Sched:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()
            def _loop(self):
                while True:
                    with self.ctx.process_lock:
                        self.advance()
            def advance(self):
                self.head = 1
            def submit(self):
                self.head = 0
    """)
    assert hits == []


# -- jit-purity -------------------------------------------------------------

def test_jit_purity_fires_on_host_effects_in_jitted_step():
    hits = _check("jit-purity", "siddhi_tpu/ops/x.py", """
        import time
        import jax
        class E:
            def build(self, fi, log):
                def step(state, cols):
                    fi.check("device.step")        # fault hook
                    log.debug("stepping")          # logging
                    t0 = time.time()               # host clock
                    self.stats.batches += 1        # stats counter
                    n = int(state.sum())           # tracer materialization
                    return state, n
                self._step = jax.jit(step)
    """)
    whats = sorted(f.message.split(" inside")[0] for f in hits)
    assert len(hits) == 5, whats
    assert all(f.scope == "E.build.step" for f in hits)


def test_jit_purity_resolves_lambdas_and_self_jax_receivers():
    hits = _check("jit-purity", "siddhi_tpu/ops/x.py", """
        class E:
            def build(self):
                self._f = self.jax.jit(lambda x: float(x.sum()))
    """)
    assert len(hits) == 1


def test_jit_purity_quiet_on_pure_step_and_host_side_effects():
    hits = _check("jit-purity", "siddhi_tpu/ops/x.py", """
        import jax
        import jax.numpy as jnp
        class E:
            def build(self):
                def step(state, cols):
                    return state + jnp.sum(cols), jnp.max(cols)
                self._step = jax.jit(step)
            def process(self, state, cols):
                state, peak = self._step(state, cols)
                self.stats.batches += 1   # host side: fine
                return state
    """)
    assert hits == []


# -- retrace-hazard ---------------------------------------------------------

def test_retrace_fires_on_per_batch_wrap():
    hits = _check("retrace-hazard", "siddhi_tpu/ops/x.py", """
        import jax
        class E:
            def process_batch(self, cols):
                f = jax.jit(lambda c: c * 2)   # fresh trace cache per call
                return f(cols)
    """)
    assert [f.scope for f in hits] == ["E.process_batch"]


def test_retrace_quiet_when_memoized_or_off_hot_path():
    memoized = """
        import jax
        class E:
            def process_batch(self, cols):
                if self._f is None:
                    self._f = jax.jit(lambda c: c * 2)
                return self._f(cols)
    """
    cached_local = """
        import jax
        class E:
            def _kernel(self, B):
                k = jax.jit(lambda c: c * 2)
                self._kernels[B] = k
                return k
    """
    builder = """
        import jax
        class E:
            def _build(self):
                return jax.jit(lambda c: c * 2)
    """
    for src in (memoized, cached_local, builder):
        assert _check("retrace-hazard", "siddhi_tpu/ops/x.py", src) == []


# -- fallback-discipline ----------------------------------------------------

def test_fallback_discipline_fires_when_not_counted():
    hits = _check("fallback-discipline", "siddhi_tpu/planner/x.py", """
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError
        def plan(log, name):
            try:
                lower(name)
            except SiddhiAppCreationError as e:
                log.warning("query '%s': fallback (%s)", name, e)
    """)
    assert [f.scope for f in hits] == ["plan"]
    assert "no record_*_fallback" in hits[0].message


def test_fallback_discipline_fires_when_not_logged():
    hits = _check("fallback-discipline", "siddhi_tpu/planner/x.py", """
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError
        def plan(sm, name):
            try:
                lower(name)
            except SiddhiAppCreationError as e:
                sm.record_hotkey_fallback(name, str(e))
    """)
    assert [f.scope for f in hits] == ["plan"]
    assert "no log.warning" in hits[0].message


def test_fallback_discipline_quiet_when_counted_and_logged_or_reraised():
    good = """
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError
        def plan(log, sm, name):
            try:
                lower(name)
            except SiddhiAppCreationError as e:
                log.warning("query '%s': fallback (%s)", name, e)
                sm.record_hotkey_fallback(name, str(e))
    """
    reraise = """
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError
        def plan(name):
            try:
                lower(name)
            except SiddhiAppCreationError:
                raise
    """
    assert _check("fallback-discipline", "siddhi_tpu/planner/x.py",
                  good) == []
    assert _check("fallback-discipline", "siddhi_tpu/planner/x.py",
                  reraise) == []


def test_fallback_discipline_follows_delegation_in_project_mode():
    """Handler delegates to self._fallback two methods away — the call
    graph proves both obligations are met."""
    rule = get_rule("fallback-discipline")
    src = """
        import logging
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError
        log = logging.getLogger("x")
        class Planner:
            def _fallback(self, name, reason):
                log.warning("query '%s': %s", name, reason)
                self.sm.record_multiplex_fallback(name, reason)
            def plan(self, name):
                try:
                    lower(name)
                except SiddhiAppCreationError as e:
                    return self._fallback(name, str(e))
    """
    idx = ModuleIndex(Path("fixture.py"), "siddhi_tpu/planner/x.py",
                      source=textwrap.dedent(src))
    # lexical mode cannot see into _fallback: it reports the gate
    rule.begin()
    assert [f.scope for f in rule.check(idx)] == ["Planner.plan"]
    # project mode follows the edge and stays quiet
    res = run_rules([idx], [rule], {"fallback-discipline":
                                    Allowlist("fallback-discipline", {})})
    assert res["findings"] == []


# -- thread-lifecycle -------------------------------------------------------

def test_thread_lifecycle_fires_on_unmanaged_thread():
    hits = _check("thread-lifecycle", "siddhi_tpu/core/x.py", """
        import threading
        class W:
            def start(self):
                self._t = threading.Thread(target=self._loop)
                self._t.start()
    """)
    assert [f.scope for f in hits] == ["W.start"]


def test_thread_lifecycle_quiet_on_daemon_or_joined():
    daemon_kw = """
        import threading
        class W:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()
    """
    daemon_attr = """
        import threading
        class W:
            def arm(self):
                t = threading.Timer(1.0, self._fire)
                t.daemon = True
                t.start()
    """
    joined = """
        import threading
        class W:
            def start(self):
                self._t = threading.Thread(target=self._loop)
                self._t.start()
            def stop(self):
                self._t.join()
    """
    cancelled = """
        import threading
        class W:
            def arm(self):
                self._timer = threading.Timer(1.0, self._fire)
                self._timer.start()
            def shutdown(self):
                self._timer.cancel()
    """
    local_joined = """
        import threading
        def run_pool(fns):
            ts = []
            for fn in fns:
                t = threading.Thread(target=fn)
                t.start()
                t.join()
    """
    for src in (daemon_kw, daemon_attr, joined, cancelled, local_joined):
        assert _check("thread-lifecycle", "siddhi_tpu/core/x.py",
                      src) == [], src


def test_thread_lifecycle_join_in_subclass_resolves_in_project_mode():
    """The mixin arms the Timer, the subclass's shutdown cancels it —
    only the MRO-merged view connects the two."""
    rule = get_rule("thread-lifecycle")
    files = {
        "pkg/__init__.py": "",
        "pkg/mix.py": """
            import threading
            class Mix:
                def arm(self):
                    self._timer = threading.Timer(1.0, self._fire)
                    self._timer.start()
        """,
        "pkg/sub.py": """
            from pkg.mix import Mix
            class Sub(Mix):
                def shutdown(self):
                    self._timer.cancel()
        """,
    }
    indexes = [ModuleIndex(Path(rel), rel, source=textwrap.dedent(src))
               for rel, src in files.items()]
    mix_idx = next(i for i in indexes if i.rel == "pkg/mix.py")
    # lexically the mixin's Timer looks unmanaged...
    rule.begin()
    assert [f.scope for f in rule.check(mix_idx)] == ["Mix.arm"]
    # ...project mode finds the subclass shutdown path
    res = run_rules(indexes, [rule], {"thread-lifecycle":
                                      Allowlist("thread-lifecycle", {})})
    assert res["findings"] == []


# -- allowlist mechanics ----------------------------------------------------

BAD_EXCEPT = """
    def f():
        try:
            g()
        except Exception:
            pass
"""


def _run_one(rule_name, rel, src, entries):
    rule = get_rule(rule_name)
    idx = ModuleIndex(Path("fixture.py"), rel,
                      source=textwrap.dedent(src))
    return run_rules([idx], [rule],
                     {rule_name: Allowlist(rule_name, entries)})


def test_allowlist_requires_justification():
    with pytest.raises(ValueError, match="justification"):
        Allowlist("broad-except-swallow", {"siddhi_tpu/core/x.py:f": ""})


def test_allowlist_suppresses_with_justification():
    res = _run_one("broad-except-swallow", "siddhi_tpu/core/x.py",
                   BAD_EXCEPT,
                   {"siddhi_tpu/core/x.py:f": "probe failure is benign"})
    assert res["findings"] == []
    assert [f.scope for f in res["suppressed"]] == ["f"]


def test_allowlist_entries_expire():
    """An entry that no longer trips the rule FAILS the run — lists
    only shrink (the old guards' test_allowlist_not_stale, generalized)."""
    res = _run_one("broad-except-swallow", "siddhi_tpu/core/x.py",
                   "def f():\n    g()\n",   # nothing to suppress anymore
                   {"siddhi_tpu/core/x.py:f": "obsolete"})
    assert [f.rule for f in res["findings"]] == ["stale-allowlist"]
    assert res["findings"][0].key == \
        "broad-except-swallow:siddhi_tpu/core/x.py:f"


def test_resolved_lock_entry_fails_as_stale_allowlist():
    """The cross-module-upgrade hygiene loop: once a sanctioned
    conflict is actually FIXED (the write is locked), its allowlist
    entry fails the run until pruned."""
    fixed = """
        import threading
        class Worker:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()
            def _loop(self):
                with self._lock:
                    self.count += 1
            def reset(self):
                with self._lock:
                    self.count = 0
    """
    res = _run_one("lock-discipline", "siddhi_tpu/core/x.py", fixed,
                   {"siddhi_tpu/core/x.py:Worker.count":
                    "was unlocked before the fix"})
    assert [f.rule for f in res["findings"]] == ["stale-allowlist"]
    assert res["findings"][0].key == \
        "lock-discipline:siddhi_tpu/core/x.py:Worker.count"


# -- SARIF round-trip -------------------------------------------------------

def test_sarif_round_trip_minimal_schema():
    """Findings render to SARIF 2.1.0 with the minimal required shape:
    schema/version, driver rule catalog, one result per finding with a
    physical location and a stable fingerprint."""
    import json

    from siddhi_tpu.analysis import all_rules

    res = _run_one("broad-except-swallow", "siddhi_tpu/core/x.py",
                   BAD_EXCEPT, {})
    rules = all_rules()
    doc = json.loads(reporting.render_sarif(res["findings"], rules))
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    assert len(doc["runs"]) == 1
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "siddhi-tpu-analysis"
    ids = [r["id"] for r in driver["rules"]]
    assert ids == [r.name for r in rules]
    assert all(r["shortDescription"]["text"] for r in driver["rules"])
    (result,) = run["results"]
    assert result["ruleId"] == "broad-except-swallow"
    assert ids[result["ruleIndex"]] == "broad-except-swallow"
    assert result["level"] == "error"
    assert result["message"]["text"]
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "siddhi_tpu/core/x.py"
    assert loc["region"]["startLine"] >= 1
    # the fingerprint is the line-number-free allowlist identity
    assert result["partialFingerprints"]["analysisKey/v1"] == \
        "broad-except-swallow:siddhi_tpu/core/x.py:f"


# -- baseline round-trip ----------------------------------------------------

def test_baseline_round_trip(tmp_path):
    res = _run_one("broad-except-swallow", "siddhi_tpu/core/x.py",
                   BAD_EXCEPT, {})
    assert len(res["findings"]) == 1
    path = tmp_path / "analysis_baseline.json"
    reporting.write_baseline(path, res["findings"])
    baseline = reporting.load_baseline(path)
    kept, baselined, stale = reporting.apply_baseline(
        res["findings"], baseline)
    assert kept == [] and len(baselined) == 1 and stale == []
    # a baselined identity that disappears is reported as stale, not fatal
    kept, baselined, stale = reporting.apply_baseline([], baseline)
    assert kept == [] and baselined == [] and \
        stale == ["broad-except-swallow:siddhi_tpu/core/x.py:f"]
