"""Tier-1 guard: every ingest-path H2D transfer goes through staging.

Thin shim over the ``ingest-put-bypass`` rule in ``siddhi_tpu.analysis``
(which absorbed this file's AST scanner, allowlist, and staleness
check).  The test names are stable tier-1 anchors; the contract and the
curated allowlist (staging/mesh/state buckets) now live in
``siddhi_tpu/analysis/rules/ingest_put.py`` and
``siddhi_tpu/analysis/allowlists.py``.
"""

from pathlib import Path

from siddhi_tpu.analysis import ModuleIndex, get_rule, index_package, run_rules

REPO = Path(__file__).resolve().parent.parent

RULE = "ingest-put-bypass"


def _run():
    indexes = index_package(REPO / "siddhi_tpu", REPO)
    return run_rules(indexes, [get_rule(RULE)])


def test_detector_sees_through_receiver_chains():
    src = ("import jax\n"
           "class E:\n"
           "    def a(self):\n"
           "        jax.device_put(1)\n"
           "    def b(self):\n"
           "        self.jax.device_put(1)\n")
    rule = get_rule(RULE)
    rule.begin()
    idx = ModuleIndex(Path("fixture.py"), "fixture.py", source=src)
    hits = [(f.line, f.scope) for f in rule.check(idx)]
    assert hits == [(4, "E.a"), (6, "E.b")]


def test_no_device_put_bypasses_ingest_staging():
    hits = [f for f in _run()["findings"] if f.rule == RULE]
    assert not hits, (
        "direct device_put outside the sanctioned staging/mesh/state "
        "sites — route batch ingest through core/ingest_stage.staged_put "
        "(fault site + counters), or allowlist it in "
        "siddhi_tpu/analysis/allowlists.py WITH a bucket justification:\n  "
        + "\n  ".join(f.render() for f in hits))


def test_allowlist_not_stale():
    """Allowlist entries expire: one that no longer matches a finding
    surfaces as a ``stale-allowlist`` finding — the list only shrinks."""
    stale = [f for f in _run()["findings"] if f.rule == "stale-allowlist"]
    assert not stale, "\n  ".join(f.render() for f in stale)


def test_sharded_route_puts_through_staging():
    """The mesh's batch path is held to the sanctioned primitive like
    every other engine's: ``route`` hands its round to ``staged_put``,
    and ``_put`` (allowlisted: state rows) is not on it."""
    import ast
    import inspect
    import textwrap

    from siddhi_tpu.parallel.mesh import ShardedPatternEngine

    def calls(fn):
        tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
        return {getattr(n.func, "attr", getattr(n.func, "id", None))
                for n in ast.walk(tree) if isinstance(n, ast.Call)}

    route = calls(ShardedPatternEngine.route)
    assert "staged_put" in route
    assert not route & {"_put", "device_put"}
    # the rounds of a batch reach the device through route alone
    deferred = calls(ShardedPatternEngine.process_deferred)
    assert "route" in deferred and "device_put" not in deferred
