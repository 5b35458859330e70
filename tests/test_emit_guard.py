"""Tier-1 guard: no stray synchronous device→host transfers.

Thin shim over the ``host-sync-hazard`` rule in ``siddhi_tpu.analysis``
(which absorbed this file's AST scanner, allowlist, and staleness
check).  The test names are stable tier-1 anchors; the contract, the
scanned-module list, and the curated allowlist (with bucket
justifications) now live in ``siddhi_tpu/analysis/rules/host_sync.py``
and ``siddhi_tpu/analysis/allowlists.py``.
"""

from pathlib import Path

from siddhi_tpu.analysis import get_rule, index_package, run_rules

REPO = Path(__file__).resolve().parent.parent

RULE = "host-sync-hazard"


def _run():
    indexes = index_package(REPO / "siddhi_tpu", REPO)
    return run_rules(indexes, [get_rule(RULE)])


def test_no_stray_sync_transfers_in_device_runtimes():
    hits = [f for f in _run()["findings"] if f.rule == RULE]
    assert not hits, (
        "synchronous device->host materialization outside the sanctioned "
        "async-emit drain path (route it through the runtime's EmitQueue, "
        "or allowlist it in siddhi_tpu/analysis/allowlists.py WITH a "
        "bucket justification):\n  "
        + "\n  ".join(f.render() for f in hits))


def test_allowlist_not_stale():
    """Allowlist entries expire: one that no longer matches a finding
    surfaces as a ``stale-allowlist`` finding — the list only shrinks."""
    stale = [f for f in _run()["findings"] if f.rule == "stale-allowlist"]
    assert not stale, "\n  ".join(f.render() for f in stale)


def test_early_copies_start_at_the_one_seam():
    """On the way back ``copy_to_host_async`` is called by
    ``DevicePipeline`` alone (core/device_pipeline.py ``_start_copies``):
    no engine and no shell keeps a copy of the rule that decides which
    arrays start early.  The one other caller is no emit: a dense
    runtime's ``snapshot()`` starts the copies of the fields its
    snapshot program wrote (core/dense_pattern.py, PR 49)."""
    callers = {
        str(p.relative_to(REPO)): p.read_text().count("copy_to_host_async(")
        for p in (REPO / "siddhi_tpu").rglob("*.py")
        if "copy_to_host_async(" in p.read_text()}
    assert sorted(callers) == ["siddhi_tpu/core/dense_pattern.py",
                               "siddhi_tpu/core/device_pipeline.py"]
    assert callers["siddhi_tpu/core/dense_pattern.py"] == 1
