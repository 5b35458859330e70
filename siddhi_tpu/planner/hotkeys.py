"""Eligibility gate + wiring for the skew-aware hot-key router.

``@app:hotkeys(...)`` asks the planner to wrap eligible partitioned
dense pattern queries in a ``HotKeyRouterRuntime``
(core/hotkey_router.py): a space-saving sketch watches the junction's
key histogram and promotes heavy keys onto a batched associative-scan
engine (ops/hotkey_scan.py) while cold keys stay on the dense path.

The gate is strictly narrower than the dense gate — the scan's
exactness contract (events of one node interchangeable, state = per
-lane youngest start + count) only holds for every-headed linear
filter chains selecting final-node attributes.  Every rejection raises
``SiddhiAppCreationError`` with a DISTINCT reason; ``try_wrap_hotkey``
converts that into a counted ``Queries.<q>.hotkeyFallbacks`` /
``hotkeyFallbackReason`` on the stats feed and leaves the query on the
plain dense path (graceful: @app:hotkeys never breaks a running app).
"""

from __future__ import annotations

import logging
from typing import Optional

from siddhi_tpu.core.exceptions import SiddhiAppCreationError

log = logging.getLogger("siddhi_tpu")


def check_hotkey_eligible(st, dense_engine) -> None:
    """Gates BEYOND what the scan engine's own constructor enforces
    (linear every-headed chain, single stream, boolean device-evaluable
    filters, 2..32 nodes, no counts/logical/absent — see
    ops/nfa_scan._chain_nodes).  Raises with a distinct reason."""
    if len(dense_engine.stream_keys) != 1:
        raise SiddhiAppCreationError(
            "hotkey routing: multi-stream chains have per-stream steps "
            "the scan cannot interleave — dense path kept")
    if getattr(dense_engine, "has_deadlines", False):
        raise SiddhiAppCreationError(
            "hotkey routing: absent/deadline nodes need per-chain "
            "timers; the scan holds only youngest-start per lane — "
            "dense path kept")
    if dense_engine.alloc.slots:
        raise SiddhiAppCreationError(
            "hotkey routing: captured attributes from non-final nodes "
            "are not representable in youngest-start/count state — "
            "dense path kept")
    for _name, src in dense_engine.out_spec:
        if not (isinstance(src, tuple) and src[0] == "cand"):
            raise SiddhiAppCreationError(
                "hotkey routing: select references a non-final-node "
                "attribute — dense path kept")


def build_hotkey_router(app, st, dense_runtime, query_name: str):
    """Construct the scan engine + router for an eligible query; raises
    SiddhiAppCreationError (with the reason) when ineligible."""
    from siddhi_tpu.core.hotkey_router import HotKeyRouterRuntime
    from siddhi_tpu.ops.hotkey_scan import HotKeyScanEngine

    ctx = app.app_context
    check_hotkey_eligible(st, dense_runtime.engine)
    sid = dense_runtime.engine.stream_keys[0]
    stream_def = app.definitions.get(sid)
    if stream_def is None:
        raise SiddhiAppCreationError(
            f"hotkey routing: stream '{sid}' has no definition")
    # the scan ctor re-runs the chain walk + filter trace and raises
    # its own distinct reasons (sequence, within, non-filter handlers,
    # non-device-evaluable filters, ...)
    scan = HotKeyScanEngine(st, stream_def, n_slots=ctx.hotkey_k)
    return HotKeyRouterRuntime(
        dense_runtime, scan,
        promote=ctx.hotkey_promote, demote=ctx.hotkey_demote,
        query_name=query_name)


def try_wrap_hotkey(app, st, dense_runtime, query_name: str
                    ) -> Optional[object]:
    """The planner hook: router on success, None (with a counted,
    logged fallback reason) when the query is outside the scan class."""
    sm = app.app_context.statistics_manager
    try:
        router = build_hotkey_router(app, st, dense_runtime, query_name)
        if sm is not None:
            sm.register_hotkey_router(query_name, router)
        return router
    except SiddhiAppCreationError as e:
        log.warning(
            "query '%s': @app:hotkeys requested but query is outside "
            "the scan class, staying dense: %s", query_name, e)
        if sm is not None:
            sm.record_hotkey_fallback(query_name, str(e))
        return None
