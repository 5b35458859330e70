"""Eligibility gates + wiring for the hand-written Pallas kernel layer.

``@app:kernels(...)`` asks the planner to swap the hot inner step of
eligible runtimes for a hand-written Pallas kernel
(siddhi_tpu/kernels/), each pinned bit-identical to the XLA
formulation it replaces:

- ``nfa``:  bit-packed dense-NFA step (kernels/dense_step.py) for
  every-headed simple filter chains;
- ``scan``: one fused kernel for the hotkey scan's max-plus + counting
  chains (kernels/scan_chain.py), replacing two associative-scan
  passes;
- ``bank``: collision-free segmented reduce (kernels/bank_scatter.py)
  replacing the aggregation bank's scatter-add.

Mirrors planner/hotkeys.py: every rejection raises
``SiddhiAppCreationError`` with a DISTINCT reason; the ``try_*``
wrappers convert that into a counted ``Queries.<q>.kernelFallbacks`` /
``kernelFallbackReason`` on the stats feed and leave the runtime on
its plain XLA path (graceful: @app:kernels never breaks a running
app).  Each enable ends by COMPILING the kernel at the block shape it
runs with (on TPU through Mosaic; interpreted elsewhere), so a compiler
refusal is known at app creation with the compiler's message in
``kernelFallbackReason`` — never at the first batch, never silently.
"""

from __future__ import annotations

import logging

from siddhi_tpu.core.exceptions import SiddhiAppCreationError

log = logging.getLogger("siddhi_tpu")


def check_dense_kernel_eligible(engine) -> None:
    """The bit-packed step kernel covers the every-headed simple-chain
    class only (one candidate plane bit per row, no counting/capture
    machinery).  Raises with a distinct reason outside it."""
    if engine.is_sequence:
        raise SiddhiAppCreationError(
            "nfa kernel: sequence semantics (strict contiguity masks) "
            "are not in the packed-plane step — XLA path kept")
    if not engine.every_start:
        raise SiddhiAppCreationError(
            "nfa kernel: non-every head needs reset-on-emit plane "
            "clears — XLA path kept")
    if engine.group_every:
        raise SiddhiAppCreationError(
            "nfa kernel: grouped-every restart masks are not in the "
            "packed-plane step — XLA path kept")
    if getattr(engine, "has_deadlines", False):
        raise SiddhiAppCreationError(
            "nfa kernel: absent/deadline nodes need per-chain timers — "
            "XLA path kept")
    for node in engine.nodes:
        if not (node.kind == "stream"
                and node.min_count == 1 and node.max_count == 1):
            raise SiddhiAppCreationError(
                "nfa kernel: counting/logical/absent nodes need the "
                "counts/register planes — XLA path kept")
    if engine.alloc.slots:
        raise SiddhiAppCreationError(
            "nfa kernel: captured attributes need the register file — "
            "XLA path kept")


def try_enable_dense_kernel(app, runtime, qname: str) -> bool:
    """Swap a DensePatternRuntime's step for the plane kernel; False
    (counted, logged) when ineligible or the compile fails."""
    sm = app.app_context.statistics_manager
    engine = runtime.engine
    try:
        check_dense_kernel_eligible(engine)
        if getattr(runtime, "mesh", None) is not None:
            raise SiddhiAppCreationError(
                "nfa kernel: mesh-sharded runtimes keep the XLA step "
                "(the kernel is single-device)")
        engine.use_kernel = True
        engine._step_cache.clear()
        try:
            from siddhi_tpu.kernels import dense_step

            dense_step.smoke_compile(engine)
        except Exception as e:
            engine.use_kernel = False
            engine._step_cache.clear()
            raise SiddhiAppCreationError(
                f"nfa kernel: compile failed: {e}")
        runtime.lowered_to = "kernel"
        return True
    except SiddhiAppCreationError as e:
        log.warning(
            "query '%s': @app:kernels(nfa) requested but the plane "
            "step cannot be used, staying on XLA: %s", qname, e)
        if sm is not None:
            sm.record_kernel_fallback(qname, str(e))
        return False


def try_enable_scan_kernel(app, router, qname: str) -> bool:
    """Swap a hotkey router's scan step for the fused chain kernel;
    False (counted, logged) when the compile fails."""
    sm = app.app_context.statistics_manager
    scan = router._scan
    try:
        scan.use_kernel = True
        scan._step_fn = None
        try:
            from siddhi_tpu.kernels import scan_chain
            from siddhi_tpu.ops.nfa_scan import NEG

            scan_chain.smoke_compile(scan.n_nodes, scan.n_slots, NEG)
        except Exception as e:
            scan.use_kernel = False
            scan._step_fn = None
            raise SiddhiAppCreationError(
                f"scan kernel: compile failed: {e}")
        return True
    except SiddhiAppCreationError as e:
        log.warning(
            "query '%s': @app:kernels(scan) requested but the fused "
            "chain kernel cannot be used, staying on XLA: %s", qname, e)
        if sm is not None:
            sm.record_kernel_fallback(qname, str(e))
        return False


def try_enable_bank_kernel(ctx, agg_name: str, bank) -> bool:
    """Route a DeviceBucketBank's scatter through the segmented-reduce
    kernel; False (counted, logged) when the compile fails."""
    sm = ctx.statistics_manager
    bank.use_kernel = True
    bank._scatter = None
    try:
        bank.smoke_compile()
        return True
    except Exception as e:
        bank.use_kernel = False
        bank._scatter = None
        reason = f"bank kernel: compile failed: {e}"
        log.warning(
            "aggregation '%s': @app:kernels(bank) requested but the "
            "segmented-reduce kernel cannot be used, staying on the "
            "XLA scatter: %s", agg_name, reason)
        if sm is not None:
            sm.record_kernel_fallback(agg_name, reason)
        return False


def try_enable_query_kernels(app, runtime, qname: str) -> None:
    """The planner hook for pattern queries: enable every requested
    kernel kind the runtime can host.  Works on both plain
    DensePatternRuntime and a HotKeyRouterRuntime wrapper (whose dense
    half and scan half are gated independently)."""
    from siddhi_tpu.core.hotkey_router import HotKeyRouterRuntime

    kinds = app.app_context.kernel_kinds
    if isinstance(runtime, HotKeyRouterRuntime):
        scan_ok = ("scan" in kinds
                   and try_enable_scan_kernel(app, runtime, qname))
        dense_ok = ("nfa" in kinds
                    and try_enable_dense_kernel(app, runtime._dense, qname))
        if scan_ok or dense_ok:
            runtime.lowered_to = "hotkey+kernel"
    elif "nfa" in kinds:
        try_enable_dense_kernel(app, runtime, qname)
