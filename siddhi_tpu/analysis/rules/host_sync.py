"""host-sync-hazard: no stray synchronous device→host transfers.

The async emit pipeline's contract is that jit outputs leave the device
ONLY through the sanctioned drain path (``core/emit_queue.py``
``fetch_coalesced`` / ``EmitQueue.drain``) or an explicit barrier
(snapshot/restore, timer steps).  An edit that sneaks a
``np.asarray(...)`` / ``jax.device_get(...)`` onto the hot batch path
re-introduces the per-batch transfer stall the pipeline removed — and
does so silently, because results stay correct.

The rule scans the device runtime modules and reports every
materializing call whose enclosing function is not allowlisted.
Host-side ingest conversions (interning, routing, padding) also use
``np.asarray`` on genuine numpy inputs; those functions are allowlisted
explicitly (bucket justifications in ``allowlists.py``) so NEW call
sites still trip the rule.  ``tests/test_device_single_integration.py``
/ ``test_dense_integration.py`` / ``test_sharded_windows.py`` pin the
same contract dynamically with ``jax.transfer_guard('disallow')``.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from ..framework import Finding, Rule, register
from ..index import ModuleIndex

#: the modules owning device-resident state; everything else in the
#: package is host-side and free to use numpy
SCANNED = (
    "siddhi_tpu/core/emit_queue.py",
    "siddhi_tpu/core/device_pipeline.py",
    "siddhi_tpu/core/device_single.py",
    "siddhi_tpu/core/dense_pattern.py",
    "siddhi_tpu/ops/device_query.py",
    "siddhi_tpu/ops/dense_nfa.py",
    "siddhi_tpu/ops/dense_layout.py",
    "siddhi_tpu/parallel/device_shard.py",
    "siddhi_tpu/parallel/mesh.py",
    "siddhi_tpu/ops/fused_graph.py",
    "siddhi_tpu/core/fused_graph.py",
    "siddhi_tpu/ops/hotkey_scan.py",
    "siddhi_tpu/core/hotkey_router.py",
    # durability: frozen device-array references may only materialize
    # through util.faults.host_copy (the injector-aware D2H choke point)
    # on the checkpoint writer thread — never inline under the barrier
    "siddhi_tpu/durability/capture.py",
    "siddhi_tpu/durability/writer.py",
    "siddhi_tpu/durability/store.py",
    "siddhi_tpu/durability/spill.py",
    # observability: span hooks ride the ingest/step/emit hot path —
    # they may timestamp and append to the ring, never materialize a
    # device array (a tracer that fetches would reintroduce the stall
    # it exists to measure)
    "siddhi_tpu/observability/trace.py",
    "siddhi_tpu/observability/recorder.py",
    "siddhi_tpu/observability/histograms.py",
    "siddhi_tpu/observability/prometheus.py",
    # Pallas kernels: the hottest device code in the tree — a
    # materialization inside a kernel wrapper would sync every step
    "siddhi_tpu/kernels/probe.py",
    # device tables: columnar HBM storage + join probes — mutations may
    # only touch the device through staged_put and leave it through the
    # count-gated fetch_coalesced drain (demotion rebuilds included)
    "siddhi_tpu/devtable/__init__.py",
    "siddhi_tpu/devtable/storage.py",
    "siddhi_tpu/devtable/join.py",
    "siddhi_tpu/devtable/planner.py",
)

MATERIALIZERS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array",
                 "jax.device_get"}


@register
class HostSyncHazardRule(Rule):
    name = "host-sync-hazard"
    description = (
        "device→host materialization outside the sanctioned count-gated "
        "emit drain / barrier paths in the device runtime modules")

    def begin(self):
        self._seen: set = set()

    def check(self, index: ModuleIndex) -> Iterable[Finding]:
        if index.rel not in SCANNED:
            return
        self._seen.add(index.rel)
        for call in index.calls():
            name = index.dotted(call.func)
            if name in MATERIALIZERS:
                yield Finding(
                    rule=self.name,
                    rel=index.rel,
                    line=call.lineno,
                    scope=index.qualname(call),
                    message=(
                        f"synchronous {name} outside the sanctioned "
                        "async-emit drain path — route it through the "
                        "runtime's EmitQueue, or allowlist it WITH a "
                        "bucket justification"),
                )

    def finish(self) -> Iterable[Finding]:
        out: List[Finding] = []
        for rel in SCANNED:
            if rel not in self._seen:
                out.append(Finding(
                    rule=self.name, rel=rel, line=0, scope="<module>",
                    message=("scanned-module list is stale: file moved "
                             "or was not analyzed"),
                    key=f"{rel}:<missing>"))
        return out
