"""Product-side runtime of one fused chain (ops/fused_graph.py).

`FusedChainRuntime` is the chain's analog of DeviceQueryRuntime
(core/device_single.py): it converts the HEAD stream's junction batches
to device columns, advances the whole chain with ONE jitted fused step,
and emits the TAIL's output batches into the tail query's
selector/output chain.  Intermediate streams never build EventBatches
and never dispatch through their junctions — their event columns live
in HBM between stages.

It rides the same async machinery as the per-query runtimes — ingest
staging window (core/ingest_stage.py), bounded pending-emit queue
(core/emit_queue.py), fault choke-points (ingest.put / step.device /
step.dense / emit.drain), NaN/Inf poison quarantine — and the same
barriers: drain on snapshot/restore, rate-limiter fires, pull queries,
and shutdown, so callback content and order stay bit-identical to the
junction path.

Snapshot/restore: the planner attaches this runtime as the TAIL
query's ``device_runtime``, so QueryRuntime.snapshot_state persists the
whole chain's state (per-stage device arrays + host epochs) under the
tail query's name and crash replay (input journal) reproduces it.

This module is scanned by the `host-sync-hazard` analysis rule with no
allowlist entries: snapshots deep-copy through util.faults.host_copy,
restores re-materialize with jnp.asarray, and every column fetch goes
through the emit queue's coalesced drain.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.device_pipeline import DevicePipeline
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import SiddhiAppRuntimeError


class FusedChainRuntime:
    """One fused chain: head-junction subscriber in, tail-query output
    chain out, everything between device-resident."""

    def __init__(self, graph, out_stream_id: str,
                 emit: Callable[[EventBatch], None], app_context=None):
        self.graph = graph
        self.out_stream_id = out_stream_id
        self.emit_cb = emit
        self.state = graph.init_state()
        self.step_invocations = 0  # fused program dispatches (tests)
        # hops kept device-resident: (stages - 1) junction dispatches
        # saved per batch (``IngestStats.fused_hops``: ``fusedHops`` in
        # ``statistics()`` under the tail query's name)
        self.hops_per_dispatch = (
            len(graph.stages) + (1 if graph.dense is not None else 0) - 1)
        # count gate, emit queue, drain(), fault isolation, poison
        # quarantine (core/device_pipeline.py); one fused dispatch is
        # one cycle, labeled with the 'fused' kind
        self.pipeline = DevicePipeline(app_context, "fused")
        self.pipeline.attach(self, graph)

    # -- event path ----------------------------------------------------------

    def process_stream_batch(self, batch: EventBatch, keys=None):
        cur = batch.only(ev.CURRENT)
        n = len(cur)
        if n == 0:
            return
        with self.pipeline.cycle(n) as tok:
            self._advance(cur, tok)

    def _advance(self, cur: EventBatch, tok):
        pipe = self.pipeline
        head = self.graph.stages[0]
        cols = {
            a: cur.columns[a]
            for a in head.all_attrs if a in cur.columns
        }
        ts = cur.timestamps
        self.state, pending = self.graph.process_batch_deferred(
            self.state, cols, ts)
        self.step_invocations += 1
        self.ingest_stats.fused_hops += self.hops_per_dispatch
        # NaN/Inf quarantine over the WHOLE chain's state tuple
        self.state, poisoned = pipe.quarantine(
            self.state, self.graph.init_state)
        if poisoned:
            pipe.drop_poisoned(tok)
            return
        now = pipe.now()  # sampled at receive time, bound into deliver
        pipe.submit(
            tok, pending,
            lambda host: self._build_deferred(pending, host, now),
            self.emit_cb)

    def _build_deferred(self, pending, host_arrays, now=None):
        out_cols, out_ts = pending.materialize(host_arrays)
        if len(out_ts) == 0:
            return None
        mb = EventBatch(
            self.out_stream_id, self.graph.output_names, out_cols,
            out_ts, np.full(len(out_ts), ev.CURRENT, dtype=np.int8),
        )
        if now is not None:
            mb.aux["emit_now"] = now
        return mb

    def close(self):
        self.drain()

    # -- scheduler task contract (the fused kinds have no pane timers;
    # registration keeps the planner wiring uniform) -------------------------

    def next_wakeup(self) -> Optional[int]:
        return None

    def fire(self, now: int):
        self.drain()

    def on_start(self, now: int):
        pass

    def on_time(self, now: int):
        pass

    # -- stats ---------------------------------------------------------------

    def stats(self) -> Dict:
        return {
            "engine": "fused",
            "stages": len(self.graph.stages)
            + (1 if self.graph.dense is not None else 0),
            "step_invocations": self.step_invocations,
            "fused_hops": self.ingest_stats.fused_hops,
        }

    # -- snapshot contract ---------------------------------------------------

    def snapshot(self) -> Dict:
        self.drain()
        from siddhi_tpu.util.faults import host_copy

        snap: Dict = {
            "chain": [host_copy(st) for st in self.state],
            "hosts": [eng.host_snapshot() for eng in self.graph.stages],
        }
        dense = self.graph.dense
        if dense is not None:
            # the dense tail's state in its logical form, whatever the
            # resident layout (ops/dense_layout.py)
            snap["chain"][-1] = dense.layout.unpack(self.state[-1])
            snap["dense_base_ts"] = dense.base_ts
        return snap

    def restore(self, state: Dict):
        self.drain()
        self.pipeline.forget_clean_copy()
        g = self.graph
        jnp = g.jnp
        chain = state["chain"]
        n_states = len(g.stages) + (1 if g.dense is not None else 0)
        if len(chain) != n_states:
            raise SiddhiAppRuntimeError(
                f"fused-chain snapshot has {len(chain)} stage states; "
                f"this chain has {n_states} — persist and restore must "
                "use the same app definition")
        restored: List = []
        for si, st in enumerate(chain):
            is_dense = si >= len(g.stages)
            eng = g.dense if is_dense else g.stages[si]
            expect = (eng.layout.logical_shapes(eng.n_partitions + 1)
                      if is_dense else
                      {k: v.shape for k, v in eng.init_state_host().items()})
            for k, v in st.items():
                if k in expect and v.shape != expect[k]:
                    raise SiddhiAppRuntimeError(
                        f"fused-chain snapshot stage {si} array '{k}' has "
                        f"shape {v.shape}; this chain expects {expect[k]}")
            if is_dense:
                st = eng.layout.pack(st)
            restored.append({k: jnp.asarray(v) for k, v in st.items()})
        self.state = tuple(restored)
        for eng, h in zip(g.stages, state["hosts"]):
            eng.host_restore(h)
        if g.dense is not None:
            g.dense.base_ts = state.get("dense_base_ts")


class _FusedChainReceiver:
    """Head-junction subscriber feeding one fused chain."""

    def __init__(self, runtime: FusedChainRuntime):
        self.runtime = runtime

    def receive(self, batch: EventBatch):
        self.runtime.process_stream_batch(batch)
