"""Partitions: ``partition with (expr of Stream, ...) begin ... end``.

Re-design of the reference ``core/partition/``
(PartitionRuntimeImpl.java:75, PartitionStreamReceiver.java:44,
ValuePartitionExecutor.java:34, RangePartitionExecutor.java): instead of
ThreadLocal flow-routing into lazily cloned per-key state holders, a
partitioned batch is key-grouped **vectorized** (one executor evaluation
per batch) and each key's sub-batch is fed into that key's *instance* —
a lazily planned copy of the inner queries whose junction namespace
overlays per-key local junctions (partitioned inputs + ``#inner``
streams) on the app's global ones.

The 1M-key hot path (pattern queries over partitioned streams) does not
use these instances — it compiles to the dense NFA engine with a
partition axis (ops/dense_nfa.py); these instances are the general-
purpose semantics-complete path, mirroring the reference's design.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.core.stream import StreamJunction
from siddhi_tpu.planner.expr import CompiledExpression, N_KEY, TS_KEY
from siddhi_tpu.query_api import (
    Partition,
    RangePartitionType,
    StreamDefinition,
    ValuePartitionType,
)
from siddhi_tpu.query_api.annotation import find_annotation


def _batch_env(batch: EventBatch) -> Dict:
    env = dict(batch.columns)
    env[TS_KEY] = batch.timestamps
    env[N_KEY] = len(batch)
    return env


class ValuePartitionExecutor:
    """Key = expression value (reference: ValuePartitionExecutor.java:34)."""

    def __init__(self, compiled: CompiledExpression):
        self.compiled = compiled

    def keys(self, batch: EventBatch) -> List:
        return self.keys_array(batch).tolist()

    def keys_array(self, batch: EventBatch) -> np.ndarray:
        """Raw key column (native dtype, no per-element boxing) — the
        dense path interns straight from this."""
        return np.broadcast_to(
            np.asarray(self.compiled.fn(_batch_env(batch))), (len(batch),))


class RangePartitionExecutor:
    """Key = label of the first matching range condition; non-matching
    rows get None and are dropped (reference: RangePartitionExecutor)."""

    def __init__(self, ranges: List[Tuple[CompiledExpression, str]]):
        self.ranges = ranges

    def keys(self, batch: EventBatch) -> List:
        return self.keys_array(batch).tolist()

    def keys_array(self, batch: EventBatch) -> np.ndarray:
        n = len(batch)
        env = _batch_env(batch)
        out = np.full(n, None, dtype=object)
        assigned = np.zeros(n, dtype=bool)
        for cond, label in self.ranges:
            m = np.broadcast_to(np.asarray(cond.fn(env)), (n,)) & ~assigned
            out[m] = label
            assigned |= m
        return out


class _ScopedScheduler:
    """Records an instance's scheduler registrations so a purged/template
    instance can be fully unregistered (no ghost window ticks)."""

    def __init__(self, real):
        self._real = real
        self._items: List[Tuple[str, object]] = []

    def register_window(self, query_runtime, window):
        self._real.register_window(query_runtime, window)
        self._items.append(("window", (query_runtime, window)))

    def register_task(self, task):
        self._real.register_task(task)
        self._items.append(("task", task))

    def unregister_all(self):
        for kind, item in self._items:
            if kind == "window":
                self._real.unregister_window(*item)
            else:
                self._real.unregister_task(item)
        self._items = []


class _InstancePlanner:
    """Planner facade for one partition-key instance: local junctions for
    partitioned inputs and ``#inner`` streams overlay the app's global
    namespace; everything else delegates."""

    # per-key clones must use the host pattern engine — the dense TPU
    # form of a partitioned pattern is ONE engine with interned keys,
    # wired by PartitionRuntime, not one engine per instance
    in_partition_instance = True

    def __init__(self, app_planner, partitioned_defs: Dict[str, StreamDefinition], key):
        self._app = app_planner
        self.key = key
        self._scoped_scheduler = _ScopedScheduler(app_planner.scheduler)
        self.local_junctions: Dict[str, StreamJunction] = {}
        self.local_definitions: Dict[str, StreamDefinition] = {}
        self.query_runtimes: Dict[str, object] = {}
        for sid, definition in partitioned_defs.items():
            j = StreamJunction(definition, app_planner.app_context)
            j.start()
            self.local_junctions[sid] = j
            self.local_definitions[sid] = definition

    # -- delegated surface --------------------------------------------------

    @property
    def functions(self):
        return getattr(self._app, "functions", {})

    @property
    def app_context(self):
        return self._app.app_context

    @property
    def extensions(self):
        return self._app.extensions

    @property
    def scheduler(self):
        return self._scoped_scheduler

    @property
    def tables(self):
        return self._app.tables

    @property
    def named_windows(self):
        return self._app.named_windows

    def table_resolver(self, table_name: str, obj: bool = False):
        return self._app.table_resolver(table_name, obj=obj)

    # -- junction namespace -------------------------------------------------

    @property
    def junctions(self):
        # input namespace is local-only: queries inside a partition may only
        # read partitioned or #inner streams (global reads would make every
        # key instance a duplicate subscriber)
        return self.local_junctions

    @staticmethod
    def _key(stream_id: str, is_inner: bool = False, is_fault: bool = False) -> str:
        if is_inner:
            return "#" + stream_id
        if is_fault:
            return "!" + stream_id
        return stream_id

    def resolve_stream_definition(self, s) -> StreamDefinition:
        key = self._key(s.stream_id, getattr(s, "is_inner", False), getattr(s, "is_fault", False))
        if key in self.local_definitions:
            return self.local_definitions[key]
        return self._app.resolve_stream_definition(s)

    def junction_for_input(self, s) -> StreamJunction:
        key = self._key(s.stream_id, s.is_inner, s.is_fault)
        if key in self.local_junctions:
            return self.local_junctions[key]
        raise SiddhiAppCreationError(
            f"stream '{key}': queries inside a partition can only read "
            "the partitioned streams or '#inner' streams"
        )

    def get_or_create_junction(
        self, stream_id: str, fallback_def: StreamDefinition, is_inner=False, is_fault=False
    ) -> StreamJunction:
        if is_inner:
            key = "#" + stream_id
            if key not in self.local_junctions:
                d = StreamDefinition(id=stream_id, attributes=list(fallback_def.attributes))
                j = StreamJunction(d, self._app.app_context)
                j.start()
                self.local_junctions[key] = j
                self.local_definitions[key] = d
            return self.local_junctions[key]
        return self._app.get_or_create_junction(stream_id, fallback_def, is_fault=is_fault)


class PartitionInstance:
    """One key's planned copy of the inner queries."""

    def __init__(self, key, partition: Partition, app_planner, partitioned_defs):
        from siddhi_tpu.planner.query_planner import QueryPlanner

        self.key = key
        self.planner = _InstancePlanner(app_planner, partitioned_defs, key)
        qp = QueryPlanner(self.planner)
        self.query_runtimes: Dict[str, object] = {}
        for qi, q in enumerate(partition.queries):
            qr = qp.plan(q, qi)
            self.query_runtimes[qr.name] = qr
        self.last_used: int = 0

    def send(self, stream_id: str, batch: EventBatch, now: int):
        self.last_used = now
        self.planner.local_junctions[stream_id].send(batch)

    def close(self):
        """Unregister every scheduler hook this instance planted."""
        self.planner._scoped_scheduler.unregister_all()
        for j in self.planner.local_junctions.values():
            j.stop()


def _pattern_stream_ids(st) -> List[str]:
    """Junction keys of every source stream in a pattern input (AST walk
    — no planning side effects)."""
    from siddhi_tpu.query_api import (
        CountStateElement,
        LogicalStateElement,
        NextStateElement,
        EveryStateElement,
        StreamStateElement,
    )

    out: List[str] = []

    def walk(el):
        if isinstance(el, NextStateElement):
            walk(el.element)
            walk(el.next)
        elif isinstance(el, EveryStateElement):
            walk(el.element)
        elif isinstance(el, CountStateElement):
            walk(el.stream_state)
        elif isinstance(el, LogicalStateElement):
            walk(el.element1)
            walk(el.element2)
        elif isinstance(el, StreamStateElement):
            s = el.stream
            prefix = "#" if s.is_inner else ("!" if s.is_fault else "")
            key = prefix + s.stream_id
            if key not in out:
                out.append(key)

    walk(st.state)
    return out


class DensePartitionReceiver:
    """Subscriber on a partitioned stream's global junction for the
    TPU form: evaluates the partition executor once per batch and
    advances every device-lowered runtime that reads this stream — no
    per-key instances, no per-key routing.  Runtimes are either dense
    NFA pattern runtimes (which intern keys to engine rows themselves)
    or partitioned device-query runtimes (which take the raw key
    column); both kinds advance in query plan order."""

    def __init__(self, stream_id: str, executor, runtimes: List):
        self.stream_id = stream_id
        self.executor = executor
        self.runtimes = runtimes

    def receive(self, batch: EventBatch):
        cur = batch.only(ev.CURRENT)
        if len(cur) == 0:
            return
        keys = self.executor.keys_array(cur)
        if keys.dtype == object:  # range partitions drop unmatched (None)
            keep = np.not_equal(keys, None)
            if not keep.all():
                cur = cur.mask(keep)
                if len(cur) == 0:
                    return
                keys = keys[keep]
            # range labels are strings: re-infer a native '<U' dtype so
            # the vectorized intern index applies
            keys = np.asarray(keys.tolist())
        for rt in self.runtimes:
            if hasattr(rt, "intern_keys"):  # dense NFA pattern runtime
                rt.receive_keyed(self.stream_id, cur, keys)
            else:  # partitioned device-query runtime
                rt.process_stream_batch(cur, keys=keys)


class PartitionStreamReceiver:
    """Subscriber on a partitioned stream's global junction: evaluates
    the partition executor once per batch, groups rows by key, and routes
    each sub-batch into that key's instance (reference:
    PartitionStreamReceiver.receive:82-118)."""

    def __init__(self, partition_runtime: "PartitionRuntime", stream_id: str, executor):
        self.partition_runtime = partition_runtime
        self.stream_id = stream_id
        self.executor = executor

    def receive(self, batch: EventBatch):
        pr = self.partition_runtime
        now = pr.app_context.timestamp_generator.current_time()
        keys = self.executor.keys(batch)
        # order-preserving group-by-key
        groups: Dict = {}
        for i, k in enumerate(keys):
            if k is None:
                continue  # range partitions drop unmatched rows
            groups.setdefault(k, []).append(i)
        for k, idx in groups.items():
            inst = pr.instance_for(k)
            sub = batch if len(idx) == len(batch) else batch.take(np.asarray(idx))
            inst.send(self.stream_id, sub, now)


class PartitionRuntime:
    """All instances of one ``partition ... begin ... end`` block
    (reference: PartitionRuntimeImpl.java:75)."""

    def __init__(self, partition: Partition, app_planner, index: int):
        self.partition = partition
        self.app_planner = app_planner
        self.app_context = app_planner.app_context
        self.name = f"partition_{index}"
        self.instances: Dict[object, PartitionInstance] = {}

        self.partitioned_defs: Dict[str, StreamDefinition] = {}
        self._executors: Dict[str, object] = {}
        from siddhi_tpu.planner.expr import ExpressionCompiler
        from siddhi_tpu.planner.query_planner import scope_for_definition

        for pt in partition.partition_types:
            sid = pt.stream_id
            if sid not in app_planner.definitions:
                raise SiddhiAppCreationError(
                    f"{self.name}: partitioned stream '{sid}' is not defined"
                )
            definition = app_planner.definitions[sid]
            self.partitioned_defs[sid] = definition
            compiler = ExpressionCompiler(
                scope_for_definition(definition, sid),
                functions=getattr(app_planner, "functions", None),
                table_resolver=app_planner.table_resolver,
            )
            if isinstance(pt, ValuePartitionType):
                ex = ValuePartitionExecutor(compiler.compile(pt.expression))
            elif isinstance(pt, RangePartitionType):
                ex = RangePartitionExecutor(
                    [(compiler.compile(c), label) for c, label in pt.ranges]
                )
            else:
                raise SiddhiAppCreationError(f"unknown partition type {pt!r}")
            self._executors[sid] = ex

        # @app:execution('tpu'): a partition whose body is all
        # dense-eligible pattern queries lowers to ONE engine per query
        # with the partition key interned onto the engine's partition
        # axis — per-key state rows in device memory instead of per-key
        # Python instances (the 1M-key hot path, BASELINE.json configs)
        self.dense_query_runtimes: Dict[str, object] = {}
        self.is_dense = False
        if app_planner.app_context.execution_mode == "tpu":
            import logging

            try:
                self._plan_dense(partition, app_planner)
                self.is_dense = True
                logging.getLogger("siddhi_tpu").info(
                    "%s: lowered to the dense TPU path (%d queries, "
                    "%d key rows)", self.name,
                    len(self.dense_query_runtimes),
                    app_planner.app_context.tpu_partitions)
            except SiddhiAppCreationError as e:
                self.dense_query_runtimes = {}
                # WARN: execution('tpu') was requested and this
                # partition is getting per-key host instances
                logging.getLogger("siddhi_tpu").warning(
                    "%s: dense TPU path unavailable (%s); using per-key "
                    "instances", self.name, e)
                sm = app_planner.app_context.statistics_manager
                if sm is not None:
                    sm.record_device_fallback(
                        self.name, f"dense partition: {e}")

        if not self.is_dense:
            for sid, ex in self._executors.items():
                app_planner.junctions[sid].subscribe(
                    PartitionStreamReceiver(self, sid, ex)
                )
            # plan an inert template instance eagerly: creates the global
            # output junctions (so downstream queries/callbacks can bind at
            # build time) and surfaces plan errors at app creation instead
            # of first event
            template = PartitionInstance(
                "__template__", partition, app_planner, self.partitioned_defs
            )
            template.close()  # only its planning side effects are needed

        # @purge(enable='true', interval='..', idle.period='..')
        self._purge_interval_ms: Optional[int] = None
        self._purge_idle_ms: Optional[int] = None
        self._next_purge: Optional[int] = None
        purge = find_annotation(partition.annotations, "purge")
        if purge is not None and (purge.element("enable") or "false").lower() == "true":
            from siddhi_tpu.compiler.parser import parse_time_string

            self._purge_interval_ms = parse_time_string(purge.element("interval") or "1 min")
            self._purge_idle_ms = parse_time_string(purge.element("idle.period") or "15 min")
            app_planner.scheduler.register_task(self)

    def _plan_dense(self, partition: Partition, app_planner):
        """Lower every inner query to a device engine or raise (caller
        falls back to per-key instances wholesale — mixed mode would
        split one partition's semantics across two engines).  Pattern
        queries lower to the dense NFA engine; general single-stream
        queries (filter/window/group-by) lower to the device query
        engine with the partition key composed into the group axis."""
        from siddhi_tpu.planner.query_planner import QueryPlanner
        from siddhi_tpu.query_api import (
            InsertIntoStream,
            Query,
            ReturnStream,
            SingleInputStream,
            StateInputStream,
        )
        from siddhi_tpu.query_api.annotation import find_annotation as _find

        # cheap AST-level validation of EVERY query before planning any,
        # so a late ineligibility doesn't leak side effects of earlier
        # fully-planned queries
        for q in partition.queries:
            if not isinstance(q, Query):
                raise SiddhiAppCreationError("nested element not a query")
            st = q.input_stream
            out = q.output_stream
            if isinstance(out, InsertIntoStream) and out.is_inner:
                raise SiddhiAppCreationError(
                    "'insert into #inner' needs per-key instances")
            elif not isinstance(out, (InsertIntoStream, ReturnStream)) and out is not None:
                raise SiddhiAppCreationError(
                    "table/window outputs need per-key instances")
            if isinstance(st, StateInputStream):
                for sid in _pattern_stream_ids(st):
                    if sid not in self.partitioned_defs:
                        raise SiddhiAppCreationError(
                            f"pattern input '{sid}' is not a partitioned stream")
            elif isinstance(st, SingleInputStream):
                if st.is_inner or st.is_fault:
                    raise SiddhiAppCreationError(
                        "inner/fault stream inputs need per-key instances")
                if st.stream_id not in self.partitioned_defs:
                    raise SiddhiAppCreationError(
                        f"input '{st.stream_id}' is not a partitioned stream")
            else:
                raise SiddhiAppCreationError(
                    "join queries inside partitions need per-key instances")

        qp = QueryPlanner(app_planner)
        planned = []  # (name, qr, runtime)
        try:
            for qi, q in enumerate(partition.queries):
                info = _find(q.annotations, "info")
                name = (info.element("name") if info else None) or f"{self.name}_q{qi}"
                if isinstance(q.input_stream, StateInputStream):
                    qr = qp._plan_dense_state(
                        q, name, q.input_stream,
                        n_partitions=app_planner.app_context.tpu_partitions,
                        subscribe=False,
                    )
                    planned.append((name, qr, qr.pattern_processor))
                else:
                    qr = qp._plan_device_single(
                        q, name, q.input_stream,
                        partition_mode=True, subscribe=False,
                    )
                    planned.append((name, qr, qr.device_runtime))
        except SiddhiAppCreationError:
            # unwind scheduler tasks of already-planned siblings before
            # the wholesale fallback to per-key instances
            for _n, qr, _r in planned:
                for attr in ("_rate_task", "_dense_timer_task"):
                    task = getattr(qr, attr, None)
                    if task is not None:
                        app_planner.scheduler.unregister_task(task)
            raise
        # all queries lowered — wire key-routed receivers
        for name, qr, runtime in planned:
            self.dense_query_runtimes[name] = qr
        for sid, ex in self._executors.items():
            runtimes = [
                r for _n, _qr, r in planned
                if (sid in r.engine.stream_keys
                    if hasattr(r, "intern_keys")
                    else r.engine.stream_id == sid)
            ]
            if runtimes:
                app_planner.junctions[sid].subscribe(
                    DensePartitionReceiver(sid, ex, runtimes)
                )

    def query_lowering(self) -> Dict[str, str]:
        """Engine placement of every inner query (see
        AppRuntime.lowering): dense-lowered bodies report per query;
        per-key instance bodies are host by construction."""
        if self.is_dense:
            return {
                n: getattr(qr, "lowered_to", "host")
                for n, qr in self.dense_query_runtimes.items()
            }
        out = {}
        for qi, q in enumerate(self.partition.queries):
            info = find_annotation(getattr(q, "annotations", []), "info")
            n = (info.element("name") if info else None) or f"{self.name}_q{qi}"
            out[n] = "host"
        return out

    def instance_for(self, key) -> PartitionInstance:
        inst = self.instances.get(key)
        if inst is None:
            inst = PartitionInstance(
                key, self.partition, self.app_planner, self.partitioned_defs
            )
            self.instances[key] = inst
        return inst

    # -- idle-key purging (scheduler task) ----------------------------------

    def next_wakeup(self) -> Optional[int]:
        return self._next_purge

    def on_start(self, now: int):
        if self._purge_interval_ms is not None:
            self._next_purge = now + self._purge_interval_ms

    def fire(self, now: int):
        while self._next_purge is not None and self._next_purge <= now:
            self._next_purge += self._purge_interval_ms
        if self.is_dense:
            # reclaim idle key rows of the shared engines (the dense
            # analog of dropping idle PartitionInstances)
            for qr in self.dense_query_runtimes.values():
                rt = (getattr(qr, "pattern_processor", None)
                      or getattr(qr, "device_runtime", None))
                rt.purge_idle(now, self._purge_idle_ms)
            return
        dead = [
            k
            for k, inst in self.instances.items()
            if now - inst.last_used >= self._purge_idle_ms
        ]
        for k in dead:
            self.instances.pop(k).close()

    # -- snapshot contract --------------------------------------------------

    def snapshot(self) -> Dict:
        if self.is_dense:
            return {
                "__dense__": {
                    qname: qr.snapshot_state()
                    for qname, qr in self.dense_query_runtimes.items()
                }
            }
        out: Dict = {}
        for k, inst in self.instances.items():
            qstates: Dict = {}
            for qname, qr in inst.query_runtimes.items():
                if hasattr(qr, "snapshot_state"):
                    qstates[qname] = qr.snapshot_state()
            out[k] = qstates
        return out

    def restore(self, state: Dict):
        if self.is_dense:
            dense = state.get("__dense__", {})
            for qname, qs in dense.items():
                qr = self.dense_query_runtimes.get(qname)
                if qr is not None:
                    qr.restore_state(qs)
            return
        for inst in self.instances.values():
            inst.close()
        self.instances.clear()
        import time as _time

        now = int(_time.time() * 1000)
        for k, qstates in state.items():
            inst = self.instance_for(k)
            # fresh instances must not look idle to the purge task
            inst.last_used = now
            for qname, qs in qstates.items():
                qr = inst.query_runtimes.get(qname)
                if qr is not None and hasattr(qr, "restore_state"):
                    qr.restore_state(qs)
