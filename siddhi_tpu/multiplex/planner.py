"""Multiplex placement: seat eligible queries in shared device engines.

``MultiplexPlanner`` rides the two ``@app:execution('tpu')`` gates in
``planner/query_planner.py``: before the dedicated dense / device-query
paths run, an ``@app:multiplex`` app first tries to seat the query in a
manager-wide shared engine keyed by structural fingerprint
(``fingerprint.py``).  Success wires a per-tenant adapter runtime
(``tumbling_group.py`` / ``dense_group.py``) behind the exact same
QueryRuntime surface the dedicated paths build, so selectors, output
callbacks, statistics and snapshots are indistinguishable downstream.

Every ineligibility is COUNTED, not silent: the reason lands on
``StatisticsManager.record_multiplex_fallback`` (REST:
``multiplexFallbackReason``) and the planner falls through to the
dedicated engine, so behavior degrades to PR-parity rather than
failing the app.
"""

from __future__ import annotations

import logging
from typing import List, Optional

from siddhi_tpu.core.exceptions import (
    DefinitionNotExistError,
    SiddhiAppCreationError,
)
from siddhi_tpu.core.query import QueryRuntime
from siddhi_tpu.multiplex.fingerprint import query_fingerprint, reads_clock
from siddhi_tpu.multiplex.registry import registry_for
from siddhi_tpu.query_api import (
    Attribute,
    Query,
    SingleInputStream,
    StreamDefinition,
    WindowHandler,
)

log = logging.getLogger("siddhi_tpu")

_TUMBLING_WINDOWS = ("lengthBatch", "timeBatch")


class MultiplexPlanner:
    """Attempts multiplex placement for one query; ``None`` = fall back."""

    def __init__(self, qp):
        self.qp = qp  # the owning QueryPlanner
        self.app = qp.app
        self.ctx = qp.app.app_context

    # -- shared helpers ------------------------------------------------------

    def _fallback(self, name: str, reason: str) -> None:
        sm = self.ctx.statistics_manager
        if sm is not None:
            sm.record_multiplex_fallback(name, reason)
        # WARN, not info: @app:multiplex was requested and this query is
        # not getting it — same visibility contract as every other
        # planner fallback
        log.warning("query '%s': multiplex ineligible (%s); dedicated "
                    "engine used", name, reason)
        return None

    def _common_reject(self, query: Query, name: str) -> Optional[str]:
        """Eligibility conditions shared by both engine families."""
        if self.ctx.tpu_devices:
            if self.ctx.multiplex:
                # pinned @app:multiplex losing to the pinned mesh is a
                # plan CONFLICT (precedence: shard > multiplex), counted
                # separately from ordinary shape ineligibility
                sm = self.ctx.statistics_manager
                if sm is not None:
                    sm.record_planner_conflict(
                        name, "@app:multiplex pinned but the app declares "
                        "a device mesh (precedence: shard > multiplex)")
            return "mesh-sharded state does not multiplex"
        if query.output_rate is not None:
            return "output rate limits need a dedicated engine"
        out = query.output_stream
        if out is not None and getattr(out, "event_type", "current") != "current":
            return "multiplexed engines emit CURRENT events only"
        clock_fn = reads_clock(query)
        if clock_fn is not None:
            # these compile against the engine's relative-time anchor,
            # which a shared group re-bases across tenants
            return f"{clock_fn}() reads the engine clock anchor"
        return None

    # -- tumbling windowed aggregates ---------------------------------------

    def try_single(self, query: Query, name: str,
                   s: SingleInputStream) -> Optional[QueryRuntime]:
        """Seat a tumbling windowed-aggregate query in a shared
        :class:`~siddhi_tpu.multiplex.tumbling_group.TumblingMultiplexGroup`;
        ``None`` (with a counted reason) falls back to the dedicated
        ``_plan_device_single`` / host path."""
        from siddhi_tpu.multiplex.tumbling_group import TumblingMultiplexGroup
        from siddhi_tpu.ops.device_query import DeviceQueryEngine

        reason = self._common_reject(query, name)
        if reason is not None:
            return self._fallback(name, reason)
        if not (s.is_inner or s.is_fault):
            if s.stream_id in self.app.named_windows:
                return self._fallback(
                    name, "named-window inputs need CURRENT+EXPIRED "
                    "semantics")
            if s.stream_id in self.app.tables or s.stream_id in getattr(
                    self.app, "aggregations", {}):
                return self._fallback(
                    name, "table/aggregation inputs need the host planner")
        window = next((h for h in s.handlers
                       if isinstance(h, WindowHandler)), None)
        if window is None or window.name not in _TUMBLING_WINDOWS or (
                window.namespace or "") != "":
            return self._fallback(
                name, "only tumbling lengthBatch/timeBatch windows "
                "multiplex")

        definition = self.app.resolve_stream_definition(s)
        slots = int(self.ctx.multiplex_slots)
        fp = query_fingerprint(
            query, [definition],
            {"family": "tumbling",
             "n_groups": self.ctx.tpu_partitions,
             "slots": slots})

        def factory():
            engine = DeviceQueryEngine(
                query, definition,
                n_groups=self.ctx.tpu_partitions,
                partition_mode=False,
                defer_order_by=True,
            )
            if engine.kind != "tumbling":
                raise SiddhiAppCreationError(
                    "engine lowered to a non-tumbling form")
            if engine.bare_attrs:
                raise SiddhiAppCreationError(
                    "bare select attributes need the dedicated engine's "
                    "last-row bookkeeping")
            return TumblingMultiplexGroup(engine, slots)

        registry = registry_for(self.ctx.siddhi_context)
        try:
            group, slot = registry.acquire(fp, factory)
        except SiddhiAppCreationError as e:
            return self._fallback(name, str(e))
        try:
            return self._wire_single(query, name, s, group, slot, registry)
        except BaseException:
            registry.release(group, slot)
            raise

    def _wire_single(self, query: Query, name: str, s: SingleInputStream,
                     group, slot: int, registry) -> QueryRuntime:
        from siddhi_tpu.core.device_single import _DeviceQueryReceiver
        from siddhi_tpu.multiplex.tumbling_group import MultiplexTenantRuntime

        engine = group.engine
        out_target = getattr(query.output_stream, "target", None) or f"__ret_{name}"
        out_attrs = [
            Attribute(nm, t)
            for nm, t in zip(engine.output_names, engine.out_types)
        ]
        selector = self.qp._passthrough_selector(
            query.selector, engine.output_names, out_target)
        out_def = StreamDefinition(id=out_target, attributes=out_attrs)
        output = self.qp._plan_output(query, out_def)
        rate_limiter = self.qp._plan_rate_limiter(query)
        qr = QueryRuntime(
            name, [[]], selector, rate_limiter, output, self.ctx)
        runtime = MultiplexTenantRuntime(
            group, slot, f"#device_{name}",
            emit=lambda b: qr.process(b, 0),
            clock=self.ctx.timestamp_generator.current_time,
            faults=self.ctx.fault_injector,
            registry=registry,
            listeners=self.ctx.exception_listeners)
        qr.device_runtime = runtime
        junction = self.app.junction_for_input(s)
        junction.subscribe(_DeviceQueryReceiver(runtime))
        # registered LAST (same contract as the dedicated paths): nothing
        # below may raise, so fallbacks never leak a live scheduler task
        self.app.scheduler.register_task(runtime)
        qr.lowered_to = "multiplex"
        self._record_placement(name, group)
        return qr

    # -- dense patterns ------------------------------------------------------

    def try_state(self, query: Query, name: str, st) -> Optional[QueryRuntime]:
        """Seat an unpartitioned non-aggregating pattern query in a shared
        :class:`~siddhi_tpu.multiplex.dense_group.DenseMultiplexGroup`
        (one partition row per tenant); ``None`` falls back to the
        dedicated ``_plan_dense_state`` / host path."""
        from siddhi_tpu.core.dense_pattern import (
            build_dense_engine,
            output_attr_types,
        )
        from siddhi_tpu.multiplex.dense_group import DenseMultiplexGroup

        reason = self._common_reject(query, name)
        if reason is not None:
            return self._fallback(name, reason)
        sel = query.selector
        if sel.group_by or sel.having is not None or \
                self.qp._has_aggregators(sel):
            return self._fallback(
                name, "aggregating pattern selectors keep per-query host "
                "state")
        defs: List[StreamDefinition] = []
        for sid in sorted(set(st.stream_ids())):
            d = self.app.definitions.get(sid)
            if d is None:
                return self._fallback(
                    name, f"input stream '{sid}' has no groupable "
                    "definition")
            defs.append(d)

        slots = int(self.ctx.multiplex_slots)
        fp = query_fingerprint(
            query, defs,
            {"family": "dense",
             "instances": self.ctx.tpu_instances,
             "slots": slots})

        def factory():
            # one partition row per tenant seat: the dedicated path runs
            # unpartitioned patterns with n_partitions=1, so row t is the
            # bit-identical single-row automaton of tenant t
            engine = build_dense_engine(
                query, st, self.app.resolve_stream_definition,
                n_partitions=slots,
                n_instances=self.ctx.tpu_instances)
            if getattr(engine, "has_deadlines", False):
                raise SiddhiAppCreationError(
                    "absent-pattern deadlines need per-query timers")
            return DenseMultiplexGroup(
                engine, [t.np_dtype for t in output_attr_types(engine)],
                slots)

        registry = registry_for(self.ctx.siddhi_context)
        try:
            group, slot = registry.acquire(fp, factory)
        except SiddhiAppCreationError as e:
            return self._fallback(name, str(e))
        try:
            return self._wire_state(query, name, group, slot, registry)
        except BaseException:
            registry.release(group, slot)
            raise

    def _wire_state(self, query: Query, name: str, group, slot: int,
                    registry) -> QueryRuntime:
        from siddhi_tpu.core.dense_pattern import (
            _DenseStreamReceiver,
            output_attr_types,
        )
        from siddhi_tpu.multiplex.dense_group import DenseMultiplexTenantRuntime

        engine = group.engine
        out_target = getattr(query.output_stream, "target", None) or f"__ret_{name}"
        out_names = engine.output_names
        out_attrs = [
            Attribute(nm, t)
            for nm, t in zip(out_names, output_attr_types(engine))
        ]
        selector = self.qp._passthrough_selector(
            query.selector, out_names, out_target)
        out_def = StreamDefinition(id=out_target, attributes=out_attrs)
        output = self.qp._plan_output(query, out_def)
        rate_limiter = self.qp._plan_rate_limiter(query)
        qr = QueryRuntime(
            name, [[]], selector, rate_limiter, output, self.ctx)
        runtime = DenseMultiplexTenantRuntime(
            group, slot, f"#matches_{name}",
            emit=lambda b: qr.process(b, 0),
            clock=self.ctx.timestamp_generator.current_time,
            faults=self.ctx.fault_injector,
            registry=registry,
            listeners=self.ctx.exception_listeners)
        qr.pattern_processor = runtime
        for sk in engine.stream_keys:
            junction = self.app.junctions.get(sk)
            if junction is None:
                raise DefinitionNotExistError(
                    f"stream '{sk}' is not defined")
            junction.subscribe(_DenseStreamReceiver(runtime, sk))
        self.app.scheduler.register_task(runtime)
        qr.lowered_to = "multiplex"
        self._record_placement(name, group)
        return qr

    def _record_placement(self, name: str, group) -> None:
        sm = self.ctx.statistics_manager
        if sm is not None and hasattr(sm, "record_multiplex_placement"):
            sm.record_multiplex_placement(
                name, getattr(group, "fingerprint", ""),
                group.occupied_count())
        log.info(
            "query '%s': multiplexed into shared engine %s (%d/%d seats)",
            name, getattr(group, "fingerprint", "?")[:12],
            group.occupied_count(), group.slots)
