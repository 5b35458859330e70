"""Query planner: Query AST -> QueryRuntime.

The analog of the reference QueryParser.parse (util/parser/QueryParser.java:90)
+ SingleInputStreamParser + SelectorParser + OutputParser, producing
columnar processors instead of per-event executor chains.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Tuple

from siddhi_tpu.core.exceptions import (
    DefinitionNotExistError,
    SiddhiAppCreationError,
)
from siddhi_tpu.core.query import (
    AggBinding,
    EventRateLimiter,
    GroupByEventRateLimiter,
    GroupByTimeRateLimiter,
    FilterProcessor,
    InsertIntoStreamCallback,
    PassThroughRateLimiter,
    ProcessStreamReceiver,
    QueryCallbackOutput,
    QueryRuntime,
    QuerySelector,
    SelectItem,
    SnapshotRateLimiter,
    TimeRateLimiter,
    WindowChainProcessor,
)
from siddhi_tpu.extension.validator import validate_extension_args
from siddhi_tpu.ops.aggregators import make_aggregator
from siddhi_tpu.planner.expr import (
    AGGREGATOR_NAMES,
    CompiledExpression,
    ExpressionCompiler,
    Scope,
)
from siddhi_tpu.query_api import (
    Annotation,
    ArithmeticOp,
    AndOp,
    Attribute,
    AttrType,
    CompareOp,
    Constant,
    Expression,
    Filter,
    FunctionCall,
    InOp,
    InsertIntoStream,
    IsNull,
    NotOp,
    OrOp,
    OutputAttribute,
    Query,
    ReturnStream,
    Selector,
    SingleInputStream,
    StreamDefinition,
    StreamFunction,
    Variable,
    WindowHandler,
)
from siddhi_tpu.query_api.annotation import find_annotation

_query_counter = itertools.count()


class _RateLimiterTask:
    """Scheduler task flushing time-based rate limiters.

    ``device_runtime`` (device-lowered queries): the query's device
    runtime — its pending-emit queue drains BEFORE the limiter's time
    decision, so queued matches land in the limiter in the same order
    the synchronous path would deliver them (async emit pipeline flush
    barrier)."""

    def __init__(self, qr, limiter, device_runtime=None):
        self.qr = qr
        self.limiter = limiter
        self.device_runtime = device_runtime

    def next_wakeup(self):
        return self.limiter.next_wakeup()

    def fire(self, now: int):
        if self.device_runtime is not None:
            self.device_runtime.drain()
        out = self.limiter.on_time(now)
        if out is not None and len(out):
            self.qr.output.send(out, now)


class _PatternStreamReceiver:
    """Junction subscriber feeding one source stream into the NFA
    (the Pattern/SequenceSingleProcessStreamReceiver analog)."""

    def __init__(self, processor, stream_key: str):
        self.processor = processor
        self.stream_key = stream_key

    def receive(self, batch):
        self.processor.process_stream_batch(self.stream_key, batch)


class AggregatorRewrite:
    """Walks a select expression, replacing aggregator calls with synthetic
    variables bound to aggregation outputs (the reference instead builds
    AttributeAggregatorExecutors inline in SelectorParser)."""

    def __init__(self, scope: Scope, compiler: ExpressionCompiler,
                 extensions=None):
        self.scope = scope
        self.compiler = compiler
        self.extensions = extensions
        self.bindings: List[AggBinding] = []

    def rewrite(self, expr: Expression) -> Expression:
        if isinstance(expr, FunctionCall):
            is_builtin = (expr.namespace is None
                          and expr.name in AGGREGATOR_NAMES)
            ext = None
            if not is_builtin and self.extensions is not None:
                # custom AttributeAggregatorExecutor analogs registered
                # via setExtension(..., kind='aggregator') (reference:
                # util/extension/holder/AttributeAggregatorExtensionHolder)
                ext = self.extensions.lookup(
                    "aggregator", expr.name, expr.namespace)
            if is_builtin or ext is not None:
                key = f"__agg_{len(self.bindings)}"
                arg: Optional[CompiledExpression] = None
                if expr.args:
                    if len(expr.args) > 1:
                        raise SiddhiAppCreationError(f"aggregator '{expr.name}' takes one argument")
                    arg = self.compiler.compile(self.rewrite(expr.args[0]))
                elif is_builtin and expr.name not in ("count",) and not expr.star:
                    raise SiddhiAppCreationError(f"aggregator '{expr.name}' needs an argument")
                if ext is not None:
                    import inspect

                    try:
                        params = [
                            p for p in
                            inspect.signature(ext).parameters.values()
                            if p.kind in (p.POSITIONAL_ONLY,
                                          p.POSITIONAL_OR_KEYWORD)
                        ]
                        takes_arg = len(params) >= 1
                    except (TypeError, ValueError):
                        takes_arg = True
                    executor = (ext(arg.type if arg is not None else None)
                                if takes_arg else ext())
                else:
                    executor = make_aggregator(expr.name, arg.type if arg is not None else None)
                self.bindings.append(AggBinding(key, executor, arg))
                self.scope.add_bare(key, executor.return_type)
                return Variable(attribute=key)
        if isinstance(expr, ArithmeticOp):
            return ArithmeticOp(expr.op, self.rewrite(expr.left), self.rewrite(expr.right))
        if isinstance(expr, CompareOp):
            return CompareOp(expr.op, self.rewrite(expr.left), self.rewrite(expr.right))
        if isinstance(expr, AndOp):
            return AndOp(self.rewrite(expr.left), self.rewrite(expr.right))
        if isinstance(expr, OrOp):
            return OrOp(self.rewrite(expr.left), self.rewrite(expr.right))
        if isinstance(expr, NotOp):
            return NotOp(self.rewrite(expr.expr))
        if isinstance(expr, IsNull):
            return IsNull(self.rewrite(expr.expr))
        if isinstance(expr, InOp):
            return InOp(self.rewrite(expr.expr), expr.source_id)
        if isinstance(expr, FunctionCall):
            return FunctionCall(
                expr.namespace, expr.name, tuple(self.rewrite(a) for a in expr.args), expr.star
            )
        return expr


def scope_for_definition(definition: StreamDefinition, stream_ref: str) -> Scope:
    scope = Scope()
    for a in definition.attributes:
        scope.add(stream_ref, a.name, a.name, a.type)
    return scope


class QueryPlanner:
    """Plans one query against the app's junction/definition registry."""

    def __init__(self, app_planner):
        self.app = app_planner  # AppPlanner
        # the PlanRecord for the query currently inside plan_query();
        # _want() consults it so the cost model's pick steers the same
        # gate sites the legacy annotations do
        self._active_record = None

    def _passthrough_selector(self, sel: Selector, out_names: List[str],
                              out_target: str) -> QuerySelector:
        """Column-passthrough selector applying only the query's
        order by / limit / offset over each chunk — the host tail of a
        device-lowered query (dense or device-single)."""
        order_by = []
        for ob in sel.order_by:
            if ob.variable.attribute not in out_names:
                raise SiddhiAppCreationError(
                    f"order by attribute '{ob.variable.attribute}' not "
                    "in select output")
            order_by.append((ob.variable.attribute, ob.ascending))
        const_compiler = ExpressionCompiler(Scope())
        limit = self._const_int(sel.limit, const_compiler, "limit")
        offset = self._const_int(sel.offset, const_compiler, "offset")
        return QuerySelector(
            out_target, None, out_names, [], [], None, order_by, limit,
            offset,
        )

    def plan_query(self, query: Query, query_index: int) -> QueryRuntime:
        """Unified lowering entry: build the query's PlanRecord (cost
        candidates + pick), plan through the existing per-kind paths
        with the record steering the fast-path gates, then pin the
        realized lowering back onto the record for /siddhi-plan.

        In legacy (annotation-only) mode the record is informational —
        _want() keeps reading the annotation flags, so annotated apps
        lower exactly as before."""
        info = find_annotation(query.annotations, "info")
        name = (info.element("name") if info else None) \
            or f"query_{query_index}"
        from siddhi_tpu.planner.costmodel import build_plan_record

        record = build_plan_record(self.app, query, name)
        self._active_record = record
        try:
            qr = self.plan(query, query_index)
        finally:
            self._active_record = None
        record.actual = getattr(qr, "lowered_to", "host")
        sm = self.app.app_context.statistics_manager
        if sm is not None:
            sm.register_plan(name, record)
        return qr

    def _host_pinned(self) -> bool:
        """An explicit pin (replan override) naming 'host' disables the
        device fast-path gates entirely — the only way a tpu app drops a
        query back to the host chain on purpose."""
        rec = self._active_record
        return rec is not None and rec.mode == "pinned" \
            and rec.chosen == "host"

    def _want(self, path: str, name: str) -> bool:
        """Does this query want fast path ``path`` ('multiplex' |
        'hotkey') at its gate site?  Pin precedence: a replan pin names
        the exact composed path; else the legacy annotation; else — in
        auto mode — the cost model's pick.  The real eligibility gate
        of the path still runs after a True."""
        ctx = self.app.app_context
        pin = (getattr(ctx, "plan_pins", None) or {}).get(name)
        if pin is not None:
            return path in str(pin).split("+")
        if path == "multiplex" and ctx.multiplex:
            return True
        if path == "hotkey" and ctx.hotkeys:
            return True
        if path == "shard" and ctx.tpu_devices:
            # legacy: a declared mesh IS the shard pin
            return True
        if getattr(ctx, "plan_auto", False):
            rec = self._active_record
            if rec is None:
                # partition-instance planning bypasses plan_query(); the
                # hotkey router self-gates (promotion needs observed
                # skew) so auto mode opts partitioned dense state in
                return path == "hotkey"
            return path in rec.components()
        return False

    def plan(self, query: Query, query_index: int) -> QueryRuntime:
        info = find_annotation(query.annotations, "info")
        name = (info.element("name") if info else None) or f"query_{query_index}"

        in_stream = query.input_stream
        if isinstance(in_stream, SingleInputStream):
            return self._plan_single(query, name, in_stream)
        from siddhi_tpu.query_api import JoinInputStream, StateInputStream

        if isinstance(in_stream, StateInputStream):
            return self._plan_state(query, name, in_stream)
        if isinstance(in_stream, JoinInputStream):
            return self._plan_join(query, name, in_stream)
        raise SiddhiAppCreationError(
            f"query '{name}': input type {type(in_stream).__name__} not supported yet"
        )

    # -- join ----------------------------------------------------------------

    def _plan_join(self, query: Query, name: str, j) -> QueryRuntime:
        from siddhi_tpu.core.join import JoinRuntime, JoinSide, JoinStreamReceiver
        from siddhi_tpu.query_api import JoinInputStream

        sides = []
        batch_mode = False
        for s in (j.left, j.right):
            if not isinstance(s, SingleInputStream):
                raise SiddhiAppCreationError(
                    f"query '{name}': join side {type(s).__name__} not supported"
                )
            table = self.app.tables.get(s.stream_id)
            ref = s.alias or s.stream_id
            aggregation = getattr(self.app, "aggregations", {}).get(s.stream_id)
            if aggregation is not None:
                if s.handlers:
                    raise SiddhiAppCreationError(
                        f"query '{name}': aggregation '{s.stream_id}' cannot take "
                        "filters/windows in a join"
                    )
                sides.append(
                    JoinSide(
                        ref, aggregation.output_definition, [], None,
                        aggregation=aggregation, triggers=False,
                    )
                )
                continue
            if table is not None:
                if s.handlers:
                    raise SiddhiAppCreationError(
                        f"query '{name}': table '{s.stream_id}' cannot take "
                        "filters/windows in a join"
                    )
                sides.append(
                    JoinSide(ref, table.definition, [], None, table=table, triggers=False)
                )
                continue
            definition = self.app.resolve_stream_definition(s)
            # side-local scope: handler expressions see bare side attrs
            side_scope = scope_for_definition(definition, ref)
            side_compiler = ExpressionCompiler(side_scope, functions=self.app.functions, table_resolver=self.app.table_resolver)
            chain, b_mode, windows, _extra = self._plan_handlers(s, definition, side_compiler)
            batch_mode = batch_mode or b_mode
            window = None
            filters = []
            for p in chain:
                if isinstance(p, WindowChainProcessor):
                    if window is not None:
                        raise SiddhiAppCreationError(
                            f"query '{name}': one window per join side"
                        )
                    window = p.window
                else:
                    filters.append(p)
            nw = (
                self.app.named_windows.get(s.stream_id)
                if not (s.is_inner or s.is_fault)
                else None
            )
            if window is None and nw is not None:
                sides.append(
                    JoinSide(ref, definition, filters, None, named_window=nw)
                )
            else:
                sides.append(JoinSide(ref, definition, filters, window))
        left, right = sides
        if left.ref == right.ref:
            raise SiddhiAppCreationError(
                f"query '{name}': join sides need distinct names/aliases"
            )
        if left.table is not None and right.table is not None:
            raise SiddhiAppCreationError(
                f"query '{name}': cannot join two tables in a stream query"
            )

        # unidirectional trigger
        if j.trigger == "left":
            right.triggers = False
        elif j.trigger == "right":
            left.triggers = False

        # an outer join can only preserve a side that triggers — otherwise
        # unmatched rows of that side would silently never be emitted
        preserve_left = j.join_type in (JoinInputStream.LEFT_OUTER, JoinInputStream.FULL_OUTER)
        preserve_right = j.join_type in (JoinInputStream.RIGHT_OUTER, JoinInputStream.FULL_OUTER)
        if (preserve_left and not left.triggers) or (preserve_right and not right.triggers):
            raise SiddhiAppCreationError(
                f"query '{name}': outer join preserves a side that never "
                "triggers (table side or disabled by 'unidirectional')"
            )

        # join scope: qualified by ref (and by raw stream id when unambiguous)
        scope = Scope()
        for side, src in ((left, j.left), (right, j.right)):
            for a in side.definition.attributes:
                scope.add(side.ref, a.name, side.qualified_key(a.name), a.type)
            if src.stream_id != side.ref:
                scope.add_alias(src.stream_id, side.ref)
        compiler = ExpressionCompiler(scope, functions=self.app.functions, table_resolver=self.app.table_resolver)
        condition = compiler.compile(j.on_condition) if j.on_condition is not None else None
        if condition is not None and condition.type != AttrType.BOOL:
            raise SiddhiAppCreationError(f"query '{name}': 'on' condition must be boolean")

        # aggregation joins: compile `within`/`per` against the join scope so
        # they may reference the probing stream's attributes
        for side in sides:
            if side.aggregation is None:
                continue
            if getattr(j, "per", None) is None:
                raise SiddhiAppCreationError(
                    f"query '{name}': join with aggregation "
                    f"'{side.aggregation.name}' requires a 'per' clause"
                )
            side.agg_per = compiler.compile(j.per)
            w = getattr(j, "within", None)
            if w is not None:
                if isinstance(w, tuple):
                    side.agg_within = (compiler.compile(w[0]), compiler.compile(w[1]))
                else:
                    side.agg_within = (compiler.compile(w), None)

        selector, out_def = self._plan_selector(
            query.selector, scope, compiler, name, query, batch_mode,
            star_sources=[left, right],
        )
        output = self._plan_output(query, out_def, qname=name)
        rate_limiter = self._plan_rate_limiter(query)
        qr = QueryRuntime(name, [[]], selector, rate_limiter, output, self.app.app_context)
        if rate_limiter.needs_scheduler_task:
            self.app.scheduler.register_task(_RateLimiterTask(qr, rate_limiter))

        jr = JoinRuntime(
            left, right, j.join_type, condition,
            emit=lambda batch, now: qr.process(batch, 0),
            out_stream_id=f"#join_{name}",
        )
        qr.join_runtime = jr
        # @app:devtables: an inner join against a DeviceTable side lowers
        # to the slot-addressed device probe (devtable/join.py) — the
        # stream side subscribes the devtable receiver INSTEAD of the
        # host JoinStreamReceiver, so matched pairs never materialize on
        # the host between ingest and emit
        devtable_runtime = None
        if self.app.app_context.devtables and (
                left.table is not None or right.table is not None):
            import logging

            from siddhi_tpu.devtable import (
                DeviceTable,
                DevTableJoinReceiver,
                try_plan_devtable_join,
            )

            if isinstance(left.table, DeviceTable) or \
                    isinstance(right.table, DeviceTable):
                try:
                    devtable_runtime = try_plan_devtable_join(
                        name, j, left, right, condition, compiler,
                        emit=lambda batch: qr.process(batch, 0),
                        app_context=self.app.app_context)
                    qr.device_runtime = devtable_runtime
                    qr.lowered_to = "devtable"
                    sm = self.app.app_context.statistics_manager
                    if sm is not None:
                        sm.register_devtable_join(name, devtable_runtime)
                    logging.getLogger("siddhi_tpu").info(
                        "query '%s': stream-table join lowered to the "
                        "device-resident table probe", name)
                except SiddhiAppCreationError as e:
                    logging.getLogger("siddhi_tpu").warning(
                        "query '%s': devtable join unavailable (%s); "
                        "host join path used", name, e)
                    sm = self.app.app_context.statistics_manager
                    if sm is not None:
                        sm.record_devtable_fallback(name, str(e))
        if devtable_runtime is not None:
            for side, src in ((left, j.left), (right, j.right)):
                if side.table is not None or side.aggregation is not None:
                    continue
                junction = self.app.junction_for_input(src)
                junction.subscribe(DevTableJoinReceiver(devtable_runtime))
            return qr
        # @app:execution('tpu'): run the O(B*W) cross-product condition
        # as a jitted device kernel (buffering/expiry/materialization
        # keep the host runtime's exact semantics — SURVEY §7 step 7's
        # masked in-batch cross products)
        if (self.app.app_context.execution_mode == "tpu"
                and condition is not None):
            import logging

            from siddhi_tpu.core.join import DeviceJoinProbe

            try:
                jr.device_probe = DeviceJoinProbe(condition, left, right)
                qr.lowered_to = "device_probe"
                logging.getLogger("siddhi_tpu").info(
                    "query '%s': join condition lowered to the jitted "
                    "device probe", name)
            except SiddhiAppCreationError as e:
                logging.getLogger("siddhi_tpu").warning(
                    "query '%s': join device probe unavailable (%s); "
                    "numpy probe used", name, e)
                sm = self.app.app_context.statistics_manager
                if sm is not None:
                    sm.record_device_fallback(name, f"join probe: {e}")
        if any(s.window is not None and getattr(s.window, "needs_scheduler", False) for s in sides):
            self.app.scheduler.register_task(jr)
        for side, src, is_left in ((left, j.left, True), (right, j.right, False)):
            if side.table is not None or side.aggregation is not None:
                continue
            junction = self.app.junction_for_input(src)
            junction.subscribe(JoinStreamReceiver(jr, is_left, self.app.app_context))
        return qr

    # -- pattern / sequence --------------------------------------------------

    def _plan_state(self, query: Query, name: str, st) -> QueryRuntime:
        from siddhi_tpu.ops.nfa import (
            NFABuilder,
            PatternProcessor,
            PatternScope,
            _collect_presence,
        )

        # @app:execution('tpu'): attempt the jitted dense-NFA path first
        # (reference analog: StateInputStreamParser wiring the pattern hot
        # path, StateInputStreamParser.java:76-146); host fallback below
        if (
            self.app.app_context.execution_mode == "tpu"
            and not getattr(self.app, "in_partition_instance", False)
            and not self._host_pinned()
        ):
            import logging

            # @app:multiplex (or the cost model's pick): try seating the
            # pattern in a manager-wide shared dense engine first;
            # ineligibility is counted (multiplexFallbackReason) and
            # falls through to the dedicated dense path below
            if self._want("multiplex", name):
                from siddhi_tpu.multiplex.planner import MultiplexPlanner

                qr = MultiplexPlanner(self).try_state(query, name, st)
                if qr is not None:
                    return qr
            try:
                qr = self._plan_dense_state(query, name, st)
                logging.getLogger("siddhi_tpu").info(
                    "query '%s': pattern lowered to the dense TPU path", name)
                return qr
            except SiddhiAppCreationError as e:
                # WARN: the user asked for execution('tpu') and is
                # getting host execution — must be visible
                logging.getLogger("siddhi_tpu").warning(
                    "query '%s': dense TPU path unavailable (%s); "
                    "using host pattern engine", name, e)
                sm = self.app.app_context.statistics_manager
                if sm is not None:
                    sm.record_device_fallback(name, f"dense pattern: {e}")

        builder = NFABuilder(st, self.app.resolve_stream_definition)
        nodes = builder.build()

        # selector scope over event refs; bare attrs resolve when unambiguous
        scope = PatternScope(builder.ref_defs, builder.stream_to_ref, cand_def=None)
        compiler = ExpressionCompiler(scope, functions=self.app.functions, table_resolver=self.app.table_resolver)
        selector, out_def = self._plan_selector(
            query.selector, scope, compiler, name, query, batch_mode=False
        )
        output = self._plan_output(query, out_def, qname=name)
        rate_limiter = self._plan_rate_limiter(query)
        qr = QueryRuntime(name, [[]], selector, rate_limiter, output, self.app.app_context)
        if rate_limiter.needs_scheduler_task:
            self.app.scheduler.register_task(_RateLimiterTask(qr, rate_limiter))

        # presence keys used anywhere in the selector expressions
        presence = {}
        sel = query.selector
        exprs = []
        if sel.selection:
            exprs.extend(oa.expression for oa in sel.selection)
        if sel.having is not None:
            exprs.append(sel.having)
        for e in exprs:
            presence.update(_collect_presence(e, builder.ref_defs, builder.stream_to_ref))

        processor = PatternProcessor(
            nodes=nodes,
            mode=st.type,
            within_ms=st.within_ms,
            ref_defs=builder.ref_defs,
            output_keys=dict(scope.used_captures),
            presence_keys=presence,
            emit=lambda batch: qr.process(batch, 0),
            out_stream_id=f"#matches_{name}",
        )
        qr.pattern_processor = processor
        self.app.scheduler.register_task(processor)

        # subscribe one receiver per distinct source junction
        seen = set()
        for node in nodes:
            for spec in node.specs:
                if spec.stream_key in seen:
                    continue
                seen.add(spec.stream_key)
                junction = self.app.junctions.get(spec.stream_key)
                if junction is None:
                    raise DefinitionNotExistError(
                        f"stream '{spec.stream_key}' is not defined"
                    )
                junction.subscribe(_PatternStreamReceiver(processor, spec.stream_key))
        return qr

    def _plan_dense_state(
        self, query: Query, name: str, st, key_fn=None,
        n_partitions: Optional[int] = None, subscribe: bool = True,
    ) -> QueryRuntime:
        """Plan a pattern query onto the dense jitted engine; raises
        SiddhiAppCreationError when the query is outside the dense
        subset (caller falls back to the host engine).

        ``key_fn``/``n_partitions`` come from the partitioned form
        (one engine, interned keys); ``subscribe=False`` lets the
        partition runtime do its own key-routed wiring."""
        from siddhi_tpu.core.dense_pattern import (
            DensePatternRuntime,
            _DenseStreamReceiver,
            build_dense_engine,
            output_attr_types,
        )

        if n_partitions is None:
            n_partitions = 1 if key_fn is None else self.app.app_context.tpu_partitions
        partitioned = key_fn is not None or n_partitions > 1
        if partitioned and query.output_rate is not None:
            # the host partitioned form gives each key instance its OWN
            # rate limiter; one shared limiter would pool emission
            # windows across keys
            raise SiddhiAppCreationError(
                "dense path: partitioned queries with output rate limits "
                "need per-key limiters — host instances used")

        # @app:execution('tpu', devices='N'): shard the partition axis
        # over an N-device mesh (BASELINE config 5's scale-out form);
        # pointless for single-partition queries.  Known before the
        # engine is built: a sharded state's rows have a shape of
        # their own (ops/dense_layout.py)
        mesh = None
        nd = self.app.app_context.tpu_devices
        if nd and n_partitions > 1 and self._want("shard", name):
            mesh = self.app.tpu_mesh

        sel = query.selector
        aggregating = bool(sel.group_by) or sel.having is not None \
            or self._has_aggregators(sel)
        if aggregating:
            # aggregating-selector form: the dense engine emits the RAW
            # captured columns (keyed exactly like the host pattern
            # scope, e.g. "e1.amount") and the ordinary host
            # QuerySelector aggregates/groups/filters the match rows —
            # matches are sparse, so selector cost is negligible next to
            # the jitted NFA step (reference analog: QuerySelector over
            # StateEvent chunks, QuerySelector.java:76-99)
            if partitioned and (sel.order_by or sel.limit is not None
                                or sel.offset is not None):
                # order-by/limit slice each output chunk; dense chunks
                # mix partition keys, which would slice ACROSS keys —
                # the host form slices per key instance
                raise SiddhiAppCreationError(
                    "dense path: partitioned aggregating selectors with "
                    "order by/limit need per-key chunks — host "
                    "instances used")
            from siddhi_tpu.ops.nfa import NFABuilder, PatternScope

            builder = NFABuilder(st, self.app.resolve_stream_definition)
            builder.build()
            scope = PatternScope(builder.ref_defs, builder.stream_to_ref,
                                 cand_def=None)
            compiler = ExpressionCompiler(
                scope, functions=self.app.functions,
                table_resolver=self.app.table_resolver)
            selector, out_def = self._plan_selector(
                query.selector, scope, compiler, name, query, batch_mode=False
            )
            select_vars = [
                Variable(stream_id=ref, attribute=attr, stream_index=idx)
                for _key, (ref, idx, attr, _t) in scope.used_captures.items()
            ]
            select_names = list(scope.used_captures.keys())
            engine = build_dense_engine(
                query, st, self.app.resolve_stream_definition, n_partitions,
                n_instances=self.app.app_context.tpu_instances,
                select_override=(select_vars, select_names),
                builder=builder, mesh=mesh)
            if partitioned:
                # ONE shared selector keeps per-(key, group) state via
                # the partition-key side channel on match rows (timer
                # matches map engine rows back through the runtime's
                # reverse row->key map)
                selector.partition_axis = True
        else:
            engine = build_dense_engine(
                query, st, self.app.resolve_stream_definition, n_partitions,
                n_instances=self.app.app_context.tpu_instances, mesh=mesh)

            out_target = getattr(query.output_stream, "target", None) or f"__ret_{name}"
            out_names = engine.output_names
            out_attrs = [
                Attribute(nm, t) for nm, t in zip(out_names, output_attr_types(engine))
            ]
            selector = self._passthrough_selector(sel, out_names, out_target)
            out_def = StreamDefinition(id=out_target, attributes=out_attrs)
        output = self._plan_output(query, out_def, qname=name)
        rate_limiter = self._plan_rate_limiter(query)
        qr = QueryRuntime(name, [[]], selector, rate_limiter, output, self.app.app_context)

        runtime = DensePatternRuntime(
            engine, f"#matches_{name}", emit=lambda b: qr.process(b, 0),
            key_fn=key_fn, mesh=mesh, app_context=self.app.app_context)
        if getattr(selector, "partition_axis", False):
            # idle-key purges must also drop the shared selector's
            # per-key aggregation state (host: the instance dies whole)
            runtime.on_purge_keys = selector.drop_partition_keys
        # @app:hotkeys: wrap eligible partitioned passthrough patterns
        # in the skew router (heavy keys ride the associative scan,
        # cold keys stay dense).  Mesh-sharded and aggregating forms
        # stay dense: the router's state handoff assumes single-device
        # rows and final-node-only selects.
        if (self._want("hotkey", name) and partitioned
                and key_fn is None and mesh is None and not aggregating):
            from siddhi_tpu.planner.hotkeys import try_wrap_hotkey

            wrapped = try_wrap_hotkey(self.app, st, runtime, name)
            if wrapped is not None:
                runtime = wrapped
        elif (self.app.app_context.hotkeys and partitioned
                and key_fn is None and mesh is not None and not aggregating):
            # pinned @app:hotkeys lost to the mesh pin: the router's
            # promote/demote state handoff assumes single-device
            # partition rows (precedence: shard > hotkeys) — count the
            # losing pin so the resolution is visible
            sm = self.app.app_context.statistics_manager
            if sm is not None:
                sm.record_planner_conflict(
                    name, "@app:hotkeys pinned but the partition axis is "
                    "mesh-sharded (precedence: shard > hotkeys)")
        qr.pattern_processor = runtime
        if subscribe:
            for sk in engine.stream_keys:
                junction = self.app.junctions.get(sk)
                if junction is None:
                    raise DefinitionNotExistError(f"stream '{sk}' is not defined")
                junction.subscribe(_DenseStreamReceiver(runtime, sk))
        # registered LAST: nothing above may raise afterwards, so a
        # fallback to the host path never leaks a live scheduler task;
        # the task handles are kept so multi-query callers (partition
        # lowering) can unregister if a LATER query fails eligibility
        if rate_limiter.needs_scheduler_task:
            task = _RateLimiterTask(qr, rate_limiter, device_runtime=runtime)
            qr._rate_task = task
            self.app.scheduler.register_task(task)
        if getattr(engine, "has_deadlines", False):
            # absent-node deadlines fire from the app scheduler (the
            # dense analog of registering the PatternProcessor's
            # on_time; reference: AbsentStreamPreStateProcessor's
            # scheduler arming)
            qr._dense_timer_task = runtime
            self.app.scheduler.register_task(runtime)
        qr.lowered_to = getattr(runtime, "lowered_to", "dense")
        return qr

    # -- single stream ------------------------------------------------------

    def _plan_single(self, query: Query, name: str, s: SingleInputStream) -> QueryRuntime:
        # @app:execution('tpu'): attempt the jitted device query path
        # first (reference analog: QueryParser wiring receiver ->
        # filter -> window -> selector, QueryParser.java:90); host
        # fallback below — same contract as the dense pattern gate
        if (
            self.app.app_context.execution_mode == "tpu"
            and not getattr(self.app, "in_partition_instance", False)
            and not self._host_pinned()
        ):
            import logging

            # @app:multiplex (or the cost model's pick): shared tumbling
            # engine attempt first, with counted fallback to the
            # dedicated device path
            if self._want("multiplex", name):
                from siddhi_tpu.multiplex.planner import MultiplexPlanner

                qr = MultiplexPlanner(self).try_single(query, name, s)
                if qr is not None:
                    return qr
            try:
                qr = self._plan_device_single(query, name, s)
                logging.getLogger("siddhi_tpu").info(
                    "query '%s': lowered to the jitted device query path",
                    name)
                return qr
            except SiddhiAppCreationError as e:
                # WARN: the user asked for execution('tpu') and is
                # getting host execution — must be visible
                logging.getLogger("siddhi_tpu").warning(
                    "query '%s': device query path unavailable (%s); "
                    "using host engine", name, e)
                sm = self.app.app_context.statistics_manager
                if sm is not None:
                    sm.record_device_fallback(name, f"device query: {e}")

        definition = self.app.resolve_stream_definition(s)
        ref = s.unique_id
        scope = scope_for_definition(definition, ref)
        if s.alias and s.alias != s.stream_id:
            scope.add_alias(s.stream_id, s.alias)
        compiler = ExpressionCompiler(scope, functions=self.app.functions, table_resolver=self.app.table_resolver)

        chain, batch_mode, windows, extra_attrs = self._plan_handlers(s, definition, compiler)
        selector, out_def = self._plan_selector(
            query.selector, scope, compiler, name, query, batch_mode,
            extra_attrs=extra_attrs,
        )
        output = self._plan_output(query, out_def, qname=name)
        rate_limiter = self._plan_rate_limiter(query)

        qr = QueryRuntime(name, [chain], selector, rate_limiter, output, self.app.app_context)
        for w in windows:
            if w.needs_scheduler:
                self.app.scheduler.register_window(qr, w)
        if rate_limiter.needs_scheduler_task:
            self.app.scheduler.register_task(_RateLimiterTask(qr, rate_limiter))
        junction = self.app.junction_for_input(s)
        junction.subscribe(ProcessStreamReceiver(qr))
        return qr

    def _plan_device_single(
        self, query: Query, name: str, s: SingleInputStream,
        partition_mode: bool = False, subscribe: bool = True,
    ) -> QueryRuntime:
        """Plan a single-stream query onto the jitted device engine;
        raises SiddhiAppCreationError when the query is outside the
        device subset (caller falls back to the host chain).

        ``partition_mode``/``subscribe=False`` come from the partitioned
        form (PartitionRuntime._plan_dense): the partition key arrives
        per batch from the partition receiver and composes into the
        engine's group axis — per-key state rows in device memory
        instead of per-key Python instances (reference semantics:
        partition/PartitionStreamReceiver.java:82-118 +
        util/snapshot/state/PartitionStateHolder.java:43)."""
        from siddhi_tpu.core.device_single import (
            DeviceQueryRuntime,
            _DeviceQueryReceiver,
        )
        from siddhi_tpu.ops.device_query import DeviceQueryEngine

        out = query.output_stream
        if out is not None and getattr(out, "event_type", "current") != "current":
            raise SiddhiAppCreationError(
                "device path emits CURRENT events only")
        # per-group first/last and snapshot rate limiters work: the
        # device runtime attaches the same group-key side channel the
        # host selector does (engine.last_group_keys -> batch.aux)
        if not (s.is_inner or s.is_fault):
            if s.stream_id in self.app.named_windows:
                raise SiddhiAppCreationError(
                    "named-window inputs need CURRENT+EXPIRED semantics")
            if s.stream_id in self.app.tables or s.stream_id in getattr(
                    self.app, "aggregations", {}):
                raise SiddhiAppCreationError(
                    "table/aggregation inputs need the host planner")

        if partition_mode and query.output_rate is not None:
            # the host partitioned form gives each key instance its OWN
            # rate limiter; one shared limiter would pool emission
            # windows across keys (same contract as the dense NFA gate)
            raise SiddhiAppCreationError(
                "partitioned queries with output rate limits need "
                "per-key limiters — host instances used")
        if partition_mode and (
                query.selector.order_by
                or query.selector.limit is not None
                or query.selector.offset is not None):
            # per-key instances slice order-by/limit PER KEY; a shared
            # chunk mixes keys and would slice across them
            raise SiddhiAppCreationError(
                "partitioned queries with order by/limit need per-key "
                "chunks — host instances used")
        definition = self.app.resolve_stream_definition(s)
        engine = DeviceQueryEngine(
            query, definition,
            n_groups=self.app.app_context.tpu_partitions,
            partition_mode=partition_mode,
            n_wgroups=(self.app.app_context.tpu_partitions
                       if partition_mode else None),
            defer_order_by=True,  # applied by the selector built below
        )
        # @app:execution('tpu', devices='N'): shard the query's windowed
        # state (group axis, key axis, or — for the global sliding ring —
        # the batch axis) over an N-device mesh; same treatment as
        # DensePatternRuntime's partition axis
        # chaos harness: the step hook reads engine.faults — set on the
        # BASE engine so the sharded wrapper's __getattr__ still sees it
        engine.faults = self.app.app_context.fault_injector
        nd = self.app.app_context.tpu_devices
        if nd and self._want("shard", name):
            from siddhi_tpu.parallel import ShardedDeviceQueryEngine

            import logging

            try:
                engine = ShardedDeviceQueryEngine(engine,
                                                  self.app.tpu_mesh)
                logging.getLogger("siddhi_tpu").info(
                    "query '%s': device %s state sharded over %d devices",
                    name, engine.engine.kind, nd)
            except SiddhiAppCreationError as e:
                # NOT silent: the mesh stays idle for this query, so log
                # the reason once and count it on the statistics feed
                # (Queries.<name>.shardedFallbacks, served over REST)
                logging.getLogger("siddhi_tpu").warning(
                    "query '%s': mesh sharding unavailable, running "
                    "single-device: %s", name, e)
                sm = self.app.app_context.statistics_manager
                if sm is not None:
                    sm.record_sharded_fallback(name, str(e))
        out_target = getattr(query.output_stream, "target", None) or f"__ret_{name}"
        out_attrs = [
            Attribute(nm, t)
            for nm, t in zip(engine.output_names, engine.out_types)
        ]
        # order by / limit / offset run host-side over each emitted
        # chunk (the host engine's per-chunk _order_limit position)
        selector = self._passthrough_selector(
            query.selector, engine.output_names, out_target)
        out_def = StreamDefinition(id=out_target, attributes=out_attrs)
        output = self._plan_output(query, out_def, qname=name)
        rate_limiter = self._plan_rate_limiter(query)
        qr = QueryRuntime(
            name, [[]], selector, rate_limiter, output, self.app.app_context)

        runtime = DeviceQueryRuntime(
            engine, f"#device_{name}", emit=lambda b: qr.process(b, 0),
            app_context=self.app.app_context)
        qr.device_runtime = runtime
        if subscribe:
            junction = self.app.junction_for_input(s)
            junction.subscribe(_DeviceQueryReceiver(runtime))
        # registered LAST: nothing below may raise, so a fallback to the
        # host path never leaks a live scheduler task.  Partition mode
        # registers nothing: tumbling panes (the only timer need) are
        # ineligible there, and the partition runtime owns purge timing.
        if not partition_mode:
            self.app.scheduler.register_task(runtime)
            if rate_limiter.needs_scheduler_task:
                task = _RateLimiterTask(qr, rate_limiter,
                                        device_runtime=runtime)
                qr._rate_task = task
                self.app.scheduler.register_task(task)
        qr.lowered_to = "device"
        return qr

    def _plan_rate_limiter(self, query: Query):
        from siddhi_tpu.query_api import (
            EventOutputRate,
            SnapshotOutputRate,
            TimeOutputRate,
        )

        r = query.output_rate
        if r is None:
            return PassThroughRateLimiter()
        if isinstance(r, EventOutputRate):
            if r.type in ("first", "last") and query.selector.group_by:
                return GroupByEventRateLimiter(r.events, r.type)
            return EventRateLimiter(r.events, r.type)
        if isinstance(r, TimeOutputRate):
            if r.type in ("first", "last") and query.selector.group_by:
                return GroupByTimeRateLimiter(r.value_ms, r.type)
            return TimeRateLimiter(r.value_ms, r.type)
        if isinstance(r, SnapshotOutputRate):
            group_names = [g.attribute for g in query.selector.group_by]
            return SnapshotRateLimiter(r.value_ms, group_names)
        raise SiddhiAppCreationError(f"unsupported output rate {r}")

    def _plan_handlers(self, s: SingleInputStream, definition, compiler):
        chain = []
        windows = []
        batch_mode = False
        extra_attrs = []  # schema-extending stream functions' outputs
        for h in s.handlers:
            if isinstance(h, Filter):
                chain.append(FilterProcessor(compiler.compile(h.expression)))
            elif isinstance(h, WindowHandler):
                factory = self.app.extensions.lookup("window", h.name, h.namespace)
                if factory is None:
                    raise SiddhiAppCreationError(f"unknown window '#{'window.'}{h.name}()'")
                args = [compiler.compile(a) for a in h.args]
                validate_extension_args(
                    factory, h.name, [a.type for a in args],
                    where=f"window '#window.{h.name}' on stream '{s.stream_id}'")
                w = factory(args, definition.attribute_names)
                windows.append(w)
                batch_mode = batch_mode or getattr(w, "is_batch", False)
                chain.append(WindowChainProcessor(w))
            elif isinstance(h, StreamFunction):
                factory = self.app.extensions.lookup(
                    "stream_processor", h.name, h.namespace
                ) or self.app.extensions.lookup("stream_function", h.name, h.namespace)
                if factory is None:
                    raise SiddhiAppCreationError(f"unknown stream function '#{h.name}()'")
                args = [compiler.compile(a) for a in h.args]
                validate_extension_args(
                    factory, h.name, [a.type for a in args],
                    where=f"stream function '#{h.name}' on stream '{s.stream_id}'")
                from siddhi_tpu.core.query import StreamFunctionChainProcessor

                fn_obj = factory(args, definition.attribute_names)
                out_attrs = getattr(fn_obj, "output_attributes", None)
                if out_attrs:
                    # schema-extending stream functions (reference:
                    # StreamProcessor.getReturnAttributes, e.g.
                    # #pol2Cart appending x/y): the new columns resolve
                    # downstream — filters later in this chain and the
                    # selector share this scope object
                    for a_ in out_attrs:
                        compiler.scope.add(
                            s.stream_id, a_.name, a_.name, a_.type)
                        uid = getattr(s, "unique_id", s.stream_id)
                        if uid != s.stream_id:
                            compiler.scope.add(
                                uid, a_.name, a_.name, a_.type)
                    extra_attrs.extend(out_attrs)
                chain.append(StreamFunctionChainProcessor(fn_obj))
            else:
                raise SiddhiAppCreationError(f"unsupported stream handler {h}")
        return chain, batch_mode, windows, extra_attrs

    # -- selector -----------------------------------------------------------

    def _plan_selector(
        self,
        sel: Selector,
        scope: Scope,
        compiler: ExpressionCompiler,
        qname: str,
        query: Query,
        batch_mode: bool,
        star_sources=None,
        extra_attrs=None,
    ) -> Tuple[QuerySelector, StreamDefinition]:
        out_target = getattr(query.output_stream, "target", None) or f"__ret_{qname}"
        rewriter = AggregatorRewrite(scope, compiler,
                                     extensions=self.app.extensions)

        items: Optional[List[SelectItem]] = None
        out_attrs: List[Attribute] = []
        if sel.is_select_all and star_sources is not None:
            # join 'select *': all attrs of both sides, plain names
            items = []
            for side in star_sources:
                for a in side.definition.attributes:
                    if any(o.name == a.name for o in out_attrs):
                        raise SiddhiAppCreationError(
                            f"query '{qname}': 'select *' is ambiguous — "
                            f"attribute '{a.name}' exists on both join sides"
                        )
                    compiled = compiler.compile(
                        Variable(stream_id=side.ref, attribute=a.name)
                    )
                    items.append(SelectItem(a.name, compiled))
                    out_attrs.append(Attribute(a.name, a.type))
            out_names = [i.name for i in items]
            for a in out_attrs:
                scope.add_bare(a.name, a.type)
        elif sel.is_select_all:
            # select * — passthrough of the input definition
            if not isinstance(query.input_stream, SingleInputStream):
                raise SiddhiAppCreationError(
                    f"query '{qname}': 'select *' needs an explicit select "
                    "clause for pattern/join inputs"
                )
            in_def = self.app.resolve_stream_definition(query.input_stream)
            # schema-extending stream functions (#pol2Cart) append to
            # the flowing schema, so `select *` includes their outputs
            out_attrs = list(in_def.attributes) + list(extra_attrs or [])
            out_names = [a.name for a in out_attrs]
        else:
            items = []
            for oa in sel.selection:
                rewritten = rewriter.rewrite(oa.expression)
                compiled = compiler.compile(rewritten)
                nm = oa.rename or (
                    oa.expression.attribute
                    if isinstance(oa.expression, Variable)
                    else None
                )
                if nm is None:
                    raise SiddhiAppCreationError(
                        f"query '{qname}': select expression needs 'as <name>'"
                    )
                items.append(SelectItem(nm, compiled))
                out_attrs.append(Attribute(nm, compiled.type))
            out_names = [i.name for i in items]
            # output attributes are referencable in having/order-by
            for a in out_attrs:
                scope.add_bare(a.name, a.type)

        group_keys = [compiler.compile(g) for g in sel.group_by]
        having = compiler.compile(rewriter.rewrite(sel.having)) if sel.having is not None else None
        order_by = []
        for ob in sel.order_by:
            if ob.variable.attribute not in out_names:
                raise SiddhiAppCreationError(
                    f"order by attribute '{ob.variable.attribute}' not in select output"
                )
            order_by.append((ob.variable.attribute, ob.ascending))
        limit = self._const_int(sel.limit, compiler, "limit")
        offset = self._const_int(sel.offset, compiler, "offset")

        selector = QuerySelector(
            out_target,
            items,
            out_names,
            rewriter.bindings,
            group_keys,
            having,
            order_by,
            limit,
            offset,
            batch_mode=batch_mode,
        )
        out_def = StreamDefinition(id=out_target, attributes=out_attrs)
        return selector, out_def

    @staticmethod
    def _has_aggregators(sel: Selector) -> bool:
        """Does any select item call an aggregator (sum/count/...)?"""
        def walk(e) -> bool:
            if isinstance(e, FunctionCall):
                if e.namespace is None and e.name in AGGREGATOR_NAMES:
                    return True
                return any(walk(a) for a in e.args)
            for attr in ("left", "right", "expr"):
                child = getattr(e, attr, None)
                if isinstance(child, Expression) and walk(child):
                    return True
            return False

        return any(walk(oa.expression) for oa in (sel.selection or []))

    @staticmethod
    def _const_int(expr, compiler, what) -> Optional[int]:
        if expr is None:
            return None
        c = compiler.compile(expr)
        try:
            return int(c.fn({}))
        except Exception as e:
            raise SiddhiAppCreationError(f"{what} must be a constant") from e

    # -- output -------------------------------------------------------------

    def _plan_output(self, query: Query, out_def: StreamDefinition,
                     qname: Optional[str] = None):
        from siddhi_tpu.query_api import DeleteStream, UpdateOrInsertStream, UpdateStream
        from siddhi_tpu.table import (
            DeleteTableCallback,
            InsertIntoTableCallback,
            UpdateOrInsertTableCallback,
            UpdateTableCallback,
            compile_set_clause,
            compile_table_condition,
        )

        out = query.output_stream
        if isinstance(out, InsertIntoStream):
            from siddhi_tpu.core.window import InsertIntoWindowCallback

            nw = self.app.named_windows.get(out.target)
            if nw is not None and not out.is_inner and not out.is_fault:
                return InsertIntoWindowCallback(
                    nw, out.event_type, [a.name for a in out_def.attributes]
                )
            table = self.app.tables.get(out.target)
            if table is not None and not out.is_inner and not out.is_fault:
                return InsertIntoTableCallback(
                    table, out.event_type, [a.name for a in out_def.attributes]
                )
            junction = self.app.get_or_create_junction(
                out.target, out_def, is_inner=out.is_inner, is_fault=out.is_fault
            )
            return InsertIntoStreamCallback(junction, out.event_type)
        if isinstance(out, (DeleteStream, UpdateStream, UpdateOrInsertStream)):
            table = self.app.tables.get(out.target)
            if table is None:
                raise SiddhiAppCreationError(
                    f"'{out.target}' is not a defined table (delete/update "
                    "targets must be tables)"
                )
            # condition + set expressions see the query's *output* attrs,
            # bare and qualified by the source stream's name (reference
            # allows `on T.k == S.k` in update/delete conditions)
            out_scope = Scope()
            src_id = getattr(query.input_stream, "stream_id", None)
            for a in out_def.attributes:
                out_scope.add_bare(a.name, a.type)
                if src_id:
                    out_scope.add(src_id, a.name, a.name, a.type)
            condition = compile_table_condition(
                table, out.on_condition, out_scope, table_resolver=self.app.table_resolver
            )
            if isinstance(out, DeleteStream):
                cb = DeleteTableCallback(table, condition, out.event_type)
            else:
                set_ops = compile_set_clause(
                    table,
                    out.set_clause,
                    out_scope,
                    [a.name for a in out_def.attributes],
                    table_resolver=self.app.table_resolver,
                )
                if isinstance(out, UpdateOrInsertStream):
                    cb = UpdateOrInsertTableCallback(
                        table, condition, set_ops, out.event_type,
                        [a.name for a in out_def.attributes],
                    )
                else:
                    cb = UpdateTableCallback(
                        table, condition, set_ops, out.event_type)
            # @app:devtables: lower the mutation to one scatter step per
            # batch when the gates pass; the generic callback rides along
            # as the per-batch delegate for kernel-inexpressible shapes
            if self.app.app_context.devtables:
                from siddhi_tpu.devtable import DeviceTable, plan_devtable_mutation

                if isinstance(table, DeviceTable):
                    import logging

                    who = qname or f"table:{out.target}"
                    try:
                        return plan_devtable_mutation(
                            who, out, out_def, out_scope, table, cb,
                            functions=self.app.functions,
                            table_resolver=self.app.table_resolver)
                    except SiddhiAppCreationError as e:
                        logging.getLogger("siddhi_tpu").warning(
                            "query '%s': devtable mutation lowering "
                            "unavailable (%s); per-row host callback "
                            "used", who, e)
                        sm = self.app.app_context.statistics_manager
                        if sm is not None:
                            sm.record_devtable_fallback(who, str(e))
            return cb
        if isinstance(out, ReturnStream) or out is None:
            return QueryCallbackOutput()
        raise SiddhiAppCreationError(
            f"output type {type(out).__name__} not supported yet"
        )
