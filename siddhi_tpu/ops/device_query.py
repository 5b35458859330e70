"""Jitted device path for the general query pipeline.

The reference's bread-and-butter hot loop — ProcessStreamReceiver.receive
(query/input/ProcessStreamReceiver.java:99-179) pushing pooled events
through FilterProcessor (query/processor/filter/FilterProcessor.java:32),
a window processor (query/processor/stream/window/*) and
QuerySelector.process (query/selector/QuerySelector.java:76-99) with
per-group AttributeAggregatorExecutors — re-designed as ONE jit-compiled
step over columnar micro-batches:

- **filter**: the jax backend of the compiled expression tree produces a
  boolean mask over the batch (no per-event virtual calls);
- **windows**: fixed-capacity ring buffers in device memory.  Sliding
  aggregates (length/time) are computed with a static ``[B, W]`` window
  gather + membership mask + reduction — every output row in the batch
  is computed in parallel, no scan.  Passing rows are compacted with a
  prefix-sum scatter so filtered-out rows never occupy window slots;
- **group-by**: group keys are interned host-side to dense slot ids
  (exactly like the dense NFA's partition interning); per-group
  aggregator state lives as ``[G]`` device arrays updated with
  scatter-add/min/max, and within-batch running prefixes use a masked
  ``[B, B]`` same-group matmul that XLA maps onto the MXU;
- **tumbling windows**: ``lengthBatch(L)`` closes every pane of a batch
  in ONE program (``make_pane_step``): the host joins the open pane's
  carried rows (fewer than ``L``) to the batch's passing rows, the lanes
  are tiled ``[L, panes]``, each (pane, group) segment is reduced under
  an ``[L, L, panes]`` same-group mask and emits at its group's last
  row, so the emit is a mask over lanes and rides the deferred,
  count-gated fetch like the sliding kind's.  ``timeBatch`` (and
  ``lengthBatch`` past ``PANE_MAX_LENGTH``, with minForever/maxForever,
  under order by/limit, or sharded) keeps per-group accumulators plus a
  flush kernel emitting one row per touched group; there the host
  wrapper splits incoming batches at pane boundaries so each step call
  stays a static-shape program.

Device-mode semantics (documented subset of the host engine — the
planner falls back to the host path otherwise, mirroring the dense NFA
contract):
 - single input stream; filters precede at most one window;
 - windows: none (running aggregates), length, time (sliding, per-event
   emission), lengthBatch, timeBatch (tumbling, per-flush emission);
 - aggregators: sum / count / avg / min / max / stdDev / minForever /
   maxForever / and / or (distinctCount and unionSet keep unbounded
   per-group value sets — documented host fallback);
 - filter / select / having expressions must be jax-traceable (numeric
   attrs, arithmetic/comparison/boolean ops) — checked at compile time
   by actually tracing them;
 - tumbling select items are group keys, aggregates, expressions over
   those, or BARE input attributes of any type: a bare attribute takes
   the value of its group's last passing row in the pane, as the host
   engine's batch selector gives it, gathered host-side at native width
   (from the batch's columns on the one-program path, from per-group
   last-row registers on the per-pane sweep).  Such a query's rows carry
   the host's stamps and order: each row its own last row's timestamp,
   a pane's rows in the order of those last rows.  With neither an
   aggregate nor a group-by a pane owes its every row, not a last one:
   declined, the host engine runs it;
 - time windows hold at most ``window_capacity`` passing events (the
   reference buffer is unbounded; overflow drops the oldest).

Partition mode (``partition with (key of S) begin ... end`` under
``@app:execution('tpu')``, reference:
partition/PartitionStreamReceiver.java:82-118):
 - the partition key arrives as an external per-row column, composes
   into the group axis for aggregation state, and scopes windows per
   key: each key owns a ``[W]`` ring-buffer row of a ``[n_wgroups, W]``
   device array (the per-instance window of the reference's cloned
   queries) — see ``_keyed_sliding_step``;
 - sliding windows expire PER ROW within a batch, preserving the
   reference's event-at-a-time semantics regardless of batch size (the
   host engine's batch path approximates time windows at the batch
   watermark);
 - tumbling windows and output rate limits need per-key pane/limiter
   state and fall back to per-key host instances;
 - idle keys are purged via ``purge_idle_keys`` (free-listed rows are
   zeroed and reused), driven by the partition's @purge annotation.

Numeric lanes (TPU-first dtype policy):
 - INT attributes ride int32 lanes — bit-exact;
 - FLOAT/DOUBLE attributes ride float32 lanes, and aggregation state
   accumulates in float32 (the MXU-native dtype) — a documented
   precision subset of the host engine's float64 numpy;
 - LONG attributes referenced by device-evaluated expressions (filters,
   aggregate arguments, computed select items, having) make the query
   ineligible until the int64 lane lands — float32 would silently round
   above 2^24.  LONG *is* fine as a group-by key or a bare select item:
   both are materialized host-side at native width (group keys are
   interned host-side; bare ``select attr`` items gather from the input
   batch, never touching a device lane).
 - emitted columns are cast back to the declared attribute types.

Host to device: a batch crosses as ONE packed ``int32 [k, B]`` buffer a
chunk (``_pack``), one ``staged_put`` of one leaf.  The per-event kinds
(``_pad_lanes``) give it a row for each lane the compiled expressions
read (found by tracing them over a recording ``env``; float32 lanes by
bit pattern, so NaN payloads, -0.0 and denormals arrive as sent), the
relative timestamps where the step keeps or reads them, the interned
group and window-group ids where the query has any, and the valid
mask; the one-program lengthBatch path (``_pane_chunk``) the lanes of
``pane_lanes()`` and the group ids.  The jitted program takes it apart
again by static slices (``_unpack``).

A batch is cut into chunks only as far as the kind's own working set
asks (``_chunk_rows``): the ``[B, B]`` same-group masks of the running
and keyed-sliding kinds (and of a sliding window that holds
minForever / maxForever) bound a chunk at ``MAX_DEVICE_BATCH`` rows;
the global sliding window gathers ``rows x W x aggregates`` elements
and takes as many rows as keep that gather inside the same budget (a
``length(10)`` window with two aggregates: 131,072); the filter and
tumbling kinds take a batch whole.  Chunks advance the state in order,
and each output row reduces the same window entries however the batch
was cut.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.core.exceptions import (
    SiddhiAppCreationError,
    SiddhiAppRuntimeError,
)
from siddhi_tpu.planner.expr import (
    AGGREGATOR_NAMES,
    CompiledExpression,
    ExpressionCompiler,
    N_KEY,
    RecordingEnv,
    Scope,
    TS_KEY,
)
from siddhi_tpu.observability.stall import waits_on_device
from siddhi_tpu.observability.trace import (
    SCOPE_PANE_ASSIGN,
    SCOPE_PANE_COUNT,
    SCOPE_PANE_EMIT,
    SCOPE_PANE_REDUCE,
    SCOPE_WINDOW_AGGREGATE,
    SCOPE_WINDOW_COUNT,
    SCOPE_WINDOW_EMIT,
    SCOPE_WINDOW_FILTER,
    SCOPE_WINDOW_SLOT,
    SCOPE_WINDOW_UPDATE,
    STAGE_CONVERT,
    STAGE_DISPATCH,
    STAGE_INTERN,
    STAGE_PANE,
    span,
)
from siddhi_tpu.query_api import (
    AndOp,
    ArithmeticOp,
    AttrType,
    CompareOp,
    Expression,
    Filter,
    FunctionCall,
    InOp,
    IsNull,
    NotOp,
    OrOp,
    OutputAttribute,
    Query,
    SingleInputStream,
    Variable,
    WindowHandler,
)

SUPPORTED_AGGS = ("sum", "count", "avg", "min", "max", "stdDev",
                  "minForever", "maxForever", "and", "or")
# distinctCount / unionSet keep per-group value-count dicts (reference:
# DistinctCountAttributeAggregatorExecutor) — unbounded value sets have
# no fixed-shape device layout, so they are a documented host fallback
SUPPORTED_WINDOWS = (None, "length", "time", "lengthBatch", "timeBatch")

# aggregators whose window/running reduction is a masked SUM of the
# (transformed) argument lane: and/or reduce over the bool lane
_SUM_KINDS = ("sum", "avg", "stdDev", "and", "or")

PER_EVENT = "per_event"
PER_FLUSH = "per_flush"

# rows a chunk where the per-event step builds [B, B] same-group masks
# (the running and keyed-sliding kinds, the sliding kind's
# minForever/maxForever prefix): an unbounded junction batch would
# allocate quadratically.  Its square is also the element budget the
# global sliding window's [rows, W, A] gather is held to
# (DeviceQueryEngine._chunk_rows); the filter and tumbling kinds are
# not chunked.  A fused chain (ops/fused_graph.py) takes the smallest
# bound among its stages; the sharded wrapper (parallel/device_shard.py)
# cuts every batch at this bound in a loop of its own
MAX_DEVICE_BATCH = 2048

# longest lengthBatch pane the one-program path tiles.  Its same-group
# mask is [L, L, panes], L times the batch: XLA fuses it away at an
# 8,192-row batch and keeps L x batch bytes of it at a 131,072-row one
# (537 MB at L = 4,096; TPU compiler, memory_analysis).  Measured on a
# v5e at 8,192-row batches, the cse_groupby query, ms a batch, program
# against sweep (PERF.md, PR 33): L = 64: 17.6 / 972; 256: 10.7 / 202;
# 1,024: 9.7 / 54; 4,096: 9.3 / 18.1; 8,192 (one pane a batch): 9.6 /
# 11.9.  The sweep costs some 8 ms a pane, so it is never ahead while a
# batch closes a pane; the cap is where the mask's memory, not the time,
# would start to bind
PANE_MAX_LENGTH = 4096


@dataclass
class DeviceAgg:
    kind: str  # one of SUPPORTED_AGGS
    arg: Optional[CompiledExpression]  # None for count
    env_key: str


def _DevicePairCompiler(scope, pair_keys):
    """Compiler for device-evaluated expressions: LONG STREAM attributes
    (``pair_keys``) ride hi/lo int32 pair lanes (bit-exact comparisons
    at any magnitude); INT keeps its plain int32 lane; synthetic
    LONG-typed env keys (count() outputs) ride ordinary float32 lanes.
    Imported lazily to keep dense_nfa out of the module import path."""
    from siddhi_tpu.ops.dense_nfa import DenseExprCompiler
    from siddhi_tpu.planner.expr import ExpressionCompiler as _Plain

    class _C(DenseExprCompiler):
        PAIR_TYPES = (AttrType.LONG,)

        def _i64_parts(self, e, var_only=False):
            if isinstance(e, Variable):
                key, _t = self.scope.resolve(e)
                if key not in pair_keys:
                    return None
            return super()._i64_parts(e, var_only)

        def _c_Variable(self, e):
            key, t = self.scope.resolve(e)
            if t in self.PAIR_TYPES and key not in pair_keys:
                return _Plain._c_Variable(self, e)
            return super()._c_Variable(e)

    return _C(scope)


def _split_i64(v: np.ndarray):
    """int64 column -> (hi, lo) int32 lanes; lo is bias-signed so SIGNED
    int32 comparison of lo equals UNSIGNED comparison of the raw low
    word (ops/dense_nfa.py:91-105)."""
    v = np.asarray(v, dtype=np.int64)
    hi = (v >> 32).astype(np.int32)
    lo = ((v & 0xFFFFFFFF) - 2**31).astype(np.int32)
    return hi, lo


def _map_children(expr: Expression, fn) -> Expression:
    """Rebuild a composite expression node with ``fn`` applied to each
    child; leaves return unchanged.  The single structural walk shared
    by every AST pass in this module — add new composite node types
    HERE, not in the passes."""
    if isinstance(expr, ArithmeticOp):
        return ArithmeticOp(expr.op, fn(expr.left), fn(expr.right))
    if isinstance(expr, CompareOp):
        return CompareOp(expr.op, fn(expr.left), fn(expr.right))
    if isinstance(expr, AndOp):
        return AndOp(fn(expr.left), fn(expr.right))
    if isinstance(expr, OrOp):
        return OrOp(fn(expr.left), fn(expr.right))
    if isinstance(expr, NotOp):
        return NotOp(fn(expr.expr))
    if isinstance(expr, IsNull):
        return IsNull(fn(expr.expr))
    if isinstance(expr, FunctionCall):
        return FunctionCall(
            expr.namespace, expr.name, tuple(fn(a) for a in expr.args),
            expr.star)
    if isinstance(expr, InOp):
        return InOp(fn(expr.expr), expr.source_id)
    return expr


class _DeviceAggRewrite:
    """Replaces aggregator calls in select/having expressions with
    synthetic variables bound to device aggregation outputs (the device
    analog of the planner's AggregatorRewrite)."""

    def __init__(self, scope: Scope, compiler: ExpressionCompiler):
        self.scope = scope
        self.compiler = compiler
        self.aggs: List[DeviceAgg] = []

    def rewrite(self, expr: Expression) -> Expression:
        if (
            isinstance(expr, FunctionCall)
            and expr.namespace is None
            and expr.name in AGGREGATOR_NAMES
        ):
            if expr.name not in SUPPORTED_AGGS:
                raise SiddhiAppCreationError(
                    f"device query path does not support aggregator "
                    f"'{expr.name}'"
                    + (" (unbounded value sets need the host engine)"
                       if expr.name in ("distinctCount", "unionSet")
                       else ""))
            key = f"__dagg_{len(self.aggs)}"
            arg = None
            if expr.args:
                if len(expr.args) > 1:
                    raise SiddhiAppCreationError(
                        f"aggregator '{expr.name}' takes one argument")
                arg = self.compiler.compile(self.rewrite(expr.args[0]))
            elif expr.name != "count":
                raise SiddhiAppCreationError(
                    f"aggregator '{expr.name}' needs an argument")
            if expr.name in ("and", "or"):
                if arg is None or arg.type != AttrType.BOOL:
                    raise SiddhiAppCreationError(
                        f"aggregator '{expr.name}' needs a boolean argument")
                out_t = AttrType.BOOL
            elif expr.name == "count":
                out_t = AttrType.LONG
            else:
                out_t = AttrType.DOUBLE
            self.aggs.append(DeviceAgg(expr.name, arg, key))
            self.scope.add_bare(key, out_t)
            return Variable(attribute=key)
        if isinstance(expr, InOp):
            raise SiddhiAppCreationError(
                "device query path does not support table membership (IN)")
        return _map_children(expr, self.rewrite)


def _subst_aliases(expr: Expression, aliases: Dict[str, Expression]) -> Expression:
    """Replace bare Variable references to select aliases with the select
    item's (already aggregator-rewritten) expression.  An alias shadows a
    same-named input attribute, matching the host selector's scope order."""
    if isinstance(expr, Variable):
        if expr.stream_id is None and expr.attribute in aliases:
            return aliases[expr.attribute]
        return expr
    return _map_children(expr, lambda e: _subst_aliases(e, aliases))


def _mm_f32(a, b):
    """Mask-times-values matmul in true float32.  The TPU's default f32
    matmul is one bf16 pass, which would round sums of prices to ~3
    digits; HIGHEST keeps the float32 contract of the module docstring
    (ops/hotkey_scan.py does the same for its count matmuls)."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# host-side rows of a tumbling pane (the one-program path's carried rows,
# the sweep's last-row registers) are one flat dict of equal-length
# arrays: input attributes by name, the event timestamp under TS_KEY,
# and these two
GRP_KEY = "__grp"   # interned group id
SEQ_KEY = "__seq"   # place in the stream
# further rows of the packed ingest buffer (DeviceQueryEngine._pad_lanes)
WGRP_KEY = "__wgrp"     # interned window-group (partition key) id
VALID_KEY = "__valid"   # 1 for a row of the batch, 0 for padding


def _copy_rows(rows: Optional[Dict]) -> Optional[Dict]:
    return None if rows is None else {k: v.copy() for k, v in rows.items()}


def _pow2(n: int, floor: int = 16) -> int:
    return max(1 << (max(n, 1) - 1).bit_length(), floor)


class DeviceQueryEngine:
    """One single-input query compiled into jitted device steps.

    Usage::

        eng = compile_query(app_str, "q1", n_groups=1024)
        state = eng.init_state()
        state, rows = eng.process(state, cols, ts)   # rows: emitted dicts
    """

    #: span-label kind for the cycle tracer (observability/trace.py) —
    #: the runtime reads it at construction, so a wrapper engine (the
    #: sharded delegate) overrides what the trace calls its cycles
    engine_kind = "device"

    def __init__(
        self,
        query: Query,
        stream_def,
        n_groups: int = 1024,
        window_capacity: int = 1024,
        partition_mode: bool = False,
        n_wgroups: Optional[int] = None,
        defer_order_by: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.query = query
        self.stream_def = stream_def
        self.n_groups = n_groups
        # partitioned form (`partition with (key of S) begin ... end`
        # under execution('tpu')): the partition key arrives per batch as
        # an external column, composes into the group axis for per-key
        # aggregation state, and scopes windows per key (each key gets
        # its own ring-buffer row — the reference's per-instance window,
        # partition/PartitionStreamReceiver.java:82-118, re-designed as
        # [n_wgroups, W] device state instead of per-key Python objects)
        self.partition_mode = bool(partition_mode)
        self.n_wgroups = int(n_wgroups) if n_wgroups else n_groups
        # fault-injection harness (util/faults.py), wired by the planner
        # when @app:faults is present; consulted before each jitted step
        self.faults = None

        s = query.input_stream
        if not isinstance(s, SingleInputStream):
            raise SiddhiAppCreationError(
                "device query path needs a single input stream")
        self.stream_id = s.stream_id

        # -- handler chain: filters then at most one window ------------------
        self.filter_exprs: List[Expression] = []
        self.window_name: Optional[str] = None
        self.window_args: List = []
        seen_window = False
        for h in s.handlers:
            if isinstance(h, Filter):
                if seen_window:
                    raise SiddhiAppCreationError(
                        "device query path: filters must precede the window")
                self.filter_exprs.append(h.expression)
            elif isinstance(h, WindowHandler):
                if seen_window:
                    raise SiddhiAppCreationError(
                        "device query path supports at most one window")
                seen_window = True
                self.window_name = h.name
                self.window_args = list(h.args)
            else:
                raise SiddhiAppCreationError(
                    f"device query path: unsupported handler {type(h).__name__}")
        if self.window_name not in SUPPORTED_WINDOWS:
            raise SiddhiAppCreationError(
                f"device query path does not support window "
                f"'{self.window_name}'")
        self.mode = (
            PER_FLUSH if self.window_name in ("lengthBatch", "timeBatch")
            else PER_EVENT
        )

        # -- scope / expression compilation ----------------------------------
        # device lanes: INT rides int32 (bit-exact), FLOAT/DOUBLE ride
        # float32, LONG rides a hi/lo int32 PAIR usable in plain
        # comparisons (bit-exact at any magnitude — the dense NFA's
        # lane technique, ops/dense_nfa.py:91-105); LONG arithmetic /
        # aggregate arguments still fall back to the host engine.
        self._lane_dtype: Dict[str, np.dtype] = {
            a.name: (np.dtype(np.int32) if a.type == AttrType.INT
                     else np.dtype(np.bool_) if a.type == AttrType.BOOL
                     else np.dtype(np.float32))
            for a in stream_def.attributes
            if (a.type.is_numeric or a.type == AttrType.BOOL)
            and a.type != AttrType.LONG
        }
        self.attrs = list(self._lane_dtype)
        self.long_attrs = [a.name for a in stream_def.attributes
                           if a.type == AttrType.LONG]
        self.all_attrs = list(stream_def.attribute_names)
        scope = Scope()
        for a in stream_def.attributes:
            scope.add(s.alias or s.stream_id, a.name, a.name, a.type)
            if s.alias:
                scope.add(s.stream_id, a.name, a.name, a.type)
        # device-evaluated expressions: LONG stream attrs ride pair lanes
        compiler = _DevicePairCompiler(scope, set(self.long_attrs))
        # host-evaluated expressions (group keys, window constants):
        # native numpy width, any type
        host_compiler = ExpressionCompiler(scope)

        self.filters = [compiler.compile(e) for e in self.filter_exprs]

        # window parameter (constant)
        self.window_param: Optional[int] = None
        if self.window_name is not None:
            if not self.window_args:
                raise SiddhiAppCreationError(
                    f"window '{self.window_name}' needs an argument")
            c = host_compiler.compile(self.window_args[0])
            try:
                self.window_param = int(c.fn({}))
            except Exception as e:
                raise SiddhiAppCreationError(
                    f"window '{self.window_name}' argument must be constant"
                ) from e

        # group-by keys (exprs; interned host-side)
        sel = query.selector
        self.group_exprs: List[CompiledExpression] = [
            host_compiler.compile(g) for g in (sel.group_by or [])
        ]
        self.group_raw: List[Expression] = list(sel.group_by or [])
        # numeric group keys usable inside flush exprs
        self._numeric_group_keys = [
            i for i, g in enumerate(self.group_exprs)
            if g.type.is_numeric
        ]

        # select items: rewrite aggregators, classify outputs
        rewriter = _DeviceAggRewrite(scope, compiler)
        if sel.selection is None:
            # select * (selection=None IS the parser's select-all form):
            # every input attribute passes through at native width
            # (stream functions never reach the device chain, so the
            # flowing schema IS the stream definition)
            sel = type(sel)(
                selection=[
                    OutputAttribute(Variable(attribute=a.name))
                    for a in stream_def.attributes
                ],
                group_by=list(sel.group_by or []),
                having=sel.having,
                order_by=list(sel.order_by or []),
                limit=sel.limit,
                offset=sel.offset,
            )
        # out_spec entries: ("expr", compiled) | ("group_key", key_index)
        # | ("passthrough", attr_name) — passthroughs gather the input
        # column host-side at native width (any type, incl. LONG/STRING)
        self.out_spec: List[Tuple[str, object, str]] = []
        self._device_expr_raw: List[Expression] = []
        # select alias -> rewritten expression AST, so `having s > 100`
        # referencing `sum(v) as s` resolves (the host path registers
        # output attrs in scope, planner/query_planner.py:530-535; here
        # aliases substitute inline before compiling having)
        alias_map: Dict[str, Expression] = {}
        for oa in sel.selection:
            gk = self._as_group_key(oa.expression)
            if gk is not None:
                self.out_spec.append(("group_key", gk, oa.name))
                alias_map[oa.name] = oa.expression
                continue
            pt = self._as_passthrough(oa.expression, stream_def, s)
            if pt is not None:
                self.out_spec.append(("passthrough", pt, oa.name))
                alias_map[oa.name] = oa.expression
                continue
            rewritten = rewriter.rewrite(oa.expression)
            compiled = compiler.compile(rewritten)
            self.out_spec.append(("expr", compiled, oa.name))
            self._device_expr_raw.append(oa.expression)
            alias_map[oa.name] = rewritten
        self.aggs = rewriter.aggs
        # declared output type per lane (emitted columns are cast back)
        self.out_types: List[AttrType] = []
        for kind, v, _name in self.out_spec:
            if kind == "group_key":
                self.out_types.append(self.group_exprs[v].type)
            elif kind == "passthrough":
                self.out_types.append(stream_def.attribute_type(v))
            else:
                self.out_types.append(v.type)
        self._check_value_types(stream_def, s, sel)
        self.having = (
            compiler.compile(rewriter.rewrite(
                _subst_aliases(sel.having, alias_map)))
            if sel.having is not None else None
        )
        # order by / limit / offset are never evaluated by this engine.
        # The PLANNER path applies them in its host-side passthrough
        # selector over each emitted chunk (defer_order_by=True, same
        # pipeline position as the host engine's per-chunk
        # _order_limit); direct-API callers have no such selector, so
        # silently dropping the clauses would corrupt results
        if not defer_order_by and (
                sel.order_by or sel.limit is not None
                or sel.offset is not None):
            raise SiddhiAppCreationError(
                "device query engine: order by/limit/offset need the "
                "planner's host-side selector (SiddhiManager path) — "
                "the direct compile_query API does not apply them")
        if self.mode == PER_FLUSH:
            for kind, _v, name in self.out_spec:
                if kind == "expr" and not self._flush_expr_ok(_v):
                    raise SiddhiAppCreationError(
                        f"tumbling device query: select item '{name}' may "
                        "be a group key, an aggregate, an expression over "
                        "those, or a bare input attribute")
        # bare input attributes of a tumbling select: the value of the
        # group's last passing row in the pane, kept host-side
        self.bare_attrs: List[str] = sorted({
            v for kind, v, _n in self.out_spec
            if kind == "passthrough"}) if self.mode == PER_FLUSH else []
        if self.bare_attrs and not (self.group_exprs or self.aggs):
            # the host's batch selector keeps a group's last row only
            # where there is a group-by or an aggregate
            # (core/query.py); without either a pane emits every row
            raise SiddhiAppCreationError(
                "tumbling device query: a select of bare attributes "
                f"{self.bare_attrs} with neither an aggregate nor a "
                "group-by emits every row of a pane — host engine used")
        if self.mode == PER_EVENT and self.window_name is None and not self.aggs:
            self.kind = "filter"  # stateless filter/projection query
        elif self.mode == PER_EVENT and self.window_name is None:
            self.kind = "running"
        elif self.mode == PER_EVENT:
            self.kind = "sliding"
        else:
            self.kind = "tumbling"
        if self.partition_mode:
            if self.kind == "tumbling":
                raise SiddhiAppCreationError(
                    "partitioned tumbling windows need per-key pane "
                    "boundaries — per-key host instances used")
            if self.kind == "sliding":
                self.kind = "keyed_sliding"

        # lengthBatch: every pane a batch closes in one program
        # (make_pane_step); the per-pane sweep keeps what that program
        # does not hold (see the module docstring)
        self.pane_batched = (
            self.kind == "tumbling" and self.window_name == "lengthBatch"
            and 1 <= int(self.window_param) <= PANE_MAX_LENGTH
            and not {a.kind for a in self.aggs} & {"minForever",
                                                   "maxForever"}
            and not (sel.order_by or sel.limit is not None
                     or sel.offset is not None))

        # window geometry
        if self.kind in ("sliding", "keyed_sliding"):
            self.W = (
                int(self.window_param) if self.window_name == "length"
                else int(window_capacity)
            )
            if self.W < 1:
                raise SiddhiAppCreationError("window size must be >= 1")
        else:
            self.W = 0

        self._trace_check()
        self._step_cache: Dict[str, Callable] = {}
        # the rows of the packed ingest buffer (_pad_lanes): the lanes
        # the device expressions read, then whatever else the kind's
        # step takes.  The sliding kinds keep every row's timestamp in
        # their ring; a group id means something only where the host
        # interns one (the tumbling sweep's callers bring their own)
        self.interns = self.kind != "filter" and bool(
            self.partition_mode or self.group_exprs)
        self.lane_rows: List[str] = list(self.read_lanes)
        if self.W or self._reads_ts:
            self.lane_rows.append(TS_KEY)
        if self.interns or self.kind == "tumbling":
            self.lane_rows.append(GRP_KEY)
        if self.kind == "keyed_sliding":
            self.lane_rows.append(WGRP_KEY)
        self.lane_rows.append(VALID_KEY)
        self.chunk_rows = self._chunk_rows()

        # host-side interning / pane bookkeeping.  In partition mode the
        # group key space is the composed tuple (partition_key, *group
        # keys); window groups (``wgrp``) intern the partition key alone.
        # Purged ids go to free lists for reuse (their state rows are
        # zeroed first) — the device analog of dropping idle
        # PartitionInstances.
        self._group_ids: Dict = {}
        self._group_vals: List = []
        self._group_free: List[int] = []
        self._group_last: Dict[int, int] = {}
        self._wgrp_ids: Dict = {}
        self._wgrp_vals: List = []
        self._wgrp_free: List[int] = []
        # per-id last-use times as ARRAYS (vectorized batch touch +
        # purge scan; a dict write per unique key was ~half the cost of
        # a warm partitioned batch)
        self._wgrp_last = np.zeros(self.n_wgroups, dtype=np.int64)
        self._wgrp_in_use = np.zeros(self.n_wgroups, dtype=bool)
        # sorted key index for the vectorized intern fast path (the
        # dense runtime's technique, core/dense_pattern.py:317); falls
        # back to dict probes on mixed/object key dtypes
        self._wgrp_sorted_keys: Optional[np.ndarray] = None
        self._wgrp_sorted_ids: Optional[np.ndarray] = None
        self._wgrp_vector = True
        self.base_ts: Optional[int] = None
        self._pane_end: Optional[int] = None  # timeBatch
        self._pane_fill = 0  # passing events in the open pane
        self._prev_pane_fill = 0  # previous pane's fill (idle detection)
        # one-program path: the open pane's passing rows, fewer than L
        # (flat rows, see GRP_KEY; None while the pane is empty)
        self._pane_carry: Optional[Dict[str, np.ndarray]] = None
        # per-pane sweep with bare attributes: per-group registers of
        # the open pane's last passing row (flat rows of [G], made at
        # the first batch); _rows_seen numbers rows across batches
        self._last_row: Optional[Dict[str, np.ndarray]] = None
        self._rows_seen = 0
        self.panes_closed = 0  # stats(): tumbling panes flushed

    # -- compilation helpers -------------------------------------------------

    def _as_group_key(self, expr: Expression) -> Optional[int]:
        """Select item that IS a group-by key -> its key index."""
        if not isinstance(expr, Variable):
            return None
        for i, g in enumerate(self.group_raw):
            if isinstance(g, Variable) and g.attribute == expr.attribute:
                return i
        return None

    @staticmethod
    def _as_passthrough(expr: Expression, stream_def, s) -> Optional[str]:
        """Select item that is a bare input-attribute reference -> the
        attribute name (materialized host-side at native width)."""
        if not isinstance(expr, Variable):
            return None
        if expr.stream_id not in (None, s.stream_id, s.alias):
            return None
        if expr.attribute not in stream_def.attribute_names:
            return None
        return expr.attribute

    def _check_value_types(self, stream_def, s, sel):
        """Reject device-evaluated expressions (filters, computed select
        items incl. aggregate arguments, having) that use a LONG
        attribute OUTSIDE a plain comparison, or a LONG constant outside
        int32 range on a non-pair lane: LONG comparisons ride bit-exact
        hi/lo int32 pairs (any magnitude), but LONG arithmetic has no
        64-bit device lane and float32 would silently round above 2^24
        (the reference is per-type exact, executor/math/ &
        condition/compare/).  Group-by keys and bare select items stay
        host-side and may be any type."""
        from siddhi_tpu.query_api import Constant

        names = set(stream_def.attribute_names)
        ids = (None, s.stream_id, s.alias)

        def is_long_var(e):
            return (isinstance(e, Variable) and e.stream_id in ids
                    and e.attribute in names
                    and stream_def.attribute_type(e.attribute)
                    == AttrType.LONG)

        def walk(e):
            if isinstance(e, CompareOp) and (
                    is_long_var(e.left) or is_long_var(e.right)):
                # pair-compare subtree: the device compiler takes the
                # hi/lo path (or raises its own eligibility error when
                # the other side is not pair-able) — any magnitude is
                # bit-exact there
                return e
            if isinstance(e, Variable):
                if is_long_var(e):
                    raise SiddhiAppCreationError(
                        f"device query path: attribute '{e.attribute}' "
                        "is LONG and used outside a plain comparison; "
                        "its hi/lo lanes support comparisons only — "
                        "host engine used (LONG is fine as a group-by "
                        "key, bare select item, or comparison operand)")
                return e
            if (isinstance(e, Constant) and e.type == AttrType.LONG
                    and e.value is not None
                    and not -(2**31) <= int(e.value) < 2**31):
                raise SiddhiAppCreationError(
                    f"device query path: constant {e.value} exceeds the "
                    "int32 device lane — host engine used")
            return _map_children(e, walk)

        for f in self.filter_exprs:
            walk(f)
        for e in self._device_expr_raw:
            walk(e)
        if sel.having is not None:
            walk(sel.having)

    def _flush_expr_ok(self, compiled) -> bool:
        """Flush-time exprs can only read aggregate keys / numeric group
        keys (probed by tracing with exactly that env)."""
        try:
            self._trace_one(compiled, self._flush_env_shapes())
            return True
        except Exception:
            return False

    def _env_shapes(self, B: int = 8):
        import jax

        env = {
            a: jax.ShapeDtypeStruct((B,), self._lane_dtype[a])
            for a in self.attrs
        }
        i32 = jax.ShapeDtypeStruct((B,), np.int32)
        for a in self.long_attrs:
            env[a + "|hi"] = i32
            env[a + "|lo"] = i32
        env[TS_KEY] = i32
        env[N_KEY] = B
        for a in self.aggs:
            env[a.env_key] = jax.ShapeDtypeStruct(
                (B,), np.bool_ if a.kind in ("and", "or") else np.float32)
        return env

    def _flush_env_shapes(self, G: int = 8):
        import jax

        f32 = jax.ShapeDtypeStruct((G,), np.float32)
        env = {
            a.env_key: (jax.ShapeDtypeStruct((G,), np.bool_)
                        if a.kind in ("and", "or") else f32)
            for a in self.aggs
        }
        for i in self._numeric_group_keys:
            g = self.group_raw[i]
            if isinstance(g, Variable):
                env[g.attribute] = f32
        env[N_KEY] = G
        return env

    def _trace_one(self, compiled, shapes, seen: Optional[set] = None):
        import jax

        seen = set() if seen is None else seen
        jax.eval_shape(lambda env: compiled.fn(RecordingEnv(env, seen)),
                       shapes)

    def _trace_check(self):
        """Compile-time eligibility: every expression must be
        jax-traceable (no object-dtype ops, no host-only functions).
        The traces over the input lanes run on an ``env`` that records
        its lookups (as ``pane_lanes`` does for the pane program):
        ``read_lanes`` are the lanes a step evaluates an expression on,
        the only ones a batch has to bring to the device."""
        shapes = self._env_shapes()
        seen = set()
        try:
            for f in self.filters:
                self._trace_one(f, shapes, seen)
            for a in self.aggs:
                if a.arg is not None:
                    self._trace_one(a.arg, shapes, seen)
            for g in self.group_exprs:
                # group keys are evaluated host-side (interning), so any
                # type is fine — no trace needed
                pass
            if self.mode == PER_EVENT:
                for kind, v, _n in self.out_spec:
                    if kind == "expr":
                        self._trace_one(v, shapes, seen)
                if self.having is not None:
                    self._trace_one(self.having, shapes, seen)
            else:
                fshapes = self._flush_env_shapes()
                for kind, v, _n in self.out_spec:
                    if kind == "expr":
                        self._trace_one(v, fshapes)
                if self.having is not None:
                    self._trace_one(self.having, fshapes)
        except SiddhiAppCreationError:
            raise
        except Exception as e:
            raise SiddhiAppCreationError(
                f"query not device-eligible (expression not jax-traceable): {e}"
            ) from e
        # of every lane a batch could fill (the numeric and bool
        # attributes, a hi/lo pair for each LONG), those looked up
        self.read_lanes: List[str] = [
            k for k in self.attrs + [a + part for a in self.long_attrs
                                     for part in ("|hi", "|lo")]
            if k in seen]
        self._reads_ts = TS_KEY in seen

    def _chunk_rows(self) -> Optional[int]:
        """Rows a chunk of the per-event step, from what the kind
        allocates (None: a batch is never cut).  Where a ``[B, B]``
        mask exists the bound is ``MAX_DEVICE_BATCH``.  The global
        sliding window without minForever/maxForever gathers ``rows x W
        x A`` elements and nothing wider: the largest power of two that
        keeps the gather inside the ``[B, B]`` kinds' element budget,
        never fewer rows than they take."""
        if self.kind in ("filter", "tumbling"):
            return None
        if self.kind != "sliding" or self._kinds() & {"minForever",
                                                      "maxForever"}:
            return MAX_DEVICE_BATCH
        rows = MAX_DEVICE_BATCH ** 2 // (self.W * max(len(self.aggs), 1))
        return max(1 << max(rows.bit_length() - 1, 0), MAX_DEVICE_BATCH)

    # -- state ---------------------------------------------------------------

    def init_state(self):
        jnp = self.jnp
        return {k: jnp.asarray(v) for k, v in self.init_state_host().items()}

    def init_state_host(self):
        """NUMPY zero state (the sharded wrapper builds its shard-major
        layout from this without touching any device backend).  numpy's
        zeros/full/float32/... names match jnp's, so the builder body
        reads identically to a device-side one."""
        jnp = np
        A = max(len(self.aggs), 1)
        G = self.n_groups
        state = {}
        kinds = {a.kind for a in self.aggs}
        if self.kind == "sliding":
            W = self.W
            state["win_vals"] = jnp.zeros((W, A), dtype=jnp.float32)
            state["win_ts"] = jnp.zeros(W, dtype=jnp.int32)
            state["win_grp"] = jnp.zeros(W, dtype=jnp.int32)
            state["win_valid"] = jnp.zeros(W, dtype=bool)
        elif self.kind == "keyed_sliding":
            # per-key ring buffers: each partition key owns one [W] row
            Gw, W = self.n_wgroups, self.W
            state["win_vals"] = jnp.zeros((Gw, W, A), dtype=jnp.float32)
            state["win_ts"] = jnp.zeros((Gw, W), dtype=jnp.int32)
            state["win_grp"] = jnp.zeros((Gw, W), dtype=jnp.int32)
            state["win_valid"] = jnp.zeros((Gw, W), dtype=bool)
            state["win_count"] = jnp.zeros(Gw, dtype=jnp.int32)
        elif self.kind in ("running", "tumbling"):
            if kinds & set(_SUM_KINDS):
                state["acc_sum"] = jnp.zeros((G, A), dtype=jnp.float32)
            if "stdDev" in kinds:
                state["acc_sumsq"] = jnp.zeros((G, A), dtype=jnp.float32)
            # counts always kept: cheap, and avg/flush-valid need them
            state["acc_cnt"] = jnp.zeros((G, A), dtype=jnp.float32)
            if "min" in kinds:
                state["acc_min"] = jnp.full((G, A), jnp.inf, dtype=jnp.float32)
            if "max" in kinds:
                state["acc_max"] = jnp.full((G, A), -jnp.inf, dtype=jnp.float32)
            if self.kind == "tumbling":
                state["touched"] = jnp.zeros(G, dtype=bool)
                K = max(len(self._numeric_group_keys), 1)
                state["grp_keys"] = jnp.zeros((G, K), dtype=jnp.float32)
        # all-time accumulators (minForever/maxForever): per agg group,
        # NEVER reset by window expiry or tumbling flushes
        if self.kind in ("running", "tumbling", "sliding", "keyed_sliding"):
            if "minForever" in kinds:
                state["acc_minf"] = jnp.full((G, A), jnp.inf,
                                             dtype=jnp.float32)
            if "maxForever" in kinds:
                state["acc_maxf"] = jnp.full((G, A), -jnp.inf,
                                             dtype=jnp.float32)
        return state

    # -- steps ---------------------------------------------------------------

    def _base_env(self, cols, ts, B):
        env = {a: cols[a] for a in self.attrs if a in cols}
        for a in self.long_attrs:
            hk, lk = a + "|hi", a + "|lo"
            if hk in cols:
                env[hk] = cols[hk]
                env[lk] = cols[lk]
        env[TS_KEY] = ts
        env[N_KEY] = B
        return env

    def _filter_mask(self, env, valid):
        jnp = self.jnp
        m = valid
        for f in self.filters:
            m = m & jnp.asarray(f.fn(env)).astype(bool)
        return m

    def _arg_vals(self, env, B):
        """[B, A] float32 aggregate-argument values (count -> ones)."""
        jnp = self.jnp
        if not self.aggs:
            return jnp.ones((B, 1), dtype=jnp.float32)
        cols = []
        for a in self.aggs:
            if a.arg is None:
                cols.append(jnp.ones(B, dtype=jnp.float32))
            else:
                v = jnp.asarray(a.arg.fn(env)).astype(jnp.float32)
                cols.append(jnp.broadcast_to(v, (B,)))
        return jnp.stack(cols, axis=-1)

    def _emit(self, env_out, fmask, B):
        """Evaluate select items / having -> (out_valid, {name: [B]}).

        Each computed column keeps a dtype matching its declared type —
        INT expressions stay int32 end-to-end (bit-exact), BOOL stays
        bool; everything else is float32 — instead of rounding through
        one shared float32 matrix."""
        jnp = self.jnp
        out = {}
        for oi, (kind, v, name) in enumerate(self.out_spec):
            if kind in ("group_key", "passthrough"):
                continue  # materialized host-side
            col = jnp.asarray(v.fn(env_out))
            if v.type == AttrType.INT:
                col = col.astype(jnp.int32)
            elif v.type == AttrType.BOOL:
                col = col.astype(bool)
            else:
                col = col.astype(jnp.float32)
            out[name] = jnp.broadcast_to(col, (B,))
        if self.having is not None:
            fmask = fmask & jnp.asarray(self.having.fn(env_out)).astype(bool)
        return fmask, out

    def _finalize_aggs(self, env_out, wsum, wcnt, wsumsq=None, wmin=None,
                       wmax=None, fmin=None, fmax=None):
        """Map reduced moments to aggregator output lanes.  ``wsum`` /
        ``wcnt`` are the masked window (or running-total) sum and count
        per row; ``wsumsq`` the sum of squares (stdDev); ``wmin/wmax``
        the window min/max; ``fmin/fmax`` the all-time accumulators.
        and/or reduce over their bool argument lane: and = no false
        member (count == sum), or = some true member (sum > 0) — the
        reference's true/false counters
        (query/selector/attribute/aggregator/
        AndAttributeAggregatorExecutor.java) as masked sums."""
        jnp = self.jnp
        for ai, a in enumerate(self.aggs):
            k = a.kind
            if k == "sum":
                env_out[a.env_key] = wsum[:, ai]
            elif k == "count":
                env_out[a.env_key] = wcnt[:, 0]
            elif k == "avg":
                env_out[a.env_key] = wsum[:, ai] / jnp.maximum(wcnt[:, 0], 1.0)
            elif k == "stdDev":
                # population stddev from (sum, sumsq, n) — the host
                # StdDevAgg decomposition in float32
                nn = jnp.maximum(wcnt[:, 0], 1.0)
                mean = wsum[:, ai] / nn
                var = jnp.maximum(wsumsq[:, ai] / nn - mean * mean, 0.0)
                env_out[a.env_key] = jnp.sqrt(var)
            elif k == "min":
                env_out[a.env_key] = wmin[:, ai]
            elif k == "max":
                env_out[a.env_key] = wmax[:, ai]
            elif k == "minForever":
                env_out[a.env_key] = fmin[:, ai]
            elif k == "maxForever":
                env_out[a.env_key] = fmax[:, ai]
            elif k == "and":
                env_out[a.env_key] = (wcnt[:, 0] - wsum[:, ai]) < 0.5
            else:  # or
                env_out[a.env_key] = wsum[:, ai] > 0.5

    def _kinds(self):
        return {a.kind for a in self.aggs}

    def _prefix_minmax(self, argvals, grp, fmask, B, need_min, need_max):
        """Within-batch same-group running min/max including self
        ([B, A] each; None when not needed)."""
        jnp = self.jnp
        tri = jnp.tril(jnp.ones((B, B), dtype=bool))
        same = tri & (grp[:, None] == grp[None, :]) & fmask[None, :]
        big = jnp.float32(np.inf)
        pmin = pmax = None
        if need_min:
            pmin = jnp.min(
                jnp.where(same[:, :, None], argvals[None, :, :], big), axis=1)
        if need_max:
            pmax = jnp.max(
                jnp.where(same[:, :, None], argvals[None, :, :], -big), axis=1)
        return pmin, pmax

    def _forever_rows(self, state, argvals, grp, fmask, B,
                      pmin=None, pmax=None):
        """Per-row all-time min/max ([B, A]) = pre-batch accumulator
        combined with the within-batch same-group prefix (callers that
        already computed the prefix pass it in to avoid tracing the
        [B, B, A] reduction twice)."""
        jnp = self.jnp
        kinds = self._kinds()
        need_min = "minForever" in kinds and pmin is None
        need_max = "maxForever" in kinds and pmax is None
        if need_min or need_max:
            cmin, cmax = self._prefix_minmax(
                argvals, grp, fmask, B, need_min, need_max)
            pmin = pmin if pmin is not None else cmin
            pmax = pmax if pmax is not None else cmax
        fmin = fmax = None
        if "minForever" in kinds:
            fmin = jnp.minimum(state["acc_minf"][grp], pmin)
        if "maxForever" in kinds:
            fmax = jnp.maximum(state["acc_maxf"][grp], pmax)
        return fmin, fmax

    def _forever_scatter(self, state, new_state, argvals, grp, fmask):
        jnp = self.jnp
        upd = fmask[:, None]
        if "acc_minf" in state:
            new_state["acc_minf"] = state["acc_minf"].at[grp].min(
                jnp.where(upd, argvals, jnp.inf))
        if "acc_maxf" in state:
            new_state["acc_maxf"] = state["acc_maxf"].at[grp].max(
                jnp.where(upd, argvals, -jnp.inf))

    def _forever_block(self, state, argvals, grp, fmask, B, rows, grp_b):
        """Per-output-row all-time min/max for a row block ([nb, A]):
        the same-group prefix mask compares each selected output row
        (global index ``rows[i]``) against the WHOLE batch, so a block
        decomposition reduces exactly the rows the full-batch
        ``_forever_rows`` would."""
        jnp = self.jnp
        kinds = self._kinds()
        if not (kinds & {"minForever", "maxForever"}):
            return None, None
        le = rows[:, None] >= jnp.arange(B)[None, :]
        same = le & (grp_b[:, None] == grp[None, :]) & fmask[None, :]
        big = jnp.float32(np.inf)
        fmin = fmax = None
        if "minForever" in kinds:
            pmin = jnp.min(
                jnp.where(same[:, :, None], argvals[None, :, :], big), axis=1)
            fmin = jnp.minimum(state["acc_minf"][grp_b], pmin)
        if "maxForever" in kinds:
            pmax = jnp.max(
                jnp.where(same[:, :, None], argvals[None, :, :], -big), axis=1)
            fmax = jnp.maximum(state["acc_maxf"][grp_b], pmax)
        return fmin, fmax

    def _sliding_step(self, state, env, fmask, ts, grp, B,
                      r0=None, nb=None):
        """Global sliding-window step body.  ``r0``/``nb`` select a
        contiguous output-row block: the ring-buffer evolution (cheap,
        O(B + W)) is always computed over the WHOLE batch, but the
        O(B*W) window gather/reduction and the emit evaluation run only
        for rows [r0, r0+nb) — the sharded wrapper splits that work
        across the mesh's batch axis while keeping the ring replicated.
        The defaults (r0=None) cover the whole batch, i.e. the
        single-device step; the block decomposition is bit-identical
        because each output row's window reduction is unchanged.
        Returns (new_state, ov[nb], out {name: [nb]})."""
        jnp = self.jnp
        named_scope = self.jax.named_scope
        W = self.W
        A = max(len(self.aggs), 1)
        # slot: passing rows compacted behind the ring's W entries
        with named_scope(SCOPE_WINDOW_SLOT):
            argvals = self._arg_vals(env, B)  # [B, A]
            pos = jnp.cumsum(fmask.astype(jnp.int32)) - 1  # [B]
            n_pass = jnp.sum(fmask.astype(jnp.int32))
            sidx = jnp.where(fmask, pos, B)  # dump lane B
            comp_vals = jnp.zeros((B + 1, A), jnp.float32).at[sidx].set(argvals)[:B]
            comp_ts = jnp.zeros(B + 1, jnp.int32).at[sidx].set(ts)[:B]
            comp_grp = jnp.zeros(B + 1, jnp.int32).at[sidx].set(grp)[:B]
            comp_valid = (jnp.zeros(B + 1, bool)
                          .at[sidx].set(jnp.ones(B, bool))[:B])
            cat_vals = jnp.concatenate([state["win_vals"], comp_vals], 0)
            cat_ts = jnp.concatenate([state["win_ts"], comp_ts], 0)
            cat_grp = jnp.concatenate([state["win_grp"], comp_grp], 0)
            cat_valid = jnp.concatenate([state["win_valid"], comp_valid], 0)
        dyn = self.jax.lax.dynamic_slice_in_dim
        if nb is None:
            nb = B
            rows = jnp.arange(B)
            blk = lambda x: x  # noqa: E731 — whole batch, no slicing
        else:
            rows = r0 + jnp.arange(nb)
            blk = lambda x: dyn(x, r0, nb, axis=0)  # noqa: E731
        pos_b = blk(pos)
        grp_b = blk(grp)
        ts_b = blk(ts)
        fmask_b = blk(fmask)
        env_b = {k: blk(v) for k, v in env.items() if k != N_KEY}
        env_b[N_KEY] = nb
        with named_scope(SCOPE_WINDOW_AGGREGATE):
            # window of output row i: concat positions pos[i]+1 .. pos[i]+W
            # (the W entries ending at the row itself)
            gidx = pos_b[:, None] + 1 + jnp.arange(W)[None, :]  # [nb, W]
            gidx = jnp.clip(gidx, 0, W + B - 1)
            w_vals = cat_vals[gidx]  # [nb, W, A]
            member = cat_valid[gidx] & (cat_grp[gidx] == grp_b[:, None])
            if self.window_name == "time":
                T = self.window_param
                member = member & (cat_ts[gidx] > (ts_b[:, None] - T))
            mf = member.astype(jnp.float32)[:, :, None]
            env_out = dict(env_b)
            kinds = self._kinds()
            wsum = jnp.sum(w_vals * mf, axis=1)  # [nb, A]
            wcnt = jnp.sum(mf, axis=1)  # [nb, 1]
            wsumsq = (jnp.sum(w_vals * w_vals * mf, axis=1)
                      if "stdDev" in kinds else None)
            m3 = member[:, :, None]
            wmin = (jnp.min(jnp.where(m3, w_vals, jnp.inf), axis=1)
                    if "min" in kinds else None)
            wmax = (jnp.max(jnp.where(m3, w_vals, -jnp.inf), axis=1)
                    if "max" in kinds else None)
            fmin, fmax = self._forever_block(state, argvals, grp, fmask, B,
                                             rows, grp_b)
            self._finalize_aggs(env_out, wsum, wcnt, wsumsq, wmin, wmax,
                                fmin, fmax)
        with named_scope(SCOPE_WINDOW_EMIT):
            ov, out = self._emit(env_out, fmask_b, nb)
        # new buffer = last W entries ending at the batch's final
        # passing row: concat[n_pass : n_pass + W]
        with named_scope(SCOPE_WINDOW_UPDATE):
            start = jnp.clip(n_pass, 0, B)
            new_state = dict(state)
            new_state["win_vals"] = dyn(cat_vals, start, W, axis=0)
            new_state["win_ts"] = dyn(cat_ts, start, W, axis=0)
            new_state["win_grp"] = dyn(cat_grp, start, W, axis=0)
            new_state["win_valid"] = dyn(cat_valid, start, W, axis=0)
            self._forever_scatter(state, new_state, argvals, grp, fmask)
        return new_state, ov, out

    def make_step(self, jit: bool = True, scoped: bool = True) -> Callable:
        """Per-event step (filter / running / sliding / keyed_sliding):

        step(state, cols {attr: [B] f32}, ts[B] i32 relative-ms,
             grp[B] i32, wgrp[B] i32 (window group; partition mode only),
             valid[B] bool)
          -> (state, out_valid[B], out_vals[B, n_out], n_match scalar i32)

        ``n_match`` is the async-emit count gate: the host fetches this
        ONE scalar per batch and skips the column fetch entirely when it
        is zero (the common case for selective filters).

        Jitted, the program takes the one packed buffer a chunk puts
        (``_pad_lanes``) in place of the five arguments behind the
        state: ``step(state, buf int32 [k, B])``.

        ``scoped=False`` (a stage of a fused chain, ops/fused_graph.py):
        the filter, and the select of the stateless kind, open no
        ``siddhi.window.*`` scope: the chain names them by the stage's
        place.  A window's own phases keep theirs.
        """
        key = ("step", jit, scoped)
        if key in self._step_cache:
            return self._step_cache[key]
        jnp = self.jnp
        A = max(len(self.aggs), 1)

        named_scope = (self.jax.named_scope if scoped
                       else lambda _name: contextlib.nullcontext())

        def step(state, cols, ts, grp, wgrp, valid):
            B = ts.shape[0]
            with named_scope(SCOPE_WINDOW_FILTER):
                env = self._base_env(cols, ts, B)
                fmask = self._filter_mask(env, valid)

            if self.kind == "filter":
                env_out = env
                with named_scope(SCOPE_WINDOW_EMIT):
                    ov, out = self._emit(env_out, fmask, B)
                return state, ov, out

            argvals = self._arg_vals(env, B)  # [B, A]

            if self.kind == "running":
                # within-batch same-group prefix (includes self): the
                # [B, B] masked matmul rides the MXU
                tri = jnp.tril(jnp.ones((B, B), dtype=jnp.float32))
                same = (grp[:, None] == grp[None, :]) & fmask[None, :]
                m = tri * same.astype(jnp.float32)  # [B, B]
                masked_vals = argvals * fmask[:, None].astype(jnp.float32)
                psum = _mm_f32(m, masked_vals)  # [B, A]
                pcnt = _mm_f32(
                    m, fmask[:, None].astype(jnp.float32))  # [B, 1]
                kinds = self._kinds()
                prev_sum = state.get("acc_sum")
                wsum = ((prev_sum[grp] if prev_sum is not None else 0.0)
                        + psum)
                wcnt = state["acc_cnt"][grp][:, :1] + pcnt
                wsumsq = None
                if "acc_sumsq" in state:
                    wsumsq = (state["acc_sumsq"][grp]
                              + _mm_f32(m, masked_vals * argvals))
                # one prefix pass covers min/max AND the forever pair
                wmin = wmax = None
                pmin, pmax = self._prefix_minmax(
                    argvals, grp, fmask, B,
                    bool(kinds & {"min", "minForever"}),
                    bool(kinds & {"max", "maxForever"}))
                if "min" in kinds:
                    wmin = jnp.minimum(state["acc_min"][grp], pmin)
                if "max" in kinds:
                    wmax = jnp.maximum(state["acc_max"][grp], pmax)
                fmin, fmax = self._forever_rows(state, argvals, grp,
                                                fmask, B, pmin, pmax)
                env_out = dict(env)
                self._finalize_aggs(env_out, wsum, wcnt, wsumsq, wmin,
                                    wmax, fmin, fmax)
                # state update (scatter; duplicate group rows combine)
                new_state = dict(state)
                upd = fmask[:, None]
                if "acc_sum" in state:
                    new_state["acc_sum"] = state["acc_sum"].at[grp].add(
                        jnp.where(upd, argvals, 0.0))
                if "acc_sumsq" in state:
                    new_state["acc_sumsq"] = state["acc_sumsq"].at[grp].add(
                        jnp.where(upd, argvals * argvals, 0.0))
                new_state["acc_cnt"] = state["acc_cnt"].at[grp].add(
                    jnp.where(upd, jnp.ones_like(argvals), 0.0))
                if "acc_min" in state:
                    new_state["acc_min"] = state["acc_min"].at[grp].min(
                        jnp.where(upd, argvals, jnp.inf))
                if "acc_max" in state:
                    new_state["acc_max"] = state["acc_max"].at[grp].max(
                        jnp.where(upd, argvals, -jnp.inf))
                self._forever_scatter(state, new_state, argvals, grp, fmask)
                ov, out = self._emit(env_out, fmask, B)
                return new_state, ov, out

            if self.kind == "keyed_sliding":
                return self._keyed_sliding_step(
                    state, env, fmask, ts, grp, wgrp, B)

            # sliding: compact passing rows, gather [B, W] windows
            return self._sliding_step(state, env, fmask, ts, grp, B)

        def step_counted(state, cols, ts, grp, wgrp, valid):
            new_state, ov, out = step(state, cols, ts, grp, wgrp, valid)
            with named_scope(SCOPE_WINDOW_COUNT):
                n = jnp.sum((ov.astype(bool) & valid).astype(jnp.int32))
            return new_state, ov, out, n

        def packed(state, buf):
            return step_counted(state, *self._unpack_lanes(buf))

        fn = (self.jax.jit(packed, donate_argnums=(0,)) if jit
              else step_counted)
        self._step_cache[key] = fn
        return fn

    def _keyed_sliding_step(self, state, env, fmask, ts, grp, wgrp, B):
        """Per-key sliding window (partition mode): each window group
        (partition key) owns one [W] ring-buffer row, so a row's window
        is ITS key's last W passing events — the reference's
        per-instance window (partition/PartitionStreamReceiver.java:
        82-118) as [n_wgroups, W] device state.  Aggregation masks
        further restrict to the composed (key, group-by) group.  All
        batch work is [B, B] / [B, W] masked reductions (the [B, B]
        matmul rides the MXU); state updates are unique-slot scatters."""
        jnp = self.jnp
        W = self.W
        # row count from the state, not self.n_wgroups: under the
        # sharded wrapper each shard sees only its slice of the window
        # groups (plus a scratch row), and every scatter below must pad
        # against the LOCAL row count
        Gw = state["win_count"].shape[0]
        argvals = self._arg_vals(env, B)  # [B, A]
        tril = jnp.tril(jnp.ones((B, B), dtype=bool))
        samew = (wgrp[:, None] == wgrp[None, :]) & fmask[None, :]
        # passing rank within the row's window group (includes self)
        r = jnp.sum(samew & tril, axis=1).astype(jnp.int32)  # [B]
        n_w = jnp.sum(samew, axis=1).astype(jnp.int32)  # whole-batch count
        # batch-side membership: among the last W passing events of the
        # row's window group
        mb = samew & tril & ((r[:, None] - r[None, :]) < W)
        # buffer-side membership: recency rank (0 = newest buffered)
        # shifted by the r batch arrivals that displace old entries
        b_vals = state["win_vals"][wgrp]  # [B, W, A]
        b_ts = state["win_ts"][wgrp]  # [B, W]
        b_grp = state["win_grp"][wgrp]  # [B, W]
        b_valid = state["win_valid"][wgrp]  # [B, W]
        cnt = state["win_count"][wgrp]  # [B]
        slots = jnp.arange(W)[None, :]
        rec = jnp.mod(cnt[:, None] - 1 - slots, W)
        mbuf = b_valid & ((rec + r[:, None]) < W)
        if self.window_name == "time":
            T = self.window_param
            mb = mb & (ts[None, :] > (ts[:, None] - T))
            mbuf = mbuf & (b_ts > (ts[:, None] - T))
        # aggregation masks: composed group within the key's window
        mba = mb & (grp[None, :] == grp[:, None])
        mbufa = mbuf & (b_grp == grp[:, None])
        f32 = jnp.float32
        kinds = self._kinds()
        bsum = _mm_f32(mba.astype(f32), argvals)  # [B, A]
        bcnt = jnp.sum(mba, axis=1).astype(f32)[:, None]  # [B, 1]
        usum = jnp.sum(b_vals * mbufa.astype(f32)[:, :, None], axis=1)
        ucnt = jnp.sum(mbufa, axis=1).astype(f32)[:, None]
        wsum = bsum + usum
        wcnt = bcnt + ucnt
        wsumsq = None
        if "stdDev" in kinds:
            wsumsq = (_mm_f32(mba.astype(f32), argvals * argvals)
                      + jnp.sum(b_vals * b_vals
                                * mbufa.astype(f32)[:, :, None], axis=1))
        env_out = dict(env)
        big = jnp.float32(np.inf)
        wmin = wmax = None
        if "min" in kinds:
            wmin = jnp.minimum(
                jnp.min(jnp.where(mba[:, :, None], argvals[None, :, :], big),
                        axis=1),
                jnp.min(jnp.where(mbufa[:, :, None], b_vals, big), axis=1))
        if "max" in kinds:
            wmax = jnp.maximum(
                jnp.max(jnp.where(mba[:, :, None], argvals[None, :, :], -big),
                        axis=1),
                jnp.max(jnp.where(mbufa[:, :, None], b_vals, -big), axis=1))
        fmin, fmax = self._forever_rows(state, argvals, grp, fmask, B)
        self._finalize_aggs(env_out, wsum, wcnt, wsumsq, wmin, wmax,
                            fmin, fmax)
        ov, out = self._emit(env_out, fmask, B)
        # state update: each kept passing row scatters to its ring slot
        # (slot = (count + r - 1) mod W).  Rows already displaced within
        # this batch, and padded/filtered rows, dump to the scratch row
        # Gw so no two real writes ever collide.
        keep = fmask & ((n_w - r) < W)
        slot = jnp.mod(cnt + r - 1, W)
        widx = jnp.where(keep, wgrp, Gw)

        def pad(x):
            return jnp.concatenate(
                [x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0)

        new_state = dict(state)
        new_state["win_vals"] = (
            pad(state["win_vals"]).at[widx, slot].set(argvals)[:Gw])
        new_state["win_ts"] = (
            pad(state["win_ts"]).at[widx, slot].set(ts)[:Gw])
        new_state["win_grp"] = (
            pad(state["win_grp"]).at[widx, slot].set(grp)[:Gw])
        new_state["win_valid"] = (
            pad(state["win_valid"]).at[widx, slot].set(True)[:Gw])
        new_state["win_count"] = (
            pad(state["win_count"])
            .at[jnp.where(fmask, wgrp, Gw)].add(1)[:Gw])
        self._forever_scatter(state, new_state, argvals, grp, fmask)
        return new_state, ov, out

    def make_acc_step(self, jit: bool = True) -> Callable:
        """Tumbling accumulate step:
        (state, cols, ts, grp, grp_key_vals[B,K], valid)
          -> (state, n_passing)."""
        key = ("acc", jit)
        if key in self._step_cache:
            return self._step_cache[key]
        jnp = self.jnp
        K = max(len(self._numeric_group_keys), 1)

        def acc(state, cols, ts, grp, gkv, valid):
            B = ts.shape[0]
            env = self._base_env(cols, ts, B)
            fmask = self._filter_mask(env, valid)
            argvals = self._arg_vals(env, B)
            upd = fmask[:, None]
            new_state = dict(state)
            if "acc_sum" in state:
                new_state["acc_sum"] = state["acc_sum"].at[grp].add(
                    jnp.where(upd, argvals, 0.0))
            if "acc_sumsq" in state:
                new_state["acc_sumsq"] = state["acc_sumsq"].at[grp].add(
                    jnp.where(upd, argvals * argvals, 0.0))
            new_state["acc_cnt"] = state["acc_cnt"].at[grp].add(
                jnp.where(upd, jnp.ones_like(argvals), 0.0))
            if "acc_min" in state:
                new_state["acc_min"] = state["acc_min"].at[grp].min(
                    jnp.where(upd, argvals, jnp.inf))
            if "acc_max" in state:
                new_state["acc_max"] = state["acc_max"].at[grp].max(
                    jnp.where(upd, argvals, -jnp.inf))
            self._forever_scatter(state, new_state, argvals, grp, fmask)
            new_state["touched"] = state["touched"].at[grp].max(fmask)
            # group-key registers: scatter only PASSING rows (filtered
            # rows go to a dump row G) — a same-batch passing+filtered
            # pair for one group would otherwise write two different
            # values in XLA-undefined order; every value written to a
            # real group row is the true (constant-per-group) key
            G = state["grp_keys"].shape[0]
            dump_idx = jnp.where(fmask, grp, G)
            padded = jnp.concatenate(
                [state["grp_keys"],
                 jnp.zeros((1,) + state["grp_keys"].shape[1:], jnp.float32)],
                axis=0)
            new_state["grp_keys"] = padded.at[dump_idx].set(
                gkv.astype(jnp.float32))[:G]
            return new_state, jnp.sum(fmask.astype(jnp.int32))

        fn = self.jax.jit(acc, donate_argnums=(0,)) if jit else acc
        self._step_cache[key] = fn
        return fn

    def make_flush_step(self, jit: bool = True,
                        n_rows: Optional[int] = None) -> Callable:
        """Tumbling flush: (state) -> (state, flush_valid[G],
        out[G, n_out], n_match scalar i32) — the count gates the host
        fetch exactly like make_step's.

        ``n_rows`` overrides the accumulator row count (default
        ``self.n_groups``): the sharded wrapper traces this body per
        shard over its local rows-per-shard slice (whose scratch row is
        never touched, so it never emits)."""
        key = ("flush", jit, n_rows)
        if key in self._step_cache:
            return self._step_cache[key]
        jnp = self.jnp
        G = self.n_groups if n_rows is None else int(n_rows)

        def flush(state):
            env = {N_KEY: G}
            self._finalize_aggs(
                env,
                state.get("acc_sum", state["acc_cnt"]),
                state["acc_cnt"][:, :1],
                state.get("acc_sumsq"),
                state.get("acc_min"),
                state.get("acc_max"),
                state.get("acc_minf"),
                state.get("acc_maxf"),
            )
            for ki, i in enumerate(self._numeric_group_keys):
                g = self.group_raw[i]
                if isinstance(g, Variable):
                    env[g.attribute] = state["grp_keys"][:, ki]
            valid = state["touched"]
            ov, out = self._emit(env, valid, G)
            # pane reset: sums/counts/min/max restart; the all-time
            # minForever/maxForever accumulators survive flushes
            new_state = dict(state)
            for k in ("acc_sum", "acc_cnt", "acc_sumsq"):
                if k in state:
                    new_state[k] = jnp.zeros_like(state[k])
            if "acc_min" in state:
                new_state["acc_min"] = jnp.full_like(state["acc_min"], jnp.inf)
            if "acc_max" in state:
                new_state["acc_max"] = jnp.full_like(state["acc_max"], -jnp.inf)
            new_state["touched"] = jnp.zeros_like(state["touched"])
            return new_state, ov, out, jnp.sum(ov.astype(jnp.int32))

        fn = self.jax.jit(flush, donate_argnums=(0,)) if jit else flush
        self._step_cache[key] = fn
        return fn

    def make_pane_step(self, jit: bool = True) -> Callable:
        """Every lengthBatch pane a batch closes, in one program:

        panes(cols {lane: [B]}, grp[B] i32)
          -> (out_valid[B], out {name: [B]}, n_match scalar i32)

        ``B`` is a whole number of panes: the lanes hold passing rows
        only, pane after pane (the host joined the open pane's carried
        rows ahead of the batch's and dropped what the filters drop), a
        lane past the last closed pane has ``grp`` -1.  Lanes are tiled
        ``[L, panes]`` (panes on the minor axis); each row is reduced
        over the rows of its group in its pane under an ``[L, L, panes]``
        mask — at most ``L`` float32 terms a sum, never a difference of
        batch-long prefixes — and the row that is its group's last in
        the pane emits, so a pane's rows come in the host engine's
        order.  No state: the open pane lives host-side as rows.

        Jitted, the program takes the one packed buffer ``_pane_chunk``
        puts (``_pack``): ``panes(buf int32 [k, B])``, a row for each of
        ``pane_lanes()``, then the group ids."""
        key = ("panes", jit)
        if key in self._step_cache:
            return self._step_cache[key]
        jnp = self.jnp
        named_scope = self.jax.named_scope
        L = int(self.window_param)
        kinds = self._kinds()

        def panes(cols, grp):
            B = grp.shape[0]
            P = B // L

            def tile(x):    # [B, ...] -> [L, P, ...]
                return jnp.swapaxes(x.reshape((P, L) + x.shape[1:]), 0, 1)

            def lanes(x):   # [L, P, ...] -> [B, ...]
                return jnp.swapaxes(x, 0, 1).reshape((B,) + x.shape[2:])

            with named_scope(SCOPE_PANE_ASSIGN):
                env = cols.copy()
                env[N_KEY] = B
                g = tile(grp)
                ok = g >= 0
                v = tile(self._arg_vals(env, B))        # [L, P, A]
            with named_scope(SCOPE_PANE_REDUCE):
                # same[i, j, p]: row j of pane p is of row i's group
                same = (g[:, None, :] == g[None, :, :]) & ok[None, :, :]
                m4 = same[:, :, :, None]
                wsum = lanes(jnp.sum(jnp.where(m4, v[None], 0.0), axis=1))
                wcnt = lanes(jnp.sum(same, axis=1).astype(
                    jnp.float32))[:, None]
                wsumsq = (lanes(jnp.sum(jnp.where(m4, (v * v)[None], 0.0),
                                        axis=1))
                          if "stdDev" in kinds else None)
                wmin = (lanes(jnp.min(jnp.where(m4, v[None], jnp.inf),
                                      axis=1))
                        if "min" in kinds else None)
                wmax = (lanes(jnp.max(jnp.where(m4, v[None], -jnp.inf),
                                      axis=1))
                        if "max" in kinds else None)
            with named_scope(SCOPE_PANE_EMIT):
                later = jnp.triu(jnp.ones((L, L), dtype=bool), k=1)
                last = lanes(ok & ~jnp.any(same & later[:, :, None],
                                           axis=1))
                self._finalize_aggs(env, wsum, wcnt, wsumsq, wmin, wmax)
                ov, out = self._emit(env, last, B)
            with named_scope(SCOPE_PANE_COUNT):
                n = jnp.sum(ov.astype(jnp.int32))
            return ov, out, n

        fn = panes
        if jit:
            names = [*self.pane_lanes(), GRP_KEY]

            def packed(buf):
                rows = self._unpack(buf, names)
                grp = rows.pop(GRP_KEY)
                return panes(rows, grp)

            fn = self.jax.jit(packed)
        self._step_cache[key] = fn
        return fn

    def pane_lanes(self) -> List[str]:
        """The input lanes the pane program reads (aggregate arguments,
        numeric group keys inside select expressions and having), found
        by tracing it over an ``env`` that records its lookups: a lane
        nothing looks at would cost a transfer of its own in every
        put."""
        key = ("pane_lanes",)
        if key in self._step_cache:
            return self._step_cache[key]
        seen = set()
        L = int(self.window_param)
        shapes = {k: v for k, v in self._env_shapes(L).items()
                  if k in self._lane_dtype or k == TS_KEY}
        panes = self.make_pane_step(jit=False)
        self.jax.eval_shape(
            lambda cols, grp: panes(RecordingEnv(cols, seen), grp), shapes,
            self.jax.ShapeDtypeStruct((L,), np.int32))
        read = [k for k in shapes if k in seen]
        self._step_cache[key] = read
        return read

    # -- host wrapper --------------------------------------------------------

    # re-anchor before relative ms approach int32 range (~24.8 days of
    # stream time); headroom covers one batch + window horizon
    _REL_LIMIT = 2**31 - 2**24

    def _re_anchor(self, state, rel64: np.ndarray):
        """Shift base_ts forward so relative timestamps stay well inside
        int32 (they silently wrap after ~24.8 days otherwise — sliding
        time windows and timeBatch panes would corrupt).  Live window
        entries and the open pane boundary shift with it."""
        horizon = (
            int(self.window_param) if self.window_name in ("time", "timeBatch")
            else 0
        )
        delta = int(rel64.min()) - 1 - horizon
        # all representability checks BEFORE any mutation, so a caller
        # catching the error keeps a consistent (anchor, window-state)
        # pair for subsequent batches
        if delta <= 0 or int(rel64.max()) - delta >= 2**31:
            raise SiddhiAppRuntimeError(
                "device query: timestamp span of one batch plus the window "
                "horizon exceeds the int32 relative-time range")
        self.base_ts += delta
        rel64 = rel64 - delta
        if "win_ts" in state:
            state = dict(state)
            # entries older than the horizon go negative and stay
            # excluded; a delta beyond int32 means EVERY buffered entry
            # is expired, so the shift clamps (old values in [0, 2^31)
            # minus the clamp land in (-2^31, 1) — no wrap either way)
            shift = np.int32(min(delta, 2**31 - 1))
            state["win_ts"] = state["win_ts"] - shift
        if self._pane_end is not None:
            self._pane_end -= delta
        return state, rel64

    def _host_env(self, cols: Dict[str, np.ndarray], ts: np.ndarray,
                  n: int) -> Dict:
        env = {a: np.asarray(cols[a]) for a in self.all_attrs if a in cols}
        env[TS_KEY] = np.asarray(ts)
        env[N_KEY] = n
        return env

    def _intern_groups(self, cols: Dict[str, np.ndarray],
                       ts: np.ndarray, n: int,
                       pk: Optional[np.ndarray] = None,
                       now: Optional[int] = None) -> np.ndarray:
        """Evaluate group-key exprs host-side and intern to dense ids.
        In partition mode (``pk`` given) the interned key is the
        composed tuple ``(partition_key, *group_keys)``."""
        if not self.group_exprs and pk is None:
            return np.zeros(n, dtype=np.int32)
        env = self._host_env(cols, ts, n)
        key_cols = [np.broadcast_to(np.asarray(g.fn(env)), (n,))
                    for g in self.group_exprs]
        if pk is not None:
            key_cols = [np.broadcast_to(pk, (n,))] + key_cols
        if len(key_cols) == 1 and pk is None:
            try:
                # vectorized: factorize the batch once; one dict probe
                # per UNIQUE value instead of per event
                uniq, inv = np.unique(key_cols[0], return_inverse=True)
            except TypeError:  # unorderable (None in an object column)
                return self._intern_rows(key_cols, n, now, scalar=True)
            out_u = np.empty(len(uniq), dtype=np.int32)
            for i, k in enumerate(uniq.tolist()):
                out_u[i] = self._alloc_group(k, now)
            return out_u[inv].astype(np.int32, copy=False)
        # multi-column / composed keys: combine per-column factor codes
        # so the dict is probed once per UNIQUE combination, not per
        # row.  Falls back to the exact per-row probe when a column is
        # unorderable (None in an object column) or the radix product
        # would overflow int64 (which would silently merge distinct
        # combinations).
        try:
            code = np.zeros(n, dtype=np.int64)
            radix = 1
            for c in key_cols:
                u, inv = np.unique(c, return_inverse=True)
                radix *= len(u) + 1
                if radix > 2**62:
                    raise OverflowError("group-key radix product")
                code = code * (len(u) + 1) + inv
        except (TypeError, OverflowError):
            return self._intern_rows(key_cols, n, now)
        _uc, first, cinv = np.unique(
            code, return_index=True, return_inverse=True)
        out_u = np.empty(len(first), dtype=np.int32)
        for j, fi in enumerate(first.tolist()):
            k = tuple(c[fi].item() if hasattr(c[fi], "item") else c[fi]
                      for c in key_cols)
            out_u[j] = self._alloc_group(k, now)
        return out_u[cinv].astype(np.int32, copy=False)

    def _intern_rows(self, key_cols, n: int, now, scalar: bool = False
                     ) -> np.ndarray:
        """Exact per-row interning (the fallback for unorderable or
        radix-overflowing key columns)."""
        out = np.empty(n, dtype=np.int32)
        for i in range(n):
            parts = tuple(c[i].item() if hasattr(c[i], "item") else c[i]
                          for c in key_cols)
            out[i] = self._alloc_group(parts[0] if scalar else parts, now)
        return out

    @staticmethod
    def _alloc_id(k, ids: Dict, vals: List, free: List[int],
                  last: Dict, limit: int, what: str,
                  now: Optional[int]) -> int:
        """Shared free-listed id allocator for group/window-group
        interning (purged ids are reused after their rows are zeroed)."""
        gid = ids.get(k)
        if gid is None:
            if free:
                gid = free.pop()
                vals[gid] = k
            else:
                gid = len(vals)
                if gid >= limit:
                    raise SiddhiAppRuntimeError(what)
                vals.append(k)
            ids[k] = gid
        if now is not None:
            last[gid] = now
        return gid

    def _alloc_group(self, k, now: Optional[int] = None) -> int:
        return self._alloc_id(
            k, self._group_ids, self._group_vals, self._group_free,
            self._group_last, self.n_groups,
            f"device query: group cardinality exceeded "
            f"n_groups={self.n_groups}", now)

    _WGRP_CAP_MSG = (
        "device query: partition-key cardinality exceeded "
        "{cap} (raise @app:execution partitions or enable @purge)")

    def _intern_wgroups(self, pk: np.ndarray, now: int) -> np.ndarray:
        """Partition-key values -> dense window-group ids.

        Vectorized: one np.unique per batch; EXISTING keys resolve with
        one searchsorted against a sorted key index; only never-seen
        keys take the python allocation path; last-use stamps update as
        one array scatter.  Object/mixed key dtypes degrade permanently
        to exact per-unique dict probes (same contract as
        core/dense_pattern.py:317 intern_keys)."""
        arr = np.asarray(pk)
        if self._wgrp_vector:
            sk = self._wgrp_sorted_keys
            if arr.dtype.kind in ("O", "V"):
                self._wgrp_vector = False
            elif sk is not None and len(sk) and arr.dtype != sk.dtype:
                if np.can_cast(arr.dtype, sk.dtype, "safe"):
                    arr = arr.astype(sk.dtype)
                elif np.can_cast(sk.dtype, arr.dtype, "safe"):
                    self._wgrp_sorted_keys = sk.astype(arr.dtype)
                else:
                    self._wgrp_vector = False
        if not self._wgrp_vector:
            uniq, inv = np.unique(arr, return_inverse=True)
            out_u = np.empty(len(uniq), dtype=np.int32)
            for i, k in enumerate(uniq.tolist()):
                out_u[i] = self._alloc_wgrp(k, now)
            return out_u[inv].astype(np.int32, copy=False)

        uniq, inv = np.unique(arr, return_inverse=True)
        nu = len(uniq)
        out_u = np.empty(nu, dtype=np.int32)
        sk = self._wgrp_sorted_keys
        if sk is not None and len(sk):
            pos = np.searchsorted(sk, uniq)
            pos_c = np.minimum(pos, len(sk) - 1)
            found = sk[pos_c] == uniq
            out_u[found] = self._wgrp_sorted_ids[pos_c[found]]
            new_idx = np.flatnonzero(~found)
        else:
            new_idx = np.arange(nu)
        if len(new_idx):
            n_new = len(new_idx)
            take_free = min(len(self._wgrp_free), n_new)
            fresh = n_new - take_free
            if len(self._wgrp_vals) + fresh > self.n_wgroups:
                raise SiddhiAppRuntimeError(
                    self._WGRP_CAP_MSG.format(cap=self.n_wgroups))
            ids = np.empty(n_new, dtype=np.int32)
            if take_free:
                ids[:take_free] = self._wgrp_free[-take_free:][::-1]
                del self._wgrp_free[-take_free:]
            if fresh:
                base = len(self._wgrp_vals)
                ids[take_free:] = np.arange(base, base + fresh,
                                            dtype=np.int32)
                self._wgrp_vals.extend(uniq[new_idx][take_free:].tolist())
            new_keys = uniq[new_idx]
            for k, wid in zip(new_keys.tolist(), ids.tolist()):
                self._wgrp_ids[k] = wid
                self._wgrp_vals[wid] = k
            out_u[new_idx] = ids
            # merge the (sorted) new keys into the sorted index
            if sk is None or not len(sk):
                self._wgrp_sorted_keys = new_keys.copy()
                self._wgrp_sorted_ids = ids.copy()
            else:
                ins = np.searchsorted(sk, new_keys)
                self._wgrp_sorted_keys = np.insert(sk, ins, new_keys)
                self._wgrp_sorted_ids = np.insert(
                    self._wgrp_sorted_ids, ins, ids)
        self._wgrp_last[out_u] = now
        self._wgrp_in_use[out_u] = True
        return out_u[inv].astype(np.int32, copy=False)

    def _alloc_wgrp(self, k, now: int) -> int:
        # the shared allocator writes last[wid] = now, which indexes the
        # ndarray the same way it indexed the old dict
        wid = self._alloc_id(
            k, self._wgrp_ids, self._wgrp_vals, self._wgrp_free,
            self._wgrp_last, self.n_wgroups,
            self._WGRP_CAP_MSG.format(cap=self.n_wgroups), now)
        self._wgrp_in_use[wid] = True
        return wid

    def purge_idle_keys(self, state, now: int, idle_ms: Optional[int],
                        remap=None, wremap=None):
        """Reclaim device state rows of partition keys idle for
        ``idle_ms`` (the analog of PartitionRuntime dropping idle
        per-key instances; ids return to the free lists after their
        rows are zeroed).  ``remap`` maps logical group ids to state
        row ids and ``wremap`` window-group ids to ring-buffer row ids
        (the sharded wrapper's shard-major bijections; identity by
        default).  Returns ``(state, n_purged_keys)``."""
        if not self.partition_mode or idle_ms is None:
            return state, 0
        dead_w = np.flatnonzero(
            self._wgrp_in_use & (now - self._wgrp_last >= idle_ms)
        ).tolist()
        if not dead_w:
            return state, 0
        jnp = self.jnp
        state = dict(state)
        dead_pk = {self._wgrp_vals[w] for w in dead_w}
        if self.group_exprs:
            # composed groups die with their partition key (the host
            # instance dies whole); key-active groups stay even if the
            # group itself has been quiet
            dead_g = [gid for k, gid in self._group_ids.items()
                      if k[0] in dead_pk]
        else:
            dead_g = list(dead_w)  # grp aliases wgrp
        if dead_g:
            # group-axis accumulators (running totals + all-time
            # forever values) die with their partition key
            rows = np.asarray(dead_g, dtype=np.int64)
            if remap is not None:
                rows = remap(rows)
            gi = jnp.asarray(rows.astype(np.int32))
            for key in ("acc_sum", "acc_cnt", "acc_sumsq"):
                if key in state:
                    state[key] = state[key].at[gi].set(0.0)
            for key, init in (("acc_min", jnp.inf), ("acc_minf", jnp.inf),
                              ("acc_max", -jnp.inf), ("acc_maxf", -jnp.inf)):
                if key in state:
                    state[key] = state[key].at[gi].set(init)
        if self.kind == "keyed_sliding":
            wrows = np.asarray(dead_w, dtype=np.int64)
            if wremap is not None:
                wrows = wremap(wrows)
            wi = jnp.asarray(wrows.astype(np.int32))
            state["win_valid"] = state["win_valid"].at[wi].set(False)
            state["win_count"] = state["win_count"].at[wi].set(0)
        for w in dead_w:
            del self._wgrp_ids[self._wgrp_vals[w]]
            self._wgrp_vals[w] = None
            self._wgrp_free.append(w)
        self._wgrp_in_use[dead_w] = False
        if self._wgrp_sorted_keys is not None and len(self._wgrp_sorted_keys):
            keep = ~np.isin(self._wgrp_sorted_ids,
                            np.asarray(dead_w, dtype=np.int32))
            self._wgrp_sorted_keys = self._wgrp_sorted_keys[keep]
            self._wgrp_sorted_ids = self._wgrp_sorted_ids[keep]
        if self.group_exprs:
            for gid in dead_g:
                del self._group_ids[self._group_vals[gid]]
                self._group_vals[gid] = None
                self._group_free.append(gid)
                self._group_last.pop(gid, None)
        return state, len(dead_w)

    def host_lane_cols(self, cols, n: int) -> Dict[str, np.ndarray]:
        """Raw input columns -> device-lane numpy columns (lane-dtype
        casts + LONG hi/lo splits), un-padded — the sharded wrapper
        routes these per shard before device_put."""
        out: Dict[str, np.ndarray] = {}
        for k in self.attrs:
            lane = self._lane_dtype[k]
            out[k] = (np.asarray(cols[k])[:n].astype(lane, copy=False)
                      if k in cols else np.zeros(n, dtype=lane))
        for k in self.long_attrs:
            if k in cols:
                hi, lo = _split_i64(np.asarray(cols[k])[:n])
            else:
                hi = np.zeros(n, dtype=np.int32)
                lo = np.zeros(n, dtype=np.int32)
            out[k + "|hi"], out[k + "|lo"] = hi, lo
        return out

    def _pack(self, lanes: Dict[str, Optional[np.ndarray]], n: int,
              B: int) -> np.ndarray:
        """Named host lanes as ONE buffer, ``int32 [k, B]``, a row a
        lane, zeros past its first ``n`` entries (a lane given as None:
        all zeros).  A float32 lane is written through a float32 view
        of its row, so what crosses is its bit pattern, not its value;
        ``_unpack`` is the device's half."""
        buf = np.zeros((len(lanes), B), dtype=np.int32)
        for row, (k, v) in zip(buf, lanes.items()):
            if v is None:
                continue
            lane = self._lane_dtype.get(k, np.int32)
            row.view(np.float32 if lane == np.float32 else np.int32)[:n] = (
                np.asarray(v)[:n].astype(lane, copy=False))
        return buf

    def _unpack(self, buf, names: List[str]) -> Dict:
        """Traced: the rows of a packed buffer by name, each in its
        lane's dtype again (static slices; float32 by
        ``bitcast_convert_type``)."""
        rows = {}
        for k, row in zip(names, buf):
            lane = self._lane_dtype.get(k, np.int32)
            if lane == np.float32:
                row = self.jax.lax.bitcast_convert_type(row, self.jnp.float32)
            rows[k] = row != 0 if lane == np.bool_ else row
        return rows

    def _host_lanes(self, cols, n: int, names: List[str]
                    ) -> Dict[str, Optional[np.ndarray]]:
        """The batch's columns under the lane names ``names`` (None: a
        column the batch does not bring), as ``_pack`` takes them."""
        lanes: Dict[str, Optional[np.ndarray]] = {
            k: cols.get(k) for k in names if "|" not in k}
        # a LONG the expressions compare: both words of its hi/lo pair
        for a in {k.split("|")[0] for k in names if "|" in k}:
            lanes[a + "|hi"], lanes[a + "|lo"] = (
                _split_i64(np.asarray(cols[a])[:n]) if a in cols
                else (None, None))
        return lanes

    def _pad_lanes(self, cols, rel, grp, n, wgrp=None) -> np.ndarray:
        """The batch as the step's one packed buffer, padded to a power
        of two: a row for each of ``lane_rows``."""
        lanes = self._host_lanes(cols, n, self.read_lanes)
        lanes.update({TS_KEY: rel, GRP_KEY: grp, WGRP_KEY: wgrp,
                      VALID_KEY: np.ones(n, dtype=np.int32)})
        return self._pack({k: lanes[k] for k in self.lane_rows}, n, _pow2(n))

    def _unpack_lanes(self, buf):
        """Traced: the packed buffer back into the step's arguments,
        ``(cols, ts, grp, wgrp, valid)``; a row the buffer does not
        carry as zeros made here."""
        rows = self._unpack(buf, self.lane_rows)
        zeros = self.jnp.zeros(buf.shape[1], self.jnp.int32)
        return ({k: rows[k] for k in self.read_lanes},
                rows.get(TS_KEY, zeros), rows.get(GRP_KEY, zeros),
                rows.get(WGRP_KEY, zeros), rows[VALID_KEY] != 0)

    def _put_lanes(self, lanes):
        # ONE H2D put for the whole padded batch, behind the ingest.put
        # fault site — the single sanctioned ingest transfer
        # (core/ingest_stage.py, tests/test_ingest_guard)
        from siddhi_tpu.core.ingest_stage import staged_put

        return staged_put(lanes, faults=self.faults,
                          stats=getattr(self, "ingest_stats", None))

    def _pad(self, cols, rel, grp, n, wgrp=None):
        """The batch on the device as the separate arrays the tumbling
        sweep's steps take: ``(cols, ts, grp, wgrp, valid, B)``."""
        with span(STAGE_CONVERT, n):
            buf = self._pad_lanes(cols, rel, grp, n, wgrp)
        key = ("unpack",)
        if key not in self._step_cache:
            self._step_cache[key] = self.jax.jit(self._unpack_lanes)
        return (*self._step_cache[key](self._put_lanes(buf)), buf.shape[1])

    def _out_columns(self, vals, sel, gids, in_cols, in_sel,
                     host_env=None, key_cols=None,
                     gvals=None) -> Dict[str, np.ndarray]:
        """Assemble output columns (declared dtypes) for the selected
        rows.  ``vals``: {name: [*]} device column dict; ``sel``: row
        indices into it; ``gids``: group id per output row (None for the
        stateless filter kind — group keys are then evaluated host-side
        from ``host_env``); ``in_cols``/``in_sel``: input batch columns
        + row indices for passthrough items (None for flush outputs,
        which cannot have passthroughs).  ``gvals``: pre-captured group
        key value per output row — deferred emits pass this so a group
        id recycled between enqueue and drain cannot alias the keys."""
        cols: Dict[str, np.ndarray] = {}
        for oi, (kind, v, name) in enumerate(self.out_spec):
            t = self.out_types[oi]
            if kind == "group_key":
                if gids is None and gvals is None:
                    # no interned ids: use the precomputed key columns
                    # (or evaluate the key expr directly)
                    if key_cols is not None:
                        col = key_cols[v]
                    else:
                        n = host_env[N_KEY]
                        col = np.broadcast_to(
                            np.asarray(self.group_exprs[v].fn(host_env)),
                            (n,))
                    cols[name] = col[in_sel].astype(t.np_dtype, copy=False)
                    continue
                comp = (list(gvals) if gvals is not None
                        else [self._group_vals[int(g)] for g in gids])
                if self.partition_mode:
                    # composed tuple is (partition_key, *group_keys)
                    comp = [k[v + 1] for k in comp]
                else:
                    comp = [k[v] if isinstance(k, tuple) else k
                            for k in comp]
                cols[name] = (
                    np.asarray(comp, dtype=t.np_dtype) if comp
                    else np.empty(0, dtype=t.np_dtype))
            elif kind == "passthrough":
                cols[name] = np.asarray(in_cols[v])[in_sel].astype(
                    t.np_dtype, copy=False)
            else:
                cols[name] = vals[name][sel].astype(t.np_dtype)
        return cols

    def _empty_cols(self) -> Dict[str, np.ndarray]:
        return {
            name: np.empty(0, dtype=self.out_types[oi].np_dtype)
            for oi, (_k, _v, name) in enumerate(self.out_spec)
        }

    # group-key side channel of the MOST RECENT process_batch call
    # (host-format scalars/tuples, aligned with its output rows) — the
    # product runtime attaches it as batch.aux['group_keys'] so
    # per-group rate limiters work on device-lowered queries.  None
    # when the query has no group-by (or in partition mode, whose rate
    # limiters are rejected at plan time).
    last_group_keys: Optional[List] = None

    def _keys_for_gids(self, gids) -> List:
        return [self._group_vals[int(g)] for g in gids]

    def _concat_chunks(self, chunks) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        """chunks: [(cols, ts_scalar, n_rows, keys|None)] -> (cols, ts);
        also sets ``last_group_keys`` from the chunk key lists."""
        chunks = [c for c in chunks if c[2]]
        if not chunks:
            self.last_group_keys = [] if self.group_exprs else None
            return self._empty_cols(), np.empty(0, dtype=np.int64)
        names = self.output_names
        out_cols = {
            nm: np.concatenate([c[0][nm] for c in chunks]) for nm in names
        }
        out_ts = np.concatenate(
            [np.full(c[2], c[1], dtype=np.int64) for c in chunks])
        if self.group_exprs:
            self.last_group_keys = [k for c in chunks for k in (c[3] or [])]
        else:
            self.last_group_keys = None
        return out_cols, out_ts

    def process_batch(self, state, cols: Dict[str, np.ndarray],
                      ts: np.ndarray,
                      part_keys: Optional[np.ndarray] = None):
        """Columnar host entry point: ``(state, out_cols, out_ts)`` with
        output columns cast back to the declared attribute types (the
        product runtime builds an EventBatch straight from these).
        ``part_keys`` (partition mode only): raw partition-key value per
        row.  Synchronous wrapper over the deferred path — one
        count-gated, coalesced fetch per call."""
        state, pending = self.process_batch_deferred(state, cols, ts,
                                                     part_keys)
        if pending is not None and pending.resolve() == 0:
            pending = None
        if pending is None:
            self.last_group_keys = (
                [] if self.group_exprs and not self.partition_mode else None)
            return state, self._empty_cols(), np.empty(0, dtype=np.int64)
        from siddhi_tpu.core.emit_queue import fetch_coalesced

        out_cols, out_ts, keys = pending.materialize(
            fetch_coalesced(pending.device_arrays()))
        self.last_group_keys = keys
        return state, out_cols, out_ts

    def process_batch_deferred(self, state, cols: Dict[str, np.ndarray],
                               ts: np.ndarray,
                               part_keys: Optional[np.ndarray] = None):
        """Async-emit entry point: run the jitted step(s) and KEEP the
        match outputs resident on device.  NOTHING crosses the device
        boundary here — even the per-chunk match-count scalar stays on
        device until ``DeferredDeviceEmit.resolve()`` fetches it (the
        ingest stage, core/ingest_stage.py, defers that fetch past the
        next batch's dispatch).  Empty input returns ``(state, None)``;
        otherwise a DeferredDeviceEmit whose ``resolve()`` /
        ``device_arrays()`` / ``materialize(host_arrays)`` triple the
        staging + pending-emit pipeline drains with one count fetch and
        one coalesced column transfer."""
        ts = np.asarray(ts, dtype=np.int64)
        n = len(ts)
        if n == 0:
            return state, None
        if self.partition_mode and part_keys is None:
            raise SiddhiAppRuntimeError(
                "partitioned device query needs per-row partition keys")
        pk = np.asarray(part_keys) if part_keys is not None else None
        pending = DeferredDeviceEmit(self)
        rows = self.chunk_rows or n
        stats = getattr(self, "ingest_stats", None)
        if stats is not None:
            stats.device_chunks += -(-n // rows)
        if n > rows:
            for i in range(0, n, rows):
                sl = slice(i, i + rows)
                state = self._deferred_chunk(
                    state, {k: np.asarray(v)[sl] for k, v in cols.items()},
                    ts[sl], pk[sl] if pk is not None else None, pending)
        else:
            state = self._deferred_chunk(state, cols, ts, pk, pending)
        return state, (pending if pending.chunks else None)

    def _deferred_chunk(self, state, cols, ts, pk, pending):
        """Process one slice of at most ``chunk_rows`` rows; non-empty
        match outputs are appended to ``pending`` as device refs."""
        n = len(ts)
        if self.interns:
            with span(STAGE_INTERN, n):
                now = int(ts.max())
                if self.partition_mode:
                    wgrp = self._intern_wgroups(pk, now)
                    grp = (self._intern_groups(cols, ts, n, pk=pk, now=now)
                           if self.group_exprs else wgrp)
                else:
                    wgrp = None
                    grp = self._intern_groups(cols, ts, n)
        else:
            # nothing to intern: a stateless filter (group-key select
            # items are evaluated host-side at materialize time, so key
            # cardinality is unbounded) or one ungrouped window
            wgrp = None
            grp = np.zeros(n, dtype=np.int32)
        device = self.kind in ("filter", "running", "sliding",
                               "keyed_sliding")
        # one convert span a chunk: relative timestamps, then the lanes
        with span(STAGE_CONVERT, n):
            if self.base_ts is None:
                self.base_ts = int(ts[0]) - 1
            rel64 = ts - self.base_ts
            if int(rel64.max()) >= self._REL_LIMIT:
                state, rel64 = self._re_anchor(state, rel64)
            rel = rel64.astype(np.int32)
            if device:
                buf = self._pad_lanes(cols, rel, grp, n, wgrp)
        if device:
            step = self.make_step()
            buf = self._put_lanes(buf)
            if self.faults is not None:
                self.faults.check("step.device")
            with span(STAGE_DISPATCH, 1):
                state, ov, out, n_match = step(state, buf)
                # the call's input is released with it: dropping the
                # device buffer is time of the dispatch
                del buf
            # the count gate is DEFERRED: ``n_match`` stays a device
            # scalar until ``DeferredDeviceEmit.resolve()`` fetches it
            # (the ingest stage calls resolve only after the NEXT
            # batch's transfer + dispatch are in flight, which is where
            # the H2D/compute overlap comes from).  Group ids are kept
            # host-side so resolve can capture the key values for
            # surviving chunks — resolve always runs before any purge or
            # later interning could recycle a gid (runtimes flush the
            # ingest stage first at every such barrier).
            gids = (grp[:n].copy()
                    if self.group_exprs and self.kind != "filter" else None)
            pending.chunks.append({
                "kind": "device", "ov": ov, "out": dict(out),
                "names": list(out), "n": n, "count": n_match,
                "gids": gids, "ts": ts,
                "cols": {k: np.asarray(v) for k, v in cols.items()},
            })
            return state
        if self.pane_batched:
            self._pane_chunk(cols, ts, rel, grp, n, pending)
            return state
        state, out_cols, out_ts = self._process_tumbling(
            state, cols, rel, grp, n)
        if len(out_ts):
            pending.chunks.append({
                "kind": "host", "cols": out_cols, "ts": out_ts,
                "keys": self.last_group_keys,
            })
        return state

    def _pane_chunk(self, cols, ts, rel, grp, n, pending):
        """One batch of the one-program lengthBatch path: the open
        pane's carried rows and the batch's passing rows, every pane
        they complete through ``make_pane_step`` as one put of one
        packed buffer and one dispatch, the rest carried.  The emit is
        a "device" chunk of ``pending`` like the sliding kind's: a mask
        over the lanes, bare attributes and row timestamps gathered
        host-side from the joined rows at materialize time."""
        L = int(self.window_param)
        read = self.pane_lanes()
        with span(STAGE_PANE) as sp:
            keep = (np.flatnonzero(self._host_filter_mask(cols, rel, n))
                    if self.filters else slice(0, n))
            rows = {a: np.asarray(cols[a])[keep]
                    for a in {*read, *self.bare_attrs} - {TS_KEY}}
            rows[TS_KEY], rows[GRP_KEY] = ts[keep], grp[keep]
            carry, self._pane_carry = self._pane_carry, None
            if carry is not None:
                rows = {k: np.concatenate([carry[k], v])
                        for k, v in rows.items()}
            closed = len(rows[TS_KEY]) // L
            m = closed * L
            if m < len(rows[TS_KEY]):
                self._pane_carry = {k: v[m:].copy() for k, v in rows.items()}
            rows = {k: v[:m] for k, v in rows.items()}
            self.panes_closed += closed
            if sp is not None:
                sp.count = closed
        if not closed:
            return
        with span(STAGE_CONVERT, m):
            # a whole number of panes that holds whatever a batch of
            # this size (to its power of two) and a carry can complete
            B = (_pow2(n) + L - 1) // L * L
            lanes = {a: rows[a] - self.base_ts if a == TS_KEY else rows[a]
                     for a in read}
            lanes[GRP_KEY] = rows[GRP_KEY]
            buf = self._pack(lanes, m, B)
            buf[-1, m:] = -1    # no group: a lane past the last pane
        step = self.make_pane_step()
        buf = self._put_lanes(buf)
        if self.faults is not None:
            self.faults.check("step.device")
        with span(STAGE_DISPATCH, 1):
            ov, out, n_match = step(buf)
            del buf
        stamps, order = rows[TS_KEY], None
        if self.group_exprs and not self.bare_attrs:
            # with no bare attribute a pane's rows come as the per-pane
            # sweep gives them (here, sharded or multiplexed alike): its
            # groups in id order, each stamped with the pane's last row
            order = (np.arange(m, dtype=np.int64) // L * self.n_groups
                     + rows[GRP_KEY])
            stamps = np.repeat(stamps[L - 1::L], L)
        pending.chunks.append({
            "kind": "device", "ov": ov, "out": dict(out),
            "names": list(out), "n": m, "count": n_match,
            "gids": rows[GRP_KEY] if self.group_exprs else None,
            "ts": stamps, "order": order,
            "cols": {a: rows[a] for a in self.bare_attrs},
        })

    def process(self, state, cols: Dict[str, np.ndarray], ts: np.ndarray,
                part_keys: Optional[np.ndarray] = None):
        """Host entry point.  Returns ``(state, rows)`` where rows are
        emitted output dicts in emission order."""
        state, out_cols, out_ts = self.process_batch(state, cols, ts,
                                                     part_keys)
        names = self.output_names
        rows = [
            {nm: out_cols[nm][i] for nm in names}
            for i in range(len(out_ts))
        ]
        return state, rows

    # -- tumbling host logic -------------------------------------------------

    def _gk_vals(self, grp: np.ndarray, n: int) -> np.ndarray:
        K = max(len(self._numeric_group_keys), 1)
        out = np.zeros((n, K), dtype=np.float32)
        for ki, i in enumerate(self._numeric_group_keys):
            for r in range(n):
                k = self._group_vals[int(grp[r])]
                v = k[i] if isinstance(k, tuple) else k
                out[r, ki] = np.float32(v)
        return out

    def _flush_cols(self, state):
        flush = self.make_flush_step()
        state, ov, out, n_match = flush(state)
        # explicit count-gate fetch: int(device_scalar) is an IMPLICIT
        # transfer and would trip jax.transfer_guard('disallow')
        if int(self.jax.device_get(n_match)) == 0:
            # count gate: empty pane — no group/output column fetched
            return state, self._empty_cols(), 0, (
                [] if self.group_exprs else None), None
        gidx = np.flatnonzero(np.asarray(ov))
        out_np = {k: np.asarray(col) for k, col in out.items()}
        reg, stamps = self._last_row, None
        if reg is not None:
            # bare attributes: the host engine's rows, each stamped with
            # its group's last row and in the order of those rows
            gidx = gidx[np.argsort(reg[SEQ_KEY][gidx], kind="stable")]
            stamps = reg[TS_KEY][gidx]
        out_cols = self._out_columns(out_np, gidx, gidx, reg, gidx)
        keys = self._keys_for_gids(gidx) if self.group_exprs else None
        return state, out_cols, len(gidx), keys, stamps

    def _note_last_rows(self, cols, rel, grp, rows, seen):
        """Per-group registers of the open pane's last passing row (the
        per-pane sweep with bare attributes): its place in the stream
        (``seen`` rows came before this batch), its timestamp, and the
        bare attributes at native width."""
        if not len(rows):
            return
        reg = self._last_row
        if reg is None:
            G = self.n_groups
            reg = self._last_row = {
                a: np.zeros(G, dtype=self.stream_def.attribute_type(
                    a).np_dtype) for a in self.bare_attrs}
            reg[SEQ_KEY] = np.zeros(G, dtype=np.int64)
            reg[TS_KEY] = np.zeros(G, dtype=np.int64)
        uniq, at = np.unique(grp[rows][::-1], return_index=True)
        last = rows[len(rows) - 1 - at]
        reg[SEQ_KEY][uniq] = seen + last
        reg[TS_KEY][uniq] = self.base_ts + rel[last].astype(np.int64)
        for a in self.bare_attrs:
            reg[a][uniq] = np.asarray(cols[a])[last]

    def _advance_pane(self):
        """Post-flush timeBatch pane bookkeeping (mirrors the host
        TimeBatchWindow): boundaries advance by T while panes stay
        non-empty; after two consecutive empty panes the window goes
        idle and re-anchors at the next event."""
        if self._pane_fill == 0 and self._prev_pane_fill == 0:
            self._pane_end = None
        else:
            self._pane_end += int(self.window_param)
            self._prev_pane_fill = self._pane_fill
            self._pane_fill = 0

    def pane_wakeup(self) -> Optional[int]:
        """Absolute ms at which the open timeBatch pane closes (the
        scheduler hook driving timer flushes, the host TimeBatchWindow's
        Scheduler.notifyAt analog); None when nothing is pending."""
        if (self.window_name != "timeBatch" or self._pane_end is None
                or self.base_ts is None):
            return None
        return self.base_ts + self._pane_end

    def flush_due(self, state, now: int):
        """Timer-driven flush: close every pane whose boundary <= now.
        Returns (state, out_cols, out_ts)."""
        chunks = []
        while True:
            w = self.pane_wakeup()
            if w is None or w > now:
                break
            state, fcols, nf, keys, stamps = self._flush_cols(state)
            chunks.append((fcols, w if stamps is None else stamps, nf, keys))
            self.panes_closed += 1
            self._advance_pane()
        out_cols, out_ts = self._concat_chunks(chunks)
        return state, out_cols, out_ts

    def _acc_segment(self, state, cols, rel, grp, idx) -> Tuple[object, int]:
        acc = self.make_acc_step()
        n = len(idx)
        c, t, g, _wg, valid, B = self._pad(
            {k: np.asarray(v)[idx] for k, v in cols.items()},
            rel[idx], grp[idx], n)
        gkv = np.zeros((B, max(len(self._numeric_group_keys), 1)),
                       dtype=np.float32)
        gkv[:n] = self._gk_vals(grp[idx], n)
        state, n_pass = acc(state, c, t, g, self.jnp.asarray(gkv), valid)
        # explicit count-gate fetch (transfer_guard-safe, see _flush_cols)
        return state, int(self.jax.device_get(n_pass))

    def _pane_sweep(self, state, cols, rel, grp, n, acc_segment,
                    flush_pane):
        """Shared tumbling pane control flow: walk one batch, feed
        intra-pane segments to ``acc_segment(state, cols, rel, grp,
        idx) -> (state, n_pass)`` and close each crossed boundary via
        ``flush_pane(state, abs_ts) -> state``.  The single-device path
        and the sharded wrapper drive the SAME sweep with their own
        accumulate/flush steps, so pane placement (``_pane_end``,
        lengthBatch fill counts — host scalars either way) cannot
        diverge between them."""
        fmask = (self._host_filter_mask(cols, rel, n)
                 if self.window_name == "lengthBatch"
                 or (self.bare_attrs and self.filters) else None)
        if self.bare_attrs:
            # a bare attribute is its group's last passing row's
            accumulate, seen = acc_segment, self._rows_seen
            self._rows_seen += n

            def acc_segment(state, cols, rel, grp, idx):
                self._note_last_rows(
                    cols, rel, grp,
                    idx if fmask is None else idx[fmask[idx]], seen)
                return accumulate(state, cols, rel, grp, idx)
        if self.window_name == "timeBatch":
            # pane bookkeeping mirrors the host TimeBatchWindow: the
            # first event anchors the boundary, boundaries advance by T
            # while panes stay non-empty, and the window goes idle
            # (re-anchoring at the next event) once a pane and its
            # predecessor are both empty.  Flushes are stamped with the
            # pane boundary time, matching the timer-driven path.
            T = int(self.window_param)
            i = 0
            while i < n:
                if self._pane_end is None:
                    self._pane_end = int(rel[i]) + T
                    self._pane_fill = 0
                    self._prev_pane_fill = 0
                # events belonging to the current pane: ts < pane_end
                j = int(np.searchsorted(rel[i:], self._pane_end,
                                        side="left")) + i
                if j > i:
                    state, n_pass = acc_segment(
                        state, cols, rel, grp, np.arange(i, j))
                    self._pane_fill += n_pass
                    i = j
                if i < n:  # boundary crossed by remaining events
                    state = flush_pane(state, self.base_ts + self._pane_end)
                    self.panes_closed += 1
                    self._advance_pane()
            return state
        # lengthBatch: need passing counts to place flush boundaries,
        # so probe the filter mask first (host-visible)
        L = int(self.window_param)
        i = 0
        while i < n:
            remaining = L - self._pane_fill
            pass_pos = np.flatnonzero(fmask[i:])
            if len(pass_pos) < remaining:
                state, _ = acc_segment(
                    state, cols, rel, grp, np.arange(i, n))
                self._pane_fill += len(pass_pos)
                break
            j = i + int(pass_pos[remaining - 1]) + 1
            state, _ = acc_segment(state, cols, rel, grp,
                                   np.arange(i, j))
            state = flush_pane(state, self.base_ts + int(rel[j - 1]))
            self.panes_closed += 1
            self._pane_fill = 0
            i = j
        return state

    def _process_tumbling(self, state, cols, rel, grp, n):
        chunks = []  # (cols, abs_ts, n_rows, keys|None)

        def flush_pane(st, when):
            st, fcols, nf, keys, stamps = self._flush_cols(st)
            chunks.append((fcols, when if stamps is None else stamps, nf,
                           keys))
            return st

        state = self._pane_sweep(state, cols, rel, grp, n,
                                 self._acc_segment, flush_pane)
        out_cols, out_ts = self._concat_chunks(chunks)
        return state, out_cols, out_ts

    def _host_filter_mask(self, cols, rel, n) -> np.ndarray:
        env = {a: np.asarray(cols[a]) for a in self.all_attrs if a in cols}
        for a in self.long_attrs:  # pair-compiled filters read hi/lo
            if a in cols:
                env[a + "|hi"], env[a + "|lo"] = _split_i64(
                    np.asarray(cols[a])[:n])
        env[TS_KEY] = np.asarray(rel)
        env[N_KEY] = n
        m = np.ones(n, dtype=bool)
        for f in self.filters:
            m = m & np.broadcast_to(np.asarray(f.fn(env)).astype(bool), (n,))
        return m

    # -- snapshot of host-side bookkeeping (device state arrays are
    # snapshotted by the product runtime that owns them) ---------------------

    def host_snapshot(self) -> Dict:
        return {
            "base_ts": self.base_ts,
            "group_ids": dict(self._group_ids),
            "group_vals": list(self._group_vals),
            "group_free": list(self._group_free),
            "group_last": dict(self._group_last),
            "wgrp_ids": dict(self._wgrp_ids),
            "wgrp_vals": list(self._wgrp_vals),
            "wgrp_free": list(self._wgrp_free),
            "wgrp_last": self._wgrp_last.copy(),
            "wgrp_in_use": self._wgrp_in_use.copy(),
            "pane_end": self._pane_end,
            "pane_fill": self._pane_fill,
            "prev_pane_fill": self._prev_pane_fill,
            "pane_carry": _copy_rows(self._pane_carry),
            "last_row": _copy_rows(self._last_row),
            "rows_seen": self._rows_seen,
        }

    def host_restore(self, s: Dict):
        self.base_ts = s["base_ts"]
        self._group_ids = dict(s["group_ids"])
        self._group_vals = list(s["group_vals"])
        self._group_free = list(s.get("group_free", []))
        self._group_last = dict(s.get("group_last", {}))
        self._wgrp_ids = dict(s.get("wgrp_ids", {}))
        self._wgrp_vals = list(s.get("wgrp_vals", []))
        self._wgrp_free = list(s.get("wgrp_free", []))
        last = s.get("wgrp_last")
        self._wgrp_last = np.zeros(self.n_wgroups, dtype=np.int64)
        self._wgrp_in_use = np.zeros(self.n_wgroups, dtype=bool)
        if isinstance(last, dict):
            # legacy dict-format snapshot: convert so restored keys
            # stay visible to the idle purge
            for wid, t in last.items():
                self._wgrp_last[wid] = t
                self._wgrp_in_use[wid] = True
        elif last is not None:
            self._wgrp_last = np.asarray(last, dtype=np.int64).copy()
            in_use = s.get("wgrp_in_use")
            if in_use is not None:
                self._wgrp_in_use = np.asarray(in_use, dtype=bool).copy()
        # rebuild the sorted intern index from the restored key map.
        # np.asarray over MIXED python key types silently stringifies
        # (int 7 and '7' would alias in searchsorted), so mixed-type
        # key sets pin the exact dict fallback instead.
        self._wgrp_sorted_keys = None
        self._wgrp_sorted_ids = None
        self._wgrp_vector = True
        if self._wgrp_ids:
            if len({type(k) for k in self._wgrp_ids}) > 1:
                self._wgrp_vector = False
            else:
                try:
                    keys = np.asarray(list(self._wgrp_ids.keys()))
                    if keys.dtype.kind in ("O", "V"):
                        raise TypeError("object keys")
                    order = np.argsort(keys)
                    self._wgrp_sorted_keys = keys[order]
                    self._wgrp_sorted_ids = np.asarray(
                        list(self._wgrp_ids.values()),
                        dtype=np.int32)[order]
                except Exception:
                    self._wgrp_vector = False
        self._pane_end = s["pane_end"]
        self._pane_fill = s["pane_fill"]
        self._prev_pane_fill = s["prev_pane_fill"]
        self._pane_carry = _copy_rows(s.get("pane_carry"))
        self._last_row = _copy_rows(s.get("last_row"))
        self._rows_seen = s.get("rows_seen", 0)

    # -- introspection -------------------------------------------------------

    @property
    def output_names(self) -> List[str]:
        return [name for _k, _v, name in self.out_spec]


class DeferredDeviceEmit:
    """Device-resident match outputs of one ``process_batch_deferred``
    call (one junction batch; several chunks where it is longer than
    the engine's ``chunk_rows``).  ``resolve()`` fetches the deferred
    count gates (the only blocking point of the whole ingest path — the
    ingest stage times it to land AFTER the next batch's dispatch); the
    pending-emit queue
    (core/emit_queue.py) then fetches ``device_arrays()`` with one
    coalesced transfer and hands the host copies back to
    ``materialize``; the result is byte-identical to what the
    synchronous ``process_batch`` would have returned."""

    __slots__ = ("engine", "chunks", "_total")

    def __init__(self, engine):
        self.engine = engine
        self.chunks: List[dict] = []
        self._total: Optional[int] = None

    def probe(self):
        """A device scalar whose readiness marks step completion for
        this batch (the ingest stage's overlap/stall evidence); None
        when every chunk is host-side."""
        for ch in self.chunks:
            if ch["kind"] in ("device", "flush"):
                return ch["count"]
        return None

    @waits_on_device
    def resolve(self) -> int:
        """Fetch the per-chunk count gates (one ``device_get``, scalars
        only), prune zero-match chunks so their columns are never
        transferred, and capture group-key values for the survivors
        (host-side, from the intern tables — safe because every gid
        purge/restore point flushes the ingest stage, and thus resolves,
        first).  Idempotent; returns the total match count."""
        if self._total is not None:
            return self._total
        dev = [(i, ch["count"]) for i, ch in enumerate(self.chunks)
               if ch["kind"] in ("device", "flush")]
        counts = {}
        if dev:
            import jax

            host = jax.device_get([c for _i, c in dev])
            counts = {i: int(c) for (i, _d), c in zip(dev, host)}
        eng = self.engine
        keep = []
        total = 0
        for i, ch in enumerate(self.chunks):
            if ch["kind"] == "host":
                total += len(ch["ts"])
                keep.append(ch)
                continue
            c = counts[i]
            if c == 0:
                continue  # count gate: zero-match pane/batch — no
                # column ever fetched
            total += c
            if ch["kind"] == "flush":
                # sharded pane flush: the matching group ids are only
                # known once ``ov`` is on the host, so key capture
                # happens in materialize.  Safe without the gvals
                # snapshot: tumbling never runs in partition mode, so
                # its group ids are never purge-recycled.
                keep.append(ch)
                continue
            gids = ch.pop("gids", None)
            ch["gvals"] = (eng._keys_for_gids(gids)
                           if gids is not None else None)
            keep.append(ch)
        self.chunks = keep
        self._total = total
        return total

    def gates(self) -> List[Tuple]:
        """``(count, arrays)`` of every device chunk the batch still
        holds, in ``device_arrays()`` order: before ``resolve()`` every
        dispatched chunk, which is what the pipeline starts for the host
        at dispatch (core/device_pipeline.py)."""
        return [(ch["count"],
                 [ch["ov"]] + [ch["out"][nm] for nm in ch["names"]])
                for ch in self.chunks if ch["kind"] in ("device", "flush")]

    def device_arrays(self) -> List:
        return [a for _count, arrays in self.gates() for a in arrays]

    def materialize(self, host_arrays):
        """``host_arrays``: fetched copies aligned with
        ``device_arrays()``.  Returns ``(out_cols, out_ts, keys)`` —
        the synchronous result triple (keys = the group-key side
        channel, None when the query carries none)."""
        eng = self.engine
        pos = 0
        parts = []  # (out_cols, out_ts, keys|None)
        for ch in self.chunks:
            if ch["kind"] == "host":
                parts.append((ch["cols"], ch["ts"], ch["keys"]))
                continue
            if ch["kind"] == "flush":
                # sharded pane flush: rows are shard-major
                # (owner * rows_per_shard + local); recover the global
                # group id and emit in ascending-gid order, exactly the
                # single-device ``_flush_cols`` ordering
                raw_ov = np.asarray(host_arrays[pos])
                pos += 1
                out_np = {}
                for nm in ch["names"]:
                    out_np[nm] = np.asarray(host_arrays[pos])
                    pos += 1
                rows = np.flatnonzero(raw_ov)
                rps = ch["rows_per_shard"]
                gid = (rows % rps) * ch["n_shards"] + rows // rps
                order = np.argsort(gid, kind="stable")
                sel, gids = rows[order], gid[order]
                out_cols = eng._out_columns(out_np, sel, gids, None, None)
                keys = (eng._keys_for_gids(gids)
                        if eng.group_exprs else None)
                parts.append((out_cols,
                              np.full(len(sel), ch["stamp"],
                                      dtype=np.int64),
                              keys))
                continue
            n = ch["n"]
            # sharded chunks carry a routed-slot map instead of plain
            # front-padding: ``pos`` maps input row -> routed slot
            sel = ch.get("pos")
            raw_ov = np.asarray(host_arrays[pos])
            ov_np = raw_ov[sel] if sel is not None else raw_ov[:n]
            pos += 1
            out_np = {}
            for nm in ch["names"]:
                raw_col = np.asarray(host_arrays[pos])
                out_np[nm] = raw_col[sel] if sel is not None else raw_col[:n]
                pos += 1
            idx = np.flatnonzero(ov_np)
            if ch.get("order") is not None:   # _pane_chunk: rows by key
                idx = idx[np.argsort(ch["order"][idx], kind="stable")]
            cols, ts = ch["cols"], ch["ts"]
            if eng.kind == "filter":
                host_env = eng._host_env(cols, ts, n)
                key_cols = ([np.broadcast_to(
                    np.asarray(g.fn(host_env)), (n,))
                    for g in eng.group_exprs]
                    if eng.group_exprs else None)
                out_cols = eng._out_columns(
                    out_np, idx, None, cols, idx, host_env=host_env,
                    key_cols=key_cols)
                if key_cols and not eng.partition_mode:
                    from siddhi_tpu.core.query import format_group_keys

                    keys = format_group_keys(key_cols, idx)
                else:
                    keys = None
            else:
                gvals = ch["gvals"]
                sel_vals = ([gvals[int(i)] for i in idx]
                            if gvals is not None else None)
                out_cols = eng._out_columns(out_np, idx, None, cols, idx,
                                            gvals=sel_vals)
                keys = (sel_vals
                        if eng.group_exprs and not eng.partition_mode
                        else None)
            parts.append((out_cols, ts[idx], keys))
        return self._concat_parts(parts)

    def _concat_parts(self, parts):
        eng = self.engine
        parts = [p for p in parts if len(p[1])]
        if not parts:
            return (eng._empty_cols(), np.empty(0, dtype=np.int64),
                    [] if eng.group_exprs and not eng.partition_mode
                    else None)
        names = eng.output_names
        out_cols = {
            nm: np.concatenate([p[0][nm] for p in parts]) for nm in names
        }
        out_ts = np.concatenate(
            [np.asarray(p[1], dtype=np.int64) for p in parts])
        key_lists = [p[2] for p in parts]
        if any(k is not None for k in key_lists):
            keys = [k for kl in key_lists for k in (kl or [])]
        else:
            keys = None
        return out_cols, out_ts, keys


# ---------------------------------------------------------------------------
# High-level compile API (mirrors ops.dense_nfa.compile_pattern)
# ---------------------------------------------------------------------------


def compile_query(
    app_str: str,
    query_name: Optional[str] = None,
    n_groups: int = 1024,
    window_capacity: int = 1024,
    partition_mode: bool = False,
    n_wgroups: Optional[int] = None,
) -> DeviceQueryEngine:
    """Compile a SiddhiQL single-stream query into a DeviceQueryEngine."""
    from siddhi_tpu.compiler import SiddhiCompiler
    from siddhi_tpu.query_api.annotation import find_annotation

    app = SiddhiCompiler.parse(app_str)
    query = None
    for i, q in enumerate(app.queries):
        info = find_annotation(q.annotations, "info")
        nm = (info.element("name") if info else None) or f"query_{i}"
        if query_name is None or nm == query_name:
            query = q
            break
    if query is None:
        raise SiddhiAppCreationError(f"query '{query_name}' not found")
    s = query.input_stream
    if not isinstance(s, SingleInputStream):
        raise SiddhiAppCreationError(
            "compile_query needs a single-input-stream query")
    d = app.stream_definitions.get(s.stream_id)
    if d is None:
        raise SiddhiAppCreationError(f"stream '{s.stream_id}' is not defined")
    return DeviceQueryEngine(
        query, d, n_groups=n_groups, window_capacity=window_capacity,
        partition_mode=partition_mode, n_wgroups=n_wgroups)
