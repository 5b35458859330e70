"""Dense (jitted TPU) pattern execution inside the product engine.

This is the glue the planner uses to route `SiddhiManager`-created
pattern/sequence queries through the bit-parallel dense NFA
(ops/dense_nfa.py) instead of the host instance engine (ops/nfa.py) —
the analog of the reference planner wiring the pattern hot path into the
runtime (util/parser/StateInputStreamParser.java:76-146,
QueryParser.java:90), re-designed so the hot path is one jit-compiled
step over partition-sharded state rows instead of a processor chain.

Activation: ``@app:execution('tpu')`` (the north-star gating from
BASELINE.json).  The planner attempts dense lowering for every
pattern/sequence query and falls back to the host engine — logging the
reason — when the query needs semantics outside the dense subset
(leading/sequence absent states, optional min-0 nodes, >32 nodes,
non-numeric captures/filters/selects, partial-chain group-every, ...).
Mid-chain and trailing absent states (`not X for t`) run densely via
per-instance deadline registers and a jitted timer step driven by the
app scheduler (``DensePatternRuntime.on_time``); whole-chain
group-every (`every (e1 -> e2)`) runs densely with an
arm-when-empty virgin.  Overlapping `every` arms
run independently on the engine's instance axis (up to
``@app:execution('tpu', instances='N')`` per (partition, node), default
4); instances dropped when every successor lane is full are counted in
the engine's per-partition ``overflow`` state — explicit capacity where
the reference grows unbounded pending lists.

Partitioned form: ``partition with (key of S) begin <pattern query> end``
lowers to ONE dense engine whose partition axis is the interned key —
per-key NFA state rows in device memory, no per-key Python instances.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, List, Optional

import numpy as np

from siddhi_tpu.core import event as ev
from siddhi_tpu.core.device_pipeline import DevicePipeline
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.exceptions import (
    SiddhiAppCreationError,
    SiddhiAppRuntimeError,
)
from siddhi_tpu.core.key_index import index_for
from siddhi_tpu.observability.stall import waits_on_device
from siddhi_tpu.observability.trace import (
    STAGE_CONVERT,
    STAGE_INTERN,
    STAGE_POLL,
    span,
)
from siddhi_tpu.query_api import AttrType, StateInputStream, Variable

log = logging.getLogger("siddhi_tpu")


def build_dense_engine(query, st: StateInputStream, resolve_def,
                       n_partitions: int, n_instances: int = 4,
                       select_override=None, builder=None, mesh=None):
    """Lower one pattern/sequence query to a DensePatternEngine or raise
    SiddhiAppCreationError with the reason it is not dense-eligible.

    ``select_override=(vars, names)`` bypasses the plain-select-items
    requirement: the engine emits those raw capture columns and the
    CALLER owns selection semantics (the aggregating-selector form runs
    the host QuerySelector over dense match rows).  ``builder`` reuses a
    caller's NFABuilder (one lowering serves both the selector scope and
    the engine).  ``mesh``: the mesh the runtime will shard the state
    over, so that the engine is made, and checked below, with the rows
    a sharded state has (``ops/dense_layout.py`` ``row_shape``)."""
    from siddhi_tpu.ops.dense_nfa import DensePatternEngine
    from siddhi_tpu.ops.nfa import NFABuilder

    sel = query.selector
    if select_override is not None:
        select_vars, select_names = select_override
    else:
        if sel.group_by or sel.having is not None:
            raise SiddhiAppCreationError(
                "dense path: group-by/having selectors take the "
                "host-selector dense form")
        if not sel.selection:
            raise SiddhiAppCreationError(
                "dense path: select * is not supported for patterns")

        select_vars = []
        select_names = []
        for oa in sel.selection:
            if not isinstance(oa.expression, Variable) or oa.expression.stream_id is None:
                raise SiddhiAppCreationError(
                    "dense path: select items must be event references (e1.attr)")
            select_vars.append(oa.expression)
            select_names.append(oa.name)

    if builder is None:
        builder = NFABuilder(st, resolve_def)
        nodes = builder.build()
    else:
        # caller's builder already lowered (build() is not idempotent —
        # it appends); reuse its node chain
        nodes = builder.nodes
    for node in nodes:
        for spec in node.specs:
            if spec.filter_presence_keys:
                raise SiddhiAppCreationError(
                    "dense path: 'is null' event-presence checks need the "
                    "host engine")

    every_start = any(n.rearm_to is not None for n in nodes)
    eng = DensePatternEngine(
        nodes=nodes,
        ref_defs=builder.ref_defs,
        stream_to_ref=builder.stream_to_ref,
        within_ms=st.within_ms,
        n_partitions=n_partitions,
        select_vars=select_vars,
        select_names=select_names,
        every_start=every_start,
        # `every`: a match consumes only the matched instance — siblings
        # (incl. the re-armed start) keep running, as in the host engine;
        # non-every stops the partition's automaton after its match
        reset_on_emit=not every_start,
        is_sequence=st.type == StateInputStream.SEQUENCE,
        n_instances=n_instances,
        mesh=mesh,
    )

    # INT/LONG captures, filters (plain comparisons) and selects ride
    # the engine's bit-exact hi/lo int32 pair bank; integer usage the
    # pair compiler cannot express (arithmetic, functions) raises inside
    # _trace_check below and falls back to the host engine.  Non-numeric
    # captures/selects (STRING/BOOL/OBJECT) have no device lane at all —
    # they must fall back, not silently emit zeros.  String keys belong
    # on the partition axis.
    def _check_numeric(ref_def, attr, what):
        if ref_def is None or attr not in ref_def.attribute_names:
            raise SiddhiAppCreationError(f"dense path: cannot type {what}")
        t = ref_def.attribute_type(attr)
        if not t.is_numeric:
            raise SiddhiAppCreationError(
                f"dense path: {what} has type {t.value}; only numeric "
                "attributes have device lanes — host engine used")

    for (ref, attr, _last) in eng.alloc.slots:
        _check_numeric(builder.ref_defs.get(ref), attr,
                       f"capture '{ref}.{attr}'")
    for _name, src in eng.out_spec:
        if isinstance(src, tuple):
            ref_def = None
            for spec in nodes[-1].specs:
                if src[1] in spec.stream_def.attribute_names:
                    ref_def = spec.stream_def
            _check_numeric(ref_def, src[1], f"select attribute '{src[1]}'")

    _trace_check(eng)
    return eng


def output_attr_types(eng) -> List[AttrType]:
    """Declared attribute type of each engine output lane (the engine
    computes in float32; callbacks/definitions keep the source types)."""
    out: List[AttrType] = []
    for _name, src in eng.out_spec:
        t = None
        if isinstance(src, tuple):  # ('cand', attr): from the last node
            for node in eng.nodes:
                for spec in node.specs:
                    if src[1] in spec.stream_def.attribute_names:
                        t = spec.stream_def.attribute_type(src[1])
        else:
            d = eng.ref_defs.get(src.ref)
            if d is not None and src.attr in d.attribute_names:
                t = d.attribute_type(src.attr)
        out.append(t or AttrType.DOUBLE)
    return out


def _numeric_attrs(eng, stream_key: str) -> List[str]:
    """Delegates to the engine so the runtime's col dict and the sharded
    step's fixed in_specs structure can never diverge."""
    return eng.numeric_stream_attrs(stream_key)


def _trace_check(eng):
    """Abstractly trace every per-stream step with exactly the env the
    runtime will provide (numeric columns only) so ineligible filters —
    e.g. referencing a string attribute — fail at plan time, not on the
    first event (mirrors DeviceQueryEngine._trace_check)."""
    import jax

    state_shapes = {
        k: jax.ShapeDtypeStruct(shape, np.int32) for k, shape in
        eng.layout.physical_shapes(eng.n_partitions + 1).items()
    }
    B = 16
    i32 = jax.ShapeDtypeStruct((B,), np.int32)
    b1 = jax.ShapeDtypeStruct((B,), bool)
    try:
        for sk in eng.stream_keys:
            cols = {
                k: jax.ShapeDtypeStruct(
                    (B,), np.int32 if "|" in k else np.float32)
                for k in eng.device_col_keys(sk)
            }
            step = eng.make_step(sk, jit=False)
            jax.eval_shape(step, state_shapes, i32, cols, i32, b1)
        if eng.has_deadlines:
            tstep = eng.make_time_step(jit=False)
            jax.eval_shape(tstep, state_shapes,
                           jax.ShapeDtypeStruct((), np.int32))
    except SiddhiAppCreationError:
        raise
    except Exception as e:
        raise SiddhiAppCreationError(
            f"dense path: step not traceable ({e})") from e


class DensePatternRuntime:
    """Product-side wrapper of one DensePatternEngine: converts junction
    batches to device columns, advances state with the jitted step, and
    emits match batches into the query's selector/output chain.

    ``key_fn(batch) -> list`` supplies partition keys (a partitioned
    pattern); plain queries run as one partition (row 0).

    ``mesh``: shard the partition axis over a jax.sharding.Mesh
    (@app:execution('tpu', devices='N')) — state rows live shard-major
    behind a ShardedPatternEngine per source stream, interned keys route
    to their owning shard host-side, and emitted matches come back
    globally (the all-gather is the host fetch of the sharded output
    arrays).  Interned rows are dealt round-robin across shards so load
    spreads from the first key on.
    """

    def __init__(self, engine, out_stream_id: str,
                 emit: Callable[[EventBatch], None],
                 key_fn: Optional[Callable] = None,
                 mesh=None, app_context=None):
        self.engine = engine
        self.out_stream_id = out_stream_id
        self.emit_cb = emit
        self.key_fn = key_fn
        self.mesh = mesh
        # count gate, emit queue, drain(), fault isolation
        # (core/device_pipeline.py); dense spans carry the engine kind
        # (shard when meshed).  The dense state is all int32: nothing
        # here to poison, so no quarantine.
        self.pipeline = DevicePipeline(
            app_context, "dense" if mesh is None else "shard")
        self.pipeline.attach(self, engine)
        self._sharded: Optional[Dict[str, object]] = None
        if mesh is not None:
            from siddhi_tpu.parallel.mesh import ShardedPatternEngine

            # one sharded wrapper per source stream (the jitted step is
            # per-stream); all share one shard-major state layout
            self._sharded = {
                sk: ShardedPatternEngine(engine, mesh, stream_key=sk)
                for sk in engine.stream_keys
            }
            first = next(iter(self._sharded.values()))
            self.n_shards = first.n_shards
            self.parts_per_shard = first.parts_per_shard
            self.state = first.init_state()
        else:
            self.state = engine.init_state()
        self.step_invocations = 0  # proof the jitted path ran (tests)
        self.time_fires = 0  # timer-driven (absent deadline) emissions
        # next_wakeup cache: the scheduler polls every send, but the
        # earliest deadline can only change when a step touched state —
        # recompute (one device reduce + scalar D2H) only then
        self._wake_cache = None
        self._wake_dirty = True
        # partitioned aggregating form: notified with purged key values
        # so the shared selector can drop their per-key state
        self.on_purge_keys = None
        # instance-capacity overflow surfacing: dropped pending instances
        # are counted on device; poll cheaply (one D2H per _OVF_POLL
        # steps) and warn when the count grows — a dense-mode match set
        # is bit-exact exactly while this stays zero
        self._ovf_warned = 0
        self._key_rows: Dict = {}
        self._row_keys: Dict = {}  # reverse map: engine row -> key value
        self._next_row = 0
        self._free_rows: List[int] = []
        # the index backing the vectorized intern (core/key_index.py):
        # None until the first key batch shows its dtype family, and
        # for good in dict mode.  _key_rows stays the source of truth
        # for snapshots/purges; the index is a rebuildable cache.
        self._index = None
        self._vector_intern = True
        self._intern_probe_lanes = 0  # lanes probed past their first slot
        self._intern_new_keys = 0  # keys given a row
        # host-side per-row activity clock driving idle-key reclamation
        # (@purge on dense partitions; the instance path purges whole
        # PartitionInstances instead)
        self._row_last_used = np.zeros(engine.n_partitions, dtype=np.int64)
        # output dtypes: cast the engine's float32 lanes back to the
        # declared attribute types for callbacks/sinks
        self._out_dtypes: List[np.dtype] = [
            t.np_dtype for t in output_attr_types(engine)
        ]

    # -- partition interning -------------------------------------------------

    def _deal_rows(self, ids: np.ndarray) -> np.ndarray:
        """Allocation-counter ids -> logical partition ids.  Sharded
        runtimes deal ids round-robin across shards (key #k lives on
        shard k % n_shards) so load spreads from the first key on."""
        if self._sharded is None:
            return ids
        return ((ids % self.n_shards) * self.parts_per_shard
                + (ids // self.n_shards))

    def _phys_rows(self, rows: np.ndarray) -> np.ndarray:
        """Logical partition ids -> physical state-array rows (the
        shard-major layout inserts one scratch row per shard)."""
        if self._sharded is None:
            return rows
        pps = self.parts_per_shard
        return (rows // pps) * (pps + 1) + (rows % pps)

    def _logical_rows(self, phys: np.ndarray) -> np.ndarray:
        """Physical state-array rows -> logical partition ids (inverse
        of _phys_rows; scratch rows never carry armed deadlines, so
        timer-fired rows are always real partitions)."""
        if self._sharded is None:
            return phys
        rps = self.parts_per_shard + 1
        return (phys // rps) * self.parts_per_shard + (phys % rps)

    def intern_keys(self, keys) -> np.ndarray:
        """Partition-key values -> dense engine row ids (stable until the
        key is purged; shared by all source streams).

        Vectorized: known keys resolve through the key index
        (``core/key_index.py``: a hash table for integer keys, so a
        warm 131k-event batch costs one gather and no sort; a sorted
        array for strings and floats) and only NEVER-SEEN keys are
        given rows, in ascending key order within a batch.

        An index only works while every key batch shares one dtype
        family (all-int, all-string, ...).  Mixing families — e.g.
        ``partition with (k of A, sym of B)`` with an int key on one
        stream and a string on the other — has no common order (and
        7 vs 7.0 alias under python hashing but not under dtype
        promotion), so the runtime then degrades permanently to the
        exact per-event dict intern."""
        arr = np.asarray(keys)
        with span(STAGE_INTERN, len(arr)):
            return self._intern(arr)

    def _intern(self, arr: np.ndarray) -> np.ndarray:
        index = self._index
        if self._vector_intern:
            if arr.dtype.kind in ("O", "V"):
                self._vector_intern = False
            elif index is None:
                pass  # first batch adopts its dtype below
            elif arr.dtype != index.dtype:
                if np.can_cast(arr.dtype, index.dtype, "safe"):
                    arr = arr.astype(index.dtype)
                elif np.can_cast(index.dtype, arr.dtype, "safe"):
                    index = self._index = index.widen(arr.dtype)
                else:
                    log.warning(
                        "dense pattern: partition keys mix dtypes (%s vs "
                        "index %s); falling back to the exact dict intern",
                        arr.dtype, index.dtype)
                    self._vector_intern = False
        if not self._vector_intern:
            self._index = None  # dict mode is for good
            return self._intern_keys_dict(arr)
        if index is None:
            new_keys, inv = np.unique(arr, return_inverse=True)
            rows = (-1 - inv).astype(np.int32)
        else:
            rows, new_keys, probed = index.lookup(arr)
            self._intern_probe_lanes += probed
        if len(new_keys):
            # new keys take rows in ascending key order; their lanes
            # hold -1 - (position in new_keys)
            row_ids = self._take_rows(len(new_keys))
            if index is None:
                self._index = index_for(
                    new_keys, row_ids, self.engine.n_partitions)
            else:
                index.insert(new_keys, row_ids)
            key_list, row_list = new_keys.tolist(), row_ids.tolist()
            self._key_rows.update(zip(key_list, row_list))
            self._row_keys.update(zip(row_list, key_list))
            missing = rows < 0
            rows[missing] = row_ids[-1 - rows[missing]]
        return rows

    def _take_rows(self, n_new: int) -> np.ndarray:
        """Rows for ``n_new`` never-seen keys: recycled rows first, then
        a fresh range dealt across shards."""
        cap = self.engine.n_partitions
        take_free = min(len(self._free_rows), n_new)
        fresh = n_new - take_free
        if self._next_row + fresh > cap:
            raise SiddhiAppRuntimeError(
                f"dense pattern: partition-key cardinality exceeded "
                f"capacity {cap} (raise it via "
                f"@app:execution('tpu', partitions='N') or enable "
                "@purge on the partition)")
        row_ids = np.empty(n_new, dtype=np.int32)
        if take_free:
            row_ids[:take_free] = self._free_rows[-take_free:][::-1]
            del self._free_rows[-take_free:]
        if fresh:
            row_ids[take_free:] = self._deal_rows(np.arange(
                self._next_row, self._next_row + fresh, dtype=np.int64)
            ).astype(np.int32)
            self._next_row += fresh
        self._intern_new_keys += n_new
        return row_ids

    def _intern_keys_dict(self, keys) -> np.ndarray:
        """Exact per-event intern (hash semantics): the fallback when
        partition keys mix dtype families, and the behavior reference
        for the vectorized path."""
        out = np.zeros(len(keys), dtype=np.int32)
        rows = self._key_rows
        for i, k in enumerate(keys):
            row = rows.get(k)
            if row is None:
                row = int(self._take_rows(1)[0])
                rows[k] = row
                self._row_keys[row] = k
            out[i] = row
        return out

    def _rebuild_key_index(self):
        """Rebuild the intern index from _key_rows (after purge or
        restore); degrades to dict mode when the stored keys do not
        form one sortable dtype family."""
        self._index = None
        if not self._key_rows or not self._vector_intern:
            return
        try:
            karr = np.array(list(self._key_rows.keys()))
        except ValueError:  # inhomogeneous keys
            karr = None
        if karr is None or karr.dtype.kind in ("O", "V"):
            self._vector_intern = False
            return
        rarr = np.fromiter(self._key_rows.values(), np.int32, len(karr))
        self._index = index_for(karr, rarr, self.engine.n_partitions)

    def purge_idle(self, now: int, idle_ms: int):
        """Reclaim rows of keys idle for >= idle_ms: reset their device
        state to the init row and recycle the row ids (the dense analog
        of PartitionRuntime's idle-instance purge)."""
        if not self._key_rows:
            return
        # by row, not in the dict's order: a runtime restored from the
        # index's vectors holds its keys in another order than the one
        # that interned them, and must free the same rows in the same order
        idle = sorted(
            ((k, r) for k, r in self._key_rows.items()
             if now - int(self._row_last_used[r]) >= idle_ms),
            key=lambda kr: kr[1])
        if not idle:
            return
        # barrier: purged keys' pending matches must reach per-key
        # selector state before on_purge_keys drops it
        self.drain()
        rows = self._phys_rows(np.asarray([r for _k, r in idle],
                                          dtype=np.int32))
        init = self.engine.layout.init_physical(1)
        jnp = self.engine.jnp
        state = dict(self.state)
        for key, arr in state.items():
            # every init row is identical; row 0 is the template
            state[key] = arr.at[rows].set(jnp.asarray(init[key][0]))
        self.state = state
        for k, r in idle:
            del self._key_rows[k]
            self._row_keys.pop(r, None)
            self._free_rows.append(r)
        self._rebuild_key_index()
        self._wake_dirty = True
        if self.on_purge_keys is not None:
            # partition-axis selectors drop the purged keys' aggregation
            # state too (host analog: the whole per-key instance dies)
            self.on_purge_keys([k for k, _r in idle])

    # -- event path ----------------------------------------------------------

    def receive_keyed(self, stream_key: str, cur: EventBatch, keys):
        """The partitioned receiver's entry (core/partition.py): the
        cycle begins here, where the batch enters the engine, so the
        interning of its keys is a span of the cycle; the ingest span
        starts after it, where it always has."""
        with self.pipeline.cycle(len(cur)) as tok:
            part = self.intern_keys(keys)
            if tok is not None:
                tok.ingest_begins()
            self._advance(stream_key, cur, part, keys, tok)

    def process_stream_batch(self, stream_key: str, batch: EventBatch,
                             part: Optional[np.ndarray] = None,
                             keys=None):
        """Advance the NFA with a junction batch.  ``part`` overrides the
        partition-row assignment (callers that interned the keys
        themselves); ``keys`` carries the raw partition-key values
        aligned with the batch so aggregating selectors can keep per-key
        state (aux side channel)."""
        cur = batch.only(ev.CURRENT)
        n = len(cur)
        if n == 0:
            return
        # the ingest span starts here, at receive time
        with self.pipeline.cycle(n) as tok:
            self._advance(stream_key, cur, part, keys, tok)

    def _advance(self, stream_key: str, cur: EventBatch, part, keys, tok):
        eng = self.engine
        if part is None:
            if self.key_fn is None:
                part = np.zeros(len(cur), dtype=np.int32)
            else:
                if keys is None:
                    keys = self.key_fn(cur)
                part = self.intern_keys(keys)
        with span(STAGE_CONVERT, len(cur)):
            cols = {}
            for a in _numeric_attrs(eng, stream_key):
                col = cur.columns.get(a)
                if col is None:
                    continue
                # native dtype: the engine splits integer columns into
                # bit-exact hi/lo pairs itself (prepare_cols)
                cols[a] = np.asarray(col)
            ts = np.asarray(cur.timestamps, dtype=np.int64)
            if len(ts):
                np.maximum.at(self._row_last_used, part, ts)
        if self._sharded is not None:
            self.state, pending = self._sharded[
                stream_key].process_deferred(self.state, part, cols, ts)
        else:
            self.state, pending = eng.process_deferred(
                self.state, stream_key, part, cols, ts)
        self.step_invocations += 1
        if eng.has_deadlines:
            self._wake_dirty = True
        if self.step_invocations % self._OVF_POLL == 0:
            # the poll's fetch blocks on the step just dispatched
            with span(STAGE_POLL, 1):
                self._check_overflow()
        # clock sampled at RECEIVE time: the count gate may resolve a
        # batch later (ingest.depth > 1) but replays the synchronous `now`
        now = self.pipeline.now()
        self.pipeline.submit(
            tok, pending,
            lambda host: self._build_deferred(pending, ts, keys, host,
                                              now=now),
            self.emit_cb)

    def _build_deferred(self, pending, ts, keys, host_arrays, now=None):
        """The batch's match rows as the ``EventBatch`` the synchronous
        path would have emitted (None: no row)."""
        ev_idx, out = pending.materialize(host_arrays)
        if len(ev_idx) == 0:
            return None
        eng = self.engine
        out_cols: Dict[str, np.ndarray] = {}
        names = eng.output_names
        for oi, name in enumerate(names):
            out_cols[name] = out[:, oi].astype(self._out_dtypes[oi])
        mb = EventBatch(
            self.out_stream_id, names, out_cols,
            ts[ev_idx], np.full(len(ev_idx), ev.CURRENT, dtype=np.int8),
        )
        if keys is not None:
            # one fancy index, not a Python loop: a skewed batch owes
            # thousands of rows
            mb.aux["partition_keys"] = (
                keys[ev_idx].tolist() if isinstance(keys, np.ndarray)
                else [keys[int(i)] for i in ev_idx])
        # original-batch positions of the completing events: the hot-key
        # router splits each cycle into cold/hot sub-batches, and
        # consumers that need the interleaved order re-sort on these
        mb.aux["event_indices"] = ev_idx
        if now is not None:
            # the clock sampled when this batch was processed: deferred
            # drains replay time-based rate limiters exactly (the
            # sync-path `now` sequence, not the drain time)
            mb.aux["emit_now"] = now
        return mb

    # -- instance-capacity overflow ------------------------------------------

    _OVF_POLL = 256  # steps between device overflow polls (one D2H each)

    def overflow_total(self) -> int:
        """Total pending instances dropped because every successor lane
        was occupied (0 == the dense match set is bit-exact vs host).
        Reduced ON DEVICE — only a scalar crosses to host."""
        return int(self.engine.jnp.sum(self.state["overflow"]))

    def stats(self) -> Dict:
        """Ops introspection (runtime.pattern_state() / the REST
        service): partition/instance occupancy of the dense engine.
        ``active_instances`` counts pending lanes of rows actually IN
        USE (interned keys; row 0 when unpartitioned) — the scratch row
        and never-touched pre-armed rows of non-every engines don't
        inflate it."""
        partitioned = self.engine.n_partitions > 1
        if self._key_rows:
            rows = self._phys_rows(np.fromiter(
                self._key_rows.values(), dtype=np.int64,
                count=len(self._key_rows)))
        elif not partitioned:
            # unpartitioned: the single automaton lives in row 0
            rows = np.zeros(1, dtype=np.int64)
        else:
            rows = None
        # the rows' active words alone are gathered and summed on device
        act = 0 if rows is None else int(self.engine.jnp.sum(
            self.engine.layout.words(self.state, "active", rows) != 0))
        return {
            "engine": "dense",
            "partitions_in_use": (
                len(self._key_rows) if partitioned else 1),
            "partition_capacity": self.engine.n_partitions,
            "instance_lanes": self.engine.I,
            "active_instances": act,
            "dropped_instances": self.overflow_total(),
            "step_invocations": self.step_invocations,
            "intern_index": (
                "dict" if not self._vector_intern
                else None if self._index is None  # no key seen yet
                else self._index.kind),
            "intern_probe_lanes": self._intern_probe_lanes,
            "intern_new_keys": self._intern_new_keys,
            # the write-back of a batch's rows: the row-scatter kernel
            # or XLA's scatter (None before a step is traced), and the
            # shape a partition's row is resident in
            "scatter_path": self.engine.layout.scatter_path,
            "state_row_shape": self.engine.layout.row_shape,
        }

    @waits_on_device
    def _check_overflow(self):
        total = self.overflow_total()
        # Queries.<q>.droppedInstances in statistics(): the polled
        # total, so reading it costs no device fetch
        self.emit_stats.dropped_instances = total
        if total > self._ovf_warned:
            msg = (
                f"dense pattern '{self.out_stream_id}': "
                f"{total} pending instance(s) dropped — instance lanes "
                "full; matches may be missing vs the host engine.  Raise "
                "@app:execution('tpu', instances='N') (current "
                f"{self.engine.I} per partition/node).")
            log.warning("%s", msg)
            # user-visible signal beyond the log: app exception
            # listeners observe lost-match capacity pressure (the
            # reference's runtime ExceptionListener channel,
            # SiddhiAppRuntimeImpl.handleRuntimeExceptionWith:827)
            self.pipeline.notify(SiddhiAppRuntimeError(msg))
            self._ovf_warned = total

    def close(self):
        """App shutdown: drain pending emits, then the final overflow
        check — short-lived apps (< one poll interval of batches) still
        get the dropped-instance warning."""
        self.drain()
        self._check_overflow()

    # -- snapshot contract ---------------------------------------------------

    def snapshot(self) -> Dict:
        self.drain()
        self._check_overflow()
        # a snapshot on the device: the logical fields ([rows, S, I] /
        # [rows, S, I, R] per field, so a checkpoint does not depend on
        # the resident layout, ops/dense_layout.py) in buffers of their
        # own, their copies to the host started and not waited for.
        # Whoever holds a barrier round this call holds it for a
        # dispatch; the bytes arrive for whoever asks np.asarray of a
        # field (durability/capture.py: the checkpoint writer)
        logical = self.engine.snapshot_state(self.state)
        for field in logical.values():
            field.flat.copy_to_host_async()
        # the index's two vectors, not a walk of the dict (a million
        # entries at the flagship's size); the dict form only for keys
        # that form no array
        index = self._index
        return {
            "dense_state": logical,
            "base_ts": self.engine.base_ts,
            "key_rows": (dict(self._key_rows) if index is None
                         else index.items()),
            "next_row": self._next_row,
            "free_rows": np.asarray(self._free_rows, dtype=np.int32),
            "row_last_used": self._row_last_used.copy(),
        }

    def restore(self, state: Dict):
        self.drain()
        jnp = self.engine.jnp
        logical = state["dense_state"]
        rows = len(next(iter(logical.values())))
        if self._sharded is not None:
            want = self.n_shards * (self.parts_per_shard + 1)
        else:
            want = self.engine.n_partitions + 1
        if rows != want:
            whose = ("this app's sharded layout" if self._sharded is not None
                     else "this app")
            raise SiddhiAppRuntimeError(
                f"cannot restore: snapshot has {rows} state rows but "
                f"{whose} needs {want} (snapshot taken under a different "
                "@app:execution devices/partitions setting)")
        try:
            physical = self.engine.layout.pack(logical)
        except (KeyError, ValueError) as e:
            raise SiddhiAppRuntimeError(
                f"cannot restore: {e} (snapshot taken under a different "
                "app definition or instances setting)") from e
        if self._sharded is not None:
            first = next(iter(self._sharded.values()))
            self.state = {k: first._put(v, first.state_specs[k])
                          for k, v in physical.items()}
        else:
            self.state = {k: jnp.asarray(v) for k, v in physical.items()}
        self.engine.base_ts = state["base_ts"]
        # either form: a dict (a revision from before the vectors, or
        # keys that form no array) or the index's (keys, rows)
        key_rows = state["key_rows"]
        if isinstance(key_rows, dict):
            self._key_rows = dict(key_rows)
            self._rebuild_key_index()
        else:
            keys, rows = (np.asarray(a) for a in key_rows)
            self._key_rows = dict(zip(keys.tolist(), rows.tolist()))
            self._index = (index_for(keys, rows, self.engine.n_partitions)
                           if self._vector_intern and len(keys) else None)
        self._row_keys = {r: k for k, r in self._key_rows.items()}
        self._next_row = state.get("next_row", len(self._key_rows))
        self._free_rows = [int(r) for r in state.get("free_rows", ())]
        rlu = state.get("row_last_used")
        if rlu is not None:
            self._row_last_used = np.asarray(rlu).copy()
        self._wake_dirty = True

    # -- scheduler integration: absent-node deadline timers.  Engines
    # without deadline nodes keep these as no-ops (within expiry is
    # event-driven on the dense path, like StreamPreStateProcessor's
    # on-arrival pruning); engines with absent states are registered as
    # a scheduler task by the planner and fire matches here.

    def on_time(self, now: int):
        eng = self.engine
        if not getattr(eng, "has_deadlines", False):
            return
        # barrier BEFORE the timer fire: event matches queued before
        # this tick must emit first (the synchronous order)
        self.drain()
        self.state, fired = eng.on_time_state(self.state, now)
        self._wake_dirty = True
        if fired is None:
            return
        self.time_fires += 1
        out, fire_ts, rows = fired
        names = eng.output_names
        out_cols = {
            name: out[:, oi].astype(self._out_dtypes[oi])
            for oi, name in enumerate(names)
        }
        mb = EventBatch(
            self.out_stream_id, names, out_cols,
            fire_ts, np.full(len(fire_ts), ev.CURRENT, dtype=np.int8),
        )
        if self._row_keys:
            # partitioned form: timer matches carry their partition key
            # (reverse row->key map; partition-axis selectors need it)
            logical = self._logical_rows(np.asarray(rows))
            mb.aux["partition_keys"] = [
                self._row_keys.get(int(r)) for r in logical]
        mb.aux["emit_now"] = now
        self.emit_cb(mb)

    def next_wakeup(self):
        eng = self.engine
        if not getattr(eng, "has_deadlines", False):
            return None
        if self._wake_dirty:
            self._wake_cache = eng.next_wakeup_state(self.state)
            self._wake_dirty = False
        return self._wake_cache

    def fire(self, now: int):
        self.on_time(now)

    def on_start(self, now: int):
        pass


class _DenseStreamReceiver:
    """Junction subscriber feeding one source stream of a dense pattern."""

    def __init__(self, runtime: DensePatternRuntime, stream_key: str):
        self.runtime = runtime
        self.stream_key = stream_key

    def receive(self, batch: EventBatch):
        self.runtime.process_stream_batch(self.stream_key, batch)
