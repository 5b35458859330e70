"""Dense vectorized NFA — the TPU hot path.

Replaces the reference's per-event pattern processing
(StreamPreStateProcessor.processAndReturn:364 — O(pending × states) Java
object walks under a ReentrantLock per event) with a bit-parallel,
jit-compiled step over **micro-batches of events across partitions**:

- per-partition NFA state lives in HBM as ONE contiguous int32 row per
  partition (``ops/dense_layout.py`` holds the layout and is the only
  file that knows it): ``active`` (one lane per chain node and instance),
  ``first_ts`` (within-window anchors), ``counts`` (Kleene counters),
  ``regs`` (captured attribute registers used by cross-state
  filters/selects), bit-cast and laid side by side, padded to a
  multiple of 128 words.  ``engine.layout`` is the accessor: logical
  ``[P, S, I]`` fields in, physical rows out, and back; snapshots hold
  the logical form;
- one step gathers the rows of the batch's partitions, splits the
  fields out of the gathered rows, unrolls the node chain in reverse
  (so an event advances at most one node, the staged-update semantics
  of the host engine), evaluates all node filters vectorized, and
  scatters the rows back in place on the donated state (rows wider
  than 128 words on one chip: a DMA a row, ``kernels/row_scatter.py``;
  the resident shape of a row follows, ``layout.row_shape``);
- cost is O(batch × states × regs) independent of the partition count —
  1M+ partitions are just HBM rows, and no operation of the step reads
  or writes more than the batch's rows;
- multi-chip: the partition axis is sharded over a ``jax.sharding.Mesh``
  (``shard()``); each shard owns its keys so the step needs no
  cross-device collectives, and emitted matches ride an all-gather only
  when the caller asks for global emission.

Dense-mode semantics (documented subset of the host engine,
ops/nfa.py — the planner falls back to the host engine otherwise):
 - linear chains (stream + count nodes; logical and/or as one node),
   <= 32 nodes; patterns and strict-continuity sequences (non-matching
   events kill pending sequence instances pre-advance, start node
   stays armed);
 - absent states (`not X for t`, `A and not B [for t]`) at positions
   >= 1 of PATTERN chains: entry arms a per-instance deadline
   register, a matching absent-stream event kills the instance, and a
   jitted timer step (make_time_step) advances/emits deadline-passed
   instances — the dense analog of the reference's scheduler-armed
   AbsentStreamPreStateProcessor.  Leading absent (deadline from app
   start), absent in sequences, and same-stream and-not stay on the
   host engine;
 - **instance axis**: up to ``n_instances`` simultaneous pending
   instances per (partition, node) — overlapping `every` arms advance
   independently, matching the reference's pendingStateEventList.
   When every slot of a successor node is occupied, the advancing
   instance is DROPPED (oldest-pending-wins) and the partition's
   ``overflow`` counter increments — the explicit-capacity analog of
   the reference's unbounded list (size the axis with
   ``@app:execution('tpu', instances='N')``).  Sequences force one
   instance (the reference keeps a single pending per state);
 - count ({m:n}) nodes: exact counts move at min==max; open-ended
   counts ({m:ANY} / min<max) stay dually pending, cloning per
   successor-matching event through the via-path with clone-time
   registers (exactly the reference's pre-capture _try_enter — [last]
   refs see the captures BEFORE the cloning event, on both engines);
   an open count's successor must be a plain stream node (fall back
   otherwise);
 - ``every`` over a count head (``every e1=S[..]<m:> -> e2``) re-arms
   the head when a count REACHES its minimum, not at every event and
   not at the emit: a fresh arm takes the first free lane of node 0
   on the next matching event, while the satisfied arms go on counting
   beside it.  A burst of F matching events therefore holds
   ceil(F / m) arms (at ``<3:>``: 4 lanes hold a burst of 12, the
   13th event finds no lane and counts one ``overflow``), and the
   successor's event emits one row an arm at or over the minimum,
   each with its own first capture and the ``[last]`` they share;
 - capture references limited to first (``ref.attr``/``ref[0]``) and
   last (``ref[last]``) events of a count state;
 - numeric attributes only (string keys are interned to partition ids
   host-side before the step).
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from siddhi_tpu.core.exceptions import (
    SiddhiAppCreationError,
    SiddhiAppRuntimeError,
)
from siddhi_tpu.observability.stall import waits_on_device
from siddhi_tpu.observability.trace import (
    SCOPE_DENSE_ADVANCE,
    SCOPE_DENSE_COUNT,
    SCOPE_DENSE_GATHER,
    SCOPE_DENSE_KLEENE,
    SCOPE_DENSE_LOGICAL,
    SCOPE_DENSE_ROUNDS,
    SCOPE_DENSE_RUN,
    SCOPE_DENSE_SCATTER,
    STAGE_CONVERT,
    STAGE_DISPATCH,
    STAGE_LANES,
    STAGE_PLAN,
    STAGE_STATE_BYTES,
    STAGE_STREAM,
    counted,
    span,
)
from siddhi_tpu.ops.dense_layout import OVERFLOW, ROWS, DenseStateLayout
from siddhi_tpu.ops.nfa import ANY, NFABuilder, Node, PatternScope, Spec
from siddhi_tpu.ops.packed_lanes import LaneTable
from siddhi_tpu.planner.expr import (
    CompiledExpression,
    ExpressionCompiler,
    N_KEY,
    RecordingEnv,
    TS_KEY,
)
from siddhi_tpu.query_api import AttrType, StateInputStream, Variable
from siddhi_tpu.query_api.definition import StreamDefinition

log = logging.getLogger("siddhi_tpu.dense")

#: Rows of a program's packed buffer beside the device columns
#: (:meth:`DensePatternEngine.lane_table`): no attribute can take the
#: names.
LANE_PART, LANE_REL, LANE_OFF = "__part", "__rel", "__off"


@dataclass
class RegSlot:
    ref: str
    attr: str
    last: bool  # False: first captured event; True: last captured event
    index: int
    integer: bool = False  # True: hi/lo int32 pair in the iregs bank


# integer (INT/LONG) values ride hi/lo int32 pairs: hi = v >> 32 (signed),
# lo = (v & 0xffffffff) - 2^31 (bias-signed, so SIGNED int32 comparison of
# lo equals UNSIGNED comparison of the raw low word) — (hi, lo)
# lexicographic signed order == int64 signed order, bit-exact at any
# magnitude, no 64-bit device lanes needed (TPUs have none)
_INT_TYPES = (AttrType.INT, AttrType.LONG)


def _i64_split_const(v: int) -> Tuple[np.int32, np.int32]:
    v = int(v)
    return (np.int32(v >> 32), np.int32((v & 0xFFFFFFFF) - 2**31))


def _i64_join(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return ((hi.astype(np.int64) << 32)
            | (lo.astype(np.int64) + 2**31).astype(np.uint32))


class DenseScope(PatternScope):
    """Filter/selector scope resolving captured refs to register slots."""

    def __init__(self, ref_defs, stream_to_ref, cand_def, alloc: "RegAllocator",
                 cand_ref=None):
        super().__init__(ref_defs, stream_to_ref, cand_def, cand_ref=cand_ref)
        self.alloc = alloc

    def resolve(self, var: Variable):
        key, t = super().resolve(var)
        if key.startswith("__cand."):
            return key, t
        # captured reference -> register slot key
        ref, idx, attr, _t = self.used_captures[key]
        integer = t in _INT_TYPES
        if idx in (None, 0):
            slot = self.alloc.slot(ref, attr, last=False, integer=integer)
        elif idx == -1:
            slot = self.alloc.slot(ref, attr, last=True, integer=integer)
        else:
            raise SiddhiAppCreationError(
                f"dense NFA supports only first/[0]/[last] capture refs, got index {idx}"
            )
        prefix = "__ireg" if integer else "__reg"
        return f"{prefix}.{slot.index}", t


class RegAllocator:
    """Two banks: float32 value slots (``regs``) and integer hi/lo pair
    slots (``iregs``) — indexed independently."""

    def __init__(self):
        self.slots: Dict[Tuple[str, str, bool], RegSlot] = {}
        self._n_float = 0
        self._n_int = 0

    def slot(self, ref: str, attr: str, last: bool,
             integer: bool = False) -> RegSlot:
        k = (ref, attr, last)
        if k not in self.slots:
            idx = self._n_int if integer else self._n_float
            self.slots[k] = RegSlot(ref, attr, last, idx, integer)
            if integer:
                self._n_int += 1
            else:
                self._n_float += 1
        return self.slots[k]

    @property
    def n(self) -> int:
        return self._n_float

    @property
    def n_int(self) -> int:
        return self._n_int


class DenseExprCompiler(ExpressionCompiler):
    """Dense-filter compiler: integer (INT/LONG) leaves ride hi/lo int32
    pairs (``<key>|hi`` / ``<key>|lo`` env lanes); comparisons between
    integer leaves compile to bit-exact paired compares at ANY
    magnitude.  Every other integer use (arithmetic, function args)
    raises, sending the query to the host engine — the reference is
    per-type exact and so are we, just along a narrower surface.

    ``PAIR_TYPES`` is the attribute-type set riding pair lanes; the
    device query engine subclasses with LONG-only (its INT attributes
    keep plain int32 lanes)."""

    PAIR_TYPES = _INT_TYPES

    def _i64_parts(self, e, var_only=False):
        """Integer leaf -> (hi_fn, lo_fn) env readers, else None.
        ``var_only`` skips constants (used to decide whether the pair
        path applies at all: an integer LITERAL against a float lane —
        ``[v > 100]`` — stays on the ordinary float compare)."""
        from siddhi_tpu.query_api import Constant

        if (not var_only and isinstance(e, Constant)
                and e.type in _INT_TYPES and e.value is not None):
            hi, lo = _i64_split_const(e.value)
            return (lambda env: hi), (lambda env: lo)
        if isinstance(e, Variable):
            key, t = self.scope.resolve(e)
            if t in self.PAIR_TYPES:
                return ((lambda env: env[key + "|hi"]),
                        (lambda env: env[key + "|lo"]))
        return None

    def _c_CompareOp(self, e):
        # pair compares engage only when an integer VARIABLE lane is
        # involved; integer constants alone coerce fine on float lanes
        if (self._i64_parts(e.left, var_only=True) is None
                and self._i64_parts(e.right, var_only=True) is None):
            return super()._c_CompareOp(e)
        lp, rp = self._i64_parts(e.left), self._i64_parts(e.right)
        if lp is None or rp is None:
            raise SiddhiAppCreationError(
                "dense NFA: comparison mixes a 64-bit integer lane with a "
                "non-integer operand — host engine used")
        lhi, llo = lp
        rhi, rlo = rp
        op = e.op

        def fn(env):
            a_hi, a_lo = lhi(env), llo(env)
            b_hi, b_lo = rhi(env), rlo(env)
            if op == "==":
                return (a_hi == b_hi) & (a_lo == b_lo)
            if op == "!=":
                return (a_hi != b_hi) | (a_lo != b_lo)
            if op == ">":
                return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo > b_lo))
            if op == ">=":
                return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo >= b_lo))
            if op == "<":
                return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))
            return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))

        return CompiledExpression(fn, AttrType.BOOL)

    def _c_Variable(self, e):
        key, t = self.scope.resolve(e)
        if t in self.PAIR_TYPES:
            raise SiddhiAppCreationError(
                "dense NFA: integer attribute used outside a plain "
                "comparison (arithmetic/functions on 64-bit lanes need "
                "the host engine)")
        return super()._c_Variable(e)


def _rank_place(jnp, mask, anchor, src_regs, src_iregs, entry_dl,
                a_t, first_t, counts_t, regs_t, iregs_t, dl_t, ovf):
    """Rank-matched placement of advancing instances into free lanes of
    one target node (shared by the event step and the timer step): the
    k-th advancing instance takes the k-th free lane; advancers beyond
    the free-lane count are dropped and counted in ``ovf`` — explicit
    capacity where the reference grows an unbounded pending list.

    ``a_t`` .. ``dl_t`` are the TARGET NODE's lanes alone ([B, I];
    ``regs_t`` / ``iregs_t`` [B, I, R]); the callers slice them out of
    whatever form they hold the state in and write the results back.
    ``entry_dl`` ([B, I] int32 or None) carries per-source deadline
    values for a target node with an absent 'for' spec; ``dl_t`` may be
    None when the engine has no deadline state at all.

    Returns updated ``(a_t, first_t, counts_t, regs_t, iregs_t, dl_t,
    ovf)``."""
    free = ~a_t & (counts_t == 0)  # [B, I]
    src_rank = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1
    free_rank = jnp.cumsum(free.astype(jnp.int32), axis=1) - 1
    n_free = jnp.sum(free.astype(jnp.int32), axis=1)  # [B]
    placed = mask & (src_rank < n_free[:, None])
    ovf = ovf + jnp.sum((mask & ~placed).astype(jnp.int32), axis=1)
    # [B, Isrc, Itgt] one-hot assignment
    assign = (placed[:, :, None] & free[:, None, :]
              & (src_rank[:, :, None] == free_rank[:, None, :]))
    got = jnp.any(assign, axis=1)  # [B, I] target lanes filled
    moved_regs = jnp.sum(
        jnp.where(assign[:, :, :, None], src_regs[:, :, None, :], 0.0),
        axis=1)  # [B, I, R]
    moved_anchor = jnp.sum(
        jnp.where(assign, anchor[:, :, None], 0), axis=1)  # [B, I]
    a_t = a_t | got
    regs_t = jnp.where(got[:, :, None], moved_regs, regs_t)
    if iregs_t.shape[-1]:
        moved_iregs = jnp.sum(
            jnp.where(assign[:, :, :, None], src_iregs[:, :, None, :], 0),
            axis=1)
        iregs_t = jnp.where(got[:, :, None], moved_iregs, iregs_t)
    first_t = jnp.where(got, moved_anchor.astype(jnp.int32), first_t)
    counts_t = jnp.where(got, 0, counts_t)
    if dl_t is not None:
        if entry_dl is not None:
            moved_dl = jnp.sum(
                jnp.where(assign, entry_dl[:, :, None], 0), axis=1)
            dl_t = jnp.where(got, moved_dl.astype(jnp.int32), dl_t)
        else:
            # target without a deadline spec: clear any stale value left
            # by a previous occupant of the lane
            dl_t = jnp.where(got, 0, dl_t)
    return a_t, first_t, counts_t, regs_t, iregs_t, dl_t, ovf


class DensePatternEngine:
    """Compiles a lowered node chain into a jitted per-stream step.

    Usage:
        eng = DensePatternEngine(nodes, ref_defs, stream_to_ref,
                                 within_ms, n_partitions, select_vars)
        state = eng.init_state()
        state, match_ev_idx, out = eng.process(state, stream_key,
                                               part_idx, cols, ts)
    """

    def __init__(
        self,
        nodes: List[Node],
        ref_defs: Dict[str, StreamDefinition],
        stream_to_ref: Dict[str, Optional[str]],
        within_ms: Optional[int],
        n_partitions: int,
        select_vars: List[Variable],
        select_names: Optional[List[str]] = None,
        every_start: bool = True,
        reset_on_emit: bool = True,
        mesh=None,
        partition_axis: str = "p",
        is_sequence: bool = False,
        n_instances: int = 4,
    ):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.nodes = nodes
        self.ref_defs = ref_defs
        self.within_ms = within_ms
        self.n_partitions = n_partitions
        # round_plan's vector over the rows, made by the first batch
        self._plan_first: Optional[np.ndarray] = None
        self.every_start = every_start
        self.reset_on_emit = reset_on_emit
        self.is_sequence = is_sequence
        self.mesh = mesh
        self.partition_axis = partition_axis
        self.S = len(nodes)
        # sequences keep one pending per state (reference
        # StreamPreStateProcessor.addState:217-223); non-every patterns
        # arm exactly one chain — the instance axis only matters for
        # overlapping `every` arms
        self.I = 1 if (is_sequence or not every_start) else max(int(n_instances), 1)
        if self.S > 32:
            raise SiddhiAppCreationError("dense NFA supports at most 32 chain nodes")
        # `every` models: a rearm at node 0's completion is the standing
        # virgin (`every e1 -> ...`); a WHOLE-CHAIN group-every
        # (`every (e1 -> e2)`, rearm on the last node back to 0) keeps
        # ONE arm at a time — the virgin arms only while the partition
        # has no active instance (completion consumes the arm, expiry
        # clears it; WithinPatternTestCase.testQuery4/6's cadence).
        # Partial-chain groups (`every (e1->e2) -> e3`) stay on the host
        # engine: the suffix instance keeps the partition occupied.
        self.group_every = False
        for n in nodes:
            if n.rearm_to is None:
                continue
            if n.pos == 0 and n.rearm_to == 0:
                continue  # standing virgin
            if (n.pos == self.S - 1 and n.rearm_to == 0
                    and not is_sequence
                    and nodes[0].kind == "stream"
                    and nodes[0].min_count == 1 and nodes[0].max_count == 1
                    and not any(sp.is_absent for nn in nodes
                                for sp in nn.specs)):
                # absent violations kill the host's single group arm
                # PERMANENTLY (no re-arm); the arm-when-empty virgin
                # would resurrect it — keep absent group-every on host
                self.group_every = True
                continue
            raise SiddhiAppCreationError(
                "dense NFA: this group-`every` shape (partial chain, or "
                "absent states whose violation must kill the arm "
                "permanently) needs the host engine")
        if self.group_every:
            # one arm at a time: a single instance lane suffices
            self.I = 1
        # absent states ride deadline-timer registers: a node with an
        # absent `for t` spec arms `deadline = entry_ts + t` on entry,
        # a matching absent-stream event kills the pending instance, and
        # the timer step (make_time_step) advances/emits instances whose
        # deadline passed — the dense analog of
        # AbsentStreamPreStateProcessor.java:35's scheduler arming
        self.deadline_w: List[Optional[int]] = []
        for n in nodes:
            w = None
            for sp in n.specs:
                if sp.is_absent and sp.waiting_ms is not None:
                    w = int(sp.waiting_ms)
            self.deadline_w.append(w)
        self.has_deadlines = any(w is not None for w in self.deadline_w)
        for ni, n in enumerate(nodes):
            if n.kind == "stream" and n.min_count == 0:
                raise SiddhiAppCreationError(
                    "dense NFA does not support optional (min 0) states yet; "
                    "use the host engine"
                )
            if n.kind != "absent" and not any(s.is_absent for s in n.specs):
                continue
            if is_sequence:
                raise SiddhiAppCreationError(
                    "dense NFA: absent states in sequences (strict "
                    "continuity over a waiting state) need the host engine")
            if n.kind == "absent" and self.deadline_w[ni] is None:
                raise SiddhiAppCreationError(
                    "dense NFA: standalone absent node without a 'for' "
                    "duration needs the host engine")
            if ni == 0 and self.deadline_w[ni] is not None:
                raise SiddhiAppCreationError(
                    "dense NFA: a leading absent 'for' deadline counts "
                    "from app start — host engine used")
            if self.deadline_w[ni] is not None and self.deadline_w[ni] > 2**23:
                raise SiddhiAppCreationError(
                    "dense NFA: absent 'for' durations above 2^23 ms would "
                    "overflow the int32 relative-time deadline — host "
                    "engine used")
            if n.kind == "logical":
                if n.logical_op == "or":
                    # the or-absent race (violation disables one branch,
                    # deadline completes with null present sides) stays
                    # on the host engine
                    raise SiddhiAppCreationError(
                        "dense NFA: 'or' with an absent side needs the "
                        "host engine")
                present_keys = {sp.stream_key for sp in n.specs
                                if not sp.is_absent}
                absent_keys = {sp.stream_key for sp in n.specs
                               if sp.is_absent}
                if present_keys & absent_keys:
                    raise SiddhiAppCreationError(
                        "dense NFA: logical and-not over the SAME stream "
                        "(one event can both match and violate) needs the "
                        "host engine")
                if ni == 0 and every_start:
                    # the host's start instance DIES on an absent-side
                    # violation and nothing re-arms it; the dense
                    # standing-virgin would immortally re-arm — diverging
                    # match sets, so this shape stays on the host engine
                    raise SiddhiAppCreationError(
                        "dense NFA: every-start logical and-not (violation "
                        "permanently kills the start state) needs the host "
                        "engine")

        self.alloc = RegAllocator()
        self._compile_filters(stream_to_ref)
        self._compile_outputs(select_vars, stream_to_ref, select_names)
        absent_refs = {sp.ref for n in nodes for sp in n.specs if sp.is_absent}
        for (ref, _attr, _last) in self.alloc.slots:
            if ref in absent_refs:
                raise SiddhiAppCreationError(
                    "dense NFA: filters/selects cannot reference an absent "
                    "event (it never arrives) — host engine used")
        # open-ended counts stay dually pending: they capture more events
        # after satisfaction and clone per successor-matching event (the
        # via-path in the step, carrying clone-time registers exactly
        # like the reference's _try_enter).  The via-path models one
        # capture+advance, so an open count's successor must be a plain
        # stream node.
        for ni, n in enumerate(nodes):
            is_count = not (n.min_count == 1 and n.max_count == 1)
            open_count = is_count and (n.max_count == ANY or n.max_count > n.min_count)
            if not open_count:
                continue
            if ni + 1 < len(nodes):
                nxt = nodes[ni + 1]
                if not (nxt.kind == "stream" and nxt.min_count == 1
                        and nxt.max_count == 1):
                    raise SiddhiAppCreationError(
                        "dense NFA: open-ended count followed by a "
                        "count/logical node needs the host engine")
        # capture slots each node writes — computed after BOTH filter and
        # output compilation so select-only slots get written too
        self.node_writes: List[List[RegSlot]] = []
        for node in self.nodes:
            writes = []
            for spec in node.specs:
                for (ref, _attr, _last), slot in self.alloc.slots.items():
                    if ref == spec.ref:
                        writes.append(slot)
            self.node_writes.append(writes)
        # where each field of a partition's state lives in its row: a
        # function of S, I and the register banks alone; the shape a row
        # is resident in follows from its width and from whether the
        # state is sharded over a mesh
        self.layout = self._make_layout(sharded=mesh is not None)
        # emit lanes of a step: bank [0, I) for completions at the last
        # node and, only where the chain has one, bank [I, 2I) for the
        # via-path's clones (a dually-pending open count before a plain
        # last node).  A bank nothing can fire in is not carried: the
        # emit arrays are fetched whole whenever a batch owes one row.
        last, before = self.nodes[-1], (self.nodes[-2] if self.S > 1
                                        else None)
        via_emits = (
            before is not None and last.kind == "stream"
            and last.min_count == 1 and last.max_count == 1
            and before.kind == "stream"
            and not (before.min_count == 1 and before.max_count == 1)
            and (before.max_count == ANY
                 or before.max_count > before.min_count))
        self.emit_lanes = self.I * (2 if via_emits else 1)
        self._step_cache: Dict[str, Callable] = {}

    # -- compilation --------------------------------------------------------

    def _compile_filters(self, stream_to_ref):
        """Per-node filters compiled against candidate columns + registers."""
        self.node_filters: List[List[Optional[CompiledExpression]]] = []
        for node in self.nodes:
            fs = []
            for spec in node.specs:
                if spec.filter_compiled is None:
                    fs.append(None)
                    continue
                # recompile the raw filter against the dense scope
                scope = DenseScope(self.ref_defs, stream_to_ref,
                                   spec.stream_def, self.alloc,
                                   cand_ref=spec.ref)
                compiler = DenseExprCompiler(scope)
                fs.append(compiler.compile(spec.raw_filter))
            self.node_filters.append(fs)

    def _compile_outputs(self, select_vars: List[Variable], stream_to_ref, select_names=None):
        """Selector variables -> (slot index | candidate attr) extractors.

        Output names use the query's `as` aliases when provided."""
        self.out_spec: List[Tuple[str, object]] = []  # (name, slot|('cand', attr))
        self.out_int: List[bool] = []  # integer (hi/lo pair) output lane?
        last_node = self.nodes[-1]
        last_refs = {s.ref for s in last_node.specs}
        for vi, var in enumerate(select_vars):
            ref = var.stream_id
            if ref not in self.ref_defs and ref in stream_to_ref:
                ref = stream_to_ref[ref]
            if ref is None or ref not in self.ref_defs:
                raise SiddhiAppCreationError(f"cannot resolve select ref '{var.stream_id}'")
            idx = var.stream_index
            name = (
                select_names[vi]
                if select_names and vi < len(select_names)
                else f"{ref}.{var.attribute}"
            )
            d = self.ref_defs[ref]
            if var.attribute not in d.attribute_names:
                raise SiddhiAppCreationError(
                    f"select ref '{ref}.{var.attribute}': no such attribute")
            integer = d.attribute_type(var.attribute) in _INT_TYPES
            if ref in last_refs and last_node.kind == "stream" and last_node.max_count == 1:
                # final event: values come from the candidate columns
                self.out_spec.append((name, ("cand", var.attribute)))
                self.out_int.append(integer)
                continue
            last = idx == -1
            if idx not in (None, 0, -1):
                raise SiddhiAppCreationError(
                    f"dense NFA supports only first/[0]/[last] select refs, got {idx}"
                )
            slot = self.alloc.slot(ref, var.attribute, last, integer=integer)
            self.out_spec.append((name, slot))
            self.out_int.append(integer)

    # -- state --------------------------------------------------------------

    def _make_layout(self, sharded: bool) -> DenseStateLayout:
        return DenseStateLayout(
            self.S, self.I, self.alloc.n, self.alloc.n_int,
            self.has_deadlines, armed_start=not self.every_start,
            sharded=sharded)

    def shard_rows(self) -> None:
        """The state will live sharded by rows, its step under
        ``shard_map`` (``parallel/mesh.py`` wraps an engine that was
        built without its mesh): the rows take the shape a sharded state
        has, as with ``mesh`` given at construction, and programs traced
        for the other shape are dropped."""
        if len(self.layout.row_shape) > 1:
            self.layout = self._make_layout(sharded=True)
            self._step_cache.clear()

    def init_state_host(self) -> Dict[str, np.ndarray]:
        """Zero state in its PHYSICAL form (ops/dense_layout.py: one
        int32 row of ``layout.width`` words per partition, plus the
        ``overflow`` vector) as NUMPY arrays — no device allocation, so
        callers (e.g. the sharded wrapper) can lay out rows before any
        backend is selected.  ``layout.unpack`` gives the logical
        ``[P, S, I]`` view, ``layout.pack`` the way back."""
        # one scratch row (index P) absorbs padded/invalid batch rows so
        # their scatter-back cannot collide with a real partition
        return self.layout.init_physical(self.n_partitions + 1)

    def state_pspecs(self):
        """Partition-axis sharding spec per state array (row-sharded;
        the words of a row replicated)."""
        return self.layout.pspecs(self.partition_axis)

    def init_state(self):
        if self.mesh is None:
            # made on the device: the host's copy of a million rows
            # would cross in seconds
            return self.layout.init_device(self.n_partitions + 1)
        from jax.sharding import NamedSharding

        jnp = self.jnp
        specs = self.state_pspecs()
        return {
            k: self.jax.device_put(jnp.asarray(v),
                                   NamedSharding(self.mesh, specs[k]))
            for k, v in self.init_state_host().items()
        }

    # -- step ---------------------------------------------------------------

    def make_step(self, stream_key: str, jit: bool = True,
                  col_keys: Optional[Tuple[str, ...]] = None) -> Callable:
        """Build the step for events of one source stream.

        step(state, part_idx[B] i32, cols {attr: [B] f32}, ts[B] i32
             relative-ms, valid[B] bool)
          -> (state, emit[B, E] bool, out_vals[B, E, n_out] f32,
              emit_anchor[B, E] i32, n_emit i32 scalar)

        The jitted program takes ``(state, buf)``: ``buf`` the ONE
        packed ``int32 [k, B]`` buffer of :meth:`lane_table` for the
        columns ``col_keys`` (default: every one the automaton reads),
        taken apart by static slices at its top; a lane is valid where
        its partition row is not the scratch row.

        ``emit[b, i]``: a pending instance of event ``b``'s partition
        completed the chain on this event.  The emit arrays carry
        E = ``emit_lanes`` lanes: [0, I) for instances completing AT the
        last node and, in chains that have a via-path into it, [I, 2I)
        for via-path clones (a dually-pending count's clone passing
        straight through the last node) — the two can fire on the same
        event at the same lane index, so they must not share a bank.
        ``emit_anchor`` carries each match's within-anchor (relative ms)
        so the host wrapper can order same-event matches by arming age,
        matching the reference's pendingStateEventList iteration order.

        One gather of the batch's rows, :meth:`make_advance`'s automaton
        on their fields, one scatter in place on the donated rows.

        ``jit=False`` returns the raw traceable function (for embedding in
        shard_map / outer jit).
        """
        if jit:
            table = self.lane_table(stream_key, col_keys)
            cache_key = (stream_key, jit, table.names)
            if cache_key not in self._step_cache:
                raw = self.make_step(stream_key, jit=False)

                def step(state, buf):
                    part, cols, ts, _off = self._unpack_lanes(table, buf)
                    return raw(state, part, cols, ts,
                               part != self.n_partitions)

                self._step_cache[cache_key] = self.jax.jit(
                    step, donate_argnums=(0,))
            return self._step_cache[cache_key]
        cache_key = (stream_key, jit)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        jnp = self.jnp
        advance = self.make_advance(stream_key)
        named_scope = self.jax.named_scope
        layout = self.layout

        def step(state, part_idx, cols, ts, valid):
            # one gather of the batch's rows; the fields are split out of
            # the gathered rows, so every relayout is of B rows
            with named_scope(SCOPE_DENSE_GATHER):
                f, old = layout.gather(state, part_idx)
            new, ovf, emit, outs, emit_anchor = advance(f, cols, ts, valid)
            # scatter back (valid rows only), in place on the donated rows
            with named_scope(SCOPE_DENSE_SCATTER):
                new_state = layout.scatter(state, part_idx, new, ovf, valid,
                                           old)
            # outs is a pytree: float lanes + integer hi/lo pair lanes;
            # n_emit is the count-gate scalar for the async emit
            # pipeline — the host fetches it alone and skips the column
            # transfer entirely on zero-match batches
            with named_scope(SCOPE_DENSE_COUNT):
                n_emit = jnp.sum((emit & valid[:, None]).astype(jnp.int32))
            return new_state, emit, outs, emit_anchor, n_emit

        self._step_cache[cache_key] = step
        return step

    def make_advance(self, stream_key: str) -> Callable:
        """The automaton alone, on the logical fields of B rows:

        advance(fields {name: [B, S, I(, R)]}, cols, ts[B], valid[B])
          -> (fields after one event a row, overflow increment [B],
              emit, {"f": out_vals, "i": out_ivals}, emit_anchor)

        What comes back for a row with ``valid`` false is not defined
        (expiry, for one, does not look at ``valid``): the caller keeps
        the fields it read for such a row and masks the rest.
        Traceable, not jitted: :meth:`make_step` wraps it in a gather
        and a scatter, :meth:`make_rounds` also applies it to resident
        rows event after event."""
        cache_key = (stream_key, "advance")
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        jnp = self.jnp
        S = self.S
        I = self.I
        nodes = self.nodes
        node_filters = self.node_filters
        within = self.within_ms
        every_start = self.every_start
        group_every = self.group_every
        reset_on_emit = self.reset_on_emit
        is_sequence = self.is_sequence
        out_spec = self.out_spec
        O = max(len(out_spec), 1)

        def env_for(node_idx, cols, ts, regs_b, iregs_b, spec_idx=0,
                    regs_node=None):
            """Filter env over [B, I] lanes: candidate columns broadcast
            down the instance axis; registers are per-instance (float
            bank + hi/lo integer pair bank).  ``regs_node`` overrides
            which node's register lanes feed the env (the via-path
            evaluates node t's filter against the dually-pending source
            registers at t-1)."""
            env = {}
            spec = nodes[node_idx].specs[spec_idx]
            rn = node_idx if regs_node is None else regs_node
            for a in spec.stream_def.attributes:
                if a.type in _INT_TYPES:
                    hk, lk = f"{a.name}|hi", f"{a.name}|lo"
                    if hk in cols:
                        env[f"__cand.{a.name}|hi"] = cols[hk][:, None]
                        env[f"__cand.{a.name}|lo"] = cols[lk][:, None]
                elif a.name in cols:
                    env["__cand." + a.name] = cols[a.name][:, None]
            for slot in self.alloc.slots.values():
                if slot.integer:
                    env[f"__ireg.{slot.index}|hi"] = (
                        iregs_b[:, rn, :, 2 * slot.index])
                    env[f"__ireg.{slot.index}|lo"] = (
                        iregs_b[:, rn, :, 2 * slot.index + 1])
                else:
                    env[f"__reg.{slot.index}"] = regs_b[:, rn, :, slot.index]
            env[TS_KEY] = ts[:, None]
            env[N_KEY] = ts.shape[0]
            return env

        def eval_ok(s, si, cols, ts, regs, iregs, B):
            f = node_filters[s][si]
            if f is None:
                return jnp.ones((B, I), dtype=bool)
            return jnp.broadcast_to(
                jnp.asarray(f.fn(
                    env_for(s, cols, ts, regs, iregs, si))).astype(bool),
                (B, I))

        n_iout = sum(self.out_int)
        named_scope = self.jax.named_scope

        def advance(a, first, counts, regs, iregs, ovf, dl, cols, ts,
                    valid):
            """The gathered rows of the batch's partitions through one
            event each: expiry, filters, placement, emission."""
            B = ts.shape[0]
            # deadline registers ride OUTSIDE the functional carry in a
            # one-cell holder: only placement and the absent kill/complete
            # branches touch them, and tracing is sequential python
            dlh = [dl]
            E = self.emit_lanes
            emit = jnp.zeros((B, E), dtype=bool)
            out_vals = jnp.zeros((B, E, O), dtype=jnp.float32)
            out_ivals = jnp.zeros((B, E, 2 * n_iout), dtype=jnp.int32)
            emit_anchor = jnp.zeros((B, E), dtype=jnp.int32)

            # within-window expiry: clear expired instances (active bits,
            # in-progress counts and logical side masks alike)
            if within is not None:
                expired = (first > 0) & (ts[:, None, None] - first > within)
                a = a & ~expired
                counts = jnp.where(expired, 0, counts)
                first = jnp.where(expired, 0, first)
                if dlh[0] is not None:
                    dlh[0] = jnp.where(expired, 0, dlh[0])

            # group-every virgin gating: the fresh arm may only form
            # while the partition has NO active instance (post-expiry,
            # pre-event state — one arm at a time, matching the host's
            # arm-at-group-completion/expiry cadence)
            if group_every:
                grp_virgin_ok = ~jnp.any(
                    a.reshape(B, -1), axis=1)[:, None]  # [B, 1]

            # node filters evaluated once against entry-state registers
            # (the reversed loop reads them before any same-step regs
            # write could affect them); None = node not on this stream
            ok_pre = []
            for s in range(S):
                node = nodes[s]
                if node.kind == "logical":
                    oks = []
                    for si, sp in enumerate(node.specs):
                        if sp.stream_key != stream_key:
                            oks.append(None)
                        else:
                            oks.append(eval_ok(s, si, cols, ts, regs, iregs, B))
                    ok_pre.append(oks)
                elif node.specs[0].stream_key != stream_key:
                    ok_pre.append(None)
                else:
                    ok_pre.append(eval_ok(s, 0, cols, ts, regs, iregs, B))

            if is_sequence:
                # strict continuity (reference: SEQUENCE keeps one pending
                # per state, a non-matching event kills it; the start node
                # stays armed — StreamPreStateProcessor.addState:217-223):
                # any pending instance whose node cannot use this event
                # dies before the advance pass
                for s in range(1, S):
                    ok_s = ok_pre[s]
                    if isinstance(ok_s, list):
                        m = jnp.zeros((B, I), dtype=bool)
                        for o in ok_s:
                            if o is not None:
                                m = m | o
                    elif ok_s is None:
                        m = jnp.zeros((B, I), dtype=bool)
                    else:
                        m = ok_s
                    kill = a[:, s, :] & ~m & valid[:, None]
                    a = a.at[:, s, :].set(a[:, s, :] & ~kill)
                    counts = counts.at[:, s, :].set(
                        jnp.where(kill, 0, counts[:, s, :]))
                    first = first.at[:, s, :].set(
                        jnp.where(kill, 0, first[:, s, :]))

            # out-spec position -> index into the integer output pairs
            int_out_idx = {}
            for _oi, _isint in enumerate(self.out_int):
                if _isint:
                    int_out_idx[_oi] = len(int_out_idx)

            def _emit_rows(mask, anchor, src_regs, carry, bank=0,
                           src_iregs=None):
                """Instances in ``mask`` (with ``src_regs`` [B, I, R] and
                ``src_iregs`` [B, I, 2*RI]) complete the chain on this
                event.  ``bank`` selects the emit lane block (0:
                last-node completions, 1: via-path clones) so same-lane
                fires from both never collide."""
                a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                if src_iregs is None:
                    src_iregs = iregs[:, S - 1, :, :]
                lo = bank * I
                sl = slice(lo, lo + I)
                emit = emit.at[:, sl].set(emit[:, sl] | mask)
                emit_anchor = emit_anchor.at[:, sl].set(
                    jnp.where(mask, anchor, emit_anchor[:, sl]))
                for oi, (_name, src) in enumerate(out_spec):
                    ii = int_out_idx.get(oi)
                    if isinstance(src, tuple):  # ('cand', attr)
                        if ii is not None:
                            hk, lk = f"{src[1]}|hi", f"{src[1]}|lo"
                            if hk not in cols:
                                continue
                            out_ivals = out_ivals.at[:, sl, 2 * ii].set(
                                jnp.where(mask, cols[hk][:, None],
                                          out_ivals[:, sl, 2 * ii]))
                            out_ivals = out_ivals.at[:, sl, 2 * ii + 1].set(
                                jnp.where(mask, cols[lk][:, None],
                                          out_ivals[:, sl, 2 * ii + 1]))
                            continue
                        val = cols.get(src[1])
                        if val is None:
                            continue
                        out_vals = out_vals.at[:, sl, oi].set(
                            jnp.where(mask, val.astype(jnp.float32)[:, None],
                                      out_vals[:, sl, oi]))
                    elif ii is not None:
                        out_ivals = out_ivals.at[:, sl, 2 * ii].set(
                            jnp.where(mask, src_iregs[:, :, 2 * src.index],
                                      out_ivals[:, sl, 2 * ii]))
                        out_ivals = out_ivals.at[:, sl, 2 * ii + 1].set(
                            jnp.where(mask, src_iregs[:, :, 2 * src.index + 1],
                                      out_ivals[:, sl, 2 * ii + 1]))
                    else:
                        out_vals = out_vals.at[:, sl, oi].set(
                            jnp.where(mask, src_regs[:, :, src.index],
                                      out_vals[:, sl, oi]))
                return (a, first, counts, regs, iregs, emit, out_vals, out_ivals,
                        emit_anchor, ovf)

            def _place(mask, anchor, src_regs, t, carry, src_iregs=None):
                """Move instances in ``mask`` into free lanes of node
                ``t`` (rank-matched; see _rank_place).  A target node
                with an absent 'for' spec arms its deadline to this
                event's ts + waiting (the reference's _enter_node
                scheduler arming)."""
                a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                si = iregs[:, t - 1, :, :] if src_iregs is None else src_iregs
                w = self.deadline_w[t]
                entry_dl = (
                    jnp.broadcast_to(ts[:, None] + w, mask.shape)
                    if w is not None else None)
                dl = dlh[0]
                a_t, first_t, counts_t, regs_t, iregs_t, dl_t, ovf = (
                    _rank_place(
                        jnp, mask, anchor, src_regs, si, entry_dl,
                        a[:, t, :], first[:, t, :], counts[:, t, :],
                        regs[:, t, :, :], iregs[:, t, :, :],
                        None if dl is None else dl[:, t, :], ovf))
                a = a.at[:, t, :].set(a_t)
                first = first.at[:, t, :].set(first_t)
                counts = counts.at[:, t, :].set(counts_t)
                regs = regs.at[:, t, :, :].set(regs_t)
                if iregs.shape[-1]:
                    iregs = iregs.at[:, t, :, :].set(iregs_t)
                if dl is not None:
                    dlh[0] = dl.at[:, t, :].set(dl_t)
                return (a, first, counts, regs, iregs, emit, out_vals, out_ivals,
                        emit_anchor, ovf)

            def _advance(s, mask, carry):
                """Instances (lanes of node s) in ``mask`` complete node s:
                emit (last node) or move into free lanes of node s+1."""
                a, first, counts, regs, iregs = (
                    carry[0], carry[1], carry[2], carry[3], carry[4])
                anchor = jnp.where(first[:, s, :] > 0, first[:, s, :],
                                   ts[:, None])  # [B, I]
                if s == S - 1:
                    return _emit_rows(mask, anchor, regs[:, s, :, :], carry,
                                      src_iregs=iregs[:, s, :, :])
                return _place(mask, anchor, regs[:, s, :, :], s + 1, carry,
                              src_iregs=iregs[:, s, :, :])

            def write_slot(regs, iregs, s, slot, upd):
                """Capture the current event into one register slot of
                node ``s`` for lanes in ``upd`` (float bank or hi/lo
                integer pair bank by slot kind)."""
                if slot.integer:
                    hk, lk = f"{slot.attr}|hi", f"{slot.attr}|lo"
                    if hk in cols:
                        iregs = iregs.at[:, s, :, 2 * slot.index].set(
                            jnp.where(upd, cols[hk][:, None],
                                      iregs[:, s, :, 2 * slot.index]))
                        iregs = iregs.at[:, s, :, 2 * slot.index + 1].set(
                            jnp.where(upd, cols[lk][:, None],
                                      iregs[:, s, :, 2 * slot.index + 1]))
                elif slot.attr in cols:
                    regs = regs.at[:, s, :, slot.index].set(
                        jnp.where(upd,
                                  cols[slot.attr].astype(jnp.float32)[:, None],
                                  regs[:, s, :, slot.index]))
                return regs, iregs

            lane0 = jnp.zeros((B, I), dtype=bool).at[:, 0].set(True)
            carry = (a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf)
            for s in reversed(range(S)):
                a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                node = nodes[s]
                spec = node.specs[0]
                if node.kind == "absent":
                    # a matching absent-stream event KILLS every pending
                    # instance waiting out the deadline (reference:
                    # absent violation, _process_event step 3); deadline
                    # completion itself runs in the timer step
                    if spec.stream_key != stream_key:
                        carry = (a, first, counts, regs, iregs, emit, out_vals,
                                 out_ivals, emit_anchor, ovf)
                        continue
                    viol = a[:, s, :] & ok_pre[s] & valid[:, None]
                    a = a.at[:, s, :].set(a[:, s, :] & ~viol)
                    counts = counts.at[:, s, :].set(
                        jnp.where(viol, 0, counts[:, s, :]))
                    first = first.at[:, s, :].set(
                        jnp.where(viol, 0, first[:, s, :]))
                    dlh[0] = dlh[0].at[:, s, :].set(
                        jnp.where(viol, 0, dlh[0][:, s, :]))
                    carry = (a, first, counts, regs, iregs, emit, out_vals,
                             out_ivals, emit_anchor, ovf)
                    continue
                if node.kind == "logical":
                    sides = [i for i, sp in enumerate(node.specs)
                             if sp.stream_key == stream_key
                             and not sp.is_absent]
                    kills = [i for i, sp in enumerate(node.specs)
                             if sp.stream_key == stream_key and sp.is_absent]
                    if not sides and not kills:
                        carry = (a, first, counts, regs, iregs, emit, out_vals,
                                 out_ivals, emit_anchor, ovf)
                        continue
                    with named_scope(SCOPE_DENSE_LOGICAL):
                        pending = a[:, s, :]
                        if s == 0 and every_start:
                            # the standing virgin lives in lane 0
                            pending = pending | lane0
                        for si in kills:
                            # and-not violation: the absent side arriving
                            # while the node is pending kills the instance
                            # (virgins re-arm per event, so only real armed
                            # lanes die)
                            viol = a[:, s, :] & ok_pre[s][si] & valid[:, None]
                            a = a.at[:, s, :].set(a[:, s, :] & ~viol)
                            counts = counts.at[:, s, :].set(
                                jnp.where(viol, 0, counts[:, s, :]))
                            first = first.at[:, s, :].set(
                                jnp.where(viol, 0, first[:, s, :]))
                            if dlh[0] is not None:
                                dlh[0] = dlh[0].at[:, s, :].set(
                                    jnp.where(viol, 0, dlh[0][:, s, :]))
                            pending = pending & ~viol
                            if s == 0 and every_start:
                                pending = pending | lane0
                        # event-time completion requires a present side to
                        # have matched THIS event (host completes only inside
                        # _try_capture's got branch); deferred completions —
                        # sides matched earlier, and-not-for deadline passing
                        # later — fire from the timer step alone
                        matched_now = jnp.zeros((B, I), dtype=bool)
                        for si in sides:
                            ok = ok_pre[s][si]
                            # an already-matched side ignores further events
                            # (the reference skips si in matched_sides —
                            # neither registers nor the anchor may refresh)
                            unmatched = (counts[:, s, :] & (1 << si)) == 0
                            fire = pending & ok & valid[:, None] & unmatched
                            if node.logical_op == "or":
                                # 'or' consumes only the FIRST matching side
                                # (host/reference leave the other side's
                                # capture null — LogicalPatternTestCase.
                                # testQuery3); 'and' lets one event fill both
                                fire = fire & ~matched_now
                            matched_now = matched_now | fire
                            counts = counts.at[:, s, :].set(
                                jnp.where(fire, counts[:, s, :] | (1 << si),
                                          counts[:, s, :]))
                            for slot in self.node_writes[s]:
                                if slot.ref == node.specs[si].ref:
                                    regs, iregs = write_slot(regs, iregs, s, slot, fire)
                            first = first.at[:, s, :].set(
                                jnp.where(fire & (first[:, s, :] == 0), ts[:, None],
                                          first[:, s, :]))
                        # completion needs every PRESENT side (absent sides
                        # contribute by staying silent); `and not B for t`
                        # additionally requires the deadline to have passed
                        # (host _logical_complete: now >= deadline, with a
                        # timer-consumed deadline reading as satisfied)
                        pmask = sum(1 << i for i, sp in enumerate(node.specs)
                                    if not sp.is_absent)
                        need = counts[:, s, :] & pmask
                        complete = (
                            (need == pmask)
                            if node.logical_op == "and"
                            else (need > 0)
                        ) & pending & valid[:, None] & matched_now
                        if self.deadline_w[s] is not None:
                            dls = dlh[0][:, s, :]
                            complete = complete & (
                                (dls == 0) | (ts[:, None] >= dls))
                        carry = _advance(s, complete,
                                         (a, first, counts, regs, iregs, emit, out_vals,
                                          out_ivals, emit_anchor, ovf))
                        a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                        # a completed logical node releases its lane (the host
                        # instance moves on); the lane-0 virgin re-arms fresh
                        a = a.at[:, s, :].set(a[:, s, :] & ~complete)
                        counts = counts.at[:, s, :].set(
                            jnp.where(complete, 0, counts[:, s, :]))
                        first = first.at[:, s, :].set(
                            jnp.where(complete, 0, first[:, s, :]))
                        if dlh[0] is not None and self.deadline_w[s] is not None:
                            dlh[0] = dlh[0].at[:, s, :].set(
                                jnp.where(complete, 0, dlh[0][:, s, :]))
                        carry = (a, first, counts, regs, iregs, emit, out_vals,
                                 out_ivals, emit_anchor, ovf)
                    continue
                if spec.stream_key != stream_key:
                    carry = (a, first, counts, regs, iregs, emit, out_vals,
                             out_ivals, emit_anchor, ovf)
                    continue
                is_count = not (node.min_count == 1 and node.max_count == 1)
                pending = a[:, s, :]
                if s == 0 and every_start:
                    if is_count:
                        with named_scope(SCOPE_DENSE_KLEENE):
                            # a fresh virgin arms only while no unsatisfied
                            # counting instance exists (the host rearms at
                            # satisfaction — StreamPostStateProcessor
                            # addEveryState), taking the first free lane
                            unsat = (a[:, 0, :] & (counts[:, 0, :] > 0)
                                     & (counts[:, 0, :] < max(node.min_count, 1)))
                            has_unsat = jnp.any(unsat, axis=1)  # [B]
                            free0 = ~a[:, 0, :] & (counts[:, 0, :] == 0)
                            vrank = jnp.cumsum(free0.astype(jnp.int32), axis=1) - 1
                            virgin = free0 & (vrank == 0) & ~has_unsat[:, None]
                            pending = pending | virgin
                            # a virgin that SHOULD arm (no unsatisfied arm, the
                            # event passes the start filter) but finds no free
                            # lane is a dropped instance — count it (node-0
                            # filters read candidate columns only, so lane 0
                            # of ok is lane-uniform)
                            no_lane = (~has_unsat & ~jnp.any(free0, axis=1)
                                       & ok_pre[s][:, 0] & valid)
                            ovf = ovf + no_lane.astype(jnp.int32)
                    elif group_every:
                        pending = pending | (lane0 & grp_virgin_ok)
                    else:
                        # simple start never rests: the standing virgin
                        # fires straight through lane 0 on every event
                        pending = pending | lane0
                fire = pending & ok_pre[s] & valid[:, None]
                if is_count:
                    with named_scope(SCOPE_DENSE_KLEENE):
                        below_max = (node.max_count == ANY) | (counts[:, s, :] < node.max_count)
                        cap = fire & below_max
                        first_cap = cap & (counts[:, s, :] == 0)
                        counts = counts.at[:, s, :].set(
                            jnp.where(cap, counts[:, s, :] + 1, counts[:, s, :]))
                        # a counting lane is occupied from its first capture
                        a = a.at[:, s, :].set(a[:, s, :] | first_cap)
                        for slot in self.node_writes[s]:
                            if slot.ref != spec.ref:
                                continue
                            upd = cap if slot.last else first_cap
                            regs, iregs = write_slot(regs, iregs, s, slot, upd)
                        first = first.at[:, s, :].set(
                            jnp.where(first_cap & (first[:, s, :] == 0), ts[:, None],
                                      first[:, s, :]))
                        open_count = (node.max_count == ANY
                                      or node.max_count > node.min_count)
                        advance = cap & (counts[:, s, :] == max(node.min_count, 1))
                        if not open_count or s == S - 1:
                            # exact counts ({n}) move at min==max; a count
                            # LAST node emits once at satisfaction
                            # (emitted_at_node semantics — later captures
                            # don't re-emit because advance fires at == min)
                            carry = _advance(s, advance,
                                             (a, first, counts, regs, iregs, emit,
                                              out_vals, out_ivals, emit_anchor, ovf))
                            a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                        # lane lifecycle at max: exact counts are spent (their
                        # advance already placed the instance); open counts
                        # MOVE the still-pending instance to s+1 at max
                        # (reference _try_capture: count >= max ->
                        # _enter_node(pos+1)); its clones already advanced via
                        # the via-path at earlier successor events
                        if node.max_count != ANY:
                            at_max = cap & (counts[:, s, :] >= node.max_count)
                            if open_count and s < S - 1:
                                anchor_s = jnp.where(
                                    first[:, s, :] > 0, first[:, s, :], ts[:, None])
                                carry = _place(at_max, anchor_s, regs[:, s, :, :],
                                               s + 1,
                                               (a, first, counts, regs, iregs,
                                                emit, out_vals, out_ivals,
                                                emit_anchor, ovf),
                                               src_iregs=iregs[:, s, :, :])
                                a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                            a = a.at[:, s, :].set(a[:, s, :] & ~at_max)
                            counts = counts.at[:, s, :].set(
                                jnp.where(at_max, 0, counts[:, s, :]))
                            first = first.at[:, s, :].set(
                                jnp.where(at_max, 0, first[:, s, :]))
                        carry = (a, first, counts, regs, iregs, emit, out_vals,
                                 out_ivals, emit_anchor, ovf)
                else:
                    # capture the node's slots for real pending lanes
                    for slot in self.node_writes[s]:
                        if slot.ref != spec.ref:
                            continue
                        regs, iregs = write_slot(regs, iregs, s, slot, fire)
                    if s == 0 and every_start:
                        # fresh arming each event: the within anchor must
                        # be this event's ts, not a stale one
                        first = first.at[:, s, :].set(
                            jnp.where(fire, ts[:, None], first[:, s, :]))
                    else:
                        first = first.at[:, s, :].set(
                            jnp.where(fire & (first[:, s, :] == 0), ts[:, None],
                                      first[:, s, :]))
                    # only `every` keeps the start armed; a non-every
                    # sequence arms once and dies with its arm (reference:
                    # init() re-arms only for every —
                    # SequenceTestCase.testQuery31, mirrored in the host
                    # engine's _process_event re-arm gate)
                    keep_armed = s == 0 and every_start
                    if not keep_armed:
                        a = a.at[:, s, :].set(a[:, s, :] & ~fire)
                    carry = _advance(s, fire,
                                     (a, first, counts, regs, iregs, emit, out_vals,
                                      out_ivals, emit_anchor, ovf))
                    # via-path: a dually-pending open count at s-1 clones
                    # straight through this node on the same event
                    # (reference: _try_enter from a satisfied count
                    # instance; StreamPreStateProcessor dual pending)
                    if s >= 1:
                        prev = nodes[s - 1]
                        prev_open = (
                            prev.kind == "stream"
                            and not (prev.min_count == 1 and prev.max_count == 1)
                            and (prev.max_count == ANY
                                 or prev.max_count > prev.min_count)
                        )
                        if prev_open:
                            with named_scope(SCOPE_DENSE_KLEENE):
                                a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                                sat = (a[:, s - 1, :]
                                       & (counts[:, s - 1, :] >= max(prev.min_count, 1)))
                                if prev.max_count != ANY:
                                    sat = sat & (counts[:, s - 1, :] < prev.max_count)
                                ok_via = (
                                    jnp.broadcast_to(jnp.asarray(
                                        node_filters[s][0].fn(
                                            env_for(s, cols, ts, regs, iregs,
                                                    regs_node=s - 1))).astype(bool),
                                        (B, I))
                                    if node_filters[s][0] is not None
                                    else jnp.ones((B, I), dtype=bool)
                                )
                                fire_via = sat & ok_via & valid[:, None]
                                via_regs = regs[:, s - 1, :, :]
                                via_iregs = iregs[:, s - 1, :, :]
                                for slot in self.node_writes[s]:
                                    if slot.ref != spec.ref:
                                        continue
                                    if slot.integer:
                                        hk, lk = (f"{slot.attr}|hi",
                                                  f"{slot.attr}|lo")
                                        if hk not in cols:
                                            continue
                                        via_iregs = via_iregs.at[
                                            :, :, 2 * slot.index].set(jnp.where(
                                                fire_via, cols[hk][:, None],
                                                via_iregs[:, :, 2 * slot.index]))
                                        via_iregs = via_iregs.at[
                                            :, :, 2 * slot.index + 1].set(jnp.where(
                                                fire_via, cols[lk][:, None],
                                                via_iregs[:, :, 2 * slot.index + 1]))
                                    elif slot.attr in cols:
                                        via_regs = via_regs.at[:, :, slot.index].set(
                                            jnp.where(
                                                fire_via,
                                                cols[slot.attr].astype(jnp.float32)[:, None],
                                                via_regs[:, :, slot.index]))
                                via_anchor = jnp.where(
                                    first[:, s - 1, :] > 0, first[:, s - 1, :],
                                    ts[:, None])
                                carry = (a, first, counts, regs, iregs, emit,
                                         out_vals, out_ivals, emit_anchor, ovf)
                                if s == S - 1:
                                    carry = _emit_rows(fire_via, via_anchor,
                                                       via_regs, carry, bank=1,
                                                       src_iregs=via_iregs)
                                else:
                                    carry = _place(fire_via, via_anchor, via_regs,
                                                   s + 1, carry,
                                                   src_iregs=via_iregs)
                                # PATTERN forward-once: the dually-pending arm
                                # is consumed at its successor match — it can
                                # emit at most once (reference
                                # removeIfNextStateProcessed; the host engine
                                # kills the source on via-advance likewise)
                                a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry
                                a = a.at[:, s - 1, :].set(
                                    a[:, s - 1, :] & ~fire_via)
                                counts = counts.at[:, s - 1, :].set(
                                    jnp.where(fire_via, 0, counts[:, s - 1, :]))
                                first = first.at[:, s - 1, :].set(
                                    jnp.where(fire_via, 0, first[:, s - 1, :]))
                                carry = (a, first, counts, regs, iregs, emit,
                                         out_vals, out_ivals, emit_anchor, ovf)

            a, first, counts, regs, iregs, emit, out_vals, out_ivals, emit_anchor, ovf = carry

            # emission restart
            if reset_on_emit:
                any_emit = jnp.any(emit, axis=1)
                a = jnp.where(any_emit[:, None, None], False, a)
                counts = jnp.where(any_emit[:, None, None], 0, counts)
                first = jnp.where(any_emit[:, None, None], 0, first)
                if dlh[0] is not None:
                    dlh[0] = jnp.where(any_emit[:, None, None], 0, dlh[0])

            return (a, first, counts, regs, iregs, ovf, dlh[0], emit,
                    out_vals, out_ivals, emit_anchor)

        def advance_fields(f, cols, ts, valid):
            B = ts.shape[0]
            iregs = f.get("iregs")
            if iregs is None:
                iregs = jnp.zeros((B, S, I, 0), dtype=jnp.int32)
            with named_scope(SCOPE_DENSE_ADVANCE):
                (a, first, counts, regs, iregs, ovf, dl, emit, out_vals,
                 out_ivals, emit_anchor) = advance(
                    f["active"], f["first_ts"], f["counts"], f["regs"],
                    iregs, jnp.zeros((B,), dtype=jnp.int32),
                    f.get("deadline"), cols, ts, valid)
            new = {"active": a, "first_ts": first, "counts": counts,
                   "regs": regs, "iregs": iregs, "deadline": dl}
            # `ovf` went in as zeros: it is this event's increment
            return (new, ovf, emit, {"f": out_vals, "i": out_ivals},
                    emit_anchor)

        self._step_cache[cache_key] = advance_fields
        return advance_fields

    # -- rounds past the first, on the device -------------------------------

    #: rounds at most this wide are the *run*: their rows stay resident
    #: (one vector of 128 lanes)
    RUN_WIDTH = 128
    #: links of a run the kernel takes in one call; a longer run takes
    #: several calls
    RUN_LINKS = 2048
    #: the wide rounds run at the segment's width, then at this fraction
    #: of it, so a round a little over RUN_WIDTH is not stepped at the
    #: width of the widest
    ROUNDS_NARROW = 8

    def rounds_ladder(self, R: int) -> List[Tuple[int, int]]:
        """The wide loops of a rounds program of ``R`` padded lanes,
        widest first: the static width each slices a round at, and the
        round width at which it hands over to the next (the last to the
        run).  A segment no wider than the run has none."""
        widths = [w for w in (R, R // self.ROUNDS_NARROW)
                  if w > self.RUN_WIDTH]
        return list(zip(widths, widths[1:] + [self.RUN_WIDTH]))

    def rounds_lanes(self, R: int, widths: np.ndarray) -> int:
        """Lanes a rounds program of ``R`` padded lanes steps for
        rounds ``widths`` wide (each no wider than the one before):
        every round at the width of the loop that takes it, a link of
        the run at ``RUN_WIDTH``."""
        sliced = np.full(len(widths), self.RUN_WIDTH, dtype=np.int64)
        for w, narrower in reversed(self.rounds_ladder(R)):
            sliced[widths > narrower] = w
        return int(sliced.sum())

    def _make_run_kernel(self, stream_key: str) -> Optional[Callable]:
        """The Pallas kernel for the run (``kernels/dense_run.py``) where
        the engine is in its class and Mosaic compiles it; None where
        the XLA loop stays: every other engine, and every backend but
        the TPU."""
        from siddhi_tpu.kernels import dense_run, probe

        if not dense_run.eligible(self, stream_key) or (
                probe.interpret_mode() and not dense_run.INTERPRET_OFF_TPU):
            return None
        run = dense_run.build_run(self, stream_key)
        if not probe.interpret_mode():
            try:
                dense_run.smoke_compile(self, stream_key, run)
            except Exception as e:   # Mosaic's refusal, with its message
                log.warning("dense run kernel not used for '%s', the XLA "
                            "loop stays: %s", stream_key, e)
                return None
        return run

    def make_rounds(self, stream_key: str,
                    col_keys: Optional[Tuple[str, ...]] = None) -> Callable:
        """Build the program for a batch's rounds past the first.

        rounds(state, buf i32 [k, R])
          -> as :meth:`make_step`, the emit arrays indexed by lane

        ``buf`` is the packed buffer of :meth:`lane_table` with its
        offsets row: partition rows, the columns ``col_keys``, relative
        ms and ``off``.  The lanes hold the events of the batch's second
        and later rounds in :func:`round_plan`'s order: round ``r`` is
        lanes ``off[r]:off[r + 1]``, each round's partitions a prefix of
        the one before, ``off`` padded with the number of lanes (which
        is also ``off[R]``, an entry the row has no room for: the lanes
        whose partition row is not the scratch row, counted here).  How
        many rounds there are and how wide each is are runtime values:
        one program serves every batch of ``R`` padded lanes.

        Wide rounds each go through :meth:`make_step`'s step (gather,
        automaton, scatter) inside a ``while_loop``.  From the first
        round of at most ``RUN_WIDTH`` partitions on, those partitions'
        rows are gathered once, their fields go through one round after
        another (a dependence chain as long as the longest run, with no
        host round trip and no access to the state per link), and the
        rows are scattered back once.  A link is
        :meth:`make_advance`'s automaton, the one every other path runs,
        so captures, counts, ``within``, deadlines, integer registers
        and overflow keep their semantics exactly; for the class of
        engines ``kernels/dense_run.py`` covers the whole chain is one
        Pallas kernel that mirrors that automaton bit for bit (a link
        is some 220 XLA operations of 128 rows each, a thousand links a
        quarter of a million kernel launches)."""
        table = self.lane_table(stream_key, col_keys, offsets=True)
        cache_key = (stream_key, "rounds", table.names)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        jax, jnp = self.jax, self.jnp
        lax = jax.lax
        tree_map = jax.tree_util.tree_map
        step = self.make_step(stream_key, jit=False)
        advance = self.make_advance(stream_key)
        run_kernel = self._make_run_kernel(stream_key)
        layout = self.layout
        I, E = self.I, self.emit_lanes
        O = max(len(self.out_spec), 1)
        n_iout = sum(self.out_int)
        H = self.RUN_WIDTH
        named_scope = jax.named_scope

        def rounds(state, buf):
            part_idx, cols, ts, off = self._unpack_lanes(table, buf)
            R = part_idx.shape[0]
            scratch = state[ROWS].shape[0] - 1
            end = jnp.sum((part_idx != scratch).astype(jnp.int32))
            off = jnp.concatenate([off, end[None]])
            n_rounds = jnp.sum((off[:R] < end).astype(jnp.int32))
            # a round is sliced at its loop's static width: pad the
            # lanes so that no slice is clamped back into other rounds
            pad = max(R, H)
            lanes = tree_map(
                lambda x: jnp.concatenate(
                    [x, jnp.zeros((pad,), dtype=x.dtype)]),
                {"p": part_idx, "t": ts, "c": cols})
            bufs = {
                "emit": jnp.zeros((R + pad, E), dtype=bool),
                "f": jnp.zeros((R + pad, E, O), dtype=jnp.float32),
                "i": jnp.zeros((R + pad, E, 2 * n_iout), dtype=jnp.int32),
                "anchor": jnp.zeros((R + pad, E), dtype=jnp.int32),
            }

            def width(r):
                return off[jnp.minimum(r + 1, R)] - off[jnp.minimum(r, R)]

            def loop(one_round, w, narrower, carry):
                """Rounds from ``r = carry[-1]`` on while they are wider
                than ``narrower``, each sliced ``w`` lanes wide.
                ``one_round(st, at, valid)`` takes the loop's state
                through the round's lanes and returns it with the
                round's emit arrays and match count."""
                def body(c):
                    st, bufs, count, r = c
                    at = tree_map(
                        lambda x: lax.dynamic_slice_in_dim(x, off[r], w),
                        lanes)
                    valid = jnp.arange(w) < width(r)
                    st, emit, outs, anchor = one_round(st, at, valid)
                    emit = emit & valid[:, None]
                    new = {"emit": emit, "f": outs["f"], "i": outs["i"],
                           "anchor": anchor}
                    # later rounds lie further on: what a round writes
                    # past its own lanes, the rounds that own them
                    # overwrite
                    bufs = {k: lax.dynamic_update_slice_in_dim(
                        bufs[k], v, off[r], 0) for k, v in new.items()}
                    return (st, bufs,
                            count + jnp.sum(emit.astype(jnp.int32)), r + 1)

                return lax.while_loop(
                    lambda c: (c[3] < n_rounds) & (width(c[3]) > narrower),
                    body, carry)

            def stepped(st, at, valid):
                st, emit, outs, anchor, _n = step(
                    st, jnp.where(valid, at["p"], scratch), at["c"],
                    at["t"], valid)
                return st, emit, outs, anchor

            carry = (state, bufs, jnp.int32(0), jnp.int32(0))
            with named_scope(SCOPE_DENSE_ROUNDS):
                for w, narrower in self.rounds_ladder(R):
                    carry = loop(stepped, w, narrower, carry)
            state, bufs, count, r = carry

            def advanced(st, at, valid):
                f, ovf = st
                new, d_ovf, emit, outs, anchor = advance(
                    f, at["c"], at["t"], valid)
                keep = lambda n, o: jnp.where(
                    valid.reshape((-1,) + (1,) * (o.ndim - 1)), n, o)
                f = {k: keep(new[k], v) for k, v in f.items()}
                return ((f, ovf + jnp.where(valid, d_ovf, 0)), emit, outs,
                        anchor)

            def run_by_kernel(f, ovf, bufs, count, r):
                """The run through ``run_kernel``, up to ``RUN_LINKS``
                links a call: each link's lanes laid out as a row of
                ``H``, and what the links emit brought back to lane
                order."""
                # the lanes in tiles of H, one tile past the last lane;
                # and the round every lane lies in
                n_tiles = -(-R // H) + 1
                tiles = tree_map(
                    lambda x: jnp.concatenate([x, jnp.zeros(
                        (n_tiles * H - R,), x.dtype)]).reshape(n_tiles, H),
                    {"c": cols, "t": ts})
                lane = jnp.arange(R)
                rnd = jnp.searchsorted(off, lane, side="right") - 1
                L = self.RUN_LINKS

                def one_call(c):
                    f, ovf, bufs, count, r = c
                    n_links = jnp.clip(n_rounds - r, 0, L)
                    at = jnp.minimum(r + jnp.arange(L), R)
                    starts = off[at]
                    widths = jnp.where(
                        jnp.arange(L) < n_links,
                        off[jnp.minimum(at + 1, R)] - starts, 0)
                    f, d_ovf, emit, anchor, out = run_kernel(
                        f, tiles["c"], tiles["t"], starts, widths, n_links)
                    # lane -> (link, position): the round it lies in
                    mine = (lane < end) & (rnd >= r) & (rnd < r + n_links)
                    li = jnp.clip(rnd - r, 0, L - 1)
                    pos = jnp.clip(lane - off[jnp.clip(rnd, 0, R)], 0,
                                   H - 1)
                    fired = emit[:, li, pos].T & mine[:, None]    # [R, I]
                    vals, o = [], 0
                    for _name, src in self.out_spec:
                        if isinstance(src, tuple):   # ('cand', attr)
                            col = cols.get(src[1])
                            vals.append(
                                jnp.zeros((R, I), jnp.float32)
                                if col is None else jnp.where(
                                    fired,
                                    col.astype(jnp.float32)[:, None], 0.0))
                        else:
                            vals.append(jnp.where(
                                fired, out[o][:, li, pos].T, 0.0))
                            o += 1
                    if not vals:
                        vals = [jnp.zeros((R, I), jnp.float32)]
                    new = {"emit": fired,
                           "f": jnp.stack(vals, axis=-1),
                           "anchor": jnp.where(fired,
                                               anchor[:, li, pos].T, 0)}
                    # (this class has no via-path: E == I)
                    bufs = dict(bufs)
                    for k, v in new.items():
                        keep = mine.reshape((R,) + (1,) * (v.ndim - 1))
                        bufs[k] = bufs[k].at[:R, :I].set(
                            jnp.where(keep, v, bufs[k][:R, :I]))
                    return (f, ovf + d_ovf, bufs,
                            count + jnp.sum(fired.astype(jnp.int32)),
                            r + n_links)

                return lax.while_loop(lambda c: c[4] < n_rounds, one_call,
                                      (f, ovf, bufs, count, r))

            with named_scope(SCOPE_DENSE_RUN):
                # position j of every remaining round is one partition:
                # that of lane j of the first of them.  Positions past
                # its width name the scratch row and write back the
                # words they read.
                in_run = jnp.arange(H) < width(r)
                rows = jnp.where(
                    in_run,
                    lax.dynamic_slice_in_dim(lanes["p"], off[r], H), scratch)
                with named_scope(SCOPE_DENSE_GATHER):
                    f, old = layout.gather(state, rows)
                ovf = jnp.zeros((H,), dtype=jnp.int32)
                if run_kernel is not None:
                    f, ovf, bufs, count, r = run_by_kernel(
                        f, ovf, bufs, count, r)
                else:
                    (f, ovf), bufs, count, r = loop(
                        advanced, H, -1, ((f, ovf), bufs, count, r))
                with named_scope(SCOPE_DENSE_SCATTER):
                    state = layout.scatter(state, rows, f, ovf, in_run, old)
            return (state, bufs["emit"][:R], {"f": bufs["f"][:R],
                                              "i": bufs["i"][:R]},
                    bufs["anchor"][:R], count)

        fn = jax.jit(rounds, donate_argnums=(0,))
        self._step_cache[cache_key] = fn
        return fn

    # -- timer step (absent-node deadlines) ---------------------------------

    def make_time_step(self, jit: bool = True) -> Callable:
        """Build the deadline-timer step (engines with absent states).

        time_step(state, now_i32_rel)
          -> (state, emit[P, I] bool, outs {f, i}, fire[P, I] i32,
              n_emit i32)

        Runs over ALL partition rows (no event batch): pending instances
        whose absent deadline passed advance to the next node — or emit,
        when the absent node ends the chain — exactly like the host
        engine's scheduler tick (ops/nfa.py on_time; reference
        AbsentStreamPreStateProcessor timer path).  ``fire[p, i]`` is the
        deadline (relative ms) the instance fired at, which becomes the
        emitted match's timestamp.
        """
        cache_key = ("__time__", jit)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        jnp = self.jnp
        S, I = self.S, self.I
        nodes = self.nodes
        within = self.within_ms
        reset_on_emit = self.reset_on_emit
        out_spec = self.out_spec
        O = max(len(out_spec), 1)
        n_iout = sum(self.out_int)

        layout = self.layout
        R = layout.fields["regs"][1][2]
        RI = 2 * self.alloc.n_int

        def nd(x, s, k=None):
            """Node ``s`` of a flat field [Pr, S*I] or register bank
            [Pr, S*I*k]: its lanes [Pr, I], or [Pr, I, k] for a bank.
            Column slices only — the state is never reshaped to
            [Pr, S, I] (a relayout of every row; ops/dense_layout.py)."""
            if k is None:
                return x[:, s * I:(s + 1) * I]
            return x[:, s * I * k:(s + 1) * I * k].reshape(x.shape[0], I, k)

        def set_nd(x, s, v):
            w = x.shape[1] // S
            return x.at[:, s * w:(s + 1) * w].set(v.reshape(x.shape[0], w))

        def time_step(state, now):
            f = layout.split(state[ROWS], shaped=False)
            a, first, counts = f["active"], f["first_ts"], f["counts"]
            regs, dl = f["regs"], f["deadline"]
            Pr = a.shape[0]
            iregs = (f["iregs"] if "iregs" in f
                     else jnp.zeros((Pr, 0), dtype=jnp.int32))
            ovf = state[OVERFLOW]
            emit = jnp.zeros((Pr, I), dtype=bool)
            out_f = jnp.zeros((Pr, I, O), dtype=jnp.float32)
            out_i = jnp.zeros((Pr, I, 2 * n_iout), dtype=jnp.int32)
            fire = jnp.zeros((Pr, I), dtype=jnp.int32)

            # within expiry first (host on_time calls _expire(now) before
            # firing deadlines): an instance that ran out of its within
            # window never fires
            if within is not None:
                expired = (first > 0) & (now - first > within)
                a = a & ~expired
                counts = jnp.where(expired, 0, counts)
                first = jnp.where(expired, 0, first)
                dl = jnp.where(expired, 0, dl)

            # descending node order: a fire at node s placing into s+1
            # cannot re-fire this tick (host on_time is likewise a
            # single pass over instances)
            for s in reversed(range(S)):
                w = self.deadline_w[s]
                if w is None:
                    continue
                node = nodes[s]
                ft = nd(dl, s)  # fire timestamps (valid where due)
                due = nd(a, s) & (ft > 0) & (now >= ft)
                if node.kind == "logical":
                    # complete only if every present side already
                    # matched; either way the deadline is CONSUMED (host
                    # clears inst.deadline at the tick — a later present
                    # match then completes immediately)
                    pmask = sum(1 << i for i, sp in enumerate(node.specs)
                                if not sp.is_absent)
                    fire_mask = due & ((nd(counts, s) & pmask) == pmask)
                else:
                    fire_mask = due
                dl = set_nd(dl, s, jnp.where(due, 0, ft))
                anchor = jnp.where(nd(first, s) > 0, nd(first, s), ft)
                regs_s, iregs_s = nd(regs, s, R), nd(iregs, s, RI)
                if s == S - 1:
                    emit = emit | fire_mask
                    fire = jnp.where(fire_mask, ft, fire)
                    # outputs come from the node's register banks alone —
                    # select items never reference the absent event
                    # (validated at construction)
                    ii = 0
                    for oi, (_name, src) in enumerate(out_spec):
                        if self.out_int[oi]:
                            out_i = out_i.at[:, :, 2 * ii].set(jnp.where(
                                fire_mask, iregs_s[:, :, 2 * src.index],
                                out_i[:, :, 2 * ii]))
                            out_i = out_i.at[:, :, 2 * ii + 1].set(jnp.where(
                                fire_mask, iregs_s[:, :, 2 * src.index + 1],
                                out_i[:, :, 2 * ii + 1]))
                            ii += 1
                        else:
                            out_f = out_f.at[:, :, oi].set(jnp.where(
                                fire_mask, regs_s[:, :, src.index],
                                out_f[:, :, oi]))
                else:
                    t = s + 1
                    w2 = self.deadline_w[t]
                    entry_dl = (ft + w2) if w2 is not None else None
                    a_t, first_t, counts_t, regs_t, iregs_t, dl_t, ovf = (
                        _rank_place(
                            jnp, fire_mask, anchor, regs_s, iregs_s,
                            entry_dl, nd(a, t), nd(first, t), nd(counts, t),
                            nd(regs, t, R), nd(iregs, t, RI), nd(dl, t),
                            ovf))
                    a = set_nd(a, t, a_t)
                    first = set_nd(first, t, first_t)
                    counts = set_nd(counts, t, counts_t)
                    regs = set_nd(regs, t, regs_t)
                    if RI:
                        iregs = set_nd(iregs, t, iregs_t)
                    dl = set_nd(dl, t, dl_t)
                a = set_nd(a, s, nd(a, s) & ~fire_mask)
                counts = set_nd(counts, s,
                                jnp.where(fire_mask, 0, nd(counts, s)))
                first = set_nd(first, s,
                               jnp.where(fire_mask, 0, nd(first, s)))

            if reset_on_emit:
                any_emit = jnp.any(emit, axis=1)[:, None]
                a = jnp.where(any_emit, False, a)
                counts = jnp.where(any_emit, 0, counts)
                first = jnp.where(any_emit, 0, first)
                dl = jnp.where(any_emit, 0, dl)

            new_state = {
                ROWS: layout.join({
                    "active": a, "first_ts": first, "counts": counts,
                    "regs": regs, "iregs": iregs, "deadline": dl}),
                OVERFLOW: ovf,
            }
            n_emit = jnp.sum(emit.astype(jnp.int32))
            return new_state, emit, {"f": out_f, "i": out_i}, fire, n_emit

        fn = self.jax.jit(time_step, donate_argnums=(0,)) if jit else time_step
        self._step_cache[cache_key] = fn
        return fn

    # -- snapshot -------------------------------------------------------------

    def snapshot_state(self, state) -> Dict[str, object]:
        """The logical fields of ``state`` as device arrays of their
        own (``ops/dense_layout.py`` ``logical``), each a
        ``SnapshotField`` under its logical shape: one program over the
        whole state, dispatched and not waited for.  Nothing is
        donated, so ``state`` is what it was and the fields outlive
        every later step."""
        fn = self._step_cache.get("snapshot")
        if fn is None:
            fn = self._step_cache["snapshot"] = self.jax.jit(
                self.layout.logical)
        return self.layout.snapshot_fields(fn(state))

    def next_wakeup_state(self, state) -> Optional[int]:
        """Earliest armed absent deadline (absolute ms), or None.  One
        device reduction + scalar transfer; engines without deadline
        nodes return None without touching the device."""
        if not self.has_deadlines or self.base_ts is None:
            return None
        fn = self._step_cache.get("wakeup")
        if fn is None:
            jnp = self.jnp
            layout = self.layout

            def earliest(rows):
                f = layout.split(rows, shaped=False)
                dl = f["deadline"]
                return jnp.min(jnp.where(f["active"] & (dl > 0), dl,
                                         jnp.int32(2**31 - 1)))

            fn = self._step_cache["wakeup"] = self.jax.jit(earliest)
        m = int(fn(state[ROWS]))
        if m >= 2**31 - 1:
            return None
        return self.base_ts + m

    def on_time_state(self, state, now: int):
        """Advance deadline timers to absolute time ``now``.

        Returns ``(state, fired)`` where ``fired`` is None (common) or
        ``(out[m, n_out], fire_ts[m] absolute-ms, part_rows[m])``
        ordered by (fire time, partition row, lane) — the host engine's
        deadline-ordered flush.  Works on sharded state too: the step is
        row-parallel, so XLA's sharding propagation runs it shard-local
        with no collectives."""
        if not self.has_deadlines or self.base_ts is None:
            return state, None
        rel = now - self.base_ts
        if rel <= 0:
            return state, None
        rel = min(rel, 2**31 - 1)
        tstep = self.make_time_step()
        state, emit, outs, fire, n_emit = tstep(state, np.int32(rel))
        # explicit count-gate fetch: int(device_scalar) is an IMPLICIT
        # transfer and would trip jax.transfer_guard('disallow')
        if int(self.jax.device_get(n_emit)) == 0:
            return state, None
        emit_np = np.asarray(emit)
        rows, lanes = np.nonzero(emit_np)
        out = self.assemble_out(np.asarray(outs["f"]), np.asarray(outs["i"]),
                                rows, lanes)
        fire_np = (np.asarray(fire)[rows, lanes].astype(np.int64)
                   + self.base_ts)
        order = np.lexsort((lanes, rows, fire_np))
        return state, (out[order], fire_np[order], rows[order])

    # -- host wrapper -------------------------------------------------------

    base_ts: Optional[int] = None
    # re-anchor before relative ms approach int32 range (~24.8 days of
    # stream time); headroom covers one batch + the within horizon
    _REL_LIMIT = 2**31 - 2**24

    def rel_ts64(self, ts: np.ndarray) -> np.ndarray:
        if self.base_ts is None:
            self.base_ts = int(ts[0]) - 1 if len(ts) else 0
        return ts - self.base_ts

    def maybe_re_anchor(self, state, rel64: np.ndarray, to_device=None):
        """Shift base_ts forward when relative timestamps approach the
        int32 range (they silently wrap after ~24.8 days otherwise and
        `within` checks corrupt).  ``first_ts`` anchors shift with it;
        instances whose anchor falls outside the `within` horizon are
        already expired and get their bits/counters cleared host-side
        (a once-per-24-days op, so the host round trip is fine).

        ``to_device(key, np_array)`` converts arrays back (defaults to
        jnp.asarray; the sharded wrapper passes a resharding put)."""
        if not len(rel64) or int(rel64.max()) < self._REL_LIMIT:
            return state, rel64
        horizon = self.within_ms or 0
        delta = int(rel64.min()) - 1 - horizon
        if delta <= 0 or int(rel64.max()) - delta >= 2**31:
            raise SiddhiAppRuntimeError(
                "dense NFA: timestamp span of one batch plus the within "
                "horizon exceeds the int32 relative-time range")
        self.base_ts += delta
        rel64 = rel64 - delta
        # host round trip in the logical form; shift_row_ts holds the
        # one definition of what a base shift does to anchors/deadlines
        logical = self.shift_row_ts(self.layout.unpack(state), delta)
        if to_device is not None:
            conv = to_device
        elif self.mesh is not None:
            # keep the partition-axis sharding init_state applied — a
            # plain jnp.asarray would silently collapse state onto the
            # default device after a re-anchor
            from jax.sharding import NamedSharding

            specs = self.state_pspecs()
            conv = lambda k, v: self.jax.device_put(
                v, NamedSharding(self.mesh, specs[k]))
        else:
            conv = lambda _k, v: self.jnp.asarray(v)
        state = dict(state)
        state[ROWS] = conv(ROWS, self.layout.pack(logical)[ROWS])
        return state, rel64

    def shift_row_ts(self, rows: Dict[str, np.ndarray],
                     delta: int) -> Dict[str, np.ndarray]:
        """Re-express HOST-side state rows against a base shifted by
        ``delta`` (new_base = old_base + delta), both directions.

        The multiplex group engine shares one ``base_ts`` across
        tenants: restoring a tenant snapshot taken under a different
        anchor, or admitting a tenant whose events predate the group
        anchor (a group-wide down-shift, delta < 0), rewrites the
        ``first_ts``/``deadline`` anchors (:meth:`maybe_re_anchor` is
        built on this) — forward shifts expire instances that
        fall out of the ``within`` horizon (or clamp inert anchors to
        stay set), backward shifts only grow the values, bounded by the
        int32 range.  ``rows`` must already be HOST numpy arrays (both
        callers fetch before shifting) — no device materialization
        happens here."""
        out = dict(rows)
        first = rows["first_ts"].astype(np.int64)
        shifted = np.where(first > 0, first - delta, 0)
        if int(shifted.max(initial=0)) >= 2**31:
            raise SiddhiAppRuntimeError(
                "dense NFA: timestamp shift exceeds the int32 "
                "relative-time range")
        if delta > 0:
            if self.within_ms is not None:
                dead = (first > 0) & (shifted <= 0)
                if dead.any():
                    active = rows["active"].copy()
                    counts = rows["counts"].copy()
                    active[dead] = False
                    counts[dead] = 0
                    shifted = np.where(dead, 0, shifted)
                    out["active"] = active
                    out["counts"] = counts
            else:
                shifted = np.where(first > 0, np.maximum(shifted, 1), 0)
        out["first_ts"] = shifted.astype(np.int32)
        if "deadline" in rows:
            dlv = rows["deadline"].astype(np.int64)
            dshift = np.where(dlv > 0, dlv - delta, 0)
            if delta > 0:
                dshift = np.where(dlv > 0, np.maximum(dshift, 1), 0)
            elif int(dshift.max(initial=0)) >= 2**31:
                raise SiddhiAppRuntimeError(
                    "dense NFA: timestamp shift exceeds the int32 "
                    "relative-time range")
            out["deadline"] = dshift.astype(np.int32)
        return out

    def process(self, state, stream_key: str, part_idx: np.ndarray, cols: Dict[str, np.ndarray], ts: np.ndarray):
        """Process a batch, splitting rounds so each partition appears at
        most once per step (scatter collisions would race).  Rounds are
        padded to powers of two to bound jit recompilation.

        Returns ``(state, match_ev_idx, match_out)``: one row per match,
        ``match_ev_idx[m]`` the batch-row index of the completing event
        (ascending; same-event matches ordered by arming age, mirroring
        the reference's pendingStateEventList iteration order) and
        ``match_out[m, n_out]`` its output values."""
        state, pending = self.process_deferred(state, stream_key, part_idx,
                                               cols, ts)
        if pending is not None and pending.resolve() == 0:
            pending = None
        if pending is None:
            return state, *flatten_match_parts(
                [], [], [], max(len(self.out_spec), 1))
        from siddhi_tpu.core.emit_queue import fetch_coalesced

        ev, out = pending.materialize(fetch_coalesced(
            pending.device_arrays()))
        return state, ev, out

    def process_deferred(self, state, stream_key: str, part_idx: np.ndarray,
                         cols: Dict[str, np.ndarray], ts: np.ndarray):
        """Async-emit variant of :meth:`process`: the batch's match
        outputs stay resident on device inside the returned
        :class:`DeferredDenseEmit` (None only for empty input).  NOTHING
        crosses device->host here — even the ``n_emit`` count gates stay
        device scalars until ``resolve()`` fetches them, which the
        ingest stage (core/ingest_stage.py) defers past the next batch's
        dispatch so the H2D transfer overlaps this batch's step.

        A partition may appear any number of times in the batch.
        :func:`round_plan` orders the events by (occurrence within their
        partition, partition); the first occurrences go through the
        plain step, every later occurrence through :meth:`make_rounds`'
        program, which loops over the remaining rounds on the device (a
        single second round through the step again).  At most two H2D
        puts, each of one packed buffer (:meth:`lane_table`), and two
        dispatches a batch, however long the longest run of one
        partition is.  The lanes those programs step, padding
        and all, are the cycle's ``lanes`` count and add to the
        runtime's ``steppedLanes``: known here from the plan's widths,
        with nothing fetched; a lane gathers one resident row, so the
        lanes times the row's bytes are the cycle's ``state_bytes`` and
        add to ``steppedStateBytes``.  The batch's stream, as its place in
        ``stream_keys``, is the cycle's ``stream`` count, and the
        runtime's ``batchesByStream.<stream>`` counts its batches."""
        faults = getattr(self, "faults", None)
        if faults is not None:
            faults.check("step.dense")
        from siddhi_tpu.core.ingest_stage import staged_put

        n = len(part_idx)
        if n == 0:
            return state, None
        with span(STAGE_CONVERT, n):
            part_idx = np.asarray(part_idx, dtype=np.int32)
            rel64 = self.rel_ts64(np.asarray(ts, dtype=np.int64))
            state, rel64 = self.maybe_re_anchor(state, rel64)
            rel = rel64.astype(np.int32)
            prepared = self.prepare_cols(stream_key, cols)
            # the programs are keyed on the columns the batch brings
            col_keys = tuple(prepared)
            step = self.make_step(stream_key, col_keys=col_keys)
        with span(STAGE_PLAN) as sp:
            plan = self.plan_rounds(part_idx)
            if sp is not None:
                sp.count = plan.n_rounds
        # the first round, and everything behind it; a second round that
        # is also the last is one more call of the step, and only a
        # third makes the rounds program worth its trace (a second or
        # two a shape, which a cell of two rounds would pay at set-up)
        programs = ((step,) * plan.n_rounds if plan.n_rounds <= 2
                    else (step, self.make_rounds(stream_key, col_keys)))
        bounds = (0, int(plan.off[1]), n)[:len(programs) + 1]
        pending = DeferredDenseEmit(self)
        stats = getattr(self, "ingest_stats", None)
        stepped = 0     # lanes the programs step, padding and all
        for program, lo, hi in zip(programs, bounds, bounds[1:]):
            ev = plan.lanes[lo:hi]
            rounds = program is not step
            with span(STAGE_CONVERT, hi - lo):
                # the rounds program's offsets row: starts of rounds 1..
                # within the rest, then its end on every further entry
                buf = self._pad_lanes(
                    self.lane_table(stream_key, col_keys, offsets=rounds),
                    part_idx, prepared, rel, ev,
                    plan.off[1:-1] - lo if rounds else None)
                stepped += (self.rounds_lanes(buf.shape[1],
                                              np.diff(plan.off[1:]))
                            if rounds else buf.shape[1])
            # ONE H2D put of one leaf a program behind the ingest.put
            # fault site (core/ingest_stage.py — the sanctioned ingest
            # path); the second goes while the device steps the first
            # round
            buf = staged_put(buf, faults=faults, stats=stats)
            with span(STAGE_DISPATCH, 1):
                state, emit, outs, emit_anchor, n_emit = program(state, buf)
            # count gate deferred: n_emit stays a device scalar until
            # DeferredDenseEmit.resolve() (driven by the ingest stage)
            pending.chunks.append({
                "emit": emit, "f": outs["f"], "i": outs["i"],
                "anchor": emit_anchor, "sel": slice(0, hi - lo),
                "ridx": ev, "count": n_emit,
            })
        state_bytes = stepped * self.layout.width * 4
        counted(STAGE_LANES, stepped)
        counted(STAGE_STATE_BYTES, state_bytes)
        counted(STAGE_STREAM, self.stream_keys.index(stream_key))
        if stats is not None:
            stats.stepped_lanes += stepped
            stats.stepped_state_bytes += state_bytes
            stats.batches_by_stream[stream_key] = (
                stats.batches_by_stream.get(stream_key, 0) + 1)
        return state, pending

    def plan_rounds(self, part_idx: np.ndarray) -> "RoundPlan":
        """:func:`round_plan` of one batch of this engine's rows, with
        the engine's own vector over the row space (made once: the
        callers plan under their runtime's lock, one batch at a time).
        The events past their partition's first add to the runtime's
        ``plannedRepeats``."""
        if self._plan_first is None:
            self._plan_first = np.empty(self.n_partitions + 1,
                                        dtype=np.int32)
        plan = round_plan(part_idx, self._plan_first)
        stats = getattr(self, "ingest_stats", None)
        if stats is not None and plan.n_rounds:
            stats.planned_repeats += len(part_idx) - int(plan.off[1])
        return plan

    def lane_table(self, stream_key: str,
                   col_keys: Optional[Tuple[str, ...]] = None,
                   offsets: bool = False) -> LaneTable:
        """The rows of the one buffer a dispatched program's host lanes
        cross as (``ops/packed_lanes.py``): the partition row, a row a
        device column (``col_keys``: those a batch brings, in
        :meth:`device_col_keys`' order; by default all of them), the
        relative ms and, for the rounds program, the rounds' offsets."""
        if col_keys is None:
            col_keys = tuple(self.device_col_keys(stream_key))
        cache_key = (stream_key, "lanes", col_keys, offsets)
        if cache_key not in self._step_cache:
            rows = [(LANE_PART, np.int32)]
            rows += [(k, np.int32 if "|" in k else np.float32)
                     for k in col_keys]
            rows.append((LANE_REL, np.int32))
            if offsets:
                rows.append((LANE_OFF, np.int32))
            self._step_cache[cache_key] = LaneTable(rows)
        return self._step_cache[cache_key]

    def _pad_lanes(self, table: LaneTable, part_idx, prepared, rel, ev,
                   starts=None) -> np.ndarray:
        """Host lanes of the events ``ev`` as ``table``'s one buffer,
        padded to a power of two (at least 16, bounding jit
        recompilation): partition rows (padding points at the scratch
        row, which is what says a lane is padding), device columns,
        relative ms.  ``starts``: the leading entries of the offsets
        row, whose every further entry is the number of events."""
        b = len(ev)
        bp = max(1 << (b - 1).bit_length(), 16)
        buf = table.pack(
            {**prepared, LANE_PART: part_idx, LANE_REL: rel}, ev, bp,
            pad={LANE_PART: self.n_partitions, LANE_OFF: b})
        if starts is not None:
            buf[-1, :len(starts)] = starts
        return buf

    def _unpack_lanes(self, table: LaneTable, buf):
        """Traced: ``table``'s packed buffer back into a program's
        ``(part_idx, cols, ts, off)``; ``off`` None where the table has
        no offsets row."""
        cols = table.unpack(buf)
        part, ts = cols.pop(LANE_PART), cols.pop(LANE_REL)
        off = cols.pop(LANE_OFF, None)
        return part, cols, ts, off

    def assemble_out(self, out_f: np.ndarray, out_i: np.ndarray,
                     rows: np.ndarray, lanes: np.ndarray) -> np.ndarray:
        """Match output rows from the device banks: float lanes stay
        float32; integer lanes re-join their hi/lo pair into exact
        int64.  All-float engines return a float32 [m, O] matrix (the
        historical shape); engines with integer outputs return an
        object-dtype matrix carrying exact per-column values."""
        if not any(self.out_int):
            return out_f[rows, lanes]
        m = len(rows)
        res = np.empty((m, len(self.out_spec)), dtype=object)
        ii = 0
        for oi, is_int in enumerate(self.out_int):
            if is_int:
                hi = out_i[rows, lanes, 2 * ii]
                lo = out_i[rows, lanes, 2 * ii + 1]
                res[:, oi] = _i64_join(hi, lo)
                ii += 1
            else:
                res[:, oi] = out_f[rows, lanes, oi].astype(np.float64)
        return res

    @property
    def output_names(self) -> List[str]:
        return [name for name, _ in self.out_spec]

    @property
    def default_stream(self) -> str:
        """Junction key of the pattern's first source stream (includes
        the '#'/'!' prefix for inner/fault streams — make_step matches
        on spec.stream_key, not the bare definition id)."""
        for node in self.nodes:
            for spec in node.specs:
                return spec.stream_key
        raise SiddhiAppCreationError("pattern has no source streams")

    @property
    def stream_keys(self) -> List[str]:
        keys = []
        for node in self.nodes:
            for spec in node.specs:
                if spec.stream_key not in keys:
                    keys.append(spec.stream_key)
        return keys

    def stream_attrs(self, stream_key: str) -> List[str]:
        """Column keys the step expects for events of one stream."""
        for node in self.nodes:
            for spec in node.specs:
                if spec.stream_key == stream_key:
                    return list(spec.stream_def.attribute_names)
        raise SiddhiAppCreationError(f"stream '{stream_key}' not in pattern")

    def numeric_stream_attrs(self, stream_key: str) -> List[str]:
        """Numeric attribute names of one stream (strings stay host-side
        as interned partition keys)."""
        return [a.name for a in self._stream_def(stream_key).attributes
                if a.type.is_numeric]

    def _stream_def(self, stream_key: str):
        for node in self.nodes:
            for spec in node.specs:
                if spec.stream_key == stream_key:
                    return spec.stream_def
        raise SiddhiAppCreationError(f"stream '{stream_key}' not in pattern")

    def device_col_keys(self, stream_key: str) -> List[str]:
        """Exact device col-dict keys the step expects: float attrs ride
        one float32 lane, integer attrs ride an ``|hi``/``|lo`` int32
        pair — the fixed pytree structure of shard_map in_specs.  Only
        the attributes the automaton reads (:meth:`read_attrs`): a
        column nothing looks at costs a transfer of its own in every
        put."""
        keys: List[str] = []
        read = self.read_attrs(stream_key)
        for a in self._stream_def(stream_key).attributes:
            if a.name not in read:
                continue
            if a.type in _INT_TYPES:
                keys.extend((f"{a.name}|hi", f"{a.name}|lo"))
            else:
                keys.append(a.name)
        return keys

    def read_attrs(self, stream_key: str) -> frozenset:
        """Numeric attributes of one stream that some node filter looks
        up, some capture stores or the select reads from the completing
        event.  Found by evaluating the compiled filters once over an
        ``env`` that records its lookups."""
        cache_key = (stream_key, "read_attrs")
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        numeric = [a for a in self._stream_def(stream_key).attributes
                   if a.type.is_numeric]
        looked_up = set()
        env = {TS_KEY: np.int32(1), N_KEY: 1}
        for a in numeric:
            if a.type in _INT_TYPES:
                env[f"__cand.{a.name}|hi"] = np.int32(1)
                env[f"__cand.{a.name}|lo"] = np.int32(1)
            else:
                env["__cand." + a.name] = np.float32(1)
        for slot in self.alloc.slots.values():
            if slot.integer:
                env[f"__ireg.{slot.index}|hi"] = np.int32(1)
                env[f"__ireg.{slot.index}|lo"] = np.int32(1)
            else:
                env[f"__reg.{slot.index}"] = np.float32(1)
        read = set()
        try:
            for s, node in enumerate(self.nodes):
                for si, spec in enumerate(node.specs):
                    if spec.stream_key != stream_key:
                        continue
                    f = self.node_filters[s][si]
                    if f is not None:
                        f.fn(RecordingEnv(env, looked_up))
                    read |= {slot.attr for slot in self.node_writes[s]
                             if slot.ref == spec.ref}
            read |= {k[len("__cand."):].split("|")[0] for k in looked_up
                     if k.startswith("__cand.")}
            read |= {src[1] for _name, src in self.out_spec
                     if isinstance(src, tuple)}
            read &= {a.name for a in numeric}
        except Exception:   # an expression that wants arrays: keep all
            read = {a.name for a in numeric}
        self._step_cache[cache_key] = frozenset(read)
        return self._step_cache[cache_key]

    def prepare_cols(self, stream_key: str,
                     cols: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Host numpy columns (native dtypes) -> device lane columns:
        float attrs cast to float32, integer attrs split into the
        bias-signed hi/lo int32 pair (bit-exact at any magnitude).
        Attributes the automaton never reads are left on the host."""
        out: Dict[str, np.ndarray] = {}
        read = self.read_attrs(stream_key)
        for a in self._stream_def(stream_key).attributes:
            v = cols.get(a.name)
            if v is None or a.name not in read:
                continue
            v = np.asarray(v)
            if a.type in _INT_TYPES:
                v64 = v.astype(np.int64)
                out[f"{a.name}|hi"] = (v64 >> 32).astype(np.int32)
                out[f"{a.name}|lo"] = (
                    (v64 & 0xFFFFFFFF) - 2**31).astype(np.int32)
            else:
                out[a.name] = v.astype(np.float32)
        return out


class DeferredDenseEmit:
    """Device-resident match outputs of one dense batch, pending drain.

    Each chunk is one dispatched program whose count gate fired (the
    unsharded engine: the batch's first round, and all its later rounds
    together; the sharded engine: one collision round each): the
    ``emit``/``f``/``i``/``anchor`` arrays are still jit outputs on
    device; ``sel`` maps padded device rows back to the chunk's events
    (a ``slice`` on the unsharded engine, a routed-slot index array on
    the sharded one) and ``ridx`` maps the chunk's rows to batch rows.
    ``device_arrays()`` + ``materialize()`` is the pending-emit queue
    contract (core/emit_queue.py): materialize receives the fetched host
    arrays in ``device_arrays()`` order and reproduces exactly what the
    synchronous path returns.
    """

    __slots__ = ("engine", "chunks", "_total")

    def __init__(self, engine):
        self.engine = engine
        self.chunks: List[dict] = []
        self._total: Optional[int] = None

    def probe(self):
        """Device scalar marking step completion (ingest-stage overlap
        evidence); None when no round dispatched."""
        return self.chunks[-1]["count"] if self.chunks else None

    @waits_on_device
    def resolve(self) -> int:
        """Fetch the deferred per-round count gates (scalars only) and
        prune rounds that matched nothing, so their column banks are
        never transferred.  Idempotent; returns total match count."""
        if self._total is not None:
            return self._total
        if self.chunks:
            import jax

            counts = jax.device_get([ch["count"] for ch in self.chunks])
        else:
            counts = []
        self.chunks = [ch for ch, c in zip(self.chunks, counts) if int(c)]
        self._total = int(sum(int(c) for c in counts))
        return self._total

    def gates(self) -> List[Tuple]:
        """``(count, arrays)`` of every round the batch still holds, in
        ``device_arrays()`` order: before ``resolve()`` every dispatched
        round, which is what the pipeline starts for the host at
        dispatch (core/device_pipeline.py)."""
        return [(ch["count"], (ch["emit"], ch["f"], ch["i"], ch["anchor"]))
                for ch in self.chunks]

    def device_arrays(self) -> List:
        return [a for _count, arrays in self.gates() for a in arrays]

    def materialize(self, host_arrays) -> Tuple[np.ndarray, np.ndarray]:
        eng = self.engine
        ev_parts: List[np.ndarray] = []
        out_parts: List[np.ndarray] = []
        key_parts: List[np.ndarray] = []  # (ev, anchor, lane) sort keys
        for ci, ch in enumerate(self.chunks):
            emit_h, f_h, i_h, anchor_h = host_arrays[4 * ci:4 * ci + 4]
            sel = ch["sel"]
            emit_np = np.asarray(emit_h)[sel]  # [b, 2I]
            if not emit_np.any():
                continue  # count gate can overcount padded lanes: skip
            out_f = np.asarray(f_h)[sel]
            out_i = np.asarray(i_h)[sel]
            anchor_np = np.asarray(anchor_h)[sel]
            rows, lanes = np.nonzero(emit_np)
            ridx = ch["ridx"]
            ev_parts.append(ridx[rows])
            out_parts.append(eng.assemble_out(out_f, out_i, rows, lanes))
            key_parts.append(np.stack(
                [ridx[rows], anchor_np[rows, lanes], lanes], axis=1))
        return flatten_match_parts(
            ev_parts, out_parts, key_parts, max(len(eng.out_spec), 1))


def flatten_match_parts(ev_parts, out_parts, key_parts, n_out: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Concatenate per-round match fragments and order them by
    (event index, arming anchor, lane) — the single definition of the
    match-ordering contract, shared by the unsharded and sharded
    wrappers."""
    if not ev_parts:
        return (np.empty(0, dtype=np.int64),
                np.empty((0, n_out), dtype=np.float32))
    ev = np.concatenate(ev_parts)
    out = np.concatenate(out_parts)
    keys = np.concatenate(key_parts)
    order = np.lexsort((keys[:, 2], keys[:, 1], keys[:, 0]))
    return ev[order].astype(np.int64), out[order]


class RoundPlan(NamedTuple):
    """Order in which a batch's events reach the device: ``lanes`` holds
    the batch-row index of every event, ``off[r]:off[r + 1]`` are the
    lanes of round ``r``."""

    lanes: np.ndarray  # int64 [n]
    off: np.ndarray    # int64 [n_rounds + 1]

    @property
    def n_rounds(self) -> int:
        """The longest run of one partition in the batch."""
        return len(self.off) - 1

    def round(self, r: int) -> np.ndarray:
        return self.lanes[self.off[r]:self.off[r + 1]]


def round_plan(part_idx: np.ndarray,
               first: Optional[np.ndarray] = None) -> RoundPlan:
    """Split a batch into rounds in which each partition appears at
    most once, preserving per-partition order: round ``r`` holds every
    partition's ``r``-th event of the batch.

    Within a round the partitions stand in one fixed order: those with
    more events in the batch first, ties by first arrival, and the
    partitions that appear once behind them in arrival order.  So the
    partitions of round ``r`` are a prefix of those of round ``r - 1``:
    position ``j`` of every round is the same partition, which is what
    lets the device keep the last rounds' few rows resident
    (:meth:`DensePatternEngine.make_rounds`).

    The partitions are rows: a partition's first arrival is found by
    one write and one read of ``first``, an ``int32`` vector over the
    row space (an engine keeps its own, :meth:`DensePatternEngine.
    plan_rounds`; without one it is made here).  It need not be clean:
    only entries this batch has written are read.  Only the events past
    their partition's first are sorted, as packed words by the plain
    sort; a batch in which no partition repeats is done after the read."""
    part_idx = np.asarray(part_idx)
    n = len(part_idx)
    if n == 0:
        return RoundPlan(np.empty(0, dtype=np.int64),
                         np.zeros(1, dtype=np.int64))
    idx = part_idx.astype(np.intp)      # what an index is cast to anyway
    if first is None:
        first = np.empty(int(idx.max()) + 1, dtype=np.int32)
    arrival = np.arange(n, dtype=np.int32)
    # written last to first, a partition's first arrival is written last
    # and stays
    first[idx[::-1]] = arrival[::-1]
    lead = first[idx]       # the first arrival of each event's partition
    while (lead > arrival).any():
        # numpy does not promise which write of a repeated index stays:
        # an event that comes before its partition's entry takes it
        # (tests/test_dense_skew.py holds that this never runs)
        early = np.flatnonzero(lead > arrival)
        first[idx[early[::-1]]] = early[::-1]
        lead = first[idx]
    repeated = lead != arrival          # events past their partition's first
    later = np.flatnonzero(repeated)
    m = len(later)
    if m == 0:
        return RoundPlan(np.arange(n, dtype=np.int64),
                         np.asarray([0, n], dtype=np.int64))
    # (first arrival, arrival) packed into one word: the keys are
    # distinct, so the plain sort groups the later events by partition,
    # each group in arrival order and the groups by first arrival
    bits = (n - 1).bit_length()
    key = lead[later].astype(np.int64)
    key <<= bits
    key |= later
    key.sort()
    later = key & ((1 << bits) - 1)
    key >>= bits                        # the first arrival again
    is_new = np.empty(m, dtype=bool)
    is_new[0] = True
    np.not_equal(key[1:], key[:-1], out=is_new[1:])
    starts = np.flatnonzero(is_new)     # of each partition's group
    extra = np.diff(starts, append=m)   # a partition's events less one
    top = int(extra.max())              # rounds behind the first
    # the groups by (more events, first arrival): they stand by first
    # arrival, so a stable counting pass over the counts ranks them (a
    # stable argsort of uint16 is a radix pass); past its reach the
    # count and the group's place packed into one word, plainly sorted
    if top < 1 << 16:
        ranked = np.argsort((top - extra).astype(np.uint16), kind="stable")
    else:
        gbits = (len(starts) - 1).bit_length()
        ranked = ((top - extra) << gbits) | np.arange(len(starts))
        ranked.sort()
        ranked &= (1 << gbits) - 1
    starts = starts[ranked]
    # round t behind the first holds the widths[t] first groups of that
    # order, each one's t-th later event
    widths = np.cumsum(np.bincount(extra)[:0:-1])[::-1]
    off = np.zeros(top + 2, dtype=np.int64)
    np.cumsum(widths, out=off[2:])
    src = np.arange(m)
    src -= np.repeat(off[1:-1], widths)
    src = starts[src]
    src += np.repeat(np.arange(top), widths)
    firsts = key[starts]                 # the repeating partitions' firsts
    repeated[firsts] = True
    once = np.flatnonzero(~repeated)    # arrival order
    off[1:] += len(firsts) + len(once)
    return RoundPlan(np.concatenate([firsts, once, later[src]]), off)


# ---------------------------------------------------------------------------
# High-level compile API
# ---------------------------------------------------------------------------


def compile_pattern(
    app_str: str,
    query_name: Optional[str] = None,
    n_partitions: int = 1024,
    mesh=None,
    every_start: Optional[bool] = None,
    n_instances: int = 4,
):
    """Compile a SiddhiQL pattern query into a DensePatternEngine.

    The partition axis is the implicit per-key replication of the query
    (the reference's `partition with (key of Stream)` over pattern
    queries); callers route events to partition ids (interned keys).
    """
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
    st = query.input_stream
    if not isinstance(st, StateInputStream):
        raise SiddhiAppCreationError("compile_pattern needs a pattern query")
    is_sequence = st.type == StateInputStream.SEQUENCE

    def resolve(s):
        d = app.stream_definitions.get(s.stream_id)
        if d is None:
            raise SiddhiAppCreationError(f"stream '{s.stream_id}' is not defined")
        return d

    builder = NFABuilder(st, resolve)
    nodes = builder.build()
    if every_start is None:
        # group-scoped `every` is rejected by DensePatternEngine.__init__
        every_start = any(n.rearm_to is not None for n in nodes)

    select_vars = []
    select_names = []
    if query.selector.selection:
        for oa in query.selector.selection:
            if not isinstance(oa.expression, Variable) or oa.expression.stream_id is None:
                raise SiddhiAppCreationError(
                    "dense NFA select items must be event references (e1.attr)"
                )
            select_vars.append(oa.expression)
            select_names.append(oa.name)

    return DensePatternEngine(
        nodes=nodes,
        ref_defs=builder.ref_defs,
        stream_to_ref=builder.stream_to_ref,
        within_ms=st.within_ms,
        n_partitions=n_partitions,
        select_vars=select_vars,
        select_names=select_names,
        every_start=every_start,
        mesh=mesh,
        is_sequence=is_sequence,
        n_instances=n_instances,
    )
