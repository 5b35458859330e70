"""Batched hot-key associative-scan NFA: the skew router's kernel.

``ops/nfa_scan.py`` proves the algebra for ONE key: linear-chain NFA
transitions compose under max-plus matmul, so a single key's events
advance in O(log n) scan depth.  This module makes that batch-capable
for the hybrid skew router (core/hotkey_router.py): H promoted hot keys
ride a ``[H, n_pad]`` leading axis through ONE jitted
``associative_scan`` per junction cycle, while cold keys stay on the
dense partition path.

Two scans ride one ``associative_scan`` call as a pytree:

- the max-plus matrix ``M`` of nfa_scan.py carries the per-lane
  YOUNGEST pending start (liveness: does a chain complete here);
- a counting matrix ``T`` with the same support carries the NUMBER of
  pending chains per lane under ordinary matmul (componentwise
  associative with max-plus, so one scan serves both).

The count scan is what upgrades the sample engine's "one detection per
completing event" to the host engine's exact multiplicity: in the
eligible chain class (every-headed linear chain, capture-free
current-event filters, selects referencing ONLY the final node, no
``within``) same-node chains are interchangeable AND their emitted rows
are identical, so emitting ``count_before[S-1]`` copies of the
final-node row at each completing event is bit-identical to the host
engine's one-row-per-pending-chain emission.  ``within`` stays gated
OUT here (partial expiry would need per-chain starts, not a count —
the simultaneous-DFA enumeration of arXiv 1512.09228 is the planned
lift); counts are float32 and exact below 2**24 pending chains per
lane, far past the dense engine's instance-lane capacity.

Padding discipline: slots and events beyond the cycle's real work carry
an all-False filter row, which makes BOTH per-event matrices the
identity (M = diag(0) over max-plus, T = I), so padded lanes are
no-ops by construction — no masking epilogue.

State handoff (promotion/demotion) converts between a dense partition
row (``active``/``first_ts`` instance lanes, ops/dense_nfa.py
``init_state_host`` layout) and the scan's per-lane (youngest start,
count) pair: dense node ``j`` holds chains that consumed pattern
events ``1..j`` — exactly scan lane ``j``.  Promotion takes the
youngest active start and the lane population; demotion re-arms
``min(count, I)`` instance lanes (the dense capacity contract — the
excess is counted in the row's ``overflow``) at the youngest start,
which is exact for emissions because starts are unobservable in the
eligible class (no ``within``, no non-final selects).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.planner.expr import N_KEY
from siddhi_tpu.query_api import StateInputStream

from .nfa_scan import NEG, ScanPatternEngine

# counts ride float32 add/matmul lanes: exact while < 2**24
COUNT_EXACT_MAX = 1 << 24


class HotKeyScanEngine:
    """H hot-key slots of one linear chain, advanced by one jitted
    batched scan per junction cycle.

    Wraps a ``ScanPatternEngine`` for chain validation and filter
    compilation (its constructor raises ``SiddhiAppCreationError`` with
    the reason for every ineligible shape — the router's fallback
    reasons), then adds the slot axis, the counting scan and the dense
    handoff converters.  State is ``{"v": [H, S] f32, "c": [H, S] f32}``
    — youngest start (relative to ``base_ts``) and pending-chain count
    per lane; lane 0 is the constant lane (v=0, c=1).
    """

    def __init__(self, st: StateInputStream, stream_def, n_slots: int):
        if st.type == StateInputStream.SEQUENCE:
            raise SiddhiAppCreationError(
                "hotkey scan: sequence (consecutive-event) semantics — "
                "the scan keep-transition implements pattern semantics")
        if st.within_ms is not None:
            raise SiddhiAppCreationError(
                "hotkey scan: 'within' needs per-chain starts for "
                "partial expiry; the count abstraction cannot express it")
        base = ScanPatternEngine(st, stream_def)
        self.base = base
        self.jax, self.jnp = base.jax, base.jnp
        self.n_nodes = base.n_nodes
        self.stream_id = base.stream_id
        self.n_slots = int(n_slots)
        self.base_ts: Optional[int] = None
        self._step_fn = None

    # -- state ---------------------------------------------------------------

    def init_state(self) -> Dict:
        H, S = self.n_slots, self.n_nodes
        v = np.full((H, S), NEG, dtype=np.float32)
        v[:, 0] = 0.0
        c = np.zeros((H, S), dtype=np.float32)
        c[:, 0] = 1.0
        return {"v": self.jnp.asarray(v), "c": self.jnp.asarray(c)}

    def slot_init_rows(self) -> Tuple[np.ndarray, np.ndarray]:
        """Host template of one empty slot (promotion writes start from
        this, demotion resets to it)."""
        S = self.n_nodes
        v = np.full(S, NEG, dtype=np.float32)
        v[0] = 0.0
        c = np.zeros(S, dtype=np.float32)
        c[0] = 1.0
        return v, c

    # -- dense handoff -------------------------------------------------------

    def dense_row_to_slot(self, active: np.ndarray, first_ts: np.ndarray,
                          dense_base: int, scan_base: int
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """One dense partition row (host ``active`` [S, I] bool,
        ``first_ts`` [S, I] int32 rel ``dense_base``) -> scan slot rows
        (v, c) relative to ``scan_base``.  Dense node j == scan lane j;
        every-start engines keep node 0 as the implicit virgin, so only
        lanes 1..S-1 carry chains."""
        v, c = self.slot_init_rows()
        S = self.n_nodes
        for j in range(1, S):
            lanes = active[j]
            nj = int(lanes.sum())
            if nj:
                youngest = int(first_ts[j][lanes].max()) + int(dense_base)
                v[j] = np.float32(youngest - scan_base)
                c[j] = np.float32(nj)
        return v, c

    def slot_to_dense_row(self, v: np.ndarray, c: np.ndarray,
                          scan_base: int, dense_base: int, n_instances: int
                          ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Scan slot rows -> one dense partition row: re-arm
        ``min(count, I)`` instance lanes per node at the youngest start;
        the excess is returned as the row's overflow increment (the
        dense capacity contract for dropped pending chains)."""
        S, I = self.n_nodes, int(n_instances)
        active = np.zeros((S, I), dtype=bool)
        first_ts = np.zeros((S, I), dtype=np.int32)
        dropped = 0
        for j in range(1, S):
            if v[j] <= NEG / 2:
                continue
            cnt = int(round(float(c[j])))
            if cnt <= 0:
                continue
            youngest = int(round(float(v[j]))) + int(scan_base)
            # rel-0 means "unset" in the dense layout; a start exactly at
            # the dense base clamps forward 1ms, which cannot change any
            # emission (starts are unobservable in the eligible class)
            rel = max(youngest - int(dense_base), 1)
            k = min(cnt, I)
            active[j, :k] = True
            first_ts[j, :k] = np.int32(rel)
            dropped += cnt - k
        return active, first_ts, dropped

    # -- jitted batched step -------------------------------------------------

    def _filter_matrix(self, env, H, n):
        """[H, n, S+1] boolean; col j = f_j (col 0 placeholder)."""
        jnp = self.jnp
        cols = [jnp.ones((H, n), dtype=bool)]
        for fs in self.base.filters:
            m = jnp.ones((H, n), dtype=bool)
            for c in fs:
                m = m & jnp.broadcast_to(
                    jnp.asarray(c.fn(env)).astype(bool), (H, n))
            cols.append(m)
        return jnp.stack(cols, axis=2)

    def make_step(self):
        """Jitted (state, cols{attr: [H,n]}, ts_rel [H,n] f32,
        valid [H,n] bool, delta f32) ->
        (state', emit [H,n] f32 row counts, n_rows i32 scalar).

        ``delta`` shifts carried live starts for the cycle's base
        rebase ON DEVICE — state never round-trips to host for
        re-anchoring (the sample engine's host-side shift would be a
        per-cycle sync)."""
        if self._step_fn is not None:
            return self._step_fn
        jax, jnp = self.jax, self.jnp
        S = self.n_nodes

        def combine(a, b):
            Ma, Ta = a
            Mb, Tb = b
            # apply a (earlier) then b: max-plus b ⊗ a; counts Tb @ Ta.
            # HIGHEST keeps the count matmul in true f32 on TPU (bf16
            # MXU inputs would corrupt counts past 256)
            return (
                jnp.max(Mb[..., :, :, None] + Ma[..., None, :, :], axis=-2),
                jnp.matmul(Tb, Ta, precision=jax.lax.Precision.HIGHEST),
            )

        def step(state, cols, ts_rel, valid, delta):
            v, c = state["v"], state["c"]
            live = v > NEG / 2
            live = live.at[:, 0].set(False)  # constant lane stays 0
            v = jnp.where(live, v - delta, v)
            H, n = ts_rel.shape
            env = dict(cols)
            env[N_KEY] = n
            F = self._filter_matrix(env, H, n) & valid[:, :, None]
            M = jnp.full((H, n, S, S), NEG, dtype=jnp.float32)
            M = M.at[:, :, 0, 0].set(0.0)
            M = M.at[:, :, 1, 0].set(jnp.where(F[:, :, 1], ts_rel, NEG))
            T = jnp.zeros((H, n, S, S), dtype=jnp.float32)
            T = T.at[:, :, 0, 0].set(1.0)
            T = T.at[:, :, 1, 0].set(F[:, :, 1].astype(jnp.float32))
            for j in range(1, S):
                adv = F[:, :, j + 1]
                M = M.at[:, :, j, j].set(jnp.where(adv, NEG, 0.0))
                T = T.at[:, :, j, j].set((~adv).astype(jnp.float32))
                if j + 1 < S:
                    M = M.at[:, :, j + 1, j].set(jnp.where(adv, 0.0, NEG))
                    T = T.at[:, :, j + 1, j].set(adv.astype(jnp.float32))
            PM, PT = jax.lax.associative_scan(combine, (M, T), axis=1)
            after_v = jnp.max(PM + v[:, None, None, :], axis=-1)
            after_c = jnp.einsum(
                "hnij,hj->hni", PT, c,
                precision=jax.lax.Precision.HIGHEST)
            before_v = jnp.concatenate(
                [v[:, None, :], after_v[:, :-1, :]], axis=1)
            before_c = jnp.concatenate(
                [c[:, None, :], after_c[:, :-1, :]], axis=1)
            start = before_v[:, :, S - 1]
            matched = F[:, :, S] & (start > NEG / 2)
            emit = jnp.where(matched, before_c[:, :, S - 1], 0.0)
            n_rows = jnp.sum(emit).astype(jnp.int32)
            return ({"v": after_v[:, -1, :], "c": after_c[:, -1, :]},
                    emit, n_rows)

        self._step_fn = jax.jit(step)
        return self._step_fn

    # -- host packing helpers ------------------------------------------------

    def rebase(self, cycle_min_ts: int) -> float:
        """Advance ``base_ts`` to just below the cycle's earliest event;
        returns the f32 delta the jitted step must shift carried live
        starts by (0.0 on the first cycle or when time stands still)."""
        new_base = int(cycle_min_ts) - 1
        if self.base_ts is None:
            self.base_ts = new_base
            return 0.0
        if new_base > self.base_ts:
            delta = float(new_base - self.base_ts)
            self.base_ts = new_base
            return delta
        return 0.0

    def pack_cycle(self, slot_pos, cols: Dict[str, np.ndarray],
                   ts: np.ndarray) -> Tuple[Dict[str, np.ndarray], dict]:
        """Pack per-slot event subsets into the fixed ``[H, n_pad]``
        layout.  ``slot_pos``: {slot: positions into the junction batch
        (ascending)}.  Returns (host arrays for one staged_put, meta for
        the deferred emit).  ``n_pad`` is pow2-bucketed so the jitted
        step sees a bounded shape variety."""
        H = self.n_slots
        n_max = max(len(p) for p in slot_pos.values())
        n_pad = max(1 << max(n_max - 1, 1).bit_length(), 16)
        min_ts = min(int(ts[p[0]]) for p in slot_pos.values())
        delta = self.rebase(min_ts)
        ts_pad = np.full((H, n_pad), min_ts, dtype=np.int64)
        valid = np.zeros((H, n_pad), dtype=bool)
        packed: Dict[str, np.ndarray] = {}
        lane_dtype = self.base._lane_dtype
        for a, dt in lane_dtype.items():
            if a in cols:
                packed[a] = np.zeros((H, n_pad), dtype=dt)
        for slot, pos in slot_pos.items():
            k = len(pos)
            ts_pad[slot, :k] = ts[pos]
            valid[slot, :k] = True
            for a in packed:
                packed[a][slot, :k] = cols[a][pos].astype(
                    lane_dtype[a], copy=False)
        rel = (ts_pad - self.base_ts).astype(np.float32)
        put = dict(packed)
        put["__ts_rel"] = rel
        put["__valid"] = valid
        put["__delta"] = np.full((), delta, dtype=np.float32)
        meta = {"slot_pos": slot_pos, "n_pad": n_pad}
        return put, meta

    def dispatch(self, state, put_dev: Dict):
        """Run the jitted step on device-resident packed arrays (the
        router stages them through ``staged_put``).  Returns
        (state', emit_dev [H, n_pad], n_rows_dev scalar)."""
        ts_rel = put_dev.pop("__ts_rel")
        valid = put_dev.pop("__valid")
        delta = put_dev.pop("__delta")
        return self.make_step()(state, put_dev, ts_rel, valid, delta)
