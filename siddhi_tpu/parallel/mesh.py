"""Mesh construction, sharded pattern-engine wrapper, event routing."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.core.ingest_stage import staged_put
from siddhi_tpu.observability.trace import (
    SCOPE_SHARD_COUNT_PSUM,
    STAGE_CONVERT,
    STAGE_DISPATCH,
    STAGE_PLAN,
    STAGE_ROUTE,
    span,
)


def distributed_initialize(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None):
    """Multi-host bring-up: one JAX process per host, ICI within a slice,
    DCN across slices (the reference has no analog — its clustering is
    an external k8s operator).  Call once per process before any other
    JAX call.

    On CPU platforms (tests, local multi-process validation) the gloo
    cross-process collective backend is selected automatically — without
    it, collectives over a multi-process CPU mesh fail at dispatch.
    Exercised by tests/test_distributed.py with a real 2-process mesh.
    """
    import os

    import jax

    # CPU detection must not touch a backend (distributed.initialize
    # must run first), so check the two explicit selection channels;
    # a no-accelerator implicit CPU fallback isn't detectable here —
    # set JAX_PLATFORMS=cpu explicitly in that case
    plat = (os.environ.get("JAX_PLATFORMS", "")
            or str(getattr(jax.config, "jax_platforms", None) or ""))
    if plat.startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(n_devices: Optional[int] = None, axis_name: str = "p",
              devices=None):
    """1-D device mesh over the partition axis, built from the default
    platform's devices and nothing else: a platform with fewer than
    ``n_devices`` raises — it never borrows devices of another platform,
    so a mesh asked for on a one-chip machine cannot land on host CPUs.
    CPU runs (tests, ``dryrun_multichip``) pin ``JAX_PLATFORMS=cpu`` and
    the virtual device count before any backend starts."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise SiddhiAppCreationError(
                f"need {n_devices} devices, platform "
                f"'{devices[0].platform}' has {len(devices)} (for CPU "
                "testing set JAX_PLATFORMS=cpu and JAX_NUM_CPU_DEVICES "
                "before JAX starts)")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), axis_names=(axis_name,))


def _pow2(n: int, floor: int = 16) -> int:
    return max(1 << (max(n, 1) - 1).bit_length(), floor)


def _shard_buckets(n_shards: int, parts_per_shard: int, part: np.ndarray,
                   batch_per_shard: Optional[int]
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Bucket a round's events by owning shard: ``(order, counts,
    starts, B)``.  ``order`` lists the events shard by shard, each
    shard's in arrival order; shard ``s`` holds ``order[starts[s] :
    starts[s] + counts[s]]``; ``B`` is the padded bucket length.

    The rank is a counting sort: the owner cast to the smallest unsigned
    integer that holds ``n_shards`` (uint8 to 256 shards, uint16 above),
    for which numpy's stable argsort is a radix pass and not the merge
    sort an int64 owner gets."""
    owner = part // parts_per_shard
    if len(part) and (owner.max() >= n_shards or owner.min() < 0):
        raise SiddhiAppCreationError(
            f"partition id out of range for {n_shards} x {parts_per_shard} layout")
    counts = np.bincount(owner, minlength=n_shards)
    max_count = int(counts.max()) if len(part) else 0
    B = int(batch_per_shard) if batch_per_shard is not None else _pow2(max_count)
    if max_count > B:
        raise SiddhiAppCreationError(
            f"shard bucket overflow: {max_count} events for one shard "
            f"> batch_per_shard={B}")
    order = np.argsort(
        owner.astype(np.uint8 if n_shards <= 256 else np.uint16),
        kind="stable")
    return order, counts, np.cumsum(counts) - counts, B


def _slots(order: np.ndarray, counts: np.ndarray, starts: np.ndarray,
           B: int) -> np.ndarray:
    """``pos[i]``: the padded slot of input event ``i`` (shard * B +
    rank within the shard's bucket)."""
    pos = np.empty(len(order), dtype=np.int64)
    pos[order] = (np.arange(len(order))
                  + np.repeat(np.arange(len(counts)) * B - starts, counts))
    return pos


def route_to_shards(n_shards: int, parts_per_shard: int,
                    part: np.ndarray, cols: Dict[str, np.ndarray],
                    ts: np.ndarray,
                    batch_per_shard: Optional[int] = None
                    ) -> Tuple[np.ndarray, Dict[str, np.ndarray], np.ndarray,
                               np.ndarray, np.ndarray]:
    """Host-side event routing: bucket a batch by owning shard
    (``global_part // parts_per_shard``, shard-major layout), rewrite
    partition ids to shard-local indices, and pad every shard's bucket
    to the same pow-2 length (bounding jit recompilation, as the
    unsharded wrapper does) so the result concatenates into one array
    whose equal slices are the per-device inputs of a shard_map step.

    Padded rows carry local index ``parts_per_shard`` — each shard's
    dedicated scratch row — so their scatter-back can never collide
    with a real partition's update.

    Returns ``(local_part, cols, ts, valid, pos)`` where ``pos[i]`` is
    the padded-slot index of input event ``i`` (for mapping per-event
    emit/out rows back to inputs).  Callers must not route two events of
    the same partition in one call (gather/scatter would race); use
    :meth:`ShardedPatternEngine.process`, which splits collision rounds.
    """
    part = np.asarray(part)
    order, counts, starts, B = _shard_buckets(
        n_shards, parts_per_shard, part, batch_per_shard)
    pos = _slots(order, counts, starts, B)
    n = n_shards * B
    # scratch slot: local index parts_per_shard (one reserved row/shard)
    local_part = np.full(n, parts_per_shard, dtype=np.int32)
    out_ts = np.zeros(n, dtype=np.asarray(ts).dtype)
    valid = np.zeros(n, dtype=bool)
    out_cols = {k: np.zeros(n, dtype=np.asarray(v).dtype) for k, v in cols.items()}
    local_part[pos] = (part % parts_per_shard).astype(np.int32)
    out_ts[pos] = np.asarray(ts)
    valid[pos] = True
    for k, v in cols.items():
        out_cols[k][pos] = np.asarray(v)
    return local_part, out_cols, out_ts, valid, pos


#: Rows of the packed round buffer ahead of the column rows.
_ROW_PART, _ROW_TS, _ROW_COLS = 0, 1, 2


def _lane_dtype(col_key: str):
    """A device column's lane: an integer attribute's ``|hi`` / ``|lo``
    word is int32, every other column float32 (``prepare_cols``)."""
    return np.int32 if "|" in col_key else np.float32


def pack_round(n_shards: int, parts_per_shard: int, part: np.ndarray,
               cols: Dict[str, np.ndarray], ts: np.ndarray,
               col_keys: List[str],
               batch_per_shard: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    """One routed round as ONE host buffer, ``int32 [2 + len(col_keys),
    n_shards * B]``: a row a lane, shard ``s`` in columns ``s * B :
    (s + 1) * B``, the slots ``route_to_shards`` gives.  Row 0: the
    shard-local partition row (padding: ``parts_per_shard``, the
    scratch row, so a lane is valid exactly where it is anything else);
    row 1: the relative timestamp; then ``col_keys`` in order, a float32
    column through a float32 view of its row, so what crosses is its bit
    pattern.  Each row is filled by gathers into the shards' contiguous
    slices.  Returns ``(buf, pos)``."""
    part = np.asarray(part)
    order, counts, starts, B = _shard_buckets(
        n_shards, parts_per_shard, part, batch_per_shard)
    buf = np.zeros((_ROW_COLS + len(col_keys), n_shards * B), dtype=np.int32)
    lanes = [(buf[_ROW_TS], np.asarray(ts))]
    for row, k in zip(buf[_ROW_COLS:], col_keys):
        lane = _lane_dtype(k)
        lanes.append((row.view(lane), np.asarray(cols[k]).astype(
            lane, copy=False)))
    local = buf[_ROW_PART]
    for s in range(n_shards):
        lo, c = int(starts[s]), int(counts[s])
        at = s * B
        idx = order[lo:lo + c]
        local[at:at + c] = part[idx] - s * parts_per_shard
        local[at + c:at + B] = parts_per_shard
        for row, lane in lanes:
            row[at:at + c] = lane[idx]
    return buf, _slots(order, counts, starts, B)


class ShardedPatternEngine:
    """A dense NFA engine sharded over a mesh's partition axis.

    Wraps ``siddhi_tpu.ops.dense_nfa.compile_pattern``'s engine: state
    rows are laid out shard-major with one scratch row per shard
    (absorbing padded lanes), device_put with a ``P('p', ...)``
    sharding, and the step runs under ``shard_map`` (shard-local state
    access, psum'd global match count).

    Use :meth:`process` for the safe high-level path (collision-round
    splitting, relative-timestamp normalization, per-event output
    mapping); ``route``/``step`` are the raw building blocks whose
    callers must uphold those contracts themselves.
    """

    def __init__(self, engine, mesh, axis_name: str = "p",
                 stream_key: Optional[str] = None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.engine = engine
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_shards = int(np.prod(mesh.devices.shape))
        if engine.n_partitions % self.n_shards:
            raise SiddhiAppCreationError(
                f"{engine.n_partitions} partitions not divisible by "
                f"{self.n_shards} shards")
        # usable partitions per shard; +1 scratch row per shard
        self.parts_per_shard = engine.n_partitions // self.n_shards
        self.rows_per_shard = self.parts_per_shard + 1

        self.stream_key = stream_key or engine.default_stream
        self.col_keys = engine.device_col_keys(self.stream_key)
        # a sharded state's rows stay flat, ``[N, W]``, under XLA's
        # scatter (``ops/dense_layout.py`` ``row_shape``)
        engine.shard_rows()
        step = engine.make_step(self.stream_key, jit=False)
        jnp = engine.jnp
        a = axis_name

        # row-sharded on this wrapper's axis name; trailing
        # node/instance/register dims replicated.  Ranks come from the
        # engine's own pspecs (len == ndim) — no throwaway host
        # allocation of the full state just to read shapes.
        self.state_specs = {
            k: P(a, *([None] * (len(spec) - 1)))
            for k, spec in engine.state_pspecs().items()
        }
        specs = self.state_specs

        pps = self.parts_per_shard
        col_keys = self.col_keys

        def sharded_step(state, buf):
            # a shard's slice of the round's one buffer (pack_round),
            # taken apart by static slices
            part = buf[_ROW_PART]
            cols = {
                k: (row if _lane_dtype(k) == np.int32
                    else jax.lax.bitcast_convert_type(row, jnp.float32))
                for k, row in zip(col_keys, buf[_ROW_COLS:])}
            new_state, emit, outs, anchor, local = step(
                state, part, cols, buf[_ROW_TS], part != pps)
            with jax.named_scope(SCOPE_SHARD_COUNT_PSUM):
                total = jax.lax.psum(local, axis_name=a)
            return new_state, emit, outs, anchor, total

        # donate the state pytree: at 1M+ partitions the rows dominate
        # HBM and double-buffering them would halve capacity
        self._step = jax.jit(jax.shard_map(
            sharded_step,
            mesh=mesh,
            in_specs=(specs, P(None, a)),
            out_specs=(specs, P(a, None),
                       {"f": P(a, None, None), "i": P(a, None, None)},
                       P(a, None), P()),
        ), donate_argnums=(0,))
        self._round_sharding = NamedSharding(mesh, P(None, a))
        self._NamedSharding = NamedSharding
        self._jax = jax

    # -- state ---------------------------------------------------------------

    def _put(self, x, spec):
        return self._jax.device_put(
            x, self._NamedSharding(self.mesh, spec))

    def init_state(self):
        """Zero state with shard-major layout: each shard owns
        ``parts_per_shard`` partition rows plus one trailing scratch
        row (same per-row init values as the unsharded engine).

        Built from the engine's NUMPY init (its state layout) — calling
        the device init here would allocate on the default backend,
        which may be a TPU the caller never intends to touch (the
        round-2 dryrun crash)."""
        host = self.engine.layout.init_physical(
            self.n_shards * self.rows_per_shard)
        return {k: self._put(v, self.state_specs[k])
                for k, v in host.items()}

    # -- stepping ------------------------------------------------------------

    def route(self, part, cols, ts, batch_per_shard=None):
        """Host arrays -> the round's one device buffer (``pack_round``:
        routed and padded per shard, a row a lane, split over the mesh
        by columns); also returns the input->slot map.  Caller contract:
        at most one event per partition per call, timestamps already
        relative int32, cols already device-lane columns
        (engine.prepare_cols: float32 floats + int32 hi/lo pairs).
        Returns ``((buf,), pos)`` for ``step(state, *args)``."""
        with span(STAGE_ROUTE, len(part)):
            buf, pos = pack_round(
                self.n_shards, self.parts_per_shard, part, cols, ts,
                self.col_keys, batch_per_shard)
        # the round's H2D transfer: one put of one leaf behind the
        # ingest.put fault site (core/ingest_stage.py)
        return (staged_put(
            buf, self._round_sharding,
            faults=getattr(self.engine, "faults", None),
            stats=getattr(self.engine, "ingest_stats", None)),), pos

    def step(self, state, buf):
        """One sharded step over a routed round's buffer: ``(state',
        emit[B, 2I], out_vals[B, 2I, O], emit_anchor[B, 2I],
        global_matches)``.

        The input ``state`` is DONATED (its buffers are consumed, on the
        CPU backend too — snapshot it before stepping if needed; always
        rebind to the returned state)."""
        return self._step(state, buf)

    def process(self, state, part: np.ndarray, cols: Dict[str, np.ndarray],
                ts: np.ndarray):
        """Safe batch entry point mirroring DensePatternEngine.process:
        splits rounds so each partition appears at most once per step,
        normalizes timestamps, and flattens per-instance matches back to
        input order.  Returns ``(state, match_ev_idx[m], out[m, n_out],
        total_matches)`` with same-event matches ordered by arming age."""
        state, pending = self.process_deferred(state, part, cols, ts)
        total = pending.resolve() if pending is not None else 0
        if total == 0:
            from siddhi_tpu.ops.dense_nfa import flatten_match_parts

            ev, out = flatten_match_parts(
                [], [], [], max(len(self.engine.out_spec), 1))
            return state, ev, out, total
        from siddhi_tpu.core.emit_queue import fetch_coalesced

        ev, out = pending.materialize(fetch_coalesced(
            pending.device_arrays()))
        return state, ev, out, total

    def process_deferred(self, state, part: np.ndarray,
                         cols: Dict[str, np.ndarray], ts: np.ndarray):
        """Async-emit variant of :meth:`process`: every round's match
        outputs stay device-resident in a :class:`DeferredDenseEmit`
        (None only for empty input).  Nothing crosses device->host here:
        the psum'd per-round count gate stays a device scalar until
        ``pending.resolve()`` — the ingest stage (core/ingest_stage.py)
        defers that fetch past the next batch's dispatch.  Returns
        ``(state, pending_or_None)``."""
        from siddhi_tpu.ops.dense_nfa import DeferredDenseEmit

        with span(STAGE_CONVERT, len(part)):
            part = np.asarray(part)
            rel64 = self.engine.rel_ts64(np.asarray(ts, dtype=np.int64))
            state, rel64 = self.engine.maybe_re_anchor(
                state, rel64,
                to_device=lambda k, v: self._put(v, self.state_specs[k]))
            rel = rel64.astype(np.int32)
            prepared = self.engine.prepare_cols(self.stream_key, cols)
        with span(STAGE_PLAN) as sp:
            plan = self.engine.plan_rounds(part)
            if sp is not None:
                sp.count = plan.n_rounds
        pending = DeferredDenseEmit(self.engine)
        faults = getattr(self.engine, "faults", None)
        if faults is not None:
            faults.check("step.shard")
        # the rounds are stepped from the host here: each is routed to
        # the shards on its own (PERF.md, Open question 3)
        for r in range(plan.n_rounds):
            ridx = plan.round(r)
            with span(STAGE_CONVERT, len(ridx)):
                round_part = part[ridx]
                round_cols = {k: v[ridx] for k, v in prepared.items()}
                round_rel = rel[ridx]
            args, pos = self.route(round_part, round_cols, round_rel)
            with span(STAGE_DISPATCH, 1):
                state, emit, outs, anchor, round_total = self.step(
                    state, *args)
            pending.chunks.append({
                "emit": emit, "f": outs["f"], "i": outs["i"],
                "anchor": anchor, "sel": pos, "ridx": ridx,
                "count": round_total,
            })
        return state, (pending if pending.chunks else None)
