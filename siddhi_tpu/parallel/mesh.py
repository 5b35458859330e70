"""Mesh construction, sharded pattern-engine wrapper, event routing."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.core.ingest_stage import host_nbytes
from siddhi_tpu.observability.trace import (
    SCOPE_SHARD_COUNT_PSUM,
    STAGE_CONVERT,
    STAGE_DISPATCH,
    STAGE_PLAN,
    STAGE_PUT,
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
    n = n_shards * B
    # scratch slot: local index parts_per_shard (one reserved row/shard)
    local_part = np.full(n, parts_per_shard, dtype=np.int32)
    out_ts = np.zeros(n, dtype=np.asarray(ts).dtype)
    valid = np.zeros(n, dtype=bool)
    out_cols = {k: np.zeros(n, dtype=np.asarray(v).dtype) for k, v in cols.items()}
    # vectorized within-bucket rank (cumcount over stably-sorted owners)
    order = np.argsort(owner, kind="stable")
    sorted_owner = owner[order]
    starts = np.searchsorted(sorted_owner, np.arange(n_shards), side="left")
    rank_sorted = np.arange(len(part)) - starts[sorted_owner]
    pos = np.empty(len(part), dtype=np.int64)
    pos[order] = sorted_owner * B + rank_sorted
    local_part[pos] = (part % parts_per_shard).astype(np.int32)
    out_ts[pos] = np.asarray(ts)
    valid[pos] = True
    for k, v in cols.items():
        out_cols[k][pos] = np.asarray(v)
    return local_part, out_cols, out_ts, valid, pos


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

        def sharded_step(state, part, cols, ts, valid):
            new_state, emit, outs, anchor, local = step(state, part, cols,
                                                        ts, valid)
            with jax.named_scope(SCOPE_SHARD_COUNT_PSUM):
                total = jax.lax.psum(local, axis_name=a)
            return new_state, emit, outs, anchor, total

        # donate the state pytree: at 1M+ partitions the rows dominate
        # HBM and double-buffering them would halve capacity
        self._step = jax.jit(jax.shard_map(
            sharded_step,
            mesh=mesh,
            in_specs=(specs, P(a), {k: P(a) for k in self.col_keys},
                      P(a), P(a)),
            out_specs=(specs, P(a, None),
                       {"f": P(a, None, None), "i": P(a, None, None)},
                       P(a, None), P()),
        ), donate_argnums=(0,))
        self._P = P
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
        """Host arrays -> device arrays routed/padded per shard; also
        returns the input->slot map.  Caller contract: at most one event
        per partition per call, timestamps already relative int32, cols
        already device-lane columns (engine.prepare_cols: float32 floats
        + int32 hi/lo pairs)."""
        P = self._P
        a = self.axis_name
        with span(STAGE_ROUTE, len(part)):
            lp, rc, rts, valid, pos = route_to_shards(
                self.n_shards, self.parts_per_shard, part, cols, ts,
                batch_per_shard)
            rc = {k: np.asarray(v) for k, v in rc.items()}
            rts = np.asarray(rts, dtype=np.int32)
        # the round's H2D transfer: one put span over its sharded puts
        with span(STAGE_PUT) as sp:
            if sp is not None:
                sp.count = host_nbytes((lp, rc, rts, valid))
            return (
                self._put(lp, P(a)),
                {k: self._put(v, P(a)) for k, v in rc.items()},
                self._put(rts, P(a)),
                self._put(valid, P(a)),
            ), pos

    def step(self, state, part, cols, ts, valid):
        """One sharded step: ``(state', emit[B, 2I], out_vals[B, 2I, O],
        emit_anchor[B, 2I], global_matches)``.

        The input ``state`` is DONATED (its buffers are consumed, on the
        CPU backend too — snapshot it before stepping if needed; always
        rebind to the returned state)."""
        return self._step(state, part, cols, ts, valid)

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
        from siddhi_tpu.ops.dense_nfa import DeferredDenseEmit, round_plan

        with span(STAGE_CONVERT, len(part)):
            part = np.asarray(part)
            rel64 = self.engine.rel_ts64(np.asarray(ts, dtype=np.int64))
            state, rel64 = self.engine.maybe_re_anchor(
                state, rel64,
                to_device=lambda k, v: self._put(v, self.state_specs[k]))
            rel = rel64.astype(np.int32)
            prepared = self.engine.prepare_cols(self.stream_key, cols)
        with span(STAGE_PLAN) as sp:
            plan = round_plan(part)
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
