"""Device-resident stream-graph fusion: one jitted program per chain.

The product path routes every inter-query hop host-side through
`StreamJunction` — the producer materializes an EventBatch, the junction
dispatches it, the consumer re-pads and re-uploads it.  For a chain of
device-mode queries (filter → window → pattern) that is three H2D/D2H
round trips and three EventBatch builds per batch cycle, which is why
the product path trails the kernel-path bench by orders of magnitude
(ROADMAP "Whole-app fusion").

`FusedGraphEngine` composes the EXISTING per-stage step kernels
(ops/device_query.py `make_step`, ops/dense_nfa.py `make_step`) into one
jit-compiled multi-stage program: each stage's "expr" output lanes feed
the next stage's input lanes directly in HBM, passthrough outputs
forward the producer's own input lane, and a per-stage valid mask
(`v & ov`) replaces the junction's row compaction — filtered-out rows
simply stop participating, they are never compacted, transferred, or
re-padded.  The host is touched only at the chain head (one
`staged_put` per chunk), at the count-gated emit drain, and at the
re-anchor horizon (~24.8 days), exactly like a single device query.

Host to device, as a single device query (ops/device_query.py): a
chunk crosses as ONE packed ``int32 [k, B]`` buffer (the head's
``_pack``), one ``staged_put`` of one leaf and one call of the fused
program, which takes it apart by static slices (``_unpack``).  Its
rows (``buf_rows``): the head lanes the chain consumes (those the
head's expressions read and those a wire carries downstream), a
relative-timestamp row for each stage whose step keeps or reads one,
and the valid mask; group ids and the timestamps of the other stages
are zeros made in the program.  A batch is cut only as far as the
stages ask: the smallest ``chunk_rows`` among them (a running stage
2,048; a ``length(10)`` window with two aggregates 131,072; filters
never).

Stage subset (the planner falls back to the junction path, with a
counted reason, for anything else — planner/fusion.py):

- interior + head stages: single-input device queries of kind
  filter / running / sliding, no group-by, CURRENT output;
- intermediate lanes: INT (int32, bit-exact), FLOAT (float32), BOOL,
  and DOUBLE expression outputs (both paths compute those in float32,
  so forwarding the f32 lane is bit-identical to the junction's
  f64 column + f32 re-pad);
- tail: a device query (as above; order-by/limit/offset ride the
  planner's host-side passthrough selector, as on the junction path)
  OR an unpartitioned dense pattern over the last intermediate stream
  (no absent-deadline timers).

The dense tail runs under `lax.scan` over the batch rows inside the
SAME jit: the junction path processes an unpartitioned pattern in B
singleton collision rounds (one dispatch each); the scan is that exact
round sequence fused into one program, with invalid rows routed to the
engine's scratch partition row — bit-identical match sets and ordering
(`flatten_match_parts` lexsort keys are preserved).

Emission follows the async-emit contract (core/emit_queue.py): one
count scalar gates the chunk, matched chunks stay device-resident in
the bounded pending-emit queue until a coalesced drain, and
`FusedDeferredEmit.materialize` reproduces exactly what the junction
path's tail query would have emitted (one EventBatch per junction
batch).

This module is scanned by the `host-sync-hazard` analysis rule: it
contains NO host materializer call sites at all — counts resolve
through `fetch_coalesced`, column fetches happen only inside the
pending-emit drain, and host-side prep uses zero-fill + `.astype`.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.observability.stall import waits_on_device
from siddhi_tpu.observability.trace import (
    SCOPE_FUSED_COUNT,
    SCOPE_FUSED_HEAD,
    SCOPE_FUSED_INTERIOR,
    SCOPE_FUSED_TAIL,
    STAGE_CONVERT,
    STAGE_DISPATCH,
    span,
)
from siddhi_tpu.ops.device_query import TS_KEY, VALID_KEY, _pow2

TAIL_DEVICE = "device"
TAIL_DENSE = "dense"


class FusedGraphEngine:
    """One fused chain: device-query stages wired output→input on
    device, with an optional dense-pattern tail.

    ``stages``: the chain's DeviceQueryEngines in flow order (each
    stage's input stream is the previous stage's `insert into` target).
    ``dense_tail``/``dense_stream_key``: terminal DensePatternEngine
    reading the last intermediate stream (or None for a device tail).
    """

    def __init__(self, stages: List, dense_tail=None,
                 dense_stream_key: Optional[str] = None):
        if not stages:
            raise SiddhiAppCreationError("fused chain needs device stages")
        if len(stages) + (1 if dense_tail is not None else 0) < 2:
            raise SiddhiAppCreationError("fused chain needs >= 2 stages")
        self.stages = list(stages)
        self.dense = dense_tail
        self.dense_stream_key = dense_stream_key
        head = stages[0]
        self.jax, self.jnp = head.jax, head.jnp
        for eng in stages:
            if eng.kind not in ("filter", "running", "sliding"):
                raise SiddhiAppCreationError(
                    f"fused chain: stage kind '{eng.kind}' not fusable")
            if eng.group_exprs or eng.partition_mode:
                raise SiddhiAppCreationError(
                    "fused chain: group-by/partition stages not fusable")
        for eng in stages[1:]:
            if eng.long_attrs:
                raise SiddhiAppCreationError(
                    "fused chain: LONG intermediate attributes have no "
                    "device-resident lane")
        # stage-to-stage wire plans: consumer attr -> producer lane
        self._wires: List[Optional[List[Tuple[str, str, str]]]] = [None]
        for si in range(1, len(stages)):
            self._wires.append(
                self._wire_for(stages[si - 1], stages[si].attrs))
        if dense_tail is not None:
            if dense_stream_key is None:
                raise SiddhiAppCreationError(
                    "fused chain: dense tail needs its stream key")
            if getattr(dense_tail, "has_deadlines", False):
                raise SiddhiAppCreationError(
                    "fused chain: absent-deadline patterns need the "
                    "scheduler-driven junction path")
            dkeys = set(dense_tail.device_col_keys(dense_stream_key))
            self._dense_wire: List[Tuple[str, str, str, bool]] = []
            spec = {name: (kind, v)
                    for kind, v, name in stages[-1].out_spec}
            for a in dense_tail.numeric_stream_attrs(dense_stream_key):
                kind, v = self._resolve_spec(spec, a)
                self._dense_wire.append(
                    (a, kind, v, (a + "|hi") in dkeys))
            self.tail_kind = TAIL_DENSE
            self.output_names = list(dense_tail.output_names)
            from siddhi_tpu.core.dense_pattern import output_attr_types

            self.out_dtypes = [
                t.np_dtype for t in output_attr_types(dense_tail)]
        else:
            tail = stages[-1]
            self.tail_kind = TAIL_DEVICE
            self.output_names = list(tail.output_names)
            self.out_dtypes = [t.np_dtype for t in tail.out_types]
            # tail passthroughs gather the tail's INPUT lane host-side;
            # those lanes are f32/i32/bool on the fused path, so only
            # types whose lane is exact may ride them (planner-enforced;
            # re-checked here for direct-API callers)
            self.fwd_names = sorted({
                v for kind, v, _n in tail.out_spec if kind == "passthrough"
            })
        # the packed buffer's rows.  Head lanes: what the head's
        # expressions read and what the first wire (or a dense tail
        # right behind the head) takes from the head's input; further
        # wires and the tail's passthroughs reach the head only through
        # those
        wire = self._wires[1] if len(stages) > 1 else self._dense_wire
        self.head_rows: List[str] = list(dict.fromkeys(
            [*head.read_lanes, *(w[2] for w in wire if w[1] == "in")]))
        # a relative-timestamp row where a step keeps or reads it (each
        # stage has its own epoch); the dense tail always does
        self.ts_rows: Dict[int, str] = {
            si: f"{TS_KEY}|{si}" for si, eng in enumerate(stages)
            if eng.W or eng._reads_ts}
        if dense_tail is not None:
            self.ts_rows[len(stages)] = f"{TS_KEY}|{len(stages)}"
        self.buf_rows: List[str] = [
            *self.head_rows, *self.ts_rows.values(), VALID_KEY]
        # rows a chunk: the tightest bound among the stages' own (None:
        # a batch is never cut).  A dense tail adds none: its scan
        # holds one row's working set at a time and its emit arrays
        # grow with the rows however they are cut
        bounds = [eng.chunk_rows for eng in stages
                  if eng.chunk_rows is not None]
        self.chunk_rows: Optional[int] = min(bounds) if bounds else None
        # wired by the runtime (staged_put device-put accounting)
        self.ingest_stats = None
        # @app:faults injector (planner-wired; one chain = one step site)
        self.faults = None
        self._fused_step: Optional[Callable] = None

    @staticmethod
    def _resolve_spec(spec, attr):
        if attr not in spec:
            raise SiddhiAppCreationError(
                f"fused chain: consumer attribute '{attr}' is not an "
                "output of the producer stage")
        kind, v = spec[attr]
        if kind == "expr":
            return "out", attr
        if kind == "passthrough":
            return "in", v
        raise SiddhiAppCreationError(
            f"fused chain: producer select item '{attr}' ({kind}) "
            "cannot stay device-resident")

    def _wire_for(self, producer, attrs):
        spec = {name: (kind, v) for kind, v, name in producer.out_spec}
        return [(a, *self._resolve_spec(spec, a)) for a in attrs]

    # -- state ---------------------------------------------------------------

    def init_state(self) -> Tuple:
        states = [eng.init_state() for eng in self.stages]
        if self.dense is not None:
            states.append(self.dense.init_state())
        return tuple(states)

    # -- the fused program ---------------------------------------------------

    def make_step(self) -> Callable:
        """One jit over the whole chain:

        fused(states, buf int32 [k, B]: a row for each of ``buf_rows``)
          -> device tail: (states, emitmask[B], out {name: [B]},
                           fwd {attr: [B]}, count)
          -> dense tail:  (states, emitmask[B, 2I], f, i, anchor, count)

        ``count`` is exact (already masked by the chain's valid lane),
        so the async-emit count gate never overcounts padding.
        """
        if self._fused_step is not None:
            return self._fused_step
        self._fused_step = self.jax.jit(self._build_fused())
        return self._fused_step

    def _build_fused(self) -> Callable:
        """The raw (un-jitted) fused chain function — the subclassable
        seam: ShardedFusedGraphEngine (parallel/fused_shard.py) wraps
        it in shard_map before jitting."""
        jax, jnp = self.jax, self.jnp
        named_scope = jax.named_scope
        # a stage's filter and select go by its place in the chain; a
        # window stage's own ``siddhi.window.*`` phases stay innermost
        dev_steps = [eng.make_step(jit=False, scoped=False)
                     for eng in self.stages]
        places = [SCOPE_FUSED_INTERIOR] * len(dev_steps)
        if self.dense is None:  # else every device stage lies before it
            places[-1] = SCOPE_FUSED_TAIL
        places[0] = SCOPE_FUSED_HEAD
        wires = self._wires
        dense = self.dense
        if dense is not None:
            dstep = dense.make_step(self.dense_stream_key, jit=False)
            dkeys = list(dense.device_col_keys(self.dense_stream_key))
            dwire = self._dense_wire
            P = dense.n_partitions

        head = self.stages[0]
        n_rels = len(dev_steps) + (dense is not None)

        def fused(states, buf):
            rows = head._unpack(buf, self.buf_rows)
            grp = jnp.zeros(buf.shape[1], jnp.int32)
            cols = {k: rows[k] for k in self.head_rows}
            rels = [rows[self.ts_rows[si]] if si in self.ts_rows else grp
                    for si in range(n_rels)]
            valid = rows[VALID_KEY] != 0
            new_states = []
            v = valid
            cur = cols
            ov = valid
            out: Dict = {}
            for si, step in enumerate(dev_steps):
                # the hop belongs to the stage it feeds
                with named_scope(places[si]):
                    if si > 0:
                        # the hop: wire producer lanes straight into the
                        # consumer's input env — no compaction, no
                        # transfer; rows the producer dropped just lose
                        # their valid bit
                        v = v & ov.astype(bool)
                        cur = {
                            a: (out[key] if src == "out" else cur[key])
                            for a, src, key in wires[si]
                        }
                    st, ov, out, _n = step(states[si], cur, rels[si],
                                           grp, grp, v)
                new_states.append(st)
            if dense is None:
                with named_scope(SCOPE_FUSED_COUNT):
                    emitmask = ov.astype(bool) & v
                    count = jnp.sum(emitmask.astype(jnp.int32))
                fwd = {k: cur[k] for k in self.fwd_names}
                return tuple(new_states), emitmask, out, fwd, count
            # dense tail: the junction path feeds an unpartitioned
            # pattern one singleton collision round per row; lax.scan is
            # that exact sequence inside the same program.  Invalid rows
            # route to the scratch partition row (what the junction
            # path's padding lanes do) so state stays bit-identical.
            # the engine's own ``siddhi.dense.*`` scopes stay innermost
            with named_scope(SCOPE_FUSED_TAIL):
                v = v & ov.astype(bool)
                dcols = {}
                for a, src, key, is_int in dwire:
                    lane = out[key] if src == "out" else cur[key]
                    if is_int:
                        # int32 lane -> the engine's bit-exact hi/lo pair
                        # (prepare_cols semantics, computed in-jit)
                        lane = lane.astype(jnp.int32)
                        dcols[a + "|hi"] = jnp.where(
                            lane < 0, jnp.int32(-1), jnp.int32(0))
                        dcols[a + "|lo"] = jnp.bitwise_xor(
                            lane, jnp.int32(-(2 ** 31)))
                    else:
                        dcols[a] = lane.astype(jnp.float32)
                xs = {"__t": rels[-1], "__v": v}
                for k in dkeys:
                    xs[k] = dcols[k]

                def body(dstate, x):
                    vb = x["__v"][None]
                    pi = jnp.where(x["__v"], jnp.int32(0),
                                   jnp.int32(P)).astype(jnp.int32)[None]
                    cb = {k: x[k][None] for k in dkeys}
                    dstate, emit, outs, anchor, _ne = dstep(
                        dstate, pi, cb, x["__t"][None], vb)
                    return dstate, (emit[0], outs["f"][0], outs["i"][0],
                                    anchor[0])

                dstate, ys = jax.lax.scan(body, states[-1], xs)
            new_states.append(dstate)
            emit, out_f, out_i, anchor = ys
            with named_scope(SCOPE_FUSED_COUNT):
                emitmask = emit & v[:, None]
                count = jnp.sum(emitmask.astype(jnp.int32))
            return (tuple(new_states), emitmask, out_f, out_i, anchor,
                    count)

        return fused

    # -- host entry points ---------------------------------------------------

    def process_batch_deferred(self, states: Tuple,
                               cols: Dict[str, np.ndarray],
                               ts: np.ndarray):
        """Run the fused program over one junction batch (whole, or
        in chunks of ``chunk_rows`` where a stage bounds them) and keep
        every output device-resident behind a FusedDeferredEmit — the
        async-emit contract of the per-query engines, for the whole
        chain at once."""
        n = len(ts)
        if n == 0:
            return states, None
        chunks: List[dict] = []
        rows = self.chunk_rows or n
        if self.ingest_stats is not None:
            self.ingest_stats.device_chunks += -(-n // rows)
        if n > rows:
            for i in range(0, n, rows):
                sl = slice(i, i + rows)
                states = self._chunk(
                    states, {k: v[sl] for k, v in cols.items()}, ts[sl],
                    i, chunks)
        else:
            states = self._chunk(states, cols, ts, 0, chunks)
        return states, FusedDeferredEmit(self, chunks, ts)

    def _pad_batch(self, n: int) -> int:
        """Padded chunk width.  The sharded subclass rounds up further
        so the batch axis splits evenly over the mesh."""
        return _pow2(n)

    def _chunk(self, states: Tuple, cols: Dict[str, np.ndarray],
               ts: np.ndarray, offset: int, chunks: List[dict]) -> Tuple:
        n = len(ts)
        B = self._pad_batch(n)
        states = list(states)
        with span(STAGE_CONVERT, n):
            buf = self._lanes(states, cols, ts, n, B)
        from siddhi_tpu.core.ingest_stage import staged_put

        buf = staged_put(buf, faults=self.faults, stats=self.ingest_stats)
        if self.faults is not None:
            self.faults.check("step.device")
            if self.dense is not None:
                self.faults.check("step.dense")
        step = self.make_step()
        with span(STAGE_DISPATCH, 1):
            res = step(tuple(states), buf)
            # the call's input is released with it: dropping the
            # device buffer is time of the dispatch
            del buf
        if self.tail_kind == TAIL_DEVICE:
            new_states, emitmask, out, fwd, count = res
            chunks.append({
                "kind": TAIL_DEVICE, "emitmask": emitmask,
                "out": dict(out), "names": list(out),
                "fwd": dict(fwd), "fwd_names": list(fwd),
                "count": count, "n": n, "ts": ts,
            })
        else:
            new_states, emitmask, out_f, out_i, anchor, count = res
            chunks.append({
                "kind": TAIL_DENSE, "emitmask": emitmask, "f": out_f,
                "i": out_i, "anchor": anchor, "count": count, "n": n,
                "offset": offset,
            })
        return tuple(new_states)

    def _lanes(self, states: List, cols: Dict[str, np.ndarray],
               ts: np.ndarray, n: int, B: int) -> np.ndarray:
        """A chunk as the fused program's one packed buffer, ``int32
        [len(buf_rows), B]``, zeros past ``n``.  A stage past the int32
        horizon is re-anchored in ``states``."""
        head = self.stages[0]
        lanes = head._host_lanes(cols, n, self.head_rows)
        # per-stage relative timestamps: each stage keeps its own epoch
        # (base_ts), re-anchored host-side at the int32 horizon exactly
        # like its standalone runtime would, whether or not its step
        # takes a row of them
        for si, eng in enumerate(self.stages):
            if eng.base_ts is None:
                eng.base_ts = int(ts[0]) - 1
            rel64 = ts - eng.base_ts
            if int(rel64.max()) >= eng._REL_LIMIT:
                states[si], rel64 = eng._re_anchor(states[si], rel64)
            if si in self.ts_rows:
                lanes[self.ts_rows[si]] = rel64
        if self.dense is not None:
            rel64 = self.dense.rel_ts64(ts)
            states[-1], rel64 = self.dense.maybe_re_anchor(
                states[-1], rel64)
            lanes[self.ts_rows[len(self.stages)]] = rel64
        lanes[VALID_KEY] = np.ones(n, dtype=np.int32)
        return head._pack({k: lanes[k] for k in self.buf_rows}, n, B)


class FusedDeferredEmit:
    """Device-resident outputs of one fused junction batch, pending
    drain — the pending-emit queue contract (core/emit_queue.py):
    ``probe``/``resolve`` fetch only count scalars, ``device_arrays`` +
    ``materialize`` reproduce exactly what the junction path's tail
    query would have emitted for this batch (ONE EventBatch worth of
    columns, already cast to the declared output dtypes)."""

    __slots__ = ("graph", "chunks", "ts64", "_total")

    def __init__(self, graph: FusedGraphEngine, chunks: List[dict],
                 ts64: np.ndarray):
        self.graph = graph
        self.chunks = chunks
        self.ts64 = ts64
        self._total: Optional[int] = None

    def probe(self):
        return self.chunks[0]["count"] if self.chunks else None

    @waits_on_device
    def resolve(self) -> int:
        if self._total is not None:
            return self._total
        if self.chunks:
            from siddhi_tpu.core.emit_queue import fetch_coalesced

            counts = fetch_coalesced([ch["count"] for ch in self.chunks])
        else:
            counts = []
        self.chunks = [ch for ch, c in zip(self.chunks, counts) if int(c)]
        self._total = int(sum(int(c) for c in counts))
        return self._total

    def gates(self) -> List[Tuple]:
        """``(count, arrays)`` of every chunk the batch still holds, in
        ``device_arrays()`` order: before ``resolve()`` every dispatched
        chunk, which is what the pipeline starts for the host at
        dispatch (core/device_pipeline.py)."""
        out = []
        for ch in self.chunks:
            arrs = [ch["emitmask"]]
            if ch["kind"] == TAIL_DEVICE:
                arrs.extend(ch["out"][nm] for nm in ch["names"])
                arrs.extend(ch["fwd"][k] for k in ch["fwd_names"])
            else:
                arrs.extend((ch["f"], ch["i"], ch["anchor"]))
            out.append((ch["count"], arrs))
        return out

    def device_arrays(self) -> List:
        return [a for _count, arrays in self.gates() for a in arrays]

    def materialize(self, host_arrays
                    ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        g = self.graph
        if g.tail_kind == TAIL_DEVICE:
            return self._materialize_device(host_arrays)
        return self._materialize_dense(host_arrays)

    def _materialize_device(self, host):
        tail = self.graph.stages[-1]
        pos = 0
        col_parts: List[Dict[str, np.ndarray]] = []
        ts_parts: List[np.ndarray] = []
        for ch in self.chunks:
            n = ch["n"]
            em = host[pos][:n]
            pos += 1
            out_np = {}
            for nm in ch["names"]:
                out_np[nm] = host[pos][:n]
                pos += 1
            fwd_cols = {}
            for k in ch["fwd_names"]:
                fwd_cols[k] = host[pos][:n]
                pos += 1
            idx = np.flatnonzero(em)
            if len(idx) == 0:
                continue
            col_parts.append(
                tail._out_columns(out_np, idx, None, fwd_cols, idx))
            ts_parts.append(ch["ts"][idx])
        if not ts_parts:
            return tail._empty_cols(), np.empty(0, dtype=np.int64)
        out_cols = {
            nm: np.concatenate([p[nm] for p in col_parts])
            for nm in tail.output_names
        }
        return out_cols, np.concatenate(ts_parts)

    def _materialize_dense(self, host):
        from siddhi_tpu.ops.dense_nfa import flatten_match_parts

        g = self.graph
        eng = g.dense
        pos = 0
        ev_parts: List[np.ndarray] = []
        out_parts: List[np.ndarray] = []
        key_parts: List[np.ndarray] = []
        for ch in self.chunks:
            n = ch["n"]
            em = host[pos][:n]
            f_h = host[pos + 1][:n]
            i_h = host[pos + 2][:n]
            anchor = host[pos + 3][:n]
            pos += 4
            if not em.any():
                continue
            rows, lanes = np.nonzero(em)
            ev_parts.append(ch["offset"] + rows)
            out_parts.append(eng.assemble_out(f_h, i_h, rows, lanes))
            key_parts.append(np.stack(
                [ch["offset"] + rows, anchor[rows, lanes], lanes],
                axis=1))
        ev, out = flatten_match_parts(
            ev_parts, out_parts, key_parts, max(len(eng.out_spec), 1))
        out_cols = {
            nm: out[:, oi].astype(g.out_dtypes[oi])
            for oi, nm in enumerate(g.output_names)
        }
        return out_cols, self.ts64[ev]
