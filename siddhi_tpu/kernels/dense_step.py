"""Plane-layout Pallas step for the simple every-chain dense NFA class.

The eligible class (gated in ``planner/kernels.py``) is the capture-free
every-start chain: all nodes are plain stream states (``min==max==1``),
no sequences, no group-every, no absent deadlines, no register slots, no
mesh.  Inside that class the XLA step's carry shrinks to two fields —
node activity and the within anchor.  ``counts``/``regs`` are provably
constant in this class: the step gathers the batch's rows through the
engine's state layout (``ops/dense_layout.py``, one contiguous row per
partition), replaces the two fields it advances and scatters the rows
back, so snapshot/restore, sharding, and the multiplex seat tiling see
the same physical state as under the XLA step.

Layout: the batch axis is the vector.  Every ``(node, instance)`` pair
is one int32 *plane* over the batch, and a block of 1024 batch rows is
exactly one ``(8, 128)`` vreg per plane — arrays enter the kernel as
``[S, I, Bp/128, 128]`` with ``(S, I, 8, 128)`` blocks, so every block's
last two dims are the native tile whatever the batch size, and the
kernel body Mosaic compiles is the same for every batch the runtime
dispatches (only the grid length changes).  The node sweep is unrolled
in Python; the instance lanes and their rank matching ride the leading
(untiled) dims, so every operand is a stack of whole vregs: elementwise
int32/bool arithmetic, no reshapes, no in-kernel cumsum.

The kernel mirrors the XLA step operation for operation — within
expiry, the reversed node sweep, the rank-matched placement
(``_rank_place``) and the overflow count — so detections, anchors, and
overflow counters are bit-identical (there is no float in the whole
step).  Candidate filters are lane-uniform in this class and are
evaluated on the XLA side into one eligibility plane per node; output
columns are pure per-event selects and are assembled outside the kernel
from the emit mask, exactly as ``_emit_rows`` writes them.
"""

from __future__ import annotations

from typing import Dict

from siddhi_tpu.planner.expr import N_KEY, TS_KEY
from siddhi_tpu.query_api import AttrType

_INT_TYPES = (AttrType.INT, AttrType.LONG)

SUBLANES = 8
LANES = 128
# batch rows per grid point: one int32 vreg per (node, instance) plane
BLOCK_ROWS = SUBLANES * LANES


def build_plane_advance(engine, stream_key: str):
    """Kernel-backed replacement for the automaton of
    ``DensePatternEngine.make_advance``: same signature and same
    returns, on the logical fields of B gathered rows (``make_step``
    gathers and scatters around it, ``make_rounds`` loops over it).
    Only callable for engines that passed
    ``check_dense_kernel_eligible``.
    """
    jax, jnp = engine.jax, engine.jnp
    from jax.experimental import pallas as pl

    from siddhi_tpu.kernels import probe

    S, I = engine.S, engine.I
    nodes = engine.nodes
    node_filters = engine.node_filters
    within = engine.within_ms
    out_spec = engine.out_spec
    out_int = engine.out_int
    O = max(len(out_spec), 1)
    n_iout = sum(out_int)
    interpret = probe.interpret_mode()
    on_stream = [n.specs[0].stream_key == stream_key for n in nodes]
    int_out_idx: Dict[int, int] = {}
    for _oi, _isint in enumerate(out_int):
        if _isint:
            int_out_idx[_oi] = len(int_out_idx)
    i32 = jnp.int32

    def kernel(ok_ref, a_ref, first_ref, ts_ref,
               a_out, first_out, emit_out, anch_out, ovf_out):
        ts = ts_ref[...]  # (8, 128): this block's relative timestamps
        zero = jnp.zeros_like(ts)
        lanes0 = jnp.zeros((I,) + ts.shape, i32)

        def as_i32(mask):
            # a select, not a cast: Mosaic folds eq(extui(x), extui(y))
            # into an i1 compare it then cannot legalize
            return jnp.where(mask, 1, 0).astype(i32)

        # per node: the instance lanes' planes, (I, 8, 128)
        a = [a_ref[s] != 0 for s in range(S)]
        first = [first_ref[s] for s in range(S)]

        if within is not None:
            for s in range(S):
                expired = (first[s] > 0) & ((ts - first[s]) > within)
                a[s] = a[s] & ~expired
                first[s] = jnp.where(expired, 0, first[s])

        emit = lanes0 != 0
        anch = lanes0
        ovf = zero
        for s in reversed(range(S)):
            if not on_stream[s]:
                continue
            ok = ok_ref[s] != 0  # valid pre-ANDed
            if s == 0:
                # the standing virgin: instance lane 0 of node 0 is
                # always pending, on every row; fresh arming each event,
                # so the anchor is THIS event
                fire = jnp.concatenate(
                    [ok[None], a[0][1:] & ok], axis=0) if I > 1 else ok[None]
                first[0] = jnp.where(fire, ts, first[0])
            else:
                fire = a[s] & ok
                first[s] = jnp.where(fire & (first[s] == 0), ts, first[s])
                a[s] = a[s] & ~fire
            anchor = jnp.where(first[s] > 0, first[s], ts)
            if s == S - 1:
                emit = emit | fire
                anch = jnp.where(fire, anchor, anch)
                continue
            # rank-matched placement into node s+1 (_rank_place with
            # counts == 0: free lanes are just the inactive ones).  The
            # XLA step's inclusive cumsum - 1 equals the exclusive
            # running count on every lane the masks let through.
            free = ~a[s + 1]
            src_rank, free_rank = [], []
            n_fire, n_free = zero, zero
            for i in range(I):
                src_rank.append(n_fire)
                free_rank.append(n_free)
                n_fire = n_fire + as_i32(fire[i])
                n_free = n_free + as_i32(free[i])
            src_rank, free_rank = jnp.stack(src_rank), jnp.stack(free_rank)
            placed = fire & (src_rank < n_free)
            ovf = ovf + jnp.sum(as_i32(fire & ~placed), axis=0)
            # (source lane, target lane, 8, 128) one-hot assignment: the
            # pairing runs over leading dims, every operand a whole vreg
            assign = (placed[:, None] & free[None, :]
                      & (src_rank[:, None] == free_rank[None, :]))
            got = jnp.sum(as_i32(assign), axis=0) > 0
            moved = jnp.sum(jnp.where(assign, anchor[:, None], 0), axis=0)
            a[s + 1] = a[s + 1] | got
            first[s + 1] = jnp.where(got, moved, first[s + 1])

        for s in range(S):
            a_out[s] = as_i32(a[s])
            first_out[s] = first[s]
        emit_out[...] = as_i32(emit)
        anch_out[...] = anch
        ovf_out[...] = ovf

    def _call(G: int):
        """pallas_call over ``G`` row groups of 128 (``G % 8 == 0``)."""
        nodes_ = pl.BlockSpec((S, I, SUBLANES, LANES),
                              lambda g: (0, 0, g, 0))
        lanes_ = pl.BlockSpec((I, SUBLANES, LANES), lambda g: (0, g, 0))
        oks = pl.BlockSpec((S, SUBLANES, LANES), lambda g: (0, g, 0))
        row = pl.BlockSpec((SUBLANES, LANES), lambda g: (g, 0))
        return pl.pallas_call(
            kernel,
            grid=(G // SUBLANES,),
            in_specs=[oks, nodes_, nodes_, row],
            out_specs=[nodes_, nodes_, lanes_, lanes_, row],
            out_shape=[
                jax.ShapeDtypeStruct((S, I, G, LANES), i32),
                jax.ShapeDtypeStruct((S, I, G, LANES), i32),
                jax.ShapeDtypeStruct((I, G, LANES), i32),
                jax.ShapeDtypeStruct((I, G, LANES), i32),
                jax.ShapeDtypeStruct((G, LANES), i32),
            ],
            interpret=interpret,
        )

    def env_for(s, cols, ts):
        env = {}
        spec = nodes[s].specs[0]
        for attr in spec.stream_def.attributes:
            if attr.type in _INT_TYPES:
                hk, lk = f"{attr.name}|hi", f"{attr.name}|lo"
                if hk in cols:
                    env[f"__cand.{attr.name}|hi"] = cols[hk][:, None]
                    env[f"__cand.{attr.name}|lo"] = cols[lk][:, None]
            elif attr.name in cols:
                env["__cand." + attr.name] = cols[attr.name][:, None]
        env[TS_KEY] = ts[:, None]
        env[N_KEY] = ts.shape[0]
        return env

    def advance(fields, cols, ts, valid):
        B = ts.shape[0]
        Bp = -(-B // BLOCK_ROWS) * BLOCK_ROWS
        G = Bp // LANES
        pad = Bp - B

        # lane-uniform candidate filters, evaluated XLA-side: one
        # eligibility plane per node, pre-ANDed with the valid mask
        ok_rows = []
        for s in range(S):
            if not on_stream[s]:
                ok_rows.append(jnp.zeros((B,), dtype=bool))
                continue
            f = node_filters[s][0]
            if f is None:
                ok_rows.append(valid)
            else:
                okb = jnp.broadcast_to(
                    jnp.asarray(f.fn(env_for(s, cols, ts))).astype(bool),
                    (B, 1))[:, 0]
                ok_rows.append(okb & valid)
        ok_mat = jnp.stack(ok_rows, axis=0)  # [S, B]

        a = fields["active"]        # [B, S, I]
        first = fields["first_ts"]  # [B, S, I]
        if pad:
            a = jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
            first = jnp.pad(first, ((0, pad), (0, 0), (0, 0)))
            ok_mat = jnp.pad(ok_mat, ((0, 0), (0, pad)))
            ts_p = jnp.pad(ts, (0, pad))
        else:
            ts_p = ts

        def to_planes(x):  # [Bp, S, I] -> [S, I, G, 128]
            return x.transpose(1, 2, 0).reshape(S, I, G, LANES)

        def from_planes(x):  # [..., I, G, 128] -> [B, ..., I]
            x = x.reshape(x.shape[:-2] + (Bp,))
            return jnp.moveaxis(x, -1, 0)[:B]

        a_o, first_o, emit_o, anch_o, ovf_o = _call(G)(
            ok_mat.astype(i32).reshape(S, G, LANES),
            to_planes(a.astype(i32)), to_planes(first),
            ts_p.reshape(G, LANES))

        a_new = from_planes(a_o) != 0
        first_new = from_planes(first_o)
        emit_b0 = from_planes(emit_o) != 0  # [B, I]
        anch_b0 = from_planes(anch_o)
        ovf_delta = ovf_o.reshape(Bp)[:B]

        # (the eligible class has no via-path: the emit lanes are bank 0)
        emit, emit_anchor = emit_b0, anch_b0

        # output columns: pure candidate selects, assembled from the
        # emit mask exactly as the XLA _emit_rows writes them (bank 0
        # only — the eligible class has no via-path)
        out_vals = jnp.zeros((B, I, O), dtype=jnp.float32)
        out_ivals = jnp.zeros((B, I, 2 * n_iout), dtype=jnp.int32)
        sl = slice(0, I)
        for oi, (_name, src) in enumerate(out_spec):
            ii = int_out_idx.get(oi)
            if ii is not None:
                hk, lk = f"{src[1]}|hi", f"{src[1]}|lo"
                if hk not in cols:
                    continue
                out_ivals = out_ivals.at[:, sl, 2 * ii].set(
                    jnp.where(emit_b0, cols[hk][:, None],
                              out_ivals[:, sl, 2 * ii]))
                out_ivals = out_ivals.at[:, sl, 2 * ii + 1].set(
                    jnp.where(emit_b0, cols[lk][:, None],
                              out_ivals[:, sl, 2 * ii + 1]))
                continue
            val = cols.get(src[1])
            if val is None:
                continue
            out_vals = out_vals.at[:, sl, oi].set(
                jnp.where(emit_b0, val.astype(jnp.float32)[:, None],
                          out_vals[:, sl, oi]))

        # counts/regs are constant in the eligible class: they ride back
        # inside the gathered rows, value-identical
        return ({**fields, "active": a_new, "first_ts": first_new},
                ovf_delta, emit, {"f": out_vals, "i": out_ivals},
                emit_anchor)

    return advance


def smoke_compile(engine):
    """Compile the kernel step for every source stream; raise on any
    failure (a Mosaic refusal, a shape bug, ...) with the compiler's
    message.

    One block of batch rows is enough: the kernel's block shapes do not
    depend on the batch size, so the body Mosaic accepts here is the
    body every runtime batch runs.  Goes through ``engine.make_step``
    (the engine's ``use_kernel`` flag must already be set) so the traced
    function lands in the engine's step cache and is reused at runtime.
    """
    import numpy as np

    jax = engine.jax
    state_shapes = {
        k: jax.ShapeDtypeStruct(shape, np.int32) for k, shape in
        engine.layout.physical_shapes(engine.n_partitions + 1).items()
    }
    B = BLOCK_ROWS
    i32 = jax.ShapeDtypeStruct((B,), np.int32)
    b1 = jax.ShapeDtypeStruct((B,), np.bool_)
    for sk in engine.stream_keys:
        cols = {
            k: jax.ShapeDtypeStruct(
                (B,), np.int32 if "|" in k else np.float32)
            for k in engine.device_col_keys(sk)
        }
        engine.make_step(sk).lower(state_shapes, i32, cols, i32, b1).compile()
