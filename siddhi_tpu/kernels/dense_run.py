"""Pallas kernel for the *run* of a skewed batch: the long tail of rounds
in which a handful of partitions carry hundreds of events each
(``DensePatternEngine.make_rounds``).

The run is a dependence chain: link ``l`` takes the same few rows
through their next event.  As XLA operations a link of the 16-state
chain is some 220 small kernels, so a batch with a run of 1,000 launches
a quarter of a million of them (and a profiler records every one).  Here
the whole chain is ONE kernel: the rows' fields stay in VMEM from the
first link to the last, a grid step takes eight links, and a link is
plain elementwise arithmetic on ``(1, 128)`` planes, one plane per
(node, instance lane[, register]) with the run's up to 128 partitions on
the vector lanes.

Eligible class (``eligible``): ``every``-headed PATTERN chains of plain
stream nodes on one stream, float captures, optional ``within`` — the
north-star app's class.  Inside it the kernel mirrors
``make_advance``'s automaton operation for operation (within expiry, the
reversed node sweep, capture writes, ``_rank_place``, the overflow
count and the restart on emission), with the node filters evaluated in
the kernel by the same compiled expressions, so state, emissions,
anchors and overflow are bit-identical; tier-1 pins that against the
XLA loop.  Every other engine keeps the XLA loop.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

from siddhi_tpu.planner.expr import N_KEY, TS_KEY

LANES = 128          # partitions of a run, on the vector lanes
LINKS_PER_STEP = 8   # links one grid step takes: one (8, 128) output tile
#: off a TPU the kernel could only run interpreted, which is exact and
#: slow to trace (the 16-node chain takes half a minute to compile on a
#: CPU): the engine keeps the XLA loop there.  The tests that pin the
#: kernel to that loop bit for bit set this.
INTERPRET_OFF_TPU = False


def eligible(engine, stream_key: str) -> bool:
    """Whether the run of ``engine`` on ``stream_key`` is in the
    kernel's class.  Everything here is known when the engine is built:
    nothing is chosen by an option."""
    if (engine.is_sequence or not engine.every_start or engine.group_every
            or engine.has_deadlines
            or engine.mesh is not None or engine.alloc.n_int
            or any(engine.out_int)):
        return False
    for node in engine.nodes:
        if not (node.kind == "stream" and len(node.specs) == 1
                and node.min_count == 1 and node.max_count == 1
                and node.specs[0].stream_key == stream_key):
            return False
    return True


def build_run(engine, stream_key: str) -> Callable:
    """``run(fields, cols, ts, starts, widths, n_links)`` for an eligible
    engine:

    - ``fields``: the logical fields of the run's 128 rows
      (``[128, S, I(, R)]``, as ``DenseStateLayout.gather`` gives them);
    - ``cols`` ``{key: [T, 128]}``, ``ts`` ``[T, 128]`` i32: the batch's
      lanes in tiles of 128 (at least one tile past the last lane);
    - ``starts`` / ``widths`` ``[L]`` i32: link ``l`` takes lanes
      ``starts[l] : starts[l] + widths[l]`` (at most 128) to rows
      ``0 : widths[l]``; ``L`` a multiple of 8;
    - ``n_links``: i32 scalar, links past it are skipped.

    Returns ``(fields, overflow increment [128], emit [I, L, 128] bool,
    anchor [I, L, 128] i32, out [n, I, L, 128] f32)``, ``out`` holding
    the register-sourced select items in ``out_spec`` order."""
    jax, jnp = engine.jax, engine.jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from siddhi_tpu.kernels import probe

    S, I = engine.S, engine.I
    Rn = max(engine.alloc.n, 1)
    nodes = engine.nodes
    node_filters = engine.node_filters
    within = engine.within_ms
    col_keys = engine.device_col_keys(stream_key)
    reg_outs = [src.index for _name, src in engine.out_spec
                if not isinstance(src, tuple)]
    n_ro = max(len(reg_outs), 1)
    writes = [[slot for slot in engine.node_writes[s]
               if slot.ref == nodes[s].specs[0].ref and slot.attr in col_keys]
              for s in range(S)]
    i32, f32 = jnp.int32, jnp.float32
    interpret = probe.interpret_mode()
    P = S * I  # planes of one [S, I] field

    def one_link(cols, ts, valid, a, first, regs):
        """One event a row: the plain-stream-node branch of
        ``make_advance``.  ``a`` / ``first`` are ``[S][I]`` lists of
        planes, ``regs`` ``[S][I][Rn]``; updated in place."""
        zero = jnp.zeros_like(ts)
        if within is not None:
            for s in range(S):
                for k in range(I):
                    # (a row with no event in this link keeps its state:
                    # the XLA step discards what expiry did to it)
                    expired = (valid & (first[s][k] > 0)
                               & (ts - first[s][k] > within))
                    a[s][k] = a[s][k] & ~expired
                    first[s][k] = jnp.where(expired, 0, first[s][k])
        emit = [zero != 0] * I
        anch = [zero] * I
        outs = [[jnp.zeros_like(ts, dtype=f32)] * I for _ in range(n_ro)]
        ovf = zero
        for s in reversed(range(S)):
            f = node_filters[s][0]
            fire = []
            for k in range(I):
                # simple start never rests: the standing virgin fires
                # straight through lane 0 on every event
                pending = (zero == 0) if s == 0 and k == 0 else a[s][k]
                if f is None:
                    ok = zero == 0
                else:
                    env = {"__cand." + key: cols[key] for key in col_keys}
                    for slot in engine.alloc.slots.values():
                        env[f"__reg.{slot.index}"] = regs[s][k][slot.index]
                    env[TS_KEY] = ts
                    env[N_KEY] = LANES
                    ok = jnp.broadcast_to(
                        jnp.asarray(f.fn(env)).astype(bool), ts.shape)
                fire.append(pending & ok & valid)
            for k in range(I):
                for slot in writes[s]:
                    regs[s][k][slot.index] = jnp.where(
                        fire[k], cols[slot.attr].astype(f32),
                        regs[s][k][slot.index])
                if s == 0:
                    # fresh arming each event: the anchor is this event
                    first[0][k] = jnp.where(fire[k], ts, first[0][k])
                else:
                    first[s][k] = jnp.where(
                        fire[k] & (first[s][k] == 0), ts, first[s][k])
                    a[s][k] = a[s][k] & ~fire[k]
            anchor = [jnp.where(first[s][k] > 0, first[s][k], ts)
                      for k in range(I)]
            if s == S - 1:
                for k in range(I):
                    emit[k] = emit[k] | fire[k]
                    anch[k] = jnp.where(fire[k], anchor[k], anch[k])
                    for o, r in enumerate(reg_outs):
                        outs[o][k] = jnp.where(fire[k], regs[s][k][r],
                                               outs[o][k])
                continue
            # _rank_place into node s + 1 (counts are 0 in this class:
            # the free lanes are the inactive ones)
            t = s + 1
            free = [~a[t][k] for k in range(I)]
            src_rank, free_rank = [], []
            n_fire, n_free = zero, zero
            for k in range(I):
                n_fire = n_fire + jnp.where(fire[k], 1, 0)
                n_free = n_free + jnp.where(free[k], 1, 0)
                src_rank.append(n_fire - 1)      # inclusive cumsum - 1
                free_rank.append(n_free - 1)
            placed = [fire[k] & (src_rank[k] < n_free) for k in range(I)]
            for k in range(I):
                ovf = ovf + jnp.where(fire[k] & ~placed[k], 1, 0)
            for kt in range(I):
                assign = [placed[ks] & free[kt]
                          & (src_rank[ks] == free_rank[kt])
                          for ks in range(I)]
                got = functools.reduce(lambda x, y: x | y, assign)
                moved_anchor = functools.reduce(
                    lambda x, y: x + y,
                    [jnp.where(assign[ks], anchor[ks], 0)
                     for ks in range(I)])
                a[t][kt] = a[t][kt] | got
                first[t][kt] = jnp.where(got, moved_anchor, first[t][kt])
                for r in range(Rn):
                    moved = functools.reduce(
                        lambda x, y: x + y,
                        [jnp.where(assign[ks], regs[s][ks][r], 0.0)
                         for ks in range(I)])
                    regs[t][kt][r] = jnp.where(got, moved, regs[t][kt][r])
        if engine.reset_on_emit:
            # emission restart: a row that emitted starts over
            any_emit = functools.reduce(lambda x, y: x | y, emit)
            for s in range(S):
                for k in range(I):
                    a[s][k] = a[s][k] & ~any_emit
                    first[s][k] = jnp.where(any_emit, 0, first[s][k])
        return emit, anch, outs, ovf

    def kernel(n_ref, starts_ref, widths_ref, *refs):
        n_cols = len(col_keys)
        col_refs = refs[:n_cols]
        ts_ref, a_in, first_in, regs_in = refs[n_cols:n_cols + 4]
        (a_ref, first_ref, regs_ref, ovf_ref, emit_ref, anch_ref,
         out_ref) = refs[n_cols + 4:]
        g = pl.program_id(0)

        @pl.when(g == 0)
        def _():
            # the state's blocks stay resident over the whole grid
            a_ref[...] = a_in[...]
            first_ref[...] = first_in[...]
            regs_ref[...] = regs_in[...]
            ovf_ref[...] = jnp.zeros_like(ovf_ref)

        def row(ref, p):
            return ref[pl.ds(p, 1), :]

        lane = jax.lax.broadcasted_iota(i32, (1, LANES), 1)

        def link(i, _carry):
            at = g * LINKS_PER_STEP + i
            # the link's 128 lanes start anywhere in a tile: two tiles
            # rotated to the start and joined
            tile, shift = starts_ref[at] // LANES, starts_ref[at] % LANES
            back = (LANES - shift) % LANES

            def window(ref):
                return jnp.where(lane < LANES - shift,
                                 pltpu.roll(row(ref, tile), back, 1),
                                 pltpu.roll(row(ref, tile + 1), back, 1))

            cols = {key: window(r) for key, r in zip(col_keys, col_refs)}
            ts = window(ts_ref)
            valid = lane < widths_ref[at]
            a = [[row(a_ref, s * I + k) != 0 for k in range(I)]
                 for s in range(S)]
            first = [[row(first_ref, s * I + k) for k in range(I)]
                     for s in range(S)]
            regs = [[[row(regs_ref, (s * I + k) * Rn + r)
                      for r in range(Rn)] for k in range(I)]
                    for s in range(S)]
            emit, anch, outs, ovf = one_link(cols, ts, valid, a, first, regs)
            for s in range(S):
                for k in range(I):
                    p = s * I + k
                    a_ref[p:p + 1, :] = jnp.where(a[s][k], 1, 0).astype(i32)
                    first_ref[p:p + 1, :] = first[s][k]
                    for r in range(Rn):
                        regs_ref[p * Rn + r:p * Rn + r + 1, :] = regs[s][k][r]
            ovf_ref[...] = ovf_ref[...] + ovf
            for k in range(I):
                emit_ref[k, pl.ds(i, 1), :] = jnp.where(
                    emit[k], 1, 0).astype(i32)
                anch_ref[k, pl.ds(i, 1), :] = anch[k]
                for o in range(n_ro):
                    out_ref[o, k, pl.ds(i, 1), :] = outs[o][k]
            return _carry

        # the links of this grid step that exist, one after another
        jax.lax.fori_loop(
            0, jnp.clip(n_ref[0] - g * LINKS_PER_STEP, 0, LINKS_PER_STEP),
            link, 0)

    def call(L: int, T: int):
        whole = lambda rows: pl.BlockSpec((rows, LANES),
                                          lambda g, *_: (0, 0))
        per_lane = pl.BlockSpec((I, LINKS_PER_STEP, LANES),
                                lambda g, *_: (0, g, 0))
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(L // LINKS_PER_STEP,),
                in_specs=[whole(T)] * (len(col_keys) + 1)
                + [whole(P), whole(P), whole(P * Rn)],
                out_specs=[whole(P), whole(P), whole(P * Rn), whole(1),
                           per_lane, per_lane,
                           pl.BlockSpec((n_ro, I, LINKS_PER_STEP, LANES),
                                        lambda g, *_: (0, 0, g, 0))]),
            out_shape=[
                jax.ShapeDtypeStruct((P, LANES), i32),
                jax.ShapeDtypeStruct((P, LANES), i32),
                jax.ShapeDtypeStruct((P * Rn, LANES), f32),
                jax.ShapeDtypeStruct((1, LANES), i32),
                jax.ShapeDtypeStruct((I, L, LANES), i32),
                jax.ShapeDtypeStruct((I, L, LANES), i32),
                jax.ShapeDtypeStruct((n_ro, I, L, LANES), f32),
            ],
            interpret=interpret,
        )

    def run(fields: Dict[str, object], cols, ts, starts, widths, n_links):
        def planes(x):   # [128, S, I(, Rn)] -> [planes, 128]
            return jnp.moveaxis(x, 0, -1).reshape(-1, LANES)

        a, first, regs, ovf, emit, anch, out = call(
            starts.shape[0], ts.shape[0])(
            jnp.reshape(n_links, (1,)).astype(i32), starts, widths,
            *[cols[key] for key in col_keys], ts,
            planes(fields["active"].astype(i32)), planes(fields["first_ts"]),
            planes(fields["regs"]))

        def rows(x, shape):  # [planes, 128] -> [128, *shape]
            return jnp.moveaxis(x.reshape(shape + (LANES,)), -1, 0)

        new = {**fields, "active": rows(a, (S, I)) != 0,
               "first_ts": rows(first, (S, I)),
               "regs": rows(regs, (S, I, Rn))}
        return new, ovf[0], emit != 0, anch, out

    return run


def smoke_compile(engine, stream_key: str, run) -> None:
    """Compile ``run`` at one grid step of links; raises what the
    compiler raises (on a TPU through Mosaic: a filter expression it
    cannot lower is known here, not inside the first skewed batch).  The
    kernel's body does not depend on the number of links."""
    import numpy as np

    jax = engine.jax
    fields = {name: jax.ShapeDtypeStruct((LANES,) + shape, dt)
              for name, (dt, shape) in engine.layout.fields.items()}
    tiles = lambda dt: jax.ShapeDtypeStruct((2, LANES), dt)
    links = jax.ShapeDtypeStruct((LINKS_PER_STEP,), np.int32)
    cols = {k: tiles(np.int32 if "|" in k else np.float32)
            for k in engine.device_col_keys(stream_key)}
    jax.jit(run).lower(fields, cols, tiles(np.int32), links, links,
                       jax.ShapeDtypeStruct((), np.int32)).compile()
