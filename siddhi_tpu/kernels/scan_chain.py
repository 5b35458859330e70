"""One fused kernel for the hotkey scan's max-plus + counting chains.

The XLA path materializes per-event transition matrices ``M [H,n,S,S]``
(max-plus) and ``T [H,n,S,S]`` (counting) and runs two passes of
``jax.lax.associative_scan`` over the event axis.  This kernel walks
the events once, carrying the value and count vectors directly — no
matrices, no second pass.

Layout: eight hot-key slots ride the sublanes and the chain's lanes ride
the 128 vector lanes, so the carried ``v``/``c`` are one ``(8, 128)``
vreg each and every event is one row load of ``x [n, Hp, 128]`` (lanes
``0..S`` the event's filter bits, lane ``S+1`` its timestamp; the scan
engine caps chains at 32 nodes, so they always fit).  Lane shifts are
XLU rolls; lanes ``>= S`` are scrubbed every event so the roll's
wrap-around never reaches a chain lane.  The event axis tiles in
chunks of ``EVENT_BLOCK`` along an ``arbitrary`` grid axis with the
carry in VMEM scratch, so block shapes — and the body Mosaic compiles —
are the same for every cycle length.  Each event's per-slot emission is
dropped into its column of a lane-dense ``(8, EVENT_BLOCK)`` tile by an
iota select, never by a dynamic lane store.

Bit-identity contract vs the XLA path (pinned by the differential
tests):

- emissions (which events fire, and their counts) are bit-identical:
  counts are exact integer-valued f32 (< 2^24 by the engine's own
  bound) and liveness is a discrete fact both paths agree on;
- live lane values are bit-identical: a live chain's value is the
  armed timestamp plus exactly-representable ``+ 0.0`` hops in both
  formulations, and ``NEG + x == NEG`` exactly for every in-range
  timestamp (f32 absorption at 1e30);
- dead lanes (``<= NEG/2``) may differ bitwise between the tree and
  sequential evaluations — they are unobservable by the engine's own
  contract (every read is thresholded at ``NEG/2``), and the explicit
  ``NEG`` floor below keeps them inside the same dead band the XLA
  ``max`` (which always includes ``NEG + v[0] == NEG``) guarantees.
"""

from __future__ import annotations

from typing import Dict, Tuple

SUBLANES = 8
LANES = 128
EVENT_BLOCK = 128

_cache: Dict[Tuple, object] = {}


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _build(Hp, n_p, S, neg, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    # a python float (ops/nfa_scan.NEG), not np.float32: a strongly-typed
    # scalar closed over by the fori_loop body becomes a jaxpr *const*
    # (Pallas rejects captured constants); a weak python float stays a
    # literal and promotes to f32 against the f32 carries
    NEG = neg
    NB = EVENT_BLOCK

    def kernel(x_ref, v_ref, c_ref, vout_ref, cout_ref, emit_ref, vs, cs):
        @pl.when(pl.program_id(1) == 0)
        def _load_carry():
            vs[...] = v_ref[...]
            cs[...] = c_ref[...]

        lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
        lane0 = lane == 0
        lane1 = lane == 1
        last = lane == S - 1
        chain = lane < S
        col = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, NB), 1)

        def body(e, carry):
            v, c, em_tile = carry
            x = x_ref[e]  # (8, 128): one event of each slot
            f = x > 0.5  # lane i: filter F_i   (lane 0 unused)
            fip1 = pltpu.roll(x, LANES - 1, 1) > 0.5  # lane i: F_{i+1}
            ts1 = pltpu.roll(x, LANES - S, 1)  # lane 1: the timestamp
            v_sh = pltpu.roll(v, 1, 1)  # lane i: v[i-1]
            c_sh = pltpu.roll(c, 1, 1)

            # emission is decided on the PRE-update vectors, exactly as
            # the XLA path reads before_v/before_c: lane S-1 holds the
            # chains one accepted event from completing
            em = jnp.where(last & fip1 & (v > NEG / 2), c, 0.0)
            em_col = jnp.sum(em, axis=1, keepdims=True)  # (8, 1)
            em_tile = jnp.where(col == e, em_col, em_tile)

            # lane i advance-in term: F_i ? (i==1 ? ts : v[i-1]) : NEG+v[i-1]
            term1 = jnp.where(f, jnp.where(lane1, ts1, v_sh), NEG + v_sh)
            # lane i keep term: F_{i+1} ? NEG+v[i] : v[i]
            term2 = jnp.where(fip1, NEG + v, v)
            nv = jnp.maximum(jnp.maximum(term1, term2), NEG)
            nv = jnp.where(lane0, 0.0, nv)

            nc = jnp.where(f, c_sh, 0.0) + jnp.where(fip1, 0.0, c)
            nc = jnp.where(lane0, 1.0, nc)
            return (jnp.where(chain, nv, NEG), jnp.where(chain, nc, 0.0),
                    em_tile)

        v_fin, c_fin, em_tile = jax.lax.fori_loop(
            0, NB, body,
            (vs[...], cs[...], jnp.zeros((SUBLANES, NB), f32)))
        vs[...] = v_fin
        cs[...] = c_fin
        vout_ref[...] = v_fin
        cout_ref[...] = c_fin
        emit_ref[...] = em_tile

    carry = pl.BlockSpec((SUBLANES, LANES), lambda h, k: (h, 0))
    return pl.pallas_call(
        kernel,
        grid=(Hp // SUBLANES, n_p // NB),
        in_specs=[
            pl.BlockSpec((NB, SUBLANES, LANES), lambda h, k: (k, h, 0)),
            carry,
            carry,
        ],
        out_specs=[
            carry,
            carry,
            pl.BlockSpec((SUBLANES, NB), lambda h, k: (h, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Hp, LANES), f32),
            jax.ShapeDtypeStruct((Hp, LANES), f32),
            jax.ShapeDtypeStruct((Hp, n_p), f32),
        ],
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), f32),
                        pltpu.VMEM((SUBLANES, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )


def _call(H, n, S, neg):
    from siddhi_tpu.kernels import probe

    key = (_round_up(H, SUBLANES), _round_up(n, EVENT_BLOCK), S, neg,
           probe.interpret_mode())
    call = _cache.get(key)
    if call is None:
        call = _cache[key] = _build(*key)
    return call


def fused_scan(jax, jnp, F, ts_rel, v, c, neg):
    """Run the fused chain: ``F [H,n,S+1] f32``, ``ts_rel/v/c`` as the
    XLA path holds them → ``(v' [H,S], c' [H,S], emit [H,n])``."""
    H, n, Sp1 = F.shape
    S = Sp1 - 1
    Hp, n_p = _round_up(H, SUBLANES), _round_up(n, EVENT_BLOCK)
    # padded events carry all-false filters (a no-op step), padded
    # slots an empty carry; lanes >= S are scrubbed in the kernel
    x = jnp.concatenate([F, ts_rel[:, :, None]], axis=2).transpose(1, 0, 2)
    x = jnp.pad(x, ((0, n_p - n), (0, Hp - H), (0, LANES - S - 2)))
    pad = ((0, Hp - H), (0, LANES - S))
    nv, nc, emit = _call(H, n, S, neg)(
        x, jnp.pad(v, pad, constant_values=neg), jnp.pad(c, pad))
    return nv[:H, :S], nc[:H, :S], emit[:H, :n]


def smoke_compile(S, H, neg):
    """Compile one fused scan end to end; raise on failure with the
    compiler's message.  One event block is enough: block shapes do not
    depend on the cycle length, so the body Mosaic accepts here is the
    body every cycle runs."""
    import jax
    import numpy as np

    f32 = np.float32
    Hp = _round_up(H, SUBLANES)
    jax.jit(_call(H, EVENT_BLOCK, S, neg)).lower(
        jax.ShapeDtypeStruct((EVENT_BLOCK, Hp, LANES), f32),
        jax.ShapeDtypeStruct((Hp, LANES), f32),
        jax.ShapeDtypeStruct((Hp, LANES), f32),
    ).compile()
