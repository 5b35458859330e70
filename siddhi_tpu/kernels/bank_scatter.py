"""Collision-free segmented reduce for the aggregation device bank.

The bank's XLA path scatters every event at its assigned row with
``.at[rows].add/min/max`` — on TPU, colliding indices (hot keys) are
applied as serialized collision rounds inside the scatter.  This
kernel computes the same per-row reduction as a dense one-hot
compare-and-reduce over an (events × rows) tile grid: every event
block contributes to every row block exactly once, so a million events
on one key cost the same as a million events on a million keys.

Contract vs the XLA scatter: int32 lanes and min/max lanes are
bit-identical (order-free).  f32 *sums* may associate differently than
the scatter's collision rounds; the bank only routes integer-valued
f32 lanes through exactness-sensitive tests, and ``COUNT_EXACT_MAX``
already bounds exact counting, so the documented contract is
unchanged.

Grid layout: ``(row_blocks, event_blocks)`` with the row axis
outermost, so each ``[1, RB]`` output block is initialized once (at
``eb == 0``) and then revisited by every event block in sequence.
Events ride the sublane axis as ``[n, 1]`` columns; the one-hot
compare broadcasts them against the row ids on the lane axis.
"""

from __future__ import annotations

from typing import Dict, Tuple

EVENT_BLOCK = 512
ROW_BLOCK = 256

_cache: Dict[Tuple, object] = {}


def pad_rows(r: int) -> int:
    """Round a row count up to a whole number of row blocks."""
    return max(ROW_BLOCK, ((r + ROW_BLOCK - 1) // ROW_BLOCK) * ROW_BLOCK)


def _build(n_pad, r_pad, dtype_name, op, identity, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)
    EB = EVENT_BLOCK
    RB = ROW_BLOCK
    grid = (r_pad // RB, n_pad // EB)

    def kernel(rows_ref, vals_ref, out_ref):
        rb = pl.program_id(0)
        eb = pl.program_id(1)

        @pl.when(eb == 0)
        def _init():
            out_ref[...] = jnp.full((1, RB), identity, dtype)

        r = rows_ref[...]  # [EB, 1] int32
        v = vals_ref[...]  # [EB, 1]
        row_ids = rb * RB + jax.lax.broadcasted_iota(jnp.int32, (EB, RB), 1)
        onehot = r == row_ids  # [EB, RB] via lane broadcast
        contrib = jnp.where(onehot, v, jnp.asarray(identity, dtype))
        if op in ("sum", "count"):
            out_ref[...] = out_ref[...] + jnp.sum(
                contrib, axis=0, keepdims=True
            )
        elif op == "min":
            out_ref[...] = jnp.minimum(
                out_ref[...], jnp.min(contrib, axis=0, keepdims=True)
            )
        else:
            out_ref[...] = jnp.maximum(
                out_ref[...], jnp.max(contrib, axis=0, keepdims=True)
            )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((EB, 1), lambda rb, eb: (eb, 0)),
            pl.BlockSpec((EB, 1), lambda rb, eb: (eb, 0)),
        ],
        out_specs=pl.BlockSpec((1, RB), lambda rb, eb: (0, rb)),
        out_shape=jax.ShapeDtypeStruct((1, r_pad), dtype),
        interpret=interpret,
    )


def segmented_reduce(rows, vals, r_pad, op, identity, interpret):
    """Per-row reduction delta: (``rows [n]``, ``vals [n]``) → ``[r_pad]``.

    Events are padded here to a whole number of event blocks with a row
    id no block owns and ``identity`` as the value, so the block shapes
    — and the body Mosaic compiles — do not depend on ``n``.  The
    result is the reduction of each row's events against ``identity`` —
    the caller combines it with the live accumulator (``+`` for sums,
    ``min``/``max`` for extrema).
    """
    import jax.numpy as jnp

    n = int(rows.shape[0])
    n_pad = -(-n // EVENT_BLOCK) * EVENT_BLOCK
    if n_pad != n:
        rows = jnp.pad(rows, (0, n_pad - n), constant_values=-1)
        vals = jnp.pad(vals, (0, n_pad - n), constant_values=identity)
    key = (n_pad, int(r_pad), str(vals.dtype), op, interpret)
    call = _cache.get(key)
    if call is None:
        call = _build(*key[:2], key[2], op, identity, interpret)
        _cache[key] = call
    out = call(rows.reshape(-1, 1), vals.reshape(-1, 1))
    return out[0]
