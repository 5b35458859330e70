"""Pallas kernel for the write-back of a batch's rows into the resident
dense state: ``rows.at[part_idx].set(new)`` as row copies, many in
flight (``DenseStateLayout.scatter``).

A partition is one row of ``W`` 32-bit words and a batch writes ``B`` of
them back into the donated state.  XLA's scatter takes 36 ns a row for
that on a v5e (4.7 ms for 131,072 rows of 1,024 bytes, 3.5% of what HBM
allows) where its own gather of the same rows takes 12.  Here a row is
one DMA, HBM to HBM, from ``new[b]`` to ``rows[part_idx[b]]``,
``IN_FLIGHT`` of them outstanding on as many DMA semaphores, the state
aliased in and out so that nothing but the batch's rows is touched:
2.4 ms, 18 ns a row (PERF.md section 6, PR 58, holds the table of the
variants: the batch as pipelined VMEM tiles is slower, 3.6).

A row has to lie on end in HBM for one copy to name it: Mosaic slices a
single row out of ``[N, 128]`` and out of ``[N, 2, 128]`` and refuses
``[N, 256]``, whose tiles of 8 rows by 128 words put the halves of a row
4 KB apart.  ``DenseStateLayout.row_shape`` keeps the state in a shape
the kernel can address wherever the kernel will write it, and flat
wherever it will not (a row of one vector; a state sharded over a mesh).

**Precondition: the real rows of one call are distinct.**  Copies in
flight land in any order, so a row named twice would keep either value.
The engine never does that: a round holds a partition once
(``round_plan``; ``tests/test_dense_skew.py`` ``test_round_plan`` holds
every plan to "once a round", ``tests/test_row_scatter.py`` holds it on
random batches).  **A lane whose row is the scratch row (``N - 1``)
starts no copy**, so the scratch row keeps its words whatever ``new``
holds there: ``DenseStateLayout.scatter`` relies on it, names that row
for every invalid lane and hands over their advanced rows unselected.
"""

from __future__ import annotations

#: row copies outstanding at a time (one DMA semaphore each): 4 are too
#: few (7.6 ms for 131,072 rows), 8 to 64 read alike
IN_FLIGHT = 32
#: lanes the loop takes an iteration: the kernel is bound by the scalar
#: core's work a lane (a padded lane costs what a real one does), and 8
#: lanes an iteration take a fifth off it (3.2 -> 2.5 ms)
UNROLL = 8
#: off a TPU the kernel could only run interpreted (exact, and slow to
#: trace): the engine keeps ``.at[].set`` there.  The tests that pin the
#: kernel to it bit for bit set this.
INTERPRET_OFF_TPU = False


def eligible(rows, n_lanes: int) -> bool:
    """Whether a write-back of ``n_lanes`` rows into the traced ``rows``
    goes through the kernel.  Read when the step is traced, from the
    backend and what the trace knows of the operand: nothing is chosen
    by an option.

    Rows of more than one vector of lanes (``[N, W // 128, 128]``) take
    it at every width the loop's stride divides: on a v5e it reads 2.40
    ms at 131,072 lanes, 0.32 at 16,384, 0.018 at 512 and 0.006 at 128,
    where XLA's scatter reads 8.36, 1.07, 0.039 and 0.011 on that shape
    (and 4.71, 1.42, 0.047, 0.013 on ``[N, 256]``).  Rows of one vector
    (``[N, 128]``) keep XLA's: at the batch's 131,072 lanes it is as
    fast (2.29 against 2.32), and ``cardfraud_100k.saturated`` lost 9%
    with the kernel in its step and loops.  A state sharded over a mesh
    is flat too (``DenseStateLayout.row_shape``) and keeps XLA's inside
    ``shard_map``: the kernel compiles there and was never run on more
    than one chip, and XLA's scatter is the slower one on
    ``[N, 2, 128]`` (PERF.md section 6, PR 58 and PR 59)."""
    from siddhi_tpu.kernels import probe

    if probe.interpret_mode() and not INTERPRET_OFF_TPU:
        return False
    return rows.ndim == 3 and n_lanes % UNROLL == 0


def row_scatter(rows, part_idx, new):
    """``rows [N, ...] i32`` with ``rows[part_idx[b]] = new[b]`` for
    every lane whose row is not ``N - 1``; ``part_idx [B] i32`` in
    ``[0, N)``, ``new [B, ...] i32``, ``B`` a multiple of ``UNROLL``.
    ``rows`` is aliased to the result: donated (or dead) in the caller,
    it is updated in place."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from siddhi_tpu.kernels import probe

    N = rows.shape[0]
    B = part_idx.shape[0]
    K = min(IN_FLIGHT, B)
    scratch = N - 1

    def kernel(idx_ref, new_ref, _rows_in, rows_ref, sems):
        def of_real(b, then):
            """``then`` on lane ``b``'s copy, if the lane has one."""
            row = idx_ref[b]

            @pl.when(row != scratch)
            def _():
                then(pltpu.make_async_copy(
                    new_ref.at[pl.ds(b, 1)], rows_ref.at[pl.ds(row, 1)],
                    sems.at[b % K]))

        def lanes(i, carry):
            for u in range(UNROLL):
                b = i * UNROLL + u
                # lane b takes the semaphore of lane b - K: that copy
                # first, if the lane started one
                pl.when(b >= K)(lambda: of_real(b - K, lambda c: c.wait()))
                of_real(b, lambda c: c.start())
            return carry

        jax.lax.fori_loop(0, B // UNROLL, lanes, 0)
        for b in range(B - K, B):
            of_real(b, lambda c: c.wait())

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((K,))]),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        # operands count the scalar-prefetched index: rows is the third
        input_output_aliases={2: 0},
        interpret=probe.interpret_mode(),
    )(part_idx.astype(jnp.int32), new, rows)
