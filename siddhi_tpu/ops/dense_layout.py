"""Resident layout of the dense pattern state — the one file that knows it.

**Logical** state (what the automaton, the host engine's parity tests
and every snapshot speak): per partition row ``active`` [S, I] bool,
``first_ts`` / ``counts`` [S, I] int32, ``regs`` [S, I, R] float32,
optionally ``iregs`` [S, I, 2*RI] int32 and ``deadline`` [S, I] int32,
plus one ``overflow`` int32 counter.

**Physical** state (what lives in HBM and what the jitted steps take
and donate): ``{"rows": int32[N, *row_shape], "overflow": int32[N]}``.
A partition is ONE contiguous row of W 32-bit words, the partition axis
major, W a multiple of 128 lanes: the fields above, flattened in the
order listed and bit-cast to int32 (``active`` one 0/1 word per lane),
then zero padding up to W.  ``row_shape`` follows the write-back: it is
``(W // 128, 128)`` where the row-scatter kernel will write the rows (a
row wider than one vector of lanes, the state on one chip) and ``(W,)``
wherever XLA's scatter will (a row of one vector; a state sharded over
a mesh, whose step runs under ``shard_map``, where the kernel has never
run).  A TPU tiles the two minor dimensions of an array: ``[N, 256]``
in tiles of 8 rows by 128 words, which puts the two halves of a row
4 KB apart, and ``[N, 2, 128]`` in tiles of 2 by 128, which is the row
itself, 1,024 bytes on end, and what one DMA can name
(``kernels/row_scatter.py``); XLA's own scatter is slower on that shape
than on ``[N, 256]`` (8.4 against 4.7 ms for 131,072 rows, PERF.md
section 6, PR 58), so a state the kernel will not write keeps the flat
one.  A batch is then one gather of B rows and one scatter of B rows;
the fields are split out of the gathered rows (a relayout of B rows,
never of N), and the donated ``rows`` array is updated in place.
Trailing dims of 16 x 4 as separate ``[N, S, I]`` arrays made XLA put the
partition axis on the lanes and transpose the whole state twice a step
(PERF.md, PR 27).  The way back, ``join``, is the split's mirror and
costs three passes over the batch's rows where the chip owes one: the
automaton leaves a field batch-on-lanes with its 4 instance lanes on the
sublanes, and XLA re-tiles each field to tiles of 8 (a ``reshape``),
transposes it (a ``copy``) and joins the vectors by a padded add.  No
form of it written in XLA saves half a millisecond of the 3.1 it takes
on 2,048-byte rows (PERF.md section 6, PR 61: the fields concatenated
along the node axis first, -0.2 to -0.3 ms of the step; a preallocated
buffer under ``dynamic_update_slice``, flat fields and an explicit
transposition, slower or level), so it stands as it was; a Pallas kernel
does it in one pass (0.75 ms, -1.8 ms of the step) and is the next
issue's, with the split's.

W and the offsets follow from S, I and the register allocator alone;
no app, annotation or option selects anything here.  ``overflow`` stays
its own vector: ``overflow_total`` sums it without touching the rows.
**It is written only by a batch that dropped an instance** (``scatter``:
the add stands behind a conditional on the batch's own increments; a
batch inside its ``instances``, which is every batch of a deployment
that heeds the overflow warning, reads their reduction and hands the
donated vector on untouched, where the add of 131,072 zeros took 0.92
ms of every step on a v5e, PERF.md section 6, PR 61).

A **snapshot** is the logical state, made on the device: ``logical``
traces the split of every row into fresh arrays (the steps donate
``rows``, so a reference to the resident state would not outlive the
next batch), each a :class:`SnapshotField`, and the host only ever
receives bytes it writes out as they are.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from siddhi_tpu.util.faults import host_view

ROWS = "rows"
OVERFLOW = "overflow"
LANES = 128


class SnapshotField:
    """One logical field of a snapshot made on the device: the array as
    the program wrote it, one dimension long, under the logical shape
    its host copy takes.  (A TPU gives a ``[N, S, I]`` result the
    partition axis on its lanes, and the copy to the host would have to
    transpose a gigabyte; one dimension crosses as it lies.)

    To ``durability/capture.py`` it is a device array: it has a shape
    and a dtype and is no numpy type, so a capture keeps it by
    reference and whoever needs the host value asks ``np.asarray``."""

    __slots__ = ("flat", "shape")

    def __init__(self, flat, shape: Tuple[int, ...]):
        self.flat = flat
        self.shape = tuple(shape)

    @property
    def dtype(self):
        return self.flat.dtype

    @property
    def nbytes(self) -> int:
        return self.flat.nbytes

    def __len__(self) -> int:
        return self.shape[0]

    def __array__(self, dtype=None, copy=None):
        # the host copy JAX keeps, waited for here: read-only, no copy
        out = host_view(self.flat).reshape(self.shape)
        return out if dtype is None else out.astype(dtype)


class DenseStateLayout:
    """Offsets of every logical field inside a partition's row, and the
    conversions between the logical and the physical form: ``pack`` /
    ``unpack`` on the host (numpy), ``logical`` for a whole state on the
    device, ``split`` / ``join`` on gathered rows inside a jitted step,
    ``words`` / ``decode`` / ``with_field`` for callers that read or
    write single fields of a few rows."""

    def __init__(self, S: int, I: int, n_regs: int, n_iregs: int,
                 has_deadlines: bool, armed_start: bool,
                 sharded: bool = False):
        self.S, self.I = S, I
        # name -> (logical dtype, trailing logical shape)
        self.fields: Dict[str, Tuple[np.dtype, Tuple[int, ...]]] = {
            "active": (np.dtype(bool), (S, I)),
            # relative ms since the engine's base_ts (int32: ~24 days of
            # horizon), 0 == unset
            "first_ts": (np.dtype(np.int32), (S, I)),
            "counts": (np.dtype(np.int32), (S, I)),
            "regs": (np.dtype(np.float32), (S, I, max(n_regs, 1))),
        }
        if n_iregs:
            # integer capture bank: hi/lo int32 pair per slot
            self.fields["iregs"] = (np.dtype(np.int32), (S, I, 2 * n_iregs))
        if has_deadlines:
            # absent-node deadlines (relative ms; 0 == unset)
            self.fields["deadline"] = (np.dtype(np.int32), (S, I))
        self.offsets: Dict[str, Tuple[int, int]] = {}
        off = 0
        for name, (_dt, shape) in self.fields.items():
            w = int(np.prod(shape))
            self.offsets[name] = (off, w)
            off += w
        self.used = off
        self.width = -(-off // LANES) * LANES
        # what a partition's W words look like in the resident array:
        # a vector of lanes at a time where the row-scatter kernel will
        # write them (``scatter``), flat where XLA's scatter will
        self.row_shape = ((self.width,) if self.width == LANES or sharded
                          else (self.width // LANES, LANES))
        # what writes a batch's rows back, "kernel" or "xla": said by
        # ``scatter`` when a program is traced, None until then
        self.scatter_path = None
        # non-every: node 0 armed once per partition (lane 0); after a
        # match reset_on_emit clears it and the automaton is done
        self.armed_start = armed_start

    # -- shapes ---------------------------------------------------------------

    def logical_shapes(self, n_rows: int) -> Dict[str, Tuple[int, ...]]:
        shapes = {k: (n_rows,) + shape for k, (_dt, shape) in
                  self.fields.items()}
        shapes[OVERFLOW] = (n_rows,)
        return shapes

    def physical_shapes(self, n_rows: int) -> Dict[str, Tuple[int, ...]]:
        """Shapes of the physical arrays (both int32), for abstract
        tracing without allocating a state."""
        return {ROWS: (n_rows,) + self.row_shape, OVERFLOW: (n_rows,)}

    def pspecs(self, axis: str):
        """Partition-axis sharding spec per physical array (row-sharded,
        the words of a row stay together)."""
        from jax.sharding import PartitionSpec as Pspec

        return {ROWS: Pspec(axis, *(None,) * len(self.row_shape)),
                OVERFLOW: Pspec(axis)}

    # -- host side (numpy) ----------------------------------------------------

    def init_logical(self, n_rows: int) -> Dict[str, np.ndarray]:
        state = {k: np.zeros((n_rows,) + shape, dtype=dt)
                 for k, (dt, shape) in self.fields.items()}
        if self.armed_start:
            state["active"][:, 0, 0] = True
        # per-partition dropped-instance count (successor slots full)
        state[OVERFLOW] = np.zeros(n_rows, dtype=np.int32)
        return state

    def init_physical(self, n_rows: int) -> Dict[str, np.ndarray]:
        rows = np.zeros((n_rows, self.width), dtype=np.int32)
        if self.armed_start:
            rows[:, self.offsets["active"][0]] = 1
        return {ROWS: self._physical(rows),
                OVERFLOW: np.zeros(n_rows, dtype=np.int32)}

    def init_device(self, n_rows: int):
        """``init_physical`` made on the device: one row broadcast by a
        program, where the host's gigabyte would cross in seconds."""
        import jax
        import jax.numpy as jnp

        row = self.init_physical(1)[ROWS][0]
        return {ROWS: jax.jit(lambda r: jnp.broadcast_to(
                    r, (n_rows,) + r.shape))(row),
                OVERFLOW: jnp.zeros(n_rows, dtype=jnp.int32)}

    def _physical(self, flat):
        """Rows ``[..., W]`` (host or device) in the resident shape."""
        if len(self.row_shape) == 1:
            return flat
        return flat.reshape(flat.shape[:-1] + self.row_shape)

    def _flat(self, rows):
        """A few resident rows ``[..., *row_shape]`` as ``[..., W]``."""
        if len(self.row_shape) == 1:
            return rows
        return rows.reshape(rows.shape[:-2] + (self.width,))

    def _words(self, rows, off: int, w: int, at=slice(None)):
        """Words ``off : off + w`` of resident device rows
        ``[n, *row_shape]``, of every row as ``[n, w]`` or of the rows
        ``at`` (an index or an array of them), by slices alone: the
        rows, which may be the whole state, are never reshaped and no
        row is gathered whole."""
        import jax.numpy as jnp

        if rows.ndim == 2:
            return rows[at, off:off + w]
        pieces = [rows[at, c, lo:hi] for c, lo, hi in self._pieces(off, w)]
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces,
                                                                  axis=-1)

    @staticmethod
    def _pieces(off: int, w: int):
        """Words ``off : off + w`` of a row as ``(vector, from, to)``
        within each vector of 128 lanes they touch."""
        out = []
        while w > 0:
            c, lo = divmod(off, LANES)
            take = min(w, LANES - lo)
            out.append((c, lo, lo + take))
            off, w = off + take, w - take
        return out

    def encode(self, name: str, value) -> np.ndarray:
        """Logical host array [..., *shape] -> int32 words [..., w]."""
        dt, shape = self.fields[name]
        v = np.ascontiguousarray(np.asarray(value))
        lead = v.shape[:v.ndim - len(shape)]
        flat = v.reshape(lead + (-1,))
        if dt == np.float32:
            return flat.astype(np.float32, copy=False).view(np.int32)
        return flat.astype(np.int32)

    def decode(self, name: str, words) -> np.ndarray:
        """int32 words [..., w] (host) -> logical array [..., *shape]."""
        dt, shape = self.fields[name]
        w = np.ascontiguousarray(np.asarray(words))
        if dt == np.float32:
            out = w.view(np.float32)
        elif dt == np.bool_:
            out = w != 0
        else:
            out = w
        return out.reshape(w.shape[:-1] + shape)

    def pack(self, logical: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Logical host state (the snapshot format) -> physical host
        state.  Raises on a missing or mis-shaped field."""
        n = len(np.asarray(logical[OVERFLOW]))
        want = self.logical_shapes(n)
        rows = np.zeros((n, self.width), dtype=np.int32)
        for name, (off, w) in self.offsets.items():
            got = logical.get(name)
            if got is None or tuple(np.shape(got)) != want[name]:
                raise ValueError(
                    f"dense state field '{name}' has shape "
                    f"{None if got is None else tuple(np.shape(got))}, "
                    f"this engine needs {want[name]}")
            rows[:, off:off + w] = self.encode(name, got)
        return {ROWS: self._physical(rows),
                OVERFLOW: np.asarray(logical[OVERFLOW], dtype=np.int32)}

    def unpack(self, physical) -> Dict[str, np.ndarray]:
        """Physical state (host or device arrays) -> logical host state."""
        rows = self._flat(np.asarray(physical[ROWS]))
        out = {name: self.decode(name, rows[:, off:off + w])
               for name, (off, w) in self.offsets.items()}
        out[OVERFLOW] = np.array(physical[OVERFLOW], dtype=np.int32)
        return out

    # -- the whole state, on the device ---------------------------------------

    def logical(self, state) -> Dict[str, object]:
        """Traced, never donated: physical state -> its logical fields
        as arrays of their own, each flattened to one dimension
        (:class:`SnapshotField` gives them their shape back).  Slices
        and casts over the partition axis alone, so a state sharded by
        rows stays sharded."""
        import jax.numpy as jnp

        out = {name: x.reshape(-1) for name, x in
               self.split(state[ROWS], shaped=False).items()}
        # an output that IS an input would be the donated buffer itself
        out[OVERFLOW] = jnp.array(state[OVERFLOW], copy=True)
        return out

    def snapshot_fields(self, flat: Dict[str, object]) -> Dict[str, SnapshotField]:
        """``logical``'s outputs under their logical shapes."""
        n = flat[OVERFLOW].shape[0]
        shapes = self.logical_shapes(n)
        return {name: SnapshotField(x, shapes[name])
                for name, x in flat.items()}

    # -- single fields of a few rows (eager device ops) -----------------------

    def words(self, state, name: str, rows=None):
        """Device int32 words [..., w] of one field — of the given
        physical row indices, or of every row.  ``decode`` turns the
        fetched words into the logical view."""
        off, w = self.offsets[name]
        return self._words(state[ROWS], off, w,
                           slice(None) if rows is None else rows)

    def field(self, state, name: str, rows=None) -> np.ndarray:
        """Logical host view ``[..., *shape]`` of one field (fetches the
        words of the given rows, or of every row)."""
        return self.decode(name, self.words(state, name, rows))

    def with_field(self, state, name: str, rows, value):
        """``state`` with one logical field of the given physical rows
        replaced by the host array ``value``."""
        import jax.numpy as jnp

        off, w = self.offsets[name]
        r = state[ROWS]
        words = jnp.asarray(self.encode(name, value))
        new = dict(state)
        if r.ndim == 2:
            new[ROWS] = r.at[rows, off:off + w].set(words)
        else:
            # the rows laid flat, the field set, the rows written back
            flat = self._flat(r[rows]).at[..., off:off + w].set(words)
            new[ROWS] = r.at[rows].set(self._physical(flat))
        return new

    # -- inside a jitted step (traced) ----------------------------------------

    def split(self, rows, shaped: bool = True) -> Dict[str, object]:
        """Rows [N, *row_shape] -> logical fields, ``[N, *shape]`` each,
        or flat ``[N, w]`` with ``shaped=False`` (the timer step, which
        runs over every row and must not reshape the partition axis)."""
        import jax
        import jax.numpy as jnp

        out = {}
        for name, (off, w) in self.offsets.items():
            dt, shape = self.fields[name]
            x = self._words(rows, off, w)
            if dt == np.float32:
                x = jax.lax.bitcast_convert_type(x, jnp.float32)
            elif dt == np.bool_:
                x = x != 0
            out[name] = x.reshape((x.shape[0],) + shape) if shaped else x
        return out

    def join(self, fields: Dict[str, object]):
        """Logical fields (shaped or flat) -> rows [N, *row_shape]."""
        import jax
        import jax.numpy as jnp

        parts = []
        n = None
        for name, (_off, w) in self.offsets.items():
            dt, _shape = self.fields[name]
            x = fields[name]
            n = x.shape[0]
            x = x.reshape(n, w)
            if dt == np.float32:
                x = jax.lax.bitcast_convert_type(x, jnp.int32)
            else:
                x = x.astype(jnp.int32)
            parts.append(x)
        if self.width > self.used:
            parts.append(jnp.zeros((n, self.width - self.used), jnp.int32))
        if len(self.row_shape) == 1:
            return jnp.concatenate(parts, axis=1)
        # a vector of 128 lanes at a time, from the pieces of the fields
        # that lie in it: the rows are written in their resident shape
        # and never relaid from [N, W]
        vectors = [[] for _ in range(self.row_shape[0])]
        off = 0
        for x in parts:
            at = 0
            for c, lo, hi in self._pieces(off, x.shape[1]):
                vectors[c].append(x[:, at:at + hi - lo])
                at += hi - lo
            off += x.shape[1]
        return jnp.stack([v[0] if len(v) == 1 else jnp.concatenate(v, axis=1)
                          for v in vectors], axis=1)

    def gather(self, state, part_idx):
        """The batch's rows: ``(fields [B, *shape], old rows
        [B, *row_shape])``.  The gathered rows are laid flat before they
        are split: one relayout of B rows, and the split reads ``[B, W]``
        whatever the resident shape is.  ``old`` feeds ``scatter`` so
        padded batch rows write back exactly what they read.
        ``overflow`` is not gathered: a step only ever adds to it."""
        old = state[ROWS][part_idx]
        return self.split(self._flat(old)), old

    def scatter(self, state, part_idx, fields, ovf_delta, valid, old):
        """Write the batch's rows back in place and add each row's newly
        dropped instances to its ``overflow`` counter.  Invalid (padded)
        batch rows all point at the scratch row: they write back the
        words they gathered (``old``), or nothing at all where the
        row-scatter kernel takes the call, and add 0, so the scratch row
        and every row outside the batch keep their values.

        The ``overflow`` vector is written when a valid lane of this
        call dropped an instance, and then takes the add lane for lane;
        otherwise the conditional's quiet branch hands the donated
        vector on, with no scatter and no copy (the compiled step holds
        it so: ``tests/test_dense_layout_overflow.py``).  What decides
        is the batch's own ``ovf_delta``: in a rounds program's loops
        each round's, under ``shard_map`` each shard's (no collective)."""
        import jax
        import jax.numpy as jnp

        from siddhi_tpu.kernels import row_scatter

        rows = state[ROWS]
        if row_scatter.eligible(rows, part_idx.shape[0]):
            # the kernel starts no copy for a lane on the scratch row:
            # an invalid lane is sent there (the step's and the rounds'
            # lanes are invalid exactly where they name it already) and
            # nothing reads ``old``
            scratch = rows.shape[0] - 1
            self.scatter_path = "kernel"
            rows = row_scatter.row_scatter(
                rows, jnp.where(valid, part_idx, scratch), self.join(fields))
        else:
            # (a width the kernel's loop does not divide, beside wider
            # ones that took it, leaves "kernel" standing)
            self.scatter_path = self.scatter_path or "xla"
            keep = valid[(slice(None),) + (None,) * len(self.row_shape)]
            rows = rows.at[part_idx].set(
                jnp.where(keep, self.join(fields), old))
        dropped = jnp.where(valid, ovf_delta, 0)
        return {
            ROWS: rows,
            OVERFLOW: jax.lax.cond(
                jnp.any(dropped != 0),
                lambda ovf: ovf.at[part_idx].add(dropped),
                lambda ovf: ovf, state[OVERFLOW]),
        }
