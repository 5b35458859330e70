"""Device-resident accumulator rows for incremental-aggregation ingest.

``DeviceBucketBank`` keeps the float base fields (sum/min/max over
FLOAT/DOUBLE arguments) of RUNNING buckets of the finest duration as
device-resident float32 rows.  Ingest scatters each micro-batch into
the rows in place with one jitted ``.at[rows].add/min/max`` — nothing
crosses the device boundary per batch.  Rows materialize to the host
bucket store only at flush barriers: watermark rollover (``_advance``),
pull queries (``find``), snapshot/restore, and row-capacity pressure.

This is the ingest-side completion of the async pipeline: the emit
queue (core/emit_queue.py) keeps match OUTPUT device-resident between
barriers; the bank does the same for aggregation STATE, so tpu-mode
ingest performs no per-batch device→host flush (the former
``_device_reduce`` fetched a [U] reduction every batch).

Precision: float rows are float32 — the device lane policy shared with
every other jitted path (ops/device_query.py docstring).  Two integer
shapes ride the bank exactly:

* count denominators of avg- or stdDev-bearing selects (avg rewrites
  to sum + count, stdDev to sum + sumsq + count — the sumsq row is a
  DOUBLE "sum"-op field and banks like any other float sum) ride as
  float32 add rows, exact below 2**24; ``count_overflow_risk`` lets
  the runtime force a flush barrier before any row could cross that
  bound, and the flush merge casts count values back to exact ints
  (aggregation/runtime.py ``_flush_bank``).

* LONG "sum" fields (``sum(intcol)`` widens INT→LONG) ride as a
  hi/lo int32 PAIR of rows: hi accumulates ``v >> 16`` and lo
  ``v & 0xFFFF`` (identities 0), and the flush merge recombines
  ``hi * 65536 + lo`` — exact for signed values because arithmetic
  shift/mask are two's-complement floor-div/mod, so
  ``v == (v >> 16) * 65536 + (v & 0xFFFF)`` and addition distributes
  over the split.  ``long_overflow_risk`` bounds both int32 lanes
  conservatively (lo grows ≤ 65535 per event; hi by the batch's max
  magnitude) and forces a flush barrier — or, for a single batch whose
  values are alone too hot for int32, the exact host path — before
  either lane could wrap.

* INT "min"/"max" fields ride as single int32 rows at native width
  (INT is exactly int32), with the int32 extrema as identities; the
  flush merge reads them back as exact ints.

* LONG "min"/"max" fields ride as a LEXICOGRAPHIC hi/lo int32 pair:
  hi is the signed high word (``v >> 32``) and lo the bias-signed low
  word (``(v & 0xFFFFFFFF) - 2**31`` — signed int32 compare of the
  biased value equals unsigned compare of the raw low bits), so
  comparing (hi, lo) pairs lexicographically is the exact signed
  64-bit compare.  The scatter updates the pair in two passes — hi
  extrema first, then lo extrema among events whose hi TIES the new
  per-row hi — and the flush merge recombines
  ``hi * 2**32 + (lo + 2**31)`` exactly.  Extrema never accumulate,
  so no overflow guard is needed (``long_overflow_risk`` watches only
  the sum pairs).

* bare "count" fields (no avg/stdDev rewrite) ride exactly like the
  avg/stdDev count denominators — float32 add rows guarded by
  ``count_overflow_risk`` — so a count-only select no longer forces
  the host reduction.

Remaining integer shapes (last/set) keep the exact host numpy scatter
ufuncs at native width.

Row layout: ``cap`` assignable rows + one dump row (index ``cap``) that
absorbs padded lanes and out-of-order events, which take the host
merge path instead (aggregation/runtime.py ``_merge_out_of_order``).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from siddhi_tpu.query_api import AttrType

_IDENTITY = {"sum": 0.0, "count": 0.0, "min": np.inf, "max": -np.inf}

# int32 lane identities: 0 for the LONG-sum hi/lo pairs, the int32
# extrema for INT min/max rows (padded lanes leave the dump row intact)
_I32_IDENTITY = {"sum": 0, "count": 0,
                 "min": np.iinfo(np.int32).max,
                 "max": np.iinfo(np.int32).min}

# float32 holds consecutive integers exactly up to 2**24: the largest
# count any bank row may accumulate between flushes
COUNT_EXACT_MAX = 1 << 24

# LONG sums split per event into hi = v >> 16 (signed) and
# lo = v & 0xFFFF (in [0, 65535]); each lane accumulates in int32 and
# the flush merge recombines hi * 65536 + lo exactly
_LONG_LO_BITS = 16
_LONG_LO_MAX = (1 << _LONG_LO_BITS) - 1
_I32_MAX = (1 << 31) - 1


class DeviceBucketBank:
    """Device rows for the float base fields of running finest buckets.

    ``fields``: the eligible BaseFields (op in sum/min/max over float
    arguments — including the stdDev sumsq row — LONG sums, plus the
    count denominator of avg- or stdDev-bearing selects).
    One [cap+1] float32 device array per field — except LONG sums,
    which own a hi/lo int32 PAIR of [cap+1] arrays; ``rows`` maps
    (bucket_start, group_key) -> row index shared by every lane.
    """

    def __init__(self, fields, cap: int = 4096):
        self.fields = list(fields)
        self.names: List[str] = [f.name for f in self.fields]
        self.ops: Tuple[str, ...] = tuple(f.op for f in self.fields)
        self.cap = int(cap)
        self.rows: Dict[Tuple[int, Tuple], int] = {}
        self._free: List[int] = list(range(self.cap))
        self._arrays = None  # per-lane jnp [cap+1]; lazy (jax import)
        self._scatter = None
        # lane plan: each field owns one float32 row, except LONG sums
        # and LONG extrema which own an exact hi/lo int32 pair
        # (module docstring)
        self._lanes: List[Tuple[str, str]] = []  # (op, "f32"|"i32")
        self._field_lanes: List[Tuple[int, ...]] = []
        for f in self.fields:
            if f.op == "sum" and f.type == AttrType.LONG:
                self._field_lanes.append((len(self._lanes),
                                          len(self._lanes) + 1))
                self._lanes += [("sum", "i32"), ("sum", "i32")]
            elif f.op in ("min", "max") and f.type == AttrType.LONG:
                # LONG extrema: lexicographic hi/lo int32 pair — exact
                # signed compare at full 64-bit width (module docstring)
                self._field_lanes.append((len(self._lanes),
                                          len(self._lanes) + 1))
                self._lanes += [(f.op, "i32"), (f.op, "i32")]
            elif f.op in ("min", "max") and f.type == AttrType.INT:
                # INT extrema fit int32 natively — exact, no pair split
                self._field_lanes.append((len(self._lanes),))
                self._lanes.append((f.op, "i32"))
            else:
                self._field_lanes.append((len(self._lanes),))
                self._lanes.append((f.op, "f32"))
        # LONG-sum pairs only: extrema pairs never accumulate, so they
        # need no overflow guard and no recombine-by-65536
        self.long_names: List[str] = [
            f.name for f, ln in zip(self.fields, self._field_lanes)
            if len(ln) == 2 and f.op == "sum"
        ]
        # flush-barrier evidence for tests/bench: ingest batches absorbed
        # on device vs host materializations
        self.scatters = 0
        self.flushes = 0
        # events scattered since the last flush: upper-bounds the count
        # any single row may have accumulated (count rows are float32,
        # exact only below COUNT_EXACT_MAX) and the lo int32 lane of a
        # LONG sum (each event adds at most _LONG_LO_MAX)
        self._has_count = "count" in self.ops
        self.events_since_flush = 0
        # per-LONG-field conservative bound on |hi| accumulated since
        # the last flush (long_overflow_risk)
        self._long_hi_used: Dict[str, int] = {}

    @property
    def dump_row(self) -> int:
        return self.cap

    def count_overflow_risk(self, n: int) -> bool:
        """True when scattering ``n`` more events could push a float32
        count row past exact-integer territory — the caller must flush
        first.  Always False when no count field is banked."""
        return (self._has_count
                and self.events_since_flush + n > COUNT_EXACT_MAX)

    @staticmethod
    def _hi_bound(v: np.ndarray, n: int) -> int:
        """Conservative bound on the |hi| lane growth one batch can
        cause in any single row: every event at the batch's max
        magnitude landing on one bucket.  Python ints — no int64
        overflow on extreme inputs."""
        m = max(abs(int(v.max())), abs(int(v.min())))
        return n * ((m >> _LONG_LO_BITS) + 1)

    def long_overflow_risk(self, fvals: Dict[str, np.ndarray],
                           n: int) -> bool:
        """True when scattering ``n`` more events with these values
        could wrap either int32 lane of a LONG-sum pair row — the
        caller must flush first (and if one batch is alone too hot,
        fall back to the exact host path for the batch).  Always False
        when no LONG sum is banked."""
        if not self.long_names:
            return False
        if (self.events_since_flush + n) * _LONG_LO_MAX > _I32_MAX:
            return True
        return any(
            self._long_hi_used.get(name, 0)
            + self._hi_bound(fvals[name], n) > _I32_MAX
            for name in self.long_names
        )

    # -- device arrays -------------------------------------------------------

    def _ensure_arrays(self):
        if self._arrays is not None:
            return
        import jax.numpy as jnp

        self._arrays = [
            jnp.full(self.cap + 1, _I32_IDENTITY[op], dtype=jnp.int32)
            if kind == "i32"
            else jnp.full(self.cap + 1, _IDENTITY[op], dtype=jnp.float32)
            for op, kind in self._lanes
        ]

    def _scatter_fn(self):
        if self._scatter is None:
            import jax
            import jax.numpy as jnp

            lanes = tuple(self._lanes)
            # hi-lane index -> op for LONG extrema pairs: their two
            # lanes update together lexicographically, unlike the
            # LONG-sum pairs whose lanes stay independent adds
            pair_ops: Dict[int, str] = {}
            for fi, fl in enumerate(self._field_lanes):
                if len(fl) == 2 and self.ops[fi] in ("min", "max"):
                    pair_ops[fl[0]] = self.ops[fi]

            def upd(a, rows, v, op):
                if op in ("sum", "count"):
                    return a.at[rows].add(v)
                return a.at[rows].min(v) if op == "min" else (
                    a.at[rows].max(v))

            def pair_update(a_hi, a_lo, rows, vh, vl, op):
                # lexicographic (hi, lo) extrema: hi decides; lo
                # competes only where its hi TIES the row's new hi
                # winner.
                ident = _I32_IDENTITY[op]
                new_hi = upd(a_hi, rows, vh, op)
                cand = jnp.where(vh == new_hi[rows], vl, ident)
                base = jnp.where(a_hi == new_hi, a_lo, ident)
                return new_hi, upd(base, rows, cand, op)

            def fn(arrays, rows, vals):
                out = list(arrays)
                li = 0
                while li < len(lanes):
                    if li in pair_ops:
                        out[li], out[li + 1] = pair_update(
                            arrays[li], arrays[li + 1], rows,
                            vals[li], vals[li + 1], pair_ops[li])
                        li += 2
                        continue
                    out[li] = upd(arrays[li], rows, vals[li], lanes[li][0])
                    li += 1
                return out

            self._scatter = jax.jit(fn)
        return self._scatter

    # -- row assignment ------------------------------------------------------

    def assign(self, keys) -> bool:
        """Reserve a row per key (idempotent for known keys).  Returns
        False when the free list cannot cover the new keys — the caller
        flushes (a capacity barrier) and retries, or falls back to the
        host path for the batch."""
        fresh = [k for k in keys if k not in self.rows]
        if len(fresh) > len(self._free):
            return False
        for k in fresh:
            self.rows[k] = self._free.pop()
        return True

    def scatter(self, ev_rows: np.ndarray, fvals: Dict[str, np.ndarray]):
        """Accumulate one micro-batch in place: ``ev_rows`` [n] row per
        event (``dump_row`` for events that take the host path),
        ``fvals`` the per-event value columns keyed by field name.  Rows
        are padded to a power of two so the jitted scatter sees a
        bounded shape variety; padded lanes target the dump row with the
        op identity."""
        import jax.numpy as jnp

        self._ensure_arrays()
        n = len(ev_rows)
        n_pad = max(1 << max(n - 1, 1).bit_length(), 256)
        rows_p = np.full(n_pad, self.dump_row, dtype=np.int32)
        rows_p[:n] = ev_rows
        vals = []
        for fi, (name, op) in enumerate(zip(self.names, self.ops)):
            lanes = self._field_lanes[fi]
            if len(lanes) == 2 and op == "sum":
                # LONG sum: exact signed hi/lo split (padded lanes add
                # the identity 0 to the dump row)
                v = np.asarray(fvals[name]).astype(np.int64)
                hi = np.zeros(n_pad, dtype=np.int32)
                lo = np.zeros(n_pad, dtype=np.int32)
                hi[:n] = (v >> _LONG_LO_BITS).astype(np.int32)
                lo[:n] = (v & _LONG_LO_MAX).astype(np.int32)
                vals += [jnp.asarray(hi), jnp.asarray(lo)]
                self._long_hi_used[name] = (
                    self._long_hi_used.get(name, 0) + self._hi_bound(v, n))
            elif len(lanes) == 2:
                # LONG extrema: lexicographic split — signed high word,
                # bias-signed low word (signed int32 compare of the
                # biased lo == unsigned compare of the raw low bits)
                v = np.asarray(fvals[name]).astype(np.int64)
                hi = np.full(n_pad, _I32_IDENTITY[op], dtype=np.int32)
                lo = np.full(n_pad, _I32_IDENTITY[op], dtype=np.int32)
                hi[:n] = (v >> 32).astype(np.int32)
                lo[:n] = ((v & 0xFFFFFFFF) - (1 << 31)).astype(np.int32)
                vals += [jnp.asarray(hi), jnp.asarray(lo)]
            elif self._lanes[lanes[0]][1] == "i32":
                # single int32 lane (INT min/max): native-width exact
                col = np.full(n_pad, _I32_IDENTITY[op], dtype=np.int32)
                col[:n] = fvals[name].astype(np.int32)
                vals.append(jnp.asarray(col))
            else:
                col = np.full(n_pad, _IDENTITY[op], dtype=np.float32)
                col[:n] = fvals[name].astype(np.float32)
                vals.append(jnp.asarray(col))
        self._arrays = self._scatter_fn()(
            self._arrays, jnp.asarray(rows_p), vals)
        self.scatters += 1
        self.events_since_flush += n

    # -- flush barriers ------------------------------------------------------

    def flush(self) -> Dict[Tuple[int, Tuple], Dict[str, float]]:
        """Materialize every assigned row to host and reset the bank:
        one coalesced device fetch, called only at barriers (rollover,
        find, snapshot, capacity pressure).  Returns
        {bucket_key: {field_name: value}}."""
        if not self.rows:
            return {}
        import jax

        host = [np.asarray(a) for a in jax.device_get(self._arrays)]
        out: Dict[Tuple[int, Tuple], Dict[str, float]] = {}
        for key, row in self.rows.items():
            values: Dict[str, float] = {}
            for fi, name in enumerate(self.names):
                lanes = self._field_lanes[fi]
                if len(lanes) == 2 and self.ops[fi] == "sum":
                    # exact int recombination of the sum hi/lo pair
                    values[name] = (
                        int(host[lanes[0]][row]) * (_LONG_LO_MAX + 1)
                        + int(host[lanes[1]][row]))
                elif len(lanes) == 2:
                    # lexicographic extrema pair: undo the bias split
                    values[name] = (
                        int(host[lanes[0]][row]) * (1 << 32)
                        + (int(host[lanes[1]][row]) + (1 << 31)))
                elif self._lanes[lanes[0]][1] == "i32":
                    values[name] = int(host[lanes[0]][row])
                else:
                    values[name] = float(host[lanes[0]][row])
            out[key] = values
        self.flushes += 1
        self.clear()
        return out

    def clear(self):
        """Drop all rows and device arrays (restore path: the host
        snapshot is the single source of truth)."""
        self.rows.clear()
        self._free = list(range(self.cap))
        self._arrays = None
        self.events_since_flush = 0
        self._long_hi_used.clear()
