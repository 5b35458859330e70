"""Partition-key value -> engine row: the lookup side of key interning.

Two indexes with one interface, chosen by the dtype kind of the key
batches a runtime observes (``core/dense_pattern.py`` ``_intern``):

- ``HashKeyIndex``: integer keys.  An open-addressing table (linear
  probe) in plain numpy, so a warm batch resolves with one gather and
  no sort: O(n) whatever the number of known keys.
- ``SortedKeyIndex``: every other sortable family (strings, floats):
  the batch is factorized (``np.unique``) and binary-searched against
  the sorted array of known keys.

Both are rebuildable caches of the runtime's ``_key_rows`` dict, which
stays the truth for purges; a snapshot takes ``items()``, the known keys
and their rows as two vectors, and never walks the dict.  Neither
allocates rows:

- ``lookup(keys)`` -> ``(rows, new_keys, probed)``: the int32 row of
  each lane; the never-seen keys, sorted ascending and unique; the
  lanes that probed past their first slot (0 for the sorted index).  A
  lane whose key is ``new_keys[j]`` holds ``-1 - j`` in ``rows``;
- the runtime gives ``new_keys`` rows and ``insert(new_keys, rows)``
  records them (unique keys the index does not hold);
- ``items()`` -> ``(keys, rows)``: every key the index holds, in its
  dtype, beside its int32 row.  What it returns is never written
  again (the hash index's log only grows, the sorted index replaces
  its arrays), so a snapshot may keep it as it is.

Nothing is ever deleted from an index; a purge rebuilds it.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

_U64 = np.uint64
# splitmix64's finaliser (Steele, Lea, Flood: "Fast splittable
# pseudorandom number generators", OOPSLA 2014): two rounds of
# xor-shift and multiply, then one more xor-shift
_MIX = ((_U64(30), _U64(0xBF58476D1CE4E5B9)),
        (_U64(27), _U64(0x94D049BB133111EB)))
_MIX_LAST = _U64(31)

#: slots per row of capacity: a full index is loaded to a quarter at
#: most, so nine lookups in ten end at their first slot
_SLOTS_PER_ROW = 4


def index_for(keys: np.ndarray, rows: np.ndarray, capacity: int):
    """The index that serves ``keys``' dtype kind, holding ``keys`` ->
    ``rows`` (unique keys, any order)."""
    if keys.dtype.kind in "iu":
        index = HashKeyIndex(capacity, keys.dtype)
        index.insert(keys, rows)
        return index
    return SortedKeyIndex(keys, rows)


def _key_bits(keys: np.ndarray) -> np.ndarray:
    """Integer keys of any width as int64 words, so 7 is 7 whatever its
    dtype.  uint64 keeps its bit pattern: the runtime never mixes it
    with signed keys (no safe cast joins them)."""
    if keys.dtype.kind == "u":
        return keys.astype(np.uint64, copy=False).view(np.int64)
    return keys.astype(np.int64, copy=False)


class HashKeyIndex:
    """Open addressing over ``[slots, 2]`` int64 words: a slot is its
    key's bits beside its row + 1, so one probe reads one cache line
    and a zeroed table is an empty one (0 marks an empty slot in the
    row word; no key value marks anything, so every int64 is a legal
    key).  The size is fixed from the capacity (a power of two, load
    at most 1/4): the table never rehashes."""

    kind = "hash"

    def __init__(self, capacity: int, dtype):
        self.dtype = np.dtype(dtype)
        bits = max(4, (_SLOTS_PER_ROW * max(capacity, 1) - 1).bit_length())
        self._mask = (1 << bits) - 1
        # empty + fill, not zeros: numpy asks the kernel for huge pages
        # on this path alone, and a probe is a random read of a table
        # that is 64 MB at a million rows
        self._tab = np.empty((1 << bits, 2), dtype=np.int64)
        self._tab.fill(0)
        # what was inserted, in order: ``items()`` without a pass over
        # the table's slots (64 MB of them at a million rows)
        self._n = 0
        self._keys = np.empty(max(capacity, 1), dtype=np.int64)
        self._rows = np.empty(max(capacity, 1), dtype=np.int32)

    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        keys = self._keys[:self._n]
        if self.dtype.kind == "u":
            keys = keys.view(np.uint64)
        return keys.astype(self.dtype, copy=False), self._rows[:self._n]

    def widen(self, dtype):
        """The index after the runtime widened its key dtype to
        ``dtype``: itself for an integer dtype (slots hold int64 words
        already), the sorted index for any other."""
        dtype = np.dtype(dtype)
        if dtype.kind in "iu":
            self.dtype = dtype
            return self
        keys, rows = self.items()
        return SortedKeyIndex(keys.astype(dtype), rows)

    def home(self, bits: np.ndarray) -> np.ndarray:
        """First slot of each key (int64 words -> slot numbers)."""
        x = bits.astype(np.uint64)  # a copy: mixed in place below
        tmp = np.empty_like(x)
        for shift, mul in _MIX:
            np.right_shift(x, shift, out=tmp)
            x ^= tmp
            x *= mul
        np.right_shift(x, _MIX_LAST, out=tmp)
        x ^= tmp
        x &= _U64(self._mask)
        return x.view(np.int64)

    def _probe(self, slot: np.ndarray, bits: np.ndarray):
        """The row words at ``slot`` and the lanes that must probe on:
        another key holds their slot."""
        got = self._tab.take(slot, axis=0)
        open_ = got[:, 0] != bits
        open_ &= got[:, 1] > 0
        return got[:, 1], open_

    def lookup(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray, int]:
        bits = _key_bits(keys)
        slot = self.home(bits)
        words, open_ = self._probe(slot, bits)
        rows = words.astype(np.int32)
        lanes = np.flatnonzero(open_)
        probed = len(lanes)
        if probed:
            slot, bits = slot[lanes], bits[lanes]
            while len(lanes):
                slot += 1
                slot &= self._mask
                words, open_ = self._probe(slot, bits)
                done = ~open_
                rows[lanes[done]] = words[done]
                lanes, slot, bits = lanes[open_], slot[open_], bits[open_]
        rows -= 1
        missing = rows < 0
        if not missing.any():
            return rows, keys[:0], probed
        new_keys, inv = np.unique(keys[missing], return_inverse=True)
        rows[missing] = -1 - inv
        return rows, new_keys, probed

    def insert(self, keys: np.ndarray, rows: np.ndarray):
        """Each key takes the first empty slot from its home on; of
        several keys that reach one empty slot in the same pass one
        claims it (whichever numpy wrote last: it reads its own bits
        back) and the rest move on."""
        words, mask = self._tab.reshape(-1), self._mask
        bits = _key_bits(keys)
        n, end = self._n, self._n + len(bits)
        self._keys[n:end], self._rows[n:end], self._n = bits, rows, end
        rows = rows.astype(np.int64) + 1
        slot = self.home(bits)
        while len(slot):
            lanes = np.flatnonzero(words.take(2 * slot + 1) == 0)
            claim = 2 * slot[lanes]
            words[claim] = bits[lanes]
            won = words.take(claim) == bits[lanes]
            words[claim[won] + 1] = rows[lanes[won]]
            keep = np.ones(len(slot), dtype=bool)
            keep[lanes[won]] = False
            slot, bits, rows = slot[keep], bits[keep], rows[keep]
            slot += 1
            slot &= mask


class SortedKeyIndex:
    """Known keys as a sorted array in their NATIVE dtype ('<U', float:
    ``searchsorted`` compares in C, not through boxed python objects)
    beside the row of each position."""

    kind = "sorted"

    def __init__(self, keys: np.ndarray, rows: np.ndarray):
        order = np.argsort(keys, kind="stable")
        self._keys = keys[order]
        self._rows = np.asarray(rows, dtype=np.int32)[order]

    @property
    def dtype(self):
        return self._keys.dtype

    def widen(self, dtype):
        self._keys = self._keys.astype(dtype)
        return self

    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        return self._keys, self._rows

    def lookup(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray, int]:
        uniq, inv = np.unique(keys, return_inverse=True)
        pos = np.minimum(np.searchsorted(self._keys, uniq),
                         len(self._keys) - 1)
        found = self._keys[pos] == uniq
        new_keys = uniq[~found]
        urows = np.empty(len(uniq), dtype=np.int32)
        urows[found] = self._rows[pos[found]]
        urows[~found] = -1 - np.arange(len(new_keys), dtype=np.int32)
        return urows[inv], new_keys, 0

    def insert(self, keys: np.ndarray, rows: np.ndarray):
        """Merge sorted unique keys the index does not hold: an O(K+U)
        two-way merge (a full argsort of the index per batch would
        dominate the step); the dtype promotes explicitly so widening
        string keys never truncate."""
        K, U = len(self._keys), len(keys)
        new_pos = np.searchsorted(self._keys, keys) + np.arange(U)
        old = np.ones(K + U, dtype=bool)
        old[new_pos] = False
        merged_keys = np.empty(
            K + U, dtype=np.promote_types(self._keys.dtype, keys.dtype))
        merged_keys[new_pos] = keys
        merged_keys[old] = self._keys
        merged_rows = np.empty(K + U, dtype=np.int32)
        merged_rows[new_pos] = rows
        merged_rows[old] = self._rows
        self._keys, self._rows = merged_keys, merged_rows
