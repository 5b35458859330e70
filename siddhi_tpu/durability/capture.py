"""Non-blocking checkpoint capture: freeze state under the barrier.

What the barrier holds (``SiddhiAppRuntime.persist``: the app's process
lock from the emit drain to the end of :func:`capture_elements`) is each
element's ``snapshot()`` call and :func:`freeze` of what it returned:

* device arrays (jax, or an engine's own device-side snapshot:
  ``ops/dense_layout.py`` ``SnapshotField``) are kept **by reference**:
  they are immutable, so the D2H fetch happens later, on whoever asks;
* host containers (dicts/lists/EventBatch/numpy) are **shallow-cheap
  copied** so post-barrier mutation cannot race the background pickle;
* anything freeze does not understand makes that ELEMENT fall back to an
  in-barrier ``pickle.dumps`` (``prepickled``), counted through
  ``persistFallbackReason`` (degradation, never corruption).

So an engine that hands out device arrays holds the stream for a
dispatch, and one that hands out numpy holds it for its own fetch and
one copy.  Every pass over host memory at the scale of the state is
:func:`materialize`'s and :meth:`StateCapture.materialize_blobs`', on the
writer thread (async) or in the caller (sync, ``snapshot()``): the wait
for the transfer (``util.faults.host_view``, the sanctioned
materializer: this module is in the host-sync-hazard scan set and must
not call ``np.asarray``/``np.array`` itself), then a pickle of the
element's skeleton with every array of a page or more left **out of
band** (protocol 5), a buffer the store writes and hashes as it lies.
All three let the interpreter go, so a sender is not held by them.
"""

from __future__ import annotations

import contextlib
import pickle
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from siddhi_tpu.core.event import Event, EventBatch
from siddhi_tpu.observability.trace import (
    STAGE_PERSIST_FETCH,
    STAGE_PERSIST_FREEZE,
    STAGE_PERSIST_PICKLE,
    span,
)
from siddhi_tpu.util.faults import host_view


class UnfreezableStateError(Exception):
    """An element's state holds a type freeze cannot safely copy."""


_SCALARS = (type(None), bool, int, float, complex, str, bytes)

#: a buffer of this many bytes or more leaves an element's pickle and
#: reaches the store as it lies (a page: below it a file of its own
#: costs more than the copy)
OUT_OF_BAND_BYTES = 4096

# the calling thread's open tally of bytes fetched from the device: by
# its elements' ``snapshot()`` while a state tree is built (under the
# barrier), by :func:`materialize` where a tally is open round that
# (``SiddhiAppRuntime._persist_write``: off the barrier)
_fetched = threading.local()


@contextlib.contextmanager
def fetch_tally():
    """Open a tally on this thread; yields a one-item list that ends up
    holding the bytes :func:`note_fetched` was told of meanwhile."""
    found = getattr(_fetched, "tally", None)
    tally = _fetched.tally = [0]
    try:
        yield tally
    finally:
        _fetched.tally = found


def note_fetched(nbytes: int) -> None:
    """``nbytes`` of device state reached the host: in an engine's
    ``snapshot()`` (core/device_single.py) or in :func:`materialize`;
    nothing where no tally is open."""
    tally = getattr(_fetched, "tally", None)
    if tally is not None:
        tally[0] += nbytes


def _is_device_array(obj: Any) -> bool:
    """Array-like that is NOT numpy: a jax device array (immutable, so a
    reference is a valid capture — fetched to host later, off-barrier)."""
    return (not isinstance(obj, (np.ndarray, np.generic))
            and hasattr(obj, "shape") and hasattr(obj, "dtype"))


def freeze(obj: Any) -> Any:
    """Cheap race-free copy of one element's snapshot state.

    Raises :class:`UnfreezableStateError` on any type whose aliasing
    semantics are unknown — the caller then pre-pickles that element
    under the barrier instead."""
    if isinstance(obj, _SCALARS):
        return obj
    if isinstance(obj, np.generic):
        return obj
    if isinstance(obj, np.ndarray):
        return obj.copy()
    if _is_device_array(obj):
        return obj  # immutable device value: capture by reference
    if isinstance(obj, dict):
        return {k: freeze(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [freeze(v) for v in obj]
    if isinstance(obj, tuple):
        return tuple(freeze(v) for v in obj)
    if isinstance(obj, (set, frozenset)):
        return set(obj) if isinstance(obj, set) else obj
    if isinstance(obj, deque):
        return deque((freeze(v) for v in obj), maxlen=obj.maxlen)
    if isinstance(obj, EventBatch):
        out = EventBatch(
            obj.stream_id,
            list(obj.attribute_names),
            {k: freeze(v) for k, v in obj.columns.items()},
            obj.timestamps.copy(),
            obj.types.copy(),
        )
        out.aux = {k: freeze(v) for k, v in obj.aux.items()}
        return out
    if isinstance(obj, Event):
        return Event(obj.timestamp, [freeze(v) for v in obj.data],
                     obj.is_expired)
    raise UnfreezableStateError(type(obj).__name__)


def materialize(obj: Any) -> Any:
    """``obj`` with every device array replaced by its host value (a
    read-only view of the buffer its transfer filled).  Containers are
    rebuilt, so ``obj`` itself stays as it was: a retry of the writer's
    job finds the device arrays again (JAX keeps their host copies)."""
    if _is_device_array(obj):
        with span(STAGE_PERSIST_FETCH, obj.nbytes):
            host = host_view(obj)
        note_fetched(host.nbytes)
        return host
    if isinstance(obj, dict):
        return {k: materialize(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [materialize(v) for v in obj]
    if isinstance(obj, tuple):
        return tuple(materialize(v) for v in obj)
    if isinstance(obj, deque):
        return deque((materialize(v) for v in obj), maxlen=obj.maxlen)
    return obj


def dumps_out_of_band(obj: Any) -> Tuple[bytes, List[memoryview]]:
    """``(skeleton, buffers)``: ``obj`` pickled with every contiguous
    buffer of :data:`OUT_OF_BAND_BYTES` or more left out, in the order
    ``pickle.loads(skeleton, buffers=buffers)`` wants them back."""
    buffers: List[memoryview] = []

    def keep_out(buf: pickle.PickleBuffer):
        view = buf.raw()
        if view.nbytes < OUT_OF_BAND_BYTES:
            return True  # in band
        buffers.append(view)
        return False

    return pickle.dumps(obj, protocol=5, buffer_callback=keep_out), buffers


class CapturedElement:
    """One state-tree element: frozen state OR an in-barrier pickle."""

    __slots__ = ("kind", "name", "state", "prepickled")

    def __init__(self, kind: str, name: str, state: Any = None,
                 prepickled: Optional[bytes] = None):
        self.kind = kind
        self.name = name
        self.state = state
        self.prepickled = prepickled


class StateCapture:
    """Everything ``persist()`` collects under the barrier.

    ``elements`` preserve the snapshot tree's (kind, name) addressing so
    the writer can emit per-element blobs (durable store) or reassemble
    the monolithic tree-pickle (plain stores) — both restore through the
    unchanged ``SnapshotService.restore`` path."""

    __slots__ = ("app", "version", "elements", "fallbacks", "clock",
                 "fetched_bytes")

    def __init__(self, app: str, version: int,
                 elements: List[CapturedElement],
                 fallbacks: List[Tuple[str, str]],
                 clock: Optional[int] = None, fetched_bytes: int = 0):
        self.app = app
        self.version = version
        self.elements = elements
        # the tree's clock (util/snapshot.py): the app's time at the barrier
        self.clock = clock
        # what the elements' snapshots fetched from the device under it
        self.fetched_bytes = fetched_bytes
        # [(element key, reason)] for elements that took the in-barrier
        # pickle fallback — surfaced as persistFallbackReason
        self.fallbacks = fallbacks

    def materialize_blobs(self) -> List[Tuple[str, str, bytes,
                                              List[memoryview]]]:
        """[(kind, name, skeleton, buffers)]: the D2H fetch and the
        pickle, off-barrier.  The span of a pickle counts what it holds
        in band; the buffers are the store's to count."""
        out = []
        for el in self.elements:
            if el.prepickled is not None:
                out.append((el.kind, el.name, el.prepickled, []))
                continue
            state = materialize(el.state)
            with span(STAGE_PERSIST_PICKLE) as sp:
                data, buffers = dumps_out_of_band(state)
                if sp is not None:
                    sp.count = len(data)
            out.append((el.kind, el.name, data, buffers))
        return out

    def tree_bytes(self) -> bytes:
        """Monolithic tree pickle, bit-compatible with
        ``SnapshotService.full_snapshot`` (for stores without a
        per-element blob layout)."""
        return pickle.dumps(self.tree(), protocol=pickle.HIGHEST_PROTOCOL)

    def tree(self) -> Dict:
        tree: Dict = {"version": self.version, "app": self.app,
                      "queries": {}, "tables": {}, "named_windows": {},
                      "partitions": {}, "aggregations": {}}
        if self.clock is not None:
            tree["clock"] = self.clock
        for el in self.elements:
            if el.prepickled is not None:
                tree[el.kind][el.name] = pickle.loads(el.prepickled)
            else:
                tree[el.kind][el.name] = materialize(el.state)
        return tree


def capture_elements(app: str, version: int, tree: Dict,
                     element_kinds: Tuple[str, ...],
                     on_fallback: Optional[Callable[[str, str], None]] = None,
                     fetched_bytes: int = 0) -> StateCapture:
    """Freeze a just-built state tree into a :class:`StateCapture`.

    Caller holds the barrier (process lock, sources paused, emits
    drained).  An element freeze cannot copy is pickled here, in-barrier
    — the per-element sync degradation path — and reported through
    ``on_fallback(element_key, reason)``."""
    elements: List[CapturedElement] = []
    fallbacks: List[Tuple[str, str]] = []
    for kind in element_kinds:
        for name, state in tree.get(kind, {}).items():
            try:
                with span(STAGE_PERSIST_FREEZE):
                    frozen = freeze(state)
                elements.append(CapturedElement(kind, name, state=frozen))
            except UnfreezableStateError as e:
                reason = f"unfreezable:{e}"
                fallbacks.append((f"{kind}:{name}", reason))
                if on_fallback is not None:
                    on_fallback(f"{kind}:{name}", reason)
                elements.append(CapturedElement(
                    kind, name,
                    prepickled=pickle.dumps(
                        materialize(state),
                        protocol=pickle.HIGHEST_PROTOCOL)))
    return StateCapture(app, version, elements, fallbacks,
                        clock=tree.get("clock"), fetched_bytes=fetched_bytes)
