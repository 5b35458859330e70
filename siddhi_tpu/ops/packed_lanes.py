"""A batch's host lanes as ONE buffer: the shared form of the packed put.

A leaf of a ``device_put`` costs about 0.2 ms on the chip whatever its
size (PERF.md section 6: PRs 36, 38, 41, 56), so a program's host lanes
cross as one ``int32 [k, B]`` array, a row a lane, and the program takes
it apart with static slices.  A :class:`LaneTable` is the one thing both
halves read: the host's :meth:`~LaneTable.pack` and the traced
:meth:`~LaneTable.unpack`.

A lane is a 32-bit word a row: ``int32`` as it is, ``float32`` written
through a float32 view of its row, so that what crosses is the bit
pattern (NaN payloads and -0.0 included) and not a value.  Wider
integers cross as the words their engine splits them into.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Sequence, Tuple

import numpy as np

_LANE_DTYPES = (np.dtype(np.int32), np.dtype(np.float32))


class LaneTable:
    """The rows of a packed buffer, in order: ``(name, dtype)`` each.
    Fixed when the program that unpacks it is built."""

    __slots__ = ("names", "dtypes")

    def __init__(self, lanes: Sequence[Tuple[str, object]]):
        self.names = tuple(name for name, _dt in lanes)
        self.dtypes = tuple(np.dtype(dt) for _name, dt in lanes)
        if len(set(self.names)) != len(self.names):
            raise ValueError(f"lane named twice in {self.names}")
        for name, dt in zip(self.names, self.dtypes):
            if dt not in _LANE_DTYPES:
                raise ValueError(
                    f"lane '{name}' is {dt}: a packed lane is an int32 or "
                    "a float32 word")

    def __len__(self) -> int:
        return len(self.names)

    def pack(self, lanes: Mapping[str, Optional[np.ndarray]],
             take: np.ndarray, width: int,
             pad: Optional[Mapping[str, int]] = None) -> np.ndarray:
        """Host half.  ``int32 [len(self), width]``: row ``i`` holds
        ``lanes[names[i]][take]`` in its first ``len(take)`` entries
        and ``pad[names[i]]`` (default 0) in the rest.  A lane the
        batch does not bring (absent, or None) is a row of its padding.
        A lane must already hold its table's dtype: a cast here would
        change a value where the caller meant a bit pattern."""
        b = len(take)
        if b > width:
            raise ValueError(f"{b} lanes do not fit a width of {width}")
        buf = np.zeros((len(self.names), width), dtype=np.int32)
        for row, name, dt in zip(buf, self.names, self.dtypes):
            lane = lanes.get(name)
            fill = pad.get(name, 0) if pad else 0
            if fill:
                row[(0 if lane is None else b):] = fill
            if lane is None:
                continue
            if lane.dtype != dt:
                raise ValueError(
                    f"lane '{name}' is {lane.dtype}, its row {dt}")
            # `mode="clip"`: numpy buffers `out` under the default mode;
            # `take` indexes the batch's own events, so nothing clips
            np.take(lane, take, out=row.view(dt)[:b], mode="clip")
        return buf

    def unpack(self, buf) -> Dict[str, object]:
        """Traced half: the rows of a packed buffer by name, each in
        its lane's dtype again (static slices; float32 by
        ``bitcast_convert_type``)."""
        import jax

        if buf.shape[0] != len(self.names):
            raise ValueError(
                f"buffer of {buf.shape[0]} rows for {len(self.names)} lanes")
        rows = {}
        for i, (name, dt) in enumerate(zip(self.names, self.dtypes)):
            row = buf[i]
            if dt == np.float32:
                row = jax.lax.bitcast_convert_type(row, np.float32)
            rows[name] = row
        return rows
