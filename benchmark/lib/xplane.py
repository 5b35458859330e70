"""From the profiler's ``.xplane.pb`` to device busy time, idle share,
the longest operations and the idle gaps by what the host was doing."""

from __future__ import annotations

import collections
import glob
import os

MARK = "bench.window"       # the harness's span over the traced window
MIN_GAP_NS = 10_000         # idle gaps under 10 us are not attributed
NAME_LEN = 96               # an operation's name is its HLO text: cut it


def union(intervals):
    """Merged, sorted ``(start, end)`` list covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def gaps(busy, lo, hi):
    """The complement of a merged ``busy`` list within ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def attribute(idle, spans):
    """Seconds of ``idle`` gaps under each host span name; where spans
    nest, the innermost (latest started) takes the time; ``none`` where
    no span was open."""
    out = collections.Counter()
    spans, i = sorted(spans), 0
    for a, b in idle:            # gaps come sorted too: one pass over both
        while i < len(spans) and spans[i][1] <= a:
            i += 1
        if b - a < MIN_GAP_NS:
            continue
        over, j = [], i
        while j < len(spans) and spans[j][0] < b:
            if spans[j][1] > a:
                over.append(spans[j])
            j += 1
        cuts = sorted({a, b, *(x for s, e, _n in over for x in (s, e)
                               if a < x < b)})
        for lo, hi in zip(cuts, cuts[1:]):
            open_ = [(s, name) for s, e, name in over if s <= lo and e >= hi]
            out[max(open_)[1] if open_ else "none"] += (hi - lo) / 1e9
    return out


def planes(path):
    """``(device, host)``: per device plane its operations
    ``(start, end, name)``, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    device, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or [
                ln for ln in lines if ln.name not in ("XLA Modules", "Steps")]
            device[plane.name] = [
                (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            host += [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                     for ln in plane.lines for e in ln.events
                     if e.name.startswith("bench.")]
    return device, host


def reduce(device, host):
    """Busy seconds (mean over the device planes), the window, the ten
    longest operations by summed time and the idle gaps by host span."""
    device = {k: v for k, v in device.items() if v}
    if not device:
        return None
    mark = [(s, e) for s, e, name in host if name == MARK]
    lo = min(s for ops in device.values() for s, _e, _n in ops)
    hi = max(e for ops in device.values() for _s, e, _n in ops)
    if mark and mark[0][0] < hi and mark[0][1] > lo:
        lo, hi = mark[0]
    busy, ops_s, first = [], collections.Counter(), None
    for name in sorted(device):
        merged = clip(union((s, e) for s, e, _n in device[name]), lo, hi)
        busy.append(length(merged))
        first = merged if first is None else first
        for s, e, op in device[name]:
            if e > lo and s < hi:
                ops_s[op[:NAME_LEN]] += (min(e, hi) - max(s, lo)) / 1e9 / len(device)
    idle = attribute(gaps(first, lo, hi),
                     [x for x in host if x[2] != MARK])
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "device_ops": [[k, v] for k, v in ops_s.most_common(10)],
            "idle_gaps": [[k, v] for k, v in idle.most_common(10)]}


def reduce_dir(log_dir):
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return reduce(*planes(max(found, key=os.path.getmtime))) if found else None
