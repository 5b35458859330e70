"""From the profiler's ``.xplane.pb`` to one ``Trace`` (every device
operation with its ``siddhi.*`` scope, the host's ``bench.*`` and
``siddhi.*`` spans, the traced window), and from that to device busy
time, idle share, the longest operations, the idle gaps by what the
host was doing and device time by scope.

The file is read once, after the clock has stopped, with
``google.protobuf`` and a schema declared here (the fields of
``tsl/profiler/protobuf/xplane.proto`` that are read): no generated
module is imported, ``jax.profiler.ProfileData`` hides the statistics
of an event's metadata, and ``tensorflow``'s copy costs seconds to
import.
"""

from __future__ import annotations

import collections
import functools
import glob
import heapq
import os

MARK = "bench.window"       # the harness's span over the traced window
MIN_GAP_NS = 10_000         # idle gaps under 10 us are not attributed
NAME_LEN = 96               # an operation's name is its HLO text: cut it
HOST_SPANS = ("bench.", "siddhi.")   # host events kept, by prefix
SCOPE = "siddhi."           # a jax.named_scope of the program
OP_NAME_STAT = "tf_op"      # the stat that holds the framework op name
CATEGORY_STAT = "hlo_category"
KERNEL = "/pallas_call"     # appended to the scope of a Pallas kernel's own call


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


def innermost(ops, lo, hi):
    """Nanoseconds of ``[lo, hi]`` under each key of ``(start, end,
    key)`` operations, every instant counted once: where operations
    nest or overlap (a ``while`` round its body, an asynchronous copy
    beside a fusion), the one that started last takes the instant, and
    of two that started together the shorter.  The values add up to the
    length of the operations' union."""
    out, open_, at = collections.Counter(), [], lo

    def run_to(t):
        nonlocal at
        while open_ and at < t:
            _start, end, _n, key = open_[0]
            if end <= at:
                heapq.heappop(open_)
                continue
            upto = min(end, t)
            out[key] += upto - at
            at = upto
        at = max(at, t)

    # keys are never compared (a scope may be None beside a string): ties
    # of start and end go by the order the operations came in
    for n, (s, e, key) in enumerate(sorted(
            ((max(s, lo), min(e, hi), key)
             for s, e, key in ops if e > lo and s < hi),
            key=lambda op: op[:2])):
        run_to(s)
        heapq.heappush(open_, (-s, e, n, key))
    run_to(hi)
    return out


def scope_of(op_name: str, category: str = ""):
    """The innermost ``siddhi.*`` ``jax.named_scope`` on an operation's
    framework path (``jit(step)/siddhi.dense.rounds/while/body/
    siddhi.dense.gather/gather:`` is ``siddhi.dense.gather``), or None.
    A Pallas kernel's own call (a ``custom-call`` whose framework op is
    ``pallas_call``; the copies round it carry that name too, as
    ``data formatting``) is set apart as ``<scope>/pallas_call``."""
    parts = op_name.rstrip(":").split("/")
    for part in reversed(parts):
        if part.startswith(SCOPE):
            kernel = category == "custom-call" and parts[-1] == "pallas_call"
            return part + KERNEL if kernel else part
    return None


class Trace:
    """What one traced window holds.

    ``device``: per device plane every operation as ``(start, end,
    name, scope)``, nanoseconds on the profiler's clock, ``name`` the
    operation's HLO text, ``scope`` as ``scope_of`` gives it (a
    kernel's own call as ``<scope>/pallas_call``).
    ``host``: the host's ``bench.*`` and ``siddhi.*`` spans as
    ``(start, end, name)``, every thread's.  ``lo``, ``hi``: the
    ``bench.window`` mark (the operations' own extent where the mark
    misses them).  ``batches``: batches sent under the mark.  Planes
    with no operation are dropped; ``device`` may be empty."""

    def __init__(self, device, host, batches=None):
        self.device = {k: v for k, v in device.items() if v}
        self.host = host
        self.batches = batches
        self.lo = self.hi = None
        if self.device:
            self.lo = min(op[0] for ops in self.device.values() for op in ops)
            self.hi = max(op[1] for ops in self.device.values() for op in ops)
            mark = [(s, e) for s, e, name in host if name == MARK]
            if mark and mark[0][0] < self.hi and mark[0][1] > self.lo:
                self.lo, self.hi = mark[0]

    def scope_seconds(self):
        """Device seconds of the window by scope (None: under no
        ``siddhi.*`` scope), each instant of a plane's busy time given to
        the operation ``innermost`` there, mean over the device planes:
        the values add up to ``reduce``'s ``busy_s``.  Empty where no
        operation carries a scope (an executable from a compile cache
        older than the scopes): a reader then has nothing to read."""
        out = collections.Counter()
        for ops in self.device.values():
            for scope, ns in innermost(
                    ((s, e, scope) for s, e, _name, scope in ops),
                    self.lo, self.hi).items():
                out[scope] += ns / 1e9 / len(self.device)
        return dict(out) if any(out) else {}


def reduce(trace):
    """Busy seconds (mean over the device planes), the window, the ten
    longest operations by summed time, each named ``<scope> <HLO
    text>``, and the idle gaps by the innermost host span."""
    if not trace.device:
        return None
    lo, hi, device = trace.lo, trace.hi, trace.device
    busy, ops_s, first = [], collections.Counter(), None
    for plane in sorted(device):
        merged = clip(union((s, e) for s, e, _n, _sc in device[plane]), lo, hi)
        busy.append(length(merged))
        first = merged if first is None else first
        for s, e, op, scope in device[plane]:
            if e > lo and s < hi:
                name = f"{scope} {op}" if scope else op
                ops_s[name[:NAME_LEN]] += ((min(e, hi) - max(s, lo)) / 1e9
                                           / len(device))
    idle = attribute(gaps(first, lo, hi),
                     [x for x in trace.host if x[2] != MARK])
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "device_ops": [[k, v] for k, v in ops_s.most_common(10)],
            "idle_gaps": [[k, v] for k, v in idle.most_common(10)]}


# --- the file ---------------------------------------------------------------

# message -> [(field, number, type, repeated)]; a type that names a message
# of this table is that message.  A proto map is, on the wire, a repeated
# entry of key and value, and is declared as that.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "XEventMetadataEntry", True),
               ("stat_metadata", 5, "XStatMetadataEntry", True)],
    "XLine": [("name", 2, "string", False), ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadataEntry": [("key", 1, "int64", False),
                            ("value", 2, "XEventMetadata", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XStatMetadata", False)],
    "XStatMetadata": [("name", 2, "string", False)],
}


@functools.cache
def _xspace_class():
    """``XSpace`` as a ``google.protobuf`` message class over a pool of
    its own, so that no other copy of the schema in the process
    collides with it.  Built on first use."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
              "uint64": F.TYPE_UINT64}
    pkg = "bench.xplane"
    file = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=pkg, syntax="proto3")
    for msg_name, fields in _SCHEMA.items():
        msg = file.message_type.add(name=msg_name)
        for name, number, kind, repeated in fields:
            f = msg.field.add(
                name=name, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _scopes(plane):
    """Event metadata id -> scope, from the stats of the metadata that
    hold the framework op name and the HLO category (each a string, or
    a reference to a stat metadata whose name is the string)."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for entry in plane.event_metadata:
        held = {stat_names.get(stat.metadata_id): (
            stat.str_value or stat_names.get(stat.ref_value, ""))
            for stat in entry.value.stats}
        out[entry.key] = scope_of(held.get(OP_NAME_STAT, ""),
                                  held.get(CATEGORY_STAT, ""))
    return out


def _events(lines):
    """``(start, end, metadata id)`` of every event, whole nanoseconds."""
    for ln in lines:
        t0 = ln.timestamp_ns
        for e in ln.events:
            start = t0 + e.offset_ps / 1e3
            yield int(start), int(start + e.duration_ps / 1e3), e.metadata_id


def read(path, batches=None):
    """The ``Trace`` of one ``.xplane.pb``."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    device, host = {}, []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        if plane.name.startswith("/device:TPU:"):
            scopes = _scopes(plane)
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"] or [
                ln for ln in plane.lines
                if ln.name not in ("XLA Modules", "Steps")]
            device[plane.name] = [
                (start, end, names.get(mid, ""), scopes.get(mid))
                for start, end, mid in _events(lines)]
        elif plane.name.startswith("/host:"):
            host += [(start, end, names[mid])
                     for start, end, mid in _events(plane.lines)
                     if names.get(mid, "").startswith(HOST_SPANS)]
    return Trace(device, host, batches)


def read_dir(log_dir, batches=None):
    """The newest trace under ``log_dir``, or None where there is none."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return read(max(found, key=os.path.getmtime), batches) if found else None
