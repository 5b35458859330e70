"""The device round trip split on the profiler's shared clock
(``run.trace``, ``lib/xplane.py``: the host's ``siddhi.*`` spans and
every device operation on one clock), per batch under the mark, mean
over the device planes:

- ``launch_lag``: from the start of a ``siddhi.dispatch`` host span to
  the first device operation that starts after it, where the plane was
  idle at that start; 0 where it was busy (a batch dispatched behind
  one in flight waits for the device, not for the launch).  It holds
  the call of the jitted program and whatever the operation waits for
  before it may start: the H2D transfer of its input is no operation
  of the plane.
- ``gate_return``: of each ``siddhi.step_wait`` host span (the host
  blocked on a count gate), the part after the plane's last operation
  that ended inside or before it: the device had finished, the host
  did not have the count yet.  0 where the plane is still busy when
  the span ends (the gate of a batch left staged resolves while the
  next batch's step runs).

On a cell whose gate is finished inline, ``launch_lag``, the device's
busy time and ``gate_return`` tile the span from the dispatch to the
resolved gate but for the host's own time between the end of
``dispatch`` and the start of the wait.  A program without the
annotations, or a trace without a device plane, yields nothing."""

import bisect

from lib.xplane import union

DISPATCH, STEP_WAIT = "siddhi.dispatch", "siddhi.step_wait"
LAUNCH_LAG, GATE_RETURN = "launch_lag_ms_per_batch", "gate_return_ms_per_batch"


class Busy:
    """One device plane's merged busy intervals."""

    def __init__(self, ops):
        merged = union((op[0], op[1]) for op in ops)
        self.starts = [a for a, _b in merged]
        self.ends = [b for _a, b in merged]

    def launch_lag(self, t):
        """Nanoseconds from ``t`` to the next operation's start; 0 where
        the plane is busy at ``t`` or no operation follows."""
        i = bisect.bisect_right(self.starts, t)
        if i and self.ends[i - 1] > t:
            return 0
        return self.starts[i] - t if i < len(self.starts) else 0

    def gate_return(self, a, b):
        """Nanoseconds of ``[a, b]`` after the last operation that ended
        at or before ``b``; 0 where the plane is busy at ``b``."""
        i = bisect.bisect_right(self.starts, b)
        if not i:
            return b - a        # nothing ran before it: all of it
        if self.ends[i - 1] > b:
            return 0
        return b - max(a, self.ends[i - 1])


def read(run):
    trace = getattr(run, "trace", None)
    if trace is None or not trace.batches or not trace.device:
        return {}
    wanted = {name: name.split(".", 1)[-1] for name in run.wanted
              if name.split(".", 1)[-1] in (LAUNCH_LAG, GATE_RETURN)}
    if not wanted:
        return {}
    lo, hi = trace.lo, trace.hi
    under = {want: [(a, b) for a, b, name in trace.host
                    if name == span and lo <= a and b <= hi]
             for want, span in ((LAUNCH_LAG, DISPATCH),
                                (GATE_RETURN, STEP_WAIT))}
    planes = [Busy(ops) for ops in trace.device.values()]
    per = 1e6 * len(planes) * trace.batches     # ns -> ms a batch
    out = {}
    for name, what in wanted.items():
        spans = under[what]
        if not spans:
            continue
        if what == LAUNCH_LAG:
            ns = sum(p.launch_lag(a) for p in planes for a, _b in spans)
        else:
            ns = sum(p.gate_return(a, b) for p in planes for a, b in spans)
        out[name] = ns / per
    return out
