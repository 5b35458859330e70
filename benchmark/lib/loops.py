"""The measured window: a closed or an open loop over a schedule that
was fixed before it, every batch sent however late, the runtime's flush
barrier, and only then the end of the clock."""

from __future__ import annotations

import faulthandler
import sys
import time
import types

clock = time.perf_counter
# an open loop sleeps to this short of a due time, then spins
SPIN_S = 0.010


def run(dep, schedule, traffic: dict, seconds: float, send, profile=None):
    """Drive the window.  ``send(batch)`` is the deployment's ``send``
    (with one input stream the handler's bound ``send_batch`` itself) or
    the control's rounding wrapper.  ``profile`` (traced runs) is an
    object with ``start()``, ``mark()`` and ``stop()``, called at batch
    boundaries: the profiler starts, one batch absorbs its first use on
    the device, the mark opens over a few seconds of the steady window.

    An open loop offers exactly ``floor(rate * seconds)`` batches, each
    at its due time or as soon after as the engine allows; a closed loop
    sends whole batches until ``seconds`` have passed.  A batch is never
    given up for being late.  The generator sleeps to within ``SPIN_S``
    of a due time and spins the rest, so that its own wake-up is not in
    the latency.  A traced closed loop goes on until its mark has closed.
    A run that hangs past three windows is killed with a traceback and
    no result line."""
    rate = traffic.get("rate_batches_per_s")
    total = int(rate * seconds) if traffic["loop"] == "open" else None
    w = types.SimpleNamespace(
        sends=[], late=[], due=[], raised=[], traced=None, clean=None,
        n_sent=0)
    # the profiler's start and stop stall the host for seconds: trace late
    # in the window, so that what is read before it (``clean`` batches:
    # latencies, generator lateness, the mean send) is not the profiler's
    trace_from = max(seconds - 7.0, seconds / 4)
    trace_for = max(seconds - 2.0, seconds * 3 / 4) - trace_from
    faulthandler.dump_traceback_later(3 * seconds + 60, exit=True,
                                      file=sys.__stderr__)
    try:
        w.t0 = t0 = clock()
        n = 0
        while (n < total) if total is not None else (
                clock() - t0 < seconds
                or (profile is not None and not (w.traced and w.traced[1]))):
            batch = schedule.batch(n)
            if profile is not None:
                now, called = clock(), True
                if w.clean is None and now - w.t0 >= trace_from:
                    profile.start()
                    w.clean = n    # this batch takes the tracer's first use
                elif w.clean is not None and w.traced is None and n > w.clean:
                    profile.mark()
                    w.traced = [n, None, clock()]  # first under the mark
                elif (w.traced and w.traced[1] is None
                      and now - w.traced[2] >= trace_for):
                    profile.stop()
                    w.traced[1] = n                # first after it
                else:
                    called = False
                if called and total is not None:
                    # an open loop goes on as paced, not catching up on
                    # what the profiler stalled: this batch is due now
                    t0 = max(t0, clock() - n / rate)
            if total is not None:
                due = t0 + n / rate
                with dep.span("bench.wait_due"):
                    # a sleeping thread is woken late, and later still on
                    # a busy host: sleep short of the due time, spin to it
                    while (wait := due - clock()) > SPIN_S:
                        time.sleep(wait - SPIN_S)
                    while clock() < due:
                        pass
                w.due.append(due)
            t_send = clock()
            try:
                with dep.span("bench.send_batch"):
                    send(batch)
            except Exception as e:  # the batch failed; the run goes on
                w.raised.append((n, repr(e)))
            w.sends.append((t_send, clock()))
            n += 1
        dep.drain()
        w.t1 = clock()
    finally:
        faulthandler.cancel_dump_traceback_later()
    if profile is not None and w.clean is not None and not (
            w.traced and w.traced[1] is not None):
        profile.stop()
        w.traced = [w.traced[0] if w.traced else n, n]
    w.n_sent = n
    if total is not None:
        w.late = [s[0] - d for s, d in zip(w.sends, w.due)]
    return w
