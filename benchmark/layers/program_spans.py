"""The program's own spans inside ``send_batch``, from its
flight-recorder ring (``@app:trace(sample='1')``, traced runs only):
interning, lane conversion, the H2D puts and their bytes, shard
routing, the wait on the count gate, and what ``send_batch`` spends
where the program has no span.  Read over the same clean batches as
``host_spans.py``: from the window's start to the profiler's.  A
program that records no such span (an older commit) yields nothing for
that metric."""

import bisect

CYCLE, STAGE, T_START, T_END, COUNT = 0, 1, 3, 4, 5   # the ring's tuples


def _clean(run):
    """The ring's spans that start in the clean part of the window, and
    that part's bounds.  The ring may have evicted the window's first
    cycles: then the part starts at the oldest span it still holds."""
    w = run.window
    hi = w.sends[w.clean][0] if w.clean is not None else float("inf")
    spans = [s for s in run.ring_spans if w.t0 <= s[T_START] < hi]
    if not spans:
        return [], hi, hi
    evicted = run.ring_spans[0][T_START] > w.t0
    lo = min(s[T_START] for s in spans) if evicted else w.t0
    return spans, lo, hi


def _per_batch(clean, stage, field=None):
    """Mean per cycle of the summed seconds (or of the summed count
    field) of every span of ``stage``: all rounds of a cycle count."""
    spans, _lo, _hi = clean
    of_stage = [s for s in spans if s[STAGE] == stage]
    if not of_stage:
        return None
    cycles = len({s[CYCLE] for s in spans})
    if field is None:
        return 1e3 * sum(s[T_END] - s[T_START] for s in of_stage) / cycles
    return sum(s[field] for s in of_stage) / cycles


def _unattributed_ms(clean, run):
    """Mean over the clean sends of the part of each ``send_batch``
    interval that no span of the ring covers."""
    spans, lo, hi = clean
    sends = [(a, b) for a, b in run.window.sends if lo <= a and b <= hi]
    if not spans or not sends:
        return None
    starts, ends, reach = [], [], []       # the union, and its running sum
    for a, b in sorted((s[T_START], s[T_END]) for s in run.ring_spans):
        if ends and a <= ends[-1]:
            if b > ends[-1]:
                reach[-1] += b - ends[-1]
                ends[-1] = b
        else:
            starts.append(a)
            ends.append(b)
            reach.append((reach[-1] if reach else 0.0) + b - a)

    def covered_to(t):
        """Seconds of the union that lie before ``t``."""
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        return reach[i] - max(0.0, ends[i] - t)

    bare = sum((b - a) - (covered_to(b) - covered_to(a)) for a, b in sends)
    return 1e3 * bare / len(sends)


# metric (the part after the prefix) -> the stage it sums, and the
# tuple field if it sums a count and not the seconds
STAGE_OF = {
    "intern_ms_per_batch": ("intern", None),
    "convert_ms_per_batch": ("convert", None),
    "put_ms_per_batch": ("put", None),
    "h2d_bytes_per_batch": ("put", COUNT),
    "route_ms_per_batch": ("route", None),
    "step_wait_ms_per_batch": ("step", None),
}
UNATTRIBUTED = "host_unattributed_ms_per_batch"


def read(run):
    out, clean = {}, None
    for name in run.wanted:
        what = name.split(".", 1)[-1]
        if what not in STAGE_OF and what != UNATTRIBUTED:
            continue
        clean = clean or _clean(run)
        value = (_unattributed_ms(clean, run) if what == UNATTRIBUTED
                 else _per_batch(clean, *STAGE_OF[what]))
        if value is not None:
            out[name] = value
    return out
