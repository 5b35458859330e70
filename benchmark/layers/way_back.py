"""A batch's way back, from the program's flight-recorder ring
(``@app:trace(sample='1')``, traced runs only): what ``emit`` is made
of (the coalesced fetch and its bytes, the rows built from the fetched
arrays, their delivery to the output chain and the callback), the call
of the jitted program (``dispatch``, as seconds), the overflow poll,
how long a dispatched batch's count gate was left staged behind the
next batch's dispatch, and how soon a batch's matches reach the
callback.  Read over the same clean batches, and with the same
arithmetic, as ``program_spans.py``.

The last three join the ring's tuples by cycle id; the program records
no tuple of their own for them (one would lie over other batches'
spans, and ``host_unattributed`` is what no span of the ring covers):

- ``staged``: the start of a cycle's ``step`` less the end of its
  ``ingest``: 0 for a gate finished inline, where ``step`` starts at
  the dispatch.  ``staged_share``: the percentage of cycles in which it
  exceeds ``DEFERRED_S``; 0, not absent, where none did.
- ``match_delay``: from the start of a cycle's first span to the end of
  its ``emit``, the median over the cycles that emitted.

A program that records no such span (an older commit has no ``build``
and no ``poll``) yields nothing for that metric.  The poll comes every
256th step: where the ring holds one and the clean batches none (the
paced cell owes one a window), the metric reads 0 for them."""

import statistics

from program_spans import (COUNT, CYCLE, STAGE, T_END, T_START, _clean,
                           _per_batch)

# metric (the part after the prefix) -> the stage it sums, and the
# tuple field if it sums a count and not the seconds
STAGE_OF = {
    "fetch_ms_per_batch": ("fetch", None),
    "d2h_bytes_per_batch": ("fetch", COUNT),
    "build_ms_per_batch": ("build", None),
    "deliver_ms_per_batch": ("deliver", None),
    "dispatch_ms_per_batch": ("dispatch", None),
    "poll_ms_per_batch": ("poll", None),
}
POLL_MS = "poll_ms_per_batch"
STAGED_MS, STAGED_SHARE = "staged_ms_per_batch", "staged_share"
MATCH_DELAY = "match_delay_ms_p50"
# a gate finished inline starts its ``step`` at the very reading that
# ends ``ingest``; one left staged waits out the next batch's way in,
# milliseconds: anything past this is a deferred gate
DEFERRED_S = 50e-6


def _by_cycle(clean):
    """cycle id -> {stage: its first span} over the clean spans."""
    out = {}
    for s in clean[0]:
        out.setdefault(s[CYCLE], {}).setdefault(s[STAGE], s)
    return out


def _staged(cycles):
    """Seconds each cycle's gate was left staged, for the cycles whose
    ``ingest`` and ``step`` the ring still holds."""
    return [max(0.0, by["step"][T_START] - by["ingest"][T_END])
            for by in cycles.values() if "ingest" in by and "step" in by]


def _match_delays(cycles):
    """Seconds from a cycle's first span (siblings never overlap, so it
    is the first of its stage) to the end of its ``emit``, for the
    cycles that emitted and whose way in the ring still holds."""
    return [by["emit"][T_END] - min(s[T_START] for s in by.values())
            for by in cycles.values() if "emit" in by and "ingest" in by]


def read(run):
    wanted = {name: what for name in run.wanted
              if (what := name.split(".", 1)[-1]) in STAGE_OF
              or what in (STAGED_MS, STAGED_SHARE, MATCH_DELAY)}
    if not wanted:
        return {}
    clean = _clean(run)
    values = {what: _per_batch(clean, *of) for what, of in STAGE_OF.items()}
    if values[POLL_MS] is None and clean[0] and any(
            s[STAGE] == "poll" for s in run.ring_spans):
        values[POLL_MS] = 0.0   # it polls, and not among these batches
    cycles = _by_cycle(clean)
    staged, delays = _staged(cycles), _match_delays(cycles)
    if staged:
        values[STAGED_MS] = 1e3 * statistics.fmean(staged)
        values[STAGED_SHARE] = 100.0 * sum(
            s > DEFERRED_S for s in staged) / len(staged)
    if delays:
        values[MATCH_DELAY] = 1e3 * statistics.median(delays)
    return {name: values[what] for name, what in wanted.items()
            if values.get(what) is not None}
