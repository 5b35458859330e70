"""The entry layer from the inside, from the program's flight-recorder
ring (``@app:trace(sample='1')``, traced runs only).  Read over the same
clean batches, and with the same arithmetic, as ``program_spans.py``:
from the window's start to the profiler's.

- ``*.admit_ms_per_batch``: the ``admit`` spans' seconds over the cycles
  of the clean part.  ``admit`` runs from ``InputHandler.send_batch``'s
  entry stamp to the runtime's ``begin_cycle``: the newest timestamp of
  the batch, admission, the process lock, the journal hook, the
  scheduler's advance, the junction and the receiver's lead.  Until the
  program recorded it, this time was part of
  ``*.host_unattributed_ms_per_batch``, which falls by it.
- ``*.send_steady_share``: 100 less the microseconds of the ``stall.*``
  tuples of the clean part over that part's seconds.  The program
  writes one zero-width tuple, stage ``stall.<cause>``, the count field
  the stall's microseconds, for every send that lasted at least 8 times
  its thread's typical send and at least 50 ms
  (``siddhi_tpu/observability/stall.py``).  100.0 on a sound run; 82.6
  on a run whose clean 23 s hold one stall of 4 s: a line that was
  measured on a stalled run shows it.  Written as the steady share and
  not the stalled one so that it is above 0 on every run.

A program that records neither (a commit before PR 55: no ``admit`` span
in its ring) yields nothing for either."""

from program_spans import COUNT, STAGE, _clean, _per_batch

ADMIT_MS = "admit_ms_per_batch"
STEADY_SHARE = "send_steady_share"
STALL = "stall."


def read(run):
    wanted = {name: what for name in run.wanted
              if (what := name.split(".", 1)[-1]) in (ADMIT_MS, STEADY_SHARE)}
    if not wanted:
        return {}
    clean = _clean(run)
    spans, lo, hi = clean
    values = {ADMIT_MS: _per_batch(clean, "admit")}
    # a program with no ``admit`` writes no stall tuple either: nothing,
    # not a steady 100
    if values[ADMIT_MS] is not None and hi > lo:
        stalled_us = sum(s[COUNT] for s in spans
                         if s[STAGE].startswith(STALL))
        values[STEADY_SHARE] = 100.0 - 100.0 * stalled_us * 1e-6 / (hi - lo)
    return {name: values[what] for name, what in wanted.items()
            if values.get(what) is not None}
