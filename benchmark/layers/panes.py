"""Tumbling panes, from the program's flight-recorder ring
(``@app:trace(sample='1')``, traced runs only): how many panes a batch
closed and what the host's pane bookkeeping cost (the open pane's
carried rows joined to the batch, the rows of whole panes cut off for
the device).  Read over the same clean batches, and with the same
arithmetic, as ``program_spans.py``.  A program that records no ``pane``
span (a commit before PR 33, a query that is not ``lengthBatch``)
yields nothing."""

from program_spans import COUNT, _clean, _per_batch

# metric (the part after the prefix) -> the tuple field if it sums the
# ``pane`` spans' count and not their seconds
FIELD_OF = {"panes_per_batch": COUNT, "pane_ms_per_batch": None}


def read(run):
    out, clean = {}, None
    for name in run.wanted:
        what = name.split(".", 1)[-1]
        if what not in FIELD_OF:
            continue
        clean = clean or _clean(run)
        value = _per_batch(clean, "pane", FIELD_OF[what])
        if value is not None:
            out[name] = value
    return out
