"""What key skew does to a batch, from the program's flight-recorder
ring (``@app:trace(sample='1')``, traced runs only): how many rounds the
round plan cut the batch into (its longest run of one key), what the
plan cost, how many calls of a jitted step the batch took, and how many
match rows it delivered.  Read over the same clean batches, and with the
same arithmetic, as ``program_spans.py``.  A program that records no
``plan`` span (a commit before PR 28) yields nothing for the two metrics
that read it."""

from program_spans import COUNT, _clean, _per_batch

# metric (the part after the prefix) -> the stage it sums, and the
# tuple field if it sums a count and not the seconds
STAGE_OF = {
    "rounds_per_batch": ("plan", COUNT),
    "plan_ms_per_batch": ("plan", None),
    "dispatches_per_batch": ("dispatch", COUNT),
    "rows_per_batch": ("deliver", COUNT),
}


def read(run):
    out, clean = {}, None
    for name in run.wanted:
        what = name.split(".", 1)[-1]
        if what not in STAGE_OF:
            continue
        clean = clean or _clean(run)
        value = _per_batch(clean, *STAGE_OF[what])
        if value is not None:
            out[name] = value
    return out
