"""The lanes a batch costs the device, from the program's
flight-recorder ring (``@app:trace(sample='1')``, traced runs only):
the dense engine's ``lanes`` count, one tuple a batch, is the width its
first program was padded to plus, for every later round, the static
width the rounds program sliced it at (a link of the run: 128).  Over
the batch's events it is the lanes stepped an event; what is over 1 is
padding.  Read over the same clean batches, and with the same
arithmetic, as ``program_spans.py``.  A program that records no such
count (a commit before PR 51) yields nothing."""

from program_spans import COUNT, _clean, _per_batch

NAME = "stepped_lanes_per_batch"


def read(run):
    names = [n for n in run.wanted if n.split(".", 1)[-1] == NAME]
    if not names:
        return {}
    value = _per_batch(_clean(run), "lanes", COUNT)
    return {} if value is None else dict.fromkeys(names, value)
