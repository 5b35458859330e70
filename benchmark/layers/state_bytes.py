"""The resident state a batch's steps touch, from the program's
flight-recorder ring (``@app:trace(sample='1')``, traced runs only):
the dense engine's ``state_bytes`` count, one zero-width tuple a batch,
is the lanes its programs step (``lanes.py``) times the bytes of a
resident row, ``layout.width * 4``: what the steps gather of the state
and, row for row, write back.  Over ``gather_ms_per_batch`` and
``scatter_ms_per_batch`` it is the bandwidth the step reaches at that
row width.  Read over the same clean batches, and with the same
arithmetic, as ``program_spans.py``.  A program that records no such
count (a commit before PR 60) yields nothing."""

from program_spans import COUNT, _clean, _per_batch

NAME = "gathered_bytes_per_batch"


def read(run):
    names = [n for n in run.wanted if n.split(".", 1)[-1] == NAME]
    if not names:
        return {}
    value = _per_batch(_clean(run), "state_bytes", COUNT)
    return {} if value is None else dict.fromkeys(names, value)
