"""A device table's mutations, from the program's flight-recorder ring
(``@app:trace(sample='1')``, traced runs only): what the host spends on
a batch a table callback hands to the table (the ``mutate`` span: the
passes over the key map and the slot allocation, the lanes, the put and
the call of the scatter, in one span with no child) and how many keys
the table was handed.  Read over the same clean batches, and with the
same arithmetic, as ``program_spans.py``: the mean over every cycle of
the window, probe batches and upsert batches alike.  A program that records no ``mutate``
span (a commit before PR 42, an app with no device table) yields
nothing."""

from program_spans import COUNT, _clean, _per_batch

# metric (the part after the prefix) -> the tuple field if it sums the
# ``mutate`` spans' count and not their seconds
FIELD_OF = {"mutate_keys_per_batch": COUNT, "mutate_ms_per_batch": None}


def read(run):
    out, clean = {}, None
    for name in run.wanted:
        what = name.split(".", 1)[-1]
        if what not in FIELD_OF:
            continue
        clean = clean or _clean(run)
        value = _per_batch(clean, "mutate", FIELD_OF[what])
        if value is not None:
            out[name] = value
    return out
