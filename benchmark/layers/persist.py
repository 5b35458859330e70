"""A deployment's own checkpoints, from the program's flight-recorder
ring (``@app:trace(sample='1')``, traced runs only): what a checkpoint
holds the barrier for (the ``persist.capture`` span, taken with the
app's process lock held: what the stream feels), how much of that is the
state's fetch from the device (its ``persist.fetch`` children), what the
writer thread then spends beside the stream (``persist.write``: pickle,
hash, the store's write and fsync) and on how many bytes (the count of
its ``persist.store`` children), and the share of the clean part of the
window that a capture held the barrier.  Each a mean over the
checkpoints whose capture began in the clean part, the batches before
the profiler started, as ``program_spans.py`` reads them.

No kernel is read here and no roofline share is owed: the fetch is a
transfer, which owns no operation of the device plane.  A program that
records no such span (a commit before PR 48, a deployment that does not
checkpoint) yields nothing."""

from program_spans import COUNT, CYCLE, STAGE, T_END, T_START, _clean

# metric (the part after the prefix) -> the stage whose spans it sums
# and the tuple field if it sums a count and not the seconds; each is
# divided by the number of spans of the stage named last
PER_CHECKPOINT = {
    "persist_capture_ms_per_checkpoint": ("persist.capture", None,
                                          "persist.capture"),
    "persist_fetch_ms_per_checkpoint": ("persist.fetch", None,
                                        "persist.capture"),
    "persist_write_ms_per_checkpoint": ("persist.write", None,
                                        "persist.write"),
    "persist_bytes_per_checkpoint": ("persist.store", COUNT,
                                     "persist.write"),
}
STALL_SHARE = "persist_stall_share"


def _checkpoints(run):
    """The ring's persist spans by stage, of the checkpoints whose
    parent span (capture or write; a child carries its parent's cycle
    id) began in the clean part; and that part's length."""
    _spans, lo, hi = _clean(run)
    by_stage = {}
    parents = {s[CYCLE] for s in run.ring_spans
               if s[STAGE] in ("persist.capture", "persist.write")
               and lo <= s[T_START] < hi}
    for s in run.ring_spans:
        if s[CYCLE] in parents and s[STAGE].startswith("persist."):
            by_stage.setdefault(s[STAGE], []).append(s)
    return by_stage, hi - lo


def read(run):
    out, found = {}, None
    for name in run.wanted:
        what = name.split(".", 1)[-1]
        if what not in PER_CHECKPOINT and what != STALL_SHARE:
            continue
        found = found or _checkpoints(run)
        by_stage, clean_s = found
        if what == STALL_SHARE:
            held = by_stage.get("persist.capture")
            if held and clean_s > 0:
                out[name] = 100.0 * sum(
                    s[T_END] - s[T_START] for s in held) / clean_s
            continue
        stage, field, per = PER_CHECKPOINT[what]
        spans, n = by_stage.get(stage), len(by_stage.get(per, ()))
        if not spans or not n:
            continue
        out[name] = (sum(s[field] for s in spans) / n if field is not None
                     else 1e3 * sum(s[T_END] - s[T_START]
                                    for s in spans) / n)
    return out
