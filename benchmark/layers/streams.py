"""What the batches of a pattern's second input stream owe, from the
program's flight-recorder ring (``@app:trace(sample='1')``, traced runs
only).  The dense engine's ``stream`` count, one zero-width tuple a
batch, is the place of the batch's stream among the streams its pattern
reads: 0 for the first, 1 for the second.  Over the clean cycles whose
count is 1, joined to their spans by cycle id:

- ``stream2_rows_per_batch``: the rows their ``deliver`` spans count, a
  cycle that delivered nothing reading 0;
- ``stream2_emit_ms_per_batch``: their ``fetch``, ``build`` and
  ``deliver`` spans, summed.

On ``ticknews_1m`` these are the news batches, one in sixteen, whose
burst of rows the means over all batches (``rows_per_batch``,
``emit_ms_per_batch``) spread thin.  Read over the same clean batches,
and with the same arithmetic, as ``program_spans.py``.  A program that
records no ``stream`` count (a commit before PR 57), and a window with
no batch of a second stream, yield nothing."""

from program_spans import COUNT, CYCLE, STAGE, T_END, T_START, _clean

ROWS, EMIT_MS = "stream2_rows_per_batch", "stream2_emit_ms_per_batch"
WAY_BACK = ("fetch", "build", "deliver")


def read(run):
    names = {n: what for n in run.wanted
             if (what := n.split(".", 1)[-1]) in (ROWS, EMIT_MS)}
    if not names:
        return {}
    spans = _clean(run)[0]
    second = {s[CYCLE] for s in spans
              if s[STAGE] == "stream" and s[COUNT] == 1}
    if not second:
        return {}
    back = [s for s in spans if s[CYCLE] in second and s[STAGE] in WAY_BACK]
    values = {
        ROWS: sum(s[COUNT] for s in back if s[STAGE] == "deliver")
        / len(second),
        EMIT_MS: 1e3 * sum(s[T_END] - s[T_START] for s in back)
        / len(second)}
    return {n: values[what] for n, what in names.items()}
