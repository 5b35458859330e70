"""Host-clock layers: the harness's span around ``send_batch``, the
program's ``ingest`` and ``emit`` spans from its flight-recorder ring
(``@app:trace(sample='1')``, traced runs only), and how late the
open-loop generator ran."""

import statistics

T_START, T_END, STAGE = 3, 4, 1   # the ring's span tuple layout


def _stage_ms_per_batch(run, stage):
    # from the window's start to the profiler's: its start stalls the host
    w = run.window
    lo = w.t0
    hi = w.sends[w.clean][0] if w.clean is not None else float("inf")
    spans = [s for s in run.ring_spans if lo <= s[T_START] < hi]
    cycles = {s[0] for s in spans}
    spans = [s for s in spans if s[STAGE] == stage]
    if not spans or not cycles:
        return None
    return 1e3 * sum(s[T_END] - s[T_START] for s in spans) / len(cycles)


def _send_ms(run):
    # batches sent before the profiler started: its start stalls the host
    return 1e3 * statistics.fmean(
        b - a for a, b in run.window.sends[:run.window.clean])


def _late_p95(run):
    late = run.window.late[:run.window.clean]
    if len(late) < 20:
        return None
    return 1e3 * statistics.quantiles(late, n=20)[-1]


READ = {
    "send_ms_per_batch": _send_ms,
    "ingest_ms_per_batch": lambda run: _stage_ms_per_batch(run, "ingest"),
    "emit_ms_per_batch": lambda run: _stage_ms_per_batch(run, "emit"),
    "generator_late_ms_p95": _late_p95,
}


def read(run):
    out = {}
    for name in run.wanted:
        fn = READ.get(name.split(".", 1)[-1])
        value = fn(run) if fn else None
        if value is not None:
            out[name] = value
    return out
