"""Device layers, from the profiler's trace of a few seconds of the
steady window (``lib/xplane.py``): busy time per batch and idle share."""


def read(run):
    x, out = run.xplane, {}
    if not x or not run.traced_batches:
        return out
    for name in run.wanted:
        what = name.split(".", 1)[-1]
        if what == "device_busy_ms_per_batch":
            out[name] = 1e3 * x["busy_s"] / run.traced_batches
        elif what == "device_idle_share":
            out[name] = 100.0 * (1.0 - x["busy_s"] / x["window_s"])
    return out
