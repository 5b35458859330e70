"""The tail beside the end-to-end median: the 95th percentile of an
open loop's latencies, from when a batch was due to the callback that
delivered its last row.  Between runs it spreads twice as widely as the
median (PERF.md), so it is read here, with no bound."""

import statistics


def read(run):
    # batches sent before the profiler started: its start stalls the host
    clean = run.window.clean
    lat = [ms for n, ms in run.window.latency_ms.items()
           if clean is None or n < clean]
    if len(lat) < 10:   # a rehearsal's two seconds yield a dozen
        return {}
    return {name: statistics.quantiles(lat, n=20)[-1]
            for name in run.wanted if name.split(".", 1)[-1] == "p95_ms"}
