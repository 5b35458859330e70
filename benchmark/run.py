#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, loads its configuration and
its traffic mix by name, imports the mix's generator and the per-layer
readers, and holds no table of cells, configurations, mixes or metrics.
One process, no children.  Without a TPU it measures nothing (exit 2, no
result line); ``--rehearsal`` runs tiny sizes on any backend and marks
its line so that nobody takes it for a chip result.  See ``README.md``.
"""

from __future__ import annotations

import argparse
import gc
import glob
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def say(msg: str) -> None:
    print(msg, flush=True)


def load(path: str):
    with open(path) as f:
        return json.load(f)


def metrics_of(bench: dict, kind: str, cell: str):
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def latencies(window, collector):
    """Open loop: per batch that delivered matches, the milliseconds from
    when it was due to the callback that delivered its last row."""
    lat = {n: 1e3 * (collector.last_seen[n] - due)
           for n, due in enumerate(window.due) if n in collector.last_seen}
    if lat:
        late = sorted(window.late)
        say(f"latency samples: {len(lat)} batches delivered matches, 95th "
            f"percentile {statistics.quantiles(lat.values(), n=20)[-1]:.3f}"
            f" ms; the generator sent {1e3 * late[len(late) // 2]:.3f} ms "
            f"late at the median, {1e3 * late[len(late) * 19 // 20]:.3f} at "
            f"the 95th percentile, at most {1e3 * late[-1]:.3f} (batch "
            f"{window.late.index(late[-1])}; the profiler started at "
            f"batch {window.clean})")
    return lat


def end_to_end(window, collector, batch_events: int, setup_s: float):
    """Every end-to-end number the window yields; the cell's entries in
    ``BENCHMARK.json`` say which of them it reports."""
    span = window.t1 - window.sends[0][0]
    out = {"setup_s": setup_s,
           "events_per_s": window.n_sent * batch_events / span,
           "rows_per_s": sum(c for n, c in collector.counts.items()
                             if n >= 0) / span}
    if len(window.latency_ms) >= 20:
        out["latency_p50_ms"] = statistics.median(window.latency_ms.values())
    return out


class Profile:
    """A few seconds of the steady window under ``jax.profiler``."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.window = None

    def start(self):
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)

    def mark(self):
        self.window = self.jax.profiler.TraceAnnotation("bench.window")
        self.window.__enter__()

    def stop(self):
        if self.window is not None:
            self.window.__exit__(None, None, None)
        self.jax.profiler.stop_trace()


def round_bf16(batch, columns):
    """The control: the named columns reach the device as bfloat16
    would hold them; the references keep what the schedule made."""
    import ml_dtypes

    from siddhi_tpu.core.event import EventBatch

    cols = dict(batch.columns)
    for c in columns:
        if c not in cols:   # a column of another input stream
            continue
        cols[c] = cols[c].astype(ml_dtypes.bfloat16).astype(cols[c].dtype)
    return EventBatch(batch.stream_id, batch.attribute_names, cols,
                      batch.timestamps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on any backend; not a chip result")
    ap.add_argument("--loop", choices=("closed", "open"), default=None,
                    help="sweep: drive the mix under the other loop, to find "
                         "the capacity a paced rate is set below")
    ap.add_argument("--control", choices=("bf16",), default=None,
                    help="lower precision on the way to the device: the "
                         "run must come out not correct")
    args = ap.parse_args(argv)

    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"no cell named {args.workload}", file=sys.stderr)
        return 2
    config = load(os.path.join(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"])))
    traffic = load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    if args.rehearsal:
        traffic.update({k: v for k, v in traffic["rehearsal"].items()
                        if k in traffic})
    if args.loop:
        traffic["loop"] = args.loop
    seconds = args.seconds or bench["run_seconds"]

    for p in (ROOT, BENCH, os.path.join(BENCH, "generators"),
              os.path.join(BENCH, "layers")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from lib import check

    try:   # a kind with no file stops the run here, before JAX is imported
        reference = check.load_reference(config["reference"]["kind"])
    except FileNotFoundError as e:
        print(f"benchmark: {e}; nothing was measured", file=sys.stderr)
        return 2
    from siddhi_tpu.util.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    from lib import deploy, loops, xplane

    platform = jax.default_backend()
    if not args.rehearsal and (platform != "tpu"
                               or len(jax.devices()) < cell["chips"]):
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(jax.devices())} '{platform}' "
              "device(s); nothing was measured", file=sys.stderr)
        return 2
    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']} ({traffic['loop']} loop), seed {args.seed}, "
        f"{seconds} s, platform {platform}"
        + ("  REHEARSAL" if args.rehearsal else "")
        + (f"  CONTROL {args.control}" if args.control else ""))

    meter = deploy.CompileMeter(jax)
    ages = [("jax up", deploy.process_age_s())]
    schedule = importlib.import_module(traffic["generator"]).make(
        args.seed, config, traffic, args.rehearsal)
    dep = deploy.Deployment(config, schedule, args.rehearsal, bool(args.trace))
    ages.append(("traffic made, app built", deploy.process_age_s()))
    send = dep.send
    if args.control:
        def send(batch, _send=send, _cols=config["control"]["round_bf16"]):
            _send(round_bf16(batch, _cols))
    try:
        for n in range(-schedule.warmup, 0):   # interns keys, compiles shapes
            send(schedule.batch(n))
        dep.drain()
        dep.overflow()   # the engine polls it every 256 steps: compile it now
        gc.collect()
        gc.freeze()
        programs, compile_s = meter.programs, meter.seconds
        setup_s = deploy.process_age_s()
        say(f"set-up {setup_s:.3f} s ("
            + ", ".join(f"{what} at {age:.3f}" for what, age in ages)
            + f", then {schedule.warmup} warm-up batches): {programs} "
            f"programs obtained in {compile_s:.3f} s, {meter.cache_hits} "
            f"from the compile cache "
            f"({jax.config.jax_compilation_cache_dir})")

        profile = Profile(jax) if args.trace else None
        window = loops.run(dep, schedule, traffic, seconds, send, profile)
        say(f"window {window.t1 - window.t0:.3f} s: {window.n_sent} batches "
            f"of {schedule.batch_events} events sent, "
            f"{window.n_sent / (window.t1 - window.t0):.3f} a second; "
            f"programs compiled in "
            f"the window: {meter.programs - programs}")
        window.latency_ms = latencies(window, dep.collector)
        device = deploy.device_line(jax)

        t_ref = time.perf_counter()
        answers = reference(config["reference"], schedule, dep.collector,
                            window.n_sent, args.seed, args.rehearsal)
        correct, attempted, failed, compared = check.judge(
            dep, schedule, window, answers, platform)
        say(f"reference and judgement took "
            f"{time.perf_counter() - t_ref:.3f} s, after the clock stopped")
        for name, value, limit in compared:
            say(f"compared: {name}: {value} (limit {limit})")
        for n, err in window.raised[:5]:
            say(f"send of batch {n} raised {err}")

        wanted = metrics_of(bench, "per_layer" if args.trace else
                            "end_to_end", cell["name"])
        if args.trace:
            t_trace = time.perf_counter()
            try:   # the file is read once; the readers are handed the trace
                batches = window.traced[1] - window.traced[0]
                trace = xplane.read_dir(profile.dir, batches)
                traced = xplane.reduce(trace) if trace else None
                run = types.SimpleNamespace(
                    wanted=[m["name"] for m in wanted], window=window,
                    ring_spans=dep.ring_spans(), xplane=traced, trace=trace,
                    traced_batches=batches, setup_compile_s=compile_s)
                values = {}
                for path in sorted(glob.glob(os.path.join(BENCH, "layers",
                                                          "*.py"))):
                    reader = importlib.import_module(
                        os.path.splitext(os.path.basename(path))[0])
                    values.update(reader.read(run))
            finally:
                shutil.rmtree(profile.dir, ignore_errors=True)
            say(f"trace read, {len(values)} per-layer values reduced from it "
                f"in {time.perf_counter() - t_trace:.3f} s, after the clock "
                "stopped")
            if traced:
                device.update(busy_s=traced["busy_s"],
                              window_s=traced["window_s"])
        else:
            traced = None
            values = end_to_end(window, dep.collector,
                                schedule.batch_events, setup_s)
        result = {
            "correct": correct, "attempted": attempted, "failed": failed,
            "metrics": {m["name"]: {"value": values[m["name"]],
                                    "unit": m["unit"]}
                        for m in wanted if m["name"] in values},
            "device": device}
        if traced:
            result["breakdown"] = {k: traced[k]
                                   for k in ("device_ops", "idle_gaps")}
        if args.rehearsal:
            result = {"rehearsal": True, **result}
        if args.control:
            result = {"control": args.control, **result}
    finally:
        dep.shutdown()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
