"""Build one deployment through ``SiddhiManager``, collect what it
emits, and say whether it runs on the path its cell is for."""

from __future__ import annotations

import collections
import contextlib
import os
import time

import numpy as np

from siddhi_tpu.core.stream import StreamCallback


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's record."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter()  # no /proc: time since first clock read


class CompileMeter:
    """Counts what JAX compiles (or fetches from the persistent cache)
    and how long obtaining the executables took.  From chip_smoke.py."""

    def __init__(self, jax):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Collector(StreamCallback):
    """The user's callback.  Stamps each delivery, counts rows per input
    batch (named by the rows' event timestamps) and keeps the rows of
    the batches the schedule wants checked, plus the newest ``tail``."""

    def __init__(self, schedule, tail: int, span):
        self.schedule = schedule
        self.span = span
        self.counts = collections.Counter()
        self.last_seen = {}            # batch index -> time of its last row
        self.kept = {}                 # batch index -> [EventBatch]
        self.tail = collections.deque(maxlen=tail)

    def receive_batch(self, batch):
        t = time.perf_counter()
        with self.span("bench.callback"):
            idx = self.schedule.batch_of(batch.timestamps)
            lo, hi = int(idx.min()), int(idx.max())
            if lo == hi:
                self.counts[lo] += len(idx)
                self.last_seen[lo] = t
            else:
                for n, c in zip(*np.unique(idx, return_counts=True)):
                    self.counts[int(n)] += int(c)
                    self.last_seen[int(n)] = t
            if hi < 0:
                return  # warm-up rows
            if all(self.schedule.keep(n) for n in range(lo, hi + 1)):
                self.kept.setdefault(lo, []).append(batch)
            else:
                self.tail.append((lo, batch))

    def rows(self, wanted=None):
        """Kept rows (and the tail's) as columns, in delivery order,
        with ``_n`` the input batch of each row."""
        batches = [b for n in sorted(self.kept) for b in self.kept[n]]
        batches += [b for _n, b in self.tail]
        if not batches:
            return None
        out = {name: np.concatenate([np.asarray(b.columns[name])
                                     for b in batches])
               for name in batches[0].attribute_names}
        out["_ts"] = np.concatenate([b.timestamps for b in batches])
        out["_n"] = self.schedule.batch_of(out["_ts"])
        return out


def sender(handlers: dict):
    """What the window calls with every batch.  One input stream: the
    handler's bound ``send_batch`` itself, no wrapper and no lookup in
    the timed path.  Several: each batch goes to the handler of its
    ``stream_id``."""
    if len(handlers) == 1:
        (handler,) = handlers.values()
        return handler.send_batch
    sends = {stream: h.send_batch for stream, h in handlers.items()}
    return lambda batch: sends[batch.stream_id](batch)


class Deployment:
    def __init__(self, config, schedule, rehearsal: bool, traced: bool):
        from siddhi_tpu import SiddhiManager

        import jax

        size = config["rehearsal" if rehearsal else "full"]
        header = config["header"].format(**size)
        if traced:
            header += " @app:trace(sample='1', cycles='4096')"
        self.span = (jax.profiler.TraceAnnotation if traced
                     else lambda _name: contextlib.nullcontext())
        self.config = config
        self.manager = SiddhiManager()
        self.rt = self.manager.create_siddhi_app_runtime(
            header + " " + config["app"])
        self.errors = []
        self.rt.add_exception_listener(self.errors.append)
        self.collector = Collector(
            schedule, config["reference"].get("edge_batches", 1), self.span)
        self.rt.add_callback(config["output"], self.collector)
        self.rt.start()
        # ``stream``: one name, or a list where the app has several inputs
        streams = config["stream"]
        self.send = sender({s: self.rt.get_input_handler(s) for s in (
            [streams] if isinstance(streams, str) else streams)})
        # every device engine of the app, whichever path it lowered to
        self.engines = [q.pattern_processor
                        for pr in self.rt.partitions.values()
                        for q in getattr(pr, "dense_query_runtimes",
                                         {}).values()]
        self.engines += [qr.device_runtime
                         for qr in self.rt.query_runtimes.values()
                         if getattr(qr, "device_runtime", None) is not None]

    def drain(self):
        """The runtime's flush barrier: every staged batch stepped,
        every pending match fetched and delivered."""
        for eng in self.engines:
            eng.drain()

    def dropped_batches(self) -> int:
        return sum(e.emit_stats.dropped_batches
                   + e.ingest_stats.dropped_batches for e in self.engines)

    def overflow(self) -> int:
        return sum(e.overflow_total() for e in self.engines
                   if hasattr(e, "overflow_total"))

    def ring_spans(self):
        tracer = self.rt.app_context.tracer
        return list(tracer.recorder.spans()) if tracer is not None else []

    def off_path(self, platform: str):
        """Why the deployment is not on the path its cell is for; empty
        when it is.  The checks ``chip_smoke.py`` asserts."""
        import jax

        want, sm = self.config["expect"], self.rt.app_context.statistics_manager
        why = []
        if not self.engines:
            return ["no device engine was built"]
        if self.rt.lowering() != want["lowering"]:
            why.append(f"lowering {self.rt.lowering()}")
        if sm.device_fallbacks or sm.sharded_fallbacks:
            why.append(f"fallbacks {dict(sm.device_fallbacks)} "
                       f"{dict(sm.sharded_fallbacks)}")
        devices = {d for e in self.engines
                   for arr in jax.tree_util.tree_leaves(e.state)
                   for d in arr.devices()}
        if {d.platform for d in devices} != {platform}:
            why.append(f"state on {sorted(map(str, devices))}")
        sharded = any(getattr(e, "_sharded", None) is not None
                      for e in self.engines)
        if sharded != want["sharded"] or (
                platform == "tpu" and len(devices) != want["state_devices"]):
            why.append(f"sharded {sharded} over {len(devices)} device(s)")
        if not all(e.step_invocations > 0 for e in self.engines):
            why.append("an engine never stepped")
        for pr in self.rt.partitions.values():
            if not getattr(pr, "is_dense", True):
                why.append("partition did not lower to the dense engine")
        return why

    def shutdown(self):
        self.rt.shutdown()
        self.manager.shutdown()


def device_line(jax) -> dict:
    devs = jax.local_devices()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs), default=0)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}
