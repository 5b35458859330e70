"""The stall watch: why a send ran far past its usual length.

``InputHandler.send_batch`` / ``send`` and an async junction's worker
stamp the tracer's clock at entry and at exit of every batch, whatever
the sample (``Tracer.send_begins`` / ``send_ends``); each sending thread
keeps a running typical send, an exponentially weighted mean (weight
1/64) seeded by the shortest of its first eight sends, which compile
and are never judged.  A send of at least ``STALL_FACTOR`` times the
typical one and at least ``STALL_FLOOR_S`` is a stall, and leaves one
record.  What the record holds beside the interval costs a send that is
not stalled nothing:

- **where the host was**: one daemon thread per app (started by the send
  that ends the seeding, stopped by ``shutdown()``) wakes every 50 ms,
  compares each open send's entry stamp with its thread's threshold and
  goes back to sleep.  Past the threshold it takes, at each wake and at
  most eight times a stall, ``sys._current_frames()``: the sender's
  innermost six frames and the innermost frame of every other thread,
  and what the sender waits for where the program marked it: the
  process lock (``Sender.waits``, set by ``core/stream.py`` round the
  acquire) or the device (a frame of a function marked
  :func:`waits_on_device`).  A stall it never woke inside has no
  samples and no process figures, and says so;
- **whether the process ran at all**: how late the watch's own wake-ups
  were inside the stall; from its first notice to the stall's end
  ``getrusage`` (processor against wall milliseconds, involuntary
  context switches, major page faults: the whole process's, the
  runtime's other threads among them); and how long the sender's own
  thread was on a processor, from the watch's last wake-up before it
  noticed to the stall's end.  That last one is not the issue's and is
  the one reading a wake takes beside the compare: a freeze of the
  process holds the watch too, so whatever it first reads at its notice
  it reads after the freeze, and the process's figures cannot say that
  the sender stood still while the runtime's other threads ran (PERF.md
  section 6, PR 55).  The clock's id is taken by the sending thread
  itself, on its first send, and read with ``clock_gettime``: a plain
  system call that fails on a thread that is gone, where
  ``pthread_getcpuclockid`` on a dead thread's ident is undefined;
- **what the interpreter and the compiler did**: one ``gc.callbacks``
  hook a process keeps the intervals of full (generation 2) collections,
  one ``jax.monitoring`` listener a process the compile events with
  their end stamps (:class:`ProcessLog`); both are read only here;
- **what else held the stream**: the ``persist.capture`` /
  ``persist.write`` free spans that overlap it, the runtime's
  pending-work gauge, the cycle id where the cycle was sampled, the
  batch's events, and the client's gap before the send (the thread's
  previous exit to this entry: the caller's time, not the program's).

A :class:`Sender` is its thread's, kept on the tracer's thread-local and
gone with the thread; the watch drops it from its list at the first wake
that finds the thread dead.

One cause, by the first rule that holds (:func:`cause_of`).  The record
goes to the flight recorder's ``stalls`` (the last 32: ``payload()``,
every dump, ``GET /siddhi-trace/<app>``), to ``statistics()`` and
``/metrics`` (``StallWatch.stats``), to one WARNING line on the logger
``siddhi_tpu.observability`` and to one zero-width tuple in the ring,
stage ``stall.<cause>``, the count field the stall's microseconds.
"""

from __future__ import annotations

import collections
import gc
import logging
import resource
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

log = logging.getLogger("siddhi_tpu.observability")

#: the watch's, the collector's and the compiler's clock: the tracer's
#: own (``Tracer.clock``) unless a test hands the tracer another, and a
#: tracer on another clock is not watched
_clock = time.perf_counter

#: a stall is a send of at least this many typical sends ...
STALL_FACTOR = 8
#: ... and at least this long.  Fixed numbers, no knob: they catch the
#: 125 ms sends on a 2.4 ms cell and the seconds on a 13 ms cell that
#: PERF.md section 6 lists, and leave a cell's ordinary tail alone
STALL_FLOOR_S = 0.050
#: a thread's first sends seed its typical send and are never judged
SEED_SENDS = 8
TYPICAL_WEIGHT = 1.0 / 64
#: the watch's period; a stall shorter than two of them may hold no wake
WATCH_PERIOD_S = 0.050
MAX_SAMPLES = 8
SENDER_FRAMES = 6
#: records the flight recorder keeps
KEPT_STALLS = 32
#: ring stage of a stall's tuple: this and the cause
STAGE_STALL = "stall."

CAUSE_GC = "gc"                    # full collections cover half of it
CAUSE_COMPILE = "compile"          # a program was traced, lowered, compiled
CAUSE_PERSIST = "persist"          # a checkpoint's capture or write overlaps
CAUSE_LOCK = "lock"                # the sender stood at the process lock
CAUSE_DEVICE_WAIT = "device_wait"  # ... in a count gate or a fetch
CAUSE_DESCHEDULED = "descheduled"  # neither the watch nor the sender ran
CAUSE_HOST = "host"                # the sender was computing: see the frames
#: the names are fixed (PERF.md section 3), in the order of their rules
CAUSES = (CAUSE_GC, CAUSE_COMPILE, CAUSE_PERSIST, CAUSE_LOCK,
          CAUSE_DEVICE_WAIT, CAUSE_DESCHEDULED, CAUSE_HOST)

#: what a sender waits for, where the program says so
WAITS_LOCK = "lock"      # ``Sender.waits``, round the process lock's acquire
WAITS_DEVICE = "device"  # a frame of a function marked ``waits_on_device``
#: the code objects of the functions that block on the device; the
#: engines mark their own (a count gate's ``resolve()``, the overflow
#: poll, the emit fetch), ``ProcessLog.install`` adds JAX's
_DEVICE_WAITS: set = set()
_COMPILE_EVENTS = ("/jax/core/compile/",
                   "/jax/compilation_cache/cache_retrieval_time_sec")
_PERSIST_STAGES = ("persist.capture", "persist.write")


def waits_on_device(fn):
    """Mark ``fn`` as one that blocks until the device answers: a
    sender sampled inside it waits for the device (``device_wait``).
    The function is handed back as it is: a mark costs a call nothing,
    and a renamed function keeps it."""
    _DEVICE_WAITS.add(fn.__code__)
    return fn


def covered(intervals, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` under the union of ``intervals``."""
    total, at = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, at), min(b, hi)
        if b > a:
            total += b - a
            at = b
    return total


class ProcessLog:
    """What the interpreter's collector and JAX's compiler did, on the
    tracers' clock (``time.perf_counter``): one of each hook a process,
    installed by the first watch that starts and kept."""

    def __init__(self):
        self.collections: collections.deque = collections.deque(maxlen=64)
        self.compiles: collections.deque = collections.deque(maxlen=256)
        self._gc_began = 0.0

    def install(self) -> None:
        import jax
        from jax import monitoring

        for fn in (jax.device_get, jax.block_until_ready):
            waits_on_device(fn)
        gc.callbacks.append(self._on_gc)
        monitoring.register_event_duration_secs_listener(self._on_compile)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._gc_began = _clock()
        else:
            self.collections.append(
                (self._gc_began, _clock(), info["collected"]))

    def _on_compile(self, event: str, seconds: float, **_kw) -> None:
        if event.startswith(_COMPILE_EVENTS):
            self.compiles.append((_clock(), seconds, event))


_process_log: Optional[ProcessLog] = None
_install_lock = threading.Lock()


def process_log() -> ProcessLog:
    global _process_log
    with _install_lock:
        if _process_log is None:
            _process_log = ProcessLog()
            _process_log.install()
        return _process_log


class Sender:
    """One sending thread of one app, kept on the tracer's thread-local
    and in the watch's list while the thread lives: its open send's
    entry stamp where the watch can read it, its last exit, its typical
    send."""

    __slots__ = ("watch", "thread", "t_in", "t_out", "n", "typical",
                 "sampled", "cycle", "ann", "waits", "cpu_clock", "cpu_seen",
                 "notice")

    def __init__(self, watch: "StallWatch"):
        self.watch = watch
        self.thread = threading.current_thread()
        self.t_in = 0.0      # a send is open while t_in > t_out
        self.t_out = 0.0
        self.n = 0           # sends ended
        self.typical = 0.0
        # the open send's cycle was sampled (it took the ``admit``; at
        # sample='1' from the entry on), that cycle's id, and the open
        # ``siddhi.admit`` annotation
        self.sampled = False
        self.cycle = 0
        self.ann = None
        #: WAITS_LOCK while the thread stands at the process lock
        self.waits: Optional[str] = None
        # the thread's CPU-time clock, taken here by the thread itself,
        # and the last reading of it before a notice, the watch's from
        # its first wake-up on: (at, ms)
        try:
            self.cpu_clock = time.pthread_getcpuclockid(self.thread.ident)
        except (AttributeError, OSError):
            self.cpu_clock = None
        self.cpu_seen = (_clock(), self.cpu_ms())
        self.notice: Optional[_Notice] = None

    def cpu_ms(self) -> Optional[float]:
        """Milliseconds the thread has spent on a processor; None where
        the platform has no such clock or the thread is gone."""
        if self.cpu_clock is None:
            return None
        try:
            return 1e3 * time.clock_gettime(self.cpu_clock)
        except OSError:
            return None


class _Notice:
    """What the watch gathered on one send past its threshold."""

    __slots__ = ("t_in", "at", "figures", "late", "samples")

    def __init__(self, t_in: float, at: float):
        self.t_in = t_in
        self.at = at                       # the first notice
        self.figures = _process_figures()
        self.late = 0.0                    # its longest lateness since
        self.samples: List[dict] = []


def _process_figures() -> Dict[str, float]:
    """The process's running totals, for a stall's record to difference."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_ms": 1e3 * (ru.ru_utime + ru.ru_stime),
            "involuntary_switches": ru.ru_nivcsw,
            "major_faults": ru.ru_majflt}


def _where(frame) -> str:
    code = frame.f_code
    path = code.co_filename.replace("\\", "/").rsplit("/", 2)
    return f"{'/'.join(path[-2:])}:{frame.f_lineno} {code.co_qualname}"


def cause_of(ms: float, gc_ms: float, compile_ms: float,
             persist: List[dict], samples: List[dict], late_ms: float,
             process: Optional[dict], sender_cpu: Optional[tuple]) -> str:
    """One cause for a stall of ``ms``, by the first rule that holds."""
    if gc_ms >= ms / 2:
        return CAUSE_GC
    if compile_ms >= ms / 2:
        return CAUSE_COMPILE
    if persist:
        return CAUSE_PERSIST
    if samples:
        half = len(samples) / 2
        if sum(s["waits"] == WAITS_LOCK for s in samples) >= half:
            return CAUSE_LOCK
        if sum(s["waits"] == WAITS_DEVICE for s in samples) >= half:
            return CAUSE_DEVICE_WAIT
    if late_ms >= ms / 2:
        # the watch did not run for half of it.  Nor did the sender,
        # where its thread's clock shows it: off a processor for half
        # of the stall, counting only what of the stall the reading
        # covers (a sender that computes is on one throughout; the
        # chip's freezes of 0.13 s read 30 and 60 ms on one, the send's
        # own work and a clock that ticks in tens).  Without that clock
        # the process's figures decide, where the watch took any: under
        # a tenth of the wall time
        if sender_cpu is not None:
            cpu_ms, over_ms = sender_cpu
            if min(over_ms, ms) - cpu_ms >= ms / 2:
                return CAUSE_DESCHEDULED
        elif (process is not None
              and process["cpu_ms"] < process["wall_ms"] / 10):
            return CAUSE_DESCHEDULED
    return CAUSE_HOST


class StallWatch:
    """Per-app: the sending threads, the watching thread, the records."""

    def __init__(self, tracer):
        self.tracer = tracer
        self.senders: List[Sender] = []
        #: the runtime's ``_pending_work`` (gates in flight, emits
        #: pending), set by ``SiddhiAppRuntime.start()``
        self.pending_work: Optional[Callable[[], int]] = None
        # cause -> [count, seconds]
        self.by_cause: Dict[str, List[float]] = {c: [0, 0.0] for c in CAUSES}
        self.longest_s = 0.0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # the watch's own: when its next wake-up is due (0 until its
        # first is), when its last one came and how late
        self._due = 0.0
        self._woke = 0.0
        self._late = 0.0

    # -- the sending threads -------------------------------------------------

    def sender(self) -> Sender:
        """The calling thread's first send to this app."""
        st = Sender(self)
        with self._lock:
            self.senders.append(st)
        return st

    # -- the watching thread -------------------------------------------------

    def start(self) -> None:
        with self._lock:
            if self._thread is not None or self._stop.is_set():
                return
            process_log()
            self._thread = threading.Thread(
                target=self._loop, name=f"stallwatch-{self.tracer.app_name}",
                daemon=True)
            self._thread.start()

    def resume(self) -> None:
        """The runtime starts (again): a watch that ``shutdown()``
        stopped may run, at once where a thread is past its seeding."""
        self._stop.clear()
        if any(st.n >= SEED_SENDS for st in self.senders):
            self.start()

    def stop(self) -> None:
        """Until ``resume()``: a send that ends after ``shutdown()``
        starts no thread."""
        with self._lock:
            self._stop.set()
            t, self._thread = self._thread, None
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2)

    def _loop(self) -> None:
        while True:
            self._due = _clock() + WATCH_PERIOD_S
            if self._stop.wait(WATCH_PERIOD_S):
                return
            if self.tracer.clock is not _clock:
                continue
            now = _clock()
            try:
                self._wake(now, max(0.0, now - self._due))
            except Exception:  # noqa: BLE001 — the watch outlives a bad wake
                log.exception("app '%s': stall watch wake-up failed",
                              self.tracer.app_name)

    def _wake(self, now: float, late: float) -> None:
        self._woke, self._late = now, late
        gone = False
        for st in self.senders:
            if not st.thread.is_alive():
                gone = True
                continue
            t_in = st.t_in
            age = now - t_in if t_in > st.t_out else 0.0
            if age < STALL_FLOOR_S:
                # the one reading beside the compare (module docstring);
                # not inside a send that is already past the floor: a
                # stall in the making keeps the reading from before it
                st.cpu_seen = (now, st.cpu_ms())
                continue
            if st.n < SEED_SENDS or age < STALL_FACTOR * st.typical:
                continue
            nt = st.notice
            if nt is None or nt.t_in != t_in:
                nt = st.notice = _Notice(t_in, now)
            nt.late = max(nt.late, late)
            if len(nt.samples) < MAX_SAMPLES:
                nt.samples.append(self._sample(st, age))
        if gone:
            with self._lock:
                self.senders = [st for st in self.senders
                                if st.thread.is_alive()]

    @staticmethod
    def _sample(st: Sender, age_s: float) -> dict:
        frames = sys._current_frames()
        names = {t.ident: t.name for t in threading.enumerate()}
        me, ident = threading.get_ident(), st.thread.ident
        sender, waits, f, deep = [], st.waits, frames.get(ident), 0
        while f is not None and deep < 64 and (
                waits is None or len(sender) < SENDER_FRAMES):
            if len(sender) < SENDER_FRAMES:
                sender.append(_where(f))
            if waits is None and f.f_code in _DEVICE_WAITS:
                waits = WAITS_DEVICE
            f, deep = f.f_back, deep + 1
        return {"at_ms": 1e3 * age_s, "sender": sender, "waits": waits,
                "others": {names.get(i, str(i)): _where(f)
                           for i, f in frames.items()
                           if i != ident and i != me}}

    # -- the record ----------------------------------------------------------

    def _evidence(self, st: Sender, t_in: float, t_out: float) -> dict:
        """What was gathered on the send ``[t_in, t_out]`` of ``st``,
        ended or still open."""
        nt, plog = st.notice, _process_log
        if nt is not None and nt.t_in != t_in:
            nt = None
        late = nt.late if nt is not None else 0.0
        if (self._thread is not None and self._due > 0.0
                and self.tracer.clock is _clock):
            # a wake-up inside a stall the watch never noticed, and the
            # one that is due and has not come
            if t_in < self._woke <= t_out:
                late = max(late, self._late)
            late = max(late, t_out - self._due)
        process = None
        if nt is not None:
            process = {"wall_ms": 1e3 * (t_out - nt.at),
                       **{k: v - nt.figures[k]
                          for k, v in _process_figures().items()}}
        # the sender's own time on a processor since the watch's last
        # wake-up before its notice: the stall and up to a period more
        sender_cpu = None
        seen, now_ms = st.cpu_seen, st.cpu_ms()
        if seen[1] is not None and now_ms is not None:
            sender_cpu = (now_ms - seen[1], 1e3 * (t_out - seen[0]))
        collected, compiles = [], []
        if plog is not None:
            collected = [(a, b, n) for a, b, n in list(plog.collections)
                         if b > t_in and a < t_out]
            compiles = [(end - s, end, ev) for end, s, ev
                        in list(plog.compiles)
                        if end > t_in and end - s < t_out]
        tracer = self.tracer
        held = [(s[1], s[3], s[4], False) for s in tracer.recorder.spans()
                if s[1] in _PERSIST_STAGES and s[4] > t_in and s[3] < t_out]
        held += [(stage, began, t_out, True)
                 for stage, began in list(tracer.free_open.values())
                 if stage in _PERSIST_STAGES and began < t_out]
        pending = None
        if self.pending_work is not None:
            try:
                pending = self.pending_work()
            except Exception:  # noqa: BLE001 — a gauge, beside the stream
                pending = None
        return {
            "noticed": nt is not None,
            "samples": list(nt.samples) if nt else [],
            "late_ms": 1e3 * late,
            "process": process,
            # (milliseconds on a processor, of this many of wall time)
            "sender_cpu": sender_cpu,
            "gc_ms": 1e3 * covered([c[:2] for c in collected],
                                   t_in, t_out),
            "gc": [{"at_ms": 1e3 * (a - t_in), "ms": 1e3 * (b - a),
                    "collected": n} for a, b, n in collected],
            "compile_ms": 1e3 * covered([c[:2] for c in compiles],
                                        t_in, t_out),
            "compile": [{"at_ms": 1e3 * (a - t_in), "ms": 1e3 * (b - a),
                         "event": ev} for a, b, ev in compiles],
            "persist": [{"stage": stage, "at_ms": 1e3 * (a - t_in),
                         "ms": 1e3 * (b - a), "open": still}
                        for stage, a, b, still in held],
            "pending_work": pending,
        }

    def stalled(self, st: Sender, left: float, n_events: int) -> dict:
        """The send of ``st`` that has just ended was a stall: write its
        record.  Called by the sender; ``left`` is the exit of the
        thread's send before it."""
        t_in, t_out = st.t_in, st.t_out
        took = t_out - t_in
        rec = self._evidence(st, t_in, t_out)
        st.notice = None
        ms = 1e3 * took
        cause = cause_of(ms, rec["gc_ms"], rec["compile_ms"], rec["persist"],
                         rec["samples"], rec["late_ms"], rec["process"],
                         rec["sender_cpu"])
        cycle = st.cycle if st.sampled and st.cycle else None
        rec = {
            "cause": cause, "ms": ms, "t_start": t_in, "t_end": t_out,
            "unix_time": time.time(), "thread": st.thread.name, "send": st.n,
            "events": n_events, "typical_ms": 1e3 * st.typical,
            # the thread's previous exit to this entry: the caller's time
            "client_gap_ms": 1e3 * (t_in - left) if left else None,
            "cycle": cycle, **rec}
        tracer = self.tracer
        tracer.record_span(STAGE_STALL + cause, "entry", t_in, t_in,
                           int(1e6 * took), cycle=cycle)
        tracer.recorder.stalls.append(rec)
        tally = self.by_cause[cause]
        tally[0] += 1
        tally[1] += took
        self.longest_s = max(self.longest_s, took)
        log.warning(
            "app '%s': send %d of thread %s stalled for %.1f ms (typical "
            "%.2f ms, %d events): %s; sender at %s", tracer.app_name, st.n,
            st.thread.name, ms, 1e3 * st.typical, n_events, cause,
            rec["samples"][-1]["sender"][0] if rec["samples"]
            and rec["samples"][-1]["sender"] else "no sample")
        return rec

    def in_flight(self) -> List[dict]:
        """The sends the watch has noticed past their threshold and that
        have not ended: where a wedge stands, for a dump."""
        out = []
        for st in self.senders:
            nt, t_in = st.notice, st.t_in
            if nt is None or nt.t_in != t_in or t_in <= st.t_out:
                continue
            now = _clock()
            out.append({"open": True, "ms": 1e3 * (now - t_in),
                        "t_start": t_in, "thread": st.thread.name,
                        "send": st.n + 1,
                        "typical_ms": 1e3 * st.typical,
                        **self._evidence(st, t_in, now)})
        return out

    def stats(self) -> Dict[str, Dict[str, float]]:
        """``all`` and each cause that stalled -> count, seconds (and,
        for ``all``, the longest); empty while no send has stalled."""
        out = {cause: {"count": n, "seconds": s}
               for cause, (n, s) in self.by_cause.items() if n}
        if not out:
            return {}
        out["all"] = {"count": sum(v["count"] for v in out.values()),
                      "seconds": sum(v["seconds"] for v in out.values()),
                      "longestMs": 1e3 * self.longest_s}
        return out
