"""App planner: SiddhiApp AST -> SiddhiAppRuntime.

The analog of the reference SiddhiAppParser.parse + SiddhiAppRuntimeBuilder
(util/parser/SiddhiAppParser.java:91, util/SiddhiAppRuntimeBuilder.java:64):
wires junctions for every stream definition (plus @OnError fault streams),
plans queries/partitions, and assembles the runtime.
"""

from __future__ import annotations

from typing import Dict, Optional

from siddhi_tpu.core.context import SiddhiAppContext, SiddhiContext
from siddhi_tpu.core.exceptions import (
    DefinitionNotExistError,
    OnErrorAction,
    SiddhiAppCreationError,
)
from siddhi_tpu.core.stream import InputManager, StreamJunction
from siddhi_tpu.extension.validator import validate_extension_args
from siddhi_tpu.query_api import (
    Attribute,
    AttrType,
    Partition,
    Query,
    SiddhiApp,
    SingleInputStream,
    StreamDefinition,
)
from siddhi_tpu.query_api.annotation import find_annotation
from siddhi_tpu.util.scheduler import Scheduler


class AppPlanner:
    def __init__(self, siddhi_app: SiddhiApp, app_string: str, siddhi_context: SiddhiContext):
        self.siddhi_app = siddhi_app
        self.app_string = app_string
        self.siddhi_context = siddhi_context
        self.extensions = siddhi_context.extensions

        self.handler_registrations = []  # (manager, element_id) to drop on shutdown
        name_ann = find_annotation(siddhi_app.annotations, "app:name")
        import uuid

        self.name = (name_ann.element() if name_ann else None) or f"app_{uuid.uuid4().hex[:8]}"
        self.app_context = SiddhiAppContext(siddhi_context, self.name)
        self.tpu_mesh = None  # @app:execution('tpu', devices='N')
        playback = find_annotation(siddhi_app.annotations, "app:playback")
        if playback is not None:
            from siddhi_tpu.compiler.parser import parse_time_string

            def time_ms(v):
                if v is None:
                    return 0
                try:
                    return int(v)
                except ValueError:
                    return parse_time_string(v)

            self.app_context.set_playback(True, time_ms(playback.element("increment")))
            self.app_context.playback_idle_ms = time_ms(playback.element("idle.time"))
        if find_annotation(siddhi_app.annotations, "app:enforceOrder") is not None:
            # the sync dispatch path is ordered by construction; the flag is
            # kept for API parity (reference: SiddhiAppParser.java:199-213)
            self.app_context.enforce_order = True
        exec_ann = find_annotation(siddhi_app.annotations, "app:execution")
        if exec_ann is not None:
            mode = (exec_ann.element() or "host").lower()
            if mode not in ("host", "tpu"):
                raise SiddhiAppCreationError(
                    f"@app:execution('{mode}'): mode must be 'host' or 'tpu'")
            self.app_context.execution_mode = mode
            parts = exec_ann.element("partitions")
            if parts:
                try:
                    n = int(parts)
                except ValueError:
                    n = -1
                if n < 1:
                    raise SiddhiAppCreationError(
                        f"@app:execution: partitions='{parts}' must be a "
                        "positive integer")
                self.app_context.tpu_partitions = n
            insts = exec_ann.element("instances")
            if insts:
                try:
                    ni = int(insts)
                except ValueError:
                    ni = -1
                if ni < 1:
                    raise SiddhiAppCreationError(
                        f"@app:execution: instances='{insts}' must be a "
                        "positive integer")
                self.app_context.tpu_instances = ni
            devs = exec_ann.element("devices")
            if devs:
                try:
                    nd = int(devs)
                except ValueError:
                    nd = -1
                if nd < 1:
                    raise SiddhiAppCreationError(
                        f"@app:execution: devices='{devs}' must be a "
                        "positive integer")
                self.app_context.tpu_devices = nd
                if self.app_context.tpu_partitions % nd:
                    raise SiddhiAppCreationError(
                        f"@app:execution: partitions="
                        f"{self.app_context.tpu_partitions} must be "
                        f"divisible by devices={nd}")
                # one app-wide mesh (shared by the dense pattern axis and
                # the device-query group axis), built here rather than
                # inside a query's lowering: a platform with too few
                # devices fails the app instead of becoming one more
                # counted fallback to the host engine
                from siddhi_tpu.parallel import make_mesh

                self.tpu_mesh = make_mesh(nd)
            depth = exec_ann.element("emit.depth")
            if depth:
                if depth.lower() == "auto":
                    # adaptive: the emit queue derives its effective
                    # depth from observed transfer RTT vs batch cadence
                    # (core/emit_queue.py EmitDepthController)
                    self.app_context.tpu_emit_depth = "auto"
                else:
                    try:
                        ed = int(depth)
                    except ValueError:
                        ed = -1
                    if ed < 1:
                        raise SiddhiAppCreationError(
                            f"@app:execution: emit.depth='{depth}' must be "
                            "a positive integer or 'auto'")
                    self.app_context.tpu_emit_depth = ed
            # ingest.depth pins the staging window (core/ingest_stage.py):
            # '1' finishes every count gate inline, so send_batch always
            # returns with its callbacks delivered; 'N' > 1 keeps each
            # gate behind the next N-1 dispatches, finished by them or a
            # barrier alone.  Unset (and 'auto', which says the same)
            # the stage chooses per batch from what it observes, at most
            # one batch in flight, an idle one finished within a cycle.
            idepth = exec_ann.element("ingest.depth")
            if idepth:
                if idepth.lower() == "auto":
                    self.app_context.tpu_ingest_depth = None
                else:
                    try:
                        nid = int(idepth)
                    except ValueError:
                        nid = -1
                    if nid < 1:
                        raise SiddhiAppCreationError(
                            f"@app:execution: ingest.depth='{idepth}' must "
                            "be a positive integer or 'auto'")
                    self.app_context.tpu_ingest_depth = nid
            amb = exec_ann.element("agg.device.min.batch")
            if amb:
                try:
                    nab = int(amb)
                except ValueError:
                    nab = -1
                if nab < 1:
                    raise SiddhiAppCreationError(
                        f"@app:execution: agg.device.min.batch='{amb}' must "
                        "be a positive integer")
                self.app_context.tpu_agg_min_batch = nab

        # @app:multiplex(slots='N'): opt this app's eligible queries into
        # manager-wide shared device engines (multiplex/) — one jitted
        # step per cycle serves every structurally-compatible tenant
        # across ALL apps under the manager.  Ineligible queries fall
        # back to dedicated engines with a counted reason.
        mux_ann = find_annotation(siddhi_app.annotations, "app:multiplex")
        if mux_ann is not None:
            if self.app_context.execution_mode != "tpu":
                raise SiddhiAppCreationError(
                    "@app:multiplex needs @app:execution('tpu')")
            self.app_context.multiplex = True
            slots = mux_ann.element("slots") or mux_ann.element()
            if slots:
                try:
                    ns = int(slots)
                except ValueError:
                    ns = -1
                if ns < 2 or ns > 64:
                    raise SiddhiAppCreationError(
                        f"@app:multiplex: slots='{slots}' must be an "
                        "integer in 2..64")
                self.app_context.multiplex_slots = ns

        # @app:fuse: fuse chains of device-lowered queries linked by
        # `insert into` streams into ONE jitted multi-stage program per
        # chain — intermediate event columns stay in HBM, no EventBatch
        # builds or junction dispatches between stages
        # (planner/fusion.py).  Ineligible chains fall back to the
        # junction path with counted fusedFallbackReasons.
        fuse_ann = find_annotation(siddhi_app.annotations, "app:fuse")
        if fuse_ann is not None:
            if self.app_context.execution_mode != "tpu":
                raise SiddhiAppCreationError(
                    "@app:fuse needs @app:execution('tpu')")
            v = (fuse_ann.element() or "true").lower()
            if v not in ("true", "false"):
                raise SiddhiAppCreationError(
                    f"@app:fuse('{v}'): expected 'true' or 'false'")
            self.app_context.fuse = v == "true"

        # @app:hotkeys(k='8', promote='0.25', demote='0.10'): skew-aware
        # hot-key routing — partitioned dense patterns promote heavy
        # partition keys onto the batched associative-scan engine
        # (planner/hotkeys.py); ineligible queries stay dense with
        # counted hotkeyFallbackReasons.
        hk_ann = find_annotation(siddhi_app.annotations, "app:hotkeys")
        if hk_ann is not None:
            if self.app_context.execution_mode != "tpu":
                raise SiddhiAppCreationError(
                    "@app:hotkeys needs @app:execution('tpu')")
            self.app_context.hotkeys = True
            k = hk_ann.element("k") or hk_ann.element()
            if k:
                try:
                    nk = int(k)
                except ValueError:
                    nk = -1
                if nk < 1 or nk > 256:
                    raise SiddhiAppCreationError(
                        f"@app:hotkeys: k='{k}' must be an integer in "
                        "1..256 (scan slots per query)")
                self.app_context.hotkey_k = nk
            pr = hk_ann.element("promote")
            dm = hk_ann.element("demote")
            try:
                promote = float(pr) if pr else self.app_context.hotkey_promote
                demote = float(dm) if dm else self.app_context.hotkey_demote
            except ValueError:
                raise SiddhiAppCreationError(
                    f"@app:hotkeys: promote='{pr}'/demote='{dm}' must be "
                    "fractions of total traffic")
            if not (0.0 < promote <= 1.0) or not (0.0 <= demote < promote):
                raise SiddhiAppCreationError(
                    f"@app:hotkeys: need 0 <= demote < promote <= 1 "
                    f"(got promote={promote}, demote={demote}) — the "
                    "hysteresis band prevents promote/demote thrash")
            self.app_context.hotkey_promote = promote
            self.app_context.hotkey_demote = demote

        # @app:devtables / @app:devtables(capacity='N'): device-resident
        # columnar tables (siddhi_tpu/devtable/); ineligible tables and
        # queries keep the host path with counted devtableFallbackReasons.
        dt_ann = find_annotation(siddhi_app.annotations, "app:devtables")
        if dt_ann is not None:
            if self.app_context.execution_mode != "tpu":
                raise SiddhiAppCreationError(
                    "@app:devtables needs @app:execution('tpu')")
            v = (dt_ann.element() or "true").strip().lower()
            if v != "false":
                self.app_context.devtables = True
            cap = dt_ann.element("capacity")
            if cap:
                try:
                    ncap = int(cap)
                except ValueError:
                    ncap = -1
                if ncap < 1 or ncap > 1 << 24:
                    raise SiddhiAppCreationError(
                        f"@app:devtables: capacity='{cap}' must be an "
                        "integer in 1..16777216 (device slots per table)")
                self.app_context.devtable_capacity = ncap

        # @app:plan(auto='true', hysteresis='0.3', interval='5 sec'):
        # cost-based unified lowering (planner/costmodel.py) — auto
        # enumerates + scores every eligible lowering per un-annotated
        # query and picks the cheapest (the legacy fast-path annotations
        # stay pins that override it); hysteresis is the PlanMonitor's
        # re-plan margin and interval paces its background sweep
        # (0 = decide() on demand only).
        plan_ann = find_annotation(siddhi_app.annotations, "app:plan")
        if plan_ann is not None:
            if self.app_context.execution_mode != "tpu":
                raise SiddhiAppCreationError(
                    "@app:plan needs @app:execution('tpu')")
            v = (plan_ann.element("auto") or plan_ann.element()
                 or "true").strip().lower()
            if v not in ("true", "false"):
                raise SiddhiAppCreationError(
                    f"@app:plan: auto='{v}' must be 'true' or 'false'")
            self.app_context.plan_auto = v == "true"
            hy = plan_ann.element("hysteresis")
            if hy:
                try:
                    h = float(hy)
                except ValueError:
                    h = -1.0
                if not (0.0 <= h <= 10.0):
                    raise SiddhiAppCreationError(
                        f"@app:plan: hysteresis='{hy}' must be a fraction "
                        "in 0..10 (margin before a live re-plan)")
                self.app_context.plan_hysteresis = h
            iv = plan_ann.element("interval")
            if iv:
                try:
                    ims = int(iv)
                except ValueError:
                    from siddhi_tpu.compiler.parser import parse_time_string

                    ims = parse_time_string(iv)
                if ims <= 0:
                    raise SiddhiAppCreationError(
                        f"@app:plan: interval {iv!r} must be > 0")
                self.app_context.plan_interval_ms = ims

        from siddhi_tpu.util.statistics import Level, StatisticsManager

        stats_ann = find_annotation(siddhi_app.annotations, "app:statistics")
        level = Level.OFF
        interval_s = 60.0
        if stats_ann is not None:
            v = (stats_ann.element() or "true").lower()
            level = {
                "true": Level.BASIC, "false": Level.OFF,
                "basic": Level.BASIC, "detail": Level.DETAIL,
            }.get(v, Level.BASIC)
            iv = stats_ann.element("interval")
            if iv:
                interval_s = float(iv)
        self.app_context.root_metrics_level = level
        self.app_context.statistics_manager = StatisticsManager(self.name, interval_s)

        # @app:trace(sample='1/64', cycles='64', dir='/path'): cycle-
        # correlated span tracing + flight recorder (observability/).
        # Default ON at 1-in-64 sampling — the recorder is the black box
        # every fault dump reads, so it must not require opting in;
        # sample='off' disables span recording (the tracer object stays
        # and every hook short-circuits on the None token).
        from siddhi_tpu.observability import Tracer

        trace_ann = find_annotation(siddhi_app.annotations, "app:trace")
        trace_sample = Tracer.DEFAULT_SAMPLE
        trace_cycles = Tracer.DEFAULT_CYCLES
        trace_dir = None
        if trace_ann is not None:
            sv = (trace_ann.element("sample") or trace_ann.element() or "")
            if sv.strip():
                trace_sample = self._parse_trace_sample(sv.strip())
            cv = trace_ann.element("cycles")
            if cv:
                try:
                    nc = int(cv)
                except ValueError:
                    nc = -1
                if nc < 1 or nc > 4096:
                    raise SiddhiAppCreationError(
                        f"@app:trace: cycles='{cv}' must be an integer in "
                        "1..4096 (flight-recorder depth in batch cycles)")
                trace_cycles = nc
            trace_dir = trace_ann.element("dir") or None
        tracer = Tracer(self.name, sample=trace_sample,
                        cycles=trace_cycles, dump_dir=trace_dir)
        self.app_context.tracer = tracer
        self.app_context.statistics_manager.register_tracer(tracer)

        # @app:faults(...): deterministic chaos harness + crash-recovery
        # journal.  The injector itself is cheap (every hook is a None
        # check when the annotation is absent); the journal is keyed by
        # app name on the MANAGER context so a replacement runtime built
        # after a simulated crash inherits the pre-crash input history.
        faults_ann = find_annotation(siddhi_app.annotations, "app:faults")
        if faults_ann is not None:
            from siddhi_tpu.util.faults import FaultInjector, InputJournal

            fi = FaultInjector()
            journal_depth = fi.configure_from_options(
                self._ann_options(faults_ann))
            fi.listeners = self.app_context.exception_listeners
            # a simulated crash kill is exactly what the flight recorder
            # exists for: the injector dumps the span ring on its way out
            fi.tracer = tracer
            self.app_context.fault_injector = fi
            if journal_depth:
                jr = siddhi_context.input_journals.get(self.name)
                if jr is None or jr.depth != journal_depth:
                    jr = InputJournal(depth=journal_depth)
                    siddhi_context.input_journals[self.name] = jr
                else:
                    # a reused (post-crash) journal carries its counter
                    # history into the replacement runtime's feed
                    for k, v in jr.stats.as_dict().items():
                        setattr(fi.stats, k, getattr(fi.stats, k) + v)
                jr.stats = fi.stats
                self.app_context.input_journal = jr
                # journal overflow spills cold segments to the app's
                # persistence store instead of dropping them — replay
                # stitches spilled + in-memory segments (durability/)
                from siddhi_tpu.durability.spill import JournalSpillSink

                jr.spill_sink = JournalSpillSink(
                    siddhi_context, self.name, self.app_context)

        # @app:persist(interval='30 sec', mode='async', location='/var/ckpt',
        # revisions.to.keep='2'): default persist mode, optional
        # periodic-checkpoint daemon, and the app's own durable store for
        # a deployment whose text has to name it (durability/)
        persist_ann = find_annotation(siddhi_app.annotations, "app:persist")
        if persist_ann is not None:
            location = persist_ann.element("location")
            keep = persist_ann.element("revisions.to.keep")
            if location is None and keep is not None:
                raise SiddhiAppCreationError(
                    "@app:persist: revisions.to.keep belongs to the store "
                    "that location names; the manager's store has its own")
            if location is not None:
                from siddhi_tpu.durability.store import open_store

                if siddhi_context.persistence_store is not None:
                    raise SiddhiAppCreationError(
                        "@app:persist: location names a store and the "
                        "manager has one (set_persistence_store); an app "
                        "has one store")
                if name_ann is None:
                    raise SiddhiAppCreationError(
                        "@app:persist: location needs @app:name: the "
                        "revisions lie under the app's name, and a "
                        "process that restarts finds them by it")
                try:
                    self.app_context.persistence_store = open_store(
                        location, keep)
                except ValueError as e:
                    raise SiddhiAppCreationError(f"@app:persist: {e}") from e
            mode = (persist_ann.element("mode")
                    or persist_ann.element() or "async").lower()
            if mode not in ("sync", "async"):
                raise SiddhiAppCreationError(
                    f"@app:persist: mode {mode!r} must be 'sync' or 'async'")
            self.app_context.persist_mode = mode
            iv = persist_ann.element("interval")
            if iv:
                try:
                    interval_ms = int(iv)
                except ValueError:
                    from siddhi_tpu.compiler.parser import parse_time_string

                    interval_ms = parse_time_string(iv)
                if interval_ms <= 0:
                    raise SiddhiAppCreationError(
                        f"@app:persist: interval {iv!r} must be > 0")
                self.app_context.persist_interval_ms = interval_ms

        # @app:limits(rate='N/s', burst='M', shed='drop|oldest|block',
        # block.max='1 sec', watchdog='2 sec', breaker='3',
        # breaker.cooldown='1 sec'): overload protection (robustness/) —
        # admission control at ingest, watchdog-driven self-healing and
        # transport circuit breakers.  Absent ⇒ every hook stays None and
        # the engine is bit-identical to an unprotected app.
        limits_ann = find_annotation(siddhi_app.annotations, "app:limits")
        if limits_ann is not None:
            from siddhi_tpu.compiler.parser import parse_time_string
            from siddhi_tpu.robustness import (
                AdmissionController,
                RobustnessStats,
            )
            from siddhi_tpu.robustness.admission import SHED_POLICIES

            ctx = self.app_context

            def limits_time_ms(key):
                v = limits_ann.element(key)
                if v is None:
                    return None
                try:
                    ms = int(v)
                except ValueError:
                    ms = parse_time_string(v)
                if ms <= 0:
                    raise SiddhiAppCreationError(
                        f"@app:limits: {key}={v!r} must be > 0")
                return ms

            rate = limits_ann.element("rate") or limits_ann.element()
            if rate:
                r = rate.strip().lower()
                for suffix in ("/sec", "/s"):
                    if r.endswith(suffix):
                        r = r[: -len(suffix)]
                        break
                try:
                    ctx.limits_rate = float(r)
                except ValueError:
                    ctx.limits_rate = -1.0
                if ctx.limits_rate <= 0:
                    raise SiddhiAppCreationError(
                        f"@app:limits: rate='{rate}' must be a positive "
                        "events-per-second figure ('1000' or '1000/s')")
            burst = limits_ann.element("burst")
            if burst:
                try:
                    ctx.limits_burst = float(burst)
                except ValueError:
                    ctx.limits_burst = -1.0
                if ctx.limits_burst < 1:
                    raise SiddhiAppCreationError(
                        f"@app:limits: burst='{burst}' must be >= 1 "
                        "(token-bucket depth in events)")
                if not ctx.limits_rate:
                    raise SiddhiAppCreationError(
                        "@app:limits: burst needs rate")
            elif ctx.limits_rate:
                ctx.limits_burst = max(ctx.limits_rate, 1.0)
            shed = limits_ann.element("shed")
            if shed:
                if shed not in SHED_POLICIES:
                    raise SiddhiAppCreationError(
                        f"@app:limits: shed='{shed}' must be one of "
                        f"{', '.join(SHED_POLICIES)}")
                if not ctx.limits_rate:
                    raise SiddhiAppCreationError(
                        "@app:limits: shed needs rate")
                ctx.limits_shed = shed
            bm = limits_time_ms("block.max")
            if bm is not None:
                ctx.limits_block_max_ms = bm
            wd = limits_time_ms("watchdog")
            if wd is not None:
                ctx.watchdog_deadline_ms = wd
            br = limits_ann.element("breaker")
            if br:
                try:
                    nb = int(br)
                except ValueError:
                    nb = -1
                if nb < 1:
                    raise SiddhiAppCreationError(
                        f"@app:limits: breaker='{br}' must be a positive "
                        "integer (consecutive failures before opening)")
                ctx.breaker_threshold = nb
            bc = limits_time_ms("breaker.cooldown")
            if bc is not None:
                ctx.breaker_cooldown_ms = bc
            if not (ctx.limits_rate or ctx.watchdog_deadline_ms
                    or ctx.breaker_threshold):
                raise SiddhiAppCreationError(
                    "@app:limits: needs at least one of rate, watchdog, "
                    "breaker")
            ctx.robustness = RobustnessStats()
            if ctx.limits_rate:
                ctx.admission = AdmissionController(ctx, ctx.robustness)

        self.scheduler = Scheduler(self.app_context)
        self.app_context.scheduler = self.scheduler

        self.junctions: Dict[str, StreamJunction] = {}
        self.definitions: Dict[str, StreamDefinition] = {}
        self.sources = []
        self.sinks = []
        self.query_runtimes: Dict[str, object] = {}
        self.tables: Dict[str, object] = {}  # name -> InMemoryTable
        self.named_windows: Dict[str, object] = {}  # name -> NamedWindowRuntime
        self.trigger_runtimes: Dict[str, object] = {}

    # -- junction / definition registry -------------------------------------

    @staticmethod
    def _key(stream_id: str, is_inner: bool = False, is_fault: bool = False) -> str:
        if is_inner:
            return "#" + stream_id
        if is_fault:
            return "!" + stream_id
        return stream_id

    def define_stream(self, definition: StreamDefinition, key: Optional[str] = None):
        key = key or definition.id
        if key in self.junctions:
            return self.junctions[key]
        is_async = False
        buffer_size = 1024
        batch_max = None
        on_error = OnErrorAction.LOG
        async_ann = find_annotation(definition.annotations, "async")
        if async_ann is not None:
            is_async = True
            bs = async_ann.element("buffer.size")
            bm = async_ann.element("batch.size.max")
            buffer_size = int(bs) if bs else 1024
            batch_max = int(bm) if bm else None
        onerror_ann = find_annotation(definition.annotations, "OnError")
        fault_junction = None
        if onerror_ann is not None and (onerror_ann.element("action") or "log").lower() == "stream":
            on_error = OnErrorAction.STREAM
            fault_def = StreamDefinition(
                id="!" + definition.id,
                attributes=list(definition.attributes) + [Attribute("_error", AttrType.OBJECT)],
            )
            fault_junction = self.define_stream(fault_def, key="!" + definition.id)
        j = StreamJunction(
            definition,
            self.app_context,
            is_async=is_async,
            buffer_size=buffer_size,
            batch_size_max=batch_max,
            on_error=on_error,
            fault_junction=fault_junction,
        )
        self.junctions[key] = j
        self.definitions[key] = definition
        self._attach_transports(definition, j)
        return j

    # -- @source / @sink ----------------------------------------------------

    @staticmethod
    def _ann_options(ann) -> Dict[str, str]:
        return {k: v for k, v in ann.elements if k is not None and k.lower() != "type"}

    @staticmethod
    def _parse_trace_sample(value: str) -> int:
        """@app:trace sample grammar: 'off' (no spans), '1' (every
        cycle), '1/N' or bare 'N' (every Nth cycle)."""
        v = value.lower()
        if v in ("off", "false", "none"):
            return 0
        num, sep, den = v.partition("/")
        try:
            n = int(den) if sep else int(num)
            if sep and int(num) != 1:
                raise ValueError(num)
        except ValueError:
            raise SiddhiAppCreationError(
                f"@app:trace: sample='{value}' must be 'off', '1', 'N' or "
                "'1/N' (record every Nth batch cycle)")
        if n < 1 or n > 1_000_000:
            raise SiddhiAppCreationError(
                f"@app:trace: sample='{value}' out of range — the sampling "
                "stride must be in 1..1000000")
        return n

    def _resolve_ref(self, ann) -> Dict[str, str]:
        """Options for @source/@sink/@store with ``ref=`` merged from the
        config manager's refs (reference: ConfigManager.extractSystemConfigs);
        inline options win over ref properties."""
        opts = self._ann_options(ann)
        ref = opts.pop("ref", None)
        if ref is not None:
            cm = self.siddhi_context.config_manager
            ref_configs = dict(cm.extract_system_configs(ref))
            if not ref_configs:
                raise SiddhiAppCreationError(f"undefined ref '{ref}'")
            ref_configs.update(opts)
            opts = ref_configs
        return opts

    def _transport_config(self, ann, what: str):
        """-> (type, init options) with ``ref=`` resolved exactly once."""
        opts = self._resolve_ref(ann)
        stype = ann.element("type") or opts.get("type")
        if stype is None:
            raise SiddhiAppCreationError(
                f"@{what} on a definition: 'type' is required (inline or via ref)")
        opts.pop("type", None)
        return stype, opts

    def _mapper(self, ann, kind: str):
        """Build the (source|sink) mapper from a nested @map annotation
        (default passThrough)."""
        map_ann = ann.nested("map")
        map_type = map_ann.element("type") if map_ann else None
        map_type = map_type or "passThrough"
        factory = self.extensions.lookup(f"{kind}_mapper", map_type)
        if factory is None:
            raise SiddhiAppCreationError(f"unknown @map(type='{map_type}') for {kind}")
        return factory(), self._ann_options(map_ann) if map_ann else {}

    def _make_breaker(self, name: str):
        """@app:limits(breaker='N'): one CircuitBreaker per transport
        endpoint, all counting on the app's RobustnessStats."""
        from siddhi_tpu.robustness import CircuitBreaker

        ctx = self.app_context
        return CircuitBreaker(
            name,
            threshold=ctx.breaker_threshold,
            cooldown_ms=ctx.breaker_cooldown_ms,
            stats=ctx.robustness,
            fault_injector=ctx.fault_injector,
        )

    def _attach_transports(self, definition, junction):
        from siddhi_tpu.transport.sink import DistributedSink, SinkStreamCallback

        for ann in definition.annotations:
            nm = ann.name.lower()
            if nm == "source":
                stype, opts = self._transport_config(ann, "source")
                factory = self.extensions.lookup("source", stype)
                if factory is None:
                    raise SiddhiAppCreationError(f"unknown @source(type='{stype}')")
                mapper, map_opts = self._mapper(ann, "source")
                mapper.init(definition, map_opts)
                src = factory()
                src.config_reader = self.siddhi_context.config_manager.generate_config_reader(
                    "source", stype)
                shm = self.siddhi_context.source_handler_manager
                if shm is not None:
                    src.handler = shm.generate(self.name, definition.id)
                    self.handler_registrations.append((shm, src.handler.element_id))
                src.init(definition, opts, mapper, junction, self.app_context)
                if self.app_context.breaker_threshold:
                    # sources have nothing to spool (their transport
                    # holds the data); the breaker just spaces out
                    # doomed connect attempts on the mixin's chain
                    src._breaker = self._make_breaker(
                        f"source:{definition.id}")
                self.sources.append(src)
            elif nm == "sink":
                stype, opts = self._transport_config(ann, "sink")
                factory = self.extensions.lookup("sink", stype)
                if factory is None:
                    raise SiddhiAppCreationError(f"unknown @sink(type='{stype}')")
                mapper, map_opts = self._mapper(ann, "sink")
                mapper.init(definition, map_opts)
                dist = ann.nested("distribution")
                if dist is not None:
                    dests = [
                        self._ann_options(d)
                        for d in dist.annotations
                        if d.name.lower() == "destination"
                    ]
                    if not dests:
                        raise SiddhiAppCreationError(
                            "@distribution needs at least one @destination"
                        )
                    sink = DistributedSink(
                        factory, dests,
                        dist.element("strategy") or "roundRobin",
                        self._ann_options(dist),
                    )
                else:
                    sink = factory()
                sink.config_reader = self.siddhi_context.config_manager.generate_config_reader(
                    "sink", stype)
                khm = self.siddhi_context.sink_handler_manager
                if khm is not None:
                    sink.handler = khm.generate(self.name, definition.id)
                    self.handler_registrations.append((khm, sink.handler.element_id))
                sink.init(definition, opts, mapper, self.app_context)
                if self.app_context.breaker_threshold:
                    # per-endpoint breakers: a distributed sink breaks
                    # each destination independently, never the fan-out
                    targets = (sink.children
                               if isinstance(sink, DistributedSink)
                               else [sink])
                    for di, child in enumerate(targets):
                        suffix = f"#{di}" if child is not sink else ""
                        child.attach_breaker(self._make_breaker(
                            f"sink:{definition.id}:{len(self.sinks)}"
                            f"{suffix}"))
                # publish failures follow the stream's @OnError contract
                # (reference: Sink.onError:354 routing into '!stream')
                sink.stream_junction = junction
                cb = SinkStreamCallback(sink)
                if self.app_context.input_journal is not None:
                    # output-ledger identity for replay dedup: stream id
                    # + ordinal keeps multiple sinks on one stream apart
                    cb.ledger_key = ("sink", definition.id, len(self.sinks))
                junction.subscribe(cb)
                self.sinks.append(sink)

    def get_or_create_junction(
        self, stream_id: str, fallback_def: StreamDefinition, is_inner=False, is_fault=False
    ) -> StreamJunction:
        key = self._key(stream_id, is_inner, is_fault)
        if key in self.junctions:
            return self.junctions[key]
        d = StreamDefinition(id=stream_id, attributes=list(fallback_def.attributes))
        return self.define_stream(d, key=key)

    def resolve_stream_definition(self, s) -> StreamDefinition:
        if isinstance(s, SingleInputStream):
            key = self._key(s.stream_id, s.is_inner, s.is_fault)
            if key in self.definitions:
                return self.definitions[key]
            raise DefinitionNotExistError(
                f"stream '{key}' is not defined in app '{self.name}'"
            )
        raise SiddhiAppCreationError(f"cannot resolve definition for {s!r}")

    def junction_for_input(self, s: SingleInputStream) -> StreamJunction:
        key = self._key(s.stream_id, s.is_inner, s.is_fault)
        if key not in self.junctions:
            raise DefinitionNotExistError(f"stream '{key}' is not defined")
        return self.junctions[key]

    def table_resolver(self, table_name: str, obj: bool = False):
        """Membership-test provider for `expr IN Table` conditions
        (``obj=True`` hands back the table itself for condition-form
        membership — see ExpressionCompiler._c_InOp)."""
        table = self.tables.get(table_name)
        if table is None:
            raise SiddhiAppCreationError(f"'IN {table_name}': table is not defined")
        return table if obj else table.contains_fn()

    # -- build --------------------------------------------------------------

    def _build_functions(self):
        """name -> expression-builder map: function extensions plus
        script-defined UDFs (``define function f[lang] ...``)."""
        from siddhi_tpu.extension.function import (
            builder_for_extension,
            make_scalar_function_builder,
        )

        fns = {}
        for full_name, factory in self.extensions.items("function"):
            fns[full_name] = builder_for_extension(factory)
        for fd in self.siddhi_app.function_definitions.values():
            engine_factory = self.extensions.lookup("script", fd.language.lower())
            if engine_factory is None:
                raise SiddhiAppCreationError(
                    f"function '{fd.id}': unknown script language '{fd.language}'")
            scalar = engine_factory().compile(fd.id, fd.body, fd.return_type)
            fns[fd.id] = make_scalar_function_builder(scalar, fd.return_type)
        return fns

    def _build_table(self, td):
        """@store tables become record-table runtimes over a store
        extension (reference: DefinitionParserHelper table wiring);
        plain tables are columnar in-memory tables."""
        from siddhi_tpu.query_api.annotation import find_annotation
        from siddhi_tpu.table import InMemoryTable, RecordTableRuntime, TableCache

        store_ann = find_annotation(td.annotations, "store")
        if store_ann is None:
            if self.app_context.devtables:
                import logging

                from siddhi_tpu.devtable import DeviceTable

                sm = self.app_context.statistics_manager
                try:
                    table = DeviceTable(
                        td, capacity=self.app_context.devtable_capacity,
                        faults=self.app_context.fault_injector,
                        statistics_manager=sm)
                    if sm is not None:
                        sm.register_devtable(td.id, table)
                    return table
                except SiddhiAppCreationError as e:
                    logging.getLogger("siddhi_tpu").warning(
                        "table '%s': @app:devtables requested but the "
                        "table stays host-resident (%s)", td.id, e)
                    if sm is not None:
                        sm.record_devtable_fallback(f"table:{td.id}", str(e))
            return InMemoryTable(td)
        stype, options = self._transport_config(store_ann, "store")
        factory = self.extensions.lookup("store", stype)
        if factory is None:
            raise SiddhiAppCreationError(
                f"table '{td.id}': unknown store type '{stype}'")
        store = factory()
        reader = self.siddhi_context.config_manager.generate_config_reader("store", stype)
        store.init(td, options, reader)
        handler = None
        rthm = self.siddhi_context.record_table_handler_manager
        if rthm is not None:
            handler = rthm.generate(self.name, td.id)
            self.handler_registrations.append((rthm, handler.element_id))
        cache = None
        cache_ann = store_ann.nested("cache")
        if cache_ann is not None:
            size = int(cache_ann.element("size") or cache_ann.element("max.size") or "50")
            policy = (cache_ann.element("cache.policy")
                      or cache_ann.element("policy") or "FIFO")
            retention = cache_ann.element("retention.period")
            if retention:
                from siddhi_tpu.compiler.parser import parse_time_string

                retention_ms = parse_time_string(retention)
            else:
                retention_ms = None
            cache = TableCache(size, policy, retention_ms=retention_ms)
        return RecordTableRuntime(td, store, cache=cache, handler=handler)

    def _note_fused_conflicts(self, qname: str):
        """A query the fusion pre-pass claimed while another fast-path
        annotation was also pinned on the app: the documented precedence
        (fuse > shard > multiplex > hotkeys) resolved it — count the
        losing pin so the resolution is visible, not implicit."""
        sm = self.app_context.statistics_manager
        if sm is None:
            return
        if self.app_context.multiplex:
            sm.record_planner_conflict(
                qname, "@app:multiplex pinned but the query fused "
                "(precedence: fuse > multiplex)")
        if self.app_context.hotkeys:
            sm.record_planner_conflict(
                qname, "@app:hotkeys pinned but the query fused "
                "(precedence: fuse > hotkeys)")

    def _pin_state_writers(self):
        """A staged count gate defers a batch's emit and whatever the
        emit does (core/ingest_stage.py).  Rows that end in a table, a
        named window or an aggregation, at once or through the streams
        between, are state that a query on ANOTHER stream reads: left
        in flight past ``send_batch``'s return they would be missed.
        The stage of a device query that writes such state stays
        inline, as under ``ingest.depth='1'``; a window the app pinned
        itself is left as it is."""
        from siddhi_tpu.core.query import (
            InsertIntoStreamCallback,
            QueryCallbackOutput,
        )
        from siddhi_tpu.planner.fusion import _query_inputs

        sa = self.siddhi_app
        queries = []
        for element in sa.execution_elements:
            queries.extend(element.queries if isinstance(element, Partition)
                           else [element])
        # the targets whose events are state or reach it: a fixpoint over
        # the queries' inputs and targets, by junction key
        feeds = set(sa.table_definitions) | set(sa.window_definitions) | {
            ad.input_stream.stream_id
            for ad in sa.aggregation_definitions.values()}
        grew = True
        while grew:
            grew = False
            for q in queries:
                out = q.output_stream
                key = self._key(getattr(out, "target", None) or "",
                                getattr(out, "is_inner", False),
                                getattr(out, "is_fault", False))
                if key in feeds:
                    new = set(_query_inputs(q)) - feeds
                    feeds |= new
                    grew = grew or bool(new)
        key_of = {id(j): key for key, j in self.junctions.items()}

        def writes_state(output) -> bool:
            if isinstance(output, InsertIntoStreamCallback):
                return key_of.get(id(output.junction)) in feeds
            # else a table's callback or a named window's
            return not isinstance(output, QueryCallbackOutput)

        planned = list(self.query_runtimes.values())
        for pr in self.partition_runtimes.values():
            planned.extend(getattr(pr, "dense_query_runtimes", {}).values())
        for qr in planned:
            rt = (getattr(qr, "device_runtime", None)
                  or getattr(qr, "pattern_processor", None))
            stage = getattr(rt, "ingest_stage", None)
            if (stage is not None and stage.rule is not None
                    and writes_state(qr.output)):
                stage.pin(1)

    def build(self):
        from siddhi_tpu.core.app_runtime import SiddhiAppRuntime
        from siddhi_tpu.planner.query_planner import QueryPlanner

        self.functions = self._build_functions()

        for d in self.siddhi_app.stream_definitions.values():
            self.define_stream(d)

        from siddhi_tpu.table import InMemoryTable

        for td in self.siddhi_app.table_definitions.values():
            self.tables[td.id] = self._build_table(td)

        from siddhi_tpu.core.trigger import TriggerRuntime
        from siddhi_tpu.core.window import NamedWindowRuntime
        from siddhi_tpu.planner.expr import ExpressionCompiler, Scope

        for wd in self.siddhi_app.window_definitions.values():
            fn = wd.window_function
            if fn is None:
                raise SiddhiAppCreationError(
                    f"window '{wd.id}': missing window function"
                )
            factory = self.extensions.lookup("window", fn.name, fn.namespace)
            if factory is None:
                raise SiddhiAppCreationError(
                    f"window '{wd.id}': unknown window '{fn.name}()'"
                )
            wscope = Scope()
            for a in wd.attributes:
                wscope.add(wd.id, a.name, a.name, a.type)
            wcompiler = ExpressionCompiler(wscope, functions=self.functions)
            args = [wcompiler.compile(a) for a in fn.args]
            validate_extension_args(
                factory, fn.name, [a.type for a in args],
                where=f"named window '{wd.id}'")
            w = factory(args, wd.attribute_names)
            junction = self.define_stream(
                StreamDefinition(id=wd.id, attributes=list(wd.attributes)),
            )
            nwr = NamedWindowRuntime(wd, w, junction, self.app_context)
            self.named_windows[wd.id] = nwr
            self.scheduler.register_task(nwr)

        for td in self.siddhi_app.trigger_definitions.values():
            junction = self.junctions[td.id]  # trigger defines its stream
            tr = TriggerRuntime(td, junction, self.app_context)
            self.trigger_runtimes[td.id] = tr
            self.scheduler.register_task(tr)

        from siddhi_tpu.aggregation import AggregationRuntime

        self.aggregations: Dict[str, AggregationRuntime] = {}
        for ad in self.siddhi_app.aggregation_definitions.values():
            ar = AggregationRuntime(ad, self)
            self.aggregations[ad.id] = ar
            junction = self.junction_for_input(ad.input_stream)
            junction.subscribe(_AggregationReceiver(ar, self.app_context))

        from siddhi_tpu.core.partition import PartitionRuntime

        qp = QueryPlanner(self)
        # @app:fuse pre-pass: detect chains of device-eligible queries
        # linked by exclusive `insert into` streams and lower each chain
        # to ONE fused engine (planner/fusion.py).  Chain members come
        # back pre-planned, keyed by query identity; everything else
        # takes the ordinary per-query path below.
        fused: Dict[int, object] = {}
        # in auto (cost-model) mode the pre-pass also runs for
        # un-annotated apps — a fused chain beats any per-query lowering
        # whenever one exists (it deletes the junction hops), so the
        # model treats chain membership as the cheapest candidate; a
        # replan pin naming 'fuse' forces the pass too
        want_fuse = (self.app_context.fuse or self.app_context.plan_auto
                     or any("fuse" in str(p).split("+")
                            for p in self.app_context.plan_pins.values()))
        if want_fuse:
            from siddhi_tpu.planner.fusion import plan_fused_chains

            fused = plan_fused_chains(self, qp)
        qi = 0
        pi = 0
        self.partition_runtimes: Dict[str, object] = {}
        for element in self.siddhi_app.execution_elements:
            if isinstance(element, Query):
                qr = fused.pop(id(element), None)
                if qr is not None:
                    self._note_fused_conflicts(qr.name)
                else:
                    qr = qp.plan_query(element, qi)
                qi += 1
                if qr.name in self.query_runtimes:
                    raise SiddhiAppCreationError(f"duplicate query name '{qr.name}'")
                self.query_runtimes[qr.name] = qr
            elif isinstance(element, Partition):
                pr = PartitionRuntime(element, self, pi)
                pi += 1
                self.partition_runtimes[pr.name] = pr

        self._pin_state_writers()

        input_manager = InputManager(self.app_context)
        for key, j in self.junctions.items():
            if not key.startswith("#") and key not in self.named_windows:
                input_manager.register(j)

        runtime = SiddhiAppRuntime(
            name=self.name,
            siddhi_app=self.siddhi_app,
            app_context=self.app_context,
            junctions=self.junctions,
            query_runtimes=self.query_runtimes,
            input_manager=input_manager,
            scheduler=self.scheduler,
            tables=self.tables,
            named_windows=self.named_windows,
            partitions=self.partition_runtimes,
            aggregations=self.aggregations,
            sources=self.sources,
            sinks=self.sinks,
            functions=self.functions,
            handler_registrations=self.handler_registrations,
        )
        # the raw source rides along so a live re-plan
        # (core/app_runtime.py replan) can rebuild from a fresh parse
        runtime._app_string = self.app_string
        return runtime


class _AggregationReceiver:
    """Junction subscriber feeding an AggregationRuntime."""

    def __init__(self, aggregation_runtime, app_context):
        self.aggregation_runtime = aggregation_runtime
        self.app_context = app_context

    def receive(self, batch):
        now = self.app_context.timestamp_generator.current_time()
        self.aggregation_runtime.on_event(batch, now)
