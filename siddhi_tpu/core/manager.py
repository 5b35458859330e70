"""SiddhiManager: the top-level entry point.

Mirrors the reference ``io.siddhi.core.SiddhiManager`` (SiddhiManager.java:49):
holds the per-manager context (extensions, persistence stores) and
creates/tracks app runtimes.
"""

from __future__ import annotations

from typing import Dict, Optional, Union

from siddhi_tpu.compiler import SiddhiCompiler
from siddhi_tpu.core.app_runtime import SiddhiAppRuntime
from siddhi_tpu.core.context import SiddhiContext
from siddhi_tpu.query_api import SiddhiApp
from siddhi_tpu.util.compile_cache import configure_compile_cache


class SiddhiManager:
    def __init__(self):
        configure_compile_cache()
        self.siddhi_context = SiddhiContext()
        self._app_runtimes: Dict[str, SiddhiAppRuntime] = {}

    def create_siddhi_app_runtime(self, app: Union[str, SiddhiApp],
                                  register: bool = True) -> SiddhiAppRuntime:
        from siddhi_tpu.planner.app_planner import AppPlanner

        if isinstance(app, str):
            app_string = SiddhiCompiler.update_variables(app)
            siddhi_app = SiddhiCompiler.parse(app_string)
        else:
            app_string = ""
            siddhi_app = app
        runtime = AppPlanner(siddhi_app, app_string, self.siddhi_context).build()
        runtime._manager = self
        if register:
            self._app_runtimes[runtime.name] = runtime
        return runtime

    # Java-style alias
    createSiddhiAppRuntime = create_siddhi_app_runtime

    def validate_siddhi_app(self, app: Union[str, SiddhiApp]):
        """Plan the app end-to-end, then discard it — raises
        SiddhiAppCreationError/SiddhiParserError on any problem
        (reference: SiddhiManager.validateSiddhiApp:144-165)."""
        # unregistered: validating 'X' must not disturb a running 'X'
        runtime = self.create_siddhi_app_runtime(app, register=False)
        runtime.shutdown()

    def create_sandbox_siddhi_app_runtime(self, app: Union[str, SiddhiApp]) -> SiddhiAppRuntime:
        """Create a runtime with external transports stripped: non-inMemory
        @source/@sink and every @store annotation are removed so the app
        runs fully in-process (reference:
        SiddhiManager.createSandboxSiddhiAppRuntime:104-132)."""
        if isinstance(app, str):
            app = SiddhiCompiler.parse(SiddhiCompiler.update_variables(app))
        else:
            import copy

            app = copy.deepcopy(app)  # never strip the caller's object

        def keep(ann) -> bool:
            nm = ann.name.lower()
            if nm not in ("source", "sink"):
                return True
            return (ann.element("type") or "").lower() == "inmemory"

        for sd in app.stream_definitions.values():
            sd.annotations[:] = [a for a in sd.annotations if keep(a)]
        for td in app.table_definitions.values():
            td.annotations[:] = [a for a in td.annotations if a.name.lower() != "store"]
        return self.create_siddhi_app_runtime(app)

    # Java-style aliases
    validateSiddhiApp = validate_siddhi_app
    createSandboxSiddhiAppRuntime = create_sandbox_siddhi_app_runtime

    def get_attributes(self) -> Dict[str, object]:
        return self.siddhi_context.attributes

    def set_attribute(self, key: str, value):
        """Shared objects visible to extensions
        (reference: SiddhiManager.setAttribute:76)."""
        self.siddhi_context.attributes[key] = value

    def remove_extension(self, name: str, kind: str = "function"):
        ns, _, nm = name.rpartition(":")
        self.siddhi_context.extensions.unregister(kind, nm, ns or None)

    def get_siddhi_app_runtime(self, name: str) -> Optional[SiddhiAppRuntime]:
        return self._app_runtimes.get(name)

    def get_siddhi_app_runtimes(self):
        return dict(self._app_runtimes)

    def health(self) -> Dict[str, dict]:
        """Overload-protection health of every registered app (the
        manager-wide roll-up of ``SiddhiAppRuntime.health`` — what
        ``GET /siddhi-health/<app>`` serves per app)."""
        return {name: rt.health()
                for name, rt in sorted(self._app_runtimes.items())}

    def set_extension(self, name: str, factory, kind: str = "function"):
        """Register a custom extension: name may be 'ns:name' or 'name'
        (reference: SiddhiManager.setExtension)."""
        ns, _, nm = name.rpartition(":")
        self.siddhi_context.extensions.register(kind, nm, factory, ns or None)

    def set_persistence_store(self, store):
        self.siddhi_context.persistence_store = store

    def set_source_handler_manager(self, m):
        """HA interception for sources (reference:
        SiddhiManager.setSourceHandlerManager:185)."""
        self.siddhi_context.source_handler_manager = m

    def set_sink_handler_manager(self, m):
        """reference: SiddhiManager.setSinkHandlerManager:176"""
        self.siddhi_context.sink_handler_manager = m

    def set_record_table_handler_manager(self, m):
        """reference: SiddhiManager.setRecordTableHandlerManager:194"""
        self.siddhi_context.record_table_handler_manager = m

    def set_data_source(self, name: str, data_source):
        """Named shared data sources for store extensions
        (reference: SiddhiManager.setDataSource:245)."""
        self.siddhi_context.data_sources[name] = data_source

    setSourceHandlerManager = set_source_handler_manager
    setSinkHandlerManager = set_sink_handler_manager
    setRecordTableHandlerManager = set_record_table_handler_manager
    setDataSource = set_data_source

    def set_config_manager(self, config_manager):
        """Deployment config source for extensions and refs
        (reference: SiddhiManager.setConfigManager:203)."""
        self.siddhi_context.config_manager = config_manager

    setConfigManager = set_config_manager

    def persist(self):
        for rt in list(self._app_runtimes.values()):
            rt.persist()

    def restore_last_state(self):
        """Restore every app to its newest saved revision
        (reference: SiddhiManager.restoreLastState:292)."""
        for rt in list(self._app_runtimes.values()):
            rt.restore_last_revision()

    # Java-style aliases
    setPersistenceStore = set_persistence_store
    restoreLastState = restore_last_state

    def shutdown(self):
        for rt in list(self._app_runtimes.values()):
            rt.shutdown()
        self._app_runtimes.clear()
