"""Config plane tests (reference: util/config/ + config test cases)."""

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.util.config import (
    ConfigReader,
    InMemoryConfigManager,
    YAMLConfigManager,
)


@pytest.fixture
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


YAML_DOC = """
properties:
  deployment.mode: test
extensions:
  - extension:
      namespace: source
      name: inMemory
      properties:
        default.prefix: pfx
refs:
  - ref:
      name: bus1
      type: inMemory
      properties:
        topic: cfg-topic
"""


class TestConfigManagers:
    def test_in_memory_reader(self):
        cm = InMemoryConfigManager(
            {"source.http.port": "8280", "global.prop": "x"},
            {"ref1": {"type": "inMemory", "topic": "t"}},
        )
        r = cm.generate_config_reader("source", "http")
        assert r.read_config("port") == "8280"
        assert r.read_config("missing", "dflt") == "dflt"
        assert cm.extract_system_configs("ref1")["topic"] == "t"
        assert cm.extract_property("global.prop") == "x"

    def test_yaml_manager(self):
        cm = YAMLConfigManager(YAML_DOC)
        assert cm.extract_property("deployment.mode") == "test"
        r = cm.generate_config_reader("source", "inMemory")
        assert r.read_config("default.prefix") == "pfx"
        refs = cm.extract_system_configs("bus1")
        assert refs == {"type": "inMemory", "topic": "cfg-topic"}
        assert cm.generate_config_reader("sink", "nope").get_all_configs() == {}

    def test_source_by_ref(self, manager):
        import time

        from siddhi_tpu.transport.broker import InMemoryBroker

        manager.set_config_manager(YAMLConfigManager(YAML_DOC))
        rt = manager.create_siddhi_app_runtime(
            "@source(ref='bus1', @map(type='passThrough')) "
            "define stream S (v long); "
            "from S[v > 1] select v insert into Out;"
        )
        got = []
        rt.add_callback("Out", lambda evs: got.extend(evs))
        rt.start()
        InMemoryBroker.publish("cfg-topic", [5])
        time.sleep(0.1)
        rt.shutdown()
        assert [e.data[0] for e in got] == [5]

    def test_undefined_ref_raises(self, manager):
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError

        with pytest.raises(SiddhiAppCreationError):
            manager.create_siddhi_app_runtime(
                "@source(ref='nope', @map(type='passThrough')) "
                "define stream S (v long); from S select v insert into O;"
            )

    def test_store_config_reader_passed(self, manager):
        from siddhi_tpu.table import InMemoryRecordStore

        seen = {}

        class CfgStore(InMemoryRecordStore):
            def init(self, definition, options, config_reader=None):
                super().init(definition, options, config_reader)
                seen["reader"] = config_reader

        manager.set_extension("cfgstore", CfgStore, kind="store")
        manager.set_config_manager(InMemoryConfigManager(
            {"store.cfgstore.flush.interval": "9"}
        ))
        rt = manager.create_siddhi_app_runtime(
            "@store(type='cfgstore') define table T (v long); "
            "define stream S (v long); from S select v insert into T;"
        )
        rt.start()
        rt.shutdown()
        assert seen["reader"].read_config("flush.interval") == "9"


class TestCompileCachePlacement:
    """JAX_COMPILATION_CACHE_DIR places the persistent compile cache
    from outside; without it the directory is one fixed path beside the
    package — never a temporary name."""

    @pytest.fixture
    def jax_config(self):
        import jax

        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes",
                 "jax_compilation_cache_include_metadata_in_key")
        saved = {n: getattr(jax.config, n) for n in names}
        yield jax.config
        for n, v in saved.items():
            jax.config.update(n, v)

    def test_environment_names_the_directory(self, jax_config, monkeypatch):
        from siddhi_tpu.util import compile_cache

        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/some/dir")
        jax_config.update("jax_compilation_cache_dir", None)
        SiddhiManager().shutdown()  # the one door every entry uses
        assert jax_config.jax_compilation_cache_dir is None  # none in code
        assert jax_config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_fixed_directory_without_the_environment(self, jax_config,
                                                     monkeypatch):
        import os

        import siddhi_tpu
        from siddhi_tpu.util import compile_cache

        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
        compile_cache.configure_compile_cache()
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(siddhi_tpu.__file__))), ".jax_cache")
        assert jax_config.jax_compilation_cache_dir == want
        assert jax_config.jax_persistent_cache_min_entry_size_bytes == -1

    def test_a_named_scope_is_part_of_the_cache_key(self, jax_config):
        """Device metrics are read by ``jax.named_scope`` names: a
        program that differs from a cached one only in them must not be
        handed the cached one (the chip run of PR 35: the parent's
        traced run reported the change's ``siddhi.dense.kleene``)."""
        import jax
        import numpy as np
        from jax._src import cache_key

        from siddhi_tpu.util import compile_cache

        from jax._src import compiler

        def key_of(scope):
            def f(x):
                with jax.named_scope(scope):
                    return x + 1
            return cache_key.get(
                jax.jit(f).lower(np.float32(0)).compiler_ir(),
                np.array(jax.devices()[:1]),
                compiler.get_compile_options(num_replicas=1,
                                             num_partitions=1),
                jax.devices()[0].client)

        def keys():     # one call site: the caller's line is metadata too
            return [key_of(s) for s in ("siddhi.a", "siddhi.b", "siddhi.a")]

        jax_config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        try:
            a, b, again = keys()
        except Exception as e:      # a private API that moved
            pytest.skip(f"cache_key.get: {e!r}")
        assert a == b == again      # the default this guards against
        compile_cache.configure_compile_cache()
        assert jax_config.jax_compilation_cache_include_metadata_in_key
        a, b, again = keys()
        assert a != b and a == again
