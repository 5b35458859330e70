"""Test configuration: the CPU platform with an 8-device virtual mesh.

Tests run on the CPU backend by contract: sharding correctness is
validated on 8 virtual CPU devices and the Pallas kernel runs
interpreted.  Both settings must be in place before any backend
starts; anything less than 8 CPU devices is a loud failure (not a
silent skip) — see _assert_virtual_mesh.
"""

import os

# The environment is what subprocesses re-exec with; the config
# updates cover a jax imported before this file (backends start lazily).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# SiddhiManager() points the persistent compile cache at a fixed
# directory of the checkout; the suite must not fill it
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_virtual_mesh():
    """Fail (don't skip) if the 8-device virtual CPU mesh never
    materialized — otherwise every sharding test silently skips and the
    scale-out module merges unexercised."""
    n = len(jax.devices())
    platform = jax.devices()[0].platform
    assert platform == "cpu" and n >= 8, (
        f"virtual CPU mesh failed to materialize: {n} {platform} devices"
    )


@pytest.fixture
def force_pipelined(monkeypatch):
    """``force_pipelined(idle=True)``: the ingest stage's rule
    (core/ingest_stage.py ``PipelineRule``) says 'one batch in flight'
    at every arrival, whatever it observes; a barrier or an idle finish
    still returns the stage to inline until the next arrival.  With
    ``idle`` False the idle finisher never finds a gate overdue, so
    what is staged stays staged until a submit or a barrier takes it."""
    from siddhi_tpu.core import ingest_stage

    def force(idle=True):
        def arrival(self, think_s):
            self.pipelined = True

        monkeypatch.setattr(ingest_stage.PipelineRule, "arrival", arrival)
        if not idle:
            monkeypatch.setattr(ingest_stage, "IDLE_CYCLES", 1e9)
            monkeypatch.setattr(ingest_stage, "IDLE_MAX_S", 3600.0)

    return force


@pytest.fixture
def no_early_copies(monkeypatch):
    """The device pipeline starts no emit array for the host at dispatch
    (core/device_pipeline.py ``_Position.start``): every drain fetches
    on demand, as it did before the early copies.  What the pipeline
    observes still decides on every app; this is the tests' twin to
    compare with, not a knob."""
    from siddhi_tpu.core.device_pipeline import _Position

    monkeypatch.setattr(_Position, "start", lambda self: False)


@pytest.fixture(params=["early", "on_demand"])
def copies(request):
    """Both ways a drain's arrays reach the host: their copies started
    at dispatch, or fetched on demand (``no_early_copies``)."""
    if request.param == "on_demand":
        request.getfixturevalue("no_early_copies")
    return request.param
