"""What the tier-1 tests of ``bruteforce_1m`` share: the benchmark's
files of the deployment, loaded by path (``benchmark/`` is no package of
the program), and one way to run its app over batches."""

import importlib.util
import json
import os
import sys

import numpy as np

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.event import EventBatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
COLUMNS = ["user", "ok", "ip"]


def _load(*path):
    spec = importlib.util.spec_from_file_location(
        "_bruteforce_" + os.path.splitext(path[-1])[0],
        os.path.join(BENCH, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


with open(os.path.join(BENCH, "configs", "bruteforce_1m.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "login_pass_saturated.json")) as _f:
    TRAFFIC = json.load(_f)
REF = _load("references", "pattern_kleene.py")
sys.path.insert(0, os.path.join(BENCH, "generators"))
try:    # login_pass imports fraud_pass, its neighbour
    GEN = _load("generators", "login_pass.py")
finally:
    sys.path.pop(0)


def login_batch(users, ok, ip, ts):
    return EventBatch(
        CONFIG["stream"], COLUMNS,
        {"user": np.asarray(users, dtype=np.int64),
         "ok": np.asarray(ok, dtype=np.int32),
         "ip": np.asarray(ip, dtype=np.int32)},
        np.asarray(ts, dtype=np.int64))


def run_app(header, batches, inspect=None):
    """The configuration's app under ``header`` over ``batches``:
    ``(ts, firstIp, lastIp, okIp)`` of every alert in delivery order,
    what reached the exception listener, the lowering, and whatever
    ``inspect(rt)`` reads off the drained runtime before its shutdown."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + " " + CONFIG["app"])
        got, errors = [], []
        rt.add_callback(CONFIG["output"], lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.add_exception_listener(errors.append)
        rt.start()
        h = rt.get_input_handler(CONFIG["stream"])
        for b in batches:
            h.send_batch(b)
        rt.drain_device_emits()
        lowering = rt.lowering()
        seen = inspect(rt) if inspect else None
        rt.shutdown()     # the dense runtime's final overflow poll
        stats = rt.statistics()
    finally:
        m.shutdown()
    return got, errors, lowering, seen, stats
