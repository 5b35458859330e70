"""What the tier-1 tests of a benchmark deployment share: the
benchmark's files of the deployment, loaded by path (``benchmark/`` is
no package of the program), and one way to run its app over batches."""

import importlib.util
import json
import os
import sys

from siddhi_tpu import SiddhiManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _module(*path):
    spec = importlib.util.spec_from_file_location(
        "_bench_" + os.path.splitext(path[-1])[0], os.path.join(BENCH, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def files(config: str, traffic: str, reference: str, generator: str):
    """A deployment's configuration and traffic mix, and the modules of
    its plain reference and its generator."""
    sys.path.insert(0, os.path.join(BENCH, "generators"))
    try:    # a generator may import fraud_pass, its neighbour
        gen = _module("generators", generator + ".py")
    finally:
        sys.path.pop(0)
    return (_json("configs", config + ".json"),
            _json("traffic", traffic + ".json"),
            _module("references", reference + ".py"), gen)


def run_app(config: dict, header: str, batches, inspect=None):
    """The configuration's app under ``header`` over ``batches``:
    ``(ts, *payload)`` of every alert in delivery order, what reached
    the exception listener, the lowering, whatever ``inspect(rt)`` reads
    off the drained runtime before its shutdown, and ``statistics()``
    after it."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + " " + config["app"])
        got, errors = [], []
        rt.add_callback(config["output"], lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.add_exception_listener(errors.append)
        rt.start()
        streams = config["stream"]      # one name, or a list of them
        send = {s: rt.get_input_handler(s).send_batch for s in (
            [streams] if isinstance(streams, str) else streams)}
        for b in batches:
            send[b.stream_id](b)
        rt.drain_device_emits()
        lowering = rt.lowering()
        seen = inspect(rt) if inspect else None
        rt.shutdown()     # the dense runtime's final overflow poll
        stats = rt.statistics()
    finally:
        m.shutdown()
    return got, errors, lowering, seen, stats
