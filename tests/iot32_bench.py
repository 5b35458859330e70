"""What the tier-1 tests of ``iot32_1250k`` share: the benchmark's
files of the deployment and its app over batches (``bench_app.py``)."""

import functools

import numpy as np

import bench_app
from siddhi_tpu.core.event import EventBatch

CONFIG, TRAFFIC, REF, GEN = bench_app.files(
    "iot32_1250k", "iot_pass_saturated", "pattern_chain_band", "iot_pass")
run_app = functools.partial(bench_app.run_app, CONFIG)
SPEC = CONFIG["reference"]
# the generator at 1,024 events a batch of which 328 devices twice: the
# cell's own rehearsal is a quarter of it (its traffic file says why)
TIER1 = {**TRAFFIC, "rehearsal": {"batch": 1024, "hot": 328}}


def head_of(device, arm=0):
    """``iot_pass``'s head reading of a device: inside the head's band,
    exact in float32; ``arm`` 1 gives a second one, a quarter of a step
    above it, that names the device too."""
    return np.float32((4 * (device + 1) + arm) / float(4 << GEN.FRAC_BITS))


def make_batch(devices, temps, ts):
    return EventBatch(
        CONFIG["stream"], list(GEN.COLUMNS),
        {"device": np.asarray(devices, dtype=np.int64),
         "temp": np.asarray(temps, dtype=np.float32)},
        np.broadcast_to(np.asarray(ts, dtype=np.int64),
                        (len(devices),)).copy())
