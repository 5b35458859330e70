"""What the tier-1 tests of ``bruteforce_1m`` share: the benchmark's
files of the deployment and its app over batches (``bench_app.py``)."""

import functools

import numpy as np

import bench_app
from siddhi_tpu.core.event import EventBatch

COLUMNS = ["user", "ok", "ip"]
CONFIG, TRAFFIC, REF, GEN = bench_app.files(
    "bruteforce_1m", "login_pass_saturated", "pattern_kleene", "login_pass")
run_app = functools.partial(bench_app.run_app, CONFIG)


def login_batch(users, ok, ip, ts):
    return EventBatch(
        CONFIG["stream"], COLUMNS,
        {"user": np.asarray(users, dtype=np.int64),
         "ok": np.asarray(ok, dtype=np.int32),
         "ip": np.asarray(ip, dtype=np.int32)},
        np.asarray(ts, dtype=np.int64))
