#!/usr/bin/env python3
"""Chip smoke: the served path, once, on a TPU, at deployment size.

The quickest proof that the system still starts on the chip.  One
process, no children, data made from ``--seed``.  It drives the entry
points a user calls — ``SiddhiManager.create_siddhi_app_runtime``,
``InputHandler.send_batch``, ``add_callback`` — and checks every answer
against the host engine (the plain reference) or, for the Pallas
kernels, against the XLA path on the same chip.  Any phase that fails
raises: the exit code is then non-zero and no result line is printed.

Phases:

- *pattern*: the north-star deployment of BASELINE.json — the 16-state
  ``every e1 -> ... -> e16 within 10 min`` chain of
  ``partitioned_app()``, partitioned by key, 1,000,000 key
  partitions, batches of 131,072 events.  Every key is interned,
  duplicate keys inside a batch run collision rounds, a seeded set of
  keys completes the chain so the count gate opens and matches are
  fetched.  A second pass over the same traffic is the warm reading and
  must compile nothing.
- *windows*: the upstream window workloads as ported in
  ``samples/performance/workloads.py`` (``sliding_window``,
  ``groupby_length_batch_agg_only``) on prices that bf16 cannot hold.
- *kernels*: each ``@app:kernels`` kind live through Mosaic at the
  ``PK_*`` / ``HK_*`` sizes below, bit-identical to the XLA path.
- *multichip*: the pattern phase again with ``devices='4'`` when the
  host has four chips.

Without a TPU the script refuses to run.  ``--rehearsal`` is the one
exception: tiny sizes on whatever backend JAX has, to debug the script
itself; its result line says ``rehearsal`` and is not a chip result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

N_BATCHES = 9          # 18 events per active key, two per batch
PASS_GAP_MS = 1_000_000  # > `within 10 min`: pass 2 starts from scratch
FRAC_BITS = 20         # key id rides the fraction of v: exact in float32

FULL = {
    "partitions": 1_000_000, "batch": 131_072, "sample": 2_048,
    "window_batch": 8_192, "window_batches": 4,
}
REHEARSAL = {
    "partitions": 4_096, "batch": 1_024, "sample": 64,
    "window_batch": 512, "window_batches": 2,
    "nfa_partitions": 2_048, "nfa_batch": 1_024,
    "bank_events": 1_024, "scan_keys": 256, "scan_batch": 1_024,
}

N_STATES = 16          # chain length of both pattern apps
# kernel phase sizes: the nfa chain, the bank scatter, the hot-key scan
PK_PARTITIONS = 65_536
PK_BATCH = 1 << 15
PK_BANK_EVENTS = 1 << 15
HK_KEYS = 4_096
HK_BATCH = 8_192

# float32 contract of ops/device_query.py: float sums accumulate in
# float32, the host engine in float64 — a few tens of roundings apart
F32_RTOL = 64 * float(np.finfo(np.float32).eps)


def say(msg: str) -> None:
    print(msg, flush=True)


def partitioned_app() -> str:
    """The north-star app: the 16-state escalation pattern ``every
    e1=[v>θ1] -> e2=[v>θ2 and v>e1.v] -> ... within 10 min``, one
    automaton per key."""
    states = ["every e1=Txn[v > 0.0]"]
    for i in range(2, N_STATES + 1):
        states.append(f"e{i}=Txn[v > {float(i - 1)} and v > e1.v]")
    pattern = " -> ".join(states)
    return ("define stream Txn (key long, v double); "
            "partition with (key of Txn) begin "
            f"@info(name='bench') from {pattern} within 10 min "
            "select e1.v as v1, e16.v as v16 insert into Alerts; end;")


def kernel_eligible_app() -> str:
    """Capture-free escalation chain: fixed thresholds, final-node
    select only — the class the packed-plane NFA kernel covers (any
    e1.v capture would need the register file and fall back)."""
    states = ["every e1=Txn[v > 1.0]"]
    for i in range(2, N_STATES + 1):
        states.append(f"e{i}=Txn[v > {float(i)}]")
    pattern = " -> ".join(states)
    return ("define stream Txn (key long, v double); "
            f"@info(name='bench') from {pattern} within 10 min "
            f"select e{N_STATES}.v as v insert into Alerts;")


class CompileMeter:
    """Counts what JAX compiles (or fetches from the persistent cache)
    and how long obtaining the executables took."""

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

    def snapshot(self):
        return self.programs, self.seconds, self.cache_hits


# -- pattern traffic ---------------------------------------------------


def pattern_traffic(seed: int, n_keys: int, batch: int):
    """Nine batches of exactly ``batch`` events over ``n_keys`` keys.

    ``batch // 32`` *active* keys get two events in every batch (so
    every batch runs a second collision round) with values that rise by
    one per event, ``v = j + frac(key)``: at most one pending instance
    per chain node, so no instance lane overflows and the dense engine
    is exact.  Three classes: rising for 16 events then idle; rising
    throughout; rising with one missed beat.  The other keys are swept
    once each (the tail of the sweep revisits the first), one event per
    batch, with uniform values.  Every value is a float32, so the
    float32 device lanes and the float64 host engine see the same
    numbers.  ``frac(key) = (id + 1) / 2**20`` survives that rounding
    beside an integer part below 16, so the ``e1.v`` payload of a match
    (one of a key's first events) names its key.

    Returns ``(key_of [n_keys] int64, batches [(ids, v)], active ids)``.
    """
    rng = np.random.default_rng(seed)
    n_active = batch // 32
    n_bulk = batch - 2 * n_active
    assert n_keys < (1 << FRAC_BITS) and n_bulk <= n_keys - n_active
    assert N_BATCHES * n_bulk >= n_keys - n_active, "sweep must cover keys"
    key_of = rng.permutation(n_keys).astype(np.int64) * 1_000_003 + 17
    ids = rng.permutation(n_keys)
    active, bulk = ids[:n_active], ids[n_active:]
    klass = rng.integers(0, 3, n_active)
    frac = (active + 1) / float(1 << FRAC_BITS)
    sweep = np.resize(bulk, N_BATCHES * n_bulk)

    def active_v(j):
        v = j + frac
        idle = (klass == 0) & (j >= 16)
        missed = (klass == 2) & (j == 7)
        return np.where(idle | missed, 0.25, v).astype(np.float32)

    batches = []
    for b in range(N_BATCHES):
        slots = rng.permutation(batch)
        s1, s2 = slots[n_bulk:n_bulk + n_active], slots[n_bulk + n_active:]
        first, second = np.minimum(s1, s2), np.maximum(s1, s2)
        ev_ids = np.empty(batch, dtype=np.int64)
        ev_v = np.empty(batch, dtype=np.float64)
        ev_ids[slots[:n_bulk]] = sweep[b * n_bulk:(b + 1) * n_bulk]
        ev_v[slots[:n_bulk]] = rng.uniform(0.0, 20.0, n_bulk).astype(
            np.float32)
        ev_ids[first] = ev_ids[second] = active
        ev_v[first] = active_v(2 * b)
        ev_v[second] = active_v(2 * b + 1)
        batches.append((ev_ids, ev_v))
    return key_of, batches, active


def payload_ids(rows) -> np.ndarray:
    """Key id of each match row, read back from its first payload."""
    v = np.asarray([r[0] for r in rows], dtype=np.float64)
    return np.rint((v - np.floor(v)) * (1 << FRAC_BITS)).astype(np.int64) - 1


def send_pattern(handler, key_of, batches, pass_no, keep=None):
    """Send one pass; ``keep`` (bool over key ids) filters the events.
    Yields after each batch."""
    from siddhi_tpu.core.event import EventBatch

    for b, (ids, v) in enumerate(batches):
        if keep is not None:
            m = keep[ids]
            ids, v = ids[m], v[m]
        ts = np.full(len(ids), 1_000 + pass_no * PASS_GAP_MS + 10 * b,
                     dtype=np.int64)
        handler.send_batch(EventBatch(
            "Txn", ["key", "v"], {"key": key_of[ids], "v": v}, ts))
        yield b


def run_pattern(env, app_body: str, n_keys: int, batch: int,
                devices: int = 0, kernels: str = ""):
    """One dense pattern deployment through the served path, two passes.
    Returns a dict of what came out and what was measured."""
    import jax

    from siddhi_tpu import SiddhiManager

    meter = env["meter"]
    key_of, batches, _active = env["traffic"](n_keys, batch)
    header = (f"@app:playback @app:execution('tpu', partitions='{n_keys}'"
              + (f", devices='{devices}'" if devices else "") + ") "
              + (f"@app:kernels('{kernels}') " if kernels else ""))
    m = SiddhiManager()
    try:
        t_build = time.perf_counter()
        c0 = meter.snapshot()
        rt = m.create_siddhi_app_runtime(header + app_body)
        errors, rows = [], []
        rt.add_exception_listener(errors.append)
        rt.add_callback(
            "Alerts", lambda evs: rows.extend(list(e.data) for e in evs))
        rt.start()
        pr = rt.partitions["partition_0"]
        assert pr.is_dense, "partition did not lower to the dense engine"
        runtime = next(iter(pr.dense_query_runtimes.values())
                       ).pattern_processor
        sm = rt.app_context.statistics_manager
        handler = rt.get_input_handler("Txn")

        per_batch = [[], []]
        compiles = []
        for pass_no in (0, 1):
            t0 = time.perf_counter()
            for b in send_pattern(handler, key_of, batches, pass_no):
                t1 = time.perf_counter()
                per_batch[pass_no].append(t1 - t0)
                if pass_no == 0 and b == 0:
                    first_batch_s = t1 - t_build
                t0 = t1
            runtime.drain()
            compiles.append(meter.snapshot())
        state_devices = {d for arr in runtime.state.values()
                         for d in arr.devices()}
        out = {
            # read while the state is alive; empty where not reported
            "memory": jax.devices()[0].memory_stats() or {},
            "rows": rows,
            "lowering": rt.lowering().get("bench"),
            "first_batch_s": first_batch_s,
            "cold_batch_s": per_batch[0],
            "warm_batch_s": per_batch[1],
            "programs_cold": compiles[0][0] - c0[0],
            "compile_s_cold": compiles[0][1] - c0[1],
            "cache_hits_cold": compiles[0][2] - c0[2],
            "programs_warm": compiles[1][0] - compiles[0][0],
            "state": {k: np.asarray(v) for k, v in runtime.state.items()}
            if n_keys <= 65_536 else None,
            "state_devices": state_devices,
            "sharded": runtime._sharded is not None,
            "kernel_fallbacks": dict(sm.kernel_fallbacks),
        }
        # nothing on this path may have fallen back, dropped or erred
        assert not sm.device_fallbacks, sm.device_fallback_reasons
        assert not sm.sharded_fallbacks, sm.sharded_fallback_reasons
        assert all(d.platform == env["platform"] for d in state_devices), (
            state_devices)
        assert runtime.step_invocations > 0
        assert runtime.emit_stats.emit_transfers > 0, "no match fetch ran"
        assert runtime.emit_stats.dropped_batches == 0
        assert runtime.ingest_stats.dropped_batches == 0
        assert runtime.overflow_total() == 0, "instance lanes overflowed"
        assert rows, "count gate never opened"
        rt.shutdown()
        assert not errors, errors
        return out
    finally:
        m.shutdown()


def host_pattern_rows(env, app_body: str, n_keys: int, batch: int, keep):
    """The same app on the host engine (ops/nfa.py), fed the same
    events filtered to the sampled keys — partitions are independent,
    so the filter is exact."""
    from siddhi_tpu import SiddhiManager

    key_of, batches, _active = env["traffic"](n_keys, batch)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime("@app:playback " + app_body)
        rows = []
        rt.add_callback(
            "Alerts", lambda evs: rows.extend(list(e.data) for e in evs))
        rt.start()
        assert rt.lowering() and set(rt.lowering().values()) == {"host"}
        handler = rt.get_input_handler("Txn")
        for pass_no in (0, 1):
            for _b in send_pattern(handler, key_of, batches, pass_no, keep):
                pass
        rt.shutdown()
        return rows
    finally:
        m.shutdown()


def median(xs):
    return float(np.median(np.asarray(xs)))


def phase_pattern(env, sizes, devices: int = 0):
    n_keys, batch = sizes["partitions"], sizes["batch"]
    name = f"multichip[{devices}]" if devices else "pattern"
    say(f"[{name}] {n_keys:,} partitions x {batch:,}-event batches, "
        f"{N_BATCHES} batches x 2 passes, {N_STATES} states")
    out = run_pattern(env, partitioned_app(), n_keys, batch,
                      devices=devices)
    assert out["lowering"] == "dense", out["lowering"]
    rows, stats = out["rows"], out["memory"]
    say(f"[{name}] build+first batch {out['first_batch_s']:.2f} s "
        f"({out['programs_cold']} programs, {out['compile_s_cold']:.2f} s "
        f"obtaining them, {out['cache_hits_cold']} from the compile cache)")
    say(f"[{name}] seconds per batch: pass 1 median "
        f"{median(out['cold_batch_s'][1:]):.4f}, pass 2 (warm) median "
        f"{median(out['warm_batch_s']):.4f} (a smoke reading, not a metric)")
    say(f"[{name}] programs compiled in pass 2: {out['programs_warm']}")
    say(f"[{name}] matches {len(rows)}, state on "
        f"{len(out['state_devices'])} {env['platform']} device(s), HBM of "
        f"device 0 with the state live: bytes_in_use "
        f"{stats.get('bytes_in_use', 'not reported')} peak "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    assert out["programs_warm"] == 0, "warm pass compiled"
    if devices:
        assert out["sharded"], "partition axis did not shard"
        assert len(out["state_devices"]) == devices, out["state_devices"]
        assert sorted(rows) == sorted(env["one_chip_rows"]), (
            "sharded matches differ from the one-chip run")
        say(f"[{name}] matches equal the one-chip run")
        return
    env["one_chip_rows"] = rows

    # the plain reference on a seeded sample of keys, payloads included
    rng = np.random.default_rng(env["seed"] + 1)
    row_ids = payload_ids(rows)
    matching = np.unique(row_ids)
    _k, _b, all_active = env["traffic"](n_keys, batch)
    assert np.isin(matching, all_active).all(), "match of a swept key"
    n_act = min(sizes["sample"] // 2, len(all_active))
    sample = np.concatenate([
        rng.choice(all_active, n_act, replace=False),
        rng.choice(np.setdiff1d(np.arange(n_keys), all_active),
                   sizes["sample"] - n_act, replace=False)])
    keep = np.zeros(n_keys, dtype=bool)
    keep[sample] = True
    t0 = time.perf_counter()
    host = host_pattern_rows(env, partitioned_app(), n_keys, batch,
                             keep)
    dev = [r for r, i in zip(rows, row_ids) if keep[i]]
    assert sorted(dev) == sorted(host), (
        f"device {len(dev)} rows vs host {len(host)} on the sample")
    say(f"[{name}] {len(sample)} sampled keys "
        f"({len(np.intersect1d(sample, matching))} matching): {len(dev)} "
        f"device rows == host engine rows, payloads included "
        f"({time.perf_counter() - t0:.1f} s)")


# -- windows -----------------------------------------------------------


def phase_windows(env, sizes):
    sys.path.insert(0, os.path.join(HERE, "samples", "performance"))
    import workloads

    import jax
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.event import EventBatch
    from siddhi_tpu.core.stream import StreamCallback

    B, n_batches = sizes["window_batch"], sizes["window_batches"]
    rng = np.random.default_rng(env["seed"] + 2)
    batches = []
    for i in range(n_batches):
        ts = 1_000 + i * B + np.arange(B, dtype=np.int64)
        batches.append(EventBatch(
            "cseEventStream", ["symbol", "price", "volume", "timestamp"],
            {"symbol": np.asarray(
                [f"S{int(s)}" for s in rng.integers(0, 50, B)], dtype=object),
             "price": rng.uniform(100.0, 1000.0, B).astype(np.float32),
             "volume": rng.integers(0, 300, B).astype(np.int32),
             "timestamp": ts.copy()}, ts))
    price = np.concatenate([b.columns["price"] for b in batches])
    assert (price != price.astype(jnp.bfloat16).astype(np.float32)
            ).mean() > 0.9  # bf16 cannot hold these prices

    class Collect(StreamCallback):
        def __init__(self):
            self.batches = []

        def receive_batch(self, batch):
            self.batches.append(batch)

        def columns(self):
            names = self.batches[0].attribute_names
            return {n: np.concatenate([np.asarray(b.columns[n])
                                       for b in self.batches])
                    for n in names}

    def run(app, device):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(app)
            errors, got = [], Collect()
            rt.add_exception_listener(errors.append)
            rt.add_callback("outputStream", got)
            rt.start()
            assert rt.lowering() == {"q0": "device" if device else "host"}, (
                rt.lowering())
            handler = rt.get_input_handler("cseEventStream")
            for b in batches:
                handler.send_batch(b)
            if device:
                dr = rt.query_runtimes["q0"].device_runtime
                assert dr.step_invocations > 0
                assert dr.emit_stats.dropped_batches == 0
                assert all(d.platform == env["platform"]
                           for arr in jax.tree_util.tree_leaves(dr.state)
                           for d in arr.devices())
                sm = rt.app_context.statistics_manager
                assert not sm.device_fallbacks, sm.device_fallback_reasons
            rt.shutdown()
            assert not errors, errors
            return got.columns()
        finally:
            m.shutdown()

    tpu = "@app:playback @app:execution('tpu', partitions='65536') "
    for name, q, by_symbol in (
            ("sliding_window", workloads.SLIDING_WINDOW_Q, False),
            ("groupby_length_batch_agg_only",
             workloads.GROUPBY_LENGTH_BATCH_AGG_ONLY_Q, True)):
        host = run("@app:playback " + q, device=False)
        dev = run(tpu + q, device=True)
        if by_symbol:
            # a pane's rows come out in first-seen order on the host and
            # in group order on the device; a symbol has one row per
            # pane, so a stable sort by symbol lines both up
            host, dev = ({k: v[np.argsort(c["symbol"], kind="stable")]
                          for k, v in c.items()} for c in (host, dev))
        n = len(host["symbol"])
        assert n > 0 and len(dev["symbol"]) == n, (n, len(dev["symbol"]))
        assert np.array_equal(host["symbol"], dev["symbol"])
        if "timestamp" in host:
            assert np.array_equal(host["timestamp"], dev["timestamp"])
        worst = 0.0
        for col in ("total", "avgVolume"):
            h, d = host[col].astype(np.float64), dev[col].astype(np.float64)
            err = float(np.max(np.abs(d - h) / np.abs(h).clip(1.0)))
            worst = max(worst, err)
            assert err <= F32_RTOL, (name, col, err)
        say(f"[windows] {name}: {n_batches * B:,} events, {n:,} rows equal "
            f"the host engine (keys, timestamps exactly; float32 sums "
            f"within {worst:.2e} <= {F32_RTOL:.2e})")


# -- kernels -----------------------------------------------------------


def phase_kernels(env, sizes):
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.event import EventBatch
    from siddhi_tpu.kernels import probe

    assert probe.interpret_mode() == (env["platform"] != "tpu")
    mode = "interpreted" if probe.interpret_mode() else "Mosaic"

    # nfa: the pattern driver on the capture-free chain, kernel vs XLA
    n_keys = sizes.get("nfa_partitions", PK_PARTITIONS)
    batch = sizes.get("nfa_batch", PK_BATCH)
    define, query = kernel_eligible_app().split("; ", 1)
    app = f"{define}; partition with (key of Txn) begin {query} end;"
    kern = run_pattern(env, app, n_keys, batch, kernels="nfa")
    xla = run_pattern(env, app, n_keys, batch)
    assert kern["lowering"] == "kernel" and xla["lowering"] == "dense", (
        kern["lowering"], xla["lowering"], kern["kernel_fallbacks"])
    assert not kern["kernel_fallbacks"]
    assert kern["rows"] == xla["rows"], "nfa kernel rows differ from XLA"
    for k, v in xla["state"].items():
        assert np.array_equal(kern["state"][k], v), f"nfa kernel state {k}"
    say(f"[kernels] nfa: {mode}, batch {batch:,}: {len(kern['rows'])} rows "
        f"and the whole engine state bit-identical to XLA")

    # bank: one aggregation, LONG sums and extrema + an exact float sum
    n_ev = sizes.get("bank_events", PK_BANK_EVENTS)
    rng = np.random.default_rng(env["seed"] + 3)
    t_base = 1_600_000_000_000
    ts = np.sort(t_base + rng.integers(0, 8_000, n_ev)).astype(np.int64)
    bank_batches = [EventBatch(
        "S", ["sym", "v", "p", "ts"],
        {"sym": np.asarray([f"s{int(s)}" for s in rng.integers(0, 64, n_ev)],
                           dtype=object),
         "v": rng.integers(-(2 ** 30), 2 ** 30, n_ev),
         "p": rng.integers(0, 100, n_ev).astype(np.float64),
         "ts": ts + i * 8_000}, ts + i * 8_000) for i in range(2)]

    def run_bank(kernels):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                "@app:playback @app:execution('tpu') "
                + ("@app:kernels('bank') " if kernels else "")
                + "define stream S (sym string, v long, p double, ts long); "
                "define aggregation A from S select sym, sum(v) as total, "
                "min(v) as lo, max(v) as hi, sum(p) as psum group by sym "
                "aggregate by ts every sec...min;")
            errors = []
            rt.add_exception_listener(errors.append)
            rt.start()
            handler = rt.get_input_handler("S")
            for b in bank_batches:
                handler.send_batch(b)
            bank = rt.aggregations["A"]._bank
            assert bank is not None and bank.scatters > 0
            assert bank.use_kernel == kernels
            assert not rt.app_context.statistics_manager.kernel_fallbacks
            got = rt.query(
                f"from A within {t_base - 1_000}, {t_base + 100_000} "
                "per 'seconds' select AGG_TIMESTAMP, sym, total, lo, hi, "
                "psum;")
            rt.shutdown()
            assert not errors, errors
            return sorted(list(e.data) for e in got)
        finally:
            m.shutdown()

    kern_rows, xla_rows = run_bank(True), run_bank(False)
    assert kern_rows and kern_rows == xla_rows, "bank kernel rows differ"
    say(f"[kernels] bank: {mode}, {n_ev:,} events per scatter: "
        f"{len(kern_rows)} bucket rows bit-identical to XLA")

    # scan: the hot-key router's scan under Zipf keys
    keys = sizes.get("scan_keys", HK_KEYS)
    n_ev = sizes.get("scan_batch", HK_BATCH)
    rng = np.random.default_rng(env["seed"] + 4)
    scan_batches = []
    for i in range(4):
        u, v = (rng.uniform(0.0, 20.0, n_ev).astype(np.float32).astype(
            np.float64) for _ in range(2))
        scan_batches.append(EventBatch(
            "S", ["k", "u", "v"],
            {"k": ((rng.zipf(1.2, n_ev) - 1) % keys).astype(np.int64),
             "u": u, "v": v},
            np.full(n_ev, 1_000 + i * 10, dtype=np.int64)))

    def run_scan(kernels):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                # 16 lanes: a cold key's `a` events pile up between two
                # `b` events, and 8 lanes overflow on this traffic
                "@app:playback @app:execution('tpu', instances='16') "
                "@app:hotkeys(k='8', promote='0.1', demote='0.04') "
                + ("@app:kernels('scan') " if kernels else "")
                + "define stream S (k long, u double, v double); "
                "partition with (k of S) begin "
                "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
                "select b.v as bv insert into Alerts; end;")
            errors, rows = [], []
            rt.add_exception_listener(errors.append)
            rt.add_callback(
                "Alerts", lambda evs: rows.extend(list(e.data) for e in evs))
            rt.start()
            handler = rt.get_input_handler("S")
            for b in scan_batches:
                handler.send_batch(b)
            assert rt.lowering()["q"] == (
                "hotkey+kernel" if kernels else "hotkey"), rt.lowering()
            router = next(iter(rt.partitions["partition_0"]
                               .dense_query_runtimes.values())
                          ).pattern_processor
            hot = router.hot_metrics()
            assert hot["hotkeyRoutedEvents"] > 0, hot
            assert not rt.app_context.statistics_manager.kernel_fallbacks
            rt.shutdown()
            assert not errors, errors
            return rows, hot["hotkeyRoutedEvents"]
        finally:
            m.shutdown()

    (kern_rows, routed), (xla_rows, _r) = run_scan(True), run_scan(False)
    assert kern_rows and kern_rows == xla_rows, "scan kernel rows differ"
    say(f"[kernels] scan: {mode}, batch {n_ev:,}: {routed:,} events through "
        f"the scan, {len(kern_rows)} rows bit-identical to XLA")


# -- entry -------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on any backend; not a chip result")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    from siddhi_tpu.util.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax
    import jaxlib

    platform = jax.default_backend()
    if platform != "tpu" and not args.rehearsal:
        print(f"chip_smoke: JAX found no TPU (default backend "
              f"'{platform}'); nothing was run", file=sys.stderr)
        return 2
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    from importlib.metadata import version

    libtpu = version("libtpu")
    say(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"devices: {device['count']}")
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}  numpy {np.__version__}  "
        f"python {sys.version.split()[0]}")
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}  "
        f"seed: {args.seed}" + ("  REHEARSAL" if args.rehearsal else ""))

    sizes = REHEARSAL if args.rehearsal else FULL
    traffic_cache = {}

    def traffic(n_keys, batch):
        key = (n_keys, batch)
        if key not in traffic_cache:
            traffic_cache[key] = pattern_traffic(args.seed, n_keys, batch)
        return traffic_cache[key]

    env = {"meter": CompileMeter(jax), "platform": platform,
           "seed": args.seed, "traffic": traffic}
    t0 = time.perf_counter()
    phase_pattern(env, sizes)
    phase_windows(env, sizes)
    phase_kernels(env, sizes)
    if device["count"] >= 4:
        phase_pattern(env, sizes, devices=4)
    else:
        say("multichip: not run (fewer than 4 devices)")
    programs, seconds, hits = env["meter"].snapshot()
    say(f"total {time.perf_counter() - t0:.1f} s; {programs} programs "
        f"obtained in {seconds:.1f} s, {hits} from the compile cache")
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result = {"rehearsal": True, **result}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
